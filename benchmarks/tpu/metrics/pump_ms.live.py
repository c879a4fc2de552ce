"""Host time of the fleet driver per round: ``pump`` (windowing, ragged-wire
packing, staging, dispatch), from the benchmark's ``pump`` spans."""


def read(ctx):
    return ctx.per_round_ms(ctx.span_s("pump"))
