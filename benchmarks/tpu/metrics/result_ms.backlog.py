"""Host time of the copy-back per round: reading every station's result of a
finished round onto the host, from the benchmark's ``result`` spans."""


def read(ctx):
    return ctx.per_round_ms(ctx.span_s("result"))
