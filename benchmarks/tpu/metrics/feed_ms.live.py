"""Host time of the session service per round: every station's ``feed``
(validation, queueing) of one beat, from the benchmark's ``feed`` spans."""


def read(ctx):
    return ctx.per_round_ms(ctx.span_s("feed"))
