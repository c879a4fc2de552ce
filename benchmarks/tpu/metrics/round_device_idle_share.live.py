"""Share of the rounds' time in which the device ran no operation, in %: over
the union of round intervals, each from the start of its ``pump`` to the end
of the ``result`` span that brought it onto the host. The wait for the next
beat lies outside every round and does not count."""


def read(ctx):
    return ctx.idle_share(ctx.round_intervals())
