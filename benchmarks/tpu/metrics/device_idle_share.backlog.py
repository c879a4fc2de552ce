"""Share of the traced window in which the device ran no operation, in %."""


def read(ctx):
    return ctx.idle_share()
