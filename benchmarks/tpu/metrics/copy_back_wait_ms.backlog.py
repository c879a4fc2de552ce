"""Host time per round that the copy-back waited for its round on the
device, from the program's ``fleet.copy_wait`` spans; the window's final
``drain``, which waits out the rounds still in flight, is left out."""


def read(ctx):
    if not ctx.has_spans("fleet.copy_wait"):
        return None
    return ctx.per_round_ms(ctx.span_s("fleet.copy_wait", outside="drain"))
