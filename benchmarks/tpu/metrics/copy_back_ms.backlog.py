"""Host time per round of the copy-back's leaf transfers, from the
program's ``fleet.copy_back`` spans."""


def read(ctx):
    if not ctx.has_spans("fleet.copy_back"):
        return None
    return ctx.per_round_ms(ctx.span_s("fleet.copy_back"))
