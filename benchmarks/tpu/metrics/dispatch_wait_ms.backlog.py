"""Host time per round that dispatch waited on the device: for the oldest
round in flight to retire (``service.retire``) and for a staging set's
borrower (``fleet.staging_wait``), from the program's spans."""

SPANS = ("service.retire", "fleet.staging_wait")


def read(ctx):
    if not ctx.has_spans(*SPANS):
        return None
    return ctx.per_round_ms(ctx.span_s(*SPANS))
