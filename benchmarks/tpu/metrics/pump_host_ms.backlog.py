"""Host work of the fleet driver per round inside ``pump``: taking the
session queues, windowing, packing, staging and dispatch, from the
program's ``service.take`` and ``fleet.{window,pack,stage,dispatch}``
spans. ``pump_ms`` less this is what ``pump`` waited."""

SPANS = ("service.take", "fleet.window", "fleet.pack", "fleet.stage", "fleet.dispatch")


def read(ctx):
    if not ctx.has_spans(*SPANS):
        return None
    return ctx.per_round_ms(ctx.span_s(*SPANS))
