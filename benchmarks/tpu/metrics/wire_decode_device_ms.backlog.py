"""Device time of the ragged-wire decoder per round: the decode program's
spans on the device in the profiler trace."""


def read(ctx):
    if not ctx.module_s("decode"):
        return None
    return ctx.per_round_ms(ctx.module_s("decode"))
