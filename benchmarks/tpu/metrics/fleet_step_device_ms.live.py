"""Device time of the fleet step per round: the step program's spans on the
device in the profiler trace."""


def read(ctx):
    if not ctx.module_s("step"):
        return None
    return ctx.per_round_ms(ctx.module_s("step"))
