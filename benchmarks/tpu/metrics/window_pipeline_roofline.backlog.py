"""Share of its roofline that the fused fixed-point window kernel reaches,
in %: the least time the chip could take for the kernel's operations and HBM
bytes (``costs/window_pipeline.py``, from each call's window grid) over the
kernel's device time. The kernel is the step's Pallas call, found by its
custom-call target; its window grid is the leading dims of its first result
(slots, windows, CL_ROWS, LANE)."""
from tpubench.trace import op_shape

NEEDLE = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    calls = [(text, s, e) for text, s, e in ctx.ops_matching(NEEDLE)]
    if not calls:
        return None
    cost = ctx.kernel_cost("window_pipeline")
    flops = hbm = seconds = 0.0
    for text, s, e in calls:
        shape = op_shape(text)
        if shape is None or len(shape) < 3:
            return None
        f, b = cost(shape[:-2], ctx.cfg)
        flops += f
        hbm += b
        seconds += e - s
    peaks = ctx.peaks()
    bound = max(flops / peaks["bf16_flops_per_s"], hbm / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds if seconds > 0 else None
