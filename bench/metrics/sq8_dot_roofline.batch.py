"""Percent of its roofline that the fused sq8_dot scoring kernel reached:
the least time for the live candidates of the traced window
(:func:`bench.cost.least_seconds`) over the kernel's device time."""
from bench import cost, trace_reduce

KERNEL = "sq8_dot"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = trace_reduce.op_seconds(ctx.trace, trace_reduce.kernel_match(
        KERNEL))
    return cost.roofline_share(KERNEL, ctx.run["live_candidates"], seconds,
                               ctx.cfg, ctx.peaks)
