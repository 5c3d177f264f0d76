"""Percent of the search program's device time spent in HLO sort
operations (dedup and top-R sort the candidate plane)."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    _, step = trace_reduce.module_stats(ctx.trace, "jit_search")
    sort = trace_reduce.op_seconds(
        ctx.trace, lambda name, text: trace_reduce.category(name) == "sort")
    return 100.0 * sort / step if step and sort else None
