"""Device milliseconds per execution of the search program (batch
cells), from the XLA Modules events of the trace."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    n, seconds = trace_reduce.module_stats(ctx.trace, "jit_search")
    return 1e3 * seconds / n if n else None
