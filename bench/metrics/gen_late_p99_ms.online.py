"""99th percentile of how late the load generator sent a request after
it was due, in milliseconds."""
from bench import loops


def read(ctx):
    late = ctx.run["stats"].get("late_s")
    if late is None or not len(late):
        return None
    return 1e3 * loops.percentile(late, 99)
