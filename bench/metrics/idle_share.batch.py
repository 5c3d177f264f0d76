"""Percent of the traced window in which no operation ran on the device
(batch cells): 1 - busy union / window."""


def read(ctx):
    if ctx.trace is None or ctx.trace["idle_share"] is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
