"""Device milliseconds per search step of the refine stage (batch
cells): the operations under ``hi2.refine``, the exact re-rank of the
top R' (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "refine")
