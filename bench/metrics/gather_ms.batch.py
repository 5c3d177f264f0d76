"""Device milliseconds per search step of the gather stage (batch
cells): the operations under ``hi2.gather``, the dispatched lists'
rows fetched into the candidate plane (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "gather")
