"""Device milliseconds per search step of the dedup stage (batch
cells): the operations under ``hi2.dedup``, the first-occurrence mask
over the candidate plane (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "dedup")
