"""Device milliseconds per search step of the scoring stage (batch
cells): the operations under ``hi2.score``, the fused scoring kernel
among them (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "score")
