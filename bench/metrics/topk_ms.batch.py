"""Device milliseconds per search step of the top-R' selection (batch
cells): the operations under ``hi2.topk`` (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "topk")
