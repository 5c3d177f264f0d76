"""Device milliseconds per search step of the search program's
operations under no ``hi2.`` scope (batch cells): copies and layout
changes XLA added (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, scopes.UNSCOPED)
