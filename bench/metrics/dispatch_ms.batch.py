"""Device milliseconds per search step of the dispatch stage (batch
cells): the operations under the program's ``hi2.dispatch`` scope,
cluster top-K^C and term selection (:mod:`bench.scopes`)."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "dispatch")
