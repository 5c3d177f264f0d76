"""Percent of the static candidate slots that were live: the live
candidates of every answered query (``SearchResult.n_candidates``) over
the answered queries times :func:`bench.cost.candidate_budget`.  The
fused scorer gathers and scores these slots and skips the rest."""
from bench import cost


def read(ctx):
    stats = ctx.run["stats"]
    answered = stats["attempted"] - stats["failed"]
    if answered <= 0:
        return None
    cfg = ctx.cfg
    slots = cost.candidate_budget(cfg["kc"], cfg["k2"],
                                  cfg["cluster_capacity"],
                                  cfg["term_capacity"])
    return 100.0 * ctx.run["live_candidates"] / answered / slots
