"""Requests per executed runtime batch over the window: the runtime's
``n_served / n_batches`` counters."""


def read(ctx):
    stats = ctx.run["stats"]
    if not stats.get("batches"):
        return None
    return stats["served"] / stats["batches"]
