"""Peak device memory of the run in GB (``peak_bytes_in_use`` after the
window): build and serving together."""


def read(ctx):
    peak = ctx.run["hbm_peak_bytes"]
    return peak / 1e9 if peak else None
