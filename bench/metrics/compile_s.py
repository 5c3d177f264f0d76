"""Seconds of XLA compilation in set-up (JAX monitoring's
``backend_compile_duration``, which a persistent-cache load also
fires)."""


def read(ctx):
    return ctx.run["compile_s"]
