"""Seconds of ``hybrid_index.build`` on the host clock, ending when the
index is on the device."""


def read(ctx):
    return ctx.run["build_s"]
