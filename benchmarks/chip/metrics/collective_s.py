"""Device seconds per round in collective operations."""


def read(ctx):
    s = ctx["collective_s"]
    return None if not s else s / ctx["rounds"]
