"""Device seconds per round in the compression layer (trace attribution)."""


def read(ctx):
    s = ctx["layer_s"].get("compress")
    return None if not s else s / ctx["rounds"]
