"""Device seconds per round in the wire layer (trace attribution)."""


def read(ctx):
    s = ctx["layer_s"].get("wire")
    return None if not s else s / ctx["rounds"]
