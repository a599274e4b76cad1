"""Device seconds per round in the local training layer (trace attribution)."""


def read(ctx):
    s = ctx["layer_s"].get("local_train")
    return None if not s else s / ctx["rounds"]
