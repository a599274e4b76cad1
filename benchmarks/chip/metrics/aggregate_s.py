"""Device seconds per round in aggregation and the server step (trace
attribution), the mesh transport's compaction included."""


def read(ctx):
    s = ctx["layer_s"].get("aggregate", 0.0) \
        + ctx["layer_s"].get("transport", 0.0)
    return None if not s else s / ctx["rounds"]
