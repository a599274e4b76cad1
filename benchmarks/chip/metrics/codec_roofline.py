"""The uplink work's least HBM time over the device time of compression,
the wire and the mesh transport's compaction (%).  The least time reads
each client's dW, dM and dV once at their dtypes and writes and reads
its payload once, at the chip's HBM peak (counters.codec_least_bytes)."""


def read(ctx):
    s = sum(ctx["layer_s"].get(k, 0.0)
            for k in ("compress", "wire", "transport"))
    if not s:
        return None
    least = ctx["codec_least_bytes_round"] * ctx["rounds"] \
        / (ctx["chips"] * ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / s
