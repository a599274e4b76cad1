"""Model FLOPs of local training over the traced window, as a share of
the chips' bf16 peak (%)."""


def read(ctx):
    if not ctx["rounds"] or ctx["window_s"] <= 0:
        return None
    rate = ctx["model_flops_round"] * ctx["rounds"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
