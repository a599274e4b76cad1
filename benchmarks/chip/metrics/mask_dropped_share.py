"""Share of the entries that selection keeps which the per-leaf cap
drops, over the last round's clients: 100 (selected - shipped) /
selected (%; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.stage_metrics(ctx["scope_s"], ctx["rounds"],
                                ctx["counts"])["mask_dropped_share"]
