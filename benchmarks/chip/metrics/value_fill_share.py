"""Share of the payload's value slots that the mask's bitmaps fill, over
the last round's clients: 100 shipped / capacity (%; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.stage_metrics(ctx["scope_s"], ctx["rounds"],
                                ctx["counts"])["value_fill_share"]
