"""Device seconds per round in the round's ``fl.select`` stage: the
threshold masks (the program's own stage scope, ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.stage_metrics(ctx["scope_s"], ctx["rounds"],
                                ctx["counts"])["select_s"]
