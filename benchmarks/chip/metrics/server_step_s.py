"""Device seconds per round in the round's ``fl.server_step`` stage:
``server_apply`` (the program's own stage scope, ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.stage_metrics(ctx["scope_s"], ctx["rounds"],
                                ctx["counts"])["server_step_s"]
