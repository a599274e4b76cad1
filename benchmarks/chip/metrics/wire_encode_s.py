"""Device seconds per round in the round's ``fl.wire_encode`` stage: every
``wire.pack_*`` (the program's own stage scope, ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.stage_metrics(ctx["scope_s"], ctx["rounds"],
                                ctx["counts"])["wire_encode_s"]
