"""The federated round's stage names.

Each stage opens a ``jax.named_scope`` where its work is done, so every
round driver and every compressor carries the same names.  The scope
reaches each compiled instruction's ``op_name`` metadata (e.g.
``jit(round_fn)/fl.round/while/body/.../fl.wire_decode/jit(_take)/gather``)
and, through it, the profiler's device trace.  Scopes nest: an op's
stage is the innermost ``fl.*`` component of its ``op_name``, looking
through the ``jvp(...)``/``transpose(...)`` wrappers autodiff adds.
Ops left only under ``fl.round`` are the round's own bookkeeping.
"""
from __future__ import annotations

import functools

import jax

ROUND = "fl.round"                # round_fn's body
LOCAL_TRAIN = "fl.local_train"    # the client's local epochs
COMPRESS = "fl.compress"          # mask apply, EF residual, diagnostics
SELECT = "fl.select"              # top-k mask / threshold selection
WIRE_ENCODE = "fl.wire_encode"    # carriers -> bitmap words + streams
WIRE_DECODE = "fl.wire_decode"    # bitmap words + streams -> carriers
FOLD = "fl.fold"                  # weighted accumulate over clients
SERVER_STEP = "fl.server_step"    # FedAvg mean + the server update

ALL = (ROUND, LOCAL_TRAIN, COMPRESS, SELECT, WIRE_ENCODE, WIRE_DECODE,
       FOLD, SERVER_STEP)


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``,
    a fresh scope per call (safe under nesting)."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
