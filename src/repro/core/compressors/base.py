"""The ``Compressor`` protocol and registry — Section IV as an API.

Every uplink scheme in the paper (and every baseline it compares against)
is a *compression operator* applied to the client's local update triple
``(dW, dM, dV)`` before it crosses the network.  Efficient-Adam and 1-bit
Adam are only correct when the operator is *stateful*: the part of the
update the compressor dropped this round (the error-feedback residual)
must be added back into the next round's input.  This module makes that
shape first-class:

* ``Deltas``   — the raw local update triple (pytrees of dW, dM, dV).
* ``Packed``   — a compressed triple plus encoder-side diagnostics.  The
  carrier stays *dense* (masked / quantized values in place); the wire
  realization (COO pack + all-gather) is a transport concern handled by
  :func:`repro.core.aggregate.packed_gather_sum` keyed on the
  compressor's ``transport`` tag.
* ``Compressor`` — ``init_state(params) -> state``,
  ``compress(deltas, state) -> (packed, state, bits)``,
  ``decompress(packed) -> deltas``.  ``state`` is per-client and is
  carried through the ``scan``/``vmap`` client axes by
  :mod:`repro.core.fed`; ``bits`` is the exact per-client uplink cost of
  the payload (the Section IV/VII formulas of :mod:`repro.core.comm`),
  so the reported metric can never drift from the transport used.
  Because ``state`` is the SOLE carrier of cross-round client memory,
  the buffered-async driver (:mod:`repro.core.async_fed`) can give it
  commit-on-accept semantics: a client whose update is lost or
  discarded mid-flight keeps its residual bitwise intact and simply
  retries from it — state is never rezeroed by churn (docs/async.md).

Declarative dispatch tags (read by ``core/fed.py`` so that adding a
compressor never requires editing the round):

* ``transport``     — ``dense`` | ``shared_sparse`` |
  ``independent_sparse`` | ``quantized``; selects the aggregation
  transport in ``core/aggregate.py``.
* ``local_update``  — ``adam`` | ``sgd`` | ``momentum`` | ``local_adam``;
  which client-side optimizer produces the deltas this compressor eats.
* ``server_update`` — ``wmv`` (advance W, M and V by the aggregate) |
  ``w_only`` | ``precond_m`` (1-bit Adam's frozen-V preconditioned step).

Registering a new scheme is a single-file drop-in::

    from repro.core.compressors import Compressor, Packed, register

    @register("fedlion_sign")
    def _factory(fed):
        return SignCompressor(q_bits=fed.q_bits)

See ``docs/compressors.md`` for the full contract and the per-algorithm
bit formulas.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sparsify as S

_F32 = jnp.float32

#: Canonical diagnostic keys every compressor reports (fed.py's scan/vmap
#: drivers stack these per client; shard_map needs the key set static).
DIAG_KEYS = ("err_w", "err_m", "err_v", "norm_dw", "norm_dm", "norm_dv")


class Deltas(NamedTuple):
    """The client's raw local update: pytrees of dW, dM, dV (Algorithm 2
    step 3).  Slots an algorithm does not communicate hold zeros-like
    trees (e.g. FedSGD only fills ``W``)."""
    W: Any
    M: Any
    V: Any


class Packed(NamedTuple):
    """A compressed update triple.

    ``W``/``M``/``V`` are the dense carriers of the compressed values
    (masked or quantized in place).  ``diag`` holds encoder-side
    diagnostics (:data:`DIAG_KEYS`) — computed where the error-feedback
    adjusted input exists, and explicitly NOT part of the transported
    payload (it never enters the bit accounting).  ``wire`` is the
    bit-packed :class:`repro.core.wire.WirePayload` realization of the
    carriers — the arrays that actually cross the uplink (``None`` only
    for configurations outside the wire format's layout constants, which
    fall back to dense transport + analytic accounting).  ``counts``
    holds a mask payload's ``wire.COUNT_KEYS`` counters (``None`` for
    schemes without a mask payload); like ``diag``, never transported."""
    W: Any
    M: Any
    V: Any
    diag: Dict[str, jax.Array]
    wire: Any = None
    counts: Optional[Dict[str, jax.Array]] = None


def tree_sub(a, b):
    """Elementwise a - b in f32, cast back to the leaf dtype."""
    return jax.tree.map(lambda x, y: (x.astype(_F32) - y.astype(_F32))
                        .astype(x.dtype), a, b)


def tree_add(a, b):
    return jax.tree.map(lambda x, y: (x.astype(_F32) + y.astype(_F32))
                        .astype(x.dtype), a, b)


def tree_zeros_like(t):
    return jax.tree.map(jnp.zeros_like, t)


def tree_size(t) -> int:
    return sum(x.size for x in jax.tree.leaves(t))


def zero_diag() -> Dict[str, jax.Array]:
    z = jnp.zeros((), _F32)
    return {k: z for k in DIAG_KEYS}


def diag_metrics(deltas: Deltas, recon: Deltas) -> Dict[str, jax.Array]:
    """Default diagnostics: per-tensor compression error ||d - C(d)||_2
    (the Theorem-1 divergence terms) and input norms.  ``deltas`` should
    be the error-feedback adjusted encoder input when EF is active."""
    nd = lambda d, r: S.tree_norm(tree_sub(d, r))
    return {
        "err_w": nd(deltas.W, recon.W),
        "err_m": nd(deltas.M, recon.M),
        "err_v": nd(deltas.V, recon.V),
        "norm_dw": S.tree_norm(deltas.W),
        "norm_dm": S.tree_norm(deltas.M),
        "norm_dv": S.tree_norm(deltas.V),
    }


class Compressor:
    """Base class / protocol.  Subclasses override :meth:`compress` and
    :meth:`bits_per_client`, plus any of the dispatch tags below."""

    name: str = "base"
    transport: str = "dense"
    local_update: str = "adam"
    server_update: str = "wmv"
    #: Wire encoding family (core/wire.py): ``mask_shared`` |
    #: ``mask_independent`` | ``sign`` | ``bbit`` | ``dense`` | None
    #: (no wire realization — dense transport, analytic bits only).
    wire_layout: Optional[str] = None

    # -- state ----------------------------------------------------------
    def init_state(self, params) -> Optional[Any]:
        """Per-client compressor state (error-feedback residuals etc.)
        for ONE client; ``fed_init`` stacks it over the client axis.
        ``None`` means the compressor is stateless."""
        return None

    # -- the operator ---------------------------------------------------
    def compress(self, deltas: Deltas, state) -> Tuple[Packed, Any, Any]:
        """``(packed, new_state, bits)``.  ``bits`` is the exact uplink
        bit count of this client's payload (static given tree shapes —
        matches ``n_clients * bits`` against core/comm.py formulas).
        Implementations MUST compute it as
        ``self.bits_per_client(tree_size(deltas.W))`` — the round's
        ``uplink_bits`` metric reads :meth:`bits_per_client` directly
        (once per round, outside the client scan/vmap), and routing both
        through one method is what makes drift impossible
        (``tests/test_compressors.py`` asserts their equality)."""
        raise NotImplementedError

    def decompress(self, packed: Packed) -> Deltas:
        """Server-side reconstruction to the dense triple.  The default
        inverts dense-carrier compressors (values already in place)."""
        return Deltas(packed.W, packed.M, packed.V)

    # -- wire realization ----------------------------------------------
    def pack_wire(self, carriers: Deltas) -> Optional[Any]:
        """Encode a dense carrier triple (the ``Packed.W/M/V`` planes, or
        equivalently the decoded outputs of :meth:`unpack_wire` — the
        encoding is idempotent) into the transported
        :class:`~repro.core.wire.WirePayload`.  Returns ``None`` when the
        configuration has no wire realization.  The buffered-async driver
        uses this to re-materialize the landed bytes per accepted update
        (:mod:`repro.core.async_fed`)."""
        return None

    def unpack_wire(self, wire, like) -> Deltas:
        """Decode a :class:`~repro.core.wire.WirePayload` produced by
        :meth:`compress` back to the dense carrier triple.  ``like`` is
        any tree with the model's structure/shapes/dtypes (the params
        template).  Only meaningful when :attr:`wire_layout` is set."""
        raise NotImplementedError(
            f"{self.name} has no wire realization")

    # -- accounting -----------------------------------------------------
    def bits_per_client(self, d: int) -> int:
        """Uplink bits ONE client pays per round for a d-dimensional
        model (Section IV / VII).  The round multiplies by the number of
        participating clients; must equal ``comm.bits_for(name, d, k, 1)``."""
        raise NotImplementedError

    def wire_bits_per_client(self, sizes) -> Optional[int]:
        """Measured wire bits ONE client pays per round, equal to
        ``8 * payload_nbytes`` of the payload :meth:`compress` builds
        for a tree with leaf ``sizes`` — or ``None`` when this
        configuration has no wire realization (the round metric then
        falls back to the analytic :meth:`bits_per_client`)."""
        return None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Compressor]] = {}


def register(name: str):
    """Decorator: register ``factory(fed_config) -> Compressor`` under an
    algorithm name.  ``fed_config`` is duck-typed (anything exposing the
    FedConfig fields the factory reads)."""
    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


def unregister(name: str) -> None:
    """Remove a registration (tests / plugin teardown)."""
    _REGISTRY.pop(name, None)


def available() -> Tuple[str, ...]:
    """Registered algorithm names, in registration order."""
    return tuple(_REGISTRY)


def make_compressor(fed) -> Compressor:
    """Build the compressor for ``fed.algorithm`` from its config."""
    try:
        factory = _REGISTRY[fed.algorithm]
    except KeyError:
        raise KeyError(
            f"no compressor registered for {fed.algorithm!r}; "
            f"known: {sorted(_REGISTRY)}") from None
    return factory(fed)


def transport_of(algorithm: str) -> str:
    """Transport tag of an algorithm's compressor (used by launchers to
    pick the aggregation path without building a round)."""
    from repro.core.fed import FedConfig
    return make_compressor(FedConfig(algorithm=algorithm)).transport
