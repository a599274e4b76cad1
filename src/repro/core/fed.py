"""FedAdam-SSM and baselines — Algorithms 1 & 2 of the paper.

One FL round (Algorithm 2):

1. every client starts local state from the global (W^t, M^t, V^t);
2. L local Adam epochs (Eqs. 3-5; no bias correction) on the client's data;
3. client deltas  dW = w - W^t, dM = m - M^t, dV = v - V^t;
4. compression:   the round's ``Compressor`` (core/compressors registry,
   selected by ``FedConfig.algorithm``) encodes the delta triple — the
   paper's SHARED sparse mask (Eq. 28: mask = Top_k(|dW|)) for
   FedAdam-SSM, or the per-algorithm alternative — carrying any
   per-client error-feedback state across rounds;
5. server FedAvg over the compressed deltas; globals advance by the
   aggregate per the compressor's ``server_update`` rule.

The paper's Algorithm 2 downloads the *previous* round's aggregate at the
start of the next round; applying the aggregate at the end of the current
round is the same sequence of states (the lag is only a pipelining detail),
which is how we implement it.

The round function is architecture-agnostic: it sees an abstract
``loss_fn(params, batch) -> scalar`` and parameter pytrees, so every
architecture in the zoo trains with the technique unchanged.  It is also
algorithm-agnostic: all per-scheme behaviour (what is communicated, the
error-feedback semantics, the uplink bit accounting, which aggregation
transport applies) lives behind the compressor's declarative tags —
adding a scheme is a compressor registration, not a surgery here.  See
docs/compressors.md.

Client execution modes
----------------------
* ``scan``  — virtual clients: sequential ``lax.scan`` over the client axis
  (memory = one client); the mesh parallelizes *within* a client.
* ``vmap``  — spatial clients: the leading client axis of the batch is
  sharded over mesh axes ("data"/"pod"); per-client local training runs
  under ``vmap`` so divergent client replicas coexist, and the aggregation
  reduce IS the uplink collective (see core/aggregate.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import aggregate, compressors, stages, wire
from repro.core.compressors import DIAG_KEYS, Deltas
from repro.core.compressors.base import tree_add as _tree_add
from repro.core.compressors.base import tree_sub as _tree_sub
from repro.optim.adam import AdamHyper, AdamState, adam_step, sgd_step

_F32 = jnp.float32

#: Built-in algorithm names, in canonical order (== the compressor
#: registry's registration order; see core/compressors/__init__.py):
#:
#: fedadam_ssm    — the paper's contribution (shared mask rule ssm_w)
#: ssm_m, ssm_v   — baselines: shared mask from |dM| / |dV|
#: fairness_top   — baseline: shared mask from the normalized union
#: fedadam_top    — baseline: three independent top-k masks
#: fedadam        — baseline: dense FedAdam (alpha=1 special case)
#: fedsgd         — baseline: dense FedSGD
#: onebit_adam    — baseline: 1-bit Adam (warmup + frozen precondition)
#: efficient_adam — baseline: two-way quantized Adam with EF
ALGORITHMS = compressors.available()

#: Per-client metrics every client step reports (stacked over clients
#: by the drivers; shard_map regions need the key set static).
CLIENT_METRIC_KEYS = DIAG_KEYS + wire.COUNT_KEYS + ("loss",)


@dataclasses.dataclass(frozen=True)
class FedConfig:
    algorithm: str = "fedadam_ssm"
    alpha: float = 0.05                   # sparsification ratio k/d
    local_epochs: int = 30
    n_clients: int = 20
    adam: AdamHyper = AdamHyper()
    mask_scope: str = "per_tensor"        # per_tensor | global
    exact_topk: bool = True               # exact sort vs threshold bisection
    # auto | kernel | reference — which sparsifier implementation the
    # threshold masks use (core/sparsify.resolve_backend: auto routes TPU
    # to the Pallas kernels; REPRO_SPARSIFY_BACKEND env overrides)
    sparsify_backend: str = "auto"
    error_feedback: bool = False          # beyond-paper for sparse algos
    quant_bits: int = 8                   # efficient_adam
    onebit_warmup_rounds: int = 2
    q_bits: int = 32                      # accounting float precision
    client_mode: str = "scan"             # scan | vmap
    aggregate: str = "dense"              # dense | sparse_gather (vmap only)
    client_axes: Optional[Tuple[str, ...]] = None  # mesh axes of client dim
    use_kernel_adam: bool = False         # fused_adam Pallas kernel
    per_epoch_batches: bool = False       # batch has a leading L axis
    value_dtype: Optional[str] = None     # beyond-paper value transport cast
    # beyond-paper: partial participation — fraction of clients sampled per
    # round (the paper uses full participation, N=20).  Sampled by masking
    # FedAvg weights so compiled shapes stay static.
    participation: float = 1.0

    def __post_init__(self):
        # any *registered* compressor is a valid algorithm — drop-in
        # schemes registered via compressors.register() pass too
        assert self.algorithm in compressors.available(), self.algorithm


def active_client_count(fed: FedConfig) -> int:
    """Clients sampled per round: ``round(participation * n_clients)``,
    never below one.  THE single site where the participation fraction
    meets host ``int()`` math — it runs at round-*build* time and its
    value is closed over by the jitted round body, so the cast can never
    see a tracer (the jit-hazard lint rule guards the round body).

    Invariant (relied on by every participation consumer):

    * host-static ``int`` in ``[1, n_clients]`` — banker's rounding via
      Python ``round`` (``participation=0.5, n_clients=5`` -> 2), and
      ``participation=0.0`` still yields 1 (a round with zero clients
      is never built);
    * the SAME count drives both participation realizations: the sync
      round samples exactly this many clients by *weight masking* (the
      ``round_fn`` permutation below — compiled shapes stay static, an
      inactive client contributes weight 0.0 and its bits are not
      accounted), and the buffered-async driver
      (:mod:`repro.core.async_fed`) restricts its *dispatch pool* to
      this many clients, so sync and async agree on how many clients a
      given ``participation`` admits.

    Boundary behaviour is pinned by ``tests/test_fed.py::
    test_active_client_count_boundaries``.
    """
    return max(1, int(round(fed.participation * fed.n_clients)))


class FedState(NamedTuple):
    W: Any                                # global model
    M: Any                                # global first moments
    V: Any                                # global second moments
    round: jax.Array                      # int32 scalar
    client_state: Any                     # per-client state (may be None):
    #   {"comp": <compressor EF state>, "m"/"v": persistent local moments}


def fed_init(fed: FedConfig, params) -> FedState:
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    comp = compressors.make_compressor(fed)
    C = fed.n_clients
    stack0 = lambda t: jax.tree.map(
        lambda x: jnp.zeros((C,) + x.shape, x.dtype), t)
    parts = {}
    cs1 = comp.init_state(params)
    if cs1 is not None:
        # replicate the single-client compressor state over the client axis
        parts["comp"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), cs1)
    if comp.local_update == "local_adam":
        # persistent local Adam moments (efficient_adam: never aggregated)
        parts["m"] = stack0(params)
        parts["v"] = stack0(params)
    return FedState(W=params, M=zeros(), V=zeros(),
                    round=jnp.zeros((), jnp.int32),
                    client_state=parts or None)


def client_state_pspecs(client_state, param_pspecs, client_axes):
    """PartitionSpec pytree for a client-stacked ``client_state`` tree.

    Every leaf gets its leading client axis placed on ``client_axes``
    (``None`` for the scan driver's virtual-client axis, which no mesh
    axis carries).  Trailing dims follow the *param* sharding whenever a
    sub-tree mirrors the params treedef — which is exactly how fed_init
    builds the EF residuals (``{"comp": {"err": params-like}}``) and the
    ``local_adam`` moments (``"m"``/``"v"``) — so at the jit boundary a
    client's residual shard is laid out like its param shard, not
    replicated across the model axes.  Unrecognized sub-trees (custom
    compressor state) fall back to client-axis-only placement.
    """
    if client_state is None:
        return None
    cax = (tuple(client_axes) if len(client_axes) > 1 else client_axes[0]) \
        if client_axes else None
    pleaves, ptreedef = jax.tree_util.tree_flatten(
        param_pspecs, is_leaf=lambda x: isinstance(x, PartitionSpec))

    def spec_for(sub):
        try:
            ptreedef.flatten_up_to(sub)
        except (ValueError, TypeError):
            if isinstance(sub, dict):
                return {k: spec_for(v) for k, v in sub.items()}
            return jax.tree.map(
                lambda x: PartitionSpec(cax, *([None] * (x.ndim - 1))), sub)
        return ptreedef.unflatten(
            [PartitionSpec(cax, *sp) for sp in pleaves])

    return spec_for(client_state)


def client_region_axes(client_axes, mesh=None) -> frozenset:
    """Mesh axes the per-client shard_map region is MANUAL over: the
    client axes plus every axis of size 1 (``mesh`` defaults to the one
    set by ``jax.set_mesh``).  A size-1 axis partitions nothing, and the
    Pallas kernels in the client step compile only where no mesh axis is
    left to the automatic partitioner."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    ones = {a for a, n in zip(mesh.axis_names, mesh.axis_sizes) if n == 1}
    return frozenset(client_axes) | ones


# ---------------------------------------------------------------------------
# Local training
# ---------------------------------------------------------------------------


def _local_adam(loss_fn, W, M, V, batch, fed: FedConfig):
    """L local Adam epochs from the downloaded global state."""
    h = fed.adam
    state0 = AdamState(M, V, jnp.zeros((), jnp.int32))

    def epoch(carry, xs):
        w, st = carry
        b = xs if fed.per_epoch_batches else batch
        loss, g = jax.value_and_grad(loss_fn)(w, b)
        w, st = adam_step(w, g, st, h, use_kernel=fed.use_kernel_adam)
        return (w, st), loss

    if fed.per_epoch_batches:
        (w, st), losses = lax.scan(epoch, (W, state0), batch)
    else:
        (w, st), losses = lax.scan(epoch, (W, state0), None,
                                   length=fed.local_epochs)
    return w, st.m, st.v, jnp.mean(losses)


def _local_sgd(loss_fn, W, batch, fed: FedConfig):
    def epoch(w, xs):
        b = xs if fed.per_epoch_batches else batch
        loss, g = jax.value_and_grad(loss_fn)(w, b)
        w, _ = sgd_step(w, g, fed.adam.lr)
        return w, loss

    if fed.per_epoch_batches:
        w, losses = lax.scan(epoch, W, batch)
    else:
        w, losses = lax.scan(epoch, W, None, length=fed.local_epochs)
    return w, jnp.mean(losses)


def _local_momentum(loss_fn, W, M, batch, fed: FedConfig):
    """One momentum step (1-bit Adam compressed phase: V frozen)."""
    b = jax.tree.map(lambda x: x[0], batch) \
        if fed.per_epoch_batches else batch
    loss, g = jax.value_and_grad(loss_fn)(W, b)
    h = fed.adam
    m_new = jax.tree.map(
        lambda m, gg: (h.beta1 * m.astype(_F32)
                       + (1 - h.beta1) * gg.astype(_F32)).astype(m.dtype),
        M, g)
    return m_new, loss


# ---------------------------------------------------------------------------
# The round
# ---------------------------------------------------------------------------


def make_client_step(fed: FedConfig, loss_fn: Callable,
                     comp: Optional[compressors.Compressor] = None,
                     *, emit: str = "dense", wire_roundtrip: bool = True):
    """Build ONE client's round: local epochs + compression.

    ``client_step(W, M, V, batch, cstate) ->
    (sW, sM, sV, new_cstate, metrics)`` — the per-client unit of work
    every driver shares: ``make_fl_round``'s scan/vmap/shard_map bodies
    run it over the cohort, and the buffered-async driver
    (:mod:`repro.core.async_fed`) runs it per dispatch against a stale
    parameter snapshot.  Keeping this a single builder is what makes
    sync <-> async degenerate-config equivalence *bitwise* rather than
    approximate (tests/test_async_fed.py).

    The carriers the step hands back are the WIRE-decoded ones whenever
    the compressor built a bit-packed payload (core/wire.py): the server
    sees exactly what survives the transported bytes, not the encoder's
    dense scratch.  For mask schemes the two are bit-identical; for
    quantized schemes they agree to the codec's round-trip (exact here:
    codes+scales reproduce the dense carrier bitwise).  Dense transport
    skips the round-trip — it is the identity, and FedSGD's identity
    carriers ship W only.

    ``wire_roundtrip=False`` keeps the dense-carrier output (the
    round-trip being bitwise, numerics are unchanged) WITHOUT touching
    the packed cohort buffer.  The mesh driver needs this: inside its
    shard_map region the leaves are model-sharded, and the wire pack's
    ravel/concatenate would force weight all-gathers in the global view
    — the transport realization there is the per-shard bitmap path in
    ``aggregate.make_shardmap_sparse_aggregate`` instead.

    ``emit="wire"`` (the vmap sparse-gather transport) returns
    ``(payload, new_cstate, metrics)`` instead — the bit-packed
    :class:`~repro.core.wire.WirePayload` IS the client's output, so the
    driver can move only packed words across the client axis and decode
    server-side.  Only valid when the compressor has a wire realization
    for this config."""
    if comp is None:
        comp = compressors.make_compressor(fed)
    assert emit in ("dense", "wire"), emit

    def client_step(W, M, V, batch, cstate):
        comp_state = cstate.get("comp") if cstate is not None else None
        extras = {}

        if comp.local_update == "sgd":
            with jax.named_scope(stages.LOCAL_TRAIN):
                w, loss = _local_sgd(loss_fn, W, batch, fed)
            dW = _tree_sub(w, W)
            z = jax.tree.map(jnp.zeros_like, dW)
            deltas = Deltas(dW, z, z)
        elif comp.local_update == "momentum":
            with jax.named_scope(stages.LOCAL_TRAIN):
                m_new, loss = _local_momentum(loss_fn, W, M, batch, fed)
            dM = _tree_sub(m_new, M)
            z = jax.tree.map(jnp.zeros_like, dM)
            deltas = Deltas(z, dM, z)
        elif comp.local_update == "local_adam":
            # persistent local moments (never aggregated — the staleness
            # the paper criticizes)
            with jax.named_scope(stages.LOCAL_TRAIN):
                w, m, v, loss = _local_adam(loss_fn, W, cstate["m"],
                                            cstate["v"], batch, fed)
            dW = _tree_sub(w, W)
            z = jax.tree.map(jnp.zeros_like, dW)
            deltas = Deltas(dW, z, z)
            extras = {"m": m, "v": v}
        else:                             # "adam": the FedAdam family
            with jax.named_scope(stages.LOCAL_TRAIN):
                w, m, v, loss = _local_adam(loss_fn, W, M, V, batch, fed)
            deltas = Deltas(_tree_sub(w, W), _tree_sub(m, M),
                            _tree_sub(v, V))

        with jax.named_scope(stages.COMPRESS):
            packed, new_comp_state, _bits = comp.compress(deltas,
                                                          comp_state)
        if cstate is None:
            new_cstate = None
        else:
            new_cstate = dict(cstate)
            if "comp" in cstate:
                new_cstate["comp"] = new_comp_state
            new_cstate.update(extras)
        # the mask counters describe the payload this step ships; the
        # mesh step (wire_roundtrip=False) ships none of its own
        ships = emit == "wire" or wire_roundtrip
        counts = packed.counts if ships and packed.counts is not None \
            else wire.zero_counts()
        mets = dict(packed.diag, **counts, loss=loss)
        if emit == "wire":
            assert packed.wire is not None, \
                f"{comp.name}: emit='wire' but compress built no payload"
            return packed.wire, new_cstate, mets
        if wire_roundtrip and packed.wire is not None \
                and comp.transport != "dense":
            sW, sM, sV = comp.unpack_wire(packed.wire, deltas.W)
        else:
            sW, sM, sV = comp.decompress(packed)
        return sW, sM, sV, new_cstate, mets

    return client_step


def make_server_apply(fed: FedConfig,
                      comp: Optional[compressors.Compressor] = None):
    """Build the server-side tail of a round: FedAvg mean + the
    compressor's declarative ``server_update`` rule.

    ``server_apply(W, M, V, aW, aM, aV, wsum) -> (W', M', V')`` where
    ``(aW, aM, aV)`` are weighted SUMS over whatever cohort delivered
    (full cohort in the sync round, the K-deep buffer in the async
    driver) and ``wsum`` the matching weight total.  Shared verbatim by
    ``make_fl_round`` and :mod:`repro.core.async_fed`, so the two
    drivers can never disagree on the server arithmetic."""
    if comp is None:
        comp = compressors.make_compressor(fed)
    h = fed.adam

    @stages.scoped(stages.SERVER_STEP)
    def server_apply(W, M, V, aW, aM, aV, wsum):
        mean = lambda t: jax.tree.map(lambda x: x / wsum, t)
        aW, aM, aV = mean(aW), mean(aM), mean(aV)
        if comp.server_update == "precond_m":
            # 1-bit Adam: M advances by the aggregated momentum delta; W
            # by the preconditioned step with frozen V.  (Warmup rounds
            # run as a separate dense FedConfig — see the two-phase
            # protocol in tests/test_fed.py.)
            M_new = _tree_add(M, aM)
            upd = jax.tree.map(
                lambda mm, vv: (h.lr * mm.astype(_F32)
                                / jnp.sqrt(vv.astype(_F32) + h.eps)),
                M_new, V)
            W_new = jax.tree.map(
                lambda w, u: (w.astype(_F32) - u).astype(w.dtype),
                W, upd)
            V_new = V
        elif comp.server_update == "w_only":
            W_new = _tree_add(W, aW)
            M_new, V_new = M, V
        else:                             # "wmv": the FedAdam family
            W_new = _tree_add(W, aW)
            M_new = _tree_add(M, aM)
            V_new = _tree_add(V, aV)
        return W_new, M_new, V_new

    return server_apply


def make_fl_round(fed: FedConfig, loss_fn: Callable,
                  sparse_aggregate_fn: Optional[Callable] = None):
    """Build ``round_fn(state, batches, weights=None) -> (state, metrics)``.

    ``sparse_aggregate_fn(sW_c, sM_c, sV_c, weights) -> (aW, aM, aV)``:
    optional shard_map-based transport (core.aggregate.
    make_shardmap_sparse_aggregate) injected by the launcher; without it the
    pure-jnp gather/scatter path is used (CPU tests, small models).

    batches: pytree whose leaves have leading dims (C, [L,] ...) — client-
    major (and epoch-major when per_epoch_batches).  weights: optional (C,)
    FedAvg weights |D_n| (defaults to uniform).
    """
    comp = compressors.make_compressor(fed)
    n_active = active_client_count(fed)
    client_step = make_client_step(fed, loss_fn, comp)
    # the mesh driver's step skips the (bitwise-identity) wire round-trip:
    # packing model-sharded leaves in the global view would all-gather
    # the weights; its transport is the per-shard bitmap aggregate
    mesh_client_step = make_client_step(fed, loss_fn, comp,
                                        wire_roundtrip=False)
    server_apply = make_server_apply(fed, comp)

    # -- round drivers --------------------------------------------------

    def round_scan(state: FedState, batches, weights):
        W, M, V = state.W, state.M, state.V
        zero = lambda: jax.tree.map(
            lambda x: jnp.zeros(x.shape, _F32), W)
        acc0 = (zero(), zero(), zero())

        cs = state.client_state
        has_cs = cs is not None

        def body(carry, xs):
            (aW, aM, aV), wsum = carry
            if has_cs:
                batch, wgt, cstate = xs
            else:
                batch, wgt = xs
                cstate = None
            sW, sM, sV, ncs, mets = client_step(W, M, V, batch, cstate)
            add = lambda a, s: jax.tree.map(
                lambda x, y: x + wgt * y.astype(_F32), a, s)
            ys = (ncs, mets) if has_cs else (0.0, mets)
            with jax.named_scope(stages.FOLD):
                acc = (add(aW, sW), add(aM, sM), add(aV, sV))
            return (acc, wsum + wgt), ys

        xs = (batches, weights, cs) if has_cs else (batches, weights)
        ((aW, aM, aV), wsum), (new_cs, mets) = lax.scan(body, (acc0, 0.0), xs)
        return (aW, aM, aV), wsum, (new_cs if has_cs else None), mets

    def round_shardmap(state: FedState, batches, weights):
        """Spatial clients, production path: the per-client local-training
        region runs under shard_map MANUAL over the client mesh axes (auto
        over a "model" axis larger than 1: ``client_region_axes``), so
        divergent client replicas are structurally
        per-device — GSPMD cannot replicate them (the pure-vmap formulation
        showed 10-100x memory blow-ups at scale).  Per-client compressor
        state (EF residuals under ``client_state["comp"]``, plus the
        ``local_adam`` persistent moments) enters the MANUAL region sharded
        over the same client axes, is consumed/produced by ``client_step``
        exactly as under scan/vmap, and leaves the region still sharded —
        it never materializes unsharded.  Aggregation then runs in the
        global view (dense) or via the injected shard_map transport."""
        W, M, V = state.W, state.M, state.V
        cs = state.client_state
        has_cs = cs is not None
        caxes = tuple(fed.client_axes)
        cax = caxes if len(caxes) > 1 else caxes[0]

        def body(Wb, Mb, Vb, batch, wts, cstate):
            batch_l = jax.tree.map(lambda x: x[0], batch)
            # one spatial client per device row: peel the client axis off
            # the state shard, thread it through the step, put it back
            cstate_l = jax.tree.map(lambda x: x[0], cstate)
            sW, sM, sV, ncs, mets = mesh_client_step(Wb, Mb, Vb, batch_l,
                                                     cstate_l)
            lead = lambda t: jax.tree.map(lambda x: x[None], t)
            mets = jax.tree.map(lambda x: x[None], mets)
            return lead(sW), lead(sM), lead(sV), lead(ncs), mets

        rep = lambda tree: jax.tree.map(lambda _: PartitionSpec(), tree)
        stk = lambda tree: jax.tree.map(
            lambda x: PartitionSpec(cax, *([None] * (x.ndim - 1))), tree)
        mets_spec = {k: PartitionSpec(cax) for k in CLIENT_METRIC_KEYS}
        # cs=None is an empty pytree: its spec entry is None and the body's
        # tree.maps over it are no-ops, so the stateless path is unchanged
        sW, sM, sV, new_cs, mets = jax.shard_map(
            body,
            in_specs=(rep(W), rep(M), rep(V), stk(batches),
                      PartitionSpec(None), stk(cs)),
            out_specs=(stk(W), stk(W), stk(W), stk(cs), mets_spec),
            axis_names=client_region_axes(caxes),
            check_vma=False,
        )(W, M, V, batches, weights, cs)

        wsum = jnp.sum(weights.astype(_F32))
        if fed.aggregate == "sparse_gather" and sparse_aggregate_fn is not None:
            # EF compressors: hand the transport the per-shard residuals so
            # values dropped by the pack's fixed capacity feed back into
            # next round's input instead of vanishing on the wire
            comp_err = new_cs["comp"].get("err") \
                if has_cs and isinstance(new_cs.get("comp"), dict) else None
            if comp_err is not None and comp.transport in (
                    "shared_sparse", "independent_sparse"):
                (aW, aM, aV), new_err = sparse_aggregate_fn(
                    sW, sM, sV, weights, comp_err)
                new_cs = dict(new_cs, comp=dict(new_cs["comp"],
                                                err=new_err))
            else:
                aW, aM, aV = sparse_aggregate_fn(sW, sM, sV, weights)
        else:
            # ordered (scan-identical) accumulation: the dense branch of
            # the mesh driver is the reference/debug path — bit-identical
            # to round_scan by construction (tests/test_fed_equivalence)
            aW = aggregate.ordered_weighted_sum(sW, weights)
            aM = aggregate.ordered_weighted_sum(sM, weights)
            aV = aggregate.ordered_weighted_sum(sV, weights)
        return (aW, aM, aV), wsum, (new_cs if has_cs else None), mets

    def round_vmap(state: FedState, batches, weights):
        W, M, V = state.W, state.M, state.V
        cs = state.client_state

        def pin(tree):
            if not fed.client_axes:
                return tree

            def one_leaf(x):
                spec = PartitionSpec(
                    tuple(fed.client_axes) if len(fed.client_axes) > 1
                    else fed.client_axes[0],
                    *([None] * (x.ndim - 1)))
                return lax.with_sharding_constraint(x, spec)
            return jax.tree.map(one_leaf, tree)

        in_axes = (0, 0 if cs is not None else None)
        wsum = jnp.sum(weights.astype(_F32))

        sizes = tuple(x.size for x in jax.tree.leaves(W))
        use_wire = (fed.aggregate == "sparse_gather"
                    and sparse_aggregate_fn is None
                    and comp.transport != "dense"
                    and comp.wire_bits_per_client(sizes) is not None)
        if use_wire:
            # wire transport: each vmapped client emits its bit-packed
            # WirePayload; ONLY the packed words + compact value/scale
            # streams cross the client axis, and the server decodes in
            # client order — the ordered fold is bitwise round_scan's
            wire_step = make_client_step(fed, loss_fn, comp, emit="wire")

            def one_wire(batch, cstate):
                return wire_step(W, M, V, batch, cstate)

            payload, new_cs, mets = jax.vmap(
                one_wire, in_axes=in_axes)(batches, cs)
            payload = pin(payload)
            aW, aM, aV = aggregate.packed_gather_sum(
                comp, None, None, None, weights, alpha=fed.alpha,
                value_dtype=fed.value_dtype, sort_free=not fed.exact_topk,
                payload_c=payload, like=W)
            return (aW, aM, aV), wsum, \
                (new_cs if cs is not None else None), mets

        def one(batch, cstate):
            return client_step(W, M, V, batch, cstate)

        sW, sM, sV, new_cs, mets = jax.vmap(one, in_axes=in_axes)(batches, cs)
        # pin the per-client delta stacks to the client mesh axes — without
        # this GSPMD may replicate the divergent client states (C x params
        # per device) through the vmapped local-training region
        sW, sM, sV = pin(sW), pin(sM), pin(sV)
        if fed.aggregate == "sparse_gather" and sparse_aggregate_fn is not None:
            aW, aM, aV = sparse_aggregate_fn(sW, sM, sV, weights)
        elif fed.aggregate == "sparse_gather":
            # transport keyed on the compressor — any shared_sparse /
            # independent_sparse compressor rides the packed all-gather
            aW, aM, aV = aggregate.packed_gather_sum(
                comp, sW, sM, sV, weights, alpha=fed.alpha,
                value_dtype=fed.value_dtype, sort_free=not fed.exact_topk)
        else:
            aW = aggregate.dense_weighted_sum(sW, weights)
            aM = aggregate.dense_weighted_sum(sM, weights)
            aV = aggregate.dense_weighted_sum(sV, weights)
        return (aW, aM, aV), wsum, \
            (new_cs if cs is not None else None), mets

    @stages.scoped(stages.ROUND)
    def round_fn(state: FedState, batches, weights=None, rng=None):
        C = fed.n_clients
        if weights is None:
            weights = jnp.ones((C,), _F32)
        if fed.participation < 1.0:
            # sample the active_client_count clients by weight masking
            # (static shapes); rng defaults to the round counter for
            # reproducibility
            key = rng if rng is not None else \
                jax.random.fold_in(jax.random.PRNGKey(17), state.round)
            perm = jax.random.permutation(key, C)
            active = jnp.zeros((C,), _F32).at[perm[:n_active]].set(1.0)
            weights = weights * active
        if fed.client_mode == "scan":
            driver = round_scan
        elif fed.client_axes is not None:
            driver = round_shardmap
        else:
            driver = round_vmap
        (aW, aM, aV), wsum, new_cs, mets = driver(state, batches, weights)
        W_new, M_new, V_new = server_apply(state.W, state.M, state.V,
                                           aW, aM, aV, wsum)

        # uplink accounting x participating clients — the metric is
        # produced by the same object that produced the payload.  When
        # the compressor ships a wire payload, report the MEASURED bytes
        # (8 * WirePayload.nbytes, core/wire.py — padding and capacity
        # slack included); only configs with no wire realization fall
        # back to the paper-analytic Section IV/VII count.
        d = sum(x.size for x in jax.tree.leaves(state.W))
        sizes = tuple(x.size for x in jax.tree.leaves(state.W))
        per_client = comp.wire_bits_per_client(sizes)
        if per_client is None:
            per_client = comp.bits_per_client(d)
        mets = dict(mets)
        mets["uplink_bits"] = jnp.asarray(n_active * per_client, _F32)
        new_state = FedState(W=W_new, M=M_new, V=V_new,
                             round=state.round + 1, client_state=new_cs)
        return new_state, mets

    return round_fn
