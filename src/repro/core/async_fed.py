"""Buffered-asynchronous FL rounds under client churn.

The paper's round (core/fed.py) is a synchronous barrier: every client
of the cohort trains, uploads, and the server steps once all N payloads
are in.  At the ROADMAP's scale — millions of intermittently-connected
devices — the barrier never closes: clients arrive, straggle, and drop
mid-round.  This module is the buffered-async driver for that traffic
pattern (FedBuff-style; the server-side adaptive step follows the
FedAdamW line of work):

* clients train against **stale parameter snapshots**: a dispatch
  captures ``(W, M, V)`` at server version ``v``; by the time the
  update lands the server may be at version ``v + s``;
* a server-side **buffer** collects ``K`` compressed updates (any
  clients, any staleness); only when the buffer holds exactly ``K``
  does the server apply one aggregate step — never fewer;
* aggregation is **staleness-weighted**: update ``i`` with staleness
  ``s_i`` contributes ``weight_i * (1 + s_i) ** -power``, normalized by
  the buffer's weight total (``staleness_scale`` below; at ``s == 0``
  the scale is exactly 1.0, which is what makes the zero-churn
  degenerate config *bitwise* equal to the sync round);
* updates older than ``max_staleness`` at arrival are **discarded**;
* per-client compressor state (error-feedback residuals, the
  ``local_adam`` persistent moments) is committed **only when the
  update is accepted** into the buffer.  A client that drops after
  compress but before delivery — or whose update is discarded as too
  stale — keeps its state bitwise untouched and retries from it: state
  survives churn, it is never rezeroed (the Efficient-Adam lesson), and
  ``uplink_bits`` counts only updates that actually landed.

Everything runs on a **virtual clock** driven by the deterministic
event model in :mod:`repro.data.churn`: no wall time anywhere, so every
simulation replays bitwise from its seed (the fault-injection harness
in tests/test_async_fed.py leans on this; debugging recipe in
docs/async.md).

The per-client compute and the server arithmetic are the SAME builders
the sync round uses (``fed.make_client_step``, ``fed.make_server_apply``,
``aggregate.ordered_weighted_sum``), composed two ways:

* ``client_exec="scan"``     — simultaneous dispatches run as one
  ``lax.scan`` cohort (the CPU/test path, and the virtual-client path);
* ``client_exec="shardmap"`` — cohorts run under the shard_map MANUAL
  region over ``fed.client_axes``, exactly like ``round_shardmap``
  (requires an ambient mesh; groups are padded to the mesh's client
  count and padded lanes are discarded on the host).
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import aggregate, compressors, wire
from repro.core.compressors import Deltas
from repro.core.fed import (
    CLIENT_METRIC_KEYS, FedConfig, FedState, active_client_count,
    client_region_axes, make_client_step, make_server_apply,
)
from repro.data.churn import ChurnConfig, ChurnModel

_F32 = jnp.float32


# ---------------------------------------------------------------------------
# Staleness weighting
# ---------------------------------------------------------------------------


def staleness_scale(staleness, power: float = 0.5):
    """Per-update multiplier ``(1 + s) ** -power`` (host math, float64).

    Monotone non-increasing in ``s``, in ``(0, 1]``, and EXACTLY 1.0 at
    ``s == 0`` — so with zero churn the effective weights equal the sync
    round's FedAvg weights bitwise."""
    s = np.asarray(staleness, np.float64)
    assert np.all(s >= 0), "staleness is a count of server steps"
    assert power >= 0.0
    return (1.0 + s) ** (-float(power))


def staleness_weights(staleness, power: float = 0.5) -> np.ndarray:
    """Normalized buffer weights ``w_i = scale(s_i) / sum_j scale(s_j)``.

    Properties (pinned by the hypothesis suite in
    tests/test_async_fed.py): nonnegative, sum to 1, and monotone
    non-increasing in staleness — a staler update never outweighs a
    fresher one.  The driver itself applies the unnormalized
    ``staleness_scale`` times the FedAvg weight and divides by the
    buffer's weight total, which is the same weighting whenever the
    FedAvg weights are uniform."""
    s = staleness_scale(staleness, power)
    return s / s.sum()


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Buffered-async server policy (the churn schedule itself lives in
    :class:`repro.data.churn.ChurnConfig`)."""
    buffer_size: int = 4              # K: updates per server step
    max_staleness: Optional[int] = None   # arrival cutoff; None = accept all
    staleness_power: float = 0.5      # (1+s)**-power aggregation weight

    def __post_init__(self):
        assert self.buffer_size >= 1
        assert self.max_staleness is None or self.max_staleness >= 0
        assert self.staleness_power >= 0.0


# ---------------------------------------------------------------------------
# Traced builders (jit/shard_map roots — guarded by the jit-hazard lint)
# ---------------------------------------------------------------------------


def make_cohort_exec(fed: FedConfig, loss_fn: Callable, has_cs: bool,
                     comp: Optional[compressors.Compressor] = None):
    """Run a group of simultaneously-dispatched clients as ONE
    ``lax.scan`` over ``fed.make_client_step`` — the same body shape as
    ``round_scan``, so per-client outputs are bitwise those of the sync
    driver.  ``exec_cohort(W, M, V, batches, cstates) -> (sW, sM, sV,
    new_cs, mets)`` with every output stacked ``(G, ...)``."""
    client_step = make_client_step(fed, loss_fn, comp)

    def exec_cohort(W, M, V, batches, cstates):
        def body(carry, xs):
            if has_cs:
                batch, cstate = xs
            else:
                batch, cstate = xs, None
            sW, sM, sV, ncs, mets = client_step(W, M, V, batch, cstate)
            return carry, (sW, sM, sV, ncs if has_cs else 0.0, mets)

        xs = (batches, cstates) if has_cs else batches
        _, (sW, sM, sV, ncs, mets) = lax.scan(body, 0.0, xs)
        return sW, sM, sV, (ncs if has_cs else None), mets

    return jax.jit(exec_cohort)


def make_mesh_cohort_exec(fed: FedConfig, loss_fn: Callable, has_cs: bool,
                          comp: Optional[compressors.Compressor] = None,
                          mesh=None):
    """shard_map realization of the cohort exec: one spatial client per
    device row over ``fed.client_axes``, exactly the MANUAL region of
    ``fed.round_shardmap``.  ``mesh`` may be omitted if an ambient mesh
    is active via ``jax.set_mesh``.  The group's leading axis G
    must equal the client-axes device count — the host pads smaller
    groups."""
    client_step = make_client_step(fed, loss_fn, comp)
    caxes = tuple(fed.client_axes)
    cax = caxes if len(caxes) > 1 else caxes[0]

    def exec_cohort(W, M, V, batches, cstates):
        def body(Wb, Mb, Vb, batch, cstate):
            batch_l = jax.tree.map(lambda x: x[0], batch)
            cstate_l = jax.tree.map(lambda x: x[0], cstate)
            sW, sM, sV, ncs, mets = client_step(Wb, Mb, Vb, batch_l,
                                                cstate_l)
            lead = lambda t: jax.tree.map(lambda x: x[None], t)
            return (lead(sW), lead(sM), lead(sV), lead(ncs),
                    jax.tree.map(lambda x: x[None], mets))

        rep = lambda tree: jax.tree.map(lambda _: PartitionSpec(), tree)
        stk = lambda tree: jax.tree.map(
            lambda x: PartitionSpec(cax, *([None] * (x.ndim - 1))), tree)
        mets_spec = {k: PartitionSpec(cax) for k in CLIENT_METRIC_KEYS}
        sW, sM, sV, ncs, mets = jax.shard_map(
            body, mesh=mesh,
            in_specs=(rep(W), rep(M), rep(V), stk(batches), stk(cstates)),
            out_specs=(stk(W), stk(W), stk(W), stk(cstates), mets_spec),
            axis_names=client_region_axes(caxes, mesh),
            check_vma=False,
        )(W, M, V, batches, cstates)
        return sW, sM, sV, (ncs if has_cs else None), mets

    return exec_cohort


def make_buffer_apply(fed: FedConfig,
                      comp: Optional[compressors.Compressor] = None):
    """One server step from a full buffer: ``apply(W, M, V, bufW, bufM,
    bufV, weights) -> (W', M', V')``.  ``buf*`` leaves are stacked
    ``(K, ...)``; ``weights`` is the (K,) effective weight vector
    (FedAvg weight x staleness scale).  Accumulation replays the scan
    driver's exact order and arithmetic (``aggregate.
    ordered_weighted_sum`` + the shared ``fed.make_server_apply``
    tail), so the K = cohort, zero-staleness case is bit-identical to
    ``round_scan``."""
    server_apply = make_server_apply(fed, comp)

    def wsum_fold(carry, w):
        return carry + w, 0.0

    def buffer_apply(W, M, V, bufW, bufM, bufV, weights):
        aW = aggregate.ordered_weighted_sum(bufW, weights)
        aM = aggregate.ordered_weighted_sum(bufM, weights)
        aV = aggregate.ordered_weighted_sum(bufV, weights)
        # left-fold, like round_scan's running wsum (not jnp.sum, whose
        # reduction order XLA may reassociate)
        wsum, _ = lax.scan(wsum_fold, jnp.zeros((), _F32), weights)
        return server_apply(W, M, V, aW, aM, aV, wsum)

    return jax.jit(buffer_apply)


def make_wire_buffer_apply(fed: FedConfig,
                           comp: Optional[compressors.Compressor] = None):
    """Wire-format twin of :func:`make_buffer_apply`: the buffer holds
    the K landed :class:`~repro.core.wire.WirePayload`\\ s (stacked
    ``(K, ...)``) — the bytes that actually crossed the uplink — and the
    server decodes them against the params template and folds in arrival
    order (``aggregate.wire_gather_sum``, which replays ``round_scan``'s
    exact arithmetic), so the degenerate-config bitwise equivalence is
    preserved payload-for-payload."""
    if comp is None:
        comp = compressors.make_compressor(fed)
    server_apply = make_server_apply(fed, comp)

    def wsum_fold(carry, w):
        return carry + w, 0.0

    def buffer_apply(W, M, V, payloads, weights):
        aW, aM, aV = aggregate.wire_gather_sum(comp, payloads, W, weights)
        wsum, _ = lax.scan(wsum_fold, jnp.zeros((), _F32), weights)
        return server_apply(W, M, V, aW, aM, aV, wsum)

    return jax.jit(buffer_apply)


def make_commit_client(has_cs: bool):
    """``commit(cs, new_c, c) -> cs`` — write ONE accepted client's new
    compressor state into slot ``c`` of the stacked ``client_state``
    (the only mutation path: drops and discards never reach it)."""

    def commit(cs, new_c, c):
        if not has_cs:
            return None
        return jax.tree.map(lambda full, new: full.at[c].set(new),
                            cs, new_c)

    return jax.jit(commit, static_argnums=())


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------

_EV_DISPATCH, _EV_ARRIVE = 0, 1


class AsyncRoundDriver:
    """Event-driven buffered-async simulation (see module docstring).

    Host-side orchestration over a virtual clock; all numerics run in
    the jitted builders above.  Build via :func:`make_async_round`."""

    def __init__(self, fed: FedConfig, loss_fn: Callable,
                 acfg: AsyncConfig, churn: Optional[ChurnModel] = None,
                 client_exec: str = "scan", mesh=None):
        assert client_exec in ("scan", "shardmap"), client_exec
        if client_exec == "shardmap":
            assert fed.client_axes, "shardmap exec needs fed.client_axes"
            assert mesh is not None, "shardmap exec needs a concrete mesh"
        self.mesh = mesh
        self.fed = fed
        self.acfg = acfg
        self.churn = churn if churn is not None \
            else ChurnModel(ChurnConfig(), fed.n_clients)
        assert self.churn.n_clients == fed.n_clients
        self.client_exec = client_exec
        self._loss_fn = loss_fn
        self._comp = compressors.make_compressor(fed)
        self._apply = make_buffer_apply(fed, self._comp)
        self._exec = None          # built on first run (has_cs known then)
        self._commit = None
        self._apply_wire = None    # wire-format server step (lazy)
        self._repack = None        # carriers -> WirePayload (lazy)

    # -- helpers --------------------------------------------------------

    def _build(self, has_cs: bool):
        if self._exec is not None:
            return
        if self.client_exec == "shardmap":
            self._exec = make_mesh_cohort_exec(
                self.fed, self._loss_fn, has_cs, self._comp, self.mesh)
        else:
            self._exec = make_cohort_exec(
                self.fed, self._loss_fn, has_cs, self._comp)
        self._commit = make_commit_client(has_cs)

    def _run_group(self, W, M, V, batches, cs, group, has_cs):
        """Execute clients ``group`` (all dispatched at the same tick)
        against the snapshot (W, M, V); returns per-client payload
        dicts indexed like ``group``."""
        idx = list(group)
        if self.client_exec == "shardmap":
            # fixed cohort width = client-axes device count; pad by
            # repeating the last client, discard the padded lanes below
            pad_to = int(np.prod(
                [self.mesh.shape[a] for a in self.fed.client_axes]))
            assert len(idx) <= pad_to, (len(idx), pad_to)
            idx = idx + [idx[-1]] * (pad_to - len(idx))
        sel = np.asarray(idx, np.int64)
        take = lambda t: jax.tree.map(lambda x: x[sel], t)
        g_batches = take(batches)
        g_cs = take(cs) if has_cs else None
        sW, sM, sV, ncs, mets = self._exec(W, M, V, g_batches, g_cs)
        out = []
        for i, _c in enumerate(group):
            pick = lambda t: jax.tree.map(lambda x: x[i], t)
            out.append(dict(
                sW=pick(sW), sM=pick(sM), sV=pick(sV),
                ncs=(pick(ncs) if has_cs else None),
                loss=mets["loss"][i],
                counts={k: mets[k][i] for k in wire.COUNT_KEYS}))
        return out

    # -- the simulation -------------------------------------------------

    def __call__(self, state: FedState, batches, weights=None, *,
                 rounds: int = 1, max_events: Optional[int] = None):
        """Run until ``rounds`` server steps have been applied (or the
        ``max_events`` budget runs out — e.g. churn so hostile the
        buffer never fills; then ``metrics["server_steps"] < rounds``
        and the returned state reflects only the steps that happened).

        ``batches``: client-major pytree, leaves ``(C, ...)`` — client
        ``c`` trains on slice ``c`` at every dispatch.  ``weights``:
        optional (C,) FedAvg weights.  Returns ``(FedState, metrics)``;
        ``metrics["events"]`` is the full replayable event log."""
        fed, acfg = self.fed, self.acfg
        C = fed.n_clients
        K = acfg.buffer_size
        if weights is None:
            weights = np.ones((C,), np.float64)
        base_w = np.asarray(weights, np.float64)
        assert base_w.shape == (C,)
        if max_events is None:
            max_events = 64 * C * max(1, rounds) + 256

        has_cs = state.client_state is not None
        self._build(has_cs)
        W, M, V = state.W, state.M, state.V
        cs = state.client_state
        server_round = int(state.round)
        round0 = server_round

        d = sum(x.size for x in jax.tree.leaves(W))
        sizes = tuple(x.size for x in jax.tree.leaves(W))
        # wire mode: buffer the bit-packed WirePayloads and bill the
        # MEASURED landed bytes; analytic fallback only for configs with
        # no wire realization (q_bits != 32 etc.)
        wire_mode = self._comp.wire_bits_per_client(sizes) is not None
        bits_client = self._comp.bits_per_client(d)
        if wire_mode and self._repack is None:
            comp = self._comp
            self._repack = jax.jit(
                lambda sW, sM, sV: comp.pack_wire(Deltas(sW, sM, sV)))
            self._apply_wire = make_wire_buffer_apply(fed, comp)

        # participation: the async realization of the seam documented on
        # fed.active_client_count — the dispatch pool is exactly the
        # n_active sampled clients; everyone else never dispatches
        if fed.participation < 1.0:
            pool = self.churn.participation_pool(active_client_count(fed))
        else:
            pool = np.arange(C)

        q: List = []
        seq = itertools.count()
        push = lambda t, kind, payload: heapq.heappush(
            q, (t, next(seq), kind, payload))
        for c in pool:
            push(0, _EV_DISPATCH, int(c))

        attempts = {int(c): 0 for c in pool}
        inflight: Dict[int, Dict[str, Any]] = {}
        buffer: List[Dict[str, Any]] = []
        events: List[tuple] = []
        landed = dropped = discarded = steps = 0
        bits_total = 0
        bits_per_step: List[int] = []
        loss_per_step: List[float] = []
        counts_per_step: List[Dict[str, int]] = []

        def redispatch(t, c):
            push(t + self.churn.cfg.rejoin_delay, _EV_DISPATCH, c)

        n_events = 0
        while q and steps < rounds and n_events < max_events:
            t, _, kind, c = heapq.heappop(q)
            n_events += 1

            if kind == _EV_DISPATCH:
                # group every dispatch sharing this tick (consecutive in
                # the queue — no ARRIVE can interleave at lower seq) into
                # one cohort against one snapshot
                group = [c]
                while q and q[0][0] == t and q[0][2] == _EV_DISPATCH:
                    group.append(heapq.heappop(q)[3])
                    n_events += 1
                payloads = self._run_group(W, M, V, batches, cs, group,
                                           has_cs)
                for gc, pay in zip(group, payloads):
                    a = attempts[gc]
                    attempts[gc] += 1
                    fate = self.churn.fate(gc, a)
                    pay["ver"] = server_round
                    pay["drop"] = fate.drop
                    inflight[gc] = pay
                    events.append((t, "dispatch", gc, a))
                    push(t + fate.duration, _EV_ARRIVE, gc)
                continue

            # _EV_ARRIVE: delivery attempt for client c
            rec = inflight.pop(c)
            stale = server_round - rec["ver"]
            if rec["drop"]:
                # lost after compress, before delivery: nothing lands,
                # nothing is committed, nothing is billed
                dropped += 1
                events.append((t, "drop", c, stale))
            elif acfg.max_staleness is not None \
                    and stale > acfg.max_staleness:
                # too stale at arrival: same guarantees as a drop
                discarded += 1
                events.append((t, "discard", c, stale))
            else:
                # ACCEPT: the only path that commits client state and
                # bills uplink bits
                if has_cs:
                    cs = self._commit(cs, rec["ncs"], c)
                landed += 1
                if wire_mode:
                    # re-materialize the landed bytes (pack_wire is
                    # idempotent on the decoded carriers) and bill the
                    # MEASURED payload size — drops/discards above never
                    # reach this line, so they stay unbilled
                    rec["wire"] = self._repack(rec["sW"], rec["sM"],
                                               rec["sV"])
                    bits_total += 8 * wire.payload_nbytes(rec["wire"])
                else:
                    bits_total += bits_client
                eff_w = float(base_w[c]) \
                    * float(staleness_scale(stale, acfg.staleness_power))
                buffer.append(dict(rec, stale=stale, w=eff_w))
                events.append((t, "deliver", c, stale))
                if len(buffer) == K:
                    stack = lambda key: jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[e[key] for e in buffer])
                    wts = jnp.asarray([e["w"] for e in buffer], _F32)
                    if wire_mode:
                        # the buffer holds WirePayloads: the server step
                        # decodes the transported bytes themselves
                        W, M, V = self._apply_wire(W, M, V, stack("wire"),
                                                   wts)
                    else:
                        W, M, V = self._apply(W, M, V, stack("sW"),
                                              stack("sM"), stack("sV"), wts)
                    server_round += 1
                    steps += 1
                    bits_per_step.append(bits_total - sum(bits_per_step))
                    loss_per_step.append(float(np.mean(
                        [float(e["loss"]) for e in buffer])))
                    counts_per_step.append(
                        {k: sum(int(e["counts"][k]) for e in buffer)
                         for k in wire.COUNT_KEYS})
                    events.append((t, "server_step", steps,
                                   [e["stale"] for e in buffer]))
                    buffer = []
            redispatch(t, c)

        new_state = FedState(
            W=W, M=M, V=V,
            round=jnp.asarray(round0 + steps, jnp.int32),
            client_state=cs)
        metrics = {
            "uplink_bits": jnp.asarray(bits_total, _F32),
            "bits_per_step": bits_per_step,
            "loss_per_step": loss_per_step,
            "counts_per_step": counts_per_step,
            "server_steps": steps,
            "landed": landed,
            "dropped": dropped,
            "discarded": discarded,
            "buffer_pending": len(buffer),
            "events": events,
        }
        return new_state, metrics


def make_async_round(fed: FedConfig, loss_fn: Callable,
                     acfg: Optional[AsyncConfig] = None, *,
                     churn: Optional[ChurnModel] = None,
                     client_exec: str = "scan",
                     mesh=None) -> AsyncRoundDriver:
    """Build the buffered-async driver (mirrors ``make_fl_round``).

    ``run(state, batches, weights=None, rounds=1) -> (state, metrics)``
    where ``state`` is the same :class:`FedState` the sync round uses —
    the two drivers are interchangeable on a checkpoint."""
    return AsyncRoundDriver(fed, loss_fn, acfg or AsyncConfig(),
                            churn=churn, client_exec=client_exec,
                            mesh=mesh)
