"""Deterministic churn harness for the buffered-async driver.

Every behaviour of core/async_fed.py is pinned here against the seeded
virtual-clock event model (data/churn.py): bitwise same-seed replay,
bitwise degenerate equivalence with the synchronous ``round_scan``,
fault injection (drops / stale discards leave per-client compressor
state untouched and unbilled), buffer semantics (a server step happens
at exactly K updates, never fewer), staleness-weighting properties, and
scan <-> shard_map composition under churn.  Replay-from-seed debugging
recipe: docs/async.md.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.core import FedConfig, fed_init, make_fl_round
from repro.core import comm
from repro.core import sparsify as S
from repro.core.async_fed import (AsyncConfig, make_async_round,
                                  staleness_scale, staleness_weights)
from repro.data.churn import ChurnConfig, ChurnModel, ClientFate
from repro.optim import AdamHyper

pytestmark = pytest.mark.churn

_REPO = Path(__file__).resolve().parents[1]


def _toy(C=4):
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1,
              "b": jnp.zeros((4,))}
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, (xs, ys), loss_fn


def _fed(C=4, **kw):
    kw.setdefault("algorithm", "fedadam_ssm")
    kw.setdefault("error_feedback", True)
    return FedConfig(alpha=0.3, local_epochs=2, n_clients=C,
                     adam=AdamHyper(lr=0.05), **kw)


def _biteq(ta, tb):
    la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
    assert len(la) == len(lb)
    return all(bool(jnp.all(a == b)) for a, b in zip(la, lb))


# ---------------------------------------------------------------------------
# Replay + degenerate equivalence (the two acceptance anchors)
# ---------------------------------------------------------------------------


def test_same_seed_bitwise_replay():
    """Same ChurnConfig seed => the full simulation replays bitwise:
    event log, final params, per-client EF state, uplink_bits."""
    C = 6
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    cc = ChurnConfig(seed=3, jitter=5, straggler_prob=0.3, drop_prob=0.2,
                     rejoin_delay=2)
    acfg = AsyncConfig(buffer_size=3, max_staleness=2)

    def go():
        run = make_async_round(fed, loss_fn, acfg,
                               churn=ChurnModel(cc, C))
        return run(fed_init(fed, params), batches, rounds=5)

    s1, m1 = go()
    s2, m2 = go()
    assert m1["events"] == m2["events"]
    assert m1["server_steps"] == 5
    # churn actually exercised something this seed
    assert m1["dropped"] > 0 and m1["discarded"] > 0
    assert float(m1["uplink_bits"]) == float(m2["uplink_bits"])
    assert _biteq(s1, s2)  # W, M, V, round, and all per-client state


def test_degenerate_config_matches_round_scan_bitwise():
    """Zero churn + buffer == cohort + staleness weight == 1 collapses
    the async driver onto the synchronous barrier: 3 rounds must match
    ``round_scan`` BIT-identically (params, moments, per-client EF
    state, round counter, and uplink accounting)."""
    C = 4
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)

    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    sync_bits = 0.0
    for _ in range(3):
        st, mets = rf(st, batches)
        sync_bits += float(mets["uplink_bits"])

    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=C),
                           churn=ChurnModel(ChurnConfig(), C))
    ast, amets = run(fed_init(fed, params), batches, rounds=3)

    assert amets["server_steps"] == 3
    assert amets["landed"] == 3 * C
    assert float(amets["uplink_bits"]) == sync_bits
    assert _biteq(st.W, ast.W)
    assert _biteq(st.M, ast.M)
    assert _biteq(st.V, ast.V)
    assert _biteq(st.client_state, ast.client_state)
    assert int(st.round) == int(ast.round) == 3


def test_async_state_is_sync_checkpoint_compatible():
    """The async driver consumes/produces the same FedState as the sync
    round: sync round 1 -> async round 2 runs and advances the clock."""
    C = 4
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st, _ = rf(fed_init(fed, params), batches)
    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=C),
                           churn=ChurnModel(ChurnConfig(), C))
    ast, mets = run(st, batches, rounds=1)
    assert mets["server_steps"] == 1 and int(ast.round) == 2


# ---------------------------------------------------------------------------
# Fault injection (scripted fates)
# ---------------------------------------------------------------------------


def _warm_state(fed, params, batches, loss_fn):
    """One clean async round so EF residuals are nonzero before the
    fault is injected (untouched-vs-zeros would be a vacuous check)."""
    run = make_async_round(fed, loss_fn,
                           AsyncConfig(buffer_size=fed.n_clients),
                           churn=ChurnModel(ChurnConfig(), fed.n_clients))
    st, _ = run(fed_init(fed, params), batches, rounds=1)
    err = st.client_state["comp"]["err"]
    assert max(float(jnp.max(jnp.abs(x)))
               for x in jax.tree.leaves(err)) > 0
    return st


def test_drop_after_compress_preserves_state_and_bits():
    """A client whose update is lost after compress but before delivery
    keeps its EF residual bitwise intact (never rezeroed — the
    Efficient-Adam lesson) and its bits are NOT billed."""
    C = 4
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    st0 = _warm_state(fed, params, batches, loss_fn)

    victim = 1
    churn = ChurnModel(ChurnConfig(), C,
                       script={(victim, 0): ClientFate(8, drop=True)})
    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=C - 1),
                           churn=churn)
    st1, mets = run(st0, batches, rounds=1)

    assert mets["dropped"] == 1 and mets["landed"] == C - 1
    pick = lambda cs, c: jax.tree.map(lambda x: x[c], cs)
    # the dropped client's whole per-client state is bitwise untouched
    assert _biteq(pick(st0.client_state, victim),
                  pick(st1.client_state, victim))
    # the survivors' residuals did move
    for c in range(C):
        if c != victim:
            assert not _biteq(pick(st0.client_state, c),
                              pick(st1.client_state, c))
    # bits: only landed updates are billed, and they match comm.bits_for
    d = sum(x.size for x in jax.tree.leaves(st0.W))
    sizes = tuple(x.size for x in jax.tree.leaves(st0.W))
    per_client = comm.bits_for(fed.algorithm, d, S.k_for(d, fed.alpha),
                               1, 32, sizes=sizes, alpha=fed.alpha)
    assert float(mets["uplink_bits"]) == (C - 1) * float(per_client)


def test_stale_straggler_discarded_with_same_guarantees():
    """An update older than max_staleness at arrival is discarded: state
    untouched bitwise, bits unbilled — exactly like a drop."""
    C = 4
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    st0 = _warm_state(fed, params, batches, loss_fn)

    victim = 2
    # base_duration=8: the pack arrives at t=8,16,24...; the victim's
    # attempt-0 update arrives at t=20 with snapshot version 0 while the
    # server is already 2 steps ahead
    churn = ChurnModel(ChurnConfig(), C,
                       script={(victim, 0): ClientFate(20, drop=False)})
    run = make_async_round(fed, loss_fn,
                           AsyncConfig(buffer_size=C - 1, max_staleness=0),
                           churn=churn)
    st1, mets = run(st0, batches, rounds=3)

    assert mets["discarded"] >= 1
    discards = [e for e in mets["events"] if e[1] == "discard"]
    assert any(e[2] == victim and e[3] == 2 for e in discards)
    # victim state frozen through its discard window: replay the sim and
    # stop before the victim's redispatched update ever lands
    landed_victim = [e for e in mets["events"]
                     if e[1] == "deliver" and e[2] == victim]
    d = sum(x.size for x in jax.tree.leaves(st0.W))
    sizes = tuple(x.size for x in jax.tree.leaves(st0.W))
    per_client = comm.bits_for(fed.algorithm, d, S.k_for(d, fed.alpha),
                               1, 32, sizes=sizes, alpha=fed.alpha)
    assert float(mets["uplink_bits"]) == \
        float(mets["landed"]) * float(per_client)
    if not landed_victim:
        pick = lambda cs, c: jax.tree.map(lambda x: x[c], cs)
        assert _biteq(pick(st0.client_state, victim),
                      pick(st1.client_state, victim))


def test_buffer_never_applies_below_k():
    """With every update lost, the buffer never reaches K and the server
    NEVER steps: params bitwise frozen, zero bits billed."""
    C = 4
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    st0 = fed_init(fed, params)
    churn = ChurnModel(ChurnConfig(drop_prob=1.0), C)
    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=2),
                           churn=churn)
    st1, mets = run(st0, batches, rounds=1, max_events=64)
    assert mets["server_steps"] == 0 and mets["landed"] == 0
    assert float(mets["uplink_bits"]) == 0.0
    assert _biteq(st0, st1)
    assert not any(e[1] == "server_step" for e in mets["events"])


def test_buffer_consumed_in_exact_multiples_of_k():
    """Accounting invariant under churn: accepted updates are consumed
    only in batches of exactly K (landed == K * steps + pending), and
    every server_step event carries exactly K staleness entries."""
    C = 6
    params, batches, loss_fn = _toy(C)
    fed = _fed(C)
    cc = ChurnConfig(seed=11, jitter=4, straggler_prob=0.25,
                     drop_prob=0.15)
    K = 4
    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=K),
                           churn=ChurnModel(cc, C))
    _, mets = run(fed_init(fed, params), batches, rounds=4)
    assert mets["landed"] == K * mets["server_steps"] \
        + mets["buffer_pending"]
    for e in mets["events"]:
        if e[1] == "server_step":
            assert len(e[3]) == K


# ---------------------------------------------------------------------------
# Staleness weighting (property-checked)
# ---------------------------------------------------------------------------


def test_staleness_scale_is_exactly_one_at_zero():
    """The anchor of the degenerate equivalence: fresh updates must get
    EXACTLY the sync round's weight, for any power."""
    for p in [0.0, 0.25, 0.5, 1.0, 2.0]:
        assert float(staleness_scale(0, p)) == 1.0


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 40), min_size=1, max_size=12),
       st.floats(0.0, 3.0))
def test_staleness_weights_properties(stales, power):
    """Nonnegative, normalized, monotone non-increasing in staleness."""
    s = np.asarray(stales)
    w = staleness_weights(s, power)
    assert w.shape == s.shape
    assert np.all(w >= 0)
    assert abs(float(w.sum()) - 1.0) < 1e-12
    order = np.argsort(s, kind="stable")
    ws = w[order]  # increasing staleness => non-increasing weight
    assert np.all(np.diff(ws) <= 1e-15)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 50), st.integers(1, 50), st.floats(0.05, 3.0))
def test_staleness_scale_strictly_penalizes(s, extra, power):
    """With power > 0, a strictly staler update gets strictly less."""
    assert float(staleness_scale(s + extra, power)) \
        < float(staleness_scale(s, power))


# ---------------------------------------------------------------------------
# scan <-> shard_map composition under churn (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

_SUB = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core import FedConfig, fed_init
    from repro.core.async_fed import AsyncConfig, make_async_round
    from repro.data.churn import ChurnConfig, ChurnModel
    from repro.optim import AdamHyper

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1,
              "b": jnp.zeros((4,))}
    C = 8
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    cc = ChurnConfig(seed=5, jitter=3, straggler_prob=0.25,
                     drop_prob=0.15)
    acfg = AsyncConfig(buffer_size=4, max_staleness=2)

    def go(exec_kind):
        kw = dict(algorithm="fedadam_ssm", alpha=0.3, local_epochs=2,
                  n_clients=C, adam=AdamHyper(lr=0.05),
                  error_feedback=True)
        if exec_kind == "shardmap":
            mesh = make_mesh((8,), ("data",))
            fed = FedConfig(client_mode="vmap", client_axes=("data",),
                            **kw)
            with jax.set_mesh(mesh):
                run = make_async_round(fed, loss_fn, acfg,
                                       churn=ChurnModel(cc, C),
                                       client_exec="shardmap", mesh=mesh)
                return run(fed_init(fed, params), (xs, ys), rounds=4)
        fed = FedConfig(**kw)
        run = make_async_round(fed, loss_fn, acfg,
                               churn=ChurnModel(cc, C))
        return run(fed_init(fed, params), (xs, ys), rounds=4)

    st_s, m_s = go("scan")
    st_m, m_m = go("shardmap")

    def cmp(ta, tb):
        md, eq = 0.0, True
        for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            md = max(md, float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))))
            eq = eq and bool(jnp.all(a == b))
        return dict(eq=eq, maxdiff=md)

    out = dict(
        events_eq=(m_s["events"] == m_m["events"]),
        steps=m_s["server_steps"],
        glob=cmp((st_s.W, st_s.M, st_s.V), (st_m.W, st_m.M, st_m.V)),
        cs=cmp(st_s.client_state, st_m.client_state),
        bits_eq=(float(m_s["uplink_bits"]) == float(m_m["uplink_bits"])),
    )
    print("RESULT", json.dumps(out))
""")


@pytest.mark.slow
def test_scan_shardmap_async_equivalence_under_churn():
    """The SAME churn schedule driven through the scan exec and the
    shard_map mesh exec (8 forced host devices, padded cohorts) produces
    the same event log and BIT-identical state — extends the sync
    scan <-> shard_map guarantee of test_fed_equivalence.py to the
    buffered-async driver."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(_REPO / "src")
    out = subprocess.run([sys.executable, "-c", _SUB], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["events_eq"], "schedules diverged between execs"
    assert res["steps"] == 4
    assert res["glob"]["eq"], res
    assert res["cs"]["eq"], res
    assert res["bits_eq"]
