"""Core FL-round behaviour: paper-exactness properties + convergence."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedConfig, fed_init, make_fl_round
from repro.core.fed import _local_adam, active_client_count
from repro.optim import AdamHyper, adam_init, adam_step


def _toy():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1, "b": jnp.zeros((4,))}
    C = 4
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, (xs, ys), loss_fn, C


def _run(algo, rounds=8, alpha=0.25, mode="scan", agg="dense", C=4, L=3,
         **kw):
    params, batches, loss_fn, _ = _toy()
    fed = FedConfig(algorithm=algo, alpha=alpha, local_epochs=L,
                    n_clients=C, adam=AdamHyper(lr=0.05),
                    client_mode=mode, aggregate=agg, **kw)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    losses = []
    for _ in range(rounds):
        st, mets = rf(st, batches)
        losses.append(float(jnp.mean(mets["loss"])))
    return st, losses, mets


def test_alpha_one_equals_dense_fedadam():
    """alpha=1 makes FedAdam-SSM *exactly* FedAdam (Sec. VII setup)."""
    st_ssm, _, _ = _run("fedadam_ssm", alpha=1.0)
    st_dense, _, _ = _run("fedadam", alpha=1.0)
    for a, b in zip(jax.tree.leaves(st_ssm.W), jax.tree.leaves(st_dense.W)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_scan_equals_vmap():
    for algo in ["fedadam_ssm", "fedadam_top", "fedadam", "fedsgd"]:
        st_s, _, _ = _run(algo, mode="scan")
        st_v, _, _ = _run(algo, mode="vmap")
        for a, b in zip(jax.tree.leaves(st_s.W), jax.tree.leaves(st_v.W)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_sparse_gather_equals_dense_transport():
    st_d, _, _ = _run("fedadam_ssm", mode="vmap", agg="dense")
    st_s, _, _ = _run("fedadam_ssm", mode="vmap", agg="sparse_gather")
    for a, b in zip(jax.tree.leaves(st_d.W), jax.tree.leaves(st_s.W)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_single_client_dense_equals_centralized_adam():
    """N=1, alpha=1: one FL round of L epochs == L centralized Adam steps
    (paper Eqs. 3-5, no bias correction)."""
    params, (xs, ys), loss_fn, _ = _toy()
    batch = (xs[:1], ys[:1])
    fed = FedConfig(algorithm="fedadam", alpha=1.0, local_epochs=5,
                    n_clients=1, adam=AdamHyper(lr=0.01))
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    st, _ = rf(st, batch)

    # centralized: plain Adam, same hyper, same data
    h = AdamHyper(lr=0.01)
    w = params
    opt = adam_init(params)
    single = (xs[0], ys[0])
    for _ in range(5):
        g = jax.grad(loss_fn)(w, single)
        w, opt = adam_step(w, g, opt, h)
    for a, b in zip(jax.tree.leaves(st.W), jax.tree.leaves(w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # moments aggregated too (the paper's point vs Efficient-Adam)
    for a, b in zip(jax.tree.leaves(st.M), jax.tree.leaves(opt.m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("algo", ["fedadam_ssm", "fedadam_top", "fedadam",
                                  "ssm_m", "ssm_v", "fairness_top",
                                  "fedsgd", "efficient_adam"])
def test_converges_on_toy(algo):
    _, losses, _ = _run(algo, rounds=15)
    assert losses[-1] < losses[0] * 0.6, losses


def test_uplink_bits_ordering():
    """SSM < Top < dense bit counts at alpha=0.05 (Section IV).

    The round now reports WIRE-EXACT bits (8 * WirePayload.nbytes,
    core/wire.py), so this runs on a model large enough that the
    format's 4096-element alignment padding is second-order — on the
    36-parameter toy tree the bitmap padding alone exceeds the dense
    payload and honest accounting inverts the paper's ordering.  The
    padding arithmetic itself is pinned by tests/test_wire.py."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (512, 32)) * 0.1,
              "b": jnp.zeros((32,))}
    C = 2
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 8, 512))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (512, 32))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    def bits(algo):
        fed = FedConfig(algorithm=algo, alpha=0.05, local_epochs=1,
                        n_clients=C, adam=AdamHyper(lr=0.05))
        rf = jax.jit(make_fl_round(fed, loss_fn))
        _, mets = rf(fed_init(fed, params), (xs, ys))
        return float(mets["uplink_bits"])

    assert bits("fedadam_ssm") < bits("fedadam_top") < bits("fedadam")


def test_shared_mask_alignment():
    """FedAdam-SSM: all three uploaded deltas share the SAME support."""
    params, batches, loss_fn, C = _toy()
    fed = FedConfig(algorithm="fedadam_ssm", alpha=0.3, local_epochs=2,
                    n_clients=C, adam=AdamHyper(lr=0.05), client_mode="vmap")
    st = fed_init(fed, params)
    # inspect one client's compression by reproducing the deltas
    from repro.core.fed import _tree_sub
    from repro.core import masks
    batch0 = jax.tree.map(lambda x: x[0], batches)
    w, m, v, _ = _local_adam(loss_fn, st.W, st.M, st.V, batch0, fed)
    dW, dM, dV = _tree_sub(w, st.W), _tree_sub(m, st.M), _tree_sub(v, st.V)
    mask = masks.shared_mask("ssm_w", dW, dM, dV, 0.3)
    from repro.core import sparsify as S
    for leaf_dw, leaf_mask in zip(jax.tree.leaves(dW),
                                  jax.tree.leaves(mask)):
        exact = S.topk_mask_exact(leaf_dw, S.k_for(leaf_dw.size, 0.3))
        assert bool(jnp.all(leaf_mask == exact))  # Eq. 28: mask=Top_k(|dW|)


def test_error_feedback_accumulates():
    """Beyond-paper EF: residuals carried to the next round change the
    trajectory and do not diverge."""
    st_ef, losses_ef, _ = _run("fedadam_ssm", rounds=12, alpha=0.1,
                               error_feedback=True)
    st_no, losses_no, _ = _run("fedadam_ssm", rounds=12, alpha=0.1)
    assert np.isfinite(losses_ef).all()
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(st_ef.W), jax.tree.leaves(st_no.W)))
    assert diff > 1e-7  # EF actually did something


@pytest.mark.parametrize("mode", ["scan", "shardmap"])
def test_ef_residual_is_carried_not_rezeroed(mode):
    """The round-2 payload must actually SEE round 1's residual: zeroing
    the carried residual between rounds changes the round-2 outcome, on
    the scan reference and on the shard_map mesh driver alike."""
    from repro.launch.mesh import make_mesh

    params, batches, loss_fn, C = _toy()
    if mode == "shardmap":
        C = 1
        batches = jax.tree.map(lambda x: x[:1], batches)
    fed = FedConfig(algorithm="fedadam_ssm", alpha=0.1, local_epochs=2,
                    n_clients=C, adam=AdamHyper(lr=0.05),
                    error_feedback=True,
                    client_mode=("scan" if mode == "scan" else "vmap"),
                    client_axes=(("data",) if mode == "shardmap"
                                 else None))
    rf = jax.jit(make_fl_round(fed, loss_fn))
    ctx = jax.set_mesh(make_mesh((1,), ("data",))) \
        if mode == "shardmap" else contextlib.nullcontext()
    with ctx:
        st1, _ = rf(fed_init(fed, params), batches)
        err1 = st1.client_state["comp"]["err"]
        assert max(float(jnp.max(jnp.abs(x)))
                   for x in jax.tree.leaves(err1)) > 0
        st2, _ = rf(st1, batches)
        zeroed = st1._replace(client_state=dict(
            st1.client_state,
            comp={"err": jax.tree.map(jnp.zeros_like, err1)}))
        st2z, _ = rf(zeroed, batches)
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(st2.W), jax.tree.leaves(st2z.W)))
    assert diff > 1e-7, "round-2 payload ignored the carried residual"


def test_onebit_adam_with_warmup_converges():
    """1-bit Adam two-phase protocol: dense FedAdam warmup populates V,
    then the compressed phase uses it as a frozen precondition."""
    params, batches, loss_fn, C = _toy()
    warm = FedConfig(algorithm="fedadam", alpha=1.0, local_epochs=1,
                     n_clients=C, adam=AdamHyper(lr=0.02))
    rf_warm = jax.jit(make_fl_round(warm, loss_fn))
    st = fed_init(warm, params)
    for _ in range(3):
        st, mets = rf_warm(st, batches)
    onebit = FedConfig(algorithm="onebit_adam", alpha=1.0, local_epochs=1,
                       n_clients=C, adam=AdamHyper(lr=0.02))
    st1 = fed_init(onebit, st.W)
    st1 = st1._replace(M=st.M, V=st.V)
    rf1 = jax.jit(make_fl_round(onebit, loss_fn))
    losses = []
    for _ in range(15):
        st1, mets = rf1(st1, batches)
        losses.append(float(jnp.mean(mets["loss"])))
    assert losses[-1] < losses[0], losses


def test_active_client_count_boundaries():
    """The participation seam shared by the sync weight-masking round
    and the async dispatch pool (see its docstring): host-static int in
    [1, n_clients], Python (banker's) rounding, floor of one."""
    mk = lambda p, C: FedConfig(algorithm="fedadam_ssm", n_clients=C,
                                participation=p)
    # boundaries: 0.0 never builds an empty round; 1.0 is everyone
    assert active_client_count(mk(0.0, 7)) == 1
    assert active_client_count(mk(1.0, 7)) == 7
    assert active_client_count(mk(1.0, 1)) == 1
    # tiny fractions clamp up to one client
    assert active_client_count(mk(0.01, 20)) == 1
    # rounding is Python round (banker's at .5 ties)
    assert active_client_count(mk(0.5, 5)) == 2      # round(2.5) == 2
    assert active_client_count(mk(0.5, 7)) == 4      # round(3.5) == 4
    assert active_client_count(mk(0.25, 20)) == 5
    # invariant over a sweep: static int within [1, C]
    for C in (1, 3, 8, 20):
        for p in np.linspace(0.0, 1.0, 21):
            n = active_client_count(mk(float(p), C))
            assert isinstance(n, int) and 1 <= n <= C


def test_partial_participation():
    """Beyond-paper: sampling a fraction of clients per round still
    converges, reduces per-round uplink proportionally, and only active
    clients contribute to the aggregate."""
    params, batches, loss_fn, C = _toy()
    fed = FedConfig(algorithm="fedadam_ssm", alpha=0.5, local_epochs=2,
                    n_clients=C, adam=AdamHyper(lr=0.05),
                    participation=0.5)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    losses = []
    for _ in range(15):
        st, mets = rf(st, batches)
        losses.append(float(jnp.mean(mets["loss"])))
    assert losses[-1] < losses[0]
    # uplink accounts only the sampled clients
    full = FedConfig(algorithm="fedadam_ssm", alpha=0.5, local_epochs=2,
                     n_clients=C, adam=AdamHyper(lr=0.05))
    rf_full = jax.jit(make_fl_round(full, loss_fn))
    _, mets_full = rf_full(fed_init(full, params), batches)
    assert float(mets["uplink_bits"]) == 0.5 * float(mets_full["uplink_bits"])
