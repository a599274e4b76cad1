"""Compressor subsystem: registry round-trips, exact bit accounting,
error-feedback state across rounds, and drop-in registration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm, masks
from repro.core import sparsify as S
from repro.core.compressors import (
    DIAG_KEYS,
    Compressor,
    Deltas,
    Packed,
    available,
    diag_metrics,
    make_compressor,
    register,
    transport_of,
    unregister,
)
from repro.core.fed import ALGORITHMS, FedConfig, fed_init, make_fl_round
from repro.optim import AdamHyper


def _tree(seed, scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"w": jax.random.normal(ks[0], (32, 8)) * scale,
            "b": jax.random.normal(ks[1], (8,)) * scale}


def _deltas(seed=1):
    return Deltas(_tree(seed), _tree(seed + 100, 0.1), _tree(seed + 200, 0.01))


def _fed(algo, **kw):
    kw.setdefault("alpha", 0.25)
    kw.setdefault("n_clients", 4)
    return FedConfig(algorithm=algo, **kw)


# ---------------------------------------------------------------------------
# Registry + round-trip
# ---------------------------------------------------------------------------


def test_registry_covers_all_algorithms_in_order():
    assert tuple(available()) == tuple(ALGORITHMS)


def test_unknown_algorithm_raises():
    class Cfg:
        algorithm = "nope"
    with pytest.raises(KeyError, match="nope"):
        make_compressor(Cfg())


@pytest.mark.parametrize("algo", ALGORITHMS)
def test_roundtrip_structure_and_finiteness(algo):
    comp = make_compressor(_fed(algo))
    deltas = _deltas()
    state = comp.init_state(deltas.W)
    packed, new_state, bits = comp.compress(deltas, state)
    rec = comp.decompress(packed)
    # reconstruction has the input's tree structure and is finite
    assert (jax.tree.structure((rec.W, rec.M, rec.V))
            == jax.tree.structure((deltas.W, deltas.M, deltas.V)))
    for a, b in zip(jax.tree.leaves((rec.W, rec.M, rec.V)),
                    jax.tree.leaves((deltas.W, deltas.M, deltas.V))):
        assert a.shape == b.shape
        assert np.isfinite(np.asarray(a)).all()
    # diagnostics carry the canonical keys
    assert set(packed.diag) == set(DIAG_KEYS)
    # stateful compressors return the same state structure
    assert (state is None) == (new_state is None)
    if state is not None:
        assert (jax.tree.structure(state) == jax.tree.structure(new_state))
    d = sum(x.size for x in jax.tree.leaves(deltas.W))
    assert bits == comp.bits_per_client(d)


@pytest.mark.parametrize("algo", ["fedadam", "fedsgd"])
def test_dense_compressor_is_identity(algo):
    comp = make_compressor(_fed(algo))
    deltas = _deltas()
    packed, _, _ = comp.compress(deltas, None)
    rec = comp.decompress(packed)
    for a, b in zip(jax.tree.leaves(tuple(rec)),
                    jax.tree.leaves(tuple(Deltas(*deltas)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ssm_compress_matches_direct_shared_mask():
    """The compressor reproduces Eq. 28 exactly: mask = Top_k(|dW|),
    applied to all three tensors."""
    alpha = 0.3
    comp = make_compressor(_fed("fedadam_ssm", alpha=alpha))
    deltas = _deltas()
    packed, _, _ = comp.compress(deltas, None)
    mask = masks.shared_mask("ssm_w", deltas.W, deltas.M, deltas.V, alpha)
    for got, want in zip(
            jax.tree.leaves((packed.W, packed.M, packed.V)),
            jax.tree.leaves((S.tree_sparsify(deltas.W, mask),
                             S.tree_sparsify(deltas.M, mask),
                             S.tree_sparsify(deltas.V, mask)))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shared_vs_independent_support():
    """SSM: one support for W/M/V.  Top: supports may differ."""
    comp = make_compressor(_fed("fedadam_ssm"))
    packed, _, _ = comp.compress(_deltas(), None)
    for w, m, v in zip(jax.tree.leaves(packed.W), jax.tree.leaves(packed.M),
                       jax.tree.leaves(packed.V)):
        assert bool(jnp.all((w != 0) == (m != 0)) &
                    jnp.all((w != 0) == (v != 0)))
    assert transport_of("fedadam_ssm") == "shared_sparse"
    assert transport_of("fedadam_top") == "independent_sparse"
    assert transport_of("fedadam") == "dense"
    assert transport_of("efficient_adam") == "quantized"


# ---------------------------------------------------------------------------
# Bit accounting: compressor reports == core/comm.py formulas, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", ALGORITHMS)
@pytest.mark.parametrize("d", [1000, 1 << 20, 12_345_678])
def test_bits_match_comm_formulas_exactly(algo, d):
    fed = _fed(algo, alpha=0.05, n_clients=7, quant_bits=4)
    comp = make_compressor(fed)
    k = S.k_for(d, fed.alpha)
    want = comm.bits_for(algo, d, k, fed.n_clients, fed.q_bits,
                         quant_bits=fed.quant_bits)
    assert fed.n_clients * comp.bits_per_client(d) == want


def test_compress_reports_the_same_bits_as_the_round_metric():
    fed = _fed("fedadam_ssm", alpha=0.1)
    comp = make_compressor(fed)
    deltas = _deltas()
    d = sum(x.size for x in jax.tree.leaves(deltas.W))
    _, _, bits = comp.compress(deltas, None)
    assert bits == comm.bits_for("fedadam_ssm", d, S.k_for(d, fed.alpha),
                                 1, fed.q_bits)


# ---------------------------------------------------------------------------
# Error feedback across rounds, scan AND vmap
# ---------------------------------------------------------------------------


def _toy():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1,
              "b": jnp.zeros((4,))}
    C = 4
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, (xs, ys), loss_fn, C


def _run_rounds(algo, mode, rounds=3, **kw):
    params, batches, loss_fn, C = _toy()
    fed = FedConfig(algorithm=algo, alpha=0.25, local_epochs=2, n_clients=C,
                    adam=AdamHyper(lr=0.05), client_mode=mode, **kw)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    errs = []
    for _ in range(rounds):
        st, mets = rf(st, batches)
        errs.append(jax.tree.map(np.asarray, st.client_state["comp"]["err"]))
    return st, errs, mets


@pytest.mark.parametrize("mode", ["scan", "vmap"])
@pytest.mark.parametrize("algo", ["onebit_adam", "efficient_adam"])
def test_error_feedback_residuals_carried_across_rounds(algo, mode):
    st, errs, mets = _run_rounds(algo, mode)
    # residual exists per client, is nonzero after round 1, and evolves
    lead = jax.tree.leaves(errs[0])[0].shape[0]
    assert lead == 4
    assert max(np.abs(l).max() for l in jax.tree.leaves(errs[0])) > 0
    moved = max(np.abs(a - b).max()
                for a, b in zip(jax.tree.leaves(errs[0]),
                                jax.tree.leaves(errs[1])))
    assert moved > 0
    for leaf in jax.tree.leaves(errs[-1]):
        assert np.isfinite(leaf).all()
    assert np.isfinite(float(jnp.mean(mets["loss"])))


@pytest.mark.parametrize("algo", ["onebit_adam", "efficient_adam"])
def test_error_feedback_scan_equals_vmap(algo):
    st_s, errs_s, _ = _run_rounds(algo, "scan")
    st_v, errs_v, _ = _run_rounds(algo, "vmap")
    for a, b in zip(jax.tree.leaves(st_s.W), jax.tree.leaves(st_v.W)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(jax.tree.leaves(errs_s[-1]), jax.tree.leaves(errs_v[-1])):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("mode", ["scan", "vmap"])
def test_sparse_error_feedback_state_lives_under_comp(mode):
    st, errs, _ = _run_rounds("fedadam_ssm", mode, error_feedback=True)
    assert set(st.client_state) == {"comp"}
    assert max(np.abs(l).max() for l in jax.tree.leaves(errs[0])) > 0


def test_efficient_adam_keeps_persistent_local_moments():
    st, _, _ = _run_rounds("efficient_adam", "scan")
    assert set(st.client_state) == {"comp", "m", "v"}
    # local moments actually trained (nonzero, per-client leading axis)
    m0 = jax.tree.leaves(st.client_state["m"])[0]
    assert m0.shape[0] == 4 and float(jnp.abs(m0).max()) > 0


# ---------------------------------------------------------------------------
# Drop-in registration: a new scheme is one registration away
# ---------------------------------------------------------------------------


def test_custom_compressor_dropin_runs_a_round():
    @dataclasses.dataclass(frozen=True)
    class SignW(Compressor):
        """FedLion-flavoured toy: sign-compress dW, drop moments."""
        name: str = "sign_w"
        q_bits: int = 32
        server_update = "w_only"

        def compress(self, deltas, state):
            from repro.core import quantize
            q = quantize.tree_sign_quant(deltas.W)
            z = jax.tree.map(jnp.zeros_like, deltas.M)
            packed = Packed(q, z, jax.tree.map(jnp.zeros_like, deltas.V),
                            diag_metrics(deltas, Deltas(q, z, z)))
            d = sum(x.size for x in jax.tree.leaves(deltas.W))
            return packed, state, self.bits_per_client(d)

        def bits_per_client(self, d):
            import math
            return d + self.q_bits * math.ceil(d / 1024)

    register("sign_w")(lambda fed: SignW(q_bits=fed.q_bits))
    try:
        assert "sign_w" in available()
        params, batches, loss_fn, C = _toy()
        fed = FedConfig(algorithm="sign_w", local_epochs=2, n_clients=C,
                        adam=AdamHyper(lr=0.05))
        rf = jax.jit(make_fl_round(fed, loss_fn))
        st = fed_init(fed, params)
        losses = []
        for _ in range(8):
            st, mets = rf(st, batches)
            losses.append(float(jnp.mean(mets["loss"])))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        assert float(mets["uplink_bits"]) == C * SignW().bits_per_client(
            sum(x.size for x in jax.tree.leaves(params)))
    finally:
        unregister("sign_w")
    assert "sign_w" not in available()


@pytest.mark.parametrize("algo,kw", [
    ("efficient_adam", {}),
    ("onebit_adam", {}),
    ("fedadam_ssm", dict(error_feedback=True, alpha=0.25)),
])
def test_stateful_compressor_runs_on_shardmap_driver(algo, kw):
    """The shard_map spatial driver THREADS per-client compressor state
    (it used to raise NotImplementedError for any stateful compressor):
    the round builds, runs, and carries a populated state tree across
    rounds.  A 1-device client mesh exercises the exact same MANUAL
    region as the multi-device CI mesh (tests/test_fed_equivalence.py
    pins multi-device equivalence)."""
    from repro.launch.mesh import make_mesh

    params, batches, loss_fn, _ = _toy()
    C = 1
    one = lambda t: jax.tree.map(lambda x: x[:1], t)
    fed = FedConfig(algorithm=algo, n_clients=C, local_epochs=2,
                    adam=AdamHyper(lr=0.05), client_mode="vmap",
                    client_axes=("data",), **kw)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    st = fed_init(fed, params)
    assert st.client_state is not None
    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        st, mets = rf(st, one(batches))
        st2, mets = rf(st, one(batches))
    assert st2.client_state is not None, "state dropped by the mesh driver"
    err_leaves = jax.tree.leaves(st2.client_state["comp"])
    assert all(x.shape[0] == C for x in err_leaves)
    err_norm = sum(float(jnp.sum(jnp.abs(x))) for x in err_leaves)
    assert np.isfinite(err_norm) and err_norm > 0, \
        "EF residual never populated — compression dropped nothing?"
    assert np.isfinite(float(jnp.mean(mets["loss"])))
