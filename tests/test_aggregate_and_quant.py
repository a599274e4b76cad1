"""Aggregation transports + quantizers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate as A
from repro.core import quantize as Q
from repro.core import sparsify as S


def _masked(key, C, n, alpha):
    x = jax.random.normal(key, (C, n))
    masks = jnp.stack([S.topk_mask_exact(x[c], S.k_for(n, alpha))
                       for c in range(C)])
    return jnp.where(masks, x, 0.0)


@pytest.mark.parametrize("n", [100, 5000])
@pytest.mark.parametrize("sort_free", [True, False])
def test_sparse_pack_roundtrip(n, sort_free):
    """gather+scatter transport == dense weighted sum on masked deltas."""
    C, alpha = 4, 0.2
    x = _masked(jax.random.PRNGKey(0), C, n, alpha)
    w = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    dense = jnp.tensordot(w, x, axes=(0, 0))
    sparse = A.sparse_independent_gather_sum({"x": x.reshape(C, n)},
                                             alpha, w,
                                             sort_free=sort_free)["x"]
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=1e-5)


def test_shared_pack_uses_w_support():
    """SSM transport: m/v values are gathered at dW's support."""
    C, n, alpha = 2, 64, 0.25
    dw = _masked(jax.random.PRNGKey(1), C, n, alpha)
    dm = jax.random.normal(jax.random.PRNGKey(2), (C, n))
    dv = jax.random.normal(jax.random.PRNGKey(3), (C, n))
    mask = dw != 0
    dm_m, dv_m = jnp.where(mask, dm, 0), jnp.where(mask, dv, 0)
    w = jnp.ones((C,))
    aw, am, av = A.sparse_shared_gather_sum(
        {"x": dw}, {"x": dm_m}, {"x": dv_m}, alpha, w)
    np.testing.assert_allclose(np.asarray(aw["x"]),
                               np.asarray(dw.sum(0)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(am["x"]),
                               np.asarray(dm_m.sum(0)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(av["x"]),
                               np.asarray(dv_m.sum(0)), atol=1e-5)


def _one_device_agg(alpha, shared=True):
    """make_shardmap_sparse_aggregate on a trivial 1-device client mesh —
    the transport arithmetic (pack, gather, scatter, EF overflow
    feedback) is mesh-size independent, so it unit-tests in-process."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    pspec = {"x": P()}
    agg = A.make_shardmap_sparse_aggregate(mesh, pspec, ("data",), alpha,
                                           shared=shared)
    return agg


def test_shardmap_aggregate_matches_reference_transport():
    """1-client shard_map transport == the jnp gather/scatter reference."""
    C, n, alpha = 1, 128, 0.25
    dw = _masked(jax.random.PRNGKey(7), C, n, alpha)
    dm = jnp.where(dw != 0, jax.random.normal(jax.random.PRNGKey(8),
                                              (C, n)), 0.0)
    w = jnp.ones((C,))
    agg = _one_device_agg(alpha)
    aw, am, av = agg({"x": dw}, {"x": dm}, {"x": dm}, w)
    np.testing.assert_allclose(np.asarray(aw["x"]), np.asarray(dw.sum(0)),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(am["x"]), np.asarray(dm.sum(0)),
                               atol=1e-6)


def test_shardmap_aggregate_ef_overflow_feedback():
    """With per-shard EF state, values the fixed-capacity pack drops from
    the wire are added back into the residual; without overflow the
    residual passes through bit-unchanged."""
    n, alpha = 64, 0.25
    k = S.k_for(n, alpha)
    from repro.kernels.topk_mask.ops import overselect_bound
    kb = min(n, k + overselect_bound(k))           # pack capacity
    assert kb < n // 2
    # MORE nonzeros than capacity: positions 0..2kb-1 hold distinct values
    wf = jnp.zeros((n,)).at[jnp.arange(2 * kb)].set(
        jnp.arange(1.0, 2 * kb + 1))
    dw = wf[None]                                  # (C=1, n)
    err0 = jax.random.normal(jax.random.PRNGKey(9), (1, n))
    w = jnp.ones((1,))
    agg = _one_device_agg(alpha)
    (aw, am, av), err1 = agg({"x": dw}, {"x": dw}, {"x": dw}, w,
                             {"x": err0})
    # kept on the wire: the first kb nonzeros (prefix-sum pack order)
    kept = jnp.zeros((n,)).at[jnp.arange(kb)].set(wf[:kb])
    np.testing.assert_allclose(np.asarray(aw["x"]), np.asarray(kept),
                               atol=1e-6)
    # residual gains exactly the dropped overflow
    np.testing.assert_allclose(np.asarray(err1["x"]),
                               np.asarray(err0 + (wf - kept)[None]),
                               atol=1e-6)

    # no overflow -> residual is returned bitwise unchanged
    few = jnp.zeros((n,)).at[jnp.arange(k // 2)].set(1.0)[None]
    (_, _, _), err2 = agg({"x": few}, {"x": few}, {"x": few}, w,
                          {"x": err0})
    assert bool((err2["x"] == err0).all())


def test_ordered_weighted_sum_matches_dense():
    C, n = 6, 257
    x = jax.random.normal(jax.random.PRNGKey(10), (C, n))
    w = jnp.asarray([1.0, 0.0, 2.0, 0.5, 1.0, 3.0])
    np.testing.assert_allclose(
        np.asarray(A.ordered_weighted_sum({"x": x}, w)["x"]),
        np.asarray(A.dense_weighted_sum({"x": x}, w)["x"]), atol=1e-5)


def test_sign_quant_preserves_block_l1():
    x = jax.random.normal(jax.random.PRNGKey(4), (4096,))
    q = Q.sign_quant(x, block=512)
    # per-block magnitude is the L1 mean: mean |q| == mean |x| per block
    xb = x.reshape(-1, 512)
    qb = np.asarray(q).reshape(-1, 512)
    np.testing.assert_allclose(np.abs(qb).mean(1),
                               np.abs(np.asarray(xb)).mean(1), rtol=1e-5)
    assert set(np.unique(np.sign(qb))) <= {-1.0, 0.0, 1.0}


@pytest.mark.parametrize("bits", [4, 8])
def test_uniform_quant_error_bound(bits):
    x = jax.random.normal(jax.random.PRNGKey(5), (4096,))
    q = Q.uniform_quant(x, bits=bits, block=256)
    qmax = 2.0 ** (bits - 1) - 1
    xb = np.asarray(x).reshape(-1, 256)
    step = np.abs(xb).max(1) / qmax
    err = np.abs(np.asarray(q).reshape(-1, 256) - xb)
    assert (err <= step[:, None] * 0.5 + 1e-6).all()


def test_int8_store_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(6), (1000,)) * 3
    q, scale = Q.int8_store(x, block=128)
    y = Q.int8_load(q, scale, x.shape, x.dtype, block=128)
    rel = float(jnp.max(jnp.abs(y - x)) / jnp.max(jnp.abs(x)))
    assert rel < 0.01
