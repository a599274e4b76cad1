"""Per-kernel interpret-mode validation: sweep shapes x dtypes against the
pure-jnp ref.py oracles (per the brief, every Pallas kernel gets this)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparsify as S
from repro.kernels.fused_adam import ops as fa_ops
from repro.kernels.fused_adam.ref import fused_adam_ref
from repro.kernels.packed_topk import ops as pk_ops
from repro.kernels.packed_topk.ref import (packed_apply_ef_ref,
                                           packed_hist_ref,
                                           packed_mask_apply_ref,
                                           refine_taus)
from repro.kernels.ssm_apply import ops as sa_ops
from repro.kernels.ssm_apply.ref import ssm_apply_ref
from repro.kernels.topk_mask import ops as tm_ops
from repro.kernels.topk_mask.ref import (log2_taus, select_tau_ref,
                                         topk_mask_exact, topk_mask_ref)
from repro.optim import AdamHyper

SHAPES = [(64,), (8192,), (8, 1024), (3, 5, 7), (50_000,), (2, 8192, 3)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("bias_correction", [False, True])
def test_fused_adam_allclose(shape, dtype, bias_correction):
    h = AdamHyper(lr=0.01, bias_correction=bias_correction)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w, g, m, v = (jax.random.normal(k, shape).astype(dtype) for k in keys)
    v = jnp.abs(v)
    count = jnp.int32(3)
    out_k = fa_ops.fused_adam(w, g, m, v, h, count)
    sc = fa_ops._effective_scalars(h, count)
    out_r = fused_adam_ref(sc, w, g, m, v)
    for a, b in zip(out_k, out_r):
        atol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol)


@pytest.mark.parametrize("n,alpha", [(8192, 0.05), (50_000, 0.05),
                                     (100_000, 0.01), (9000, 0.3),
                                     (8192, 0.99)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_topk_mask_kernel_matches_ref(n, alpha, dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (n,)).astype(dtype)
    k = max(1, int(alpha * n))
    mask_k, tau_k, cnt = tm_ops.topk_mask_kernel(x, k)
    mask_r = topk_mask_ref(x, k)
    assert bool(jnp.all(mask_k == mask_r)), "kernel != jnp oracle"
    # selection quality vs exact top-k: the enforced contract is
    # overselect_bound — assert against it, never a re-derived constant
    assert int(mask_k.sum()) >= min(k, n)
    assert int(mask_k.sum()) <= k + tm_ops.overselect_bound(k, n)
    # level-set property: kept |x| >= dropped |x|
    kept_min = jnp.min(jnp.where(mask_k, jnp.abs(x.astype(jnp.float32)),
                                 jnp.inf))
    drop_max = jnp.max(jnp.where(mask_k, -jnp.inf,
                                 jnp.abs(x.astype(jnp.float32))))
    assert float(kept_min) >= float(drop_max) - 1e-6


@pytest.mark.parametrize("shape", [(8192,), (50_000,), (8, 4096)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_ssm_apply_matches_ref(shape, dtype):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    dw, dm, dv = (jax.random.normal(k, shape).astype(dtype) for k in keys)
    tau = jnp.float32(0.7)
    out_k = sa_ops.ssm_apply(tau, dw, dm, dv)
    out_r = ssm_apply_ref(tau, dw, dm, dv)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("with_residual", [False, True])
@pytest.mark.parametrize("value_dtype", [None, "bfloat16"])
def test_ssm_apply_ef_matches_ref(with_residual, value_dtype):
    from repro.kernels.ssm_apply.ref import ssm_apply_ef_ref
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    dw, dm, dv, score = (jax.random.normal(k, (50_000,)) for k in keys)
    tau = jnp.float32(0.9)
    out_k = sa_ops.ssm_apply_ef(tau, dw, dm, dv, score,
                                with_residual=with_residual,
                                value_dtype=value_dtype)
    out_r = ssm_apply_ef_ref(tau, dw, dm, dv, score,
                             with_residual=with_residual,
                             value_dtype=value_dtype)
    assert len(out_k) == (4 if with_residual else 3)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_pipeline_equals_algorithm():
    """topk_mask kernel + ssm_apply == the core sparsify path semantics."""
    n, alpha = 30_000, 0.05
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    dw, dm, dv = (jax.random.normal(k, (n,)) for k in keys)
    k = max(1, int(alpha * n))
    mask, tau, _ = tm_ops.topk_mask_kernel(dw, k)
    sw, sm, sv = sa_ops.ssm_apply(tau, dw, dm, dv)
    assert bool(jnp.all((sw != 0) == mask))
    assert bool(jnp.all(jnp.where(mask, dm, 0) == sm))
    assert bool(jnp.all(jnp.where(mask, dv, 0) == sv))


# --- packed cohort kernels (kernels/packed_topk) ---------------------------

PACKED_SHAPES = ((37,), (3, 5, 7), (8, 1024), (2000,), (50_000,))


def _packed_fixture(seed, dtype, groups=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(PACKED_SHAPES))
    leaves = [jax.random.normal(k, s).astype(dtype)
              for k, s in zip(keys, PACKED_SHAPES)]
    layout = S.plan_packed_layout(leaves, groups)
    return layout, leaves


def _select_inputs_ref(layout, leaves, xp, alpha=0.05):
    """taus2/ks/ns through the REF histogram, so kernel-vs-ref apply
    comparisons share identical prefetch operands."""
    ks = jnp.asarray([S.k_for(n, alpha) for n in layout.seg_sizes],
                     jnp.float32)
    ns = jnp.asarray(layout.seg_sizes, jnp.float32)
    absmax = S._segment_absmax(layout, leaves)
    edges = jnp.stack([log2_taus(a) for a in absmax])
    c1 = packed_hist_ref(xp, layout.seg_ids, edges)
    return refine_taus(c1, edges, absmax, ks), ks, ns, edges


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("scope", ["per_tensor", "global"])
def test_packed_hist_kernel_matches_ref(dtype, scope):
    groups = None if scope == "per_tensor" else [0] * len(PACKED_SHAPES)
    layout, leaves = _packed_fixture(7, dtype, groups)
    xp = layout.pack(leaves)
    _, _, _, edges = _select_inputs_ref(layout, leaves, xp)
    c_k = pk_ops.packed_hist_kernel(xp, layout.seg_ids, edges)
    c_r = packed_hist_ref(xp, layout.seg_ids, edges)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


@pytest.mark.parametrize("with_residual", [False, True])
@pytest.mark.parametrize("value_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("has_score", [False, True])
def test_packed_apply_ef_matches_ref(with_residual, value_dtype, has_score):
    layout, w_leaves = _packed_fixture(8, jnp.float32)
    _, m_leaves = _packed_fixture(9, jnp.float32)
    _, v_leaves = _packed_fixture(10, jnp.float32)
    wp, mp, vp = (layout.pack(ls) for ls in (w_leaves, m_leaves, v_leaves))
    if has_score:
        _, s_leaves = _packed_fixture(11, jnp.float32)
        sp, score_leaves = layout.pack(s_leaves), s_leaves
    else:
        sp, score_leaves = None, w_leaves
    taus2, ks, ns, _ = _select_inputs_ref(
        layout, score_leaves, wp if sp is None else sp)
    out_k = pk_ops.packed_apply_ef(taus2, layout.seg_ids, ks, ns,
                                   wp, mp, vp, sp,
                                   with_residual=with_residual,
                                   value_dtype=value_dtype)
    out_r = packed_apply_ef_ref(taus2, layout.seg_ids, ks, ns,
                                (wp, mp, vp), sp,
                                with_residual=with_residual,
                                value_dtype=value_dtype)
    assert len(out_k) == len(out_r) == (6 if with_residual else 5)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", DTYPES)
def test_packed_mask_apply_matches_ref(dtype):
    # independent-compress shape: one buffer, one tau segment per leaf
    layout, leaves = _packed_fixture(12, dtype)
    xp = layout.pack(leaves)
    taus2, ks, ns, _ = _select_inputs_ref(layout, leaves, xp)
    out_k = pk_ops.packed_mask_apply(taus2, layout.seg_ids, ks, ns, xp,
                                     value_dtype="bfloat16")
    out_r = packed_mask_apply_ref(taus2, layout.seg_ids, ks, ns, xp,
                                  value_dtype="bfloat16")
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_tau_equals_perleaf_tau():
    """The hinge of the whole packed design: each segment's tau (and
    kept count) is BITWISE the per-leaf 3-pass select_tau_kernel's."""
    layout, leaves = _packed_fixture(13, jnp.float32)
    xp = layout.pack(leaves)
    taus2, ks, ns, _ = _select_inputs_ref(layout, leaves, xp)
    outs = pk_ops.packed_mask_apply(taus2, layout.seg_ids, ks, ns, xp)
    taus, cnts = outs[-2][:, 0], outs[-1][:, 0]
    for i, leaf in enumerate(leaves):
        tau_i, cnt_i = tm_ops.select_tau_kernel(
            leaf, S.k_for(leaf.size, 0.05))
        assert float(taus[i]) == float(tau_i), f"leaf {i} tau"
        assert float(cnts[i]) == float(cnt_i), f"leaf {i} count"


def test_fused_adam_in_optimizer_loop():
    """use_kernel=True path of adam_step converges like the jnp path."""
    from repro.optim import adam_init, adam_step
    h = AdamHyper(lr=0.05)
    w_true = jax.random.normal(jax.random.PRNGKey(4), (9000,))

    def run(use_kernel):
        w = {"p": jnp.zeros((9000,))}
        st = adam_init(w)
        for _ in range(20):
            g = jax.tree.map(lambda x: x - w_true, w)
            w, st = adam_step(w, g, st, h, use_kernel=use_kernel)
        return w["p"]

    a, b = run(False), run(True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# wirepack: word-level pack/unpack parity (kernel vs oracle, bitwise)
# ---------------------------------------------------------------------------

from repro.kernels.wirepack import ops as wp_ops
from repro.kernels.wirepack.ref import (pack_bbit_ref, pack_mask_bits_ref,
                                        pack_sign_scale_ref, pack_words_ref,
                                        unpack_bbit_ref,
                                        unpack_mask_bits_ref,
                                        unpack_sign_scale_ref,
                                        unpack_words_ref)
from repro.kernels.wirepack.wirepack import (pack_words_2d, unpack_words_2d)

_WP_ROWS = [32, 96]  # row-group quantum is 32; cover multi-group grids


@pytest.mark.parametrize("rows", _WP_ROWS)
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_wirepack_words_kernel_matches_ref(rows, bits):
    """The one kernel pair under everything: (rows,128) codes <->
    uint32 words, bitwise against the jnp shift/mask oracle."""
    codes = jax.random.randint(jax.random.PRNGKey(bits * 100 + rows),
                               (rows, 128), 0, 1 << bits, jnp.int32)
    words = pack_words_2d(codes, bits=bits, interpret=True)
    assert words.dtype == jnp.uint32
    assert words.shape == (rows * bits // 32, 128)
    assert bool(jnp.all(words == pack_words_ref(codes, bits)))
    back = unpack_words_2d(words, bits=bits, interpret=True)
    assert bool(jnp.all(back == codes))
    assert bool(jnp.all(unpack_words_ref(words, bits) == codes))


@pytest.mark.parametrize("rows", _WP_ROWS)
def test_wirepack_mask_bits_matches_ref(rows):
    sup = (jax.random.uniform(jax.random.PRNGKey(rows), (rows, 128))
           < 0.3).astype(jnp.int32)
    words = wp_ops.pack_mask_bits(sup)
    assert bool(jnp.all(words == pack_mask_bits_ref(sup)))
    assert bool(jnp.all(unpack_mask_bits_ref(words) == sup))


@pytest.mark.parametrize("rows", _WP_ROWS)
def test_wirepack_sign_scale_matches_ref(rows):
    """Exact on sign_quant carriers: blocks are two-valued +-scale, so
    the decode is bitwise the carrier."""
    from repro.core import quantize
    x = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows * 128,))
    carrier = quantize.sign_quant(x, block=1024).reshape(rows, 128)
    wk, sk = wp_ops.pack_sign_scale(carrier)
    wr, sr = pack_sign_scale_ref(carrier)
    assert bool(jnp.all(wk == wr)) and bool(jnp.all(sk == sr))
    out_k = wp_ops.unpack_sign_scale(wk, sk)
    out_r = unpack_sign_scale_ref(wr, sr)
    assert bool(jnp.all(out_k == carrier))
    assert bool(jnp.all(out_r == carrier))


@pytest.mark.parametrize("rows", _WP_ROWS)
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_wirepack_bbit_matches_ref(rows, bits):
    qmax = (1 << (bits - 1)) - 1
    codes = jax.random.randint(jax.random.PRNGKey(bits * 7 + rows),
                               (rows, 128), -qmax, qmax + 1, jnp.int32)
    wk = wp_ops.pack_bbit(codes, bits)
    wr = pack_bbit_ref(codes, bits)
    assert bool(jnp.all(wk == wr))
    assert bool(jnp.all(wp_ops.unpack_bbit(wk, bits) == codes))
    assert bool(jnp.all(unpack_bbit_ref(wr, bits) == codes))


# ---------------------------------------------------------------------------
# wirepack: tile-local decode of mask value streams (kernel vs oracle vs
# the wire's jnp decode, bitwise)
# ---------------------------------------------------------------------------

from repro.core import wire
from repro.kernels.wirepack.ref import expand_mask_values_ref


def _odd_values(n, seed):
    """(n,) f32 stream holding -0.0 and subnormals beside normals."""
    v = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)
    i = jnp.arange(n)
    v = jnp.where(i % 7 == 3, jnp.float32(-0.0), v)
    v = jnp.where(i % 11 == 5, jnp.float32(1e-40), v)
    return jnp.where(i % 13 == 6, jnp.float32(-2.5e-39), v)


def _support(case):
    """(rows, 128) 0/1 support and the stream capacity of each case."""
    u = lambda rows, seed: jax.random.uniform(jax.random.PRNGKey(seed),
                                              (rows, 128))
    if case == "empty":
        return jnp.zeros((256, 128), jnp.int32), 5
    if case == "full":                  # two blocks, the second partial
        return jnp.ones((288, 128), jnp.int32), 288 * 128
    if case == "sparse_5pct":
        sup = (u(1024, 1) < 0.05).astype(jnp.int32)
        return sup, int(sup.sum())
    if case == "over_capacity":         # later blocks start past it
        sup = (u(1024, 2) < 0.3).astype(jnp.int32)
        return sup, int(sup.sum()) // 2 + 3
    if case == "single_block":          # unused stream tail
        sup = (u(256, 3) < 0.5).astype(jnp.int32)
        return sup, int(sup.sum()) + 100
    # block 0 ships 2047 slots, so block 1 starts at offset 1023 of its
    # aligned window and, full, runs to the window's last tile; block 2
    # straddles 128-lane rows and tiles at random
    flat = jnp.arange(256 * 128)
    first = (flat < 2047).astype(jnp.int32).reshape(256, 128)
    rest = (u(256, 4) < 0.4).astype(jnp.int32)
    sup = jnp.concatenate([first, jnp.ones((256, 128), jnp.int32), rest])
    return sup, int(sup.sum()) - 5


_EXPAND_CASES = ["empty", "full", "sparse_5pct", "over_capacity",
                 "single_block", "straddle"]


def _bits_of(x):
    return np.asarray(x).view(np.int32)


@pytest.mark.parametrize("n_streams", [1, 3])
@pytest.mark.parametrize("case", _EXPAND_CASES)
def test_wirepack_expand_matches_ref_and_wire(case, n_streams):
    """The tile-local expand is bitwise the global cumsum + take decode
    (the oracle) and the wire's own jnp ``_expand``: capacity overflow
    and slots off the support decode to +0.0, -0.0 and subnormals
    survive."""
    sup, cap = _support(case)
    words = pack_mask_bits_ref(sup)
    streams = tuple(_odd_values(cap, s) for s in range(n_streams))
    got = wp_ops.expand_mask_values(words, streams)
    want = expand_mask_values_ref(words, streams)
    flat = sup.reshape(-1) == 1
    pos = wire._support_positions(flat)
    assert len(got) == n_streams
    for g, w, v in zip(got, want, streams):
        assert g.shape == sup.shape and g.dtype == jnp.float32
        np.testing.assert_array_equal(_bits_of(g), _bits_of(w))
        np.testing.assert_array_equal(
            _bits_of(g), _bits_of(wire._expand(flat, pos, v, sup.shape)))
    if case == "over_capacity":
        assert bool(jnp.all(jnp.where(pos >= cap, got[0].reshape(-1), 0)
                            == 0))


def test_wirepack_expand_block_starts():
    """Block starts are the exclusive prefix of per-tile popcounts."""
    from repro.kernels.wirepack.expand import block_starts
    sup, _ = _support("straddle")
    words = pack_mask_bits_ref(sup)
    per = sup.reshape(3, -1).sum(axis=1)
    assert block_starts(words).tolist() == [0, int(per[0]),
                                            int(per[0] + per[1])]

