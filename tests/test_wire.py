"""The bit-packed wire format (core/wire.py): round-trips, measured
bytes == reported bits, and transport equivalences.

The contract under test, per registered compressor:

* ``8 * WirePayload.nbytes == Compressor.wire_bits_per_client(sizes)
  == comm.bits_for(algo, ..., sizes=...)`` — the metric IS the payload.
* decode(encode(carriers)) is bitwise the dense carriers for mask and
  sign schemes, and bitwise the quantizer's own reconstruction for the
  b-bit scheme.
* the vmap wire transport (packed words crossing the client axis)
  aggregates exactly like the scan reference fold.

Property tests ride tests/_propcheck.py (hypothesis when installed,
seeded deterministic fallback otherwise): random leaf shapes with odd
tails exercise the 1024-element block padding and the 4096-element
word-group alignment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.core import FedConfig, comm, compressors, fed_init, make_fl_round
from repro.core import aggregate, quantize, sparsify as S, wire
from repro.core.compressors import Deltas
from repro.optim import AdamHyper

_F32 = jnp.float32


def _tree(shapes, seed=0, scale=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {f"t{i}": jax.random.normal(k, s) * scale
            for i, (k, s) in enumerate(zip(keys, shapes))}


def _sizes(tree):
    return tuple(x.size for x in jax.tree.leaves(tree))


def _biteq(ta, tb):
    la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
    assert len(la) == len(lb)
    return all(bool(jnp.all(a == b)) for a, b in zip(la, lb))


def _exact_mask(tree, alpha):
    return jax.tree.map(
        lambda x: S.topk_mask_exact(x, S.k_for(x.size, alpha)), tree)


@st.composite
def _shapes(draw):
    """1-3 leaves, 1-D or 2-D, sizes with odd tails (1..~1800)."""
    n = draw(st.integers(1, 3))
    out = []
    for _ in range(n):
        if draw(st.integers(0, 1)):
            out.append((draw(st.integers(1, 1800)),))
        else:
            out.append((draw(st.integers(1, 60)),
                        draw(st.integers(1, 30))))
    return tuple(out)


# ---------------------------------------------------------------------------
# Round-trip properties (random shapes, odd tails)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(_shapes(), st.floats(0.05, 0.8))
def test_shared_mask_wire_roundtrip(shapes, alpha):
    dW, dM, dV = (_tree(shapes, seed=s) for s in (0, 1, 2))
    mask = _exact_mask(dW, alpha)
    sp = lambda t: jax.tree.map(lambda x, m: x * m, t, mask)
    sW, sM, sV = sp(dW), sp(dM), sp(dV)
    cap = wire.mask_leaf_capacities(_sizes(dW), alpha)
    payload, _ = wire.pack_shared_mask(sW, sM, sV, cap)
    rW, rM, rV = wire.unpack_shared_mask(payload, sW)
    assert _biteq((rW, rM, rV), (sW, sM, sV))
    # idempotence: re-encoding the decoded triple reproduces the payload
    # (the async driver's re-materialization relies on this)
    again, _ = wire.pack_shared_mask(rW, rM, rV, cap)
    assert _biteq(again, payload)


@settings(max_examples=10, deadline=None)
@given(_shapes(), st.floats(0.05, 0.8))
def test_independent_mask_wire_roundtrip(shapes, alpha):
    trees = [_tree(shapes, seed=s) for s in (3, 4, 5)]
    sp = [jax.tree.map(lambda x, m: x * m, t, _exact_mask(t, alpha))
          for t in trees]
    cap = wire.mask_leaf_capacities(_sizes(trees[0]), alpha)
    payload, _ = wire.pack_independent_mask(*sp, cap)
    out = wire.unpack_independent_mask(payload, sp[0])
    assert _biteq(out, tuple(sp))


def _mask_payload(layout, alpha=0.05):
    """A mask payload over leaves spanning several word tiles, with a
    threshold-style over-selected leaf so one stream runs full."""
    x = jax.random.normal(jax.random.PRNGKey(21), (70000,))
    y = jax.random.normal(jax.random.PRNGKey(22), (1500, 9))
    exact = lambda v: v * S.topk_mask_exact(v, S.k_for(v.size, alpha))
    sW = {"a": exact(x), "b": exact(y), "c": jnp.full((3000,), -0.0)}
    sM = {"a": 2.0 * exact(x), "b": exact(jnp.roll(y, 3)),
          "c": jnp.full((3000,), 0.25)}
    sV = jax.tree.map(lambda t: 3.0 * t, sW)
    caps = wire.mask_leaf_capacities(_sizes(sW), alpha, exact_topk=False)
    pack = (wire.pack_shared_mask if layout == "shared"
            else wire.pack_independent_mask)
    payload, _ = pack(sW, sM, sV, caps)
    return payload, sW


@pytest.mark.parametrize("layout", ["shared", "independent"])
def test_mask_decode_kernel_equals_jnp_path(layout, monkeypatch):
    """The tile-local Pallas decode (interpret mode here) is bitwise the
    jnp ``_expand`` decode of the same payload, shared- and
    independent-mask layouts alike."""
    unpack = (wire.unpack_shared_mask if layout == "shared"
              else wire.unpack_independent_mask)
    monkeypatch.setenv(S.SPARSIFY_BACKEND_ENV, "reference")
    payload, like = _mask_payload(layout)
    want = unpack(payload, like)
    monkeypatch.setenv(S.SPARSIFY_BACKEND_ENV, "kernel")
    got = unpack(payload, like)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))
    assert any(bool(jnp.any(leaf != 0)) for leaf in jax.tree.leaves(got))


@pytest.mark.parametrize("layout", ["shared", "independent"])
def test_mask_overflow_is_capped_per_leaf_like_the_mesh_transport(layout):
    """A threshold mask that over-selects a leaf past its capacity (tied
    magnitudes, as in bf16 deltas) loses the overflow inside that leaf
    only: the next leaf arrives whole, the payload keeps its size, and
    each decoded leaf equals what the shard_map bitmap transport
    (``aggregate._local_pack``) delivers for it."""
    alpha = 0.05
    tied = jnp.full((4096,), 0.5, _F32)               # every entry selected
    x = jax.random.normal(jax.random.PRNGKey(0), (3000,))
    sW = {"a": tied, "b": x * S.topk_mask_exact(x, S.k_for(x.size, alpha))}
    sM = jax.tree.map(lambda t: 2.0 * t, sW)
    sV = jax.tree.map(lambda t: 3.0 * t, sW)
    sizes = _sizes(sW)
    caps = wire.mask_leaf_capacities(sizes, alpha, exact_topk=False)
    assert caps[0] < tied.size
    if layout == "shared":
        payload, _ = wire.pack_shared_mask(sW, sM, sV, caps)
        out = wire.unpack_shared_mask(payload, sW)
    else:
        payload, _ = wire.pack_independent_mask(sW, sM, sV, caps)
        out = wire.unpack_independent_mask(payload, sW)
    assert 8 * wire.payload_nbytes(payload) == wire.mask_wire_bits(
        sizes, alpha, exact_topk=False, shared=(layout == "shared"))
    for sent, got in zip((sW, sM, sV), out):
        assert bool(jnp.all(got["b"] == sent["b"]))
        kept = jnp.where(jnp.arange(tied.size) < caps[0], sent["a"], 0.0)
        assert bool(jnp.all(got["a"] == kept))
        for name, leaf in sent.items():
            words, pos, keep, kb = aggregate._local_pack(leaf, alpha)
            mesh = aggregate._expand_vals(
                words, aggregate._compact_vals(leaf, pos, keep, kb),
                leaf.size)
            assert bool(jnp.all(mesh == got[name])), name
    again, _ = (wire.pack_shared_mask if layout == "shared"
                else wire.pack_independent_mask)(*out, caps)
    assert _biteq(again, payload)


@settings(max_examples=10, deadline=None)
@given(_shapes())
def test_sign_wire_roundtrip(shapes):
    x = _tree(shapes, seed=6)
    q = quantize.tree_sign_quant(x, wire.SCALE_BLOCK)
    payload = wire.pack_sign(q)
    out = wire.unpack_sign(payload, q)
    assert _biteq(out, q)


@settings(max_examples=10, deadline=None)
@given(_shapes(), st.sampled_from([2, 4, 8]))
def test_bbit_wire_roundtrip(shapes, bits):
    x = _tree(shapes, seed=7)
    leaves, treedef = jax.tree_util.tree_flatten(x)
    enc = [quantize.uniform_encode(v, bits, wire.SCALE_BLOCK)
           for v in leaves]
    payload = wire.pack_bbit_codes([c for c, _ in enc],
                                   [s for _, s in enc], bits)
    out = wire.unpack_bbit_codes(payload, x, bits)
    # the wire reconstructs exactly what the quantizer reconstructs
    want = jax.tree_util.tree_unflatten(treedef, [
        quantize.uniform_quant(v, bits, wire.SCALE_BLOCK) for v in leaves])
    assert _biteq(out, want)


@settings(max_examples=10, deadline=None)
@given(_shapes())
def test_dense_wire_roundtrip(shapes):
    trees = tuple(_tree(shapes, seed=s) for s in (8, 9, 10))
    payload = wire.pack_dense(trees)
    out = wire.unpack_dense(payload, trees[0])
    assert _biteq(out, trees)
    assert 8 * wire.payload_nbytes(payload) == \
        wire.dense_wire_bits(_sizes(trees[0]), 3)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5000))
def test_pack_bits_1d_roundtrip(n):
    bits = (jax.random.uniform(jax.random.PRNGKey(n), (n,)) < 0.37)
    words = wire.pack_bits_1d(bits)
    assert words.dtype == jnp.uint32 and words.shape == (-(-n // 32),)
    back = wire.unpack_bits_1d(words, n)
    assert bool(jnp.all(back == bits.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# Measured bytes == reported bits, per registered compressor
# ---------------------------------------------------------------------------

_PARAMS_SHAPES = ((37, 5), (11,))


def _compress_once(algo, alpha=0.25):
    fed = FedConfig(algorithm=algo, alpha=alpha, n_clients=2)
    comp = compressors.make_compressor(fed)
    params = _tree(_PARAMS_SHAPES, seed=11, scale=0.1)
    state = comp.init_state(params)
    deltas = Deltas(_tree(_PARAMS_SHAPES, seed=12),
                    _tree(_PARAMS_SHAPES, seed=13),
                    _tree(_PARAMS_SHAPES, seed=14))
    packed, _, _ = comp.compress(deltas, state)
    return fed, comp, params, packed


@pytest.mark.parametrize("algo", compressors.available())
def test_measured_bits_equal_accounting(algo):
    """THE acceptance identity: 8 * payload.nbytes ==
    wire_bits_per_client == comm.bits_for(..., sizes=...)."""
    fed, comp, params, packed = _compress_once(algo)
    assert packed.wire is not None, f"{algo}: no wire payload at q=32"
    sizes = _sizes(params)
    wb = comp.wire_bits_per_client(sizes)
    assert wb is not None
    assert 8 * wire.payload_nbytes(packed.wire) == wb, algo
    d = sum(sizes)
    assert wb == comm.bits_for(algo, d, S.k_for(d, fed.alpha), 1, 32,
                               sizes=sizes, alpha=fed.alpha), algo


@pytest.mark.parametrize("algo", compressors.available())
def test_unpack_wire_matches_decompress(algo):
    """The wire round-trip reconstructs the dense carriers the legacy
    path would have shipped — bitwise, on every communicated plane."""
    _, comp, params, packed = _compress_once(algo)
    rec = comp.unpack_wire(packed.wire, params)
    dec = comp.decompress(packed)
    planes = {"mask_shared": ("W", "M", "V"),
              "mask_independent": ("W", "M", "V"),
              "sign": ("M",), "bbit": ("W",),
              "dense": ("W", "M", "V")[:getattr(comp, "n_tensors", 3)]}
    for p in planes[comp.wire_layout]:
        assert _biteq(getattr(rec, p), getattr(dec, p)), (algo, p)


def test_wire_bits_refused_off_contract():
    """Configs outside the layout constants get NO wire payload and an
    analytic-fallback metric instead of a silently wrong byte count."""
    fed = FedConfig(algorithm="fedadam_ssm", q_bits=16)
    comp = compressors.make_compressor(fed)
    assert comp.wire_bits_per_client((64,)) is None
    deltas = Deltas(*(_tree(((8, 8),), seed=i) for i in (1, 2, 3)))
    packed, _, _ = comp.compress(deltas, None)
    assert packed.wire is None
    with pytest.raises(ValueError):
        comm.bits_for("fedadam_ssm", 64, 3, 1, 16, sizes=(64,), alpha=0.05)


# ---------------------------------------------------------------------------
# Transport equivalences
# ---------------------------------------------------------------------------


def test_wire_gather_sum_matches_scan_fold():
    """The vmap wire transport's decode-fold is bitwise the scan
    reference accumulation of the decoded carriers."""
    fed = FedConfig(algorithm="fedadam_ssm", alpha=0.25, n_clients=3)
    comp = compressors.make_compressor(fed)
    params = _tree(_PARAMS_SHAPES, seed=15, scale=0.1)
    payloads, triples = [], []
    for c in range(3):
        deltas = Deltas(_tree(_PARAMS_SHAPES, seed=20 + c),
                        _tree(_PARAMS_SHAPES, seed=30 + c),
                        _tree(_PARAMS_SHAPES, seed=40 + c))
        packed, _, _ = comp.compress(deltas, None)
        payloads.append(packed.wire)
        triples.append(comp.unpack_wire(packed.wire, params))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
    weights = jnp.asarray([1.0, 2.0, 0.5], _F32)
    aW, aM, aV = aggregate.wire_gather_sum(comp, stacked, params, weights)
    for plane, want in zip(
            (aW, aM, aV),
            (aggregate.ordered_weighted_sum(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[t[i] for t in triples]), weights)
             for i in range(3))):
        assert _biteq(plane, want)


@pytest.mark.parametrize("algo", ["fedadam_ssm", "fedadam_top",
                                  "efficient_adam"])
def test_vmap_wire_transport_matches_scan(algo):
    """3 rounds, scan driver vs vmap driver over the wire transport
    (packed words crossing the client axis): same server state, same
    wire-exact uplink_bits."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1,
              "b": jnp.zeros((4,))}
    C = 4
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    ys = jnp.einsum("cbi,ij->cbj", xs,
                    jax.random.normal(jax.random.PRNGKey(2), (8, 4)))

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    def run(mode, agg):
        fed = FedConfig(algorithm=algo, alpha=0.3, local_epochs=2,
                        n_clients=C, adam=AdamHyper(lr=0.05),
                        client_mode=mode, aggregate=agg)
        rf = jax.jit(make_fl_round(fed, loss_fn))
        st = fed_init(fed, params)
        for _ in range(3):
            st, mets = rf(st, (xs, ys))
        return st, float(mets["uplink_bits"])

    st_s, bits_s = run("scan", "dense")
    st_w, bits_w = run("vmap", "sparse_gather")
    assert bits_s == bits_w
    sizes = tuple(x.size for x in jax.tree.leaves(params))
    comp = compressors.make_compressor(
        FedConfig(algorithm=algo, alpha=0.3, n_clients=C))
    assert bits_w == C * comp.wire_bits_per_client(sizes)
    for a, b in zip(jax.tree.leaves(st_s.W), jax.tree.leaves(st_w.W)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# Accounting boundary fix
# ---------------------------------------------------------------------------


def test_ceil_log2_boundaries():
    """d <= 1 needs ZERO index bits — the old max(2, d) clamp billed 1
    bit for single-slot index sets."""
    assert comm._ceil_log2(0) == 0
    assert comm._ceil_log2(1) == 0
    assert comm._ceil_log2(2) == 1
    assert comm._ceil_log2(3) == 2
    assert comm._ceil_log2(4) == 2
    assert comm._ceil_log2(5) == 3
    # the degenerate 1-element tree: index representation is pure values
    assert comm.bits_fedadam_ssm(1, 1, 1, q=32) == min(1 * (3 + 1), 3) * 32
