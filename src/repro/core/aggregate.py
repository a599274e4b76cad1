"""Server-side aggregation of per-client deltas, in two HLO-visible forms.

``dense``          — weighted sum over the client axis of dense (masked)
                     deltas.  When the client axis is sharded over mesh axes
                     this lowers to an ALL-REDUCE of the full model: the
                     FedAdam baseline's uplink, ~2*d*q bytes/link.
``sparse_gather``  — per client, pack the wire representation — a uint32
                     support bitmap (ONE bitmap for all three tensors —
                     the SSM alignment!) + the k kept values — and
                     ALL-GATHER it; every client then replays the server
                     fold locally.  Collective bytes drop from O(d*q) to
                     O(N*(d/8 + 3kq/8)) — the paper's Section-IV uplink
                     saving realized on ICI, byte-for-byte the reported
                     ``uplink_bits`` (core/wire.py).

Napkin math (per link, bf16 values, int32 indices, alpha=0.05, N=16):
  dense all-reduce of 3 tensors : ~2 * 3d * 2B       = 12 d bytes
  SSM sparse all-gather         : 16 * 0.05d * (3*2+4)B = 8 d bytes
  Top (3 index sets)            : 16 * 0.05d * 3*(2+4)B = 14.4 d bytes
i.e. on a 16-client axis the SHARED mask is exactly what keeps the sparse
transport under the dense baseline — FedAdam-Top's independent masks are
*worse* than dense at this (alpha, N).  With N=2 pod-clients the SSM gather
is ~12x under dense.  (Recorded in EXPERIMENTS.md §Transport.)

Entry point for the round: ``packed_gather_sum`` dispatches on the
compressor's ``transport`` tag (docs/compressors.md), so new compressors
ride the sparse transport without edits here.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import sparsify as S
from repro.core import stages
from repro.kernels.topk_mask.ops import overselect_bound
from repro.sharding import hint

_F32 = jnp.float32


@stages.scoped(stages.FOLD)
def dense_weighted_sum(tree_c, weights):
    """tree_c: leaves (C, ...); returns weighted sum over C."""
    return jax.tree.map(
        lambda x: jnp.tensordot(weights.astype(_F32), x.astype(_F32),
                                axes=(0, 0)), tree_c)


@stages.scoped(stages.FOLD)
def ordered_weighted_sum(tree_c, weights):
    """Weighted sum over the leading client axis with ``round_scan``'s
    exact accumulation order and arithmetic (``acc + w * x.astype(f32)``,
    client 0 first), so the mesh driver's dense aggregation is
    bit-identical to the scan reference (tests/test_fed_equivalence.py).
    The buffered-async driver's server step (core/async_fed.py) runs
    its K-update buffer through this same fold in arrival order, which
    is what makes its zero-churn degenerate config bit-identical to the
    sync round too.  O(C) sequential adds — the reference/debug
    aggregation; the production uplink is the sparse shard_map
    transport."""
    zero = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], _F32), tree_c)

    def body(acc, xs):
        x, w = xs
        return jax.tree.map(
            lambda a, y: a + w * y.astype(_F32), acc, x), 0.0

    acc, _ = lax.scan(body, zero, (tree_c, weights))
    return acc


def _to_blocks(x_c, n):
    """(C, n) -> (C, nb, B) zero-padded; B per core/sparsify.BLOCK."""
    B = S.BLOCK
    C = x_c.shape[0]
    nb = -(-n // B)
    pad = nb * B - n
    return jnp.pad(x_c, ((0, 0), (0, pad))).reshape(C, nb, B), nb, B


def _capacity(n, B, alpha):
    """Per-block packed capacity: threshold masks over-select by ties/bin
    width, so size the pack for the kernel contract's worst case —
    ``k + overselect_bound(k)`` (kernels/topk_mask/ops.py, the single
    source of truth; see docs/kernels.md).  Overflow beyond capacity is
    dropped and accounted — reported by fed metrics."""
    size = B if n > B else n
    base = S.k_for(size, alpha)
    return min(size, base + overselect_bound(base))


@stages.scoped(stages.WIRE_ENCODE)
def _pack(x_c, n, alpha, *, sort_free: bool = True):
    """Pack the nonzeros of masked dense deltas into a fixed-capacity COO.

    x_c: (C, n) masked dense -> (vals (C, nb, kb), idx (C, nb, kb) int32
    block-local).  sort_free=True (production): prefix-sum position
    assignment — O(n), no sort temps.  sort_free=False: exact |.| top-k
    per block (sort-based; small models / tests)."""
    xb, nb, B = _to_blocks(x_c, n)
    C = xb.shape[0]
    if not sort_free:
        kb = S.k_for(B, alpha) if n > B else S.k_for(n, alpha)
        _, idx = lax.top_k(jnp.abs(xb.astype(_F32)), kb)
        vals = jnp.take_along_axis(xb, idx, axis=2)
        return vals, idx, jnp.ones(vals.shape, bool)
    kb = _capacity(n, B, alpha)
    m = xb != 0
    pos = jnp.cumsum(m.astype(jnp.int32), axis=-1) - 1        # (C, nb, B)
    keep = m & (pos < kb)
    dst = jnp.where(keep, pos, kb)                            # kb = drop slot
    src_idx = jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[None, None, :], xb.shape)
    ci = jnp.broadcast_to(jnp.arange(C)[:, None, None], xb.shape)
    ri = jnp.broadcast_to(jnp.arange(nb)[None, :, None], xb.shape)
    vals = jnp.zeros((C, nb, kb + 1), xb.dtype) \
        .at[ci, ri, dst].set(xb, mode="drop")[..., :kb]
    # store index+1 so empty capacity slots are detectable (idx_plus == 0)
    idx_plus = jnp.zeros((C, nb, kb + 1), jnp.int32) \
        .at[ci, ri, dst].set(src_idx + 1, mode="drop")[..., :kb]
    valid = idx_plus > 0
    idx = jnp.maximum(idx_plus - 1, 0)
    return vals, idx, valid


@stages.scoped(stages.FOLD)
def _scatter_weighted(vals, idx, valid, weights, n):
    """vals/idx/valid: (C, nb, kb) replicated; dense (n,) weighted sum."""
    C, nb, kb = vals.shape
    B = S.BLOCK if n > S.BLOCK else -(-n // nb)
    wv = vals.astype(_F32) * weights.astype(_F32)[:, None, None]
    wv = jnp.where(valid, wv, 0.0)
    rows = jnp.broadcast_to(jnp.arange(nb)[None, :, None], idx.shape)
    out = jnp.zeros((nb, B), _F32)
    out = out.at[rows.reshape(-1), idx.reshape(-1)].add(wv.reshape(-1))
    return out.reshape(-1)[:n]


def sparse_shared_gather_sum(sW_c, sM_c, sV_c, alpha, weights,
                             value_dtype=None, sort_free=True):
    """FedAdam-SSM transport: ONE index vector per tensor-leaf per client
    (from the shared mask), three value vectors.  All-gather the packed
    (3k values + k indices), scatter-add locally."""

    def leaf(w_c, m_c, v_c):
        C = w_c.shape[0]
        n = int(math.prod(w_c.shape[1:])) if w_c.ndim > 1 else 1
        # ONE index set from dW's mask (the shared mask), three value sets
        vw, idx, valid = _pack(w_c.reshape(C, n), n, alpha,
                               sort_free=sort_free)
        mf, _, _ = _to_blocks(m_c.reshape(C, n), n)
        vf, _, _ = _to_blocks(v_c.reshape(C, n), n)
        take = lambda t: jnp.take_along_axis(t, idx, axis=2)
        vm, vv = take(mf), take(vf)
        if value_dtype is not None:
            dt = jnp.dtype(value_dtype)
            vw, vm, vv = (t.astype(dt) for t in (vw, vm, vv))
        # the uplink: replicate the packed representation (all-gather)
        idx = hint(idx)
        valid = hint(valid)
        vw, vm, vv = map(hint, (vw, vm, vv))
        shape = w_c.shape[1:]
        return (
            _scatter_weighted(vw, idx, valid, weights, n).reshape(shape),
            _scatter_weighted(vm, idx, valid, weights, n).reshape(shape),
            _scatter_weighted(vv, idx, valid, weights, n).reshape(shape),
        )

    # explicit flatten/unflatten: the tree may itself contain tuples
    lw, treedef = jax.tree_util.tree_flatten(sW_c)
    lm = treedef.flatten_up_to(sM_c)
    lv = treedef.flatten_up_to(sV_c)
    outs = [leaf(w, m, v) for w, m, v in zip(lw, lm, lv)]
    return (treedef.unflatten([o[0] for o in outs]),
            treedef.unflatten([o[1] for o in outs]),
            treedef.unflatten([o[2] for o in outs]))


# ---------------------------------------------------------------------------
# shard_map realization — the production path
# ---------------------------------------------------------------------------
#
# In global-view jnp, GSPMD turns the pack's scatter into replicated giant
# index tensors (observed: s32[16,1080,1M,3] all-gathers).  Under shard_map
# the pack is a *local* O(n_loc) program per device and the ONLY collective
# is the explicit all-gather of the WIRE representation — a uint32 support
# bitmap (1 bit per local slot, core/wire.py word convention) plus the
# first-kb compacted f32 value stream.  No index tensor crosses the links:
# the receiver recomputes positions from the bitmap by prefix sum, so the
# gathered bytes are exactly the Section-IV count (d bits of mask + k q-bit
# values per client), matching ``8 * WirePayload.nbytes``.  Each (data-row,
# model-col) device packs its own client's slice of its own model shard;
# after the gather over the client axes, every device replays the server
# fold into its local dense shard: no model-axis communication at all.


@stages.scoped(stages.WIRE_ENCODE)
def _local_pack(wf, alpha):
    """wf: (n_loc,) masked dense, device-local.  -> (words, pos, keep, kb):
    the support bitmap word-packed to uint32 + the compaction plan
    (prefix-sum positions, keep = supported and under capacity).
    Capacity kb per the over-selection contract, as in _capacity above."""
    from repro.core import wire
    n = wf.shape[0]
    k = S.k_for(n, alpha)
    kb = min(n, k + overselect_bound(k))
    m = wf != 0
    words = wire.pack_bits_1d(m)
    pos = jnp.cumsum(m.astype(jnp.int32)) - 1
    keep = m & (pos < kb)
    return words, pos, keep, kb


@stages.scoped(stages.WIRE_ENCODE)
def _compact_vals(xf, pos, keep, kb):
    """First-kb compaction of ``xf`` onto the support plan (slot kb is
    the overflow drop slot, sliced away)."""
    dst = jnp.where(keep, pos, kb)
    return jnp.zeros((kb + 1,), _F32).at[dst].set(
        xf.astype(_F32), mode="drop")[:kb]


@stages.scoped(stages.WIRE_DECODE)
def _expand_vals(words, vals, n_loc):
    """Inverse of the (bitmap, stream) pack: (nw,) uint32 words + (kb,)
    values -> (n_loc,) f32 dense (capacity-overflow slots decode to 0)."""
    from repro.core import wire
    sup = wire.unpack_bits_1d(words, n_loc) == 1
    pos = jnp.cumsum(sup.astype(jnp.int32)) - 1
    kb = vals.shape[0]
    taken = jnp.take(vals.astype(_F32), jnp.clip(pos, 0, kb - 1))
    return jnp.where(sup & (pos < kb), taken, 0.0)


def _gathered_decode_sum(words_g, vals_g, weights, n_loc):
    """words_g (C, nw) + vals_g (C, kb) post-gather -> (n_loc,) f32
    weighted sum, folded in client order with ``round_scan``'s exact
    arithmetic (``acc + w * x``, client 0 first) so the mesh transport
    is bit-identical to the scan reference when nothing overflows."""
    def body(acc, xs):
        wrds, vals, wgt = xs
        x = _expand_vals(wrds, vals, n_loc)
        with jax.named_scope(stages.FOLD):
            return acc + wgt * x, 0.0

    acc, _ = lax.scan(body, jnp.zeros((n_loc,), _F32),
                      (words_g, vals_g, weights.astype(_F32)))
    return acc


def make_shardmap_sparse_aggregate(mesh, param_pspecs, client_axes, alpha,
                                   *, shared: bool = True,
                                   value_dtype=None):
    """Build the shard_map sparse-transport aggregation::

        agg(sW_c, sM_c, sV_c, weights)           -> (aW, aM, aV)
        agg(sW_c, sM_c, sV_c, weights, comp_err) -> (aW, aM, aV), new_err

    (weighted SUMS).  param_pspecs: pytree of PartitionSpec for the
    *unstacked* params; the client-stacked inputs get
    P(client_axes, *param_spec).

    ``comp_err`` (optional) is the per-shard error-feedback residual tree
    on dW (client-stacked, same treedef as the params), as carried by the
    shard_map round driver under ``client_state["comp"]["err"]``.  When
    given, values the fixed-capacity pack DROPS from the wire (capacity =
    k + overselect_bound(k) per device shard; overflow beyond it never
    reaches the server) are added back into the residual, so transport
    drop obeys the same error-feedback semantics as mask drop instead of
    silently vanishing.  When nothing overflows the residual is returned
    bit-unchanged."""
    caxes = tuple(client_axes)
    cax_entry = caxes if len(caxes) > 1 else caxes[0]

    leaves_spec, treedef = jax.tree_util.tree_flatten(
        param_pspecs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    stacked_spec = treedef.unflatten(
        [PartitionSpec(cax_entry, *sp) for sp in leaves_spec])
    wspec = PartitionSpec(None)
    vdt = jnp.dtype(value_dtype) if value_dtype else None

    def body(w_tree, m_tree, v_tree, weights, err_tree):
        lw = jax.tree_util.tree_leaves(w_tree)
        lm = jax.tree_util.tree_leaves(m_tree)
        lv = jax.tree_util.tree_leaves(v_tree)
        lerr = jax.tree_util.tree_leaves(err_tree)
        has_err = len(lerr) > 0    # list emptiness: static at trace time
        outs_w, outs_m, outs_v, outs_err = [], [], [], []
        for i, (w, m, v) in enumerate(zip(lw, lm, lv)):
            c_loc = w.shape[0]
            assert c_loc == 1, "one spatial client per device row"
            shape_loc = w.shape[1:]
            n_loc = 1
            for sdim in shape_loc:
                n_loc *= sdim
            wf = w.reshape(n_loc)
            words, pos, keep, kb = _local_pack(wf, alpha)
            vals_w = _compact_vals(wf, pos, keep, kb)
            vals_m = _compact_vals(m.reshape(n_loc), pos, keep, kb)
            vals_v = _compact_vals(v.reshape(n_loc), pos, keep, kb)
            if vdt is not None:
                vals_w = vals_w.astype(vdt)
                vals_m = vals_m.astype(vdt)
                vals_v = vals_v.astype(vdt)
            if has_err:
                # what the server actually receives for this client: the
                # (possibly wire-cast) value stream expanded back onto the
                # bitmap; the capacity-overflow remainder feeds the EF
                # residual
                kept = jnp.where(
                    keep, jnp.take(vals_w.astype(_F32),
                                   jnp.clip(pos, 0, kb - 1)), 0.0)
                err = lerr[i].reshape(n_loc)
                # drop first, then add: when nothing overflows the drop is
                # exactly 0.0 and the residual passes through bitwise
                drop = wf.astype(_F32) - kept
                new_err = (err.astype(_F32) + drop).astype(err.dtype)
                outs_err.append(new_err.reshape(lerr[i].shape))
            # THE UPLINK: all-gather bitmap words + value streams over the
            # client axes — the only arrays that cross the links
            gather = lambda t: _gather_clients(t, caxes)
            words_g = gather(words)
            outs_w.append(_gathered_decode_sum(
                words_g, gather(vals_w), weights, n_loc).reshape(shape_loc))
            if shared:
                # the SSM alignment: ONE bitmap describes all three streams
                outs_m.append(_gathered_decode_sum(
                    words_g, gather(vals_m), weights,
                    n_loc).reshape(shape_loc))
                outs_v.append(_gathered_decode_sum(
                    words_g, gather(vals_v), weights,
                    n_loc).reshape(shape_loc))
            else:
                # independent masks: m and v ship their own bitmaps
                for src, sink in ((m, outs_m), (v, outs_v)):
                    sf = src.reshape(n_loc)
                    wds, ps, kp, cap = _local_pack(sf, alpha)
                    va = _compact_vals(sf, ps, kp, cap)
                    if vdt is not None:
                        va = va.astype(vdt)
                    sink.append(_gathered_decode_sum(
                        gather(wds), gather(va), weights,
                        n_loc).reshape(shape_loc))
        unf = lambda leaves: jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(w_tree), leaves)
        new_err_tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(err_tree), outs_err) \
            if has_err else None
        return unf(outs_w), unf(outs_m), unf(outs_v), new_err_tree

    def agg(sW_c, sM_c, sV_c, weights, comp_err=None):
        has_err = comp_err is not None
        err_spec = stacked_spec if has_err else None
        aW, aM, aV, new_err = jax.shard_map(
            body, mesh=mesh,
            in_specs=(stacked_spec, stacked_spec, stacked_spec, wspec,
                      err_spec),
            out_specs=(param_pspecs, param_pspecs, param_pspecs,
                       err_spec),
            check_vma=False,
        )(sW_c, sM_c, sV_c, weights, comp_err)
        if has_err:
            return (aW, aM, aV), new_err
        return aW, aM, aV

    return agg


def _gather_clients(x, caxes):
    """all_gather over the client mesh axes -> (C, *x.shape).  The gather
    order (axis-tuple order) matches the row-major client linearization of
    the batch sharding P(caxes, ...)."""
    name = caxes if len(caxes) > 1 else caxes[0]
    return jax.lax.all_gather(x, name, axis=0, tiled=False)


def wire_gather_sum(compressor, payload_c, like, weights):
    """Aggregate client-stacked :class:`~repro.core.wire.WirePayload`\\ s:
    replicate the payload arrays (THE uplink — only bit-packed words and
    compact f32 value/scale streams cross the client axis), then decode
    and fold in client order with ``round_scan``'s exact arithmetic, so
    the vmap wire transport is bit-identical to the scan reference.
    ``like`` is the params template the decoder shapes against."""
    payload_c = jax.tree.map(hint, payload_c)
    zero = lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, _F32), like)
    acc0 = (zero(), zero(), zero())

    def body(acc, xs):
        payload, wgt = xs
        sW, sM, sV = compressor.unpack_wire(payload, like)
        add = lambda a, s: jax.tree.map(
            lambda x, y: x + wgt * y.astype(_F32), a, s)
        aW, aM, aV = acc
        with jax.named_scope(stages.FOLD):
            return (add(aW, sW), add(aM, sM), add(aV, sV)), 0.0

    (aW, aM, aV), _ = lax.scan(body, acc0,
                               (payload_c, weights.astype(_F32)))
    return aW, aM, aV


def packed_gather_sum(compressor, sW_c, sM_c, sV_c, weights, *, alpha,
                      value_dtype=None, sort_free=True,
                      payload_c=None, like=None):
    """Aggregate any compressor's packed representation.

    With ``payload_c`` (client-stacked WirePayloads from
    ``make_client_step(..., emit="wire")``) the transport is the wire
    format itself: :func:`wire_gather_sum` moves the bit-packed words
    across the client axis — the bytes ARE the reported
    ``8 * WirePayload.nbytes`` — for every wire-enabled scheme, sparse
    and quantized alike.

    Otherwise the legacy dense-carrier paths apply, keyed on the
    ``transport`` tag (see core/compressors and docs/compressors.md):

    * ``shared_sparse``      — one index set per client-leaf, three value
                               sets (FedAdam-SSM family).
    * ``independent_sparse`` — three (values, indices) packs per leaf
                               (FedAdam-Top).
    * anything else          — dense weighted sum (identity / quantized
                               carriers have no sparse structure to pack).

    New compressors therefore get the sparse all-gather path for free by
    declaring the matching transport (or the wire path by declaring a
    ``wire_layout``).
    """
    if payload_c is not None:
        return wire_gather_sum(compressor, payload_c, like, weights)
    t = getattr(compressor, "transport", "dense")
    if t == "shared_sparse":
        return sparse_shared_gather_sum(sW_c, sM_c, sV_c, alpha, weights,
                                        value_dtype, sort_free)
    if t == "independent_sparse":
        agg = lambda tr: sparse_independent_gather_sum(
            tr, alpha, weights, value_dtype, sort_free)
        return agg(sW_c), agg(sM_c), agg(sV_c)
    return (dense_weighted_sum(sW_c, weights),
            dense_weighted_sum(sM_c, weights),
            dense_weighted_sum(sV_c, weights))


def sparse_independent_gather_sum(tree_c, alpha, weights, value_dtype=None,
                                  sort_free=True):
    """FedAdam-Top transport: per-tensor independent (values, indices)."""

    def leaf(x_c):
        C = x_c.shape[0]
        n = int(math.prod(x_c.shape[1:])) if x_c.ndim > 1 else 1
        vals, idx, valid = _pack(x_c.reshape(C, n), n, alpha,
                                 sort_free=sort_free)
        if value_dtype is not None:
            vals = vals.astype(jnp.dtype(value_dtype))
        vals = hint(vals)
        idx = hint(idx)
        valid = hint(valid)
        return _scatter_weighted(vals, idx, valid, weights, n) \
            .reshape(x_c.shape[1:])

    return jax.tree.map(leaf, tree_c)
