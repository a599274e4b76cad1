"""Top-k sparsification primitives (Definition 1 & 2 of the paper).

Two mask constructions:

* ``topk_mask_exact`` — scatter of the exact top-k indices (|mask| == k
  always; ties broken by index order).  O(d log d) sort-based; used for
  small models, tests and anywhere exactness matters.
* ``topk_mask_threshold`` — mask = |x| >= tau with tau chosen by the
  O(d)-per-pass bisection the ``topk_mask`` Pallas kernel implements;
  |mask| may exceed k by ties.  This is the production path for d ~ 1e9+.

Masks are computed per-tensor ("per_tensor" scope, k_i = ceil(alpha * n_i))
or over the concatenated flat model ("global" scope — the paper's exact
formulation; feasible when the model fits one host).

These are the primitives under the top-k compressors in
core/compressors/topk.py (see docs/compressors.md).

Backend dispatch
----------------
Threshold-mask construction and the fused shared-mask compress have two
interchangeable implementations: the streaming Pallas kernels
(kernels/topk_mask + kernels/ssm_apply + kernels/packed_topk) and the
pure-jnp references in this module.  :func:`resolve_backend` picks one —
``auto`` routes TPU to the kernels and everything else to the
references; a ``FedConfig``/compressor ``sparsify_backend`` field or the
``REPRO_SPARSIFY_BACKEND`` environment variable forces either
(``kernel`` off-TPU runs the kernels in Pallas interpret mode, which is
how CPU CI exercises them).

Packed cohort layer
-------------------
On the kernel path, :class:`PackedLayout` flattens every pytree leaf
into ONE (8, 128)-tile-aligned buffer so the whole-model compress costs
exactly TWO Pallas launches instead of 4 per leaf:
:func:`tree_shared_compress_packed` (shared mask, the default under
:func:`tree_shared_compress_fused`) and
:func:`tree_independent_compress_packed` (FedAdam-Top's three masks,
one buffer, per-stream tau segments).  Outputs are bit-identical to the
per-leaf path.  Rules, layout and launch accounting: docs/kernels.md.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree

from repro.core import stages
from repro.kernels.packed_topk.ops import (
    packed_apply_ef, packed_hist_kernel, packed_mask_apply)
from repro.kernels.packed_topk.packed_topk import (
    BLOCK_ELEMS as PACK_BLOCK_ELEMS, LANES as PACK_LANES)
from repro.kernels.packed_topk.ref import refine_taus
from repro.kernels.ssm_apply.ops import ssm_apply_ef
from repro.kernels.topk_mask.ops import select_tau_kernel, topk_mask_kernel
from repro.kernels.topk_mask.ref import log2_taus

_F32 = jnp.float32

#: Environment override for the sparsifier backend (see resolve_backend).
SPARSIFY_BACKEND_ENV = "REPRO_SPARSIFY_BACKEND"

_BACKENDS = ("auto", "kernel", "reference")


def resolve_backend(override: Optional[str] = None) -> str:
    """Resolve the sparsifier backend to ``kernel`` | ``reference``.

    Priority: explicit non-auto ``override`` (config) >
    ``REPRO_SPARSIFY_BACKEND`` (env) > auto rule (TPU -> kernel,
    CPU/GPU -> reference).  Off-TPU the kernel backend runs in Pallas
    interpret mode (kernels/*/ops.py), so forcing ``kernel`` is valid —
    and is exactly what the parity tests do."""
    choice = (override or "auto").lower()
    if choice == "auto":
        choice = os.environ.get(SPARSIFY_BACKEND_ENV, "auto").lower()
    if choice not in _BACKENDS:
        raise ValueError(
            f"sparsify backend {choice!r} not in {_BACKENDS}")
    if choice == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "reference"
    return choice


def use_kernel_path(override: Optional[str] = None) -> bool:
    return resolve_backend(override) == "kernel"


def k_for(n: int, alpha: float) -> int:
    """Number of kept elements for a tensor of n elements (>=1).

    Static by construction: every hot-path caller passes a Python shape
    int and the config alpha, so the host cast runs at trace time — this
    is the one blessed host-math site (jit-hazard treats calls to it as
    static; the definition itself carries the suppression)."""
    return max(1, int(round(alpha * n)))  # repro-lint: disable=jit-hazard


# Tensors larger than BLOCK elements use *blocked* top-k: the flat tensor is
# tiled into BLOCK-sized rows and top-(alpha*BLOCK) is taken per row.  This
# (a) keeps every index within int32 (XLA scatter/gather requirement —
# stacked MoE leaves reach 3e11 elements), (b) is embarrassingly shardable,
# and (c) is the standard practical surrogate for global top-k (same
# k-contraction factor per block).  Leaves <= BLOCK use exact top-k.
BLOCK = 1 << 20


def blocked_topk_mask(x: jax.Array, alpha: float,
                      block: int = BLOCK) -> jax.Array:
    """Exact top-k within each BLOCK-sized tile of flat x."""
    flat = x.reshape(-1)
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    a = jnp.abs(jnp.pad(flat, (0, pad))).reshape(nb, block)
    k = k_for(block, alpha)
    _, idx = lax.top_k(a, k)                      # (nb, k) int32 local
    mask = jnp.zeros((nb, block), bool)
    rows = jnp.broadcast_to(jnp.arange(nb)[:, None], idx.shape)
    mask = mask.at[rows, idx].set(True)
    return mask.reshape(-1)[:n].reshape(x.shape)


def topk_mask_exact(x: jax.Array, k: int) -> jax.Array:
    """Boolean mask of the k largest-|.| elements of flat/ND x."""
    flat = jnp.abs(x.reshape(-1))
    _, idx = lax.top_k(flat, k)
    mask = jnp.zeros(flat.shape, bool).at[idx].set(True)
    return mask.reshape(x.shape)


def topk_mask_threshold(x: jax.Array, k: int, iters: int = 24) -> jax.Array:
    """Threshold-bisection mask (ties may push count above k).

    Pure-jnp reference of the Pallas ``topk_mask`` kernel: binary-search a
    threshold tau in [0, max|x|] such that count(|x| >= tau) ~ k, then mask.

    SHAPE-PRESERVING on purpose: no reshape/flatten — reductions over the
    (possibly mesh-sharded) dims lower to partial-reduce + tiny all-reduce,
    whereas a flatten of a sharded tensor forces a full all-gather.  Counts
    accumulate in f32 (exact to 2^24 per partial; bisection tolerance far
    coarser than the rounding).
    """
    a = jnp.abs(x).astype(_F32)
    hi = jnp.max(a)
    lo = jnp.zeros((), _F32)
    kf = jnp.asarray(k, _F32)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(_F32))
        # too many kept -> raise threshold (move lo up)
        lo, hi = jnp.where(cnt > kf, mid, lo), jnp.where(cnt > kf, hi, mid)
        return lo, hi

    lo, hi = lax.fori_loop(0, iters, body, (lo, hi))
    # `lo` keeps count >= k; guard the degenerate all-equal case by falling
    # back to hi when lo never moved.
    tau = jnp.where(jnp.sum((a >= lo).astype(_F32)) >= kf, lo, hi)
    return a >= tau


def sparsify(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Top_k(x) = x . mask (Definition 1)."""
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


def compress_to_coo(x: jax.Array, mask_idx: jax.Array) -> jax.Array:
    """Gather the k masked values (mask_idx: (k,) int32 into flat x)."""
    return jnp.take(x.reshape(-1), mask_idx)


def mask_indices(mask: jax.Array, k: int) -> jax.Array:
    """Indices of the k True entries of mask (flat order).  Requires the
    mask to have >= k set bits (exact construction guarantees == k)."""
    score = mask.reshape(-1).astype(jnp.int8)
    _, idx = lax.top_k(score, k)
    return jnp.sort(idx)


def scatter_from_coo(values: jax.Array, idx: jax.Array, n: int,
                     dtype=None) -> jax.Array:
    out = jnp.zeros((n,), dtype or values.dtype)
    return out.at[idx].add(values)


# ---------------------------------------------------------------------------
# Pytree-level helpers
# ---------------------------------------------------------------------------


def tree_topk_masks(score_tree, alpha: float, scope: str = "per_tensor",
                    exact: bool = True, backend: Optional[str] = None):
    """Boolean mask pytree selecting ~alpha of the elements of score_tree
    by magnitude.  scope="global" ranks across the whole flattened model
    (the paper's Definition 1 applied to the full d-vector).  The
    threshold (``exact=False``) production path dispatches per
    :func:`resolve_backend`: the streaming 3-pass Pallas kernel, or the
    jnp bisection reference."""
    def mk(s, k):
        if not exact:
            # production path: O(n) streaming threshold selection — no
            # sort, O(1) temp memory
            if use_kernel_path(backend):
                return topk_mask_kernel(s, k)[0]
            return topk_mask_threshold(s, k)
        if s.size > BLOCK:
            return blocked_topk_mask(s, alpha)
        return topk_mask_exact(s, k)

    if scope == "per_tensor":
        return jax.tree.map(lambda s: mk(s, k_for(s.size, alpha)), score_tree)
    flat, unravel = ravel_pytree(score_tree)
    mask_flat = mk(flat, k_for(flat.size, alpha))
    return unravel_bool(mask_flat, score_tree)


def unravel_bool(mask_flat, like_tree):
    leaves, treedef = jax.tree_util.tree_flatten(like_tree)
    out, off = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(mask_flat[off:off + n].reshape(leaf.shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_sparsify(tree, masks):
    return jax.tree.map(sparsify, tree, masks)


def tree_sparsity_error(tree, masks):
    """|| (1 - mask) . x ||_2 over the whole pytree (Theorem 1 terms)."""
    sq = jax.tree.map(
        lambda x, m: jnp.sum(jnp.where(m, 0.0, x.astype(_F32)) ** 2),
        tree, masks)
    return jnp.sqrt(sum(jax.tree.leaves(sq)))


def tree_norm(tree):
    sq = jax.tree.map(lambda x: jnp.sum(x.astype(_F32) ** 2), tree)
    return jnp.sqrt(sum(jax.tree.leaves(sq)))


# ---------------------------------------------------------------------------
# Packed cohort layout — every leaf through ONE buffer, 2 launches total
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Static descriptor of a multi-leaf packed buffer.

    Every leaf is flattened and zero-padded to a multiple of the
    (8, 128) f32 min tile (``PACK_BLOCK_ELEMS`` = 1024 elements), then
    the leaves are concatenated into one (R, 128) buffer.  All fields
    are Python/static, so :meth:`unpack` is shape-only slicing (no
    data-dependent work) and the layout never forces a host sync.

    ``seg_of_leaf`` maps each leaf to its tau *segment*: identity for
    scope="per_tensor", all-zeros for scope="global", and stream ids
    for the independent compressor's 3-stream packing — the kernels
    only ever see block->segment ids, so every scope is the same two
    launches.  ``seg_ids`` (block->segment, one entry per (8, 128)
    block) is the scalar-prefetch operand of both packed kernels.
    """

    shapes: tuple
    sizes: tuple
    padded: tuple
    offsets: tuple
    seg_of_leaf: tuple
    num_segments: int
    seg_sizes: tuple
    seg_ids: jax.Array = dataclasses.field(compare=False, repr=False)

    @property
    def num_leaves(self) -> int:
        return len(self.sizes)

    @property
    def total(self) -> int:
        return sum(self.padded)

    @property
    def num_blocks(self) -> int:
        return self.total // PACK_BLOCK_ELEMS

    def pack(self, leaves: Sequence[jax.Array]) -> jax.Array:
        """Flatten + pad + concatenate into the (R, 128) buffer.  All
        offsets are static, so this lowers to dynamic_update_slices a
        compiler can turn into plain copies."""
        dtype = leaves[0].dtype
        buf = jnp.zeros((self.total,), dtype)
        for leaf, off in zip(leaves, self.offsets):
            buf = lax.dynamic_update_slice(
                buf, leaf.reshape(-1).astype(dtype), (off,))
        return buf.reshape(-1, PACK_LANES)

    def unpack(self, buf: jax.Array) -> list:
        """Shape-only inverse of :meth:`pack` (padding discarded)."""
        flat = buf.reshape(-1)
        return [flat[off:off + n].reshape(shape) for off, n, shape
                in zip(self.offsets, self.sizes, self.shapes)]


def plan_packed_layout(leaves, groups: Optional[Sequence[int]] = None
                       ) -> PackedLayout:
    """Build the static :class:`PackedLayout` for a list of leaves.

    ``groups`` assigns each leaf to a tau segment (default: one segment
    per leaf, i.e. scope="per_tensor").  Segment ids must be dense in
    ``range(max+1)``; a segment's leaves need not be contiguous in the
    buffer — the kernels accumulate by block segment id."""
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    sizes = tuple(int(leaf.size) for leaf in leaves)
    padded = tuple(-(-n // PACK_BLOCK_ELEMS) * PACK_BLOCK_ELEMS
                   for n in sizes)
    offsets, off = [], 0
    for p in padded:
        offsets.append(off)
        off += p
    if groups is None:
        groups = range(len(sizes))
    # groups is always a host-side list of Python ints — the layout is
    # static by construction, never built from traced values
    seg_of_leaf = tuple(int(g) for g in groups)  # repro-lint: disable=jit-hazard
    num_segments = max(seg_of_leaf) + 1
    seg_sizes = [0] * num_segments
    for n, g in zip(sizes, seg_of_leaf):
        seg_sizes[g] += n
    seg_ids = jnp.asarray(np.concatenate(
        [np.full(p // PACK_BLOCK_ELEMS, g, np.int32)
         for p, g in zip(padded, seg_of_leaf)]))
    return PackedLayout(shapes=shapes, sizes=sizes, padded=padded,
                        offsets=tuple(offsets), seg_of_leaf=seg_of_leaf,
                        num_segments=num_segments,
                        seg_sizes=tuple(seg_sizes), seg_ids=seg_ids)


def _segment_absmax(layout: PackedLayout, score_leaves):
    """Per-segment max|x| as a list of f32 scalars.  max is exact, so
    the reduce over a segment's leaves is bitwise the raveled max the
    per-leaf global path computes."""
    per_leaf = [jnp.max(jnp.abs(leaf.astype(_F32)))
                for leaf in score_leaves]
    out = [None] * layout.num_segments
    for am, g in zip(per_leaf, layout.seg_of_leaf):
        out[g] = am if out[g] is None else jnp.maximum(out[g], am)
    return out


@stages.scoped(stages.SELECT)
def _packed_select_inputs(layout: PackedLayout, score_leaves, score_p,
                          alpha: float):
    """Launch 1 (histogram) + the host-side CDF refine.  Returns the
    prefetch operands of the apply launch: (taus2, ks, ns)."""
    ks = jnp.asarray([k_for(n, alpha) for n in layout.seg_sizes], _F32)
    ns = jnp.asarray(layout.seg_sizes, _F32)
    absmax = _segment_absmax(layout, score_leaves)
    edges = jnp.stack([log2_taus(a) for a in absmax])
    c1 = packed_hist_kernel(score_p, layout.seg_ids, edges)
    taus2 = refine_taus(c1, edges, absmax, ks)
    return taus2, ks, ns


def _leaf_masks(layout: PackedLayout, score_leaves, taus):
    """Diagnostic boolean masks, recomputed per leaf from tau (same
    compare the kernels use; XLA fuses it into consuming reductions)."""
    return [jnp.abs(leaf.astype(_F32)) >= taus[g]
            for leaf, g in zip(score_leaves, layout.seg_of_leaf)]


def _uniform_dtype(*trees) -> bool:
    dts = {leaf.dtype for t in trees if t is not None
           for leaf in jax.tree_util.tree_leaves(t)}
    return len(dts) == 1


def tree_shared_compress_packed(score_tree, dW, dM, dV, alpha: float,
                                scope: str = "per_tensor", *,
                                value_dtype=None,
                                with_residual: bool = False):
    """Packed realization of the shared-mask compress: every leaf of
    (score, dW, dM, dV) rides ONE tile-aligned buffer, and the whole
    cohort costs exactly TWO Pallas launches — the segmented histogram
    and the fused refine-count/tau-pick/apply pass — plus the jnp
    absmax reduction and the O(L * N_BINS) host refine.

    tau per segment is bitwise equal to the per-leaf
    ``select_tau_kernel`` tau (same candidates, same pick), so outputs
    — masks, wire-cast values, the EF residual — are bit-identical to
    :func:`tree_shared_compress_fused`'s per-leaf path.  Same return
    shape: ``(sW, sM, sV, err_tree | None, mask_tree)``."""
    w_leaves, treedef = jax.tree_util.tree_flatten(dW)
    m_leaves = treedef.flatten_up_to(dM)
    v_leaves = treedef.flatten_up_to(dV)
    s_leaves = (None if score_tree is None
                else treedef.flatten_up_to(score_tree))
    groups = None if scope == "per_tensor" else [0] * len(w_leaves)
    layout = plan_packed_layout(w_leaves, groups)

    wp = layout.pack(w_leaves)
    mp = layout.pack(m_leaves)
    vp = layout.pack(v_leaves)
    sp = None if s_leaves is None else layout.pack(s_leaves)
    score_leaves = w_leaves if s_leaves is None else s_leaves

    taus2, ks, ns = _packed_select_inputs(
        layout, score_leaves, wp if sp is None else sp, alpha)
    outs = packed_apply_ef(taus2, layout.seg_ids, ks, ns, wp, mp, vp, sp,
                           with_residual=with_residual,
                           value_dtype=value_dtype)
    taus = outs[-2][:, 0]
    unflat = lambda buf: jax.tree_util.tree_unflatten(
        treedef, layout.unpack(buf))
    err_tree = unflat(outs[3]) if with_residual else None
    mask_tree = jax.tree_util.tree_unflatten(
        treedef, _leaf_masks(layout, score_leaves, taus))
    return unflat(outs[0]), unflat(outs[1]), unflat(outs[2]), err_tree, \
        mask_tree


def tree_independent_compress_packed(dW, dM, dV, alpha: float,
                                     scope: str = "per_tensor", *,
                                     value_dtype=None,
                                     with_residual: bool = False):
    """Packed compress for the THREE-mask (FedAdam-Top) scheme: all
    leaves of dW ++ dM ++ dV share one packed buffer, each stream's
    leaves in their own tau segments (3L segments for "per_tensor",
    3 for "global") — so three independent top-k selections still cost
    the same TWO launches.  Each segment's score is the stream itself.

    Returns ``(sW, sM, sV, err_tree | None, (mW, mM, mV))``; the
    residual is dW's (the M/V rows of the kernel's residual output are
    discarded, matching the composed path's EF contract)."""
    w_leaves, treedef = jax.tree_util.tree_flatten(dW)
    m_leaves = treedef.flatten_up_to(dM)
    v_leaves = treedef.flatten_up_to(dV)
    leaves = w_leaves + m_leaves + v_leaves
    L = len(w_leaves)
    if scope == "per_tensor":
        groups = list(range(3 * L))
    else:
        groups = [0] * L + [1] * L + [2] * L
    layout = plan_packed_layout(leaves, groups)

    xp = layout.pack(leaves)
    taus2, ks, ns = _packed_select_inputs(layout, leaves, xp, alpha)
    outs = packed_mask_apply(taus2, layout.seg_ids, ks, ns, xp,
                             with_residual=with_residual,
                             value_dtype=value_dtype)
    taus = outs[-2][:, 0]
    sx = layout.unpack(outs[0])
    unflat = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
    err_tree = (unflat(layout.unpack(outs[1])[:L])
                if with_residual else None)
    masks = _leaf_masks(layout, leaves, taus)
    return (unflat(sx[:L]), unflat(sx[L:2 * L]), unflat(sx[2 * L:]),
            err_tree,
            (unflat(masks[:L]), unflat(masks[L:2 * L]),
             unflat(masks[2 * L:])))


# ---------------------------------------------------------------------------
# Kernel-path fused shared-mask compress
# ---------------------------------------------------------------------------


def _fused_leaf(score, w, m, v, k: int, value_dtype, with_residual: bool):
    """One leaf of the fused compress: 3-pass tau selection on the score
    (== w when score is None), then ONE fused apply/cast/residual pass.
    Returns (sw, sm, sv, err|None, mask)."""
    with jax.named_scope(stages.SELECT):
        tau, _ = select_tau_kernel(w if score is None else score, k)
    outs = ssm_apply_ef(tau, w, m, v, score,
                        with_residual=with_residual,
                        value_dtype=value_dtype)
    err = outs[3] if with_residual else None
    # mask reconstructed for diagnostics only (never re-materialized by
    # the kernel); XLA fuses this compare into the consuming reductions.
    s = w if score is None else score
    mask = jnp.abs(s.astype(_F32)) >= tau
    return outs[0], outs[1], outs[2], err, mask


def tree_shared_compress_fused(score_tree, dW, dM, dV, alpha: float,
                               scope: str = "per_tensor", *,
                               value_dtype=None,
                               with_residual: bool = False,
                               packed: bool = True):
    """Fused kernel-path realization of the shared-mask compress: for
    each leaf (or the raveled model when ``scope == "global"``), select
    tau with the streaming topk_mask kernel and apply mask + optional
    ``value_dtype`` wire cast + optional error-feedback residual in a
    single ``ssm_apply_ef`` pass.

    ``score_tree=None`` means the mask scores ARE ``|dW|`` (the paper's
    optimal ssm_w rule) — the kernel then derives the mask from the dW
    stream it is already reading instead of streaming a score tensor.

    ``packed=True`` (the default) routes uniform-dtype cohorts through
    :func:`tree_shared_compress_packed` — bit-identical outputs in TWO
    Pallas launches total instead of 4 per leaf.  Mixed-dtype trees (no
    single packed buffer dtype) and ``packed=False`` take the per-leaf
    loop below.

    Returns ``(sW, sM, sV, err_tree | None, mask_tree)``; arithmetic is
    bit-identical to the composed reference ops given the same tau
    (asserted by tests/test_sparsify_dispatch.py)."""
    if packed and _uniform_dtype(score_tree, dW, dM, dV):
        return tree_shared_compress_packed(
            score_tree, dW, dM, dV, alpha, scope,
            value_dtype=value_dtype, with_residual=with_residual)
    if scope == "global":
        flat_w, unravel = ravel_pytree(dW)
        flat_m, _ = ravel_pytree(dM)
        flat_v, _ = ravel_pytree(dV)
        flat_s = None if score_tree is None else ravel_pytree(score_tree)[0]
        sw, sm, sv, err, mask = _fused_leaf(
            flat_s, flat_w, flat_m, flat_v, k_for(flat_w.size, alpha),
            value_dtype, with_residual)
        return (unravel(sw), unravel(sm), unravel(sv),
                unravel(err) if err is not None else None,
                unravel_bool(mask, dW))

    w_leaves, treedef = jax.tree_util.tree_flatten(dW)
    m_leaves = treedef.flatten_up_to(dM)
    v_leaves = treedef.flatten_up_to(dV)
    s_leaves = ([None] * len(w_leaves) if score_tree is None
                else treedef.flatten_up_to(score_tree))
    outs = [_fused_leaf(s, w, m, v, k_for(w.size, alpha), value_dtype,
                        with_residual)
            for s, w, m, v in zip(s_leaves, w_leaves, m_leaves, v_leaves)]
    unflat = lambda i: jax.tree_util.tree_unflatten(
        treedef, [o[i] for o in outs])
    err_tree = unflat(3) if with_residual else None
    return unflat(0), unflat(1), unflat(2), err_tree, unflat(4)
