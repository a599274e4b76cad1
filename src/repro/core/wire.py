"""The uplink wire format: what a client's payload ACTUALLY ships.

``comm.py`` counts Section IV's bits analytically; this module makes the
transport match the count.  A :class:`WirePayload` holds the real wire
arrays — uint32 bit-packed words plus f32 value/scale side streams — and
its :func:`payload_nbytes` is measured from the array shapes, so
``uplink_bits == 8 * nbytes`` holds by construction instead of by
formula.  The per-scheme encodings (word layout diagrams and the
analytic-vs-measured bits ledger: docs/wire.md):

* ``mask_shared`` (FedAdam-SSM family) — ONE support bitmap (1 bit per
  padded parameter slot) + three compacted f32 value streams of static
  capacity K (the worst-case mask population; unused tail slots are
  zero but still shipped — capacity must be static under jit).
* ``mask_independent`` (FedAdam-Top) — three (bitmap, value stream)
  pairs, one per tensor.
* ``sign`` (1-bit Adam, arXiv 2109.05109) — sign bitplane + one f32
  scale per 1024-element block.  Exact for ``quantize.sign_quant``
  carriers: each block is two-valued ``+-scale``.
* ``bbit`` (Efficient-Adam, arXiv 2205.02719) — b-bit offset codes
  (b in {2, 4, 8}) + the quantizer's per-block f32 scales.
* ``dense`` (FedAdam / FedSGD) — raveled f32 planes; measured bytes
  equal the analytic ``n_tensors * d * q`` exactly (no padding).

Layout reuses :class:`repro.core.sparsify.PackedLayout`: every leaf is
zero-padded to 1024 elements (so packed blocks align with the
quantizers' 1024-element scale blocks) and the concatenated buffer is
further padded to 4096 elements — the (32, 128) row-group granularity
of the ``kernels/wirepack`` word packers.  Padding slots cost wire bits
(they are honest transport overhead) and decode to values that the
shape-only ``layout.unpack`` slices away, so round-trips are exact.

Pack/unpack dispatches like every other hot path: Pallas kernels when
:func:`repro.core.sparsify.use_kernel_path` says so (TPU, or forced via
``REPRO_SPARSIFY_BACKEND``), bitwise-identical jnp references otherwise.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import sparsify as S
from repro.core import stages
from repro.kernels.topk_mask.ops import overselect_bound
from repro.kernels.wirepack import ops as _wops
from repro.kernels.wirepack import ref as _wref
from repro.kernels.wirepack.wirepack import (
    CODE_SUBLANES, LANES, SUPPORTED_BITS, WORD_BITS)

_F32 = jnp.float32

#: Elements per f32 side-stream scale block == the packed layout's
#: per-leaf padding quantum, so buffer blocks ARE quantizer blocks.
SCALE_BLOCK = 1024
assert SCALE_BLOCK == S.PACK_BLOCK_ELEMS, \
    "wire scale blocks must match the packed-layout block size"

#: Word-packer row-group granularity: buffers are padded to a multiple
#: of 32 sublanes x 128 lanes so every (32, 128) code block maps to
#: whole uint32 word rows.
ALIGN_ELEMS = CODE_SUBLANES * LANES

#: All value/scale side streams ship as f32.
VALUE_BITS = 32

#: Per-client counters of a mask payload, reported beside ``loss`` in
#: the round's metrics (never part of the payload): entries the mask
#: selected (union support, before the per-leaf cap), entries the
#: bitmap(s) ship, and the value slots the stream(s) hold.
COUNT_KEYS = ("mask_selected", "mask_shipped", "mask_capacity")


def zero_counts():
    """The counters of a scheme that ships no mask payload."""
    z = jnp.zeros((), jnp.int32)
    return {k: z for k in COUNT_KEYS}


def mask_shares(counts) -> Optional[Tuple[float, float]]:
    """``(fill, dropped)`` in % from counters summed over clients: the
    shipped entries' share of the value slots, and the selected entries'
    share that the per-leaf cap dropped.  ``None`` without a mask
    payload (zero capacity)."""
    sel, shipped, cap = (int(counts[k]) for k in COUNT_KEYS)
    if cap == 0:
        return None
    return 100.0 * shipped / cap, 100.0 * (sel - shipped) / max(sel, 1)


class WirePayload(NamedTuple):
    """A client's transported payload: the ONLY arrays that cross the
    client axis for wire-enabled schemes.

    ``words``  — uint32 bit-packed buffers (bitmaps / sign planes /
    b-bit codes); ``values`` — f32 value streams (compacted mask values
    or dense planes); ``scales`` — f32 per-block quantizer scales.  All
    three are tuples so the payload is a fixed-structure pytree that
    ``scan``/``vmap``/``shard_map`` can stack over clients."""
    words: Tuple[jax.Array, ...]
    values: Tuple[jax.Array, ...]
    scales: Tuple[jax.Array, ...]


def payload_nbytes(payload: WirePayload) -> int:
    """Measured payload size in bytes — from array shapes/dtypes (static
    under jit; works on tracers, which have no ``.nbytes``)."""
    return sum(int(a.size) * jnp.dtype(a.dtype).itemsize
               for part in payload for a in part)


# ---------------------------------------------------------------------------
# Static layout math (host ints — the accounting side of the format)
# ---------------------------------------------------------------------------


def padded_total(sizes: Sequence[int]) -> int:
    """Packed-buffer elements: each leaf padded to SCALE_BLOCK."""
    return sum(-(-int(n) // SCALE_BLOCK) * SCALE_BLOCK for n in sizes)


def aligned_total(sizes: Sequence[int]) -> int:
    """Word-packable elements: :func:`padded_total` padded to the
    (32, 128) row-group quantum."""
    t = padded_total(sizes)
    return -(-t // ALIGN_ELEMS) * ALIGN_ELEMS


def mask_leaf_capacities(sizes: Sequence[int], alpha: float,
                         mask_scope: str = "per_tensor",
                         exact_topk: bool = True) -> Tuple[int, ...]:
    """Static worst-case population of one top-k mask over a tree with
    leaf ``sizes``: one entry per leaf for ``per_tensor`` masks, one for
    the whole tree for ``global`` ones.  Their sum is the capacity of
    each compacted value stream.

    Mirrors the mask constructions in ``core/sparsify``: exact masks
    keep ``k_for`` per tensor (per-BLOCK for tensors above the blocked
    cutoff); threshold masks may overshoot by ``overselect_bound``."""
    def cap_exact(n: int) -> int:
        if n <= S.BLOCK:
            return min(n, S.k_for(n, alpha))
        nb = -(-n // S.BLOCK)
        return min(n, nb * S.k_for(S.BLOCK, alpha))

    def cap_thresh(n: int) -> int:
        k = S.k_for(n, alpha)
        return min(n, k + overselect_bound(k, n))

    cap = cap_exact if exact_topk else cap_thresh
    if mask_scope == "per_tensor":
        return tuple(cap(int(n)) for n in sizes)
    return (cap(int(sum(int(n) for n in sizes))),)


def mask_wire_bits(sizes: Sequence[int], alpha: float,
                   mask_scope: str = "per_tensor",
                   exact_topk: bool = True, *, shared: bool = True) -> int:
    """Wire bits of one client's mask-scheme payload: bitmap (1 bit per
    aligned slot) + K f32 values per stream; one bitmap for the shared
    (SSM) layout, three for the independent (Top) layout."""
    t32 = aligned_total(sizes)
    cap = sum(mask_leaf_capacities(sizes, alpha, mask_scope, exact_topk))
    if shared:
        return t32 + 3 * cap * VALUE_BITS
    return 3 * (t32 + cap * VALUE_BITS)


def sign_wire_bits(sizes: Sequence[int]) -> int:
    """1-bit Adam payload: sign bitplane + one f32 scale per block of
    the ALIGNED buffer (alignment blocks carry zero scales)."""
    t32 = aligned_total(sizes)
    return t32 + VALUE_BITS * (t32 // SCALE_BLOCK)


def bbit_wire_bits(sizes: Sequence[int], bits: int) -> int:
    """Efficient-Adam payload: b bits per aligned slot + the quantizer's
    per-block scales (one per UNALIGNED block — scales are per-leaf)."""
    t = padded_total(sizes)
    t32 = aligned_total(sizes)
    return bits * t32 + VALUE_BITS * (t // SCALE_BLOCK)


def dense_wire_bits(sizes: Sequence[int], n_tensors: int = 3) -> int:
    """Dense payload: raveled f32 planes, no padding — equals the
    analytic ``n_tensors * d * 32`` exactly."""
    return n_tensors * int(sum(int(n) for n in sizes)) * VALUE_BITS


# ---------------------------------------------------------------------------
# Backend dispatch + aligned-buffer plumbing
# ---------------------------------------------------------------------------


def _use_kernels() -> bool:
    return S.use_kernel_path()


def _pack_mask_bits(support):
    if _use_kernels():
        return _wops.pack_mask_bits(support)
    return _wref.pack_mask_bits_ref(support)


def _pack_sign_scale(xp):
    if _use_kernels():
        return _wops.pack_sign_scale(xp)
    return _wref.pack_sign_scale_ref(xp)


def _unpack_sign_scale(words, scales):
    if _use_kernels():
        return _wops.unpack_sign_scale(words, scales)
    return _wref.unpack_sign_scale_ref(words, scales)


def _pack_bbit(codes, bits):
    if _use_kernels():
        return _wops.pack_bbit(codes, bits)
    return _wref.pack_bbit_ref(codes, bits)


def _unpack_bbit(words, bits):
    if _use_kernels():
        return _wops.unpack_bbit(words, bits)
    return _wref.unpack_bbit_ref(words, bits)


def _layout_for(leaves) -> S.PackedLayout:
    return S.plan_packed_layout(leaves)


def _pack_aligned(layout: S.PackedLayout, leaves) -> jax.Array:
    """Leaves -> the ALIGNED (R32, 128) buffer (f32 unless told not)."""
    buf = layout.pack(leaves)
    rows = buf.shape[0]
    arows = -(-rows // CODE_SUBLANES) * CODE_SUBLANES
    if arows != rows:
        buf = jnp.pad(buf, ((0, arows - rows), (0, 0)))
    return buf


def _unpack_aligned(layout: S.PackedLayout, buf, like_leaves) -> list:
    """Aligned buffer -> leaves cast to the template dtypes (shape-only
    slicing of each leaf out of the whole buffer: alignment and per-leaf
    padding discarded, and no copy of the buffer's leading rows)."""
    leaves = layout.unpack(buf)
    return [x.astype(t.dtype) for x, t in zip(leaves, like_leaves)]


def _f32_leaves(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return [x.astype(_F32) for x in leaves], treedef


def _compact(flat_support, pos, buf, capacity: int) -> jax.Array:
    """Gather the supported entries of ``buf`` into the first
    ``count <= capacity`` slots of a static (capacity,) stream (slot
    ``capacity`` is the overflow drop slot; unused tail stays zero)."""
    flat = buf.reshape(-1).astype(_F32)
    idx = jnp.where(flat_support, pos, capacity)
    out = jnp.zeros((capacity + 1,), _F32).at[idx].set(flat, mode="drop")
    return out[:capacity]


def _expand(flat_support, pos, values, shape) -> jax.Array:
    """Inverse of :func:`_compact`: scatter the value stream back onto
    the support (capacity-overflow slots decode to zero)."""
    cap = values.shape[0]
    taken = jnp.take(values, jnp.clip(pos, 0, cap - 1))
    return jnp.where(flat_support & (pos < cap), taken,
                     jnp.zeros((), _F32)).reshape(shape)


def _expand_streams(words, streams):
    """Decode value streams onto the support of bitmap ``words``: the
    tile-local Pallas expand on the kernel path (it ranks each slot
    inside its word tile), else :func:`_expand` over the global
    prefix-sum ranks of the unpacked support.  Bitwise the same."""
    if _use_kernels():
        return _wops.expand_mask_values(words, streams)
    support = _wref.unpack_mask_bits_ref(words)
    flat_sup = support.reshape(-1) == 1
    pos = _support_positions(flat_sup)
    return tuple(_expand(flat_sup, pos, v, support.shape) for v in streams)


def _support_positions(flat_support):
    """Rank of each supported slot in flat order (prefix-sum - 1)."""
    return jnp.cumsum(flat_support.astype(jnp.int32)) - 1


def _capped_support(layout: S.PackedLayout, support,
                    capacity: Sequence[int]):
    """``(shipped, counts)``: the support a payload ships — the first
    ``capacity[i]`` supported slots of leaf ``i`` (flat order), or of
    the whole buffer when ``capacity`` has one entry (``global`` masks)
    — and its :data:`COUNT_KEYS` counters (:func:`_counts`).

    A threshold mask can over-select past its contracted capacity (tied
    magnitudes, as in bf16 deltas).  Capping per leaf drops the overflow
    inside the leaf that caused it — as the shard_map transport does
    (``aggregate._local_pack``) — instead of dropping the last leaves of
    the tree, and the bitmap then marks exactly the shipped values."""
    flat = support.reshape(-1)
    if len(capacity) == 1:
        pos = _support_positions(flat)
        kept = flat & (pos < capacity[0])
        return kept.reshape(support.shape), _counts([pos[-1] + 1], capacity)
    assert len(capacity) == layout.num_leaves, capacity
    parts, selected = [], []
    for off, p, c in zip(layout.offsets, layout.padded, capacity):
        pos = _support_positions(flat[off:off + p])
        parts.append(flat[off:off + p] & (pos < c))
        selected.append(pos[-1] + 1)
    # the alignment tail past the last leaf holds no support
    parts.append(flat[layout.total:])
    return (jnp.concatenate(parts).reshape(support.shape),
            _counts(selected, capacity))


def _counts(selected, capacity: Sequence[int]):
    """The :data:`COUNT_KEYS` counters of a capped support from the
    count each capacity entry selected (the last entry of its prefix
    sum): the cap ships the first ``capacity[i]`` of entry ``i``'s, so
    no pass over the slots is needed to count the bitmap."""
    sel = jnp.stack(selected)
    caps = jnp.asarray(capacity, jnp.int32)
    return dict(zip(COUNT_KEYS, (jnp.sum(sel),
                                 jnp.sum(jnp.minimum(sel, caps)),
                                 jnp.sum(caps))))


def pack_bits_1d(bits) -> jax.Array:
    """(n,) bool/int bitmap -> (ceil(n/32),) uint32, bit ``i`` of word
    ``w`` = slot ``32 w + i``.  Pure jnp on an arbitrary-length vector —
    usable inside shard_map MANUAL regions, where the tile-shaped Pallas
    word packers do not apply (device-local shards are 1-D and not
    (32, 128)-aligned).  Same little-endian-in-word convention as
    ``kernels/wirepack``."""
    n = bits.shape[0]
    nw = -(-n // WORD_BITS)
    b = jnp.pad(bits.astype(jnp.uint32), (0, nw * WORD_BITS - n))
    b = b.reshape(nw, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(jnp.left_shift(b, shifts[None, :]), axis=1,
                   dtype=jnp.uint32)


def unpack_bits_1d(words, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits_1d`: (nw,) uint32 -> (n,) int32 in
    {0, 1} (word-padding tail sliced away)."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = jnp.bitwise_and(
        jnp.right_shift(words[:, None], shifts[None, :]), jnp.uint32(1))
    return bits.reshape(-1)[:n].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Scheme encoders/decoders
# ---------------------------------------------------------------------------


@stages.scoped(stages.WIRE_ENCODE)
def pack_shared_mask(sW, sM, sV, capacity: Sequence[int]):
    """FedAdam-SSM wire: one bitmap of the UNION support of the three
    sparse carriers + three compacted value streams.  Returns
    ``(payload, counts)``; ``counts`` holds the :data:`COUNT_KEYS`
    counters, which the payload does not carry.

    ``capacity``: :func:`mask_leaf_capacities`; the union is capped to
    it (:func:`_capped_support`).  Re-encoding a decoded triple reproduces
    the same union — packing is idempotent, which is what lets the async
    driver buffer payloads."""
    w_leaves, _ = _f32_leaves(sW)
    m_leaves, _ = _f32_leaves(sM)
    v_leaves, _ = _f32_leaves(sV)
    layout = _layout_for(w_leaves)
    wp = _pack_aligned(layout, w_leaves)
    mp = _pack_aligned(layout, m_leaves)
    vp = _pack_aligned(layout, v_leaves)
    support, counts = _capped_support(
        layout, (wp != 0) | (mp != 0) | (vp != 0), capacity)
    words = _pack_mask_bits(support.astype(jnp.int32))
    flat_sup = support.reshape(-1)
    pos = _support_positions(flat_sup)
    total = sum(capacity)
    payload = WirePayload(
        words=(words,),
        values=(_compact(flat_sup, pos, wp, total),
                _compact(flat_sup, pos, mp, total),
                _compact(flat_sup, pos, vp, total)),
        scales=())
    return payload, counts


@stages.scoped(stages.WIRE_DECODE)
def unpack_shared_mask(payload: WirePayload, like):
    """Decode to the (sW, sM, sV) triple; ``like`` is any tree with the
    carrier's structure/shapes/dtypes (e.g. the params template)."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    layout = _layout_for(leaves)
    bufs = _expand_streams(payload.words[0], payload.values)
    return tuple(jax.tree_util.tree_unflatten(
        treedef, _unpack_aligned(layout, buf, leaves)) for buf in bufs)


@stages.scoped(stages.WIRE_ENCODE)
def pack_independent_mask(sW, sM, sV, capacity: Sequence[int]):
    """FedAdam-Top wire: three (bitmap, value stream) pairs — each
    tensor's own support, capped as in :func:`pack_shared_mask`.
    Returns ``(payload, counts)``, the counters summed over the three
    tensors."""
    words, values = [], []
    total = sum(capacity)
    counts = zero_counts()
    for tree in (sW, sM, sV):
        leaves, _ = _f32_leaves(tree)
        layout = _layout_for(leaves)
        xp = _pack_aligned(layout, leaves)
        support, cnt = _capped_support(layout, xp != 0, capacity)
        flat_sup = support.reshape(-1)
        pos = _support_positions(flat_sup)
        words.append(_pack_mask_bits(support.astype(jnp.int32)))
        values.append(_compact(flat_sup, pos, xp, total))
        counts = {k: counts[k] + cnt[k] for k in COUNT_KEYS}
    payload = WirePayload(words=tuple(words), values=tuple(values),
                          scales=())
    return payload, counts


@stages.scoped(stages.WIRE_DECODE)
def unpack_independent_mask(payload: WirePayload, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    layout = _layout_for(leaves)
    outs = []
    for wrds, vals in zip(payload.words, payload.values):
        (buf,) = _expand_streams(wrds, (vals,))
        outs.append(jax.tree_util.tree_unflatten(
            treedef, _unpack_aligned(layout, buf, leaves)))
    return tuple(outs)


@stages.scoped(stages.WIRE_ENCODE)
def pack_sign(carrier) -> WirePayload:
    """1-bit Adam wire: sign bitplane + per-block max-|.| scales of the
    aligned carrier buffer.  Exact for ``sign_quant`` carriers (every
    block is two-valued ``+-scale``; padding zeros never raise a max)."""
    leaves, _ = _f32_leaves(carrier)
    layout = _layout_for(leaves)
    xp = _pack_aligned(layout, leaves)
    words, scales = _pack_sign_scale(xp)
    return WirePayload(words=(words,), values=(), scales=(scales,))


@stages.scoped(stages.WIRE_DECODE)
def unpack_sign(payload: WirePayload, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    layout = _layout_for(leaves)
    buf = _unpack_sign_scale(payload.words[0], payload.scales[0])
    return jax.tree_util.tree_unflatten(
        treedef, _unpack_aligned(layout, buf, leaves))


@stages.scoped(stages.WIRE_ENCODE)
def pack_bbit_codes(codes_leaves, scales_leaves, bits: int) -> WirePayload:
    """Efficient-Adam wire: the quantizer's int32 codes word-packed at b
    bits (offset by qmax to unsigned; layout padding encodes code 0,
    i.e. offset qmax — decoded then sliced away) + per-leaf scales."""
    layout = _layout_for(codes_leaves)
    cp = _pack_aligned(layout, [c.astype(jnp.int32) for c in codes_leaves])
    words = _pack_bbit(cp, bits)
    return WirePayload(words=(words,), values=(),
                       scales=tuple(s.astype(_F32) for s in scales_leaves))


@stages.scoped(stages.WIRE_DECODE)
def unpack_bbit_codes(payload: WirePayload, like, bits: int):
    """Decode to the dequantized f32 carrier tree (``uniform_decode`` of
    each leaf's codes with its shipped scales)."""
    from repro.core import quantize
    leaves, treedef = jax.tree_util.tree_flatten(like)
    layout = _layout_for(leaves)
    cbuf = _unpack_bbit(payload.words[0], bits)
    rows = layout.total // S.PACK_LANES
    code_leaves = layout.unpack(cbuf[:rows])
    outs = [quantize.uniform_decode(c, s, SCALE_BLOCK).astype(t.dtype)
            for c, s, t in zip(code_leaves, payload.scales, leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)


@stages.scoped(stages.WIRE_ENCODE)
def pack_dense(trees: Sequence[Any]) -> WirePayload:
    """FedAdam/FedSGD wire: one raveled f32 plane per communicated
    tensor — byte count equals the analytic formula exactly."""
    planes = tuple(
        jnp.concatenate([x.reshape(-1).astype(_F32)
                         for x in jax.tree_util.tree_leaves(t)])
        for t in trees)
    return WirePayload(words=(), values=planes, scales=())


@stages.scoped(stages.WIRE_DECODE)
def unpack_dense(payload: WirePayload, like):
    """Decode each plane back onto the ``like`` tree structure."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    outs = []
    for plane in payload.values:
        rebuilt, off = [], 0
        for t in leaves:
            rebuilt.append(plane[off:off + t.size]
                           .reshape(t.shape).astype(t.dtype))
            off += t.size
        outs.append(jax.tree_util.tree_unflatten(treedef, rebuilt))
    return tuple(outs)
