"""Tile-local decode of a mask payload's value streams (Pallas).

A mask payload (core/wire.py, docs/wire.md) ships a support bitmap in
the word layout of ``wirepack.py`` (bit ``t`` of word row ``q`` is slot
row ``32 q + t``) and compacted f32 value streams: the supported slots
of the (R, 128) buffer, in flat row-major order, hold the stream's
entries ``0, 1, 2, ...``; a slot whose rank reaches the stream's
capacity, and every slot off the support, decodes to 0.

This kernel decodes one (8, 128) word tile per grid step, i.e. a block
of 256 slot rows x 128 lanes = 32768 slots, without a global prefix sum
or a per-slot gather:

1. Block starts (outside, jnp): each block's popcount and their
   exclusive prefix sum ``start`` — the stream index of the block's
   first supported slot.  They reach the kernel by scalar prefetch.
2. Window: the stream rows ``[row0, row0 + 264)`` with ``row0 = 8 *
   (start // 1024)`` (an 8-row aligned element-indexed block), which
   hold the block's values at flat offsets ``off + rank`` where ``off =
   start % 1024`` and ``rank`` < 32768 is the slot's rank in the block.
3. Rank: the block's bits unpacked in VMEM; the in-row exclusive prefix
   and the prefix of the row counts as 0/1 matmuls on the MXU (bf16
   operands, f32 accumulation: every count is an integer below 2**15,
   so the sums are exact).
4. Placement, a log-step network in a 264-row frame whose rows 8..263
   are the block (so every move is to the right): value ``rank`` moves
   from ``off + rank`` by ``d = 1024 + slot - off - rank``, which does
   not decrease with the rank.  Moving by the bits of ``d``, the largest
   first, no two values ever meet; which entries move at each stage is
   found by running the inverse network (a compaction, smallest bit
   first) on the shift codes themselves, from the slots, and recording
   each stage's movers.  Values travel as int32 bit patterns through
   rolls and selects only, so the decode is bitwise (-0.0, subnormals
   and NaN payloads included).

Ranks past the capacity read the zero padding behind the stream, and a
block whose start reaches the capacity is written as zeros.  Oracle:
ref.expand_mask_values_ref (the jnp ``cumsum`` + ``take`` decode);
parity: tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.wirepack.wirepack import LANES, WORD_BITS, WORD_SUBLANES

#: Slot rows per block: one (8, 128) word tile of a 1-bit bitmap.
ROWS = WORD_SUBLANES * WORD_BITS
#: Stream entries per 8-row tile: windows start on this boundary.
ALIGN = WORD_SUBLANES * LANES
#: Rows of the placement frame: an 8-row lead, then the block.
FRAME = WORD_SUBLANES + ROWS
#: Network stages: every shift code is below ``FRAME * LANES <= 2**16``.
STAGES = 16
#: Shift code of a frame position that holds no value: no stage bit set.
EMPTY = 1 << STAGES


def _shift_right(x, s: int):
    """``y[p] = x[p - s]`` in the frame's flat row-major order (wraps)."""
    if s % LANES == 0:
        return pltpu.roll(x, s // LANES, 0)
    y = pltpu.roll(x, s, 1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= s, y, pltpu.roll(y, 1, 0))


def _shift_left(x, s: int):
    """``y[p] = x[p + s]`` in flat row-major order (wraps)."""
    n = x.shape[0]
    if s % LANES == 0:
        return pltpu.roll(x, n - s // LANES, 0)
    y = pltpu.roll(x, LANES - s, 1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < LANES - s, y, pltpu.roll(y, n - 1, 0))


def _block_ranks(bits):
    """(ROWS, LANES) 0/1 int32 -> the exclusive flat rank of each slot
    in the block, int32 (exact: 0/1 bf16 operands, sums < 2**15)."""
    b = bits.astype(jnp.bfloat16)
    lane_i = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lane_j = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    before_lane = (lane_i < lane_j).astype(jnp.bfloat16)
    row_i = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    row_j = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    before_row = (row_j < row_i).astype(jnp.bfloat16)
    in_row = jnp.dot(b, before_lane, preferred_element_type=jnp.float32)
    above = jnp.dot(before_row, b, preferred_element_type=jnp.float32)
    rows_before = jnp.dot(above.astype(jnp.bfloat16),
                          jnp.ones((LANES, LANES), jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return (in_row + rows_before).astype(jnp.int32)


def _movers(bits, off):
    """The block's bits -> a (FRAME, LANES) int32 word whose bit k marks
    the frame positions that stage k of :func:`_place` fills from
    ``2**k`` positions to their left."""
    slot = (lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    d = WORD_SUBLANES * LANES + slot - off - _block_ranks(bits)
    code = jnp.concatenate(
        [jnp.full((WORD_SUBLANES, LANES), EMPTY, jnp.int32),
         jnp.where(bits != 0, d, EMPTY)], axis=0)
    movers = jnp.zeros((FRAME, LANES), jnp.int32)
    for k in range(STAGES):
        bit = jnp.int32(1 << k)
        moving = code & bit
        movers = movers | moving
        nxt = _shift_left(code, 1 << k)
        code = jnp.where((nxt & bit) != 0, nxt,
                         jnp.where(moving != 0, EMPTY, code))
    return movers


def _place(movers, window):
    """Run the placement network on a (FRAME, LANES) int32 window."""
    x = window
    for k in reversed(range(STAGES)):
        x = jnp.where((movers & (1 << k)) != 0, _shift_right(x, 1 << k), x)
    return x[WORD_SUBLANES:]


def _make_expand_kernel(n_streams: int, capacity: int):
    def kernel(starts_ref, w_ref, *refs):
        wins, outs = refs[:n_streams], refs[n_streams:2 * n_streams]
        bits_ref = refs[2 * n_streams]
        start = starts_ref[pl.program_id(0)]

        @pl.when(start >= capacity)
        def _():
            for o in outs:
                o[...] = jnp.zeros(o.shape, o.dtype)

        @pl.when(start < capacity)
        def _():
            w = w_ref[...]
            for t in range(WORD_BITS):
                bits_ref[pl.ds(t, WORD_SUBLANES, stride=WORD_BITS), :] = (
                    (w >> jnp.uint32(t)) & jnp.uint32(1)).astype(jnp.int32)
            bits = bits_ref[...]
            movers = _movers(bits, start % ALIGN)
            for win, o in zip(wins, outs):
                o[...] = lax.bitcast_convert_type(
                    jnp.where(bits != 0, _place(movers, win[...]), 0),
                    jnp.float32)

    return kernel


def block_starts(words):
    """(nb * 8, LANES) uint32 bitmap words -> (nb,) int32 stream index
    of each block's first supported slot (exclusive popcount prefix)."""
    nb = words.shape[0] // WORD_SUBLANES
    counts = jnp.sum(lax.population_count(words).astype(jnp.int32)
                     .reshape(nb, WORD_SUBLANES * LANES), axis=1)
    return jnp.cumsum(counts) - counts


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_streams_2d(words, streams, *, interpret: bool = True):
    """Decode f32 value streams onto the support of a 1-bit bitmap.

    ``words``: (W, LANES) uint32 bitmap of an (R, LANES) buffer, R = 32
    W; ``streams``: a tuple of (K,) f32 streams (K >= 1) that share it.
    Returns one (R, LANES) f32 buffer per stream, bitwise equal to
    ``ref.expand_mask_values_ref``.  ONE launch for all streams."""
    rows = words.shape[0] * WORD_BITS
    cap = streams[0].shape[0]
    assert all(v.shape == (cap,) for v in streams), streams
    pad = (-words.shape[0]) % WORD_SUBLANES
    wp = jnp.pad(words, ((0, pad), (0, 0))) if pad else words
    nb = wp.shape[0] // WORD_SUBLANES
    starts = block_starts(wp)
    # a window starts at most at the last aligned tile before `cap`
    vrows = (cap - 1) // ALIGN * WORD_SUBLANES + FRAME
    wins = [jnp.pad(lax.bitcast_convert_type(v, jnp.int32),
                    (0, vrows * LANES - cap)).reshape(vrows, LANES)
            for v in streams]

    def window_row(i, starts_ref):
        last = jnp.minimum(starts_ref[i], cap - 1)
        return (last // ALIGN * WORD_SUBLANES, 0)

    n = len(streams)
    return tuple(pl.pallas_call(
        _make_expand_kernel(n, cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[pl.BlockSpec((WORD_SUBLANES, LANES),
                                   lambda i, s: (i, 0))]
            + [pl.BlockSpec((pl.Element(FRAME), pl.Element(LANES)),
                           window_row)] * n,
            out_specs=[pl.BlockSpec((ROWS, LANES),
                                    lambda i, s: (i, 0))] * n,
            scratch_shapes=[pltpu.VMEM((ROWS, LANES), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * n,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(starts, wp, *wins))
