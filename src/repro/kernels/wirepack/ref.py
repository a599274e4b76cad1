"""Pure-jnp oracles for the wire-format pack/unpack kernels.

``pack_words_ref`` / ``unpack_words_ref`` are the vectorized rendering
of the kernel's per-block loop: the (R, 128) code buffer viewed as
(nb, b, T, 128) row groups, one uint32 multiply-accumulate over the T
axis.  All arithmetic is integer (multiplies by static powers of two),
so oracle and kernel agree bitwise — these doubles as the CPU reference
transport in ``core/wire.py``.

The scheme-level oracles repeat the ops-layer jnp conversions verbatim
(sign extraction, block scales, offset shift) around the word oracles.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.wirepack.wirepack import (
    CODE_SUBLANES, LANES, SUPPORTED_BITS, WORD_BITS)

SCALE_BLOCK = 1024


def _weights(bits: int):
    T = WORD_BITS // bits
    return jnp.asarray([1 << (t * bits) for t in range(T)], jnp.uint32)


def pack_words_ref(codes, bits: int):
    """Oracle for ``pack_words_2d``: (R, LANES) unsigned int32 codes ->
    (R*bits/32, LANES) uint32 words."""
    T = WORD_BITS // bits
    nb = codes.shape[0] // CODE_SUBLANES
    u = codes.astype(jnp.uint32).reshape(nb, bits, T, LANES)
    w = jnp.sum(u * _weights(bits)[None, None, :, None], axis=2,
                dtype=jnp.uint32)
    return w.reshape(nb * bits, LANES)


def unpack_words_ref(words, bits: int):
    """Oracle for ``unpack_words_2d``: words back to int32 codes."""
    T = WORD_BITS // bits
    nb = words.shape[0] // bits
    mask = jnp.uint32((1 << bits) - 1)
    w = words.reshape(nb, bits, 1, LANES)
    shifts = jnp.asarray([t * bits for t in range(T)], jnp.uint32)
    u = (w >> shifts[None, None, :, None]) & mask
    return u.astype(jnp.int32).reshape(nb * CODE_SUBLANES, LANES)


def pack_mask_bits_ref(support):
    return pack_words_ref(support.astype(jnp.int32), 1)


def unpack_mask_bits_ref(words):
    return unpack_words_ref(words, 1)


def pack_sign_scale_ref(xp):
    x = xp.astype(jnp.float32)
    bits = (x >= 0).astype(jnp.int32)
    scales = jnp.max(jnp.abs(x).reshape(-1, SCALE_BLOCK), axis=1)
    return pack_words_ref(bits, 1), scales


def unpack_sign_scale_ref(words, scales):
    bits = unpack_words_ref(words, 1)
    s = jnp.broadcast_to(scales[:, None],
                         (scales.shape[0], SCALE_BLOCK)).reshape(bits.shape)
    return jnp.where(bits == 1, s, -s)


def pack_bbit_ref(codes, bits: int):
    qmax = (1 << (bits - 1)) - 1
    return pack_words_ref(codes + qmax, bits)


def unpack_bbit_ref(words, bits: int):
    qmax = (1 << (bits - 1)) - 1
    return unpack_words_ref(words, bits) - qmax


def expand_mask_values_ref(words, streams):
    """Oracle for ``expand_mask_values``: the global decode.  Each
    supported slot's rank is its flat prefix sum; slot ``i`` takes
    ``stream[rank]`` where ``rank < capacity``, every other slot 0."""
    support = unpack_words_ref(words, 1)
    flat = support.reshape(-1) == 1
    pos = jnp.cumsum(flat.astype(jnp.int32)) - 1
    outs = []
    for values in streams:
        cap = values.shape[0]
        taken = jnp.take(values, jnp.clip(pos, 0, cap - 1))
        outs.append(jnp.where(flat & (pos < cap), taken, jnp.float32(0))
                    .reshape(support.shape))
    return tuple(outs)
