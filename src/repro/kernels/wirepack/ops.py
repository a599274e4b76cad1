"""Public wire-format pack/unpack ops: scheme-level encodings over the
packed (R, 128) cohort buffer.

Three encoding families, one kernel pair (``pack_words_2d`` /
``unpack_words_2d`` with static code width b):

* ``pack_mask_bits`` — b=1 bitmap of a sparse support (FedAdam-SSM's
  shared-mask wire: 1 bit/param + the compacted value stream, Section
  IV); ``expand_mask_values`` decodes the streams onto it tile by tile
  (``expand.py``), reading the words directly, so the support is never
  unpacked to HBM.
* ``pack_sign_scale`` / ``unpack_sign_scale`` — b=1 sign bitplane plus
  one f32 scale per 1024-element block (1-bit Adam, arXiv 2109.05109).
  Exact for ``quantize.sign_quant`` carriers: every block is two-valued
  ``+-scale`` so ``max|block|`` recovers the scale bitwise.
* ``pack_bbit`` / ``unpack_bbit`` — b-bit two's-offset codes (b in
  {2, 4, 8}) from ``quantize.uniform_encode`` (Efficient-Adam, arXiv
  2205.02719); scales travel beside the words in the WirePayload.

All scheme-specific arithmetic (sign extraction, offset shift, block
scales) is elementwise jnp around the single word-packing launch; the
packed rows are the ONLY buffer that crosses the client axis.  Oracles:
ref.py; parity: tests/test_kernels.py; payload layout: core/wire.py and
docs/wire.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.wirepack.expand import expand_streams_2d
from repro.kernels.wirepack.wirepack import (
    CODE_SUBLANES, LANES, SUPPORTED_BITS, WORD_BITS, pack_words_2d,
    unpack_words_2d)

#: Elements per f32 scale block (must match core/sparsify.PACK_BLOCK_ELEMS
#: so packed-buffer blocks align with quantizer blocks; wire.py asserts).
SCALE_BLOCK = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pack_mask_bits(support):
    """(R, LANES) 0/1 support (R % 32 == 0) -> (R/32, LANES) uint32
    bitmap words, 1 bit per parameter.  ONE launch."""
    return pack_words_2d(support.astype(jnp.int32), bits=1,
                         interpret=_interpret())


def expand_mask_values(words, streams):
    """(W, LANES) uint32 bitmap words + a tuple of (K,) f32 value
    streams -> one (32 W, LANES) f32 buffer per stream: entry ``i`` of
    the stream on the ``i``-th supported slot in flat order, 0 off the
    support and for ``i >= K``.  ONE launch for all streams."""
    return expand_streams_2d(words, tuple(streams), interpret=_interpret())


def pack_sign_scale(xp):
    """(R, LANES) f32 carrier -> ``(words, scales)``: (R/32, LANES)
    uint32 sign-bitplane words (bit = x >= 0) and (R*LANES/1024,) f32
    per-block ``max|x|`` scales.  ONE launch plus a jnp reduction."""
    x = xp.astype(jnp.float32)
    bits = (x >= 0).astype(jnp.int32)
    scales = jnp.max(jnp.abs(x).reshape(-1, SCALE_BLOCK), axis=1)
    return pack_words_2d(bits, bits=1, interpret=_interpret()), scales


def unpack_sign_scale(words, scales):
    """Inverse of :func:`pack_sign_scale`: reconstruct the two-valued
    carrier ``where(bit, +scale, -scale)`` of shape (R, LANES)."""
    bits = unpack_words_2d(words, bits=1, interpret=_interpret())
    s = jnp.broadcast_to(scales[:, None],
                         (scales.shape[0], SCALE_BLOCK)).reshape(bits.shape)
    return jnp.where(bits == 1, s, -s)


def pack_bbit(codes, bits: int):
    """(R, LANES) int32 symmetric codes in [-qmax, qmax] (qmax =
    2**(bits-1) - 1) -> (R*bits/32, LANES) uint32 words of unsigned
    offset codes ``code + qmax``.  ONE launch."""
    qmax = (1 << (bits - 1)) - 1
    return pack_words_2d(codes + qmax, bits=bits, interpret=_interpret())


def unpack_bbit(words, bits: int):
    """Inverse of :func:`pack_bbit`: words back to (R, LANES) int32
    signed codes.  ONE launch."""
    qmax = (1 << (bits - 1)) - 1
    return unpack_words_2d(words, bits=bits, interpret=_interpret()) - qmax
