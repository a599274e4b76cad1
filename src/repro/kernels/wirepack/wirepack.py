"""Bit-pack / bit-unpack Pallas kernels for the wire format.

The uplink wire format (core/wire.py) ships b-bit unsigned codes packed
into uint32 words: mask bitmaps (b=1, FedAdam-SSM Section IV), sign
bitplanes (b=1, 1-bit Adam, arXiv 2109.05109), and b-bit quantizer
codes (b in {2, 4, 8}, Efficient-Adam, arXiv 2205.02719).  These two
kernels are the only data-touching passes — everything scheme-specific
(code construction, scales, value compaction) is cheap jnp around them.

Layout.  Input is the (R, 128) packed cohort buffer convention of
``core/sparsify.PackedLayout`` with R a multiple of 32 (the wire's
alignment quantum: 32 sublanes x 128 lanes = 4096 codes).  Each group of
T = 32 // b code rows collapses into one word row::

    word[q, c] = sum_t code[q*T + t, c] * 2**(t*b)      (uint32)

so the word buffer is exactly ``R * b / 32`` rows — bits on the wire ==
b bits per code, by construction.  Codes must already be unsigned in
[0, 2**b); the ops layer owns the signed-offset / sign-bit conversions.

Blocking.  A grid step handles one full (8, 128) uint32 word tile, i.e.
``8 * T = 256 / b`` code rows; code row ``t`` of each of the 8 word rows
is one sublane-strided slice (stride T).  The jitted wrappers pad the
buffer to whole word tiles before the launch and slice the padding off
after it, so the word layout above (and every payload byte built on
it) does not depend on the block size.

Words accumulate in uint32: at b=8 the top code contributes
``255 << 24``, which overflows int32 but is exact in uint32 (all
multiplies are by static powers of two, so packing is lossless and
``unpack(pack(x)) == x`` bitwise).  Oracles: ref.py; parity:
tests/test_kernels.py; format spec: docs/wire.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
#: Wire alignment quantum: code rows per word-row group of every width.
CODE_SUBLANES = 32
#: Word rows per grid step: one full (8, 128) uint32 tile.
WORD_SUBLANES = 8
#: Word size on the wire.
WORD_BITS = 32
#: Supported code widths (32 must divide evenly into b-bit lanes).
SUPPORTED_BITS = (1, 2, 4, 8)


def _check_bits(bits: int) -> int:
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits must be one of {SUPPORTED_BITS}, got {bits}")
    return WORD_BITS // bits


def _make_pack_kernel(bits: int):
    T = _check_bits(bits)

    def kernel(x_ref, w_ref):
        # x_ref: (8*T, LANES) codes; w_ref: (8, LANES) words
        acc = jnp.zeros((WORD_SUBLANES, LANES), jnp.uint32)
        for t in range(T):
            xt = x_ref[pl.ds(t, WORD_SUBLANES, stride=T), :]
            acc = acc + xt.astype(jnp.uint32) * jnp.uint32(1 << (t * bits))
        w_ref[...] = acc

    return kernel


def _make_unpack_kernel(bits: int):
    T = _check_bits(bits)
    mask = (1 << bits) - 1

    def kernel(w_ref, x_ref):
        w = w_ref[...]                               # (8, LANES) uint32
        for t in range(T):
            x_ref[pl.ds(t, WORD_SUBLANES, stride=T), :] = (
                (w >> jnp.uint32(t * bits)) & jnp.uint32(mask)
            ).astype(jnp.int32)

    return kernel


def _pad_rows(x, quantum: int):
    pad = (-x.shape[0]) % quantum
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack_words_2d(codes, *, bits: int, interpret: bool = True):
    """Pack an (R, LANES) int32 unsigned-code buffer (R % 32 == 0, codes
    in [0, 2**bits)) into an (R * bits / 32, LANES) uint32 word buffer.
    ONE launch."""
    T = _check_bits(bits)
    n_words = codes.shape[0] * bits // WORD_BITS
    xp = _pad_rows(codes, WORD_SUBLANES * T)
    nb = xp.shape[0] // (WORD_SUBLANES * T)
    words = pl.pallas_call(
        _make_pack_kernel(bits),
        grid=(nb,),
        in_specs=[pl.BlockSpec((WORD_SUBLANES * T, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((WORD_SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * WORD_SUBLANES, LANES),
                                       jnp.uint32),
        interpret=interpret,
    )(xp)
    return words[:n_words]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def unpack_words_2d(words, *, bits: int, interpret: bool = True):
    """Exact inverse of :func:`pack_words_2d`: (R * bits / 32, LANES)
    uint32 words back to (R, LANES) int32 unsigned codes.  ONE launch."""
    T = _check_bits(bits)
    n_codes = words.shape[0] * T
    wp = _pad_rows(words, WORD_SUBLANES)
    nb = wp.shape[0] // WORD_SUBLANES
    codes = pl.pallas_call(
        _make_unpack_kernel(bits),
        grid=(nb,),
        in_specs=[pl.BlockSpec((WORD_SUBLANES, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((WORD_SUBLANES * T, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * WORD_SUBLANES * T, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(wp)
    return codes[:n_codes]
