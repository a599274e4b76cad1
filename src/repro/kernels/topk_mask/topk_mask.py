"""Top-k threshold selection kernel — the paper's Top_k sparsifier hot-spot.

A sort-based top-k over d ~ 1e9..1e12 is O(d log d) compute and worse, it
is HBM-layout hostile (global sort = multi-pass shuffles).  The mask only
needs a *threshold* tau with count(|x| >= tau) ~ k.  TPU-native selection:

  pass 1 (absmax):   stream (8, 1024) VMEM tiles, per-grid-step running
                     max into a (1, 1) SMEM accumulator output.
  pass 2 (histogram): per tile, count |x| >= tau_j for 32 log2-spaced
                     candidates tau_j = absmax * 2^(-j/2); accumulate
                     counts into a (1, 32) SMEM output (f32 adds — counts to
                     2^24 exact per block, summed in f64-free streaming;
                     documented precision note in ops.py).
  pass 3 (refine):   32 linear candidates between the two bracketing
                     log2 levels; same kernel.
  apply:             mask = |x| >= tau (elementwise, fused downstream by
                     ssm_apply).

Each pass is one streaming read of x: O(d) total, no sort, no layout
change.  Count exactness: the final tau over-selects by at most the
refinement-bin width (<0.5% of k typical; contract bound
``ops.overselect_bound`` = 6% of k + 8); ties share the bin edge.  The
ops.py wrapper reports the achieved count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024
SUBLANES = 8
BLOCK = (SUBLANES, LANES)
N_BINS = 32


def _absmax_kernel(x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[0, 0] = jnp.float32(0)

    m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)))
    o_ref[0, 0] = jnp.maximum(o_ref[0, 0], m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def absmax_2d(x, *, interpret: bool = True):
    """x: (R, LANES) -> f32 scalar max|x|."""
    grid = (x.shape[0] // SUBLANES,)
    out = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(BLOCK, lambda i: (i, 0))],
        # a scalar running max the grid revisits every step: SMEM, since
        # the chip stores no scalars to VMEM
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(x)
    return out[0, 0]


def _count_kernel(taus_ref, x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for j in range(N_BINS):
            o_ref[0, j] = jnp.float32(0)

    a = jnp.abs(x_ref[...].astype(jnp.float32))
    # unrolled over the N_BINS candidates: VPU reductions in registers
    for j in range(N_BINS):
        cnt = jnp.sum((a >= taus_ref[j]).astype(jnp.float32))
        o_ref[0, j] += cnt


@functools.partial(jax.jit, static_argnames=("interpret",))
def count_ge_2d(taus, x, *, interpret: bool = True):
    """taus: f32[N_BINS] candidates; x: (R, LANES).
    Returns f32[N_BINS] counts of |x| >= tau_j."""
    grid = (x.shape[0] // SUBLANES,)
    out = pl.pallas_call(
        _count_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(BLOCK, lambda i, s: (i, 0))],
            # the (1, N_BINS) scalar counts are revisited every grid
            # step: SMEM, since the chip stores no scalars to VMEM
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((1, N_BINS), jnp.float32),
        interpret=interpret,
    )(taus, x)
    return out[0]


def _apply_kernel(tau_ref, x_ref, o_ref):
    a = jnp.abs(x_ref[...].astype(jnp.float32))
    o_ref[...] = (a >= tau_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_mask_2d(tau, x, *, interpret: bool = True):
    """mask = |x| >= tau as int8 (bool VMEM stores are int8-backed)."""
    grid = (x.shape[0] // SUBLANES,)
    return pl.pallas_call(
        _apply_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(BLOCK, lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec(BLOCK, lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int8),
        interpret=interpret,
    )(jnp.asarray([tau], jnp.float32), x)
