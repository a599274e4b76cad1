"""Packed multi-leaf threshold selection + fused apply — 2 launches/cohort.

The per-leaf hot path (kernels/topk_mask + kernels/ssm_apply) costs 4
Pallas launches PER PYTREE LEAF (absmax, 2 count passes, fused apply): a
whisper-base client pays ~100 kernel round trips per round.  These
kernels batch every leaf of the (score, dW, dM, dV) cohort through ONE
tile-aligned packed buffer (layout: core/sparsify.PackedLayout) so the
whole-model compress is exactly TWO launches:

  launch 1 (``_hist_kernel``)  — segmented log2 histogram: each (8, 128)
      block accumulates count(|x| >= edge_j) for its segment's 32 bin
      edges into an SMEM (L, 32) accumulator (rows = segments; one row
      for scope="global"), indexed by the prefetched segment id.
  host refine (no launch)      — the CDF bracket (first bin with count
      >= k) and the 32 linear-refine candidates are derived from the
      (L, 32) histogram with the SAME eager jnp arithmetic as the
      per-leaf ``select_tau_kernel``, so the candidate taus are
      bit-identical to the per-leaf path's.
  launch 2 (``_make_apply_kernel``) — a (2, nb) two-sweep grid: sweep 0
      counts |score| against the refine candidates into SMEM scratch;
      sweep 1 PICKS tau per segment from the completed counts
      (a select, not arithmetic — so tau is bit-exact vs per-leaf) and
      streams mask-apply x3 + ``value_dtype`` wire cast + error-feedback
      residual, extending kernels/ssm_apply's fused structure.

Why the tau *pick* lives in the kernel: deriving tau needs the refine
counts, which need a full pass over the data — folding that pass into
the apply launch (sweep 0) is what collapses selection+apply to one
launch without giving up the 3-pass algorithm's ``overselect_bound``
contract.  The w/m/v streams use a ``(i * p, 0)`` index map so sweep 0
re-fetches only block 0 (revisited = free) instead of streaming the
whole tensor twice; only the score stream is read in both sweeps.

Padding is inert: per-leaf zero padding never counts (all candidate
edges are > 0 unless a segment is all-zero, where tau = 0 anyway) and
never survives the mask for tau > 0.  Counts accumulate in f32 — exact
integers below 2^24 per block, add-chain error << 1 count to d <= 2^40
(same argument as kernels/topk_mask).  Contract and launch accounting:
docs/kernels.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One packed block = the (8, 128) f32 min tile; per-leaf padding rounds
# to BLOCK_ELEMS, so small leaves waste at most one tile each (vs one
# (8, 1024) super-tile per leaf on the per-leaf path).
LANES = 128
SUBLANES = 8
BLOCK = (SUBLANES, LANES)
BLOCK_ELEMS = SUBLANES * LANES
N_BINS = 32


def _zero_smem(ref):
    def body(r, carry):
        ref[r] = jnp.float32(0)
        return carry
    lax.fori_loop(0, ref.shape[0], body, 0)


def _hist_kernel(seg_ref, e_ref, x_ref, c_ref):
    i = pl.program_id(0)
    base = seg_ref[i] * N_BINS
    a = jnp.abs(x_ref[...].astype(jnp.float32))

    @pl.when(i == 0)
    def _init():
        _zero_smem(c_ref)

    # unrolled over the N_BINS candidates: VPU reductions in registers,
    # each added into this segment's SMEM histogram row
    for j in range(N_BINS):
        c_ref[base + j] += jnp.sum((a >= e_ref[base + j]).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def packed_hist_2d(xp, seg_ids, edges, *, interpret: bool = True):
    """Segmented histogram over a packed (R, LANES) buffer.

    ``seg_ids``: (nb,) int32 segment of each (8, 128) block (scalar
    prefetch); ``edges``: (L, N_BINS) descending per-segment candidates.
    Returns (L, N_BINS) f32 counts of |x| >= edge_j per segment.  ONE
    launch.  Edges and counts live flat in SMEM, indexed by segment.
    """
    nb = xp.shape[0] // SUBLANES
    L = edges.shape[0]
    counts = pl.pallas_call(
        _hist_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(BLOCK, lambda i, seg: (i, 0)),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((L * N_BINS,), jnp.float32),
        interpret=interpret,
    )(seg_ids, edges.reshape(-1), xp)
    return counts.reshape(L, N_BINS)


def _make_apply_kernel(n_streams: int, has_score: bool,
                       with_residual: bool, value_dtype):
    """Two-sweep fused kernel body.  Static shape:
    scalar prefetch  seg_ids, ks, ns
    inputs           taus2 row, [score?], x_0 .. x_{n_streams-1}
    outputs          s_0 .. s_{n_streams-1}, [err?], taus, counts
    scratch          (L * N_BINS,) SMEM refine-count accumulator

    The candidates, the counts and the picked taus/counts are scalars
    indexed by segment, so all of them live flat in SMEM.

    Sweep p=0 counts |score| >= taus2_j into the scratch row of this
    block's segment; sweep p=1 picks tau (first candidate whose count
    reaches k — exactly the per-leaf selection rule, ties included),
    then applies keep/cast/residual to every stream.  ``err`` is the
    residual of stream 0 (dW), matching ssm_apply_ef's contract."""
    vdt = None if value_dtype is None else jnp.dtype(value_dtype)

    def cast(x):
        return x if vdt is None else x.astype(vdt).astype(x.dtype)

    def kernel(seg_ref, ks_ref, ns_ref, t2_ref, *refs):
        *io, c2_ref = refs
        if has_score:
            score_ref, io = io[0], io[1:]
        ins, outs = io[:n_streams], io[n_streams:]
        if not has_score:
            score_ref = ins[0]
        p = pl.program_id(0)
        i = pl.program_id(1)
        seg = seg_ref[i]
        base = seg * N_BINS
        a = jnp.abs(score_ref[...].astype(jnp.float32))

        @pl.when((p == 0) & (i == 0))
        def _init():
            _zero_smem(c2_ref)

        @pl.when(p == 0)
        def _count():
            for j in range(N_BINS):
                c2_ref[base + j] += jnp.sum(
                    (a >= t2_ref[base + j]).astype(jnp.float32))

        @pl.when(p == 1)
        def _apply():
            k = ks_ref[seg]
            n = ns_ref[seg]
            # first candidate whose count reaches k (candidate 0 when
            # none does), by scalar selects — a pick, not arithmetic, so
            # tau is bitwise one of the prefetched candidates (the
            # bit-exactness hinge; see module docstring)
            tau = t2_ref[base]
            cnt = c2_ref[base]
            for j in reversed(range(N_BINS)):
                c_j = c2_ref[base + j]
                hit = c_j >= k
                tau = jnp.where(hit, t2_ref[base + j], tau)
                cnt = jnp.where(hit, c_j, cnt)
            tau = jnp.where(k >= n, jnp.float32(0), tau)
            cnt = jnp.where(k >= n, n, cnt)

            keep = a >= tau
            x0 = ins[0][...]
            zero = jnp.zeros((), x0.dtype)
            s0 = jnp.where(keep, cast(x0), zero)
            outs[0][...] = s0
            for t in range(1, n_streams):
                outs[t][...] = jnp.where(
                    keep, cast(ins[t][...]),
                    jnp.zeros((), ins[t].dtype))
            nxt = n_streams
            if with_residual:
                outs[nxt][...] = (x0.astype(jnp.float32)
                                  - s0.astype(jnp.float32)).astype(x0.dtype)
                nxt += 1
            outs[nxt][seg] = tau
            outs[nxt + 1][seg] = cnt

    return kernel


@functools.partial(jax.jit, static_argnames=("with_residual", "value_dtype",
                                             "interpret"))
def packed_apply_2d(taus2, seg_ids, ks, ns, streams, sp=None, *,
                    with_residual: bool = True, value_dtype=None,
                    interpret: bool = True):
    """Two-sweep fused refine-count + tau-pick + mask-apply.  ONE launch.

    ``streams``: tuple of packed (R, LANES) buffers sharing the mask
    (the (dW, dM, dV) triple for the shared-mask compress; a 1-tuple
    for the independent compress, where every stream is its own score).
    ``sp``: optional packed score buffer (non-ssm_w rules).  Returns
    ``(*sparse_streams, [err], taus (L, 1), counts (L, 1))``; ``err``
    is stream 0's error-feedback residual.
    """
    streams = tuple(streams)
    n_streams = len(streams)
    nb = streams[0].shape[0] // SUBLANES
    L = ks.shape[0]
    has_score = sp is not None
    # the count sweep (p=0) reads only the score stream; w/m/v index
    # maps collapse to block 0 there so their HBM traffic happens once
    stream_spec = pl.BlockSpec(BLOCK, lambda p, i, *s: (i, 0))
    lazy_spec = pl.BlockSpec(BLOCK, lambda p, i, *s: (i * p, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    ins = ([sp] if has_score else []) + list(streams)
    in_specs = [smem]
    if has_score:
        in_specs += [stream_spec] + [lazy_spec] * n_streams
    else:
        in_specs += [stream_spec] + [lazy_spec] * (n_streams - 1)
    n_data_out = n_streams + (1 if with_residual else 0)
    out_specs = tuple([lazy_spec] * n_data_out + [smem, smem])
    out_shape = tuple(
        jax.ShapeDtypeStruct(t.shape, t.dtype)
        for t in streams + ((streams[0],) if with_residual else ())
    ) + (jax.ShapeDtypeStruct((L,), jnp.float32),) * 2
    *data, taus, counts = pl.pallas_call(
        _make_apply_kernel(n_streams, has_score, with_residual, value_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(2, nb),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.SMEM((L * N_BINS,), jnp.float32)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(seg_ids, ks, ns, taus2.reshape(-1), *ins)
    return (*data, taus.reshape(L, 1), counts.reshape(L, 1))
