"""Kernel micro-benchmarks: us/call of the jnp reference paths at FL-client
scales (CPU timings; the Pallas kernels themselves are TPU-targeted and
interpret-mode timing is not meaningful — what we measure here is the
ALGORITHMIC win of threshold-selection over sort-based top-k and of the
packed cohort pipeline over the per-leaf loop, which holds on any
backend).  Selection *quality* (achieved-k vs requested k) is measured
through the 3-pass oracle ``select_tau_ref`` / the packed counts — a row
whose over-selection exceeds the kernel's published ``overselect_bound``
FAILS the run (raise, not a log line): the benchmark doubles as the
contract's regression gate.

Byte models come from ``repro.roofline`` (single source of truth shared
with the roofline projections — docs/benchmarks.md §4).

Row groups (BENCH_kernels.json):

* ``topk_sort`` / ``topk_threshold``       — per-leaf selection at flat n
* ``ssm_apply_ef_fused``                   — per-leaf fused apply at flat n
* ``packed_select`` / ``packed_apply_ef``  — the packed cohort kernels'
  scan-form oracles at flat n (single segment)
* ``compress_perleaf_<model>`` / ``compress_packed_<model>`` — END TO END
  compress of a real smoke pytree: the per-leaf loop (4 launches/leaf on
  TPU) vs the packed two-launch pipeline, same arithmetic, bit-identical
  outputs.  ``launches``/``leaves`` record the launch accounting.
* ``wirepack_*``                           — word-level wire encode/decode
  (the bit-packing the transport actually ships) at flat n
* ``uplink_bytes_dense_<model>`` / ``uplink_bytes_wire_<model>`` — the
  transported-bytes ledger on a real smoke pytree: dense f32 planes vs
  the measured WirePayload (``bytes_moved`` is the payload size; the
  wire row's ``speedup_vs_reference`` is the byte reduction).  A
  reduction below 8x at alpha=0.01 FAILS the run.

``run(json_out=True)`` additionally emits the schema-versioned
``BENCH_kernels.json`` artifact (schema: docs/benchmarks.md, enforced by
``benchmarks.common.validate_bench``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import row_builder, write_bench_json, write_csv
from repro.core import sparsify as S
from repro.kernels.packed_topk.ref import packed_apply_ef_ref, \
    packed_hist_ref, refine_taus
from repro.kernels.ssm_apply.ref import ssm_apply_ef_ref
from repro.kernels.topk_mask.ops import overselect_bound
from repro.kernels.topk_mask.ref import log2_taus, select_tau_ref
from repro.kernels.wirepack.ref import pack_bbit_ref, pack_mask_bits_ref, \
    unpack_mask_bits_ref
from repro.roofline import fused_apply_bytes, fused_compress_bytes, \
    packed_apply_bytes, packed_compress_bytes, packed_select_bytes, \
    selection_bytes

E2E_CONFIGS = ("whisper-base", "starcoder2-3b")


def _time(fn, *args, iters=5, best=False):
    # ONE warmup call (compile + first run); block on its full pytree.
    # (A previous version probed the output with isinstance(fn(*args), ..)
    # which invoked fn a second time during warmup.)  ``best=True`` takes
    # the minimum over iters instead of the mean — the standard noise
    # floor for the multi-ms end-to-end rows, whose CPU timings jitter
    # far more than the flat micro rows.
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return (min(ts) if best else sum(ts) / len(ts)) * 1e6


def _check_overselect(name: str, n: int, k: int, achieved: int):
    """Hard gate: a benchmark row violating the kernel's published
    over-selection bound fails the whole run — the bound is part of the
    selection contract (docs/kernels.md), not a soft metric."""
    bound = overselect_bound(k, n)
    if achieved - k > bound:
        raise RuntimeError(
            f"benchmark row {name!r}: achieved_k={achieved} exceeds "
            f"k={k} by {achieved - k} > overselect_bound={bound} (n={n})")


def _packed_flat_standins(x, k: int):
    """Single-segment packed pipeline over flat x, as the jit-able
    scan-form oracles (the CPU stand-in for the two TPU launches)."""
    layout = S.plan_packed_layout([x])
    seg_ids = layout.seg_ids
    ks = jnp.asarray([k], jnp.float32)
    ns = jnp.asarray([x.size], jnp.float32)

    def select(xp):
        am = jnp.max(jnp.abs(xp.astype(jnp.float32)))
        edges = log2_taus(am).reshape(1, -1)
        c1 = packed_hist_ref(xp, seg_ids, edges)
        return refine_taus(c1, edges, [am], ks)

    def apply_(taus2, wp, mp, vp):
        return packed_apply_ef_ref(taus2, seg_ids, ks, ns, (wp, mp, vp),
                                   value_dtype="bfloat16")

    return layout, select, apply_


def _tree_standins(tree, alpha: float):
    """End-to-end compress of a pytree under the ssm_w rule, both ways:
    the per-leaf loop (select + fused apply per leaf — 4 TPU launches
    each) and the packed cohort pipeline (2 launches total).  Both are
    the jnp oracles the kernels are tested bit-identical to, so this
    times the same arithmetic the TPU paths run."""
    leaves, _ = jax.tree_util.tree_flatten(tree)
    layout = S.plan_packed_layout(leaves)
    ks_list = [S.k_for(leaf.size, alpha) for leaf in leaves]
    ks = jnp.asarray(ks_list, jnp.float32)
    ns = jnp.asarray(layout.seg_sizes, jnp.float32)

    def perleaf(wl, ml, vl):
        out = []
        for w, m, v, k in zip(wl, ml, vl, ks_list):
            tau = select_tau_ref(w, k)
            out.append(ssm_apply_ef_ref(tau, w, m, v,
                                        value_dtype="bfloat16"))
        return out

    def packed(wl, ml, vl):
        wp, mp, vp = layout.pack(wl), layout.pack(ml), layout.pack(vl)
        absmax = [jnp.max(jnp.abs(w.astype(jnp.float32))) for w in wl]
        edges = jnp.stack([log2_taus(a) for a in absmax])
        c1 = packed_hist_ref(wp, layout.seg_ids, edges)
        taus2 = refine_taus(c1, edges, absmax, ks)
        outs = packed_apply_ef_ref(taus2, layout.seg_ids, ks, ns,
                                   (wp, mp, vp), value_dtype="bfloat16")
        return [layout.unpack(o) for o in outs[:4]] + [outs[-1]]

    return layout, perleaf, packed, ks_list


def _e2e_rows(add, alpha: float):
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import abstract_params, params as PM

    for cname in E2E_CONFIGS:
        cfg = reduce_for_smoke(get_config(cname))
        sds = PM.abstract(abstract_params(cfg), "float32")
        leaves, treedef = jax.tree_util.tree_flatten(sds)
        keys = jax.random.split(jax.random.PRNGKey(0),
                                3 * len(leaves)).reshape(3, len(leaves), 2)
        mk = lambda row, scale: [
            jax.random.normal(kk, l.shape, jnp.float32) * scale
            for kk, l in zip(row, leaves)]
        wl, ml = mk(keys[0], 1.0), mk(keys[1], 0.1)
        vl = [jnp.abs(v) for v in mk(keys[2], 0.01)]

        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        layout, perleaf, packed, ks_list = _tree_standins(tree, alpha)
        L = layout.num_leaves
        d = sum(layout.sizes)
        k = sum(ks_list)

        perleaf_fn = jax.jit(perleaf)
        packed_fn = jax.jit(packed)
        t_perleaf = _time(perleaf_fn, wl, ml, vl, iters=10, best=True)
        t_packed = _time(packed_fn, wl, ml, vl, iters=10, best=True)

        outs = packed_fn(wl, ml, vl)
        achieved = int(sum(float(c) for c in outs[-1][:, 0]))
        for leaf_k, leaf_n, cnt in zip(ks_list, layout.sizes,
                                       [float(c) for c in outs[-1][:, 0]]):
            _check_overselect(f"compress_packed_{cname}", leaf_n, leaf_k,
                              int(cnt))

        label = cname.replace("-", "_")
        add(f"compress_perleaf_{label}", d, t_perleaf, k=k,
            launches=4 * L, leaves=L,
            bytes_moved=sum(fused_compress_bytes(n)
                            for n in layout.sizes),
            speedup_vs_reference=1.0)
        add(f"compress_packed_{label}", d, t_packed,
            f"speedup={t_perleaf / t_packed:.2f}x", k=k,
            achieved_k=achieved, launches=2, leaves=L,
            bytes_moved=packed_compress_bytes(d),
            speedup_vs_reference=round(t_perleaf / t_packed, 3))


def _wire_rows(add, alpha: float):
    """Transported-bytes ledger on the smoke pytrees: ravel-dense f32
    planes vs the WirePayload the SSM compressor actually ships.
    ``bytes_moved`` is MEASURED from the payload arrays (and cross-checked
    against the static layout math); us_per_call times the jitted encode.
    The >=8x byte reduction at alpha=0.01 is a hard gate — padding or
    capacity regressions in the wire layout fail the benchmark run."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.core import wire
    from repro.models import abstract_params, params as PM

    for cname in E2E_CONFIGS:
        cfg = reduce_for_smoke(get_config(cname))
        sds = PM.abstract(abstract_params(cfg), "float32")
        leaves, treedef = jax.tree_util.tree_flatten(sds)
        keys = jax.random.split(jax.random.PRNGKey(3),
                                3 * len(leaves)).reshape(3, len(leaves), 2)
        trees = [jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(kk, l.shape, jnp.float32)
            for kk, l in zip(row, leaves)]) for row in keys]
        mask = jax.tree_util.tree_unflatten(treedef, [
            S.topk_mask_exact(w, S.k_for(w.size, alpha))
            if w.size <= S.BLOCK else S.blocked_topk_mask(w, alpha)
            for w in jax.tree_util.tree_leaves(trees[0])])
        sW, sM, sV = (jax.tree_util.tree_map(
            lambda x, m: x * m, t, mask) for t in trees)

        sizes = tuple(l.size for l in leaves)
        d = sum(sizes)
        cap = wire.mask_leaf_capacities(sizes, alpha)

        dense_fn = jax.jit(lambda a, b, c: wire.pack_dense((a, b, c)))
        t_dense = _time(dense_fn, sW, sM, sV)
        dense_bytes = wire.payload_nbytes(dense_fn(sW, sM, sV))
        assert 8 * dense_bytes == wire.dense_wire_bits(sizes, 3)

        wire_fn = jax.jit(
            lambda a, b, c: wire.pack_shared_mask(a, b, c, cap)[0])
        t_wire = _time(wire_fn, sW, sM, sV)
        wire_bytes = wire.payload_nbytes(wire_fn(sW, sM, sV))
        assert 8 * wire_bytes == wire.mask_wire_bits(sizes, alpha)

        ratio = dense_bytes / wire_bytes
        if ratio < 8.0:
            raise RuntimeError(
                f"uplink_bytes_wire_{cname}: {wire_bytes} B is only "
                f"{ratio:.2f}x below dense {dense_bytes} B "
                f"(alpha={alpha}; wire-format regression)")

        label = cname.replace("-", "_")
        add(f"uplink_bytes_dense_{label}", d, t_dense,
            bytes_moved=dense_bytes, speedup_vs_reference=1.0)
        add(f"uplink_bytes_wire_{label}", d, t_wire,
            f"reduction={ratio:.1f}x", bytes_moved=wire_bytes,
            speedup_vs_reference=round(ratio, 3))


def run(sizes=(1 << 16, 1 << 20, 1 << 23), alpha=0.05, json_out=False):
    rows, jrows = [], []
    add = row_builder(rows, jrows)

    for n in sizes:
        x = jax.random.normal(jax.random.PRNGKey(0), (n,))
        k = S.k_for(n, alpha)
        sort_fn = jax.jit(lambda v: S.topk_mask_exact(v, k))
        thr_fn = jax.jit(lambda v: S.topk_mask_threshold(v, k))
        t_sort = _time(sort_fn, x)
        t_thr = _time(thr_fn, x)

        # selection quality of the kernel's 3-pass algorithm, via the
        # bit-identical jnp oracle (cheap at any n)
        tau = select_tau_ref(x, k)
        achieved = int(jnp.sum(jnp.abs(x) >= tau))
        over = (achieved - k) / k
        _check_overselect("topk_threshold", n, k, achieved)

        add("topk_sort", n, t_sort, k=k, speedup_vs_reference=1.0)
        add("topk_threshold", n, t_thr,
            f"speedup={t_sort / t_thr:.2f}x",
            k=k, achieved_k=achieved, overselect_frac=round(over, 5),
            bytes_moved=selection_bytes(n),
            gb_per_s=round(selection_bytes(n) / (t_thr * 1e-6) / 1e9, 3),
            speedup_vs_reference=round(t_sort / t_thr, 3))

        # fused compress arithmetic (what ssm_apply_ef streams in one
        # pass), timed as the composed jnp expression
        keys = jax.random.split(jax.random.PRNGKey(1), 2)
        dm, dv = (jax.random.normal(kk, (n,)) for kk in keys)
        fused_fn = jax.jit(lambda w, m, v: ssm_apply_ef_ref(
            tau, w, m, v, value_dtype="bfloat16"))
        t_fused = _time(fused_fn, x, dm, dv)
        add("ssm_apply_ef_fused", n, t_fused,
            bytes_moved=fused_apply_bytes(n),
            gb_per_s=round(fused_apply_bytes(n) / (t_fused * 1e-6) / 1e9,
                           3))

        # the packed cohort kernels' scan-form oracles (single segment):
        # launch 1 (histogram + host refine) and launch 2 (two-sweep
        # refine-count + tau-pick + apply)
        layout1, sel, app = _packed_flat_standins(x, k)
        xp = layout1.pack([x])
        wp, mp, vp = xp, layout1.pack([dm]), layout1.pack([dv])
        sel_fn = jax.jit(sel)
        t_psel = _time(sel_fn, xp)
        taus2 = sel_fn(xp)
        app_fn = jax.jit(app)
        t_papp = _time(app_fn, taus2, wp, mp, vp)
        pouts = app_fn(taus2, wp, mp, vp)
        pach = int(float(pouts[-1][0, 0]))
        _check_overselect("packed_apply_ef", n, k, pach)
        add("packed_select", n, t_psel, k=k,
            bytes_moved=packed_select_bytes(n),
            gb_per_s=round(packed_select_bytes(n) / (t_psel * 1e-6) / 1e9,
                           3),
            launches=1)
        add("packed_apply_ef", n, t_papp, k=k, achieved_k=pach,
            overselect_frac=round((pach - k) / k, 5),
            bytes_moved=packed_apply_bytes(n),
            gb_per_s=round(packed_apply_bytes(n) / (t_papp * 1e-6) / 1e9,
                           3),
            launches=1)

        # word-level wire encode/decode (the ref oracles the Pallas
        # kernels are bitwise-tested against): bitmap pack/unpack and
        # 8-bit code pack over the (n/128, 128) aligned buffer
        sup = (jnp.abs(x) >= tau).astype(jnp.int32).reshape(-1, 128)
        pm_fn = jax.jit(pack_mask_bits_ref)
        t_pm = _time(pm_fn, sup)
        words = pm_fn(sup)
        um_fn = jax.jit(unpack_mask_bits_ref)
        t_um = _time(um_fn, words)
        codes = jax.random.randint(jax.random.PRNGKey(2), sup.shape,
                                   0, 256, jnp.int32)
        pb_fn = jax.jit(lambda c: pack_bbit_ref(c - 127, 8))
        t_pb = _time(pb_fn, codes)
        add("wirepack_pack_mask", n, t_pm, bytes_moved=4 * n + n // 8,
            gb_per_s=round((4 * n + n // 8) / (t_pm * 1e-6) / 1e9, 3))
        add("wirepack_unpack_mask", n, t_um, bytes_moved=4 * n + n // 8,
            gb_per_s=round((4 * n + n // 8) / (t_um * 1e-6) / 1e9, 3))
        add("wirepack_pack_bbit8", n, t_pb, bytes_moved=5 * n,
            gb_per_s=round(5 * n / (t_pb * 1e-6) / 1e9, 3))

    _e2e_rows(add, alpha)
    _wire_rows(add, alpha=0.01)

    write_csv("kernel_bench", ("name", "n", "us_per_call", "derived"), rows)
    if json_out:
        write_bench_json("kernels", jrows)
    return rows


if __name__ == "__main__":
    for r in run(json_out=True):
        print(",".join(str(c) for c in r))
