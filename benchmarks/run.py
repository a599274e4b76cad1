"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # fast CPU suite
    PYTHONPATH=src python -m benchmarks.run --full     # larger models
    PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_*.json

Prints ``name,us_per_call,derived`` CSV lines (plus per-benchmark CSV
artifacts under experiments/benchmarks/).  With ``--json``, the kernels
and compress suites additionally write the schema-versioned perf
trajectory artifacts ``BENCH_kernels.json`` / ``BENCH_compress.json`` to
the working directory (schema: docs/benchmarks.md; CI validates them via
``python -m benchmarks.common``).
"""
from __future__ import annotations

import argparse
import sys
import time

SUITES = ("fig1", "fig2", "fig345", "kernels", "compress", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="bigger models / more rounds")
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SUITES))
    ap.add_argument("--json", action="store_true",
                    help="emit BENCH_*.json artifacts (kernels, compress)")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else set(SUITES)
    unknown = want - set(SUITES)
    if unknown:
        ap.error(f"unknown suite(s) {sorted(unknown)}; known: {SUITES}")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    rows = []

    def emit(name, us, derived):
        rows.append((name, us, derived))
        print(f"{name},{us:.1f},{derived}", flush=True)

    if "fig1" in want:
        from benchmarks import fig1_delta_magnitudes as F1
        t0 = time.time()
        out = F1.run(width=0.5 if args.full else 0.25,
                     local_epochs=10 if args.full else 5)
        emit("fig1_delta_magnitudes", (time.time() - t0) * 1e6,
             f"ordering_dW>dM>dV={out['magnitude_ordering_holds']};"
             f"mean_log10={ {k: round(v, 2) for k, v in out['mean_log10'].items()} }")

    if "fig2" in want:
        from benchmarks import fig2_table1_acc_vs_comm as F2
        t0 = time.time()
        summary = F2.run(rounds=30 if args.full else 18,
                         width=0.5 if args.full else 0.25)
        ssm_iid = summary[("cnn", "iid", "fedadam_ssm")]
        dense_iid = summary[("cnn", "iid", "fedadam")]
        speedup = (dense_iid["comm_to_target_mbit"]
                   / max(ssm_iid["comm_to_target_mbit"], 1e-9))
        emit("fig2_table1_cnn", (time.time() - t0) * 1e6,
             f"ssm_final_acc={ssm_iid['final_acc']:.3f};"
             f"comm_speedup_vs_fedadam={speedup:.2f}x")

    if "fig345" in want:
        from benchmarks import fig345_sweeps as F3
        t0 = time.time()
        F3.run_L(rounds=12)
        F3.run_lr(rounds=12)
        final = F3.run_alpha(rounds=12)
        emit("fig345_sweeps", (time.time() - t0) * 1e6,
             f"alpha_final_accs={ {k: round(v, 3) for k, v in final.items()} }")

    if "kernels" in want:
        from benchmarks import kernel_bench as KB
        t0 = time.time()
        out = KB.run(json_out=args.json)
        emit("kernel_bench", (time.time() - t0) * 1e6,
             f"rows={len(out)} (see experiments/benchmarks/kernel_bench.csv)")

    if "compress" in want:
        from benchmarks import compress_bench as CB
        t0 = time.time()
        out = CB.run(json_out=args.json, full=args.full)
        emit("compress_bench", (time.time() - t0) * 1e6,
             f"rows={len(out)} "
             "(see experiments/benchmarks/compress_bench.csv)")

    if "roofline" in want:
        from benchmarks import roofline_table as RT
        t0 = time.time()
        out = RT.run()
        emit("roofline_table", (time.time() - t0) * 1e6,
             f"ok={out['n_ok']};skip={out['n_skip']};err={out['n_err']};"
             f"bottlenecks={out['bottlenecks']}")


if __name__ == "__main__":
    main()
