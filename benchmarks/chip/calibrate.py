"""Upper readings for a cell's limits: the control and the planted faults,
each put in the program's place and compared with the reference, at the
cell's own sizes, on the chip.

    python3 benchmarks/chip/calibrate.py --workload whisper-base.ssm-bisect.c4-l2 \\
        --seeds 11,12,13 [--variants control,half_batch,unchanged]
    python3 benchmarks/chip/calibrate.py --config CONFIG.json \\
        --traffic MIX.json --seeds 11 --variants ""

``control`` is the reference computed with float8 matmul operands, one
precision step below the configuration's bfloat16; ``half_batch`` trains
each client on half of its batch; ``unchanged`` returns the state it was
given.  Prints one JSON line per seed and variant with the numbers of
``check.numbers``, and one per seed with the reference's readings, its
seconds and the chip's peak bytes in use so far (with ``--variants ""``
the reference alone: how a configuration that no cell names yet is
sized).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench
import check
import reference

VARIANTS = {"control": ("fp8", None), "half_batch": ("f32", "half_batch"),
            "unchanged": ("f32", "unchanged")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    bench.add_cell_args(ap)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args(argv)
    cell = bench.cell_from_args(ap, args)
    devs = bench.devices(cell["chips"])
    bench.enable_cache()
    c, mix = cell["c"], cell["mix"]
    n = mix["check_rounds"]
    for seed in map(int, args.seeds.split(",")):
        batches = bench.device_batches(cell, seed)
        t0 = time.perf_counter()
        ref = reference.run(c, mix, seed, batches, n)
        t_ref = time.perf_counter() - t0
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "reference_s": t_ref, "peak_bytes_in_use": peak,
                          "reference": ref}), flush=True)
        for v in filter(None, args.variants.split(",")):
            mode, fault = VARIANTS[v]
            got = reference.run(c, mix, seed, batches, n, mode, fault)
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "variant": v, "reference_s": t_ref,
                              "numbers": check.numbers(got, ref),
                              "readings": got, "reference": ref}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
