"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload whisper-base.ssm-bisect.c4-l2 \\
        --seed 1234 --seconds 10 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  Set-up builds the program's round
for the cell, makes the weights on the device from ``--seed``, compiles
(through the persistent compilation cache in ``.jax_cache`` at the
checkout's root, unless ``JAX_COMPILATION_CACHE_DIR`` names another) and
drives the compiled round through its first rounds, whose outputs the
reference checks once the window has closed.  The window then runs rounds
back to back, each blocked on the new state, until ``--seconds`` have
passed.  ``--trace 1`` records the window with the profiler and reports
the per-layer metrics in place of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``, each compared number beside its limit.
Without the chips the cell asks for it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import check  # noqa: E402
import counters  # noqa: E402
import reference  # noqa: E402
import scopes  # noqa: E402
import trace as tr  # noqa: E402
import traffic  # noqa: E402


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a run needs of cell ``name``, found by name from
    ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {
        "name": name, "chips": w["chips"],
        "c": json.loads((root / conf["file"]).read_text()),
        "mix": json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                          .read_text()),
        "limits": check.limits(name),
        "end_to_end": [m["name"] for m in end_to_end],
        "per_layer": [m["name"] for m in per_layer],
    }


def add_cell_args(ap) -> None:
    """``--workload NAME``, or ``--config FILE --traffic FILE`` for a
    configuration and a traffic mix that no cell names yet."""
    name = ap.add_mutually_exclusive_group(required=True)
    name.add_argument("--workload", help="a cell of BENCHMARK.json")
    name.add_argument("--config", help="a configuration file (with "
                      "--traffic) that no cell names yet")
    ap.add_argument("--traffic", help="the traffic mix file of --config")


def cell_from_args(ap, args) -> dict:
    """The cell that :func:`add_cell_args`' arguments name; one from files
    runs on one chip and has no limits."""
    if args.workload:
        return load_cell(args.workload)
    if not args.traffic:
        ap.error("--config needs --traffic")
    c = json.loads(Path(args.config).read_text())
    return {"name": f"{c['name']}.{Path(args.traffic).stem}", "chips": 1,
            "c": c, "mix": json.loads(Path(args.traffic).read_text()),
            "limits": {}, "end_to_end": [], "per_layer": []}


def devices(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _units(kind: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: m["unit"] for m in bench[kind]}


def device_batches(cell: dict, seed: int):
    import jax
    c, mix = cell["c"], cell["mix"]
    return [jax.device_put(traffic.client_batches(
        mix, c["vocab_size"], c["hidden_size"], c["encoder_frames"], seed, r))
        for r in range(mix["batches"])]


def run(cell: dict, seed: int, seconds: float, traced: bool,
        require_chip: bool = True, units=None) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import program

    devs = devices(cell["chips"], require_chip)
    enable_cache()
    c, mix = cell["c"], cell["mix"]
    n_check = mix["check_rounds"]

    # -- set-up: the program, its weights, its batches, its first rounds
    prog = program.Program(c, mix, seed, check=require_chip)
    batches = device_batches(cell, seed)
    prog.compile(batches[0])
    losses, m1, mets = [], None, None
    for r in range(n_check):
        mets = prog.run_round(batches[r])
        jax.block_until_ready(prog.state)
        losses.append([float(x) for x in jax.device_get(mets["loss"])])
        if r == 0:
            m1 = prog.m_norms()
    readings = {"losses": losses, "m1_norms": m1,
                "dw_norms": prog.change_norms()}
    uplink = float(mets["uplink_bits"]) / 8 / prog.active
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s!r} s; first-round losses {losses[0]}")

    # -- the window
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        jax.profiler.start_trace(tdir)
    rounds, failed = 0, 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.batch"):
                b = batches[(n_check + rounds) % len(batches)]
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                mets = prog.run_round(b)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(prog.state)
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    failed = sum(not math.isfinite(float(x))
                 for x in jax.device_get(mets["loss"]))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    log(f"window {window_s!r} s, {rounds} rounds; peak bytes {peak}")

    result = {"correct": False, "attempted": n_check + rounds,
              "failed": failed}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if traced:
        trace = load_trace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        hlo = prog.hlo_text()
        red = tr.reduce(trace, tr.hlo_sources(hlo), tr.layer_tables())
        staged = scopes.reduce(trace, *scopes.hlo_scopes(hlo))
        log(f"device seconds by layer over {rounds} round(s), "
            f"unattributed included: {red['layer_s']}; busy "
            f"{red['busy_s']!r} of {red['window_s']!r} s")
        log(f"device seconds by stage: {staged['scope_s']}; unscoped "
            f"{staged['unscoped_s']!r} s, inherited "
            f"{staged['inherited_s']!r} s")
        ctx = dict(red, rounds=rounds, chips=cell["chips"],
                   peak=counters.peaks(devs[0].device_kind),
                   model_flops_round=counters.round_model_flops(c, mix),
                   codec_least_bytes_round=counters.codec_least_bytes(
                       c, mix, uplink),
                   scope_s=staged["scope_s"],
                   counts=scopes.summed(scopes.client_counts(
                       jax.device_get(mets))))
        values = {m: _reader(m)(ctx) for m in cell["per_layer"]}
        metrics = {m: v for m, v in values.items() if v is not None}
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    else:
        metrics = {"round_s": window_s / rounds, "uplink_bytes": uplink,
                   "peak_hbm_bytes": float(peak), "setup_s": setup_s}
        metrics = {m: metrics[m] for m in cell["end_to_end"]}
    units = units or {}
    result["metrics"] = {m: {"value": v, "unit": units.get(m, "")}
                         for m, v in metrics.items()}
    result["device"] = dev

    # -- the reference, once the program's state is freed
    names = prog.leaf_names
    del prog, mets
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.run(c, mix, seed, batches, n_check)
    nums = check.numbers(readings, ref)
    ok, checks = check.judge(nums, cell["limits"])
    log(f"reference {time.perf_counter() - t_ref!r} s; numbers {nums}")
    log("readings " + json.dumps({"program": readings, "reference": ref}))
    for key in ("m1_norms", "dw_norms"):
        log(f"worst leaves of {key} (program, reference): " + ", ".join(
            f"{names[i]} {p!r} {r!r}"
            for i, p, r in check.worst_leaves(readings, ref, key)))
    result["correct"] = ok and failed == 0
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def load_trace(tdir: str) -> dict:
    paths = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    return tr.load(paths[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     units=_units("per_layer" if args.trace
                                  else "end_to_end"))
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
