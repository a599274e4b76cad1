"""Device time by the round's own stage names, and the wire's counters.

The program opens a ``jax.named_scope`` for each stage of its round
(``fl.round``, ``fl.local_train``, ``fl.compress``, ``fl.select``,
``fl.wire_encode``, ``fl.wire_decode``, ``fl.fold``, ``fl.server_step``)
and XLA keeps the name in each compiled instruction's ``op_name``
metadata.  :func:`hlo_scopes` reads the stage of each instruction from the
compiled round's HLO text: the innermost ``fl.<stage>`` component of its
``op_name``, looking through the ``jvp(...)``/``transpose(...)`` wrappers
that autodiff adds.  Instructions the compiler makes carry no ``op_name``
(the TPU compiler rewrites a ``cumsum`` into reduce-windows without
metadata, and adds copies); such an instruction inherits the stage of its
nearest consumer that has one, else of its nearest producer.
:func:`reduce` sums each device op's self time over the traced window
under its stage (``scope_s``, averaged over devices as ``trace.reduce``
averages ``layer_s``; ``inherited_s`` is the inherited part of it); ops
with no stage go to ``unscoped_s``.  A program without scopes reduces to
an empty ``scope_s``.

The round also reports three int32 counters per client beside ``loss``:
``mask_selected`` (entries of the mask's union support before the
per-leaf cap), ``mask_shipped`` (entries the payload's bitmaps mark) and
``mask_capacity`` (value slots the streams hold).  :func:`stage_metrics`
turns the scope seconds and the counters summed over a round's clients
into per-round numbers.

Run from the root of a checkout, on the chips the cell asks for::

    python3 benchmarks/chip/scopes.py \\
        --workload whisper-base.ssm-bisect.c4-l2 --seed 1234 --seconds 10 \\
        [--record FILE.json.gz]

It builds the cell as ``run.py`` does, runs its checked rounds, then one
window untraced and one traced, each ``--seconds`` long, and prints one
JSON line: both windows' seconds per round, the scope, inherited and
unscoped seconds (the unscoped also on stderr), the table attribution of
``trace.reduce`` over the same trace, each round's counters per client
(checked rounds, then the last round of each window) and
:func:`stage_metrics` of the traced window.  ``--record`` writes the
traced window's device op events, the stage of each of their
instructions, which of them inherited it, and the printed numbers (the
test data of ``tests/test_scopes.py``).
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace as tr  # noqa: E402

COUNT_KEYS = ("mask_selected", "mask_shipped", "mask_capacity")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(r"(?<![\w.])fl\.([a-z_]+)")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")


def stage_of(op_name: str) -> Optional[str]:
    """The innermost ``fl.<stage>`` of an op name, without the prefix
    (``".../fl.compress/fl.select/gt"`` -> ``"select"``), else None."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def _operands(rest: str) -> List[str]:
    """The instruction names in the operand list of an instruction's
    text after ``=`` (the parentheses after its opcode)."""
    m = _OPCODE.search(rest)
    if not m:
        return []
    i, depth = m.end() - 1, 0
    for j in range(i, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            return _NAME.findall(rest[i:j])
    return []


def hlo_scopes(hlo_text: str) -> Tuple[Dict[str, str], Set[str]]:
    """``({instruction name: stage}, inherited names)``: the stage of
    each instruction of the HLO text whose ``op_name`` holds one, and of
    each instruction without one that inherits it from its nearest
    consumer with a stage, else from its nearest producer (breadth
    first, the first in text order among equally near ones)."""
    own, operands = {}, {}
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if not m or "(" not in m.group(2):
            continue
        name, rest = m.group(1), m.group(2)
        operands[name] = _operands(rest.split(", metadata=")[0])
        op = _OP_NAME.search(rest)
        stage = stage_of(op.group(1)) if op else None
        if stage:
            own[name] = stage
    users = defaultdict(list)
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)
    stages = dict(own)
    for edges in (users, operands):
        frontier = [n for n in operands if n not in stages]
        while frontier:
            found = {}
            for n in frontier:
                near = [stages[e] for e in edges.get(n, ()) if e in stages]
                if near:
                    found[n] = near[0]
            if not found:
                break
            stages.update(found)
            frontier = [n for n in frontier if n not in found]
    return stages, set(stages) - set(own)


def reduce(trace: dict, scopes: Dict[str, str],
           inherited: Set[str] = frozenset()) -> dict:
    """Per-stage device self seconds over the window, averaged over the
    devices: ``scope_s``, ``inherited_s`` (the part of ``scope_s`` whose
    ops inherited their stage), ``unscoped_s``, ``busy_s`` and
    ``window_s``.  ``scope_s`` and ``unscoped_s`` add up to ``busy_s``."""
    lo, hi = trace["window_ns"]
    scope_s, unscoped, inh, busy = defaultdict(float), 0.0, 0.0, 0.0
    devices = trace["devices"]
    for _, events in sorted(devices.items()):
        ev = tr._clip(events, lo, hi)
        busy += sum(b - a for a, b in tr._union(ev)) / 1e9
        for (name, _, _), st in zip(ev, tr._self_times(ev)):
            op = tr.op_name(name)
            stage = scopes.get(op)
            if stage is None:
                unscoped += st / 1e9
            else:
                scope_s[stage] += st / 1e9
                inh += st / 1e9 if op in inherited else 0.0
    n = max(1, len(devices))
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())},
            "inherited_s": inh / n, "unscoped_s": unscoped / n,
            "busy_s": busy / n, "window_s": (hi - lo) / 1e9}


def client_counts(mets) -> Optional[Dict[str, list]]:
    """A round's counters, one entry per client; None where the round
    reports none."""
    if not all(k in mets for k in COUNT_KEYS):
        return None
    return {k: [int(x) for x in mets[k]] for k in COUNT_KEYS}


def summed(counts: Optional[Dict[str, list]]) -> Optional[Dict[str, int]]:
    return None if counts is None else {k: sum(v)
                                        for k, v in counts.items()}


def stage_metrics(scope_s: Dict[str, float], rounds: int,
                  counts: Optional[Dict[str, int]]) -> dict:
    """Per-round numbers: ``wire_encode_s``, ``wire_decode_s``,
    ``select_s`` and ``server_step_s`` (device s/round of the stage),
    ``value_fill_share`` (shipped over capacity, %) and
    ``mask_dropped_share`` (selected past the cap, %).  A number whose
    stage or counter the program lacks is None; so are both shares for
    a scheme without a mask payload."""
    out = {f"{k}_s": (scope_s[k] / rounds if k in scope_s else None)
           for k in ("wire_encode", "wire_decode", "select", "server_step")}
    sel, shipped, cap = ((counts[k] for k in COUNT_KEYS) if counts
                         else (0, 0, 0))
    out["value_fill_share"] = 100.0 * shipped / cap if cap else None
    out["mask_dropped_share"] = 100.0 * (sel - shipped) / sel \
        if cap and sel else None
    return out


# ---------------------------------------------------------------------------
# One measurement on the chip
# ---------------------------------------------------------------------------


def _window(prog, batches, start: int, seconds: float, traced: bool,
            tdir: Optional[str]):
    import jax
    if traced:
        jax.profiler.start_trace(tdir)
    rounds, mets = 0, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.batch"):
                b = batches[(start + rounds) % len(batches)]
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
    return window_s, rounds, mets


def measure(cell: dict, seed: int, seconds: float, record=None,
            require_chip: bool = True) -> dict:
    """Set-up as ``run.run`` does, then an untraced and a traced window."""
    import jax
    import program
    import run as bench

    bench.devices(cell["chips"], require_chip)
    bench.enable_cache()
    prog = program.Program(cell["c"], cell["mix"], seed, check=require_chip)
    batches = bench.device_batches(cell, seed)
    prog.compile(batches[0])
    counts = []
    n_check = cell["mix"]["check_rounds"]
    for r in range(n_check):
        mets = prog.run_round(batches[r])
        jax.block_until_ready(prog.state)
        counts.append(client_counts(jax.device_get(mets)))
    plain_s, plain_rounds, mets = _window(prog, batches, n_check, seconds,
                                          False, None)
    counts.append(client_counts(jax.device_get(mets)))
    tdir = tempfile.mkdtemp(prefix="bench_scopes_")
    traced_s, rounds, mets = _window(prog, batches, n_check + plain_rounds,
                                     seconds, True, tdir)
    counts.append(client_counts(jax.device_get(mets)))
    trace = bench.load_trace(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    hlo = prog.hlo_text()
    scopes, inherited = hlo_scopes(hlo)
    red = reduce(trace, scopes, inherited)
    tables = tr.reduce(trace, tr.hlo_sources(hlo), tr.layer_tables())
    out = {
        "workload": cell["name"], "seed": seed,
        "round_s_untraced": plain_s / plain_rounds,
        "round_s_traced": traced_s / rounds, "rounds": rounds,
        **red, "layer_s": tables["layer_s"],
        "counts": counts,
        "metrics": stage_metrics(red["scope_s"], rounds,
                                 summed(counts[-1])),
    }
    if record:
        names = {tr.op_name(e[0]) for ev in trace["devices"].values()
                 for e in ev}
        data = {"trace": {"window_ns": trace["window_ns"],
                          "devices": trace["devices"], "host": []},
                "scopes": {k: v for k, v in scopes.items() if k in names},
                "inherited": sorted(inherited & names),
                "printed": {k: out[k] for k in (
                    "scope_s", "inherited_s", "unscoped_s", "busy_s",
                    "window_s", "rounds", "counts", "metrics")}}
        with gzip.open(record, "wt") as f:
            json.dump(data, f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    import run as bench
    cell = bench.load_cell(args.workload)
    try:
        out = measure(cell, args.seed, args.seconds, args.record)
    except bench.NoChip as e:
        bench.log(f"no result: {e}")
        return 3
    bench.log(f"unscoped {out['unscoped_s']!r} s of busy "
              f"{out['busy_s']!r} s; inherited {out['inherited_s']!r} s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
