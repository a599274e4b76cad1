"""Reduction from a profiler trace to the per-layer numbers.

Two steps, kept apart so that the second can be tested on a recorded
trace without a chip:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps what the reduction needs, as plain lists: each device's XLA op
   events ``[name, start_ns, duration_ns]`` and the host spans the
   benchmark opened (``bench.*``), and :func:`hlo_sources` reads each
   instruction's Python call stack (file, function, line) from the
   compiled round's HLO text.
2. :func:`reduce` computes, over the traced window: busy time (the union
   of op intervals) and idle share per device, each op's self time (its
   duration less that of the ops nested in it), the layer of each op
   from its call stack and the layer tables (``layers/*.json``),
   collective time, and the
   ``breakdown`` lists: the ops that took most device time, and the
   longest idle gaps named by the host span open over them.
"""
from __future__ import annotations

import functools
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
UNATTRIBUTED = "unattributed"


# ---------------------------------------------------------------------------
# 1. Reading
# ---------------------------------------------------------------------------


def load(xplane_path: str, window: str = "bench.window") -> dict:
    """The events of the trace at ``xplane_path`` as plain lists; the
    window is the host span named ``window``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(xplane_path))
    devices, host = {}, []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(m.group(2), []).extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events)
            elif not m:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    spans = [h for h in host if h[0] == window]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {window!r} spans in the trace")
    _, start, dur = spans[0]
    return {"window_ns": [start, start + dur], "devices": devices,
            "host": [h for h in host if h[0] != window]}


_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?[\s,]metadata=\{([^}]*)\}")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r'^(\d+)\s+(?:"(.*)"|\{(.*)\})\s*$')
_FIELDS = re.compile(r"(\w+)=(\d+)")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a trace event, which the TPU trace
    names by the instruction's whole text (``%fusion.12 = f32[...] ...``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def hlo_sources(hlo_text: str) -> Dict[str, list]:
    """``{instruction name: [[file, function, line], ...]}``: the Python
    call stack, innermost frame first, that the compiled round's HLO
    records for each instruction (its ``FileNames``, ``FunctionNames``,
    ``FileLocations`` and ``StackFrames`` tables and each instruction's
    ``stack_frame_id``)."""
    tables = {"FileNames": {}, "FunctionNames": {}, "FileLocations": {},
              "StackFrames": {}}
    section, out = None, {}
    for line in hlo_text.splitlines():
        if line.strip() in tables:
            section = tables[line.strip()]
            continue
        row = _TABLE_ROW.match(line) if section is not None else None
        if row:
            section[int(row.group(1))] = row.group(2) if row.group(2) \
                is not None else dict((k, int(v)) for k, v in
                                      _FIELDS.findall(row.group(3)))
            continue
        section = None
        m = _INSTR.match(line)
        f = _FRAME_ID.search(m.group(2)) if m else None
        if f:
            out[m.group(1)] = int(f.group(1))
    files, funcs = tables["FileNames"], tables["FunctionNames"]
    locs, frames = tables["FileLocations"], tables["StackFrames"]

    @functools.lru_cache(maxsize=None)
    def stack(fid: int) -> tuple:
        chain, seen = [], set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc = locs.get(frames[fid]["file_location_id"], {})
            chain.append((files.get(loc.get("file_name_id"), ""),
                          funcs.get(loc.get("function_name_id"), ""),
                          loc.get("line", 0)))
            # the text prints each parent's id plus one; 0 means none
            fid = frames[fid].get("parent_frame_id", 0) - 1
        return tuple(chain)

    return {name: [list(f) for f in stack(fid)] for name, fid in out.items()}


# ---------------------------------------------------------------------------
# 2. Layers
# ---------------------------------------------------------------------------


def layer_tables(directory: Path = HERE / "layers") -> List[dict]:
    """The layer tables, one file per layer: ``{"layer": <name>,
    "key": <metric key>, "priority": <lower is tried first>, "rules":
    [{"file": <path suffix>, "function": <def name, optional>}]}``;
    a rule's function matches the last part of a frame's qualified name."""
    tables = [json.loads(p.read_text())
              for p in sorted(directory.glob("*.json"))]
    return sorted(tables, key=lambda t: t.get("priority", 1))


def classify(frames: Optional[list], tables: List[dict]) -> str:
    """The ``key`` of the layer of the innermost frame that a rule of
    some table matches (tables in priority order), else unattributed."""
    for path, function, _ in frames or ():
        name = function.rsplit(".", 1)[-1]        # qualified in the HLO
        for t in tables:
            for rule in t["rules"]:
                if (path.endswith(rule["file"])
                        or ("/" + rule["file"]) in path) and \
                        rule.get("function", name) == name:
                    return t["key"]
    return UNATTRIBUTED


# ---------------------------------------------------------------------------
# 3. Reduction
# ---------------------------------------------------------------------------


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _union(intervals):
    merged = []
    for _, a, b in intervals:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _self_times(events):
    """Self time of each event: its span less its children's (events of
    the same line nested inside it).  ``events`` sorted by (start, -end)."""
    out = [b - a for _, a, b in events]
    stack = []
    for i, (_, a, b) in enumerate(events):
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            out[stack[-1]] -= b - a
        stack.append(i)
    return out


def _host_span_at(host, t):
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host span"


def reduce(trace: dict, sources: Dict[str, list], tables: List[dict],
           top: int = 10) -> dict:
    """Per-device busy, idle and per-layer self times over the window,
    averaged over the devices, and the ``breakdown`` lists."""
    lo, hi = trace["window_ns"]
    window = (hi - lo) / 1e9
    devices = trace["devices"]
    layer_s = defaultdict(float)
    op_s = defaultdict(float)
    busy, coll, gaps = [], 0.0, []
    cache = {}
    for dev, events in sorted(devices.items()):
        ev = _clip(events, lo, hi)
        union = _union(ev)
        busy.append(sum(b - a for a, b in union) / 1e9)
        gaps.extend((b[0] - a[1], a[1], b[0])
                    for a, b in zip([[lo, lo]] + union, union + [[hi, hi]])
                    if b[0] > a[1])
        for (name, _, _), st in zip(ev, _self_times(ev)):
            if name not in cache:
                cache[name] = classify(sources.get(op_name(name)), tables)
            layer_s[cache[name]] += st / 1e9
            op_s[name] += st / 1e9
            if op_name(name).startswith(COLLECTIVES):
                coll += st / 1e9
    n = max(1, len(devices))
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, reverse=True)[:top]
    return {
        "window_s": window,
        "busy_s": sum(busy) / n,
        "devices": len(devices),
        "layer_s": {k: v / n for k, v in layer_s.items()},
        "collective_s": coll / n,
        "breakdown": {
            "device_ops": [[f"{op_name(name)} [{cache[name]}]", s / n]
                           for name, s in ops],
            "idle_gaps": [[_host_span_at(trace["host"], (a + b) // 2),
                           g / 1e9] for g, a, b in gaps],
        },
    }
