"""The trace reduction, on a hand-made trace and on a trace of one
whisper-base.ssm-bisect.c4-l2 round recorded on a TPU v5e
(``data/whisper_round.json.gz``: the window's device op events named by
their HLO instruction, the benchmark's host spans, each instruction's
call-stack frames under ``repro/``, and the layer seconds the run
printed)."""
import gzip
import json

import pytest

import trace as tr
from conftest import HERE

DATA = HERE / "data"

SOURCES = {
    "fusion.1": [["/x/src/repro/models/layers.py", "mlp_fwd", 10]],
    "fusion.2": [["/x/src/repro/core/wire.py", "_expand", 240],
                 ["/x/src/repro/core/fed.py", "client_step", 300]],
    "custom-call.3": [["/x/src/repro/kernels/topk_mask/ops.py", "f", 60]],
    "all-gather.4": [["/x/src/repro/core/aggregate.py", "g", 100]],
}


def hand_made():
    # window 0..100 ns; a parent op with a nested child, a gap 60..70
    dev = [["fusion.1", 0, 40], ["fusion.2", 10, 20],
           ["custom-call.3", 40, 20], ["all-gather.4", 70, 20],
           ["fusion.1", 90, 20]]
    host = [["bench.dispatch", 55, 20], ["bench.block", 0, 200]]
    return {"window_ns": [0, 100], "devices": {"0": dev}, "host": host}


def test_busy_union_self_times_and_layers():
    red = tr.reduce(hand_made(), SOURCES, tr.layer_tables())
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(90e-9)        # idle 60..70
    ls = red["layer_s"]
    assert ls["local_train"] == pytest.approx((40 - 20 + 10) * 1e-9)
    assert ls["wire"] == pytest.approx(20e-9)
    assert ls["compress"] == pytest.approx(20e-9)
    assert ls["aggregate"] == pytest.approx(20e-9)
    assert red["collective_s"] == pytest.approx(20e-9)
    assert sum(ls.values()) == pytest.approx(red["busy_s"])
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps == [["bench.dispatch", pytest.approx(10e-9)]]
    top = red["breakdown"]["device_ops"][0]
    assert top[0] == "fusion.1 [local_train]"


def test_unknown_source_is_unattributed():
    t = hand_made()
    red = tr.reduce(t, {}, tr.layer_tables())
    assert set(red["layer_s"]) == {tr.UNATTRIBUTED}


def test_function_rules_walk_the_call_stack():
    tables = tr.layer_tables()
    fed = "/y/src/repro/core/fed.py"
    local = [[fed, "_local_adam.<locals>.epoch", 200],
             [fed, "_local_adam", 210],
             [fed, "make_client_step.<locals>.client_step", 300],
             [fed, "make_fl_round.<locals>.round_scan", 400]]
    fold = [[fed, "make_fl_round.<locals>.round_scan.<locals>.body."
                  "<locals>.<lambda>", 410],
            [fed, "make_fl_round.<locals>.round_scan.<locals>.body", 405],
            [fed, "make_fl_round.<locals>.round_scan", 400],
            [fed, "make_fl_round.<locals>.round_fn", 500]]
    assert tr.classify(local, tables) == "local_train"
    assert tr.classify(fold, tables) == "aggregate"
    assert tr.classify([[fed, "<lambda>", 1]], tables) == tr.UNATTRIBUTED
    assert tr.classify(None, tables) == tr.UNATTRIBUTED


def test_hlo_sources_reads_the_stack_frame_tables():
    text = """HloModule jit_f

FileNames
1 "/a/src/repro/core/wire.py"
2 "/a/src/repro/core/fed.py"

FunctionNames
1 "_compact"
2 "client_step"

FileLocations
1 {file_name_id=2 function_name_id=2 line=300 end_line=300 column=4}
2 {file_name_id=1 function_name_id=1 line=245 end_line=245 column=8}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="x"}
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/add" stack_frame_id=2}
}
"""
    assert tr.hlo_sources(text) == {"fusion.7": [
        ["/a/src/repro/core/wire.py", "_compact", 245],
        ["/a/src/repro/core/fed.py", "client_step", 300]]}
    assert tr.op_name("%fusion.7 = f32[8]{0} fusion(%p)") == "fusion.7"


def recorded():
    with gzip.open(DATA / "whisper_round.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_round_accounts_for_the_window():
    """One traced whisper-base.ssm-bisect.c4-l2 round on a TPU v5e: the
    layers, unattributed time included, add up to the busy time, busy
    and idle to the window, and the wire takes 96% as the chip's trace
    showed."""
    data = recorded()
    red = tr.reduce(data["trace"], data["sources"], tr.layer_tables())
    assert red["layer_s"] == pytest.approx(data["printed"]["layer_s"])
    assert sum(red["layer_s"].values()) == pytest.approx(red["busy_s"])
    assert red["busy_s"] <= red["window_s"]
    assert 1 - red["busy_s"] / red["window_s"] < 1e-3
    assert red["layer_s"]["wire"] / red["window_s"] == pytest.approx(
        0.962, abs=0.005)
    assert set(red["layer_s"]) == {"local_train", "compress", "wire",
                                   "aggregate", tr.UNATTRIBUTED}
    assert red["collective_s"] == 0.0
    bd = red["breakdown"]
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0].endswith("[wire]")
    assert {g[0] for g in bd["idle_gaps"]} <= {"bench.batch",
                                              "bench.dispatch",
                                              "bench.block"}


def test_recorded_round_metrics():
    """The per-layer readers on the recorded round."""
    import importlib.util
    import counters
    data = recorded()
    red = tr.reduce(data["trace"], data["sources"], tr.layer_tables())
    c = json.loads((HERE.parent / "configs" / "whisper-base.json")
                   .read_text())
    mix = json.loads((HERE.parent / "traffic" / "ssm-bisect.c4-l2.json")
                     .read_text())
    ctx = dict(red, rounds=1, chips=1, peak=counters.peaks("TPU v5 lite"),
               model_flops_round=counters.round_model_flops(c, mix),
               codec_least_bytes_round=counters.codec_least_bytes(
                   c, mix, 53778144.0))

    def read(name):
        spec = importlib.util.spec_from_file_location(
            name, HERE.parent / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    assert read("wire_s") == pytest.approx(11.237, abs=0.01)
    assert 0 < read("local_train_s") < 0.5
    assert 0 < read("compress_s") < 0.5
    assert 0 < read("aggregate_s") < 0.1
    assert read("collective_s") is None
    assert 0 < read("idle_share") < 0.1
    mfu = read("mfu")                        # 5.21e12 FLOP in 11.68 s
    assert mfu == pytest.approx(100 * 5.2137e12 / 11.6849 / 197e12,
                                rel=1e-3)
    assert 0 < read("codec_roofline") < 1
