"""The stage reduction (``scopes.py``) on a hand-made compiled round and
trace: nested ops, stages wrapped by autodiff, compiler-made ops that
inherit a stage, ops with no stage, and a program that names no stage
at all, and a round recorded on the chip (``data/whisper_round_scoped
.json.gz``: the traced window's device op events, the stage of each of
their instructions and the numbers the run printed)."""
import gzip
import json

import pytest

import scopes

HLO = """HloModule jit_round_fn

%body (p: f32[8], q: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(round_fn)/fl.round/while/body/fl.local_train/while/body/transpose(jvp(dot_general))" source_file="/x/src/repro/models/layers.py" source_line=10}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(round_fn)/fl.round/while/body/transpose(jvp(fl.local_train))/mul"}
  %gather.3 = f32[8]{0} gather(%p, %p), metadata={op_name="jit(round_fn)/fl.round/while/body/fl.compress/fl.wire_decode/jit(_take)/gather" stack_frame_id=3}
  %custom-call.4 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/fl.round/while/body/fl.compress/fl.select/gt"}
  %copy.5 = f32[8]{0:T(8,128)} copy(%fusion.2)
  %reduce-window.6 = f32[8]{0} reduce-window(%p), metadata={op_name="jit(round_fn)/fl.round/cumsum"}
  %reduce-window.8 = s32[8]{0} reduce-window(%p, %p), window={size=8 pad=7_0}, to_apply=%add
  %copy.11 = s32[8]{0} copy(%reduce-window.8)
  %fusion.9 = f32[8]{0} fusion(%copy.11, %p), kind=kLoop, calls=%f9, metadata={op_name="jit(round_fn)/fl.round/while/body/fl.compress/fl.wire_encode/scatter"}
  %copy.10 = f32[8]{0} copy(%q)
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(round_fn)/fl.round/fl.server_step/add"}
}
"""

WANT_SCOPES = {"fusion.1": "local_train", "fusion.2": "local_train",
               "gather.3": "wire_decode", "custom-call.4": "select",
               "reduce-window.6": "round", "fusion.9": "wire_encode",
               "fusion.7": "server_step"}
# XLA-made instructions with no op_name: the nearest consumer with a
# stage names theirs, else the nearest producer; copy.10 has neither
WANT_INHERITED = {"reduce-window.8": "wire_encode",
                  "copy.11": "wire_encode", "copy.5": "local_train"}


def hand_made():
    # window 0..100 ns on two devices; fusion.1 holds gather.3 nested
    # in it; idle 60..70 on device 0; nothing names "bogus.99"
    dev0 = [["%fusion.1", 0, 40], ["%gather.3", 10, 20],
            ["%custom-call.4", 40, 10], ["%copy.5", 50, 10],
            ["%reduce-window.6", 70, 10], ["%fusion.7", 80, 10],
            ["%bogus.99", 90, 20]]
    dev1 = [["%fusion.2", 0, 30], ["%copy.10", 30, 15],
            ["%reduce-window.8", 45, 5], ["%copy.11", 50, 5],
            ["%fusion.9", 55, 5]]
    return {"window_ns": [0, 100], "devices": {"0": dev0, "1": dev1},
            "host": []}


@pytest.mark.parametrize("name, stage", [
    ("jit(f)/fl.round/while/body/transpose(jvp(fl.local_train))/dot",
     "local_train"),
    ("jit(f)/fl.round/while/body/fl.compress/fl.select/gt", "select"),
    ("jit(f)/fl.round/jit(_take)/gather", "round"),
    ("jit(f)/fl.round/jvp(fl.fold)/add", "fold"),
    ("jit(f)/while/body/add", None),
    ("jit(f)/self.fl.round/add", None),
    ("jit(f)/myfl.round/add", None),
    ("", None),
])
def test_stage_of(name, stage):
    assert scopes.stage_of(name) == stage


def test_hlo_scopes_reads_op_names_and_inherits():
    stages, inherited = scopes.hlo_scopes(HLO)
    assert {k: v for k, v in stages.items() if k not in ("p", "q")} == \
        dict(WANT_SCOPES, **WANT_INHERITED)
    assert inherited - {"p"} == set(WANT_INHERITED)
    assert "copy.10" not in stages and "q" not in stages


def test_stage_and_unscoped_seconds_add_up_to_busy():
    red = scopes.reduce(hand_made(), *scopes.hlo_scopes(HLO))
    ns = 1e-9 / 2                         # averaged over two devices
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((90 + 60) * ns)
    assert red["scope_s"] == pytest.approx({
        "local_train": (40 - 20 + 30 + 10) * ns, "wire_decode": 20 * ns,
        "select": 10 * ns, "round": 10 * ns, "server_step": 10 * ns,
        "wire_encode": (5 + 5 + 5) * ns})
    assert red["inherited_s"] == pytest.approx((10 + 5 + 5) * ns)
    assert red["unscoped_s"] == pytest.approx((10 + 15) * ns)
    assert sum(red["scope_s"].values()) + red["unscoped_s"] == \
        pytest.approx(red["busy_s"])


def test_a_program_without_stages_is_all_unscoped():
    plain = HLO.replace("fl.", "")
    assert scopes.hlo_scopes(plain) == ({}, set())
    red = scopes.reduce(hand_made(), *scopes.hlo_scopes(plain))
    assert red["scope_s"] == {} and red["inherited_s"] == 0
    assert red["unscoped_s"] == pytest.approx(red["busy_s"])
    m = scopes.stage_metrics(red["scope_s"], 1, None)
    assert m == dict.fromkeys(
        ("wire_encode_s", "wire_decode_s", "select_s", "server_step_s",
         "value_fill_share", "mask_dropped_share"))


def test_counts_and_stage_metrics():
    mets = {"loss": [1.0, 2.0], "mask_selected": [60, 50],
            "mask_shipped": [40, 50], "mask_capacity": [50, 50]}
    cc = scopes.client_counts(mets)
    assert cc == {"mask_selected": [60, 50], "mask_shipped": [40, 50],
                  "mask_capacity": [50, 50]}
    assert scopes.client_counts({"loss": [1.0]}) is None
    total = scopes.summed(cc)
    assert total == {"mask_selected": 110, "mask_shipped": 90,
                     "mask_capacity": 100}
    m = scopes.stage_metrics({"wire_encode": 4.0, "select": 0.5}, 2, total)
    assert m["wire_encode_s"] == 2.0 and m["select_s"] == 0.25
    assert m["wire_decode_s"] is None and m["server_step_s"] is None
    assert m["value_fill_share"] == pytest.approx(90.0)
    assert m["mask_dropped_share"] == pytest.approx(100 * 20 / 110)
    zero = dict.fromkeys(scopes.COUNT_KEYS, 0)
    m = scopes.stage_metrics({}, 1, zero)
    assert m["value_fill_share"] is None
    assert m["mask_dropped_share"] is None


def recorded():
    from conftest import HERE
    with gzip.open(HERE / "data" / "whisper_round_scoped.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_round_reproduces_the_printed_stage_seconds():
    """One traced whisper-base.ssm-bisect.c4-l2 round of the scoped
    program on a TPU v5e: the reduction reproduces the seconds the run
    printed, the stages and the unscoped rest add up to the busy time,
    and the wire's decode and encode are the round."""
    data = recorded()
    red = scopes.reduce(data["trace"], data["scopes"],
                        set(data.get("inherited", ())))
    printed = data["printed"]
    assert red["scope_s"] == pytest.approx(printed["scope_s"])
    assert red["unscoped_s"] == pytest.approx(printed["unscoped_s"])
    assert red["busy_s"] == pytest.approx(printed["busy_s"])
    assert sum(red["scope_s"].values()) + red["unscoped_s"] == \
        pytest.approx(red["busy_s"])
    assert set(red["scope_s"]) == {
        "round", "local_train", "compress", "select", "wire_encode",
        "wire_decode", "fold", "server_step"}
    wire = red["scope_s"]["wire_encode"] + red["scope_s"]["wire_decode"]
    assert wire / red["busy_s"] > 0.95
    m = scopes.stage_metrics(red["scope_s"], printed["rounds"],
                             scopes.summed(printed["counts"][-1]))
    assert m == pytest.approx(printed["metrics"])


def test_recorded_counters_hold_their_bounds():
    for counts in recorded()["printed"]["counts"]:
        for sel, shipped, cap in zip(*(counts[k]
                                       for k in scopes.COUNT_KEYS)):
            assert 0 < shipped <= min(sel, cap)
            assert cap == 3745384            # counters.payload_bytes' slots


READERS = ("wire_encode_s", "wire_decode_s", "select_s", "server_step_s",
           "value_fill_share", "mask_dropped_share")


def reader(name):
    import importlib.util
    from conftest import HERE
    spec = importlib.util.spec_from_file_location(
        name, HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", READERS)
def test_readers_give_the_recorded_stage_metrics(name):
    """Each stage metric's reader, on the context ``run.py`` builds from
    the recorded round, gives the number ``scopes.py`` printed."""
    data = recorded()
    printed = data["printed"]
    red = scopes.reduce(data["trace"], data["scopes"],
                        set(data.get("inherited", ())))
    ctx = {"scope_s": red["scope_s"], "rounds": printed["rounds"],
           "counts": scopes.summed(printed["counts"][-1])}
    got = reader(name)(ctx)
    assert got is not None
    assert got == pytest.approx(printed["metrics"][name])


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_a_program_without_stages(name):
    red = scopes.reduce(hand_made(), *scopes.hlo_scopes(
        HLO.replace("fl.", "")))
    assert reader(name)({"scope_s": red["scope_s"], "rounds": 1,
                         "counts": None}) is None


def test_a_traced_run_hands_the_counters_to_the_readers(monkeypatch):
    """A traced run of the harness, without its look for a chip: the
    last round's counters reach the share readers; the CPU's trace holds
    no device op, so the stage times find nothing and are left out."""
    import counters
    import run as bench
    from conftest import TINY, TINY_MIX
    monkeypatch.setattr(counters, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    cell = dict(name="tiny", chips=1, c=dict(TINY), mix=dict(TINY_MIX),
                limits={"loss0_gap": 2e-3}, end_to_end=[],
                per_layer=list(READERS))
    res = bench.run(cell, 2**31 + 9, 0.2, True, require_chip=False)
    got = res["metrics"]
    assert set(got) == {"value_fill_share", "mask_dropped_share"}
    assert 0 < got["value_fill_share"]["value"] <= 100
    assert 0 <= got["mask_dropped_share"]["value"] < 100
