"""Integration tests for tools/lint (repro-lint).

Each rule gets a known-bad and a known-good fixture tree under
``tests/lint_fixtures/<case>/`` which acts as a standalone lint root;
plus: the real repo must be clean against the committed baseline with no
stale entries, suppression comments must silence (only) their rule, and
the CLI must hold its exit-code contract.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from tools.lint import run_lint
from tools.lint.core import DEFAULT_BASELINE, Finding, write_baseline

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "lint_fixtures"


def _findings(case, rules=None, **kw):
    res = run_lint(FIXTURES / case, rules=rules, baseline_path=None, **kw)
    return res.findings


# -- pallas-contract -------------------------------------------------------


def test_pallas_bad_flags_misalignment_and_vmem():
    found = _findings("pallas_bad", rules=["pallas-contract"])
    msgs = [f.message for f in found]
    assert any("(3, 100)" in m and "not aligned" in m for m in msgs), msgs
    assert any("VMEM estimate" in m and "vmem_hog" in m for m in msgs), msgs
    # anchored at real lines in the fixture file
    assert all(f.path == "src/repro/kernels/demo/demo.py" for f in found)
    assert all(f.line > 0 for f in found)


def test_pallas_good_is_clean():
    assert _findings("pallas_good", rules=["pallas-contract"]) == []


def test_vmem_budget_is_configurable():
    # the good fixture's (8, 1024) f32 spec pair is ~64 KiB doubled;
    # a 0.01 MiB budget must flag it
    found = _findings("pallas_good", rules=["pallas-contract"],
                      vmem_budget_mb=0.01)
    assert any("VMEM estimate" in f.message for f in found)


# -- jit-hazard ------------------------------------------------------------


def test_jit_bad_flags_every_hazard_class():
    found = _findings("jit_bad", rules=["jit-hazard"])
    msgs = " | ".join(f.message for f in found)
    assert "host cast" in msgs
    assert ".item()" in msgs
    assert "np.asarray" in msgs
    assert "if thresh > 0.5" in msgs
    assert len(found) == 4, found


def test_jit_good_is_clean():
    assert _findings("jit_good", rules=["jit-hazard"]) == []


# -- ref-parity ------------------------------------------------------------


def test_refparity_bad_flags_missing_oracle_and_test():
    found = _findings("refparity_bad", rules=["ref-parity"])
    msgs = " | ".join(f.message for f in found)
    assert "`orphan_kernel` has no `orphan_ref`" in msgs
    assert "`orphan_kernel` is not referenced" in msgs
    # docstring mention must not count as a test reference
    assert "`tested_only` has no `tested_only_ref`" in msgs
    assert "`tested_only` is not referenced" not in msgs
    assert len(found) == 3, found


def test_refparity_good_is_clean():
    assert _findings("refparity_good", rules=["ref-parity"]) == []


# -- bits-accounting -------------------------------------------------------


def test_bits_bad_flags_missing_bits_and_doc_drift():
    found = _findings("bits_bad", rules=["bits-accounting"])
    msgs = " | ".join(f.message for f in found)
    assert "`no_bits` resolves to ['NoBitsCompressor']" in msgs
    assert "`NoBitsCompressor` neither defines nor inherits" in msgs
    assert "`undocumented` is missing from" in msgs
    assert "`ghost_entry` names no registered compressor" in msgs
    assert "`no_wire` (NoWireCompressor.compress) builds no WirePayload" \
        in msgs
    assert "`OddBlockCompressor` sets block=512" in msgs
    assert len(found) == 6, found


def test_bits_good_is_clean():
    assert _findings("bits_good", rules=["bits-accounting"]) == []


# -- repo + baseline + suppressions ----------------------------------------


def test_repo_is_clean_against_committed_baseline():
    res = run_lint(REPO, baseline_path=DEFAULT_BASELINE)
    assert res.findings == [], res.findings
    assert res.stale_baseline == [], res.stale_baseline


def test_committed_baseline_is_exact():
    """Every committed baseline entry must still match a live finding —
    stale entries fail the run (the baseline can only shrink honestly)."""
    entries = json.loads(DEFAULT_BASELINE.read_text())["findings"]
    res = run_lint(REPO, baseline_path=DEFAULT_BASELINE)
    matched = {f.key for f in res.baselined}
    for e in entries:
        assert (e["rule"], e["path"], e["message"]) in matched, (
            f"stale baseline entry: {e}")
        assert e.get("justification", "").strip(), (
            f"baseline entry without justification: {e}")


def test_repo_suppressions_are_counted_and_scoped():
    """The repo's inline suppressions actually silence findings (they
    reappear when the baseline is the only escape hatch removed), and a
    suppression for rule A does not silence rule B."""
    res = run_lint(REPO, baseline_path=None)
    assert len(res.suppressed) >= 2
    rules_suppressed = {f.rule for f in res.suppressed}
    assert "jit-hazard" in rules_suppressed
    # scoping: every suppressed finding's line carries ITS rule name
    for f in res.suppressed:
        line = (REPO / f.path).read_text().splitlines()[f.line - 1]
        assert f"disable={f.rule}" in line


def test_suppression_silences_only_its_rule(tmp_path):
    """On a copy of the bad pallas fixture: a pallas-contract suppression
    on one finding's line silences it; a suppression naming another
    rule on the other finding's line does not."""
    root = tmp_path / "pallas_bad"
    shutil.copytree(FIXTURES / "pallas_bad", root)
    found = run_lint(root, rules=["pallas-contract"],
                     baseline_path=None).findings
    assert len(found) == 2, found
    first, second = sorted(found, key=lambda f: f.line)
    src = root / first.path
    lines = src.read_text().splitlines()
    lines[first.line - 1] += "  # repro-lint: disable=pallas-contract"
    lines[second.line - 1] += "  # repro-lint: disable=jit-hazard"
    src.write_text("\n".join(lines) + "\n")
    res = run_lint(root, rules=["pallas-contract"], baseline_path=None)
    assert [f.line for f in res.suppressed] == [first.line]
    assert [f.line for f in res.findings] == [second.line]


def test_stale_baseline_entry_fails_run(tmp_path):
    ghost = tmp_path / "baseline.json"
    write_baseline(ghost, [Finding("jit-hazard", "src/nope.py", 1,
                                   "never matches")])
    res = run_lint(REPO, baseline_path=ghost)
    assert res.stale_baseline and not res.ok


def test_baseline_absorbs_findings(tmp_path):
    """A finding written to the baseline stops being actionable."""
    bad_root = FIXTURES / "jit_bad"
    res = run_lint(bad_root, rules=["jit-hazard"], baseline_path=None)
    assert res.findings
    bl = tmp_path / "baseline.json"
    write_baseline(bl, res.findings)
    res2 = run_lint(bad_root, rules=["jit-hazard"], baseline_path=bl)
    assert res2.findings == [] and len(res2.baselined) == len(res.findings)
    assert res2.ok


# -- CLI contract ----------------------------------------------------------


def _cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.lint", *args],
                          cwd=cwd, capture_output=True, text=True)


def test_cli_repo_exits_zero():
    proc = _cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.parametrize("case", ["pallas_bad", "jit_bad",
                                  "refparity_bad", "bits_bad"])
def test_cli_known_bad_fixture_exits_nonzero(case):
    proc = _cli("--root", str(FIXTURES / case))
    assert proc.returncode == 1, proc.stdout + proc.stderr


def test_cli_json_output_is_machine_readable():
    proc = _cli("--root", str(FIXTURES / "jit_bad"), "--json")
    assert proc.returncode == 1
    report = json.loads(proc.stdout)
    assert report["ok"] is False
    assert {f["rule"] for f in report["findings"]} == {"jit-hazard"}
    assert all({"rule", "path", "line", "message"} <= set(f)
               for f in report["findings"])


def test_cli_unknown_rule_is_usage_error():
    proc = _cli("--rules", "no-such-rule")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr
