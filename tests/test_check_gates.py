"""Unit tests for the consolidated CI gate checker (benchmarks/check.py).

The gates themselves run in CI against real bench JSON; here we pin the
*checker's* contract — the assertion helper's failure message carries
gate name, threshold, and actual value; each gate accepts a passing
report and rejects each individually-broken field; the CLI exits
non-zero on failure and zero on success.
"""

import copy
import json
import sys
import pathlib

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import check  # noqa: E402


class TestRequire:
    def test_pass_is_silent(self):
        check.require("g", True, "x >= 1", 2)

    def test_failure_message_names_gate_threshold_actual(self):
        with pytest.raises(check.GateFailure) as ei:
            check.require("elastic", False, "replayed <= remaining", 7)
        msg = str(ei.value)
        assert "[gate elastic]" in msg
        assert "replayed <= remaining" in msg
        assert "7" in msg


GOOD_ELASTIC = {
    "dead_at_start": {"bit_identical": True, "dead_slot_load": 0.0},
    "die_mid_wave": {"bit_identical": True, "num_waves": 4,
                     "checkpoint_wave": 2, "replayed_waves": 2,
                     "replay_bound_ok": True,
                     "replay_dead_slot_load": 0.0},
    "resizes": {"after_8to6_reason": "ok", "after_6to8_reason": "ok",
                "no_cold_after_resize": True, "reprojections": 2,
                "outputs_6_match": True, "outputs_8_bit_identical": True},
    "bit_identical": True,
    "dead_load_total": 0.0,
}

GOOD_REUSE = {
    "bit_identical": True, "stationary_replans": 1, "drift_replans": 2,
    "replan_rate": 0.1, "steady_state_seconds": 0.01,
    "always_replan_seconds": 0.05, "speedup": 5.0,
}


def _write(tmp_path, payload):
    p = tmp_path / "r.json"
    p.write_text(json.dumps(payload))
    return str(p)


class TestElasticGate:
    def test_good_report_passes(self, tmp_path, capsys):
        check.gate_elastic(_write(tmp_path, GOOD_ELASTIC))
        assert "reprojections=2" in capsys.readouterr().out

    @pytest.mark.parametrize("mutate", [
        lambda r: r["dead_at_start"].update(bit_identical=False),
        lambda r: r["dead_at_start"].update(dead_slot_load=3.0),
        lambda r: r["die_mid_wave"].update(replay_bound_ok=False),
        lambda r: r["die_mid_wave"].update(replay_dead_slot_load=1.0),
        lambda r: r["resizes"].update(no_cold_after_resize=False),
        lambda r: r["resizes"].update(reprojections=1),
        lambda r: r["resizes"].update(outputs_6_match=False),
    ])
    def test_each_broken_field_fails(self, tmp_path, mutate):
        r = copy.deepcopy(GOOD_ELASTIC)
        mutate(r)
        # keep the roll-up flag consistent with the scenario flags
        r["bit_identical"] = (r["dead_at_start"]["bit_identical"]
                              and r["die_mid_wave"]["bit_identical"]
                              and r["resizes"]["outputs_8_bit_identical"])
        with pytest.raises(check.GateFailure):
            check.gate_elastic(_write(tmp_path, r))


class TestReuseGate:
    def test_good_report_passes(self, tmp_path):
        check.gate_reuse(_write(tmp_path, GOOD_REUSE))

    @pytest.mark.parametrize("field,value", [
        ("bit_identical", False),
        ("stationary_replans", 2),
        ("drift_replans", 0),
    ])
    def test_thresholds(self, tmp_path, field, value):
        r = dict(GOOD_REUSE, **{field: value})
        with pytest.raises(check.GateFailure):
            check.gate_reuse(_write(tmp_path, r))


GOOD_MULTIJOB = {
    "fifo": {"order": ["bulk", "urgent"], "weighted_completion_s": 0.4},
    "wspt": {"order": ["urgent", "bulk"], "weighted_completion_s": 0.15},
    "improvement": 0.62,
    "bit_identical": True,
    "coschedule_overlap": 1.0,
    "cache": {"tenants": 2, "collisions": 0},
}


class TestMultijobGate:
    def test_good_report_passes(self, tmp_path, capsys):
        check.gate_multijob(_write(tmp_path, GOOD_MULTIJOB))
        assert "collisions=0" in capsys.readouterr().out

    @pytest.mark.parametrize("mutate", [
        lambda r: r.update(improvement=0.1),
        lambda r: r.update(bit_identical=False),
        lambda r: r["cache"].update(collisions=1),
        lambda r: r["cache"].update(tenants=1),
        lambda r: r["wspt"].update(order=["bulk", "urgent"]),
    ])
    def test_each_broken_field_fails(self, tmp_path, mutate):
        r = copy.deepcopy(GOOD_MULTIJOB)
        mutate(r)
        with pytest.raises(check.GateFailure):
            check.gate_multijob(_write(tmp_path, r))


GOOD_SKETCH = {
    "plan_path": {"exact_seconds": 0.25, "sketch_seconds": 0.10,
                  "speedup": 2.5, "exact_pull_floats": 1_048_576,
                  "sketch_pull_floats": 32_768},
    "scenarios": {
        "benign": {"batches": 4, "overflow_replans": 0,
                   "replan_rate": 0.0, "overflow_free": True},
        "adversarial": {"batches": 4, "overflow_replans": 4,
                        "replan_rate": 1.0, "overflow_free": True},
    },
    "bit_identical": True,
}


class TestSketchGate:
    def test_good_report_passes(self, tmp_path, capsys):
        check.gate_sketch(_write(tmp_path, GOOD_SKETCH))
        assert "2.50x" in capsys.readouterr().out

    @pytest.mark.parametrize("mutate", [
        lambda r: r.update(bit_identical=False),
        lambda r: r["plan_path"].update(speedup=1.1),
        lambda r: r["plan_path"].update(sketch_pull_floats=2_000_000),
        lambda r: r["scenarios"]["benign"].update(overflow_replans=1),
        lambda r: r["scenarios"]["adversarial"].update(overflow_replans=0),
        lambda r: r["scenarios"]["adversarial"].update(overflow_free=False),
    ])
    def test_each_broken_field_fails(self, tmp_path, mutate):
        r = copy.deepcopy(GOOD_SKETCH)
        mutate(r)
        with pytest.raises(check.GateFailure):
            check.gate_sketch(_write(tmp_path, r))


class TestDocsLinksGate:
    def test_clean_tree_passes(self, tmp_path):
        (tmp_path / "a.md").write_text("see [b](b.md)")
        (tmp_path / "b.md").write_text("ok")
        check.gate_docs_links(str(tmp_path))

    def test_broken_link_fails_with_path(self, tmp_path):
        (tmp_path / "a.md").write_text("see [gone](missing.md)")
        with pytest.raises(check.GateFailure) as ei:
            check.gate_docs_links(str(tmp_path))
        assert "missing.md" in str(ei.value)

    def test_external_and_anchor_links_skipped(self, tmp_path):
        (tmp_path / "a.md").write_text(
            "[x](https://example.com/y.md) [y](b.md#frag) [z](img.png)")
        (tmp_path / "b.md").write_text("ok")
        check.gate_docs_links(str(tmp_path))


class TestCli:
    def test_unknown_gate_rejected(self):
        with pytest.raises(SystemExit):
            check.main(["--gate", "nope"])

    def test_failure_exits_nonzero(self, tmp_path):
        r = dict(GOOD_REUSE, bit_identical=False)
        with pytest.raises(SystemExit) as ei:
            check.main(["--gate", "reuse", "--path", _write(tmp_path, r)])
        assert "[gate reuse]" in str(ei.value)

    def test_missing_report_is_a_clean_failure(self):
        with pytest.raises(SystemExit) as ei:
            check.main(["--gate", "elastic", "--path", "/nonexistent.json"])
        assert "missing report" in str(ei.value)

    def test_success_exits_zero(self, tmp_path, capsys):
        check.main(["--gate", "reuse", "--path", _write(tmp_path, GOOD_REUSE)])
        assert "[gate reuse] ok" in capsys.readouterr().out


class TestStaticAnalysisGate:
    def test_gate_registered_for_ci(self):
        assert "static-analysis" in check.GATES

    def test_passes_on_the_real_repo(self, capsys):
        # The full analyzer (all checkers + mutation self-tests) on the
        # shipped engine: the gate's clean path is the repo itself.
        check.gate_static_analysis()
        out = capsys.readouterr().out
        assert "overlap" in out and "self-test" in out

    def test_nonzero_bitmask_fails_naming_the_layers(self, monkeypatch):
        import repro.analysis.__main__ as analysis_main

        monkeypatch.setattr(analysis_main, "run",
                            lambda check="all", self_test=False: 2 | 16)
        with pytest.raises(check.GateFailure) as ei:
            check.gate_static_analysis()
        msg = str(ei.value)
        assert "[gate static-analysis]" in msg
        assert "18" in msg                       # the failing bitmask
        assert "determinism 2" in msg            # ...and its legend
        assert "self-test 16" in msg

    def test_cli_runs_the_gate(self, capsys):
        check.main(["--gate", "static-analysis"])
        assert "[gate static-analysis] ok" in capsys.readouterr().out
