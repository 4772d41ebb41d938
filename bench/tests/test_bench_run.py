"""Whole runs of the paper cell at a CPU size: a sound run proves correct;
the control and each planted fault in the timed path do not."""
import json

import pytest

from bench import calibrate, harness


@pytest.fixture(scope="module")
def spec(tiny_root):
    return harness.cell_spec("paper_mlp.tiny", tiny_root)


def _run(spec, seed, trace=False):
    return harness.run_cell(spec, seed, seconds=0.3, trace=trace,
                            require_chip=False)


def test_sound_run_is_correct(spec, capsys):
    r = _run(spec, 2 ** 31 + 77)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"rounds_per_s", "round_ms_p95", "setup_s"}
    assert list(r)[-1] == "checks"            # the numbers come last
    assert set(r["checks"]) == set(spec["traffic"]["check"]["limits"])
    err = capsys.readouterr().err.strip().splitlines()
    assert all(line.startswith("check ") and " limit " in line
               for line in err[-len(r["checks"]):])
    json.dumps(r)


def test_traced_run_reports_per_layer_metrics(spec):
    r = _run(spec, 5, trace=True)
    assert r["correct"] is True
    assert "host_input_ms" in r["metrics"]      # no device plane on CPU
    assert r["device"]["window_s"] > 0
    assert "breakdown" in r


# p_ignored reads under the limits (PERF.md §2): not one of the faults
# this cell's numbers have to catch
@pytest.mark.parametrize("fault", ["frozen", "half", "no_signal"])
def test_planted_fault_is_not_correct(spec, fault, monkeypatch):
    engine = harness.module("engines", spec["traffic"]["engine"],
                            spec["root"])
    build = engine.Cell.__init__

    undo = []

    def broken_init(self, *args):
        build(self, *args)
        undo.append(calibrate.FAULTS[fault](self))

    monkeypatch.setattr(engine.Cell, "__init__", broken_init)
    try:
        assert _run(spec, 9)["correct"] is False
    finally:
        for u in undo:
            u()


@pytest.mark.parametrize("seed", [31, 2 ** 31 + 32])
def test_control_is_not_correct(spec, seed):
    """The reference in the precision below the configuration's (bf16 for
    float32), put in the program's place, fails the limits."""
    readings = calibrate.readings_for(spec, seed, "control",
                                      require_chip=False)
    ok, checks = harness.judge(readings, spec["traffic"]["check"]["limits"])
    assert not ok, checks
