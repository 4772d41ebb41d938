"""The trace reduction: on hand-made events, and on a short trace of the
paper cell recorded on a TPU v5e and committed beside these tests."""
import os
import re

import pytest

from bench import trace
from bench.trace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
CHIP_TRACE = os.path.join(DATA, "paper_mlp_round_v5e.json.gz")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _op(plane, name, start, dur):
    return Event(plane, trace.OPS_LINE, name, float(start), float(dur))


def _span(name, start, dur):
    return Event("/host:CPU", "python", name, float(start), float(dur))


def test_merge_clip_subtract():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (7, 9), (10, 10)])
    assert m == [[0, 3], [5, 9]]
    assert trace.total(m) == 7
    assert trace.clip(m, 2, 6) == [[2, 3], [5, 6]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 6]]) == [
        [0, 2], [3, 5], [6, 10]]
    assert trace.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert trace.subtract([[0, 4]], []) == [[0, 4]]


def _view():
    ev = [_span("bench.round", 0, 100), _span("bench.input", 0, 10),
          _span("bench.readback", 60, 40),
          # device 0: busy [10, 50) with nested-free ops, a collective
          # [50, 60) that nothing overlaps, then idle
          _op(DEV0, "fusion.1", 10, 20), _op(DEV0, "ota_aggregate_client",
                                             25, 25),
          _op(DEV0, "all-reduce.3", 45, 15),
          # device 1: busy [0, 30) and [40, 80); the collective [70, 90)
          # overlaps compute for 10 of its 20
          _op(DEV1, "fusion.1", 0, 30), _op(DEV1, "fusion.2", 40, 40),
          _op(DEV1, "reduce-scatter.1", 70, 20),
          _op(DEV1, "fusion.9", 150, 10)]          # outside the window
    return trace.TraceView(ev)


def test_busy_idle_and_window():
    v = _view()
    assert v.rounds == 1 and v.window_s == pytest.approx(100e-9)
    assert v.planes == [DEV0, DEV1]
    assert trace.total(v.busy(DEV0)) == 50          # [10, 60)
    assert trace.total(v.busy(DEV1)) == 80          # [0, 30) + [40, 90)
    assert v.busy_s() == pytest.approx(65e-9)


def test_kernel_grouping_by_name():
    v = _view()
    secs = v.op_seconds()
    assert secs["fusion.1"] == pytest.approx(50e-9)
    assert "fusion.9" not in secs
    ota = v.op_seconds(re.compile("ota_"))
    assert ota == {"ota_aggregate_client": pytest.approx(25e-9)}


def test_exposed_collective_time():
    v = _view()
    # device 0: the all-reduce [45, 60) is exposed on [50, 60) = 10;
    # device 1: the reduce-scatter [70, 90) is exposed on [80, 90) = 10
    assert v.exposed_collective_s() == pytest.approx(10e-9)


def test_idle_gaps_by_host_span():
    v = _view()
    gaps = dict(v.idle_gaps())
    # device 0 idle [0, 10) in bench.input and [60, 100) in readback;
    # device 1 idle [30, 40) outside spans and [90, 100) in readback
    assert gaps["bench.input"] == pytest.approx(5e-9)
    assert gaps["bench.readback"] == pytest.approx(25e-9)
    assert gaps["host outside bench spans"] == pytest.approx(5e-9)


def test_events_round_trip(tmp_path):
    ev = _view().events
    path = str(tmp_path / "ev.json.gz")
    trace.save_events(ev, path)
    assert trace.load_events(path) == ev


@pytest.fixture(scope="module")
def chip():
    return trace.TraceView(trace.load_events(CHIP_TRACE))


def test_chip_trace_reduces(chip):
    assert chip.planes == ["/device:TPU:0"]
    assert chip.rounds == 6
    busy = chip.busy_s()
    assert 0 < busy < chip.window_s
    assert sum(s for _, s in chip.idle_gaps()) == pytest.approx(
        chip.window_s - busy)
    assert chip.exposed_collective_s() == 0.0      # one chip
    ops = chip.op_seconds()
    assert sum(ops.values()) >= busy


def test_chip_trace_finds_the_ota_kernels(chip):
    from bench import harness
    reader = harness.module("metrics", "ota_kernel_ms")
    secs = chip.op_seconds(reader.OTA_KERNELS)
    assert secs and all(v > 0 for v in secs.values())
    assert sum(secs.values()) < chip.busy_s()


def test_chip_trace_per_layer_metrics(chip):
    from bench import harness
    ctx = harness.Context(trace=chip, peak=harness.peak_of("TPU v5 lite"),
                          cfg=None, traffic=None, model_mod=None,
                          n_chips=1)

    def read(name):
        return harness.module("metrics", name).read(ctx)

    roof = read("ota_client_fold_roofline")
    assert 0 < roof <= 100                 # a share of a roofline
    assert 0 < read("ota_kernel_ms") < chip.window_s * 1e3 / chip.rounds
    idle = read("device_idle_pct")
    assert 0 < idle < 100
    assert read("device_idle_pct") == pytest.approx(
        100 * (1 - chip.busy_s() / chip.window_s))
    assert read("host_input_ms") > 0
    assert read("step_mfu") is None        # needs the configuration
