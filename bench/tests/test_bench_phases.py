"""The round's phases: the scope rules and the partition of op time on
hand-made events and HLO text, the phase readers, and the scoped trace of
the paper cell recorded on a TPU v5e and committed beside these tests."""
import gzip
import json
import os

import pytest

from bench import harness, scopes, trace
from bench.trace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV0 = "/device:TPU:0"
PHASE_READERS = ("client_update_ms", "fgn_ms", "ota_draw_ms", "ota_fold_ms",
                 "ps_update_ms")


@pytest.mark.parametrize("scope, phase", [
    ("jit(_step)/hota.client_update/vmap(vmap())/while/body/dot_general",
     "client_update"),
    ("jit(_step)/hota.ota_fold/vmap(hota.ota_draw)/vmap()/xor", "ota_draw"),
    ("jit(_step)/hota.fgn/vmap(jit(masked_gradnorm))/pallas_call", "fgn"),
    ("jit(_step)/hota.fgn/hota.ota_draw/slice", "ota_draw"),
    ("jit(_step)/hota.ota_fold/reshape;jit(_step)/hota.ps_update/add",
     "ota_fold"),
    ("jit(_step)/add;jit(_step)/hota.ps_update/sqrt", "ps_update"),
    ("jit(_step)/jit(_threefry_fold_in)/sim_channel_key/add", None),
    ("jit(_step)/hota.ota_folded/add", None),
    ("", None),
])
def test_phase_is_innermost_named_scope(scope, phase):
    assert scopes.phase_of(scope) == phase


@pytest.mark.parametrize("text, sig", [
    # a compiled module's line: operands by name, metadata
    ('  %copy.151 = u32[2,8]{1,0:T(8,128)} copy(%bc.2), metadata={op_name='
     '"jit(_step)/hota.ota_fold/copy"}', "copy.151 u32[2,8]{1,0:T(8,128)} "
     "copy"),
    # the same instruction as the trace names it: operands with shapes
    ("%copy.151 = u32[2,8]{1,0:T(8,128)} copy(u32[2,8]{1,0} %bc.2)",
     "copy.151 u32[2,8]{1,0:T(8,128)} copy"),
    # a tuple result, whose layouts hold parentheses of their own
    ("  ROOT %copy-start = (f32[3]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start("
     "%xb.1), cross_program_prefetch_index=0",
     "copy-start (f32[3]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start"),
    ("HloModule jit__step", None),
])
def test_signature_of_line_and_trace_name(text, sig):
    assert scopes.signature(text) == sig


HLO = """HloModule jit__step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body.1 (p.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %p.1), index=1
  %copy.9 = f32[4]{0} copy(f32[4]{0} %gte.1)
  %i.1 = s32[] get-tuple-element((s32[], f32[4]{0}) %p.1), index=0
  ROOT %t.1 = (s32[], f32[4]{0}) tuple(s32[] %i.1, f32[4]{0} %copy.9)
}

ENTRY %main.2 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fold.1 = f32[4]{0} fusion(f32[4]{0} %x.1), kind=kLoop, calls=%f.1, metadata={op_name="jit(_step)/hota.ota_fold/vmap(hota.ota_draw)/xor" source_line=3}
  %bc.1 = f32[2,2]{1,0} bitcast(f32[4]{0} %fold.1)
  %copy.1 = f32[2,2]{0,1:T(8,128)} copy(f32[2,2]{1,0} %bc.1)
  %copy.2 = f32[4]{0} copy(f32[4]{0} %x.1)
  %add.1 = f32[4]{0} add(f32[4]{0} %copy.2, f32[4]{0} %fold.1), metadata={op_name="jit(_step)/hota.ps_update/add"}
  %key.1 = u32[2]{0} constant({0, 1}), metadata={op_name="jit(_step)/hota.fgn/key"}
  %step.1 = s32[] add(s32[] %c.1, s32[] %c.1), metadata={op_name="jit(_step)/add"}
  %c.1 = s32[] constant(1)
  %tup.1 = (s32[], f32[4]{0}) tuple(s32[] %c.1, f32[4]{0} %add.1)
  %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tup.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(_step)/hota.client_update/while"}
  %out.1 = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %while.1), index=1
  ROOT %copy.3 = f32[4]{0} copy(f32[4]{0} %out.1)
}
"""  # noqa: E501


def test_scope_rules_on_hand_made_hlo():
    instrs, entry = scopes.parse_hlo(HLO)
    assert entry == "main.2"
    assert instrs["fold.1"].operands == ["x.1"]
    assert instrs["while.1"].calls == ["cond.1", "body.1"]
    got = scopes.resolve_scopes(HLO)
    rule = {k: v[1] for k, v in got.items()}
    phase = {k: scopes.phase_of(v[0]) for k, v in got.items()}
    assert (rule["fold.1"], phase["fold.1"]) == ("op_name", "ota_draw")
    # walks back through the bitcast to the draw
    assert (rule["copy.1"], phase["copy.1"]) == ("operand", "ota_draw")
    # a parameter has no phase: the copy takes its first user's
    assert (rule["copy.2"], phase["copy.2"]) == ("user", "ps_update")
    # back through the get-tuple-element to the while that made it
    assert (rule["copy.3"], phase["copy.3"]) == ("operand", "client_update")
    # inside the loop body nothing has a phase: the caller's
    assert (rule["copy.9"], phase["copy.9"]) == ("caller", "client_update")
    assert rule["step.1"] == "outside" and phase["step.1"] is None
    # a constant counts as having no phase, even with a scope of its own
    assert rule["key.1"] != "op_name"
    # a constant whose only user lies outside every phase: no rule
    assert rule["c.1"] == "none" and phase["c.1"] is None


def test_scope_table_keys_by_signature():
    table = scopes.scope_table(HLO)
    assert table["copy.1 f32[2,2]{0,1:T(8,128)} copy"] == (
        "jit(_step)/hota.ota_fold/vmap(hota.ota_draw)/xor")
    assert table["step.1 s32[] add"] == "jit(_step)/add"
    assert len(table) == len(scopes.parse_hlo(HLO)[0])


def _op(name, start, dur):
    return Event(DEV0, trace.OPS_LINE, name, float(start), float(dur))


def _span(name, start, dur):
    return Event("/host:CPU", "python", name, float(start), float(dur))


# the round's program for ``_phase_view``: one instruction per scope
STEP = {
    "fusion.1 f32[4] fusion": "jit(_step)/hota.client_update/dot_general",
    "ota_client_fold.3 f32[8,128] custom-call":
        "jit(_step)/hota.ota_fold/ota_client_fold/pallas_call",
    "copy.4 u32[8] copy": "jit(_step)/hota.ota_fold/vmap(hota.ota_draw)/add",
    "add.2 s32[] add": "jit(_step)/add",
    "copy.5 f32[4] copy": "",
    "masked_gradnorm.1 f32[3] custom-call":
        "jit(_step)/hota.fgn/masked_gradnorm/pallas_call",
    "fusion.7 f32[4] fusion": "jit(_step)/hota.ps_update/sqrt",
    "fusion.8 f32[4] fusion": "jit(_step)/hota.ota_fold/slice"}


def _phase_view():
    ev = [_span("bench.round", 0, 50), _span("bench.round", 50, 50),
          _span("bench.input", 0, 10), _span("bench.input", 50, 10),
          _op("%fusion.1 = f32[4] fusion()", 10, 20),
          _op("%ota_client_fold.3 = f32[8,128] custom-call(), "
              'custom_call_target="tpu_custom_call"', 30, 10),
          _op("%copy.4 = u32[8] copy()", 40, 5),
          _op("%add.2 = s32[] add()", 45, 2),
          _op("%copy.5 = f32[4] copy()", 47, 2),
          # another program: a name the round has, with another shape
          _op("%fusion.1 = f32[2,4] fusion()", 60, 4),
          _op("%masked_gradnorm.1 = f32[3] custom-call(), "
              'custom_call_target="tpu_custom_call"', 64, 6),
          _op("%fusion.7 = f32[4] fusion()", 70, 10),
          _op("%fusion.8 = f32[4] fusion()", 95, 10)]      # half outside
    return trace.TraceView(ev)


def test_phase_seconds_partition_op_time():
    v = _phase_view()
    ph = scopes.phase_seconds(v, STEP)
    assert set(ph) == set(scopes.PHASES) | {"other_programs",
                                            "unattributed"}
    want = {"client_update": 20, "ota_fold": 10 + 5, "ota_draw": 5,
            "fgn": 6, "ps_update": 10, "other_programs": 4,
            "unattributed": 2 + 2}
    assert ph == {k: pytest.approx(want.get(k, 0) * 1e-9) for k in ph}
    assert sum(ph.values()) == pytest.approx(sum(v.op_seconds().values()))
    assert sum(ph.values()) == pytest.approx(v.busy_s())   # a flat line
    assert scopes.kernel_seconds(v, STEP, "ota_fold") == {
        "ota_client_fold": pytest.approx(10e-9)}
    assert scopes.kernel_seconds(v, STEP, "fgn") == {
        "masked_gradnorm": pytest.approx(6e-9)}


def _read(name, view, table, monkeypatch, engine="sim"):
    monkeypatch.setattr(scopes, "round_table", lambda ctx: table)
    ctx = harness.Context(trace=view, traffic={"engine": engine})
    return harness.module("metrics", name).read(ctx)


def test_phase_readers(monkeypatch):
    v = _phase_view()
    assert _read("client_update_ms", v, STEP, monkeypatch) == (
        pytest.approx(20e-6 / 2))
    assert _read("ota_draw_ms", v, STEP, monkeypatch) == (
        pytest.approx(5e-6 / 2))
    assert _read("ota_fold_ms", v, STEP, monkeypatch) == (
        pytest.approx(15e-6 / 2))


@pytest.mark.parametrize("name", PHASE_READERS)
def test_phase_readers_read_nothing_without_scopes(name, monkeypatch):
    v = _phase_view()
    # a program without the scopes, as the parent commit's
    bare = {k: "" for k in STEP}
    assert scopes.phase_seconds(v, bare)["unattributed"] == pytest.approx(
        sum(v.op_seconds().values()) - 4e-9)
    assert _read(name, v, bare, monkeypatch) is None
    # no trace, no device plane, or another engine: nothing compiles
    monkeypatch.setattr(scopes, "step_hlo", None)
    assert _read(name, None, STEP, monkeypatch) is None
    host = trace.TraceView([e for e in v.events
                            if not e.plane.startswith("/device")])
    assert harness.module("metrics", name).read(
        harness.Context(trace=host, traffic={"engine": "sim"})) is None
    assert harness.module("metrics", name).read(
        harness.Context(trace=v, traffic={"engine": "dist"})) is None


def test_phases_line_printed_once_per_window(monkeypatch, capsys):
    v = _phase_view()
    calls = []
    monkeypatch.setattr(scopes, "round_table",
                        lambda ctx: calls.append(1) or STEP)
    ctx = harness.Context(trace=v, traffic={"engine": "sim"})
    for name in PHASE_READERS:
        harness.module("metrics", name).read(ctx)
    assert len(calls) == 1
    err = [ln for ln in capsys.readouterr().err.splitlines()
           if ln.startswith("phases: ")]
    assert len(err) == 1
    line = json.loads(err[0][len("phases: "):])
    assert line["ms_per_round"]["ota_draw"] == pytest.approx(5e-6 / 2)
    assert line["ota_fold_kernels_ms"] == {
        "ota_client_fold": pytest.approx(10e-6 / 2)}


# ------------------------------------------------------- the v5e traces
# the readings of the five per-layer metrics on the committed unscoped
# trace, as the parent commit's reduction read them
PINNED = {"host_input_ms": 3.146363166666667,
          "step_mfu": 0.6625638065268323,
          "ota_kernel_ms": 0.652742,
          "ota_client_fold_roofline": 86.79437147285087,
          "device_idle_pct": 27.399421665117842}


def _ctx(view):
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "paper_mlp.json"))
    traffic = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                             "round_c10n3.json"))
    return harness.Context(trace=view, peak=harness.peak_of("TPU v5 lite"),
                           cfg=cfg, traffic=traffic, n_chips=1,
                           model_mod=harness.module("configs", "paper_mlp"))


@pytest.fixture(scope="module")
def chip():
    return trace.TraceView(trace.load_events(
        os.path.join(DATA, "paper_mlp_round_v5e.json.gz")))


@pytest.mark.parametrize("name", sorted(PINNED))
def test_chip_trace_readings_unchanged(chip, name):
    assert harness.module("metrics", name).read(_ctx(chip)) == PINNED[name]
    assert chip.idle_gaps() == [("bench.input", 0.026247431999999613),
                                ("bench.readback", 0.002056458)]


SCOPED_TRACE = os.path.join(DATA, "paper_mlp_round_scoped_v5e.json.gz")
STEP_PROGRAM = "jit__step"


@pytest.fixture(scope="module")
def scoped():
    """Three rounds of the paper cell on a v5e. Each row is an event with,
    for a device op, the program it ran in and the scope path of its
    instruction there: the view of the events, and the round's program
    as a ``scope_table``."""
    with gzip.open(SCOPED_TRACE, "rt") as f:
        rows = json.load(f)
    view = trace.TraceView([Event(*r[:5]) for r in rows])
    table = {scopes.signature(r[2]): r[6] for r in rows
             if len(r) > 5 and r[5] == STEP_PROGRAM}
    return view, table


def test_scoped_trace_phases_cover_busy_time(scoped):
    view, table = scoped
    assert view.rounds == 3 and view.planes == [DEV0]
    ph = scopes.phase_seconds(view, table)
    busy = view.busy_s()
    assert sum(ph.values()) / view.rounds == pytest.approx(
        busy / view.rounds, rel=1e-3)
    assert all(ph[p] > 0 for p in scopes.PHASES)
    step_ops = busy - ph["other_programs"]
    assert ph["unattributed"] < 0.05 * step_ops
    assert scopes.kernel_seconds(view, table, "ota_fold").keys() == {
        "ota_client_fold"}
    assert scopes.kernel_seconds(view, table, "fgn").keys() == {
        "masked_gradnorm"}


def test_scoped_trace_metrics(scoped, monkeypatch):
    view, table = scoped
    monkeypatch.setattr(scopes, "round_table", lambda ctx: table)
    ctx = _ctx(view)

    def read(name):
        return harness.module("metrics", name).read(ctx)

    for name in PHASE_READERS + ("host_input_ms", "ota_kernel_ms",
                                 "ota_client_fold_roofline",
                                 "device_idle_pct", "step_mfu"):
        assert read(name) > 0, name
    assert 0 < read("ota_client_fold_roofline") <= 100
    # the shape-matched kernels are the named client-fold kernels
    assert read("ota_kernel_ms") == pytest.approx(
        scopes.kernel_seconds(view, table, "ota_fold")["ota_client_fold"]
        / view.rounds * 1e3)
    # the phases and the ops outside them add up to the busy time a round
    busy_ms = view.busy_s() / view.rounds * 1e3
    rest = scopes.phase_seconds(view, table)
    rest_ms = (rest["other_programs"] + rest["unattributed"]) / (
        view.rounds) * 1e3
    assert sum(read(n) for n in PHASE_READERS) + rest_ms == pytest.approx(
        busy_ms, rel=1e-3)
