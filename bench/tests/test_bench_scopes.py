"""The round's phase scopes in the compiled step: every instruction of the
test-size ``HotaSim`` round, compiled on the CPU, maps to a phase or to a
named rule of the phase reduction; the round the readers compile again is
the round the engine runs."""
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from bench import harness, scopes
from bench.trace import OPS_LINE, Event, TraceView

RULES = {"op_name", "operand", "user", "caller", "outside", "none"}
VARIANTS = {"plain": {}, "faults": {"faults": True},
            "streaming": {"ota_streaming": True}}


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def step_hlo(request):
    from repro.common.config import FLConfig, ModelConfig, TrainConfig
    from repro.core.sim import HotaSim
    from repro.models.model import build_model
    fl = FLConfig(n_clusters=2, n_clients=2, **VARIANTS[request.param])
    sim = HotaSim(build_model(ModelConfig(family="mlp")), fl,
                  TrainConfig(lr=3e-4), [4, 4])
    state = sim.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 2, 4, 256), jnp.float32)
    y = jnp.zeros((2, 2, 4), jnp.int32)
    compiled = HotaSim._step.lower(sim, state, x, y, jax.random.PRNGKey(1),
                                   sim.chan, sim.faults).compile()
    return request.param, compiled.as_text()


def test_step_names_every_phase(step_hlo):
    _, text = step_hlo
    instrs, _ = scopes.parse_hlo(text)
    named = {scopes.phase_of(i.op_name) for i in instrs.values()}
    assert set(scopes.PHASES) <= named


def test_every_entry_instruction_maps_to_a_phase_or_rule(step_hlo):
    variant, text = step_hlo
    instrs, entry = scopes.parse_hlo(text)
    got = scopes.resolve_scopes(text)
    ent = [n for n, i in instrs.items() if i.computation == entry]
    assert len(ent) > 100
    rules = Counter()
    for name in ent:
        path, rule = got[name]
        assert rule in RULES, (name, rule)
        assert (scopes.phase_of(path) is not None) == (
            rule in {"op_name", "operand", "user", "caller"}), name
        rules[rule] += 1
        if rule == "outside":         # a scope of its own, but no phase
            assert "hota." not in instrs[name].op_name, name
    # the rule for instructions without a scope does the work: the copies
    # XLA adds carry no op_name
    assert rules["operand"] + rules["user"] > 0.2 * len(ent)
    if variant != "faults":           # the fault path's extras are unscoped
        assert rules["outside"] + rules["none"] < 0.05 * len(ent)


# ---------------------------------------------- the round the readers see
@pytest.fixture(scope="module")
def tiny_cell(tiny_root):
    spec = harness.cell_spec("paper_mlp.tiny", tiny_root)
    model_mod = harness.module("configs", "paper_mlp", tiny_root)
    generator = harness.module("generators", spec["traffic"]["generator"],
                               tiny_root)
    return spec["cfg"], spec["traffic"], model_mod, generator


@pytest.fixture(scope="module")
def tiny_hlo(tiny_cell):
    return scopes.step_hlo(*tiny_cell, jax.devices()[0])


def _instructions(text):
    """The instruction lines of a compiled module, metadata left out."""
    import re
    return [re.sub(r", metadata=\{[^}]*\}", "", ln)
            for ln in text.splitlines() if re.match(r"^\s+(ROOT )?%", ln)]


def test_step_hlo_is_the_round_the_engine_runs(tiny_cell, tiny_hlo):
    """The stand-in arguments compile to the program that the engine's own
    arguments (a jitted state, a batch and key placed on the device)
    compile to, instruction for instruction."""
    from repro.core.sim import HotaSim
    cfg, traffic, model_mod, generator = tiny_cell
    sim, init = scopes.build_sim(cfg, traffic, model_mod, generator)
    seeds = harness.Seeds(2 ** 31 + 9)
    state = jax.jit(init)(seeds.weight_key())
    xs, ys = generator.make_pool(traffic, seeds.data_key())
    x, y, k = jax.device_put((xs[0], ys[0], seeds.round_key(0)),
                             jax.devices()[0])
    lowered = HotaSim._step.lower(sim, state, x, y, k, sim.chan, sim.faults)
    abstract = HotaSim._step.lower(
        sim, *scopes.round_args(sim, init, traffic, jax.devices()[0]),
        sim.chan, sim.faults)
    assert lowered.as_text() == abstract.as_text()
    engine = lowered.compile().as_text()
    assert _instructions(engine) == _instructions(tiny_hlo)
    assert scopes.scope_table(engine) == scopes.scope_table(tiny_hlo)


def test_step_ops_partition_into_phases(tiny_hlo):
    """Every instruction of the compiled round, named as a trace names a
    device op, falls in a phase; an op of another program does not."""
    instrs, entry = scopes.parse_hlo(tiny_hlo)
    lines = [ln.strip() for ln in tiny_hlo.splitlines()
             if ln.startswith("  %") or ln.startswith("  ROOT %")]
    entry_ops = [ln[len("ROOT "):] if ln.startswith("ROOT ") else ln
                 for ln in lines
                 if instrs[scopes.signature(ln).split(" ")[0]].computation
                 == entry
                 and instrs[scopes.signature(ln).split(" ")[0]].opcode
                 not in scopes.NO_PHASE]
    ev = [Event("/host:CPU", "python", "bench.round", 0.0, 1e9)]
    ev += [Event("/device:TPU:0", OPS_LINE, ln.split(", metadata=")[0],
                 float(10 * i), 10.0) for i, ln in enumerate(entry_ops)]
    ev.append(Event("/device:TPU:0", OPS_LINE,
                    "%dynamic-slice.1 = f32[2,3,4,256]{3,2,1,0} "
                    "dynamic-slice(f32[4,2,3,4,256]{4,3,2,1,0} %p.1)",
                    1e6, 10.0))
    view = TraceView(ev)
    secs = scopes.phase_seconds(view, scopes.scope_table(tiny_hlo))
    assert all(secs[p] > 0 for p in scopes.PHASES), secs
    assert secs[scopes.OTHER_PROGRAMS] == pytest.approx(10e-9)
    assert secs[scopes.UNATTRIBUTED] < 0.05 * sum(secs.values())
    assert sum(secs.values()) == pytest.approx(view.busy_s())
