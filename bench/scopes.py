"""The round's phases in a traced window: each device op of the compiled
round placed in the phase that the program's scopes name.

The program names five phases with ``jax.named_scope``:
``hota.client_update``, ``hota.fgn``, ``hota.ota_draw``, ``hota.ota_fold``
and ``hota.ps_update``. An op's phase is the innermost of them in its
scope path, so a channel draw made while folding counts as ``ota_draw``.

The trace names a device op by its HLO instruction, without the
instruction's metadata. The scope path comes from the same instruction in
the round's compiled HLO text (``step_hlo``: the cell's round compiled
again for its device once the window is over, from the persistent
compilation cache). The two are joined by signature: the instruction's
name, result shape and opcode (``signature``). An op whose signature is
not in the round's program ran in another program (``other_programs``:
the harness's pool slices and key ops). An op of the round whose path
names no phase is ``unattributed``.

XLA adds instructions that carry no ``op_name`` (layout copies above
all); ``resolve_scopes`` gives each the path of a neighbour, by the rules
in its docstring.
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

PHASES = ("client_update", "fgn", "ota_draw", "ota_fold", "ps_update")
SCOPE_PREFIX = "hota."
OTHER_PROGRAMS = "other_programs"
UNATTRIBUTED = "unattributed"

_PHASE_IN_PATH = re.compile(
    re.escape(SCOPE_PREFIX) + r"(" + "|".join(PHASES) + r")(?![\w.])")


def phase_of(scope: str) -> Optional[str]:
    """The innermost of the round's phases named in a scope path. A scope
    entered under a transformation shows wrapped in it
    (``vmap(hota.ota_draw)``); a fused op's path may join several with
    ``;``, of which the first that names a phase counts."""
    for path in scope.split(";"):
        found = _PHASE_IN_PATH.findall(path)
        if found:
            return found[-1]
    return None


# ------------------------------------------------------------ HLO text
_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation"
    r"|false_computation|branch_computations)=\{?((?:%[\w.\-]+(?:, )?)+)")
_SIGNATURE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
# Instructions that run no op of their own: they count as having no phase
# when an unscoped instruction's phase is looked for through them.
NO_PHASE = frozenset({"get-tuple-element", "bitcast", "parameter",
                      "constant"})


class Instr(NamedTuple):
    computation: str
    opcode: str
    operands: List[str]
    op_name: str
    calls: List[str]
    signature: str


def signature(text: str) -> Optional[str]:
    """``name = result-shape opcode`` of one HLO instruction, as a compiled
    module's text prints it or as the trace names a device op (which
    prints the operands' shapes, and no metadata)."""
    m = _SIGNATURE.match(text)
    return " ".join(m.groups()) if m else None


def _operand_text(rest: str, open_at: int) -> str:
    depth = 0
    for i in range(open_at, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return rest[open_at + 1:i]
    return rest[open_at + 1:]


def parse_hlo(text: str) -> Tuple[Dict[str, Instr], str]:
    """Every instruction of an HLO module's text, by name, and the name of
    its entry computation."""
    instrs: Dict[str, Instr] = {}
    comp, entry = "", ""
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(2)
            entry = comp if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        if op is None:
            continue
        operands = _operand_text(rest, op.end() - 1)
        attrs = rest[op.end() + len(operands) + 1:]
        name = _OP_NAME.search(attrs)
        calls = [c for group in _CALLS.findall(attrs)
                 for c in _NAME.findall(group)]
        instrs[m.group(1)] = Instr(comp, op.group(1),
                                   _NAME.findall(operands),
                                   name.group(1) if name else "", calls,
                                   signature(line) or "")
    return instrs, entry


def resolve_scopes(text: str) -> Dict[str, Tuple[str, str]]:
    """The scope path of every instruction of an HLO module, with the rule
    that gave it.

    ``op_name``: the instruction's own ``op_name``. An instruction with
    none takes the path of the first operand that has a phase, walking
    back through operands that have none (``operand``; instructions that
    run no op of their own, ``NO_PHASE``, count as having none); failing
    that, of its first user with a phase, walking forward through first
    users that have none (``user``); failing that, inside a called
    computation, the path of the instruction that calls it (``caller``).
    ``outside``: an ``op_name`` that names none of the phases. ``none``:
    no rule applies (an instruction of the entry computation whose
    operands and users have no phase: parameters, constants)."""
    instrs, _ = parse_hlo(text)
    users: Dict[str, List[str]] = {}
    callers: Dict[str, str] = {}
    for name, ins in instrs.items():
        for o in ins.operands:
            users.setdefault(o, []).append(name)
        for c in ins.calls:
            callers.setdefault(c, name)

    def own(name):
        """The instruction's own path, where it names a phase."""
        ins = instrs.get(name)
        if ins is None or ins.opcode in NO_PHASE or not phase_of(ins.op_name):
            return None
        return ins.op_name

    def unscoped(name):
        ins = instrs.get(name)
        return ins is not None and (ins.opcode in NO_PHASE or not ins.op_name)

    def back(name):
        seen, stack = set(), list(reversed(instrs[name].operands))
        while stack:
            o = stack.pop()
            if o in seen:
                continue
            seen.add(o)
            if path := own(o):
                return path
            if unscoped(o):
                stack.extend(reversed(instrs[o].operands))
        return None

    def forward(name):
        seen = set()
        while users.get(name) and name not in seen:
            seen.add(name)
            name = users[name][0]
            if path := own(name):
                return path
            if not unscoped(name):
                return None
        return None

    out: Dict[str, Tuple[str, str]] = {}

    def resolve(name):
        if name in out:
            return out[name]
        ins = instrs[name]
        caller = callers.get(ins.computation)
        if ins.op_name and ins.opcode not in NO_PHASE:
            res = (ins.op_name, "op_name" if own(name) else "outside")
        elif path := back(name):
            res = (path, "operand")
        elif path := forward(name):
            res = (path, "user")
        elif caller and phase_of(resolve(caller)[0]):
            res = (out[caller][0], "caller")
        else:
            res = ("", "none")
        out[name] = res
        return res

    for name in instrs:
        resolve(name)
    return out


def scope_table(text: str) -> Dict[str, str]:
    """Scope path by instruction signature, for every instruction of an
    HLO module's text."""
    instrs, _ = parse_hlo(text)
    return {instrs[name].signature: path
            for name, (path, _) in resolve_scopes(text).items()
            if instrs[name].signature}


# ------------------------------------------------------------ the window
def _in_window_s(view, e) -> float:
    return (min(e.end_ns, view.hi) - max(e.start_ns, view.lo)) * 1e-9


def phase_seconds(view, table: Dict[str, str]) -> Dict[str, float]:
    """Device seconds of the window's ops (summed over planes) by the
    round's phase, with ``table`` (``scope_table``) the round's program.
    The values partition the ops' time exactly."""
    out = dict.fromkeys(PHASES + (OTHER_PROGRAMS, UNATTRIBUTED), 0.0)
    for e in view.ops():
        path = table.get(signature(e.name))
        if path is None:
            key = OTHER_PROGRAMS
        else:
            key = phase_of(path) or UNATTRIBUTED
        out[key] += _in_window_s(view, e)
    return out


def kernel_seconds(view, table: Dict[str, str], phase: str
                   ) -> Dict[str, float]:
    """Device seconds per Pallas kernel in one phase, by the kernel's name
    (its instruction's name less the number)."""
    out: Dict[str, float] = {}
    for e in view.ops():
        sig = signature(e.name)
        if ('custom_call_target="tpu_custom_call"' in e.name
                and phase_of(table.get(sig, "")) == phase):
            name = re.sub(r"\.\d+$", "", sig.split(" ")[0])
            out[name] = out.get(name, 0.0) + _in_window_s(view, e)
    return out


# ------------------------------------------------------------ the round
def build_sim(cfg, traffic, model_mod, generator):
    """The cell's round as ``bench/engines/sim.py`` builds it: the
    simulator and the function that makes its state from a key."""
    import jax
    from repro.common.config import FLConfig, ModelConfig, TrainConfig
    from repro.core.sim import HotaSim
    from repro.models.model import build_model

    c, n = traffic["n_clusters"], traffic["n_clients"]
    fl = dict(cfg["fl"], sigma2=tuple(cfg["fl"]["sigma2"]))
    sim = HotaSim(build_model(ModelConfig(**cfg["model"])),
                  FLConfig(n_clusters=c, n_clients=n, **fl),
                  TrainConfig(lr=cfg["lr"]), generator.n_classes(traffic),
                  max_classes=cfg["head_classes"])

    def init(key):
        w = model_mod.init_weights(cfg, key, c, n)
        st = sim.init(jax.random.fold_in(key, 1))
        return st._replace(omega=w["omega"], heads=w["heads"])

    return sim, init


def round_args(sim, init, traffic, device):
    """Stand-ins for the round's arguments as the engine passes them: the
    state as ``jax.jit(init)`` leaves it (on the default device, not
    committed), the batch and key placed on ``device``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    c, n, b = traffic["n_clusters"], traffic["n_clients"], traffic["batch"]
    placed = SingleDeviceSharding(device)
    state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    x = jax.ShapeDtypeStruct((c, n, b, traffic["data"]["feature_dim"]),
                             jnp.float32, sharding=placed)
    y = jax.ShapeDtypeStruct((c, n, b), jnp.int32, sharding=placed)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=placed)
    return state, x, y, key


def step_hlo(cfg, traffic, model_mod, generator, device) -> str:
    """The compiled round's HLO text, for the device the cell ran on."""
    from repro.core.sim import HotaSim
    sim, init = build_sim(cfg, traffic, model_mod, generator)
    args = round_args(sim, init, traffic, device)
    return HotaSim._step.lower(sim, *args, sim.chan,
                               sim.faults).compile().as_text()


def round_table(ctx) -> Dict[str, str]:
    """``scope_table`` of the round a metric reader's cell ran."""
    import jax
    from bench import harness
    generator = harness.module("generators", ctx.traffic["generator"])
    return scope_table(step_hlo(ctx.cfg, ctx.traffic, ctx.model_mod,
                                generator, jax.devices()[0]))


_SEEN: Dict[int, Tuple[object, Optional[Dict[str, float]]]] = {}


def window_phases(ctx) -> Optional[Dict[str, float]]:
    """``phase_seconds`` of a metric reader's traced window, computed once
    per window; None without device planes, for an engine other than the
    simulator, or where no op of the round names a phase (a program
    without the scopes). The first call prints the per-round split on
    standard error (``phases:``, ms a round, with the kernels' time
    inside ``ota_fold``)."""
    view = ctx.trace
    if view is None or not view.planes or not view.rounds:
        return None
    if getattr(ctx, "traffic", None) is None or ctx.traffic.get(
            "engine") != "sim":
        return None
    got = _SEEN.get(id(view))
    if got is not None and got[0] is view:
        return got[1]
    table = round_table(ctx)
    secs = phase_seconds(view, table)
    if not any(secs[p] > 0 for p in PHASES):
        secs = None
    else:
        per_round = {k: v / view.rounds * 1e3 for k, v in secs.items()}
        kernels = {k: v / view.rounds * 1e3 for k, v in
                   kernel_seconds(view, table, "ota_fold").items()}
        print("phases: " + json.dumps({"ms_per_round": per_round,
                                       "ota_fold_kernels_ms": kernels}),
              file=sys.stderr)
    _SEEN.clear()
    _SEEN[id(view)] = (view, secs)
    return secs


def phase_ms_per_round(ctx, phase: str) -> Optional[float]:
    """Device ms a round in one of the round's phases (see
    ``window_phases``)."""
    secs = window_phases(ctx)
    if secs is None or secs[phase] <= 0:
        return None
    return secs[phase] / ctx.trace.rounds * 1e3
