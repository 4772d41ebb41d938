"""ScenarioBank — vectorized multi-scenario sweeps in a single jit.

The paper's headline results (Figs. 2-4) are comparisons *across channel
scenarios*: dynamic vs. equal weighting, one bad-channel cluster, diverse
σ². Historically each scenario was its own ``FLConfig`` — and because the
frozen config is part of the jit cache key, a figure meant a Python loop of
re-traced, re-compiled sims.

``ScenarioBank`` instead stacks the scenarios' traced knobs
(``repro.core.channel.ChannelParams``) into one bank with a leading (S,)
axis and ``vmap``s ``HotaSim.step_with_channel`` over it inside one jit:

* one trace + one compile for the whole figure;
* the batch/PRNG inputs are *shared* (``in_axes=None``) across scenarios —
  common random numbers by construction, so every scenario sees identical
  data order, channel gains (scaled by its own σ), masks-before-threshold
  and AWGN draws. Paired contrasts like Fig. 2's dynamic-vs-equal curves
  are variance-reduced for free;
* XLA batches the S scenarios through the same fused kernels, so the sweep
  costs far less than S sequential runs even ignoring compile time.

``ShardedScenarioBank`` (DESIGN.md §3.8) puts the same (S,) axis on a
1-D ``("scenario",)`` device mesh: scenario-batched state and ChannelParams
leaves are scenario-split, while the batch/PRNG inputs stay replicated on
every shard — common random numbers are preserved ACROSS shards, and the
plain ``vmap`` memory ceiling (all S states resident on one device) becomes
S/n_devices per device, so S ≫ 8 banks scale out instead of OOMing. The
packed OTA path's ``ota_bits_mode="supplied"`` draw depends only on the
shared key, so every shard computes the identical bit stream its scenarios
would see unsharded — the draw never varies per scenario or per shard.

``DistScenarioBank`` (DESIGN.md §3.10) lifts the *distributed* step onto
a 2-D ``("scenario", "cluster", "client")`` mesh: the raw Alg.-1 round
body (``repro.core.hota_step.make_hota_step_parts``) is vmapped over each
device row's local S/n_rows scenario slice INSIDE one shard_map, so the
client/cluster collectives (LAN psum, MAC psum, FSDP gathers) run
per-scenario on the trailing FL axes while scenario rows stay
embarrassingly parallel. Batch/PRNG enter replicated along the scenario
axis and nothing in the step reads a scenario coordinate, so CRN holds
across scenario shards by construction.

Every bank checkpoints through ``save``/``restore`` (DESIGN.md §3.9):
the (S,)-banked state rides the generic msgpack+npy envelope, the
scenario count is pinned in the manifest metadata, and restore re-places
leaves on the bank's own shardings — bit-identical trajectories across a
save/restore boundary.

Scenarios may vary only the traced knobs (``sigma2``, ``h_threshold``,
``noise_std``, ``ota``, ``weighting``); every other ``FLConfig`` field —
topology, local steps, FGN hyper-params, ``ota_mode``, ... — is baked into
the trace, and the bank rejects any scenario that differs in one.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Iterable, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.common.config import FLConfig
from repro.core.channel import ChannelParams, FaultParams, channel_params, \
    fault_params, stack_channel_params, stack_fault_params
from repro.core.sim import HotaSim, SimState
from repro.sharding.mesh_utils import SCENARIO_AXIS, bank_sharding, \
    replicated_sharding, scenario_axis_size, scenario_banked_spec, \
    scenario_banked_tree

# the ONLY FLConfig fields a scenario may vary — everything else is baked
# into the trace (topology, local steps, FGN hyper-params, ota_mode, ...).
# Fault knobs (DESIGN.md §3.14) are traced VALUES like the channel knobs,
# but ``faults`` itself is the static gate and must match the base config.
_FAULT_FIELDS = ("dropout_rate", "blackout_rate", "straggler_rate",
                 "staleness_rounds", "spike_norm")
TRACED_FIELDS = frozenset(
    {"sigma2", "h_threshold", "noise_std", "ota", "weighting",
     *_FAULT_FIELDS})

Scenario = Union[FLConfig, ChannelParams, FaultParams, Dict[str, Any]]


def _as_channel_params(sc: Scenario, base: FLConfig) -> ChannelParams:
    if isinstance(sc, ChannelParams):
        if sc.sigma2.shape != (base.n_clusters,):
            raise ValueError(
                f"scenario sigma2 shape {sc.sigma2.shape} != "
                f"(n_clusters,) = ({base.n_clusters},)")
        return sc
    if isinstance(sc, FaultParams):
        return channel_params(base)      # fault-only scenario: base channel
    if isinstance(sc, dict):
        sc = dataclasses.replace(base, **sc)
    if not isinstance(sc, FLConfig):
        raise TypeError(f"scenario must be FLConfig | ChannelParams | dict "
                        f"of FLConfig overrides, got {type(sc)}")
    for f in dataclasses.fields(FLConfig):
        if f.name in TRACED_FIELDS:
            continue
        sc_val, base_val = getattr(sc, f.name), getattr(base, f.name)
        if sc_val != base_val:
            raise ValueError(
                f"scenario field {f.name!r} differs from the bank's base "
                f"config: scenario has {f.name}={sc_val!r}, base has "
                f"{f.name}={base_val!r}; only traced knobs "
                f"{sorted(TRACED_FIELDS)} may vary within a ScenarioBank — "
                f"build a second bank for static changes")
    return channel_params(sc)


def _as_fault_params(sc: Scenario, base: FLConfig) -> FaultParams:
    """The scenario's FaultParams (DESIGN.md §3.14). Channel-only
    scenarios inherit the base config's fault knobs; static-field
    validation already happened in ``_as_channel_params``."""
    if isinstance(sc, FaultParams):
        if not base.faults:
            raise ValueError(
                "FaultParams scenario in a bank whose base config has "
                "faults=False — the fault gate is static (it changes the "
                "trace), so build the bank from a faults=True base")
        return sc
    if isinstance(sc, ChannelParams):
        return fault_params(base)
    if isinstance(sc, dict):
        sc = dataclasses.replace(base, **sc)
    if not base.faults:
        for f in _FAULT_FIELDS:
            if getattr(sc, f) != getattr(base, f):
                raise ValueError(
                    f"scenario varies fault knob {f!r} but the bank's base "
                    f"config has faults=False — the knob would be silently "
                    f"inert; build the bank from a faults=True base")
    return fault_params(sc)


class _BankCheckpoint:
    """Sweep-aware checkpointing shared by every bank flavor (DESIGN.md
    §3.9): one envelope for the whole (S,)-banked state, scenario count
    pinned in the manifest, restore re-placed on the bank's shardings."""

    def _abstract_states(self):
        raise NotImplementedError

    def _state_shardings(self):
        return None          # default placement (single-device banks)

    def _bank_fl(self):
        """The bank's FLConfig (sim banks hold it on the sim)."""
        fl = getattr(self, "fl", None)
        if fl is None and getattr(self, "sim", None) is not None:
            fl = self.sim.fl
        return fl

    def _layout_metadata(self):
        """The bank's packed-layout pin (DESIGN.md §3.13): section folds
        — and so every channel stream — depend on the layout, so it is
        saved with, and checked against, every bank checkpoint."""
        from repro.common.layout_tune import layout_of
        fl = self._bank_fl()
        return None if fl is None else layout_of(fl).to_metadata()

    def save(self, ckpt_dir: str, step: int, states) -> str:
        from repro.checkpoint.store import save_checkpoint
        md = {"kind": type(self).__name__,
              "n_scenarios": self.n_scenarios}
        layout = self._layout_metadata()
        if layout is not None:
            md["layout"] = layout
        return save_checkpoint(ckpt_dir, step, states, md)

    def restore(self, ckpt_dir: str, step: int):
        """Restore a state saved by ``save`` into THIS bank's layout —
        shape-checked against the bank's abstract state and re-placed on
        its shardings, so a restored bank continues bit-identically.
        Raises if the checkpoint pins a different scenario count or a
        different packed layout (the streams would silently change)."""
        from repro.checkpoint.store import checkpoint_metadata, \
            restore_checkpoint
        s = checkpoint_metadata(ckpt_dir, step).get("n_scenarios")
        if s is not None and s != self.n_scenarios:
            raise ValueError(
                f"checkpoint at step {step} was saved from a {s}-scenario "
                f"bank but this bank has S={self.n_scenarios} — a bank "
                f"only restores states with a matching scenario axis")
        return restore_checkpoint(ckpt_dir, step, self._abstract_states(),
                                  shardings=self._state_shardings(),
                                  expected_layout=self._layout_metadata())


class ScenarioBank(_BankCheckpoint):
    """An (S,)-batched bank of channel scenarios over one ``HotaSim``.

    >>> sim = HotaSim(model, base_fl, tcfg, n_cls)
    >>> bank = ScenarioBank(sim, [dict(weighting="equal"),
    ...                           dict(sigma2=(0.05, 1.0)),
    ...                           base_fl])
    >>> states = bank.init(jax.random.PRNGKey(0))
    >>> states, m = bank.step(states, xb, yb, jax.random.PRNGKey(1))
    >>> m["loss"].shape      # (S, C, N)
    """

    def __init__(self, sim: HotaSim, scenarios: Sequence[Scenario]):
        self.sim = sim
        self.chan_bank = stack_channel_params(
            [_as_channel_params(sc, sim.fl) for sc in scenarios])
        # fault knobs bank exactly like channel knobs (DESIGN.md §3.14);
        # with faults=False the bank is inert (the legacy trace never
        # reads it) but keeps the step arity uniform
        self.fault_bank = stack_fault_params(
            [_as_fault_params(sc, sim.fl) for sc in scenarios])
        self.n_scenarios = int(self.chan_bank.ota_on.shape[0])

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> SimState:
        """(S,)-batched initial state. All scenarios start from the SAME
        model/optimizer state (common random numbers extend to init)."""
        state = self.sim.init(key)
        s = self.n_scenarios
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (s,) + x.shape), state)

    # ------------------------------------------------------------------
    def step(self, states: SimState, xb, yb, key: jax.Array):
        """One Alg.-1 round for every scenario at once. ``xb``/``yb``/``key``
        are UNBATCHED and shared across scenarios (common random numbers);
        states and the returned metrics carry the leading (S,) axis."""
        return self._step(states, xb, yb, key, self.chan_bank,
                          self.fault_bank)

    def _vmapped_step(self, states, xb, yb, key, chan_bank, fault_bank):
        # supplied bits mode: the OTA stream draw is a function of the
        # shared key only, so it hoists out of the scenario vmap — one
        # draw per round, not per scenario. The client-folded sim path
        # (DESIGN.md §3.12) draws key-only in either mode; the flag is
        # kept so the per-slab kernel path composes identically.
        # The participation draw (PART_FOLD) likewise depends only on
        # the shared key — scenarios vary the fault RATES the shared
        # uniforms are compared against, so participation is monotone-
        # coupled across the bank (CRN for fault sweeps).
        def step(st, x, y, k, ch, fp):
            return self.sim.step_with_channel(
                st, x, y, k, ch, ota_bits_mode="supplied", faults=fp)
        return jax.vmap(step, in_axes=(0, None, None, None, 0, 0))(
            states, xb, yb, key, chan_bank, fault_bank)

    @partial(jax.jit, static_argnums=0)
    def _step(self, states, xb, yb, key, chan_bank, fault_bank):
        return self._vmapped_step(states, xb, yb, key, chan_bank,
                                  fault_bank)

    # ------------------------------------------------------------------
    def run(self, states: SimState, batches: Iterable[Tuple[Any, Any]],
            keys: Sequence[jax.Array]):
        """Drive the bank over an iterable of (x, y) batches; returns the
        final states and metrics stacked along a leading time axis:
        leaves (T, S, ...)."""
        history: List[Any] = []
        for (x, y), k in zip(batches, keys):
            states, m = self.step(states, jnp.asarray(x), jnp.asarray(y), k)
            history.append(m)
        if not history:
            raise ValueError("no batches supplied")
        return states, jax.tree.map(lambda *xs: jnp.stack(xs), *history)

    # ------------------------------------------------------------------
    def scenario_state(self, states: SimState, s: int) -> SimState:
        """Slice one scenario's unbatched SimState out of the bank."""
        return jax.tree.map(lambda x: x[s], states)

    # ------------------------------------------------------------------
    def _abstract_states(self):
        # the PLAIN init's shapes (placement-free): subclasses re-place
        # via _state_shardings, so eval_shape must not hit device_put
        return jax.eval_shape(lambda k: ScenarioBank.init(self, k),
                              jax.random.PRNGKey(0))


class ShardedScenarioBank(ScenarioBank):
    """A ScenarioBank whose (S,) axis is sharded over a "scenario" mesh.

    Same single-trace vmapped step as the base class, but wrapped in a
    manual ``shard_map`` over the 1-D ``("scenario",)`` mesh: each device
    runs the step on its LOCAL S/n_devices slice of the scenario-batched
    state and ChannelParams bank, while the per-step batch and PRNG key
    enter replicated (``P()``) — every shard consumes bit-identical
    data/keys, so the CRN contract survives sharding. The step body has
    no cross-scenario collectives, so the shards run embarrassingly
    parallel (manual mode — GSPMD never gets the chance to replicate the
    compute or insert all-gathers). See DESIGN.md §3.8.

    >>> mesh = make_scenario_mesh()                 # repro.launch.mesh
    >>> bank = ShardedScenarioBank(sim, scenarios, mesh)
    >>> states = bank.init(jax.random.PRNGKey(0))   # leaves (S,...) sharded
    >>> states, m = bank.step(states, xb, yb, key)  # m: (S, C, N) sharded
    """

    def __init__(self, sim: HotaSim, scenarios: Sequence[Scenario],
                 mesh=None):
        super().__init__(sim, scenarios)
        if mesh is None:
            from repro.launch.mesh import make_scenario_mesh
            mesh = make_scenario_mesh()
        n_dev = scenario_axis_size(mesh)
        if self.n_scenarios % n_dev:
            raise ValueError(
                f"scenario count S={self.n_scenarios} must divide evenly "
                f"over the {n_dev}-device scenario mesh — pad the bank or "
                f"shrink the mesh (make_scenario_mesh(n_devices=...))")
        self.mesh = mesh
        self._banked = bank_sharding(mesh)
        self._shared = replicated_sharding(mesh)
        self.chan_bank = jax.device_put(self.chan_bank, self._banked)
        self.fault_bank = jax.device_put(self.fault_bank, self._banked)

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> SimState:
        """(S,)-batched initial state, scenario-split across the mesh.
        Init itself is shared (CRN extends to init): each shard holds its
        scenarios' identical copy of the same model/optimizer state."""
        return jax.device_put(super().init(key), self._banked)

    # ------------------------------------------------------------------
    def step(self, states: SimState, xb, yb, key: jax.Array):
        """One Alg.-1 round for every scenario, scenario-parallel across
        devices. ``xb``/``yb``/``key`` are committed replicated so every
        shard reads identical data and keys; the supplied-bits channel
        draw depends only on the shared key, so each shard computes the
        same stream its scenarios would see unsharded."""
        xb = jax.device_put(jnp.asarray(xb), self._shared)
        yb = jax.device_put(jnp.asarray(yb), self._shared)
        key = jax.device_put(key, self._shared)
        return self._step(states, xb, yb, key, self.chan_bank,
                          self.fault_bank)

    @partial(jax.jit, static_argnums=0)
    def _step(self, states, xb, yb, key, chan_bank, fault_bank):
        from jax.sharding import PartitionSpec as P
        banked, shared = P(SCENARIO_AXIS), P()
        f = jax.shard_map(
            self._vmapped_step,
            mesh=self.mesh,
            in_specs=(banked, shared, shared, shared, banked, banked),
            out_specs=(banked, banked),
            axis_names={SCENARIO_AXIS}, check_vma=False)
        return f(states, xb, yb, key, chan_bank, fault_bank)

    # ------------------------------------------------------------------
    def _state_shardings(self):
        return self._banked


class DistScenarioBank(_BankCheckpoint):
    """The DISTRIBUTED step on a 2-D (scenario × client) mesh.

    Where ``ScenarioBank`` sweeps the vmap *simulator*, this bank sweeps
    the production shard_map step (``repro.core.hota_step``): the mesh is
    ("scenario", "cluster", "client") — ``repro.launch.mesh.
    make_dist_scenario_mesh`` — and ONE shard_map covers all three axes.
    Each scenario row vmaps the raw Alg.-1 round body over its local
    S/n_rows scenario slice while the body's client/cluster collectives
    (LAN psum, MAC psum, FSDP gathers — slab-native per DESIGN.md §3.10)
    run on the trailing FL axes. Scenario rows never communicate.

    CRN across scenario shards: batch and PRNG enter replicated along
    the scenario axis, channel keys fold only (step, section, cluster,
    chunk) — no scenario coordinate exists in the step — so every
    scenario sees bit-identical data and channel draws whether it lives
    on row 0 or row k, and a bank sharded S-ways reproduces the 1-row
    bank exactly.

    >>> mesh = make_dist_scenario_mesh(n_clusters=1, n_clients=2)
    >>> bank = DistScenarioBank(model, fl, tcfg, scenarios, mesh,
    ...                         loss_kind="cls", n_out=8)
    >>> states = bank.init(jax.random.PRNGKey(0))
    >>> states, m = bank.step(states, tokens, labels, key)  # m: (S, ...)
    """

    def __init__(self, model, fl: FLConfig, tcfg, scenarios:
                 Sequence[Scenario], mesh=None, *, loss_kind: str = "lm",
                 n_out=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.hota import _mesh_client_axes
        from repro.core.hota_step import make_hota_step_parts
        if mesh is None:
            from repro.launch.mesh import make_dist_scenario_mesh
            mesh = make_dist_scenario_mesh(fl.n_clusters, fl.n_clients)
        assert SCENARIO_AXIS in mesh.axis_names, mesh
        self.mesh = mesh
        self.fl = fl
        parts = make_hota_step_parts(model, mesh, fl, tcfg,
                                     loss_kind=loss_kind, n_out=n_out)
        if parts.n_total_clusters != fl.n_clusters:
            raise ValueError(
                f"mesh has {parts.n_total_clusters} clusters but "
                f"fl.n_clusters={fl.n_clusters}")
        self._parts = parts
        self.chan_bank = stack_channel_params(
            [_as_channel_params(sc, fl) for sc in scenarios])
        self.fault_bank = stack_fault_params(
            [_as_fault_params(sc, fl) for sc in scenarios])
        self.n_scenarios = int(self.chan_bank.ota_on.shape[0])
        n_rows = scenario_axis_size(mesh)
        if self.n_scenarios % n_rows:
            raise ValueError(
                f"scenario count S={self.n_scenarios} must divide evenly "
                f"over the {n_rows}-row scenario axis — pad the bank or "
                f"shrink the mesh")

        self._state_banked = scenario_banked_tree(parts.state_specs)
        self._metric_banked = scenario_banked_tree(parts.metric_spec)
        chan_banked = scenario_banked_tree(parts.chan_spec)
        faults_banked = scenario_banked_tree(parts.faults_spec)

        def body(states, tokens, labels, key, chan_bank, fault_bank):
            # local scenario slice: vmap the single-scenario round body;
            # its client/cluster collectives batch over the vmap axis
            return jax.vmap(parts.step,
                            in_axes=(0, None, None, None, 0, 0))(
                states, tokens, labels, key, chan_bank, fault_bank)

        self._inner = jax.shard_map(
            body, mesh=mesh,
            in_specs=(self._state_banked, parts.batch_spec[0],
                      parts.batch_spec[1], P(), chan_banked, faults_banked),
            out_specs=(self._state_banked, self._metric_banked),
            axis_names=set(_mesh_client_axes(mesh)) | {SCENARIO_AXIS},
            check_vma=False)
        self._jstep = jax.jit(self._inner)
        self.chan_bank = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(SCENARIO_AXIS))), self.chan_bank)
        self.fault_bank = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(SCENARIO_AXIS))), self.fault_bank)

    # ------------------------------------------------------------------
    def _init_states(self, key: jax.Array):
        st = self._parts.init_fn(key)
        s = self.n_scenarios
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (s,) + x.shape), st)

    def init(self, key: jax.Array):
        """(S,)-banked initial HotaState, scenario-split over the rows
        and FSDP-sharded inside each row (CRN extends to init: every
        scenario starts from the same state)."""
        return self._place(self._init_states(key))

    def _place(self, states):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(self.mesh, sp)),
            states, self._state_banked,
            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------
    def step(self, states, tokens, labels, key: jax.Array):
        """One distributed Alg.-1 round for every scenario. ``tokens``/
        ``labels`` are the GLOBAL flat client batch (the 1-D step's
        layout), committed replicated along the scenario axis; ``key``
        is shared — CRN across scenarios and across scenario rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        tokens = jax.device_put(
            jnp.asarray(tokens),
            NamedSharding(self.mesh, self._parts.batch_spec[0]))
        labels = jax.device_put(
            jnp.asarray(labels),
            NamedSharding(self.mesh, self._parts.batch_spec[1]))
        key = jax.device_put(key, NamedSharding(self.mesh, P()))
        return self._jstep(states, tokens, labels, key, self.chan_bank,
                           self.fault_bank)

    # ------------------------------------------------------------------
    def scenario_state(self, states, s: int):
        """Slice one scenario's unbatched HotaState out of the bank."""
        return jax.tree.map(lambda x: x[s], states)

    # ------------------------------------------------------------------
    def _abstract_states(self):
        return jax.eval_shape(self._init_states, jax.random.PRNGKey(0))

    def _state_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), self._state_banked,
            is_leaf=lambda x: isinstance(x, P))
