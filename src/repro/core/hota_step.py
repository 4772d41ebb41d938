"""The distributed HOTA-FedGradNorm training step (shard_map + custom-vjp OTA).

``make_hota_train_step(model, mesh, fl, tcfg)`` returns (init_fn, step_fn,
state_specs) where step_fn is the *full* Algorithm 1 round:

  phase 0  trunk forward once (PS->IS->client broadcast = FSDP gather)
  phase A  τ_h personalized-head Adam steps on the frozen features
  phase B  FGN inputs: per-client tail loss + masked ‖∇_{ω̃}F‖ (eq. 6),
           then the distributed Alg. 2 update of p (psum-means over "client")
  phase C  full forward/backward; every shared-param gradient flows through
           the custom-vjp OTA gather (LAN psum -> masked MAC psum -> ĝ);
           Adam on the FSDP shards (the PS update), local Adam on heads.

Two phase-C/optimizer engines share the phases (DESIGN.md §3.10):

* **slab-native** (``fl.use_pallas_ota=True``, the default): the WHOLE
  shared model rides ONE packed multi-section layout — a single
  custom-vjp gather (``repro.core.hota_slab.make_packed_omega_gather``)
  whose backward runs the fused mask+weighted-apply kernel on each
  leaf's storage in place (zero-copy — the (P,) slab never
  materializes) and needs one psum set for the whole model; phase B's
  ‖M∘∇ω̃‖ re-draws only the ω̃ section stream; the PS Adam runs on the
  slab view (``repro.optim.adam.SlabAdamState`` — moments as one flat
  slab, params unpacked once at the model-apply boundary). ``ota_mode``
  does not apply to this engine (DESIGN.md §3.11).
* **per-leaf** (``use_pallas_ota=False``): the PR-1 oracle — per-leaf
  param hooks, per-leaf gain draws, 3 psums per leaf, pytree Adam.

Every channel/weighting knob is TRACED (DESIGN.md §3.8): ``step_fn`` takes
an optional ``ChannelParams`` whose leaves (σ², H_th, noise std, the
``ota_on`` gate AND the ``fgn_on`` weighting gate) are plain arrays, so one
compiled step serves every scenario — dynamic vs. equal weighting is a
``jnp.where`` blend of the Alg.-2 update and the p≡1 passthrough (the same
gating ``sim.step_with_channel`` uses via ``fgn_update_gated``), never a
retrace. Phases 0/A/B always run; the equal-weight scenario simply selects
the passthrough (collectives stay uniform across devices — no lax.cond).
Omitting ``chan`` uses the knobs baked from the factory's ``FLConfig`` —
and when that config is the naive baseline (equal weighting AND τ_h = 0),
default-chan calls take a statically-specialized trace with phases 0/A/B
removed entirely (their outputs could never be consumed).

``make_hota_step_parts`` exposes the raw (un-shard_mapped) step body plus
its specs so other harnesses can lay it on bigger meshes — the 2-D
(scenario × client) ``DistScenarioBank`` (``repro.core.sweep``) vmaps it
over scenario slices inside one shard_map.

Scale adaptations vs the paper (DESIGN.md §3.7): τ_ω = 1 (per-client local
ω copies are impossible at 14B-141B params); the loss over the vocab head
is computed in sequence chunks to bound logit memory. With τ_h = 0 there
is no phase A, so heads train on the phase-C gradient instead (for every
scenario — head training must be scenario-uniform under a traced gate).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import FLConfig, TrainConfig
from repro.core import ota
from repro.core.channel import (
    ChannelParams, FaultParams, channel_params, cluster_channel,
    fault_params,
)
from repro.core.hota import (
    OTACtx, build_axes_registry, cluster_index, fold_tags,
    full_transmission_mask, make_ota_gather, make_param_hook,
    shard_specs_for, _fsdp_axis, _is_axes, _mesh_client_axes,
    _mesh_cluster_axes, _mesh_data_axes,
)
from repro.core.hota_slab import (
    _fsdp_axis_full, make_packed_omega_gather, packed_omega_key,
    plain_gather_full, sectioned_final_norm,
)
from repro.models.model import Model
from repro.models.params import abstract_params, init_params, logical_axes
from repro.optim.adam import (
    AdamState, SlabAdamState, adam_init, adam_update, slab_adam_init,
    slab_adam_update,
)

LOSS_CHUNK = 512

# One entry appended per TRACE of a step body (tag, ota_mode). Pinned by
# the retrace check in tests/dist_programs/dist_slab_step.py: sweeping
# ChannelParams VALUES through a compiled step must never grow this list —
# only genuinely static knobs (ota_mode, use_pallas_ota, topology) may
# (DESIGN.md §3.11).
TRACE_LOG: List[Tuple[str, str]] = []


def chunked_lm_loss(head, head_apply, feats, labels, chunk=LOSS_CHUNK):
    """CE over a big vocab computed in sequence chunks (remat'd)."""
    b, s, d = feats.shape
    if s % chunk != 0 or s <= chunk:
        logits = head_apply(head, feats)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return -jnp.mean(ll)
    n = s // chunk
    fc = feats.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, xs):
        f, l = xs
        logits = head_apply(head, f)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, l[..., None], -1)[..., 0]
        return acc + jnp.sum(ll), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (fc, lc))
    return -tot / (b * s)


def cls_head_loss(head, head_apply, feats, labels):
    logits = head_apply(head, feats)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])


class HotaState(NamedTuple):
    omega: Any          # {"trunk","final"} — FSDP shards (global arrays)
    opt: Any            # AdamState over omega
    heads: Any          # per-client stacked: leaves (n_total_clients, ...)
    head_opt: Any
    p: jax.Array        # (n_total_clients,)
    fgn_mu: jax.Array   # (n_total_clients,)
    fgn_nu: jax.Array
    fgn_t: jax.Array    # scalar
    f0: jax.Array       # (n_total_clients,)
    step: jax.Array
    # Stale-model state (DESIGN.md §3.15) — present only when fl.faults
    # (None = empty pytree node, legacy states and specs unchanged).
    # Trailing position matters: flatten order keeps the legacy prefix,
    # so fault-free states round-trip checkpoints bit-identically.
    omega_stale: Any = None   # delayed FSDP-sharded copy stragglers use
    stale_age: Any = None     # () rounds since omega_stale was refreshed


class StepParts(NamedTuple):
    """The raw distributed round, before any shard_map: everything a
    harness needs to lay the body on its own mesh (the 1-D wrapper below,
    or the 2-D scenario × client ``DistScenarioBank``)."""
    init_fn: Callable
    step: Callable          # step(state, tokens, labels, key, chan, faults[, fast])
    state_specs: Any        # HotaState of PartitionSpecs (FL axes only)
    batch_spec: Tuple
    metric_spec: Dict
    chan_spec: Any          # ChannelParams of P() (replicated knobs)
    chan_all: Any           # the factory FLConfig's baked ChannelParams
    n_total_clusters: int
    has_fast: bool          # statically-specialized naive baseline exists
    faults_spec: Any = None     # FaultParams of P() (replicated knobs)
    faults_all: Any = None      # the factory FLConfig's baked FaultParams


def make_hota_step_parts(
    model: Model,
    mesh,
    fl: FLConfig,
    tcfg: TrainConfig,
    *,
    loss_kind: str = "lm",
    n_out: Optional[int] = None,
) -> StepParts:
    """Build the un-shard_mapped Alg.-1 round body + its specs for ``mesh``
    (only the FL axes — cluster/client/pod — of the mesh are read, so the
    same body serves 1-D FL meshes and the 2-D scenario × client mesh)."""
    cfg = model.cfg
    data_axes = _mesh_data_axes(mesh)           # ("cluster","client")
    cluster_axes = _mesh_cluster_axes(mesh)     # ("pod","cluster") | ("cluster",)
    client_axes = _mesh_client_axes(mesh)       # all FL axes
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_clients = sizes["client"]
    n_shards = int(np.prod([sizes[a] for a in data_axes]))
    n_total_clients = int(np.prod([sizes[a] for a in client_axes]))
    n_total_clusters = int(np.prod([sizes[a] for a in cluster_axes]))

    compute_dtype = jnp.dtype(cfg.compute_dtype)
    gather = make_ota_gather(data_axes, cluster_axes, n_clients, n_shards,
                             compute_dtype, mode=fl.ota_mode)
    registry = build_axes_registry(model)
    chan_all = channel_params(fl, n_clusters=n_total_clusters)
    faults_all = fault_params(fl)
    if fl.faults and not fl.use_pallas_ota:
        raise ValueError(
            "fl.faults requires the slab engine (use_pallas_ota=True): the "
            "per-leaf distributed path has no participation-aware "
            "aggregation — use the per-leaf SIMULATOR (repro.core.sim) as "
            "the fault oracle instead (DESIGN.md §3.14)")
    if fl.ota_streaming:
        raise ValueError(
            "fl.ota_streaming is a SIMULATOR engine (DESIGN.md §3.15): the "
            "distributed round already holds one cluster per device group, "
            "so there is no cluster batch to stream — the flag would be "
            "silently inert here. Use fl.ota_sectioned for the section-"
            "streaming distributed schedule (DESIGN.md §3.16)")
    if fl.ota_sectioned and not fl.use_pallas_ota:
        raise ValueError(
            "fl.ota_sectioned requires the slab engine (use_pallas_ota="
            "True): the per-leaf distributed path has no section layout to "
            "stream — the flag would be silently inert (DESIGN.md §3.16)")
    if fl.ota_sectioned and fl.ota_sections != "toplevel":
        raise ValueError(
            "fl.ota_sectioned requires a multi-section layout "
            "(ota_sections='toplevel'): with the legacy two-section 'tail' "
            "layout the head IS the whole trunk, so section streaming "
            "cannot bound peak memory (DESIGN.md §3.16)")
    if fl.max_section_rows and not fl.use_pallas_ota:
        raise ValueError(
            "fl.max_section_rows splits the slab engine's section layout "
            "(use_pallas_ota=True); on the per-leaf path it would be "
            "silently inert (DESIGN.md §4)")

    head_specs = model.head_specs(n_out)
    final_axes = [a for a in jax.tree.leaves(
        logical_axes(model.final_specs()), is_leaf=_is_axes)]
    use_slab = fl.use_pallas_ota
    if use_slab:
        # slab-native engine: the ENTIRE shared model {final, trunk} rides
        # one multi-section packed gather — one fused kernel per leaf (in
        # place), ONE psum set for the whole model (DESIGN.md §3.10).
        omega_template = {"final": abstract_params(model.final_specs()),
                          "trunk": abstract_params(model.trunk_specs())}
        omega_axes = [a for a in jax.tree.leaves(
            {"final": logical_axes(model.final_specs()),
             "trunk": logical_axes(model.trunk_specs())}, is_leaf=_is_axes)]
        # section layout from the static FLConfig fields (normally the
        # tuned LayoutChoice — repro.common.layout_tune): the Section
        # partition decides the stream folds of every channel draw
        omega_gather, omega_pk = make_packed_omega_gather(
            data_axes, cluster_axes, n_clients, n_shards, compute_dtype,
            omega_template, omega_axes, n_clusters=n_total_clusters,
            sections=fl.ota_sections,
            min_section_rows=fl.min_section_rows,
            max_section_rows=fl.max_section_rows,
            sectioned=fl.ota_sectioned)
        # local (per-device) slab length: FSDP leaves contribute their
        # shard, replicated leaves their full size — the SlabAdamState
        # moments layout (repro.optim.adam)
        omega_fsdp = [_fsdp_axis_full(ax) for ax in omega_axes]
        slab_local_len = sum(
            int(np.prod(l.shape)) // (n_shards if ax >= 0 else 1)
            for l, ax in zip(jax.tree.leaves(omega_template), omega_fsdp))
    else:
        # use_pallas_ota=True means the whole-model slab engine, and
        # False is the all-per-leaf oracle
        omega_gather = omega_pk = None
        omega_axes = omega_fsdp = slab_local_len = None

    if loss_kind == "lm":
        loss_fn = lambda head, feats, labels: chunked_lm_loss(
            head, model.head_apply, feats, labels)
    else:
        loss_fn = lambda head, feats, labels: cls_head_loss(
            head, model.head_apply, feats, labels)

    # ---------------- shardings ----------------
    omega_manual = shard_specs_for(model, mesh)          # manual FL axes only
    heads_manual = jax.tree.map(
        lambda s: P(client_axes), head_specs,
        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))
    scalar_clients = P(client_axes)

    if use_slab:
        # moments live as ONE flat slab per device; the global array is
        # the shard-major concatenation of the local slabs
        slab_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        opt_spec = SlabAdamState(step=P(), mu=slab_spec, nu=slab_spec)
    else:
        opt_spec = AdamState(step=P(), mu=omega_manual, nu=omega_manual)
    state_specs = HotaState(
        omega=omega_manual,
        opt=opt_spec,
        heads=heads_manual,
        head_opt=AdamState(step=P(), mu=heads_manual, nu=heads_manual),
        p=scalar_clients, fgn_mu=scalar_clients, fgn_nu=scalar_clients,
        fgn_t=P(), f0=scalar_clients, step=P(),
        # the stale copy shards exactly like omega (same FSDP layout)
        omega_stale=(omega_manual if fl.faults else None),
        stale_age=(P() if fl.faults else None))
    batch_spec = (P(client_axes), P(client_axes))
    metric_spec = {"loss": P(), "p_mean": P(), "p_min": P(), "p_max": P(),
                   "fgrad": P(), "gnorm_mean": P()}
    if fl.faults:
        metric_spec = dict(metric_spec, skipped=P(), n_participants=P())

    # ---------------- init ----------------
    def init_fn(key: jax.Array) -> HotaState:
        k1, k2 = jax.random.split(key)
        omega = {
            "final": init_params(model.final_specs(),
                                 jax.random.fold_in(k1, ota.FINAL_INIT_FOLD)),
            "trunk": init_params(model.trunk_specs(), k1),
        }
        heads = jax.vmap(lambda kc: init_params(head_specs, kc))(
            jax.random.split(k2, n_total_clients))
        zc = jnp.zeros((n_total_clients,), jnp.float32)
        zeros32 = lambda t: jax.tree.map(
            lambda x: jnp.zeros_like(x, jnp.float32), t)
        head_opt = AdamState(step=jnp.zeros((), jnp.int32),
                             mu=zeros32(heads), nu=zeros32(heads))
        if use_slab:
            opt0 = SlabAdamState(
                step=jnp.zeros((), jnp.int32),
                mu=jnp.zeros((n_shards * slab_local_len,), jnp.float32),
                nu=jnp.zeros((n_shards * slab_local_len,), jnp.float32))
        else:
            opt0 = adam_init(omega)
        return HotaState(
            omega=omega, opt=opt0, heads=heads,
            head_opt=head_opt,
            p=jnp.ones((n_total_clients,), jnp.float32),
            fgn_mu=zc, fgn_nu=zc, fgn_t=jnp.zeros((), jnp.int32),
            f0=jnp.ones((n_total_clients,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
            omega_stale=(jax.tree.map(jnp.array, omega) if fl.faults
                         else None),
            stale_age=(jnp.zeros((), jnp.float32) if fl.faults else None))

    # ---------------- the sharded step ----------------
    def _step(state: HotaState, tokens, labels, key, chan: ChannelParams,
              faults: FaultParams = None, fast: bool = False):
        TRACE_LOG.append(("slab" if use_slab else "leaf", fl.ota_mode))
        base_key = jax.random.fold_in(key, state.step)
        cidx = cluster_index(cluster_axes)
        chan_c = cluster_channel(chan, cidx)
        head = jax.tree.map(lambda a: a[0], state.heads)
        head_opt = AdamState(step=state.head_opt.step,
                             mu=jax.tree.map(lambda a: a[0], state.head_opt.mu),
                             nu=jax.tree.map(lambda a: a[0], state.head_opt.nu))
        head0, head_opt0 = head, head_opt
        p_i = state.p[0]
        f0_i = state.f0[0]

        # fault injection (DESIGN.md §3.14, static fl.faults gate): every
        # device draws the SAME (C, N) participation from base_key's
        # reserved PART_FOLD domain (disjoint from all channel streams —
        # resampling fault rates is CRN-safe), then reads its own slot.
        # Stragglers carry the stale-model variant (DESIGN.md §3.15):
        # the whole client round — features, head steps, FGN inputs and
        # the phase-C loss — evaluates against the delayed ``omega_stale``
        # copy, and the transmit weight takes the FedBuff 1/√(1+age)
        # discount from the carried age, exactly like the sim engine.
        partc = None
        stale_full = None
        if fl.faults:
            fp = faults_all if faults is None else faults
            partc = ota.draw_participation(base_key, fp, n_total_clusters,
                                           n_clients)
            client_idx = jax.lax.axis_index(CLIENT_AXIS_NAME)
            part_me = partc.part[cidx, client_idx]
            stale_me = partc.stale[cidx, client_idx]
            live_me = partc.live[cidx]

        if fast:
            # statically-specialized naive baseline (equal weighting,
            # τ_h = 0, no chan override): phases 0/A/B vanish. Same
            # passthrough semantics as the traced gate below, minus the
            # discarded FGN inputs (f0 stays frozen — it is only read by
            # the FGN branch, which this trace can never take).
            p_new = p_i
            mu, nu = state.fgn_mu[0], state.fgn_nu[0]
            fgn_t_new = state.fgn_t
            fgrad_val = jnp.zeros(())
            n_i = jnp.zeros(())
            f0 = f0_i
        else:
            # ---- phase 0: trunk features (ω frozen; broadcast = gather) --
            if use_slab:
                # one plain whole-model gather — phases 0/B never
                # backprop through the channel, so no custom vjp here
                omega_full0 = plain_gather_full(state.omega, omega_fsdp,
                                                data_axes, compute_dtype)
                if partc is not None:
                    # stale-model variant (§3.15): gather the delayed
                    # copy too and let each straggler's device see IT for
                    # the whole round — the dist analogue of the sim's
                    # per-client om_eff select. The gathers stay device-
                    # uniform; only the scalar select differs per client.
                    stale_full = plain_gather_full(
                        state.omega_stale, omega_fsdp, data_axes,
                        compute_dtype)
                    omega_full0 = jax.tree.map(
                        lambda f, s: jnp.where(stale_me > 0.5, s, f),
                        omega_full0, stale_full)
                hidden, _, _ = model.trunk_apply(omega_full0["trunk"],
                                                 tokens, mode="train")
                final_full = omega_full0["final"]
            else:
                hook_fwd = make_param_hook(gather, registry, base_key, 1.0,
                                           chan_c)
                hidden, _, _ = model.trunk_apply(state.omega["trunk"],
                                                 tokens, mode="train",
                                                 param_hook=hook_fwd)
                final_full = _plain_gather_tree(state.omega["final"],
                                                final_axes, data_axes,
                                                compute_dtype)
            hidden = jax.lax.stop_gradient(hidden)

            def tail_loss(ff, hd):
                feats = model.final_apply(ff, hidden)
                return loss_fn(hd, feats, labels)

            # ---- phase A: τ_h personalized-head steps (Alg. 1 l. 10-11) --
            def head_step(carry, _):
                hd, hopt = carry
                g = jax.grad(lambda h_: tail_loss(final_full, h_))(hd)
                hd, hopt = adam_update(g, hopt, hd, tcfg.lr)
                return (hd, hopt), None
            (head, head_opt), _ = jax.lax.scan(
                head_step, (head, head_opt), None, length=fl.tau_h)

            # ---- phase B: FGN inputs + distributed Alg. 2 ----
            F_i, g_final = jax.value_and_grad(
                lambda ff: tail_loss(ff, head))(final_full)
            if use_slab:
                # eq. 5 masks = the ω̃ SECTION of the same slab draw the
                # phase-C backward applies (only that stream is re-drawn)
                n_i = sectioned_final_norm(g_final,
                                           packed_omega_key(base_key),
                                           chan_c, cluster_axes, omega_pk)
            else:
                n_i = _masked_final_norm(g_final, final_axes, base_key,
                                         chan_c, fl, cluster_axes,
                                         n_clients)
            f0 = jnp.where(state.step == 0, F_i, f0_i)
            ratio = F_i / jnp.maximum(f0, 1e-12)

            # Alg. 2, computed unconditionally so the psums stay uniform
            # across devices, then selected by the traced weighting gate —
            # equal-weight scenarios take the passthrough of the SAME
            # trace (the distributed analogue of fgn_update_gated).
            gbar = jax.lax.pmean(p_i * n_i, CLIENT_AXIS_NAME)
            rmean = jax.lax.pmean(ratio, CLIENT_AXIS_NAME)
            target = jnp.power(
                jnp.maximum(ratio / jnp.maximum(rmean, 1e-12), 1e-12),
                fl.gamma)
            resid = p_i * n_i - gbar * target
            gp = jnp.sign(resid) * n_i
            fgrad_fgn = jax.lax.psum(jnp.abs(resid), CLIENT_AXIS_NAME)
            # scalar Adam on p_i (state shared-stepped)
            t = (state.fgn_t + 1).astype(jnp.float32)
            b1, b2, eps = 0.9, 0.999, 1e-8
            mu_fgn = b1 * state.fgn_mu[0] + (1 - b1) * gp
            nu_fgn = b2 * state.fgn_nu[0] + (1 - b2) * gp * gp
            p_fgn = p_i - fl.alpha * (mu_fgn / (1 - b1 ** t)) / (
                jnp.sqrt(nu_fgn / (1 - b2 ** t)) + eps)
            p_fgn = jnp.maximum(p_fgn, fl.p_min + 1e-6)
            p_fgn = p_fgn * n_clients / jnp.maximum(
                jax.lax.psum(p_fgn, CLIENT_AXIS_NAME), 1e-12)

            # gate off: p/mu/nu/t ALL pass through untouched — identical
            # to fgn_update_gated's FGNState gating, so a scenario
            # schedule that flips the gate mid-run sees the same p
            # trajectory (and the same Adam bias-correction t) as the
            # sim path. p starts at 1, so for pure-equal runs the
            # passthrough is the old static p≡1 branch.
            fgn_on = chan_c.fgn_on > 0.5
            # under faults a dead cluster's (p, Adam moment) state also
            # freezes (its IS heard nothing this round); fgn_t stays
            # device-uniform — it is a single replicated scalar, unlike
            # the sim's per-cluster FGNState (DESIGN.md §3.14)
            fgn_upd = (fgn_on if partc is None
                       else jnp.logical_and(fgn_on, live_me > 0.5))
            p_new = jnp.where(fgn_upd, p_fgn, p_i)
            mu = jnp.where(fgn_upd, mu_fgn, state.fgn_mu[0])
            nu = jnp.where(fgn_upd, nu_fgn, state.fgn_nu[0])
            fgn_t_new = jnp.where(fgn_on, state.fgn_t + 1, state.fgn_t)
            fgrad_val = jnp.where(fgn_on, fgrad_fgn, jnp.zeros(()))

        # ---- phase C: full backward through the OTA aggregation ----
        # Channel keys fold only (step, layer, leaf): masks and AWGN are
        # identical across microbatches, so averaging the per-microbatch
        # estimates equals ONE MAC transmission of the round-averaged
        # x^(l) — exact Alg.-1 round semantics under grad accumulation.
        if use_slab:
            # one custom-vjp gather for the WHOLE model: its backward is
            # the slab-native aggregation (fused w·g·M kernel per leaf in
            # place + ONE psum set — repro.core.hota_slab). Under faults
            # the transmit weight folds participation and the FedBuff
            # staleness discount; live/n_eff generalize the eq.-10 guard.
            if partc is not None:
                # FedBuff discount from the CARRIED age (how long ago the
                # stale copy was refreshed), not the static τ — a copy
                # refreshed last round is barely discounted
                disc = jnp.where(stale_me > 0.5,
                                 jax.lax.rsqrt(1.0 + state.stale_age), 1.0)
                w_tx = jnp.asarray(p_new, jnp.float32) * part_me * disc
                ctx_live, ctx_n_eff = partc.live, partc.n_eff
            else:
                w_tx = jnp.asarray(p_new, jnp.float32)
                ctx_live = ctx_n_eff = None
            slab_ctx = OTACtx(
                p_weight=w_tx,
                key=packed_omega_key(base_key),
                # FULL (C,) σ² vector: the backward narrows to its own
                # cluster (ctx.sigma2[cidx]) in the default psum count
                # mode, and needs every cluster's σ² under
                # count_mode="local" (collective-free |M|)
                sigma2=jnp.asarray(chan.sigma2, jnp.float32),
                h_th=jnp.asarray(chan_c.h_threshold, jnp.float32),
                noise_std=jnp.asarray(chan_c.noise_std, jnp.float32),
                ota_on=jnp.asarray(chan_c.ota_on, jnp.float32),
                live=ctx_live, n_eff=ctx_n_eff)

            def mb_loss(omega, hd, tok_mb, lab_mb):
                full = omega_gather(omega, slab_ctx)
                if stale_full is not None:
                    # straight-through stale select (§3.15): a straggler
                    # evaluates the loss at the DELAYED params while the
                    # gradient still flows through the custom-vjp OTA
                    # gather. stop(sel) + fr - stop(fr) is exactly sel in
                    # value (fr - fr ≡ 0, no dtype promotion, no
                    # precision loss) and exactly d/dfr = 1 in gradient —
                    # the FedBuff delayed gradient, masked / weighted /
                    # discounted by the same kernel path as a fresh one.
                    def st_sel(fr, st):
                        sel = jnp.where(stale_me > 0.5, st, fr)
                        return (jax.lax.stop_gradient(sel) + fr
                                - jax.lax.stop_gradient(fr))
                    full = jax.tree.map(st_sel, full, stale_full)
                h, aux, _ = model.trunk_apply(full["trunk"], tok_mb,
                                              mode="train")
                feats = model.final_apply(full["final"], h)
                return loss_fn(hd, feats, lab_mb) + aux
        else:
            hook = make_param_hook(gather, registry, base_key, p_new,
                                   chan_c)

            def mb_loss(omega, hd, tok_mb, lab_mb):
                h, aux, _ = model.trunk_apply(omega["trunk"], tok_mb,
                                              mode="train", param_hook=hook)
                ff = hook(omega["final"], "final")
                feats = model.final_apply(ff, h)
                return loss_fn(hd, feats, lab_mb) + aux

        n_mb = max(fl.microbatches, 1)
        b_loc = tokens.shape[0]
        assert b_loc % n_mb == 0, (b_loc, n_mb)
        if n_mb == 1:
            loss_val, (g_omega, g_head) = jax.value_and_grad(
                mb_loss, argnums=(0, 1))(state.omega, head, tokens, labels)
        else:
            tok_mb = tokens.reshape((n_mb, b_loc // n_mb) + tokens.shape[1:])
            lab_mb = labels.reshape((n_mb, b_loc // n_mb) + labels.shape[1:])

            def mb_body(carry, xs):
                g_acc, h_acc, l_acc = carry
                t_i, l_i = xs
                l_val, (g_om, g_hd) = jax.value_and_grad(
                    mb_loss, argnums=(0, 1))(state.omega, head, t_i, l_i)
                g_acc = jax.tree.map(jnp.add, g_acc, g_om)
                h_acc = jax.tree.map(jnp.add, h_acc, g_hd)
                return (g_acc, h_acc, l_acc + l_val), None

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              state.omega)
            h0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), head)
            (g_omega, g_head, l_sum), _ = jax.lax.scan(
                mb_body, (g0, h0, jnp.zeros((), jnp.float32)),
                (tok_mb, lab_mb))
            g_omega = jax.tree.map(lambda x: x / n_mb, g_omega)
            g_head = jax.tree.map(lambda x: x / n_mb, g_head)
            loss_val = l_sum / n_mb

        if use_slab:
            # slab-view PS update: moments stay one flat slab, params
            # unpack exactly once (the model-apply boundary)
            omega, opt = slab_adam_update(
                g_omega, state.opt, state.omega, tcfg.lr, tcfg.betas[0],
                tcfg.betas[1], tcfg.eps, tcfg.weight_decay)
        else:
            omega, opt = adam_update(g_omega, state.opt, state.omega,
                                     tcfg.lr, tcfg.betas[0], tcfg.betas[1],
                                     tcfg.eps, tcfg.weight_decay)
        # Alg. 1 trains heads only in the τ_h phase (lines 10-11); with
        # τ_h = 0 there is no phase A, so heads train on the phase-C
        # gradient instead — statically, for EVERY scenario, so the trace
        # stays weighting-polymorphic.
        if fl.tau_h == 0:
            head, head_opt = adam_update(g_head, head_opt, head, tcfg.lr)

        if partc is not None:
            # non-participant clients keep last round's head + moments
            # (the shared head-Adam step counter stays device-uniform —
            # unlike the sim's per-slot counters; DESIGN.md §3.14)
            keep = part_me > 0.5
            head = jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                                head, head0)
            head_opt = AdamState(
                step=head_opt.step,
                mu=jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                                head_opt.mu, head_opt0.mu),
                nu=jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                                head_opt.nu, head_opt0.nu))

        new_state = HotaState(
            omega=omega, opt=opt,
            heads=jax.tree.map(lambda a: a[None], head),
            head_opt=AdamState(step=head_opt.step,
                               mu=jax.tree.map(lambda a: a[None], head_opt.mu),
                               nu=jax.tree.map(lambda a: a[None], head_opt.nu)),
            p=p_new[None], fgn_mu=mu[None], fgn_nu=nu[None],
            fgn_t=fgn_t_new, f0=f0[None], step=state.step + 1)

        metrics = {
            "loss": jax.lax.pmean(loss_val, client_axes),
            "p_mean": jax.lax.pmean(p_new, client_axes),
            "p_min": -jax.lax.pmax(-p_new, client_axes),
            "p_max": jax.lax.pmax(p_new, client_axes),
            "fgrad": jax.lax.pmean(fgrad_val, client_axes),
            "gnorm_mean": jax.lax.pmean(n_i, client_axes),
        }

        if partc is not None:
            # round guard (DESIGN.md §3.14): gn2 is the EXACT squared
            # estimate norm, device-uniform by construction — FSDP leaves
            # psum their shard sums over the data axes, replicated leaves
            # are already identical everywhere. spike_norm=inf leaves only
            # the non-finite check; a tripped guard (or a zero-participant
            # round) freezes the whole state — bit-exact identity, step
            # counter aside — via the fgn_on-style jnp.where passthrough.
            leaves_g = jax.tree.leaves(g_omega)
            gn2_loc = sum((jnp.sum(l.astype(jnp.float32) ** 2)
                           for l, ax in zip(leaves_g, omega_fsdp)
                           if ax >= 0), jnp.zeros((), jnp.float32))
            gn2_rep = sum((jnp.sum(l.astype(jnp.float32) ** 2)
                           for l, ax in zip(leaves_g, omega_fsdp)
                           if ax < 0), jnp.zeros((), jnp.float32))
            gn2 = jax.lax.psum(gn2_loc, data_axes) + gn2_rep
            ok = jnp.logical_and(jnp.isfinite(gn2),
                                 gn2 <= fp.spike_norm * fp.spike_norm)
            skip = jnp.logical_or(partc.total < 0.5, ~ok)
            # stale-model bookkeeping (mirrors the sim): refresh the
            # delayed FSDP-sharded copy every fp.staleness rounds (age in
            # [0, τ)); the skip freeze below covers these fields too, so
            # a skipped round leaves copy + age untouched
            refresh = (state.stale_age + 1.0) >= fp.staleness
            new_state = new_state._replace(
                omega_stale=jax.tree.map(
                    lambda new, old: jnp.where(refresh, new, old),
                    omega, state.omega_stale),
                stale_age=jnp.where(refresh, 0.0, state.stale_age + 1.0))
            new_state = jax.tree.map(
                lambda new, old: jnp.where(skip, old, new),
                new_state, state)
            new_state = new_state._replace(step=state.step + 1)
            metrics = dict(metrics, skipped=skip.astype(jnp.float32),
                           n_participants=partc.total)
        return new_state, metrics

    chan_spec = ChannelParams(*([P()] * len(ChannelParams._fields)))
    faults_spec = FaultParams(*([P()] * len(FaultParams._fields)))
    return StepParts(
        init_fn=init_fn, step=_step, state_specs=state_specs,
        batch_spec=batch_spec, metric_spec=metric_spec, chan_spec=chan_spec,
        chan_all=chan_all, n_total_clusters=n_total_clusters,
        has_fast=(fl.weighting == "equal" and fl.tau_h == 0
                  and not fl.faults),
        faults_spec=faults_spec, faults_all=faults_all)


def make_hota_train_step(
    model: Model,
    mesh,
    fl: FLConfig,
    tcfg: TrainConfig,
    *,
    loss_kind: str = "lm",
    n_out: Optional[int] = None,
):
    """Returns (init_fn, sharded_step_fn, state_sharding, batch_sharding).

    ``sharded_step_fn(state, tokens, labels, key, chan=None, faults=None)``:
    ``chan`` is an optional traced ``ChannelParams`` (σ² of shape
    (n_total_clusters,)) overriding the factory config's knobs for this
    call — scenario sweeps pass a different ``chan`` per call into ONE
    compiled step. ``faults`` likewise overrides the traced fault knobs
    (consumed only when the static ``fl.faults`` gate is on)."""
    parts = make_hota_step_parts(model, mesh, fl, tcfg, loss_kind=loss_kind,
                                 n_out=n_out)
    # manual over EVERY mesh axis: a Mosaic kernel cannot be partitioned
    # automatically, so no axis may be left to the SPMD partitioner. No
    # spec names a non-FL axis ("model"), so devices along it hold and
    # compute replicas.
    manual_axes = set(mesh.axis_names)
    state_specs, metric_spec = parts.state_specs, parts.metric_spec
    in_specs = (state_specs, parts.batch_spec[0], parts.batch_spec[1], P(),
                parts.chan_spec, parts.faults_spec)
    sharded_inner = jax.shard_map(
        parts.step, mesh=mesh, in_specs=in_specs,
        out_specs=(state_specs, metric_spec), axis_names=manual_axes,
        check_vma=False)
    # statically-specialized naive baseline: with equal weighting and no
    # head phase baked into the config, the FGN inputs can never be
    # consumed, so default-chan calls dispatch to a trace with phases
    # 0/A/B removed (the pre-traced-knobs fast path). A supplied chan
    # always takes the scenario-polymorphic trace.
    fast_inner = (jax.shard_map(
        partial(parts.step, fast=True), mesh=mesh, in_specs=in_specs,
        out_specs=(state_specs, metric_spec), axis_names=manual_axes,
        check_vma=False)
        if parts.has_fast else None)
    n_total_clusters = parts.n_total_clusters
    chan_all = parts.chan_all
    faults_all = parts.faults_all

    def sharded_step(state: HotaState, tokens, labels, key,
                     chan: Optional[ChannelParams] = None,
                     faults: Optional[FaultParams] = None):
        fp = faults_all if faults is None else faults
        if chan is None:
            inner = fast_inner if fast_inner is not None else sharded_inner
            return inner(state, tokens, labels, key, chan_all, fp)
        if chan.sigma2.shape != (n_total_clusters,):
            raise ValueError(
                f"chan.sigma2 shape {chan.sigma2.shape} != "
                f"(n_total_clusters,) = ({n_total_clusters},)")
        return sharded_inner(state, tokens, labels, key, chan, fp)

    return parts.init_fn, sharded_step, state_specs, parts.batch_spec


CLIENT_AXIS_NAME = "client"


def _plain_gather_tree(shards, axes_list, data_axes, compute_dtype):
    return plain_gather_full(shards, [_fsdp_axis(a) for a in axes_list],
                             data_axes, compute_dtype)


def _masked_final_norm(g_final, axes_list, base_key, chan_c: ChannelParams,
                       fl, cluster_axes, n_clients):
    """n_i = ‖M ∘ ∇_{ω̃}F_i‖ with the same masks the transmission uses
    (per-region draws in scatter mode — full_transmission_mask mirrors the
    gather backward's key scheme exactly)."""
    leaves = jax.tree.leaves(g_final)
    total = jnp.zeros((), jnp.float32)
    for i, (g, axes) in enumerate(zip(leaves, axes_list)):
        key = fold_tags(base_key, "final", (), i)
        mask = full_transmission_mask(
            key, g.shape, _fsdp_axis(axes), n_clients, chan_c.sigma2,
            chan_c.h_threshold, chan_c.ota_on, cluster_axes,
            scatter_mode=(fl.ota_mode == "scatter"))
        total = total + jnp.sum(
            jnp.where(mask, g.astype(jnp.float32), 0.0) ** 2)
    return jnp.sqrt(total)
