"""Paper-scale simulator of HOTA-FedGradNorm (Algorithm 1 + Algorithm 2).

Faithful execution of the paper's loop at its native scale (C=10 clusters,
N=3 clients, MLP) via ``vmap`` over (cluster, client) — no mesh required,
runs on one CPU device. This is the engine behind the reproduction
experiments (Figs. 2-4) and the oracle the distributed path is tested
against.

Per global iteration k (Alg. 1):
 1. PS broadcasts ω_k (implicit: clients read the shared tree).
 2. Each client: τ_h personalized-head steps (Adam), then τ_ω local shared
    steps (SGD, line 13), accumulating ḡ_k^(l,i) and F̄_k^(l,i).
 3. IS l runs FGN_Server (Alg. 2) on masked last-layer grad norms → p_k.
 4. IS l transmits x^(l) = Σ_i β∘g (channel-inverted, thresholded); the MAC
    superimposes clusters; PS estimates ĝ (eqs. 3, 8-10).
 5. PS updates ω (Adam by default, matching Sec. IV-B; SGD available).

With ``use_pallas_ota=True`` (the default) the channel is **slab-native**
(DESIGN.md §3.12): step 4 runs client-folded — Σ_l M_l ∘ (Σ_n p·g) is
computed leaf by leaf from the raw (C, N, ·) gradients against the
multi-section zero-copy stream layout, so neither the client-weighted
tree nor a (C, P) packed slab is ever materialized (HLO-pinned), and
step 5 is the slab-view Adam (moments as one flat slab). The per-leaf
jnp path (``use_pallas_ota=False``) stays the bit-exact oracle.

Heads are padded to the max class count across tasks so clients vmap
homogeneously; logits above a client's class count are masked to -inf.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import FLConfig, TrainConfig
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.channel import (
    ChannelParams, FaultParams, channel_params, fault_params,
)
from repro.core.fedgradnorm import FGNState, fgn_init, fgn_update_gated
from repro.kernels.masked_gradnorm.ops import masked_gradnorm
from repro.models.model import Model
from repro.models.params import init_params
from repro.optim.adam import (
    AdamState, adam_init, adam_update, slab_adam_init, slab_adam_update,
)


class SimState(NamedTuple):
    omega: Any                  # {"final": ..., "trunk": ...} shared net
    heads: Any                  # stacked (C, N, ...)
    p: jax.Array                # (C, N) loss weights
    ps_opt: Any                 # PS optimizer state for ω
    head_opt: Any               # stacked (C, N, ...) Adam states
    fgn: FGNState               # stacked per cluster: leaves (C, N)
    f0: jax.Array               # (C, N) initial losses (for F̃)
    step: jax.Array
    # Fault-injection state (DESIGN.md §3.14) — present only when
    # fl.faults (None = empty pytree node, legacy states unchanged):
    omega_stale: Any = None     # delayed shared-model copy stragglers use
    stale_age: Any = None       # () rounds since omega_stale was refreshed


def masked_cls_loss(logits: jax.Array, labels: jax.Array,
                    n_valid: jax.Array) -> jax.Array:
    """CE with classes ≥ n_valid masked out (heads padded to max classes)."""
    c = logits.shape[-1]
    valid = jnp.arange(c) < n_valid
    logits = jnp.where(valid, logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])


class HotaSim:
    def __init__(self, model: Model, fl: FLConfig, tcfg: TrainConfig,
                 n_classes_per_client, max_classes: int = None):
        self.model = model
        self.fl = fl
        self.tcfg = tcfg
        self.n_classes = jnp.asarray(n_classes_per_client, jnp.int32)  # (N,)
        self.max_classes = int(max_classes or int(max(n_classes_per_client)))
        # runtime channel/weighting knobs live in a traced pytree so scenario
        # sweeps (repro.core.sweep) can batch them; this is the default row.
        self.chan = channel_params(fl)
        # fault knobs are the same pattern (traced, bankable); fl.faults is
        # the one static gate that decides whether they are consumed at all
        self.faults = fault_params(fl)
        # no-silent-inertness (the PR-7 pattern): a static gate that the
        # chosen engine cannot honor must refuse loudly at build time,
        # not silently run the un-gated path
        if fl.ota_sectioned and not fl.use_pallas_ota:
            raise ValueError(
                "fl.ota_sectioned requires the slab engine "
                "(use_pallas_ota=True): the per-leaf oracle has no "
                "Section partition to stream — the gate would be "
                "silently inert (DESIGN.md §3.16)")
        if fl.ota_sectioned and fl.ota_sections != "toplevel":
            raise ValueError(
                "fl.ota_sectioned requires a multi-section layout "
                f"(ota_sections='toplevel', got {fl.ota_sections!r}): "
                "section streaming over the legacy two-section layout "
                "holds most of the model in its head section — the "
                "memory bound would be silently vacuous (DESIGN.md §3.16)")
        if fl.max_section_rows and not fl.use_pallas_ota:
            raise ValueError(
                "fl.max_section_rows requires the slab engine "
                "(use_pallas_ota=True): the per-leaf oracle has no "
                "section layout to split — the cap would be silently "
                "inert (DESIGN.md §3.16)")

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> SimState:
        fl = self.fl
        k1, k2 = jax.random.split(key)
        omega = {"trunk": init_params(self.model.trunk_specs(), k1),
                 "final": init_params(self.model.final_specs(),
                                      jax.random.fold_in(
                                          k1, ota.FINAL_INIT_FOLD))}
        # reorder so "final" flattens first (leaf offset 0 for channel keys)
        omega = {"final": omega["final"], "trunk": omega["trunk"]}
        head_specs = self.model.head_specs(self.max_classes)

        def one_head(kc):
            return init_params(head_specs, kc)
        keys = jax.random.split(k2, fl.n_clusters * fl.n_clients).reshape(
            fl.n_clusters, fl.n_clients, -1)
        heads = jax.vmap(jax.vmap(one_head))(keys)
        head_opt = jax.vmap(jax.vmap(adam_init))(heads)
        p = jnp.ones((fl.n_clusters, fl.n_clients), jnp.float32)
        fgn = jax.vmap(lambda _: fgn_init(fl.n_clients))(
            jnp.arange(fl.n_clusters))
        # slab-native path (DESIGN.md §3.12): PS Adam moments live as one
        # flat slab — n_leaves-independent update, params unpacked once
        ps_opt = (slab_adam_init(omega) if fl.use_pallas_ota
                  else adam_init(omega))
        return SimState(
            omega=omega, heads=heads, p=p, ps_opt=ps_opt,
            head_opt=head_opt, fgn=fgn,
            f0=jnp.ones((fl.n_clusters, fl.n_clients), jnp.float32),
            step=jnp.zeros((), jnp.int32),
            omega_stale=(jax.tree.map(jnp.array, omega) if fl.faults
                         else None),
            stale_age=(jnp.zeros((), jnp.float32) if fl.faults else None))

    # ------------------------------------------------------------------
    def _client_update(self, omega, head, head_opt, x, y, n_valid):
        """τ_h head steps then τ_ω local shared steps (Alg. 1 lines 10-15)."""
        model, tcfg, fl = self.model, self.tcfg, self.fl

        def features(om, xx):
            h, _, _ = model.trunk_apply(om["trunk"], xx, mode="train")
            return model.final_apply(om["final"], h)

        def head_loss(hd, om):
            return masked_cls_loss(model.head_apply(hd, features(om, x)),
                                   y, n_valid)

        def head_step(carry, _):
            hd, hopt = carry
            g = jax.grad(head_loss)(hd, omega)
            hd, hopt = adam_update(g, hopt, hd, tcfg.lr)
            return (hd, hopt), None

        (head, head_opt), _ = jax.lax.scan(
            head_step, (head, head_opt), None, length=fl.tau_h)

        def omega_step(carry, _):
            om, gacc, lacc = carry
            l, g = jax.value_and_grad(
                lambda om_: head_loss(head, om_))(om)
            om = jax.tree.map(lambda w, gg: w - tcfg.lr * gg, om, g)
            gacc = jax.tree.map(jnp.add, gacc, g)
            return (om, gacc, lacc + l), None

        gacc0 = jax.tree.map(jnp.zeros_like, omega)
        (_, gacc, lsum), _ = jax.lax.scan(
            omega_step, (omega, gacc0, jnp.zeros(())), None, length=fl.tau_w)
        g_avg = jax.tree.map(lambda a: a / fl.tau_w, gacc)
        f_avg = lsum / fl.tau_w
        return head, head_opt, g_avg, f_avg

    # ------------------------------------------------------------------
    def _masked_final_norms(self, g_final, final_masks) -> jax.Array:
        """(C, N) masked last-shared-layer grad norms n_i (eq. 6), routed
        through the ``masked_gradnorm`` kernel per cluster: clients are
        the task rows, the cluster's eq.-7 mask is the shared column
        mask. Off-TPU the kernel wrapper dispatches to its jnp reference
        (same values — see repro.kernels.masked_gradnorm.ops), replacing
        the old per-(cluster, client) double-vmap tree walk."""
        c, n = self.fl.n_clusters, self.fl.n_clients
        gm = jnp.concatenate(
            [l.reshape(c, n, -1).astype(jnp.float32)
             for l in jax.tree.leaves(g_final)], axis=-1)        # (C, N, P̃)
        mm = jnp.concatenate(
            [m.reshape(c, -1).astype(jnp.float32)
             for m in jax.tree.leaves(final_masks)], axis=-1)    # (C, P̃)
        return jax.vmap(masked_gradnorm)(gm, mm)

    # ------------------------------------------------------------------
    def step(self, state: SimState, xb, yb, key,
             chan: ChannelParams = None, faults: FaultParams = None):
        """One Alg.-1 round. xb: (C,N,B,d) float32; yb: (C,N,B) int32.

        ``chan`` overrides the channel/weighting knobs at trace time
        (defaults to this sim's ``FLConfig``); the sweep engine vmaps
        ``step_with_channel`` over a bank of them. ``faults`` likewise
        overrides the traced fault knobs (consumed only when the static
        ``fl.faults`` gate is on)."""
        return self._step(state, xb, yb, key,
                          self.chan if chan is None else chan,
                          self.faults if faults is None else faults)

    @partial(jax.jit, static_argnums=0)
    def _step(self, state, xb, yb, key, chan, faults):
        return self.step_with_channel(state, xb, yb, key, chan,
                                      faults=faults)

    def step_with_channel(self, state: SimState, xb, yb, key,
                          chan: ChannelParams, ota_bits_mode: str = "fused",
                          faults: FaultParams = None):
        """Un-jitted step body with explicit traced ChannelParams — the
        vmap target of ``repro.core.sweep.ScenarioBank`` and, per device,
        of ``ShardedScenarioBank``'s scenario-sharded shard_map (DESIGN.md
        §3.8). Both pass ``ota_bits_mode="supplied"`` so the packed
        channel draw — a function of the shared key only — hoists out of
        the scenario vmap and is never re-drawn per scenario or per
        shard; same stream, same results as the fused default.

        Fault injection (DESIGN.md §3.14, static ``fl.faults`` gate):
        participation is drawn from the round key's reserved PART_FOLD
        domain — disjoint from every channel stream, so resampling fault
        rates is CRN-safe. Stragglers compute against the delayed
        ``omega_stale`` copy and transmit with the FedBuff-style
        1/√(1+age) discount; non-participant head slots and dead-cluster
        FGN state freeze; blackouts mask the MAC and the traced N_eff
        replaces N in eq. 10; a zero-participant or guard-tripped round
        degrades to a bit-exact identity step (step counter aside)."""
        fl, tcfg = self.fl, self.tcfg
        partc = None
        if fl.faults:
            fp = self.faults if faults is None else faults
            partc = ota.draw_participation(key, fp, fl.n_clusters,
                                           fl.n_clients)

            def client_upd(om, om_stale, stale_flag, head, hopt, x, y, nv):
                om_eff = jax.tree.map(
                    lambda f, s: jnp.where(stale_flag > 0.5, s, f),
                    om, om_stale)
                return self._client_update(om_eff, head, hopt, x, y, nv)

            upd = jax.vmap(jax.vmap(client_upd,
                                    in_axes=(None, None, 0, 0, 0, 0, 0, 0)),
                           in_axes=(None, None, 0, 0, 0, 0, 0, None))
            with jax.named_scope("hota.client_update"):
                heads, head_opt, g, F = upd(
                    state.omega, state.omega_stale, partc.stale, state.heads,
                    state.head_opt, xb, yb, self.n_classes)
                # non-participant slots keep last round's head + optimizer
                pm = partc.part

                def sel_slot(new, old):
                    m = pm.reshape(pm.shape + (1,) * (new.ndim - 2))
                    return jnp.where(m > 0.5, new, old)

                heads = jax.tree.map(sel_slot, heads, state.heads)
                head_opt = jax.tree.map(sel_slot, head_opt, state.head_opt)
        else:
            upd = jax.vmap(jax.vmap(self._client_update,
                                    in_axes=(None, 0, 0, 0, 0, 0)),
                           in_axes=(None, 0, 0, 0, 0, None))
            with jax.named_scope("hota.client_update"):
                heads, head_opt, g, F = upd(state.omega, state.heads,
                                            state.head_opt, xb, yb,
                                            self.n_classes)
        # g leaves: (C, N, ...); F: (C, N)

        chan_key = ota.sim_channel_key(key)   # reserved fold (DESIGN.md §4)
        # slab-native OTA (DESIGN.md §3.12): the shared tree is laid out by
        # a multi-section zero-copy packer (per-layer-stack trunk sections,
        # ω̃ tail) and the channel consumes every RAW (C, N, ·) gradient
        # leaf in place — no client-weighted tree, no (C, P) pack copy.
        # fl.use_pallas_ota is static config — the per-leaf jnp path stays
        # available as the property-test oracle. The section layout
        # (fl.ota_sections / fl.min_section_rows — normally written by
        # repro.common.layout_tune.apply_layout) decides the stream
        # folds, so it is static and checkpoint-pinned (DESIGN.md §3.13).
        packer = (packer_for(state.omega, tail="final",
                             sections=fl.ota_sections,
                             min_section_rows=fl.min_section_rows,
                             max_section_rows=fl.max_section_rows)
                  if fl.use_pallas_ota else None)

        # --- Alg. 2: FGN_Server per cluster -------------------------------
        # f0 latches each slot's FIRST observed loss (the F̃ baseline).
        # Besides step 0, a NEGATIVE f0 marks a never-seen slot — the
        # sampling layer (DESIGN.md §3.15) initializes its population
        # bank to -1 so a client first drawn at round k latches F at k.
        # Legacy states never hold a negative f0 (CE losses are ≥ 0 and
        # init is ones), so the extra clause is trace-only for them.
        with jax.named_scope("hota.fgn"):
            f0 = jnp.where(jnp.logical_or(state.step == 0, state.f0 < 0.0),
                           F, state.f0)
            ratios = F / jnp.maximum(f0, 1e-12)

            if packer is not None:   # tail section of the round's stream draw
                final_masks = ota.final_layer_masks_packed(chan_key, chan,
                                                           packer)
            else:
                final_masks = ota.final_layer_masks(
                    chan_key, state.omega["final"], chan)   # leaves (C, ...)

            norms = self._masked_final_norms(g["final"], final_masks)  # (C, N)

            # weighting gate is traced (chan.fgn_on): "equal" scenarios take
            # the same trace and just select the passthrough; under faults a
            # dead cluster's gate also drops, freezing its (p, FGN) state
            if partc is not None:
                p_new, fgn_state, fval = jax.vmap(
                    lambda pc, nc, rc, st, on: fgn_update_gated(
                        pc, nc, rc, st, fl, on)
                )(state.p, norms, ratios, state.fgn, chan.fgn_on * partc.live)
            else:
                p_new, fgn_state, fval = jax.vmap(
                    lambda pc, nc, rc, st: fgn_update_gated(
                        pc, nc, rc, st, fl, chan.fgn_on)
                )(state.p, norms, ratios, state.fgn)

        # --- eqs. (3), (8)-(10): weighted transmission + OTA --------------
        # under faults the transmit weights fold participation and the
        # FedBuff staleness discount into the (C, N) matrix the channel
        # already carries; live/n_eff generalize the eq.-10 guard
        with jax.named_scope("hota.ota_fold"):
            if partc is not None:
                disc = jnp.where(partc.stale > 0.5,
                                 jax.lax.rsqrt(1.0 + state.stale_age), 1.0)
                w_tx = p_new * partc.part * disc
                live, n_eff = partc.live, partc.n_eff
            else:
                w_tx, live, n_eff = p_new, None, None
            if packer is not None:
                # client-folded: Σ_n p[l,n]·g[l,n] folds into the masked MAC
                # sum leaf by leaf — the einsum'd weighted tree never exists.
                # fl.ota_streaming (static, DESIGN.md §3.15) swaps in the
                # scan-over-clusters fold: identical streams, one cluster's
                # contribution resident at a time instead of all C.
                # fl.ota_sectioned (static, DESIGN.md §3.16) walks the
                # Section partition one section at a time — bit-identical
                # per leaf, peak live streams one section — and composes
                # with the cluster scan (the scan runs inside each section).
                if fl.ota_sectioned:
                    ghat = ota.ota_aggregate_sectioned(
                        chan_key, g, w_tx, chan, fl.n_clients, packer,
                        bits_mode=ota_bits_mode, live=live, n_eff=n_eff,
                        streaming=fl.ota_streaming)
                else:
                    agg = (ota.ota_aggregate_streaming if fl.ota_streaming
                           else ota.ota_aggregate_client_folded)
                    ghat = agg(
                        chan_key, g, w_tx, chan, fl.n_clients, packer,
                        bits_mode=ota_bits_mode, live=live, n_eff=n_eff)
            else:
                weighted = jax.tree.map(
                    lambda gl: jnp.einsum("cn,cn...->c...", w_tx, gl), g)
                ghat = ota.ota_aggregate_tree(chan_key, weighted, chan,
                                              fl.n_clients, live=live,
                                              n_eff=n_eff)

        # --- PS update (line 20) -------------------------------------------
        with jax.named_scope("hota.ps_update"):
            if packer is not None:
                # slab-view PS update: moments stay one flat slab, params
                # unpack exactly once (the model-apply boundary)
                omega, ps_opt = slab_adam_update(ghat, state.ps_opt,
                                                 state.omega, tcfg.lr)
            else:
                omega, ps_opt = adam_update(ghat, state.ps_opt, state.omega,
                                            tcfg.lr)

        metrics = {"loss": F, "p": p_new, "fgrad": fval,
                   "grad_norms": norms}
        if partc is None:
            return SimState(omega=omega, heads=heads, p=p_new,
                            ps_opt=ps_opt, head_opt=head_opt, fgn=fgn_state,
                            f0=f0, step=state.step + 1), metrics

        # --- round guard + degradation (DESIGN.md §3.14) ------------------
        # gn2 is the exact squared estimate norm; spike_norm=inf leaves
        # only the non-finite check (inf² = inf makes the ≤ vacuous)
        gn2 = sum(jnp.sum(l.astype(jnp.float32) ** 2)
                  for l in jax.tree.leaves(ghat))
        ok = jnp.logical_and(jnp.isfinite(gn2),
                             gn2 <= fp.spike_norm * fp.spike_norm)
        skip = jnp.logical_or(partc.total < 0.5, ~ok)
        # stale-model bookkeeping: refresh the delayed copy every
        # fp.staleness rounds (age in [0, τ))
        refresh = (state.stale_age + 1.0) >= fp.staleness
        omega_stale = jax.tree.map(
            lambda new, old: jnp.where(refresh, new, old),
            omega, state.omega_stale)
        stale_age = jnp.where(refresh, 0.0, state.stale_age + 1.0)
        new_state = SimState(omega=omega, heads=heads, p=p_new,
                             ps_opt=ps_opt, head_opt=head_opt,
                             fgn=fgn_state, f0=f0, step=state.step,
                             omega_stale=omega_stale, stale_age=stale_age)
        # skipped round = bit-exact identity (params, Adam moments, FGN
        # state, stale copy all frozen — like the fgn_on passthrough);
        # only the step counter advances
        new_state = jax.tree.map(
            lambda new, old: jnp.where(skip, old, new), new_state, state)
        new_state = new_state._replace(step=state.step + 1)
        metrics = dict(metrics, skipped=skip.astype(jnp.float32),
                       n_participants=partc.total)
        return new_state, metrics
