"""Slab-native distributed HOTA aggregation (DESIGN.md §3.10).

PR 2 packed the *simulator's* whole-model channel into one fused kernel,
but the distributed step still aggregated the trunk per leaf: every leaf
paid its own gain draw and its own set of psums in the custom-vjp
backward, with only the ω̃ tail riding the packed path. This module makes
the WHOLE shared model slab-native:

* the parameter template is laid out by a multi-section ``TreePacker``
  (``sections="toplevel"``: one ROW_QUANTUM-aligned section per top-level
  layer stack, ω̃ last — ``repro.common.flatpack``);
* the (P,) slab is NEVER materialized — ``TreePacker.leaf_runs()`` maps
  each leaf's storage to a static slice of its section's chunk-quantized
  bit stream (DESIGN.md §4), and the fused mask+weighted-apply kernel
  (``ota_mask_weight_apply``) consumes each leaf in place. This is the
  zero-copy layout: the dynamic-update-slice pack chain that lost to
  XLA's per-leaf path at 16M params simply does not exist here;
* the FedGradNorm weight folds INTO the kernel (w·g·M in one pass), so
  the backward needs exactly ONE psum set for the whole model: a single
  pytree psum of the masked weighted gradients over (client ∪ cluster)
  axes — eqs. 3 and 8 combined, since M_l ∘ Σ_i p_i g_i = Σ_i M_l ∘
  (p_i·g_i) with M constant across a cluster — plus one mask-count psum
  over the cluster axes for the |M|·N estimate (eq. 10).

``sectioned_final_norm`` re-draws ONLY the ω̃ section's stream (the tail
keeps ``PACKED_TAIL_FOLD`` in every layout), so the FGN phase (eq. 5)
sees bit-identical masks to the ones the transmission backward applies.

The per-leaf path (``repro.core.hota.make_ota_gather``) stays as the
numerical oracle behind ``FLConfig.use_pallas_ota=False``; memory trade:
this path materializes the full per-client gradient tree at the pack
point (fine up to ~1B params — the per-leaf path remains the
layer-at-a-time option for the 14B+ configs, DESIGN.md §3.7).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.flatpack import TreePacker, check_tree_matches_packer, \
    packer_for
from repro.core.channel import ChannelParams
from repro.core.hota import OTACtx, _zero_cot, cluster_index
from repro.core.ota import (
    _chunked_stream, packed_section_folds, section_gain_key,
    section_noise_key,
)
from repro.kernels.ota_channel.ops import ota_mask_count_apply, \
    ota_mask_weight_apply
from repro.kernels.ota_channel.ref import bits_to_gaussian, bits_to_mask
from repro.kernels.slab import on_tpu

CLIENT_AXIS = "client"


def _fsdp_axis_full(axes: tuple) -> int:
    """FSDP dim index in the FULL logical-axes tuple (scan-stacked leaves
    keep their leading 'layer' dim here, unlike the per-layer hook view
    that ``hota._fsdp_axis`` serves)."""
    return axes.index("embed") if "embed" in axes else -1


def plain_gather_full(shard_tree, fsdp_axes: List[int],
                      data_axes: Tuple[str, ...], compute_dtype):
    """Per-leaf all-gather of a whole shard tree (no custom vjp) —
    phases 0/B of the slab-native step, which never backprop through the
    channel. ``fsdp_axes`` are full-tuple dim indices (-1 = replicated)."""
    leaves, treedef = jax.tree.flatten(shard_tree)
    out = []
    for leaf, ax in zip(leaves, fsdp_axes):
        if ax >= 0:
            leaf = jax.lax.all_gather(leaf, data_axes, axis=ax, tiled=True)
        out.append(leaf.astype(compute_dtype))
    return jax.tree.unflatten(treedef, out)

# the whole-model slab's channel key domain — reserved fold near 2³¹,
# disjoint from PACKED_FINAL_FOLD (the PR-2 packed-ω̃ gather) and every
# cluster/leaf index (DESIGN.md §4)
PACKED_OMEGA_FOLD = 0x7FFF00F2


def packed_omega_key(base_key: jax.Array) -> jax.Array:
    """The single channel key of the slab-native whole-model round."""
    return jax.random.fold_in(base_key, PACKED_OMEGA_FOLD)


def omega_packer(template, sections: str = "toplevel",
                 min_section_rows: int = 0,
                 max_section_rows: int = 0) -> TreePacker:
    """The slab-native layout of one omega template, all-f32. Defaults
    to multi-section (per layer-stack trunk sections, ω̃ tail last);
    ``sections``/``min_section_rows``/``max_section_rows`` come from the
    tuned LayoutChoice (repro.common.layout_tune) so the engine, the
    simulator and the checkpoint manifest agree on one stream layout."""
    f32 = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(tuple(l.shape), jnp.float32), template)
    return packer_for(f32, tail="final", sections=sections,
                      min_section_rows=min_section_rows,
                      max_section_rows=max_section_rows)


# ---------------------------------------------------------------------------
# the whole-model custom-vjp gather
# ---------------------------------------------------------------------------

def make_packed_omega_gather(data_axes: Tuple[str, ...],
                             cluster_axes: Tuple[str, ...],
                             n_clients: int, n_shards: int, compute_dtype,
                             template, axes_list: List[tuple],
                             n_clusters: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             count_mode: Optional[str] = None,
                             sections: str = "toplevel",
                             min_section_rows: int = 0,
                             max_section_rows: int = 0,
                             sectioned: bool = False):
    """Custom-vjp FSDP gather for the ENTIRE shared model {trunk, final}.

    forward : per-leaf all-gather of the FSDP shards -> full tree
    backward: per leaf (IN PLACE — no slab pack): fused mask+weighted
              apply on the leaf's static slice of its section's
              chunk-quantized streams (``ota_mask_count_apply``: the
              FedGradNorm weight folds into the kernel, and because
              EVERY cluster's mask is a pure function of the
              counter-based streams, the |M| count is computed locally —
              zero mask collectives); then one collective pipeline:
              per-FSDP-leaf ``psum_scatter`` over "client" (the LAN sum
              of eq. 3 arrives pre-split into 1/N regions) + ONE pytree
              psum of all regions over the cluster axes (the MAC of
              eq. 8), replicated leaves in one full-size psum; AWGN from
              the per-section noise streams; guarded |M|·N estimate
              (eq. 10) on local counts; slice each leaf's own shard.

    ``ctx.sigma2`` must be the FULL (n_clusters,) per-cluster vector.
    Masks are whole-tensor draws positioned by the layout, so a region's
    mask is literally a slice of the same stream — ``ota_mode`` does not
    apply to this engine (DESIGN.md §3.11). Gain/noise bits for each
    section are drawn once per round and sliced per leaf, so two leaves
    never recompute a chunk.

    ``count_mode`` picks how |M| reaches the estimate (identical values
    either way — masks are pure stream functions):

    * ``"psum"``: draw only THIS cluster's stream; the region-sliced
      mask rides the same pytree MAC psum as the data. Minimal PRNG
      volume — right on CPU and small meshes.
    * ``"local"``: draw EVERY cluster's stream and count locally via the
      fused ``ota_mask_count_apply`` kernel — zero mask collectives at
      C× the PRNG. Right where collectives cross pods and PRNG is
      hardware (TPU, DESIGN.md §3.10).
    * ``None`` (default): by platform — "local" on TPU, "psum"
      elsewhere, resolved at gather build time like ``interpret``.

    ``sectioned`` (DESIGN.md §3.16) makes the Section partition the unit
    of scheduling: the backward walks the layout one section at a time
    — draw that section's streams, mask/apply its leaf runs, ISSUE its
    psums — and finalizes (AWGN + guarded estimate + shard slice) each
    section one step LATE, so section s's collectives are in flight
    while section s+1 draws and packs (double-buffered carry). Peak live
    streams are one section's (bounded by ``max_section_rows``), never
    the (P,) or (C,P) slab; per-leaf values are bit-identical to the
    full-slab schedule (same streams, same kernels — only stream
    lifetime and psum grouping change, and psum is per-leaf
    elementwise).
    """
    if count_mode is None:
        # default-by-platform (ROADMAP): zero-mask-collective local
        # counting where the PRNG is hardware; minimal PRNG volume
        # where it is not. Resolved at gather build time (post backend
        # selection), never at module import.
        count_mode = "local" if on_tpu() else "psum"
    assert count_mode in ("psum", "local"), count_mode
    interp = (not on_tpu()) if interpret is None else interpret
    packer = omega_packer(template, sections=sections,
                          min_section_rows=min_section_rows,
                          max_section_rows=max_section_rows)
    folds = packed_section_folds(packer)
    runs = {run.leaf: run for run in packer.leaf_runs()}
    n_leaves = len(packer.slots)
    assert len(axes_list) == n_leaves, (len(axes_list), n_leaves)
    # full-tuple FSDP dims: whole-tree leaves keep their 'layer' dim
    fsdp_axes = [_fsdp_axis_full(ax) for ax in axes_list]
    n_sub = n_shards // n_clients      # cluster sub-shards per region

    @jax.custom_vjp
    def gather_omega(shard_tree, ctx: OTACtx):
        return plain_gather_full(shard_tree, fsdp_axes, data_axes,
                                 compute_dtype)

    def _fwd(shard_tree, ctx):
        return gather_omega(shard_tree, ctx), (ctx,)

    # a region (1/n_clients slice along the FSDP dim) is a CONTIGUOUS
    # range of the leaf's stream slice iff every dim before the FSDP dim
    # is trivial — then region r of leaf i occupies stream positions
    # [offset + r·(size/N), offset + (r+1)·(size/N)) and a device can
    # draw ONLY its region's chunks (lax.switch over the N static
    # offsets — 1/N the PRNG volume, same values as the full draw)
    def _contig(i):
        ax = fsdp_axes[i]
        shape = packer.slots[i].shape
        return ax >= 0 and all(s == 1 for s in shape[:ax])

    def _bwd(res, g_tree):
        (ctx,) = res
        check_tree_matches_packer(packer, g_tree,
                                  "gradient pytree (packed omega gather)")
        leaves = packer.treedef.flatten_up_to(g_tree)
        cidx = cluster_index(cluster_axes)
        n_cl = (int(ctx.sigma2.shape[0]) if n_clusters is None
                else n_clusters)
        sig_me = ctx.sigma2[cidx]
        my_reg = jax.lax.axis_index(CLIENT_AXIS)
        sub_idx = jax.lax.axis_index(data_axes[1])
        for a in data_axes[2:]:
            sub_idx = sub_idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)

        def _region(a, i):
            sz_r = a.shape[fsdp_axes[i]] // n_clients
            return jax.lax.dynamic_slice_in_dim(a, my_reg * sz_r, sz_r,
                                                fsdp_axes[i])

        def _range_draw(key, start, length):
            # my region's slice of a stream: one statically-drawn branch
            # per region offset, selected by the traced region index
            from repro.core.ota import stream_range_bits
            return jax.lax.switch(
                my_reg,
                [(lambda s=s: stream_range_bits(key, s, length))
                 for s in range(start, start + n_clients * length, length)])

        # Partial participation (DESIGN.md §3.14): a dead cluster (ctx.live
        # = 0) contributes neither data nor mask count to the MAC psums —
        # its local y/mask are zeroed pre-collective (psum mode) or masked
        # inside the fused count kernel (local mode) — and the traced
        # N_eff replaces the static N denominator of eq. 10.
        live_me = None if ctx.live is None else ctx.live[cidx]
        denom = (jnp.float32(n_clients) if ctx.n_eff is None
                 else jnp.maximum(ctx.n_eff, 1.0))
        grads = [None] * n_leaves

        def _collect(idxs):
            """Local channel work + the group's collectives for the
            leaves ``idxs``. Returns ({leaf: y}, {leaf: cnt}), post-psum
            for FSDP leaves. A group is the whole model (full-slab
            schedule) or ONE section (sectioned schedule): per-leaf
            values are bit-identical either way — only the stream
            lifetime and the psum grouping differ, and the psums are
            per-leaf elementwise."""
            reg_idx = [i for i in idxs if fsdp_axes[i] >= 0]
            rep_idx = [i for i in idxs if fsdp_axes[i] < 0]
            if count_mode == "local":
                # TPU-oriented variant: draw EVERY cluster's stream and
                # count |M| locally via the fused kernel — zero mask
                # collectives at C× the (hardware-cheap) PRNG; cnt is
                # exact because masks are pure stream functions.
                secs = sorted({runs[i].section for i in idxs})
                gbits_all = {s: jnp.stack([
                    _chunked_stream(
                        section_gain_key(ctx.key, folds[s], c),
                        packer.sections[s].length)
                    for c in range(n_cl)]) for s in secs}
                outs, cnts = {}, {}
                for i in idxs:
                    run = runs[i]
                    b = jax.lax.slice(gbits_all[run.section],
                                      (0, run.offset),
                                      (n_cl, run.offset + run.size))
                    o, c = ota_mask_count_apply(
                        leaves[i].astype(jnp.float32), b, cidx, ctx.sigma2,
                        ctx.h_th, ctx.ota_on, ctx.p_weight,
                        live_all=ctx.live, interpret=interp)
                    outs[i], cnts[i] = o, c
                y_reg = [jax.lax.psum_scatter(outs[i], CLIENT_AXIS,
                                              scatter_dimension=fsdp_axes[i],
                                              tiled=True) for i in reg_idx]
                cnt_reg = [_region(cnts[i], i) for i in reg_idx]
                cnt_rep = [cnts[i] for i in rep_idx]
                if reg_idx:
                    y_reg = jax.lax.psum(y_reg, tuple(cluster_axes))
                y_rep = (jax.lax.psum([outs[i] for i in rep_idx],
                                      (CLIENT_AXIS,) + tuple(cluster_axes))
                         if rep_idx else [])
            else:
                # default pipeline: LAN psum_scatter FIRST (mask commutes
                # with the client sum — it is cluster-constant), then this
                # cluster's REGION mask on a region-sized stream draw; the
                # mask rides the same pytree MAC psum as the data.
                y_reg, mask_reg = [], []
                full_bits = {}          # sections needing a full draw
                for i in rep_idx + [i for i in reg_idx if not _contig(i)]:
                    s = runs[i].section
                    if s not in full_bits:
                        full_bits[s] = _chunked_stream(
                            section_gain_key(ctx.key, folds[s], cidx),
                            packer.sections[s].length)
                for i in reg_idx:
                    run, ax = runs[i], fsdp_axes[i]
                    g32 = leaves[i].astype(jnp.float32)
                    if _contig(i):
                        x_reg = jax.lax.psum_scatter(
                            ctx.p_weight * g32, CLIENT_AXIS,
                            scatter_dimension=ax, tiled=True)
                        lreg = run.size // n_clients
                        b = _range_draw(
                            section_gain_key(ctx.key, folds[run.section],
                                             cidx), run.offset, lreg)
                        o, m = ota_mask_weight_apply(
                            x_reg, b, sig_me, ctx.h_th, ctx.ota_on, 1.0,
                            interpret=interp)
                        if live_me is not None:
                            o, m = o * live_me, m * live_me
                        y_reg.append(o)
                        mask_reg.append(m)
                    else:
                        b = jax.lax.slice(full_bits[run.section],
                                          (run.offset,),
                                          (run.offset + run.size,))
                        o, m = ota_mask_weight_apply(
                            g32, b, sig_me, ctx.h_th, ctx.ota_on,
                            ctx.p_weight, interpret=interp)
                        if live_me is not None:
                            o, m = o * live_me, m * live_me
                        y_reg.append(jax.lax.psum_scatter(
                            o, CLIENT_AXIS, scatter_dimension=ax,
                            tiled=True))
                        mask_reg.append(_region(m, i))
                rep_out, rep_mask = [], []
                for i in rep_idx:
                    run = runs[i]
                    b = jax.lax.slice(full_bits[run.section], (run.offset,),
                                      (run.offset + run.size,))
                    o, m = ota_mask_weight_apply(
                        leaves[i].astype(jnp.float32), b, sig_me, ctx.h_th,
                        ctx.ota_on, ctx.p_weight, interpret=interp)
                    if live_me is not None:
                        o, m = o * live_me, m * live_me
                    rep_out.append(o)
                    rep_mask.append(m)
                if reg_idx:
                    y_reg, cnt_reg = jax.lax.psum((y_reg, mask_reg),
                                                  tuple(cluster_axes))
                else:
                    cnt_reg = []
                if rep_idx:
                    y_rep = jax.lax.psum(rep_out,
                                         (CLIENT_AXIS,) + tuple(cluster_axes))
                    cnt_rep = jax.lax.psum(rep_mask, tuple(cluster_axes))
                else:
                    y_rep, cnt_rep = [], []

            y, cnt = {}, {}
            y.update(zip(reg_idx, y_reg))
            y.update(zip(rep_idx, y_rep))
            cnt.update(zip(reg_idx, cnt_reg))
            cnt.update(zip(rep_idx, cnt_rep))
            return y, cnt

        def _finalize(idxs, y, cnt):
            """AWGN (section noise streams; contiguous-region leaves draw
            only their region's slice — same switch trick), guarded
            estimate, own-shard slice. Consumes the group's psum results
            — the sectioned schedule calls this one section LATE so the
            collectives overlap the next section's local work."""
            full_nbits = {}
            for i in [i for i in idxs if fsdp_axes[i] < 0 or not _contig(i)]:
                s = runs[i].section
                if s not in full_nbits:
                    full_nbits[s] = _chunked_stream(
                        section_noise_key(ctx.key, folds[s]),
                        packer.sections[s].length)
            for i in idxs:
                run, ax = runs[i], fsdp_axes[i]
                if ax >= 0:
                    if _contig(i):
                        lreg = run.size // n_clients
                        nb = _range_draw(
                            section_noise_key(ctx.key, folds[run.section]),
                            run.offset, lreg)
                        z = bits_to_gaussian(nb, 1.0).reshape(y[i].shape)
                    else:
                        nb = jax.lax.slice(full_nbits[run.section],
                                           (run.offset,),
                                           (run.offset + run.size,))
                        z = _region(bits_to_gaussian(nb, 1.0).reshape(
                            leaves[i].shape), i)
                    z = z * ctx.noise_std * ctx.ota_on
                    ghat = jnp.where(
                        cnt[i] > 0,
                        (y[i] + z) / (jnp.maximum(cnt[i], 1.0) * denom),
                        0.0)
                    sz = ghat.shape[ax] // n_sub
                    ghat = jax.lax.dynamic_slice_in_dim(ghat, sub_idx * sz,
                                                        sz, ax)
                else:
                    nb = jax.lax.slice(full_nbits[run.section],
                                       (run.offset,),
                                       (run.offset + run.size,))
                    z = (bits_to_gaussian(nb, 1.0).reshape(leaves[i].shape)
                         * ctx.noise_std * ctx.ota_on)
                    ghat = jnp.where(
                        cnt[i] > 0,
                        (y[i] + z) / (jnp.maximum(cnt[i], 1.0) * denom),
                        0.0)
                grads[i] = ghat

        if sectioned:
            # section-streaming schedule (DESIGN.md §3.16): walk the
            # Section partition in layout order, double-buffered — issue
            # section s's psums, then finalize section s-1 while they
            # are in flight, so the latency-hiding scheduler overlaps
            # each section's collectives with the next one's stream draw
            # + mask/apply. Peak live streams: one section.
            pending = None
            for sec in packer.sections:
                idxs = list(sec.leaf_indices)
                if not idxs:
                    continue
                y, cnt = _collect(idxs)
                if pending is not None:
                    _finalize(*pending)
                pending = (idxs, y, cnt)
            if pending is not None:
                _finalize(*pending)
        else:
            idxs = list(range(n_leaves))
            y, cnt = _collect(idxs)
            _finalize(idxs, y, cnt)
        return (packer.treedef.unflatten(grads),
                jax.tree.map(_zero_cot, ctx))

    gather_omega.defvjp(_fwd, _bwd)
    return gather_omega, packer


# ---------------------------------------------------------------------------
# FGN inputs from the same round draw (eq. 5)
# ---------------------------------------------------------------------------

def sectioned_final_norm(g_final, slab_key: jax.Array,
                         chan_c: ChannelParams, cluster_axes,
                         packer: TreePacker) -> jax.Array:
    """n_i = ‖M ∘ ∇_{ω̃}F_i‖ (eq. 6) from the ω̃ SECTION of the round's
    slab draw — bit-identical masks to the ones ``make_packed_omega_
    gather``'s backward applies to the same entries (the tail keeps
    ``PACKED_TAIL_FOLD`` in every layout, so only this one stream is
    re-drawn — no full-model draw in the FGN phase)."""
    folds = packed_section_folds(packer)
    tail_secs = [s for s in packer.sections if s.name == packer.tail_name]
    assert tail_secs, packer.sections
    sec = tail_secs[0]
    cidx = cluster_index(cluster_axes)
    bits = _chunked_stream(
        section_gain_key(slab_key, folds[sec.index], cidx), sec.length)
    leaves = jax.tree.leaves(g_final)
    assert len(leaves) == len(sec.leaf_indices), \
        (len(leaves), sec.leaf_indices)
    runs = {r.leaf: r for r in packer.leaf_runs()}
    total = jnp.zeros((), jnp.float32)
    for leaf, i in zip(leaves, sec.leaf_indices):
        run = runs[i]
        b = jax.lax.slice(bits, (run.offset,), (run.offset + run.size,))
        mask = bits_to_mask(b, chan_c.sigma2, chan_c.h_threshold,
                            chan_c.ota_on).reshape(leaf.shape)
        total = total + jnp.sum(
            jnp.where(mask, leaf.astype(jnp.float32), 0.0) ** 2)
    return jnp.sqrt(total)


# ---------------------------------------------------------------------------
# jnp oracle on the identical streams (tests — DESIGN.md §3.10)
# ---------------------------------------------------------------------------

def packed_omega_aggregate_ref(g_tree, slab_key: jax.Array,
                               chan: ChannelParams, n_clients: int,
                               packer: TreePacker,
                               live=None, n_eff=None):
    """Single-process oracle of the slab backward for ONE weighted-grad
    tree with leading (C,) cluster axes on every leaf: same section
    streams, same mask law, same guarded estimate — plain jnp, so the
    forced-multi-device slab step can be pinned to it on shared keys.
    ``live``/``n_eff`` mirror the backward's partial-participation flow
    (DESIGN.md §3.14); None is the full-participation identity."""
    folds = packed_section_folds(packer)
    n_clusters = int(chan.sigma2.shape[0])
    leaves = packer.treedef.flatten_up_to(g_tree)
    runs = {run.leaf: run for run in packer.leaf_runs()}
    gbits = [jnp.stack([
        _chunked_stream(section_gain_key(slab_key, folds[s.index], c),
                        s.length) for c in range(n_clusters)])
        for s in packer.sections]
    nbits = [_chunked_stream(section_noise_key(slab_key, folds[s.index]),
                             s.length) for s in packer.sections]
    denom = (jnp.float32(n_clients) if n_eff is None
             else jnp.maximum(jnp.asarray(n_eff, jnp.float32), 1.0))
    out = []
    for i in range(len(leaves)):
        run = runs[i]
        b = jax.lax.slice(gbits[run.section], (0, run.offset),
                          (n_clusters, run.offset + run.size))
        sig = chan.sigma2.reshape((n_clusters,) + (1,))
        masks = bits_to_mask(b, sig, chan.h_threshold, chan.ota_on)
        if live is not None:
            masks = jnp.logical_and(
                masks, jnp.asarray(live, jnp.float32)
                .reshape(n_clusters, 1) > 0.5)
        wg = leaves[i].astype(jnp.float32).reshape(n_clusters, -1)
        y = jnp.sum(jnp.where(masks, wg, 0.0), axis=0)
        nb = jax.lax.slice(nbits[run.section], (run.offset,),
                           (run.offset + run.size,))
        z = bits_to_gaussian(nb, 1.0) * chan.noise_std * chan.ota_on
        cnt = jnp.sum(masks.astype(jnp.float32), axis=0)
        ghat = jnp.where(cnt > 0,
                         (y + z) / (jnp.maximum(cnt, 1.0) * denom), 0.0)
        out.append(ghat.reshape(leaves[i].shape[1:]))
    return packer.treedef.unflatten(out)
