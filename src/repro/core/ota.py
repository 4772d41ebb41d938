"""Over-the-air aggregation over the wireless fading MAC (paper Sec. III-B).

The channel model, exactly as the paper defines it:

* channel gains  H_k^(l)(j) ~ N(0, σ_l²) i.i.d. per entry j, per cluster l,
  per iteration k                                                  (Sec. III-A)
* threshold mask M_k^(l)(j) = 1{ |H(j)|² ≥ H_th }                  (eq. 7)
* power allocation β_k^(l,i)(j) = p_k^(l,i) / H(j) on passing entries,
  0 otherwise (channel inversion)                                   (eq. 3)
* MAC superposition y(j) = Σ_{l∈M(j)} H(j) x^(l)(j) + z(j), z ~ N(0,1) (eq. 8)
* PS estimator ĝ(j) = y(j) / (|M_k(j)| · N)                         (eq. 10)

Because β inverts the channel, H·(β∘g) = p·g on passing entries — the
faithful-but-redundant inversion is implemented in ``faithful=True`` mode
(used by property tests to verify the cancellation); the fast path sums the
masked weighted gradients directly, which is bit-for-bit the same math.

The implementations share the math:

* the **per-leaf path** (this module's historical core) walks the pytree,
  drawing gains/masks/noise per leaf per cluster with ``jax.random`` —
  the readable oracle the property tests pin everything to;
* the **client-folded zero-copy path** (``ota_aggregate_client_folded``,
  the simulator's hot path — DESIGN.md §3.12) folds eq. 3's Σ_i p_i g_i
  INTO the masked MAC sum and consumes each raw (C, N, ·) gradient leaf
  in place against the section streams of the packer's layout
  (``repro.common.flatpack.TreePacker``) — no weighted tree, no (C, P)
  pack copy. The tests hold it to the per-leaf estimator run on the
  same words (``packed_gain_bits`` / ``packed_noise_bits``, the whole
  slab's draw); the last-shared-layer masks FedGradNorm needs (eq. 5)
  are the tail section's words of the same draw
  (``final_layer_masks_packed``);
* the **cluster-streaming path** (``ota_aggregate_streaming`` —
  DESIGN.md §3.15) folds one cluster at a time into a running sum;
* the **section-streaming path** (``ota_aggregate_sectioned`` —
  DESIGN.md §3.16) schedules the client-folded math one SECTION at a
  time (optionally with the §3.15 cluster scan inside each section), so
  peak live streams are one section of the layout — the
  billion-parameter memory shape.

Per-leaf channel keys are derived with ``fold_in(cluster_key, leaf_index)``,
which realizes the paper's "one i.i.d. gain per parameter entry" over an
arbitrary pytree. Noise keys live in a disjoint fold-in domain
(``NOISE_FOLD``, near 2³¹) so they can never collide with a cluster
index; the section streams fold section salts (``PACKED_*_FOLD``) from
the same reserved range.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax._src.prng import threefry2x32_p

from repro.common.flatpack import TreePacker, check_tree_matches_packer
from repro.core.channel import ChannelParams
from repro.kernels.ota_channel.kernel import CHUNK_ROWS, _client_cluster_block
from repro.kernels.ota_channel.ops import (
    ota_client_fold_apply, ota_client_fold_drawn_apply,
    ota_stream_fold_apply,
)
from repro.kernels.ota_channel.ref import bits_to_gaussian, bits_to_mask
from repro.kernels.slab import LANE, ROW_QUANTUM, on_tpu


# --------------------------------------------------------------------------
# key schedule
# --------------------------------------------------------------------------
# Reserved fold-in values near 2³¹ — structurally disjoint from cluster and
# leaf indices (both bounded by topology sizes far below 2³¹). The noise
# fold used to be 999, which collided with cluster_key(ks, 999) once
# n_clusters > 999.
NOISE_FOLD = 0x7FFFFFFF          # AWGN stream (per-leaf AND packed)
PACKED_HEAD_FOLD = 0x7FFF0001    # gain bits for the packed head section
PACKED_TAIL_FOLD = 0x7FFF0002    # gain bits for the packed tail (ω̃) section
# the simulator round's channel-key domain (DESIGN.md §4): HotaSim derives
# its per-round channel key as fold_in(step_key, SIM_CHAN_FOLD) — a
# reserved value, NOT a bare literal, so no future fold of the step key
# (data order, head init, ...) can collide with the channel streams.
SIM_CHAN_FOLD = 0x7FFF0003
# the participation-draw domain (DESIGN.md §4): every per-slot fault draw
# — client dropout, cluster blackout, straggler flags — folds off
# fold_in(round_key, PART_FOLD). The draws depend ONLY on the round key
# and the slot position, never on the fault rates themselves, so
# resampling FaultParams perturbs no channel stream (CRN across fault
# scenarios) and raising a rate only grows the dropped set (monotone
# coupling u < rate on a shared uniform).
PART_FOLD = 0x7FFF0004
# the client-sampling domain (DESIGN.md §4): the per-round client-id
# draw — which population member fills each (cluster, slot) position —
# folds off fold_in(round_key, SAMPLE_FOLD). Channel and participation
# streams key off the SLOT position, never the drawn ids, so resampling
# the population (or growing it) perturbs no mask, no noise and no fault
# draw: CRN survives resampling byte-for-byte (the position-determinism
# rule; pinned in tests/test_sampling.py).
SAMPLE_FOLD = 0x7FFF0005
# multi-section layouts (DESIGN.md §3.10): trunk section s folds BASE + s;
# the tail (ω̃) section keeps PACKED_TAIL_FOLD in EVERY layout, so eq.-5
# consumers re-draw only the ω̃ stream without knowing the trunk split.
PACKED_SECTION_FOLD_BASE = 0x7FFF0100
# ---- aux salts (DESIGN.md §4, class ``aux``) -----------------------------
# Small-valued salts folded off keys that never meet the per-round channel
# key domain, registered here (with their historical values, so no stream
# moves) rather than spelled as bare literals at the call sites — the
# `bare-fold-salt` lint rule (§3.17) rejects the literal spelling.
FINAL_INIT_FOLD = 7      # ω̃ (final shared layer) init off the trunk key
SAMPLE_INIT_FOLD = 11    # population client-bank init off the sim init key
HOTA_MASK_SALT = 0xBEEF  # dist backward's AWGN z off the round mask key
TUNE_PROBE_FOLD = 99     # layout autotuner's probe-weight draw
# participation sub-streams: per-kind uniforms fold off the PART_FOLD
# key (draw_participation), one sub-fold per fault kind
PART_DROP_FOLD = 0       # client dropout uniforms
PART_BLACK_FOLD = 1      # cluster blackout uniforms
PART_STRAG_FOLD = 2      # straggler-flag uniforms


def cluster_key(key: jax.Array, cluster: jax.Array | int) -> jax.Array:
    return jax.random.fold_in(key, cluster)


def leaf_key(ckey: jax.Array, leaf_idx: int) -> jax.Array:
    return jax.random.fold_in(ckey, leaf_idx)


def noise_key(key: jax.Array) -> jax.Array:
    """AWGN key in a fold-in domain no cluster index can reach."""
    return jax.random.fold_in(key, NOISE_FOLD)


def sim_channel_key(key: jax.Array) -> jax.Array:
    """The simulator round's channel key (DESIGN.md §4): every channel
    stream of a ``HotaSim.step_with_channel`` round — per-leaf gains,
    packed section bits, AWGN — folds off this key, in a reserved domain
    disjoint from any other fold of the step key."""
    return jax.random.fold_in(key, SIM_CHAN_FOLD)


def participation_key(key: jax.Array) -> jax.Array:
    """The round's participation-draw key (DESIGN.md §4): every fault
    draw — dropout, blackout, straggler — folds off this key, in a
    reserved domain disjoint from every channel stream."""
    return jax.random.fold_in(key, PART_FOLD)


def sample_key(key: jax.Array) -> jax.Array:
    """The round's client-sample key (DESIGN.md §4): the id draw that
    fills each (cluster, slot) position from its subpopulation folds off
    this key, in a reserved domain disjoint from every channel and
    participation stream — so resampling moves no mask, noise or fault
    draw (position determinism)."""
    return jax.random.fold_in(key, SAMPLE_FOLD)


def draw_client_sample(key: jax.Array, n_clusters: int, n_clients: int,
                       population: int) -> jax.Array:
    """(C, N) int32 ids in [0, population): which member of each
    (cluster, slot) subpopulation participates this round (DESIGN.md
    §3.15). One uniform id per slot — O(C·N) work regardless of the
    population size, so rounds/sec stays flat as the population grows
    (BENCH_sample.json). Slots draw from DISJOINT subpopulations (a
    slot is a task), so two slots can never select the same client and
    the post-round scatter back into the ``ClientBank`` is
    conflict-free. Ids are a pure function of (round key, slot) — host
    callers can recompute them without threading state."""
    return jax.random.randint(sample_key(key), (n_clusters, n_clients),
                              0, population, jnp.int32)


class Participation(NamedTuple):
    """One round's fault realization (all f32, all traced).

    ``part`` is the P of the |M∩P| estimator: the guarded PS estimate
    counts only LIVE clusters (``live`` masks the per-cluster eq.-7
    masks) and divides by ``n_eff`` — the mean participant count over
    live clusters — instead of the static N. With no faults injected
    ``part`` is all-ones, ``live`` all-ones and ``n_eff == N`` exactly,
    so the generalized estimator is bit-identical to eq. 10.
    """
    part: jax.Array      # (C, N) 1.0 = client participates this round
    stale: jax.Array     # (C, N) 1.0 = participates with a stale gradient
    live: jax.Array      # (C,)   1.0 = cluster has ≥ 1 participant
    n_live: jax.Array    # ()     live-cluster count
    total: jax.Array     # ()     total participant count
    n_eff: jax.Array     # ()     total / max(n_live, 1) — the N of eq. 10


def draw_participation(key: jax.Array, faults, n_clusters: int,
                       n_clients: int) -> Participation:
    """Per-slot participation draws for one round (DESIGN.md §3.14).

    ``faults`` is a ``repro.core.channel.FaultParams``. Uniforms are
    drawn once per (kind, slot) under sub-folds of ``participation_key``
    and compared against the traced rates, so the fault knobs vmap
    through the scenario banks without retracing and resampling a rate
    never moves another scenario's draw."""
    pk = participation_key(key)
    u_drop = jax.random.uniform(jax.random.fold_in(pk, PART_DROP_FOLD),
                                (n_clusters, n_clients))
    u_black = jax.random.uniform(jax.random.fold_in(pk, PART_BLACK_FOLD),
                                 (n_clusters,))
    u_strag = jax.random.uniform(jax.random.fold_in(pk, PART_STRAG_FOLD),
                                 (n_clusters, n_clients))
    on = faults.faults_on >= 0.5
    drop = jnp.logical_and(on, u_drop < faults.dropout)
    black = jnp.logical_and(on, u_black < faults.blackout)
    part = jnp.logical_and(~drop, ~black[:, None]).astype(jnp.float32)
    stale = part * jnp.logical_and(
        on, u_strag < faults.straggler).astype(jnp.float32)
    live = (jnp.sum(part, axis=1) > 0).astype(jnp.float32)
    n_live = jnp.sum(live)
    total = jnp.sum(part)
    n_eff = total / jnp.maximum(n_live, 1.0)
    return Participation(part=part, stale=stale, live=live, n_live=n_live,
                         total=total, n_eff=n_eff)


def sample_gain(key: jax.Array, shape, sigma2) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(
        jnp.asarray(sigma2, jnp.float32))


def gain_mask(h: jax.Array, h_threshold: float) -> jax.Array:
    """eq. (7): pass entries with |H|² ≥ H_th."""
    return (h * h) >= h_threshold


def tree_channel(key: jax.Array, tree, sigma2, h_threshold: float):
    """Draw (gains, masks) trees matching ``tree``'s structure/shapes."""
    leaves, treedef = jax.tree.flatten(tree)
    gains, masks = [], []
    for i, leaf in enumerate(leaves):
        h = sample_gain(leaf_key(key, i), leaf.shape, sigma2)
        gains.append(h)
        masks.append(gain_mask(h, h_threshold))
    return jax.tree.unflatten(treedef, gains), jax.tree.unflatten(treedef, masks)


# --------------------------------------------------------------------------
# power allocation + transmission (single cluster)
# --------------------------------------------------------------------------

def power_allocation(p_i: jax.Array, h: jax.Array, mask: jax.Array) -> jax.Array:
    """eq. (3): β = p / H where the channel passes, else 0."""
    safe_h = jnp.where(mask, h, 1.0)
    return jnp.where(mask, p_i / safe_h, 0.0)


def transmit_signal(p_i, g, h, mask):
    """x^(l,i) = β ∘ g (the signal a cluster's IS puts on the air for one
    client's gradient). Faithful path (channel inversion explicit)."""
    return power_allocation(p_i, h, mask) * g


def transmit_power(x: jax.Array) -> jax.Array:
    """E-free instantaneous ||x||² for the average power constraint (eq. 4)."""
    return jnp.sum(jnp.square(x))


# --------------------------------------------------------------------------
# full OTA aggregation across clusters (sim path)
# --------------------------------------------------------------------------

def ota_aggregate_leaf(
    weighted_grads: jax.Array,   # (C, ...) already Σ_i p_i g_i per cluster
    masks: jax.Array,            # (C, ...) bool
    noise: jax.Array,            # (...)
    n_clients: int,
    gains: Optional[jax.Array] = None,      # (C, ...) — faithful mode
    cluster_grads_scaled: Optional[jax.Array] = None,  # (C,...) β∘g sums
    live: Optional[jax.Array] = None,       # (C,) participation (§3.14)
    n_eff: Optional[jax.Array] = None,      # () traced effective N
):
    """eqs. (8)-(10) for one pytree leaf.

    Fast path: y = Σ_l mask_l * wg_l + z. Faithful path: y = Σ_l mask_l *
    H_l * (β∘g)_l + z (identical up to float assoc.; property-tested).

    Partial participation (DESIGN.md §3.14): ``live`` ANDs into the
    per-cluster masks — a blacked-out cluster transmits nothing and
    never reaches the |M| count, even under the ``ota_on`` all-pass gate
    — and the traced ``n_eff`` replaces the static N in the |M∩P|·N_eff
    denominator. Both default to the full-participation identity.
    """
    if live is not None:
        lv = live.reshape((masks.shape[0],) + (1,) * (masks.ndim - 1))
        masks = jnp.logical_and(masks, lv > 0.5)
    if gains is not None and cluster_grads_scaled is not None:
        y = jnp.sum(jnp.where(masks, gains * cluster_grads_scaled, 0.0), axis=0)
    else:
        y = jnp.sum(jnp.where(masks, weighted_grads, 0.0), axis=0)
    y = y + noise
    cnt = jnp.sum(masks.astype(jnp.float32), axis=0)
    denom = n_clients if n_eff is None else jnp.maximum(n_eff, 1.0)
    # |M_k(j)| = 0 -> nothing received but noise; estimator guarded to 0
    ghat = jnp.where(cnt > 0, y / (jnp.maximum(cnt, 1.0) * denom), 0.0)
    return ghat


def ota_aggregate_tree(
    key: jax.Array,
    weighted_grads,              # pytree with leading (C, ...) leaves
    chan: ChannelParams,         # traced knobs; chan.sigma2 is (C,)
    n_clients: int,
    live: Optional[jax.Array] = None,   # (C,) cluster participation
    n_eff: Optional[jax.Array] = None,  # () traced effective N
):
    """Sim-path OTA aggregation over a pytree of per-cluster weighted grads.

    The ``ota_on`` gate is traced (no Python branch): off forces every mask
    all-pass and zeroes the AWGN, so one jit serves fading and error-free
    scenarios alike. ``live``/``n_eff`` inject partial participation
    (DESIGN.md §3.14); None keeps the full-participation trace bit-exact.
    """
    leaves, treedef = jax.tree.flatten(weighted_grads)
    n_clusters = leaves[0].shape[0]
    out = []
    for i, wg in enumerate(leaves):
        ks = leaf_key(key, i)
        # per-cluster gains: vmap the draw over the cluster axis
        hs = jax.vmap(
            lambda c: sample_gain(cluster_key(ks, c), wg.shape[1:],
                                  chan.sigma2[c])
        )(jnp.arange(n_clusters))
        masks = jnp.logical_or(gain_mask(hs, chan.h_threshold),
                               chan.ota_on < 0.5)
        noise = (jax.random.normal(noise_key(ks), wg.shape[1:])
                 * chan.noise_std * chan.ota_on)
        out.append(ota_aggregate_leaf(wg, masks, noise, n_clients,
                                      live=live, n_eff=n_eff))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------
# section streams (DESIGN.md §4)
# --------------------------------------------------------------------------
#
# Key schedule: one gain-bit stream per (section, cluster) —
#   bits_sec[c] = random.bits(fold_in(fold_in(key, PACKED_*_FOLD), c))
# — and one AWGN stream per section (fold_in(noise_key(key), fold)).
# Sections are the TreePacker's slices, each lane-aligned, so
# ``final_layer_masks_packed`` re-draws ONLY the tail (ω̃) stream and gets
# bit-identical masks to the full aggregation's tail slice — no second
# per-leaf loop, no full-model draw in the FGN phase.

CHUNK = CHUNK_ROWS * LANE    # the stream quantum (entries per chunk draw)


def _chunked_stream(key: jax.Array, length: int) -> jax.Array:
    """(length,) uint32 of the chunk-quantized stream: chunk j is
    ``bits(fold_in(key, j), (CHUNK,))``; a partial last chunk is
    truncated — exactly the words the drawing kernel computes from their
    positions, independent of kernel blocking."""
    n_chunks = -(-length // CHUNK)
    with jax.named_scope("hota.ota_draw"):
        chunks = jax.vmap(
            lambda j: jax.random.bits(jax.random.fold_in(key, j), (CHUNK,),
                                      jnp.uint32)
        )(jnp.arange(n_chunks))
        return chunks.reshape(-1)[:length]


def _section_bits(key: jax.Array, fold: int, n_clusters: int, length: int):
    """(C, length) uint32 gain bits for one packed section: cluster c's
    stream is chunk-quantized under ``fold_in(section_key, c)``, and the
    section fold keeps head/tail streams disjoint so the FGN phase
    re-draws just the tail."""
    skey = jax.random.fold_in(key, fold)
    return jax.vmap(
        lambda c: _chunked_stream(cluster_key(skey, c), length)
    )(jnp.arange(n_clusters))


def packed_section_folds(packer: TreePacker) -> List[int]:
    """The stream fold of each ``packer.sections`` entry (DESIGN.md §4).

    Legacy two-section layouts keep PACKED_HEAD_FOLD / PACKED_TAIL_FOLD
    (streams bit-identical to PR 2); multi-section ("toplevel") layouts
    fold PACKED_SECTION_FOLD_BASE + index per trunk section while the
    tail section always keeps PACKED_TAIL_FOLD."""
    folds = []
    for sec in packer.sections:
        if sec.name == packer.tail_name:
            folds.append(PACKED_TAIL_FOLD)
        elif packer.layout == "tail":
            folds.append(PACKED_HEAD_FOLD)
        else:
            folds.append(PACKED_SECTION_FOLD_BASE + sec.index)
    return folds


def stream_range_bits(key: jax.Array, start: int, length: int) -> jax.Array:
    """uint32 elements [start, start+length) of ``key``'s chunk-quantized
    stream (chunk j is ``bits(fold_in(key, j), (CHUNK,))`` — DESIGN.md §4).

    ``start``/``length`` are STATIC: only the chunks intersecting the
    range are drawn, and because the kernel's partial-chunk rule is
    truncation, a mid-chunk slice here is bit-identical to what a kernel
    sweeping the whole section would apply at these positions. This is
    the zero-copy executor's bit source: a leaf's run (see
    ``TreePacker.leaf_runs``) maps to exactly one such range."""
    j0 = start // CHUNK
    j1 = (start + length - 1) // CHUNK
    a = start - j0 * CHUNK
    with jax.named_scope("hota.ota_draw"):
        chunks = jax.vmap(
            lambda j: jax.random.bits(jax.random.fold_in(key, j), (CHUNK,),
                                      jnp.uint32)
        )(jnp.arange(j0, j1 + 1))
        return jax.lax.slice(chunks.reshape(-1), (a,), (a + length,))


def stream_chunk_keys(key: jax.Array, start: int,
                      length: int) -> Tuple[int, jax.Array]:
    """The chunks of ``key``'s chunk-quantized stream that cover the
    STATIC positions [start, start+length): ``(j0, keys)`` where
    ``keys[i]`` is chunk j0+i's key ``fold_in(key, j0 + i)`` as a (2,)
    uint32 pair — one fold per chunk, nothing else drawn."""
    j0 = start // CHUNK
    j1 = (start + length - 1) // CHUNK
    with jax.named_scope("hota.ota_draw"):
        keys = jax.vmap(lambda j: jax.random.key_data(
            jax.random.fold_in(key, j)))(jnp.arange(j0, j1 + 1))
    return j0, keys


def stream_words(k0: jax.Array, k1: jax.Array, m: jax.Array) -> jax.Array:
    """Word ``m`` of the chunk keyed (k0, k1), element by element: the
    x0 ^ x1 of threefry2x32 on the counter pair (0, m) — what
    ``bits(chunk_key, (CHUNK,))[m]`` is under the partitionable threefry
    (DESIGN.md §4, position form). The ONE home of the word formula: the
    jnp paths and the drawing client-fold kernel both call it. (Not
    ``jax.extend.random.threefry_2x32``: it splits its count array into
    two halves and gives other words.)"""
    x0, x1 = threefry2x32_p.bind(k0, k1, jnp.zeros_like(m), m)
    return x0 ^ x1


def _chunk_key_table(keys: jax.Array, start: int, length: int):
    """``stream_chunk_keys`` of each of the S streams keyed by ``keys``
    (S, 2): (j0, (S, n_chunks, 2) uint32)."""
    table = jax.vmap(lambda k: stream_chunk_keys(k, start, length)[1])(keys)
    return start // CHUNK, table


def stream_words_at(keys: jax.Array, start: int, length: int) -> jax.Array:
    """(S, length) uint32: positions [start, start+length) (STATIC) of the
    S chunk-quantized streams keyed by ``keys`` (S, 2) — exactly these
    words, computed from their positions in one elementwise expression
    (each position picks its chunk's key); bit-identical to slicing
    ``_chunked_stream`` / ``stream_range_bits`` there."""
    j0, table = _chunk_key_table(keys, start, length)
    shape = (keys.shape[0], length)
    with jax.named_scope("hota.ota_draw"):
        pos = (start - j0 * CHUNK) + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        chunk = pos // CHUNK
        k0, k1 = (jnp.broadcast_to(table[:, 0, h:h + 1], shape)
                  for h in (0, 1))
        for i in range(1, table.shape[1]):
            k0, k1 = (jnp.where(chunk == i, table[:, i, h:h + 1], k)
                      for h, k in ((0, k0), (1, k1)))
        return stream_words(k0, k1, (pos % CHUNK).astype(jnp.uint32))


def section_gain_key(slab_key: jax.Array, fold: int,
                     cluster: jax.Array | int) -> jax.Array:
    """Gain-bit stream key for one (section, cluster) — the same
    fold_in(fold_in(key, section_fold), cluster) scheme as
    ``_section_bits``, usable with a TRACED cluster index (the
    distributed path folds the mesh position)."""
    return cluster_key(jax.random.fold_in(slab_key, fold), cluster)


def section_noise_key(slab_key: jax.Array, fold: int) -> jax.Array:
    """AWGN stream key for one section (``packed_noise_bits``' scheme)."""
    return jax.random.fold_in(noise_key(slab_key), fold)


def section_stream_keys(key: jax.Array, fold: int, n_clusters: int,
                        noise: bool = True) -> jax.Array:
    """(C+1, 2) uint32 stream keys of one section: the C gain streams
    (``section_gain_key``), then the noise stream (``section_noise_key``)
    — or (C, 2) with ``noise=False``."""
    with jax.named_scope("hota.ota_draw"):
        keys = [jax.vmap(lambda c: section_gain_key(key, fold, c))(
            jnp.arange(n_clusters))]
        if noise:
            keys.append(section_noise_key(key, fold)[None])
        return jax.random.key_data(jnp.concatenate(keys))


def section_gain_streams(key: jax.Array, packer: TreePacker,
                         n_clusters: int) -> List[jax.Array]:
    """One (C, length) gain-bit stream per ``packer.sections`` entry,
    drawn under the fold ``packed_section_folds`` assigns it. The SINGLE
    source of the packed gain schedule: ``packed_gain_bits`` concatenates
    these, the zero-copy consumers (``ota_aggregate_client_folded`` in
    supplied mode, ``repro.core.hota_slab``) slice them per leaf — so sim
    and distributed paths draw identical bits for identical layouts
    (pinned in tests/test_client_folded.py)."""
    folds = packed_section_folds(packer)
    return [_section_bits(key, folds[sec.index], n_clusters, sec.length)
            for sec in packer.sections]


def section_noise_streams(key: jax.Array,
                          packer: TreePacker) -> List[jax.Array]:
    """One (length,) AWGN bit stream per section — the noise twin of
    ``section_gain_streams`` (same fold schedule, noise-key domain)."""
    folds = packed_section_folds(packer)
    return [_chunked_stream(section_noise_key(key, folds[sec.index]),
                            sec.length)
            for sec in packer.sections]


def packed_gain_bits(key: jax.Array, packer: TreePacker, n_clusters: int):
    """The whole round's (C, P) gain-bit slab: the per-section streams of
    ``section_gain_streams`` in layout order — the legacy head ++ tail
    pair for two-section layouts (bit-identical to PR 2), one stream per
    trunk section for multi-section layouts."""
    parts = section_gain_streams(key, packer, n_clusters)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def packed_noise_bits(key: jax.Array, packer: TreePacker) -> jax.Array:
    """The round's (P,) AWGN bit stream: the per-section chunk-quantized
    noise streams of ``section_noise_streams`` in layout order."""
    parts = section_noise_streams(key, packer)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def ota_aggregate_client_folded(
    key: jax.Array,
    grads,                       # pytree with leading (C, N, ...) leaves
    p: jax.Array,                # (C, N) loss weights
    chan: ChannelParams,         # traced knobs; chan.sigma2 is (C,)
    n_clients: int,
    packer: TreePacker,
    bits_mode: str = "fused",    # "fused" | "supplied" (see below)
    live: Optional[jax.Array] = None,   # (C,) cluster participation (§3.14)
    n_eff: Optional[jax.Array] = None,  # () traced effective N
    impl: Optional[str] = None,  # "pallas" | "jnp"; None: by platform
):
    """Slab-native sim-path OTA aggregation (DESIGN.md §3.12): fold the
    client-weight einsum INTO the channel and consume every gradient
    leaf's storage in place.

    Same math as ``einsum("cn,cn...->c...", p, g)`` followed by the
    reference estimator (``ota_aggregate_slab_ref``, or the per-leaf
    ``ota_aggregate_leaf``) on the words of ``packed_gain_bits`` /
    ``packed_noise_bits`` — eqs. 3 + 8-10 with the traced ``ota_on``
    gate — but computed leaf by leaf against the static zero-copy maps
    (``TreePacker.leaf_runs``): neither the client-weighted tree nor the
    (C, P) packed slab is ever materialized. Streams are the per-section
    chunk-quantized streams of ``packed_section_folds`` — identical words
    to the slab-native distributed engine on the same layout.

    ``bits_mode`` picks where the words are computed; both modes return
    identical values:

    * ``"fused"``: each leaf's words come from their stream positions
      (DESIGN.md §4, position form). On the kernel path the leaf's
      ROW_QUANTUM main body runs the drawing kernel, which computes
      them from a table of chunk keys, so no word reaches HBM; a 2-D
      leaf of whole (8, 128) tiles goes in its own layout
      (``ota_client_fold_drawn2d``), others as their flat (rows, 128)
      view (``ota_client_fold_drawn``). The ragged remainder and the
      jnp path draw exactly their positions with ``stream_words_at``.
    * ``"supplied"``: the streams are drawn once per (section, cluster)
      outside the kernels and sliced per leaf. The draw depends only on
      ``key``, so under ``ScenarioBank``'s scenario vmap (shared key,
      ``in_axes=None``) it hoists out of the scenario axis, where an
      in-kernel draw would be repeated once per scenario.

    ``impl``: the per-leaf dispatch of ``ota_client_fold_apply`` —
    "pallas" on TPU, "jnp" elsewhere; tests force "pallas" + interpret.
    """
    if bits_mode not in ("fused", "supplied"):
        raise ValueError(bits_mode)
    check_tree_matches_packer(packer, grads,
                              "gradient pytree (client-folded OTA)",
                              batch_ndim=2)
    n_clusters = int(chan.sigma2.shape[0])
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    leaves = packer.treedef.flatten_up_to(grads)
    out = [None] * len(leaves)
    fold_kw = dict(live=live, n_eff=n_eff, interpret=not on_tpu())
    if bits_mode == "supplied":
        gbits = section_gain_streams(key, packer, n_clusters)
        nbits = section_noise_streams(key, packer)
        for run in packer.leaf_runs():
            b = jax.lax.slice(gbits[run.section], (0, run.offset),
                              (n_clusters, run.offset + run.size))
            nb = jax.lax.slice(nbits[run.section], (run.offset,),
                               (run.offset + run.size,))
            out[run.leaf] = ota_client_fold_apply(
                leaves[run.leaf], p, b, nb, chan.sigma2, chan.h_threshold,
                chan.noise_std, chan.ota_on, n_clients, impl=impl,
                **fold_kw)
        return packer.treedef.unflatten(out)

    folds = packed_section_folds(packer)
    skeys = {}
    # the drawing kernel has no C-blocked variant: where the C·N blocks
    # outgrow VMEM (no benchmark cell does) the words are drawn by
    # position outside and ota_client_fold_apply takes its C-blocked
    # kernel
    drawn = (impl == "pallas" and _client_cluster_block(
        n_clusters, n_clients, not on_tpu()) == n_clusters)
    for run in packer.leaf_runs():
        if run.section not in skeys:
            skeys[run.section] = section_stream_keys(
                key, folds[run.section], n_clusters)
        keys = skeys[run.section]
        g = leaves[run.leaf]
        main = run.size - run.size % ROW_QUANTUM if drawn else 0
        parts = []
        if main:
            # a whole leaf goes in its own layout: the kernel wrapper picks
            # the native 2-D blocks or the flat view from its shape
            j0, table = _chunk_key_table(keys, run.offset, main)
            parts.append(ota_client_fold_drawn_apply(
                g if main == run.size else g.reshape(
                    n_clusters, n_clients, run.size)[..., :main],
                p, table.reshape(-1), run.offset - j0 * CHUNK, stream_words,
                chan.sigma2, chan.h_threshold, chan.noise_std, chan.ota_on,
                n_clients, **fold_kw))
        if run.size - main:
            w = stream_words_at(keys, run.offset + main, run.size - main)
            parts.append(ota_client_fold_apply(
                g.reshape(n_clusters, n_clients, run.size)[..., main:],
                p, w[:n_clusters], w[n_clusters], chan.sigma2,
                chan.h_threshold, chan.noise_std, chan.ota_on, n_clients,
                impl=impl, **fold_kw))
        ghat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        out[run.leaf] = ghat.reshape(g.shape[2:])
    return packer.treedef.unflatten(out)


class OTAStreamAcc(NamedTuple):
    """Running state of the streaming aggregator (DESIGN.md §3.15): the
    masked MAC sum and the |M∩P| pass count, one leaf-shaped f32 array
    each — NO cluster axis. Peak memory of a streaming round is one
    cluster's contribution plus this accumulator (HLO-pinned in
    tests/test_sampling.py)."""
    y: Any       # pytree, leaf-shaped f32: Σ_{folded l} M_l ∘ (Σ_n p g)
    cnt: Any     # pytree, leaf-shaped f32: Σ_{folded l} M_l


def ota_stream_init(packer: TreePacker) -> OTAStreamAcc:
    """Zeroed accumulator matching ``packer``'s tree."""
    def zeros():
        return packer.treedef.unflatten(
            [jnp.zeros(packer.slots[i].shape, jnp.float32)
             for i in range(len(packer.slots))])
    return OTAStreamAcc(y=zeros(), cnt=zeros())


def ota_stream_fold(
    key: jax.Array,
    acc: OTAStreamAcc,
    grads_c,                     # pytree with leading (N, ...) leaves
    p_c: jax.Array,              # (N,) this cluster's loss weights
    chan: ChannelParams,
    cluster: jax.Array | int,    # traced cluster index
    packer: TreePacker,
    live_c=None,                 # () this cluster's participation flag
) -> OTAStreamAcc:
    """Fold ONE cluster's contribution into the running sum (DESIGN.md
    §3.15): draw only cluster ``cluster``'s per-section streams
    (``stream_range_bits`` under ``section_gain_key`` — byte-identical
    to the slice ``ota_aggregate_client_folded`` applies at the same
    positions, because partial chunks truncate), fold the client weights
    into the masked apply, and accumulate the masked sum + pass count.
    The cluster index is traced, so a ``lax.scan``/``fori_loop`` over
    arriving clusters compiles to ONE fold body — no (C, ·) stream or
    mask buffer ever exists."""
    folds = packed_section_folds(packer)
    sig_c = jnp.asarray(chan.sigma2, jnp.float32)[cluster]
    leaves = packer.treedef.flatten_up_to(grads_c)
    y = packer.treedef.flatten_up_to(acc.y)
    cnt = packer.treedef.flatten_up_to(acc.cnt)
    for run in packer.leaf_runs():
        gkey = section_gain_key(key, folds[run.section], cluster)
        b = stream_range_bits(gkey, run.offset, run.size)
        dy, dc = ota_stream_fold_apply(
            leaves[run.leaf], p_c, b, sig_c, chan.h_threshold,
            chan.ota_on, live_c=live_c, interpret=not on_tpu())
        y[run.leaf] = y[run.leaf] + dy
        cnt[run.leaf] = cnt[run.leaf] + dc
    return OTAStreamAcc(y=packer.treedef.unflatten(y),
                        cnt=packer.treedef.unflatten(cnt))


def ota_stream_finalize(
    key: jax.Array,
    acc: OTAStreamAcc,
    chan: ChannelParams,
    n_clients: int,
    packer: TreePacker,
    n_eff=None,                  # () traced effective N (§3.14)
):
    """Close a streaming round: add the AWGN (the same per-section noise
    streams ``section_noise_streams`` draws, sliced per leaf) and apply
    the guarded |M∩P|·N_eff estimate (eq. 10). Returns the ĝ pytree."""
    folds = packed_section_folds(packer)
    y = packer.treedef.flatten_up_to(acc.y)
    cnt = packer.treedef.flatten_up_to(acc.cnt)
    denom = (jnp.float32(n_clients) if n_eff is None
             else jnp.maximum(jnp.asarray(n_eff, jnp.float32), 1.0))
    out = [None] * len(y)
    for run in packer.leaf_runs():
        nkey = section_noise_key(key, folds[run.section])
        nb = stream_range_bits(nkey, run.offset, run.size)
        z = (bits_to_gaussian(nb, 1.0) * chan.noise_std
             * jnp.asarray(chan.ota_on, jnp.float32))
        yl = y[run.leaf].reshape(-1) + z
        cl = cnt[run.leaf].reshape(-1)
        g = jnp.where(cl > 0, yl / (jnp.maximum(cl, 1.0) * denom), 0.0)
        out[run.leaf] = g.reshape(y[run.leaf].shape)
    return packer.treedef.unflatten(out)


def ota_aggregate_streaming(
    key: jax.Array,
    grads,                       # pytree with leading (C, N, ...) leaves
    p: jax.Array,                # (C, N) loss weights
    chan: ChannelParams,         # traced knobs; chan.sigma2 is (C,)
    n_clients: int,
    packer: TreePacker,
    bits_mode: str = "fused",    # accepted for API symmetry (key-only draw)
    live: Optional[jax.Array] = None,   # (C,) cluster participation
    n_eff: Optional[jax.Array] = None,  # () traced effective N
):
    """Streaming OTA aggregation (DESIGN.md §3.15): same math and same
    streams as ``ota_aggregate_client_folded`` — eqs. 3 + 8-10 with the
    traced ``ota_on`` gate, partial participation included — but the
    cluster axis is a ``lax.scan`` over ``ota_stream_fold``, so peak
    memory holds ONE cluster's masked contribution plus the running
    accumulator instead of every cluster's stream and mask at once
    (HLO-pinned: no (C, section)-sized buffer compiles). This is the
    aggregation shape for rounds whose cluster contributions ARRIVE one
    at a time (million-client sampling, ROADMAP); the equivalence to the
    all-at-once path is property-tested."""
    if bits_mode not in ("fused", "supplied"):
        raise ValueError(bits_mode)
    check_tree_matches_packer(packer, grads,
                              "gradient pytree (streaming OTA)",
                              batch_ndim=2)
    n_clusters = int(chan.sigma2.shape[0])
    live_v = (jnp.ones((n_clusters,), jnp.float32) if live is None
              else jnp.asarray(live, jnp.float32).reshape(n_clusters))

    def body(acc, xs):
        c, g_c, p_c, lv_c = xs
        return ota_stream_fold(key, acc, g_c, p_c, chan, c, packer,
                               live_c=lv_c), None

    acc, _ = jax.lax.scan(
        body, ota_stream_init(packer),
        (jnp.arange(n_clusters), grads,
         jnp.asarray(p, jnp.float32), live_v))
    return ota_stream_finalize(key, acc, chan, n_clients, packer,
                               n_eff=n_eff)


def ota_aggregate_sectioned(
    key: jax.Array,
    grads,                       # pytree with leading (C, N, ...) leaves
    p: jax.Array,                # (C, N) loss weights
    chan: ChannelParams,         # traced knobs; chan.sigma2 is (C,)
    n_clients: int,
    packer: TreePacker,
    bits_mode: str = "fused",    # accepted for API symmetry (key-only draw)
    live: Optional[jax.Array] = None,   # (C,) cluster participation
    n_eff: Optional[jax.Array] = None,  # () traced effective N
    streaming: bool = False,     # compose with the cluster scan (§3.15)
):
    """Section-streaming OTA aggregation (DESIGN.md §3.16): the Section
    partition is the unit of scheduling. Sections are heterogeneous
    (length AND leaf set differ), so the scan over the section index is
    a STATIC unrolled schedule — per section, draw only that section's
    chunk-quantized gain/noise streams (the same ``packed_section_folds``
    folds, so the draws are byte-identical to the batch draw), fold only
    that section's leaf runs, then release the buffers. Peak live
    streams are one section — bounded by the layout's
    ``max_section_rows`` cap — never the (P,) or (C, P) slab
    (HLO-pinned in tests/test_sectioned.py).

    Equivalence: with ``streaming=False`` every per-leaf kernel call
    receives byte-identical inputs to ``ota_aggregate_client_folded``'s,
    so the result is BIT-identical (not just associativity-close). With
    ``streaming=True`` the cluster ``lax.scan`` runs INSIDE each
    section (one cluster's slice of one section live at a time) and
    every leaf accumulates in the same cluster order as
    ``ota_aggregate_streaming`` — bit-identical to that engine."""
    if bits_mode not in ("fused", "supplied"):
        raise ValueError(bits_mode)
    check_tree_matches_packer(packer, grads,
                              "gradient pytree (sectioned OTA)",
                              batch_ndim=2)
    n_clusters = int(chan.sigma2.shape[0])
    folds = packed_section_folds(packer)
    leaves = packer.treedef.flatten_up_to(grads)
    out = [None] * len(leaves)
    runs_by_sec: dict = {}
    for run in packer.leaf_runs():
        runs_by_sec.setdefault(run.section, []).append(run)

    def _fold_section(sec, runs):
        # all-clusters-at-once fold of ONE section: the client-folded
        # math restricted to this section's runs, on this section's draw
        gb = _section_bits(key, folds[sec.index], n_clusters, sec.length)
        nb = _chunked_stream(section_noise_key(key, folds[sec.index]),
                             sec.length)
        for run in runs:
            b = jax.lax.slice(gb, (0, run.offset),
                              (n_clusters, run.offset + run.size))
            nbs = jax.lax.slice(nb, (run.offset,),
                                (run.offset + run.size,))
            out[run.leaf] = ota_client_fold_apply(
                leaves[run.leaf], p, b, nbs, chan.sigma2,
                chan.h_threshold, chan.noise_std, chan.ota_on, n_clients,
                live=live, n_eff=n_eff, interpret=not on_tpu())

    def _stream_section(sec, runs, p_v, live_v, denom):
        # cluster scan INSIDE the section: one (cluster, section) slice
        # live at a time, leaf sums in ota_aggregate_streaming's order
        def body(acc, xs):
            c, gs, p_c, lv_c = xs
            sig_c = jnp.asarray(chan.sigma2, jnp.float32)[c]
            y, cnt = acc
            for k, run in enumerate(runs):
                gkey = section_gain_key(key, folds[sec.index], c)
                b = stream_range_bits(gkey, run.offset, run.size)
                dy, dc = ota_stream_fold_apply(
                    gs[k], p_c, b, sig_c, chan.h_threshold, chan.ota_on,
                    live_c=lv_c, interpret=not on_tpu())
                y[k] = y[k] + dy
                cnt[k] = cnt[k] + dc
            return (y, cnt), None

        zeros = [jnp.zeros(packer.slots[r.leaf].shape, jnp.float32)
                 for r in runs]
        (y, cnt), _ = jax.lax.scan(
            body, (list(zeros), list(zeros)),
            (jnp.arange(n_clusters), [leaves[r.leaf] for r in runs],
             p_v, live_v))
        nkey = section_noise_key(key, folds[sec.index])
        for k, run in enumerate(runs):
            nbs = stream_range_bits(nkey, run.offset, run.size)
            z = (bits_to_gaussian(nbs, 1.0) * chan.noise_std
                 * jnp.asarray(chan.ota_on, jnp.float32))
            yl = y[k].reshape(-1) + z
            cl = cnt[k].reshape(-1)
            g = jnp.where(cl > 0, yl / (jnp.maximum(cl, 1.0) * denom), 0.0)
            out[run.leaf] = g.reshape(y[k].shape)

    if streaming:
        p_v = jnp.asarray(p, jnp.float32)
        live_v = (jnp.ones((n_clusters,), jnp.float32) if live is None
                  else jnp.asarray(live, jnp.float32).reshape(n_clusters))
        denom = (jnp.float32(n_clients) if n_eff is None
                 else jnp.maximum(jnp.asarray(n_eff, jnp.float32), 1.0))
    for sec in packer.sections:
        runs = runs_by_sec.get(sec.index, [])
        if not runs:
            continue
        if streaming:
            _stream_section(sec, runs, p_v, live_v, denom)
        else:
            _fold_section(sec, runs)
    return packer.treedef.unflatten(out)


def final_layer_masks_packed(key: jax.Array, chan: ChannelParams,
                             packer: TreePacker):
    """Masks M^(l) on the last-shared-layer params ω̃ (eq. 5-7), drawn
    from the tail section's stream — bit-identical to the masks
    ``ota_aggregate_client_folded`` applies to the same entries.

    Draws the stream per leaf at the SAME ``leaf_runs`` positions the
    zero-copy engines walk (the tail section is never coalesced, so its
    fold and runs are layout-stable): each mask leaf is computed from
    exactly its positions (``stream_words_at``) and reshaped in place —
    no chunk is drawn whole and the (C, tail_len) slab is never
    unpacked. ``bits_to_mask`` is elementwise, so this is bit-identical
    to masking the whole tail draw.
    """
    if packer.tail_name is None or not packer.tail_len:
        raise ValueError(
            "final_layer_masks_packed needs a packer with a non-empty "
            f"tail section (tail={packer.tail_name!r}) — the eq.-5 masks "
            "are defined on the last-shared-layer params ω̃")
    n_clusters = chan.sigma2.shape[0]
    tail_sec = next(s for s in packer.sections
                    if s.name == packer.tail_name)
    keys = section_stream_keys(key, PACKED_TAIL_FOLD, n_clusters,
                               noise=False)                     # (C, 2)
    sig = chan.sigma2.reshape(n_clusters, 1)
    sub_leaves = []
    for run in packer.leaf_runs():
        if run.section != tail_sec.index:
            continue
        b = stream_words_at(keys, run.offset, run.size)
        m = bits_to_mask(b, sig, chan.h_threshold, chan.ota_on)
        sub_leaves.append(
            m.reshape((n_clusters,) + packer.slots[run.leaf].shape))
    full = packer.treedef.unflatten(list(range(len(packer.slots))))
    _, tail_def = jax.tree_util.tree_flatten(full[packer.tail_name])
    return jax.tree_util.tree_unflatten(tail_def, sub_leaves)


def final_layer_masks(key: jax.Array, final_tree, chan: ChannelParams,
                      leaf_offset: int = 0):
    """Masks M^(l) restricted to the last-shared-layer params ω̃, for the
    sparsified F_grad (eq. 5-7). Uses the same per-leaf keys as the full
    aggregation so FGN sees exactly the channel the transmission will use."""
    leaves, treedef = jax.tree.flatten(final_tree)
    n_clusters = chan.sigma2.shape[0]
    masks = []
    for i, leaf in enumerate(leaves):
        ks = leaf_key(key, leaf_offset + i)
        hs = jax.vmap(
            lambda c: sample_gain(cluster_key(ks, c), leaf.shape,
                                  chan.sigma2[c])
        )(jnp.arange(n_clusters))
        m = jnp.logical_or(gain_mask(hs, chan.h_threshold),
                           chan.ota_on < 0.5)
        masks.append(m)
    return jax.tree.unflatten(treedef, masks)
