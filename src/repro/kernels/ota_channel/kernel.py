"""Pallas TPU kernels for the OTA fading-MAC channel (paper Sec. III).

* ``ota_mask_weight_pallas`` / ``ota_mask_count_pallas`` — the
  slab-native distributed engine's local channel work (DESIGN.md §3.10):
  one device's masked, weighted gradient M ∘ (w·x), and with it the
  Σ_l M_l pass count over every cluster's stream (the MAC psum runs
  across the mesh, so masking is the only local per-entry work).

* ``ota_aggregate_client_pallas`` — the client-folded PS estimator
  (DESIGN.md §3.12) on caller-supplied channel words: input the RAW
  (C, N, rows, 128) per-client gradient slab and the (C, N) loss-weight
  matrix (riding the params block); the MAC loop computes
  Σ_l mask_l · (Σ_n p[l,n]·g[l,n]) in block — eqs. 3 + 8-10 in one
  pass, so the caller never materializes the client-weighted tree.
  Masks are drawn by inverse-CDF thresholding (``u < erfc(√(H_th/2σ²))``
  — exactly the law of 1{|H|² ≥ H_th}; the estimator never consumes H
  because channel inversion cancels it on passing entries), so the
  per-entry cost is one compare, not a transcendental chain. The pass
  probability is a per-cluster scalar computed by the wrappers (Mosaic
  has no erfc lowering) and the uniform is the word's top 24 bits, so
  every cast the kernel makes is exact. Per-cluster masks and the noise
  tree never touch HBM. At large C the cluster axis is blocked
  (``ota_client_fold_cblk``).

* ``ota_client_fold_drawn_pallas`` — the client-folded estimator that
  computes its own channel words: each element's gain and noise words
  are the stream's word formula (``repro.core.ota.stream_words``)
  evaluated at its stream position under its chunk's key, read from a
  small key table in SMEM — the same words as the chunked draw, none of
  them in HBM (DESIGN.md §4, position form). A 2-D leaf of whole (8, 128)
  tiles is read in its own (a, b) layout (``ota_client_fold_drawn2d``),
  element (r, c) at position ``word0 + r·b + c``, with each draw tile
  spanning at most one chunk (``drawn_native``); any other leaf is read
  as its flat (rows, 128) view (``ota_client_fold_drawn``).

Channel knobs (the per-cluster pass probabilities, noise std, the ota_on
gate) arrive as one traced params block, so scenario sweeps
(``ScenarioBank``) vmap over them without re-tracing; ``ota_on < 0.5``
forces every mask all-pass and zeroes the AWGN (the error-free baseline)
inside the same kernel.

Tiling: slabs are (rows, 128) — lane-aligned for the VPU — with the
cluster loop unrolled in-kernel (C is static). All compute is
elementwise VPU work. Every channel word is a threefry word of the
chunk-quantized stream, bit-reproducible from the round key: which
streams exist, what CHUNK_ROWS pins, and why blocking can never shift a
draw is specified normatively in DESIGN.md §4 (the RNG stream spec).

Validated in interpret mode against ``ref.bits_to_mask`` /
``ref.ota_aggregate_client_ref`` on the same words.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.slab import LANE, SUBLANE

TWO_PI = 6.283185307179586
DEFAULT_BLOCK_ROWS = 256
VMEM_BUDGET_BYTES = 6 * 1024 * 1024
# The stream quantum: channel words are drawn in (CHUNK_ROWS, 128) chunks
# keyed by fold_in(stream_key, chunk), so the stream NEVER depends on how
# a kernel is blocked. Changing CHUNK_ROWS changes the draw — it is part
# of the stream spec (DESIGN.md §4).
CHUNK_ROWS = 1024


def _u32_to_f32(v):
    """Exact float of a uint32 below 2²⁴, cast through int32 (Mosaic has
    no uint32 -> float32 conversion)."""
    return v.astype(jnp.int32).astype(jnp.float32)


def _box_muller(bits, sigma2):
    """One N(0, σ²) draw per uint32 word (two u16 halves -> Box-Muller)."""
    hi = _u32_to_f32(bits >> 16)
    lo = _u32_to_f32(bits & jnp.uint32(0xFFFF))
    u1 = (hi + 1.0) * (1.0 / 65536.0)     # (0, 1]: log-safe
    u2 = lo * (1.0 / 65536.0)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(TWO_PI * u2) * jnp.sqrt(sigma2)


def _bits_mask(bits, p_pass, off):
    """Inverse-CDF mask draw (eq. 7): the estimator never consumes H
    itself (channel inversion cancels it on passing entries), and
    1{|H|² ≥ H_th} is exactly Bernoulli(p_pass) — sampled here as
    u < p_pass on the word's top 24 bits (an exact f32 uniform in
    [0, 1)). ``p_pass`` is the cluster's scalar pass probability
    (``ref.pass_probability``, computed by the wrappers). Matches
    ref.bits_to_mask."""
    u = _u32_to_f32(bits >> 8) * jnp.float32(2.0 ** -24)
    return jnp.logical_or(u < p_pass, off)


def _pick_block_rows(rows: int, n_slabs: int,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = False, width: int = LANE) -> int:
    """Largest row-block <= block_rows dividing ``rows`` that keeps
    ``n_slabs`` concurrent (block, width) f32 buffers under the VMEM
    budget.

    Interpret mode has no VMEM: one whole-slab grid step avoids the
    interpreter's per-block copy overhead (~10x on the 1M-param slab).
    """
    if interpret:
        return rows
    cap = max(SUBLANE, VMEM_BUDGET_BYTES // (n_slabs * width * 4))
    br = min(block_rows, rows, cap - cap % SUBLANE)
    br = max(SUBLANE, br - br % SUBLANE)
    while rows % br:
        br -= SUBLANE
    return br


# ---------------------------------------------------------------------------
# mask + weighted apply, mask count (distributed path)
# ---------------------------------------------------------------------------

def _ota_mask_weight_kernel(x_ref, bits_ref, params_ref, out_ref, mask_ref):
    """Weighted-einsum fold (DESIGN.md §3.10): out = M ∘ (w·x) in ONE pass.

    This is the slab-native distributed trunk's local kernel — the
    FedGradNorm weight w multiplies inside the masked apply, so the
    LAN/MAC psum consumes the kernel output directly (no separate p·g
    materialization). Masks use the same inverse-CDF law as the
    client-fold kernels (one compare per entry, matches ref.bits_to_mask
    on the identical bit stream)."""
    p_pass = params_ref[0, 0]
    ota_on = params_ref[0, 1]
    w = params_ref[0, 2]
    mask = _bits_mask(bits_ref[...], p_pass, ota_on < 0.5)
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.where(mask, w * x, 0.0)
    mask_ref[...] = mask.astype(mask_ref.dtype)


def ota_mask_weight_pallas(
    x: jax.Array,            # (rows, 128) slab
    bits: jax.Array,         # (rows, 128) uint32
    params: jax.Array,       # (1, 3) f32: [p_pass, ota_on, w] (traced)
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
):
    """Fused mask + weighted apply. Returns (M∘(w·x), M) as f32 slabs."""
    rows, lane = x.shape
    assert lane == LANE, x.shape
    br = _pick_block_rows(rows, 4, block_rows, interpret)
    grid = (rows // br,)

    out, mask = pl.pallas_call(
        _ota_mask_weight_kernel,
        grid=grid,
        name="ota_mask_weight",
        in_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(x, bits, params.astype(jnp.float32))
    return out, mask


def _ota_mask_count_kernel(x_ref, bits_ref, params_ref, out_ref, cnt_ref,
                           *, n_clusters):
    """Slab-native local channel work (DESIGN.md §3.10): from the
    counter-based per-cluster bit streams, compute in ONE pass
    out = M_me ∘ (w·x) (this device's masked weighted gradient) and
    cnt = Σ_l M_l (the |M| count — every cluster's mask is a pure
    function of the streams, so the count needs NO collective). The
    per-cluster ``live`` flags (DESIGN.md §3.14) AND into the masks
    after the ``ota_on`` all-pass gate; all-ones = bit-exact legacy."""
    c = n_clusters
    ota_on = params_ref[0, c]
    w = params_ref[0, c + 1]
    me = params_ref[0, c + 2]
    off = ota_on < 0.5
    x = x_ref[...].astype(jnp.float32)
    out = jnp.zeros_like(x)
    cnt = jnp.zeros_like(x)
    for l in range(n_clusters):              # static unrolled cluster loop
        live_l = params_ref[0, c + 3 + l]
        mask = jnp.logical_and(
            _bits_mask(bits_ref[l], params_ref[0, l], off),
            live_l >= 0.5)
        cnt = cnt + mask.astype(jnp.float32)
        mine = jnp.logical_and(mask, me == jnp.float32(l))
        out = out + jnp.where(mine, w * x, 0.0)
    out_ref[...] = out
    cnt_ref[...] = cnt


def ota_mask_count_pallas(
    x: jax.Array,            # (rows, 128) slab
    bits: jax.Array,         # (C, rows, 128) uint32 — per-cluster streams
    params: jax.Array,       # (1, 2C+3): [p_pass_·, ota_on, w, me, live_·]
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
):
    """Fused M_me∘(w·x) + Σ_l M_l. Returns (out, cnt) as f32 slabs."""
    n_clusters, rows, lane = bits.shape
    assert lane == LANE and x.shape == (rows, LANE), (bits.shape, x.shape)
    assert params.shape == (1, 2 * n_clusters + 3), params.shape
    br = _pick_block_rows(rows, n_clusters + 3, block_rows, interpret)
    grid = (rows // br,)

    kernel = functools.partial(_ota_mask_count_kernel,
                               n_clusters=n_clusters)
    out, cnt = pl.pallas_call(
        kernel,
        grid=grid,
        name="ota_mask_count",
        in_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((n_clusters, br, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((1, 2 * n_clusters + 3), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(x, bits, params.astype(jnp.float32))
    return out, cnt


def _client_fold_block(x_of, bits_of, params_ref, n_clusters, n_clients,
                       acc, cnt):
    """Fold ``n_clusters`` clusters' masked, client-weighted gradients
    into (acc, cnt), in cluster order. ``x_of(l, i)`` is client i of
    cluster l's gradient block and ``bits_of(l)`` cluster l's gain
    words, both shaped like ``acc``. The params row is [p_pass_·,
    w_··, z_std, ota_on, live_·, N_eff] over these clusters."""
    c, n = n_clusters, n_clients
    off = params_ref[0, c + c * n + 1] < 0.5     # traced error-free gate
    for l in range(c):                       # static unrolled cluster loop
        wg = jnp.zeros_like(acc)
        for i in range(n):                   # eq. 3: Σ_n p[l,n]·g[l,n]
            wg = wg + params_ref[0, c + l * n + i] * (
                x_of(l, i).astype(jnp.float32))
        live_l = params_ref[0, c + c * n + 2 + l]
        mask = jnp.logical_and(
            _bits_mask(bits_of(l), params_ref[0, l], off), live_l >= 0.5)
        acc = acc + jnp.where(mask, wg, 0.0)
        cnt = cnt + mask.astype(jnp.float32)
    return acc, cnt


def _client_finish(acc, cnt, nbits, params_ref, n_clusters, n_clients):
    """AWGN + the guarded |M|·N_eff estimate (eqs. 8-10)."""
    base = n_clusters + n_clusters * n_clients
    noise_std = params_ref[0, base]
    ota_on = params_ref[0, base + 1]
    n_eff = params_ref[0, base + 2 + n_clusters]
    y = acc + _box_muller(nbits, 1.0) * noise_std * ota_on
    return jnp.where(
        cnt > 0, y / (jnp.maximum(cnt, 1.0) * jnp.maximum(n_eff, 1.0)), 0.0)


def _ota_aggregate_client_kernel(x_ref, bits_ref, nbits_ref, params_ref,
                                 out_ref, *, n_clusters, n_clients):
    """Client-folded PS estimator (DESIGN.md §3.12): the MAC loop computes
    Σ_l M_l ∘ (Σ_n p[l,n]·x[l,n]) IN BLOCK from the raw (C, N, ·) gradient
    slab and the (C, N) loss-weight matrix — eqs. 3 + 8-10 in one pass;
    neither the client-weighted tree nor a (C, P) pack copy exists. The
    weight matrix rides the params block after the per-cluster pass
    probabilities; the per-cluster ``live`` flags and the traced N_eff
    denominator (DESIGN.md §3.14) ride after the scalars — live ANDs into
    the masks AFTER the ``ota_on`` all-pass gate, and live=ones/n_eff=N
    is the bit-exact full-participation identity."""
    zeros = jnp.zeros(out_ref.shape, jnp.float32)
    acc, cnt = _client_fold_block(lambda l, i: x_ref[l, i],
                                  lambda l: bits_ref[l], params_ref,
                                  n_clusters, n_clients, zeros, zeros)
    out_ref[...] = _client_finish(acc, cnt, nbits_ref[...], params_ref,
                                  n_clusters, n_clients)


def _ota_aggregate_client_cblk_kernel(x_ref, bits_ref, nbits_ref, params_ref,
                                      out_ref, acc_ref, cnt_ref, *,
                                      cb, n_clients):
    """C-axis-blocked client-folded estimator (ROADMAP: large cluster
    counts). Grid is (row_blocks, cluster_blocks) with the cluster axis
    minor: each step accumulates ``cb`` clusters' masked contributions
    into VMEM scratch SEQUENTIALLY — the same accumulation ORDER as the
    unblocked kernel, so results agree to fusion level (XLA may contract
    mul+add into FMA differently around the scratch round-trip; ~1 ulp,
    pinned in tests/test_sectioned.py). The last cluster block adds AWGN
    and finishes the guarded estimate. The per-block params row carries
    that block's p_pass/w/live slices (padded tail clusters arrive
    live=0, so they contribute nothing)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    acc_ref[...], cnt_ref[...] = _client_fold_block(
        lambda l, i: x_ref[l, i], lambda l: bits_ref[l], params_ref, cb,
        n_clients, acc_ref[...], cnt_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = _client_finish(acc_ref[...], cnt_ref[...],
                                      nbits_ref[...], params_ref, cb,
                                      n_clients)


def _client_cluster_block(n_clusters: int, n_clients: int,
                          interpret: bool) -> int:
    """Largest cluster block whose (cb·(N+1)+2) concurrent SUBLANE-row
    buffers fit the VMEM budget — n_clusters (one block, the fast
    unblocked kernel) whenever it fits."""
    if interpret:
        return n_clusters
    unit = SUBLANE * LANE * 4
    cb = max(1, (VMEM_BUDGET_BYTES // unit - 2) // (n_clients + 1))
    return min(n_clusters, cb)


def _client_params_blocked(params, n_clusters, n_clients, cb, n_cb):
    """Re-tile the (1, C(N+2)+3) client params row into (n_cb, cb(N+2)+3)
    per-cluster-block rows of the SAME layout (p_pass, w, scalars, live,
    N_eff), padding the tail block's clusters with live=0."""
    c, n = n_clusters, n_clients
    pad = n_cb * cb - c
    p_pass = jnp.pad(params[0, :c], (0, pad))
    w = jnp.pad(params[0, c:c + c * n].reshape(c, n), ((0, pad), (0, 0)))
    live = jnp.pad(params[0, c + c * n + 2:c + c * n + 2 + c], (0, pad))
    scal = jnp.broadcast_to(params[0, c + c * n:c + c * n + 2].reshape(1, 2),
                            (n_cb, 2))
    n_eff = jnp.broadcast_to(params[0, -1].reshape(1, 1), (n_cb, 1))
    return jnp.concatenate([
        p_pass.reshape(n_cb, cb), w.reshape(n_cb, cb * n), scal,
        live.reshape(n_cb, cb), n_eff], axis=1)


def ota_aggregate_client_pallas(
    x: jax.Array,            # (C, N, rows, 128) f32 — RAW per-client grads
    bits: jax.Array,         # (C, rows, 128) uint32 — gain bits per cluster
    nbits: jax.Array,        # (rows, 128) uint32 — AWGN bits
    params: jax.Array,       # (1, C·(N+2)+3):
                             #   [p_pass_·, w_··, z_std, ota_on, live_·, N_eff]
    *,
    n_clients: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
    cluster_block: int = 0,  # 0 = auto; tests force small blocks
) -> jax.Array:
    """Fused client-folded OTA aggregation for one leaf/section slab.

    Returns the (rows, 128) PS estimate ĝ. The caller supplies the bit
    streams (the chunk-quantized key schedule lives in ``repro.core.ota``
    — under a scenario vmap the draw depends only on the shared key and
    hoists out of the scenario axis). At large cluster counts the C·N
    concurrent VMEM blocks outgrow the budget faster than row blocking
    can shrink them, so the call auto-switches to the C-axis-blocked
    kernel (scratch accumulation over cluster blocks in the same float
    order — equal to fusion level, validated in interpret mode)."""
    n_clusters, n_cl, rows, lane = x.shape
    assert lane == LANE and n_cl == n_clients, (x.shape, n_clients)
    assert bits.shape == (n_clusters, rows, LANE), (bits.shape, x.shape)
    assert nbits.shape == (rows, LANE), nbits.shape
    assert params.shape == (1, n_clusters * (n_clients + 2) + 3), params.shape
    cb = (cluster_block if cluster_block
          else _client_cluster_block(n_clusters, n_clients, interpret))
    if cb < n_clusters:
        n_cb = pl.cdiv(n_clusters, cb)
        # cb·N grad blocks + cb bits blocks + noise + out + 2 scratch
        br = _pick_block_rows(rows, cb * (n_clients + 1) + 4,
                              block_rows, interpret)
        kernel = functools.partial(_ota_aggregate_client_cblk_kernel,
                                   cb=cb, n_clients=n_clients)
        from jax.experimental.pallas import tpu as pltpu
        return pl.pallas_call(
            kernel,
            grid=(rows // br, n_cb),
            name="ota_client_fold_cblk",
            in_specs=[
                pl.BlockSpec((cb, n_clients, br, LANE),
                             lambda i, j: (j, 0, i, 0)),
                pl.BlockSpec((cb, br, LANE), lambda i, j: (j, i, 0)),
                pl.BlockSpec((br, LANE), lambda i, j: (i, 0)),
                # one (1, K) row per cluster block: the leading block dim
                # is squeezed so the last two dims span the whole array
                pl.BlockSpec((None, 1, cb * (n_clients + 2) + 3),
                             lambda i, j: (j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((br, LANE), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            scratch_shapes=[pltpu.VMEM((br, LANE), jnp.float32),
                            pltpu.VMEM((br, LANE), jnp.float32)],
            interpret=interpret,
        )(x, bits, nbits,
          _client_params_blocked(params.astype(jnp.float32), n_clusters,
                                 n_clients, cb, n_cb)[:, None, :])

    # C·N grad blocks + C bits blocks + noise + out resident at once
    br = _pick_block_rows(rows, n_clusters * (n_clients + 1) + 2,
                          block_rows, interpret)
    grid = (rows // br,)

    kernel = functools.partial(_ota_aggregate_client_kernel,
                               n_clusters=n_clusters, n_clients=n_clients)
    return pl.pallas_call(
        kernel,
        grid=grid,
        name="ota_client_fold",
        in_specs=[
            pl.BlockSpec((n_clusters, n_clients, br, LANE),
                         lambda i: (0, 0, i, 0)),
            pl.BlockSpec((n_clusters, br, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, n_clusters * (n_clients + 2) + 3),
                         lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(x, bits, nbits, params.astype(jnp.float32))


# words per in-kernel draw tile, 8 vregs per operand: the threefry chain
# of one tile's stream stays near the registers while the tile loop walks
# the block (64 rows of 128 timed fastest of 8-128 rows on one v5e at the
# largest paper leaf). A native block's tile holds as many words in
# (rows, bc): (8, 1024) at the paper's fc2.w.
DRAW_TILE_WORDS = 64 * LANE
# widest column block of the native path: its draw tile keeps 8 rows
DRAW_TILE_COLS = DRAW_TILE_WORDS // SUBLANE


def _draw_tile_rows(bc: int) -> int:
    """Most rows of a draw tile over ``bc`` columns: DRAW_TILE_WORDS
    words, never fewer than one sublane group."""
    t = DRAW_TILE_WORDS // bc
    return max(SUBLANE, t - t % SUBLANE)


def _native_cols(b: int) -> int:
    """Column block of a native (a, b) leaf: the widest multiple of LANE
    up to DRAW_TILE_COLS that divides ``b``."""
    bc = min(b, DRAW_TILE_COLS)
    while b % bc:
        bc -= LANE
    return bc


def drawn_native(shape) -> bool:
    """Whether the drawing kernel reads a leaf of this per-client shape in
    its own layout: two dims (a, b) of whole (8, 128) tiles (``a % 8 ==
    0``, ``b % 128 == 0``) whose draw tile spans at most one chunk of
    positions, so it touches at most two chunks. Other leaves take the
    flat (rows, 128) view."""
    if len(shape) != 2 or shape[0] % SUBLANE or shape[1] % LANE:
        return False
    b = shape[1]
    bc = _native_cols(b)
    return (_draw_tile_rows(bc) - 1) * b + bc <= CHUNK_ROWS * LANE


def _ota_client_fold_drawn_kernel(x_ref, keys_ref, params_ref, out_ref,
                                  words_ref, *, n_clusters, n_clients,
                                  n_chunks, word0, stride, words):
    """Client-folded PS estimator that computes its channel words itself
    (DESIGN.md §4, position form): word m of chunk j of a stream is
    ``words(k0, k1, m)`` under that chunk's key, so each element's gain
    and noise words come from its stream position and no word is read
    from HBM. ``keys_ref`` (SMEM, flat) holds the (k0, k1) chunk keys of
    the C gain streams and the noise stream over the leaf's chunks.
    Element (r, c) of the (a, ``stride``) leaf sits at position
    ``word0 + r·stride + c`` counted from the first of them (the flat
    view is stride 128). A draw tile spans fewer positions than a chunk
    (``drawn_native``), so it touches at most two chunks: each element
    picks the first or the next key. Per tile a loop over the C+1
    streams (one threefry in the program, not C+1) fills ``words_ref``
    (VMEM); the mask, MAC and estimate are the supplied-words kernel's,
    unchanged."""
    br, bc = out_ref.shape
    tile = words_ref.shape[1]
    shift = CHUNK_ROWS.bit_length() - 1 + LANE.bit_length() - 1
    base = (word0 + pl.program_id(0) * (br * stride)
            + pl.program_id(1) * bc)
    tile_pos = (jax.lax.broadcasted_iota(jnp.int32, (tile, bc), 0) * stride
                + jax.lax.broadcasted_iota(jnp.int32, (tile, bc), 1))

    def body(t, carry):
        r0 = pl.multiple_of(t * tile, tile)
        p0 = base + r0 * stride
        pos = p0 + tile_pos
        m = (pos & ((1 << shift) - 1)).astype(jnp.uint32)
        ja = p0 >> shift
        jb = jnp.minimum(ja + 1, n_chunks - 1)
        first = (pos >> shift) == ja

        def draw(s, c):
            k0, k1 = (jnp.where(first, keys_ref[(s * n_chunks + ja) * 2 + h],
                                keys_ref[(s * n_chunks + jb) * 2 + h])
                      for h in (0, 1))
            words_ref[s] = words(k0, k1, m)
            return c

        jax.lax.fori_loop(0, n_clusters + 1, draw, 0)
        rows = pl.ds(r0, tile)
        zeros = jnp.zeros((tile, bc), jnp.float32)
        acc, cnt = _client_fold_block(lambda l, i: x_ref[l, i, rows, :],
                                      lambda l: words_ref[l], params_ref,
                                      n_clusters, n_clients, zeros, zeros)
        out_ref[rows, :] = _client_finish(acc, cnt, words_ref[n_clusters],
                                          params_ref, n_clusters, n_clients)
        return carry

    jax.lax.fori_loop(0, br // tile, body, 0)


def ota_client_fold_drawn_pallas(
    x: jax.Array,            # (C, N, a, b) f32 — RAW per-client grads
    keys: jax.Array,         # ((C+1)·n_chunks·2,) uint32 chunk keys
    params: jax.Array,       # (1, C·(N+2)+3), as ota_aggregate_client_pallas
    *,
    word0: int,
    words,
    n_clients: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
    name: str = "ota_client_fold_drawn",
) -> jax.Array:
    """``ota_aggregate_client_pallas`` with the channel words drawn in
    the kernel; returns the (a, b) PS estimate. ``keys`` is the flat
    table of chunk keys, stream-major (C gain streams, then the noise
    stream), chunk, then (k0, k1); ``word0`` (static) is the position of
    element (0, 0) in the first chunk; ``words(k0, k1, m)`` is the
    stream's word formula (``repro.core.ota.stream_words``).

    ``x`` is any (C, N, a, b) block of whole (8, 128) tiles
    (``drawn_native``): a 2-D leaf in its own layout, or a leaf's flat
    (rows, 128) view. It is read in (br, bc) blocks, bc the widest LANE
    multiple up to DRAW_TILE_COLS dividing b, br at most ``block_rows``
    with the C·N + 2 resident blocks under the VMEM budget (one whole
    block in interpret mode). ``name`` names the call, so a trace tells
    the layouts apart (``ops.ota_client_fold_drawn_apply``). Operand 0
    stays the gradient block and the table a plain uint32 operand in
    SMEM, so a trace reads the call as a kernel that takes channel words.
    Callers check ``_client_cluster_block`` first: the drawing kernel has
    no C-blocked variant."""
    n_clusters, n_cl, a, b = x.shape
    assert n_cl == n_clients, (x.shape, n_clients)
    assert drawn_native((a, b)), x.shape
    assert params.shape == (1, n_clusters * (n_clients + 2) + 3), params.shape
    n_chunks = keys.shape[0] // (2 * (n_clusters + 1))
    assert keys.shape == (2 * (n_clusters + 1) * n_chunks,), keys.shape
    assert 0 <= word0 and word0 + a * b <= n_chunks * CHUNK_ROWS * LANE
    from jax.experimental.pallas import tpu as pltpu
    bc = _native_cols(b)
    # C·N grad blocks + out resident at once; the words never leave the core
    br = _pick_block_rows(a, n_clusters * n_clients + 2, block_rows,
                          interpret, width=bc)
    kernel = functools.partial(
        _ota_client_fold_drawn_kernel, n_clusters=n_clusters,
        n_clients=n_clients, n_chunks=n_chunks, word0=word0, stride=b,
        words=words)
    return pl.pallas_call(
        kernel,
        grid=(a // br, b // bc),
        name=name,
        in_specs=[
            pl.BlockSpec((n_clusters, n_clients, br, bc),
                         lambda i, j: (0, 0, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_clusters * (n_clients + 2) + 3),
                         lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM(
            (n_clusters + 1, math.gcd(br, _draw_tile_rows(bc)), bc),
            jnp.uint32)],
        interpret=interpret,
    )(x, keys.astype(jnp.uint32), params.astype(jnp.float32))
