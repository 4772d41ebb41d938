"""Jit'd public wrappers for the ota_channel kernel package.

``ota_channel(x, key, sigma2, h_th)`` accepts an arbitrary-shape slab,
pads/reshapes it to the kernels' (rows, 128) layout (shared helper in
``repro.kernels.slab``), draws the uniform bits with JAX's counter-based
threefry (cheap, fused by XLA), and invokes the Pallas kernel (interpret
mode on CPU — this container has no TPU).

``ota_aggregate(wg, bits, nbits, sigma2, ...)`` is the flat-packed whole-
model aggregation (eqs. 8-10): the caller supplies the lane-aligned
(C, P) weighted-grad slab and bit streams (see ``repro.core.ota``'s
packed path, which owns the key schedule), and one fused kernel returns
the (P,) PS estimate. All channel knobs are traced, so ``ScenarioBank``
vmaps over them freely.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ota_channel.kernel import (
    drawn_native, ota_aggregate_client_pallas, ota_aggregate_fused_pallas,
    ota_aggregate_pallas, ota_channel_pallas, ota_client_fold_drawn_pallas,
    ota_mask_count_pallas, ota_mask_weight_pallas,
)
from repro.kernels.ota_channel.ref import (
    bits_to_mask, ota_aggregate_client_ref, ota_aggregate_slab_ref,
    ota_channel_ref, ota_stream_fold_ref, pass_probability,
)
from repro.kernels.slab import (
    LANE, ROW_QUANTUM, flat_to_slab, on_tpu, pad_to_lanes,
)


def _ota_channel_impl(slab, bits, sigma2, h_th, ota_on, interpret: bool):
    """Un-jitted mask+apply on a (rows, 128) slab — the single home for
    the (1, 3) params-block layout (also used by the packed final gather
    in repro.core.hota, so the two call sites can never diverge)."""
    params = jnp.stack([jnp.asarray(sigma2, jnp.float32).reshape(()),
                        jnp.asarray(h_th, jnp.float32).reshape(()),
                        jnp.asarray(ota_on, jnp.float32).reshape(())])
    return ota_channel_pallas(slab, bits, params.reshape(1, 3),
                              interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def ota_channel(x: jax.Array, key: jax.Array, sigma2, h_th,
                ota_on=1.0, interpret: bool = None):
    """Fused channel mask+apply. Returns (masked_x, mask) shaped like x.

    All channel knobs (σ², H_th, the ota_on gate) are traced — one
    compiled kernel serves every scenario. ``interpret=None`` resolves
    the platform at trace time (compiled on TPU, interpret elsewhere) —
    never baked at import, so late backend selection dispatches right.
    """
    if interpret is None:
        interpret = not on_tpu()
    slab, n = pad_to_lanes(x)
    bits = jax.random.bits(key, slab.shape, jnp.uint32)
    out, mask = _ota_channel_impl(slab, bits, sigma2, h_th, ota_on,
                                  interpret)
    out = out.reshape(-1)[:n].reshape(x.shape)
    mask = mask.reshape(-1)[:n].reshape(x.shape)
    return out, mask


def ota_mask_weight_apply(x: jax.Array, bits: jax.Array, sigma2, h_th,
                          ota_on, weight,
                          interpret: bool = None,
                          impl: str = None):
    """Zero-copy fused mask + weighted apply for ONE leaf (DESIGN.md §3.10).

    ``x`` is consumed through a reshape of its own storage — no slab is
    packed: the LANE-aligned main body (a ROW_QUANTUM multiple) runs the
    ``ota_mask_weight_pallas`` kernel in place and the < ROW_QUANTUM
    ragged remainder takes the jnp reference on the SAME pre-sliced bit
    stream (``bits`` is the leaf's static slice of its section stream —
    see ``repro.common.flatpack.TreePacker.leaf_runs``). Returns
    (M ∘ (w·x), M) shaped like ``x``, both f32. This is the weighted-
    einsum fold: the FedGradNorm weight multiplies inside the kernel, so
    the caller's psum consumes the output directly.

    ``impl``: "pallas" | "jnp". Default: "pallas" on TPU (the compiled
    kernel), "jnp" elsewhere — per-device there is no cluster axis to
    fuse over, so on CPU the interpret-mode pallas_call is pure dispatch
    overhead while the jnp form computes the identical values
    (bit-equality pinned in tests/test_slab_native.py) AND fuses with
    the adjacent psums. Tests force ``impl="pallas"`` + interpret to
    validate the kernel itself.
    """
    if interpret is None:
        interpret = not on_tpu()
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    n = int(x.size)
    assert bits.shape == (n,), (bits.shape, n)
    flat = x.reshape(-1).astype(jnp.float32)
    w = jnp.asarray(weight, jnp.float32)
    if impl == "jnp":
        m = bits_to_mask(bits, sigma2, h_th, ota_on)
        out = jnp.where(m, w * flat, 0.0)
        return out.reshape(x.shape), m.astype(jnp.float32).reshape(x.shape)
    main = n - n % ROW_QUANTUM
    outs, masks = [], []
    if main:
        params = jnp.stack([
            pass_probability(sigma2, h_th).reshape(()),
            jnp.asarray(ota_on, jnp.float32).reshape(()),
            w.reshape(())]).reshape(1, 3)
        o, m = ota_mask_weight_pallas(
            jax.lax.slice(flat, (0,), (main,)).reshape(main // LANE, LANE),
            jax.lax.slice(bits, (0,), (main,)).reshape(main // LANE, LANE),
            params, interpret=interpret)
        outs.append(o.reshape(main))
        masks.append(m.reshape(main))
    if n - main:
        m = bits_to_mask(jax.lax.slice(bits, (main,), (n,)), sigma2, h_th,
                         ota_on)
        x_rem = jax.lax.slice(flat, (main,), (n,))
        outs.append(jnp.where(m, w * x_rem, 0.0))
        masks.append(m.astype(jnp.float32))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    mask = masks[0] if len(masks) == 1 else jnp.concatenate(masks)
    return out.reshape(x.shape), mask.reshape(x.shape)


def _client_params_row(p32, sig, h_th, noise_std, ota_on, live, n_eff,
                       n_clients: int):
    """The client-fold kernels' (1, C·(N+2)+3) row: [p_pass_·, w_··,
    z_std, ota_on, live_·, N_eff]; live=None / n_eff=None give the
    full-participation identity (ones, N)."""
    n_clusters = sig.shape[0]
    live_v = (jnp.ones((n_clusters,), jnp.float32) if live is None
              else jnp.asarray(live, jnp.float32).reshape(n_clusters))
    n_eff_v = (jnp.float32(n_clients) if n_eff is None
               else jnp.maximum(jnp.asarray(n_eff, jnp.float32), 1.0)
               .reshape(()))
    return jnp.concatenate([
        pass_probability(sig, h_th),
        p32.reshape(n_clusters * n_clients),
        jnp.stack([jnp.asarray(noise_std, jnp.float32).reshape(()),
                   jnp.asarray(ota_on, jnp.float32).reshape(())]),
        live_v,
        n_eff_v.reshape(1),
    ]).reshape(1, n_clusters * (n_clients + 2) + 3)


def ota_client_fold_apply(g: jax.Array, p: jax.Array, bits: jax.Array,
                          nbits: jax.Array, sigma2, h_th, noise_std, ota_on,
                          n_clients: int,
                          live=None, n_eff=None,
                          interpret: bool = None,
                          impl: str = None):
    """Zero-copy client-folded OTA aggregation for ONE leaf (DESIGN.md
    §3.12): ĝ = guard(Σ_l M_l ∘ (Σ_n p[l,n]·g[l,n]) + z), eqs. 3 + 8-10
    in one pass from the RAW (C, N, *shape) gradient leaf and the (C, N)
    loss-weight matrix — the client-weighted tree is never materialized.

    ``g`` is consumed through a reshape of its own storage: the
    LANE-aligned main body runs the ``ota_aggregate_client_pallas``
    kernel in place, the < ROW_QUANTUM ragged remainder takes the jnp
    reference on the SAME pre-sliced streams (``bits``/``nbits`` are the
    leaf's static slices of its section streams — see
    ``repro.common.flatpack.TreePacker.leaf_runs``). Returns the
    (*shape,) f32 PS estimate.

    ``impl``: "pallas" | "jnp". Default: "pallas" on TPU (the compiled
    kernel), "jnp" elsewhere — on CPU the interpret-mode pallas_call is
    pure dispatch overhead while the jnp form computes the identical
    values (pinned in tests/test_client_folded.py) AND lets XLA fuse the
    weight fold with the masked sum. Tests force ``impl="pallas"`` +
    interpret to validate the kernel itself.

    ``live`` (C,) / ``n_eff`` () inject partial participation
    (DESIGN.md §3.14): live ANDs into the cluster masks after the
    ``ota_on`` all-pass gate, n_eff replaces the static N denominator.
    None keeps the full-participation math bit-exact (the kernel is fed
    the identity values live=ones, n_eff=N).
    """
    if interpret is None:
        interpret = not on_tpu()
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    n_clusters, n_cl = g.shape[:2]
    assert n_cl == n_clients, (g.shape, n_clients)
    shape = g.shape[2:]
    n = int(g.size) // (n_clusters * n_clients)
    assert bits.shape == (n_clusters, n) and nbits.shape == (n,), \
        (bits.shape, nbits.shape, n)
    flat = g.reshape(n_clusters, n_clients, n)
    p32 = jnp.asarray(p, jnp.float32).reshape(n_clusters, n_clients)
    sig = jnp.asarray(sigma2, jnp.float32).reshape(n_clusters)
    if impl == "jnp":
        out = ota_aggregate_client_ref(flat, p32, bits, nbits, sig, h_th,
                                       noise_std, ota_on, n_clients,
                                       live=live, n_eff=n_eff)
        return out.reshape(shape)
    params = _client_params_row(p32, sig, h_th, noise_std, ota_on, live,
                                n_eff, n_clients)
    main = n - n % ROW_QUANTUM
    outs = []
    if main:
        rows = main // LANE
        o = ota_aggregate_client_pallas(
            jax.lax.slice(flat, (0, 0, 0), (n_clusters, n_clients, main))
            .astype(jnp.float32).reshape(n_clusters, n_clients, rows, LANE),
            jax.lax.slice(bits, (0, 0), (n_clusters, main))
            .reshape(n_clusters, rows, LANE),
            jax.lax.slice(nbits, (0,), (main,)).reshape(rows, LANE),
            params, n_clients=n_clients, interpret=interpret)
        outs.append(o.reshape(main))
    if n - main:
        outs.append(ota_aggregate_client_ref(
            jax.lax.slice(flat, (0, 0, main), (n_clusters, n_clients, n)),
            p32,
            jax.lax.slice(bits, (0, main), (n_clusters, n)),
            jax.lax.slice(nbits, (main,), (n,)),
            sig, h_th, noise_std, ota_on, n_clients,
            live=live, n_eff=n_eff))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out.reshape(shape)


def ota_client_fold_drawn_apply(g: jax.Array, p: jax.Array, keys: jax.Array,
                                word0: int, words, sigma2, h_th, noise_std,
                                ota_on, n_clients: int, live=None,
                                n_eff=None, interpret: bool = None):
    """``ota_client_fold_apply``'s kernel path with the channel words
    computed in the kernel (``ota_client_fold_drawn_pallas``): the same
    ĝ from the RAW (C, N, *shape) gradient leaf, whose element count must
    be a ROW_QUANTUM multiple. A leaf of whole (8, 128) tiles
    (``drawn_native``) is read in its own (a, b) layout, in a call named
    ``ota_client_fold_drawn2d``; any other takes the flat (rows, 128)
    view, named ``ota_client_fold_drawn``. ``keys`` is the leaf's flat
    chunk-key table (C gain streams, then noise), ``word0`` the leaf's
    first stream position counted from the table's first chunk, ``words``
    the word formula. Returns the (*shape,) f32 PS estimate."""
    if interpret is None:
        interpret = not on_tpu()
    n_clusters, n_cl = g.shape[:2]
    assert n_cl == n_clients, (g.shape, n_clients)
    shape = g.shape[2:]
    n = int(g.size) // (n_clusters * n_clients)
    assert n % ROW_QUANTUM == 0, (g.shape, ROW_QUANTUM)
    p32 = jnp.asarray(p, jnp.float32).reshape(n_clusters, n_clients)
    sig = jnp.asarray(sigma2, jnp.float32).reshape(n_clusters)
    params = _client_params_row(p32, sig, h_th, noise_std, ota_on, live,
                                n_eff, n_clients)
    x, name = g.astype(jnp.float32), "ota_client_fold_drawn2d"
    if not drawn_native(shape):
        x = x.reshape(n_clusters, n_clients, n // LANE, LANE)
        name = "ota_client_fold_drawn"
    out = ota_client_fold_drawn_pallas(
        x, keys, params, word0=word0, words=words, n_clients=n_clients,
        interpret=interpret, name=name)
    return out.reshape(shape)


def ota_stream_fold_apply(g: jax.Array, p_c: jax.Array, bits: jax.Array,
                          sigma2_c, h_th, ota_on,
                          live_c=None,
                          interpret: bool = None,
                          impl: str = None):
    """Zero-copy streaming fold for ONE (leaf, cluster) pair (DESIGN.md
    §3.15): returns (M ∘ (Σ_n p[n]·g[n]), M) shaped like ``g[0]``, both
    f32 — the per-cluster term the streaming aggregator adds into its
    running sum. ``bits`` is this cluster's pre-sliced section stream
    (``stream_range_bits``), so the values are byte-identical to what
    the all-at-once client-folded path applies at the same positions.

    ``impl``: "pallas" | "jnp". Default: "pallas" on TPU, "jnp"
    elsewhere (same dispatch rationale as ``ota_client_fold_apply``).
    The pallas branch folds the (N,) weights with one einsum and runs
    the fused ``ota_mask_weight_pallas`` MAC kernel on the result — the
    same mask+apply loop the distributed per-leaf path uses — then
    scales both outputs by ``live_c`` (a {0,1} flag, so multiplying
    equals ANDing it into the mask)."""
    if interpret is None:
        interpret = not on_tpu()
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    n_cl = g.shape[0]
    shape = g.shape[1:]
    n = int(g.size) // n_cl
    assert bits.shape == (n,), (bits.shape, n)
    flat = g.reshape(n_cl, n).astype(jnp.float32)
    p32 = jnp.asarray(p_c, jnp.float32).reshape(n_cl)
    if impl == "jnp":
        y, cnt = ota_stream_fold_ref(flat, p32, bits, sigma2_c, h_th,
                                     ota_on, live_c=live_c)
        return y.reshape(shape), cnt.reshape(shape)
    wg = jnp.einsum("n,np->p", p32, flat)
    out, mask = ota_mask_weight_apply(wg, bits, sigma2_c, h_th, ota_on,
                                      1.0, interpret=interpret,
                                      impl="pallas")
    if live_c is not None:
        lv = jnp.asarray(live_c, jnp.float32).reshape(())
        lv = (lv > 0.5).astype(jnp.float32)
        out, mask = out * lv, mask * lv
    return out.reshape(shape), mask.reshape(shape)


def ota_mask_count_apply(x: jax.Array, bits_all: jax.Array, me, sigma2_all,
                         h_th, ota_on, weight,
                         live_all=None,
                         interpret: bool = None,
                         impl: str = None):
    """Slab-native local channel work for ONE leaf (DESIGN.md §3.10):
    returns (M_me ∘ (w·x), Σ_l M_l) shaped like ``x``, both f32.

    ``bits_all`` is the (C, n) stack of EVERY cluster's stream slice for
    this leaf — the masks are pure functions of the counter-based
    streams, so the |M| count is computed locally and the backward needs
    NO mask collective. ``me`` is this device's (traced) cluster index;
    the FedGradNorm weight folds into the apply (w·g·M in one pass).

    ``impl``: "pallas" | "jnp" — default "pallas" on TPU, "jnp"
    elsewhere (per-device elementwise work; in interpret mode the
    pallas_call is pure dispatch overhead while the jnp form computes
    identical values — pinned in tests/test_slab_native.py — and fuses
    with the adjacent psums).

    ``live_all`` (C,) injects cluster participation (DESIGN.md §3.14):
    dead clusters drop out of BOTH the |M| count and ``me``'s own mask,
    after the ``ota_on`` all-pass gate. None = all live (bit-exact).
    """
    if interpret is None:
        interpret = not on_tpu()
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    n = int(x.size)
    n_clusters = bits_all.shape[0]
    assert bits_all.shape == (n_clusters, n), (bits_all.shape, n)
    flat = x.reshape(-1).astype(jnp.float32)
    w = jnp.asarray(weight, jnp.float32)
    sig = jnp.asarray(sigma2_all, jnp.float32).reshape(n_clusters, 1)
    if impl == "jnp":
        masks = bits_to_mask(bits_all, sig, h_th, ota_on)   # (C, n)
        if live_all is not None:
            lv = jnp.asarray(live_all, jnp.float32).reshape(n_clusters, 1)
            masks = jnp.logical_and(masks, lv > 0.5)
        cnt = jnp.sum(masks.astype(jnp.float32), axis=0)
        mine = jnp.take(masks, me, axis=0)
        out = jnp.where(mine, w * flat, 0.0)
        return out.reshape(x.shape), cnt.reshape(x.shape)
    live_v = (jnp.ones((n_clusters,), jnp.float32) if live_all is None
              else jnp.asarray(live_all, jnp.float32).reshape(n_clusters))
    main = n - n % ROW_QUANTUM
    params = jnp.concatenate([
        pass_probability(sig.reshape(n_clusters), h_th),
        jnp.stack([jnp.asarray(ota_on, jnp.float32).reshape(()),
                   w.reshape(()),
                   jnp.asarray(me, jnp.float32).reshape(())]),
        live_v,
    ]).reshape(1, 2 * n_clusters + 3)
    outs, cnts = [], []
    if main:
        o, c = ota_mask_count_pallas(
            jax.lax.slice(flat, (0,), (main,)).reshape(main // LANE, LANE),
            jax.lax.slice(bits_all, (0, 0), (n_clusters, main)).reshape(
                n_clusters, main // LANE, LANE),
            params, interpret=interpret)
        outs.append(o.reshape(main))
        cnts.append(c.reshape(main))
    if n - main:
        b_rem = jax.lax.slice(bits_all, (0, main), (n_clusters, n))
        masks = bits_to_mask(b_rem, sig, h_th, ota_on)
        if live_all is not None:
            lv = jnp.asarray(live_all, jnp.float32).reshape(n_clusters, 1)
            masks = jnp.logical_and(masks, lv > 0.5)
        cnts.append(jnp.sum(masks.astype(jnp.float32), axis=0))
        mine = jnp.take(masks, me, axis=0)
        outs.append(jnp.where(
            mine, w * jax.lax.slice(flat, (main,), (n,)), 0.0))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    cnt = cnts[0] if len(cnts) == 1 else jnp.concatenate(cnts)
    return out.reshape(x.shape), cnt.reshape(x.shape)


@jax.jit
def ota_channel_reference(x: jax.Array, key: jax.Array, sigma2, h_th,
                          ota_on=1.0):
    """Oracle path on the same bit stream (for tests/benchmarks)."""
    slab, n = pad_to_lanes(x)
    bits = jax.random.bits(key, slab.shape, jnp.uint32)
    out, mask, _ = ota_channel_ref(slab, bits, sigma2, h_th, ota_on)
    return (out.reshape(-1)[:n].reshape(x.shape),
            mask.reshape(-1)[:n].reshape(x.shape))


def _channel_params_block(sigma2, h_th, noise_std, ota_on, c: int):
    """The aggregate kernels' (1, C+2) row: [p_pass_·, z_std, ota_on]."""
    return jnp.concatenate([
        pass_probability(jnp.asarray(sigma2, jnp.float32).reshape(c), h_th),
        jnp.asarray(noise_std, jnp.float32).reshape(1),
        jnp.asarray(ota_on, jnp.float32).reshape(1),
    ]).reshape(1, c + 2)


def _ota_aggregate_fused_impl(wg, section_keys, section_lens, sigma2, h_th,
                              noise_std, ota_on, n_clients: int,
                              interpret: bool, bits=None,
                              nbits=None) -> jax.Array:
    """In-kernel-RNG whole-model aggregation (the packed slab path).

    ``section_keys``: (S, 2, 2) uint32 threefry keys — [section][gain|awgn]
    for each of the packer's sections in layout order (the caller derives
    the folds from ``ota.packed_section_folds``); ``section_lens``: the
    matching static lengths. Each section runs its own kernel call
    (disjoint row ranges of the slab, disjoint chunk-quantized streams),
    so the FGN phase can re-draw just the ω̃ tail. The interpret-mode
    stream is reproducible outside the kernel (see
    repro.core.ota._section_bits); pass the pre-drawn ``bits``/``nbits``
    slabs (the identical stream) to hoist the RNG out of a scenario vmap
    (ScenarioBank's supplied mode).
    """
    c, p = wg.shape
    params = _channel_params_block(sigma2, h_th, noise_std, ota_on, c)
    keys = jnp.asarray(section_keys, jnp.uint32)
    wg32 = wg.astype(jnp.float32)
    outs, off = [], 0
    for s, length in enumerate(section_lens):
        if not length:
            continue
        sec = jax.lax.slice_in_dim(wg32, off, off + length, axis=1)
        kw = {}
        if bits is not None:
            kw = dict(
                bits=flat_to_slab(
                    jax.lax.slice_in_dim(bits, off, off + length, axis=1)),
                nbits=flat_to_slab(
                    jax.lax.slice_in_dim(nbits, off, off + length, axis=0)))
        out = ota_aggregate_fused_pallas(
            flat_to_slab(sec), keys[s], params,
            n_clients=n_clients, interpret=interpret, **kw)
        outs.append(out.reshape(length))
        off += length
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _ota_aggregate_impl(wg, bits, nbits, sigma2, h_th, noise_std, ota_on,
                        n_clients: int, interpret: bool) -> jax.Array:
    """Un-jitted body of ``ota_aggregate`` — callers inside a jit use this
    directly so slab prep fuses with the kernel."""
    c, p = wg.shape
    params = _channel_params_block(sigma2, h_th, noise_std, ota_on, c)
    out = ota_aggregate_pallas(
        flat_to_slab(wg.astype(jnp.float32)),
        flat_to_slab(bits),
        flat_to_slab(nbits),
        params,
        n_clients=n_clients,
        interpret=interpret,
    )
    return out.reshape(p)


@partial(jax.jit, static_argnames=("n_clients", "interpret"))
def ota_aggregate(
    wg: jax.Array,           # (C, P) f32 slab, P lane-aligned (packer layout)
    bits: jax.Array,         # (C, P) uint32 gain bits
    nbits: jax.Array,        # (P,) uint32 AWGN bits
    sigma2: jax.Array,       # (C,) traced per-cluster variance
    h_th, noise_std, ota_on,
    n_clients: int,
    interpret: bool = None,
) -> jax.Array:
    """Whole-model OTA aggregation (eqs. 8-10) in one fused kernel pass.

    Returns the (P,) PS estimate ĝ. Bit streams are the caller's (the
    packed key schedule lives in ``repro.core.ota``), so the jnp oracle
    ``ota_aggregate_reference`` consumes the identical stream.
    ``interpret=None`` resolves the platform at trace time.
    """
    if interpret is None:
        interpret = not on_tpu()
    return _ota_aggregate_impl(wg, bits, nbits, sigma2, h_th, noise_std,
                               ota_on, n_clients, interpret)


@partial(jax.jit, static_argnames=("n_clients",))
def ota_aggregate_reference(wg, bits, nbits, sigma2, h_th, noise_std, ota_on,
                            n_clients: int) -> jax.Array:
    """Oracle for ``ota_aggregate`` on the same bit stream."""
    return ota_aggregate_slab_ref(wg, bits, nbits, sigma2, h_th, noise_std,
                                  ota_on, n_clients)
