"""Pure-jnp oracles for the ota_channel kernel package.

Math (paper eqs. 3, 7-10): from counter-based uniform bits, draw per-entry
channel gains H ~ N(0, σ²) via Box-Muller, threshold |H|² ≥ H_th into the
sparsification mask M, and either apply it to one weighted-gradient slab
(``ota_channel_ref``) or run the whole PS estimator across the cluster
axis (``ota_aggregate_slab_ref``):

    y(j)  = Σ_{l∈M(j)} wg_l(j) + z(j)          (eq. 8, channel inverted)
    ĝ(j)  = y(j) / (|M_k(j)| · N), 0 if |M|=0  (eq. 10, guarded)

Bits are supplied by the caller (jax.random.bits), so kernel and oracle
consume the identical stream — outputs match bit-for-bit up to float
associativity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TWO_PI = 6.283185307179586


def bits_to_gaussian(bits: jax.Array, sigma2) -> jax.Array:
    """Box-Muller on the two u16 halves of each u32 word -> one N(0, σ²)."""
    hi = (bits >> 16).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.float32)
    # map to (0,1]: (k + 1) / 65536 keeps u1 away from 0 (log-safe)
    u1 = (hi + 1.0) * (1.0 / 65536.0)
    u2 = lo * (1.0 / 65536.0)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    h = r * jnp.cos(TWO_PI * u2)
    return h * jnp.sqrt(jnp.asarray(sigma2, jnp.float32))


def pass_probability(sigma2, h_th) -> jax.Array:
    """P(|H|² ≥ H_th) for H ~ N(0, σ²): erfc(√(H_th / 2σ²)) (eq. 7)."""
    sig2 = jnp.maximum(jnp.asarray(sigma2, jnp.float32), 1e-30)
    return jax.lax.erfc(jnp.sqrt(jnp.asarray(h_th, jnp.float32)
                                 / (2.0 * sig2)))


def bits_to_mask(bits: jax.Array, sigma2, h_th, ota_on=1.0) -> jax.Array:
    """eq. (7) from a bit stream by inverse-CDF thresholding: the
    estimator only ever consumes the MASK (channel inversion cancels H on
    passing entries), and 1{|H|² ≥ H_th} for H ~ N(0, σ²) is exactly
    Bernoulli(erfc(√(H_th/2σ²))) — so ``u < p_pass`` on the raw uniform
    draw is the identical distribution at one compare per entry instead
    of a Box-Muller log/sqrt/cos chain. The uniform is the word's top 24
    bits over 2²⁴ — exact in f32, and the form the compiled kernels use,
    so kernel and oracle masks agree bit for bit on the same stream.
    ``ota_on < 0.5`` forces all-pass.
    """
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(
        2.0 ** -24)
    p = pass_probability(sigma2, h_th)
    return jnp.logical_or(u < p, jnp.asarray(ota_on, jnp.float32) < 0.5)


def ota_channel_ref(x: jax.Array, bits: jax.Array, sigma2, h_th, ota_on=1.0):
    """x: any-shape slab; bits: same-shape uint32. Returns (masked_x, mask, gain)."""
    h = bits_to_gaussian(bits, sigma2)
    mask = jnp.logical_or((h * h) >= h_th,
                          jnp.asarray(ota_on, jnp.float32) < 0.5)
    out = jnp.where(mask, x, jnp.zeros_like(x))
    return out, mask.astype(x.dtype), h


def ota_aggregate_client_ref(
    g: jax.Array,            # (C, N, ...) RAW per-client gradients
    p: jax.Array,            # (C, N) loss weights
    bits: jax.Array,         # (C, ...) uint32 gain bits per cluster
    nbits: jax.Array,        # (...) uint32 AWGN bits
    sigma2: jax.Array,       # (C,)
    h_th, noise_std, ota_on,
    n_clients: int,
    live=None,               # (C,) cluster participation (DESIGN.md §3.14)
    n_eff=None,              # () traced effective N
) -> jax.Array:
    """Client-folded oracle (eqs. 3 + 8-10): fold the per-client weights
    into the MAC sum — Σ_l M_l ∘ (Σ_n p[l,n]·g[l,n]) — then AWGN and the
    guarded |M|·N estimate. Same bits/mask/noise laws as
    ``ota_aggregate_slab_ref``; the weighted tree is never an input."""
    wg = jnp.einsum("cn,cn...->c...", p.astype(jnp.float32),
                    g.astype(jnp.float32))
    return ota_aggregate_slab_ref(wg, bits, nbits, sigma2, h_th, noise_std,
                                  ota_on, n_clients, live=live, n_eff=n_eff)


def ota_stream_fold_ref(
    g: jax.Array,            # (N, ...) ONE cluster's raw client gradients
    p_c: jax.Array,          # (N,) this cluster's loss weights
    bits: jax.Array,         # (...) uint32 gain bits, this cluster's stream
    sigma2_c, h_th, ota_on,
    live_c=None,             # () cluster participation flag (§3.14)
):
    """One cluster's streaming-fold contribution (DESIGN.md §3.15):
    (M_l ∘ Σ_n p[n]·g[n], M_l) — the per-cluster term of the eq.-8 MAC
    sum plus its |M| count, BEFORE any cross-cluster reduction. The
    streaming aggregator accumulates these one arriving cluster at a
    time; folding all C and adding the AWGN + eq.-10 guard reproduces
    ``ota_aggregate_client_ref`` exactly (same weight fold, same mask
    law, same term order). ``live_c`` ANDs into the mask after the
    ``ota_on`` all-pass gate, like ``live`` does in the slab oracle."""
    wg = jnp.einsum("n,n...->...", p_c.astype(jnp.float32),
                    g.astype(jnp.float32))
    m = bits_to_mask(bits.reshape(wg.shape), sigma2_c, h_th, ota_on)
    if live_c is not None:
        m = jnp.logical_and(m, jnp.asarray(live_c, jnp.float32) > 0.5)
    return jnp.where(m, wg, 0.0), m.astype(jnp.float32)


def ota_aggregate_slab_ref(
    wg: jax.Array,           # (C, ...) weighted grads, already Σ_i p_i g_i
    bits: jax.Array,         # (C, ...) uint32 gain bits per cluster
    nbits: jax.Array,        # (...) uint32 AWGN bits
    sigma2: jax.Array,       # (C,)
    h_th, noise_std, ota_on,
    n_clients: int,
    live=None,               # (C,) cluster participation (DESIGN.md §3.14)
    n_eff=None,              # () traced effective N
) -> jax.Array:
    """eqs. (8)-(10) on flat slabs, per-cluster where+sum in plain jnp.

    The packed kernel's oracle: same bits, same inverse-CDF mask rule
    (``bits_to_mask``), same Box-Muller AWGN, same |M|·N guard — but
    per-cluster masks materialize as full (C, ...) arrays. A non-None
    ``live`` ANDs cluster participation into the masks AFTER the
    ``ota_on`` all-pass gate (blackout removes a cluster even in the
    error-free baseline); ``n_eff`` replaces the static N denominator.
    """
    c = wg.shape[0]
    sig = jnp.asarray(sigma2, jnp.float32).reshape((c,) + (1,) * (wg.ndim - 1))
    masks = bits_to_mask(bits, sig, h_th, ota_on)
    if live is not None:
        lv = jnp.asarray(live, jnp.float32).reshape(
            (c,) + (1,) * (wg.ndim - 1))
        masks = jnp.logical_and(masks, lv > 0.5)
    y = jnp.sum(jnp.where(masks, wg.astype(jnp.float32), 0.0), axis=0)
    z = bits_to_gaussian(nbits, 1.0) * noise_std * jnp.asarray(
        ota_on, jnp.float32)
    y = y + z
    cnt = jnp.sum(masks.astype(jnp.float32), axis=0)
    denom = (jnp.float32(n_clients) if n_eff is None
             else jnp.maximum(jnp.asarray(n_eff, jnp.float32), 1.0))
    return jnp.where(cnt > 0, y / (jnp.maximum(cnt, 1.0) * denom), 0.0)
