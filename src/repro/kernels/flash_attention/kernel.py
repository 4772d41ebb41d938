"""Pallas TPU kernel: flash attention forward (causal + sliding window, GQA).

Grid: (B, H, num_q_blocks, num_kv_blocks) — the KV dimension is innermost
(sequential on TPU), so the online-softmax state for one q block lives in
VMEM scratch across KV steps:

    m   (bq, 1)  running max
    l   (bq, 1)  running denominator
    acc (bq, D)  running numerator

Blocks whose (q, kv) range is fully masked (above the causal diagonal or
beyond the sliding window) skip their MXU work via ``pl.when`` — on real
TPUs the fetch still happens (BlockSpec-driven), but the dominant matmul
cost is skipped; the pure-JAX blocked path cannot skip at all, which is
exactly the gap this kernel closes (EXPERIMENTS.md §Perf).

MXU alignment: block_q x block_kv default 512 x 512; D padded to a lane
multiple by the wrapper. fp32 accumulation throughout.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_kv, n_kv_blocks, window, causal):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_kv

    # static-ish skip test (traced on grid ids; pl.when gates the compute)
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, (q_start - (k_start + block_kv - 1)) < window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bkv)

        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        diff = pos_q - pos_k
        mask = diff >= 0 if causal else jnp.ones_like(diff, jnp.bool_)
        if window is not None:
            mask = jnp.logical_and(mask, diff < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, NEG_INF)           # keep finite
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, window: Optional[int] = None, causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q, block_kv: int = DEFAULT_BLOCK_KV,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D) with H % KV == 0."""
    b, h, sq, d = q.shape
    n_kv, skv = k.shape[1], k.shape[2]
    g = h // n_kv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    grid = (b, h, sq // block_q, skv // block_kv)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        n_kv_blocks=grid[3], window=window, causal=causal)

    return pl.pallas_call(
        kernel,
        grid=grid,
        name="flash_attention",
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            # online-softmax state in VMEM, persistent across the KV grid dim
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
