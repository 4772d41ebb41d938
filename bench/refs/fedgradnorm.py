"""Plain reference of the optimizers a federated round uses: Adam (the
paper's Sec. IV-B choice for the clients' heads and the parameter
server) and the FedGradNorm server step (paper Alg. 2, eqs. 5-6).
Nothing here imports the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8


def adam_init(params, dtype=jnp.float32):
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, dtype), params)
    return {"t": jnp.zeros((), jnp.int32), "m": zeros, "v": zeros}


def adam_step(grads, opt, params, lr, dtype=jnp.float32):
    """One Adam step. Parameters and moments are kept in ``dtype``; the
    update itself is worked in float32 and rounded back to ``dtype``."""
    f32 = jnp.float32
    t = opt["t"] + 1
    tf = t.astype(f32)
    bc1, bc2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
    m = jax.tree.map(lambda m_, g: B1 * m_.astype(f32) + (1 - B1) * g.astype(
        f32), opt["m"], grads)
    v = jax.tree.map(lambda v_, g: B2 * v_.astype(f32) + (1 - B2) * jnp.square(
        g.astype(f32)), opt["v"], grads)
    new = jax.tree.map(
        lambda p, m_, v_: (p.astype(f32) - lr * (m_ / bc1) / (
            jnp.sqrt(v_ / bc2) + EPS)).astype(dtype), params, m, v)
    m = jax.tree.map(lambda a: a.astype(dtype), m)
    v = jax.tree.map(lambda a: a.astype(dtype), v)
    return new, {"t": t, "m": m, "v": v}


def fgn_step(p, norms, ratios, opt, alpha, gamma, p_min=0.0):
    """Alg. 2 for one cluster: p (N,), masked final-layer gradient norms
    (N,), loss ratios F/F0 (N,). Gradient of
    F_grad = sum_i |p_i n_i - G r_i^gamma| with G and r held constant,
    one Adam step of size alpha, then p_i > p_min and sum_i p_i = N."""
    n = p.shape[0]
    gbar = jnp.mean(p * norms)
    r = ratios / jnp.maximum(jnp.mean(ratios), 1e-12)
    target = jnp.power(jnp.maximum(r, 1e-12), gamma)
    resid = p * norms - gbar * target
    g = jnp.sign(resid) * norms
    t = opt["t"] + 1
    tf = t.astype(jnp.float32)
    m = B1 * opt["m"] + (1 - B1) * g
    v = B2 * opt["v"] + (1 - B2) * g * g
    p_new = p - alpha * (m / (1 - B1 ** tf)) / (
        jnp.sqrt(v / (1 - B2 ** tf)) + EPS)
    p_new = jnp.maximum(p_new, p_min + 1e-6)
    p_new = p_new * (n / jnp.maximum(jnp.sum(p_new), 1e-12))
    return p_new, {"t": t, "m": m, "v": v}
