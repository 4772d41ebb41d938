"""The paper's Table-I network as a plain reference, with its model FLOPs.

HOTA-FedGradNorm (arXiv 2212.07414) Sec. IV: a shared FC net
256-512-1024-2048-512 with ReLU, a last shared layer 512-256 (the
FedGradNorm layer), and one linear head per client. One federated round
(Alg. 1 with Alg. 2):

1. each client takes tau_h Adam steps on its head, then computes the loss
   and the gradient of the shared net (tau_w = 1: one local step);
2. each cluster's server masks the last shared layer's gradients with the
   round's eq.-7 channel masks and runs the FedGradNorm step (Alg. 2);
3. the clusters transmit sum_n p_n g_n over the fading MAC; the server
   estimates g from the masked sum plus noise (eqs. 3, 8-10);
4. the server takes an Adam step on the shared net.

Everything is plain ``jax.numpy`` in a stated dtype, with matmuls at
``HIGHEST`` precision. The channel comes from ``bench.refs.ota_spec``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import fedgradnorm as fgn
from bench.refs import ota_spec

HIGHEST = jax.lax.Precision.HIGHEST


def _layers(cfg) -> int:
    return len(cfg["dims"]) - 2


def init_weights(cfg, key, n_clusters: int, n_clients: int):
    """Shared net and per-client heads from ``key``: weights normal with
    std 1/sqrt(fan_in), biases zero, all float32."""
    dims, n_out = cfg["dims"], cfg["head_classes"]
    keys = jax.random.split(key, _layers(cfg) + 2)

    def dense(k, d_in, d_out, lead=()):
        w = jax.random.normal(k, lead + (d_in, d_out), jnp.float32)
        return {"w": w / np.sqrt(d_in),
                "b": jnp.zeros(lead + (d_out,), jnp.float32)}

    trunk = {f"fc{i}": dense(keys[i], dims[i], dims[i + 1])
             for i in range(_layers(cfg))}
    final = dense(keys[-2], dims[-2], dims[-1])
    heads = dense(keys[-1], dims[-1], n_out, (n_clusters, n_clients))
    return {"omega": {"final": final, "trunk": trunk}, "heads": heads}


def model_flops_per_round(cfg, traffic) -> float:
    """Matmul FLOPs one round needs, from the shapes (biases, ReLU and
    the channel are not counted; nothing is counted twice)."""
    dims, n_out = cfg["dims"], cfg["head_classes"]
    b = traffic["batch"]
    fl = cfg["fl"]
    trunk = [2.0 * b * dims[i] * dims[i + 1] for i in range(_layers(cfg))]
    final = 2.0 * b * dims[-2] * dims[-1]
    head = 2.0 * b * dims[-1] * n_out
    fwd = sum(trunk) + final + head
    # head step: forward, then the head's weight gradient
    head_step = fwd + head
    # shared step: forward, the head's input gradient, weight and input
    # gradients of every shared layer but the first's input gradient
    shared_step = fwd + head + 2 * final + 2 * sum(trunk) - trunk[0]
    per_client = fl["tau_h"] * head_step + fl["tau_w"] * shared_step
    return traffic["n_clusters"] * traffic["n_clients"] * per_client


# --------------------------------------------------------------- reference
def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _loss(head, omega, x, y, n_valid, n_layers, dtype):
    h = x.astype(dtype)
    for i in range(n_layers):
        lay = omega["trunk"][f"fc{i}"]
        h = jax.nn.relu(_mm(h, lay["w"]) + lay["b"])
    h = jax.nn.relu(_mm(h, omega["final"]["w"]) + omega["final"]["b"])
    logits = _mm(h, head["w"]) + head["b"]
    valid = jnp.arange(logits.shape[-1]) < n_valid
    logits = jnp.where(valid, logits, jnp.asarray(-1e30, dtype))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1)[..., 0])


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


@functools.lru_cache(maxsize=None)
def _round_fn(cfg_items, n_clusters, n_clients, dtype_name):
    cfg = dict(cfg_items)
    fl = dict(cfg["fl"])
    dtype = jnp.dtype(dtype_name)
    n_layers = len(cfg["dims"]) - 2
    lr = cfg["lr"]
    sig = list(fl["sigma2"]) or [1.0]
    sigma2 = jnp.asarray([sig[c % len(sig)] for c in range(n_clusters)],
                         jnp.float32)
    ota_on = bool(fl["ota"])
    fgn_on = fl["weighting"] == "fedgradnorm"
    loss = functools.partial(_loss, n_layers=n_layers, dtype=dtype)
    if fl["tau_h"] != 1 or fl["tau_w"] != 1:
        raise ValueError("the reference follows tau_h = tau_w = 1")

    def client(omega, head, hopt, x, y, n_valid):
        gh = jax.grad(loss)(head, omega, x, y, n_valid)
        head, hopt = fgn.adam_step(gh, hopt, head, lr, dtype)
        f, g = jax.value_and_grad(loss, argnums=1)(head, omega, x, y,
                                                   n_valid)
        return head, hopt, g, f, gh

    def round_(state, x, y, key, n_classes):
        vm = jax.vmap(jax.vmap(client, in_axes=(None, 0, 0, 0, 0, 0)),
                      in_axes=(None, 0, 0, 0, 0, None))
        heads, hopt, g, f, gh = vm(state["omega"], state["heads"],
                                   state["hopt"], x, y, n_classes)
        f = f.astype(jnp.float32)
        f0 = jnp.where(state["t"] == 0, f, state["f0"])
        ratios = f / jnp.maximum(f0, 1e-12)
        chan_key = jax.random.fold_in(key, ota_spec.SIM_CHAN_FOLD)
        runs = ota_spec.layout(state["omega"])
        p_pass = ota_spec.pass_probability(sigma2, fl["h_threshold"])
        if not ota_on:
            p_pass = jnp.full_like(p_pass, 2.0)     # every entry passes
        flat_g = {ota_spec.path_str(p): leaf for p, leaf in
                  jax.tree_util.tree_flatten_with_path(g)[0]}
        tail = [r.path for r in runs if r.path.startswith("final/")]
        masks = ota_spec.tail_masks(chan_key, runs, tail, n_clusters, p_pass)
        sq = 0.0
        for path in tail:
            gl = flat_g[path].astype(jnp.float32).reshape(
                n_clusters, n_clients, -1)
            sq = sq + jnp.sum(jnp.where(masks[path][:, None, :], gl, 0.0)
                              ** 2, axis=-1)
        norms = jnp.sqrt(sq)
        if fgn_on:
            p_new, fopt = jax.vmap(
                lambda p, n, r, o: fgn.fgn_step(p, n, r, o, fl["alpha"],
                                                fl["gamma"]))(
                state["p"], norms, ratios, state["fopt"])
        else:
            p_new, fopt = state["p"], state["fopt"]
        noise_std = fl["noise_std"] if ota_on else 0.0
        ghat, noise = {}, {}
        for run in runs:
            ghat[run.path], noise[run.path] = ota_spec.aggregate_leaf(
                flat_g[run.path], p_new,
                ota_spec.gain_bits(chan_key, run, n_clusters),
                ota_spec.noise_bits(chan_key, run),
                p_pass, noise_std, n_clients, dtype)
        treedef = jax.tree.structure(state["omega"])
        order = [ota_spec.path_str(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(state["omega"])[0]]
        ghat_tree = jax.tree.unflatten(treedef, [ghat[k] for k in order])
        noise_tree = jax.tree.unflatten(treedef, [noise[k] for k in order])
        omega, psopt = fgn.adam_step(ghat_tree, state["psopt"],
                                     state["omega"], lr, dtype)
        new = dict(state, omega=omega, heads=heads, hopt=hopt, p=p_new,
                   fopt=fopt, f0=f0, t=state["t"] + 1, psopt=psopt)
        rec = {"loss": f, "p": p_new, "norms": norms, "ghat": ghat_tree,
               "ghat_noise": noise_tree, "head_grad": gh}
        return new, rec

    return jax.jit(round_)


def reference(cfg, traffic, weights, xs, ys, keys, n_classes, steps=3,
              dtype="float32") -> Dict[str, object]:
    """Follow the first ``steps`` rounds from ``weights`` on batches
    ``xs[k]`` (C, N, B, d), ``ys[k]`` (C, N, B) and round keys
    ``keys[k]``. Returns host arrays: per-round losses, weights p and
    masked norms, the first round's estimate g-hat as the server's
    optimizer receives it and its noise term, the first round's head
    gradients, and the parameters after ``steps`` rounds."""
    c, n = traffic["n_clusters"], traffic["n_clients"]
    dt = jnp.dtype(dtype)
    round_fn = _round_fn(_freeze(cfg), c, n, dt.name)
    omega = _cast(weights["omega"], dt)
    heads = _cast(weights["heads"], dt)
    zeros_p = {"t": jnp.zeros((c,), jnp.int32),
               "m": jnp.zeros((c, n), jnp.float32),
               "v": jnp.zeros((c, n), jnp.float32)}
    hopt = jax.vmap(jax.vmap(lambda h: fgn.adam_init(h, dt)))(heads)
    state = {"omega": omega, "heads": heads, "hopt": hopt,
             "psopt": fgn.adam_init(omega, dt),
             "p": jnp.ones((c, n), jnp.float32), "fopt": zeros_p,
             "f0": jnp.ones((c, n), jnp.float32),
             "t": jnp.zeros((), jnp.int32)}
    ncls = jnp.asarray(n_classes, jnp.int32)
    out = {"loss": [], "p": [], "norms": []}
    for k in range(steps):
        state, rec = round_fn(state, jnp.asarray(xs[k]), jnp.asarray(ys[k]),
                              jnp.asarray(keys[k]), ncls)
        for name in ("loss", "p", "norms"):
            out[name].append(np.asarray(rec[name], np.float64))
        if k == 0:
            out["ghat1"] = _host(rec["ghat"])
            out["noise1"] = _host(rec["ghat_noise"])
            out["head_grad1"] = _host(rec["head_grad"])
    out["omega"] = _host(state["omega"])
    out["heads"] = _host(state["heads"])
    return out


def _host(tree) -> Dict[str, np.ndarray]:
    return {ota_spec.path_str(p): np.asarray(leaf, np.float64)
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, list):
        return tuple(_freeze(v) for v in obj)
    return obj
