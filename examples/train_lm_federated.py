"""End-to-end driver: federated HOTA-FedGradNorm training of a ~100M-param
dense LM for a few hundred rounds on the distributed (shard_map) path.

Topology: 2 clusters x 2 clients x 2 replicas on the "model" axis = 8
host devices (the step is not tensor-parallel; see repro.core.hota).
Each client owns a differently-skewed synthetic token stream (statistical
heterogeneity), personalized output heads, dynamic FedGradNorm weighting,
and the fading-MAC OTA aggregation between cluster ISs and the PS.

    PYTHONPATH=src python examples/train_lm_federated.py --steps 200

(~100M params; on this CPU container a step takes a few seconds — trim
--steps for a quick look. Checkpoints land in results/example_lm/.)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.core.hota_step import make_hota_train_step
from repro.data.lm import synthetic_lm_batches
from repro.models.model import build_model
from repro.models.params import param_count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--weighting", default="fedgradnorm")
    ap.add_argument("--out", default="results/example_lm")
    args = ap.parse_args()

    # ~100M-parameter dense GQA transformer
    cfg = ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=640, n_heads=8,
        n_kv_heads=4, d_ff=2560, vocab_size=32_000, compute_dtype="float32",
        remat_policy="none", attn_block_q=64, attn_block_kv=64)
    model = build_model(cfg)
    n_params = param_count({"t": model.trunk_specs()})
    print(f"model: {n_params/1e6:.1f}M shared params")

    devs = np.array(jax.devices())[:8].reshape(2, 2, 2)
    mesh = Mesh(devs, ("cluster", "client", "model"))
    fl = FLConfig(n_clusters=2, n_clients=2, weighting=args.weighting,
                  noise_std=0.5, ota_mode="scatter")
    tcfg = TrainConfig(lr=3e-4)
    init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
        model, mesh, fl, tcfg, loss_kind="lm")

    state = init_fn(jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state, state_specs, is_leaf=lambda x: isinstance(x, P))

    # per-client skewed streams: different zipf exponents = heterogeneity
    streams = [synthetic_lm_batches(cfg.vocab_size, args.batch_per_client,
                                    args.seq_len, seed=i, zipf_s=1.05 + 0.15 * i)
               for i in range(4)]
    jstep = jax.jit(step_fn)

    t0 = time.time()
    for step in range(args.steps):
        toks, labs = zip(*(next(s) for s in streams))
        toks = jnp.concatenate([jnp.asarray(t) for t in toks])
        labs = jnp.concatenate([jnp.asarray(l) for l in labs])
        toks = jax.device_put(toks, NamedSharding(mesh, batch_spec[0]))
        labs = jax.device_put(labs, NamedSharding(mesh, batch_spec[1]))
        state, m = jstep(state, toks, labs, jax.random.PRNGKey(1))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"round {step:4d} | loss {float(m['loss']):.4f} | "
                  f"p∈[{float(m['p_min']):.3f},{float(m['p_max']):.3f}] | "
                  f"fgrad {float(m['fgrad']):.3f} | "
                  f"{(time.time()-t0)/(step+1):.2f}s/round", flush=True)

    os.makedirs(args.out, exist_ok=True)
    path = save_checkpoint(args.out, args.steps,
                           jax.tree.map(np.asarray, state.omega),
                           {"params_m": n_params / 1e6})
    print("saved shared-network checkpoint:", path)


if __name__ == "__main__":
    main()
