"""Quickstart: train the paper's exact setting for a few rounds.

HOTA-FedGradNorm (Alg. 1 + 2) on synthetic RadComDynamic with the Table-I
MLP, C=4 clusters x N=3 clients, fading MAC with AWGN, dynamic loss
weights. Runs on CPU in ~a minute.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.core.sim import HotaSim
from repro.data.federated import FederatedBatcher
from repro.data.radcom import (
    N_CLASSES, RadComConfig, TASKS, client_partition, make_radcom_dataset,
)
from repro.models.model import build_model


def main(steps: int = 60):
    print("== HOTA-FedGradNorm quickstart ==")
    data = make_radcom_dataset(RadComConfig(n_points=20_000))
    parts = client_partition(data, n_clusters=4, n_clients=3)
    batcher = FederatedBatcher(parts, batch=32)
    n_cls = [N_CLASSES[TASKS[i % 3]] for i in range(3)]

    model = build_model(ModelConfig(family="mlp"))
    fl = FLConfig(n_clusters=4, n_clients=3, weighting="fedgradnorm",
                  h_threshold=3.2e-2, noise_std=1.0, gamma=0.6, alpha=8e-3)
    sim = HotaSim(model, fl, TrainConfig(lr=3e-4), n_cls)
    state = sim.init(jax.random.PRNGKey(0))

    for step in range(steps):
        x, y = batcher.next_stacked()
        state, m = sim.step(state, jnp.asarray(x), jnp.asarray(y),
                            jax.random.PRNGKey(step))
        if step % 10 == 0 or step == steps - 1:
            loss = np.asarray(m["loss"]).mean(axis=0)   # per-task mean
            p = np.asarray(m["p"]).mean(axis=0)
            print(f"round {step:3d} | loss per task "
                  f"mod={loss[0]:.3f} sig={loss[1]:.3f} anom={loss[2]:.3f} "
                  f"| p = [{p[0]:.3f} {p[1]:.3f} {p[2]:.3f}]")
    print("done — task weights adapted to task difficulty & channel state.")


def sweep(steps: int = 20):
    """Multi-scenario sweep: 3 channel scenarios, ONE compiled step.

    ScenarioBank batches the traced channel knobs (σ², noise, threshold,
    OTA on/off, weighting) over a leading scenario axis and vmaps the
    simulator across it. Data batches and PRNG keys are shared between
    scenarios (common random numbers), so the comparison is paired.
    """
    print("== 3-scenario ScenarioBank sweep ==")
    from repro.core.paper_setup import paper_mlp_setup
    from repro.core.sweep import ScenarioBank

    base_fl = FLConfig(n_clusters=4, n_clients=3)
    sim, batcher = paper_mlp_setup(base_fl, batch=32, n_points=20_000)
    bank = ScenarioBank(sim, [
        dict(),                                  # fading MAC + FedGradNorm
        dict(weighting="equal"),                 # naive baseline
        dict(sigma2=(0.05, 1.0, 1.0, 1.0)),      # one bad channel
    ])
    labels = ["hota_fgn", "equal", "bad_channel"]

    states = bank.init(jax.random.PRNGKey(0))
    states, history = bank.run(
        states,
        (batcher.next_stacked() for _ in range(steps)),
        [jax.random.PRNGKey(step) for step in range(steps)])
    loss = np.asarray(history["loss"][-1]).mean(axis=(1, 2))   # (S,)
    for lbl, l in zip(labels, loss):
        print(f"  scenario {lbl:12s} mean loss after {steps} rounds: {l:.3f}")
    print("one jit served all scenarios — same data, same channel draws.")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
    sweep()
