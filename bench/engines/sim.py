"""The simulator engine (``repro.core.sim.HotaSim``) as a cell runs it: clients
vmapped on one device, one compiled round per call.

Set-up makes the pool of batches and the weights on the device from the
seed, builds the round as the figure runners do (``HotaSim._step``, the
jitted entry), and drives it. Each round places its pre-drawn batch and
key on the device, dispatches the step and reads the round's losses and
weights back, which ends it.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.refs import fedgradnorm as fgn_ref
from bench.refs.ota_spec import path_str


def _host(tree):
    """Host float64 copies of a tree's leaves, by path, in flatten order
    (the order the server's slab Adam lays its moments out in)."""
    return {path_str(p): np.asarray(leaf, np.float64) for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class Cell:
    def __init__(self, cfg, traffic, model_mod, generator, seeds, devices):
        from repro.common.config import FLConfig, ModelConfig, TrainConfig
        from repro.core.sim import HotaSim
        from repro.models.model import build_model

        self.cfg, self.traffic = cfg, traffic
        self.device = devices[0]
        c, n = traffic["n_clusters"], traffic["n_clients"]
        fl = dict(cfg["fl"], sigma2=tuple(cfg["fl"]["sigma2"]))
        self.n_classes = generator.n_classes(traffic)
        model = build_model(ModelConfig(**cfg["model"]))
        sim = HotaSim(model, FLConfig(n_clusters=c, n_clients=n, **fl),
                      TrainConfig(lr=cfg["lr"]), self.n_classes,
                      max_classes=cfg["head_classes"])
        self.seeds = seeds
        self.xs, self.ys = generator.make_pool(traffic, seeds.data_key())
        chan, faults = sim.chan, sim.faults
        self.step = lambda st, x, y, k: sim._step(st, x, y, k, chan, faults)

        def init(key):
            w = model_mod.init_weights(cfg, key, c, n)
            st = sim.init(jax.random.fold_in(key, 1))
            return st._replace(omega=w["omega"], heads=w["heads"])

        self.state = jax.jit(init)(seeds.weight_key())
        self.check_steps = traffic["check"]["steps"]
        self.done = 0
        self.kept = {"loss": [], "p": [], "norms": []}

    def round(self):
        """One round; returns the host losses and weights."""
        i = self.done
        j = i % self.traffic["pool_rounds"]
        with jax.profiler.TraceAnnotation("bench.input"):
            x, y, k = jax.device_put(
                (self.xs[j], self.ys[j], self.seeds.round_key(i)),
                self.device)
            self.state, m = self.step(self.state, x, y, k)
        with jax.profiler.TraceAnnotation("bench.readback"):
            loss, p = np.asarray(m["loss"]), np.asarray(m["p"])
        if i < self.check_steps:
            self.kept["loss"].append(np.asarray(loss, np.float64))
            self.kept["p"].append(np.asarray(p, np.float64))
            self.kept["norms"].append(np.asarray(m["grad_norms"],
                                                 np.float64))
            if i == 0:
                self.kept["mu1"] = np.asarray(self.state.ps_opt.mu,
                                              np.float64)
            if i == self.check_steps - 1:
                self.kept["omega"] = _host(self.state.omega)
                self.kept["heads"] = _host(self.state.heads)
        self.done += 1
        return loss, p

    def finish(self):
        jax.block_until_ready(self.state)

    def check_inputs(self):
        """What the reference follows: the first rounds' batches and keys."""
        k = self.check_steps
        return {"xs": self.xs[:k], "ys": self.ys[:k],
                "keys": [self.seeds.round_key(i) for i in range(k)],
                "n_classes": self.n_classes}

    def readings(self):
        """Host copies of what the first rounds produced: losses, weights
        and masked norms per round, the estimate the server's Adam got in
        round 1 (its first moment over 1 - beta1), the parameters after
        the last checked round."""
        mu = self.kept["mu1"] / (1.0 - fgn_ref.B1)
        ghat, off = {}, 0
        for path, leaf in self.kept["omega"].items():
            ghat[path] = mu[off:off + leaf.size].reshape(leaf.shape)
            off += leaf.size
        if off != mu.size:
            raise ValueError(f"server moments hold {mu.size} entries, the "
                             f"shared tree {off}")
        out = {k: self.kept[k] for k in ("loss", "p", "norms", "omega",
                                         "heads")}
        out["ghat1"] = ghat
        return out

    def release(self):
        self.state = self.kept = self.step = None
