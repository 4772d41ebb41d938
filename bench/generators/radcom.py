"""Synthetic RadComDynamic batches for federated rounds, drawn on the
device from a seed in one jitted call.

A copy of the repo's synthetic stand-in for RadComDynamic (Jagannath &
Jagannath, ICC'21): 256-dim features built from class prototypes of three
tasks (modulation, 6 classes; signal type, 8; anomaly = SNR below a
threshold, 2), nonlinear mixing, SNR-dependent gain and noise, then
standardized. Client i of each cluster owns task i % 3; each client's
sample pool is its shard of the points resampled with Dirichlet(alpha)
class skew; a round's batch is ``batch`` rows drawn from every client's
pool. Every parameter comes from the traffic file.

Deviation from the repo's numpy generator: the draws are jax.random, and
the points are split into equal shards (the remainder of n_points over
C*N is left out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TASK_CLASSES = (6, 8, 2)          # modulation, signal type, anomaly


def n_classes(traffic):
    return [TASK_CLASSES[i % 3] for i in range(traffic["n_clients"])]


@functools.lru_cache(maxsize=None)
def _pool_fn(n_points, dim, task_scale, snr_threshold_db, noise, alpha,
             n_clusters, n_clients, batch, rounds):
    n_slots = n_clusters * n_clients
    n_per = n_points // n_slots
    max_cls = max(TASK_CLASSES)

    def pool(key):
        ks = jax.random.split(key, 10)
        mod = jax.random.randint(ks[0], (n_points,), 0, TASK_CLASSES[0])
        sig = jax.random.randint(ks[1], (n_points,), 0, TASK_CLASSES[1])
        snr_db = jax.random.uniform(ks[2], (n_points,), jnp.float32,
                                    -10.0, 16.0)
        anomaly = (snr_db < snr_threshold_db).astype(jnp.int32)
        proto_mod = jax.random.normal(ks[3], (TASK_CLASSES[0], dim))
        proto_sig = jax.random.normal(ks[4], (TASK_CLASSES[1], dim))
        mix = jax.random.normal(ks[5], (dim, dim)) / np.sqrt(dim)
        s_mod, s_sig, s_snr = task_scale
        x = s_mod * proto_mod[mod] + s_sig * proto_sig[sig]
        x = jnp.tanh(jnp.matmul(x, mix, precision="highest")) + 0.5 * x
        snr_lin = (10.0 ** (snr_db / 20.0))[:, None]
        x = x * (0.25 + s_snr * snr_lin / (1.0 + snr_lin))
        x = x + jax.random.normal(ks[6], (n_points, dim)) * noise
        x = (x - x.mean(0)) / (x.std(0) + 1e-6)
        labels = jnp.stack([mod, sig, anomaly])              # (3, n)

        perm = jax.random.permutation(ks[7], n_points)[:n_slots * n_per]
        shards = perm.reshape(n_slots, n_per)
        task = jnp.arange(n_slots) % n_clients % 3
        n_cls = jnp.asarray(TASK_CLASSES)[task]

        def client_pool(k, idx, t, nc):
            kd, kc = jax.random.split(k)
            valid = jnp.arange(max_cls) < nc
            gam = jnp.where(valid, jax.random.gamma(kd, alpha, (max_cls,)),
                            0.0)
            weights = gam / jnp.sum(gam)                      # Dirichlet
            lab = labels[t][idx]
            p = weights[lab]
            take = jax.random.choice(kc, idx, (n_per,), replace=True,
                                     p=p / jnp.sum(p))
            return take, labels[t][take]

        take, lab = jax.vmap(client_pool)(
            jax.random.split(ks[8], n_slots), shards, task, n_cls)
        rows = jax.random.randint(ks[9], (rounds, n_slots, batch), 0, n_per)
        pick = jnp.take_along_axis(take[None], rows, axis=2)
        ys = jnp.take_along_axis(lab[None], rows, axis=2)
        xs = x[pick]
        shape = (rounds, n_clusters, n_clients, batch)
        return xs.reshape(shape + (dim,)), ys.reshape(shape).astype(jnp.int32)

    return jax.jit(pool)


def make_pool(traffic, key):
    """Host arrays xs (rounds, C, N, B, d) float32, ys (rounds, C, N, B)
    int32, drawn on the default device from ``key``."""
    d = traffic["data"]
    fn = _pool_fn(d["n_points"], d["feature_dim"], tuple(d["task_scale"]),
                  d["snr_threshold_db"], d["feature_noise"],
                  d["noniid_alpha"], traffic["n_clusters"],
                  traffic["n_clients"], traffic["batch"],
                  traffic["pool_rounds"])
    xs, ys = fn(key)
    return np.asarray(xs), np.asarray(ys)
