"""Subprocess program: the SECTIONED distributed backward (DESIGN.md
§3.16) on a forced 4-device (2 clusters × 2 clients) mesh.

Pins:

1. the sectioned gather backward (per-section collect → one-section-
   deferred finalize, double-buffered) is BIT-identical to the full-slab
   schedule for every composed mode: count_mode ∈ {psum, local} ×
   max_section_rows ∈ {0, 8} — the section pipeline changes stream
   lifetime and psum grouping, never a per-leaf value;
2. the sectioned backward ≡ the jnp oracle ``packed_omega_aggregate_ref``
   on shared keys (float tolerance — the oracle differs at fusion level);
3. end-to-end: ``make_hota_train_step`` with ``fl.ota_sectioned=True``
   tracks the full-slab step over 2 FedGradNorm rounds (the whole round
   path accepts the sectioned schedule, not just the isolated gather);
4. the distributed step REJECTS ``fl.ota_streaming`` by name — the
   simulator engine must never be silently inert here.

Run: python dist_sectioned.py   (sets its own XLA_FLAGS)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.core.channel import channel_params
from repro.core.hota import OTACtx, _is_axes
from repro.core.hota_slab import (
    _fsdp_axis_full, make_packed_omega_gather, packed_omega_aggregate_ref,
    packed_omega_key,
)
from repro.core.hota_step import make_hota_train_step
from repro.models.model import build_model
from repro.models.params import abstract_params, init_params, logical_axes

C, N, B, D = 2, 2, 4, 256
MAXC = 8

cfg = ModelConfig(family="mlp", compute_dtype="float32")
model = build_model(cfg)
tcfg = TrainConfig(lr=1e-3)
devs = np.array(jax.devices()).reshape(C, N)
mesh = Mesh(devs, ("cluster", "client"))

fl_ota = FLConfig(n_clusters=C, n_clients=N, noise_std=0.3,
                  sigma2=(0.5, 1.5), h_threshold=0.2)
chan = channel_params(fl_ota)
template = {"final": abstract_params(model.final_specs()),
            "trunk": abstract_params(model.trunk_specs())}
axes_list = [a for a in jax.tree.leaves(
    {"final": logical_axes(model.final_specs()),
     "trunk": logical_axes(model.trunk_specs())}, is_leaf=_is_axes)]
n_shards = C * N

base_key = jax.random.PRNGKey(42)
slab_key = packed_omega_key(base_key)
p_dev = jax.random.uniform(jax.random.fold_in(base_key, 5), (C, N),
                           jnp.float32, 0.5, 1.5)
cnt = [0]


def _draw(l):
    cnt[0] += 1
    return jax.random.normal(jax.random.fold_in(base_key, 100 + cnt[0]),
                             (C, N) + tuple(l.shape), jnp.float32)


g_full = jax.tree.map(_draw, template)
g_dev_major = jax.tree.map(
    lambda l: jnp.swapaxes(l, 0, 1).reshape((N * C,) + l.shape[2:]), g_full)
spec_in = jax.tree.map(lambda l: P(("client", "cluster")), g_dev_major)
out_specs = jax.tree.unflatten(
    jax.tree.structure(template),
    [P(*[("client", "cluster") if d == _fsdp_axis_full(ax) else None
         for d in range(len(l.shape))]) if _fsdp_axis_full(ax) >= 0 else P()
     for l, ax in zip(jax.tree.leaves(template), axes_list)])


def build_bwd(count_mode, max_section_rows, sectioned):
    gather, packer = make_packed_omega_gather(
        ("client", "cluster"), ("cluster",), N, n_shards, jnp.float32,
        template, axes_list, n_clusters=C, count_mode=count_mode,
        max_section_rows=max_section_rows, sectioned=sectioned)

    def local_bwd(g_loc, p_loc):
        g_loc = jax.tree.map(lambda l: l[0], g_loc)
        ctx = OTACtx(p_weight=p_loc.reshape(()), key=slab_key,
                     sigma2=chan.sigma2, h_th=chan.h_threshold,
                     noise_std=chan.noise_std, ota_on=chan.ota_on)
        shard = jax.tree.unflatten(
            jax.tree.structure(g_loc),
            [jnp.zeros(tuple(s // n_shards if d == _fsdp_axis_full(ax)
                             else s for d, s in enumerate(l.shape)),
                       jnp.float32)
             for l, ax in zip(jax.tree.leaves(g_loc), axes_list)])
        _, vjp = jax.vjp(lambda t: gather(t, ctx), shard)
        (g_shards,) = vjp(g_loc)
        return g_shards

    return jax.jit(jax.shard_map(
        local_bwd, mesh=mesh,
        in_specs=(spec_in, P("cluster", "client")),
        out_specs=out_specs,
        axis_names={"cluster", "client"}, check_vma=False)), packer


# --- 1. sectioned ≡ full-slab backward, BITWISE, composed modes -------------
# (psum, 0) is the legacy default; (local, 8) composes the platform
# count fold with a split layout — the two corners exercise every
# branch pair without compiling the full product on 4 host CPUs
for count_mode, msr in (("psum", 0), ("local", 8)):
        f_full, packer = build_bwd(count_mode, msr, sectioned=False)
        f_sec, _ = build_bwd(count_mode, msr, sectioned=True)
        a = jax.tree.map(np.asarray, f_full(g_dev_major, p_dev))
        b = jax.tree.map(np.asarray, f_sec(g_dev_major, p_dev))
        for (ka, la), (_, lb) in zip(
                jax.tree_util.tree_flatten_with_path(a)[0],
                jax.tree_util.tree_flatten_with_path(b)[0]):
            np.testing.assert_array_equal(
                la, lb,
                err_msg=(f"sectioned != full-slab at "
                         f"{jax.tree_util.keystr(ka)} "
                         f"(count_mode={count_mode}, msr={msr})"))

# --- 2. sectioned backward ≡ jnp oracle on shared keys ----------------------
f_sec, packer = build_bwd("psum", 0, sectioned=True)
ghat = f_sec(g_dev_major, p_dev)
wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p_dev, l), g_full)
ghat_ref = packed_omega_aggregate_ref(wg, slab_key, chan, N, packer)
for (ka, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(ghat)[0],
                           jax.tree_util.tree_flatten_with_path(ghat_ref)[0]):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-5,
        err_msg=f"sectioned bwd vs oracle at {jax.tree_util.keystr(ka)}")

# --- 3. end-to-end train step: sectioned tracks full-slab -------------------
key = jax.random.PRNGKey(0)
x = jax.random.normal(jax.random.fold_in(key, 1), (C * N * B, D))
y = jax.random.randint(jax.random.fold_in(key, 2), (C * N * B,), 0, MAXC)
omega0 = {"final": init_params(model.final_specs(), jax.random.fold_in(key, 7)),
          "trunk": init_params(model.trunk_specs(), key)}


def run(fl, steps=2):
    init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
        model, mesh, fl, tcfg, loss_kind="cls", n_out=MAXC)
    state = init_fn(jax.random.PRNGKey(123))._replace(omega=omega0)
    state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state, state_specs, is_leaf=lambda z: isinstance(z, P))
    xb = jax.device_put(x, NamedSharding(mesh, batch_spec[0]))
    yb = jax.device_put(y, NamedSharding(mesh, batch_spec[1]))
    jstep = jax.jit(step_fn)
    for s in range(steps):
        state, m = jstep(state, xb, yb, jax.random.PRNGKey(7 + s))
    return state, m


fl_kw = dict(n_clusters=C, n_clients=N, noise_std=0.3, sigma2=(0.5, 1.5),
             h_threshold=0.2, tau_h=1)
# max_section_rows RE-KEYS the trunk streams (§4 split rule), so both
# runs share the split layout — they differ ONLY in the engine schedule
st_full, m_full = run(FLConfig(max_section_rows=8, **fl_kw))
st_sec, m_sec = run(FLConfig(ota_sectioned=True, max_section_rows=8,
                             **fl_kw))
for la, lb in zip(jax.tree.leaves(st_full.omega),
                  jax.tree.leaves(st_sec.omega)):
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=1e-6, atol=1e-7,
                               err_msg="end-to-end omega diverged")

# --- 4. fl.ota_streaming is rejected by name in the distributed step --------
try:
    make_hota_train_step(model, mesh,
                         FLConfig(ota_streaming=True, **fl_kw), tcfg,
                         loss_kind="cls", n_out=MAXC)
    raise SystemExit("fl.ota_streaming was accepted by the distributed step")
except ValueError as e:
    assert "ota_streaming" in str(e) and "ota_sectioned" in str(e), e

print(f"DIST_SECTIONED_OK sections={len(packer.sections)} "
      f"loss={float(m_sec['loss']):.4f}")
