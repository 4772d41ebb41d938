"""Subprocess program: the slab-native distributed step (DESIGN.md §3.10).

Forced 4-device (2 clusters × 2 clients) mesh. Four pins:

1. slab-native step ≡ per-leaf oracle (``use_pallas_ota=False``) to float
   tolerance over 3 FedGradNorm rounds in the error-free case (the
   channel is inert, so the whole LAN psum → FGN → slab-Adam pipeline
   must agree exactly; slab Adam is elementwise-identical math);
2. with the channel ON, the slab gather's backward ≡ the jnp oracle
   ``packed_omega_aggregate_ref`` on SHARED keys — the section streams,
   inverse-CDF masks, AWGN and the |M|·N guard line up bit-for-bit
   between the distributed kernel path and the single-process reference;
3. zero-copy: the compiled backward materializes NO buffer of the packed
   slab size (the pack's dynamic-update-slice chain is gone — the kernel
   reads leaf storage in place);
4. retrace pin (DESIGN.md §3.11): sweeping ChannelParams VALUES through
   the compiled step never re-traces — TRACE_LOG stays flat — while
   ``ota_mode`` stays static by design (it changes collective structure).

Run: python dist_slab_step.py   (sets its own XLA_FLAGS)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.hota_step as hota_step
from repro.analysis import hlo_audit
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
STEPS = 3

cfg = ModelConfig(family="mlp", compute_dtype="float32")
model = build_model(cfg)
tcfg = TrainConfig(lr=1e-3)
devs = np.array(jax.devices()).reshape(C, N)
mesh = Mesh(devs, ("cluster", "client"))

key = jax.random.PRNGKey(0)
x = jax.random.normal(jax.random.fold_in(key, 1), (C * N * B, D))
y = jax.random.randint(jax.random.fold_in(key, 2), (C * N * B,), 0, MAXC)
omega0 = {"final": init_params(model.final_specs(), jax.random.fold_in(key, 7)),
          "trunk": init_params(model.trunk_specs(), key)}


def run(fl):
    init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
        model, mesh, fl, tcfg, loss_kind="cls", n_out=MAXC)
    state = init_fn(jax.random.PRNGKey(123))
    state = state._replace(omega=omega0)
    state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state, state_specs, is_leaf=lambda z: isinstance(z, P))
    xb = jax.device_put(x, NamedSharding(mesh, batch_spec[0]))
    yb = jax.device_put(y, NamedSharding(mesh, batch_spec[1]))
    jstep = jax.jit(step_fn)
    ms = []
    for s in range(STEPS):
        state, m = jstep(state, xb, yb, jax.random.PRNGKey(7 + s))
        ms.append(m)
    return state, ms


# --- 1. slab-native ≡ per-leaf oracle (error-free channel) -------------------
fl_base = dict(n_clusters=C, n_clients=N, weighting="fedgradnorm",
               ota=False, tau_h=1)
st_slab, ms_slab = run(FLConfig(use_pallas_ota=True, **fl_base))
st_leaf, ms_leaf = run(FLConfig(use_pallas_ota=False, **fl_base))
for la, lb in zip(jax.tree.leaves(st_slab.omega),
                  jax.tree.leaves(st_leaf.omega)):
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=2e-5, atol=1e-5, err_msg="omega")
for field in ("p", "fgn_mu", "fgn_nu", "f0"):
    np.testing.assert_allclose(np.asarray(getattr(st_slab, field)),
                               np.asarray(getattr(st_leaf, field)),
                               rtol=2e-5, atol=1e-6, err_msg=field)
for ma, mb in zip(ms_slab, ms_leaf):
    assert abs(float(ma["loss"]) - float(mb["loss"])) < 2e-5
    np.testing.assert_allclose(float(ma["gnorm_mean"]),
                               float(mb["gnorm_mean"]), rtol=2e-5)

# --- 2. channel ON: slab backward ≡ jnp oracle on shared keys ---------------
fl_ota = FLConfig(n_clusters=C, n_clients=N, noise_std=0.3, sigma2=(0.5, 1.5),
                  h_threshold=0.2)
chan = channel_params(fl_ota)
template = {"final": abstract_params(model.final_specs()),
            "trunk": abstract_params(model.trunk_specs())}
axes_list = [a for a in jax.tree.leaves(
    {"final": logical_axes(model.final_specs()),
     "trunk": logical_axes(model.trunk_specs())}, is_leaf=_is_axes)]
n_shards = C * N
gather, packer = make_packed_omega_gather(
    ("client", "cluster"), ("cluster",), N, n_shards, jnp.float32,
    template, axes_list, n_clusters=C)

base_key = jax.random.PRNGKey(42)
slab_key = packed_omega_key(base_key)
p_dev = jax.random.uniform(jax.random.fold_in(base_key, 5), (C, N),
                           jnp.float32, 0.5, 1.5)
cnt = [0]


def _draw(l):
    cnt[0] += 1
    return jax.random.normal(jax.random.fold_in(base_key, 100 + cnt[0]),
                             (C, N) + tuple(l.shape), jnp.float32)


g_full = jax.tree.map(_draw, template)     # per-device full-size cotangents


def local_bwd(g_loc, p_loc):
    """One device's slice of the slab aggregation backward."""
    g_loc = jax.tree.map(lambda l: l[0], g_loc)      # drop device dim
    ctx = OTACtx(p_weight=p_loc.reshape(()), key=slab_key,
                 sigma2=chan.sigma2,    # FULL (C,) — local |M| count
                 h_th=chan.h_threshold, noise_std=chan.noise_std,
                 ota_on=chan.ota_on)
    # zeros shard tree with the true local shard shapes (fwd all-gathers
    # it back to full size; values are irrelevant to the backward)
    shard = jax.tree.unflatten(
        jax.tree.structure(g_loc),
        [jnp.zeros(tuple(s // n_shards if d == _fsdp_axis_full(ax)
                         else s for d, s in enumerate(l.shape)), jnp.float32)
         for l, ax in zip(jax.tree.leaves(g_loc), axes_list)])
    _, vjp = jax.vjp(lambda t: gather(t, ctx), shard)
    (g_shards,) = vjp(g_loc)
    return g_shards


# device (cluster c, client i) consumes g_full[c, i]: the leading device
# dim is split CLIENT-major (the data_axes order), so lay it out as
# [i·C + c] — swapaxes before the reshape
g_dev_major = jax.tree.map(
    lambda l: jnp.swapaxes(l, 0, 1).reshape((N * C,) + l.shape[2:]), g_full)
spec_in = jax.tree.map(lambda l: P(("client", "cluster")), g_dev_major)
out_specs = jax.tree.unflatten(
    jax.tree.structure(template),
    [P(*[("client", "cluster") if d == _fsdp_axis_full(ax) else None
         for d in range(len(l.shape))]) if _fsdp_axis_full(ax) >= 0 else P()
     for l, ax in zip(jax.tree.leaves(template), axes_list)])

jf = jax.jit(jax.shard_map(
    local_bwd, mesh=mesh,
    in_specs=(spec_in, P("cluster", "client")),
    out_specs=out_specs,
    axis_names={"cluster", "client"}, check_vma=False))
ghat = jf(g_dev_major, p_dev)

wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p_dev, l), g_full)
ghat_ref = packed_omega_aggregate_ref(wg, slab_key, chan, N, packer)
for (ka, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(ghat)[0],
                           jax.tree_util.tree_flatten_with_path(ghat_ref)[0]):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-5,
        err_msg=f"slab bwd vs oracle at {jax.tree_util.keystr(ka)}")

# --- 3. zero-copy: no slab-sized buffer in the compiled backward ------------
hlo = jf.lower(g_dev_major, p_dev).compile().as_text()
P_slab = packer.size
hlo_audit.assert_hlo_pins(hlo, [
    hlo_audit.forbid_buffer((P_slab,), dtypes=("f32",),
                            note="full (P,) slab — zero-copy regressed"),
    hlo_audit.forbid_buffer((C, P_slab), dtypes=("f32",),
                            note="(C, P) slab"),
    hlo_audit.forbid_opcode(
        "dynamic-update-slice",
        note="pack-style scatter chain in the slab backward"),
], context="slab backward zero-copy (§3.10)")

# --- 4. retrace pin: chan VALUES never re-trace (ota_mode is static) --------
fl_tr = FLConfig(n_clusters=C, n_clients=N, weighting="fedgradnorm",
                 noise_std=0.1, tau_h=1)
init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
    model, mesh, fl_tr, tcfg, loss_kind="cls", n_out=MAXC)
state = init_fn(jax.random.PRNGKey(123))
state = jax.tree.map(
    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
    state, state_specs, is_leaf=lambda z: isinstance(z, P))
xb = jax.device_put(x, NamedSharding(mesh, batch_spec[0]))
yb = jax.device_put(y, NamedSharding(mesh, batch_spec[1]))
jstep = jax.jit(step_fn)
chans = [channel_params(FLConfig(n_clusters=C, n_clients=N,
                                 sigma2=(s2, 2 * s2), noise_std=0.1))
         for s2 in (0.25, 1.0, 4.0)]
state, _ = jstep(state, xb, yb, jax.random.PRNGKey(1), chans[0])
n_traces_after_first = len(hota_step.TRACE_LOG)
for i, ch in enumerate(chans):
    state, _ = jstep(state, xb, yb, jax.random.PRNGKey(2 + i), ch)
assert len(hota_step.TRACE_LOG) == n_traces_after_first, (
    "sweeping ChannelParams values re-traced the step: "
    f"{n_traces_after_first} -> {len(hota_step.TRACE_LOG)}")

print(f"DIST_SLAB_OK steps={STEPS} "
      f"loss={float(ms_slab[-1]['loss']):.4f} "
      f"slab_P={P_slab} traces={n_traces_after_first}")
