"""Smoke run of the federated round on a TPU, through the normal entry points.

    python chip_smoke.py               # one chip: phases 1 and 2
    python chip_smoke.py --four-chips  # four chips: phase 3 only

1. Paper round, simulator: the Table-I MLP (3.9M params) at the paper's
   setting (C=10 clusters × N=3 clients, batch 24, H_th=3.2e-2, noise
   std 1.0, FedGradNorm) through ``HotaSim.step``. The compiled step must
   hold Mosaic kernels (``tpu_custom_call``); one round's OTA aggregate
   is recomputed by the jnp reference on the same bits (masks identical,
   estimates to float32 tolerance).
2. Training CLI: ``repro.launch.train`` on one smoke config, mesh
   1,1,1, five rounds, explicit layout (no autotuner).
3. Four chips: the distributed engine on a 2×2 (cluster × client) mesh
   against ``HotaSim`` on the same setting (OTA off, equal weighting),
   then OTA-on FedGradNorm rounds; state shards must sit on all four.

Everything runs in this one process (a chip belongs to one process).
The script refuses to run without a TPU, and any failed check exits
non-zero. The last line of standard output is one JSON object naming
the device.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.common.config import (  # noqa: E402
    FLConfig, ModelConfig, TrainConfig,
)
from repro.common.flatpack import packer_for  # noqa: E402
from repro.core import ota  # noqa: E402
from repro.core.paper_setup import paper_mlp_setup  # noqa: E402
from repro.core.sim import HotaSim  # noqa: E402
from repro.kernels.ota_channel.ops import (  # noqa: E402
    ota_client_fold_apply, ota_mask_count_apply, ota_stream_fold_apply,
)

# paper Sec. IV (benchmarks/paper_common.py)
PAPER = dict(n_clusters=10, n_clients=3, h_threshold=3.2e-2, noise_std=1.0,
             weighting="fedgradnorm")
PAPER_BATCH = 24
EST_TOL = 1e-5          # kernel vs jnp estimate: rtol and atol


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


def compile_with_kernels(jitted, *args):
    """AOT-compile ``jitted`` and count its Mosaic kernel calls."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    secs = time.perf_counter() - t0
    n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    return compiled, n, secs


# ---------------------------------------------------------------- phase 1
def _aggregate_vs_reference(sim, state, x, y, key):
    """One round's client gradients, aggregated leaf by leaf by the
    kernels and by the jnp reference on the identical streams."""
    fl = sim.fl
    c, n = fl.n_clusters, fl.n_clients
    upd = jax.vmap(jax.vmap(sim._client_update,
                            in_axes=(None, 0, 0, 0, 0, 0)),
                   in_axes=(None, 0, 0, 0, 0, None))
    packer = packer_for(state.omega, tail="final", sections=fl.ota_sections,
                        min_section_rows=fl.min_section_rows,
                        max_section_rows=fl.max_section_rows)
    chan = sim.chan

    @jax.jit
    def run(state, x, y, key):
        _, _, g, _ = upd(state.omega, state.heads, state.head_opt, x, y,
                         sim.n_classes)
        chan_key = ota.sim_channel_key(key)
        gbits = ota.section_gain_streams(chan_key, packer, c)
        nbits = ota.section_noise_streams(chan_key, packer)
        leaves = packer.treedef.flatten_up_to(g)
        est_k, est_r, mask_k, mask_r, cnt_k, cnt_r = [], [], [], [], [], []
        for r in packer.leaf_runs():
            b = jax.lax.slice(gbits[r.section], (0, r.offset),
                              (c, r.offset + r.size))
            nb = jax.lax.slice(nbits[r.section], (r.offset,),
                               (r.offset + r.size,))
            leaf = leaves[r.leaf]
            for impl, est in (("pallas", est_k), ("jnp", est_r)):
                est.append(ota_client_fold_apply(
                    leaf, state.p, b, nb, chan.sigma2, chan.h_threshold,
                    chan.noise_std, chan.ota_on, n, impl=impl).reshape(-1))
            for impl, masks, cnts in (("pallas", mask_k, cnt_k),
                                      ("jnp", mask_r, cnt_r)):
                for l in range(c):
                    _, mask = ota_stream_fold_apply(
                        leaf[l], state.p[l], b[l], chan.sigma2[l],
                        chan.h_threshold, chan.ota_on, impl=impl)
                    masks.append(mask.reshape(-1))
                _, cnt = ota_mask_count_apply(
                    jnp.sum(leaf, axis=(0, 1)), b, 0, chan.sigma2,
                    chan.h_threshold, chan.ota_on, 1.0, impl=impl)
                cnts.append(cnt.reshape(-1))
        cat = jnp.concatenate
        return (cat(est_k), cat(est_r), cat(mask_k), cat(mask_r),
                cat(cnt_k), cat(cnt_r))

    with jax.default_matmul_precision("highest"):
        compiled, n_kernels, _ = compile_with_kernels(run, state, x, y, key)
    return n_kernels, [np.asarray(a) for a in compiled(state, x, y, key)]


def phase_paper_round(rounds=10):
    log("== phase 1: paper round, simulator (C=10, N=3, batch 24) ==")
    t0 = time.perf_counter()
    fl = FLConfig(**PAPER)
    sim, batcher = paper_mlp_setup(fl, batch=PAPER_BATCH, seed=0)
    state = sim.init(jax.random.PRNGKey(0))
    n_params = sum(int(l.size) for l in jax.tree.leaves(state.omega))
    log(f"shared params: {n_params}")
    x, y = (jnp.asarray(a) for a in batcher.next_stacked())
    key0 = jax.random.PRNGKey(1000)
    step, n_kernels, secs = compile_with_kernels(
        HotaSim._step, sim, state, x, y, key0, sim.chan, sim.faults)
    log(f"compiled HotaSim step: {n_kernels} tpu_custom_call "
        f"({secs:.1f} s to compile)")
    check(n_kernels > 0, "the compiled simulator step holds no Mosaic kernel")

    losses = []
    for r in range(rounds):
        if r:
            x, y = (jnp.asarray(a) for a in batcher.next_stacked())
        state, m = step(state, x, y, jax.random.PRNGKey(1000 + r),
                        sim.chan, sim.faults)
        loss = np.asarray(m["loss"])              # (C, N)
        p = np.asarray(m["p"])
        losses.append(loss)
        log(f"round {r:2d} loss per task "
            + " ".join(f"{v:.4f}" for v in loss.mean(axis=0))
            + " | p " + " ".join(f"{v:.3f}" for v in p.mean(axis=0)))
    check(all(np.isfinite(l).all() for l in losses),
          "non-finite paper-round loss")

    n_k, (est_k, est_r, mask_k, mask_r, cnt_k, cnt_r) = (
        _aggregate_vs_reference(sim, state, x, y,
                                jax.random.PRNGKey(2000)))
    est_diff = float(np.max(np.abs(est_k - est_r)))
    n_mask_diff = int(np.sum(mask_k != mask_r))
    n_cnt_diff = int(np.sum(cnt_k != cnt_r))
    log(f"aggregate vs jnp reference ({n_k} tpu_custom_call): "
        f"{est_k.size} estimates, largest |diff| {est_diff:.3e}; "
        f"{mask_k.size} mask entries, {n_mask_diff} differ; "
        f"{cnt_k.size} |M| counts, {n_cnt_diff} differ; "
        f"pass rate {float(mask_k.mean()):.4f}")
    check(n_k > 0, "the aggregate comparison ran no Mosaic kernel")
    check(np.isfinite(est_k).all(), "non-finite kernel estimate")
    check(n_mask_diff == 0, "kernel masks differ from the jnp reference")
    check(n_cnt_diff == 0, "kernel |M| counts differ from the jnp reference")
    check(np.allclose(est_k, est_r, rtol=EST_TOL, atol=EST_TOL),
          f"kernel estimate off the jnp reference by {est_diff:.3e}")
    log(f"phase 1 ok ({time.perf_counter() - t0:.1f} s wall, "
        "compiles included)")


# ---------------------------------------------------------------- phase 2
def phase_train_cli(steps=5):
    log("== phase 2: training CLI (repro.launch.train, mesh 1,1,1) ==")
    from repro.launch.train import main as train_main
    t0 = time.perf_counter()
    m = train_main(["--mesh", "1,1,1", "--steps", str(steps),
                    "--no-tune-layout", "--log-every", "1"])
    check(np.isfinite(m["loss"]), f"non-finite training loss {m['loss']}")
    log(f"phase 2 ok: {steps} launch.train steps, last loss "
        f"{m['loss']:.4f} ({time.perf_counter() - t0:.1f} s wall)")


# ---------------------------------------------------------------- phase 3
def phase_four_chips(steps=3, ota_rounds=5):
    """The distributed engine over a 2×2 (cluster × client) mesh against
    the simulator on the same setting (the dist_vs_sim comparison)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.hota_step import make_hota_train_step
    from repro.models.model import build_model
    from repro.models.params import init_params

    log("== phase 3: distributed engine, 2x2 (cluster x client) mesh ==")
    t0 = time.perf_counter()
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 chips, found {len(devs)}")
    devs = devs[:4]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("cluster", "client"))
    c, n, b, d, maxc = 2, 2, 4, 256, 8
    model = build_model(ModelConfig(family="mlp", compute_dtype="float32"))
    tcfg = TrainConfig(lr=1e-3)

    key = jax.random.PRNGKey(0)
    omega = {"final": init_params(model.final_specs(),
                                  jax.random.fold_in(key, 7)),
             "trunk": init_params(model.trunk_specs(), key)}
    head0 = init_params(model.head_specs(maxc), jax.random.fold_in(key, 9))
    x = jax.random.normal(jax.random.fold_in(key, 1), (c, n, b, d))
    y = jax.random.randint(jax.random.fold_in(key, 2), (c, n, b), 0, maxc)

    def dist_setup(fl):
        init_fn, step_fn, specs, batch_spec = make_hota_train_step(
            model, mesh, fl, tcfg, loss_kind="cls", n_out=maxc)
        st = init_fn(jax.random.PRNGKey(123))
        st = st._replace(omega=omega, heads=jax.tree.map(
            lambda h: jnp.broadcast_to(h, (c * n,) + h.shape).copy(),
            head0))
        st = jax.tree.map(lambda a, s: jax.device_put(
            a, NamedSharding(mesh, s)), st, specs,
            is_leaf=lambda v: isinstance(v, P))
        xs = jax.device_put(x.reshape(c * n * b, d),
                            NamedSharding(mesh, batch_spec[0]))
        ys = jax.device_put(y.reshape(c * n * b),
                            NamedSharding(mesh, batch_spec[1]))
        return jax.jit(step_fn), st, xs, ys

    with jax.default_matmul_precision("highest"):
        # --- simulator, error-free and equal-weighted ---
        fl_sim = FLConfig(n_clusters=c, n_clients=n, weighting="equal",
                          ota=False, tau_h=1)
        sim = HotaSim(model, fl_sim, tcfg, [maxc] * n)
        state = sim.init(jax.random.PRNGKey(123))
        state = state._replace(omega=omega, heads=jax.tree.map(
            lambda h: jnp.broadcast_to(h, (c, n) + h.shape).copy(), head0))
        sim_losses = []
        for s in range(steps):
            state, m = sim.step(state, x, y, jax.random.PRNGKey(7 + s))
            sim_losses.append(float(np.asarray(m["loss"]).mean()))
        sim_omega = jax.tree.map(np.asarray, state.omega)

        # --- distributed engine, same setting ---
        jstep, dstate, xs, ys = dist_setup(FLConfig(
            n_clusters=c, n_clients=n, weighting="equal", ota=False,
            tau_h=1, ota_mode="scatter"))
        key7 = jax.random.PRNGKey(7)
        step, n_kernels, secs = compile_with_kernels(jstep, dstate, xs, ys,
                                                     key7)
        log(f"compiled distributed step: {n_kernels} tpu_custom_call "
            f"({secs:.1f} s to compile)")
        check(n_kernels > 0, "the distributed step holds no Mosaic kernel")
        placed = {dv for leaf in jax.tree.leaves(dstate)
                  for dv in leaf.sharding.device_set}
        check(placed == set(devs),
              f"state sits on {len(placed)} devices, not the 4 of the mesh")
        fsdp = [leaf for leaf in jax.tree.leaves(dstate.omega)
                if leaf.addressable_shards[0].data.size < leaf.size]
        check(fsdp and all(len({s.device for s in leaf.addressable_shards})
                           == 4 for leaf in fsdp),
              "FSDP shards do not cover all four devices")
        log(f"state on all 4 devices; {len(fsdp)} omega leaves FSDP-sharded "
            f"4 ways")
        dist_losses = []
        for s in range(steps):
            dstate, dm = step(dstate, xs, ys, jax.random.PRNGKey(7 + s))
            dist_losses.append(float(dm["loss"]))
        dist_omega = jax.tree.map(np.asarray, dstate.omega)

        gaps = [abs(a - b_) for a, b_ in zip(sim_losses, dist_losses)]
        log("sim  losses " + " ".join(f"{v:.6f}" for v in sim_losses))
        log("dist losses " + " ".join(f"{v:.6f}" for v in dist_losses))
        flat_a = np.concatenate([v.ravel()
                                 for v in jax.tree.leaves(sim_omega)])
        flat_b = np.concatenate([v.ravel()
                                 for v in jax.tree.leaves(dist_omega)])
        diff = np.abs(flat_a - flat_b)
        flip = float((diff > tcfg.lr).mean())
        log(f"dist vs sim: largest loss gap {max(gaps):.3e}, largest param "
            f"diff {diff.max():.3e}, Adam sign-flip fraction {flip:.4f}")
        check(max(gaps) < 2e-4, f"dist/sim loss gap {max(gaps):.3e}")
        check(diff.max() < 2 * steps * tcfg.lr + 1e-5,
              f"dist/sim param diff {diff.max():.3e}")
        check(flip < 0.05, f"dist/sim flip fraction {flip:.4f}")

        # --- OTA on, FedGradNorm ---
        jstep, dstate, xs, ys = dist_setup(FLConfig(
            n_clusters=c, n_clients=n, weighting="fedgradnorm", ota=True,
            h_threshold=3.2e-2, noise_std=1.0))
        for r in range(ota_rounds):
            dstate, dm = jstep(dstate, xs, ys, jax.random.PRNGKey(50 + r))
            log(f"OTA round {r} loss {float(dm['loss']):.4f} p "
                f"[{float(dm['p_min']):.3f}, {float(dm['p_max']):.3f}] "
                f"gnorm {float(dm['gnorm_mean']):.4f}")
            check(np.isfinite(float(dm["loss"])), "non-finite OTA-on loss")
    log(f"phase 3 ok ({time.perf_counter() - t0:.1f} s wall, "
        "compiles included)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip distributed phase")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU visible (found {dev.platform}: "
              f"{dev.device_kind}); refusing to run", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_paper_round()
            phase_train_cli()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
