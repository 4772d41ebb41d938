"""Kernel microbenchmarks: us/call of each Pallas kernel (interpret mode on
this CPU container — wall times are NOT TPU times; the oracle comparison
shows relative cost of the fused formulation) and of the pure-jnp oracle.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import (
    flash_attention, flash_attention_reference,
    masked_gradnorm, masked_gradnorm_reference,
)


def _time(fn, *args, iters=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    rows = []
    key = jax.random.PRNGKey(0)

    g = jax.random.normal(key, (8, 1 << 16))
    m = jax.random.uniform(jax.random.fold_in(key, 1), (1 << 16,)) > 0.3
    rows.append(("masked_gradnorm_pallas_8x64k",
                 _time(masked_gradnorm, g, m, impl="pallas"),
                 "tiled masked L2 (impl forced)"))
    rows.append(("masked_gradnorm_dispatch_8x64k",
                 _time(masked_gradnorm, g, m),
                 "default dispatch (jnp off-TPU)"))
    rows.append(("masked_gradnorm_ref_8x64k",
                 _time(masked_gradnorm_reference, g, m), "jnp oracle"))

    q = jax.random.normal(key, (1, 1024, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 1024, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 1024, 2, 64))
    rows.append(("flash_attn_pallas_1k", _time(
        flash_attention, q, k, v, iters=2, block_q=256, block_kv=256),
        "interpret mode"))
    rows.append(("flash_attn_ref_1k", _time(
        flash_attention_reference, q, k, v, iters=2), "jnp oracle"))
    return rows


def _client_grad_tree(n_params: int, n_leaves: int, C: int, N: int, key):
    """Synthetic RAW per-client gradient pytree — leaves (C, N, ...)."""
    final_n = max(128, n_params // 20)
    trunk_n = max(128, (n_params - final_n) // n_leaves)
    tree = {"final": {"w": jax.random.normal(key, (C, N, final_n))},
            "trunk": {}}
    for i in range(n_leaves):
        tree["trunk"][f"l{i}"] = jax.random.normal(
            jax.random.fold_in(key, i + 1), (C, N, trunk_n))
    return tree


def _paper_mlp_client_tree(C: int, N: int, key) -> dict:
    """The real paper-MLP omega shapes, (cluster, client)-batched."""
    from repro.common.config import ModelConfig
    from repro.models.model import build_model
    from repro.models.params import ParamSpec

    model = build_model(ModelConfig(family="mlp"))
    specs = {"final": model.final_specs(), "trunk": model.trunk_specs()}
    i = [0]

    def draw(spec):
        i[0] += 1
        return jax.random.normal(jax.random.fold_in(key, i[0]),
                                 (C, N) + spec.shape)
    return jax.tree.map(draw, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def client_folded_rows(n_scenarios: int = 8, iters: int = 3,
                       quick: bool = False):
    """Client-folded zero-copy OTA (DESIGN.md §3.12) vs the sim's old
    formulation (einsum the client weights, then per-leaf jnp channel) —
    BOTH paths start from the RAW (C, N, ...) gradient tree + the (C, N)
    weight matrix, i.e. exactly what ``HotaSim.step_with_channel`` holds
    after the local phase."""
    from repro.common.config import FLConfig
    from repro.common.flatpack import packer_for
    from repro.core import ota
    from repro.core.channel import channel_params, stack_channel_params

    rows = []
    key = jax.random.PRNGKey(0)
    N = 3
    cases = [
        ("paperMLP_3.9M", None, 10),            # real Table-I shapes
        ("1M_x32leaves", (1 << 20, 32), 10),
        ("16M_x64leaves", (1 << 24, 64), 10),   # raw grads = 1.9 GB
    ]
    if quick:                                   # CI smoke: small case only
        cases, n_scenarios, iters = cases[:1], min(n_scenarios, 4), 1
    for label, spec, C in cases:
        if spec is None:
            g = _paper_mlp_client_tree(C, N, key)
        else:
            g = _client_grad_tree(spec[0], spec[1], C, N, key)
        p = jax.random.uniform(jax.random.fold_in(key, 99), (C, N),
                               jnp.float32, 0.5, 1.5)
        n_leaves = len(jax.tree.leaves(g))
        fl = FLConfig(n_clusters=C, n_clients=N,
                      sigma2=tuple(0.25 + 0.25 * i for i in range(C)))
        chan = channel_params(fl)
        template = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype), g)
        packer = packer_for(template, tail="final", sections="toplevel")

        f_fold = jax.jit(lambda k, gg, pp, ch: ota.ota_aggregate_client_folded(
            k, gg, pp, ch, N, packer))
        f_leaf = jax.jit(lambda k, gg, pp, ch: ota.ota_aggregate_tree(
            k, jax.tree.map(
                lambda l: jnp.einsum("cn,cn...->c...", pp, l), gg), ch, N))
        t_fold = _time(f_fold, key, g, p, chan, iters=iters)
        t_leaf = _time(f_leaf, key, g, p, chan, iters=iters)
        rows.append((f"ota_agg_clientfold_{label}", t_fold,
                     f"{n_leaves} leaves,C={C},N={N};zero-copy client fold"))
        rows.append((f"ota_agg_perleaf_raw_{label}", t_leaf,
                     f"einsum+jnp per-leaf;"
                     f"clientfold_speedup={t_leaf / t_fold:.2f}x"))

        # autotuned layout (DESIGN.md §3.13): the calibration sweep picks
        # engine x sections x coalescing threshold; when it picks the
        # per-leaf engine the tuned path IS f_leaf (reuse its time), so
        # the tuned row is >= 1.0x vs per-leaf by construction and > 1.0x
        # exactly where a coalesced slab layout genuinely wins
        from repro.common.layout_tune import packer_for_layout, tune_layout
        choice = tune_layout(template, C, N, iters=max(1, iters - 1))
        if choice.engine == "slab":
            tuned_pk = packer_for_layout(template, choice)
            f_tuned = jax.jit(
                lambda k, gg, pp, ch: ota.ota_aggregate_client_folded(
                    k, gg, pp, ch, N, tuned_pk))
            t_tuned = _time(f_tuned, key, g, p, chan, iters=iters)
        elif choice.engine == "sectioned":
            tuned_pk = packer_for_layout(template, choice)
            f_tuned = jax.jit(
                lambda k, gg, pp, ch: ota.ota_aggregate_sectioned(
                    k, gg, pp, ch, N, tuned_pk))
            t_tuned = _time(f_tuned, key, g, p, chan, iters=iters)
        else:
            t_tuned = t_leaf
        rows.append((f"ota_agg_clientfold_tuned_{label}", t_tuned,
                     f"layout={choice.describe()};"
                     f"tuned_speedup={t_leaf / t_tuned:.2f}x_vs_perleaf"))

        # banked: vmap over an (S,)-batched ChannelParams bank — shared
        # key/grads/weights (CRN); the key-only stream draw hoists out of
        # the scenario vmap by construction
        bank = stack_channel_params(
            [channel_params(FLConfig(
                n_clusters=C, n_clients=N,
                sigma2=(0.25 + 0.25 * (s % 8),),
                ota=(s % 4 != 3))) for s in range(n_scenarios)])
        fb_fold = jax.jit(jax.vmap(
            lambda ch, k, gg, pp: ota.ota_aggregate_client_folded(
                k, gg, pp, ch, N, packer),
            in_axes=(0, None, None, None)))
        fb_leaf = jax.jit(jax.vmap(
            lambda ch, k, gg, pp: ota.ota_aggregate_tree(
                k, jax.tree.map(
                    lambda l: jnp.einsum("cn,cn...->c...", pp, l), gg),
                ch, N),
            in_axes=(0, None, None, None)))
        tb_fold = _time(fb_fold, bank, key, g, p, iters=iters)
        tb_leaf = _time(fb_leaf, bank, key, g, p, iters=iters)
        rows.append((f"ota_agg_clientfold_S{n_scenarios}_{label}", tb_fold,
                     "banked vmap"))
        rows.append((f"ota_agg_perleaf_raw_S{n_scenarios}_{label}", tb_leaf,
                     f"clientfold_speedup={tb_leaf / tb_fold:.2f}x"))
        del g
    return rows


def layout_tune_rows(quick: bool = False, iters: int = 2):
    """The section-layout autotuner's calibration sweep (DESIGN.md
    §3.13), reported as bench rows: one row per candidate layout
    (engine x sections x coalescing threshold) per template, plus the
    chosen LayoutChoice. This is what ``run.py --tune`` emits — the CI
    smoke runs it quick to pin that the sweep executes end to end."""
    from repro.common.layout_tune import calibrate_layout

    rows = []
    key = jax.random.PRNGKey(0)
    N = 3
    cases = [
        ("paperMLP_3.9M", None, 10),
        ("1M_x32leaves", (1 << 20, 32), 10),   # the adversarial layout
    ]
    if quick:
        cases, iters = cases[:1], 1
    for label, spec, C in cases:
        if spec is None:
            g = _paper_mlp_client_tree(C, N, key)
        else:
            g = _client_grad_tree(spec[0], spec[1], C, N, key)
        template = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype), g)
        del g
        choice, report = calibrate_layout(template, C, N, iters=iters)
        for entry in report:
            rows.append((f"layout_tune_{label}[{entry['layout']}]",
                         entry["us"], "calibration candidate"))
        rows.append((f"layout_tune_{label}_chosen", 0.0,
                     f"layout={choice.describe()}"))
    return rows


def _time_bank(bank, batches, keys, steps, block):
    """(compile_s, steady_step_s) of one bank flavor over the shared
    batch/key schedule."""
    import time as _time_mod
    t0 = _time_mod.perf_counter()
    states = bank.init(jax.random.PRNGKey(0))
    states, _ = bank.step(states, *batches[0], keys[0])   # compile
    block(states)
    compile_s = _time_mod.perf_counter() - t0
    t0 = _time_mod.perf_counter()
    for t in range(1, steps + 1):
        states, _ = bank.step(states, *batches[t], keys[t])
    block(states)
    return compile_s, (_time_mod.perf_counter() - t0) / steps


def sweep_rows(n_scenarios: int = 8, steps: int = 3, n_clusters: int = 10,
               n_clients: int = 3, batch: int = 24,
               include_sequential: bool = True):
    """ScenarioBank (one jit, vmap over S scenarios) vs ShardedScenarioBank
    (scenario axis on the device mesh) vs the old sequential Python loop
    (S re-jitted HotaSims) on the paper-scale MLP config. Reports
    steady-state per-round wall time for the WHOLE scenario set and total
    wall including compilation. Sharded rows appear only when more than
    one device is visible and the device count divides S (force host
    devices with XLA_FLAGS=--xla_force_host_platform_device_count)."""
    import dataclasses
    import time as _time_mod

    from repro.common.config import FLConfig, TrainConfig
    from repro.core.paper_setup import paper_mlp_setup
    from repro.core.sim import HotaSim
    from repro.core.sweep import ScenarioBank, ShardedScenarioBank

    base_fl = FLConfig(n_clusters=n_clusters, n_clients=n_clients)
    sim, batcher = paper_mlp_setup(base_fl, batch=batch, n_points=6000)

    sigmas = [0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0]
    scenarios = [
        dict(sigma2=(sigmas[s % len(sigmas)],),
             weighting="fedgradnorm" if s % 2 == 0 else "equal")
        for s in range(n_scenarios)
    ]
    batches = [[jnp.asarray(a) for a in batcher.next_stacked()]
               for _ in range(steps + 1)]
    keys = [jax.random.PRNGKey(s) for s in range(steps + 1)]

    def _block(x):
        jax.block_until_ready(jax.tree.leaves(x)[0])

    # --- banked: one jit over all scenarios -------------------------------
    t_compile_bank, bank_step = _time_bank(
        ScenarioBank(sim, scenarios), batches, keys, steps, _block)
    bank_total = t_compile_bank + bank_step * steps
    rows = [(f"sweep_bank_S{n_scenarios}_step", bank_step * 1e6,
             f"total={bank_total:.2f}s(incl compile)")]

    # --- sharded: the same jit, scenario axis split across devices --------
    n_dev = len(jax.devices())
    if n_dev > 1 and n_scenarios % n_dev == 0:
        t_compile_sh, sh_step = _time_bank(
            ShardedScenarioBank(sim, scenarios), batches, keys, steps,
            _block)
        sh_total = t_compile_sh + sh_step * steps
        rows += [
            (f"sweep_sharded_S{n_scenarios}_step", sh_step * 1e6,
             f"total={sh_total:.2f}s(incl compile);{n_dev} devices"),
            (f"sweep_sharded_speedup_S{n_scenarios}", 0.0,
             f"steady={bank_step/sh_step:.2f}x_vs_vmap;"
             f"end_to_end={bank_total/sh_total:.2f}x"),
        ]

    # --- sequential: one re-jitted HotaSim per scenario -------------------
    if include_sequential:
        t0 = _time_mod.perf_counter()
        seq_steady = 0.0
        n_cls = [int(c) for c in sim.n_classes]
        for spec in scenarios:
            fl_s = dataclasses.replace(base_fl, **spec)
            sim_s = HotaSim(sim.model, fl_s, TrainConfig(lr=3e-4), n_cls)
            st = sim_s.init(jax.random.PRNGKey(0))
            st, _ = sim_s.step(st, *batches[0], keys[0])      # compile
            _block(st)
            t1 = _time_mod.perf_counter()
            for t in range(1, steps + 1):
                st, _ = sim_s.step(st, *batches[t], keys[t])
            _block(st)
            seq_steady += _time_mod.perf_counter() - t1
        seq_total = _time_mod.perf_counter() - t0
        seq_step = seq_steady / steps
        rows += [
            (f"sweep_seq_S{n_scenarios}_step", seq_step * 1e6,
             f"total={seq_total:.2f}s(incl {n_scenarios}x compile)"),
            (f"sweep_speedup_S{n_scenarios}", 0.0,
             f"steady={seq_step/bank_step:.2f}x;"
             f"end_to_end={seq_total/bank_total:.2f}x"),
        ]
    return rows


if __name__ == "__main__":
    for name, us, note in (run() + client_folded_rows()
                           + layout_tune_rows() + sweep_rows()):
        print(f"{name},{us:.0f},{note}")
