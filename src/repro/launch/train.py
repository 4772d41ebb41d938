"""Training CLI: the distributed HOTA-FedGradNorm round on a device mesh.

Runs any --arch's reduced (smoke) config on a (clusters, clients, model)
mesh of the visible devices, with checkpointing and metric logging. On
one TPU chip:

    PYTHONPATH=src python -m repro.launch.train --mesh 1,1,1 --steps 5

On CPU, force host devices for a larger mesh:

    XLA_FLAGS="--xla_force_host_platform_device_count=8" \\
    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \\
        --steps 50 --mesh 2,2,2

For the paper's own experiment use the faithful C=10/N=3 simulator
(``repro.core.paper_setup``, ``benchmarks/fig*``).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.checkpoint.store import latest_step, restore_checkpoint
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import FLConfig, TrainConfig
from repro.configs import ALIASES, get_smoke_config
from repro.core.hota_step import make_hota_train_step
from repro.data.lm import synthetic_lm_batches
from repro.models.model import build_model


class RoundGuard:
    """Host-side divergence recovery (DESIGN.md §3.14).

    The traced guard inside the step already degrades a non-finite or
    grad-spike round to a bit-exact skip (state frozen, ``skipped``
    metric set). This class watches that metric across rounds: after
    ``patience`` CONSECUTIVE skipped rounds it restores the full train
    state from the newest complete checkpoint — the traced skip handles
    transients, the guard handles a wedged run (e.g. a persistently
    tripping spike threshold on corrupted optimizer state). Any clean
    round resets the streak.
    """

    def __init__(self, ckpt_dir: str, abstract_state, shardings=None,
                 patience: int = 3):
        if patience < 1:
            raise ValueError(f"patience must be >= 1, got {patience}")
        self.ckpt_dir = ckpt_dir
        self.abstract_state = abstract_state
        self.shardings = shardings
        self.patience = patience
        self.streak = 0
        self.n_restores = 0

    def observe(self, skipped, state):
        """Feed one round's ``skipped`` metric; returns
        ``(state, restored)`` where ``state`` is the checkpoint-restored
        train state when the streak hit ``patience`` (and a complete
        checkpoint exists), else the state passed in, untouched."""
        if float(skipped) < 0.5:
            self.streak = 0
            return state, False
        self.streak += 1
        if self.streak < self.patience:
            return state, False
        self.streak = 0
        step = None if not self.ckpt_dir else latest_step(self.ckpt_dir)
        if step is None:          # nothing to restore from: keep going
            return state, False   # (the traced skip still froze the state)
        self.n_restores += 1
        return restore_checkpoint(self.ckpt_dir, step, self.abstract_state,
                                  shardings=self.shardings), True


def main(argv=None):
    """Train for ``--steps`` rounds; returns the last round's metrics as
    floats. ``argv`` defaults to the command line."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--mesh", default="2,2,2",
                    help="clusters,clients,model (needs that many devices)")
    ap.add_argument("--weighting", default="fedgradnorm",
                    choices=["fedgradnorm", "equal"])
    ap.add_argument("--ota-mode", default="scatter", choices=["scatter", "naive"])
    ap.add_argument("--no-ota", action="store_true")
    # section-streaming engines (DESIGN.md §3.15/§3.16). Neither flag is
    # ever silently inert: --ota-streaming is a SIMULATOR engine and the
    # distributed step rejects it by name (make_hota_step_parts guard);
    # --ota-sectioned/--max-section-rows are validated against the
    # layout gates the same way. Explicit flags skip the autotuner so
    # the tuned layout cannot clobber the requested engine.
    ap.add_argument("--ota-streaming", action="store_true",
                    help="simulator-only cluster-scan engine; the "
                         "distributed round rejects it with the reason "
                         "named (use --ota-sectioned here)")
    ap.add_argument("--ota-sectioned", action="store_true",
                    help="section-streaming slab aggregation: peak live "
                         "channel memory is one section, not the slab")
    ap.add_argument("--max-section-rows", type=int, default=0,
                    help="split packed sections above this many 128-lane "
                         "slab rows (0 = off); bounds --ota-sectioned's "
                         "peak section size")
    ap.add_argument("--memory-budget-mb", type=int, default=0,
                    help="aggregation working-set budget for the layout "
                         "autotuner (MB, 0 = unconstrained): full-slab "
                         "candidates over budget are excluded and a "
                         "budget-sized sectioned candidate is added")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the FULL train state every K rounds "
                         "(0 = only the final omega snapshot)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10,
                    help="print metrics every K rounds (and the last)")
    # fault injection (DESIGN.md §3.14) — traced knobs, one static gate
    ap.add_argument("--faults", action="store_true",
                    help="enable the fault-injection round path")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-client dropout rate")
    ap.add_argument("--blackout", type=float, default=0.0,
                    help="per-cluster blackout rate")
    ap.add_argument("--straggler", type=float, default=0.0,
                    help="per-client straggler rate")
    ap.add_argument("--staleness", type=int, default=1,
                    help="straggler staleness depth in rounds")
    ap.add_argument("--spike-norm", type=float, default=float("inf"),
                    help="skip a round whose aggregate grad norm exceeds this")
    ap.add_argument("--guard-patience", type=int, default=3,
                    help="consecutive skipped rounds before the RoundGuard "
                         "restores from the latest checkpoint")
    # section-layout autotuner (DESIGN.md §3.13) — default ON: a one-shot
    # calibration bench per template, persisted across runs
    ap.add_argument("--no-tune-layout", action="store_true",
                    help="skip the layout autotuner and keep FLConfig's "
                         "default packed layout")
    ap.add_argument("--layout-cache", default=None,
                    help="path of the persisted calibration cache "
                         "(default ~/.cache/repro/layout_tune.json or "
                         "$REPRO_LAYOUT_CACHE; pass '' to disable "
                         "persistence)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    shape = tuple(int(x) for x in args.mesh.split(","))
    n_dev = int(np.prod(shape))
    devs = np.array(jax.devices())
    if devs.size < n_dev:
        raise SystemExit(
            f"--mesh {args.mesh} needs {n_dev} devices; found {devs.size}: "
            f"{[f'{d.platform}:{d.device_kind}' for d in devs]}")
    mesh = Mesh(devs[:n_dev].reshape(shape), ("cluster", "client", "model"))

    cfg = get_smoke_config(ALIASES.get(args.arch, args.arch))
    model = build_model(cfg)
    fl = FLConfig(n_clusters=shape[0], n_clients=shape[1],
                  weighting=args.weighting, ota=not args.no_ota,
                  ota_mode=args.ota_mode, noise_std=0.1,
                  ota_streaming=args.ota_streaming,
                  ota_sectioned=args.ota_sectioned,
                  max_section_rows=args.max_section_rows,
                  faults=args.faults, dropout_rate=args.dropout,
                  blackout_rate=args.blackout,
                  straggler_rate=args.straggler,
                  staleness_rounds=args.staleness,
                  spike_norm=args.spike_norm)
    tcfg = TrainConfig(lr=args.lr)

    explicit_layout = (args.ota_streaming or args.ota_sectioned
                       or bool(args.max_section_rows))
    if not args.no_tune_layout and not explicit_layout:
        # tuned section layout, default on: the same {final, trunk}
        # template the step builds its packer from, so the tuned folds
        # are exactly the streams the run draws (checkpoint-pinned)
        from repro.common.layout_tune import layout_of, tuned_fl
        from repro.models.params import abstract_params
        template = {"final": abstract_params(model.final_specs()),
                    "trunk": abstract_params(model.trunk_specs())}
        budget = args.memory_budget_mb * (1 << 20) or None
        fl = tuned_fl(fl, template, cache_path=args.layout_cache,
                      memory_budget_bytes=budget)
        print(f"layout: {layout_of(fl).describe()}", flush=True)
    elif explicit_layout:
        from repro.common.layout_tune import layout_of
        print(f"layout: {layout_of(fl).describe()} (explicit; "
              "autotuner skipped)", flush=True)

    init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
        model, mesh, fl, tcfg, loss_kind="lm")
    state = init_fn(jax.random.PRNGKey(args.seed))
    state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state, state_specs, is_leaf=lambda x: isinstance(x, P))

    guard = None
    if args.faults and args.ckpt_dir:
        state_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_specs,
            is_leaf=lambda x: isinstance(x, P))
        guard = RoundGuard(args.ckpt_dir,
                           jax.eval_shape(init_fn, jax.random.PRNGKey(0)),
                           shardings=state_shardings,
                           patience=args.guard_patience)

    n_clients_total = shape[0] * shape[1]
    batches = synthetic_lm_batches(
        cfg.vocab_size, n_clients_total * args.batch_per_client,
        args.seq_len, seed=args.seed)
    jstep = jax.jit(step_fn)
    run_key = jax.random.PRNGKey(args.seed + 1)

    m = {}
    t0 = time.time()
    for step in range(args.steps):
        toks, labs = next(batches)
        toks = jax.device_put(jnp.asarray(toks), NamedSharding(mesh, batch_spec[0]))
        labs = jax.device_put(jnp.asarray(labs), NamedSharding(mesh, batch_spec[1]))
        state, m = jstep(state, toks, labs,
                         jax.random.fold_in(run_key, step))
        if guard is not None:
            state, restored = guard.observe(m["skipped"], state)
            if restored:
                print(f"step {step:4d} RoundGuard: {args.guard_patience} "
                      f"consecutive skipped rounds — restored from "
                      f"checkpoint step {latest_step(args.ckpt_dir)}",
                      flush=True)
        if args.ckpt_dir and args.ckpt_every \
                and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, int(state.step),
                            jax.tree.map(np.asarray, state),
                            {"arch": args.arch, "kind": "full_state"})
        if step % args.log_every == 0 or step == args.steps - 1:
            faulty = (f" part {float(m['n_participants']):.0f}"
                      f" skip {float(m['skipped']):.0f}"
                      if args.faults else "")
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"p [{float(m['p_min']):.3f},{float(m['p_max']):.3f}] "
                  f"fgrad {float(m['fgrad']):.4f}{faulty} "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)", flush=True)
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps,
                               jax.tree.map(np.asarray, state.omega),
                               {"arch": args.arch})
        print("checkpoint:", path)
    return {k: float(v) for k, v in m.items()}


if __name__ == "__main__":
    main()
