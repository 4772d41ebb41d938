"""Readings that set a training cell's limits, for many seeds in one process.

    python bench/calibrate.py --workload paper_mlp.round_c10n3 \
        --seeds 101,102,103 [--control-seeds 101,102,103] [--out file.json]

For each seed: the program's first rounds against the plain reference
(the lower readings), and for the control seeds the reference computed in
the precision below the configuration's, put in the program's place, and
the program with a planted fault (the upper readings). Faults: ``half``
leaves out half of each client's batch, so the loss is the mean over the
rest; ``frozen`` returns the state unchanged from every round;
``no_signal`` leaves the gradients out of the over-the-air sum, so the
server receives the channel noise alone; ``p_ignored`` transmits the
gradients unweighted, as if FedGradNorm's p were all ones. Prints one
JSON line per seed and run. Needs the cell's chips, like ``run.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


CONTROL_DTYPE = {"float32": "bfloat16"}


def _wrap_step(fault):
    def plant(cell):
        cell.step = fault(cell.step)
        return lambda: None
    return plant


def _half(step):
    def broken(state, x, y, key):
        h = x.shape[2] // 2
        return step(state, x[:, :, :h], y[:, :, :h], key)
    return broken


def _frozen(step):
    def broken(state, x, y, key):
        return state, step(state, x, y, key)[1]
    return broken


def _aggregation(change):
    """A fault inside the round's over-the-air sum: the client-folded
    aggregation is called with ``change(grads, p)`` in place of its
    gradients and weights. Planted before the round is first traced."""
    def plant(cell):
        from repro.core import ota
        orig = ota.ota_aggregate_client_folded

        def broken(key, grads, p, *args, **kw):
            return orig(key, *change(grads, p), *args, **kw)

        ota.ota_aggregate_client_folded = broken

        def undo():
            ota.ota_aggregate_client_folded = orig
        return undo
    return plant


def _no_signal(grads, p):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.zeros_like, grads), p


def _p_ignored(grads, p):
    import jax.numpy as jnp
    return grads, jnp.ones_like(p)


# name -> plant(cell) -> undo()
FAULTS = {"half": _wrap_step(_half), "frozen": _wrap_step(_frozen),
          "no_signal": _aggregation(_no_signal),
          "p_ignored": _aggregation(_p_ignored)}


def readings_for(spec, seed, run="program", require_chip=True):
    """Readings of one seed. ``run`` is "program", "control" or a name
    in ``FAULTS``."""
    import gc

    from bench import harness

    cfg, traffic = spec["cfg"], spec["traffic"]
    root = spec["root"]
    _, devs, _ = harness.device_info(spec["cell"]["chips"], require_chip)
    model_mod = harness.module("configs", spec["cell"]["config"], root)
    engine = harness.module("engines", traffic["engine"], root)
    generator = harness.module("generators", traffic["generator"], root)
    seeds = harness.Seeds(seed)

    cell = engine.Cell(cfg, traffic, model_mod, generator, seeds, devs)
    undo = FAULTS[run](cell) if run in FAULTS else (lambda: None)
    prog = None
    try:
        if run != "control":
            for _ in range(traffic["check"]["steps"]):
                cell.round()
            prog = cell.readings()
    finally:
        undo()
    check_in = cell.check_inputs()
    cell.release()
    del cell
    gc.collect()
    return harness.follow_reference(
        model_mod, cfg, traffic, seeds, check_in, prog,
        dtype=CONTROL_DTYPE[cfg["param_dtype"]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from bench.harness import cell_spec, use_checkout_cache
    use_checkout_cache()

    spec = cell_spec(args.workload)
    plan = [(int(s), "program") for s in args.seeds.split(",") if s]
    for s in (x for x in args.control_seeds.split(",") if x):
        plan.append((int(s), "control"))
        plan += [(int(s), f) for f in args.faults.split(",") if f]
    rows = []
    for seed, run in plan:
        t = time.perf_counter()
        r = readings_for(spec, seed, run)
        row = {"seed": seed, "run": run, "readings": r,
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(f"calibrate: {len(rows)} runs in {time.perf_counter() - T0:.1f} s",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
