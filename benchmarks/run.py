"""Benchmark entry point: one function per paper table/figure + the
framework's own harnesses. Prints ``name,us_per_call,derived`` CSV.

Default mode is quick (reads cached results where the full experiments are
long-running; see scripts/run_paper_experiments.sh and
scripts/run_dryrun_sweep.sh for the full passes). ``--full`` recomputes the
paper figures at full length.

One parser, one mode: the row-set selectors (``--kernels``/``--sweep``/
``--tune``/``--faults``/``--sample``/``--dist``/``--sections``) are
mutually exclusive and unknown flags are an ERROR — the old
``parse_known_args`` silently ignored typos like ``--smoke=1`` or a
misspelled mode and ran the wrong (often much longer) benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _figure_rows(results: dict):
    """Derive the paper's claim metrics from cached loss curves."""
    rows = []
    for name, r in results.items():
        # wall_s covers the whole sweep; divide by sweep size for this
        # scenario's share
        wall_us = (r.get("wall_s", 0.0) / max(r.get("sweep_size", 1), 1)
                   / max(r["steps"], 1) * 1e6)
        auc = sum(r["auc_loss_per_task"]) / len(r["auc_loss_per_task"])
        rows.append((name, wall_us, f"mean_auc_loss={auc:.4f}"))
    return rows


def _print_rows(rows) -> None:
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def _write_rows_json(rows, path: str, merge: bool = False) -> None:
    """Write rows as the perf-trajectory JSON artifact. With ``merge``,
    update an existing artifact by row name — a partial (smoke/tune)
    pass refreshes only the rows it ran and the committed full-size
    rows survive."""
    new = {n: {"name": n, "us_per_call": round(us, 1), "derived": d}
           for n, us, d in rows}
    merged = []
    if merge and os.path.exists(path):
        with open(path) as f:
            merged = [new.pop(row["name"], row)
                      for row in json.load(f).get("rows", [])]
    merged += list(new.values())
    with open(path, "w") as f:
        json.dump({"rows": merged}, f, indent=1)


def _mode_json_path(args, default: str) -> str | None:
    """The JSON artifact path for a non-kernel mode: honor an explicit
    --json PATH; the bare flag's const names the kernel artifact, so
    each mode defaults to its own file instead."""
    if not args.json:
        return None
    return default if args.json == "BENCH_kernels.json" else args.json


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--kernels", action="store_true",
                      help="kernel/packed/sweep rows only (skip paper "
                           "figures and roofline)")
    mode.add_argument("--sweep", action="store_true",
                      help="sweep-engine rows only (sharded vs vmap vs "
                           "sequential banks) on a forced multi-device CPU "
                           "mesh; with --json writes BENCH_sweep.json")
    mode.add_argument("--tune", action="store_true",
                      help="section-layout autotuner rows only (the "
                           "calibration sweep of DESIGN.md §3.13 per bench "
                           "template); with --json merges into "
                           "BENCH_kernels.json by row name")
    mode.add_argument("--faults", action="store_true",
                      help="fault-injection rows only (round throughput vs "
                           "dropout rate on the slab sim engine, DESIGN.md "
                           "§3.14); with --json writes BENCH_faults.json")
    mode.add_argument("--sample", action="store_true",
                      help="client-sampling rows only (round throughput vs "
                           "population size at fixed C*N, plus the "
                           "streaming aggregator, DESIGN.md §3.15); with "
                           "--json writes BENCH_sample.json")
    mode.add_argument("--dist", action="store_true",
                      help="distributed-step rows only (slab-native vs "
                           "per-leaf engines + the 2-D scenario × client "
                           "bank) on a forced 4-device CPU mesh; with "
                           "--json writes BENCH_dist.json")
    mode.add_argument("--sections", action="store_true",
                      help="section-streaming rows only (sectioned vs "
                           "full-slab engines with estimated peak working "
                           "set, DESIGN.md §3.16); with --json writes "
                           "BENCH_sections.json")
    ap.add_argument("--full", action="store_true",
                    help="recompute paper figures at full length")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast variant of every row set (CI)")
    ap.add_argument("--sweep-devices", type=int, default=2,
                    help="forced host device count for --sweep (default 2)")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="also write the rows to PATH as JSON (default "
                         "BENCH_kernels.json) — the perf trajectory "
                         "artifact; each mode defaults to its own "
                         "BENCH_<mode>.json")
    return ap


def main() -> None:
    args = build_parser().parse_args()

    if args.sweep or args.dist:
        # must land before ANY jax import in this process
        n_dev = 4 if args.dist else args.sweep_devices
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{n_dev}").strip()

    if args.tune:
        # --- section-layout autotuner calibration (DESIGN.md §3.13) ------
        from benchmarks.kernel_bench import layout_tune_rows
        trows = layout_tune_rows(quick=args.smoke,
                                 iters=1 if args.smoke else 2)
        if args.json:
            # merge into the kernel artifact by row name: a tune pass
            # refreshes only its own rows and leaves the committed
            # kernel rows intact
            _write_rows_json(trows, args.json, merge=True)
        _print_rows(trows)
        return

    if args.faults:
        # --- fault injection: rounds/sec vs dropout rate (§3.14) ---------
        from benchmarks.faults_bench import fault_rows
        frows = fault_rows(smoke=args.smoke)
        if args.json:
            _write_rows_json(frows, _mode_json_path(args, "BENCH_faults.json"))
        _print_rows(frows)
        return

    if args.sample:
        # --- client sampling: rounds/sec vs population size (§3.15) ------
        from benchmarks.sample_bench import sample_rows
        srows = sample_rows(smoke=args.smoke)
        if args.json:
            _write_rows_json(srows, _mode_json_path(args, "BENCH_sample.json"))
        _print_rows(srows)
        return

    if args.dist:
        # --- distributed step: slab-native vs per-leaf + 2-D bank --------
        from benchmarks.dist_bench import dist_rows
        drows = dist_rows(smoke=args.smoke)
        if args.json:
            _write_rows_json(drows, _mode_json_path(args, "BENCH_dist.json"))
        _print_rows(drows)
        return

    if args.sections:
        # --- section streaming: sectioned vs full-slab engines (§3.16) ---
        from benchmarks.sections_bench import section_rows
        xrows = section_rows(smoke=args.smoke)
        if args.json:
            _write_rows_json(xrows,
                             _mode_json_path(args, "BENCH_sections.json"),
                             merge=True)
        _print_rows(xrows)
        return

    if args.sweep:
        # --- sweep-engine comparison: sharded vs vmap vs sequential -------
        from benchmarks.kernel_bench import sweep_rows
        s, steps = (8, 2) if args.smoke else (16, 3)
        # smoke (CI) skips the S sequential re-compiles; the full pass
        # keeps all three flavors for BENCH_sweep.json
        srows = sweep_rows(n_scenarios=s, steps=steps,
                           include_sequential=not args.smoke)
        if args.json:
            _write_rows_json(srows, _mode_json_path(args, "BENCH_sweep.json"))
        _print_rows(srows)
        return

    rows = []
    if not args.kernels:
        # --- paper figures (Figs. 2-4) -----------------------------------
        steps = args.steps or (500 if args.full else 40)
        from benchmarks.fig2_dynamic_vs_equal import run as fig2
        from benchmarks.fig3_bad_channel import run as fig3
        from benchmarks.fig4_diverse_sigma import run as fig4
        rows += _figure_rows(fig2(steps=steps))
        rows += _figure_rows(fig3(steps=steps))
        rows += _figure_rows(fig4(steps=steps))

        # claim check: dynamic beats equal on loss-AUC (Fig. 2 headline)
        try:
            from benchmarks.paper_common import RESULTS_DIR
            for fig in ("fig2", "fig3"):
                with open(os.path.join(RESULTS_DIR,
                                       f"{fig}_hota_fgn.json")) as f:
                    dyn = json.load(f)
                with open(os.path.join(RESULTS_DIR, f"{fig}_equal.json")) as f:
                    eq = json.load(f)
                adv = (sum(eq["auc_loss_per_task"])
                       - sum(dyn["auc_loss_per_task"]))
                rows.append((f"{fig}_claim_dynamic_faster", 0.0,
                             f"auc_advantage={adv:+.4f} "
                             f"({'PASS' if adv > 0 else 'CHECK'})"))
        except FileNotFoundError:
            pass

    # --- kernel microbenchmarks ------------------------------------------
    from benchmarks.kernel_bench import client_folded_rows, \
        run as kbench, sweep_rows
    kernel_rows = kbench()

    # --- client-folded zero-copy sim channel vs einsum+per-leaf ----------
    kernel_rows += client_folded_rows(quick=args.smoke)

    # --- scenario-sweep engine: banked vs sequential ----------------------
    if not args.smoke:
        kernel_rows += sweep_rows()
    rows += kernel_rows

    if args.json:
        # merge by row name into an existing artifact: a --smoke pass
        # refreshes only the rows it actually ran, so the committed
        # full-size rows (1M/16M, banked S=8) survive a local CI-smoke
        # invocation instead of being clobbered by the smaller row set
        _write_rows_json(kernel_rows, args.json, merge=True)

    if not args.kernels:
        # --- roofline table (from cached dry-run JSONs) -------------------
        from benchmarks.roofline import load_all
        dr = load_all()
        ok = [r for r in dr if r["status"] == "ok"]
        skipped = [r for r in dr if r["status"] == "skipped"]
        err = [r for r in dr if r["status"] == "error"]
        rows.append(("dryrun_pairs", 0.0,
                     f"ok={len(ok)} skipped={len(skipped)} error={len(err)} "
                     f"total={len(dr)}"))
        for r in ok:
            rl = r["roofline"]
            rows.append((
                f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
                f"dom={rl['dominant']};c={rl['compute_s']:.3f}s;"
                f"m={rl['memory_s']:.3f}s;coll={rl['collective_s']:.3f}s"))

    _print_rows(rows)


if __name__ == "__main__":
    main()
