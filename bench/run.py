"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload paper_mlp.round_c10n3 --seed 7 \
        --seconds 10 --trace 0

Set-up (batches, weights, the compiled round from the persistent cache in
``<checkout>/.jax_cache``, the first rounds), then a timed window of
``--seconds``, then the first rounds compared with the plain reference.
The last line of standard output is one JSON object; the last lines of
standard error name each compared number beside its limit. With
``--trace 1`` a profiler trace of the window gives the per-layer metrics.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import (NoChip, cell_spec, run_cell,
                               use_checkout_cache)
    use_checkout_cache()

    try:
        result = run_cell(cell_spec(args.workload), args.seed, args.seconds,
                          bool(args.trace), t0=T0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
