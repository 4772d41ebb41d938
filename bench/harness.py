"""One run of one benchmark cell: set-up, the first rounds checked against
the plain reference, a timed window, the metrics by name.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic; ``bench/configs/<config>.json`` holds
its sizes and ``bench/configs/<config>.py`` its plain reference and model
FLOPs; ``bench/traffic/<traffic>.json`` names the engine module
(``bench/engines/<engine>.py``) and the batch generator
(``bench/generators/<generator>.py``); each metric is read by
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PEAKS = os.path.join(BENCH, "peaks.json")


class NoChip(RuntimeError):
    pass


def use_checkout_cache() -> str:
    """Put JAX's persistent compilation cache at one fixed path inside the
    checkout, so that only a cell's first run there compiles, and point
    the program's own helper at it. Called once by the entry scripts,
    before anything compiles."""
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def load_json(path):
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: str = ROOT) -> dict:
    """The cell's manifest entry joined with its configuration, traffic
    and the metrics it reports, all read from the checkout ``root``."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"root": root, "cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": mine(manifest["end_to_end"]),
            "per_layer": mine(manifest["per_layer"])}


_MODULES: Dict[str, object] = {}


def module(kind: str, name: str, root: str = ROOT):
    """The module ``<root>/bench/<kind>/<name>.py``."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


class Seeds:
    """Every random input of a run, from ``--seed`` (any non-negative
    integer): the weights, the batch pool and the round keys."""

    def __init__(self, seed: int):
        self.words = [int(w) for w in
                      np.random.SeedSequence(seed).generate_state(4)]

    def _key(self, a, b):
        return np.asarray([a, b], np.uint32)

    def weight_key(self):
        return self._key(self.words[0], 1)

    def data_key(self):
        return self._key(self.words[1], 2)

    def round_key(self, i: int):
        return self._key(self.words[2], (self.words[3] + i) % 2 ** 32)


# ------------------------------------------------------------- devices
def peak_of(kind: str) -> dict:
    """The peak table's row for a device kind; a kind missing from it is
    an error, never a default."""
    peaks = load_json(PEAKS)["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def device_info(chips: int, require_chip: bool = True):
    """The devices JAX reports, the ``chips`` the cell uses and their
    peaks. Without a TPU, or with fewer chips than the cell asks for,
    raises ``NoChip``. With ``require_chip=False`` (the CPU tests) the
    check is skipped and the peaks are None."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if not require_chip:
        return devs, devs[:chips], None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} ({kind}) "
                     f"x{len(devs)}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)} {kind}")
    return devs, devs[:chips], peak_of(kind)


def peak_bytes(devices) -> Optional[int]:
    """``memory_stats()["peak_bytes_in_use"]`` of the fullest device."""
    vals = [int(s["peak_bytes_in_use"]) for s in
            (d.memory_stats() or {} for d in devices)
            if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


# ------------------------------------------------------------ checking
def _norms(leaves: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in leaves.items()}


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                keep: Optional[List[str]] = None) -> float:
    """max over leaves of |prog norm - ref norm| / max(ref norm, median
    leaf's ref norm)."""
    keep = list(ref) if keep is None else keep
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keep)


def training_readings(prog: dict, ref: dict, theta0: dict,
                      skip_rule: float = 1e-3) -> Dict[str, float]:
    """The numbers a training cell compares with its reference.

    loss_gap: worst relative gap of a client's loss over the checked
    rounds. grad_gap: worst leaf's gap of the norm of the first round's
    server gradient less its channel noise: both sides draw the same
    noise bits, and the reference's noise term (``noise1``) is taken off
    both, since at the paper's noise level it outweighs the gradients
    it is added to. update_gap: worst leaf's gap of the norm of the change
    the checked rounds made, over shared and head leaves, leaving out
    leaves whose first reference gradient is under ``skip_rule`` times
    the median leaf's. p_gap, norm_gap: largest gap of the FedGradNorm
    weights, and relative gap of the masked last-layer norms.
    leaves_skipped counts the leaves the rule left out.
    """
    out = {}
    out["loss_gap"] = max(
        float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        for a, b in zip(prog["loss"], ref["loss"]))
    out["p_gap"] = max(float(np.max(np.abs(a - b)))
                       for a, b in zip(prog["p"], ref["p"]))
    out["norm_gap"] = max(
        float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        for a, b in zip(prog["norms"], ref["norms"]))
    noise = ref["noise1"]
    g_ref = _norms({k: v - noise[k] for k, v in ref["ghat1"].items()})
    g_prog = _norms({k: v - noise[k] for k, v in prog["ghat1"].items()})
    out["grad_gap"] = _worst_leaf(g_prog, g_ref)

    med_g = statistics.median(g_ref.values())
    keep = [k for k, v in g_ref.items() if v >= skip_rule * med_g]
    h_ref = _norms(ref["head_grad1"])
    med_h = statistics.median(h_ref.values())
    keep_h = ["heads/" + k for k, v in h_ref.items()
              if v >= skip_rule * med_h]
    d_prog, d_ref = {}, {}
    for group in ("omega", "heads"):
        for k, v in prog[group].items():
            name = k if group == "omega" else "heads/" + k
            d_prog[name] = float(np.linalg.norm(v - theta0[group][k]))
            d_ref[name] = float(np.linalg.norm(
                ref[group][k] - theta0[group][k]))
    out["update_gap"] = _worst_leaf(d_prog, d_ref, keep + keep_h)
    out["leaves_skipped"] = len(d_ref) - len(keep) - len(keep_h)
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): every limited number finite and under its
    limit; with no limit at all nothing is proven."""
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in limits}
    ok = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks


# ------------------------------------------------------------ the run
class Context:
    """What the metric readers see."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _host_weights(model_mod, cfg, traffic, seeds):
    import jax
    w = jax.jit(lambda k: model_mod.init_weights(
        cfg, k, traffic["n_clusters"], traffic["n_clients"]))(
        seeds.weight_key())
    from bench.refs.ota_spec import path_str
    return {g: {path_str(p): np.asarray(v, np.float64) for p, v in
                jax.tree_util.tree_flatten_with_path(w[g])[0]}
            for g in ("omega", "heads")}, w


def follow_reference(model_mod, cfg, traffic, seeds, check_in, prog=None,
                     dtype="float32") -> Dict[str, float]:
    """Follow the checked rounds with the plain reference and compare
    ``prog``, what the program produced. With ``prog=None`` the reference
    computed in ``dtype`` takes the program's place: the control."""
    theta0, weights = _host_weights(model_mod, cfg, traffic, seeds)
    args = (cfg, traffic, weights, check_in["xs"], check_in["ys"],
            check_in["keys"], check_in["n_classes"])
    steps = traffic["check"]["steps"]
    ref = model_mod.reference(*args, steps=steps)
    if prog is None:
        prog = model_mod.reference(*args, steps=steps, dtype=dtype)
    return training_readings(prog, ref, theta0)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t0: Optional[float] = None, require_chip: bool = True) -> dict:
    """One run of the cell ``spec`` (see ``cell_spec``); returns the
    result line as a dict."""
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    root = spec["root"]
    cfg, traffic = spec["cfg"], spec["traffic"]
    all_devs, devs, peak = device_info(spec["cell"]["chips"], require_chip)
    model_mod = module("configs", spec["cell"]["config"], root)
    engine = module("engines", traffic["engine"], root)
    generator = module("generators", traffic["generator"], root)
    seeds = Seeds(seed)

    t_cell = time.perf_counter()
    cell = engine.Cell(cfg, traffic, model_mod, generator, seeds, devs)
    t_rounds = time.perf_counter()
    for _ in range(traffic["check"]["steps"]):
        with jax.profiler.TraceAnnotation("bench.round"):
            cell.round()
    check_in = cell.check_inputs()
    print(f"setup: {t_cell - t0:.2f} s imports and devices, "
          f"{t_rounds - t_cell:.2f} s batches and state, "
          f"{time.perf_counter() - t_rounds:.2f} s checked rounds",
          file=sys.stderr)

    limit_s = min(seconds, traffic["trace_seconds"]) if trace else seconds
    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    round_s: List[float] = []
    failed = 0
    t_start = time.perf_counter()
    setup_s = t_start - t0
    while True:
        t = time.perf_counter()
        if t - t_start >= limit_s:
            break
        with jax.profiler.TraceAnnotation("bench.round"):
            loss, p = cell.round()
        round_s.append(time.perf_counter() - t)
        if not (np.isfinite(loss).all() and np.isfinite(p).all()):
            failed += 1
    cell.finish()
    window_s = time.perf_counter() - t_start
    view = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod
        events = trace_mod.load_xplane(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = trace_mod.TraceView(events)
    mem = peak_bytes(devs)
    for d in devs:
        print(f"memory: {d} {d.memory_stats()}", file=sys.stderr)

    prog = cell.readings()
    cell.release()
    del cell
    gc.collect()
    readings = follow_reference(model_mod, cfg, traffic, seeds, check_in,
                                prog)
    correct, checks = judge(readings, traffic["check"]["limits"])
    correct = correct and failed == 0

    ctx = Context(cfg=cfg, traffic=traffic, model_mod=model_mod, peak=peak,
                  n_chips=len(devs), setup_s=setup_s,
                  window_s=window_s, round_s=round_s, rounds=len(round_s),
                  peak_bytes=mem, trace=view)
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        value = module("metrics", m["name"], root).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = all_devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(all_devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(round_s),
              "failed": failed, "metrics": metrics, "device": device}
    if view is not None:
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        ops = sorted(view.op_seconds().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            # an op's name is its HLO text: keep the name and result type
            "device_ops": [[k.split("{")[0][:120], v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in view.idle_gaps()[:10]]}
    print(json.dumps({"readings": readings}), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    return result
