"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every (architecture x input shape x mesh) combination, lower + compile
the real step function on the production mesh with ShapeDtypeStruct inputs
(no allocation), then record:

* memory_analysis()  — proves the program fits per device,
* cost_analysis() + HLO reparse (repro.launch.hlo_cost) — FLOPs / bytes /
  collective bytes per device with loop multipliers,
* the roofline terms (§ROOFLINE) and the dominant bottleneck.

Usage:
    python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all [--multi-pod both]
Results land in results/dryrun/<arch>__<shape>__<mesh>.json. Run as a
script, it forces 512 host devices (``XLA_FLAGS``) before JAX starts;
importing the module changes nothing.
"""

import argparse
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import FLConfig, INPUT_SHAPES, InputShape, TrainConfig
from repro.configs import ALIASES, ARCH_IDS, get_config
from repro.core.hota_step import HotaState, make_hota_train_step
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    abstract_serve_state, cache_specs_tree, input_specs, make_decode_step,
    make_prefill_step, param_specs_tree, serve_rules_for,
)
from repro.models.model import build_model
from repro.models.params import abstract_params, logical_axes, param_count
from repro.sharding.mesh_utils import fl_view
from repro.sharding.rules import TRAIN_RULES, spec_for
from repro.optim.adam import AdamState

PEAK_FLOPS, HBM_BW, ICI_BW = 197e12, 819e9, 50e9
N_CLIENTS = 4
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

SERVE_ARCH_OVERRIDES = dict(compute_dtype="bfloat16", remat_policy="none")
TRAIN_ARCH_OVERRIDES = dict(compute_dtype="bfloat16",
                            remat_policy="nothing_saveable")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _sds(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def active_params(cfg) -> float:
    """Parameter count with MoE experts scaled to top-k/E (6·N_active·D)."""
    model = build_model(cfg)
    total = param_count({"t": model.trunk_specs(), "f": model.final_specs()})
    if cfg.moe is not None:
        from repro.models.moe import moe_specs
        expert_per_layer = sum(
            int(np.prod(s.shape)) for k, s in moe_specs(cfg).items()
            if k.startswith("w_"))
        n_layers_moe = cfg.n_layers
        inactive = expert_per_layer * n_layers_moe * (
            1.0 - cfg.moe.top_k / cfg.moe.n_experts)
        total -= inactive
    return float(total)


def hota_state_shardings(model, mesh, state_abs, n_out=None):
    """Full (FL + model axes) shardings for the HotaState pytree."""
    client_axes = tuple(a for a in mesh.axis_names
                        if a in ("pod", "cluster", "client"))

    def omega_spec(axes, shape):
        sp = spec_for(axes, TRAIN_RULES, shape, mesh)
        # params use CLIENT-major FSDP piece order (scatter-region
        # alignment — repro.core.hota.make_ota_gather)
        return P(*[("client", "cluster") if p_ == ("cluster", "client")
                   else p_ for p_ in sp])

    def tree_spec(specs_tree):
        ax = logical_axes(specs_tree)
        return jax.tree.map(
            lambda a, s: omega_spec(a, s.shape), ax, specs_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(i, (str, type(None))) for i in x))

    omega = {"final": tree_spec(model.final_specs()),
             "trunk": tree_spec(model.trunk_specs())}
    head_specs = model.head_specs(n_out)
    heads = jax.tree.map(
        lambda s: spec_for(("clients",) + s.axes, TRAIN_RULES,
                           (int(np.prod([mesh.devices.shape[
                               mesh.axis_names.index(a)] for a in client_axes])),)
                           + s.shape, mesh),
        head_specs, is_leaf=lambda x: hasattr(x, "axes"))
    sc = P(client_axes)
    specs = HotaState(
        omega=omega,
        opt=AdamState(step=P(), mu=omega, nu=omega),
        heads=heads,
        head_opt=AdamState(step=P(), mu=heads, nu=heads),
        p=sc, fgn_mu=sc, fgn_nu=sc, fgn_t=P(), f0=sc, step=P())
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _pick_microbatches(cfg, shape: InputShape, n_total_clients: int) -> int:
    """Smallest power-of-2 microbatch count keeping saved layer-boundary
    activations (L x B_mb x S x d x 2B) under ~4 GiB per device."""
    b_loc = shape.global_batch // n_total_clients
    budget = 4 * 2**30
    act = cfg.n_layers * b_loc * shape.seq_len * cfg.d_model * 2
    mb = 1
    while act / mb > budget and mb < b_loc:
        mb *= 2
    return mb


def lower_train(cfg, mesh_prod, shape: InputShape):
    cfg = cfg.replace(**TRAIN_ARCH_OVERRIDES)
    model = build_model(cfg)
    mesh = fl_view(mesh_prod, N_CLIENTS)
    n_total_clients = int(np.prod(
        [s for s, a in zip(mesh.devices.shape, mesh.axis_names)
         if a in ("pod", "cluster", "client")]))
    fl = FLConfig(n_clients=N_CLIENTS, ota_mode="scatter",
                  microbatches=_pick_microbatches(cfg, shape, n_total_clients))
    tcfg = TrainConfig(lr=3e-4, global_batch=shape.global_batch,
                       seq_len=shape.seq_len, fl=fl)
    init_fn, step_fn, state_specs, batch_spec = make_hota_train_step(
        model, mesh, fl, tcfg, loss_kind="lm")
    state_abs = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
    state_sh = hota_state_shardings(model, mesh, state_abs)

    ins = input_specs(cfg, shape)
    tok_spec = ins["tokens"]
    client_axes = tuple(a for a in mesh.axis_names
                        if a in ("pod", "cluster", "client"))
    tok_sh = NamedSharding(mesh, P(client_axes))
    lab_sh = NamedSharding(mesh, P(client_axes))
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)

    # donate the train state: params/opt buffers update in place
    jf = jax.jit(step_fn, in_shardings=(state_sh, tok_sh, lab_sh,
                                        NamedSharding(mesh, P())),
                 donate_argnums=(0,))
    lowered = jf.lower(state_abs, tok_spec, ins["labels"], key_abs)
    return lowered


def lower_serve(cfg, mesh, shape: InputShape):
    cfg = cfg.replace(**SERVE_ARCH_OVERRIDES)
    model = build_model(cfg)
    rules = serve_rules_for(shape)
    backbone_abs, head_abs, cache_abs = abstract_serve_state(model, shape)
    pspecs = param_specs_tree(model, rules, mesh, include_head=True)
    bb_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         pspecs["backbone"], is_leaf=lambda x: isinstance(x, P))
    head_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs["head"],
                           is_leaf=lambda x: isinstance(x, P))
    ins = input_specs(cfg, shape)

    if shape.kind == "prefill":
        step = make_prefill_step(model, cache_len=shape.seq_len + 1)
        tok_axes = ("batch", "seq") if ins["tokens"].ndim == 2 else \
            ("batch", "seq", None)
        tok_sh = NamedSharding(mesh, spec_for(tok_axes, rules,
                                              ins["tokens"].shape, mesh))
        jf = jax.jit(step, in_shardings=(bb_sh, head_sh, tok_sh))
        return jf.lower(backbone_abs, head_abs, ins["tokens"])

    # decode
    step = make_decode_step(model)
    cache_sp = cache_specs_tree(model, cache_abs, rules, mesh)
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_sp,
                            is_leaf=lambda x: isinstance(x, P))
    tok_sh = NamedSharding(mesh, spec_for(("batch", None), rules,
                                          ins["tokens"].shape, mesh))
    pos_sh = NamedSharding(mesh, spec_for(("batch",), rules,
                                          ins["positions"].shape, mesh))
    # donate the KV cache: the in-place update must not double-buffer
    jf = jax.jit(step, in_shardings=(bb_sh, head_sh, cache_sh, tok_sh, pos_sh),
                 donate_argnums=(2,))
    return jf.lower(backbone_abs, head_abs, cache_abs, ins["tokens"],
                    ins["positions"])


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, force: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{_mesh_tag(multi_pod)}"
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    result = {"arch": arch, "shape": shape_name,
              "mesh": _mesh_tag(multi_pod), "status": "?"}

    if shape_name == "long_500k" and not cfg.is_subquadratic:
        result["status"] = "skipped"
        result["reason"] = ("pure full-attention arch; long_500k requires "
                            "sub-quadratic attention (DESIGN.md §3.6)")
        _write(out_path, result)
        return result

    t0 = time.time()
    try:
        mesh_prod = make_production_mesh(multi_pod=multi_pod)
        n_dev = int(np.prod(mesh_prod.devices.shape))
        if shape.kind == "train":
            lowered = lower_train(cfg, mesh_prod, shape)
        else:
            lowered = lower_serve(cfg, mesh_prod, shape)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                              + mem["temp_bytes"] - mem["alias_bytes"])

        totals = hlo_cost.analyze(compiled.as_text())
        ca = compiled.cost_analysis() or {}

        n_tok = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mf = (6.0 if shape.kind == "train" else 2.0) * active_params(cfg) * n_tok
        compute_s = totals.flops / PEAK_FLOPS
        # memory term uses the fusion-optimistic (major-ops) byte count —
        # XLA:TPU fuses elementwise chains the CPU backend leaves separate;
        # the all-ops upper bound is recorded alongside.
        memory_s = totals.bytes_major / HBM_BW
        coll_s = sum(totals.coll_bytes.values()) / ICI_BW
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "collective_s": coll_s}
        result.update({
            "status": "ok",
            "n_devices": n_dev,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": mem,
            "flops_per_device": totals.flops,
            "bytes_per_device": totals.bytes_major,
            "bytes_per_device_upper": totals.bytes,
            "memory_s_upper": totals.bytes / HBM_BW,
            "collective_bytes": {k: float(v) for k, v in totals.coll_bytes.items()},
            "collective_sites": sorted(
                [{"comp": c, "op": o, "bytes_once": b, "mult": m,
                  "total": b * m} for c, o, b, m in totals.coll_detail],
                key=lambda d: -d["total"])[:12],
            "roofline": {**terms,
                         "dominant": max(terms, key=terms.get).replace("_s", "")},
            "model_flops_global": mf,
            "hlo_flops_global": totals.flops * n_dev,
            "useful_flops_ratio": mf / max(totals.flops * n_dev, 1.0),
            "cost_analysis_raw_flops": float(ca.get("flops", 0.0)),
        })
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _write(out_path, result)
    return result


def _write(path: str, obj: dict):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"], default="off")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = ([a for a in ARCH_IDS if a != "paper_mlp"]
             if args.arch == "all" else [ALIASES.get(args.arch, args.arch)])
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]

    for arch in archs:
        for shape in shapes:
            for mp in pods:
                r = run_pair(arch, shape, mp, args.out_dir, args.force)
                dom = r.get("roofline", {}).get("dominant", "-")
                print(f"{arch:20s} {shape:12s} {_mesh_tag(mp):10s} "
                      f"{r['status']:8s} dom={dom} "
                      f"mem={r.get('memory', {}).get('total_bytes', 0)/2**30:.2f}GiB "
                      f"compile={r.get('compile_s', 0)}s", flush=True)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
