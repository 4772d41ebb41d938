"""Regenerate the data-driven sections of EXPERIMENTS.md from results/.

Usage: PYTHONPATH=src:. python scripts/update_experiments.py
Reads results/dryrun/*.json and results/repro/*.json; rewrites the blocks
between the AUTOGEN markers in EXPERIMENTS.md.
"""
from __future__ import annotations

import glob
import json
import os
import re

ROOT = os.path.join(os.path.dirname(__file__), "..")


def load(pattern):
    out = []
    for p in sorted(glob.glob(os.path.join(ROOT, "results", pattern))):
        with open(p) as f:
            out.append(json.load(f))
    return out


def fmt_bytes(b):
    return f"{b/2**30:.2f}"


def dryrun_section() -> str:
    rows = load("dryrun/*.json")
    lines = [
        "| arch | shape | mesh | status | fits ≤16GiB | arg+temp GiB | "
        "compile s | collectives |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"skipped | — | — | — | {r['reason'][:60]} |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"**ERROR** | — | — | — | {r.get('error','')[:60]} |")
            continue
        mem = r["memory"]
        tot = (mem["argument_bytes"] + mem["temp_bytes"]) / 2**30
        fits = "✅" if tot <= 16 else "⚠️"
        coll = ", ".join(f"{k.split('-')[-1][:3]}:{v/2**30:.1f}G"
                         for k, v in sorted(r["collective_bytes"].items()))
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | {fits} | "
            f"{tot:.2f} | {r['compile_s']} | {coll} |")
    n_ok = sum(1 for r in rows if r["status"] == "ok")
    n_skip = sum(1 for r in rows if r["status"] == "skipped")
    n_err = len(rows) - n_ok - n_skip
    lines.append("")
    lines.append(f"**{n_ok} ok / {n_skip} skipped (documented) / "
                 f"{n_err} errors, of {len(rows)} recorded runs.**")
    return "\n".join(lines)


def roofline_section() -> str:
    rows = [r for r in load("dryrun/*.json") if r["status"] == "ok"
            and r["mesh"] == "pod16x16"]
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "MODEL/HLO flops | one-line lever |",
        "|---|---|---|---|---|---|---|---|",
    ]
    LEVERS = {
        ("compute",): "raise MXU utilization (bigger per-chip batch, fused attn)",
        ("memory",): "cut HBM traffic: flash-attn keeps S² scores in VMEM; "
                     "fuse channel-mask (ota_mask_weight kernel)",
        ("collective",): "shard-level OTA (defer), 2D-sharded gathers, "
                         "overlap gather with compute",
    }
    for r in rows:
        rl = r["roofline"]
        lever = LEVERS[(rl["dominant"],)]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {rl['compute_s']:.3f} | "
            f"{rl['memory_s']:.3f} | {rl['collective_s']:.3f} | "
            f"**{rl['dominant']}** | {r['useful_flops_ratio']:.3f} | "
            f"{lever} |")
    return "\n".join(lines)


def repro_section() -> str:
    names = ["fig2_hota_fgn", "fig2_equal", "fig3_hota_fgn", "fig3_equal",
             "fig4_s1_2.0_fedgradnorm", "fig4_s1_2.0_equal",
             "fig4_s1_0.25_fedgradnorm", "fig4_s1_0.25_equal"]
    rows = {}
    for n in names:
        p = os.path.join(ROOT, "results", "repro", n + ".json")
        if os.path.exists(p):
            with open(p) as f:
                rows[n] = json.load(f)
    if not rows:
        return "_(experiments still running)_"
    lines = [
        "| run | weighting | σ² pattern | final loss (mod/sig/anom) | "
        "AUC loss (mod/sig/anom) |",
        "|---|---|---|---|---|",
    ]
    for n, r in rows.items():
        fl = "/".join(f"{x:.3f}" for x in r["final_loss_per_task"])
        auc = "/".join(f"{x:.3f}" for x in r["auc_loss_per_task"])
        sig = ",".join(str(s) for s in r["sigma2"][:2]) + ",…" if r["sigma2"] else "all 1"
        lines.append(f"| {n} | {r['weighting']} | {sig} | {fl} | {auc} |")

    # claim verdicts
    lines.append("")
    for fig in ("fig2", "fig3"):
        a, b = rows.get(f"{fig}_hota_fgn"), rows.get(f"{fig}_equal")
        if a and b:
            adv = sum(b["auc_loss_per_task"]) - sum(a["auc_loss_per_task"])
            verdict = "✅ dynamic faster" if adv > 0 else "❌ check"
            lines.append(f"* **{fig} claim**: AUC-loss advantage of dynamic "
                         f"over equal = {adv:+.4f} → {verdict}")
    for tag in ("s1_2.0", "s1_0.25"):
        a = rows.get(f"fig4_{tag}_fedgradnorm")
        b = rows.get(f"fig4_{tag}_equal")
        if a and b:
            adv = sum(b["auc_loss_per_task"]) - sum(a["auc_loss_per_task"])
            verdict = "✅" if adv > 0 else "❌"
            lines.append(f"* **fig4 {tag}**: advantage {adv:+.4f} {verdict}")
    return "\n".join(lines)


def replace_block(text: str, tag: str, content: str) -> str:
    start, end = f"<!-- AUTOGEN:{tag} -->", f"<!-- /AUTOGEN:{tag} -->"
    pattern = re.compile(re.escape(start) + ".*?" + re.escape(end), re.S)
    return pattern.sub(start + "\n" + content + "\n" + end, text)


def main():
    path = os.path.join(ROOT, "EXPERIMENTS.md")
    with open(path) as f:
        text = f.read()
    text = replace_block(text, "dryrun", dryrun_section())
    text = replace_block(text, "roofline", roofline_section())
    text = replace_block(text, "repro", repro_section())
    with open(path, "w") as f:
        f.write(text)
    print("EXPERIMENTS.md updated")


if __name__ == "__main__":
    main()
