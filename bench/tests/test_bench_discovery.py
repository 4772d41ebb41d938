"""Cells, configurations and metrics are found by name: adding one is
adding files, with no edit to a file that is there."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import ROOT, add_cell, copy_checkout


def _traffic():
    with open(os.path.join(ROOT, "bench", "traffic",
                           "round_c10n3.json")) as f:
        return json.load(f)


def test_new_cell_is_found_by_name(tmp_path):
    root = copy_checkout(tmp_path)
    before = {p: open(p, "rb").read() for p in _bench_files(root)}
    traffic = dict(_traffic(), n_clusters=4, batch=8)
    add_cell(root, "paper_mlp.round_c4n3", "paper_mlp", "round_c4n3",
             traffic)
    spec = harness.cell_spec("paper_mlp.round_c4n3", root)
    assert spec["traffic"]["n_clusters"] == 4
    assert spec["cfg"]["name"] == "paper_mlp"
    assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
    assert harness.module("engines", spec["traffic"]["engine"], root).Cell
    # nothing under bench/ changed; one file was added
    after = _bench_files(root)
    assert set(after) - set(before) == {
        os.path.join(root, "bench", "traffic", "round_c4n3.json")}
    assert all(open(p, "rb").read() == before[p] for p in before)


def test_new_metric_is_found_by_name(tmp_path):
    root = copy_checkout(tmp_path)
    with open(os.path.join(root, "bench", "metrics", "rounds_x2.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 2 * ctx.rounds\n")
    reader = harness.module("metrics", "rounds_x2", root)
    assert reader.read(harness.Context(rounds=21)) == 42


def test_new_config_is_found_by_name(tmp_path):
    root = copy_checkout(tmp_path)
    cdir = os.path.join(root, "bench", "configs")
    for ext in (".json", ".py"):
        shutil.copy(os.path.join(cdir, "paper_mlp" + ext),
                    os.path.join(cdir, "paper_mlp_wide" + ext))
    mod = harness.module("configs", "paper_mlp_wide", root)
    assert mod.__file__.startswith(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append(dict(man["configs"][0], name="paper_mlp_wide",
                               file="bench/configs/paper_mlp_wide.json"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    add_cell(root, "paper_mlp_wide.round_c10n3", "paper_mlp_wide",
             "round_c10n3_w", _traffic())
    spec = harness.cell_spec("paper_mlp_wide.round_c10n3", root)
    assert spec["cfg"]["dims"][0] == 256


def test_missing_reader_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        harness.module("metrics", "no_such_metric", copy_checkout(tmp_path))
    with pytest.raises(KeyError):
        harness.cell_spec("no_such.cell")


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="peaks.json"):
        harness.peak_of("TPU v99 imaginary")
    assert harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(1)          # the tests run on the CPU


@pytest.mark.parametrize("where", ["with_program", "benchmark_only"])
def test_run_without_chip_or_program_prints_no_result(tmp_path, where):
    root = copy_checkout(tmp_path)
    if where == "with_program":
        os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper_mlp.round_c10n3", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_seeds_are_deterministic_and_take_large_values():
    a, b = harness.Seeds(2 ** 31 + 12345), harness.Seeds(2 ** 31 + 12345)
    assert np.array_equal(a.round_key(7), b.round_key(7))
    assert not np.array_equal(a.round_key(7), a.round_key(8))
    assert not np.array_equal(a.weight_key(), harness.Seeds(3).weight_key())
    assert a.round_key(0).dtype == np.uint32


def _bench_files(root):
    out = []
    for d, _, files in os.walk(os.path.join(root, "bench")):
        out += [os.path.join(d, f) for f in files if not f.endswith(".pyc")]
    return out
