"""The benchmark's own tests run on the CPU at small sizes."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a paper-MLP round small enough for the CPU: 2 clusters x 3 clients,
# batch 4, a 3,000-point dataset
TINY = {"n_clusters": 2, "n_clients": 3, "batch": 4, "pool_rounds": 4}
TINY_POINTS = 3000


def copy_checkout(dst) -> str:
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    dst = str(dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


def add_cell(root, name, config, traffic_name, traffic) -> None:
    """Add a cell to the copy's manifest: one new traffic file and one new
    workload entry, listed where a metric names its cells."""
    with open(os.path.join(root, "bench", "traffic",
                           traffic_name + ".json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["workloads"].append({"name": name, "config": config,
                             "traffic": traffic_name, "chips": 1,
                             "why": "test cell"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(man, f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with the cell ``paper_mlp.tiny``: the paper
    cell's traffic and limits at the small size."""
    root = copy_checkout(tmp_path_factory.mktemp("checkout"))
    with open(os.path.join(ROOT, "bench", "traffic",
                           "round_c10n3.json")) as f:
        traffic = json.load(f)
    traffic.update(TINY)
    traffic["data"]["n_points"] = TINY_POINTS
    add_cell(root, "paper_mlp.tiny", "paper_mlp", "tiny", traffic)
    return root
