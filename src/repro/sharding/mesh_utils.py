"""Mesh helpers: the FL-refined view, scenario axis, and axis bookkeeping.

``make_production_mesh()`` (repro.launch.mesh) returns the assignment's
meshes: (16,16) ("data","model") and (2,16,16) ("pod","data","model").
The HOTA trainer needs to distinguish *clients within a cluster* (LAN
aggregation) from *clusters* (over-the-air MAC). ``fl_view`` reshapes the
same devices, in the same order, splitting "data" into
("cluster", "client") — global array layouts are unchanged, only collective
scoping differs. This mirrors the dp/fsdp axis split in MaxText.

The SCENARIO axis (DESIGN.md §3.8) is orthogonal to the FL axes: a sweep
bank's (S,) leading dimension lives on a 1-D ("scenario",) mesh
(``repro.launch.mesh.make_scenario_mesh``); ``bank_sharding`` /
``replicated_sharding`` below are the two placements a sharded bank uses —
scenario-split state vs. replicated batch/PRNG (common random numbers).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

SCENARIO_AXIS = "scenario"


def fl_view(mesh: Mesh, n_clients: int) -> Mesh:
    """Refine a production mesh's 'data' axis into ('cluster','client')."""
    names = list(mesh.axis_names)
    assert "data" in names and "model" in names, mesh
    data_idx = names.index("data")
    shape = list(mesh.devices.shape)
    data_size = shape[data_idx]
    assert data_size % n_clients == 0, (data_size, n_clients)
    n_clusters = data_size // n_clients
    new_shape = shape[:data_idx] + [n_clusters, n_clients] + shape[data_idx + 1:]
    new_names = names[:data_idx] + ["cluster", "client"] + names[data_idx + 1:]
    return Mesh(mesh.devices.reshape(new_shape), tuple(new_names))


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    """All batch-like axes of a mesh, in major-to-minor order."""
    out = []
    for name in mesh.axis_names:
        if name in ("pod", "data", "cluster", "client"):
            out.append(name)
    return tuple(out)


def flat_client_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes that enumerate FL clients (cluster x client, plus pod)."""
    out = []
    for name in mesh.axis_names:
        if name in ("pod", "cluster", "client"):
            out.append(name)
    return tuple(out)


def cluster_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    """Axes that enumerate clusters (the OTA MAC sums over these)."""
    return tuple(n for n in mesh.axis_names if n in ("pod", "cluster"))


def total_clients(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ("pod", "cluster", "client"):
        n *= sizes.get(a, 1)
    return n


# --------------------------------------------------------------------------
# scenario axis (sharded sweep banks — DESIGN.md §3.8)
# --------------------------------------------------------------------------

def scenario_axis_size(mesh: Mesh) -> int:
    """Device count along the scenario axis of a sweep mesh."""
    assert SCENARIO_AXIS in mesh.axis_names, mesh
    return int(mesh.devices.shape[mesh.axis_names.index(SCENARIO_AXIS)])


def scenario_banked_spec(spec: PartitionSpec) -> PartitionSpec:
    """Prepend the scenario axis to a single-scenario PartitionSpec: an
    FL-sharded leaf P(*dims) becomes the bank leaf P("scenario", *dims) —
    the 2-D (scenario × client) layout of ``DistScenarioBank``'s
    (S,)-leading state/metric/ChannelParams leaves."""
    return PartitionSpec(SCENARIO_AXIS, *tuple(spec))


def scenario_banked_tree(spec_tree):
    """``scenario_banked_spec`` over a pytree of PartitionSpecs."""
    import jax
    return jax.tree.map(scenario_banked_spec, spec_tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def bank_sharding(mesh: Mesh) -> NamedSharding:
    """Placement for (S, ...) bank leaves: leading axis scenario-split."""
    return NamedSharding(mesh, PartitionSpec(SCENARIO_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Placement for the shared batch/PRNG inputs: fully replicated, so
    every scenario shard consumes identical data and keys (the common-
    random-numbers contract of the sweep engine)."""
    return NamedSharding(mesh, PartitionSpec())
