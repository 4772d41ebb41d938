"""TreePacker: layout contract + round-trip properties (the flat-packed
OTA engine's foundation — see repro/common/flatpack.py), including the
multi-section / zero-copy layout of DESIGN.md §3.10 and the edge cases
(empty tail, mixed dtypes, single leaf, non-contiguous tail key)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.flatpack import (
    TreePacker, check_tree_matches_packer, packer_for,
)
from repro.kernels.slab import LANE, ROW_QUANTUM, pad_to_lanes, slab_rows

TREE = {
    "final": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))},
    "trunk": {"fc0": {"w": jnp.full((5, 7), 2.0), "b": jnp.zeros((7,))},
              "fc1": {"w": jnp.full((2, 3), 3.0)}},
}


def test_roundtrip_exact():
    p = TreePacker(TREE, tail="final")
    slab = p.pack(TREE)
    assert slab.shape == (p.size,)
    out = p.unpack(slab)
    for a, b in zip(jax.tree.leaves(TREE), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_alignment_contract():
    p = TreePacker(TREE, tail="final")
    # lane-aligned slab, lane-aligned sections (kernel (rows, 128) view)
    assert p.size % ROW_QUANTUM == 0
    assert p.head_len % ROW_QUANTUM == 0
    assert p.tail_len % ROW_QUANTUM == 0
    assert p.size == p.head_len + p.tail_len
    assert p.n_rows * LANE == p.size


def test_final_leaves_are_contiguous_tail():
    """The last-shared-layer params must occupy one contiguous tail slice
    (final_layer_masks_packed slices exactly this)."""
    p = TreePacker(TREE, tail="final")
    slab = p.pack(TREE)
    tail = p.tail_slice(slab)
    assert tail.shape == (p.tail_len,)
    flat_final = jnp.concatenate(
        [l.reshape(-1) for l in jax.tree.leaves(TREE["final"])])
    np.testing.assert_array_equal(np.asarray(tail[:flat_final.size]),
                                  np.asarray(flat_final))
    # and the padding after the tail leaves is zero
    np.testing.assert_array_equal(np.asarray(tail[flat_final.size:]), 0.0)


def test_unpack_tail_matches_subtree():
    p = TreePacker(TREE, tail="final")
    tail = p.tail_slice(p.pack(TREE))
    sub = p.unpack_tail(tail)
    assert jax.tree.structure(sub) == jax.tree.structure(TREE["final"])
    for a, b in zip(jax.tree.leaves(TREE["final"]), jax.tree.leaves(sub)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_preserves_leading_batch_axes():
    """(C, ...) leaves (the per-cluster weighted grads) pack to (C, P)."""
    C = 3
    batched = jax.tree.map(
        lambda l: jnp.stack([l * (c + 1) for c in range(C)]), TREE)
    p = TreePacker(TREE, tail="final")
    slab = p.pack(batched)
    assert slab.shape == (C, p.size)
    for c in range(C):
        np.testing.assert_array_equal(
            np.asarray(slab[c]),
            np.asarray(p.pack(jax.tree.map(lambda l: l[c], batched))))


def test_packer_cache_hits():
    a = packer_for(TREE, tail="final")
    b = packer_for(jax.tree.map(jnp.zeros_like, TREE), tail="final")
    assert a is b
    c = packer_for(TREE, tail=None)
    assert c is not a and c.tail_len == 0 and c.head_len == c.size


def test_no_tail_packs_everything_in_head():
    p = TreePacker(TREE["trunk"], tail="final")   # key absent -> all head
    assert p.tail_len == 0
    out = p.unpack(p.pack(TREE["trunk"]))
    for a, b in zip(jax.tree.leaves(TREE["trunk"]), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=20, deadline=None)
@given(
    shapes=st.lists(
        st.tuples(st.integers(1, 17), st.integers(1, 23)), min_size=1,
        max_size=6),
    final_n=st.integers(1, 50),
    seed=st.integers(0, 99),
)
def test_roundtrip_property(shapes, final_n, seed):
    key = jax.random.PRNGKey(seed)
    tree = {
        "final": {"w": jax.random.normal(key, (final_n,))},
        "trunk": {f"l{i}": jax.random.normal(jax.random.fold_in(key, i), s)
                  for i, s in enumerate(shapes)},
    }
    p = packer_for(tree, tail="final")
    slab = p.pack(tree)
    assert slab.shape[-1] % ROW_QUANTUM == 0
    out = p.unpack(slab)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_toplevel_sections_layout_contract():
    """Multi-section layout: one ROW_QUANTUM-aligned section per layer
    stack (depth-≤2 path prefix), tail last, every leaf ROW_QUANTUM-
    aligned inside its section."""
    p = TreePacker(TREE, tail="final", sections="toplevel")
    names = [s.name for s in p.sections]
    assert names == ["trunk/fc0", "trunk/fc1", "final"]   # tail last
    off = 0
    for s in p.sections:
        assert s.start == off and s.length % ROW_QUANTUM == 0
        off += s.length
    assert off == p.size
    for run in p.leaf_runs():
        assert run.offset % ROW_QUANTUM == 0         # zero-copy contract
    # round-trip still exact (padding between leaves stays zero)
    out = p.unpack(p.pack(TREE))
    for a, b in zip(jax.tree.leaves(TREE), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # tail slice/unpack agree with the legacy layout's contract
    sub = p.unpack_tail(p.tail_slice(p.pack(TREE)))
    for a, b in zip(jax.tree.leaves(TREE["final"]), jax.tree.leaves(sub)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_leaf_map_partitions_leaves():
    """The chunk->leaf map covers every leaf run exactly, in order."""
    p = TreePacker(TREE, tail="final", sections="toplevel")
    cmap = p.chunk_leaf_map(ROW_QUANTUM)
    seen = set()
    for sec_idx, per_chunk in cmap.items():
        for j, runs in per_chunk:
            for run in runs:
                assert run.section == sec_idx
                assert run.offset < (j + 1) * ROW_QUANTUM
                assert run.offset + run.size > j * ROW_QUANTUM
                seen.add(run.leaf)
    assert seen == set(range(len(jax.tree.leaves(TREE))))


def test_legacy_layout_unchanged_by_sections_param():
    """sections='tail' (the default) must keep PR-2's exact offsets."""
    a = TreePacker(TREE, tail="final")
    b = TreePacker(TREE, tail="final", sections="tail")
    assert a.slots == b.slots and a.size == b.size
    assert [s[:4] for s in a.sections] == [s[:4] for s in b.sections]


def test_empty_tail_subtree():
    """A tail key with no leaves: no tail section, everything head."""
    tree = {"final": {}, "trunk": TREE["trunk"]}
    for sections in ("tail", "toplevel"):
        p = TreePacker(tree, tail="final", sections=sections)
        assert p.tail_len == 0 and p.head_len == p.size
        assert all(s.name != "final" for s in p.sections)
        out = p.unpack(p.pack(tree))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mixed_dtypes_rejected_with_clear_error():
    tree = {"final": {"w": jnp.zeros((3,), jnp.float32)},
            "trunk": {"w": jnp.zeros((3,), jnp.bfloat16)}}
    with pytest.raises(ValueError) as e:
        TreePacker(tree, tail="final")
    msg = str(e.value)
    assert "uniform leaf dtype" in msg and "bfloat16" in msg \
        and "float32" in msg
    # the offending leaves are named
    assert "trunk" in msg and "w" in msg
    with pytest.raises(ValueError):
        packer_for(tree, tail="final")


def test_single_leaf_tree():
    """A bare array (no container) packs as one head section."""
    x = jnp.arange(300.0)
    for sections in ("tail", "toplevel"):
        p = TreePacker(x, tail="final", sections=sections)
        assert p.tail_len == 0 and p.size == ROW_QUANTUM
        np.testing.assert_array_equal(np.asarray(p.unpack(p.pack(x))),
                                      np.asarray(x))
        assert len(p.sections) == 1 and p.sections[0].leaf_indices == (0,)


def test_non_contiguous_tail_name():
    """The tail key need not flatten last — its leaves still form the
    contiguous tail slice (the layout reorders, unpack restores)."""
    tree = {"a_first": jnp.ones((5,)),
            "final": {"w": jnp.arange(6.0)},       # flattens in the middle
            "z_last": jnp.full((7,), 3.0)}
    for sections in ("tail", "toplevel"):
        p = TreePacker(tree, tail="final", sections=sections)
        slab = p.pack(tree)
        tail = p.tail_slice(slab)
        np.testing.assert_array_equal(np.asarray(tail[:6]),
                                      np.arange(6.0))
        out = p.unpack(slab)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_check_tree_matches_packer_names_leaf_and_section():
    p = TreePacker(TREE, tail="final", sections="toplevel")
    check_tree_matches_packer(p, TREE, "ok tree")        # no raise
    bad_shape = jax.tree.map(lambda l: l, TREE)
    bad_shape["trunk"]["fc1"]["w"] = jnp.zeros((9, 9))
    with pytest.raises(ValueError) as e:
        check_tree_matches_packer(p, bad_shape, "gradient pytree")
    msg = str(e.value)
    assert "fc1" in msg and "section" in msg and "(9, 9)" in msg
    bad_struct = {"final": TREE["final"],
                  "trunk": {"fc0": TREE["trunk"]["fc0"]}}   # fc1 missing
    with pytest.raises(ValueError) as e:
        check_tree_matches_packer(p, bad_struct, "gradient pytree")
    assert "missing" in str(e.value) or "fc1" in str(e.value)


def test_slab_helpers_roundtrip():
    x = jnp.arange(1000.0).reshape(10, 100)
    slab, n = pad_to_lanes(x)
    assert n == 1000 and slab.shape == (slab_rows(1000), LANE)
    assert slab.shape[0] % 8 == 0
    np.testing.assert_array_equal(
        np.asarray(slab.reshape(-1)[:n].reshape(x.shape)), np.asarray(x))
