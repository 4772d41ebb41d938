"""Section-layout coalescing + autotuner pins (DESIGN.md §3.13): slot
offsets are threshold-invariant, threshold=0 is bit-identical to the
uncoalesced layout (stream pin), the client-folded engine matches the
per-leaf oracle on a coalesced layout's shared streams, the calibration
bench returns a usable LayoutChoice, checkpoints refuse a cross-layout
restore, and the TPU/CPU dispatch resolves at trace time."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.config import FLConfig
from repro.common.flatpack import ROW_QUANTUM, TreePacker, packer_for
from repro.common.layout_tune import (
    DEFAULT_THRESHOLDS, LayoutChoice, apply_layout, calibrate_layout,
    layout_of, packer_for_layout, tune_layout,
)
from repro.core import ota
from repro.core.channel import channel_params
from repro.kernels.ota_channel.ref import bits_to_gaussian, bits_to_mask

C, N = 3, 2


def _template():
    """Many small top-level trunk groups — the coalescing target."""
    t = {"final": {"w": jax.ShapeDtypeStruct((40, 8), jnp.float32),
                   "b": jax.ShapeDtypeStruct((8,), jnp.float32)}}
    t["trunk"] = {f"fc{i}": {"w": jax.ShapeDtypeStruct((10 + i, 9), jnp.float32),
                             "b": jax.ShapeDtypeStruct((9,), jnp.float32)}
                  for i in range(6)}
    return t


def _grad_tree(key, template):
    leaves, treedef = jax.tree.flatten(template)
    return jax.tree.unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i), (C, N) + l.shape)
        for i, l in enumerate(leaves)])


# ------------------------------------------------------------ coalescing
@settings(max_examples=12, deadline=None)
@given(rows=st.integers(0, 2 * ROW_QUANTUM // 128), seed=st.integers(0, 50))
def test_coalesced_roundtrip_and_offsets_property(rows, seed):
    """ANY min_section_rows: unpack∘pack == identity, and every leaf's
    slab offset is IDENTICAL to the uncoalesced layout — coalescing only
    re-partitions sections, it never moves bytes."""
    template = _template()
    p0 = packer_for(template, tail="final", sections="toplevel")
    pk = packer_for(template, tail="final", sections="toplevel",
                    min_section_rows=rows)
    assert pk.slots == p0.slots
    assert pk.head_len == p0.head_len and pk.tail_len == p0.tail_len
    tree = jax.tree.map(
        lambda l: jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(seed), l.shape[0]), l.shape),
        template)
    back = pk.unpack(pk.pack(tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_coalesced_sections_partition_head_exactly():
    """Sections tile [0, head_len) disjointly, each ROW_QUANTUM-aligned,
    tail still its own LAST section, and the merged section count shrinks
    monotonically as the threshold grows."""
    template = _template()
    counts = []
    for rows in (0, 8, 64, 1024):
        pk = packer_for(template, tail="final", sections="toplevel",
                        min_section_rows=rows)
        off = 0
        for sec in pk.sections[:-1]:
            assert sec.start == off and sec.length % ROW_QUANTUM == 0
            off += sec.length
        assert off == pk.head_len
        assert pk.sections[-1].name == "final"
        assert pk.sections[-1].start == pk.head_len
        counts.append(len(pk.sections))
    assert counts[0] >= counts[1] >= counts[2] >= counts[3]
    assert counts[-1] == 2          # one merged trunk section + tail


def test_threshold_zero_is_bit_identical_stream_pin():
    """min_section_rows=0 must reproduce today's layout EXACTLY: same
    cached packer object, same section folds, same gain bits."""
    template = _template()
    p_default = packer_for(template, tail="final", sections="toplevel")
    p_zero = packer_for(template, tail="final", sections="toplevel",
                        min_section_rows=0)
    assert p_zero is p_default      # cache key identity for the default
    assert ota.packed_section_folds(p_zero) == \
        ota.packed_section_folds(p_default)
    key = jax.random.PRNGKey(7)
    np.testing.assert_array_equal(
        np.asarray(ota.packed_gain_bits(key, p_zero, C)),
        np.asarray(ota.packed_gain_bits(key, p_default, C)))


def test_coalesced_folds_follow_post_merge_section_index():
    """Fold-after-coalescing rule (§4): section s draws under
    PACKED_SECTION_FOLD_BASE + s where s is the POST-merge index — the
    tail keeps PACKED_TAIL_FOLD in every layout."""
    pk = packer_for(_template(), tail="final", sections="toplevel",
                    min_section_rows=1024)
    folds = ota.packed_section_folds(pk)
    assert folds[-1] == ota.PACKED_TAIL_FOLD
    assert folds[:-1] == [ota.PACKED_SECTION_FOLD_BASE + s
                          for s in range(len(pk.sections) - 1)]


def test_clientfold_matches_per_leaf_oracle_on_coalesced_layout():
    """The client-folded engine on a COALESCED layout == the per-leaf
    estimator fed masks/noise decoded from the same coalesced streams."""
    template = _template()
    pk = packer_for(template, tail="final", sections="toplevel",
                    min_section_rows=64)
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.25, 0.5, 1.0),
                  noise_std=0.4)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(5)
    g = _grad_tree(jax.random.fold_in(key, 1), template)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    ghat = ota.ota_aggregate_client_folded(key, g, p, chan, N, pk)
    bits = ota.packed_gain_bits(key, pk, C)
    nbits = ota.packed_noise_bits(key, pk)
    sig = chan.sigma2.reshape(C, 1)
    mask_tree = pk.unpack(
        bits_to_mask(bits, sig, chan.h_threshold, chan.ota_on)
        .astype(jnp.float32))
    noise_tree = pk.unpack(bits_to_gaussian(nbits, 1.0)
                           * chan.noise_std * chan.ota_on)
    wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p, l), g)
    oracle = jax.tree.map(
        lambda w, m, z: ota.ota_aggregate_leaf(w, m > 0.5, z, N),
        wg, mask_tree, noise_tree)
    for a, b in zip(jax.tree.leaves(ghat), jax.tree.leaves(oracle)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_final_layer_masks_packed_invariant_to_coalescing():
    """Eq.-5 masks come off the tail stream (PACKED_TAIL_FOLD), which no
    coalescing threshold touches — identical masks at any threshold."""
    template = _template()
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.25, 0.5, 1.0),
                  h_threshold=0.9)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(9)
    ref = ota.final_layer_masks_packed(
        key, chan, packer_for(template, tail="final", sections="toplevel"))
    got = ota.final_layer_masks_packed(
        key, chan, packer_for(template, tail="final", sections="toplevel",
                              min_section_rows=1024))
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_min_section_rows_requires_toplevel():
    with pytest.raises(ValueError, match="min_section_rows"):
        TreePacker(_template(), tail="final", sections="tail",
                   min_section_rows=8)


def test_chunk_leaf_map_keeps_zero_size_leaves():
    """Regression: a zero-size leaf used to vanish from chunk_leaf_map
    ((offset + size - 1) // chunk underflows when size == 0)."""
    template = {"final": {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
                "trunk": {"fc0": {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32),
                                  "empty": jax.ShapeDtypeStruct((0,), jnp.float32),
                                  "b": jax.ShapeDtypeStruct((8,), jnp.float32)}}}
    pk = packer_for(template, tail="final", sections="toplevel")
    seen = {r.leaf for per in pk.chunk_leaf_map(131072).values()
            for _, runs in per for r in runs}
    assert seen == set(range(len(pk.slots)))
    tree = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), template)
    back = pk.unpack(pk.pack(tree))
    assert jax.tree.leaves(back)[
        jax.tree.leaves(template).index(template["trunk"]["fc0"]["empty"])
    ].shape == (0,)


# -------------------------------------------------------------- autotuner
def test_calibrate_layout_reports_all_candidates():
    choice, report = calibrate_layout(_template(), C, N, iters=1)
    layouts = {r["layout"] for r in report}
    assert "perleaf" in layouts
    assert "slab/sections=tail/min_section_rows=0" in layouts
    for t in DEFAULT_THRESHOLDS:
        assert f"slab/sections=toplevel/min_section_rows={t}" in layouts
    assert choice.describe() in layouts
    assert min(report, key=lambda r: r["us"])["choice"] == choice


def test_tune_layout_cache_and_apply_roundtrip():
    template = _template()
    c1 = tune_layout(template, C, N, iters=1)
    c2 = tune_layout(template, C, N, iters=1)   # cached — no re-timing
    assert c1 == c2
    fl = apply_layout(FLConfig(n_clusters=C, n_clients=N), c1)
    assert layout_of(fl) == c1
    assert LayoutChoice.from_metadata(c1.to_metadata()) == c1
    if c1.engine == "slab":
        pk = packer_for_layout(template, c1)
        assert pk is packer_for(template, tail="final",
                                sections=c1.sections,
                                min_section_rows=c1.min_section_rows)
    else:
        with pytest.raises(ValueError, match="per-leaf"):
            packer_for_layout(template, c1)


def test_tune_layout_disk_cache_roundtrip(tmp_path):
    """The persisted calibration cache (keyed by template hash) answers a
    cold-process tune without re-timing: seed the file with a sentinel
    choice no measurement would pick, clear the in-memory cache, and the
    sentinel must come back verbatim. A stale/corrupt entry re-measures
    instead of crashing."""
    import repro.common.layout_tune as lt

    template = _template()
    path = str(tmp_path / "layout_tune.json")
    h = lt.template_hash(template, C, N)
    sentinel = LayoutChoice("slab", "tail", 0)
    lt._store_disk_cache(path, {h: sentinel.to_metadata()})
    lt._TUNE_CACHE.clear()
    try:
        got = tune_layout(template, C, N, iters=1, cache_path=path)
        assert got == sentinel
        # memory cache now holds it too — second call never touches disk
        assert tune_layout(template, C, N, iters=1,
                           cache_path=str(tmp_path / "gone.json")) == sentinel
        # corrupt entry -> fall back to measuring (any valid choice is fine)
        lt._store_disk_cache(path, {h: {"engine": "warp-drive"}})
        lt._TUNE_CACHE.clear()
        measured = tune_layout(template, C, N, iters=1, cache_path=path)
        assert isinstance(measured, LayoutChoice)
        # a different template hashes differently: its entry is untouched
        assert lt.template_hash(template, C, N + 1) != h
    finally:
        lt._TUNE_CACHE.clear()   # drop sentinel so later tests re-measure


# ------------------------------------------------- checkpoint layout pin
def test_restore_refuses_cross_layout_checkpoint(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    tree = {"w": jnp.arange(6.0).reshape(2, 3)}
    saved = LayoutChoice("slab", "toplevel", 256)
    save_checkpoint(str(tmp_path), 3, tree,
                    {"layout": saved.to_metadata()})
    # same layout restores fine
    back = restore_checkpoint(str(tmp_path), 3, tree,
                              expected_layout=saved.to_metadata())
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    other = LayoutChoice("slab", "toplevel", 0)
    with pytest.raises(ValueError) as ei:
        restore_checkpoint(str(tmp_path), 3, tree,
                           expected_layout=other.to_metadata())
    msg = str(ei.value)
    assert "min_section_rows': 256" in msg and "min_section_rows': 0" in msg
    # a legacy checkpoint with no layout metadata still restores
    save_checkpoint(str(tmp_path), 4, tree, {})
    restore_checkpoint(str(tmp_path), 4, tree,
                       expected_layout=saved.to_metadata())


def test_restore_leaf_count_mismatch_raises_value_error(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    save_checkpoint(str(tmp_path), 0, {"a": jnp.zeros(3), "b": jnp.ones(2)})
    with pytest.raises(ValueError, match="2 leaves.*has 1"):
        restore_checkpoint(str(tmp_path), 0, {"a": jnp.zeros(3)})


# ------------------------------------------------- trace-time dispatch
def test_on_tpu_resolves_at_trace_time(monkeypatch):
    """No import-time _ON_TPU pin anywhere: faking the backend AFTER
    import flips the dispatch."""
    from repro.kernels import slab
    from repro.kernels.masked_gradnorm import ops as mg_ops
    from repro.kernels.ota_channel import ops as oc_ops

    for mod in (slab, oc_ops, mg_ops):
        assert not hasattr(mod, "_ON_TPU")
    assert slab.on_tpu() is False           # CPU test host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert slab.on_tpu() is True
    assert oc_ops.on_tpu() is True and mg_ops.on_tpu() is True


def test_interpret_default_matches_explicit_on_cpu():
    """interpret=None resolves from the live backend inside the op: on
    this CPU host it must take exactly the interpret=True path."""
    from repro.kernels.ota_channel.ops import ota_mask_weight_apply

    x = jax.random.normal(jax.random.PRNGKey(0), (2048,))
    bits = jax.random.bits(jax.random.PRNGKey(1), (2048,), jnp.uint32)
    a_out, a_mask = ota_mask_weight_apply(x, bits, 0.5, 0.1, 1.0, 0.7,
                                          impl="pallas")
    b_out, b_mask = ota_mask_weight_apply(x, bits, 0.5, 0.1, 1.0, 0.7,
                                          interpret=True, impl="pallas")
    np.testing.assert_array_equal(np.asarray(a_out), np.asarray(b_out))
    np.testing.assert_array_equal(np.asarray(a_mask), np.asarray(b_mask))
