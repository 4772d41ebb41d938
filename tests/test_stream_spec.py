"""Executable DESIGN.md §4 — the RNG stream specification as a test.

The channel is *defined* by its random streams: every reserved fold
domain, the chunk-quantized threefry draw, and the position-determinism
slice rule are contract, not implementation detail. This suite pins all
of it in one place, parametrized over every reserved fold, so a stream
regression names the offending fold instead of surfacing as a mystery
mismatch three engines away.

What is pinned here (anything that changes a pinned value is a stream-
spec BREAK and needs a DESIGN.md §4 edit + checkpoint-migration story):

* the reserved fold VALUES themselves, and that they are pairwise
  distinct and live at/above the 0x7FFF0000 floor (structurally
  disjoint from any cluster / leaf / chunk index);
* golden first-u32 digests of the gain stream (per fold, cluster 0)
  and the noise stream (per fold) under ``jax.random.PRNGKey(0)``;
* the chunk-slice identity: ``stream_range_bits(key, a, n)`` equals the
  same positions of the whole-stream draw, across chunk boundaries;
* the section-fold schedule: trunk section s ⇒ BASE + s, the ω̃ tail
  keeps PACKED_TAIL_FOLD in every layout;
* the participation sub-folds (dropout/blackout/straggler) and the
  SAMPLE_FOLD client-id draw are disjoint from every channel stream;
* the aux-class salts (init folds, probe folds, the dist backward's
  mask/region salts — DESIGN.md §4 table, class ``aux``) with their own
  value + golden pins, and the KLASS_SALT dict's collision-freedom.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.stream_registry import is_salt_name
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.hota import KLASS_SALT, PACKED_FINAL_FOLD, REGION_SALT
from repro.core.hota_slab import PACKED_OMEGA_FOLD

# Every reserved fold domain of DESIGN.md §4, by name. New domains MUST
# be registered here — the golden tables below force the registration.
RESERVED_FOLDS = {
    "NOISE_FOLD": ota.NOISE_FOLD,
    "PACKED_HEAD_FOLD": ota.PACKED_HEAD_FOLD,
    "PACKED_TAIL_FOLD": ota.PACKED_TAIL_FOLD,
    "SIM_CHAN_FOLD": ota.SIM_CHAN_FOLD,
    "PART_FOLD": ota.PART_FOLD,
    "SAMPLE_FOLD": ota.SAMPLE_FOLD,
    "PACKED_FINAL_FOLD": PACKED_FINAL_FOLD,
    "PACKED_OMEGA_FOLD": PACKED_OMEGA_FOLD,
    "PACKED_SECTION_FOLD_0": ota.PACKED_SECTION_FOLD_BASE + 0,
    "PACKED_SECTION_FOLD_1": ota.PACKED_SECTION_FOLD_BASE + 1,
    "PACKED_SECTION_FOLD_2": ota.PACKED_SECTION_FOLD_BASE + 2,
}

# the spec'd values — a constant that drifts is a silent re-keying of
# every checkpointed stream
FOLD_VALUES = {
    "NOISE_FOLD": 0x7FFFFFFF,
    "PACKED_HEAD_FOLD": 0x7FFF0001,
    "PACKED_TAIL_FOLD": 0x7FFF0002,
    "SIM_CHAN_FOLD": 0x7FFF0003,
    "PART_FOLD": 0x7FFF0004,
    "SAMPLE_FOLD": 0x7FFF0005,
    "PACKED_FINAL_FOLD": 0x7FFF00F1,
    "PACKED_OMEGA_FOLD": 0x7FFF00F2,
    "PACKED_SECTION_FOLD_0": 0x7FFF0100,
    "PACKED_SECTION_FOLD_1": 0x7FFF0101,
    "PACKED_SECTION_FOLD_2": 0x7FFF0102,
}

# Every golden below is drawn under JAX's default threefry, which is the
# partitionable one since JAX 0.5 (digests taken with JAX 0.9.0): a
# sharded draw stays local to its shard. The spec sets no override.

# golden first u32 of the cluster-0 gain stream under PRNGKey(0):
# stream_range_bits(section_gain_key(key, fold, 0), 0, 4)[0]
GOLDEN_GAIN_U32 = {
    "NOISE_FOLD": 0xC4D018C7,
    "PACKED_HEAD_FOLD": 0x07CD6A8E,
    "PACKED_TAIL_FOLD": 0x79A67452,
    "SIM_CHAN_FOLD": 0x5CCC7490,
    "PART_FOLD": 0xF8B2A85D,
    "SAMPLE_FOLD": 0xBAE01355,
    "PACKED_FINAL_FOLD": 0x8163EC7B,
    "PACKED_OMEGA_FOLD": 0x889D730E,
    "PACKED_SECTION_FOLD_0": 0xC679C106,
    "PACKED_SECTION_FOLD_1": 0xBDE68ED0,
    "PACKED_SECTION_FOLD_2": 0x08EC73FE,
}

# golden first u32 of the per-fold noise stream under PRNGKey(0):
# stream_range_bits(section_noise_key(key, fold), 0, 4)[0]
GOLDEN_NOISE_U32 = {
    "NOISE_FOLD": 0xF3DCCBE8,
    "PACKED_HEAD_FOLD": 0x73EC0EF3,
    "PACKED_TAIL_FOLD": 0x7820E606,
    "SIM_CHAN_FOLD": 0x7C91C72F,
    "PART_FOLD": 0xE3797962,
    "SAMPLE_FOLD": 0xECB34803,
    "PACKED_FINAL_FOLD": 0xCDB9FA54,
    "PACKED_OMEGA_FOLD": 0x859F44BF,
    "PACKED_SECTION_FOLD_0": 0x958B3077,
    "PACKED_SECTION_FOLD_1": 0x1F60CBA1,
    "PACKED_SECTION_FOLD_2": 0x03C3E473,
}

# aux-class salts (DESIGN.md §4 table): folded off keys that never meet
# the per-round channel key domain (init keys, probe keys, sub-folds of
# an already-reserved parent), so they may be small — but they are
# registered, value-pinned, and golden-pinned all the same. The four
# *_INIT/*_PROBE/*_MASK entries are the historical bare literals the
# §3.17 lint found; registration kept their VALUES so no stream moved.
AUX_SALTS = {
    "PART_DROP_FOLD": ota.PART_DROP_FOLD,
    "PART_BLACK_FOLD": ota.PART_BLACK_FOLD,
    "PART_STRAG_FOLD": ota.PART_STRAG_FOLD,
    "FINAL_INIT_FOLD": ota.FINAL_INIT_FOLD,
    "SAMPLE_INIT_FOLD": ota.SAMPLE_INIT_FOLD,
    "TUNE_PROBE_FOLD": ota.TUNE_PROBE_FOLD,
    "REGION_SALT": REGION_SALT,
    "HOTA_MASK_SALT": ota.HOTA_MASK_SALT,
}

AUX_VALUES = {
    "PART_DROP_FOLD": 0,
    "PART_BLACK_FOLD": 1,
    "PART_STRAG_FOLD": 2,
    "FINAL_INIT_FOLD": 7,
    "SAMPLE_INIT_FOLD": 11,
    "TUNE_PROBE_FOLD": 99,
    "REGION_SALT": 0xC0,
    "HOTA_MASK_SALT": 0xBEEF,
}

# golden first u32 of bits(fold_in(PRNGKey(0), salt), (4,))[0] — the raw
# derived-key digest (aux salts have no section/noise stream schedule)
GOLDEN_AUX_U32 = {
    "PART_DROP_FOLD": 0xD7A1E7E1,
    "PART_BLACK_FOLD": 0x01DE0365,
    "PART_STRAG_FOLD": 0xE706EF41,
    "FINAL_INIT_FOLD": 0x799CA2BA,
    "SAMPLE_INIT_FOLD": 0x89B358BA,
    "TUNE_PROBE_FOLD": 0x6D60FF09,
    "REGION_SALT": 0x15401035,
    "HOTA_MASK_SALT": 0x2318DD61,
}

# the dist backward's per-klass region-key salts — collision-free dict
KLASS_SALT_VALUES = {"embed": 1, "layers": 2, "final": 3, "mamba": 4,
                     "shared_attn": 5, "shared_mlp": 6, "mlstm": 7,
                     "slstm": 8}

KEY = jax.random.PRNGKey(0)
FOLD_NAMES = sorted(RESERVED_FOLDS)
AUX_NAMES = sorted(AUX_SALTS)


# -------------------------------------------------------------- constants
@pytest.mark.parametrize("name", FOLD_NAMES)
def test_reserved_fold_value_pinned(name):
    assert RESERVED_FOLDS[name] == FOLD_VALUES[name], (
        f"reserved fold {name} changed: 0x{RESERVED_FOLDS[name]:08X} != "
        f"spec'd 0x{FOLD_VALUES[name]:08X} — this re-keys every stream "
        f"drawn under it (DESIGN.md §4)")


@pytest.mark.parametrize("name", FOLD_NAMES)
def test_reserved_fold_above_floor(name):
    assert RESERVED_FOLDS[name] >= 0x7FFF0000, (
        f"reserved fold {name} = 0x{RESERVED_FOLDS[name]:08X} is below "
        f"the 0x7FFF0000 reserved floor — it can collide with a cluster/"
        f"leaf/section index fold")


def test_reserved_folds_pairwise_distinct():
    for a, b in itertools.combinations(FOLD_NAMES, 2):
        assert RESERVED_FOLDS[a] != RESERVED_FOLDS[b], (
            f"reserved folds {a} and {b} collide at "
            f"0x{RESERVED_FOLDS[a]:08X} — their streams are identical")


def test_registry_is_complete():
    """Every named FOLD/SALT constant in the core modules is registered
    here, reserved or aux (new domains must land with golden digests).
    The name filter is the same ``is_salt_name`` the §3.17 lint uses, so
    a constant can't claim registry membership to the linter while
    dodging this scan (or vice versa)."""
    from repro.core import hota, hota_slab
    registered = set(RESERVED_FOLDS.values()) | set(AUX_SALTS.values())
    for mod in (ota, hota, hota_slab):
        for attr in dir(mod):
            if attr.startswith("_") or not is_salt_name(attr):
                continue
            val = getattr(mod, attr)
            if isinstance(val, dict):
                vals = list(val.values())
                assert len(set(vals)) == len(vals), (
                    f"salt dict {attr} has colliding values: {val}")
                continue
            if not isinstance(val, int):
                continue
            if attr == "PACKED_SECTION_FOLD_BASE":
                # registered through its BASE+s instances above
                assert val == FOLD_VALUES["PACKED_SECTION_FOLD_0"]
                continue
            assert val in registered, (
                f"salt constant {attr} = 0x{val:08X} is not registered "
                f"in tests/test_stream_spec.py (RESERVED_FOLDS or "
                f"AUX_SALTS) — register it with golden digests "
                f"(DESIGN.md §4)")


def test_klass_salt_pinned():
    """The per-klass region salts are part of the dist backward's key
    schedule — pinned like any other salt."""
    assert KLASS_SALT == KLASS_SALT_VALUES, (
        f"KLASS_SALT drifted: {KLASS_SALT} != spec'd {KLASS_SALT_VALUES}"
        f" — this re-keys the region mask streams (DESIGN.md §4)")


# ------------------------------------------------------------- aux salts
@pytest.mark.parametrize("name", AUX_NAMES)
def test_aux_salt_value_pinned(name):
    assert AUX_SALTS[name] == AUX_VALUES[name], (
        f"aux salt {name} changed: {AUX_SALTS[name]} != spec'd "
        f"{AUX_VALUES[name]} — this re-keys every draw folded under it "
        f"(DESIGN.md §4)")


def test_aux_salts_pairwise_distinct():
    for a, b in itertools.combinations(AUX_NAMES, 2):
        assert AUX_SALTS[a] != AUX_SALTS[b], (
            f"aux salts {a} and {b} collide at {AUX_SALTS[a]} — draws "
            f"folded under them off a shared parent key are identical")


@pytest.mark.parametrize("name", AUX_NAMES)
def test_golden_aux_first_u32(name):
    got = int(jax.random.bits(
        jax.random.fold_in(KEY, AUX_SALTS[name]), (4,), jnp.uint32)[0])
    assert got == GOLDEN_AUX_U32[name], (
        f"aux-salt stream for {name} drifted: first u32 is 0x{got:08X}, "
        f"spec'd 0x{GOLDEN_AUX_U32[name]:08X} — the derived key moved "
        f"(DESIGN.md §4)")


# ----------------------------------------------------------- derived keys
def test_derived_stream_keys_pairwise_disjoint():
    """fold_in(key, fold) gives pairwise-distinct key material — the
    fold constants separating in key space, not just in value."""
    data = {n: np.asarray(jax.random.key_data(
        jax.random.fold_in(KEY, f))) for n, f in RESERVED_FOLDS.items()}
    for a, b in itertools.combinations(FOLD_NAMES, 2):
        assert not np.array_equal(data[a], data[b]), (
            f"derived keys for folds {a} and {b} coincide — their "
            f"streams are identical")


# --------------------------------------------------------- golden digests
def test_threefry_is_partitionable():
    """The goldens assume JAX's default partitionable threefry; a process
    that turns it off draws different bits from every stream."""
    assert jax.config.jax_threefry_partitionable, (
        "jax_threefry_partitionable is off — every §4 stream differs from "
        "the spec'd draw (DESIGN.md §4)")


@pytest.mark.parametrize("name", FOLD_NAMES)
def test_golden_gain_first_u32(name):
    got = int(ota.stream_range_bits(
        ota.section_gain_key(KEY, RESERVED_FOLDS[name], 0), 0, 4)[0])
    assert got == GOLDEN_GAIN_U32[name], (
        f"gain stream for fold {name} drifted: first u32 is "
        f"0x{got:08X}, spec'd 0x{GOLDEN_GAIN_U32[name]:08X} — the "
        f"chunk-quantized threefry draw changed (DESIGN.md §4)")


@pytest.mark.parametrize("name", FOLD_NAMES)
def test_golden_noise_first_u32(name):
    got = int(ota.stream_range_bits(
        ota.section_noise_key(KEY, RESERVED_FOLDS[name]), 0, 4)[0])
    assert got == GOLDEN_NOISE_U32[name], (
        f"noise stream for fold {name} drifted: first u32 is "
        f"0x{got:08X}, spec'd 0x{GOLDEN_NOISE_U32[name]:08X} — the "
        f"chunk-quantized threefry draw changed (DESIGN.md §4)")


# ----------------------------------------------------- position rules
def test_chunk_slice_identity():
    """stream_range_bits(key, a, n) == whole-stream[a : a+n], including
    across a chunk boundary — the position-determinism slice rule that
    lets per-cluster streaming draws, per-region backward draws and
    whole-section oracle draws consume identical bits."""
    k = ota.section_gain_key(KEY, ota.PACKED_TAIL_FOLD, 1)
    length = ota.CHUNK + 640
    full = ota._chunked_stream(k, length)
    for start, n in [(0, 16), (ota.CHUNK - 8, 16), (ota.CHUNK, 128),
                     (513, 257), (length - 64, 64)]:
        part = ota.stream_range_bits(k, start, n)
        assert jnp.array_equal(part, full[start:start + n]), (
            f"stream_range_bits(start={start}, n={n}) != whole-stream "
            f"slice — the chunk-quantization slice rule broke")


def test_section_fold_schedule():
    """Trunk section s ⇒ PACKED_SECTION_FOLD_BASE + s; the ω̃ tail keeps
    PACKED_TAIL_FOLD in EVERY layout; the legacy two-section layout maps
    to the HEAD/TAIL pair (DESIGN.md §4, fold-after-coalescing rule)."""
    tmpl = {
        "final": {"w": jax.ShapeDtypeStruct((40, 8), jnp.float32)},
        "trunk": {"fc0": {"w": jax.ShapeDtypeStruct((30, 50), jnp.float32)},
                  "fc1": {"w": jax.ShapeDtypeStruct((50, 40), jnp.float32)}},
    }
    multi = packer_for(tmpl, tail="final", sections="toplevel")
    folds = ota.packed_section_folds(multi)
    assert folds[-1] == ota.PACKED_TAIL_FOLD, (
        f"ω̃ tail section fold is 0x{folds[-1]:08X}, not "
        f"PACKED_TAIL_FOLD — eq.-5 consumers would re-draw wrong masks")
    for i, f in enumerate(folds[:-1]):
        assert f == ota.PACKED_SECTION_FOLD_BASE + i, (
            f"trunk section {i} fold is 0x{f:08X}, spec'd BASE+{i} = "
            f"0x{ota.PACKED_SECTION_FOLD_BASE + i:08X}")
    legacy = packer_for(tmpl, tail="final")
    assert ota.packed_section_folds(legacy) == [
        ota.PACKED_HEAD_FOLD, ota.PACKED_TAIL_FOLD], (
        "legacy two-section layout no longer maps to HEAD/TAIL folds")


# --------------------------------------------- participation + sampling
def test_participation_subfolds_disjoint():
    """The dropout/blackout/straggler uniforms draw from sub-folds 0/1/2
    of participation_key(round_key) — pairwise distinct, and distinct
    from the channel key and the sample key of the same round."""
    pk = ota.participation_key(KEY)
    keys = {f"PART_FOLD/{i}": jax.random.fold_in(pk, i) for i in range(3)}
    keys["SIM_CHAN_FOLD"] = ota.sim_channel_key(KEY)
    keys["SAMPLE_FOLD"] = ota.sample_key(KEY)
    keys["NOISE_FOLD"] = ota.noise_key(KEY)
    data = {n: np.asarray(jax.random.key_data(k)) for n, k in keys.items()}
    for a, b in itertools.combinations(sorted(data), 2):
        assert not np.array_equal(data[a], data[b]), (
            f"stream keys {a} and {b} coincide — resampling one would "
            f"perturb the other's draws")


def test_sample_draw_golden():
    """The client-id draw is a pure function of the round key through
    SAMPLE_FOLD — golden-pinned so a re-keying shows up by name."""
    ids = ota.draw_client_sample(KEY, 2, 3, 7)
    assert ids.dtype == jnp.int32
    assert ids.tolist() == [[0, 5, 2], [4, 0, 0]], (
        f"SAMPLE_FOLD client-id draw drifted: {ids.tolist()} — the "
        f"sample stream was re-keyed (DESIGN.md §4)")
    assert bool(jnp.all((ids >= 0) & (ids < 7)))
