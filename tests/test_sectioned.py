"""Section-streaming rounds (DESIGN.md §3.16): the sectioned engine's
equivalence and memory pins, its composition gates, and the kernel-level
cluster blocking it rides on.

Covers: bitwise equivalence of ``ota_aggregate_sectioned`` to the
client-folded engine (streaming=False) and to the cluster-scan streaming
engine (streaming=True) under every composed feature (faults via
live/n_eff, split layouts via max_section_rows); the peak-memory HLO
pins with positive controls (the whole layout's (C, P) gain-word draw,
the supplied-mode client-folded engine's (C, CHUNK) stream draw); the no-silent-inertness
refusals (HotaSim build guards, the distributed step's ota_streaming
rejection, ``apply_layout``'s named LayoutUnavailableError, the stale
disk-cache re-measure path, LayoutBudgetError); the C-axis-blocked
client kernel vs its unblocked form; and the forced-4-device
distributed program (slow marker).
"""
import functools
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_audit
from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.common.flatpack import TreePacker, packer_for
from repro.common import layout_tune as lt
from repro.core import ota
from repro.core.channel import channel_params
from repro.core.sim import HotaSim
from repro.kernels.ota_channel import kernel as K
from repro.models.model import build_model

C, N = 2, 2
SRC = str(Path(__file__).resolve().parent.parent / "src")


def _grad_tree(key, c, n, scale=1.0):
    ks = [jax.random.fold_in(key, i) for i in range(6)]
    return {
        "final": {"w": jax.random.normal(ks[0], (c, n, 40, 8)) * scale,
                  "b": jax.random.normal(ks[1], (c, n, 8)) * scale},
        "trunk": {"fc0": {"w": jax.random.normal(ks[2], (c, n, 30, 50)) * scale,
                          "b": jax.random.normal(ks[3], (c, n, 50)) * scale},
                  "fc1": {"w": jax.random.normal(ks[4], (c, n, 50, 40)) * scale,
                          "b": jax.random.normal(ks[5], (c, n, 40)) * scale}},
    }


def _template(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype),
                        tree)


def _setup(c=C, n=N, key=11, max_section_rows=0):
    fl = FLConfig(n_clusters=c, n_clients=n,
                  sigma2=tuple(0.5 + 0.5 * i for i in range(c)),
                  noise_std=0.7)
    chan = channel_params(fl)
    k = jax.random.PRNGKey(key)
    g = _grad_tree(jax.random.fold_in(k, 1), c, n)
    p = jax.random.uniform(jax.random.fold_in(k, 2), (c, n), jnp.float32,
                           0.5, 1.5)
    packer = packer_for(_template(g), tail="final", sections="toplevel",
                        max_section_rows=max_section_rows)
    return fl, chan, k, g, p, packer


@functools.lru_cache(maxsize=None)
def _jitted(c=C, n=N, msr=0):
    """One compile per (C, N, max_section_rows) topology, shared across
    tests (interpret-mode kernels re-dispatch eagerly otherwise)."""
    fl, chan, key, g, p, packer = _setup(c, n, max_section_rows=msr)

    def wrap(agg, faulted, **kw):
        if faulted:
            return jax.jit(lambda k, gg, pp, lv, ne: agg(
                k, gg, pp, chan, n, packer, live=lv, n_eff=ne, **kw))
        return jax.jit(lambda k, gg, pp: agg(k, gg, pp, chan, n, packer,
                                             **kw))

    return {
        "args": (key, g, p),
        "packer": packer,
        "chan": chan,
        "fold": wrap(ota.ota_aggregate_client_folded, False),
        "stream": wrap(ota.ota_aggregate_streaming, False),
        "sec": wrap(ota.ota_aggregate_sectioned, False),
        "sec_s": wrap(ota.ota_aggregate_sectioned, False, streaming=True),
        "fold_f": wrap(ota.ota_aggregate_client_folded, True),
        "stream_f": wrap(ota.ota_aggregate_streaming, True),
        "sec_f": wrap(ota.ota_aggregate_sectioned, True),
        "sec_sf": wrap(ota.ota_aggregate_sectioned, True, streaming=True),
    }


def _tree_equal(a, b, msg):
    for (ka, la), (_, lb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{msg} at {jax.tree_util.keystr(ka)}")


# ===================================================== engine equivalence

@pytest.mark.parametrize("msr", [0, 8])
def test_sectioned_matches_client_folded_bitwise(msr):
    """streaming=False: every per-leaf kernel call sees byte-identical
    inputs to the client-folded engine's, so the result is BIT-identical
    — not merely associativity-close. Holds on split layouts too (the
    fold schedule changes WITH the packer, identically for both)."""
    j = _jitted(msr=msr)
    _tree_equal(j["sec"](*j["args"]), j["fold"](*j["args"]),
                f"sectioned != client-folded (msr={msr})")


@pytest.mark.parametrize("msr", [0, 8])
def test_sectioned_streaming_matches_streaming_bitwise(msr):
    """streaming=True: the cluster scan nested inside each section
    accumulates every leaf in the same cluster order as the §3.15
    engine — bit-identical to ota_aggregate_streaming."""
    j = _jitted(msr=msr)
    _tree_equal(j["sec_s"](*j["args"]), j["stream"](*j["args"]),
                f"sectioned(streaming) != streaming (msr={msr})")


def test_sectioned_matches_under_faults():
    """Composed partial participation: live-masked clusters and the
    traced n_eff denominator flow through the section schedule
    unchanged — still bit-identical to the respective engines."""
    j = _jitted()
    live = jnp.asarray([1.0, 0.0])
    n_eff = jnp.float32(1.5)
    _tree_equal(j["sec_f"](*j["args"], live, n_eff),
                j["fold_f"](*j["args"], live, n_eff),
                "faulted sectioned != faulted client-folded")
    _tree_equal(j["sec_sf"](*j["args"], live, n_eff),
                j["stream_f"](*j["args"], live, n_eff),
                "faulted sectioned(streaming) != faulted streaming")


def test_sectioned_rejects_bad_bits_mode():
    fl, chan, key, g, p, packer = _setup()
    with pytest.raises(ValueError):
        ota.ota_aggregate_sectioned(key, g, p, chan, N, packer,
                                    bits_mode="nope")


# ======================================================== peak-memory HLO

def _lower(agg, setup, **kw):
    fl, chan, key, g, p, packer = setup
    return jax.jit(lambda k, gg, pp: agg(
        k, gg, pp, chan, N, packer, **kw)).lower(
            key, g, p).compile().as_text()


def test_sectioned_hlo_no_full_slab():
    """The §3.16 pin: the compiled sectioned round holds no (P,)-sized
    or (C, P)-sized f32/u32 buffer — peak live streams are one section.
    Positive control: the whole layout's gain-word draw
    (``packed_gain_bits``) holds the u32[C, P] buffer the pin must
    detect (so this pin cannot rot into vacuity)."""
    setup = _setup()
    fl, chan, key, g, p, packer = setup
    P = packer.size
    for kw in ({}, {"streaming": True}):
        hlo = _lower(ota.ota_aggregate_sectioned, setup, **kw)
        hlo_audit.assert_hlo_pins(
            hlo, hlo_audit.no_slab_pins(C, P),
            context=f"sectioned round {kw} — per-section peak (§3.16)")
    hlo_bits = jax.jit(lambda k: ota.packed_gain_bits(
        k, packer, C)).lower(key).compile().as_text()
    hlo_audit.assert_hlo_pins(
        hlo_bits,
        [hlo_audit.require_buffer((C, P), dtypes=("u32",),
                                  note="the whole layout's (C, P) words")],
        context="whole-layout draw positive control")
    with pytest.raises(AssertionError):
        hlo_audit.assert_hlo_pins(hlo_bits, hlo_audit.no_slab_pins(C, P),
                                  context="the pin sees the control")


def test_sectioned_streaming_hlo_holds_one_cluster_one_section():
    """Composed with the cluster scan, the peak drops further: no
    (C, ·) stream buffer at ANY size — per-section AND per-cluster.
    Positive control: the all-clusters engines that draw whole chunks
    (client-folded in supplied mode, sectioned streaming=False) draw the
    (C, CHUNK) chunked stream."""
    setup = _setup()
    _, chan, key, g, p, packer = setup
    lengths = sorted({sec.length for sec in packer.sections})
    hlo_s = _lower(ota.ota_aggregate_sectioned, setup, streaming=True)
    hlo_audit.assert_hlo_pins(
        hlo_s,
        hlo_audit.no_cluster_stream_pins(
            C, lengths + [packer.size, ota.CHUNK]),
        context="sectioned(streaming=True) — one-cluster peak (§3.16)")
    for agg, kw in ((ota.ota_aggregate_client_folded,
                     {"bits_mode": "supplied"}),
                    (ota.ota_aggregate_sectioned, {})):
        hlo_c = _lower(agg, setup, **kw)
        hlo_audit.assert_hlo_pins(
            hlo_c, hlo_audit.cluster_chunk_stream_pin(C, ota.CHUNK),
            context=f"all-clusters positive control ({agg.__name__})")


# ================================================== no-silent-inertness

def _mk_model():
    return build_model(ModelConfig(family="mlp"))


def test_hotasim_rejects_sectioned_without_slab_engine():
    fl = FLConfig(n_clusters=C, n_clients=N, ota_sectioned=True,
                  use_pallas_ota=False)
    with pytest.raises(ValueError, match="ota_sectioned"):
        HotaSim(_mk_model(), fl, TrainConfig(lr=3e-4), [4, 4])


def test_hotasim_rejects_sectioned_on_two_section_layout():
    fl = FLConfig(n_clusters=C, n_clients=N, ota_sectioned=True,
                  ota_sections="tail")
    with pytest.raises(ValueError, match="multi-section"):
        HotaSim(_mk_model(), fl, TrainConfig(lr=3e-4), [4, 4])


def test_hotasim_rejects_section_split_without_slab_engine():
    fl = FLConfig(n_clusters=C, n_clients=N, max_section_rows=8,
                  use_pallas_ota=False)
    with pytest.raises(ValueError, match="max_section_rows"):
        HotaSim(_mk_model(), fl, TrainConfig(lr=3e-4), [4, 4])


def test_sectioned_sim_round_runs_and_matches():
    """End-to-end sim: one FGN round under ota_sectioned tracks the
    default engine's round (same streams, float-level agreement)."""
    def round_metrics(**kw):
        fl = FLConfig(n_clusters=C, n_clients=N, noise_std=0.1,
                      sigma2=(0.5, 1.0), **kw)
        sim = HotaSim(_mk_model(), fl, TrainConfig(lr=3e-4), [4, 4])
        state = sim.init(jax.random.PRNGKey(0))
        k = jax.random.PRNGKey(1)
        x = jax.random.normal(jax.random.fold_in(k, 0), (C, N, 4, 256))
        y = jax.random.randint(jax.random.fold_in(k, 1), (C, N, 4), 0, 4)
        state, m = sim.step(state, x, y, jax.random.fold_in(k, 2))
        return state.omega, m

    om_a, _ = round_metrics()
    om_b, _ = round_metrics(ota_sectioned=True)
    # a split layout RE-KEYS the streams (fold = BASE + section index),
    # so the msr run compares against the full-slab engine on the SAME
    # split layout — the streaming composition changes only the cluster
    # reduction order (associativity-level)
    om_c, _ = round_metrics(max_section_rows=8)
    om_d, _ = round_metrics(ota_sectioned=True, ota_streaming=True,
                            max_section_rows=8)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), om_a, om_b)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), om_c, om_d)


# ============================================= layout autotuner refusals

def test_apply_layout_refuses_unavailable_engine():
    fl = FLConfig(n_clusters=C, n_clients=N)
    bad = [
        lt.LayoutChoice("warp", "toplevel", 0),            # unknown engine
        lt.LayoutChoice("sectioned", "tail", 0),           # two-section
        lt.LayoutChoice("perleaf", "toplevel", 0, 8),      # split sans slab
        lt.LayoutChoice("slab", "toplevel", 16, 8),        # max < min
        lt.LayoutChoice("slab", "toplevel", 0, -1),        # negative cap
    ]
    for choice in bad:
        with pytest.raises(lt.LayoutUnavailableError):
            lt.apply_layout(fl, choice)


def test_from_metadata_validates_availability():
    with pytest.raises(lt.LayoutUnavailableError):
        lt.LayoutChoice.from_metadata(
            {"engine": "warp", "sections": "toplevel",
             "min_section_rows": 0})
    # the max_section_rows key is optional — old manifests stay valid
    c = lt.LayoutChoice.from_metadata(
        {"engine": "slab", "sections": "toplevel", "min_section_rows": 0})
    assert c.max_section_rows == 0
    assert "max_section_rows" not in c.to_metadata()
    c2 = lt.LayoutChoice("sectioned", "toplevel", 0, 8)
    assert lt.LayoutChoice.from_metadata(c2.to_metadata()) == c2


def test_tune_layout_remeasures_stale_cache(tmp_path):
    """A disk-cache entry naming an engine the current gates cannot run
    is re-measured, not crashed on and not honored (satellite: stale
    LayoutChoice refusal)."""
    template = _template(_grad_tree(jax.random.PRNGKey(0), C, N))
    thresholds = (0,)
    h = lt.template_hash(template, C, N, thresholds, False, None)
    cache = tmp_path / "layout_cache.json"
    cache.write_text(json.dumps(
        {h: {"engine": "warp", "sections": "toplevel",
             "min_section_rows": 0}}))
    lt._TUNE_CACHE.clear()
    choice = lt.tune_layout(template, C, N, thresholds=thresholds,
                            iters=1, include_perleaf=False,
                            cache_path=str(cache))
    assert choice.engine in lt.ENGINES
    # the re-measured winner replaced the stale entry on disk
    fresh = json.loads(cache.read_text())[h]
    assert fresh["engine"] in lt.ENGINES
    lt._TUNE_CACHE.clear()


def test_calibrate_layout_budget_error():
    template = _template(_grad_tree(jax.random.PRNGKey(0), C, N))
    with pytest.raises(lt.LayoutBudgetError):
        lt.calibrate_layout(template, C, N, thresholds=(0,), iters=1,
                            include_perleaf=False, memory_budget_bytes=1)


def test_estimate_peak_slab_bytes_ordering():
    """The coarse working-set model ranks engines the way the §3.16
    scheduling argument says it must: per-leaf ≤ sectioned ≤ full slab,
    and a budget split shrinks the sectioned peak further."""
    template = _template(_grad_tree(jax.random.PRNGKey(0), C, N))
    est = lambda ch: lt.estimate_peak_slab_bytes(template, ch, C, N)
    slab = est(lt.LayoutChoice("slab", "toplevel", 0))
    sec = est(lt.LayoutChoice("sectioned", "toplevel", 0))
    leaf = est(lt.LayoutChoice("perleaf", "toplevel", 0))
    split = est(lt.LayoutChoice("sectioned", "toplevel", 0, 8))
    assert leaf <= sec < slab
    assert split <= sec
    rows = lt._budget_section_rows(C, N, slab)
    assert rows >= 1
    assert lt._budget_section_rows(C, N, 1) == 1


# ================================================ kernel cluster blocking

def _client_kernel_inputs(c=5, n=2, rows=16, key=3):
    k = jax.random.PRNGKey(key)
    x = jax.random.normal(jax.random.fold_in(k, 0),
                          (c, n, rows, K.LANE), jnp.float32)
    bits = jax.random.bits(jax.random.fold_in(k, 1),
                           (c, rows, K.LANE), jnp.uint32)
    nbits = jax.random.bits(jax.random.fold_in(k, 2),
                            (rows, K.LANE), jnp.uint32)
    p_pass = jnp.linspace(0.2, 0.9, c, dtype=jnp.float32)
    p = jax.random.uniform(jax.random.fold_in(k, 4), (c, n), jnp.float32,
                           0.5, 1.5)
    live = jnp.ones((c,), jnp.float32).at[1].set(0.0)
    params = jnp.concatenate([
        p_pass, p.reshape(c * n),
        jnp.asarray([0.7, 1.0], jnp.float32),           # z_std, on
        live, jnp.asarray([float(n)], jnp.float32),
    ]).reshape(1, c * (n + 2) + 3)
    return x, bits, nbits, params


@pytest.mark.parametrize("cb", [1, 2, 3])
def test_blocked_client_kernel_matches_unblocked(cb):
    """C-axis blocking (scratch accumulation over cluster blocks,
    including a live-masked cluster and a padded tail block) equals the
    single-block kernel to fusion level — same float order, so the
    tolerance is ulps, not associativity."""
    x, bits, nbits, params = _client_kernel_inputs(c=5, n=2)
    run = lambda blk: K.ota_aggregate_client_pallas(
        x, bits, nbits, params, n_clients=2, interpret=True,
        cluster_block=blk)
    ref = run(0)       # interpret auto-picks cb=C: the unblocked kernel
    np.testing.assert_allclose(np.asarray(run(cb)), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_auto_cluster_block_fits_budget():
    """The auto block size always fits the VMEM model and never blocks
    when the whole cluster axis fits."""
    assert K._client_cluster_block(4, 2, interpret=True) == 4
    unit = K.SUBLANE * K.LANE * 4
    for c, n in [(4, 2), (64, 8), (1024, 32)]:
        cb = K._client_cluster_block(c, n, interpret=False)
        assert 1 <= cb <= c
        assert cb == c or (cb * (n + 1) + 2) * unit <= K.VMEM_BUDGET_BYTES


def test_client_params_blocked_layout():
    """The re-tiled per-block params rows carry the same (p_pass, w,
    scalars, live, N_eff) layout with live=0 padding on the tail block."""
    _, _, _, params = _client_kernel_inputs(c=5, n=2)
    cb, n_cb = 2, 3
    rows = K._client_params_blocked(params, 5, 2, cb, n_cb)
    assert rows.shape == (n_cb, cb * (2 + 2) + 3)
    p_pass = np.asarray(params[0, :5])
    live = np.asarray(params[0, 5 + 10 + 2:5 + 10 + 2 + 5])
    got_p = np.asarray(rows[:, :cb]).reshape(-1)
    got_live = np.asarray(rows[:, cb * 3 + 2:cb * 3 + 2 + cb]).reshape(-1)
    np.testing.assert_array_equal(got_p[:5], p_pass)
    np.testing.assert_array_equal(got_p[5:], 0.0)
    np.testing.assert_array_equal(got_live[:5], live)
    np.testing.assert_array_equal(got_live[5:], 0.0)    # padded dead
    np.testing.assert_array_equal(np.asarray(rows[:, -1]), 2.0)


# =============================================== distributed (slow path)

@pytest.mark.slow
def test_dist_sectioned_program():
    """Forced-4-device program: sectioned distributed backward bitwise
    vs full-slab under count_mode x max_section_rows, the jnp oracle,
    the end-to-end sectioned train step, and the ota_streaming
    rejection. See tests/dist_programs/dist_sectioned.py."""
    prog = Path(__file__).resolve().parent / "dist_programs" / \
        "dist_sectioned.py"
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin:/usr/local/bin",
           "HOME": "/tmp"}
    r = subprocess.run([sys.executable, str(prog)], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "DIST_SECTIONED_OK" in r.stdout, r.stdout
