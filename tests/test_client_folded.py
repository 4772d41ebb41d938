"""Slab-native simulator channel (DESIGN.md §3.12): the client-folded
zero-copy OTA aggregation vs the per-leaf and slab oracles on shared bit
streams, its fused and supplied word modes, the sim-vs-distributed stream-schedule pin, the SIM_CHAN_FOLD
reserved-domain pin, and the HLO assertion that the new sim step
allocates no (C, P) slab-sized buffer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import hlo_audit
from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.channel import channel_params, stack_channel_params
from repro.kernels.ota_channel.ops import ota_client_fold_apply
from repro.kernels.ota_channel.ref import (
    bits_to_gaussian, bits_to_mask, ota_aggregate_slab_ref,
)

C, N = 3, 2


def _grad_tree(key, C, N, scale=1.0):
    """A raw per-client gradient pytree in the sim's omega layout —
    leaves (C, N, *shape), several trunk layer stacks."""
    ks = [jax.random.fold_in(key, i) for i in range(6)]
    return {
        "final": {"w": jax.random.normal(ks[0], (C, N, 40, 8)) * scale,
                  "b": jax.random.normal(ks[1], (C, N, 8)) * scale},
        "trunk": {"fc0": {"w": jax.random.normal(ks[2], (C, N, 30, 50)) * scale,
                          "b": jax.random.normal(ks[3], (C, N, 50)) * scale},
                  "fc1": {"w": jax.random.normal(ks[4], (C, N, 50, 40)) * scale,
                          "b": jax.random.normal(ks[5], (C, N, 40)) * scale}},
    }


def _template(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype),
                        tree)


def _packer(tree):
    return packer_for(_template(tree), tail="final", sections="toplevel")


# ----------------------------------------------------------------- oracle
def test_client_folded_matches_einsum_plus_slab_ref():
    """Client-folded == einsum("cn,cn...->c...") followed by the slab
    oracle (``ota_aggregate_slab_ref``) on the whole layout's words
    (``packed_gain_bits`` / ``packed_noise_bits``) of the SAME
    multi-section layout — the weighted tree is mathematically folded
    in, not re-derived from different streams."""
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.5, 1.0, 2.0),
                  noise_std=0.7)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(11)
    g = _grad_tree(jax.random.fold_in(key, 1), C, N)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g)

    ghat = ota.ota_aggregate_client_folded(key, g, p, chan, N, packer)
    wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p, l), g)
    oracle = packer.unpack(ota_aggregate_slab_ref(
        packer.pack(wg), ota.packed_gain_bits(key, packer, C),
        ota.packed_noise_bits(key, packer), chan.sigma2, chan.h_threshold,
        chan.noise_std, chan.ota_on, N))
    for (kp, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(ghat)[0],
                               jax.tree_util.tree_flatten_with_path(oracle)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(kp))


@pytest.mark.parametrize("c,sigmas", [(2, (1.0, 0.25)), (4, (0.5,)),
                                      (10, (0.25, 0.5, 1.0, 2.0))])
def test_client_folded_matches_per_leaf_oracle_on_shared_streams(c, sigmas):
    """Decode the SAME section streams into per-leaf masks/noise and run
    the seed per-leaf estimator (ota_aggregate_leaf) on the einsum'd
    weighted tree — the client-folded path must reproduce it."""
    fl = FLConfig(n_clusters=c, n_clients=N, sigma2=sigmas, noise_std=0.4)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(5)
    g = _grad_tree(jax.random.fold_in(key, 1), c, N)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (c, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g)

    ghat = ota.ota_aggregate_client_folded(key, g, p, chan, N, packer)

    bits = ota.packed_gain_bits(key, packer, c)              # (C, P)
    nbits = ota.packed_noise_bits(key, packer)
    sig = chan.sigma2.reshape(c, 1)
    mask_tree = packer.unpack(
        bits_to_mask(bits, sig, chan.h_threshold, chan.ota_on)
        .astype(jnp.float32))
    noise_tree = packer.unpack(bits_to_gaussian(nbits, 1.0)
                               * chan.noise_std * chan.ota_on)
    wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p, l), g)
    oracle = jax.tree.map(
        lambda w, m, z: ota.ota_aggregate_leaf(w, m > 0.5, z, N),
        wg, mask_tree, noise_tree)
    for a, b in zip(jax.tree.leaves(ghat), jax.tree.leaves(oracle)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


BITS_MODES = ["fused", "supplied"]


@pytest.mark.parametrize("bits_mode", BITS_MODES)
def test_client_folded_ota_off_is_weighted_mean(bits_mode):
    """ota=False: all-pass masks, zero AWGN -> ĝ = Σ_l Σ_n p·g / (C·N)."""
    fl = FLConfig(n_clusters=C, n_clients=N, noise_std=7.0, ota=False)
    chan = channel_params(fl)
    g = _grad_tree(jax.random.PRNGKey(3), C, N)
    p = jax.random.uniform(jax.random.PRNGKey(4), (C, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g)
    ghat = ota.ota_aggregate_client_folded(jax.random.PRNGKey(8), g, p,
                                           chan, N, packer,
                                           bits_mode=bits_mode)
    for a, l in zip(jax.tree.leaves(ghat), jax.tree.leaves(g)):
        ref = np.einsum("cn,cn...->...", np.asarray(p), np.asarray(l)) / (C * N)
        np.testing.assert_allclose(np.asarray(a), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bits_mode", BITS_MODES)
def test_client_folded_all_blocked_is_exact_zero(bits_mode):
    """σ² → 0 with H_th > 0: |M| = 0 everywhere -> exactly 0, never
    noise/(cnt·N), never NaN."""
    fl = FLConfig(n_clusters=C, n_clients=N, h_threshold=0.5, noise_std=5.0,
                  sigma2=(1e-14,))
    chan = channel_params(fl)
    g = jax.tree.map(lambda l: jnp.full_like(l, 1e6),
                     _grad_tree(jax.random.PRNGKey(0), C, N))
    p = jnp.full((C, N), 2.0)
    packer = _packer(g)
    ghat = ota.ota_aggregate_client_folded(jax.random.PRNGKey(13), g, p,
                                           chan, N, packer,
                                           bits_mode=bits_mode)
    for leaf in jax.tree.leaves(ghat):
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr, np.zeros_like(arr))


@pytest.mark.parametrize("bits_mode", BITS_MODES)
def test_client_folded_composes_with_scenario_vmap(bits_mode):
    """Under an (S≥4,)-batched ChannelParams bank with a shared key/grads
    (the ScenarioBank contract), every row equals its unbanked run."""
    base = FLConfig(n_clusters=C, n_clients=N)
    bank = stack_channel_params([
        channel_params(base),
        channel_params(FLConfig(n_clusters=C, n_clients=N,
                                sigma2=(0.05, 1.0, 1.0))),
        channel_params(FLConfig(n_clusters=C, n_clients=N, ota=False)),
        channel_params(FLConfig(n_clusters=C, n_clients=N, noise_std=3.0)),
    ])
    key = jax.random.PRNGKey(21)
    g = _grad_tree(jax.random.fold_in(key, 1), C, N)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g)
    banked = jax.vmap(
        lambda ch: ota.ota_aggregate_client_folded(
            key, g, p, ch, N, packer, bits_mode=bits_mode))(bank)
    for s in range(4):
        one = ota.ota_aggregate_client_folded(
            key, g, p, jax.tree.map(lambda x: x[s], bank), N, packer,
            bits_mode=bits_mode)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[s], banked))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(3, 5000), seed=st.integers(0, 99),
       noise=st.floats(0.0, 3.0))
def test_client_fold_kernel_matches_jnp_property(n, seed, noise):
    """ota_client_fold_apply: the Pallas kernel (interpret, main body +
    ragged jnp remainder) == the jnp dispatch on identical pre-sliced
    streams — the kernel-level contract for arbitrary leaf sizes."""
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (C, N, n))
    p = jax.random.uniform(jax.random.fold_in(key, 1), (C, N), jnp.float32,
                           0.5, 1.5)
    bits = jax.random.bits(jax.random.fold_in(key, 2), (C, n), jnp.uint32)
    nbits = jax.random.bits(jax.random.fold_in(key, 3), (n,), jnp.uint32)
    sig = jnp.asarray([0.25, 1.0, 2.0])
    a = ota_client_fold_apply(g, p, bits, nbits, sig, 0.1, noise, 1.0, N,
                              impl="jnp")
    b = ota_client_fold_apply(g, p, bits, nbits, sig, 0.1, noise, 1.0, N,
                              impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def _missing_leaf(g):
    return {"final": g["final"], "trunk": {"fc0": g["trunk"]["fc0"]}}


def _wrong_leaf_shape(g):
    return {**g, "final": {"w": g["final"]["w"][:, :, :10, :],
                           "b": g["final"]["b"]}}


@pytest.mark.parametrize("mangle,names", [
    (_missing_leaf, ("fc1", "missing")),
    (_wrong_leaf_shape, ("final", "(10, 8)")),
], ids=["missing_leaf", "wrong_leaf_shape"])
def test_client_folded_rejects_mismatched_tree(mangle, names):
    """A grads tree that does not match the packer template (beyond its
    (C, N) batch axes) raises the readable error: it names the engine,
    the offending leaf path or shape and the section it belongs in, not
    an opaque downstream shape error."""
    g = _grad_tree(jax.random.PRNGKey(0), C, N)
    packer = _packer(g)
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(1.0,))
    chan = channel_params(fl)
    with pytest.raises(ValueError, match="client-folded") as e:
        ota.ota_aggregate_client_folded(jax.random.PRNGKey(1), mangle(g),
                                        jnp.ones((C, N)), chan, N, packer)
    msg = str(e.value)
    assert "section" in msg and any(s in msg for s in names), msg


def test_client_folded_supplied_equals_fused_on_multisection_layout():
    """The supplied-words mode (the ScenarioBank hoist: whole chunks
    drawn outside, sliced per leaf) must reproduce the fused mode (each
    leaf's words from their positions, the drawing kernel on a leaf's
    ROW_QUANTUM body) on a ``sections="toplevel"`` packer — the
    per-section schedule on BOTH sides. The kernels run in interpret
    mode."""
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.5, 1.0, 2.0),
                  noise_std=0.8)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(31)
    g = _grad_tree(jax.random.fold_in(key, 1), C, N)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g)
    a, b = (ota.ota_aggregate_client_folded(key, g, p, chan, N, packer,
                                            bits_mode=m, impl="pallas")
            for m in BITS_MODES)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


# --------------------------------------------------- stream-schedule pins
def test_sim_and_dist_schedules_draw_identical_bits():
    """The generalized packed schedule (packed_gain_bits/packed_noise_bits
    over ``packed_section_folds``) must produce, section for section, the
    exact streams the slab-native distributed engine draws via
    section_gain_key/section_noise_key (repro.core.hota_slab's scheme) —
    sim and distributed paths see identical bits for identical layouts."""
    g = _grad_tree(jax.random.PRNGKey(0), C, N)
    packer = _packer(g)
    key = jax.random.PRNGKey(77)
    folds = ota.packed_section_folds(packer)
    assert len(folds) == len(packer.sections) > 2     # truly multi-section
    gain_slab = np.asarray(ota.packed_gain_bits(key, packer, C))
    noise_slab = np.asarray(ota.packed_noise_bits(key, packer))
    for sec in packer.sections:
        # the distributed engine's draw (hota_slab count_mode="local")
        dist_bits = np.stack([np.asarray(ota._chunked_stream(
            ota.section_gain_key(key, folds[sec.index], c), sec.length))
            for c in range(C)])
        np.testing.assert_array_equal(
            gain_slab[:, sec.start:sec.start + sec.length], dist_bits)
        dist_nbits = np.asarray(ota._chunked_stream(
            ota.section_noise_key(key, folds[sec.index]), sec.length))
        np.testing.assert_array_equal(
            noise_slab[sec.start:sec.start + sec.length], dist_nbits)


def test_legacy_tail_layout_streams_unchanged():
    """The generalized schedule is bit-identical to the PR-2 head/tail
    derivation on two-section layouts — no silent re-draw of every
    existing figure."""
    g = _grad_tree(jax.random.PRNGKey(0), C, N)
    packer = packer_for(_template(g), tail="final")       # legacy layout
    key = jax.random.PRNGKey(4)
    bits = np.asarray(ota.packed_gain_bits(key, packer, C))
    head = np.asarray(ota._section_bits(key, ota.PACKED_HEAD_FOLD, C,
                                        packer.head_len))
    tail = np.asarray(ota._section_bits(key, ota.PACKED_TAIL_FOLD, C,
                                        packer.tail_len))
    np.testing.assert_array_equal(bits, np.concatenate([head, tail], -1))
    nk = ota.noise_key(key)
    nbits = np.asarray(ota.packed_noise_bits(key, packer))
    nhead = np.asarray(ota._chunked_stream(
        jax.random.fold_in(nk, ota.PACKED_HEAD_FOLD), packer.head_len))
    ntail = np.asarray(ota._chunked_stream(
        jax.random.fold_in(nk, ota.PACKED_TAIL_FOLD), packer.tail_len))
    np.testing.assert_array_equal(nbits, np.concatenate([nhead, ntail]))


# ------------------------------------------------------ SIM_CHAN_FOLD pin
def _key_data(k):
    return tuple(np.asarray(jax.random.key_data(k)).tolist()
                 if hasattr(jax.random, "key_data")
                 else np.asarray(k).tolist())


def test_sim_chan_fold_reserved_and_disjoint():
    """The sim's per-round channel key derives from a named reserved
    fold (DESIGN.md §4) — pinned so a future fold of the step key cannot
    silently collide with the channel streams."""
    assert ota.SIM_CHAN_FOLD == 0x7FFF0003
    k = jax.random.PRNGKey(3)
    ck = ota.sim_channel_key(k)
    assert _key_data(ck) == _key_data(
        jax.random.fold_in(k, ota.SIM_CHAN_FOLD))
    reserved = {ota.NOISE_FOLD, ota.PACKED_HEAD_FOLD, ota.PACKED_TAIL_FOLD,
                ota.PACKED_SECTION_FOLD_BASE, ota.SIM_CHAN_FOLD}
    assert len(reserved) == 5                    # all five domains distinct
    for fold in (0, 1, 17, 999, ota.NOISE_FOLD, ota.PACKED_HEAD_FOLD,
                 ota.PACKED_TAIL_FOLD):
        assert _key_data(jax.random.fold_in(k, fold)) != _key_data(ck)


def test_sim_step_derives_channel_key_from_reserved_fold(monkeypatch):
    """Behavioral pin: tracing one sim round calls ota.sim_channel_key on
    the step key (not a bare literal fold)."""
    from repro.core.sim import HotaSim
    from repro.models.model import build_model
    calls = []
    orig = ota.sim_channel_key

    def spy(k):
        calls.append(k)
        return orig(k)

    monkeypatch.setattr(ota, "sim_channel_key", spy)
    model = build_model(ModelConfig(family="mlp"))
    fl = FLConfig(n_clusters=2, n_clients=2)
    sim = HotaSim(model, fl, TrainConfig(lr=3e-4), [4, 4])
    st_ = sim.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 2, 4, 256))
    y = jnp.zeros((2, 2, 4), jnp.int32)
    sim.step(st_, x, y, jax.random.PRNGKey(9))
    assert len(calls) == 1


# ----------------------------------------------------------- sim HLO pin
def test_sim_packed_step_allocates_no_slab_buffer():
    """The slab-native sim step (use_pallas_ota=True) must compile with
    NO (C, P)- or (P,)-sized buffer, f32 or u32 — neither the einsum'd
    weighted slab, nor a pack copy, nor a slab-wide bit draw exists
    (mirror of the hota_slab assertion in dist_programs/dist_slab_step).
    The (L,) slab-view Adam moments (L = raw param count < P) are the
    allowed flat state."""
    from repro.core.sim import HotaSim
    from repro.models.model import build_model
    Cc, Nn = 2, 2
    model = build_model(ModelConfig(family="mlp"))
    fl = FLConfig(n_clusters=Cc, n_clients=Nn, noise_std=0.4)
    sim = HotaSim(model, fl, TrainConfig(lr=3e-4), [4, 4])
    st_ = sim.init(jax.random.PRNGKey(0))
    x = jnp.zeros((Cc, Nn, 4, 256))
    y = jnp.zeros((Cc, Nn, 4), jnp.int32)
    packer = packer_for(st_.omega, tail="final", sections="toplevel")
    P = packer.size
    L = sum(int(l.size) for l in jax.tree.leaves(st_.omega))
    assert L < P                  # padding makes the sizes distinguishable
    f = jax.jit(lambda s, xx, yy, k, ch: sim.step_with_channel(
        s, xx, yy, k, ch))
    hlo = f.lower(st_, x, y, jax.random.PRNGKey(1),
                  sim.chan).compile().as_text()
    hlo_audit.assert_hlo_pins(
        hlo, hlo_audit.no_slab_pins(Cc, P, note="packed/weighted slab"),
        context="compiled sim step — slab-native channel (§3.12)")
