"""The packed layout's streams: eq.-5 masks as the tail slice of the
round's draw, the slab engine vs the per-leaf path with the channel off,
and the PRNG stream-disjointness pins (noise vs cluster fold-in
domains). The client-folded engine's equivalence to the per-leaf oracle
on shared streams lives in tests/test_client_folded.py."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import FLConfig
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.channel import channel_params
from repro.kernels.ota_channel.ref import bits_to_mask


def _wg_tree(key, C, scale=1.0):
    """A per-cluster weighted-grad pytree in the sim's omega layout."""
    ks = [jax.random.fold_in(key, i) for i in range(4)]
    return {
        "final": {"w": jax.random.normal(ks[0], (C, 40, 8)) * scale,
                  "b": jax.random.normal(ks[1], (C, 8)) * scale},
        "trunk": {"fc0": {"w": jax.random.normal(ks[2], (C, 30, 50)) * scale,
                          "b": jax.random.normal(ks[3], (C, 50)) * scale}},
    }


def _template(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                        tree)


def test_final_masks_are_tail_slice_of_round_draw():
    """final_layer_masks_packed must reproduce, bit-for-bit, the masks the
    full aggregation applies to the ω̃ tail (eq. 5 == transmission)."""
    C = 3
    fl = FLConfig(n_clusters=C, n_clients=2, sigma2=(0.5, 1.0, 2.0))
    chan = channel_params(fl)
    key = jax.random.PRNGKey(7)
    wg = _wg_tree(jax.random.fold_in(key, 1), C)
    packer = packer_for(_template(wg), tail="final")

    fmasks = ota.final_layer_masks_packed(key, chan, packer)

    bits = ota.packed_gain_bits(key, packer, C)
    sig = chan.sigma2.reshape(C, 1)
    full_masks = bits_to_mask(bits, sig, chan.h_threshold, chan.ota_on)
    tail_masks = packer.unpack_tail(packer.tail_slice(full_masks))

    for a, b in zip(jax.tree.leaves(fmasks), jax.tree.leaves(tail_masks)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # masks are non-trivial at the default threshold
    rate = float(jnp.mean(jnp.concatenate(
        [m.reshape(-1).astype(jnp.float32)
         for m in jax.tree.leaves(fmasks)])))
    assert 0.5 < rate < 1.0


def test_sim_packed_equals_per_leaf_when_ota_off():
    """End-to-end: with the channel off both sim paths are the exact same
    weighted mean, so one step from identical init must match leaf-for-leaf
    (the only scenario where the two PRNG schemes cannot differ). The
    packed path keeps its PS Adam moments as one flat slab
    (repro.optim.adam.SlabAdamState), so the optimizer states are
    compared through ``tree_to_slab`` rather than leaf-zipped."""
    import dataclasses
    from repro.common.config import ModelConfig, TrainConfig
    from repro.core.sim import HotaSim
    from repro.optim.adam import tree_to_slab
    C, N = 2, 2
    model_cfg = ModelConfig(family="mlp")
    from repro.models.model import build_model
    model = build_model(model_cfg)
    base = FLConfig(n_clusters=C, n_clients=N, ota=False, noise_std=3.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (C, N, 8, 256))
    y = jax.random.randint(jax.random.PRNGKey(2), (C, N, 8), 0, 4)
    outs = []
    for packed in (True, False):
        fl = dataclasses.replace(base, use_pallas_ota=packed)
        sim = HotaSim(model, fl, TrainConfig(lr=3e-4), [4, 4])
        st_ = sim.init(jax.random.PRNGKey(0))
        st_, m = sim.step(st_, x, y, jax.random.PRNGKey(9))
        outs.append((st_, m))
    (st_p, m_p), (st_l, m_l) = outs
    for field in ("omega", "heads", "p", "head_opt", "fgn", "f0", "step"):
        for a, b in zip(jax.tree.leaves(getattr(st_p, field)),
                        jax.tree.leaves(getattr(st_l, field))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=field)
    for a, b in zip(jax.tree.leaves(m_p), jax.tree.leaves(m_l)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert int(st_p.ps_opt.step) == int(st_l.ps_opt.step)
    for slab, tree in ((st_p.ps_opt.mu, st_l.ps_opt.mu),
                       (st_p.ps_opt.nu, st_l.ps_opt.nu)):
        np.testing.assert_allclose(np.asarray(slab),
                                   np.asarray(tree_to_slab(tree)),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------- PRNG pins
def _key_data(k):
    return tuple(np.asarray(jax.random.key_data(k)).tolist()
                 if hasattr(jax.random, "key_data")
                 else np.asarray(k).tolist())


def test_noise_key_disjoint_from_cluster_keys():
    """The old noise fold (999) collided with cluster_key(ks, 999); the new
    NOISE_FOLD domain sits above any reachable cluster index."""
    ks = ota.leaf_key(jax.random.PRNGKey(0), 0)
    nk = _key_data(ota.noise_key(ks))
    for c in (0, 1, 998, 999, 1000, 4095):
        assert _key_data(ota.cluster_key(ks, c)) != nk
    assert ota.NOISE_FOLD == 0x7FFFFFFF
    # the packed section folds live in the same reserved range
    assert ota.PACKED_HEAD_FOLD > 0x7FFF0000
    assert ota.PACKED_TAIL_FOLD > 0x7FFF0000


def test_noise_stream_pinned():
    """Pin the per-leaf noise stream to the NOISE_FOLD derivation so future
    refactors can't silently shift every figure's AWGN draws."""
    fl = FLConfig(n_clusters=2, n_clients=1, h_threshold=0.0, noise_std=1.0,
                  use_pallas_ota=False)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(4)
    wg = {"w": jnp.zeros((2, 64))}       # all-pass masks, zero signal
    ghat = ota.ota_aggregate_tree(key, wg, chan, 1)
    ks = ota.leaf_key(key, 0)
    expected = jax.random.normal(
        jax.random.fold_in(ks, ota.NOISE_FOLD), (64,)) / 2.0
    np.testing.assert_allclose(np.asarray(ghat["w"]), np.asarray(expected),
                               rtol=1e-6)
