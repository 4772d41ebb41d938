"""Channel words computed from their stream positions (DESIGN.md §4,
position form): word m of chunk j of a stream is x0 ^ x1 of
threefry2x32(fold_in(stream_key, j), (0, m)). The position helpers
against the chunked draw, the drawing client-fold kernel (interpret
mode) against the supplied-words path, in both of its block geometries
(``ota_client_fold_drawn2d`` on a 2-D leaf in its own layout,
``ota_client_fold_drawn`` on the flat view), the ω̃ masks FedGradNorm
reads, and whole ``HotaSim`` rounds in both bits modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.channel import channel_params
from repro.kernels.ota_channel.ref import bits_to_mask

CHUNK = ota.CHUNK
C, N = 2, 2


def _stream_keys(key, n):
    return jnp.stack([jax.random.fold_in(key, 100 + s) for s in range(n)])


def _tree(key, tail_w=(40, 8)):
    """(C, N, *shape) gradient leaves; ``trunk/fc0/w`` starts at 2048 in
    its section and runs past the first chunk boundary, the biases and
    ``fc1/w`` leave ragged remainders under ROW_QUANTUM."""
    ks = [jax.random.fold_in(key, i) for i in range(6)]
    shapes = {"final": {"w": tail_w, "b": (8,)},
              "trunk": {"fc0": {"w": (300, 460), "b": (2048,)},
                        "fc1": {"w": (50, 40), "b": (40,)}}}
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    return treedef.unflatten([
        jax.random.normal(k, (C, N) + s) for k, s in zip(ks, leaves)])


def _packer(tree, sections="toplevel"):
    tmpl = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype), tree)
    return packer_for(tmpl, tail="final", sections=sections)


# ------------------------------------------------------ position helpers
# (stream length, start, count): the range drawn from a stream of that
# length, whose last chunk is partial when the length is not a multiple
RANGES = {
    "offset_0": (3 * CHUNK, 0, 5000),
    "mid_chunk": (3 * CHUNK, 777, 3000),
    "crosses_chunk": (3 * CHUNK, CHUNK - 1000, 2500),
    "partial_last_chunk": (2 * CHUNK + 3000, CHUNK + 5, CHUNK + 2995),
}


@pytest.mark.parametrize("case", sorted(RANGES) + ["tail_section"])
def test_stream_words_at_matches_chunked_draw(case):
    key = jax.random.PRNGKey(21)
    if case == "tail_section":
        # the ω̃ tail of a layout whose final.w crosses a chunk boundary
        g = _tree(key, tail_w=(520, 256))
        packer = _packer(g)
        tail = packer.sections[-1]
        keys = ota.section_stream_keys(key, ota.PACKED_TAIL_FOLD, C,
                                       noise=False)
        full = np.asarray(ota._section_bits(key, ota.PACKED_TAIL_FOLD, C,
                                            tail.length))
        runs = [r for r in packer.leaf_runs() if r.section == tail.index]
        assert any(r.offset // CHUNK != (r.offset + r.size - 1) // CHUNK
                   for r in runs)
        for r in runs:
            got = np.asarray(ota.stream_words_at(keys, r.offset, r.size))
            np.testing.assert_array_equal(
                got, full[:, r.offset:r.offset + r.size])
        return
    length, start, count = RANGES[case]
    assert start + count <= length
    keys = _stream_keys(key, 3)
    got = np.asarray(ota.stream_words_at(keys, start, count))
    assert got.shape == (3, count) and got.dtype == np.uint32
    for s in range(3):
        full = np.asarray(ota._chunked_stream(keys[s], length))
        np.testing.assert_array_equal(got[s], full[start:start + count])
        np.testing.assert_array_equal(
            got[s], np.asarray(ota.stream_range_bits(keys[s], start, count)))


def test_stream_chunk_keys_are_the_chunk_folds():
    key = jax.random.PRNGKey(4)
    j0, keys = ota.stream_chunk_keys(key, CHUNK + 10, 2 * CHUNK)
    assert j0 == 1 and keys.shape == (3, 2) and keys.dtype == jnp.uint32
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(keys[i]), np.asarray(jax.random.fold_in(key, 1 + i)))
    # the word formula is jax.random.bits of the chunk key, word by word
    m = jnp.arange(CHUNK, dtype=jnp.uint32)
    words = ota.stream_words(jnp.broadcast_to(keys[0, 0], m.shape),
                             jnp.broadcast_to(keys[0, 1], m.shape), m)
    np.testing.assert_array_equal(
        np.asarray(words),
        np.asarray(jax.random.bits(jax.random.fold_in(key, 1), (CHUNK,),
                                   jnp.uint32)))


# ------------------------------------------------- the drawing kernel
@pytest.mark.parametrize("sections", ["toplevel", "tail"])
def test_drawn_kernel_equals_supplied_words(sections):
    """Fused mode runs ``ota_client_fold_drawn`` on every leaf's
    ROW_QUANTUM main body and the position words on the remainder;
    supplied mode slices the chunked draw. Same words, so the estimate
    is bit-identical, leaf by leaf — with partial participation on."""
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.5, 2.0),
                  noise_std=0.7)
    chan = channel_params(fl)
    key = jax.random.PRNGKey(11)
    g = _tree(jax.random.fold_in(key, 1))
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    packer = _packer(g, sections)
    assert any(r.offset // CHUNK != (r.offset + r.size - 1) // CHUNK
               for r in packer.leaf_runs())
    kw = dict(live=jnp.array([1.0, 0.0]), n_eff=jnp.float32(1.5),
              impl="pallas")
    fused, supplied = (jax.jit(
        lambda k, gg, pp, mode=mode: ota.ota_aggregate_client_folded(
            k, gg, pp, chan, N, packer, bits_mode=mode, **kw))
        for mode in ("fused", "supplied"))
    assert "ota_client_fold_drawn" in str(jax.make_jaxpr(fused)(key, g, p))
    a, b = fused(key, g, p), supplied(key, g, p)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# (per-client leaf shape, the leaf's first stream position, whether the
# kernel reads it in its own layout)
LEAVES = {
    "b128": ((48, 128), 0, True),
    "b256": ((40, 256), 5 * 1024, True),
    "b2048": ((16, 2048), 1024, True),         # two 1024-column blocks
    # starts 5000 words before a chunk boundary: a draw tile takes the
    # first chunk's key for some elements and the next one's for others
    "crosses_chunk": ((64, 384), CHUNK - 5000, True),
    "rows_not_8": ((12, 256), 777, False),
    "cols_not_128": ((64, 80), 3, False),
}


def _fold_case(case):
    """The leaf of ``LEAVES[case]``, its keys and knobs, and ĝ from the
    supplied-words kernel on the positions' words (the oracle)."""
    from repro.kernels.ota_channel.ops import ota_client_fold_apply
    shape, start, native = LEAVES[case]
    key = jax.random.PRNGKey(31)
    g = jax.random.normal(jax.random.fold_in(key, 1), (C, N) + shape)
    p = jax.random.uniform(jax.random.fold_in(key, 2), (C, N), jnp.float32,
                           0.5, 1.5)
    keys = _stream_keys(key, C + 1)
    knobs = (jnp.array([0.5, 2.0]), 3.2e-2, 0.7, 1.0, N)
    kw = dict(live=jnp.array([1.0, 0.0]), n_eff=jnp.float32(1.5),
              interpret=True)
    n = shape[0] * shape[1]
    w = ota.stream_words_at(keys, start, n)
    want = ota_client_fold_apply(g, p, w[:C], w[C], *knobs, impl="pallas",
                                 **kw)
    return g, p, keys, knobs, kw, want


def _pallas_calls(jaxpr):
    """(name, first operand shape) of every pallas_call in ``jaxpr``."""
    return [(e.params["name"], e.invars[0].aval.shape) for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("case", sorted(LEAVES))
def test_native_kernel_equals_supplied_words(case):
    """A leaf of whole (8, 128) tiles goes to ``ota_client_fold_drawn2d``
    as (C, N, a, b), at positions ``word0 + row·b + column``; any other
    to ``ota_client_fold_drawn`` as (C, N, rows, 128). Either way ĝ is
    bit-identical to the supplied-words kernel on the same positions."""
    from repro.kernels.ota_channel.ops import ota_client_fold_drawn_apply
    shape, start, native = LEAVES[case]
    g, p, keys, knobs, kw, want = _fold_case(case)
    j0, table = ota._chunk_key_table(keys, start, shape[0] * shape[1])

    def drawn(gg, pp):
        return ota_client_fold_drawn_apply(
            gg, pp, table.reshape(-1), start - j0 * CHUNK, ota.stream_words,
            *knobs, **kw)
    calls = _pallas_calls(jax.make_jaxpr(drawn)(g, p).jaxpr)
    rows = shape[0] * shape[1] // 128
    assert calls == ([("ota_client_fold_drawn2d", g.shape)] if native else
                     [("ota_client_fold_drawn", (C, N, rows, 128))])
    got = drawn(g, p)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case,block_rows", [
    ("b128", 16), ("b256", 8), ("b2048", 8), ("crosses_chunk", 16)])
def test_native_kernel_row_blocks(case, block_rows, monkeypatch):
    """The same words on a grid of several row blocks (and column blocks
    at b = 2048) as in one block: each block starts at its own position.
    Interpret mode always picks one whole block, so the test sets the
    row block itself."""
    from repro.kernels.ota_channel import kernel
    from repro.kernels.ota_channel.kernel import ota_client_fold_drawn_pallas
    from repro.kernels.ota_channel.ops import _client_params_row
    monkeypatch.setattr(kernel, "_pick_block_rows",
                        lambda *args, **kwargs: block_rows)
    shape, start, _ = LEAVES[case]
    g, p, keys, knobs, kw, want = _fold_case(case)
    j0, table = ota._chunk_key_table(keys, start, shape[0] * shape[1])
    sig, h_th, noise_std, ota_on, _ = knobs
    params = _client_params_row(p, sig, h_th, noise_std, ota_on, kw["live"],
                                kw["n_eff"], N)
    got = ota_client_fold_drawn_pallas(
        g, table.reshape(-1), params, word0=start - j0 * CHUNK,
        words=ota.stream_words, n_clients=N, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_client_folded_passes_leaves_in_their_layout():
    """``ota_aggregate_client_folded(impl="pallas")`` hands fc-shaped
    leaves to ``ota_client_fold_drawn2d`` as they are, (C, N, a, b): no
    reshape in the round makes their (…, 128) view. The 1-D bias of
    1024 entries keeps the flat kernel, the ragged ones the jnp words."""
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.5, 2.0))
    chan = channel_params(fl)
    key = jax.random.PRNGKey(5)
    shapes = {"final": {"w": (64, 256), "b": (256,)},
              "trunk": {"fc0": {"w": (256, 512), "b": (1024,)},
                        "fc1": {"w": (512, 1024), "b": (40,)}}}
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    g = treedef.unflatten([jax.ShapeDtypeStruct((C, N) + s, jnp.float32)
                           for s in leaves])
    p = jax.ShapeDtypeStruct((C, N), jnp.float32)
    packer = _packer(g)
    jaxpr = jax.make_jaxpr(
        lambda k, gg, pp: ota.ota_aggregate_client_folded(
            k, gg, pp, chan, N, packer, impl="pallas"))(key, g, p)
    calls = _pallas_calls(jaxpr.jaxpr)
    native = [(C, N) + s for s in leaves if len(s) == 2]
    assert sorted(s for n, s in calls if n == "ota_client_fold_drawn2d") \
        == sorted(native)
    assert [s for n, s in calls if n == "ota_client_fold_drawn"] == \
        [(C, N, 8, 128)]
    views = {(C, N, a * b // 128, 128) for _, _, a, b in native}
    reshaped = {tuple(e.outvars[0].aval.shape) for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "reshape"}
    assert not views & reshaped, views & reshaped


def test_final_layer_masks_packed_matches_tail_chunks():
    """The ω̃ masks FedGradNorm reads, now drawn at their positions, are
    the masks of the whole tail section's chunked draw."""
    fl = FLConfig(n_clusters=C, n_clients=N, sigma2=(0.5, 2.0))
    chan = channel_params(fl)
    key = jax.random.PRNGKey(9)
    g = _tree(key, tail_w=(520, 256))
    packer = _packer(g)
    tail = packer.sections[-1]
    bits = ota._section_bits(key, ota.PACKED_TAIL_FOLD, C, tail.length)
    want = {}
    for r in packer.leaf_runs():
        if r.section == tail.index:
            b = bits[:, r.offset:r.offset + r.size]
            want[r.leaf] = np.asarray(bits_to_mask(
                b, chan.sigma2.reshape(C, 1), chan.h_threshold,
                chan.ota_on)).reshape((C,) + packer.slots[r.leaf].shape)
    got = ota.final_layer_masks_packed(key, chan, packer)
    assert set(got) == {"w", "b"}
    for name, leaf in (("b", 0), ("w", 1)):
        np.testing.assert_array_equal(np.asarray(got[name]), want[leaf])


# ------------------------------------------------------- whole rounds
@pytest.mark.parametrize("faults", [False, True])
def test_hotasim_rounds_fused_equal_supplied(faults):
    from repro.core.sim import HotaSim
    from repro.models.model import build_model
    fl = FLConfig(n_clusters=C, n_clients=N, faults=faults,
                  dropout_rate=0.3 if faults else 0.0)
    sim = HotaSim(build_model(ModelConfig(family="mlp")), fl,
                  TrainConfig(lr=3e-4), [4, 4])
    x = jax.random.normal(jax.random.PRNGKey(2), (C, N, 4, 256))
    y = jax.random.randint(jax.random.PRNGKey(3), (C, N, 4), 0, 4)

    def rounds(mode):
        step = jax.jit(lambda st, k: sim.step_with_channel(
            st, x, y, k, sim.chan, ota_bits_mode=mode))
        st = sim.init(jax.random.PRNGKey(0))
        for r in range(2):
            st, m = step(st, jax.random.PRNGKey(10 + r))
        return st, m

    (sa, ma), (sb, mb) = rounds("fused"), rounds("supplied")
    for x_, y_ in zip(jax.tree.leaves((sa, ma)), jax.tree.leaves((sb, mb))):
        np.testing.assert_array_equal(np.asarray(x_), np.asarray(y_))
