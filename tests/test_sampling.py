"""Million-client rounds (DESIGN.md §3.15): traced client sampling from
a population bank + streaming cluster aggregation.

Covers the SAMPLE_FOLD reserved domain's position-determinism rule
(channel streams are byte-identical across resamples and across
population sizes — the single-round bit-exactness pin), the
gather/scatter bank shell (population-1 ≡ the plain sim, skipped rounds
are bank identities, the f0 first-seen latch), the streaming aggregator's
equivalence to the all-at-once client-folded path (stream bits EXACT,
values equal up to float associativity) and its peak-memory HLO pin (no
(C, section)-sized stream/mask buffer compiles), the |M∩P|/n_eff
estimator properties under composed sampling+faults (monotone coupling
in every rate, full participation bit-equal to the legacy /N path,
zero-participant identity), and the sweep-engine composition
(ScenarioBank over a SampledHotaSim).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import hlo_audit
from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.common.flatpack import packer_for
from repro.core import ota
from repro.core.channel import channel_params, fault_params
from repro.core.sampling import (ClientBank, SampledHotaSim,
                                 gather_clients, init_client_bank,
                                 scatter_clients)
from repro.kernels.ota_channel.ref import ota_aggregate_slab_ref

C, N = 2, 2


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


def _packer(tree):
    return packer_for(_template(tree), tail="final", sections="toplevel")


def _setup(c=C, n=N, key=11):
    fl = FLConfig(n_clusters=c, n_clients=n,
                  sigma2=tuple(0.5 + 0.5 * i for i in range(c)),
                  noise_std=0.7)
    chan = channel_params(fl)
    k = jax.random.PRNGKey(key)
    g = _grad_tree(jax.random.fold_in(k, 1), c, n)
    p = jax.random.uniform(jax.random.fold_in(k, 2), (c, n), jnp.float32,
                           0.5, 1.5)
    return fl, chan, k, g, p, _packer(g)


@functools.lru_cache(maxsize=None)
def _jitted(c=C, n=N):
    """One compile per (C, N) topology, shared across tests — the eager
    aggregation re-dispatches every interpret-mode kernel per call and
    dominates the suite's runtime otherwise."""
    fl, chan, key, g, p, packer = _setup(c, n)

    def wrap(agg, faulted):
        if faulted:
            return jax.jit(lambda k, gg, pp, lv, ne: agg(
                k, gg, pp, chan, n, packer, live=lv, n_eff=ne))
        return jax.jit(lambda k, gg, pp: agg(k, gg, pp, chan, n, packer))

    return {
        "args": (key, g, p),
        "packer": packer,
        "chan": chan,
        "stream": wrap(ota.ota_aggregate_streaming, False),
        "fold": wrap(ota.ota_aggregate_client_folded, False),
        "stream_f": wrap(ota.ota_aggregate_streaming, True),
        "fold_f": wrap(ota.ota_aggregate_client_folded, True),
        "slab_ref": jax.jit(lambda k, wg: packer.unpack(
            ota_aggregate_slab_ref(
                packer.pack(wg), ota.packed_gain_bits(k, packer, c),
                ota.packed_noise_bits(k, packer), chan.sigma2,
                chan.h_threshold, chan.noise_std, chan.ota_on, n))),
    }


# =================================================== streaming aggregator

def test_streaming_matches_client_folded():
    """Same streams, same math: the lax.scan-over-clusters fold equals
    the all-at-once client-folded path. Values agree up to float
    associativity only (the cross-cluster reduction order changes), so
    the bits are pinned EXACTLY (next test) and the values tightly."""
    j = _jitted()
    s = j["stream"](*j["args"])
    c = j["fold"](*j["args"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), s, c)


def test_streaming_matches_client_folded_faulted():
    """Partial participation: dead clusters masked via live, the traced
    n_eff replacing N — both paths implement the same |M∩P|/n_eff
    estimator."""
    j = _jitted()
    live = jnp.asarray([1.0, 0.0])
    n_eff = jnp.float32(1.5)
    s = j["stream_f"](*j["args"], live, n_eff)
    c = j["fold_f"](*j["args"], live, n_eff)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), s, c)


def test_streaming_stream_bits_exact():
    """The per-cluster streaming draw (stream_range_bits under
    section_gain_key with a traced cluster) is BYTE-identical to the
    corresponding slice of the all-at-once section draw — the chunk-
    truncation rule of DESIGN.md §4, which is what makes resampled /
    streamed rounds consume the same channel."""
    j = _jitted()
    key, packer = j["args"][0], j["packer"]
    folds = ota.packed_section_folds(packer)
    full = ota.section_gain_streams(key, packer, C)       # [(C, L_s)]
    for run in packer.leaf_runs():
        for c in range(C):
            part = ota.stream_range_bits(
                ota.section_gain_key(key, folds[run.section], c),
                run.offset, run.size)
            ref = full[run.section][c, run.offset:run.offset + run.size]
            np.testing.assert_array_equal(
                np.asarray(part), np.asarray(ref),
                err_msg=(f"streaming draw for section {run.section} "
                         f"cluster {c} leaf {run.leaf} diverged from the "
                         f"all-at-once slice"))


def test_streaming_full_participation_bit_equal_legacy():
    """live=1, n_eff=N is BIT-equal to the legacy |M|·N path (live=None)
    — the generalized estimator degrades to eq. 10 exactly, in both the
    streaming and the all-at-once formulation."""
    j = _jitted()
    for plain, faulted in (("stream", "stream_f"), ("fold", "fold_f")):
        a = j[plain](*j["args"])
        b = j[faulted](*j["args"], jnp.ones((C,)), jnp.float32(N))
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)


def test_streaming_zero_participants_zero_estimate():
    """No live cluster ⇒ the guarded estimator returns exactly 0 on
    every entry (no AWGN-only garbage update) in both paths."""
    j = _jitted()
    for faulted in ("stream_f", "fold_f"):
        out = j[faulted](*j["args"], jnp.zeros((C,)), jnp.float32(0.0))
        for leaf in jax.tree.leaves(out):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_streaming_rejects_bad_bits_mode():
    fl, chan, key, g, p, packer = _setup()
    with pytest.raises(ValueError):
        ota.ota_aggregate_streaming(key, g, p, chan, N, packer,
                                    bits_mode="nope")


def test_streaming_hlo_holds_one_cluster():
    """Peak-memory pin: the compiled streaming aggregation contains NO
    (C, L_s) stream/mask buffer for any section and no (C, P) slab — the
    scan body holds one cluster's draw plus the leaf-shaped running sum.
    The all-at-once path in supplied mode, which draws whole chunks,
    compiles exactly such a buffer (positive control, so this pin cannot
    rot into vacuity)."""
    fl, chan, key, g, p, packer = _setup()
    P = packer.size
    lengths = sorted({sec.length for sec in packer.sections})

    def lower(agg, **kw):
        return jax.jit(lambda k, gg, pp: agg(
            k, gg, pp, chan, N, packer, **kw)).lower(
                key, g, p).compile().as_text()

    hlo_s = lower(ota.ota_aggregate_streaming)
    hlo_c = lower(ota.ota_aggregate_client_folded, bits_mode="supplied")
    hlo_audit.assert_hlo_pins(
        hlo_s,
        hlo_audit.no_cluster_stream_pins(C, lengths + [P, ota.CHUNK]),
        context="streaming aggregation — one-cluster peak (§3.15)")
    hlo_audit.assert_hlo_pins(
        hlo_c, hlo_audit.cluster_chunk_stream_pin(C, ota.CHUNK),
        context="client-folded positive control")


@settings(max_examples=3, deadline=None)
@given(c=st.integers(1, 2), n=st.integers(1, 3))
def test_streaming_triple_equivalence(c, n):
    """client-folded ≡ streaming ≡ slab oracle (einsum, then
    ``ota_aggregate_slab_ref`` on the whole layout's words) on shared
    streams, across random (C, N) topologies — three formulations of
    eqs. 3 + 8-10 drawing the same §4 streams."""
    j = _jitted(c, n)
    key, g, p = j["args"]
    s = j["stream"](key, g, p)
    f = j["fold"](key, g, p)
    wg = jax.tree.map(lambda l: jnp.einsum("cn,cn...->c...", p, l), g)
    d = j["slab_ref"](key, wg)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), s, f)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), s, d)


# ============================================ participation × sampling

@settings(max_examples=5, deadline=None)
@given(r1=st.floats(0.0, 1.0), r2=st.floats(0.0, 1.0))
def test_participation_monotone_coupling(r1, r2):
    """Shared uniforms ⇒ raising any single rate only removes (or for
    stragglers, only adds) participants — the CRN coupling that makes
    fault sweeps comparable scenario to scenario."""
    lo, hi = min(r1, r2), max(r1, r2)
    key = jax.random.PRNGKey(7)
    base = fault_params(FLConfig(n_clusters=C, n_clients=N, faults=True))
    for knob in ("dropout", "blackout"):
        plo = ota.draw_participation(
            key, base._replace(**{knob: jnp.float32(lo)}), C, N)
        phi = ota.draw_participation(
            key, base._replace(**{knob: jnp.float32(hi)}), C, N)
        assert float(phi.total) <= float(plo.total), (
            f"{knob}: participant count increased with the rate")
        assert bool(jnp.all(phi.part <= plo.part)), (
            f"{knob}: a client joined when the rate rose — coupling broke")
    slo = ota.draw_participation(
        key, base._replace(straggler=jnp.float32(lo)), C, N)
    shi = ota.draw_participation(
        key, base._replace(straggler=jnp.float32(hi)), C, N)
    assert bool(jnp.all(shi.stale >= slo.stale))


@settings(max_examples=5, deadline=None)
@given(c=st.integers(1, 4), n=st.integers(1, 3), m=st.integers(1, 9))
def test_sample_draw_shape_and_determinism(c, n, m):
    """The SAMPLE_FOLD draw is a pure function of the round key: in
    range, dtype-stable, identical across calls, and independent of
    every other stream (it never consumes channel entropy)."""
    key = jax.random.PRNGKey(c * 100 + n * 10 + m)
    ids = ota.draw_client_sample(key, c, n, m)
    assert ids.shape == (c, n) and ids.dtype == jnp.int32
    assert bool(jnp.all((ids >= 0) & (ids < m)))
    np.testing.assert_array_equal(
        np.asarray(ids), np.asarray(ota.draw_client_sample(key, c, n, m)))


# ======================================================== the client bank

def _mk_sampled(fl, population, n_cls=(4, 4)):
    from repro.models.model import build_model
    model = build_model(ModelConfig(family="mlp"))
    return SampledHotaSim(model, fl, TrainConfig(lr=3e-4), list(n_cls),
                          population)


def _sim_batch(c, n, key=None):
    if key is None:
        return (jnp.zeros((c, n, 4, 256)), jnp.zeros((c, n, 4), jnp.int32))
    return (jax.random.normal(jax.random.fold_in(key, 0), (c, n, 4, 256)),
            jax.random.randint(jax.random.fold_in(key, 1), (c, n, 4), 0, 4))


def test_client_bank_init_shapes_and_sentinel():
    fl = FLConfig(n_clusters=2, n_clients=2)
    samp = _mk_sampled(fl, population=5)
    state = samp.init(jax.random.PRNGKey(0))
    for leaf in jax.tree.leaves(state.bank.heads):
        assert leaf.shape[:3] == (2, 2, 5)
    np.testing.assert_array_equal(np.asarray(state.bank.f0),
                                  -np.ones((2, 2, 5), np.float32))


def test_gather_scatter_roundtrip_and_isolation():
    """scatter(gather) is the identity, and a scatter at ids touches NO
    other bank entry — the disjoint-subpopulation guarantee."""
    fl = FLConfig(n_clusters=2, n_clients=2)
    samp = _mk_sampled(fl, population=5)
    bank = samp.init(jax.random.PRNGKey(0)).bank
    ids = jnp.asarray([[4, 0], [2, 2]], jnp.int32)
    heads, head_opt, f0 = gather_clients(bank, ids)
    back = scatter_clients(bank, ids, heads, head_opt, f0)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), bank, back)
    # a real write lands at ids only
    marked = jax.tree.map(lambda l: l + 1.0, heads)
    out = scatter_clients(bank, ids, marked, head_opt, f0)
    leaf0, new0 = (jax.tree.leaves(bank.heads)[0],
                   jax.tree.leaves(out.heads)[0])
    touched = np.zeros((2, 2, 5), bool)
    touched[np.arange(2)[:, None], np.arange(2)[None, :],
            np.asarray(ids)] = True
    diff = np.any(np.asarray(new0 != leaf0).reshape(2, 2, 5, -1), axis=-1)
    np.testing.assert_array_equal(diff, touched)


def test_population_one_round_equals_plain_sim():
    """With M=1 and the bank holding the plain sim's own slot state, a
    sampled round is BIT-identical to the plain round — the
    gather/scatter shell adds nothing to the round math."""
    fl = FLConfig(n_clusters=2, n_clients=2)
    samp = _mk_sampled(fl, population=1)
    key = jax.random.PRNGKey(3)
    sst = samp.init(key)
    plain_state = sst.sim
    bank = ClientBank(
        heads=jax.tree.map(lambda l: l[:, :, None], plain_state.heads),
        head_opt=jax.tree.map(lambda l: l[:, :, None],
                              plain_state.head_opt),
        f0=plain_state.f0[:, :, None])
    sst = sst._replace(bank=bank)
    x, y = _sim_batch(2, 2, jax.random.fold_in(key, 5))
    rk = jax.random.PRNGKey(9)
    new_s, m_s = samp.step(sst, x, y, rk)
    new_p, m_p = samp.sim.step(plain_state, x, y, rk)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), new_s.sim, new_p)
    np.testing.assert_array_equal(np.asarray(m_s["loss"]),
                                  np.asarray(m_p["loss"]))
    np.testing.assert_array_equal(np.asarray(m_s["sample_ids"]),
                                  np.zeros((2, 2), np.int32))


def test_position_determinism_across_populations():
    """THE tentpole pin (DESIGN.md §4, SAMPLE_FOLD): channel and
    participation streams key off the slot position, never the drawn
    ids — so two rounds that gather identical slot state produce
    BIT-identical outputs even though their populations (3 vs 13) and
    drawn ids differ. Growing the population, or resampling, perturbs
    no mask, no AWGN draw, no fault draw."""
    fl = FLConfig(n_clusters=2, n_clients=2)
    key = jax.random.PRNGKey(0)
    sims = [_mk_sampled(fl, population=m) for m in (3, 13)]
    states = [s.init(key) for s in sims]
    # make every member of BOTH banks equal to bank A's member 0, so any
    # drawn id gathers the same slot state
    src = jax.tree.map(lambda l: l[:, :, :1], states[0].bank.heads)
    states = [
        st_._replace(bank=st_.bank._replace(heads=jax.tree.map(
            lambda s, l: jnp.broadcast_to(s, l.shape), src,
            st_.bank.heads)))
        for st_ in states]
    x, y = _sim_batch(2, 2, jax.random.fold_in(key, 5))
    rk = jax.random.PRNGKey(21)
    outs = [s.step(st_, x, y, rk) for s, st_ in zip(sims, states)]
    ids_a, ids_b = (np.asarray(outs[0][1]["sample_ids"]),
                    np.asarray(outs[1][1]["sample_ids"]))
    assert not np.array_equal(ids_a, ids_b), (
        "degenerate test: both populations drew the same ids")
    for field in ("omega", "p", "heads", "f0"):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=(f"round output {field!r} depends on the drawn "
                         f"ids/population — a stream keyed off the "
                         f"sample draw (DESIGN.md §4 violation)")),
            getattr(outs[0][0].sim, field), getattr(outs[1][0].sim, field))
    np.testing.assert_array_equal(np.asarray(outs[0][1]["loss"]),
                                  np.asarray(outs[1][1]["loss"]))


def test_sampled_f0_latch_and_coverage():
    """Over a few rounds the bank's f0 sentinel flips to a real loss
    exactly for the sampled ids; never-sampled members keep -1."""
    fl = FLConfig(n_clusters=2, n_clients=2)
    samp = _mk_sampled(fl, population=4)
    key = jax.random.PRNGKey(1)
    state = samp.init(key)
    seen = np.zeros((2, 2, 4), bool)
    for r in range(3):
        rk = jax.random.fold_in(key, 100 + r)
        x, y = _sim_batch(2, 2, jax.random.fold_in(rk, 5))
        state, m = samp.step(state, x, y, rk)
        ids = np.asarray(m["sample_ids"])
        seen[np.arange(2)[:, None], np.arange(2)[None, :], ids] = True
        np.testing.assert_array_equal(
            np.asarray(ids), np.asarray(ota.draw_client_sample(
                rk, 2, 2, 4)))
    f0 = np.asarray(state.bank.f0)
    assert np.all(f0[seen] >= 0.0), "a sampled client kept the sentinel"
    assert np.all(f0[~seen] == -1.0), "an unsampled client's f0 moved"


def test_sampled_skip_round_is_bank_identity():
    """dropout=1 ⇒ zero participants ⇒ the round degrades to a bit-exact
    identity on the BANK too (the frozen slot state scatters back
    unchanged), and the skip is reported."""
    fl = FLConfig(n_clusters=2, n_clients=2, faults=True,
                  dropout_rate=1.0)
    samp = _mk_sampled(fl, population=3)
    key = jax.random.PRNGKey(2)
    state = samp.init(key)
    x, y = _sim_batch(2, 2, jax.random.fold_in(key, 5))
    new, m = samp.step(state, x, y, jax.random.PRNGKey(7))
    assert float(m["skipped"]) == 1.0
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state.bank, new.bank)


def test_sim_streaming_gate():
    """fl.ota_streaming=True swaps the sim's aggregation for the
    streaming fold — same streams, so the round agrees with the default
    path to float-associativity tolerance; and the gate composes with
    sampling."""
    key = jax.random.PRNGKey(4)
    x, y = _sim_batch(2, 2, jax.random.fold_in(key, 5))
    rk = jax.random.PRNGKey(6)
    outs = {}
    for streaming in (False, True):
        fl = FLConfig(n_clusters=2, n_clients=2, ota_streaming=streaming)
        samp = _mk_sampled(fl, population=3)
        outs[streaming] = samp.sim.step(samp.sim.init(key), x, y, rk)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        outs[False][0].omega, outs[True][0].omega)
    np.testing.assert_allclose(np.asarray(outs[False][1]["loss"]),
                               np.asarray(outs[True][1]["loss"]),
                               rtol=1e-5, atol=1e-6)
    # sampled + streaming runs end to end
    fl = FLConfig(n_clusters=2, n_clients=2, ota_streaming=True)
    samp = _mk_sampled(fl, population=3)
    state = samp.init(key)
    state, m = samp.step(state, x, y, rk)
    assert np.isfinite(np.asarray(m["loss"])).all()


def test_scenario_bank_over_sampled_sim():
    """The sweep engine composes with sampling unchanged: a ScenarioBank
    over a SampledHotaSim is one vmapped jit, the sample draw shared
    across scenarios (key-only draw ⇒ same ids every scenario)."""
    from repro.core.sweep import ScenarioBank
    fl = FLConfig(n_clusters=2, n_clients=2)
    samp = _mk_sampled(fl, population=4)
    bank = ScenarioBank(samp, [dict(noise_std=0.3), fl])
    states = bank.init(jax.random.PRNGKey(0))
    x, y = _sim_batch(2, 2, jax.random.PRNGKey(5))
    states, m = bank.step(states, x, y, jax.random.PRNGKey(1))
    assert m["loss"].shape[0] == 2
    ids = np.asarray(m["sample_ids"])
    assert ids.shape == (2, 2, 2)
    np.testing.assert_array_equal(ids[0], ids[1])
    for leaf in jax.tree.leaves(states.bank.heads):
        assert leaf.shape[:4] == (2, 2, 2, 4)
