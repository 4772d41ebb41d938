"""Per-kernel shape/dtype sweeps asserting allclose against the pure-jnp
oracles (interpret mode on CPU), plus hypothesis property sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (
    flash_attention, flash_attention_reference,
    masked_gradnorm, masked_gradnorm_reference,
)
from repro.kernels.ota_channel.ops import (
    ota_mask_count_apply, ota_mask_weight_apply,
)
from repro.kernels.ota_channel.ref import bits_to_mask


# ------------------------------------------------- ota_mask_weight / count
# impl="pallas" forces the kernels (interpret mode on CPU). A leaf's
# ROW_QUANTUM main body runs the kernel and the ragged remainder the jnp
# reference, so the grid holds kernel-only leaves ((8, 128), (2048,)) and
# remainder-only ones ((100,), (3, 17, 11)); the oracle is ref.bits_to_mask
# on the same words.
SHAPES = [(100,), (8, 128), (2048,), (3, 17, 11)]


def _mask_weight_ref(x, bits, sigma2, h_th, w):
    m = bits_to_mask(bits, sigma2, h_th).reshape(x.shape)
    return jnp.where(m, w * x.astype(jnp.float32), 0.0), m


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ota_mask_weight_matches_ref(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    n = int(np.prod(shape))
    bits = jax.random.bits(jax.random.PRNGKey(7), (n,), jnp.uint32)
    o1, m1 = ota_mask_weight_apply(x, bits, 1.0, 0.032, 1.0, 0.7,
                                   interpret=True, impl="pallas")
    o2, m2 = _mask_weight_ref(x, bits, 1.0, 0.032, 0.7)
    assert o1.shape == m1.shape == shape and o1.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2, np.float32))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 5000), sigma2=st.floats(0.25, 2.0),
       seed=st.integers(0, 99))
def test_ota_mask_weight_property(n, sigma2, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    bits = jax.random.bits(jax.random.PRNGKey(seed + 1), (n,), jnp.uint32)
    o1, m1 = ota_mask_weight_apply(x, bits, sigma2, 0.032, 1.0, 0.7,
                                   interpret=True, impl="pallas")
    o2, m2 = _mask_weight_ref(x, bits, sigma2, 0.032, 0.7)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2, np.float32))
    # masked entries are exactly zeroed; unmasked carry w·x
    np.testing.assert_array_equal(np.asarray(o1[m1 < 0.5]), 0.0)
    np.testing.assert_allclose(np.asarray(o1[m1 > 0.5]),
                               0.7 * np.asarray(x[m1 > 0.5]), rtol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_ota_mask_count_matches_ref(shape):
    """M_me ∘ (w·x) and Σ_l M_l over three clusters' streams, one of them
    blacked out (live=0): it drops out of the count."""
    c, me = 3, 1
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    n = int(np.prod(shape))
    bits = jax.random.bits(jax.random.PRNGKey(2), (c, n), jnp.uint32)
    sig = jnp.asarray([0.25, 1.0, 2.0])
    live = jnp.asarray([1.0, 1.0, 0.0])
    out, cnt = ota_mask_count_apply(x, bits, me, sig, 0.032, 1.0, 0.7,
                                    live_all=live, interpret=True,
                                    impl="pallas")
    masks = bits_to_mask(bits, sig.reshape(c, 1), 0.032) & (
        live.reshape(c, 1) > 0.5)
    ref_cnt = masks.sum(axis=0).astype(jnp.float32).reshape(shape)
    ref_out = jnp.where(masks[me].reshape(shape), 0.7 * x, 0.0)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(ref_cnt))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-6)
    assert float(jnp.max(cnt)) <= 2.0


# ------------------------------------------------------------ masked_gradnorm
# impl="pallas" forces the tiled kernel (interpret mode on CPU) — off-TPU
# the wrapper dispatches to its jnp reference by default, so the kernel
# itself would silently stop being exercised without the override.
@pytest.mark.parametrize("t,p", [(1, 100), (3, 500), (8, 4096), (16, 10000),
                                 (5, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_gradnorm_matches_ref(t, p, dtype):
    g = jax.random.normal(jax.random.PRNGKey(1), (t, p)).astype(dtype)
    m = jax.random.uniform(jax.random.PRNGKey(2), (p,)) > 0.3
    n1 = masked_gradnorm(g, m, impl="pallas")
    n2 = masked_gradnorm_reference(g, m)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2),
                               rtol=3e-3 if dtype == jnp.bfloat16 else 1e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 12), p=st.integers(1, 3000), seed=st.integers(0, 99))
def test_masked_gradnorm_property(t, p, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (t, p))
    m = jax.random.uniform(jax.random.PRNGKey(seed + 1), (p,)) > 0.5
    n1 = masked_gradnorm(g, m, impl="pallas")
    n2 = masked_gradnorm_reference(g, m)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=2e-5,
                               atol=1e-6)


def test_masked_gradnorm_dispatch_off_tpu():
    """Off-TPU the default dispatch is the jnp reference (the
    interpret-mode pallas_call is ~28x slower for identical values —
    BENCH_kernels.json); both impls agree and the override still forces
    the kernel."""
    from repro.kernels.slab import on_tpu
    g = jax.random.normal(jax.random.PRNGKey(3), (6, 2000))
    m = jax.random.uniform(jax.random.PRNGKey(4), (2000,)) > 0.4
    default = masked_gradnorm(g, m)
    ref = masked_gradnorm_reference(g, m)
    if not on_tpu():  # default == jnp dispatch: bit-identical to the ref
        np.testing.assert_array_equal(np.asarray(default), np.asarray(ref))
    forced = masked_gradnorm(g, m, impl="pallas")
    np.testing.assert_allclose(np.asarray(forced), np.asarray(ref),
                               rtol=2e-5, atol=1e-6)


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize("b,s,h,kv,d,w", [
    (2, 256, 4, 2, 64, None),
    (1, 512, 4, 4, 128, 128),
    (2, 256, 8, 2, 96, 64),
    (1, 128, 2, 1, 32, None),
])
def test_flash_attention_matches_ref(b, s, h, kv, d, w):
    q = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(5), (b, s, kv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (b, s, kv, d), jnp.float32)
    o1 = flash_attention(q, k, v, window=w, block_q=128, block_kv=128)
    o2 = flash_attention_reference(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    b, s, h, kv, d = 1, 256, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(5), (b, s, kv, d)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(6), (b, s, kv, d)).astype(dtype)
    o1 = flash_attention(q, k, v, block_q=128, block_kv=128)
    o2 = flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(
    sq_blocks=st.integers(1, 3),
    heads=st.sampled_from([(2, 1), (4, 2), (4, 4)]),
    d=st.sampled_from([32, 64]),
    window=st.sampled_from([None, 64]),
    seed=st.integers(0, 50),
)
def test_flash_attention_property(sq_blocks, heads, d, window, seed):
    h, kv = heads
    s = 64 * sq_blocks
    q = jax.random.normal(jax.random.PRNGKey(seed), (1, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, s, kv, d))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (1, s, kv, d))
    o1 = flash_attention(q, k, v, window=window, block_q=64, block_kv=64)
    o2 = flash_attention_reference(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)
