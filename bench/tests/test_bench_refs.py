"""The reference's channel estimate and the readings taken from it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import ota_spec


def _leaf(seed, noise_std, c=3, n=2, size=4096):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    g = jax.random.normal(k[0], (c, n, size)) * 1e-4
    p = 1.0 + 0.01 * jax.random.normal(k[1], (c, n))
    gbits = jax.random.bits(k[2], (c, size), jnp.uint32)
    nbits = jax.random.bits(k[3], (size,), jnp.uint32)
    p_pass = ota_spec.pass_probability(jnp.ones((c,)), 0.032)
    return ota_spec.aggregate_leaf(g, p, gbits, nbits, p_pass, noise_std, n)


@pytest.mark.parametrize("seed", [0, 1])
def test_estimate_holds_its_noise_term_once(seed):
    """The estimate less its noise term is the noise-free estimate."""
    est, noise = _leaf(seed, 1.0)
    clean, none = _leaf(seed, 0.0)
    assert float(jnp.max(jnp.abs(none))) == 0.0
    # equal to the rounding of the noisy sum, a few ulps of the noise
    ulps = float(jnp.max(jnp.abs(noise))) * 2.0 ** -23
    np.testing.assert_allclose(est - noise, clean, rtol=0, atol=4 * ulps)
    # at the paper's noise level the noise outweighs the gradients
    assert float(jnp.linalg.norm(noise)) > 30 * float(jnp.linalg.norm(clean))


def test_grad_gap_reads_the_gradients_under_the_noise():
    """A server gradient that lost its gradient part but kept the same
    noise reads a grad_gap of 1; one that is right reads 0."""
    rng = np.random.default_rng(0)
    noise = {"a": rng.normal(size=512) * 0.04, "b": rng.normal(size=64) * 0.04}
    signal = {"a": rng.normal(size=512) * 1e-4, "b": rng.normal(size=64) * 1e-4}
    ref_ghat = {k: signal[k] + noise[k] for k in noise}
    theta = {"omega": {k: np.zeros_like(v) for k, v in noise.items()},
             "heads": {"w": np.zeros(8)}}

    def run(ghat):
        return {"loss": [np.ones(3)], "p": [np.ones(3)],
                "norms": [np.ones(3)], "ghat1": ghat,
                "omega": {k: np.ones_like(v) for k, v in noise.items()},
                "heads": {"w": np.ones(8)}}

    ref = dict(run(ref_ghat), noise1=noise, head_grad1={"w": np.ones(8)})
    sound = harness.training_readings(run(ref_ghat), ref, theta)
    silent = harness.training_readings(run(dict(noise)), ref, theta)
    assert sound["grad_gap"] == 0.0
    assert silent["grad_gap"] == pytest.approx(1.0)
