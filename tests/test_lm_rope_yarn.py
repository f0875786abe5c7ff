"""YaRN rotary scaling (ops/lm_attention.py::RopeScaling, ``rope``)
against the formulas written out in plain numpy, at the published
``rope_scaling`` of A.X-K1 and at a toy's; ``scaling=None`` is today's
``rope`` bit for bit."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models import lm
from analytics_zoo_tpu.ops import lm_attention as att

PUBLISHED = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
             "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
             "type": "yarn"}
TOY = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
       "beta_fast": 1, "beta_slow": 0.1, "mscale": 1, "mscale_all_dim": 0.5}


def numpy_inv_freq(sc, r, theta):
    f = np.array([theta ** (-2 * i / r) for i in range(r // 2)])
    L = sc["original_max_position_embeddings"]
    at = lambda turns: r * math.log(L / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))                              # noqa: E731
    lo = max(math.floor(at(sc["beta_fast"])), 0)
    hi = min(math.ceil(at(sc["beta_slow"])), r // 2 - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / (hi - lo), 0, 1)
    return lo, hi, f / sc["factor"] * ramp + f * (1 - ramp)


def test_published_frequencies_and_mscale():
    sc = att.RopeScaling.from_dict(PUBLISHED)
    lo, hi, want = numpy_inv_freq(PUBLISHED, 64, 10000.0)
    assert (lo, hi) == (10, 23)                 # ISSUE 33, section 1
    got = sc.inv_freq(64, 10000.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)     # kept
    np.testing.assert_allclose(got[23:], f[23:] / 32, rtol=1e-6)  # slowed
    assert sc.softmax_mscale == pytest.approx(0.1 * math.log(32) + 1)
    assert sc.softmax_mscale == pytest.approx(1.3466, abs=1e-4)
    assert sc.amplitude == pytest.approx(1.0)
    dims = lm.MLADims(64, 1536, 512, 128, 64, 128, 10000.0, sc)
    assert dims.scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2, rel=1e-4)
    assert lm.MLADims(64, 1536, 512, 128, 64, 128, 10000.0).scale \
        == pytest.approx(192 ** -0.5)


@pytest.mark.parametrize("sc,r,theta", [(PUBLISHED, 64, 10000.0),
                                        (TOY, 8, 100.0)])
def test_rope_with_scaling_equals_numpy(sc, r, theta):
    scaling = att.RopeScaling.from_dict(sc)
    rng = np.random.RandomState(0)
    x = rng.normal(size=(5, 3, r)).astype(np.float32)
    pos = np.array([0, 1, 17, 4095, 40000])
    _, _, inv = numpy_inv_freq(sc, r, theta)
    amp = (0.1 * sc["mscale"] * math.log(sc["factor"]) + 1) \
        / (0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1)
    ang = pos[:, None, None] * inv
    cos, sin = np.cos(ang) * amp, np.sin(ang) * amp
    want = np.stack([x[..., 0::2] * cos - x[..., 1::2] * sin,
                     x[..., 0::2] * sin + x[..., 1::2] * cos],
                    -1).reshape(x.shape)
    got = att.rope(jnp.asarray(x), jnp.asarray(pos), theta, scaling)
    # float32 angles of up to 40,000 radians: a few 1e-3 of a turn
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-3)
    assert scaling.amplitude == pytest.approx(amp)


def test_no_scaling_is_todays_rope_bit_for_bit():
    def todays(x, pos, theta):                  # ops/lm_attention.py, PR 32
        r = x.shape[-1]
        freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
        ang = pos.astype(jnp.float32).reshape(
            pos.shape + (1,) * (x.ndim - pos.ndim)) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        xf = x.astype(jnp.float32)
        x0, x1 = xf[..., 0::2], xf[..., 1::2]
        return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                         -1).reshape(x.shape).astype(x.dtype)

    rng = np.random.RandomState(1)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.normal(size=(7, 4, 64)), dtype)
        pos = jnp.asarray(rng.randint(0, 60000, size=7))
        for theta in (8e7, 5e4):
            assert np.array_equal(
                np.asarray(att.rope(x, pos, theta).astype(jnp.float32)),
                np.asarray(todays(x, pos, theta).astype(jnp.float32)))
    assert att.RopeScaling.from_dict(None) is None
    with pytest.raises(ValueError):
        att.RopeScaling.from_dict({"type": "linear", "factor": 2})
