"""The state-space mixer's ``jax.numpy`` forms (ops/ssm.py) against the
recurrence written a token at a time: ``ssd_chunked`` at block sizes that
do and do not divide the chunk, a state in and the state at the last REAL
token out; the causal convolution with its state; the one-token step; the
gated group norm."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import ssm

H, P, N, G = 4, 8, 16, 2


def draw(seed, T):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    return dict(x=f(T, H, P), dt=f(T, H), Bm=f(T, G, N), Cm=f(T, G, N),
                dt_bias=f(H) - 2.0, a_log=jnp.log(jnp.asarray(
                    r.uniform(1, 16, H), jnp.float32)), D=f(H),
                state=f(H, P, N))


def token_at_a_time(t, n_valid):
    """(y (n_valid, H, P), the state after token n_valid − 1)."""
    S = np.asarray(t["state"], np.float64)
    A = -np.exp(np.asarray(t["a_log"], np.float64))
    ys = []
    for i in range(n_valid):
        delta = np.log1p(np.exp(np.asarray(t["dt"][i] + t["dt_bias"],
                                           np.float64)))
        x = np.asarray(t["x"][i], np.float64)
        Bh, Ch = (np.repeat(np.asarray(t[k][i], np.float64), H // G, 0)
                  for k in ("Bm", "Cm"))
        S = np.exp(delta * A)[:, None, None] * S \
            + (delta[:, None] * x)[:, :, None] * Bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", S, Ch)
                  + np.asarray(t["D"], np.float64)[:, None] * x)
    return np.asarray(ys).reshape(n_valid, H, P), S


@pytest.mark.parametrize("T,chunk,n_valid", [
    (16, 8, 16), (16, 8, 11), (13, 8, 13), (13, 5, 9), (7, 128, 7),
    (24, 8, 1), (24, 6, 0), (130, 128, 130), (1, 8, 1)])
def test_chunked_scan_equals_the_recurrence(T, chunk, n_valid):
    t = draw(T * 31 + chunk, T)
    delta, _ = ssm.step_sizes(t["dt"], t["dt_bias"], t["a_log"])
    y, last = jax.jit(ssm.ssd_chunked, static_argnums=7)(
        t["x"], delta, t["a_log"], t["Bm"], t["Cm"], t["D"], t["state"],
        chunk, jnp.asarray(n_valid, jnp.int32))
    want_y, want_s = token_at_a_time(t, n_valid)
    assert y.shape == (T, H, P)
    # float32 sums of up to 128 terms against a float64 recurrence
    np.testing.assert_allclose(np.asarray(y)[:n_valid], want_y, atol=2e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(last, want_s, atol=2e-4, rtol=1e-4)
    if n_valid == 0:
        assert np.array_equal(np.asarray(last), np.asarray(t["state"]))


def test_two_chunks_carry_the_state_between_them():
    t = draw(3, 20)
    delta, _ = ssm.step_sizes(t["dt"], t["dt_bias"], t["a_log"])
    cut = lambda lo, hi: [t[k][lo:hi] if k != "delta" else delta[lo:hi]  # noqa
                          for k in ("x", "delta", "Bm", "Cm")]
    x, d, Bm, Cm = cut(0, 12)
    y0, mid = ssm.ssd_chunked(x, d, t["a_log"], Bm, Cm, t["D"], t["state"],
                              8, 12)
    x, d, Bm, Cm = cut(12, 20)
    y1, last = ssm.ssd_chunked(x, d, t["a_log"], Bm, Cm, t["D"], mid, 8, 8)
    want_y, want_s = token_at_a_time(t, 20)
    np.testing.assert_allclose(np.concatenate([y0, y1]), want_y, atol=5e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(last, want_s, atol=5e-5, rtol=1e-5)


def test_one_token_step_equals_the_recurrence():
    rows = [draw(40 + i, 1) for i in range(3)]
    stack = lambda k: jnp.stack([t[k][0] for t in rows])      # noqa: E731
    delta, a = ssm.step_sizes(stack("dt"), rows[0]["dt_bias"],
                              rows[0]["a_log"])
    y, new = ssm.ssd_step(stack("x"), delta, a, stack("Bm"), stack("Cm"),
                          rows[0]["D"], jnp.stack([t["state"] for t in rows]))
    for i, t in enumerate(rows):
        t = dict(t, dt_bias=rows[0]["dt_bias"], a_log=rows[0]["a_log"],
                 D=rows[0]["D"])
        want_y, want_s = token_at_a_time(t, 1)
        np.testing.assert_allclose(y[i], want_y[0], atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(new[i], want_s, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("T,n_valid", [(9, 9), (9, 5), (9, 2), (9, 0),
                                       (2, 2)])
def test_convolution_keeps_the_last_real_inputs(T, n_valid):
    r = np.random.RandomState(T + n_valid)
    W, K = 6, 4
    u, prev = (jnp.asarray(r.standard_normal(s), jnp.float32)
               for s in ((T, W), (K - 1, W)))
    w, b = (jnp.asarray(r.standard_normal(s), jnp.float32)
            for s in ((W, K), (W,)))
    c, tail = ssm.conv_chunk(u, prev, w, b, jnp.asarray(n_valid, jnp.int32))
    seq = np.concatenate([prev, u])
    want = np.stack([sum(seq[t + i] * np.asarray(w)[:, i] for i in range(K))
                     for t in range(T)]) + np.asarray(b)
    np.testing.assert_allclose(c, want / (1 + np.exp(-want)), atol=1e-5)
    np.testing.assert_array_equal(tail, seq[n_valid:n_valid + K - 1])
    # a token at a time gives the same outputs and the same state
    state = prev[None]
    for t in range(n_valid):
        one, state = ssm.conv_step(u[t][None], state, w, b)
        np.testing.assert_allclose(one[0], c[t], atol=1e-5)
    np.testing.assert_array_equal(state[0], tail)


def test_gated_norm_norms_each_group_apart_after_the_gate():
    r = np.random.RandomState(0)
    y, z = (jnp.asarray(r.standard_normal((5, 12)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(r.standard_normal(12), jnp.float32)
    got = np.asarray(ssm.gated_norm(y, z, w, 3, 1e-5))
    g = np.asarray(y * jax.nn.silu(z)).reshape(5, 3, 4)
    want = (g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(5, 12) * np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # one group's scale leaves the others' outputs alone
    y2 = y.at[:, :4].multiply(100.0)
    again = np.asarray(ssm.gated_norm(y2, z, w, 3, 1e-5))
    np.testing.assert_allclose(again[:, 4:], got[:, 4:], atol=1e-6)
