"""The state-space mixer of the decoder LM (models/lm.py): a Mamba-2
selective recurrence with a short depthwise causal convolution in front of
it and a gated group norm behind it, in plain ``jax.numpy``.

For head ``n`` of group ``g``, with a state ``S`` (P, N) in float32::

    S_t = a_t S_{t-1} + Δ_t x_t ⊗ B_t^g        a_t = exp(Δ_t A),  A < 0
    y_t = S_t C_t^g + D x_t

Two forms of the same sum:

- :func:`ssd_step` — one token of each of B rows, the states handed in and
  out (the decode kernel's fallback at widths off its tiles,
  ops/pallas_ssm_decode.py, and the toy's path);
- :func:`ssd_chunked` — a chunk of T tokens of ONE session in blocks of
  ``chunk`` tokens: inside a block the sum over earlier tokens is a masked
  (L, L) product, between blocks a state is carried.  A state comes in and
  the state AT THE LAST REAL TOKEN goes out: a padding position has Δ = 0,
  so it adds nothing and decays nothing.

Everything that feeds the state is float32, and the products that make it
run at ``Precision.HIGHEST``: on a TPU a float32 product otherwise rounds
its operands to bfloat16, and a running sum keeps every such error for as
long as it remembers.  Beside a layer's weight products their cost is
nothing (11 GFLOP against 1.35 TFLOP a 2,048-token chunk at the published
widths).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _ein(spec: str, *operands):
    return jnp.einsum(spec, *operands, precision=HIGHEST,
                      preferred_element_type=F32)


def step_sizes(dt, dt_bias, a_log):
    """(Δ, a): ``Δ = softplus(dt + dt_bias)``, ``a = exp(−Δ exp(A_log))``,
    both float32; ``dt`` (..., H)."""
    delta = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    return delta, jnp.exp(-delta * jnp.exp(a_log.astype(F32)))


def conv_chunk(u, prev, w, b, n_valid):
    """Depthwise causal convolution over a chunk: ``u`` (T, W) of which
    the first ``n_valid`` rows are real, ``prev`` (K − 1, W) the rows
    before the chunk (zeros before a session's first token), ``w`` (W, K),
    ``b`` (W,) → (``SiLU(b + Σ_i w[:, i] u_{t−K+1+i})`` (T, W) float32,
    the last K − 1 REAL rows (``prev``'s own where the chunk has fewer))."""
    K = w.shape[1]
    seq = jnp.concatenate([prev.astype(u.dtype), u], 0)       # (K−1+T, W)
    T = u.shape[0]
    wf = w.astype(F32)
    c = b.astype(F32)[None, :] + sum(
        seq[i:i + T].astype(F32) * wf[None, :, i] for i in range(K))
    return jax.nn.silu(c), lax.dynamic_slice_in_dim(seq, n_valid, K - 1, 0)


def conv_step(u, prev, w, b):
    """The same convolution for one token of each of B rows: ``u`` (B, W),
    ``prev`` (B, K − 1, W) → ((B, W) float32, the rows' new (B, K − 1, W))."""
    seq = jnp.concatenate([prev.astype(u.dtype), u[:, None, :]], 1)
    c = b.astype(F32) + jnp.einsum("bkw,wk->bw", seq.astype(F32),
                                   w.astype(F32), precision=HIGHEST)
    return jax.nn.silu(c), seq[:, 1:]


def ssd_step(x, delta, a, Bm, Cm, D, state):
    """One token of each of B rows.  ``x`` (B, H, P), ``delta``, ``a``
    (B, H), ``Bm``, ``Cm`` (B, G, N), ``D`` (H,), ``state`` (B, H, P, N)
    float32 → (y (B, H, P) float32, the new states)."""
    B_, H, P = x.shape
    G = Bm.shape[1]
    xf = x.astype(F32).reshape(B_, G, H // G, P)
    grouped = lambda t: t.reshape(B_, G, H // G)              # noqa: E731
    s = state.reshape(B_, G, H // G, P, -1)
    s = grouped(a)[..., None, None] * s + (
        (grouped(delta)[..., None] * xf)[..., None]
        * Bm.astype(F32)[:, :, None, None, :])
    y = jnp.sum(s * Cm.astype(F32)[:, :, None, None, :], -1) \
        + D.astype(F32).reshape(G, H // G)[None, :, :, None] * xf
    return y.reshape(B_, H, P), s.reshape(state.shape)


def ssd_chunked(x, delta, a_log, Bm, Cm, D, state, chunk: int, n_valid):
    """A chunk of T tokens of one session, the first ``n_valid`` real.
    ``x`` (T, H, P), ``delta`` (T, H) float32, ``a_log`` (H,), ``Bm``,
    ``Cm`` (T, G, N), ``D`` (H,), ``state`` (H, P, N) float32 the state
    before the chunk → (y (T, H, P) float32, the state after token
    ``n_valid − 1``: ``state`` itself where nothing is real)."""
    T, H, P = x.shape
    G, N = Bm.shape[1:]
    L = min(chunk, T)
    pad = -T % L
    delta = jnp.where((jnp.arange(T) < n_valid)[:, None], delta, 0.0)
    xf, Bf, Cf = (jnp.pad(t.astype(F32), ((0, pad),) + ((0, 0),) * 2)
                  for t in (x, Bm, Cm))
    delta = jnp.pad(delta, ((0, pad), (0, 0)))
    n = (T + pad) // L
    hp = H // G
    # heads lead and a block's tokens are minor: the TPU tiles the last
    # two dims, and (G, H / G) there would pad every array 32-fold
    xf = xf.reshape(n, L, G, hp, P).transpose(0, 2, 3, 1, 4)  # (n,G,hp,L,P)
    Bf = Bf.reshape(n, L, G, N).transpose(0, 2, 1, 3)         # (n, G, L, N)
    Cf = Cf.reshape(n, L, G, N).transpose(0, 2, 1, 3)
    delta = delta.reshape(n, L, G, hp).transpose(0, 2, 3, 1)  # (n, G, hp, L)
    # log of the decay from a block's start to each of its tokens
    cum = jnp.cumsum(
        -delta * jnp.exp(a_log.astype(F32)).reshape(G, hp, 1), -1)
    dx = delta[..., None] * xf                                # Δ_s x_s
    # inside a block: y_l += Σ_{s<=l} exp(cum_l − cum_s) (C_l · B_s) Δ_s x_s
    seen = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    cb = _ein("cgln,cgsn->cgls", Cf, Bf)
    y = _ein("cghls,cghsp->cghlp", decay * cb[:, :, None], dx)
    # what a block adds to the state that leaves it
    to_end = jnp.exp(cum[..., -1:] - cum)                     # (n, G, hp, L)
    added = _ein("cghlp,cgln->cghpn", to_end[..., None] * dx, Bf)
    whole = jnp.exp(cum[..., -1])                             # (n, G, hp)

    def carry(s, block):
        add, shrink = block
        return shrink[..., None, None] * s + add, s           # emits s BEFORE

    last, before = lax.scan(carry, state.reshape(G, hp, P, N).astype(F32),
                            (added, whole))
    y = y + jnp.exp(cum)[..., None] * _ein("cgln,cghpn->cghlp", Cf, before)
    y = y + D.astype(F32).reshape(G, hp)[None, :, :, None, None] * xf
    return (y.transpose(0, 3, 1, 2, 4).reshape(n * L, H, P)[:T],
            last.reshape(H, P, N))


def gated_norm(y, z, w, groups: int, eps: float):
    """``y ⊙ SiLU(z)``, RMS-normed in float32 over each of ``groups``
    groups of channels apart, times ``w``: ``y``, ``z`` (N, C) → (N, C)
    float32 (the gate BEFORE the norm: ``mamba_norm_before_gate`` false)."""
    r = y.astype(F32) * jax.nn.silu(z.astype(F32))
    g = r.reshape(r.shape[0], groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(r.shape) * w.astype(F32)
