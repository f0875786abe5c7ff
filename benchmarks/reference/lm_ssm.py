"""Plain float32 reference of one pipeline stage's share of a ``falcon_h1``
decoder LM (configs/falcon-h1-34b-pp12.json): weights from a seed and the
full forward of one session's tokens — no cache, no kernels, no chunks:
the recurrence a token at a time, attention over the whole session.

Straightforward ``jax.numpy``; it imports nothing of ``analytics_zoo_tpu``
and takes nothing the program made.  From ``reference/lm.py`` it takes
what is not the model: the seed law (``_key``, ``_normal``, the norms'
weights), ``ein`` (a product in a stated arithmetic), ``jit`` /
``compile_only`` (compiling ahead), ``in_blocks`` and the norm; from
``reference/lm_gqa.py`` the rotary in pairs at a distance and one block of
softmax attention.  The weight trees' NAMES are the program's interface.

Every block is the same (ISSUE 39, section 1; eps ``rms_norm_eps``, no
bias but the convolution's).  With the multipliers ``m_*`` the config's
keys give, for a token at position ``t``, ``x = RMSNorm(h; w_in)``:

- **attention**: ``q = (m_ai x) W_q`` (20 heads x 128), ``k = m_k (m_ai x)
  W_k``, ``v = (m_ai x) W_v`` (4 KV heads x 128); rotary on ALL 128 dims of
  every ``q`` and ``k`` head in pairs ``(j, j + 64)``, base ``rope_theta``,
  unscaled; head ``a`` reads KV head ``a // 5``; scores ``q · k · 128^-1/2``
  over ``j <= t``, softmax in float32; ``A = m_ao (o W_o)``;
- **mixer**: ``p = ((m_si x) W_in) ⊙ μ`` (μ: ``m_z`` over the gate's 4,096
  columns, ``m_x`` over x's 4,096, ``m_B``, ``m_C`` over 512 each, ``m_dt``
  over the last 32), ``p = [z | u | dt]``; ``c_t = SiLU(b + Σ_i w[:, i]
  u_{t−3+i})`` (zeros before the first token), ``c = [x̃ (32 x 128) | B
  (2 x 256) | C (2 x 256)]``; ``Δ = softplus(dt + dt_bias)``, ``a = exp(−Δ
  exp(A_log))``; head ``n`` of group ``n // 16``: ``S_t = a S_{t−1} + Δ x̃_t
  ⊗ B_t``, ``y_t = S_t C_t + D x̃_t``, ``S`` (128 x 256) float32, zero
  before the first token; ``r = y ⊙ SiLU(z)`` RMS-normed over each group
  of 2,048 channels apart, times ``w_norm``; ``M = m_so (r W_out)``;
- ``h += M + A``; ``x' = RMSNorm(h; w_ff)``; ``h += m_d ((SiLU(m_g x'
  W_gate) ⊙ x' W_up) W_down)``;
- after the last block ``logits = m_h (RMSNorm(h; w_f) W_head)`` over the
  vocabulary slice.

``mode``: the arithmetic of every matrix product (``f32`` at HIGHEST — the
reference; ``bf16`` — operands rounded, a second witness; ``int8`` — the
control the comparison has to fail).  ``fault`` plants one fault:
``truncate[:n]`` (attention to the last n = 2,048 positions only),
``shift_cache`` (every key and value one position late), ``state_bf16``
(``S`` rounded to bfloat16 after every token), ``state_not_reset`` (``S``
starts from the state the session before it in the call ended with; the
first from the last's), ``conv_state_late`` (the convolution reads ``u``
one token late), ``pad_advances`` (``S`` decays on for as many further
tokens as pad the session to whole chunks of ``mamba_chunk_size``),
``no_dt_bias``, ``no_D``, ``one_group`` (group 0's ``B`` and ``C`` for
all heads), ``norm_before_gate``, ``no_mup`` (every multiplier 1),
``heads_per_kv_8`` (head ``a`` reads KV head ``a // 8``).

The model makes no discrete choice: there is nothing to ``follow``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.lm import (ROUNDED, _key, _norm_weight,  # noqa: F401
                                     _normal, compile_only, ein, in_blocks,
                                     jit, rms_norm)
from benchmarks.reference.lm_gqa import _attend_block, rope_first

F32 = jnp.float32
FAULTS = ("truncate", "shift_cache", "state_bf16", "state_not_reset",
          "conv_state_late", "pad_advances", "no_dt_bias", "no_D",
          "one_group", "norm_before_gate", "no_mup", "heads_per_kv_8")


def dims(cfg: Dict) -> Dict:
    """The sizes and the multipliers the equations use, from the
    configuration's published keys."""
    H = int(cfg["mamba_n_heads"])
    inner = int(cfg.get("mamba_d_ssm")
                or cfg["mamba_expand"] * cfg["hidden_size"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    gate, down = cfg["mlp_multipliers"]
    return dict(
        d=int(cfg["hidden_size"]), layers=int(cfg["num_hidden_layers"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]), k=int(cfg["head_dim"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        f=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        H=H, P=inner // H, N=N, G=G, K=int(cfg["mamba_d_conv"]), inner=inner,
        conv_width=inner + 2 * G * N, chunk=int(cfg["mamba_chunk_size"]),
        m_e=float(cfg["embedding_multiplier"]),
        m_ai=float(cfg["attention_in_multiplier"]),
        m_k=float(cfg["key_multiplier"]),
        m_ao=float(cfg["attention_out_multiplier"]),
        m_si=float(cfg["ssm_in_multiplier"]),
        m_ssm=tuple(float(m) for m in cfg["ssm_multipliers"]),
        m_so=float(cfg["ssm_out_multiplier"]), m_g=float(gate),
        m_d=float(down), m_h=float(cfg["lm_head_multiplier"]))


def proj_blocks(D: Dict):
    """The in-projection's five column blocks ``[z | x | B | C | dt]``:
    (width, its multiplier)."""
    bc = D["G"] * D["N"]
    return tuple(zip((D["inner"], D["inner"], bc, bc, D["H"]), D["m_ssm"]))


# ---------------------------------------------------------------------------
# weights from the seed (reference/lm.py's law; every matrix of variance
# 1 / fan_in DIVIDED BY THE MULTIPLIER THAT FOLLOWS IT, so that with the
# published multipliers applied every branch carries the magnitude it would
# at unit multipliers)
# ---------------------------------------------------------------------------

def _matrix(key, shape, fan_in: int, by: float):
    return _normal(key, shape, std=1.0 / (math.sqrt(fan_in) * by))


def layer_weights(seed: int, cfg: Dict, layer: int) -> Dict:
    """One block's weights (bfloat16 arrays on the default device;
    ``dt_bias``, ``A_log`` and ``D`` float32)."""
    D = dims(cfg)
    d, H = D["d"], D["H"]
    k = iter(jax.random.split(_key(seed, 1, layer), 24))
    attn = {"wq": _matrix(next(k), (d, D["heads"], D["k"]), d, 1.0),
            "wk": _matrix(next(k), (d, D["kv_heads"], D["k"]), d, D["m_k"]),
            "wv": _matrix(next(k), (d, D["kv_heads"], D["k"]), d, 1.0),
            "wo": _matrix(next(k), (D["heads"], D["k"], d),
                          D["heads"] * D["k"], D["m_ao"])}
    step = jnp.exp(jax.random.uniform(next(k), (H,), F32, math.log(1e-3),
                                      math.log(1e-1)))
    mixer = {
        "in_proj": jnp.concatenate(
            [_matrix(next(k), (d, n), d, D["m_si"] * m)
             for n, m in proj_blocks(D)], 1),
        "conv_w": (jax.random.uniform(next(k), (D["conv_width"], D["K"]), F32)
                   - 0.5).astype(jnp.bfloat16),
        "conv_b": jnp.zeros((D["conv_width"],), jnp.bfloat16),
        # softplus(dt_bias) is log-uniform on (0.001, 0.1)
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(next(k), (H,), F32, 1.0, 16.0)),
        "D": jnp.ones((H,), F32),
        "norm": _norm_weight(next(k), D["inner"]),
        "out_proj": _matrix(next(k), (D["inner"], d), D["inner"], D["m_so"])}
    mlp = {"w_gate": _matrix(next(k), (d, D["f"]), d, D["m_g"]),
           "w_up": _matrix(next(k), (d, D["f"]), d, 1.0),
           "w_down": _matrix(next(k), (D["f"], d), D["f"], D["m_d"])}
    return {"attn_norm": _norm_weight(next(k), d),
            "mlp_norm": _norm_weight(next(k), d),
            "attn": attn, "ssm": mixer, "mlp": mlp}


def end_weights(seed: int, cfg: Dict) -> Dict:
    D = dims(cfg)
    k = jax.random.split(_key(seed, 2), 3)
    return {"embed": _normal(k[0], (D["vocab"], D["d"]),
                             std=1.0 / D["m_e"]),
            "final_norm": _norm_weight(k[1], D["d"]),
            "head": _matrix(k[2], (D["d"], D["vocab"]), D["d"], D["m_h"])}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@jit(static_argnums=(3, 4, 5, 6))
def _project(x, w, pos, theta, m_ai, m_k, mode):
    """(q (L, H, dk), k (L, KVH, dk), v (L, KVH, dv)), q and k rotated on
    all their dims."""
    x = m_ai * x
    q = ein("nd,dhe->nhe", x, w["wq"], mode)
    k = m_k * ein("nd,dge->nge", x, w["wk"], mode)
    v = ein("nd,dge->nge", x, w["wv"], mode)
    r = q.shape[-1]
    return rope_first(q, pos, r, theta), rope_first(k, pos, r, theta), v


def attention(x, w, D: Dict, pos, mode, fault: Optional[str], blocks: Dict):
    """``m_ao (o W_o)`` (L, d) for normed input ``x``: every block of
    queries against the keys up to its own end."""
    L, rows, H = x.shape[0], blocks["pad_to"], D["heads"]
    q, k, v = in_blocks(
        lambda x_, p_: _project(
            x_, {n: w[n] for n in ("wq", "wk", "wv")}, p_, D["theta"],
            D["m_ai"], D["m_k"], mode),
        x, pos, block=rows)
    if fault == "shift_cache":
        k, v = (jnp.roll(t, 1, 0) for t in (k, v))
    # a head's own copy of its KV head's keys and values: head a reads KV
    # head a // (H / KVH)
    per_kv = 8 if fault == "heads_per_kv_8" else H // D["kv_heads"]
    of_head = np.arange(H) // per_kv
    k, v = k[:, of_head], v[:, of_head]
    last = int(fault.partition(":")[2] or 2048) \
        if fault and fault.startswith("truncate") else 0
    qb = min(blocks["q_block"], L)
    if L % qb or H % blocks["head_group"]:
        raise ValueError(f"pad_to {rows} is not whole blocks of {qb} "
                         f"queries, or {H} heads not whole groups")
    static = dict(mode=mode, scale=D["k"] ** -0.5, last=last, qb=qb,
                  kg=blocks["head_group"])
    q, wo = q[:, :, None, :], w["wo"][:, None]
    parts = [sum(_attend_block(
        q, pos, k, v, None, wo, np.int32(lo), np.int32(0), np.int32(g),
        span=min(L, -(-(lo + qb) // blocks["key_round"])
                 * blocks["key_round"]), **static)
        for g in range(0, H, blocks["head_group"]))
        for lo in range(0, L, qb)]
    return D["m_ao"] * jnp.concatenate(parts, 0)


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------

@jit(static_argnums=(2, 3))
def _in_proj(x, w_in, by, mode):
    """``((m_si x) W_in) ⊙ μ``: ``by`` = (m_si, ((width, m), ...))."""
    m_si, cols = by
    mu = np.concatenate([np.full(n, m, np.float32) for n, m in cols])
    return ein("nd,dp->np", m_si * x, w_in, mode) * mu


def conv(u, w, b, late: bool):
    """``SiLU(b + Σ_i w[:, i] u_{t−K+1+i})`` with zeros before the first
    token; ``u`` (L, W), ``w`` (W, K)."""
    L, K = u.shape[0], w.shape[1]
    seq = jnp.pad(u, ((K - 1 + late, 0), (0, 0)))
    w = w.astype(F32)
    return jax.nn.silu(b.astype(F32) + sum(seq[i:i + L] * w[:, i]
                                           for i in range(K)))


@jit(static_argnums=(6, 7))
def _recur(S, x, delta, a, Bm, Cm, round_state, groups):
    """``S_t = a_t S_{t−1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t``, a token at
    a time.  ``S`` (H, P, N); ``x`` (T, H, P); ``delta``, ``a`` (T, H);
    ``Bm``, ``Cm`` (T, G, N) → (y (T, H, P), S after the last token).  A
    token with Δ = 0 and a = 1 moves nothing (what stands behind a
    session's last token is given so)."""
    hp = x.shape[1] // groups

    def token(S, t):
        x_t, d_t, a_t, B_t, C_t = t
        B_t, C_t = (jnp.repeat(g, hp, 0) for g in (B_t, C_t))     # (H, N)
        S = a_t[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if round_state:
            # not a pair of casts: the TPU's compiler takes those out
            S = lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(S * C_t[:, None, :], -1)

    S, y = lax.scan(token, S, (x, delta, a, Bm, Cm))
    return y, S


@jit(static_argnums=(3, 4, 5, 6))
def _mixer_out(y, z, w, groups, eps, m_so, mode_before):
    """``m_so (norm(y ⊙ SiLU(z)) W_out)``; ``mode_before`` = (mode, the
    planted fault ``norm_before_gate``)."""
    mode, before = mode_before

    def norm(t):
        g = t.reshape(t.shape[0], groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return g.reshape(t.shape) * w["norm"].astype(F32)
    r = norm(y) * jax.nn.silu(z) if before else norm(y * jax.nn.silu(z))
    return m_so * ein("ni,id->nd", r, w["out_proj"], mode)


def mixer(x, w, D: Dict, S0, n_real: int, mode, fault: Optional[str],
          blocks: Dict):
    """(M (L, d), S after token ``n_real − 1``) for normed input ``x``
    (L, d) of which the first ``n_real`` rows are the session."""
    L, rows = x.shape[0], blocks["pad_to"]
    p = in_blocks(lambda t: _in_proj(t, w["in_proj"],
                                     (D["m_si"], proj_blocks(D)), mode),
                  x, block=rows)
    inner, bc = D["inner"], D["G"] * D["N"]
    z, u, dt = p[:, :inner], p[:, inner:inner + D["conv_width"]], \
        p[:, inner + D["conv_width"]:]
    c = conv(u, w["conv_w"], w["conv_b"], fault == "conv_state_late")
    xs = c[:, :inner].reshape(L, D["H"], D["P"])
    Bm, Cm = (c[:, lo:lo + bc].reshape(L, D["G"], D["N"])
              for lo in (inner, inner + bc))
    if fault == "one_group":
        Bm, Cm = (jnp.repeat(t[:, :1], D["G"], 1) for t in (Bm, Cm))
    delta = jax.nn.softplus(
        dt + (0.0 if fault == "no_dt_bias" else w["dt_bias"]))
    a = jnp.exp(-delta * jnp.exp(w["A_log"]))
    # the planted fault: the last token's decay goes on over the positions
    # that pad the session to whole chunks
    idle = a[n_real - 1] ** ((-n_real) % D["chunk"])
    # behind the session's last token nothing moves the state
    real = (jnp.arange(L) < n_real)[:, None]
    delta, a = jnp.where(real, delta, 0.0), jnp.where(real, a, 1.0)
    ys, S = [], S0
    for lo in range(0, L, rows):
        y, S = _recur(S, xs[lo:lo + rows], delta[lo:lo + rows],
                      a[lo:lo + rows], Bm[lo:lo + rows], Cm[lo:lo + rows],
                      fault == "state_bf16", D["G"])
        ys.append(y)
    y = jnp.concatenate(ys, 0)
    if fault == "pad_advances":
        S = S * idle[:, None, None]
    if fault != "no_D":
        y = y + w["D"][None, :, None] * xs
    M = in_blocks(lambda y_, z_: _mixer_out(
        y_, z_, {n: w[n] for n in ("norm", "out_proj")}, D["G"], D["eps"],
        D["m_so"], (mode, fault == "norm_before_gate")),
        y.reshape(L, inner), z, block=rows)
    return M, S


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

@jit(static_argnums=(2, 3, 4))
def _mlp(x, w, m_g, m_d, mode):
    g = m_g * ein("nd,df->nf", x, w["w_gate"], mode)
    u = ein("nd,df->nf", x, w["w_up"], mode)
    return m_d * ein("nf,fd->nd", jax.nn.silu(g) * u, w["w_down"], mode)


@jit(static_argnums=(3, 4, 5))
def _logits(h, norm_w, head, eps, m_h, mode):
    return m_h * ein("nd,dv->nv", rms_norm(h, norm_w, eps), head, mode)


#: a call scores ``q_block`` queries x ``head_group`` heads against up to
#: every key of the session (230 MB of float32 scores at 5,632 keys);
#: sessions are padded to whole blocks of ``pad_to`` tokens, so that those
#: of a run share compiled shapes, and the recurrence runs ``pad_to`` tokens
#: a call with the state carried
BLOCKS = {"q_block": 512, "head_group": 20, "key_round": 2048,
          "pad_to": 2048, "mlp_block": 2048}


def forward(cfg: Dict, seed: int, tokens, mode: str = "f32",
            fault: Optional[str] = None, blocks: Optional[Dict] = None,
            weights: Optional[Dict] = None, keep: Sequence[int] = ()) -> Dict:
    """The full forward of one session's ``tokens`` (L,).  Returns
    ``logits`` (L, vocab) float32 — or, with ``keep``, only those
    positions' rows — and ``state``: every layer's ``S`` (H, P, N) after
    the last token.  ``weights`` ({"layers": [...], "ends": {...}}): use
    these in place of the seed's."""
    return forward_many(cfg, seed, [dict(tokens=tokens, keep=keep)], mode,
                        fault, blocks, weights)[0]


def forward_many(cfg: Dict, seed: int, sessions: Sequence[Dict],
                 mode: str = "f32", fault: Optional[str] = None,
                 blocks: Optional[Dict] = None,
                 weights: Optional[Dict] = None) -> Sequence[Dict]:
    """:func:`forward` for several sessions (each a dict of its ``tokens``
    and, if any, ``keep``), a layer at a time over all of them: a layer's
    weights are made from the seed once."""
    D = dims(cfg)
    blocks = dict(BLOCKS, **(blocks or {}))
    rows = blocks["pad_to"]
    if mode not in ROUNDED:
        raise KeyError(f"unknown mode {mode!r}")
    if fault and fault.partition(":")[0] not in FAULTS:
        raise KeyError(f"unknown fault {fault!r}")
    if fault == "no_mup":
        D = dict(D, m_ssm=(1.0,) * 5,
                 **{m: 1.0 for m in D if m.startswith("m_") and m != "m_ssm"})
    ends = weights["ends"] if weights else end_weights(seed, cfg)
    state = []
    for one in sessions:
        tokens = jnp.asarray(one["tokens"], jnp.int32)
        n_real = tokens.shape[0]
        # padded at the end to a multiple of ``pad_to`` (a causal model's
        # earlier positions do not see the padding; the recurrence stands
        # still behind the last token)
        tokens = jnp.pad(tokens, (0, (-n_real) % rows))
        state.append(dict(
            n_real=n_real, pos=jnp.arange(tokens.shape[0]),
            h=D["m_e"] * ends["embed"][tokens].astype(F32),
            keep=list(one.get("keep") or ()), state=[]))
    zeros = jnp.zeros((D["H"], D["P"], D["N"]), F32)
    with jax.default_matmul_precision("highest"):
        for i in range(D["layers"]):
            w = weights["layers"][i] if weights \
                else layer_weights(seed, cfg, i)
            for j, st in enumerate(state):
                S0 = zeros
                if fault == "state_not_reset" and state[j - 1]["state"]:
                    S0 = state[j - 1]["state"][-1]
                _layer(st, w, D, S0, mode, fault, blocks)
            del w
        for st in state:
            h = st.pop("h")
            h = h[jnp.asarray(st["keep"])] if st["keep"] \
                else h[:st["n_real"]]
            st["logits"] = _logits(h, ends["final_norm"], ends["head"],
                                   D["eps"], D["m_h"], mode)
    return [{k: st[k] for k in ("logits", "state")} for st in state]


def _layer(st: Dict, w: Dict, D: Dict, S0, mode: str, fault: Optional[str],
           blocks: Dict) -> None:
    rows, h = blocks["pad_to"], st["h"]
    x = in_blocks(lambda t: rms_norm(t, w["attn_norm"], D["eps"]), h,
                  block=rows)
    M, S = mixer(x, w["ssm"], D, S0, st["n_real"], mode, fault, blocks)
    h = h + M + attention(x, w["attn"], D, st["pos"], mode, fault, blocks)
    x = in_blocks(lambda t: rms_norm(t, w["mlp_norm"], D["eps"]), h,
                  block=rows)
    st["h"] = h + in_blocks(lambda t: _mlp(
        t, w["mlp"], D["m_g"], D["m_d"], mode), x, block=blocks["mlp_block"])
    st["state"].append(S)
