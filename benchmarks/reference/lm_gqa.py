"""Plain float32 reference of one chip's share of a ``mimo_v2`` decoder LM
(configs/mimo-v25-ep16.json): weights from a seed and the full forward of
one session's tokens — no cache, no kernels, no batching.

Straightforward ``jax.numpy``; it imports nothing of ``analytics_zoo_tpu``
and takes nothing the program made.  From ``reference/lm.py`` it takes
what is not the model: the seed law (``_key``, ``_normal``, the norms'
weights, an MLP's), ``ein`` (a product in a stated arithmetic), ``jit`` /
``compile_only`` (compiling ahead), ``in_blocks``, the norm, the gated MLP
and the expert layer (``moe``: a sigmoid router of the published width
with a bias in the choice only, no groups — this model's too; called
without its shared expert).  The weight trees' NAMES are the program's
interface; a key's dims stand in the checkpoint's order here (the rotated
ones first), whatever order the program caches them in.

The layer equations are ISSUE 35's section 1 (RMS norm eps 1e-5, pre-norm
residual blocks, no bias anywhere), layer ``i`` of kind
``hybrid_layer_pattern[i]`` (0 global, 1 window):

- ``q = x W_q`` (H heads x dk), ``k = x W_k`` (KVH x dk), ``v =
  attention_value_scale · x W_v`` (KVH x dv), KVH the kind's; no norm on
  ``q`` or ``k``;
- rotary on the FIRST ``r = int(partial_rotary_factor · dk)`` dims of
  every ``q`` and ``k`` head in pairs ``(j, j + r/2)``, base ``rope_theta``
  (global) or ``swa_rope_theta`` (window), unscaled;
- head ``a`` reads KV head ``a // (H / KVH)``; scores ``q · k · dk^-1/2``
  over ``j <= t`` (global) or ``t − window < j <= t`` (window: the token
  itself and the ``window − 1`` before it); softmax in float32; a window
  layer's learned ``sink_a`` joins it as one more column and is then
  dropped; ``W_o``;
- layer 0 a gated MLP; layers >= 1 experts: ``sigmoid(x W_r)`` over the
  PUBLISHED width, the ``num_experts_per_tok`` largest of score + bias
  (ties to the lower id), weights the chosen scores over their sum, no
  scaling factor; only the HELD experts' part is computed, and there is no
  shared expert;
- ends: embedding and head over the vocabulary slice, untied.

``mode``: the arithmetic of every matrix product (``f32`` at HIGHEST — the
reference; ``bf16`` — what the configuration states; ``int8`` — the
control the comparison has to fail).  ``fault`` plants one fault:
``truncate[:n]`` (a GLOBAL layer attends to its last n = 2,048 positions
only), ``shift_cache`` (every key and value one position late),
``no_sink``, ``window_129`` (a window layer sees one position too many),
``full_rotary`` (all dk dims turned), ``swap_theta`` (window layers at the
global base), ``no_value_scale``, ``drop_expert[:j]`` (held expert j left
out).

``follow`` = {"routed": {layer: (L, k) expert ids}}: as
``reference/lm_mla.py``'s.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.lm import (ROUNDED, _key, _norm_weight,  # noqa: F401
                                     _normal, compile_only, ein, gated_mlp,
                                     in_blocks, jit, mlp_weights, moe,
                                     rms_norm)

F32 = jnp.float32
NEG = -1e30
GLOBAL, WINDOW = "global", "swa"


def dims(cfg: Dict) -> Dict:
    """The sizes the equations use, by layer kind, from the configuration's
    published keys."""
    n = int(cfg["num_hidden_layers"])
    share = cfg["expert_share"]
    held = int(cfg["n_routed_experts"])
    freq = [int(f) for f in cfg["moe_layer_freq"]][:n]

    def kind(p, theta, sink):
        dk = int(cfg[p + "head_dim"])
        return dict(heads=int(cfg[p + "num_attention_heads"]),
                    kv_heads=int(cfg[p + "num_key_value_heads"]), k=dk,
                    v=int(cfg[p + "v_head_dim"]),
                    rotary=int(float(cfg["partial_rotary_factor"]) * dk),
                    theta=float(cfg[theta]), sink=bool(cfg[sink]))
    return dict(
        d=int(cfg["hidden_size"]), layers=n,
        kinds=[WINDOW if g else GLOBAL
               for g in cfg["hybrid_layer_pattern"][:n]],
        **{GLOBAL: kind("", "rope_theta", "add_full_attention_sink_bias"),
           WINDOW: kind("swa_", "swa_rope_theta",
                        "add_swa_attention_sink_bias")},
        window=int(cfg["sliding_window"]),
        value_scale=float(cfg["attention_value_scale"]),
        eps=float(cfg["layernorm_epsilon"]),
        dense_layers=freq.index(1) if 1 in freq else n,
        f_dense=int(cfg["intermediate_size"]),
        f_expert=int(cfg["moe_intermediate_size"]),
        experts=int(share["published_experts"]), held=held,
        first_held=int(share["index"]) * held,
        per_tok=int(cfg["num_experts_per_tok"]),
        route_scale=float(cfg["routed_scaling_factor"] or 1.0),
        vocab=int(cfg["vocab_size"]))


# ---------------------------------------------------------------------------
# weights from the seed (reference/lm.py's law)
# ---------------------------------------------------------------------------

def attention_weights(key, D: Dict, kind: str) -> Dict:
    a, d = D[kind], D["d"]
    k = jax.random.split(key, 5)
    w = {"wq": _normal(k[0], (d, a["heads"], a["k"]), d),
         "wk": _normal(k[1], (d, a["kv_heads"], a["k"]), d),
         "wv": _normal(k[2], (d, a["kv_heads"], a["v"]), d),
         "wo": _normal(k[3], (a["heads"], a["v"], d), a["heads"] * a["v"])}
    if a["sink"]:
        # of the scores' own scale, so that leaving the sink out is seen
        w["sink"] = _normal(k[4], (a["heads"],), std=1.0).astype(F32)
    return w


def layer_weights(seed: int, cfg: Dict, layer: int) -> Dict:
    """One layer's weights (bfloat16 arrays on the default device; the
    router's bias and the sinks float32)."""
    D = dims(cfg)
    k = jax.random.split(_key(seed, 1, layer), 8)
    w = {"attn_norm": _norm_weight(k[0], D["d"]),
         "mlp_norm": _norm_weight(k[1], D["d"]),
         "attn": attention_weights(k[2], D, D["kinds"][layer])}
    if layer < D["dense_layers"]:
        w["mlp"] = mlp_weights(k[3], D["d"], D["f_dense"])
    else:
        w["moe"] = {
            "router_w": _normal(k[4], (D["d"], D["experts"]), D["d"]),
            "router_b": _normal(k[5], (D["experts"],),
                                std=0.05).astype(F32),
            "experts": mlp_weights(k[6], D["d"], D["f_expert"],
                                   (D["held"],))}
    return w


def end_weights(seed: int, cfg: Dict) -> Dict:
    D = dims(cfg)
    k = jax.random.split(_key(seed, 2), 3)
    return {"embed": _normal(k[0], (D["vocab"], D["d"]), std=1.0),
            "final_norm": _norm_weight(k[1], D["d"]),
            "head": _normal(k[2], (D["d"], D["vocab"]), D["d"])}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def rope_first(x, pos, r: int, theta: float):
    """Rotary embedding of the first ``r`` dims of the last axis in pairs
    ``(x[j], x[j + r/2])``, the others untouched; ``x`` (L, heads, dk),
    ``pos`` (L,)."""
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin,
                            x[..., r:]], -1)


@jit(static_argnums=(3, 4, 5, 6))
def _project(x, w, pos, rotary, theta, value_scale, mode):
    """(q (L, H, dk), k (L, KVH, dk), v (L, KVH, dv)), q and k rotated."""
    q = ein("nd,dhe->nhe", x, w["wq"], mode)
    k = ein("nd,dge->nge", x, w["wk"], mode)
    v = value_scale * ein("nd,dge->nge", x, w["wv"], mode)
    return rope_first(q, pos, rotary, theta), \
        rope_first(k, pos, rotary, theta), v


@jit(static_argnames=("mode", "scale", "last", "qb", "span", "kg"))
def _attend_block(q, pos, k, v, sink, wo, lo, k0, g0, *, mode, scale, last,
                  qb, span, kg):
    """``qb`` queries from ``lo`` on x ``kg`` KV heads from ``g0`` on
    against the ``span`` keys from ``k0`` on (all cut out in here: the
    starts are arguments, so one program serves every block of a shape):
    softmax attention over ``key <= query`` and, with ``last``, ``key >
    query − last``; ``sink`` (G, hp) or None the extra column; those
    heads' rows of the output projection ``wo`` (G, hp, dv, d) → (qb, d).
    ``q`` (L, G, hp, dk), ``k`` (L, G, dk), ``v`` (L, G, dv), ``pos``
    (L,).  A start too near the end is moved back by ``dynamic_slice``,
    for keys and their positions alike."""
    cut = lambda t, at, n, axis: lax.dynamic_slice_in_dim(  # noqa: E731
        t, at, n, axis)
    q, p = cut(cut(q, lo, qb, 0), g0, kg, 1), cut(pos, lo, qb, 0)
    k, v, key_pos = (cut(t, k0, span, 0) for t in (k, v, pos))
    k, v, wo = (cut(t, g0, kg, a) for t, a in ((k, 1), (v, 1), (wo, 0)))
    s = ein("ngae,sge->gans", q, k, mode) * scale
    ok = key_pos[None, :] <= p[:, None]
    if last:
        ok &= key_pos[None, :] > p[:, None] - last
    s = jnp.where(ok[None, None], s, NEG)
    if sink is not None:
        col = cut(sink, g0, kg, 0)[:, :, None, None]
        s = jnp.concatenate(
            [s, jnp.broadcast_to(col, s.shape[:-1] + (1,))], -1)
    prob = jax.nn.softmax(s, -1)[..., :span]
    o = ein("gans,sge->ngae", prob, v, mode)
    return ein("ngae,gaed->nd", o, wo, mode)


def attention(x, w, D: Dict, kind: str, pos, mode, fault: Optional[str],
              blocks: Dict, first: int = 0):
    """One attention block's output (L, d) for normed input ``x``.  Every
    block of queries works on the keys it can see: up to its own end
    rounded up to ``key_round`` and, in a window layer, from
    ``window_round`` before its start on.  ``first``: the first query
    whose output is wanted (the blocks before its block are left at
    zero)."""
    a, L, rows = D[kind], x.shape[0], blocks["pad_to"]
    last = D["window"] if kind == WINDOW else 0
    if fault and fault.startswith("truncate"):
        if kind == GLOBAL:
            last = int(fault.partition(":")[2] or 2048)
    elif fault == "window_129" and kind == WINDOW:
        last += 1
    theta = D[GLOBAL]["theta"] if fault == "swap_theta" else a["theta"]
    q, k, v = in_blocks(
        lambda x_, p_: _project(
            x_, {n: w[n] for n in ("wq", "wk", "wv")}, p_,
            a["k"] if fault == "full_rotary" else a["rotary"], theta,
            1.0 if fault == "no_value_scale" else D["value_scale"], mode),
        x, pos, block=rows)
    if fault == "shift_cache":
        k, v = (jnp.roll(t, 1, 0) for t in (k, v))
    G, hp = a["kv_heads"], a["heads"] // a["kv_heads"]
    q = q.reshape(L, G, hp, a["k"])
    wo = w["wo"].reshape(G, hp, a["v"], D["d"])
    sink = None if "sink" not in w or fault == "no_sink" \
        else w["sink"].reshape(G, hp)
    qb = min(blocks["q_block"], L)
    if L % qb:
        raise ValueError(f"pad_to {rows} is not whole blocks of {qb} queries")
    kg = max(1, min(G, blocks["head_group"] // hp))    # KV heads a call
    q0 = first // qb * qb
    static = dict(mode=mode, scale=a["k"] ** -0.5, last=last, qb=qb, kg=kg)
    parts = []
    for lo in range(q0, L, qb):
        if kind == WINDOW:
            k0, span = lo - blocks["window_round"], \
                min(L, qb + blocks["window_round"])
        else:
            k0, span = 0, min(L, -(-(lo + qb) // blocks["key_round"])
                              * blocks["key_round"])
        # the starts as arrays: ONE program a shape, for the calls and for
        # ``compile_only``'s count of them alike
        parts.append(sum(
            _attend_block(q, pos, k, v, sink, wo, np.int32(lo),
                          np.int32(max(k0, 0)), np.int32(g), span=span,
                          **static) for g in range(0, G, kg)))
    return jnp.concatenate([jnp.zeros((q0, D["d"]), F32)] + parts, 0)


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

@jit(static_argnums=(3, 4))
def _logits(h, norm_w, head, eps, mode):
    return ein("nd,dv->nv", rms_norm(h, norm_w, eps), head, mode)


#: a call scores ``q_block`` queries x ``head_group`` heads against up to
#: every key of the session (1.3 GB of float32 scores at 40,960 keys); a
#: window layer's against the ``window_round`` before the block and the
#: block's own (``window_round`` >= the window + 1, a multiple of 8)
BLOCKS = {"q_block": 512, "head_group": 16, "key_round": 8192,
          "window_round": 136, "mlp_block": 8192, "pad_to": 8192,
          "expert_group": 512}


def forward(cfg: Dict, seed: int, tokens, mode: str = "f32",
            fault: Optional[str] = None, blocks: Optional[Dict] = None,
            weights: Optional[Dict] = None, keep: Sequence[int] = (),
            follow: Optional[Dict] = None) -> Dict:
    """The full forward of one session's ``tokens`` (L,).  Returns
    ``logits`` (L, vocab) float32 — or, with ``keep``, only those
    positions' rows — ``chosen`` {layer: (L, k) expert ids used} and
    ``miss`` = {"route": (differing, counted) (token, expert) pairs} on
    which this side's own routing differs from ``follow``'s.  ``weights``
    ({"layers": [...], "ends": {...}}): use these in place of the seed's.
    With ``keep`` the LAST layer computes only from the block of the first
    kept row on."""
    return forward_many(cfg, seed, [dict(tokens=tokens, keep=keep,
                                         follow=follow)],
                        mode, fault, blocks, weights)[0]


def forward_many(cfg: Dict, seed: int, sessions: Sequence[Dict],
                 mode: str = "f32", fault: Optional[str] = None,
                 blocks: Optional[Dict] = None,
                 weights: Optional[Dict] = None) -> Sequence[Dict]:
    """:func:`forward` for several sessions (each a dict of its ``tokens``
    and, if any, ``keep``, ``follow``), a layer at a time over all of
    them: a layer's weights are made from the seed once."""
    D = dims(cfg)
    blocks = dict(BLOCKS, **(blocks or {}))
    rows = blocks["pad_to"]
    if mode not in ROUNDED:
        raise KeyError(f"unknown mode {mode!r}")
    drop = None
    if fault and fault.startswith("drop_expert"):
        drop, fault = int(fault.partition(":")[2] or 0), "drop_expert"
    ends = weights["ends"] if weights else end_weights(seed, cfg)
    state = []
    for one in sessions:
        tokens = jnp.asarray(one["tokens"], jnp.int32)
        n_real = tokens.shape[0]
        # padded at the end to a multiple of ``pad_to`` (a causal model's
        # earlier positions do not see the padding), so that sessions of
        # different lengths share compiled shapes
        tokens = jnp.pad(tokens, (0, (-n_real) % rows))
        keep = list(one.get("keep") or ())
        start = [0] * D["layers"]
        if keep:
            start[-1] = min(keep) // rows * rows
        state.append(dict(
            n_real=n_real, L=tokens.shape[0], pos=jnp.arange(tokens.shape[0]),
            h=ends["embed"][tokens].astype(F32), keep=keep, start=start,
            follow=one.get("follow") or {}, chosen={},
            miss={"route": [0, 0]}))
    with jax.default_matmul_precision("highest"):
        for i in range(D["layers"]):
            w = weights["layers"][i] if weights \
                else layer_weights(seed, cfg, i)
            for st in state:
                _layer(st, i, w, D, mode, fault, drop, blocks)
            del w
        for st in state:
            h = st.pop("h")
            h = h[jnp.asarray(st["keep"])] if st["keep"] \
                else h[:st["n_real"]]
            st["logits"] = _logits(h, ends["final_norm"], ends["head"],
                                   D["eps"], mode)
    return [{k: st[k] for k in ("logits", "miss", "chosen")} for st in state]


def _layer(st: Dict, i: int, w: Dict, D: Dict, mode: str,
           fault: Optional[str], drop: Optional[int], blocks: Dict) -> None:
    """Layer ``i`` of one session, from row ``st["start"][i]`` on."""
    rows = blocks["pad_to"]
    h, n_real, first = st["h"], st["n_real"], st["start"][i]
    x = in_blocks(lambda t: rms_norm(t, w["attn_norm"], D["eps"]), h,
                  block=rows)
    h = h + attention(x, w["attn"], D, D["kinds"][i], st["pos"], mode, fault,
                      blocks, first=first)
    x = in_blocks(lambda t: rms_norm(t, w["mlp_norm"], D["eps"]), h[first:],
                  block=rows)
    if "mlp" in w:
        y = in_blocks(lambda t: gated_mlp(t, w["mlp"], mode), x,
                      block=blocks["mlp_block"])
    else:
        given = st["follow"].get("routed", {}).get(i)
        if given is not None:
            given = np.pad(np.asarray(given, np.int32),
                           ((0, st["L"] - len(given)), (0, 0)))[first:]
        y, used, own = moe(x, w["moe"], D, mode, drop=drop, shared=False,
                           block=rows, given=given,
                           group=blocks["expert_group"])
        u, o_ = (np.asarray(t)[:n_real - first] for t in (used, own))
        st["chosen"][i] = np.concatenate(
            [np.full((first,) + u.shape[1:], -1, u.dtype), u])
        if given is not None:
            st["miss"]["route"][0] += int(
                (u[:, :, None] != o_[:, None, :]).all(-1).sum())
            st["miss"]["route"][1] += u.size
    st["h"] = h.at[first:].add(y)
