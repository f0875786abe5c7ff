"""Plain float32 reference of one chip's share of an ``axk1`` decoder LM
(configs/ax-k1-ep16.json): weights from a seed and the full forward of one
session's tokens — no cache, no kernels, no batching.

Straightforward ``jax.numpy``; it imports nothing of ``analytics_zoo_tpu``
and takes nothing the program made.  From ``reference/lm.py`` it takes
what is not the model: the seed law (``_key``, ``_normal``, the norms'
weights, an MLP's), ``ein`` (a product in a stated arithmetic), ``jit`` /
``compile_only`` (compiling ahead), ``in_blocks``, the norm and the gated
MLP.  The weight trees' NAMES are the program's interface.

The layer equations are ISSUE 33's section 1 (RMS norm eps 1e-6, pre-norm
residual blocks, no bias anywhere):

- every layer: MLA — q latent (normed), kv latent (normed) + one shared
  rotary key — causal over ALL positions ``s <= t``; no rescale of the
  latents, no gate on the heads; scores times ``(nope + rope)^-1/2 ·
  mscale²``, ``mscale = 0.1 · mscale_all_dim · ln(factor) + 1``;
- rotary: interleaved pairs, YaRN frequencies (``yarn_inv_freq``), cos and
  sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
- layer 0 a gated MLP; layers >= 1 experts: sigmoid router of the PUBLISHED
  width without bias, group-limited choice (a group's score the sum of its
  two largest, the ``topk_group`` best groups stay, of their experts the
  ``num_experts_per_tok`` largest, ties to the lower id), weights the
  chosen scores over their sum times ``routed_scaling_factor``; only the
  HELD experts' part is computed, plus the shared expert;
- ends: embedding and head over the vocabulary slice, untied.

Departures from the published description, each assumed (the configuration
lists them): ``topk_method: "none"`` is read as no bias correction; the
group score is the family's ``noaux_tc`` one; ``rope_interleave`` absent
is read as interleaved pairs.

``mode``: the arithmetic of every matrix product (``f32`` at HIGHEST — the
reference; ``bf16`` — what the configuration states; ``int8`` — the
control the comparison has to fail).  ``fault`` plants one fault:
``truncate[:n]`` (a token attends to its last n = 2,048 positions only),
``shift_cache`` (every latent and rotary key one position late),
``no_group_limit`` (plain top-k over the whole router), ``no_yarn``
(unscaled frequencies and scale), ``drop_expert[:j]`` (held expert j left
out).

``follow`` = {"routed": {layer: (L, k) expert ids}} hands the forward
somebody else's routed experts, taken as given (weights from this side's
own scores of them), while this side still makes its own choice and
counts on how many (token, expert) pairs they differ (``miss``): a
rounding flips a routed expert, and with seeded random weights a flip
moves the logits as much as a fault would.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.lm import (ROUNDED, _add_expert, _key,  # noqa: F401
                                     _norm_weight, _normal, compile_only, ein,
                                     gated_mlp, in_blocks, jit, mlp_weights,
                                     rms_norm)

F32 = jnp.float32
NEG = -1e30


def dims(cfg: Dict) -> Dict:
    """The sizes the equations use, from the configuration's published
    keys."""
    share = cfg["expert_share"]
    held = int(cfg["n_routed_experts"])
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]),
        scaling=cfg.get("rope_scaling"), eps=float(cfg["rms_norm_eps"]),
        layers=int(cfg["num_hidden_layers"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        f_dense=int(cfg["intermediate_size"]),
        f_expert=int(cfg["moe_intermediate_size"]),
        f_shared=int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        experts=int(share["published_experts"]), held=held,
        first_held=int(share["index"]) * held,
        per_tok=int(cfg["num_experts_per_tok"]),
        n_group=int(cfg.get("n_group") or 1),
        topk_group=int(cfg.get("topk_group") or 1),
        route_scale=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]))


# ---------------------------------------------------------------------------
# rotary: YaRN
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(D: Dict, fault: Optional[str] = None) -> np.ndarray:
    """The rotary pairs' frequencies: for pair i of r/2, ``f_i = theta^(-2i
    / r)``; the pairs that make ``beta_fast`` rotations or more in the
    original context keep it, those that make ``beta_slow`` or fewer get
    ``f_i / factor``, a linear ramp between."""
    r, theta, sc = D["rope"], D["theta"], D["scaling"]
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if not sc or fault == "no_yarn":
        return f.astype(np.float32)
    L = float(sc["original_max_position_embeddings"])

    def pair_at(turns):
        return r * math.log(L / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(pair_at(float(sc["beta_fast"]))), 0)
    hi = min(math.ceil(pair_at(float(sc["beta_slow"]))), r // 2 - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 0.001), 0, 1)
    return (f / float(sc["factor"]) * ramp + f * (1 - ramp)).astype(
        np.float32)


def softmax_scale(D: Dict, fault: Optional[str] = None) -> float:
    sc = D["scaling"]
    m = 1.0 if not sc or fault == "no_yarn" else yarn_mscale(
        float(sc["factor"]), float(sc.get("mscale_all_dim", 0)))
    return m * m / math.sqrt(D["nope"] + D["rope"])


def rope_amplitude(D: Dict, fault: Optional[str] = None) -> float:
    sc = D["scaling"]
    if not sc or fault == "no_yarn":
        return 1.0
    return yarn_mscale(float(sc["factor"]), float(sc.get("mscale", 1))) \
        / yarn_mscale(float(sc["factor"]), float(sc.get("mscale_all_dim", 0)))


def rope(x, pos, inv_freq, amplitude: float = 1.0):
    """Rotary embedding of the last axis in interleaved pairs
    ``(x[2i], x[2i+1])``; ``x`` (L, ..., r), ``pos`` (L,)."""
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape)


# ---------------------------------------------------------------------------
# weights from the seed (reference/lm.py's law)
# ---------------------------------------------------------------------------

def attention_weights(key, D: Dict) -> Dict:
    d, H = D["d"], D["heads"]
    k = iter(jax.random.split(key, 16))
    return {"wq_a": _normal(next(k), (d, D["q_rank"]), d),
            "q_norm": _norm_weight(next(k), D["q_rank"]),
            "wq_b": _normal(next(k), (D["q_rank"], H, D["nope"] + D["rope"]),
                            D["q_rank"]),
            "wkv_a": _normal(next(k), (d, D["kv_rank"] + D["rope"]), d),
            "kv_norm": _norm_weight(next(k), D["kv_rank"]),
            "wkv_b": _normal(next(k), (D["kv_rank"], H, D["nope"] + D["v"]),
                             D["kv_rank"]),
            "wo": _normal(next(k), (H, D["v"], d), H * D["v"])}


def layer_weights(seed: int, cfg: Dict, layer: int) -> Dict:
    """One layer's weights (bfloat16 arrays on the default device)."""
    D = dims(cfg)
    k = jax.random.split(_key(seed, 1, layer), 8)
    w = {"attn_norm": _norm_weight(k[0], D["d"]),
         "mlp_norm": _norm_weight(k[1], D["d"]),
         "attn": attention_weights(k[2], D)}
    if layer < D["dense_layers"]:
        w["mlp"] = mlp_weights(k[3], D["d"], D["f_dense"])
    else:
        w["moe"] = {
            "router_w": _normal(k[4], (D["d"], D["experts"]), D["d"]),
            "experts": mlp_weights(k[6], D["d"], D["f_expert"],
                                   (D["held"],)),
            "shared": mlp_weights(k[7], D["d"], D["f_shared"])}
    return w


def end_weights(seed: int, cfg: Dict) -> Dict:
    D = dims(cfg)
    k = jax.random.split(_key(seed, 2), 3)
    return {"embed": _normal(k[0], (D["vocab"], D["d"]), std=1.0),
            "final_norm": _norm_weight(k[1], D["d"]),
            "head": _normal(k[2], (D["d"], D["vocab"]), D["d"])}


# ---------------------------------------------------------------------------
# the router and the expert layer
# ---------------------------------------------------------------------------

@jit(static_argnums=(2, 3, 4, 5, 6))
def _route_all(x, router_w, per_tok: int, scale: float, n_group: int,
               topk_group: int, mode):
    s = jax.nn.sigmoid(ein("nd,de->ne", x, router_w, mode))
    eligible = s
    if n_group > 1:
        groups = s.reshape(s.shape[0], n_group, -1)
        best_two, _ = lax.top_k(groups, 2)
        _, kept = lax.top_k(jnp.sum(best_two, -1), topk_group)
        stays = jnp.zeros((s.shape[0], n_group), bool).at[
            jnp.arange(s.shape[0])[:, None], kept].set(True)
        eligible = jnp.where(stays[:, :, None], groups, -jnp.inf).reshape(
            s.shape)
    _, chosen = lax.top_k(eligible, per_tok)
    return chosen, _route_weights(s, chosen, scale), s


def _route_weights(s, chosen, scale):
    picked = jnp.take_along_axis(s, chosen, 1)
    return scale * picked / jnp.sum(picked, 1, keepdims=True)


def moe(x, w, D: Dict, mode="f32", drop: Optional[int] = None,
        first_held: Optional[int] = None, held: Optional[int] = None,
        shared: bool = True, block: int = 0, given=None, group: int = 512,
        grouped: bool = True):
    """The held experts' part of the layer for tokens ``x`` (N, d), plus
    the shared expert.  Expert by expert: the tokens routed to it are
    gathered, ``group`` at a time (the last group padded), run through it
    and added back with their weights.  ``drop``: a held expert left out
    (a planted fault).  ``block``: run the tokens in blocks of that many.
    ``given`` (N, k): route every token to these experts (weights from this
    side's own scores of them).  ``grouped`` False: the fault
    ``no_group_limit``.  → (y, the experts used, the router's own choice)."""
    first_held = D["first_held"] if first_held is None else first_held
    held = D["held"] if held is None else held
    if block and x.shape[0] > block:
        one = lambda t, g=None: moe(t, w, D, mode, drop,    # noqa: E731
                                    first_held, held, shared, given=g,
                                    group=group, grouped=grouped)
        return in_blocks(one, x, *(() if given is None
                                   else (jnp.asarray(given),)), block=block)
    own, weights, scores = _route_all(
        x, w["router_w"], D["per_tok"], D["route_scale"],
        D["n_group"] if grouped else 1, D["topk_group"], mode)
    chosen = own
    if given is not None:
        chosen = jnp.asarray(given, own.dtype)
        weights = _route_weights(scores, chosen, D["route_scale"])
    y = gated_mlp(x, w["shared"], mode) if shared else jnp.zeros_like(x)
    chosen_h, weights_h = np.asarray(chosen), np.asarray(weights)
    for j in range(held):
        if drop is not None and j == drop:
            continue
        rows, slot = np.nonzero(chosen_h == first_held + j)
        wts = weights_h[rows, slot].astype(np.float32)
        e = {k: v[j] for k, v in w["experts"].items()}
        for lo in range(0, len(rows), group):
            idx, wt = (np.concatenate([t[lo:lo + group], np.zeros(
                max(0, lo + group - len(rows)), t.dtype)])
                for t in (rows, wts))
            y = _add_expert(y, x, jnp.asarray(idx), jnp.asarray(wt), e, mode)
    return y, chosen, own


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@jit(static_argnums=(4, 5, 6, 7, 8))
def _latents(x, w, pos, inv_freq, q_rank, kv_rank, eps, amplitude, mode):
    """(c_q (L, q_rank), c_kv (L, kv_rank), k_r (L, rope))."""
    c_q = rms_norm(ein("nd,dr->nr", x, w["wq_a"], mode), w["q_norm"], eps)
    kv = ein("nd,dr->nr", x, w["wkv_a"], mode)
    c_kv = rms_norm(kv[:, :kv_rank], w["kv_norm"], eps)
    return c_q, c_kv, rope(kv[:, kv_rank:], pos, inv_freq, amplitude)


@jit(static_argnames=("mode", "nope"))
def _group_keys(c_kv, wkv_b, *, mode, nope):
    """A group of heads' keys and values for every position."""
    kv = ein("sr,rhe->she", c_kv, wkv_b, mode)
    return kv[..., :nope], kv[..., nope:]


@jit(static_argnames=("mode", "nope", "scale", "amplitude", "k1", "last"))
def _attend_block(cq, p, wq_b, k_nope, v, k_rot, wo, inv_freq, *, mode, nope,
                  scale, amplitude, k1, last):
    """A block of queries x a group of heads against the session's first
    ``k1`` keys (cut out in here): causal softmax attention, the group's
    rows of the output projection.  ``last``: 0, or how many positions
    back a token attends to (the fault ``truncate``)."""
    k_nope, v, k_rot = k_nope[:k1], v[:k1], k_rot[:k1]
    key_pos = jnp.arange(k1)
    q = ein("nr,rhe->nhe", cq, wq_b, mode)
    q_r = rope(q[..., nope:], p, inv_freq, amplitude)
    s = (ein("nhe,she->hns", q[..., :nope], k_nope, mode)
         + ein("nhe,se->hns", q_r, k_rot, mode)) * scale
    ok = key_pos[None, :] <= p[:, None]
    if last:
        ok &= key_pos[None, :] > p[:, None] - last
    prob = jax.nn.softmax(jnp.where(ok[None], s, NEG), -1)
    o = ein("hns,she->nhe", prob, v, mode)
    return ein("nhe,hed->nd", o, wo, mode)


def attention(x, w, D: Dict, pos, mode, fault: Optional[str], blocks: Dict,
              first: int = 0):
    """One attention block's output (L, d) for normed input ``x``.  Every
    block of queries works on the keys up to its own end, rounded up to
    ``key_round``.  ``first``: the first query whose output is wanted (the
    blocks of queries before its block are left at zero)."""
    L, rows = x.shape[0], blocks["pad_to"]
    last = 0
    if fault and fault.startswith("truncate"):
        last, fault = int(fault.partition(":")[2] or 2048), "truncate"
    inv_freq = yarn_inv_freq(D, fault)
    amplitude = rope_amplitude(D, fault)
    w_lat = {k: w[k] for k in ("wq_a", "q_norm", "wkv_a", "kv_norm")}
    c_q, c_kv, k_r = in_blocks(
        lambda x_, p_: _latents(x_, w_lat, p_, inv_freq, D["q_rank"],
                                D["kv_rank"], D["eps"], amplitude, mode),
        x, pos, block=rows)
    if fault == "shift_cache":
        c_kv, k_r = (jnp.roll(t, 1, 0) for t in (c_kv, k_r))
    qb, hg = blocks["q_block"], min(blocks["head_group"], D["heads"])
    q0 = first // qb * qb
    static = dict(mode=mode, nope=D["nope"], scale=softmax_scale(D, fault),
                  amplitude=amplitude, last=last)
    parts = {}
    for h0 in range(0, D["heads"], hg):
        hs = slice(h0, h0 + hg)
        wkv_b, wq_b, wo = w["wkv_b"][:, hs], w["wq_b"][:, hs], w["wo"][hs]
        k_nope, v = in_blocks(
            lambda c: _group_keys(c, wkv_b, mode=mode, nope=D["nope"]),
            c_kv, block=rows)
        for lo in range(q0, L, qb):
            hi = min(L, lo + qb)
            k1 = min(L, -(-hi // blocks["key_round"]) * blocks["key_round"])
            part = _attend_block(c_q[lo:hi], pos[lo:hi], wq_b, k_nope, v, k_r,
                                 wo, inv_freq, k1=k1, **static)
            parts[lo] = part if lo not in parts else parts[lo] + part
    return jnp.concatenate([jnp.zeros((q0, D["d"]), F32)]
                           + [parts[lo] for lo in range(q0, L, qb)], 0)


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

@jit(static_argnums=(3, 4))
def _logits(h, norm_w, head, eps, mode):
    return ein("nd,dv->nv", rms_norm(h, norm_w, eps), head, mode)


#: a call scores ``q_block`` queries x ``head_group`` heads against up to
#: every key of the session (0.8 GB of float32 scores at 24,576 keys)
BLOCKS = {"q_block": 512, "head_group": 16, "key_round": 8192,
          "mlp_block": 8192, "pad_to": 8192, "expert_group": 512}


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
    kept row on (every layer below is needed whole: every layer attends to
    the whole context)."""
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
    h = h + attention(x, w["attn"], D, st["pos"], mode, fault, blocks,
                      first=first)
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
        y, used, own = moe(x, w["moe"], D, mode, drop=drop, block=rows,
                           given=given, group=blocks["expert_group"],
                           grouped=fault != "no_group_limit")
        u, o_ = (np.asarray(t)[:n_real - first] for t in (used, own))
        st["chosen"][i] = np.concatenate(
            [np.full((first,) + u.shape[1:], -1, u.dtype), u])
        if given is not None:
            st["miss"]["route"][0] += int(
                (u[:, :, None] != o_[:, None, :]).all(-1).sum())
            st["miss"]["route"][1] += u.size
    st["h"] = h.at[first:].add(y)
