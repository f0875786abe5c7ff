"""Plain float32 reference of one chip's share of a ``dots3_note``
decoder LM (configs/dots3-note-prev-ep8.json): weights from a seed and the
full forward of one session's tokens — no cache, no kernels, no batching.

Straightforward ``jax.numpy``; it imports nothing of ``analytics_zoo_tpu``
and takes nothing the program made.  The weight trees' NAMES are the
program's interface (``layers[i]["attn"]["wq_a"]`` ...): the benchmark
makes the weights here and hands the same arrays to the program.

The layer equations are ISSUE 28's section 1 (RMS norm eps from the
configuration, pre-norm residual blocks, no biases but the router's and
the indexer's LayerNorm):

- full layers: MLA (q latent, kv latent + one shared rotary key), scores
  restricted to the ``index_topk`` positions the indexer selects, headwise
  sigmoid gate on the heads' outputs;
- sliding layers: the same MLA at the ``swa_`` sizes over the last
  ``sliding_window_size`` positions (the token itself counted), no indexer;
- MoE: sigmoid router of the PUBLISHED width, bias-corrected top-k,
  normalised weights; only the HELD experts' part is computed, plus the
  shared expert (what the absent experts would add is left out);
- ends: embedding and head over the vocabulary slice, untied.

Departures from the published description, each assumed (the
configuration lists them): the ``apply_mla_qkv_lora_rescale`` factors
``sqrt(hidden/rank)`` after the latent norms; the window counts the token
itself; the indexer's rotary pairs are interleaved like the layer's and
its LayerNorm uses ``rms_norm_eps``; the orthogonal rotation the published
inference code applies before quantising the index keys leaves every dot
product unchanged and is left out.

``mode`` selects the arithmetic of every matrix product:

- ``"f32"``  float32 at ``Precision.HIGHEST`` — the reference;
- ``"bf16"`` operands rounded to bfloat16, float32 accumulation — what the
  configuration states;
- ``"int8"`` operands rounded to 8-bit integers (per tensor, symmetric) —
  the precision below, used only by the CONTROL the comparison has to fail.

``fault`` plants one fault (the comparison has to fail each): ``no_select``
(the indexer's selection left out: dense causal attention), ``drop_expert``
(the first held expert left out), ``shift_cache`` (every key and latent of
the full layers written one position late).

``follow`` hands the forward somebody else's DISCRETE choices — which
positions each query of a full layer selected (bit-packed rows) and which
experts each token was routed to — and the forward takes them as given in
place of its own ``top_k``s, while still making its own and counting on how
many they differ (``miss``).  Past ``index_topk`` tokens a rounding flips
members of the selected set and of the routed experts, and with seeded
random weights every flip moves the logits as much as a fault would: with
the choices given, what is left between two sound computations of the same
model is rounding alone.  ``emit`` returns the forward's own choices in the
same form.

Memory: the weights are bfloat16 VALUES (made in float32, rounded once;
those are the model), upcast a layer at a time; attention runs in blocks
of queries and groups of heads, and the selected sets are kept bit-packed,
so that a session of 64 k tokens fits one chip.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
NEG = -1e30


# ---------------------------------------------------------------------------
# jit, and a way to compile ahead
# ---------------------------------------------------------------------------

_thread = threading.local()


def jit(**options):
    """``jax.jit`` for this module's functions.  On a thread inside
    :func:`compile_only` a call compiles its program and runs nothing."""
    def wrap(fn):
        jitted = jax.jit(fn, **options)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            ahead = getattr(_thread, "ahead", None)
            if ahead is None:
                return jitted(*args, **kwargs)
            return ahead(jitted, args, kwargs)
        return call
    return wrap


@contextlib.contextmanager
def compile_only(device, submit=None):
    """On this thread, until the block ends: every call of a jitted function
    of this module compiles its program for ``device`` — into JAX's
    persistent compilation cache, where the same call finds it later — and
    returns zeros of its result's shapes without running it.  The code
    between the calls runs as it is: a forward over zeros then costs the
    device a few copies and leaves every program of the same forward over
    real tokens compiled, the small ones of the code in between too (those
    in this process alone: JAX keeps none under a second on disk).  The
    float32 programs at HIGHEST precision take 5 to 20 s each to compile
    for a TPU, a forward's score or so of them minutes (PR 28).  ``submit``
    (an executor's): the compiling itself is handed to it and goes on side
    by side, and the block yields {program: (zeros of its result's shapes,
    the job)}."""
    done = {}

    def ahead(jitted, args, kwargs):
        leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return jitted(*args, **kwargs)      # inside another's trace
        shaped = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                  if hasattr(x, "shape") and hasattr(x, "dtype") else x
                  for x in leaves]
        key = (jitted, tree, tuple(
            (x.shape, str(x.dtype)) if isinstance(x, jax.ShapeDtypeStruct)
            else x for x in shaped))
        if key not in done:
            a, k = jax.tree_util.tree_unflatten(tree, shaped)
            with jax.default_device(device):
                lowered = jitted.lower(*a, **k)
            done[key] = (jax.tree_util.tree_map(
                lambda o: jnp.zeros(o.shape, o.dtype), lowered.out_info),
                submit(lowered.compile) if submit else lowered.compile())
        return done[key][0]

    _thread.ahead = ahead
    try:
        yield done
    finally:
        _thread.ahead = None


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def dims(cfg: Dict) -> Dict:
    """The sizes the equations use, by layer kind, from the configuration's
    published keys."""
    d = int(cfg["hidden_size"])
    full = dict(heads=int(cfg["num_attention_heads"]),
                q_rank=int(cfg["q_lora_rank"]),
                kv_rank=int(cfg["kv_lora_rank"]),
                nope=int(cfg["qk_nope_head_dim"]),
                rope=int(cfg["qk_rope_head_dim"]),
                v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]))
    swa = dict(heads=int(cfg["swa_num_attention_heads"]),
               q_rank=int(cfg["swa_q_lora_rank"]),
               kv_rank=int(cfg["swa_kv_lora_rank"]),
               nope=int(cfg["swa_qk_nope_head_dim"]),
               rope=int(cfg["swa_qk_rope_head_dim"]),
               v=int(cfg["swa_v_head_dim"]),
               theta=float(cfg["swa_rope_theta"]))
    share = cfg["expert_share"]
    held = int(cfg["n_routed_experts"])
    return dict(
        d=d, full=full, swa=swa, eps=float(cfg["rms_norm_eps"]),
        layers=int(cfg["num_hidden_layers"]),
        kinds=list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])],
        dense_layers=int(cfg["first_k_dense_replace"]),
        window=int(cfg["sliding_window_size"]),
        idx_heads=int(cfg["index_n_heads"]),
        idx_dim=int(cfg["index_head_dim"]), topk=int(cfg["index_topk"]),
        f_dense=int(cfg["intermediate_size"]),
        f_expert=int(cfg["moe_intermediate_size"]),
        f_shared=int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        experts=int(share["published_experts"]), held=held,
        first_held=int(share["index"]) * held,
        per_tok=int(cfg["num_experts_per_tok"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _key(seed: int, *path: int):
    """The ``rbg`` generator: 4 billion normals from the default one took
    two minutes of a run's set-up on a v5e (PR 28).  Its bits are the same
    for the same seed on the same backend, which is all a run needs: both
    sides are handed the arrays made here."""
    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    for p in (int(seed) // (2 ** 31),) + path:
        key = jax.random.fold_in(key, p)
    return key


#: every weight is cut out of blocks of normals of one of two sizes (the
#: small one for a norm's weight, a bias, a toy): TWO generating programs
#: whatever the shapes (a generator compiled for a shape of its own took
#: 4.7 s on a v5e, and the model has some twenty-five shapes)
NORMAL_BLOCKS = (1 << 16, 1 << 24)


@functools.partial(jax.jit, static_argnums=(3,))
def _normal_block(key, mean, std, size):
    return (mean + std * jax.random.normal(key, (size,), F32)
            ).astype(jnp.bfloat16)


def _normal(key, shape, fan_in: Optional[int] = None, std: float = 1.0,
            mean: float = 0.0):
    """Normal of variance 1/fan_in (or ``std``², about ``mean``), made in
    float32 and rounded once to bfloat16: the rounded values ARE the
    weights."""
    if fan_in is not None:
        std = 1.0 / math.sqrt(fan_in)
    n = math.prod(shape)
    size = NORMAL_BLOCKS[n > NORMAL_BLOCKS[0]]
    blocks = [_normal_block(jax.random.fold_in(key, i), mean, std, size)
              for i in range(-(-n // size))]
    return _cut(blocks, n, tuple(shape))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _cut(blocks, n, shape):
    return jnp.concatenate(blocks)[:n].reshape(shape)


def _norm_weight(key, n):
    return _normal(key, (n,), std=0.1, mean=1.0)


def attention_weights(key, D: Dict, kind: str) -> Dict:
    a = D["full"] if kind == "full_attention" else D["swa"]
    d, H = D["d"], a["heads"]
    k = iter(jax.random.split(key, 16))
    w = {"wq_a": _normal(next(k), (d, a["q_rank"]), d),
         "q_norm": _norm_weight(next(k), a["q_rank"]),
         "wq_b": _normal(next(k), (a["q_rank"], H, a["nope"] + a["rope"]),
                         a["q_rank"]),
         "wkv_a": _normal(next(k), (d, a["kv_rank"] + a["rope"]), d),
         "kv_norm": _norm_weight(next(k), a["kv_rank"]),
         "wkv_b": _normal(next(k), (a["kv_rank"], H, a["nope"] + a["v"]),
                          a["kv_rank"]),
         "wo": _normal(next(k), (H, a["v"], d), H * a["v"]),
         "w_gate": _normal(next(k), (d, H), d)}
    if kind == "full_attention":
        w.update({
            "idx_wq_b": _normal(next(k), (a["q_rank"], D["idx_heads"],
                                          D["idx_dim"]), a["q_rank"]),
            "idx_wk": _normal(next(k), (d, D["idx_dim"]), d),
            "idx_k_norm_w": _norm_weight(next(k), D["idx_dim"]),
            "idx_k_norm_b": _normal(next(k), (D["idx_dim"],), std=0.05),
            "idx_w": _normal(next(k), (d, D["idx_heads"]), d)})
    return w


def mlp_weights(key, d: int, f: int, lead: Sequence[int] = ()) -> Dict:
    k = jax.random.split(key, 3)
    lead = tuple(lead)
    return {"w_gate": _normal(k[0], lead + (d, f), d),
            "w_up": _normal(k[1], lead + (d, f), d),
            "w_down": _normal(k[2], lead + (f, d), f)}


def layer_weights(seed: int, cfg: Dict, layer: int) -> Dict:
    """One layer's weights (bfloat16 arrays on the default device)."""
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
# arithmetic
# ---------------------------------------------------------------------------

def _round8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale).clip(-127, 127) * scale


ROUNDED = {"f32": lambda t: t,
           "bf16": lambda t: t.astype(jnp.bfloat16).astype(F32),
           "int8": _round8}


def ein(spec: str, a, b, mode="f32"):
    """One matrix product in the arithmetic ``mode`` names (static in
    every jitted function: the float32 reference runs no rounding pass and
    makes no copy of an operand; a control compiles its own programs)."""
    a, b = (ROUNDED[mode](t.astype(F32)) for t in (a, b))
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


@jit(static_argnums=(2,))
def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


@jit(static_argnums=(3,))
def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def rope(x, pos, theta: float):
    """Rotary embedding of the last axis in interleaved pairs
    ``(x[2i], x[2i+1])``; ``pos`` broadcasts against ``x``'s leading axes
    (``x`` (L, ..., r), ``pos`` (L,))."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape)


@jit(static_argnums=(2,))
def gated_mlp(x, w, mode):
    g = ein("nd,df->nf", x, w["w_gate"], mode)
    u = ein("nd,df->nf", x, w["w_up"], mode)
    return ein("nf,fd->nd", jax.nn.silu(g) * u, w["w_down"], mode)


def in_blocks(fn, *xs, block: int):
    """``fn`` over blocks of rows of the row-aligned arrays ``xs`` (the
    same block shape every time, the last block padded), its output — an
    array or a tuple of arrays — concatenated.  Every per-position
    function runs through here, so that its compiled shape does not depend
    on the session's length."""
    n = xs[0].shape[0]
    pad = (-n) % block
    if pad:
        xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in xs]
    outs = [fn(*(x[i:i + block] for x in xs)) for i in range(0, n + pad,
                                                             block)]
    if isinstance(outs[0], tuple):
        return tuple(jnp.concatenate(part, 0)[:n] for part in zip(*outs))
    return jnp.concatenate(outs, 0)[:n]


# ---------------------------------------------------------------------------
# the router and the expert layer
# ---------------------------------------------------------------------------

@jit(static_argnums=(3, 4, 5))
def _route_all(x, router_w, router_b, per_tok: int, scale: float, mode):
    s = jax.nn.sigmoid(ein("nd,de->ne", x, router_w, mode))
    _, chosen = lax.top_k(s + router_b.astype(F32), per_tok)
    return chosen, _route_weights(s, chosen, scale), s


def _route_weights(s, chosen, scale):
    picked = jnp.take_along_axis(s, chosen, 1)
    return scale * picked / jnp.sum(picked, 1, keepdims=True)


def route(x, router_w, router_b, per_tok: int, scale: float, mode="f32"):
    """(chosen (N, k) expert ids, weights (N, k)): sigmoid scores, the
    ``per_tok`` largest of score + bias (ties to the lower id, as
    ``lax.top_k`` breaks them), weights the chosen scores over their sum."""
    return _route_all(x, router_w, router_b, per_tok, scale, mode)[:2]


def moe(x, w, D: Dict, mode="f32", drop: Optional[int] = None,
        first_held: Optional[int] = None, held: Optional[int] = None,
        shared: bool = True, block: int = 0, given=None, group: int = 512):
    """The held experts' part of the layer for tokens ``x`` (N, d), plus
    the shared expert.  Expert by expert: the tokens routed to it are
    gathered, ``group`` at a time (the last group padded: one compiled
    shape whatever an expert gets), run through it and added back with
    their weights.  ``drop``: a held expert left out (a planted fault).
    ``block``: run the tokens in blocks of that many.  ``given`` (N, k):
    route every token to these experts (weights from this side's own
    scores of them).  → (y, the experts used, the router's own choice)."""
    first_held = D["first_held"] if first_held is None else first_held
    held = D["held"] if held is None else held
    if block and x.shape[0] > block:
        one = lambda t, g=None: moe(t, w, D, mode, drop,    # noqa: E731
                                    first_held, held, shared, given=g,
                                    group=group)
        return in_blocks(one, x, *(() if given is None
                                   else (jnp.asarray(given),)), block=block)
    own, weights, scores = _route_all(x, w["router_w"], w["router_b"],
                                      D["per_tok"], D["route_scale"], mode)
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


@jit(static_argnums=(5,), donate_argnums=(0,))
def _add_expert(y, x, idx, wt, e, mode):
    return y.at[idx].add(gated_mlp(x[idx], e, mode) * wt[:, None])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _latents(x, w, a: Dict, D: Dict, pos, mode):
    """(c_q (L, q_rank), c_kv (L, kv_rank), k_r (L, rope), gate (L, H))."""
    return _latents_jit(x, {k: w[k] for k in ("wq_a", "q_norm", "wkv_a",
                                              "kv_norm", "w_gate")}, pos,
                        a["q_rank"], a["kv_rank"], a["theta"], D["eps"],
                        D["d"], mode)


@jit(static_argnums=(3, 4, 5, 6, 7, 8))
def _latents_jit(x, w, pos, q_rank, kv_rank, theta, eps, d, mode):
    c_q = rms_norm(ein("nd,dr->nr", x, w["wq_a"], mode), w["q_norm"],
                   eps) * math.sqrt(d / q_rank)
    kv = ein("nd,dr->nr", x, w["wkv_a"], mode)
    c_kv = rms_norm(kv[:, :kv_rank], w["kv_norm"], eps) \
        * math.sqrt(d / kv_rank)
    k_r = rope(kv[:, kv_rank:], pos, theta)
    gate = jax.nn.sigmoid(ein("nd,dh->nh", x, w["w_gate"], mode))
    return c_q, c_kv, k_r, gate


@jit(static_argnums=(3, 4, 5, 6, 7, 8))
def _index_keys(x, w, pos, r, theta, eps, heads, dim, mode):
    """(the indexer's keys (L, dim), its head weights (L, heads))."""
    k = layer_norm(ein("nd,di->ni", x, w["idx_wk"], mode),
                   w["idx_k_norm_w"], w["idx_k_norm_b"], eps)
    k = jnp.concatenate([rope(k[:, :r], pos, theta), k[:, r:]], -1)
    wt = ein("nd,dh->nh", x, w["idx_w"], mode) * (heads ** -0.5) \
        * (dim ** -0.5)
    return k, wt


def selected(scores, topk: int):
    """The mask of the ``topk`` largest of each row (every position s <= t
    while there are no more than ``topk``); of equal scores the earlier
    position first, as ``lax.top_k`` orders them."""
    if scores.shape[1] <= topk:
        return scores > NEG / 2
    _, idx = lax.top_k(scores, topk)
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return mask & (scores > NEG / 2)


@jit(static_argnames=("mode", "r", "theta", "topk",
                                             "fault"))
def _select_block(cq, p, wt_b, k, wq_b, *, mode, r, theta, topk, fault):
    """A block of queries: I(t, s) against every key (s > t at NEG), the
    selected set of each query, bit-packed (block, ceil(L/8))."""
    L = k.shape[0]
    if fault == "no_select":
        return jnp.packbits(jnp.arange(L)[None, :] <= p[:, None], axis=1)
    q = ein("nr,rhi->nhi", cq, wq_b, mode)
    q = jnp.concatenate([rope(q[..., :r], p, theta), q[..., r:]], -1)
    s = jax.nn.relu(ein("nhi,si->nhs", q, k, mode))
    s = jnp.einsum("nhs,nh->ns", s, wt_b, precision=lax.Precision.HIGHEST)
    causal = jnp.arange(L)[None, :] <= p[:, None]
    if fault == "shift_cache":          # the keys stand one position late
        s = jnp.roll(s, 1, 1)
    s = jnp.where(causal, s, NEG)
    return jnp.packbits(selected(s, topk), axis=1)


@jit(static_argnames=("mode", "nope"))
def _group_keys(c_kv, wkv_b, *, mode, nope):
    """A group of heads' keys and values for every position."""
    kv = ein("sr,rhe->she", c_kv, wkv_b, mode)
    return kv[..., :nope], kv[..., nope:]


@jit(static_argnames=("mode", "nope", "theta",
                                             "scale", "window", "k1"))
def _attend_block(cq, p, g, wq_b, k_nope, v, k_rot, key_pos, mask, wo, *,
                  mode, nope, theta, scale, window, k1=0):
    """A block of queries x a group of heads: masked softmax attention,
    the headwise gate, the group's rows of the output projection.
    ``window``: 0 for a full layer.  ``k1`` (a full layer): the keys are
    the first ``k1`` of the session's, cut out in here (outside, the cut is
    a copy of every key a call), and ``mask`` comes bit-packed."""
    if k1:
        k_nope, v, k_rot = k_nope[:k1], v[:k1], k_rot[:k1]
        key_pos = jnp.arange(k1)
        mask = jnp.unpackbits(mask, axis=1, count=k1).astype(bool)
    q = ein("nr,rhe->nhe", cq, wq_b, mode)
    q_r = rope(q[..., nope:], p, theta)
    s = (ein("nhe,she->hns", q[..., :nope], k_nope, mode)
         + ein("nhe,se->hns", q_r, k_rot, mode)) * scale
    ok = mask & (key_pos[None, :] <= p[:, None]) & (key_pos[None, :] >= 0)
    if window:
        ok &= key_pos[None, :] > p[:, None] - window
    s = jnp.where(ok[None], s, NEG)
    prob = jax.nn.softmax(s, -1)
    o = ein("hns,she->nhe", prob, v, mode) * g[:, :, None]
    return ein("nhe,hed->nd", o, wo, mode)


def _pad_cols(m, cols: int):
    return np.pad(m, ((0, 0), (0, cols - m.shape[1])))


def attention(x, w, a: Dict, D: Dict, pos, mode, kind: str,
              fault: Optional[str], blocks: Dict, given=None,
              keep: Sequence[int] = (), emit: bool = False, first: int = 0):
    """One attention block's output (L, d) for normed input ``x``.  Every
    block of queries works on the keys up to its own end, rounded up to
    ``key_round`` (so that few shapes compile, whatever the length).
    ``first``: the first query whose output is wanted (the blocks of
    queries before its block are left at zero).

    ``given`` (a full layer): bit-packed rows (n, ceil(n/8)) of the
    positions each of the first n queries attends to, taken in place of
    this side's own selection; the own selection is then made for the
    queries ``keep`` alone, and the positions on which the two differ are
    counted over those of them that have more than ``index_topk`` keys.
    → (out, {"selected": this side's own sets, bit-packed (L, L/8), with
    ``emit``; "miss": (positions in one set only, positions in both sets
    together)})."""
    L = x.shape[0]
    full = kind == "full_attention"
    rows = blocks["pad_to"]
    c_q, c_kv, k_r, gate = in_blocks(
        lambda x_, p_: _latents(x_, w, a, D, p_, mode), x, pos, block=rows)
    if fault == "shift_cache" and full:
        c_kv, k_r = (jnp.roll(t, 1, 0) for t in (c_kv, k_r))
    pre = "" if full else "swa_"
    qb, hg = blocks[pre + "q_block"], min(blocks[pre + "head_group"],
                                          a["heads"])
    window, q0 = D["window"], first // qb * qb

    def keys_end(hi):
        return min(L, -(-hi // blocks["key_round"]) * blocks["key_round"])

    packed, info = {}, {"miss": (0, 0)}
    if full:
        ib, r = blocks["idx_q_block"], a["rope"]
        w_idx = {k: w[k] for k in ("idx_wk", "idx_k_norm_w", "idx_k_norm_b",
                                   "idx_w")}
        k_idx, wt = in_blocks(
            lambda x_, p_: _index_keys(x_, w_idx, p_, r, a["theta"],
                                       D["eps"], D["idx_heads"],
                                       D["idx_dim"], mode),
            x, pos, block=rows)
        sel = dict(mode=mode, r=r, theta=a["theta"], topk=D["topk"],
                   fault=fault if fault in ("no_select", "shift_cache")
                   else None)
        for lo in range(q0, L, qb):
            hi, k1 = min(L, lo + qb), keys_end(min(L, lo + qb))
            if given is not None:
                g = _pad_cols(given[lo:hi, :k1 // 8], k1 // 8)
                packed[lo] = jnp.asarray(np.pad(
                    g, ((0, hi - lo - g.shape[0]), (0, 0))))
                continue
            packed[lo] = jnp.concatenate([
                _select_block(c_q[i:i + ib], pos[i:i + ib], wt[i:i + ib],
                              k_idx[:k1], w["idx_wq_b"], **sel)
                for i in range(lo, hi, ib)], 0)
        if given is not None:
            long_ = [t for t in keep if t >= D["topk"]]
            if long_:
                at, k1 = jnp.asarray(long_), keys_end(max(long_) + 1)
                own = np.asarray(_select_block(
                    c_q[at], pos[at], wt[at], k_idx[:k1], w["idx_wq_b"],
                    **sel))
                theirs = _pad_cols(given[long_, :k1 // 8], k1 // 8)
                info["miss"] = (
                    int(np.unpackbits(own ^ theirs).sum()),
                    int(np.unpackbits(own).sum()
                        + np.unpackbits(theirs).sum()))
        elif emit:
            info["selected"] = np.concatenate(
                [_pad_cols(np.asarray(packed[lo]), L // 8)
                 for lo in range(0, L, qb)], 0)
    static = dict(mode=mode, nope=a["nope"], theta=a["theta"],
                  scale=1.0 / math.sqrt(a["nope"] + a["rope"]),
                  window=0 if full else window)
    parts = {}                    # a block of queries' output, heads summed
    for h0 in range(0, a["heads"], hg):
        hs = slice(h0, h0 + hg)
        wkv_b, wq_b, wo = w["wkv_b"][:, hs], w["wq_b"][:, hs], w["wo"][hs]
        k_nope, v = in_blocks(
            lambda c: _group_keys(c, wkv_b, mode=mode, nope=a["nope"]),
            c_kv, block=rows)
        for lo in range(q0, L, qb):
            hi = min(L, lo + qb)
            if full:
                kk, vv, kr, key_pos, mask = k_nope, v, k_r, None, packed[lo]
                static["k1"] = keys_end(hi)
            else:
                # a fixed span of qb + window - 1 keys ending at the
                # block's end; positions before the session's start are
                # negative and masked
                span = qb + window - 1
                at = jnp.arange(lo + qb - span, lo + qb)
                idx = jnp.clip(at, 0, L - 1)
                key_pos = jnp.where(at < L, at, -1)
                kk, vv, kr = k_nope[idx], v[idx], k_r[idx]
                mask = jnp.ones((hi - lo, span), bool)
            part = _attend_block(c_q[lo:hi], pos[lo:hi], gate[lo:hi, hs],
                                 wq_b, kk, vv, kr, key_pos, mask, wo,
                                 **static)
            parts[lo] = part if lo not in parts else parts[lo] + part
    out = jnp.concatenate([jnp.zeros((q0, D["d"]), F32)]
                          + [parts[lo] for lo in range(q0, L, qb)], 0)
    return out, info


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

@jit(static_argnums=(3, 4))
def _logits(h, norm_w, head, eps, mode):
    return ein("nd,dv->nv", rms_norm(h, norm_w, eps), head, mode)


#: a full layer's call scores ``q_block`` queries x ``head_group`` heads
#: against up to every key of the session (2.1 GB of float32 scores at
#: 65,536 keys); a sliding layer's ``swa_q_block`` queries x all heads
#: against ``swa_q_block + window - 1`` keys.  Few large calls: at 256 x 16
#: a call's dispatch and its copies, not its arithmetic, were most of a
#: 33 k session's 27 s in the first layer's attention on a v5e (PR 28)
BLOCKS = {"q_block": 512, "swa_q_block": 1024, "idx_q_block": 64,
          "head_group": 16, "swa_head_group": 64, "key_round": 8192,
          "mlp_block": 8192, "pad_to": 8192, "expert_group": 512}


def forward(cfg: Dict, seed: int, tokens, mode: str = "f32",
            fault: Optional[str] = None, blocks: Optional[Dict] = None,
            weights: Optional[Dict] = None, keep: Sequence[int] = (),
            follow: Optional[Dict] = None, emit: bool = False) -> Dict:
    """The full forward of one session's ``tokens`` (L,).  Returns
    ``logits`` (L, vocab) float32 — or, with ``keep``, only those
    positions' rows — and ``chosen`` {layer: (L, k) expert ids used}.
    ``weights`` ({"layers": [...], "ends": {...}}): use these in place of
    the seed's (the tests' way in; the same names).

    ``follow`` = {"selected": {layer: uint8 (L, ceil(L/8)) bit-packed rows},
    "routed": {layer: (L, k) expert ids}}: the discrete choices to take as
    given (module docstring); the result then has ``miss`` = {"select":
    (differing, counted) over the rows ``keep`` with more than
    ``index_topk`` keys, "route": (differing, counted) (token, expert)
    pairs over the positions computed}: on how much this side's own
    choices, made from the same followed state, differ from the given
    ones.  ``emit``: also return this side's own ``selected`` in that form.
    A ``fault`` of ``"drop_expert:<j>"`` leaves out held expert j
    (``drop_expert``: 0); ``no_select`` ignores a given selection as it
    ignores its own.

    With ``keep`` (and no ``emit``) a layer computes only the rows that
    the kept ones can see: a sliding layer's output at t needs its input
    on (t − window, t] alone, so above the last full layer each layer
    starts ``window − 1`` rows before the next, rounded down to a whole
    block of ``pad_to`` rows so that every session's layers share their
    compiled shapes (``chosen`` then holds those rows only, the others
    −1)."""
    return forward_many(cfg, seed, [dict(tokens=tokens, keep=keep,
                                         follow=follow, emit=emit)],
                        mode, fault, blocks, weights)[0]


def forward_many(cfg: Dict, seed: int, sessions: Sequence[Dict],
                 mode: str = "f32", fault: Optional[str] = None,
                 blocks: Optional[Dict] = None,
                 weights: Optional[Dict] = None) -> Sequence[Dict]:
    """:func:`forward` for several sessions (each a dict of its
    ``tokens`` and, if any, ``keep``, ``follow``, ``emit``), a layer at a
    time over all of them: a layer's weights are made from the seed once
    (the 4 billion of the published widths take longer than a short
    session's forward)."""
    D = dims(cfg)
    given_blocks = dict(blocks or {})
    blocks = dict(BLOCKS, **given_blocks)
    for key in ("q_block", "head_group"):   # a sliding layer's follow them
        if key in given_blocks:
            blocks["swa_" + key] = given_blocks.get("swa_" + key,
                                                    given_blocks[key])
    rows, full = blocks["pad_to"], "full_attention"
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
        # the first row each layer has to compute
        start = [0] * D["layers"]
        if keep and not one.get("emit"):
            lo = min(keep)
            for i in reversed(range(D["layers"])):
                start[i] = lo // rows * rows
                lo = 0 if D["kinds"][i] == full \
                    else max(0, lo - (D["window"] - 1))
        state.append(dict(
            n_real=n_real, L=tokens.shape[0], pos=jnp.arange(tokens.shape[0]),
            h=ends["embed"][tokens].astype(F32), keep=keep, start=start,
            follow=one.get("follow") or {}, emit=bool(one.get("emit")),
            chosen={}, selected={},
            miss={"select": [0, 0], "route": [0, 0]}))
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
    return [{k: st[k] for k in ("logits", "miss", "selected", "chosen")}
            for st in state]


def _layer(st: Dict, i: int, w: Dict, D: Dict, mode: str,
           fault: Optional[str], drop: Optional[int], blocks: Dict) -> None:
    """Layer ``i`` of one session, from row ``st["start"][i]`` on."""
    rows, kind = blocks["pad_to"], D["kinds"][i]
    a = D["full"] if kind == "full_attention" else D["swa"]
    h, n_real, first = st["h"], st["n_real"], st["start"][i]
    x = in_blocks(lambda t: rms_norm(t, w["attn_norm"], D["eps"]), h,
                  block=rows)
    given = st["follow"].get("selected", {}).get(i)
    o, info = attention(x, w["attn"], a, D, st["pos"], mode, kind, fault,
                        blocks, given=None if fault == "no_select" else given,
                        keep=st["keep"], emit=st["emit"], first=first)
    h = h + o
    if given is not None and fault == "no_select":
        # the fault's own sets: every position up to the query's
        info["miss"] = _dense_miss(given, [t for t in st["keep"]
                                           if t >= D["topk"]])
    st["miss"]["select"] = [m + n for m, n in zip(st["miss"]["select"],
                                                  info["miss"])]
    if "selected" in info:
        st["selected"][i] = info["selected"][:n_real, :-(-n_real // 8)]
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
                           given=given, group=blocks["expert_group"])
        u, o_ = (np.asarray(t)[:n_real - first] for t in (used, own))
        st["chosen"][i] = np.concatenate(
            [np.full((first,) + u.shape[1:], -1, u.dtype), u])
        if given is not None:
            st["miss"]["route"][0] += int(
                (u[:, :, None] != o_[:, None, :]).all(-1).sum())
            st["miss"]["route"][1] += u.size
    st["h"] = h.at[first:].add(y)


def _dense_miss(given, rows):
    """(positions in one set only, positions in both together) of the
    given rows against dense causal sets."""
    one = both = 0
    for t in rows:
        n = int(np.unpackbits(given[t]).sum())
        one, both = one + (t + 1 - n), both + (t + 1 + n)
    return one, both
