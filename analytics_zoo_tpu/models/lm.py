"""A causal decoder LM for serving through device-resident session caches
(pipelines/lm.py): RMS-norm pre-norm residual blocks; attention in one of
two FORMS — multi-head latent attention (MLA: one latent + rotary key a
token in the cache, shared by all heads) or grouped-query attention (GQA:
real keys and values a KV head in the cache) — and three kinds of layer by
REACH: FULL layers (MLA only) with a learned sparse indexer that selects
the ``index_topk`` positions a token attends to, SLIDING layers over a
window (a ring a session), CAUSAL layers over the whole context (every
page a row holds, read by a paged decode kernel: ops/pallas_lm_decode.py);
a gated dense MLP in the leading layers, then sparse experts (sigmoid
router of the published width, top-k — group-limited where the config says
so — a shared expert where the config has one) of which THIS CHIP HOLDS A
SHARE (parallel/expert.py); embedding and head over a slice of the
vocabulary.

WHICH model it is comes from the config's keys alone
(``LMConfig.from_dict``).  The FORM: a config with ``kv_lora_rank`` is
latent (``MLADims``), one with ``num_key_value_heads`` and none is
grouped-query (``GQADims``: heads and KV heads, key and value widths,
partial rotary, value scale and a learned softmax sink a kind of layer).
The KINDS: ``layer_types`` names FULL and SLIDING layers (the
``dots3_note`` family: headwise sigmoid gates on the heads' outputs,
rescaled latents, a bias-corrected router); else ``hybrid_layer_pattern``
names CAUSAL (0) and SLIDING (1) layers (the ``mimo_v2`` family:
grouped-query, KV heads and rotary base a kind, sinks in the window
layers, no shared expert); a config with neither is CAUSAL throughout
(the ``axk1`` family: YaRN rotary scaling, routing groups, no gate, no
rescale, no router bias).  The leading dense layers are
``first_k_dense_replace`` or the leading zeros of a ``moe_layer_freq``
list.  ``param_shapes`` and ``cache_shapes`` follow: a model without
sliding layers has no ring, one without an indexer no index keys, one
without a shared expert no ``shared`` leaf; a grouped-query model's pools
and rings are as wide as their kind's KV heads.

Functional, not flax: the step functions take the parameters and the
cache and return the new cache, so one jitted call is a whole batch of
rows and the cache is donated from call to call.

``LMConfig.from_dict`` reads a HuggingFace-style ``config.json`` with the
share beside it (``expert_share``); the parameter tree's names are the
benchmark reference's (benchmarks/reference/lm.py), which is how the same
weights reach both.

Two step programs:

- :func:`decode_step` — B rows of any sessions, one token each (its
  integer arguments in one vector, :func:`pack_rows`: one transfer a
  step; :func:`decode_rows` is the same step over them apart);
- :func:`prefill_step` — one session's chunk of T tokens (chunked
  prefill: a chunk of any length up to T, at any position).

Both write the new tokens' cache entries, and both return float32 logits
over the vocabulary slice at each row's last position, the tokens each held
expert got, and the step's discrete CHOICES: ``{"selected": a full layer's
selected positions (ops/lm_attention.py; an empty list without full
layers), "routed": (MoE layers, tokens, k) expert ids}``.  They stay on
the device unless somebody fetches them
(pipelines/lm.py records them for the sessions it is asked to).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import lm_attention as att
from analytics_zoo_tpu.ops import pallas_ssm_decode, ssm
from analytics_zoo_tpu.parallel.expert import moe_held_experts

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
#: a third kind, for a config that names no ``layer_types``: causal MLA over
#: the WHOLE context — no indexer, no index keys, no ring; decode reads every
#: page a row holds (ops/pallas_lm_decode.py)
CAUSAL = "causal_attention"
#: a cache entry's width is rounded up to this many elements: the TPU
#: tiles the minor axis by 128, and for a width of 576 its default layout
#: puts the PAGE axis minor, so that every gather and scatter of a token's
#: row first copies the whole pool into the other layout and back (seen
#: in the compiled decode step: four 1.4 GB copies a step)
LANE = 128
#: heads a step of prefill's Pallas attention (ops/pallas_lm_prefill.py):
#: at 4 the kernel's blocks and scratch take 25 MiB of VMEM at the
#: published widths and 1,024 queries
PREFILL_HEADS_PER_STEP = 4


@dataclasses.dataclass(frozen=True)
class MLADims:
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    scaling: Optional[att.RopeScaling] = None    # a config's rope_scaling

    @property
    def scale(self) -> float:
        """The softmax scale; YaRN's ``mscale``, squared, lives here."""
        m = self.scaling.softmax_mscale if self.scaling else 1.0
        return m * m / math.sqrt(self.nope + self.rope)

    @property
    def entry(self) -> int:
        """Width of a token's cache entry: latent, rotary key, padding."""
        return -(-(self.kv_rank + self.rope) // LANE) * LANE


@dataclasses.dataclass(frozen=True)
class GQADims:
    """A layer of grouped-query attention: ``heads`` query heads, each
    reading KV head ``a // (heads / kv_heads)``; keys ``k`` and values
    ``v`` wide; rotary on a head's first ``rotary`` dims in pairs at a
    distance (ops/lm_attention.py ``rope_half``); the values times
    ``value_scale``; ``sink``: one learned scalar a head joins the
    softmax as one more column."""
    heads: int
    kv_heads: int
    k: int
    v: int
    rotary: int
    theta: float
    value_scale: float = 1.0
    sink: bool = False

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.k)

    @property
    def entry(self) -> int:
        """Width of a token's cache entry: every KV head's key and value
        (ops/lm_attention.py ``gqa_entry``), nothing else."""
        return self.kv_heads * (self.k + self.v)


@dataclasses.dataclass(frozen=True)
class SSMDims:
    """A layer's state-space mixer (ops/ssm.py): ``heads`` heads of
    ``head`` channels in ``groups`` groups that share ``B`` and ``C``, a
    state of ``state`` a channel, a causal convolution ``conv`` wide in
    front, prefill in blocks of ``chunk`` tokens."""
    heads: int
    head: int
    state: int
    groups: int
    conv: int
    chunk: int

    @property
    def inner(self) -> int:
        """Channels of the gate ``z`` and of ``x``."""
        return self.heads * self.head

    @property
    def blocks(self) -> Tuple[int, ...]:
        """The in-projection's column blocks ``[z | x | B | C | dt]``."""
        shared = self.groups * self.state
        return (self.inner, self.inner, shared, shared, self.heads)

    @property
    def conv_width(self) -> int:
        """What the convolution runs over: ``x``, ``B``, ``C``."""
        return sum(self.blocks[1:4])

    @property
    def proj(self) -> int:
        return sum(self.blocks)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The muP multipliers a config's keys give (``falcon_h1``); all 1
    for a config that has none, and a factor of 1 is never multiplied
    in."""
    embed: float = 1.0            # embedding_multiplier
    attn_in: float = 1.0          # attention_in_multiplier
    key: float = 1.0              # key_multiplier
    attn_out: float = 1.0         # attention_out_multiplier
    ssm_in: float = 1.0           # ssm_in_multiplier
    ssm: Tuple[float, ...] = (1.0,) * 5   # ssm_multipliers: z, x, B, C, dt
    ssm_out: float = 1.0          # ssm_out_multiplier
    mlp_gate: float = 1.0         # mlp_multipliers[0]
    mlp_down: float = 1.0         # mlp_multipliers[1]
    head: float = 1.0             # lm_head_multiplier

    @classmethod
    def from_dict(cls, cfg: Dict) -> "Multipliers":
        gate, down = cfg.get("mlp_multipliers") or (1.0, 1.0)
        one = lambda key: float(cfg.get(key) or 1.0)          # noqa: E731
        return cls(
            embed=one("embedding_multiplier"),
            attn_in=one("attention_in_multiplier"),
            key=one("key_multiplier"),
            attn_out=one("attention_out_multiplier"),
            ssm_in=one("ssm_in_multiplier"),
            ssm=tuple(float(m) for m in cfg.get("ssm_multipliers")
                      or (1.0,) * 5),
            ssm_out=one("ssm_out_multiplier"), mlp_gate=float(gate),
            mlp_down=float(down), head=one("lm_head_multiplier"))


@dataclasses.dataclass(frozen=True)
class LMConfig:
    d: int
    kinds: Tuple[str, ...]            # per layer: FULL, SLIDING or CAUSAL
    dense_layers: int                 # leading layers with a dense MLP
    full: Union[MLADims, GQADims]     # a FULL or CAUSAL layer's sizes
    swa: Union[MLADims, GQADims, None]  # a SLIDING layer's (None: none)
    window: int
    idx_heads: int
    idx_dim: int
    topk: int
    f_dense: int
    f_expert: int
    f_shared: int
    experts: int                      # the router's (published) width
    held: int                         # experts held here
    first_held: int
    per_tok: int
    route_scale: float
    vocab: int                        # rows of the vocabulary held here
    eps: float
    dtype: str = "bfloat16"
    # what a config's keys switch on (from_dict): absent, a plain block
    gate: bool = False                # attention_gate_type: headwise
    rescale: bool = False             # apply_mla_qkv_lora_rescale
    route_bias: bool = False          # topk_method: noaux_tc (a router_b)
    n_group: int = 1                  # routing groups ...
    topk_group: int = 1               # ... of which a token's experts lie in
    ssm: Optional[SSMDims] = None     # mamba_*: a mixer beside EVERY attention
    mup: Multipliers = Multipliers()

    @classmethod
    def from_dict(cls, cfg: Dict) -> "LMConfig":
        """A config's keys are the only source of what the model is.
        ``layer_types`` (with the ``swa_*`` and ``index_*`` sizes its
        kinds need) names FULL and SLIDING layers; a config without it is
        CAUSAL MLA throughout.  ``attention_gate_type: "headwise"`` → the
        heads' sigmoid gate; ``apply_mla_qkv_lora_rescale`` → sqrt(hidden /
        rank) on the latents; ``rope_scaling`` → YaRN; ``topk_method:
        "noaux_tc"`` → the router's bias; ``n_group`` / ``topk_group`` →
        group-limited routing.  A config without ``n_routed_experts`` has
        no experts: every layer's MLP is dense.  ``mamba_d_state`` → a
        state-space mixer beside every layer's attention (``SSMDims``; its
        inner width is ``mamba_d_ssm``, or ``mamba_expand`` x hidden where
        that is null); the ``*_multiplier(s)`` keys → ``Multipliers``."""
        n = int(cfg["num_hidden_layers"])
        held = int(cfg.get("n_routed_experts") or 0)
        share = cfg.get("expert_share") or {"published_experts": held,
                                            "index": 0}
        if cfg.get("layer_types"):
            kinds = tuple(cfg["layer_types"][:n])
        elif cfg.get("hybrid_layer_pattern"):
            kinds = tuple(SLIDING if g else CAUSAL
                          for g in cfg["hybrid_layer_pattern"][:n])
        else:
            kinds = (CAUSAL,) * n
        if cfg.get("attention_gate_type") not in (None, "headwise"):
            raise ValueError("attention_gate_type: only headwise is known, "
                             f"got {cfg['attention_gate_type']!r}")
        if "kv_lora_rank" in cfg:
            dims = lambda p, theta, scaling=None: MLADims(     # noqa: E731
                heads=int(cfg[p + "num_attention_heads"]),
                q_rank=int(cfg[p + "q_lora_rank"]),
                kv_rank=int(cfg[p + "kv_lora_rank"]),
                nope=int(cfg[p + "qk_nope_head_dim"]),
                rope=int(cfg[p + "qk_rope_head_dim"]),
                v=int(cfg[p + "v_head_dim"]), theta=float(cfg[theta]),
                scaling=scaling)
            full = dims("", "rope_theta",
                        att.RopeScaling.from_dict(cfg.get("rope_scaling")))
        elif "num_key_value_heads" in cfg:
            scaling = cfg.get("rope_scaling") or {}
            if scaling.get("type", scaling.get("rope_type",
                                               "default")) != "default":
                raise ValueError("rope_scaling: a grouped-query model's is "
                                 f"not scaled here, got {scaling}")
            if FULL in kinds or cfg.get("add_full_attention_sink_bias"):
                raise ValueError("grouped-query attention: no indexer and "
                                 "no sink in a global layer are known")

            def dims(p, theta):
                k = int(cfg[p + "head_dim"])
                return GQADims(
                    heads=int(cfg[p + "num_attention_heads"]),
                    kv_heads=int(cfg[p + "num_key_value_heads"]), k=k,
                    v=int(cfg.get(p + "v_head_dim") or k),
                    rotary=int(float(cfg.get("partial_rotary_factor", 1.0))
                               * k),
                    theta=float(cfg[theta]),
                    value_scale=float(cfg.get("attention_value_scale")
                                      or 1.0),
                    sink=bool(cfg.get(
                        f"add_{p or 'full_'}attention_sink_bias")))
            full = dims("", "rope_theta")
        else:
            raise ValueError("neither kv_lora_rank (latent attention) nor "
                             "num_key_value_heads (grouped-query) in the "
                             "config")
        if not held:
            dense_layers = n
        elif "first_k_dense_replace" in cfg:
            dense_layers = int(cfg["first_k_dense_replace"])
        else:
            freq = [int(f) for f in cfg["moe_layer_freq"]][:n]
            dense_layers = freq.index(1) if 1 in freq else n
            if any(f != 1 for f in freq[dense_layers:]):
                raise ValueError("moe_layer_freq: only dense layers first, "
                                 f"then expert layers, got {freq}")
        route_scale = cfg.get("routed_scaling_factor")
        dtype = cfg.get("compute_dtype", "bfloat16")
        mixer = None
        if cfg.get("mamba_d_state"):
            if cfg.get("attn_layer_indices") is not None \
                    or not cfg.get("mamba_rms_norm", True) \
                    or cfg.get("mamba_norm_before_gate"):
                raise ValueError("state-space mixer: only one beside every "
                                 "attention, with the gated norm after the "
                                 "gate, is known")
            inner = int(cfg.get("mamba_d_ssm")
                        or cfg["mamba_expand"] * cfg["hidden_size"])
            mixer = SSMDims(
                heads=int(cfg["mamba_n_heads"]),
                head=inner // int(cfg["mamba_n_heads"]),
                state=int(cfg["mamba_d_state"]),
                groups=int(cfg["mamba_n_groups"]),
                conv=int(cfg["mamba_d_conv"]),
                chunk=int(cfg["mamba_chunk_size"]))
        return cls(
            d=int(cfg["hidden_size"]), kinds=kinds,
            dense_layers=dense_layers, full=full,
            swa=dims("swa_", "swa_rope_theta") if SLIDING in kinds else None,
            window=int(cfg["sliding_window_size"]) if SLIDING in kinds else 0,
            idx_heads=int(cfg["index_n_heads"]) if FULL in kinds else 0,
            idx_dim=int(cfg["index_head_dim"]) if FULL in kinds else 0,
            topk=int(cfg["index_topk"]) if FULL in kinds else 0,
            f_dense=int(cfg["intermediate_size"]),
            f_expert=int(cfg.get("moe_intermediate_size") or 0),
            f_shared=int(cfg.get("moe_intermediate_size") or 0)
            * int(cfg.get("n_shared_experts") or 0),
            experts=int(share["published_experts"]), held=held,
            first_held=int(share["index"]) * held,
            per_tok=int(cfg.get("num_experts_per_tok") or 0),
            route_scale=1.0 if route_scale is None else float(route_scale),
            vocab=int(cfg["vocab_size"]),
            eps=float(cfg["rms_norm_eps"] if "rms_norm_eps" in cfg
                      else cfg["layernorm_epsilon"]),
            dtype={"bf16": "bfloat16"}.get(dtype, dtype),
            gate=cfg.get("attention_gate_type") == "headwise",
            rescale=bool(cfg.get("apply_mla_qkv_lora_rescale", False)),
            route_bias=cfg.get("topk_method", "noaux_tc") == "noaux_tc",
            n_group=int(cfg.get("n_group") or 1),
            topk_group=int(cfg.get("topk_group") or 1),
            ssm=mixer, mup=Multipliers.from_dict(cfg))

    @property
    def n_full(self) -> int:
        return sum(k == FULL for k in self.kinds)

    @property
    def n_sliding(self) -> int:
        return sum(k == SLIDING for k in self.kinds)

    @property
    def n_pools(self) -> int:
        """Layers whose entries live in a paged pool: FULL and CAUSAL."""
        return len(self.kinds) - self.n_sliding

    def dims(self, kind: str) -> Union[MLADims, GQADims]:
        return self.swa if kind == SLIDING else self.full


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: LMConfig) -> Dict:
    """The parameter tree as shapes: {"layers": [...], "ends": {...}}."""
    dt = jnp.dtype(cfg.dtype)
    S = lambda *s: jax.ShapeDtypeStruct(s, dt)          # noqa: E731

    def mlp(f, *lead):
        return {"w_gate": S(*lead, cfg.d, f), "w_up": S(*lead, cfg.d, f),
                "w_down": S(*lead, f, cfg.d)}

    layers = []
    for i, kind in enumerate(cfg.kinds):
        a = cfg.dims(kind)
        if isinstance(a, GQADims):
            attn = {"wq": S(cfg.d, a.heads, a.k),
                    "wk": S(cfg.d, a.kv_heads, a.k),
                    "wv": S(cfg.d, a.kv_heads, a.v),
                    "wo": S(a.heads, a.v, cfg.d)}
            if a.sink:
                attn["sink"] = jax.ShapeDtypeStruct((a.heads,), F32)
        else:
            attn = {"wq_a": S(cfg.d, a.q_rank), "q_norm": S(a.q_rank),
                    "wq_b": S(a.q_rank, a.heads, a.nope + a.rope),
                    "wkv_a": S(cfg.d, a.kv_rank + a.rope),
                    "kv_norm": S(a.kv_rank),
                    "wkv_b": S(a.kv_rank, a.heads, a.nope + a.v),
                    "wo": S(a.heads, a.v, cfg.d)}
        if cfg.gate:
            attn["w_gate"] = S(cfg.d, a.heads)
        if kind == FULL:
            attn.update({"idx_wq_b": S(a.q_rank, cfg.idx_heads, cfg.idx_dim),
                         "idx_wk": S(cfg.d, cfg.idx_dim),
                         "idx_k_norm_w": S(cfg.idx_dim),
                         "idx_k_norm_b": S(cfg.idx_dim),
                         "idx_w": S(cfg.d, cfg.idx_heads)})
        layer = {"attn_norm": S(cfg.d), "mlp_norm": S(cfg.d), "attn": attn}
        if cfg.ssm:
            m = cfg.ssm
            f32 = lambda *s: jax.ShapeDtypeStruct(s, F32)     # noqa: E731
            layer["ssm"] = {
                "in_proj": S(cfg.d, m.proj), "conv_w": S(m.conv_width, m.conv),
                "conv_b": S(m.conv_width), "dt_bias": f32(m.heads),
                "A_log": f32(m.heads), "D": f32(m.heads),
                "norm": S(m.inner), "out_proj": S(m.inner, cfg.d)}
        if i < cfg.dense_layers:
            layer["mlp"] = mlp(cfg.f_dense)
        else:
            layer["moe"] = {
                "router_w": S(cfg.d, cfg.experts),
                "experts": mlp(cfg.f_expert, cfg.held)}
            if cfg.f_shared:
                layer["moe"]["shared"] = mlp(cfg.f_shared)
            if cfg.route_bias:
                layer["moe"]["router_b"] = jax.ShapeDtypeStruct(
                    (cfg.experts,), F32)
        layers.append(layer)
    return {"layers": layers,
            "ends": {"embed": S(cfg.vocab, cfg.d), "final_norm": S(cfg.d),
                     "head": S(cfg.d, cfg.vocab)}}


def init_params(cfg: LMConfig, seed: int = 0) -> Dict:
    """Random parameters: normal of variance 1/fan_in (the first of a
    matrix's contracted axes sets it), norm weights one, router bias and
    sinks 0."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for key, (path, s) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "_b" not in name:
            out.append(jnp.ones(s.shape, s.dtype))
        elif name.endswith(("router_b']", "sink']")) or "norm_b" in name:
            out.append(jnp.zeros(s.shape, s.dtype))
        else:
            fan = s.shape[-2] if len(s.shape) > 1 else s.shape[0]
            if name.endswith(("wq_b']", "wkv_b']", "wq']", "wk']", "wv']")):
                fan = s.shape[0]
            elif name.endswith("wo']"):
                fan = s.shape[0] * s.shape[1]
            elif name.endswith("embed']"):
                fan = 1
            out.append((jax.random.normal(key, s.shape, F32)
                        / math.sqrt(fan)).astype(s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """``n_pages`` pages of ``page`` tokens for the full and causal layers
    (page 0 is nobody's), ``max_pages`` pages a session at most,
    ``n_slots`` sessions (each a row of the page tables and, where there
    are sliding layers, a ring; where there is a state-space mixer, a
    recurrent state and a convolution's state a layer)."""
    n_pages: int
    page: int
    max_pages: int
    n_slots: int

    @property
    def max_len(self) -> int:
        return self.max_pages * self.page


def cache_shapes(cfg: LMConfig, geo: CacheGeometry) -> Dict:
    """One array a layer (a layer's pool is never sliced out of a stack),
    for the kinds of layer the model has: ``kv`` for the full and the
    causal layers, ``ik`` for the full ones (the indexer's keys), ``ring``
    for the sliding ones; for a model with a state-space mixer, a layer's
    recurrent states ``ssm`` (float32, a slot a session) and the last
    ``conv − 1`` inputs of its convolution ``conv``."""
    dt = jnp.dtype(cfg.dtype)
    S = jax.ShapeDtypeStruct
    m, mixers = cfg.ssm, len(cfg.kinds) if cfg.ssm else 0
    return {
        "kv": [S((geo.n_pages, geo.page, cfg.full.entry), dt)
               for _ in range(cfg.n_pools)],
        "ik": [S((geo.n_pages, geo.page, cfg.idx_dim), dt)
               for _ in range(cfg.n_full)],
        "ring": [S((geo.n_slots, cfg.window, cfg.swa.entry), dt)
                 for _ in range(cfg.n_sliding)],
        "ssm": [S((geo.n_slots, m.heads, m.head, m.state), F32)
                for _ in range(mixers)],
        "conv": [S((geo.n_slots, m.conv - 1, m.conv_width), dt)
                 for _ in range(mixers)]}


#: the cache's leaves in the order the step programs unpack them
CACHE_KEYS = ("kv", "ik", "ring", "ssm", "conv")


def new_cache(cfg: LMConfig, geo: CacheGeometry) -> Dict:
    return jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, v.dtype),
                                  cache_shapes(cfg, geo))


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(F32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(F32)).astype(x.dtype)


def layer_norm(x, w, b, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def times(x, by: float):
    """``by · x`` through float32, rounded once; ``x`` itself at 1.  The
    ``== 1.0`` forks here and in ``gated_mlp``, ``gqa_project`` and ``head``
    are for one thing: a configuration without multipliers lowers to
    the program it did before there were any (the three decoder cells'
    step programs are compared as text with their parent's)."""
    return x if by == 1.0 else (x.astype(F32) * by).astype(x.dtype)


def scaled(spec: str, x, w, by):
    """``by · einsum(x, w)``: accumulated and scaled in float32 (``by`` a
    number or a vector over the last axis), rounded once."""
    return (jnp.einsum(spec, x, w, preferred_element_type=F32)
            * by).astype(x.dtype)


def gated_mlp(x, w, gate: float = 1.0, down: float = 1.0):
    """``down · (SiLU(gate · x W_gate) ⊙ x W_up) W_down``; without
    multipliers the plain products."""
    if gate == 1.0 and down == 1.0:
        return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    return scaled("nf,fd->nd", jax.nn.silu(
        scaled("nd,df->nf", x, w["w_gate"], gate)) * (x @ w["w_up"]),
        w["w_down"], down)


def latents(cfg: LMConfig, a: MLADims, w: Dict, x, pos):
    """(c_q (N, q_rank), c (N, a.entry) the cache entry — latent, rotary
    key, zeros up to the entry's width — gate (N, H), ``None`` without one)
    of normed inputs ``x`` (N, d) at positions ``pos`` (N,).
    ``cfg.rescale``: sqrt(hidden/rank) after the norms."""
    c_q = rms_norm(x @ w["wq_a"], w["q_norm"], cfg.eps)
    kv = x @ w["wkv_a"]
    c_kv = rms_norm(kv[:, :a.kv_rank], w["kv_norm"], cfg.eps)
    if cfg.rescale:
        c_q = c_q * jnp.asarray(math.sqrt(cfg.d / a.q_rank), x.dtype)
        c_kv = c_kv * jnp.asarray(math.sqrt(cfg.d / a.kv_rank), x.dtype)
    k_r = att.rope(kv[:, a.kv_rank:], pos, a.theta, a.scaling)
    gate = jax.nn.sigmoid((x @ w["w_gate"]).astype(F32)) if cfg.gate \
        else None
    pad = jnp.zeros((x.shape[0], a.entry - a.kv_rank - a.rope), x.dtype)
    return c_q, jnp.concatenate([c_kv, k_r, pad], -1), gate


def queries(a: MLADims, w: Dict, c_q, pos):
    """(q_nope (N, H, nope), q_rope (N, H, rope) rotated)."""
    q = jnp.einsum("nr,rhe->nhe", c_q, w["wq_b"])
    return q[..., :a.nope], att.rope(q[..., a.nope:], pos, a.theta,
                                     a.scaling)


def gqa_project(a: GQADims, w: Dict, x, pos, mup: Multipliers = Multipliers()):
    """A grouped-query layer's (q_plain (N, H, k − rotary), q_rot (N, H,
    rotary) rotated, c (N, a.entry) the cache entry: the rotated keys and
    the scaled values, ops/lm_attention.py ``gqa_entry``) of normed inputs
    ``x`` (N, d) at positions ``pos`` (N,); with multipliers, of
    ``attn_in · x``, the keys times ``key``."""
    x = times(x, mup.attn_in)
    q = jnp.einsum("nd,dhe->nhe", x, w["wq"])
    k = jnp.einsum("nd,dge->nge", x, w["wk"]) if mup.key == 1.0 \
        else scaled("nd,dge->nge", x, w["wk"], mup.key)
    v = (jnp.einsum("nd,dge->nge", x, w["wv"], preferred_element_type=F32)
         * a.value_scale).astype(x.dtype)
    r = a.rotary
    return (q[..., r:], att.rope_half(q[..., :r], pos, a.theta),
            att.gqa_entry(k[..., r:], att.rope_half(k[..., :r], pos, a.theta),
                          v))


def indexer(cfg: LMConfig, w: Dict, x, c_q, pos):
    """(q_idx (N, Hi, Di), k_idx (N, Di), w_idx (N, Hi) float32)."""
    a = cfg.full
    q = att.rope_head(jnp.einsum("nr,rhi->nhi", c_q, w["idx_wq_b"]), pos,
                      a.theta, a.rope)
    k = layer_norm(x @ w["idx_wk"], w["idx_k_norm_w"], w["idx_k_norm_b"],
                   cfg.eps)
    k = att.rope_head(k, pos, a.theta, a.rope)
    wt = (x @ w["idx_w"]).astype(F32) \
        * (cfg.idx_heads ** -0.5) * (cfg.idx_dim ** -0.5)
    return q, k, wt


def finish_attention(w: Dict, o, gate, out: float = 1.0):
    """Headwise gate (if the model has one), then the output projection
    (times ``out``, a model's ``attention_out_multiplier``): ``o`` (N, H,
    v)."""
    if gate is not None:
        o = o * gate[..., None].astype(o.dtype)
    if out != 1.0:
        return scaled("nhv,hvd->nd", o, w["wo"], out)
    return jnp.einsum("nhv,hvd->nd", o, w["wo"])


def embed(cfg: LMConfig, ends: Dict, tokens):
    with jax.named_scope("lm/embed"):
        return times(ends["embed"][tokens], cfg.mup.embed)


# -- the state-space mixer (ops/ssm.py), off the same normed input as the
# -- attention: four sibling scopes a layer

def ssm_project(cfg: LMConfig, w: Dict, x):
    """(z (N, inner) the gate, u (N, conv_width) what the convolution
    runs over — both in the stream's dtype — dt (N, heads) float32):
    ``((ssm_in · x) W_in) ⊙ μ``, μ the five ``ssm_multipliers`` over their
    column blocks, accumulated and scaled in float32."""
    m = cfg.ssm
    with jax.named_scope("lm/ssm_proj"):
        by = cfg.mup.ssm_in * np.concatenate([
            np.full(n, f, np.float32) for n, f in zip(m.blocks, cfg.mup.ssm)])
        p = jnp.einsum("nd,dp->np", x, w["in_proj"],
                       preferred_element_type=F32) * by
        return (p[:, :m.inner].astype(x.dtype),
                p[:, m.inner:m.inner + m.conv_width].astype(x.dtype),
                p[:, m.inner + m.conv_width:])


def ssm_split(m: SSMDims, c):
    """The convolution's output (N, conv_width) → (x (N, H, P), B, C
    (N, G, N_state))."""
    n, width = c.shape[0], m.blocks[2]
    return (c[:, :m.inner].reshape(n, m.heads, m.head),
            c[:, m.inner:m.inner + width].reshape(n, m.groups, m.state),
            c[:, m.inner + width:].reshape(n, m.groups, m.state))


def ssm_finish(cfg: LMConfig, w: Dict, y, z, dtype):
    """``ssm_out · (norm(y ⊙ SiLU(z)) W_out)``: ``y`` (N, H, P) float32."""
    m = cfg.ssm
    with jax.named_scope("lm/ssm_out"):
        r = ssm.gated_norm(y.reshape(y.shape[0], m.inner), z, w["norm"],
                           m.groups, cfg.eps).astype(dtype)
        return scaled("ni,id->nd", r, w["out_proj"], cfg.mup.ssm_out)


def ssm_decode(cfg: LMConfig, w: Dict, x, slots, pos, states, conv):
    """One token of each of B rows through a layer's mixer: ``x`` (B, d)
    the normed input, ``states`` (n_slots, H, P, N) and ``conv``
    (n_slots, K − 1, W) the layer's session states → (M (B, d), states,
    conv).  A row at position 0 starts from zeros; a padding row (slot
    −1) moves nothing."""
    m = cfg.ssm
    z, u, dt = ssm_project(cfg, w, x)
    live = slots >= 0
    carried = live & (pos > 0)         # the others start from zeros
    with jax.named_scope("lm/ssm_conv"):
        mine = jnp.where(carried[:, None, None],
                         conv[jnp.maximum(slots, 0)], 0)
        c, mine = ssm.conv_step(u, mine, w["conv_w"], w["conv_b"])
        conv = conv.at[jnp.where(live, slots, conv.shape[0])].set(
            mine, mode="drop")
        xs, Bm, Cm = ssm_split(m, c)
        delta, a = ssm.step_sizes(dt, w["dt_bias"], w["A_log"])
    with jax.named_scope("lm/ssm_update"):
        if pallas_ssm_decode.supported(m.heads, m.head, m.state, m.groups):
            states, y = pallas_ssm_decode.ssm_decode_update(
                states, slots, pos, xs, delta, a, Bm, Cm)
            y = y + w["D"][None, :, None] * xs
        else:
            old = jnp.where(carried[:, None, None, None],
                            states[jnp.maximum(slots, 0)], 0.0)
            y, new = ssm.ssd_step(xs, delta, a, Bm, Cm, w["D"], old)
            states = states.at[jnp.where(live, slots, states.shape[0])].set(
                new, mode="drop")
    return ssm_finish(cfg, w, y, z, x.dtype), states, conv


def ssm_prefill(cfg: LMConfig, w: Dict, x, slot, start, n_valid, states,
                conv):
    """A chunk of one session through a layer's mixer: ``x`` (T, d) of
    which the first ``n_valid`` rows are real, the first at position
    ``start`` (0: the session's states start from zeros) → (M (T, d),
    states, conv), the slot's states those at the last real token (as they
    were where nothing is real)."""
    m = cfg.ssm
    z, u, dt = ssm_project(cfg, w, x)
    fresh = (start == 0) & (n_valid > 0)
    with jax.named_scope("lm/ssm_conv"):
        c, tail = ssm.conv_chunk(u, jnp.where(fresh, 0, conv[slot]),
                                 w["conv_w"], w["conv_b"], n_valid)
        conv = conv.at[slot].set(tail)
        xs, Bm, Cm = ssm_split(m, c)
        delta, _ = ssm.step_sizes(dt, w["dt_bias"], w["A_log"])
    with jax.named_scope("lm/ssm_scan"):
        y, last = ssm.ssd_chunked(
            xs, delta, w["A_log"], Bm, Cm, w["D"],
            jnp.where(fresh, 0.0, states[slot]), m.chunk, n_valid)
        states = states.at[slot].set(last)
    return ssm_finish(cfg, w, y, z, x.dtype), states, conv


def feed_forward(cfg: LMConfig, layer: Dict, x):
    """The layer's MLP or its share of the expert layer; the tokens each
    held expert got (zeros for a dense layer); the experts each token was
    routed to (``None`` for a dense layer)."""
    if "mlp" in layer:
        with jax.named_scope("lm/dense_mlp"):
            return (gated_mlp(x, layer["mlp"], cfg.mup.mlp_gate,
                              cfg.mup.mlp_down),
                    jnp.zeros((cfg.held,), jnp.int32), None)
    y, chosen, counts = moe_held_experts(
        x, layer["moe"], cfg.first_held, cfg.per_tok, cfg.route_scale,
        shared=cfg.f_shared > 0, n_group=cfg.n_group,
        topk_group=cfg.topk_group)
    return y, counts, chosen


def choices(selected, routed) -> Dict:
    """``routed`` (MoE layers, tokens, k): empty for a model of dense
    layers alone."""
    routed = [c for c in routed if c is not None]
    return {"selected": selected,
            "routed": jnp.stack(routed) if routed
            else jnp.zeros((0, 0, 0), jnp.int32)}


def head(cfg: LMConfig, ends: Dict, h):
    with jax.named_scope("lm/head"):
        x = rms_norm(h, ends["final_norm"], cfg.eps)
        logits = jnp.einsum("nd,dv->nv", x, ends["head"],
                            preferred_element_type=F32)
        return logits if cfg.mup.head == 1.0 else logits * cfg.mup.head


# ---------------------------------------------------------------------------
# decode: B rows of any sessions, one token each
# ---------------------------------------------------------------------------

def pack_rows(tokens, slots, pos, tables, owner) -> np.ndarray:
    """A decode step's five integer arguments (:func:`decode_rows`) as ONE
    int32 vector on the host: the tier then makes one transfer a step where
    five took 1.4 ms of a 25 ms cycle (PERF.md, PR 33)."""
    return np.concatenate([np.asarray(a, np.int32).ravel() for a in
                           (tokens, slots, pos, tables, owner)])


def unpack_rows(geo: CacheGeometry, rows):
    """:func:`pack_rows` undone inside the step: B follows from the
    vector's length and the geometry."""
    B = (rows.shape[0] - geo.n_pages) // (3 + geo.max_pages)
    tokens, slots, pos = (rows[i * B:(i + 1) * B] for i in range(3))
    tables = rows[3 * B:3 * B + B * geo.max_pages].reshape(B, geo.max_pages)
    return tokens, slots, pos, tables, rows[rows.shape[0] - geo.n_pages:]


def decode_step(cfg: LMConfig, geo: CacheGeometry, params: Dict,
                cache: Dict, rows):
    """The step as the tier calls it: :func:`decode_rows` over the packed
    arguments of :func:`pack_rows`."""
    return decode_rows(cfg, geo, params, cache, *unpack_rows(geo, rows))


def decode_rows(cfg: LMConfig, geo: CacheGeometry, params: Dict,
                cache: Dict, tokens, slots, pos, tables, owner):
    """One token for each of B rows.  ``tokens`` (B,) ids; ``slots`` (B,)
    the rows' ring slots (−1: a padding row); ``pos`` (B,) the positions
    the tokens stand at; ``tables`` (B, max_pages) the rows' page tables;
    ``owner`` (n_pages,) which row owns each page (−1: none).
    → (cache, logits (B, vocab) float32, expert tokens (n_layers, held),
    choices: ``selected`` (B, topk) positions a full layer, −1 where a row
    has fewer).

    A full layer: the indexer scores every page once, ``select_topk``
    names the row's ``topk`` positions (ascending), their addresses in the
    pool come from the row's page table by a compare-and-sum
    (``selected_addresses``: no lookup a position at a time), and ONE
    gather a layer — the only one left between the selection and the
    softmax — fetches the entries ``mla_selected`` attends to, reading the
    copy once at lane-aligned widths (a Pallas program a layer) and as
    ``mla_absorbed`` does at any other.  A slot past a row's count reads
    the row's first entry, which the mask drops."""
    B = tokens.shape[0]
    live = slots >= 0
    slot = jnp.maximum(slots, 0)
    # a padding row writes to page 0 and to no ring slot
    page = jnp.where(live, tables[jnp.arange(B), pos // geo.page], 0)
    off = pos % geo.page
    lengths = jnp.where(live, pos + 1, 0)
    h = embed(cfg, params["ends"], tokens)
    kv, ik, ring, states, conv = (list(cache[k]) for k in CACHE_KEYS)
    counts, selected, routed = [], [], []
    i_pool = i_full = i_slide = 0
    for i_layer, (layer, kind) in enumerate(zip(params["layers"],
                                                cfg.kinds)):
        a, w = cfg.dims(kind), layer["attn"]
        gqa = isinstance(a, GQADims)
        # a sibling of the attention scopes below, never around one: the
        # readers take the first lm/ name of an op_name (obs/names.py)
        with jax.named_scope("lm/proj"):
            x = rms_norm(h, layer["attn_norm"], cfg.eps)
            if gqa:
                q_plain, q_rot, c = gqa_project(a, w, x, pos, cfg.mup)
                gate = None
            else:
                c_q, c, gate = latents(cfg, a, w, x, pos)
                q_nope, q_rope = queries(a, w, c_q, pos)
        if cfg.ssm:
            mixed, states[i_layer], conv[i_layer] = ssm_decode(
                cfg, layer["ssm"], x, slots, pos, states[i_layer],
                conv[i_layer])
        if kind == FULL:
            with jax.named_scope("lm/indexer"):
                q_idx, k_idx, w_idx = indexer(cfg, w, x, c_q, pos)
                kv[i_pool] = kv[i_pool].at[page, off].set(c)
                ik[i_full] = ik[i_full].at[page, off].set(k_idx)
                by_page = att.index_scores_paged(q_idx, w_idx, ik[i_full],
                                                 owner)
                scores = by_page[tables].reshape(B, geo.max_len)
            with jax.named_scope("lm/select"):
                idx, valid = att.select_topk(scores, lengths, cfg.topk)
                selected.append(jnp.where(valid, idx, -1))
                phys = att.selected_addresses(tables, idx, geo.page)
                chosen = kv[i_pool].reshape(
                    geo.n_pages * geo.page, -1)[phys]
            with jax.named_scope("lm/mla_full"):
                o = att.mla_selected(q_nope, q_rope, chosen, valid,
                                     w["wkv_b"], a.nope, a.rope, a.scale)
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
            i_pool, i_full = i_pool + 1, i_full + 1
        elif kind == CAUSAL:
            with jax.named_scope("lm/cache_write"):
                kv[i_pool] = kv[i_pool].at[page, off].set(c)
            with jax.named_scope("lm/gqa_paged" if gqa else "lm/mla_paged"):
                if gqa:
                    o = att.gqa_paged(q_plain, q_rot, kv[i_pool], tables,
                                      lengths, a.kv_heads, a.v, a.scale)
                else:
                    o = att.mla_paged(q_nope, q_rope, kv[i_pool], tables,
                                      lengths, w["wkv_b"], a.nope, a.rope,
                                      a.scale)
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
            i_pool += 1
        else:
            with jax.named_scope("lm/gqa_window" if gqa else "lm/mla_window"):
                at = jnp.where(live, pos % cfg.window, cfg.window)
                ring[i_slide] = ring[i_slide].at[slot, at].set(
                    c, mode="drop")
                mine = ring[i_slide][slot]                 # (B, W, entry)
                age = (pos[:, None] - jnp.arange(cfg.window)[None, :]) \
                    % cfg.window
                valid = (age <= pos[:, None]) & live[:, None]
                if gqa:
                    o = att.gqa_gathered(q_plain, q_rot, mine, valid,
                                         w.get("sink"), a.kv_heads, a.v,
                                         a.scale)
                else:
                    o = att.mla_absorbed(q_nope, q_rope, mine, valid,
                                         w["wkv_b"], a.nope, a.rope, a.scale)
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
            i_slide += 1
        if cfg.ssm:
            h = h + mixed
        x = rms_norm(h, layer["mlp_norm"], cfg.eps)
        y, n, chosen = feed_forward(cfg, layer, x)
        h = h + y
        counts.append(n)
        routed.append(chosen)
    logits = head(cfg, params["ends"], h)
    return (dict(zip(CACHE_KEYS, (kv, ik, ring, states, conv))), logits,
            jnp.stack(counts), choices(selected, routed))


# ---------------------------------------------------------------------------
# prefill: one session's chunk of T tokens
# ---------------------------------------------------------------------------

def prefill_step(cfg: LMConfig, geo: CacheGeometry, params: Dict,
                 cache: Dict, tokens, slot, start, n_valid, table,
                 pages_per_step: int = 1, q_block: int = 512):
    """A chunk of one session: ``tokens`` (T,) ids of which the first
    ``n_valid`` are real, standing at positions ``start ..``; ``slot`` the
    session's ring slot; ``table`` (max_pages,) its page table.
    → (cache, logits (1, vocab) float32 at the last real token, expert
    tokens (n_layers, held), choices: ``selected`` uint8 (T, max_len / 8)
    bit-packed rows a full layer)."""
    T = tokens.shape[0]
    pos = start + jnp.arange(T)
    real = jnp.arange(T) < n_valid
    page = jnp.where(real, table[pos // geo.page], 0)
    off = pos % geo.page
    h = embed(cfg, params["ends"], tokens)
    kv, ik, ring, states, conv = (list(cache[k]) for k in CACHE_KEYS)
    W = cfg.window
    counts, selected, routed = [], [], []
    i_pool = i_full = i_slide = 0
    for i_layer, (layer, kind) in enumerate(zip(params["layers"],
                                                cfg.kinds)):
        a, w = cfg.dims(kind), layer["attn"]
        gqa = isinstance(a, GQADims)
        # a sibling of the attention scopes below, never around one: the
        # readers take the first lm/ name of an op_name (obs/names.py)
        with jax.named_scope("lm/proj"):
            x = rms_norm(h, layer["attn_norm"], cfg.eps)
            if gqa:
                q_plain, q_rot, c = gqa_project(a, w, x, pos, cfg.mup)
                gate = None
            else:
                c_q, c, gate = latents(cfg, a, w, x, pos)
                q_nope, q_rope = queries(a, w, c_q, pos)
        if cfg.ssm:
            mixed, states[i_layer], conv[i_layer] = ssm_prefill(
                cfg, layer["ssm"], x, slot, start, n_valid, states[i_layer],
                conv[i_layer])
        if kind == FULL:
            with jax.named_scope("lm/indexer"):
                q_idx, k_idx, w_idx = indexer(cfg, w, x, c_q, pos)
                kv[i_pool] = kv[i_pool].at[page, off].set(c)
                ik[i_full] = ik[i_full].at[page, off].set(k_idx)
            with jax.named_scope("lm/mla_full"):
                o, sets = att.prefill_full_attention(
                    q_nope, q_rope, q_idx, w_idx, kv[i_pool], ik[i_full],
                    table, start, n_valid, w["wkv_b"], a.nope, a.rope,
                    a.scale, cfg.topk, pages_per_step,
                    flash=PREFILL_HEADS_PER_STEP)
                selected.append(sets)
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
            i_pool, i_full = i_pool + 1, i_full + 1
        elif kind == CAUSAL:
            with jax.named_scope("lm/cache_write"):
                kv[i_pool] = kv[i_pool].at[page, off].set(c)
            with jax.named_scope("lm/gqa_paged" if gqa else "lm/mla_paged"):
                if gqa:
                    o = att.prefill_gqa_causal(
                        q_plain, q_rot, kv[i_pool], table, start, n_valid,
                        a.kv_heads, a.v, a.scale, pages_per_step)
                else:
                    o = att.prefill_causal_attention(
                        q_nope, q_rope, kv[i_pool], table, start, n_valid,
                        w["wkv_b"], a.nope, a.rope, a.scale, pages_per_step,
                        flash=PREFILL_HEADS_PER_STEP)
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
            i_pool += 1
        else:
            with jax.named_scope("lm/gqa_window" if gqa else "lm/mla_window"):
                prev_pos = start - (W - 1) + jnp.arange(W - 1)
                prev = ring[i_slide][slot, prev_pos % W]
                if gqa:
                    o = att.prefill_gqa_window(
                        q_plain, q_rot, c, prev, prev_pos, start, n_valid,
                        w.get("sink"), a.kv_heads, a.v, a.scale, W,
                        min(q_block, T))
                else:
                    o = att.prefill_window_attention(
                        jnp.concatenate([q_nope, q_rope], -1), c, prev,
                        prev_pos, start, n_valid, w["wkv_b"], a.nope, a.rope,
                        a.scale, W, min(q_block, T))
                h = h + finish_attention(w, o, gate, cfg.mup.attn_out)
                # the chunk's last W real tokens go into the ring; the
                # others (overwritten within the chunk, or padding) are
                # dropped by an index past the end
                keep = real & (jnp.arange(T) >= n_valid - W)
                ring[i_slide] = ring[i_slide].at[
                    slot, jnp.where(keep, pos % W, W)].set(c, mode="drop")
            i_slide += 1
        if cfg.ssm:
            h = h + mixed
        x = rms_norm(h, layer["mlp_norm"], cfg.eps)
        y, n, chosen = feed_forward(cfg, layer, x)
        h = h + y
        counts.append(n)
        routed.append(chosen)
    last = jax.lax.dynamic_slice_in_dim(h, jnp.maximum(n_valid - 1, 0), 1, 0)
    logits = head(cfg, params["ends"], last)
    return (dict(zip(CACHE_KEYS, (kv, ik, ring, states, conv))), logits,
            jnp.stack(counts), choices(selected, routed))


#: the two step programs, jitted once for the process: configuration and
#: geometry are static (two tiers of one model share the compiled step),
#: the cache is donated.  The device trace names them ``jit_decode_step``
#: and ``jit_prefill_step``.
decode_jit = jax.jit(decode_step, static_argnums=(0, 1), donate_argnums=(3,))
prefill_jit = jax.jit(prefill_step, static_argnums=(0, 1),
                      static_argnames=("pages_per_step", "q_block"),
                      donate_argnums=(3,))
