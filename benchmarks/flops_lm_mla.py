"""Operations and bytes of one DECODE step of a decoder LM whose every
layer is causal latent attention over the whole context
(configs/ax-k1-ep16.json), from shapes — whatever implements them.
``cfg`` is the configuration file's dict; ``lengths`` the rows' context
lengths (the token being decoded included).  ``flops_lm.py``'s
conventions: every weight the step touches counted once (of the held
experts those the step's tokens reach, in expectation over a uniform
router); of the cache what the ALGORITHM needs — here EVERY entry of every
row's context, a layer — at its unpadded width (576, not the pool's 640);
norms, softmax, rotary and the router's comparisons count as zero
operations."""

from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


def _dims(cfg: Dict) -> Dict:
    return dict(
        d=int(cfg["hidden_size"]), layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        H=int(cfg["num_attention_heads"]), qr=int(cfg["q_lora_rank"]),
        kvr=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]), v=int(cfg["v_head_dim"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        E=int(cfg["expert_share"]["published_experts"]),
        held=int(cfg["n_routed_experts"]),
        k=int(cfg["num_experts_per_tok"]), V=int(cfg["vocab_size"]))


def mla_paged_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One layer's paged attention (scope ``lm/mla_paged``): every head's
    absorbed score (576 wide) and value (512 wide) against every entry of
    every row's context, the absorb and value products of ``wkv_b``, the
    output projection; every entry read once, ``wkv_b`` and ``wo`` once."""
    D = _dims(cfg)
    B, total = len(lengths), sum(int(n) for n in lengths)
    H, kvr, rope, nope, v, d = (D[k] for k in
                                ("H", "kvr", "rope", "nope", "v", "d"))
    flops = 2 * H * (total * (kvr + rope + kvr)
                     + B * (nope * kvr + kvr * v + v * d))
    bytes_ = BF16 * (total * (kvr + rope) + kvr * H * (nope + v) + H * v * d)
    return {"flops": flops, "bytes": bytes_}


def experts_cost(cfg: Dict, rows: int) -> Dict:
    """One expert layer's held experts (scope ``lm/experts``): the
    products of the pairs routed here and the weights of the experts they
    reach, both in expectation over a uniform router."""
    D = _dims(cfg)
    pairs = rows * D["k"] * D["held"] / D["E"]
    reached = D["held"] * (1.0 - (1.0 - 1.0 / D["E"]) ** (rows * D["k"]))
    per_expert = 3 * D["d"] * D["fe"]
    return {"flops": 2 * pairs * per_expert,
            "bytes": BF16 * reached * per_expert}


def decode_step_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """The whole decode step for ``len(lengths)`` rows."""
    D = _dims(cfg)
    B, d, n = len(lengths), D["d"], D["layers"]
    # the projections into the latents and the queries (the rest of an
    # attention block is in mla_paged_cost)
    proj = d * D["qr"] + D["qr"] * D["H"] * (D["nope"] + D["rope"]) \
        + d * (D["kvr"] + D["rope"])
    paged = mla_paged_cost(cfg, lengths)
    flops = n * (paged["flops"] + 2 * B * proj)
    bytes_ = n * (paged["bytes"] + BF16 * proj)
    n_moe = n - D["dense"]
    e = experts_cost(cfg, B)
    shared = 3 * d * D["fs"] + d * D["E"]          # shared expert, router
    flops += D["dense"] * 2 * B * 3 * d * D["f"] \
        + n_moe * (e["flops"] + 2 * B * shared)
    bytes_ += BF16 * D["dense"] * 3 * d * D["f"] \
        + n_moe * (e["bytes"] + BF16 * shared)
    # ends: an embedding row a token, the head, the float32 logits
    flops += 2 * B * d * D["V"]
    bytes_ += BF16 * (B * d + d * D["V"]) + 4 * B * D["V"]
    return {"flops": flops, "bytes": bytes_}


def _times(cost: Dict, n: int) -> Dict:
    return {k: v * n for k, v in cost.items()}


#: a whole step's worth of each: what the readers divide device seconds by
COSTS = {"mla_paged": lambda cfg, lengths: _times(
             mla_paged_cost(cfg, lengths), _dims(cfg)["layers"]),
         "experts": lambda cfg, lengths: _times(
             experts_cost(cfg, len(lengths)),
             _dims(cfg)["layers"] - _dims(cfg)["dense"]),
         "decode_step": decode_step_cost}
