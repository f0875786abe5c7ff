"""Operations and bytes of one DECODE step of a decoder LM of grouped-query
attention in global layers (the whole context out of a paged pool) and
window layers (a ring a session) (configs/mimo-v25-ep16.json), from shapes
— whatever implements them.  ``cfg`` is the configuration file's dict;
``lengths`` the rows' context lengths (the token being decoded included).
``flops_lm_mla.py``'s conventions: every weight the step touches counted
once (of the held experts those the step's tokens reach, in expectation
over a uniform router); of the cache what the ALGORITHM needs — every
entry of every row's context in a global layer, the last ``window`` in a
window layer — at its own width (KV heads x (key + value)); norms,
softmax, rotary and the router's comparisons count as zero operations."""

from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


def _dims(cfg: Dict) -> Dict:
    n = int(cfg["num_hidden_layers"])
    freq = [int(f) for f in cfg["moe_layer_freq"]][:n]

    def kind(p):
        return dict(H=int(cfg[p + "num_attention_heads"]),
                    G=int(cfg[p + "num_key_value_heads"]),
                    k=int(cfg[p + "head_dim"]), v=int(cfg[p + "v_head_dim"]))
    return dict(
        d=int(cfg["hidden_size"]), layers=n,
        window_layers=sum(1 for g in cfg["hybrid_layer_pattern"][:n] if g),
        dense=freq.index(1) if 1 in freq else n,
        glob=kind(""), swa=kind("swa_"), window=int(cfg["sliding_window"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        E=int(cfg["expert_share"]["published_experts"]),
        held=int(cfg["n_routed_experts"]),
        k=int(cfg["num_experts_per_tok"]), V=int(cfg["vocab_size"]))


def _attend_cost(a: Dict, d: int, rows: int, entries: int) -> Dict:
    """Every head's score (key wide) and weighted sum (value wide) against
    ``entries`` cache entries of ``G (k + v)`` read once, and the output
    projection of ``rows`` tokens."""
    H, G, k, v = a["H"], a["G"], a["k"], a["v"]
    return {"flops": 2 * H * (entries * (k + v) + rows * v * d),
            "bytes": BF16 * (entries * G * (k + v) + H * v * d)}


def gqa_paged_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One global layer's paged attention (scope ``lm/gqa_paged``): every
    entry of every row's context once at 4 x 320, 2 x 64 x 320 operations
    an entry, ``W_o``."""
    D = _dims(cfg)
    return _attend_cost(D["glob"], D["d"], len(lengths),
                        sum(int(n) for n in lengths))


def window_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One window layer's attention out of the rings (scope
    ``lm/gqa_window``): a row's last ``window`` entries at 8 x 320,
    ``W_o``."""
    D = _dims(cfg)
    return _attend_cost(D["swa"], D["d"], len(lengths),
                        sum(min(int(n), D["window"]) for n in lengths))


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
    flops = bytes_ = 0
    for a, cost, count in (
            (D["glob"], gqa_paged_cost, n - D["window_layers"]),
            (D["swa"], window_cost, D["window_layers"])):
        # the projections into queries, keys and values (the rest of an
        # attention block is in the kind's cost)
        proj = d * (a["H"] * a["k"] + a["G"] * (a["k"] + a["v"]))
        attend = cost(cfg, lengths)
        flops += count * (attend["flops"] + 2 * B * proj)
        bytes_ += count * (attend["bytes"] + BF16 * proj)
    n_moe = n - D["dense"]
    e = experts_cost(cfg, B)
    router = d * D["E"]                         # no shared expert
    flops += D["dense"] * 2 * B * 3 * d * D["f"] \
        + n_moe * (e["flops"] + 2 * B * router)
    bytes_ += BF16 * D["dense"] * 3 * d * D["f"] \
        + n_moe * (e["bytes"] + BF16 * router)
    # ends: an embedding row a token, the head, the float32 logits
    flops += 2 * B * d * D["V"]
    bytes_ += BF16 * (B * d + d * D["V"]) + 4 * B * D["V"]
    return {"flops": flops, "bytes": bytes_}


def _times(cost: Dict, n: int) -> Dict:
    return {k: v * n for k, v in cost.items()}


#: a whole step's worth of each: what the readers divide device seconds by
COSTS = {"gqa_paged": lambda cfg, lengths: _times(
             gqa_paged_cost(cfg, lengths),
             _dims(cfg)["layers"] - _dims(cfg)["window_layers"]),
         "window": lambda cfg, lengths: _times(
             window_cost(cfg, lengths), _dims(cfg)["window_layers"]),
         "experts": lambda cfg, lengths: _times(
             experts_cost(cfg, len(lengths)),
             _dims(cfg)["layers"] - _dims(cfg)["dense"]),
         "decode_step": decode_step_cost}
