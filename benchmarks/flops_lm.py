"""Operations and bytes of one DECODE step of the decoder LM
(configs/dots3-note-prev-ep8.json), from shapes — whatever implements
them.  ``cfg`` is the configuration file's dict; ``lengths`` the rows'
context lengths (the token being decoded included).

What is counted: every weight the step touches, once (an embedding row a
token; of the held experts only those the step's tokens reach, in
expectation over a uniform router); of the cache what the ALGORITHM needs
— the indexer reads every key of every row's context, attention reads
only the selected latents (at most ``index_topk`` a row), a sliding layer
its window; the experts' products for the (token, expert) pairs routed
here, in expectation ``rows * k * held / published``.  Entries are
counted at their unpadded widths.  Norms, softmax, rotary, gates and the
selection's comparisons count as zero operations.
"""

from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


def _dims(cfg: Dict) -> Dict:
    n = int(cfg["num_hidden_layers"])
    kinds = list(cfg["layer_types"])[:n]
    return dict(
        d=int(cfg["hidden_size"]), kinds=kinds,
        n_full=kinds.count("full_attention"),
        n_swa=kinds.count("sliding_attention"),
        dense=int(cfg["first_k_dense_replace"]),
        H=int(cfg["num_attention_heads"]), qr=int(cfg["q_lora_rank"]),
        kvr=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]), v=int(cfg["v_head_dim"]),
        sH=int(cfg["swa_num_attention_heads"]),
        sqr=int(cfg["swa_q_lora_rank"]), skvr=int(cfg["swa_kv_lora_rank"]),
        snope=int(cfg["swa_qk_nope_head_dim"]),
        srope=int(cfg["swa_qk_rope_head_dim"]), sv=int(cfg["swa_v_head_dim"]),
        W=int(cfg["sliding_window_size"]), Hi=int(cfg["index_n_heads"]),
        Di=int(cfg["index_head_dim"]), topk=int(cfg["index_topk"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        E=int(cfg["expert_share"]["published_experts"]),
        held=int(cfg["n_routed_experts"]),
        k=int(cfg["num_experts_per_tok"]), V=int(cfg["vocab_size"]))


def _attn_params(D: Dict, full: bool) -> int:
    """Parameters of one attention block's projections (indexer apart)."""
    if full:
        H, qr, kvr, nope, rope, v = (D[k] for k in
                                     ("H", "qr", "kvr", "nope", "rope", "v"))
    else:
        H, qr, kvr, nope, rope, v = (D[k] for k in
                                     ("sH", "sqr", "skvr", "snope", "srope",
                                      "sv"))
    d = D["d"]
    return (d * qr + qr * H * (nope + rope) + d * (kvr + rope)
            + kvr * H * (nope + v) + H * v * d + d * H)


def _indexer_params(D: Dict) -> int:
    return D["qr"] * D["Hi"] * D["Di"] + D["d"] * D["Di"] + D["d"] * D["Hi"]


def mla_decode_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One full layer's attention over the selected latents (scope
    ``lm/mla_full``): absorbed scores and values over min(L, topk) entries
    a row, the kv up-projection's two halves, the output projection."""
    D = _dims(cfg)
    B = len(lengths)
    S = sum(min(int(n), D["topk"]) for n in lengths)
    H, kvr, rope, nope, v, d = (D[k] for k in
                                ("H", "kvr", "rope", "nope", "v", "d"))
    flops = 2 * (B * H * nope * kvr + S * H * (kvr + rope) + S * H * kvr
                 + B * H * kvr * v + B * H * v * d)
    bytes_ = BF16 * (S * (kvr + rope) + kvr * H * (nope + v) + H * v * d)
    return {"flops": flops, "bytes": bytes_}


def select_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One full layer's indexer and selection (scopes ``lm/indexer`` +
    ``lm/select``): the index projections, every key of every row's
    context read and scored once, the new entries written, the selected
    latents gathered (read and written once)."""
    D = _dims(cfg)
    B = len(lengths)
    total = sum(int(n) for n in lengths)
    S = sum(min(int(n), D["topk"]) for n in lengths)
    flops = 2 * (B * _indexer_params(D) + total * D["Hi"] * D["Di"])
    bytes_ = BF16 * (_indexer_params(D) + total * D["Di"]
                     + B * (D["Di"] + D["kvr"] + D["rope"])
                     + 2 * S * (D["kvr"] + D["rope"]))
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
    B, d = len(lengths), D["d"]
    win = sum(min(int(n), D["W"]) for n in lengths)
    full_w = _attn_params(D, True) - D["kvr"] * D["H"] * (D["nope"] + D["v"]) \
        - D["H"] * D["v"] * d          # those two are in mla_decode_cost
    flops = bytes_ = 0.0
    for part in (mla_decode_cost(cfg, lengths), select_cost(cfg, lengths)):
        flops += D["n_full"] * part["flops"]
        bytes_ += D["n_full"] * part["bytes"]
    flops += D["n_full"] * 2 * B * full_w
    bytes_ += D["n_full"] * BF16 * full_w
    # sliding layers: projections, absorbed attention over the window
    sH, skvr, srope, snope, sv = (D[k] for k in
                                  ("sH", "skvr", "srope", "snope", "sv"))
    swa_w = _attn_params(D, False)
    flops += D["n_swa"] * 2 * (B * swa_w + B * sH * snope * skvr
                               + win * sH * (2 * skvr + srope))
    bytes_ += D["n_swa"] * BF16 * (swa_w + win * (skvr + srope))
    # feed-forward
    n_moe = len(D["kinds"]) - D["dense"]
    e = experts_cost(cfg, B)
    shared = 3 * d * D["fs"] + d * D["E"]
    flops += D["dense"] * 2 * B * 3 * d * D["f"] \
        + n_moe * (e["flops"] + 2 * B * shared)
    bytes_ += BF16 * D["dense"] * 3 * d * D["f"] \
        + n_moe * (e["bytes"] + BF16 * shared)
    # ends: an embedding row a token, the head, the float32 logits
    flops += 2 * B * d * D["V"]
    bytes_ += BF16 * (B * d + d * D["V"]) + 4 * B * D["V"]
    return {"flops": flops, "bytes": bytes_}


COSTS = {"mla_decode": lambda cfg, lengths: _times(
             mla_decode_cost(cfg, lengths), _dims(cfg)["n_full"]),
         "select": lambda cfg, lengths: _times(
             select_cost(cfg, lengths), _dims(cfg)["n_full"]),
         "experts": lambda cfg, lengths: _times(
             experts_cost(cfg, len(lengths)),
             len(_dims(cfg)["kinds"]) - _dims(cfg)["dense"]),
         "decode_step": decode_step_cost}


def _times(cost: Dict, n: int) -> Dict:
    return {k: v * n for k, v in cost.items()}
