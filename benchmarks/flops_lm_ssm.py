"""Operations and bytes of one DECODE step of a decoder LM whose every
block is a state-space mixer and grouped-query attention in parallel, then
a dense gated MLP (configs/falcon-h1-34b-pp12.json), from shapes —
whatever implements them.  ``cfg`` is the configuration file's dict;
``lengths`` the rows' context lengths (the token being decoded included).
``flops_lm_gqa.py``'s conventions: every weight the step touches counted
once; of the cache what the ALGORITHM needs — every entry of every row's
context at its own width (KV heads x (key + value)), and every row's
recurrent state ONCE READ AND ONCE WRITTEN at 4 bytes; a state element
costs 6 operations (decay, ``Δ x ⊗ B`` and its add, ``· C`` and its sum);
norms, softmax, rotary and the convolution count as zero operations."""

from __future__ import annotations

from typing import Dict, Sequence

BF16, F32 = 2, 4
STATE_OPS = 6


def _dims(cfg: Dict) -> Dict:
    H = int(cfg["mamba_n_heads"])
    inner = int(cfg.get("mamba_d_ssm")
                or cfg["mamba_expand"] * cfg["hidden_size"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    return dict(
        d=int(cfg["hidden_size"]), layers=int(cfg["num_hidden_layers"]),
        heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), k=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
        H=H, P=inner // H, N=N, G=G, inner=inner,
        conv=int(cfg["mamba_d_conv"]),
        conv_width=inner + 2 * G * N, proj=2 * inner + 2 * G * N + H)


def ssm_update_cost(cfg: Dict, rows: int) -> Dict:
    """One layer's state update (scope ``lm/ssm_update``): every row's
    (H, P, N) float32 state read once and written once — 2 x 4.19 MB a row
    at the published widths — its ``x``, ``B``, ``C`` in and ``y`` out."""
    D = _dims(cfg)
    state = rows * D["H"] * D["P"] * D["N"]
    small = rows * (2 * D["H"] * D["P"] + 2 * D["G"] * D["N"])
    return {"flops": STATE_OPS * state,
            "bytes": F32 * (2 * state + small)}


def gqa_paged_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """One layer's paged attention (scope ``lm/gqa_paged``): every entry of
    every row's context once at 4 x 256, 2 x 20 x 256 operations an entry,
    ``W_o``."""
    D = _dims(cfg)
    rows, entries = len(lengths), sum(int(n) for n in lengths)
    H, G, k, d = D["heads"], D["kv"], D["k"], D["d"]
    return {"flops": 2 * H * (entries * 2 * k + rows * k * d),
            "bytes": BF16 * (entries * G * 2 * k + H * k * d)}


def decode_step_cost(cfg: Dict, lengths: Sequence[int]) -> Dict:
    """The whole decode step for ``len(lengths)`` rows."""
    D = _dims(cfg)
    B, d, n = len(lengths), D["d"], D["layers"]
    attend, update = gqa_paged_cost(cfg, lengths), ssm_update_cost(cfg, B)
    # a block's weights outside W_o: q, k, v; the mixer's two projections
    # and its convolution; the MLP
    weights = d * (D["heads"] + 2 * D["kv"]) * D["k"] \
        + d * D["proj"] + D["conv_width"] * (D["conv"] + 1) \
        + D["inner"] * d + 3 * d * D["f"]
    conv_state = B * (D["conv"] - 1) * D["conv_width"]      # in and out
    flops = n * (attend["flops"] + update["flops"] + 2 * B * weights)
    bytes_ = n * (attend["bytes"] + update["bytes"]
                  + BF16 * (weights + 2 * conv_state))
    # ends: an embedding row a token, the head, the float32 logits
    flops += 2 * B * d * D["V"]
    bytes_ += BF16 * (B * d + d * D["V"]) + F32 * B * D["V"]
    return {"flops": flops, "bytes": bytes_}


def _times(cost: Dict, n: int) -> Dict:
    return {k: v * n for k, v in cost.items()}


#: a whole step's worth of each: what the readers divide device seconds by
COSTS = {"ssm_update": lambda cfg, lengths: _times(
             ssm_update_cost(cfg, len(lengths)), _dims(cfg)["layers"]),
         "gqa_paged": lambda cfg, lengths: _times(
             gqa_paged_cost(cfg, lengths), _dims(cfg)["layers"]),
         "decode_step": decode_step_cost}
