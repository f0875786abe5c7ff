"""The toy size the grouped-query LM tests run at on the CPU: a
``mimo_v2``-shaped config — ``hybrid_layer_pattern`` (0 a global layer, 1
a window layer) and a ``moe_layer_freq`` LIST in place of ``layer_types``
and ``first_k_dense_replace``; 4 query heads on 2 KV heads in the global
layers and on 4 in the window layers, keys 12 and values 8 wide, rotary on
the first ``int(0.334 * 12)`` = 4 dims with a base a kind, window 5 with a
learned sink a head, values times 0.707; 8 experts of which 2 are held
(four shares), a router bias, NO shared expert, no scaling factor — every
width small."""

import numpy as np

TOY = {
    "hidden_size": 32, "num_hidden_layers": 5,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 12,
    "v_head_dim": 8, "rope_theta": 1000,
    "swa_num_attention_heads": 4, "swa_num_key_value_heads": 4,
    "swa_head_dim": 12, "swa_v_head_dim": 8, "swa_rope_theta": 10,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "sliding_window": 5, "sliding_window_size": 5,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 2, "n_shared_experts": None,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": None, "scoring_func": "sigmoid",
    "vocab_size": 40, "layernorm_epsilon": 1e-5,
    "compute_dtype": "float32",
    "expert_share": {"published_experts": 8, "chips": 4, "index": 0},
}


def tokens(seed: int, n: int) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, TOY["vocab_size"], size=n).astype(np.int32)
