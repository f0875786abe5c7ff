"""The toy size the LM tests run at on the CPU: every width small, 8
experts of which 4 are held (two shares), 2 full + 3 sliding layers in
the published pattern's order, window 5, top-k 4 — with the keys of the
published config that switch on its headwise gates, its latents' rescale
and its router's bias (models/lm.py::LMConfig.from_dict)."""

import numpy as np

TOY = {
    "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "first_k_dense_replace": 1,
    "attention_gate_type": "headwise", "apply_mla_qkv_lora_rescale": True,
    "topk_method": "noaux_tc", "rope_scaling": None,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 80000000,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 16,
    "swa_kv_lora_rank": 12, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8, "swa_rope_theta": 50000,
    "sliding_window_size": 5,
    "index_n_heads": 4, "index_head_dim": 8, "index_topk": 4,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1, "vocab_size": 40, "rms_norm_eps": 1e-5,
    "compute_dtype": "float32",
    "expert_share": {"published_experts": 8, "chips": 2, "index": 0},
}


def tokens(seed: int, n: int) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, TOY["vocab_size"], size=n).astype(np.int32)
