"""The toy size the causal-MLA LM tests run at on the CPU: an
``axk1``-shaped config — no ``layer_types`` (every layer causal MLA over
the whole context), YaRN rotary scaling with a ramp over its 4 pairs, 8
experts in 2 routing groups of which 1 stays, 2 experts held (four
shares), no router bias — every width small."""

import numpy as np

TOY = {
    "hidden_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "rope_theta": 100,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 16, "beta_fast": 1,
                     "beta_slow": 0.1, "mscale": 1, "mscale_all_dim": 1},
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 2, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 2, "topk_group": 1, "topk_method": "none",
    "routed_scaling_factor": 2.5, "vocab_size": 40, "rms_norm_eps": 1e-6,
    "compute_dtype": "float32",
    "expert_share": {"published_experts": 8, "chips": 4, "index": 0},
}


def tokens(seed: int, n: int) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, TOY["vocab_size"], size=n).astype(np.int32)
