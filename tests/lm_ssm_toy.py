"""The toy size the state-space LM tests run at on the CPU: a
``falcon_h1``-shaped config — every block a Mamba-2 mixer (4 heads of 8
channels in 2 groups, a state of 16 a channel, a convolution 4 wide,
prefill in blocks of 8 tokens) AND grouped-query attention (10 query heads
on 2 KV heads: 5 a KV head, as published; rotary on all 8 dims of a head)
off one norm, then a dense gated MLP; no experts at all; every one of the
fourteen multipliers off 1 — every width small."""

import numpy as np

TOY = {
    "model_type": "falcon_h1", "hidden_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 1000.0, "rope_scaling": None, "attn_layer_indices": None,
    "intermediate_size": 48, "vocab_size": 40, "rms_norm_eps": 1e-5,
    "mamba_d_ssm": 32, "mamba_n_heads": 4, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_use_mlp": True,
    "embedding_multiplier": 1.7, "attention_in_multiplier": 0.9,
    "key_multiplier": 0.3, "attention_out_multiplier": 0.6,
    "ssm_in_multiplier": 0.8, "ssm_multipliers": [0.7, 0.5, 0.6, 1.3, 0.9],
    "ssm_out_multiplier": 0.4, "mlp_multipliers": [0.75, 0.35],
    "lm_head_multiplier": 0.25,
    "compute_dtype": "float32",
}


def tokens(seed: int, n: int) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, TOY["vocab_size"], size=n).astype(np.int32)
