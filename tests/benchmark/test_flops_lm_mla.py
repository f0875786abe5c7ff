"""flops_lm_mla.py at the published widths against ISSUE 33's table and its
cell's reckoning, counted here by hand."""

import json
import os

import pytest

from benchmarks import flops_lm_mla, harness


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(harness.HERE, "configs", "ax-k1-ep16.json")) as f:
        return json.load(f)


def test_configuration_keeps_every_published_width(cfg):
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"]) \
        == (7168, 64, 1536, 512, 64, 128, 128, 18432, 2048)
    assert (cfg["expert_share"]["published_experts"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"]) == (192, 8, 8, 4, 2.5)
    assert cfg["rope_scaling"]["factor"] == 32 \
        and cfg["rope_scaling"]["original_max_position_embeddings"] == 4096
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 12, 20480)
    assert cfg["published"]["num_hidden_layers"] == 61 \
        and cfg["published"]["n_routed_experts"] == 192 \
        and cfg["published"]["vocab_size"] == 163840
    assert cfg["expert_share"]["chips"] == 16
    assert "layer_types" not in cfg and "attention_gate_type" not in cfg \
        and "apply_mla_qkv_lora_rescale" not in cfg


def test_paged_attention_reads_every_entry_once(cfg):
    one = flops_lm_mla.mla_paged_cost(cfg, [1000])
    two = flops_lm_mla.mla_paged_cost(cfg, [1000, 41000])
    # 40,000 more entries and a row: 576 bf16 an entry; 64 heads score 576
    # and accumulate 512 wide; the row's absorb, value and output products
    assert two["bytes"] - one["bytes"] == 2 * 41000 * 576
    assert two["flops"] - one["flops"] == 2 * 64 * (
        41000 * (576 + 512) + 128 * 512 + 512 * 128 + 128 * 7168)
    # wkv_b 512 x 64 x 256 and wo 64 x 128 x 7,168, once
    assert one["bytes"] == 2 * (1000 * 576 + 512 * 64 * 256 + 64 * 128 * 7168)
    # 139 k operations a cached entry and layer (ISSUE 33)
    assert 2 * 64 * (576 + 512) == 139264


def test_experts_are_bound_by_the_weights_they_reach(cfg):
    # one expert: 3 x 7,168 x 2,048 = 44.04 M parameters, 88 MB
    e = flops_lm_mla.experts_cost(cfg, 64)
    per = 3 * 7168 * 2048
    assert per == 44040192
    # 64 rows x 8 of 192 routed, 12 held: 32 pairs here in expectation
    assert e["flops"] == pytest.approx(2 * 32 * per)
    reached = 12 * (1 - (191 / 192) ** 512)
    assert 11 < reached < 12
    assert e["bytes"] == pytest.approx(2 * reached * per)


def test_decode_step_is_the_issues_reckoning(cfg):
    """0.70 M cached tokens in 64 rows: 4.0 GB of latents beside 6.7 GB of
    weights a step, 13 ms of the memory's bandwidth; the attention's
    0.49 TFLOP are 2.5 ms of the MXU."""
    lengths = [700000 // 64] * 64
    step = flops_lm_mla.decode_step_cost(cfg, lengths)
    paged = flops_lm_mla.COSTS["mla_paged"](cfg, lengths)
    latents = 2 * sum(lengths) * 576 * 5
    assert latents == pytest.approx(4.03e9, rel=0.01)
    weights = step["bytes"] - latents
    assert 6.3e9 < weights < 6.9e9
    assert step["bytes"] / 819e9 == pytest.approx(13e-3, rel=0.05)
    rows = 5 * 64 * 2 * 64 * (128 * 512 + 512 * 128 + 128 * 7168)
    assert paged["flops"] - rows == pytest.approx(0.49e12, rel=0.02)
    assert (paged["flops"] - rows) / 197e12 == pytest.approx(2.5e-3, rel=0.03)
    # bytes bind the kernel's roofline and the step's
    assert paged["bytes"] / 819e9 > paged["flops"] / 197e12
    assert step["bytes"] / 819e9 > 3 * step["flops"] / 197e12
    # MLA of one layer: 101.1 M parameters (the issue's table)
    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 7168
    assert mla == pytest.approx(101.1e6, rel=0.002)
    experts = flops_lm_mla.COSTS["experts"](cfg, lengths)
    assert experts["bytes"] == pytest.approx(
        4 * flops_lm_mla.experts_cost(cfg, 64)["bytes"])
