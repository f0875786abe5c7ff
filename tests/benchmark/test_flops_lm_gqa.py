"""flops_lm_gqa.py at the published widths against ISSUE 35's table and its
cell's reckoning, counted here by hand."""

import json
import os

import pytest

from benchmarks import flops_lm_gqa, harness

CELL = "mimo-v25-ep16-decode-ctx1k-64k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(harness.HERE, "configs",
                           "mimo-v25-ep16.json")) as f:
        return json.load(f)


def test_configuration_keeps_every_published_width(cfg):
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["swa_head_dim"],
            cfg["swa_v_head_dim"], cfg["sliding_window"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"]) \
        == (4096, 64, 4, 8, 192, 128, 192, 128, 128, 16384, 2048)
    assert (cfg["expert_share"]["published_experts"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"]) == (256, 8, None, None)
    assert (cfg["rope_theta"], cfg["swa_rope_theta"],
            cfg["partial_rotary_factor"], cfg["attention_value_scale"]) \
        == (10000000, 10000, 0.334, 0.707)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19072)
    assert cfg["published"]["num_hidden_layers"] == 48 \
        and cfg["published"]["n_routed_experts"] == 256 \
        and cfg["published"]["vocab_size"] == 152576
    assert cfg["expert_share"]["chips"] == 16
    # the pattern and the list keep their 48 entries; the first 7 are read
    assert len(cfg["hybrid_layer_pattern"]) == 48 == len(cfg["moe_layer_freq"])
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert {"deployment", "vocab_share", "assumed", "left_out"} <= set(cfg)


def test_configuration_holds_every_key_of_the_catalog_row(cfg):
    """Every number of the catalog's ``config`` under the same key, but the
    three keys ``reduced`` names (where the catalog is at hand)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differ == set(cfg["reduced"])


def test_paged_attention_reads_every_entry_once(cfg):
    one = flops_lm_gqa.gqa_paged_cost(cfg, [1000])
    two = flops_lm_gqa.gqa_paged_cost(cfg, [1000, 41000])
    # 41,000 more entries and a row: 4 KV heads x 320 bf16 an entry; 64
    # heads score 192 and accumulate 128 wide; the row's output product
    assert two["bytes"] - one["bytes"] == 2 * 41000 * 1280
    assert two["flops"] - one["flops"] == 2 * 64 * (41000 * 320 + 128 * 4096)
    assert one["bytes"] == 2 * (1000 * 1280 + 64 * 128 * 4096)
    # 2 x 64 x 320 operations on 2,560 bytes: 16 a byte (ISSUE 35)
    assert 2 * 64 * 320 / 2560 == 16


def test_window_attention_reads_the_last_128_of_a_row(cfg):
    short = flops_lm_gqa.window_cost(cfg, [100])
    long_ = flops_lm_gqa.window_cost(cfg, [100, 60000])
    assert long_["bytes"] - short["bytes"] == 2 * 128 * 2560
    assert short["bytes"] == 2 * (100 * 2560 + 64 * 128 * 4096)
    # the mix: every context over 1,024, so 64 x 128 entries a layer; five
    # layers' rings read 0.21 GB a step
    full = flops_lm_gqa.window_cost(cfg, [2000] * 64)
    assert 5 * 2 * 64 * 128 * 2560 == pytest.approx(0.21e9, rel=0.01)
    assert full["bytes"] == 2 * (64 * 128 * 2560 + 64 * 128 * 4096)


def test_experts_are_bound_by_the_weights_they_reach(cfg):
    # one expert: 3 x 4,096 x 2,048 = 25.17 M parameters, 50.3 MB
    per = 3 * 4096 * 2048
    assert per == 25165824
    e = flops_lm_gqa.experts_cost(cfg, 64)
    # 64 rows x 8 of 256 routed, 16 held: 32 pairs here, 2 a held expert
    assert e["flops"] == pytest.approx(2 * 32 * per)
    # 13.8 of the 16 reached in expectation
    reached = 16 * (1 - (255 / 256) ** 512)
    assert reached == pytest.approx(13.84, abs=0.01)
    assert e["bytes"] == pytest.approx(2 * reached * per)
    # 32 tokens a held expert (the 16 chips' tokens) still stay under the
    # weights' bytes: 61 us of bytes against 8 us of the MXU
    assert 2 * per / 819e9 == pytest.approx(61e-6, rel=0.02)
    assert 2 * 32 * per / 197e12 == pytest.approx(8e-6, rel=0.05)


def test_the_step_is_bound_by_bytes_as_the_issue_reckons(cfg):
    lengths = [14063] * 64                   # 0.90 M cached tokens
    c = flops_lm_gqa.decode_step_cost(cfg, lengths)
    # ISSUE 35's table, in bytes: attention blocks 2 x 0.178 + 5 x 0.189 GB
    attn = 2 * (2 * 89128960 + 5 * 94371840)
    assert attn == pytest.approx(1.30e9, rel=0.01)
    kv = 2 * sum(lengths) * 1280 * 2
    assert kv == pytest.approx(4.6e9, rel=0.01)
    rings = 5 * 2 * 64 * 128 * 2560
    experts = 6 * flops_lm_gqa.experts_cost(cfg, 64)["bytes"]
    assert experts == pytest.approx(4.18e9, rel=0.01)
    dense = 2 * 3 * 4096 * 16384
    assert dense == pytest.approx(0.40e9, rel=0.01)
    router = 6 * 2 * 4096 * 256
    ends = 2 * (64 * 4096 + 4096 * 19072) + 4 * 64 * 19072
    assert ends == pytest.approx(0.16e9, rel=0.03)
    assert c["bytes"] == pytest.approx(
        attn + kv + rings + experts + dense + router + ends)
    # a floor of 13.3 ms at 819 GB/s; the operations are a twelfth of it
    assert c["bytes"] / 819e9 == pytest.approx(13.3e-3, rel=0.01)
    assert c["flops"] / 197e12 < 0.1 * c["bytes"] / 819e9
    whole = flops_lm_gqa.COSTS
    assert whole["gqa_paged"](cfg, lengths)["bytes"] \
        == 2 * flops_lm_gqa.gqa_paged_cost(cfg, lengths)["bytes"]
    assert whole["window"](cfg, lengths)["bytes"] \
        == 5 * flops_lm_gqa.window_cost(cfg, lengths)["bytes"]
    assert whole["experts"](cfg, lengths)["bytes"] == experts
    assert whole["decode_step"](cfg, lengths) == c


@pytest.mark.parametrize("metric,cost,scope,against", [
    ("step_mfu.lm_gqa_serve", "decode_step", None, "flops"),
    ("step_hbm_roofline.lm_gqa_serve", "decode_step", None, "bytes"),
    ("gqa_paged_roofline.lm_gqa_serve", "gqa_paged", "lm/gqa_paged", "max"),
    ("window_roofline.lm_gqa_serve", "window", "lm/gqa_window", "max"),
    ("experts_roofline.lm_gqa_serve", "experts", "lm/experts", "max"),
])
def test_metric_files_name_the_costs_and_the_scopes(metric, cost, scope,
                                                    against):
    spec = harness.load_json(harness.HERE, "metrics", metric + ".json")
    p = spec["params"]
    assert spec["reader"] == "lm_cost_roofline"
    assert (p["module"], p["cost"], p["program"], p["against"]) \
        == ("flops_lm_gqa", cost, "jit_decode_step", against)
    assert p.get("scopes") == ([scope] if scope else None)
    assert cost in flops_lm_gqa.COSTS
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == [CELL] and entry["layer"] == spec["layer"] \
        and entry["moves"] == "serve_throughput"
