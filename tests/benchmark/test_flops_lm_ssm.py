"""flops_lm_ssm.py at the published widths against ISSUE 39's table and its
cell's reckoning, counted here by hand; and that the configuration's file
keeps what the catalog's row gives."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_ssm_cell_toy import CELL  # noqa: E402

from benchmarks import flops_lm_ssm, harness  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(harness.HERE, "configs",
                           "falcon-h1-34b-pp12.json")) as f:
        return json.load(f)


def test_configuration_keeps_every_published_width(cfg):
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"]) == (5120, 20, 4, 128, 21504)
    assert (cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"], cfg["mamba_expand"]) \
        == (4096, 32, 128, 256, 2, 4, 128, 2)
    assert (cfg["rope_theta"], cfg["rope_scaling"], cfg["rms_norm_eps"],
            cfg["attn_layer_indices"]) == (100000000000, None, 1e-5, None)
    assert (len(cfg["ssm_multipliers"]), len(cfg["mlp_multipliers"])) == (5, 2)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 32640)
    assert cfg["published"]["num_hidden_layers"] == 72 == 12 * 6 \
        and cfg["published"]["vocab_size"] == 261120 == 8 * 32640
    assert (cfg["pipeline"], cfg["job"]) == ("lm", "serve_ssm")
    assert {"deployment", "vocab_share", "assumed", "left_out"} <= set(cfg)
    assert {"mixer_width", "time_step", "state_dtype", "weights"} \
        <= set(cfg["assumed"])


def test_configuration_holds_every_key_of_the_catalog_row(cfg):
    """Every value of the catalog's ``config`` under the same key, but the
    two keys ``reduced`` names (where the catalog is at hand)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differ == set(cfg["reduced"])
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == "falcon-h1-34b-pp12")
    assert entry["source"] == row["source_url"] \
        and entry["reduced"] == cfg["reduced"]


def test_state_update_moves_every_state_twice(cfg):
    """128 rows x 32 x 128 x 256 float32 = 537 MB a layer, once read and
    once written; 6.44 GB a step over the six layers."""
    state = 128 * 32 * 128 * 256
    one = flops_lm_ssm.ssm_update_cost(cfg, 128)
    small = 128 * (2 * 32 * 128 + 2 * 2 * 256)
    assert one["bytes"] == 4 * (2 * state + small)
    assert 2 * 4 * state == 1073741824
    assert one["bytes"] / (2 * 4 * state) < 1.005        # the rest is nothing
    assert one["flops"] == 6 * state
    step = flops_lm_ssm.COSTS["ssm_update"](cfg, [300] * 128)
    assert step["bytes"] == 6 * one["bytes"]
    assert 6.44e9 < step["bytes"] < 6.48e9
    # bytes bind: 1.31 ms a layer at 819 GB/s against 4 us at the MXU's peak
    assert one["bytes"] / 819e9 == pytest.approx(1.31e-3, rel=0.01)
    assert one["flops"] / 197e12 < 1e-5


def test_paged_attention_reads_every_entry_once(cfg):
    lengths = [300, 4096, 1000]
    one = flops_lm_ssm.gqa_paged_cost(cfg, lengths)
    entries = 5396
    assert one["bytes"] == 2 * (entries * 4 * 256 + 20 * 128 * 5120)
    assert one["flops"] == 2 * 20 * (entries * 256 + 3 * 128 * 5120)
    assert flops_lm_ssm.COSTS["gqa_paged"](cfg, lengths)["bytes"] \
        == 6 * one["bytes"]


def test_decode_step_is_the_issue_s_table(cfg):
    """ISSUE 39: a block 430.12 M parameters, the step at 128 rows and
    180-300 k cached tokens 14.2-15.7 GB against 0.70 TFLOP."""
    block = (5120 * (20 + 8) * 128 + 20 * 128 * 5120      # attention
             + 5120 * 9248 + 5120 * 5 + 4096 * 5120       # the mixer
             + 3 * 5120 * 21504)                          # the MLP
    # what the table counts and a step's cost does not: the two block
    # norms, the mixer's norm and its 96 scalars
    assert 430120032 - block == 2 * 5120 + 4096 + 96
    for total, lo, hi in ((180000, 14.1e9, 14.4e9), (300000, 15.5e9, 15.9e9)):
        lengths = [total // 128] * 128
        got = flops_lm_ssm.decode_step_cost(cfg, lengths)
        by_hand = 6 * (2 * block + 1073741824 + 4 * 128 * 9216
                       + 2 * 2 * 128 * 3 * 5120
                       + 2 * sum(lengths) * 1024) \
            + 2 * (128 * 5120 + 5120 * 32640) + 4 * 128 * 32640
        assert got["bytes"] == by_hand
        assert lo < got["bytes"] < hi
        assert 0.67e12 < got["flops"] < 0.75e12
    # the mixer's share of the bytes: its states and its two projections
    got = flops_lm_ssm.decode_step_cost(cfg, [180000 // 128] * 128)
    mixer = 6 * (1073741824 + 2 * (5120 * 9248 + 4096 * 5120))
    assert 0.47 < mixer / got["bytes"] < 0.53


def test_metric_files_name_costs_scopes_and_programs_that_exist():
    mine = [m for m in harness.load_benchmark()["per_layer"]
            if m["name"].endswith(".lm_ssm_serve")]
    assert {m["name"] for m in mine} == {
        "step_mfu.lm_ssm_serve", "step_hbm_roofline.lm_ssm_serve",
        "ssm_update_roofline.lm_ssm_serve", "gqa_paged_roofline.lm_ssm_serve",
        "ssm_mixer_device_ms.lm_ssm_serve"}
    from analytics_zoo_tpu.obs import names

    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_throughput"
        spec = harness.load_json(harness.HERE, "metrics",
                                 m["name"] + ".json")
        assert (spec["layer"], spec["moves"]) == (m["layer"], m["moves"])
        p = spec["params"]
        assert p["program"] == "jit_decode_step"
        if spec["reader"] == "lm_cost_roofline":
            assert p["module"] == "flops_lm_ssm" \
                and p["cost"] in flops_lm_ssm.COSTS
            assert all(s in names.SCOPES for s in p.get("scopes", ()))
        else:
            assert spec["reader"] == "scope_device"
            assert all(f"lm/{s}" in names.SCOPES for s in
                       p["scopes"][len("lm/("):-1].split("|"))
