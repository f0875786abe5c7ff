"""The grouped-query LM's cell at toy size on the CPU: the driver end to
end through ``harness.drive`` (sessions through the runtime, the window,
the check against ``reference/lm_gqa.py``), the faults planted in the
PROGRAM that ``correct`` has to fail, the reference's own controls, and
what the benchmark's files say of the cell."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_gqa_cell_toy import CONFIG, TRAFFIC  # noqa: E402

from benchmarks import harness  # noqa: E402
from benchmarks.drivers import lm_serve_gqa  # noqa: E402

CELL = "mimo-v25-ep16-decode-ctx1k-64k"


def drive(seed, prepare=None, traffic=None):
    resolved = {"cell": {"name": CELL, "chips": 1}, "config": CONFIG,
                "traffic": traffic or TRAFFIC, "driver": lm_serve_gqa}
    return harness.drive(resolved, harness.load_benchmark(), seed, 0.05,
                         False, time.monotonic(), harness.describe_device(),
                         prepare=prepare)


def test_cell_runs_correct_through_sessions():
    line = drive(4100000001)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_throughput", "setup_s"}
    assert set(line["checks"]) == set(TRAFFIC["limits"]) == {
        "logits_rel_rms", "logits_max_gap", "route_miss", "choices_missing"}


def test_benchmark_names_the_cell_its_files_and_its_metrics():
    bench = harness.load_benchmark()
    resolved = harness.resolve_cell(bench, CELL)
    assert resolved["driver"] is lm_serve_gqa
    assert resolved["cell"]["chips"] == 1
    mix = resolved["traffic"]
    assert (mix["sessions"], mix["ctx_min"], mix["ctx_max"], mix["ctx_sum"],
            mix["ctx_sum_tolerance"], mix["page"], mix["cache_tokens"],
            mix["max_len"], mix["max_batch"], mix["queue_capacity"],
            mix["deadline_s"], mix["bucket_edges"], mix["prefill_chunk"],
            mix["trace_after_steps"], mix["trace_steps"]) \
        == (64, 1024, 65536, 900000, 0.005, 512, 1075200, 69632, 64, 128,
            30.0, [1, 256, 2048], 2048, 50, 20)
    other = harness.load_json(harness.HERE, "traffic",
                              "sessions64-ctx1k-64k.json")
    assert all(mix[k] == other[k] for k in mix if k.startswith("check_"))
    assert set(mix["limits"]) == set(TRAFFIC["limits"])
    names = {m["name"] for m in harness.cell_metrics(bench, "per_layer",
                                                     CELL)}
    assert {"step_mfu.lm_gqa_serve", "step_hbm_roofline.lm_gqa_serve",
            "gqa_paged_roofline.lm_gqa_serve", "window_roofline.lm_gqa_serve",
            "experts_roofline.lm_gqa_serve", "paged_grid_fill.lm_mla_serve",
            "step_ms.lm_serve", "cache_fill.lm_serve",
            "expert_load_max_over_mean.lm_serve", "batch_fill.serve",
            "device_idle_share.serve"} <= names
    # the other LMs' costs read their own keys: not this cell's
    assert not {"step_mfu.lm_serve", "step_mfu.lm_mla_serve",
                "mla_paged_roofline.lm_mla_serve",
                "select_roofline.lm_serve"} & names
    assert {m["name"] for m in harness.cell_metrics(bench, "end_to_end",
                                                    CELL)} \
        == {"serve_throughput", "setup_s"}
    assert len(bench["workloads"]) == 5 \
        and all(w["chips"] == 1 for w in bench["workloads"])


# -- planted faults: each has to come out not correct -----------------------

def another_config(monkeypatch, **changes):
    """The program reads a config that differs from the file's."""
    from analytics_zoo_tpu.models import lm

    made = lm.LMConfig.from_dict

    def from_dict(cfg):
        c = made(cfg)
        return dataclasses.replace(
            c, full=dataclasses.replace(c.full, **changes.get("full", {})),
            swa=dataclasses.replace(c.swa, **changes.get("swa", {})),
            **changes.get("top", {}))

    monkeypatch.setattr(lm.LMConfig, "from_dict", staticmethod(from_dict))
    return lambda driver: None


def global_layers_attend_to_their_last_positions_only(monkeypatch):
    """Decode of a global layer attends to a row's last 4 entries."""
    from analytics_zoo_tpu.ops import lm_attention as att

    def truncated(q_plain, q_rot, kv_pool, tables, lengths, G, dv, scale):
        mine = kv_pool[tables].reshape(q_plain.shape[0], -1,
                                       kv_pool.shape[2])
        at = jnp.arange(mine.shape[1])[None, :]
        valid = (at < lengths[:, None]) & (at >= lengths[:, None] - 4)
        return att.gqa_gathered(q_plain, q_rot, mine, valid, None, G, dv,
                                scale)

    monkeypatch.setattr(att, "gqa_paged", truncated)
    return lambda driver: None


def sink_left_out(monkeypatch):
    from analytics_zoo_tpu.ops import lm_attention as att

    monkeypatch.setattr(att, "softmax_sink",
                        lambda s, sink: jax.nn.softmax(s, -1))
    return lambda driver: None


def value_scale_left_out(monkeypatch):
    return another_config(monkeypatch, full={"value_scale": 1.0},
                          swa={"value_scale": 1.0})


def every_dim_turned(monkeypatch):
    return another_config(monkeypatch, full={"rotary": 12},
                          swa={"rotary": 12})


def window_layers_at_the_global_base(monkeypatch):
    return another_config(monkeypatch, swa={"theta": 1000.0})


def pairs_of_neighbours(monkeypatch):
    """The latent family's rotary layout in this family's place."""
    from analytics_zoo_tpu.ops import lm_attention as att

    monkeypatch.setattr(att, "rope_half", att.rope)
    return lambda driver: None


def one_expert_left_out(monkeypatch):
    def sabotage(driver):
        for layer in driver.model.params["layers"]:
            if "moe" in layer:
                e = layer["moe"]["experts"]
                e["w_down"] = e["w_down"].at[0].set(0.0)
    return sabotage


def cache_at_the_wrong_position(monkeypatch):
    """Every decoded token written one position late."""
    def sabotage(driver):
        tier = driver.tiers[0]
        inner = tier.forward

        def forward(batch):
            if np.asarray(batch["input"]).shape[1] == 1:
                live = sorted(tier.books.slot_of.values())
                tier.books.length[live] += 1
            return inner(batch)
        tier.forward = forward
    return sabotage


def operands_in_8_bits(monkeypatch):
    def round8(a):
        if a.ndim < 2:
            return a
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
        return (jnp.round(a / scale) * scale).astype(a.dtype)

    def sabotage(driver):
        driver.model.params = jax.tree_util.tree_map(
            round8, driver.model.params)
    return sabotage


FAULTS = [global_layers_attend_to_their_last_positions_only, sink_left_out,
          value_scale_left_out, every_dim_turned,
          window_layers_at_the_global_base, pairs_of_neighbours,
          one_expert_left_out, cache_at_the_wrong_position,
          operands_in_8_bits]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault, monkeypatch):
    # a geometry of its own, so that no step compiled without the fault
    # is found in the process's jit cache
    traffic = dict(TRAFFIC, cache_tokens=TRAFFIC["cache_tokens"]
                   + 4 * (1 + FAULTS.index(fault)))
    sabotage = fault(monkeypatch)

    def prepare(driver):
        driver.sabotage = sabotage
    line = drive(4100000002, prepare, traffic)
    over = {k: c for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]}
    assert not line["correct"] and "logits_rel_rms" in over, line["checks"]


def test_controls_fail_and_the_stated_precision_reads_low():
    d = lm_serve_gqa.Driver(CONFIG, TRAFFIC, 4100000003,
                            "/tmp/lm_gqa_toy_control")
    d.setup()
    w = d.window(0.05, harness.Tracer(False, d.workdir))
    scopes = d.decode_scopes()
    d.free()
    assert harness.judge(d.check())
    c = d.control_readings()
    limits = TRAFFIC["limits"]
    assert set(c) == set(lm_serve_gqa.CONTROLS) | {"sessions",
                                                   "program_subset"}
    for name in set(lm_serve_gqa.CONTROLS) - {"reference_bf16"}:
        assert c[name]["logits_rel_rms"] > 100 * limits["logits_rel_rms"], \
            (name, c[name])
    assert c["reference_bf16"]["logits_rel_rms"] \
        < c["control_int8"]["logits_rel_rms"]
    assert all(c["program_subset"][k] <= limits[k]
               for k in c["program_subset"])
    # what the window hands the readers: lm_serve.py's keys
    assert {"lengths", "config", "op_scopes"} <= set(w["lm"])
    gauges = w["counters"]["lm"]["gauges"]
    assert 0 < gauges["lm/paged_pages"] <= gauges["lm/paged_grid_steps"]
    assert 0 < gauges["lm/ring_tokens"] \
        <= TRAFFIC["sessions"] * CONFIG["sliding_window"]
    assert {"lm/gqa_paged", "lm/gqa_window", "lm/experts", "lm/route",
            "lm/dense_mlp", "lm/head"} <= set(scopes)
    assert not {"lm/indexer", "lm/select", "lm/mla_full", "lm/mla_window",
                "lm/mla_paged", "lm/shared_mlp"} & set(scopes)


def test_checked_sessions_alone_read_the_same_numbers():
    whole = lm_serve_gqa.Driver(CONFIG, TRAFFIC, 4100000005,
                                "/tmp/lm_gqa_toy_whole")
    few = lm_serve_gqa.Driver(CONFIG, TRAFFIC, 4100000005,
                              "/tmp/lm_gqa_toy_few", checked_only=True,
                              controls={"4100000005": ["control_int8"]})
    for d in (whole, few):
        d.setup()
        d.window(0.05, harness.Tracer(False, d.workdir))
        d.free()
        assert harness.judge(d.check())
    assert sorted(few.sids) == sorted(few.checked) == sorted(whole.checked)
    assert len(whole.sids) == TRAFFIC["sessions"]
    for c in whole.checked:
        np.testing.assert_allclose(few.window_rows()[c],
                                   whole.window_rows()[c], atol=2e-5)
    assert set(few.control_readings()) == {"sessions", "control_int8",
                                           "program_subset"}
