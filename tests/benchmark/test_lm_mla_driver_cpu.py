"""The causal-MLA LM's cell at toy size on the CPU: the driver end to end
through ``harness.drive`` (sessions through the runtime, the window, the
check against ``reference/lm_mla.py``), the faults planted in the PROGRAM
that ``correct`` has to fail, the reference's own controls, and the new
reader on what it can and cannot read."""

import dataclasses
import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_mla_cell_toy import CONFIG, TRAFFIC  # noqa: E402

from benchmarks import flops_lm_mla, harness  # noqa: E402
from benchmarks.drivers import lm_serve_mla  # noqa: E402
from benchmarks.readers import counter_value, lm_cost_roofline  # noqa: E402

CELL = "ax-k1-ep16-decode-ctx1k-40k"


def drive(seed, prepare=None, traffic=None):
    resolved = {"cell": {"name": CELL, "chips": 1}, "config": CONFIG,
                "traffic": traffic or TRAFFIC, "driver": lm_serve_mla}
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
    assert resolved["driver"] is lm_serve_mla
    assert resolved["cell"]["chips"] == 1
    mix = resolved["traffic"]
    assert (mix["sessions"], mix["ctx_min"], mix["ctx_max"], mix["ctx_sum"],
            mix["page"], mix["cache_tokens"], mix["max_len"],
            mix["max_batch"], mix["queue_capacity"], mix["deadline_s"],
            mix["bucket_edges"], mix["prefill_chunk"]) \
        == (64, 1024, 40960, 700000, 512, 896000, 45056, 64, 128, 30.0,
            [1, 256, 2048], 2048)
    assert set(mix["limits"]) == set(TRAFFIC["limits"])
    names = {m["name"] for m in harness.cell_metrics(bench, "per_layer",
                                                     CELL)}
    assert {"step_mfu.lm_mla_serve", "step_hbm_roofline.lm_mla_serve",
            "mla_paged_roofline.lm_mla_serve", "experts_roofline.lm_mla_serve",
            "paged_grid_fill.lm_mla_serve", "step_ms.lm_serve",
            "cache_fill.lm_serve", "batch_fill.serve",
            "device_idle_share.serve"} <= names
    # the other LM's costs read its own keys: not this cell's
    assert not {"step_mfu.lm_serve", "select_roofline.lm_serve",
                "mla_decode_roofline.lm_serve"} & names
    assert {m["name"] for m in harness.cell_metrics(bench, "end_to_end",
                                                    CELL)} \
        == {"serve_throughput", "setup_s"}


# -- planted faults: each has to come out not correct -----------------------

def another_config(monkeypatch, **changes):
    """The program reads a config that differs from the file's."""
    from analytics_zoo_tpu.models import lm

    made = lm.LMConfig.from_dict

    def from_dict(cfg):
        c = made(cfg)
        full = dataclasses.replace(c.full, **changes.get("full", {}))
        return dataclasses.replace(c, full=full, **changes.get("top", {}))

    monkeypatch.setattr(lm.LMConfig, "from_dict", staticmethod(from_dict))
    return lambda driver: None


def attends_to_its_last_positions_only(monkeypatch):
    """Decode attends to a row's last 4 entries."""
    from analytics_zoo_tpu.ops import lm_attention as att

    def truncated(q_nope, q_rope, kv_pool, tables, lengths, wkv_b, nope, r,
                  scale):
        mine = kv_pool[tables].reshape(q_nope.shape[0], -1, kv_pool.shape[2])
        at = jnp.arange(mine.shape[1])[None, :]
        valid = (at < lengths[:, None]) & (at >= lengths[:, None] - 4)
        return att.mla_absorbed(q_nope, q_rope, mine, valid, wkv_b, nope, r,
                                scale)

    monkeypatch.setattr(att, "mla_paged", truncated)
    return lambda driver: None


def yarn_left_out(monkeypatch):
    return another_config(monkeypatch, full={"scaling": None})


def group_limit_left_out(monkeypatch):
    return another_config(monkeypatch, top={"n_group": 1, "topk_group": 1})


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


#: the number that has to catch each fault (others may as well)
CAUGHT_BY = {"attends_to_its_last_positions_only": "logits_rel_rms",
             "yarn_left_out": "logits_rel_rms",
             "group_limit_left_out": "route_miss",
             "one_expert_left_out": "logits_rel_rms",
             "cache_at_the_wrong_position": "logits_rel_rms",
             "operands_in_8_bits": "logits_rel_rms"}
FAULTS = [attends_to_its_last_positions_only, yarn_left_out,
          group_limit_left_out, one_expert_left_out,
          cache_at_the_wrong_position, operands_in_8_bits]


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
    assert not line["correct"] and CAUGHT_BY[fault.__name__] in over, \
        line["checks"]
    if fault is group_limit_left_out:
        # the reference follows the program's experts, so the logits
        # agree: a group limit that is not kept shows in the routing alone
        assert "logits_rel_rms" not in over


def test_controls_fail_and_the_stated_precision_reads_low():
    d = lm_serve_mla.Driver(CONFIG, TRAFFIC, 4100000003,
                            "/tmp/lm_mla_toy_control")
    d.setup()
    w = d.window(0.05, harness.Tracer(False, d.workdir))
    scopes = d.decode_scopes()
    d.free()
    assert harness.judge(d.check())
    c = d.control_readings()
    limits = TRAFFIC["limits"]
    assert set(c) == set(lm_serve_mla.CONTROLS) | {"sessions",
                                                   "program_subset"}
    for name in ("control_int8", "fault_truncate", "fault_shift_cache",
                 "fault_no_yarn", "fault_drop_expert"):
        assert c[name]["logits_rel_rms"] > 100 * limits["logits_rel_rms"], \
            (name, c[name])
    assert c["fault_no_group_limit"]["route_miss"] > 5 * limits["route_miss"]
    assert c["fault_no_group_limit"]["logits_rel_rms"] \
        <= limits["logits_rel_rms"]
    assert c["reference_bf16"]["logits_rel_rms"] \
        < c["control_int8"]["logits_rel_rms"]
    assert all(c["program_subset"][k] <= limits[k]
               for k in c["program_subset"])
    # what the window hands the readers: lm_serve.py's keys
    assert {"lengths", "config", "op_scopes"} <= set(w["lm"])
    gauges = w["counters"]["lm"]["gauges"]
    assert 0 < gauges["lm/paged_pages"] <= gauges["lm/paged_grid_steps"]
    assert {"lm/mla_paged", "lm/experts", "lm/route", "lm/shared_mlp",
            "lm/dense_mlp", "lm/head"} <= set(scopes)
    assert not {"lm/indexer", "lm/select", "lm/mla_full",
                "lm/mla_window"} & set(scopes)


def test_checked_sessions_alone_read_the_same_numbers():
    whole = lm_serve_mla.Driver(CONFIG, TRAFFIC, 4100000005,
                                "/tmp/lm_mla_toy_whole")
    few = lm_serve_mla.Driver(CONFIG, TRAFFIC, 4100000005,
                              "/tmp/lm_mla_toy_few", checked_only=True,
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


# -- the reader --------------------------------------------------------------

class FakeReduction:
    def __init__(self, programs=None, ops=None):
        self.programs = programs or {}
        if ops is not None:
            self.devices = [types.SimpleNamespace(ops=ops)]

    def program(self, name):
        return self.programs.get(name, (0.0, 0))


def real_config():
    with open(os.path.join(harness.HERE, "configs", "ax-k1-ep16.json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LENGTHS = [11000] * 64


def ctx(red, scopes=None, lengths=LENGTHS):
    return {"trace": red, "peaks": PEAKS, "counters": {},
            "window": {"lm": {"config": real_config(), "lengths": lengths,
                              "op_scopes": scopes or {}}}}


@pytest.mark.parametrize("against", ["flops", "bytes", "max"])
def test_cost_reader_reads_the_program_or_nothing(against):
    params = {"module": "flops_lm_mla", "cost": "decode_step",
              "program": "jit_decode_step", "against": against}
    assert lm_cost_roofline.read(ctx(None), params) is None
    assert lm_cost_roofline.read(ctx(FakeReduction()), params) is None
    red = FakeReduction({"jit_decode_step": (0.25, 10)})
    assert lm_cost_roofline.read(dict(ctx(red), window={}), params) is None
    assert lm_cost_roofline.read(ctx(red, lengths=[]), params) is None
    cost = flops_lm_mla.decode_step_cost(real_config(), LENGTHS)
    least = {"flops": cost["flops"] / 197e12, "bytes": cost["bytes"] / 819e9}
    least["max"] = max(least.values())
    got = lm_cost_roofline.read(ctx(red), params)
    assert got == pytest.approx(100 * least[against] * 10 / 0.25)
    assert 0 < got < 100


def test_cost_reader_reads_a_scope_or_nothing():
    params = {"module": "flops_lm_mla", "cost": "mla_paged",
              "program": "jit_decode_step", "scopes": ["lm/mla_paged"],
              "against": "max"}
    ops = [("%lm_decode_mla_paged.3 = bf16[64,64,512] custom-call(...)", 0.0,
            0.06),
           ("%fusion.8 = bf16[64,7168] fusion(...)", 0.1, 0.04),
           ("%fusion.9 = bf16[64,7168] fusion(...)", 0.2, 0.5)]
    programs = {"jit_decode_step": (0.7, 10)}
    # a program without the scope (the parent's), a trace without the
    # scope's operations, a reduction without a device: nothing, not 0
    assert lm_cost_roofline.read(ctx(FakeReduction(programs, ops)),
                                 params) is None
    assert lm_cost_roofline.read(
        ctx(FakeReduction(programs, ops), {"lm/mla_paged": ["fusion.99"]}),
        params) is None
    assert lm_cost_roofline.read(
        ctx(FakeReduction(programs), {"lm/mla_paged": ["fusion.8"]}),
        params) is None
    cost = flops_lm_mla.COSTS["mla_paged"](real_config(), LENGTHS)
    got = lm_cost_roofline.read(
        ctx(FakeReduction(programs, ops),
            {"lm/mla_paged": ["lm_decode_mla_paged.3", "fusion.8"],
             "lm/experts": ["fusion.9"]}), params)
    assert got == pytest.approx(100 * cost["bytes"] / 819e9 * 10 / 0.1)


def test_grid_fill_is_pages_over_grid_steps():
    spec = harness.load_json(harness.HERE, "metrics",
                             "paged_grid_fill.lm_mla_serve.json")
    counters = {"lm": {"gauges": {"lm/paged_pages": 1400.0,
                                  "lm/paged_grid_steps": 1750.0}}}
    assert counter_value.read({"counters": counters}, spec["params"]) \
        == pytest.approx(80.0)
    # the parent's program sets no such gauge: nothing
    assert counter_value.read({"counters": {"lm": {"gauges": {}}}},
                              spec["params"]) is None
