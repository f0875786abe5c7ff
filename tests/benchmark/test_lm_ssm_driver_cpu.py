"""The state-space LM's cell at toy size on the CPU: the driver end to end
through ``harness.drive`` (sessions through the runtime, the window, the
states handed over when it has closed, the check against
``reference/lm_ssm.py``), every fault of the reference's list planted in
the PROGRAM — ``correct`` has to fail each — the reference's own controls,
and what the benchmark's files say of the cell."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_ssm_cell_toy import CELL, CONFIG, TRAFFIC  # noqa: E402

from benchmarks import harness  # noqa: E402
from benchmarks.drivers import lm_serve_ssm  # noqa: E402
from benchmarks.reference import lm_ssm as ref  # noqa: E402


def drive(seed, prepare=None, traffic=None):
    resolved = {"cell": {"name": CELL, "chips": 1}, "config": CONFIG,
                "traffic": traffic or TRAFFIC, "driver": lm_serve_ssm}
    return harness.drive(resolved, harness.load_benchmark(), seed, 0.05,
                         False, time.monotonic(), harness.describe_device(),
                         prepare=prepare)


def test_cell_runs_correct_through_sessions():
    line = drive(4100000001)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_throughput", "setup_s"}
    assert set(line["checks"]) == set(TRAFFIC["limits"]) == {
        "logits_rel_rms", "logits_max_gap", "state_rel_rms",
        "state_layer0_worst_head"}


def test_benchmark_names_the_cell_its_files_and_its_metrics():
    bench = harness.load_benchmark()
    resolved = harness.resolve_cell(bench, CELL)
    assert resolved["driver"] is lm_serve_ssm
    assert resolved["cell"]["chips"] == 1
    mix = resolved["traffic"]
    assert (mix["sessions"], mix["ctx_min"], mix["ctx_max"],
            mix["ctx_sum_tolerance"], mix["page"], mix["max_len"],
            mix["max_batch"], mix["queue_capacity"], mix["deadline_s"],
            mix["bucket_edges"], mix["prefill_chunk"],
            mix["trace_after_steps"], mix["trace_steps"]) \
        == (128, 256, 4096, 0.005, 256, 5376, 128, 256, 30.0,
            [1, 256, 2048], 2048, 50, 20)
    # ISSUE 39: 180,000 of context, or the one lowered sum with the pool
    # lowered by the same 30,000
    assert (mix["ctx_sum"], mix["cache_tokens"]) in ((180000, 377600),
                                                     (150000, 347600))
    assert (mix["check_sessions"], mix["check_steps"],
            mix["check_steps_below"], mix["check_short_below"],
            mix["check_long_above"], mix["check_mid_below"]) \
        == (4, 4, 48, 512, 2048, 1500)
    other = harness.load_json(harness.HERE, "traffic",
                              "sessions64-ctx1k-64k-sum900k.json")
    assert all(mix[k] == other[k] for k in (
        "prefill_deadline_s", "wedge_timeout_s", "deadline_s"))
    assert set(mix["limits"]) == set(TRAFFIC["limits"])
    names = {m["name"] for m in harness.cell_metrics(bench, "per_layer",
                                                     CELL)}
    assert {"step_mfu.lm_ssm_serve", "step_hbm_roofline.lm_ssm_serve",
            "ssm_update_roofline.lm_ssm_serve",
            "gqa_paged_roofline.lm_ssm_serve",
            "ssm_mixer_device_ms.lm_ssm_serve",
            "paged_grid_fill.lm_mla_serve", "step_ms.lm_serve",
            "cache_fill.lm_serve", "proj_device_ms.lm_serve",
            "dense_mlp_device_ms.lm_serve", "head_device_ms.lm_serve",
            "scope_coverage.lm_serve", "batch_fill.serve",
            "device_idle_share.serve"} <= names
    # the other LMs' costs read their own keys, and there is no expert
    assert not {"step_mfu.lm_serve", "step_mfu.lm_gqa_serve",
                "gqa_paged_roofline.lm_gqa_serve",
                "expert_load_max_over_mean.lm_serve"} & names
    assert {m["name"] for m in harness.cell_metrics(bench, "end_to_end",
                                                    CELL)} \
        == {"serve_throughput", "setup_s"}
    # (no count of the benchmark's cells: a later PR adds one)
    entry = resolved["config_entry"]
    assert entry["reduced"] == resolved["config"]["reduced"] \
        == ["num_hidden_layers", "vocab_size"]


MINE = ("step_mfu", "step_hbm_roofline", "ssm_update_roofline",
        "gqa_paged_roofline", "ssm_mixer_device_ms")


def test_cell_and_configuration_stand_last_in_their_lists():
    """ISSUE 39: one configuration, one cell, five metrics, each at the
    end of its list; nothing but these reads ``lm_ssm_serve``."""
    bench = harness.load_benchmark()
    assert bench["configs"][-1]["name"] == "falcon-h1-34b-pp12"
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-5:]] \
        == [f"{m}.lm_ssm_serve" for m in MINE]
    assert [m["name"] for m in bench["per_layer"]
            if m["name"].endswith(".lm_ssm_serve")] \
        == [f"{m}.lm_ssm_serve" for m in MINE]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", ()):
                assert m["workloads"][-1] == CELL


@pytest.mark.parametrize("metric", MINE)
def test_metric_file_is_its_entry_and_names_a_reader_that_exists(metric):
    import importlib

    name = metric + ".lm_ssm_serve"
    spec = harness.load_json(harness.HERE, "metrics", name + ".json")
    entry = {m["name"]: m
             for m in harness.load_benchmark()["per_layer"]}[name]
    assert (spec["layer"], spec["moves"]) == (entry["layer"], entry["moves"])
    assert entry["workloads"] == [CELL]
    assert callable(importlib.import_module(
        f"benchmarks.readers.{spec['reader']}").read)


# -- planted faults: each has to come out not correct -----------------------

def patched(monkeypatch, module, name, wrap):
    inner = getattr(module, name)
    monkeypatch.setattr(module, name, wrap(inner))
    return lambda driver: None


def attention_to_the_last_positions_only(monkeypatch):
    """Decode attends to a row's last 4 entries."""
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


def state_kept_in_bfloat16(monkeypatch):
    from analytics_zoo_tpu.ops import ssm

    def rounding(inner):
        def step(*args):
            y, s = inner(*args)
            return y, s.astype(jnp.bfloat16).astype(jnp.float32)
        return step
    patched(monkeypatch, ssm, "ssd_chunked", rounding)
    return patched(monkeypatch, ssm, "ssd_step", rounding)


def a_new_session_takes_its_slot_s_old_state(monkeypatch):
    """No token counts as a session's first — and the slots are not
    fresh: sessions that have left held them before."""
    from analytics_zoo_tpu.models import lm

    patched(monkeypatch, lm, "ssm_decode", lambda inner: (
        lambda cfg, w, x, slots, pos, states, conv: inner(
            cfg, w, x, slots, pos + 1, states, conv)))
    patched(monkeypatch, lm, "ssm_prefill", lambda inner: (
        lambda cfg, w, x, slot, start, n_valid, states, conv: inner(
            cfg, w, x, slot, start + 1, n_valid, states, conv)))

    def sabotage(driver):
        n = driver.max_batch
        driver.tiers[0].forward({
            "input": np.ones((n, 4), np.int32),
            "n_tokens": np.full(n, 4), "final": np.ones(n, np.int8),
            "session": 1000 + np.arange(n, dtype=np.int64)})
    return sabotage


def convolution_reads_its_input_late(monkeypatch):
    from analytics_zoo_tpu.ops import ssm

    def late(inner):
        def step(u, prev, w, b):
            c, _ = inner(jnp.zeros_like(u), jnp.concatenate(
                [jnp.zeros_like(prev[:, :1]), prev[:, :-1]], 1), w, b)
            return c, inner(u, prev, w, b)[1]
        return step
    return patched(monkeypatch, ssm, "conv_step", late)


def padding_advances_the_state(monkeypatch):
    from analytics_zoo_tpu.ops import ssm

    return patched(monkeypatch, ssm, "ssd_chunked", lambda inner: (
        lambda x, delta, a_log, Bm, Cm, D, state, chunk, n_valid: inner(
            x, delta, a_log, Bm, Cm, D, state, chunk, x.shape[0])))


def leaf_set_to(name, value):
    def fault(monkeypatch):
        def sabotage(driver):
            for layer in driver.model.params["layers"]:
                layer["ssm"][name] = jnp.full_like(layer["ssm"][name], value)
        return sabotage
    fault.__name__ = f"{name}_is_{value}"
    return fault


def one_group_for_all_heads(monkeypatch):
    from analytics_zoo_tpu.models import lm

    def first(inner):
        def split(m, c):
            x, Bm, Cm = inner(m, c)
            return x, *(jnp.repeat(t[:, :1], m.groups, 1) for t in (Bm, Cm))
        return split
    return patched(monkeypatch, lm, "ssm_split", first)


def norm_before_the_gate(monkeypatch):
    from analytics_zoo_tpu.ops import ssm

    return patched(monkeypatch, ssm, "gated_norm", lambda inner: (
        lambda y, z, w, groups, eps: inner(
            y, jnp.full_like(z, 1.2784645), w, groups, eps)    # SiLU = 1
        * jax.nn.silu(z.astype(jnp.float32))))


def every_multiplier_one(monkeypatch):
    from analytics_zoo_tpu.models import lm

    made = lm.LMConfig.from_dict
    monkeypatch.setattr(lm.LMConfig, "from_dict", staticmethod(
        lambda cfg: dataclasses.replace(made(cfg), mup=lm.Multipliers())))
    return lambda driver: None


def eight_heads_a_kv_head(monkeypatch):
    """Head ``a`` reads KV head ``a // 8``: every head gets a KV head of
    its own, a copy of the one the fault names."""
    from analytics_zoo_tpu.ops import lm_attention as att

    def per_head(entries, G, H, dv):
        r = (entries.shape[-1] - G * dv) // G
        k = entries[..., :G * r].reshape(entries.shape[:-1] + (G, r))
        v = entries[..., G * r:].reshape(entries.shape[:-1] + (G, dv))
        of = np.arange(H) // 8
        return jnp.concatenate([t[..., of, :].reshape(
            entries.shape[:-1] + (-1,)) for t in (k, v)], -1)

    patched(monkeypatch, att, "gqa_paged", lambda inner: (
        lambda qp, qr, pool, tables, lengths, G, dv, scale: inner(
            qp, qr, per_head(pool, G, qr.shape[1], dv), tables, lengths,
            qr.shape[1], dv, scale)))
    return patched(monkeypatch, att, "prefill_gqa_causal", lambda inner: (
        lambda qp, qr, pool, table, start, n_valid, G, dv, scale, pps=1:
        inner(qp, qr, per_head(pool, G, qr.shape[1], dv), table, start,
              n_valid, qr.shape[1], dv, scale, pps)))


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


#: the reference's faults, each as the program would commit it
FAULTS = {
    "truncate": attention_to_the_last_positions_only,
    "shift_cache": cache_at_the_wrong_position,
    "state_bf16": state_kept_in_bfloat16,
    "state_not_reset": a_new_session_takes_its_slot_s_old_state,
    "conv_state_late": convolution_reads_its_input_late,
    "pad_advances": padding_advances_the_state,
    "no_dt_bias": leaf_set_to("dt_bias", 0.0),
    "no_D": leaf_set_to("D", 0.0),
    "one_group": one_group_for_all_heads,
    "norm_before_gate": norm_before_the_gate,
    "no_mup": every_multiplier_one,
    "heads_per_kv_8": eight_heads_a_kv_head,
    "int8": operands_in_8_bits,
}


def test_every_fault_of_the_reference_is_planted_here():
    assert set(FAULTS) == set(ref.FAULTS) | {"int8"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    # a geometry of its own, so that no step compiled without the fault
    # is found in the process's jit cache
    traffic = dict(TRAFFIC, cache_tokens=TRAFFIC["cache_tokens"]
                   + 4 * (1 + sorted(FAULTS).index(fault)))
    sabotage = FAULTS[fault](monkeypatch)

    def prepare(driver):
        driver.sabotage = sabotage
    line = drive(4100000002, prepare, traffic)
    over = {k for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]}
    assert not line["correct"] and over, line["checks"]
    if fault in ("state_bf16", "pad_advances", "state_not_reset"):
        assert "state_rel_rms" in over, line["checks"]


def test_controls_fail_and_the_stated_precision_reads_low():
    d = lm_serve_ssm.Driver(CONFIG, TRAFFIC, 4100000003,
                            "/tmp/lm_ssm_toy_control")
    d.setup()
    w = d.window(0.05, harness.Tracer(False, d.workdir))
    scopes = d.decode_scopes()
    d.free()
    assert harness.judge(d.check())
    c = d.control_readings()
    limits = TRAFFIC["limits"]
    assert set(c) == set(lm_serve_ssm.CONTROLS) | {"sessions",
                                                   "program_subset"}
    for name in set(lm_serve_ssm.CONTROLS) - {"reference_bf16"}:
        assert any(c[name][k] > 10 * limits[k] for k in limits), \
            (name, c[name])
    assert c["fault_state_bf16"]["state_rel_rms"] \
        > 10 * limits["state_rel_rms"]
    assert c["reference_bf16"]["logits_rel_rms"] \
        < c["control_int8"]["logits_rel_rms"]
    assert all(c["program_subset"][k] <= limits[k] for k in limits)
    # what the window hands the readers: lm_serve.py's keys
    assert {"lengths", "config", "op_scopes"} <= set(w["lm"])
    gauges = w["counters"]["lm"]["gauges"]
    assert 0 < gauges["lm/paged_pages"] <= gauges["lm/paged_grid_steps"]
    D = ref.dims(CONFIG)
    assert gauges["lm/ssm_slots_live"] == TRAFFIC["sessions"]
    assert gauges["lm/ssm_state_bytes"] == TRAFFIC["sessions"] \
        * D["layers"] * D["H"] * D["P"] * D["N"] * 4
    assert w["counters"]["lm"]["counters"]["lm/ssm_state_starts"] \
        == TRAFFIC["sessions"]
    assert {"lm/gqa_paged", "lm/ssm_proj", "lm/ssm_conv", "lm/ssm_update",
            "lm/ssm_out", "lm/dense_mlp", "lm/head"} <= set(scopes)
    assert not {"lm/experts", "lm/route", "lm/gqa_window", "lm/ssm_scan",
                "lm/mla_paged"} & set(scopes)
    # the states compared are the window's LAST: every decoded id counts
    assert all(len(d.session_tokens(c)) == len(d.context[c])
               + len(d.decoded[c]) for c in d.checked)
    assert d.numbers["compared_tokens"] > sum(
        len(d.context[c]) + max(d.steps) for c in d.checked)


def test_checked_sessions_alone_read_the_same_numbers():
    whole = lm_serve_ssm.Driver(CONFIG, TRAFFIC, 4100000005,
                                "/tmp/lm_ssm_toy_whole")
    few = lm_serve_ssm.Driver(CONFIG, TRAFFIC, 4100000005,
                              "/tmp/lm_ssm_toy_few", checked_only=True,
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


def test_a_drifting_head_of_the_first_layer_is_seen_where_pooled_it_is_not():
    """``state_layer0_worst_head``: one head of the first layer at eight
    times its neighbours' error reads as that head's, where the pooled
    number hardly moves; the later layers' larger error is not in it."""
    r = np.random.RandomState(0)
    want = [[r.standard_normal((8, 4, 16)) for _ in range(3)]
            for _ in range(2)]

    def off_by(levels):
        return [[w + lv[:, None, None] * r.standard_normal(w.shape)
                 for w, lv in zip(ws, levels)] for ws in want]

    even = np.array([[0.004] * 8, [0.012] * 8, [0.019] * 8])
    got = lm_serve_ssm.state_numbers(off_by(even), want)
    assert 0.003 < got["state_layer0_worst_head"] < 0.0052
    assert 0.011 < got["state_rel_rms"] < 0.015
    drift = even.copy()
    drift[0, 5] = 0.032
    got = lm_serve_ssm.state_numbers(off_by(drift), want)
    assert 0.027 < got["state_layer0_worst_head"] < 0.037
    assert got["state_rel_rms"] < 0.016          # pooled, it is not seen
