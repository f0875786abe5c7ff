"""The decoder LM's cell at toy size on the CPU: the driver end to end
through ``harness.drive`` (sessions through the runtime, the window, the
check against the plain reference), the planted faults that ``correct``
has to fail, the new readers on what they can and cannot read, and
flops_lm.py on shapes worked out by hand."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_cell_toy import CONFIG, TRAFFIC  # noqa: E402

from benchmarks import flops_lm, harness  # noqa: E402
from benchmarks.drivers import lm_serve  # noqa: E402
from benchmarks.readers import (counter_value, lm_scope_roofline,  # noqa: E402
                                lm_step)

CELL = "dots3-ep8-decode-ctx1k-64k"


def drive(seed, prepare=None, traffic=None):
    resolved = {"cell": {"name": CELL, "chips": 1}, "config": CONFIG,
                "traffic": traffic or TRAFFIC, "driver": lm_serve}
    return harness.drive(resolved, harness.load_benchmark(), seed, 0.05,
                         False, time.monotonic(), harness.describe_device(),
                         prepare=prepare)


def test_cell_runs_correct_through_sessions():
    line = drive(4100000001)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_throughput", "setup_s"}
    assert set(line["checks"]) == set(TRAFFIC["limits"])


# -- (g) planted faults: each has to come out not correct -------------------

def selection_left_out(monkeypatch):
    """Every position selected, in decode and in prefill."""
    from analytics_zoo_tpu.ops import lm_attention as att

    def all_of_them(scores, lengths, k):
        n = scores.shape[1]
        idx = jnp.broadcast_to(jnp.arange(n)[None, :], scores.shape)
        return idx, idx < lengths[:, None]

    monkeypatch.setattr(att, "select_topk", all_of_them)
    monkeypatch.setattr(att, "kth_largest", lambda count, k, rows: jnp.zeros(
        (rows,), jnp.uint32))
    return lambda driver: None


def one_expert_left_out(monkeypatch):
    def sabotage(driver):
        layers = driver.model.params["layers"]
        for layer in layers:
            if "moe" in layer:
                e = layer["moe"]["experts"]
                e["w_down"] = e["w_down"].at[0].set(0.0)
    return sabotage


def cache_at_the_wrong_position(monkeypatch):
    """Every decoded token written one position late (the position it
    should have stood at keeps what was there)."""
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
CAUGHT_BY = {"selection_left_out": "select_miss",
             "one_expert_left_out": "logits_rel_rms",
             "cache_at_the_wrong_position": "logits_rel_rms",
             "operands_in_8_bits": "logits_rel_rms"}


@pytest.mark.parametrize("fault", [selection_left_out, one_expert_left_out,
                                   cache_at_the_wrong_position,
                                   operands_in_8_bits])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    # a geometry of its own, so that no step compiled without the fault
    # is found in the process's jit cache
    traffic = dict(TRAFFIC, cache_tokens=TRAFFIC["cache_tokens"] + 4 * (
        1 + [selection_left_out, one_expert_left_out,
             cache_at_the_wrong_position, operands_in_8_bits].index(fault)))
    sabotage = fault(monkeypatch)

    def prepare(driver):
        driver.sabotage = sabotage
    line = drive(4100000002, prepare, traffic)
    over = {k: c for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]}
    assert not line["correct"] and CAUGHT_BY[fault.__name__] in over, \
        line["checks"]
    if fault is selection_left_out:
        # the reference follows the program's sets, so the logits agree:
        # a selection that is not made shows in the sets alone
        assert "logits_rel_rms" not in over


def test_controls_fail_and_the_stated_precision_reads_low():
    d = lm_serve.Driver(CONFIG, TRAFFIC, 4100000003, "/tmp/lm_toy_control")
    d.setup()
    d.window(0.05, harness.Tracer(False, "/tmp/lm_toy_control"))
    d.free()
    assert harness.judge(d.check())
    c = d.control_readings()
    limits = TRAFFIC["limits"]
    for name in ("control_int8", "fault_no_select", "fault_drop_expert",
                 "fault_shift_cache"):
        assert c[name]["logits_rel_rms"] > 100 * limits["logits_rel_rms"], \
            (name, c[name])
    # every position "selected": the sets differ on more than half
    assert c["fault_no_select"]["select_miss"] > 0.5
    assert c["reference_bf16"]["logits_rel_rms"] \
        < c["control_int8"]["logits_rel_rms"]
    assert all(c["program_subset"][k] <= limits[k]
               for k in c["program_subset"])


def test_checked_sessions_alone_read_the_same_numbers():
    """``checked_only`` (control.py's way to many seeds a call) serves the
    compared sessions alone: the same rows, the same numbers; ``controls``
    picks what ``control_readings`` reads."""
    whole = lm_serve.Driver(CONFIG, TRAFFIC, 4100000005, "/tmp/lm_toy_whole")
    few = lm_serve.Driver(CONFIG, TRAFFIC, 4100000005, "/tmp/lm_toy_few",
                          checked_only=True, controls=["control_int8"])
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


def test_reference_is_compiled_ahead_and_runs_nothing():
    """Set-up compiles on threads what the check will run: the reference's
    forward over zeros, every jitted function lowering and compiling its
    program and returning zeros (``ref.compile_only``), and the step
    program of every edge; the check's own calls are untouched."""
    from benchmarks.reference import lm as ref

    d = lm_serve.Driver(CONFIG, TRAFFIC, 4100000007, "/tmp/lm_toy_ahead")
    d.setup()
    d.window(0.05, harness.Tracer(False, d.workdir))
    d.free()
    assert len(d.reference_jobs) >= 10          # programs, not calls
    assert harness.judge(d.check())
    x = jnp.ones((4, 8), jnp.float32)
    w = {k: jnp.ones(s, jnp.float32) for k, s in
         (("w_gate", (8, 6)), ("w_up", (8, 6)), ("w_down", (6, 8)))}
    real = np.asarray(ref.gated_mlp(x, w, "f32"))
    with ref.compile_only(jax.devices()[0]) as done:
        dry = np.asarray(ref.gated_mlp(x, w, "f32"))
        ref.gated_mlp(x, w, "f32")                # the same program again
    assert len(done) == 1 and dry.shape == real.shape
    done[next(iter(done))][1]                   # (zeros, the compiled)
    assert not dry.any() and real.all()
    assert np.array_equal(np.asarray(ref.gated_mlp(x, w, "f32")), real)


def test_compared_sessions_are_the_ends_and_short_ones_between():
    """The shortest session, the shortest over ``check_long_above``, and
    the rest from those over ``check_short_below`` and under
    ``check_mid_below`` — the shortest others where there are too few."""
    mix = dict(TRAFFIC, check_short_below=10, check_long_above=30,
               check_mid_below=14)
    d = lm_serve.Driver(CONFIG, mix, 1, "/tmp/unused")
    d.lengths = np.array([3, 40, 11, 12, 13, 20, 29, 35, 9])
    d.pick_checked()
    picked = sorted(d.checked, key=lambda c: d.lengths[c])
    assert picked[0] == 0 and picked[-1] == 7
    assert {int(d.lengths[c]) for c in picked[1:-1]} < {11, 12, 13}
    d.lengths = np.array([3, 40, 25, 12, 20, 35])
    d.pick_checked()            # one under 14: the two shortest over 10
    assert sorted(int(d.lengths[c]) for c in d.checked) == [3, 12, 20, 35]


def test_batch_fill_is_the_window_s_alone():
    """Set-up's prefill batches (partial ones) are not in the window's
    ``mean_batch_fill``: every decode batch holds all six callers."""
    d = lm_serve.Driver(CONFIG, TRAFFIC, 4100000006, "/tmp/lm_toy_fill")
    d.setup()
    before = d.runtime.snapshot()["metrics"]
    w = d.window(0.05, harness.Tracer(False, d.workdir))
    assert before["batches"] > 0 and before["mean_batch_fill"] < 1.0
    assert w["counters"]["batches"] == w["steps"]
    assert w["counters"]["mean_batch_fill"] == pytest.approx(1.0)


# -- the readers -------------------------------------------------------------

class FakeReduction:
    def __init__(self, programs=None, ops=None):
        self.programs, self.ops = programs or {}, ops or {}

    def program(self, name):
        return self.programs.get(name, (0.0, 0))

    def pattern_seconds(self, pattern):
        return self.ops.get(pattern, (0.0, 0))


def real_config():
    with open(os.path.join(harness.HERE, "configs",
                           "dots3-note-prev-ep8.json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ctx(red, lengths=(4096,) * 64):
    return {"trace": red, "peaks": PEAKS, "counters": {},
            "window": {"lm": {"config": real_config(),
                              "lengths": list(lengths)}}}


def test_step_readers_read_the_program_or_nothing():
    params = {"program": "jit_decode_step", "against": "bytes"}
    assert lm_step.read(ctx(None), params) is None
    assert lm_step.read(ctx(FakeReduction()), params) is None
    no_shapes = dict(ctx(FakeReduction({"jit_decode_step": (1.0, 10)})),
                     window={})
    assert lm_step.read(no_shapes, params) is None
    cost = flops_lm.decode_step_cost(real_config(), [4096] * 64)
    red = FakeReduction({"jit_decode_step": (0.2, 10)})
    got = lm_step.read(ctx(red), params)
    assert got == pytest.approx(100 * cost["bytes"] / 819e9 * 10 / 0.2)
    mfu = lm_step.read(ctx(red), dict(params, against="flops"))
    assert 0 < mfu < got < 100


def test_scope_roofline_reads_the_scope_or_nothing():
    import types

    params = {"scopes": ["lm/experts"], "cost": "experts",
              "program": "jit_decode_step"}
    assert lm_scope_roofline.read(ctx(None), params) is None

    def traced(ops, scopes):
        red = FakeReduction({"jit_decode_step": (0.2, 10)})
        red.devices = [types.SimpleNamespace(ops=ops)]
        c = ctx(red)
        c["window"]["lm"]["op_scopes"] = scopes
        return c

    ops = [("%fusion.7 = bf16[64,5120] fusion(...)", 0.0, 0.06),
           ("%fusion.8 = bf16[64,5120] fusion(...)", 0.1, 0.04),
           ("%sort.1 = (f32[64,69632]) sort(...)", 0.2, 0.5)]
    # a program without the scope (the parent's), or a trace without the
    # scope's operations: nothing, not 0
    assert lm_scope_roofline.read(traced(ops, {}), params) is None
    assert lm_scope_roofline.read(
        traced(ops, {"lm/experts": ["fusion.99"]}), params) is None
    cost = flops_lm.COSTS["experts"](real_config(), [4096] * 64)
    got = lm_scope_roofline.read(
        traced(ops, {"lm/experts": ["fusion.7", "fusion.8"],
                     "lm/select": ["sort.1"]}), params)
    assert got == pytest.approx(100 * cost["bytes"] / 819e9 * 10 / 0.1)


def test_scope_map_reads_scopes_off_the_compiled_text():
    from benchmarks import hlo_scopes

    text = """
ENTRY %main {
  %fusion.3 = bf16[64,5120]{1,0} fusion(%p0), kind=kOutput, calls=%fc.3, metadata={op_name="jit(decode_step)/lm/experts/hnf,hfd->hnd/dot_general" stack_frame_id=7}
  ROOT %sort.1 = (f32[64,69632]{1,0}) sort(%a, %b), dimensions={1}, metadata={op_name="jit(decode_step)/lm/select/top_k"}
  %copy.2 = f32[8]{0} copy(%c), metadata={op_name="jit(decode_step)/convert_element_type"}
  %add.9 = f32[8]{0} add(%c, %c)
}"""
    assert hlo_scopes.scope_map(text) == {"lm/experts": ["fusion.3"],
                                          "lm/select": ["sort.1"]}
    assert hlo_scopes.scope_seconds(
        [("%fusion.3 = bf16[64,5120]{1,0} fusion(%p0)", 0.0, 0.25),
         ("%other = f32[] add()", 0.3, 1.0)], ["fusion.3"]) == (0.25, 1)


def test_driver_hands_over_the_decode_steps_scopes():
    d = lm_serve.Driver(CONFIG, TRAFFIC, 4100000004, "/tmp/lm_toy_scopes")
    d.setup()
    scopes = d.decode_scopes()
    assert {"lm/experts", "lm/indexer", "lm/select", "lm/mla_full",
            "lm/mla_window", "lm/route", "lm/shared_mlp", "lm/head"} \
        <= set(scopes)
    assert all(scopes.values())


def test_counter_value_reads_gauges_histograms_and_ratios():
    counters = {"lm": {"gauges": {"lm/cache_fill": 0.5},
                       "histograms": {"a": {"mean": 6.0}, "b": {"mean": 2.0}}}}
    c = {"counters": counters}
    assert counter_value.read(c, {"path": ["lm", "gauges", "lm/cache_fill"],
                                  "scale": 100.0}) == 50.0
    assert counter_value.read(c, {"path": ["lm", "histograms", "a"],
                                  "over": ["lm", "histograms", "b"]}) == 3.0
    assert counter_value.read({"counters": {}}, {"path": ["lm", "x"]}) is None
    assert counter_value.read(c, {"path": ["lm", "gauges", "nope"]}) is None


# -- flops_lm ----------------------------------------------------------------

def test_flops_lm_matches_hand_counts():
    cfg = real_config()
    # one expert: 3 x 5120 x 1536 parameters; 64 rows x 8 of 256 routed,
    # 32 held: 64 pairs here in expectation
    e = flops_lm.experts_cost(cfg, 64)
    assert e["flops"] == pytest.approx(2 * 64 * 3 * 5120 * 1536)
    reached = 32 * (1 - (255 / 256) ** 512)
    assert e["bytes"] == pytest.approx(2 * reached * 3 * 5120 * 1536)
    # the indexer reads every key once: 128 wide, bf16
    short, long_ = flops_lm.select_cost(cfg, [1000]), \
        flops_lm.select_cost(cfg, [41000])
    assert long_["bytes"] - short["bytes"] == pytest.approx(
        2 * (40000 * 128 + 2 * (2048 - 1000) * 576))
    # attention reads min(L, 2048) latents a row, whatever the context
    a, b = flops_lm.mla_decode_cost(cfg, [30000]), \
        flops_lm.mla_decode_cost(cfg, [60000])
    assert a == b
    step = flops_lm.decode_step_cost(cfg, [16000] * 64)
    # a decode step has to read the weights: 8.17 GB less the embedding's
    # rows and the experts not reached; bytes bind by far
    assert 7.5e9 < step["bytes"] < 10e9
    assert step["flops"] / 197e12 < 0.2 * step["bytes"] / 819e9
