"""A decoder LM of grouped-query attention (a ``mimo_v2``-shaped config:
``hybrid_layer_pattern`` of global and window layers with KV heads and a
rotary base a kind, partial rotary in pairs at a distance, learned sinks
in the window layers, scaled values, a router with a bias and NO shared
expert) against its plain reference (benchmarks/reference/lm_gqa.py), at
toy size on the CPU (tests/lm_gqa_toy.py) in float32 on both sides — so
every tolerance below is round-off of two orders of summation, not
precision.  And what ``LMConfig.from_dict`` makes of the configuration
file the benchmark serves."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_gqa_toy import TOY, tokens  # noqa: E402

from analytics_zoo_tpu.models import lm  # noqa: E402
from analytics_zoo_tpu.ops import pallas_lm_decode  # noqa: E402
from analytics_zoo_tpu.parallel import moe_held_experts  # noqa: E402
from analytics_zoo_tpu.pipelines.lm import (lm_serving_tiers,  # noqa: E402
                                            make_lm_model)
from analytics_zoo_tpu.serving import ServingRuntime  # noqa: E402
from analytics_zoo_tpu.serving.runtime import ModelConfig  # noqa: E402
from benchmarks.reference import lm_gqa as ref  # noqa: E402

SEED = 7
TOL = 2e-5
BLOCKS = {"q_block": 8, "head_group": 2, "key_round": 16, "window_round": 8,
          "mlp_block": 16, "pad_to": 48, "expert_group": 4}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    t = 0.0

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += s


def seeded(config):
    w = {"layers": [ref.layer_weights(SEED, config, i)
                    for i in range(config["num_hidden_layers"])],
         "ends": ref.end_weights(SEED, config)}
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


@pytest.fixture(scope="module")
def weights():
    return seeded(TOY)


def serve(config, weights, **tier_args):
    model = make_lm_model(config, params=weights)
    args = dict(cache_tokens=96, max_sessions=4, max_batch=4, page=4,
                max_len=48)
    args.update(tier_args)
    tiers = lm_serving_tiers(model, **args)
    mc = ModelConfig(name="lm", streaming=True, serial_chunks=True,
                     tiers=tiers, tier_factory=lambda rid: tiers,
                     pad_key="input", length_key="n_tokens",
                     bucket_edges=[1, 4, 8], max_batch=4,
                     chunk_deadline_s=1e9)
    rt = ServingRuntime(models=[mc], n_replicas=1, max_batch=4,
                        queue_capacity=16, clock=Clock(),
                        service_time=lambda *a: 0.0)
    rt.warm({"input": np.zeros(1, np.int32)}, model="lm")
    return rt, tiers[0]


@pytest.fixture(scope="module")
def served(weights):
    return serve(TOY, weights)


def run_session(rt, toks, chunks):
    sid = rt.open_session("lm")
    out, p = {}, 0
    for n in chunks:
        r = rt.submit_chunk(sid, {"input": toks[p:p + n]}, length=n)
        rt.pump(force=True)
        assert r.state == "done", r.state
        p += n
        out[p - 1] = np.asarray(r.result)
    return sid, out


# -- (a) chunked prefill, then decode through pools and rings ---------------

@pytest.mark.parametrize("name,chunks", [
    # the toy's window is 5: contexts of window - 1, window, window + 1
    ("one_short_of_the_window", (4, 1, 1, 1)),
    ("the_window_exactly", (5, 1, 1)),
    ("one_past_the_window", (6, 1, 1)),
    ("decode_wraps_the_ring", (3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1)),
    ("chunks_across_pages_and_the_ring", (8, 6, 1, 1, 1, 4, 1)),
    ("ragged_chunks", (5, 7, 2, 1, 8, 1, 1)),
    ("a_page_edge", (8, 8, 8, 1, 1, 1)),
])
def test_prefill_then_decode_equals_full_forward(served, weights, name,
                                                 chunks):
    rt, _ = served
    toks = tokens(len(name), sum(chunks))
    sid, got = run_session(rt, toks, chunks)
    rt.close_session(sid)
    want = np.asarray(ref.forward(TOY, SEED, toks, weights=weights,
                                  blocks=BLOCKS)["logits"])
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0,
                                   err_msg=f"{name}: position {pos}")


def test_sessions_share_a_decode_batch_and_the_tier_counts_its_work(weights):
    """Rows of different sessions and lengths in one decode call; the
    recorded choices are the routed experts alone; the gauges say what the
    paged attention walked and what the rings held."""
    rt, tier = serve(TOY, weights)
    a, b = tokens(1, 9), tokens(2, 3)
    sids = [rt.open_session("lm") for _ in range(2)]
    tier.record_choices(sids)
    for s, t in zip(sids, (a, b)):
        rt.submit_chunk(s, {"input": t[:-1]}, length=len(t) - 1)
    rt.pump(force=True)
    reqs = [rt.submit_chunk(s, {"input": t[-1:]}, length=1)
            for s, t in zip(sids, (a, b))]
    rt.pump(force=True)
    for r, t in zip(reqs, (a, b)):
        want = np.asarray(ref.forward(TOY, SEED, t, weights=weights,
                                      blocks=BLOCKS)["logits"])[-1]
        np.testing.assert_allclose(np.asarray(r.result), want, atol=TOL,
                                   rtol=0)
    gauges = tier.registry.snapshot()["gauges"]
    assert gauges["lm/paged_pages"] == 3 + 1            # 9 and 3 tokens
    assert gauges["lm/paged_grid_steps"] == pallas_lm_decode.grid_steps(
        4, 12, 25)
    assert gauges["lm/ring_tokens"] == 5 + 3            # window 5
    for sid, n in zip(sids, (9, 3)):
        recorded = tier.choices[sid]
        assert [(s, k) for s, k, _ in recorded] == [(0, n - 1), (n - 1, 1)]
        assert all(c["selected"] == [] and c["routed"].shape == (4, k, 2)
                   for _, k, c in recorded)


def test_lane_wide_heads_decode_through_the_kernel(monkeypatch):
    """A toy whose keys and values are whole lane tiles takes the Pallas
    kernel (in interpret mode here) inside the decode step; the gathering
    form gives the same logits, and both the reference's."""
    config = dict(TOY, num_hidden_layers=2, num_attention_heads=16,
                  head_dim=64, v_head_dim=128, partial_rotary_factor=0.5,
                  swa_num_attention_heads=8)
    w = seeded(config)
    cfg = lm.LMConfig.from_dict(config)
    geo = lm.CacheGeometry(n_pages=7, page=16, max_pages=3, n_slots=2)
    a = cfg.full
    assert (a.rotary, a.entry) == (32, 2 * (64 + 128))
    assert pallas_lm_decode.gqa_supported(a.kv_heads, a.k, a.v, a.heads,
                                          geo.page)
    toks = tokens(5, 21)
    cache = lm.new_cache(cfg, geo)
    table = jnp.asarray([4, 2, 0], jnp.int32)
    for lo in (0, 8, 16):
        cache, *_ = lm.prefill_step(
            cfg, geo, w, cache, jnp.asarray(toks[lo:lo + 8]), 0, lo,
            min(8, 20 - lo), table)
    args = (jnp.asarray([toks[20], 0]), jnp.asarray([0, -1]),
            jnp.asarray([20, 0]), jnp.stack([table, jnp.zeros_like(table)]),
            jnp.zeros((7,), jnp.int32))
    _, kernel, *_ = lm.decode_rows(cfg, geo, w, cache, *args)
    monkeypatch.setattr(pallas_lm_decode, "gqa_supported", lambda *a: False)
    _, gathered, *_ = lm.decode_rows(cfg, geo, w, cache, *args)
    want = np.asarray(ref.forward(config, SEED, toks, weights=w,
                                  blocks=BLOCKS)["logits"])[20]
    np.testing.assert_allclose(np.asarray(kernel)[0], want, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(gathered)[0], want, atol=TOL,
                               rtol=0)


# -- (b) the shares add up --------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """4 shares of 8 experts: the held experts' parts over all shares —
    there is no shared expert to count once — equal the reference's layer
    with all 8 experts held."""
    D = ref.dims(TOY)
    assert (D["experts"], D["held"], D["route_scale"]) == (8, 2, 1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D["d"]), jnp.float32)
    whole = ref.mlp_weights(jax.random.PRNGKey(2), D["d"], D["f_expert"],
                            (D["experts"],))
    whole = {n: v.astype(jnp.float32) for n, v in whole.items()}
    base = weights["layers"][1]["moe"]
    assert "shared" not in base and "router_b" in base
    want, used, _ = ref.moe(x, dict(base, experts=whole), D, first_held=0,
                            held=D["experts"], shared=False)
    total = jnp.zeros_like(x)
    for share in range(D["experts"] // D["held"]):
        lo = share * D["held"]
        part = dict(base, experts={n: v[lo:lo + D["held"]]
                                   for n, v in whole.items()})
        y, chosen, _ = moe_held_experts(x, part, lo, D["per_tok"],
                                        D["route_scale"], shared=False)
        mine, *_ = ref.moe(x, part, D, first_held=lo, held=D["held"],
                           shared=False)
        np.testing.assert_allclose(y, mine, atol=TOL, rtol=0)
        assert np.array_equal(np.asarray(chosen), np.asarray(used))
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


# -- (c) what makes this model this model: each left out, the logits move ---

@pytest.mark.parametrize("fault", [
    "truncate:4", "shift_cache", "no_sink", "window_129", "full_rotary",
    "swap_theta", "no_value_scale", "drop_expert:0"])
def test_reference_faults_show_against_the_program(served, weights, fault):
    """The program's last row agrees with the sound reference and with no
    faulty one: the sink, the value scale, partial rotary, the two bases
    and the window's edge are each in the program."""
    rt, _ = served
    toks = tokens(11, 30)
    sid, got = run_session(rt, toks, (8, 8, 8, 5, 1))
    rt.close_session(sid)
    sound = np.asarray(ref.forward(TOY, SEED, toks, weights=weights,
                                   blocks=BLOCKS)["logits"])[-1]
    faulty = np.asarray(ref.forward(TOY, SEED, toks, weights=weights,
                                    blocks=BLOCKS, fault=fault)["logits"])[-1]
    np.testing.assert_allclose(got[29], sound, atol=TOL, rtol=0)
    assert np.abs(got[29] - faulty).max() > 1e-2, fault


# -- (d) the configuration's keys ---------------------------------------------

def test_served_configuration_is_what_its_file_says():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v25-ep16.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    S, C = lm.SLIDING, lm.CAUSAL
    assert cfg == lm.LMConfig(
        d=4096, kinds=(C, S, S, S, S, C, S), dense_layers=1,
        full=lm.GQADims(64, 4, 192, 128, 64, 1e7, 0.707, False),
        swa=lm.GQADims(64, 8, 192, 128, 64, 1e4, 0.707, True), window=128,
        idx_heads=0, idx_dim=0, topk=0, f_dense=16384, f_expert=2048,
        f_shared=0, experts=256, held=16, first_held=0, per_tok=8,
        route_scale=1.0, vocab=19072, eps=1e-5, dtype="bfloat16",
        route_bias=True)
    assert (cfg.full.entry, cfg.swa.entry) == (1280, 2560)
    assert cfg.full.scale == pytest.approx(192 ** -0.5, rel=1e-15)
    flat = {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v in
            jax.tree_util.tree_flatten_with_path(lm.param_shapes(cfg))[0]}
    bf = "bfloat16"
    # ISSUE 35's table: 3.43 B parameters in layers 0-6 and the ends
    count = lambda keep: sum(int(np.prod(s)) for k, (s, _)  # noqa: E731
                             in flat.items() if keep(k))
    assert count(lambda k: k.startswith("['layers'][0]['attn']")) == 89128960
    assert count(lambda k: k.startswith("['layers'][1]['attn']")) \
        == 94371840 + 64
    assert count(lambda k: True) == 3429955392
    assert flat["['layers'][0]['attn']['wk']"] == ((4096, 4, 192), bf)
    assert flat["['layers'][1]['attn']['wk']"] == ((4096, 8, 192), bf)
    assert flat["['layers'][1]['attn']['sink']"] == ((64,), "float32")
    assert "['layers'][5]['attn']['sink']" not in flat
    assert flat["['layers'][6]['moe']['experts']['w_up']"] \
        == ((16, 4096, 2048), bf)
    assert not any("shared" in k for k in flat)
    assert flat["['layers'][3]['moe']['router_b']"] == ((256,), "float32")
    geo = lm.CacheGeometry(n_pages=2101, page=512, max_pages=136, n_slots=64)
    cache = lm.cache_shapes(cfg, geo)
    assert [(v.shape, str(v.dtype)) for v in cache["kv"]] \
        == [((2101, 512, 1280), bf)] * 2
    assert [v.shape for v in cache["ring"]] == [(64, 128, 2560)] * 5
    assert cache["ik"] == []


@pytest.mark.parametrize("change,message", [
    ({"moe_layer_freq": [0, 1, 0, 1, 1]}, "moe_layer_freq"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"add_full_attention_sink_bias": True}, "sink in a global layer"),
    ({"num_key_value_heads": None}, "neither kv_lora_rank"),
])
def test_a_config_the_model_cannot_be_is_refused_by_name(change, message):
    config = {k: v for k, v in dict(TOY, **change).items() if v is not None}
    with pytest.raises(ValueError, match=message):
        lm.LMConfig.from_dict(config)


def test_kinds_and_dense_layers_come_from_whichever_keys_are_there():
    """``layer_types`` before ``hybrid_layer_pattern`` before causal
    throughout; ``first_k_dense_replace`` before the leading zeros of a
    ``moe_layer_freq`` list."""
    cfg = lm.LMConfig.from_dict(TOY)
    assert cfg.kinds == (lm.CAUSAL, lm.SLIDING, lm.SLIDING, lm.CAUSAL,
                         lm.SLIDING) and cfg.dense_layers == 1
    two = lm.LMConfig.from_dict(dict(TOY, first_k_dense_replace=2))
    assert two.dense_layers == 2
    plain = {k: v for k, v in TOY.items() if k != "hybrid_layer_pattern"}
    assert lm.LMConfig.from_dict(plain).kinds == (lm.CAUSAL,) * 5
    assert lm.LMConfig.from_dict(plain).swa is None
