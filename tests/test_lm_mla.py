"""A decoder LM of causal latent attention throughout (an ``axk1``-shaped
config: no ``layer_types``, YaRN, group-limited routing, no gate, no
rescale, no router bias) against its plain reference
(benchmarks/reference/lm_mla.py), at toy size on the CPU
(tests/lm_mla_toy.py) in float32 on both sides — so every tolerance below
is round-off of two orders of summation, not precision.  And the shared
module under the OTHER model: what ``LMConfig.from_dict`` makes of
``dots3-note-prev-ep8.json`` is written out here."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_mla_toy import TOY, tokens  # noqa: E402

from analytics_zoo_tpu.models import lm  # noqa: E402
from analytics_zoo_tpu.ops import pallas_lm_decode  # noqa: E402
from analytics_zoo_tpu.parallel import moe_held_experts  # noqa: E402
from analytics_zoo_tpu.pipelines.lm import (lm_serving_tiers,  # noqa: E402
                                            make_lm_model)
from analytics_zoo_tpu.serving import ServingRuntime  # noqa: E402
from analytics_zoo_tpu.serving.runtime import ModelConfig  # noqa: E402
from benchmarks.reference import lm_mla as ref  # noqa: E402

SEED = 7
TOL = 2e-5
BLOCKS = {"q_block": 8, "head_group": 2, "key_round": 16, "mlp_block": 16,
          "pad_to": 48, "expert_group": 4}
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


# -- (a) chunked prefill, then decode through the cache ---------------------

@pytest.mark.parametrize("name,chunks", [
    ("one_chunk_then_decode", (3, 1, 1)),
    ("chunks_across_pages", (8, 6, 1, 1, 1, 4, 1)),
    ("ragged_chunks", (5, 7, 2, 1, 8, 1, 1)),
    ("past_the_original_context", (8, 8, 8, 1, 1, 1)),
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


def test_sessions_share_a_decode_batch_and_the_tier_counts_pages(weights):
    """Rows of different sessions and lengths in one decode call; the
    recorded choices are the routed experts alone; the gauges say what the
    paged attention walked."""
    rt, tier = serve(TOY, weights)
    a, b = tokens(1, 9), tokens(2, 3)
    sids = [rt.open_session("lm") for _ in range(2)]
    tier.record_choices(sids)
    reqs = [rt.submit_chunk(s, {"input": t[:-1]}, length=len(t) - 1)
            for s, t in zip(sids, (a, b))]
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
    assert gauges["lm/selected_one_pass"] == 0           # no full layer
    for sid, n in zip(sids, (9, 3)):
        recorded = tier.choices[sid]
        assert [(s, k) for s, k, _ in recorded] == [(0, n - 1), (n - 1, 1)]
        assert all(c["selected"] == [] and c["routed"].shape == (4, k, 2)
                   for _, k, c in recorded)
    # pools only: no ring, no index keys
    shapes = lm.cache_shapes(tier_config(), lm.CacheGeometry(25, 4, 12, 4))
    assert shapes["ik"] == [] and shapes["ring"] == [] \
        and len(shapes["kv"]) == 5


def tier_config():
    return lm.LMConfig.from_dict(TOY)


def test_lane_wide_latents_decode_through_the_kernel(monkeypatch):
    """A toy whose latent is a whole lane tile takes the Pallas kernel (in
    interpret mode here) inside the decode step; the gathering form gives
    the same logits."""
    config = dict(TOY, num_hidden_layers=2, kv_lora_rank=128)
    w = seeded(config)
    cfg = lm.LMConfig.from_dict(config)
    geo = lm.CacheGeometry(n_pages=7, page=16, max_pages=3, n_slots=2)
    assert pallas_lm_decode.supported(128, cfg.full.entry, geo.page)
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
    monkeypatch.setattr(pallas_lm_decode, "supported", lambda *a: False)
    _, gathered, *_ = lm.decode_rows(cfg, geo, w, cache, *args)
    want = np.asarray(ref.forward(config, SEED, toks, weights=w,
                                  blocks=BLOCKS)["logits"])[20]
    np.testing.assert_allclose(np.asarray(kernel)[0], want, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(gathered)[0], want, atol=TOL,
                               rtol=0)


# -- (b) the shares add up --------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """4 shares of 8 experts in 2 routing groups: the held experts' parts
    over all shares, the shared expert counted once, equal the reference's
    layer with all 8 experts held."""
    D = ref.dims(TOY)
    assert (D["experts"], D["held"], D["n_group"]) == (8, 2, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D["d"]), jnp.float32)
    whole = ref.mlp_weights(jax.random.PRNGKey(2), D["d"], D["f_expert"],
                            (D["experts"],))
    whole = {n: v.astype(jnp.float32) for n, v in whole.items()}
    base = weights["layers"][1]["moe"]
    want, used, _ = ref.moe(x, dict(base, experts=whole), D, first_held=0,
                            held=D["experts"])
    # group-limited: a token's experts lie in one group of four
    assert all(len({e // 4 for e in row}) == 1 for row in np.asarray(used))
    total = jnp.zeros_like(x)
    for share in range(D["experts"] // D["held"]):
        lo = share * D["held"]
        part = dict(base, experts={n: v[lo:lo + D["held"]]
                                   for n, v in whole.items()})
        y, chosen, _ = moe_held_experts(
            x, part, lo, D["per_tok"], D["route_scale"],
            shared=(share == 0), n_group=D["n_group"],
            topk_group=D["topk_group"])
        mine, *_ = ref.moe(x, part, D, first_held=lo, held=D["held"],
                           shared=(share == 0))
        np.testing.assert_allclose(y, mine, atol=TOL, rtol=0)
        assert np.array_equal(np.asarray(chosen), np.asarray(used))
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


# -- (c) the reference's faults each move its logits ------------------------

@pytest.mark.parametrize("fault", ["truncate:4", "shift_cache", "no_yarn",
                                   "drop_expert:0", "no_group_limit"])
def test_reference_faults_show(weights, fault):
    toks = tokens(11, 30)
    sound = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS)
    faulty = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                         fault=fault)
    gap = np.abs(np.asarray(faulty["logits"]) - np.asarray(sound["logits"]))
    assert gap[-1].max() > 1e-2, fault
    if fault == "no_group_limit":
        # following the sound side's experts: equal logits, its own
        # routing counted against them
        given = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                            fault=fault, follow={"routed": sound["chosen"]})
        np.testing.assert_allclose(np.asarray(given["logits"]),
                                   np.asarray(sound["logits"]), atol=TOL)
        differ, counted = given["miss"]["route"]
        assert counted == 4 * 30 * 2 and differ > 0.05 * counted


# -- (d) the other model of the shared module is what it was ----------------

def test_dots3_config_and_its_trees_are_unchanged():
    """``LMConfig.from_dict`` of the benchmark's other LM, its parameter
    tree and its cache, written out as PR 32 had them."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev-ep8.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    assert cfg == lm.LMConfig(
        d=5120, kinds=(lm.FULL, lm.FULL, lm.SLIDING, lm.SLIDING, lm.SLIDING),
        dense_layers=1,
        full=lm.MLADims(128, 1024, 512, 128, 64, 128, 8e7, None),
        swa=lm.MLADims(64, 1024, 1024, 192, 64, 128, 5e4, None), window=513,
        idx_heads=64, idx_dim=128, topk=2048, f_dense=13824, f_expert=1536,
        f_shared=1536, experts=256, held=32, first_held=0, per_tok=8,
        route_scale=1.0, vocab=19008, eps=1e-5, dtype="bfloat16", gate=True,
        rescale=True, route_bias=True, n_group=1, topk_group=1)
    assert cfg.full.scale == 1.0 / math.sqrt(192) and cfg.full.entry == 640 \
        and cfg.swa.entry == 1152
    shapes = lm.param_shapes(cfg)
    flat = {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    bf = "bfloat16"
    assert len(flat) == 98
    assert sum(int(np.prod(s)) for s, _ in flat.values()) == 4087154176
    assert flat["['layers'][1]['attn']['idx_wq_b']"] == ((1024, 64, 128), bf)
    assert flat["['layers'][0]['attn']['w_gate']"] == ((5120, 128), bf)
    assert flat["['layers'][2]['attn']['wkv_b']"] == ((1024, 64, 320), bf)
    assert "['layers'][2]['attn']['idx_wk']" not in flat
    assert flat["['layers'][1]['moe']['router_b']"] == ((256,), "float32")
    assert flat["['layers'][4]['moe']['experts']['w_down']"] \
        == ((32, 1536, 5120), bf)
    assert flat["['ends']['head']"] == ((5120, 19008), bf)
    geo = lm.CacheGeometry(n_pages=2401, page=512, max_pages=136, n_slots=64)
    cache = lm.cache_shapes(cfg, geo)
    assert [(v.shape, str(v.dtype)) for v in cache["kv"]] \
        == [((2401, 512, 640), bf)] * 2
    assert [v.shape for v in cache["ik"]] == [(2401, 512, 128)] * 2
    assert [v.shape for v in cache["ring"]] == [(64, 513, 1152)] * 3
