"""A decoder LM whose every block is a state-space mixer AND grouped-query
attention off one norm (a ``falcon_h1``-shaped config: muP multipliers, no
experts) against its plain reference (benchmarks/reference/lm_ssm.py), at
toy size on the CPU (tests/lm_ssm_toy.py) in float32 on both sides — so
every tolerance below is round-off of two orders of summation, not
precision.  The recurrent state a session: born zero, carried through
chunked prefill and decode, left alone by padding, gone with its slot.
And what ``LMConfig.from_dict`` makes of the configuration files the
benchmark serves."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_ssm_toy import TOY, tokens  # noqa: E402

from analytics_zoo_tpu.models import lm  # noqa: E402
from analytics_zoo_tpu.ops import pallas_ssm_decode  # noqa: E402
from analytics_zoo_tpu.pipelines.lm import (lm_serving_tiers,  # noqa: E402
                                            make_lm_model)
from analytics_zoo_tpu.serving import ServingRuntime  # noqa: E402
from analytics_zoo_tpu.serving.runtime import ModelConfig  # noqa: E402
from benchmarks.reference import lm_ssm as ref  # noqa: E402

SEED = 7
TOL = 2e-5
BLOCKS = {"q_block": 8, "head_group": 5, "key_round": 16, "pad_to": 16,
          "mlp_block": 16}
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
    args = dict(cache_tokens=640, max_sessions=4, max_batch=4, page=8,
                max_len=288)
    args.update(tier_args)
    tiers = lm_serving_tiers(model, **args)
    mc = ModelConfig(name="lm", streaming=True, serial_chunks=True,
                     tiers=tiers, tier_factory=lambda rid: tiers,
                     pad_key="input", length_key="n_tokens",
                     bucket_edges=[1, 8, 136], max_batch=4,
                     chunk_deadline_s=1e9)
    rt = ServingRuntime(models=[mc], n_replicas=1, max_batch=4,
                        queue_capacity=16, clock=Clock(),
                        service_time=lambda *a: 0.0)
    rt.warm({"input": np.zeros(1, np.int32)}, model="lm")
    return rt, tiers[0]


@pytest.fixture(scope="module")
def served(weights):
    return serve(TOY, weights)


def feed(rt, sid, toks, chunks, at=0):
    """The session's next chunks; {position of a chunk's last token: its
    logits}."""
    out = {}
    for n in chunks:
        r = rt.submit_chunk(sid, {"input": toks[at:at + n]}, length=n)
        rt.pump(force=True)
        assert r.state == "done", r.state
        at += n
        out[at - 1] = np.asarray(r.result)
    return out


def reference(weights, toks, **kw):
    out = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS, **kw)
    return np.asarray(out["logits"]), [np.asarray(s) for s in out["state"]]


def assert_session(tier, sid, got, toks, weights):
    """Logits at the answered positions and every layer's state after the
    last token, against the reference's full forward."""
    logits, states = reference(weights, toks)
    for pos, row in got.items():
        np.testing.assert_allclose(row, logits[pos], atol=TOL, rtol=0,
                                   err_msg=f"position {pos}")
    for layer, (mine, want) in enumerate(zip(tier.state_of(sid), states)):
        np.testing.assert_allclose(mine, want, atol=TOL, rtol=0,
                                   err_msg=f"layer {layer}'s state")


# -- (a) chunked prefill, then decode through the state ----------------------

@pytest.mark.parametrize("name,chunks", [
    # the toy's mixer runs prefill in blocks of 8 tokens; edges 1, 8, 136
    ("one_then_seven", (1, 7, 1, 1)),
    ("a_padded_last_chunk", (8, 5, 1, 1, 1)),
    ("a_whole_edge_then_two_more", (1, 7, 128, 130, 1, 1)),
    ("decode_from_the_first_token", (1, 1, 1, 1, 1, 1, 1, 1, 1, 1)),
    ("ragged_chunks", (5, 7, 2, 1, 8, 1, 1)),
    ("a_long_chunk_short_of_its_edge", (100, 1, 30, 1)),
])
def test_prefill_then_decode_equals_full_forward(served, weights, name,
                                                 chunks):
    rt, tier = served
    toks = tokens(len(name), sum(chunks))
    sid = rt.open_session("lm")
    got = feed(rt, sid, toks, chunks)
    assert_session(tier, sid, got, toks, weights)
    rt.close_session(sid)


# -- (b) a slot's state: born zero, left alone, gone with the slot -----------

def test_a_slot_given_to_a_second_session_answers_as_a_fresh_one(weights):
    rt, tier = serve(TOY, weights, max_sessions=1)
    first, second = tokens(21, 20), tokens(22, 14)
    sid = rt.open_session("lm")
    feed(rt, sid, first, (8, 8, 1, 1, 1, 1))
    slot = tier.books.slot_of[sid]
    rt.close_session(sid)
    sid = rt.open_session("lm")
    got = feed(rt, sid, second, (1, 1, 8, 4))            # decode comes first
    assert tier.books.slot_of[sid] == slot
    assert_session(tier, sid, got, second, weights)
    rt.close_session(sid)
    sid = rt.open_session("lm")
    got = feed(rt, sid, first, (8, 8, 1))                # prefill comes first
    assert tier.books.slot_of[sid] == slot
    assert_session(tier, sid, got, first[:17], weights)
    starts = tier.registry.snapshot()["counters"]["lm/ssm_state_starts"]
    assert starts == 3


def test_padding_rows_and_padded_chunks_leave_every_state_as_it_was(weights):
    """Two sessions hold states; a third steps alone (the batch's other
    rows are padding), prefills a chunk padded to its edge, and warm-up's
    dry batches run again: the bystanders' states do not move by a bit."""
    rt, tier = serve(TOY, weights)
    a, b, c = tokens(31, 12), tokens(32, 9), tokens(33, 40)
    sa, sb, sc = (rt.open_session("lm") for _ in range(3))
    feed(rt, sa, a, (8, 4))
    feed(rt, sb, b, (1, 8))
    before = [tier.state_of(s) for s in (sa, sb)]
    got = feed(rt, sc, c, (1, 1, 5, 30, 1, 1, 1))
    rt.warm({"input": np.zeros(1, np.int32)}, model="lm")
    for s, was in zip((sa, sb), before):
        for mine, old in zip(tier.state_of(s), was):
            assert np.array_equal(mine, old)
    assert_session(tier, sc, got, c, weights)
    # and the bystanders go on from where they stood
    more = tokens(34, 3)
    got = feed(rt, sa, np.concatenate([a, more]), (1, 1, 1), at=12)
    assert_session(tier, sa, got, np.concatenate([a, more]), weights)


def test_sessions_turn_over_while_others_decode(weights):
    """A session is closed and another opened — into the slot that was
    freed — while two others keep decoding in the same batches; every
    answer and every state is the reference's for its own session."""
    rt, tier = serve(TOY, weights, max_sessions=3)
    toks = {n: tokens(40 + i, 26) for i, n in enumerate("abcd")}
    sid = {n: rt.open_session("lm") for n in "abc"}
    at = {n: 0 for n in "abcd"}
    got = {n: {} for n in "abcd"}

    def step(names, n_tokens=1):
        reqs = {n: rt.submit_chunk(
            sid[n], {"input": toks[n][at[n]:at[n] + n_tokens]},
            length=n_tokens) for n in names}
        rt.pump(force=True)
        for n, r in reqs.items():
            assert r.state == "done", r.state
            at[n] += n_tokens
            got[n][at[n] - 1] = np.asarray(r.result)

    step("abc", 8)
    for _ in range(4):
        step("abc")
    gone = tier.books.slot_of[sid["b"]]
    tier.record_state([sid["b"]])
    rt.close_session(sid["b"])
    step("ac")
    sid["d"] = rt.open_session("lm")
    step("d", 5)                        # a prefill batch of its own
    assert tier.books.slot_of[sid["d"]] == gone
    for _ in range(6):
        step("acd")                     # the newcomer decodes beside them
    for n in "abcd":
        assert_session(tier, sid[n], got[n], toks[n][:at[n]], weights)
    gauges = tier.registry.snapshot()["gauges"]
    D = ref.dims(TOY)
    assert gauges["lm/ssm_slots_live"] == 3
    assert gauges["lm/ssm_state_bytes"] \
        == 3 * D["layers"] * D["H"] * D["P"] * D["N"] * 4


def test_nothing_is_kept_of_a_session_nobody_asked_about(served):
    rt, tier = served
    sid = rt.open_session("lm")
    feed(rt, sid, tokens(50, 4), (1, 1, 1, 1))
    rt.close_session(sid)
    with pytest.raises(KeyError):
        tier.state_of(sid)


# -- (c) the kernel inside the step, and what makes this model this model ----

def test_lane_wide_states_decode_through_the_kernel(monkeypatch):
    """A toy whose states are whole (8, 128) tiles takes the Pallas kernel
    (in interpret mode here) inside the decode step; the ``jax.numpy`` step
    gives the same logits and states, and both the reference's."""
    config = dict(TOY, num_hidden_layers=2, mamba_d_state=128)
    w = seeded(config)
    cfg = lm.LMConfig.from_dict(config)
    m = cfg.ssm
    assert pallas_ssm_decode.supported(m.heads, m.head, m.state, m.groups)
    geo = lm.CacheGeometry(n_pages=9, page=8, max_pages=4, n_slots=3)
    toks = tokens(5, 14)
    table = jnp.asarray([4, 2, 7, 0], jnp.int32)
    cache = lm.new_cache(cfg, geo)
    cache, *_ = lm.prefill_step(cfg, geo, w, cache, jnp.asarray(toks[:8]),
                                1, 0, 8, table)

    def decode(cache):
        for t in range(8, 14):
            args = (jnp.asarray([0, toks[t]]), jnp.asarray([-1, 1]),
                    jnp.asarray([0, t]),
                    jnp.stack([jnp.zeros_like(table), table]),
                    jnp.zeros((9,), jnp.int32))
            cache, logits, *_ = lm.decode_rows(cfg, geo, w, cache, *args)
        return cache, np.asarray(logits)[1]

    k_cache, kernel = decode(cache)
    monkeypatch.setattr(pallas_ssm_decode, "supported", lambda *a: False)
    p_cache, plain = decode(cache)
    want = ref.forward(config, SEED, toks, weights=w, blocks=BLOCKS)
    for got, c in ((kernel, k_cache), (plain, p_cache)):
        np.testing.assert_allclose(got, np.asarray(want["logits"])[13],
                                   atol=TOL, rtol=0)
        for layer in range(2):
            np.testing.assert_allclose(c["ssm"][layer][1],
                                       want["state"][layer], atol=TOL, rtol=0)
            assert not np.asarray(c["ssm"][layer])[[0, 2]].any()


@pytest.mark.parametrize("fault", [
    "truncate:4", "shift_cache", "state_not_reset", "conv_state_late",
    "pad_advances", "no_dt_bias", "no_D", "one_group", "norm_before_gate",
    "no_mup", "heads_per_kv_8"])
def test_reference_faults_show_against_the_program(served, weights, fault):
    """The program agrees with the sound reference and with no faulty one,
    in its last row of logits or in its states."""
    rt, tier = served
    toks = tokens(11, 30)
    sid = rt.open_session("lm")
    got = feed(rt, sid, toks, (8, 8, 8, 5, 1))
    mine = np.concatenate([s.ravel() for s in tier.state_of(sid)])
    rt.close_session(sid)
    other = dict(tokens=tokens(12, 13))
    sound, faulty = (ref.forward_many(
        TOY, SEED, [other, dict(tokens=toks)], weights=weights,
        blocks=BLOCKS, fault=f)[1] for f in (None, fault))
    flat = lambda out: np.concatenate(                     # noqa: E731
        [np.asarray(s).ravel() for s in out["state"]])
    np.testing.assert_allclose(got[29], np.asarray(sound["logits"])[-1],
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(mine, flat(sound), atol=TOL, rtol=0)
    rel = np.sqrt(np.mean((mine - flat(faulty)) ** 2)
                  / np.mean(flat(sound) ** 2))
    gap = np.abs(got[29] - np.asarray(faulty["logits"])[-1]).max()
    assert gap > 1e-2 or rel > 1e-2, (fault, gap, rel)


def test_a_state_rounded_to_bfloat16_every_token_drifts(weights):
    """The control the cell's ``state_rel_rms`` has to fail: the same
    forward with ``S`` rounded after every token ends a hundred times
    further from the float32 states than round-off puts the program."""
    toks = tokens(13, 120)
    _, sound = reference(weights, toks)
    _, rounded = reference(weights, toks, fault="state_bf16")
    rel = np.sqrt(sum(np.sum((a - b) ** 2) for a, b in zip(sound, rounded))
                  / sum(np.sum(a ** 2) for a in sound))
    assert rel > 100 * TOL


# -- (d) the share and the configuration -------------------------------------

def test_vocabulary_slices_side_by_side_are_the_whole_vocabulary(weights):
    """The deployment divides the head's rows over stages: a stage with
    slice ``j`` of the head (and the embedding rows its tokens come from)
    answers with the whole model's logits over its rows."""
    toks = tokens(61, 12) % 5                  # ids of the first slice
    whole, _ = reference(weights, toks)
    parts = []
    for j in range(8):
        config = dict(TOY, vocab_size=5)
        ends = dict(weights["ends"], embed=weights["ends"]["embed"][:5],
                    head=weights["ends"]["head"][:, 5 * j:5 * j + 5])
        rt, tier = serve(config, dict(weights, ends=ends))
        sid = rt.open_session("lm")
        parts.append(feed(rt, sid, toks, (8, 3, 1))[11])
        rt.close_session(sid)
    np.testing.assert_allclose(np.concatenate(parts), whole[11], atol=TOL,
                               rtol=0)


def test_served_configuration_is_what_its_file_says():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-pp12.json")) as f:
        config = json.load(f)
    cfg = lm.LMConfig.from_dict(config)
    assert cfg.kinds == (lm.CAUSAL,) * 6 and cfg.dense_layers == 6
    assert cfg.full == lm.GQADims(20, 4, 128, 128, 128, 1e11, 1.0, False)
    assert cfg.ssm == lm.SSMDims(32, 128, 256, 2, 4, 128)
    assert (cfg.ssm.inner, cfg.ssm.conv_width, cfg.ssm.proj) \
        == (4096, 5120, 9248)
    assert (cfg.held, cfg.experts, cfg.per_tok, cfg.vocab) == (0, 0, 0, 32640)
    assert cfg.mup == lm.Multipliers(
        embed=5.656854249492381, attn_in=1.0, key=0.011048543456039804,
        attn_out=0.0375, ssm_in=0.25,
        ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
             0.3535533905932738),
        ssm_out=0.08838834764831845, mlp_gate=0.1767766952966369,
        mlp_down=0.011160714285714284, head=0.0078125)
    flat = {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v in
            jax.tree_util.tree_flatten_with_path(lm.param_shapes(cfg))[0]}
    count = lambda keep: sum(int(np.prod(s)) for k, (s, _)  # noqa: E731
                             in flat.items() if keep(k))
    # ISSUE 39's table: a block 430.12 M, the stage 2.915 B
    assert count(lambda k: k.startswith("['layers'][0]['attn']")) == 31457280
    assert count(lambda k: k.startswith("['layers'][0]['ssm']")) == 68351072
    assert count(lambda k: k.startswith("['layers'][0]['mlp']")) == 330301440
    assert count(lambda k: k.startswith("['layers'][0]")) == 430120032
    assert count(lambda k: True) == 6 * 430120032 + 2 * 32640 * 5120 + 5120
    assert flat["['layers'][5]['ssm']['A_log']"] == ((32,), "float32")
    assert not any("moe" in k for k in flat)
    geo = lm.CacheGeometry(n_pages=1476, page=256, max_pages=21, n_slots=128)
    cache = lm.cache_shapes(cfg, geo)
    assert [(v.shape, str(v.dtype)) for v in cache["ssm"]] \
        == [((128, 32, 128, 256), "float32")] * 6
    assert [(v.shape, str(v.dtype)) for v in cache["conv"]] \
        == [((128, 3, 5120), "bfloat16")] * 6
    assert [v.shape for v in cache["kv"]] == [(1476, 256, 1024)] * 6
    assert cache["ik"] == [] and cache["ring"] == []
    # what the file says of itself
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 72
    assert all(config[k] for k in ("deployment", "assumed", "left_out"))


@pytest.mark.parametrize("change,message", [
    ({"attn_layer_indices": [0, 2]}, "state-space mixer"),
    ({"mamba_norm_before_gate": True}, "state-space mixer"),
    ({"num_key_value_heads": None}, "neither kv_lora_rank"),
])
def test_a_config_the_model_cannot_be_is_refused_by_name(change, message):
    config = {k: v for k, v in dict(TOY, **change).items() if v is not None}
    with pytest.raises(ValueError, match=message):
        lm.LMConfig.from_dict(config)


def test_a_model_without_experts_chooses_nothing(weights):
    cfg = lm.LMConfig.from_dict(TOY)
    geo = lm.CacheGeometry(n_pages=5, page=8, max_pages=2, n_slots=2)
    _, logits, counts, chosen = lm.prefill_step(
        cfg, geo, weights, lm.new_cache(cfg, geo),
        jnp.asarray(tokens(3, 8)), 0, 0, 8, jnp.asarray([1, 0], jnp.int32))
    assert counts.shape == (3, 0) and chosen["selected"] == []
    assert chosen["routed"].size == 0 and logits.shape == (1, 40)
    made = lm.init_params(cfg, 0)
    assert jax.tree_util.tree_structure(made) \
        == jax.tree_util.tree_structure(lm.param_shapes(cfg))


@pytest.mark.parametrize("toy,program,text,scopes", [
    ("lm_toy", "decode", "691f70b026500a1f", "d663be26cdd6d859"),
    ("lm_toy", "prefill", "27d7c7ecbe8983a3", "3610de390ec14d5b"),
    ("lm_mla_toy", "decode", "dfb009b1ad9811c6", "2f79f68be408fbca"),
    ("lm_mla_toy", "prefill", "b7376527b5f9763e", "97a8a984d281daee"),
    ("lm_gqa_toy", "decode", "493ebd5061cd864e", "46227f1397096846"),
    ("lm_gqa_toy", "prefill", "3e34b80840374f97", "4045a9966d42e5fa"),
    ("lm_ssm_toy", "decode", "c7beb0bd68165361", "09d40f5afd66d751"),
    ("lm_ssm_toy", "prefill", "38df50ed73fd6947", "24ff44bad2e266fa"),
])
def test_the_other_models_step_programs_lower_as_they_did(toy, program, text,
                                                          scopes):
    """The four toys' step programs as lowered text (locations cut out),
    a digest taken at commit 008c4ee (the state-space toy's at dde06de):
    no multiplier of 1 is multiplied in, no empty list of states is an
    argument, and the grouped-query kernel's wider layout pads nothing at
    MiMo's 16 heads a KV head.  And the ``lm/`` scope of
    every op, in program order (what the decoder cells' device time by
    scope reads, benchmarks/lm_scopes.py), as a digest taken at commit
    dde06de: an op moved from one scope to another fails."""
    import importlib
    import re

    cfg = lm.LMConfig.from_dict(importlib.import_module(toy).TOY)
    geo = lm.CacheGeometry(n_pages=25, page=4, max_pages=12, n_slots=4)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    params, cache = lm.param_shapes(cfg), lm.cache_shapes(cfg, geo)
    if program == "decode":
        lowered = jax.jit(
            lambda p, c, r: lm.decode_step(cfg, geo, p, c, r)).lower(
            params, cache, S((4 * (3 + geo.max_pages) + geo.n_pages,), i32))
    else:
        lowered = jax.jit(
            lambda p, c, t, s, st, n, tb: lm.prefill_step(
                cfg, geo, p, c, t, s, st, n, tb)).lower(
            params, cache, S((8,), i32), S((), i32), S((), i32), S((), i32),
            S((geo.max_pages,), i32))
    got = re.sub(r"loc\(.*?\)", "", lowered.as_text())
    assert hashlib.sha256(got.encode()).hexdigest()[:16] == text
    # an op's location is an alias; a named one reads
    # ``#locN = loc("jit(..)/lm/<scope>/../<op>"(#locM))``, #locM its file
    # and line, which are left out
    debug = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(', debug, re.M))
    seq = []
    for line in debug.splitlines():
        op = re.search(r"loc\((#loc\d+)\)$", line)
        if op and not line.startswith("#loc"):
            scope = re.search(r"(?:^|/)(lm/\w+)", names.get(op.group(1), ""))
            seq.append(scope.group(1) if scope else "")
    assert any(seq)
    assert hashlib.sha256("\n".join(seq).encode()).hexdigest()[:16] == scopes
