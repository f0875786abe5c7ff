"""The decoder LM against its plain reference, at toy size on the CPU
(tests/lm_toy.py: every width small, 8 experts of which 4 held, 2 full +
3 sliding layers, window 5, top-k 4), in float32 on both sides — so every
tolerance below is round-off of two orders of summation, not precision."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_toy import TOY, tokens  # noqa: E402

from analytics_zoo_tpu.models import lm  # noqa: E402
from analytics_zoo_tpu.parallel import expert  # noqa: E402
from analytics_zoo_tpu.parallel import (moe_held_experts,  # noqa: E402
                                        moe_held_experts_parallel,
                                        route_topk_sigmoid)
from analytics_zoo_tpu.pipelines import lm as lm_pipeline  # noqa: E402
from analytics_zoo_tpu.pipelines.lm import (CacheExhausted,  # noqa: E402
                                            lm_serving_tiers, make_lm_model)
from analytics_zoo_tpu.serving import ServingRuntime  # noqa: E402
from analytics_zoo_tpu.serving.runtime import ModelConfig  # noqa: E402
from benchmarks.reference import lm as ref  # noqa: E402

SEED = 7
#: float32 both sides; logits are O(1): sums of a few hundred terms in two
#: orders differ by a few units of 1e-7 a term
TOL = 2e-5
BLOCKS = {"q_block": 8, "idx_q_block": 4, "head_group": 2, "key_round": 16,
          "mlp_block": 16, "pad_to": 48, "expert_group": 4}


class Clock:
    t = 0.0

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += s


@pytest.fixture(scope="module")
def weights():
    w = {"layers": [ref.layer_weights(SEED, TOY, i) for i in range(5)],
         "ends": ref.end_weights(SEED, TOY)}
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


def serve(weights, max_batch=4, retain_requests=True, toy=TOY, **tier_args):
    """(runtime, the replica's tier) over the toy model."""
    model = make_lm_model(toy, params=weights)
    args = dict(cache_tokens=96, max_sessions=4, max_batch=max_batch,
                page=4, max_len=48)
    args.update(tier_args)
    tiers = lm_serving_tiers(model, **args)
    mc = ModelConfig(name="lm", streaming=True, serial_chunks=True,
                     tiers=tiers, tier_factory=lambda rid: tiers,
                     pad_key="input", length_key="n_tokens",
                     bucket_edges=[1, 4, 8], max_batch=max_batch,
                     chunk_deadline_s=1e9)
    rt = ServingRuntime(models=[mc], n_replicas=1, max_batch=max_batch,
                        queue_capacity=16, clock=Clock(),
                        service_time=lambda *a: 0.0,
                        retain_requests=retain_requests)
    return rt, tiers[0]


@pytest.fixture(scope="module")
def served(weights):
    rt, tier = serve(weights)
    rt.warm({"input": np.zeros(1, np.int32)}, model="lm")
    return rt, tier


def run_session(rt, toks, chunks, sid=None):
    """Feed ``toks`` in ``chunks``; the logits each chunk returned, keyed
    by the position of its last token."""
    sid = rt.open_session("lm") if sid is None else sid
    out, p = {}, 0
    for n in chunks:
        r = rt.submit_chunk(sid, {"input": toks[p:p + n]}, length=n)
        rt.pump(force=True)
        assert r.state == "done", r.state
        p += n
        out[p - 1] = np.asarray(r.result)
    return sid, out


def reference_logits(weights, toks):
    return np.asarray(ref.forward(TOY, SEED, toks, weights=weights,
                                  blocks=BLOCKS)["logits"])


# -- (a) chunked prefill, then decode through the cache ---------------------

@pytest.mark.parametrize("name,chunks", [
    ("shorter_than_topk", (2, 1)),
    ("longer_than_topk", (8, 3, 1, 1, 1)),
    ("wraps_the_ring", (8, 6, 1, 1, 1, 1, 1, 4, 1, 1)),
    ("ragged_chunks", (5, 7, 2, 1, 8, 1)),
])
def test_prefill_then_decode_equals_full_forward(served, weights, name,
                                                 chunks):
    rt, _ = served
    toks = tokens(hash(name) % 1000, sum(chunks))
    sid, got = run_session(rt, toks, chunks)
    rt.close_session(sid)
    want = reference_logits(weights, toks)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0,
                                   err_msg=f"{name}: position {pos}")


def test_lane_aligned_full_layers_decode_through_the_one_pass_kernel():
    """A toy whose latent is a whole lane tile and whose ``index_topk`` is
    whole sublane tiles: its full layers' decode attention is
    ``selected_mla_decode`` (interpreted here), the tier's gauge says so,
    and prefill then decode still equal the full forward."""
    toy = dict(TOY, kv_lora_rank=128, index_topk=16)
    w = {"layers": [ref.layer_weights(SEED, toy, i) for i in range(5)],
         "ends": ref.end_weights(SEED, toy)}
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    rt, tier = serve(w, toy=toy)
    toks = tokens(38, 23)
    _, got = run_session(rt, toks, (8, 8, 4, 1, 1, 1))
    want = np.asarray(ref.forward(toy, SEED, toks, weights=w,
                                  blocks=BLOCKS)["logits"])
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)
    assert tier.registry.snapshot()["gauges"]["lm/selected_one_pass"] == 2


def test_long_chunk_runs_as_blocks(weights, monkeypatch):
    """A chunk longer than ``PREFILL_BLOCK`` runs as consecutive calls and
    gives the same logits."""
    monkeypatch.setattr(lm_pipeline, "PREFILL_BLOCK", 4)
    rt, _ = serve(weights, cache_tokens=100)
    toks = tokens(3, 15)
    _, got = run_session(rt, toks, (8, 7))
    want = reference_logits(weights, toks)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)


# -- (b) the shares add up --------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """The held experts' parts over both shares, the shared expert counted
    once, equal the reference's layer with all 8 experts held."""
    D = ref.dims(TOY)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D["d"]), jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    whole = ref.mlp_weights(k[0], D["d"], D["f_expert"], (D["experts"],))
    whole = {n: v.astype(jnp.float32) for n, v in whole.items()}
    base = weights["layers"][1]["moe"]
    uncut = dict(base, experts=whole)
    want, *_ = ref.moe(x, uncut, D, first_held=0, held=D["experts"])
    total = jnp.zeros_like(x)
    for share in range(D["experts"] // D["held"]):
        lo = share * D["held"]
        part = dict(base, experts={n: v[lo:lo + D["held"]]
                                   for n, v in whole.items()})
        y, _, counts = moe_held_experts(x, part, lo, D["per_tok"],
                                        D["route_scale"],
                                        shared=(share == 0))
        mine, *_ = ref.moe(x, part, D, first_held=lo, held=D["held"],
                           shared=(share == 0))
        np.testing.assert_allclose(y, mine, atol=TOL, rtol=0)
        # both forms of the held experts' part, whatever the token count
        # would have picked
        routed, *_ = ref.moe(x, part, D, first_held=lo, held=D["held"],
                             shared=False)
        for form in (expert._held_dense, expert._held_grouped):
            np.testing.assert_allclose(held_part(form, x, part, lo, D),
                                       routed, atol=TOL, rtol=0)
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert int(counts.sum()) <= x.shape[0] * D["per_tok"]


def held_part(form, x, part, lo, D):
    """One form of the held experts' part, called as
    ``held_experts_apply`` calls it."""
    chosen, weights = route_topk_sigmoid(
        x, part["router_w"], part["router_b"], D["per_tok"],
        D["route_scale"])
    h = part["experts"]["w_gate"].shape[0]
    local = chosen - lo
    local = jnp.where((local >= 0) & (local < h), local, h)
    counts = jnp.sum(jax.nn.one_hot(local, h + 1, dtype=jnp.int32),
                     (0, 1))[:h]
    return form(x, part["experts"], local, weights, counts)


def test_token_count_alone_picks_the_form(monkeypatch):
    """Up to ``DENSE_BELOW`` tokens every token runs through every held
    expert; one more and the pairs are grouped."""
    called = []
    for name in ("_held_dense", "_held_grouped"):
        monkeypatch.setattr(expert, name, lambda x, *a, _n=name: (
            called.append(_n), x)[1])
    e = {"w_gate": jnp.zeros((2, 4, 3))}
    for n in (expert.DENSE_BELOW, expert.DENSE_BELOW + 1):
        expert.held_experts_apply(jnp.zeros((n, 4)), e,
                                  jnp.zeros((n, 2), jnp.int32),
                                  jnp.ones((n, 2)), 0)
    assert called == ["_held_dense", "_held_grouped"]


def test_grouped_experts_small_buffer_and_its_fallback():
    """The grouped product runs over a buffer of twice the tokens; a step
    whose router sends more pairs here than that takes the full buffer.
    Both equal running every token through every held expert."""
    n, d, f, E, H, k = expert.DENSE_BELOW + 8, 16, 8, 32, 4, 8
    kx, kw, kr = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    experts = {name: v.astype(jnp.float32) for name, v in
               ref.mlp_weights(kw, d, f, (H,)).items()}
    router = jax.random.normal(kr, (d, E), jnp.float32)
    for bias, full in ((jnp.zeros(E), False),
                       (jnp.zeros(E).at[:H].set(50.0), True)):
        part = {"router_w": router, "router_b": bias, "experts": experts}
        D = {"per_tok": k, "route_scale": 1.0}
        dense = held_part(expert._held_dense, x, part, 0, D)
        grouped, counts = expert.held_experts_apply(
            x, experts, *route_topk_sigmoid(x, router, bias, k, 1.0), 0)
        assert n > expert.DENSE_BELOW       # the grouped form, by count
        assert (int(counts.sum()) > 2 * n) == full
        np.testing.assert_allclose(grouped, dense, atol=TOL, rtol=0)


# -- (c) the router ---------------------------------------------------------

def test_router_matches_reference_on_ties_and_bias():
    """Equal scores go to the lower expert id on both sides; the bias
    moves the choice but not the weights (which are the chosen SCORES
    over their sum)."""
    d, E, k = 4, 8, 2
    x = jnp.ones((3, d), jnp.float32)
    w = jnp.zeros((d, E), jnp.float32).at[:, 5].set(0.3)   # all tie but 5
    for bias in (jnp.zeros(E), jnp.zeros(E).at[2].set(0.5),
                 jnp.zeros(E).at[5].set(-5.0)):
        got_c, got_w = route_topk_sigmoid(x, w, bias, k, 1.0)
        want_c, want_w = ref.route(x, w, bias, k, 1.0)
        np.testing.assert_array_equal(got_c, want_c)
        np.testing.assert_allclose(got_w, want_w, atol=1e-6)
    chosen, wts = route_topk_sigmoid(x, w, jnp.zeros(E).at[2].set(0.5),
                                     k, 1.0)
    assert set(np.asarray(chosen[0]).tolist()) == {2, 5}
    s = jax.nn.sigmoid(jnp.asarray([0.0, 1.2]))            # expert 2, 5
    np.testing.assert_allclose(sorted(np.asarray(wts[0])),
                               sorted(np.asarray(s / s.sum())), atol=1e-6)


# -- (d) rows of different sessions in one batch ----------------------------

def test_two_sessions_in_one_batch_equal_each_alone(served, weights):
    rt, _ = served
    a, b = tokens(11, 14), tokens(12, 9)
    sa, sb = rt.open_session("lm"), rt.open_session("lm")
    got = {sa: {}, sb: {}}
    plan = {sa: (a, [8, 4, 1, 1]), sb: (b, [3, 4, 1, 1])}
    at = {sa: 0, sb: 0}
    for step in range(4):
        reqs = {}
        for sid, (toks, chunks) in plan.items():
            n = chunks[step]
            reqs[sid] = (rt.submit_chunk(
                sid, {"input": toks[at[sid]:at[sid] + n]}, length=n), n)
        rt.pump(force=True)
        for sid, (r, n) in reqs.items():
            assert r.state == "done"
            at[sid] += n
            got[sid][at[sid] - 1] = np.asarray(r.result)
    for sid, toks in ((sa, a), (sb, b)):
        rt.close_session(sid)
        want = reference_logits(weights, toks)
        for pos, row in got[sid].items():
            np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)


# -- (e) eviction -----------------------------------------------------------

def test_eviction_frees_the_pool_and_leaves_no_trace(weights):
    rt, tier = serve(weights, cache_tokens=24, max_sessions=1, max_len=24)
    books = tier.books
    free0 = len(books.free_pages)
    old = tokens(21, 20)
    sid, _ = run_session(rt, old, (8, 8, 4))
    assert len(books.free_pages) == free0 - 5 and not books.free_slots
    with pytest.raises(Exception):          # no slot for a second session
        run_session(rt, old, (4,))
    rt.close_session(sid)                   # evict_session on the tier
    assert len(books.free_pages) == free0 and books.tokens == 0
    new = tokens(22, 11)
    _, got = run_session(rt, new, (8, 1, 1, 1))
    want = reference_logits(weights, new)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)


def test_final_chunk_retires_the_session(served):
    rt, tier = served
    before = len(tier.books.free_pages)
    sid = rt.open_session("lm")
    r = rt.submit_chunk(sid, {"input": tokens(5, 6)}, length=6, final=True)
    rt.pump(force=True)
    assert r.state == "done" and len(tier.books.free_pages) == before
    gauges = tier.registry.snapshot()["gauges"]
    assert gauges["lm/sessions_live"] == len(tier.books.slot_of)
    assert gauges["lm/selected_one_pass"] == 0     # a toy's widths: XLA


def test_admission_refuses_what_does_not_fit(weights):
    _, tier = serve(weights, cache_tokens=8, max_sessions=2, max_len=8)
    books = tier.books
    books.admit(1, 8)
    with pytest.raises(CacheExhausted, match="page"):
        books.admit(2, 1)
    with pytest.raises(CacheExhausted, match="max_len"):
        books.admit(1, 1)
    assert 2 not in books.slot_of and len(books.free_slots) == 1


# -- (f) one chip and the expert axis ---------------------------------------

def test_expert_axis_path_equals_the_shares(weights):
    D = ref.dims(TOY)
    n = D["experts"] // D["held"]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("expert",))
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    whole = {name: v.astype(jnp.float32) for name, v in ref.mlp_weights(
        k[0], D["d"], D["f_expert"], (D["experts"],)).items()}
    params = dict(weights["layers"][2]["moe"], experts=whole)
    x = jax.random.normal(k[1], (16, D["d"]), jnp.float32)
    got = moe_held_experts_parallel(x, params, mesh, D["per_tok"],
                                    D["route_scale"])
    want = jnp.zeros_like(x)
    for share in range(n):
        lo = share * D["held"]
        part = dict(params, experts={name: v[lo:lo + D["held"]]
                                     for name, v in whole.items()})
        y, _, _ = moe_held_experts(x, part, lo, D["per_tok"],
                                   D["route_scale"], shared=(share == 0))
        want = want + y
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# -- the runtime's side ------------------------------------------------------

def test_serial_chunks_refuses_a_second_chunk_in_flight(served):
    rt, _ = served
    sid = rt.open_session("lm")
    rt.submit_chunk(sid, {"input": tokens(1, 4)}, length=4)
    with pytest.raises(RuntimeError, match="serial_chunks"):
        rt.submit_chunk(sid, {"input": tokens(1, 1)}, length=1)
    rt.pump(force=True)
    rt.submit_chunk(sid, {"input": tokens(1, 1)}, length=1)
    rt.pump(force=True)
    rt.close_session(sid)


def test_warm_compiles_every_edge_and_touches_no_session(weights):
    rt, tier = serve(weights, cache_tokens=40)
    assert tier.pads_session_rows
    took = rt.warm({"input": np.zeros(1, np.int32)}, model="lm")
    assert sorted(k[1] for k in took) == [1, 4, 8]
    assert tier.books.tokens == 0 and not tier.books.slot_of


def test_warm_is_refused_unless_the_tier_pads_session_rows(weights):
    """``serial_chunks`` says nothing about a dry run: a session tier
    that steps whatever its rows name keeps the old refusal."""
    rt, tier = serve(weights, cache_tokens=44)
    tier.pads_session_rows = False
    with pytest.raises(ValueError, match="no dry run"):
        rt.warm({"input": np.zeros(1, np.int32)}, model="lm")


def test_answers_reach_the_caller_without_retained_requests(weights):
    """``retain_requests=False``: the runtime lists no request, and the
    caller's ``Request`` still gets its row of logits."""
    rt, _ = serve(weights, retain_requests=False, cache_tokens=52)
    toks = tokens(31, 9)
    _, got = run_session(rt, toks, (8, 1))
    assert not rt.requests
    want = reference_logits(weights, toks)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)


@pytest.mark.parametrize("B,max_pages,n_pages", [(1, 1, 2), (4, 12, 25),
                                                 (64, 88, 1751)])
def test_packed_rows_unpack_to_the_step_s_arguments(B, max_pages, n_pages):
    """The tier's one transfer a decode step: ``pack_rows`` on the host,
    ``unpack_rows`` inside the jitted step, B read off the length."""
    rng = np.random.default_rng(B)
    geo = lm.CacheGeometry(n_pages=n_pages, page=4, max_pages=max_pages,
                           n_slots=B)
    args = (rng.integers(0, 99, B), rng.integers(-1, B, B).astype(np.int64),
            rng.integers(0, 40, B), rng.integers(0, n_pages, (B, max_pages)),
            rng.integers(-1, B, n_pages))
    rows = lm.pack_rows(*args)
    assert rows.dtype == np.int32 and rows.ndim == 1
    got = jax.jit(lambda r: lm.unpack_rows(geo, r))(jnp.asarray(rows))
    for mine, want in zip(got, args):
        np.testing.assert_array_equal(np.asarray(mine), want)


def test_a_decode_batch_is_handed_out_without_a_second_copy(served):
    """The tier returns the (max_batch, vocab) array itself, so the rows
    the callers get are views of ONE array (a list of rows was stacked
    again, the whole batch copied, in the runtime's handout)."""
    rt, _ = served
    sids = [rt.open_session("lm") for _ in range(3)]
    for step in (3, 1):                     # a prefill chunk, then a decode
        reqs = [rt.submit_chunk(sid, {"input": tokens(40 + i, step)},
                                length=step) for i, sid in enumerate(sids)]
        rt.pump(force=True)
        assert all(r.state == "done" for r in reqs)
    rows = [np.asarray(r.result) for r in reqs]
    assert rows[0].base is not None
    assert all(r.base is rows[0].base for r in rows)
    assert rows[0].base.shape == (4, TOY["vocab_size"])
    for sid in sids:
        rt.close_session(sid)


# -- the steps' discrete choices ----------------------------------------------

def packed_rows(log, length):
    """A session's recorded selections as the reference's ``follow``
    takes them: uint8 (length, ceil(length / 8)) a full layer."""
    cols = -(-length // 8)
    out = [np.zeros((length, cols), np.uint8) for _ in log[0][2]["selected"]]
    for start, n, chosen in log:
        for layer, sel in enumerate(chosen["selected"]):
            if sel.dtype == np.uint8:               # a prefill call's rows
                out[layer][start:start + n, :sel.shape[1]] = sel
            else:                                   # a decode step's row
                bits = np.zeros(cols * 8, np.uint8)
                bits[sel[0][sel[0] >= 0]] = 1
                out[layer][start] = np.packbits(bits)
    return out


def test_recorded_choices_are_the_reference_s_own(weights):
    """In float32 the program selects and routes as the reference does:
    what ``record_choices`` keeps for a session, prefill calls and decode
    steps, equals the reference's ``emit``; a session nobody asked about
    leaves nothing."""
    rt, tier = serve(weights, cache_tokens=60)
    toks, other = tokens(41, 19), tokens(42, 6)
    sid, quiet = rt.open_session("lm"), rt.open_session("lm")
    tier.record_choices([sid])
    run_session(rt, toks, (8, 7, 1, 1, 1, 1), sid=sid)
    run_session(rt, other, (4, 1, 1), sid=quiet)
    tier.record_choices([])
    assert set(tier.choices) == {sid}
    log = tier.choices[sid]
    assert [(s, n) for s, n, _ in log] == [(0, 8), (8, 7), (15, 1), (16, 1),
                                           (17, 1), (18, 1)]
    want = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                       emit=True)
    full = [i for i, k in enumerate(TOY["layer_types"][:5])
            if k == "full_attention"]
    for mine, layer in zip(packed_rows(log, len(toks)), full):
        np.testing.assert_array_equal(mine, want["selected"][layer])
    routed = np.concatenate([c["routed"] for _, _, c in log], 1)
    for mine, layer in zip(routed, sorted(want["chosen"])):
        np.testing.assert_array_equal(np.sort(mine, 1),
                                      np.sort(want["chosen"][layer], 1))


def test_reference_follows_given_choices(weights):
    """``follow`` with the forward's own choices changes nothing and
    counts no difference; with another side's it takes theirs, and
    counts on how many its own differ."""
    toks = tokens(43, 21)
    own = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                      emit=True)
    follow = {"selected": own["selected"], "routed": own["chosen"]}
    keep = [12, 20]
    same = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                       follow=follow, keep=keep)
    np.testing.assert_allclose(same["logits"],
                               np.asarray(own["logits"])[keep], atol=TOL)
    assert same["miss"]["select"][0] == 0 and same["miss"]["route"][0] == 0
    assert same["miss"]["select"][1] == 2 * 2 * 2 * TOY["index_topk"]
    # the first selected position of the last query moved to the one
    # position it had not selected; one token routed elsewhere
    theirs = {"selected": {i: m.copy() for i, m in own["selected"].items()},
              "routed": {i: c.copy() for i, c in own["chosen"].items()}}
    row = np.unpackbits(theirs["selected"][0][20])[:21]
    out_, in_ = np.flatnonzero(row)[0], np.flatnonzero(row == 0)[0]
    row[[out_, in_]] = [0, 1]
    theirs["selected"][0][20] = np.packbits(np.pad(row, (0, 3)))
    layer = min(theirs["routed"])
    theirs["routed"][layer][5] = (theirs["routed"][layer][5] + 1) % 8
    moved = ref.forward(TOY, SEED, toks, weights=weights, blocks=BLOCKS,
                        follow=theirs, keep=keep)
    assert moved["miss"]["select"][0] >= 2 and moved["miss"]["route"][0] >= 1
    assert np.abs(np.asarray(moved["logits"])
                  - np.asarray(same["logits"])).max() > 100 * TOL
    np.testing.assert_array_equal(moved["chosen"][layer][5],
                                  theirs["routed"][layer][5])


def test_config_reads_the_published_keys():
    cfg = lm.LMConfig.from_dict(TOY)
    assert cfg.kinds == tuple(TOY["layer_types"]) and cfg.n_full == 2
    assert (cfg.experts, cfg.held, cfg.first_held) == (8, 4, 0)
    assert cfg.full.entry % lm.LANE == 0 and cfg.swa.entry % lm.LANE == 0
    shapes = lm.param_shapes(cfg)
    made = lm.init_params(cfg, 0)
    assert jax.tree_util.tree_structure(shapes) \
        == jax.tree_util.tree_structure(made)
    assert shapes["layers"][1]["moe"]["router_w"].shape == (32, 8)


@pytest.mark.parametrize("name,geo,leaves,count,tree,cache", [
    ("dots3-note-prev-ep8", (2401, 512, 136, 64), 98, 4087154176,
     "09edca6ccd04cd81", "c24b4c820c95c402"),
    ("ax-k1-ep16", (1751, 512, 88, 64), 79, 3491257344,
     "b3901c3932d8e722", "8286b4e17aca8eb3"),
])
def test_the_benchmark_s_latent_models_are_what_they_were(name, geo, leaves,
                                                          count, tree, cache):
    """``LMConfig.from_dict`` of the two configuration files the benchmark
    had before the grouped-query form joined the module: every leaf's
    name, shape and dtype of the parameter tree and of the cache at the
    cell's geometry, as a digest taken at PR 34."""
    import hashlib
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    assert isinstance(cfg.full, lm.MLADims) \
        and isinstance(cfg.swa, (lm.MLADims, type(None)))

    def flat(shapes):
        return [(jax.tree_util.keystr(p), v.shape, str(v.dtype)) for p, v
                in jax.tree_util.tree_flatten_with_path(shapes)[0]]

    def digest(rows):
        return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]

    params = flat(lm.param_shapes(cfg))
    assert len(params) == leaves
    assert sum(int(np.prod(s)) for _, s, _ in params) == count
    assert digest(params) == tree
    assert digest(flat(lm.cache_shapes(cfg, lm.CacheGeometry(*geo)))) == cache


def test_kept_rows_alone_equal_the_whole_forward(weights):
    """With ``keep`` a layer computes only the rows the kept ones can
    see; their logits are the whole forward's, one session or several in
    one call."""
    a, b = tokens(44, 40), tokens(45, 23)
    keep_a, keep_b = [33, 39], [22]
    whole = [np.asarray(reference_logits(weights, t)) for t in (a, b)]
    got = ref.forward_many(TOY, SEED, [dict(tokens=a, keep=keep_a),
                                       dict(tokens=b, keep=keep_b)],
                           weights=weights, blocks=dict(BLOCKS, pad_to=16))
    np.testing.assert_allclose(got[0]["logits"], whole[0][keep_a], atol=TOL)
    np.testing.assert_allclose(got[1]["logits"], whole[1][keep_b], atol=TOL)
    # rows before the first a layer computes — the block of ``pad_to`` rows
    # that holds the first kept one — are left out
    last = max(got[0]["chosen"])
    assert (got[0]["chosen"][last][:32] == -1).all() \
        and (got[0]["chosen"][last][32:] >= 0).all()
