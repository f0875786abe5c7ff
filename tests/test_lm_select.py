"""``ops/lm_attention.py::select_topk`` — decode's selection by threshold,
tie room and a counting compaction — against ``lax.top_k``, whose SET it
has to return (ties to the earlier position), and the toy decode step's
program, which may hold no sort.  Then what follows the selection in a
full layer (PR 38): the selected positions' addresses in the pool without
a gather, and the decode step with them, and with the one-pass attention
kernel, against the parent's forms."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lm_toy import TOY  # noqa: E402

from analytics_zoo_tpu.models import lm  # noqa: E402
from analytics_zoo_tpu.ops import lm_attention as att  # noqa: E402
from benchmarks import hlo_scopes  # noqa: E402
from benchmarks.reference import lm as ref  # noqa: E402

#: n is no multiple of a group of words (32 × GROUP positions), nor of 32
N, K = 1500, 48


def scores_of(kind: str, rows: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(len(kind) * 1000 + n)
    s = rng.standard_normal((rows, n)).astype(np.float32)
    if kind == "tied":            # nine values: ties straddle the threshold
        s = np.round(s * 2) / 2
    if kind == "constant":        # the k earliest positions win
        s = np.full_like(s, 0.25)
    if kind == "negative":        # under zero, where the bits' order turns
        s = -np.abs(s) - 1.0
    return s


def check(scores, lengths, k):
    """The new selection on ``scores`` (rows, n) at ``lengths`` (rows,):
    top_k's set a row, ascending, the right count, every index in range."""
    n = scores.shape[1]
    lengths = np.asarray(lengths, np.int32)
    idx, valid = jax.jit(att.select_topk, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(lengths), k)
    idx, valid = np.asarray(idx), np.asarray(valid)
    masked = jnp.where(np.arange(n)[None, :] < lengths[:, None],
                       jnp.asarray(scores), att.NEG)
    top, want = (np.asarray(a) for a in lax.top_k(masked, min(k, n)))
    assert idx.shape == valid.shape == (len(lengths), min(k, n))
    assert ((idx >= 0) & (idx < n)).all()
    np.testing.assert_array_equal(valid.sum(1), np.minimum(lengths, k))
    for row in range(len(lengths)):
        mine = idx[row][valid[row]]
        assert (np.diff(mine) > 0).all()
        assert set(mine.tolist()) == set(
            want[row][top[row] > att.NEG / 2].tolist()), (row, lengths[row])
    return idx, valid


LENGTHS = {"padding_row": [0, 0, 0], "short": [5, 1, K - 1],
           "exactly_k": [K, K, K], "k_plus_one": [K + 1, K + 1, K + 2],
           "whole_row": [N, N, N - 1], "mixed": [0, K, N]}


@pytest.mark.parametrize("length", list(LENGTHS))
@pytest.mark.parametrize("kind", ["distinct", "tied", "constant", "negative"])
def test_selects_top_k_s_set(kind, length):
    check(scores_of(kind, 3, N), LENGTHS[length], K)


@pytest.mark.parametrize("n,k,rows", [
    (69632, 2048, 2),            # the LM cell's own geometry
    (4100, 2048, 3),             # k over half of n, n off every boundary
    (1025, 1024, 2),             # one position to drop
    (2048, 32, 4),               # exactly two groups of words
    (48, 4, 4),                  # tests/lm_toy.py's
    (40, 64, 3),                 # n <= k: every live position, in order
])
@pytest.mark.parametrize("kind", ["distinct", "tied"])
def test_geometries(kind, n, k, rows):
    rng = np.random.default_rng(n + k)
    lengths = np.concatenate(
        [[n], rng.integers(0, n + 1, rows - 1)]).astype(np.int32)
    check(scores_of(kind, rows, n), lengths, k)


def test_the_earliest_ties_win():
    scores = np.zeros((2, N), np.float32)
    scores[0, 100:110] = 1.0           # ten over the threshold, 38 ties fit
    scores[1, 7] = -1.0                # under it: position 48 gets its place
    idx, valid = check(scores, [N, N], K)
    assert valid.all()
    assert idx[0].tolist() == list(range(38)) + list(range(100, 110))
    assert idx[1].tolist() == [p for p in range(K + 1) if p != 7]


def test_nth_set_bit_and_pack_words():
    rng = np.random.default_rng(5)
    mask = rng.random((3, 96)) < 0.4
    words = np.asarray(att.pack_words(jnp.asarray(mask)))
    for row in range(3):
        at = np.nonzero(mask[row])[0]
        for w in range(3):
            mine = at[(at >= 32 * w) & (at < 32 * w + 32)] - 32 * w
            assert words[row, w] == sum(1 << int(b) for b in mine)
            got = att.nth_set_bit(
                jnp.full((len(mine),), words[row, w], jnp.uint32),
                jnp.arange(len(mine)))
            assert np.asarray(got).tolist() == mine.tolist()


def test_decode_step_holds_no_sort_under_the_selection():
    cfg = lm.LMConfig.from_dict(TOY)
    geo = lm.CacheGeometry(n_pages=25, page=4, max_pages=12, n_slots=4)
    weights = {"layers": [ref.layer_weights(7, TOY, i) for i in range(5)],
               "ends": ref.end_weights(7, TOY)}
    B = 4
    S = jax.ShapeDtypeStruct
    text = lm.decode_jit.lower(
        cfg, geo, weights, lm.cache_shapes(cfg, geo),
        S((B * (3 + geo.max_pages) + geo.n_pages,), jnp.int32)
    ).compile().as_text()
    assert geo.max_len > cfg.topk        # the long path
    by_scope = hlo_scopes.scope_map(text)
    assert by_scope["lm/select"] and by_scope["lm/experts"]
    names = set(by_scope["lm/select"])
    mine = [ln for ln in text.splitlines()
            if (m := hlo_scopes._INSTRUCTION.match(ln))
            and m.group(1) in names]
    assert len(mine) >= len(names)
    for line in mine:
        assert not re.search(r"\bsort\(|top_?k", line, re.I), line
    # the router's top-8 stands elsewhere, and is found by the same reading
    assert re.search(r"top_?k", text, re.I)


# -- the selected positions' addresses in the pool: no gather (PR 38) -------

PAGE, MAX_PAGES = 16, 12            # 192 positions a row, k = 48 of them
ADDRESS_ROWS = {"padding_row": 0, "one_token": 1, "k_minus_one": K - 1,
                "exactly_k": K, "k_plus_one": K + 1,
                "ends_inside_a_page": 5 * PAGE + 3,
                "fills_max_pages": PAGE * MAX_PAGES}


def address_case(length: int, order: str):
    """One row of ``length`` tokens among two others, its selection as
    decode makes it, and page tables in the given ``order``."""
    rng = np.random.default_rng(length)
    lengths = np.asarray([length, PAGE * MAX_PAGES, 37], np.int32)
    free = np.arange(1, 3 * MAX_PAGES + 1)
    if order == "descending":
        free = free[::-1]
    if order == "shuffled":
        free = rng.permutation(free)
    tables = np.zeros((3, MAX_PAGES), np.int32)      # page 0: nobody's
    for b, n in enumerate(lengths):
        held = -(-int(n) // PAGE)
        tables[b, :held], free = free[:held], free[held:]
    scores = rng.standard_normal((3, PAGE * MAX_PAGES)).astype(np.float32)
    idx, valid = att.select_topk(jnp.asarray(scores), jnp.asarray(lengths), K)
    return tables, np.asarray(idx), np.asarray(valid), lengths


@pytest.mark.parametrize("order", ["ascending", "descending", "shuffled"])
@pytest.mark.parametrize("row", list(ADDRESS_ROWS))
def test_addresses_without_a_gather_are_the_page_tables(row, order):
    tables, idx, valid, lengths = address_case(ADDRESS_ROWS[row], order)
    got = np.asarray(jax.jit(att.selected_addresses, static_argnums=2)(
        jnp.asarray(tables), jnp.asarray(idx), PAGE))
    want = tables[np.arange(3)[:, None], idx // PAGE] * PAGE + idx % PAGE
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(valid.sum(1), np.minimum(lengths, K))
    # a slot past a row's count reads the row's first entry (page 0's for
    # a padding row), which the mask then drops
    np.testing.assert_array_equal(
        got[~valid], np.broadcast_to(tables[:, :1] * PAGE, got.shape)[~valid])


def test_addresses_reach_the_last_word_of_the_pool():
    """The cell's own widths: the sum is exact where a float's would not
    be (2,399 x 512 + 511 needs 21 bits)."""
    tables = np.zeros((2, 136), np.int32)
    tables[0, 135], tables[1, 0] = 2399, 2399
    idx = np.asarray([[135 * 512 + 511, 0], [511, 512]], np.int32)
    got = np.asarray(att.selected_addresses(jnp.asarray(tables),
                                            jnp.asarray(idx), 512))
    assert got.tolist() == [[2399 * 512 + 511, 0], [2399 * 512 + 511, 0]]


def toy_step(toy):
    """The decode step of ``toy`` over a seeded cache and five rows — a
    padding row, a row of 1 token, rows of topk, topk + 1 and max_len
    tokens, pages handed out in no order: (cfg, the rows' lengths, a thunk
    that traces and runs the step anew)."""
    cfg = lm.LMConfig.from_dict(toy)
    geo = lm.CacheGeometry(n_pages=25, page=4, max_pages=12, n_slots=4)
    weights = {"layers": [ref.layer_weights(7, toy, i) for i in range(5)],
               "ends": ref.end_weights(7, toy)}
    rng = np.random.default_rng(38)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), s.dtype),
        lm.cache_shapes(cfg, geo))
    lengths = np.asarray([0, 1, cfg.topk, cfg.topk + 1, geo.max_len])
    free = rng.permutation(np.arange(1, geo.n_pages))
    tables = np.zeros((5, geo.max_pages), np.int32)
    owner = np.full(geo.n_pages, -1, np.int32)
    for b, n in enumerate(lengths):
        held = -(-int(n) // geo.page)
        tables[b, :held], free = free[:held], free[held:]
        owner[tables[b, :held]] = b
    args = (jnp.asarray(rng.integers(0, cfg.vocab, 5), jnp.int32),
            jnp.asarray([-1, 0, 1, 2, 3], jnp.int32),
            jnp.asarray(np.maximum(lengths - 1, 0), jnp.int32),
            jnp.asarray(tables), jnp.asarray(owner))

    def step():
        jax.clear_caches()
        return jax.jit(lm.decode_rows, static_argnums=(0, 1))(
            cfg, geo, weights, cache, *args)
    return cfg, lengths, step


def test_decode_rows_equal_the_parent_s_gathered_addresses(monkeypatch):
    """The toy's decode step with the page-table words by compare-and-sum
    against the parent's form, the table looked up a position at a time:
    ``selected`` identical, the logits too (integers either way)."""
    _, _, step = toy_step(TOY)
    _, logits, _, chosen = step()

    def gathered(tables, idx, page):
        return tables[jnp.arange(idx.shape[0])[:, None], idx // page] \
            * page + idx % page
    monkeypatch.setattr(att, "selected_addresses", gathered)
    _, want, _, parent = step()
    for mine, theirs in zip(chosen["selected"], parent["selected"]):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert (np.asarray(chosen["selected"][0])[4] >= 0).all()   # a long row
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))


def test_decode_rows_with_the_one_pass_kernel_equal_mla_absorbed(monkeypatch):
    """A toy whose latent is a whole lane tile and whose ``index_topk`` is
    whole sublane tiles takes ``selected_mla_decode`` (interpreted) in its
    full layers: against the same step with ``mla_absorbed`` there, the
    parent's form, ``selected`` is identical and the logits agree to the
    paged kernels' tolerance."""
    cfg, lengths, step = toy_step(dict(TOY, kv_lora_rank=128, index_topk=16))
    full = cfg.dims(lm.FULL)
    assert att.pallas_lm_decode.supported(full.kv_rank, full.entry, cfg.topk)
    calls = []
    kernel = att.pallas_lm_decode.selected_mla_decode
    monkeypatch.setattr(att.pallas_lm_decode, "selected_mla_decode",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    _, logits, _, chosen = step()
    assert len(calls) == cfg.n_full
    monkeypatch.setattr(att, "mla_selected", att.mla_absorbed)
    _, want, _, parent = step()
    assert len(calls) == cfg.n_full
    for mine, theirs in zip(chosen["selected"], parent["selected"]):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(logits)[live],
                               np.asarray(want)[live], atol=2e-5, rtol=0)
