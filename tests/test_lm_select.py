"""``ops/lm_attention.py::select_topk`` — decode's selection by threshold,
tie room and a counting compaction — against ``lax.top_k``, whose SET it
has to return (ties to the earlier position), and the toy decode step's
program, which may hold no sort."""

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
