"""The paged decode attention of the causal layers (ops/pallas_lm_decode.py)
in interpret mode against the XLA form — every row's pages gathered, then
``mla_absorbed`` — on seeded pools, tables and queries; the flat list of
(row, page) work items against a loop in plain Python.  Then the full
layers' kernel, which reads a row's gathered selection once, against
``mla_absorbed`` over the same copy."""

import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import lm_attention as att
from analytics_zoo_tpu.ops import pallas_lm_decode as pd

H, RANK, ROPE, ENTRY, PAGE, NOPE, V = 4, 128, 8, 256, 16, 8, 8
N_PAGES, MAX_PAGES = 24, 6


def case(lengths, seed=0, shuffle=True):
    """Pool, tables (pages out of order unless told), queries."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    pool = rng.normal(size=(N_PAGES, PAGE, ENTRY)).astype(np.float32)
    pool[..., RANK + ROPE:] = 0.0
    free = list(range(1, N_PAGES))
    if shuffle:
        rng.shuffle(free)
    tables = np.zeros((B, MAX_PAGES), np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // PAGE)):
            tables[b, j] = free.pop()
    q_nope = rng.normal(size=(B, H, NOPE)).astype(np.float32)
    q_rope = rng.normal(size=(B, H, ROPE)).astype(np.float32)
    wkv_b = (rng.normal(size=(RANK, H, NOPE + V)) / 8).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        pool, tables, np.asarray(lengths, np.int32), q_nope, q_rope, wkv_b))


def gathered(pool, tables, lengths, q_nope, q_rope, wkv_b):
    mine = pool[tables].reshape(len(lengths), -1, ENTRY)
    valid = jnp.arange(mine.shape[1])[None, :] < lengths[:, None]
    return att.mla_absorbed(q_nope, q_rope, mine, valid, wkv_b, NOPE, ROPE,
                            0.25)


CASES = {
    "ragged": [5, 96, 33, 17],
    "one_token_row": [1, 40, 1, 7],
    "on_a_page_boundary": [16, 32, 48, 15],
    "padding_rows": [0, 20, 0, 64, 0],
    "only_padding": [0, 0, 0],
    "one_row_all_its_pages": [96],
}


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("shuffle", [True, False],
                         ids=["pages_out_of_order", "pages_in_order"])
def test_kernel_equals_gather_then_absorbed(name, shuffle):
    lengths = CASES[name]
    pool, tables, n, q_nope, q_rope, wkv_b = case(lengths, len(name), shuffle)
    assert pd.supported(RANK, ENTRY, PAGE)
    got = att.mla_paged(q_nope, q_rope, pool, tables, n, wkv_b, NOPE, ROPE,
                        0.25)
    want = gathered(pool, tables, n, q_nope, q_rope, wkv_b)
    live = np.asarray(n) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[~live].any()       # a padding row: zeros


def test_narrow_widths_take_the_gathering_form():
    """A toy's latent is no whole lane tile: ``mla_paged`` gathers."""
    assert not pd.supported(8, 128, 4) and not pd.supported(512, 576, 512)
    assert pd.supported(512, 640, 512)
    rng = np.random.RandomState(1)
    pool = jnp.asarray(rng.normal(size=(9, 4, 128)).astype(np.float32))
    tables = jnp.asarray([[3, 1, 0], [2, 0, 0]], jnp.int32)
    n = jnp.asarray([7, 2], jnp.int32)
    q_nope, q_rope = (jnp.asarray(rng.normal(size=(2, 2, k)).astype(
        np.float32)) for k in (4, 4))
    wkv_b = jnp.asarray(rng.normal(size=(8, 2, 12)).astype(np.float32))
    got = att.mla_paged(q_nope, q_rope, pool, tables, n, wkv_b, 4, 4, 0.3)
    mine = pool[tables].reshape(2, -1, 128)
    want = att.mla_absorbed(q_nope, q_rope, mine,
                            jnp.arange(12)[None, :] < n[:, None], wkv_b, 4,
                            4, 0.3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lengths", [[5, 96, 33, 17], [0, 20, 0, 64, 0],
                                     [0, 0], [96, 96, 96, 80]])
def test_work_items_are_the_rows_pages_in_order(lengths):
    _, tables, n, *_ = case(lengths, 3)
    items = pd.grid_steps(len(lengths), MAX_PAGES, N_PAGES)
    row, phys, idx = (np.asarray(a) for a in pd.work_items(
        n, tables, PAGE, items))
    want = [(b, int(tables[b, j]), j) for b, L in enumerate(lengths)
            for j in range(-(-L // PAGE))]
    assert len(want) <= items == min(len(lengths) * MAX_PAGES, N_PAGES - 1)
    got = list(zip(row.tolist(), phys.tolist(), idx.tolist()))
    assert got[:len(want)] == want
    # the steps past the last item repeat its blocks and do nothing
    last = want[-1][:2] if want else got[0][:2]
    assert all(g[:2] == last and g[2] == -1 for g in got[len(want):])


# -- a full layer: the selected entries, gathered, read once (PR 38) --------

K = 32                                  # slots a row: whole sublane tiles


def selected_case(lengths, kind, seed=0, shuffle=True):
    """A pool and tables as :func:`case`'s, and each row's selection as
    decode makes it (``select_topk`` on seeded scores of ``kind``), its
    addresses and the gathered entries."""
    pool, tables, n, q_nope, q_rope, wkv_b = case(lengths, seed, shuffle)
    rng = np.random.RandomState(seed + 1)
    scores = rng.normal(size=(len(lengths), PAGE * MAX_PAGES))
    if kind == "tied":                  # ties straddle the threshold
        scores = np.round(scores)
    if kind == "one_run":               # the first K positions win
        scores = np.broadcast_to(-np.arange(PAGE * MAX_PAGES, dtype=float),
                                 scores.shape)
    idx, valid = att.select_topk(jnp.asarray(scores, jnp.float32), n, K)
    phys = att.selected_addresses(tables, idx, PAGE)
    chosen = pool.reshape(N_PAGES * PAGE, ENTRY)[phys]
    return chosen, valid, idx, q_nope, q_rope, wkv_b


SELECTED = {
    "ragged": [5, 96, 33, 17],
    "one_token_row": [1, 40, 1, 7],
    "k_minus_one_k_k_plus_one": [K - 1, K, K + 1, 96],
    "padding_rows": [0, 20, 0, 64, 0],
    "only_padding": [0, 0, 0],
    "one_row_all_its_pages": [96],
}


@pytest.mark.parametrize("kind", ["distinct", "tied", "one_run"])
@pytest.mark.parametrize("name", sorted(SELECTED))
def test_selected_kernel_equals_absorbed_over_the_gathered_entries(name,
                                                                   kind):
    lengths = SELECTED[name]
    chosen, valid, idx, q_nope, q_rope, wkv_b = selected_case(
        lengths, kind, len(name))
    assert pd.supported(RANK, ENTRY, K)
    got = np.asarray(att.mla_selected(q_nope, q_rope, chosen, valid, wkv_b,
                                      NOPE, ROPE, 0.25))
    want = np.asarray(att.mla_absorbed(q_nope, q_rope, chosen, valid, wkv_b,
                                       NOPE, ROPE, 0.25))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)
    assert not got[~live].any()                   # a padding row: zeros
    if kind == "one_run":               # runs that cross a page boundary
        for b, n in enumerate(lengths):
            assert np.asarray(idx)[b, :min(n, K)].tolist() == \
                list(range(min(n, K)))


def test_selected_kernel_takes_any_mask_and_refuses_other_widths():
    """The mask need not be a prefix, and what an invalid slot holds does
    not matter while it is finite; widths off the lane tiles raise (and
    ``mla_selected`` takes ``mla_absorbed`` there)."""
    chosen, valid, _, q_nope, q_rope, wkv_b = selected_case(
        [96, 40, 33], "distinct", 3)
    rng = np.random.RandomState(4)
    valid = jnp.asarray(rng.rand(3, K) < 0.5)
    chosen = jnp.where(valid[..., None], chosen, 1e4)
    got = att.mla_selected(q_nope, q_rope, chosen, valid, wkv_b, NOPE, ROPE,
                           0.25)
    want = att.mla_absorbed(q_nope, q_rope, chosen, valid, wkv_b, NOPE, ROPE,
                            0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)
    q = att.absorbed_queries(q_nope, q_rope, wkv_b, NOPE, ENTRY)
    with pytest.raises(ValueError):
        pd.selected_mla_decode(q, chosen[:, :K - 1], valid[:, :K - 1],
                               rank=RANK, scale=0.25)
    narrow = att.mla_selected(q_nope, q_rope, chosen[:, :K - 1],
                              valid[:, :K - 1], wkv_b, NOPE, ROPE, 0.25)
    np.testing.assert_array_equal(
        np.asarray(narrow), np.asarray(att.mla_absorbed(
            q_nope, q_rope, chosen[:, :K - 1], valid[:, :K - 1], wkv_b,
            NOPE, ROPE, 0.25)))
