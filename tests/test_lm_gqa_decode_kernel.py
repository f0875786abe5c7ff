"""The paged grouped-query decode attention of the global layers
(ops/pallas_lm_decode.py ``paged_gqa_decode``) in interpret mode against
the XLA form — every row's pages gathered, then ``gqa_gathered`` — and
against plain softmax attention a head, on seeded pools, tables and
queries: ragged rows, padding rows, one page, every page a row may hold."""

import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import lm_attention as att
from analytics_zoo_tpu.ops import pallas_lm_decode as pd

H, G, PLAIN, ROT, DV, PAGE = 16, 2, 64, 64, 128, 16
ENTRY = G * (PLAIN + ROT + DV)
N_PAGES, MAX_PAGES = 24, 6
SCALE = (PLAIN + ROT) ** -0.5


def case(lengths, seed=0, shuffle=True):
    """Pool, tables (pages out of order unless told), queries."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    pool = rng.normal(size=(N_PAGES, PAGE, ENTRY)).astype(np.float32)
    free = list(range(1, N_PAGES))
    if shuffle:
        rng.shuffle(free)
    tables = np.zeros((B, MAX_PAGES), np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // PAGE)):
            tables[b, j] = free.pop()
    q_plain = rng.normal(size=(B, H, PLAIN)).astype(np.float32)
    q_rot = rng.normal(size=(B, H, ROT)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        q_plain, q_rot, pool, tables, np.asarray(lengths, np.int32)))


CASES = {
    "ragged": [5, 96, 33, 17],
    "one_token_row": [1, 40, 1, 7],
    "on_a_page_boundary": [16, 32, 48, 15],
    "padding_rows": [0, 20, 0, 64, 0],
    "only_padding": [0, 0, 0],
    "one_page": [16],
    "max_pages": [96],
}


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("shuffle", [True, False],
                         ids=["pages_out_of_order", "pages_in_order"])
def test_kernel_equals_gather_then_attend(name, shuffle, monkeypatch):
    lengths = CASES[name]
    q_plain, q_rot, pool, tables, n = case(lengths, len(name), shuffle)
    assert pd.gqa_supported(G, PLAIN + ROT, DV, H, PAGE)
    got = att.gqa_paged(q_plain, q_rot, pool, tables, n, G, DV, SCALE)
    monkeypatch.setattr(pd, "gqa_supported", lambda *a: False)
    want = att.gqa_paged(q_plain, q_rot, pool, tables, n, G, DV, SCALE)
    live = np.asarray(n) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[~live].any()       # a padding row: zeros


def test_gathered_form_is_plain_attention_a_head():
    """Head a against KV head a // (H / G), its key the entry's unrotated
    and rotated dims of that KV head, a sink column where there is one."""
    q_plain, q_rot, pool, tables, n = case([33, 7], 5)
    sink = jnp.asarray(np.random.RandomState(9).normal(size=H), jnp.float32)
    mine = pool[tables].reshape(2, -1, ENTRY)
    valid = jnp.arange(mine.shape[1])[None, :] < n[:, None]
    for s in (None, sink):
        got = np.asarray(att.gqa_gathered(q_plain, q_rot, mine, valid, s, G,
                                          DV, SCALE))
        for b, L in enumerate((33, 7)):
            e = np.asarray(mine)[b, :L]
            for a in range(H):
                g = a // (H // G)
                kp = e[:, g * PLAIN:(g + 1) * PLAIN]
                kr = e[:, G * PLAIN + g * ROT:G * PLAIN + (g + 1) * ROT]
                v = e[:, G * (PLAIN + ROT) + g * DV:][:, :DV]
                sc = (kp @ np.asarray(q_plain)[b, a]
                      + kr @ np.asarray(q_rot)[b, a]) * SCALE
                if s is not None:
                    sc = np.append(sc, float(s[a]))
                p = np.exp(sc - sc.max())
                p = (p / p.sum())[:L]
                np.testing.assert_allclose(got[b, a], p @ v, atol=2e-5,
                                           rtol=0)


def test_widths_the_kernel_takes():
    assert pd.gqa_supported(4, 192, 128, 64, 512)       # the published
    assert not pd.gqa_supported(2, 12, 8, 4, 4)         # the toy's
    assert not pd.gqa_supported(4, 192, 128, 16, 512)   # 4 heads a KV head
    assert not pd.gqa_supported(4, 192, 96, 64, 512)    # values off a tile
    q = jnp.zeros((2, 64, 768))
    with pytest.raises(ValueError, match="do not fit"):
        pd.paged_gqa_decode(q, jnp.zeros((5, 512, 1024)),
                            jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2,), jnp.int32), kv_heads=4, v=128,
                            scale=1.0)


def test_block_queries_stand_against_their_own_kv_head():
    q_plain, q_rot, *_ = case([3], 2)
    q = np.asarray(att.gqa_block_queries(q_plain, q_rot, G))
    assert q.shape == (1, H, G * (PLAIN + ROT))
    for a in range(H):
        g = a // (H // G)
        want = np.zeros(G * (PLAIN + ROT), np.float32)
        want[g * PLAIN:(g + 1) * PLAIN] = np.asarray(q_plain)[0, a]
        want[G * PLAIN + g * ROT:G * PLAIN + (g + 1) * ROT] \
            = np.asarray(q_rot)[0, a]
        np.testing.assert_array_equal(q[0, a], want)
