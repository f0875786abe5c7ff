"""The paged grouped-query decode attention of the global layers
(ops/pallas_lm_decode.py ``paged_gqa_decode``) in interpret mode against
the XLA form — every row's pages gathered, then ``gqa_gathered`` — and
against plain softmax attention a head, on seeded pools, tables and
queries: ragged rows, padding rows, one page, every page a row may hold;
since PR 39 also at 5 query heads a KV head (padded to the kernel's 8
rows and cut off after it) and at keys with no unrotated part."""

import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import lm_attention as att
from analytics_zoo_tpu.ops import pallas_lm_decode as pd

H, G, PLAIN, ROT, DV, PAGE = 16, 2, 64, 64, 128, 16
ENTRY = G * (PLAIN + ROT + DV)
N_PAGES, MAX_PAGES = 24, 6
SCALE = (PLAIN + ROT) ** -0.5


def case(lengths, seed=0, shuffle=True, H=H, G=G, PLAIN=PLAIN, ROT=ROT):
    """Pool, tables (pages out of order unless told), queries."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    pool = rng.normal(size=(N_PAGES, PAGE, G * (PLAIN + ROT + DV))
                      ).astype(np.float32)
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


#: (heads, KV heads, unrotated dims, rotated dims): 5 heads a KV head as
#: Falcon-H1 has them, with MiMo's split key and with a key that is rotary
#: throughout; 3 a KV head (one tile short by five rows); 16 with no plain
SHAPES = {"5_heads_a_kv_head": (10, 2, 64, 64),
          "5_heads_rotary_throughout": (20, 4, 0, 128),
          "3_heads_a_kv_head": (6, 2, 0, 128),
          "16_heads_rotary_throughout": (16, 1, 0, 128)}


@pytest.mark.parametrize("name", ["ragged", "padding_rows", "max_pages"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_takes_any_heads_a_kv_head_and_keys_without_a_plain_part(
        name, shape, monkeypatch):
    h, g, plain, rot = SHAPES[shape]
    q_plain, q_rot, pool, tables, n = case(CASES[name], len(name), True, h,
                                           g, plain, rot)
    scale = (plain + rot) ** -0.5
    assert pd.gqa_supported(g, plain + rot, DV, h, PAGE)
    got = att.gqa_paged(q_plain, q_rot, pool, tables, n, g, DV, scale)
    assert got.shape == (len(CASES[name]), h, DV)
    monkeypatch.setattr(pd, "gqa_supported", lambda *a: False)
    want = att.gqa_paged(q_plain, q_rot, pool, tables, n, g, DV, scale)
    live = np.asarray(n) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[~live].any()


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
    assert pd.gqa_supported(4, 128, 128, 20, 256)       # Falcon-H1's: 5 a KV
    assert pd.gqa_supported(4, 192, 128, 16, 512)       # 4 heads a KV head
    assert not pd.gqa_supported(4, 192, 128, 18, 512)   # heads not whole KVs
    assert not pd.gqa_supported(4, 192, 96, 64, 512)    # values off a tile
    q = jnp.zeros((2, 64, 768))
    with pytest.raises(ValueError, match="do not fit"):
        pd.paged_gqa_decode(q, jnp.zeros((5, 512, 1024)),
                            jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2,), jnp.int32), kv_heads=4, v=128,
                            scale=1.0)


def test_block_queries_pad_a_kv_head_s_heads_to_whole_tiles():
    """5 heads a KV head stand in 8 rows, the last 3 of zeros; a key with
    no unrotated part has no plain columns; the kernel itself refuses
    heads that are not whole tiles."""
    q_plain, q_rot, *_ = case([3], 2, True, 10, 2, 0, 128)
    q = np.asarray(att.gqa_block_queries(q_plain, q_rot, 2, 8))
    assert q.shape == (1, 16, 2 * 128)
    for g in range(2):
        for j in range(8):
            want = np.zeros(256, np.float32)
            if j < 5:
                want[g * 128:(g + 1) * 128] = np.asarray(q_rot)[0, g * 5 + j]
            np.testing.assert_array_equal(q[0, g * 8 + j], want)
    with pytest.raises(ValueError, match="do not fit"):
        pd.paged_gqa_decode(
            jnp.asarray(att.gqa_block_queries(q_plain, q_rot, 2)),
            jnp.zeros((5, 16, 512)), jnp.zeros((1, 3), jnp.int32),
            jnp.zeros((1,), jnp.int32), kv_heads=2, v=128, scale=1.0)


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
