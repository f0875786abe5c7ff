"""Attention over device-resident session caches for the decoder LM
(models/lm.py): multi-head latent attention (MLA) in the absorbed form
over a PAGED pool of latents — over the positions a learned sparse
indexer selects (full layers) or over the whole context (causal layers)
— and windowed MLA over per-session rings; rotary embedding, plain or
YaRN-scaled (``RopeScaling``).  XLA, but for the Pallas programs it hands
lane-aligned widths to: prefill's attention (ops/pallas_lm_prefill.py),
a causal layer's paged decode and a full layer's attention over its
gathered entries (ops/pallas_lm_decode.py).  At the end
of the file the same two reaches — the whole context out of a pool, a
window out of a ring — for GROUPED-QUERY attention, whose cache holds real
keys and values a KV head (``gqa_entry``), with partial rotary in pairs at
a distance (``rope_half``) and a learned sink column (``softmax_sink``).

Cache layout (one replica's, all sessions'):

- ``kv``  a full or causal layer: (n_pages, page, entry) — per token the
  normed kv latent, the rotated shared key, and zeros up to ``entry`` (a multiple
  of the TPU's 128 lanes, so a token's row is the minor axis);
- ``ik``  a full layer: (n_pages, page, idx_dim) — the indexer's key;
- ``ring`` a sliding layer: (n_slots, window, swa entry) — the last
  ``window`` tokens of a session at ``position % window``.

A grouped-query layer's entry, in a pool or a ring, is its KV heads' keys
and values and nothing else (``gqa_entry``), so a model's pools and rings
can be of two widths.

A session's tokens live in the pages its row of the page table names
(position ``p`` at page ``table[p // page]``, offset ``p % page``); page
0 belongs to no session and takes the writes of padding rows.  The page
table, the pages' owners and the positions come from the host with every
call (pipelines/lm.py owns the allocation); the arrays here hold data
only.

Two shapes of work (a causal layer's are the plain ones: decode is
``mla_paged`` over every page a row holds, prefill
``prefill_causal_attention`` with the causal mask alone as the bias; what
follows is a full layer's):

- **decode** — a batch of rows, one new token each, rows of any sessions:
  the indexer scores every page once against its owner's query (each key
  is read once, whatever the mix of lengths), the scores are laid out by
  session, the ``topk`` largest are selected — by the threshold prefill
  uses, never by a sort: the positions over each row's ``topk``-th
  largest score, and of those equal to it the earliest that still fit,
  are packed into bits and counted out into indices (``select_topk``) —
  and only those latents are gathered for the attention, from addresses
  that the row's page table gives by a compare-and-sum
  (``selected_addresses``): an XLA gather costs a v5e 10–15 ns an INDEX
  whatever it fetches, so the entries' own gather is the one that is left,
  and its copy is read once (``mla_selected``);
- **prefill** — one session's chunk of T tokens: two loops over the
  session's pages (as many as it has, not as many as the longest could
  have): the first fills the index scores, from which each query's
  selection threshold is found exactly by bisection on the scores' bits;
  the second is attention with an online softmax over the selected keys.
  The selected SET is the same either way: the ``topk`` largest scores,
  by ONE rule (``ordered_bits``, ``kth_largest``) with ONE order among
  equal scores, the earlier position first, as ``lax.top_k`` orders them.

Both shapes hand the selected set back beside their result — decode the
positions (−1 where a row has fewer), prefill a bit-packed row a query —
so that a comparison with another implementation can be made on EQUAL
sets: past ``topk`` tokens a rounding flips members of the set, and what
the flip does to the output says nothing about either side's arithmetic
(benchmarks/reference/lm.py ``follow``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from analytics_zoo_tpu.ops import pallas_lm_decode, pallas_lm_prefill
from analytics_zoo_tpu.ops.ranking import kth_largest, ordered_bits

F32 = jnp.float32
NEG = -1e30
#: prefill's selection (the threshold's 32 counting passes, the sets) works
#: on blocks of about this many columns of a chunk's scores, as many as the
#: session has: a pass over all ``max_len`` columns of 1,024 queries is
#: 285 MB, and 32 of them took 16 ms on a v5e whatever the session (PR 28)
SELECT_BLOCK = 4096


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN (a config's ``rope_scaling`` of type ``yarn``): rotary pairs
    that turn often inside the ``original`` context keep their frequency,
    those that turn less than once are slowed by ``factor``, a linear ramp
    between the pairs that make ``beta_fast`` and ``beta_slow`` turns."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def from_dict(cls, d: Optional[Dict]) -> Optional["RopeScaling"]:
        if not d:
            return None
        if d.get("type", d.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling: only yarn is known, got {d}")
        return cls(float(d["factor"]),
                   int(d["original_max_position_embeddings"]),
                   float(d.get("beta_fast", 32)), float(d.get("beta_slow", 1)),
                   float(d.get("mscale", 1)),
                   float(d.get("mscale_all_dim", 0)))

    def inv_freq(self, r: int, theta: float) -> np.ndarray:
        """The ``r / 2`` pairs' frequencies, float32."""
        def pair_at(turns):       # the pair that makes ``turns`` rotations
            return r * math.log(self.original / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))
        lo = max(math.floor(pair_at(self.beta_fast)), 0)
        hi = min(math.ceil(pair_at(self.beta_slow)), r // 2 - 1)
        f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
        ramp = np.clip((np.arange(r // 2) - lo)
                       / (hi - lo if hi > lo else 0.001), 0, 1)
        return (f / self.factor * ramp + f * (1 - ramp)).astype(np.float32)

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def amplitude(self) -> float:
        """What cos and sin are multiplied by."""
        return self._mscale(self.factor, self.mscale) \
            / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_mscale(self) -> float:
        """The attention scale is multiplied by its square."""
        return self._mscale(self.factor, self.mscale_all_dim)


def rope(x, pos, theta: float, scaling: Optional[RopeScaling] = None):
    """Rotary embedding of the last axis in interleaved pairs; ``pos``
    has ``x``'s leading axes up to where it stops (``x`` (..., [h,] r)).
    ``scaling``: YaRN's frequencies and amplitude in place of the plain
    ones."""
    r = x.shape[-1]
    if scaling is None:
        freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    else:
        freq = jnp.asarray(scaling.inv_freq(r, theta))
    ang = pos.astype(F32).reshape(pos.shape + (1,) * (x.ndim - pos.ndim)) \
        * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.amplitude != 1.0:
        cos, sin = cos * scaling.amplitude, sin * scaling.amplitude
    xf = x.astype(F32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def rope_head(x, pos, theta: float, r: int):
    """Rotary on the first ``r`` dims of the last axis."""
    return jnp.concatenate([rope(x[..., :r], pos, theta), x[..., r:]], -1)


def rope_half(x, pos, theta: float):
    """Rotary embedding of the whole last axis (r wide) in pairs AT A
    DISTANCE, ``(x[j], x[j + r/2])`` for ``j < r/2`` — the rotate-half
    layout of the grouped-query family's checkpoints, where :func:`rope`
    pairs neighbours; ``pos`` as :func:`rope`'s.  A model of partial
    rotary hands in the dims that turn and keeps the others as they are."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32).reshape(pos.shape + (1,) * (x.ndim - pos.ndim)) \
        * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    x0, x1 = xf[..., :r // 2], xf[..., r // 2:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                           -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# decode: B rows, one token each
# ---------------------------------------------------------------------------

def index_scores_paged(q_idx, w_idx, ik_pool, owner):
    """Indexer scores of every page against its owner's query.
    ``q_idx`` (B, Hi, Di), ``w_idx`` (B, Hi) float32, ``ik_pool``
    (n_pages, page, Di), ``owner`` (n_pages,) row of the batch or −1.
    → (n_pages, page) float32."""
    row = jnp.maximum(owner, 0)
    q = q_idx[row]                                    # (n_pages, Hi, Di)
    s = jnp.einsum("pti,phi->pth", ik_pool, q,
                   preferred_element_type=F32)
    return jnp.einsum("pth,ph->pt", jax.nn.relu(s), w_idx[row])


#: words a group of ``select_topk``'s search: a slot finds its group among
#: n / (32 GROUP), then its word among the group's GROUP
GROUP = 32


def nth_set_bit(x, r):
    """Where the ``r``-th set bit (from 0, lowest first) of each uint32 of
    ``x`` stands: five steps of bisection on popcounts.  ``r`` int32 under
    the word's popcount; anything where it is not."""
    at = jnp.zeros(x.shape, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = lax.population_count(
            x & jnp.uint32((1 << width) - 1)).astype(jnp.int32)
        up = r >= low
        r = jnp.where(up, r - low, r)
        x = jnp.where(up, x >> width, x)
        at = at + jnp.where(up, width, 0)
    return at


def pack_words(mask):
    """(B, 32 W) bool → (B, W) uint32: bit b of word w is position 32 w + b."""
    m = mask.reshape(mask.shape[0], -1, 32).astype(jnp.uint32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.uint32), -1,
                   dtype=jnp.uint32)


def holder(sizes, rank):
    """Runs of ``sizes`` (..., M) items stand one after another; which run
    holds item ``rank`` (...,), and the item's rank inside it: a
    compare-and-sum over the runs, no search and no gather.  Run M where
    there are fewer items."""
    before = jnp.cumsum(sizes, -1) <= rank[..., None]
    return (jnp.sum(before, -1, dtype=jnp.int32),
            rank - jnp.sum(jnp.where(before, sizes, 0), -1))


def rows_of(table, row):
    """``table[b, row[b, j]]`` of a uint32 ``table`` (B, G, S), ``row``
    (B, k) → (B, k, S); zeros where ``row`` is G.  A 0/1 product on the MXU,
    a byte at a time (exact in bfloat16): gathering 131,072 words took a
    v5e 1.35 ms (PR 32)."""
    B, G, S = table.shape
    one = (row[..., None] == jnp.arange(G)).astype(jnp.bfloat16)
    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)[:, None]
    byte = ((table[:, :, None, :] >> shifts) & 255).astype(jnp.bfloat16)
    got = jnp.einsum("bkg,bgc->bkc", one, byte.reshape(B, G, 4 * S),
                     preferred_element_type=F32).astype(jnp.uint32)
    return jnp.sum(got.reshape(B, -1, 4, S) << shifts, 2, dtype=jnp.uint32)


def selected_addresses(tables, idx, page: int):
    """Where the positions ``idx`` (B, k) of B rows lie in a pool laid flat,
    ``tables[b, idx // page] * page + idx % page`` with ``tables`` (B,
    max_pages) the rows' page tables: each position's word of its row's
    table by a compare-and-sum over the row's pages, exact in int32, as
    :func:`holder` finds a run — no gather.  Looked up a position at a time
    the 131,072 words of a step's full layer took a v5e 1.34 ms, a gather's
    10 ns an index whatever it fetches (PERF.md §6, PR 38).  The positions
    stand on the minor axis, so the sum runs over whole vectors and never
    across lanes."""
    at = (idx // page)[:, None, :]                     # (B, 1, k)
    mine = at == jnp.arange(tables.shape[1])[None, :, None]
    word = jnp.sum(jnp.where(mine, tables[:, :, None], 0), 1,
                   dtype=jnp.int32)
    return word * page + idx % page


def select_topk(scores, lengths, k: int):
    """The ``k`` largest of each row's first ``lengths`` scores: (indices
    (B, k), valid (B, k)).  Rows shorter than ``k`` select all they have.
    ``where(valid, indices, -1)`` is the selected set as decode reports it.

    Nothing is sorted, scattered or gathered.  The set is prefill's: every
    score over the row's ``k``-th largest (``kth_largest`` on the scores'
    ordered bits) and, of the scores equal to it, as many as still fit, the
    earlier positions first (as ``top_k`` orders equal scores).  The kept
    positions are packed into 32-bit words, and slot j of a row finds the
    j-th of them by counting: its group of ``GROUP`` words, its word in the
    group, its bit in the word.  So the indices come out ascending by
    position; slots past a row's count are not ``valid`` and hold 0."""
    B, n = scores.shape
    live = jnp.arange(n)[None, :] < lengths[:, None]
    if n <= k:
        idx = jnp.broadcast_to(jnp.arange(n)[None, :], scores.shape)
        return idx, live
    bits = ordered_bits(jnp.where(live, scores, NEG))

    def count(above):
        return jnp.sum(above(bits), 1, dtype=jnp.int32)

    tau = kth_largest(count, k, B)[:, None]
    room = k - count(lambda v: v > tau)
    pad = ((0, 0), (0, -n % (32 * GROUP)))
    over = pack_words(jnp.pad(bits > tau, pad))
    tie = pack_words(jnp.pad((bits == tau) & live, pad))
    # of a word's ties the first ``fit``: all of them, or those under the
    # fit-th
    n_tie = lax.population_count(tie).astype(jnp.int32)
    fit = room[:, None] - (jnp.cumsum(n_tie, 1) - n_tie)
    under = jnp.uint32(1) << nth_set_bit(
        tie, jnp.maximum(fit, 0)).astype(jnp.uint32)
    tie = jnp.where(fit >= n_tie, tie, tie & (under - 1))
    groups = (over | tie).reshape(B, -1, GROUP)
    kept = jnp.sum(lax.population_count(groups).astype(jnp.int32), -1)
    slot = jnp.broadcast_to(jnp.arange(k), (B, k))
    g, rank = holder(kept[:, None, :], slot)
    mine = rows_of(groups, g)                                # (B, k, GROUP)
    w, rank = holder(lax.population_count(mine).astype(jnp.int32), rank)
    word = jnp.sum(jnp.where(w[..., None] == jnp.arange(GROUP), mine, 0), -1,
                   dtype=jnp.uint32)
    idx = (g * GROUP + w) * 32 + nth_set_bit(word, rank)
    valid = g < kept.shape[1]
    return jnp.where(valid, idx, 0), valid


def mla_absorbed(q_nope, q_rope, latents, valid, wkv_b, nope: int,
                 r: int, scale: float):
    """MLA over gathered cache entries in the absorbed form.  ``q_nope``
    (B, H, nope), ``q_rope`` (B, H, r), ``latents`` (B, S, entry),
    ``valid`` (B, S), ``wkv_b`` (kv_rank, H, nope + v) → (B, H, v)."""
    rank = wkv_b.shape[0]
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, wkv_b[..., :nope])
    s = (jnp.einsum("bhr,bsr->bhs", q_abs, latents[..., :rank],
                    preferred_element_type=F32)
         + jnp.einsum("bhe,bse->bhs", q_rope, latents[..., rank:rank + r],
                      preferred_element_type=F32)) * scale
    s = jnp.where(valid[:, None, :], s, NEG)
    p = jax.nn.softmax(s, -1).astype(latents.dtype)
    o = jnp.einsum("bhs,bsr->bhr", p, latents[..., :rank])
    return jnp.einsum("bhr,rhv->bhv", o, wkv_b[..., nope:])


def absorbed_queries(q_nope, q_rope, wkv_b, nope: int, entry: int):
    """A head's query as the decode kernels take it (ops/pallas_lm_decode.py):
    ``[q_nope W_kvb^K ; q_rope ; 0]`` against an entry's ``[c_kv ; k_r ;
    0]`` → (B, H, entry)."""
    B, H, _ = q_nope.shape
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, wkv_b[..., :nope])
    pad = entry - q_abs.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype),
                            jnp.zeros((B, H, pad), q_abs.dtype)], -1)


def mla_selected(q_nope, q_rope, latents, valid, wkv_b, nope: int, r: int,
                 scale: float):
    """:func:`mla_absorbed` over the entries a full layer's rows selected
    (arguments and result as its own).  At widths the Pallas kernel takes
    (ops/pallas_lm_decode.py ``selected_mla_decode``) a row's gathered
    entries are read ONCE, scores and probabilities never leave VMEM; at
    others (a toy's) this IS ``mla_absorbed``, which reads the copy for
    the scores, for their rotary part and for the values."""
    rank = wkv_b.shape[0]
    _, k, entry = latents.shape
    if not pallas_lm_decode.supported(rank, entry, k):
        return mla_absorbed(q_nope, q_rope, latents, valid, wkv_b, nope, r,
                            scale)
    o = pallas_lm_decode.selected_mla_decode(
        absorbed_queries(q_nope, q_rope, wkv_b, nope, entry), latents, valid,
        rank=rank, scale=scale)
    return jnp.einsum("bhr,rhv->bhv", o, wkv_b[..., nope:])


def mla_paged(q_nope, q_rope, kv_pool, tables, lengths, wkv_b, nope: int,
              r: int, scale: float):
    """MLA of B rows, each over ALL the entries of its own pages (a causal
    layer's decode), in the absorbed form.  ``q_nope`` (B, H, nope),
    ``q_rope`` (B, H, r), ``kv_pool`` (n_pages, page, entry), ``tables``
    (B, max_pages), ``lengths`` (B,) the entries a row attends to (0: a
    padding row) → (B, H, v).  At widths the Pallas kernel takes
    (ops/pallas_lm_decode.py) the pages are read where they lie; at others
    (a toy's) every row's pages are gathered for ``mla_absorbed``."""
    rank = wkv_b.shape[0]
    B = q_nope.shape[0]
    _, page, entry = kv_pool.shape
    if not pallas_lm_decode.supported(rank, entry, page):
        mine = kv_pool[tables].reshape(B, -1, entry)
        valid = jnp.arange(mine.shape[1])[None, :] < lengths[:, None]
        return mla_absorbed(q_nope, q_rope, mine, valid, wkv_b, nope, r,
                            scale)
    o = pallas_lm_decode.paged_mla_decode(
        absorbed_queries(q_nope, q_rope, wkv_b, nope, entry), kv_pool,
        tables, lengths, rank=rank, scale=scale)
    return jnp.einsum("bhr,rhv->bhv", o, wkv_b[..., nope:])


# ---------------------------------------------------------------------------
# prefill: one session's chunk of T tokens
# ---------------------------------------------------------------------------

def prefill_full_attention(q_nope, q_rope, q_idx, w_idx, kv_pool, ik_pool,
                           table, start, n_valid, wkv_b, nope: int,
                           r: int, scale: float, topk: int,
                           pages_per_step: int = 2, flash: int = 0):
    """Sparse-selected MLA of a chunk against its session's pages, the
    chunk's own tokens (already written to the pool) included.

    ``q_nope`` (T, H, nope), ``q_rope`` (T, H, r), ``q_idx`` (T, Hi, Di),
    ``w_idx`` (T, Hi) float32; ``kv_pool`` (n_pages, page, entry),
    ``ik_pool`` (n_pages, page, Di); ``table`` (max_pages,) the session's
    pages; the chunk's tokens stand at ``start + arange(T)``, the first
    ``n_valid`` of them real → ((T, H, v), the selected sets: uint8
    (T, max_len / 8), bit s of row t — in ``numpy.packbits``'s order — set
    where query t attends to position s).  ``flash``: how many heads a step
    of the Pallas form of pass 2 takes (ops/pallas_lm_prefill.py; 0, or
    widths it does not take: the XLA loop)."""
    T = q_nope.shape[0]
    page = kv_pool.shape[1]
    # the selection works on blocks of ``blk`` columns of the scores: whole
    # pages, whole bytes of the selected sets, whole steps of the XLA
    # attention loop, and no fewer than ``topk`` (a session's first block
    # then holds a k-th largest, be it a masked column's)
    pages_per_step *= 8 // math.gcd(8, pages_per_step * page)
    step = pages_per_step * page
    blk = step * -(-max(topk, SELECT_BLOCK) // step)
    max_len = -(-table.shape[0] * page // blk) * blk
    end = start + n_valid
    n_pages = (end + page - 1) // page
    n_blk = (end + blk - 1) // blk
    q_pos = start + jnp.arange(T)

    def seen(first, n):
        key_pos = first + jnp.arange(n)
        return (key_pos[None, :] <= q_pos[:, None]) & (key_pos[None, :] < end)

    # pass 1: the index scores of every (query, key), page by page
    def fill(j, buf):
        ik = ik_pool[table[j]]                                 # (page, Di)
        s = jnp.einsum("thi,si->ths", q_idx, ik,
                       preferred_element_type=F32)
        s = jnp.einsum("ths,th->ts", jax.nn.relu(s), w_idx)
        s = jnp.where(seen(j * page, page), s, NEG)
        return lax.dynamic_update_slice(buf, ordered_bits(s), (0, j * page))

    neg_bits = ordered_bits(jnp.full((), NEG, F32))
    bits = lax.fori_loop(0, n_pages, fill,
                         jnp.full((T, max_len), neg_bits, jnp.uint32))

    def count(above):
        """Per query, how many of the session's scores ``above`` holds
        for, a block of columns at a time: as many blocks as the session
        has, not as many as the longest could have."""
        def body(b, acc):
            blk_bits = lax.dynamic_slice(bits, (0, b * blk), (T, blk))
            return acc + jnp.sum(above(blk_bits), 1, dtype=jnp.int32)
        return lax.fori_loop(0, n_blk, body, jnp.zeros((T,), jnp.int32))

    # each query's threshold: the topk-th largest of its scores (a row
    # with fewer than topk keys ends at a masked column's NEG and keeps
    # all it has)
    tau = kth_largest(count, topk, T)
    # of the scores equal to the threshold only as many as still fit are
    # selected, the earlier positions first (as top_k orders equal scores,
    # so that decode and prefill select the same set)
    room = topk - count(lambda b: b > tau[:, None])

    # the selected sets, a block at a time: as an additive bias for the
    # attention (0 selected, NEG not) and bit-packed for the caller
    def mark(b, carry):
        ties, bias, sets = carry
        blk_bits = lax.dynamic_slice(bits, (0, b * blk), (T, blk))
        ok = seen(b * blk, blk)
        tie = (blk_bits == tau[:, None]) & ok
        nth = ties[:, None] + jnp.cumsum(tie, 1, dtype=jnp.int32)
        keep = ((blk_bits > tau[:, None])
                | (tie & (nth <= room[:, None]))) & ok
        bias = lax.dynamic_update_slice(
            bias, jnp.where(keep, 0.0, NEG).astype(bias.dtype), (0, b * blk))
        sets = lax.dynamic_update_slice(sets, jnp.packbits(keep, axis=1),
                                        (0, b * (blk // 8)))
        return nth[:, -1], bias, sets

    _, bias, sets = lax.fori_loop(
        0, n_blk, mark,
        (jnp.zeros((T,), jnp.int32),
         jnp.full((T, max_len), NEG, kv_pool.dtype),
         jnp.zeros((T, max_len // 8), jnp.uint8)))

    return attend_biased(q_nope, q_rope, kv_pool, bias, table, n_pages, wkv_b,
                         nope, r, scale, pages_per_step, flash), sets


def prefill_causal_attention(q_nope, q_rope, kv_pool, table, start, n_valid,
                             wkv_b, nope: int, r: int, scale: float,
                             pages_per_step: int = 2, flash: int = 0):
    """MLA of a chunk against ALL of its session's entries up to each
    query's own (a causal layer: no indexer, no selected sets), the
    chunk's own tokens (already written to the pool) included.  Arguments
    as :func:`prefill_full_attention`'s → (T, H, v)."""
    T = q_nope.shape[0]
    page = kv_pool.shape[1]
    span = pages_per_step * page
    max_len = -(-table.shape[0] * page // span) * span
    end = start + n_valid
    key_pos = jnp.arange(max_len)[None, :]
    seen = (key_pos <= (start + jnp.arange(T))[:, None]) & (key_pos < end)
    bias = jnp.where(seen, 0.0, NEG).astype(kv_pool.dtype)
    return attend_biased(q_nope, q_rope, kv_pool, bias, table,
                         (end + page - 1) // page, wkv_b, nope, r, scale,
                         pages_per_step, flash)


def attend_biased(q_nope, q_rope, kv_pool, bias, table, n_pages, wkv_b,
                  nope: int, r: int, scale: float, pages_per_step: int,
                  flash: int):
    """Attention of a chunk's queries over the first ``n_pages`` pages of
    its session, online softmax; which keys a query attends to comes as
    ``bias`` (T, whole steps of ``pages_per_step`` pages): 0 or ``NEG``.
    ``flash``: how many heads a step of the Pallas form takes
    (ops/pallas_lm_prefill.py; 0, or widths it does not take: the XLA
    loop) → (T, H, v)."""
    T, H, _ = q_nope.shape
    page = kv_pool.shape[1]
    rank = wkv_b.shape[0]
    max_len = bias.shape[1]
    entry = kv_pool.shape[2]
    if flash and pallas_lm_prefill.supported(nope, wkv_b.shape[2] - nope,
                                             rank, entry, r, H, flash):
        # pass 2 as one Pallas program: the pages decompressed in VMEM,
        # the plain form, nothing of the scores in HBM
        q = jnp.concatenate(
            [q_nope, q_rope.astype(q_nope.dtype),
             jnp.zeros((T, H, entry - rank - r), q_nope.dtype)], -1)
        o = pallas_lm_prefill.flash_mla_prefill(
            q.transpose(1, 0, 2), wkv_b, kv_pool, bias, table, n_pages,
            nope=nope, scale=scale, steps=max_len // page,
            heads_per_step=flash)
        return o.transpose(1, 0, 2)

    # pass 2 in XLA: attention over the selected keys, online softmax, the
    # absorbed form; ``pages_per_step`` pages an iteration, because every
    # iteration reads and writes the whole (T, H, rank) accumulator once
    # (268 MB at 1,024 queries: with 256 keys an iteration that was half
    # of the loop's time on a v5e, PR 28).  Absorbed queries and rotary
    # queries side by side: ONE product a step writes the scores once
    q_abs = jnp.einsum("thn,rhn->thr", q_nope, wkv_b[..., :nope])
    q_cat = jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], -1)
    span = pages_per_step * page

    def attend(i, carry):
        m, l, acc = carry
        pages = [jnp.where(i * pages_per_step + b < n_pages,
                           table[jnp.minimum(i * pages_per_step + b,
                                             table.shape[0] - 1)], 0)
                 for b in range(pages_per_step)]
        kv = jnp.concatenate([kv_pool[p] for p in pages], 0)  # (span, entry)
        keep_blk = lax.dynamic_slice(bias, (0, i * span), (T, span)) == 0
        s = jnp.einsum("the,se->ths", q_cat, kv[:, :rank + r],
                       preferred_element_type=F32) * scale
        s = jnp.where(keep_blk[:, None, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(keep_blk[:, None, :], jnp.exp(s - m_new[..., None]),
                      0.0)
        fix = jnp.exp(m - m_new)
        l = l * fix + jnp.sum(p, -1)
        acc = acc * fix[..., None] + jnp.einsum(
            "ths,sr->thr", p.astype(kv.dtype), kv[:, :rank],
            preferred_element_type=F32)
        return m_new, l, acc

    m0 = jnp.full((T, H), NEG, F32)
    _, l, acc = lax.fori_loop(
        0, (n_pages + pages_per_step - 1) // pages_per_step, attend,
        (m0, jnp.zeros((T, H), F32), jnp.zeros((T, H, rank), F32)))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(kv_pool.dtype)
    return jnp.einsum("thr,rhv->thv", o, wkv_b[..., nope:])


def prefill_window_attention(q, c_new, prev, prev_pos, start, n_valid,
                             wkv_b, nope: int, r: int, scale: float,
                             window: int, q_block: int):
    """Windowed MLA of a chunk: keys are the session's last ``window − 1``
    tokens before the chunk (``prev`` (window − 1, entry) in position
    order, at positions ``prev_pos``, negative where there is none) and
    the chunk's own (``c_new`` (T, entry)).  Decompressed keys: a
    window is short, so the plain form is the cheaper one here.
    ``q`` (T, H, nope + r) with its rotary part rotated → (T, H, v)."""
    T, H, _ = q.shape
    rank = wkv_b.shape[0]
    lat = jnp.concatenate([prev, c_new], 0)              # (W-1+T, rank+r)
    pos = jnp.concatenate([prev_pos, start + jnp.arange(T)])
    ok_key = jnp.concatenate([prev_pos >= 0, jnp.arange(T) < n_valid])
    kv = jnp.einsum("sr,rhe->she", lat[:, :rank], wkv_b)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(lat[:, None, rank:rank + r],
                          (lat.shape[0], H, r))], -1)
    v = kv[..., nope:]
    out = []
    for lo in range(0, T, q_block):
        hi = min(T, lo + q_block)
        # queries lo..hi see keys (lo .. hi + window - 1) of ``lat``
        ks = slice(lo, hi + window - 1)
        q_pos = start + jnp.arange(lo, hi)
        ok = (ok_key[ks][None, :] & (pos[ks][None, :] <= q_pos[:, None])
              & (pos[ks][None, :] > q_pos[:, None] - window))
        s = jnp.einsum("the,she->hts", q[lo:hi], k[ks],
                       preferred_element_type=F32) * scale
        s = jnp.where(ok[None], s, NEG)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        out.append(jnp.einsum("hts,she->the", p, v[ks]))
    return jnp.concatenate(out, 0)


# ---------------------------------------------------------------------------
# grouped-query attention: real keys and values a KV head in the cache
# ---------------------------------------------------------------------------

def gqa_entry(k_plain, k_rot, v):
    """A token's cache entry of a grouped-query layer: every KV head's
    unrotated key dims, then every KV head's rotated dims, then every KV
    head's values — ``k_plain`` (N, G, dk − r), ``k_rot`` (N, G, r)
    rotated, ``v`` (N, G, dv) → (N, G (dk + dv)).  At the published widths
    (G 4, 128 + 64 + 128) each part ends on a 128-lane tile, which a KV
    head's own 192-wide key would not: the paged decode kernel
    (ops/pallas_lm_decode.py) cuts keys and values out of a page on tile
    edges, and the entry stays as wide as what it holds."""
    return jnp.concatenate([t.reshape(t.shape[0], -1)
                            for t in (k_plain, k_rot, v)], -1)


def gqa_split(entries, G: int, plain: int, r: int, dv: int):
    """:func:`gqa_entry` undone: ``entries`` (..., entry) → (k_plain
    (..., G, plain), k_rot (..., G, r), v (..., G, dv))."""
    lead = entries.shape[:-1]
    a, b = G * plain, G * (plain + r)
    return (entries[..., :a].reshape(lead + (G, plain)),
            entries[..., a:b].reshape(lead + (G, r)),
            entries[..., b:].reshape(lead + (G, dv)))


def softmax_sink(s, sink):
    """Softmax over the last axis of float32 scores ``s`` (..., G, hp, S)
    with one more column a head, the learned ``sink`` (G hp,), which takes
    its share of the sum and is then dropped; ``sink`` None: the plain
    softmax."""
    if sink is None:
        return jax.nn.softmax(s, -1)
    col = sink.astype(F32).reshape(s.shape[-3:-1] + (1,))
    col = jnp.broadcast_to(col, s.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]


def gqa_gathered(q_plain, q_rot, entries, valid, sink, G: int, dv: int,
                 scale: float):
    """Grouped-query attention over gathered cache entries: a sliding
    layer's decode over its rows' rings, and the paged decode's form at
    widths the kernel does not take.  ``q_plain`` (B, H, dk − r),
    ``q_rot`` (B, H, r) rotated — head a reads KV head ``a // (H / G)``,
    and its score is the sum of its unrotated and its rotated dims'
    products; ``entries`` (B, S, entry); ``valid`` (B, S); ``sink`` (H,)
    or None → (B, H, dv)."""
    B, H, plain = q_plain.shape
    kp, kr, v = gqa_split(entries, G, plain, q_rot.shape[-1], dv)
    s = (jnp.einsum("bgae,bsge->bgas", q_plain.reshape(B, G, H // G, -1), kp,
                    preferred_element_type=F32)
         + jnp.einsum("bgae,bsge->bgas", q_rot.reshape(B, G, H // G, -1), kr,
                      preferred_element_type=F32)) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG)
    p = softmax_sink(s, sink).astype(v.dtype)
    return jnp.einsum("bgas,bsge->bgae", p, v).reshape(B, H, dv)


def gqa_block_queries(q_plain, q_rot, G: int, rows: int = 1):
    """The paged kernel's queries: (B, H', G (dk − r) + G r), a head's
    dims standing against the keys of ITS KV head in an entry's ``[plain |
    rotary]`` columns and zeros against the others'.  A KV head's query
    heads are padded with rows of zeros to a multiple of ``rows`` (the
    kernel cuts a KV head's heads out on sublane tiles: 5 heads stand in 8
    rows, and nobody reads the other 3), so H' = G · ceil(H / G / rows) ·
    rows; a key with no unrotated part has no ``plain`` columns."""
    B, H, _ = q_plain.shape
    own = jnp.eye(G, dtype=q_rot.dtype)
    pad = -(H // G) % rows

    def spread(q):
        q = q.reshape(B, G, H // G, -1)
        if pad:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return jnp.einsum("bgae,gk->bgake", q, own).reshape(
            B, H + G * pad, -1)
    return jnp.concatenate([spread(q) for q in (q_plain, q_rot)
                            if q.shape[-1]], -1)


def gqa_paged(q_plain, q_rot, kv_pool, tables, lengths, G: int, dv: int,
              scale: float):
    """Grouped-query attention of B rows, each over ALL the entries of its
    own pages (a causal layer's decode): arguments as :func:`mla_paged`'s
    and :func:`gqa_gathered`'s → (B, H, dv).  At widths the Pallas kernel
    takes the pages are read where they lie; at others (a toy's) every
    row's pages are gathered."""
    B, H, plain = q_plain.shape
    _, page, entry = kv_pool.shape
    if not pallas_lm_decode.gqa_supported(G, plain + q_rot.shape[-1], dv, H,
                                          page):
        mine = kv_pool[tables].reshape(B, -1, entry)
        valid = jnp.arange(mine.shape[1])[None, :] < lengths[:, None]
        return gqa_gathered(q_plain, q_rot, mine, valid, None, G, dv, scale)
    o = pallas_lm_decode.paged_gqa_decode(
        gqa_block_queries(q_plain, q_rot, G, pallas_lm_decode.SUBLANES),
        kv_pool, tables, lengths, kv_heads=G, v=dv, scale=scale)
    if o.shape[1] != H:       # the rows of zeros that padded a KV head's heads
        o = o.reshape(B, G, -1, dv)[:, :, :H // G].reshape(B, H, dv)
    return o


def prefill_gqa_causal(q_plain, q_rot, kv_pool, table, start, n_valid,
                       G: int, dv: int, scale: float,
                       pages_per_step: int = 1):
    """Grouped-query attention of a chunk against ALL of its session's
    entries up to each query's own, the chunk's own tokens (already
    written to the pool) included: a loop over the session's pages — as
    many as it has — with an online softmax, so that of the scores only a
    step's (T, H, ``pages_per_step`` pages) exist.  ``q_plain`` (T, H,
    dk − r), ``q_rot`` (T, H, r); the rest as
    :func:`prefill_causal_attention`'s → (T, H, dv)."""
    T, H, plain = q_plain.shape
    hp, r = H // G, q_rot.shape[-1]
    page = kv_pool.shape[1]
    span = pages_per_step * page
    end = start + n_valid
    n_pages = (end + page - 1) // page
    # KV head first, a KV head's queries (token, head) as ONE long axis,
    # a head's unrotated and rotated dims side by side again: a step is ONE
    # product of (T hp, dk) x (dk, span) a KV head, and the (T, H, span)
    # float32 scores — a step's traffic is theirs — are written once
    q = jnp.concatenate([q_plain, q_rot], -1).reshape(T, G, hp, -1) \
        .transpose(1, 0, 2, 3).reshape(G, T * hp, -1)
    q_pos = jnp.repeat(start + jnp.arange(T), hp)             # (T hp,)

    def attend(i, carry):
        m, l, acc = carry
        pages = [jnp.where(i * pages_per_step + b < n_pages,
                           table[jnp.minimum(i * pages_per_step + b,
                                             table.shape[0] - 1)], 0)
                 for b in range(pages_per_step)]
        kv = jnp.concatenate([kv_pool[p] for p in pages], 0)  # (span, entry)
        kp, kr, v = gqa_split(kv, G, plain, r, dv)
        key_pos = i * span + jnp.arange(span)
        ok = (key_pos[None, :] <= q_pos[:, None]) & (key_pos[None, :] < end)
        s = jnp.einsum("gne,sge->gns", q, jnp.concatenate([kp, kr], -1),
                       preferred_element_type=F32) * scale
        s = jnp.where(ok[None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        l = l * fix + jnp.sum(p, -1)
        acc = acc * fix[..., None] + jnp.einsum(
            "gns,sge->gne", p.astype(kv.dtype), v,
            preferred_element_type=F32)
        return m_new, l, acc

    _, l, acc = lax.fori_loop(
        0, (n_pages + pages_per_step - 1) // pages_per_step, attend,
        (jnp.full((G, T * hp), NEG, F32), jnp.zeros((G, T * hp), F32),
         jnp.zeros((G, T * hp, dv), F32)))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(kv_pool.dtype)
    return o.reshape(G, T, hp, dv).transpose(1, 0, 2, 3).reshape(T, H, dv)


def prefill_gqa_window(q_plain, q_rot, c_new, prev, prev_pos, start, n_valid,
                       sink, G: int, dv: int, scale: float, window: int,
                       q_block: int):
    """Windowed grouped-query attention of a chunk, with the sink column
    where the layer has one: keys and values are the session's last
    ``window − 1`` entries before the chunk (``prev`` (window − 1, entry)
    in position order, at positions ``prev_pos``, negative where there is
    none) and the chunk's own (``c_new`` (T, entry)); ``q_block`` queries
    at a time against the ``q_block + window − 1`` entries they can see
    → (T, H, dv)."""
    T, H, plain = q_plain.shape
    entries = jnp.concatenate([prev, c_new], 0)            # (W-1+T, entry)
    pos = jnp.concatenate([prev_pos, start + jnp.arange(T)])
    ok_key = jnp.concatenate([prev_pos >= 0, jnp.arange(T) < n_valid])
    kp, kr, v = gqa_split(entries, G, plain, q_rot.shape[-1], dv)
    qp, qr = (q.reshape(T, G, H // G, -1) for q in (q_plain, q_rot))
    out = []
    for lo in range(0, T, q_block):
        hi = min(T, lo + q_block)
        # queries lo..hi see entries (lo .. hi + window - 1)
        ks = slice(lo, hi + window - 1)
        q_pos = start + jnp.arange(lo, hi)
        ok = (ok_key[ks][None, :] & (pos[ks][None, :] <= q_pos[:, None])
              & (pos[ks][None, :] > q_pos[:, None] - window))
        s = (jnp.einsum("tgae,sge->tgas", qp[lo:hi], kp[ks],
                        preferred_element_type=F32)
             + jnp.einsum("tgae,sge->tgas", qr[lo:hi], kr[ks],
                          preferred_element_type=F32)) * scale
        s = jnp.where(ok[:, None, None, :], s, NEG)
        p = softmax_sink(s, sink).astype(v.dtype)
        out.append(jnp.einsum("tgas,sge->tgae", p, v[ks]).reshape(
            hi - lo, H, dv))
    return jnp.concatenate(out, 0)
