"""Decode attention of the decoder LM (models/lm.py) as ONE Pallas program
a layer and step.  A CAUSAL layer: multi-head latent attention in the
absorbed form (``paged_mla_decode``) or grouped-query attention
(``paged_gqa_decode``, at the end of the file) of B rows of any sessions,
each over ALL of its own pages, read straight out of the paged pool
through the rows' page tables — online softmax, nothing gathered, nothing
of the scores in HBM.  A FULL layer, whose rows attend to the ``topk``
entries they selected: ``selected_mla_decode`` over the gathered copy of
those entries, read once (after the paged kernels, with why it does not
read the pool itself).  What follows is the paged latent kernel's story;
the grouped-query one shares the grid, the work items and the online
softmax (``_accumulate``).

A step that gathered its rows' contexts first (``pool[tables]``, the XLA
form: ops/lm_attention.py ``mla_paged``'s fallback) would copy every
row's padded ``max_len`` entries a layer: 64 rows x 45 k x 1,280 B = 3.7 GB.
Here a page is DMA'd once and scored by all heads against the one
``entry``-wide row it holds a token: a head's absorbed query
``[q_nope W_kvb^K ; q_rope ; 0]`` against ``[c_kv ; k_r ; 0]``.

**The grid is a flat list of (row, page) work items**, not (B, max_pages):
rows are ragged (2 to 88 pages at the benchmark's mix), and a rectangular
grid would be 5,632 steps for some 1,400 pages that hold anything.  A
page has one owner, so the pool's ``n_pages − 1`` pages bound the list:
``work_items`` lays the live rows' pages one row after another (a row's
pages consecutive: its running maximum, sum and accumulator stay in VMEM
scratch from its first page to its last) and the steps past the last item
repeat it and do nothing (the same blocks: no DMA).  ``grid_steps`` is
what the tier's gauge ``lm/paged_grid_steps`` reports beside the pages
that held something.

Per item the MXU sees two products with the 64 heads as the short side —
``(H, entry) x (page, entry)ᵀ`` and ``(H, page) x (page, rank)`` — so a
loaded tile of the page is used by 64 rows only.  On a v5e the kernel
takes 1.04–1.13 µs a page where the page's DMA is 0.80 (PERF.md §5, PR 33):
the short side and a grid step's own cost are reckoned to be the rest, not
read apart.

Off the TPU the kernel runs in interpret mode (the tests' way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import vmem
from analytics_zoo_tpu.utils import engine

F32 = jnp.float32
NEG = -1e30
LANES = 128
SUBLANES = 8


def supported(rank: int, entry: int, page: int) -> bool:
    """Whether the kernel takes these widths: the latent it cuts out of a
    page ends on a lane tile, and a page is whole sublane tiles."""
    return rank % LANES == 0 and entry % LANES == 0 and page % 16 == 0


def grid_steps(rows: int, max_pages: int, n_pages: int) -> int:
    """Steps the kernel is launched with for ``rows`` rows of up to
    ``max_pages`` pages over a pool of ``n_pages`` (page 0 nobody's)."""
    return max(1, min(rows * max_pages, n_pages - 1))


def work_items(lengths, tables, page: int, n_items: int):
    """The flat list: item w is page ``idx[w]`` (in its row's order) of
    row ``row[w]``, pool page ``phys[w]``; rows one after another, each
    row's ``ceil(length / page)`` pages in order.  Items past the last
    repeat its row and page with ``idx`` −1.  ``lengths`` (B,) tokens a
    row holds (0: a padding row), ``tables`` (B, max_pages)."""
    B, max_pages = tables.shape
    pages = (lengths + page - 1) // page
    ends = jnp.cumsum(pages)
    w = jnp.minimum(jnp.arange(n_items), jnp.maximum(ends[-1] - 1, 0))
    row = jnp.minimum(jnp.sum(ends[None, :] <= w[:, None], 1), B - 1)
    idx = w - (ends - pages)[row]
    phys = tables[row, jnp.clip(idx, 0, max_pages - 1)]
    real = jnp.arange(n_items) < ends[-1]
    return (row.astype(jnp.int32), phys.astype(jnp.int32),
            jnp.where(real, idx, -1).astype(jnp.int32))


def _accumulate(s, values, j, n, m_sc, l_sc, acc_sc, *, page: int):
    """One page of a row's online softmax: ``s`` (H, page) float32 scores
    of the page's tokens (row positions ``j * page ..``, of which the row
    holds ``n``), ``values`` (page, ·) what the probabilities weigh."""
    at = j * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(at < n, s, NEG)                         # (H, page)
    m_prev, l_prev = m_sc[...], l_sc[...]                 # (H, LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a row's first page holds its first token, so from the first
    # step on the maximum is a real score and a masked key's
    # exp(NEG − m) is 0
    p = jnp.exp(s - m_new[:, :1])
    l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_sc[...] = m_new
    acc_sc[...] = acc_sc[...] * alpha[:, :1] + jnp.dot(
        p.astype(values.dtype), values, preferred_element_type=F32)


def _start(j, m_sc, l_sc, acc_sc):
    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)


def _kernel(row_ref, page_ref, idx_ref, len_ref, q_ref, kv_ref, o_ref,
            m_sc, l_sc, acc_sc, *, page: int, rank: int, scale: float):
    w = pl.program_id(0)
    j = idx_ref[w]
    n = len_ref[row_ref[w]]
    _start(j, m_sc, l_sc, acc_sc)

    @pl.when(j >= 0)
    def _():
        kv = kv_ref[0]                                    # (page, entry)
        s = lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
        _accumulate(s, kv[:, :rank], j, n, m_sc, l_sc, acc_sc, page=page)

    @pl.when((j >= 0) & ((j + 1) * page >= n))            # its last page
    def _():
        o_ref[0] = (acc_sc[...] / l_sc[...][:, :1]).astype(o_ref.dtype)


def _gqa_kernel(row_ref, page_ref, idx_ref, len_ref, q_ref, kv_ref, o_ref,
                m_sc, l_sc, acc_sc, *, page: int, keys: int, groups: int,
                scale: float):
    w = pl.program_id(0)
    j = idx_ref[w]
    n = len_ref[row_ref[w]]
    _start(j, m_sc, l_sc, acc_sc)

    @pl.when(j >= 0)
    def _():
        kv = kv_ref[0]                                    # (page, entry)
        # every head against every KV head's keys in ONE product: a head's
        # query has zeros in the other KV heads' columns
        s = lax.dot_general(q_ref[0], kv[:, :keys], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
        _accumulate(s, kv[:, keys:], j, n, m_sc, l_sc, acc_sc, page=page)

    @pl.when((j >= 0) & ((j + 1) * page >= n))            # its last page
    def _():
        # of a head's sums over all KV heads' values, its own KV head's
        H, v = o_ref.shape[1], o_ref.shape[2]
        hp = H // groups
        for g in range(groups):
            rows = slice(g * hp, (g + 1) * hp)
            o_ref[0, rows, :] = (
                acc_sc[rows, g * v:(g + 1) * v] / l_sc[rows, :1]
            ).astype(o_ref.dtype)


def _vmem_bytes(H: int, page: int, q: int, entry: int, out: int, acc: int,
                dtype) -> int:
    """A paged kernel's blocks (double-buffered) and scratch as Mosaic lays
    them out, plus a step's scores and probabilities: queries ``q`` wide,
    a page of ``entry``, an output of ``out`` and an accumulator of
    ``acc`` a head."""
    blocks = (vmem.padded_bytes((H, q), dtype)
              + vmem.padded_bytes((page, entry), dtype)
              + vmem.padded_bytes((H, out), dtype))
    scratch = (2 * vmem.padded_bytes((H, LANES), F32)
               + vmem.padded_bytes((H, acc), F32))
    return 2 * blocks + scratch + 3 * vmem.padded_bytes((H, page), F32)


def declared_vmem_bytes(H: int, page: int, entry: int, rank: int,
                        dtype) -> int:
    """What the latent kernel asks for."""
    return _vmem_bytes(H, page, entry, entry, rank, rank, dtype)


def _paged_call(kernel, name: str, q, kv_pool, tables, lengths, out: int,
                acc: int, interpret):
    """One paged kernel over the flat (row, page) list: ``q`` (B, H, ·) a
    row's block, a page of the pool an item, the row's (H, ``out``) block
    written at its last page; scratch: running maximum, sum and an (H,
    ``acc``) accumulator.  → (B, H, out), zeros for a padding row (its
    block is never visited: whatever stood there)."""
    B, H, width = q.shape
    n_pages, page, entry = kv_pool.shape
    if interpret is None:
        interpret = not engine.on_tpu()
    lengths = lengths.astype(jnp.int32)
    n_items = grid_steps(B, tables.shape[1], n_pages)
    row, phys, idx = work_items(lengths, tables, page, n_items)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n_items,),
        in_specs=[
            pl.BlockSpec((1, H, width), lambda w, r, p, i, n: (r[w], 0, 0)),
            pl.BlockSpec((1, page, entry),
                         lambda w, r, p, i, n: (p[w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, out),
                               lambda w, r, p, i, n: (r[w], 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, LANES), F32),
                        pltpu.VMEM((H, LANES), F32),
                        pltpu.VMEM((H, acc), F32)])
    res = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, out), kv_pool.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem.limit_bytes(_vmem_bytes(
                H, page, width, entry, out, acc, kv_pool.dtype))),
        name=name,
        interpret=interpret,
    )(row, phys, idx, lengths, q, kv_pool)
    return jnp.where((lengths > 0)[:, None, None], res, 0)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def paged_mla_decode(q, kv_pool, tables, lengths, *, rank: int, scale: float,
                     interpret=None):
    """Absorbed MLA of B rows over their own pages.

    ``q`` (B, H, entry): a head's absorbed query — ``q_nope W_kvb^K``
    (``rank`` wide), the rotated rotary part, zeros up to the entry's
    width; ``kv_pool`` (n_pages, page, entry): latent, rotated shared key,
    zeros; ``tables`` (B, max_pages) the rows' page tables; ``lengths``
    (B,) the tokens each row attends to (the one being decoded, already
    written, included; 0: a padding row) → (B, H, rank) in the pool's
    dtype, the softmax-weighted latents (zeros for a padding row)."""
    entry, page = q.shape[2], kv_pool.shape[1]
    if kv_pool.shape[2] != entry or not supported(rank, entry, page):
        raise ValueError(f"paged_mla_decode: q {q.shape}, pool "
                         f"{kv_pool.shape}, rank {rank} do not fit")
    return _paged_call(
        functools.partial(_kernel, page=page, rank=rank, scale=scale),
        "lm_decode_mla_paged", q, kv_pool, tables, lengths, rank, rank,
        interpret)


# ---------------------------------------------------------------------------
# a full layer: the entries a row selected, gathered, read once
# ---------------------------------------------------------------------------
#
# The selected entries are NOT read where they lie (PERF.md §6, PR 38): the
# pool's HBM layout is ``tiled<(8,128)(2,1)>`` — two positions share every
# 32-bit word, eight a tile — so Mosaic refuses a copy of fewer than 8
# aligned entries, and a copy of 8 issues in 27–29 ns on a v5e however many
# are in flight, where the XLA gather takes 15.7 ns an entry.  (From 64
# entries a copy the same loop runs at 715 GB/s: whole pages are another
# matter.)  So XLA gathers, once a layer, and this program keeps the copy
# from being read twice more: ``mla_absorbed`` read it for the scores, for
# their rotary part and for the values (alone: 1.0 ms a layer where one pass
# takes 0.45; in the dots3 step the scope ``lm/mla_full`` went 3.03 → 1.38 ms).

def _selected_kernel(q_ref, kv_ref, ok_ref, o_ref, *, rank: int,
                     scale: float):
    kv = kv_ref[0]                                        # (k, entry)
    s = lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                        preferred_element_type=F32) * scale
    ok = jnp.broadcast_to(ok_ref[0], s.shape) != 0        # (H, k)
    s = jnp.where(ok, s, NEG)
    # a row with no entry at all (a padding row) would weigh every slot
    # alike: its probabilities are dropped, its output zeros
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jnp.dot(p.astype(kv.dtype), kv[:, :rank], preferred_element_type=F32)
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def selected_declared_vmem_bytes(H: int, k: int, entry: int, rank: int,
                                 dtype) -> int:
    """What :func:`selected_mla_decode` asks for: a row's queries, its
    ``k`` entries, their mask and its output (double-buffered), the (H, k)
    float32 scores and their probabilities."""
    return (_vmem_bytes(H, k, entry, entry, rank, rank, dtype)
            + 2 * vmem.padded_bytes((1, k), jnp.int32))


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def selected_mla_decode(q, entries, valid, *, rank: int, scale: float,
                        interpret=None):
    """Absorbed MLA of B rows, each over its own ``k`` gathered entries.

    ``q`` (B, H, entry): a head's absorbed query as
    :func:`paged_mla_decode` takes it; ``entries`` (B, k, entry): latent,
    rotated shared key, zeros; ``valid`` (B, k): which slots hold an entry
    the row attends to (the others hold anything finite) → (B, H, rank) in
    the entries' dtype, the softmax-weighted latents — float32 scores and
    sums, probabilities in the entries' dtype, as the paged kernels have
    them; zeros for a row with no valid slot.  A grid step is a row: its
    entries come into VMEM once (2.6 MB at 2,048 x 640 in bfloat16) while
    the row before is scored."""
    B, H, entry = q.shape
    k = entries.shape[1]
    if entries.shape != (B, k, entry) or not supported(rank, entry, k):
        raise ValueError(f"selected_mla_decode: q {q.shape}, entries "
                         f"{entries.shape}, rank {rank} do not fit")
    if interpret is None:
        interpret = not engine.on_tpu()
    return pl.pallas_call(
        functools.partial(_selected_kernel, rank=rank, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), entries.dtype),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, entry), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, k, entry), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, 1, k), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, H, rank), lambda b: (b, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem.limit_bytes(selected_declared_vmem_bytes(
                H, k, entry, rank, entries.dtype))),
        name="lm_decode_mla_selected",
        interpret=interpret,
    )(q, entries, valid.astype(jnp.int32)[:, None, :])


# ---------------------------------------------------------------------------
# grouped-query attention: real keys and values a KV head in the pool
# ---------------------------------------------------------------------------

def gqa_supported(kv_heads: int, k: int, v: int, heads: int,
                  page: int) -> bool:
    """Whether :func:`paged_gqa_decode` takes these widths: the entry's
    keys ``[plain | rotary]`` of all KV heads end on a lane tile, a KV
    head's values are whole lane tiles, a page whole sublane tiles, and
    every KV head has as many query heads (the caller pads them to whole
    sublane tiles: ops/lm_attention.py ``gqa_block_queries``)."""
    return (kv_heads * k % LANES == 0 and v % LANES == 0
            and heads % kv_heads == 0 and page % 16 == 0)


def gqa_declared_vmem_bytes(H: int, page: int, keys: int, values: int,
                            v: int, dtype) -> int:
    """What the grouped-query kernel asks for."""
    return _vmem_bytes(H, page, keys, keys + values, v, values, dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "v", "scale",
                                             "interpret"))
def paged_gqa_decode(q, kv_pool, tables, lengths, *, kv_heads: int, v: int,
                     scale: float, interpret=None):
    """Grouped-query attention of B rows over their own pages.

    ``kv_pool`` (n_pages, page, entry): a token's entry is ``[keys |
    values]``, the keys ``keys = entry − kv_heads · v`` wide — every KV
    head's unrotated dims, then every KV head's rotated dims
    (ops/lm_attention.py ``gqa_entry``: each part ends on a lane tile where
    a head's own 192 do not) — the values a KV head after another.  ``q``
    (B, H, keys): a head's query laid out against the keys of ITS KV head
    (head a reads KV head ``a // (H / kv_heads)``), zeros elsewhere
    (``gqa_block_queries``), so one product scores a page for all heads;
    ``tables``, ``lengths`` as :func:`paged_mla_decode`'s → (B, H, v) in
    the pool's dtype (zeros for a padding row).  A page is DMA'd once and
    serves all H heads: 64 x 768 x 512 and 64 x 512 x 512 multiply-adds a
    page of 1.3 MB — three quarters of them against another KV head's
    columns (zeros, or sums nobody reads), all of them under the page's
    DMA."""
    H, keys = q.shape[1:]
    _, page, entry = kv_pool.shape
    values = kv_heads * v
    if keys + values != entry or keys % kv_heads \
            or H // kv_heads % SUBLANES \
            or not gqa_supported(kv_heads, keys // kv_heads, v, H, page):
        raise ValueError(f"paged_gqa_decode: q {q.shape}, pool "
                         f"{kv_pool.shape}, {kv_heads} KV heads of {v} "
                         f"values do not fit")
    return _paged_call(
        functools.partial(_gqa_kernel, page=page, keys=keys, groups=kv_heads,
                          scale=scale),
        "lm_decode_gqa_paged", q, kv_pool, tables, lengths, v, values,
        interpret)
