"""Prefill attention of the decoder LM's full layers as ONE Pallas program
a chunk and layer: masked multi-head latent attention of a chunk's T
queries over its session's pages, online softmax, nothing of the scores
in HBM.

The XLA form (ops/lm_attention.py ``prefill_full_attention``'s loop) is
the absorbed one — every head scores the 576-wide latent and accumulates
a 512-wide output, 2,176 operations a (query, key, head) — and it writes
the (T, H, keys) scores and reads and writes a (T, H, rank) float32
accumulator in HBM every iteration: 2.3 ms a page of 512 keys at 1,024
queries on a v5e, 145 of the 233 s that the prefill of a million tokens
took (PR 28).  Here a page's latents are DECOMPRESSED in VMEM for a group
of heads (``latent @ wkv_b``: keys' nope part and values), and the scores
are taken in the plain form: 640 operations a (query, key, head) plus the
decompression, the scores and the accumulator never leave VMEM.

Grid ``(H / heads_per_step, steps)``, the page axis innermost: a head
group's queries, its columns of ``wkv_b`` and its accumulators stay in
VMEM while the session's pages stream through (the page table is a
scalar-prefetch argument, so a page's DMA is addressed by it); steps past
the session's last page do nothing.  Which keys a query attends to comes
in as an additive bias (0 or ``NEG``; the selection itself —
ops/lm_attention.py — is the caller's).

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


def supported(nope: int, v: int, rank: int, entry: int, rope: int,
              heads: int, heads_per_step: int) -> bool:
    """Whether the kernel takes these widths: every slice it cuts out of
    a page or out of ``wkv_b`` starts and ends on a lane tile, and the
    entry's tail past the latent (rotary key, zeros) is what the queries'
    rotary part is padded to."""
    return (nope % LANES == 0 and v % LANES == 0 and rank % LANES == 0
            and entry > rank and (entry - rank) % LANES == 0
            and rope <= entry - rank and heads % heads_per_step == 0)


def _kernel(table_ref, n_ref, q_ref, w_ref, kv_ref, bias_ref, o_ref,
            m_sc, l_sc, acc_sc, *, hb: int, rank: int, nope: int, v: int,
            scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    @pl.when(j < n_ref[0])
    def _():
        kv = kv_ref[0]                                    # (page, entry)
        k_rot = kv[:, rank:]                              # rotary key, zeros
        # the group's keys (nope part) and values of this page
        kvx = jnp.dot(kv[:, :rank], w_ref[...],
                      preferred_element_type=F32).astype(kv.dtype)
        bias = bias_ref[...].astype(F32)                  # (T, page)
        for i in range(hb):
            c = i * (nope + v)
            k_i = jnp.concatenate([kvx[:, c:c + nope], k_rot], axis=1)
            s = lax.dot_general(q_ref[i], k_i, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale + bias
            m_prev, l_prev = m_sc[i], l_sc[i]             # (T, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row none of whose keys so far is selected stands at NEG and
            # collects exp(0) of masked keys; its first selected key's
            # alpha = exp(NEG - m) = 0 wipes that out (every real query
            # selects at least itself)
            p = jnp.exp(s - m_new[:, :1])
            l_sc[i] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_sc[i] = m_new
            acc_sc[i] = acc_sc[i] * alpha[:, :1] + jnp.dot(
                p.astype(kv.dtype), kvx[:, c + nope:c + nope + v],
                preferred_element_type=F32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for i in range(hb):
            o_ref[i] = (acc_sc[i] / jnp.maximum(l_sc[i][:, :1], 1e-30)
                        ).astype(o_ref.dtype)


def declared_vmem_bytes(T: int, page: int, entry: int, rank: int, nope: int,
                        v: int, hb: int, itemsize: int = 2) -> int:
    """The kernel's blocks (double-buffered) and scratch, padded as Mosaic
    lays them out, plus the values a step holds: the decompressed page and
    one head's scores and probabilities."""
    dt = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    qk = nope + entry - rank
    blocks = (vmem.padded_bytes((hb, T, qk), dt)
              + vmem.padded_bytes((rank, hb * (nope + v)), dt)
              + vmem.padded_bytes((page, entry), dt)
              + vmem.padded_bytes((T, page), dt)
              + vmem.padded_bytes((hb, T, v), dt))
    scratch = (2 * vmem.padded_bytes((hb, T, LANES), F32)
               + vmem.padded_bytes((hb, T, v), F32))
    values = (vmem.padded_bytes((page, hb * (nope + v)), F32)
              + 3 * vmem.padded_bytes((T, page), F32))
    return 2 * blocks + scratch + values


@functools.partial(jax.jit, static_argnames=("nope", "scale", "steps",
                                             "heads_per_step", "interpret"))
def flash_mla_prefill(q, wkv_b, kv_pool, bias, table, n_pages, *, nope: int,
                      scale: float, steps: int, heads_per_step: int = 4,
                      interpret=None):
    """Masked MLA of one chunk against its session's pages.

    ``q`` (H, T, nope + pad): a head's queries — nope part, rotated rotary
    part, zeros up to ``pad`` = entry − rank; ``wkv_b`` (rank, H, nope + v);
    ``kv_pool`` (n_pages, page, entry): latent, rotated shared key, zeros;
    ``bias`` (T, steps · page): 0 where the query attends to the key at
    that position of the session, ``NEG`` elsewhere; ``table`` (max_pages,)
    the session's pages; ``n_pages`` () how many of them hold keys.
    → (H, T, v) in the pool's dtype (zeros where ``n_pages`` is 0)."""
    H, T, qk = q.shape
    rank, _, nv = wkv_b.shape
    v = nv - nope
    _, page, entry = kv_pool.shape
    hb = heads_per_step
    if interpret is None:
        interpret = not engine.on_tpu()
    if qk != nope + entry - rank or H % hb or bias.shape != (T, steps * page):
        raise ValueError(f"flash_mla_prefill: q {q.shape}, wkv_b "
                         f"{wkv_b.shape}, pool {kv_pool.shape}, bias "
                         f"{bias.shape} do not fit (steps={steps}, "
                         f"heads_per_step={hb})")

    def last(j, n):                     # the page a step works on or keeps
        return jnp.maximum(jnp.minimum(j, n[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(H // hb, steps),
        in_specs=[
            pl.BlockSpec((hb, T, qk), lambda g, j, tab, n: (g, 0, 0)),
            pl.BlockSpec((rank, hb * nv), lambda g, j, tab, n: (0, g)),
            pl.BlockSpec((1, page, entry),
                         lambda g, j, tab, n: (tab[last(j, n)], 0, 0)),
            pl.BlockSpec((T, page), lambda g, j, tab, n: (0, last(j, n))),
        ],
        out_specs=pl.BlockSpec((hb, T, v), lambda g, j, tab, n: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hb, T, LANES), F32),
                        pltpu.VMEM((hb, T, LANES), F32),
                        pltpu.VMEM((hb, T, v), F32)])
    declared = declared_vmem_bytes(T, page, entry, rank, nope, v, hb,
                                   jnp.dtype(kv_pool.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, rank=rank, nope=nope, v=v,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((H, T, v), kv_pool.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem.limit_bytes(declared)),
        name="lm_prefill_mla",
        interpret=interpret,
    )(table.astype(jnp.int32), jnp.reshape(n_pages, (1,)).astype(jnp.int32),
      q, wkv_b.reshape(rank, H * nv), kv_pool, bias)
