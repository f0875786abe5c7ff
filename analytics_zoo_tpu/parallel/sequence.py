"""Sequence/context parallelism: ring attention + sequence-sharded helpers.

The reference's only long-sequence mechanism is data-level chunking
(``TimeSegmenter.scala:11``: split audio into independent rows, re-join by
``(audio_id, seq)`` — see SURVEY.md §5 "Long-context").  A TPU-native
framework needs true *sequence parallelism*: shard the time axis T across
the mesh's ``sequence`` axis and exchange blocks over ICI.

This module provides:

- :func:`ring_attention` — blockwise attention where K/V blocks rotate
  around the ring via ``lax.ppermute`` while each device keeps a running
  online-softmax (flash-attention style) over its local Q block.  Memory
  per device is O(T/n · T/n) instead of O(T²); the n-step rotation overlaps
  compute with ICI transfers.  Supports causal masking via global block
  offsets.
- :func:`shard_sequence` / :func:`unshard_sequence` — place (B, T, …)
  activations on the sequence axis.
- collective helpers (:func:`psum_mean`, :func:`ring_shift`) used by
  sequence-parallel layers.

All functions are built on ``shard_map`` over an explicit Mesh, so they
compose with the data-parallel train step (mesh axes ``("data",
"sequence")``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel.mesh import SEQUENCE_AXIS

NEG_INF = -1e30


def shard_sequence(x, mesh: Mesh, axis_name: str = SEQUENCE_AXIS):
    """Place (B, T, …) on the mesh with T sharded over ``axis_name``."""
    spec = P(None, axis_name, *([None] * (np.ndim(x) - 2)))
    # az-allow: one-placement-site — T-axis staging predates the SpecSet substrate; folding sequence parallelism into specs is ROADMAP work
    return jax.device_put(x, NamedSharding(mesh, spec))


def unshard_sequence(x):
    return jax.device_get(x)


def psum_mean(x, axis_name: str):
    """Mean across an axis's devices (gradient/metric reduction helper)."""
    return jax.lax.psum(x, axis_name) / jax.lax.psum(1, axis_name)


def ring_shift(x, axis_name: str, shift: int = 1):
    """Rotate a block one hop around the ring (ppermute over ICI)."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float]):
    """Per-device body: q/k/v are LOCAL blocks (B, Tb, H, D)."""
    B, Tb, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    # accumulators in (B, H, Tq) layout for the online softmax
    o = jnp.zeros((B, H, Tb, D), q.dtype)
    l = jnp.zeros((B, H, Tb), jnp.float32)
    m = jnp.full((B, H, Tb), NEG_INF, jnp.float32)
    q_pos = my_idx * Tb + jnp.arange(Tb)                 # global q positions

    def step(r, carry):
        o, l, m, k_cur, v_cur = carry
        # k_cur originated on device (my_idx - r) mod n
        src = (my_idx - r) % n
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur) * scale
        scores = scores.astype(jnp.float32)
        if causal:
            k_pos = src * Tb + jnp.arange(Tb)
            mask = q_pos[:, None] >= k_pos[None, :]      # (Tq, Tk)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        blk_max = jnp.max(scores, axis=-1)               # (B, H, Tq)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(scores - new_m[..., None])
        # rows with no valid key yet: new_m stays NEG_INF -> p would be
        # exp(0)=1 garbage; zero them explicitly
        p = jnp.where((new_m[..., None] > NEG_INF / 2), p, 0.0)
        corr = jnp.where(m > NEG_INF / 2, jnp.exp(m - new_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_cur.dtype), v_cur)
        o = o * corr[..., None].astype(o.dtype) + pv
        # rotate K/V one hop; after n steps every device saw every block
        k_next = ring_shift(k_cur, axis_name)
        v_next = ring_shift(v_cur, axis_name)
        return o, l, m * 0 + new_m, k_next, v_next

    o, l, m, _, _ = jax.lax.fori_loop(0, n, step, (o, l, m, k, v))
    out = o / jnp.maximum(l, 1e-20)[..., None].astype(o.dtype)
    return jnp.einsum("bhqd->bqhd", out)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = SEQUENCE_AXIS,
                   causal: bool = False, scale: Optional[float] = None):
    """Sequence-parallel attention over a T-sharded batch.

    q, k, v: (B, T, H, D) with T sharded over ``axis_name`` (use
    :func:`shard_sequence`).  Returns (B, T, H, D), same sharding.  Inside
    jit, XLA lowers the per-step ``ppermute`` to ICI sends overlapping the
    per-block matmuls — the standard ring-attention schedule.
    """
    spec = P(None, axis_name, None, None)
    body = functools.partial(_ring_attention_local, axis_name=axis_name,
                             causal=causal, scale=scale)
    fn = _shard_map(body, mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def full_attention(q, k, v, causal: bool = False,
                   scale: Optional[float] = None):
    """Single-device reference implementation (for tests and small T)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Tq, Tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _shard_map(body, mesh, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def halo_exchange(x, axis_name: str, left: int, right: int, time_axis: int = 1):
    """Append neighbors' edge frames to a T-sharded block (non-wrapping).

    For temporal convs over a sharded time axis: each device receives the
    last ``left`` frames of its left neighbor and the first ``right``
    frames of its right neighbor.  Edge devices receive ZEROS (ppermute's
    semantics for non-receivers), which exactly emulates the zero padding
    a global SAME/padded conv would apply — so a VALID conv on the extended
    block reproduces the unsharded result.  Call inside shard_map.
    """
    n = jax.lax.psum(1, axis_name)
    parts = []
    if left:
        edge = jax.lax.slice_in_dim(x, x.shape[time_axis] - left, None,
                                    axis=time_axis)
        recv = jax.lax.ppermute(edge, axis_name,
                                [(i, i + 1) for i in range(n - 1)])
        parts.append(recv)
    parts.append(x)
    if right:
        edge = jax.lax.slice_in_dim(x, 0, right, axis=time_axis)
        recv = jax.lax.ppermute(edge, axis_name,
                                [(i + 1, i) for i in range(n - 1)])
        parts.append(recv)
    return jnp.concatenate(parts, axis=time_axis)


def sequence_sharded_scan(step_fn, h0, xs, mesh: Mesh,
                          axis_name: str = SEQUENCE_AXIS,
                          reverse: bool = False,
                          batch_axis: Optional[str] = None):
    """Exact RNN scan over a time-sharded sequence (SURVEY.md §5 north star).

    ``xs``: (B, T, D) with T sharded over ``axis_name``; ``h0``: (B, H)
    replicated; ``step_fn(h, x_t) → (h', y_t)`` with y the same shape as h.
    Returns (B, T, H), T-sharded like the input.

    Schedule: n SPMD rounds.  Every round each device scans its local
    chunk from its current boundary state, then passes its final state one
    hop along the pipeline via ``ppermute``.  Device k's input state is
    exact in round k (it has received the chained boundary states of all
    predecessors), so its outputs from that round are kept and the rest
    discarded.  Wall-clock equals the unsharded scan (the recurrence is
    inherently sequential) but per-device *activation memory* is O(T/n) —
    the enabler for sequences that do not fit one chip; the reference's
    only answer was lossy chunking (``TimeSegmenter.scala:11``).  For a
    bidirectional pair use :func:`sequence_scan_local_bidir`, which fuses
    both directions into ONE round loop (opposite pipelines sharing the
    same n rounds) instead of two sequential loops.

    ``batch_axis``: name of the mesh axis sharding B (for 2-D
    ("data","sequence") meshes) — only used to build the in/out specs.
    """
    time_spec = P(batch_axis, axis_name, None)
    h_spec = P(batch_axis, None)

    def local(h0_l, x_l):
        return sequence_scan_local(step_fn, h0_l, x_l, axis_name, reverse)

    fn = _shard_map(local, mesh, in_specs=(h_spec, time_spec),
                    out_specs=time_spec)
    return fn(h0, xs)


def sequence_scan_local(step_fn, h0_l, x_l, axis_name: str,
                        reverse: bool = False):
    """Per-device body of :func:`sequence_sharded_scan` — call inside an
    enclosing ``shard_map`` (e.g. a whole sequence-parallel model forward).
    ``x_l``: local (B, Tb, D) chunk; ``h0_l``: (B, H)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    eff = (n - 1 - idx) if reverse else idx
    xt = jnp.moveaxis(x_l, 1, 0)                         # (Tb, B, D)
    if reverse:
        xt = jnp.flip(xt, 0)

    def chunk_scan(h):
        return jax.lax.scan(lambda c, x: step_fn(c, x), h, xt)

    # pipeline hop: forward passes state idx→idx+1; reverse idx→idx-1
    if reverse:
        perm = [(i + 1, i) for i in range(n - 1)]
    else:
        perm = [(i, i + 1) for i in range(n - 1)]

    ys_init = jnp.zeros((xt.shape[0],) + h0_l.shape, h0_l.dtype)

    def round_body(r, carry):
        h_in, ys_acc = carry
        h_fin, ys = chunk_scan(h_in)
        ys_acc = jnp.where(eff == r, ys, ys_acc)
        h_next = jax.lax.ppermute(h_fin, axis_name, perm)
        # devices at the pipeline head re-enter with the true initial
        # state (they only matter in round 0, already kept)
        h_next = jnp.where(eff == 0, h0_l, h_next)
        return h_next, ys_acc

    _, ys = jax.lax.fori_loop(0, n, round_body, (h0_l, ys_init))
    if reverse:
        ys = jnp.flip(ys, 0)
    return jnp.moveaxis(ys, 0, 1)                        # (B, Tb, H)


def sequence_scan_local_bidir(step_fwd, step_bwd, h0_l, x_l, axis_name: str):
    """Fused bidirectional pipelined scan — fwd and bwd directions share
    the SAME n rounds (one loop, two opposite ppermute pipelines), so a
    BiRNN layer costs n rounds, not 2n.  Returns (ys_fwd, ys_bwd), each
    (B, Tb, H).  Call inside shard_map."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    eff_f = idx
    eff_b = n - 1 - idx
    xt = jnp.moveaxis(x_l, 1, 0)                         # (Tb, B, D)
    xt_rev = jnp.flip(xt, 0)

    perm_f = [(i, i + 1) for i in range(n - 1)]
    perm_b = [(i + 1, i) for i in range(n - 1)]
    ys_init = jnp.zeros((xt.shape[0],) + h0_l.shape, h0_l.dtype)

    def round_body(r, carry):
        hf_in, hb_in, ysf_acc, ysb_acc = carry
        hf_fin, ysf = jax.lax.scan(lambda c, x: step_fwd(c, x), hf_in, xt)
        hb_fin, ysb = jax.lax.scan(lambda c, x: step_bwd(c, x), hb_in, xt_rev)
        ysf_acc = jnp.where(eff_f == r, ysf, ysf_acc)
        ysb_acc = jnp.where(eff_b == r, ysb, ysb_acc)
        hf_next = jax.lax.ppermute(hf_fin, axis_name, perm_f)
        hb_next = jax.lax.ppermute(hb_fin, axis_name, perm_b)
        hf_next = jnp.where(eff_f == 0, h0_l, hf_next)
        hb_next = jnp.where(eff_b == 0, h0_l, hb_next)
        return hf_next, hb_next, ysf_acc, ysb_acc

    _, _, ysf, ysb = jax.lax.fori_loop(
        0, n, round_body, (h0_l, h0_l, ys_init, ys_init))
    return (jnp.moveaxis(ysf, 0, 1),
            jnp.moveaxis(jnp.flip(ysb, 0), 0, 1))


class RingAttentionLayer:
    """Callable bundling a mesh + settings, usable as a model-side op for
    long-context attention blocks (net-new capability vs the reference)."""

    def __init__(self, mesh: Mesh, axis_name: str = SEQUENCE_AXIS,
                 causal: bool = False):
        self.mesh = mesh
        self.axis_name = axis_name
        self.causal = causal

    def __call__(self, q, k, v):
        return ring_attention(q, k, v, self.mesh, self.axis_name, self.causal)
