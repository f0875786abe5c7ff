"""Pallas TPU kernel: greedy NMS suppression sweep.

The XLA-level NMS (``ops.nms``) materializes a K×K IoU matrix and runs a
``fori_loop`` of argmax+mask rounds.  This kernel instead keeps everything
resident in VMEM and exploits the *sorted* candidate order: one sequential
sweep i = 0..K-1 — if candidate i is still active it is kept and its IoU
row (computed on the fly, one VPU pass over K lanes) deactivates later
overlapping candidates.  No K×K matrix, no per-round argmax: O(K) kept-box
row computations instead of O(K²) storage + O(K·argmax) scans.

Per-class NMS is the grid dimension: scores/coords arrive as (C, K) arrays
(boxes pre-sorted by score descending per class, K padded to a lane
multiple), one grid step per class.

Correctness contract matches ``ops.nms.nms`` for pre-sorted input; the
wrapper :func:`pallas_nms` does the sort/top-k in XLA, calls the kernel,
and re-expresses the result as (keep_idx, keep_mask) in the original index
space.  ``interpret=True`` makes it runnable on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.vmem import round_up as _round_up


def _nms_kernel(x1_ref, y1_ref, x2_ref, y2_ref, valid_ref, keep_ref,
                active_ref, *, iou_threshold: float, k: int,
                off: float):
    """One class: sweep sorted candidates, suppress by IoU.

    TPU VMEM has no scalar stores, so all per-candidate reads/writes are
    masked full-row VPU ops over the (1, 1, K) lane vectors.  (The refs
    are 3-D because Mosaic requires the trailing two block dims to be
    (8k, 128k) or exactly the array dims — a (1, 1, K) block over a
    (C, 1, K) array satisfies the "exact" rule per class.)
    """
    active_ref[:] = valid_ref[:]                    # (1, 1, K) 1.0 = in play
    keep_ref[:] = jnp.zeros_like(keep_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2)
    # candidates arrive sorted by score descending with invalid lanes
    # masked out, so in practice valid is a prefix and usually short (the
    # conf_thresh pre-filter kills most of a class's priors).  The sweep
    # only needs to visit lanes up to the LAST valid one — a dynamic
    # bound (lowered to a while_loop) that collapses the common sparse
    # case from K iterations to a handful, and stays correct even for a
    # non-prefix valid mask.
    n_valid = jnp.max(jnp.where(valid_ref[:] > 0, lane + 1, 0))

    def pick(ref, is_i):
        return jnp.sum(jnp.where(is_i, ref[:], 0.0))

    def body(i, _):
        is_i = lane == i
        is_active = pick(active_ref, is_i) > 0.0

        @pl.when(is_active)
        def _():
            keep_ref[:] = jnp.where(is_i, 1.0, keep_ref[:])
            bx1 = pick(x1_ref, is_i)
            by1 = pick(y1_ref, is_i)
            bx2 = pick(x2_ref, is_i)
            by2 = pick(y2_ref, is_i)
            ix1 = jnp.maximum(x1_ref[:], bx1)
            iy1 = jnp.maximum(y1_ref[:], by1)
            ix2 = jnp.minimum(x2_ref[:], bx2)
            iy2 = jnp.minimum(y2_ref[:], by2)
            inter = (jnp.maximum(ix2 - ix1 + off, 0.0)
                     * jnp.maximum(iy2 - iy1 + off, 0.0))
            area = ((x2_ref[:] - x1_ref[:] + off)
                    * (y2_ref[:] - y1_ref[:] + off))
            area_i = (bx2 - bx1 + off) * (by2 - by1 + off)
            union = jnp.maximum(area + area_i - inter, 1e-12)
            iou = inter / union
            # deactivate everything overlapping the kept box (including
            # itself; its keep bit is already written)
            active_ref[:] = jnp.where(iou >= iou_threshold, 0.0,
                                      active_ref[:])

        return 0

    jax.lax.fori_loop(0, n_valid, body, 0)


def nms_sweep(x1, y1, x2, y2, valid, iou_threshold: float = 0.45,
              normalized: bool = True, interpret: bool = False):
    """(C, K) sorted per-class candidates → (C, K) keep mask.
    ``normalized=False`` uses the +1-pixel-width convention (matching
    ``ops.bbox.iou_matrix``'s flag)."""
    C, K = x1.shape
    kernel = functools.partial(_nms_kernel, iou_threshold=iou_threshold, k=K,
                               off=0.0 if normalized else 1.0)
    spec = pl.BlockSpec((1, 1, K), lambda c: (c, 0, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(C,),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((C, 1, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1, K), jnp.float32)],
        interpret=interpret,
    )(x1.astype(jnp.float32)[:, None, :], y1.astype(jnp.float32)[:, None, :],
      x2.astype(jnp.float32)[:, None, :], y2.astype(jnp.float32)[:, None, :],
      valid.astype(jnp.float32)[:, None, :])
    return out[:, 0, :]


@functools.partial(
    jax.jit,
    static_argnames=("iou_threshold", "max_output", "pre_topk",
                     "normalized", "interpret"))
def pallas_nms(boxes: jax.Array, scores: jax.Array,
               iou_threshold: float = 0.45, max_output: int = 200,
               pre_topk: int = 400, score_threshold: float = -1e30,
               normalized: bool = True,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for ``ops.nms.nms`` (single class) backed by the kernel.

    boxes (N,4), scores (N,) → (keep_idx (max_output,), keep_mask) in the
    original index space, ranked by score.
    """
    n = scores.shape[0]
    k = min(_round_up(pre_topk, 128), _round_up(n, 128))
    masked = jnp.where(scores > score_threshold, scores, -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(masked, min(k, n))
    pad = k - top_scores.shape[0]
    if pad:
        top_scores = jnp.pad(top_scores, (0, pad), constant_values=-jnp.inf)
        top_idx = jnp.pad(top_idx, (0, pad))
    tb = boxes[top_idx]                                   # (K, 4)
    valid = (top_scores > -jnp.inf).astype(jnp.float32)
    keep = nms_sweep(tb[None, :, 0], tb[None, :, 1], tb[None, :, 2],
                     tb[None, :, 3], valid[None], iou_threshold,
                     normalized=normalized, interpret=interpret)[0]  # (K,)
    # first max_output kept candidates, in sorted (score) order
    rank = jnp.cumsum(keep) - 1                           # rank among kept
    sel = (keep > 0) & (rank < max_output)
    # scatter kept candidates into their rank slot
    slot = jnp.where(sel, rank.astype(jnp.int32), max_output)
    keep_idx = jnp.full((max_output + 1,), -1, jnp.int32).at[slot].set(
        top_idx.astype(jnp.int32), mode="drop")[:max_output]
    keep_mask = jnp.zeros((max_output + 1,), jnp.float32).at[slot].set(
        1.0, mode="drop")[:max_output]
    return keep_idx, keep_mask
