"""Fused DetectionOutput: the whole SSD post-processing chain as ONE
batched Pallas program.

The unfused serve path (``ops/detection_output.py`` backend="pallas")
is four XLA/Pallas stages with materialized intermediates between them:
decode (B,P,4) → per-class ``lax.top_k`` + gathers (B,C,K scores, idx,
boxes) → the ``pallas_nms.nms_sweep`` kernel (B·C,K) → a global
``lax.top_k`` over (B, C·K).  Every arrow is an HBM round-trip and a
stage boundary the serve-profile decomposition could not attribute
(SERVE_PROFILE.json's pre-r9 −423 ms residual).  This module is the
same math as ONE kernel over a ``(batch, class)`` grid:

- **decode** runs in-kernel at the first class step of each image (loc
  and prior blocks have constant-over-class index maps, so Pallas
  keeps them VMEM-resident; the corner boxes land in VMEM scratch that
  persists across the class grid — the ``pallas_rnn`` residency trick);
- **confidence filter + candidate selection + suppression sweep** fuse
  into a single greedy loop per (image, class): pop the max remaining
  score above ``conf_thresh`` (the pop ORDER is the sorted order, so
  no top_k materialization is needed), stop after ``nms_topk`` pops
  (the reference's nmsFast topk-400 pre-filter, reproduced exactly:
  rank is the pop index), and for each still-active pop write its keep
  bit and deactivate overlapping candidates with one VPU IoU row.
  The background class never enters: only foreground rows are in the
  grid, so the discard happens at selection, not by post-hoc masking;
- **global cross-class top-K** runs at the last class step from the
  accumulated per-class keep scores (a ``(C_fg, P)`` VMEM scratch):
  pop the global max ``keep_topk`` times, tie-broken by flattened
  (class, prior) index — exactly ``lax.top_k``'s stable order over the
  reference's class-major candidate layout — and write ``(class_id,
  score, x1, y1, x2, y2)`` rows directly into the output block.

Candidates never leave VMEM between the stages; the only HBM traffic
is streaming the inputs once and writing the (B, keep_topk, 6) result.

Semantics contract: bit-for-bit the same detections as
``detection_output_single`` (and therefore the xla/pallas backends) up
to float associativity — pinned ≤1e-5 (measured exact on the test
geometries) by ``tests/test_pallas_detout.py``, including score-tie
ordering (int8-quantized confidences) because both tie-break rules
reduce to lowest-flat-index-first.

``interpret=True`` (automatic off-TPU) discharges the kernel to XLA so
CPU tier-1 runs the fused semantics.  ``detection_output`` selects this
kernel only for geometries whose :func:`fused_vmem_bytes` fits
``ops.vmem.VMEM_BUDGET_BYTES``, and the same figure is the VMEM limit
the kernel requests from Mosaic.

``stage`` builds prefix programs of the same kernel ("decode" →
"select" → "full") so ``tools/profile_serve.py`` can ladder the fused
cost into parts that sum to the whole BY CONSTRUCTION (each rung is a
prefix; rung deltas are stage costs) — the coherence the pre-r9
decomposition lacked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.pallas_nms import _round_up
from analytics_zoo_tpu.ops.vmem import compiler_params, padded_bytes

#: prefix programs for the profile ladder (each includes the previous)
STAGES = ("decode", "select", "full")


def fused_vmem_bytes(n_priors: int, n_classes: int, keep_topk: int) -> int:
    """VMEM the fused program's buffers occupy, priced the way Mosaic
    lays them out (``ops.vmem.padded_bytes``): every ``(1, 1, P)`` lane
    vector and every ``(1, 4, P)`` block pads to 8 sublanes, so the
    figure is ~8× the logical bytes.  Counted: the per-class keep
    scratch (C_fg rows), the seven work vectors, the double-buffered
    score and loc blocks, the single-buffered prior/variance blocks
    (whole-array windows are not double-buffered) and the
    double-buffered output block.  ``detection_output`` selects on it and
    ``fused_detection_output`` hands it to Mosaic as the VMEM limit."""
    ppad = _round_up(n_priors, 128)
    n_fg = max(n_classes - 1, 1)
    vec = padded_bytes((1, 1, ppad), np.float32)
    quad = padded_bytes((1, 4, ppad), np.float32)
    scratch = (n_fg + 7) * vec
    blocks = 2 * vec + 2 * quad + 2 * quad
    return scratch + blocks + 2 * padded_bytes((keep_topk, 6), np.float32)


def _fused_kernel(scores_ref, loc_ref, priors_ref, var_ref, out_ref,
                  bx1, by1, bx2, by2, active, remaining, curkeep, allkeep,
                  *, n_fg: int, n_priors: int, ppad: int, kout: int,
                  conf_thresh: float, nms_thresh: float, nms_topk: int,
                  bg_id: int, clip: bool, stage: str):
    """One (image, class) grid step.  All per-candidate reads/writes are
    masked full-row VPU ops (TPU VMEM has no scalar stores — the
    ``pallas_nms`` convention); scratch persists across the class grid,
    which is what lets decode run once per image and the global merge
    see every class's keeps without an HBM round-trip."""
    c = pl.program_id(1)
    f32 = jnp.float32
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, ppad), 2)

    def pick(vec_, is_):
        return jnp.sum(jnp.where(is_, vec_, 0.0))

    # -- stage 1: box decode, once per image (class-constant blocks) ------
    @pl.when(c == 0)
    def _decode():
        r4 = jax.lax.broadcasted_iota(jnp.int32, (1, 4, ppad), 1)

        def row(ref, i):
            # masked cross-sublane reduce: sublane i of the (1,4,ppad)
            # block as a (1,1,ppad) lane vector (static sublane slices
            # at non-8-aligned offsets are not a Mosaic-legal load)
            return jnp.sum(jnp.where(r4 == i, ref[...], 0.0), axis=1,
                           keepdims=True)

        dx, dy, dw, dh = (row(loc_ref, i) for i in range(4))
        px1, py1, px2, py2 = (row(priors_ref, i) for i in range(4))
        v0, v1, v2, v3 = (row(var_ref, i) for i in range(4))
        # exact decode_bbox math (ops/bbox.py): center-size deltas
        pw = px2 - px1
        ph = py2 - py1
        pcx = px1 + pw * 0.5
        pcy = py1 + ph * 0.5
        cx = v0 * dx * pw + pcx
        cy = v1 * dy * ph + pcy
        w = jnp.exp(v2 * dw) * pw
        h = jnp.exp(v3 * dh) * ph
        x1, y1 = cx - w * 0.5, cy - h * 0.5
        x2, y2 = cx + w * 0.5, cy + h * 0.5
        if clip:
            x1, y1 = jnp.clip(x1, 0.0, 1.0), jnp.clip(y1, 0.0, 1.0)
            x2, y2 = jnp.clip(x2, 0.0, 1.0), jnp.clip(y2, 0.0, 1.0)
        bx1[:], by1[:], bx2[:], by2[:] = x1, y1, x2, y2

    # -- stage 2: per-class filter + selection + suppression, fused -------
    if stage in ("select", "full"):
        s = scores_ref[...][0]                          # (1, 1, ppad)
        valid = ((lane < n_priors)
                 & (s > conf_thresh)).astype(f32)
        active[:] = valid
        remaining[:] = valid
        curkeep[:] = jnp.zeros_like(curkeep)
        # pop order IS descending-score order (ties: lowest prior index,
        # lax.top_k's stable order), and the pop INDEX is the sorted
        # rank — so stopping at nms_topk pops reproduces the reference's
        # topk-400 pre-filter without materializing a sorted list.  The
        # bound is dynamic (a while_loop), so the common sparse case
        # (conf_thresh kills most priors) costs #valid pops, not K.
        bound = jnp.minimum(jnp.sum(valid).astype(jnp.int32), nms_topk)

        def body(i, _):
            vals = jnp.where(remaining[:] > 0, s, -jnp.inf)
            m = jnp.max(vals)
            p = jnp.min(jnp.where(vals == m, lane, ppad))
            is_p = lane == p
            remaining[:] = jnp.where(is_p, 0.0, remaining[:])

            @pl.when(pick(active[:], is_p) > 0.0)
            def _keep():
                curkeep[:] = jnp.where(is_p, s, curkeep[:])
                x1 = pick(bx1[:], is_p)
                y1 = pick(by1[:], is_p)
                x2 = pick(bx2[:], is_p)
                y2 = pick(by2[:], is_p)
                ix1 = jnp.maximum(bx1[:], x1)
                iy1 = jnp.maximum(by1[:], y1)
                ix2 = jnp.minimum(bx2[:], x2)
                iy2 = jnp.minimum(by2[:], y2)
                inter = (jnp.maximum(ix2 - ix1, 0.0)
                         * jnp.maximum(iy2 - iy1, 0.0))
                area = (bx2[:] - bx1[:]) * (by2[:] - by1[:])
                area_p = (x2 - x1) * (y2 - y1)
                union = jnp.maximum(area + area_p - inter, 1e-12)
                # deactivate everything overlapping the kept box
                # (including itself; its keep score is already written)
                active[:] = jnp.where(inter / union >= nms_thresh, 0.0,
                                      active[:])

            return 0

        jax.lax.fori_loop(0, bound, body, 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (n_fg, 1, ppad), 0)
        allkeep[:] = jnp.where(ci == c, curkeep[:], allkeep[:])

    # -- stage 3: global cross-class top-K, last class step ---------------
    if stage == "full":
        @pl.when(c == n_fg - 1)
        def _merge():
            rowi = jax.lax.broadcasted_iota(jnp.int32, (1, kout, 6), 1)
            coli = jax.lax.broadcasted_iota(jnp.int32, (1, kout, 6), 2)
            out_ref[:] = jnp.where(coli == 0, -1.0, 0.0)  # empty rows
            ci = jax.lax.broadcasted_iota(jnp.int32, (n_fg, 1, ppad), 0)
            li = jax.lax.broadcasted_iota(jnp.int32, (n_fg, 1, ppad), 2)
            flat = ci * ppad + li
            n_kept = jnp.sum((allkeep[:] > 0).astype(f32)).astype(jnp.int32)
            npop = jnp.minimum(n_kept, kout)

            def body(j, _):
                ak = allkeep[:]
                m = jnp.max(ak)
                # tie-break: lowest flattened (class, prior) index ==
                # lax.top_k's stable order over the reference's
                # class-major candidate layout
                idx = jnp.min(jnp.where(ak == m, flat, n_fg * ppad))
                cstar = idx // ppad
                pstar = idx - cstar * ppad
                is_p = lane == pstar
                # foreground row → original class id (the background
                # column was dropped before the kernel)
                cls = (cstar
                       + (cstar >= bg_id).astype(jnp.int32)).astype(f32)
                x1 = pick(bx1[:], is_p)
                y1 = pick(by1[:], is_p)
                x2 = pick(bx2[:], is_p)
                y2 = pick(by2[:], is_p)
                vals = jnp.where(coli == 0, cls,
                       jnp.where(coli == 1, m,
                       jnp.where(coli == 2, x1,
                       jnp.where(coli == 3, y1,
                       jnp.where(coli == 4, x2, y2)))))
                out_ref[:] = jnp.where(rowi == j, vals, out_ref[:])
                allkeep[:] = jnp.where(flat == idx, 0.0, ak)
                return 0

            jax.lax.fori_loop(0, npop, body, 0)
    else:
        # prefix stages for the profile ladder: the output must DEPEND
        # on the computed scratch (an all-constant write would let the
        # interpret-mode emulation dead-code the measured work)
        @pl.when(c == n_fg - 1)
        def _touch():
            probe = (jnp.sum(bx1[:]) + jnp.sum(by2[:])
                     + (jnp.sum(allkeep[:]) if stage == "select" else 0.0))
            out_ref[:] = jnp.zeros((1, kout, 6), f32) + probe


@functools.partial(jax.jit, static_argnames=("param", "interpret", "stage"))
def fused_detection_output(loc: jax.Array, conf: jax.Array,
                           priors: jax.Array, variances: jax.Array, *,
                           param, interpret: bool = False,
                           stage: str = "full") -> jax.Array:
    """Batched fused DetectionOutput: loc (B,P,4), conf (B,P,C)
    probabilities → (B, keep_topk, 6) rows ``(class_id, score, x1, y1,
    x2, y2)``, empty slots class_id=-1/score=0 — the
    ``detection_output`` output contract, produced by one pallas_call.

    ``stage``: "full" (the product), or the "decode"/"select" prefix
    programs for the profile ladder (their outputs are probes, not
    detections).  Callers normally go through ``detection_output``
    with ``DetectionOutputParam(backend="fused")``, which checks the
    VMEM budget first."""
    if stage not in STAGES:
        raise ValueError(f"stage must be one of {STAGES}, got {stage!r}")
    B, P, C = conf.shape
    fg_ids = np.asarray([i for i in range(C) if i != param.background_id],
                        np.int32)
    n_fg = len(fg_ids)
    if not n_fg:
        raise ValueError("fused DetectionOutput needs >= 1 foreground "
                         "class")
    ppad = _round_up(P, 128)
    pad = ppad - P

    # background dropped HERE (layout, not masking): only foreground
    # rows enter the (batch, class) grid
    scores = jnp.swapaxes(conf.astype(jnp.float32)[..., fg_ids], 1, 2)
    scores = jnp.pad(scores, ((0, 0), (0, 0), (0, pad)))[:, :, None, :]
    loc_t = jnp.pad(jnp.swapaxes(loc.astype(jnp.float32), 1, 2),
                    ((0, 0), (0, 0), (0, pad)))
    pr = jnp.pad(jnp.swapaxes(jnp.asarray(priors, jnp.float32), 0, 1),
                 ((0, 0), (0, pad)))[None]
    vr = jnp.pad(jnp.swapaxes(jnp.asarray(variances, jnp.float32), 0, 1),
                 ((0, 0), (0, pad)))[None]

    kernel = functools.partial(
        _fused_kernel, n_fg=n_fg, n_priors=P, ppad=ppad,
        kout=int(param.keep_topk), conf_thresh=float(param.conf_thresh),
        nms_thresh=float(param.nms_thresh), nms_topk=int(param.nms_topk),
        bg_id=int(param.background_id), clip=bool(param.clip_boxes),
        stage=stage)
    return pl.pallas_call(
        kernel,
        grid=(B, n_fg),
        in_specs=[
            pl.BlockSpec((1, 1, 1, ppad), lambda b, c: (b, c, 0, 0),
                         memory_space=pltpu.VMEM),
            # loc / priors / variances: class-constant index maps keep
            # the blocks VMEM-resident across the inner class grid
            pl.BlockSpec((1, 4, ppad), lambda b, c: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4, ppad), lambda b, c: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4, ppad), lambda b, c: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        # one output block per image, revisited across the class grid
        out_specs=pl.BlockSpec((1, int(param.keep_topk), 6),
                               lambda b, c: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, int(param.keep_topk), 6),
                                       jnp.float32),
        scratch_shapes=(
            [pltpu.VMEM((1, 1, ppad), jnp.float32) for _ in range(7)]
            + [pltpu.VMEM((n_fg, 1, ppad), jnp.float32)]),
        compiler_params=compiler_params(
            fused_vmem_bytes(P, C, int(param.keep_topk))),
        interpret=interpret,
    )(scores, loc_t, pr, vr)
