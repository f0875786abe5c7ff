"""Fused DetectionOutput: the whole SSD post-processing chain as ONE
batched Pallas program.

The unfused serve path (``ops/detection_output.py`` backend="pallas")
is four XLA/Pallas stages with materialized intermediates between them:
decode (B,P,4) → per-class ``lax.top_k`` + gathers (B,C,K scores, idx,
boxes) → the ``pallas_nms.nms_sweep`` kernel (B·C,K) → a global
``lax.top_k`` over (B, C·K).  Every arrow is an HBM round-trip and a
stage boundary.  This module is the same math as ONE kernel over a
``(batch, class)`` grid:

- **decode** runs in-kernel at the first class step of each image (loc
  and prior blocks have constant-over-class index maps, so Pallas
  keeps them VMEM-resident; the corner boxes land in VMEM scratch that
  persists across the class grid — the ``pallas_rnn`` residency trick);
- **confidence filter + candidate selection + suppression sweep** fuse
  into a single greedy loop per (image, class) over ONE pool of the
  candidates still eligible.  The pool starts as the scores above
  ``conf_thresh`` of rank under ``nms_topk`` (the reference's nmsFast
  topk-400 pre-filter, reproduced exactly: a row with more candidates
  finds its cut once, by counting passes over the scores' bits and
  then over the priors tied at the cut).  Each pop takes the pool's max
  (the pop ORDER is the sorted order, so no top_k materialization is
  needed), appends it to the class's keep list and clears it and every
  candidate it overlaps from the pool with one VPU IoU row: every pop
  is a keep, and the loop runs while the pool holds a candidate.  The
  background class never enters: only foreground rows are in the grid,
  so the discard happens at selection, not by post-hoc masking;
- **global cross-class top-K** runs at the last class step over the
  per-class keep lists alone: pop the global max ``min(kept,
  keep_topk)`` times, tie-broken by (class, slot) — within a class the
  slot order is the pop order, score then prior, so this is exactly
  ``lax.top_k``'s stable order over the reference's class-major
  candidate layout — and write ``(class_id, score, x1, y1, x2, y2)``
  rows directly into the output block.

Candidates never leave VMEM between the stages; the only HBM traffic
is streaming the inputs once and writing the (B, keep_topk, 6) result.

**Layout.**  Mosaic tiles a buffer's last two dims in ``(8, 128)``
registers, so every per-prior vector (scores, the four box rows, the
sweep's pool) is a dense ``(P_pad / 128, 128)`` tile with
``P_pad`` a multiple of 1,024: SSD512's 24,564 priors are 192 rows, 24
full registers (as ``(1, P)`` rows they were 192 registers with one
sublane of eight in use).  Prior ``p`` sits at ``[p // 128, p % 128]``
and ``p`` is the index every tie-break is stated on.  A pass over a
vector (max, index of the max, the box of the popped prior as masked
sums, the IoU row) costs 24 register operations; appending a keep to
its class's list loads, selects in and stores the one aligned register
of the list that holds its slot.

**The keep lists.**  A class keeps at most ``min(nms_topk, P)``
candidates, so its list has that many slots, rounded up to 128 lanes:
``(slots / 128, C_fg rounded up to 8, 128)`` scratches of the kept
scores and of the kept boxes' four corners, class ``k``'s slot ``n`` at
``[n // 128, k, n % 128]``.  The sweep writes its ``n``-th keep into
slot ``n`` (the count is the loop's carry); it pops in descending
order, so every list comes out sorted.  At SSD512 the scores are 12
registers where one ``(P_pad / 128, 128)`` tile a class was 480.  A
pop, of the sweep or of the merge, stays in vector registers: the max,
the lowest tie key among the maxima, then the popped entry as masked
sums, all as whole-vector passes — never a scalar read back from a
vector to address one register (a pop is bound by its chain of
reductions, not by the registers it scans).  The sweep's one scalar
read-back a pop is its loop's continue test.

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
"select" → "full") so a caller can ladder the fused cost into parts
that sum to the whole BY CONSTRUCTION (each rung is a prefix; rung
deltas are stage costs; the chip's ladder: PERF.md, PR 30).  The
"select" rung's probe also carries the sweep's trip count, its pops (=
keeps) a picture, in column 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.vmem import (compiler_params, padded_bytes,
                                        round_up)

#: prefix programs for the profile ladder (each includes the previous)
STAGES = ("decode", "select", "full")


def _padded_priors(n_priors: int) -> int:
    """Priors padded to whole ``(8, 128)`` float32 registers: a per-prior
    vector is then a dense ``(ppad / 128, 128)`` tile."""
    return round_up(n_priors, 8 * 128)


def _list_slots(n_priors: int, nms_topk: int) -> int:
    """Slots of a class's keep list: it keeps at most ``min(nms_topk,
    P)`` candidates; whole 128-lane rows."""
    return round_up(max(min(nms_topk, n_priors), 1), 128)


def fused_vmem_bytes(n_priors: int, n_classes: int, keep_topk: int,
                     nms_topk: int = 400) -> int:
    """VMEM the fused program's buffers occupy, priced the way Mosaic
    lays them out (``ops.vmem.padded_bytes``).  Every per-prior vector is
    a dense ``(R, 128)`` tile with ``R`` a multiple of 8, so the figure is
    the logical bytes of the padded priors; the keep lists pad their
    class axis to 8 sublanes and their slots to 128 lanes, and the
    ``(keep_topk, 6)`` output block pads 6 lanes to 128.  Counted: the
    decoded boxes (4 vectors) and the sweep's pool, the five keep
    lists (score and box corners, ``min(nms_topk, P)`` slots a
    foreground class; ``nms_topk`` defaults to ``DetectionOutputParam``'s
    400), the double-buffered score and loc blocks, the single-buffered
    prior/variance blocks (whole-array windows are not double-buffered)
    and the double-buffered output block.  ``detection_output`` selects on
    it and ``fused_detection_output`` hands it to Mosaic as the VMEM
    limit."""
    rows = _padded_priors(n_priors) // 128
    n_fg = max(n_classes - 1, 1)
    vec = padded_bytes((rows, 128), np.float32)
    quad = padded_bytes((4, rows, 128), np.float32)
    lists = padded_bytes(
        (5, _list_slots(n_priors, nms_topk) // 128, n_fg, 128), np.float32)
    scratch = vec + quad + lists
    blocks = 2 * vec + 2 * quad + 2 * quad
    return scratch + blocks + 2 * padded_bytes((keep_topk, 6), np.float32)


def _fused_kernel(scores_ref, loc_ref, priors_ref, var_ref, out_ref,
                  boxes, pool, kscore, kbox,
                  *, n_fg: int, n_priors: int, rows: int, slots: int,
                  kout: int, conf_thresh: float, nms_thresh: float,
                  nms_topk: int, bg_id: int, clip: bool, stage: str):
    """One (image, class) grid step.  A per-prior vector is an
    ``(rows, 128)`` tile and prior ``p`` sits at ``[p // 128, p % 128]``;
    class ``k``'s keep list slot ``n`` sits at ``[n // 128, k, n % 128]``
    of ``kscore`` and of each corner of ``kbox``.  A pop, of the sweep
    over a tile or of the merge over the lists, is whole-vector work (the
    max, the lowest tie key among the maxima, the popped entry as masked
    sums) kept in (1, 1) vectors; a scalar addresses a register only by
    a loop's count: the sweep's keep-list slot, the merge's output row
    (TPU VMEM has no scalar stores).  Scratch persists across the class grid, which is what lets
    decode run once per image and the global merge see every class's
    keeps without an HBM round-trip."""
    c = pl.program_id(1)
    f32 = jnp.float32
    ppad = rows * 128
    # a prior's flat index: what every tie-break below is stated on
    flat = (jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1))
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def slot_of(k, n):
        """The register of the keep lists holding class ``k``'s slot
        ``n`` (a row of 128 slots, an aligned window of 8 classes) and
        the slot's place in it."""
        k0 = pl.multiple_of((k // 8) * 8, 8)
        row = n // 128
        return row, pl.ds(k0, 8), (sub8 == k - k0) & (lane8 == n - row * 128)

    def whole(x, op):
        """``op`` over a whole tile or all the lists, kept a (1, 1)
        vector: a pop never reads a scalar back from the vector unit."""
        while x.ndim > 2:
            x = op(x, axis=0)
        return op(op(x, axis=0, keepdims=True), axis=1, keepdims=True)

    def count(mask):
        return jnp.sum(mask.astype(f32))

    # -- stage 1: box decode, once per image (class-constant blocks) ------
    @pl.when(c == 0)
    def _decode():
        dx, dy, dw, dh = (loc_ref[0, i] for i in range(4))
        px1, py1, px2, py2 = (priors_ref[i] for i in range(4))
        v0, v1, v2, v3 = (var_ref[i] for i in range(4))
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
        boxes[0], boxes[1], boxes[2], boxes[3] = x1, y1, x2, y2

    # -- stage 2: per-class filter + selection + suppression, fused -------
    if stage in ("select", "full"):
        @pl.when(c == 0)
        def _clear_lists():                 # a score of 0 is an empty slot
            kscore[:] = jnp.zeros(kscore.shape, f32)

        # the pool: the scores of the candidates still eligible, -inf
        # elsewhere.  Rank is score descending, then prior ascending
        # (lax.top_k's stable order), and only the first nms_topk by
        # rank enter: the reference's topk-400 pre-filter
        s = scores_ref[0, 0]
        valid = (flat < n_priors) & (s > conf_thresh)
        pool[:] = jnp.where(valid, s, -jnp.inf)

        @pl.when(count(valid) > nms_topk)
        def _cut():
            # the nms_topk-th largest score exactly: its bits, in an
            # order signed compares keep (-0.0 as +0.0, as the pops
            # compare), built from the top by 32 counting passes
            lowest = jnp.int32(-2 ** 31)
            b = jax.lax.bitcast_convert_type(jnp.where(s == 0.0, 0.0, s),
                                             jnp.int32)
            key = jnp.where(valid, b ^ ((b >> 31) & 0x7FFFFFFF), lowest)

            def bit(i, tau):
                cand = tau | (jnp.int32(1) << (31 - i))
                return jnp.where(count(key >= (cand ^ lowest)) >= nms_topk,
                                 cand, tau)

            t = jax.lax.fori_loop(0, 32, bit, jnp.int32(0)) ^ lowest
            # of the candidates tied at it, the lowest priors that fit:
            # the last one's index, built from the top the same way
            tie = key == t
            room = nms_topk - count(key > t)
            nbits = (ppad - 1).bit_length()

            def place(i, tau):
                cand = tau | (jnp.int32(1) << (nbits - 1 - i))
                return jnp.where(count(tie & (flat < cand)) < room, cand, tau)

            last = jax.lax.fori_loop(0, nbits, place, jnp.int32(0))
            pool[:] = jnp.where((key > t) | (tie & (flat <= last)),
                                pool[:], -jnp.inf)

        # greedy suppression: the pool's max (ties: lowest prior) is the
        # next keep in rank order, since every candidate above it has
        # been kept or suppressed.  Each pop appends it to the class's
        # list (the pops come in descending order, so the list is
        # sorted) and takes it and every candidate whose IoU with it
        # reaches nms_thresh out of the pool — the popped one by its own
        # mask, since a box of zero area has IoU 0 with itself.  So every
        # pop is a keep and the loop runs while the pool holds one.
        def pop(carry):
            n, m = carry
            pv = pool[:]
            sel = flat == whole(jnp.where(pv == m, flat, ppad), jnp.min)
            x1, y1, x2, y2 = [whole(jnp.where(sel, boxes[i], 0.0), jnp.sum)
                              for i in range(4)]
            row, kwin, at = slot_of(c, n)
            kscore[row, kwin, :] = jnp.where(at, m, kscore[row, kwin, :])
            for corner, v in enumerate((x1, y1, x2, y2)):
                kbox[corner, row, kwin, :] = jnp.where(
                    at, v, kbox[corner, row, kwin, :])
            bx1, by1, bx2, by2 = (boxes[i] for i in range(4))
            ix1 = jnp.maximum(bx1, x1)
            iy1 = jnp.maximum(by1, y1)
            ix2 = jnp.minimum(bx2, x2)
            iy2 = jnp.minimum(by2, y2)
            inter = jnp.maximum(ix2 - ix1, 0.0) * jnp.maximum(iy2 - iy1, 0.0)
            area = (bx2 - bx1) * (by2 - by1)
            area_p = (x2 - x1) * (y2 - y1)
            union = jnp.maximum(area + area_p - inter, 1e-12)
            pv = jnp.where(sel | (inter / union >= nms_thresh), -jnp.inf, pv)
            pool[:] = pv
            return n + 1, whole(pv, jnp.max)

        n_kept, _ = jax.lax.while_loop(
            lambda carry: jnp.max(carry[1]) > -jnp.inf, pop,
            (jnp.int32(0), whole(pool[:], jnp.max)))

    # -- stage 3: global cross-class top-K, last class step ---------------
    if stage == "full":
        @pl.when(c == n_fg - 1)
        def _merge():
            coli = jax.lax.broadcasted_iota(jnp.int32, (8, 6), 1)
            rowi = jax.lax.broadcasted_iota(jnp.int32, (8, 6), 0)
            out_ref[0] = jnp.where(             # empty rows
                jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
                == 0, -1.0, 0.0)
            # a slot's tie key: class-major, then slot — within a class
            # the pop order, score then prior.  The lowest key among the
            # maxima is lax.top_k's stable order over the reference's
            # class-major candidate layout
            key = (jax.lax.broadcasted_iota(jnp.int32, kscore.shape, 1)
                   * slots
                   + jax.lax.broadcasted_iota(jnp.int32, kscore.shape, 0)
                   * 128
                   + jax.lax.broadcasted_iota(jnp.int32, kscore.shape, 2))
            no_key = kscore.shape[1] * slots
            npop = jnp.minimum(count(kscore[:] > 0).astype(jnp.int32), kout)

            # foreground row → original class id (the background column
            # was dropped before the kernel)
            cls_id = jax.lax.broadcasted_iota(jnp.int32, kscore.shape, 1)
            cls_id = (cls_id + (cls_id >= bg_id).astype(jnp.int32)
                      ).astype(f32)

            def body(j, _):
                ks = kscore[:]
                m = whole(ks, jnp.max)
                sel = key == whole(jnp.where(ks == m, key, no_key), jnp.min)
                kscore[:] = jnp.where(sel, 0.0, ks)
                cls = whole(jnp.where(sel, cls_id, 0.0), jnp.sum)
                x1, y1, x2, y2 = [
                    whole(jnp.where(sel, kbox[i], 0.0), jnp.sum)
                    for i in range(4)]
                vals = jnp.where(coli == 0, cls,
                       jnp.where(coli == 1, m,
                       jnp.where(coli == 2, x1,
                       jnp.where(coli == 3, y1,
                       jnp.where(coli == 4, x2, y2)))))
                j0 = pl.multiple_of((j // 8) * 8, 8)
                out_ref[0, pl.ds(j0, 8), :] = jnp.where(
                    rowi == j - j0, vals, out_ref[0, pl.ds(j0, 8), :])
                return 0

            jax.lax.fori_loop(0, npop, body, 0)
    else:
        # prefix stages for the profile ladder: the output must DEPEND
        # on the computed scratch (an all-constant write would let the
        # interpret-mode emulation dead-code the measured work).  The
        # "select" probe's column 1 is the sweep's engagement counter:
        # its pops (= keeps) a picture, summed over the class steps
        col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 2)
        if stage == "select":
            @pl.when(c == 0)
            def _zero():
                out_ref[:] = jnp.zeros(out_ref.shape, f32)

            out_ref[:] = out_ref[:] + jnp.where(col == 1,
                                                n_kept.astype(f32), 0.0)

        @pl.when(c == n_fg - 1)
        def _touch():
            probe = jnp.sum(boxes[0]) + jnp.sum(boxes[3])
            pops = 0.0
            if stage == "select":
                ks = kscore[:]
                probe += jnp.sum(ks) + jnp.sum(
                    jnp.where(ks > 0, kbox[0], 0.0))
                pops = out_ref[:]
            out_ref[:] = jnp.where(col == 1, pops, probe)


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
    ppad = _padded_priors(P)
    rows = ppad // 128
    kout = int(param.keep_topk)
    nms_topk = int(param.nms_topk)
    slots = _list_slots(P, nms_topk)
    # the merge writes an answer's row through the aligned 8-row window
    # that holds it: the output block is whole registers, cut after
    kpad = round_up(kout, 8)

    def tiles(x):
        """(..., P) → (..., rows, 128): prior p at [p // 128, p % 128]."""
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, ppad - P)])
        return x.reshape(x.shape[:-1] + (rows, 128))

    # background dropped HERE (layout, not masking): only foreground
    # rows enter the (batch, class) grid
    scores = tiles(jnp.swapaxes(conf.astype(jnp.float32)[..., fg_ids], 1, 2))
    loc_t = tiles(jnp.swapaxes(loc.astype(jnp.float32), 1, 2))
    pr = tiles(jnp.swapaxes(jnp.asarray(priors, jnp.float32), 0, 1))
    vr = tiles(jnp.swapaxes(jnp.asarray(variances, jnp.float32), 0, 1))

    kernel = functools.partial(
        _fused_kernel, n_fg=n_fg, n_priors=P, rows=rows, slots=slots,
        kout=kout, conf_thresh=float(param.conf_thresh),
        nms_thresh=float(param.nms_thresh), nms_topk=nms_topk,
        bg_id=int(param.background_id), clip=bool(param.clip_boxes),
        stage=stage)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_fg),
        in_specs=[
            pl.BlockSpec((1, 1, rows, 128), lambda b, c: (b, c, 0, 0),
                         memory_space=pltpu.VMEM),
            # loc / priors / variances: class-constant index maps keep
            # the blocks VMEM-resident across the inner class grid
            pl.BlockSpec((1, 4, rows, 128), lambda b, c: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, rows, 128), lambda b, c: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, rows, 128), lambda b, c: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        # one output block per image, revisited across the class grid
        out_specs=pl.BlockSpec((1, kpad, 6), lambda b, c: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, kpad, 6), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((4, rows, 128), jnp.float32),        # boxes
            pltpu.VMEM((rows, 128), jnp.float32),           # pool
            # the keep lists: scores, box corners
            pltpu.VMEM((slots // 128, round_up(n_fg, 8), 128), jnp.float32),
            pltpu.VMEM((4, slots // 128, round_up(n_fg, 8), 128),
                       jnp.float32),
        ],
        compiler_params=compiler_params(
            fused_vmem_bytes(P, C, kout, nms_topk)),
        interpret=interpret,
    )(scores, loc_t, pr, vr)
    return out[:, :kout]
