"""DetectionOutput: SSD serving-side post-processing, fully on device.

Reference ``common/nn/DetectionOutput.scala:34`` (decode loc deltas vs
priors → per-class confidence filter → per-class NMS topk 400 → global
keep-topK 200) runs as a *layer inside the model graph*, so serving is one
forward pass.  Same here: ``detection_output`` is jittable and is the last
stage of the SSD model's ``apply``; per-class NMS is a ``vmap`` over the
class axis and the global top-K is one ``lax.top_k`` — no host round-trip.

Output layout per image: ``(keep_topk, 6)`` rows ``(class_id, score,
x1, y1, x2, y2)``; empty slots have class_id = -1, score = 0 (static shape
for XLA; the reference's variable-row output becomes mask-by-convention).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.bbox import decode_bbox
from analytics_zoo_tpu.ops.nms import nms
from analytics_zoo_tpu.ops import vmem
from analytics_zoo_tpu.utils import engine


@dataclasses.dataclass(frozen=True)
class DetectionOutputParam:
    """Reference ``PostProcessParam`` (``ssd/model/SSDGraph.scala:36``).

    ``backend`` selects the implementation:

    - ``"xla"``: per-class IoU matrix + fori_loop NMS (``ops/nms.py``);
    - ``"pallas"``: candidate selection in XLA, the suppression sweep as
      the VMEM-resident ``ops/pallas_nms.py`` kernel — four stages with
      (B, C, K) intermediates between them;
    - ``"fused"``: the whole chain (decode → filter+selection →
      suppression → global top-K) as ONE batched Pallas program over a
      (batch, class) grid (``ops/pallas_detout.py``) — candidates never
      leave VMEM between stages.  A geometry over the kernel's VMEM
      budget is a ``ValueError``;
    - ``"auto"`` (default): see :func:`resolve_backend` — fused on a TPU
      when it fits VMEM, else pallas; XLA off-TPU (interpret-mode pallas
      is slow on CPU).

    All backends implement the same reference semantics (topk-400
    pre-filter, greedy IoU suppression, global keep-topk), so outputs
    agree up to float associativity (score-tie ORDER also agrees:
    every backend tie-breaks lowest-index-first).
    """

    n_classes: int = 21
    background_id: int = 0
    conf_thresh: float = 0.01
    nms_thresh: float = 0.45
    nms_topk: int = 400
    keep_topk: int = 200
    share_location: bool = True
    clip_boxes: bool = False
    backend: str = "auto"
    # ``approx_topk`` swaps the per-(image, class) exact ``lax.top_k``
    # over all P priors — the serve program's dominant non-conv cost —
    # for TPU's partition-reduce ``lax.approx_max_k`` at the given
    # recall target.  The ~(1-recall) misses are NOT confined to ranks
    # near ``nms_topk``: approx_max_k partitions the input and keeps
    # bin-local maxima, so any element colliding with a larger one in
    # its bin can drop — including a top-scoring detection.  The
    # guardrail is therefore empirical: measured mAP delta on a trained
    # model is reported next to the serve bench, and the default stays
    # exact (``approx_topk=False``).  Only the pallas backend consumes
    # it (the XLA fallback stays exact).
    approx_topk: bool = False
    approx_recall: float = 0.95


def detection_output_single(loc: jax.Array, conf: jax.Array,
                            priors: jax.Array, variances: jax.Array,
                            param: DetectionOutputParam) -> jax.Array:
    """One image: loc (P,4) deltas, conf (P,C) probabilities → (keep_topk, 6)."""
    decoded = decode_bbox(priors, variances, loc, clip=param.clip_boxes)  # (P,4)

    class_ids = jnp.arange(param.n_classes)
    fg = class_ids != param.background_id  # (C,)

    def per_class(scores):
        return nms(decoded, scores, iou_threshold=param.nms_thresh,
                   max_output=param.nms_topk, pre_topk=param.nms_topk,
                   score_threshold=param.conf_thresh)

    keep_idx, keep_mask = jax.vmap(per_class, in_axes=1)(conf)  # (C, nms_topk)
    keep_mask = keep_mask * fg[:, None].astype(jnp.float32)

    # flatten class×topk candidates, rank globally by score
    flat_idx = keep_idx.reshape(-1)                       # (C·topk,)
    flat_mask = keep_mask.reshape(-1)
    flat_cls = jnp.repeat(class_ids, param.nms_topk)
    safe_idx = jnp.maximum(flat_idx, 0)
    flat_scores = conf[safe_idx, flat_cls] * flat_mask
    top_scores, order = jax.lax.top_k(flat_scores, param.keep_topk)
    top_cls = flat_cls[order]
    top_boxes = decoded[safe_idx[order]]
    valid = top_scores > 0
    out = jnp.concatenate([
        jnp.where(valid, top_cls, -1)[:, None].astype(jnp.float32),
        top_scores[:, None],
        jnp.where(valid[:, None], top_boxes, 0.0),
    ], axis=1)
    return out


@partial(jax.jit, static_argnames=("param",))
def _detection_output_xla(loc: jax.Array, conf: jax.Array, priors: jax.Array,
                          variances: jax.Array,
                          param: DetectionOutputParam) -> jax.Array:
    return jax.vmap(
        lambda l, c: detection_output_single(l, c, priors, variances, param)
    )(loc, conf)


@partial(jax.jit, static_argnames=("param", "interpret"))
def _detection_output_pallas(loc: jax.Array, conf: jax.Array,
                             priors: jax.Array, variances: jax.Array,
                             param: DetectionOutputParam,
                             interpret: bool) -> jax.Array:
    """Batched pallas path: per-class candidate selection stays in XLA
    (top_k + gathers feed the MXU-side sort network well); the sequential
    suppression sweep — the part XLA can only express as an O(K·argmax)
    fori_loop — runs in one VMEM-resident kernel over a (B·C,) grid."""
    from analytics_zoo_tpu.ops.pallas_nms import _round_up, nms_sweep

    B, P, C = conf.shape
    decoded = jax.vmap(
        lambda l: decode_bbox(priors, variances, l, clip=param.clip_boxes)
    )(loc)                                                  # (B,P,4)

    # the background class is discarded from the output, yet it is the
    # one DENSE row (its softmax score beats conf_thresh on essentially
    # every prior, so its sweep always runs the full nms_topk
    # iterations) — drop it before top_k/sweep instead of masking after
    fg_ids = np.asarray([c for c in range(C) if c != param.background_id],
                        np.int32)                           # static
    Cf = len(fg_ids)
    scores = jnp.swapaxes(conf[..., fg_ids], 1, 2)          # (B,Cf,P)
    masked = jnp.where(scores > param.conf_thresh, scores, -jnp.inf)
    k = min(_round_up(param.nms_topk, 128), _round_up(P, 128))
    kk = min(k, P)
    if param.approx_topk:
        # aggregate_to_topk (default) finishes with an exact top_k over
        # the gathered candidates, so the output stays sorted descending
        # — the order contract nms_sweep relies on.
        top_scores, top_idx = jax.lax.approx_max_k(
            masked, kk, recall_target=param.approx_recall)
    else:
        top_scores, top_idx = jax.lax.top_k(masked, kk)     # (B,Cf,kk)
    if k - kk:
        top_scores = jnp.pad(top_scores, ((0, 0), (0, 0), (0, k - kk)),
                             constant_values=-jnp.inf)
        top_idx = jnp.pad(top_idx, ((0, 0), (0, 0), (0, k - kk)))
    boxes = jnp.take_along_axis(decoded[:, None], top_idx[..., None],
                                axis=2)                     # (B,Cf,k,4)
    # reference nmsFast's topk-400 pre-filter: lanes past nms_topk are
    # padding from rounding k up to the 128-lane multiple
    valid = (jnp.isfinite(top_scores)
             & (jnp.arange(k) < param.nms_topk)).astype(jnp.float32)

    def flat(a):
        return a.reshape(B * Cf, k)

    keep = nms_sweep(flat(boxes[..., 0]), flat(boxes[..., 1]),
                     flat(boxes[..., 2]), flat(boxes[..., 3]), flat(valid),
                     iou_threshold=param.nms_thresh,
                     interpret=interpret).reshape(B, Cf, k)

    sel = jnp.where(jnp.isfinite(top_scores), top_scores, 0.0) * keep
    flat_scores = sel.reshape(B, Cf * k)
    out_scores, order = jax.lax.top_k(flat_scores, param.keep_topk)  # (B,K)
    out_cls = jnp.asarray(fg_ids)[order // k]
    out_boxes = jnp.take_along_axis(boxes.reshape(B, Cf * k, 4),
                                    order[..., None], axis=1)
    ok = out_scores > 0
    return jnp.concatenate([
        jnp.where(ok, out_cls, -1)[..., None].astype(jnp.float32),
        out_scores[..., None],
        jnp.where(ok[..., None], out_boxes, 0.0),
    ], axis=-1)


BACKENDS = ("xla", "pallas", "fused")


class ResolvedBackend(NamedTuple):
    """What ``detection_output`` runs for a param + geometry in this
    process — the answer callers assert on (``chip_smoke.py``)."""

    name: str          # one of BACKENDS
    interpret: bool    # a Pallas backend running through the interpreter
    #                    (no TPU) instead of a compiled Mosaic kernel


def resolve_backend(param: DetectionOutputParam, n_priors: int,
                    n_classes: int) -> ResolvedBackend:
    """Resolve ``param.backend`` for a ``(n_priors, n_classes)`` geometry.

    ``"auto"`` selects from what the code can observe: off-TPU the XLA
    path; on a TPU the fused kernel when its VMEM estimate
    (``ops.pallas_detout.fused_vmem_bytes``) fits (``ops.vmem.fits``),
    else the unfused pallas path (also when ``approx_topk`` is asked for
    — the approx selection only exists there).  A backend named EXPLICITLY is
    never swapped for another: ``"fused"`` over the budget is a
    ``ValueError``, and a kernel that fails to compile raises from the
    compiler — there is no silent fall-back to a slower path."""
    from analytics_zoo_tpu.ops import pallas_detout

    tpu = engine.on_tpu()
    name = param.backend
    need = pallas_detout.fused_vmem_bytes(n_priors, n_classes,
                                          param.keep_topk, param.nms_topk)
    fits = vmem.fits(need)
    if name == "auto":
        if not tpu:
            name = "xla"
        else:
            name = ("fused" if fits and not param.approx_topk
                    else "pallas")
    elif name not in BACKENDS:
        raise ValueError(f"backend={name!r} not in {('auto',) + BACKENDS}")
    elif name == "fused" and not fits:
        raise ValueError(
            f"fused DetectionOutput would ask for "
            f"{vmem.limit_bytes(need) / 2**20:.1f} MiB of VMEM "
            f"(P={n_priors}, C={n_classes}, keep_topk={param.keep_topk}), "
            f"over the {vmem.VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget — "
            f"use backend='pallas' (or 'auto', which selects it)")
    return ResolvedBackend(name, interpret=name != "xla" and not tpu)


def detection_output(loc: jax.Array, conf: jax.Array, priors: jax.Array,
                     variances: jax.Array,
                     param: DetectionOutputParam = DetectionOutputParam()
                     ) -> jax.Array:
    """Batched: loc (B,P,4), conf (B,P,C) → (B, keep_topk, 6).

    Runs the backend :func:`resolve_backend` names; the pallas/fused
    paths compile Mosaic kernels on a TPU and interpret elsewhere (CI)."""
    backend, interpret = resolve_backend(param, priors.shape[0],
                                         conf.shape[-1])
    if backend == "fused":
        from analytics_zoo_tpu.ops import pallas_detout

        return pallas_detout.fused_detection_output(
            loc, conf, priors, variances, param=param, interpret=interpret)
    if backend == "pallas":
        return _detection_output_pallas(loc, conf, priors, variances,
                                        param=param, interpret=interpret)
    return _detection_output_xla(loc, conf, priors, variances, param=param)


def scale_detections(dets: jax.Array, heights, widths) -> jax.Array:
    """Project normalized detections to original pixel sizes (reference
    ``BboxUtil.scaleBatchOutput:384`` using imInfo): dets (B,K,6)."""
    h = jnp.asarray(heights).reshape(-1, 1, 1)
    w = jnp.asarray(widths).reshape(-1, 1, 1)
    return jnp.concatenate([
        dets[..., :2],
        dets[..., 2:3] * w, dets[..., 3:4] * h,
        dets[..., 4:5] * w, dets[..., 5:6] * h,
    ], axis=-1)
