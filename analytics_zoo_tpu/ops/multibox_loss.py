"""MultiBoxLoss: SSD training criterion, vectorized for the MXU.

The reference ``common/nn/MultiBoxLoss.scala:41`` (624 LoC) runs per-image
sequential loops: bipartite + per-prediction matching (``matchBbox:167``),
hard-negative mining with sorting (``mineHardExamples:334``), then
SmoothL1(loc) + CrossEntropy(conf) normalized by match count
(``updateOutput:477``).  Here the whole criterion is one jittable array
program (SURVEY.md §7.3 hard part #1):

- matching = IoU matrix + per-prior argmax, with each gt's best prior
  force-matched (the bipartite phase) via scatter;
- hard-negative mining = rank negatives by background conf loss (one
  descending argsort — or a static ``lax.top_k`` window in
  ``mining="topk"`` mode — plus a scatter of the keep mask) and select
  the top ``neg_pos_ratio·num_pos``, count-exact;
- losses are masked sums — no gather/boolean filtering, shapes stay static.

Gradient-explosion guard: the reference skips backward when loss > 50
(``updateGradInput:546``); the equivalent lives in the train step's
``skip_loss_above`` (parallel/train.py), keeping this criterion pure.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import numpy as np
import jax.numpy as jnp

from analytics_zoo_tpu.core.criterion import Criterion, smooth_l1
from analytics_zoo_tpu.ops.bbox import encode_bbox, iou_matrix


@dataclasses.dataclass(frozen=True)
class MultiBoxLossParam:
    """Reference ``MultiBoxLossParam`` defaults (``MultiBoxLoss.scala:32``):
    locWeight 1.0, nClasses 21, overlap 0.5, negPosRatio 3."""

    loc_weight: float = 1.0
    n_classes: int = 21
    overlap_threshold: float = 0.5
    background_id: int = 0
    neg_pos_ratio: float = 3.0
    neg_overlap: float = 0.5
    # Hard-negative selection engine (docs/MFU_CEILING.md: mining was
    # ~20% of the SSD300 train step at 1.3% of its FLOPs on a v5e in
    # round 4).  "sort": one value
    # sort of the (P,) negative losses — exact reference semantics up to
    # float ties (the former double-argsort rank trick cost two sorts
    # for the same selection).  "topk": lax.top_k over a static window
    # of ``mining_topk`` candidates — cheapest, and exact whenever
    # ``num_neg = min(3·num_pos, #candidates) <= mining_topk`` (i.e.
    # fewer than ~mining_topk/3 positive priors per image; beyond that
    # the negative count is capped at mining_topk, a documented
    # deviation).
    mining: str = "sort"
    mining_topk: int = 1024


def match_priors(priors: jax.Array, gt_boxes: jax.Array, gt_mask: jax.Array,
                 overlap_threshold: float = 0.5):
    """Match P priors to G (masked) ground truths.

    Returns ``(matched_gt_idx (P,) int32, positive (P,) bool,
    best_gt_iou (P,))``.
    Per-prior phase: each prior takes its best-IoU gt if IoU ≥ threshold.
    Bipartite phase (reference ``matchBbox:167``): every valid gt claims its
    best prior unconditionally, overriding the per-prior result.
    """
    iou = iou_matrix(priors, gt_boxes)                       # (P, G)
    iou = jnp.where(gt_mask[None, :] > 0, iou, -1.0)
    best_gt = jnp.argmax(iou, axis=1)                        # (P,)
    best_gt_iou = jnp.max(iou, axis=1)
    positive = best_gt_iou >= overlap_threshold

    # bipartite: gt g's best prior is forced to match g
    best_prior = jnp.argmax(iou, axis=0)                     # (G,)
    g_ids = jnp.arange(gt_boxes.shape[0])
    valid = gt_mask > 0
    # scatter: later gts win collisions, mirroring sequential overwrite
    matched = best_gt.at[jnp.where(valid, best_prior, priors.shape[0])].set(
        g_ids, mode="drop")
    forced = jnp.zeros((priors.shape[0],), bool).at[
        jnp.where(valid, best_prior, priors.shape[0])
    ].set(True, mode="drop")
    positive = positive | forced
    return matched, positive, best_gt_iou


def multibox_loss(loc_pred: jax.Array, conf_logits: jax.Array,
                  priors: jax.Array, variances: jax.Array,
                  gt_boxes: jax.Array, gt_labels: jax.Array,
                  gt_mask: jax.Array,
                  param: MultiBoxLossParam = MultiBoxLossParam()) -> jax.Array:
    """Batched SSD loss.

    loc_pred (B,P,4), conf_logits (B,P,C) **raw logits** (the reference
    feeds raw conf and does its own log-sum-exp, ``encodeConfPrediction``),
    priors/variances (P,4), gt_boxes (B,G,4) normalized corner form,
    gt_labels (B,G) int (background = ``param.background_id``),
    gt_mask (B,G) 1.0=valid.  Scalar loss = (loc + conf) / total matches.
    """

    def per_image(loc_p, conf_l, boxes, labels, mask):
        # four named sections of the compiled step, forward and backward
        # (obs/names.py::SCOPES)
        with jax.named_scope("ssd/loss_match"):
            matched, positive, best_iou = match_priors(
                priors, boxes, mask, param.overlap_threshold)
            pos_f = positive.astype(jnp.float32)
            num_pos = jnp.sum(pos_f)

        # --- localization: smooth-L1 on encoded deltas, positives only
        with jax.named_scope("ssd/loss_loc"):
            matched_boxes = boxes[matched]                    # (P,4)
            loc_target = encode_bbox(priors, variances, matched_boxes)
            loc_loss = jnp.sum(
                jnp.sum(smooth_l1(loc_p - loc_target), axis=-1) * pos_f)

        # --- confidence: CE with matched label for positives, bg for rest
        with jax.named_scope("ssd/loss_conf"):
            matched_label = jnp.where(
                positive, labels[matched].astype(jnp.int32),
                param.background_id)
            logp = jax.nn.log_softmax(conf_l, axis=-1)        # (P,C)
            ce = -jnp.take_along_axis(logp, matched_label[:, None],
                                      axis=1)[:, 0]

        # --- hard-negative mining (reference ``mineHardExamples:334``):
        # candidates = non-positive priors whose best gt overlap is below
        # negOverlap (near-matches are neither positive nor negative)
        with jax.named_scope("ssd/loss_mine"):
            neg_cand = (~positive) & (best_iou < param.neg_overlap)
            neg_loss = jnp.where(neg_cand, -logp[:, param.background_id],
                                 -jnp.inf)
            num_neg = jnp.minimum(param.neg_pos_ratio * num_pos,
                                  jnp.sum(neg_cand.astype(jnp.float32)))
            # count-exact top-num_neg selection with ONE sort + a scatter
            # (the former double-argsort rank trick paid a second full
            # sort for the same mask; a value-threshold variant would be
            # cheaper still but over-selects whole tie groups — e.g. the
            # uniform logits of a fresh model — so the count contract
            # would break)
            if param.mining == "topk":
                k = min(param.mining_topk, neg_loss.shape[0])
                _, cand_idx = jax.lax.top_k(neg_loss, k)      # desc (k,)
                num_neg = jnp.minimum(num_neg, float(k))
            elif param.mining == "sort":
                cand_idx = jnp.argsort(-neg_loss)             # desc (P,)
            else:
                raise ValueError(f"unknown mining mode {param.mining!r}")
            take = jnp.arange(cand_idx.shape[0]) < num_neg
            neg_selected = (jnp.zeros(neg_loss.shape[0], bool)
                            .at[cand_idx].set(take)) & neg_cand

        with jax.named_scope("ssd/loss_conf"):
            conf_loss = jnp.sum(
                ce * (pos_f + neg_selected.astype(jnp.float32)))
        return param.loc_weight * loc_loss, conf_loss, num_pos

    loc_l, conf_l, n_pos = jax.vmap(per_image)(
        loc_pred, conf_logits, gt_boxes, gt_labels, gt_mask)
    total_pos = jnp.maximum(jnp.sum(n_pos), 1.0)
    return (jnp.sum(loc_l) + jnp.sum(conf_l)) / total_pos


class MultiBoxLoss(Criterion):
    """Criterion wrapper over :func:`multibox_loss` for the train loop.

    Expects model output ``(loc (B,P,4), conf (B,P,C))`` and target dict
    ``{"bboxes": (B,G,4), "labels": (B,G), "mask": (B,G)}`` — the padded
    form of the reference's ragged 7-col gt matrix.
    """

    def __init__(self, priors, variances,
                 param: MultiBoxLossParam = MultiBoxLossParam()):
        # host numpy on purpose: a jnp array built here would be
        # committed to whatever device is default at construction —
        # before the caller's mesh exists — and every jitted step that
        # closes over it has to fetch it back to embed it as a constant;
        # numpy constants embed directly
        self.priors = np.asarray(priors)
        self.variances = np.asarray(variances)
        self.param = param

    def __call__(self, output, target, mask=None):
        loc, conf = output
        return multibox_loss(
            loc, conf, self.priors, self.variances,
            target["bboxes"], target["labels"], target["mask"], self.param)
