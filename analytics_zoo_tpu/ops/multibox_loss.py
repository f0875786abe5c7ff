"""MultiBoxLoss: SSD training criterion, vectorized for the MXU.

The reference ``common/nn/MultiBoxLoss.scala:41`` (624 LoC) runs per-image
sequential loops: bipartite + per-prediction matching (``matchBbox:167``),
hard-negative mining with sorting (``mineHardExamples:334``), then
SmoothL1(loc) + CrossEntropy(conf) normalized by match count
(``updateOutput:477``).  Here the whole criterion is one jittable array
program (SURVEY.md §7.3 hard part #1):

- matching = IoU matrix + per-prior argmax, with each gt's best prior
  force-matched (the bipartite phase) via a scatter indexed by gt (G
  indices an image);
- every choice made a PRIOR at a time is a compare where the data lies,
  never an index: the matched gt's label and box are sums over the G
  under ``matched == arange(G)``, the matched class's log-probability a
  sum over the C under ``label == arange(C)`` (its gradient a select);
  an XLA gather costs a v5e 7–15 ns an index whatever it fetches, and
  the two gathers of 558,848 indices a step were 11.7 ms (PERF.md §5);
- hard-negative mining = the ``min(neg_pos_ratio·num_pos, #candidates)``
  largest background losses an image, count-exact and ties to the lower
  prior as a stable sort keeps them, by threshold and tie room
  (``hard_negatives``: ``kth_largest``'s counting passes, a running
  count) — no sort, no scatter of a keep mask;
- losses are masked sums, shapes stay static.

Gradient-explosion guard: the reference skips backward when loss > 50
(``updateGradInput:546``); the equivalent lives in the train step's
``skip_loss_above`` (parallel/train.py), keeping this criterion pure.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import jax.numpy as jnp

from analytics_zoo_tpu.core.criterion import Criterion, smooth_l1
from analytics_zoo_tpu.ops.bbox import encode_bbox, iou_matrix
from analytics_zoo_tpu.ops.ranking import kth_largest, ordered_bits


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


def hard_negatives(loss: jax.Array, k: jax.Array) -> jax.Array:
    """Of each row of ``loss`` (B, P) its ``k`` (B,) int32 largest: the
    mask a stable descending sort's first ``k`` would keep — every value
    over the row's ``k``-th largest and, of those equal to it, the lowest
    indices that still fit.  No sort and no scatter: the threshold by
    ``kth_largest``'s 32 counting passes over the values' ordered bits,
    the ties' ranks by a running count.  −0.0 is counted as +0.0 (a sort
    holds them equal, their bits do not)."""
    bits = jnp.where(loss == 0, jnp.uint32(0x80000000), ordered_bits(loss))

    def count(above):
        return jnp.sum(above(bits), 1, dtype=jnp.int32)

    tau = kth_largest(count, k, bits.shape[0])[:, None]
    room = k - count(lambda v: v > tau)
    tie = bits == tau
    return (bits > tau) | (tie & (jnp.cumsum(tie, 1, dtype=jnp.int32)
                                  <= room[:, None]))


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
    G = gt_boxes.shape[1]
    # four named sections of the compiled step, forward and backward
    # (obs/names.py::SCOPES)
    with jax.named_scope("ssd/loss_match"):
        matched, positive, best_iou = jax.vmap(
            lambda b, m: match_priors(priors, b, m, param.overlap_threshold)
        )(gt_boxes, gt_mask)                                  # (B,P) each
        pos_f = positive.astype(jnp.float32)
        num_pos = jnp.sum(pos_f, -1)                          # (B,)
        # the prior's matched ground truth among the G, (B,G,P): the priors
        # stand on the minor axis, so a sum over the G adds whole vectors
        own = matched[:, None, :] == jnp.arange(G)[None, :, None]

    def of_matched(v):
        """``v[b, matched[b, p]]`` of a (B,G) ``v`` → (B,P): one term of
        the sum is not zero, so it is exact (a ``where``, so a padded
        ground truth's NaN stays out)."""
        return jnp.sum(jnp.where(own, v[:, :, None], 0), 1)

    # --- localization: smooth-L1 on encoded deltas, positives only
    with jax.named_scope("ssd/loss_loc"):
        matched_boxes = jnp.stack(
            [of_matched(gt_boxes[..., c]) for c in range(4)], -1)
        loc_target = encode_bbox(priors, variances, matched_boxes)
        loc_loss = jnp.sum(
            jnp.sum(smooth_l1(loc_pred - loc_target), axis=-1) * pos_f, -1)

    # --- confidence: CE with matched label for positives, bg for rest; the
    # matched class's log-probability by a compare against an iota, so its
    # gradient is a select
    with jax.named_scope("ssd/loss_conf"):
        matched_label = jnp.where(
            positive, of_matched(gt_labels.astype(jnp.int32)),
            param.background_id)
        logp = jax.nn.log_softmax(conf_logits, axis=-1)       # (B,P,C)
        ce = -jnp.sum(jnp.where(
            jnp.arange(logp.shape[-1]) == matched_label[..., None], logp, 0),
            -1)

    # --- hard-negative mining (reference ``mineHardExamples:334``):
    # candidates = non-positive priors whose best gt overlap is below
    # negOverlap (near-matches are neither positive nor negative); the
    # min(ratio·num_pos, #candidates) hardest of them by background loss
    with jax.named_scope("ssd/loss_mine"):
        neg_cand = (~positive) & (best_iou < param.neg_overlap)
        neg_loss = jnp.where(neg_cand, -logp[..., param.background_id],
                             -jnp.inf)
        num_neg = jnp.minimum(
            jnp.ceil(param.neg_pos_ratio * num_pos).astype(jnp.int32),
            jnp.sum(neg_cand, -1, dtype=jnp.int32))
        neg_selected = hard_negatives(neg_loss, num_neg) & neg_cand

    with jax.named_scope("ssd/loss_conf"):
        conf_loss = jnp.sum(
            ce * (pos_f + neg_selected.astype(jnp.float32)), -1)
    total_pos = jnp.maximum(jnp.sum(num_pos), 1.0)
    return (jnp.sum(param.loc_weight * loc_loss)
            + jnp.sum(conf_loss)) / total_pos


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
