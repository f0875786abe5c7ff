"""Detection ops: golden-value tests (reference test style — BboxUtilSpec,
PriorBoxSpec, MultiBoxLossSpec, NMS behavior in Nms.scala) plus
vectorization-correctness checks against straightforward numpy re-computation.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.ops import (
    DetectionOutputParam,
    MultiBoxLoss,
    MultiBoxLossParam,
    PriorBoxParam,
    bbox,
    detection_output,
    generate_base_anchors,
    match_priors,
    multibox_loss,
    nms,
    prior_box,
    proposal,
    ProposalParam,
    shift_anchors,
)


# ---------------------------------------------------------------------------
# bbox math
# ---------------------------------------------------------------------------


def test_iou_normalized():
    a = jnp.array([[0.0, 0.0, 2.0, 2.0]])
    b = jnp.array([[1.0, 1.0, 3.0, 3.0], [10.0, 10.0, 11.0, 11.0]])
    m = bbox.iou_matrix(a, b, normalized=True)
    np.testing.assert_allclose(np.asarray(m), [[1.0 / 7.0, 0.0]], atol=1e-6)


def test_iou_pixel_plus_one():
    # pixel convention: widths are x2-x1+1 (BboxUtil.bboxOverlap normalized=false)
    a = jnp.array([[0.0, 0.0, 1.0, 1.0]])     # 2x2 = 4 px
    b = jnp.array([[1.0, 1.0, 2.0, 2.0]])     # 2x2 = 4 px, 1 px overlap
    m = bbox.iou_matrix(a, b, normalized=False)
    np.testing.assert_allclose(np.asarray(m), [[1.0 / 7.0]], atol=1e-6)


def test_encode_golden():
    prior = jnp.array([0.1, 0.1, 0.3, 0.3])
    var = jnp.array([0.1, 0.1, 0.2, 0.2])
    gt = jnp.array([0.15, 0.15, 0.35, 0.35])
    enc = bbox.encode_bbox(prior, var, gt)
    np.testing.assert_allclose(np.asarray(enc), [2.5, 2.5, 0.0, 0.0], atol=1e-5)


def test_encode_decode_roundtrip():
    rng = np.random.RandomState(0)
    priors = np.abs(rng.rand(50, 2)) * 0.5
    priors = np.concatenate([priors, priors + 0.1 + rng.rand(50, 2) * 0.4], axis=1)
    var = np.tile([0.1, 0.1, 0.2, 0.2], (50, 1)).astype(np.float32)
    gt = priors + rng.randn(50, 4) * 0.01
    enc = bbox.encode_bbox(jnp.asarray(priors), jnp.asarray(var), jnp.asarray(gt))
    dec = bbox.decode_bbox(jnp.asarray(priors), jnp.asarray(var), enc)
    np.testing.assert_allclose(np.asarray(dec), gt, atol=1e-5)


def test_clip_and_scale():
    boxes = jnp.array([[-0.1, 0.5, 1.2, 0.9]])
    np.testing.assert_allclose(
        np.asarray(bbox.clip_boxes(boxes)), [[0.0, 0.5, 1.0, 0.9]])
    scaled = bbox.scale_boxes(boxes, 100.0, 200.0)
    np.testing.assert_allclose(np.asarray(scaled), [[-10.0, 100.0, 120.0, 180.0]])


def test_bbox_transform_roundtrip():
    ex = jnp.array([[10.0, 10.0, 40.0, 60.0]])
    gt = jnp.array([[12.0, 8.0, 48.0, 50.0]])
    deltas = bbox.bbox_transform(ex, gt)
    back = bbox.bbox_transform_inv(ex, deltas)
    np.testing.assert_allclose(np.asarray(back), np.asarray(gt), atol=1e-4)


# ---------------------------------------------------------------------------
# PriorBox
# ---------------------------------------------------------------------------


def test_prior_box_counts_and_first_box():
    # SSD300 conv4_3 head: 38x38, min 30, max 60, ar {2}, flip -> 4 priors/cell
    p = PriorBoxParam(min_sizes=[30], max_sizes=[60], aspect_ratios=[2],
                      flip=True, step=8)
    assert p.num_priors == 4
    priors, variances = prior_box((38, 38), (300, 300), p)
    assert priors.shape == (38 * 38 * 4, 4)
    assert variances.shape == priors.shape
    # first cell center = (0.5*8, 0.5*8) = (4, 4); first box = min 30x30
    np.testing.assert_allclose(
        priors[0], np.array([4 - 15, 4 - 15, 4 + 15, 4 + 15]) / 300.0, atol=1e-6)
    # second box: sqrt(30*60) square
    s = np.sqrt(30 * 60) / 2
    np.testing.assert_allclose(
        priors[1], np.array([4 - s, 4 - s, 4 + s, 4 + s]) / 300.0, atol=1e-6)
    # third box: ar=2 -> w = 30*sqrt(2), h = 30/sqrt(2)
    w, h = 30 * np.sqrt(2) / 2, 30 / np.sqrt(2) / 2
    np.testing.assert_allclose(
        priors[2], np.array([4 - w, 4 - h, 4 + w, 4 + h]) / 300.0, atol=1e-6)
    np.testing.assert_allclose(variances[0], [0.1, 0.1, 0.2, 0.2])


def test_prior_box_clip():
    p = PriorBoxParam(min_sizes=[200], clip=True)
    priors, _ = prior_box((2, 2), (100, 100), p)
    assert priors.min() >= 0.0 and priors.max() <= 1.0


# ---------------------------------------------------------------------------
# NMS
# ---------------------------------------------------------------------------


def test_nms_greedy_selection():
    boxes = jnp.array([
        [0.0, 0.0, 0.4, 0.4],    # A
        [0.01, 0.01, 0.41, 0.41],  # overlaps A heavily
        [0.5, 0.5, 0.9, 0.9],    # B far away
        [0.02, 0.0, 0.42, 0.4],  # overlaps A heavily
    ])
    scores = jnp.array([0.9, 0.8, 0.7, 0.85])
    keep, mask = nms(boxes, scores, iou_threshold=0.5, max_output=4)
    kept = [int(i) for i, m in zip(keep, mask) if m > 0]
    assert kept == [0, 2]


def test_nms_score_threshold_and_padding():
    boxes = jnp.array([[0.0, 0.0, 0.1, 0.1], [0.5, 0.5, 0.6, 0.6]])
    scores = jnp.array([0.9, 0.001])
    keep, mask = nms(boxes, scores, score_threshold=0.01, max_output=3)
    assert mask.tolist() == [1.0, 0.0, 0.0]
    assert int(keep[0]) == 0 and int(keep[1]) == -1


def test_nms_matches_numpy_reference():
    rng = np.random.RandomState(1)
    n = 80
    xy = rng.rand(n, 2)
    wh = rng.rand(n, 2) * 0.3 + 0.02
    boxes = np.concatenate([xy, xy + wh], axis=1).astype(np.float32)
    scores = rng.rand(n).astype(np.float32)

    def np_nms(boxes, scores, thresh):
        order = np.argsort(-scores)
        keep = []
        sup = np.zeros(n, bool)
        for i in order:
            if sup[i]:
                continue
            keep.append(i)
            ious = np.asarray(bbox.iou_matrix(
                jnp.asarray(boxes[i:i + 1]), jnp.asarray(boxes)))[0]
            sup |= ious >= thresh
        return keep

    expected = np_nms(boxes, scores, 0.5)
    keep, mask = nms(jnp.asarray(boxes), jnp.asarray(scores),
                     iou_threshold=0.5, max_output=n, pre_topk=n)
    got = [int(i) for i, m in zip(keep, mask) if m > 0]
    assert got == expected


# ---------------------------------------------------------------------------
# Matching + MultiBoxLoss
# ---------------------------------------------------------------------------


def _grid_priors(k=4):
    """k×k grid of touching square priors covering [0,1]²."""
    cells = np.linspace(0, 1, k + 1)
    out = []
    for i in range(k):
        for j in range(k):
            out.append([cells[j], cells[i], cells[j + 1], cells[i + 1]])
    return np.asarray(out, np.float32)


def test_match_priors_forced_bipartite():
    priors = jnp.asarray(_grid_priors(4))   # 16 priors
    # one gt that overlaps prior 5 modestly (IoU < 0.5): bipartite must still
    # force-match its best prior
    gt = jnp.array([[0.26, 0.26, 0.62, 0.62]])
    mask = jnp.array([1.0])
    matched, positive, _ = match_priors(priors, gt, mask, overlap_threshold=0.5)
    assert positive.sum() >= 1
    best = int(jnp.argmax(bbox.iou_matrix(priors, gt)[:, 0]))
    assert bool(positive[best])
    assert int(matched[best]) == 0


def test_match_priors_threshold():
    priors = jnp.asarray(_grid_priors(2))
    gt = jnp.array([[0.0, 0.0, 0.5, 0.5], [0.5, 0.5, 1.0, 1.0]])
    mask = jnp.array([1.0, 1.0])
    matched, positive, _ = match_priors(priors, gt, mask)
    # prior 0 == gt 0 exactly; prior 3 == gt 1 exactly
    assert bool(positive[0]) and int(matched[0]) == 0
    assert bool(positive[3]) and int(matched[3]) == 1
    # off-diagonal priors have IoU 0 with both gts -> negative
    assert not bool(positive[1]) and not bool(positive[2])


def test_match_ignores_masked_gt():
    priors = jnp.asarray(_grid_priors(2))
    gt = jnp.array([[0.0, 0.0, 0.5, 0.5], [0.5, 0.5, 1.0, 1.0]])
    mask = jnp.array([1.0, 0.0])  # second gt is padding
    matched, positive, _ = match_priors(priors, gt, mask)
    assert not bool(positive[3])


def test_multibox_loss_perfect_prediction_low_loss():
    priors = _grid_priors(4)
    P = priors.shape[0]
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    gt_boxes = np.array([[[0.0, 0.0, 0.25, 0.25]]], np.float32)   # == prior 0
    gt_labels = np.array([[7]], np.int32)
    gt_mask = np.array([[1.0]], np.float32)

    # perfect loc: zero deltas for the matched prior; perfect conf: huge logit
    loc = np.zeros((1, P, 4), np.float32)
    conf = np.zeros((1, P, 21), np.float32)
    conf[0, :, 0] = 20.0      # everything confidently background...
    conf[0, 0, 0] = 0.0
    conf[0, 0, 7] = 20.0      # ...except prior 0 -> class 7
    loss = multibox_loss(jnp.asarray(loc), jnp.asarray(conf),
                         jnp.asarray(priors), jnp.asarray(var),
                         jnp.asarray(gt_boxes), jnp.asarray(gt_labels),
                         jnp.asarray(gt_mask))
    assert float(loss) < 1e-3

    # and a wrong-class prediction must cost a lot more
    conf_bad = conf.copy()
    conf_bad[0, 0, 7] = -20.0
    loss_bad = multibox_loss(jnp.asarray(loc), jnp.asarray(conf_bad),
                             jnp.asarray(priors), jnp.asarray(var),
                             jnp.asarray(gt_boxes), jnp.asarray(gt_labels),
                             jnp.asarray(gt_mask))
    assert float(loss_bad) > 5.0


def test_multibox_loss_hard_negative_ratio():
    """With no positive-adjacent misclassification, conf loss only counts
    3·num_pos hardest negatives (reference mineHardExamples 3:1)."""
    priors = _grid_priors(4)
    P = priors.shape[0]
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    gt_boxes = np.array([[[0.0, 0.0, 0.25, 0.25]]], np.float32)
    gt_labels = np.array([[3]], np.int32)
    gt_mask = np.array([[1.0]], np.float32)
    loc = np.zeros((1, P, 4), np.float32)
    # uniform logits everywhere: each prior's CE = log(21)
    conf = np.zeros((1, P, 21), np.float32)
    conf[0, 0, 3] = 20.0  # positive prior perfectly classified
    loss = multibox_loss(jnp.asarray(loc), jnp.asarray(conf),
                         jnp.asarray(priors), jnp.asarray(var),
                         jnp.asarray(gt_boxes), jnp.asarray(gt_labels),
                         jnp.asarray(gt_mask))
    # num_pos=1 -> 3 negatives, each CE=log(21); / num_pos
    np.testing.assert_allclose(float(loss), 3 * np.log(21.0), rtol=1e-4)


def _oracle_loss_and_mask(loc_pred, conf_logits, priors, variances,
                          gt_boxes, gt_labels, gt_mask, param):
    """MultiBoxLoss as it was written up to PR 39: the matched gt's box and
    label and the matched class's log-probability GATHERED a prior at a
    time, the hard negatives by a stable descending argsort and a scatter
    of the keep mask.  → (loss, mask of the mined negatives (B, P))."""
    from analytics_zoo_tpu.core.criterion import smooth_l1

    def per_image(loc_p, conf_l, boxes, labels, mask):
        matched, positive, best_iou = match_priors(
            priors, boxes, mask, param.overlap_threshold)
        pos_f = positive.astype(jnp.float32)
        num_pos = jnp.sum(pos_f)
        loc_target = bbox.encode_bbox(priors, variances, boxes[matched])
        loc_loss = jnp.sum(
            jnp.sum(smooth_l1(loc_p - loc_target), axis=-1) * pos_f)
        matched_label = jnp.where(
            positive, labels[matched].astype(jnp.int32), param.background_id)
        logp = jax.nn.log_softmax(conf_l, axis=-1)
        ce = -jnp.take_along_axis(logp, matched_label[:, None], axis=1)[:, 0]
        neg_cand = (~positive) & (best_iou < param.neg_overlap)
        neg_loss = jnp.where(neg_cand, -logp[:, param.background_id],
                             -jnp.inf)
        num_neg = jnp.minimum(param.neg_pos_ratio * num_pos,
                              jnp.sum(neg_cand.astype(jnp.float32)))
        cand_idx = jnp.argsort(-neg_loss)
        take = jnp.arange(cand_idx.shape[0]) < num_neg
        neg_selected = (jnp.zeros(neg_loss.shape[0], bool)
                        .at[cand_idx].set(take)) & neg_cand
        conf_loss = jnp.sum(ce * (pos_f + neg_selected.astype(jnp.float32)))
        return (param.loc_weight * loc_loss, conf_loss, num_pos,
                neg_selected, neg_loss, num_neg)

    loc_l, conf_l, n_pos, mined, neg_loss, num_neg = jax.vmap(per_image)(
        loc_pred, conf_logits, gt_boxes, gt_labels, gt_mask)
    loss = (jnp.sum(loc_l) + jnp.sum(conf_l)) / jnp.maximum(
        jnp.sum(n_pos), 1.0)
    return loss, mined, neg_loss, num_neg


def _loss_case(name):
    """Inputs of the loss for one case of the equivalence test: 36 priors,
    two images, up to 12 ground truths."""
    rng = np.random.RandomState(sum(map(ord, name)))
    priors = _grid_priors(6)
    P, B, G, C = priors.shape[0], 2, 4, 21
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    boxes = rng.rand(B, G, 4).astype(np.float32) * 0.6
    boxes[..., 2:] = np.clip(boxes[..., :2] + 0.3, 0, 1)
    labels = rng.randint(1, C, (B, G)).astype(np.int32)
    mask = np.ones((B, G), np.float32)
    loc = rng.randn(B, P, 4).astype(np.float32) * 0.1
    conf = rng.randn(B, P, C).astype(np.float32)
    param = MultiBoxLossParam()
    if name == "uniform":                 # every candidate tied
        conf[:] = 0.0
    elif name == "no_positives":
        mask[:] = 0.0
    elif name == "capped":                # 3·num_pos > #candidates
        G = 12
        boxes = np.broadcast_to(priors[:G], (B, G, 4)).copy()
        labels = rng.randint(1, C, (B, G)).astype(np.int32)
        mask = np.ones((B, G), np.float32)
    elif name == "negative_zero":         # saturated background: −0.0 loss
        conf[:, np.arange(P) % 4 > 0, 0] = 100.0
    elif name == "masked_gt":
        mask[0, 1:] = 0.0
        mask[1, ::2] = 0.0
    elif name == "shared_prior":          # two gts claim one prior
        boxes[:, 1] = boxes[:, 0]
    elif name == "fractional_ratio":
        param = MultiBoxLossParam(neg_pos_ratio=2.5)
    args = tuple(jnp.asarray(x) for x in
                 (loc, conf, priors, var, boxes, labels, mask))
    return args, param


@pytest.mark.parametrize("case", [
    "uniform", "random", "no_positives", "capped", "negative_zero",
    "masked_gt", "shared_prior", "fractional_ratio"])
def test_multibox_loss_matches_gather_and_sort_oracle(case):
    """The loss that selects a prior in place (compares against an iota,
    the mining by threshold and tie room) equals the one that gathered a
    prior at a time and sorted: in value and gradient to 1e-6, and the
    mined mask bit for bit — also where zeros of both signs tie."""
    from analytics_zoo_tpu.ops.multibox_loss import hard_negatives
    args, param = _loss_case(case)

    def new(loc, conf):
        return multibox_loss(loc, conf, *args[2:], param)

    def old(loc, conf):
        return _oracle_loss_and_mask(loc, conf, *args[2:], param)[0]

    for f_new, f_old in ((new, old), (jax.grad(new, (0, 1)),
                                      jax.grad(old, (0, 1)))):
        for a, b in zip(jax.tree.leaves(f_new(*args[:2])),
                        jax.tree.leaves(f_old(*args[:2]))):
            b = np.asarray(b)
            np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6,
                                       atol=1e-6 * np.abs(b).max())

    _, mined, neg_loss, num_neg = _oracle_loss_and_mask(*args, param)
    k = jnp.ceil(num_neg).astype(jnp.int32)
    np.testing.assert_array_equal(hard_negatives(neg_loss, k), mined)
    # the sort holds −0.0 and +0.0 equal: flip every other zero's sign
    flip = (neg_loss == 0) & (jnp.arange(neg_loss.shape[1]) % 2 == 0)
    np.testing.assert_array_equal(
        hard_negatives(jnp.where(flip, 0.0, neg_loss), k), mined)
    if case == "negative_zero":
        assert bool(jnp.any(flip & mined))
    if case == "capped":
        assert bool(jnp.all(k < 3 * jnp.sum(args[6] > 0, -1)))


def test_multibox_loss_lowers_without_a_gather_of_priors():
    """At the SSD300 geometry (8,732 priors, 21 classes, 100 padded gts)
    the loss and its gradient lower with no sort and no gather or scatter
    of B·P indices or more: only the bipartite scatter, G an image."""
    import re
    B, P, C, G = 4, 8732, 21, 100
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    loss = jax.value_and_grad(
        lambda loc, conf, pr, va, bx, lb, mk: multibox_loss(
            loc, conf, pr, va, bx, lb, mk), (0, 1))
    text = jax.jit(loss).lower(
        S((B, P, 4), f32), S((B, P, C), f32), S((P, 4), f32), S((P, 4), f32),
        S((B, G, 4), f32), S((B, G), jnp.int32), S((B, G), f32)).as_text()
    assert "stablehlo.sort" not in text
    ops = re.findall(r'stablehlo\.(gather|scatter|dynamic_gather)"?'
                     r'.*?: \(tensor<[^>]*>, tensor<([0-9x]*)x?[a-z]+[0-9]*>',
                     text, re.S)
    assert ops, "the bipartite scatter is lowered"
    for op, dims in ops:
        n = int(np.prod([int(d) for d in dims.split("x") if d]))
        assert n < B * P, (op, dims)


def test_multibox_loss_grad_flows():
    priors = _grid_priors(2)
    P = priors.shape[0]
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    crit = MultiBoxLoss(priors, var, MultiBoxLossParam(n_classes=5))
    target = {
        "bboxes": jnp.asarray([[[0.0, 0.0, 0.5, 0.5]]]),
        "labels": jnp.asarray([[2]]),
        "mask": jnp.asarray([[1.0]]),
    }

    def f(loc, conf):
        return crit((loc, conf), target)

    loc = jnp.ones((1, P, 4)) * 0.1
    conf = jnp.zeros((1, P, 5))
    g_loc, g_conf = jax.grad(f, argnums=(0, 1))(loc, conf)
    assert np.isfinite(np.asarray(g_loc)).all()
    assert np.isfinite(np.asarray(g_conf)).all()
    assert float(jnp.abs(g_loc).sum()) > 0
    assert float(jnp.abs(g_conf).sum()) > 0


# ---------------------------------------------------------------------------
# DetectionOutput
# ---------------------------------------------------------------------------


def test_detection_output_end_to_end():
    priors = _grid_priors(4)
    P = priors.shape[0]
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    param = DetectionOutputParam(n_classes=3, keep_topk=10, nms_topk=16,
                                 conf_thresh=0.1)
    loc = np.zeros((1, P, 4), np.float32)
    conf = np.full((1, P, 3), 0.0, np.float32)
    conf[0, :, 0] = 0.98
    conf[0, :, 1:] = 0.01
    conf[0, 5] = [0.05, 0.9, 0.05]     # class-1 hit at prior 5
    conf[0, 10] = [0.1, 0.1, 0.8]      # class-2 hit at prior 10
    out = detection_output(jnp.asarray(loc), jnp.asarray(conf),
                           jnp.asarray(priors), jnp.asarray(var), param)
    out = np.asarray(out[0])
    valid = out[out[:, 0] >= 0]
    assert valid.shape[0] == 2
    # ranked by score: class 1 (0.9) first, then class 2 (0.8)
    assert valid[0, 0] == 1 and valid[0, 1] == pytest.approx(0.9, abs=1e-5)
    assert valid[1, 0] == 2 and valid[1, 1] == pytest.approx(0.8, abs=1e-5)
    np.testing.assert_allclose(valid[0, 2:], priors[5], atol=1e-5)
    np.testing.assert_allclose(valid[1, 2:], priors[10], atol=1e-5)


def test_detection_output_suppresses_background():
    priors = _grid_priors(2)
    P = priors.shape[0]
    var = np.tile([0.1, 0.1, 0.2, 0.2], (P, 1)).astype(np.float32)
    param = DetectionOutputParam(n_classes=3, keep_topk=5, nms_topk=4,
                                 conf_thresh=0.3)
    loc = np.zeros((1, P, 4), np.float32)
    conf = np.zeros((1, P, 3), np.float32)
    conf[0, :, 0] = 1.0   # pure background
    out = np.asarray(detection_output(jnp.asarray(loc), jnp.asarray(conf),
                                      jnp.asarray(priors), jnp.asarray(var),
                                      param)[0])
    assert (out[:, 0] == -1).all()


# ---------------------------------------------------------------------------
# Anchor / Proposal (Faster-RCNN)
# ---------------------------------------------------------------------------


def test_base_anchors_golden():
    """Canonical py-faster-rcnn generate_anchors output (the values the
    reference's Anchor.scala reproduces)."""
    a = generate_base_anchors(16, (0.5, 1.0, 2.0), (8, 16, 32))
    expected_first = np.array([
        [-84.0, -40.0, 99.0, 55.0],
        [-176.0, -88.0, 191.0, 103.0],
        [-360.0, -184.0, 375.0, 199.0],
        [-56.0, -56.0, 71.0, 71.0],
    ])
    np.testing.assert_allclose(a[:4], expected_first)
    assert a.shape == (9, 4)


def test_shift_anchors():
    base = generate_base_anchors()
    shifted = shift_anchors(base, 2, 3, 16)
    assert shifted.shape == (2 * 3 * 9, 4)
    np.testing.assert_allclose(shifted[:9], base)
    np.testing.assert_allclose(shifted[9], base[0] + [16, 0, 16, 0])


def test_proposal_smoke():
    base = generate_base_anchors()
    anchors = jnp.asarray(shift_anchors(base, 4, 4, 16))
    n = anchors.shape[0]
    rng = np.random.RandomState(0)
    scores = jnp.asarray(rng.rand(n).astype(np.float32))
    deltas = jnp.asarray((rng.randn(n, 4) * 0.1).astype(np.float32))
    rois, mask = proposal(scores, deltas, anchors,
                          jnp.asarray(64.0), jnp.asarray(64.0),
                          jnp.asarray(1.0),
                          ProposalParam(post_nms_topn=20, pre_nms_topn=64))
    assert rois.shape == (20, 4)
    kept = np.asarray(mask).sum()
    assert kept > 0
    r = np.asarray(rois)[np.asarray(mask) > 0]
    assert (r[:, 0] >= 0).all() and (r[:, 2] <= 63).all()
