"""Pallas NMS kernel parity tests (interpret mode on CPU)."""

import numpy as np
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.ops import nms
from analytics_zoo_tpu.ops.pallas_nms import pallas_nms


def _random_boxes(n, seed):
    rng = np.random.RandomState(seed)
    xy = rng.rand(n, 2)
    wh = rng.rand(n, 2) * 0.3 + 0.02
    boxes = np.concatenate([xy, xy + wh], axis=1).astype(np.float32)
    scores = rng.rand(n).astype(np.float32)
    return jnp.asarray(boxes), jnp.asarray(scores)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_nms_matches_xla_nms(seed):
    boxes, scores = _random_boxes(100, seed)
    ref_idx, ref_mask = nms(boxes, scores, iou_threshold=0.5,
                            max_output=50, pre_topk=100)
    got_idx, got_mask = pallas_nms(boxes, scores, iou_threshold=0.5,
                                   max_output=50, pre_topk=100,
                                   interpret=True)
    ref = [int(i) for i, m in zip(ref_idx, ref_mask) if m > 0]
    got = [int(i) for i, m in zip(got_idx, got_mask) if m > 0]
    assert got == ref


def test_pallas_nms_score_threshold():
    boxes = jnp.asarray([[0.0, 0.0, 0.1, 0.1], [0.5, 0.5, 0.6, 0.6]],
                        jnp.float32)
    scores = jnp.asarray([0.9, 0.001], jnp.float32)
    idx, mask = pallas_nms(boxes, scores, score_threshold=0.01,
                           max_output=4, interpret=True)
    assert mask.tolist() == [1.0, 0.0, 0.0, 0.0]
    assert int(idx[0]) == 0


def test_pallas_nms_max_output_truncates():
    rng = np.random.RandomState(3)
    # 30 well-separated boxes -> all survive; max_output=10 keeps top 10
    centers = np.arange(30, dtype=np.float32)[:, None] * 2.0
    boxes = np.concatenate([centers, centers, centers + 1, centers + 1],
                           axis=1)
    scores = rng.rand(30).astype(np.float32)
    idx, mask = pallas_nms(jnp.asarray(boxes), jnp.asarray(scores),
                           max_output=10, interpret=True)
    assert mask.sum() == 10
    kept_scores = scores[np.asarray(idx)]
    assert (np.diff(kept_scores) <= 1e-6).all()  # score-ranked


@pytest.mark.pallas(device=True)
@pytest.mark.parametrize("n_rows", [8 * 20, 32 * 20])
def test_compiled_sweep_matches_interpret_at_serving_geometry(n_rows):
    """The sweep as the unfused serve path calls it, compiled by Mosaic:
    one grid row per (image, foreground class) — B x 20 for SSD at B = 8
    and 32 — over K = 512 lanes (nms_topk 400 rounded up to the lane
    multiple), ragged valid prefixes.  Skipped off TPU."""
    from analytics_zoo_tpu.ops.pallas_nms import nms_sweep

    K = 512
    rng = np.random.RandomState(1)
    cx = rng.rand(n_rows, K, 2).astype(np.float32)
    wh = (rng.rand(n_rows, K, 2) * 0.2 + 0.05).astype(np.float32)
    x1, y1 = cx[..., 0] - wh[..., 0] / 2, cx[..., 1] - wh[..., 1] / 2
    x2, y2 = cx[..., 0] + wh[..., 0] / 2, cx[..., 1] + wh[..., 1] / 2
    valid = (np.arange(K)[None] < rng.randint(0, 400, (n_rows, 1))).astype(
        np.float32)
    got = np.asarray(nms_sweep(x1, y1, x2, y2, valid, interpret=False))
    ref = np.asarray(nms_sweep(x1, y1, x2, y2, valid, interpret=True))
    np.testing.assert_array_equal(got, ref)
    assert got.sum() > n_rows          # the sweep kept and suppressed


@pytest.mark.pallas(device=True)
@pytest.mark.parametrize("resolution", [300, 512])
def test_compiled_pallas_backend_matches_xla_at_ssd_geometry(resolution):
    """backend="pallas" end to end at P = 8732 / 24564, C = 21, B = 8 —
    the path "auto" falls to when the fused kernel does not fit."""
    import dataclasses

    from test_pallas_detout import _assert_rows_match, _ssd_inputs

    from analytics_zoo_tpu.ops.detection_output import (
        DetectionOutputParam, detection_output)

    loc, conf, priors, variances = _ssd_inputs(resolution, 8)
    p = DetectionOutputParam(backend="pallas")
    got = np.asarray(detection_output(loc, conf, priors, variances, p))
    ref = np.asarray(detection_output(
        loc, conf, priors, variances, dataclasses.replace(p, backend="xla")))
    _assert_rows_match(got, ref, atol=1e-4)


class TestDetectionOutputPallasBackend:
    """The serving-path wiring: DetectionOutputParam(backend='pallas')
    must agree with the XLA backend end to end (VERDICT round-1 item 6)."""

    def _inputs(self, seed, batch=2, priors_n=160, classes=6):
        import jax
        from analytics_zoo_tpu.ops.priorbox import PriorBoxParam, prior_box
        rng = np.random.RandomState(seed)
        cx = rng.rand(priors_n, 2).astype(np.float32)
        wh = (rng.rand(priors_n, 2) * 0.2 + 0.05).astype(np.float32)
        priors = np.concatenate([cx - wh / 2, cx + wh / 2], 1)
        variances = np.tile(np.asarray([0.1, 0.1, 0.2, 0.2], np.float32),
                            (priors_n, 1))
        loc = (rng.randn(batch, priors_n, 4) * 0.1).astype(np.float32)
        logits = rng.randn(batch, priors_n, classes).astype(np.float32)
        conf = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        return (jnp.asarray(loc), jnp.asarray(conf), jnp.asarray(priors),
                jnp.asarray(variances))

    @pytest.mark.parametrize("seed", [0, 7])
    def test_backend_parity(self, seed):
        from analytics_zoo_tpu.ops.detection_output import (
            DetectionOutputParam, detection_output)
        loc, conf, priors, variances = self._inputs(seed)
        base = dict(n_classes=conf.shape[-1], nms_topk=64, keep_topk=32)
        ref = detection_output(loc, conf, priors, variances,
                               DetectionOutputParam(**base, backend="xla"))
        got = detection_output(loc, conf, priors, variances,
                               DetectionOutputParam(**base, backend="pallas"))
        ref, got = np.asarray(ref), np.asarray(got)
        # identical detections (class, box) row by row; scores to fp tolerance
        np.testing.assert_array_equal(got[..., 0], ref[..., 0])
        np.testing.assert_allclose(got[..., 1], ref[..., 1], atol=1e-6)
        np.testing.assert_allclose(got[..., 2:], ref[..., 2:], atol=1e-6)

    def test_backend_reaches_ssd_predictor_param(self):
        from analytics_zoo_tpu.ops.detection_output import DetectionOutputParam
        p = DetectionOutputParam(backend="pallas")
        assert p.backend == "pallas" and hash(p)  # static-arg usable

    @pytest.mark.parametrize("seed", [0, 7])
    def test_backend_parity_sparse_scores(self, seed):
        """Realistic serving sparsity: most scores below conf_thresh, so
        the sweep's dynamic lane bound (the round-4 optimization) kicks
        in — valid lanes are a short sorted prefix — and the result must
        still match the XLA backend exactly."""
        import jax
        from analytics_zoo_tpu.ops.detection_output import (
            DetectionOutputParam, detection_output)
        loc, conf, priors, variances = self._inputs(seed)
        # background-dominate the softmax: boost class 0, leave a few hot
        logits = np.log(np.asarray(conf) + 1e-9)
        logits[..., 0] += 8.0
        rng = np.random.RandomState(seed + 100)
        hot = rng.rand(*logits.shape[:2]) < 0.05
        logits[..., 1:] += np.where(hot[..., None], 10.0, 0.0)
        sparse_conf = np.asarray(
            jax.nn.softmax(jnp.asarray(logits), axis=-1))
        # genuinely sparse foreground (background col is always ~1.0)
        assert (sparse_conf[..., 1:] > 0.01).mean() < 0.15
        base = dict(n_classes=conf.shape[-1], nms_topk=64, keep_topk=32)
        ref = detection_output(loc, jnp.asarray(sparse_conf), priors,
                               variances,
                               DetectionOutputParam(**base, backend="xla"))
        got = detection_output(loc, jnp.asarray(sparse_conf), priors,
                               variances,
                               DetectionOutputParam(**base, backend="pallas"))
        ref, got = np.asarray(ref), np.asarray(got)
        np.testing.assert_array_equal(got[..., 0], ref[..., 0])
        np.testing.assert_allclose(got[..., 1], ref[..., 1], atol=1e-6)
        np.testing.assert_allclose(got[..., 2:], ref[..., 2:], atol=1e-6)

    def test_approx_topk_path(self, ):
        """approx_topk=True routes candidate selection through
        lax.approx_max_k.  On CPU the lowering is exact, so the pallas
        backend must still match XLA bit-for-bit — this pins the code
        path; the recall/mAP cost on real TPU is measured by
        tools/eval_quantized_ssd.py --approx."""
        from analytics_zoo_tpu.ops.detection_output import (
            DetectionOutputParam, detection_output)
        loc, conf, priors, variances = self._inputs(3)
        base = dict(n_classes=conf.shape[-1], nms_topk=64, keep_topk=32)
        ref = detection_output(loc, conf, priors, variances,
                               DetectionOutputParam(**base, backend="xla"))
        got = detection_output(
            loc, conf, priors, variances,
            DetectionOutputParam(**base, backend="pallas",
                                 approx_topk=True))
        ref, got = np.asarray(ref), np.asarray(got)
        np.testing.assert_array_equal(got[..., 0], ref[..., 0])
        np.testing.assert_allclose(got[..., 1], ref[..., 1], atol=1e-6)
        np.testing.assert_allclose(got[..., 2:], ref[..., 2:], atol=1e-6)
