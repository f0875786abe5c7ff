"""Fused DetectionOutput kernel parity suite (interpret mode on CPU).

The fused single-kernel program (``ops/pallas_detout.py``) must produce
the SAME detections as ``detection_output_single`` — the reference
semantics every backend implements — across the distributions serving
actually sees: trained-like background-dominated conf, ragged per-class
candidate populations, empty classes, all-background batches, and
int8-quantized score grids (massive score ties, where the tie-break
ORDER must also agree).  Plus the VMEM-budget fallback contract:
over-budget geometries warn and return the unfused pallas path's
output bit-for-bit.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.ops.detection_output import (
    DetectionOutputParam, detection_output, detection_output_single)


def _geometry(seed, priors_n=160):
    rng = np.random.RandomState(seed)
    cx = rng.rand(priors_n, 2).astype(np.float32)
    wh = (rng.rand(priors_n, 2) * 0.2 + 0.05).astype(np.float32)
    priors = np.concatenate([cx - wh / 2, cx + wh / 2], 1)
    variances = np.tile(np.asarray([0.1, 0.1, 0.2, 0.2], np.float32),
                        (priors_n, 1))
    return jnp.asarray(priors), jnp.asarray(variances)


def _inputs(seed, batch=2, priors_n=160, classes=6, bg_bias=0.0,
            hot_frac=0.0, per_class_hot=None):
    """Seeded loc/conf; ``bg_bias`` background-dominates the softmax
    (trained-like), ``hot_frac`` re-boosts a random prior fraction in
    every foreground class, ``per_class_hot`` gives each foreground
    class its OWN hot fraction (ragged candidate rows)."""
    rng = np.random.RandomState(seed)
    priors, variances = _geometry(seed, priors_n)
    loc = jnp.asarray((rng.randn(batch, priors_n, 4) * 0.1)
                      .astype(np.float32))
    logits = rng.randn(batch, priors_n, classes).astype(np.float32)
    logits[..., 0] += bg_bias
    if hot_frac:
        hot = rng.rand(batch, priors_n) < hot_frac
        logits[..., 1:] += np.where(hot[..., None], 9.0, 0.0)
    if per_class_hot is not None:
        for j, frac in enumerate(per_class_hot, start=1):
            hot = rng.rand(batch, priors_n) < frac
            logits[..., j] += np.where(hot, 9.0, 0.0)
    conf = jnp.asarray(np.asarray(
        jax.nn.softmax(jnp.asarray(logits), axis=-1)))
    return loc, conf, priors, variances


def _reference(loc, conf, priors, variances, param):
    return np.asarray(jax.vmap(
        lambda l, c: detection_output_single(l, c, priors, variances,
                                             param))(loc, conf))


def _fused(loc, conf, priors, variances, param):
    return np.asarray(detection_output(
        loc, conf, priors, variances,
        dataclasses.replace(param, backend="fused")))


def _assert_rows_match(got, ref, atol=1e-5):
    np.testing.assert_array_equal(got[..., 0], ref[..., 0])     # classes
    np.testing.assert_allclose(got[..., 1], ref[..., 1], atol=1e-6)
    np.testing.assert_allclose(got[..., 2:], ref[..., 2:], atol=atol)


BASE = dict(n_classes=6, nms_topk=64, keep_topk=32)


class TestFusedParity:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_trained_like_conf(self, seed):
        """The serving distribution: background bias +7 makes conf
        sparse exactly like a trained SSD's softmax (the SERVE_PROFILE
        methodology), a few re-boosted hot priors carry detections."""
        loc, conf, priors, variances = _inputs(seed, bg_bias=7.0,
                                               hot_frac=0.05)
        assert (np.asarray(conf)[..., 1:] > 0.01).mean() < 0.15
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    @pytest.mark.parametrize("seed", [0, 3])
    def test_dense_untrained_conf(self, seed):
        """Dense near-uniform conf (untrained init): every class row
        saturates the nms_topk pop bound — the opposite regime."""
        loc, conf, priors, variances = _inputs(seed)
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_ragged_valid_candidate_rows(self):
        """Per-class candidate populations from dense to empty: the
        dynamic pop bound must handle every row width in ONE grid."""
        loc, conf, priors, variances = _inputs(
            11, bg_bias=6.0, per_class_hot=[0.5, 0.1, 0.02, 0.002, 0.0])
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_all_background_and_empty_classes(self):
        """No foreground score above conf_thresh → every output row is
        the empty convention (class -1, score 0, zero box), matching
        the reference exactly."""
        loc, conf, priors, variances = _inputs(5, bg_bias=20.0)
        p = DetectionOutputParam(**BASE)
        got = _fused(loc, conf, priors, variances, p)
        ref = _reference(loc, conf, priors, variances, p)
        _assert_rows_match(got, ref)
        assert (got[..., 0] == -1).all() and (got[..., 1] == 0).all()
        assert (got[..., 2:] == 0).all()

    def test_int8_quantized_conf_ties_agree(self):
        """Int8-quantized score grids (the int8 serving tiers' regime)
        create massive exact TIES; the fused kernel's lowest-flat-index
        pop order must reproduce lax.top_k's stable order both per
        class and in the global merge — row-for-row equality, not just
        set equality."""
        loc, conf, priors, variances = _inputs(2, bg_bias=5.0,
                                               hot_frac=0.08)
        qconf = jnp.asarray(
            np.round(np.asarray(conf) * 127.0) / 127.0)
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, qconf, priors, variances, p),
                           _reference(loc, qconf, priors, variances, p))

    def test_clip_boxes(self):
        loc, conf, priors, variances = _inputs(4, bg_bias=4.0,
                                               hot_frac=0.1)
        p = DetectionOutputParam(**BASE, clip_boxes=True)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_nonzero_background_id(self):
        """The foreground-row → class-id mapping when background is not
        class 0 (the discard-at-selection layout must skip the right
        column)."""
        loc, conf, priors, variances = _inputs(6, hot_frac=0.05)
        p = DetectionOutputParam(**BASE, background_id=3)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_matches_unfused_pallas_backend(self):
        """Backend triple-point: fused == pallas == xla on one batch."""
        loc, conf, priors, variances = _inputs(8, bg_bias=6.0,
                                               hot_frac=0.05)
        outs = {}
        for backend in ("xla", "pallas", "fused"):
            p = DetectionOutputParam(**BASE, backend=backend)
            outs[backend] = np.asarray(detection_output(
                loc, conf, priors, variances, p))
        _assert_rows_match(outs["fused"], outs["pallas"])
        _assert_rows_match(outs["fused"], outs["xla"])

    def test_keep_topk_exceeds_kept_count(self):
        """keep_topk far above the surviving-candidate count: the tail
        rows are the empty convention and the head rows still match."""
        loc, conf, priors, variances = _inputs(9, bg_bias=8.0,
                                               hot_frac=0.01)
        p = DetectionOutputParam(n_classes=6, nms_topk=64, keep_topk=120)
        got = _fused(loc, conf, priors, variances, p)
        ref = _reference(loc, conf, priors, variances, p)
        _assert_rows_match(got, ref)
        assert (got[..., 1] > 0).sum() < got.shape[0] * 120


class TestBackendResolution:
    """``resolve_backend`` is the one place a backend is chosen, and what
    it returns is what ``detection_output`` runs: an explicitly named
    backend is never swapped for another."""

    def test_auto_off_tpu_is_xla_and_not_interpreted(self):
        from analytics_zoo_tpu.ops.detection_output import resolve_backend

        r = resolve_backend(DetectionOutputParam(), 8732, 21)
        assert (r.name, r.interpret) == ("xla", False)

    def test_explicit_pallas_backends_interpret_off_tpu(self):
        from analytics_zoo_tpu.ops.detection_output import resolve_backend

        for name in ("pallas", "fused"):
            r = resolve_backend(DetectionOutputParam(backend=name), 160, 6)
            assert (r.name, r.interpret) == (name, True)

    def test_auto_on_tpu_selects_by_the_vmem_estimate(self, monkeypatch):
        from analytics_zoo_tpu.ops import vmem
        from analytics_zoo_tpu.ops.detection_output import resolve_backend
        from analytics_zoo_tpu.utils import engine

        monkeypatch.setattr(engine, "on_tpu", lambda: True)
        p = DetectionOutputParam()
        assert resolve_backend(p, 8732, 21) == ("fused", False)
        assert resolve_backend(p, 24564, 21) == ("fused", False)
        assert resolve_backend(
            dataclasses.replace(p, approx_topk=True), 8732, 21
        ) == ("pallas", False)
        monkeypatch.setattr(vmem, "VMEM_BUDGET_BYTES", 1)
        assert resolve_backend(p, 8732, 21) == ("pallas", False)

    def test_explicit_fused_over_budget_is_an_error(self, monkeypatch):
        """No silent swap to a slower path: the caller named the kernel."""
        from analytics_zoo_tpu.ops import vmem

        loc, conf, priors, variances = _inputs(0, bg_bias=6.0,
                                               hot_frac=0.05)
        monkeypatch.setattr(vmem, "VMEM_BUDGET_BYTES", 1)
        with pytest.raises(ValueError, match="VMEM.*budget"):
            detection_output(loc, conf, priors, variances,
                             DetectionOutputParam(**BASE, backend="fused"))

    def test_unknown_backend_is_an_error(self):
        loc, conf, priors, variances = _inputs(0)
        with pytest.raises(ValueError, match="backend"):
            detection_output(loc, conf, priors, variances,
                             DetectionOutputParam(**BASE, backend="mosaic"))

    def test_estimate_counts_tile_padding(self):
        """Every (1, 1, P) lane vector and (1, 4, P) block occupies 8
        sublanes in VMEM: the estimate is ~8x the logical bytes, and it
        matches what the v5e accepted (PR 21 chip run: SSD300 compiled
        inside its 21.6 MiB request; SSD512 needed 33.3 MiB, more than
        its 24.9 MiB of declared buffers, less than the 45.4 MiB it now
        asks for)."""
        from analytics_zoo_tpu.ops import vmem
        from analytics_zoo_tpu.ops.pallas_detout import fused_vmem_bytes

        small = fused_vmem_bytes(160, 6, 32)
        ssd300 = fused_vmem_bytes(8732, 21, 200)
        ssd512 = fused_vmem_bytes(24564, 21, 200)
        assert small < ssd300 < ssd512
        ppad = 8832
        logical = (20 + 7) * ppad * 4        # scratch rows, unpadded
        assert ssd300 > 8 * logical
        assert 9.0 < ssd300 / 2**20 < 9.2
        assert 24.9 < ssd512 / 2**20 < 25.0
        assert vmem.limit_bytes(ssd512) > 33.34 * 2**20
        assert vmem.fits(ssd300) and vmem.fits(ssd512)

    def test_param_is_static_arg_usable(self):
        p = DetectionOutputParam(backend="fused")
        assert p.backend == "fused" and hash(p)


def _ssd_inputs(resolution, batch, seed=0):
    """Seeded trained-like (background-dominated, ~3 % hot priors)
    loc/conf at a real SSD geometry: P=8732 (300) or 24564 (512)."""
    from analytics_zoo_tpu.models import (build_priors, ssd300_config,
                                          ssd512_config)

    priors, variances = build_priors(
        ssd300_config() if resolution == 300 else ssd512_config())
    P, C = priors.shape[0], 21
    rng = np.random.RandomState(seed)
    loc = (rng.randn(batch, P, 4) * 0.1).astype(np.float32)
    logits = rng.randn(batch, P, C).astype(np.float32)
    logits[..., 0] += 6.0
    logits[..., 1:] += np.where(rng.rand(batch, P, 1) < 0.03, 9.0, 0.0)
    conf = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    return loc, conf, np.asarray(priors), np.asarray(variances)


class TestFusedDeviceTwins:
    """Compiled-Mosaic twins of the interpret-mode pins — skipped off
    TPU; `JAX_PLATFORMS=tpu python -m pytest tests/test_pallas_detout.py
    -m pallas` runs them on the chip."""

    @pytest.mark.pallas(device=True)
    @pytest.mark.parametrize("resolution,batch",
                             [(300, 8), (300, 32), (512, 8)])
    def test_compiled_kernel_at_zoo_geometries(self, resolution, batch):
        """The geometries serving runs: SSD300 at the runtime's and the
        trainer's batch, SSD512 — default DetectionOutputParam
        (keep_topk=200, nms_topk=400), through ``"auto"``, which must
        resolve to the compiled fused kernel."""
        from analytics_zoo_tpu.ops.detection_output import resolve_backend

        loc, conf, priors, variances = _ssd_inputs(resolution, batch)
        p = DetectionOutputParam()
        assert resolve_backend(p, priors.shape[0], 21) == ("fused", False)
        got = np.asarray(detection_output(loc, conf, priors, variances, p))
        ref = np.asarray(detection_output(
            loc, conf, priors, variances,
            dataclasses.replace(p, backend="xla")))
        assert (ref[..., 1] > 0).sum() == batch * 200
        _assert_rows_match(got, ref, atol=1e-4)

    @pytest.mark.pallas(device=True)
    def test_compiled_kernel_matches_reference(self):
        from analytics_zoo_tpu.ops.pallas_detout import (
            fused_detection_output)

        loc, conf, priors, variances = _inputs(0, bg_bias=7.0,
                                               hot_frac=0.05)
        p = DetectionOutputParam(**BASE)
        got = np.asarray(fused_detection_output(
            loc, conf, priors, variances, param=p, interpret=False))
        _assert_rows_match(got, _reference(loc, conf, priors, variances,
                                           p))

    @pytest.mark.pallas(device=True)
    def test_compiled_stage_prefixes_run(self):
        from analytics_zoo_tpu.ops.pallas_detout import (
            STAGES, fused_detection_output)

        loc, conf, priors, variances = _inputs(1, bg_bias=7.0,
                                               hot_frac=0.05)
        p = DetectionOutputParam(**BASE)
        for stage in STAGES:
            out = fused_detection_output(loc, conf, priors, variances,
                                         param=p, interpret=False,
                                         stage=stage)
            assert np.isfinite(np.asarray(out)).all()
