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

#: the kernel pads P to whole (8, 128) registers (a multiple of 1,024):
#: under one register; a multiple of 128 but not of 1,024 (padded rows,
#: no padded lane); neither (padded lanes in the last row, and padded rows)
PRIOR_COUNTS = [160, 1152, 1300]


@pytest.mark.parametrize("priors_n", PRIOR_COUNTS)
class TestFusedParity:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_trained_like_conf(self, seed, priors_n):
        """The serving distribution: background bias +7 makes conf
        sparse exactly like a trained SSD's softmax (what the benchmark's
        `ssd512-vgg16` configuration does), a few re-boosted hot priors
        carry detections."""
        loc, conf, priors, variances = _inputs(
            seed, priors_n=priors_n, bg_bias=7.0, hot_frac=0.05)
        assert (np.asarray(conf)[..., 1:] > 0.01).mean() < 0.15
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    @pytest.mark.parametrize("seed", [0, 3])
    def test_dense_untrained_conf(self, seed, priors_n):
        """Dense near-uniform conf (untrained init): every class row
        saturates the nms_topk pop bound — the opposite regime."""
        loc, conf, priors, variances = _inputs(seed, priors_n=priors_n)
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_ragged_valid_candidate_rows(self, priors_n):
        """Per-class candidate populations from dense to empty: the
        dynamic pop bound must handle every row width in ONE grid."""
        loc, conf, priors, variances = _inputs(
            11, priors_n=priors_n, bg_bias=6.0,
            per_class_hot=[0.5, 0.1, 0.02, 0.002, 0.0])
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_all_background_and_empty_classes(self, priors_n):
        """No foreground score above conf_thresh → every output row is
        the empty convention (class -1, score 0, zero box), matching
        the reference exactly."""
        loc, conf, priors, variances = _inputs(5, priors_n=priors_n,
                                               bg_bias=20.0)
        p = DetectionOutputParam(**BASE)
        got = _fused(loc, conf, priors, variances, p)
        ref = _reference(loc, conf, priors, variances, p)
        _assert_rows_match(got, ref)
        assert (got[..., 0] == -1).all() and (got[..., 1] == 0).all()
        assert (got[..., 2:] == 0).all()

    def test_int8_quantized_conf_ties_agree(self, priors_n):
        """Int8-quantized score grids (the int8 serving tiers' regime)
        create massive exact TIES; the fused kernel's lowest-flat-index
        pop order must reproduce lax.top_k's stable order both per
        class and in the global merge — row-for-row equality, not just
        set equality."""
        loc, conf, priors, variances = _inputs(
            2, priors_n=priors_n, bg_bias=5.0, hot_frac=0.08)
        qconf = jnp.asarray(
            np.round(np.asarray(conf) * 127.0) / 127.0)
        p = DetectionOutputParam(**BASE)
        _assert_rows_match(_fused(loc, qconf, priors, variances, p),
                           _reference(loc, qconf, priors, variances, p))

    def test_clip_boxes(self, priors_n):
        loc, conf, priors, variances = _inputs(
            4, priors_n=priors_n, bg_bias=4.0, hot_frac=0.1)
        p = DetectionOutputParam(**BASE, clip_boxes=True)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_nonzero_background_id(self, priors_n):
        """The foreground-row → class-id mapping when background is not
        class 0 (the discard-at-selection layout must skip the right
        column)."""
        loc, conf, priors, variances = _inputs(
            6, priors_n=priors_n, hot_frac=0.05)
        p = DetectionOutputParam(**BASE, background_id=3)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))

    def test_matches_unfused_pallas_backend(self, priors_n):
        """Backend triple-point: fused == pallas == xla on one batch."""
        loc, conf, priors, variances = _inputs(
            8, priors_n=priors_n, bg_bias=6.0, hot_frac=0.05)
        outs = {}
        for backend in ("xla", "pallas", "fused"):
            p = DetectionOutputParam(**BASE, backend=backend)
            outs[backend] = np.asarray(detection_output(
                loc, conf, priors, variances, p))
        _assert_rows_match(outs["fused"], outs["pallas"])
        _assert_rows_match(outs["fused"], outs["xla"])

    @pytest.mark.parametrize("keep_topk", [5, 13, 50])
    def test_keep_topk_off_a_multiple_of_eight(self, priors_n, keep_topk):
        """The merge writes a row through the aligned 8-row window that
        holds it; a ``keep_topk`` that ends inside a window (50 is the
        ``int8_topk50`` serving tier's) gets exactly its rows."""
        loc, conf, priors, variances = _inputs(
            1, priors_n=priors_n, bg_bias=5.0, hot_frac=0.1)
        p = DetectionOutputParam(n_classes=6, nms_topk=64,
                                 keep_topk=keep_topk)
        got = _fused(loc, conf, priors, variances, p)
        assert got.shape == (2, keep_topk, 6)
        assert (got[..., 0] >= 0).all()         # more kept than asked for
        _assert_rows_match(got, _reference(loc, conf, priors, variances, p))

    def test_keep_topk_exceeds_kept_count(self, priors_n):
        """keep_topk far above the surviving-candidate count: the tail
        rows are the empty convention and the head rows still match."""
        loc, conf, priors, variances = _inputs(
            9, priors_n=priors_n, bg_bias=8.0, hot_frac=0.01)
        p = DetectionOutputParam(n_classes=6, nms_topk=64, keep_topk=120)
        got = _fused(loc, conf, priors, variances, p)
        ref = _reference(loc, conf, priors, variances, p)
        _assert_rows_match(got, ref)
        assert (got[..., 1] > 0).sum() < got.shape[0] * 120


def _one_image_empty(loc, conf, priors, variances):
    """The first image with no foreground score at all."""
    conf = np.array(conf)
    conf[0, :, 1:] = 0.0
    return loc, jnp.asarray(conf), priors, variances


class TestKeepLists:
    """Each foreground class's keeps go to a list of min(nms_topk, P)
    slots in pop order, and the merge pops over those lists alone: the
    lists' edges, each against the xla backend."""

    @pytest.mark.parametrize("inputs,param", [
        # dense conf and suppression only of identical boxes: every
        # class keeps all its 130 pops, a list full to its last slot and
        # 130 not a multiple of 128; every slot of every list comes out
        (dict(seed=3), dict(nms_topk=130, nms_thresh=1.0, keep_topk=700)),
        # keep_topk over one class's capacity: the merge cuts across
        # lists
        (dict(seed=3), dict(nms_topk=130, nms_thresh=1.0, keep_topk=300)),
        (dict(seed=4, bg_bias=4.0, hot_frac=0.1),
         dict(nms_topk=20, keep_topk=70)),
        # one image with no candidate, one with fewer keeps than keep_topk
        (dict(seed=9, bg_bias=8.0, hot_frac=0.01, empty_first=True),
         dict(nms_topk=64, keep_topk=120)),
    ], ids=["full_list_130", "merge_crosses_lists", "capacity_20",
            "no_candidate_and_few_keeps"])
    def test_list_edges(self, inputs, param):
        inputs = dict(inputs)
        empty_first = inputs.pop("empty_first", False)
        case = _inputs(priors_n=1300, **inputs)
        if empty_first:
            case = _one_image_empty(*case)
        p = DetectionOutputParam(n_classes=6, **param)
        got = _fused(*case, p)
        ref = _reference(*case, p)
        _assert_rows_match(got, ref)
        rows = (ref[..., 1] > 0).sum(axis=1)
        if empty_first:
            assert rows[0] == 0 and 0 < rows[1] < p.keep_topk
        elif p.nms_thresh == 1.0:
            # every class's list full: 5 x 130 keeps, cut at keep_topk
            assert (rows == min(5 * 130, p.keep_topk)).all()


def _placed(priors_n, scores, overlaps=(), classes=4):
    """One image of hand-placed candidates.  Prior ``i`` is a small box in
    its own cell of a 64-wide grid and ``loc`` is zero, so a decoded box
    IS its prior's box and no two overlap — but for ``overlaps`` pairs
    ``(a, b)``, where ``b``'s box is ``a``'s moved by a tenth of its
    width (IoU 0.82).  ``scores``: {(class, prior): score}; every other
    score is 0."""
    i = np.arange(priors_n)
    x1 = (i % 64) / 64.0
    y1 = (i // 64) / 32.0
    priors = np.stack([x1, y1, x1 + 0.01, y1 + 0.02], 1).astype(np.float32)
    for a, b in overlaps:
        priors[b] = priors[a] + np.float32([0.001, 0.0, 0.001, 0.0])
    variances = np.tile(np.float32([0.1, 0.1, 0.2, 0.2]), (priors_n, 1))
    conf = np.zeros((1, priors_n, classes), np.float32)
    for (cls, prior), score in scores.items():
        conf[0, prior, cls] = score
    loc = np.zeros((1, priors_n, 4), np.float32)
    return loc, conf, priors, variances


def _expected_rows(priors, rows, keep_topk):
    """(class, score, prior) triples → the (1, keep_topk, 6) answer."""
    out = np.zeros((1, keep_topk, 6), np.float32)
    out[..., 0] = -1.0
    for k, (cls, score, prior) in enumerate(rows):
        out[0, k] = [cls, score, *priors[prior]]
    return out


class TestFlatIndexOrder:
    """A prior's flat index is ``row * 128 + lane`` of the kernel's
    ``(rows, 128)`` tiles.  Every tie-break is stated on it, and nothing
    may depend on where a row or a register ends: hand-placed candidates,
    each case against the xla reference AND against the answer written
    out by hand."""

    PARAM = dict(n_classes=4, nms_topk=64, keep_topk=8)

    def _check(self, case, rows, **param):
        loc, conf, priors, variances = case
        p = DetectionOutputParam(**{**self.PARAM, **param})
        got = _fused(loc, conf, priors, variances, p)
        _assert_rows_match(got, _reference(loc, conf, priors, variances, p))
        _assert_rows_match(got, _expected_rows(priors, rows, p.keep_topk))

    def test_equal_scores_in_two_rows_and_two_classes(self):
        """The sweep: of two overlapping candidates with one score, in
        rows 0 and 3, the lower prior is popped first and suppresses
        the other.  The merge: equal scores in two classes come out
        class-major (class 2's prior 700 before class 3's prior 7), and
        within a class by prior (130, row 1, before 900, row 7)."""
        case = _placed(1300, {(1, 5): 0.9, (1, 389): 0.9,
                              (2, 700): 0.8, (3, 7): 0.8,
                              (2, 130): 0.7, (2, 900): 0.7},
                       overlaps=[(5, 389)])
        self._check(case, [(1, 0.9, 5), (2, 0.8, 700), (3, 0.8, 7),
                           (2, 0.7, 130), (2, 0.7, 900)])

    @pytest.mark.parametrize("keep_topk", [2, 3, 4, 5])
    def test_equal_scores_in_several_classes_at_the_cut(self, keep_topk):
        """Four candidates of one score in three classes where keep_topk
        ends among them: class-major, within a class by prior (class 2's
        10 before its 700, popped in that order into its list), and the
        cut takes exactly the first ``keep_topk``."""
        case = _placed(1300, {(1, 5): 0.9, (3, 40): 0.6, (2, 700): 0.6,
                              (1, 900): 0.6, (2, 10): 0.6, (3, 7): 0.3})
        rows = [(1, 0.9, 5), (1, 0.6, 900), (2, 0.6, 10), (2, 0.6, 700),
                (3, 0.6, 40), (3, 0.3, 7)]
        self._check(case, rows[:keep_topk], keep_topk=keep_topk)

    def test_higher_prior_wins_on_score_not_on_place(self):
        """The same pair with the higher prior scoring higher: it is
        kept and the lower one suppressed."""
        case = _placed(1300, {(1, 5): 0.8, (1, 389): 0.9},
                       overlaps=[(5, 389)])
        self._check(case, [(1, 0.9, 389)])

    @pytest.mark.parametrize("priors_n", PRIOR_COUNTS)
    def test_last_valid_prior_is_the_top_candidate(self, priors_n):
        """The last lane that holds a prior (the lanes after it are
        padding) pops first, and is found again by the merge."""
        last = priors_n - 1
        case = _placed(priors_n, {(1, last): 0.95, (1, 0): 0.5,
                                  (3, last): 0.6})
        self._check(case, [(1, 0.95, last), (3, 0.6, last), (1, 0.5, 0)])

    @pytest.mark.parametrize("first,second", [(127, 128), (128, 127),
                                              (1023, 1024), (1024, 1023)])
    def test_suppression_crosses_a_row_and_a_register(self, first, second):
        """A candidate in the last lane of a row suppresses its
        neighbour in the first lane of the next row (127 | 128), the
        same across two registers (1,023 | 1,024), and the other way
        round; a third candidate far away is untouched."""
        lo, hi = min(first, second), max(first, second)
        case = _placed(1300, {(2, first): 0.9, (2, second): 0.8,
                              (2, 640): 0.3}, overlaps=[(lo, hi)])
        self._check(case, [(2, 0.9, first), (2, 0.3, 640)])

    @pytest.mark.parametrize("conf_thresh", [0.0, -1.0])
    @pytest.mark.parametrize("priors_n", PRIOR_COUNTS)
    def test_padding_is_never_a_candidate(self, priors_n, conf_thresh):
        """With ``conf_thresh`` <= 0 a padding lane's score of 0 is over
        the threshold (at -1) or ties with real priors' (at 0): only the
        flat index keeps it out.  Three real candidates and ``nms_topk``
        larger than the priors, so every lane that counts as valid is
        popped: the answer holds the three, in order, and no box of a
        padding lane (all zeros)."""
        last = priors_n - 1
        case = _placed(priors_n, {(1, last): 0.4, (2, 3): 0.6,
                                  (3, 129): 0.5})
        self._check(case, [(2, 0.6, 3), (3, 0.5, 129), (1, 0.4, last)],
                    conf_thresh=conf_thresh, nms_topk=2048)

    @pytest.mark.parametrize("conf_thresh", [0.0, -1.0])
    def test_dense_conf_at_a_threshold_of_zero(self, conf_thresh):
        """Every real prior of every class is a candidate (dense
        untrained conf, all scores > 0): ``nms_topk`` pops a class, none
        of them a padding lane."""
        loc, conf, priors, variances = _inputs(3, priors_n=1300)
        p = DetectionOutputParam(**BASE, conf_thresh=conf_thresh)
        _assert_rows_match(_fused(loc, conf, priors, variances, p),
                           _reference(loc, conf, priors, variances, p))


class TestPoolSweep:
    """The sweep pops from ONE pool of eligible candidates — the first
    ``nms_topk`` by rank, each keep taking out of it what it suppresses —
    so every pop is a keep.  Each case bit for bit against the xla
    reference, and where the answer is known, against it written out."""

    def _check(self, case, rows=None, **param):
        p = DetectionOutputParam(**{"n_classes": 4, **param})
        got = _fused(*case, p)
        np.testing.assert_array_equal(got, _reference(*case, p))
        if rows is not None:
            _assert_rows_match(got, _expected_rows(case[2], rows,
                                                   p.keep_topk))

    def test_scores_tied_across_the_cut(self):
        """506 candidates of one class and nms_topk 400: 390 distinct
        scores, then 16 tied at 0.5 across rows and registers, then 100
        below.  The cut takes the tied ones with the lowest priors that
        fit (10 of 16), and counts one that a keep suppresses (401, by
        prior 5) in the rank: 902 does not move up in its place."""
        top = {(1, i): float(np.float32(0.9) - np.float32(i * 1e-4))
               for i in range(390)}
        tied = [1299, 1024, 1023, 400, 401, 900, 901, 902, 390, 391, 392,
                393, 394, 395, 1100, 1101]
        below = {(1, i): 0.3 for i in range(600, 700)}
        case = _placed(1300, {**top, **{(1, i): 0.5 for i in tied},
                              **below, (2, 7): 0.95},
                       overlaps=[(5, 401)])
        rows = ([(2, 0.95, 7)] + [(1, top[(1, i)], i) for i in range(390)]
                + [(1, 0.5, i) for i in (390, 391, 392, 393, 394, 395,
                                         400, 900, 901)])
        self._check(case, rows, nms_topk=400, keep_topk=420)

    @pytest.mark.parametrize("clip", [True, False])
    def test_zero_area_candidates(self, clip):
        """Priors past the picture's right edge clip to boxes of zero
        width (IoU 0 with themselves, and with everything): the pop takes
        each out by its own mask, the loop ends, and all are kept.
        Unclipped, the same priors are ordinary boxes."""
        case = _placed(1300, {(1, i): 0.5 + i * 1e-4 for i in range(12)})
        loc, conf, priors, variances = case
        priors = priors.copy()
        priors[:6, [0, 2]] += 1.5           # six boxes past x = 1
        priors[6:9] = priors[6]             # three identical ones
        self._check((loc, conf, priors, variances), clip_boxes=clip,
                    keep_topk=16)

    @pytest.mark.parametrize("n", [300, 450])
    def test_one_box_suppresses_all_the_others(self, n):
        """Every candidate of the class overlaps the best one: one keep,
        under the cut and over it."""
        case = _placed(1300, {**{(1, i): 0.2 + i * 1e-4 for i in range(n)},
                              (3, 1299): 0.1},
                       overlaps=[(n - 1, i) for i in range(n - 1)])
        self._check(case, [(1, 0.2 + (n - 1) * 1e-4, n - 1),
                           (3, 0.1, 1299)], nms_topk=400, keep_topk=8)

    @pytest.mark.parametrize("priors_n", PRIOR_COUNTS)
    def test_select_rung_counts_pops_equal_to_keeps(self, priors_n):
        """The ``select`` prefix's column 1 is the sweep's trip count, a
        picture: equal to the keeps of every class (the xla reference's
        answer with room for all of them), and fewer than the ranked
        candidates a sweep that also pops discards would take."""
        from analytics_zoo_tpu.ops.pallas_detout import (
            fused_detection_output)

        loc, conf, priors, variances = _inputs(
            4, priors_n=priors_n, bg_bias=4.0, hot_frac=0.1)
        p = DetectionOutputParam(**BASE)
        probe = np.asarray(fused_detection_output(
            loc, conf, priors, variances, param=p, interpret=True,
            stage="select"))
        pops = probe[:, 0, 1]
        assert (probe[..., 1] == pops[:, None]).all()
        every = dataclasses.replace(p, keep_topk=5 * p.nms_topk)
        keeps = (_reference(loc, conf, priors, variances, every)[..., 0]
                 >= 0).sum(axis=1)
        np.testing.assert_array_equal(pops, keeps)
        ranked = np.minimum((np.asarray(conf)[..., 1:] > p.conf_thresh)
                            .sum(axis=1), p.nms_topk).sum(axis=1)
        assert (pops < ranked).all()


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
        """A per-prior vector is a dense (P_pad / 128, 128) tile, P padded
        to whole (8, 128) registers: the sweep's scratch (the decoded
        boxes and ONE pool of eligible candidates) and the input blocks
        cost their logical bytes.  The five keep lists (scores,
        box corners) hold min(nms_topk, P) slots a foreground class,
        padded to 128 lanes and the class axis to 8 sublanes; the
        (keep_topk, 6) output block pads 6 lanes to 128.  Keeps held as
        one prior tile a class cost 1.7 MiB at SSD300 and 4.3 MiB at
        SSD512; the one-sublane layout before that 9.1 and 24.9 MiB."""
        from analytics_zoo_tpu.ops import vmem
        from analytics_zoo_tpu.ops.pallas_detout import fused_vmem_bytes

        small = fused_vmem_bytes(160, 6, 32)
        ssd300 = fused_vmem_bytes(8732, 21, 200)
        ssd512 = fused_vmem_bytes(24564, 21, 200)
        assert small < ssd300 < ssd512
        out = 2 * 200 * 128 * 4              # two (200, 6) blocks, padded
        # 4 box tiles + 1 pool of scratch; 2 score, 2 x 4 loc, 4 prior
        # and 4 variance tiles of input blocks
        vectors = (4 + 1) + (2 + 8 + 4 + 4)
        # min(400, P) = 400 slots -> 4 rows of 128; 20 classes -> 24
        lists = 5 * 4 * 24 * 128 * 4
        assert ssd300 == vectors * 9216 * 4 + lists + out   # 72 rows
        assert ssd512 == vectors * 24576 * 4 + lists + out  # 192 rows
        # 160 priors: 2 rows of slots, 5 classes -> 8
        assert small == ((5 + 18) * 1024 * 4 + 5 * 2 * 8 * 128 * 4
                         + 2 * 32 * 128 * 4)
        # a list never holds more slots than there are priors
        assert fused_vmem_bytes(160, 6, 32, nms_topk=2048) == small
        assert fused_vmem_bytes(24564, 21, 200, nms_topk=130) == (
            ssd512 - lists // 2)
        assert 1.23 < ssd300 / 2**20 < 1.24
        assert 2.58 < ssd512 / 2**20 < 2.59
        assert vmem.limit_bytes(ssd512) < 13 * 2**20
        assert vmem.fits(ssd300) and vmem.fits(ssd512)
        # four times SSD512's priors: 81 classes fit, and so do 201,
        # which a prior tile of keeps a class did not
        assert vmem.fits(fused_vmem_bytes(4 * 24564, 81, 200))
        assert vmem.fits(fused_vmem_bytes(4 * 24564, 201, 200))

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
                             [(300, 8), (300, 32), (512, 8),
                              (512, 64)])
    def test_compiled_kernel_at_zoo_geometries(self, resolution, batch):
        """The geometries serving runs: SSD300 at the runtime's and the
        trainer's batch, SSD512 at 8 and at the benchmark's serve cell's
        64 — default DetectionOutputParam
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
    @pytest.mark.parametrize("keep_topk", [32, 50])
    def test_compiled_kernel_matches_reference(self, keep_topk):
        from analytics_zoo_tpu.ops.pallas_detout import (
            fused_detection_output)

        loc, conf, priors, variances = _inputs(0, bg_bias=7.0,
                                               hot_frac=0.05)
        p = DetectionOutputParam(**{**BASE, "keep_topk": keep_topk})
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
