"""Benchmark runner — one JSON line per BASELINE.json metric; the LAST
line is the headline (SSD300 train images/sec/chip) for the driver.

Unlike the round-1 harness, every measurement here is end-to-end honest:

* **ssd300_train** feeds real JPEG-encoded images through the *full*
  canonical augmentation chain (``load_train_set``: decode → RoiNormalize
  → ColorJitter → Expand → RandomSampler → Resize → HFlip → MatToFloats,
  reference ``ssd/Utils.scala:56``) with ``ParallelTransformer`` host
  workers + ``device_prefetch`` double-buffering, into the bf16
  mixed-precision jitted train step.  HOT LOOP #1 (SURVEY.md §3.1) is
  inside the measurement.
* **ssd300_serve** measures the serving path — decode + preprocess +
  forward + in-graph DetectionOutput (decode/NMS/topk) + rescale —
  via ``SSDPredictor.predict`` (reference ``SSDPredictor.scala:54``).
* **ds2** measures utterances/sec through the whole ASR pipeline:
  segment → host FFT/mel featurization → batched forward → CTC greedy
  decode → (id,seq) re-join (reference ``InferenceEvaluate.scala`` wall
  time; the reference ran this batch-1 inside a DataFrame udf).
* **detection_output pallas vs xla**: correctness + microbench of the
  Pallas NMS kernel on the real chip (reference ``Nms.scala:131``).
* **MFU**: achieved model TFLOP/s from XLA's compiled cost analysis,
  against the chip's advertised bf16 peak (v5e ≈ 197 TFLOP/s).

``vs_baseline`` anchors: the reference publishes NO absolute numbers
(SURVEY.md §6).  For the headline we keep the round-1 *labeled estimate*:
the SSD README's 4×28-core Xeon train cluster credited at an optimistic
~0.5 img/s/core → 56 img/s total.  Lines without a defensible anchor set
``vs_baseline`` to null.

One process per phase: a TPU belongs to one process at a time, so the
parent never imports jax and runs its phase children strictly one after
another (see ``main``).  Every line a phase emits carries the platform,
``device_kind`` and device count JAX reported; MFU lines need a device
listed in ``PEAK_TFLOPS`` and fail on any other.

Usage: ``python bench.py [--quick] [--skip ssd_train,...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import warnings


# Labeled estimate, NOT a published number: 4 executors x 28 cores x
# ~0.5 img/s/core (reference pipeline/ssd/README.md cluster shape).
REFERENCE_ANCHOR_IMAGES_PER_SEC = 56.0

# advertised bf16 peak matmul throughput per chip
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,            # v5e
    "TPU v5e": 197.0,
    "TPU v4": 275.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,            # v6e / Trillium
}


def _median(xs):
    s = sorted(xs)
    n = len(s)
    if n % 2 == 1:
        return s[n // 2]
    return 0.5 * (s[n // 2 - 1] + s[n // 2])


def _interleaved_ab(fn_a, fn_b, windows: int = 3, on_pair=None):
    """Drift-cancelling A/B: ``windows`` pairs in ONE process, the pair
    order ALTERNATING each round (a host or link that drifts over the
    run would otherwise bias whichever side always runs later), compared by
    the MEDIAN of per-pair b/a ratios (cancels the common drift within a
    pair).  Returns (a_rates, b_rates, ratios)."""
    a_rates, b_rates, ratios = [], [], []
    for i in range(windows):
        pair = (fn_a, fn_b) if i % 2 == 0 else (fn_b, fn_a)
        x = pair[0]()
        y = pair[1]()
        a, b = (x, y) if i % 2 == 0 else (y, x)
        a_rates.append(a)
        b_rates.append(b)
        ratios.append(b / max(a, 1e-9))
        if on_pair is not None:
            on_pair(i, a, b)
    return a_rates, b_rates, ratios


def _flops_per_record(step, state, dev_batches, recs):
    """Blended FLOPs per processed record: XLA's compiled FLOP count per
    pinned batch SHAPE (tools/profile_mfu.flops_of — the shared cost
    model, not re-derived), weighted by how many batches run at that
    shape.  Basis of the per-window ``mfu_est`` readouts."""
    from tools.profile_mfu import flops_of

    by_shape = {}
    for b in dev_batches:
        x = b["input"][0] if isinstance(b["input"], tuple) else b["input"]
        cnt, ex = by_shape.get(x.shape, (0, b))
        by_shape[x.shape] = (cnt + 1, ex)
    fl = sum(flops_of(step, state, ex, 1.0) * cnt
             for cnt, ex in by_shape.values())
    return fl / max(recs, 1)


def _peak_tflops(kind: str) -> float:
    """Advertised bf16 peak of ``kind``.  An unknown device is an error:
    an MFU against some other chip's peak is not a measurement."""
    if kind not in PEAK_TFLOPS:
        raise RuntimeError(
            f"no bf16 peak recorded for device_kind {kind!r} — MFU lines "
            f"need a TPU listed in PEAK_TFLOPS (add it with its source)")
    return PEAK_TFLOPS[kind]


# platform / device_kind / device_count as JAX reports them, stamped on
# every line a phase emits so no number is read without the device it came
# from.  Set by main() in the process that runs phases; None in the phase
# parent, which never imports jax (its only lines are exit records).
_DEVICE = None

# every emitted line is also appended here (jsonl) so exploratory sweeps
# accumulate under bench_artifacts/ instead of littering the repo root
# with per-run BENCH_rNN_*.jsonl files; only the canonical per-round
# BENCH_rNN.json artifacts live at top level.  Set by --sweep-log.
_SWEEP_LOG = None


def _emit(metric: str, value: float, unit: str, vs_baseline, **extra):
    line = {"metric": metric, "value": round(float(value), 3), "unit": unit,
            "vs_baseline": (round(float(vs_baseline), 3)
                            if vs_baseline is not None else None)}
    line.update(extra)
    line.update(_DEVICE or {})
    print(json.dumps(line), flush=True)
    if _SWEEP_LOG:
        try:
            os.makedirs(os.path.dirname(_SWEEP_LOG) or ".", exist_ok=True)
            with open(_SWEEP_LOG, "a") as f:
                f.write(json.dumps(line) + "\n")
        except OSError:
            pass                      # the log is a convenience, never fatal
    return line


def _flops_per_step(step_fn, *example_args) -> float:
    """XLA's own FLOP count for the compiled train step (fwd+bwd+update).
    A step that fails to lower or reports no FLOPs raises — a silent 0.0
    would print as an MFU of zero."""
    ca = step_fn.lower(*example_args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def bench_ssd_train(args, mesh, shard_pattern, device_aug: bool):
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import device_prefetch
    from analytics_zoo_tpu.models import SSDVgg, build_priors
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (
        SGD, create_train_state, make_train_step, replicate)
    from analytics_zoo_tpu.pipelines.ssd import (
        PreProcessParam, load_train_set, load_train_set_device)

    n_chips = jax.device_count()
    res = args.res
    model = Model(SSDVgg(num_classes=args.classes, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    priors, variances = build_priors(model.module.config)
    criterion = MultiBoxLoss(priors, variances,
                             MultiBoxLossParam(n_classes=args.classes))
    optim = SGD(1e-3, momentum=0.9)
    state = replicate(create_train_state(model, optim), mesh)

    # bench records are exactly res×res, so a tight staging canvas is
    # lossless and cuts host→device bytes ~2.8× vs the 512 default;
    # the yuv420 wire format halves the remaining bytes again (the
    # e2e path is input-link-bound, not host-CPU-bound — measured:
    # the host chain alone does ~700 img/s single-threaded)
    # wire_format/pack_staging only exist on the device-aug path; the
    # host chain would ignore (and now warns on) them, so pin bgr there
    param = PreProcessParam(batch_size=args.batch, resolution=res,
                            num_workers=args.workers, max_gt=8,
                            canvas_size=((res + 7) // 8) * 8,
                            wire_format=(args.wire_format if device_aug
                                         else "bgr"),
                            pack_staging=device_aug and not args.no_pack)
    if device_aug:
        dataset, augment = load_train_set_device(shard_pattern, param)
    else:
        dataset, augment = load_train_set(shard_pattern, param), None

    # no skip_loss_above guard: it is fine-tuning semantics and would mask
    # every update of this from-scratch model (loss starts ~100 > 50),
    # making the reported final_loss a frozen artifact.  The device-side
    # augmentation is FUSED into the step — one dispatch per iteration.
    step = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype,
                           device_transform=augment)

    def batches():   # epoch-looping stream, prefetched to device
        while True:
            yield from device_prefetch(iter(dataset), mesh)

    # JAX dispatch returns before the device finishes, so every timed
    # window ends with a scalar READBACK (np.asarray of the last loss):
    # the host clock stops only after everything queued before it has
    # completed.  The end-to-end window runs first, the compute-only
    # window (same device-resident batch re-fed) after it.
    import numpy as _np

    stream = batches()
    first = next(stream)
    state, metrics = step(state, first, 1.0)      # compile
    for _ in range(max(args.warmup - 1, 0)):
        state, metrics = step(state, next(stream), 1.0)
    jax.block_until_ready(metrics["loss"])        # best-effort warm drain

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step(state, next(stream), 1.0)
    loss = float(_np.asarray(metrics["loss"]))    # fence: forces the drain
    dt = time.perf_counter() - t0

    images_per_sec = args.batch * args.steps / dt
    per_chip = images_per_sec / max(n_chips, 1)

    dt_step = None
    if device_aug:
        # compute-only ceiling: a SEPARATE unfused step on the
        # pre-augmented batch — model fwd+bwd+update only, matching the
        # metric's "input pipeline excluded" claim (the fused e2e step
        # above includes the on-device augmentation).  Same device-
        # resident batch re-fed: no host↔device traffic inside the
        # window.
        core_step = make_train_step(model.module, criterion, optim,
                                    mesh=mesh,
                                    compute_dtype=args.compute_dtype)
        first_aug = augment(first)
        state, metrics = core_step(state, first_aug, 1.0)   # compile
        jax.block_until_ready(metrics["loss"])
        flops = _flops_per_step(core_step, state, first_aug, 1.0)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, metrics = core_step(state, first_aug, 1.0)
        float(_np.asarray(metrics["loss"]))       # fence
        dt_step = time.perf_counter() - t0
        step_per_chip = args.batch * args.steps / dt_step / max(n_chips, 1)
        _emit(f"ssd{res}_train_step_images_per_sec_per_chip",
              step_per_chip, "images/sec/chip",
              None, batch=args.batch,
              note="device step only (batch re-fed) — input pipeline "
                   "excluded")
        kind = jax.devices()[0].device_kind
        peak = _peak_tflops(kind)
        if flops > 0:
            tflops = flops / (dt_step / args.steps) / 1e12 / max(n_chips, 1)
            _emit(f"ssd{res}_train_model_tflops_per_chip", tflops,
                  "TFLOP/s/chip", tflops / peak,
                  mfu=round(tflops / peak, 4),
                  peak_tflops=peak, device_kind=kind, batch=args.batch,
                  note="fwd+bwd+update FLOPs from XLA compiled "
                       "cost_analysis over the compute-only step time; "
                       "vs_baseline = MFU against advertised bf16 peak")
        _emit(f"ssd{res}_train_host_bound_fraction",
              max(0.0, 1.0 - (dt_step / dt)), "fraction", None,
              host_cpus=os.cpu_count(),
              note="1 - step_time/e2e_time with device-side augmentation "
                   "(this VM exposes few host cores; a real v5e TPU-VM "
                   "host has ~112)")
    else:
        _emit(f"ssd{res}_train_hostaug_images_per_sec_per_chip", per_chip,
              "images/sec/chip", None, host_cpus=os.cpu_count(),
              note="reference-style host (OpenCV) augmentation chain "
                   "end-to-end — compare with the device-aug headline")
    return per_chip, images_per_sec, loss


def bench_ssd_serve(args, mesh, records, res=None):
    import jax

    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg
    from analytics_zoo_tpu.ops import DetectionOutputParam
    from analytics_zoo_tpu.ops.detection_output import resolve_backend
    from analytics_zoo_tpu.pipelines.ssd import PreProcessParam, SSDPredictor

    res = res or args.res
    # 512 serve: forward-only fits a bigger batch than 512 TRAIN does,
    # but 2.9x the pixels per image still means halving vs the 300 batch
    batch = args.batch if res == args.res else max(args.batch // 2, 1)
    model = Model(SSDVgg(num_classes=args.classes, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    param = PreProcessParam(batch_size=batch, resolution=res,
                            num_workers=args.workers,
                            wire_format=args.wire_format)
    post = DetectionOutputParam(n_classes=args.classes, backend="auto")
    predictor = SSDPredictor(model, param, post=post,
                             compute_dtype=args.compute_dtype)

    def _time_predict(p):
        warm = p.predict(records[:batch])               # compile
        assert len(warm) == batch
        t0 = time.perf_counter()
        out = p.predict(records)
        dt = time.perf_counter() - t0
        assert len(out) == len(records)
        return len(records) / dt / max(jax.device_count(), 1)

    per_chip = _time_predict(predictor)
    _emit(f"ssd{res}_serve_images_per_sec_per_chip", per_chip,
          "images/sec/chip", None,
          nms_backend=resolve_backend(
              post, predictor._priors.shape[0], args.classes).name,
          batch=batch, wire_format=args.wire_format,
          note="decode+preprocess+forward+DetectionOutput+rescale; "
               "no published reference anchor")

    # int8 COMPUTE serving (utils.quantize compute="int8"): ~4x smaller
    # params in HBM AND real int8 convolutions on the MXU; both
    # predictors stay live so their windows can interleave (SSD-VGG
    # fp32+int8 together is ~125 MB — nowhere near HBM pressure; the 4x
    # artifact-size claim is pinned separately by tests/test_quantize.py).
    q_predictor = SSDPredictor(
        model, param,
        post=DetectionOutputParam(n_classes=args.classes, backend="auto"),
        compute_dtype=args.compute_dtype, quantize="int8")
    # int8-vs-fp ratio via _interleaved_ab: a sequential pair charges
    # whichever side runs second for any drift over the run
    fp_rates, q_rates, ratios = _interleaved_ab(
        lambda: _time_predict(predictor), lambda: _time_predict(q_predictor))

    # DEVICE-PROGRAM-only comparison: the e2e predict above includes
    # JPEG decode + preprocess + transfer (decode-bound on a 1-core
    # host), which dilutes the conv-level int8 gain — time the fused
    # forward+DetectionOutput program alone on a RESIDENT batch
    import numpy as _np

    x_dev = jax.device_put(_np.random.RandomState(0).rand(
        batch, res, res, 3).astype(_np.float32))

    def _time_device(p, iters=10):
        o = p.detect_normalized(x_dev)
        _np.asarray(o)                           # warm + fence
        t0 = time.perf_counter()
        for _ in range(iters):
            o = p.detect_normalized(x_dev)
        _np.asarray(o)                           # fence
        return batch * iters / (time.perf_counter() - t0)

    dfp, dq, dratio = _interleaved_ab(lambda: _time_device(predictor),
                                      lambda: _time_device(q_predictor))
    _emit(f"ssd{res}_serve_int8_device_speedup", _median(dratio), "x",
          None, fp_images_per_sec_one_device=round(_median(dfp), 1),
          int8_images_per_sec_one_device=round(_median(dq), 1),
          note="fused forward+DetectionOutput on a SINGLE-device resident "
               "batch (no decode/transfer; unlike the per-chip e2e lines "
               "above): the int8 compute gain undiluted by the host-bound "
               "e2e serve path")

    per_chip_q = _median(q_rates)
    return _emit(f"ssd{res}_serve_int8_images_per_sec_per_chip", per_chip_q,
                 "images/sec/chip", _median(ratios),
                 fp_windows=[round(x, 2) for x in fp_rates],
                 int8_windows=[round(x, 2) for x in q_rates],
                 note="int8 COMPUTE serving (dynamic activation quant + "
                      "int8xint8->int32 convs on the MXU, r4; was "
                      "weight-only dequant in r3); vs_baseline = median "
                      "of per-pair int8/fp ratios over interleaved "
                      "windows with alternating order (drift-cancelling)")


def bench_ds2_train(args, mesh):
    """DS2 CTC TRAINING throughput (records/s) + MFU — VERDICT r3 item 3:
    training existed only as an ACCURACY.md aside.  Runs BOTH the
    TPU-friendly hidden=1024 geometry and the reference-parity 1760
    (``models/deepspeech2.py:24``: the reference's serialized DS2 is
    hidden 1760).  The batch featurization (Windower → DFTSpecgram →
    MelFilterBank) runs ON DEVICE fused into the train step
    (``make_featurizer_device``), so the measurement covers raw samples →
    update, not just the RNN."""
    import numpy as np
    import jax

    from analytics_zoo_tpu.core.criterion import CTCCriterion
    from analytics_zoo_tpu.parallel import (Adam, create_train_state,
                                            make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.deepspeech2 import make_ds2_model
    from analytics_zoo_tpu.transform.audio.featurize import (
        WINDOW_SIZE, WINDOW_STRIDE, make_featurizer_device)

    sec = args.ds2_seconds
    S = 16000 * sec
    n_frames = (S - WINDOW_SIZE) // WINDOW_STRIDE + 1
    n_dev = max(jax.device_count(), 1)
    # training batches bigger than the inference default: the scan-RNN
    # step is dispatch/latency-bound at batch 8 — batch 32 measured
    # 2.4-2.5x the records/s at both geometries (BENCH_r04_supplement)
    B = args.ds2_train_batch if args.ds2_train_batch else 4 * args.ds2_batch
    B = ((B + n_dev - 1) // n_dev) * n_dev                # shards over data
    rng = np.random.RandomState(0)
    samples = rng.randn(B, S).astype(np.float32) * 0.1
    labels = rng.randint(1, 29, (B, 50)).astype(np.int32)
    batch = {"samples": samples,
             "n_valid": np.full((B,), S, np.int32),
             "labels": labels,
             "label_mask": np.ones((B, 50), np.float32)}
    featurize = make_featurizer_device(S, utt_length=n_frames)
    ctc = CTCCriterion(blank_id=0)

    def device_transform(b):
        return {"input": featurize(b["samples"], b["n_valid"]),
                "labels": b["labels"], "label_mask": b["label_mask"]}

    def criterion(log_probs, b):
        return ctc(log_probs, b["labels"], label_mask=b.get("label_mask"))

    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    n_chips = max(jax.device_count(), 1)
    steps = max(4, args.steps // 3)
    last = None
    for hidden in (args.ds2_hidden, 1760) if not args.quick \
            else (args.ds2_hidden,):
        # make_ds2_model already returns a BUILT core.Model
        model = make_ds2_model(hidden=hidden, n_rnn_layers=args.ds2_layers,
                               utt_length=n_frames)
        optim = Adam(3e-4)
        state = replicate(create_train_state(model, optim), mesh)
        step = make_train_step(model.module, criterion, optim, mesh=mesh,
                               compute_dtype=args.compute_dtype,
                               device_transform=device_transform)
        dev_batch = mesh_lib.shard_batch(batch, mesh)
        state, m = step(state, dev_batch, 1.0)            # compile
        # READBACK-fenced warmup: queued work left over from the compile
        # step must not land in the first timed window
        float(np.asarray(m["loss"]))
        flops = _flops_per_step(step, state, dev_batch, 1.0)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, dev_batch, 1.0)
        loss = float(np.asarray(m["loss"]))               # fence
        dt = time.perf_counter() - t0
        rec_s = B * steps / dt / n_chips
        extra = {}
        if flops > 0 and peak:
            tflops = flops / (dt / steps) / 1e12 / n_chips
            extra = {"model_tflops_per_chip": round(tflops, 2),
                     "mfu": round(tflops / peak, 4), "peak_tflops": peak}
        last = _emit(
            f"ds2_train_h{hidden}_records_per_sec_per_chip", rec_s,
            "records/sec/chip", None, batch=B,
            utterance_seconds=sec, hidden=hidden, layers=args.ds2_layers,
            final_loss=round(loss, 3), device_kind=kind, **extra,
            note="raw samples → on-device featurize → BiRNN → CTC → "
                 "update, one fused jit step; hidden=1760 is the "
                 "reference's serialized DS2 geometry")
    return last


def _ds2_ragged_lengths(n_records: int, n_frames_max: int, seed: int = 42):
    """Seeded realistic utterance-length distribution (frames): lognormal
    duration fractions with median ≈ 0.27 of the segment cap and a long
    tail reaching it — the VAD-split-conversational-speech shape (most
    utterances a few seconds, the segmenter cap rarely hit), clipped so
    every record survives the conv front-end."""
    import numpy as np

    rng = np.random.RandomState(seed)
    frac = np.clip(rng.lognormal(mean=-1.3, sigma=0.7, size=n_records),
                   0.08, 1.0)
    return np.clip((frac * n_frames_max).astype(np.int32), 16, n_frames_max)


def _ds2_ragged_workload(args, n_max):
    """Seeded ragged DS2 workload SHARED by the ds2_ragged and
    ds2_persistent phases (one synthesis = the two A/Bs measure the
    same distribution): lognormal lengths, random mel features/labels,
    quantile bucket edges, and the production ``BucketBatcher``
    assembly with ``(x, n_frames)`` inputs at its drop_remainder=True
    default.  Returns ``(B, lengths, feats, labels, lab_mask, edges,
    bucketed_batches)``."""
    import numpy as np
    import jax

    from analytics_zoo_tpu.data.bucket import BucketBatcher

    n_dev = max(jax.device_count(), 1)
    B = args.ds2_train_batch if args.ds2_train_batch else 4 * args.ds2_batch
    B = ((B + n_dev - 1) // n_dev) * n_dev
    n_records = B * 16
    lengths = _ds2_ragged_lengths(n_records, n_max)
    L = 20
    rng = np.random.RandomState(0)
    feats = [rng.randn(int(n), 13).astype(np.float32) * 0.1
             for n in lengths]
    labels = rng.randint(1, 29, (n_records, L)).astype(np.int32)
    lab_mask = np.ones((n_records, L), np.float32)
    # quantile-derived pinned bucket edges (the jit cache warms once per
    # edge); last edge = the max so nothing truncates
    qs = np.quantile(lengths, np.linspace(1.0 / args.ds2_buckets, 1.0,
                                          args.ds2_buckets))
    edges = sorted(set(int(np.ceil(q)) for q in qs) | {int(lengths.max())})

    def sample_stream():
        for i in range(n_records):
            yield {"input": feats[i], "n_frames": np.int32(lengths[i]),
                   "labels": labels[i], "label_mask": lab_mask[i]}

    batches = []
    for b in BucketBatcher(B, edges).apply_iter(sample_stream()):
        batches.append({"input": (b["input"], b["n_frames"]),
                        "n_frames": b["n_frames"],
                        "labels": b["labels"],
                        "label_mask": b["label_mask"]})
    return B, lengths, feats, labels, lab_mask, edges, batches


def bench_ds2_ragged(args, mesh):
    """DS2 RNN training fast path A/B on a RAGGED-length workload —
    the bench_ds2_train honesty fix: that phase re-feeds ONE resident
    uniform-length batch, which cannot show padding waste.  Here a
    seeded length distribution (``_ds2_ragged_lengths``) is fed through
    both training disciplines at EQUAL geometry:

    * **old**: legacy per-step scan body (``rnn_hoist=False``), every
      record padded to the max utterance length, padding scanned as if
      real — the previous pipeline's behavior;
    * **fastpath**: hoisted projections + time-blocked scan
      (``rnn_block``), records batched into quantile-derived
      length buckets (``data.bucket.BucketBatcher``) with per-row
      ``n_frames`` masking and a masked CTC loss.

    Interleaved drift-cancelling windows (``_interleaved_ab``), one
    line per path per geometry (h=1024 and the reference-parity 1760),
    each carrying ``padding_efficiency`` (valid/padded frames) and the
    per-window rates.  Features are pre-staged device-resident random
    mels on BOTH sides: the phase isolates the train-step cost, the
    host featurize/input story is PR-2's host_wall phase."""
    import numpy as np
    import jax

    from analytics_zoo_tpu.data.bucket import padding_efficiency
    from analytics_zoo_tpu.parallel import (Adam, create_train_state,
                                            make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.deepspeech2 import (
        ds2_ctc_criterion, make_ds2_model)
    from analytics_zoo_tpu.transform.audio.featurize import (
        WINDOW_SIZE, WINDOW_STRIDE)

    sec = args.ds2_seconds
    n_max = (16000 * sec - WINDOW_SIZE) // WINDOW_STRIDE + 1
    n_dev = max(jax.device_count(), 1)
    B, lengths, feats, labels, lab_mask, edges, new_batches = \
        _ds2_ragged_workload(args, n_max)
    n_records = len(lengths)

    # old discipline: stream order, everything padded to n_max; the
    # fastpath side is the shared workload's REAL BucketBatcher
    # assembly at its production default drop_remainder=True
    # (partially-filled buckets at end of stream are dropped and
    # counted — a thin partial batch costs nearly a full batch's wall
    # time, and the training pipeline's uniform-path Batcher drops
    # remainders too)
    old_batches = []
    for s in range(0, n_records, B):
        x = np.zeros((B, n_max, 13), np.float32)
        for j in range(B):
            x[j, :lengths[s + j]] = feats[s + j]
        old_batches.append({"input": x, "labels": labels[s:s + B],
                            "label_mask": lab_mask[s:s + B]})
    old_eff = padding_efficiency(lengths, n_max)

    new_padded = sum(b["input"][0].shape[0] * b["input"][0].shape[1]
                     for b in new_batches)
    new_valid = sum(int(b["n_frames"].sum()) for b in new_batches)
    new_eff = new_valid / max(new_padded, 1)
    new_records = sum(b["n_frames"].shape[0] for b in new_batches)
    dropped = n_records - new_records

    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    # blended-MFU estimate basis: the device's own advertised peak
    mfu_peak, mfu_basis = peak, "device_peak"
    n_chips = max(jax.device_count(), 1)
    reps = max(1, max(4, args.steps // 3) // max(len(old_batches), 1))
    criterion = ds2_ctc_criterion()
    last = None
    for hidden in (args.ds2_hidden, 1760) if not args.quick \
            else (args.ds2_hidden,):

        def build(hoist):
            model = make_ds2_model(hidden=hidden,
                                   n_rnn_layers=args.ds2_layers,
                                   utt_length=n_max, rnn_hoist=hoist,
                                   rnn_block=args.ds2_block)
            optim = Adam(3e-4)
            state = replicate(create_train_state(model, optim), mesh)
            step = make_train_step(model.module, criterion, optim,
                                   mesh=mesh,
                                   compute_dtype=args.compute_dtype)
            return state, step

        def stage(batches):
            return [mesh_lib.shard_batch(b, mesh) for b in batches]

        sides = {}
        side_fpr = {}                       # FLOPs per processed record
        for name, hoist, host_batches in (
                ("old", False, old_batches),
                ("fastpath", True, new_batches)):
            state, step = build(hoist)
            dev = stage(host_batches)
            for b in dev:                      # compile each pinned shape
                state, m = step(state, b, 1.0)
            float(np.asarray(m["loss"]))       # readback-fenced warmup
            recs = sum(_b["labels"].shape[0] for _b in host_batches)
            side_fpr[name] = _flops_per_record(step, state, dev, recs)
            hold = {"state": state}            # step donates its input
            #                                    state; thread it across
            #                                    windows, never reuse it

            def run(hold=hold, step=step, dev=dev, recs=recs):
                t0 = time.perf_counter()
                m = None
                s = hold["state"]
                for _ in range(reps):
                    for b in dev:
                        s, m = step(s, b, 1.0)
                hold["state"] = s
                loss = float(np.asarray(m["loss"]))   # fence
                dt = time.perf_counter() - t0
                run.loss = loss
                return recs * reps / dt / n_chips

            sides[name] = run

        o_rates, f_rates, ratios = _interleaved_ab(sides["old"],
                                                   sides["fastpath"])

        def mfu_of(rate, name):
            return rate * side_fpr[name] / (mfu_peak * 1e12)

        extra = {}
        if peak:
            extra["peak_tflops"] = peak
        _emit(f"ds2_ragged_h{hidden}_old_records_per_sec_per_chip",
              _median(o_rates), "records/sec/chip", None, batch=B,
              hidden=hidden, layers=args.ds2_layers,
              utterance_seconds=sec, padding_efficiency=round(old_eff, 4),
              records=n_records,
              windows=[round(r, 3) for r in o_rates],
              mfu_est=round(mfu_of(_median(o_rates), "old"), 5),
              mfu_est_windows=[round(mfu_of(r, "old"), 5)
                               for r in o_rates],
              flops_per_record_gflop=round(side_fpr["old"] / 1e9, 3),
              mfu_basis=mfu_basis,
              note="legacy per-step scan, all records padded to the max "
                   "length (previous pipeline discipline); device-"
                   "resident pre-featurized batches; mfu_est = rate x "
                   "XLA-counted FLOPs/record / peak (basis recorded)")
        last = _emit(
            f"ds2_ragged_h{hidden}_fastpath_records_per_sec_per_chip",
            _median(f_rates), "records/sec/chip",
            _median(ratios), batch=B, hidden=hidden,
            layers=args.ds2_layers, utterance_seconds=sec,
            padding_efficiency=round(new_eff, 4),
            bucket_edges=edges, block_size=args.ds2_block,
            records=new_records, dropped_remainder_records=dropped,
            windows=[round(r, 3) for r in f_rates],
            old_windows=[round(r, 3) for r in o_rates],
            ratio_windows=[round(r, 3) for r in ratios],
            mfu_est=round(mfu_of(_median(f_rates), "fastpath"), 5),
            mfu_est_windows=[round(mfu_of(r, "fastpath"), 5)
                             for r in f_rates],
            flops_per_record_gflop=round(side_fpr["fastpath"] / 1e9, 3),
            mfu_basis=mfu_basis,
            device_kind=kind, **extra,
            note="hoisted+blocked scan, quantile length buckets "
                 "(production drop_remainder=True; dropped records "
                 "counted, rate is per PROCESSED record), n_frames-"
                 "masked BiRNN + masked CTC; vs_baseline = median "
                 "per-pair fastpath/old records-per-sec ratio, "
                 "interleaved windows, equal geometry, same seeded "
                 "length distribution; mfu_est = rate x XLA-counted "
                 "FLOPs/record / peak (the blended estimate "
                 "docs/MFU_CEILING.md reasons in; basis recorded)")
    return last


def bench_ds2_persistent(args, mesh):
    """Persistent-RNN kernel A/B (ISSUE 6, extended by ISSUE 13):
    ``rnn_engine='blocked'`` vs ``rnn_engine='pallas'`` at EQUAL
    geometry — same seeded ragged length distribution, same quantile
    buckets, same n_frames masking and masked CTC on both sides; the
    ONLY variable is the recurrence engine.  TWO sub-phases per hidden
    size, each its own interleaved drift-cancelling A/B:

    * **fwd** — the forward program only (jitted masked BiRNN forward
      to a scalar fence): the r7 residency story.
    * **train** — the full train step (fwd+bwd+Adam update): since r10
      the pallas side's backward is the TRANSPOSED persistent kernel
      (reversed time grid, W/Wᵀ VMEM-resident, fused dW accumulation)
      instead of the recompute-through-scan vjp — the grad-dominated
      pass the ≈B/128 ceiling was derived for.

    ``engine_fallback`` is recorded **per pass per line** (the budget
    warning names which pass overflowed): a fallen-back backward must
    not bank a scan-vs-scan ratio unnoticed.  Every line carries the
    achieved-intensity readout for its pass — the h2h term's FLOP/byte
    under each engine's weight-streaming discipline (re-streamed per
    step vs loaded once per sequence; the backward moves 2× the
    forward's h2h FLOPs against W *and* Wᵀ, so its persistent/blocked
    intensity RATIO is the forward's T′) against the v5e ridge of ~240,
    plus a blended mfu_est from XLA's compiled FLOP count.

    On a CPU backend both kernels run interpret-mode (discharged to
    XLA): the A/B then banks SCHEDULE parity/overhead, not the HBM
    term — weight residency only pays on a real TPU.  The backend is
    recorded on every line.  ``--ds2-persistent-out`` additionally
    banks the phase's lines as one run_metadata-stamped artifact (the
    BENCH_r10.json path)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.rnn import Recurrent
    from analytics_zoo_tpu.parallel import (Adam, create_train_state,
                                            make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.deepspeech2 import (
        ds2_ctc_criterion, make_ds2_model)
    from analytics_zoo_tpu.transform.audio.featurize import (
        WINDOW_SIZE, WINDOW_STRIDE)
    from tools.profile_mfu import flops_of

    sec = args.ds2_seconds
    n_max = (16000 * sec - WINDOW_SIZE) // WINDOW_STRIDE + 1
    B, _, _, _, _, edges, batches = _ds2_ragged_workload(args, n_max)
    recs = sum(b["n_frames"].shape[0] for b in batches)

    kind = jax.devices()[0].device_kind
    backend = jax.default_backend()
    peak = _peak_tflops(kind)
    mfu_peak, mfu_basis = peak, "device_peak"
    n_chips = max(jax.device_count(), 1)
    reps = max(1, max(4, args.steps // 3) // max(len(batches), 1))
    criterion = ds2_ctc_criterion()
    dt_bytes = 2 if args.compute_dtype in ("bf16", "bfloat16") else 4
    emitted = []
    last = None
    for hidden in (args.ds2_hidden, 1760) if not args.quick \
            else (args.ds2_hidden,):
        sides, info = {}, {}
        for engine in ("blocked", "pallas"):
            model = make_ds2_model(hidden=hidden,
                                   n_rnn_layers=args.ds2_layers,
                                   utt_length=n_max,
                                   rnn_block=args.ds2_block,
                                   rnn_engine=engine)
            optim = Adam(3e-4)
            state = replicate(create_train_state(model, optim), mesh)
            step = make_train_step(model.module, criterion, optim,
                                   mesh=mesh,
                                   compute_dtype=args.compute_dtype)
            dev = [mesh_lib.shard_batch(b, mesh) for b in batches]
            # the train step DONATES its state buffers and
            # model.variables aliases them (the profile_mfu caveat) —
            # the fwd sub-phase needs its own device copy
            variables = jax.device_put(jax.device_get(model.variables))
            # the fwd sub-phase is a forward-only program: price only
            # the forward's VMEM residency, or a backward-only budget
            # overflow (possible on TPU, e.g. H=1760 bf16) would fell
            # the forward kernel too and bank blocked-vs-blocked
            fwd_module = model.module.clone(rnn_pallas_grad=False)

            def jfwd_fn(v, x, nf, module=fwd_module):
                # scalar output = cheap readback fence, identical on
                # both sides (the forward sub-phase's program)
                return jnp.sum(module.apply(v, x, nf))

            jfwd = jax.jit(jfwd_fn)

            # the pallas engine warns and runs the blocked scan when a
            # pass cannot be VMEM-resident — capture PER SUB-PHASE
            # around each program's compiles (make_ds2_model's fp32
            # batch-1 build trace above can warn at geometries where
            # the measured program fits), and attribute per PASS from
            # the warning text (the budget warning names which of
            # forward/backward overflowed): a fallen-back backward
            # banking a scan-vs-scan ratio is the failure mode this
            # field exists to expose.
            with warnings.catch_warnings(record=True) as caught_f:
                warnings.simplefilter("always")
                for b in dev:                  # compile each pinned shape
                    out = jfwd(variables, b["input"][0], b["n_frames"])
            float(np.asarray(out))             # readback-fenced warmup
            fwd_msgs = [str(w.message) for w in caught_f
                        if "falling back" in str(w.message)]

            with warnings.catch_warnings(record=True) as caught_t:
                warnings.simplefilter("always")
                for b in dev:
                    state, m = step(state, b, 1.0)
            float(np.asarray(m["loss"]))
            train_msgs = [str(w.message) for w in caught_t
                          if "falling back" in str(w.message)]

            def per_pass(msgs):
                return {"forward": any("forward" in m for m in msgs),
                        "backward": any("backward" in m for m in msgs),
                        "any": bool(msgs)}

            by_shape = {}
            for b in dev:
                x = b["input"][0]
                cnt, ex = by_shape.get(x.shape, (0, b))
                by_shape[x.shape] = (cnt + 1, ex)
            fpr_fwd = sum(
                flops_of(jfwd, variables, ex["input"][0], ex["n_frames"])
                * cnt for cnt, ex in by_shape.values()) / max(recs, 1)
            fpr_train = _flops_per_record(step, state, dev, recs)
            hold = {"state": state}

            def run_train(hold=hold, step=step, dev=dev):
                t0 = time.perf_counter()
                m = None
                s = hold["state"]
                for _ in range(reps):
                    for b in dev:
                        s, m = step(s, b, 1.0)
                hold["state"] = s
                float(np.asarray(m["loss"]))   # fence
                return recs * reps / (time.perf_counter() - t0) / n_chips

            def run_fwd(jfwd=jfwd, variables=variables, dev=dev):
                t0 = time.perf_counter()
                out = None
                for _ in range(reps):
                    for b in dev:
                        out = jfwd(variables, b["input"][0],
                                   b["n_frames"])
                float(np.asarray(out))         # fence
                return recs * reps / (time.perf_counter() - t0) / n_chips

            sides[(engine, "fwd")] = run_fwd
            sides[(engine, "train")] = run_train
            info[engine] = {
                "fb": {"fwd": per_pass(fwd_msgs),
                       "train": per_pass(train_msgs)},
                "fpr": {"fwd": fpr_fwd, "train": fpr_train},
            }

        # achieved-intensity readout for the h2h term (analytic — the
        # MFU_CEILING.md roofline algebra), PER PASS: forward, 2·B·H²
        # FLOPs/step against the H²·db weight block; backward, 4·B·H²
        # FLOPs/step (dh ← dgate·Wᵀ + dW += hᵀ·dgate) against BOTH
        # blocks (2·H²·db) — re-read every step by the blocked/scan
        # paths, once per sequence of T′ steps by the persistent
        # kernels.  PER-CHIP batch: each core's matmul only runs its
        # own data-parallel shard.
        b_chip = max(B // n_chips, 1)
        t_out = (n_max + 1) // 2
        i_blocked = 2.0 * b_chip / dt_bytes
        i_pallas = i_blocked * t_out

        for sub in ("fwd", "train"):
            b_rates, p_rates, ratios = _interleaved_ab(
                sides[("blocked", sub)], sides[("pallas", sub)])

            def mfu_of(rate, eng, sub=sub):
                return rate * info[eng]["fpr"][sub] / (mfu_peak * 1e12)

            sub_note = (
                "forward program only (jitted masked BiRNN to a scalar "
                "fence)" if sub == "fwd" else
                "full train step fwd+bwd+Adam; the pallas backward is "
                "the r10 TRANSPOSED persistent kernel (reversed grid, "
                "W/Wt VMEM-resident, fused dW accumulation) — "
                "bwd_h2h_intensity is its 4BH2-per-step term against "
                "both resident blocks")
            emitted.append(_emit(
                f"ds2_persistent_h{hidden}_{sub}_blocked"
                "_records_per_sec_per_chip",
                _median(b_rates), "records/sec/chip", None, batch=B,
                hidden=hidden, layers=args.ds2_layers, backend=backend,
                utterance_seconds=sec, bucket_edges=edges, subphase=sub,
                windows=[round(r, 3) for r in b_rates],
                mfu_est=round(mfu_of(_median(b_rates), "blocked"), 5),
                mfu_est_windows=[round(mfu_of(r, "blocked"), 5)
                                 for r in b_rates],
                flops_per_record_gflop=round(
                    info["blocked"]["fpr"][sub] / 1e9, 3),
                mfu_basis=mfu_basis,
                engine_fallback=info["blocked"]["fb"][sub],
                h2h_intensity_flops_per_byte=round(i_blocked, 1),
                **({"bwd_h2h_intensity_flops_per_byte":
                    round(i_blocked, 1)} if sub == "train" else {}),
                note="blocked-scan engine (rnn_engine='blocked'): the "
                     "h2h weight block re-streams from HBM every "
                     "timestep on every pass — intensity "
                     "~2B/dtype_bytes vs the v5e ridge ~240; " + sub_note))
            last = _emit(
                f"ds2_persistent_h{hidden}_{sub}_pallas"
                "_records_per_sec_per_chip",
                _median(p_rates), "records/sec/chip", _median(ratios),
                batch=B, hidden=hidden, layers=args.ds2_layers,
                backend=backend, utterance_seconds=sec,
                bucket_edges=edges, subphase=sub,
                records=recs, time_block=int(Recurrent.pallas_time_block),
                windows=[round(r, 3) for r in p_rates],
                blocked_windows=[round(r, 3) for r in b_rates],
                ratio_windows=[round(r, 3) for r in ratios],
                mfu_est=round(mfu_of(_median(p_rates), "pallas"), 5),
                mfu_est_windows=[round(mfu_of(r, "pallas"), 5)
                                 for r in p_rates],
                flops_per_record_gflop=round(
                    info["pallas"]["fpr"][sub] / 1e9, 3),
                mfu_basis=mfu_basis,
                h2h_intensity_flops_per_byte=round(i_pallas, 1),
                **({"bwd_h2h_intensity_flops_per_byte":
                    round(i_pallas, 1)} if sub == "train" else {}),
                h2h_weight_mbytes_per_direction=round(
                    hidden**2 * dt_bytes / 2**20, 2),
                v5e_ridge_flops_per_byte=240,
                device_kind=kind,
                engine_fallback=info["pallas"]["fb"][sub],
                note="persistent-RNN Pallas engine (rnn_engine="
                     "'pallas', ops.pallas_rnn): h2h weights load into "
                     "VMEM once per sequence — intensity "
                     "~2*B*T'/dtype_bytes, decoupled from batch size; "
                     "engine_fallback records PER PASS (from the "
                     "budget warning's named pass) whether this side "
                     "ACTUALLY ran the blocked scan; vs_baseline = "
                     "median per-pair pallas/blocked records-per-sec "
                     "ratio, interleaved windows, equal geometry/"
                     "buckets/masking.  On a CPU backend the kernels "
                     "run interpret-mode (discharged to XLA) and the "
                     "ratio banks schedule parity, not the "
                     "HBM-residency term; " + sub_note)
            emitted.append(last)

    if getattr(args, "ds2_persistent_out", ""):
        from analytics_zoo_tpu.obs import run_metadata

        def pick(h, sub, eng):
            m = (f"ds2_persistent_h{h}_{sub}_{eng}"
                 "_records_per_sec_per_chip")
            return next(ln for ln in emitted if ln["metric"] == m)

        hiddens = sorted({ln["hidden"] for ln in emitted})
        headline = {}
        for h in hiddens:
            for sub in ("fwd", "train"):
                p = pick(h, sub, "pallas")
                headline[f"pallas_over_blocked_ratio_h{h}_{sub}"] = \
                    p["vs_baseline"]
                headline[f"engine_fallback_h{h}_{sub}"] = \
                    p["engine_fallback"]
            headline[f"h2h_intensity_pallas_h{h}"] = \
                pick(h, "train", "pallas")["h2h_intensity_flops_per_byte"]
            headline[f"bwd_h2h_intensity_pallas_h{h}"] = \
                pick(h, "train", "pallas")[
                    "bwd_h2h_intensity_flops_per_byte"]
        argv = []
        skip_next = False
        for a in sys.argv[1:]:
            if skip_next:
                argv.append("<all other phases>")
                skip_next = False
            elif a == "--skip":
                argv.append(a)
                skip_next = True
            elif a.startswith("--skip="):
                argv.append("--skip <all other phases>")
            else:
                argv.append(a)
        doc = {
            "round": 10,
            "phase": "ds2_persistent",
            "command": "python bench.py " + " ".join(argv),
            "backend": backend,
            "host_cpus": os.cpu_count(),
            "headline": headline,
            "policy": (
                "interleaved drift-cancelling window pairs per "
                "sub-phase in ONE process (_interleaved_ab, "
                "alternating order); committed ratio = median of "
                "per-pair pallas/blocked records-per-sec ratios; "
                "per-window values kept in each line; EQUAL geometry "
                "(hidden, layers, batch, optimizer, dtype), the SAME "
                "seeded ragged length distribution, the SAME quantile "
                "buckets and n_frames masking on both sides — the "
                "ONLY variable is the recurrence engine; "
                "engine_fallback recorded per pass per line (the "
                "budget warning names the overflowing pass), so a "
                "fallen-back backward cannot bank a scan-vs-scan "
                "ratio"),
            "context": (
                "ISSUE 13: the grad pass joins the persistent "
                "formulation.  TRAIN sub-phase = full train step "
                "(fwd+bwd+Adam) where the pallas side's custom_vjp "
                "backward is the TRANSPOSED persistent kernel "
                "(Diamos et al. ICML'16 §4 restated for TPU): "
                "reversed time grid, W_h2h AND W_h2h^T resident in "
                "VMEM via constant-index-map BlockSpecs, dh carry in "
                "fp32 VMEM scratch, dW/db fused-accumulated in fp32 "
                "VMEM scratch across all time blocks (streamed out "
                "once at the final grid step), within-block recompute "
                "from streamed block-boundary carry residuals (T/U "
                "slabs, not T per-step activations).  FWD sub-phase = "
                "the forward program alone (the r7 reading, "
                "re-banked at the same workload for a per-pass "
                "decomposition).  On this CPU host both kernels run "
                "interpret-mode: the ratios bank schedule parity; the "
                "intensity columns (per pass, per line) are the "
                "HBM-residency term that pays on silicon."),
            "lines": emitted,
            "run_metadata": run_metadata("bench_ds2_persistent", seed=0),
        }
        with open(args.ds2_persistent_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"ds2_persistent: banked {len(emitted)} lines -> "
              f"{args.ds2_persistent_out}", file=sys.stderr)
    return last


def bench_ds2_globalbatch(args, mesh):
    """DS2 global-batch scaling on the declare-once mesh substrate
    (ISSUE 9): the post-persistent-kernel lever of docs/MFU_CEILING.md
    r7 — MXU occupancy ≈ B/128 — exercised as bucketed large global
    batch over the ``data`` axis, with sharding declared ONCE
    (``pipeline_specs("ds2")``) and consumed by the annotated train
    step (host batches go straight into jit; no shard_batch call in
    this phase).  Two readouts:

    * **width A/B at EQUAL per-chip geometry** — the same per-chip
      batch and the same quantile bucket edges on a width-1 mesh vs the
      full width-N data mesh (global batch = per-chip × width; the mesh
      is the ONLY variable).  Interleaved drift-cancelling windows;
      vs_baseline = median per-pair global-records/sec ratio (ideal = N
      on real chips).
    * **occupancy trend toward the B/128 knee** — per-chip batch swept
      upward at full width; every line records ``occupancy_b_over_128``
      and the r7 blended-ceiling algebra (h2h share 2/3 at b/128, rest
      at the SSD-class 0.55), plus ``mfu_est`` from XLA's compiled FLOP
      count.

    On this CPU host the virtual devices share cores, so measured
    records/sec does NOT scale with width — lines carry
    ``virtual: true`` and the banked claim is the MECHANISM (the same
    declared specs compile and run at every width with the jit placing
    global batches) plus the occupancy algebra that transfers to real
    chips; the MULTICHIP artifacts have always used this labeling."""
    import numpy as np
    import jax

    from analytics_zoo_tpu.data.bucket import BucketBatcher
    from analytics_zoo_tpu.parallel import (Adam, create_mesh,
                                            create_train_state,
                                            make_train_step,
                                            pipeline_specs)
    from analytics_zoo_tpu.pipelines.deepspeech2 import (
        ds2_ctc_criterion, make_ds2_model)
    from analytics_zoo_tpu.transform.audio.featurize import (
        WINDOW_SIZE, WINDOW_STRIDE)

    sec = args.ds2_seconds
    n_max = (16000 * sec - WINDOW_SIZE) // WINDOW_STRIDE + 1
    devices = jax.devices()
    n_dev = max(len(devices), 1)
    backend = jax.default_backend()
    kind = devices[0].device_kind
    peak = _peak_tflops(kind)
    mfu_peak, mfu_basis = peak, "device_peak"
    virtual = backend != "tpu"

    b_chip = max(args.ds2_batch, 1)
    bchips = [b_chip] if args.quick else [b_chip, 4 * b_chip]
    widths = [1] if n_dev == 1 else [1, n_dev]

    # ONE seeded sample set and ONE quantile edge set, shared by every
    # (width, per-chip batch) config — "BucketBatcher edges shared" is
    # the phase's equal-geometry contract.  Quantile edges spread the
    # records ~evenly across buckets, so the WIDEST config (global
    # batch = max per-chip × max width) needs ~buckets × B records
    # before any bucket fills at all; sizing below that would bank a
    # zero-batch side silently.
    n_records = max(128, args.ds2_buckets * max(bchips) * max(widths))
    lengths = _ds2_ragged_lengths(n_records, n_max)
    rng = np.random.RandomState(0)
    L = 20
    feats = [rng.randn(int(n), 13).astype(np.float32) * 0.1
             for n in lengths]
    labels = rng.randint(1, 29, (n_records, L)).astype(np.int32)
    lab_mask = np.ones((n_records, L), np.float32)
    qs = np.quantile(lengths, np.linspace(1.0 / args.ds2_buckets, 1.0,
                                          args.ds2_buckets))
    edges = sorted(set(int(np.ceil(q)) for q in qs) | {int(lengths.max())})

    def assemble(global_b):
        def stream():
            for i in range(n_records):
                yield {"input": feats[i], "n_frames": np.int32(lengths[i]),
                       "labels": labels[i], "label_mask": lab_mask[i]}

        out = []
        for b in BucketBatcher(global_b, edges).apply_iter(stream()):
            out.append({"input": (b["input"], b["n_frames"]),
                        "n_frames": b["n_frames"],
                        "labels": b["labels"],
                        "label_mask": b["label_mask"]})
        return out

    def ceiling_blend(b):
        """docs/MFU_CEILING.md r7 blend: h2h share (2/3 of FLOPs) at
        the B/128 occupancy, the rest at the SSD-class 0.55."""
        occ = min(b / 128.0, 1.0)
        return 1.0 / ((2.0 / 3.0) / occ + (1.0 / 3.0) / 0.55)

    criterion = ds2_ctc_criterion()
    hidden = args.ds2_hidden
    configs = [(w, b_chip) for w in widths] \
        + [(max(widths), b) for b in bchips[1:]]
    sides = {}
    for w, bc in configs:
        mesh_w = create_mesh(devices=devices[:w])
        specs = pipeline_specs("ds2", mesh=mesh_w)
        model = make_ds2_model(hidden=hidden, n_rnn_layers=args.ds2_layers,
                               utt_length=n_max, rnn_block=args.ds2_block)
        optim = Adam(3e-4)
        state = specs.place_state(create_train_state(model, optim))
        step = make_train_step(model.module, criterion, optim, specs=specs,
                               compute_dtype=args.compute_dtype)
        batches = assemble(bc * w)          # HOST batches: jit places them
        recs = sum(b["n_frames"].shape[0] for b in batches)
        for b in batches:                   # compile each pinned shape
            state, m = step(state, b, 1.0)
        float(np.asarray(m["loss"]))        # readback-fenced warmup
        fpr = _flops_per_record(step, state, batches, recs)
        reps = max(1, max(4, args.steps // 3) // max(len(batches), 1))
        hold = {"state": state}

        def run(hold=hold, step=step, batches=batches, recs=recs,
                reps=reps):
            t0 = time.perf_counter()
            m = None
            s = hold["state"]
            for _ in range(reps):
                for b in batches:
                    s, m = step(s, b, 1.0)
            hold["state"] = s
            float(np.asarray(m["loss"]))    # fence
            return recs * reps / (time.perf_counter() - t0)

        sides[(w, bc)] = {
            "run": run, "recs": recs, "fpr": fpr,
            "dropped": n_records - recs, "batches": len(batches),
        }

    # round-robin interleaved windows: every config measured once per
    # round in rotating order, ratios taken WITHIN a round so common
    # drift cancels (the _interleaved_ab policy generalized to N sides)
    keys = list(sides)
    windows = {k: [] for k in keys}
    rounds = 3
    for i in range(rounds):
        order = keys[i % len(keys):] + keys[:i % len(keys)]
        for k in order:
            windows[k].append(sides[k]["run"]())

    anchor = (1, b_chip)
    last = None
    for k in keys:
        w, bc = k
        info = sides[k]
        rates = windows[k]
        ratios = [r / max(a, 1e-9)
                  for r, a in zip(rates, windows[anchor])]
        is_anchor = k == anchor
        # fpr is XLA's compiled count on the SPMD-partitioned program —
        # per-PARTITION FLOPs per global record — so per-chip MFU is
        # global_rate × fpr / peak (each chip contributes fpr FLOPs to
        # every global record)
        mfu = [r * info["fpr"] / (mfu_peak * 1e12) for r in rates]
        last = _emit(
            f"ds2_globalbatch_w{w}_bchip{bc}_records_per_sec",
            _median(rates), "records/sec (global)",
            None if is_anchor else _median(ratios),
            width=w, per_chip_batch=bc, global_batch=bc * w,
            hidden=hidden, layers=args.ds2_layers, backend=backend,
            device_kind=kind, virtual=virtual,
            utterance_seconds=sec, bucket_edges=edges,
            records=info["recs"],
            dropped_remainder_records=info["dropped"],
            windows=[round(r, 3) for r in rates],
            **({} if is_anchor else
               {"ratio_windows": [round(r, 3) for r in ratios],
                "anchor": "w1_bchip%d" % b_chip}),
            records_per_sec_per_chip=round(_median(rates) / max(w, 1), 3),
            occupancy_b_over_128=round(min(bc / 128.0, 1.0), 4),
            ceiling_blend_est=round(ceiling_blend(bc), 4),
            mfu_est=round(_median(mfu), 5),
            mfu_est_windows=[round(v, 5) for v in mfu],
            flops_per_record_gflop=round(info["fpr"] / 1e9, 3),
            mfu_basis=mfu_basis,
            note="declare-once substrate (pipeline_specs('ds2') -> "
                 "annotated jit places HOST batches; no shard_batch in "
                 "this phase); equal per-chip geometry across widths, "
                 "ONE shared seeded length distribution + bucket edge "
                 "set; vs_baseline = median within-round rate ratio vs "
                 "the width-1 anchor (ideal = width on real chips; on "
                 "a shared-core CPU host ~1, virtual=true); "
                 "ceiling_blend_est = MFU_CEILING.md r7 blend "
                 "(2/3 h2h share at b/128 occupancy + 1/3 at 0.55) — "
                 "the per-chip-batch occupancy term that transfers to "
                 "TPU; flops_per_record_gflop = XLA's count on the "
                 "SPMD-partitioned program (per-chip share of one "
                 "global record); mfu_est = global rate x that / peak "
                 "(basis recorded)")
    return last


def bench_rec_embedding(args, mesh):
    """Embedding hot path (ISSUE 17): the dedup'd gather/segment-sum
    lookup vs the two references it replaces, plus the row-sharded
    table sweep and the sparse optimizer apply.  Three readouts:

    * **lookup A/B at EQUAL seeded Zipfian geometry** — fwd+bwd
      (grad wrt the table) through ``sharded_embedding_lookup`` in each
      mode: ``dedup`` (unique-gather + segment-sum custom_vjp) vs
      ``onehot`` (the reference ``LookupTable`` semantics — a
      ``(batch, vocab)`` one-hot matmul whose vjp densifies the
      cotangent) and vs ``naive`` (plain per-position gather).  ONE
      seeded id batch (np.RandomState(0) Zipf) shared by every side —
      the implementation is the ONLY variable; each line records the
      batch's ``unique_fraction`` (the dedup win ratio).  Interleaved
      drift-cancelling windows, committed ratio = median per-pair.
    * **sparse vs dense optimizer apply** — ``sparse_adam_apply`` (the
      touched-rows-only Adam fed by ``embedding_grad_rows``) vs the
      repo's full-table optax chain on the SAME gradient; rate =
      applies/sec, rows_touched recorded.
    * **row-sharded table sweep** — the SAME dedup fwd+bwd program
      with the table row-sharded (``embedding_row_rules`` — vocab dim 0
      over the mesh) at width 1 vs the full virtual width; within-round
      ratios vs the width-1 anchor.  On this CPU host the virtual
      devices share cores (lines carry ``virtual: true``): the banked
      claim is the MECHANISM — the declared row shard compiles and runs
      the gather shard-local at every width — not a speedup number."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.embedding import (embedding_grad_rows,
                                                 lookup_stats,
                                                 sharded_embedding_lookup,
                                                 sparse_rows_to_dense)
    from analytics_zoo_tpu.parallel import (Adam, SpecSet, create_mesh,
                                            embedding_row_rules,
                                            sparse_adam_apply)

    backend = jax.default_backend()
    devices = jax.devices()
    n_dev = max(len(devices), 1)
    virtual = backend != "tpu"
    vocab, dim, batch = args.rec_vocab, args.rec_dim, args.rec_batch
    windows = args.rec_windows
    target_s = 0.25 if args.quick else 1.0

    rng = np.random.RandomState(0)
    ids_np = (rng.zipf(1.3, size=batch) % vocab).astype(np.int32)
    stats = lookup_stats(ids_np)
    ids = jnp.asarray(ids_np)
    table = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.01)
    w = jnp.asarray(rng.randn(batch, dim).astype(np.float32))

    geometry = dict(vocab=vocab, dim=dim, batch=batch, seed=0,
                    zipf_a=1.3, unique_fraction=round(
                        stats["unique_fraction"], 4),
                    rows_touched=stats["rows_touched"],
                    backend=backend, virtual=virtual)

    def timed_rate(fn, fence, units):
        """Calibrated window: reps sized so one window ≈ target_s, rate
        normalized to units/sec (unequal per-side reps are fine — the
        ratio compares RATES, not raw walls)."""
        fence(fn())                               # compile + warm
        t0 = time.perf_counter()
        fence(fn())
        t1 = max(time.perf_counter() - t0, 1e-6)
        reps = max(1, int(target_s / t1))

        def run():
            t0 = time.perf_counter()
            out = None
            for _ in range(reps):
                out = fn()
            fence(out)
            return units * reps / (time.perf_counter() - t0)
        return run

    def lookup_run(mode):
        g = jax.jit(jax.grad(lambda t: jnp.vdot(
            sharded_embedding_lookup(t, ids, mode=mode), w)))
        return timed_rate(lambda: g(table),
                          lambda o: o.block_until_ready(), batch)

    emitted = []
    ab_note = ("fwd+bwd (jitted grad wrt the table) per side; ONE "
               "seeded Zipfian id batch (np.RandomState(0).zipf(1.3) "
               "% vocab) shared by all sides — equal geometry, the "
               "lookup implementation is the only variable; "
               "vs_baseline = median per-pair dedup/<rival> "
               "positions-per-sec ratio over interleaved "
               "drift-cancelling windows; onehot = the reference "
               "LookupTable semantics (one-hot matmul, densifying "
               "vjp), naive = per-position gather")
    for rival in ("onehot", "naive"):
        r_rates, d_rates, ratios = _interleaved_ab(
            lookup_run(rival), lookup_run("dedup"), windows=windows)
        emitted.append(_emit(
            f"rec_embedding_lookup_{rival}_positions_per_sec",
            _median(r_rates), "positions/sec", None,
            windows=[round(r, 1) for r in r_rates], **geometry))
        emitted.append(_emit(
            f"rec_embedding_lookup_dedup_over_{rival}_positions_per_sec",
            _median(d_rates), "positions/sec", _median(ratios),
            windows=[round(r, 1) for r in d_rates],
            ratio_windows=[round(r, 3) for r in ratios],
            anchor=rival, note=ab_note, **geometry))

    # -- sparse vs dense optimizer apply (SAME gradient) ---------------
    lr = 1e-3
    grad = embedding_grad_rows(ids, w)
    dense_grad = sparse_rows_to_dense(grad, vocab)
    tx = Adam(lr).tx
    st0 = tx.init(table)
    st0.hyperparams["learning_rate"] = jnp.asarray(lr, jnp.float32)

    def dense_apply():
        import optax

        upd, _ = tx.update(dense_grad, st0, table)
        return optax.apply_updates(table, upd)

    dense_j = jax.jit(dense_apply)
    sparse_j = jax.jit(lambda: sparse_adam_apply(
        table, jnp.zeros_like(table), jnp.zeros_like(table),
        jnp.zeros((), jnp.int32), grad, learning_rate=lr))
    d_rates, s_rates, ratios = _interleaved_ab(
        timed_rate(dense_j, lambda o: jax.block_until_ready(o), 1),
        timed_rate(sparse_j, lambda o: jax.block_until_ready(o), 1),
        windows=windows)
    emitted.append(_emit(
        "rec_embedding_sparse_over_dense_adam_applies_per_sec",
        _median(s_rates), "applies/sec", _median(ratios),
        dense_windows=[round(r, 1) for r in d_rates],
        windows=[round(r, 1) for r in s_rates],
        ratio_windows=[round(r, 3) for r in ratios],
        anchor="full_table_optax_adam",
        note="sparse_adam_apply (touched rows + their Adam slots only, "
             "fed by embedding_grad_rows) vs the repo's full-table "
             "optax chain on the SAME gradient; both jitted; the "
             "sparse side moves rows_touched x dim instead of "
             "vocab x dim per step", **geometry))

    # -- row-sharded table sweep (virtual mesh) ------------------------
    widths = [1] if n_dev == 1 else [1, n_dev]
    sides = {}
    for width in widths:
        mesh_w = create_mesh((1, width), axis_names=("data", "model"),
                             devices=devices[:width])
        specs = SpecSet(mesh_w, rules=embedding_row_rules())
        placed = specs.place_state({"embed": {"embedding": table}})
        t_sharded = placed["embed"]["embedding"]
        g = jax.jit(jax.grad(lambda t: jnp.vdot(
            sharded_embedding_lookup(t, ids, mode="dedup"), w)))
        sides[width] = {
            "run": timed_rate(lambda g=g, t=t_sharded: g(t),
                              lambda o: o.block_until_ready(), batch),
            "replicated": t_sharded.sharding.is_fully_replicated,
        }
    sweep_windows = {k: [] for k in sides}
    for i in range(windows):                     # round-robin rounds
        order = list(sides)[i % len(sides):] + list(sides)[:i % len(sides)]
        for k in order:
            sweep_windows[k].append(sides[k]["run"]())
    last = None
    for width in sides:
        rates = sweep_windows[width]
        ratios = [r / max(a, 1e-9)
                  for r, a in zip(rates, sweep_windows[widths[0]])]
        is_anchor = width == widths[0]
        last = _emit(
            f"rec_embedding_sharded_w{width}_positions_per_sec",
            _median(rates), "positions/sec",
            None if is_anchor else _median(ratios),
            width=width,
            table_row_sharded=not sides[width]["replicated"],
            windows=[round(r, 1) for r in rates],
            **({} if is_anchor else
               {"ratio_windows": [round(r, 3) for r in ratios],
                "anchor": "w1"}),
            note="SAME dedup fwd+bwd program, table row-sharded over "
                 "the model axis (embedding_row_rules: vocab dim 0) on "
                 "a width-N virtual mesh; vs_baseline = median "
                 "within-round ratio vs the width-1 anchor; on a "
                 "shared-core CPU host the ratio banks the MECHANISM "
                 "(declared row shard compiles/runs at every width), "
                 "not a speedup — virtual=true", **geometry)
        emitted.append(last)

    if getattr(args, "rec_embedding_out", ""):
        from analytics_zoo_tpu.obs import run_metadata

        def ratio_of(metric):
            return next(ln["vs_baseline"] for ln in emitted
                        if ln["metric"] == metric)

        headline = {
            "dedup_over_onehot_ratio": ratio_of(
                "rec_embedding_lookup_dedup_over_onehot_positions_per_sec"),
            "dedup_over_naive_ratio": ratio_of(
                "rec_embedding_lookup_dedup_over_naive_positions_per_sec"),
            "sparse_over_dense_apply_ratio": ratio_of(
                "rec_embedding_sparse_over_dense_adam_applies_per_sec"),
            "unique_fraction": geometry["unique_fraction"],
            "sharded_widths": widths,
        }
        argv = []
        skip_next = False
        for a in sys.argv[1:]:
            if skip_next:
                argv.append("<all other phases>")
                skip_next = False
            elif a == "--skip":
                argv.append(a)
                skip_next = True
            elif a.startswith("--skip="):
                argv.append("--skip <all other phases>")
            else:
                argv.append(a)
        env_prefix = (f"XLA_FLAGS={os.environ['XLA_FLAGS']} "
                      if "XLA_FLAGS" in os.environ else "")
        doc = {
            "round": 11,
            "phase": "rec_embedding",
            "command": env_prefix + "python bench.py " + " ".join(argv),
            "backend": backend,
            "host_cpus": os.cpu_count(),
            "headline": headline,
            "policy": (
                "interleaved drift-cancelling window pairs per A/B in "
                "ONE process (_interleaved_ab, alternating order); "
                "committed ratio = median of per-pair rate ratios; "
                "per-window values kept in each line; EQUAL geometry "
                "— ONE seeded Zipfian id batch "
                "(np.RandomState(0).zipf(1.3) % vocab), ONE table, "
                "ONE cotangent — shared by every side of every A/B; "
                "the lookup implementation (or apply sparsity, or "
                "mesh width) is the only variable per readout; "
                "calibrated per-side reps (rates normalized to "
                "units/sec, so unequal reps cannot bias a ratio)"),
            "context": (
                "ISSUE 17: the recommendation/sentiment families' hot "
                "path is a sparse gather, not a matmul.  dedup = "
                "unique-gather + segment-sum custom_vjp "
                "(ops.embedding.dedup_lookup): gathers each unique id "
                "once, backward segment-sums the cotangent into "
                "(ids, rows) and lands ONE vocab-sized scatter-add — "
                "no (batch, vocab) one-hot, no densified cotangent.  "
                "onehot = the reference LookupTable semantics the zoo "
                "inherited (BigDL expresses a lookup as a one-hot "
                "matmul whose vjp materializes a full (vocab, dim) "
                "gradient).  sparse_adam_apply moves only touched "
                "rows and their Adam slots (lazy Adam; bit-matches "
                "the dense chain on touched rows — "
                "tests/test_embedding.py).  The sharded sweep "
                "row-shards the table (vocab dim 0, "
                "embedding_row_rules — the ISSUE-17 fix of the "
                "column shard that put a slice of every row on every "
                "device) on a virtual CPU mesh: mechanism, not "
                "speedup (virtual=true)."),
            "lines": emitted,
            "run_metadata": run_metadata("bench_rec_embedding", seed=0),
        }
        with open(args.rec_embedding_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"rec_embedding: banked {len(emitted)} lines -> "
              f"{args.rec_embedding_out}", file=sys.stderr)
    return last


def bench_frcnn_serve(args, mesh, records):
    """Faster-RCNN serving (+int8 compute) — VERDICT r3 item 3: the
    flagship net-new family had zero benchmark lines.  Full pipeline per
    ``FrcnnPredictor.predict``: decode → AspectScaleCanvas → one jitted
    trunk→RPN→proposal→ROI-pool→heads→per-class-NMS program → rescale."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models import FasterRcnnDetector, FrcnnParam
    from analytics_zoo_tpu.ops import ProposalParam
    from analytics_zoo_tpu.pipelines.frcnn import FrcnnPredictor
    from analytics_zoo_tpu.pipelines.ssd import PreProcessParam

    res = 512 if not args.quick else 128
    batch = min(max(args.batch // 8, 2), len(records))
    det = FasterRcnnDetector(param=FrcnnParam(
        num_classes=args.classes,
        proposal=ProposalParam(pre_nms_topn=2000 if not args.quick else 64,
                               post_nms_topn=128 if not args.quick else 16)))
    x0 = jnp.zeros((1, res, res, 3))
    info0 = jnp.asarray([[float(res), float(res), 1.0]])
    variables = det.init(jax.random.PRNGKey(0), x0, info0)
    param = PreProcessParam(batch_size=batch, resolution=res)

    def _time_predict(p):
        warm = p.predict(records[:batch])                 # compile
        assert len(warm) == batch
        t0 = time.perf_counter()
        out = p.predict(records)
        dt = time.perf_counter() - t0
        assert len(out) == len(records)
        return len(records) / dt / max(jax.device_count(), 1)

    predictor = FrcnnPredictor(det, variables, param)
    per_chip = _time_predict(predictor)
    _emit("frcnn_serve_images_per_sec_per_chip", per_chip,
          "images/sec/chip", None, batch=batch, resolution=res,
          note="decode+aspect-canvas+trunk/RPN/proposal/ROI-pool/heads/"
               "NMS in one jit+rescale; the reference can only serve "
               "this family (Proposal.scala throws on backward)")

    q_predictor = FrcnnPredictor(det, variables, param, quantize="int8")
    fp_rates, q_rates, ratios = _interleaved_ab(
        lambda: _time_predict(predictor), lambda: _time_predict(q_predictor))
    return _emit("frcnn_serve_int8_images_per_sec_per_chip",
                 _median(q_rates), "images/sec/chip", _median(ratios),
                 fp_windows=[round(x, 2) for x in fp_rates],
                 int8_windows=[round(x, 2) for x in q_rates],
                 note="int8 COMPUTE serving (dynamic activation quant + "
                      "int8xint8->int32 convs on the MXU); vs_baseline = "
                      "median per-pair int8/fp ratio, interleaved windows")


def bench_ssd512_step(args, mesh):
    """SSD512 device-step throughput + MFU (VERDICT r3 weak #7: 512
    existed only as tables + TP rules).  Compute-only window on a
    device-resident batch — the 512 e2e/input-link story is the same as
    300's; what's 512-specific is the model geometry (7 heads, 24564
    priors, conv10 extra block), which this phase compiles and runs."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg, build_priors
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (
        SGD, create_train_state, make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    res = 512
    # 512² ≈ 2.9× 300² pixels — and fwd+bwd activations for batch 64 at
    # 512 measure 16.4 GB, past the v5e's 15.75 GB HBM; 32 fits
    B = max(args.batch // 4, jax.device_count())
    model = Model(SSDVgg(num_classes=args.classes, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    priors, variances = build_priors(model.module.config)
    assert priors.shape[0] == 24564, priors.shape   # the canonical 512 count
    criterion = MultiBoxLoss(priors, variances,
                             MultiBoxLossParam(n_classes=args.classes))
    optim = SGD(1e-3, momentum=0.9)
    state = replicate(create_train_state(model, optim), mesh)
    step = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype)
    rng = np.random.RandomState(0)
    batch = mesh_lib.shard_batch({
        "input": rng.rand(B, res, res, 3).astype(np.float32),
        "target": {
            "bboxes": np.tile(np.asarray([0.1, 0.1, 0.6, 0.6], np.float32),
                              (B, 4, 1)),
            "labels": np.ones((B, 4), np.int32),
            "mask": np.ones((B, 4), np.float32),
        },
    }, mesh)
    state, m = step(state, batch, 1.0)               # compile
    # readback-fenced warmup — see bench_ds2_train: an un-fenced warmup
    # bleeds into the first (transfer-free) timed window
    float(np.asarray(m["loss"]))
    flops = _flops_per_step(step, state, batch, 1.0)
    steps = max(4, args.steps // 3)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch, 1.0)
    loss = float(np.asarray(m["loss"]))              # fence
    dt = time.perf_counter() - t0
    n_chips = max(jax.device_count(), 1)
    per_chip = B * steps / dt / n_chips
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    extra = {}
    if flops > 0 and peak:
        tflops = flops / (dt / steps) / 1e12 / n_chips
        extra = {"model_tflops_per_chip": round(tflops, 2),
                 "mfu": round(tflops / peak, 4), "peak_tflops": peak}
    return _emit("ssd512_train_step_images_per_sec_per_chip", per_chip,
                 "images/sec/chip", None, batch=B, priors=24564,
                 final_loss=round(loss, 3), device_kind=kind, **extra,
                 note="bf16 fwd+bwd+update on a device-resident batch, "
                      "7-head SSD512 geometry (SSDVgg.scala:58-70 parity)")


def bench_frcnn_train(args, mesh):
    """Faster-RCNN TRAINING device-step throughput + MFU (VERDICT r4 item
    7: training throughput existed only as an ACCURACY.md aside).  Same
    discipline as bench_ssd512_step: bf16 fwd+bwd+update on a
    device-resident batch — approximate-joint losses (RPN + head,
    ``ops/frcnn_train.py``) with gt boxes injected as extra ROIs, the
    full in-graph proposal/ROI-pool path in the backward."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import FasterRcnnVgg, FrcnnParam
    from analytics_zoo_tpu.ops import ProposalParam
    from analytics_zoo_tpu.ops.frcnn_train import (FrcnnLossParam,
                                                   frcnn_training_loss)
    from analytics_zoo_tpu.parallel import (
        SGD, create_train_state, make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    res = 512 if not args.quick else 128
    # py-faster-rcnn trains near batch 1-2 at ~600px; on TPU we batch —
    # VGG fwd+bwd at 512 fits 8/chip comfortably (SSD512 fits 32)
    B = max(min(args.batch // 16, 8), 1) * max(jax.device_count(), 1)
    param = FrcnnParam(
        num_classes=args.classes,
        proposal=ProposalParam(pre_nms_topn=2000 if not args.quick else 64,
                               post_nms_topn=128 if not args.quick else 16))
    model = Model(FasterRcnnVgg(param=param))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32),
                jnp.asarray([[res, res, 1.0]], jnp.float32))
    loss_param = FrcnnLossParam()
    module = model.module

    def forward_fn(variables, inputs, train=False, rngs=None):
        x, im_info, gt_px, gt_mask = inputs
        out = module.apply(variables, x, im_info, train=train,
                           extra_rois=gt_px, extra_rois_mask=gt_mask,
                           train_outputs=True, rngs=rngs)
        return out, None

    def criterion(outputs, batch):
        return frcnn_training_loss(outputs, batch, loss_param)

    optim = SGD(1e-3, momentum=0.9)
    state = replicate(create_train_state(model, optim), mesh)
    step = make_train_step(module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype,
                           forward_fn=forward_fn)
    rng = np.random.RandomState(0)
    G = 4
    gt_px = np.tile(np.asarray([0.1, 0.1, 0.6, 0.6], np.float32) * res,
                    (B, G, 1))
    gt_mask = np.ones((B, G), np.float32)
    im_info = np.tile(np.asarray([[res, res, 1.0]], np.float32), (B, 1))
    batch = mesh_lib.shard_batch({
        "input": (rng.rand(B, res, res, 3).astype(np.float32), im_info,
                  gt_px, gt_mask),
        "im_info": im_info,
        "target": {"bboxes": gt_px,
                   "labels": np.ones((B, G), np.int32),
                   "mask": gt_mask},
    }, mesh)
    state, m = step(state, batch, 1.0)               # compile
    float(np.asarray(m["loss"]))                     # readback fence
    flops = _flops_per_step(step, state, batch, 1.0)
    steps = max(4, args.steps // 3)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch, 1.0)
    loss = float(np.asarray(m["loss"]))              # fence
    dt = time.perf_counter() - t0
    n_chips = max(jax.device_count(), 1)
    per_chip = B * steps / dt / n_chips
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind)
    extra = {}
    if flops > 0 and peak:
        tflops = flops / (dt / steps) / 1e12 / n_chips
        extra = {"model_tflops_per_chip": round(tflops, 2),
                 "mfu": round(tflops / peak, 4), "peak_tflops": peak}
    return _emit("frcnn_train_step_images_per_sec_per_chip", per_chip,
                 "images/sec/chip", None, batch=B, resolution=res,
                 final_loss=round(loss, 3), device_kind=kind, **extra,
                 note="bf16 fwd+bwd+update, device-resident batch; "
                      "RPN+head approximate-joint losses with in-graph "
                      "proposal/ROI-pool — a capability the reference "
                      "does not have (Proposal.scala throws on backward)")


def bench_overlap(args, mesh, shard_pattern):
    """Does H2D/compute overlap actually pay on this link?  Interleaved
    A/B in ONE process (the bench_wire.py methodology): window A runs the
    e2e device-aug train loop through ``device_prefetch`` (transfer of
    batch t+1 overlaps the step on t), window B runs the identical loop
    serialized (shard_batch inline, then step).  Also times the
    compute-only step on a re-fed batch so both modes get an honest
    host_bound_fraction at the SAME link state."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import device_prefetch
    from analytics_zoo_tpu.models import SSDVgg, build_priors
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (
        SGD, create_train_state, make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.ssd import (
        PreProcessParam, load_train_set_device)

    res = args.res
    model = Model(SSDVgg(num_classes=args.classes, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    priors, variances = build_priors(model.module.config)
    criterion = MultiBoxLoss(priors, variances,
                             MultiBoxLossParam(n_classes=args.classes))
    optim = SGD(1e-3, momentum=0.9)
    state = replicate(create_train_state(model, optim), mesh)
    param = PreProcessParam(batch_size=args.batch, resolution=res,
                            num_workers=args.workers, max_gt=8,
                            canvas_size=((res + 7) // 8) * 8,
                            wire_format=args.wire_format,
                            pack_staging=not args.no_pack)
    dataset, augment = load_train_set_device(shard_pattern, param)
    step = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype,
                           device_transform=augment)

    def host_batches():                  # epoch-looping HOST batches
        while True:
            yield from iter(dataset)

    host_iter = host_batches()
    first = mesh_lib.shard_batch(next(host_iter), mesh)
    state, metrics = step(state, first, 1.0)          # compile
    float(np.asarray(metrics["loss"]))   # fence the compile step

    steps = max(4, args.steps // 3)

    def window_overlapped():
        nonlocal state
        stream = device_prefetch(host_iter, mesh)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, next(stream), 1.0)
        float(np.asarray(m["loss"]))                  # fence
        dt = time.perf_counter() - t0
        stream.close()
        return args.batch * steps / dt

    def window_serialized():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state,
                            mesh_lib.shard_batch(next(host_iter), mesh), 1.0)
        float(np.asarray(m["loss"]))                  # fence
        dt = time.perf_counter() - t0
        return args.batch * steps / dt

    s_rates, o_rates, _ = _interleaved_ab(
        window_serialized, window_overlapped,
        on_pair=lambda i, s, o: _emit(
            "overlap_window_pair", round(o / max(s, 1e-9), 3), "x", None,
            window=i, overlapped=round(o, 2), serialized=round(s, 2)))

    # compute-only step: re-fed device-resident batch, no transfers
    # inside the window
    core = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype)
    first_aug = augment(first)
    state, m = core(state, first_aug, 1.0)            # compile
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = core(state, first_aug, 1.0)
    float(np.asarray(m["loss"]))
    step_rate = args.batch * steps / (time.perf_counter() - t0)

    o_med, s_med = _median(o_rates), _median(s_rates)
    return _emit(
        "ssd_train_overlap_speedup", o_med / max(s_med, 1e-9), "x", None,
        overlapped_images_per_sec=round(o_med, 2),
        serialized_images_per_sec=round(s_med, 2),
        host_bound_fraction_overlapped=round(
            max(0.0, 1.0 - o_med / step_rate), 3),
        host_bound_fraction_serialized=round(
            max(0.0, 1.0 - s_med / step_rate), 3),
        step_images_per_sec=round(step_rate, 2),
        note="interleaved windows in one process; overlap = "
             "device_prefetch double-buffering vs inline shard_batch+step "
             "on the SAME link")


def bench_host_wall(args, mesh, shard_pattern):
    """Host input wall A/B: serial vs multiprocess loader, equal link
    state (VERDICT r5 top item — every committed train sweep is
    host-bound, host_bound_fraction 0.81-0.88).

    One process, one fence, then interleaved windows (the
    ``_interleaved_ab`` drift-cancelling discipline) of the SAME
    end-to-end loop — full host-aug chain (decode → ColorJitter →
    Expand → RandomSampler → Resize → HFlip → MatToFloats) feeding a
    train step through ``device_prefetch`` — with the input pipeline
    either serial (``ParallelLoader(num_workers=0)``, the
    deterministically-seeded reference) or fanned out to
    ``num_workers ∈ {1,2,4,8}`` worker processes with shared-memory
    rings (``data.parallel``).  Both sides share one step function,
    one record set and one process, so the only variable is the host
    input pipeline.  ``host_bound_fraction = 1 - t_step_only/t_e2e``
    is computed against a step-only window on a re-fed device batch.

    On a CPU backend the device step is a light conv net (the real
    SSD step would out-starve a 2-core host the other way around —
    the device must outrun the host to expose the input wall, which
    is exactly the TPU regime this phase models); on a TPU backend it
    is the real bf16 SSDVgg step."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import device_prefetch
    from analytics_zoo_tpu.data.parallel import ParallelLoader
    from analytics_zoo_tpu.parallel import (
        SGD, create_train_state, make_train_step, replicate)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 load_train_set)
    from analytics_zoo_tpu.utils import engine

    res = args.res
    on_tpu = engine.on_tpu()
    if on_tpu:
        from analytics_zoo_tpu.models import SSDVgg, build_priors
        from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam

        model = Model(SSDVgg(num_classes=args.classes, resolution=res))
        model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
        priors, variances = build_priors(model.module.config)
        criterion = MultiBoxLoss(priors, variances,
                                 MultiBoxLossParam(n_classes=args.classes))
    else:
        import flax.linen as nn

        class _LightConv(nn.Module):
            """Device-step stand-in for CPU runs: a real jitted conv
            train step, cheap enough (4x input pooling first) that the
            host input pipeline is the bottleneck — the TPU regime,
            where the chip outruns the feeding host."""

            @nn.compact
            def __call__(self, x):
                x = nn.avg_pool(x, (4, 4), strides=(4, 4))
                for f in (8, 16):
                    x = nn.relu(nn.Conv(f, (3, 3), strides=(2, 2))(x))
                return nn.Dense(8)(x.mean(axis=(1, 2)))

        model = Model(_LightConv())
        model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))

        def criterion(output, batch):
            return jnp.mean(output ** 2)

    optim = SGD(1e-3, momentum=0.9)
    state = replicate(create_train_state(model, optim), mesh)
    step = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype=args.compute_dtype if on_tpu
                           else None)
    steps = max(4, args.steps // 3)
    batch_size = args.batch if on_tpu else max(args.batch // 8, 4)

    def make_stream(workers):
        """Epoch-looping device-batch stream through the full pipeline;
        returns (stream, loader) — the pool persists across windows so
        fork cost amortizes like a real epoch (steady state)."""
        param = PreProcessParam(batch_size=batch_size, resolution=res,
                                max_gt=8, num_workers=1,
                                worker_processes=workers, loader_seed=0)
        ds = load_train_set(shard_pattern, param)
        if workers == 0:
            ds = ParallelLoader(ds, 0, base_seed=0)   # seeded serial ref

        def host_epochs():
            while True:
                yield from iter(ds)

        # close_source: closing the stream closes the epoch generator
        # (and so the worker pool) from the prefetch thread itself
        return device_prefetch(host_epochs(), mesh, close_source=True), ds

    def window(stream):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, next(stream), 1.0)
        float(np.asarray(m["loss"]))                      # fence
        return batch_size * steps / (time.perf_counter() - t0)

    # compile and fence before any timed window
    serial_stream, _ = make_stream(0)
    first = next(serial_stream)
    state, m = step(state, first, 1.0)
    float(np.asarray(m["loss"]))

    # step-only rate on the re-fed resident batch (no input pipeline):
    # the denominator every mode's host_bound_fraction shares.  Median
    # of 3 fenced windows after a warm window — a single cold window
    # under-reads the steady step rate on a shared host.
    def step_only_window():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, first, 1.0)
        float(np.asarray(m["loss"]))
        return batch_size * steps / (time.perf_counter() - t0)

    step_only_window()                        # warm
    step_rate = _median([step_only_window() for _ in range(3)])

    window(serial_stream)                     # warm cache + pipeline
    worker_counts = [1, 2, 4, 8] if not args.quick else [1, 2]
    summary = {}
    s_all = []
    for W in worker_counts:
        par_stream, par_loader = make_stream(W)
        next(par_stream)                      # spin the pool up
        window(par_stream)                    # warm window (untimed)
        s_rates, w_rates, _ = _interleaved_ab(
            lambda: window(serial_stream), lambda: window(par_stream),
            windows=3)
        par_stream.close()
        s_med, w_med = _median(s_rates), _median(w_rates)
        hbf_s = max(0.0, 1.0 - s_med / step_rate)
        hbf_w = max(0.0, 1.0 - w_med / step_rate)
        s_all.extend(s_rates)
        summary[W] = (w_med, hbf_w)
        _emit("host_wall_images_per_sec", w_med, "images/sec",
              w_med / max(s_med, 1e-9), num_workers=W,
              serial_windows=[round(x, 2) for x in s_rates],
              parallel_windows=[round(x, 2) for x in w_rates],
              host_bound_fraction_serial=round(hbf_s, 3),
              host_bound_fraction_parallel=round(hbf_w, 3),
              respawns=par_loader.respawns, spills=par_loader.spills,
              note="interleaved e2e windows, one process, equal link "
                   "state; vs_baseline = parallel/serial rate ratio")
    serial_stream.close()
    s_med = _median(s_all)
    best_w = max(summary, key=lambda k: summary[k][0])
    return _emit(
        "host_wall_host_bound_fraction", summary[best_w][1], "fraction",
        None, serial_host_bound_fraction=round(
            max(0.0, 1.0 - s_med / step_rate), 3),
        best_num_workers=best_w, step_images_per_sec=round(step_rate, 2),
        serial_images_per_sec=round(s_med, 2),
        parallel_images_per_sec=round(summary[best_w][0], 2),
        host_cpus=os.cpu_count(), batch=batch_size, resolution=res,
        device_step="ssd_vgg" if on_tpu else "light_conv_standin",
        note="host_bound_fraction at the best worker count vs the "
             "serial loader, same step/link/process; the input-wall "
             "deliverable of ISSUE r5 (acceptance: parallel < serial)")


def bench_serve_sched(args):
    """Serving-runtime scheduler cost (host-only, no device): (1) how
    many requests/sec the submit → EDF queue → batch assembly → dispatch
    loop moves with a no-op forward — the ceiling the host scheduler
    imposes on one serving cell (it must sit far above any realistic
    arrival rate, or the scheduler IS the wall); (2) a virtual-clock
    offered-load sweep (0.5×..4× of tier-0 capacity) recording miss
    rate, shed fraction and batch fill — the shape of the shedding
    frontier docs/SERVING.md describes, banked per bench run."""
    import numpy as np

    from analytics_zoo_tpu.resilience.errors import ServerOverloaded
    from analytics_zoo_tpu.serving import (ServingRuntime, ServingTier,
                                           VirtualClock)

    def noop_tier():
        return [ServingTier("noop",
                            lambda b: b["input"].reshape(
                                b["input"].shape[0], -1).sum(axis=1))]

    # -- host scheduler throughput (real wall time, virtual service) ------
    n = 500 if args.quick else 5000
    clock = VirtualClock()
    rt = ServingRuntime(noop_tier(), n_replicas=2, clock=clock,
                        queue_capacity=256, max_batch=8,
                        default_deadline_s=1.0, wedge_timeout_s=100.0,
                        service_time=lambda e, nv, t: 0.0)
    payload = {"input": np.ones((1, 16), np.float32)}
    t0 = time.perf_counter()
    for i in range(n):
        rt.submit(payload)
        clock.advance(1e-4)
        rt.pump()
    rt.drain()
    wall = time.perf_counter() - t0
    assert rt.accounting()["unaccounted"] == 0
    sched_rps = n / wall

    # -- offered-load sweep on the virtual clock --------------------------
    service_s, max_batch = 0.08, 8          # capacity = 100 req/s
    capacity = max_batch / service_s
    sweep = {}
    for load_x in (0.5, 1.0, 2.0, 4.0):
        clock = VirtualClock()
        rt = ServingRuntime(noop_tier(), n_replicas=1, clock=clock,
                            queue_capacity=64, max_batch=max_batch,
                            default_deadline_s=0.3, wedge_timeout_s=100.0,
                            service_time=lambda e, nv, t: service_s)
        gap = 1.0 / (capacity * load_x)
        n_req = 200 if args.quick else 2000
        for i in range(n_req):
            # open-loop offered load: deadlines anchor at the SCHEDULED
            # arrival instant (i * gap), so time the server spent busy
            # while this request waited to be admitted counts against it
            t_sched = i * gap
            if clock.now() < t_sched:
                clock.advance(t_sched - clock.now())
            try:
                rt.submit(payload,
                          deadline_s=t_sched + 0.3 - clock.now())
            except ServerOverloaded:    # accounted as shed by the queue
                pass
            rt.pump()
        rt.drain()
        m = rt.metrics.snapshot()
        assert rt.accounting()["unaccounted"] == 0
        sweep[f"{load_x:g}x"] = {
            "miss_rate": round(m["deadline_miss_rate"], 4),
            "shed_fraction": round(m["shed_total"] / m["submitted"], 4),
            "mean_batch_fill": round(m["mean_batch_fill"], 4),
        }
    return _emit("serve_sched_requests_per_sec", sched_rps, "req/s", None,
                 n_requests=n, load_sweep=sweep,
                 note="host scheduler ceiling (no-op forward, virtual "
                      "service); load_sweep = shedding frontier vs "
                      "offered load as a fraction of tier-0 capacity")


def obs_overhead_ab(hidden: int = 1024, in_dim: int = 32, batch: int = 128,
                    steps: int = 4, chunks: int = 30, warmup: int = 5):
    """Instrumented-vs-bare train-step A/B — the telemetry spine's cost,
    measured instead of assumed.

    Both sides run the SAME compiled train step over the SAME resident
    batch; the instrumented side additionally does exactly what
    ``Optimizer.set_observability`` does per step — start/end a span at
    the step's loader coordinates (two clock reads + a ring append) and
    feed a ``StepTimer`` registering into the shared ``MetricRegistry``
    (a reservoir observe + two counter incs).

    Measurement design: the signal is O(µs)/step against ~ms steps, so
    long A/B windows drown it in scheduler noise (observed ±30 % per
    window on a contended host).  Two mitigations, both banked: (1) the
    sides alternate in FINE-GRAINED pairs of short ``steps``-step
    chunks over a deliberately LARGE step (~25 ms at the defaults) with
    the headline as the RATIO OF TOTAL TIMES — local drift lands on
    both sides of each pair almost equally and cancels in the sums,
    per-pair ratios kept as the dispersion readout; (2) a DIRECT
    microbench of the pure instrumentation ops (span + StepTimer +
    registry, no jax) gives the per-step cost free of e2e noise —
    ``overhead_fraction_direct`` is that cost over the measured bare
    step time, and — being the only number resolvable above the e2e
    noise floor — is what the ≤ 3 % acceptance gates on (the ratio is
    banked as the no-hidden-systematic-cost evidence).  Returns the
    dict ``tools/obs_drill.py`` banks into ``OBS_r01.json``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.core.criterion import MSECriterion
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.obs import Observability
    from analytics_zoo_tpu.parallel import Adam, create_train_state, \
        make_train_step
    from analytics_zoo_tpu.utils.profiling import StepTimer

    class MLP(nn.Module):
        hidden: int

        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(self.hidden)(x))
            x = nn.relu(nn.Dense(self.hidden)(x))
            return nn.Dense(1)(x)

    model = Model(MLP(hidden))
    model.build(0, jnp.zeros((1, in_dim), jnp.float32))
    optim = Adam(1e-3)
    step = make_train_step(model.module, MSECriterion(), optim)
    rng = np.random.RandomState(0)
    dev_batch = {
        "input": jnp.asarray(rng.randn(batch, in_dim), jnp.float32),
        "target": jnp.asarray(rng.randn(batch, 1), jnp.float32)}
    state = create_train_state(model, optim)
    for _ in range(warmup):                      # compile + settle
        state, metrics = step(state, dev_batch, 1.0)
    jax.block_until_ready(metrics["loss"])

    obs = Observability(capacity=max(4096, chunks * steps + 64))
    timer = StepTimer("train/dispatch", registry=obs.registry)
    tracer = obs.tracer
    counters = {"it": 0}

    def chunk_bare():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, dev_batch, 1.0)
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    def chunk_instrumented():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            it = counters["it"]
            span = tracer.start("train_step", f"train-e0-b{it}",
                                iteration=it, epoch=0, batch=it)
            with timer.step(batch):
                state, metrics = step(state, dev_batch, 1.0)
            span.end(status="ok")
            counters["it"] = it + 1
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    t_bare = t_instr = 0.0
    pair_ratios = []                 # per-pair instr/bare RATE ratio
    for c in range(chunks):
        if c % 2 == 0:
            b = chunk_bare()
            i = chunk_instrumented()
        else:
            i = chunk_instrumented()
            b = chunk_bare()
        t_bare += b
        t_instr += i
        pair_ratios.append(b / max(i, 1e-12))
    ratio = t_bare / t_instr         # instrumented/bare rate, on totals

    # direct microbench: the pure per-step instrumentation ops with a
    # no-op "step" — the µs-scale cost, free of e2e scheduler noise
    obs_d = Observability(capacity=4096)
    timer_d = StepTimer("train/dispatch", registry=obs_d.registry)
    n_direct = 5000
    t0 = time.perf_counter()
    for i in range(n_direct):
        span = obs_d.tracer.start("train_step", f"train-e0-b{i}",
                                  iteration=i, epoch=0, batch=i)
        with timer_d.step(batch):
            pass
        span.end(status="ok")
    instr_us = (time.perf_counter() - t0) / n_direct * 1e6
    bare_step_us = t_bare / (chunks * steps) * 1e6
    direct_frac = instr_us / bare_step_us
    return {
        "config": {"hidden": hidden, "in_dim": in_dim, "batch": batch,
                   "steps_per_chunk": steps, "chunk_pairs": chunks},
        "bare_steps_per_sec": round(chunks * steps / t_bare, 2),
        "instrumented_steps_per_sec": round(chunks * steps / t_instr, 2),
        "pair_ratio_p25_p50_p75": [
            round(_median(sorted(pair_ratios)[:len(pair_ratios) // 2]), 4),
            round(_median(pair_ratios), 4),
            round(_median(sorted(pair_ratios)[len(pair_ratios) // 2:]), 4)],
        "ratio_of_totals": round(ratio, 4),
        "overhead_fraction": round(1.0 - ratio, 4),
        "instrumentation_us_per_step": round(instr_us, 2),
        "bare_step_us": round(bare_step_us, 1),
        "overhead_fraction_direct": round(direct_frac, 5),
        "spans_recorded": obs.tracer.spans_ended,
        "ring_dropped": obs.recorder.dropped,
        "registry_step_count": obs.registry.histogram(
            "train/dispatch/step_s").count,
        # the GATE is the direct measurement: the e2e ratio's noise
        # floor on a contended host (measured swings up to ±8 % of
        # TOTALS) sits above the µs-scale signal, so gating on it would
        # flake in both directions — it is banked as evidence that no
        # hidden systematic cost exists (ratio ≈ 1 within noise), while
        # the direct per-step cost over the measured bare step time is
        # the resolvable overhead number the bound applies to
        "overhead_le_3pct": direct_frac <= 0.03,
    }


def bench_obs_overhead(args):
    """bench.py phase wrapper: emit the instrumented-vs-bare A/B as one
    line; the committed execution lives in ``OBS_r01.json``
    (``tools/obs_drill.py`` calls :func:`obs_overhead_ab` directly)."""
    quick = args.quick
    # --quick only shortens the run (fewer chunk pairs); the MODEL
    # geometry stays at the full-size default — the spine's ~µs/step
    # host cost only reads meaningfully against a realistic ~25 ms step
    out = obs_overhead_ab(chunks=10 if quick else 60)
    return _emit("obs_overhead_step_ratio", out["ratio_of_totals"],
                 "instrumented/bare", None,
                 overhead_fraction=out["overhead_fraction"],
                 overhead_le_3pct=out["overhead_le_3pct"],
                 spans_recorded=out["spans_recorded"],
                 config=out["config"],
                 pair_ratio_p25_p50_p75=out["pair_ratio_p25_p50_p75"],
                 note="per-step cost of the obs spine (span + StepTimer "
                      "+ registry) on the Optimizer hot path; acceptance "
                      "<= 3% overhead")


def bench_detection_output_backends(args):
    """Pallas NMS vs XLA NMS on the same batch: parity + speed, on the
    real chip (VERDICT round-1 item 6)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.models import build_priors, ssd300_config
    from analytics_zoo_tpu.ops import DetectionOutputParam, detection_output

    priors, variances = build_priors(ssd300_config())
    n_p = priors.shape[0]
    rng = np.random.RandomState(0)
    b = max(2, args.batch // 4)
    loc = jnp.asarray(rng.randn(b, n_p, 4).astype(np.float32) * 0.1)
    logits = rng.randn(b, n_p, args.classes).astype(np.float32)
    logits[:, :, 0] += 2.0                     # mostly background, as served
    conf = jax.nn.softmax(jnp.asarray(logits), axis=-1)

    outs, times = {}, {}
    for backend in ("xla", "pallas"):
        p = DetectionOutputParam(n_classes=args.classes, backend=backend)
        f = jax.jit(lambda l, c, p=p: detection_output(
            l, c, jnp.asarray(priors), jnp.asarray(variances), p))
        o = f(loc, conf)
        np.asarray(o)     # warmup fence: compile + drain; inputs are
        #                   already device-committed so the timed window
        #                   that follows contains no host→device transfers
        t0 = time.perf_counter()
        for _ in range(args.nms_iters):
            o = f(loc, conf)
        # readback INSIDE the window (see bench_ssd_train fence note)
        outs[backend] = np.asarray(o)
        times[backend] = (time.perf_counter() - t0) / args.nms_iters

    # parity: kept-detection scores should agree (box sets can differ at
    # score ties); compare sorted score vectors per image
    sx = np.sort(outs["xla"][..., 1], axis=-1)
    sp = np.sort(outs["pallas"][..., 1], axis=-1)
    parity = float(np.abs(sx - sp).max())
    speedup = times["xla"] / max(times["pallas"], 1e-12)
    return _emit("detection_output_pallas_speedup_vs_xla", speedup, "x",
                 None, parity_max_score_diff=round(parity, 5),
                 xla_ms=round(times["xla"] * 1e3, 3),
                 pallas_ms=round(times["pallas"] * 1e3, 3),
                 backend=jax.default_backend())


def bench_ssd_detout(args):
    """ISSUE 12: the fused single-kernel DetectionOutput A/B plus the
    serving-runtime int8-vs-fp device-program ratio.

    Part 1 — unfused (backend="pallas", four staged programs) vs fused
    (backend="fused", one pallas_call) at EQUAL geometry on trained-like
    sparse conf, interleaved drift-cancelling windows, per-window
    values.  Off-TPU both kernels run interpret-mode: the fused side's
    in-kernel selection emulates at O(P) lanes per pop vs the unfused
    path's O(K) sweep, so the CPU ratio understates the kernel (the
    banked quantity there is parity + the per-side HBM-intermediate
    accounting; the compiled ratio banks on silicon).

    Part 2 — per-tier device-program latency measured THROUGH
    ``ServingRuntime``: fp vs int8 tiers of ``ssd_serving_tiers``
    dispatched by the real scheduler (forced-tier windows, interleaved),
    so the int8 rung's end-to-end worth is a serving-runtime reading,
    not a conv microbench.  On CPU int8 weight-only serving is fp math
    after dequant (ratio ≈ 1); the artifact records the measured ratio
    plus the on-TPU projection from the banked conv ratio and the
    fused detout share.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.models import build_priors, ssd300_config
    from analytics_zoo_tpu.ops import DetectionOutputParam, detection_output
    from analytics_zoo_tpu.utils import engine

    on_tpu = engine.on_tpu()
    quick = args.quick
    B = 2 if quick else args.detout_batch
    C = args.classes
    priors, variances = build_priors(ssd300_config())
    P = priors.shape[0]
    rng = np.random.RandomState(0)
    loc = jnp.asarray(rng.randn(B, P, 4).astype(np.float32) * 0.1)
    logits = rng.randn(B, P, C).astype(np.float32)
    logits[:, :, 0] += 7.0              # trained-like: background dominates
    hot = rng.rand(B, P) < 0.005        # a few confident foreground priors
    logits[:, :, 1:] += np.where(hot[:, :, None], 9.0, 0.0)
    conf = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    pri, var = jnp.asarray(priors), jnp.asarray(variances)

    posts = {"unfused": DetectionOutputParam(n_classes=C, backend="pallas"),
             "fused": DetectionOutputParam(n_classes=C, backend="fused")}
    fns = {name: jax.jit(lambda l, c_, p=p: detection_output(
        l, c_, pri, var, p)) for name, p in posts.items()}
    outs = {name: np.asarray(f(loc, conf)) for name, f in fns.items()}
    parity = float(np.abs(outs["unfused"] - outs["fused"]).max())

    iters = 2 if quick else args.detout_iters
    windows = 2 if quick else args.detout_windows

    def side(fn):
        def run():
            t0 = time.perf_counter()
            o = None
            for _ in range(iters):
                o = fn(loc, conf)
            np.asarray(o)               # readback fence inside the window
            return iters * B / (time.perf_counter() - t0)
        return run

    a_rates, b_rates, ratios = _interleaved_ab(
        side(fns["unfused"]), side(fns["fused"]), windows=windows)
    # per-side HBM bytes materialized BETWEEN stages (f32): the unfused
    # path round-trips decoded boxes + per-class top-k scores/idx/boxes;
    # the fused kernel's only intermediate state lives in VMEM
    Cf = C - 1
    k = min(((posts["fused"].nms_topk + 127) // 128) * 128,
            ((P + 127) // 128) * 128)
    # decoded (B,P,4) + per-class top-k scores/idx/boxes (B,Cf,k,{1,1,4})
    # + the sweep's keep mask (B,Cf,k), all f32/i32
    unfused_mb = B * (P * 4 + Cf * k * (1 + 1 + 4 + 1)) * 4 / 2**20
    ab = _emit(
        "ssd_detout_fused_vs_unfused_ratio", _median(ratios), "x", None,
        unfused_img_per_s=[round(v, 2) for v in a_rates],
        fused_img_per_s=[round(v, 2) for v in b_rates],
        per_window_ratios=[round(r, 3) for r in ratios],
        parity_max_abs_diff=round(parity, 6),
        batch=B, priors=int(P), classes=C, iters_per_window=iters,
        interpret_mode=not on_tpu, backend=jax.default_backend(),
        interstage_hbm_mb={"unfused": round(unfused_mb, 2), "fused": 0.0},
        note="equal geometry, interleaved windows, median of per-window "
             "fused/unfused ratios; off-TPU both kernels are "
             "interpret-mode emulation (the fused selection emulates at "
             "O(P) per pop vs the staged path's O(K) sweep — the ratio "
             "understates the kernel there); interstage_hbm_mb is the "
             "(B,C,K) traffic the fusion deletes, the term that pays on "
             "silicon")

    # ---- part 2: tier latency through the serving runtime ----------------
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg
    from analytics_zoo_tpu.pipelines import PreProcessParam
    from analytics_zoo_tpu.pipelines.ssd import ssd_serving_tiers
    from analytics_zoo_tpu.serving import ServingRuntime
    from tools.profile_serve import bias_background

    Bs = 2 if quick else args.detout_serve_batch
    model = Model(SSDVgg(num_classes=C, resolution=300))
    model.build(0, jnp.zeros((1, 300, 300, 3)))
    model.variables = {"params": bias_background(
        model.variables["params"], C, 7.0)}
    post = DetectionOutputParam(
        n_classes=C, backend="fused" if (on_tpu or not quick) else "auto")
    tiers = ssd_serving_tiers(
        model, PreProcessParam(batch_size=Bs, resolution=300),
        post=post, n_classes=C, compute_dtype=args.compute_dtype)
    rt = ServingRuntime(tiers, n_replicas=1, max_batch=Bs,
                        queue_capacity=8 * Bs, default_deadline_s=600.0)
    imgs = (rng.rand(Bs, 300, 300, 3).astype(np.float32) * 60.0)

    def dispatch_window(tier_idx):
        rt.ladder.tier = tier_idx       # forced rung (honest: recorded)
        for i in range(Bs):
            rt.submit({"input": imgs[i]})
        t0 = time.perf_counter()
        n = rt.pump(force=True)
        dt = time.perf_counter() - t0
        assert n == 1, f"expected one assembled batch, got {n}"
        return dt * 1e3

    dispatch_window(0)                  # compile fp
    dispatch_window(1)                  # compile int8
    serve_windows = 2 if quick else args.detout_serve_windows
    fp_ms, int8_ms, tier_ratios = [], [], []
    for w in range(serve_windows):
        order = (0, 1) if w % 2 == 0 else (1, 0)
        pair = {}
        for t in order:
            pair[t] = dispatch_window(t)
        fp_ms.append(pair[0])
        int8_ms.append(pair[1])
        tier_ratios.append(pair[1] / max(pair[0], 1e-9))
    # on-TPU projection: backbone share speeds up by the banked conv
    # ratio, the fused detout share does not (INT8_CONV_PROBE.json 1.3x;
    # detout share from the regenerated SERVE_PROFILE decomposition)
    conv_ratio = 1.3
    detout_share = args.detout_share_projection
    # same direction as the measured metric: int8/fp LATENCY (lower is
    # better) — the backbone share shrinks by the conv ratio, the fused
    # detout share does not
    projected = (1 - detout_share) / conv_ratio + detout_share
    serve_line = _emit(
        "ssd_detout_serving_int8_vs_fp_latency_ratio",
        _median(tier_ratios), "x", None,
        fp_ms_per_window=[round(v, 1) for v in fp_ms],
        int8_ms_per_window=[round(v, 1) for v in int8_ms],
        per_window_ratios=[round(r, 3) for r in tier_ratios],
        serve_batch=Bs, detout_backend=post.backend,
        requests_accounted=rt.accounting(),
        tiers=[t.name for t in rt.tiers],
        backend=jax.default_backend(),
        projected_tpu_latency_ratio_at_conv13x=round(projected, 3),
        detout_share_assumed=detout_share,
        note="per-tier device-program latency measured through "
             "ServingRuntime.pump (forced-tier interleaved windows, "
             "readback inside the runtime dispatch); on CPU weight-only "
             "int8 is dequant+fp math so the measured ratio banks the "
             "MECHANISM; projected_tpu_latency_ratio applies the banked "
             "1.3x conv reading to the non-detout share (same int8/fp "
             "direction as the measured value)")

    if args.detout_out:
        from analytics_zoo_tpu.obs import run_metadata

        artifact = {
            "round": 9,
            "phase": "ssd_detout",
            "context": "ISSUE 12 tentpole banking: (1) the fused "
                       "single-kernel DetectionOutput vs the four-stage "
                       "unfused path at equal geometry; (2) the int8 "
                       "ladder rung's device-program latency vs fp "
                       "measured through ServingRuntime — the serve-side "
                       "worth of int8 as a runtime reading plus the "
                       "on-TPU projection, not just the banked conv "
                       "ratio (INT8_CONV_PROBE.json)",
            "detout_ab": ab,
            "serving_tier_ab": serve_line,
            "run_metadata": run_metadata(
                "bench_ssd_detout", seed=0,
                extra={"quick": bool(quick)}),
        }
        with open(args.detout_out, "w") as f:
            json.dump(artifact, f, indent=2)
    return ab


def bench_ds2(args, mesh):
    import jax
    import numpy as np

    from analytics_zoo_tpu.pipelines.deepspeech2 import (
        DS2Param, DeepSpeech2Pipeline, make_ds2_model)

    param = DS2Param(segment_seconds=args.ds2_seconds,
                     batch_size=args.ds2_batch)
    model = make_ds2_model(hidden=args.ds2_hidden,
                           n_rnn_layers=args.ds2_layers,
                           utt_length=param.utt_length)
    pipe = DeepSpeech2Pipeline(model, param)

    rng = np.random.RandomState(0)
    n_utt = args.ds2_utts
    sec = args.ds2_seconds
    utts = {f"utt{i:03d}": rng.randn(16000 * sec).astype(np.float32) * 0.1
            for i in range(n_utt)}

    # both the TPU-friendly geometry AND reference parity (VERDICT r3
    # weak #4: the serialized reference DS2 is hidden 1760 — ~2.9x the
    # 1024 model's FLOPs; a committed line must exist at parity too)
    hiddens = ((args.ds2_hidden, 1760)
               if not args.quick and args.ds2_hidden != 1760
               else (args.ds2_hidden,))
    per_sec = None
    for hidden in hiddens:
        p = (pipe if hidden == args.ds2_hidden
             else DeepSpeech2Pipeline(
                 make_ds2_model(hidden=hidden, n_rnn_layers=args.ds2_layers,
                                utt_length=param.utt_length), param))
        p.transcribe_samples({"warm": utts["utt000"]})       # compile
        t0 = time.perf_counter()
        out = p.transcribe_samples(utts)
        dt = time.perf_counter() - t0
        assert len(out) == n_utt
        rate = n_utt / dt
        per_sec = per_sec if per_sec is not None else rate
        suffix = "" if hidden == args.ds2_hidden else f"_h{hidden}"
        _emit(f"ds2_utterances_per_sec{suffix}", rate, "utterances/sec",
              None, utterance_seconds=sec, hidden=hidden,
              layers=args.ds2_layers,
              realtime_factor=round(n_utt * sec / dt, 1),
              note="segment+FFT/mel featurize+forward+CTC decode+rejoin; "
                   "reference logs wall time only (batch-1 udf)"
                   + ("; hidden=1760 is the reference's serialized DS2 "
                      "geometry" if hidden == 1760 else ""))

    # streaming path: 1 s feeds through the stateful StreamingDS2 —
    # realtime factor = audio seconds per wall second (must be >> 1 to
    # keep up with a live source)
    from analytics_zoo_tpu.pipelines.deepspeech2 import StreamingDS2

    uni = make_ds2_model(hidden=args.ds2_hidden,
                         n_rnn_layers=args.ds2_layers,
                         utt_length=100, bidirectional=False)
    stream = StreamingDS2(uni)
    wave = rng.randn(16000 * sec).astype(np.float32) * 0.1
    # warm ALL THREE compiled shapes: >= 2 full 100-frame blocks (first
    # block + steady block) then flush — 33600 samples = 208 frames
    stream.accept(wave[:16000])
    stream.accept(wave[16000:33600])
    stream.flush()
    stream.reset()
    t0 = time.perf_counter()
    for k in range(0, len(wave), 16000):                     # 1 s feeds
        stream.accept(wave[k:k + 16000])
    stream.flush()
    dt_s = time.perf_counter() - t0
    rtf = sec / dt_s
    return _emit("ds2_streaming_realtime_factor", rtf, "x", None,
                 chunk_seconds=1,
                 note="stateful StreamingDS2 (unidirectional), 1 s feeds; "
                      "audio-seconds processed per wall-second")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=128)   # MFU knee (see
    # MFU_PROFILE.json batch sweep: 0.39 @ 32 → 0.54 @ 128); the
    # reference's own train config used batch 112 (ssd/README.md)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--wire-format", choices=("bgr", "yuv420"),
                   default="yuv420",
                   help="staged-pixel host→device wire format for the "
                        "device-aug train phase (yuv420 = 1.5 B/px)")
    p.add_argument("--no-pack", action="store_true",
                   help="stage the train batch as ~11 separate arrays "
                        "instead of one packed (B, item_bytes) transfer")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--res", type=int, default=300)
    p.add_argument("--classes", type=int, default=21)
    p.add_argument("--workers", type=int, default=max(os.cpu_count() or 8, 8))
    p.add_argument("--n-images", type=int, default=1024)
    p.add_argument("--compute-dtype", default="bf16")
    p.add_argument("--nms-iters", type=int, default=20)
    p.add_argument("--detout-batch", type=int, default=8,
                   help="ssd_detout phase: batch for the fused-vs-unfused "
                        "DetectionOutput A/B")
    p.add_argument("--detout-iters", type=int, default=4,
                   help="ssd_detout: dispatches per timed window")
    p.add_argument("--detout-windows", type=int, default=3,
                   help="ssd_detout: interleaved A/B window pairs")
    p.add_argument("--detout-serve-batch", type=int, default=4,
                   help="ssd_detout: ServingRuntime tier-latency batch")
    p.add_argument("--detout-serve-windows", type=int, default=3,
                   help="ssd_detout: forced-tier fp/int8 window pairs "
                        "through the runtime")
    p.add_argument("--detout-share-projection", type=float, default=0.14,
                   help="ssd_detout: DetectionOutput share of the serve "
                        "program assumed by the on-TPU int8 projection "
                        "(default = detout_fraction_of_serve in the "
                        "regenerated SERVE_PROFILE.json; update together)")
    p.add_argument("--detout-out", default="",
                   help="when set, also write the ssd_detout phase's two "
                        "readings as one run_metadata-stamped artifact "
                        "(the BENCH_r09.json banking path)")
    p.add_argument("--ds2-persistent-out", default="",
                   help="when set, also write the ds2_persistent "
                        "phase's fwd/train A/B lines as one "
                        "run_metadata-stamped artifact (the "
                        "BENCH_r10.json banking path)")
    p.add_argument("--rec-vocab", type=int, default=32768,
                   help="rec_embedding: table vocab (rows)")
    p.add_argument("--rec-dim", type=int, default=64,
                   help="rec_embedding: embedding feature dim")
    p.add_argument("--rec-batch", type=int, default=2048,
                   help="rec_embedding: id-batch positions per lookup "
                        "(one-hot side materializes batch x vocab)")
    p.add_argument("--rec-windows", type=int, default=3,
                   help="rec_embedding: interleaved A/B window pairs")
    p.add_argument("--rec-embedding-out", default="",
                   help="when set, also write the rec_embedding phase's "
                        "A/B + sweep lines as one run_metadata-stamped "
                        "artifact (the BENCH_r11.json banking path)")
    p.add_argument("--ds2-seconds", type=int, default=15)
    p.add_argument("--ds2-batch", type=int, default=8)
    p.add_argument("--ds2-train-batch", type=int, default=0,
                   help="ds2_train phase batch (0 = 4x --ds2-batch; the "
                        "scan-RNN train step is latency-bound at small "
                        "batches)")
    p.add_argument("--ds2-hidden", type=int, default=1024)
    p.add_argument("--ds2-layers", type=int, default=3)
    p.add_argument("--ds2-utts", type=int, default=32)
    p.add_argument("--ds2-block", type=int, default=16,
                   help="ds2_ragged fastpath scan block size U (unrolled "
                        "steps per scan iteration, core.rnn Recurrent)")
    p.add_argument("--ds2-buckets", type=int, default=5,
                   help="ds2_ragged: number of quantile-derived length "
                        "buckets")
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes/models for CI smoke (CPU-friendly)")
    p.add_argument("--skip", default="",
                   help="comma list: serve_sched,obs_overhead,nms,"
                        "ssd_detout,ds2,ds2_train,ds2_ragged,"
                        "ds2_persistent,ds2_globalbatch,rec_embedding,"
                        "ssd_serve,"
                        "ssd512_serve,frcnn_serve,frcnn_train,"
                        "ssd512_step,overlap,host_wall,ssd_train,"
                        "ssd_train_hostaug")
    p.add_argument("--sweep-log", default=os.path.join(
                       "bench_artifacts", "BENCH_sweeps.jsonl"),
                   help="jsonl file every emitted line is ALSO appended "
                        "to — exploratory sweeps accumulate under "
                        "bench_artifacts/ instead of littering the repo "
                        "root with per-run BENCH_rNN_*.jsonl files "
                        "(docs/PERFORMANCE.md artifact index).  Empty "
                        "string disables")
    p.add_argument("--no-isolate", action="store_true",
                   help="run all phases in THIS process instead of one "
                        "subprocess per phase (see note in main)")
    p.add_argument("--phase-timeout", type=int, default=2400,
                   help="seconds per phase subprocess; a hung phase then "
                        "yields an error line instead of blocking the "
                        "whole run forever.  <= 0 disables the limit")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args()
    global _SWEEP_LOG
    _SWEEP_LOG = args.sweep_log or None
    if args.quick:
        args.batch, args.steps, args.warmup, args.n_images = 4, 3, 1, 32
        args.ds2_hidden, args.ds2_layers, args.ds2_utts = 64, 1, 2
        args.ds2_seconds, args.ds2_batch, args.nms_iters = 2, 2, 2
        args.workers = 4
        args.rec_vocab, args.rec_dim, args.rec_batch = 2048, 16, 256
    skip = set(s for s in args.skip.split(",") if s)

    # cheap phases first; ssd_train stays last (the driver reads the LAST
    # line as headline)
    ALL_PHASES = ["serve_sched", "obs_overhead", "nms",
                  "ssd_detout", "ds2",
                  "ds2_train",
                  "ds2_ragged", "ds2_persistent", "ds2_globalbatch",
                  "rec_embedding",
                  "ssd_serve",
                  "ssd512_serve", "frcnn_serve",
                  "frcnn_train", "ssd512_step", "overlap", "host_wall",
                  "ssd_train_hostaug", "ssd_train"]
    if not args.child and not args.no_isolate:
        # One SUBPROCESS per phase, strictly one at a time, from a parent
        # that never imports jax.  The reason is the chip: a TPU belongs
        # to ONE process at a time, so a parent that had touched the
        # backend would hold it and every child would fail or hang at
        # start-up, and two children at once would contend for it.  A
        # process per phase also gives each phase the whole HBM.  Every
        # child imports the package and with it the persistent compile
        # cache (analytics_zoo_tpu/__init__.py), so a re-run compiles
        # nothing twice.  ssd_train runs last so the headline is the
        # final JSON line.
        import signal
        import subprocess

        passthrough = []
        argv = sys.argv[1:]
        i = 0
        while i < len(argv):
            if argv[i] == "--skip":
                i += 2
                continue
            if argv[i].startswith("--skip="):
                i += 1
                continue
            passthrough.append(argv[i])
            i += 1
        rc = 0
        limit = args.phase_timeout if args.phase_timeout > 0 else None
        for phase in ALL_PHASES:
            if phase in skip:
                continue
            child_skip = ",".join(q for q in ALL_PHASES if q != phase)
            cmd = [sys.executable, os.path.abspath(__file__), "--child",
                   "--skip", child_skip] + passthrough
            # new session so a timeout can kill the WHOLE group — a hung
            # loader-worker grandchild would otherwise survive the child
            # and keep the chip from the next phase
            proc = subprocess.Popen(cmd, start_new_session=True)
            try:
                phase_rc = proc.wait(timeout=limit)
                cause = f"phase child exited rc={phase_rc}"
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                phase_rc = -1       # killed by this parent, did not exit
                cause = f"phase exceeded {limit}s — killed by parent"
            if phase_rc != 0:
                # a failed phase fails the run, once: no retry — the
                # lines it emitted before dying stay above this record
                _emit(f"{phase}_exit", float(phase_rc), "returncode", None,
                      error=cause)
                rc = rc or phase_rc
        return rc

    from analytics_zoo_tpu.data import generate_shapes_records, read_ssd_records
    from analytics_zoo_tpu.parallel import create_mesh

    mesh = create_mesh()
    import jax

    global _DEVICE
    _DEVICE = {"platform": jax.devices()[0].platform,
               "device_kind": jax.devices()[0].device_kind,
               "device_count": jax.device_count()}
    n_dev = jax.device_count()
    if args.batch % n_dev:          # batch shards over the data axis
        args.batch = ((args.batch + n_dev - 1) // n_dev) * n_dev
    needs_shards = {"ssd_serve", "ssd512_serve", "frcnn_serve", "ssd_train",
                    "ssd_train_hostaug", "overlap", "host_wall"} - skip
    with tempfile.TemporaryDirectory() as tmp:
        pattern = os.path.join(tmp, "shapes-*.azr")
        records = []
        if needs_shards:
            shards = generate_shapes_records(
                os.path.join(tmp, "shapes"), n_images=args.n_images,
                resolution=args.res, num_shards=8, seed=0)
            records = list(read_ssd_records(shards))

        # --no-isolate: phases share one process and one HBM — for
        # debugging; the default subprocess-per-phase mode is the
        # configuration the numbers come from.
        headline = None
        if "serve_sched" not in skip:
            bench_serve_sched(args)     # host-only, never touches a device
        if "obs_overhead" not in skip:
            bench_obs_overhead(args)    # telemetry-spine step-cost A/B
        if "ssd_train" not in skip:
            headline = bench_ssd_train(args, mesh, pattern, device_aug=True)
        if "overlap" not in skip:
            bench_overlap(args, mesh, pattern)
        if "host_wall" not in skip:
            bench_host_wall(args, mesh, pattern)
        if "ssd_train_hostaug" not in skip:
            bench_ssd_train(args, mesh, pattern, device_aug=False)
        if "ssd_serve" not in skip:
            bench_ssd_serve(args, mesh, records[:min(len(records), 256)])
        if "nms" not in skip:
            bench_detection_output_backends(args)
        if "ssd_detout" not in skip:
            bench_ssd_detout(args)
        if "ds2" not in skip:
            bench_ds2(args, mesh)
        if "ds2_train" not in skip:
            bench_ds2_train(args, mesh)
        if "ds2_ragged" not in skip:
            bench_ds2_ragged(args, mesh)
        if "ds2_persistent" not in skip:
            bench_ds2_persistent(args, mesh)
        if "ds2_globalbatch" not in skip:
            bench_ds2_globalbatch(args, mesh)
        if "rec_embedding" not in skip:
            bench_rec_embedding(args, mesh)
        if "frcnn_serve" not in skip:
            bench_frcnn_serve(args, mesh, records[:min(len(records), 64)])
        if "ssd512_serve" not in skip and not args.quick:
            bench_ssd_serve(args, mesh, records[:min(len(records), 128)],
                            res=512)
        if "frcnn_train" not in skip:
            bench_frcnn_train(args, mesh)
        if "ssd512_step" not in skip and not args.quick:
            bench_ssd512_step(args, mesh)
        if headline is not None:
            per_chip, total, loss = headline
            _emit(f"ssd{args.res}_train_images_per_sec_per_chip",
                  per_chip, "images/sec/chip",
                  (total / REFERENCE_ANCHOR_IMAGES_PER_SEC
                   if args.res == 300 else None),
                  final_loss=round(float(loss), 3),
                  batch=args.batch, wire_format=args.wire_format,
                  packed=not args.no_pack,
                  anchor="LABELED ESTIMATE ~56 img/s: reference 4x28-core "
                         "Xeon cluster @ ~0.5 img/s/core; reference "
                         "publishes no absolute numbers (SURVEY.md §6). "
                         "Full input pipeline (device-side augmentation "
                         "path) inside the measurement.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
