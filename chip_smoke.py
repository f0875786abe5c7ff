"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the SSD300-VGG16 main path once, through the entry points a user
calls, at the width the repo ships (21 classes, 300x300, 8,732 priors,
bf16 compute, global batch 32):

- **train**: seeded ``generate_shapes_records`` -> ``.azr`` shards ->
  ``load_train_set_device`` -> ``train_ssd(..., mesh=create_mesh())``
  (``pipeline_specs("ssd")`` -> ``SpecSet`` -> ``Optimizer.optimize()``,
  ``prefetch=2``) for 7 optimizer steps; every loss finite, the last lower
  than the first;
- **serve**: ``ServingRuntime(ssd_serving_tiers(model, param),
  n_replicas=1, max_batch=8)`` on the real clock, geometries compiled by
  ``ServingRuntime.warm``, 16 requests, every one answered with a finite
  ``(200, 6)`` array, zero wedges / sheds / timeouts / failovers, on a
  DetectionOutput backend that was compiled (not interpreted) and that
  agrees with the XLA reference on a small seeded input.  One replica:
  the runtime puts every replica on the first device(s) (ROADMAP S7).

Run ``python3 chip_smoke.py`` from the checkout root on a machine with a
TPU.  It refuses to run on anything else, uses every device JAX finds,
lets this one process hold the chip (its only child is ``make`` building
the native decoder), generates all inputs from a seed under
``chiprun_out/``, and prints as its last line
``{"ok": true, "device": {...}}``.  Any phase failing is a non-zero exit:
nothing here catches or retries.  The seconds it prints are smoke
timings — not metrics, and they belong in no table.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import sys
import time

# the program under test; importing it also places the compile cache
# (analytics_zoo_tpu/__init__.py).  A directory that holds this script and
# nothing else of the repo fails right here.
import analytics_zoo_tpu  # noqa: F401

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 0
BATCH = 32              # TrainParams default, rounded up to the device count
TRAIN_STEPS = 7         # the compile step + 6 more
# TrainParams' 0.0035 is tuned for fine-tuning pretrained VGG weights; from
# the seeded random weights used here it diverges by the second step (loss
# 22 -> 48 -> 197 at batch 8, where 1e-4 gives 21.5 -> 16.4 over 7 steps)
LEARNING_RATE = 1e-4
MAX_BATCH = 8
N_REQUESTS = 16
# the jitted programs whose compile-cache fate main() reports
MAIN_PROGRAMS = {"jit_step_fn", "jit_detect", "jit_fused_detection_output"}


class CacheLog(logging.Filter):
    """Collects the names of the programs JAX's persistent compile cache
    served (``hits``) or had to compile (``misses``).  JAX logs both at
    DEBUG on ``jax._src.compiler``, naming the jitted program; this filter
    reads those records and drops them, so nothing else gets noisier."""

    def __init__(self):
        super().__init__()
        self.hits, self.misses = [], []

    def filter(self, record) -> bool:
        msg = str(record.msg)
        if msg.startswith("Persistent compilation cache hit"):
            self.hits.append(record.args[0])
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(record.args[0])
        return record.levelno > logging.DEBUG

    def install(self) -> "CacheLog":
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.addFilter(self)
        return self


def device_report() -> dict:
    """Refuse anything but a TPU; otherwise say what JAX found."""
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform={platform!r} "
                 f"({len(devices)} device(s)) — refusing to run")
    from importlib.metadata import version

    libtpu = version("libtpu")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: platform={platform} device_kind={device['kind']!r} "
          f"count={device['count']}  jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    return device


def build_native() -> None:
    """Build the JPEG decoder from committed source (git ignores the
    ``.so``) and fail if it does not build or does not decode, so a clean
    checkout takes the same decode path as a developer's tree."""
    import cv2
    import numpy as np

    from analytics_zoo_tpu.data import native

    native.build()
    ok, jpg = cv2.imencode(".jpg", np.full((8, 8, 3), 127, np.uint8))
    if not ok or native.decode_jpeg(jpg.tobytes()) is None:
        raise RuntimeError("native/libazrecord.so built but cannot decode")
    print("input path: decoder=native libjpeg (native/azrecord.cpp, built "
          "here), record reader=python (data.records.read_records)")


def train_phase(workdir: str, *, batch: int = BATCH, steps: int = TRAIN_STEPS,
                model=None, learning_rate: float = LEARNING_RATE,
                seed: int = SEED) -> dict:
    """``steps`` optimizer steps of SSD300 through ``train_ssd`` on all
    devices — ``model=None`` is the shipped SSD300-VGG16 (the tier-1 test
    passes a toy trunk with the same 8,732-prior heads, and the rate that
    suits it).  Returns the trained model and the per-step losses; raises
    if a loss is non-finite or the last is not below the first.  The
    in-process loader's augmentation draws are not seeded, so losses
    repeat only to the first decimal."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.data import generate_shapes_records
    from analytics_zoo_tpu.parallel import create_mesh
    from analytics_zoo_tpu.parallel.summary import read_scalars
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 TrainParams,
                                                 load_train_set_device,
                                                 train_ssd)

    n_dev = len(jax.devices())
    batch = -(-batch // n_dev) * n_dev
    t0 = time.time()
    generate_shapes_records(os.path.join(workdir, "shapes"),
                            n_images=batch * steps, resolution=300,
                            num_shards=2, seed=seed)
    train_set, augment = load_train_set_device(
        os.path.join(workdir, "shapes-*.azr"),
        PreProcessParam(batch_size=batch, resolution=300))
    params = TrainParams(batch_size=batch, max_epoch=1,
                         learning_rate=learning_rate,
                         log_dir=os.path.join(workdir, "tb"),
                         job_name="chip_smoke")
    mesh = create_mesh()
    t_data = time.time()
    model = train_ssd(train_set, None, params, model=model, mesh=mesh,
                      device_transform=augment)
    t_end = time.time()

    _, losses, walls = zip(*read_scalars(
        os.path.join(workdir, "tb", "chip_smoke", "train"))["Loss"])
    print(f"train: mesh={dict(mesh.shape)} global_batch={batch} "
          f"compute_dtype={params.compute_dtype} steps={len(losses)}")
    print("train: loss per step: " + " ".join(f"{v:.4f}" for v in losses))
    if len(losses) != steps:
        raise RuntimeError(f"expected {steps} steps, summary has "
                           f"{len(losses)}")
    if not np.isfinite(losses).all():
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not go down: first {losses[0]:.4f}, "
                           f"last {losses[-1]:.4f}")
    # smoke timings, not metrics: the summary's float(loss) fences a step
    print(f"train: smoke timings — data {t_data - t0:.1f}s, set-up + "
          f"compile step {walls[0] - t_data:.1f}s, {steps - 1} steady steps "
          f"{walls[-1] - walls[0]:.2f}s, total {t_end - t0:.1f}s")
    return {"model": model, "losses": list(losses)}


def check_every_device_used(model) -> None:
    """Training used all devices JAX found: each one's peak holds at least
    one copy of the parameters.  (Bytes in use NOW would not say so: the
    trainer hands the state back to the host when it returns — four-chip
    run, PR 21: 39 MB on device 0, 29 KB on the others.)"""
    import jax

    params = sum(x.nbytes for x in jax.tree_util.tree_leaves(model.variables))
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"train: parameters {params} bytes; peak_bytes_in_use per device: "
          + " ".join(map(str, peaks)))
    if min(peaks) < params:
        raise RuntimeError("a device never held a copy of the parameters")


def check_detection_backend(seed: int = SEED):
    """The DetectionOutput backend ``"auto"`` resolves to at the SSD300
    geometry must be a compiled one, and must agree with the XLA
    reference on a small seeded, trained-like input."""
    import dataclasses

    import jax
    import numpy as np

    from analytics_zoo_tpu.models import build_priors, ssd300_config
    from analytics_zoo_tpu.ops.detection_output import (
        DetectionOutputParam, detection_output, resolve_backend)

    post = DetectionOutputParam()
    priors, variances = build_priors(ssd300_config())
    P, C = priors.shape[0], post.n_classes
    resolved = resolve_backend(post, P, C)
    print(f"serve: DetectionOutput backend 'auto' -> {resolved.name!r} "
          f"(interpret={resolved.interpret})")
    if resolved.interpret:
        raise RuntimeError("DetectionOutput would run in interpret mode")
    rng = np.random.RandomState(seed)
    loc = (rng.randn(2, P, 4) * 0.1).astype(np.float32)
    logits = rng.randn(2, P, C).astype(np.float32)
    logits[..., 0] += 6.0                       # background-dominated
    logits[..., 1:] += np.where(rng.rand(2, P, 1) < 0.03, 9.0, 0.0)
    conf = np.asarray(jax.nn.softmax(logits, axis=-1))
    got = np.asarray(detection_output(loc, conf, priors, variances, post))
    ref = np.asarray(detection_output(
        loc, conf, priors, variances,
        dataclasses.replace(post, backend="xla")))
    np.testing.assert_array_equal(got[..., 0], ref[..., 0])
    np.testing.assert_allclose(got[..., 1], ref[..., 1], atol=1e-5)
    np.testing.assert_allclose(got[..., 2:], ref[..., 2:], atol=1e-4)
    print(f"serve: {resolved.name!r} agrees with the XLA reference on a "
          f"(2, {P}, {C}) input: {int((ref[..., 1] > 0).sum())} detections")
    return resolved


def serve_phase(model, *, max_batch: int = MAX_BATCH,
                n_requests: int = N_REQUESTS, deadline_s: float = 1.0,
                seed: int = SEED) -> dict:
    """``n_requests`` requests through a one-replica ``ServingRuntime``
    on the real clock.  Raises unless every one is answered inside
    ``deadline_s`` (the runtime's default) with a finite ``(keep_topk,
    6)`` array and nothing wedged, shed, timed out or failed over."""
    import numpy as np

    from analytics_zoo_tpu.data.synthetic import render_shapes_image
    from analytics_zoo_tpu.ops import DetectionOutputParam
    from analytics_zoo_tpu.pipelines.ssd import (BGR_MEANS, PreProcessParam,
                                                 TrainParams,
                                                 ssd_serving_tiers)
    from analytics_zoo_tpu.serving import ServingRuntime

    resolved = check_detection_backend(seed)
    param = PreProcessParam(batch_size=max_batch, resolution=300)
    tiers = ssd_serving_tiers(model, param,
                              compute_dtype=TrainParams().compute_dtype)
    if resolved.name != "xla":
        # the resolved name is the chooser's word; the lowered serving
        # program is the proof a Mosaic kernel is in it
        fn, args, _ = tiers[0].device_program()
        if "tpu_custom_call" not in fn.lower(*args).as_text():
            raise RuntimeError("no Mosaic kernel in the serving program")
    runtime = ServingRuntime(tiers, n_replicas=1, max_batch=max_batch,
                             default_deadline_s=deadline_s)
    rng = np.random.RandomState(seed)
    means = np.asarray(BGR_MEANS, np.float32)
    payloads = [{"input": render_shapes_image(rng, 300, 3)[0]
                 .astype(np.float32) - means} for _ in range(n_requests)]

    t0 = time.time()
    warmed = runtime.warm(payloads[0])
    t_warm = time.time()
    requests = [runtime.submit(p) for p in payloads]
    runtime.pump()
    runtime.drain()
    t_end = time.time()

    snap = runtime.snapshot()
    metrics, pool = snap["metrics"], snap["replicas"]
    wedges = sum(r["wedges"] for r in pool["replicas"])
    print(f"serve: tiers={[t.name for t in tiers]} replicas=1 (every "
          f"replica sits on the first device: ROADMAP S7) "
          f"max_batch={max_batch}")
    print(f"serve: submitted={metrics['submitted']} "
          f"completed={metrics['completed']} failed={metrics['failed']} "
          f"shed={metrics['shed_total']} wedges={wedges} "
          f"failovers={metrics['redispatched_batches']} "
          f"late={metrics['deadline_misses_completed_late']} "
          f"batches={metrics['batches']}")
    states = [r.state for r in requests]
    if states != ["done"] * n_requests:
        raise RuntimeError(f"not every request was answered: {states}")
    if (wedges or metrics["failed"] or metrics["shed_total"]
            or metrics["redispatched_batches"]
            or metrics["deadline_misses_completed_late"]
            or snap["accounting"]["unaccounted"]):
        raise RuntimeError(f"serving was not clean: {metrics} {pool}")
    keep = DetectionOutputParam().keep_topk     # the tiers' default post
    for r in requests:
        out = np.asarray(r.result)
        if out.shape != (keep, 6) or not np.isfinite(out).all():
            raise RuntimeError(f"request {r.rid}: bad answer {out.shape}")
    print("serve: smoke timings — warm (compile) "
          + ", ".join(f"{tiers[k[2]].name} {s:.1f}s"
                      for k, s in warmed.items())
          + f"; {n_requests} requests {t_end - t_warm:.2f}s; total "
          f"{t_end - t0:.1f}s")
    return {"backend": resolved.name, "answered": n_requests}


def main() -> int:
    cache = CacheLog().install()
    device = device_report()
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    build_native()
    trained = train_phase(WORKDIR)
    check_every_device_used(trained["model"])
    n_hit, n_miss = len(cache.hits), len(cache.misses)
    served = serve_phase(trained["model"])
    # programs under the cache's 1 s threshold always "miss"; the two that
    # matter are the train step and the serve program
    for phase, hits, misses in (
            ("train", cache.hits[:n_hit], cache.misses[:n_miss]),
            ("serve", cache.hits[n_hit:], cache.misses[n_miss:])):
        print(f"compile cache: {phase} phase hits={sorted(set(hits))} "
              f"compiled={sorted(set(misses) & MAIN_PROGRAMS)}")
    print(f"chip_smoke: train {len(trained['losses'])} steps "
          f"{trained['losses'][0]:.4f} -> {trained['losses'][-1]:.4f}; "
          f"serve {served['answered']} answered on {served['backend']!r}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
