"""Interleaved A/B of the host→device wire formats (bgr vs yuv420) on
the END-TO-END device-aug train path.

Why a dedicated tool: host→device bandwidth differs between processes
and drifts within a run, so comparing one bench run per wire format
measures the state of the link as much as the format.  Here both
configurations run in ONE process, in ALTERNATING windows, so every
window sees the same link and the ratio isolates the wire format
itself.  Report per-window rates plus the median ratio.

Writes one JSON to --out (default WIRE_AB.json); last stdout line is the
summary JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable from a checkout without installing the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--steps", type=int, default=8, help="batches per window")
    p.add_argument("--windows", type=int, default=3, help="windows per wire")
    p.add_argument("--res", type=int, default=300)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--n-images", type=int, default=512)
    p.add_argument("--out", default="WIRE_AB.json")
    args = p.parse_args()

    import tempfile

    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import device_prefetch, generate_shapes_records
    from analytics_zoo_tpu.models import SSDVgg, build_priors
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (SGD, create_mesh,
                                            create_train_state,
                                            make_train_step, replicate)
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 load_train_set_device)

    res = args.res
    mesh = create_mesh()
    tmp = tempfile.mkdtemp()
    generate_shapes_records(os.path.join(tmp, "s"), n_images=args.n_images,
                            resolution=res, num_shards=8, seed=0)
    pattern = os.path.join(tmp, "s-*.azr")

    model = Model(SSDVgg(num_classes=21, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    priors, variances = build_priors(model.module.config)
    criterion = MultiBoxLoss(priors, variances, MultiBoxLossParam())
    host_state0 = jax.device_get(
        create_train_state(model, SGD(1e-3, momentum=0.9)))

    rigs = {}
    for name, wire, pack in (("bgr", "bgr", False),
                             ("yuv420", "yuv420", False),
                             ("yuv420_packed", "yuv420", True)):
        param = PreProcessParam(batch_size=args.batch, resolution=res,
                                num_workers=args.workers, max_gt=8,
                                canvas_size=((res + 7) // 8) * 8,
                                wire_format=wire, pack_staging=pack)
        ds, aug = load_train_set_device(pattern, param)
        step = make_train_step(model.module, criterion,
                               SGD(1e-3, momentum=0.9), mesh=mesh,
                               compute_dtype="bf16", device_transform=aug)
        rigs[name] = {"ds": ds, "step": step,
                      "state": replicate(host_state0, mesh),
                      "stream": None, "windows": []}

    def next_batch(rig):
        # epoch-looping prefetched stream shared across windows
        if rig["stream"] is None:
            def gen():
                while True:
                    yield from device_prefetch(iter(rig["ds"]), mesh)
            rig["stream"] = gen()
        return next(rig["stream"])

    # compile + warm both rigs and fence, so no window pays a compile
    last = {}
    for wire, rig in rigs.items():
        rig["state"], m = rig["step"](rig["state"], next_batch(rig), 1.0)
        last[wire] = m["loss"]
    for wire in rigs:
        float(np.asarray(last[wire]))

    # Rotate the rig order each window: on a monotonically drifting link a
    # fixed order biases whichever config always runs later in the window
    # (same reason bench.py's int8 comparison alternates order).
    names = list(rigs)
    for w in range(args.windows):
        for wire in names[w % len(names):] + names[:w % len(names)]:
            rig = rigs[wire]
            t0 = time.perf_counter()
            for _ in range(args.steps):
                rig["state"], m = rig["step"](rig["state"],
                                              next_batch(rig), 1.0)
            float(np.asarray(m["loss"]))           # fence ends the window
            dt = time.perf_counter() - t0
            rate = args.batch * args.steps / dt
            rig["windows"].append(round(rate, 2))
            print(json.dumps({"window": w, "wire": wire,
                              "images_per_sec": round(rate, 2)}), flush=True)

    import statistics

    med = {w: round(statistics.median(r["windows"]), 2)
           for w, r in rigs.items()}
    report = {
        "batch": args.batch, "steps_per_window": args.steps,
        "windows": {w: r["windows"] for w, r in rigs.items()},
        "median_images_per_sec": med,
        "yuv420_speedup": round(med["yuv420"] / med["bgr"], 3),
        "packed_speedup_vs_bgr": round(med["yuv420_packed"] / med["bgr"], 3),
        "note": "interleaved windows in one process; the ratio "
                "isolates wire format from link drift",
    }
    print(json.dumps(report))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
