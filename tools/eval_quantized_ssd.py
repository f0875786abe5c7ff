"""Serving-accuracy cost of int8 quantization on a TRAINED SSD model.

``tests/test_quantize.py`` pins int8 numerics on untrained nets; this
tool closes the remaining evidence gap: VOC07 mAP of the SAME trained
weights served three ways — fp, weight-only int8 (``quantize=True``),
and int8 COMPUTE (``quantize="int8"``) — on a freshly generated shapes
val set.  Train the weights first, e.g.::

    python examples/train_shapes_e2e.py --target-map 0.9 \
        --params-out ssd_shapes.msgpack
    python tools/eval_quantized_ssd.py --params ssd_shapes.msgpack

Writes one JSON to --out (default INT8_MAP_PARITY.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--params", required=True)
    p.add_argument("--resolution", type=int, default=300)
    p.add_argument("--val-images", type=int, default=200)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seed", type=int, default=1,
                   help="val-set seed (train_shapes_e2e uses seed 1 for "
                        "its val split)")
    p.add_argument("--out", default="INT8_MAP_PARITY.json")
    p.add_argument("--backend", default="fused",
                   choices=("fused", "pallas", "xla", "auto"),
                   help="DetectionOutput backend for every served config "
                        "(default: the FUSED single-kernel program, "
                        "interpret-mode off-TPU) — quantized-ACCURACY "
                        "numbers then come from the same device program "
                        "the serving tiers dispatch, not a parallel "
                        "decomposition that could drift")
    p.add_argument("--approx", action="store_true",
                   help="also evaluate fp serving with "
                        "DetectionOutputParam(approx_topk=True) — the "
                        "recall-0.95 candidate selection — to measure its "
                        "mAP cost on a trained model (TPU: real "
                        "approx_max_k; CPU lowering is exact)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import SHAPE_CLASSES, generate_shapes_records
    from analytics_zoo_tpu.models import SSDVgg
    from analytics_zoo_tpu.ops import DetectionOutputParam
    from analytics_zoo_tpu.pipelines import PreProcessParam, Validator
    from analytics_zoo_tpu.pipelines.evaluation import (
        MeanAveragePrecision, PascalVocEvaluator)
    from analytics_zoo_tpu.pipelines.ssd import load_val_set
    from analytics_zoo_tpu.utils import engine

    n_classes = len(SHAPE_CLASSES)
    res = args.resolution
    model = Model(SSDVgg(num_classes=n_classes, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    model.load(args.params)

    with tempfile.TemporaryDirectory() as tmp:
        generate_shapes_records(os.path.join(tmp, "val"),
                                n_images=args.val_images, resolution=res,
                                num_shards=2, seed=args.seed)
        pre = PreProcessParam(batch_size=args.batch_size, resolution=res,
                              max_gt=8)
        results = {}
        post = DetectionOutputParam(n_classes=n_classes,
                                    backend=args.backend)
        configs = [("fp", False, post),
                   ("int8_weight_only", True, post),
                   ("int8_compute", "int8", post)]
        if args.approx:
            if not engine.on_tpu():
                # CPU lowers approx_max_k exactly AND runs the pallas
                # kernel in interpret mode: delta_approx_topk == 0 by
                # construction there — not evidence of TPU safety
                print("WARNING: --approx on a non-TPU backend: "
                      "approx_max_k lowers EXACTLY here, so "
                      "delta_approx_topk==0 is vacuous; run on TPU for "
                      "meaningful data", file=sys.stderr)
            configs.append(
                ("fp_approx_topk", False,
                 DetectionOutputParam(n_classes=n_classes,
                                      backend="pallas", approx_topk=True)))
        for name, mode, post in configs:
            val_set = load_val_set(os.path.join(tmp, "val-*.azr"), pre)
            validator = Validator(
                model, pre,
                evaluator=MeanAveragePrecision(n_classes=n_classes),
                post=post,
                quantize=mode)
            r = validator.test(val_set)
            m = PascalVocEvaluator(class_names=SHAPE_CLASSES).evaluate(r)
            results[name] = float(m)       # raw: deltas must not be
            #                                rounding artifacts
            print(json.dumps({name: round(results[name], 4)}), flush=True)

    report = {
        "task": "VOC07 mAP of ONE trained SSD served fp vs int8 "
                "(weight-only and real int8 compute), same val set",
        "resolution": res, "val_images": args.val_images,
        "detout_backend": args.backend,
        "map": {k: round(v, 4) for k, v in results.items()},
        "delta_weight_only": round(results["int8_weight_only"]
                                   - results["fp"], 6),
        "delta_int8_compute": round(results["int8_compute"]
                                    - results["fp"], 6),
        "backend": jax.default_backend(),
    }
    if "fp_approx_topk" in results:
        report["delta_approx_topk"] = round(results["fp_approx_topk"]
                                            - results["fp"], 6)
    print(json.dumps(report))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
