"""Train Faster-RCNN end-to-end on rendered shapes and report VOC07 mAP
— accuracy evidence for the Faster-RCNN family, using a capability THE
REFERENCE DOES NOT HAVE (its proposal layer throws on backward; its
Faster-RCNN story is import-pretrained-and-serve only).

Same rendered-shapes methodology as ``train_shapes_e2e.py`` (exact
ground truth, full stack in the loop): generate → decode/augment →
approximate-joint training (RPN + head losses, ``ops.frcnn_train``) →
in-graph proposal/ROI-pool/per-class-NMS detector → VOC07 mAP.

Usage::

    python examples/train_frcnn_shapes.py --epochs 20 --out ACCURACY.md
"""

import argparse
import json
import logging
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--res", type=int, default=128)
    p.add_argument("--train-images", type=int, default=320)
    p.add_argument("--val-images", type=int, default=96)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--epochs", type=int, default=20)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--pre-nms", type=int, default=512)
    p.add_argument("--post-nms", type=int, default=64)
    p.add_argument("--anchor-scales", type=float, nargs="+",
                   default=[1, 2, 4],
                   help="anchor side = scale*16px.  The py-faster-rcnn "
                        "default (8,16,32) is sized for ~600px inputs; "
                        "at small --res those anchors all hang off the "
                        "image, every one is cross-boundary-ignored, and "
                        "the RPN never gets a positive")
    p.add_argument("--out", default=None)
    p.add_argument("--eval-every", type=int, default=0, metavar="N",
                   help="evaluate VOC07 mAP on the val set every N epochs "
                        "during training and record the trajectory (the "
                        "detector eval program compiles once; later probes "
                        "are cheap).  0 = final eval only")
    p.add_argument("--lr-decay-at", type=float, nargs="*", default=None,
                   metavar="FRAC",
                   help="multiply LR by 0.1 at these epoch fractions "
                        "(e.g. 0.6 0.85 — py-faster-rcnn style step decay)")
    p.add_argument("--params-out", default="frcnn_shapes_params.msgpack",
                   help="save trained variables here right after training "
                        "(a failure in the evaluation that follows then "
                        "does not lose the run)")
    p.add_argument("--eval-only", default=None, metavar="PARAMS_FILE",
                   help="skip training; evaluate saved variables "
                        "(shape-checked against the built model)")
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import generate_shapes_records
    from analytics_zoo_tpu.models import (FasterRcnnDetector, FasterRcnnVgg,
                                          FrcnnParam)
    from analytics_zoo_tpu.ops import ProposalParam
    from analytics_zoo_tpu.ops.frcnn import FrcnnPostParam
    from analytics_zoo_tpu.pipelines.evaluation import MeanAveragePrecision
    from analytics_zoo_tpu.pipelines.frcnn import train_frcnn
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 load_train_set,
                                                 load_val_set)

    classes = ["__background__", "rectangle", "ellipse", "triangle"]
    param = FrcnnParam(
        num_classes=len(classes),
        anchor_scales=tuple(args.anchor_scales),
        proposal=ProposalParam(pre_nms_topn=args.pre_nms,
                               post_nms_topn=args.post_nms))

    with tempfile.TemporaryDirectory() as tmp:
        train_shards = generate_shapes_records(
            os.path.join(tmp, "train"), n_images=args.train_images,
            resolution=args.res, num_shards=4, seed=0)
        val_shards = generate_shapes_records(
            os.path.join(tmp, "val"), n_images=args.val_images,
            resolution=args.res, num_shards=2, seed=100)
        pp = PreProcessParam(batch_size=args.batch_size,
                             resolution=args.res, max_gt=8)
        # augment=False: shuffled + flipped but NO Expand/zoom-out — that
        # chain shrinks objects well below the stride-16 feature grid at
        # small --res (observed 7px gt = half a feature cell, invisible
        # to RPN anchors and ROI pooling)
        train_set = load_train_set(os.path.join(tmp, "train-*.azr"), pp,
                                   augment=False)
        val_set = load_val_set(os.path.join(tmp, "val-*.azr"), pp)

        model = Model(FasterRcnnVgg(param=param))
        model.build(0, jnp.zeros((1, args.res, args.res, 3), jnp.float32),
                    jnp.asarray([[args.res, args.res, 1.0]], jnp.float32))

        # the serving assembly; built ONCE so the jitted eval program
        # compiles once and every trajectory probe reuses it
        det = FasterRcnnDetector(
            param=param,
            post=FrcnnPostParam(nms_thresh=0.3, conf_thresh=0.05,
                                nms_topk=args.post_nms, max_per_image=20))
        fwd = jax.jit(lambda v, x, info: det.apply(v, x, info))
        # host-materialized val batches: re-decoding per probe would make
        # the trajectory cost scale with the host chain, not the chip
        val_batches = list(val_set)

        def evaluate(frcnn_params):
            # params may arrive as HOST numpy (e.g. after optimize() writes
            # the trained variables back, or --eval-only's load): commit
            # them to device ONCE, or every fwd call below re-uploads the
            # full ~500 MB tree
            variables = jax.device_put({"params": {"frcnn": frcnn_params}})
            evaluator = MeanAveragePrecision(n_classes=len(classes),
                                             class_names=classes)
            total = None
            for batch in val_batches:
                B = batch["input"].shape[0]
                info = jnp.tile(jnp.asarray([[args.res, args.res, 1.0]],
                                            jnp.float32), (B, 1))
                dets = np.array(fwd(variables, jnp.asarray(batch["input"]),
                                    info))
                dets[..., 2:6] /= args.res      # pixel → normalized (gt space)
                r = evaluator(dets, batch)
                total = r if total is None else total + r
            return total.result(), total.ap_per_class()

        trajectory = []

        def probe(loop, state):
            if args.eval_every and loop.epoch % args.eval_every == 0:
                m, _ = evaluate(state.params)
                trajectory.append({"epoch": loop.epoch,
                                   "map_voc07": round(float(m), 4)})
                logging.info("mAP trajectory @ epoch %d: %.4f",
                             loop.epoch, float(m))
                if args.params_out:
                    # crash insurance: hours of training are in this state
                    from flax import serialization
                    from analytics_zoo_tpu.parallel.train import \
                        state_to_variables
                    with open(args.params_out + ".latest", "wb") as f:
                        f.write(serialization.to_bytes(
                            jax.device_get(state_to_variables(state))))

        schedule = None
        if args.lr_decay_at:
            from analytics_zoo_tpu.parallel.optim import multistep
            iters_per_epoch = -(-args.train_images // args.batch_size)
            schedule = multistep(
                args.lr,
                [int(f * args.epochs * iters_per_epoch)
                 for f in args.lr_decay_at])

        t0 = time.time()
        if args.eval_only:
            model.load(args.eval_only)     # from_bytes shape-checks vs build
            wall = 0.0
        else:
            train_frcnn(model, train_set, args.res, epochs=args.epochs,
                        lr=args.lr, lr_schedule=schedule,
                        epoch_hook=probe if args.eval_every else None)
            wall = time.time() - t0
            if args.params_out:
                model.save(args.params_out)

        mean_ap, per_class = evaluate(model.params)

        report = {
            "task": "Faster-RCNN-VGG from scratch on rendered shapes "
                    "(3 classes) — reference cannot train this family",
            "final_map_voc07": round(float(mean_ap), 4),
            "ap_per_class": {c: round(float(a), 4)
                             for c, a in zip(classes[1:], per_class[1:])},
            "resolution": args.res,
            "train_images": args.train_images,
            "val_images": args.val_images,
            "epochs": args.epochs,
            "wall_seconds": round(wall, 1),
            "backend": jax.default_backend(),
        }
        if trajectory:
            report["map_trajectory"] = trajectory
        if args.lr_decay_at:
            report["lr_decay_at"] = args.lr_decay_at
        print(json.dumps(report))
        if args.out:
            from analytics_zoo_tpu.utils.report import append_report
            append_report(args.out, "Faster-RCNN shapes end-to-end",
                          "examples/train_frcnn_shapes.py", report)


if __name__ == "__main__":
    main()
