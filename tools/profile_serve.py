"""Decompose the SSD serve program: backbone vs DetectionOutput, and
DetectionOutput's internals as a stage ladder that SUMS.

Coherence contract, two levels:

1. **Program level** (round-5): ``full ≈ backbone + detection_output
   (+ small jit-boundary residual)``, with the residual reported
   explicitly.  The trained-like conf distribution is baked into the
   conf-head biases (+bg_bias on the background channel, layout
   ``a*C + 0`` — see ``models/ssd.py:224-227``) so whole and parts see
   the same data; every standalone stage is timed on the (loc, conf)
   the biased backbone ACTUALLY produced.

2. **DetectionOutput level** (round-9): the internals ladder must sum
   to the DetectionOutput total.  The pre-r9 version violated this —
   it timed the PALLAS path's internals (decode+topk 21 + sweep 60 +
   final topk 5 ≈ 86 ms) under a DetectionOutput total measured on
   whatever backend ``auto`` resolved to (518 ms on CPU → a −423 ms
   term no stage owned).  The fused backend
   (``ops/pallas_detout.py``) makes the ladder coherent BY
   CONSTRUCTION: each rung is a PREFIX program of the same kernel
   (``stage="decode" | "select" | "full"``), so rung deltas are stage
   costs and they telescope to the fused total exactly; the only
   incoherence left is window noise, reported as
   ``detout_ladder_residual_fraction``.

``--backend pallas`` keeps the legacy four-stage decomposition for
comparison (its parts do NOT sum — that is the point).

Usage (on the TPU):  python tools/profile_serve.py --batch 128
Artifact: SERVE_PROFILE.json (run_metadata-stamped, linted by
tools/check_artifacts.py as a STAMPED artifact since r9)
"""

import argparse
import json
import os
import sys
import time

# runnable as `python tools/<name>.py` from a checkout: the package is
# not installed, so the repo root goes on the path here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, iters=10, windows=3):
    import jax

    def fence(out):
        # scalar readback: ends the window once the device has finished,
        # without a whole-tensor transfer inside it
        leaf = jax.tree_util.tree_leaves(out)[0]
        float(leaf.ravel()[0])

    fence(fn(*args))                 # compile + drain the first-dispatch
    fence(fn(*args))                 # backlog
    best = []
    for _ in range(windows):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        fence(out)
        best.append((time.perf_counter() - t0) / iters)
    best.sort()
    return best[len(best) // 2]      # median window


def bias_background(params, num_classes: float, bg_bias: float):
    """Shift every conf head's background-channel bias by ``bg_bias``.

    Conf heads are ``nn.Conv(k*C)`` named ``conf_{i}`` whose output is
    reshaped ``(B, -1, C)`` (models/ssd.py:224-227), so bias channel
    ``j`` maps to class ``j % C`` — background is ``j % C == 0``.
    """
    import jax.numpy as jnp

    def walk(tree):
        out = {}
        for name, sub in tree.items():
            if name.startswith("conf_") and "bias" in sub:
                b = sub["bias"]
                mask = (jnp.arange(b.shape[0]) % num_classes) == 0
                out[name] = dict(sub)
                out[name]["bias"] = b + bg_bias * mask.astype(b.dtype)
            elif isinstance(sub, dict):
                out[name] = walk(sub)
            else:
                out[name] = sub
        return out

    return walk(params)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--res", type=int, default=300)
    p.add_argument("--classes", type=int, default=21)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--out", default="SERVE_PROFILE.json")
    p.add_argument("--bg-bias", type=float, default=8.0,
                   help="background-logit shift baked into the conf head "
                        "biases; 0 reproduces the untrained dense-conf "
                        "slow path for comparison")
    p.add_argument("--backend", default="fused",
                   choices=("fused", "pallas", "xla"),
                   help="DetectionOutput backend for BOTH the full "
                        "program and the standalone stages (the pre-r9 "
                        "incoherence was mixing them); 'fused' adds the "
                        "prefix-program stage ladder that sums by "
                        "construction")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.models.ssd import SSDDetector, SSDVgg, build_priors
    from analytics_zoo_tpu.obs import run_metadata
    from analytics_zoo_tpu.ops.detection_output import (
        DetectionOutputParam, detection_output)
    from analytics_zoo_tpu.ops.bbox import decode_bbox
    from analytics_zoo_tpu.ops.pallas_nms import _round_up, nms_sweep
    from analytics_zoo_tpu.parallel.train import cast_floating
    from analytics_zoo_tpu.utils import engine

    on_tpu = engine.on_tpu()
    B, res, C = args.batch, args.res, args.classes
    post = DetectionOutputParam(n_classes=C, backend=args.backend)

    rng = jax.random.PRNGKey(0)
    det = SSDDetector(num_classes=C, resolution=res, post=post)
    x_host = np.random.RandomState(0).rand(B, res, res, 3).astype(np.float32)
    params = det.init(rng, jnp.zeros((1, res, res, 3), jnp.float32))
    # bake the trained-like background prior into the params the FULL
    # program runs — the whole and the parts must see the same conf
    # distribution for the decomposition to sum
    params = {"params": bias_background(params["params"], C, args.bg_bias)}
    # serve runs bf16 compute (pipelines.ssd PreProcessParam default)
    params = cast_floating(params, jnp.bfloat16)
    x = jax.device_put(x_host.astype(jnp.bfloat16))

    full = jax.jit(lambda p, xx: det.apply(p, xx))

    bb = SSDVgg(num_classes=C, resolution=res)
    bb_params = {"params": params["params"]["ssd"]}
    backbone = jax.jit(lambda p, xx: bb.apply(p, xx))

    priors, variances = build_priors(bb.config)
    priors = np.asarray(priors)
    variances = np.asarray(variances)
    P = priors.shape[0]

    # the standalone stages run on the loc/conf the biased backbone
    # ACTUALLY produces — same data the full program's detout sees
    loc_raw, conf_logits = jax.block_until_ready(backbone(bb_params, x))
    loc = loc_raw.astype(jnp.float32)
    conf = jax.nn.softmax(conf_logits.astype(jnp.float32), axis=-1)
    loc, conf = jax.device_put(loc), jax.device_put(conf)

    def detout(l, c):
        return detection_output(l, c, priors, variances, post)

    k = min(_round_up(post.nms_topk, 128), _round_up(P, 128))
    Cf = C - 1          # foreground class rows (background dropped)

    t_full = timed(full, params, x, iters=args.iters)
    t_backbone = timed(backbone, bb_params, x, iters=args.iters)
    t_detout = timed(detout, loc, conf, iters=args.iters)
    residual = t_full - (t_backbone + t_detout)

    # candidate-population stat on the SAME conf the stages ran on
    valid_counts = np.asarray(jnp.sum(
        (jnp.swapaxes(conf[..., 1:], 1, 2)
         > post.conf_thresh).astype(jnp.float32), axis=-1)).reshape(-1)

    ms = {
        "full_serve_program": round(t_full * 1e3, 2),
        "backbone_only": round(t_backbone * 1e3, 2),
        "detection_output_total": round(t_detout * 1e3, 2),
        "residual_jit_boundary": round(residual * 1e3, 2),
    }
    detout_coherence = None

    if args.backend == "fused":
        # the fused stage ladder: each rung a PREFIX program of the ONE
        # kernel, so rung deltas are stage costs and telescope to the
        # full-kernel time exactly — the only residual left vs the
        # detection_output total (same program, timed independently)
        # is window noise
        from analytics_zoo_tpu.ops.pallas_detout import (
            fused_detection_output)

        def stage_fn(stage):
            return jax.jit(lambda l, c: fused_detection_output(
                l, c, priors, variances, param=post,
                interpret=not on_tpu, stage=stage))

        t_decode = timed(stage_fn("decode"), loc, conf, iters=args.iters)
        t_select = timed(stage_fn("select"), loc, conf, iters=args.iters)
        t_kernel = timed(stage_fn("full"), loc, conf, iters=args.iters)
        ms.update({
            "detout_ladder_decode_and_stream": round(t_decode * 1e3, 2),
            "detout_ladder_select_and_sweep":
                round((t_select - t_decode) * 1e3, 2),
            "detout_ladder_global_topk_merge":
                round((t_kernel - t_select) * 1e3, 2),
            "detout_full_kernel": round(t_kernel * 1e3, 2),
        })
        detout_coherence = {
            "ladder_sum_ms": round(t_kernel * 1e3, 2),
            "detout_total_ms": round(t_detout * 1e3, 2),
            "ladder_residual_fraction": round(
                (t_detout - t_kernel) / max(t_detout, 1e-9), 3),
            "note": "rungs are prefix programs of one kernel — deltas "
                    "sum to the full-kernel time BY CONSTRUCTION; the "
                    "residual vs detection_output_total is window noise "
                    "between two timings of the same program",
        }
    elif args.backend == "pallas":
        # legacy four-stage decomposition (pre-r9): its parts do NOT
        # tile the detout total — selection/gather work between the
        # staged programs has no owner.  Kept for comparison.
        from functools import partial as _partial

        @_partial(jax.jit, static_argnames=("approx",))
        def stage_topk(loc, conf, approx=False):
            decoded = jax.vmap(
                lambda l: decode_bbox(priors, variances, l, clip=False))(loc)
            scores = jnp.swapaxes(conf[..., 1:], 1, 2)      # (B,Cf,P)
            masked = jnp.where(scores > post.conf_thresh, scores, -jnp.inf)
            kk = min(k, P)
            if approx:
                top_scores, top_idx = jax.lax.approx_max_k(masked, kk)
            else:
                top_scores, top_idx = jax.lax.top_k(masked, kk)
            if kk < k:   # pad to the sweep's lane count, as the real
                # _detection_output_pallas does (advisor r4: unpadded
                # lanes break the arange(k) mask for small prior counts)
                pad = k - kk
                top_scores = jnp.pad(top_scores, ((0, 0), (0, 0), (0, pad)),
                                     constant_values=-jnp.inf)
                top_idx = jnp.pad(top_idx, ((0, 0), (0, 0), (0, pad)))
            boxes = jnp.take_along_axis(decoded[:, None], top_idx[..., None],
                                        axis=2)
            return top_scores, top_idx, boxes

        top_scores, top_idx, boxes = jax.block_until_ready(
            stage_topk(loc, conf))
        valid = (jnp.isfinite(top_scores)
                 & (jnp.arange(k) < post.nms_topk)).astype(jnp.float32)

        def flat(a):
            return a.reshape(B * Cf, k)

        fx1, fy1, fx2, fy2 = (flat(boxes[..., i]) for i in range(4))
        fvalid = flat(valid)

        @jax.jit
        def stage_sweep(x1, y1, x2, y2, v):
            return nms_sweep(x1, y1, x2, y2, v,
                             iou_threshold=post.nms_thresh,
                             interpret=not on_tpu)

        keep = jax.block_until_ready(stage_sweep(fx1, fy1, fx2, fy2, fvalid))

        @jax.jit
        def stage_final(top_scores, keep, boxes):
            kk_ = keep.reshape(B, Cf, k)
            sel = jnp.where(jnp.isfinite(top_scores), top_scores, 0.0) * kk_
            out_scores, order = jax.lax.top_k(sel.reshape(B, Cf * k),
                                              post.keep_topk)
            out_boxes = jnp.take_along_axis(boxes.reshape(B, Cf * k, 4),
                                            order[..., None], axis=1)
            return out_scores, out_boxes

        t_topk = timed(stage_topk, loc, conf, iters=args.iters)
        try:
            t_topk_approx = timed(lambda l, c: stage_topk(l, c, approx=True),
                                  loc, conf, iters=args.iters)
        except Exception as e:   # approx_max_k unsupported on this backend
            print(f"approx_max_k unavailable: {e}", file=sys.stderr)
            t_topk_approx = None
        t_sweep = timed(stage_sweep, fx1, fy1, fx2, fy2, fvalid,
                        iters=args.iters)
        t_final = timed(stage_final, top_scores, keep, boxes,
                        iters=args.iters)
        ms.update({
            "detout_decode_topk": round(t_topk * 1e3, 2),
            "detout_decode_topk_approx": (
                None if t_topk_approx is None
                else round(t_topk_approx * 1e3, 2)),
            "detout_pallas_sweep": round(t_sweep * 1e3, 2),
            "detout_final_topk": round(t_final * 1e3, 2),
        })
        parts = t_topk + t_sweep + t_final
        detout_coherence = {
            "ladder_sum_ms": round(parts * 1e3, 2),
            "detout_total_ms": round(t_detout * 1e3, 2),
            "ladder_residual_fraction": round(
                (t_detout - parts) / max(t_detout, 1e-9), 3),
            "note": "legacy decomposition: staged sub-programs re-built "
                    "outside the dispatched path — the residual is real "
                    "unattributed work (the r9 fused ladder closes it)",
        }

    result = {
        "device": jax.devices()[0].device_kind,
        "batch": B, "resolution": res, "classes": C, "priors": int(P),
        "detout_backend": args.backend,
        "sweep_lanes_k": int(k), "grid_instances": int(B * Cf),
        "bg_bias": args.bg_bias,
        "ms": ms,
        "coherence": {
            "parts_sum_ms": round((t_backbone + t_detout) * 1e3, 2),
            "full_ms": round(t_full * 1e3, 2),
            "residual_fraction": round(residual / max(t_full, 1e-9), 3),
        },
        "detout_coherence": detout_coherence,
        "conf_distribution": (
            "untrained dense (bg_bias=0)" if args.bg_bias == 0 else
            f"trained-like: background bias +{args.bg_bias} baked into "
            "the conf heads; stages timed on the backbone's real output"),
        "valid_candidates_per_class_row": {
            "mean": round(float(valid_counts.mean()), 1),
            "p95": round(float(np.percentile(valid_counts, 95)), 1),
            "max": int(valid_counts.max()),
        },
        "detout_fraction_of_serve": round(t_detout / max(t_full, 1e-9), 3),
        "images_per_sec_full": round(B / t_full, 1),
        "images_per_sec_backbone_only": round(B / t_backbone, 1),
        "note": "device-resident inputs; scalar-readback-fenced windows; "
                "bf16 backbone compute to match the serve path; whole and "
                "parts share one conf distribution AND one backend (see "
                "module docstring); off-TPU the pallas/fused kernels run "
                "interpret-mode — absolute ms are emulation, the "
                "coherence contract is what a CPU run banks",
        "run_metadata": run_metadata(
            "profile_serve", seed=0,
            extra={"iters": args.iters, "bg_bias": args.bg_bias,
                   "detout_backend": args.backend}),
    }
    print(json.dumps(result, indent=2))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
