"""MFU breakdown + batch sweep for the bf16 SSD300 train step (VERDICT
round-2 item 7: name the time sinks, push past 0.463, or commit a
profile-backed analysis of why SSD-VGG caps below 0.5).

Method (needs no trace viewer): build four compiled programs of
increasing scope —

  fwd            model forward only
  fwd_loss       forward + MultiBoxLoss
  grads          forward + backward (no update)
  step           the full train step (fwd+bwd+SGD update)

time each with readback-fenced windows on the SAME device-resident
batch, and report each stage's incremental cost plus MFU from XLA's
compiled FLOP count.  Then sweep batch size at fixed resolution — the
usual single-chip MFU lever (bigger batch = better MXU tiling and less
per-dispatch overhead per image).

Writes one JSON to --out (default MFU_PROFILE.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python tools/<name>.py` from a checkout: the package is
# not installed, so the repo root goes on the path here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, iters=10):
    import jax

    out = fn(*args)                  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    # SCALAR readback fence: reading a whole output tensor would put
    # the transfer inside the timed window — slice to one element ON
    # DEVICE first
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(leaf.ravel()[0])
    return (time.perf_counter() - t0) / iters


def flops_of(jitted, *args):
    """FLOPs from an ALREADY-JITTED fn's compiled cost analysis (reuses
    the jit cache — wrapping in a fresh jit would force a recompile).
    Raises if the program does not lower or reports no FLOPs: a silent
    0.0 would print as an MFU of zero."""
    ca = jitted.lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def cost_of(jitted, *args):
    """(flops, bytes_accessed) from a jitted fn's compiled cost analysis."""
    try:
        ca = jitted.lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)))
    except Exception:
        return 0.0, 0.0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batches", type=int, nargs="+", default=[32, 48, 64])
    p.add_argument("--res", type=int, default=300)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--ceiling", action="store_true",
                   help="MFU-ceiling decomposition (VERDICT r3 item 10): "
                        "scoped programs + a roofline estimate naming the "
                        "residual non-MXU time; writes --out "
                        "(default MFU_CEILING.json)")
    p.add_argument("--mining-ab", action="store_true",
                   help="bank the mining='topk' vs 'sort' claim (ISSUE r5 "
                        "satellite): time the standalone jitted "
                        "MultiBoxLoss fwd+bwd under both hard-negative "
                        "engines and MERGE the reading into --out "
                        "(default MFU_PROFILE.json) under 'mining_topk_ab' "
                        "with the device kind recorded per-section")
    p.add_argument("--rnn-ab", action="store_true",
                   help="persistent-RNN h2h probe (ISSUE 6): time one "
                        "Recurrent direction fwd+bwd under the blocked "
                        "vs pallas engines at equal geometry and write "
                        "the h2h-share artifact (default out "
                        "MFU_RNN_AB.json): XLA flops/bytes per program, "
                        "the h2h term's analytic share of both, and its "
                        "arithmetic intensity under each engine against "
                        "the v5e ridge")
    p.add_argument("--rnn-hidden", type=int, default=1760,
                   help="--rnn-ab hidden size (DS2 parity default)")
    p.add_argument("--rnn-batch", type=int, default=8)
    p.add_argument("--rnn-frames", type=int, default=150,
                   help="--rnn-ab timestep count (post-conv frames)")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    if args.out is None:
        args.out = ("MFU_RNN_AB.json" if args.rnn_ab
                    else "MFU_CEILING.json" if args.ceiling
                    else "MFU_PROFILE.json")

    global jax
    import numpy as np
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg, build_priors
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (SGD, create_mesh,
                                            create_train_state,
                                            make_train_step, replicate,
                                            shard_batch)
    from analytics_zoo_tpu.parallel.train import cast_floating

    kind = jax.devices()[0].device_kind
    peak = {"TPU v5 lite": 197.0, "TPU v5e": 197.0, "TPU v4": 275.0,
            "TPU v5p": 459.0, "TPU v6 lite": 918.0}.get(kind)

    if args.rnn_ab:
        # one Recurrent direction, blocked vs pallas engine at equal
        # geometry — the h2h-share artifact docs/MFU_CEILING.md's DS2
        # verdict reasons from: how much of the program's FLOPs the h2h
        # recurrence is, and its arithmetic intensity under each
        # engine's weight-streaming discipline (re-read per step vs
        # VMEM-resident per sequence) against the v5e ridge.
        from analytics_zoo_tpu.core.rnn import Recurrent, RnnCell

        B, T, H = args.rnn_batch, args.rnn_frames, args.rnn_hidden
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, T, H).astype(np.float32) * 0.1)
        n = jnp.asarray(np.linspace(max(T // 2, 1), T, B)
                        .astype(np.int32))
        db = x.dtype.itemsize
        report = {"device_kind": kind, "backend": jax.default_backend(),
                  "peak_bf16_tflops": peak,
                  "geometry": {"batch": B, "frames": T, "hidden": H,
                               "cell": "vanilla", "dtype_bytes": db,
                               "iters": args.iters},
                  "engines": {}}
        params = None
        # analytic h2h terms (vanilla k=1): the forward recurrence does
        # 2·B·H² FLOPs per step against the H²·db weight block; the
        # TRANSPOSED backward does 2× that per step (dh ← dgate·Wᵀ plus
        # the fused dW += hᵀ·dgate accumulation)
        h2h_fwd_flops = 2.0 * B * T * H * H
        h2h_bwd_flops = 2.0 * h2h_fwd_flops
        for engine in ("blocked", "pallas"):
            net = Recurrent(cell=RnnCell(hidden_size=H), engine=engine)
            # the fwd-only program prices only the forward's VMEM
            # residency (pallas_grad=False): a backward-only budget
            # overflow must fall back the fwd_bwd timing alone, not
            # drag the forward reading down to blocked-vs-blocked
            net_fwd = net.clone(pallas_grad=False)
            if params is None:
                params = net.init(jax.random.PRNGKey(0), x)

            def loss(v, net=net):
                return jnp.sum(net.apply(v, x, n_frames=n) ** 2)

            jf = jax.jit(lambda v, net=net_fwd:
                         jnp.sum(net.apply(v, x, n_frames=n) ** 2))
            jg = jax.jit(jax.grad(loss))
            # the pallas engine warns + runs the blocked scan when the
            # geometry cannot be VMEM-resident (possible on TPU at
            # fp32/H=1760 — and the BACKWARD budget term can overflow
            # where the forward fits) — record it PER PASS, or this
            # artifact could bank a blocked-vs-blocked "A/B" (the trace
            # happens inside each program's first timed call, so capture
            # around each timing separately)
            import warnings

            with warnings.catch_warnings(record=True) as caught_f:
                warnings.simplefilter("always")
                t_f = timed(jf, params, iters=args.iters)
            with warnings.catch_warnings(record=True) as caught_g:
                warnings.simplefilter("always")
                t_g = timed(jg, params, iters=args.iters)
            f_f, by_f = cost_of(jf, params)
            f_g, by_g = cost_of(jg, params)
            bwd_only = (f_g - f_f) if (f_g and f_f) else 0.0
            report["engines"][engine] = {
                "engine_fallback": {
                    "fwd": any("falling back" in str(w.message)
                               for w in caught_f),
                    "fwd_bwd": any("falling back" in str(w.message)
                                   for w in caught_g),
                },
                "fwd_ms": round(t_f * 1e3, 2),
                "fwd_bwd_ms": round(t_g * 1e3, 2),
                "fwd_gflops": round(f_f / 1e9, 3) if f_f else None,
                "fwd_bwd_gflops": round(f_g / 1e9, 3) if f_g else None,
                "fwd_gbytes_accessed": (round(by_f / 1e9, 3)
                                        if by_f else None),
                "fwd_bwd_gbytes_accessed": (round(by_g / 1e9, 3)
                                            if by_g else None),
                "program_intensity_flops_per_byte": (
                    round(f_g / by_g, 1) if by_g else None),
                "h2h_share_of_fwd_flops": (
                    round(h2h_fwd_flops / f_f, 3) if f_f else None),
                "h2h_share_of_bwd_flops": (
                    round(h2h_bwd_flops / bwd_only, 3)
                    if bwd_only > 0 else None),
            }
        eng = report["engines"]
        report["speedup_pallas_vs_blocked"] = {
            "fwd": round(eng["blocked"]["fwd_ms"]
                         / max(eng["pallas"]["fwd_ms"], 1e-9), 3),
            "fwd_bwd": round(eng["blocked"]["fwd_bwd_ms"]
                             / max(eng["pallas"]["fwd_bwd_ms"], 1e-9), 3),
        }
        report["h2h"] = {
            "weight_mbytes_per_direction": round(H * H * db / 2**20, 3),
            "flops_per_step": 2.0 * B * H * H,
            "intensity_blocked_flops_per_byte": round(2.0 * B / db, 2),
            "intensity_persistent_flops_per_byte": round(
                2.0 * B * T / db, 1),
            # backward: 4·B·H² FLOPs per step (dh chain + dW accum)
            # against 2·H²·db weight bytes (W and Wᵀ) — restreamed per
            # step under the scan vjp, read once per sequence by the
            # transposed persistent kernel: the RATIO is the forward's
            "bwd_flops_per_step": 4.0 * B * H * H,
            "bwd_intensity_blocked_flops_per_byte": round(
                2.0 * B / db, 2),
            "bwd_intensity_persistent_flops_per_byte": round(
                2.0 * B * T / db, 1),
            # within the ANALYTIC backward matmul decomposition
            # (h2h: dh 2BTH² + dW_h2h 2BTH²; i2h: dW_i2h 2BTH² for the
            # vanilla D=H cell) — the basis-robust share
            "bwd_h2h_share_of_analytic_matmul_flops": round(4 / 6, 3),
            "v5e_ridge_flops_per_byte": 240,
        }
        report["note"] = (
            "h2h_share_of_fwd_flops = analytic 2·B·T·H² over XLA's "
            "compiled FLOP count; h2h_share_of_bwd_flops = analytic "
            "4·B·T·H² (dh ← dgate·Wᵀ plus dW += hᵀ·dgate) over the "
            "bwd-only FLOPs (fwd_bwd − fwd) — NOTE this counted basis "
            "can read >1 on the CPU backend, whose cost analysis "
            "under-counts transposed contractions; recorded honestly "
            "rather than clipped, with h2h.bwd_h2h_share_of_analytic_"
            "matmul_flops (2/3) as the basis-robust companion; "
            "intensity_* is the h2h "
            "term's FLOP/byte under each weight-streaming discipline "
            "(blocked/scan-vjp re-reads the weight block every step, "
            "the persistent kernels — forward AND the r10 transposed "
            "backward — read it once per sequence).  engine_fallback "
            "is recorded per pass: a fallen-back backward must not "
            "bank a scan-vs-scan reading.  On a CPU backend the pallas "
            "engine runs interpret-mode (discharged to XLA): timings "
            "then bank schedule parity/overhead only — the HBM "
            "residency term pays on a real TPU.")
        from analytics_zoo_tpu.obs import run_metadata

        report["run_metadata"] = run_metadata("profile_mfu_rnn_ab", seed=0)
        print(json.dumps(report, indent=2))
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        return 0

    mesh = create_mesh()
    model = Model(SSDVgg(num_classes=21, resolution=args.res))
    model.build(0, jnp.zeros((1, args.res, args.res, 3), jnp.float32))
    priors, variances = build_priors(model.module.config)
    criterion = MultiBoxLoss(priors, variances, MultiBoxLossParam())
    optim = SGD(1e-3, momentum=0.9)

    if args.mining_ab:
        # standalone loss fwd+bwd A/B — the exact program the
        # MFU_CEILING.md mining table describes, now committed as a
        # merge-in section of the MFU profile artifact so the doc claim
        # is BANKED, not prose.  The gradient runs w.r.t. (loc, conf),
        # matching the in-step backward through the detector heads.
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops import MultiBoxLossParam as MBParam

        B = args.batches[0]
        n_p = np.asarray(priors).shape[0]
        rng = np.random.RandomState(0)
        loc = jnp.asarray(rng.randn(B, n_p, 4).astype(np.float32) * 0.1)
        conf = jnp.asarray(rng.randn(B, n_p, 21).astype(np.float32))
        target = {
            "bboxes": jnp.asarray(np.tile(np.asarray(
                [0.1, 0.1, 0.6, 0.6], np.float32), (B, 4, 1))),
            "labels": jnp.ones((B, 4), jnp.int32),
            "mask": jnp.ones((B, 4), jnp.float32),
        }
        section = {"device_kind": kind, "batch": B, "priors": int(n_p),
                   "iters": args.iters}
        times = {}
        for mining in ("sort", "topk"):
            crit = MultiBoxLoss(priors, variances,
                                MBParam(mining=mining))

            def loss(lc, cf, crit=crit):
                return crit((lc, cf), target)

            jf = jax.jit(loss)
            jg = jax.jit(jax.grad(loss, argnums=(0, 1)))
            times[mining] = {
                "loss_fwd_ms": round(timed(jf, loc, conf,
                                           iters=args.iters) * 1e3, 2),
                "loss_fwd_bwd_ms": round(timed(jg, loc, conf,
                                               iters=args.iters) * 1e3, 2),
            }
        section.update(times)
        section["fwd_bwd_speedup_topk_vs_sort"] = round(
            times["sort"]["loss_fwd_bwd_ms"]
            / max(times["topk"]["loss_fwd_bwd_ms"], 1e-9), 3)
        section["note"] = (
            "standalone jitted MultiBoxLoss fwd+bwd (grad w.r.t. "
            "loc/conf); per-section device_kind — compare only within "
            "one device.  In-step MFU deltas require the full-step "
            "rerun (MFU_CEILING_r4mining.json methodology).")
        merged = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                merged = json.load(f)
        merged["mining_topk_ab"] = section
        print(json.dumps(section, indent=2))
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=2)
        return 0

    report = {"device_kind": kind, "peak_bf16_tflops": peak,
              "resolution": args.res, "stages": {}, "batch_sweep": []}

    def make_batch(b):
        rng = np.random.RandomState(0)
        return shard_batch({
            "input": rng.rand(b, args.res, args.res, 3).astype(np.float32),
            "target": {
                "bboxes": np.tile(np.asarray([0.1, 0.1, 0.6, 0.6],
                                             np.float32), (b, 4, 1)),
                "labels": np.ones((b, 4), np.int32),
                "mask": np.ones((b, 4), np.float32),
            },
        }, mesh)

    # one host snapshot of the initial state: the train step DONATES its
    # state buffers, and model.variables aliases them — later rebuilds
    # would hand deleted arrays to device_put
    host_state0 = jax.device_get(create_train_state(model, optim))

    if args.ceiling:
        # advertised HBM bandwidth per chip (GB/s)
        hbm_bw = {"TPU v5 lite": 819.0, "TPU v5e": 819.0,
                  "TPU v4": 1228.0, "TPU v5p": 2765.0,
                  "TPU v6 lite": 1640.0}.get(kind)
        B = args.batches[0]
        batch = make_batch(B)
        state = replicate(host_state0, mesh)
        params_bf16 = cast_floating(state.params, jnp.bfloat16)
        x_bf16 = batch["input"].astype(jnp.bfloat16)
        tgt = batch["target"]

        def fwd(p, x):
            return model.module.apply(
                {"params": p}, x, train=True,
                rngs={"dropout": jax.random.PRNGKey(0)},
                mutable=["batch_stats"])[0]

        def loss_mb(p, x, t):
            out = fwd(p, x)
            out = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), out)
            return criterion(out, t)

        def loss_sum(p, x):
            loc, conf = fwd(p, x)
            return (loc.astype(jnp.float32).sum()
                    + conf.astype(jnp.float32).sum())

        step = make_train_step(model.module, criterion, optim, mesh=mesh,
                               compute_dtype="bf16")
        jg_mb = jax.jit(jax.grad(loss_mb))
        jg_sum = jax.jit(jax.grad(loss_sum))

        st = replicate(host_state0, mesh)
        st, m = step(st, batch, 1.0)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            st, m = step(st, batch, 1.0)
        float(np.asarray(m["loss"]))
        t_step = (time.perf_counter() - t0) / args.iters
        t_gmb = timed(jg_mb, params_bf16, x_bf16, tgt, iters=args.iters)
        t_gsum = timed(jg_sum, params_bf16, x_bf16, iters=args.iters)

        f_step, by_step = cost_of(step, st, batch, 1.0)
        f_gmb, by_gmb = cost_of(jg_mb, params_bf16, x_bf16, tgt)
        f_gsum, by_gsum = cost_of(jg_sum, params_bf16, x_bf16)

        tf_step = f_step / t_step / 1e12
        # roofline: compute-time floor vs HBM-traffic floor for the SAME
        # compiled program (XLA's own flops + bytes-accessed accounting)
        t_compute_floor = f_step / (peak * 1e12) if peak else None
        t_memory_floor = by_step / (hbm_bw * 1e9) if hbm_bw else None
        roofline = (max(t_compute_floor, t_memory_floor)
                    if t_compute_floor and t_memory_floor else None)
        report = {
            "device_kind": kind, "peak_bf16_tflops": peak,
            "hbm_gb_per_sec": hbm_bw, "resolution": args.res, "batch": B,
            "full_step_ms": round(t_step * 1e3, 2),
            "fwd_bwd_multibox_ms": round(t_gmb * 1e3, 2),
            "fwd_bwd_trivial_loss_ms": round(t_gsum * 1e3, 2),
            "multibox_loss_cost_ms": round((t_gmb - t_gsum) * 1e3, 2),
            "sgd_update_and_cast_cost_ms": round((t_step - t_gmb) * 1e3, 2),
            "step_gflops": round(f_step / 1e9, 1),
            "step_gbytes_accessed": round(by_step / 1e9, 2),
            "arithmetic_intensity_flops_per_byte": round(f_step / by_step, 1)
            if by_step else None,
            "measured_tflops": round(tf_step, 2),
            "measured_mfu": round(tf_step / peak, 4) if peak else None,
            "roofline_floor_ms": round(roofline * 1e3, 2) if roofline else None,
            "roofline_mfu_bound": (
                round(t_compute_floor / roofline, 4) if roofline else None),
            "bound_by": (None if roofline is None else
                         "memory" if roofline == t_memory_floor
                         else "compute"),
            "grads_trivial_vs_multibox": {
                "flops_gflops": [round(f_gsum / 1e9, 1),
                                 round(f_gmb / 1e9, 1)],
                "bytes_gb": [round(by_gsum / 1e9, 2), round(by_gmb / 1e9, 2)],
            },
        }
        print(json.dumps(report))
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        return 0

    # ---- stage breakdown at the first batch size ----
    B = args.batches[0]
    batch = make_batch(B)
    state = replicate(host_state0, mesh)
    params_bf16 = cast_floating(state.params, jnp.bfloat16)
    # device-side cast KEEPS the batch sharding (a host round-trip would
    # hand the stage fns a replicated batch while the full step runs the
    # sharded one — incomparable timings on a multi-device mesh)
    x_bf16 = batch["input"].astype(jnp.bfloat16)

    def fwd(p, x):
        return model.module.apply({"params": p}, x, train=True,
                                  rngs={"dropout": jax.random.PRNGKey(0)},
                                  mutable=["batch_stats"])[0]

    def loss_only(p, x, tgt):
        out = fwd(p, x)
        out = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), out)
        return criterion(out, tgt)

    def grads(p, x, tgt):
        return jax.grad(loss_only)(p, x, tgt)

    tgt = batch["target"]
    jf = jax.jit(fwd)
    jl = jax.jit(loss_only)
    jg = jax.jit(grads)
    step = make_train_step(model.module, criterion, optim, mesh=mesh,
                           compute_dtype="bf16")

    t_fwd = timed(jf, params_bf16, x_bf16, iters=args.iters)
    t_loss = timed(jl, params_bf16, x_bf16, tgt, iters=args.iters)
    t_grad = timed(jg, params_bf16, x_bf16, tgt, iters=args.iters)

    st = replicate(host_state0, mesh)
    st, m = step(st, batch, 1.0)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        st, m = step(st, batch, 1.0)
    float(np.asarray(m["loss"]))
    t_step = (time.perf_counter() - t0) / args.iters

    f_step = flops_of(step, st, batch, 1.0)
    f_fwd = flops_of(jf, params_bf16, x_bf16)
    f_grad = flops_of(jg, params_bf16, x_bf16, tgt)
    tf_step = f_step / t_step / 1e12 if f_step else None
    report["stages"] = {
        "batch": B,
        "fwd_ms": round(t_fwd * 1e3, 2),
        "fwd_plus_loss_ms": round(t_loss * 1e3, 2),
        "fwd_bwd_ms": round(t_grad * 1e3, 2),
        "full_step_ms": round(t_step * 1e3, 2),
        "loss_increment_ms": round((t_loss - t_fwd) * 1e3, 2),
        "bwd_increment_ms": round((t_grad - t_loss) * 1e3, 2),
        "update_increment_ms": round((t_step - t_grad) * 1e3, 2),
        "fwd_gflops": round(f_fwd / 1e9, 1) if f_fwd else None,
        "fwd_bwd_gflops": round(f_grad / 1e9, 1) if f_grad else None,
        "step_gflops": round(f_step / 1e9, 1) if f_step else None,
        "step_tflops_per_sec": round(tf_step, 2) if tf_step else None,
        "step_mfu": (round(tf_step / peak, 4)
                     if (tf_step and peak) else None),
    }

    # ---- batch sweep on the full step ----
    # ONE jitted step serves every batch size (its cache is keyed on
    # shapes, so only genuinely-new shapes compile; rebuilding the step
    # per size would recompile even the shape the stage section used)
    for b in args.batches:
        bt = make_batch(b)
        st = replicate(host_state0, mesh)
        st, m = step(st, bt, 1.0)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            st, m = step(st, bt, 1.0)
        float(np.asarray(m["loss"]))
        dt = (time.perf_counter() - t0) / args.iters
        fl = flops_of(step, st, bt, 1.0)
        tflops = fl / dt / 1e12 if fl else None
        report["batch_sweep"].append({
            "batch": b,
            "step_ms": round(dt * 1e3, 2),
            "images_per_sec": round(b / dt, 1),
            "model_tflops": round(tflops, 2) if tflops else None,
            "mfu": round(tflops / peak, 4) if (tflops and peak) else None,
        })
        print(json.dumps(report["batch_sweep"][-1]), flush=True)

    print(json.dumps(report))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
