"""Scaling-efficiency sweep + preemption drill over the spec substrate.

BASELINE.json's third metric is "8→64-chip scaling efficiency ≥60%".
This harness measures weak scaling (fixed per-chip batch) for the TWO
flagship training pipelines — SSD300 and length-bucketed DS2 — each
through exactly the program the real pipeline uses: sharding declared
once via ``pipeline_specs(...)`` (parallel/specs.py), the annotated
train step placing HOST batches itself, gradient mean compiled to an
all-reduce.  ``efficiency(n) = throughput(n) / (n · throughput(1))``,
with per-window values kept per device count (a window's drift shows
per mesh size).

``--drill`` adds the chaos leg ISSUE 9 banks: on the widest mesh, a
host preemption (real SIGTERM mid-epoch through the multiprocess
loader) forces the boundary checkpoint and raises ``Preempted``; a
fresh process resumes from the atomic snapshot and must land on
byte-equal final parameters vs an uninterrupted reference run — which
is only possible if the loader's deterministic coordinates
``(base_seed, epoch, batch index)`` survived the round trip.

On real TPU slices the numbers are the metric.  Without enough real
chips, pass ``--virtual`` to emulate each mesh with
``--xla_force_host_platform_device_count`` on CPU: that validates the
mechanism (sharding, collectives, program correctness at each mesh
size) but NOT performance — virtual devices share the host's cores, so
efficiency trends toward 1/n by construction and every line is labeled
``"virtual": true`` (the MULTICHIP_r0* convention).

Each device count runs in a fresh subprocess because XLA fixes the
device count at backend init.  ``--sweep-log PATH`` also appends every
emitted sweep line to a ``.jsonl`` file.

Usage::

    python tools/bench_scaling.py --devices 1 2 4 8 --virtual \
        --models ssd ds2 --drill --emit MULTICHIP_r06.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_CHILD_FLAG = "--_child"
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)       # the parent stamps obs.run_metadata

#: drill geometry (shared by all three drill legs so their streams are
#: byte-identical): fraud MLP, 256 records, batch 16 -> 16 batches/epoch
_DRILL = dict(n_records=256, batch=16, epochs=4, workers=2,
              base_seed=7, lr=0.1)


def _append_sweep_log(path: str, line: dict) -> None:
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError:
        pass                          # the log is a convenience, never fatal


# ---------------------------------------------------------------------------
# sweep children (one process per device count; XLA pins the count at init)
# ---------------------------------------------------------------------------


def child_ssd(n: int, batch_per_chip: int, steps: int, res: int,
              windows: int) -> None:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg, build_priors, ssd300_config
    from analytics_zoo_tpu.ops import MultiBoxLoss, MultiBoxLossParam
    from analytics_zoo_tpu.parallel import (SGD, create_train_state,
                                            make_train_step, pipeline_specs)

    assert jax.device_count() == n, (jax.device_count(), n)
    specs = pipeline_specs("ssd", resolution=res)     # declared once
    model = Model(SSDVgg(num_classes=21, resolution=res))
    model.build(0, jnp.zeros((1, res, res, 3), jnp.float32))
    priors, variances = build_priors(ssd300_config())
    criterion = MultiBoxLoss(priors, variances, MultiBoxLossParam())
    optim = SGD(1e-3, momentum=0.9)
    state = specs.place_state(create_train_state(model, optim))
    step = make_train_step(model.module, criterion, optim, specs=specs,
                           compute_dtype="bf16")

    b = batch_per_chip * n
    rng = np.random.RandomState(0)
    # HOST batch on purpose: the annotated jit's in_shardings place it
    batch = {
        "input": rng.rand(b, res, res, 3).astype(np.float32),
        "target": {
            "bboxes": np.tile(np.asarray([0.1, 0.1, 0.6, 0.6], np.float32),
                              (b, 8, 1)),
            "labels": rng.randint(1, 21, (b, 8)).astype(np.int32),
            "mask": np.ones((b, 8), np.float32),
        },
    }

    state, m = step(state, batch, 1.0)                 # compile
    jax.block_until_ready(m["loss"])
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch, 1.0)
        jax.block_until_ready(m["loss"])
        rates.append(b * steps / (time.perf_counter() - t0))
    rates.sort()
    print(json.dumps({"model": "ssd", "n": n,
                      "images_per_sec": rates[len(rates) // 2],
                      "windows": [round(r, 3) for r in rates],
                      "global_batch": b,
                      "loss": float(m["loss"])}))


def child_ds2(n: int, batch_per_chip: int, steps: int, windows: int,
              hidden: int, layers: int, seconds: int) -> None:
    import time

    import jax
    import numpy as np

    from analytics_zoo_tpu.data.bucket import BucketBatcher
    from analytics_zoo_tpu.parallel import (Adam, create_train_state,
                                            make_train_step, pipeline_specs)
    from analytics_zoo_tpu.pipelines.deepspeech2 import (ds2_ctc_criterion,
                                                         make_ds2_model)
    from analytics_zoo_tpu.transform.audio.featurize import (WINDOW_SIZE,
                                                             WINDOW_STRIDE)

    assert jax.device_count() == n, (jax.device_count(), n)
    n_max = (16000 * seconds - WINDOW_SIZE) // WINDOW_STRIDE + 1
    B = batch_per_chip * n
    n_records = B * 4
    rng = np.random.RandomState(42)
    frac = np.clip(rng.lognormal(-1.3, 0.7, n_records), 0.08, 1.0)
    lengths = np.clip((frac * n_max).astype(np.int32), 16, n_max)
    feats = [rng.randn(int(ln), 13).astype(np.float32) * 0.1
             for ln in lengths]
    labels = rng.randint(1, 29, (n_records, 20)).astype(np.int32)
    # edges derived from the distribution, NOT the draw, so every mesh
    # width shares the same compiled bucket geometries
    edges = sorted({n_max // 8, n_max // 4, n_max // 2, n_max})

    def stream():
        for i in range(n_records):
            yield {"input": feats[i], "n_frames": np.int32(lengths[i]),
                   "labels": labels[i],
                   "label_mask": np.ones((20,), np.float32)}

    batches = []
    for bb in BucketBatcher(B, edges).apply_iter(stream()):
        batches.append({"input": (bb["input"], bb["n_frames"]),
                        "n_frames": bb["n_frames"],
                        "labels": bb["labels"],
                        "label_mask": bb["label_mask"]})
    recs = sum(bb["n_frames"].shape[0] for bb in batches)

    specs = pipeline_specs("ds2")                     # declared once
    model = make_ds2_model(hidden=hidden, n_rnn_layers=layers,
                           utt_length=n_max)
    optim = Adam(3e-4)
    state = specs.place_state(create_train_state(model, optim))
    step = make_train_step(model.module, ds2_ctc_criterion(), optim,
                           specs=specs, compute_dtype="fp32")
    for bb in batches:                                # compile per bucket
        state, m = step(state, bb, 1.0)
    float(np.asarray(m["loss"]))
    reps = max(1, steps // max(len(batches), 1))
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(reps):
            for bb in batches:
                state, m = step(state, bb, 1.0)
        float(np.asarray(m["loss"]))
        rates.append(recs * reps / (time.perf_counter() - t0))
    rates.sort()
    print(json.dumps({"model": "ds2", "n": n,
                      "records_per_sec": rates[len(rates) // 2],
                      "windows": [round(r, 3) for r in rates],
                      "global_batch": B, "bucket_edges": edges,
                      "records": recs,
                      "loss": float(np.asarray(m["loss"]))}))


# ---------------------------------------------------------------------------
# preemption-resume drill children
# ---------------------------------------------------------------------------


class _SigtermAt:
    """Wrap the batched dataset; deliver a REAL SIGTERM to this process
    just before yielding global batch ``at`` (counted across epochs) —
    the host-preemption notice, trapped by the PreemptionHandler."""

    def __init__(self, inner, at):
        self.inner = inner
        self.at = at
        self._count = 0

    def __getattr__(self, name):          # loader attrs (base_seed, ...)
        return getattr(self.inner, name)

    def __iter__(self):
        import signal

        for batch in self.inner:
            if self.at is not None and self._count == self.at:
                os.kill(os.getpid(), signal.SIGTERM)
            self._count += 1
            yield batch


def _tree_sha256(tree) -> str:
    """Order-stable byte digest of a pytree's leaves — the elastic
    drill's bit-exactness witness (repr(float) fingerprints collapse
    distinct trees; this doesn't)."""
    import hashlib

    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def drill_child(mode: str, ckpt: str, preempt_at: int,
                workers: int = 0) -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.data import DataSet
    from analytics_zoo_tpu.models.simple import FraudMLP
    from analytics_zoo_tpu.parallel import (SGD, Optimizer, Trigger,
                                            pipeline_specs)
    from analytics_zoo_tpu.resilience.errors import Preempted

    cfg = dict(_DRILL)
    if workers:
        # shard-count-independence leg of the elastic drill: the stream
        # must be byte-identical for ANY worker count
        cfg["workers"] = workers
    rng = np.random.RandomState(0)
    x = rng.randn(cfg["n_records"], 29).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
    # the PR-2 deterministic multiprocess loader: byte-identical stream
    # for any worker count, coordinates (base_seed, epoch, batch index).
    # A RESUMED process rebuilds the loader AT the checkpointed epoch
    # (start_epoch) — the per-epoch shuffle then replays the exact
    # stream the interrupted run was consuming.
    start_epoch, resume_meta = 0, None
    if mode == "resume":
        from analytics_zoo_tpu.parallel import checkpoint as ckpt_lib

        _, man = ckpt_lib.newest_intact(ckpt)
        resume_meta = {k: man["meta"][k] for k in
                       ("epoch", "iteration", "iter_in_epoch")}
        for k in ("samples_in_epoch", "world_width"):
            if k in man["meta"]:
                resume_meta[k] = man["meta"][k]
        start_epoch = int(resume_meta["epoch"])
    dataset = (DataSet.from_arrays(shuffle=True, seed=3, input=x, target=y)
               .batch(cfg["batch"])
               .parallel(cfg["workers"], base_seed=cfg["base_seed"],
                         start_epoch=start_epoch))
    if mode == "preempt":
        dataset = _SigtermAt(dataset, preempt_at)

    specs = pipeline_specs("fraud")
    model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
    model.build(0, jnp.zeros((1, 29), jnp.float32))
    opt = (Optimizer(model, dataset, ClassNLLCriterion(), specs=specs)
           .set_optim_method(SGD(cfg["lr"], momentum=0.9))
           .set_end_when(Trigger.max_epoch(cfg["epochs"])))
    if mode in ("preempt", "resume"):
        opt.set_checkpoint(ckpt, Trigger.every_epoch())
    if mode == "preempt":
        opt.set_preemption_handler()
    if mode == "resume":
        opt.set_resume()

    report = {"mode": mode, "n_devices": jax.device_count(),
              "worker_processes": cfg["workers"],
              "base_seed": cfg["base_seed"]}
    if mode == "resume":
        # elastic placement probe: re-placing the saved-at-W bytes onto
        # THIS width's mesh must preserve them exactly — checkpoints
        # hold width-agnostic host values, so restore_elastic is pure
        # placement, never a resample
        from analytics_zoo_tpu.parallel import checkpoint as ckpt_lib

        raw = ckpt_lib.load(ckpt)
        placed = ckpt_lib.restore_elastic(ckpt, target=raw, specs=specs)
        report["placement_probe"] = {
            "raw_sha256": _tree_sha256(raw),
            "placed_sha256": _tree_sha256(placed),
        }
        del raw, placed
    try:
        opt.optimize()
    except Preempted as e:
        from analytics_zoo_tpu.parallel import checkpoint as ckpt_lib

        snap_dir, man = ckpt_lib.newest_intact(ckpt)
        report.update({
            "preempted": True, "message": str(e)[:160],
            "snapshot": os.path.basename(snap_dir),
            "manifest_meta": {k: man["meta"][k] for k in
                              ("epoch", "iteration", "iter_in_epoch")},
        })
        print("DRILL " + json.dumps(report))
        return
    state = opt._last_state
    fp = float(sum(np.abs(np.asarray(l)).sum()
                   for l in jax.tree_util.tree_leaves(state.params)))
    report.update({"steps": int(np.asarray(state.step)),
                   "fingerprint": repr(fp),
                   "params_sha256": _tree_sha256(state.params)})
    if resume_meta is not None:
        report["resumed_from"] = resume_meta
        report["loader_start_epoch"] = start_epoch
    print("DRILL " + json.dumps(report))


def run_drill(args, env_for) -> dict:
    """Three legs in fresh processes on the widest mesh: reference
    (uninterrupted), preempt (SIGTERM mid-epoch 2 → forced checkpoint →
    ``Preempted``), resume (same snapshot dir → finish).  Verdict:
    resume fingerprint must equal the reference's — which requires the
    loader's deterministic coordinates to survive the round trip."""
    import tempfile

    n = max(args.devices)
    batches_per_epoch = _DRILL["n_records"] // _DRILL["batch"]
    preempt_at = batches_per_epoch + 3          # 4 batches into epoch 2
    legs = {}
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "drill_ckpt")
        for mode in ("reference", "preempt", "resume"):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--_drill-child", mode, "--_drill-ckpt", ckpt,
                   "--_drill-preempt-at", str(preempt_at),
                   _CHILD_FLAG, str(n)]
            out = subprocess.run(cmd, env=env_for(n), capture_output=True,
                                 text=True, cwd=_REPO, timeout=600)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("DRILL ")]
            if out.returncode != 0 or not line:
                return {"ok": False, "failed_leg": mode,
                        "stderr": out.stderr[-800:]}
            legs[mode] = json.loads(line[-1][len("DRILL "):])

    ref, pre, res = legs["reference"], legs["preempt"], legs["resume"]
    fp_ref = float(ref["fingerprint"])
    fp_res = float(res["fingerprint"])
    meta = pre.get("manifest_meta", {})
    return {
        "ok": (pre.get("preempted") is True
               and res["steps"] == ref["steps"]
               and fp_ref == fp_res),
        "n_devices": n,
        "preempt_at_global_batch": preempt_at,
        "batches_per_epoch": batches_per_epoch,
        "preempt": pre,
        "resume": {**res, "fingerprint_delta": abs(fp_res - fp_ref)},
        "reference": ref,
        "fingerprint_match_bitexact": fp_ref == fp_res,
        "loader_coordinates": {
            "base_seed": _DRILL["base_seed"],
            "checkpointed_epoch": meta.get("epoch"),
            "checkpointed_iter_in_epoch": meta.get("iter_in_epoch"),
            "mid_epoch": bool(meta.get("iter_in_epoch", 0)),
        },
        "policy": "resume == uninterrupted reference bit-exactly ⇔ the "
                  "deterministic loader re-seeked to the exact "
                  "(base_seed, epoch, batch index) coordinate the "
                  "forced checkpoint recorded",
    }


#: elastic drill geometry: SIGTERM the width-W run, resume on W′
_ELASTIC_SAVE_W = 4
_ELASTIC_RESUME_W = (2, 8)


def run_elastic_drill(args, env_for) -> dict:
    """The ISSUE-19 elastic mesh drill: SIGTERM a width-4 run mid-epoch
    2, then resume the SAME snapshot on width-2 and width-8 meshes (and
    width-4 as the control).  Fresh subprocess per leg — XLA pins the
    device count at init, exactly like the scaling sweep.

    What is pinned bit-exactly, and what honestly cannot be:

    - same-width control: resume@4 ends byte-identical to the
      uninterrupted reference@4 (params sha256, not just the scalar
      fingerprint) — the PR-4 drill's guarantee, restated in bytes;
    - placement: every resume leg re-places the saved-at-4 checkpoint
      onto its own mesh and the placed tree's bytes equal the raw
      restored bytes (``restore_elastic`` is placement, not resample);
    - shard-count independence: resume@2 with 2 loader workers ends
      byte-identical to resume@2 with 4 — the GLOBAL sample coordinate
      re-seek is worker-count-free;
    - cross-width: resume@W′ completes the exact step count of an
      uninterrupted reference@W′ and agrees to ~1 float32 ulp — XLA's
      cross-replica reduction ORDER differs per width, so bitwise
      equality across widths is physically false on this backend (the
      recorded deltas witness how close "not bit-exact" actually is).
    """
    import shutil
    import tempfile

    batches_per_epoch = _DRILL["n_records"] // _DRILL["batch"]
    preempt_at = batches_per_epoch + 3          # 4 batches into epoch 2
    expected_steps = batches_per_epoch * _DRILL["epochs"]

    def leg(mode, n, ckpt, workers=0):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--_drill-child", mode, "--_drill-ckpt", ckpt,
               "--_drill-preempt-at", str(preempt_at),
               "--_drill-workers", str(workers),
               _CHILD_FLAG, str(n)]
        out = subprocess.run(cmd, env=env_for(n), capture_output=True,
                             text=True, cwd=_REPO, timeout=600)
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("DRILL ")]
        if out.returncode != 0 or not line:
            raise RuntimeError(
                f"elastic leg {mode}@w{n}: {out.stderr[-800:]}")
        return json.loads(line[-1][len("DRILL "):])

    with tempfile.TemporaryDirectory() as tmp:
        master = os.path.join(tmp, "ckpt_master")
        try:
            pre = leg("preempt", _ELASTIC_SAVE_W, master)
            refs = {w: leg("reference", w,
                           os.path.join(tmp, f"unused_{w}"))
                    for w in (_ELASTIC_SAVE_W,) + _ELASTIC_RESUME_W}

            def resumed(w, workers=0, tag=""):
                # a resume leg checkpoints into its dir — copy per leg
                # so every one restores the SAME preempted snapshot
                c = os.path.join(tmp, f"ckpt_w{w}{tag}")
                shutil.copytree(master, c)
                return leg("resume", w, c, workers=workers)

            res = {_ELASTIC_SAVE_W: resumed(_ELASTIC_SAVE_W)}
            for w in _ELASTIC_RESUME_W:
                res[w] = resumed(w)
            res2_more_workers = resumed(
                _ELASTIC_RESUME_W[0], workers=4, tag="_w4workers")
        except RuntimeError as e:
            return {"ok": False, "error": str(e)}

    w0 = _ELASTIC_RESUME_W[0]
    sw = _ELASTIC_SAVE_W
    deltas = {
        f"w{w}": abs(float(res[w]["fingerprint"])
                     - float(refs[w]["fingerprint"]))
        for w in res
    }
    checks = {
        "preempted_mid_epoch2": (
            pre.get("preempted") is True
            and pre["manifest_meta"]["iter_in_epoch"] > 0),
        "meta_carries_world_width": (
            res[sw]["resumed_from"].get("world_width") == sw
            and "samples_in_epoch" in res[sw]["resumed_from"]),
        "same_width_resume_bitexact": (
            res[sw]["params_sha256"] == refs[sw]["params_sha256"]
            and res[sw]["fingerprint"] == refs[sw]["fingerprint"]),
        "placement_preserves_bytes_all_widths": all(
            r["placement_probe"]["raw_sha256"]
            == r["placement_probe"]["placed_sha256"]
            for r in list(res.values()) + [res2_more_workers]),
        "shard_count_independent": (
            res[w0]["params_sha256"]
            == res2_more_workers["params_sha256"]),
        "cross_width_completes_exact_steps": all(
            res[w]["steps"] == refs[w]["steps"] == expected_steps
            for w in res),
        "cross_width_float_agreement": all(
            d <= 1e-4 * abs(float(refs[sw]["fingerprint"]))
            for d in deltas.values()),
    }
    return {
        "ok": all(checks.values()),
        "save_width": sw,
        "resume_widths": sorted(res),
        "preempt_at_global_batch": preempt_at,
        "batches_per_epoch": batches_per_epoch,
        "expected_steps": expected_steps,
        "preempt": pre,
        "reference": {f"w{w}": refs[w] for w in sorted(refs)},
        "resume": {f"w{w}": res[w] for w in sorted(res)},
        "resume_w2_4workers": res2_more_workers,
        "fingerprint_delta_vs_reference": deltas,
        "checks": checks,
        "policy": "save at W, resume at W' — the manifest's GLOBAL "
                  "sample coordinate (samples_in_epoch) re-seeks the "
                  "deterministic loader under any shard count, and "
                  "restore_elastic re-places the width-agnostic host "
                  "bytes under the W' SpecSet.  Same-width resume and "
                  "shard-count changes are pinned bit-exact "
                  "(params sha256); CROSS-width step math agrees to "
                  "~1 float32 ulp but is not bitwise identical — XLA "
                  "fixes the cross-replica reduction order per width, "
                  "so the drill pins exact step completion plus the "
                  "recorded ulp-scale deltas instead of a physically "
                  "false bitwise claim",
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    p.add_argument("--models", nargs="+", default=["ssd"],
                   choices=["ssd", "ds2"])
    p.add_argument("--batch-per-chip", type=int, default=8)
    p.add_argument("--ds2-batch-per-chip", type=int, default=None,
                   help="per-chip batch for the ds2 sweep (default: "
                        "--batch-per-chip); the SSD step is far heavier "
                        "per record on a CPU host, so the two models "
                        "usually want different sizes")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--windows", type=int, default=3,
                   help="timed windows per device count (per-window "
                        "values kept; committed value = median)")
    p.add_argument("--res", type=int, default=300)
    p.add_argument("--ds2-hidden", type=int, default=256)
    p.add_argument("--ds2-layers", type=int, default=2)
    p.add_argument("--ds2-seconds", type=int, default=2)
    p.add_argument("--virtual", action="store_true",
                   help="emulate each mesh size on CPU (mechanism check, "
                        "NOT a performance measurement)")
    p.add_argument("--drill", action="store_true",
                   help="preemption-resume chaos drill on the widest mesh")
    p.add_argument("--elastic-drill", action="store_true",
                   help="ISSUE-19 elastic mesh drill: SIGTERM at width "
                        "4, resume the same snapshot at widths 2 and 8 "
                        "(implies --virtual); with --emit, writes the "
                        "ELASTIC artifact (training legs + the serving "
                        "width-vs-count reshape segment) and skips the "
                        "scaling sweeps")
    p.add_argument("--emit", default=None,
                   help="write the full artifact (sweeps + drill + "
                        "run_metadata) to this path, e.g. "
                        "MULTICHIP_r06.json")
    p.add_argument("--sweep-log", default="",
                   help="append every sweep line to this .jsonl file; "
                        "'' (the default) writes none")
    p.add_argument(_CHILD_FLAG, type=int, default=None,
                   dest="child_n", help=argparse.SUPPRESS)
    p.add_argument("--_child-model", default="ssd", dest="child_model",
                   help=argparse.SUPPRESS)
    p.add_argument("--_drill-child", default=None, dest="drill_child",
                   help=argparse.SUPPRESS)
    p.add_argument("--_drill-ckpt", default=None, dest="drill_ckpt",
                   help=argparse.SUPPRESS)
    p.add_argument("--_drill-preempt-at", type=int, default=0,
                   dest="drill_preempt_at", help=argparse.SUPPRESS)
    p.add_argument("--_drill-workers", type=int, default=0,
                   dest="drill_workers", help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.child_n is not None and args.drill_child:
        drill_child(args.drill_child, args.drill_ckpt,
                    args.drill_preempt_at, args.drill_workers)
        return 0
    if args.child_n is not None:
        if args.child_model == "ds2":
            child_ds2(args.child_n, args.batch_per_chip, args.steps,
                      args.windows, args.ds2_hidden, args.ds2_layers,
                      args.ds2_seconds)
        else:
            child_ssd(args.child_n, args.batch_per_chip, args.steps,
                      args.res, args.windows)
        return 0

    if args.elastic_drill:
        # widths 2/4/8 exist only as virtual meshes on this host
        args.virtual = True

    def env_for(n: int) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = (_REPO + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else _REPO)
        if args.virtual:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + f" --xla_force_host_platform_device_count={n}"
                                ).strip()
        return env

    if args.elastic_drill:
        elastic = run_elastic_drill(args, env_for)
        print(json.dumps({"elastic_drill": {
            "ok": elastic.get("ok"),
            "checks": elastic.get("checks"),
            "fingerprint_delta_vs_reference":
                elastic.get("fingerprint_delta_vs_reference"),
            "error": elastic.get("error")}}))
        if not args.emit:
            return 0 if elastic.get("ok") else 1

        # serving half: the width-vs-count reshape segment, in a fresh
        # process (its own XLA device pool), embedded in the artifact
        import tempfile

        from analytics_zoo_tpu.obs import run_metadata

        with tempfile.TemporaryDirectory() as tmp:
            seg_path = os.path.join(tmp, "reshape_segment.json")
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "serve_fleet_drill.py"),
                 "--reshape-segment", "--seed", "0", "--out", seg_path],
                env=env_for(8), capture_output=True, text=True,
                cwd=_REPO, timeout=900)
            if out.returncode == 0 and os.path.exists(seg_path):
                with open(seg_path) as f:
                    segment = json.load(f)
            else:
                segment = {"error": out.stderr[-800:],
                           "checks": {"ok": False}}
        ok = bool(elastic.get("ok")
                  and segment.get("checks", {}).get("ok"))
        artifact = {
            "round": 1,
            "tool": "bench_scaling --elastic-drill",
            "drill": "elastic_mesh",
            "virtual": True,
            "policy": "one checkpoint, any world: the training half "
                      "SIGTERMs a width-4 run and resumes the same "
                      "snapshot at widths 2/4/8 (restore_elastic + "
                      "global-sample loader re-seek); the serving half "
                      "reshapes a batch-saturated model's ladder onto "
                      "width-4 mesh slices instead of adding replicas "
                      "(the B/128 occupancy-knee rationale, "
                      "docs/MFU_CEILING.md).  Virtual meshes: MECHANISM "
                      "validation, not performance measurement — the "
                      "MULTICHIP_r0* convention",
            "training": elastic,
            "serving_reshape_segment": segment,
            "run_metadata": run_metadata("bench_scaling", seed=0,
                                         extra={"mode": "elastic_drill"}),
            "verdict": "PASS" if ok else "FAIL",
        }
        with open(args.emit, "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
        print(f"elastic drill: {artifact['verdict']} — wrote {args.emit}")
        return 0 if ok else 1

    rate_key = {"ssd": "images_per_sec", "ds2": "records_per_sec"}
    all_sweeps = {}
    for model in args.models:
        bpc = (args.ds2_batch_per_chip
               if model == "ds2" and args.ds2_batch_per_chip is not None
               else args.batch_per_chip)
        results = []
        for n in args.devices:
            cmd = [sys.executable, os.path.abspath(__file__), _CHILD_FLAG,
                   str(n), "--_child-model", model,
                   "--batch-per-chip", str(bpc),
                   "--steps", str(args.steps),
                   "--windows", str(args.windows),
                   "--res", str(args.res),
                   "--ds2-hidden", str(args.ds2_hidden),
                   "--ds2-layers", str(args.ds2_layers),
                   "--ds2-seconds", str(args.ds2_seconds)]
            out = subprocess.run(cmd, env=env_for(n), capture_output=True,
                                 text=True, cwd=_REPO)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("{")]
            if not line:
                print(json.dumps({"model": model, "n": n,
                                  "error": out.stderr[-500:]}),
                      file=sys.stderr)
                continue
            results.append(json.loads(line[-1]))

        key = rate_key[model]
        if results:
            base = results[0][key] / results[0]["n"]
            for r in results:
                r["efficiency_vs_1chip"] = round(
                    r[key] / (r["n"] * base), 3)
                r["virtual"] = bool(args.virtual)
                print(json.dumps(r))
                _append_sweep_log(args.sweep_log,
                                  {"metric": f"scaling_{model}_n{r['n']}",
                                   **r})
        all_sweeps[model] = results

    drill = None
    if args.drill:
        drill = run_drill(args, env_for)
        print(json.dumps({"drill": drill}))

    if args.emit:
        from analytics_zoo_tpu.obs import run_metadata

        artifact = {
            "round": 6,
            "tool": "bench_scaling",
            "virtual": bool(args.virtual),
            "devices": args.devices,
            "batch_per_chip": args.batch_per_chip,
            "windows_per_point": args.windows,
            "substrate": "parallel/specs.py declare-once SpecSet: "
                         "pipeline_specs('ssd'/'ds2') -> annotated jit "
                         "(in_shardings place host batches; state "
                         "NamedShardings declared once) — the ISSUE 9 "
                         "unified mesh substrate; children never call "
                         "shard_batch/device_put",
            "policy": "weak scaling at fixed per-chip batch, one fresh "
                      "subprocess per device count (XLA pins the count "
                      "at init), median of per-window rates with "
                      "windows recorded; virtual=true ⇒ CPU host "
                      "emulation validates MECHANISM not performance "
                      "(cores shared, efficiency trends to 1/n by "
                      "construction — the MULTICHIP_r0* convention)",
            "sweeps": all_sweeps,
            "drill": drill,
            "run_metadata": run_metadata("bench_scaling", seed=0),
        }
        with open(args.emit, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=False)
            f.write("\n")
        print(f"wrote {args.emit}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
