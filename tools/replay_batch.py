"""Deterministic bad-batch forensics: replay a recorded anomaly bundle.

The anomaly sentinel (``resilience.anomaly``, armed via
``Optimizer.set_anomaly_policy``) writes ``anomaly_<step>.json`` on the
first unhealthy step of an episode: the batch's coordinates under the
PR-2 determinism contract (``base_seed``, loader epoch, batch index), a
content hash of the offending batch, the decoded health word, and the
recent loss history.  This tool closes the loop:

1. **Re-materialize** the exact batch through
   ``data.parallel.replay_batches`` (fresh pipeline, serial path — the
   stream is byte-identical for any worker count) and assert the bytes
   match the recorded hash.
2. **Re-run one train step in full float32** (no bf16, no loss scale)
   from the last-known-good params when a checkpoint path is given, and
   read the in-graph health word again.
3. **Classify**: non-finite values in the batch itself → ``data`` (a
   corrupt record — fix the shard / add a filter); a clean batch that
   still trips the f32 health word → ``optimization`` (genuine
   divergence — lower the LR, clip harder); a clean batch AND a clean
   f32 step → ``not_reproducible_in_f32`` (precision- or
   state-dependent — suspect bf16 overflow or poisoned optimizer
   slots).

Usage::

    python tools/replay_batch.py --bundle ckpts/anomaly_42.json \
        --provider my_job:make_replay_provider [--out REPLAY.json]

The provider is an importable ``module:function`` returning a dict::

    {"dataset":   <freshly-constructed DataSet or ParallelLoader>,
     "model":     <built core.module.Model>,
     "criterion": <loss callable>,
     "optim":     <OptimMethod>,                      # optional
     "checkpoint_path": "ckpts/run1",                 # optional
     "batch_transform": lambda batch, index: batch}   # optional

``batch_transform`` re-applies any transformation the training loop did
AFTER the loader (chaos drills re-apply the recorded injected
corruption here, so the replayed bytes still match the recorded hash).

Length-bucketed streams (``data.bucket.BucketBatcher``, e.g.
``load_asr_train_set(bucket_edges=...)``) replay through the same hook
unchanged: the batcher is a trailing parent-process stage, so a
recorded bucketed batch re-materializes byte-identically from its
``(base_seed, epoch, index)`` coordinates for any worker count
(pinned by ``tests/test_bucket.py``).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from typing import Any, Dict, Optional

# runnable from a checkout without installing the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def replay(bundle: Dict[str, Any], dataset, model, criterion,
           optim=None, batch_transform=None,
           checkpoint_path: Optional[str] = None,
           data_abs_threshold: float = 1e8) -> Dict[str, Any]:
    """Programmatic core (the chaos drill calls this directly).

    ``data_abs_threshold``: a batch whose finite values exceed this
    magnitude is still classified as a ``data`` cause — a byte-scrambled
    payload usually decodes to wild-but-finite floats, not NaNs."""
    import numpy as np
    import jax

    from analytics_zoo_tpu.data.parallel import replay_batches
    from analytics_zoo_tpu.parallel import (SGD, create_train_state,
                                            make_train_step)
    from analytics_zoo_tpu.parallel import checkpoint as ckpt
    from analytics_zoo_tpu.resilience.anomaly import (batch_fingerprint,
                                                      decode_health,
                                                      health_sections)

    rng = bundle.get("rng", {}) or {}
    epoch = rng.get("loader_epoch")
    if epoch is None:
        epoch = bundle["epoch"]
    base_seed = rng.get("base_seed") or 0
    idx = int(bundle["batch_in_epoch"])

    got = replay_batches(dataset, int(epoch), [idx], base_seed=base_seed,
                         batch_transform=batch_transform)
    batch = got[idx]
    replayed_hash = batch_fingerprint(batch)
    recorded_hash = bundle.get("batch_hash")
    byte_identical = (recorded_hash is not None
                      and replayed_hash == recorded_hash)

    # -- data-cause check on the raw payload ------------------------------
    finite = True
    max_abs = 0.0
    for leaf in jax.tree_util.tree_leaves(batch):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.inexact):
            finite = finite and bool(np.all(np.isfinite(arr)))
            vals = np.abs(arr[np.isfinite(arr)])
            if vals.size:
                max_abs = max(max_abs, float(vals.max()))

    # -- one full-float32 step from last-known-good params ----------------
    optim = optim or SGD(0.05)
    state = create_train_state(model, optim)
    restored_from = None
    if checkpoint_path:
        found = ckpt.lkg_snapshot(checkpoint_path) \
            or ckpt.newest_intact(checkpoint_path)
        if found is not None:
            state = ckpt.load(found[0], target=state, verify=False)
            restored_from = os.path.basename(found[0])
    step = make_train_step(model.module, criterion, optim,
                           compute_dtype=None,      # full float32
                           health_check=True, skip_unhealthy=True)
    _, metrics = step(state, batch, 1.0)
    word = int(metrics["health"])
    loss = float(metrics["loss"])

    if not finite or max_abs > data_abs_threshold:
        cause = "data"
    elif word:
        cause = "optimization"
    else:
        cause = "not_reproducible_in_f32"
    return {
        "tool": "replay_batch",
        "epoch": int(epoch),
        "batch_in_epoch": idx,
        "base_seed": base_seed,
        "rematerialized": True,
        "byte_identical": bool(byte_identical),
        "recorded_hash": recorded_hash,
        "replayed_hash": replayed_hash,
        "batch_finite": bool(finite),
        "batch_max_abs": max_abs,
        "f32_restored_from": restored_from,
        "f32_health_word": word,
        "f32_health": decode_health(word,
                                    health_sections(state.params)),
        "f32_loss": loss if np.isfinite(loss) else repr(loss),
        "cause": cause,
    }


def _load_provider(spec: str):
    mod, _, fn = spec.partition(":")
    if not fn:
        raise SystemExit(f"--provider must be module:function, got {spec!r}")
    return getattr(importlib.import_module(mod), fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--bundle", required=True,
                    help="anomaly_<step>.json forensics bundle")
    ap.add_argument("--provider", required=True,
                    help="module:function returning the replay provider "
                         "dict (see module docstring)")
    ap.add_argument("--out", default=None,
                    help="write the replay report JSON here")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with open(args.bundle) as f:
        bundle = json.load(f)
    prov = _load_provider(args.provider)()
    report = replay(bundle, prov["dataset"], prov["model"],
                    prov["criterion"], optim=prov.get("optim"),
                    batch_transform=prov.get("batch_transform"),
                    checkpoint_path=prov.get("checkpoint_path"))
    report["bundle"] = os.path.basename(args.bundle)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    print(f"replay: cause={report['cause']} byte_identical="
          f"{report['byte_identical']}", file=sys.stderr)
    return 0 if report["byte_identical"] else 2


if __name__ == "__main__":
    raise SystemExit(main())
