"""Central metric-name catalog: every registry name, declared once.

The registry accepts free-form names, which is how five generations of
ad-hoc telemetry drifted apart in the first place.  This module is the
single source of truth: every ``registry.counter/gauge/histogram`` name
used anywhere in the package is declared here with its kind and one
line of meaning.  Three consumers pin against it:

- the ``registered-metric-names`` az-analyze source rule
  (``analysis/source.py``) — a call site registering an undeclared
  name fails tier-1 (dynamic, caller-parameterized names carry a
  reasoned ``# az-allow:`` waiver at the call site and declare their
  canonical families here);
- the docs table (``docs/OBSERVABILITY.md`` "What registers into it
  today") — ``tests/test_obs.py`` pins table ⇄ catalog equality, so
  the documentation cannot drift from the declaration;
- humans adding a metric: declare it here first, with the name
  convention ``<subsystem>/<metric>[/k=v...]`` (trailing ``k=v``
  segments become Prometheus labels; a trailing ``*`` in a catalog
  entry marks the labeled-family wildcard).

Entries map name (or ``...=*`` family pattern) → ``"<kind> · <doc>"``.
"""

from __future__ import annotations

from typing import Dict

CATALOG: Dict[str, str] = {
    # -- serving (ServingMetrics, fed by ServingRuntime) --------------------
    "serve/submitted":
        "counter · requests submitted to the runtime (admitted or shed "
        "at the door)",
    "serve/completed":
        "counter · requests that reached a device and returned a result",
    "serve/failed":
        "counter · requests failed after exhausting replica failover",
    "serve/batches":
        "counter · batches dispatched to the replica pool",
    "serve/redispatches":
        "counter · batches re-dispatched exactly once after a replica "
        "fence",
    "serve/deadline_misses_completed_late":
        "counter · completed requests whose result landed past the "
        "deadline",
    "serve/shed/cause=*":
        "counter · requests shed before device dispatch, by cause "
        "(queue_full | deadline)",
    "serve/latency_s/tier=*":
        "histogram · end-to-end request latency per degradation tier",
    "serve/batch_fill":
        "histogram · dispatched-batch fill fraction (n_valid/max_batch)",
    "serve/queue_depth":
        "histogram · admission-queue depth sampled at each dispatch",
    "serve/staging_alloc":
        "counter · batches (warm() included) whose pair of staging "
        "buffers was allocated or replaced instead of reused: stays at "
        "the number of geometries when every model's rows keep their "
        "shape",
    "serve/assembled_ahead":
        "counter · batches assembled while another batch's program ran "
        "(ServingRuntime._assemble_ahead: a tier that returns a device "
        "array; never registered by a runtime whose tiers answer on the "
        "host)",
    # -- decoder-LM session tier (pipelines/lm.py, ISSUE 28) ----------------
    "lm/cache_tokens":
        "gauge · tokens the replica's live sessions hold in the paged "
        "cache",
    "lm/cache_fill":
        "gauge · share of the paged pool's pages that sessions hold",
    "lm/sessions_live":
        "gauge · sessions that hold a slot (and pages) on the replica",
    "lm/paged_pages":
        "gauge · pages that hold at least one token of the last decode "
        "step's live rows, one causal layer: what the paged decode "
        "attention (ops/pallas_lm_decode.py, the latent or the "
        "grouped-query kernel) had to read",
    "lm/paged_grid_steps":
        "gauge · grid steps the paged decode attention was launched with "
        "in the last decode step, one causal layer (the flat list of "
        "(row, page) items is as long as the pool; the steps past the "
        "last item do nothing)",
    "lm/selected_one_pass":
        "gauge · full layers of the decode step program whose attention "
        "over the selected entries is ONE Pallas program that reads a "
        "row's gathered copy once (ops/pallas_lm_decode.py "
        "selected_mla_decode); 0 where the widths leave it to XLA's "
        "mla_absorbed, and in a model without full layers",
    "lm/ring_tokens":
        "gauge · entries the last decode step's live rows hold in a "
        "sliding layer's ring, one layer (sum of min(length, window)): "
        "the windows' work beside lm/paged_pages, the paged layers'",
    "lm/ssm_slots_live":
        "gauge · slots whose recurrent state belongs to a live session (a "
        "model with a state-space mixer: every layer keeps one float32 "
        "state and one convolution state a slot), set each decode step",
    "lm/ssm_state_bytes":
        "gauge · bytes of float32 recurrent state the live sessions hold "
        "over all layers: what a decode step of all of them reads once "
        "and writes once (ops/pallas_ssm_decode.py, in place)",
    "lm/ssm_state_starts":
        "counter · decode rows and prefill chunks at position 0: states "
        "that started from zeros inside the step program, whatever the "
        "slot held before (a recycled slot is never zeroed by a program "
        "or a transfer of its own)",
    "lm/expert_tokens/stat=*":
        "histogram · tokens a held expert of an expert layer got in one "
        "decode step: stat=mean over the held experts of the step's "
        "expert layers, stat=max the busiest of them",
    # -- multiplexed fleet (ServingRuntime(models=...), ISSUE 14) -----------
    "serve/submitted/model=*":
        "counter · requests submitted per multiplexed model",
    "serve/completed/model=*":
        "counter · requests completed per multiplexed model",
    "serve/failed/model=*":
        "counter · requests failed per multiplexed model",
    "serve/shed/model=*":
        "counter · requests shed per multiplexed model, by cause "
        "(model= then cause= labels)",
    "serve/deadline_misses_completed_late/model=*":
        "counter · completed-late requests per multiplexed model",
    "serve/latency_s/model=*":
        "histogram · end-to-end request latency per (model, tier)",
    "serve/model_weight/model=*":
        "gauge · weighted-EDF dispatch weight per model (1 = plain EDF; "
        "follows the model's worst fast-window SLO burn)",
    "serve/sessions/opened":
        "counter · streaming sessions opened (session-affine scheduling)",
    "serve/sessions/closed":
        "counter · streaming sessions closed (final chunk or state loss)",
    "serve/sessions_open":
        "gauge · streaming sessions currently open",
    "serve/cold_compiles":
        "counter · dispatches that paid the cold-compile tax (a replica "
        "served a geometry it had never compiled — what pre-warm deletes)",
    # -- live-weight hot-swap + canary (ServingRuntime.hot_swap) ------------
    "serve/swap/rollouts":
        "counter · hot-swap rollouts started (checkpoint verified, "
        "canary stage armed)",
    "serve/swap/replicas_swapped":
        "counter · replicas drained, re-installed with new weights and "
        "rejoined during rollouts",
    "serve/swap/rollbacks":
        "counter · rollouts reverted to the serve-lkg checkpoint tier "
        "(tripped canary or mid-rollout anomaly; exactly once each)",
    "serve/swap/lkg_promotions":
        "counter · serving last-known-good promotions after fully "
        "healthy rollouts (the hysteresis mirror of train LKG)",
    "serve/canary/mirrored/model=*":
        "counter · live requests mirrored to the canary weights per "
        "model (seeded fraction; never counted in accounting())",
    "serve/canary/divergence/model=*":
        "histogram · per-row output divergence between live and canary "
        "weights, labeled model= and swap= (rollout index)",
    "serve/canary/latency_s/model=*":
        "histogram · modeled service latency of the canary tier, "
        "labeled model= and swap= (rollout index)",
    "serve/canary/trips":
        "counter · canary stages tripped over their divergence/latency "
        "budgets (each one triggers a rollback)",
    # -- autoscaler (serving.autoscale.Autoscaler) --------------------------
    "autoscale/replicas":
        "gauge · current (or just-actuated target) replica-pool size",
    "autoscale/grow":
        "counter · pool-growth actuations taken by the policy loop",
    "autoscale/shrink":
        "counter · drain-then-retire shrink actuations taken",
    "autoscale/reshape":
        "counter · width-vs-count reshape actuations: a batch-saturated "
        "model's tier ladder swapped onto wider mesh slices instead of "
        "adding replicas (the B/128 occupancy-knee rationale)",
    # -- elastic mesh (parallel.train Optimizer elastic resume) -------------
    "elastic/restores":
        "counter · checkpoint restores re-placed onto a different world "
        "width than they were saved at",
    "elastic/world_width":
        "gauge · data-axis width the last elastic restore re-placed "
        "onto",
    # -- device health (resilience.health.HealthSentinel(registry=)) --------
    "health/audits":
        "counter · cross-replica parity audits run (per-replica param "
        "fingerprints compared at the decision boundary)",
    "health/audit_divergences":
        "counter · audits whose replica fingerprints disagreed (proven "
        "silent data corruption)",
    "health/shadow_checks":
        "counter · shadow recomputes run (sampled microbatch forward "
        "re-executed on a second device)",
    "health/shadow_mismatches":
        "counter · shadow recomputes disagreeing with the primary",
    "health/straggler_flags":
        "counter · devices flagged by the step-time EWMA hysteresis "
        "ladder as persistent stragglers",
    "health/quarantines":
        "counter · devices quarantined (training eviction raised or "
        "serving replica drained with device_budget decremented)",
    # -- SLO engine (obs.slo.SloEvaluator(registry=)) -----------------------
    "slo/fast_burn/slo=*":
        "gauge · latest fast-window burn rate per SLO (1.0 = budget "
        "consumed exactly at the sustainable rate)",
    "slo/slow_burn/slo=*":
        "gauge · latest slow-window burn rate per SLO",
    "slo/trips/slo=*":
        "counter · rising-edge transitions into burning per SLO (the "
        "fast-window trips the drill banks)",
    # -- training (Optimizer.set_observability) -----------------------------
    "train/dispatch/step_s":
        "histogram · host interval of the train-step call (async "
        "dispatch latency, not fenced device wall)",
    "train/dispatch/steps":
        "counter · train steps dispatched",
    "train/dispatch/records":
        "counter · training records dispatched",
    "train/anomaly/bad_steps":
        "counter · steps the anomaly sentinel discarded in-graph",
    "train/anomaly/rollbacks":
        "counter · last-known-good rollbacks the anomaly ladder took",
    "checkpoint/save_s":
        "histogram · checkpoint save wall seconds (sha256-manifested "
        "atomic publish)",
    "checkpoint/restore_s":
        "histogram · checkpoint restore wall seconds",
    # -- embedding lookups (ops.embedding.publish_lookup_stats) -------------
    "embed/lookups":
        "counter · id batches whose dedup stats were published",
    "embed/rows_touched":
        "gauge · unique table rows the last id batch gathered (what the "
        "dedup'd lookup actually fetches; the sparse apply's row count)",
    "embed/unique_fraction":
        "gauge · unique/total id ratio of the last batch (the dedup "
        "win: Zipfian traffic sits well below 1.0)",
    # -- data loading (ReadStats.publish) -----------------------------------
    "data/read/records":
        "gauge · records successfully yielded by resilient shard reads",
    "data/read/retries":
        "gauge · transient I/O errors retried",
    "data/read/skipped_records":
        "gauge · undecodable records dropped (skip-and-count)",
    "data/read/skipped_shards":
        "gauge · whole shards dropped after retry exhaustion",
}

#: Every :func:`analytics_zoo_tpu.obs.stage` name, declared once with
#: the thread it runs on and the code it brackets.  Stages land in the
#: profiler's trace and in the process's stage ring (``obs.stages()``),
#: not in a registry, so they have no kind.  ``az/input/worker`` is the
#: one record that no ``stage`` writes: a forked worker must never touch
#: JAX, so it counts seconds into shared memory, sends them with the
#: epoch's end marker, and the parent records them as it reads that.
STAGES: Dict[str, str] = {
    "az/input/next":
        "prefetch thread · the loader's next(): one host batch",
    "az/input/place":
        "prefetch thread · shard_batch: the batch's transfer to the mesh",
    "az/input/put_wait":
        "prefetch thread · the put into the bounded prefetch queue "
        "(blocked = the loader is ahead of the train loop)",
    "az/input/get_wait":
        "main thread · the get from the prefetch queue up to the item's "
        "arrival (blocked = the train loop is starved)",
    "az/input/pool_start":
        "prefetch thread · ParallelLoader forking a worker pool, until "
        "its first group is ready to yield: once a POOL (kept from epoch "
        "to epoch), inside az/input/epoch_start of the epoch that had to "
        "fork it",
    "az/input/epoch_start":
        "prefetch thread · ParallelLoader's first next() of an epoch, "
        "until the epoch's first group is handed over (inside that "
        "az/input/next); attrs: kept (True when the pool that serves the "
        "epoch was alive before it, False when the epoch had to fork one)",
    "az/input/worker":
        "record, one a worker an epoch, written when the parent reads "
        "the worker's end marker of that epoch (and once for the epoch "
        "a worker was in when its pool stopped or it died) · from the "
        "start of the worker's walk of the epoch to its end marker; "
        "attrs: worker, epoch, chain_s (decode + augment of its own "
        "samples), put_s (copy into the ring + blocked on full slots: "
        "where a kept worker that is ahead stands still), walk_s "
        "(reading samples that are another worker's), groups shipped, "
        "spills",
    "az/train/prepare":
        "main thread · from the batch's arrival to the step's call: its "
        "size, the choice of step program, and place_batch where neither "
        "the prefetch thread nor the jit places it",
    "az/train/dispatch":
        "main thread · the train step's call (asynchronous dispatch)",
    "az/train/summary":
        "main thread · TrainSummary.add_scalar of loss and learning "
        "rate: the loss's float() is the step's fence",
    "az/train/boundary":
        "main thread · _boundary_checks (validation, checkpoint, stall, "
        "preemption) and end_when",
    "az/serve/pump":
        "main thread · ServingRuntime.pump(), whole",
    "az/serve/collate":
        "main thread · DeadlineBatcher._collate: the batch's payloads "
        "copied, padded, into one of the two staging buffers kept for "
        "the geometry; attrs: reused (False when the pair was allocated "
        "or replaced for this batch), ahead (True when the batch was "
        "assembled while another's program ran, inside that batch's "
        "az/serve/forward; counter serve/assembled_ahead counts those)",
    "az/serve/forward":
        "main thread · ReplicaPool.dispatch of one batch (replica "
        "choice, watchdog, the tier's forward, failover); for a tier "
        "that returns a device array also the NEXT batch's "
        "az/serve/collate and the start of its transfer, between the "
        "tier's return and the fetch",
    "az/serve/h2d":
        "main thread · a tier's jnp.asarray of the host batch (SSD: the "
        "pictures; LM: the token ids, positions and page tables): the "
        "host's side of the transfer (staging and enqueue)",
    "az/serve/dispatch":
        "main thread · a tier's call of its serve program (SSD: "
        "detect_normalized; LM: the decode or prefill step; "
        "asynchronous dispatch)",
    "az/serve/result_wait":
        "main thread · np.asarray of the answer: waits for the program "
        "and copies the answer (detections, logits) to the host. The LM "
        "and DS2 tiers' own, inside their forward; for a tier that "
        "returns a device array (SSD) Replica.forward's fetch of it",
    "az/lm/step":
        "main thread · the LM tier's forward of one batch, whole; attrs: "
        "rows (live rows), edge, phase (prefill or decode)",
    "az/lm/cache_admit":
        "main thread · the LM tier's admission of a batch's chunks into "
        "the session cache: slots and pages for the new tokens",
    "az/serve/handout":
        "main thread · _dispatch after the pool returns: canary, "
        "finishing each request, accounting, _after_dispatch",
}

#: Every ``jax.named_scope`` of a hot-path device program, declared once
#: with the code it brackets.  A scope is metadata of the compiled
#: program (the ``op_name`` of its instructions): it changes no
#: instruction and costs nothing at run time.  The device trace names an
#: operation by its HLO line alone, so ``obs.device_scopes`` maps the
#: instructions of a registered program to these names from its compiled
#: text.  A scope is a SIBLING of the ones beside it, never around one:
#: readers take the first ``lm/`` name of an ``op_name``.
SCOPES: Dict[str, str] = {
    "train/augment":
        "make_train_step · the device_transform fused into the step (the "
        "device-side augmentation)",
    "train/update":
        "make_train_step · everything after the gradient: loss-scale and "
        "clip, lr_for_step, the optimizer's update, apply_updates, the "
        "health word, the masked selects of a skipped step",
    "ssd/base":
        "SSDVgg · VGGBase: conv1_1 to fc7 (the flax layer names follow "
        "the scope in an op_name: ssd/base/vgg/conv1_2)",
    "ssd/extras":
        "SSDVgg · ExtraLayers: conv6_1 onwards",
    "ssd/heads":
        "SSDVgg · conv4_3_norm, the loc_i / conf_i convolutions, their "
        "reshapes and the two concatenations",
    "ssd/loss_match":
        "multibox_loss · match_priors: the IoU matrix, each prior's best "
        "ground truth, the bipartite scatter",
    "ssd/loss_loc":
        "multibox_loss · the matched ground truths' boxes by masked sums "
        "over the G, smooth-L1 of the encoded deltas on the positives",
    "ssd/loss_conf":
        "multibox_loss · the cross-entropy: the matched labels by a masked "
        "sum over the G, log_softmax, the matched class's log-probability "
        "by a masked sum over the C, the masked sum",
    "ssd/loss_mine":
        "multibox_loss · hard-negative mining: the threshold's 32 counting "
        "passes over the candidates' ordered bits, the ties' running count",
    "ssd/normalize":
        "SSDPredictor._detect / _detect_yuv · the staging arithmetic "
        "before the forward: uint8 to float less the pixel means, the "
        "yuv420 reconstruction",
    "ssd/softmax":
        "SSDPredictor._forward_tail · jax.nn.softmax of the conf logits",
    "ssd/detout":
        "SSDPredictor._forward_tail · detection_output whole: the Pallas "
        "program (or the XLA path) and whatever XLA puts round it",
    "ssd/rescale":
        "SSDPredictor._forward_tail · scale_detections: the boxes to the "
        "pictures' sizes",
    "lm/embed":
        "decode_rows / prefill_step · the embedding look-up",
    "lm/proj":
        "decode_rows / prefill_step · the top of each layer: rms_norm of "
        "the stream, then gqa_project, or latents and queries",
    "lm/cache_write":
        "decode_rows / prefill_step · a CAUSAL layer's write of the new "
        "entries into the paged pool (a full layer's stands under "
        "lm/indexer, a window layer's under its window scope)",
    "lm/indexer":
        "decode_rows / prefill_step · a full layer's index projections, "
        "its cache writes and the paged index scores",
    "lm/select":
        "decode_rows · a full layer's top-k of the index scores and the "
        "gather of the selected entries",
    "lm/mla_full":
        "decode_rows / prefill_step · a full layer's latent attention "
        "over the selected entries, with its output projection",
    "lm/mla_paged":
        "decode_rows / prefill_step · a causal latent layer's attention "
        "over the paged pool, with its output projection",
    "lm/gqa_paged":
        "decode_rows / prefill_step · a global grouped-query layer's "
        "attention over the paged pool, with its output projection",
    "lm/mla_window":
        "decode_rows / prefill_step · a sliding latent layer: ring write, "
        "gather, attention, output projection",
    "lm/gqa_window":
        "decode_rows / prefill_step · a window grouped-query layer: ring "
        "write, gather, attention with the sink, output projection",
    "lm/ssm_proj":
        "ssm_project · a state-space mixer's in-projection off the "
        "layer's normed input, with its multipliers: the gate z, the "
        "convolution's input u, dt",
    "lm/ssm_conv":
        "ssm_decode / ssm_prefill · the causal convolution with the "
        "session's last inputs read and written, the split into x, B, C "
        "and the step sizes",
    "lm/ssm_update":
        "ssm_decode · one token's state update of every row: the Pallas "
        "kernel lm_decode_ssm_update over the slots' array in place (the "
        "jax.numpy step with a gather and a scatter at widths off its "
        "tiles), D x",
    "lm/ssm_scan":
        "ssm_prefill · a chunk's chunked scan from the session's state "
        "to its state at the last real token, and the slot's write",
    "lm/ssm_out":
        "ssm_finish · the gate, the group norm and the out-projection "
        "with its multiplier",
    "lm/dense_mlp":
        "feed_forward · a dense layer's gated MLP",
    "lm/route":
        "moe_held_experts · the router: scores, groups, top-k",
    "lm/experts":
        "moe_held_experts · the held experts' products",
    "lm/shared_mlp":
        "moe_held_experts · the shared expert's gated MLP",
    "lm/head":
        "head · the final norm and the vocabulary projection",
}


def lookup(name: str) -> bool:
    """Whether a concrete registry name is covered by the catalog —
    exact entry, or a ``...=*`` family whose prefix matches."""
    if name in CATALOG:
        return True
    for pattern in CATALOG:
        if pattern.endswith("*") and name.startswith(pattern[:-1]):
            return True
    return False
