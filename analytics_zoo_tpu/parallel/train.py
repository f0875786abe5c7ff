"""Train/eval step factories + the distributed Optimizer.

This is the TPU-native replacement for BigDL's ``DistriOptimizer`` stack
(reference ``Optimizer(model, trainSet, criterion).setOptimMethod
.setValidation.setCheckpoint.setTrainSummary.setEndWhen.optimize()``,
``ssd/example/Train.scala:219-252``).  Where BigDL runs a Spark job per
iteration — executor model replicas, block-manager AllReduce, driver-side
weight update — here the whole iteration is ONE jitted function: batches
arrive sharded over the mesh's ``data`` axis, parameters are replicated, and
XLA compiles the gradient mean into an ICI all-reduce.  There is no
parameter server and no explicit communication code in the loss path.

The host-side loop (this file's ``Optimizer.optimize``) only does what must
stay on host: data feeding, triggers, validation, checkpointing, summaries,
and metric-driven LR control (Plateau).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from analytics_zoo_tpu.core.module import Model, accepted_kwargs
from analytics_zoo_tpu.obs import device_scopes
from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.parallel.optim import (
    Adam,
    OptimMethod,
    TrainingState,
    Trigger,
)

logger = logging.getLogger("analytics_zoo_tpu")


class TrainState(struct.PyTreeNode):
    """Everything the jitted step mutates, as one donated pytree."""

    step: jax.Array
    params: Any
    model_state: Any          # batch_stats & friends (may be empty dict)
    opt_state: Any
    rng: jax.Array


def create_train_state(model: Model, optim: OptimMethod, rng=0) -> TrainState:
    if model.variables is None:
        raise ValueError("model.build(...) before creating a train state")
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    variables = dict(model.variables)
    params = variables.pop("params")
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        model_state=variables,
        opt_state=optim.tx.init(params),
        rng=rng,
    )


def state_to_variables(state: TrainState):
    return {"params": state.params, **state.model_state}


def _forward(module, variables, inputs, train: bool, rngs=None, mutable=False):
    args = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
    kwargs = accepted_kwargs(module, {"train": train})
    if rngs:
        kwargs["rngs"] = rngs
    if mutable:
        return module.apply(variables, *args, mutable=["batch_stats"], **kwargs)
    return module.apply(variables, *args, **kwargs), None


def _call_criterion(criterion, output, batch):
    """Criterion protocol: ``crit(output, target)`` with optional ``mask``;
    plain callables instead take ``(output, batch)`` for full control."""
    from analytics_zoo_tpu.core.criterion import Criterion

    if isinstance(criterion, Criterion):
        target = batch.get("target")
        if "target_mask" in batch:
            return criterion(output, target, mask=batch["target_mask"])
        return criterion(output, target)
    return criterion(output, batch)


def cast_floating(tree: Any, dtype) -> Any:
    """Cast every floating-point leaf of a pytree to ``dtype``."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def resolve_compute_dtype(compute_dtype):
    """'bf16'/'fp32'/None/dtype → jnp dtype or None (no casting)."""
    if compute_dtype is None or compute_dtype in ("fp32", "float32"):
        return None
    if compute_dtype in ("bf16", "bfloat16"):
        return jnp.bfloat16
    return jnp.dtype(compute_dtype)


def make_train_step(
    module,
    criterion: Callable,
    optim: OptimMethod,
    mesh=None,  # legacy hint; pass specs= for annotated in/out shardings
    specs=None,
    state=None,
    annotate_batches: bool = True,
    loss_scale: float = 1.0,
    grad_clip_norm: Optional[float] = None,
    skip_loss_above: Optional[float] = None,
    compute_dtype=None,
    grad_accum: int = 1,
    device_transform: Optional[Callable] = None,
    forward_fn: Optional[Callable] = None,
    health_check: bool = False,
    skip_unhealthy: bool = False,
    metric_fn: Optional[Callable] = None,
):
    """Build the jitted train step.

    ``specs`` (optional, a :class:`~analytics_zoo_tpu.parallel.specs.
    SpecSet`): the pipeline's declare-once sharding.  The step is then
    jitted with explicit ``in_shardings``/``out_shardings`` — state and
    metrics carry the declared NamedShardings, and (single-process, no
    per-key batch overrides) HOST batches can be passed straight in:
    jit itself places them dim-0 over the ``data`` axis, so no pipeline
    calls ``device_put``/``shard_batch`` anywhere.  With tensor-parallel
    rules armed, pass the concrete ``state`` too (per-leaf specs need
    the tree structure).  Batch leaves must be batch-major arrays (the
    ``shard_batch`` contract); for batches carrying 0-d leaves pass
    ``annotate_batches=False`` (state/metrics keep their declared
    shardings, batches arrive pre-placed by ``specs.place_batch``,
    whose documented contract replicates scalars) — the Optimizer does
    this automatically when it meets such a batch.

    ``metric_fn`` (optional): ``metric_fn(batch) → {name: scalar}``,
    fused into the compiled step and merged into the returned metrics —
    e.g. the length-bucketed DS2 path reports ``padding_efficiency``
    (valid / padded frames) per step from the batch's ``n_frames``.

    ``device_transform`` (optional) is fused INTO the compiled step: the
    batch passes through it on-device before the loss (used for the
    device-side augmentation path — halves per-step dispatches and
    avoids materializing the transformed batch in HBM between calls).

    ``forward_fn`` (optional) replaces the default ``module.apply``
    forward: ``forward_fn(variables, inputs, train, rngs) → (output,
    new_model_state)``.  Used for parallel-forward variants whose
    program differs from the plain apply — e.g. the sequence-parallel
    DS2 forward (``models.deepspeech2.make_sequence_parallel_forward_fn``)
    that shards T over a ("data", "sequence") mesh inside the step.

    ``skip_loss_above`` reproduces MultiBoxLoss's gradient-explosion guard
    (reference ``common/nn/MultiBoxLoss.scala:546``: skip backward when
    loss > 50) — the update is zeroed when the loss exceeds the threshold,
    as a lax.cond-free masked select so the step stays a single program.

    ``health_check=True`` adds the anomaly sentinel's in-graph health
    fold (``resilience.anomaly``): one fused isfinite-and-threshold
    reduction over the loss, the (unscaled, clipped) grads, and the
    UPDATED params, emitted as ``metrics["health"]`` — an int32 word
    whose per-tree-section bits name which parameter subtree went
    non-finite (``decode_health``).  ``skip_unhealthy=True`` additionally
    discards the whole update in-graph whenever the word is non-zero —
    params, optimizer slots AND batch stats keep their pre-step values —
    subsuming ``skip_loss_above`` (which becomes the word's spike bit).

    ``compute_dtype='bf16'`` enables mixed precision: parameters stay fp32
    masters (the optimizer update is fp32), the forward/backward runs in
    bfloat16 — convs/matmuls hit the MXU at its native rate — and model
    outputs are cast back to fp32 before the criterion so softmax/log
    numerics are unaffected.  bf16 shares fp32's exponent range, so the
    default ``loss_scale=1.0`` is safe (unlike fp16); the scale hook stays
    plumbed for experimentation.  This replaces the reference's MKL-tuned
    kernels as the fast-kernel story (``pipeline/ssd/pom.xml:73-83``).

    ``grad_accum=N`` splits the batch into N microbatches and accumulates
    their gradients with a ``lax.scan`` inside the SAME jitted step —
    activation memory drops ~N× (large effective batches on one chip)
    while the update equals the full-batch step exactly for mean-reduced
    losses.  BatchNorm running stats are chained through the N
    microbatches sequentially (the EMA advances N times per step — same
    data seen, faster-moving stats than a single full-batch update).
    """

    cdtype = resolve_compute_dtype(compute_dtype)

    def loss_fn(params, model_state, batch, rng):
        if cdtype is not None:
            params_c = cast_floating(params, cdtype)
            inputs = cast_floating(batch["input"], cdtype)
        else:
            params_c, inputs = params, batch["input"]
        variables = {"params": params_c, **model_state}
        if forward_fn is not None:
            output, new_model_state = forward_fn(
                variables, inputs, train=True, rngs={"dropout": rng})
            new_model_state = new_model_state or {}
        else:
            output, new_model_state = _forward(
                module, variables, inputs, train=True,
                rngs={"dropout": rng}, mutable=True,
            )
        if cdtype is not None:
            output = cast_floating(output, jnp.float32)
            # batch stats remain fp32 masters
            new_model_state = cast_floating(new_model_state, jnp.float32)
        loss = _call_criterion(criterion, output, batch)
        return loss * loss_scale, (new_model_state, loss)

    def _grads(params, model_state, batch, rng):
        """(grads, model_state, loss) — single-shot or scan-accumulated."""
        if grad_accum <= 1:
            g, (ms, loss) = jax.grad(loss_fn, has_aux=True)(
                params, model_state, batch, rng)
            return g, ms, loss
        # every batch leaf must be batch-major with the SAME dim 0,
        # divisible by grad_accum — a silent reshape of a shared (non-
        # batch) leaf would feed each microbatch a slice of it
        sizes = {getattr(leaf, "shape", (None,))[0] if getattr(
            leaf, "ndim", 0) > 0 else None
            for leaf in jax.tree_util.tree_leaves(batch)}
        if None in sizes or len(sizes) != 1:
            raise ValueError(
                f"grad_accum needs batch-major array leaves with one "
                f"common dim 0, got leading dims {sizes}")
        (B,) = sizes
        if B % grad_accum:
            raise ValueError(f"batch size {B} not divisible by "
                             f"grad_accum={grad_accum} (pad or "
                             f"drop_remainder the tail batch)")
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((grad_accum, B // grad_accum) + x.shape[1:]),
            batch)

        # only the mutable collection rides the scan carry — constant
        # collections in model_state would mismatch the returned structure
        mut0 = ({"batch_stats": model_state["batch_stats"]}
                if "batch_stats" in model_state else {})

        def body(carry, inp):
            g_acc, loss_acc, mut = carry
            mb, j = inp
            g, (new_mut, l) = jax.grad(loss_fn, has_aux=True)(
                params, {**model_state, **mut}, mb,
                jax.random.fold_in(rng, j))
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (g_acc, loss_acc + l, new_mut), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (g_sum, loss_sum, mut), _ = jax.lax.scan(
            body, (zeros, 0.0, mut0), (micro, jnp.arange(grad_accum)))
        inv = 1.0 / grad_accum
        return (jax.tree_util.tree_map(lambda g: g * inv, g_sum),
                mut, loss_sum * inv)

    def step_fn(state: TrainState, batch, lr_scale):
        if device_transform is not None:
            # fused in-graph (e.g. the device-side augmentation): ONE
            # compiled program and one dispatch per step instead of
            # transform + step as separate calls — a jitted transform
            # passed here simply inlines during tracing.  stop_gradient
            # marks the batch constant w.r.t. params so autodiff/remat
            # never recomputes the transform in the backward pass.
            with jax.named_scope("train/augment"):
                batch = jax.lax.stop_gradient(device_transform(batch))
        rng, new_rng = jax.random.split(jax.random.fold_in(state.rng, state.step))
        grads, new_model_state, loss = _grads(
            state.params, state.model_state, batch, rng)
        # everything after the gradient stands under ONE named section of
        # the compiled step (obs/names.py::SCOPES), in two pieces: the
        # model's and the criterion's own scopes end where _grads returns
        with jax.named_scope("train/update"):
            if loss_scale != 1.0:
                grads = jax.tree_util.tree_map(lambda g: g / loss_scale,
                                               grads)
            gnorm = optax.global_norm(grads) if grad_clip_norm else None
            if grad_clip_norm:
                scale = jnp.minimum(1.0, grad_clip_norm / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            lr = optim.lr_for_step(state.step, lr_scale)
            opt_state = _set_lr(state.opt_state, lr)
            updates, new_opt_state = optim.tx.update(grads, opt_state,
                                                     state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "lr": lr}
        if metric_fn is not None:
            metrics.update(metric_fn(batch))
        # merge: mutable apply only returns the batch_stats collection; any
        # other collection in model_state must survive untouched
        merged_model_state = {**state.model_state, **new_model_state}

        def masked(keep, new, old):
            """Elementwise select: the update applies only where ``keep``."""
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(keep, n, o), new, old)

        with jax.named_scope("train/update"):
            health = None
            if health_check or skip_unhealthy:
                from analytics_zoo_tpu.resilience import anomaly

                health = anomaly.tree_health_word(
                    loss, grads, new_params,
                    anomaly.health_sections(state.params),
                    spike_loss_above=skip_loss_above)
                metrics["health"] = health
            if skip_unhealthy:
                # anomaly-sentinel guard: ANY non-finite loss/grad/param
                # (or a loss spike past skip_loss_above) discards the
                # entire update — params, optimizer slots and batch stats
                # keep their pre-step values, so a poison batch can never
                # seed NaNs into the training state
                keep = health == 0
                new_params = masked(keep, new_params, state.params)
                new_opt_state = masked(keep, new_opt_state, opt_state)
                merged_model_state = masked(keep, merged_model_state,
                                            state.model_state)
            elif skip_loss_above is not None:
                # reference guard (MultiBoxLoss.scala:546): a loss spike
                # skips the ENTIRE update — params and optimizer state
                # (momentum/Adam moments, counts) stay untouched, not
                # just zeroed grads
                keep = loss <= skip_loss_above
                new_params = masked(keep, new_params, state.params)
                new_opt_state = masked(keep, new_opt_state, opt_state)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            model_state=merged_model_state,
            opt_state=new_opt_state,
            rng=new_rng,
        )
        return new_state, metrics

    donate = (0,)
    if specs is not None:
        # declare-once substrate: the ONLY sharding source is the
        # pipeline's SpecSet — state in/out carry its NamedShardings,
        # batches ride the data-axis prefix (jit transfers host arrays
        # itself on the single-process fast path), scalars (lr_scale,
        # every metric) are replicated
        state_sh = specs.state_shardings(state)
        return jax.jit(
            step_fn, donate_argnums=donate,
            in_shardings=(state_sh,
                          (specs.batch_shardings() if annotate_batches
                           else None),
                          specs.replicated),
            out_shardings=(state_sh, specs.replicated))
    return jax.jit(step_fn, donate_argnums=donate)


def _set_lr(opt_state, lr):
    """Write the traced LR into optax's injected hyperparams slot."""
    if hasattr(opt_state, "hyperparams"):
        hp = dict(opt_state.hyperparams)
        hp["learning_rate"] = lr
        return opt_state._replace(hyperparams=hp)
    return opt_state


def make_eval_step(module, compute_dtype=None, specs=None):
    """Jitted inference step: ``outputs = eval_step(variables, inputs)``.

    ``compute_dtype='bf16'`` runs the forward in bfloat16 (serving-path
    mixed precision) with outputs cast back to fp32.

    ``specs`` (a :class:`~analytics_zoo_tpu.parallel.specs.SpecSet`):
    mesh-annotated serving — jit places the variables replicated and the
    batch dim-0 over the ``data`` axis, so a serving forward scales out
    by widening the mesh with no predictor code change (the same
    declare-once substrate the train step consumes).
    """

    cdtype = resolve_compute_dtype(compute_dtype)

    def eval_fn(variables, inputs):
        if cdtype is not None:
            variables = dict(variables)
            variables["params"] = cast_floating(variables["params"], cdtype)
            inputs = cast_floating(inputs, cdtype)
        out, _ = _forward(module, variables, inputs, train=False)
        if cdtype is not None:
            out = cast_floating(out, jnp.float32)
        return out

    if specs is not None:
        # ragged tail batches (dim 0 not divisible by the data axis)
        # run the un-annotated program — validation/predict sets keep
        # their remainder batches; the routing rule lives in the spec
        # layer so every annotated serving program shares it
        return specs.ragged_dispatch(
            jax.jit(eval_fn, in_shardings=(specs.replicated,
                                           specs.batch_shardings())),
            jax.jit(eval_fn))
    return jax.jit(eval_fn)


# ---------------------------------------------------------------------------
# Validation methods (BigDL ValidationMethod monoid, SURVEY.md §2.7)
# ---------------------------------------------------------------------------


class ValidationResult:
    """Mergeable (monoid) metric accumulator — reference
    ``common/DetectionResult.scala:57`` ``+``-reduce across partitions."""

    def __init__(self, value: float, count: float, name: str):
        self.value = value
        self.count = count
        self.name = name

    def __add__(self, other: "ValidationResult") -> "ValidationResult":
        return ValidationResult(self.value + other.value, self.count + other.count,
                                self.name)

    def result(self) -> float:
        return self.value / max(self.count, 1e-12)

    def __repr__(self):
        return f"{self.name}: {self.result():.6f} ({int(self.count)} samples)"


class ValidationMethod:
    name = "validation"

    def __call__(self, output, batch) -> ValidationResult:  # pragma: no cover
        raise NotImplementedError


class Top1Accuracy(ValidationMethod):
    name = "Top1Accuracy"

    def __call__(self, output, batch):
        target = np.asarray(batch["target"]).reshape(-1)
        pred = np.asarray(jnp.argmax(output, axis=-1)).reshape(-1)
        mask = np.asarray(batch.get("target_mask", np.ones_like(target))).reshape(-1)
        correct = float(np.sum((pred == target) * mask))
        return ValidationResult(correct, float(mask.sum()), self.name)


class Loss(ValidationMethod):
    name = "Loss"

    def __init__(self, criterion):
        self.criterion = criterion

    def __call__(self, output, batch):
        n = np.asarray(batch["target"]).shape[0]
        loss = float(_call_criterion(self.criterion, output, batch))
        return ValidationResult(loss * n, n, self.name)


class MAE(ValidationMethod):
    """Mean absolute error on the argmax class (the recommender notebook's
    validation metric over 5 rating classes)."""

    name = "MAE"

    def __call__(self, output, batch):
        target = np.asarray(batch["target"]).reshape(-1).astype(np.float32)
        pred = np.asarray(jnp.argmax(output, axis=-1)).reshape(-1).astype(np.float32)
        return ValidationResult(float(np.abs(pred - target).sum()), target.size,
                                self.name)


# ---------------------------------------------------------------------------
# The Optimizer (host loop)
# ---------------------------------------------------------------------------


class Optimizer:
    """BigDL-``Optimizer``-shaped trainer over a mesh.

    Usage (mirrors ``ssd/example/Train.scala:219-252``)::

        opt = (Optimizer(model, train_set, criterion, mesh=mesh)
               .set_optim_method(SGD(lr, momentum=0.9, plateau=...))
               .set_validation(Trigger.every_epoch(), val_set, [Top1Accuracy()])
               .set_checkpoint(path, Trigger.every_epoch())
               .set_train_summary(TrainSummary(logdir, app))
               .set_end_when(Trigger.max_epoch(250)))
        trained_model = opt.optimize()
    """

    def __init__(self, model: Model, dataset, criterion, mesh=None,
                 skip_loss_above: Optional[float] = None,
                 grad_clip_norm: Optional[float] = None,
                 compute_dtype=None, device_transform=None,
                 param_rules=None, prefetch: int = 0,
                 grad_accum: int = 1, forward_fn=None,
                 batch_overrides=None, metric_fn=None, specs=None,
                 clock=None):
        from analytics_zoo_tpu.parallel.specs import SpecSet
        from analytics_zoo_tpu.utils.clock import as_now_fn

        # epoch/throughput timing reads the ONE injected clock (utils.
        # clock, az-analyze one-clock rule) — a VirtualClock makes the
        # records/s epoch log deterministic in drills
        self._now = as_now_fn(clock)

        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.compute_dtype = compute_dtype
        # jitted on-device batch rewrite (e.g. the device-augmentation
        # program, transform/vision/device.py) applied after sharding
        self.device_transform = device_transform
        # the declare-once sharding substrate (parallel.specs): EVERY
        # placement this loop performs — state replication/TP sharding,
        # batch feeds, the step's jit in/out shardings — flows through
        # one SpecSet.  `param_rules`/`batch_overrides` remain as sugar
        # that BUILDS the SpecSet, so legacy callers land on the same
        # single path.
        if specs is not None:
            if mesh is not None and mesh is not specs.mesh:
                raise ValueError("pass mesh= OR specs= (the SpecSet "
                                 "carries its mesh), not conflicting both")
            if param_rules is not None or batch_overrides is not None:
                raise ValueError("param_rules/batch_overrides are the "
                                 "legacy sugar for building a SpecSet — "
                                 "declare them inside specs= instead")
            self.specs = specs
        else:
            self.specs = SpecSet(mesh or mesh_lib.create_mesh(),
                                 rules=param_rules,
                                 batch_overrides=batch_overrides)
        self.mesh = self.specs.mesh
        self.optim: OptimMethod = Adam(1e-3)
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset = None
        self.val_methods: Sequence[ValidationMethod] = ()
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.overwrite_checkpoint = False
        self.train_summary = None
        self.val_summary = None
        self.skip_loss_above = skip_loss_above
        self.grad_clip_norm = grad_clip_norm
        # views onto the SpecSet (back-compat attribute surface)
        self.param_rules = self.specs.rules
        # > 0: shard+transfer batches on a background thread, staying
        # `prefetch` ahead of the device (data.prefetch double-buffering,
        # SURVEY.md §3.1 HOT LOOP #1 overlap)
        self.prefetch = prefetch
        # > 1: accumulate gradients over N microbatches inside the step
        self.grad_accum = grad_accum
        # custom forward (make_train_step forward_fn hook), e.g. the
        # sequence-parallel DS2 program
        self.forward_fn = forward_fn
        # in-graph extra step metrics (make_train_step metric_fn hook),
        # e.g. the bucketed DS2 padding_efficiency report
        self.metric_fn = metric_fn
        # per-key PartitionSpec overrides for shard_batch, e.g.
        # {"input": tensor.spatial_input_spec()} for spatial TP
        self.batch_overrides = self.specs.batch_overrides
        if self.batch_overrides and prefetch:
            raise ValueError("batch_overrides is not supported with "
                             "prefetch (the prefetch path shards with "
                             "the default data-axis specs)")
        self._score_name: Optional[str] = None
        self.resume_path: Optional[str] = None
        self._resume_requested = False
        self.failure_detector = None
        self.preemption_handler = None
        self.stall_watchdog = None
        self.checkpoint_keep_last: Optional[int] = None
        self.epoch_hook = None
        self._skip_batches = 0      # mid-epoch resume fast-forward
        self._iter_in_epoch = 0
        # elastic resume: GLOBAL sample offset into the current epoch.
        # Checkpoint meta records it so a restore under a DIFFERENT
        # world size / batch geometry re-seeks the deterministic stream
        # by sample coordinate instead of batch count (the PR-8 resume
        # bug generalized — see docs/PARALLELISM.md "Elastic resize").
        self._samples_in_epoch = 0
        self._skip_samples: Optional[int] = None
        self.anomaly_policy = None
        self._anomaly = None        # AnomalySentinel, built per optimize()
        self.health_policy = None
        self._health = None         # HealthSentinel, built per optimize()
        self._audit_fn = None       # jitted parity audit, built lazily
        self._shadow_fn = None      # jitted shadow forward, built lazily
        self.obs = None             # obs.Observability (set_observability)

    # -- fluent config (reference API names, snake_cased) ------------------
    def set_optim_method(self, m: OptimMethod) -> "Optimizer":
        self.optim = m
        return self

    def set_end_when(self, t: Trigger) -> "Optimizer":
        self.end_when = t
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod],
                       score_name: Optional[str] = None) -> "Optimizer":
        self.val_trigger = trigger
        self.val_dataset = dataset
        self.val_methods = list(methods)
        self._score_name = score_name or (methods[0].name if methods else None)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       overwrite: bool = True,
                       keep_last: Optional[int] = None) -> "Optimizer":
        """``overwrite=True`` keeps one 'latest' snapshot; ``False``
        publishes ``step_N`` snapshots, with ``keep_last=N`` retention GC
        (older snapshots are fallbacks when the newest is corrupt)."""
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.overwrite_checkpoint = overwrite
        self.checkpoint_keep_last = keep_last
        return self

    def set_preemption_handler(self, handler=None) -> "Optimizer":
        """Trap SIGTERM/SIGINT during ``optimize()``: the loop finishes
        the in-flight step, takes a forced checkpoint at the boundary,
        and raises a retryable ``Preempted`` (see docs/RESILIENCE.md)."""
        from analytics_zoo_tpu.resilience.preempt import PreemptionHandler
        self.preemption_handler = handler or PreemptionHandler()
        return self

    def set_stall_watchdog(self, watchdog) -> "Optimizer":
        """Raise ``StallError`` (instead of hanging forever) when the
        loop makes no progress within a deadline.  Pass a
        ``StallWatchdog`` or a float timeout in seconds; the heartbeat is
        per-phase (step / validation / checkpoint save), so size it to
        cover the slowest SINGLE legitimate phase — including the
        first-step XLA compile and the full snapshot write."""
        from analytics_zoo_tpu.resilience.watchdog import StallWatchdog
        if not hasattr(watchdog, "beat"):
            watchdog = StallWatchdog(float(watchdog))
        self.stall_watchdog = watchdog
        return self

    def set_anomaly_policy(self, policy=None) -> "Optimizer":
        """Arm the training anomaly sentinel (``resilience.anomaly``):
        the jitted step folds an in-graph health word over loss / grads /
        updated params, unhealthy updates are discarded in-graph, and
        the host ladder escalates — skip → rollback to the
        last-known-good checkpoint tier (+ deterministic re-seek past
        the bad region) → fatal ``TrainingDiverged`` after
        ``max_rollbacks``.  A forensics bundle (``anomaly_<step>.json``)
        is written on the first bad step of each episode; replay it with
        ``tools/replay_batch.py``.  Rollback needs ``set_checkpoint`` so
        the LKG tier has somewhere to live.  Costs one device→host
        round trip per step (health word + loss fetched together)."""
        from analytics_zoo_tpu.resilience.anomaly import AnomalyPolicy
        self.anomaly_policy = policy or AnomalyPolicy()
        return self

    def set_health_policy(self, policy=None) -> "Optimizer":
        """Arm the device-health sentinel (``resilience.health``): every
        ``audit_every`` steps an in-graph per-replica param fingerprint
        (one shard_map program, no per-step cost) is fetched at the
        decision boundary and compared — data-parallel replicas must be
        bit-identical post-all-reduce, so a divergence proves silent
        data corruption and the minority vote names the device; every
        ``shadow_every`` steps the current microbatch's forward is
        recomputed on a second device and the output fingerprints
        compared (a third device breaks ties when available).  A named
        suspect raises retryable ``DeviceQuarantine`` — pair with
        ``set_anomaly_policy`` + ``set_checkpoint`` so the supervisor
        can rebuild on the surviving devices from the LKG tier
        (``health.evict_device`` + elastic resume); an unattributable
        divergence raises fatal ``SdcDetected``.  Default policy audits
        every 8 steps; all knobs default off on an un-armed Optimizer."""
        from analytics_zoo_tpu.resilience.health import HealthPolicy
        self.health_policy = policy or HealthPolicy(audit_every=8)
        return self

    def set_observability(self, obs=None) -> "Optimizer":
        """Arm the telemetry spine (:class:`analytics_zoo_tpu.obs.
        Observability`): per-step spans at their loader coordinates
        (trace id ``train-e<epoch>-b<batch>``), checkpoint save/restore
        spans, ``train/dispatch/*`` metrics via
        :class:`~analytics_zoo_tpu.utils.profiling.StepTimer`, and
        anomaly-ladder counters — all in the shared registry/flight
        recorder.  On ``TrainingDiverged`` (ladder OR failure detector)
        the recorder dumps its ring (the black box) to ``obs.dump_path``
        when one is configured.

        Timing semantics: the step span and ``train/dispatch/step_s``
        cover the HOST interval of the train-step call — jax dispatch
        is asynchronous, so without a per-step sync this is dispatch
        latency, not device wall time (with the anomaly sentinel armed
        its per-step health fetch makes it ≈wall).  A deliberate
        choice: fencing every step to measure it would serialize the
        pipeline the PR-2 work overlapped.  The input-wait / dispatch /
        fence split of a step needs no arming: it is the always-on
        stages ``az/input/get_wait`` / ``az/train/dispatch`` /
        ``az/train/summary`` (``obs.stages()``, docs/OBSERVABILITY.md).
        ``None`` builds a default bundle."""
        from analytics_zoo_tpu.obs import Observability
        self.obs = obs or Observability()
        return self

    def set_resume(self, path: Optional[str] = None) -> "Optimizer":
        """Resume from the latest checkpoint under ``path`` (defaults to the
        ``set_checkpoint`` path, resolved at ``optimize()`` time so the
        fluent-call order doesn't matter) when one exists — the reference's
        ``--model``/``--state`` snapshot restart (``Train.scala:161-163``)."""
        self.resume_path = path
        self._resume_requested = True
        return self

    def set_epoch_hook(self, fn) -> "Optimizer":
        """``fn(loop, state)`` after each completed epoch (post
        validation/checkpoint) — e.g. an mAP-trajectory probe that runs a
        detector assembly the ``ValidationMethod`` protocol can't express.
        ``state`` params are live device arrays; pass them straight into a
        jitted eval to avoid a host round-trip."""
        self.epoch_hook = fn
        return self

    def set_failure_detector(self, detector) -> "Optimizer":
        """Periodic loss-health check (``parallel.elastic.DivergenceDetector``);
        raises out of ``optimize()``.  Ignored while an anomaly policy is
        armed: the sentinel discards bad updates in-graph, so the
        detector would read discarded steps' NaN losses and raise fatal
        ``TrainingDiverged`` before the ladder could roll back."""
        self.failure_detector = detector
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        self.val_summary = summary
        return self

    # -- loop --------------------------------------------------------------
    def optimize(self) -> Model:
        state = create_train_state(self.model, self.optim)
        loop = TrainingState()
        if self._resume_requested:
            resume_base = self.resume_path or self.checkpoint_path
            if resume_base:
                state, loop = self._try_resume(resume_base, state, loop)
        state = self._place_state(state)
        anomaly_on = self.anomaly_policy is not None
        spike = self.skip_loss_above
        if anomaly_on and self.anomaly_policy.spike_loss_above is not None:
            spike = self.anomaly_policy.spike_loss_above
        # step program's registered name -> the first batch it was
        # dispatched with, as shapes (obs/device_scopes.py)
        batch_shapes: Dict[str, Any] = {}

        def build_step(annotate_batches=True):
            step = make_train_step(
                self.model.module, self.criterion, self.optim,
                specs=self.specs, state=state,
                annotate_batches=annotate_batches,
                skip_loss_above=spike,
                grad_clip_norm=self.grad_clip_norm,
                compute_dtype=self.compute_dtype,
                grad_accum=self.grad_accum,
                device_transform=self.device_transform,
                forward_fn=self.forward_fn,
                health_check=anomaly_on,
                skip_unhealthy=anomaly_on and self.anomaly_policy.skip,
                metric_fn=self.metric_fn,
            )
            # how a traced run finds which instructions of this program
            # stand under which named scope: the step and its arguments
            # as shapes (the state is donated every step: nothing live is
            # held), compiled again only when someone asks for the map
            name = "train/step" if annotate_batches else "train/step_scalar"
            state_shapes = device_scopes.abstract(state)

            def program():
                if name not in batch_shapes:
                    raise LookupError(f"{name} dispatched no step")
                return step, (state_shapes, batch_shapes[name], 1.0)

            device_scopes.register_program(name, program)
            return step

        train_step = build_step()
        # built lazily the first time a batch carries a 0-d leaf: the
        # data-axis batch annotation cannot express "replicate this
        # scalar", so such batches ride an un-annotated-batch variant
        # of the SAME step, pre-placed by specs.place_batch (whose
        # documented contract replicates scalars)
        scalar_step = [None]
        if anomaly_on:
            from analytics_zoo_tpu.resilience.anomaly import (
                AnomalySentinel, health_sections)
            self._anomaly = AnomalySentinel(
                self.anomaly_policy,
                sections=health_sections(
                    mesh_lib.host_local_state(state.params)))
            if (self.anomaly_policy.promote_initial
                    and self.checkpoint_path is not None):
                # seed the last-known-good tier with the (trivially
                # healthy) starting state so a rollback ALWAYS has a
                # target, even before the first clean-streak promotion
                from analytics_zoo_tpu.parallel import checkpoint as ckpt
                if ckpt.lkg_snapshot(self.checkpoint_path) is None:
                    self._promote_lkg(loop, state)
        eval_step = make_eval_step(
            self.model.module, compute_dtype=self.compute_dtype,
            # validation rides the same substrate: replicated variables
            # + data-axis batches via jit in_shardings.  A mesh spanning
            # processes keeps the un-annotated path (host arrays cannot
            # be jit-placed across processes), and tensor-parallel rules
            # keep theirs (a replicated prefix would all-gather the
            # sharded params every call).
            specs=(self.specs
                   if (self.specs.rules is None
                       and not mesh_lib.spans_processes(self.mesh))
                   else None))
        # telemetry spine: the tracer/StepTimer pair is None-checked on
        # the hot path so an un-instrumented loop pays nothing
        obs = self.obs
        tracer = obs.tracer if obs is not None else None
        step_timer = None
        if obs is not None:
            from analytics_zoo_tpu.utils.profiling import StepTimer
            # "dispatch" named honestly: async dispatch returns before
            # the device finishes (see set_observability docstring)
            step_timer = StepTimer("train/dispatch", registry=obs.registry)
        self._health = None
        # the jitted audit/shadow programs close over the mesh and the
        # forward fn — a reused Optimizer may have swapped either (the
        # elastic replace_mesh path), so they rebuild per optimize()
        # alongside the sentinel, never across calls
        self._audit_fn = None
        self._shadow_fn = None
        if (self.health_policy is not None
                and (self.health_policy.audit_every > 0
                     or self.health_policy.shadow_every > 0)):
            from analytics_zoo_tpu.resilience.health import HealthSentinel
            self._health = HealthSentinel(
                self.health_policy,
                registry=obs.registry if obs is not None else None)
        if self.prefetch:
            from analytics_zoo_tpu.data.prefetch import device_prefetch
        # single-process, no per-key overrides: host batches go straight
        # into the annotated jit (its in_shardings do the placement)
        jit_places = self.specs.jit_places_batches()
        batch_annotated = self.specs.batch_shardings() is not None

        def _has_scalar_leaf(b):
            return any(getattr(leaf, "ndim", 0) == 0
                       for leaf in jax.tree_util.tree_leaves(b))

        ph = self.preemption_handler
        wd = self.stall_watchdog
        if ph is not None:
            ph.stall_watchdog = wd   # stall interrupts beat preemption
            ph.install()
        if wd is not None:
            wd.start()
        t_epoch = self._now()
        records = 0
        stop = False
        sentinel = object()
        try:
            while not stop and not self.end_when(loop):
                loop.epoch_finished = False
                host_iter = iter(self.dataset)
                # mid-epoch resume: fast-forward past already-trained batches
                # ON THE HOST — never shard/transfer data that will be
                # dropped.  Elastic resume (meta carried the GLOBAL sample
                # offset): consume by sample count so a stream re-batched
                # under a different world size lands on the same global
                # coordinate; the offset must land on a batch boundary of
                # the NEW stream or the geometries are incompatible.
                while self._skip_samples is not None and self._skip_samples > 0:
                    b = next(host_iter, sentinel)
                    if b is sentinel:
                        break
                    n_skip = _batch_size(b)
                    if n_skip > self._skip_samples:
                        raise ValueError(
                            f"elastic resume: checkpointed sample offset "
                            f"leaves {self._skip_samples} samples to skip "
                            f"but the next batch holds {n_skip} — the "
                            f"offset does not land on a batch boundary of "
                            f"the resumed stream (incompatible global "
                            f"batch geometry)")
                    self._skip_samples -= n_skip
                    self._samples_in_epoch += n_skip
                    self._iter_in_epoch += 1
                self._skip_samples = None
                while self._skip_batches > 0:
                    b = next(host_iter, sentinel)
                    if b is sentinel:
                        break
                    self._skip_batches -= 1
                    self._samples_in_epoch += _batch_size(b)
                    self._iter_in_epoch += 1
                # close_source: the prefetch worker thread closes
                # host_iter itself on cancel/end — a consumer-side close
                # could land while the thread is inside next(host_iter)
                epoch_batches = (device_prefetch(host_iter, self.mesh,
                                                 self.prefetch,
                                                 close_source=True)
                                 if self.prefetch else host_iter)
                epoch_iter = iter(epoch_batches)
                try:
                    for batch in epoch_iter:
                        # prefetch path: already sharded on the worker
                        # thread.  jit fast path: the annotated step's
                        # in_shardings place the HOST batch (one
                        # transfer, no explicit device_put).  Otherwise
                        # (per-key overrides, multi-process mesh) the
                        # spec layer assembles the device batch.  A
                        # batch with a 0-d leaf takes the lazily-built
                        # un-annotated-batch step (the data-axis prefix
                        # is invalid for rank-0; place_batch replicates
                        # scalars, preserving the shard_batch contract).
                        with stage("az/train/prepare"):
                            n = _batch_size(batch)
                            step_fn, step_name = train_step, "train/step"
                            if batch_annotated and _has_scalar_leaf(batch):
                                if scalar_step[0] is None:
                                    scalar_step[0] = build_step(
                                        annotate_batches=False)
                                step_fn = scalar_step[0]
                                step_name = "train/step_scalar"
                                dev_batch = (
                                    batch if self.prefetch
                                    else self.specs.place_batch(batch))
                            else:
                                dev_batch = (
                                    batch if (self.prefetch or jit_places)
                                    else self.specs.place_batch(batch))
                            if step_name not in batch_shapes:
                                # once a step program, at its first step
                                batch_shapes[step_name] = \
                                    device_scopes.abstract(dev_batch)
                        # device_transform is fused INSIDE train_step
                        step_span = None
                        if tracer is not None:
                            # loader coordinates ARE the trace identity:
                            # the same (epoch, batch) replays as the
                            # same trace under the PR-2 determinism
                            # contract
                            step_span = tracer.start(
                                "train_step",
                                f"train-e{loop.epoch}"
                                f"-b{self._iter_in_epoch}",
                                iteration=loop.iteration + 1,
                                epoch=loop.epoch,
                                batch=self._iter_in_epoch)
                        try:
                            with stage("az/train/dispatch"):
                                if step_timer is None:
                                    state, metrics = step_fn(
                                        state, dev_batch,
                                        self.optim.lr_scale)
                                else:
                                    with step_timer.step(n):
                                        state, metrics = step_fn(
                                            state, dev_batch,
                                            self.optim.lr_scale)
                        except BaseException as e:
                            # an exception escaping the step (XLA error,
                            # watchdog interrupt) must still CLOSE the
                            # span — spans reach the flight recorder on
                            # end(), and the crashed step is exactly the
                            # event the black box exists to capture
                            if step_span is not None:
                                step_span.end(
                                    status="error",
                                    error=f"{type(e).__name__}: {e}")
                            raise
                        loop.iteration += 1
                        self._iter_in_epoch += 1
                        self._samples_in_epoch += n
                        records += n
                        # keep the loss as a device array — only force a host
                        # sync when something host-side actually reads it
                        loop.loss = metrics["loss"]
                        if self._anomaly is not None:
                            # skip / rollback / diverge ladder; may
                            # replace `state` (rollback restores the
                            # last-known-good tier), consume re-seek
                            # batches from epoch_iter, and reset
                            # loop.loss/health after a rollback
                            state = self._anomaly_step(
                                loop, state, metrics, dev_batch,
                                epoch_iter, step_span=step_span)
                        elif (self.failure_detector is not None
                                and self.failure_detector.should_check(
                                    loop.iteration)):
                            # detector only when NO sentinel is armed:
                            # the sentinel discards bad updates in-graph,
                            # so feeding the detector a discarded step's
                            # NaN loss would raise fatal TrainingDiverged
                            # before the ladder could roll back
                            try:
                                self.failure_detector.check(
                                    float(metrics["loss"]), loop.iteration)
                            except Exception as e:
                                # same black-box contract as the ladder
                                # path: a diverged run dumps the ring
                                # before propagating
                                if (step_span is not None
                                        and not step_span.ended):
                                    step_span.end(
                                        status="error",
                                        error=f"{type(e).__name__}: {e}")
                                if obs is not None:
                                    obs.recorder.note(
                                        "training_diverged",
                                        iteration=loop.iteration)
                                    obs.dump("training_diverged")
                                raise
                        if self._health is not None:
                            # parity audit / shadow recompute at their
                            # cadences; a confirmed bad device raises
                            # DeviceQuarantine (retryable — supervisor
                            # rebuilds on survivors), unattributable
                            # corruption raises fatal SdcDetected
                            try:
                                self._health_step(loop, state, dev_batch)
                            except Exception as e:
                                if (step_span is not None
                                        and not step_span.ended):
                                    step_span.end(
                                        status="error",
                                        error=f"{type(e).__name__}: {e}")
                                if obs is not None:
                                    obs.recorder.note(
                                        "device_health",
                                        iteration=loop.iteration)
                                    obs.dump("device_health")
                                raise
                        if step_span is not None and not step_span.ended:
                            step_span.end(status="ok")
                        if self.train_summary is not None:
                            # device arrays on purpose: add_scalar floats them
                            # only when the tag's trigger fires (the loss's
                            # float() is then the step's fence)
                            with stage("az/train/summary"):
                                self.train_summary.add_scalar(
                                    "Loss", metrics["loss"], loop.iteration)
                                self.train_summary.add_scalar(
                                    "LearningRate", metrics["lr"],
                                    loop.iteration)
                        with stage("az/train/boundary"):
                            self._boundary_checks(loop, state, eval_step,
                                                  wd, ph)
                            stop = bool(self.end_when(loop))
                        if stop:
                            break
                finally:
                    # early exit (end_when break / detector raise): release
                    # the prefetch worker and its HBM-pinned queued batches;
                    # close_source above hands host_iter (possibly a
                    # multiprocess loader epoch owning worker processes)
                    # to the prefetch thread for closing
                    if hasattr(epoch_batches, "close"):
                        epoch_batches.close()
                if stop:
                    break  # partial epoch: don't count or re-trigger it
                loop.epoch += 1
                loop.epoch_finished = True
                self._iter_in_epoch = 0
                self._samples_in_epoch = 0
                loop.loss = float(loop.loss)
                dt = self._now() - t_epoch
                logger.info("Epoch %d done: %d records in %.1fs (%.1f records/s), loss %.4f",
                            loop.epoch, records, dt, records / max(dt, 1e-9), loop.loss)
                t_epoch, records = self._now(), 0
                self._boundary_checks(loop, state, eval_step, wd, ph)
                if self.epoch_hook is not None:
                    self.epoch_hook(loop, state)
        except KeyboardInterrupt:
            # the stall watchdog signals via a main-thread interrupt; a
            # REAL Ctrl-C (watchdog quiet) keeps its usual meaning
            self._raise_if_stalled(wd, loop)
            raise
        finally:
            if wd is not None:
                wd.stop()
            if ph is not None:
                ph.uninstall()
            if hasattr(self.dataset, "close"):
                self.dataset.close()    # a kept loader pool ends here
        # write trained variables back into the model wrapper (local-
        # replica read: safe on a mesh spanning processes)
        host_state = mesh_lib.host_local_state(state)
        self.model.variables = state_to_variables(host_state)
        self._last_state = host_state
        return self.model

    # -- helpers -----------------------------------------------------------
    def _maybe_validate(self, loop: TrainingState, state: TrainState, eval_step):
        if self.val_trigger is None or not self.val_trigger(loop):
            return
        # iteration-based triggers stay true at the epoch boundary; don't
        # re-validate (and double-count toward Plateau patience) at the same
        # iteration the in-loop pass already handled
        if getattr(self, "_last_val_iter", None) == loop.iteration:
            return
        self._last_val_iter = loop.iteration
        variables = state_to_variables(state)
        results = validate(self.model.module, variables, self.val_dataset,
                           self.val_methods, eval_step=eval_step)
        metrics = {r.name: r.result() for r in results}
        for name, value in metrics.items():
            logger.info("Validation @ iter %d: %s = %.5f", loop.iteration, name, value)
            if self.val_summary is not None:
                self.val_summary.add_scalar(name, value, loop.iteration)
        if self._score_name and self._score_name in metrics:
            loop.score = metrics[self._score_name]
            self.optim.on_validation({"score": loop.score, **metrics})

    def _boundary_checks(self, loop: TrainingState, state: TrainState,
                         eval_step, wd, ph) -> None:
        """Everything that runs at a step/epoch boundary, in order:
        validation, checkpoint, stall classification, preemption.  Kept
        in ONE place so step and epoch boundaries cannot drift apart.

        Per-phase heartbeats: the step, the validation pass, and the
        (sha256-hashed) checkpoint save each get their own deadline
        window — size the watchdog for the slowest SINGLE phase.  Stall
        beats preempt: the watchdog's interrupt may have been absorbed
        by the signal handler as a preempt request, so it must be
        re-classified before the preemption check."""
        if wd is not None:
            wd.beat()
        self._maybe_validate(loop, state, eval_step)
        if wd is not None:
            wd.beat()
        self._maybe_checkpoint(loop, state)
        self._raise_if_stalled(wd, loop)
        if wd is not None:
            wd.beat()
        if ph is not None and self._preempt_agreed(ph, loop):
            self._graceful_preempt(loop, state)

    def _raise_if_stalled(self, wd, loop: TrainingState) -> None:
        if wd is None or not wd.stalled:
            return
        from analytics_zoo_tpu.resilience.errors import StallError

        # absorb the watchdog's simulated SIGINT if it is still pending
        # (the monitor sets `stalled` a moment before interrupt_main; a
        # boundary check landing in that window would otherwise leave a
        # stray KeyboardInterrupt to pop in unrelated code later)
        try:
            time.sleep(0.2)
        except KeyboardInterrupt:
            pass
        raise StallError(
            f"no training progress past the {wd.timeout_s:.1f}s stall "
            f"deadline at iteration {loop.iteration}")

    #: multi-host boundaries between preemption-agreement collectives —
    #: bounds the graceful-response latency to this many steps while
    #: keeping the per-step hot path free of cross-host syncs
    preempt_sync_every: int = 16

    def _preempt_agreed(self, ph, loop: TrainingState) -> bool:
        """Whether to act on a preemption request at this boundary.
        Multi-host: the request flags are OR-reduced across hosts — a
        signal landing on ANY process (single-pod eviction, per-host
        OOM-kill) makes EVERY process enter the forced final checkpoint,
        which is a COLLECTIVE save, at the same step boundary.  The
        agreement gather is itself a cross-host sync, so it runs only
        every ``preempt_sync_every`` iterations (a replicated,
        deterministic schedule), not on every step."""
        if jax.process_count() == 1:
            return ph.requested
        if loop.iteration % max(self.preempt_sync_every, 1):
            return False  # pragma: no cover - multi-host only
        from jax.experimental import multihost_utils  # pragma: no cover

        flags = multihost_utils.process_allgather(
            np.asarray([ph.requested]))  # pragma: no cover
        return bool(np.any(flags))  # pragma: no cover

    def _graceful_preempt(self, loop: TrainingState, state: TrainState):
        """Step-boundary response to SIGTERM/SIGINT: force a final
        checkpoint, then raise the retryable ``Preempted`` so a
        supervisor (or the job's next incarnation) resumes from it.
        Preemption is a terminal condition for THIS incarnation, so the
        flight recorder dumps its ring alongside the boundary
        checkpoint — the preemption drill carries a black box of the
        steps leading into the signal, same as a divergence does."""
        from analytics_zoo_tpu.resilience.errors import Preempted

        saved = False
        if self.checkpoint_path is not None:
            saved = bool(self._maybe_checkpoint(loop, state, force=True))
        if self.obs is not None:
            self.obs.recorder.note(
                "preempted", iteration=loop.iteration, epoch=loop.epoch,
                checkpoint_saved=saved)
            if self.obs.dump_path:
                self.obs.dump("preempted")
        raise Preempted(
            f"preemption signal received at iteration {loop.iteration}; "
            + ("final checkpoint written"
               if saved else
               "NO final checkpoint written (no path configured, already "
               "saved this iteration, or loss non-finite) — resume falls "
               "back to the previous snapshot"))

    def _place_state(self, state: TrainState) -> TrainState:
        """Host/state pytree → mesh placement through the declared
        SpecSet (tensor-parallel rules when declared, else full
        replication).  The ONE placement decision, shared by the initial
        `optimize()` setup and the anomaly rollback restore so they can
        never drift."""
        return self.specs.place_state(state)

    # -- anomaly sentinel (resilience.anomaly ladder) ----------------------
    def _anomaly_step(self, loop: TrainingState, state: TrainState,
                      metrics, dev_batch, epoch_iter,
                      step_span=None) -> TrainState:
        """Per-step ladder: feed the health word to the sentinel, write
        forensics on an episode's first bad step, roll back / escalate.
        Returns the (possibly restored) state.  ``step_span`` (telemetry
        spine): closed here with the ladder's verdict so the flight
        recorder names unhealthy steps; ladder actions also count into
        the shared registry, and a diverged run dumps the black box
        before raising."""
        from analytics_zoo_tpu.resilience import anomaly as anomaly_lib
        from analytics_zoo_tpu.resilience.errors import TrainingDiverged

        sent = self._anomaly
        obs = self.obs
        # ONE device->host round trip for both scalars (the sentinel's
        # documented per-step host cost)
        word, loss_host = jax.device_get((metrics["health"],
                                          metrics["loss"]))
        word = int(word)
        loop.health = word
        sent.record_loss(float(loss_host))
        action, first = sent.observe(word)
        if step_span is not None:
            step_span.end(status="ok" if word == 0 else "unhealthy",
                          **({} if word == 0
                             else {"health_word": word, "action": action}))
        if obs is not None and word:
            obs.registry.counter("train/anomaly/bad_steps").inc()
        if word:
            sent.note_skip(word, step=loop.iteration)
            logger.warning(
                "anomaly sentinel: unhealthy step at iteration %d "
                "(word %#x, %d consecutive): %s", loop.iteration, word,
                sent.consecutive_bad,
                anomaly_lib.decode_health(word, sent.sections))
        if first:
            self._write_forensics(sent, word, loop, state, dev_batch)
        if action == "rollback":
            if obs is not None:
                obs.registry.counter("train/anomaly/rollbacks").inc()
            state = self._anomaly_rollback(loop, state)
            self._reseek(epoch_iter, sent.policy.reseek)
        elif action == "diverged":
            if obs is not None:
                # terminal condition: the ring becomes the black box
                obs.recorder.note(
                    "training_diverged", iteration=loop.iteration,
                    health_word=word,
                    rollbacks=sent.rollbacks,
                    consecutive_bad=sent.consecutive_bad)
                obs.dump("training_diverged")
            raise TrainingDiverged(
                f"anomaly ladder exhausted at iteration {loop.iteration}: "
                f"{sent.consecutive_bad} consecutive unhealthy steps with "
                f"the rollback budget spent ({sent.rollbacks}/"
                f"{sent.policy.max_rollbacks}); last health "
                f"{anomaly_lib.decode_health(word, sent.sections)}; "
                f"forensics bundles: {sent.forensics_paths or 'none'}")
        elif (action == "ok" and sent.should_promote()
                and self.checkpoint_path is not None):
            self._promote_lkg(loop, state)
        if word and action != "diverged" and sent.policy.skip:
            # with in-graph skip armed the LIVE state after a bad step is
            # provably clean (the update was discarded; a rollback just
            # restored the promoted LKG tier) — clear the word and swap
            # the discarded step's (usually non-finite) loss for the last
            # finite reading, so the checkpoint guards don't refuse to
            # persist a clean state (e.g. a preemption-forced snapshot
            # landing inside a bad-data window).  Without skip the
            # update DID apply, so the guards must keep refusing.
            loop.health = 0
            finite = [v for v in sent.loss_history if np.isfinite(v)]
            if finite:
                loop.loss = finite[-1]
        return state

    # -- device-health sentinel (resilience.health) ------------------------
    def _health_step(self, loop: TrainingState, state: TrainState,
                     dev_batch) -> None:
        """Run the armed detectors at their cadences.  The audit is one
        pre-built jitted program fetched with a single ``jax.device_get``
        at the decision boundary (the ``_anomaly_step`` host-cost
        contract) — steps between audits pay nothing.  Raises
        ``DeviceQuarantine`` (named suspect, eviction budget permitting)
        or ``SdcDetected`` (proven but unattributable corruption)."""
        from analytics_zoo_tpu.resilience import health as health_lib
        from analytics_zoo_tpu.resilience.errors import (DeviceQuarantine,
                                                         SdcDetected)

        pol = self.health_policy
        sent = self._health
        step = loop.iteration
        flip = health_lib.active_bit_flip() or (-1, 0, 0)
        if pol.audit_every > 0 and step % pol.audit_every == 0:
            if self._audit_fn is None:
                self._audit_fn = health_lib.make_audit_fn(self.mesh)
            target, element, bit = flip
            fps = jax.device_get(self._audit_fn(
                state.params, jnp.int32(target), jnp.int32(element),
                jnp.int32(bit)))
            verdict = sent.observe_audit(step, [int(v) for v in fps])
            self._health_verdict(loop, verdict, "parity audit",
                                 DeviceQuarantine, SdcDetected)
        if pol.shadow_every > 0 and step % pol.shadow_every == 0:
            devices = list(self.mesh.devices.flat)
            if len(devices) >= 2:
                verdict = self._shadow_check(step, state, dev_batch,
                                             devices, flip)
                self._health_verdict(loop, verdict, "shadow recompute",
                                     DeviceQuarantine, SdcDetected)

    def _health_verdict(self, loop, verdict, what, quarantine_cls,
                        sdc_cls) -> None:
        if verdict.ok:
            return
        pol, sent = self.health_policy, self._health
        if verdict.ambiguous:
            raise sdc_cls(
                f"{what} diverged at iteration {loop.iteration} with no "
                f"attributable minority device (fingerprints "
                f"{list(verdict.fingerprints)}); corruption is proven "
                f"but eviction has no target — triage the hardware")
        if pol.evict and sent.eviction_budget_left:
            sent.note_quarantine(verdict.suspect, what.replace(" ", "_"))
            raise quarantine_cls(
                f"{what} named device {verdict.suspect} as corrupt at "
                f"iteration {loop.iteration} (fingerprints "
                f"{list(verdict.fingerprints)}); quarantining — rebuild "
                f"on the surviving devices and resume from the LKG tier",
                device=verdict.suspect)
        logger.error("health: %s named device %s at iteration %d but "
                     "eviction is %s — continuing (detect-only)", what,
                     verdict.suspect, loop.iteration,
                     "off" if not pol.evict else "budget-exhausted")

    def _shadow_check(self, step: int, state: TrainState, dev_batch,
                      devices, flip):
        """Re-execute the current microbatch's forward on the shadow
        device and fingerprint-compare against the primary (a third
        device votes on a mismatch when the mesh has one).  Host-side by
        design: the spot-check must NOT share the primary's compiled
        program or placed arrays — a corrupt device's results re-read
        from HBM would just agree with themselves."""
        from analytics_zoo_tpu.resilience import health as health_lib

        pol, sent = self.health_policy, self._health
        if self._shadow_fn is None:
            self._shadow_fn = health_lib.make_shadow_fn(
                self.model.module, forward_fn=self.forward_fn)
        variables = state_to_variables(mesh_lib.host_local_state(state))
        host_batch = jax.device_get(dev_batch)
        target, element, bit = flip
        shadow_i = min(pol.shadow_device, len(devices) - 1)

        def fp_on(i):
            with jax.default_device(devices[i]):
                return int(jax.device_get(self._shadow_fn(
                    variables, host_batch, jnp.int32(element),
                    jnp.int32(bit), jnp.bool_(target == i))))

        fp_primary, fp_shadow = fp_on(0), fp_on(shadow_i)
        tiebreak = None
        if fp_primary != fp_shadow:
            third = next((j for j in range(len(devices))
                          if j not in (0, shadow_i)), None)
            if third is not None:
                tiebreak = fp_on(third)
        return sent.observe_shadow(step, fp_primary, fp_shadow,
                                   device=shadow_i, tiebreak_fp=tiebreak)

    def _anomaly_rollback(self, loop: TrainingState,
                          state: TrainState) -> TrainState:
        """Restore the last-known-good tier (falling back to the newest
        intact regular snapshot — those are health-guarded too) and
        re-replicate it over the mesh."""
        from analytics_zoo_tpu.parallel import checkpoint as ckpt
        from analytics_zoo_tpu.resilience.errors import TrainingDiverged

        sent = self._anomaly
        found, tier = None, "lkg"
        if self.checkpoint_path is not None:
            found = ckpt.lkg_snapshot(self.checkpoint_path)
            if found is None:
                found, tier = ckpt.newest_intact(self.checkpoint_path), \
                    "regular"
        if found is None:
            raise TrainingDiverged(
                f"anomaly rollback requested at iteration {loop.iteration} "
                "but no last-known-good (or intact regular) snapshot "
                "exists — configure set_checkpoint so the ladder has a "
                "rollback target")
        snap_dir, man = found
        host_target = mesh_lib.host_local_state(state)
        restored = ckpt.load(snap_dir, target=host_target, verify=False)
        new_state = self._place_state(restored)
        # bit-identity proof: the live post-replication params equal the
        # snapshot's bytes (the chaos drill banks this check)
        live = mesh_lib.host_local_state(new_state)
        match = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(live.params),
                            jax.tree_util.tree_leaves(restored.params)))
        self.optim.load_state_dict(
            (man.get("meta", {}) or {}).get("optim", {}) or {})
        sent.note_rollback(
            iteration=loop.iteration, tier=tier,
            snapshot=os.path.basename(snap_dir),
            restored_step=int(np.asarray(restored.step)),
            params_match_snapshot=bool(match),
            reseek_batches=sent.policy.reseek)
        logger.warning(
            "anomaly sentinel: rollback %d/%d at iteration %d -> %s "
            "(restored step %d, params bit-identical to snapshot: %s)",
            sent.rollbacks, sent.policy.max_rollbacks, loop.iteration,
            snap_dir, int(np.asarray(restored.step)), match)
        return new_state

    def _reseek(self, epoch_iter, n: int) -> None:
        """Advance the deterministic stream past the bad region: drop the
        next ``n`` batches on the host (they count as consumed for the
        mid-epoch-resume position, but train no step)."""
        done = object()
        skipped = 0
        for _ in range(max(n, 0)):
            b = next(epoch_iter, done)
            if b is done:
                break
            skipped += 1
            self._iter_in_epoch += 1
            self._samples_in_epoch += _batch_size(b)
        if skipped:
            logger.warning("anomaly sentinel: re-sought stream past %d "
                           "batch(es) after rollback", skipped)

    def _write_forensics(self, sent, word: int, loop: TrainingState,
                         state: TrainState, dev_batch) -> None:
        from analytics_zoo_tpu.resilience import anomaly as anomaly_lib

        directory = (sent.policy.forensics_dir or self.checkpoint_path
                     or os.getcwd())
        batch_in_epoch = self._iter_in_epoch - 1
        num_workers = getattr(self.dataset, "num_workers", None)
        group_size = getattr(self.dataset, "group_size", None)
        # worker shards owning the groups this batch spans (a batch is
        # assembled in the parent from one or MORE groups; assumes no
        # upstream sample drops shifted the mapping).  Replay itself
        # needs only (base_seed, epoch, batch index).
        worker_shards = None
        if num_workers and group_size:
            B = _batch_size(dev_batch)
            first = (batch_in_epoch * B) // group_size
            last = ((batch_in_epoch + 1) * B - 1) // group_size
            worker_shards = sorted({g % num_workers
                                    for g in range(first, last + 1)})
        payload = {
            "bundle": "anomaly_forensics",
            "format": 1,
            "step": int(np.asarray(mesh_lib.host_local_state(state.step))),
            "iteration": loop.iteration,
            "epoch": loop.epoch,
            "batch_in_epoch": batch_in_epoch,
            "health_word": int(word),
            "health": anomaly_lib.decode_health(word, sent.sections),
            "sections": sent.sections,
            "batch_hash": anomaly_lib.batch_fingerprint(dev_batch),
            # strict-JSON loss history: non-finite floats become strings
            "loss_history": [v if np.isfinite(v) else repr(v)
                             for v in sent.loss_history],
            # the PR-2 determinism coordinates replay_batch.py consumes
            "rng": {
                "base_seed": getattr(self.dataset, "base_seed", None),
                "loader_epoch": getattr(self.dataset, "last_epoch", None),
                "num_workers": num_workers,
                "worker_shards": worker_shards,
            },
        }
        sent.write_forensics(directory, payload)

    def _promote_lkg(self, loop: TrainingState, state: TrainState) -> None:
        from analytics_zoo_tpu.parallel import checkpoint as ckpt

        target = ckpt.save(
            self.checkpoint_path, state, tier="lkg",
            meta={"epoch": loop.epoch, "iteration": loop.iteration,
                  "iter_in_epoch": self._iter_in_epoch,
                  "samples_in_epoch": self._samples_in_epoch,
                  "world_width": self.specs.data_axis_size,
                  "health_word": 0,
                  "optim": self.optim.state_dict()})
        self._anomaly.note_promoted(step=loop.iteration,
                                    snapshot=os.path.basename(target))
        logger.info("anomaly sentinel: promoted last-known-good snapshot "
                    "at iteration %d", loop.iteration)

    def _maybe_checkpoint(self, loop: TrainingState, state: TrainState,
                          force: bool = False) -> bool:
        """Returns True when this iteration's state is persisted (saved
        now, or already saved at this very iteration)."""
        if not force and (self.checkpoint_trigger is None
                          or not self.checkpoint_trigger(loop)):
            return False
        if getattr(self, "_last_ckpt_iter", None) == loop.iteration:
            return True
        # never snapshot a poisoned state: the anomaly health word covers
        # non-finite GRADS/PARAMS even when this step's scalar loss is
        # finite; the loss check alone remains the guard for runs without
        # an anomaly policy (loop.health then stays 0)
        loss_now = float(loop.loss)
        health_now = int(getattr(loop, "health", 0) or 0)
        if health_now or not np.isfinite(loss_now):
            logger.warning("skipping checkpoint at iteration %d: "
                           "health word %#x, loss %s", loop.iteration,
                           health_now, loss_now)
            return False
        # memoized only on an ACTUAL save: a skipped save must not make a
        # later forced call at this iteration report "already persisted"
        self._last_ckpt_iter = loop.iteration
        from analytics_zoo_tpu.parallel import checkpoint as ckpt
        tag = None if self.overwrite_checkpoint else loop.iteration
        # multi-host: EVERY process calls save (orbax has internal
        # cross-process barriers and elects the writer itself); the
        # trigger decision above is deterministic and replicated, so all
        # processes reach this point together.  Loop position + host-side
        # optim state (Plateau's learned LR scale) ride in the snapshot's
        # own manifest, so a restore can never pair params with metadata
        # from a DIFFERENT snapshot.
        import contextlib
        # with obs armed the save is both a span (trace
        # ckpt-i<iteration>) and a checkpoint/save_s histogram entry
        t0 = time.perf_counter()
        span = (self.obs.tracer.span(
                    "checkpoint_save", f"ckpt-i{loop.iteration}",
                    iteration=loop.iteration,
                    tag="latest" if tag is None else f"step_{tag}")
                if self.obs is not None else contextlib.nullcontext())
        with span:
            ckpt.save(self.checkpoint_path, state, step=tag,
                      keep_last=self.checkpoint_keep_last,
                      meta={"epoch": loop.epoch, "iteration": loop.iteration,
                            "iter_in_epoch": self._iter_in_epoch,
                            "samples_in_epoch": self._samples_in_epoch,
                            "world_width": self.specs.data_axis_size,
                            "optim": self.optim.state_dict()})
        if self.obs is not None:
            self.obs.registry.histogram("checkpoint/save_s").observe(
                time.perf_counter() - t0)
        return True

    def _apply_resume_meta(self, meta, loop: TrainingState, state) -> None:
        loop.epoch = int(meta.get("epoch", 0))
        loop.iteration = int(meta.get("iteration", int(state.step)))
        if meta.get("samples_in_epoch") is not None:
            # sample-coordinate resume (elastic-capable): the skip loop
            # consumes batches until the GLOBAL sample offset is reached,
            # valid under any world size whose stream re-batches the same
            # merged sample sequence.  Same-geometry resumes consume
            # exactly iter_in_epoch batches — bit-identical to the
            # legacy batch-count path.
            self._skip_samples = int(meta["samples_in_epoch"])
            self._skip_batches = 0
        else:
            self._skip_batches = int(meta.get("iter_in_epoch", 0))
        saved_width = meta.get("world_width")
        if (saved_width is not None
                and int(saved_width) != self.specs.data_axis_size):
            logger.info(
                "elastic resume: checkpoint saved at world width %d, "
                "re-placing at width %d (sample offset %s)",
                int(saved_width), self.specs.data_axis_size,
                meta.get("samples_in_epoch"))
            if self.obs is not None:
                self.obs.registry.counter("elastic/restores").inc()
                self.obs.registry.gauge("elastic/world_width").set(
                    float(self.specs.data_axis_size))
        self.optim.load_state_dict(meta.get("optim", {}) or {})

    def _try_resume(self, base: str, state: TrainState, loop: TrainingState):
        """Restore (state, loop, host optim state) from the newest INTACT
        checkpoint under ``base`` if one exists; otherwise return the
        fresh pair unchanged.  A corrupt/truncated newest snapshot falls
        back to the next older intact one — loop metadata comes from the
        restored snapshot's own manifest, so position and params always
        match."""
        import json

        from analytics_zoo_tpu.parallel import checkpoint as ckpt
        base = os.path.abspath(base)
        if not ckpt.has_checkpoint(base):
            return state, loop
        found = ckpt.newest_intact(base)
        if found is not None:
            snap_dir, manifest = found
            # newest_intact already checksummed this exact dir — do not
            # pay a second full read+sha256 pass on the restart hot path
            import contextlib
            t0 = time.perf_counter()
            span = (self.obs.tracer.span(
                        "checkpoint_restore", "ckpt-restore",
                        snapshot=os.path.basename(snap_dir))
                    if self.obs is not None else contextlib.nullcontext())
            with span:
                state = ckpt.load(snap_dir, target=state, verify=False)
            if self.obs is not None:
                self.obs.registry.histogram("checkpoint/restore_s").observe(
                    time.perf_counter() - t0)
            self._apply_resume_meta(manifest.get("meta", {}), loop, state)
        else:
            # legacy layout (pre-manifest snapshots): best-effort restore
            # with the loop_meta.json sidecar older builds wrote
            state = ckpt.load(base, target=state)
            meta_path = os.path.join(base, "loop_meta.json")
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    self._apply_resume_meta(json.load(f), loop, state)
            else:
                loop.iteration = int(state.step)
        if self._skip_samples is not None:
            logger.info("resumed from %s at epoch %d, iteration %d "
                        "(re-seeking %d in-epoch samples)",
                        base, loop.epoch, loop.iteration, self._skip_samples)
        else:
            logger.info("resumed from %s at epoch %d, iteration %d "
                        "(skipping %d in-epoch batches)",
                        base, loop.epoch, loop.iteration, self._skip_batches)
        return state, loop


def _batch_size(batch) -> int:
    leaf = jax.tree_util.tree_leaves(batch)[0]
    # .shape directly: np.asarray on a device-resident (prefetched) leaf
    # would device_get the whole array just to read its shape
    shape = getattr(leaf, "shape", None)
    return int(shape[0]) if shape else int(np.asarray(leaf).shape[0])


def sparse_adam_apply(table, mu, nu, count, grad, learning_rate,
                      b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """Row-sparse Adam: update ONLY the rows a batch touched, and only
    their optimizer slots — the embedding-table apply that scales past
    one chip (a full-table apply moves ``vocab × dim`` for every step no
    matter how few rows the batch referenced).

    ``grad`` is an ``ops.embedding.SparseRows`` (the (ids, segment-summed
    rows) gradient the dedup'd lookup backward produces);
    ``mu``/``nu``/``count`` are the table's Adam slots.  The math runs
    the SAME optax transforms as the full-table path
    (``scale_by_adam`` → ``scale(-lr)`` → ``p + u``) on the gathered
    rows, so touched rows bit-match a dense ``optax.adam`` apply —
    ``tests/test_embedding.py`` pins this.  Untouched rows keep stale
    moments (lazy Adam): their ``mu``/``nu`` do not decay until the next
    time they are touched, the standard sparse-trainer tradeoff.

    Padded tail entries of ``grad.ids`` are redirected OUT OF BOUNDS:
    jax gathers clamp (harmless garbage rows in dead slots) and jax
    scatters DROP out-of-bounds updates, so padding never corrupts row
    0 and valid unique ids make every scatter-set deterministic.

    Returns ``(table, mu, nu, count)`` updated."""
    vocab = table.shape[0]
    n = grad.ids.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < grad.count
    safe_ids = jnp.where(valid, grad.ids, vocab)
    t_rows, mu_rows, nu_rows = table[safe_ids], mu[safe_ids], nu[safe_ids]
    adam = optax.scale_by_adam(b1=b1, b2=b2, eps=eps)
    row_state = optax.ScaleByAdamState(count=count, mu=mu_rows, nu=nu_rows)
    upd, new_state = adam.update(grad.rows, row_state, t_rows)
    # mirror optax.scale_by_learning_rate + apply_updates op-for-op so
    # the arithmetic is bit-identical to the dense chain
    step_size = -1 * jnp.asarray(learning_rate, dtype=jnp.float32)
    new_rows = (t_rows + step_size * upd).astype(table.dtype)
    return (table.at[safe_ids].set(new_rows),
            mu.at[safe_ids].set(new_state.mu.astype(mu.dtype)),
            nu.at[safe_ids].set(new_state.nu.astype(nu.dtype)),
            new_state.count)


def validate(module, variables, dataset, methods: Sequence[ValidationMethod],
             eval_step=None) -> List[ValidationResult]:
    """Forward a dataset and monoid-reduce validation results (reference
    ``Validator.test``, ``ssd/Validator.scala:59-86``)."""
    eval_step = eval_step or make_eval_step(module)
    totals: List[Optional[ValidationResult]] = [None] * len(methods)
    for batch in dataset:
        out = eval_step(variables, batch["input"])
        for i, m in enumerate(methods):
            r = m(out, batch)
            totals[i] = r if totals[i] is None else totals[i] + r
    return [t for t in totals if t is not None]
