"""Declare-once PartitionSpecs: the single sharding substrate.

The reference distributes one way — synchronous data-parallel replicas
over Spark executors (``DistriOptimizer``, SURVEY.md §2.7) — and every
entry point re-implements that placement.  Before this module our TPU
port had started to mirror the same drift: ``parallel/mesh.py`` placed
data-parallel batches, ``parallel/tensor.py`` placed tensor-parallel
weights, and each pipeline picked its own combination inline.  Here the
GSPMD/pjit pattern (SNIPPETS.md [1]–[3]) is made the ONE convention:

* a pipeline declares its PartitionSpec tree **exactly once** — a
  :class:`SpecSet` built from the registry below — and everything that
  places arrays (``make_train_step``/``make_eval_step`` jit
  ``in_shardings``/``out_shardings``, ``Optimizer._place_state``, the
  serving predictors) consumes that object;
* data/tensor/pipeline parallelism then compose by changing the MESH
  SHAPE, not the pipeline: the same declared specs resolve against a
  ``(8,)`` data mesh, a ``(2, 4)`` data×model mesh, or a multi-host
  mesh, with non-divisible dims degrading to replicated
  (``tensor.partition_spec``).

Axis conventions (``parallel.mesh``): ``data`` carries dim 0 of every
batch leaf; ``model`` carries weight shards (Megatron rules) or image
height (spatial partitioning); ``sequence`` carries time.  Parameters
without a matching rule are replicated — sharding is an optimization,
never a correctness requirement.

Registry::

    specs = pipeline_specs("ds2", mesh=mesh)          # declared once
    state = specs.place_state(create_train_state(model, optim))
    step = make_train_step(model.module, crit, optim, specs=specs)
    ...                                # jit places host batches itself

``tests/test_specs.py`` pins the contract: every registered pipeline's
spec tree structure-matches its param tree, and a shard→gather
roundtrip is byte-identical.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.resilience.errors import ElasticPlacementError


def _spec_axes(spec) -> set:
    """Mesh-axis names one PartitionSpec (or axis sequence) references."""
    axes = set()
    for part in spec:
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            axes.add(ax)
    return axes


@dataclasses.dataclass(frozen=True)
class SpecSet:
    """One pipeline's declared sharding: mesh + state rules + batch specs.

    ``rules``: ``parallel.tensor`` ``(path_regex, spec_fn)`` pairs
    resolving parameter/optimizer-slot leaves (``None`` = everything
    replicated — pure data parallelism).  ``batch_overrides``: top-level
    batch keys whose leaves take an explicit PartitionSpec instead of the
    default dim-0-over-``data`` (e.g. spatial tensor parallelism's
    ``{"input": tensor.spatial_input_spec()}``).

    The object is both the *declaration* (spec trees, for tests and
    docs) and the *placement engine* (``place_state``/``place_batch``/
    jit sharding annotations) — one source of truth, so a refactor
    cannot change where arrays land without changing what the pipeline
    declared.
    """

    mesh: Mesh
    rules: Optional[Sequence] = None
    batch_overrides: Optional[Dict[str, P]] = None

    # -- spec trees (the declaration) -----------------------------------
    def state_specs(self, state: Any) -> Any:
        """PartitionSpec tree structure-matching ``state`` (a params dict
        or a whole TrainState; optimizer slots mirror their parameter's
        spec through path matching)."""
        from analytics_zoo_tpu.parallel import tensor as tensor_lib

        if self.rules is None:
            return jax.tree_util.tree_map(lambda _: P(), state)
        return tensor_lib.spec_tree(state, self.mesh, self.rules)

    def batch_specs(self, batch: Any) -> Any:
        """PartitionSpec tree for one batch pytree: dim 0 over ``data``,
        scalars replicated, ``batch_overrides`` honored per top-level
        key."""
        axis = mesh_lib.data_axis(self.mesh)

        def default(leaf):
            arr = np.asarray(leaf) if not hasattr(leaf, "ndim") else leaf
            if arr.ndim == 0:
                return P()
            return P(*([axis] + [None] * (arr.ndim - 1)))

        if not (self.batch_overrides and isinstance(batch, dict)):
            return jax.tree_util.tree_map(default, batch)
        return {k: (jax.tree_util.tree_map(
                        lambda leaf, k=k: self.batch_overrides[k], v)
                    if k in self.batch_overrides
                    else jax.tree_util.tree_map(default, v))
                for k, v in batch.items()}

    # -- jit annotations ------------------------------------------------
    @property
    def replicated(self) -> NamedSharding:
        """Replicated NamedSharding — scalars (lr, metrics) and, as a
        pytree prefix, whole replicated trees (variables, DP state)."""
        return NamedSharding(self.mesh, P())

    @property
    def data_axis_size(self) -> int:
        """Width of the batch-carrying mesh axis (replica count)."""
        return int(self.mesh.shape[mesh_lib.data_axis(self.mesh)])

    @property
    def data_sharding(self) -> NamedSharding:
        """Dim-0-over-``data`` NamedSharding; as a jit pytree PREFIX it
        broadcasts over a whole batch tree of batch-major leaves."""
        return NamedSharding(self.mesh, P(mesh_lib.data_axis(self.mesh)))

    def state_shardings(self, state: Any = None):
        """jit ``in_shardings``/``out_shardings`` entry for the train
        state.  Pure data parallelism needs no structure — a replicated
        prefix covers any state tree; with rules armed the concrete
        ``state`` is required to resolve per-leaf specs."""
        if self.rules is None:
            return self.replicated
        if state is None:
            raise ValueError("state_shardings with tensor-parallel rules "
                             "needs the concrete state tree")
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.state_specs(state))

    def batch_shardings(self):
        """jit ``in_shardings`` entry for batches, or ``None`` when jit
        cannot place them (per-key overrides need the spec layer's own
        ``place_batch``; jit prefixes cannot express per-key specs over
        an open batch structure)."""
        if self.batch_overrides:
            return None
        return self.data_sharding

    def ragged_dispatch(self, annotated: Callable, plain: Callable
                        ) -> Callable:
        """ONE routing rule for annotated serving/eval programs, owned
        by the spec layer: ``dispatch(variables, *batch_args)`` runs the
        mesh-``annotated`` program when the first batch argument's
        leading dim divides the data axis, and the ``plain`` program for
        ragged tails (remainder predict/validation batches) or 0-d
        probes.  `make_eval_step` and the serving predictors share this
        instead of hand-rolling divergent copies."""
        width = self.data_axis_size

        def dispatch(variables, *args):
            leaf = jax.tree_util.tree_leaves(args[0])[0]
            shape = getattr(leaf, "shape", None)
            if shape and shape[0] % width == 0:
                return annotated(variables, *args)
            return plain(variables, *args)

        return dispatch

    def jit_places_batches(self) -> bool:
        """True when host batches can go straight into the annotated jit
        (single-process mesh, no per-key overrides) — the GSPMD
        declare-once fast path.  Multi-process meshes assemble global
        arrays from per-host shards (``place_batch``) instead."""
        return (self.batch_shardings() is not None
                and not mesh_lib.spans_processes(self.mesh))

    # -- elastic resize (declaration ⊆ mesh coverage) --------------------
    def declared_axes(self) -> frozenset:
        """Every mesh-axis name the declaration references: the batch
        overrides' PartitionSpecs plus the axes the state rules can
        resolve to (probed — rule spec builders close over their axis
        names; see ``tensor.rule_axes``)."""
        from analytics_zoo_tpu.parallel import tensor as tensor_lib

        axes = set()
        for spec in (self.batch_overrides or {}).values():
            axes |= _spec_axes(spec)
        if self.rules:
            axes |= set(tensor_lib.rule_axes(self.rules))
        return frozenset(axes)

    def missing_axes(self) -> tuple:
        """Declared axes ``self.mesh`` does not carry, sorted.  Rule axes
        in this set DEGRADE to replicated (sharding is an optimization);
        override axes in it would fail placement — ``place_batch`` /
        ``place_state`` surface that as ElasticPlacementError."""
        return tuple(sorted(self.declared_axes()
                            - set(self.mesh.axis_names)))

    def replace_mesh(self, new_mesh: Mesh) -> "SpecSet":
        """The elastic-resize boundary: the SAME declaration re-placed
        onto a different mesh (a checkpoint saved at width W restores at
        W′ by re-running ``place_state`` under the returned SpecSet —
        params are width-agnostic host values by construction).

        Raises :class:`ElasticPlacementError` when ``new_mesh`` drops an
        axis the declaration RESOLVED on the current mesh: silently
        degrading active tensor-parallel sharding mid-resize would
        change program geometry without a trace.  Callers who want the
        degradation build a fresh SpecSet via ``pipeline_specs``."""
        active = self.declared_axes() & set(self.mesh.axis_names)
        missing = tuple(sorted(active - set(new_mesh.axis_names)))
        if missing:
            raise ElasticPlacementError(
                f"replace_mesh: new mesh axes {tuple(new_mesh.axis_names)} "
                f"do not cover declared axes {missing} that the current "
                f"mesh {tuple(self.mesh.axis_names)} resolves — an elastic "
                f"re-placement must not silently drop active sharding")
        return dataclasses.replace(self, mesh=new_mesh)

    def _require_override_axes(self, site: str) -> None:
        """Boundary check: batch-override axes absent from the mesh would
        otherwise surface as an opaque NamedSharding failure deep inside
        jax at device_put time."""
        missing = tuple(sorted(
            {ax for spec in (self.batch_overrides or {}).values()
             for ax in _spec_axes(spec)} - set(self.mesh.axis_names)))
        if missing:
            raise ElasticPlacementError(
                f"{site}: mesh axes {tuple(self.mesh.axis_names)} do not "
                f"cover batch-override axes {missing} — the declaration "
                f"cannot be placed on this mesh")

    # -- placement (the one device_put site) ----------------------------
    def place_state(self, state: Any) -> Any:
        """Host state pytree → mesh placement per the declared specs:
        replicate (multi-host aware) without rules, rule-resolved
        ``NamedSharding`` placement with them."""
        from analytics_zoo_tpu.parallel import tensor as tensor_lib

        self._require_override_axes("place_state")
        if self.rules is None:
            return mesh_lib.replicate(state, self.mesh)
        return tensor_lib.shard_tree(state, self.mesh, self.rules)

    def place_batch(self, batch: Any) -> Any:
        """Host batch pytree → mesh placement (dim 0 over ``data``,
        overrides honored, multi-host local-shard assembly)."""
        self._require_override_axes("place_batch")
        return mesh_lib.shard_batch(batch, self.mesh,
                                    overrides=self.batch_overrides)

    def gather(self, tree: Any) -> Any:
        """Device pytree → host numpy copy (replicated leaves read their
        local replica; byte-identical to what was placed — the
        roundtrip ``tests/test_specs.py`` pins)."""
        return mesh_lib.host_local_state(tree)


# ---------------------------------------------------------------------------
# Pipeline registry — every entry point declares here, once
# ---------------------------------------------------------------------------

_PIPELINES: Dict[str, Callable[..., SpecSet]] = {}


def register_pipeline(name: str):
    """Register a ``builder(mesh, **opts) -> SpecSet`` under ``name``.
    ``tests/test_specs.py`` iterates the registry, so a new pipeline
    gets the structure-match + roundtrip guards for free."""
    def deco(fn: Callable[..., SpecSet]):
        _PIPELINES[name] = fn
        return fn
    return deco


def registered_pipelines() -> Sequence[str]:
    return tuple(sorted(_PIPELINES))


def pipeline_specs(name: str, mesh: Optional[Mesh] = None,
                   **opts: Any) -> SpecSet:
    """The declared :class:`SpecSet` for a registered pipeline on
    ``mesh`` (default: 1-D data mesh over every device)."""
    if name not in _PIPELINES:
        raise KeyError(f"no specs registered for pipeline {name!r} "
                       f"(registered: {', '.join(registered_pipelines())})")
    return _PIPELINES[name](mesh or mesh_lib.create_mesh(), **opts)


@register_pipeline("ssd")
def _ssd_specs(mesh: Mesh, tp: Optional[str] = None,
               resolution: int = 300) -> SpecSet:
    """SSD detection training/serving.  ``tp=None``: pure data parallel
    (params replicated).  ``tp="spatial"``: image HEIGHT over ``model``
    — the conv-trunk mode that exchanges halo rows where channel
    sharding all-reduces whole activation maps
    (``tensor.spatial_input_spec``).  ``tp="megatron"``: paired col/row
    weight sharding (``tensor.ssd_tp_rules``)."""
    from analytics_zoo_tpu.parallel import tensor as tensor_lib

    if tp is None:
        return SpecSet(mesh)
    if tp == "spatial":
        return SpecSet(mesh, batch_overrides={
            "input": tensor_lib.spatial_input_spec()})
    if tp == "megatron":
        return SpecSet(mesh,
                       rules=tensor_lib.ssd_tp_rules(resolution=resolution))
    raise ValueError(f"ssd tp mode {tp!r} (None | 'spatial' | 'megatron')")


@register_pipeline("frcnn")
def _frcnn_specs(mesh: Mesh) -> SpecSet:
    """Faster-RCNN joint training: data parallel (the proposal/ROI ops
    are batch-local; weights replicated)."""
    return SpecSet(mesh)


@register_pipeline("ds2")
def _ds2_specs(mesh: Mesh, param_rules: Optional[Sequence] = None
               ) -> SpecSet:
    """DeepSpeech2 CTC training: length-bucketed batches dim-0 over
    ``data`` (the (features, n_frames) input tuple is batch-major on
    both legs); optional tensor-parallel weight rules on a data×model
    mesh."""
    return SpecSet(mesh, rules=param_rules)


@register_pipeline("fraud")
def _fraud_specs(mesh: Mesh) -> SpecSet:
    """Fraud-detection MLP: pure data parallel."""
    return SpecSet(mesh)


@register_pipeline("rec")
def _rec_specs(mesh: Mesh, shard_tables: bool = True) -> SpecSet:
    """Recommendation (NeuralCF / Wide&Deep): data-parallel batches with
    every ``(vocab, dim)`` lookup table ROW-sharded over ``model`` when
    the mesh declares that axis (``tensor.embedding_row_rules`` — each
    device owns an id range; the lookup compiles to a shard-local gather
    plus the partitioner's collectives).  On a pure data mesh the rule
    degrades to replicated, so the same declaration serves both."""
    from analytics_zoo_tpu.parallel import tensor as tensor_lib

    rules = tensor_lib.embedding_row_rules() if shard_tables else None
    return SpecSet(mesh, rules=rules)


@register_pipeline("sentiment")
def _sentiment_specs(mesh: Mesh, shard_tables: bool = True) -> SpecSet:
    """Sentiment heads over a GloVe-scale vocab table: same embedding
    row-sharding declaration as ``rec`` (the table dominates the model's
    parameter count; the recurrent/conv head stays replicated)."""
    from analytics_zoo_tpu.parallel import tensor as tensor_lib

    rules = tensor_lib.embedding_row_rules() if shard_tables else None
    return SpecSet(mesh, rules=rules)


@register_pipeline("lm")
def _lm_specs(mesh: Mesh) -> SpecSet:
    """Decoder-LM serving (models/lm.py): on a mesh with an ``expert``
    axis the stacked routed experts are sharded over it by their leading
    (expert) dim — what ``expert.moe_held_experts_parallel`` expects —
    and everything else (attention, router, shared expert, the ends) is
    replicated; on any other mesh all of it is replicated (one chip
    serves its own share and its own sessions).  The rules go by the
    parameters' names, which every decoder configuration shares (a model
    without a router bias, headwise gates or a shared expert has fewer
    leaves; a grouped-query model's attention has ``wq``, ``wk``, ``wv``
    and ``sink`` for the latent one's leaves — replicated like them:
    attention is data-parallel over the sessions; a model with a
    state-space mixer has an ``ssm`` group a layer — ``in_proj``,
    ``conv_w``, ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``norm``,
    ``out_proj`` — replicated too, and no ``moe`` at all; its sessions'
    recurrent and convolution states are cache leaves of the replica
    that serves them)."""
    from analytics_zoo_tpu.parallel import tensor as tensor_lib
    from analytics_zoo_tpu.parallel.expert import EXPERT_AXIS

    if EXPERT_AXIS not in mesh.axis_names:
        return SpecSet(mesh)
    return SpecSet(mesh, rules=[
        (r".*moe/experts/w_(gate|up|down)$",
         tensor_lib._row_dim(EXPERT_AXIS))])
