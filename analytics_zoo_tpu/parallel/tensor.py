"""Tensor (model) parallelism via GSPMD sharding rules.

The reference scales one way only — data-parallel replicas with a
block-manager AllReduce (SURVEY.md §2.7 "Optimizer") — because BigDL
models must fit one executor.  On TPU the idiomatic generalization is not
explicit collectives but *sharding annotations*: place weight shards on a
``model`` mesh axis with ``NamedSharding`` and let XLA's SPMD partitioner
split the matmuls/convs and insert the all-gathers/reduce-scatters over
ICI (the scaling-book recipe: pick a mesh, annotate, let XLA do the
rest).  Nothing in the train step changes — the same jitted program runs
1D data-parallel or 2D data×model depending only on where the arrays
live.

Rules are matched against the '/'-joined pytree path, so they apply
equally to ``params`` and to optimizer slots that mirror params (optax's
``mu``/``nu``/``trace`` carry the same sub-paths).  A dimension that
doesn't divide the mesh axis falls back to replicated — sharding is an
optimization, never a correctness requirement.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

logger = logging.getLogger("analytics_zoo_tpu")

# rule: (path_regex, spec_fn(shape) -> PartitionSpec-axis-tuple)
Rule = Tuple[str, Callable[[Tuple[int, ...]], Sequence[Optional[str]]]]


def _last_dim(axis: str):
    """Shard the trailing (output-feature) dim — Dense kernels (in, out),
    Conv kernels (kh, kw, cin, cout), Embed tables (vocab, features)."""
    def spec(shape):
        return [None] * (len(shape) - 1) + [axis]
    return spec


def _contract_dim(axis: str):
    """Shard the CONTRACTION (input-feature) dim — dim 0 of a Dense
    (in, out) kernel, dim -2 of a Conv (kh, kw, cin, cout) kernel.  The
    matmul/conv then reduces over a sharded dim: each device contracts
    its channel slice locally and XLA inserts one all-reduce after
    (Megatron's "row-parallel" half)."""
    def spec(shape):
        axes: List[Optional[str]] = [None] * len(shape)
        axes[0 if len(shape) <= 2 else len(shape) - 2] = axis
        return axes
    return spec


def _row_dim(axis: str):
    """Shard dim 0 — the VOCAB dim of an Embed (vocab, features) table.
    Row sharding is what large lookup tables want: each device owns a
    contiguous id range and a lookup is a shard-local gather (the SPMD
    partitioner inserts the combine), whereas column sharding splits
    every row's features and makes EVERY lookup touch EVERY device."""
    def spec(shape):
        axes: List[Optional[str]] = [None] * len(shape)
        axes[0] = axis
        return axes
    return spec


def embedding_row_rules(axis: str = MODEL_AXIS) -> List[Rule]:
    """Row-shard every ``embedding`` table over ``axis`` (vocab dim 0).
    The rule a pipeline's ``param_rules`` prepends for large-vocab
    lookup tables; optimizer slots mirror it through their sub-paths."""
    return [
        (r"(^|.*/)embedding$", _row_dim(axis)),
    ]


def default_tp_rules(axis: str = MODEL_AXIS) -> List[Rule]:
    """Megatron-style column sharding of every learnable matrix's output
    features; biases/scales stay replicated (1-D, tiny).  Embedding
    tables take the ROW rule first: a (vocab, dim) table column-sharded
    on dim 1 (the pre-ISSUE-17 behavior of the generic rule below) puts
    a slice of every row on every device, which is the wrong axis for
    large vocabularies — first-match precedence routes them to
    ``embedding_row_rules`` instead."""
    return embedding_row_rules(axis) + [
        (r"(^|.*/)kernel$", _last_dim(axis)),
    ]


def megatron_tp_rules(col: Sequence[str], row: Sequence[str],
                      axis: str = MODEL_AXIS) -> List[Rule]:
    """Paired column/row rules from two lists of layer names.

    ``col`` layers shard output features (their activations leave
    channel-sharded); ``row`` layers shard the contraction dim (they
    consume a channel-sharded OR replicated input with zero gather cost
    and emit a replicated output after one all-reduce).  Chaining
    col→row is the Megatron MLP pattern: exactly one collective per
    pair, never an activation all-gather.  Names match any path
    component, so ``"conv1_1"`` covers ``params/vgg/conv1_1/kernel`` and
    its optimizer-slot mirrors."""
    def name_rule(names: Sequence[str], spec_fn) -> Rule:
        alt = "|".join(re.escape(n) for n in names)
        return (rf"(^|.*/)({alt})/(kernel|embedding)$", spec_fn)

    return [name_rule(col, _last_dim(axis)),
            name_rule(row, _contract_dim(axis))]


def ssd_tp_rules(axis: str = MODEL_AXIS,
                 resolution: int = 300) -> List[Rule]:
    """Tensor-parallel rules tuned to the SSDVgg topology.

    The generic ``default_tp_rules`` col-shards EVERY kernel — but the
    SSD conf/loc heads have small non-divisible cout (84/126), so their
    kernels fall back to replicated while their INPUTS arrive
    channel-sharded from the col-sharded trunk: GSPMD then has no
    efficient path and emits "Involuntary full rematerialization"
    (observed on the conf_2 conv in the 8-device dryrun).

    Here every edge is a clean Megatron pair instead: layers whose
    outputs feed another sharded conv or a detection head are column
    (cout) sharded; their consumers — including every loc_*/conf_* head,
    whose contraction dim (512/1024/256) always divides the axis — are
    row (cin) sharded.  Head outputs come back replicated (one psum),
    which is exactly what the concat + MultiBoxLoss want."""
    col = [
        # one col per VGG block boundary + the head-source producers
        "conv1_1", "conv2_1", "conv3_1", "conv4_1", "conv4_3",
        "conv5_2", "fc7",
        "conv6_2", "conv7_2", "conv8_2", "conv9_2",
    ]
    row = [
        "conv1_2", "conv2_2", "conv3_2", "conv3_3", "conv4_2",
        "conv5_1", "conv5_3", "fc6",
        "conv6_1", "conv7_1", "conv8_1", "conv9_1",
        "loc_0", "loc_1", "loc_2", "loc_3", "loc_4", "loc_5",
        "conf_0", "conf_1", "conf_2", "conf_3", "conf_4", "conf_5",
    ]
    if resolution != 300:
        # SSD512 adds one extra block + a 7th head pair, same pairing.
        # Mirror the MODEL's branch (models/ssd.py ExtraLayers builds the
        # conv10/7-source topology for any resolution != 300) — an
        # inverted guard would hand a 512-topology model the 300 rule
        # set, recreating the replicated-kernel-fed-by-sharded-input
        # rematerialization this module exists to avoid.
        col.append("conv10_2")
        row += ["conv10_1", "loc_6", "conf_6"]
    return megatron_tp_rules(col, row, axis)


def spatial_input_spec(axis: str = MODEL_AXIS,
                       data_axis_name: str = DATA_AXIS) -> P:
    """PartitionSpec for NHWC image batches with the HEIGHT axis sharded
    over the model axis — *spatial partitioning*, the conv-net tensor
    parallelism that actually pays on TPU.

    Channel (Megatron) sharding of a VGG-style trunk all-reduces FULL
    spatial activation maps once per col/row pair (neither mode's speed
    is measured on the chip: PERF.md §7, collectives).
    With H sharded and weights replicated, XLA's SPMD partitioner inserts
    only halo exchanges of kernel_h/2 edge rows per conv (communication
    O(B·W·C·halo), not O(B·H·W·C)), so each device convolves a horizontal
    stripe.  Use with ``shard_batch(..., overrides={"input":
    spatial_input_spec()})`` — parameters stay replicated (no rules).
    Keep ``ssd_tp_rules``/``megatron_tp_rules`` for models whose FLOPs
    live in dense/1×1 layers, where the activation all-reduce is small
    relative to the weight shards gained."""
    return P(data_axis_name, axis, None, None)


def rule_axes(rules: Sequence[Rule]) -> frozenset:
    """Mesh-axis names a rule set can resolve to, discovered by probing
    each spec builder across leaf ranks 1..4 (builders close over their
    axis names — there is no declarative field to read).  Used by the
    elastic boundary (``SpecSet.declared_axes``) to check whether a new
    mesh still covers what the declaration shards."""
    axes = set()
    for _, spec_fn in rules:
        for rank in (1, 2, 3, 4):
            try:
                resolved = spec_fn((2,) * rank)
            except Exception:
                continue
            for part in resolved:
                if part is None:
                    continue
                for ax in (part if isinstance(part, tuple) else (part,)):
                    axes.add(ax)
    return frozenset(axes)


def partition_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
                   rules: Sequence[Rule]) -> P:
    """Resolve the first matching rule into a PartitionSpec, degrading to
    replicated when the sharded dim doesn't divide the mesh axis."""
    for pattern, spec_fn in rules:
        if re.match(pattern, path):
            axes = list(spec_fn(shape))
            for i, ax in enumerate(axes):
                if ax is not None and (ax not in mesh.shape
                                       or shape[i] % mesh.shape[ax] != 0):
                    logger.debug("tp: %s dim %d (%d) not divisible by "
                                 "axis %r — replicating", path, i, shape[i], ax)
                    axes[i] = None
            return P(*axes)
    return P()


def spec_tree(tree: Any, mesh: Mesh,
              rules: Optional[Sequence[Rule]] = None) -> Any:
    """PartitionSpec for every leaf of ``tree``, structure-matched —
    the declare-once form the spec layer (``parallel.specs``) registers
    per pipeline.  Scalars and rule-misses resolve to replicated.
    ``shard_tree`` is exactly ``device_put`` over this tree, so the
    specs a pipeline declares and the placement it gets can't drift."""
    rules = default_tp_rules() if rules is None else rules

    def resolve(path_entries, leaf):
        path = "/".join(str(getattr(e, "key", getattr(e, "name", e)))
                        for e in path_entries)
        # read .shape where the leaf carries one (arrays AND abstract
        # ShapeDtypeStructs — the az-analyze audit resolves specs over
        # eval_shape trees); only coerce true scalars/lists through numpy
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = np.asarray(leaf).shape
        return (partition_spec(path, tuple(shape), mesh, rules)
                if len(shape) > 0 else P())

    return jax.tree_util.tree_map_with_path(resolve, tree)


def shard_tree(tree: Any, mesh: Mesh,
               rules: Optional[Sequence[Rule]] = None) -> Any:
    """device_put every leaf with its rule-resolved NamedSharding.  Works
    on a params dict or a whole TrainState (optimizer slots that mirror
    params pick up the same specs through their matching sub-paths)."""
    specs = spec_tree(tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        tree, specs)


def sharded_param_count(tree: Any) -> int:
    """Number of array LEAVES whose sharding actually splits data across
    more than one device (diagnostic for tests/logging).  On a full
    TrainState this counts optimizer-slot mirrors too (momentum/mu/nu
    carry the same sharding as their parameter), so it is a leaf count,
    not a distinct-parameter count — pass just the params subtree for
    the latter."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if sh is not None and not sh.is_fully_replicated:
            n += 1
    return n
