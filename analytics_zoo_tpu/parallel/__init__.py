"""Distributed runtime: mesh/sharding, jitted train loop, optim, checkpointing.

The TPU-native replacement for the reference's BigDL DistriOptimizer + Spark
distribution stack (SURVEY.md §2.7 "Optimizer" and §5 "Distributed
communication backend").
"""

from analytics_zoo_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    batch_sharding,
    batch_spec,
    create_mesh,
    replicate,
    replicated_sharding,
    shard_batch,
)
from analytics_zoo_tpu.parallel.optim import (
    SGD,
    Adam,
    AdamW,
    OptimMethod,
    Plateau,
    TrainingState,
    Trigger,
    multistep,
    polynomial,
    warmup_linear,
)
from analytics_zoo_tpu.parallel.train import (
    MAE,
    Loss,
    Optimizer,
    Top1Accuracy,
    TrainState,
    ValidationMethod,
    ValidationResult,
    create_train_state,
    make_eval_step,
    make_train_step,
    sparse_adam_apply,
    state_to_variables,
    validate,
)
from analytics_zoo_tpu.parallel.specs import (
    SpecSet,
    pipeline_specs,
    register_pipeline,
    registered_pipelines,
)
from analytics_zoo_tpu.parallel.summary import TrainSummary, ValidationSummary
from analytics_zoo_tpu.parallel import checkpoint
from analytics_zoo_tpu.parallel.expert import (
    held_experts_apply,
    moe_apply_dense,
    moe_apply_expert_parallel,
    moe_held_experts,
    moe_held_experts_parallel,
    route_top1,
    route_topk_sigmoid,
)
from analytics_zoo_tpu.parallel.pipeline import (
    carrier_decay_mask,
    flatten_stage_params,
    flatten_stage_params_grouped,
    pipeline_forward,
    pipeline_forward_het,
    stage_carrier_slice,
    unflatten_stage,
    split_microbatches,
    stack_stage_params,
)
from analytics_zoo_tpu.parallel.tensor import (
    default_tp_rules,
    embedding_row_rules,
    megatron_tp_rules,
    spatial_input_spec,
    ssd_tp_rules,
    shard_tree,
    sharded_param_count,
)
from analytics_zoo_tpu.parallel.elastic import (
    RETRYABLE_ERRORS,
    DivergenceDetector,
    FaultInjector,
    TrainingDiverged,
    run_resilient,
)
from analytics_zoo_tpu.resilience import (
    FATAL_ERRORS,
    AnomalyPolicy,
    CheckpointCorrupt,
    ElasticPlacementError,
    InjectedFault,
    Preempted,
    PreemptionHandler,
    PrefetchWorkerDied,
    ShardReadError,
    StallError,
    StallWatchdog,
)

__all__ = [k for k in dir() if not k.startswith("_")]
