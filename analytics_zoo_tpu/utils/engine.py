"""Engine: process/topology initialization for single- and multi-host runs.

TPU-native replacement for BigDL's ``Engine.createSparkConf`` /
``Engine.init`` / ``Engine.nodeNumber`` (reference
``pipeline/ssd/.../ssd/example/Train.scala:152-155``).  Where the reference
configures Spark executors, this configures the JAX runtime: optional
``jax.distributed`` init (one process per TPU-VM host) and lazily-queried
device/host topology used for per-host data sharding and batch splitting.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

import jax

logger = logging.getLogger("analytics_zoo_tpu")

_initialized = False


@dataclasses.dataclass
class EngineConfig:
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None


def init(config: Optional[EngineConfig] = None) -> None:
    """Initialize multi-host JAX if coordinator info is provided (or found in
    the standard env vars); no-op on single host.  Safe to call twice."""
    global _initialized
    if _initialized:
        return
    config = config or EngineConfig()
    coord = config.coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
        logger.info(
            "jax.distributed initialized: process %d/%d",
            jax.process_index(), jax.process_count(),
        )
    _initialized = True


def on_tpu() -> bool:
    """THE device predicate: whether this process's default JAX backend
    is a TPU.  Pallas kernels compile through Mosaic when it holds and
    run in interpret mode when it does not; ``backend="auto"``
    selections key on it.  Asked in one place so no call site can grow
    its own notion of "a TPU"."""
    return jax.default_backend() == "tpu"


def node_number() -> int:
    """Number of participating hosts (reference ``Engine.nodeNumber``)."""
    return jax.process_count()


def core_number() -> int:
    """Number of local accelerator devices (per-host 'cores')."""
    return jax.local_device_count()


def device_count() -> int:
    return jax.device_count()


def local_batch(global_batch: int) -> int:
    """Per-host share of a global batch (reference
    ``dataset.Utils.getBatchSize`` core-aware batching,
    ``RoiImageToBatch.scala:47``)."""
    n = jax.process_count()
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by {n} hosts")
    return global_batch // n
