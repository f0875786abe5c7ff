"""analytics_zoo_tpu — a TPU-native analytics/deep-learning framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of the early
Analytics Zoo (BigDL-on-Spark zoo of pipelines: SSD object detection,
DeepSpeech2 ASR, fraud detection, sentiment / recommendation apps, and the
transform/vision image-augmentation library).

Reference capability map: see SURVEY.md at the repo root.  Design notes:

- Compute path is jax.numpy / flax on XLA:TPU; hot detection ops (NMS,
  multibox matching) are vectorized with static shapes so they stay on the MXU
  instead of the reference's sequential JVM loops
  (reference: pipeline/ssd/.../common/nn/MultiBoxLoss.scala, Nms.scala).
- Distribution is jax.sharding.Mesh + pjit/shard_map with XLA collectives
  over ICI, replacing BigDL's Spark block-manager AllReduce
  (reference: §2.7 of SURVEY.md).
- The data layer is a host-side iterator-transformer pipeline with device
  prefetch, replacing Spark RDD chains and Hadoop SequenceFiles.
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# The ONE compile-cache site.  Every entry point (chip_smoke.py, the
# benchmark's drivers, the tools) gets the persistent XLA compile cache by
# importing the package.  JAX itself reads JAX_COMPILATION_CACHE_DIR, so
# when the caller places the cache from outside nothing is set here;
# otherwise it lives at a FIXED path under the checkout — the directory
# is part of a cache entry's key, so a path derived from tempfile/pid/
# time would never hit.
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache"))

from analytics_zoo_tpu.utils import engine  # noqa: F401
