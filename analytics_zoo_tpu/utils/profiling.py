"""Profiling utilities: traces + step timing.

The reference's tracing story (SURVEY.md §5): BigDL per-module
``getTimes()`` aggregated by ``TestUtil.printModuleTime``, plus wall-clock
throughput accumulators in ``Validator.test``.  TPU equivalents:

- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-viewable trace (op-level timing replaces module-level);
- :class:`StepTimer` — host-side per-step wall-clock accumulator with the
  Validator-style "[N] in T seconds. Throughput is …" summary.

The ``getTimes`` analogue — device time by named section of a program — is
``jax.named_scope`` in the model code with ``obs/device_scopes.py``'s map
(docs/OBSERVABILITY.md, "Device scopes").
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Dict, List, Optional

import jax

logger = logging.getLogger("analytics_zoo_tpu")


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace viewable in TensorBoard's profile tab."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Accumulate per-step wall times + record counts; print throughput in
    the reference Validator's format (``Validator.scala:82-86``).

    ``registry`` (optional, an :class:`analytics_zoo_tpu.obs.registry.
    MetricRegistry`): every step also lands in the central registry —
    a ``<name>/step_s`` bounded-reservoir histogram plus
    ``<name>/records`` and ``<name>/steps`` counters — so the timer's
    numbers appear in the same snapshot/Prometheus/TensorBoard surfaces
    as the serving and data metrics instead of only in its own log
    line."""

    def __init__(self, name: str = "train", registry=None):
        self.name = name
        self.registry = registry
        self.times: List[float] = []
        self.records = 0
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            raise RuntimeError(f"StepTimer[{self.name}]: __exit__ without "
                               "a matching __enter__")
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.times.append(dt)
        if self.registry is not None:
            # az-allow: registered-metric-names — timer-name-prefixed; the Optimizer's canonical train/dispatch/* family is declared in obs/names.py
            self.registry.histogram(f"{self.name}/step_s").observe(dt)
            # az-allow: registered-metric-names — timer-name-prefixed steps counter, same train/dispatch/* family as the step histogram
            self.registry.counter(f"{self.name}/steps").inc()

    def step(self, n_records: int = 0):
        """Use as ``with timer.step(n):`` — counts records too."""
        self.records += n_records
        if self.registry is not None and n_records:
            # az-allow: registered-metric-names — timer-name-prefixed records counter, same train/dispatch/* family as the step histogram
            self.registry.counter(f"{self.name}/records").inc(n_records)
        return self

    def summary(self) -> Dict[str, float]:
        total = sum(self.times)
        n = len(self.times)
        out = {
            "steps": n,
            "total_s": total,
            "mean_ms": (total / n * 1e3) if n else 0.0,
            "records": self.records,
            "records_per_sec": self.records / total if total else 0.0,
        }
        return out

    def log(self) -> None:
        s = self.summary()
        logger.info("[%s] %d in %.2f seconds. Throughput is %.2f records/sec "
                    "(%.1f ms/step)", self.name, s["records"], s["total_s"],
                    s["records_per_sec"], s["mean_ms"])


def memory_summary() -> Dict[str, Dict[str, float]]:
    """Per-device HBM usage in MB (where the backend exposes
    ``memory_stats`` — TPU/GPU; CPU devices report {}).  The observability
    the reference delegated to Spark's executor UI."""
    import jax

    out: Dict[str, Dict[str, float]] = {}
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if not stats:
            out[str(d)] = {}
            continue
        out[str(d)] = {
            k: round(v / 1e6, 2)
            for k, v in stats.items()
            if isinstance(v, (int, float)) and "bytes" in k
        }
    return out


def log_memory(prefix: str = "memory") -> None:
    for dev, stats in memory_summary().items():
        if stats:
            logger.info("%s %s: %s", prefix, dev, stats)
