"""TensorBoard summaries — reference ``TrainSummary``/``ValidationSummary``
(``ssd/example/Train.scala:237-243``; notebook
``set_summary_trigger("Parameters", SeveralIteration(50))``).

Backed by tensorboardX event files; per-tag triggers gate how often a tag is
written.  Multi-host: only process 0 writes (metrics are already global
since the loss/metrics come out of the psum'd step).
"""

from __future__ import annotations

import glob
import os
import struct
from typing import Dict, List, Optional, Tuple

import jax

from analytics_zoo_tpu.parallel.optim import TrainingState, Trigger


class _Summary:
    def __init__(self, log_dir: str, app_name: str, kind: str):
        self.log_dir = os.path.join(log_dir, app_name, kind)
        self._writer = None
        self.triggers: Dict[str, Trigger] = {}

    @property
    def writer(self):
        if self._writer is None and jax.process_index() == 0:
            from tensorboardX import SummaryWriter

            os.makedirs(self.log_dir, exist_ok=True)
            self._writer = SummaryWriter(self.log_dir)
        return self._writer

    def set_summary_trigger(self, tag: str, trigger: Trigger) -> "_Summary":
        self.triggers[tag] = trigger
        return self

    def _gated(self, tag: str, iteration: int) -> bool:
        t = self.triggers.get(tag)
        if t is None:
            return True
        # summary gating is iteration-granular (the reference's notebook use
        # is SeveralIteration); epoch_finished=True keeps everyEpoch-style
        # triggers from silently never firing here
        state = TrainingState(iteration=iteration, epoch_finished=True)
        return t(state)

    def add_scalar(self, tag: str, value, iteration: int) -> None:
        """``value`` may be a device array: it is only forced to a host
        float AFTER the trigger gate, so gated-off iterations never pay a
        device→host sync (which stalls the async dispatch pipeline)."""
        if self.writer is not None and self._gated(tag, iteration):
            self.writer.add_scalar(tag, float(value), iteration)

    def add_histogram(self, tag: str, values, iteration: int) -> None:
        if self.writer is not None and self._gated(tag, iteration):
            self.writer.add_histogram(tag, values, iteration)

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


class TrainSummary(_Summary):
    def __init__(self, log_dir: str, app_name: str):
        super().__init__(log_dir, app_name, "train")


class ValidationSummary(_Summary):
    def __init__(self, log_dir: str, app_name: str):
        super().__init__(log_dir, app_name, "validation")


def read_scalars(summary_dir: str) -> Dict[str, List[Tuple[int, float, float]]]:
    """Read back what a summary wrote: ``{tag: [(iteration, value,
    wall_time), ...]}`` in write order, from the event files under
    ``summary_dir`` (a ``_Summary.log_dir``).  The reading half of the
    summaries — how ``chip_smoke.py`` sees the per-step loss of a run
    driven through ``train_ssd``."""
    from tensorboardX.proto import event_pb2

    out: Dict[str, List[Tuple[int, float, float]]] = {}
    for path in sorted(glob.glob(os.path.join(summary_dir,
                                              "events.out.tfevents.*"))):
        with open(path, "rb") as f:
            data = f.read()
        pos = 0
        # TFRecord framing: u64 length, u32 crc, payload, u32 crc
        while pos + 12 <= len(data):
            (n,) = struct.unpack_from("<Q", data, pos)
            event = event_pb2.Event.FromString(data[pos + 12:pos + 12 + n])
            pos += 12 + n + 4
            for v in event.summary.value:
                if v.HasField("simple_value"):
                    out.setdefault(v.tag, []).append(
                        (int(event.step), float(v.simple_value),
                         float(event.wall_time)))
    return out
