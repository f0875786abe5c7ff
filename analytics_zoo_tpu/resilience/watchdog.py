"""Host-side stall detection.

A hung device step (lost device, deadlocked collective) or a dead input
pipeline does not raise — it blocks the host loop forever, which is the
worst failure mode for a supervised job: no error, no restart, no
progress.  :class:`StallWatchdog` turns "no progress past a deadline"
into an exception the :func:`~analytics_zoo_tpu.parallel.elastic.
run_resilient` supervisor can retry.

Mechanism: the watched loop calls :meth:`StallWatchdog.beat` on every
unit of progress (one optimizer step, one batch fetched); a daemon
monitor thread checks the heartbeat age every ``poll_s`` and, past
``timeout_s``, marks the watchdog stalled and interrupts the main thread
(``_thread.interrupt_main`` — a simulated KeyboardInterrupt that fires
even while the main thread is blocked in Python-level waits).  The
training loop translates that interrupt into :class:`StallError` when
``stalled`` is set, so a real Ctrl-C is never misclassified.

The deadline must cover the slowest *legitimate* step, including the
first-step XLA compile — size ``timeout_s`` generously (minutes for real
models; the tests use sub-second steps).
"""

from __future__ import annotations

import _thread
import logging
import threading
from typing import Callable, Optional

from analytics_zoo_tpu.resilience.errors import StallError
from analytics_zoo_tpu.utils.clock import as_now_fn

logger = logging.getLogger("analytics_zoo_tpu")


class StallWatchdog:
    """Heartbeat-based stall detector.

    Usage::

        wd = StallWatchdog(timeout_s=300)
        wd.start()
        try:
            for batch in data:
                step(batch)
                wd.beat()
        except KeyboardInterrupt:
            if wd.stalled:
                raise StallError("train step stalled") from None
            raise
        finally:
            wd.stop()

    ``on_stall`` (optional) replaces the default main-thread interrupt —
    e.g. a callback that dumps stacks or pages an operator.  Pull-style
    consumers can instead call :meth:`check` periodically.
    """

    def __init__(self, timeout_s: float, poll_s: Optional[float] = None,
                 name: str = "train",
                 on_stall: Optional[Callable[["StallWatchdog"], None]] = None,
                 clock=None):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.poll_s = max(0.01, poll_s if poll_s is not None
                          else min(timeout_s / 4.0, 1.0))
        self.name = name
        self.on_stall = on_stall
        # injectable time source — a utils.clock.Clock object or a bare
        # now() callable (both normalized): the serving runtime
        # supervises replica forwards in PULL mode (beat → check) on a
        # virtual clock so the wedged-replica path is deterministic in
        # tests and the drill; the threaded monitor path keeps real
        # time by default
        self._clock = as_now_fn(clock)
        self._last = self._clock()
        self._stalled = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "StallWatchdog":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._stalled = False
        self._last = self._clock()
        self._thread = threading.Thread(
            target=self._monitor, name=f"stall-watchdog-{self.name}",
            daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.poll_s * 4)
            self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- heartbeat ---------------------------------------------------------
    def beat(self) -> None:
        """Record one unit of progress (resets the deadline)."""
        self._last = self._clock()

    def reset(self) -> None:
        """Clear a latched stall verdict and restart the deadline —
        for supervised units that RECOVER in place (a serving replica
        coming back from its background restart).  The push-mode
        monitor thread latches via ``start()`` instead."""
        self._stalled = False
        self._last = self._clock()

    @property
    def stalled(self) -> bool:
        return self._stalled

    @property
    def age_s(self) -> float:
        """Seconds since the last heartbeat."""
        return self._clock() - self._last

    def check(self) -> None:
        """Pull-style: raise :class:`StallError` if the deadline passed
        (for loops that can poll instead of being interrupted)."""
        if self._stalled or self.age_s > self.timeout_s:
            self._stalled = True
            raise StallError(
                f"{self.name}: no progress for {self.age_s:.1f}s "
                f"(deadline {self.timeout_s:.1f}s)")

    # -- monitor -----------------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_s):
            age = self._clock() - self._last
            if age > self.timeout_s:
                self._stalled = True
                logger.error(
                    "StallWatchdog[%s]: no progress for %.1fs "
                    "(deadline %.1fs) — interrupting", self.name, age,
                    self.timeout_s)
                if self.on_stall is not None:
                    self.on_stall(self)
                else:
                    # interrupt_main simulates SIGINT.  With a
                    # PreemptionHandler installed, ITS handler receives
                    # the interrupt — it checks `stalled` on the
                    # watchdog wired to it and raises KeyboardInterrupt
                    # immediately instead of treating it as preemption.
                    _thread.interrupt_main()
                return
