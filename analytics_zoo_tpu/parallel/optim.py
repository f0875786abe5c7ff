"""Optim methods, LR schedules, and triggers — BigDL ``OptimMethod``/``Trigger``
parity on optax.

The reference trains SSD with SGD(momentum 0.9) under a MultiStep or
plateau-on-score schedule and warms up with Adam to a target mAP
(``ssd/example/Train.scala:178-210``); the notebooks use Adam.  Triggers
drive epoch/iteration control flow (``Trigger.everyEpoch``, ``maxEpoch``,
``severalIteration``, ``maxScore``, SURVEY.md §2.7 "Optimizer").

Design: an ``OptimMethod`` owns an ``optax.GradientTransformation`` whose
learning rate is injected as a hyperparameter, so *metric-driven* schedules
(Plateau) can rescale the LR from the host between jitted steps without
recompilation.  Step-driven schedules (MultiStep, warmup, poly) are pure
functions of the step count and live inside the jitted update.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np
import optax


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------


def multistep(base_lr: float, milestones, gamma: float = 0.1) -> Callable:
    """MultiStep LR: multiply by ``gamma`` at each milestone iteration
    (reference SGD ``MultiStep`` branch, ``Train.scala:206-210``)."""
    # host numpy: this closure runs inside the jitted train step, which
    # embeds numpy constants directly; a committed device array would be
    # fetched back from its device at every trace
    ms = np.asarray(sorted(milestones))

    def schedule(step):
        n = jnp.sum(step >= ms)
        return base_lr * (gamma ** n)

    return schedule


def polynomial(base_lr: float, power: float, max_iter: int) -> Callable:
    def schedule(step):
        frac = jnp.clip(step / max_iter, 0.0, 1.0)
        return base_lr * (1.0 - frac) ** power

    return schedule


def warmup_linear(base_lr: float, warmup_steps: int, after: Optional[Callable] = None):
    def schedule(step):
        warm = base_lr * (step + 1) / max(warmup_steps, 1)
        rest = after(step - warmup_steps) if after is not None else base_lr
        return jnp.where(step < warmup_steps, warm, rest)

    return schedule


class Plateau:
    """Host-side plateau-on-metric LR controller (reference SGD ``Plateau``
    monitoring "score", factor 0.5, ``Train.scala:196-204``).

    Stateful and metric-driven, so it cannot live inside jit: call
    ``update(metric)`` once per validation; the resulting ``scale`` is fed to
    the train step as the injected LR multiplier.
    """

    def __init__(self, monitor: str = "score", factor: float = 0.5,
                 patience: int = 10, mode: str = "max", epsilon: float = 1e-4,
                 min_lr: float = 0.0, base_lr: float = 1.0):
        self.monitor = monitor
        self.factor = factor
        self.patience = patience
        self.mode = mode
        self.epsilon = epsilon
        self.min_lr = min_lr
        self.base_lr = base_lr
        self.scale = 1.0
        self.best: Optional[float] = None
        self.num_bad = 0

    def update(self, metric: float) -> float:
        better = (
            self.best is None
            or (self.mode == "max" and metric > self.best + self.epsilon)
            or (self.mode == "min" and metric < self.best - self.epsilon)
        )
        if better:
            self.best = metric
            self.num_bad = 0
        else:
            self.num_bad += 1
            if self.num_bad > self.patience:
                new_scale = self.scale * self.factor
                if self.base_lr * new_scale >= self.min_lr:
                    self.scale = new_scale
                self.num_bad = 0
        return self.scale


# ---------------------------------------------------------------------------
# OptimMethod
# ---------------------------------------------------------------------------


class OptimMethod:
    """Wraps an optax transformation with an injected LR hyperparameter.

    ``tx.init(params)`` / ``tx.update`` are used by the train-step factory;
    ``lr_for_step`` is traced inside jit; ``lr_scale`` (host float) carries
    Plateau rescaling across steps.
    """

    def __init__(self, opt_factory: Callable[[], optax.GradientTransformation],
                 schedule: Callable, plateau: Optional[Plateau] = None):
        self._factory = opt_factory
        self.schedule = schedule
        self.plateau = plateau
        self.tx = opt_factory()

    def lr_for_step(self, step, lr_scale):
        return self.schedule(step) * lr_scale

    @property
    def lr_scale(self) -> float:
        return self.plateau.scale if self.plateau is not None else 1.0

    def on_validation(self, metrics: Dict[str, float]) -> None:
        if self.plateau is not None and self.plateau.monitor in metrics:
            self.plateau.update(metrics[self.plateau.monitor])

    def state_dict(self) -> Dict[str, Any]:
        """Host-side state that must survive a checkpoint/resume (the
        device-side opt_state lives in the TrainState; this is the rest —
        Plateau's learned LR scale and patience counters)."""
        if self.plateau is None:
            return {}
        return {"plateau": {"scale": self.plateau.scale,
                            "best": self.plateau.best,
                            "num_bad": self.plateau.num_bad}}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        p = d.get("plateau")
        if p and self.plateau is not None:
            self.plateau.scale = float(p["scale"])
            self.plateau.best = p["best"]
            self.plateau.num_bad = int(p["num_bad"])


def _with_injected_lr(inner: Callable[[float], optax.GradientTransformation]):
    return optax.inject_hyperparams(inner)(learning_rate=1.0)


class SGD(OptimMethod):
    """SGD + momentum + optional L2 weight decay (the reference's workhorse:
    ``new SGD(learningRate=lr, momentum=0.9)``, ``Train.scala:192``)."""

    def __init__(self, learning_rate: float = 1e-3, momentum: float = 0.0,
                 weight_decay: float = 0.0, nesterov: bool = False,
                 schedule: Optional[Callable] = None,
                 plateau: Optional[Plateau] = None):
        if plateau is not None:
            plateau.base_lr = learning_rate

        def factory():
            def inner(learning_rate):
                parts = []
                if weight_decay:
                    parts.append(optax.add_decayed_weights(weight_decay))
                parts.append(optax.sgd(learning_rate, momentum=momentum or None,
                                       nesterov=nesterov))
                return optax.chain(*parts)

            return _with_injected_lr(inner)

        sched = schedule or (lambda step: learning_rate)
        super().__init__(factory, sched, plateau)


class Adam(OptimMethod):
    def __init__(self, learning_rate: float = 1e-3, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8,
                 schedule: Optional[Callable] = None,
                 plateau: Optional[Plateau] = None):
        if plateau is not None:
            plateau.base_lr = learning_rate

        def factory():
            return _with_injected_lr(
                lambda learning_rate: optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)
            )

        sched = schedule or (lambda step: learning_rate)
        super().__init__(factory, sched, plateau)


class AdamW(OptimMethod):
    def __init__(self, learning_rate: float = 1e-3, weight_decay: float = 1e-4,
                 schedule: Optional[Callable] = None):
        def factory():
            return _with_injected_lr(
                lambda learning_rate: optax.adamw(learning_rate, weight_decay=weight_decay)
            )

        super().__init__(factory, schedule or (lambda step: learning_rate))


# ---------------------------------------------------------------------------
# Triggers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainingState:
    """Host-visible loop state that triggers predicate over."""

    epoch: int = 0
    iteration: int = 0
    epoch_finished: bool = False
    loss: float = float("inf")
    score: Optional[float] = None
    #: last anomaly health word (``resilience.anomaly`` bit layout);
    #: 0 = healthy, and always 0 when no anomaly policy is armed.  The
    #: checkpoint guard refuses to snapshot while it is non-zero.
    health: int = 0


class Trigger:
    """Predicate over TrainingState (reference ``Trigger`` companion:
    everyEpoch / maxEpoch / severalIteration / maxScore / minLoss)."""

    def __init__(self, fn: Callable[[TrainingState], bool], name: str = "trigger"):
        self._fn = fn
        self.name = name

    def __call__(self, state: TrainingState) -> bool:
        return self._fn(state)

    # -- factories ---------------------------------------------------------
    @staticmethod
    def always() -> "Trigger":
        """Fires at every evaluation (per-iteration checkpointing in
        chaos drills / debugging — expensive for real jobs)."""
        return Trigger(lambda s: True, "always")

    @staticmethod
    def max_wall_time(seconds: float, clock=None) -> "Trigger":
        """Fires once ``seconds`` of wall time elapsed since the trigger
        was CREATED (host-side clock).  The bounded-run guard for drills
        and preemptible jobs: compose as ``Trigger.or_(max_epoch(n),
        max_wall_time(t))`` so a restart-looping run still terminates.
        ``clock``: injected time source (utils.clock convention) — a
        VirtualClock makes the trigger deterministic in drills."""
        from analytics_zoo_tpu.utils.clock import as_now_fn

        now = as_now_fn(clock)
        start = now()
        return Trigger(lambda s: now() - start >= seconds,
                       f"maxWallTime({seconds}s)")

    @staticmethod
    def every_epoch() -> "Trigger":
        return Trigger(lambda s: s.epoch_finished, "everyEpoch")

    @staticmethod
    def max_epoch(n: int) -> "Trigger":
        return Trigger(lambda s: s.epoch >= n, f"maxEpoch({n})")

    @staticmethod
    def max_iteration(n: int) -> "Trigger":
        return Trigger(lambda s: s.iteration >= n, f"maxIteration({n})")

    @staticmethod
    def several_iteration(n: int) -> "Trigger":
        return Trigger(lambda s: s.iteration > 0 and s.iteration % n == 0,
                       f"severalIteration({n})")

    @staticmethod
    def max_score(s: float) -> "Trigger":
        return Trigger(lambda st: st.score is not None and st.score >= s,
                       f"maxScore({s})")

    @staticmethod
    def min_loss(l: float) -> "Trigger":
        return Trigger(lambda st: st.loss <= l, f"minLoss({l})")

    @staticmethod
    def or_(*triggers: "Trigger") -> "Trigger":
        return Trigger(lambda s: any(t(s) for t in triggers),
                       " | ".join(t.name for t in triggers))

    @staticmethod
    def and_(*triggers: "Trigger") -> "Trigger":
        return Trigger(lambda s: all(t(s) for t in triggers),
                       " & ".join(t.name for t in triggers))
