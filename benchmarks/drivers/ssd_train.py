"""SSD training through the program's ``Optimizer.optimize()``.

The resolution comes from the configuration, the global batch from the
traffic mix and the mesh from the devices JAX finds (``create_mesh()``),
so ``ssd300-train-dp4`` or ``ssd512-train-b32`` are data alone.

``train_ssd`` offers neither an end trigger nor a look at the state, so
the ``Optimizer`` is assembled here exactly as ``train_ssd``'s ``run``
does (``pipeline_specs("ssd")``, ``MultiBoxLoss``, ``skip_loss_above=50``,
``prefetch``, the device augment fused in, SGD with the plateau schedule,
``TrainSummary`` on ``log_dir``) plus two hooks the class already has:
``set_end_when`` (the benchmark's own trigger, which stamps every step on
the benchmark's clock right after the summary has read that step's loss,
and ends the run when the window is over) and ``set_epoch_hook`` (the
state after step 1 and after step 3, copied to the host for the check).

ONE ``optimize()`` call is the whole run: the feed's first "epoch" is one
batch (the compile step), the second two more — the three steps the
reference follows — and from then on the data set's own epochs, whose
first ``warm_steps`` steps are still set-up.  The window opens at the
stamp of the last of them.
"""

from __future__ import annotations

import glob
import os
import struct
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import datagen
from benchmarks.reference import ssd as ref

ANNOTATE_LOADER = "bench/loader_next"


class Feed:
    """The ``DataSet`` handed to the Optimizer, wrapped: epoch 0 is one
    batch, epoch 1 two, later ones the data set's own.  Times how long the
    Optimizer's prefetch thread sits inside the loader's ``next`` for each
    batch, and keeps the first ``keep`` host batches for the check."""

    def __init__(self, dataset, keep: int):
        self.dataset, self.keep = dataset, keep
        self.epoch = 0
        self.kept: List[Dict] = []
        self.waits: List[tuple] = []       # (monotonic at return, seconds)

    def __iter__(self):
        import jax

        limit = {0: 1, 1: 2}.get(self.epoch)
        self.epoch += 1
        it = iter(self.dataset)
        try:
            n = 0
            while limit is None or n < limit:
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation(ANNOTATE_LOADER):
                    batch = next(it, None)
                t1 = time.monotonic()
                if batch is None:
                    return
                self.waits.append((t1, t1 - t0))
                if len(self.kept) < self.keep:
                    self.kept.append(jax.tree_util.tree_map(np.array, batch))
                n += 1
                yield batch
        finally:
            if hasattr(it, "close"):
                it.close()


class WindowTrigger:
    """The run's ``end_when``.  Called by the Optimizer after every step,
    once the summary has read the step's loss: stamps the step, opens the
    window at step ``open_at``, starts and stops the tracer's slice, and
    says "end" at the first step past ``seconds``."""

    def __init__(self, open_at: int, seconds: float, tracer,
                 trace_from: int, trace_steps: int):
        self.open_at, self.seconds, self.tracer = open_at, seconds, tracer
        self.trace_from, self.trace_to = trace_from, trace_from + trace_steps
        self.stamps: Dict[int, float] = {}
        self.t_open: Optional[float] = None

    def __call__(self, loop) -> bool:
        it = loop.iteration
        if it and it not in self.stamps:
            now = self.stamps[it] = time.monotonic()
            if it == self.open_at:
                self.t_open = now
            if it == self.trace_from:
                self.tracer.start()
            elif it == self.trace_to:
                self.tracer.stop()
        if self.t_open is None or self.tracer.running:
            return False
        return time.monotonic() >= self.t_open + self.seconds


def read_loss_scalars(summary_dir: str) -> Dict[int, float]:
    """{iteration: loss} from the event files the program's TrainSummary
    wrote (TFRecord framing: u64 length, u32 crc, payload, u32 crc)."""
    from tensorboardX.proto import event_pb2

    out: Dict[int, float] = {}
    for path in sorted(glob.glob(os.path.join(summary_dir,
                                              "events.out.tfevents.*"))):
        with open(path, "rb") as f:
            data = f.read()
        pos = 0
        while pos + 12 <= len(data):
            (n,) = struct.unpack_from("<Q", data, pos)
            event = event_pb2.Event.FromString(data[pos + 12:pos + 12 + n])
            pos += 12 + n + 4
            for v in event.summary.value:
                if v.tag == "Loss" and v.HasField("simple_value"):
                    out[int(event.step)] = float(v.simple_value)
    return out


def leaves64(tree) -> List[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64).ravel()
            for x in jax.tree_util.tree_leaves(tree)]


def norm_gaps(got: List[np.ndarray], want: List[np.ndarray]):
    """(program's norms, reference's norms, gaps) a leaf: the gap between
    the two sides' norms of a leaf, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    g = np.asarray([np.linalg.norm(x) for x in got])
    w = np.asarray([np.linalg.norm(x) for x in want])
    return g, w, np.abs(g - w) / np.maximum(w, np.median(w))


def median_difference(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    """The median leaf's norm of the difference against the reference's
    norm of that leaf: first order in the rounding of either side, and
    steady from seed to seed, where a gap of norms is second order."""
    return float(np.median([np.linalg.norm(a - b) / max(np.linalg.norm(b),
                                                        1e-30)
                            for a, b in zip(got, want)]))


def compare_training(got: Dict, want: Dict) -> Dict[str, float]:
    """Every number that can be compared, from two sides' {losses, grads
    (leaves), updates (leaves)}; the traffic mix's ``limits`` say which are
    held to a limit.  Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out of the update's gap of
    norms (they move by round-off alone)."""
    _, norms, grad_gaps = norm_gaps(got["grads"], want["grads"])
    moved = norms >= 1e-3 * np.median(norms)
    return {
        "loss_gap": float(max(abs(a - b) / abs(b) for a, b in
                              zip(got["losses"], want["losses"]))),
        "grad_norm_gap": float(grad_gaps.max()),
        "update_norm_gap": float(
            norm_gaps(got["updates"], want["updates"])[2][moved].max()),
        "grad_diff_median": median_difference(got["grads"], want["grads"])}


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str,
                 toy: Any = None):
        """``toy`` (tests only): an object with ``module`` (the flax module
        the program trains), ``weights(seed)`` and ``net(params, x, mode)``
        (the plain reference of that module) in place of SSD-VGG16."""
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.workdir, self.toy = workdir, toy
        self.res = int(config["resolution"])
        self.classes = int(config["num_classes"])
        self.state_after: Dict[int, Any] = {}
        # a fault planted by the tests: called with the Optimizer before
        # optimize(), may break the timed path underneath
        self.sabotage = None

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.data import native
        from analytics_zoo_tpu.models.ssd import (SSDVgg, build_priors,
                                                  ssd300_config,
                                                  ssd512_config)
        from analytics_zoo_tpu.ops.multibox_loss import (MultiBoxLoss,
                                                         MultiBoxLossParam)
        from analytics_zoo_tpu.parallel import (Optimizer, SGD, create_mesh,
                                                pipeline_specs)
        from analytics_zoo_tpu.parallel.optim import Plateau
        from analytics_zoo_tpu.parallel.summary import TrainSummary
        from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                     load_train_set_device)

        cfg, mix, a = self.config, self.traffic, self.config["assumed"]
        native.build()
        n_dev = len(jax.devices())
        self.batch = -(-int(mix["global_batch"]) // n_dev) * n_dev
        datagen.write_shapes_records(
            os.path.join(self.workdir, "shapes"), int(mix["images"]),
            self.res, int(mix["shards"]), self.seed,
            int(mix["max_shapes"]), int(mix["jpeg_quality"]))
        train_set, augment = load_train_set_device(
            os.path.join(self.workdir, "shapes-*.azr"),
            PreProcessParam(batch_size=self.batch, resolution=self.res,
                            canvas_size=a.get("canvas_size"),
                            worker_processes=int(mix.get(
                                "worker_processes", a["worker_processes"])),
                            loader_seed=self.seed % (2 ** 31)))
        self.feed = Feed(train_set, keep=int(mix["check_steps"]))

        if self.toy is None:
            module = SSDVgg(num_classes=self.classes, resolution=self.res)
            self.weights = ref.make_weights(self.seed, self.res, self.classes)
        else:
            module = self.toy.module
            self.weights = self.toy.weights(self.seed)
        self.weights0 = jax.tree_util.tree_map(np.asarray, self.weights)
        self.model = Model(module, {"params": self.weights})

        # the assembly of pipelines/ssd.py::train_ssd's run(), verbatim
        mesh = create_mesh()
        specs = pipeline_specs("ssd", mesh=mesh, tp=None,
                               resolution=self.res)
        priors, variances = build_priors(
            ssd300_config() if self.res == 300 else ssd512_config())
        criterion = MultiBoxLoss(priors, variances,
                                 MultiBoxLossParam(n_classes=self.classes))
        opt_cfg = cfg["optimizer"]
        self.lr = float(mix.get("learning_rate", a["learning_rate"]))
        self.summary = TrainSummary(os.path.join(self.workdir, "tb"), "bench")
        self.opt = (Optimizer(self.model, self.feed, criterion, specs=specs,
                              skip_loss_above=float(cfg["skip_loss_above"]),
                              compute_dtype=cfg["compute_dtype"],
                              prefetch=int(a["prefetch"]),
                              device_transform=augment)
                    .set_optim_method(SGD(
                        self.lr, momentum=float(opt_cfg["momentum"]),
                        weight_decay=float(opt_cfg["weight_decay"]),
                        plateau=Plateau(monitor="score", factor=0.5,
                                        patience=10, mode="max",
                                        min_lr=1e-5)))
                    .set_train_summary(self.summary)
                    .set_epoch_hook(self._epoch_hook))
        self.mesh_shape = dict(mesh.shape)

    def _epoch_hook(self, loop, state) -> None:
        """State after step 1 (the momentum buffer is the first gradient
        as the optimizer got it, plus the decay term) and after step 3,
        copied to the host before the next step donates it."""
        import jax

        if loop.iteration == 1:
            traces = [leaf for path, leaf in
                      jax.tree_util.tree_leaves_with_path(state.opt_state)
                      if "trace" in jax.tree_util.keystr(path)]
            self.state_after[1] = [np.asarray(x) for x in traces]
        elif loop.iteration == int(self.traffic["check_steps"]):
            self.state_after[loop.iteration] = jax.tree_util.tree_map(
                np.asarray, state.params)

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, tracer) -> Dict:
        mix = self.traffic
        open_at = int(mix["check_steps"]) + int(mix["warm_steps"])
        self.trigger = WindowTrigger(
            open_at, seconds, tracer,
            open_at + int(mix["trace_after_steps"]), int(mix["trace_steps"]))
        self.opt.set_end_when(self.trigger)
        if self.sabotage is not None:
            self.sabotage(self)
        try:
            self.opt.optimize()
        finally:
            self.summary.close()
        trig = self.trigger
        t_open, t_close = trig.t_open, trig.t_open + seconds
        inside = sorted(t for i, t in trig.stamps.items()
                        if i > open_at and t <= t_close)
        self.losses = read_loss_scalars(self.summary.log_dir)
        window_steps = [i for i, t in trig.stamps.items()
                        if i > open_at and t <= t_close]
        bad = [i for i in window_steps
               if not np.isfinite(self.losses.get(i, np.nan))
               or self.losses[i] > float(self.config["skip_loss_above"])]
        waits = [w for t, w in self.feed.waits if t_open < t <= t_close]
        traced = [i for i in trig.stamps
                  if trig.trace_from < i <= trig.trace_to]
        return {
            "t_open": t_open,
            "attempted": len(window_steps), "failed": len(bad),
            "end_to_end": {"train_throughput":
                           len(inside) * self.batch
                           / (inside[-1] - t_open) if inside else 0.0},
            "steps": len(inside), "batch": self.batch,
            "resolution": self.res, "num_classes": self.classes,
            "traced_steps": len(traced),
            "loader_waits_s": waits,
            "counters": {"steps": len(inside)},
        }

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        self.opt = self.model = self.summary = None

    # -- correct -----------------------------------------------------------
    def program_readings(self) -> Dict:
        import jax

        k = int(self.traffic["check_steps"])
        wd = float(self.config["optimizer"]["weight_decay"])
        p0 = jax.tree_util.tree_leaves(self.weights0)
        grads = [t - wd * p for t, p in zip(self.state_after[1], p0)]
        p3 = jax.tree_util.tree_leaves(self.state_after[k])
        return {"losses": [self.losses[i] for i in range(1, k + 1)],
                "grads": leaves64(grads),
                "updates": leaves64([a - b for a, b in zip(p3, p0)])}

    def reference_readings(self, mode: str = "f32",
                           leave_out_half: bool = False) -> Dict:
        import jax

        opt_cfg = self.config["optimizer"]
        net = (self.toy.net if self.toy is not None
               else ref.vgg_net(self.res, self.classes))
        p0 = jax.tree_util.tree_map(jax.numpy.asarray, self.weights0)
        losses, g1, p3 = ref.train_steps(
            p0, self.feed.kept, self.res, net, self.lr,
            float(opt_cfg["momentum"]), float(opt_cfg["weight_decay"]),
            float(self.config["skip_loss_above"]), mode=mode,
            block=int(self.traffic.get("reference_block", 16)),
            leave_out_half=leave_out_half)
        return {"losses": losses, "grads": leaves64(g1),
                "updates": leaves64(jax.tree_util.tree_map(
                    lambda a, b: np.asarray(a) - b, p3, self.weights0))}

    def check(self) -> Dict[str, Dict[str, float]]:
        import sys

        import jax

        self.want = self.reference_readings()
        got = self.program_readings()
        self.numbers = compare_training(got, self.want)
        # what a reader of a failed run needs: both sides' losses and the
        # leaves whose norms lie farthest apart
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_leaves_with_path(self.weights0)]
        print(f"losses program {got['losses']} reference "
              f"{self.want['losses']}", file=sys.stderr)
        for key in ("grads", "updates"):
            g, w, gap = norm_gaps(got[key], self.want[key])
            worst = np.argsort(-gap)[:3]
            print(f"{key}: median norm {np.median(w):.3g}; widest gaps "
                  + "; ".join(f"{names[i]} program {g[i]:.3g} reference "
                              f"{w[i]:.3g}" for i in worst), file=sys.stderr)
        return {k: {"value": self.numbers[k], "limit": float(limit)}
                for k, limit in self.traffic["limits"].items()}

    def control_readings(self) -> Dict[str, Dict[str, float]]:
        """After ``check()``: the same numbers for the reference put in
        the program's place — in the precision the configuration states
        (a second witness of the lower reading), one precision down (the
        control, which has to fail) and with half of the batch left out
        (a fault, which has to fail)."""
        return {
            "reference_bf16": compare_training(
                self.reference_readings("bf16"), self.want),
            "control_int8": compare_training(
                self.reference_readings("int8"), self.want),
            "fault_half_batch": compare_training(
                self.reference_readings("f32", leave_out_half=True),
                self.want)}
