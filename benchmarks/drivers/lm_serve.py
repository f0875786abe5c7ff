"""Decoder-LM serving through ``ServingRuntime`` SESSIONS:
``lm_serving_tiers`` as shipped, one replica, the real clock, ``warm()``
before any traffic; the driver calls ``open_session`` / ``submit_chunk`` /
``pump`` and nothing below them (benchmarks/README_lm.md).

The traffic mix is a closed loop of ``sessions`` callers, one session
each.  In SET-UP every session's context — its length drawn from the seed
log-uniformly between ``ctx_min`` and ``ctx_max``, the draw redone until
the sum is within ``ctx_sum_tolerance`` of ``ctx_sum`` — goes in through
``submit_chunk`` in chunks of up to ``prefill_chunk`` tokens, all sessions
interleaved.  The WINDOW is decode only: every caller submits one token
id, drawn from the seed, when its last chunk is answered, so each pump
assembles one batch of up to ``max_batch`` one-token rows.  ``pump`` is
called with ``force=True``: in a closed loop nobody else can arrive, so a
bucket that is not full has nothing to wait for.

``check()`` compares the logits the window itself returned, at
``check_steps`` of its first ``check_steps_below`` steps for
``check_sessions`` sessions drawn from the seed (the shortest, which is
under ``check_short_below`` tokens; the shortest of those over
``check_long_above``; the others from those in between), with
``reference/lm.py``'s forward over the session's context plus the decoded
ids — the reference FOLLOWING the program's discrete choices (which
positions each query of a full layer selected, which experts each token
was routed to; the tier records them for the compared sessions:
``tier.record_choices``).  Past ``index_topk`` tokens a rounding flips
members of those sets, and with seeded random weights each flip moves the
logits as much as a fault does; on equal sets the two sides differ by
rounding alone.  The choices themselves are held to limits of their own:
``select_miss`` and ``route_miss``, the share of selected positions and of
routed (token, expert) pairs on which the reference's own choices, made
from the same followed state, differ from the program's.
"""

from __future__ import annotations

import gc
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import datagen
from benchmarks.drivers.ssd_serve import BenchClock, percentile
from benchmarks.reference import lm as ref

MODEL = "lm"
#: ``control_readings``: the reference in another arithmetic, or with a
#: fault planted, put in the program's place: name -> (mode, fault)
CONTROLS = {"reference_bf16": ("bf16", None), "control_int8": ("int8", None),
            "fault_no_select": ("f32", "no_select"),
            "fault_drop_expert": ("f32", "drop_expert"),
            "fault_shift_cache": ("f32", "shift_cache")}


def draw_lengths(rng, mix: Dict) -> np.ndarray:
    """The sessions' context lengths: log-uniform, redrawn until the sum
    is within tolerance and there is a session for each end of the check."""
    lo, hi = float(mix["ctx_min"]), float(mix["ctx_max"])
    want, tol = float(mix["ctx_sum"]), float(mix["ctx_sum_tolerance"])
    for _ in range(10000):
        n = np.exp(rng.uniform(np.log(lo), np.log(hi),
                               int(mix["sessions"]))).astype(np.int64)
        if abs(n.sum() - want) <= tol * want \
                and (n < int(mix["check_short_below"])).any() \
                and (n > int(mix["check_long_above"])).any():
            return n
    raise RuntimeError("no draw of context lengths met the mix's sum")


def compare_logits(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """Rows of logits against the reference's: the relative rms of the
    difference over all rows, and the widest single gap in units of the
    reference row's rms."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = got - want
    scale = np.sqrt(np.mean(want ** 2, 1, keepdims=True))
    return {"logits_rel_rms": float(np.sqrt(np.sum(diff ** 2)
                                            / np.sum(want ** 2))),
            "logits_max_gap": float(np.max(np.abs(diff) / scale))}


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str,
                 tier_args: Optional[Dict] = None, checked_only: bool = False,
                 controls=None):
        """``tier_args`` (tests only): extra arguments of
        ``lm_serving_tiers`` (a toy cache geometry).  ``checked_only``
        (``control.py``'s way to many seeds in one call): only the
        compared sessions are opened, prefilled and decoded — their rows
        run the same programs at the same shapes, the other rows of a
        batch are padding.  ``controls``: which of ``CONTROLS``
        ``control_readings`` reads (default: all) — a list, or {seed: list}
        for a call of many seeds (a seed it does not name reads none)."""
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.workdir, self.tier_args = workdir, dict(tier_args or {})
        self.checked_only = bool(checked_only)
        if isinstance(controls, dict):
            controls = controls.get(str(self.seed), ())
        self.controls = list(CONTROLS if controls is None else controls)
        # a fault planted by the tests: called with the driver after
        # set-up has built the model, may break the timed path underneath
        self.sabotage = None

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from analytics_zoo_tpu.obs.registry import MetricRegistry
        from analytics_zoo_tpu.pipelines.lm import (lm_serving_tiers,
                                                    make_lm_model)
        from analytics_zoo_tpu.serving import ServingRuntime
        from analytics_zoo_tpu.serving.runtime import ModelConfig

        cfg, mix = self.config, self.traffic
        t_setup = time.monotonic()
        marks = [("start", t_setup)]
        mark = lambda name: marks.append((name, time.monotonic()))  # noqa
        self.wseed = int(cfg["assumed"].get("weights_seed", self.seed))
        D = ref.dims(cfg)
        rng = np.random.RandomState(datagen.numpy_seed(self.seed))
        self.lengths = draw_lengths(rng, mix)
        self.context = [rng.randint(0, D["vocab"], size=int(n)).astype(
            np.int32) for n in self.lengths]
        self.decoded: List[List[int]] = [[] for _ in self.lengths]
        self.token_rng = np.random.RandomState(
            datagen.numpy_seed(self.seed + 2))
        self.pick_checked()
        self.max_batch = int(mix["max_batch"])
        tier_args = dict(
            cache_tokens=int(mix["cache_tokens"]),
            max_sessions=int(mix["sessions"]), max_batch=self.max_batch,
            page=int(mix["page"]), max_len=int(mix["max_len"]))
        tier_args.update(self.tier_args)
        mark("traffic")
        ahead = self.compile_ahead(tier_args)
        # while the chip is still empty: the reference's forward over zeros
        # holds gigabytes for a moment (beside the weights it ran out of
        # the chip's memory, PR 28)
        self.reference_jobs = self.compile_reference()
        mark("reference_ahead")
        params = {"layers": [ref.layer_weights(self.wseed, cfg, i)
                             for i in range(D["layers"])],
                  "ends": ref.end_weights(self.wseed, cfg)}
        if cfg.get("compute_dtype") == "float32":      # the tests' toy
            params = jax.tree_util.tree_map(
                lambda a: a.astype(np.float32), params)
        jax.block_until_ready(params)
        mark("weights")
        self.model = model = make_lm_model(cfg, params=params)
        self.registry = MetricRegistry()
        self.tiers = lm_serving_tiers(model, registry=self.registry,
                                      **tier_args)
        if self.sabotage is not None:
            self.sabotage(self)
        mc = ModelConfig(
            name=MODEL, streaming=True, serial_chunks=True,
            tiers=self.tiers, tier_factory=lambda rid: self.tiers,
            pad_key="input", length_key="n_tokens",
            bucket_edges=[int(e) for e in mix["bucket_edges"]],
            max_batch=self.max_batch,
            chunk_deadline_s=float(mix["deadline_s"]))
        self.runtime = rt = ServingRuntime(
            models=[mc], n_replicas=1, max_batch=self.max_batch,
            queue_capacity=int(mix["queue_capacity"]),
            default_deadline_s=float(mix["deadline_s"]), clock=BenchClock(),
            wedge_timeout_s=float(mix["wedge_timeout_s"]),
            retain_requests=False)
        mark("runtime")
        for program in ahead:
            program.result()
        mark("programs_ahead")
        t0 = time.monotonic()
        rt.warm({"input": np.zeros(1, np.int32)}, model=MODEL)
        self.warm_s = time.monotonic() - t0
        mark("warm")
        self.callers = sorted(self.checked) if self.checked_only \
            else list(range(len(self.lengths)))
        self.sids = {c: rt.open_session(MODEL) for c in self.callers}
        # the compared sessions' choices are kept from their first chunk
        # to their last compared step
        self.tiers[0].record_choices(self.sids[c] for c in self.checked)
        self.prefill()
        mark("prefill")
        self.setup_parts = {name: t - before for (name, t), (_, before)
                            in zip(marks[1:], marks)}
        print("set-up: " + ", ".join(f"{k} {v:.1f} s" for k, v
                                     in self.setup_parts.items())
              + f" ({self.prefill_tokens} tokens prefilled)",
              file=sys.stderr, flush=True)

    # -- compiling ahead ---------------------------------------------------
    def compile_ahead(self, tier_args: Dict) -> List:
        """On threads, from shapes alone, while set-up makes the weights
        and prefills: the step program of every bucket edge (returned as
        futures: ``warm()`` waits for them and then finds each in JAX's
        compilation cache, three compiled side by side); the pool also
        takes the reference's programs (:meth:`compile_reference`).  With a
        cold compilation cache a run compiled for 48 s in ``warm()`` and
        for some 500 s in ``check()`` (PR 28); with a warm one each thread
        finds its programs there."""
        from concurrent.futures import ThreadPoolExecutor

        from analytics_zoo_tpu.models import lm
        from analytics_zoo_tpu.pipelines.lm import LMModel, lm_serving_tiers

        cfg = lm.LMConfig.from_dict(self.config)
        tier = lm_serving_tiers(LMModel(cfg, lm.param_shapes(cfg)),
                                **tier_args)[0]

        def program(edge: int) -> None:
            fn, args, _ = tier.device_program_for(edge)()
            fn.lower(*args).compile()

        self.pool = ThreadPoolExecutor(max_workers=max(4, os.cpu_count() or 4),
                                       thread_name_prefix="compile-ahead")
        return [self.pool.submit(program, int(edge))
                for edge in self.traffic["bucket_edges"]]

    def compile_reference(self) -> List:
        """The reference's forward over the compared sessions' lengths
        with zeros for weights, tokens and choices, every jitted function
        handing its program to the pool to compile and running nothing
        (``ref.compile_only``).  What stands between them runs, on the
        chip, before anything else is on it: its few hundred small
        programs are then compiled for the check too (JAX keeps none under
        a second on disk, so the check compiled them in every run), and
        its arrays are gone before the weights are made — on the host's
        CPU the same copies took minutes (PR 28).  The check waits for the
        pool's jobs.  → those jobs."""
        import jax
        import jax.numpy as jnp

        cfg, seed = self.config, self.wseed

        def zeros(make):
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(make))

        class Layers:                       # a layer's zeros when asked for
            def __getitem__(self, i):
                return zeros(lambda: ref.layer_weights(seed, cfg, i))

        # one session of each shape the compared ones give the programs:
        # the padded length, and how many of the compared rows are past
        # index_topk (their own selection is made in one call); its tokens
        # routed evenly over the router's width
        rows = int(dict(ref.BLOCKS, **(self.traffic.get("reference_blocks")
                                       or {}))["pad_to"])
        shapes = {}
        for c in sorted(self.checked):
            n = int(self.lengths[c]) + max(self.steps) + 1
            past = sum(int(self.lengths[c]) + s >= int(cfg["index_topk"])
                       for s in self.steps)
            shapes[-(-n // rows), past] = (c, n)
        sessions = []
        for c, n in shapes.values():
            follow = self.empty_follow(n)
            for routed in follow["routed"].values():
                routed += (np.arange(routed.size).reshape(routed.shape)
                           % int(cfg["expert_share"]["published_experts"]))
            sessions.append(dict(
                tokens=np.zeros(n, np.int32), follow=follow,
                keep=[int(self.lengths[c]) + s for s in self.steps]))
        with ref.compile_only(jax.devices()[0], self.pool.submit) as done:
            ref.forward_many(
                cfg, seed, sessions,
                blocks=self.traffic.get("reference_blocks"),
                weights={"layers": Layers(),
                         "ends": zeros(lambda: ref.end_weights(seed, cfg))})
        return [job for _, job in done.values()]

    def empty_follow(self, n: int) -> Dict:
        """``ref.forward``'s ``follow`` for a session of ``n`` tokens with
        nothing chosen: {"selected": {full layer: bit-packed rows},
        "routed": {MoE layer: (n, k) expert ids}}."""
        cfg = self.config
        kinds = cfg["layer_types"][:int(cfg["num_hidden_layers"])]
        return {"selected": {i: np.zeros((n, -(-n // 8)), np.uint8)
                             for i, k in enumerate(kinds)
                             if k == "full_attention"},
                "routed": {i: np.zeros((n, int(cfg["num_experts_per_tok"])),
                                       np.int32)
                           for i in range(int(cfg["first_k_dense_replace"]),
                                          len(kinds))}}

    def pick_checked(self) -> None:
        """The compared sessions and steps, from the seed: the shortest
        session (under check_short_below tokens: with the steps it decodes
        it stays under index_topk, so nothing is selected for it); the
        shortest over check_long_above (the reference's cost grows with
        the square of the length); the others from those in between that
        are over check_short_below — something is selected for them — and
        under check_mid_below (the shortest others if there are too few:
        the reference pads a session to whole blocks of 8,192 rows, and
        one long session is most of a check's time as it is)."""
        mix = self.traffic
        rng = np.random.RandomState(datagen.numpy_seed(self.seed + 1))
        long_ = np.flatnonzero(self.lengths > int(mix["check_long_above"]))
        picked = [int(np.argmin(self.lengths)),
                  int(long_[np.argmin(self.lengths[long_])])]
        more = int(mix["check_sessions"]) - 2
        rest = sorted((c for c in range(len(self.lengths))
                       if c not in picked
                       and self.lengths[c] >= int(mix["check_short_below"])),
                      key=lambda c: self.lengths[c])
        mid = [c for c in rest
               if self.lengths[c] < int(mix.get("check_mid_below", 1 << 62))]
        mid = mid if len(mid) >= more else rest[:more]
        picked += [int(c) for c in rng.choice(
            mid, size=min(len(mid), more), replace=False)]
        self.steps = sorted({0} | {int(s) for s in rng.randint(
            1, int(mix["check_steps_below"]),
            size=int(mix["check_steps"]) - 1)})
        self.checked = {c: {} for c in picked}      # caller -> step -> row

    def submit(self, caller: int, ids: np.ndarray,
               deadline_s: Optional[float] = None):
        return self.runtime.submit_chunk(
            self.sids[caller], {"input": np.asarray(ids, np.int32)},
            length=len(ids), deadline_s=deadline_s)

    def prefill(self) -> None:
        """Every session's context in chunks, all sessions interleaved:
        each session sends its next chunk when its last is answered."""
        chunk = int(self.traffic["prefill_chunk"])
        # set-up's chunks wait for one another (a batch of 64 rows of
        # 2,048 tokens takes seconds): their deadline is set-up's own
        deadline = float(self.traffic["prefill_deadline_s"])
        sent = {c: 0 for c in self.callers}
        t0 = time.monotonic()
        while True:
            pending = []
            for c in self.callers:
                ids = self.context[c]
                if sent[c] < len(ids):
                    pending.append(self.submit(
                        c, ids[sent[c]:sent[c] + chunk], deadline))
                    sent[c] += chunk
            if not pending:
                break
            self.runtime.pump(force=True)
            bad = [r for r in pending if r.state != "done"]
            if bad:
                raise RuntimeError(f"prefill: {len(bad)} chunk(s) not "
                                   f"answered: {bad[0].state}")
        self.prefill_s = time.monotonic() - t0
        self.prefill_tokens = int(sum(self.lengths[c] for c in self.callers))

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, tracer) -> Dict:
        from analytics_zoo_tpu.resilience.errors import ServerOverloaded

        mix, rt = self.traffic, self.runtime
        steps = self.steps
        trace_from = int(mix["trace_after_steps"])
        trace_to = trace_from + int(mix["trace_steps"])
        vocab = int(self.config["vocab_size"])
        lat: List[float] = []
        done_t: List[float] = []
        failed = refused = capped = step = 0
        max_len = int(mix["max_len"])
        lengths_traced = None
        alive = list(self.callers)
        before = rt.snapshot()["metrics"]
        t_open = time.monotonic()
        t_close = t_open + seconds
        while alive and (time.monotonic() < t_close or tracer.running):
            if step == trace_from:
                lengths_traced = [len(self.context[c]) + len(self.decoded[c])
                                  + 1 for c in alive]
                tracer.start()
            if step == max(steps) + 1:
                self.tiers[0].record_choices(())
            ids = self.token_rng.randint(0, vocab, size=len(self.lengths))
            pending = []
            for c in list(alive):
                if len(self.context[c]) + len(self.decoded[c]) >= max_len:
                    alive.remove(c)         # its session is full: it stops
                    capped += 1
                    continue
                t = time.monotonic()
                try:
                    req = self.submit(c, ids[c:c + 1],
                                      float(mix["deadline_s"]))
                except ServerOverloaded:
                    refused += 1
                    continue
                self.decoded[c].append(int(ids[c]))
                pending.append((c, t, req))
            rt.pump(force=True)
            for c, t, req in pending:
                if req.state != "done":
                    failed += 1
                    alive.remove(c)         # its session is dead
                    continue
                lat.append(req.completed_t - t)
                done_t.append(req.completed_t)
                if c in self.checked and step in steps:
                    self.checked[c][step] = np.array(req.result, np.float32)
            step += 1
            if step == trace_to:
                tracer.stop()
        inside = [t for t in done_t if t <= t_close]
        print(f"window: {step} steps, {len(lat)} chunks answered, "
              f"{failed} failed, {capped} capped", file=sys.stderr, flush=True)
        snap = rt.snapshot()["metrics"]
        # the runtime's counters run from its start: set-up's prefill
        # batches (partial ones among them) are taken out again
        batches = snap["batches"] - before["batches"]
        fill = ((snap["mean_batch_fill"] * snap["batches"]
                 - before["mean_batch_fill"] * before["batches"])
                / batches) if batches else 0.0
        gauges = self.registry.snapshot()
        cfg = self.config
        if lengths_traced is None:
            lengths_traced = [len(self.context[c]) + len(self.decoded[c])
                              for c in self.callers]
        op_scopes = self.decode_scopes() if tracer.enabled else {}
        return {
            "t_open": t_open,
            "attempted": len(lat) + failed + refused,
            "failed": failed + refused,
            "end_to_end": {
                "serve_throughput": (len(inside) / (max(inside) - t_open)
                                     if inside else 0.0),
                "serve_latency_p95": (1e3 * percentile(lat, 0.95)
                                      if lat else 0.0)},
            "steps": step, "capped_callers": capped, "warm_s": self.warm_s,
            "prefill_s": self.prefill_s,
            "context_tokens": self.prefill_tokens,
            "lm": {"lengths": lengths_traced, "config": cfg,
                   "op_scopes": op_scopes},
            "counters": {"mean_batch_fill": fill, "batches": batches,
                         "shed_total": snap["shed_total"],
                         "failed": snap["failed"],
                         "lm": gauges},
        }

    def decode_scopes(self) -> Dict[str, List[str]]:
        """Which operations of the compiled decode step stand under which
        named scope, from the tier's audit hook (the program the runtime
        dispatches, compiled from shapes: a hit in the compile cache)."""
        from benchmarks import hlo_scopes

        fn, args, _ = self.tiers[0].device_program()
        return hlo_scopes.scope_map(fn.lower(*args).compile().as_text())

    def free(self) -> None:
        """Keep what the tier recorded of the compared sessions, then drop
        the program's state (weights and caches) before the reference
        runs."""
        self.choices = {c: self.tiers[0].choices.get(self.sids[c], [])
                        for c in self.checked}
        self.runtime = self.tiers = self.model = None
        gc.collect()
        t0 = time.monotonic()
        for job in self.reference_jobs:
            job.result()
        print(f"compiled ahead: {len(self.reference_jobs)} programs of the "
              f"reference, waited {time.monotonic() - t0:.1f} s",
              file=sys.stderr, flush=True)
        self.pool.shutdown()

    # -- correct -----------------------------------------------------------
    def session_tokens(self, caller: int) -> np.ndarray:
        """Context plus the decoded ids up to the last compared step."""
        n = max(self.checked[caller]) + 1
        return np.concatenate([self.context[caller],
                               np.asarray(self.decoded[caller][:n],
                                          np.int32)])

    def followed(self, caller: int) -> Dict:
        """What the tier recorded of one session, as the reference's
        ``follow``: a full layer's selected sets as bit-packed rows (a
        prefill call's as they came, a decode step's positions packed
        here), a MoE layer's routed experts."""
        n = len(self.session_tokens(caller))
        follow = self.empty_follow(n)
        cols = -(-n // 8)
        sets = list(follow["selected"].values())
        routed = list(follow["routed"].values())
        seen = np.zeros(n, bool)
        for start, count, chosen in self.choices[caller]:
            if start >= n:
                continue
            seen[start:start + count] = True
            for mine, sel in zip(sets, chosen["selected"]):
                if sel.dtype == np.uint8:           # a prefill call's rows
                    mine[start:start + count, :sel.shape[1]] = sel[:, :cols]
                else:                               # a decode step's row
                    bits = np.zeros(cols * 8, np.uint8)
                    bits[sel[0][sel[0] >= 0]] = 1
                    mine[start] = np.packbits(bits)
            for mine, r in zip(routed, chosen["routed"]):
                mine[start:start + count] = r
        # a token whose step recorded nothing (a program that stepped the
        # session at other positions than its tokens') attends to nothing
        # here: ``choices_missing`` counts them, and its limit is 0
        return dict(follow, missing=int((~seen).sum()), tokens=n)

    def reference_rows(self, callers: List[int], mode: str = "f32",
                       fault: Optional[str] = None) -> Dict[int, Dict]:
        """{caller: {"logits": the reference's at the caller's compared
        steps, in step order, "miss": ...}}, the reference following the
        program's choices."""
        callers = [c for c in callers if self.checked[c]]
        t0 = time.monotonic()
        res = ref.forward_many(
            self.config, self.wseed,
            [dict(tokens=self.session_tokens(c), follow=self.follows[c],
                  keep=[len(self.context[c]) + s
                        for s in sorted(self.checked[c])])
             for c in callers],
            mode=mode, fault=fault,
            blocks=self.traffic.get("reference_blocks"))
        print(f"reference {mode} {fault or ''}: sessions of "
              f"{[int(self.lengths[c]) for c in callers]} tokens in "
              f"{time.monotonic() - t0:.1f} s", file=sys.stderr, flush=True)
        return {c: {"logits": np.asarray(r["logits"]), "miss": r["miss"]}
                for c, r in zip(callers, res)}

    @staticmethod
    def readings(rows: Dict[int, np.ndarray], want: Dict[int, Dict],
                 miss: Dict[int, Dict]) -> Dict[str, float]:
        """The compared numbers over the sessions of ``want``: ``rows``
        against its logits; ``miss`` as the reference counted it."""
        keys = sorted(want)
        out = compare_logits(
            np.concatenate([rows[c] for c in keys]),
            np.concatenate([want[c]["logits"] for c in keys]))
        for name, kind in (("select_miss", "select"), ("route_miss", "route")):
            differ, counted = (sum(miss[c]["miss"][kind][j] for c in keys)
                               for j in (0, 1))
            out[name] = differ / counted if counted else 0.0
        return out

    def window_rows(self) -> Dict[int, np.ndarray]:
        return {c: np.stack([rows[s] for s in sorted(rows)])
                for c, rows in self.checked.items() if rows}

    def check(self) -> Dict[str, Dict[str, float]]:
        self.follows = {c: self.followed(c) for c in self.checked
                        if self.checked[c]}
        self.want = self.reference_rows(sorted(self.follows))
        got = self.window_rows()
        self.numbers = self.readings(got, self.want, self.want)
        self.numbers["choices_missing"] = (
            sum(f["missing"] for f in self.follows.values())
            / sum(f["tokens"] for f in self.follows.values()))
        for c in sorted(self.want):         # what a failed run's reader needs
            one = self.readings(got, {c: self.want[c]}, self.want)
            print(f"session {c} of {int(self.lengths[c])} tokens, steps "
                  f"{sorted(self.checked[c])}: "
                  + " ".join(f"{k} {v:.4g}" for k, v in one.items()),
                  file=sys.stderr, flush=True)
        self.numbers["compared_rows"] = float(
            sum(len(v["logits"]) for v in self.want.values()))
        return {k: {"value": self.numbers[k], "limit": float(limit)}
                for k, limit in self.traffic["limits"].items()}

    def control_readings(self) -> Dict[str, Dict[str, float]]:
        """After ``check()``: the same numbers for the reference put in
        the program's place and handed the program's choices as the
        float32 reference was — in the precision the configuration states
        (a second witness of the lower reading), one precision down (the
        control, which has to fail), and in float32 with a fault planted
        (each has to fail).  ``select_miss`` and ``route_miss`` are then
        the control's own choices against the program's.  Over two of the
        compared sessions, to keep the cost down: the longest and the
        shortest.  The expert left out is the held one that the compared
        rows of those sessions were routed to most often."""
        by_len = sorted(self.want, key=lambda c: self.lengths[c])
        subset = sorted({by_len[0], by_len[-1]})
        want = {c: self.want[c] for c in subset}
        out = {"sessions": [int(self.lengths[c]) for c in subset]}
        first = int(self.config["expert_share"]["index"]) \
            * int(self.config["n_routed_experts"])
        routed = np.concatenate([
            r[len(self.context[c]):].ravel() - first
            for c in subset for r in self.follows[c]["routed"].values()])
        held = routed[(routed >= 0)
                      & (routed < int(self.config["n_routed_experts"]))]
        busiest = int(np.bincount(held).argmax()) if len(held) else 0
        for name in self.controls:
            mode, fault = CONTROLS[name]
            if fault == "drop_expert":
                fault = f"drop_expert:{busiest}"
            res = self.reference_rows(subset, mode, fault)
            out[name] = self.readings(
                {c: r["logits"] for c, r in res.items()}, want, res)
        out["program_subset"] = self.readings(self.window_rows(), want, want)
        return out
