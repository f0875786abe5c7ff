"""Serving of a decoder LM of grouped-query attention — global layers over
the WHOLE context out of a paged pool, window layers with learned sinks
out of rings (configs/mimo-v25-ep16.json) — through ``ServingRuntime``
SESSIONS: ``drivers/lm_serve.py``'s driver as ``lm_serve_mla.py`` is — the
same traffic, set-up, window, keys handed to the readers and stage table
on stderr — with ``reference/lm_gqa.py`` as the plain reference
(benchmarks/README_lm_gqa.md).

As in ``lm_serve_mla.py`` the model selects nothing: the only discrete
choice the reference FOLLOWS is which experts each token was routed to,
and ``check()`` compares ``logits_rel_rms``, ``logits_max_gap``,
``route_miss`` and ``choices_missing``; the controls are this model's
(``CONTROLS``).
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import datagen
from benchmarks.drivers import lm_serve, lm_serve_mla
from benchmarks.drivers.lm_serve import MODEL, draw_lengths
from benchmarks.drivers.ssd_serve import BenchClock
from benchmarks.reference import lm_gqa as ref

#: ``control_readings``: the reference in another arithmetic, or with a
#: fault planted, put in the program's place: name -> (mode, fault)
CONTROLS = {"reference_bf16": ("bf16", None), "control_int8": ("int8", None),
            "fault_truncate": ("f32", "truncate"),
            "fault_shift_cache": ("f32", "shift_cache"),
            "fault_no_sink": ("f32", "no_sink"),
            "fault_window_129": ("f32", "window_129"),
            "fault_full_rotary": ("f32", "full_rotary"),
            "fault_swap_theta": ("f32", "swap_theta"),
            "fault_no_value_scale": ("f32", "no_value_scale"),
            "fault_drop_expert": ("f32", "drop_expert")}


class Driver(lm_serve_mla.Driver):
    """``lm_serve_mla.Driver`` (its window and its readings) over this
    model's reference and controls."""

    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str,
                 tier_args: Optional[Dict] = None, checked_only: bool = False,
                 controls=None):
        lm_serve.Driver.__init__(self, config, traffic, seed, workdir,
                                 tier_args, checked_only, controls=[])
        if isinstance(controls, dict):
            controls = controls.get(str(self.seed), ())
        self.controls = list(CONTROLS if controls is None else controls)

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        """``lm_serve.Driver.setup`` with this model's reference making
        the weights."""
        import jax

        from analytics_zoo_tpu.obs.registry import MetricRegistry
        from analytics_zoo_tpu.pipelines.lm import (lm_serving_tiers,
                                                    make_lm_model)
        from analytics_zoo_tpu.serving import ServingRuntime
        from analytics_zoo_tpu.serving.runtime import ModelConfig

        cfg, mix = self.config, self.traffic
        marks = [("start", time.monotonic())]
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
        # while the chip is still empty (lm_serve.py says why)
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
        self.tiers[0].record_choices(self.sids[c] for c in self.checked)
        self.prefill()
        mark("prefill")
        self.setup_parts = {name: t - before for (name, t), (_, before)
                            in zip(marks[1:], marks)}
        print("set-up: " + ", ".join(f"{k} {v:.1f} s" for k, v
                                     in self.setup_parts.items())
              + f" ({self.prefill_tokens} tokens prefilled)",
              file=sys.stderr, flush=True)

    def compile_reference(self) -> List:
        """The reference's forward over the compared sessions' padded
        lengths with zeros for weights, tokens and choices, every jitted
        function handing its program to the pool and running nothing
        (``lm_serve.Driver.compile_reference``).  → the pool's jobs."""
        import jax
        import jax.numpy as jnp

        cfg, seed = self.config, self.wseed

        def zeros(make):
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(make))

        class Layers:                       # a layer's zeros when asked for
            def __getitem__(self, i):
                return zeros(lambda: ref.layer_weights(seed, cfg, i))

        rows = int(dict(ref.BLOCKS, **(self.traffic.get("reference_blocks")
                                       or {}))["pad_to"])
        shapes = {}
        for c in sorted(self.checked):
            n = int(self.lengths[c]) + max(self.steps) + 1
            shapes[-(-n // rows)] = (c, n)
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
        """The reference's ``follow`` for a session of ``n`` tokens with
        nothing chosen: nothing is selected in this model, {MoE layer:
        (n, k) expert ids} routed."""
        D = ref.dims(self.config)
        return {"selected": {},
                "routed": {i: np.zeros((n, D["per_tok"]), np.int32)
                           for i in range(D["dense_layers"], D["layers"])}}

    # -- correct -----------------------------------------------------------
    def reference_rows(self, callers: List[int], mode: str = "f32",
                       fault: Optional[str] = None) -> Dict[int, Dict]:
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

    def control_readings(self) -> Dict[str, Dict[str, float]]:
        """``lm_serve_mla.Driver.control_readings`` over this model's
        ``CONTROLS``: the reference put in the program's place and handed
        the program's routed experts — in the precision the configuration
        states, one precision down (the control, which has to fail), and
        in float32 with a fault planted — over the longest and the
        shortest of the compared sessions.  The expert left out is the
        held one that the compared rows were routed to most often."""
        by_len = sorted(self.want, key=lambda c: self.lengths[c])
        subset = sorted({by_len[0], by_len[-1]})
        want = {c: self.want[c] for c in subset}
        out = {"sessions": [int(self.lengths[c]) for c in subset]}
        D = ref.dims(self.config)
        routed = np.concatenate([
            r[len(self.context[c]):].ravel() - D["first_held"]
            for c in subset for r in self.follows[c]["routed"].values()])
        held = routed[(routed >= 0) & (routed < D["held"])]
        busiest = int(np.bincount(held).argmax()) if len(held) else 0
        for name in self.controls:
            mode, fault = CONTROLS[name]
            if fault == "drop_expert":
                fault = f"drop_expert:{busiest}"
            if fault == "truncate" and "control_truncate" in self.traffic:
                fault = f"truncate:{int(self.traffic['control_truncate'])}"
            res = self.reference_rows(subset, mode, fault)
            out[name] = self.readings(
                {c: r["logits"] for c, r in res.items()}, want, res)
        out["program_subset"] = self.readings(self.window_rows(), want, want)
        return out
