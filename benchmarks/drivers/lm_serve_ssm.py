"""Serving of a decoder LM whose every block is a state-space mixer and
grouped-query attention in parallel (configs/falcon-h1-34b-pp12.json)
through ``ServingRuntime`` SESSIONS: ``drivers/lm_serve_gqa.py``'s driver
— its traffic, set-up, window, keys handed to the readers and stage table
on stderr — with ``reference/lm_ssm.py`` as the plain reference
(benchmarks/README_lm_ssm.md).

The model makes no discrete choice, so the reference follows nothing.
What it has instead is a state that nothing can recompute: ``check()``
compares, beside ``logits_rel_rms`` and ``logits_max_gap`` at
``check_steps`` of the window's first steps, the compared sessions'
recurrent states of every layer AS THEY STAND WHEN THE WINDOW HAS CLOSED
(``tier.state_of``: context and every decoded token through chunked
prefill and the in-place decode kernel) against the reference's states
after the same tokens: ``state_rel_rms``, the rms of the difference over
the rms of the reference's with everything pooled, and
``state_layer0_worst_head``, the same ratio of the FIRST layer's states a
head, the worst head's (``state_numbers`` says what the second is for: it
is the number a state kept in bfloat16 fails).
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.drivers import lm_serve_gqa
from benchmarks.drivers.lm_serve import compare_logits
from benchmarks.reference import lm_ssm as ref

#: ``control_readings``: the reference in another arithmetic, or with a
#: fault planted, put in the program's place: name -> (mode, fault)
CONTROLS = dict(
    {"reference_bf16": ("bf16", None), "control_int8": ("int8", None)},
    **{f"fault_{f}": ("f32", f) for f in ref.FAULTS})


def state_numbers(got: List[List[np.ndarray]],
                  want: List[List[np.ndarray]]) -> Dict[str, float]:
    """Sessions' layers' states (heads, head, state) against the
    reference's.  ``state_rel_rms``: the rms of the difference over the rms
    of the reference's, sessions, layers and heads pooled.
    ``state_layer0_worst_head``: the same ratio A HEAD of the first layer
    (its sessions pooled), the worst head's.  Why the second: pooled, a
    state kept in bfloat16 reads a third of what a sound program reads
    (0.5 % against 1.4 %, PERF.md section 2) — the bfloat16 activations
    that feed the state cost every head of a layer alike, 0.4 % in the
    first layer and 1.9 % in the sixth, while rounding the state costs the
    heads that forget fast (most of them) 0.2–0.5 % — and is lost under
    it.  The heads that forget slowly are where a rounded state drifts (a
    thousand roundings add up, a decay of less than half an ulp is lost
    altogether): 1.3–3.2 % on the worst of a layer's 32, which stands out
    only in the first layer, where the program's own error is smallest
    and the same on every head."""
    by_head = state_rel_by_head(got, want)
    return {"state_rel_rms": float(np.sqrt(
                by_head["diff"].sum() / by_head["norm"].sum())),
            "state_layer0_worst_head": float(by_head["rel"][0].max())}


def state_rel_by_head(got, want) -> Dict[str, np.ndarray]:
    """(layers, heads) arrays over the sessions pooled: the summed squares
    of the difference and of the reference, and their ratio's root."""
    got, want = ([np.stack(layers).astype(np.float64) for layers in side]
                 for side in (got, want))            # a session: (L, H, P, N)
    diff = sum(np.sum((g - w) ** 2, (-2, -1)) for g, w in zip(got, want))
    norm = sum(np.sum(w ** 2, (-2, -1)) for w in want)
    return {"diff": diff, "norm": norm, "rel": np.sqrt(diff / norm)}


def print_by_head(name: str, got, want) -> None:
    """A failed run's reader wants the heads: every layer's relative rms a
    head, in percent, on stderr."""
    for layer, row in enumerate(state_rel_by_head(got, want)["rel"]):
        print(f"state {name} layer {layer} % a head: "
              + " ".join(f"{100 * v:.2f}" for v in row), file=sys.stderr,
              flush=True)


class Driver(lm_serve_gqa.Driver):
    """``lm_serve_gqa.Driver`` (its set-up and window) over this model's
    reference, controls and state hand-over."""

    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str,
                 tier_args: Optional[Dict] = None, checked_only: bool = False,
                 controls=None):
        super().__init__(config, traffic, seed, workdir, tier_args,
                         checked_only, controls)
        if controls is None:
            self.controls = list(CONTROLS)

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        """``lm_serve_gqa.Driver.setup`` with this model's reference
        making the weights — that set-up reads its module's ``ref``, the
        accepted drivers have no other hook (PERF.md section 7), so the
        name stands for this model's while it runs — then nothing to
        follow, and the compared sessions' states asked for."""
        theirs, lm_serve_gqa.ref = lm_serve_gqa.ref, ref
        try:
            super().setup()
        finally:
            lm_serve_gqa.ref = theirs
        self.tiers[0].record_choices(())
        # kept even if a compared session leaves before the window closes
        self.tiers[0].record_state(self.sids[c] for c in self.checked)

    def compile_reference(self) -> List:
        """The reference's forward with zeros for weights and tokens, over
        every padded length a compared session can have when the window
        has closed (its context and as many decoded tokens as the window
        makes steps, which is not known yet: from the last compared step
        to the ``max_len − ctx_max`` the cache has room for), every
        jitted function handing its program to the pool and running
        nothing (``lm_serve.Driver.compile_reference``).  → the pool's
        jobs."""
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
        max_len = int(self.traffic["max_len"])
        room = max_len - int(self.traffic["ctx_max"])
        shapes = {}
        for c in sorted(self.checked):
            n = int(self.lengths[c])
            for blocks in range(-(-(n + max(self.steps) + 1) // rows),
                                -(-min(max_len, n + room) // rows) + 1):
                shapes[blocks] = min(blocks * rows, max_len)
        sessions = [dict(tokens=np.zeros(n, np.int32),
                         keep=[n - 1 - s for s in self.steps])
                    for n in shapes.values()]
        with ref.compile_only(jax.devices()[0], self.pool.submit) as done:
            ref.forward_many(
                cfg, seed, sessions,
                blocks=self.traffic.get("reference_blocks"),
                weights={"layers": Layers(),
                         "ends": zeros(lambda: ref.end_weights(seed, cfg))})
        return [job for _, job in done.values()]

    def free(self) -> None:
        """Fetch the compared sessions' recurrent states as the window
        left them, then ``lm_serve.Driver.free``."""
        t0 = time.monotonic()
        self.states = {c: self.tiers[0].state_of(self.sids[c])
                       for c in self.checked}
        print(f"states of {len(self.states)} sessions handed over in "
              f"{time.monotonic() - t0:.2f} s", file=sys.stderr, flush=True)
        super().free()

    # -- correct -----------------------------------------------------------
    def session_tokens(self, caller: int) -> np.ndarray:
        """Context plus EVERY id the window decoded: the states are the
        window's last."""
        return np.concatenate([self.context[caller],
                               np.asarray(self.decoded[caller], np.int32)])

    def reference_rows(self, callers: List[int], mode: str = "f32",
                       fault: Optional[str] = None) -> Dict[int, Dict]:
        """{caller: {"logits": the reference's at the caller's compared
        steps, in step order, "state": its states after the session's last
        token, a layer each}}."""
        callers = [c for c in callers if self.checked[c]]
        t0 = time.monotonic()
        res = ref.forward_many(
            self.config, self.wseed,
            [dict(tokens=self.session_tokens(c),
                  keep=[len(self.context[c]) + s
                        for s in sorted(self.checked[c])])
             for c in callers],
            mode=mode, fault=fault,
            blocks=self.traffic.get("reference_blocks"))
        out = {c: {"logits": np.asarray(r["logits"]),
                   "state": [np.asarray(s) for s in r["state"]]}
               for c, r in zip(callers, res)}
        print(f"reference {mode} {fault or ''}: sessions of "
              f"{[len(self.session_tokens(c)) for c in callers]} tokens in "
              f"{time.monotonic() - t0:.1f} s", file=sys.stderr, flush=True)
        return out

    @staticmethod
    def readings(rows: Dict[int, np.ndarray],
                 states: Dict[int, List[np.ndarray]],
                 want: Dict[int, Dict]) -> Dict[str, float]:
        """The compared numbers over the sessions of ``want``: ``rows``
        against its logits, ``states`` against its states."""
        keys = sorted(want)
        out = compare_logits(
            np.concatenate([rows[c] for c in keys]),
            np.concatenate([want[c]["logits"] for c in keys]))
        out.update(state_numbers([states[c] for c in keys],
                                 [want[c]["state"] for c in keys]))
        return out

    def check(self) -> Dict[str, Dict[str, float]]:
        compared = sorted(c for c in self.checked if self.checked[c])
        self.want = self.reference_rows(compared)
        got = self.window_rows()
        self.numbers = self.readings(got, self.states, self.want)
        for c in sorted(self.want):         # what a failed run's reader needs
            one = self.readings(got, self.states, {c: self.want[c]})
            print(f"session {c} of {int(self.lengths[c])} tokens and "
                  f"{len(self.decoded[c])} decoded, steps "
                  f"{sorted(self.checked[c])}: "
                  + " ".join(f"{k} {v:.4g}" for k, v in one.items()),
                  file=sys.stderr, flush=True)
        print_by_head("program", [self.states[c] for c in sorted(self.want)],
                      [self.want[c]["state"] for c in sorted(self.want)])
        self.numbers["compared_rows"] = float(
            sum(len(v["logits"]) for v in self.want.values()))
        self.numbers["compared_tokens"] = float(
            sum(len(self.session_tokens(c)) for c in self.want))
        return {k: {"value": self.numbers[k], "limit": float(limit)}
                for k, limit in self.traffic["limits"].items()}

    def control_readings(self) -> Dict[str, Dict[str, float]]:
        """After ``check()``: the same numbers for the reference put in
        the program's place — in the precision the configuration states
        (a second witness of the lower reading), one precision down (the
        control, which has to fail), and in float32 with a fault planted —
        over the longest and the shortest of the compared sessions."""
        by_len = sorted(self.want, key=lambda c: self.lengths[c])
        subset = sorted({by_len[0], by_len[-1]})
        want = {c: self.want[c] for c in subset}
        out = {"sessions": [len(self.session_tokens(c)) for c in subset]}
        for name in self.controls:
            mode, fault = CONTROLS[name]
            if fault == "truncate" and "control_truncate" in self.traffic:
                fault = f"truncate:{int(self.traffic['control_truncate'])}"
            res = self.reference_rows(subset, mode, fault)
            print_by_head(name, [res[c]["state"] for c in subset],
                          [want[c]["state"] for c in subset])
            out[name] = self.readings(
                {c: r["logits"] for c, r in res.items()},
                {c: r["state"] for c, r in res.items()}, want)
        out["program_subset"] = self.readings(self.window_rows(),
                                              self.states, want)
        return out
