"""Decoder-LM serving through ``ServingRuntime`` sessions, with the
sessions' caches ON THE DEVICE (ISSUE 28).

A session is a caller's token stream: ``open_session`` pins it to a
replica, every ``submit_chunk`` carries a run of token ids (a prefill
chunk of up to the largest bucket edge, or one decoded token), and the
answer to a chunk is the float32 logits over the vocabulary slice at the
chunk's last token.  ``lm_serving_tiers`` is the model's ``tier_factory``::

    model = make_lm_model(config, seed=0)
    factory = lambda rid: lm_serving_tiers(model, cache_tokens=1 << 20,
                                           max_sessions=64, max_batch=64)
    ModelConfig(name="lm", streaming=True, serial_chunks=True,
                tiers=factory(-1), tier_factory=factory,
                pad_key="input", length_key="n_tokens",
                bucket_edges=[1, 256, 2048], max_batch=64)

**Who owns what.**  One replica's tier instance owns one
:class:`SessionCache`: a paged pool for the full-attention layers' latents
and index keys and for the causal layers' latents, and a ring a session
for the sliding layers (ops/lm_attention.py) — of each only what the
model's kinds of layer need: a model of causal layers alone has pools and
nothing else; a grouped-query model's pools and rings hold keys and values
at their kind's width — the device arrays, and the host's books —
which pages a session holds, how long it is, which pages are free.  A
chunk is admitted before it runs (``az/lm/cache_admit``): its session gets
a slot on first sight and as many pages as its new length needs; a chunk
that does not fit (pool exhausted, session past ``max_len``) raises
:class:`CacheExhausted` for the whole batch.  ``evict_session`` gives the
session's pages and slot back to the pool — the runtime calls it for a
killed or closed session, the tier itself after a ``final`` chunk — and
nothing of the session is read again: a page's stale tail is masked by
its next owner's length, a ring's by its positions.

**A recurrent state a session.**  A model with a state-space mixer
(models/lm.py ``SSMDims``) keeps, beside the pages, a float32 state and
the convolution's last inputs a layer in the session's SLOT
(``cache["ssm"]``, ``cache["conv"]``: ``n_slots`` of each).  Unlike a ring
such a state is a running sum, valid by nothing but its history: the slot
of a new session has to start from zeros, a padding row or a padded
position must not advance it, and nothing can be rolled back.  The books
stay as they are — a slot's state dies with the slot — and the step
programs take a token at position 0 as the start of a session: its state
in is zeros whatever the slot held (counter ``lm/ssm_state_starts``), so
a recycled slot needs no zeroing program.  ``tier.record_state(ids)``
makes the tier keep those sessions' states when they leave;
``tier.state_of(id)`` hands over a session's states of every layer as
they stand (fetched when asked; of a recorded session that has left, as
they stood then).  Nothing is fetched for a session nobody asked about.

**One jitted call a batch.**  A batch at edge 1 (decode) is ONE call of
``decode_step`` over all ``max_batch`` rows, whatever sessions they belong
to.  A batch at a prefill edge is one call of ``prefill_step`` a row (a
row longer than ``PREFILL_BLOCK`` tokens: one a block): a chunk attends
to its own session's pages only, and the loop over them is as long as
that session, so rows are not padded to the longest.  Every (edge)
program compiles in ``ServingRuntime.warm``: the tier takes a row of
session −1 for padding (``ServingTier.pads_session_rows``), so a batch of
such rows runs each program once and touches no session.

**The steps' discrete choices.**  ``tier.record_choices(session_ids)``
makes the tier keep, from then on, what the steps chose for those
sessions' rows — the positions each full layer selected and the experts
each token was routed to — in ``tier.choices[session_id]``, a list of
``(position of the chunk's first token, tokens, {"selected": [a full
layer: (tokens, topk) positions, −1 where there are fewer, from a decode
step; uint8 (tokens, ceil(end / 8)) bit-packed rows from a prefill call],
"routed": (MoE layers, tokens, k)})`` in the order the chunks ran
(``selected`` is empty for a model without full layers: nothing is
selected, ``routed`` is all there is to record).  A
comparison with another implementation needs them (past ``index_topk``
tokens a rounding flips members of the sets: benchmarks/reference/lm.py).
Nothing is fetched from the device for a session nobody asked about.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import lm
from analytics_zoo_tpu.obs.registry import MetricRegistry
from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.ops import pallas_lm_decode


#: the most tokens one prefill call takes; a longer chunk runs as
#: consecutive calls of that size (the program's temporaries grow with the
#: chunk, and what a call costs whatever its length — the held experts'
#: weights read once, the selection's passes — is shared by its tokens)
PREFILL_BLOCK = 2048


class CacheExhausted(RuntimeError):
    """A chunk's session does not fit the cache (no free page or slot, or
    the session would pass ``max_len``)."""


@dataclasses.dataclass
class LMModel:
    """The LM's configuration and parameters (``{"layers", "ends"}``, the
    names of models/lm.py::param_shapes)."""

    config: lm.LMConfig
    params: Any

    def parameter_count(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))


def make_lm_model(config: Dict, seed: int = 0,
                  params: Optional[Any] = None) -> LMModel:
    """The model of a HuggingFace-style ``config`` dict (models/lm.py::
    LMConfig.from_dict), with ``params`` or random ones from ``seed``."""
    cfg = lm.LMConfig.from_dict(config)
    return LMModel(cfg, params if params is not None
                   else lm.init_params(cfg, seed))


class SessionCache:
    """The host's books of one replica's cache: slots, pages, lengths."""

    def __init__(self, geo: lm.CacheGeometry):
        self.geo = geo
        self.free_pages = list(range(geo.n_pages - 1, 0, -1))   # 0: nobody's
        self.free_slots = list(range(geo.n_slots - 1, -1, -1))
        self.slot_of: Dict[int, int] = {}
        self.length = np.zeros(geo.n_slots, np.int64)
        self.tables = np.zeros((geo.n_slots, geo.max_pages), np.int32)
        self.n_pages = np.zeros(geo.n_slots, np.int32)
        self.page_slot = np.full(geo.n_pages, -1, np.int32)

    @property
    def tokens(self) -> int:
        return int(self.length.sum())

    @property
    def fill(self) -> float:
        """Share of the pool's pages that sessions hold."""
        return 1.0 - len(self.free_pages) / max(1, self.geo.n_pages - 1)

    def admit(self, sid: int, n_tokens: int) -> Tuple[int, int]:
        """Room for ``n_tokens`` more tokens of session ``sid``, which
        from now on count as its own; returns (its slot, the position of
        the first of them).  Raises :class:`CacheExhausted` with nothing
        changed."""
        geo, slot = self.geo, self.slot_of.get(sid)
        have = 0 if slot is None else int(self.n_pages[slot])
        new_len = (0 if slot is None else int(self.length[slot])) + n_tokens
        need = -(-new_len // geo.page) - have
        if new_len > geo.max_len:
            raise CacheExhausted(f"session {sid} would hold {new_len} "
                                 f"tokens, over max_len={geo.max_len}")
        if need > len(self.free_pages) or (slot is None
                                           and not self.free_slots):
            raise CacheExhausted(
                f"session {sid} needs {need} page(s) and "
                f"{'a' if slot is None else 'no'} slot; free: "
                f"{len(self.free_pages)} pages, {len(self.free_slots)} slots")
        if slot is None:
            slot = self.slot_of[sid] = self.free_slots.pop()
            self.length[slot] = self.n_pages[slot] = 0
            self.tables[slot] = 0
        for _ in range(need):
            page = self.free_pages.pop()
            self.tables[slot, self.n_pages[slot]] = page
            self.page_slot[page] = slot
            self.n_pages[slot] += 1
        self.length[slot] = new_len
        return slot, new_len - n_tokens

    def evict(self, sid: int) -> int:
        """Give the session's pages and slot back; returns the pages."""
        slot = self.slot_of.pop(sid, None)
        if slot is None:
            return 0
        n = int(self.n_pages[slot])
        pages = self.tables[slot, :n].tolist()
        self.page_slot[pages] = -1
        self.free_pages.extend(reversed(pages))
        self.free_slots.append(slot)
        self.length[slot] = self.n_pages[slot] = 0
        self.tables[slot] = 0
        return n


def lm_serving_tiers(model: LMModel, cache_tokens: int = 1 << 16,
                     max_sessions: int = 8, max_batch: int = 8,
                     page: int = 512, max_len: Optional[int] = None,
                     registry: Optional[MetricRegistry] = None) -> List:
    """ONE replica's tier instances for an LM session model: a stateful
    forward that owns this replica's :class:`SessionCache` and its device
    arrays.

    Batch contract (what a ``ModelConfig(streaming=True, pad_key="input",
    length_key="n_tokens")`` plan assembles): ``{"input": (max_batch,
    edge) int32 token ids, "n_tokens": (max_batch,) how many of each row
    are real, "session": (max_batch,) int64 ids (−1: a padding row),
    "final": (max_batch,) int8}``.  Returns one float32 (vocab,) row of
    logits a row of the batch (a decode batch's as ONE (max_batch, vocab)
    array: the runtime hands its rows out without copying them again).
    ``cache_tokens``: the paged pool's size;
    ``max_len``: the longest a session may grow (default: the pool).
    ``registry``: where the cache's gauges and the experts' load go."""
    from analytics_zoo_tpu.serving.ladder import ServingTier

    cfg = model.config
    max_len = int(max_len if max_len is not None else cache_tokens)
    geo = lm.CacheGeometry(n_pages=-(-int(cache_tokens) // page) + 1,
                           page=page, max_pages=-(-max_len // page),
                           n_slots=int(max_sessions))
    books = SessionCache(geo)
    registry = registry if registry is not None else MetricRegistry()
    state = {"cache": None}
    recorded: set = set()
    choices: Dict[int, List] = {}
    state_kept: set = set()
    states_left: Dict[int, List[np.ndarray]] = {}

    def record_choices(session_ids) -> None:
        recorded.clear()
        recorded.update(int(s) for s in session_ids)

    def keep_choices(sid: int, start: int, n: int, chosen: Dict, rows,
                     cols=None) -> None:
        """The rows ``rows`` of a step's choices (fetched: host arrays)
        for session ``sid`` whose ``n`` tokens stand at ``start ..``."""
        choices.setdefault(sid, []).append((start, n, {
            "selected": [s[rows, :cols].copy() for s in chosen["selected"]],
            "routed": chosen["routed"][:, rows].copy()}))

    def cache():
        if state["cache"] is None:
            state["cache"] = lm.new_cache(cfg, geo)
        return state["cache"]

    def record_state(session_ids) -> None:
        state_kept.clear()
        state_kept.update(int(s) for s in session_ids)

    def state_of(sid: int) -> List[np.ndarray]:
        """A layer's (heads, head, state) float32 recurrent state of
        session ``sid``, every layer's, as they stand."""
        slot = books.slot_of.get(int(sid))
        if slot is None:
            return states_left[int(sid)]
        return [np.asarray(s[slot]) for s in cache()["ssm"]]

    def release(sid: int) -> None:
        """The session's pages and slot back to the pool (a recorded
        session's recurrent states are kept first)."""
        if sid in state_kept and sid in books.slot_of:
            states_left[sid] = state_of(sid)
        books.evict(sid)

    def note_states(starts: int) -> None:
        m = cfg.ssm
        live = len(books.slot_of)
        registry.gauge("lm/ssm_slots_live").set(live)
        registry.gauge("lm/ssm_state_bytes").set(
            live * len(cfg.kinds) * m.heads * m.head * m.state * 4)
        registry.counter("lm/ssm_state_starts").inc(starts)

    def note_experts(counts: np.ndarray) -> None:
        moe = counts[cfg.dense_layers:]
        if moe.size:
            registry.histogram("lm/expert_tokens/stat=mean").observe(
                float(moe.mean()))
            registry.histogram("lm/expert_tokens/stat=max").observe(
                float(moe.max()))

    paged = lm.CAUSAL in cfg.kinds
    # the full layers whose attention over the selected entries is the
    # Pallas program that reads their copy once (``lm_attention.
    # mla_selected`` decides on the same widths); 0: ``mla_absorbed`` runs
    one_pass = 0
    if cfg.n_full:
        full = cfg.dims(lm.FULL)
        if pallas_lm_decode.supported(full.kv_rank, full.entry,
                                      min(cfg.topk, geo.max_len)):
            one_pass = cfg.n_full
    registry.gauge("lm/selected_one_pass").set(one_pass)

    def note_paged(lengths: np.ndarray) -> None:
        """What a causal layer's decode walked this step: the pages that
        hold a token of a live row, and the steps the kernel's grid (the
        latent or the grouped-query one: ops/pallas_lm_decode.py) was
        launched with, one layer."""
        registry.gauge("lm/paged_pages").set(
            int((-(-lengths // geo.page)).sum()))
        registry.gauge("lm/paged_grid_steps").set(
            pallas_lm_decode.grid_steps(len(lengths), geo.max_pages,
                                        geo.n_pages))

    def note_cache() -> None:
        registry.gauge("lm/cache_tokens").set(books.tokens)
        registry.gauge("lm/cache_fill").set(books.fill)
        registry.gauge("lm/sessions_live").set(len(books.slot_of))

    def run_decode(ids, sessions):
        """One call over every row of the batch."""
        B = len(sessions)
        live = sessions >= 0
        slots = np.full(B, -1, np.int32)
        pos = np.zeros(B, np.int32)
        with stage("az/lm/cache_admit"):
            for i in np.nonzero(live)[0]:
                slots[i], pos[i] = books.admit(int(sessions[i]), 1)
        tables = books.tables[np.maximum(slots, 0)]
        row_of_slot = np.full(geo.n_slots + 1, -1, np.int32)
        row_of_slot[slots[live]] = np.nonzero(live)[0]
        owner = row_of_slot[books.page_slot]      # slot −1 → last entry
        with stage("az/serve/h2d"):
            rows = jnp.asarray(lm.pack_rows(ids[:, 0], slots, pos, tables,
                                            owner))
        with stage("az/serve/dispatch"):
            state["cache"], logits, counts, chosen = lm.decode_jit(
                cfg, geo, model.params, cache(), rows)
            # the answers' copies start when the step ends, not when the
            # host comes to ask for them
            logits.copy_to_host_async()
            counts.copy_to_host_async()
        with stage("az/serve/result_wait"):
            logits, counts = np.asarray(logits), np.asarray(counts)
        mine = [i for i in np.nonzero(live)[0]
                if int(sessions[i]) in recorded]
        if mine:
            chosen = jax.device_get(chosen)
            for i in mine:
                keep_choices(int(sessions[i]), int(pos[i]), 1, chosen,
                             slice(i, i + 1))
        note_experts(counts)
        if cfg.ssm:
            note_states(int((live & (pos == 0)).sum()))
        if paged:
            note_paged(np.where(live, pos + 1, 0))
        if cfg.n_sliding:
            # the windows' work beside the paged layers': the entries the
            # live rows hold in a sliding layer's ring
            registry.gauge("lm/ring_tokens").set(
                int(np.minimum(pos[live] + 1, cfg.window).sum()))
        # the (B, vocab) array itself: a list of its rows is stacked again,
        # 5 MB copied, when the runtime hands the answers out
        return logits

    def run_prefill(ids, lens, sessions):
        """One call a live row (one, dry, when there is none: warm-up)."""
        rows = np.nonzero(sessions >= 0)[0]
        plan = []
        with stage("az/lm/cache_admit"):
            for i in rows:
                slot, start = books.admit(int(sessions[i]), int(lens[i]))
                plan.append((i, slot, start, int(lens[i])))
        if not plan:
            plan = [(0, 0, 0, 0)]
        block = min(ids.shape[1], PREFILL_BLOCK)
        outs = []
        for i, slot, start, n in plan:
            # the row's real tokens, a block a call (one call, dry, for a
            # row that has none)
            for lo in range(0, max(n, 1), block):
                n_call = min(block, n - lo)
                with stage("az/serve/h2d"):
                    args = [jnp.asarray(ids[i, lo:lo + block].astype(
                                np.int32)),
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(start + lo, jnp.int32),
                            jnp.asarray(n_call, jnp.int32),
                            jnp.asarray(books.tables[slot])]
                with stage("az/serve/dispatch"):
                    state["cache"], logits, _, chosen = lm.prefill_jit(
                        cfg, geo, model.params, cache(), *args)
                if int(sessions[i]) in recorded:
                    keep_choices(int(sessions[i]), start + lo, n_call,
                                 jax.device_get(chosen), slice(0, n_call),
                                 -(-(start + lo + n_call) // 8))
            outs.append(logits)
        if cfg.ssm:
            note_states(sum(start == 0 and n > 0 for _, _, start, n in plan))
        with stage("az/serve/result_wait"):
            outs = [np.asarray(o)[0] for o in outs]
        answers = [np.zeros(cfg.vocab, np.float32)] * len(sessions)
        for (i, *_), o in zip(plan, outs):
            answers[i] = o
        return answers

    def forward(batch: Dict) -> Sequence[np.ndarray]:
        ids = np.asarray(batch["input"])
        sessions = np.asarray(batch["session"])
        edge = ids.shape[1]
        with stage("az/lm/step", rows=int((sessions >= 0).sum()), edge=edge,
                   phase="decode" if edge == 1 else "prefill"):
            if edge == 1:
                answers = run_decode(ids, sessions)
            else:
                answers = run_prefill(ids, np.asarray(batch["n_tokens"]),
                                      sessions)
            for sid in sessions[np.asarray(batch["final"]) > 0]:
                release(int(sid))
            note_cache()
        return answers

    def evict(sid: int) -> None:
        release(int(sid))
        note_cache()

    def device_program(edge: int, rows: Optional[int] = None):
        """``az_analyze --program`` hook, and what a runtime registers a
        geometry (``ServingTier.device_program_for``): the jitted step of
        one edge with shape-only arguments.  The tier's batch is its own
        (``max_batch``), whatever ``rows`` says."""
        def thunk():
            S = jax.ShapeDtypeStruct
            i32 = jnp.int32
            params = jax.tree_util.tree_map(
                lambda p: S(p.shape, p.dtype), model.params)
            shapes = lm.cache_shapes(cfg, geo)
            if edge == 1:
                B = max_batch
                return (lm.decode_jit,
                        (cfg, geo, params, shapes,
                         S((B * (3 + geo.max_pages) + geo.n_pages,), i32)),
                        (0, 1))
            return (lm.prefill_jit, (cfg, geo, params, shapes,
                              S((min(edge, PREFILL_BLOCK),), i32), S((), i32),
                              S((), i32), S((), i32),
                              S((geo.max_pages,), i32)), (0, 1))
        return thunk

    tier = ServingTier(
        "bf16", forward, speed=1.0,
        quality_note="paged latent cache on the device; decode is one "
                     "jitted call a batch, prefill one a chunk",
        device_program=device_program(1), evict_session=evict,
        pads_session_rows=True, device_program_for=device_program)
    # what the driver, the tests and the audit reach for
    tier.books, tier.registry = books, registry
    tier.record_choices, tier.choices = record_choices, choices
    tier.record_state, tier.state_of = record_state, state_of
    return [tier]
