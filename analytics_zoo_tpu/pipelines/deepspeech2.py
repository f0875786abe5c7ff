"""DeepSpeech2 inference pipeline: audio → transcript, batched on TPU.

Port of the reference's L6 ASR pipeline (``deepspeech2/example/
InferenceExample.scala:11``, ``InferenceEvaluate.scala:14``): read audio →
TimeSegmenter chunks tagged (audio_id, seq) → featurize → model forward →
greedy CTC decode → re-join per utterance ordered by seq → WER/CER.

The reference forwards one 1×1×13×T chunk per DataFrame row (batch size 1,
SURVEY.md §3.4 hot-loop note); here all segments across utterances are
padded to ``utt_length`` and forwarded as ONE batch per ``batch_size``
group — the MXU sees big matmuls, not row-at-a-time traffic.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.models import DeepSpeech2
from analytics_zoo_tpu.parallel import make_eval_step
from analytics_zoo_tpu.transform.audio import (
    ALPHABET,
    ASREvaluator,
    SAMPLE_RATE,
    TimeSegmenter,
    VocabDecoder,
    best_path_decode,
    featurize,
    read_audio,
)

logger = logging.getLogger("analytics_zoo_tpu")


@dataclasses.dataclass
class DS2Param:
    """Reference ``util/Param.scala:17-34``: segment seconds, partitions…"""

    segment_seconds: int = 30
    batch_size: int = 8
    n_mels: int = 13
    vocab: Optional[Sequence[str]] = None
    # featurize (window → rFFT → mel) on device as one jitted batch
    # program instead of per-segment host numpy (SURVEY.md §3.4 hot loop)
    device_featurize: bool = True
    # 'greedy' (reference BestPathDecoder) | 'beam' (prefix beam search —
    # sums alignment mass per prefix; net-new over the reference)
    decoder: str = "greedy"
    beam_width: int = 16

    @property
    def utt_length(self) -> int:
        # uttLength = segment·100 frames (reference InferenceExample.scala:58)
        return self.segment_seconds * 100


class DeepSpeech2Pipeline:
    """fit-less inference pipeline (the reference's Spark ML Pipeline of 6
    stages collapses into segment → featurize → forward → decode).

    ``sequence_mesh``: a Mesh with a ``sequence`` axis switches the forward
    to the time-sharded ``models.deepspeech2.sequence_parallel_forward`` —
    utterances longer than one chip's HBM stream through exactly, instead
    of relying on the lossy TimeSegmenter chunking alone.
    """

    def __init__(self, model: Model, param: DS2Param = DS2Param(),
                 sequence_mesh=None, clock=None):
        from analytics_zoo_tpu.utils.clock import as_now_fn

        self.model = model
        self.param = param
        # eval/throughput timing reads the ONE injected clock (utils.
        # clock) — az-analyze's one-clock rule pins it; tests may pass a
        # VirtualClock for deterministic throughput logs
        self._now = as_now_fn(clock)
        self.segmenter = TimeSegmenter(
            segment_size=SAMPLE_RATE * param.segment_seconds)
        self.utt_length = param.utt_length
        if sequence_mesh is not None:
            import jax

            from analytics_zoo_tpu.models.deepspeech2 import (
                sequence_parallel_forward)

            # chunks must be even per device (stride-2 conv front-end)
            mult = 2 * sequence_mesh.shape["sequence"]
            self.utt_length = ((self.utt_length + mult - 1) // mult) * mult
            batch_axis = ("data" if "data" in sequence_mesh.axis_names
                          else None)
            # data-axis sharding needs B divisible by the axis: pad ragged
            # final chunks up to batch_size (trimmed again after forward)
            self._pad_to_batch = batch_axis is not None
            # jit once: re-invocations hit the compile cache per batch shape
            self._eval_step = jax.jit(
                lambda variables, x: sequence_parallel_forward(
                    variables, x, sequence_mesh, batch_axis=batch_axis,
                    model=model.module))
        else:
            self._eval_step = make_eval_step(model.module)
            self._pad_to_batch = False
        self.vocab_decoder = (VocabDecoder(param.vocab)
                              if param.vocab else None)
        self._dev_featurizer = None      # built lazily per segment size
        self._fused_asr = None           # featurize→forward→argmax, one jit
        # the fused single-program path covers the standard forward; the
        # sequence-parallel forward keeps the split pipeline
        self._fused_ok = sequence_mesh is None

    def _make_featurizer(self):
        """The ONE construction site for the device featurizer — both the
        split path and the fused greedy program must featurize
        identically."""
        if self._dev_featurizer is None:
            from analytics_zoo_tpu.transform.audio import (
                make_featurizer_device)

            self._dev_featurizer = make_featurizer_device(
                self.segmenter.segment_size, utt_length=self.utt_length,
                n_mels=self.param.n_mels)
        return self._dev_featurizer

    def _pack_batch(self, chunk: List[dict]):
        """Zero-pad a chunk of segments to one fixed (batch_size,
        segment_samples) array + per-row valid sample counts — the
        shared packing contract of the split and fused paths."""
        bs = self.param.batch_size
        seg_samples = self.segmenter.segment_size
        batch = np.zeros((bs, seg_samples), np.float32)
        n_valid = np.zeros((bs,), np.int32)
        for i, s in enumerate(chunk):
            x = s["samples"]
            batch[i, :len(x)] = x
            n_valid[i] = len(x)
        return batch, n_valid

    def _featurize_device(self, segments: List[dict]) -> np.ndarray:
        """Featurize in fixed ``batch_size`` device batches (last one
        zero-padded) with host-parity frame masking — one static shape,
        so exactly one XLA compile and bounded device memory regardless
        of how many segments a call carries."""
        featurizer = self._make_featurizer()
        bs = self.param.batch_size
        out = np.zeros((len(segments), self.utt_length, self.param.n_mels),
                       np.float32)
        for start in range(0, len(segments), bs):
            chunk = segments[start:start + bs]
            batch, n_valid = self._pack_batch(chunk)
            out[start:start + len(chunk)] = np.asarray(
                featurizer(batch, n_valid))[:len(chunk)]
        return out

    def _fused_greedy(self):
        """ONE jitted program: device featurize → DS2 forward → per-frame
        argmax.  Features never round-trip to host (the split path reads
        them back only to re-upload), and the readback is (B, T) int ids
        — ~30× fewer bytes than (B, T, C) log-probs.  Each extra
        dispatch pays a launch and an HBM round-trip of its operands, so
        the greedy path is a single call per batch
        (docs/PERFORMANCE.md)."""
        if self._fused_asr is None:
            import jax

            feat_fn = self._make_featurizer()
            eval_step = self._eval_step

            def run(variables, samples, n_valid):
                feats = feat_fn(samples, n_valid)
                log_probs = eval_step(variables, feats)
                return jnp.argmax(log_probs, axis=-1)

            self._fused_asr = jax.jit(run)
        return self._fused_asr

    def _decode(self, log_probs: np.ndarray) -> str:
        if self.param.decoder == "beam":
            from analytics_zoo_tpu.transform.audio import beam_search_decode
            return beam_search_decode(log_probs,
                                      beam_width=self.param.beam_width)
        return best_path_decode(log_probs)

    def _transcribe_fused(self, segments: List[dict]) -> List[str]:
        """Greedy + device-featurize fast path: one jit call per batch of
        raw samples, bounded dispatch-ahead window, int-ids readback."""
        from analytics_zoo_tpu.data import overlap_window
        from analytics_zoo_tpu.transform.audio.decoders import ids_to_text

        fused = self._fused_greedy()
        bs = self.param.batch_size
        texts: List[str] = []

        def dispatch(start):
            chunk = segments[start:start + bs]
            batch, n_valid = self._pack_batch(chunk)
            return fused(self.model.variables, batch, n_valid), len(chunk)

        def consume(token):
            ids, n_real = token
            ids = np.asarray(ids)
            texts.extend(ids_to_text(ids[j]) for j in range(n_real))

        overlap_window(range(0, len(segments), bs), dispatch, consume)
        return texts

    def transcribe_samples(self, utterances: Dict[str, np.ndarray]
                           ) -> Dict[str, str]:
        """{audio_id: samples} → {audio_id: transcript}."""
        segments: List[dict] = []
        for audio_id, samples in utterances.items():
            segments.extend(self.segmenter.segment(samples, audio_id))

        if segments and self._fused_ok and self.param.device_featurize \
                and self.param.decoder == "greedy":
            texts = self._transcribe_fused(segments)
        else:
            if not segments:
                feats = np.zeros((0, self.utt_length, self.param.n_mels),
                                 np.float32)
            elif self.param.device_featurize:
                feats = np.asarray(self._featurize_device(segments))
            else:
                feats = np.stack([
                    featurize(s["samples"], utt_length=self.utt_length,
                              n_mels=self.param.n_mels)
                    for s in segments
                ])

            texts = []
            for i in range(0, len(segments), self.param.batch_size):
                chunk = feats[i:i + self.param.batch_size]
                n_real = chunk.shape[0]
                if self._pad_to_batch and n_real < self.param.batch_size:
                    pad = np.zeros((self.param.batch_size - n_real,)
                                   + chunk.shape[1:], chunk.dtype)
                    chunk = np.concatenate([chunk, pad])
                log_probs = self._eval_step(self.model.variables,
                                            jnp.asarray(chunk))
                texts.extend(self._decode(np.asarray(log_probs[j]))
                             for j in range(n_real))

        # re-join by (audio_id, audio_seq) (reference InferenceEvaluate
        # groupBy(audio_id).sort(audio_seq) concat)
        joined: Dict[str, List[Tuple[int, str]]] = {}
        for seg, text in zip(segments, texts):
            joined.setdefault(seg["audio_id"], []).append(
                (seg["audio_seq"], text))
        out = {}
        for audio_id, parts in joined.items():
            text = " ".join(t for _, t in sorted(parts)).strip()
            if self.vocab_decoder is not None:
                text = self.vocab_decoder(text)
            out[audio_id] = text
        return out

    def transcribe_files(self, paths: Sequence[str]) -> Dict[str, str]:
        utts = {}
        for p in paths:
            samples, rate = read_audio(p)
            if rate != SAMPLE_RATE:
                raise ValueError(f"{p}: expected {SAMPLE_RATE} Hz, got {rate}")
            utts[p] = samples
        return self.transcribe_samples(utts)

    def evaluate(self, utterances: Dict[str, np.ndarray],
                 transcripts: Dict[str, str]) -> ASREvaluator:
        """WER/CER over labeled utterances (reference InferenceEvaluate
        per-utterance WER/CER print + total time log)."""
        t0 = self._now()
        hyps = self.transcribe_samples(utterances)
        ev = ASREvaluator()
        for audio_id, ref in transcripts.items():
            hyp = hyps.get(audio_id, "")
            ev.add(ref.upper(), hyp)
        dt = self._now() - t0
        logger.info("DS2 eval: %d utterances in %.2fs (%.2f utt/sec), "
                    "WER=%.4f CER=%.4f", len(transcripts), dt,
                    len(transcripts) / max(dt, 1e-9), ev.wer, ev.cer)
        return ev


def make_ds2_model(hidden: int = 1024, n_rnn_layers: int = 3,
                   n_mels: int = 13, utt_length: int = 300,
                   seed: int = 0, bidirectional: bool = True,
                   rnn_hoist: bool = True, rnn_block: int = 16,
                   rnn_engine: Optional[str] = None,
                   rnn_pallas_backward: str = "pallas",
                   rnn_pallas_grad: bool = True) -> Model:
    """``bidirectional=False`` builds the forward-only (streamable)
    variant consumed by :class:`StreamingDS2`.  ``rnn_hoist=False``
    selects the legacy per-step scan body (the bench A/B baseline);
    ``rnn_engine`` overrides the recurrence engine explicitly
    ("legacy" | "blocked" | "pallas" — "pallas" is the persistent-RNN
    kernel of ``ops.pallas_rnn``, which ``train_ds2`` consumes through
    the model; ``rnn_pallas_backward``/``rnn_pallas_grad`` are its
    grad-pass knobs — forward-only consumers pass
    ``rnn_pallas_grad=False`` so the VMEM budget prices only the
    forward).  The parameter tree is identical across engines, so
    checkpoints move freely between them."""
    model = Model(DeepSpeech2(hidden=hidden, n_rnn_layers=n_rnn_layers,
                              n_mels=n_mels, bidirectional=bidirectional,
                              rnn_hoist=rnn_hoist, rnn_block=rnn_block,
                              rnn_engine=rnn_engine,
                              rnn_pallas_backward=rnn_pallas_backward,
                              rnn_pallas_grad=rnn_pallas_grad))
    model.build(seed, jnp.zeros((1, utt_length, n_mels)))
    return model


def ds2_ctc_criterion(blank_id: int = 0):
    """CTC criterion closure for DS2 batches.  Length-bucketed batches
    carry per-row ``n_frames``; the valid OUTPUT frame count after the
    stride-2 conv is ``ceil(n/2)``, and frames past it are masked out of
    the loss (they carry no signal — the model zeroes them when fed
    ``n_frames``)."""
    from analytics_zoo_tpu.core.criterion import CTCCriterion

    ctc = CTCCriterion(blank_id=blank_id)

    def criterion(log_probs, batch):
        from analytics_zoo_tpu.models.deepspeech2 import ds2_valid_out_frames

        logit_mask = None
        if isinstance(batch, dict) and "n_frames" in batch:
            out_n = ds2_valid_out_frames(batch["n_frames"].astype(jnp.int32))
            T = log_probs.shape[1]
            logit_mask = (jnp.arange(T, dtype=jnp.int32)[None, :]
                          < out_n[:, None]).astype(jnp.float32)
        return ctc(log_probs, batch["labels"], logit_mask=logit_mask,
                   label_mask=batch.get("label_mask"))

    return criterion


def ds2_padding_metric(batch):
    """``make_train_step metric_fn``: valid/padded input-frame ratio of a
    length-bucketed batch (1.0 for unbucketed fixed-shape batches)."""
    if not (isinstance(batch, dict) and "n_frames" in batch):
        return {}
    x = batch["input"][0] if isinstance(batch["input"], tuple) \
        else batch["input"]
    total = x.shape[0] * x.shape[1]
    return {"padding_efficiency":
            jnp.sum(batch["n_frames"].astype(jnp.float32)) / total}


def train_ds2(model: Model, dataset, epochs: int = 10, lr: float = 3e-4,
              mesh=None, checkpoint_path: Optional[str] = None,
              param_rules=None, sequence_parallel: bool = False,
              specs=None):
    """CTC training for DS2 — capability the reference lacks (its DS2 is
    inference-only; SURVEY.md §2.3).  ``dataset`` yields batches
    ``{"input": (B,T,n_mels), "labels": (B,L) int32, "label_mask": (B,L)}``.
    Length-bucketed batches (``load_asr_train_set(bucket_edges=...)``)
    instead carry ``"input": ((B,T_bucket,n_mels), n_frames)`` — the model
    length-masks padding, the CTC loss masks invalid output frames, and
    step metrics gain ``padding_efficiency``.  The recurrence engine is
    the model's: build with ``make_ds2_model(rnn_engine="pallas")`` to
    train on the persistent-RNN kernel (h2h weights VMEM-resident —
    the docs/MFU_CEILING.md roofline lever; its speed against the
    blocked scan is not measured on the chip: PERF.md §7).
    ``param_rules`` enables tensor-parallel weight sharding
    (``parallel.tensor.default_tp_rules``) on a data×model mesh.

    ``sequence_parallel=True`` (mesh must carry a "sequence" axis, e.g.
    ``create_mesh((2, 4), axis_names=("data", "sequence"))``) trains with
    the TIME axis sharded: the step's forward is the pipelined-scan +
    halo-exchange program of ``models.deepspeech2.sequence_parallel_forward``
    with global-batch BN statistics, so activation memory per device is
    O(T/n) — long-audio CTC training beyond single-chip HBM.  The CTC
    loss itself consumes the (tiny, n_alphabet-wide) log-probs gathered
    back over T.

    Sharding is declared ONCE through the spec registry
    (``specs=pipeline_specs("ds2", mesh=mesh, param_rules=...)``; built
    here from ``mesh``/``param_rules`` when not given) and consumed by
    the annotated train step — this entry point performs no device
    placement, and a wider ``data`` axis is the global-batch lever of
    docs/MFU_CEILING.md (per-chip batch × mesh width toward the B/128
    occupancy knee).
    """
    from analytics_zoo_tpu.parallel import (Adam, Optimizer, Trigger,
                                            pipeline_specs)

    if specs is None:
        specs = pipeline_specs("ds2", mesh=mesh, param_rules=param_rules)
    elif mesh is not None or param_rules is not None:
        raise ValueError("pass specs= OR (mesh=, param_rules=), not both")
    mesh = specs.mesh
    criterion = ds2_ctc_criterion(blank_id=0)

    forward_fn = None
    if sequence_parallel:
        from analytics_zoo_tpu.models.deepspeech2 import (
            make_sequence_parallel_forward_fn)
        if "sequence" not in mesh.axis_names:
            raise ValueError("sequence_parallel=True needs a mesh with a "
                             f"'sequence' axis, got {mesh.axis_names}")
        forward_fn = make_sequence_parallel_forward_fn(
            model.module, mesh,
            batch_axis="data" if "data" in mesh.axis_names else None)

    opt = (Optimizer(model, dataset, criterion, specs=specs,
                     forward_fn=forward_fn,
                     metric_fn=ds2_padding_metric)
           .set_optim_method(Adam(lr))
           .set_end_when(Trigger.max_epoch(epochs)))
    if checkpoint_path:
        opt.set_checkpoint(checkpoint_path, Trigger.every_epoch())
    return opt.optimize()


class StreamingDS2:
    """Stateful streaming ASR: feed successive sample chunks, get
    incremental transcript pieces — net-new over the reference, whose only
    long-audio mechanism processes chunks INDEPENDENTLY with zeroed
    context (``TimeSegmenter.scala:11``).

    Exactness contract: the emitted log-probs exactly equal the batch
    forward of the same (unidirectional) model over the whole utterance,
    because every boundary carries its true state:

    - featurization: a 240-sample window-overlap residue carries across
      chunks, so frames are identical to whole-utterance framing;
    - conv front-end (kernel 11, stride 2, SAME(5,5) in batch mode): the
      stream starts with 5 zero context frames (= the left SAME pad),
      carries the last 9 real mel frames between chunks, and ``flush()``
      appends the 5-zero right pad; the model runs the conv VALID on the
      extended chunk, so output indices line up exactly;
    - RNN layers: forward-only scan with hidden state carried across
      chunks (``DeepSpeech2(bidirectional=False)``);
    - decoding: greedy CTC with the collapse state (previous argmax id)
      carried, so repeats spanning a boundary collapse correctly.

    Compilation: chunks are processed in FIXED ``chunk_frames`` blocks
    (remainder buffered) so the jitted forward compiles exactly three
    shapes — first block, steady block, and the padded flush block (flush
    pads to the steady shape and truncates emissions to the true
    remaining count, which keeps the tail exact for any stream length).

    Latency: ``chunk_frames`` mel frames (10 ms each) of buffering plus
    the conv's inherent 5-input-frame lookahead.
    """

    _CTX = 9            # real mel frames carried between blocks
    _PAD = 5            # zero frames standing in for SAME padding at ends

    def __init__(self, model: Model, n_mels: int = 13,
                 chunk_frames: int = 100, keep_log_probs: bool = False):
        import jax

        if getattr(model.module, "bidirectional", True):
            raise ValueError("streaming needs DeepSpeech2(bidirectional="
                             "False) — the backward pass needs the future")
        if chunk_frames < 6 or chunk_frames % 2:
            raise ValueError("chunk_frames must be even and >= 6")
        self.model = model
        self.n_mels = n_mels
        self.chunk_frames = chunk_frames
        # retain emitted per-frame log-probs (exactness testing / lattice
        # consumers); unbounded for endless streams, so off by default
        self.keep_log_probs = keep_log_probs
        self._apply = jax.jit(lambda v, x, c: model.module.apply(
            v, x, carry=c, return_carry=True))
        self._hidden = model.module.hidden
        self._layers = model.module.n_rnn_layers
        from analytics_zoo_tpu.transform.audio.featurize import (
            WINDOW_SIZE, mel_filterbank_matrix)
        self._fb = mel_filterbank_matrix(n_mels, WINDOW_SIZE)
        self.reset()

    def reset(self) -> None:
        self._samples = np.zeros((0,), np.float32)
        self._frames = np.zeros((0, self.n_mels), np.float32)
        self._ctx: Optional[np.ndarray] = None     # None = stream start
        self._h = {"h": tuple(
            jnp.zeros((1, self._hidden)) for _ in range(self._layers))}
        self._prev_id = 0                          # CTC collapse carry
        self._pieces: List[str] = []
        self._log_probs: List[np.ndarray] = []
        self._total_frames = 0                     # real mel frames seen
        self._emitted = 0                          # output frames emitted
        self._finished = False

    # -- internals ---------------------------------------------------------
    def _featurize_new(self, samples: np.ndarray) -> np.ndarray:
        """Consume buffered samples into mel frames, keeping the
        window-overlap residue (window 400, stride 160 → 240 overlap)."""
        from analytics_zoo_tpu.transform.audio.featurize import (
            WINDOW_SIZE, WINDOW_STRIDE, dft_specgram, frame_signal,
            mel_features)

        self._samples = np.concatenate([self._samples, samples])
        n = max((len(self._samples) - WINDOW_SIZE) // WINDOW_STRIDE + 1, 0)
        if n == 0:
            return np.zeros((0, self.n_mels), np.float32)
        take = WINDOW_SIZE + WINDOW_STRIDE * (n - 1)
        frames = frame_signal(self._samples[:take])
        self._samples = self._samples[WINDOW_STRIDE * n:]
        return mel_features(dft_specgram(frames), n_mels=self.n_mels,
                            fb=self._fb)

    def _run(self, ext: np.ndarray, n_emit: Optional[int] = None) -> str:
        log_probs, self._h = self._apply(
            self.model.variables, jnp.asarray(ext[None]), self._h)
        lp = np.asarray(log_probs[0])
        if n_emit is not None:
            lp = lp[:n_emit]
        self._emitted += lp.shape[0]
        if self.keep_log_probs:
            self._log_probs.append(lp)
        return self._decode(lp)

    def _update_ctx(self, real_frames: np.ndarray) -> None:
        """ctx = last 9 REAL frames of the stream (zero-left-padded while
        fewer have been seen)."""
        prev = (self._ctx if self._ctx is not None
                else np.zeros((self._CTX, self.n_mels), np.float32))
        self._ctx = np.concatenate([prev, real_frames])[-self._CTX:]

    def _decode(self, log_probs: np.ndarray) -> str:
        out = []
        for t in np.argmax(log_probs, axis=-1):
            if t != self._prev_id and t != 0:
                out.append(ALPHABET[int(t)])
            self._prev_id = int(t)
        piece = "".join(out)
        self._pieces.append(piece)
        return piece

    # -- public API --------------------------------------------------------
    def accept(self, samples: np.ndarray) -> str:
        """Feed raw samples; returns the transcript piece decoded from any
        completed fixed-size frame blocks (possibly "")."""
        if self._finished:
            raise RuntimeError("stream finished — call reset() first")
        frames = self._featurize_new(np.asarray(samples, np.float32))
        if frames.shape[0]:
            self._frames = np.concatenate([self._frames, frames])
            self._total_frames += frames.shape[0]
        pieces = []
        C = self.chunk_frames
        while self._frames.shape[0] >= C:
            chunk, self._frames = self._frames[:C], self._frames[C:]
            if self._ctx is None:
                ext = np.concatenate(
                    [np.zeros((self._PAD, self.n_mels), np.float32), chunk])
            else:
                ext = np.concatenate([self._ctx, chunk])
            self._update_ctx(chunk)
            pieces.append(self._run(ext))
        return "".join(pieces)

    def flush(self) -> str:
        """End of stream: process buffered frames + the right SAME pad,
        padded up to the steady block shape (emissions truncated to the
        true remaining count, so the tail stays exact)."""
        if self._finished:
            return ""
        self._finished = True
        r = self._frames.shape[0]
        virgin = self._ctx is None
        ctx = (np.zeros((self._PAD, self.n_mels), np.float32) if virgin
               else self._ctx)
        # ONE flush shape regardless of remainder size or virginity:
        # r <= C-1 (accept drains full blocks) and ctx is 5 or 9 frames,
        # so pad >= PAD always holds
        target = self.chunk_frames + self._CTX + self._PAD
        pad = target - ctx.shape[0] - r
        assert pad >= self._PAD, (pad, r)
        ext = np.concatenate([
            ctx, self._frames,
            np.zeros((pad, self.n_mels), np.float32)])
        self._frames = np.zeros((0, self.n_mels), np.float32)
        expected_total = (self._total_frames + 1) // 2
        n_emit = max(expected_total - self._emitted, 0)
        return self._run(ext, n_emit=n_emit) if n_emit else ""

    @property
    def transcript(self) -> str:
        return "".join(self._pieces)

    @property
    def log_probs(self) -> np.ndarray:
        """Concatenated emitted log-probs (requires keep_log_probs)."""
        if not self._log_probs:
            return np.zeros((0, 0), np.float32)
        return np.concatenate(self._log_probs, axis=0)


# ---------------------------------------------------------------------------
# Training input pipeline
# ---------------------------------------------------------------------------


def load_asr_train_set(samples: np.ndarray, labels: np.ndarray,
                       label_lengths: Optional[np.ndarray] = None,
                       batch_size: int = 8,
                       utt_length: Optional[int] = None,
                       n_mels: int = 13, shuffle: bool = True,
                       seed: int = 0, worker_processes: int = 0,
                       sample_lengths: Optional[np.ndarray] = None,
                       bucket_edges: Optional[Sequence[int]] = None,
                       param=None):
    """DataSet of featurized CTC train batches from raw waveforms.

    The host featurize (frame → rFFT → mel, ``transform.audio.
    featurize``) is the per-sample hot loop, so ``worker_processes > 0``
    fans it out through the multiprocess loader
    (``data.parallel.ParallelLoader`` — shared-memory rings,
    order-preserving, deterministically seeded).  Prefer
    ``make_featurizer_device`` fused into the train step when the chip
    has headroom; this host path is for hosts feeding featurize-bound
    accelerators, and is the DS2 wiring of docs/PERFORMANCE.md "Host
    input pipeline".

    ``samples``: (N, S) float32 waveforms; ``labels``: (N, L) int32
    (0-padded); ``label_lengths``: (N,) true lengths (defaults to
    counting nonzero labels).  Batches: ``{"input", "labels",
    "label_mask"}`` ready for ``CTCCriterion``.

    **Length-bucketed mode** (``bucket_edges``, frame counts): ragged
    waveforms (``sample_lengths`` giving true per-row sample counts)
    are featurized at their TRUE length and batched into the smallest
    fitting padded bucket (``data.bucket.BucketBatcher`` — compile once
    per bucket, deterministic for any worker count, replayable from the
    PR-2 ``(base_seed, epoch, index)`` coordinates).  Batches then carry
    ``"input": (features, n_frames)`` so the model length-masks padding,
    plus top-level ``n_frames`` for the CTC logit mask and the
    ``padding_efficiency`` step metric.  ``param``
    (:class:`~analytics_zoo_tpu.pipelines.ssd.PreProcessParam`) supplies
    ``batch_size`` / ``worker_processes`` / ``loader_seed`` /
    ``bucket_edges`` in one object for pipeline-level wiring.
    """
    from analytics_zoo_tpu.data import DataSet, FnTransformer

    if param is not None:
        batch_size = param.batch_size
        worker_processes = param.worker_processes
        seed = param.loader_seed
        if getattr(param, "bucket_edges", None):
            bucket_edges = param.bucket_edges

    samples = np.asarray(samples, np.float32)
    labels = np.asarray(labels, np.int32)
    if label_lengths is None:
        label_lengths = (labels != 0).sum(axis=1).astype(np.int32)
    if sample_lengths is None:
        sample_lengths = np.full((len(samples),), samples.shape[1], np.int64)
    sample_lengths = np.asarray(sample_lengths, np.int64)
    L = labels.shape[1]

    base = DataSet.from_arrays(samples=samples, labels=labels,
                               n_label=label_lengths,
                               n_sample=sample_lengths,
                               shuffle=shuffle, seed=seed)

    if bucket_edges is None:
        def feat(s):
            x = featurize(s["samples"], utt_length=utt_length,
                          n_mels=n_mels)
            mask = (np.arange(L) < s["n_label"]).astype(np.float32)
            return {"input": x.astype(np.float32), "labels": s["labels"],
                    "label_mask": mask}

        return (base.transform(FnTransformer(feat))
                .batch(batch_size, num_workers=worker_processes,
                       base_seed=seed))

    # fail fast on under-sized edges: BucketBatcher would silently
    # truncate input FRAMES while the labels stay full-length, which can
    # leave CTC with no feasible alignment (inf loss poisoning the batch)
    from analytics_zoo_tpu.transform.audio.featurize import (
        WINDOW_SIZE, WINDOW_STRIDE)
    max_frames = (int(sample_lengths.max()) - WINDOW_SIZE) \
        // WINDOW_STRIDE + 1
    if max_frames > max(bucket_edges):
        raise ValueError(
            f"bucket_edges[-1]={max(bucket_edges)} < the longest "
            f"utterance's {max_frames} frames — add a covering last "
            "edge (or pre-segment the audio); truncating frames but "
            "not labels can make the CTC loss infeasible")

    def feat_ragged(s):
        n_samp = int(s["n_sample"])
        x = featurize(s["samples"][:n_samp], utt_length=None,
                      n_mels=n_mels)
        mask = (np.arange(L) < s["n_label"]).astype(np.float32)
        return {"input": x.astype(np.float32),
                "n_frames": np.int32(x.shape[0]),
                "labels": s["labels"], "label_mask": mask}

    def pack(batch):
        # model contract: inputs as (features, n_frames) so the forward
        # receives the lengths positionally; n_frames stays top-level
        # for the CTC logit mask + padding_efficiency metric
        return {"input": (batch["input"], batch["n_frames"]),
                "n_frames": batch["n_frames"],
                "labels": batch["labels"],
                "label_mask": batch["label_mask"]}

    from analytics_zoo_tpu.data.bucket import BucketBatcher
    ds = (base.transform(FnTransformer(feat_ragged))
          .transform(BucketBatcher(batch_size, bucket_edges,
                                   length_key="n_frames",
                                   pad_key="input"))
          .transform(FnTransformer(pack)))
    if worker_processes > 0:
        return ds.parallel(worker_processes, base_seed=seed)
    return ds


def ds2_serving_tiers(model: Model, param: Optional[DS2Param] = None,
                      degraded_beam: Optional[int] = None,
                      specs=None) -> List:
    """Degradation-ladder rungs for the online serving runtime
    (``serving.ServingRuntime``): prefix-beam width is DS2's analog of
    the SSD ladder's NMS top-K — the decode-side work that can be cut
    under overload with a bounded, explicit quality loss.

    Requests carry ONE featurized utterance
    (``{"input": (n_frames, n_mels) float32}``, ``length=n_frames``);
    the serving batcher pads the time axis to a configured bucket edge
    (``bucket_edges`` should match the training ``BucketBatcher`` edges
    so serving reuses compiled geometries) and hands the forward
    ``{"input": (B, edge, n_mels), "n_frames": (B,)}``.  Each tier
    decodes only ``ds2_valid_out_frames(n)`` output frames per row —
    padding never reaches the decoder.

    Tiers (cheapest last): full prefix-beam (``param.beam_width``),
    reduced beam (``degraded_beam``, default ``max(4, width // 4)``),
    greedy best-path.  With ``param.decoder == "greedy"`` there is no
    decode quality to shed, so the ladder is the single greedy tier.

    ``specs`` (e.g. ``pipeline_specs("ds2", mesh=mesh)``): the shared
    forward is then mesh-annotated through the spec layer (variables
    replicated, batch over ``data``).
    """
    from analytics_zoo_tpu.models.deepspeech2 import ds2_valid_out_frames
    from analytics_zoo_tpu.serving.ladder import ServingTier
    from analytics_zoo_tpu.transform.audio import beam_search_decode

    param = param or DS2Param()
    eval_step = make_eval_step(model.module, specs=specs)

    def audit_program(edge: int = 64):
        """``az_analyze --program`` hook: every tier dispatches this ONE
        annotated forward (tiers differ only in host-side decode), so
        each rung exposes it with shape-only example args."""
        B = specs.data_axis_size if specs is not None else 1
        return (eval_step,
                (model.variables,
                 jax.ShapeDtypeStruct((B, edge, param.n_mels),
                                      jnp.float32)),
                ())

    def forward_with(decode: Callable[[np.ndarray], str]):
        def forward(batch: Dict) -> List[str]:
            feats = batch["input"]
            n_frames = batch.get("n_frames")
            log_probs = np.asarray(eval_step(model.variables,
                                             jnp.asarray(feats)))
            texts: List[str] = []
            for i in range(feats.shape[0]):
                n = (int(n_frames[i]) if n_frames is not None
                     else feats.shape[1])
                if n <= 0:          # batch-axis padding row
                    texts.append("")
                    continue
                texts.append(decode(log_probs[i, :ds2_valid_out_frames(n)]))
            return texts
        return forward

    if param.decoder == "greedy":
        return [ServingTier("greedy", forward_with(best_path_decode),
                            speed=1.0, quality_note="best-path decode",
                            device_program=audit_program)]
    width = param.beam_width
    low = degraded_beam if degraded_beam is not None else max(4, width // 4)
    return [
        ServingTier(f"beam{width}",
                    forward_with(lambda lp: beam_search_decode(
                        lp, beam_width=width)),
                    speed=1.0,
                    quality_note=f"prefix beam search, width {width}",
                    device_program=audit_program),
        ServingTier(f"beam{low}",
                    forward_with(lambda lp: beam_search_decode(
                        lp, beam_width=low)),
                    speed=0.85,
                    quality_note=f"reduced beam width {low} (bounded "
                                 "WER cost under overload)",
                    device_program=audit_program),
        ServingTier("greedy", forward_with(best_path_decode), speed=0.7,
                    quality_note="best-path decode (no beam) — the "
                                 "cheapest rung",
                    device_program=audit_program),
    ]


def ds2_streaming_tiers(model: Model, n_mels: int = 13,
                        chunk_frames: int = 100) -> List:
    """ONE replica's tier instances for the first-class streaming ASR
    session model (ISSUE 14): a stateful forward owning this replica's
    session store — ``{session id: StreamingDS2}`` — so session-affine
    scheduling is physically meaningful (the carry state LIVES on the
    pinned replica; a migrated session would decode from zeroed state,
    which is exactly why the pool never fails a session batch over).

    Batch contract (what a ``ModelConfig(streaming=True)`` plan
    assembles): ``{"input": (B, edge) float32 raw samples, "n_samples":
    (B,) true lengths, "session": (B,) int64 ids (−1 padding),
    "final": (B,) int8 flush flags}``.  Each row routes to its
    session's :class:`StreamingDS2` — featurization residue, conv
    context, RNN hidden state and CTC collapse state all carry across
    chunks, so the concatenated pieces exactly equal the whole-
    utterance forward (the ``StreamingDS2`` exactness contract, now
    riding the multiplexed runtime).  A ``final`` row appends the
    stream's :meth:`StreamingDS2.flush` tail and retires the session's
    state.

    Use with ``functools.partial`` as the per-replica factory::

        ModelConfig(name="ds2-stream", streaming=True,
                    tiers=ds2_streaming_tiers(model),
                    tier_factory=lambda rid: ds2_streaming_tiers(model),
                    pad_key="input", length_key="n_samples",
                    bucket_edges=[...sample-count edges...])
    """
    from analytics_zoo_tpu.serving.ladder import ServingTier

    store: Dict[int, StreamingDS2] = {}

    def forward(batch: Dict) -> List[str]:
        sessions = batch["session"]
        final = batch["final"]
        lens = batch.get("n_samples")
        texts: List[str] = []
        for i in range(len(sessions)):
            sid = int(sessions[i])
            if sid < 0:             # batch-axis padding row
                texts.append("")
                continue
            stream = store.get(sid)
            if stream is None:
                stream = StreamingDS2(model, n_mels=n_mels,
                                      chunk_frames=chunk_frames)
                store[sid] = stream
            n = (int(lens[i]) if lens is not None
                 else batch["input"].shape[1])
            piece = (stream.accept(np.asarray(batch["input"][i][:n],
                                              np.float32))
                     if n > 0 else "")
            if int(final[i]):
                piece += stream.flush()
                store.pop(sid, None)
            texts.append(piece)
        return texts

    def device_program():
        """``az_analyze --program`` hook: the steady-block jitted apply
        every chunk dispatches (carry in, carry out)."""
        hidden = model.module.hidden
        layers = model.module.n_rnn_layers
        S = jax.ShapeDtypeStruct
        carry = {"h": tuple(S((1, hidden), jnp.float32)
                            for _ in range(layers))}
        fn = jax.jit(lambda v, x, c: model.module.apply(
            v, x, carry=c, return_carry=True))
        ext = chunk_frames + StreamingDS2._CTX
        return (fn, (model.variables,
                     S((1, ext, n_mels), jnp.float32), carry), ())

    return [ServingTier(
        "stream", forward, speed=1.0,
        quality_note=f"stateful streaming session ({chunk_frames}-frame "
                     f"blocks, exact to the whole-utterance forward)",
        device_program=device_program,
        # the runtime evicts a killed session's carry here, so failed
        # sessions don't leak StreamingDS2 state on the replica (its
        # still-queued chunks are failed before dispatch, so the entry
        # is never recreated)
        evict_session=lambda sid: store.pop(sid, None))]
