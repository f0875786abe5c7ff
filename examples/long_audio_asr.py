"""Long-audio ASR: sequence-parallel DS2 vs the reference's lossy chunking.

The reference's only long-audio mechanism is ``TimeSegmenter`` — chop the
waveform into fixed segments, transcribe each with batch-1 forwards, and
re-join text (``deepspeech2/.../TimeSegmenter.scala:11``,
``InferenceEvaluate.scala``).  Chunking loses cross-boundary context and
caps the model's receptive field at the segment size.

This example runs BOTH paths on one long utterance:

1. chunked  — ``DeepSpeech2Pipeline`` with a short ``segment_seconds``
   (the reference behavior, batched here);
2. sequence-parallel — ONE forward over the whole utterance with the
   time axis sharded across the mesh's ``sequence`` devices
   (``models.deepspeech2.sequence_parallel_forward``: ppermute boundary
   exchange for the conv halo and the BiRNN recurrence) — per-device
   activation memory is O(T/n), no context loss.

Without real multi-chip hardware, run on the virtual CPU mesh::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_audio_asr.py --seconds 30
"""

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description="Long-audio sequence-parallel ASR")
    p.add_argument("--audio", default=None,
                   help="wav/flac file; synthetic tone sweep if unset")
    p.add_argument("--seconds", type=float, default=30.0,
                   help="synthetic utterance length")
    p.add_argument("--segment-seconds", type=int, default=5,
                   help="chunked-path segment size")
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--sequence-devices", type=int, default=0,
                   help="sequence-axis size (0 = all devices)")
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    import numpy as np
    import jax

    from analytics_zoo_tpu.pipelines.deepspeech2 import (
        DS2Param, DeepSpeech2Pipeline, make_ds2_model)
    from analytics_zoo_tpu.transform.audio import SAMPLE_RATE, read_audio
    from analytics_zoo_tpu.parallel import create_mesh

    if args.audio:
        samples, rate = read_audio(args.audio)
        assert rate == SAMPLE_RATE, f"expected {SAMPLE_RATE} Hz, got {rate}"
    else:
        t = np.arange(int(args.seconds * SAMPLE_RATE)) / SAMPLE_RATE
        sweep = np.sin(2 * np.pi * (200 + 30 * t) * t).astype(np.float32)
        samples = 0.1 * sweep

    n_seq = args.sequence_devices or len(jax.devices())
    mesh = create_mesh((n_seq,), axis_names=("sequence",),
                       devices=jax.devices()[:n_seq])

    # one shared model: both paths decode with identical weights
    param_chunk = DS2Param(segment_seconds=args.segment_seconds,
                           batch_size=4)
    model = make_ds2_model(hidden=args.hidden, n_rnn_layers=1,
                           utt_length=param_chunk.utt_length)

    t0 = time.time()
    chunked = DeepSpeech2Pipeline(model, param_chunk).transcribe_samples(
        {"utt": samples})["utt"]
    t_chunk = time.time() - t0

    # sequence-parallel: segment only to the FULL utterance length
    # (rounded to the mesh multiple inside the pipeline)
    whole = DS2Param(segment_seconds=int(np.ceil(len(samples) / SAMPLE_RATE)),
                     batch_size=1)
    pipe_sp = DeepSpeech2Pipeline(model, whole, sequence_mesh=mesh)
    t0 = time.time()
    seqpar = pipe_sp.transcribe_samples({"utt": samples})["utt"]
    t_sp = time.time() - t0

    print(f"audio: {len(samples) / SAMPLE_RATE:.1f}s "
          f"({len(samples)} samples)")
    print(f"chunked  ({args.segment_seconds}s segments): {t_chunk:.1f}s  "
          f"-> {chunked[:60]!r}")
    print(f"seq-par  (T sharded over {n_seq} devices): {t_sp:.1f}s  "
          f"-> {seqpar[:60]!r}")
    print("note: untrained demo weights — transcripts are noise; the point "
          "is the execution paths (chunk-and-rejoin vs one sharded forward)")


if __name__ == "__main__":
    main()
