"""Length-bucketed batching: pinned padded shapes, bounded waste.

The DS2 CTC train path pads every utterance to one global ``utt_length``
— on a realistic length distribution most of the padded tensor is zeros
(the RNN stack then *scans* those zeros).  :class:`BucketBatcher` groups
samples into a small FIXED set of padded-length buckets instead:

- **Compile-once shapes**: every emitted batch's time axis is one of
  ``bucket_edges``, so the jit cache warms once per bucket and stays
  warm (the same pinned-shape discipline as the SSD canvas staging).
- **Determinism**: bucket assignment is a pure function of the sample's
  own length, and batches are emitted the moment a bucket fills while
  iterating the (already deterministic) sample stream — so the batch
  stream is byte-identical for any ``ParallelLoader`` worker count, and
  ``data.parallel.replay_batches`` re-materializes a recorded batch from
  its ``(base_seed, epoch, index)`` coordinates unchanged.  The batcher
  is a stream (trailing) stage: it always runs in the parent process.
- **Waste accounting**: each batch carries per-row ``n_frames``; the
  train step reports ``padding_efficiency`` (valid / padded frames) in
  its metrics.

Samples are dicts with ``pad_key`` holding a ``(n, D)`` array and
``length_key`` its true length ``n``.  A sample longer than the last
edge is truncated to it (counted in ``truncated``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from analytics_zoo_tpu.data.transformer import Transformer


class BucketBatcher(Transformer):
    """Batch a sample stream into fixed padded-length buckets.

    ``bucket_edges``: ascending padded lengths; a sample lands in the
    smallest bucket that fits it.  ``drop_remainder=False`` flushes
    partial buckets at end of stream in ascending-edge order (shapes
    stay pinned — only dim 0 shrinks).
    """

    def __init__(self, batch_size: int, bucket_edges: Sequence[int],
                 length_key: str = "n_frames", pad_key: str = "input",
                 drop_remainder: bool = True,
                 collate_fn: Optional[Callable] = None):
        edges = sorted(int(e) for e in bucket_edges)
        if not edges or any(e <= 0 for e in edges):
            raise ValueError(f"bucket_edges must be positive, got "
                             f"{bucket_edges!r}")
        if len(set(edges)) != len(edges):
            raise ValueError(f"duplicate bucket edges in {bucket_edges!r}")
        self.batch_size = int(batch_size)
        self.bucket_edges = edges
        self.length_key = length_key
        self.pad_key = pad_key
        self.drop_remainder = drop_remainder
        from analytics_zoo_tpu.data.dataset import default_collate
        self.collate_fn = collate_fn or default_collate
        #: samples truncated to the last edge (observability; reset per
        #: epoch by apply_iter)
        self.truncated = 0

    def _edge_for(self, n: int) -> int:
        return edge_for(n, self.bucket_edges)

    def _make_batch(self, edge: int, samples: List[Dict[str, Any]]):
        rows = []
        lengths = []
        for s in samples:
            arr = np.asarray(s[self.pad_key])
            n = min(int(s[self.length_key]), edge, arr.shape[0])
            padded = np.zeros((edge,) + arr.shape[1:], arr.dtype)
            padded[:n] = arr[:n]
            out = dict(s)
            out[self.pad_key] = padded
            out[self.length_key] = np.int32(n)
            rows.append(out)
            lengths.append(n)
        batch = self.collate_fn(rows)
        if isinstance(batch, dict):
            batch[self.length_key] = np.asarray(lengths, np.int32)
        return batch

    def apply_iter(self, it: Iterator[Any]) -> Iterator[Any]:
        self.truncated = 0
        buckets: Dict[int, List[Any]] = {e: [] for e in self.bucket_edges}
        for sample in it:
            n = int(sample[self.length_key])
            edge = self._edge_for(n)
            if n > edge:
                self.truncated += 1
            buckets[edge].append(sample)
            if len(buckets[edge]) == self.batch_size:
                yield self._make_batch(edge, buckets[edge])
                buckets[edge] = []
        if not self.drop_remainder:
            for edge in self.bucket_edges:
                if buckets[edge]:
                    yield self._make_batch(edge, buckets[edge])


def edge_for(n: int, edges: Sequence[int]) -> int:
    """Smallest bucket edge that fits length ``n`` (the last edge when
    none does — the caller truncates).  THE bucket-assignment rule:
    shared by the train-side :class:`BucketBatcher` and the serving
    batcher (``serving.batcher.DeadlineBatcher``), so online batches
    land on exactly the padded geometries training already compiled."""
    for e in edges:
        if n <= e:
            return e
    return edges[-1]


def padding_efficiency(n_frames, padded_len: int) -> float:
    """valid frames / padded frames for rows padded to ``padded_len`` —
    the host-side waste metric.  The in-graph step metric re-derives the
    same ratio in jnp (``pipelines.deepspeech2.ds2_padding_metric``)."""
    n = np.asarray(n_frames)
    return float(n.sum()) / float(max(n.shape[0] * padded_len, 1))
