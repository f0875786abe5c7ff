"""SSD pipeline: train / test / predict over the TPU runtime.

Port of the reference's L6 pipeline (``pipeline/ssd``): the canonical data
chains (``IOUtils.loadTrainSet/loadValSet``, ``ssd/Utils.scala:56,72``),
``SSDPredictor`` (``ssd/SSDPredictor.scala:30``), ``Validator``
(``ssd/Validator.scala:34`` with its throughput log) and the ``Train``
entry point's optimizer assembly (``ssd/example/Train.scala:140-252``:
optional Adam warm-up to a target mAP, then SGD + MultiStep/Plateau,
per-epoch validation/checkpoint/summaries).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.data import (
    DataSet,
    ParallelTransformer,
    RandomTransformer,
    SSDByteRecord,
    Transformer,
    overlap_window,
    pad_ragged,
)
from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.models import SSDVgg, build_priors, ssd300_config, ssd512_config
from analytics_zoo_tpu.ops import (
    DetectionOutputParam,
    MultiBoxLoss,
    MultiBoxLossParam,
    detection_output,
    scale_detections,
)
from analytics_zoo_tpu.parallel import (
    SGD,
    Adam,
    Optimizer,
    Plateau,
    TrainSummary,
    Trigger,
    ValidationSummary,
    make_eval_step,
    multistep,
)
from analytics_zoo_tpu.pipelines.evaluation import (
    CocoMeanAveragePrecision, DetectionResult, MeanAveragePrecision,
    MultiIoUResult)
from analytics_zoo_tpu.transform.vision import (
    BytesToMat,
    ColorJitter,
    Expand,
    HFlip,
    ImageFeature,
    MatToFloats,
    RandomSampler,
    Resize,
    RoiExpand,
    RoiHFlip,
    RoiLabel,
    RoiNormalize,
)

logger = logging.getLogger("analytics_zoo_tpu")

# Caffe-VGG channel means, BGR (reference PreProcessParam meansRGB defaults)
BGR_MEANS = (104.0, 117.0, 123.0)


@dataclasses.dataclass
class PreProcessParam:
    """Reference ``PreProcessParam`` (``ssd/model/SSDGraph.scala:30``)."""

    batch_size: int = 32
    resolution: int = 300
    pixel_means: Sequence[float] = BGR_MEANS
    n_partition: int = 1
    max_gt: int = 100
    # host augmentation worker threads (SURVEY.md §7.3 hard part 4);
    # 1 = serial (deterministic order), >1 = ParallelTransformer pool
    num_workers: int = 1
    # host augmentation worker PROCESSES (data.parallel.ParallelLoader):
    # 0 = in-process; >0 fans decode+augment out to that many forked
    # workers with shared-memory rings — order-preserving and, unlike
    # the thread pool, deterministically seeded (byte-identical stream
    # for any worker count, seeded from loader_seed).  When set, the
    # thread-pool num_workers is ignored (the process pool replaces it).
    worker_processes: int = 0
    loader_seed: int = 0
    # record-level windowed shuffle (data.ShuffleBuffer) applied to the
    # decoded record stream; 0 disables (file-order shuffle still on).
    # Replaces the global shuffle Spark RDD repartitioning provided.
    shuffle_buffer: int = 0
    shuffle_seed: int = 0
    # device-augmentation staging canvas (None = DeviceAugParam default
    # 512).  Images larger than this are pre-downscaled on host; a tight
    # canvas cuts host→device transfer bytes (the staging tensor is the
    # whole uint8 canvas) at the cost of resolution for oversized images.
    canvas_size: Optional[int] = None
    # staged-pixel wire format for the device-aug path ("bgr" | "yuv420");
    # see DeviceAugParam.wire_format — "yuv420" halves host→device bytes
    wire_format: str = "bgr"
    # pack the device-aug staged batch into one (B, item_bytes) uint8
    # transfer (DeviceAugParam.pack): wins when per-transfer latency,
    # not bandwidth, bounds the input link
    pack_staging: bool = False
    # length-bucketed batching edges (data.bucket.BucketBatcher) for
    # variable-length pipelines — consumed by the DS2 ASR loader
    # (pipelines.deepspeech2.load_asr_train_set(param=...)); the fixed-
    # resolution SSD/FRCNN image chains have no length axis and ignore it
    bucket_edges: Optional[Sequence[int]] = None

    def __post_init__(self):
        # fail fast on the serving path too — a typo'd wire_format would
        # otherwise silently fall through to the 3 B/px bgr wire (the
        # train path already validates via DeviceAugParam.__post_init__)
        if self.wire_format not in ("bgr", "yuv420"):
            raise ValueError(f"unknown wire_format {self.wire_format!r}; "
                             "expected 'bgr' or 'yuv420'")


class RecordToFeature(Transformer):
    """SSDByteRecord → ImageFeature (reference ``RecordToFeature.scala:28``)."""

    def transform(self, record: SSDByteRecord) -> ImageFeature:
        f = ImageFeature(record.data, path=record.path)
        gt = record.gt if record.gt is not None else np.zeros((0, 6), np.float32)
        f["label"] = RoiLabel.from_gt_matrix(gt)
        return f


class RoiImageToBatch(Transformer):
    """Batch ImageFeatures into padded device-ready dicts — the
    ``SSDMiniBatch`` equivalent (reference ``RoiImageToBatch.scala:41``,
    ``Types.scala:41``): CHW float pack becomes NHWC stack; the ragged
    7-col label matrix becomes (B, max_gt, ·) + mask (SURVEY.md §7.3)."""

    def __init__(self, batch_size: int, max_gt: int = 100,
                 keep_label: bool = True, drop_remainder: bool = True):
        self.batch_size = batch_size
        self.max_gt = max_gt
        self.keep_label = keep_label
        self.drop_remainder = drop_remainder

    def _usable(self, f: ImageFeature) -> bool:
        # invalid features stay in the batch ONLY once MatToFloats has
        # zero-filled them — callers' outputs stay index-aligned
        return f.is_valid or f.get("floats") is not None

    def apply_iter(self, it):
        buf: List[ImageFeature] = []
        for f in it:
            if not self._usable(f):
                continue
            buf.append(f)
            if len(buf) == self.batch_size:
                yield self.collate(buf)
                buf = []
        if buf and not self.drop_remainder:
            yield self.collate(buf)

    def collate(self, feats: Sequence[ImageFeature]) -> Dict:
        imgs = np.stack([f["floats"] for f in feats]).astype(np.float32)
        im_info = np.stack([f.get_im_info() for f in feats])
        batch = {"input": imgs, "im_info": im_info}
        if self.keep_label:
            boxes, labels, difficult = [], [], []
            for f in feats:
                lab = f.label if isinstance(f.label, RoiLabel) else RoiLabel(
                    np.zeros(0), np.zeros((0, 4)))
                boxes.append(lab.bboxes)
                labels.append(lab.labels.reshape(-1, 1))
                difficult.append(lab.difficult.reshape(-1, 1))
            b, mask = pad_ragged(boxes, self.max_gt)
            l, _ = pad_ragged(labels, self.max_gt)
            d, _ = pad_ragged(difficult, self.max_gt)
            batch["target"] = {
                "bboxes": b, "labels": l[..., 0].astype(np.int32),
                "difficult": d[..., 0], "mask": mask,
            }
        return batch


def train_transformer(param: PreProcessParam) -> Transformer:
    """The canonical SSD augmentation chain (reference
    ``IOUtils.loadTrainSet:56``): RecordToFeature -> BytesToMat ->
    RoiNormalize -> ColorJitter -> Random(Expand->RoiExpand) ->
    RandomSampler -> Resize(random interp) -> Random(HFlip->RoiHFlip) ->
    MatToFloats(mean subtract)."""
    return (
        RecordToFeature()
        >> BytesToMat()
        >> RoiNormalize()
        >> ColorJitter()
        >> RandomTransformer(Expand(means=param.pixel_means) >> RoiExpand(), 0.5)
        >> RandomSampler()
        >> Resize(param.resolution, param.resolution, interp=-1)
        >> RandomTransformer(HFlip() >> RoiHFlip(), 0.5)
        >> MatToFloats(mean=param.pixel_means,
                       valid_height=param.resolution,
                       valid_width=param.resolution)
    )


def val_transformer(param: PreProcessParam,
                    flip: bool = False) -> Transformer:
    """Validation chain without augmentation (reference ``loadValSet:72``).

    ``flip=True`` inserts a random horizontal flip before the float
    extraction — the resize-only TRAIN chain
    (``load_train_set(augment=False)``) shares this one implementation
    so train/val preprocessing can never skew."""
    chain = (
        RecordToFeature()
        >> BytesToMat()
        >> RoiNormalize()
        >> Resize(param.resolution, param.resolution)
    )
    if flip:
        # before MatToFloats: the float tensor is extracted there, so a
        # later mat flip would desync pixels from the flipped labels
        chain = chain >> RandomTransformer(HFlip() >> RoiHFlip(), 0.5)
    return chain >> MatToFloats(mean=param.pixel_means,
                                valid_height=param.resolution,
                                valid_width=param.resolution)


def _maybe_parallel(t: Transformer, workers: int) -> Transformer:
    return ParallelTransformer(t, workers) if workers > 1 else t


def _maybe_loader(ds: DataSet, param: PreProcessParam):
    """Wrap the assembled dataset in the multiprocess loader when the
    param asks for worker processes (docs/PERFORMANCE.md "Host input
    pipeline"); otherwise return the DataSet unchanged."""
    if param.worker_processes > 0:
        return ds.parallel(param.worker_processes,
                           base_seed=param.loader_seed)
    return ds


def load_train_set_device(pattern: str, param: PreProcessParam,
                          aug: Optional["DeviceAugParam"] = None):
    """Device-augmentation train path (``transform/vision/device.py``):
    host does decode + geometry/label math; all pixel work runs on-chip.
    Returns (DataSet of staging batches, jitted augment fn).

    Supported usage: pass the augment fn as ``device_transform=`` to the
    ``Optimizer`` / ``make_train_step`` so it FUSES into the compiled
    train step (one dispatch per iteration).  Applying it manually per
    batch also works (e.g. for inspection) but costs an extra dispatch —
    don't do both."""
    from analytics_zoo_tpu.transform.vision import (DeviceAugBatch,
                                                    DeviceAugParam,
                                                    DeviceAugPrepare,
                                                    make_device_augment)

    if aug is None:
        extra = ({"canvas_size": param.canvas_size}
                 if param.canvas_size else {})
        aug = DeviceAugParam(resolution=param.resolution,
                             pixel_means=tuple(param.pixel_means),
                             wire_format=param.wire_format,
                             pack=param.pack_staging, **extra)
    chain = (RecordToFeature() >> BytesToMat(to_float=False) >> RoiNormalize()
             >> DeviceAugPrepare(aug))
    ds = DataSet.from_record_files(pattern, SSDByteRecord.decode,
                                   shuffle_files=True)
    if param.shuffle_buffer:
        ds = ds.shuffle(param.shuffle_buffer, seed=param.shuffle_seed)
    ds = (ds.transform(_maybe_parallel(chain, param.num_workers))
          .transform(DeviceAugBatch(param.batch_size, param.max_gt,
                                    pack=aug.pack)))
    return _maybe_loader(ds, param), make_device_augment(aug)


def _warn_host_chain_ignores_wire(param: PreProcessParam, fn: str) -> None:
    # The host-aug chains always ship plain bgr float batches; silently
    # dropping a requested yuv420/packed wire would make callers believe
    # they measured the thin wire.  Mirror the FrcnnPredictor guard:
    # loud, not fatal.
    if param.wire_format != "bgr" or param.pack_staging:
        import warnings
        warnings.warn(
            f"{fn}: wire_format={param.wire_format!r} / "
            f"pack_staging={param.pack_staging} are device-aug options; the "
            "host-aug chain ignores them (use load_train_set_device)",
            stacklevel=3)


def load_train_set(pattern: str, param: PreProcessParam,
                   augment: bool = True) -> DataSet:
    """``augment=False`` keeps the TRAINING conveniences (file shuffling,
    shuffle buffer, random flip, drop_remainder batching — one compiled
    shape) but swaps the heavy geometric chain (Expand zoom-out + crop
    samplers) for a plain resize: detectors whose feature stride is
    coarse relative to the image (e.g. Faster-RCNN at small
    resolutions) lose their objects below the feature grid under
    zoom-out augmentation."""
    _warn_host_chain_ignores_wire(param, "load_train_set")
    ds = DataSet.from_record_files(pattern, SSDByteRecord.decode,
                                   shuffle_files=True)
    if param.shuffle_buffer:
        ds = ds.shuffle(param.shuffle_buffer, seed=param.shuffle_seed)
    chain = (train_transformer(param) if augment
             else val_transformer(param, flip=True))
    if param.worker_processes > 0:
        # strip decode bytes + working mat (im_info materialized first)
        # so the shared-memory ring ships only what the batcher reads
        from analytics_zoo_tpu.transform.vision import SealForWire
        chain = chain >> SealForWire()
    return _maybe_loader(
        ds.transform(_maybe_parallel(chain, param.num_workers))
        .transform(RoiImageToBatch(param.batch_size, param.max_gt)), param)


def load_val_set(pattern: str, param: PreProcessParam) -> DataSet:
    # no wire guard here: device-aug training legitimately shares one
    # PreProcessParam between load_train_set_device and this val loader
    # (examples/train_ssd.py), and validation has no device-aug variant
    # to redirect to
    chain = val_transformer(param)
    if param.worker_processes > 0:
        # same wire shrink as load_train_set: RoiImageToBatch reads only
        # floats/im_info/labels, so the decode bytes + working mat are
        # dead weight through the shared-memory ring (and raw JPEG bytes
        # pickle IN-BAND — they would blow the slot budget)
        from analytics_zoo_tpu.transform.vision import SealForWire
        chain = chain >> SealForWire()
    return _maybe_loader(
        DataSet.from_record_files(pattern, SSDByteRecord.decode)
        .transform(_maybe_parallel(chain, param.num_workers))
        .transform(RoiImageToBatch(param.batch_size, param.max_gt,
                                   drop_remainder=False)), param)


class SSDPredictor:
    """Distributed inference (reference ``SSDPredictor.scala:30``): jitted
    forward + in-graph DetectionOutput, detections rescaled to original
    image size via im_info (``BboxUtil.scaleBatchOutput``)."""

    def __init__(self, model: Model, param: PreProcessParam,
                 post: Optional[DetectionOutputParam] = None,
                 n_classes: int = 21, compute_dtype=None,
                 quantize=False, specs=None):
        """``quantize``: ``False`` (fp serving), ``True``/``"weight"``
        (int8 weights in HBM, fp math — bandwidth compression), or
        ``"int8"`` (real int8×int8→int32 convolutions on the MXU with
        dynamic per-tensor activation quantization).

        ``specs`` (:class:`~analytics_zoo_tpu.parallel.specs.SpecSet`):
        serve over a sharded mesh — the jitted detect program is
        annotated with the declared shardings (variables replicated,
        batch dim-0 over ``data``), so widening the mesh widens serving
        with no predictor change.  The predictor itself never calls
        ``device_put``; placement lives in the spec layer only."""
        self.model = model
        self.param = param
        self.specs = specs
        self.post = post or DetectionOutputParam(n_classes=n_classes)
        priors, variances = build_priors(
            ssd300_config() if param.resolution == 300 else ssd512_config())
        # host numpy on purpose: jit embeds it directly, where a
        # COMMITTED device array closed into the jitted _detect would be
        # fetched back from its device at every trace
        self._priors = np.asarray(priors)
        self._variances = np.asarray(variances)
        # quantized mode snapshots int8 weights and drops the Model
        # reference so the caller CAN release the fp32 tree (otherwise the
        # 4x HBM saving never materializes); fp32 mode reads
        # model.variables at call time so later load_weights take effect
        self._variables = None
        if quantize:
            from analytics_zoo_tpu.parallel.train import resolve_compute_dtype
            from analytics_zoo_tpu.utils.quantize import (
                make_quantized_forward, quantize_params)
            self._variables = quantize_params(model.variables)
            self._eval_step = make_quantized_forward(
                model.module, resolve_compute_dtype(compute_dtype),
                compute="int8" if quantize == "int8" else "dequant")
            self.model = None
        else:
            self._eval_step = make_eval_step(model.module,
                                             compute_dtype=compute_dtype)

    def set_top_k(self, k: int) -> "SSDPredictor":
        """Return a predictor serving ``keep_topk=k`` (reference
        ``setTopK``, which mutates the DetectionOutput layer in place).

        Copy-on-write on purpose: the RECEIVER is unchanged.  Serving
        tiers close over a shared predictor and read ``self.post`` at
        dispatch time, so the old in-place mutation silently changed
        every tier's output geometry and forced a recompile of each
        tier's serving program (``post`` is a static jit argument).
        The returned copy shares weights and the cached jitted
        programs — ``post`` is an argument, so no recompile of the
        receiver's geometry ever happens."""
        import copy

        new = copy.copy(self)
        new.post = dataclasses.replace(self.post, keep_topk=k)
        return new

    def _serving_jit(self, fn, static_argnums, n_batch_args: int):
        """jit a serving program through the spec layer: with a declared
        SpecSet the program carries in_shardings (variables replicated,
        the ``n_batch_args`` leading batch-major array args dim-0 over
        ``data``); batches whose dim 0 doesn't divide the data axis
        (ragged predict tails) fall back to the un-annotated program.
        No SpecSet → the legacy single-program jit."""
        plain = jax.jit(fn, static_argnums=static_argnums)
        if self.specs is None:
            return plain
        annotated = jax.jit(
            fn, static_argnums=static_argnums,
            in_shardings=(self.specs.replicated,)
            + (self.specs.data_sharding,) * n_batch_args)
        return self.specs.ragged_dispatch(annotated, plain)

    @functools.cached_property
    def _detect(self):
        """ONE jitted program for forward + softmax + DetectionOutput +
        rescale.  Every dispatch pays a fixed launch cost and every
        eager op between two programs materializes its operands in HBM,
        so serving is a single call per batch, not a chain of eager ops
        (the in-graph-DetectionOutput philosophy the reference applies by
        making post-processing a model layer, ``SSDGraph.scala``)."""
        means = np.asarray(self.param.pixel_means, np.float32)
        tail = self._forward_tail

        def detect(variables, inputs, h, w, post):
            if inputs.dtype == jnp.uint8:
                # uint8 staging path: normalize ON DEVICE (host sends 4×
                # fewer bytes; MatToFloats semantics, in-graph)
                with jax.named_scope("ssd/normalize"):
                    inputs = inputs.astype(jnp.float32) - means
            return tail(variables, inputs, h, w, post)

        return self._serving_jit(detect, static_argnums=(4,),
                                 n_batch_args=3)

    @property
    def _forward_tail(self):
        """Shared post-input serving pipeline (forward + softmax +
        DetectionOutput + rescale) closed over by every staging variant —
        one place to change, no way for the wire paths to diverge."""
        eval_step = self._eval_step
        priors, variances = self._priors, self._variances

        def tail(variables, inputs, h, w, post):
            # the model's own sections (ssd/base, ssd/extras, ssd/heads)
            # and three more of the compiled program
            # (obs/names.py::SCOPES)
            loc, conf = eval_step(variables, inputs)
            with jax.named_scope("ssd/softmax"):
                probs = jax.nn.softmax(conf, axis=-1)
            with jax.named_scope("ssd/detout"):
                dets = detection_output(loc, probs, priors, variances, post)
            with jax.named_scope("ssd/rescale"):
                return scale_detections(dets, h, w)

        return tail

    @functools.cached_property
    def _detect_yuv(self):
        """yuv420-staged variant: the host ships Y + 2×2-subsampled
        chroma (1.5 B/px — half the uint8 staging bytes); BGR
        reconstruction, normalize, forward and DetectionOutput all run
        in the ONE jitted program."""
        from analytics_zoo_tpu.transform.vision.device import (
            yuv420_to_bgr_device)

        means = np.asarray(self.param.pixel_means, np.float32)
        tail = self._forward_tail

        def detect(variables, y, uv, h, w, post):
            with jax.named_scope("ssd/normalize"):
                bgr = yuv420_to_bgr_device(y, uv) - means
            return tail(variables, bgr, h, w, post)

        return self._serving_jit(detect, static_argnums=(5,),
                                 n_batch_args=4)

    def detect_normalized(self, inputs) -> jnp.ndarray:
        """Forward + softmax + DetectionOutput → (B, K, 6) normalized-box
        detections (shared by predict and Validator so serving and eval
        can't diverge)."""
        variables = (self._variables if self._variables is not None
                     else self.model.variables)
        ones = jnp.ones((inputs.shape[0],), jnp.float32)
        return self._detect(variables, jnp.asarray(inputs), ones, ones,
                            self.post)

    def _detect_device(self, batch: Dict) -> jnp.ndarray:
        """Dispatch one batch; returns the (B, K, 6) device array WITHOUT
        forcing a host sync (jax dispatch is async — callers can overlap
        the next batch's host prep with this one's device execution)."""
        variables = (self._variables if self._variables is not None
                     else self.model.variables)
        # rescale normalized boxes to ORIGINAL pixel sizes: im_info rows are
        # (h, w, scale_h, scale_w); original = current / scale
        h = batch["im_info"][:, 0] / np.maximum(batch["im_info"][:, 2], 1e-8)
        w = batch["im_info"][:, 1] / np.maximum(batch["im_info"][:, 3], 1e-8)
        if "input_uv" in batch:
            return self._detect_yuv(variables, jnp.asarray(batch["input"]),
                                    jnp.asarray(batch["input_uv"]),
                                    jnp.asarray(h), jnp.asarray(w), self.post)
        return self._detect(variables, jnp.asarray(batch["input"]),
                            jnp.asarray(h), jnp.asarray(w), self.post)

    def detect_batch(self, batch: Dict) -> np.ndarray:
        return np.asarray(self._detect_device(batch))

    def predict(self, records) -> List[np.ndarray]:
        """records: iterable of SSDByteRecord → per-image (K, 6) arrays.

        Uses the uint8 staging chain: pixels stay uint8 from decode to
        device, normalize runs in-graph (4× fewer host→device bytes)."""
        return run_serving_loop(
            serving_chain(self.param, uint8=True)(records),
            self._detect_device, np.asarray)


class Uint8ToBatch(RoiImageToBatch):
    """Serving-path batcher: stacks RESIZED uint8 mats + im_info.

    Staging uint8 instead of mean-subtracted float32 sends 4× fewer
    host→device bytes; the cast + mean-subtract runs inside the jitted
    serving program (``SSDPredictor._detect``).

    Invalid (decode-failed) records become zero images so predict()
    outputs stay index-aligned with the input records — the same
    contract ``MatToFloats`` gives the float chain (reference
    ``Convertor.scala:74-84``)."""

    def __init__(self, batch_size: int, resolution: int,
                 drop_remainder: bool = False, wire_format: str = "bgr"):
        super().__init__(batch_size, keep_label=False,
                         drop_remainder=drop_remainder)
        self.resolution = resolution
        if wire_format == "yuv420" and resolution % 2:
            raise ValueError("yuv420 serving needs an even resolution, "
                             f"got {resolution}")
        self.wire_format = wire_format

    def _usable(self, f: ImageFeature) -> bool:
        return True                     # invalid → zero image in collate

    def apply_iter(self, it):
        # A final partial batch would be a NEW shape — one extra XLA
        # compile of the whole fused serving program per distinct
        # remainder size (minutes on a cold cache).  Pad it to
        # ``batch_size`` with zero images (the existing invalid-record
        # convention) and record the true count; ``run_serving_loop``
        # slices the outputs back.
        for batch in super().apply_iter(it):
            n = batch["input"].shape[0]
            if n < self.batch_size:
                pad = self.batch_size - n

                def _pad(arr, fill=0):
                    return np.concatenate(
                        [arr, np.full((pad,) + arr.shape[1:], fill,
                                      arr.dtype)])

                padded = {"input": _pad(batch["input"]),
                          "im_info": np.concatenate(
                              [batch["im_info"],
                               np.tile(np.array([[self.resolution,
                                                  self.resolution,
                                                  1.0, 1.0]], np.float32),
                                       (pad, 1))]),
                          "n_valid": n}
                if "input_uv" in batch:     # neutral chroma → black pixels
                    padded["input_uv"] = _pad(batch["input_uv"], 128)
                batch = padded
            yield batch

    def collate(self, feats: Sequence[ImageFeature]) -> Dict:
        res = self.resolution
        default_info = np.array([res, res, 1.0, 1.0], np.float32)
        infos = [f.get_im_info() if (f.is_valid and f.mat is not None)
                 else default_info for f in feats]
        if self.wire_format == "yuv420":
            # planes were staged per-feature by Yuv420Staging INSIDE the
            # (possibly parallel) chain; invalid records get black frames
            zero_y = np.zeros((res, res), np.uint8)
            zero_uv = np.full((res // 2, res // 2, 2), 128, np.uint8)
            ys = [f.get("yuv_y", zero_y) if f.is_valid else zero_y
                  for f in feats]
            uvs = [f.get("yuv_uv", zero_uv) if f.is_valid else zero_uv
                   for f in feats]
            return {"input": np.stack(ys), "input_uv": np.stack(uvs),
                    "im_info": np.stack(infos)}
        zero = np.zeros((res, res, 3), np.uint8)
        mats = [f.mat if (f.is_valid and f.mat is not None) else zero
                for f in feats]
        return {"input": np.stack(mats), "im_info": np.stack(infos)}


def serving_chain(param: PreProcessParam, uint8: bool = False,
                  resize: Optional[Transformer] = None):
    """The shared serving preprocess chain (reference ``SSDPredictor.
    scala:55-60``): val transformer + unlabeled batching.

    ``uint8=True`` keeps pixels uint8 end-to-end on the host (decode →
    resize → stack) and defers normalize to the device program.
    ``resize`` overrides the square ``Resize`` (e.g. Faster-RCNN's
    aspect-preserving ``AspectScaleCanvas``) — it must still emit mats of
    exactly ``param.resolution``² so every batch shares one shape."""
    if uint8:
        chain = (RecordToFeature() >> BytesToMat(to_float=False)
                 >> (resize if resize is not None
                     else Resize(param.resolution, param.resolution)))
        if param.wire_format == "yuv420":
            from analytics_zoo_tpu.transform.vision.device import (
                Yuv420Staging)

            chain = chain >> Yuv420Staging()
        return (_maybe_parallel(chain, param.num_workers)
                >> Uint8ToBatch(param.batch_size, param.resolution,
                                wire_format=param.wire_format))
    return (_maybe_parallel(val_transformer(param), param.num_workers)
            >> RoiImageToBatch(param.batch_size, keep_label=False,
                               drop_remainder=False))


def run_serving_loop(batches, dispatch, readback,
                     max_inflight: int = 4) -> List[np.ndarray]:
    """``overlap_window`` specialized to collecting per-image arrays.

    Honors the padded-final-batch convention (``Uint8ToBatch``): a batch
    carrying ``n_valid`` yields only its first ``n_valid`` rows."""
    out: List[np.ndarray] = []

    def dispatch_sliced(batch):
        n = batch.pop("n_valid", None) if isinstance(batch, dict) else None
        return dispatch(batch), n

    def consume(token):
        tok, n = token
        arr = readback(tok)
        out.extend(arr[i] for i in range(arr.shape[0] if n is None else n))

    overlap_window(batches, dispatch_sliced, consume, max_inflight)
    return out


class Validator:
    """Distributed eval with throughput logging (reference
    ``Validator.scala:34,56-86``: forward + evaluator per batch, monoid
    reduce, records/sec accumulator log)."""

    def __init__(self, model: Model, param: PreProcessParam,
                 evaluator: Optional[MeanAveragePrecision] = None,
                 post: Optional[DetectionOutputParam] = None,
                 quantize=False, clock=None):
        """``quantize`` forwards to :class:`SSDPredictor` — evaluate the
        int8 serving modes with the same Validator the fp path uses.
        ``clock``: injected time source for the throughput log (utils.
        clock convention — the one-clock rule bans raw time.time)."""
        from analytics_zoo_tpu.utils.clock import as_now_fn

        self.predictor = SSDPredictor(model, param, post=post,
                                      quantize=quantize)
        self.evaluator = evaluator or MeanAveragePrecision()
        self._now = as_now_fn(clock)

    def test(self, dataset) -> DetectionResult:
        total: Optional[DetectionResult] = None
        n_records = 0
        t0 = self._now()

        def dispatch(batch):
            nonlocal n_records
            n_records += batch["input"].shape[0]
            return self.predictor.detect_normalized(batch["input"]), batch

        def consume(token):
            nonlocal total
            dets, batch = token
            r = self.evaluator(np.asarray(dets), batch)
            total = r if total is None else total + r

        # dispatch-ahead window: the next batches' forwards overlap this
        # one's readback + host-side eval
        overlap_window(dataset, dispatch, consume)
        dt = self._now() - t0
        logger.info("[Prediction] %d in %.2f seconds. Throughput is %.2f "
                    "records/sec", n_records, dt, n_records / max(dt, 1e-9))
        return total


class SSDMeanAveragePrecision:
    """ValidationMethod adapter for the Optimizer's validation loop: the
    raw SSDVgg output is (loc, conf) logits, so decode + NMS runs here
    before delegating to MeanAveragePrecision (the reference's
    MeanAveragePrecision similarly decodes inside the ValidationMethod,
    ``DetectionResult.scala`` → ``BboxUtil.decodeBatchOutput``)."""

    def __init__(self, n_classes: int = 21, resolution: int = 300,
                 post: Optional[DetectionOutputParam] = None,
                 use_07_metric: bool = True, metric: str = "voc"):
        if metric == "coco":
            self.inner = CocoMeanAveragePrecision(n_classes=n_classes)
        elif metric == "voc":
            self.inner = MeanAveragePrecision(n_classes=n_classes,
                                              use_07_metric=use_07_metric)
        else:
            raise ValueError(f"metric must be 'voc' or 'coco', got {metric!r}")
        self.post = post or DetectionOutputParam(n_classes=n_classes)
        priors, variances = build_priors(
            ssd300_config() if resolution == 300 else ssd512_config())
        # host numpy (see SSDPredictor: jit embeds numpy constants
        # directly)
        self._priors = np.asarray(priors)
        self._variances = np.asarray(variances)
        self.name = self.inner.name

    def __call__(self, output, batch) -> "DetectionResult | MultiIoUResult":
        loc, conf = output
        probs = jax.nn.softmax(conf, axis=-1)
        dets = detection_output(loc, probs, self._priors, self._variances,
                                self.post)
        return self.inner(np.asarray(dets), batch)


@dataclasses.dataclass
class TrainParams:
    """Reference ``TrainParams`` (``ssd/example/Train.scala:39``)."""

    batch_size: int = 32
    resolution: int = 300
    n_classes: int = 21
    learning_rate: float = 0.0035
    momentum: float = 0.9
    weight_decay: float = 0.0005
    max_epoch: int = 250
    schedule: str = "plateau"           # 'plateau' | 'multistep'
    lr_steps: Sequence[int] = ()
    warm_up_map: Optional[float] = None  # Adam warm-up target mAP
    warm_up_lr: float = 1e-4
    checkpoint_path: Optional[str] = None
    overwrite_checkpoint: bool = True
    log_dir: Optional[str] = None
    job_name: str = "ssd300"
    max_gt: int = 100
    # MXU-native mixed precision (fp32 masters, bf16 compute); None = fp32
    compute_dtype: Optional[str] = "bf16"
    # background shard+transfer depth (Optimizer prefetch); 0 = sync
    prefetch: int = 2


def train_ssd(train_set, val_set, params: TrainParams,
              model: Optional[Model] = None, mesh=None,
              device_transform: Optional[Callable] = None,
              tp: Optional[str] = None) -> Model:
    """The Train entry point's optimize() assembly (reference
    ``Train.scala:150-252``).

    ``device_transform``: the jitted augment returned by
    ``load_train_set_device`` — fuses the on-device augmentation into
    every compiled train step (pass the matching staged ``train_set``).

    Sharding is declared ONCE through the spec registry
    (``pipeline_specs("ssd", ...)``) and consumed by the Optimizer's
    annotated jit — this entry point performs no device placement.
    ``tp``: ``None`` (data parallel) | ``"spatial"`` (image height over
    the ``model`` axis) | ``"megatron"`` (paired col/row weight
    sharding); parallelism modes compose by changing the MESH SHAPE
    (e.g. ``create_mesh((2, 4), axis_names=("data", "model"))``), not
    this function."""
    from analytics_zoo_tpu.parallel import pipeline_specs

    specs = pipeline_specs("ssd", mesh=mesh, tp=tp,
                           resolution=params.resolution)
    cfg = (ssd300_config() if params.resolution == 300 else ssd512_config())
    priors, variances = build_priors(cfg)
    criterion = MultiBoxLoss(priors, variances,
                             MultiBoxLossParam(n_classes=params.n_classes))
    if model is None:
        model = Model(SSDVgg(num_classes=params.n_classes,
                             resolution=params.resolution))
        model.build(0, jnp.zeros((1, params.resolution, params.resolution, 3)))

    evaluator = SSDMeanAveragePrecision(n_classes=params.n_classes,
                                        resolution=params.resolution)

    def run(optim_method, end_when):
        opt = (Optimizer(model, train_set, criterion, specs=specs,
                         skip_loss_above=50.0,
                         compute_dtype=params.compute_dtype,
                         prefetch=params.prefetch,
                         device_transform=device_transform)
               .set_optim_method(optim_method)
               .set_end_when(end_when))
        if val_set is not None:
            opt.set_validation(Trigger.every_epoch(), val_set, [evaluator])
        if params.checkpoint_path:
            opt.set_checkpoint(params.checkpoint_path, Trigger.every_epoch(),
                               overwrite=params.overwrite_checkpoint)
        summaries = []
        if params.log_dir:
            summaries = [TrainSummary(params.log_dir, params.job_name),
                         ValidationSummary(params.log_dir, params.job_name)]
            opt.set_train_summary(summaries[0])
            opt.set_validation_summary(summaries[1])
        try:
            opt.optimize()
        finally:
            # this function opened the event files, so it closes them:
            # the writer flushes on a timer, and a caller reading the
            # run's scalars back must not race it
            for summary in summaries:
                summary.close()

    # optional Adam warm-up until a target mAP (reference Train.scala:178-187)
    if params.warm_up_map is not None and val_set is not None:
        logger.info("warm-up with Adam until mAP >= %.3f", params.warm_up_map)
        run(Adam(params.warm_up_lr),
            Trigger.or_(Trigger.max_score(params.warm_up_map),
                        Trigger.max_epoch(params.max_epoch)))

    if params.schedule == "multistep" and params.lr_steps:
        optim = SGD(params.learning_rate, momentum=params.momentum,
                    weight_decay=params.weight_decay,
                    schedule=multistep(params.learning_rate, params.lr_steps,
                                       0.1))
    else:
        optim = SGD(params.learning_rate, momentum=params.momentum,
                    weight_decay=params.weight_decay,
                    plateau=Plateau(monitor="score", factor=0.5, patience=10,
                                    mode="max", min_lr=1e-5))
    run(optim, Trigger.max_epoch(params.max_epoch))
    return model


def ssd_serving_tiers(model: Model, param: PreProcessParam,
                      post: Optional[DetectionOutputParam] = None,
                      n_classes: int = 21, compute_dtype=None,
                      degraded_topk: int = 50, specs=None) -> List:
    """Degradation-ladder rungs for the online serving runtime
    (``serving.ServingRuntime``): three :class:`~analytics_zoo_tpu.
    serving.ladder.ServingTier` s over the SAME ``SSDPredictor`` serving
    program, cheapest last.

    - tier 0 ``fp``: full-precision weights, full NMS ``keep_topk``;
    - tier 1 ``int8``: weight-only int8 via ``quantize_params`` (~4×
      fewer parameter bytes; mAP delta +0.0001 —
      INT8_MAP_PARITY.json);
    - tier 2 ``int8_topk``: int8 plus ``keep_topk=degraded_topk`` — a
      bounded, explicit post-processing cut (reference ``setTopK``).

    All three rungs dispatch whatever DetectionOutput backend ``post``
    selects — with the default ``backend="auto"`` that is the FUSED
    single-kernel post-processing program on a TPU backend
    (``ops/pallas_detout.py``; pass ``post=DetectionOutputParam(
    backend="fused")`` to force it elsewhere, interpret-mode off-TPU),
    so the int8 rung's conv win is no longer buried under four staged
    post-processing dispatches (docs/PERFORMANCE.md "DetectionOutput").
    The ``device_program`` thunks below expose exactly those fused
    programs to the az-analyze serving audit.

    Requests carry preprocessed fixed-resolution images
    (``{"input": (H, W, 3) float32}``, no variable axis — the serving
    batcher's FIXED bucket); every tier's forward is jit-compiled once
    per (tier, batch) geometry, which the runtime pins by always padding
    the batch axis to ``max_batch``.  ``speed`` values are relative
    service-time HINTS for the batcher's flush heuristic, not readings
    — the EWMA refines them online.

    ``specs`` (:class:`~analytics_zoo_tpu.parallel.specs.SpecSet`, e.g.
    ``pipeline_specs("ssd", mesh=mesh)``): every tier's detect program
    is then mesh-annotated (variables replicated, batch over ``data``)
    — serving scales out by widening the mesh, with the spec layer as
    the only placement site.
    """
    import copy

    from analytics_zoo_tpu.serving.ladder import ServingTier

    full = SSDPredictor(model, param, post=post, n_classes=n_classes,
                        compute_dtype=compute_dtype, specs=specs)
    int8 = SSDPredictor(model, param, post=post, n_classes=n_classes,
                        compute_dtype=compute_dtype, quantize=True,
                        specs=specs)
    # tier 2 shares tier 1's quantized variables (no second quantize
    # pass); only the DetectionOutput param differs — `post` is a static
    # jit argument, so the shared program specializes per tier
    low = copy.copy(int8)
    low.post = dataclasses.replace(int8.post, keep_topk=degraded_topk)

    def place(batch: Dict) -> Dict:
        """``ServingTier.place``: the transfer ``forward`` would start."""
        return {"input": jnp.asarray(batch["input"])}

    def fwd(pred: SSDPredictor) -> Callable[[Dict], jnp.ndarray]:
        def forward(batch: Dict) -> jnp.ndarray:
            # two stages of one batch: the host's side of the transfer
            # (staging and enqueue; free where the runtime placed the
            # batch ahead, as detect_normalized's own jnp.asarray of a
            # device array is), and the program's asynchronous dispatch
            # with the start of the answer's copy.  The answer goes back
            # as the device array: the replica fetches it
            # (az/serve/result_wait), and the runtime assembles the next
            # batch in between
            with stage("az/serve/h2d"):
                x = jnp.asarray(batch["input"])
            with stage("az/serve/dispatch"):
                out = pred.detect_normalized(x)
                out.copy_to_host_async()
            return out
        return forward

    def audit(pred: SSDPredictor, rows: Optional[int] = None
              ) -> Callable[[], tuple]:
        """``az_analyze --program`` hook: the tier's actual jitted
        detect program + shape-only example args (ShapeDtypeStructs —
        the audit traces, it never dispatches).  ``rows``: the batch of
        a runtime's geometry (``ServingTier.device_program_for``: the
        program a trace of that runtime ran); the audit's own is the
        smallest the data axis divides."""
        def device_program():
            B = rows or (pred.specs.data_axis_size
                         if pred.specs is not None else 1)
            res = pred.param.resolution
            variables = (pred._variables if pred._variables is not None
                         else pred.model.variables)
            S = jax.ShapeDtypeStruct
            ones = S((B,), jnp.float32)
            return (pred._detect,
                    (variables, S((B, res, res, 3), jnp.float32),
                     ones, ones, pred.post),
                    (4,))
        return device_program

    # the transfer ahead is offered off a mesh only: with specs= the
    # annotated program places its batch over the data axis itself
    ahead = place if specs is None else None

    def tier(name: str, pred: SSDPredictor, speed: float,
             note: str) -> ServingTier:
        # the FIXED bucket has one edge: a geometry is its rows
        return ServingTier(
            name, fwd(pred), speed=speed, quality_note=note,
            device_program=audit(pred), place=ahead,
            device_program_for=lambda edge, rows: audit(pred, rows))

    return [
        tier("fp", full, 1.0, "full precision, full NMS top-K"),
        tier("int8", int8, 0.77,
             "int8 weights, fp math (mAP delta +0.0001, "
             "INT8_MAP_PARITY.json)"),
        tier(f"int8_topk{degraded_topk}", low, 0.7,
             f"int8 + keep_topk={degraded_topk} (fewer kept detections "
             "per image)"),
    ]
