"""Device-side augmentation: the TPU-native answer to HOT LOOP #1.

The reference runs the whole SSD augmentation chain per image on host CPU
through OpenCV JNI (SURVEY.md §3.1 HOT LOOP #1; chain
``ssd/Utils.scala:56``), which is fine with 28-core Xeon executors but
starves an accelerator whose host has few cores (SURVEY.md §7.3 hard
part 4).  This module splits the chain TPU-first:

* **Host** (cheap, per image): JPEG decode, the *geometry decisions*
  (expand ratio/offset, the 7-sampler constrained crop, flip coin, color
  jitter parameters) and the label re-projections — all label/scalar
  math, no pixel work except one uint8 paste into a fixed canvas.
* **Device** (one jitted, vmapped program over the batch): color jitter
  (brightness/contrast/saturation/hue in the reference's two orders),
  crop+resize as a bilinear gather with channel-mean border fill (the
  Expand canvas is never materialized — sampling outside the image IS
  the mean-filled expand), horizontal flip, mean subtraction.

Semantics match ``augmentation.py``'s host ops distributionally: the same
random decisions drive both paths (identical label projections —
reused code), while pixel interpolation is bilinear (vs the host chain's
random cv2 interp mode) and saturation/hue run in float HSV rather than
OpenCV's uint8 round-trip.  ``tests/test_device_aug.py`` pins the parity
bounds.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Sequence

import numpy as np

from analytics_zoo_tpu.transform.vision.image import (FeatureTransformer,
                                                      ImageFeature)
from analytics_zoo_tpu.transform.vision.roi import (
    RoiLabel,
    meet_emit_center_constraint,
    project_bbox,
)
from analytics_zoo_tpu.transform.vision.sampler import (
    BatchSampler,
    generate_batch_samples,
    standard_samplers,
)

BGR_MEANS = (104.0, 117.0, 123.0)


@dataclasses.dataclass
class DeviceAugParam:
    """Knobs mirroring the canonical train chain (``ssd/Utils.scala:59``)."""

    resolution: int = 300
    canvas_size: int = 512          # fixed host→device staging canvas
    pixel_means: Sequence[float] = BGR_MEANS
    # Host→device wire format for the staged pixels.  "bgr" ships the
    # uint8 canvas as-is (3 bytes/px).  "yuv420" ships a full-res luma
    # plane plus 2×2-subsampled chroma (1.5 bytes/px — the same
    # decimation JPEG itself stores, so for JPEG-sourced images the
    # extra loss is ~quantization only) and reconstructs BGR on-device
    # inside the fused augmentation program.  Halves host→device bytes:
    # the lever when the input link (PCIe) — not host CPU — bounds
    # end-to-end training throughput.
    wire_format: str = "bgr"
    # Pack the whole staged batch into ONE (B, item_bytes) uint8 array:
    # a single host→device transfer per batch instead of ~11 per-leaf
    # transfers.  Where per-transfer overhead — not bandwidth —
    # dominates the input path this wins; on a local chip it is not
    # measured.  The device program unpacks by
    # slice + bitcast inside the fused augmentation, so nothing else in
    # the step changes.  Row-major (B first) keeps data-parallel dim-0
    # sharding working unchanged.
    pack: bool = False

    def __post_init__(self):
        # fail fast: inside the pipeline these would be caught by the
        # per-record exception isolator and silently drop every record
        if self.wire_format not in ("bgr", "yuv420"):
            raise ValueError(f"unknown wire_format {self.wire_format!r}; "
                             "expected 'bgr' or 'yuv420'")
        if self.wire_format == "yuv420" and self.canvas_size % 2:
            raise ValueError("yuv420 wire format needs an even "
                             f"canvas_size, got {self.canvas_size}")
    expand_prob: float = 0.5
    max_expand_ratio: float = 4.0
    hflip_prob: float = 0.5
    brightness_prob: float = 0.5
    brightness_delta: float = 32.0
    contrast_prob: float = 0.5
    contrast_range: Sequence[float] = (0.5, 1.5)
    saturation_prob: float = 0.5
    saturation_range: Sequence[float] = (0.5, 1.5)
    hue_prob: float = 0.5
    hue_delta: float = 18.0


def bgr_to_yuv420_host(mat: np.ndarray):
    """uint8 BGR (H,W,3) → (Y (H,W), CrCb (⌈H/2⌉,⌈W/2⌉,2)) uint8 planes:
    full-range BT.601 luma plus 2×2 box-filtered chroma — the same
    decimation a JPEG encoder applies, so for JPEG-sourced images the
    round-trip loses ~quantization only."""
    import cv2

    h, w = mat.shape[:2]
    ycrcb = cv2.cvtColor(mat, cv2.COLOR_BGR2YCrCb)
    chroma = cv2.resize(ycrcb[:, :, 1:], ((w + 1) // 2, (h + 1) // 2),
                        interpolation=cv2.INTER_AREA)
    return ycrcb[:, :, 0], chroma.reshape((h + 1) // 2, (w + 1) // 2, 2)


def yuv420_to_bgr_device(y, uv):
    """Device half of the yuv420 wire: nearest 2× chroma upsample +
    OpenCV's full-range BT.601 YCrCb→BGR affine, clipped to [0,255] so
    downstream math sees uint8-canvas semantics.  Returns float32 BGR."""
    import jax.numpy as jnp

    yf = y.astype(jnp.float32)
    uvf = uv.astype(jnp.float32)
    uvf = jnp.repeat(jnp.repeat(uvf, 2, axis=-3), 2, axis=-2)
    cr = uvf[..., 0] - 128.0
    cb = uvf[..., 1] - 128.0
    img = jnp.stack([yf + 1.773 * cb,                        # B
                     yf - 0.714 * cr - 0.344 * cb,           # G
                     yf + 1.403 * cr], axis=-1)              # R
    return jnp.clip(img, 0.0, 255.0)


class Yuv420Staging(FeatureTransformer):
    """Serving-chain stage: convert the (already resized) uint8 BGR mat
    to yuv420 wire planes, stored as ``feature["yuv_y"]`` /
    ``feature["yuv_uv"]``.  Runs INSIDE the per-feature chain so
    ``_maybe_parallel`` spreads the conversion across workers instead of
    serializing it in the batcher."""

    def transform_mat(self, feature: ImageFeature) -> None:
        mat = feature.mat
        if mat is None:
            raise ValueError("Yuv420Staging needs a decoded mat")
        if mat.dtype != np.uint8:
            mat = np.clip(mat, 0, 255).astype(np.uint8)
        y, uv = bgr_to_yuv420_host(mat)
        feature["yuv_y"] = y
        feature["yuv_uv"] = uv


class DeviceAugPrepare(FeatureTransformer):
    """Host half: decode → geometry/labels → staging tensors.

    Consumes an ImageFeature after ``RecordToFeature >> BytesToMat >>
    RoiNormalize`` and emits a dict of fixed-shape numpy arrays the device
    program consumes (no variable shapes reach XLA)."""

    def __init__(self, param: DeviceAugParam,
                 samplers: Optional[List[BatchSampler]] = None):
        super().__init__()
        self.p = param
        self.samplers = samplers or standard_samplers()

    def transform(self, feature: ImageFeature) -> Optional[Dict]:
        """Exception-isolating like ``FeatureTransformer.transform``
        (``image/Types.scala:192-198``): a corrupt record is dropped with
        a warning, never killing the epoch."""
        try:
            return self._transform(feature)
        except Exception:                                   # noqa: BLE001
            import logging

            logging.getLogger("analytics_zoo_tpu").warning(
                "DeviceAugPrepare failed for %s — dropping",
                feature.get("path", "<unknown>"), exc_info=True)
            return None

    def _transform(self, feature: ImageFeature) -> Optional[Dict]:
        if not feature.is_valid:
            return None
        p = self.p
        mat = feature.mat
        if mat.dtype != np.uint8:
            mat = np.clip(mat, 0, 255).astype(np.uint8)
        h, w = mat.shape[:2]
        label: RoiLabel = feature.label

        # --- pre-downscale so the image fits the staging canvas ----------
        if max(h, w) > p.canvas_size:
            import cv2

            s = p.canvas_size / max(h, w)
            mat = cv2.resize(mat, (max(1, int(w * s)), max(1, int(h * s))))
            h, w = mat.shape[:2]   # labels are normalized — unaffected

        # --- expand (zoom-out) decision: label math only ------------------
        # The mean-filled canvas is never built; the device sampler's
        # mean-border fill realises it (reference Expand.scala:28).
        ox = oy = 0.0
        ew, eh = float(w), float(h)
        if random.random() < p.expand_prob:
            ratio = random.uniform(1.0, p.max_expand_ratio)
            if ratio > 1.0 + 1e-6:
                ew, eh = w * ratio, h * ratio
                ox = random.uniform(0, ew - w)
                oy = random.uniform(0, eh - h)
                expand_box = np.array([-ox / w, -oy / h, (ew - ox) / w,
                                       (eh - oy) / h], np.float32)
                if label.size():
                    boxes, valid = project_bbox(expand_box, label.bboxes)
                    new = label.select(valid)
                    new.bboxes = boxes[valid]
                    label = new

        # --- constrained random crop (7 SSD samplers) ---------------------
        crop = np.array([0.0, 0.0, 1.0, 1.0], np.float32)  # of expanded frame
        boxes = generate_batch_samples(label, self.samplers)
        if boxes:
            crop = boxes[random.randrange(len(boxes))]
            if label.size():
                projected, valid = project_bbox(crop, label.bboxes)
                valid &= meet_emit_center_constraint(crop, label.bboxes)
                new = label.select(valid)
                new.bboxes = projected[valid]
                label = new

        # --- flip decision -------------------------------------------------
        flip = random.random() < p.hflip_prob
        if flip and label.size():
            b = label.bboxes.copy()
            b[:, 0], b[:, 2] = 1.0 - label.bboxes[:, 2], 1.0 - label.bboxes[:, 0]
            label = RoiLabel(label.labels, b, label.difficult)

        # source rect of the crop in ORIGINAL image pixel coords (may
        # extend beyond [0,w)×[0,h): outside = channel-mean fill)
        rect = np.array([crop[0] * ew - ox, crop[1] * eh - oy,
                         crop[2] * ew - ox, crop[3] * eh - oy], np.float32)

        # --- color jitter parameters (reference ColorJitter.scala:38) ----
        rr = random.random
        jitter = np.zeros(5, np.float32)
        jitter[0] = rr()                                    # order coin
        jitter[1] = (random.uniform(-p.brightness_delta, p.brightness_delta)
                     if rr() < p.brightness_prob else 0.0)
        jitter[2] = (random.uniform(*p.contrast_range)
                     if rr() < p.contrast_prob else 1.0)
        jitter[3] = (random.uniform(*p.saturation_range)
                     if rr() < p.saturation_prob else 1.0)
        jitter[4] = (random.uniform(-p.hue_delta, p.hue_delta)
                     if rr() < p.hue_prob else 0.0)

        if p.wire_format == "yuv420":
            S = p.canvas_size
            yp, chroma = bgr_to_yuv420_host(mat)
            ch, cw = (h + 1) // 2, (w + 1) // 2
            y_canvas = np.zeros((S, S), np.uint8)
            y_canvas[:h, :w] = yp
            # neutral-chroma padding (128 ⇒ black), matching Uint8ToBatch's
            # serving-path semantics; zero would reconstruct to bright green
            uv_canvas = np.full((S // 2, S // 2, 2), 128, np.uint8)
            uv_canvas[:ch, :cw] = chroma
            staged = {"y": y_canvas, "uv": uv_canvas}
        else:
            canvas = np.zeros((p.canvas_size, p.canvas_size, 3), np.uint8)
            canvas[:h, :w] = mat
            staged = {"canvas": canvas}
        return {
            **staged,
            "rect": rect,
            "size": np.array([h, w], np.float32),
            "flip": np.float32(1.0 if flip else 0.0),
            "jitter": jitter,
            "label": label,
            "im_info": np.array([p.resolution, p.resolution, 1.0, 1.0],
                                np.float32),
        }


def packed_layout(canvas_size: int, wire_format: str, max_gt: int):
    """Single source of truth for the packed-staging row layout:
    ``[(key, dtype, per-image shape)]`` in byte order.  The host packer
    (``DeviceAugBatch``) and the device unpacker (``make_device_augment``)
    both iterate this list, so they cannot drift apart."""
    S = canvas_size
    if wire_format == "yuv420":
        pixels = [("y", np.uint8, (S, S)),
                  ("uv", np.uint8, (S // 2, S // 2, 2))]
    else:
        pixels = [("canvas", np.uint8, (S, S, 3))]
    return pixels + [
        ("rect", np.float32, (4,)),
        ("size", np.float32, (2,)),
        ("flip", np.float32, ()),
        ("jitter", np.float32, (5,)),
        ("im_info", np.float32, (4,)),
        ("bboxes", np.float32, (max_gt, 4)),
        ("labels", np.int32, (max_gt,)),
        ("difficult", np.float32, (max_gt,)),
        ("mask", np.float32, (max_gt,)),
    ]


class DeviceAugBatch(FeatureTransformer):
    """Collate DeviceAugPrepare dicts into a device-ready batch: the
    ``RoiImageToBatch`` counterpart for the device-augmentation path.

    ``pack=True`` emits ``{"packed": (B, item_bytes) uint8}`` instead of
    the ~11-leaf dict (see ``DeviceAugParam.pack``); field order and
    dtypes come from ``packed_layout``, shapes from the collated arrays
    themselves, so no extra configuration can drift from the unpacker."""

    def __init__(self, batch_size: int, max_gt: int = 100,
                 drop_remainder: bool = True, pack: bool = False):
        super().__init__()
        self.batch_size = batch_size
        self.max_gt = max_gt
        self.drop_remainder = drop_remainder
        self.pack = pack

    def apply_iter(self, it):
        buf: List[Dict] = []
        for d in it:
            if d is None:
                continue
            buf.append(d)
            if len(buf) == self.batch_size:
                yield self.collate(buf)
                buf = []
        if buf and not self.drop_remainder:
            yield self.collate(buf)

    def collate(self, ds: List[Dict]) -> Dict:
        from analytics_zoo_tpu.data.dataset import pad_ragged

        boxes = [d["label"].bboxes for d in ds]
        labels = [d["label"].labels.reshape(-1, 1) for d in ds]
        diff = [d["label"].difficult.reshape(-1, 1) for d in ds]
        b, mask = pad_ragged(boxes, self.max_gt)
        l, _ = pad_ragged(labels, self.max_gt)
        dd, _ = pad_ragged(diff, self.max_gt)
        pixel_keys = ("y", "uv") if "y" in ds[0] else ("canvas",)
        aug = {k: np.stack([d[k] for d in ds]) for k in pixel_keys}
        aug.update({
            "rect": np.stack([d["rect"] for d in ds]),
            "size": np.stack([d["size"] for d in ds]),
            "flip": np.stack([d["flip"] for d in ds]),
            "jitter": np.stack([d["jitter"] for d in ds]),
        })
        batch = {
            "aug": aug,
            "im_info": np.stack([d["im_info"] for d in ds]),
            "target": {
                "bboxes": b, "labels": l[..., 0].astype(np.int32),
                "difficult": dd[..., 0], "mask": mask,
            },
        }
        if not self.pack:
            return batch
        flat_src = {**aug, "im_info": batch["im_info"], **batch["target"]}
        B = flat_src["rect"].shape[0]
        # key order + dtypes from packed_layout (the unpacker's source of
        # truth; sizes there are irrelevant for ordering), shapes from
        # the arrays; fill a preallocated row buffer — one host copy
        fields = [(flat_src[key], np.dtype(dtype))
                  for key, dtype, _ in packed_layout(
                      2, "yuv420" if "y" in flat_src else "bgr", 1)]
        views = [np.ascontiguousarray(a.astype(dt, copy=False))
                 .reshape(B, -1).view(np.uint8) for a, dt in fields]
        packed = np.empty((B, sum(v.shape[1] for v in views)), np.uint8)
        off = 0
        for v in views:
            packed[:, off:off + v.shape[1]] = v
            off += v.shape[1]
        return {"packed": packed}


# ---------------------------------------------------------------------------
# device half (pure jax — jit once, static output shapes)
# ---------------------------------------------------------------------------


def _bgr_to_hsv(img):
    """Float BGR (0..255) → OpenCV-convention HSV (H in [0,180))."""
    import jax.numpy as jnp

    b, g, r = img[..., 0], img[..., 1], img[..., 2]
    v = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    c = v - mn
    safe_c = jnp.where(c > 0, c, 1.0)
    h = jnp.where(
        v == r, (g - b) / safe_c,
        jnp.where(v == g, 2.0 + (b - r) / safe_c, 4.0 + (r - g) / safe_c))
    h = jnp.where(c > 0, jnp.mod(h * 30.0, 180.0), 0.0)   # 60°/2 per unit
    s = jnp.where(v > 0, c / jnp.where(v > 0, v, 1.0) * 255.0, 0.0)
    return h, s, v


def _hsv_to_bgr(h, s, v):
    import jax.numpy as jnp

    c = v * s / 255.0
    hp = h / 30.0                                          # [0, 6)
    x = c * (1.0 - jnp.abs(jnp.mod(hp, 2.0) - 1.0))
    m = v - c
    i = jnp.floor(hp).astype(jnp.int32) % 6
    r = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                   [c, x, jnp.zeros_like(c), jnp.zeros_like(c), x, c])
    g = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                   [x, c, c, x, jnp.zeros_like(c), jnp.zeros_like(c)])
    b = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5],
                   [jnp.zeros_like(c), jnp.zeros_like(c), x, c, c, x])
    return jnp.stack([b + m, g + m, r + m], axis=-1)


def _jitter_one(img, jitter):
    """Reference ColorJitter: brightness → {contrast → sat/hue | sat/hue →
    contrast} picked by the order coin (``ColorJitter.scala:38`` two fixed
    orders; channel-order has prob 0 in the canonical chain)."""
    import jax.numpy as jnp

    order, bright, alpha_c, alpha_s, hue_d = (jitter[0], jitter[1], jitter[2],
                                              jitter[3], jitter[4])
    x = img + bright

    # single HSV pass for both orders: pre-scale for order1 (contrast
    # first), post-scale for order2 (contrast last)
    z = jnp.where(order < 0.5, x * alpha_c, x)
    h, s, v = _bgr_to_hsv(jnp.clip(z, 0, 255))
    s = jnp.clip(s * alpha_s, 0, 255)
    h = jnp.mod(h + hue_d, 180.0)
    w = _hsv_to_bgr(h, s, v)
    return jnp.where(order < 0.5, w, w * alpha_c)


def _sample_one(img, rect, size, flip, out_res, means):
    """Bilinear crop+resize with channel-mean border (Expand + Crop +
    Resize + HFlip fused; reference ``Expand.scala``/``Crop.scala``/
    ``Resize.scala``/``HFlip.scala``).

    TPU-first formulation: bilinear interpolation is separable, so the
    resample is TWO MATMULS — ``out = Wy @ img @ Wxᵀ`` with hat-function
    weight matrices (≤2 nonzeros per row) — instead of per-pixel 2D
    gathers, which the TPU vector unit executes orders of magnitude
    slower than the MXU runs dense contractions.  Out-of-image taps
    carry zero weight; the mean border is added analytically as
    ``mean · (1 − row_weight ⊗ col_weight)``, which equals the tap
    formulation's per-tap mean replacement exactly (weights and
    validity are both separable)."""
    import jax.numpy as jnp

    H, W = img.shape[0], img.shape[1]
    h, w = size[0], size[1]
    x1, y1, x2, y2 = rect[0], rect[1], rect[2], rect[3]
    sx = (x2 - x1) / out_res
    sy = (y2 - y1) / out_res
    xs = x1 + (jnp.arange(out_res) + 0.5) * sx - 0.5       # (R,)
    ys = y1 + (jnp.arange(out_res) + 0.5) * sy - 0.5
    # flip = reversed output columns = reversed sample positions
    xs = jnp.where(flip > 0.5, xs[::-1], xs)

    iy = jnp.arange(H, dtype=jnp.float32)
    ix = jnp.arange(W, dtype=jnp.float32)
    wy = jnp.maximum(0.0, 1.0 - jnp.abs(ys[:, None] - iy[None, :]))
    wx = jnp.maximum(0.0, 1.0 - jnp.abs(xs[:, None] - ix[None, :]))
    # taps beyond the image extent (canvas padding or outside) are
    # invalid → mean; matches ``(yi >= 0) & (yi < h)`` in tap form
    wy = wy * (iy[None, :] < h)
    wx = wx * (ix[None, :] < w)
    sy_sum = wy.sum(axis=1)                                # (R,) ∈ [0,1]
    sx_sum = wx.sum(axis=1)

    core = jnp.einsum("rh,hwc->rwc", wy, img)
    core = jnp.einsum("rwc,sw->rsc", core, wx)             # (R, R, 3)
    border = 1.0 - sy_sum[:, None] * sx_sum[None, :]
    return core + border[..., None] * means


def make_device_augment(param: DeviceAugParam, compute_dtype=None):
    """Build the jitted batch augmentation: ``aug_batch = fn(batch)``
    rewrites ``batch["aug"]`` staging tensors into ``batch["input"]``
    (B, res, res, 3).  Runs entirely on device.

    Preferred wiring: pass it as ``device_transform=`` to the train step
    / Optimizer so it fuses into the compiled step; standalone per-batch
    application (after ``device_prefetch``) works too but pays one extra
    dispatch per batch."""
    import jax
    import jax.numpy as jnp

    # host numpy on purpose: jit embeds it directly; an eagerly-committed
    # device array closed into the jitted augment would sit on one device
    # of the mesh and be fetched back at every trace
    means = np.asarray(param.pixel_means, np.float32)
    res = param.resolution
    yuv = param.wire_format == "yuv420"

    def finish(img, rect, size, flip, jitter):
        img = _jitter_one(img, jitter)
        out = _sample_one(img, rect, size, flip, res, means)
        out = out - means
        if compute_dtype is not None:
            out = out.astype(compute_dtype)
        return out

    def one_bgr(canvas, rect, size, flip, jitter):
        return finish(canvas.astype(jnp.float32), rect, size, flip, jitter)

    def one_yuv(y, uv, rect, size, flip, jitter):
        return finish(yuv420_to_bgr_device(y, uv), rect, size, flip, jitter)

    vone = jax.vmap(one_yuv if yuv else one_bgr)

    def unpack(arr):
        """(B, item_bytes) uint8 → the staged batch dict, by slice +
        bitcast against the shared ``packed_layout``.  max_gt is solved
        from the row size (every non-gt field's extent is fixed by the
        canvas), so the unpacker needs no extra configuration."""
        from jax import lax

        B, item = arr.shape
        S = param.canvas_size

        def row_bytes(layout):
            # np.prod(()) == 1 handles the scalar field; (0, ...) shapes
            # correctly contribute zero bytes
            return sum(int(np.prod(shape, dtype=np.int64))
                       * np.dtype(dtype).itemsize
                       for _, dtype, shape in layout)

        # solve max_gt from the row size using the layout itself (no
        # duplicated byte constants to drift from packed_layout)
        base = row_bytes(packed_layout(S, param.wire_format, 0))
        per_gt = row_bytes(packed_layout(S, param.wire_format, 1)) - base
        rest = item - base
        if rest < 0 or rest % per_gt:
            raise ValueError(
                f"packed row of {item} B doesn't fit canvas {S} "
                f"({param.wire_format}): check the packer's layout")
        layout = packed_layout(S, param.wire_format, rest // per_gt)
        fields, off = {}, 0
        for key, dtype, shape in layout:
            n = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
            piece = arr[:, off:off + n]
            off += n
            if dtype is np.uint8:
                fields[key] = piece.reshape((B,) + shape)
            else:
                tgt = jnp.float32 if dtype is np.float32 else jnp.int32
                piece = lax.bitcast_convert_type(
                    piece.reshape(B, n // 4, 4), tgt)
                fields[key] = piece.reshape((B,) + shape)
        pix = (("y", "uv") if yuv else ("canvas",))
        return {
            "aug": {k: fields[k] for k in
                    pix + ("rect", "size", "flip", "jitter")},
            "im_info": fields["im_info"],
            "target": {k: fields[k] for k in
                       ("bboxes", "labels", "difficult", "mask")},
        }

    @jax.jit
    def augment(batch):
        if "packed" in batch:
            extra = {k: v for k, v in batch.items() if k != "packed"}
            batch = {**unpack(batch["packed"]), **extra}
        aug = batch["aug"]
        out = dict(batch)
        out.pop("aug")
        pixels = ((aug["y"], aug["uv"]) if yuv else (aug["canvas"],))
        out["input"] = vone(*pixels, aug["rect"], aug["size"],
                            aug["flip"], aug["jitter"])
        return out

    return augment
