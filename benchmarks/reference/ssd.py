"""Plain float32 reference of SSD-VGG16 (Liu et al., arXiv:1512.02325):
weights from a seed, the train-time pixel augmentation, the forward pass,
MultiBoxLoss with its gradient, SGD with momentum, and DetectionOutput.

Straightforward ``jax.numpy``; it imports nothing of ``analytics_zoo_tpu``
and takes nothing the program made.  The parameter tree's NAMES are the
program's interface (``vgg/conv1_1/kernel`` ...): the benchmark makes the
weights here and hands the same tree to the program and to this file.

``mode`` selects the arithmetic of the convolutions:

- ``"f32"``  float32 at ``Precision.HIGHEST`` — the reference;
- ``"bf16"`` operands rounded to bfloat16, float32 accumulation — what
  the configurations state (``compute_dtype: bf16``);
- ``"int8"`` operands rounded to 8-bit integers (activations per tensor,
  weights per output channel, symmetric, straight-through gradient) —
  the precision below the stated one, used only by the CONTROL that the
  comparison has to fail (benchmarks/README.md, "correct").
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BGR_MEANS = (104.0, 117.0, 123.0)

# ---------------------------------------------------------------------------
# geometry: the paper's / Caffe-SSD's published tables (pascal)
# ---------------------------------------------------------------------------

GEOMETRY = {
    300: dict(feature_shapes=(38, 19, 10, 5, 3, 1),
              min_sizes=(30, 60, 111, 162, 213, 264),
              max_sizes=(60, 111, 162, 213, 264, 315),
              aspect_ratios=((2,), (2, 3), (2, 3), (2, 3), (2,), (2,)),
              steps=(8, 16, 32, 64, 100, 300)),
    512: dict(feature_shapes=(64, 32, 16, 8, 4, 2, 1),
              min_sizes=(35.84, 76.8, 153.6, 230.4, 307.2, 384.0, 460.8),
              max_sizes=(76.8, 153.6, 230.4, 307.2, 384.0, 460.8, 537.6),
              aspect_ratios=((2,), (2, 3), (2, 3), (2, 3), (2, 3), (2,), (2,)),
              steps=(8, 16, 32, 64, 128, 256, 512)),
}
VARIANCES = (0.1, 0.1, 0.2, 0.2)

# (name, out channels, kernel, stride, pad, dilation); "P"/"Pc"/"P5" are
# the 2x2 pool, its ceil-mode form (Caffe pool3) and SSD's 3x3/1 pool5
VGG = [("conv1_1", 64), ("conv1_2", 64), "P",
       ("conv2_1", 128), ("conv2_2", 128), "P",
       ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256), "Pc",
       ("conv4_1", 512), ("conv4_2", 512), ("conv4_3", 512), "SRC", "P",
       ("conv5_1", 512), ("conv5_2", 512), ("conv5_3", 512), "P5",
       ("fc6", 1024, 3, 1, 6, 6), ("fc7", 1024, 1, 1, 0, 1), "SRC"]


def extra_layers(resolution: int) -> list:
    """conv6_1 ... conv9_2 (conv10_2 at 512): (name, out, k, stride, pad)."""
    head = [("conv6_1", 256, 1, 1, 0), ("conv6_2", 512, 3, 2, 1), "SRC",
            ("conv7_1", 128, 1, 1, 0), ("conv7_2", 256, 3, 2, 1), "SRC",
            ("conv8_1", 128, 1, 1, 0)]
    if resolution == 300:
        return head + [("conv8_2", 256, 3, 1, 0), "SRC",
                       ("conv9_1", 128, 1, 1, 0), ("conv9_2", 256, 3, 1, 0),
                       "SRC"]
    return head + [("conv8_2", 256, 3, 2, 1), "SRC",
                   ("conv9_1", 128, 1, 1, 0), ("conv9_2", 256, 3, 2, 1), "SRC",
                   ("conv10_1", 128, 1, 1, 0), ("conv10_2", 256, 4, 1, 1),
                   "SRC"]


def _conv_spec(entry) -> Tuple[str, int, int, int, int, int]:
    return entry if len(entry) == 6 else (entry[0], entry[1], 3, 1, 1, 1)


def priors_per_cell(resolution: int) -> List[int]:
    return [2 + 2 * len(ars)
            for ars in GEOMETRY[resolution]["aspect_ratios"]]


def source_channels(resolution: int) -> List[int]:
    """Channels of the feature maps the heads read: conv4_3, fc7, then
    each extra stage's last convolution."""
    chans, last = [512, 1024], None
    for e in extra_layers(resolution):
        if e == "SRC":
            chans.append(last)
        else:
            last = e[1]
    return chans


def build_priors(resolution: int) -> np.ndarray:
    """(P, 4) corner-form priors in [0,1] image coordinates, Caffe order:
    per cell the min box, the sqrt(min*max) box, then each aspect ratio
    and its reciprocal."""
    g = GEOMETRY[resolution]
    out = []
    for fs, mn, mx, ars, step in zip(g["feature_shapes"], g["min_sizes"],
                                     g["max_sizes"], g["aspect_ratios"],
                                     g["steps"]):
        sizes = [(mn, mn), (math.sqrt(mn * mx),) * 2]
        for ar in ars:
            r = math.sqrt(ar)
            sizes += [(mn * r, mn / r), (mn / r, mn * r)]
        for i in range(fs):
            for j in range(fs):
                cx, cy = (j + 0.5) * step, (i + 0.5) * step
                for w, h in sizes:
                    out.append((cx - w / 2, cy - h / 2, cx + w / 2,
                                cy + h / 2))
    return (np.asarray(out, np.float64) / resolution).astype(np.float32)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def layer_shapes(resolution: int, num_classes: int
                 ) -> List[Tuple[Tuple[str, ...], Tuple[int, ...]]]:
    """Every kernel of the model as ((path...), (kh, kw, cin, cout)), in
    forward order; biases and the conv4_3 scale follow from them."""
    shapes, cin = [], 3
    for e in VGG:
        if isinstance(e, str):
            continue
        name, out, k, *_ = _conv_spec(e)
        shapes.append((("vgg", name), (k, k, cin, out)))
        cin = out
    for e in extra_layers(resolution):
        if isinstance(e, str):
            continue
        name, out, k, *_ = e
        shapes.append((("extra", name), (k, k, cin, out)))
        cin = out
    for i, (c, n) in enumerate(zip(source_channels(resolution),
                                   priors_per_cell(resolution))):
        shapes.append(((f"loc_{i}",), (3, 3, c, n * 4)))
        shapes.append(((f"conf_{i}",), (3, 3, c, n * num_classes)))
    return shapes


def make_weights(seed: int, resolution: int, num_classes: int = 21,
                 background_bias: float = 0.0) -> Dict:
    """All parameters in ONE jitted call on the default device: normal
    kernels of variance 1/fan_in (the scale of flax's default, which the
    program's own ``Model.build`` draws from; He's 2/fan_in puts the
    logits in the hundreds and the first loss over the program's skip
    guard), normal biases of deviation 0.05, conv4_3 scale 20.  The biases
    are not zero because no trained detector's are, and because with zero
    biases the mean-filled border of an expanded picture is an all-zero
    feature vector at conv4_3, where the L2 normalisation is singular: a
    rounding of 1e-5 in the border then moves the loss by 2 % (PR 25).
    ``background_bias`` is added to every conf head's background channel
    (class ``j % C == 0`` of bias channel ``j``): seeded random heads put
    every prior over DetectionOutput's 0.01 threshold, which no deployed
    detector does (arithmetic copied from tools/profile_serve.py)."""
    shapes = layer_shapes(resolution, num_classes)

    @jax.jit
    def build(key):
        tree: Dict = {}
        for i, (path, shape) in enumerate(shapes):
            node = tree
            for p in path:
                node = node.setdefault(p, {})
            fan_in = shape[0] * shape[1] * shape[2]
            node["kernel"] = (jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
                              * math.sqrt(1.0 / fan_in))
            bias = 0.05 * jax.random.normal(
                jax.random.fold_in(key, 1000 + i), (shape[3],), jnp.float32)
            if path[0].startswith("conf_") and background_bias:
                bias = bias + background_bias * (
                    jnp.arange(shape[3]) % num_classes == 0)
            node["bias"] = bias
        tree["conv4_3_norm"] = {"cmul": {
            "weight": jnp.full((512,), 20.0, jnp.float32)}}
        return tree

    return build(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _quant8(x, axes):
    """Symmetric 8-bit rounding with a straight-through gradient."""
    scale = jnp.max(jnp.abs(lax.stop_gradient(x)), axis=axes,
                    keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + lax.stop_gradient(q - x)


def conv(x, w, b, stride=1, pad=1, dilation=1, mode="f32"):
    if mode == "bf16":
        # rounded to bfloat16, multiplied exactly, accumulated in float32
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "int8":
        x, w = _quant8(x, None), _quant8(w, (0, 1, 2))
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return y + b


def _pool(x, k, s, pad):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, s, s, 1), ((0, 0), pad, pad, (0, 0)))


def forward(params: Dict, x, resolution: int, num_classes: int = 21,
            mode: str = "f32"):
    """x (B, R, R, 3) mean-subtracted BGR → (loc (B,P,4), conf (B,P,C))
    raw head outputs."""
    x = x.astype(jnp.float32)
    sources = []
    for e in VGG:
        if e == "P":
            x = _pool(x, 2, 2, (0, 0))
        elif e == "Pc":
            x = _pool(x, 2, 2, (0, x.shape[1] % 2))
        elif e == "P5":
            x = _pool(x, 3, 1, (1, 1))
        elif e == "SRC":
            sources.append(x)
        else:
            name, _, _, s, p, d = _conv_spec(e)
            lp = params["vgg"][name]
            x = jax.nn.relu(conv(x, lp["kernel"], lp["bias"], s, p, d, mode))
    for e in extra_layers(resolution):
        if e == "SRC":
            sources.append(x)
        else:
            name, _, _, s, p = e
            lp = params["extra"][name]
            x = jax.nn.relu(conv(x, lp["kernel"], lp["bias"], s, p, 1, mode))
    c43 = sources[0]
    norm = jnp.sqrt(jnp.sum(c43 * c43, axis=-1, keepdims=True))
    sources[0] = c43 / (norm + 1e-10) * params["conv4_3_norm"]["cmul"]["weight"]
    locs, confs = [], []
    for i, src in enumerate(sources):
        lp, cp = params[f"loc_{i}"], params[f"conf_{i}"]
        loc = conv(src, lp["kernel"], lp["bias"], mode=mode)
        cf = conv(src, cp["kernel"], cp["bias"], mode=mode)
        locs.append(loc.reshape(loc.shape[0], -1, 4))
        confs.append(cf.reshape(cf.shape[0], -1, num_classes))
    return jnp.concatenate(locs, 1), jnp.concatenate(confs, 1)


# ---------------------------------------------------------------------------
# the train-time pixel augmentation (colour jitter, expand + crop + resize
# as one bilinear resample with a channel-mean border, flip, mean subtract)
# ---------------------------------------------------------------------------


def _hsv(img):
    b, g, r = img[..., 0], img[..., 1], img[..., 2]
    v = jnp.maximum(jnp.maximum(r, g), b)
    c = v - jnp.minimum(jnp.minimum(r, g), b)
    sc = jnp.where(c > 0, c, 1.0)
    h = jnp.where(v == r, (g - b) / sc,
                  jnp.where(v == g, 2.0 + (b - r) / sc, 4.0 + (r - g) / sc))
    h = jnp.where(c > 0, jnp.mod(h * 30.0, 180.0), 0.0)
    s = jnp.where(v > 0, c / jnp.where(v > 0, v, 1.0) * 255.0, 0.0)
    return h, s, v


def _bgr(h, s, v):
    c = v * s / 255.0
    hp = h / 30.0
    x = c * (1.0 - jnp.abs(jnp.mod(hp, 2.0) - 1.0))
    i = jnp.floor(hp).astype(jnp.int32) % 6
    z = jnp.zeros_like(c)
    pick = lambda vals: jnp.select([i == k for k in range(6)], vals)
    r, g, b = (pick([c, x, z, z, x, c]), pick([x, c, c, x, z, z]),
               pick([z, z, x, c, c, x]))
    m = v - c
    return jnp.stack([b + m, g + m, r + m], -1)


def _jitter(img, j):
    """Brightness, then contrast before (order coin < 0.5) or after the
    saturation/hue pass."""
    order, bright, contrast, sat, hue = j[0], j[1], j[2], j[3], j[4]
    x = img + bright
    x = jnp.where(order < 0.5, x * contrast, x)
    h, s, v = _hsv(jnp.clip(x, 0, 255))
    y = _bgr(jnp.mod(h + hue, 180.0), jnp.clip(s * sat, 0, 255), v)
    return jnp.where(order < 0.5, y, y * contrast)


def _resample(img, rect, size, flip, res, means):
    """Four-tap bilinear gather of the crop ``rect`` (source pixels; it
    may reach outside the image) to res x res, minus the channel means; a
    tap outside the image reads the mean, so it adds exactly nothing."""
    h, w = size[0], size[1]
    xs = rect[0] + (jnp.arange(res) + 0.5) * (rect[2] - rect[0]) / res - 0.5
    ys = rect[1] + (jnp.arange(res) + 0.5) * (rect[3] - rect[1]) / res - 0.5
    xs = jnp.where(flip > 0.5, xs[::-1], xs)
    x0, y0 = jnp.floor(xs), jnp.floor(ys)
    out = 0.0
    for dy in (0, 1):
        for dx in (0, 1):
            yi, xi = y0 + dy, x0 + dx
            wy = 1.0 - jnp.abs(ys - yi)
            wx = 1.0 - jnp.abs(xs - xi)
            ok = (((yi >= 0) & (yi < h))[:, None]
                  & ((xi >= 0) & (xi < w))[None, :])
            tap = img[jnp.clip(yi, 0, img.shape[0] - 1).astype(jnp.int32)][
                :, jnp.clip(xi, 0, img.shape[1] - 1).astype(jnp.int32)]
            tap = jnp.where(ok[..., None], tap - means, 0.0)
            out = out + (wy[:, None] * wx[None, :])[..., None] * tap
    return out


def augment(aug: Dict, resolution: int):
    """Staged batch (``canvas`` uint8 (B,S,S,3), ``rect``, ``size``,
    ``flip``, ``jitter``) → (B, R, R, 3) float32 network input."""
    means = jnp.asarray(BGR_MEANS, jnp.float32)

    def one(canvas, rect, size, flip, jit):
        img = _jitter(canvas.astype(jnp.float32), jit)
        return _resample(img, rect, size, flip, resolution, means)

    return jax.vmap(one)(aug["canvas"], aug["rect"], aug["size"],
                         aug["flip"], aug["jitter"])


# ---------------------------------------------------------------------------
# MultiBoxLoss
# ---------------------------------------------------------------------------


def _iou(a, b):
    x1 = jnp.maximum(a[:, None, 0], b[None, :, 0])
    y1 = jnp.maximum(a[:, None, 1], b[None, :, 1])
    x2 = jnp.minimum(a[:, None, 2], b[None, :, 2])
    y2 = jnp.minimum(a[:, None, 3], b[None, :, 3])
    inter = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)

    def area(t):
        w, h = t[:, 2] - t[:, 0], t[:, 3] - t[:, 1]
        return jnp.where((w > 0) & (h > 0), w * h, 0.0)

    union = area(a)[:, None] + area(b)[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _encode(priors, gt):
    pw, ph = priors[:, 2] - priors[:, 0], priors[:, 3] - priors[:, 1]
    pcx, pcy = priors[:, 0] + pw / 2, priors[:, 1] + ph / 2
    gw, gh = gt[:, 2] - gt[:, 0], gt[:, 3] - gt[:, 1]
    gcx, gcy = gt[:, 0] + gw / 2, gt[:, 1] + gh / 2
    v = VARIANCES
    return jnp.stack([(gcx - pcx) / pw / v[0], (gcy - pcy) / ph / v[1],
                      jnp.log(jnp.maximum(gw, 1e-8) / pw) / v[2],
                      jnp.log(jnp.maximum(gh, 1e-8) / ph) / v[3]], -1)


def match(priors, boxes, mask, overlap=0.5):
    """Per-prior best ground truth at IoU >= overlap, then every valid
    ground truth claims its best prior (later ones win a collision)."""
    P = priors.shape[0]
    iou = jnp.where(mask[None, :] > 0, _iou(priors, boxes), -1.0)
    best_gt, best_iou = jnp.argmax(iou, 1), jnp.max(iou, 1)
    positive = best_iou >= overlap
    best_prior = jnp.argmax(iou, 0)
    for g in range(boxes.shape[0]):
        hit = (jnp.arange(P) == best_prior[g]) & (mask[g] > 0)
        best_gt = jnp.where(hit, g, best_gt)
        positive = positive | hit
    return best_gt, positive, best_iou


def image_loss(loc, conf, priors, boxes, labels, mask, neg_pos_ratio=3.0):
    """One image: (smooth-L1 localisation sum, cross-entropy sum over
    positives and mined negatives, number of positives)."""
    matched, positive, best_iou = match(priors, boxes, mask)
    pos = positive.astype(jnp.float32)
    n_pos = pos.sum()
    d = loc - _encode(priors, boxes[matched])
    ad = jnp.abs(d)
    loc_loss = jnp.sum(jnp.where(ad < 1.0, 0.5 * d * d, ad - 0.5).sum(-1) * pos)
    logp = jax.nn.log_softmax(conf, -1)
    label = jnp.where(positive, labels[matched].astype(jnp.int32), 0)
    ce = -jnp.take_along_axis(logp, label[:, None], 1)[:, 0]
    cand = (~positive) & (best_iou < 0.5)
    neg_loss = jnp.where(cand, -logp[:, 0], -jnp.inf)
    n_neg = jnp.minimum(neg_pos_ratio * n_pos, cand.sum().astype(jnp.float32))
    rank = jnp.argsort(jnp.argsort(-neg_loss))
    neg = ((rank < n_neg) & cand).astype(jnp.float32)
    return loc_loss, jnp.sum(ce * (pos + neg)), n_pos


def vgg_net(resolution: int, num_classes: int = 21):
    """``net(params, x, mode) -> (loc, conf)`` of the published model."""
    return lambda params, x, mode="f32": forward(params, x, resolution,
                                                 num_classes, mode)


def batch_loss_sums(params, inputs, target, priors, net, mode):
    """Un-normalised loss of a block of rows and its count of positives
    (the batch's loss is the sum over blocks over the batch's positives,
    so a batch can be walked in blocks of rows)."""
    loc, conf = net(params, inputs, mode)
    ll, cl, n = jax.vmap(
        lambda l, c, b, lab, m: image_loss(l, c, priors, b, lab, m))(
        loc, conf, target["bboxes"], target["labels"], target["mask"])
    return ll.sum() + cl.sum(), n.sum()


def train_steps(params, batches: Sequence[Dict], resolution: int, net,
                lr: float, momentum: float, weight_decay: float,
                skip_loss_above: float = 50.0, mode: str = "f32",
                block: int = 16, leave_out_half: bool = False):
    """SGD with momentum over ``batches`` (each the staged batch the
    program was fed), in blocks of ``block`` rows so float32 fits.
    Returns (losses, first gradient tree, final params).  Update rule:
    g' = g + wd*p; v = mu*v + g'; p = p - lr*v; a step whose loss is over
    ``skip_loss_above`` changes nothing (the reference implementation's
    guard against exploding gradients).  ``leave_out_half`` plants the
    fault "half of the batch left out, the mean taken over the rest"."""
    priors = jnp.asarray(build_priors(resolution))

    @jax.jit
    def block_grad(p, aug, target):
        x = augment(aug, resolution)
        (s, n), g = jax.value_and_grad(
            lambda q: batch_loss_sums(q, x, target, priors, net, mode),
            has_aux=True)(p)
        return s, n, g

    @jax.jit
    def update(p, v, g, n_pos, loss_sum):
        n = jnp.maximum(n_pos, 1.0)
        loss = loss_sum / n
        keep = loss <= skip_loss_above
        g = jax.tree_util.tree_map(lambda t: t / n, g)
        v2 = jax.tree_util.tree_map(
            lambda vv, gg, pp: momentum * vv + gg + weight_decay * pp, v, g, p)
        p2 = jax.tree_util.tree_map(lambda pp, vv: pp - lr * vv, p, v2)
        sel = lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.where(keep, x, y), a, b)
        return sel(p2, p), sel(v2, v), g, loss

    velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for batch in batches:
        B = batch["aug"]["rect"].shape[0]
        rows = B // 2 if leave_out_half else B
        tot_s = tot_n = 0.0
        grad = jax.tree_util.tree_map(jnp.zeros_like, params)
        for lo in range(0, rows, block):
            cut = lambda t: t[lo:min(lo + block, rows)]
            aug = {k: cut(batch["aug"][k])
                   for k in ("canvas", "rect", "size", "flip", "jitter")}
            tgt = {k: cut(batch["target"][k])
                   for k in ("bboxes", "labels", "mask")}
            s, n, g = block_grad(params, aug, tgt)
            tot_s, tot_n = tot_s + s, tot_n + n
            grad = jax.tree_util.tree_map(jnp.add, grad, g)
        params, velocity, g_mean, loss = update(params, velocity, grad,
                                                tot_n, tot_s)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g_mean
    return losses, first_grad, params


# ---------------------------------------------------------------------------
# DetectionOutput
# ---------------------------------------------------------------------------


def decode(priors, loc):
    pw, ph = priors[:, 2] - priors[:, 0], priors[:, 3] - priors[:, 1]
    pcx, pcy = priors[:, 0] + pw / 2, priors[:, 1] + ph / 2
    v = VARIANCES
    cx, cy = v[0] * loc[..., 0] * pw + pcx, v[1] * loc[..., 1] * ph + pcy
    w, h = jnp.exp(v[2] * loc[..., 2]) * pw, jnp.exp(v[3] * loc[..., 3]) * ph
    return jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)


def scores_and_boxes(params, images, resolution, net, mode="f32"):
    """images (B,R,R,3) mean-subtracted → (softmax scores (B,P,C), decoded
    boxes (B,P,4) in [0,1] coordinates)."""
    loc, conf = net(params, images, mode)
    return (jax.nn.softmax(conf, -1),
            decode(jnp.asarray(build_priors(resolution)), loc))


def detection_output(scores, boxes, conf_thresh=0.01, nms_thresh=0.45,
                     nms_topk=400, keep_topk=200):
    """One image: scores (P,C), boxes (P,4) → (prior index (K,), class
    (K,), score (K,)), class −1 where empty.  Per foreground class: the
    ``nms_topk`` best candidates over ``conf_thresh``, greedy suppression
    at IoU >= ``nms_thresh``; then the ``keep_topk`` best of all classes."""
    P, C = scores.shape

    def per_class(s):
        s = jnp.where(s > conf_thresh, s, -jnp.inf)
        top, idx = lax.top_k(s, min(nms_topk, P))
        iou = _iou(boxes[idx], boxes[idx])
        valid = jnp.isfinite(top)

        def body(i, keep):
            earlier = (jnp.arange(top.shape[0]) < i) & keep
            clash = jnp.any(earlier & (iou[i] >= nms_thresh))
            return keep.at[i].set(valid[i] & ~clash)

        keep = lax.fori_loop(0, top.shape[0], body,
                             jnp.zeros(top.shape, bool))
        return idx, jnp.where(keep, top, 0.0)

    idx, kept = jax.vmap(per_class, in_axes=1)(scores[:, 1:])   # (C-1, k)
    k = idx.shape[1]
    best, order = lax.top_k(kept.reshape(-1), keep_topk)
    cls = order // k + 1
    return (jnp.where(best > 0, idx.reshape(-1)[order], -1),
            jnp.where(best > 0, cls, -1), best)
