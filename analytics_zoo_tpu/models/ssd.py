"""SSD-VGG object detector, TPU-first.

Re-design of the reference model zoo (``ssd/model/SSDGraph.scala:41``,
``SSDVgg.scala:25`` with its 300/512 × pascal/coco prior tables,
``SSD.scala:44`` head plumbing) as one flax module:

- NHWC layout, bf16-friendly; convs map straight onto the MXU.
- The ConcatTable/SelectTable/JoinTable head plumbing of the reference
  collapses into plain Python: each source feature map gets a loc head and
  a conf head; outputs are reshaped to (B, priors, ·) and concatenated.
- PriorBox is a host-precomputed constant (``analytics_zoo_tpu.ops.priorbox``)
  — nothing anchor-related runs per step on device.
- ``DetectionOutput`` (decode + NMS) stays a jittable tail so serving is a
  single XLA program, mirroring the reference's in-graph post-processor.

Weight import: layer names follow VGG/Caffe conventions (conv1_1 … fc7,
conv6_1 …) so a name-keyed converter can load the reference's pretrained
backbones.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.core.layers import NormalizeScale
from analytics_zoo_tpu.ops.detection_output import (
    DetectionOutputParam,
    detection_output,
)
from analytics_zoo_tpu.ops.priorbox import PriorBoxParam, concat_priors, prior_box


# ---------------------------------------------------------------------------
# Prior-box hyperparameter tables (reference SSDVgg.scala:58-70)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SSDConfig:
    resolution: int
    feature_shapes: Sequence[int]
    min_sizes: Sequence[float]
    max_sizes: Sequence[float]
    aspect_ratios: Sequence[Sequence[float]]
    steps: Sequence[int]


def ssd300_config(dataset: str = "pascal") -> SSDConfig:
    if dataset == "coco":
        # coco 300 uses smaller minimum scales (reference SSDVgg coco table)
        mins = (21, 45, 99, 153, 207, 261)
        maxs = (45, 99, 153, 207, 261, 315)
    else:
        mins = (30, 60, 111, 162, 213, 264)
        maxs = (60, 111, 162, 213, 264, 315)
    return SSDConfig(
        resolution=300,
        feature_shapes=(38, 19, 10, 5, 3, 1),
        min_sizes=mins,
        max_sizes=maxs,
        aspect_ratios=((2,), (2, 3), (2, 3), (2, 3), (2,), (2,)),
        steps=(8, 16, 32, 64, 100, 300),
    )


def ssd512_config(dataset: str = "pascal") -> SSDConfig:
    if dataset == "coco":
        mins = (20.48, 51.2, 133.12, 215.04, 296.96, 378.88, 460.8)
        maxs = (51.2, 133.12, 215.04, 296.96, 378.88, 460.8, 542.72)
    else:
        mins = (35.84, 76.8, 153.6, 230.4, 307.2, 384.0, 460.8)
        maxs = (76.8, 153.6, 230.4, 307.2, 384.0, 460.8, 537.6)
    return SSDConfig(
        resolution=512,
        feature_shapes=(64, 32, 16, 8, 4, 2, 1),
        min_sizes=mins,
        max_sizes=maxs,
        aspect_ratios=((2,), (2, 3), (2, 3), (2, 3), (2, 3), (2,), (2,)),
        steps=(8, 16, 32, 64, 128, 256, 512),
    )


def build_priors(config: SSDConfig) -> Tuple[np.ndarray, np.ndarray]:
    """(P,4) priors + (P,4) variances for the whole model."""
    per_map = []
    for i, fs in enumerate(config.feature_shapes):
        p = PriorBoxParam(
            min_sizes=[config.min_sizes[i]],
            max_sizes=[config.max_sizes[i]],
            aspect_ratios=list(config.aspect_ratios[i]),
            flip=True, clip=False, step=config.steps[i],
        )
        per_map.append(prior_box((fs, fs),
                                 (config.resolution, config.resolution), p))
    return concat_priors(per_map)


def num_priors_per_cell(config: SSDConfig) -> List[int]:
    return [
        PriorBoxParam(min_sizes=[config.min_sizes[i]],
                      max_sizes=[config.max_sizes[i]],
                      aspect_ratios=list(config.aspect_ratios[i]),
                      flip=True).num_priors
        for i in range(len(config.feature_shapes))
    ]


# ---------------------------------------------------------------------------
# VGG16 backbone (reference SSDVgg VGG16():27)
# ---------------------------------------------------------------------------


def _conv(x, features, name, kernel=3, stride=1, pad=1, dilation=1):
    return nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                   padding=((pad, pad), (pad, pad)),
                   kernel_dilation=(dilation, dilation), name=name)(x)


def _pool(x, ceil=False, kernel=2, stride=2):
    pad = ((0, 1), (0, 1)) if ceil else ((0, 0), (0, 0))
    return nn.max_pool(x, (kernel, kernel), (stride, stride), padding=pad)


class VGGBase(nn.Module):
    """VGG16 trunk through conv5_3 + dilated fc6/fc7 (reference
    ``SSDVgg.scala`` VGG16 + ``SSD.scala`` dilated fc6 pad/dilation 6).
    Returns (conv4_3, fc7) feature maps."""

    @nn.compact
    def __call__(self, x):
        x = _conv(x, 64, "conv1_1"); x = nn.relu(x)
        x = _conv(x, 64, "conv1_2"); x = nn.relu(x)
        x = _pool(x)
        x = _conv(x, 128, "conv2_1"); x = nn.relu(x)
        x = _conv(x, 128, "conv2_2"); x = nn.relu(x)
        x = _pool(x)
        x = _conv(x, 256, "conv3_1"); x = nn.relu(x)
        x = _conv(x, 256, "conv3_2"); x = nn.relu(x)
        x = _conv(x, 256, "conv3_3"); x = nn.relu(x)
        x = _pool(x, ceil=True)   # 75 -> 38 (ceil mode, Caffe pool3)
        x = _conv(x, 512, "conv4_1"); x = nn.relu(x)
        x = _conv(x, 512, "conv4_2"); x = nn.relu(x)
        x = _conv(x, 512, "conv4_3"); x = nn.relu(x)
        conv4_3 = x
        x = _pool(x)
        x = _conv(x, 512, "conv5_1"); x = nn.relu(x)
        x = _conv(x, 512, "conv5_2"); x = nn.relu(x)
        x = _conv(x, 512, "conv5_3"); x = nn.relu(x)
        # pool5: 3x3 stride 1 pad 1 (SSD modification)
        x = nn.max_pool(x, (3, 3), (1, 1), padding=((1, 1), (1, 1)))
        x = _conv(x, 1024, "fc6", kernel=3, pad=6, dilation=6); x = nn.relu(x)
        x = _conv(x, 1024, "fc7", kernel=1, pad=0); x = nn.relu(x)
        return conv4_3, x


class ExtraLayers(nn.Module):
    """conv6_1..conv9_2 (… conv10 for 512) extra feature stages (reference
    ``SSD.scala`` addComponet conv6-9/pool6)."""

    resolution: int = 300

    @nn.compact
    def __call__(self, x):
        feats = []
        x = _conv(x, 256, "conv6_1", kernel=1, pad=0); x = nn.relu(x)
        x = _conv(x, 512, "conv6_2", stride=2); x = nn.relu(x)
        feats.append(x)                                   # 10 / 32
        x = _conv(x, 128, "conv7_1", kernel=1, pad=0); x = nn.relu(x)
        x = _conv(x, 256, "conv7_2", stride=2); x = nn.relu(x)
        feats.append(x)                                   # 5 / 16
        x = _conv(x, 128, "conv8_1", kernel=1, pad=0); x = nn.relu(x)
        if self.resolution == 300:
            x = _conv(x, 256, "conv8_2", pad=0); x = nn.relu(x)   # 3
            feats.append(x)
            x = _conv(x, 128, "conv9_1", kernel=1, pad=0); x = nn.relu(x)
            x = _conv(x, 256, "conv9_2", pad=0); x = nn.relu(x)   # 1
            feats.append(x)
        else:
            x = _conv(x, 256, "conv8_2", stride=2); x = nn.relu(x)  # 8
            feats.append(x)
            x = _conv(x, 128, "conv9_1", kernel=1, pad=0); x = nn.relu(x)
            x = _conv(x, 256, "conv9_2", stride=2); x = nn.relu(x)  # 4
            feats.append(x)
            x = _conv(x, 128, "conv10_1", kernel=1, pad=0); x = nn.relu(x)
            x = _conv(x, 256, "conv10_2", kernel=4, pad=1); x = nn.relu(x)  # 2 -> 1
            feats.append(x)
        return feats


class SSDVgg(nn.Module):
    """SSD300/512-VGG16: returns raw ``(loc (B,P,4), conf (B,P,C))``.

    Matches the reference's source list: conv4_3 (L2-normalized, scale 20),
    fc7, conv6_2 … (reference ``SSDGraph.scala:41`` multi-source heads).
    """

    num_classes: int = 21
    resolution: int = 300
    dataset: str = "pascal"

    @property
    def config(self) -> SSDConfig:
        return (ssd300_config(self.dataset) if self.resolution == 300
                else ssd512_config(self.dataset))

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.config
        priors_per_cell = num_priors_per_cell(cfg)
        # three named sections of the compiled program, forward and
        # backward (obs/names.py::SCOPES); the flax layer names follow
        # the scope in an instruction's op_name
        with jax.named_scope("ssd/base"):
            conv4_3, fc7 = VGGBase(name="vgg")(x)
        with jax.named_scope("ssd/extras"):
            extra = ExtraLayers(resolution=self.resolution,
                                name="extra")(fc7)
        with jax.named_scope("ssd/heads"):
            sources = [NormalizeScale(channels=512, scale=20.0,
                                      name="conv4_3_norm")(conv4_3),
                       fc7] + extra
            locs, confs = [], []
            for i, (src, k) in enumerate(zip(sources, priors_per_cell)):
                loc = nn.Conv(k * 4, (3, 3), padding=((1, 1), (1, 1)),
                              name=f"loc_{i}")(src)
                conf = nn.Conv(k * self.num_classes, (3, 3),
                               padding=((1, 1), (1, 1)),
                               name=f"conf_{i}")(src)
                locs.append(loc.reshape(loc.shape[0], -1, 4))
                confs.append(conf.reshape(conf.shape[0], -1,
                                          self.num_classes))
            return (jnp.concatenate(locs, axis=1),
                    jnp.concatenate(confs, axis=1))


class SSDDetector(nn.Module):
    """SSD + in-graph DetectionOutput: serving is one jitted forward
    (reference runs ``DetectionOutput`` as the model's top layer,
    ``SSDGraph.scala`` post-processor / ``DetectionOutput.scala:34``)."""

    num_classes: int = 21
    resolution: int = 300
    dataset: str = "pascal"
    post: DetectionOutputParam = DetectionOutputParam()

    def setup(self):
        self.ssd = SSDVgg(num_classes=self.num_classes,
                          resolution=self.resolution, dataset=self.dataset)
        priors, variances = build_priors(self.ssd.config)
        # host numpy on purpose: when setup runs eagerly, jnp.asarray would
        # commit device arrays that later jitted applies capture as
        # constants — fetched back from the device at every trace, where
        # numpy constants embed directly
        self._priors = np.asarray(priors)
        self._variances = np.asarray(variances)

    def __call__(self, x):
        loc, conf = self.ssd(x)
        probs = jax.nn.softmax(conf, axis=-1)
        post = dataclasses.replace(self.post, n_classes=self.num_classes)
        return detection_output(loc, probs, self._priors, self._variances, post)
