"""Seeded inputs: rendered-shapes pictures with exact ground truth.

``render_shapes_image`` is a copy of the program's
``analytics_zoo_tpu/data/synthetic.py`` generator (sound; the benchmark
keeps its own so that no later PR can move the traffic under it).  The
``.azr`` shard format and ``SSDByteRecord`` are the program's INPUT
interface and are used as such."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def render_shapes_image(rng: np.random.RandomState, resolution: int = 300,
                        max_shapes: int = 3, n_classes: int = 3
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """One BGR uint8 picture: a low-frequency textured background and
    1..max_shapes bright rectangles, ellipses or triangles.  Returns the
    picture and its ground truth, rows of (label, difficult, x1, y1, x2,
    y2) in pixels."""
    import cv2

    res = resolution
    base = rng.randint(0, 120, (res // 10, res // 10, 3), np.uint8)
    img = cv2.resize(base, (res, res), interpolation=cv2.INTER_CUBIC)
    img = cv2.GaussianBlur(img, (5, 5), 0)
    gt: List[List[float]] = []
    for _ in range(rng.randint(1, max_shapes + 1)):
        cls = rng.randint(1, n_classes + 1)
        size = rng.randint(res // 6, res // 2)
        x1 = rng.randint(0, res - size)
        y1 = rng.randint(0, res - size)
        w = size
        h = rng.randint(int(size * 0.6), size + 1)
        y1 = min(y1, res - h)
        x2, y2 = x1 + w, y1 + h
        color = tuple(int(c) for c in rng.randint(140, 256, 3))
        if cls == 1:
            cv2.rectangle(img, (x1, y1), (x2, y2), color, -1)
        elif cls == 2:
            cv2.ellipse(img, ((x1 + x2) // 2, (y1 + y2) // 2),
                        (w // 2, h // 2), 0, 0, 360, color, -1)
        else:
            pts = np.array([[(x1 + x2) // 2, y1], [x1, y2 - 1],
                            [x2 - 1, y2 - 1]], np.int32)
            cv2.fillPoly(img, [pts], color)
        gt.append([float(cls), 0.0, float(x1), float(y1), float(x2 - 1),
                   float(y2 - 1)])
    return img, np.asarray(gt, np.float32)


def numpy_seed(seed: int) -> int:
    """Any whole number → what ``np.random.RandomState`` takes."""
    return int(seed) % (2 ** 32)


def write_shapes_records(prefix: str, n_images: int, resolution: int,
                         shards: int, seed: int, max_shapes: int = 3,
                         jpeg_quality: int = 92) -> List[str]:
    """Render, JPEG-encode and write ``n_images`` pictures as the
    program's ``.azr`` shards.  Returns the shard paths."""
    import cv2

    from analytics_zoo_tpu.data.records import (SSDByteRecord,
                                                write_ssd_records)

    rng = np.random.RandomState(numpy_seed(seed))
    records = []
    for i in range(n_images):
        img, gt = render_shapes_image(rng, resolution, max_shapes)
        ok, buf = cv2.imencode(".jpg", img,
                               [cv2.IMWRITE_JPEG_QUALITY, jpeg_quality])
        if not ok:
            raise RuntimeError("cv2.imencode failed")
        records.append(SSDByteRecord(data=buf.tobytes(),
                                     path=f"shapes/{i:06d}.jpg", gt=gt))
    return write_ssd_records(records, prefix, shards)
