"""VMEM accounting shared by the Pallas kernels (``ops/pallas_*.py``).

One budget and one padding rule, so every kernel's planning estimate, the
selection made on it and the limit handed to Mosaic are the same
arithmetic:

- Mosaic lays a VMEM buffer out in ``(sublanes, 128)`` tiles over its
  last two dims — 8 sublanes for 32-bit types, 16 for 16-bit, 32 for
  8-bit — so a ``(1, 1, P)`` f32 scratch occupies 8× its logical bytes
  (why a kernel keeps a long vector as ``(P / 128, 128)`` instead) and a
  ``(B, 8, H)`` bf16 block 2×.  :func:`padded_bytes` prices that.
- On top of the buffers a kernel declares, the compiler keeps its own
  working set in VMEM: spill slots and layout-change copies of large
  values.  :func:`limit_bytes` allows for it, and a kernel passes the
  result to Mosaic (:func:`compiler_params`) — the default scoped limit
  is far below the physical VMEM, so a kernel that needs more must say
  so.
- :func:`fits` is the selection rule: a geometry runs on a kernel only
  if what the kernel will ask for is inside :data:`VMEM_BUDGET_BYTES`.

The constants are measurements on a TPU v5e (PR 21, jax 0.9.0 /
libtpu 0.0.34; CHANGES.md has the probe): a 124 MiB scratch compiles
under ``vmem_limit_bytes`` = 128 MiB and a 140 MiB one is refused
("would exceed memory (size=134217728)"), so physical VMEM is 128 MiB;
fused DetectionOutput at SSD512, in the one-sublane layout it had then,
needed 33.3 MiB against 24.9 MiB of declared buffers (1.34×) and the
bf16 GRU H=1760 forward 45.9 MiB against 36.3 MiB (1.26×).  (Since PR 30
DetectionOutput's vectors are dense tiles: Mosaic, compiling for a v5e
without one, accepts SSD512 at batch 64 from 4.5 MiB against 4.3 MiB
declared, and SSD300 from 1.5 MiB against 1.7.)
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np
from jax.experimental.pallas import tpu as pltpu

#: the most VMEM a kernel may ask Mosaic for: the 128 MiB of a v5e core
#: less room for the XLA program around the kernel
VMEM_BUDGET_BYTES = 120 * (1 << 20)

#: the compiler's working set, as a multiple of the declared buffers
#: (measured 1.26× and 1.34×, see the module docstring) ...
WORKING_SET_FACTOR = 1.5
#: ... plus a floor that covers small kernels
HEADROOM_BYTES = 8 * (1 << 20)


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def padded_bytes(shape: Sequence[int], dtype) -> int:
    """Bytes a VMEM buffer of ``shape``/``dtype`` really occupies: the
    last dim pads to 128 lanes and the second-minor to the dtype's
    sublane tile (8 × 4/itemsize)."""
    itemsize = np.dtype(dtype).itemsize
    shape = tuple(int(d) for d in shape)
    if len(shape) < 2:
        shape = (1,) * (2 - len(shape)) + shape
    sublanes = 8 * max(4 // itemsize, 1)
    return (math.prod(shape[:-2]) * round_up(shape[-2], sublanes)
            * round_up(shape[-1], 128) * itemsize)


def limit_bytes(declared_bytes: int) -> int:
    """The scoped-VMEM limit a kernel whose declared buffers total
    ``declared_bytes`` (a :func:`padded_bytes` sum) asks Mosaic for."""
    return int(declared_bytes * WORKING_SET_FACTOR) + HEADROOM_BYTES


def fits(declared_bytes: int) -> bool:
    """Whether a kernel with these declared buffers may be selected."""
    return limit_bytes(declared_bytes) <= VMEM_BUDGET_BYTES


def compiler_params(declared_bytes: int) -> pltpu.CompilerParams:
    """Mosaic parameters carrying :func:`limit_bytes`."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=limit_bytes(declared_bytes))
