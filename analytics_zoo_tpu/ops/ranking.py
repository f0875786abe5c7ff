"""The k largest of a row without a sort: a float's bits in an order
unsigned compares keep, and the k-th largest of them found by counting.

Two users: the decoder's sparse attention selects its ``topk`` keys a
query by it (``ops/lm_attention.py``: ``select_topk`` in decode, the
threshold of prefill), with one static ``k``; MultiBoxLoss mines its hard
negatives by it (``ops/multibox_loss.py``), with a ``k`` an image.  Both
keep the values over the threshold and, of those equal to it, the lowest
indices that still fit — the set a stable descending sort's first ``k``
is.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ordered_bits(x):
    """float32 → uint32 whose unsigned order is the floats' order."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(count, k, rows: int):
    """Per row the k-th largest of its uint32 values, exactly: 32 counting
    passes build it bit by bit from the top.  ``count(above)`` → (rows,)
    says for how many of a row's values ``above(values)`` holds; ``k`` is
    one int for every row or a (rows,) int32 array, one a row."""
    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count(lambda v: v >= cand[:, None]) >= k, cand, tau)
    return lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32))
