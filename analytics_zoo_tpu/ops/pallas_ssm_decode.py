"""One decode token of the decoder LM's state-space mixer (models/lm.py,
ops/ssm.py ``ssd_step``) for B rows of any sessions, as ONE Pallas program
a layer that updates the rows' recurrent states IN PLACE.

The states of a layer live in one array a replica, a slot a session:
``(n_slots, H, P, N)`` float32, 4.19 MB a slot at the published widths
(32 x 128 x 256).  A step needs each live row's state once in and once
out.  What XLA makes of ``state[slots]`` … ``.at[slots].set`` is a gather
into a copy, the update over the copy and a scatter back: the states
moved three to four times.  Here the slots' array is aliased in and out
(``input_output_aliases``), a grid step is a row, the row's slot comes
from a prefetched scalar, and the pipeline brings the slot's block into
VMEM, where it is decayed, takes ``Δ x ⊗ B``, gives ``y = S C`` and goes
back to where it came from.

**Rows that are not sessions.**  A padding row (slot −1) names the block
of the live row before it (of the first live row where none is before it)
and does nothing: the same block index as its neighbour means no DMA, and
the neighbour's write goes back once, when the index changes.  A batch
with no live row at all (warm-up) passes slot 0 through untouched.

**A slot's first token.**  A row at position 0 takes zeros for its state,
whatever its slot held: a slot handed to a new session needs no zeroing
program and no transfer of its own.

**Layout.**  A state block is (H, P, N) with N on the lanes.  ``B`` and
``C`` are rows over N (broadcast along sublanes); ``Δ x`` and the decay
are needed along P, so the caller hands them over transposed, (P, 2H),
and head ``h``'s column is cut out and broadcast along lanes; ``y`` is a
lane reduction a head, collected as (P, H) and transposed back by the
caller.  All of it on the vector units: 6 operations a state element,
under the element's 8 bytes of traffic.

Off the TPU the kernel runs in interpret mode (the tests' way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import vmem
from analytics_zoo_tpu.utils import engine

F32 = jnp.float32
LANES = 128


def supported(H: int, P: int, N: int, G: int) -> bool:
    """Whether the kernel takes these widths: a head's state is whole
    (8, 128) tiles and the heads divide into the groups."""
    return P % 8 == 0 and N % LANES == 0 and H % G == 0 \
        and vmem.fits(declared_vmem_bytes(H, P, N, G))


def declared_vmem_bytes(H: int, P: int, N: int, G: int) -> int:
    """A row's state in and out, its columns, rows and output, all
    double-buffered, and a head's working set."""
    blocks = (2 * vmem.padded_bytes((H, P, N), F32)
              + vmem.padded_bytes((P, 2 * H), F32)
              + vmem.padded_bytes((2 * G, N), F32)
              + vmem.padded_bytes((P, H), F32))
    return 2 * blocks + 4 * vmem.padded_bytes((P, N), F32)


def block_slots(slots):
    """The slot whose block each row's grid step names: its own for a
    live row; for a padding row the live row's before it, the first live
    row's where none is before it, 0 where no row is live."""
    B = slots.shape[0]
    live = slots >= 0
    before = lax.cummax(jnp.where(live, jnp.arange(B), -1))
    source = jnp.where(before >= 0, before, jnp.argmax(live))
    return jnp.where(jnp.any(live), slots[source], 0).astype(jnp.int32)


def _kernel(slot_ref, live_ref, pos_ref, s_ref, cols_ref, rows_ref, o_ref,
            y_ref, *, H: int, G: int):
    i = pl.program_id(0)
    live = live_ref[i] != 0

    @pl.when(live)
    def _():
        cols = cols_ref[0]                                    # (P, 2H)
        rows = rows_ref[0]                                    # (2G, N)
        fresh = pos_ref[i] == 0
        lane = lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)
        y = jnp.zeros(y_ref.shape[1:], F32)
        for h in range(H):
            g = h // (H // G)
            s = jnp.where(fresh, 0.0, s_ref[0, h])            # (P, N)
            s = cols[:, H + h:H + h + 1] * s \
                + cols[:, h:h + 1] * rows[g:g + 1, :]
            o_ref[0, h] = s
            y = jnp.where(lane == h, jnp.sum(
                s * rows[G + g:G + g + 1, :], axis=1, keepdims=True), y)
        y_ref[0] = y

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(states, slots, pos, x, delta, a, Bm, Cm, *,
                      interpret=None):
    """``states`` (n_slots, H, P, N) float32, updated in place where the
    caller's program donates them (the step programs do); ``slots``
    (B,) the rows' slots (−1: a padding row); ``pos`` (B,) the positions
    of the rows' tokens (0: the state starts from zeros); ``x`` (B, H, P),
    ``delta``, ``a`` (B, H) the step and the decay (ops/ssm.py
    ``step_sizes``), ``Bm``, ``Cm`` (B, G, N) → (the states, ``S C``
    (B, H, P) float32 — zeros for a padding row; the caller adds
    ``D x``)."""
    _, H, P, N = states.shape
    B, G = Bm.shape[:2]
    if not supported(H, P, N, G):
        raise ValueError(f"ssm_decode_update: states {states.shape} in "
                         f"{G} groups do not fit")
    if interpret is None:
        interpret = not engine.on_tpu()
    live = slots >= 0
    delta, a = delta.astype(F32), a.astype(F32)
    cols = jnp.concatenate(
        [(delta[..., None] * x.astype(F32)).transpose(0, 2, 1),
         jnp.broadcast_to(a[:, None, :], (B, P, H))], -1)     # (B, P, 2H)
    rows = jnp.concatenate([Bm, Cm], 1).astype(F32)           # (B, 2G, N)
    own = lambda i, slot, live, pos: (slot[i], 0, 0, 0)       # noqa: E731
    mine = lambda i, slot, live, pos: (i, 0, 0)               # noqa: E731
    states, y = pl.pallas_call(
        functools.partial(_kernel, H=H, G=G),
        out_shape=(jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((B, P, H), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, H, P, N), own),
                      pl.BlockSpec((1, P, 2 * H), mine),
                      pl.BlockSpec((1, 2 * G, N), mine)],
            out_specs=(pl.BlockSpec((1, H, P, N), own),
                       pl.BlockSpec((1, P, H), mine))),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem.limit_bytes(
                declared_vmem_bytes(H, P, N, G))),
        name="lm_decode_ssm_update",
        interpret=interpret,
    )(block_slots(slots), live.astype(jnp.int32), pos.astype(jnp.int32),
      states, cols, rows)
    return states, jnp.where(live[:, None, None], y.transpose(0, 2, 1), 0.0)
