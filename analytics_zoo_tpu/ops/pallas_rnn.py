"""Pallas TPU kernel: persistent RNN recurrence (VMEM-resident h2h).

The structural DS2 training ceiling named by docs/MFU_CEILING.md: a
scan-formulated recurrence re-streams the 2·H² h2h weight bytes from HBM
every timestep, so the h2h matmul's arithmetic intensity is ≈ B FLOP/byte
against the v5e ridge of ≈ 240 — the MFU ceiling is ~B/240 no matter how
good the schedule is.  This kernel is the Diamos et al. "Persistent RNNs"
(ICML 2016) answer restated for TPU/Pallas: load a direction's h2h weight
block into VMEM **once** and iterate the whole timestep loop on-chip, so
the weights are read from HBM once per sequence instead of once per step
— intensity becomes ≈ B·T/2 FLOP/byte, decoupled from batch size.

Mechanics
---------
* The grid iterates over time blocks; the weight/bias/carry BlockSpecs
  use a **constant index map**, so Pallas keeps them VMEM-resident across
  grid steps (no re-fetch — the revisited block is not re-DMA'd) while the
  per-block input projections / outputs stream through double-buffered
  VMEM windows.  The running carry lives in VMEM scratch, which persists
  across the (sequential) TPU grid.
* The kernel consumes the already-hoisted input projections
  (``core.rnn`` fast path: ``[B·T, D] → [B·T, k·H]`` computed before the
  scan), so the body is exactly the h2h recurrence + gate math.
* Cell math is ported into the kernel body for the three ``core.rnn``
  cells: ``vanilla`` (ReLU / clipped-ReLU / tanh — the identity-i2h
  clipped-ReLU cell is what DS2 actually runs), ``gru`` and ``lstm``,
  with the same gate order as the hoisted projections (r,z,n / i,f,g,o).
* ``n_frames`` length masking matches ``core.rnn._masked_step``: a row's
  carry freezes past its true length and masked outputs are zeroed, so
  zero-padding (bucket padding AND time-block padding) is
  correctness-inert.  The reverse direction is handled by the caller
  (``Recurrent``) with the same prefix-gather used by the blocked scan.
* ``interpret=True`` (the default off-TPU) discharges the kernel to
  plain XLA ops, so CPU tier-1 pins fwd+grad equivalence against the
  blocked scan (tests/test_pallas_rnn.py) — the ``ops.pallas_nms``
  pattern.

Transposed persistent backward (``backward="pallas"``, the default)
-------------------------------------------------------------------
The DS2 training step is *grad-dominated* (the backward's recurrence
carries ~2× the forward's h2h FLOPs), so a backward that re-streams the
h2h weights from HBM every timestep forfeits the residency win on
exactly the pass the MFU ceiling was derived for.  The ``custom_vjp``
bwd is therefore its own persistent Pallas kernel — the Diamos et al.
§4 transposed-weights trick:

* the grid runs the time blocks **reversed**; ``W_h2h`` *and*
  ``W_h2hᵀ`` load into VMEM once per direction (constant index maps,
  the forward's residency trick — W for the within-block recompute,
  Wᵀ for the ``dh ← dgate·Wᵀ`` chain), so backward h2h arithmetic
  intensity decouples from batch exactly like the forward's;
* the running ``dh`` carry lives in fp32 VMEM scratch across grid
  steps, and **dW/db accumulate in fp32 VMEM scratch across all time
  blocks** — ``dW_h2h += dgateᵀ·h`` runs per step on-chip and the
  accumulator streams out ONCE at the final grid step, not per step;
* the forward saves only the **block-boundary carries** as residuals
  (one ``[C,B,H]`` fp32 slab per time block, streamed out per grid
  step) and the backward *recomputes within a block* from that saved
  carry — residual HBM is T/U× the activations instead of T×;
* masking is the forward's (``_masked_step`` semantics transposed):
  an invalid step's cotangent passes through the frozen carry and
  contributes nothing to dW/db/d_pre.

``backward="scan"`` keeps the pre-existing fallback: the bwd
recomputes the recurrence with a differentiable ``lax.scan`` of the
identical fp32 math (``_scan_reference``) and pulls cotangents through
it — bit-compatible with the pre-transposed-kernel behavior and the
parity reference for the kernel bwd.  Grad parity against the blocked
scan is the acceptance gate either way.

Alignment: H pads up to the 128-lane multiple **per gate segment**, B to
the 8-sublane multiple, T to the time block.  Padded weight rows/columns
are zero, padded batch rows carry n=0, so padding never contaminates
real outputs (forward or backward — padded-lane cotangents are zero and
every cross-lane coupling runs through the zero-padded weight blocks).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.vmem import compiler_params, padded_bytes
from analytics_zoo_tpu.ops.vmem import round_up as _round_up
from analytics_zoo_tpu.utils import engine

# gates per cell (k: width multiple of the stacked h2h matmul) and carry
# slots (C: vanilla/gru carry h; lstm carries (c, h))
CELL_GATES = {"vanilla": 1, "gru": 3, "lstm": 4}
CELL_CARRY = {"vanilla": 1, "gru": 1, "lstm": 2}

class RnnKernelConfig(NamedTuple):
    """Hashable static config (``custom_vjp`` nondiff argument)."""

    cell: str               # 'vanilla' | 'gru' | 'lstm'
    activation: str         # vanilla only: 'relu' | 'clipped_relu' | 'tanh'
    time_block: int         # unrolled steps per grid iteration
    interpret: bool
    backward: str = "pallas"   # 'pallas' (transposed persistent kernel)
    #                            | 'scan' (reference-scan recompute vjp)


BACKWARDS = ("pallas", "scan")


def default_interpret() -> bool:
    """Interpret (discharge to XLA) unless a TPU backend is active, so
    CPU tier-1 runs the kernel semantics."""
    return not engine.on_tpu()


def persistent_vmem_bytes(hidden: int, cell: str = "vanilla",
                          batch: int = 8, time_block: int = 8,
                          weight_bytes: int = 4,
                          backward: bool = False) -> int:
    """VMEM the kernel's buffers occupy, priced the way Mosaic lays them
    out (``ops.vmem.padded_bytes`` — a ``(B, 8, H)`` bf16 stream block
    pads its 8 time rows to the 16-sublane bf16 tile, a ``(1, k·H)``
    bias to 8/16 sublanes).  ``weight_bytes`` is the compute dtype's
    itemsize (4 = fp32, 2 = bf16: weights AND streamed blocks).  The
    resident weight block (whole-array window) is single-buffered;
    blocks whose index map moves with the time grid are double-buffered.
    ``_run_kernel``/``_run_bwd_kernel`` hand the same figure to Mosaic
    as the VMEM limit, and ``core.rnn.Recurrent`` refuses geometries
    over ``ops.vmem.VMEM_BUDGET_BYTES``.

    ``backward=True`` prices the transposed persistent *backward*
    program instead — a strictly larger residency than the forward's:
    ``W`` **and** ``Wᵀ`` resident, the fp32 dW/db accumulators (the
    fused cross-block accumulation that streams out once), the streamed
    cotangent/residual windows (g_ys, d_pre, block-boundary carries),
    the dh carry scratch, and the within-block recompute working set
    (``time_block`` carries + gate pre-activations, plus the fp32 copy
    of ``Wᵀ`` the bf16 chain materializes).  Training geometry must fit
    BOTH passes."""
    k = CELL_GATES[cell]
    c = CELL_CARRY[cell]
    hp = _round_up(hidden, 128)
    bp = _round_up(batch, 8)
    tb = int(time_block)
    dt = np.float32 if weight_bytes >= 4 else jnp.bfloat16
    f32 = np.float32
    w = padded_bytes((hp, k * hp), dt) + padded_bytes((1, k * hp), dt)
    carry = padded_bytes((c, bp, hp), f32)
    n_len = padded_bytes((bp, 128), np.int32)
    gates = padded_bytes((bp, k * hp), f32)
    if not backward:
        stream = 2 * (padded_bytes((bp, tb, k * hp), dt)
                      + padded_bytes((bp, tb, hp), dt)
                      + carry)                       # pre, ys, cs windows
        # h0 in, cf out, carry scratch; ~4 gate-wide fp32 live values
        return w + stream + 3 * carry + n_len + 4 * gates
    w2 = w + padded_bytes((k * hp, hp), dt)                  # + Wᵀ resident
    if weight_bytes < 4:
        w2 += padded_bytes((k * hp, hp), f32)        # fp32 copy of Wᵀ
    acc = (padded_bytes((hp, k * hp), f32) + gates           # dW/db scratch
           + padded_bytes((hp, k * hp), dt)                  # dW out block
           + padded_bytes((1, k * hp), dt))
    stream = 2 * (2 * padded_bytes((bp, tb, k * hp), dt)     # pre + d_pre
                  + padded_bytes((bp, tb, hp), dt)           # g_ys
                  + carry)                                   # cs window
    # g_cf in, dh0 out, dh scratch + within-block recompute live set
    # (tb+1 carries, tb gate pre-activation rows)
    live = (3 + tb + 1) * carry + tb * gates
    return w2 + acc + stream + live + n_len


def _gate_slices(a, k: int, hp: int):
    return [a[:, s * hp:(s + 1) * hp] for s in range(k)]


def _cell_step(cfg: RnnKernelConfig, pre_t, hh, carry):
    """One step of gate math from the input projection ``pre_t`` and the
    recurrent projection ``hh`` (both fp32, gate-stacked).  Returns
    (new_carry, output).  The math mirrors ``core.rnn``'s ``recur``
    methods exactly (same gate order, same biased/unbiased split)."""
    hp = carry[0].shape[-1]
    if cfg.cell == "vanilla":
        z = pre_t + hh
        if cfg.activation == "relu":
            act = jnp.maximum(z, 0.0)
        elif cfg.activation == "clipped_relu":
            act = jnp.clip(z, 0.0, 20.0)
        else:
            act = jnp.tanh(z)
        return (act,), act
    if cfg.cell == "gru":
        (h,) = carry
        i_r, i_z, i_n = _gate_slices(pre_t, 3, hp)
        h_r, h_z, h_n = _gate_slices(hh, 3, hp)
        r = jax.nn.sigmoid(i_r + h_r)
        z = jax.nn.sigmoid(i_z + h_z)
        n = jnp.tanh(i_n + r * h_n)
        new_h = (1.0 - z) * n + z * h
        return (new_h,), new_h
    # lstm — gate order (i, f, g, o), carry (c, h)
    c, h = carry
    i_i, i_f, i_g, i_o = _gate_slices(pre_t, 4, hp)
    h_i, h_f, h_g, h_o = _gate_slices(hh, 4, hp)
    i = jax.nn.sigmoid(i_i + h_i)
    f = jax.nn.sigmoid(i_f + h_f)
    g = jnp.tanh(i_g + h_g)
    o = jax.nn.sigmoid(i_o + h_o)
    new_c = f * c + i * g
    new_h = o * jnp.tanh(new_c)
    return (new_c, new_h), new_h


def _rnn_kernel(pre_ref, w_ref, b_ref, h0_ref, n_ref, ys_ref, cf_ref,
                *rest, cfg: RnnKernelConfig):
    """Grid step: advance the carry through ``time_block`` timesteps.

    ``w_ref``/``b_ref``/``h0_ref``/``n_ref`` have constant index maps —
    VMEM-resident for the whole sequence; ``pre_ref``/``ys_ref`` stream
    per block.  The carry persists in ``h_scr`` across grid steps.

    When the forward runs under ``custom_vjp`` with the transposed
    persistent backward, ``rest`` carries an extra ``cs_ref`` output
    (block shape ``(1, C, B, H)``, per-block index map): the carry at
    the START of each time block streams out as the backward's
    recompute residual — T/U slabs instead of T per-step activations."""
    h_scr = rest[-1]
    cs_ref = rest[0] if len(rest) == 2 else None
    C = h_scr.shape[0]
    tb = pre_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    if cs_ref is not None:
        cs_ref[0] = h_scr[:]
    w = w_ref[:]
    b = b_ref[:].astype(jnp.float32)
    # per-row valid lengths arrive lane-replicated (B, 128) so the array
    # is a legal VMEM block; collapse to a (B, 1) column for broadcasting
    n_col = jnp.max(n_ref[:], axis=1, keepdims=True)
    t0 = pl.program_id(0) * tb
    for u in range(tb):
        keep = n_col > (t0 + u)
        carry = tuple(h_scr[i] for i in range(C))
        h = carry[-1]
        hh = jnp.dot(h.astype(w.dtype), w,
                     preferred_element_type=jnp.float32) + b
        pre_t = pre_ref[:, u, :].astype(jnp.float32)
        new_carry, y = _cell_step(cfg, pre_t, hh, carry)
        # _masked_step semantics: invalid rows freeze the carry and emit 0
        for i in range(C):
            h_scr[i] = jnp.where(keep, new_carry[i], carry[i])
        ys_ref[:, u, :] = jnp.where(keep, y, 0.0).astype(ys_ref.dtype)
    cf_ref[:] = h_scr[:].astype(cf_ref.dtype)


def _pad_gated(a, h: int, hp: int, k: int, axis: int):
    """Pad the gate-stacked trailing axis [..., k·h] → [..., k·hp] with
    zeros per gate segment (so static kernel slices at hp multiples hit
    gate boundaries)."""
    if h == hp:
        return a
    shape = a.shape[:axis] + (k, h)
    pad = [(0, 0)] * (len(shape))
    pad[-1] = (0, hp - h)
    return jnp.pad(a.reshape(shape), pad).reshape(
        a.shape[:axis] + (k * hp,))


def _run_kernel(cfg: RnnKernelConfig, pre, w, b, h0, n,
                save_residuals: bool = False):
    """Pad/align, invoke the kernel, un-pad.  Shapes:
    pre [B, T, k·H], w [H, k·H], b [k·H], h0 [C, B, H], n [B] int32.
    Returns ys [B, T, H], carry [C, B, H] — plus, under
    ``save_residuals``, the padded fp32 block-boundary carries
    ``cs [T́/U, C, B́, H́]`` the transposed backward recomputes from."""
    k, c = CELL_GATES[cfg.cell], CELL_CARRY[cfg.cell]
    B, T, _ = pre.shape
    H = w.shape[0]
    tb = max(1, int(cfg.time_block))
    hp, bp = _round_up(H, 128), _round_up(B, 8)
    tp = _round_up(T, tb)
    dt = pre.dtype

    pre_p = _pad_gated(pre, H, hp, k, axis=2)
    pre_p = jnp.pad(pre_p, ((0, bp - B), (0, tp - T), (0, 0)))
    w_p = _pad_gated(w, H, hp, k, axis=1)
    w_p = jnp.pad(w_p, ((0, hp - H), (0, 0)))
    b_p = _pad_gated(b[None, :], H, hp, k, axis=1)
    h0_p = jnp.pad(h0.astype(jnp.float32),
                   ((0, 0), (0, bp - B), (0, hp - H)))
    # padded batch rows get n=0: carry frozen at h0, outputs zero
    n_p = jnp.pad(jnp.minimum(n, T).astype(jnp.int32), (0, bp - B))
    n_b = jnp.broadcast_to(n_p[:, None], (bp, 128))

    const3 = lambda t: (0, 0, 0)  # noqa: E731
    const2 = lambda t: (0, 0)     # noqa: E731
    out_specs = [
        pl.BlockSpec((bp, tb, hp), lambda t: (0, t, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((c, bp, hp), const3, memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bp, tp, hp), dt),
        jax.ShapeDtypeStruct((c, bp, hp), dt),
    ]
    if save_residuals:
        out_specs.append(pl.BlockSpec((1, c, bp, hp),
                                      lambda t: (t, 0, 0, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((tp // tb, c, bp, hp), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_rnn_kernel, cfg=cfg),
        grid=(tp // tb,),
        in_specs=[
            pl.BlockSpec((bp, tb, k * hp), lambda t: (0, t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hp, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((c, bp, hp), const3, memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, 128), const2, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((c, bp, hp), jnp.float32)],
        compiler_params=compiler_params(persistent_vmem_bytes(
            H, cfg.cell, batch=B, time_block=tb,
            weight_bytes=jnp.dtype(dt).itemsize)),
        interpret=cfg.interpret,
    )(pre_p, w_p, b_p, h0_p, n_b)
    if save_residuals:
        ys, cf, cs = outs
        return ys[:B, :T, :H], cf[:, :B, :H], cs
    ys, cf = outs
    return ys[:B, :T, :H], cf[:, :B, :H]


def _unpad_gated(a, h: int, hp: int, k: int):
    """Inverse of ``_pad_gated`` on the trailing gate-stacked axis:
    [..., k·hp] → [..., k·h], dropping the per-gate lane padding."""
    if h == hp:
        return a
    parts = a.reshape(a.shape[:-1] + (k, hp))[..., :h]
    return parts.reshape(a.shape[:-1] + (k * h,))


def _rnn_bwd_kernel(pre_ref, gys_ref, cs_ref, w_ref, wt_ref, b_ref,
                    gcf_ref, n_ref, dpre_ref, dw_ref, db_ref, dh0_ref,
                    dc_scr, dw_scr, db_scr, *, cfg: RnnKernelConfig):
    """Transposed persistent backward, one REVERSED time block per grid
    step (grid index r walks blocks nb-1 … 0).

    Residency discipline mirrors the forward: ``w_ref`` (for the
    within-block forward recompute) and ``wt_ref`` (``W_h2hᵀ``, for the
    ``dh ← dgate·Wᵀ`` chain) carry constant index maps and stay
    VMEM-resident across the whole reversed sequence; ``pre``/``g_ys``/
    ``d_pre`` and the block-boundary carry residual ``cs`` stream per
    block.  The running dh carry persists in ``dc_scr`` (fp32), and
    dW/db accumulate in ``dw_scr``/``db_scr`` (fp32) across ALL grid
    steps — they stream out exactly once, at the final grid step.

    Within a block: forward-recompute the ``time_block`` carries and
    gate pre-activations from the streamed block-start carry, then
    pull the cotangents back step by step (the cell math's VJP, with
    the h2h matmul gradients taken explicitly against the resident
    transposed block so the weight traffic stays on-chip)."""
    C = dc_scr.shape[0]
    tb = pre_ref.shape[1]
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _():
        dc_scr[:] = gcf_ref[:].astype(jnp.float32)
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    w = w_ref[:]
    wt = wt_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    n_col = jnp.max(n_ref[:], axis=1, keepdims=True)
    t0 = (pl.num_programs(0) - 1 - r) * tb      # this block's first step

    # -- within-block forward recompute from the saved block-start carry
    carry = tuple(cs_ref[0, i] for i in range(C))
    carries = [carry]
    hhs = []
    for u in range(tb):
        keep = n_col > (t0 + u)
        h = carry[-1]
        hh = jnp.dot(h.astype(w.dtype), w,
                     preferred_element_type=jnp.float32) + b
        pre_t = pre_ref[:, u, :].astype(jnp.float32)
        new_carry, _ = _cell_step(cfg, pre_t, hh, carry)
        carry = tuple(jnp.where(keep, nw, old)
                      for nw, old in zip(new_carry, carry))
        carries.append(carry)
        hhs.append(hh)

    # -- reversed cotangent sweep through the block
    dcarry = tuple(dc_scr[i] for i in range(C))
    for u in reversed(range(tb)):
        keep = n_col > (t0 + u)
        carry_in = carries[u]
        pre_t = pre_ref[:, u, :].astype(jnp.float32)
        _, pull = jax.vjp(
            lambda p, hhv, cv: _cell_step(cfg, p, hhv, cv),
            pre_t, hhs[u], carry_in)
        # _masked_step transposed: only a VALID step's cotangent enters
        # the cell math; an invalid step passes dcarry straight through
        # the frozen carry (and its zeroed output contributes nothing)
        cot_carry = tuple(jnp.where(keep, d, 0.0) for d in dcarry)
        cot_y = jnp.where(keep, gys_ref[:, u, :].astype(jnp.float32), 0.0)
        d_pre, d_hh, d_cin = pull((cot_carry, cot_y))
        dcarry = tuple(dc + jnp.where(keep, 0.0, d)
                       for dc, d in zip(d_cin, dcarry))
        # transposed h2h chain: dh flows to the previous step through
        # the RESIDENT Wᵀ block — no per-step weight restream
        dh = jnp.dot(d_hh, wt, preferred_element_type=jnp.float32)
        dcarry = dcarry[:-1] + (dcarry[-1] + dh,)
        # fused dW/db accumulation (dW_h2h += hᵀ·dgate), on-chip fp32
        h_in = carry_in[-1].astype(w.dtype).astype(jnp.float32)
        dw_scr[:] += jax.lax.dot_general(
            h_in, d_hh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db_scr[:] += d_hh
        dpre_ref[:, u, :] = d_pre.astype(dpre_ref.dtype)

    for i in range(C):
        dc_scr[i] = dcarry[i]

    @pl.when(r == pl.num_programs(0) - 1)
    def _():
        # block 0 processed: the accumulators stream out ONCE
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[:] = jnp.sum(db_scr[:], axis=0,
                            keepdims=True).astype(db_ref.dtype)
        dh0_ref[:] = dc_scr[:].astype(dh0_ref.dtype)


def _run_bwd_kernel(cfg: RnnKernelConfig, pre, w, b, h0, n, cs,
                    g_ys, g_cf):
    """Pad/align the cotangents, invoke the reversed-grid kernel over
    the forward's saved block-boundary carries, un-pad.  Returns
    ``(d_pre [B,T,k·H], d_w [H,k·H], d_b [k·H], d_h0 [C,B,H])``."""
    k, c = CELL_GATES[cfg.cell], CELL_CARRY[cfg.cell]
    B, T, _ = pre.shape
    H = w.shape[0]
    tb = max(1, int(cfg.time_block))
    hp, bp = _round_up(H, 128), _round_up(B, 8)
    tp = _round_up(T, tb)
    nb = tp // tb
    dt = pre.dtype

    pre_p = _pad_gated(pre, H, hp, k, axis=2)
    pre_p = jnp.pad(pre_p, ((0, bp - B), (0, tp - T), (0, 0)))
    gys_p = jnp.pad(g_ys, ((0, bp - B), (0, tp - T), (0, hp - H)))
    w_p = _pad_gated(w, H, hp, k, axis=1)
    w_p = jnp.pad(w_p, ((0, hp - H), (0, 0)))
    wt_p = w_p.T                               # [k·hp, hp] resident block
    b_p = _pad_gated(b[None, :], H, hp, k, axis=1)
    gcf_p = jnp.pad(g_cf, ((0, 0), (0, bp - B), (0, hp - H)))
    n_p = jnp.pad(jnp.minimum(n, T).astype(jnp.int32), (0, bp - B))
    n_b = jnp.broadcast_to(n_p[:, None], (bp, 128))

    rev3 = lambda r: (0, nb - 1 - r, 0)        # noqa: E731
    rev_cs = lambda r: (nb - 1 - r, 0, 0, 0)   # noqa: E731
    const3 = lambda r: (0, 0, 0)               # noqa: E731
    const2 = lambda r: (0, 0)                  # noqa: E731
    dpre, dw, db, dh0 = pl.pallas_call(
        functools.partial(_rnn_bwd_kernel, cfg=cfg),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bp, tb, k * hp), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, tb, hp), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c, bp, hp), rev_cs, memory_space=pltpu.VMEM),
            pl.BlockSpec((hp, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((k * hp, hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((c, bp, hp), const3, memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, 128), const2, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bp, tb, k * hp), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((hp, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k * hp), const2, memory_space=pltpu.VMEM),
            pl.BlockSpec((c, bp, hp), const3, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp, k * hp), dt),
            jax.ShapeDtypeStruct((hp, k * hp), w.dtype),
            jax.ShapeDtypeStruct((1, k * hp), b.dtype),
            jax.ShapeDtypeStruct((c, bp, hp), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((c, bp, hp), jnp.float32),
                        pltpu.VMEM((hp, k * hp), jnp.float32),
                        pltpu.VMEM((bp, k * hp), jnp.float32)],
        compiler_params=compiler_params(persistent_vmem_bytes(
            H, cfg.cell, batch=B, time_block=tb,
            weight_bytes=jnp.dtype(dt).itemsize, backward=True)),
        interpret=cfg.interpret,
    )(pre_p, gys_p, cs, w_p, wt_p, b_p, gcf_p, n_b)
    d_pre = _unpad_gated(dpre[:B, :T], H, hp, k)
    d_w = _unpad_gated(dw[:H], H, hp, k)
    d_b = _unpad_gated(db, H, hp, k)[0]
    d_h0 = dh0[:, :B, :H]
    return d_pre, d_w, d_b, d_h0


def _scan_reference(cfg: RnnKernelConfig, pre, w, b, h0, n):
    """Differentiable ``lax.scan`` of the identical fp32 recurrence —
    the ``backward="scan"`` fallback recomputes through this, and the
    transposed-kernel backward is parity-tested against its vjp.  Math,
    gate order and masking are the same as the kernel body; only the
    schedule differs."""
    B, T, _ = pre.shape
    dt = pre.dtype
    n_col = jnp.minimum(n, T).astype(jnp.int32)[:, None]
    carry0 = tuple(h0[i].astype(jnp.float32)
                   for i in range(CELL_CARRY[cfg.cell]))

    def step(carry, inp):
        pre_t, t = inp
        keep = n_col > t
        h = carry[-1]
        hh = jnp.dot(h.astype(w.dtype), w,
                     preferred_element_type=jnp.float32)
        hh = hh + b.astype(jnp.float32)
        new_carry, y = _cell_step(cfg, pre_t.astype(jnp.float32), hh, carry)
        new_carry = tuple(jnp.where(keep, nw, old)
                          for nw, old in zip(new_carry, carry))
        return new_carry, jnp.where(keep, y, 0.0)

    xs = (pre.transpose(1, 0, 2), jnp.arange(T, dtype=jnp.int32))
    final, ys = jax.lax.scan(step, carry0, xs)
    return (ys.transpose(1, 0, 2).astype(dt),
            jnp.stack(final).astype(dt))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _persistent(cfg: RnnKernelConfig, pre, w, b, h0, n):
    return _run_kernel(cfg, pre, w, b, h0, n)


def _persistent_fwd(cfg, pre, w, b, h0, n):
    # residuals are the kernel INPUTS plus (transposed backward only)
    # the streamed block-boundary carries — T/U fp32 slabs, never the
    # per-step gate activations; the backward recomputes within a block
    if cfg.backward == "pallas":
        ys, cf, cs = _run_kernel(cfg, pre, w, b, h0, n,
                                 save_residuals=True)
        return (ys, cf), (pre, w, b, h0, n, cs)
    return _run_kernel(cfg, pre, w, b, h0, n), (pre, w, b, h0, n, None)


def _persistent_bwd(cfg, res, g):
    pre, w, b, h0, n, cs = res
    if cfg.backward == "pallas":
        # transposed persistent kernel: reversed time grid, W/Wᵀ
        # VMEM-resident, dW fused-accumulated across blocks
        g_ys, g_cf = g
        d_pre, d_w, d_b, d_h0 = _run_bwd_kernel(
            cfg, pre, w, b, h0, n, cs, g_ys, g_cf)
    else:
        # reference-scan recompute (the pre-transposed-kernel behavior,
        # kept bit-compatible as the fallback + parity reference)
        _, vjp = jax.vjp(
            lambda pre, w, b, h0: _scan_reference(cfg, pre, w, b, h0, n),
            pre, w, b, h0)
        d_pre, d_w, d_b, d_h0 = vjp(g)
    return (d_pre, d_w, d_b, d_h0,
            np.zeros(n.shape, jax.dtypes.float0))


_persistent.defvjp(_persistent_fwd, _persistent_bwd)


def persistent_rnn(pre: jax.Array, w: jax.Array, b: jax.Array,
                   h0: jax.Array, n_frames: Optional[jax.Array] = None,
                   *, cell: str = "vanilla", activation: str = "relu",
                   time_block: int = 8,
                   interpret: Optional[bool] = None,
                   backward: str = "pallas"
                   ) -> Tuple[jax.Array, jax.Array]:
    """Run one direction's recurrence with the h2h weights VMEM-resident.

    Args:
      pre: ``[B, T, k·H]`` hoisted input projections (gate-stacked in the
        cell's canonical order: vanilla k=1; GRU ``r,z,n``; LSTM
        ``i,f,g,o`` — what ``core.rnn`` cells' ``project`` emits).
      w: ``[H, k·H]`` gate-stacked h2h kernel.
      b: ``[k·H]`` gate-stacked h2h bias (zeros for unbiased gates).
      h0: ``[C, B, H]`` initial carry (vanilla/GRU C=1: ``(h,)``; LSTM
        C=2: ``(c, h)``).
      n_frames: optional ``[B]`` int32 valid lengths — the carry freezes
        and outputs zero past each row's length (``_masked_step``
        semantics).  ``None`` = all frames valid.
      cell / activation / time_block: static kernel config.  Compiled
        (``interpret=False``), ``time_block`` must be a multiple of 8.
      interpret: force interpreter mode; default: on unless a TPU
        backend is active.
      backward: ``"pallas"`` (default) runs the transposed persistent
        backward kernel — reversed time grid, ``W``/``Wᵀ``
        VMEM-resident, dW fused-accumulated in VMEM scratch across
        time blocks, block-boundary carries saved as streamed
        residuals; ``"scan"`` keeps the reference-scan recompute vjp
        (bit-compatible pre-existing behavior, the parity reference).

    Returns ``(ys [B, T, H], carry [C, B, H])``.
    """
    if cell not in CELL_GATES:
        raise ValueError(f"unknown cell kind {cell!r}")
    if backward not in BACKWARDS:
        raise ValueError(f"backward={backward!r} not in {BACKWARDS}")
    B, T, _ = pre.shape
    if n_frames is None:
        n_frames = jnp.full((B,), T, jnp.int32)
    cfg = RnnKernelConfig(
        cell=cell, activation=activation, time_block=int(time_block),
        interpret=default_interpret() if interpret is None else interpret,
        backward=backward)
    if not cfg.interpret and cfg.time_block % 8:
        # the time rows are the second-minor dim of every streamed block;
        # Mosaic refuses a block whose second-minor dim is neither a
        # multiple of 8 nor the whole axis
        raise ValueError(
            f"time_block={cfg.time_block}: a compiled persistent-RNN "
            f"kernel needs a multiple of 8 (interpret mode takes any)")
    return _persistent(cfg, pre, w, b, jnp.asarray(h0),
                       jnp.asarray(n_frames, jnp.int32))
