"""Recurrent layers via ``lax.scan`` — compiler-friendly TPU recurrence.

Replaces the reference's BigDL ``Recurrent``/``Cell`` machinery and the DS2
extensions (``RnnCellDS``, ``BiRecurrentDS`` — reference
``pipeline/deepspeech2/src/main/scala/com/intel/analytics/bigdl/nn/*``).
Time is axis 1 ([B, T, D]); the bidirectional pass is a flip + second scan
(no dynamic shapes).

Training fast path (default, ``hoist=True``): the cuDNN-class RNN
restructuring (persistent/fused RNNs à la Deep Speech 2, Amodei et al.
2015) applied to the scan formulation —

- **Hoisted input projections**: every input-side matmul of a cell
  (``RnnCell.i2h``, the ``ir/iz/in`` gates of :class:`GRUCell`, the
  ``ii/if/ig/io`` gates of :class:`LSTMCell`) is computed for the WHOLE
  sequence as one ``[B·T, D] → [B·T, k·H]`` MXU-shaped matmul before the
  scan; the scan body keeps only the ``h2h`` recurrence.  The parameter
  tree is IDENTICAL to the per-step path (same names, same shapes, same
  init), so existing checkpoints restore unchanged — pinned by
  ``tests/test_rnn_fastpath.py``.
- **Blocked scan**: the scan runs over ``T/U`` chunks with a ``U``-step
  unrolled body (``block_size``), amortising per-step dispatch/loop
  overhead ~U× while keeping compile size bounded.
- **Length masking** (``n_frames``): the carry freezes past each row's
  true length and masked outputs are zeroed, so zero-padding is
  correctness-inert; the reverse pass reverses only the valid prefix
  (a per-row gather, not a whole-axis flip), fixing the padded-reverse
  defect where ``BiRecurrent``'s backward scan ingested trailing padding
  FIRST.

``hoist=False`` keeps the original per-step ``nn.scan`` body (one tiny
latency-bound matmul per timestep per gate) — retained as the equivalence
reference.

**Engines.**  ``Recurrent(engine=...)`` names the recurrence schedule
explicitly; all three share ONE parameter tree (checkpoints move freely):

- ``"legacy"`` — the per-step ``nn.scan`` body (``hoist=False``);
- ``"blocked"`` — hoisted projections + time-blocked scan (the default,
  ``hoist=True``);
- ``"pallas"`` — the persistent-RNN kernel (``ops.pallas_rnn``): the
  h2h weights load into VMEM once and the timestep loop runs on-chip,
  breaking the ≈ B/240 HBM-restream roofline of docs/MFU_CEILING.md
  (Diamos et al., "Persistent RNNs", ICML 2016).  The grad pass is the
  matching TRANSPOSED persistent kernel (``pallas_backward="pallas"``,
  Diamos §4): reversed time grid with ``W``/``Wᵀ`` VMEM-resident and
  the dW accumulation fused in VMEM scratch, so the backward's h2h
  intensity decouples from batch exactly like the forward's.  Falls
  back to ``"blocked"`` with a warning when the geometry cannot be
  VMEM-resident (budget formula: ``persistent_vmem_bytes`` — priced
  for BOTH passes; the warning names which overflowed) or the cell
  kind is not ported into the kernel.
"""

from __future__ import annotations

import warnings
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import initializers

ENGINES = ("legacy", "blocked", "pallas")


def _cell_kwargs(cell: nn.Module) -> dict:
    """Dataclass fields of a cell template, for re-instantiation under an
    explicit scope name (shared by the legacy scan and the fast path)."""
    return {
        k: getattr(cell, k)
        for k in type(cell).__dataclass_fields__
        if k not in ("parent", "name")
    }


class RnnCell(nn.Module):
    """Vanilla RNN cell: ``h' = act(W_i x + W_h h + b)``.

    With ``identity_input=True`` the input projection is the identity — the
    DS2 trick where inputs are pre-projected by the preceding conv/linear
    (reference ``bigdl/nn/RNN.scala:28`` ``RnnCellDS`` identity i2h).  In that
    mode the input width must equal ``hidden_size``.
    """

    hidden_size: int
    identity_input: bool = False
    activation: str = "relu"  # DS2 uses clipped ReLU

    def setup(self):
        if not self.identity_input:
            self.i2h = nn.Dense(self.hidden_size)
        self.h2h = nn.Dense(self.hidden_size, use_bias=True)

    def project(self, x):
        """Input projection over ANY leading dims — called once on the
        whole [B, T, D] sequence by the hoisted path."""
        return x if self.identity_input else self.i2h(x)

    def recur(self, carry, pre):
        """One recurrence step from a precomputed input projection."""
        h = carry
        z = pre + self.h2h(h)
        if self.activation == "relu":
            new_h = nn.relu(z)
        elif self.activation == "clipped_relu":
            new_h = jnp.clip(z, 0.0, 20.0)
        else:
            new_h = jnp.tanh(z)
        return new_h, new_h

    def __call__(self, carry, x):
        return self.recur(carry, self.project(x))

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.hidden_size), dtype)


class _GruGates(nn.Module):
    """``flax.linen.GRUCell``-compatible gate math with the input-side
    matmuls split out for hoisting.  Parameter tree (names, shapes, init
    distributions) is identical to ``nn.GRUCell``: biased input denses
    ``ir/iz/in``, orthogonal recurrent denses ``hr/hz`` (no bias) and
    ``hn`` (biased) — so checkpoints trained against the wrapped flax
    cell restore unchanged."""

    features: int

    def setup(self):
        H = self.features
        self.d_ir = nn.Dense(H, use_bias=True, name="ir")
        self.d_iz = nn.Dense(H, use_bias=True, name="iz")
        self.d_in = nn.Dense(H, use_bias=True, name="in")
        ortho = initializers.orthogonal()
        self.d_hr = nn.Dense(H, use_bias=False, name="hr", kernel_init=ortho)
        self.d_hz = nn.Dense(H, use_bias=False, name="hz", kernel_init=ortho)
        self.d_hn = nn.Dense(H, use_bias=True, name="hn", kernel_init=ortho)

    def project(self, x):
        return jnp.concatenate(
            [self.d_ir(x), self.d_iz(x), self.d_in(x)], axis=-1)

    def recur(self, h, pre):
        i_r, i_z, i_n = jnp.split(pre, 3, axis=-1)
        r = nn.sigmoid(i_r + self.d_hr(h))
        z = nn.sigmoid(i_z + self.d_hz(h))
        n = jnp.tanh(i_n + r * self.d_hn(h))
        new_h = (1.0 - z) * n + z * h
        return new_h, new_h

    def __call__(self, h, x):
        return self.recur(h, self.project(x))


class GRUCell(nn.Module):
    hidden_size: int

    def setup(self):
        self.gru = _GruGates(features=self.hidden_size)

    def project(self, x):
        return self.gru.project(x)

    def recur(self, carry, pre):
        return self.gru.recur(carry, pre)

    def __call__(self, carry, x):
        return self.gru(carry, x)

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.hidden_size), dtype)


class _LstmGates(nn.Module):
    """``flax.linen.OptimizedLSTMCell``-compatible gate math with the
    input-side matmuls split out for hoisting.  Parameter tree matches
    the flax cell (= ``LSTMCell``'s): unbiased input kernels
    ``ii/if/ig/io``, biased orthogonal recurrent kernels ``hi/hf/hg/ho``;
    gate order in every concatenation is (i, f, g, o), matching the flax
    concat-then-split evaluation."""

    features: int

    def setup(self):
        H = self.features
        ortho = initializers.orthogonal()
        self.d_ii = nn.Dense(H, use_bias=False, name="ii")
        self.d_if = nn.Dense(H, use_bias=False, name="if")
        self.d_ig = nn.Dense(H, use_bias=False, name="ig")
        self.d_io = nn.Dense(H, use_bias=False, name="io")
        self.d_hi = nn.Dense(H, use_bias=True, name="hi", kernel_init=ortho)
        self.d_hf = nn.Dense(H, use_bias=True, name="hf", kernel_init=ortho)
        self.d_hg = nn.Dense(H, use_bias=True, name="hg", kernel_init=ortho)
        self.d_ho = nn.Dense(H, use_bias=True, name="ho", kernel_init=ortho)

    def project(self, x):
        return jnp.concatenate(
            [self.d_ii(x), self.d_if(x), self.d_ig(x), self.d_io(x)],
            axis=-1)

    def recur(self, carry, pre):
        c, h = carry
        i_i, i_f, i_g, i_o = jnp.split(pre, 4, axis=-1)
        i = nn.sigmoid(i_i + self.d_hi(h))
        f = nn.sigmoid(i_f + self.d_hf(h))
        g = jnp.tanh(i_g + self.d_hg(h))
        o = nn.sigmoid(i_o + self.d_ho(h))
        new_c = f * c + i * g
        new_h = o * jnp.tanh(new_c)
        return (new_c, new_h), new_h

    def __call__(self, carry, x):
        return self.recur(carry, self.project(x))


class LSTMCell(nn.Module):
    hidden_size: int

    def setup(self):
        self.lstm = _LstmGates(features=self.hidden_size)

    def project(self, x):
        return self.lstm.project(x)

    def recur(self, carry, pre):
        return self.lstm.recur(carry, pre)

    def __call__(self, carry, x):
        return self.lstm(carry, x)

    def initial_carry(self, batch: int, dtype=jnp.float32):
        z = jnp.zeros((batch, self.hidden_size), dtype)
        return (z, z)


def _pallas_cell_kind(cell) -> Optional[str]:
    """Kernel cell kind for a ``core.rnn`` cell, or None if the cell's
    gate math is not ported into ``ops.pallas_rnn``."""
    if isinstance(cell, RnnCell):
        return "vanilla"
    if isinstance(cell, GRUCell):
        return "gru"
    if isinstance(cell, LSTMCell):
        return "lstm"
    return None


def _stack_recurrent_params(kind: str, params):
    """Gate-stack a cell's h2h kernels/biases into the ``[H, k·H]`` /
    ``[k·H]`` layout ``ops.pallas_rnn`` consumes.  Gate order matches
    each cell's ``project`` concatenation (vanilla; GRU r,z,n; LSTM
    i,f,g,o); unbiased gates contribute zero bias columns."""
    if kind == "vanilla":
        p = params["h2h"]
        return p["kernel"], p["bias"]
    if kind == "gru":
        g = params["gru"]
        w = jnp.concatenate(
            [g["hr"]["kernel"], g["hz"]["kernel"], g["hn"]["kernel"]], 1)
        H = g["hn"]["bias"].shape[0]
        b = jnp.concatenate(
            [jnp.zeros((2 * H,), g["hn"]["bias"].dtype), g["hn"]["bias"]])
        return w, b
    l = params["lstm"]
    w = jnp.concatenate([l[k]["kernel"] for k in ("hi", "hf", "hg", "ho")], 1)
    b = jnp.concatenate([l[k]["bias"] for k in ("hi", "hf", "hg", "ho")])
    return w, b


def _masked_step(cell, carry, pre_t, m_t):
    """One recurrence step with an optional per-row validity mask: an
    invalid row's carry freezes and its output is zeroed (padding is
    correctness-inert)."""
    new_carry, y = cell.recur(carry, pre_t)
    if m_t is not None:
        keep = m_t[:, None]
        new_carry = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(keep, nw, old), new_carry, carry)
        y = jnp.where(keep, y, jnp.zeros_like(y))
    return new_carry, y


class Recurrent(nn.Module):
    """Run a cell over time axis 1: [B, T, D] → [B, T, H].

    BigDL ``Recurrent().add(cell)`` equivalent.  ``hoist=True`` (default)
    runs the fast path: one hoisted input-projection matmul for the whole
    sequence, then a time-blocked scan (``block_size`` unrolled steps per
    scan iteration) applying only the ``h2h`` recurrence.  ``n_frames``
    (per-row valid lengths) makes padding correctness-inert: the carry
    freezes past each row's length, masked outputs are zeros, and
    ``reverse=True`` reverses only the valid prefix.  ``hoist=False`` is
    the original per-step ``nn.scan`` body (equivalence/A-B reference;
    no masking support).  All engines share one parameter tree.

    ``engine`` names the schedule explicitly ("legacy" | "blocked" |
    "pallas"); ``None`` derives it from ``hoist`` for backward
    compatibility.  ``engine="pallas"`` runs ``ops.pallas_rnn``'s
    persistent kernel (h2h weights VMEM-resident across all timesteps);
    if the geometry exceeds the VMEM budget (``pallas_vmem_limit``,
    default ``ops.vmem.VMEM_BUDGET_BYTES`` — checked only when the
    kernel would actually compile for a TPU, interpret mode has no VMEM)
    or the cell kind is not ported, it warns and falls back to the
    blocked scan, bit-identical results either way.
    :meth:`resolved_engine` says which engine a geometry runs, so a
    caller that asked for the kernel can check it got it.
    """

    cell: nn.Module
    reverse: bool = False
    hoist: bool = True
    block_size: int = 16
    engine: Optional[str] = None
    pallas_time_block: int = 8
    pallas_vmem_limit: Optional[int] = None
    # data-parallel shard count the VMEM estimate divides the jit-global
    # batch by (each core only holds global/shards rows).  None = the
    # device count — right for pure data parallelism; set explicitly on
    # tensor-parallel meshes whose data axis is smaller.
    pallas_data_shards: Optional[int] = None
    # grad-pass engine: "pallas" = the transposed persistent backward
    # (W/Wᵀ VMEM-resident, fused dW accumulation); "scan" = the
    # reference-scan recompute vjp (bit-compatible pre-r10 behavior)
    pallas_backward: str = "pallas"
    # whether the VMEM budget prices the transposed BACKWARD program
    # too (its residency is strictly larger: W and Wᵀ resident plus the
    # fp32 dW accumulator).  True is the training-safe default — a
    # geometry that fits fwd-only but not fwd+bwd falls back BEFORE
    # compile.  Set False for inference-only programs so fwd-only
    # geometries keep the kernel.
    pallas_grad: bool = True

    def _resolve_engine(self) -> str:
        eng = self.engine
        if eng is None:
            return "blocked" if self.hoist else "legacy"
        if eng not in ENGINES:
            raise ValueError(f"engine={eng!r} not in {ENGINES}")
        return eng

    def resolved_engine(self, batch: int, dtype=jnp.float32) -> str:
        """The engine that runs for a ``[batch, T, D]`` input of
        ``dtype`` in this process: ``engine="pallas"`` resolves to
        ``"blocked"`` when the kernel does not apply (cell kind not
        ported, or over the VMEM budget on a TPU)."""
        engine = self._resolve_engine()
        if engine == "pallas" and self._pallas_or_fallback(batch,
                                                           dtype) is None:
            return "blocked"
        return engine

    def _pallas_or_fallback(self, batch: int, dtype) -> Optional[str]:
        """Cell kind if the persistent kernel applies, else None (warn +
        blocked-scan fallback)."""
        from analytics_zoo_tpu.ops import pallas_rnn, vmem

        kind = _pallas_cell_kind(self.cell)
        if kind is None:
            warnings.warn(
                f"engine='pallas' does not support {type(self.cell).__name__}"
                " — falling back to the blocked scan")
            return None
        interp = pallas_rnn.default_interpret()
        limit = self.pallas_vmem_limit
        if limit is None:
            if interp:          # interpret mode discharges to XLA: no VMEM
                return kind
            # what the kernel will ask Mosaic for (declared buffers plus
            # the compiler's working set) against the chip's budget
            limit, ask = vmem.VMEM_BUDGET_BYTES, vmem.limit_bytes
        else:
            ask = int               # an explicit cap on the declared bytes
        # budget against the dtype that will actually compile (fp32 by
        # default, bf16 under make_train_step(compute_dtype='bf16')
        # casting) and the PER-DEVICE batch: a pre-sharded global batch
        # traces with the global row count, but each core only holds
        # global/shards rows of the streaming working set.  BOTH passes
        # are priced (pallas_grad=True): the transposed backward holds
        # W AND Wᵀ resident plus the fp32 dW accumulator, so a training
        # geometry can fit fwd-only yet overflow on the grad pass — it
        # must fall back BEFORE compile, with the warning naming the
        # overflowing pass.
        shards = self.pallas_data_shards or max(jax.device_count(), 1)
        size_kwargs = dict(batch=-(-batch // shards),
                           time_block=self.pallas_time_block,
                           weight_bytes=jnp.dtype(dtype).itemsize)
        need = {"forward": ask(pallas_rnn.persistent_vmem_bytes(
            self.cell.hidden_size, kind, **size_kwargs))}
        if self.pallas_grad and self.pallas_backward == "pallas":
            need["backward"] = ask(pallas_rnn.persistent_vmem_bytes(
                self.cell.hidden_size, kind, backward=True, **size_kwargs))
        over = {p: nb for p, nb in need.items() if nb > limit}
        if over:
            detail = ", ".join(f"{p} ~{nb / 2**20:.1f} MB"
                               for p, nb in over.items())
            warnings.warn(
                f"persistent-RNN kernel over the {limit / 2**20:.1f} MB "
                f"VMEM budget on the {'+'.join(over)} pass"
                f"{'es' if len(over) > 1 else ''} ({detail}; "
                f"H={self.cell.hidden_size}, {kind}) — falling back to "
                f"the blocked scan")
            return None
        return kind

    @nn.compact
    def __call__(self, x, carry0=None, return_carry: bool = False,
                 n_frames=None):
        """``carry0``/``return_carry`` expose the scan's boundary state for
        streaming inference (chunked input, state carried across calls);
        params are identical either way."""
        engine = self._resolve_engine()
        if engine == "legacy":
            if n_frames is not None:
                raise ValueError(
                    "length masking (n_frames) requires hoist=True (the "
                    "blocked engine) or engine='pallas' — the legacy "
                    "per-step scan path has no masked reverse")
            return self._legacy_scan(x, carry0, return_carry)
        if engine == "pallas":
            kind = self._pallas_or_fallback(x.shape[0], x.dtype)
            if kind is not None:
                return self._pallas_scan(x, carry0, return_carry,
                                         n_frames, kind)
        return self._blocked_scan(x, carry0, return_carry, n_frames)

    # -- legacy per-step body (A/B + equivalence reference) ----------------
    def _legacy_scan(self, x, carry0, return_carry):
        if self.reverse:
            x = jnp.flip(x, axis=1)
        scan = nn.scan(
            type(self.cell),
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=1,
            out_axes=1,
        )
        carry = (carry0 if carry0 is not None
                 else self.cell.initial_carry(x.shape[0], x.dtype))
        final, ys = scan(**_cell_kwargs(self.cell), name="body")(carry, x)
        if self.reverse:
            ys = jnp.flip(ys, axis=1)
        return (ys, final) if return_carry else ys

    # -- hoisted-projection blocked scan -----------------------------------
    def _blocked_scan(self, x, carry0, return_carry, n_frames):
        cell = type(self.cell)(**_cell_kwargs(self.cell), name="body")
        B, T, _ = x.shape
        mask = perm = None
        if n_frames is not None:
            # clamp to T: a row claiming more frames than the batch holds
            # would otherwise drive the reverse prefix gather out of
            # bounds (take_along_axis fills NaN — silent divergence)
            n = jnp.minimum(jnp.asarray(n_frames, jnp.int32), T)
            t_idx = jnp.arange(T, dtype=jnp.int32)
            mask = t_idx[None, :] < n[:, None]                    # [B, T]
            if self.reverse:
                # prefix reversal: valid frames reverse in place, padding
                # stays put (an involution, so the same gather restores
                # output order) — the backward scan starts at each row's
                # TRUE last frame instead of ingesting padding first
                perm = jnp.where(mask, n[:, None] - 1 - t_idx[None, :],
                                 t_idx[None, :])
                x = jnp.take_along_axis(x, perm[..., None], axis=1)
        elif self.reverse:
            x = jnp.flip(x, axis=1)

        pre = cell.project(x)                  # ONE [B·T, D]→[B·T, kH] matmul
        carry = (carry0 if carry0 is not None
                 else cell.initial_carry(B, x.dtype))
        U = max(1, min(int(self.block_size), T))
        nb = -(-T // U)
        Tp = nb * U
        if Tp != T:
            # block padding must not advance the carry: synthesize the
            # full-length mask when the caller didn't pass one
            if mask is None:
                mask = (jnp.arange(Tp, dtype=jnp.int32)[None, :]
                        < jnp.full((B, 1), T, jnp.int32))
            else:
                mask = jnp.pad(mask, ((0, 0), (0, Tp - T)))
            pre = jnp.pad(pre, ((0, 0), (0, Tp - T), (0, 0)))

        # first block unrolled OUTSIDE the scan: creates every param
        # (project made the input denses; recur makes the h2h denses) so
        # the lax.scan body below only ever reads existing params
        ys_first = []
        for u in range(U):
            carry, y = _masked_step(
                cell, carry, pre[:, u],
                None if mask is None else mask[:, u])
            ys_first.append(y)
        parts = [jnp.stack(ys_first, axis=1)]
        if nb > 1:
            H = parts[0].shape[-1]
            pre_r = pre[:, U:].reshape(B, nb - 1, U, pre.shape[-1])
            xs = (pre_r.transpose(1, 0, 2, 3),)
            if mask is not None:
                xs += (mask[:, U:].reshape(B, nb - 1, U).transpose(1, 0, 2),)

            def block(c, inp):
                pre_b = inp[0]
                m_b = inp[1] if len(inp) > 1 else None
                ys_b = []
                for u in range(U):
                    c, y = _masked_step(
                        cell, c, pre_b[:, u],
                        None if m_b is None else m_b[:, u])
                    ys_b.append(y)
                return c, jnp.stack(ys_b, axis=1)

            carry, ys_rest = jax.lax.scan(block, carry, xs)
            parts.append(
                ys_rest.transpose(1, 0, 2, 3).reshape(B, (nb - 1) * U, H))
        ys = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        ys = ys[:, :T]
        if self.reverse:
            ys = (jnp.take_along_axis(ys, perm[..., None], axis=1)
                  if perm is not None else jnp.flip(ys, axis=1))
        return (ys, carry) if return_carry else ys

    # -- persistent-RNN Pallas kernel --------------------------------------
    def _pallas_scan(self, x, carry0, return_carry, n_frames, kind):
        """Hoist the input projections exactly like the blocked scan,
        then hand the whole recurrence to ``ops.pallas_rnn`` — the h2h
        weights stay VMEM-resident across every timestep instead of
        re-streaming from HBM per step.  Reverse / length-mask prep is
        the blocked scan's (prefix gather, not whole-axis flip)."""
        from analytics_zoo_tpu.ops.pallas_rnn import persistent_rnn

        cell = type(self.cell)(**_cell_kwargs(self.cell), name="body")
        B, T, _ = x.shape
        n = perm = None
        if n_frames is not None:
            # same clamp as the blocked scan: n > T must not drive the
            # reverse prefix gather out of bounds (NaN fill)
            n = jnp.minimum(jnp.asarray(n_frames, jnp.int32), T)
            if self.reverse:
                t_idx = jnp.arange(T, dtype=jnp.int32)
                mask = t_idx[None, :] < n[:, None]
                perm = jnp.where(mask, n[:, None] - 1 - t_idx[None, :],
                                 t_idx[None, :])
                x = jnp.take_along_axis(x, perm[..., None], axis=1)
        elif self.reverse:
            x = jnp.flip(x, axis=1)

        pre = cell.project(x)              # ONE [B·T, D]→[B·T, kH] matmul
        carry = (carry0 if carry0 is not None
                 else cell.initial_carry(B, x.dtype))
        if self.is_initializing():
            # one throwaway step creates the h2h params with the exact
            # same names/shapes/init as the scan engines (shared tree)
            cell.recur(carry, pre[:, 0])
        w, b = _stack_recurrent_params(kind, self.variables["params"]["body"])
        h0 = jnp.stack(carry) if isinstance(carry, tuple) \
            else carry[None]
        act = getattr(self.cell, "activation", "relu")
        ys, cf = persistent_rnn(pre, w, b, h0, n, cell=kind,
                                activation=act,
                                time_block=self.pallas_time_block,
                                backward=self.pallas_backward)
        if self.reverse:
            ys = (jnp.take_along_axis(ys, perm[..., None], axis=1)
                  if perm is not None else jnp.flip(ys, axis=1))
        final = tuple(cf[i] for i in range(cf.shape[0])) \
            if isinstance(carry, tuple) else cf[0]
        return (ys, final) if return_carry else ys


class BiRecurrent(nn.Module):
    """Bidirectional recurrence, forward + time-reversed backward pass.

    Reference ``bigdl/nn/BiRecurrentDS.scala:26``: a fwd/rev ``Recurrent``
    pair with ``Reverse`` on the time dim, merged by ``CAddTable`` (sum) or
    concat.  ``merge='sum'`` reproduces DS2; ``merge='concat'`` is the
    general BiLSTM used by the sentiment notebook.

    ``n_frames`` (fast path only) length-masks BOTH directions: the
    backward pass reverses each row's valid prefix instead of flipping
    the whole padded axis, so ragged batches match their per-example
    unpadded references exactly (``tests/test_rnn_fastpath.py``).
    """

    cell: nn.Module
    merge: str = "sum"  # 'sum' | 'concat'
    hoist: bool = True
    block_size: int = 16
    engine: Optional[str] = None
    pallas_time_block: int = 8
    pallas_data_shards: Optional[int] = None
    pallas_backward: str = "pallas"
    pallas_grad: bool = True

    @nn.compact
    def __call__(self, x, n_frames=None):
        fwd = Recurrent(cell=self.cell, hoist=self.hoist,
                        block_size=self.block_size, engine=self.engine,
                        pallas_time_block=self.pallas_time_block,
                        pallas_data_shards=self.pallas_data_shards,
                        pallas_backward=self.pallas_backward,
                        pallas_grad=self.pallas_grad,
                        name="fwd")(
            x, n_frames=n_frames)
        bwd = Recurrent(cell=self.cell, reverse=True, hoist=self.hoist,
                        block_size=self.block_size, engine=self.engine,
                        pallas_time_block=self.pallas_time_block,
                        pallas_data_shards=self.pallas_data_shards,
                        pallas_backward=self.pallas_backward,
                        pallas_grad=self.pallas_grad,
                        name="bwd")(
            x, n_frames=n_frames)
        if self.merge == "sum":
            return fwd + bwd
        return jnp.concatenate([fwd, bwd], axis=-1)
