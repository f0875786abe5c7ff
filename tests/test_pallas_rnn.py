"""Persistent-RNN Pallas kernel (ops.pallas_rnn) parity tests.

Interpret mode on CPU pins the acceptance gate of ISSUE 6: the pallas
engine must match the blocked scan to ≤1e-5 fwd AND grad — uniform and
ragged/masked batches, both directions, every ported cell — plus the
H-too-large-for-VMEM fallback (warn + blocked scan, never an error).
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.core.rnn import (
    BiRecurrent,
    GRUCell,
    LSTMCell,
    Recurrent,
    RnnCell,
)
from analytics_zoo_tpu.ops.pallas_rnn import (
    CELL_CARRY,
    CELL_GATES,
    RnnKernelConfig,
    persistent_rnn,
    persistent_vmem_bytes,
)

pytestmark = pytest.mark.pallas

RNG = jax.random.PRNGKey(7)

CELLS = [
    ("rnn", lambda: RnnCell(hidden_size=6)),
    ("rnn_identity", lambda: RnnCell(hidden_size=5, identity_input=True,
                                     activation="clipped_relu")),
    ("gru", lambda: GRUCell(hidden_size=6)),
    ("lstm", lambda: LSTMCell(hidden_size=6)),
]


def _x_for(name, key=RNG, B=3, T=7):
    # T=7: still exercises time-block padding (pads to the 8-step time
    # block) and multi-block blocked scans (block_size=4 → 2 blocks),
    # at ~60% of the T=11 interpret-mode wall time the r7 suite paid
    # (the tier-1 budget satellite of ISSUE 9) — coverage-equivalent,
    # cheaper geometry
    D = 5 if name == "rnn_identity" else 4  # identity i2h: D == hidden
    return jax.random.normal(key, (B, T, D))


def _assert_tree_close(a, b, atol):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


class TestEngineEquivalence:
    # ragged/masked for every ported cell; the uniform variant only for
    # the vanilla cells (for the gated cells it exercises a strict
    # subset of the ragged path — dropping it keeps tier-1 wall time
    # bounded without narrowing the acceptance gate)
    @pytest.mark.parametrize(
        "name,make,masked",
        [(n, m, True) for n, m in CELLS]
        + [(n, m, False) for n, m in CELLS[:2]],
        ids=[f"{c[0]}-ragged" for c in CELLS]
        + [f"{c[0]}-uniform" for c in CELLS[:2]])
    def test_fwd_and_grad_match_blocked_scan(self, name, make, masked):
        """The ISSUE-6 acceptance gate: ≤1e-5 fwd+grad vs the blocked
        scan, uniform and masked ragged batches."""
        x = _x_for(name)
        n = jnp.array([7, 5, 2], jnp.int32) if masked else None
        blocked = Recurrent(cell=make(), block_size=4)
        pallas = Recurrent(cell=make(), engine="pallas", pallas_time_block=4)
        v = blocked.init(RNG, x)
        # shared parameter tree: pallas-engine init is shape-identical
        v_p = pallas.init(RNG, x)
        assert (jax.tree_util.tree_map(lambda a: a.shape, v)
                == jax.tree_util.tree_map(lambda a: a.shape, v_p))

        y_b = blocked.apply(v, x, n_frames=n)
        y_p = pallas.apply(v, x, n_frames=n)
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_p),
                                   atol=1e-5)

        def loss(net):
            return lambda v: jnp.sum(net.apply(v, x, n_frames=n) ** 2)

        _assert_tree_close(jax.grad(loss(blocked))(v),
                           jax.grad(loss(pallas))(v), atol=1e-5)

    # vanilla covers the single-carry prefix gather, lstm the stacked
    # (c, h) carry; gru's reverse path is structurally identical
    @pytest.mark.parametrize("name,make",
                             [CELLS[0], CELLS[3]],
                             ids=[CELLS[0][0], CELLS[3][0]])
    def test_reverse_direction_matches_blocked_scan(self, name, make):
        """Reverse engine parity — the prefix-only backward scan
        BiRecurrent needs (valid frames reverse in place, padding
        untouched)."""
        x = _x_for(name)
        n = jnp.array([7, 5, 2], jnp.int32)
        blocked = Recurrent(cell=make(), block_size=4, reverse=True)
        pallas = Recurrent(cell=make(), engine="pallas", reverse=True,
                          pallas_time_block=4)
        v = blocked.init(RNG, x)
        np.testing.assert_allclose(
            np.asarray(blocked.apply(v, x, n_frames=n)),
            np.asarray(pallas.apply(v, x, n_frames=n)), atol=1e-5)

    def test_birecurrent_masked_matches_unpadded_references(self):
        """End-to-end bidirectional check on the pallas engine: padded
        ragged rows equal their own unpadded forwards (the padded-
        reverse defect must stay fixed on the kernel path too)."""
        x = _x_for("rnn")
        n = np.array([7, 5, 2], np.int32)
        bi = BiRecurrent(cell=RnnCell(hidden_size=6), merge="sum",
                         engine="pallas")
        v = bi.init(RNG, x)
        y = np.asarray(bi.apply(v, x, n_frames=jnp.asarray(n)))
        for i, ni in enumerate(n):
            ref = np.asarray(bi.apply(v, x[i:i + 1, :ni]))
            np.testing.assert_allclose(y[i:i + 1, :ni], ref, atol=1e-5,
                                       err_msg=f"row {i} (n={ni})")
            assert np.abs(y[i, ni:]).max(initial=0.0) == 0.0

    def test_carry_and_return_carry_parity(self):
        cell = RnnCell(hidden_size=4)
        x = _x_for("rnn")
        blocked = Recurrent(cell=cell, block_size=3)
        pallas = Recurrent(cell=cell, engine="pallas", pallas_time_block=4)
        v = blocked.init(RNG, x)
        c0 = jnp.full((3, 4), 0.25)
        y1, c1 = blocked.apply(v, x, carry0=c0, return_carry=True)
        y2, c2 = pallas.apply(v, x, carry0=c0, return_carry=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   atol=1e-5)

    def test_lstm_tuple_carry_roundtrips(self):
        """LSTM's (c, h) carry stacks into the kernel and unstacks back
        to the blocked path's tuple convention."""
        cell = LSTMCell(hidden_size=6)
        x = _x_for("lstm")
        blocked = Recurrent(cell=cell, block_size=3)
        pallas = Recurrent(cell=cell, engine="pallas", pallas_time_block=4)
        v = blocked.init(RNG, x)
        _, c1 = blocked.apply(v, x, return_carry=True)
        _, c2 = pallas.apply(v, x, return_carry=True)
        assert isinstance(c2, tuple) and len(c2) == 2
        _assert_tree_close(c1, c2, atol=1e-5)

    @pytest.mark.parametrize("engine", [None, "pallas"],
                             ids=["blocked", "pallas"])
    def test_n_frames_beyond_t_clamps_instead_of_nan(self, engine):
        """n_frames > T (e.g. a caller passing pre-conv frame counts to
        a truncated batch) must clamp to T, not drive the reverse
        prefix gather out of bounds (take_along_axis NaN fill)."""
        x = _x_for("rnn")
        net = Recurrent(cell=RnnCell(hidden_size=6), reverse=True,
                        engine=engine, block_size=4)
        v = net.init(RNG, x)
        y_over = net.apply(v, x, n_frames=jnp.array([9, 5, 2]))
        y_full = net.apply(v, x, n_frames=jnp.array([7, 5, 2]))
        assert np.isfinite(np.asarray(y_over)).all()
        np.testing.assert_allclose(np.asarray(y_over), np.asarray(y_full),
                                   atol=1e-6)

    def test_masked_carry_freezes_at_true_length(self):
        cell = GRUCell(hidden_size=5)
        x = _x_for("gru", B=2, T=7)
        n = np.array([7, 4], np.int32)
        net = Recurrent(cell=cell, engine="pallas", pallas_time_block=4)
        v = net.init(RNG, x)
        _, c = net.apply(v, x, n_frames=jnp.asarray(n), return_carry=True)
        _, c_short = net.apply(v, x[1:2, :4], return_carry=True)
        np.testing.assert_allclose(np.asarray(c[1:2]),
                                   np.asarray(c_short), atol=1e-5)


def _kernel_grad_case(cell, T=7, time_block=4, masked=True, seed=0):
    """Kernel-direct grad comparison: full (d_pre, dW, db, dh0) under a
    mixed ys+carry cotangent, transposed-kernel backward vs the
    reference-scan vjp (the pre-r10 bit-compatible path)."""
    k, C = CELL_GATES[cell], CELL_CARRY[cell]
    B, H = 3, 6
    rng = np.random.RandomState(seed)
    pre = jnp.asarray(rng.randn(B, T, k * H).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(H, k * H).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.randn(k * H).astype(np.float32) * 0.1)
    h0 = jnp.asarray(rng.randn(C, B, H).astype(np.float32) * 0.2)
    n = jnp.array([T, max(T - 4, 1), 2], jnp.int32) if masked else None
    gy = jnp.asarray(rng.randn(B, T, H).astype(np.float32))
    gc = jnp.asarray(rng.randn(C, B, H).astype(np.float32))

    def grads(backward):
        def loss(pre, w, b, h0):
            ys, cf = persistent_rnn(
                pre, w, b, h0, n, cell=cell, activation="tanh",
                time_block=time_block, interpret=True, backward=backward)
            # cotangents on BOTH outputs so g_cf exercises the dh seed
            return jnp.sum(ys * gy) + jnp.sum(cf * gc)
        return jax.grad(loss, argnums=(0, 1, 2, 3))(pre, w, b, h0)

    return grads("pallas"), grads("scan")


class TestTransposedBackward:
    """ISSUE 13 acceptance gate: the transposed persistent backward
    (reversed time grid, W/Wᵀ VMEM-resident, dW fused-accumulated in
    VMEM scratch, within-block recompute from streamed block-boundary
    carries) matches the reference-scan vjp ≤1e-5 on every ported cell
    — dx, dW_h2h, db and dh0 each checked explicitly."""

    # ragged for every cell (uniform is a strict subset of the masked
    # path — one vanilla variant keeps it covered at tier-1 cost, the
    # ISSUE-9 budget discipline)
    @pytest.mark.parametrize(
        "cell,masked",
        [("vanilla", True), ("gru", True), ("lstm", True),
         ("vanilla", False)],
        ids=["vanilla-ragged", "gru-ragged", "lstm-ragged",
             "vanilla-uniform"])
    def test_kernel_bwd_matches_scan_vjp(self, cell, masked):
        got, ref = _kernel_grad_case(cell, masked=masked)
        for name, a, r in zip(("d_pre", "dW_h2h", "db", "dh0"), got, ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), atol=1e-5,
                err_msg=f"{cell} {name}")

    def test_dw_accumulates_across_time_blocks(self):
        """T=11 at time_block=3 runs a 4-step reversed grid: the fp32
        dW/db accumulators must carry across every grid step and
        stream out once — a per-block reset or a missed final flush
        shows up directly in dW."""
        got, ref = _kernel_grad_case("gru", T=11, time_block=3)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(ref[2]),
                                   atol=1e-5)

    def test_reverse_grads_match_blocked_scan(self):
        """Grad parity THROUGH the reverse prefix gather — what the
        BiRecurrent backward direction runs.  The gather transpose is
        outside the kernel and cell-independent; the kernel-direct
        tests above carry the per-cell grad coverage."""
        name, make = CELLS[0]
        x = _x_for(name)
        n = jnp.array([7, 5, 2], jnp.int32)
        blocked = Recurrent(cell=make(), block_size=4, reverse=True)
        pallas = Recurrent(cell=make(), engine="pallas", reverse=True,
                          pallas_time_block=4)
        v = blocked.init(RNG, x)

        def loss(net):
            return lambda v: jnp.sum(net.apply(v, x, n_frames=n) ** 2)

        _assert_tree_close(jax.grad(loss(blocked))(v),
                           jax.grad(loss(pallas))(v), atol=1e-5)

    def test_birecurrent_padded_row_grads_match_blocked(self):
        """Bidirectional ragged grads on the pallas engine: the padded
        rows' gradients must match the blocked scan's exactly — the
        masked cotangent pass-through (frozen carry transposed) is
        what keeps padding inert in the backward too."""
        x = _x_for("rnn")
        n = jnp.array([7, 5, 2], jnp.int32)
        cellf = lambda: RnnCell(hidden_size=6)  # noqa: E731
        blocked = BiRecurrent(cell=cellf(), merge="sum", block_size=4)
        pallas = BiRecurrent(cell=cellf(), merge="sum", engine="pallas",
                             pallas_time_block=4)
        v = blocked.init(RNG, x)

        def loss(net):
            return lambda v: jnp.sum(net.apply(v, x, n_frames=n) ** 2)

        _assert_tree_close(jax.grad(loss(blocked))(v),
                           jax.grad(loss(pallas))(v), atol=1e-5)

    def test_recurrent_scan_backward_matches_blocked(self):
        """``pallas_backward='scan'`` keeps the pre-r10 recompute vjp
        available through the flax layer (the bit-compatible
        fallback)."""
        x = _x_for("rnn")
        n = jnp.array([7, 5, 2], jnp.int32)
        blocked = Recurrent(cell=RnnCell(hidden_size=6), block_size=4)
        pallas = Recurrent(cell=RnnCell(hidden_size=6), engine="pallas",
                           pallas_backward="scan", pallas_time_block=4)
        v = blocked.init(RNG, x)

        def loss(net):
            return lambda v: jnp.sum(net.apply(v, x, n_frames=n) ** 2)

        _assert_tree_close(jax.grad(loss(blocked))(v),
                           jax.grad(loss(pallas))(v), atol=1e-5)

    def test_bad_backward_name_rejected(self):
        pre = jnp.zeros((2, 4, 4))
        with pytest.raises(ValueError, match="backward"):
            persistent_rnn(pre, jnp.zeros((4, 4)), jnp.zeros((4,)),
                           jnp.zeros((1, 2, 4)), backward="magic")

    @pytest.mark.pallas(device=True)
    def test_compiled_bwd_matches_interpret(self):
        """Compiled-Mosaic twin of the backward parity test — skipped
        off TPU by the conftest `pallas` marker hook."""
        rng = np.random.RandomState(3)
        B, T, H = 8, 32, 128
        pre = jnp.asarray(rng.randn(B, T, H).astype(np.float32) * 0.3)
        w = jnp.asarray(rng.randn(H, H).astype(np.float32) * 0.3)
        b = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
        h0 = jnp.zeros((1, B, H))

        def grads(interpret):
            def loss(pre, w, b, h0):
                ys, cf = persistent_rnn(pre, w, b, h0, cell="vanilla",
                                        activation="relu",
                                        interpret=interpret)
                return jnp.sum(ys ** 2) + jnp.sum(cf ** 2)
            return jax.grad(loss, argnums=(0, 1, 2, 3))(pre, w, b, h0)

        for a, r in zip(grads(False), grads(True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=1e-4)


class TestBackwardBudget:
    """ISSUE 13 satellite: the Recurrent budget check prices BOTH
    passes, so training geometry that fits fwd-only but not fwd+bwd
    falls back BEFORE compile, with the warning naming the pass."""

    def _patched(self, monkeypatch, fwd_bytes, bwd_bytes):
        from analytics_zoo_tpu.ops import pallas_rnn

        def fake(hidden, cell="vanilla", batch=8, time_block=8,
                 weight_bytes=4, backward=False):
            return bwd_bytes if backward else fwd_bytes

        monkeypatch.setattr(pallas_rnn, "persistent_vmem_bytes", fake)

    def test_backward_overflow_falls_back_naming_the_pass(
            self, monkeypatch):
        self._patched(monkeypatch, fwd_bytes=10, bwd_bytes=10 ** 12)
        x = _x_for("rnn")
        n = jnp.array([7, 5, 2], jnp.int32)
        blocked = Recurrent(cell=RnnCell(hidden_size=6), block_size=4)
        tight = Recurrent(cell=RnnCell(hidden_size=6), engine="pallas",
                          pallas_vmem_limit=1000)
        v = blocked.init(RNG, x)
        with pytest.warns(UserWarning,
                          match="backward.*falling back") as rec:
            y = tight.apply(v, x, n_frames=n)
        assert not any("forward" in str(w.message) for w in rec)
        # bit-identical to the pre-PR fallback: the blocked scan runs
        np.testing.assert_array_equal(
            np.asarray(blocked.apply(v, x, n_frames=n)), np.asarray(y))

    def test_forward_overflow_named_too(self, monkeypatch):
        self._patched(monkeypatch, fwd_bytes=10 ** 12, bwd_bytes=10 ** 12)
        x = _x_for("rnn")
        net = Recurrent(cell=RnnCell(hidden_size=6), engine="pallas",
                        pallas_vmem_limit=1000)
        v = net.init(RNG, x)
        with pytest.warns(UserWarning, match="forward\\+backward"):
            net.apply(v, x)

    def test_pallas_grad_false_prices_forward_only(self, monkeypatch):
        """Inference-only callers opt out of the backward term: the
        same bwd-overflowing geometry keeps the kernel."""
        self._patched(monkeypatch, fwd_bytes=10, bwd_bytes=10 ** 12)
        x = _x_for("rnn")
        blocked = Recurrent(cell=RnnCell(hidden_size=6), block_size=4)
        net = Recurrent(cell=RnnCell(hidden_size=6), engine="pallas",
                        pallas_vmem_limit=1000, pallas_grad=False)
        v = blocked.init(RNG, x)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            y = net.apply(v, x)
        np.testing.assert_allclose(np.asarray(blocked.apply(v, x)),
                                   np.asarray(y), atol=1e-5)

    def test_ds2_threads_pallas_grad_to_recurrent(self, monkeypatch):
        """Forward-only DS2 programs (bench fwd sub-phases, inference)
        build with ``rnn_pallas_grad=False`` so a backward-only VMEM
        overflow cannot fell the forward kernel — pin that the module
        actually threads the knob down to the budget decision."""
        from analytics_zoo_tpu.models import DeepSpeech2

        seen = []
        orig = Recurrent._pallas_or_fallback

        def spy(self, batch, dtype):
            seen.append((self.pallas_grad, self.pallas_backward))
            return orig(self, batch, dtype)

        monkeypatch.setattr(Recurrent, "_pallas_or_fallback", spy)
        module = DeepSpeech2(hidden=8, n_rnn_layers=1, n_mels=13,
                             rnn_engine="pallas",
                             rnn_pallas_backward="scan",
                             rnn_pallas_grad=False)
        x = jnp.zeros((2, 12, 13))
        v = module.init(RNG, x)
        module.apply(v, x)
        assert seen and all(s == (False, "scan") for s in seen)

    def test_budget_backward_term_exceeds_forward(self):
        """The real formula: the transposed backward's residency (W and
        Wᵀ resident + fp32 dW accumulator) strictly exceeds the
        forward's at every cell."""
        for cell in ("vanilla", "gru", "lstm"):
            f = persistent_vmem_bytes(512, cell)
            bwd = persistent_vmem_bytes(512, cell, backward=True)
            assert bwd > f, cell


class TestVmemFallback:
    def test_h_too_large_falls_back_to_blocked_with_warning(self):
        """A geometry that cannot be VMEM-resident must WARN and run the
        blocked scan — same numbers, never an error."""
        x = _x_for("rnn")
        blocked = Recurrent(cell=RnnCell(hidden_size=6), block_size=4)
        tight = Recurrent(cell=RnnCell(hidden_size=6), engine="pallas",
                          pallas_vmem_limit=1)      # nothing fits
        v = blocked.init(RNG, x)
        with pytest.warns(UserWarning, match="falling back"):
            y = tight.apply(v, x)
        np.testing.assert_allclose(np.asarray(blocked.apply(v, x)),
                                   np.asarray(y), atol=1e-6)

    def test_unsupported_cell_falls_back(self):
        import flax.linen as nn

        class OddCell(nn.Module):
            hidden_size: int = 4

            def setup(self):
                self.h2h = nn.Dense(self.hidden_size)
                self.i2h = nn.Dense(self.hidden_size)

            def project(self, x):
                return self.i2h(x)

            def recur(self, carry, pre):
                h = jnp.tanh(pre + self.h2h(carry))
                return h, h

            def __call__(self, carry, x):
                return self.recur(carry, self.project(x))

            def initial_carry(self, batch, dtype=jnp.float32):
                return jnp.zeros((batch, self.hidden_size), dtype)

        x = jax.random.normal(RNG, (2, 7, 3))
        net = Recurrent(cell=OddCell(), engine="pallas")
        with pytest.warns(UserWarning, match="does not support"):
            v = net.init(RNG, x)
            net.apply(v, x)

    def test_budget_formula_scales_with_h_and_gates(self):
        """The docs/PERFORMANCE.md budget formula: the weight term is
        k·H_pad²·weight_bytes — monotone in H and gate count — and the
        verdicts match what Mosaic accepted on the v5e (PR 21 chip run):
        the DS2 parity geometry (vanilla H=1760, B=32) fits both passes
        in fp32 and bf16; the GRU at H=1760 fits forward only."""
        from analytics_zoo_tpu.ops import vmem

        small = persistent_vmem_bytes(256, "vanilla")
        big = persistent_vmem_bytes(2048, "vanilla")
        assert big > small
        assert (persistent_vmem_bytes(256, "lstm")
                > persistent_vmem_bytes(256, "vanilla"))
        for wb in (4, 2):
            for bwd in (False, True):
                assert vmem.fits(persistent_vmem_bytes(
                    1760, "vanilla", batch=32, weight_bytes=wb,
                    backward=bwd)), (wb, bwd)
            assert vmem.fits(persistent_vmem_bytes(
                1760, "gru", batch=32, weight_bytes=wb))
            assert not vmem.fits(persistent_vmem_bytes(
                1760, "gru", batch=32, weight_bytes=wb, backward=True))

    def test_estimate_counts_tile_padding(self):
        """Mosaic pads the second-minor dim of a VMEM buffer to the
        dtype's sublane tile (8 rows of f32, 16 of bf16): a bf16 stream
        block of 8 time rows costs what 16 rows cost, and the (1, k·H)
        bias costs 8 (f32) / 16 (bf16) rows — the estimate must count
        that, or it admits geometries the chip refuses."""
        from analytics_zoo_tpu.ops.vmem import padded_bytes

        assert padded_bytes((1, 1, 8832), np.float32) == 8 * 8832 * 4
        assert padded_bytes((1, 4, 8832), np.float32) == 8 * 8832 * 4
        assert padded_bytes((32, 8, 1792), jnp.bfloat16) \
            == padded_bytes((32, 16, 1792), jnp.bfloat16)
        assert padded_bytes((200, 6), np.float32) == 200 * 128 * 4
        kw = dict(batch=32, weight_bytes=2)
        assert (persistent_vmem_bytes(1024, "vanilla", time_block=8, **kw)
                == persistent_vmem_bytes(1024, "vanilla", time_block=16,
                                         **kw))
        # logical bytes of the declared forward buffers (no padding)
        # undercount: the estimate must be strictly above them
        logical = (1024 * 1024 * 2 + 2 * 32 * 8 * (1024 + 1024) * 2)
        assert persistent_vmem_bytes(1024, "vanilla", **kw) > logical

    def test_bad_engine_name_rejected(self):
        x = _x_for("rnn")
        net = Recurrent(cell=RnnCell(hidden_size=6), engine="warp")
        with pytest.raises(ValueError, match="engine"):
            net.init(RNG, x)


class TestKernelDirect:
    """ops.pallas_rnn API-level checks (no flax wrapper)."""

    def test_matches_reference_scan_nonaligned_shapes(self):
        """Lane/sublane/time padding is correctness-inert: B=3 (pads to
        8), H=6 (pads to 128), T=11 (pads to the time block)."""
        from analytics_zoo_tpu.ops.pallas_rnn import _scan_reference

        rng = np.random.RandomState(0)
        B, T, H = 3, 11, 6
        pre = jnp.asarray(rng.randn(B, T, H).astype(np.float32) * 0.3)
        w = jnp.asarray(rng.randn(H, H).astype(np.float32) * 0.3)
        b = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
        h0 = jnp.zeros((1, B, H))
        n = jnp.array([11, 5, 2], jnp.int32)
        ys, cf = persistent_rnn(pre, w, b, h0, n, cell="vanilla",
                                activation="tanh", interpret=True)
        cfg = RnnKernelConfig("vanilla", "tanh", 8, True)
        ys_ref, cf_ref = _scan_reference(cfg, pre, w, b, h0, n)
        np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_ref),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(cf), np.asarray(cf_ref),
                                   atol=1e-6)

    def test_unknown_cell_kind_raises(self):
        pre = jnp.zeros((2, 4, 4))
        with pytest.raises(ValueError, match="cell"):
            persistent_rnn(pre, jnp.zeros((4, 4)), jnp.zeros((4,)),
                           jnp.zeros((1, 2, 4)), cell="elman")

    def test_compiled_time_block_must_be_a_multiple_of_8(self):
        """Mosaic refuses a stream block of 4 time rows (chip run,
        PR 21: "last two dimensions of your block shape are divisible
        by 8 and 128"); the wrapper says so before the compiler does."""
        pre = jnp.zeros((2, 8, 4))
        with pytest.raises(ValueError, match="multiple of 8"):
            persistent_rnn(pre, jnp.zeros((4, 4)), jnp.zeros((4,)),
                           jnp.zeros((1, 2, 4)), time_block=4,
                           interpret=False)

    @pytest.mark.pallas(device=True)
    def test_compiled_kernel_matches_interpret(self):
        """Compiled-Mosaic twin of the parity test — auto-skipped off
        TPU by the conftest `pallas` marker hook."""
        rng = np.random.RandomState(1)
        B, T, H = 8, 32, 128
        pre = jnp.asarray(rng.randn(B, T, H).astype(np.float32) * 0.3)
        w = jnp.asarray(rng.randn(H, H).astype(np.float32) * 0.3)
        b = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
        h0 = jnp.zeros((1, B, H))
        ys_c, cf_c = persistent_rnn(pre, w, b, h0, cell="vanilla",
                                    activation="relu", interpret=False)
        ys_i, cf_i = persistent_rnn(pre, w, b, h0, cell="vanilla",
                                    activation="relu", interpret=True)
        np.testing.assert_allclose(np.asarray(ys_c), np.asarray(ys_i),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cf_c), np.asarray(cf_i),
                                   atol=1e-5)


def _zoo_inputs(cell, H, dtype, B=32, T=32):
    k = CELL_GATES[cell]
    rng = np.random.RandomState(0)
    pre = jnp.asarray(rng.randn(B, T, k * H) * 0.1, dtype)
    w = jnp.asarray(rng.randn(H, k * H) / np.sqrt(H) * 0.5, dtype)
    b = jnp.asarray(rng.randn(k * H) * 0.01, dtype)
    h0 = jnp.zeros((1, B, H), dtype)
    n = jnp.asarray(rng.randint(T // 2, T + 1, (B,)), jnp.int32)
    return pre, w, b, h0, n


ZOO = [(cell, H, dtype) for cell in ("vanilla", "gru")
       for H in (1024, 1760) for dtype in ("float32", "bfloat16")]


class TestZooGeometriesOnDevice:
    """The widths the zoo trains (DS2: H=1024 default, 1760 reference),
    B=32, compiled by Mosaic — `JAX_PLATFORMS=tpu python -m pytest
    tests/test_pallas_rnn.py -m pallas`.  Tolerances are the TPU's
    default matmul precision (bf16 passes), not the kernel's."""

    @pytest.mark.pallas(device=True)
    @pytest.mark.parametrize("cell,H,dtype", ZOO)
    def test_forward_compiles_and_matches_scan(self, cell, H, dtype):
        from analytics_zoo_tpu.ops.pallas_rnn import _scan_reference

        pre, w, b, h0, n = _zoo_inputs(cell, H, dtype)
        act = "clipped_relu" if cell == "vanilla" else "relu"
        ys, _ = persistent_rnn(pre, w, b, h0, n, cell=cell, activation=act,
                               interpret=False)
        ref, _ = _scan_reference(RnnKernelConfig(cell, act, 8, False, "scan"),
                                 pre, w, b, h0, n)
        np.testing.assert_allclose(
            np.asarray(ys, np.float32), np.asarray(ref, np.float32),
            atol=2e-3 if dtype == "float32" else 2e-2)

    @pytest.mark.pallas(device=True)
    @pytest.mark.parametrize("cell,H,dtype", ZOO)
    def test_backward_compiles_where_the_estimate_admits_it(
            self, cell, H, dtype):
        """The estimate and the chip must agree: every geometry
        ``vmem.fits`` admits compiles and matches the scan vjp; the GRU
        at H=1760 it refuses (Mosaic: "Used 195.72M of 128.00M vmem",
        fp32) and ``Recurrent`` resolves to the blocked scan."""
        from analytics_zoo_tpu.ops import vmem

        wb = jnp.dtype(dtype).itemsize
        if not vmem.fits(persistent_vmem_bytes(H, cell, batch=32,
                                               weight_bytes=wb,
                                               backward=True)):
            assert (cell, H) == ("gru", 1760)
            net = Recurrent(cell=GRUCell(hidden_size=H), engine="pallas")
            with pytest.warns(UserWarning, match="backward.*falling back"):
                assert net.resolved_engine(32, dtype) == "blocked"
            return
        pre, w, b, h0, n = _zoo_inputs(cell, H, dtype)
        act = "clipped_relu" if cell == "vanilla" else "relu"

        def grads(backward):
            def loss(pre, w, b):
                ys, _ = persistent_rnn(pre, w, b, h0, n, cell=cell,
                                       activation=act, interpret=False,
                                       backward=backward)
                return jnp.sum(ys.astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(pre, w, b)

        # Not elementwise: the two backwards round their matmuls
        # differently (the scan vjp runs XLA's default TPU precision),
        # which flips the clipped-ReLU mask of units sitting at 0 and
        # moves single elements by ~10 % of the largest gradient
        # (measured PR 21).  A wrong mask, carry or accumulation moves
        # the whole tensor; direction and norm catch that.
        for name, a, r in zip(("d_pre", "d_w", "d_b"),
                              grads("pallas"), grads("scan")):
            a = np.asarray(a, np.float32).ravel()
            r = np.asarray(r, np.float32).ravel()
            assert np.isfinite(a).all(), name
            cos = float(a @ r / (np.linalg.norm(a) * np.linalg.norm(r)))
            rel = float(np.linalg.norm(a - r) / np.linalg.norm(r))
            assert cos > 0.99 and rel < 0.15, (name, cos, rel)


class TestDS2Wiring:
    def test_ds2_model_pallas_engine_matches_blocked(self):
        """models/deepspeech2 → pipelines wiring: the full DS2 forward
        (conv + BN + BiRNN) agrees across engines on a masked ragged
        batch, params shared."""
        from analytics_zoo_tpu.pipelines.deepspeech2 import make_ds2_model

        blocked = make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=32,
                                 rnn_block=4)
        pallas = make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=32,
                                rnn_engine="pallas")
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3, 32, 13).astype(np.float32) * 0.3)
        n = jnp.array([32, 27, 12], jnp.int32)
        y_b = blocked.module.apply(blocked.variables, x, n)
        y_p = pallas.module.apply(blocked.variables, x, n)
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_p),
                                   atol=1e-5)

    @pytest.mark.slow
    def test_ds2_pallas_train_grads_match_blocked(self):
        """Full CTC-loss grad parity through the DS2 model — heavier
        assurance on top of the tier-1 engine-level grad gate
        (TestEngineEquivalence), so it rides the slow lane."""
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            ds2_ctc_criterion, make_ds2_model)

        blocked = make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=24,
                                 rnn_block=4)
        pallas = make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=24,
                                rnn_engine="pallas")
        rng = np.random.RandomState(0)
        batch = {
            "input": (jnp.asarray(rng.randn(2, 24, 13).astype(np.float32)),
                      jnp.array([24, 15], jnp.int32)),
            "n_frames": jnp.array([24, 15], jnp.int32),
            "labels": jnp.asarray(rng.randint(1, 29, (2, 4)), jnp.int32),
            "label_mask": jnp.ones((2, 4), jnp.float32),
        }
        crit = ds2_ctc_criterion()

        def loss_for(model):
            def loss(params):
                x, n = batch["input"]
                lp = model.module.apply(
                    {"params": params,
                     **{k: v for k, v in model.variables.items()
                        if k != "params"}}, x, n)
                return crit(lp, batch)
            return loss

        p = blocked.variables["params"]
        l_b, g_b = jax.value_and_grad(loss_for(blocked))(p)
        l_p, g_p = jax.value_and_grad(loss_for(pallas))(p)
        np.testing.assert_allclose(float(l_b), float(l_p), atol=1e-5)
        _assert_tree_close(g_b, g_p, atol=1e-4)
