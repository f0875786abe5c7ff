"""RNN training fast path (core.rnn): hoisted input projections +
blocked scan + length masking must be numerically equivalent to the
per-step scan body, bit-compatible in parameters (existing checkpoints
restore), and correct on ragged (length-masked) batches — the padded-
reverse-scan defect fix is pinned against per-example unpadded
references."""

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

RNG = jax.random.PRNGKey(7)

CELLS = [
    ("rnn", lambda: RnnCell(hidden_size=6)),
    ("rnn_identity", lambda: RnnCell(hidden_size=5, identity_input=True,
                                     activation="clipped_relu")),
    ("gru", lambda: GRUCell(hidden_size=6)),
    ("lstm", lambda: LSTMCell(hidden_size=6)),
]


def _x_for(name, key=RNG, B=3, T=11):
    D = 5 if name == "rnn_identity" else 4  # identity i2h: D == hidden
    return jax.random.normal(key, (B, T, D))


class TestHoistedEquivalence:
    # reverse=True only for one cell: the reverse transform is cell-
    # independent (flip before/after the shared scan), so one cell pins
    # it and the matrix stays CPU-CI-cheap
    @pytest.mark.parametrize("name,make,reverse",
                             [(n, m, False) for n, m in CELLS]
                             + [("gru", CELLS[2][1], True)],
                             ids=[c[0] for c in CELLS] + ["gru-rev"])
    def test_fwd_and_grad_match_per_step_scan(self, name, make, reverse):
        x = _x_for(name)
        legacy = Recurrent(cell=make(), hoist=False, reverse=reverse)
        fast = Recurrent(cell=make(), reverse=reverse, block_size=4)
        v = legacy.init(RNG, x)
        # same param tree: the fast path restores legacy-initialized
        # variables verbatim (names, shapes, dtypes)
        v_fast = fast.init(RNG, x)
        assert (jax.tree_util.tree_map(lambda a: a.shape, v)
                == jax.tree_util.tree_map(lambda a: a.shape, v_fast))

        y_legacy = legacy.apply(v, x)
        y_fast = fast.apply(v, x)
        np.testing.assert_allclose(np.asarray(y_legacy),
                                   np.asarray(y_fast), atol=1e-5)

        def loss(fn):
            return lambda v: jnp.sum(fn.apply(v, x) ** 2)

        g_legacy = jax.grad(loss(legacy))(v)
        g_fast = jax.grad(loss(fast))(v)
        for a, b in zip(jax.tree_util.tree_leaves(g_legacy),
                        jax.tree_util.tree_leaves(g_fast)):
            # gradients reach magnitudes of hundreds, where one float32
            # ulp (3e-5 at 256-512) is already over the atol
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-5)

    @pytest.mark.parametrize("U", [1, 3, 11, 16])
    def test_block_size_is_numerics_inert(self, U):
        """Any block size (divisible or not, larger than T or not) gives
        the same answer — block padding never advances the carry."""
        x = _x_for("gru")
        ref = Recurrent(cell=GRUCell(hidden_size=6), hoist=False)
        v = ref.init(RNG, x)
        y_ref = ref.apply(v, x)
        y = Recurrent(cell=GRUCell(hidden_size=6), block_size=U).apply(v, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                                   atol=1e-5)

    def test_carry_and_return_carry_parity(self):
        """Streaming contract: carry0/return_carry behave identically on
        both paths (StreamingDS2 rides the fast path by default)."""
        cell = RnnCell(hidden_size=4)
        x = _x_for("rnn")
        legacy = Recurrent(cell=cell, hoist=False)
        fast = Recurrent(cell=cell, block_size=3)
        v = legacy.init(RNG, x)
        c0 = jnp.full((3, 4), 0.25)
        y1, c1 = legacy.apply(v, x, carry0=c0, return_carry=True)
        y2, c2 = fast.apply(v, x, carry0=c0, return_carry=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)

    def test_legacy_path_rejects_n_frames(self):
        x = _x_for("rnn")
        net = Recurrent(cell=RnnCell(hidden_size=6), hoist=False)
        v = net.init(RNG, x)
        with pytest.raises(ValueError, match="hoist"):
            net.apply(v, x, n_frames=jnp.array([11, 5, 3]))


class TestLengthMasking:
    @pytest.mark.parametrize("name,make", CELLS, ids=[c[0] for c in CELLS])
    def test_masked_birecurrent_matches_unpadded_references(self, name,
                                                            make):
        """The padded-reverse defect fix: ragged rows of a padded batch
        must equal their own UNPADDED forward — before length masking
        the backward scan ingested trailing zero-padding first."""
        x = _x_for(name, B=3, T=11)
        n = np.array([11, 7, 3], np.int32)
        bi = BiRecurrent(cell=make(), merge="sum", block_size=4)
        v = bi.init(RNG, x)
        y = np.asarray(bi.apply(v, x, n_frames=jnp.asarray(n)))
        for i, ni in enumerate(n):
            ref = np.asarray(bi.apply(v, x[i:i + 1, :ni]))
            np.testing.assert_allclose(y[i:i + 1, :ni], ref, atol=1e-5,
                                       err_msg=f"row {i} (n={ni})")
            # padded positions are zeroed, not garbage
            assert np.abs(y[i, ni:]).max(initial=0.0) == 0.0

    def test_masked_forward_freezes_carry(self):
        """return_carry under masking yields the state at each row's TRUE
        last frame, not the state after scanning padding."""
        cell = GRUCell(hidden_size=5)
        x = _x_for("gru", B=2, T=11)
        n = np.array([11, 6], np.int32)
        net = Recurrent(cell=cell, block_size=4)
        v = net.init(RNG, x)
        _, c = net.apply(v, x, n_frames=jnp.asarray(n), return_carry=True)
        _, c_short = net.apply(v, x[1:2, :6], return_carry=True)
        np.testing.assert_allclose(np.asarray(c[1:2]),
                                   np.asarray(c_short), atol=1e-6)

    def test_full_lengths_equal_unmasked(self):
        x = _x_for("lstm")
        bi = BiRecurrent(cell=LSTMCell(hidden_size=6), block_size=4)
        v = bi.init(RNG, x)
        y0 = bi.apply(v, x)
        y1 = bi.apply(v, x, n_frames=jnp.full((3,), x.shape[1], jnp.int32))
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=1e-6)


class TestDS2ModelMasking:
    def _model(self, **kw):
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.models import DeepSpeech2

        m = Model(DeepSpeech2(hidden=16, n_rnn_layers=2, rnn_block=4, **kw))
        m.build(0, jnp.zeros((1, 40, 13)))
        return m

    def test_ragged_batch_matches_per_example(self):
        """Eval-mode DS2 forward on a zero-padded ragged batch equals the
        per-example unpadded forwards on each row's valid output prefix
        (ceil(n/2) frames after the stride-2 conv)."""
        m = self._model()
        rng = np.random.RandomState(0)
        x = rng.randn(3, 40, 13).astype(np.float32) * 0.3
        n = np.array([40, 27, 12], np.int32)
        for i in range(3):
            x[i, n[i]:] = 0.0                   # zero padding, as batched
        y = np.asarray(m.module.apply(m.variables, jnp.asarray(x),
                                      jnp.asarray(n)))
        for i, ni in enumerate(n):
            ref = np.asarray(m.module.apply(m.variables,
                                            jnp.asarray(x[i:i + 1, :ni])))
            out_n = (ni + 1) // 2
            np.testing.assert_allclose(y[i:i + 1, :out_n], ref[:, :out_n],
                                       atol=1e-4, err_msg=f"row {i}")

    def test_masked_train_step_runs_and_bn_sees_valid_frames_only(self):
        """Train-mode BN statistics exclude padding: feeding the same
        valid content with more padding must not change the masked
        batch-stats update."""
        m = self._model()
        x = np.random.RandomState(1).randn(2, 40, 13).astype(np.float32)
        n = np.array([20, 14], np.int32)
        x[0, 20:] = 0.0
        x[1, 14:] = 0.0
        _, mut = m.module.apply(m.variables, jnp.asarray(x),
                                jnp.asarray(n), train=True,
                                mutable=["batch_stats"])
        x2 = np.zeros((2, 60, 13), np.float32)   # same content, more pad
        x2[:, :40] = x
        _, mut2 = m.module.apply(m.variables, jnp.asarray(x2),
                                 jnp.asarray(n), train=True,
                                 mutable=["batch_stats"])
        for a, b in zip(jax.tree_util.tree_leaves(mut),
                        jax.tree_util.tree_leaves(mut2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_legacy_and_fast_model_share_checkpoints(self, tmp_path):
        """PR-3 LKG snapshot taken from the legacy-scan model restores
        into the hoisted model (same param tree) and both forwards
        agree."""
        from analytics_zoo_tpu.parallel import (SGD, checkpoint as ckpt,
                                                create_train_state)
        from analytics_zoo_tpu.pipelines.deepspeech2 import make_ds2_model

        old = make_ds2_model(hidden=16, n_rnn_layers=2, utt_length=40,
                             rnn_hoist=False)
        new = make_ds2_model(hidden=16, n_rnn_layers=2, utt_length=40,
                             seed=1)
        state_old = create_train_state(old, SGD(0.1))
        ckpt.save(str(tmp_path / "ck"), state_old, tier="lkg",
                  meta={"iteration": 0})
        found = ckpt.lkg_snapshot(str(tmp_path / "ck"))
        assert found is not None
        state_new = ckpt.load(found[0],
                              target=create_train_state(new, SGD(0.1)),
                              verify=False)
        for a, b in zip(jax.tree_util.tree_leaves(state_old.params),
                        jax.tree_util.tree_leaves(state_new.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        x = jnp.asarray(
            np.random.RandomState(2).randn(2, 40, 13).astype(np.float32))
        y_old = old.module.apply({"params": state_new.params,
                                  **state_new.model_state}, x)
        y_new = new.module.apply({"params": state_new.params,
                                  **state_new.model_state}, x)
        np.testing.assert_allclose(np.asarray(y_old), np.asarray(y_new),
                                   atol=1e-5)
