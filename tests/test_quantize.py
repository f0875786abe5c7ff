"""Int8 weight quantization (utils/quantize.py): round-trip bounds,
selective quantization, fused-forward parity, size accounting, and an
end-to-end SSD detection check."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

from analytics_zoo_tpu.utils.quantize import (
    QTensor,
    dequantize_params,
    make_quantized_forward,
    quantize_params,
    quantize_tensor,
    quantized_nbytes,
)


class TestQTensor:
    def test_roundtrip_error_bound(self):
        rng = np.random.RandomState(0)
        w = rng.randn(64, 128).astype(np.float32)
        qt = quantize_tensor(w)
        assert qt.q.dtype == jnp.int8
        back = np.asarray(qt.dequant())
        # per-channel symmetric: error <= scale/2 elementwise
        scale = np.asarray(qt.scale)
        assert (np.abs(back - w) <= scale[None, :] / 2 + 1e-7).all()

    def test_zero_channel(self):
        w = np.zeros((8, 4), np.float32)
        w[:, 0] = 1.0
        qt = quantize_tensor(w)
        np.testing.assert_allclose(np.asarray(qt.dequant()), w, atol=1e-7)

    def test_pytree_registered(self):
        qt = quantize_tensor(np.ones((4, 4), np.float32))
        leaves = jax.tree_util.tree_leaves(qt)
        assert len(leaves) == 2            # q + scale
        moved = jax.device_put(qt)
        assert isinstance(moved, QTensor)


class TestQuantizeParams:
    def _params(self):
        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        return m, m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))

    def test_selective(self):
        _, variables = self._params()
        q = quantize_params(variables, min_size=1024)
        flat = jax.tree_util.tree_leaves(
            q, is_leaf=lambda x: isinstance(x, QTensor))
        n_q = sum(isinstance(l, QTensor) for l in flat)
        assert n_q == 2                    # both kernels; biases untouched
        qb, fb = quantized_nbytes(q)
        assert qb < fb * 0.5               # material saving

    def test_small_tensors_skipped(self):
        _, variables = self._params()
        q = quantize_params(variables, min_size=10**9)
        flat = jax.tree_util.tree_leaves(
            q, is_leaf=lambda x: isinstance(x, QTensor))
        assert not any(isinstance(l, QTensor) for l in flat)

    def test_forward_parity(self):
        m, variables = self._params()
        x = jnp.asarray(np.random.RandomState(1).randn(4, 64), jnp.float32)
        ref = m.apply(variables, x)
        fwd = make_quantized_forward(m)
        out = fwd(quantize_params(variables, min_size=1024), x)
        ref_n = np.asarray(ref)
        err = np.abs(np.asarray(out) - ref_n).max()
        assert err < 0.05 * (np.abs(ref_n).max() + 1e-6), err

    def test_dequantize_params_dtype(self):
        _, variables = self._params()
        deq = dequantize_params(quantize_params(variables, min_size=1024),
                                jnp.bfloat16)
        kernel = deq["params"]["layers_0"]["kernel"]
        assert kernel.dtype == jnp.bfloat16


class TestQuantizedSSD:
    def test_ssd_detections_survive_quantization(self):
        """End-to-end: quantized SSD forward keeps detection outputs close
        to fp32 (scores within tolerance, same output structure)."""
        from analytics_zoo_tpu.models import SSDDetector

        model = SSDDetector(num_classes=4, resolution=300)
        x = jnp.asarray(
            np.random.RandomState(2).randn(1, 300, 300, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x)
        ref = np.asarray(model.apply(variables, x))

        fwd = make_quantized_forward(model)
        out = np.asarray(fwd(quantize_params(variables), x))
        assert out.shape == ref.shape
        # scores: top detections must stay close (untrained net -> loose)
        np.testing.assert_allclose(out[..., 1], ref[..., 1], atol=0.05)


class TestQuantizedPredictor:
    def test_predictor_quantized_close_to_fp32(self):
        """SSDPredictor(quantize=True): same records, detections close to
        the fp32 predictor's."""
        import cv2

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.data import SSDByteRecord
        from analytics_zoo_tpu.models import SSDVgg
        from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                     SSDPredictor)

        rng = np.random.RandomState(3)
        model = Model(SSDVgg(num_classes=4, resolution=300))
        model.build(0, jnp.zeros((1, 300, 300, 3), jnp.float32))
        recs = []
        for i in range(2):
            img = rng.randint(0, 255, (80, 60, 3), np.uint8)
            _, buf = cv2.imencode(".jpg", img)
            recs.append(SSDByteRecord(data=buf.tobytes(), path=f"{i}.jpg"))

        param = PreProcessParam(batch_size=2, resolution=300)
        base = SSDPredictor(model, param, n_classes=4).predict(recs)
        quant = SSDPredictor(model, param, n_classes=4,
                             quantize=True).predict(recs)
        assert len(base) == len(quant) == 2
        for b, q in zip(base, quant):
            assert b.shape == q.shape
            np.testing.assert_allclose(q[:, 1], b[:, 1], atol=0.05)

    def test_frcnn_predictor_quantized_matches_dequantized_fp32(self):
        """FrcnnPredictor(quantize=True)'s serving-path contract: the
        int8-in-HBM program equals the fp32 program run on the SAME
        dequantized weights.  (Closeness to the ORIGINAL fp32 weights is
        a model property, not a serving-path one: with random weights the
        two-stage proposal top-k amplifies int8-sized score shifts into
        entirely different ROI sets, unlike the single-stage SSD test
        above.)"""
        import cv2

        from analytics_zoo_tpu.data import SSDByteRecord
        from analytics_zoo_tpu.models import FasterRcnnDetector, FrcnnParam
        from analytics_zoo_tpu.ops import ProposalParam
        from analytics_zoo_tpu.pipelines.frcnn import FrcnnPredictor
        from analytics_zoo_tpu.pipelines.ssd import PreProcessParam

        rng = np.random.RandomState(5)
        det = FasterRcnnDetector(param=FrcnnParam(
            num_classes=3, proposal=ProposalParam(pre_nms_topn=64,
                                                  post_nms_topn=16)))
        x0 = jnp.zeros((1, 128, 128, 3))
        info0 = jnp.asarray([[128.0, 128.0, 1.0]])
        variables = det.init(jax.random.PRNGKey(0), x0, info0)

        recs = []
        for i in range(2):
            img = rng.randint(0, 255, (100, 80, 3), np.uint8)
            _, buf = cv2.imencode(".jpg", img)
            recs.append(SSDByteRecord(data=buf.tobytes(), path=f"{i}.jpg"))
        param = PreProcessParam(batch_size=2, resolution=128)

        # full precision: the two differently-compiled programs (dequant
        # fused into convs vs precomputed fp32 weights) must not diverge
        # in low-order bf16 bits that the proposal top-k would amplify
        with jax.default_matmul_precision("float32"):
            qp = FrcnnPredictor(det, variables, param, quantize=True)
            assert any("int8" in str(l.dtype) for l in
                       jax.tree_util.tree_leaves(qp.variables))
            quant = qp.predict(recs)

            dq_vars = dequantize_params(qp.variables)
            base = FrcnnPredictor(det, dq_vars, param).predict(recs)
        assert len(base) == len(quant) == 2
        for b, q in zip(base, quant):
            assert b.shape == q.shape
            np.testing.assert_allclose(q, b, rtol=1e-4, atol=1e-4)

    def test_fp32_predictor_sees_later_weight_loads(self):
        """fp32 path must read model.variables at CALL time: weights
        loaded after predictor construction take effect."""
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.models import SSDVgg
        from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                     SSDPredictor)

        model = Model(SSDVgg(num_classes=4, resolution=300))
        model.build(0, jnp.zeros((1, 300, 300, 3), jnp.float32))
        pred = SSDPredictor(model, PreProcessParam(batch_size=1,
                                                   resolution=300),
                            n_classes=4)
        x = jnp.asarray(np.random.RandomState(4).randn(1, 300, 300, 3),
                        jnp.float32)
        before = np.asarray(pred.detect_normalized(x))
        # perturb weights through the Model API
        import jax as _jax
        new = _jax.tree_util.tree_map(lambda p: p * 1.5,
                                      model.variables["params"])
        model.load_weights(new)
        after = np.asarray(pred.detect_normalized(x))
        assert not np.allclose(before, after)

    def test_bf16_quantized_forward_runs(self):
        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
        x = jnp.asarray(np.random.RandomState(5).randn(4, 64), jnp.float32)
        fwd = make_quantized_forward(m, jnp.bfloat16)
        out = fwd(quantize_params(variables, min_size=1024), x)
        assert out.dtype == jnp.float32     # cast back after bf16 compute
        ref = np.asarray(m.apply(variables, x))
        assert np.abs(np.asarray(out) - ref).max() < 0.1 * (
            np.abs(ref).max() + 1e-6)


class TestInt8Compute:
    """compute="int8": real int8×int8→int32 matmuls/convs with dynamic
    per-tensor activation quantization (VERDICT r3 item 2 — the
    weight-only path compresses HBM but does fp math)."""

    def test_dense_parity(self):
        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
        x = jnp.asarray(np.random.RandomState(1).randn(4, 64), jnp.float32)
        ref = np.asarray(m.apply(variables, x))
        fwd = make_quantized_forward(m, compute="int8")
        out = np.asarray(fwd(quantize_params(variables, min_size=1024), x))
        # activation quant adds error on top of weight quant: looser bound
        assert np.abs(out - ref).max() < 0.1 * (np.abs(ref).max() + 1e-6)

    def test_conv_parity_all_geometries(self):
        """Strided / padded / dilated / grouped convs all route through
        the interceptor's lax.conv_general_dilated reconstruction."""

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.Conv(32, (3, 3), strides=(2, 2), padding="SAME")(x)
                x = nn.relu(x)
                x = nn.Conv(32, (3, 3), padding=((1, 1), (1, 1)),
                            kernel_dilation=(2, 2))(x)
                x = nn.relu(x)
                x = nn.Conv(32, (3, 3), padding=1, feature_group_count=2)(x)
                return nn.Conv(8, (1, 1))(x)

        m = Net()
        x = jnp.asarray(np.random.RandomState(2).randn(2, 16, 16, 8),
                        jnp.float32)
        variables = m.init(jax.random.PRNGKey(0), x)
        ref = np.asarray(m.apply(variables, x))
        fwd = make_quantized_forward(m, compute="int8")
        out = np.asarray(fwd(quantize_params(variables, min_size=256), x))
        assert out.shape == ref.shape
        assert np.abs(out - ref).max() < 0.15 * (np.abs(ref).max() + 1e-6)

    def test_int8_math_is_exact_for_integer_weights(self):
        """With integer-valued weights and activations in range, the int8
        path must be bit-exact (q*scale reconstruction introduces no
        float error beyond the rescale): proves the conv really runs on
        integer values, not dequantized floats."""
        from analytics_zoo_tpu.utils.quantize import int8_apply

        m = nn.Conv(4, (3, 3), padding=1, use_bias=False)
        rng = np.random.RandomState(3)
        w = rng.randint(-126, 127, (3, 3, 2, 4)).astype(np.float32)
        w[0, 0, 0, :] = 127          # per-channel amax exactly 127 →
        x_np = rng.randint(-126, 127, (1, 8, 8, 2)).astype(np.float32)
        x_np[0, 0, 0, 0] = 127       # → weight AND activation scales == 1
        x = jnp.asarray(x_np)
        variables = {"params": {"kernel": jnp.asarray(w)}}
        ref = np.asarray(m.apply(variables, x))
        q = quantize_params(variables, min_size=1)
        out = np.asarray(int8_apply(m.apply, q, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-2)

    def test_unselected_layers_stay_fp(self):
        """Layers whose kernel is NOT a QTensor run the normal fp path —
        mixed graphs work (quantize_params selectivity is honored)."""
        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
        # only the big first kernel quantizes; Dense(8)'s 2048-element
        # kernel stays fp under min_size=4096
        q = quantize_params(variables, min_size=4096)
        n_q = sum(isinstance(l, QTensor) for l in jax.tree_util.tree_leaves(
            q, is_leaf=lambda x: isinstance(x, QTensor)))
        assert n_q == 1
        x = jnp.asarray(np.random.RandomState(4).randn(4, 64), jnp.float32)
        ref = np.asarray(m.apply(variables, x))
        out = np.asarray(make_quantized_forward(m, compute="int8")(q, x))
        assert np.abs(out - ref).max() < 0.1 * (np.abs(ref).max() + 1e-6)

    @pytest.mark.slow
    def test_ssd_predictor_int8_compute(self):
        """SSDPredictor(quantize="int8") end-to-end on records: output
        structure intact, scores close to fp on an untrained net.

        Slow lane (ISSUE 9 tier-1 budget): this single test compiled
        TWO full SSD300 programs (fp + int8-intercepted) for ~280 s of
        the 870 s budget.  The int8-compute mechanism itself stays in
        tier-1 through the dense/conv-geometry/exactness/fallback parity
        tests above — only this end-to-end SSD assurance pass rides the
        slow lane."""
        import cv2

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.data import SSDByteRecord
        from analytics_zoo_tpu.models import SSDVgg
        from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                     SSDPredictor)

        rng = np.random.RandomState(6)
        model = Model(SSDVgg(num_classes=4, resolution=300))
        model.build(0, jnp.zeros((1, 300, 300, 3), jnp.float32))
        recs = []
        for i in range(2):
            img = rng.randint(0, 255, (80, 60, 3), np.uint8)
            _, buf = cv2.imencode(".jpg", img)
            recs.append(SSDByteRecord(data=buf.tobytes(), path=f"{i}.jpg"))
        param = PreProcessParam(batch_size=2, resolution=300)
        base = SSDPredictor(model, param, n_classes=4).predict(recs)
        quant = SSDPredictor(model, param, n_classes=4,
                             quantize="int8").predict(recs)
        assert len(base) == len(quant) == 2
        for b, q in zip(base, quant):
            assert b.shape == q.shape
            np.testing.assert_allclose(q[:, 1], b[:, 1], atol=0.1)

    def test_non_conv_dense_qtensors_fall_back_to_dequant(self):
        """DEFAULT_PATTERN also quantizes nn.Embed's `embedding` (and
        would catch RNN-cell kernels) — modules the interceptor can't
        run in int8.  compute="int8" must dequantize those up front
        (discovered by an abstract trace) instead of crashing."""

        class Net(nn.Module):
            @nn.compact
            def __call__(self, ids):
                x = nn.Embed(64, 128, name="emb")(ids)
                return nn.Dense(8, name="out")(x)

        m = Net()
        ids = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        variables = m.init(jax.random.PRNGKey(0), ids)
        q = quantize_params(variables, min_size=512)
        kinds = {k for k in ("embedding", "kernel")
                 for l in [q["params"]["emb" if k == "embedding" else "out"]]
                 if isinstance(l.get(k), QTensor)}
        assert kinds == {"embedding", "kernel"}   # BOTH got quantized
        ref = np.asarray(m.apply(variables, ids))
        out = np.asarray(make_quantized_forward(m, compute="int8")(q, ids))
        assert np.abs(out - ref).max() < 0.1 * (np.abs(ref).max() + 1e-6)

    def test_int8_conv1d_channel_last(self):
        """1-D convs are channel-last in flax; the interceptor must NOT
        fall into lax's channel-first default dimension numbers."""
        m = nn.Conv(16, (5,), padding="SAME")
        x = jnp.asarray(np.random.RandomState(8).randn(2, 32, 8),
                        jnp.float32)
        variables = m.init(jax.random.PRNGKey(0), x)
        ref = np.asarray(m.apply(variables, x))
        q = quantize_params(variables, min_size=256)
        from analytics_zoo_tpu.utils.quantize import int8_apply
        out = np.asarray(int8_apply(m.apply, q, x))
        assert out.shape == ref.shape
        assert np.abs(out - ref).max() < 0.1 * (np.abs(ref).max() + 1e-6)

    def test_bf16_mixed_int8(self):
        """compute="int8" with bf16 remainder: QTensor scales must stay
        fp32 (accuracy-critical rescale) while unselected layers cast."""
        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
        q = quantize_params(variables, min_size=1024)
        fwd = make_quantized_forward(m, jnp.bfloat16, compute="int8")
        x = jnp.asarray(np.random.RandomState(7).randn(4, 64), jnp.float32)
        out = fwd(q, x)
        assert out.dtype == jnp.float32
        ref = np.asarray(m.apply(variables, x))
        assert np.abs(np.asarray(out) - ref).max() < 0.15 * (
            np.abs(ref).max() + 1e-6)


class TestServingArtifact:
    def test_npz_roundtrip(self, tmp_path):
        from analytics_zoo_tpu.utils.quantize import (load_quantized_npz,
                                                      save_quantized_npz)

        m = nn.Sequential([nn.Dense(256), nn.relu, nn.Dense(8)])
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
        q = quantize_params(variables, min_size=1024)
        path = str(tmp_path / "art.npz")
        save_quantized_npz(path, q)
        back = load_quantized_npz(path)

        x = jnp.asarray(np.random.RandomState(6).randn(2, 64), jnp.float32)
        fwd = make_quantized_forward(m)
        np.testing.assert_allclose(np.asarray(fwd(back, x)),
                                   np.asarray(fwd(q, x)),
                                   rtol=1e-6, atol=1e-7)

    def test_export_cli_end_to_end(self, tmp_path):
        import subprocess
        import sys as _sys

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.models import DeepSpeech2

        m = Model(DeepSpeech2(hidden=64))
        m.build(0, jnp.zeros((1, 100, 13), jnp.float32))
        import os as _os
        repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        model_file = str(tmp_path / "m.flax")
        m.save(model_file)
        out = str(tmp_path / "m_int8.npz")
        env = dict(_os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
        r = subprocess.run(
            [_sys.executable, _os.path.join(repo, "tools/export_serving.py"),
             "--model-file", model_file, "--arch", "ds2", "--hidden", "64",
             "--out", out, "--verify"],
            capture_output=True, text=True, env=env)
        assert r.returncode == 0, r.stderr[-800:]
        assert "verify: max abs err" in r.stdout

    def test_npz_suffix_normalized_and_root_leaf(self, tmp_path):
        from analytics_zoo_tpu.utils.quantize import (load_quantized_npz,
                                                      save_quantized_npz)

        qt = quantize_tensor(np.random.RandomState(7)
                             .randn(64, 64).astype(np.float32))
        p = save_quantized_npz(str(tmp_path / "noext"), qt)
        assert p.endswith(".npz")
        back = load_quantized_npz(p)
        assert isinstance(back, QTensor)
        np.testing.assert_array_equal(np.asarray(back.q), np.asarray(qt.q))
