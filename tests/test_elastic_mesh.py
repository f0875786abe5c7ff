"""Elastic mesh (ISSUE 19): checkpoint re-placement across world
sizes, and serving replicas that ARE mesh slices.

Training half: ``SpecSet.replace_mesh`` + ``checkpoint.restore_elastic``
re-place a checkpoint saved at width W onto a W′ mesh (params are
width-agnostic host values by construction), and
``elastic_resume_coordinates`` translates the manifest's GLOBAL sample
coordinate into loader re-seek terms under any shard count.  The
width-change matrix pins, for EVERY registered pipeline: restoring a
width-4 save onto w′ ∈ {1, 2} preserves the bytes exactly, and one
train step from the restored state is bit-identical to the same step
from a never-resized placement at w′.  (Cross-WIDTH step math is NOT
bitwise — XLA fixes the cross-replica reduction order per width; the
banked ELASTIC_r01.json records those ulp-scale deltas.)

Serving half: ``ReplicaSlice`` (a replica occupying ``width`` devices,
jitted against a sub-mesh via its tier's SpecSet), the pool's
``device_budget`` clamp at the actuator, the policy's slice-unit bound
validation, and the width-vs-count ``Reshape`` decision with the
≈B/128 occupancy-knee rationale (docs/MFU_CEILING.md).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.core import (Linear, LogSoftMax, Model, ReLU,
                                    Sequential)
from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
from analytics_zoo_tpu.data.parallel import elastic_resume_coordinates
from analytics_zoo_tpu.parallel import (
    SGD,
    checkpoint as ckpt_lib,
    create_mesh,
    create_train_state,
    make_train_step,
    pipeline_specs,
    registered_pipelines,
)
from analytics_zoo_tpu.parallel.specs import SpecSet
from analytics_zoo_tpu.resilience.errors import ElasticPlacementError
from analytics_zoo_tpu.serving import (
    OCCUPANCY_KNEE,
    Autoscaler,
    AutoscalePolicy,
    Replica,
    ReplicaPool,
    ReplicaSlice,
    Reshape,
    ServingRuntime,
    VirtualClock,
)


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# replace_mesh: the declaration survives, active sharding may not drop
# ---------------------------------------------------------------------------


class TestReplaceMesh:
    def test_same_declaration_new_mesh(self):
        full = create_mesh()
        half = create_mesh(devices=jax.devices()[:4])
        specs = pipeline_specs("fraud", mesh=full)
        resized = specs.replace_mesh(half)
        assert resized.mesh is half
        assert resized.data_axis_size == 4
        assert resized.rules == specs.rules
        assert resized.batch_overrides == specs.batch_overrides
        # the original declaration is untouched (dataclasses.replace)
        assert specs.data_axis_size == 8

    def test_dropping_an_active_axis_is_refused(self):
        """ssd megatron rules RESOLVE on a data x model mesh; an elastic
        re-placement onto a pure data mesh would silently de-shard the
        weights — replace_mesh refuses by name instead."""
        dm = create_mesh((2, 4), axis_names=("data", "model"))
        specs = pipeline_specs("ssd", mesh=dm, tp="megatron")
        with pytest.raises(ElasticPlacementError, match="model"):
            specs.replace_mesh(create_mesh(devices=jax.devices()[:4]))

    def test_unresolved_declared_axis_moves_freely(self):
        """rec's row-sharding rule declares ``model`` but degrades to
        replicated on a pure data mesh — resizing between pure data
        meshes never activates it, so the move is legal."""
        specs = pipeline_specs("rec", mesh=create_mesh())
        assert "model" in specs.missing_axes()
        resized = specs.replace_mesh(create_mesh(devices=jax.devices()[:2]))
        assert resized.data_axis_size == 2


class TestElasticPlacementBoundary:
    def test_override_axes_missing_from_mesh_named_error(self):
        """Satellite 2: a declaration whose batch-override axes the mesh
        cannot carry fails AT the substrate boundary with the missing
        axes listed — not deep inside jax at device_put time."""
        from jax.sharding import PartitionSpec as P

        specs = SpecSet(create_mesh(),
                        batch_overrides={"input": P("data", "model")})
        with pytest.raises(ElasticPlacementError, match="model"):
            specs.place_state({"w": np.zeros((4,), np.float32)})
        with pytest.raises(ElasticPlacementError, match="model"):
            specs.place_batch({"input": np.zeros((8, 4), np.float32)})

    def test_restore_elastic_structure_mismatch_named_error(self, tmp_path):
        base = str(tmp_path / "c")
        ckpt_lib.save(base, {"w": np.ones((4,), np.float32)})
        specs = pipeline_specs("fraud")
        with pytest.raises(ElasticPlacementError, match="structure"):
            ckpt_lib.restore_elastic(
                base, target={"w": np.ones((4,), np.float32),
                              "extra": np.ones((2,), np.float32)},
                specs=specs)


# ---------------------------------------------------------------------------
# The global sample coordinate → loader re-seek translation
# ---------------------------------------------------------------------------


class TestElasticResumeCoordinates:
    def test_translation_across_geometries(self):
        # 64 samples into epoch 1, new global batch 16 → skip 4 batches
        assert elastic_resume_coordinates(1, 64, 16) == (1, 4)
        # same coordinate, wider world with the same global batch
        assert elastic_resume_coordinates(1, 64, 32) == (1, 2)
        assert elastic_resume_coordinates(0, 0, 8) == (0, 0)

    def test_misaligned_boundary_raises(self):
        with pytest.raises(ValueError, match="not .* multiple"):
            elastic_resume_coordinates(1, 60, 16)

    def test_invalid_coordinates_raise(self):
        with pytest.raises(ValueError):
            elastic_resume_coordinates(-1, 0, 8)
        with pytest.raises(ValueError):
            elastic_resume_coordinates(0, 0, 0)


# ---------------------------------------------------------------------------
# Width-change matrix: every registered pipeline, save@4 → restore@{1,2}
# ---------------------------------------------------------------------------


def _matrix_batch(seed=0, n=8, d=8, classes=4):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    y = (rng.rand(n) * classes).astype(np.int32)
    return {"input": x, "target": y}


class TestWidthChangeMatrix:
    SAVE_W = 4
    RESTORE_WS = (1, 2)

    def test_registry_is_the_expected_zoo(self):
        assert set(registered_pipelines()) == {
            "ssd", "frcnn", "ds2", "fraud", "rec", "sentiment", "lm"}

    @pytest.mark.parametrize("name", sorted(registered_pipelines()))
    def test_save_at_4_restore_at_narrower_bitexact(self, name, tmp_path):
        """Save under the pipeline's width-4 declaration, restore onto
        w′ ∈ {1, 2} via restore_elastic: the placed bytes equal the
        saved bytes, and ONE train step from the restored state is
        bit-identical (loss AND post-step params) to the same step from
        a never-resized width-w′ placement of the same initial state."""
        mesh4 = create_mesh(devices=jax.devices()[:self.SAVE_W])
        specs4 = pipeline_specs(name, mesh=mesh4)
        model = Model(Sequential(layers=[
            Linear(16), ReLU(), Linear(4), LogSoftMax()]))
        model.build(0, jnp.zeros((1, 8), jnp.float32))
        optim = SGD(0.1, momentum=0.9)
        host0 = jax.device_get(create_train_state(model, optim))
        batch = _matrix_batch()

        # the width-4 run's checkpoint: place, gather, atomic save
        placed4 = specs4.place_state(host0)
        base = str(tmp_path / f"ckpt_{name}")
        ckpt_lib.save(base, specs4.gather(placed4),
                      meta={"world_width": self.SAVE_W})

        for w in self.RESTORE_WS:
            specs_w = pipeline_specs(
                name, mesh=create_mesh(devices=jax.devices()[:w]))
            restored = ckpt_lib.restore_elastic(base, target=host0,
                                                specs=specs_w)
            # placement preserved the saved bytes exactly
            assert _leaves_equal(jax.device_get(restored), host0)

            step = make_train_step(model.module, ClassNLLCriterion(),
                                   optim, specs=specs_w, state=restored)
            st_el, m_el = step(restored, batch, 1.0)

            # never-resized control at the SAME width w′
            control = specs_w.place_state(host0)
            st_ref, m_ref = step(control, batch, 1.0)

            assert repr(float(m_el["loss"])) == repr(float(m_ref["loss"]))
            assert _leaves_equal(jax.device_get(st_el.params),
                                 jax.device_get(st_ref.params))


# ---------------------------------------------------------------------------
# Serving: slices, the device budget, and width-vs-count
# ---------------------------------------------------------------------------


def _fwd(batch):
    x = batch["input"]
    return x.reshape(x.shape[0], -1).sum(axis=1)


def _slice_factory(clock, width):
    def make(rid):
        return ReplicaSlice(rid, [_fwd], clock, wedge_timeout_s=5.0,
                            width=width)
    return make


class TestReplicaSlices:
    def test_slice_width_and_validation(self):
        clock = VirtualClock()
        r = ReplicaSlice(0, [_fwd], clock, wedge_timeout_s=5.0, width=2)
        assert r.width == 2
        assert Replica(1, [_fwd], clock, wedge_timeout_s=5.0).width == 1
        with pytest.raises(ValueError, match="width"):
            ReplicaSlice(2, [_fwd], clock, wedge_timeout_s=5.0, width=0)

    def test_slice_jitted_against_submesh_specs(self):
        """A width-2 slice carries the tier's SpecSet rebased onto its
        own 2-device sub-mesh — the programs it dispatches are jitted
        against exactly the devices the slice occupies."""
        sub = create_mesh(devices=jax.devices()[:2])
        specs = pipeline_specs("fraud", mesh=sub)
        r = ReplicaSlice(0, [_fwd], VirtualClock(), wedge_timeout_s=5.0,
                        width=2, specs=specs)
        assert r.specs.data_axis_size == 2
        assert r.specs.mesh.devices.size == r.width

    def test_pool_device_budget_clamps_growth(self):
        """The 2-device regression (satellite 1): width-2 slices under
        device_budget=4 — the pool actuator refuses the third slice
        even though max_replicas-style counting would allow it."""
        clock = VirtualClock()
        factory = _slice_factory(clock, width=2)
        pool = ReplicaPool([factory(0)], clock,
                           replica_factory=factory, device_budget=4)
        assert pool.devices_used == 2
        pool.resize(3, prewarm=False)
        assert pool.size == 2                       # clamped at 4 devices
        assert pool.devices_used == 4
        clamped = [e for e in pool.events
                   if e["kind"] == "resize_budget_clamped"]
        assert clamped and clamped[0]["device_budget"] == 4
        assert clamped[0]["width"] == 2

    def test_draining_slices_release_their_devices(self):
        clock = VirtualClock()
        factory = _slice_factory(clock, width=2)
        pool = ReplicaPool([factory(0), factory(1)], clock,
                           replica_factory=factory, device_budget=4)
        assert pool.devices_used == 4
        pool.resize(1)                              # drain-then-retire
        assert pool.devices_used == 2
        pool.resize(2, prewarm=False)               # budget free again
        assert pool.devices_used == 4


class TestSliceUnitPolicy:
    def test_bounds_validated_in_slice_units(self):
        """Satellite 1: max_replicas is SLICE units — a policy whose
        ceiling times slice width over-subscribes the device budget is
        rejected at construction, not discovered mid-drill."""
        with pytest.raises(ValueError, match="SLICE units"):
            AutoscalePolicy(min_replicas=1, max_replicas=4,
                            slice_width=2, device_budget=6)
        with pytest.raises(ValueError, match="floor"):
            AutoscalePolicy(min_replicas=3, max_replicas=3,
                            slice_width=2, device_budget=4)
        p = AutoscalePolicy(min_replicas=1, max_replicas=3,
                            slice_width=2, device_budget=6)
        assert p.max_devices == 6

    def test_reshape_width_must_fit(self):
        with pytest.raises(ValueError, match="reshape_width"):
            AutoscalePolicy(max_replicas=1, slice_width=2,
                            reshape_width=2)
        with pytest.raises(ValueError, match="reshape_width"):
            AutoscalePolicy(max_replicas=1, slice_width=1,
                            device_budget=2, reshape_width=4)


class TestWidthVsCount:
    def _scaler(self, **kw):
        base = dict(min_replicas=1, max_replicas=4, grow_after=1,
                    cooldown=0, device_budget=8, reshape_width=4,
                    reshape_fill=0.9)
        base.update(kw)
        return Autoscaler(AutoscalePolicy(**base))

    def test_saturated_grow_becomes_reshape(self):
        sc = self._scaler()
        out = sc.observe_hint(1, 2, saturation={"fraud": 0.97,
                                                "ssd": 0.2},
                              widths={"fraud": 1, "ssd": 1})
        assert isinstance(out, Reshape)
        assert out.model == "fraud" and out.to_width == 4
        assert f"B/{OCCUPANCY_KNEE}" in out.rationale
        assert "MFU_CEILING" in out.rationale
        assert sc.reshapes == 1
        ev = [e for e in sc.events if e["kind"] == "scale_reshape"]
        assert ev and ev[0]["model"] == "fraud"

    def test_below_fill_bar_falls_back_to_count_grow(self):
        sc = self._scaler()
        out = sc.observe_hint(1, 2, saturation={"fraud": 0.5},
                              widths={"fraud": 1})
        assert out == 3                             # plain count grow
        assert sc.reshapes == 0

    def test_already_wide_model_count_grows(self):
        sc = self._scaler()
        out = sc.observe_hint(1, 2, saturation={"fraud": 1.0},
                              widths={"fraud": 4})
        assert out == 3
        assert sc.reshapes == 0

    def test_unarmed_policy_never_reshapes(self):
        sc = Autoscaler(AutoscalePolicy(min_replicas=1, max_replicas=4,
                                        grow_after=1, cooldown=0))
        out = sc.observe_hint(1, 2, saturation={"fraud": 1.0},
                              widths={"fraud": 1})
        assert out == 3

    def test_width_speedup_occupancy_model(self):
        """The ≈B/128 knee: widening pays ONLY above it — full batches
        split across width stay on the roofline; small batches starve."""
        sp = ServingRuntime._width_speedup
        assert sp(8, 4) == 1.0                      # far below the knee
        assert sp(OCCUPANCY_KNEE, 4) == 1.0         # exactly at it
        assert sp(2 * OCCUPANCY_KNEE, 4) == 2.0
        assert sp(4 * OCCUPANCY_KNEE, 4) == 4.0     # saturated: full w

    def test_runtime_reshape_actuation_drops_warm_keys(self):
        """An armed runtime actuating a Reshape: the model's width map
        updates, its warm geometries drop (the wider slice's programs
        are different programs), and the event lands in the pool log."""
        from analytics_zoo_tpu.serving import ModelConfig, ServingTier

        clock = VirtualClock()
        cfg = ModelConfig(name="fraud",
                          tiers=[ServingTier("fp", _fwd, speed=1.0)],
                          default_deadline_s=1.0)
        rt = ServingRuntime(models=[cfg], n_replicas=1, clock=clock,
                            max_batch=256, compile_s=1.0,
                            service_time=lambda m, e, n, t: 0.01)
        rt._do_reshape(Reshape(model="fraud", from_width=1, to_width=4,
                               fill=1.0, rationale="test"))
        assert rt._model_width["fraud"] == 4
        assert rt._reshape_log and rt._reshape_log[0]["to_width"] == 4
        assert not any(k[0] == "fraud"
                       for r in rt.pool.replicas
                       for k in (r.warm_keys or ()))
        snap = rt.snapshot()
        assert snap["slices"]["model_width"]["fraud"] == 4
        # the reshaped model's service now divides by the width speedup
        assert rt._width_speedup(256, 4) == 2.0
