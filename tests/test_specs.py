"""The declare-once sharding substrate (``parallel.specs``).

Three contracts pinned here, each guarding a refactor failure mode:

1. **Structure match** — every REGISTERED pipeline's PartitionSpec tree
   structure-matches its real param/state tree (a model edit that adds a
   parameter without a spec, or a registry edit that drifts from the
   model, fails here — silent spec/param drift is the bug class this
   kills).
2. **Roundtrip identity** — ``place_state`` → ``gather`` on a 1-device
   mesh is byte-identical (placement must never rewrite values).
3. **One placement site** — no module outside the spec substrate
   constructs device placement itself (``jax.device_put`` /
   ``NamedSharding(``): the ISSUE-9 acceptance gate, enforced since
   ISSUE 10 by az-analyze's ``one-placement-site`` AST rule (package-
   wide, waivers visible and reasoned) so it cannot rot.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.parallel import (
    Adam,
    SGD,
    SpecSet,
    create_mesh,
    create_train_state,
    make_train_step,
    pipeline_specs,
    registered_pipelines,
)
from analytics_zoo_tpu.parallel import mesh as mesh_lib


def _small_model_for(name: str) -> Model:
    """The smallest real model of each registered pipeline — the spec
    trees must match the PIPELINE'S OWN param structure, not a stand-in."""
    if name == "ssd":
        from analytics_zoo_tpu.models import SSDVgg

        model = Model(SSDVgg(num_classes=4, resolution=300))
        model.build(0, jnp.zeros((1, 300, 300, 3), jnp.float32))
        return model
    if name == "frcnn":
        from analytics_zoo_tpu.models import FasterRcnnVgg, FrcnnParam
        from analytics_zoo_tpu.ops.proposal import ProposalParam

        model = Model(FasterRcnnVgg(param=FrcnnParam(
            num_classes=4,
            proposal=ProposalParam(pre_nms_topn=64, post_nms_topn=16))))
        model.build(0, jnp.zeros((1, 128, 128, 3), jnp.float32),
                    jnp.asarray([[128.0, 128.0, 1.0]], jnp.float32))
        return model
    if name == "ds2":
        from analytics_zoo_tpu.pipelines.deepspeech2 import make_ds2_model

        return make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=32)
    if name == "fraud":
        from analytics_zoo_tpu.models import FraudMLP

        model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
        model.build(0, jnp.zeros((1, 29), jnp.float32))
        return model
    if name == "rec":
        from analytics_zoo_tpu.models import NeuralCF

        model = Model(NeuralCF(n_users=16, n_items=12, n_classes=5,
                               embedding_dim=8, mf_embedding_dim=4,
                               hidden=(16, 8)))
        model.build(0, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
        return model
    if name == "sentiment":
        from analytics_zoo_tpu.models import SentimentNet

        model = Model(SentimentNet(vocab_size=64, embedding_dim=8,
                                   hidden=8, head="gru"))
        model.build(0, jnp.zeros((1, 12), jnp.int32))
        return model
    if name == "lm":
        # functional, not flax: the parameter tree is the whole model
        from analytics_zoo_tpu.models import lm

        cfg = lm.LMConfig(
            d=16, kinds=(lm.FULL, lm.SLIDING), dense_layers=1,
            full=lm.MLADims(2, 8, 8, 4, 4, 4, 8e7),
            swa=lm.MLADims(2, 8, 8, 4, 4, 4, 5e4), window=5, idx_heads=2,
            idx_dim=8, topk=4, f_dense=16, f_expert=8, f_shared=8,
            experts=4, held=4, first_held=0, per_tok=2, route_scale=1.0,
            vocab=16, eps=1e-5, dtype="float32")
        return Model(None, {"params": lm.init_params(cfg, 0)})
    raise AssertionError(
        f"pipeline {name!r} registered in parallel.specs but this test "
        f"has no model factory for it — add one so the structure-match "
        f"guard covers it")


#: per-pipeline extra spec-builder variants worth pinning beyond the
#: default (the rule-resolved trees are where drift actually bites)
_VARIANTS = {
    "ssd": [{}, {"tp": "megatron"}, {"tp": "spatial"}],
    "frcnn": [{}],
    "ds2": [{}],
    "fraud": [{}],
    "rec": [{}, {"shard_tables": False}],
    "sentiment": [{}, {"shard_tables": False}],
    "lm": [{}],
}


class TestRegistryStructureMatch:
    @pytest.mark.parametrize("name", registered_pipelines())
    def test_spec_tree_structure_matches_param_tree(self, name):
        model = _small_model_for(name)
        state = create_train_state(model, Adam(1e-3))
        for opts in _VARIANTS.get(name, [{}]):
            specs = pipeline_specs(name, mesh=create_mesh(), **opts)
            for tree in (model.variables["params"], state):
                spec_tree = specs.state_specs(tree)
                assert (jax.tree_util.tree_structure(spec_tree)
                        == jax.tree_util.tree_structure(tree)), (
                    f"{name} {opts}: spec tree does not structure-match")
                assert all(isinstance(s, P) for s in
                           jax.tree_util.tree_leaves(spec_tree))
            # jit annotations resolve without needing more than the
            # declaration (+ state only when rules are armed)
            sh = specs.state_shardings(state)
            assert sh is not None

    def test_every_variant_table_entry_is_registered(self):
        assert set(_VARIANTS) == set(registered_pipelines())

    def test_unknown_pipeline_raises_with_registry_listing(self):
        with pytest.raises(KeyError, match="fraud"):
            pipeline_specs("nope")

    def test_rules_require_state_for_shardings(self):
        specs = pipeline_specs("ssd", mesh=create_mesh(), tp="megatron")
        with pytest.raises(ValueError, match="state"):
            specs.state_shardings()


class TestRoundtrip:
    def test_place_gather_roundtrip_byte_identical_one_device(self):
        """shard → gather on a 1-device mesh returns the exact bytes —
        for the plain-replication AND the rule-resolved path."""
        mesh1 = create_mesh(devices=jax.devices()[:1])
        model = _small_model_for("fraud")
        state = create_train_state(model, SGD(0.1, momentum=0.9))
        host = jax.tree_util.tree_leaves(state)
        for opts in ({}, {"rules": []}):
            specs = SpecSet(mesh1, **opts)
            placed = specs.place_state(state)
            back = specs.gather(placed)
            for a, b in zip(host, jax.tree_util.tree_leaves(back)):
                a, b = np.asarray(a), np.asarray(b)
                assert a.dtype == b.dtype
                assert np.array_equal(a, b), "roundtrip changed bytes"

    def test_tp_rules_roundtrip_byte_identical(self):
        from analytics_zoo_tpu.parallel import default_tp_rules

        mesh = create_mesh((2, 4), axis_names=("data", "model"))
        model = _small_model_for("ds2")
        specs = SpecSet(mesh, rules=default_tp_rules())
        params = model.variables["params"]
        placed = specs.place_state(params)
        back = specs.gather(placed)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestAnnotatedStep:
    def test_jit_placed_host_batch_matches_explicit_place_batch(self):
        """The declare-once fast path (host batch → annotated jit) and
        the explicit ``place_batch`` path must produce the SAME update —
        placement mechanism is not allowed to change math."""
        from analytics_zoo_tpu.core.criterion import ClassNLLCriterion

        mesh = create_mesh()
        specs = pipeline_specs("fraud", mesh=mesh)
        assert specs.jit_places_batches()
        optim = SGD(0.1, momentum=0.9)
        crit = ClassNLLCriterion()
        rng = np.random.RandomState(0)
        batch = {"input": rng.randn(16, 29).astype(np.float32),
                 "target": rng.randint(0, 2, (16,)).astype(np.int32)}

        # two independent (seed-identical) models: the donated step
        # invalidates its input state's buffers, which on the virtual
        # CPU mesh can alias the source model's arrays
        model = _small_model_for("fraud")
        step = make_train_step(model.module, crit, optim, specs=specs)
        s1 = specs.place_state(create_train_state(model, optim))
        s1, m1 = step(s1, batch, 1.0)                 # jit places host batch
        model2 = _small_model_for("fraud")
        s2 = specs.place_state(create_train_state(model2, optim))
        s2, m2 = step(s2, specs.place_batch(batch), 1.0)

        assert float(m1["loss"]) == float(m2["loss"])
        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scalar_batch_leaf_trains_via_fallback_step(self):
        """A 0-d batch leaf (the old shard_batch contract replicated
        scalars) must still train end to end: the Optimizer routes such
        batches through the un-annotated-batch step variant + explicit
        place_batch instead of the jit fast path (a P('data') prefix is
        invalid for rank-0 and would crash the first step)."""
        from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
        from analytics_zoo_tpu.parallel import Optimizer, SGD, Trigger

        model = _small_model_for("fraud")
        rng = np.random.RandomState(0)
        crit = ClassNLLCriterion()
        batches = [{"input": rng.randn(16, 29).astype(np.float32),
                    "target": rng.randint(0, 2, (16,)).astype(np.int32),
                    "loss_weight": np.float32(1.0)}      # 0-d leaf
                   for _ in range(2)]
        opt = (Optimizer(model, batches,
                         lambda out, b: crit(out, b["target"])
                         * b["loss_weight"])
               .set_optim_method(SGD(0.1))
               .set_end_when(Trigger.max_epoch(1)))
        opt.optimize()
        assert int(np.asarray(opt._last_state.step)) == 2

    def test_batch_overrides_disable_jit_placement(self):
        from analytics_zoo_tpu.parallel import spatial_input_spec

        mesh = create_mesh((2, 4), axis_names=("data", "model"))
        specs = pipeline_specs("ssd", mesh=mesh, tp="spatial")
        assert specs.batch_shardings() is None
        assert not specs.jit_places_batches()
        # the spec layer still owns the placement for this mode
        x = np.zeros((4, 8, 8, 3), np.float32)
        placed = specs.place_batch({"input": x})
        assert placed["input"].sharding.spec == spatial_input_spec()

    def test_annotated_eval_matches_plain_including_ragged_tail(self):
        """make_eval_step(specs=): the mesh-annotated program and the
        plain one agree, and a ragged tail batch (dim 0 not divisible
        by the data axis) still evaluates (fallback program)."""
        from analytics_zoo_tpu.parallel import make_eval_step

        specs = pipeline_specs("fraud", mesh=create_mesh())
        model = _small_model_for("fraud")
        plain = make_eval_step(model.module)
        annotated = make_eval_step(model.module, specs=specs)
        rng = np.random.RandomState(1)
        for b in (16, 5):                    # divisible, ragged tail
            x = rng.randn(b, 29).astype(np.float32)
            np.testing.assert_allclose(
                np.asarray(annotated(model.variables, x)),
                np.asarray(plain(model.variables, x)), atol=1e-6)

    def test_batch_specs_tree_shapes(self):
        specs = pipeline_specs("ds2", mesh=create_mesh())
        batch = {"input": (np.zeros((8, 32, 13), np.float32),
                           np.zeros((8,), np.int32)),
                 "labels": np.zeros((8, 4), np.int32)}
        tree = specs.batch_specs(batch)
        x_spec, n_spec = tree["input"]
        assert x_spec == P("data", None, None)
        assert n_spec == P("data")
        assert tree["labels"] == P("data", None)


class TestOnePlacementSite:
    """ISSUE-9 acceptance gate, now enforced by az-analyze's
    ``one-placement-site`` AST rule (ISSUE 10) — package-wide instead of
    two directories, alias-aware, docstring-proof, and with visible
    reasoned waivers instead of silent exemptions."""

    def test_no_unwaived_placement_outside_spec_layer(self):
        from analytics_zoo_tpu.analysis.source import (OnePlacementSite,
                                                       run_source_engine)

        violations = run_source_engine(rules=[OnePlacementSite()])
        offenders = [v for v in violations if not v.waived]
        assert not offenders, (
            "device placement outside the spec layer (declare specs in "
            "parallel/specs.py and consume them, or waive with a "
            "reason):\n" + "\n".join(
                f"{v.file}:{v.line} {v.message}" for v in offenders))
        # every surviving exception is a visible, reasoned waiver
        for v in violations:
            if v.waived:
                assert v.waiver_reason

    def test_rule_fires_on_seeded_violation(self, tmp_path):
        """The rule must actually detect ad-hoc placement — pin it on a
        fixture so a rule refactor can't silently go blind."""
        from analytics_zoo_tpu.analysis.source import (OnePlacementSite,
                                                       run_source_engine)

        (tmp_path / "rogue.py").write_text(
            "import jax\n"
            "from jax.sharding import NamedSharding, PartitionSpec\n\n"
            "def place(x, mesh):\n"
            "    s = NamedSharding(mesh, PartitionSpec('data'))\n"
            "    return jax.device_put(x, s)\n")
        got = run_source_engine(root=str(tmp_path),
                                rules=[OnePlacementSite()])
        lines = {v.line for v in got}
        assert {5, 6} <= lines and not any(v.waived for v in got)
