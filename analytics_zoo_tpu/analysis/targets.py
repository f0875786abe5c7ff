"""The repo's program-audit suite: what ``az_analyze --program`` traces.

Coverage contract (the ISSUE-10 acceptance line): all four registered
pipelines' train + eval programs, plus every SSD and DS2 serving tier
the degradation-ladder factories hand the runtime.

Construction is ABSTRACT wherever values don't matter: parameters come
from ``jax.eval_shape`` over ``module.init`` (a shape/dtype tree, no
weight init compile, no FLOPs), batches are ``ShapeDtypeStruct`` s, and
only the SSD serving tiers get cheap filled arrays because
``quantize_params`` must read real values to compute int8 scales.  The
whole suite traces in a few seconds on the 2-core CPU host — which is
what lets the audit run inside tier-1 on every suite pass.

The serving-tier programs are NOT reconstructed here: the tier
factories (``pipelines.ssd.ssd_serving_tiers`` / ``pipelines.
deepspeech2.ds2_serving_tiers``) attach a ``device_program`` thunk to
each :class:`~analytics_zoo_tpu.serving.ladder.ServingTier`, and this
module audits exactly those — the audit covers the programs the
runtime will actually dispatch, not a parallel copy that could drift.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.analysis.program import AuditProgram, BuiltProgram


def _S(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_variables(module, *example_inputs, **init_kwargs):
    """``module.init``'s variable tree as shapes/dtypes only — traced
    under ``eval_shape``, so no RNG work and no init compile."""
    return jax.eval_shape(
        lambda rng, *args: module.init(rng, *args, **init_kwargs),
        jax.random.PRNGKey(0), *example_inputs)


def abstract_train_state(module, optim, *example_inputs, **init_kwargs
                         ) -> Tuple[Any, Any]:
    """(variables, TrainState) as abstract trees — structure-true to
    ``create_train_state`` (same leaves, same optimizer slots), value-
    free."""
    from analytics_zoo_tpu.parallel.train import TrainState

    variables = abstract_variables(module, *example_inputs, **init_kwargs)
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}
    state = TrainState(
        step=_S((), np.int32),
        params=params,
        model_state=model_state,
        opt_state=jax.eval_shape(optim.tx.init, params),
        rng=jax.eval_shape(jax.random.PRNGKey, 0),
    )
    return variables, state


def filled(tree) -> Any:
    """Abstract tree → cheap concrete arrays (0.5 for floats, zeros for
    ints) — for the paths that must read values (int8 quantization
    scales)."""
    return jax.tree_util.tree_map(
        lambda s: np.full(s.shape, 0.5, s.dtype)
        if np.issubdtype(s.dtype, np.floating)
        else np.zeros(s.shape, s.dtype), tree)


# ---------------------------------------------------------------------------
# Per-pipeline target builders (lazy — nothing imports models until the
# program engine actually runs)
# ---------------------------------------------------------------------------


def _fraud(mesh) -> List[AuditProgram]:
    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
        from analytics_zoo_tpu.models import FraudMLP
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)

        module = FraudMLP(in_features=29, hidden=10, n_classes=2)
        specs = pipeline_specs("fraud", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(module, optim,
                                        _S((1, 29), np.float32))
        step = make_train_step(module, ClassNLLCriterion(), optim,
                               specs=specs, state=state)
        B = specs.data_axis_size
        batch = {"input": _S((B, 29), np.float32),
                 "target": _S((B,), np.int32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.models import FraudMLP
        from analytics_zoo_tpu.parallel import (Adam, make_eval_step,
                                                pipeline_specs)

        module = FraudMLP(in_features=29, hidden=10, n_classes=2)
        specs = pipeline_specs("fraud", mesh=mesh)
        variables = abstract_variables(module, _S((1, 29), np.float32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev, args=(variables, _S((B, 29),
                                                       np.float32)),
                            specs=specs)

    return [AuditProgram("fraud/train", build_train),
            AuditProgram("fraud/eval", build_eval)]


def _rec(mesh) -> List[AuditProgram]:
    # web-scale recommendation (ISSUE 17): the dedup'd-gather train and
    # eval programs for BOTH family architectures — the sparse lookup +
    # segment-sum backward is the hot path the audit must trace
    U, I, CLS = 64, 48, 5

    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
        from analytics_zoo_tpu.models import NeuralCF
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)

        module = NeuralCF(n_users=U, n_items=I, embedding_dim=8,
                          mf_embedding_dim=4, hidden=(16, 8), n_classes=CLS)
        specs = pipeline_specs("rec", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(module, optim,
                                        _S((1,), np.int32),
                                        _S((1,), np.int32))
        step = make_train_step(module, ClassNLLCriterion(), optim,
                               specs=specs, state=state)
        B = specs.data_axis_size
        batch = {"input": (_S((B,), np.int32), _S((B,), np.int32)),
                 "target": _S((B,), np.int32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_wd_train() -> BuiltProgram:
        from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
        from analytics_zoo_tpu.models import WideAndDeep
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)

        module = WideAndDeep(n_users=U, n_items=I, embedding_dim=8,
                             hidden=(16, 8), n_classes=CLS,
                             cross_buckets=32)
        specs = pipeline_specs("rec", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(module, optim,
                                        _S((1,), np.int32),
                                        _S((1,), np.int32))
        step = make_train_step(module, ClassNLLCriterion(), optim,
                               specs=specs, state=state)
        B = specs.data_axis_size
        batch = {"input": (_S((B,), np.int32), _S((B,), np.int32)),
                 "target": _S((B,), np.int32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.models import NeuralCF
        from analytics_zoo_tpu.parallel import (make_eval_step,
                                                pipeline_specs)

        module = NeuralCF(n_users=U, n_items=I, embedding_dim=8,
                          mf_embedding_dim=4, hidden=(16, 8), n_classes=CLS)
        specs = pipeline_specs("rec", mesh=mesh)
        variables = abstract_variables(module, _S((1,), np.int32),
                                       _S((1,), np.int32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev,
                            args=(variables, (_S((B,), np.int32),
                                              _S((B,), np.int32))),
                            specs=specs)

    return [AuditProgram("rec/train", build_train),
            AuditProgram("rec-wd/train", build_wd_train),
            AuditProgram("rec/eval", build_eval)]


def _sentiment(mesh) -> List[AuditProgram]:
    V, D, T = 256, 16, 24

    def _module():
        from analytics_zoo_tpu.models import SentimentNet

        return SentimentNet(vocab_size=V, embedding_dim=D, hidden=8,
                            head="gru")

    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.core.criterion import BCECriterion
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)

        module = _module()
        specs = pipeline_specs("sentiment", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(module, optim,
                                        _S((1, T), np.int32))
        step = make_train_step(module, BCECriterion(), optim,
                               specs=specs, state=state)
        B = specs.data_axis_size
        batch = {"input": _S((B, T), np.int32),
                 "target": _S((B,), np.float32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.parallel import (make_eval_step,
                                                pipeline_specs)

        module = _module()
        specs = pipeline_specs("sentiment", mesh=mesh)
        variables = abstract_variables(module, _S((1, T), np.int32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev, args=(variables, _S((B, T), np.int32)),
                            specs=specs)

    return [AuditProgram("sentiment/train", build_train),
            AuditProgram("sentiment/eval", build_eval)]


def _ds2(mesh) -> List[AuditProgram]:
    T, MELS, LAB = 32, 13, 4

    def _module():
        from analytics_zoo_tpu.models import DeepSpeech2

        return DeepSpeech2(hidden=16, n_rnn_layers=1, n_mels=MELS)

    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            ds2_ctc_criterion, ds2_padding_metric)

        module = _module()
        specs = pipeline_specs("ds2", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(
            module, optim, _S((1, T, MELS), np.float32))
        step = make_train_step(module, ds2_ctc_criterion(), optim,
                               specs=specs, state=state,
                               metric_fn=ds2_padding_metric)
        B = specs.data_axis_size
        # the production bucketed-batch contract: input=(features,
        # n_frames), n_frames top-level for the CTC logit mask + metric
        batch = {"input": (_S((B, T, MELS), np.float32),
                           _S((B,), np.int32)),
                 "n_frames": _S((B,), np.int32),
                 "labels": _S((B, LAB), np.int32),
                 "label_mask": _S((B, LAB), np.float32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.parallel import (make_eval_step,
                                                pipeline_specs)

        module = _module()
        specs = pipeline_specs("ds2", mesh=mesh)
        variables = abstract_variables(module, _S((1, T, MELS),
                                                  np.float32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev,
                            args=(variables, _S((B, T, MELS), np.float32)),
                            specs=specs)

    def build_pallas_train() -> BuiltProgram:
        # the persistent-RNN engine's TRAIN program (ISSUE 13): the
        # custom_vjp backward is the transposed persistent Pallas
        # kernel since r10, so the jaxpr audit must trace the
        # pallas-engine training pipeline — not just the default
        # blocked-scan one — or the kernel path (fwd AND bwd pallas
        # calls) sits outside the audit surface.  Traces interpret-mode off-TPU,
        # same as the program the CPU tier dispatches.
        from analytics_zoo_tpu.models import DeepSpeech2
        from analytics_zoo_tpu.parallel import (Adam, make_train_step,
                                                pipeline_specs)
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            ds2_ctc_criterion, ds2_padding_metric)

        module = DeepSpeech2(hidden=16, n_rnn_layers=1, n_mels=MELS,
                             rnn_engine="pallas")
        specs = pipeline_specs("ds2", mesh=mesh)
        optim = Adam(1e-3)
        _, state = abstract_train_state(
            module, optim, _S((1, T, MELS), np.float32))
        step = make_train_step(module, ds2_ctc_criterion(), optim,
                               specs=specs, state=state,
                               metric_fn=ds2_padding_metric)
        B = specs.data_axis_size
        batch = {"input": (_S((B, T, MELS), np.float32),
                           _S((B,), np.int32)),
                 "n_frames": _S((B,), np.int32),
                 "labels": _S((B, LAB), np.int32),
                 "label_mask": _S((B, LAB), np.float32)}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    return [AuditProgram("ds2/train", build_train),
            AuditProgram("ds2/eval", build_eval),
            AuditProgram("ds2-pallas/train", build_pallas_train)]


def _ssd(mesh) -> List[AuditProgram]:
    RES, NCLS, G = 300, 4, 8

    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.models import (SSDVgg, build_priors,
                                              ssd300_config)
        from analytics_zoo_tpu.ops.multibox_loss import (MultiBoxLoss,
                                                         MultiBoxLossParam)
        from analytics_zoo_tpu.parallel import (SGD, make_train_step,
                                                pipeline_specs)

        module = SSDVgg(num_classes=NCLS, resolution=RES)
        specs = pipeline_specs("ssd", mesh=mesh)
        optim = SGD(1e-3, momentum=0.9)
        _, state = abstract_train_state(
            module, optim, _S((1, RES, RES, 3), np.float32))
        priors, variances = build_priors(ssd300_config())
        crit = MultiBoxLoss(priors, variances,
                            MultiBoxLossParam(n_classes=NCLS))
        step = make_train_step(module, crit, optim, specs=specs,
                               state=state, skip_loss_above=50.0)
        B = specs.data_axis_size
        batch = {"input": _S((B, RES, RES, 3), np.float32),
                 "target": {"bboxes": _S((B, G, 4), np.float32),
                            "labels": _S((B, G), np.float32),
                            "mask": _S((B, G), np.float32)}}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.models import SSDVgg
        from analytics_zoo_tpu.parallel import (make_eval_step,
                                                pipeline_specs)

        module = SSDVgg(num_classes=NCLS, resolution=RES)
        specs = pipeline_specs("ssd", mesh=mesh)
        variables = abstract_variables(module,
                                       _S((1, RES, RES, 3), np.float32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev,
                            args=(variables,
                                  _S((B, RES, RES, 3), np.float32)),
                            specs=specs)

    return [AuditProgram("ssd/train", build_train),
            AuditProgram("ssd/eval", build_eval)]


def _frcnn(mesh) -> List[AuditProgram]:
    RES, NCLS, G = 128, 4, 8

    def _module():
        from analytics_zoo_tpu.models import FasterRcnnVgg, FrcnnParam
        from analytics_zoo_tpu.ops.proposal import ProposalParam

        return FasterRcnnVgg(param=FrcnnParam(
            num_classes=NCLS,
            proposal=ProposalParam(pre_nms_topn=64, post_nms_topn=16)))

    def build_train() -> BuiltProgram:
        from analytics_zoo_tpu.ops.frcnn_train import (
            FrcnnLossParam, frcnn_training_loss)
        from analytics_zoo_tpu.parallel import (SGD, make_train_step,
                                                pipeline_specs)

        module = _module()
        specs = pipeline_specs("frcnn", mesh=mesh)
        optim = SGD(1e-3, momentum=0.9)
        _, state = abstract_train_state(
            module, optim, _S((1, RES, RES, 3), np.float32),
            _S((1, 3), np.float32))

        def forward_fn(variables, inputs, train=False, rngs=None):
            x, im_info, gt_px, gt_mask = inputs
            out = module.apply(variables, x, im_info, train=train,
                               extra_rois=gt_px, extra_rois_mask=gt_mask,
                               train_outputs=True, rngs=rngs)
            return out, None

        loss_param = FrcnnLossParam()
        step = make_train_step(
            module, lambda out, b: frcnn_training_loss(out, b, loss_param),
            optim, specs=specs, state=state, forward_fn=forward_fn,
            grad_clip_norm=10.0)
        B = specs.data_axis_size
        batch = {"input": (_S((B, RES, RES, 3), np.float32),
                           _S((B, 3), np.float32),
                           _S((B, G, 4), np.float32),
                           _S((B, G), np.float32)),
                 "im_info": _S((B, 3), np.float32),
                 "target": {"bboxes": _S((B, G, 4), np.float32),
                            "labels": _S((B, G), np.int32),
                            "mask": _S((B, G), np.float32)}}
        return BuiltProgram(fn=step, args=(state, batch, 1.0),
                            specs=specs, donate_state=state)

    def build_eval() -> BuiltProgram:
        from analytics_zoo_tpu.parallel import (make_eval_step,
                                                pipeline_specs)

        module = _module()
        specs = pipeline_specs("frcnn", mesh=mesh)
        variables = abstract_variables(module,
                                       _S((1, RES, RES, 3), np.float32),
                                       _S((1, 3), np.float32))
        ev = make_eval_step(module, specs=specs)
        B = specs.data_axis_size
        return BuiltProgram(fn=ev,
                            args=(variables,
                                  (_S((B, RES, RES, 3), np.float32),
                                   _S((B, 3), np.float32))),
                            specs=specs)

    return [AuditProgram("frcnn/train", build_train),
            AuditProgram("frcnn/eval", build_eval)]


def _tier_targets(kind: str, tiers, specs) -> List[AuditProgram]:
    """Wrap each ServingTier's attached ``device_program`` thunk as an
    audit target (a tier without one is itself a finding — the factory
    stopped exposing its program to the audit)."""
    out: List[AuditProgram] = []
    for tier in tiers:
        name = f"{kind}/serve:{tier.name}"
        if tier.device_program is None:
            def build_missing(tier_name=tier.name) -> BuiltProgram:
                raise RuntimeError(
                    f"serving tier {tier_name!r} carries no "
                    f"device_program thunk — the tier factory must "
                    f"expose its jitted program for the audit")
            out.append(AuditProgram(name, build_missing))
            continue

        def build(thunk=tier.device_program, specs=specs) -> BuiltProgram:
            fn, args, static = thunk()
            return BuiltProgram(fn=fn, args=args, static_argnums=static,
                                specs=specs)
        out.append(AuditProgram(name, build))
    return out


def _ssd_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SSDVgg
    from analytics_zoo_tpu.ops import DetectionOutputParam
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 ssd_serving_tiers)

    RES, NCLS = 300, 4
    module = SSDVgg(num_classes=NCLS, resolution=RES)
    # int8 quantization reads weight values for its scales → filled
    # arrays (cheap constants), not eval_shape structs
    model = Model(module)
    model.variables = filled(abstract_variables(
        module, _S((1, RES, RES, 3), np.float32)))
    specs = pipeline_specs("ssd", mesh=mesh)
    param = PreProcessParam(batch_size=specs.data_axis_size,
                            resolution=RES)
    tiers = ssd_serving_tiers(model, param, n_classes=NCLS, specs=specs)
    # the FUSED post-processing programs ("auto" resolves to them on a
    # TPU backend, but this audit traces on CPU where auto is xla):
    # audit the single-kernel DetectionOutput path explicitly so the
    # exact programs the TPU serving tiers dispatch are covered like
    # every other rung
    fused = ssd_serving_tiers(
        model, param, n_classes=NCLS, specs=specs,
        post=DetectionOutputParam(n_classes=NCLS, backend="fused"))
    return (_tier_targets("ssd", tiers, specs)
            + _tier_targets("ssd-fused", fused, specs))


def _ds2_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import DeepSpeech2
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.deepspeech2 import (DS2Param,
                                                         ds2_serving_tiers)

    module = DeepSpeech2(hidden=16, n_rnn_layers=1, n_mels=13)
    model = Model(module)
    model.variables = abstract_variables(module,
                                         _S((1, 64, 13), np.float32))
    specs = pipeline_specs("ds2", mesh=mesh)
    tiers = ds2_serving_tiers(model, DS2Param(decoder="beam"), specs=specs)
    return _tier_targets("ds2", tiers, specs)


def _ds2_streaming_serving(mesh) -> List[AuditProgram]:
    # the ISSUE-14 first-class streaming session model: audit the
    # steady-block carry-in/carry-out program every chunk dispatches
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import DeepSpeech2
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.deepspeech2 import ds2_streaming_tiers

    module = DeepSpeech2(hidden=16, n_rnn_layers=1, n_mels=13,
                         bidirectional=False)
    model = Model(module)
    model.variables = abstract_variables(module,
                                         _S((1, 64, 13), np.float32))
    specs = pipeline_specs("ds2", mesh=mesh)
    tiers = ds2_streaming_tiers(model, n_mels=13, chunk_frames=50)
    return _tier_targets("ds2-stream", tiers, specs)


def _frcnn_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.models import FasterRcnnDetector, FrcnnParam
    from analytics_zoo_tpu.ops.proposal import ProposalParam
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.frcnn import frcnn_serving_tiers
    from analytics_zoo_tpu.pipelines.ssd import PreProcessParam

    RES, NCLS = 128, 4
    detector = FasterRcnnDetector(param=FrcnnParam(
        num_classes=NCLS,
        proposal=ProposalParam(pre_nms_topn=64, post_nms_topn=16)))
    # int8 quantization reads weight values for its scales → filled
    variables = filled(abstract_variables(
        detector, _S((1, RES, RES, 3), np.float32),
        _S((1, 3), np.float32)))
    specs = pipeline_specs("frcnn", mesh=mesh)
    tiers = frcnn_serving_tiers(
        detector, variables,
        param=PreProcessParam(batch_size=specs.data_axis_size,
                              resolution=RES),
        specs=specs)
    return _tier_targets("frcnn", tiers, specs)


def _fraud_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import FraudMLP
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.fraud import fraud_serving_tiers

    module = FraudMLP(in_features=29, hidden=10, n_classes=2)
    model = Model(module)
    model.variables = filled(abstract_variables(
        module, _S((1, 29), np.float32)))
    specs = pipeline_specs("fraud", mesh=mesh)
    tiers = fraud_serving_tiers(model, specs=specs)
    return _tier_targets("fraud", tiers, specs)


def _fraud_slice_serving(mesh) -> List[AuditProgram]:
    """ISSUE 19: the width-2 :class:`ReplicaSlice` geometry — the SAME
    fraud tier ladder re-jitted against a 2-device sub-mesh via
    ``SpecSet.replace_mesh``, exactly how the runtime builds a slice's
    programs.  Auditing it pins that the slice path produces genuine
    annotated programs (donation/sharding/collectives discipline), not
    a degenerate single-device trace wearing a wide name."""
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import FraudMLP
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipelines.fraud import fraud_serving_tiers

    devs = list(mesh.devices.reshape(-1)[:2])
    sub = mesh_lib.create_mesh((len(devs),),
                               (mesh_lib.data_axis(mesh),), devices=devs)
    module = FraudMLP(in_features=29, hidden=10, n_classes=2)
    model = Model(module)
    model.variables = filled(abstract_variables(
        module, _S((1, 29), np.float32)))
    specs = pipeline_specs("fraud", mesh=mesh).replace_mesh(sub)
    tiers = fraud_serving_tiers(model, specs=specs)
    return _tier_targets("fraud-slice-w2", tiers, specs)


def _rec_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.recommendation import (
        make_ncf_model, rec_serving_tiers)

    # sized like the train targets; tiny enough that a real init is
    # cheaper than the abstract+filled dance (int8 scales read values)
    model = make_ncf_model(n_users=64, n_items=48, embedding_dim=8,
                           mf_embedding_dim=4, hidden=(16, 8))
    specs = pipeline_specs("rec", mesh=mesh)
    tiers = rec_serving_tiers(model, specs=specs)
    return _tier_targets("rec", tiers, specs)


def _sentiment_serving(mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import SentimentNet
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.sentiment import sentiment_serving_tiers

    T = 24
    module = SentimentNet(vocab_size=256, embedding_dim=16, hidden=8,
                          head="gru")
    model = Model(module)
    model.variables = filled(abstract_variables(
        module, _S((1, T), np.int32)))
    specs = pipeline_specs("sentiment", mesh=mesh)
    tiers = sentiment_serving_tiers(model, specs=specs, seq_len=T)
    return _tier_targets("sentiment", tiers, specs)


def _lm_serving(mesh) -> List[AuditProgram]:
    """ISSUE 28: the decoder LM's session tier — its decode step (one
    jitted call a batch of rows of any sessions) under the rung's own
    name, and the prefill program of each chunk edge beside it."""
    from analytics_zoo_tpu.models import lm

    cfg = lm.LMConfig(
        d=32, kinds=(lm.FULL, lm.SLIDING), dense_layers=1,
        full=lm.MLADims(4, 16, 8, 8, 4, 8, 8e7),
        swa=lm.MLADims(2, 16, 12, 12, 4, 8, 5e4), window=5, idx_heads=4,
        idx_dim=8, topk=4, f_dense=48, f_expert=16, f_shared=16, experts=8,
        held=4, first_held=0, per_tok=2, route_scale=1.0, vocab=40,
        eps=1e-5, dtype="float32", gate=True, rescale=True, route_bias=True)
    return _lm_tier_programs("lm", cfg, mesh)


def _lm_mla_serving(mesh) -> List[AuditProgram]:
    """ISSUE 33: the same tier over a model of causal latent attention
    throughout (no ``layer_types`` in its config: pools only, YaRN,
    group-limited routing, no gate, no router bias) — its decode step with
    the paged attention (ops/lm_attention.py ``mla_paged``) and its prefill
    programs."""
    from analytics_zoo_tpu.models import lm
    from analytics_zoo_tpu.ops.lm_attention import RopeScaling

    cfg = lm.LMConfig(
        d=32, kinds=(lm.CAUSAL, lm.CAUSAL), dense_layers=1,
        full=lm.MLADims(4, 16, 8, 8, 8, 8, 100.0,
                        RopeScaling(4.0, 16, 1.0, 0.1, 1.0, 1.0)),
        swa=None, window=0, idx_heads=0, idx_dim=0, topk=0, f_dense=48,
        f_expert=16, f_shared=16, experts=8, held=2, first_held=0, per_tok=2,
        route_scale=2.5, vocab=40, eps=1e-6, dtype="float32", n_group=2,
        topk_group=1)
    return _lm_tier_programs("lm-mla", cfg, mesh)


def _lm_gqa_serving(mesh) -> List[AuditProgram]:
    """ISSUE 35: the same tier over a model of grouped-query attention
    (``hybrid_layer_pattern`` in its config: global layers in pools, read
    by the paged attention ``gqa_paged``, window layers with sinks in
    rings, entries of two widths; partial rotary, scaled values, a router
    bias, no shared expert) — its decode step and its prefill programs."""
    from analytics_zoo_tpu.models import lm

    cfg = lm.LMConfig(
        d=32, kinds=(lm.CAUSAL, lm.SLIDING, lm.SLIDING), dense_layers=1,
        full=lm.GQADims(4, 2, 12, 8, 4, 1e3, 0.707, False),
        swa=lm.GQADims(4, 4, 12, 8, 4, 10.0, 0.707, True), window=5,
        idx_heads=0, idx_dim=0, topk=0, f_dense=48, f_expert=16, f_shared=0,
        experts=8, held=2, first_held=0, per_tok=2, route_scale=1.0,
        vocab=40, eps=1e-5, dtype="float32", route_bias=True)
    return _lm_tier_programs("lm-gqa", cfg, mesh)


def _lm_ssm_serving(mesh) -> List[AuditProgram]:
    """ISSUE 39: the same tier over a model whose every block is a
    state-space mixer AND grouped-query attention off one norm (5 query
    heads a KV head, rotary on every dim, muP multipliers, no experts):
    its decode step with the recurrent and the convolution states among
    the cache's leaves (a slot a session) and its prefill programs with
    the chunked scan."""
    from analytics_zoo_tpu.models import lm

    cfg = lm.LMConfig(
        d=32, kinds=(lm.CAUSAL, lm.CAUSAL), dense_layers=2,
        full=lm.GQADims(10, 2, 8, 8, 8, 1e3), swa=None, window=0,
        idx_heads=0, idx_dim=0, topk=0, f_dense=48, f_expert=0, f_shared=0,
        experts=0, held=0, first_held=0, per_tok=0, route_scale=1.0,
        vocab=40, eps=1e-5, dtype="float32",
        ssm=lm.SSMDims(4, 8, 16, 2, 4, 8),
        mup=lm.Multipliers(embed=1.7, key=0.3, attn_out=0.6, ssm_in=0.8,
                           ssm=(0.7, 0.5, 0.6, 1.3, 0.9), ssm_out=0.4,
                           mlp_gate=0.75, mlp_down=0.35, head=0.25))
    return _lm_tier_programs("lm-ssm", cfg, mesh)


def _lm_tier_programs(kind: str, cfg, mesh) -> List[AuditProgram]:
    from analytics_zoo_tpu.models import lm
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.lm import LMModel, lm_serving_tiers

    model = LMModel(cfg, filled(lm.param_shapes(cfg)))
    specs = pipeline_specs("lm", mesh=mesh)
    tiers = lm_serving_tiers(model, cache_tokens=64, max_sessions=4,
                             max_batch=4, page=4, max_len=32)
    out = _tier_targets(kind, tiers, specs)
    for edge in (4, 8):
        def build(thunk=tiers[0].device_program_for(edge)) -> BuiltProgram:
            fn, args, static = thunk()
            return BuiltProgram(fn=fn, args=args, static_argnums=static,
                                specs=specs)
        out.append(AuditProgram(f"{kind}/serve:prefill{edge}", build))
    return out


def _fraud_swapped_serving(mesh) -> List[AuditProgram]:
    """ISSUE 18 (live weights): ``ServingRuntime.hot_swap`` rebuilds a
    family's tier stack from a RESTORED checkpoint pytree — plain
    nested dicts of host arrays (what ``checkpoint.load`` returns, not
    the boot-time FrozenDict) pushed through the declared SpecSet's
    ``place_state``.  The programs a swapped-in replica dispatches must
    stay under the audit exactly like the boot-time stack, so this
    target builds the fraud tiers through that restore → place →
    rebuild path."""
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models import FraudMLP
    from analytics_zoo_tpu.parallel import pipeline_specs
    from analytics_zoo_tpu.pipelines.fraud import fraud_serving_tiers

    def plain(tree):
        if hasattr(tree, "items"):
            return {k: plain(v) for k, v in tree.items()}
        return np.asarray(tree)

    module = FraudMLP(in_features=29, hidden=10, n_classes=2)
    model = Model(module)
    restored = plain(filled(abstract_variables(
        module, _S((1, 29), np.float32))))
    specs = pipeline_specs("fraud", mesh=mesh)
    model.variables = specs.place_state(restored)
    tiers = fraud_serving_tiers(model, specs=specs)
    return _tier_targets("fraud-swapped", tiers, specs)


def _guarded_tiers(kind: str, builder, mesh) -> List[AuditProgram]:
    """The serving-tier targets need the tier FACTORIES to run before
    the target names are even known (names come from the rungs).  A
    factory that explodes must surface as a finding on that family —
    not crash suite construction and take the healthy train/eval
    targets down with it (audit_program's per-target contract)."""
    try:
        return builder(mesh)
    except Exception as e:
        msg = f"{type(e).__name__}: {e}"

        def build_fail() -> BuiltProgram:
            raise RuntimeError(
                f"serving-tier factory failed before any program could "
                f"be traced: {msg}")
        return [AuditProgram(f"{kind}/serve:<factory-failed>", build_fail)]


def repo_audit_suite(mesh=None) -> List[AuditProgram]:
    """Every program the ISSUE-10 audit must cover, lazily built on
    ``mesh`` (default: 1-D data mesh over all local devices)."""
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    mesh = mesh or mesh_lib.create_mesh()
    targets: List[AuditProgram] = []
    targets += _ssd(mesh)
    targets += _frcnn(mesh)
    targets += _ds2(mesh)
    targets += _fraud(mesh)
    # the ISSUE-17 long tail: recommendation (NCF + Wide&Deep) and
    # sentiment ride the sharded-embedding substrate
    targets += _rec(mesh)
    targets += _sentiment(mesh)
    targets += _guarded_tiers("ssd", _ssd_serving, mesh)
    targets += _guarded_tiers("ds2", _ds2_serving, mesh)
    # the ISSUE-14 multiplexed fleet: every model family the shared
    # replica pool schedules exposes its serving programs to the audit
    targets += _guarded_tiers("ds2-stream", _ds2_streaming_serving, mesh)
    targets += _guarded_tiers("frcnn", _frcnn_serving, mesh)
    targets += _guarded_tiers("fraud", _fraud_serving, mesh)
    # ISSUE 18: the hot-swapped tier stack (checkpoint-restored
    # variables → place_state → tiers) audits like the boot-time one
    targets += _guarded_tiers("fraud-swapped", _fraud_swapped_serving,
                              mesh)
    # ISSUE 19: serving replicas that ARE mesh slices — the width-2
    # sub-mesh tier ladder audits alongside the full-width one
    targets += _guarded_tiers("fraud-slice-w2", _fraud_slice_serving,
                              mesh)
    targets += _guarded_tiers("rec", _rec_serving, mesh)
    targets += _guarded_tiers("sentiment", _sentiment_serving, mesh)
    # ISSUE 28: the decoder LM is served, not trained: its session
    # tier's decode and prefill programs are its whole audit surface
    targets += _guarded_tiers("lm", _lm_serving, mesh)
    targets += _guarded_tiers("lm-mla", _lm_mla_serving, mesh)
    targets += _guarded_tiers("lm-gqa", _lm_gqa_serving, mesh)
    targets += _guarded_tiers("lm-ssm", _lm_ssm_serving, mesh)
    return targets
