"""The program's side of device time by named scope (PR 37): the catalog
of ``jax.named_scope`` names, the registry of hot-path programs
(``obs.device_scopes``), the charging rule, and what the new scopes leave
as it was."""

import ast
import contextlib
import os
import re
import sys
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import analytics_zoo_tpu
from analytics_zoo_tpu.obs import device_scopes
from analytics_zoo_tpu.obs.names import SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "benchmark"))
sys.path.insert(0, ROOT)

MODEL = re.compile(r"ssd/(base|extras|heads)")


# -- (a) the catalog -----------------------------------------------------------

def scopes_in_source():
    """{scope literal: [files]} of every ``jax.named_scope(...)`` call
    under the package (a conditional of two literals gives both)."""
    used = {}
    root = os.path.dirname(analytics_zoo_tpu.__file__)
    for folder, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(folder, f)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "named_scope"):
                    continue
                literals = [c.value for a in node.args for c in ast.walk(a)
                            if isinstance(c, ast.Constant)
                            and isinstance(c.value, str)]
                assert literals, f"{path}:{node.lineno}: a scope that is " \
                                 f"no literal cannot be declared"
                for name in literals:
                    used.setdefault(name, []).append(os.path.relpath(path,
                                                                     root))
    return used


def test_every_named_scope_is_declared_and_every_declared_one_is_used():
    used = scopes_in_source()
    assert set(used) - set(SCOPES) == set(), "undeclared scopes"
    assert set(SCOPES) - set(used) == set(), "declared, used nowhere"
    for name, doc in SCOPES.items():
        assert re.fullmatch(r"[a-z]+/[a-z_0-9]+", name), name
        assert "·" in doc, f"{name}: '<where> · <what it brackets>'"


def test_no_new_scope_is_a_prefix_of_another():
    """``hlo_scopes.scope_map`` takes the first ``lm/[a-z_]+`` of an
    op_name: one scope's name inside another's would be read as it."""
    for a in SCOPES:
        for b in SCOPES:
            assert a == b or not b.startswith(a + "_") or a in (
                "lm/mla", "lm/gqa"), (a, b)
    assert device_scopes.declared_scope(
        "jit(f)/transpose(jvp(ssd/loss_conf))/mul") == "ssd/loss_conf"
    assert device_scopes.declared_scope("jit(f)/lm/projection/x") is None
    assert device_scopes.declared_scope(
        "jit(step)/jvp(M)/ssd/base/vgg/conv1_2/conv") == "ssd/base"


def test_docs_scope_table_matches_the_catalog_exactly():
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md"),
              encoding="utf-8") as f:
        doc = f.read()
    section = doc.split("## Device scopes", 1)[1].split("\n## ", 1)[0]
    rows = [ln for ln in section.splitlines()
            if ln.lstrip().startswith("| `")]
    names = {re.match(r"\|\s*`([^`]+)`", ln).group(1) for ln in rows}
    assert names == set(SCOPES)


# -- (c) the charging rule, on a hand-written text ------------------------------

HLO = '''HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()->f32[]}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step_fn)/train/update/reduce_sum"}
}

%fused_computation.inner (p: bf16[8,32,32,64]) -> bf16[8,32,32,64] {
  %p = bf16[8,32,32,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  ROOT %max.1 = bf16[8,32,32,64]{3,0,2,1:T(8,128)(2,1)} maximum(%p, %p), metadata={op_name="jit(step_fn)/jvp(SSDVgg)/ssd/base/vgg/jit(relu)/max" stack_frame_id=3}
}

%fused_computation.35 (p0: f32[3,3,64,64], p1: f32[8,32,32,64], p2: bf16[8,32,32,64]) -> f32[3,3,64,64] {
  %p0 = f32[3,3,64,64]{3,2,1,0:T(8,128)S(1)} parameter(0)
  %p2 = bf16[8,32,32,64]{3,0,2,1:T(8,128)(2,1)S(1)} parameter(2)
  %fusion.22 = bf16[8,32,32,64]{3,0,2,1:T(8,128)(2,1)} fusion(%p2), kind=kLoop, calls=%fused_computation.inner, metadata={op_name="jit(step_fn)/jvp(SSDVgg)/ssd/base/vgg/jit(relu)/max"}
  %p1 = f32[8,32,32,64]{3,0,2,1:T(8,128)S(1)} parameter(1)
  %small.1 = f32[3,3]{1,0} dot(%p0, %p0), metadata={op_name="jit(step_fn)/train/update/dot_general"}
  %conv.10 = f32[3,3,64,64]{3,2,1,0:T(8,128)} convolution(%fusion.22, %p1), window={size=32x32 pad=1_1x1_1}, dim_labels=f01b_i01o->01bf, metadata={op_name="jit(step_fn)/transpose(jvp(SSDVgg))/ssd/base/vgg/conv1_2/conv_general_dilated" stack_frame_id=19}
  %mul.7 = f32[3,3,64,64]{3,2,1,0:T(8,128)} multiply(%conv.10, %conv.10), metadata={op_name="jit(step_fn)/train/update/mul"}
  ROOT %sub.39 = f32[3,3,64,64]{3,2,1,0:T(8,128)} subtract(%p0, %mul.7), metadata={op_name="jit(step_fn)/train/update/sub" stack_frame_id=23}
}

%fused_computation.37 (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0.1 = f32[64]{0:T(128)} parameter(0)
  %p1.1 = f32[64]{0:T(128)} parameter(1)
  ROOT %sub.40 = f32[64]{0:T(128)} subtract(%p0.1, %p1.1), metadata={op_name="jit(step_fn)/train/update/sub"}
}

ENTRY %main.11 (w: f32[3,3,64,64], g: f32[8,32,32,64], x: bf16[8,32,32,64], b: f32[64]) -> (f32[3,3,64,64], f32[64]) {
  %w = f32[3,3,64,64]{3,2,1,0:T(8,128)} parameter(0), metadata={op_name="state.params"}
  %g = f32[8,32,32,64]{3,0,2,1:T(8,128)} parameter(1)
  %x = bf16[8,32,32,64]{3,0,2,1:T(8,128)(2,1)} parameter(2)
  %b = f32[64]{0:T(128)} parameter(3)
  %copy-start = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)}, u32[]{:S(2)}) copy-start(%b)
  %copy-done = f32[64]{0:T(128)S(1)} copy-done(%copy-start)
  %multiply_subtract_fusion = f32[3,3,64,64]{3,2,1,0:T(8,128)} fusion(%w, %g, %x), kind=kOutput, calls=%fused_computation.35, metadata={op_name="jit(step_fn)/train/update/sub" stack_frame_id=23}, backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":["32","32","1","1"]}}
  %multiply_subtract_fusion.2 = f32[64]{0:T(128)} fusion(%copy-done, %copy-done), kind=kLoop, calls=%fused_computation.37, metadata={op_name="jit(step_fn)/train/update/sub"}
  %loss.1 = f32[]{:T(128)} reduce(%b, %b), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step_fn)/jvp(ssd/loss_conf)/reduce_sum"}
  ROOT %tuple.3 = (f32[3,3,64,64]{3,2,1,0:T(8,128)}, f32[64]{0:T(128)}) tuple(%multiply_subtract_fusion, %multiply_subtract_fusion.2)
}
'''


def test_a_fusion_that_holds_a_convolution_is_the_convolutions():
    got = device_scopes.parse_hlo_scopes(HLO)
    assert got["module"] == "jit_step_fn"
    ops = got["ops"]
    # rooted under train/update, holding conv1_2's kernel gradient (and a
    # smaller dot of the optimizer's): the backward pass's
    assert ops["multiply_subtract_fusion"] == (
        "jit(step_fn)/transpose(jvp(SSDVgg))/ssd/base/vgg/conv1_2/"
        "conv_general_dilated")
    assert "transpose(" in ops["multiply_subtract_fusion"]
    # without one: its root's, which is its own
    assert ops["multiply_subtract_fusion.2"] == "jit(step_fn)/train/update/sub"
    # an unfused instruction: its own
    assert ops["loss.1"] == "jit(step_fn)/jvp(ssd/loss_conf)/reduce_sum"
    # the compiler's own, without metadata: what uses its result, through
    # others without one (the copy ahead feeds the bias's update) ...
    assert ops["copy-done"] == ops["copy-start"] \
        == "jit(step_fn)/train/update/sub"
    # ... and where nothing that uses it has a name, what feeds it
    assert ops["tuple.3"] == ops["multiply_subtract_fusion"]
    assert ops["g"] == ops["multiply_subtract_fusion"]
    # what stands inside a fused computation names no event of a trace
    assert "conv.10" not in ops and "fusion.22" not in ops
    # members under ssd/base (the relu in a nested fusion, the
    # convolution) and train/update: mixed; one scope alone: not
    assert got["mixed"] == {
        "multiply_subtract_fusion": ["ssd/base", "train/update"]}


def test_type_and_opcode_of_tuple_shaped_instructions():
    t, op = device_scopes._type_and_opcode(
        "(u32[8,32,64]{2,0,1:T(8,128)S(1)}, bf16[8,32,32,64]{3,0,2,1}) "
        "fusion(%a, %b), kind=kOutput, calls=%fc")
    assert op == "fusion" and t.endswith("})")
    assert device_scopes._elements(t) == 8 * 32 * 32 * 64
    assert device_scopes._type_and_opcode(
        "f32[]{:T(128)} constant(0.1)") == ("f32[]{:T(128)}", "constant")
    assert device_scopes._elements("f32[]") == 1


# -- (d) the registry ---------------------------------------------------------

@pytest.fixture
def registry():
    """The registry as a test found it, put back afterwards."""
    programs = dict(device_scopes._PROGRAMS)
    maps = dict(device_scopes._MAPS)
    yield device_scopes
    device_scopes._PROGRAMS.clear()
    device_scopes._PROGRAMS.update(programs)
    device_scopes._MAPS.clear()
    device_scopes._MAPS.update(maps)


def test_registering_calls_no_thunk_and_a_second_ask_is_the_memo(registry):
    calls = []

    def f(x):
        with jax.named_scope("train/update"):
            return jnp.sin(x) * 2.0

    jitted = jax.jit(f)

    def thunk():
        calls.append(1)
        return jitted, (jax.ShapeDtypeStruct((8, 8), jnp.float32),), ()

    registry.register_program("test/one", thunk)
    assert "test/one" in registry.registered() and calls == []
    assert jitted._cache_size() == 0                # nothing was compiled
    first = registry.program_scopes("test/one")
    assert calls == [1] and first["module"] == "jit_f"
    assert any(device_scopes.declared_scope(v) == "train/update"
               for v in first["ops"].values())
    assert registry.program_scopes("test/one") is first and calls == [1]
    # a new registration under the name drops the map
    registry.register_program("test/one", thunk)
    assert registry.program_scopes("test/one") is not first
    assert registry.program_scopes("test/none") is None


def test_a_dropped_owner_and_a_program_without_shapes_read_nothing(registry):
    class Owner:
        pass

    owner = Owner()
    registry.register_program("test/weak", device_scopes.weak_thunk(
        owner, lambda o: (jax.jit(jnp.sin),
                          (jax.ShapeDtypeStruct((4,), jnp.float32),))))
    del owner
    assert registry.program_scopes("test/weak") is None

    def unready():
        raise LookupError("no step dispatched")

    registry.register_program("test/unready", unready)
    assert registry.program_scopes("test/unready") is None
    registry.register_program("test/plain", lambda: (lambda x: x, (1,)))
    assert registry.program_scopes("test/plain") is None


def test_abstract_keeps_shape_dtype_weak_type_and_sharding():
    x = jnp.ones((4, 2), jnp.bfloat16)
    tree = device_scopes.abstract({"a": x, "b": np.zeros((3,), np.int32),
                                   "c": 1.0, "w": jnp.asarray(2.0)})
    assert tree["a"].shape == (4, 2) and tree["a"].dtype == jnp.bfloat16
    assert tree["a"].sharding == x.sharding
    assert tree["b"].shape == (3,) and tree["c"] == 1.0
    assert tree["w"].weak_type


def test_dump_writes_the_maps_asked_for(registry, tmp_path):
    registry.register_program(
        "test/dump", lambda: (jax.jit(jnp.cos),
                              (jax.ShapeDtypeStruct((4,), jnp.float32),)))
    registry.program_scopes("test/dump")
    path = tmp_path / "scopes.json"
    registry.dump_program_scopes(str(path))
    import json

    doc = json.loads(path.read_text())
    assert set(doc["test/dump"]) == {"module", "ops", "mixed"}


def test_stale_metadata_out_of_the_compile_cache_is_compiled_again(
        tmp_path, monkeypatch, capfd):
    """JAX's persistent cache leaves metadata out of its key: the same
    program under another scope name is a hit and its text names the old
    scope.  The map has to be of THIS source's names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) @ x
        return jax.jit(f)

    shape = (jax.ShapeDtypeStruct((16, 16), jnp.float32),)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        program("ssd/base").lower(*shape).compile()      # fills the cache
        text = device_scopes._compiled_text(
            program("train/update").lower(*shape))
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert "train/update" in text and "ssd/base" not in text
    assert "compiling again" in capfd.readouterr().err


# -- (b) the registered programs at toy size -----------------------------------

class ScopedToyModule(nn.Module):
    """tests/benchmark/toy.py's trunk and heads under SSDVgg's scope
    names (the toy's own module declares none): same layer names, so the
    toy's weights and reference fit."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        import toy

        feats = []
        with jax.named_scope("ssd/base"):
            for i, fs in enumerate(toy.SHAPES):
                f = toy._source(x, fs)
                for d in range(toy.DEPTH):
                    f = nn.relu(nn.Conv(toy.WIDTH, (3, 3),
                                        name=f"trunk_{i}_{d}")(f))
                feats.append(f)
        with jax.named_scope("ssd/heads"):
            locs, confs = [], []
            for i, (f, k) in enumerate(zip(feats, toy.CELLS)):
                locs.append(nn.Conv(k * 4, (3, 3), name=f"loc_{i}")(f)
                            .reshape(x.shape[0], -1, 4))
                confs.append(nn.Conv(k * toy.CLASSES, (3, 3),
                                     name=f"conf_{i}")(f)
                             .reshape(x.shape[0], -1, toy.CLASSES))
            return jnp.concatenate(locs, 1), jnp.concatenate(confs, 1)


@pytest.fixture(scope="module")
def toy_runs():
    """One run of each SSD driver at toy size over the scoped toy: the
    programs are registered by the Optimizer and the runtime themselves."""
    import test_drivers_cpu as cpu
    import toy

    class Scoped:
        module = ScopedToyModule()
        weights = staticmethod(toy.weights)
        net = staticmethod(toy.net)

    runs = {}

    def get(kind):
        if kind not in runs:
            module, config, mix, over = cpu.TOY[kind]
            from benchmarks import harness

            config = harness.load_json(harness.HERE, "configs",
                                       config + ".json")
            config.update(resolution=300, num_priors=8732)
            traffic = dict(harness.load_json(harness.HERE, "traffic",
                                             mix + ".json"), **over)
            resolved = {"cell": {"name": f"scoped-{kind}", "chips": 1},
                        "config": config, "traffic": traffic,
                        "driver": module}
            kept = {}
            line = harness.drive(
                resolved, cpu.BENCH, cpu.SEED, 1.0, False, time.monotonic(),
                harness.describe_device(), {"toy": Scoped},
                lambda d: kept.update(driver=d))
            assert line["correct"], line["checks"]
            runs[kind] = kept["driver"]
        return runs[kind]

    return get


def holds_convolution(text: str) -> set:
    """Names of the instructions outside fused computations that are, or
    whose fused computation holds, a convolution (read apart from the
    parser under test: by line, one level of fusion)."""
    comp, conv_in, calls = None, {}, {}
    for line in text.splitlines():
        head = device_scopes._HEADER.match(line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        m = device_scopes._INSTRUCTION.match(line)
        if not m:
            continue
        before = line.split(", metadata=")[0]
        if " convolution(" in before:
            conv_in.setdefault(comp, []).append(m.group(1))
        c = device_scopes._CALLS.search(before)
        if c and " fusion(" in before:
            calls[m.group(1)] = (comp, c.group(1))
    fused = {callee for _, callee in calls.values()}
    holders = {name for name, (comp, callee) in calls.items()
               if comp not in fused and callee in conv_in}
    for comp, names in conv_in.items():
        if comp not in fused:
            holders.update(names)
    return holders


def test_train_step_map_charges_convolutions_to_the_model_and_names_every_section(
        toy_runs):
    toy_runs("train")
    assert "train/step" in device_scopes.registered()
    # the run was not traced: nobody asked for a map, so no thunk was
    # called and nothing was compiled a second time (a registration
    # drops the name's memo, and the run registered)
    assert "train/step" not in device_scopes._MAPS
    got = device_scopes.program_scopes("train/step")
    assert got is device_scopes.program_scopes("train/step")
    assert got["module"] == "jit_step_fn"
    by_scope = {}
    for name, op_name in got["ops"].items():
        by_scope.setdefault(device_scopes.declared_scope(op_name),
                            []).append((name, op_name))
    for scope in ("train/augment", "train/update", "ssd/loss_match",
                  "ssd/loss_loc", "ssd/loss_conf", "ssd/loss_mine",
                  "ssd/base", "ssd/heads"):
        assert by_scope.get(scope), f"{scope}: no instruction"
    # every instruction that holds a convolution and names its origin is
    # the model's, the forward ones forward and the others backward.  (The
    # CPU's compiler rewrites a kernel gradient into a convolution WITHOUT
    # metadata; the TPU's keeps it: PERF.md, PR 37.)
    jitted, args = device_scopes._PROGRAMS["train/step"]()[:2]
    text = jitted.lower(*args).compile().as_text()
    holders = holds_convolution(text)
    assert holders
    named = {n: got["ops"][n] for n in holders if got["ops"].get(n)}
    assert len(named) >= 30, named      # 5 convolutions x 6 sources, forward
    for name, op_name in named.items():
        assert MODEL.search(op_name), (name, op_name)
        assert "conv_general_dilated" in op_name, (name, op_name)
    forward = [o for o in named.values() if "transpose(" not in o]
    backward = [o for o in named.values() if "transpose(" in o]
    assert len(forward) >= 30 and all("jvp(" in o for o in forward)
    assert backward, "no input gradient kept its name"
    # a loss's backward stands under the loss, transposed
    assert any(re.search(r"transpose\(jvp\((vmap\()?ssd/loss_", o)
               for _, o in by_scope["ssd/loss_conf"] + by_scope["ssd/loss_loc"])


def test_a_dropped_runtime_leaves_its_registrations_nothing_to_hold(toy_runs):
    driver = toy_runs("serve")
    names = [n for n in device_scopes.registered()
             if n.startswith("serve/default/")]
    assert sorted(n.split("/")[2] for n in names) == sorted(driver.tier_names)
    assert all(n.endswith("/fixed") for n in names)
    assert not set(names) & set(device_scopes._MAPS)    # an untraced run
    thunk = device_scopes._PROGRAMS["serve/default/fp/fixed"]
    # the runtime was dropped by the driver's free(): the registry held it
    # weakly, so the weights went with it
    import gc

    gc.collect()
    with pytest.raises(LookupError):
        thunk()
    assert device_scopes.program_scopes("serve/default/fp/fixed") is None


def test_serve_program_of_a_live_runtime(registry):
    import toy
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 ssd_serving_tiers)
    from analytics_zoo_tpu.serving import ServingRuntime

    model = Model(ScopedToyModule(), {"params": toy.weights(3, 7.0)})
    tiers = ssd_serving_tiers(
        model, PreProcessParam(batch_size=4, resolution=300),
        compute_dtype="bf16")
    # the audit's own thunk is batch 1, as az_analyze traces it ...
    fn, args, static = tiers[0].device_program()
    assert args[1].shape == (1, 300, 300, 3) and static == (4,)
    before = set(device_scopes.registered())
    rt = ServingRuntime(tiers, n_replicas=1, max_batch=4, queue_capacity=8,
                        default_deadline_s=60.0)
    new = set(device_scopes.registered()) - before
    assert {"serve/default/fp/fixed", "serve/default/int8/fixed"} <= (
        new | before)
    # ... and the registered geometry is the runtime's rows
    fn, args, static = device_scopes._PROGRAMS["serve/default/fp/fixed"]()
    assert args[1].shape == (4, 300, 300, 3)
    assert args[2].shape == (4,) and static == (4,)
    rt.warm({"input": np.zeros((300, 300, 3), np.float32)})
    got = device_scopes.program_scopes("serve/default/fp/fixed")
    assert got["module"] == "jit_detect"
    scopes = {device_scopes.declared_scope(v) for v in got["ops"].values()}
    assert {"ssd/base", "ssd/heads", "ssd/softmax", "ssd/detout"} <= scopes
    # the charged instructions that hold a convolution are the trunk's
    convs = [v for v in got["ops"].values() if "conv_general_dilated" in v]
    assert len(convs) >= 30 and all(MODEL.search(v) for v in convs)
    del rt


def test_az_analyze_still_traces_the_ssd_serving_targets():
    """The audit's thunk is still the zero-argument one, at its own
    smallest batch."""
    from analytics_zoo_tpu.analysis import targets
    from analytics_zoo_tpu.parallel import create_mesh

    programs = {p.name: p for p in targets._ssd_serving(create_mesh())}
    assert {"ssd/serve:fp", "ssd/serve:int8"} <= set(programs)
    built = programs["ssd/serve:fp"].build()
    closed = jax.make_jaxpr(built.fn, static_argnums=built.static_argnums)(
        *built.args)
    assert closed.jaxpr.eqns


def test_ssdvgg_lowers_with_its_three_sections():
    """The real trunk's names, read off the lowering (no compile)."""
    from analytics_zoo_tpu.models.ssd import SSDVgg

    module = SSDVgg(num_classes=4, resolution=300)
    x = jax.ShapeDtypeStruct((1, 300, 300, 3), jnp.float32)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros(x.shape, x.dtype)))
    text = jax.jit(module.apply).lower(params, x).as_text(debug_info=True)
    for want in ("ssd/base/vgg/conv1_2/conv_general_dilated",
                 "ssd/base/vgg/fc7/", "ssd/extras/extra/conv6_2/",
                 "ssd/heads/loc_0/", "ssd/heads/conf_5/",
                 "ssd/heads/conv4_3_norm/"):
        assert want in text, want


# -- (e) the decode step's older scopes keep their instructions ---------------

OLD_LM = ("lm/indexer", "lm/select", "lm/mla_full", "lm/mla_paged",
          "lm/gqa_paged", "lm/mla_window", "lm/gqa_window", "lm/dense_mlp",
          "lm/route", "lm/experts", "lm/shared_mlp", "lm/head")
NEW_LM = ("lm/proj", "lm/embed", "lm/cache_write")


@pytest.mark.parametrize("toy_name", ["lm_toy", "lm_mla_toy", "lm_gqa_toy"])
def test_new_lm_scopes_leave_the_older_scopes_instruction_sets(toy_name,
                                                               monkeypatch):
    import importlib

    from analytics_zoo_tpu.models import lm
    from benchmarks import hlo_scopes

    config = importlib.import_module(toy_name).TOY
    cfg = lm.LMConfig.from_dict(config)
    geo = lm.CacheGeometry(n_pages=25, page=4, max_pages=12, n_slots=4)
    S = jax.ShapeDtypeStruct
    args = (lm.param_shapes(cfg), lm.cache_shapes(cfg, geo),
            S((4 * (3 + geo.max_pages) + geo.n_pages,), jnp.int32))

    def text(without_new: bool) -> str:
        real = jax.named_scope

        def scope(name):
            return contextlib.nullcontext() if name in NEW_LM else real(name)

        if without_new:
            monkeypatch.setattr(lm.jax, "named_scope", scope)
        try:
            # a function of its own each time: nothing is read out of the
            # other build's trace cache
            step = jax.jit(lambda p, c, r: lm.decode_step(cfg, geo, p, c, r))
            return step.lower(*args).compile().as_text()
        finally:
            monkeypatch.undo()

    now, before = (hlo_scopes.scope_map(text(False)),
                   hlo_scopes.scope_map(text(True)))
    assert set(NEW_LM) & set(now) and not set(NEW_LM) & set(before)
    for scope in OLD_LM:
        assert now.get(scope) == before.get(scope), scope
    assert any(now.get(s) for s in OLD_LM)
