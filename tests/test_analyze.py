"""az-analyze (ISSUE 10): the two-engine static invariant checker.

Contract per engine:

- every SOURCE rule has a firing + clean fixture pair (a rule that
  cannot fire is a dead gate; a rule that fires on clean idiom is a
  nuisance that gets deleted), plus the waiver syntax tests (trailing /
  standalone coverage, mandatory reason, unused-waiver escalation);
- the PROGRAM engine's four checks each fire on a seeded bad program —
  including the collective inventory catching a deliberately
  MIS-DECLARED SpecSet — and pass on the correct twin;
- the repo itself runs clean end to end: ``tools/az_analyze.py --all``
  in-process, zero un-waived violations, every waiver reasoned, the
  full registered-pipeline + serving-tier audit surface covered,
  inside the ≤20 s tier-1 budget.
"""

import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from analytics_zoo_tpu.analysis.base import (
    Violation,
    apply_waivers,
    format_violation,
    parse_waivers,
)
from analytics_zoo_tpu.analysis.program import (
    AuditProgram,
    BuiltProgram,
    audit_program,
    collective_inventory,
)
from analytics_zoo_tpu.analysis.source import (
    NoHostSyncInHotPath,
    OneClock,
    OnePlacementSite,
    RegisteredMetricNames,
    SeededRngOnly,
    ErrorClassesComplete,
    default_rules,
    run_source_engine,
)


def _scan(tmp_path, name, text, rules):
    (tmp_path / name).write_text(text)
    return run_source_engine(root=str(tmp_path), rules=rules)


def _unwaived(violations):
    return [v for v in violations if not v.waived]


# ---------------------------------------------------------------------------
# Source rules: firing + clean fixture per rule
# ---------------------------------------------------------------------------


class TestOneClockRule:
    def test_fires_on_raw_time_reads_through_aliases(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "import time as _t\n"
            "from time import monotonic\n"
            "a = time.time()\n"
            "b = _t.monotonic()\n"
            "c = monotonic()\n"), [OneClock()])
        assert {v.line for v in got} == {4, 5, 6}
        assert all(v.rule == "one-clock" for v in got)

    def test_clean_on_injected_clock_and_unbanned_time_fns(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "from analytics_zoo_tpu.utils.clock import as_now_fn\n"
            "now = as_now_fn(None)\n"
            "t0 = now()\n"
            "time.sleep(0.1)\n"            # sleeping isn't a clock read
            "t1 = time.perf_counter()\n"), [OneClock()])   # probe domain
        assert got == []

    def test_allowed_module_is_exempt(self, tmp_path):
        (tmp_path / "utils").mkdir()
        (tmp_path / "utils" / "clock.py").write_text(
            "import time\nnow = time.monotonic()\n")
        got = run_source_engine(root=str(tmp_path), rules=[OneClock()])
        assert got == []


class TestOnePlacementSiteRule:
    # the firing fixture lives with the substrate tests
    # (tests/test_specs.py::TestOnePlacementSite) — here: clean idiom
    def test_clean_on_spec_layer_consumption(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "from analytics_zoo_tpu.parallel import pipeline_specs\n"
            "specs = pipeline_specs('ssd')\n"
            "placed = specs.place_state({'w': 1})\n"), [OnePlacementSite()])
        assert got == []

    def test_substrate_modules_are_exempt(self, tmp_path):
        (tmp_path / "parallel").mkdir()
        (tmp_path / "parallel" / "mesh.py").write_text(
            "import jax\n"
            "def place(x, sh):\n"
            "    return jax.device_put(x, sh)\n")
        got = run_source_engine(root=str(tmp_path),
                                rules=[OnePlacementSite()])
        assert got == []


class TestSeededRngOnlyRule:
    def test_fires_on_global_seed_draw_and_unseeded_ctor(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import numpy as np\n"
            "np.random.seed(0)\n"
            "x = np.random.rand(4)\n"
            "g = np.random.default_rng()\n"
            "r = np.random.RandomState()\n"), [SeededRngOnly()])
        assert {v.line for v in got} == {2, 3, 4, 5}

    def test_fires_on_unseeded_bitgens_and_explicit_none_seed(
            self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import numpy as np\n"
            "a = np.random.Generator(np.random.PCG64())\n"
            "b = np.random.default_rng(None)\n"
            "c = np.random.SeedSequence()\n"
            "d = np.random.dirichlet([1.0, 2.0])\n"), [SeededRngOnly()])
        assert {v.line for v in got} == {2, 3, 4, 5}

    def test_clean_on_seeded_local_generators(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import numpy as np\n"
            "g = np.random.default_rng(42)\n"
            "r = np.random.RandomState(7)\n"
            "p = np.random.Generator(np.random.PCG64(3))\n"
            "q = np.random.SeedSequence(entropy=9)\n"
            "x = g.random(4)\n"), [SeededRngOnly()])
        assert got == []


class TestNoHostSyncInHotPathRule:
    RULES = [NoHostSyncInHotPath(hot_modules=frozenset({"hot.py"}))]

    def test_fires_on_sync_and_tracer_materialization(self, tmp_path):
        got = _scan(tmp_path, "hot.py", (
            "import jax\n"
            "import numpy as np\n"
            "def step(state, batch):\n"
            "    x = np.asarray(batch)\n"     # inside a jit-bound fn
            "    return state\n"
            "step_j = jax.jit(step)\n"
            "def host_loop(out):\n"
            "    jax.block_until_ready(out)\n"
            "    return out.item()\n"), self.RULES)
        assert {v.line for v in got} == {4, 8, 9}

    def test_fires_inside_decorator_jitted_functions(self, tmp_path):
        got = _scan(tmp_path, "hot.py", (
            "import functools\n"
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(batch):\n"
            "    return np.asarray(batch)\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def step2(state):\n"
            "    return np.array(state)\n"), self.RULES)
        assert {v.line for v in got} == {6, 9}

    def test_jit_name_match_is_not_a_bare_substring(self, tmp_path):
        # a helper that merely mentions 'jit' mid-name is not a jit site
        got = _scan(tmp_path, "hot.py", (
            "import numpy as np\n"
            "def jitter_noise(fn):\n"
            "    return fn\n"
            "def decode(x):\n"
            "    return np.asarray(x)\n"
            "out = jitter_noise(decode)\n"), self.RULES)
        assert got == []

    def test_clean_outside_jit_and_outside_hot_modules(self, tmp_path):
        # np.asarray in plain host code of a hot module: fine
        got = _scan(tmp_path, "hot.py", (
            "import numpy as np\n"
            "def readback(dets):\n"
            "    return np.asarray(dets)\n"), self.RULES)
        assert got == []
        # a cold module may sync (e.g. a bench/drill helper)
        got = _scan(tmp_path, "cold.py", (
            "import jax\n"
            "def bench(out):\n"
            "    jax.block_until_ready(out)\n"), self.RULES)
        assert got == []


class TestErrorClassesCompleteRule:
    RULES = [ErrorClassesComplete(target="errors.py")]

    def test_fires_on_unclassified_class_and_ghost_registration(
            self, tmp_path):
        got = _scan(tmp_path, "errors.py", (
            "class Covered(RuntimeError):\n    pass\n"
            "class Orphan(RuntimeError):\n    pass\n"
            "_RETRYABLE_CLASSES = (Covered, Ghost)\n"
            "FATAL_ERRORS = ()\n"), self.RULES)
        assert len(got) == 2
        assert any("Orphan" in v.message and v.line == 3 for v in got)
        assert any("Ghost" in v.message for v in got)

    def test_clean_on_fully_classified_errors(self, tmp_path):
        got = _scan(tmp_path, "errors.py", (
            "from typing import Tuple, Type\n"
            "class A(RuntimeError):\n    pass\n"
            "class B(IOError):\n    pass\n"
            "_RETRYABLE_CLASSES: Tuple[Type[BaseException], ...] = (A,)\n"
            "FATAL_ERRORS = (B,)\n"), self.RULES)
        assert got == []


class TestRegisteredMetricNamesRule:
    RULES = [RegisteredMetricNames()]

    def test_fires_on_undeclared_static_prefixed_and_dynamic_names(
            self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "def f(reg, name, cause):\n"
            "    reg.counter('made/up').inc()\n"              # undeclared
            "    reg.gauge(f'serve/unknown_{cause}').set(1)\n"  # bad family
            "    reg.histogram(name).observe(1.0)\n"),        # dynamic
            self.RULES)
        assert {v.line for v in got} == {2, 3, 4}
        assert all(v.rule == "registered-metric-names" for v in got)
        assert any("'made/up'" in v.message for v in got)
        assert any("'serve/unknown_*'" in v.message for v in got)
        assert any("not statically resolvable" in v.message for v in got)

    def test_clean_on_declared_names_families_and_waived_dynamics(
            self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "def f(reg, name, cause, tier):\n"
            "    reg.counter('serve/submitted').inc()\n"
            "    reg.counter(f'serve/shed/cause={cause}').inc()\n"
            "    reg.histogram(f'serve/latency_s/tier={tier}')"
            ".observe(0.1)\n"
            "    reg.counter('serve/shed/cause=deadline').inc()\n"
            "    reg.gauge(name).set(1)  "
            "# az-allow: registered-metric-names — caller passes a "
            "declared data/read/* name\n"), self.RULES)
        assert _unwaived(got) == []

    def test_substrate_and_catalog_modules_are_exempt(self, tmp_path):
        (tmp_path / "obs").mkdir()
        (tmp_path / "obs" / "registry.py").write_text(
            "def counter(self, name):\n"
            "    return self._get(name)\n"
            "def snapshot(reg, name):\n"
            "    return reg.counter(name).value\n")
        got = run_source_engine(root=str(tmp_path), rules=self.RULES)
        assert got == []

    def test_catalog_loaded_from_the_real_package_by_ast(self):
        """The rule reads obs/names.py without importing it; its view
        must match the live CATALOG exactly."""
        from analytics_zoo_tpu.obs.names import CATALOG

        rule = RegisteredMetricNames()
        assert rule._catalog() == frozenset(CATALOG)
        assert rule._covered("serve/submitted")
        assert rule._covered("serve/shed/cause=deadline")
        assert rule._covered("serve/shed/cause=*")
        assert not rule._covered("made/up")


# ---------------------------------------------------------------------------
# Waiver syntax
# ---------------------------------------------------------------------------


class TestWaivers:
    def test_trailing_waiver_silences_and_records_reason(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "t = time.time()  # az-allow: one-clock — drill wall-clock "
            "stamp, never compared across runs\n"), [OneClock()])
        assert len(got) == 1 and got[0].waived
        assert "drill wall-clock" in got[0].waiver_reason
        assert "[waived:" in format_violation(got[0])

    def test_standalone_waiver_covers_next_line(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "# az-allow: one-clock — startup banner only\n"
            "t = time.time()\n"), [OneClock()])
        assert len(got) == 1 and got[0].waived

    def test_standalone_waiver_covers_multiline_statement(self, tmp_path):
        """The violation anchors on the continuation line holding the
        call — the standalone waiver must cover the whole statement
        below it, with no waiver-unused ghost."""
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "# az-allow: one-clock — banner stamp\n"
            "t = (\n"
            "    time.time())\n"), [OneClock()])
        assert len(got) == 1 and got[0].waived

    def test_waiver_without_reason_is_a_violation(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "t = time.time()  # az-allow: one-clock\n"), [OneClock()])
        rules = {v.rule for v in _unwaived(got)}
        assert rules == {"one-clock", "waiver-syntax"}

    def test_unused_waiver_is_a_violation(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "# az-allow: one-clock — nothing here reads time anymore\n"
            "x = 1\n"), [OneClock()])
        assert [v.rule for v in got] == ["waiver-unused"]

    def test_waiver_only_covers_its_own_rule(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "t = time.time()  # az-allow: seeded-rng-only — wrong rule\n"),
            [OneClock(), SeededRngOnly()])
        rules = sorted(v.rule for v in _unwaived(got))
        assert rules == ["one-clock", "waiver-unused"]

    def test_trailing_waiver_covers_multiline_statement(self, tmp_path):
        """Violations anchor to a multi-line call's FIRST line while a
        trailing comment sits on its last — the waiver must cover the
        whole logical statement, with no waiver-unused ghost."""
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "t = max(\n"
            "    time.time(),\n"
            "    0.0,\n"
            ")  # az-allow: one-clock — wall stamp for a log banner\n"),
            [OneClock()])
        assert len(got) == 1 and got[0].waived

    def test_trailing_waiver_mid_statement_covers_full_extent(
            self, tmp_path):
        """A trailing comment on the FIRST physical line of a wrapped
        call must still waive the violation anchored on a continuation
        line."""
        got = _scan(tmp_path, "mod.py", (
            "import time\n"
            "t = max(  # az-allow: one-clock — banner stamp\n"
            "    time.time(),\n"
            "    0.0)\n"), [OneClock()])
        assert len(got) == 1 and got[0].waived

    def test_other_rules_waivers_survive_subset_runs(self, tmp_path):
        """Running ONE rule must not report another rule's legitimate
        waiver as unused (tests pin single rules; the in-tree placement
        waivers must not poison them)."""
        got = _scan(tmp_path, "mod.py", (
            "import jax\n"
            "x = jax.device_put(1, None)  # az-allow: one-placement-site"
            " — fixture exception\n"), [OneClock()])
        assert got == []

    def test_waiver_syntax_in_docstring_is_inert(self, tmp_path):
        got = _scan(tmp_path, "mod.py", (
            '"""Docs: use `# az-allow: one-clock — why` to waive."""\n'
            "x = 1\n"), [OneClock()])
        assert got == []

    def test_parse_waivers_unit(self):
        waivers, bad = parse_waivers(
            ["x = 1  # az-allow: some-rule — because reasons"], "f.py")
        assert len(waivers) == 1 and not bad
        assert waivers[0].rule == "some-rule"
        assert set(waivers[0].covers) == {1}
        marked = apply_waivers(
            [Violation("some-rule", "f.py", 1, "m")], waivers)
        assert marked[0].waived


# ---------------------------------------------------------------------------
# Program engine: each check fires on a seeded bad program
# ---------------------------------------------------------------------------


def _audit_one(fn, args, **kw):
    return audit_program(AuditProgram(
        "fixture", lambda: BuiltProgram(fn=fn, args=args, **kw)))


class TestProgramEngine:
    def test_callback_in_hot_program_fires(self):
        def noisy(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        got = _audit_one(jax.jit(noisy), (jnp.ones(3),))
        assert [v.rule for v in got] == ["no-callbacks-in-hot-program"]

        got = _audit_one(jax.jit(lambda x: x * 2), (jnp.ones(3),))
        assert got == []

    def test_donation_check_fires_without_donate_argnums(self):
        state = {"w": jnp.ones(4), "m": jnp.zeros(4)}

        def step(state, lr):
            return {k: v - lr for k, v in state.items()}

        got = _audit_one(jax.jit(step), (state, 0.1), donate_state=state)
        assert [v.rule for v in got] == ["donation-materialized"]
        assert "2/2" in got[0].message

        donating = jax.jit(step, donate_argnums=(0,))
        assert _audit_one(donating, (state, 0.1),
                          donate_state=state) == []

    def test_float64_leak_fires(self):
        def f(x):
            return x * 2

        try:
            jax.config.update("jax_enable_x64", True)
            got = _audit_one(jax.jit(f),
                             (np.ones(3, np.float64),))
        finally:
            jax.config.update("jax_enable_x64", False)
        assert [v.rule for v in got] == ["no-float64"]

        assert _audit_one(jax.jit(f), (np.ones(3, np.float32),)) == []

    def test_collective_inventory_catches_misdeclared_specset(self):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from analytics_zoo_tpu.parallel.specs import SpecSet

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        mesh = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
        fn = shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                       in_specs=P("data", "model"), out_specs=P("data"))
        x = jnp.ones((4, 2))
        assert collective_inventory(jax.make_jaxpr(fn)(x)) == {"model"}

        # deliberately MIS-DECLARED: the pipeline claims a data-only
        # mesh while the program psums over 'model'
        from analytics_zoo_tpu.parallel import mesh as mesh_lib

        lying = SpecSet(mesh_lib.create_mesh(devices=devs[:4]))
        assert list(lying.mesh.axis_names) == ["data"]
        got = _audit_one(fn, (x,), specs=lying)
        assert [v.rule for v in got] == ["collective-inventory"]
        assert "'model'" in got[0].message

        honest = SpecSet(mesh)
        assert _audit_one(fn, (x,), specs=honest) == []

    def test_untraceable_target_is_reported_not_raised(self):
        def build():
            raise RuntimeError("model zoo import exploded")

        got = audit_program(AuditProgram("broken", build))
        assert [v.rule for v in got] == ["program-trace-error"]
        assert "exploded" in got[0].message

    def test_broken_tier_factory_is_a_finding_not_a_crash(self):
        """Suite construction must survive an exploding serving-tier
        factory: the family degrades to one reported target, the rest
        of the audit still runs."""
        from analytics_zoo_tpu.analysis.targets import _guarded_tiers

        def broken_factory(mesh):
            raise TypeError("tiers() got an unexpected keyword")

        targets = _guarded_tiers("ssd", broken_factory, mesh=None)
        assert [t.name for t in targets] == ["ssd/serve:<factory-failed>"]
        got = audit_program(targets[0])
        assert [v.rule for v in got] == ["program-trace-error"]
        assert "unexpected keyword" in got[0].message


# ---------------------------------------------------------------------------
# The repo itself: tier-1 wiring (the ISSUE-10 acceptance gate)
# ---------------------------------------------------------------------------


class TestRepoClean:
    def test_source_engine_repo_clean_and_waivers_reasoned(self):
        got = run_source_engine(rules=default_rules())
        offenders = _unwaived(got)
        assert not offenders, "\n".join(map(format_violation, offenders))
        for v in got:
            assert v.waived and v.waiver_reason

    def test_repo_checkout_root_normalizes_to_the_package(self):
        """``--root .`` from the checkout must not void the
        package-relative rule scopes (allowed lists, hot modules) and
        mass-flag the sanctioned substrate modules."""
        import analytics_zoo_tpu

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(analytics_zoo_tpu.__file__)))
        got = run_source_engine(root=repo_root, rules=default_rules())
        assert not _unwaived(got), "\n".join(map(format_violation,
                                                 _unwaived(got)))

    def test_az_analyze_all_clean_within_budget(self, capsys):
        """``tools/az_analyze.py --all`` in-process: exit 0, the full
        audit surface covered, inside its tier-1 budget.  The budget is
        sized for the installed JAX: tracing the 36-program surface takes
        34 s under jax 0.9.0 on the 8-core host (PR 21), so 90 s flags a
        surface that doubled, not a loaded machine."""
        import tools.az_analyze as az
        from analytics_zoo_tpu.analysis.targets import repo_audit_suite

        t0 = time.time()
        rc = az.main(["--all"])
        dt = time.time() - t0
        out = capsys.readouterr().out
        assert rc == 0, out
        assert dt < 90.0, f"az-analyze --all took {dt:.1f}s (budget 90s)"
        assert "0 violation(s)" in out
        n = len(repo_audit_suite())
        assert n >= 21  # 6 pipelines × train+eval, ≥3+3+2+2 serving tiers
        assert f"{n} program(s) audited" in out

    def test_program_audit_surface_covers_acceptance_list(self):
        """All six registered pipelines' train+eval programs plus every
        family's serving tiers — the ISSUE-10 coverage line, pinned
        against the live registry so a new pipeline must join the
        audit to register."""
        from analytics_zoo_tpu.analysis.targets import repo_audit_suite
        from analytics_zoo_tpu.parallel import registered_pipelines

        names = {t.name for t in repo_audit_suite()}
        # a pipeline that is served and never trained (ISSUE 28: the
        # decoder LM) joins with its serving programs alone
        serve_only = {"lm"}
        for pipe in registered_pipelines():
            if pipe in serve_only:
                assert any(n.startswith(f"{pipe}/serve:") for n in names)
                continue
            assert f"{pipe}/train" in names, names
            assert f"{pipe}/eval" in names, names
        assert {"lm/serve:bf16", "lm/serve:prefill4",
                "lm/serve:prefill8"} <= names
        # ISSUE 33: the same tier over a model of causal latent attention
        # throughout (the paged decode attention, pools only)
        assert {"lm-mla/serve:bf16", "lm-mla/serve:prefill4",
                "lm-mla/serve:prefill8"} <= names
        # ISSUE 35: and over a model of grouped-query attention (pools
        # and rings of two widths, sinks, no shared expert)
        assert {"lm-gqa/serve:bf16", "lm-gqa/serve:prefill4",
                "lm-gqa/serve:prefill8"} <= names
        # ISSUE 39: and over a model with a state-space mixer beside
        # every attention (a recurrent state a session among the
        # cache's leaves, no experts)
        assert {"lm-ssm/serve:bf16", "lm-ssm/serve:prefill4",
                "lm-ssm/serve:prefill8"} <= names
        assert {"ssd/serve:fp", "ssd/serve:int8"} <= names
        # ISSUE 13: the persistent-RNN TRAIN program (pallas engine,
        # transposed persistent backward) is audited alongside the
        # default-engine pipeline — a pallas-engine training pipeline
        # absent from the audit surface fails here
        assert "ds2-pallas/train" in names
        # ISSUE 12: the FUSED DetectionOutput serving programs (what
        # "auto" dispatches on TPU) are audited like every other rung
        assert {"ssd-fused/serve:fp", "ssd-fused/serve:int8"} <= names
        assert any(n.startswith("ssd-fused/serve:int8_topk")
                   for n in names)
        assert any(n.startswith("ds2/serve:beam") for n in names)
        assert "ds2/serve:greedy" in names
        # ISSUE 14: the multiplexed fleet's per-model serving programs
        # — frcnn + fraud joined the rung factories, and the streaming
        # DS2 session model exposes its carry-in/carry-out steady-block
        # program — all audited like every other rung
        assert {"frcnn/serve:fp", "frcnn/serve:int8"} <= names
        assert {"fraud/serve:fp", "fraud/serve:int8"} <= names
        assert "ds2-stream/serve:stream" in names
        # ISSUE 17: the sharded-embedding long tail — recommendation
        # (both architectures: NCF train/eval + the Wide&Deep train
        # program) and sentiment, serving rungs included
        assert "rec-wd/train" in names
        assert {"rec/serve:fp", "rec/serve:int8"} <= names
        assert {"sentiment/serve:fp", "sentiment/serve:int8"} <= names
        # ISSUE 19: the width-2 replica-slice geometry — the fraud tier
        # ladder re-jitted against a 2-device sub-mesh via replace_mesh
        # audits alongside the full-width programs
        assert {"fraud-slice-w2/serve:fp",
                "fraud-slice-w2/serve:int8"} <= names

    def test_serving_tiers_expose_device_programs(self):
        """Every ladder rung the factories hand the runtime must carry
        its audit hook — a tier without one degrades the program audit
        silently."""
        from analytics_zoo_tpu.analysis.targets import (
            _ds2_serving, _ds2_streaming_serving, _fraud_serving,
            _frcnn_serving, _lm_serving, _rec_serving, _sentiment_serving,
            _ssd_serving)
        from analytics_zoo_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh()
        for target in (_ssd_serving(mesh) + _ds2_serving(mesh)
                       + _ds2_streaming_serving(mesh)
                       + _frcnn_serving(mesh) + _fraud_serving(mesh)
                       + _rec_serving(mesh) + _sentiment_serving(mesh)
                       + _lm_serving(mesh)):
            built = target.build()      # raises if the hook is missing
            assert callable(built.fn)

    def test_fused_tier_without_device_program_is_a_finding(self):
        """ISSUE 12 coverage pin: a backend="fused" serving tier that
        stops exposing its ``device_program`` thunk must FAIL the audit
        (the fused program would otherwise silently leave the audit
        surface)."""
        from analytics_zoo_tpu.analysis.targets import _tier_targets
        from analytics_zoo_tpu.serving.ladder import ServingTier

        tier = ServingTier("fp", forward=lambda b: b,
                           device_program=None)
        targets = _tier_targets("ssd-fused", [tier], specs=None)
        assert [t.name for t in targets] == ["ssd-fused/serve:fp"]
        got = audit_program(targets[0])
        assert [v.rule for v in got] == ["program-trace-error"]
        assert "device_program" in got[0].message

    def test_cli_exits_nonzero_with_file_line_diagnostics(self, tmp_path,
                                                          capsys):
        import tools.az_analyze as az

        (tmp_path / "mod.py").write_text("import time\nt = time.time()\n")
        rc = az.main(["--source", "--root", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1
        assert f"{tmp_path.name}/mod.py:2 one-clock" in out

    def test_cli_list_rules(self, capsys):
        import tools.az_analyze as az

        assert az.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("one-clock", "one-placement-site", "seeded-rng-only",
                     "no-host-sync-in-hot-path", "error-classes-complete"):
            assert rule in out
