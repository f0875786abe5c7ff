"""``readers/scope_device.py`` on a hand-made trace and a stubbed registry:
the numbers it gives, and nothing where a piece is missing; the metric
files that use it; ``scope_table.py``'s table."""

import glob
import json
import os

import pytest

from benchmarks import harness, scope_table, trace_reduce
from benchmarks.readers import scope_device

BENCH = harness.load_benchmark()
LINE = "%{} = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fc"


def event(name, start, dur):
    return (LINE.format(name), start, dur)


def reduction():
    """Two modules that both hold a ``fusion.1``: two runs of
    ``jit_step_fn`` (0.0-1.0 and 2.0-3.0) and one of ``jit_other``
    (4.0-5.0), with operations inside the runs, between them and before
    the first; a loop that lists its body too."""
    modules = [("jit_step_fn(123)", 0.0, 1.0), ("jit_step_fn(123)", 2.0, 1.0),
               ("jit_other(9)", 4.0, 1.0)]
    ops = []
    for t in (0.0, 2.0):
        ops += [event("fusion.1", t + 0.0, 0.30),      # forward conv
                event("fusion.2", t + 0.3, 0.40),      # kernel grad + SGD
                event("loss.3", t + 0.7, 0.10),        # the loss, forward
                event("while.4", t + 0.8, 0.10),       # a loop ...
                event("body.5", t + 0.82, 0.03),       # ... and its body,
                event("body.5", t + 0.86, 0.03),       # twice
                event("copy.6", t + 0.9, 0.05)]        # under no scope
    ops += [event("fusion.1", 1.5, 0.2),               # between the runs
            event("fusion.1", 4.0, 0.9),               # the other module's
            event("fusion.2", -1.0, 0.5)]              # before the first
    dev = trace_reduce.DevicePlane("/device:TPU:0", ops, modules)
    return trace_reduce.Reduction([dev], [])


OPS = {
    "fusion.1": "jit(step_fn)/jvp(SSDVgg)/ssd/base/vgg/conv1_1/"
                "conv_general_dilated",
    "fusion.2": "jit(step_fn)/transpose(jvp(SSDVgg))/ssd/base/vgg/conv1_2/"
                "conv_general_dilated",
    "loss.3": "jit(step_fn)/jvp(vmap(ssd/loss_conf))/reduce_sum",
    "while.4": "jit(step_fn)/train/update/while",
    "body.5": "jit(step_fn)/train/update/while/body/add",
    "copy.6": "",
}


class Registry:
    """What the reader uses of ``obs.device_scopes``."""

    def __init__(self, maps):
        self.maps, self.asked, self.dumped = maps, [], []

    def registered(self):
        return sorted(self.maps)

    def program_scopes(self, name):
        self.asked.append(name)
        return self.maps[name]

    def dump_program_scopes(self, path):
        self.dumped.append(path)

    @staticmethod
    def declared_scope(op_name):
        from analytics_zoo_tpu.obs import device_scopes

        return device_scopes.declared_scope(op_name)


def context(red, **window):
    return {"trace": red, "window": dict({"batch": 2, "resolution": 300,
                                          "num_classes": 21}, **window),
            "counters": {}, "peaks": {"bf16_flops_per_s": 1e12}}


@pytest.fixture
def stub(monkeypatch):
    reg = Registry({"train/step": {"module": "jit_step_fn", "ops": OPS,
                                   "mixed": {"fusion.2": ["ssd/base",
                                                          "train/update"]}}})
    monkeypatch.setattr(scope_device, "registry", lambda: reg)
    monkeypatch.setattr(scope_device, "trace_dir", lambda: None)
    return reg


P = {"program": "jit_step_fn", "registered": "train/step"}
MODEL = "ssd/(base|extras|heads)"


def test_module_ops_counts_only_inside_the_modules_runs_and_own_time():
    runs, seconds, by_op = scope_device.module_ops(reduction(),
                                                   "jit_step_fn")
    assert runs == 2 and seconds == pytest.approx(2.0)
    assert by_op["fusion.1"] == pytest.approx(0.6)     # not 0.8, not 1.7
    assert by_op["fusion.2"] == pytest.approx(0.8)     # not 1.3
    # the loop less its body's two turns; the body its own
    assert by_op["while.4"] == pytest.approx(2 * (0.10 - 0.06))
    assert by_op["body.5"] == pytest.approx(2 * 0.06)
    assert sum(by_op.values()) == pytest.approx(2 * 0.95)
    other = scope_device.module_ops(reduction(), "jit_other")
    assert other[0] == 1 and other[2] == {"fusion.1": pytest.approx(0.9)}
    assert scope_device.module_ops(reduction(), "jit_none")[0] == 0


def test_ms_per_run_by_scope_and_pass(stub):
    ctx = context(reduction())
    read = lambda **p: scope_device.read(ctx, dict(P, **p))    # noqa: E731
    assert read(scopes=MODEL, **{"as": "ms_per_run", "pass": "forward"}) \
        == pytest.approx(300.0)
    assert read(scopes=MODEL, **{"as": "ms_per_run", "pass": "backward"}) \
        == pytest.approx(400.0)
    assert read(scopes=MODEL, **{"as": "ms_per_run"}) == pytest.approx(700.0)
    assert read(scopes=r"ssd/loss_\w+", **{"as": "ms_per_run"}) \
        == pytest.approx(100.0)
    assert read(scopes="train/update", **{"as": "ms_per_run"}) \
        == pytest.approx(100.0)
    # the map was asked for once, whatever the number of metrics
    assert stub.asked == ["train/step"]


def test_coverage_and_mfu(stub):
    from benchmarks import flops

    ctx = context(reduction())
    cov = scope_device.read(ctx, dict(P, **{"as": "coverage"}))
    assert cov == pytest.approx(100.0 * (1.9 - 0.1) / 1.9)
    mfu = scope_device.read(ctx, dict(P, scopes=MODEL, flops="ssd_train_step",
                                      **{"as": "mfu"}))
    want = flops.ssd_train_step_flops(300, 2, 21) * 2 / (1.4 * 1 * 1e12)
    assert mfu == pytest.approx(100.0 * want)
    fwd = scope_device.read(ctx, dict(P, scopes=MODEL, flops="ssd_forward",
                                      **{"as": "mfu"}))
    assert fwd == pytest.approx(
        100.0 * 2 * flops.ssd_forward_flops(300, 21) * 2 / (1.4 * 1e12))
    with pytest.raises(KeyError):
        scope_device.read(ctx, dict(P, scopes=MODEL, flops="nothing",
                                    **{"as": "mfu"}))


@pytest.mark.parametrize("missing", [
    "trace", "registry", "registered", "module", "runs", "operations",
    "map"])
def test_none_for_each_missing_piece(missing, stub, monkeypatch):
    ctx = context(reduction())
    params = dict(P, scopes=MODEL, **{"as": "ms_per_run"})
    if missing == "trace":
        ctx["trace"] = None
    elif missing == "registry":
        monkeypatch.setattr(scope_device, "registry", lambda: None)
    elif missing == "registered":
        params["registered"] = "serve/default/"
    elif missing == "module":
        params["program"] = "jit_detect"     # the map is another module's
    elif missing == "runs":
        stub.maps["train/step"] = dict(stub.maps["train/step"],
                                       module="jit_gone")
        params["program"] = "jit_gone"
    elif missing == "operations":
        params["scopes"] = "ssd/detout"
    elif missing == "map":
        stub.maps["train/step"] = None
    assert scope_device.read(ctx, params) is None
    assert scope_device.read(ctx, dict(params, **{"as": "coverage"})) is None \
        or missing == "operations"


def test_the_harness_contract_nothing_to_read_is_none():
    assert scope_device.read({"trace": None, "window": {}, "counters": {},
                              "peaks": None}, dict(P, **{"as": "coverage"})) \
        is None


def test_a_program_without_the_registry_reads_nothing(monkeypatch):
    """The parent of PR 37 has no ``obs.device_scopes``: an ImportError,
    and every metric of this reader is left out of the line."""
    import builtins

    real = builtins.__import__

    def no_scopes(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "analytics_zoo_tpu.obs" and "device_scopes" in fromlist:
            raise ImportError("cannot import name 'device_scopes'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_scopes)
    assert scope_device.registry() is None
    assert scope_device.read(context(reduction()),
                             dict(P, **{"as": "coverage"})) is None


def test_pick_registered_exact_then_by_the_tier_that_answered():
    names = ["serve/default/fp/fixed", "serve/default/int8/fixed",
             "serve/default/int8_topk50/fixed", "serve/lm/bf16/1",
             "serve/lm/bf16/128", "train/step"]
    pick = scope_device.pick_registered
    assert pick(names, "train/step", {}) == "train/step"
    assert pick(names, "serve/lm/bf16/1", {}) == "serve/lm/bf16/1"
    assert pick(names, "serve/default/", {"tiers_answered": {
        "fp": 3, "int8": 120}}) == "serve/default/int8/fixed"
    assert pick(names, "serve/default/", {"tiers_answered": {"fp": 9}}) \
        == "serve/default/fp/fixed"
    assert pick(names, "serve/default/", {}) is None      # which of three?
    assert pick(names, "serve/none/", {"tiers_answered": {"fp": 9}}) is None


def test_the_maps_are_written_beside_the_trace(stub, monkeypatch, tmp_path):
    monkeypatch.setattr(scope_device, "trace_dir", lambda: str(tmp_path))
    scope_device.read(context(reduction()), dict(P, **{"as": "coverage"}))
    assert stub.dumped == [os.path.join(str(tmp_path), "scopes.json")]


def test_trace_dir_is_the_newest_trace_under_the_scratch(monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    assert scope_device.trace_dir() is None
    for cell, stamp in (("a", 1), ("b", 2)):
        d = tmp_path / cell / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(b"")
        os.utime(d / "h.xplane.pb", (stamp, stamp))
    assert scope_device.trace_dir() == str(tmp_path / "b" / "trace")


NEW = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(harness.HERE, "metrics", "*.json"))
    if harness.load_json(p)["reader"] == "scope_device")


def test_seventeen_metrics_read_through_this_reader():
    assert len(NEW) == 17
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(entries)
    by_cell = {}
    for name in NEW:
        for cell in entries[name]["workloads"]:
            by_cell[cell] = by_cell.get(cell, 0) + 1
    assert by_cell == {"ssd300-train-b64": 7, "ssd512-serve-closed-b64": 5,
                       "dots3-ep8-decode-ctx1k-64k": 5,
                       "ax-k1-ep16-decode-ctx1k-40k": 4,
                       "mimo-v25-ep16-decode-ctx1k-64k": 4}


@pytest.mark.parametrize("metric", NEW)
def test_metric_file_names_its_scopes_and_a_metric_its_cells_report(metric):
    from analytics_zoo_tpu.obs.names import SCOPES

    spec = harness.load_json(harness.HERE, "metrics", metric + ".json")
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    params = spec["params"]
    assert entry["source"] == "device_trace"
    assert params["as"] in ("ms_per_run", "coverage", "mfu")
    assert entry["unit"] == ("ms" if params["as"] == "ms_per_run" else "%")
    assert params["program"] in ("jit_step_fn", "jit_detect",
                                 "jit_decode_step")
    for cell in entry["workloads"]:
        assert entry["moves"] in {m["name"] for m in harness.cell_metrics(
            BENCH, "end_to_end", cell)}
    if params["as"] == "coverage":
        return
    import re

    hit = [s for s in SCOPES if re.search(params["scopes"], s)]
    assert hit, params["scopes"]
    assert all(s in spec["what"] or s.split("/")[0] + "/loss_*"
               in spec["what"] for s in hit), (hit, spec["what"])
    if params["as"] == "mfu":
        assert params["flops"] in ("ssd_train_step", "ssd_forward")


def test_scope_table_prints_scopes_layers_heaviest_and_the_mixed_share(
        stub, capsys):
    ctx = context(reduction())
    tab = scope_device.table(ctx, dict(P, **{"as": "coverage"}))
    scope_table.print_program("train/step (jit_step_fn)", tab,
                              stub.declared_scope, ctx["trace"], rest=3)
    out = capsys.readouterr().out
    assert "2 runs, 1000.000 ms a run (operations inside: 950.000)" in out
    rows = {ln.split()[0]: ln.split()[1:] for ln in out.splitlines()
            if ln and ln.split()[0] in ("ssd/base", "ssd/loss_conf",
                                        "train/update", "conv1_1", "conv1_2")}
    assert rows["ssd/base"] == ["300.000", "400.000", "700.000"]
    assert rows["ssd/loss_conf"] == ["100.000", "0.000", "100.000"]
    assert rows["train/update"][2] == "100.000"
    assert rows["conv1_1"] == ["300.000", "0.000"]
    assert rows["conv1_2"] == ["0.000", "400.000"]
    assert "coverage 94.74 %" in out
    # fusion.2 is the one mixed fusion: 0.8 of 1.9 s
    assert "mixed share 42.11 % (1 fusions)" in out
    assert "MIXED ssd/base+train/update" in out
    assert "under no declared scope (1 instructions)" in out
    assert "%copy.6 = f32[8]" in out


def test_scope_table_main_reads_the_two_files(stub, monkeypatch, tmp_path,
                                               capsys):
    (tmp_path / "scopes.json").write_text(json.dumps(
        {"train/step": stub.maps["train/step"]}))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_file", lambda p: reduction())
    assert scope_table.main(["scope_table.py", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== train/step (jit_step_fn): 2 runs" in out
