"""The program's stages at the toy trunk on the CPU: what one ``pump()``
leaves in the stage ring, and in a ``jax.profiler`` trace as
``benchmarks/program_spans.py`` reads it; and the train cell's window as
a traced run cuts it, inside one epoch, through the cell's own readers."""

import collections
import time

import jax
import numpy as np
import pytest

import toy
from benchmarks import harness, program_spans, trace_reduce
from benchmarks.drivers import ssd_train

BATCH = 4
PER_BATCH = ["az/serve/collate", "az/serve/forward", "az/serve/h2d",
             "az/serve/dispatch", "az/serve/result_wait", "az/serve/handout"]


@pytest.fixture(scope="module")
def runtime():
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 ssd_serving_tiers)
    from analytics_zoo_tpu.serving import ServingRuntime

    model = Model(toy.Toy.module, {"params": toy.weights(3)})
    tiers = ssd_serving_tiers(
        model, PreProcessParam(batch_size=BATCH, resolution=toy.RES))
    rt = ServingRuntime(tiers, n_replicas=1, max_batch=BATCH,
                        queue_capacity=2 * BATCH, default_deadline_s=600)
    pump(rt, 1)                         # compiles the first tier
    return rt


def pump(rt, batches):
    picture = np.zeros((toy.RES, toy.RES, 3), np.float32)
    requests = [rt.submit({"input": picture})
                for _ in range(batches * BATCH)]
    assert rt.pump() == batches
    assert all(r.state == "done" for r in requests)


def test_a_pump_leaves_one_of_each_stage_a_batch_all_inside_it(runtime):
    from analytics_zoo_tpu import obs

    t0 = time.monotonic()
    pump(runtime, 2)
    records = obs.stages(since=t0)
    got = collections.Counter(r.name for r in records)
    assert got == dict({name: 2 for name in PER_BATCH}, **{"az/serve/pump": 1})
    whole = next(r for r in records if r.name == "az/serve/pump")
    assert all(whole.t0 <= r.t0 and r.t1 <= whole.t1 and
               r.thread == whole.thread for r in records)
    # the three stages of the tier's forward lie inside forward, in order
    by = {name: [r for r in records if r.name == name] for name in PER_BATCH}
    for fwd, h2d, run, wait in zip(by["az/serve/forward"], by["az/serve/h2d"],
                                   by["az/serve/dispatch"],
                                   by["az/serve/result_wait"]):
        assert fwd.t0 <= h2d.t0 <= h2d.t1 <= run.t0 <= run.t1 <= wait.t0
        assert wait.t1 <= fwd.t1
    # self time: pump's own is what its children on the thread leave
    (line,) = program_spans.ring_lines(records)
    own = collections.Counter()
    for name, start, end, leaf in program_spans.self_pieces(line):
        own[name] += end - start
        assert leaf == (name not in ("az/serve/pump", "az/serve/forward"))
    assert sum(own.values()) == pytest.approx(whole.t1 - whole.t0)


def test_a_profiler_trace_round_a_pump_has_the_stages_on_one_host_line(
        runtime, tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        pump(runtime, 1)
    finally:
        jax.profiler.stop_trace()
    lines = program_spans.host_lines(trace_reduce.find_xplane(str(tmp_path)))
    line = program_spans.line_of(lines, "az/serve/pump")
    assert line is not None
    assert sorted(e[0] for e in line) == sorted(PER_BATCH + ["az/serve/pump"])
    collate = next(e for e in line if e[0] == "az/serve/collate")
    assert collate[2] > collate[1]


class Slice:
    """The harness's tracer without a profiler: a traced run's window
    lasts until the driver has stopped its slice, however few seconds
    it was given."""

    running = False

    def start(self):
        self.running = True

    def stop(self):
        self.running = False


def test_a_window_inside_one_epoch_reads_every_ring_metric_of_the_cell(
        tmp_path):
    """The check's traced runs are short: the window is the slice, 20 steps
    of an epoch of 32 on the chip (here 4 of 16), so no pool starts or
    closes in it.  Every metric the cell reads from the ring still has a
    reading — the once-an-epoch ones from the pool that feeds the window."""
    cell = "ssd300-train-b64"
    bench = harness.load_benchmark()
    config = harness.load_json(harness.HERE, "configs", "ssd300-vgg16.json")
    config.update(resolution=toy.RES, num_priors=8732)
    traffic = dict(
        harness.load_json(harness.HERE, "traffic", "train-shapes-b64.json"),
        global_batch=4, images=64, shards=2, warm_steps=2,
        worker_processes=2, trace_after_steps=0, trace_steps=4)
    driver = ssd_train.Driver(config, traffic, 2 ** 31 + 9, str(tmp_path),
                              toy=toy.Toy)
    driver.setup()
    window = driver.window(0.0, Slice())
    assert window["traced_steps"] == 4
    ring = [m["name"] for m in harness.cell_metrics(bench, "per_layer", cell)
            if m["source"] in ("program_span", "program_counter")]
    ring.remove("loader_wait_ms.train")    # the benchmark's own; 0 s here
    assert len(ring) == 6
    ctx = {"trace": None, "config": config, "traffic": traffic,
           "window": window, "counters": window["counters"], "peaks": None}
    assert window["t_open"] > min(
        r.t0 for r in program_spans.ring(ctx, reach_back=True)
        if r.name == "az/input/pool_start")
    assert not [r for r in program_spans.ring(ctx)
                if r.name == "az/input/pool_start"]
    got = harness.read_per_layer(bench, cell, ctx)
    assert set(ring) <= set(got)
    assert 0 < got["worker_busy_share.train"]["value"] <= 100
    assert got["pool_start_ms.train"]["value"] > 0
