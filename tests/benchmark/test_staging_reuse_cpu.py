"""The serve cell's window at the toy trunk on the CPU, through its ring
readers: the batcher's staging buffer is allocated by ``warm()`` and kept,
so ``staging_reuse.serve`` reads 100; and what ``stage_attr_share`` reads
from records that lack the attribute."""

import time

import pytest

import toy
from analytics_zoo_tpu.obs import span
from benchmarks import harness
from benchmarks.drivers import ssd_serve
from benchmarks.readers import stage_attr_share

CELL = "ssd512-serve-closed-b64"
PARAMS = {"span": "az/serve/collate", "attr": "reused"}


class Slice:
    """The harness's tracer without a profiler: a traced run's window
    lasts until the driver has stopped its slice."""

    running = False

    def start(self):
        self.running = True

    def stop(self):
        self.running = False


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    config = harness.load_json(harness.HERE, "configs", "ssd512-vgg16.json")
    config.update(resolution=toy.RES, num_priors=8732)
    traffic = dict(
        harness.load_json(harness.HERE, "traffic", "closed-128.json"),
        callers=8, max_batch=4, queue_capacity=8, pictures=6)
    driver = ssd_serve.Driver(config, traffic, 2 ** 31 + 27,
                              str(tmp_path_factory.mktemp("serve")),
                              toy=toy.Toy)
    driver.setup()
    allocs = driver.runtime.metrics.registry.counter("serve/staging_alloc")
    warmed = allocs.value
    window = driver.window(0.5, Slice())
    ctx = {"trace": None, "config": config, "traffic": traffic,
           "window": window, "counters": window["counters"], "peaks": None}
    return ctx, warmed, allocs.value


def test_the_serve_cells_window_reuses_the_staging_buffer_warm_allocated(
        served):
    """``warm()`` collates once a tier before the window opens, so every
    batch of the window is assembled in the buffer that was kept."""
    ctx, warmed, after = served
    assert warmed == 1                  # three tiers warmed, one geometry
    assert ctx["window"]["batches"] >= 2 and after == 1
    got = harness.read_per_layer(harness.load_benchmark(), CELL, ctx)
    assert got["staging_reuse.serve"] == {"value": 100.0, "unit": "%"}
    assert got["collate_ms.serve"]["value"] > 0


def test_one_allocation_among_four_batches_reads_75():
    """Records without the attribute (an older program's) count on
    neither side."""
    later = {"window": {"t_open": time.monotonic()}}
    for attrs in ({"reused": False}, {}, {"reused": True}, {"reused": True},
                  {"reused": True}):
        span.record_stage("az/serve/collate", time.monotonic(),
                          time.monotonic(), **attrs)
    assert stage_attr_share.read(later, PARAMS) == pytest.approx(75.0)


@pytest.mark.parametrize("params", [
    dict(PARAMS, attr="absent"),        # a program without the attribute
    dict(PARAMS, span="az/none"),       # a program without the stage
])
def test_the_reader_finds_nothing_where_the_program_has_nothing(served,
                                                                params):
    """The parent of the PR that brought ``reused`` prints no such metric:
    the reader returns ``None`` and the line leaves it out."""
    assert stage_attr_share.read(served[0], params) is None


def test_the_reader_finds_nothing_without_a_window():
    assert stage_attr_share.read({"window": {}}, PARAMS) is None
