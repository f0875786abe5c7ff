"""The benchmark's reading of the program's stages: self time and the
device's idle time under a stage on planes built from tuples, the three
readers on a synthetic ring and trace, and every metric file against
``BENCHMARK.json``."""

import collections
import glob
import importlib
import os

import pytest

from analytics_zoo_tpu.obs import span
from benchmarks import harness, program_spans, trace_reduce
from benchmarks.readers import idle_unattributed, stage_ms, worker_busy

E = collections.namedtuple("E", "name start_ns duration_ns")
L = collections.namedtuple("L", "name events")
P = collections.namedtuple("P", "name lines")
MS = 1_000_000


def planes():
    """Two batches of a 10 ms program, 30 ms apart.  The main thread is
    inside ``az/serve/pump`` 8..52 ms: collate 10..20, forward 20..50 with
    h2d 22..30 and result_wait 30..48 inside it.  A second thread sits in
    ``az/input/next`` 5..45 ms, overlapping all of it; the benchmark's own
    ``bench/pump`` and a runtime event share the main line."""
    ops = [E("fusion.1", 0, 10 * MS), E("fusion.1", 40 * MS, 10 * MS)]
    main = [E("bench/pump", 7 * MS, 46 * MS),
            E("az/serve/pump", 8 * MS, 44 * MS),
            E("az/serve/collate", 10 * MS, 10 * MS),
            E("az/serve/forward", 20 * MS, 30 * MS),
            E("az/serve/h2d", 22 * MS, 8 * MS),
            E("az/serve/result_wait", 30 * MS, 18 * MS),
            E("PjitFunction(detect)", 30 * MS, 1 * MS)]
    other = [E("az/input/next", 5 * MS, 40 * MS)]
    return [P("/device:TPU:0", [L("XLA Ops", ops), L("XLA Modules", [])]),
            P("/host:CPU", [L("main", main), L("prefetch", other),
                            L("runtime", [E("Transfer", 0, 5 * MS)])]),
            P("/host:metadata", [])]


def total(pieces, leaves_only=False):
    out = collections.Counter()
    for name, start, end, leaf in pieces:
        if leaf or not leaves_only:
            out[name] += end - start
    return out


def test_lines_keep_threads_apart_and_only_the_programs_stages():
    lines = program_spans.lines_of_planes(planes())
    assert [len(line) for line in lines] == [5, 1]
    assert program_spans.line_of(lines, "az/serve/pump") is lines[0]
    assert program_spans.line_of(lines, "az/input/next") is lines[1]
    assert program_spans.line_of(lines, "az/train/dispatch") is None


def test_self_time_is_the_interval_less_the_children_on_the_line():
    main, _ = program_spans.lines_of_planes(planes())
    pieces = program_spans.self_pieces(main)
    own = total(pieces)
    assert own["az/serve/pump"] == pytest.approx(0.004)     # 8-10, 50-52
    assert own["az/serve/collate"] == pytest.approx(0.010)
    assert own["az/serve/forward"] == pytest.approx(0.004)  # 20-22, 48-50
    assert own["az/serve/h2d"] == pytest.approx(0.008)
    assert own["az/serve/result_wait"] == pytest.approx(0.018)
    assert sum(own.values()) == pytest.approx(0.044)
    assert set(total(pieces, leaves_only=True)) == {
        "az/serve/collate", "az/serve/h2d", "az/serve/result_wait"}
    # pieces of one line are disjoint and in order
    assert all(a[2] <= b[1] + 1e-12 for a, b in zip(pieces, pieces[1:]))


def test_idle_under_a_stage_counts_the_gap_once_a_line():
    red = trace_reduce.reduce_planes(planes())
    gaps = program_spans.device_gaps(red)
    assert gaps == [(pytest.approx(0.010), pytest.approx(0.040))]
    main, other = program_spans.lines_of_planes(planes())
    under = program_spans.overlap_by_name(program_spans.self_pieces(main),
                                          gaps)
    assert under == {"az/serve/collate": pytest.approx(0.010),
                     "az/serve/forward": pytest.approx(0.002),
                     "az/serve/h2d": pytest.approx(0.008),
                     "az/serve/result_wait": pytest.approx(0.010)}
    assert sum(under.values()) == pytest.approx(0.030)
    # the other thread's stage overlaps the same gap, on its own line
    assert program_spans.overlap_by_name(
        program_spans.self_pieces(other), gaps) == {
            "az/input/next": pytest.approx(0.030)}


def test_idle_unattributed_reads_what_no_leaf_on_the_driving_line_covers(
        monkeypatch):
    red = trace_reduce.reduce_planes(planes())
    monkeypatch.setattr(program_spans, "traced_lines",
                        lambda ctx: program_spans.lines_of_planes(planes()))
    ctx = {"trace": red, "window": {}}
    # 30 ms idle; leaves cover 28 (forward's own 2 ms are no leaf's)
    assert idle_unattributed.read(
        ctx, {"line_of": "az/serve/pump"}) == pytest.approx(100 * 2 / 30)
    assert idle_unattributed.read(
        ctx, {"line_of": "az/input/next"}) == pytest.approx(0.0)
    assert idle_unattributed.read(ctx, {"line_of": "az/train/dispatch"}) is None
    # a trace of a program without stages: nothing to read
    monkeypatch.setattr(program_spans, "traced_lines", lambda ctx: None)
    assert idle_unattributed.read(ctx, {"line_of": "az/serve/pump"}) is None


@pytest.fixture
def ring():
    """A synthetic window in the program's ring, far in the future of the
    monotonic clock so nothing real falls into it (and taken out again
    afterwards): two pumps of two batches each, two workers, and of the
    time before it two pool starts and the two workers of the older."""
    t_open = 1e9
    for k in range(2):
        t = t_open + k
        span.record_stage("az/serve/pump", t, t + 0.600)
        for b in range(2):
            u = t + 0.3 * b
            span.record_stage("az/serve/collate", u, u + 0.040 + 0.01 * k)
            span.record_stage("az/serve/forward", u + 0.05, u + 0.28)
            span.record_stage("az/serve/h2d", u + 0.05, u + 0.10)
    span.record_stage("az/serve/collate", t_open - 5, t_open - 4)   # set-up
    span.record_stage("az/input/pool_start", t_open - 9, t_open - 8.5)
    span.record_stage("az/input/pool_start", t_open - 3, t_open - 2.0)
    for w in range(2):
        span.record_stage("az/input/worker", t_open - 9, t_open - 4,
                          worker=w, chain_s=5.0, put_s=0.0, walk_s=0.0,
                          groups=4, spills=0)
    for w in range(2):
        span.record_stage("az/input/worker", t_open, t_open + 4.0,
                          worker=w, chain_s=3.0 - w, put_s=0.1, walk_s=0.2,
                          groups=4, spills=0)
    yield {"window": {"t_open": t_open}, "trace": None, "counters": {}}
    real = [r for r in span._STAGES if r.t0 < t_open - 10]
    span._STAGES.clear()            # later tests read the ring by time
    span._STAGES.extend(real)


def test_stage_ms_median_and_mean_less_other_stages_a_batch(ring):
    assert stage_ms.read(ring, {"span": "az/serve/collate",
                                "stat": "median"}) == pytest.approx(45.0)
    assert stage_ms.read(ring, {"span": "az/serve/pump",
                                "stat": "mean"}) == pytest.approx(600.0)
    # (2 x 600 - 4 x 50 - (40 + 40 + 50 + 50)) / 4 batches
    assert stage_ms.read(ring, {
        "span": "az/serve/pump", "stat": "mean", "per": "az/serve/forward",
        "minus": ["az/serve/h2d", "az/serve/collate"]}) == pytest.approx(205.0)
    assert stage_ms.read(ring, {"span": "az/serve/handout",
                                "stat": "median"}) is None
    with pytest.raises(KeyError):
        stage_ms.read(ring, {"span": "az/serve/pump", "stat": "p99"})


def test_worker_busy_is_chain_seconds_over_lifetimes(ring):
    assert worker_busy.read(ring, {}) == pytest.approx(100 * 5.0 / 8.0)
    later = {"window": {"t_open": 1e12}}
    assert stage_ms.read(later, {"span": "az/serve/pump",
                                 "stat": "mean"}) is None


def test_a_window_inside_one_epoch_reads_the_pool_that_feeds_it(ring):
    """A traced run's window is 20 steps of an epoch of 32: no pool starts
    or closes in it.  The once-an-epoch readings come from the pool that
    was started last before it and whose workers live into it."""
    pool_start = {"span": "az/input/pool_start", "stat": "mean"}
    assert stage_ms.read(ring, pool_start) is None
    pool_start["reach_back"] = True
    assert stage_ms.read(ring, pool_start) == pytest.approx(1000.0)
    # a pool started inside the window counts beside the one before it
    t_open = ring["window"]["t_open"]
    span.record_stage("az/input/pool_start", t_open + 1, t_open + 1.5)
    assert stage_ms.read(ring, pool_start) == pytest.approx(750.0)
    # the workers of the pool started last before the window count even
    # though they were done before it opened; an older pool's stay out
    inside = {"window": {"t_open": t_open + 0.5}}
    assert worker_busy.read(inside, {}) == pytest.approx(100 * 5.0 / 8.0)
    for w in range(2):
        span.record_stage("az/input/worker", t_open - 2.9, t_open - 0.9,
                          worker=w, chain_s=1.0, put_s=0.0, walk_s=0.0,
                          groups=4, spills=0)
    assert worker_busy.read(ring, {}) == pytest.approx(100 * 7.0 / 12.0)
    # a program without stages (the parent): nothing, and no error
    assert stage_ms.read({"window": {}}, pool_start) is None
    assert worker_busy.read({"window": {}}, {}) is None


def test_span_table_prints_a_row_a_stage_with_its_idle_time(ring):
    from benchmarks import span_table

    red = trace_reduce.reduce_planes(planes())
    text = span_table.table(program_spans.lines_of_planes(planes()),
                            program_spans.device_gaps(red))
    rows = {r.split()[0]: r.split()[1:] for r in text.splitlines()[1:-1]}
    assert rows["az/serve/forward"] == ["1", "30.000", "30.000", "4.000",
                                        "2.000"]
    assert text.splitlines()[-1] == (
        "device idle 30.000 ms; under no stage of the thread whose first "
        "stage is az/serve/pump: 0.000 ms, az/input/next: 0.000 ms")
    from analytics_zoo_tpu.obs import stages

    text = span_table.ring_table(
        [list(r) for r in stages(since=ring["window"]["t_open"])])
    assert "az/serve/pump" in text and "az/input/worker: 2 records" in text


METRIC_FILES = sorted(glob.glob(os.path.join(harness.HERE, "metrics",
                                             "*.json")))


@pytest.mark.parametrize("path", METRIC_FILES, ids=os.path.basename)
def test_metric_file_is_an_entry_and_names_a_reader_that_exists(path):
    name = os.path.basename(path)[:-len(".json")]
    spec = harness.load_json(path)
    entry = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}[name]
    assert (spec["layer"], spec["moves"]) == (entry["layer"], entry["moves"])
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    if spec["reader"] == "stage_ms":
        from analytics_zoo_tpu.obs.names import STAGES

        p = spec["params"]
        assert {p["span"], p.get("per", p["span"]),
                *p.get("minus", ())} <= set(STAGES)
        assert entry["source"] == "program_span"
