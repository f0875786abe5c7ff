"""Both drivers end to end at a toy trunk on the CPU: the result line's
keys, the control that ``correct`` has to fail, and a run with the timed
path broken underneath for each fault a cell can have."""

import json
import time

import numpy as np
import pytest

import toy
from benchmarks import harness
from benchmarks.drivers import ssd_serve, ssd_train

SEED = 2 ** 31 + 9
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
             "check_s", "checks"]
BENCH = {
    "end_to_end": [
        {"name": "train_throughput", "unit": "samples/s",
         "workloads": ["train"]},
        {"name": "serve_throughput", "unit": "requests/s",
         "workloads": ["serve"]},
        {"name": "serve_latency_p95", "unit": "ms", "workloads": ["serve"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}
# limits at the toy's size, from CPU readings at this seed under the test
# session's 8 virtual devices (program / control): loss_gap 1e-4 / 6e-4,
# update_norm_gap 0.07 / 0.09, grad_diff_median 0.0057 / 0.0142; box_gap
# 0.0011 / 0.0037, score_gap 0.003 / 0.002, set_miss 0.012 / 0.028
TOY = {
    "train": (ssd_train, "ssd300-vgg16", "train-shapes-b64",
              dict(global_batch=4, images=16, shards=2, warm_steps=2,
                   worker_processes=1, reference_block=2,
                   limits={"loss_gap": 0.003, "update_norm_gap": 0.2,
                           "grad_diff_median": 0.0095})),
    "serve": (ssd_serve, "ssd512-vgg16", "closed-128",
              dict(callers=8, max_batch=4, queue_capacity=8, pictures=6,
                   check_requests=6, reference_block=2,
                   limits={"box_gap": 0.002, "score_gap": 0.006,
                           "set_miss": 0.05})),
}


def drive(kind, sabotage=None, seconds=1.0):
    module, config, mix, over = TOY[kind]
    config = harness.load_json(harness.HERE, "configs", config + ".json")
    config.update(resolution=300, num_priors=8732)
    traffic = dict(harness.load_json(harness.HERE, "traffic", mix + ".json"),
                   **over)
    resolved = {"cell": {"name": kind, "chips": 1}, "config": config,
                "traffic": traffic, "driver": module}
    kept = {}

    def prepare(driver):
        kept["driver"] = driver
        driver.sabotage = sabotage

    line = harness.drive(resolved, BENCH, SEED, seconds, False,
                         time.monotonic(), harness.describe_device(),
                         {"toy": toy.Toy}, prepare)
    return line, kept["driver"]


@pytest.fixture(scope="module")
def sound():
    """One sound run of each driver, shared by the tests that read it."""
    runs = {}

    def get(kind):
        if kind not in runs:
            runs[kind] = drive(kind)
        return runs[kind]

    return get


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_result_line_has_the_contracts_keys(kind, sound, capsys):
    line, _ = sound(kind)
    assert list(line) == LINE_KEYS          # check_s is the harness's own
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", kind)}
    assert set(line["metrics"]) == want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    harness.print_line(line)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["checks"] == line["checks"]
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_the_control_comes_out_not_correct(kind, sound):
    """The reference put in the program's place one precision down fails a
    number that the program passes with room; in the stated precision it
    passes.  (The factor of three between the two readings is shown at
    the cell's own size, on the chip: PERF.md.  This toy's first layer
    reads 8-bit pictures, which 8-bit activations keep exactly.)"""
    line, driver = sound(kind)
    readings = driver.control_readings()
    limits = {k: c["limit"] for k, c in line["checks"].items()}
    control = readings["control_int8"]
    failed = [k for k in limits if control[k] > limits[k]]
    assert failed, control
    assert any(control[k] >= 1.5 * line["checks"][k]["value"] for k in failed)
    stated = readings["reference_bf16"]
    assert all(stated[k] <= limits[k] for k in limits), stated
    if kind == "train":
        half = readings["fault_half_batch"]
        assert any(half[k] > limits[k] for k in limits), half


def state_unchanged(driver):
    """A step that returns its parameters unchanged."""
    from analytics_zoo_tpu.parallel import SGD

    o = driver.config["optimizer"]
    driver.opt.set_optim_method(SGD(0.0, momentum=o["momentum"],
                                    weight_decay=o["weight_decay"]))


def half_batch_left_out(driver):
    """Half of the batch left out, the mean taken over the rest."""
    whole = driver.opt.criterion

    def half(output, batch):
        h = output[0].shape[0] // 2
        return whole(tuple(o[:h] for o in output),
                     {k: v[:h] for k, v in batch["target"].items()})

    driver.opt.criterion = half


def score_altered(rows):
    rows = np.array(rows)
    rows[..., 1] = np.where(rows[..., 0] >= 0, rows[..., 1] + 0.01, 0.0)
    return rows


def answers_swapped(rows):
    """Each request gets its neighbour's answer."""
    return np.roll(np.asarray(rows), 1, axis=0)


@pytest.mark.parametrize("kind,fault", [
    ("train", state_unchanged), ("train", half_batch_left_out),
    ("serve", score_altered), ("serve", answers_swapped)],
    ids=lambda v: getattr(v, "__name__", v))
def test_a_broken_timed_path_is_not_correct(kind, fault):
    line, _ = drive(kind, sabotage=fault)
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
