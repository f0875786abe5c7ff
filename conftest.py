"""Pytest bootstrap: where the tests run.

By default — ``JAX_PLATFORMS`` unset or ``cpu``, which is what tier-1 sets
— the session runs on the CPU with 8 virtual devices, so the multi-chip
sharding logic is exercised without hardware (SURVEY.md §4 "Implication
for the TPU build").  Point the session at a chip instead and nothing
here overrides it::

    JAX_PLATFORMS=tpu python -m pytest tests/test_pallas_*.py -m pallas

runs the ``pallas(device=True)`` twins (compiled Mosaic kernels) that are
skipped on the CPU.
"""

import os

# Must be decided before the first jax import in this process.
if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Tests compile from nothing every run: the package's persistent compile
# cache (analytics_zoo_tpu/__init__.py) would make a run depend on the
# previous one, and every XLA:CPU cache hit logs kilobytes of
# machine-feature warnings into the progress lines tier-1 is parsed from.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "pallas(device): Pallas kernel test, one of two kinds.  Bare = "
        "kernel semantics pinned in interpret mode, at toy geometries "
        "Mosaic need not accept (a 4-step time block): runs on the CPU, "
        "in tier-1.  device=True = the compiled Mosaic kernel at a real "
        "geometry: runs on a TPU (JAX_PLATFORMS=tpu).  Each kind is "
        "skipped on the other's backend.")


def pytest_collection_modifyitems(config, items):
    on_tpu = jax.default_backend() == "tpu"
    skip = pytest.mark.skip(
        reason="pallas: interpret-mode pin, runs on the CPU" if on_tpu else
               "pallas(device=True): compiled-kernel twin, runs on a TPU")
    for item in items:
        m = item.get_closest_marker("pallas")
        if m is not None and bool(m.kwargs.get("device", False)) != on_tpu:
            item.add_marker(skip)
