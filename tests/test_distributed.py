"""Multi-process ``jax.distributed`` smoke test (2 CPU processes).

The reference delegates multi-node behavior to Spark and never tests it
(SURVEY.md §4); here the multi-host claims of ``utils.engine.init`` and
``parallel.mesh.local_data_slice`` are exercised for real: two spawned
processes form a distributed JAX runtime, build a global mesh over both
processes' devices, and run a psum across the process boundary.
"""

import os
import socket
import subprocess
import sys

import pytest

#: minimal 2-process capability probe: some jaxlib CPU backends register
#: the distributed runtime but cannot EXECUTE cross-process computations
#: ("Multiprocess computations aren't implemented on the CPU backend").
#: That is an environment limit, not a framework bug — the tests below
#: must SKIP with a clear reason there, not fail tier-1.
_PROBE_CHILD = r"""
import os, sys
import numpy as np

sys.path.insert(0, os.environ["AZ_REPO"])

from analytics_zoo_tpu.utils import engine

pid = int(os.environ["AZ_PROC_ID"])
engine.init(engine.EngineConfig(
    coordinator_address=os.environ["AZ_COORD"],
    num_processes=2, process_id=pid))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel import mesh as mesh_lib

mesh = mesh_lib.create_mesh()
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")),
    np.ones((2, 1), np.float32), (4, 1))
val = float(jax.jit(jnp.sum)(garr))
assert val == 4.0, val
print("MULTIPROC_PROBE_OK")
"""

_probe_cache = None


def _multiprocess_cpu_support():
    """(supported, reason) — cached per session.  Spawns two 1-device
    CPU processes and runs one cross-process reduction; a backend that
    cannot execute multiprocess computations yields the skip reason."""
    global _probe_cache
    if _probe_cache is not None:
        return _probe_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["AZ_REPO"] = repo
        env["AZ_COORD"] = f"localhost:{port}"
        env["AZ_PROC_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PROBE_CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out = "(probe timed out)"
        outs.append(out)
    joined = "\n".join(outs)
    if all(p.returncode == 0 for p in procs) \
            and joined.count("MULTIPROC_PROBE_OK") == 2:
        _probe_cache = (True, "")
    elif "aren't implemented on the CPU backend" in joined:
        _probe_cache = (False,
                        "this jaxlib's CPU backend cannot execute "
                        "multiprocess computations (probe: 'Multiprocess "
                        "computations aren't implemented on the CPU "
                        "backend') — multi-host coverage needs a "
                        "collectives-capable backend")
    else:
        # an UNRECOGNIZED probe failure must not silently skip the
        # suite: let the real tests run and show the real error
        _probe_cache = (True, "")
    return _probe_cache


def _require_multiprocess_cpu():
    supported, reason = _multiprocess_cpu_support()
    if not supported:
        pytest.skip(reason)


_CHILD = r"""
import os, sys
import numpy as np

sys.path.insert(0, os.environ["AZ_REPO"])

from analytics_zoo_tpu.utils import engine

pid = int(os.environ["AZ_PROC_ID"])
engine.init(engine.EngineConfig(
    coordinator_address=os.environ["AZ_COORD"],
    num_processes=2, process_id=pid))

import jax
import jax.numpy as jnp

assert jax.process_count() == 2, jax.process_count()
assert jax.process_index() == pid
# 2 local virtual CPU devices per process -> 4 global
assert jax.local_device_count() == 2, jax.local_device_count()
assert jax.device_count() == 4, jax.device_count()

assert engine.node_number() == 2
assert engine.core_number() == 2
assert engine.local_batch(8) == 4

from analytics_zoo_tpu.parallel import mesh as mesh_lib

start, size = mesh_lib.local_data_slice(8, None)
assert (start, size) == (4 * pid, 4), (start, size)

# cross-process collective: global mesh over all 4 devices, psum of ones
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = mesh_lib.create_mesh()
assert mesh.devices.size == 4

local = np.full((4, 2), 1.0, np.float32)  # this host's batch shard
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), local, (8, 2))

@jax.jit
def total(x):
    return jnp.sum(x)

val = float(total(garr))
assert val == 16.0, val
print(f"proc {pid} OK: {jax.process_count()} processes, "
      f"{jax.device_count()} devices, psum={val}")
"""


_TRAIN_CHILD = r"""
import os, sys
import numpy as np

sys.path.insert(0, os.environ["AZ_REPO"])

from analytics_zoo_tpu.utils import engine

pid = int(os.environ["AZ_PROC_ID"])
engine.init(engine.EngineConfig(
    coordinator_address=os.environ["AZ_COORD"],
    num_processes=2, process_id=pid))

import jax
import jax.numpy as jnp

assert jax.process_count() == 2

from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.models.simple import FraudMLP
from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger
from analytics_zoo_tpu.parallel import mesh as mesh_lib

mesh = mesh_lib.create_mesh()              # global: 2 procs x 2 devices
assert mesh.devices.size == 4
assert mesh_lib.spans_processes(mesh)

# deterministic dataset, identical on both processes; each feeds ONLY its
# local_data_slice of every global batch (per-host input sharding)
rng = np.random.RandomState(0)
x = rng.randn(64, 29).astype(np.float32)
y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
GLOBAL_BATCH = 16
start, size = mesh_lib.local_data_slice(GLOBAL_BATCH, mesh)
assert (start, size) == (8 * pid, 8)
batches = [{"input": x[i:i + GLOBAL_BATCH][start:start + size],
            "target": y[i:i + GLOBAL_BATCH][start:start + size]}
           for i in range(0, 64, GLOBAL_BATCH)]

model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
model.build(0, jnp.zeros((1, 29), jnp.float32))

ckpt_dir = os.environ["AZ_CKPT"]
opt = (Optimizer(model, batches, ClassNLLCriterion(), mesh=mesh)
       .set_optim_method(SGD(0.1, momentum=0.9))
       .set_end_when(Trigger.max_epoch(5))
       .set_checkpoint(ckpt_dir, Trigger.every_epoch()))
opt.optimize()

steps = int(np.asarray(opt._last_state.step))
assert steps == 20, steps
fp = float(sum(np.abs(np.asarray(l)).sum()
               for l in jax.tree_util.tree_leaves(
                   jax.device_get(opt._last_state.params))))
print(f"proc {pid} TRAINED steps={steps} fingerprint={fp:.8f}")
if pid == 0:
    assert os.path.exists(os.path.join(ckpt_dir, "latest")), "no checkpoint"
    # loop position rides in the snapshot's own manifest now
    from analytics_zoo_tpu.parallel import checkpoint as _ckpt
    man = _ckpt.verify_snapshot(os.path.join(ckpt_dir, "latest"))
    assert man["meta"]["iteration"] == 20, man["meta"]
    print("proc 0 CKPT_OK")
"""


_ELASTIC_CHILD = r"""
import os, sys
import numpy as np

sys.path.insert(0, os.environ["AZ_REPO"])

from analytics_zoo_tpu.utils import engine

pid = int(os.environ["AZ_PROC_ID"])
nproc = int(os.environ["AZ_NPROC"])
epochs = int(os.environ["AZ_EPOCHS"])
engine.init(engine.EngineConfig(
    coordinator_address=os.environ["AZ_COORD"],
    num_processes=nproc, process_id=pid))

import jax
import jax.numpy as jnp

assert jax.process_count() == nproc
assert jax.device_count() == 8      # topology changes, world size doesn't

from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.models.simple import FraudMLP
from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger
from analytics_zoo_tpu.parallel import mesh as mesh_lib

mesh = mesh_lib.create_mesh()
assert mesh.devices.size == 8

rng = np.random.RandomState(0)
x = rng.randn(64, 29).astype(np.float32)
y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
GLOBAL_BATCH = 16
start, size = mesh_lib.local_data_slice(GLOBAL_BATCH, mesh)
batches = [{"input": x[i:i + GLOBAL_BATCH][start:start + size],
            "target": y[i:i + GLOBAL_BATCH][start:start + size]}
           for i in range(0, 64, GLOBAL_BATCH)]

model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
model.build(0, jnp.zeros((1, 29), jnp.float32))

opt = (Optimizer(model, batches, ClassNLLCriterion(), mesh=mesh)
       .set_optim_method(SGD(0.1, momentum=0.9))
       .set_end_when(Trigger.max_epoch(epochs))
       .set_checkpoint(os.environ["AZ_CKPT"], Trigger.every_epoch()))
if os.environ.get("AZ_RESUME") == "1":
    opt.set_resume()
opt.optimize()

steps = int(np.asarray(opt._last_state.step))
fp = float(sum(np.abs(np.asarray(l)).sum()
               for l in jax.tree_util.tree_leaves(
                   jax.device_get(opt._last_state.params))))
print(f"proc {pid} TRAINED steps={steps} fingerprint={fp:.8f}")
"""


def _spawn_world(nproc, local_devices, epochs, ckpt, repo, resume=False):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={local_devices}")
        env["AZ_REPO"] = repo
        env["AZ_COORD"] = f"localhost:{port}"
        env["AZ_PROC_ID"] = str(pid)
        env["AZ_NPROC"] = str(nproc)
        env["AZ_EPOCHS"] = str(epochs)
        env["AZ_CKPT"] = ckpt
        env["AZ_RESUME"] = "1" if resume else "0"
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _ELASTIC_CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid}/{nproc} failed:\n{out}"
    return outs


def test_four_process_train_then_elastic_resume_as_two(tmp_path):
    """VERDICT r3 item 7 — elastic + multi-host COMPOSED: train 4 procs ×
    2 devices through ``Optimizer.optimize()`` to epoch 3 (checkpoint
    every epoch), world ends, resume the SAME checkpoint as 2 procs × 4
    devices to epoch 6; final parameters must match a single-process
    8-device run of all 6 epochs (repartitioning is a layout change, not
    a math change)."""
    _require_multiprocess_cpu()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckpt = str(tmp_path / "ckpt")

    outs_a = _spawn_world(4, 2, epochs=3, ckpt=ckpt, repo=repo)
    for pid, out in enumerate(outs_a):
        assert f"proc {pid} TRAINED steps=12" in out, out

    outs_b = _spawn_world(2, 4, epochs=6, ckpt=ckpt, repo=repo, resume=True)
    fps = []
    for pid, out in enumerate(outs_b):
        # 12 resumed + 12 new
        assert f"proc {pid} TRAINED steps=24" in out, out
        fps.append(float(out.split("fingerprint=")[1].split()[0]))
    assert fps[0] == fps[1], fps

    # single-process reference: all 6 epochs, same global batches
    import numpy as np

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models.simple import FraudMLP
    from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger, create_mesh

    rng = np.random.RandomState(0)
    x = rng.randn(64, 29).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
    batches = [{"input": x[i:i + 16], "target": y[i:i + 16]}
               for i in range(0, 64, 16)]
    model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
    model.build(0, jnp.zeros((1, 29), jnp.float32))
    opt = (Optimizer(model, batches, ClassNLLCriterion(),
                     mesh=create_mesh((8,), axis_names=("data",)))
           .set_optim_method(SGD(0.1, momentum=0.9))
           .set_end_when(Trigger.max_epoch(6)))
    opt.optimize()
    fp_ref = float(sum(np.abs(np.asarray(l)).sum()
                       for l in jax.tree_util.tree_leaves(
                           jax.device_get(opt._last_state.params))))
    np.testing.assert_allclose(fps[0], fp_ref, rtol=2e-5)


def test_two_process_distributed_init(tmp_path):
    _require_multiprocess_cpu()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    procs = []
    for pid in range(2):
        env = dict(os.environ)
        # fresh jax in each child: 2 virtual CPU devices
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["AZ_REPO"] = repo
        env["AZ_COORD"] = f"localhost:{port}"
        env["AZ_PROC_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} OK" in out, out


def test_two_process_optimizer_matches_single_process(tmp_path):
    """DistriOptimizer parity (SURVEY.md §2.7): ``Optimizer.optimize()``
    actually TRAINS across a process boundary — 2 processes × 2 virtual
    devices, per-host input shards via ``local_data_slice``, 20 SGD
    steps on the fraud MLP, checkpoint written by process 0 only — and
    the final parameters match a single-process run on the same global
    batches to float tolerance (data-parallel partitioning is a layout
    change, not a math change)."""
    _require_multiprocess_cpu()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    ckpt = str(tmp_path / "ckpt")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["AZ_REPO"] = repo
        env["AZ_COORD"] = f"localhost:{port}"
        env["AZ_PROC_ID"] = str(pid)
        env["AZ_CKPT"] = ckpt
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _TRAIN_CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    fps = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} TRAINED steps=20" in out, out
        fps.append(float(out.split("fingerprint=")[1].split()[0]))
    assert "CKPT_OK" in outs[0]
    assert fps[0] == fps[1], fps   # replicated params: identical view

    # single-process reference on the SAME global batches (this pytest
    # process has the 8-device virtual mesh from conftest.py)
    import numpy as np

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.core.criterion import ClassNLLCriterion
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.models.simple import FraudMLP
    from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger, create_mesh

    rng = np.random.RandomState(0)
    x = rng.randn(64, 29).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
    batches = [{"input": x[i:i + 16], "target": y[i:i + 16]}
               for i in range(0, 64, 16)]
    model = Model(FraudMLP(in_features=29, hidden=10, n_classes=2))
    model.build(0, jnp.zeros((1, 29), jnp.float32))
    opt = (Optimizer(model, batches, ClassNLLCriterion(),
                     mesh=create_mesh((4,), axis_names=("data",),
                                      devices=jax.devices()[:4]))
           .set_optim_method(SGD(0.1, momentum=0.9))
           .set_end_when(Trigger.max_epoch(5)))
    opt.optimize()
    fp_ref = float(sum(np.abs(np.asarray(l)).sum()
                       for l in jax.tree_util.tree_leaves(
                           jax.device_get(opt._last_state.params))))
    np.testing.assert_allclose(fps[0], fp_ref, rtol=2e-5)
