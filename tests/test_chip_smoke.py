"""The parts of ``chip_smoke.py``'s contract that need no chip.

The smoke itself only passes on a TPU (the driver runs it there); what
tier-1 can pin is that it REFUSES anything else, that its two phase
functions drive the real entry points end to end (at toy width, on the
CPU), and the rules it leans on: where the compile cache lives and which
errors the runtime calls retryable.
"""

import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform='cpu'" in r.stderr and "refusing" in r.stderr
    # no result line: nothing on stdout parses as the smoke's JSON verdict
    assert '"ok"' not in r.stdout


class TestCompileCachePlacement:
    """One site (the package import) and one rule: the environment wins,
    otherwise a fixed path under the checkout."""

    PRINT = ("import analytics_zoo_tpu, jax; "
             "print(jax.config.jax_compilation_cache_dir)")

    def test_unset_means_fixed_path_under_the_checkout(self):
        r = _run(["-c", self.PRINT])
        assert r.stdout.strip() == os.path.join(REPO, ".jax_cache"), r.stderr

    def test_environment_places_it_and_code_sets_nothing(self, tmp_path):
        where = str(tmp_path / "elsewhere")
        r = _run(["-c", self.PRINT], JAX_COMPILATION_CACHE_DIR=where)
        assert r.stdout.strip() == where, r.stderr


def test_runtime_errors_are_retryable():
    """jax 0.9.0 has the runtime error at ``jax.errors.JaxRuntimeError``;
    a lookup that silently fails would drop it from the retry filter."""
    from analytics_zoo_tpu.resilience.errors import (is_retryable,
                                                     retryable_errors)

    assert jax.errors.JaxRuntimeError in retryable_errors()
    assert is_retryable(jax.errors.JaxRuntimeError("lost device"))


class ToySSD(nn.Module):
    """SSD300's head plumbing (six sources, 8,732 priors, 21 classes) on a
    one-conv trunk: everything downstream of the model — MultiBoxLoss,
    DetectionOutput, the serving tiers — runs at its real geometry."""

    num_classes: int = 21

    @nn.compact
    def __call__(self, x, train: bool = False):
        from analytics_zoo_tpu.models.ssd import (num_priors_per_cell,
                                                  ssd300_config)

        cfg = ssd300_config()
        B = x.shape[0]
        locs, confs = [], []
        for i, (fs, k) in enumerate(zip(cfg.feature_shapes,
                                        num_priors_per_cell(cfg))):
            f = jax.image.resize(x, (B, fs, fs, 3), "linear") / 128.0
            f = nn.relu(nn.Conv(8, (3, 3), name=f"trunk_{i}")(f))
            locs.append(nn.Conv(k * 4, (3, 3), name=f"loc_{i}")(f)
                        .reshape(B, -1, 4))
            confs.append(nn.Conv(k * self.num_classes, (3, 3),
                                 name=f"conf_{i}")(f)
                         .reshape(B, -1, self.num_classes))
        return jnp.concatenate(locs, 1), jnp.concatenate(confs, 1)


def test_phases_run_at_toy_width_on_cpu(tmp_path, capsys):
    """train_phase -> serve_phase exactly as ``main`` chains them: shards
    from a seed, ``load_train_set_device``, ``train_ssd`` over the mesh,
    losses read back from the summary, then a one-replica
    ``ServingRuntime`` warmed, fed and drained."""
    import chip_smoke
    from analytics_zoo_tpu.core.module import Model

    model = Model(ToySSD())
    model.build(0, jnp.zeros((1, 300, 300, 3)))
    # 0.03 suits the toy trunk: 18.3 -> 15.9 over 5 steps, against
    # +-0.4 from the unseeded augmentation draws
    trained = chip_smoke.train_phase(str(tmp_path), batch=2, steps=5,
                                     model=model, learning_rate=0.03)
    assert len(trained["losses"]) == 5
    assert trained["losses"][-1] < trained["losses"][0]
    served = chip_smoke.serve_phase(trained["model"], max_batch=2,
                                    n_requests=4, deadline_s=60.0)
    # off-TPU "auto" is the XLA path, never an interpreted kernel
    assert served == {"backend": "xla", "answered": 4}
    out = capsys.readouterr().out
    assert "wedges=0" in out and "shed=0" in out and "failovers=0" in out


def test_a_failing_phase_is_not_swallowed(tmp_path):
    """No try/except around a phase: a run whose last loss is not below
    its first (here, a run of one step) raises out of ``train_phase``."""
    import chip_smoke
    from analytics_zoo_tpu.core.module import Model

    model = Model(ToySSD())
    model.build(0, jnp.zeros((1, 300, 300, 3)))
    with pytest.raises(RuntimeError, match="loss did not go down"):
        chip_smoke.train_phase(str(tmp_path), batch=2, steps=1, model=model)
