"""``benchmarks/flops.py`` against XLA's own count of the float32 forward
(CPU), and the shapes it is built from against the program's model."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import flops
from benchmarks.reference import ssd as ref


@pytest.mark.parametrize("resolution", [300, 512])
def test_forward_flops_match_xla_within_a_few_percent(resolution):
    from analytics_zoo_tpu.models.ssd import SSDVgg

    module = SSDVgg(num_classes=21, resolution=resolution)
    x = jax.ShapeDtypeStruct((1, resolution, resolution, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros(x.shape)))
    cost = jax.jit(module.apply).lower(variables, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = flops.ssd_forward_flops(resolution)
    # XLA adds biases, ReLUs, pools and the normalisation, and leaves out
    # the multiplications by the zero padding at each map's border
    assert 0.96 < mine / cost["flops"] < 1.06, (mine, cost["flops"])


@pytest.mark.parametrize("resolution,priors,gflop", [(300, 8732, 62.7),
                                                     (512, 24564, 180.4)])
def test_published_sizes(resolution, priors, gflop):
    assert flops.n_priors(resolution) == priors
    assert ref.build_priors(resolution).shape == (priors, 4)
    assert flops.ssd_forward_flops(resolution) / 1e9 == pytest.approx(
        gflop, abs=0.1)
    assert flops.ssd_train_step_flops(resolution, 64) == (
        3 * 64 * flops.ssd_forward_flops(resolution))


def test_reference_weights_fit_the_programs_tree():
    from analytics_zoo_tpu.models.ssd import SSDVgg

    for resolution in (300, 512):
        module = SSDVgg(num_classes=21, resolution=resolution)
        want = jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, resolution, resolution, 3))))["params"]
        got = jax.eval_shape(lambda: ref.make_weights(2 ** 31 + 5,
                                                      resolution))
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        assert ([x.shape for x in jax.tree_util.tree_leaves(got)]
                == [x.shape for x in jax.tree_util.tree_leaves(want)])


def test_detection_output_cost_is_bound_by_bytes():
    import json
    import os

    from benchmarks import harness

    cost = flops.detection_output_cost(64, 512)
    peaks = harness.load_peaks("TPU v5 lite")
    assert (cost["bytes"] / peaks["hbm_bytes_per_s"]
            > cost["flops"] / peaks["bf16_flops_per_s"])
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    assert json.load(open(os.path.join(
        harness.HERE, "peaks.json")))["source"].startswith("Google Cloud")
