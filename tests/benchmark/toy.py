"""A toy SSD for the CPU rehearsals: SSD300's head plumbing (six sources,
8,732 priors, 21 classes) on a trunk of three narrow convolutions a
source (deep enough for 8-bit activations to show), once as the flax
module the program runs and once as the plain function the reference
runs."""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import ssd as ref

RES, CLASSES, WIDTH, DEPTH = 300, 21, 8, 3
SHAPES = ref.GEOMETRY[RES]["feature_shapes"]
CELLS = ref.priors_per_cell(RES)


def _source(x, fs):
    """A strided sample of the picture, fs x fs."""
    stride = x.shape[1] // fs
    return x[:, :fs * stride:stride, :fs * stride:stride] / 128.0


class ToyModule(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        locs, confs = [], []
        for i, (fs, k) in enumerate(zip(SHAPES, CELLS)):
            f = _source(x, fs)
            for d in range(DEPTH):
                f = nn.relu(nn.Conv(WIDTH, (3, 3), name=f"trunk_{i}_{d}")(f))
            locs.append(nn.Conv(k * 4, (3, 3), name=f"loc_{i}")(f)
                        .reshape(x.shape[0], -1, 4))
            confs.append(nn.Conv(k * CLASSES, (3, 3), name=f"conf_{i}")(f)
                         .reshape(x.shape[0], -1, CLASSES))
        return jnp.concatenate(locs, 1), jnp.concatenate(confs, 1)


def weights(seed, background_bias=0.0):
    key, tree = ref.seed_key(seed), {}
    for i, k in enumerate(CELLS):
        layers = [(f"trunk_{i}_{d}", WIDTH if d else 3, WIDTH)
                  for d in range(DEPTH)]
        layers += [(f"loc_{i}", WIDTH, k * 4), (f"conf_{i}", WIDTH, k * CLASSES)]
        for j, (name, cin, cout) in enumerate(layers):
            kern = jax.random.normal(jax.random.fold_in(key, 16 * i + j),
                                     (3, 3, cin, cout)) * math.sqrt(
                                         2.0 / (9 * cin))
            bias = jnp.zeros((cout,))
            if name.startswith("conf_"):
                bias = bias + background_bias * (jnp.arange(cout)
                                                 % CLASSES == 0)
            tree[name] = {"kernel": kern, "bias": bias}
    return tree


def net(params, x, mode="f32"):
    locs, confs = [], []
    for i, fs in enumerate(SHAPES):
        c = lambda t, n: ref.conv(t, params[n]["kernel"], params[n]["bias"],
                                  mode=mode)
        f = _source(x.astype(jnp.float32), fs)
        for d in range(DEPTH):
            f = jax.nn.relu(c(f, f"trunk_{i}_{d}"))
        locs.append(c(f, f"loc_{i}").reshape(x.shape[0], -1, 4))
        confs.append(c(f, f"conf_{i}").reshape(x.shape[0], -1, CLASSES))
    return jnp.concatenate(locs, 1), jnp.concatenate(confs, 1)


class Toy:
    module = ToyModule()
    weights = staticmethod(weights)
    net = staticmethod(net)
