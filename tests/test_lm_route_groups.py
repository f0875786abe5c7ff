"""Group-limited sigmoid routing (parallel/expert.py::route_topk_sigmoid
with ``n_group`` / ``topk_group``) against a loop in plain numpy; ties;
one group is today's routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.parallel import route_topk_sigmoid


def numpy_route(s, bias, k, scale, n_group, topk_group):
    """Row by row: group score = its two largest summed, the best groups
    stay (ties to the lower group), of their experts the k largest (ties
    to the lower id), weights the UNBIASED scores over their sum."""
    chosen, weights = [], []
    for row in s:
        biased = row + (0 if bias is None else bias)
        per = len(row) // n_group
        score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[
            :topk_group]
        ids = [e for e in range(len(row)) if e // per in kept]
        pick = sorted(ids, key=lambda e: (-biased[e], e))[:k]
        chosen.append(pick)
        weights.append(scale * row[pick] / row[pick].sum())
    return np.array(chosen), np.array(weights)


@pytest.mark.parametrize("E,n_group,topk_group,k,bias", [
    (192, 8, 4, 8, False), (16, 4, 2, 3, True), (8, 2, 1, 2, False),
    (12, 1, 1, 4, True)])
def test_group_limited_choice_equals_numpy(E, n_group, topk_group, k, bias):
    rng = np.random.RandomState(E)
    x = rng.normal(size=(40, 24)).astype(np.float32)
    w = rng.normal(size=(24, E)).astype(np.float32) / 3
    b = rng.normal(size=E).astype(np.float32) * 0.05 if bias else None
    chosen, wts = route_topk_sigmoid(
        jnp.asarray(x), jnp.asarray(w), None if b is None else jnp.asarray(b),
        k, 2.5, n_group, topk_group)
    s = 1 / (1 + np.exp(-(x @ w)))
    want_c, want_w = numpy_route(s, b, k, 2.5, n_group, topk_group)
    assert np.array_equal(np.asarray(chosen), want_c)
    np.testing.assert_allclose(np.asarray(wts), want_w, rtol=1e-5)
    per = E // n_group
    assert all(len({e // per for e in row}) <= topk_group
               for row in np.asarray(chosen))


def test_ties_go_to_the_lower_group_and_the_lower_id():
    # a zero router: every score 0.5, every group's score 1.0
    x = jnp.ones((3, 4), jnp.float32)
    chosen, wts = route_topk_sigmoid(x, jnp.zeros((4, 12)), None, 3, 1.0,
                                     n_group=4, topk_group=2)
    assert np.array_equal(np.asarray(chosen), [[0, 1, 2]] * 3)
    np.testing.assert_allclose(np.asarray(wts), 1 / 3, rtol=1e-6)
    # the best group is the last: its experts come first, then group 0's
    w = jnp.zeros((4, 12)).at[:, 9:].set(1.0)
    chosen, _ = route_topk_sigmoid(x, w, None, 4, 1.0, n_group=4,
                                   topk_group=2)
    assert np.array_equal(np.asarray(chosen), [[9, 10, 11, 0]] * 3)


def test_one_group_is_todays_routing():
    def todays(x, router_w, router_b, top_k, scale):   # expert.py, PR 32
        s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, router_w,
                                      preferred_element_type=jnp.float32))
        _, chosen = jax.lax.top_k(s + router_b.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(s, chosen, 1)
        return chosen, scale * picked / jnp.sum(picked, 1, keepdims=True)

    rng = np.random.RandomState(2)
    x, w, b = (jnp.asarray(rng.normal(size=s).astype(np.float32))
               for s in ((30, 16), (16, 32), (32,)))
    for got, want in zip(route_topk_sigmoid(x, w, b, 8, 1.5),
                         todays(x, w, b, 8, 1.5)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # no bias: the plain top-k of the scores
    chosen, _ = route_topk_sigmoid(x, w, None, 8)
    assert np.array_equal(np.asarray(chosen),
                          np.asarray(todays(x, w, jnp.zeros(32), 8, 1.0)[0]))
