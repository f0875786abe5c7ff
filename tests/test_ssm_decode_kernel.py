"""The in-place decode kernel of the state-space mixer
(ops/pallas_ssm_decode.py ``ssm_decode_update``) in interpret mode against
the ``jax.numpy`` step (ops/ssm.py ``ssd_step``): the rows' slots updated
where they lie, every other slot as it was, a padding row (slot −1)
nowhere, a row at position 0 from zeros whatever its slot held."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import pallas_ssm_decode as pk
from analytics_zoo_tpu.ops import ssm

H, P, N, G, SLOTS = 4, 8, 128, 2, 6


def draw(seed, B):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    delta = jnp.asarray(r.uniform(0.01, 0.5, (B, H)), jnp.float32)
    a = jnp.exp(-delta * jnp.asarray(r.uniform(1, 4, (H,)), jnp.float32))
    return dict(states=f(SLOTS, H, P, N), x=f(B, H, P), delta=delta, a=a,
                Bm=f(B, G, N), Cm=f(B, G, N))


def plain(states, slots, pos, x, delta, a, Bm, Cm):
    """What the kernel has to do, by gather, ``ssd_step`` and scatter."""
    live = slots >= 0
    old = jnp.where((live & (pos > 0))[:, None, None, None],
                    states[jnp.maximum(slots, 0)], 0.0)
    y, new = ssm.ssd_step(x, delta, a, Bm, Cm, jnp.zeros((H,)), old)
    states = states.at[jnp.where(live, slots, SLOTS)].set(new, mode="drop")
    return states, jnp.where(live[:, None, None], y, 0.0)


@pytest.mark.parametrize("name,slots,pos", [
    ("all_live", [3, 0, 5, 1], [7, 2, 9, 1]),
    ("padding_between_and_after", [4, -1, 2, -1], [3, 0, 8, 0]),
    ("padding_first", [-1, -1, 1, 5], [0, 0, 4, 6]),
    ("a_fresh_session_in_a_used_slot", [2, 3, -1, 0], [0, 5, 0, 0]),
    ("no_live_row", [-1, -1, -1, -1], [0, 0, 0, 0]),
    ("one_row", [5], [11]),
])
def test_kernel_updates_the_rows_slots_in_place(name, slots, pos):
    slots, pos = (jnp.asarray(v, jnp.int32) for v in (slots, pos))
    t = draw(len(name), len(slots))
    want_s, want_y = plain(t["states"], slots, pos, t["x"], t["delta"],
                           t["a"], t["Bm"], t["Cm"])
    got_s, got_y = pk.ssm_decode_update(
        jnp.array(t["states"]), slots, pos, t["x"], t["delta"], t["a"],
        t["Bm"], t["Cm"], interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6, rtol=1e-6)
    untouched = np.setdiff1d(np.arange(SLOTS), np.asarray(slots))
    assert np.array_equal(np.asarray(got_s)[untouched],
                          np.asarray(t["states"])[untouched]), name


def test_block_slots_names_a_neighbour_s_block_for_a_padding_row():
    f = lambda v: np.asarray(pk.block_slots(jnp.asarray(v, jnp.int32)))  # noqa
    assert f([4, -1, 2, -1]).tolist() == [4, 4, 2, 2]
    assert f([-1, -1, 1, 5]).tolist() == [1, 1, 1, 5]
    assert f([-1, -1]).tolist() == [0, 0]


def test_widths_the_kernel_takes():
    assert pk.supported(32, 128, 256, 2)            # the published
    assert not pk.supported(4, 8, 16, 2)            # the toy's: off the lanes
    assert not pk.supported(32, 128, 256, 3)        # heads not whole groups
    with pytest.raises(ValueError, match="do not fit"):
        pk.ssm_decode_update(
            jnp.zeros((2, 4, 8, 16)), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.zeros((1, 4, 8)), jnp.zeros((1, 4)),
            jnp.zeros((1, 4)), jnp.zeros((1, 2, 16)), jnp.zeros((1, 2, 16)))
