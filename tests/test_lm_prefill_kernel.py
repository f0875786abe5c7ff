"""The Pallas form of prefill's attention (ops/pallas_lm_prefill.py):
against the XLA loop at small lane-aligned widths in interpret mode, and
compiled for a v5e at the published widths (no chip needed: the TPU's
compiler is described, not attached).  The repo's other compile-for-a-v5e
tests live here too (decode's selection and its addresses, the causal
layers' paged decode kernel and their prefill at 64 heads, fused
DetectionOutput, last): only the one xdist worker that is given this file
loads the TPU's library."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import lm_attention as att
from analytics_zoo_tpu.ops import pallas_lm_prefill as pf

H, NOPE, ROPE, V, RANK, ENTRY, PAGE = 4, 128, 64, 128, 128, 256, 8


def chunk(seed=0, T=16, start=19, n_valid=13, topk=8, pool_pages=12):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)      # noqa: E731
    kv = np.zeros((pool_pages, PAGE, ENTRY), np.float32)
    kv[:, :, :RANK + ROPE] = rng.randn(pool_pages, PAGE, RANK + ROPE)
    table = jnp.asarray([3, 7, 1, 9, 5, 0, 0, 0], jnp.int32)
    args = (f(T, H, NOPE), f(T, H, ROPE), f(T, 2, 16), jnp.abs(f(T, 2)),
            jnp.asarray(kv), f(pool_pages, PAGE, 16), table,
            jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32),
            f(RANK, H, NOPE + V) / np.sqrt(RANK))
    return args, dict(nope=NOPE, r=ROPE, scale=float(1 / np.sqrt(NOPE + ROPE)),
                      topk=topk)


@pytest.mark.parametrize("start,n_valid,topk", [(19, 13, 8), (0, 16, 8),
                                                (24, 16, 64), (0, 0, 8)])
def test_kernel_equals_the_xla_loop(start, n_valid, topk):
    assert pf.supported(NOPE, V, RANK, ENTRY, ROPE, H, 2)
    args, kw = chunk(start=start, n_valid=n_valid, topk=topk)
    want, sets_x = att.prefill_full_attention(*args, **kw, flash=0)
    got, sets_k = att.prefill_full_attention(*args, **kw, flash=2)
    assert np.array_equal(np.asarray(sets_x), np.asarray(sets_k))
    real = slice(0, n_valid)      # a padding row's output is nobody's
    # float32 on both sides: two orders of summation
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)


def test_widths_the_kernel_does_not_take_run_the_xla_loop():
    assert not pf.supported(16, 16, 32, 128, 8, 4, 4)        # the toy's
    assert not pf.supported(128, 128, 512, 640, 64, 128, 3)  # heads % step
    assert pf.supported(128, 128, 512, 640, 64, 128, 4)      # published
    assert pf.supported(128, 128, 512, 640, 64, 64, 4)       # A.X-K1's heads


@pytest.mark.parametrize("start,n_valid", [(19, 13), (0, 16), (40, 16),
                                           (0, 0)])
def test_causal_prefill_kernel_equals_the_xla_loop(start, n_valid):
    """A causal layer's chunk: the causal mask alone as the kernel's bias,
    against the XLA loop and against plain softmax attention."""
    (q_nope, q_rope, _, _, kv, _, table, s, n, wkv_b), kw = chunk(
        start=start, n_valid=n_valid)
    kw.pop("topk")
    want = att.prefill_causal_attention(q_nope, q_rope, kv, table, s, n,
                                        wkv_b, **kw, flash=0)
    got = att.prefill_causal_attention(q_nope, q_rope, kv, table, s, n,
                                       wkv_b, **kw, flash=2)
    real = slice(0, n_valid)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)
    if n_valid:
        mine = kv[table].reshape(-1, ENTRY)
        valid = (jnp.arange(mine.shape[0])[None, :]
                 <= start + jnp.arange(16)[:, None])
        plain = att.mla_absorbed(
            q_nope, q_rope, jnp.broadcast_to(mine, (16,) + mine.shape),
            valid, wkv_b, NOPE, ROPE, kw["scale"])
        np.testing.assert_allclose(np.asarray(want)[real],
                                   np.asarray(plain)[real], rtol=2e-5,
                                   atol=2e-5)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("T", [256, 2048])
def test_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, T,
                                                           monkeypatch):
    # the code asks the backend whether to interpret: steered here
    monkeypatch.setattr(pf.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    heads, rank, entry, page, steps = 128, 512, 640, 512, 136
    fn = lambda q, w, kv, b, tab, n: pf.flash_mla_prefill(   # noqa: E731
        q, w, kv, b, tab, n, nope=128, scale=0.07, steps=steps,
        heads_per_step=4)
    compiled = jax.jit(fn).lower(
        S((heads, T, 128 + entry - rank)), S((rank, heads, 256)),
        S((2401, page, entry)), S((T, steps * page)),
        S((steps,), jnp.int32), S((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert pf.declared_vmem_bytes(T, page, entry, rank, 128, 128, 4) \
        < 64 * (1 << 20)


def test_paged_decode_kernel_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """ops/pallas_lm_decode.py at the A.X-K1 cell's geometry: 64 rows x 64
    heads, a pool of 1,751 pages of 512 x 640, tables of 88 pages; Mosaic
    takes the flat (row, page) grid inside the VMEM the kernel asks for."""
    from analytics_zoo_tpu.ops import pallas_lm_decode as pd

    monkeypatch.setattr(pd.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    fn = lambda q, pool, tables, n: pd.paged_mla_decode(     # noqa: E731
        q, pool, tables, n, rank=512, scale=0.13)
    compiled = jax.jit(fn).lower(
        S((64, 64, 640)), S((1751, 512, 640)), S((64, 88), jnp.int32),
        S((64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert pd.grid_steps(64, 88, 1751) == 1750
    assert pd.declared_vmem_bytes(64, 512, 640, 512, jnp.bfloat16) \
        < 8 * (1 << 20)


def test_paged_gqa_decode_kernel_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """``paged_gqa_decode`` at the MiMo cell's geometry: 64 rows x 64 heads
    on 4 KV heads, a pool of 2,101 pages of 512 x 1,280 (keys 768: every
    KV head's 128 unrotated dims, then every KV head's 64 rotated ones;
    values 512), tables of 136 pages."""
    from analytics_zoo_tpu.ops import pallas_lm_decode as pd

    monkeypatch.setattr(pd.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    fn = lambda qp, qr, pool, tables, n: att.gqa_paged(      # noqa: E731
        qp, qr, pool, tables, n, 4, 128, 192 ** -0.5)
    compiled = jax.jit(fn).lower(
        S((64, 64, 128)), S((64, 64, 64)), S((2101, 512, 1280)),
        S((64, 136), jnp.int32), S((64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert pd.grid_steps(64, 136, 2101) == 2100
    assert pd.gqa_declared_vmem_bytes(64, 512, 768, 512, 128, jnp.bfloat16) \
        < 8 * (1 << 20)


def test_paged_gqa_decode_kernel_compiles_for_a_v5e_at_5_heads_a_kv_head(
        one_chip, monkeypatch):
    """``gqa_paged`` at the Falcon-H1 cell's geometry: 128 rows x 20 heads
    on 4 KV heads (5 a KV head, padded to 8 rows for the kernel and cut
    off after it), keys with NO unrotated part (rotary = k = 128), a pool
    of 1,476 pages of 256 x 1,024, tables of 21 pages."""
    from analytics_zoo_tpu.ops import pallas_lm_decode as pd

    monkeypatch.setattr(pd.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    fn = lambda qp, qr, pool, tables, n: att.gqa_paged(      # noqa: E731
        qp, qr, pool, tables, n, 4, 128, 128 ** -0.5)
    lowered = jax.jit(fn).lower(
        S((128, 20, 0)), S((128, 20, 128)), S((1476, 256, 1024)),
        S((128, 21), jnp.int32), S((128,), jnp.int32))
    assert lowered.out_info.shape == (128, 20, 128)
    text = lowered.compile().as_text()
    # the page tables' words are gathered (work_items), never a page
    assert "tpu_custom_call" in text \
        and not re.search(r"bf16\[[\d,]+\]\S* gather\(", text)
    assert pd.gqa_declared_vmem_bytes(32, 256, 512, 512, 128, jnp.bfloat16) \
        < 8 * (1 << 20)


def test_ssm_decode_kernel_compiles_for_a_v5e_in_place(one_chip, monkeypatch):
    """ops/pallas_ssm_decode.py at the Falcon-H1 cell's geometry: 128 rows
    into 128 slots of 32 x 128 x 256 float32 (537 MB a layer).  Mosaic
    takes a slot's 4.19 MB block in and out inside the VMEM the kernel
    asks for, and the compiled program neither gathers nor scatters nor
    copies the slots' array: the output IS the donated input."""
    from analytics_zoo_tpu.ops import pallas_ssm_decode as pk

    monkeypatch.setattr(pk.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(       # noqa: E731
        s, d, sharding=one_chip)
    B, H, P, N, G = 128, 32, 128, 256, 2
    compiled = jax.jit(pk.ssm_decode_update, donate_argnums=0).lower(
        S((128, H, P, N)), S((B,), jnp.int32), S((B,), jnp.int32),
        S((B, H, P)), S((B, H)), S((B, H)), S((B, G, N)),
        S((B, G, N))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    whole = r"f32\[128,32,128,256\]\S* (gather|scatter|copy|fusion)\("
    assert not re.search(whole, text), re.search(whole, text).group(0)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * (1 << 20)
    assert pk.declared_vmem_bytes(H, P, N, G) < 24 * (1 << 20)


def test_selected_decode_kernel_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """``selected_mla_decode`` at the dots3 cell's geometry: 64 rows x 128
    heads over 2,048 gathered entries of 640 — two buffers of 2.6 MB, the
    (128, 2,048) float32 scores and their probabilities: Mosaic takes it
    inside the VMEM the kernel asks for."""
    from analytics_zoo_tpu.ops import pallas_lm_decode as pd
    from analytics_zoo_tpu.ops import vmem

    monkeypatch.setattr(pd.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    fn = lambda qn, qr, c, ok, w: att.mla_selected(          # noqa: E731
        qn, qr, c, ok, w, 128, 64, 0.07)
    compiled = jax.jit(fn).lower(
        S((64, 128, 128)), S((64, 128, 64)), S((64, 2048, 640)),
        S((64, 2048), jnp.bool_), S((512, 128, 256))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    declared = pd.selected_declared_vmem_bytes(128, 2048, 640, 512,
                                               jnp.bfloat16)
    assert 8 * (1 << 20) < declared < 10 * (1 << 20)
    assert vmem.fits(declared)


def test_causal_prefill_compiles_for_a_v5e_at_64_heads(one_chip,
                                                       monkeypatch):
    """A causal layer's chunk of 2,048 tokens at A.X-K1's widths through
    the prefill kernel, the causal mask as its bias."""
    monkeypatch.setattr(pf.engine, "on_tpu", lambda: True)
    S = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(      # noqa: E731
        s, d, sharding=one_chip)
    fn = lambda qn, qr, kv, tab, s, n, w: att.prefill_causal_attention(  # noqa
        qn, qr, kv, tab, s, n, w, 128, 64, 0.13, pages_per_step=1, flash=4)
    compiled = jax.jit(fn).lower(
        S((2048, 64, 128)), S((2048, 64, 64)), S((1751, 512, 640)),
        S((88,), jnp.int32), S((), jnp.int32), S((), jnp.int32),
        S((512, 64, 256))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_selection_compiles_for_a_v5e_with_no_sort_and_no_big_array(one_chip):
    """``select_topk`` at the LM cell's geometry: the chip's compiler
    leaves no sort in it and keeps every compare-and-sum inside a fusion
    (a (64, 2,048, 2,176) array would be 1.1 GB of a chip that the cell
    fills to 85 %; the largest is the 0/1 product's (64, 2,048, 128))."""
    B, n, k = 64, 69632, 2048
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa
    text = jax.jit(att.select_topk, static_argnums=2).lower(
        S((B, n), jnp.float32), S((B,), jnp.int32), k).compile().as_text()
    assert not re.search(r"\b(sort|topk)\(|TopK", text)
    entry = text[text.index("ENTRY"):]
    sizes = [np.prod([int(d) for d in dims.split(",")])
             for dims in re.findall(r"\w+\[([\d,]+)\]", entry)]
    assert B * n <= max(sizes) <= B * k * 4 * att.GROUP


def test_selected_addresses_compile_for_a_v5e_with_no_gather(one_chip):
    """``selected_addresses`` at the LM cell's geometry (64 rows x 2,048
    slots into tables of 136 pages): the chip's compiler leaves no gather
    in it — the parent's lookup of a word a position took 1.34 ms a layer —
    and the compare-and-sum stays inside a fusion (a (64, 136, 2,048)
    array would be 71 MB; nothing is larger than the (64, 2,048) result)."""
    B, k, max_pages = 64, 2048, 136
    S = lambda s: jax.ShapeDtypeStruct(s, jnp.int32,         # noqa: E731
                                       sharding=one_chip)
    text = jax.jit(att.selected_addresses, static_argnums=2).lower(
        S((B, max_pages)), S((B, k)), 512).compile().as_text()
    assert not re.search(r"\bgather\(|dynamic-slice\(", text)
    entry = text[text.index("ENTRY"):]
    sizes = [np.prod([int(d) for d in dims.split(",")])
             for dims in re.findall(r"\w+\[([\d,]+)\]", entry)]
    assert max(sizes) == B * k


@pytest.mark.parametrize("batch,n_priors,stage,keep_topk", [
    (32, 8732, "full", 200), (64, 24564, "decode", 200),
    (64, 24564, "select", 200), (64, 24564, "full", 200),
    (64, 24564, "full", 50)])
def test_fused_detection_output_compiles_for_a_v5e(one_chip, batch,
                                                   n_priors, stage,
                                                   keep_topk):
    """``ops/pallas_detout.py`` at SSD300's and SSD512's priors (the
    serve cell's batch), each prefix program, and the ``int8_topk50``
    tier's ``keep_topk``: Mosaic takes the dense (rows, 128) tiles, the
    dynamic aligned register windows and the keep lists' dynamic row and
    class window, inside the VMEM the kernel asks for."""
    from analytics_zoo_tpu.ops.detection_output import DetectionOutputParam
    from analytics_zoo_tpu.ops.pallas_detout import fused_detection_output

    S = lambda *s: jax.ShapeDtypeStruct(                     # noqa: E731
        s, jnp.float32, sharding=one_chip)
    fn = lambda l, c, p, v: fused_detection_output(          # noqa: E731
        l, c, p, v, param=DetectionOutputParam(keep_topk=keep_topk),
        stage=stage)
    compiled = jax.jit(fn).lower(
        S(batch, n_priors, 4), S(batch, n_priors, 21), S(n_priors, 4),
        S(n_priors, 4)).compile()
    assert "tpu_custom_call" in compiled.as_text()
