"""Raw int8-vs-bf16 convolution throughput on the chip — the ground
truth under the int8-serving story (VERDICT r3 item 2).

Per-dispatch latency swamps a single conv, so N convs are chained
inside ONE jit via ``lax.fori_loop`` (int8 chains re-quantize between
convs the way the serving interceptor does: int32 → clip → int8; bf16
chains clip+cast to bf16).  Alternating windows, scalar-sum fence.  Writes --out (default INT8_CONV_PROBE.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--hw", type=int, default=38, help="spatial size (SSD "
                   "conv4_3 grid)")
    p.add_argument("--channels", type=int, default=512)
    p.add_argument("--chain", type=int, default=100, help="convs per jit")
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--out", default="INT8_CONV_PROBE.json")
    args = p.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, H, C = args.batch, args.hw, args.channels
    N = args.chain
    rng = np.random.RandomState(0)
    x8 = jnp.asarray(rng.randint(-4, 4, (B, H, H, C)).astype(np.int8))
    w8 = jnp.asarray(rng.randint(-4, 4, (3, 3, C, C)).astype(np.int8))
    xb = x8.astype(jnp.bfloat16)
    wb = w8.astype(jnp.bfloat16)
    dn = lax.conv_dimension_numbers(x8.shape, w8.shape,
                                    ("NHWC", "HWIO", "NHWC"))

    def chain(x, w, pet, cast):
        def body(i, acc):
            r = lax.conv_general_dilated(acc, w, (1, 1), ((1, 1), (1, 1)),
                                         dimension_numbers=dn,
                                         preferred_element_type=pet)
            return cast(r)
        return lax.fori_loop(0, N, body, x).sum()

    conv_i8 = jax.jit(lambda x, w: chain(
        x, w, jnp.int32, lambda r: jnp.clip(r, -4, 4).astype(jnp.int8)))
    conv_bf = jax.jit(lambda x, w: chain(
        x, w, jnp.float32, lambda r: jnp.clip(r, -4, 4).astype(jnp.bfloat16)))

    flop = 2 * B * H * H * C * 3 * 3 * C * N
    results = {"int8": [], "bf16": []}
    for rnd in range(args.rounds):
        order = [("int8", conv_i8, x8, w8), ("bf16", conv_bf, xb, wb)]
        if rnd % 2:
            order = order[::-1]
        for name, f, a, b in order:
            r = f(a, b)
            float(np.asarray(r))                         # warm + fence
            t0 = time.perf_counter()
            for _ in range(3):
                r = f(a, b)
            float(np.asarray(r))                         # fence
            dt = (time.perf_counter() - t0) / 3
            results[name].append(round(flop / dt / 1e12, 1))
            print(json.dumps({"round": rnd, "dtype": name,
                              "tops": results[name][-1],
                              "ms_per_conv": round(dt * 1e3 / N, 3)}),
                  flush=True)

    med = {k: sorted(v)[len(v) // 2] for k, v in results.items()}
    report = {
        "shape": f"{B}x{H}x{H}x{C} conv3x3x{C}->{C}, {N}-conv chain",
        "median_tops": med,
        "int8_speedup_vs_bf16": round(med["int8"] / max(med["bf16"], 1e-9), 3),
        "windows": results,
        "device": jax.devices()[0].device_kind,
        "note": "int8 wins at the CONV level; the SSD serve program is "
                "DetectionOutput-bound at batch 128, which is why the "
                "e2e int8 serve ratio stays ~1.0-1.1 "
                "(ssd300_serve_int8_device_speedup)",
    }
    print(json.dumps(report))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
