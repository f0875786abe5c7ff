"""Operations and bytes from shapes: what the algorithm needs, whatever
implements it.  Nothing here asks the compiler (XLA's ``cost_analysis``
counts what was emitted — zero for a Mosaic call — and changes with the
change under test)."""

from __future__ import annotations

from benchmarks.reference import ssd as ref


def _out(size: int, k: int, stride: int, pad: int, dilation: int = 1) -> int:
    return (size + 2 * pad - dilation * (k - 1) - 1) // stride + 1


def ssd_forward_flops(resolution: int, num_classes: int = 21) -> int:
    """Multiply-adds x2 of one image's SSD-VGG16 forward: every
    convolution 2*k*k*Cin*Cout*Hout*Wout, heads included; pools, ReLUs,
    the conv4_3 normalisation and the biases count as zero."""
    total, size, cin, sources = 0, resolution, 3, []
    for e in ref.VGG:
        if e == "P":
            size //= 2
        elif e == "Pc":
            size = -(-size // 2)
        elif e == "SRC":
            sources.append((size, cin))
        elif e != "P5":
            _, cout, k, s, p, d = ref._conv_spec(e)
            size = _out(size, k, s, p, d)
            total += 2 * k * k * cin * cout * size * size
            cin = cout
    for e in ref.extra_layers(resolution):
        if e == "SRC":
            sources.append((size, cin))
        else:
            _, cout, k, s, p = e
            size = _out(size, k, s, p)
            total += 2 * k * k * cin * cout * size * size
            cin = cout
    for (fs, c), n in zip(sources, ref.priors_per_cell(resolution)):
        total += 2 * 9 * c * n * (4 + num_classes) * fs * fs
    return total


def ssd_train_step_flops(resolution: int, batch: int,
                         num_classes: int = 21) -> int:
    """Forward + backward of one step: 3 x forward (the gradient of a
    convolution costs its forward twice over: one pass for the input, one
    for the kernel).  The loss, the augment and the optimizer count as
    zero, so the share of peak this feeds is a lower bound."""
    return 3 * batch * ssd_forward_flops(resolution, num_classes)


def n_priors(resolution: int) -> int:
    g = ref.GEOMETRY[resolution]
    return sum(fs * fs * n for fs, n in zip(g["feature_shapes"],
                                            ref.priors_per_cell(resolution)))


def detection_output_cost(batch: int, resolution: int, num_classes: int = 21,
                          keep_topk: int = 200) -> dict:
    """One DetectionOutput call over (B, P, C) scores and (B, P, 4) deltas.
    Operations: the decode (14 a prior) and one threshold compare a score;
    the suppression's work depends on the data and is left out, so both
    numbers are floors.  Bytes: every score and delta read once as
    float32, the priors once, the (B, K, 6) answer written once."""
    P = n_priors(resolution)
    return {"flops": batch * P * (14 + num_classes),
            "bytes": 4 * (batch * P * (num_classes + 4) + 8 * P
                          + batch * keep_topk * 6)}
