"""Post-training int8 quantization for serving — two modes.

Net-new capability (the reference serves fp32 through MKL; SURVEY.md
§2.6).  Weights are stored as per-output-channel symmetric int8
(``QTensor`` — int8 values + one fp32 scale per trailing axis), cutting
parameter HBM ~4×.  From that shared storage, two serving modes:

1. **Weight-only** (``quantize=True``): the forward dequantizes inside
   jit, so XLA fuses the ``q * scale`` broadcast into the adjacent
   matmul/conv and the bf16/fp32 MXU path is unchanged.  Lossless-
   ergonomics compression — identical arithmetic, smaller params.
2. **Int8 compute** (``quantize="int8"``): a flax method interceptor
   (``_int8_interceptor`` below) dynamically quantizes conv activations
   per-tensor and runs real ``int8×int8→int32`` convolutions on the
   MXU (``lax.conv_general_dilated`` with ``preferred_element_type=
   int32``), rescaling once on the way out.  mAP delta +0.000145 on
   a trained model (``INT8_MAP_PARITY.json``); its speed against the
   bf16 path is not measured on the chip (no cell serves it: PERF.md).

Which layers quantize is an abstract-trace census (``QTensor`` hygiene:
every int8 leaf must be consumed by exactly one conv/matmul), not a
name-pattern guess — see ``quantize_params``.

Usage::

    qparams = quantize_params(model.params)         # ~4x smaller pytree
    fwd = make_quantized_forward(model.module)      # weight-only
    y = fwd(qparams, x)                             # == model.forward(x) ± eps
    fwd8 = make_quantized_forward(model.module, compute="int8")
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_PATTERN = r"(^|.*/)(kernel|embedding)$"


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Symmetric per-trailing-axis int8 quantized tensor."""

    def __init__(self, q: jax.Array, scale: jax.Array):
        self.q = q          # int8, original shape
        self.scale = scale  # f32, shape (trailing_dim,)

    def dequant(self, dtype=jnp.float32) -> jax.Array:
        return self.q.astype(dtype) * self.scale.astype(dtype)

    @property
    def shape(self):
        return self.q.shape

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"QTensor(shape={tuple(self.q.shape)}, int8)"


def quantize_tensor(w) -> QTensor:
    """w (..., C) → int8 values + per-C scale (symmetric, round-to-nearest)."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))     # (C,)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return QTensor(jnp.asarray(q), jnp.asarray(scale))


def quantize_params(params: Any,
                    pattern: str = DEFAULT_PATTERN,
                    min_size: int = 4096) -> Any:
    """Replace every ≥2-D leaf whose path matches ``pattern`` (and holds
    at least ``min_size`` elements — tiny tensors aren't worth the
    rounding error) with a :class:`QTensor`; everything else passes
    through untouched."""
    rx = re.compile(pattern)

    def maybe_q(path_entries, leaf):
        path = "/".join(str(getattr(e, "key", getattr(e, "name", e)))
                        for e in path_entries)
        arr = np.asarray(leaf)
        if (arr.ndim >= 2 and arr.size >= min_size and rx.match(path)):
            return quantize_tensor(arr)
        return leaf

    return jax.tree_util.tree_map_with_path(maybe_q, params)


def dequantize_params(qparams: Any, dtype=jnp.float32) -> Any:
    return jax.tree_util.tree_map(
        lambda t: t.dequant(dtype) if isinstance(t, QTensor) else t,
        qparams, is_leaf=lambda x: isinstance(x, QTensor))


def _cast_floating(tree, dtype):
    # QTensors pass through whole: their int8 payload isn't floating and
    # their fp32 scale must NOT degrade to bf16 (the rescale is the
    # accuracy-critical step of the int8 path)
    return jax.tree_util.tree_map(
        lambda x: x if isinstance(x, QTensor)
        else (x.astype(dtype)
              if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
              else x),
        tree, is_leaf=lambda x: isinstance(x, QTensor))


def _canon_conv_padding(padding, kernel_size):
    """nn.Conv padding attribute → lax.conv_general_dilated padding."""
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return [(padding, padding)] * len(kernel_size)
    out = []
    for p in padding:
        out.append((p, p) if isinstance(p, int) else tuple(p))
    return out


def _maybe_tuple(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _dynamic_quant_activation(x):
    """Per-tensor symmetric dynamic quantization of an activation: the
    scale is data-dependent, computed in-graph (one max-reduce XLA fuses
    with the producer), so serving needs no calibration pass."""
    a = x.astype(jnp.float32)
    a_scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-8) / 127.0
    qa = jnp.clip(jnp.round(a / a_scale), -127, 127).astype(jnp.int8)
    return qa, a_scale


def _int8_conv(m, x, qk: QTensor, bias):
    """``nn.Conv.__call__`` replacement: int8×int8→int32 on the MXU (the
    TPU's int8 matmul peak is 2× its bf16 peak), rescaled by
    activation-scale × per-output-channel weight-scale in fp32."""
    from jax import lax

    n_spatial = len(m.kernel_size)
    qa, a_scale = _dynamic_quant_activation(x)
    # flax convs are channel-LAST for every rank; lax's default
    # dimension numbers are channel-first, so spell them out per rank
    spatial = {1: "W", 2: "HW", 3: "DHW"}[n_spatial]
    dn = lax.conv_dimension_numbers(
        qa.shape, qk.q.shape,
        (f"N{spatial}C", f"{spatial}IO", f"N{spatial}C"))
    y = lax.conv_general_dilated(
        qa, qk.q,
        window_strides=_maybe_tuple(m.strides, n_spatial),
        padding=_canon_conv_padding(m.padding, m.kernel_size),
        lhs_dilation=_maybe_tuple(m.input_dilation, n_spatial),
        rhs_dilation=_maybe_tuple(m.kernel_dilation, n_spatial),
        dimension_numbers=dn,
        feature_group_count=m.feature_group_count,
        preferred_element_type=jnp.int32)
    y = y.astype(jnp.float32) * (a_scale * qk.scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype) if x.dtype != jnp.int8 else y


def _int8_dense(m, x, qk: QTensor, bias):
    from jax import lax

    qa, a_scale = _dynamic_quant_activation(x)
    y = lax.dot_general(qa, qk.q, (((qa.ndim - 1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
    y = y.astype(jnp.float32) * (a_scale * qk.scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype) if x.dtype != jnp.int8 else y


def _int8_interceptor(next_fun, args, kwargs, context):
    """``nn.intercept_methods`` hook: when a Conv/Dense's kernel arrives
    as a :class:`QTensor`, replace the whole layer call with the int8
    compute path (``next_fun`` — and with it flax's param shape check —
    never runs for that layer); every other module is untouched."""
    import flax.linen as nn

    m = context.module
    if context.method_name == "__call__" and type(m) in (nn.Conv, nn.Dense):
        params = m.variables.get("params", {})
        qk = params.get("kernel")
        if isinstance(qk, QTensor):
            bias = params.get("bias") if m.use_bias else None
            fn = _int8_conv if type(m) is nn.Conv else _int8_dense
            return fn(m, args[0], qk, bias)
    return next_fun(*args, **kwargs)


def int8_apply(apply_fn: Callable, variables, *inputs, **kw):
    """Run ``apply_fn(variables, *inputs)`` with every QTensor-kerneled
    Conv/Dense executed as int8×int8→int32 (see ``_int8_interceptor``)."""
    import flax.linen as nn

    with nn.intercept_methods(_int8_interceptor):
        return apply_fn(variables, *inputs, **kw)


def _conv_dense_kernel_paths(apply_fn, variables, *inputs):
    """Param-tree paths (collection-relative) of every kernel the int8
    interceptor WILL consume — discovered by abstractly tracing the
    model once (``jax.eval_shape``, no FLOPs) with a recording
    interceptor.  ``quantize_params``' pattern can't know module types
    (``kernel|embedding`` also matches nn.Embed / RNN cells); any
    QTensor OUTSIDE this set must be dequantized up front or it reaches
    module code raw."""
    import flax.linen as nn

    paths = set()

    def rec(next_fun, args, kwargs, context):
        m = context.module
        if context.method_name == "__call__" and type(m) in (nn.Conv,
                                                             nn.Dense):
            paths.add(tuple(m.path) + ("kernel",))
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(rec):
        jax.eval_shape(apply_fn, variables, *inputs)
    return frozenset(paths)


def _dequantize_except(qparams, keep_paths):
    """Dequantize every QTensor whose path is NOT in ``keep_paths``
    (paths are relative to the variables collection, i.e. with a
    leading "params" entry stripped)."""

    def go(path_entries, leaf):
        if not isinstance(leaf, QTensor):
            return leaf
        names = tuple(str(getattr(e, "key", getattr(e, "name", e)))
                      for e in path_entries)
        rel = names[1:] if names and names[0] == "params" else names
        return leaf if rel in keep_paths else leaf.dequant(jnp.float32)

    return jax.tree_util.tree_map_with_path(
        go, qparams, is_leaf=lambda x: isinstance(x, QTensor))


def make_quantized_forward(module, dtype=None,
                           apply_fn: Optional[Callable] = None,
                           compute: str = "dequant") -> Callable:
    """Jitted ``fwd(qparams, *inputs)``.

    ``compute="dequant"`` (default): dequantization happens inside the
    traced program so XLA fuses it into the consuming matmul/conv —
    int8 lives in HBM, fp enters the MXU.  Weight-bandwidth compression
    only; the arithmetic is unchanged.

    ``compute="int8"``: activations are dynamically quantized per tensor
    and every QTensor-kerneled Conv/Dense issues a real
    int8×int8→int32 convolution/``dot_general`` on the MXU (2× the bf16
    peak on v5e), rescaled in fp32.  The layers NOT selected by
    ``quantize_params`` still run in fp/bf16.

    The default apply runs the module in eval mode (``train=False`` when
    the module takes it).  ``dtype`` (e.g. ``jnp.bfloat16``) mirrors
    ``make_eval_step``'s mixed precision: dequant happens in fp32 for
    accuracy, then weights AND inputs are cast to ``dtype`` so the MXU
    actually runs at that precision, with outputs cast back to fp32."""
    if apply_fn is None:
        import inspect

        # only pass train= when __call__ NAMES it — containers like
        # nn.Sequential advertise **kwargs but forward them to layers
        # that reject the keyword
        sig = inspect.signature(type(module).__call__)
        kw = {"train": False} if "train" in sig.parameters else {}

        def apply_fn(variables, *a):
            return module.apply(variables, *a, **kw)

    if compute not in ("dequant", "int8"):
        raise ValueError(f"unknown compute mode {compute!r}")
    mixed = dtype is not None and dtype != jnp.float32

    if compute == "int8":
        # Lazy one-time discovery at first call (needs concrete input
        # shapes): find which QTensors the Conv/Dense interceptor will
        # consume; dequantize the rest up front so e.g. a quantized
        # nn.Embed `embedding` or RNN-cell `kernel` never reaches
        # module code as a raw QTensor.  Mixed-precision casting applies
        # to the NON-int8 remainder (bias/BN/fallback-dequantized).
        cache: dict = {}

        def fwd(qvariables, *inputs):
            if "jit" not in cache:
                probe = dequantize_params(qvariables, jnp.float32)
                keep = _conv_dense_kernel_paths(apply_fn, probe, *inputs)

                @jax.jit
                def inner(qv, *ins):
                    v = _dequantize_except(qv, keep)
                    if mixed:
                        v = _cast_floating(v, dtype)
                        ins = _cast_floating(ins, dtype)
                    out = int8_apply(apply_fn, v, *ins)
                    return _cast_floating(out, jnp.float32) if mixed else out

                cache["jit"] = inner
            return cache["jit"](qvariables, *inputs)

        return fwd

    @jax.jit
    def fwd(qvariables, *inputs):
        variables = dequantize_params(qvariables, jnp.float32)
        if mixed:
            variables = _cast_floating(variables, dtype)
            inputs = _cast_floating(inputs, dtype)
        out = apply_fn(variables, *inputs)
        if mixed:
            out = _cast_floating(out, jnp.float32)
        return out

    return fwd


def save_quantized_npz(path: str, qparams: Any) -> str:
    """Persist a (possibly quantized) variables pytree as one npz file —
    the serving artifact format (``tools/export_serving.py``): QTensors
    become ``<path>#q`` (int8) + ``<path>#scale`` pairs, plain leaves
    ``<path>#raw``.  Returns the actual file path (np.savez appends
    ``.npz`` when missing — normalized here so save/load stay inverses)."""
    if not path.endswith(".npz"):
        path += ".npz"
    flat: dict = {}

    def rec(prefix: str, node: Any) -> None:
        if isinstance(node, QTensor):
            flat[prefix + "#q"] = np.asarray(node.q)
            flat[prefix + "#scale"] = np.asarray(node.scale)
        elif hasattr(node, "items"):
            for k, v in node.items():
                if "#" in str(k) or "/" in str(k):
                    raise ValueError(f"key {k!r} contains a reserved char")
                rec(f"{prefix}/{k}" if prefix else str(k), v)
        else:
            flat[prefix + "#raw"] = np.asarray(node)

    rec("", qparams)
    np.savez_compressed(path, **flat)
    return path


def load_quantized_npz(path: str) -> Any:
    """Inverse of :func:`save_quantized_npz`: nested dict pytree with
    QTensor leaves restored, ready for :func:`make_quantized_forward`."""
    import jax.numpy as jnp

    data = np.load(path)
    out: dict = {}
    pending: dict = {}
    for key in data.files:
        name, kind = key.rsplit("#", 1)
        if kind in ("q", "scale"):
            pending.setdefault(name, {})[kind] = data[key]
            continue
        if name == "":                       # bare-leaf root
            return jnp.asarray(data[key])
        parts = name.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(data[key])
    for name, qs in pending.items():
        qt = QTensor(jnp.asarray(qs["q"]), jnp.asarray(qs["scale"]))
        if name == "":                       # bare-QTensor root
            return qt
        parts = name.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = qt
    return out


def quantized_nbytes(tree: Any) -> Tuple[int, int]:
    """(quantized_bytes, fp32_equivalent_bytes) across the pytree."""
    qb = fb = 0
    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            n = int(np.prod(leaf.q.shape))
            qb += n + 4 * int(np.prod(leaf.scale.shape))
            fb += 4 * n
        else:
            n = int(np.prod(np.shape(leaf)))
            itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            qb += itemsize * n
            fb += 4 * n
    return qb, fb
