"""Expert (MoE) parallelism — switch-style top-1 routing with capacity,
experts sharded one-per-device over an ``expert`` mesh axis and tokens
exchanged with ``lax.all_to_all`` over ICI.

Net-new capability (nothing MoE-shaped exists in the 2017 reference);
completes the framework's mesh-axis story alongside ``data`` / ``model``
/ ``sequence`` / ``pipe``.

Two execution paths share ONE routing implementation
(:func:`route_top1` — argmax gate, per-expert capacity positions via
one-hot cumsum, over-capacity tokens dropped to zero, switch-style gate
scaling):

- :func:`moe_apply_dense` — single-program path: dispatch/combine as
  einsums against the (N, E, C) dispatch tensor, experts vmapped.  This
  is also the numerical oracle.
- :func:`moe_apply_expert_parallel` — ``shard_map`` path: tokens arrive
  sharded over the expert axis, each device einsum-packs per-expert
  buckets, one ``all_to_all`` ships every bucket to its expert's device,
  the local expert runs once on all its tokens, a second ``all_to_all``
  ships results back.  Parity with the dense path is exact (same
  routing, same drops) and is what the tests assert.

Everything is static-shape: capacity ``C`` is a Python int, dropped
tokens are zeros, so both paths jit cleanly.

**Held experts (ISSUE 28).**  The second family below is the layer a
wide-expert-parallel deployment runs: the router keeps its PUBLISHED
width (:func:`route_topk_sigmoid`: sigmoid scores, bias-corrected top-k,
normalised weights), and a chip is TOLD which experts it holds
(``first_held`` and the leading axis of its expert weights), routes over
all of them and computes its own experts' part of the result for the
tokens routed to them (:func:`held_experts_apply`) — no capacity, no
dropped token.  On one chip that is the whole layer's local share, run
without its exchange (:func:`moe_held_experts`);
:func:`moe_held_experts_parallel` runs every share on an ``expert`` mesh
axis with the exchange (tokens all-gathered over the axis, the shares'
parts reduce-scattered back), and equals the sum of the shares.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.parallel.sequence import _shard_map

EXPERT_AXIS = "expert"
#: :func:`held_experts_apply` runs a batch of at most this many tokens
#: through every held expert, a larger one through the grouped product.
#: On a v5e at the published widths (32 held experts of 5,120 x 1,536,
#: 8 of 256 a token; PR 28, ms a layer, dense / grouped): 64 tokens 2.04 /
#: 2.86, 256 2.34 / 5.80, 512 4.65 / 6.81, 1,024 10.1 / 8.86.  Reading the
#: weights once is the cost of a small batch, and the dense form's N·H
#: products overtake it between 512 and 1,024 tokens
DENSE_BELOW = 512


def route_top1(x: jax.Array, gate_kernel: jax.Array, capacity: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Top-1 routing: returns (dispatch (N, E, C) float 0/1, scale (N,)).

    ``dispatch[i, e, c] = 1`` iff token i goes to expert e at bucket slot
    c; tokens beyond an expert's ``capacity`` are dropped (all-zero row).
    ``scale[i]`` is the token's softmax gate probability for its chosen
    expert (switch-transformer output scaling).
    """
    logits = x @ gate_kernel                                # (N, E)
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)                 # (N,)
    E = gate_kernel.shape[-1]
    oh = jax.nn.one_hot(expert_idx, E, dtype=x.dtype)       # (N, E)
    # slot within the chosen expert's bucket = how many earlier tokens
    # picked the same expert.  Counted in int32, NOT x.dtype: a bf16
    # cumsum stops incrementing at 256 and would assign duplicate slots.
    oh_i = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
    pos_i = jnp.sum((jnp.cumsum(oh_i, axis=0) - 1) * oh_i, axis=-1)  # (N,)
    keep = pos_i < capacity
    slot_oh = jax.nn.one_hot(pos_i, capacity, dtype=x.dtype)  # (N, C)
    dispatch = (oh[:, :, None] * slot_oh[:, None, :]
                * keep[:, None, None].astype(x.dtype))      # (N, E, C)
    scale = jnp.sum(gates * oh, axis=-1) * keep.astype(x.dtype)
    return dispatch, scale


def default_capacity(n_tokens: int, n_experts: int,
                     capacity_factor: float = 1.25) -> int:
    return max(1, math.ceil(n_tokens / n_experts * capacity_factor))


def moe_apply_dense(apply_expert: Callable[[Any, jax.Array], jax.Array],
                    stacked_params: Any, gate_kernel: jax.Array,
                    x: jax.Array, capacity: Optional[int] = None
                    ) -> jax.Array:
    """Reference/single-device path: x (N, D) → (N, D)."""
    E = gate_kernel.shape[-1]
    n_experts = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_experts != E:
        raise ValueError(
            f"stacked_params has {n_experts} experts but gate_kernel "
            f"routes to {E}")
    C = capacity if capacity is not None else default_capacity(x.shape[0], E)
    if C < 1:
        raise ValueError(f"capacity must be >= 1, got {C}")
    dispatch, scale = route_top1(x, gate_kernel, C)
    xe = jnp.einsum("nec,nd->ecd", dispatch, x)             # (E, C, D)
    ye = jax.vmap(apply_expert)(stacked_params, xe)         # (E, C, D)
    y = jnp.einsum("nec,ecd->nd", dispatch, ye)
    return y * scale[:, None]


def moe_apply_expert_parallel(
    apply_expert: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any, gate_kernel: jax.Array,
    x: jax.Array, mesh: Mesh,
    axis_name: str = EXPERT_AXIS,
    capacity: Optional[int] = None,
) -> jax.Array:
    """Expert-parallel path: E == mesh.shape[axis_name], one expert per
    device; ``x`` (N, D) with N sharded over the expert axis.

    Per-device capacity applies to each (sender, expert) pair, so the
    effective global capacity per expert is ``n_devices · C_local`` —
    pass ``capacity`` computed from the LOCAL token count for parity with
    a dense run at the same per-pair capacity.
    """
    E = gate_kernel.shape[-1]
    n = mesh.shape[axis_name]
    if E != n:
        raise ValueError(f"{E} experts but {axis_name!r} axis has {n} "
                         f"devices — one expert per device required")
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stages != E:
        raise ValueError(f"stacked_params has {n_stages} experts, expected {E}")
    if x.shape[0] % n:
        raise ValueError(f"token count {x.shape[0]} not divisible by {n}")
    C = (capacity if capacity is not None
         else default_capacity(x.shape[0] // n, E))
    if C < 1:
        raise ValueError(f"capacity must be >= 1, got {C}")

    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    tok_spec = P(axis_name, None)

    def local(params_l, gk, x_l):
        params = jax.tree_util.tree_map(lambda p: p[0], params_l)
        dispatch, scale = route_top1(x_l, gk, C)            # (N_l, E, C)
        xe = jnp.einsum("nec,nd->ecd", dispatch, x_l)       # (E, C, D)
        # ship bucket e to device e; receive (n, C, D): row j = sender j's
        # bucket for MY expert
        recv = jax.lax.all_to_all(xe, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
        ye = apply_expert(params, recv.reshape(n * C, -1)).reshape(n, C, -1)
        back = jax.lax.all_to_all(ye, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)  # (E, C, D)
        y = jnp.einsum("nec,ecd->nd", dispatch, back)
        return y * scale[:, None]

    fn = _shard_map(local, mesh,
                    in_specs=(param_spec, P(), tok_spec),
                    out_specs=tok_spec)
    return fn(stacked_params, gate_kernel, x)


# ---------------------------------------------------------------------------
# held experts: a published-width router, this chip's share of the experts
# ---------------------------------------------------------------------------

def route_topk_sigmoid(x: jax.Array, router_w: jax.Array,
                       router_b: Optional[jax.Array], top_k: int,
                       scale: float = 1.0, n_group: int = 1,
                       topk_group: int = 1) -> Tuple[jax.Array, jax.Array]:
    """``noaux_tc`` routing: scores ``sigmoid(x W_r)`` over the router's
    whole width, the ``top_k`` largest of score + bias chosen (ties to the
    lower expert id; ``router_b`` ``None``: no bias correction), weights
    the chosen SCORES over their sum, times ``scale``.  With ``n_group``
    groups (of consecutive expert ids) the choice is group-limited: a
    group's score is the sum of its two largest score + bias, only the
    ``topk_group`` best groups (ties to the lower group) stay eligible.
    → (chosen (N, k) int32, weights (N, k) f32)."""
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, router_w,
                                  preferred_element_type=jnp.float32))
    biased = s if router_b is None else s + router_b.astype(jnp.float32)
    if n_group > 1:
        groups = biased.reshape(s.shape[0], n_group, -1)
        best_two, _ = jax.lax.top_k(groups, 2)
        _, kept = jax.lax.top_k(jnp.sum(best_two, -1), topk_group)
        eligible = jnp.any(kept[:, :, None] == jnp.arange(n_group), 1)
        biased = jnp.where(eligible[:, :, None], groups,
                           -jnp.inf).reshape(s.shape)
    _, chosen = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(s, chosen, 1)
    return chosen, scale * picked / jnp.sum(picked, 1, keepdims=True)


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def held_experts_apply(x: jax.Array, experts: Any, chosen: jax.Array,
                       weights: jax.Array, first_held: Any
                       ) -> Tuple[jax.Array, jax.Array]:
    """This share's part of the routed result: ``Σ_{e chosen ∩ held} w_e ·
    E_e(x)`` for tokens ``x`` (N, d), with ``experts`` the HELD experts'
    gated-MLP weights (``w_gate``/``w_up`` (H, d, f), ``w_down`` (H, f, d))
    for expert ids ``first_held .. first_held + H``.  Every routed pair is
    computed; none is dropped.  → (y (N, d), tokens per held expert (H,)).

    The number of tokens alone decides the form: up to ``DENSE_BELOW``
    every token runs through every held expert and the results are weighed
    (weights read once, N·H products: a decode batch, where reading the
    weights is the cost); beyond, the (token, expert) pairs are sorted by
    expert and run through one grouped product (N·k rows whatever H: a
    prefill chunk)."""
    h = experts["w_gate"].shape[0]
    local = chosen - first_held
    held = (local >= 0) & (local < h)
    counts = jnp.sum(jax.nn.one_hot(jnp.where(held, local, h), h + 1,
                                    dtype=jnp.int32), (0, 1))[:h]
    form = _held_dense if x.shape[0] <= DENSE_BELOW else _held_grouped
    return form(x, experts, jnp.where(held, local, h), weights, counts), counts


def _held_dense(x, experts, local, weights, counts):
    """``local`` (N, k): the held expert's index, or H for an absent one."""
    h = experts["w_gate"].shape[0]
    combine = jnp.sum(jax.nn.one_hot(local, h + 1, dtype=jnp.float32)
                      * weights[..., None], 1)[:, :h]           # (N, H)
    g = jnp.einsum("nd,hdf->hnf", x, experts["w_gate"])
    u = jnp.einsum("nd,hdf->hnf", x, experts["w_up"])
    out = jnp.einsum("hnf,hfd->hnd", jax.nn.silu(g) * u, experts["w_down"])
    return jnp.einsum("hnd,nh->nd", out, combine.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _held_grouped(x, experts, local, weights, counts):
    n, k = local.shape
    h = experts["w_gate"].shape[0]
    key = local.reshape(-1)                                     # (N*k,)
    order = jnp.argsort(key, stable=True)       # this share's pairs first
    wt = jnp.where(local < h, weights, 0.0).reshape(-1)

    def grouped(rows_max: int):
        """The first ``rows_max`` sorted pairs through the grouped
        product, back in token order."""
        take = order[:rows_max]
        rows = x[take // k]
        g = jax.lax.ragged_dot(rows, experts["w_gate"], counts)
        u = jax.lax.ragged_dot(rows, experts["w_up"], counts)
        out = jax.lax.ragged_dot(jax.nn.silu(g) * u, experts["w_down"],
                                 counts, preferred_element_type=jnp.float32)
        out = jnp.where((key[take] < h)[:, None], out * wt[take][:, None],
                        0.0).astype(x.dtype)
        # a token's pairs summed by a 0/1 product (exact: the rounded rows
        # are added in float32); a scatter of the rows into an (N·k, d)
        # buffer took 3.8 of a layer's 9.1 ms at 1,024 tokens on a v5e
        mine = (take[None, :] // k == jnp.arange(n)[:, None]).astype(x.dtype)
        return jnp.dot(mine, out,
                       preferred_element_type=jnp.float32).astype(x.dtype)

    # a uniform router sends N·k·H/E pairs here, N at the published
    # ratio; the buffer takes twice the tokens, and the step that gets
    # more than that (the router may send all N·k) runs the full one: a
    # buffer's rows cost their time filled or not (12.2 ms for 16 k rows
    # at 1,024 tokens on a v5e, PR 28)
    small = min(n * k, max(2 * n, 256))
    if small == n * k:
        return grouped(n * k)
    return jax.lax.cond(jnp.sum(counts) <= small, lambda: grouped(small),
                        lambda: grouped(n * k))


def moe_held_experts(x: jax.Array, params: Any, first_held: Any,
                     top_k: int, scale: float = 1.0, shared: bool = True,
                     n_group: int = 1, topk_group: int = 1):
    """One share of the layer on one chip, without its exchange: route
    over the router's whole width, the held experts' part, plus the shared
    expert.  ``params``: ``router_w`` (d, E), ``router_b`` (E,) if the
    router corrects by a bias, ``experts`` (held, stacked), ``shared`` (a
    gated MLP); ``n_group`` / ``topk_group``: :func:`route_topk_sigmoid`'s.
    → (y (N, d), chosen (N, k), tokens per held expert (H,))."""
    with jax.named_scope("lm/route"):
        chosen, weights = route_topk_sigmoid(
            x, params["router_w"], params.get("router_b"), top_k, scale,
            n_group, topk_group)
    with jax.named_scope("lm/experts"):
        y, counts = held_experts_apply(x, params["experts"], chosen,
                                       weights, first_held)
    if shared:
        with jax.named_scope("lm/shared_mlp"):
            sh = params["shared"]
            y = y + _gated(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y, chosen, counts


def moe_held_experts_parallel(x: jax.Array, params: Any, mesh: Mesh,
                              top_k: int, scale: float = 1.0,
                              axis_name: str = EXPERT_AXIS, n_group: int = 1,
                              topk_group: int = 1) -> jax.Array:
    """Every share of the layer on an ``expert`` mesh axis, with the
    exchange: ``params["experts"]`` holds ALL experts stacked and sharded
    over the axis (device i holds experts ``i·H .. (i+1)·H``), ``x``
    (N, d) is sharded over it by tokens.  Each device all-gathers the
    tokens, computes its held experts' part for all of them, and the
    parts are reduce-scattered back to the tokens' owners; the shared
    expert runs on the local tokens, once.  Equals the sum of the shares
    :func:`moe_held_experts` gives, the shared expert counted once."""
    n = mesh.shape[axis_name]
    n_experts = params["experts"]["w_gate"].shape[0]
    if n_experts % n or x.shape[0] % n:
        raise ValueError(f"{n_experts} experts / {x.shape[0]} tokens do not "
                         f"divide over {n} devices of {axis_name!r}")
    held = n_experts // n
    spec = {"router_w": P(),
            "experts": jax.tree_util.tree_map(lambda _: P(axis_name),
                                              params["experts"]),
            "shared": jax.tree_util.tree_map(lambda _: P(),
                                             params["shared"])}
    if "router_b" in params:
        spec["router_b"] = P()

    def local(p, x_l):
        x_all = jax.lax.all_gather(x_l, axis_name, axis=0, tiled=True)
        first = jax.lax.axis_index(axis_name) * held
        part, _, _ = moe_held_experts(x_all, p, first, top_k, scale,
                                      shared=False, n_group=n_group,
                                      topk_group=topk_group)
        y = jax.lax.psum_scatter(part, axis_name, scatter_dimension=0,
                                 tiled=True)
        sh = p["shared"]
        return y + _gated(x_l, sh["w_gate"], sh["w_up"], sh["w_down"])

    fn = _shard_map(local, mesh, in_specs=(spec, P(axis_name, None)),
                    out_specs=P(axis_name, None))
    return fn(params, x)
