"""A routed-expert layer that is told which experts it holds.

The router keeps its published width: every token scores all ``E`` experts
and chooses ``top_k`` of them, among all or among those of the groups a
group-limited router keeps (``limit_to_groups``).  This chip holds the ``count`` experts
``first .. first + count`` (``held``) and computes their part of the
result: the assignments that fall on held experts are sorted by expert,
their token rows gathered, two grouped products (three for a gated
expert) run over them, and the weighted rows are scatter-added back to
their tokens.  On a TPU the
products, and through a ``custom_vjp`` the four of their backward pass,
are the row-tiled Mosaic kernels of ``ops/gmm_pallas.py``; on any other
platform they are ``lax.ragged_dot`` and its autodiff (a Mosaic kernel
lowers nowhere else; the tests compare the two).
What the absent experts would add is left out, and nothing stands in for
the chips that hold them or for the exchange with them.

Static shapes: the sorted assignments are cut to ``capacity`` rows, a
stated bound on the assignments to *all* held experts together (how they
split among the held experts is free: the grouped product takes the
sizes).  ``overflow`` counts the assignments beyond it; a run in which it
is not zero has dropped work and is not a sound run.  Where ``capacity``
is ``tokens * min(top_k, count)`` it cannot be exceeded, unless the rows
are aligned (``held_assignments``' ``align``: each held expert's rows in
whole tiles of their own, the rows between them padding).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Routed(NamedTuple):
    """The held assignments in expert order, ``capacity`` rows.

    token (C,) int32: the row's token; weight (C,) float32: its routing
    weight, 0 for a row past the last held assignment; valid (C,) bool;
    group_sizes (count,) int32: rows of each held expert among the
    ``capacity`` kept, the last group taking the rows past the last
    assignment too (they sum to ``capacity``); sizes (count,) int32:
    assignments to each held expert, kept or not; overflow () int32:
    assignments not kept."""

    token: jax.Array
    weight: jax.Array
    valid: jax.Array
    group_sizes: jax.Array
    sizes: jax.Array
    overflow: jax.Array


def row_capacity(tokens: int, top_k: int, n_experts: int, count: int,
                 factor: float) -> int:
    """Rows kept for the held experts: ``factor`` times the assignments
    expected under even routing, rounded up to a multiple of 128, never
    more than the bound that cannot be exceeded."""
    bound = tokens * min(top_k, count)
    expected = tokens * top_k * count / n_experts
    return int(min(bound, -(-int(factor * expected) // 128) * 128))


def limit_to_groups(biased, n_group: int, topk_group: int):
    """Group-limited choice: ``biased`` (T, E) in ``n_group`` groups of
    consecutive experts, a group's score the sum of its two best, the
    ``topk_group`` best groups kept (the lower index on a tie, as
    ``lax.top_k`` breaks it); the other groups' entries become -inf."""
    t, e = biased.shape
    grouped = biased.reshape(t, n_group, e // n_group)
    score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, kept = jax.lax.top_k(score, topk_group)
    keep = (kept[:, :, None] == jnp.arange(n_group)).any(1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)


def route(x, w_router, bias, top_k: int, scale: float, norm_topk: bool,
          groups: Optional[Tuple[int, int]] = None,
          scoring: str = "sigmoid"):
    """x (T, H) -> (idx (T, k) int32, weight (T, k) float32).  Logits and
    scores in float32, the scores a sigmoid of each logit or (``scoring``
    'softmax') a softmax over all experts; the choice is by ``scores +
    bias`` — among all
    experts, or with ``groups = (n_group, topk_group)`` among the experts of
    the groups :func:`limit_to_groups` keeps — the weights are the unbiased
    scores of the chosen (divided by their sum with ``norm_topk``) times
    ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"router scoring is sigmoid or softmax, got "
                         f"{scoring!r}")
    scores = (jax.nn.softmax(logits, -1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))
    biased = scores + bias
    if groups is not None:
        biased = limit_to_groups(biased, *groups)
    _, idx = jax.lax.top_k(biased, top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return idx, weight * scale


def held_assignments(idx, weight, held: Tuple[int, int],
                     capacity: int, align: int = 0) -> Routed:
    """Sort the (T, k) assignments by held expert; keep ``capacity``.
    ``align`` > 0 lays each held expert's rows out from a multiple of
    ``align`` rows, in at least one such block (``_aligned``)."""
    first, count = held
    t, k = idx.shape
    local = idx - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)[:capacity]
    valid = key[order] < count
    sizes = (key[:, None] == jnp.arange(count)).sum(0).astype(jnp.int32)
    if align:
        return _aligned(order, weight.reshape(-1), sizes, k, capacity, align)
    ends = jnp.minimum(jnp.cumsum(sizes), capacity)
    # the rows past the last held assignment go to the last group: they
    # enter as zeros and leave masked, and the grouped products then run
    # over all ``capacity`` rows whatever the routing, so that a step's
    # work does not depend on the data (a kernel that skips empty rows
    # made the step's time follow the seed: PERF.md section 6, PR 34)
    group_sizes = jnp.diff(ends.at[-1].set(capacity), prepend=0).astype(
        jnp.int32)
    return Routed(
        token=(order // k).astype(jnp.int32),
        weight=jnp.where(valid, weight.reshape(-1)[order], 0.0),
        valid=valid, group_sizes=group_sizes, sizes=sizes,
        overflow=jnp.maximum(sizes.sum() - capacity, 0).astype(jnp.int32))


def _aligned(order, weight, sizes, k: int, capacity: int,
             align: int) -> Routed:
    """The held rows with each expert's block starting on a multiple of
    ``align`` and at least ``align`` rows long, the rows between them
    padding: with ``align`` the grouped products' row tile, every tile is
    one expert's whatever the routing, so that the products' work is the
    same on every step (where experts share tiles, how many tiles they
    share and how many experts have no rows follow the routing, and the
    step's time follows it).  ``order``: the sorted assignments' first
    ``capacity``; an expert's rows past ``capacity`` are not kept."""
    count = sizes.shape[0]
    padded = jnp.maximum(-(-sizes // align), 1) * align
    block_ends = jnp.cumsum(padded)
    block_starts = block_ends - padded
    starts = jnp.cumsum(sizes) - sizes
    rows = jnp.arange(capacity, dtype=jnp.int32)
    group = jnp.sum(rows[:, None] >= block_ends[None, :], axis=1)
    g = jnp.minimum(group, count - 1)
    offset = rows - block_starts[g]
    valid = (group < count) & (offset < sizes[g])
    # a padding row reads what the packed layout had in its place: the rows
    # of one step stay distinct tokens, as without alignment
    pick = order[jnp.where(valid, starts[g] + offset, rows)]
    ends = jnp.minimum(block_ends, capacity)
    group_sizes = jnp.diff(ends.at[-1].set(capacity), prepend=0).astype(
        jnp.int32)
    kept = jnp.sum(jnp.clip(capacity - block_starts, 0, sizes))
    return Routed(
        token=(pick // k).astype(jnp.int32),
        weight=jnp.where(valid, weight[pick], 0.0), valid=valid,
        group_sizes=group_sizes, sizes=sizes,
        overflow=(sizes.sum() - kept).astype(jnp.int32))


def relu2_ffn(x, w_up, w_down):
    """down(relu(up(x))^2), no gate, no bias; products accumulate in
    float32, the result is float32."""
    h = jnp.dot(x, w_up.astype(x.dtype), preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)).astype(x.dtype)
    return jnp.dot(h, w_down.astype(x.dtype),
                   preferred_element_type=jnp.float32)


def swiglu_ffn(x, w_gate, w_up, w_down):
    """down(silu(gate(x)) * up(x)), no bias, no clamp; products accumulate
    in float32, the result is float32."""
    g = jnp.dot(x, w_gate.astype(x.dtype), preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up.astype(x.dtype), preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype),
                   w_down.astype(x.dtype), preferred_element_type=jnp.float32)


def grouped_product(rows, w, group_sizes, interpret: bool = False):
    """rows (C, K) sorted by group; w (count, K, N), cast to ``rows``'
    dtype for the product -> (C, N) float32, accumulated in float32.  On a
    TPU the kernels of ``ops/gmm_pallas.py`` (``interpret`` runs them in
    the Pallas interpreter instead: what a test passes, on any platform);
    elsewhere ``lax.ragged_dot``.  The platform is read at trace time."""
    with jax.named_scope("moe_grouped"):
        if interpret or jax.default_backend() == "tpu":
            from mx_rcnn_tpu.ops.gmm_pallas import grouped_matmul

            return grouped_matmul(rows, w, group_sizes, None, interpret)
        return jax.lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes,
                                  preferred_element_type=jnp.float32)


def held_experts(x, routed: Routed, w_up, w_down, interpret: bool = False,
                 w_gate=None):
    """The held experts' part of the layer's output, (T, H) float32.
    x (T, H); w_up (count, H, F); w_down (count, F, H): an expert is
    ``down(relu(up x)^2)``, or with ``w_gate`` (count, H, F) the gated
    ``down(silu(gate x) * up x)``, a third grouped product.  Rows past the
    last held assignment enter as zeros and leave as zeros, whatever the
    grouped product (``grouped_product``: a Mosaic kernel on a TPU,
    ``ragged_dot`` elsewhere) writes there."""
    keep = routed.valid[:, None]
    xs = jnp.where(keep, x[routed.token], 0)
    h = grouped_product(xs, w_up, routed.group_sizes, interpret)
    if w_gate is None:
        h = jnp.square(jax.nn.relu(h))
    else:
        h = h * jax.nn.silu(
            grouped_product(xs, w_gate, routed.group_sizes, interpret))
    y = grouped_product(h.astype(x.dtype), w_down, routed.group_sizes,
                        interpret)
    y = jnp.where(keep, y * routed.weight[:, None], 0.0)
    return jnp.zeros(x.shape, jnp.float32).at[routed.token].add(y)
