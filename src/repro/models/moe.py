"""Mixture-of-Experts FFN with top-k routing and capacity-bounded dispatch.

Dispatch strategy (DESIGN.md §5): tokens scatter into per-expert slot
buffers; expert FFNs run as one batched einsum over the expert-stacked
weights; results gather back weighted by router probs. Under pjit the
buffer's expert dim shards over ``model`` (expert parallelism, when E
divides the axis) and the group dim over ``data`` — GSPMD inserts the
all-to-all-equivalent collectives. Capacity drops follow GShard/Switch
semantics (priority = routing order); dropped pairs renormalize over the
surviving ones.

Perf notes (qwen3 train_4k iteration):

* **Grouped dispatch** — slot positions need a running count of tokens per
  expert. A single global cumsum over (T·k, E) is a sequential scan over
  up to 8M rows (and XLA's cost model prices it quadratically); GShard's
  answer, used here, is G independent dispatch groups (aligned with the
  ``data`` axis shards) with capacity C/G each: the count is a per-group
  cumsum — G-way parallel and G× shorter.
* **Scatter-free combine** — the (token,k)-major gather comes back as
  (T, k, D); the output is a plain weighted sum over k, NOT a scatter-add
  (the original ``at[tok].add`` scatter was pure overhead since token ids
  are just ``repeat(arange(T), k)``).

The Pallas ``grouped_gemm`` kernel provides the dropless single-device
path used by the serving engine when a whole model fits one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import activation_fn, init_dense

__all__ = ["init_moe", "moe_ffn", "router_aux_loss"]


def init_moe(key, d_model: int, d_ff: int, num_experts: int, gated: bool,
             dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 4)
    import numpy as np

    def expert_stack(k, d_in, d_out):
        scale = 1.0 / np.sqrt(d_in)
        return (
            jax.random.normal(k, (num_experts, d_in, d_out), jnp.float32) * scale
        ).astype(dtype)

    params = {
        "router": init_dense(ks[0], d_model, num_experts, jnp.float32),
        "up": expert_stack(ks[1], d_model, d_ff),
        "down": expert_stack(ks[2], d_ff, d_model),
    }
    if gated:
        params["gate"] = expert_stack(ks[3], d_model, d_ff)
    return params


def _route(router_logits: jax.Array, k: int):
    """Top-k routing with renormalized probabilities (qwen3/mixtral style)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_i


def moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    num_experts: int,
    experts_per_token: int,
    capacity_factor: float,
    activation: str,
    dropless: bool = False,
    dispatch_groups: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (output (B,S,D), router aux loss scalar).

    ``dropless=True`` sets capacity = T (no token ever dropped) — used for
    decode steps, where T is tiny and drops would corrupt generation.
    ``dispatch_groups=G`` splits tokens into G independent dispatch groups
    (GShard semantics: capacity and drop decisions are per-group).
    """
    b, s, d = x.shape
    t = b * s
    k = experts_per_token
    g = 1 if dropless else max(1, dispatch_groups)
    if t % g:
        g = 1
    tg = t // g

    xf = x.reshape(t, d)
    logits = xf @ params["router"]  # (T, E) fp32
    probs, top_p, top_i = _route(logits, k)

    capacity = tg if dropless else int(
        max(1, capacity_factor * k * t / (num_experts * g)))

    # Per-group slot assignment: position of each (token, choice) within its
    # expert = exclusive running count, token-major within the group.
    # Computed SORT-BASED (§Perf iteration 2): a stable argsort of the
    # (tg*k,) expert ids + rank-within-segment is O(n log n) and O(n)
    # memory, vs the one-hot cumsum's O(n*E) tensors (8.6 GB/layer/pass at
    # qwen3 train_4k scale).
    flat_e = top_i.reshape(g, tg * k)  # (G, tg*k) expert ids

    def ranks_group(e_):
        n = e_.shape[0]
        order = jnp.argsort(e_, stable=True)  # routing-priority order
        seg_start = jnp.cumsum(jnp.bincount(e_, length=num_experts)) - jnp.bincount(
            e_, length=num_experts)
        rank_sorted = jnp.arange(n, dtype=jnp.int32) - seg_start[e_[order]].astype(
            jnp.int32)
        return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)

    slot = jax.vmap(ranks_group)(flat_e)  # (G, tg*k)
    keep = slot < capacity
    slot = jnp.where(keep, slot, capacity)  # parked off-end row, sliced away

    # Scatter tokens into the (G, E, C, D) buffer (vmapped over groups).
    xg = xf.reshape(g, tg, d)
    tok_idx = jnp.repeat(jnp.arange(tg), k)  # (tg*k,)

    def scatter_group(xg_, e_, s_):
        buf = jnp.zeros((num_experts, capacity + 1, d), x.dtype)
        return buf.at[e_, s_].add(xg_[tok_idx])

    buf = jax.vmap(scatter_group)(xg, flat_e, slot)[:, :, :capacity]

    # Batched expert FFN: (G, E, C, D) x (E, D, F) -> (G, E, C, F).
    act = activation_fn(activation)
    up = jnp.einsum("gecd,edf->gecf", buf, params["up"])
    if "gate" in params:
        up = act(jnp.einsum("gecd,edf->gecf", buf, params["gate"])) * up
    else:
        up = act(up)
    out_buf = jnp.einsum("gecf,efd->gecd", up, params["down"])  # (G, E, C, D)

    # Gather back in (token, k)-major order; combine is a weighted sum over
    # the k choices — no scatter needed.
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((g, num_experts, 1, d), out_buf.dtype)], axis=2
    )

    def gather_group(ob, e_, s_):
        return ob[e_, s_]  # (tg*k, D); parked slot -> zeros row

    gathered = jax.vmap(gather_group)(out_buf, flat_e, slot)  # (G, tg*k, D)
    gathered = gathered.reshape(t, k, d)
    w = top_p * keep.reshape(t, k).astype(jnp.float32)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    out = jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32), w)

    aux = router_aux_loss(probs, top_i, num_experts)
    return out.reshape(b, s, d).astype(x.dtype), aux


def router_aux_loss(probs: jax.Array, top_i: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    t = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)
