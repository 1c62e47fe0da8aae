"""Generic decoder-only model assembled from a ModelConfig.

Supports every assigned architecture family through the config's
``layer_pattern``: pure attention (llama-family), attention+MoE, Mamba-2
SSD stacks, and Griffin-style recurrent/attention hybrids — plus the
multimodal input conventions (musicgen codebook sums, llava vision-prefix
embeddings).

Depth is organized as ``num_groups`` repetitions of the pattern; parameters
are *stacked* over groups and the stack is driven by ``lax.scan``, keeping
HLO size independent of depth (26-64 layer dry-runs compile fast).

Entry points:
  * ``init_params(key, cfg)``
  * ``forward_train(params, cfg, batch)   -> (logits, aux)``
  * ``init_serve_cache(cfg, batch, cache_len)``
  * ``forward_prefill(params, cfg, batch, cache) -> (logits, cache)``
  * ``forward_decode(params, cfg, tokens, cur_pos, cache) -> (logits, cache)``
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn_lib
from . import moe as moe_lib
from . import rglru as rglru_lib
from . import ssm as ssm_lib
from .config import ModelConfig
from .layers import init_dense, init_mlp, init_rms_norm, mlp, rms_norm, softcap

__all__ = [
    "init_params",
    "forward_train",
    "init_serve_cache",
    "forward_prefill",
    "forward_decode",
    "loss_fn",
]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---- init --------------------------------------------------------------------


def _init_slot(key, cfg: ModelConfig, kind: str) -> dict:
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    params: dict[str, Any] = {"ln1": init_rms_norm(d, dt)}
    if kind in ("attention", "moe"):
        params["attn"] = attn_lib.init_attention(
            ks[0], d, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            cfg.qk_norm, dt,
        )
        params["ln2"] = init_rms_norm(d, dt)
        if kind == "attention":
            params["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.gated_mlp, dt)
        else:
            params["moe"] = moe_lib.init_moe(
                ks[1], d, cfg.d_ff, cfg.num_experts, cfg.gated_mlp, dt
            )
    elif kind == "ssd":
        params["mamba"] = ssm_lib.init_mamba2(
            ks[0], d, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_head_dim,
            cfg.ssm_conv_width, dt,
        )
    elif kind == "recurrent":
        params["rec"] = rglru_lib.init_rglru_block(
            ks[0], d, cfg.resolved_lru_width, cfg.rglru_conv_width, dt
        )
        params["ln2"] = init_rms_norm(d, dt)
        params["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.gated_mlp, dt)
    else:  # pragma: no cover
        raise ValueError(kind)
    return params


def init_params(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 3 + len(cfg.layer_pattern))
    params: dict[str, Any] = {}
    # Embeddings. musicgen: one table per codebook, summed on input.
    embed_shape = (cfg.num_codebooks, cfg.vocab_size, cfg.d_model)
    params["embed"] = (
        jax.random.normal(keys[0], embed_shape, jnp.float32) * 0.02
    ).astype(dt)
    if not cfg.tie_embeddings:
        params["unembed"] = init_dense(
            keys[1], cfg.d_model, cfg.num_codebooks * cfg.vocab_size, dt
        )
    params["final_norm"] = init_rms_norm(cfg.d_model, dt)
    if cfg.modality == "vision_prefix":
        # Projector from the (stubbed) vision encoder space to d_model.
        params["vision_proj"] = init_dense(keys[2], cfg.d_model, cfg.d_model, dt)

    # Stacked per-slot block params: leading axis = num_groups.
    blocks = []
    for slot, kind in enumerate(cfg.layer_pattern):
        gkeys = jax.random.split(keys[3 + slot], cfg.num_groups)
        blocks.append(jax.vmap(lambda k: _init_slot(k, cfg, kind))(gkeys))
    params["blocks"] = tuple(blocks)
    return params


# ---- embeddings / logits ------------------------------------------------------


def embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch: {"tokens": (B,S) or (B,S,K)} [+ "vision_embeds": (B,Nv,D)]."""
    tokens = batch["tokens"]
    if cfg.num_codebooks > 1:
        # (B,S,K) EnCodec token lattice: sum codebook embeddings.
        assert tokens.ndim == 3
        x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), _dtype(cfg))
        for k in range(cfg.num_codebooks):
            x = x + jnp.take(params["embed"][k], tokens[..., k], axis=0)
    else:
        tok = tokens if tokens.ndim == 2 else tokens[..., 0]
        x = jnp.take(params["embed"][0], tok, axis=0)
    if cfg.modality == "vision_prefix" and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([vis, x], axis=1)
    if cfg.embed_scale_by_sqrt_dim:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def unembed(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        # (B,S,D) x (K,V,D) -> (B,S,K,V)
        logits = jnp.einsum("bsd,kvd->bskv", x, params["embed"])
    else:
        logits = (x @ params["unembed"]).reshape(
            x.shape[0], x.shape[1], cfg.num_codebooks, cfg.vocab_size
        )
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    if cfg.num_codebooks == 1:
        logits = logits[:, :, 0, :]
    return logits


# ---- block application ---------------------------------------------------------


def _apply_slot_train(cfg: ModelConfig, kind: str, window: int | None,
                      slot_params: dict, x: jax.Array,
                      positions: jax.Array,
                      unroll: bool = False) -> tuple[jax.Array, jax.Array]:
    """Residual block application (training / no cache). Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, slot_params["ln1"], cfg.norm_eps)
    if kind in ("attention", "moe"):
        h = attn_lib.attention_train(
            slot_params["attn"], h, positions,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=window, logit_softcap=cfg.attn_logit_softcap,
            norm_eps=cfg.norm_eps, unroll=unroll,
        )
        x = x + h
        h = rms_norm(x, slot_params["ln2"], cfg.norm_eps)
        if kind == "attention":
            h = mlp(slot_params["mlp"], h, cfg.mlp_activation)
        else:
            h, aux = moe_lib.moe_ffn(
                slot_params["moe"], h,
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                capacity_factor=cfg.moe_capacity_factor,
                activation=cfg.mlp_activation,
                dispatch_groups=cfg.moe_dispatch_groups,
            )
        x = x + h
    elif kind == "ssd":
        h = ssm_lib.mamba2_train(
            slot_params["mamba"], h, d_inner=cfg.ssm_d_inner,
            d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            chunk=cfg.ssm_chunk, norm_eps=cfg.norm_eps, unroll=unroll,
        )
        x = x + h
    elif kind == "recurrent":
        h = rglru_lib.rglru_train(slot_params["rec"], h)
        x = x + h
        h = rms_norm(x, slot_params["ln2"], cfg.norm_eps)
        h = mlp(slot_params["mlp"], h, cfg.mlp_activation)
        x = x + h
    return x, aux


def forward_train(params: dict, cfg: ModelConfig, batch: dict,
                  *, remat: bool = False, unroll: bool = False,
                  act_spec=None):
    """Returns (logits (B,S,[K,]V), aux losses dict).

    ``remat=True`` activation-checkpoints each layer group (the production
    policy for the train_4k dry-runs: recompute within groups, save the
    inter-group residual stream). ``act_spec`` (a PartitionSpec) pins the
    residual-stream sharding inside the depth scan — without it the
    remat-saved carry stack loses its sharding and balloons per-device
    memory (found via dry-run memory_analysis).
    """
    x = embed_inputs(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def _pin(x):
        if act_spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, act_spec)

    x = _pin(x)

    def group_fn(carry, group_params):
        x, aux = carry
        for slot, kind in enumerate(cfg.layer_pattern):
            window = cfg.window_for_slot(slot)
            x, a = _apply_slot_train(
                cfg, kind, window, group_params[slot], x, positions,
                unroll=unroll,
            )
            aux = aux + a
        return (_pin(x), aux), None

    if remat:
        group_fn = jax.checkpoint(group_fn, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(
        group_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"],
        unroll=cfg.num_groups if unroll else 1,
    )
    logits = unembed(params, cfg, x)
    return logits, {"router_aux": aux / max(cfg.num_layers, 1)}


# ---- serving ------------------------------------------------------------------


def _slot_cache_init(cfg: ModelConfig, kind: str, window: int | None,
                     batch: int, cache_len: int, long_context: bool) -> dict:
    dt = _dtype(cfg)
    if kind in ("attention", "moe"):
        eff = cache_len if window is None else min(cache_len, window)
        return attn_lib.init_cache(batch, eff, cfg.num_kv_heads,
                                   cfg.resolved_head_dim, dt)
    if kind == "ssd":
        return ssm_lib.mamba2_init_cache(batch, cfg.ssm_d_inner, cfg.ssm_state,
                                         cfg.ssm_head_dim, cfg.ssm_conv_width, dt)
    if kind == "recurrent":
        return rglru_lib.rglru_init_cache(batch, cfg.resolved_lru_width,
                                          cfg.rglru_conv_width, dt)
    raise ValueError(kind)


def init_serve_cache(cfg: ModelConfig, batch: int, cache_len: int,
                     *, long_context: bool = False) -> tuple:
    """Per-slot caches stacked over groups (leading axis num_groups)."""
    caches = []
    for slot, kind in enumerate(cfg.layer_pattern):
        window = cfg.window_for_slot(slot, long_context=long_context)
        one = _slot_cache_init(cfg, kind, window, batch, cache_len, long_context)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.num_groups,) + a.shape), one
        )
        caches.append(stacked)
    return tuple(caches)


def _apply_slot_serve(cfg: ModelConfig, kind: str, window: int | None,
                      slot_params: dict, slot_cache: dict, x: jax.Array,
                      positions: jax.Array, cur_pos: jax.Array | None,
                      decode: bool, unroll: bool = False):
    """Returns (x, new slot cache)."""
    h = rms_norm(x, slot_params["ln1"], cfg.norm_eps)
    if kind in ("attention", "moe"):
        kw = dict(
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=window, logit_softcap=cfg.attn_logit_softcap,
            norm_eps=cfg.norm_eps,
        )
        if decode:
            h, new_cache = attn_lib.attention_decode(
                slot_params["attn"], h, cur_pos, slot_cache, **kw
            )
        else:
            h, new_cache = attn_lib.prefill_into_cache(
                slot_params["attn"], h, positions, slot_cache, unroll=unroll,
                **kw
            )
        x = x + h
        h = rms_norm(x, slot_params["ln2"], cfg.norm_eps)
        if kind == "attention":
            h = mlp(slot_params["mlp"], h, cfg.mlp_activation)
        else:
            h, _ = moe_lib.moe_ffn(
                slot_params["moe"], h,
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                capacity_factor=cfg.moe_capacity_factor,
                activation=cfg.mlp_activation,
                dropless=decode,  # decode: capacity = T, no drops
                dispatch_groups=cfg.moe_dispatch_groups,
            )
        x = x + h
    elif kind == "ssd":
        kw = dict(d_inner=cfg.ssm_d_inner, d_state=cfg.ssm_state,
                  head_dim=cfg.ssm_head_dim, norm_eps=cfg.norm_eps)
        if decode:
            h, new_cache = ssm_lib.mamba2_decode(
                slot_params["mamba"], h, slot_cache, **kw
            )
        else:
            h, new_cache = ssm_lib.mamba2_prefill(
                slot_params["mamba"], h, slot_cache, chunk=cfg.ssm_chunk,
                unroll=unroll, **kw
            )
        x = x + h
    elif kind == "recurrent":
        if decode:
            h, new_cache = rglru_lib.rglru_decode(slot_params["rec"], h, slot_cache)
        else:
            h, new_cache = rglru_lib.rglru_prefill(slot_params["rec"], h, slot_cache)
        x = x + h
        h = rms_norm(x, slot_params["ln2"], cfg.norm_eps)
        h = mlp(slot_params["mlp"], h, cfg.mlp_activation)
        x = x + h
    else:  # pragma: no cover
        raise ValueError(kind)
    return x, new_cache


def _forward_serve(params: dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, cur_pos: jax.Array | None,
                   caches: tuple, decode: bool, long_context: bool,
                   unroll: bool = False):
    def group_fn(x, group_in):
        group_params, group_cache = group_in
        new_caches = []
        for slot, kind in enumerate(cfg.layer_pattern):
            window = cfg.window_for_slot(slot, long_context=long_context)
            x, nc = _apply_slot_serve(
                cfg, kind, window, group_params[slot], group_cache[slot], x,
                positions, cur_pos, decode, unroll=unroll,
            )
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(group_fn, x, (params["blocks"], caches),
                                 unroll=cfg.num_groups if unroll else 1)
    logits = unembed(params, cfg, x)
    return logits, new_caches


def forward_prefill(params: dict, cfg: ModelConfig, batch: dict, caches: tuple,
                    *, long_context: bool = False, unroll: bool = False):
    x = embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    return _forward_serve(params, cfg, x, positions, None, caches,
                          decode=False, long_context=long_context,
                          unroll=unroll)


def forward_decode(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   cur_pos: jax.Array, caches: tuple, *,
                   vision_embeds: jax.Array | None = None,
                   long_context: bool = False, unroll: bool = False):
    """tokens: (B,1) or (B,1,K); cur_pos: scalar int32 position of the token."""
    batch = {"tokens": tokens}
    x = embed_inputs(params, cfg, batch)
    positions = cur_pos.reshape(1).astype(jnp.int32)
    return _forward_serve(params, cfg, x, positions, cur_pos, caches,
                          decode=True, long_context=long_context,
                          unroll=unroll)


# ---- loss ----------------------------------------------------------------------


def loss_fn(params: dict, cfg: ModelConfig, batch: dict,
            *, remat: bool = False, unroll: bool = False,
            act_spec=None) -> tuple[jax.Array, dict]:
    """Next-token cross entropy (+ MoE aux). batch needs "tokens" and "labels"."""
    logits, aux = forward_train(params, cfg, batch, remat=remat, unroll=unroll,
                                act_spec=act_spec)
    labels = batch["labels"]
    if cfg.num_codebooks > 1:
        assert labels.ndim == 3
    if cfg.modality == "vision_prefix" and "vision_embeds" in batch:
        # Logits cover [vision prefix + text]; score text positions only.
        nv = batch["vision_embeds"].shape[1]
        logits = logits[:, nv:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if cfg.num_codebooks > 1:
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    else:
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    total = loss + cfg.router_aux_loss_coef * aux["router_aux"]
    return total, {"ce": loss, **aux}
