"""PartitionSpec rules for params, activations, and caches.

Baseline sharding plan (DESIGN.md §6):

* ``model`` axis — tensor parallel: attention q/k/v column-sharded, o
  row-sharded; MLP up/gate column-, down row-sharded; vocab/embedding
  sharded; MoE experts expert-parallel when E % axis == 0 (qwen3:
  128/16=8), else tensor-parallel inside each expert (grok: 8 experts,
  d_ff 32768/16); Mamba z/x projections and RG-LRU width column-sharded
  with block-local gates.
* ``data`` (x ``pod``) axis — batch sharding for train/prefill/decode; for
  long_500k (batch=1) the KV cache *sequence* dim shards over ``data``
  (context-parallel decode) while recurrent states shard nothing.

Everything here is *rules over pytree paths*, so new substrates
automatically get sane defaults (replicated) until given a rule.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

__all__ = [
    "param_specs",
    "train_batch_specs",
    "decode_input_specs",
    "cache_specs",
    "data_axes",
]


def data_axes(multi_pod: bool):
    """The composite data-parallel mesh axes."""
    return ("pod", "data") if multi_pod else ("data",)


# ---- parameters ---------------------------------------------------------------


def _param_rule(path: str, ndim: int, cfg: ModelConfig, model_axis: int):
    """Return a PartitionSpec for a parameter leaf (stacked leaves included).

    ``path`` is the '/'-joined pytree path; stacked block leaves start with
    ``blocks/[i]/`` and carry a leading group axis (never sharded).
    """
    expert_parallel = cfg.num_experts > 0 and cfg.num_experts % model_axis == 0

    def stacked(*spec):
        # Prepend None for the group axis if this leaf is depth-stacked.
        return P(None, *spec) if "blocks/" in path else P(*spec)

    # --- embeddings / head ---
    if path == "embed":  # (K, V, D)
        return P(None, "model", None)
    if path == "unembed":  # (D, K*V)
        return P(None, "model")
    if path == "vision_proj":
        return P(None, "model")
    if path == "final_norm":
        return P(None)

    # --- attention ---
    if re.search(r"attn/w[qkv]$", path):  # (D, H*hd) column parallel
        return stacked(None, "model")
    if path.endswith("attn/wo"):  # (H*hd, D) row parallel
        return stacked("model", None)
    if re.search(r"attn/[qk]_norm$", path):
        return stacked(None)

    # --- dense MLP ---
    if re.search(r"mlp/(up|gate)$", path):
        return stacked(None, "model")
    if path.endswith("mlp/down"):
        return stacked("model", None)

    # --- MoE ---
    if path.endswith("moe/router"):  # (D, E)
        return stacked(None, None)
    if re.search(r"moe/(up|gate)$", path):  # (E, D, F)
        return stacked("model", None, None) if expert_parallel else stacked(
            None, None, "model"
        )
    if path.endswith("moe/down"):  # (E, F, D)
        return stacked("model", None, None) if expert_parallel else stacked(
            None, "model", None
        )

    # --- Mamba-2 ---
    if re.search(r"mamba/in_[zx]$", path):  # (D, d_inner) column parallel
        return stacked(None, "model")
    if re.search(r"mamba/(in_bc|in_dt|conv_bc_w|conv_bc_b|A_log|D|dt_bias)$", path):
        return stacked(*([None] * (ndim - (1 if "blocks/" in path else 0))))
    if path.endswith("mamba/conv_x_w"):  # (width, d_inner)
        return stacked(None, "model")
    if path.endswith("mamba/conv_x_b"):
        return stacked("model")
    if path.endswith("mamba/norm"):
        return stacked("model")
    if path.endswith("mamba/out_proj"):  # (d_inner, D) row parallel
        return stacked("model", None)

    # --- RG-LRU ---
    if re.search(r"rec/in_(x|gate)$", path):
        return stacked(None, "model")
    if re.search(r"rec/w_[ri]$", path):  # (_NB, blk, blk) block-diagonal
        return stacked("model", None, None)
    if re.search(r"rec/(b_[ri]|lam|conv_w|conv_b)$", path):
        if path.endswith("conv_w"):
            return stacked(None, "model")
        return stacked("model")
    if path.endswith("rec/out"):  # (W, D) row parallel
        return stacked("model", None)

    # --- norms & defaults ---
    if re.search(r"ln[12]$", path):
        return stacked(None)
    # Fallback: replicate.
    n_extra = ndim - (1 if "blocks/" in path else 0)
    return stacked(*([None] * n_extra))


def _path_str(entries) -> str:
    parts = []
    for e in entries:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(f"[{e.idx}]")
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_specs(params: Any, cfg: ModelConfig, *, model_axis: int) -> Any:
    """Pytree of PartitionSpecs matching ``params``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path_entries, leaf in flat:
        path = _path_str(path_entries).replace("/[", "/[").replace("blocks/[",
                                                                   "blocks/[")
        # Normalize "blocks/[0]/..." -> "blocks/..." marker retained.
        norm = re.sub(r"blocks/\[\d+\]/", "blocks/", path)
        spec = _param_rule(norm, leaf.ndim, cfg, model_axis)
        # Guard: never shard a dim that isn't divisible by the axis size.
        spec = _check_divisible(spec, leaf.shape, model_axis)
        specs.append(spec)
    return jax.tree.unflatten(jax.tree.structure(params), specs)


def apply_fsdp(specs: Any, params: Any, *, fsdp_axes=("data",),
               axis_size: int = 16, min_elements: int = 1 << 16) -> Any:
    """ZeRO/FSDP-style extra sharding: on each large leaf, shard the biggest
    still-replicated dim over ``fsdp_axes`` when divisible. Applied to both
    params and optimizer state for the train dry-runs — without it the
    314B-param archs cannot fit 16 GB/chip (DESIGN.md §6)."""

    def one(spec: P, leaf) -> P:
        import numpy as np

        if np.prod(leaf.shape) < min_elements:
            return spec
        entries = list(tuple(spec) + (None,) * (leaf.ndim - len(spec)))
        # Largest still-replicated dim. (Sharding the leading layer-stack
        # axis instead was tried and REFUTED: the depth scan then gathers
        # the whole stacked array up front — +210% temp memory on
        # grok-1-314b prefill.)
        order = sorted(range(leaf.ndim), key=lambda i: -leaf.shape[i])
        for i in order:
            if entries[i] is None and leaf.shape[i] % axis_size == 0:
                entries[i] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
                break
        return P(*entries)

    return jax.tree.map(one, specs, params)


def _check_divisible(spec: P, shape, model_axis: int) -> P:
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax == "model" and dim % model_axis != 0:
            fixed.append(None)
        else:
            fixed.append(ax)
    return P(*fixed)


# ---- inputs --------------------------------------------------------------------


def train_batch_specs(cfg: ModelConfig, *, multi_pod: bool) -> dict:
    dp = data_axes(multi_pod)
    specs = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.num_codebooks > 1:
        specs = {"tokens": P(dp, None, None), "labels": P(dp, None, None)}
    if cfg.modality == "vision_prefix":
        specs["vision_embeds"] = P(dp, None, None)
    return specs


def decode_input_specs(cfg: ModelConfig, batch: int, *, multi_pod: bool,
                       n_data: int) -> dict:
    dp = data_axes(multi_pod)
    shard_batch = batch % (n_data * (2 if multi_pod else 1)) == 0
    bspec = dp if shard_batch else None
    tok = P(bspec, None, None) if cfg.num_codebooks > 1 else P(bspec, None)
    return {"tokens": tok, "cur_pos": P()}


def cache_specs(cfg: ModelConfig, batch: int, *, multi_pod: bool,
                n_data: int, model_axis: int, context_parallel: bool,
                decode: bool = False) -> tuple:
    """Per-slot cache PartitionSpecs mirroring ``init_serve_cache`` output.

    ``context_parallel=True`` (long_500k, batch too small to shard) shards
    attention cache *sequence* over ``data`` instead of batch.

    ``decode=True`` additionally shards the cache sequence over the
    ``model`` axis whenever KV heads cannot shard it (kv % model != 0):
    flash-decode-style context parallelism. Without this, GSPMD replicates
    (all-gathers) the fp32-converted cache on every layer — 135 GB/device
    per decoded token on yi-34b decode_32k.
    """
    dp = data_axes(multi_pod)
    total_dp = n_data * (2 if multi_pod else 1)
    shard_batch = batch % total_dp == 0 and not context_parallel
    b = dp if shard_batch else None
    kv_ax = "model" if cfg.num_kv_heads % model_axis == 0 else None

    specs = []
    for slot, kind in enumerate(cfg.layer_pattern):
        if kind in ("attention", "moe"):
            seq_axes = []
            if context_parallel:
                seq_axes += list(dp)
            if decode and kv_ax is None:
                seq_axes.append("model")
            seq_ax = tuple(seq_axes) if seq_axes else None
            specs.append({
                "k": P(None, b, seq_ax, kv_ax, None),  # (G,B,L,KV,hd)
                "v": P(None, b, seq_ax, kv_ax, None),
                "pos": P(None, seq_ax),  # (G, L)
            })
        elif kind == "ssd":
            h_ax = "model" if cfg.ssm_heads % model_axis == 0 else None
            specs.append({
                "ssm": P(None, b, h_ax, None, None),  # (G,B,H,P,N)
                "conv": P(None, b, None, None),  # (G,B,w-1,C)
            })
        elif kind == "recurrent":
            w_ax = "model" if cfg.resolved_lru_width % model_axis == 0 else None
            specs.append({
                "h": P(None, b, w_ax),  # (G,B,W)
                "conv": P(None, b, None, w_ax),  # (G,B,w-1,W)
            })
        else:  # pragma: no cover
            raise ValueError(kind)
    return tuple(specs)
