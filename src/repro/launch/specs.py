"""ShapeDtypeStruct stand-ins + sharding specs for every (arch x shape).

`train_input_specs(cfg, shape)` produces abstract inputs (no allocation);
`cache_specs` assigns PartitionSpecs with divisibility-aware fallbacks
(e.g. long_500k batch=1: the batch axis cannot shard, so the sequence
axis of attention caches shards over `data` instead, and SSM states shard
heads over `model`).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.configs.base import InputShape, ModelConfig
from repro.core.parallelism import data_axes

VOCAB_PAD = 16       # model-axis shard count
VISION_PATCHES = 256
SWA_WINDOW = 4096    # sliding-window override for dense archs at long_500k


def _div(n: int, k: int) -> bool:
    return n % k == 0


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def train_input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.is_encoder_decoder:
        # conv/mel frontend stub: precomputed frame embeddings
        return {"frames": jax.ShapeDtypeStruct(
                    (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
             "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.family == "vlm":
        # ViT stub: precomputed patch embeddings + M-RoPE position ids
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (B, VISION_PATCHES, cfg.d_model), jnp.bfloat16)
        specs["positions"] = jax.ShapeDtypeStruct((B, 3, S), i32)
    return specs


# ------------------------------------------------------------------- caches
def decode_window(cfg: ModelConfig, shape: InputShape) -> int:
    """window_override for the serve step (0 = full cache)."""
    if shape.name != "long_500k":
        return 0
    if cfg.attn_type == "mla":
        return 0          # MLA latent cache makes full 500k memory-feasible
    if cfg.family in ("dense", "vlm", "moe"):
        return SWA_WINDOW  # sub-quadratic requirement: sliding window
    return 0              # hybrid/ssm already have bounded state


def cache_leaf_spec(names, shape_t: Tuple[int, ...], *, multi_pod: bool,
                    shard_batch: bool, model_n: int, data_n: int) -> P:
    name = names[-1] if names else ""
    dp = data_axes(multi_pod)
    b = dp if shard_batch else None

    def model_split(*dims):
        """pick the first trailing dim divisible by the model axis."""
        for di in dims:
            if _div(shape_t[di], model_n):
                return di
        return None

    if name in ("k", "v"):           # [..., B, L, KV, hd]
        nd = len(shape_t)
        lead = (None,) * (nd - 4)
        l_spec = None if shard_batch else (dp if _div(shape_t[nd - 3],
                                                      data_n) else None)
        mi = model_split(nd - 2, nd - 1)
        tail = [b, l_spec, None, None]
        if mi is not None:
            tail[mi - (nd - 4)] = "model"
        return P(*(lead + tuple(tail)))
    if name in ("c_kv", "k_rope"):   # [..., B, L, r]
        nd = len(shape_t)
        lead = (None,) * (nd - 3)
        l_spec = None if shard_batch else (dp if _div(shape_t[nd - 2],
                                                      data_n) else None)
        r_spec = "model" if _div(shape_t[nd - 1], model_n) else None
        return P(*(lead + (b, l_spec, r_spec)))
    if name == "S":                  # [..., B, H, hs, hs]
        nd = len(shape_t)
        lead = (None,) * (nd - 4)
        h_spec = "model" if _div(shape_t[nd - 3], model_n) else None
        return P(*(lead + (b, h_spec, None, None)))
    if name in ("h", "shift", "shift_tm", "shift_cm"):   # [..., B, w]
        nd = len(shape_t)
        lead = (None,) * (nd - 2)
        w_spec = "model" if _div(shape_t[nd - 1], model_n) else None
        return P(*(lead + (b, w_spec)))
    if name == "conv":               # [..., B, cw-1, w]
        nd = len(shape_t)
        lead = (None,) * (nd - 3)
        w_spec = "model" if _div(shape_t[nd - 1], model_n) else None
        return P(*(lead + (b, None, w_spec)))
    return P(*([None] * len(shape_t)))


def cache_specs(cache_shapes, mesh: Mesh, multi_pod: bool,
                shard_batch: bool):
    sizes = mesh_axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    data_n = sizes.get("data", 1) * sizes.get("pod", 1)

    def one(path, leaf):
        names = []
        for k in path:
            if isinstance(k, DictKey):
                names.append(str(k.key))
            elif isinstance(k, SequenceKey):
                names.append(f"[{k.idx}]")
        return cache_leaf_spec(names, leaf.shape, multi_pod=multi_pod,
                               shard_batch=shard_batch, model_n=model_n,
                               data_n=data_n)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)
