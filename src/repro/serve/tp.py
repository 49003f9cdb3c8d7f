"""Tensor-parallel decode for the serving plane.

Reuses the training-side Megatron decomposition (repro.core.parallelism
role tables, repro.parallel.staged f/g collectives) for inference: over a
("model",) mesh of ``tp`` devices,

  * wq/wk/wv and w_gate/w_up are **column-sharded** (each rank owns
    ``H/tp`` query heads, ``KV/tp`` kv heads, ``ff/tp`` hidden),
  * wo and w_down are **row-sharded**, their partial products summed by
    ``tensor_reduce`` inside ``decode_step(tp_axis="model")``,
  * cache pages are sharded on the **KV-head axis** (always ``ndim-2`` of
    every attention cache leaf — contiguous rows, ring buffers, and paged
    pools alike), so each rank holds only its heads' history,
  * embeddings / norms / lm_head stay replicated — decode activations are
    replicated between the f/g pairs, exactly the training-side layout.

Inside the ``shard_map`` each rank runs the *same* engine step function
against a head-shrunk config (``num_heads/tp``, ``num_kv_heads/tp``), so
paged gather/scatter and sampling need no TP-specific code.  Serving TP
is restricted to pure-GQA decoders (no MoE / MLA / recurrent blocks and
no biases — row-parallel bias would be added ``tp`` times).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

_COL = frozenset({"wq", "wk", "wv", "w_gate", "w_up"})
_ROW = frozenset({"wo", "w_down"})


def check_tp_supported(cfg: ModelConfig, tp: int) -> None:
    bad = [k for k in cfg.layer_kinds if k not in ("attn", "local")]
    if bad:
        raise ValueError(f"tp decode needs attention-only stacks, got {bad}")
    if cfg.attn_type == "mla":
        raise ValueError("tp decode does not shard MLA latent caches")
    if cfg.moe:
        raise ValueError("tp decode does not support MoE layers")
    if cfg.use_bias:
        raise ValueError("tp decode requires use_bias=False "
                         "(row-parallel bias would be applied tp times)")
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={cfg.num_heads} and "
            f"num_kv_heads={cfg.num_kv_heads}")


def _path_names(path) -> list:
    return [getattr(p, "key", None) for p in path]


def param_specs(params) -> Any:
    """PartitionSpec tree for serve-TP: column weights shard their last
    axis, row weights their second-to-last (leading scan-group axes
    shift positions, hence from-the-end indexing); the rest replicate."""
    def spec(path, leaf):
        names = _path_names(path)
        if any(n in _COL for n in names):
            return P(*([None] * (leaf.ndim - 1) + ["model"]))
        if any(n in _ROW for n in names):
            return P(*([None] * (leaf.ndim - 2) + ["model", None]))
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def store_specs(store) -> Any:
    """Every cache leaf of a pure-GQA decoder is [..., KV, hd]-shaped
    (contiguous [B,L,KV,hd], ring [B,W,KV,hd], pools [Np,page,KV,hd],
    scan-stacked with a leading G) — shard the KV-head axis at ndim-2."""
    return jax.tree.map(
        lambda a: P(*([None] * (a.ndim - 2) + ["model", None])), store)


class TPContext:
    def __init__(self, cfg: ModelConfig, tp: int):
        check_tp_supported(cfg, tp)
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(f"tp={tp} but only {len(devs)} devices")
        self.tp = tp
        self.mesh = Mesh(np.array(devs[:tp]), ("model",))
        self.cfg = cfg
        # each rank runs the ordinary decode math at 1/tp the heads
        self.cfg_local = dataclasses.replace(
            cfg, num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp)

    def place(self, params, store):
        """Params and KV store laid out on the mesh as the step's
        ``shard_map`` reads them.  Done once, at engine construction, so
        no step call re-shards the model."""
        def put(tree, specs):
            return jax.device_put(tree, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))
        return (put(params, param_specs(params)),
                put(store, store_specs(store)))

    def replicate(self, tree):
        """``tree`` copied to every device of the mesh."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def wrap_step(self, step_fn, params, store):
        """shard_map the engine's step(params, store, bt, tokens, pos,
        active, seeds, tok_idx, temp, topk) -> (next_tokens, new_store).
        Everything but params/store is replicated; sampled tokens come
        back replicated (every rank computes them identically from the
        reduced logits), so the result is checked loosely."""
        ss = store_specs(store)
        in_specs = (param_specs(params), ss) + (P(),) * 8
        out_specs = (P(), ss)
        return jax.shard_map(step_fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
