"""Model zoo: unified decoder LM + whisper encoder-decoder.

`build_model(cfg)` returns a uniform functional API used by the trainer,
the server, and the chip benchmark.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable            # (key, dtype=..., vocab_pad_multiple=1) -> params
    loss_fn: Callable          # (params, batch) -> (loss, metrics)
    forward: Callable | None   # decoder-only full forward
    init_cache: Callable       # (batch, max_len, dtype, ...) -> caches
    decode_step: Callable      # (params, caches, token, pos) -> (logits, caches)
    prefill: Callable | None
    # prefill states -> init_cache decode layout (serving-plane plumbing)
    cache_from_prefill: Callable | None = None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encoder_decoder:
        from repro.models import whisper as W
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32, vocab_pad_multiple=1:
                W.init_params(key, cfg, dtype, vocab_pad_multiple),
            loss_fn=lambda params, batch, compute_dtype=jnp.bfloat16,
                remat=False, unroll=False:
                W.loss_fn(params, cfg, batch, compute_dtype, remat, unroll),
            forward=None,
            init_cache=lambda batch, max_len, dtype=jnp.bfloat16, **kw:
                W.init_cache(cfg, batch, max_len, dtype, **kw),
            decode_step=lambda params, caches, token, pos,
                compute_dtype=jnp.bfloat16, **kw:
                W.decode_step(params, cfg, caches, token, pos, compute_dtype,
                              **kw),
            prefill=None,
        )
    from repro.models import transformer as T
    return Model(
        cfg=cfg,
        init=lambda key, dtype=jnp.float32, vocab_pad_multiple=1:
            T.init_params(key, cfg, dtype, vocab_pad_multiple),
        loss_fn=lambda params, batch, compute_dtype=jnp.bfloat16, remat=False,
            unroll=False:
            T.loss_fn(params, cfg, batch, compute_dtype, remat, unroll),
        forward=lambda params, tokens, **kw: T.forward(params, cfg, tokens, **kw),
        init_cache=lambda batch, max_len, dtype=jnp.bfloat16, **kw:
            T.init_cache(cfg, batch, max_len, dtype, **kw),
        decode_step=lambda params, caches, token, pos,
            compute_dtype=jnp.bfloat16, **kw:
            T.decode_step(params, cfg, caches, token, pos, compute_dtype, **kw),
        prefill=lambda params, tokens, **kw: T.prefill(params, cfg, tokens, **kw),
        cache_from_prefill=lambda fwd_caches, max_len, dtype=jnp.bfloat16, **kw:
            T.cache_from_prefill(cfg, fwd_caches, max_len, dtype, **kw),
    )
