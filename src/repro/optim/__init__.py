"""Optimizers (pure pytree transforms; eval_shape friendly).

Interface: ``opt.init(params) -> state``; ``opt.step(params, grads, state,
lr) -> (params, state)``.
"""
from repro.optim.sgd import SGD
from repro.optim.adam import Adam, AdamW
from repro.optim.adafactor import Adafactor
from repro.optim.schedule import cosine_warmup, constant

OPTIMIZERS = {"sgd": SGD, "adam": Adam, "adamw": AdamW,
              "adafactor": Adafactor}

__all__ = ["SGD", "Adam", "AdamW", "Adafactor", "cosine_warmup", "constant",
           "OPTIMIZERS"]
