"""Traffic generation from a mix file: one general generator for every cell.

A serve mix (``traffic/<mix>.json`` with ``"kind": "serve"``) gives
lognormal prompt and output lengths, the bucket lengths prompts snap up
to, and an arrival process.  The *set* of lengths and inter-arrival gaps
is drawn once from the mix's own ``shape_seed``; the run's ``--seed``
only permutes them and draws the prompt token ids.  So every seed offers
the same work, in another order, and runs of different seeds stay
comparable.

A train mix (``"kind": "train"``) gives the sequence length; its token
rows follow the synthetic Markov rule of ``repro.data.pipeline`` (next =
(3 * cur + 7) mod V, replaced by a uniform draw with probability 0.1),
here vectorised over rows and positions so that generating them costs
the yardstick nothing measurable.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class ServeSchedule:
    due_s: np.ndarray          # [n] scheduled send times, seconds into the window
    prompt_len: np.ndarray     # [n]
    output_len: np.ndarray     # [n]
    prompts: List[np.ndarray]  # n int32 arrays of token ids

    @property
    def n(self) -> int:
        return len(self.due_s)


def lognormal_lengths(rng, n, median, sigma, lo, hi):
    x = np.exp(np.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def snap_up(lengths, buckets):
    """Each length rounded up to the smallest bucket that holds it."""
    b = np.asarray(sorted(buckets))
    idx = np.searchsorted(b, lengths, side="left")
    if np.any(idx >= len(b)):
        raise ValueError(f"a length exceeds the largest bucket {b[-1]}")
    return b[idx]


def arrival_gaps(rng, n, process):
    """Unit-mean inter-arrival gaps: ``poisson`` (exponential gaps) or
    ``gamma`` with a coefficient of variation ``cv``."""
    kind = process.get("kind", "poisson")
    if kind == "poisson":
        return rng.exponential(1.0, n)
    if kind == "gamma":
        shape = 1.0 / process["cv"] ** 2
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {kind!r}")


def serve_schedule(mix: dict, rate: float, seconds: float, seed: int,
                   vocab: int) -> ServeSchedule:
    """The open-loop schedule of one run: ``round(rate * seconds)``
    requests whose gaps are scaled to fill the window exactly."""
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(int(mix["shape_seed"]))
    p, o = mix["prompt"], mix["output"]
    plen = snap_up(lognormal_lengths(base, n, p["median"], p["sigma"],
                                     p["min"], p["max"]), p["buckets"])
    olen = lognormal_lengths(base, n, o["median"], o["sigma"], o["min"],
                             o["max"])
    gaps = arrival_gaps(base, n, mix.get("arrivals", {}))
    rng = np.random.default_rng(seed)
    plen, olen, gaps = rng.permutation(plen), rng.permutation(olen), \
        rng.permutation(gaps)
    # the first request is due at 0 and the n gaps span the window
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds
                                                           / gaps.sum())
    prompts = [rng.integers(0, vocab, size=int(k), dtype=np.int64)
               .astype(np.int32) for k in plen]
    return ServeSchedule(due, plen, olen, prompts)


def markov_rows(rng, n_rows: int, length: int, vocab: int,
                noise: float = 0.1) -> np.ndarray:
    """[n_rows, length] int32 rows of the synthetic Markov chain, built
    without a Python loop over positions: position t holds the value the
    affine map x -> 3x + 7 (mod V) reaches from the row's last reset."""
    reset = rng.random((n_rows, length)) < noise
    reset[:, 0] = True
    draws = rng.integers(0, vocab, size=(n_rows, length))
    pos = np.arange(length)
    last = np.maximum.accumulate(np.where(reset, pos, -1), axis=1)
    k = pos[None, :] - last
    # a_k = 3^k mod V, b_k = 7 (3^k - 1) / 2 mod V, by the recurrence
    a = np.empty(length, np.int64)
    b = np.empty(length, np.int64)
    a[0], b[0] = 1 % vocab, 0
    for i in range(1, length):
        a[i] = (3 * a[i - 1]) % vocab
        b[i] = (3 * b[i - 1] + 7) % vocab
    start = np.take_along_axis(draws, last, axis=1)
    return ((a[k] * start + b[k]) % vocab).astype(np.int32)


def train_rows(mix: dict, n_rows: int, seed: int, vocab: int) -> dict:
    """Token and label rows for ``n_rows`` training sequences."""
    rng = np.random.default_rng(seed)
    toks = markov_rows(rng, n_rows, int(mix["seq_len"]) + 1, vocab,
                       float(mix.get("noise", 0.1)))
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
