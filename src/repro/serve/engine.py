"""Step-synchronous serving engine: continuous batching over a paged KV
cache with prefill/decode separation.

One ``ServeEngine`` iteration is

  1. **admission** — the batcher moves QUEUED requests into free batch
     slots once their cache pages are reserved (serve/batcher.py);
  2. **prefill** — admitted prompts run as *batched forward passes*
     (grouped by prompt length so recurrent states see no padding), the
     resulting states are converted to decode layout by
     ``Model.cache_from_prefill`` and written into the request's cache
     pages; the prompt's last-token logits yield the first new token;
  3. **decode** — every DECODE-state slot advances one token through a
     single jitted step: gather pages -> per-slot-position decode
     (``decode_step`` vmapped over batch slots, so each slot carries its
     own position) -> sample -> scatter the new KV row back to its page.

The engine clock is **virtual iteration time** — each prefill group and
each decode iteration costs 1.0 — so time-to-first-token / per-token
latencies and the continuous-vs-oneshot comparison are deterministic and
machine-independent (benchmarks additionally record wall seconds).

Decode is vmapped at batch size 1 per slot, so co-batched requests can
never influence each other's tokens — the isolation continuous batching
promises.  (For capacity-based MoE models this differs from the seed's
batched decode, where expert-capacity dropping depended on whichever
requests happened to share the batch; per-request isolation is the
behavior we actually want, but it means MoE token streams are not
bit-compatible with the old loop.)

Tensor-parallel decode (``ServeConfig.tp > 1``) wraps the same jitted
step in ``shard_map`` over a ("model",) mesh: attention heads and MLP
hidden are sharded via serve/tp.py, cache pages are sharded on the KV
head axis, and ``decode_step(tp_axis=...)`` inserts the Megatron-style
``tensor_reduce`` pair after the row-parallel matmuls.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.transformer import plan_segments
from repro.obs.trace import get_recorder
from repro.serve.batcher import Batcher
from repro.serve.cache import make_kv_store
from repro.serve.request import Request, RequestState, summarize
from repro.serve.sampling import sample_tokens


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs.  ``page_size == 0`` keeps the seed's contiguous
    per-slot cache; ``> 0`` switches to paged pools (``num_pages`` caps
    the pool — None sizes it so every slot can hold ``max_len``)."""
    slots: int = 4
    max_len: int = 128
    page_size: int = 0
    num_pages: Optional[int] = None
    policy: str = "continuous"           # | "oneshot"
    cache_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    window_override: int = 0
    tp: int = 1                          # tensor-parallel decode degree


class ServeEngine:
    """``slo`` optionally attaches an ``obs.slo.SLOMonitor``: the engine
    feeds it TTFT/TPOT on every completion and a stall sample every
    iteration, emits an ``slo_burn`` instant on each transition into
    firing, and records the alert times in ``slo_alerts`` — the signal
    ``Autoscaler.schedule(..., burn_times=...)`` consumes."""

    def __init__(self, model, params, scfg: ServeConfig, slo=None):
        if model.forward is None:
            raise ValueError("ServeEngine serves decoder-only models")
        self.model, self.params, self.scfg = model, params, scfg
        self.slo = slo
        self.slo_alerts: List[dict] = []
        self._slo_firing = False
        self.cfg = model.cfg
        self.vocab = self.cfg.vocab_size

        self._tp = None
        if scfg.tp > 1:
            from repro.serve.tp import TPContext
            self._tp = TPContext(self.cfg, scfg.tp)

        self.kv = make_kv_store(
            model, scfg.slots, scfg.max_len, scfg.page_size, scfg.num_pages,
            dtype=scfg.cache_dtype, window_override=scfg.window_override)
        # the decode step's params: under tp, sharded over the mesh once
        # here (prefill keeps ``params``: its Pallas kernels run on one
        # device)
        self._step_params = params
        if self._tp is not None:
            self._step_params, self.kv.store = self._tp.place(
                params, self.kv.store)
        self.batcher = Batcher(self.kv, scfg.slots, scfg.policy)

        self.requests: List[Request] = []
        self.clock = 0.0
        self.decode_iterations = 0
        self.prefill_groups = 0
        # rids whose lifecycle spans this engine opened — a request is
        # only ever *ended* on the trace if tracing saw it get submitted
        self._traced_rids: set = set()

        B = scfg.slots
        self._last_tok = np.zeros(B, np.int32)
        self._seeds = np.zeros(B, np.int32)
        self._temp = np.zeros(B, np.float32)
        self._topk = np.zeros(B, np.int32)
        # per-segment batch axis of the cache pytree (scan groups stack a
        # leading group axis, pushing batch to axis 1)
        self._axes = [0 if seg[0] == "plain" else 1
                      for seg in plan_segments(self.cfg)]
        self._step = self._build_step()

    # ------------------------------------------------------- jitted step
    def _build_step(self):
        kv, axes, vocab = self.kv, self._axes, self.vocab
        cdt, wov = self.scfg.compute_dtype, self.scfg.window_override
        cfg_used = self._tp.cfg_local if self._tp else self.cfg
        tp_axis = "model" if self._tp else None

        def step(params, store, bt, tokens, pos, active, seeds, tok_idx,
                 temp, topk):
            contig = kv.gather(store, bt)

            def one(tok, p, caches_nb):
                # re-add the batch dim vmap stripped, per segment axis
                c1 = [jax.tree.map(lambda a, _ax=ax: jnp.expand_dims(a, _ax),
                                   sub) for sub, ax in zip(caches_nb, axes)]
                lg, nc = T.decode_step(params, cfg_used, c1,
                                       tok[None, None], p,
                                       compute_dtype=cdt,
                                       window_override=wov, tp_axis=tp_axis)
                nc = [jax.tree.map(lambda a, _ax=ax: jnp.squeeze(a, _ax),
                                   sub) for sub, ax in zip(nc, axes)]
                return lg[0, 0], nc

            # vmap over batch slots so every slot decodes AT ITS OWN
            # position — the heart of continuous batching
            logits, new = jax.vmap(one, in_axes=(0, 0, axes),
                                   out_axes=(0, axes))(tokens, pos, contig)
            keys = jax.vmap(
                lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i))(
                seeds, tok_idx)
            nxt = sample_tokens(logits, vocab, keys, temp, topk)
            new_store = kv.scatter(store, new, bt, pos, active)
            return nxt, new_store

        if self._tp is None:
            return jax.jit(step)
        return jax.jit(self._tp.wrap_step(step, self._step_params,
                                          self.kv.store))

    # --------------------------------------------------------- lifecycle
    def submit(self, request: Request) -> None:
        self.requests.append(request)
        self.batcher.submit(request)
        rec = get_recorder()
        if rec.enabled:
            # lifecycle track per request: QUEUED -> PREFILL -> DECODE
            # spans back to back on tid=req<rid> (docs/observability.md)
            self._traced_rids.add(request.rid)
            rec.begin("queued", pid="serve", tid=f"req{request.rid}",
                      cat="serve", clock=("serve_iter", self.clock),
                      rid=request.rid, prompt_len=request.prompt_len,
                      max_new_tokens=request.max_new_tokens,
                      arrival=request.arrival)

    def _finish(self, r: Request) -> None:
        r.state = RequestState.DONE
        r.finish_time = self.clock
        self.batcher.release(r)
        if self.slo is not None:
            self.slo.observe("ttft", self.clock, r.first_token_latency())
            self.slo.observe("tpot", self.clock, r.per_token_latency())
        rec = get_recorder()
        if rec.enabled and r.rid in self._traced_rids:
            rec.end(pid="serve", tid=f"req{r.rid}",      # closes "decode"
                    generated=len(r.output))
            rec.instant("done", pid="serve", tid=f"req{r.rid}", cat="serve",
                        clock=("serve_iter", self.clock), rid=r.rid)
            self._traced_rids.discard(r.rid)

    def _set_slot(self, r: Request, token: int) -> None:
        i = r.slot
        self._last_tok[i] = token
        self._seeds[i] = r.sampling.seed
        self._temp[i] = r.sampling.temperature
        self._topk[i] = r.sampling.top_k

    def _prefill(self, admitted: Sequence[Request]) -> None:
        """Batched prefill, grouped by prompt length (equal lengths — no
        padding, so recurrent states and ring buffers stay exact)."""
        groups: Dict[int, List[Request]] = {}
        for r in admitted:
            groups.setdefault(r.prompt_len, []).append(r)
        rec = get_recorder()
        for plen in sorted(groups):
            rs = groups[plen]
            if rec.enabled:
                for r in rs:
                    if r.rid in self._traced_rids:
                        rec.end(pid="serve", tid=f"req{r.rid}")  # "queued"
                        rec.begin("prefill", pid="serve",
                                  tid=f"req{r.rid}", cat="serve",
                                  clock=("serve_iter", self.clock),
                                  rid=r.rid, slot=r.slot, group_len=plen)
            toks = jnp.asarray(
                np.array([list(r.prompt) for r in rs], np.int32))
            logits, states = self.model.prefill(
                self.params, toks, compute_dtype=self.scfg.compute_dtype,
                window_override=self.scfg.window_override)
            conv = self.model.cache_from_prefill(
                states, self.scfg.max_len, dtype=self.scfg.cache_dtype,
                window_override=self.scfg.window_override)
            if self._tp is not None:
                conv = self._tp.replicate(conv)
            for j, r in enumerate(rs):
                self.kv.write_prefill(r.slot, conv, j, plen)

            # first new token straight from the prefill logits
            seeds = jnp.asarray([r.sampling.seed for r in rs],
                                dtype=jnp.int32)
            keys = jax.vmap(
                lambda s: jax.random.fold_in(jax.random.PRNGKey(s), 0))(
                seeds)
            t0 = np.asarray(sample_tokens(
                logits[:, 0].astype(jnp.float32), self.vocab, keys,
                jnp.asarray([r.sampling.temperature for r in rs],
                            dtype=jnp.float32),
                jnp.asarray([r.sampling.top_k for r in rs],
                            dtype=jnp.int32)))

            self.clock += 1.0
            self.prefill_groups += 1
            for j, r in enumerate(rs):
                tok = int(t0[j])
                r.output.append(tok)
                r.first_token_time = self.clock
                r.state = RequestState.DECODE
                self._set_slot(r, tok)
                if rec.enabled and r.rid in self._traced_rids:
                    rec.end(pid="serve", tid=f"req{r.rid}")  # "prefill"
                    rec.begin("decode", pid="serve", tid=f"req{r.rid}",
                              cat="serve",
                              clock=("serve_iter", self.clock),
                              rid=r.rid, slot=r.slot)
                if len(r.output) >= r.max_new_tokens:
                    self._finish(r)

    def _step_args(self, pos, active, tok_idx):
        return (self._step_params, self.kv.store,
                self.kv.block_tables_device(), jnp.asarray(self._last_tok),
                jnp.asarray(pos), jnp.asarray(active),
                jnp.asarray(self._seeds), jnp.asarray(tok_idx),
                jnp.asarray(self._temp), jnp.asarray(self._topk))

    def lower_step(self):
        """The jitted decode step lowered at this engine's shapes (read
        the program it compiles to with ``.compile().as_text()``)."""
        idle = np.zeros(self.scfg.slots, np.int32)
        return self._step.lower(*self._step_args(
            idle, np.zeros(self.scfg.slots, bool), idle))

    def _decode_iteration(self) -> None:
        B = self.scfg.slots
        pos = np.zeros(B, np.int32)
        tok_idx = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        decoding: List[Request] = []
        for i, r in enumerate(self.batcher.running):
            if r is not None and r.state is RequestState.DECODE:
                active[i] = True
                pos[i] = r.prompt_len + len(r.output) - 1
                tok_idx[i] = len(r.output)
                decoding.append(r)
        nxt, new_store = self._step(*self._step_args(pos, active, tok_idx))
        self.kv.store = new_store
        self.clock += 1.0
        self.decode_iterations += 1
        nxt = np.asarray(nxt)
        for r in decoding:
            tok = int(nxt[r.slot])
            r.output.append(tok)
            self._last_tok[r.slot] = tok
            if len(r.output) >= r.max_new_tokens:
                self._finish(r)

    def _emit_occupancy(self, rec) -> None:
        """Counter tracks: paged-KV pool occupancy (or contiguous slot
        occupancy) sampled once per engine iteration."""
        alloc = getattr(self.kv, "allocator", None)
        clock = ("serve_iter", self.clock)
        if alloc is not None:
            rec.counter("kv_pages",
                        {"used": alloc.capacity - alloc.free_pages,
                         "free": alloc.free_pages},
                        pid="serve", cat="serve", clock=clock)
        busy = sum(r is not None for r in self.batcher.running)
        rec.counter("slots", {"used": busy, "free": self.scfg.slots - busy},
                    pid="serve", cat="serve", clock=clock)

    def step_iteration(self) -> bool:
        """One engine iteration: admit+prefill, then one decode step.
        Returns False when nothing could make progress at this clock
        (the caller should jump the clock to the next arrival)."""
        progressed = False
        rec = get_recorder()
        stalls0 = self.batcher.stalls
        admitted = self.batcher.admit(self.clock)
        if rec.enabled and self.batcher.stalls > stalls0:
            # the FIFO head could not reserve pages/a slot this iteration
            rec.instant("admission_stall", pid="serve", tid="engine",
                        cat="serve", clock=("serve_iter", self.clock),
                        stalls=self.batcher.stalls,
                        free_pages=(self.kv.allocator.free_pages
                                    if getattr(self.kv, "allocator", None)
                                    is not None else -1))
        if admitted:
            self._prefill(admitted)
            progressed = True
        if any(r is not None and r.state is RequestState.DECODE
               for r in self.batcher.running):
            self._decode_iteration()
            progressed = True
        if rec.enabled:
            self._emit_occupancy(rec)
        if self.slo is not None:
            self.slo.observe("stall", self.clock,
                             1.0 if self.batcher.stalls > stalls0 else 0.0)
            self._slo_tick(rec)
        return progressed

    def _slo_tick(self, rec) -> None:
        """Evaluate the attached monitor at the current clock; on a
        transition into firing, record the alert and emit an
        ``slo_burn`` instant on the serve timeline."""
        firing = self.slo.firing(self.clock)
        if firing and not self._slo_firing:
            self.slo_alerts.append(dict(
                t=self.clock,
                objectives=[f["objective"] for f in firing]))
            if rec.enabled:
                rec.instant(
                    "slo_burn", pid="serve", tid="slo", cat="serve",
                    clock=("serve_iter", self.clock),
                    objectives=",".join(f["objective"] for f in firing),
                    burn_long=round(max(f["burn_long"] for f in firing),
                                    4),
                    burn_short=round(max(f["burn_short"] for f in firing),
                                     4))
        self._slo_firing = bool(firing)

    def run(self, requests: Optional[Sequence[Request]] = None) -> dict:
        """Drive every submitted request to DONE; returns the metrics row
        (throughput + latency percentiles on the virtual clock, plus wall
        seconds and stall count)."""
        if requests:
            for r in requests:
                self.submit(r)
        t_wall = time.perf_counter()
        while not self.batcher.idle:
            if not self.step_iteration():
                na = self.batcher.next_arrival()
                if na is None or na <= self.clock:
                    # head arrived, batch is empty, and it still can't
                    # reserve: no future event can unblock it
                    raise RuntimeError(
                        "serving deadlock: queued requests can never be "
                        "admitted (pool too small for any single request?)")
                self.clock = na
        wall = time.perf_counter() - t_wall
        m = summarize(self.requests, makespan=self.clock)
        m.update(
            policy=self.scfg.policy,
            paged=bool(self.scfg.page_size),
            page_size=self.scfg.page_size,
            tp=self.scfg.tp,
            clock=self.clock,
            decode_iterations=self.decode_iterations,
            prefill_groups=self.prefill_groups,
            admission_stalls=self.batcher.stalls,
            wall_s=wall,
        )
        if self.slo is not None:
            m["slo_alerts"] = len(self.slo_alerts)
        return m
