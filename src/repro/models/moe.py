"""Token-choice top-k MoE: capacity dispatch over every expert, or dropless
dispatch over a held block of experts (TPU-friendly, all shapes static).

The survey's hybrid-parallelism discussion (§3.2.4) maps MoE onto the
"parameter dimension": experts are sharded over the `model` mesh axis and
token dispatch becomes the all-to-all the survey flags as the communication
bottleneck for parameter-heavy layers.

Routing (``route``) is one rule for both: fp32 softmax scores over all
``num_experts``, greedy top-k, gates renormalised (``norm_topk_prob``) or
the raw scores (DeepSeek-V2-Lite's ``routed_scaling_factor`` is 1), and a
Switch-style or DeepSeek-V2 per-sequence (``seq_aux``) balance loss.

Capacity dispatch (``experts_held`` empty; MaxText-style, no [T, E, C]
one-hot): assignments -> stable sort by expert id -> per-expert positions
via cumulative counts -> gather into an [E, C, d] buffer -> batched expert
einsum -> gather back + weighted combine.  Assignments past an expert's
capacity C are dropped.

Held-expert dispatch (``experts_held = (first, count)``): the layer holds
experts ``first .. first + count - 1`` of an expert-parallel group, routes
over all of them, and computes its own experts' part of the result for the
tokens routed to them, with no capacity: the assignments to held experts
are sorted by expert into a buffer that holds every assignment a token can
make to them, and one grouped matmul per projection runs over exactly the
rows each expert got.  Dispatch and combine are a permutation of the
K-fold repeated tokens and its inverse, so each pass's transpose is a
gather by the other: neither direction scatters rows of d_model, and a
token's gradient sums its assignments' rows in fp32.  Buffer rows past
the held assignments hold any token, and nothing reads what the experts
make of them.  The absent experts' part, and the all-to-all that would
bring it, are left out; the shared experts are computed alike on every
member of the group.

Capacity dispatch serves every configuration without ``experts_held``:
serving's per-slot decode and the Kimi-K2 and DeepSeek configurations of
the zoo.  ``tests/test_moe.py`` pins its capacity rule.  A held block
gets its tokens only from an all-to-all that does not exist yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import kernel_interpret, resolve_backend
from repro.models.common import dense_init, mlp_init, mlp_apply


def moe_init(key, cfg, dtype=jnp.float32):
    d, ff = cfg.d_model, cfg.moe_d_ff
    E = cfg.experts_held[1] if cfg.experts_held else cfg.num_experts
    ks = jax.random.split(key, 2 + cfg.num_shared_experts)
    p = {
        # the router keeps every expert's output, in fp32
        "router": dense_init(ks[0], d, cfg.num_experts, False, jnp.float32),
        # stacked weights of the experts held [E, d, ff] / [E, ff, d]
        "w_gate": (jax.random.normal(ks[1], (E, d, ff)) / np.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(jax.random.fold_in(ks[1], 1), (E, d, ff))
                 / np.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(jax.random.fold_in(ks[1], 2), (E, ff, d))
                   / np.sqrt(ff)).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[2], d, ff * cfg.num_shared_experts,
                               "swiglu", cfg.use_bias, dtype)
    return p


def _capacity(T: int, K: int, E: int, factor: float) -> int:
    c = int((T * K * factor + E - 1) // E)
    return max(c, 1)


def route(router_w, x, cfg):
    """x [B, S, d] -> (gate [T, K] fp32, expert ids [T, K], aux loss)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = jnp.dot(x.reshape(B * S, d).astype(jnp.float32), router_w,
                     precision=jax.lax.Precision.HIGHEST)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, K)                            # [T, K]
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if cfg.seq_aux:
        # DeepSeek-V2: per sequence, f_i = E / (K S) * (selections of i)
        # and P_i = the mean score of i; alpha * sum_i f_i P_i, averaged
        sel = jax.nn.one_hot(ids.reshape(B, S * K), E, dtype=jnp.float32)
        f = sel.sum(1) * (E / (K * S))                              # [B, E]
        P = probs.reshape(B, S, E).mean(1)
        aux = cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, -1))
    else:
        # Switch-style over the top-1 choice
        ce = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32).mean(0)
        aux = cfg.router_aux_coef * E * jnp.sum(probs.mean(0) * ce)
    return gate, ids, aux


def moe_apply(p, x, cfg):
    """x [B, S, d] -> (out [B, S, d], stats): ``aux`` the balance loss,
    ``dropped`` the assignments left uncomputed, ``held_load`` the most
    tokens any held expert got over the balanced T*K/E (0 where every
    expert is computed with capacity dispatch)."""
    B, S, d = x.shape
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            gate, ids, aux = route(p["router"]["w"], x, cfg)
        xt = x.reshape(B * S, d)
        if cfg.experts_held:
            out, dropped, load = _held_experts(p, xt, gate, ids, cfg)
        else:
            out, dropped = _capacity_experts(p, xt, gate, ids, cfg)
            load = jnp.float32(0.0)
        if "shared" in p:
            with jax.named_scope("shared"):
                out = out + mlp_apply(p["shared"], xt, "swiglu")
    return out.reshape(B, S, d), {"aux": aux, "dropped": dropped,
                                  "held_load": load}


def _capacity_experts(p, xt, gate, expert_ids, cfg):
    T, d = xt.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    C = _capacity(T, K, E, cfg.capacity_factor)
    # ---- sort-based dispatch (gather formulation)
    # A scatter into the expert-sharded [E*C, d] buffer makes GSPMD
    # replicate + all-reduce the full buffer (measured: ~E*C*d bytes of
    # all-reduce per layer).  Instead index slot -> source token and GATHER:
    # slot (e, c) is filled by the c-th token routed to expert e.
    with jax.named_scope("dispatch"):
        flat_e = expert_ids.reshape(-1)                           # [T*K]
        sort_idx = jnp.argsort(flat_e, stable=True)               # [T*K]
        sorted_e = flat_e[sort_idx]
        counts = jnp.bincount(flat_e, length=E)                   # [E]
        starts = jnp.cumsum(counts) - counts                      # [E]

        slot_c = jnp.arange(E * C) % C                            # [E*C]
        slot_e = jnp.arange(E * C) // C
        slot_valid = slot_c < counts[slot_e]
        slot_sorted_idx = jnp.minimum(starts[slot_e] + slot_c, T * K - 1)
        slot_token = sort_idx[slot_sorted_idx] // K               # source token
        buf = jnp.where(slot_valid[:, None],
                        xt[slot_token], jnp.zeros((), dtype=xt.dtype))
        buf = buf.reshape(E, C, d)

    # ---- batched expert FFN (swiglu)
    with jax.named_scope("experts"):
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   p["w_gate"].astype(xt.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(xt.dtype))
        out_buf = jnp.einsum("ecf,efd->ecd", h,
                             p["w_down"].astype(xt.dtype))
        out_buf = out_buf.reshape(E * C, d)

    # ---- combine: slot of the i-th sorted assignment (gather, no scatter)
    with jax.named_scope("combine"):
        pos_in_e = jnp.arange(T * K) - starts[sorted_e]           # [T*K]
        valid = pos_in_e < C
        dest = jnp.minimum(sorted_e * C + jnp.minimum(pos_in_e, C - 1),
                           E * C - 1)
        out_sorted = out_buf[dest] * valid[:, None].astype(xt.dtype)
        inv = jnp.argsort(sort_idx)                               # unsort perm
        out_flat = out_sorted[inv]                                # [T*K, d]
        out = (out_flat.reshape(T, K, d)
               * gate.astype(xt.dtype)[..., None]).sum(axis=1)    # [T, d]
    return out, jnp.sum(~valid).astype(jnp.float32)


ROW_TILE = 256     # grouped-matmul rows a step


def _gmm_tiles(m: int, k: int, n: int) -> tuple:
    """Tiles of a grouped matmul [m, k] x [G, k, n] (and of its two
    transposes in the backward pass, which reuse this rule): ``ROW_TILE``
    rows, and a dimension of up to 1536 whole, else 512, 256 or 128 lanes,
    whichever divides it."""
    def lanes(x):
        if x <= 1536:
            return x
        return next((t for t in (512, 256, 128) if x % t == 0), x)
    return min(ROW_TILE, m), lanes(k), lanes(n)


def _grouped(lhs, rhs, sizes):
    """``lhs`` rows grouped by ``sizes`` [G] times ``rhs`` [G, k, n]: the
    megablox Pallas kernel on the kernel path, ``lax.ragged_dot`` on the
    reference path.  Rows past ``sum(sizes)`` hold no defined value."""
    if resolve_backend("auto") == "kernel":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiles,
                   interpret=kernel_interpret())
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def _held_experts(p, xt, gate, ids, cfg):
    """Dropless part of the held experts (module docstring)."""
    T, d = xt.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    first, n = cfg.experts_held
    # a token selects an expert at most once, so the held experts get at
    # most T * min(K, n) assignments: the buffer holds every one of them
    M = T * min(K, n)
    rows = -(-M // min(ROW_TILE, M)) * min(ROW_TILE, M)
    with jax.named_scope("dispatch"):
        local = ids.reshape(-1) - first                           # [T*K]
        key = jnp.where((local >= 0) & (local < n), local, n)     # absent: n
        perm = jnp.argsort(key, stable=True)      # held first, by expert
        rank = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(T * K, dtype=perm.dtype), unique_indices=True)
        # counted by comparison: a bincount is a scatter-add of T*K ones
        sizes = jnp.sum(key[:, None] == jnp.arange(n), axis=0,
                        dtype=jnp.int32)
        total = jnp.sum(sizes)
        # each row's assignment; rows past the held ones hold any, and
        # nothing reads what the experts make of them
        src = jnp.pad(perm[:M], (0, rows - M))
    dt = xt.dtype
    y = jax.checkpoint(_expert_ffn, static_argnums=(0,))(
        K, xt, src, rank, sizes, p["w_gate"].astype(dt),
        p["w_up"].astype(dt), p["w_down"].astype(dt))             # [rows, d]
    with jax.named_scope("combine"):
        out = _combine(gate, y, src, rank.reshape(T, K), total)
    dropped = jnp.sum((key < n) & (rank >= M)).astype(jnp.float32)
    load = jnp.max(sizes).astype(jnp.float32) / (T * K / E)
    return out.astype(xt.dtype), dropped, load


def _expert_ffn(K, xt, src, rank, sizes, w_gate, w_up, w_down):
    """The held experts' SwiGLU over the rows of assignments ``src``,
    grouped by ``sizes``.  Checkpointed: the backward pass keeps its
    inputs and recomputes the rest, since every row buffer is sized for
    the most assignments the held experts could get."""
    with jax.named_scope("dispatch"):
        buf = _dispatch(K, xt, src, rank, jnp.sum(sizes))
    with jax.named_scope("experts"):
        h = jax.nn.silu(_grouped(buf, w_gate, sizes)) \
            * _grouped(buf, w_up, sizes)
        return _grouped(h, w_down, sizes)


# Dispatch and combine move rows by a permutation of the K-fold repeated
# tokens (``src`` the assignment of each buffer row, ``rank`` its inverse:
# the row of each assignment, held where below ``total``).  The transpose
# of a permutation is its inverse, so each VJP is a gather too and the
# backward pass holds no scatter.  Rows of the experts' output, and of its
# cotangent, past ``total`` hold no defined value (``_grouped``): both
# directions mask them with ``where``, never a multiply.  A token's K rows
# are gathered a column k at a time, K slabs of [T, d] summed elementwise
# in fp32: a [T, K, d] gather puts K (6) in the second-minor dimension of
# the tiled layout, and XLA relayouts and writes out its fp32 copy.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(K, xt, src, rank, total):
    """Buffer row r: token ``src[r] // K`` of ``xt``."""
    return xt[src // K]


def _dispatch_fwd(K, xt, src, rank, total):
    return xt[src // K], (rank, total)


def _dispatch_bwd(K, res, g):
    # a token's gradient: the sum, in fp32, of its held assignments' rows
    rank, total = res
    dxt = sum(r.astype(jnp.float32) for r in _rows(g, rank.reshape(-1, K),
                                                   total))
    return dxt.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _rows(y, at, total):
    """For each column k of ``at`` [T, K], the rows ``y[at[:, k]]``, zero
    where ``at`` is past ``total``."""
    return [jnp.where((a < total)[:, None], y[jnp.minimum(a, len(y) - 1)],
                      jnp.zeros((), y.dtype)) for a in at.T]


@jax.custom_vjp
def _combine(gate, y, src, rank, total):
    """sum_k gate[t, k] * y[rank[t, k]] in fp32, over the held
    assignments."""
    return sum(g[:, None] * r.astype(jnp.float32)
               for g, r in zip(gate.T, _rows(y, rank, total)))


def _combine_fwd(gate, y, src, rank, total):
    return _combine(gate, y, src, rank, total), (gate, y, src, rank, total)


def _combine_bwd(res, dout):
    gate, y, src, rank, total = res
    # dout is the cotangent of ``out.astype(y.dtype)`` and holds values
    # of that dtype, so it is gathered and multiplied in it with no loss
    dout = dout.astype(y.dtype)
    dgate = jnp.stack([jnp.einsum("td,td->t", dout, r,
                                  preferred_element_type=jnp.float32)
                       for r in _rows(y, rank, total)], axis=1)
    dy = gate.reshape(-1)[src][:, None] \
        * dout[src // gate.shape[1]].astype(jnp.float32)
    dy = jnp.where((jnp.arange(len(y)) < total)[:, None], dy, 0.0)
    return dgate, dy.astype(y.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)
