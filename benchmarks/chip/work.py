"""Operations and bytes per call, computed from shapes.

Every count here is the work the *algorithm* needs, not what one
implementation happens to do: causal attention counts the keys at or
before each query, the LM head of a prefill counts the one row whose
logits are used, a decode step reads each weight once and each live
cache position once.  A roofline or utilisation share built on these counts
therefore reads the same work whatever implements it, and cannot pass
100% unless the time leaves part of the work out.

``c`` is a configuration file's dict (Hugging Face key names) of a dense
MHA/GQA + SwiGLU decoder with LayerNorm.
"""
from __future__ import annotations


def dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return dict(d=d, H=H, KV=c.get("num_key_value_heads", H),
                hd=c.get("head_dim") or d // H, ff=c["intermediate_size"],
                V=c["vocab_size"], L=c["num_hidden_layers"])


def layer_matmul_params(c: dict) -> int:
    """Matmul weights one token passes through in all layers, the LM head
    excluded."""
    k = dims(c)
    attn = k["d"] * k["H"] * k["hd"] * 2 + 2 * k["d"] * k["KV"] * k["hd"]
    return k["L"] * (attn + 3 * k["d"] * k["ff"])


def matmul_params(c: dict) -> int:
    """Every matmul weight a token passes through, the LM head included
    (the embedding is a gather, not a matmul)."""
    k = dims(c)
    return layer_matmul_params(c) + k["d"] * k["V"]


def attn_pair_flops(c: dict) -> int:
    """FLOPs of one (query, key) pair over all layers: QK and PV."""
    k = dims(c)
    return 4 * k["L"] * k["H"] * k["hd"]


def weight_bytes(c: dict, itemsize: int) -> int:
    """Bytes of every weight a decode step must read once: the matmul
    weights and the norm scales and biases; the embedding rows are
    counted per token."""
    k = dims(c)
    norms = (2 * k["L"] + 1) * 2 * k["d"]      # LayerNorm: scale and bias
    return (matmul_params(c) + norms) * itemsize


def kv_bytes_per_position(c: dict, itemsize: int) -> int:
    k = dims(c)
    return k["L"] * 2 * k["KV"] * k["hd"] * itemsize


def causal_pairs(S: int) -> int:
    """(query, key) pairs a causal sequence of ``S`` tokens attends."""
    return S * (S + 1) // 2


def train_flops_per_token(c: dict, S: int) -> float:
    """Forward + backward model FLOPs per trained token at sequence length
    ``S``: 6 per matmul weight, and three times the forward attention of
    the token's causal keys.  Nothing recomputed is counted."""
    return (6 * matmul_params(c)
            + 3 * attn_pair_flops(c) * causal_pairs(S) / S)


def prefill_flops(c: dict, S: int) -> float:
    """One prompt of ``S`` tokens: every layer at every position, causal
    attention, and the LM head at the last position only."""
    k = dims(c)
    return (2 * layer_matmul_params(c) * S
            + attn_pair_flops(c) * causal_pairs(S) + 2 * k["d"] * k["V"])


def decode_flops(c: dict, n_active: int, live_total: int) -> float:
    """One decode step: ``n_active`` slots, each through every matmul,
    attending ``live_total`` cache positions in all (summed over slots)."""
    return (2 * matmul_params(c) * n_active
            + attn_pair_flops(c) * live_total)


def decode_least_bytes(c: dict, itemsize: int, n_active: int,
                       live_total: int) -> float:
    """The least bytes any implementation of one decode step moves: every
    weight once, the embedding row of each active slot, each live cache
    position of each active slot once, and each new cache row once."""
    k = dims(c)
    per_pos = kv_bytes_per_position(c, itemsize)
    return (weight_bytes(c, itemsize) + n_active * k["d"] * itemsize
            + live_total * per_pos + n_active * per_pos)


def decode_program_bytes(c: dict, itemsize: int, slots: int,
                         max_len: int) -> float:
    """A lower bound on what the repository's paged decode step moves
    today: every weight, every slot's embedding row, the whole reserved
    pool, which it gathers into a contiguous view of ``slots * max_len``
    positions, and every slot's new cache row."""
    per_pos = kv_bytes_per_position(c, itemsize)
    return (weight_bytes(c, itemsize) + slots * dims(c)["d"] * itemsize
            + slots * max_len * per_pos + slots * per_pos)


def flash_fwd(c: dict, rows: int, S: int, itemsize: int) -> tuple:
    """(FLOPs, bytes) of one causal flash-attention forward over every
    layer's heads: QK and PV over causal pairs; q, k, v read and the
    output written once."""
    k = dims(c)
    flops = attn_pair_flops(c) * rows * causal_pairs(S)
    nbytes = k["L"] * rows * S * (2 * k["H"] + 2 * k["KV"]) * k["hd"] \
        * itemsize
    return flops, nbytes
