"""Operations and bytes of a DeepSeek-V2 block on one chip's share of
experts (multi-head latent attention, a leading dense SwiGLU, then MoE
layers of routed and shared experts), computed from shapes.

As in ``work.py``, every count is the work the algorithm needs: causal
attention counts the keys at or before each query, and nothing recomputed
is counted.  ``c`` is a configuration file's dict (Hugging Face key names;
``n_routed_experts`` the experts held here, ``expert_share.of`` the
router's outputs).

Routed experts are counted at the balanced expectation: each token makes
``num_experts_per_tok`` assignments, of which the held experts get the
share ``n_routed_experts / expert_share.of``.  Across an expert-parallel
group that holds every expert once this is exactly the average member's
work, whatever the routing; one chip's actual load, its most loaded
expert over the balanced count, is the program's ``moe_held_load``.
"""
from __future__ import annotations

from chip.work import causal_pairs


def dims(c: dict) -> dict:
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                r=c["kv_lora_rank"], nd=c["qk_nope_head_dim"],
                rd=c["qk_rope_head_dim"], vd=c["v_head_dim"],
                ff=c["intermediate_size"], eff=c["moe_intermediate_size"],
                held=c["n_routed_experts"], E=c["expert_share"]["of"],
                K=c["num_experts_per_tok"], shared=c["n_shared_experts"],
                V=c["vocab_size"], L=L, dense=dense, moe=L - dense)


def mla_params(c: dict) -> int:
    """Matmul weights of one latent-attention layer."""
    k = dims(c)
    d, H = k["d"], k["H"]
    return (d * H * (k["nd"] + k["rd"]) + d * (k["r"] + k["rd"])
            + k["r"] * H * (k["nd"] + k["vd"]) + H * k["vd"] * d)


def expert_assignments_per_token(c: dict) -> float:
    """Assignments per token that the held experts get, balanced."""
    k = dims(c)
    return k["K"] * k["held"] / k["E"]


def token_matmul_params(c: dict) -> float:
    """Matmul weights one token passes through in every layer and the head:
    attention, the dense MLP, the router, the shared experts and the held
    experts' balanced share of its assignments."""
    k = dims(c)
    expert = 3 * k["d"] * k["eff"]
    moe = (k["d"] * k["E"] + k["shared"] * expert
           + expert_assignments_per_token(c) * expert)
    return (k["L"] * mla_params(c) + k["dense"] * 3 * k["d"] * k["ff"]
            + k["moe"] * moe + k["d"] * k["V"])


def attn_pair_flops(c: dict) -> int:
    """FLOPs of one (query, key) pair over all layers: QK over the q/k head
    dim (nope + rope) and PV over the v head dim."""
    k = dims(c)
    return 2 * k["L"] * k["H"] * (k["nd"] + k["rd"] + k["vd"])


def train_flops_per_token(c: dict, S: int) -> float:
    """Forward + backward model FLOPs per trained token at sequence length
    ``S``: 6 per matmul weight and three times the forward attention."""
    return (6 * token_matmul_params(c)
            + 3 * attn_pair_flops(c) * causal_pairs(S) / S)


def experts_train(c: dict, tokens: int, itemsize: int) -> tuple:
    """(FLOPs, bytes) of the held experts' SwiGLU in every MoE layer for
    ``tokens`` tokens, forward and backward: 6 FLOPs per weight per
    assignment; bytes the held weights read three times (forward, input
    gradient, weight gradient) and their gradients written once, and each
    assignment's input row and output row three times."""
    k = dims(c)
    a = tokens * expert_assignments_per_token(c)
    w = k["held"] * 3 * k["d"] * k["eff"]
    flops = k["moe"] * 6 * a * 3 * k["d"] * k["eff"]
    nbytes = k["moe"] * (4 * w + 3 * a * 2 * k["d"]) * itemsize
    return flops, nbytes


def flash_fwd(c: dict, rows: int, S: int, itemsize: int) -> tuple:
    """(FLOPs, bytes) of the causal flash forward of every layer: QK over
    nope + rope and PV over v on causal pairs; q and k (the shared rope
    key broadcast to every head), v read and the output written once."""
    k = dims(c)
    flops = attn_pair_flops(c) * rows * causal_pairs(S)
    nbytes = k["L"] * rows * S * k["H"] * (
        2 * (k["nd"] + k["rd"]) + 2 * k["vd"]) * itemsize
    return flops, nbytes
