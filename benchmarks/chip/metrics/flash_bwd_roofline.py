"""The flash-attention backward kernels' least time over their device time
in the traced window, as a percentage.

The least time is the larger of FLOPs over the peak and bytes over HBM
bandwidth.  FLOPs: twice the forward's on causal pairs (dV and dP from
P and dO, dQ and dK from dS), the work ``train_flops_per_token`` counts
for the backward; the probabilities the kernels recompute are not
counted.  Bytes: q, k, v, the output, its gradient and the per-row
log-sum-exp read once, dq, dk and dv written once.  Latent attention (a
configuration with ``kv_lora_rank``) counts its q/k and v head dims as
``work_mla_moe.flash_fwd`` does."""
import jax.numpy as jnp

from chip import work, work_mla_moe
from chip.metrics import _train
from chip.peaks import roofline_s

# the dK/dV and dQ Pallas kernels of kernels/flash_attention, which run
# under a jit of their own (their custom calls are named after it)
KERNEL = r"^flash_attention_bwd\b.*\[pallas\]$"


def flash_bwd(c: dict, rows: int, S: int, itemsize: int) -> tuple:
    """(FLOPs, bytes) of one causal flash backward over every layer's
    heads."""
    if "kv_lora_rank" in c:
        k = work_mla_moe.dims(c)
        pair, H = work_mla_moe.attn_pair_flops(c), k["H"]
        widths = H * 4 * (k["nd"] + k["rd"] + k["vd"])
    else:
        k = work.dims(c)
        pair, H = work.attn_pair_flops(c), k["H"]
        widths = 4 * (H + k["KV"]) * k["hd"]
    flops = 2 * pair * rows * work.causal_pairs(S)
    nbytes = k["L"] * rows * S * (widths * itemsize + 4 * H)
    return flops, nbytes


def read(run):
    tr = run.trace
    t = tr.op_time_s(tr.devices[0], KERNEL)
    n = _train.steps_in_window(run)
    if not t or not n:
        return None
    c, cp = run.ctx.config, run.ctx.cell_params
    flops, nbytes = flash_bwd(c, cp["rows_per_worker"],
                              run.ctx.mix["seq_len"],
                              jnp.dtype(c["compute_dtype"]).itemsize)
    return 100.0 * n * roofline_s(flops, nbytes, run.peak)[0] / t
