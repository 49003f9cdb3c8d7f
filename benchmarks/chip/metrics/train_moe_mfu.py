"""Model FLOPs utilisation of the traced window for a latent-attention
MoE model: steps whose program started in it, times tokens per step and
FLOPs per token (``work_mla_moe.train_flops_per_token``, the held experts
at their balanced share), over the window, the chips and the bf16 peak, as
a percentage."""
from chip import work_mla_moe
from chip.metrics import _train


def read(run):
    n = _train.steps_in_window(run)
    if not n:
        return None
    flops = n * run.outcome.extra["tokens_per_step"] * \
        work_mla_moe.train_flops_per_token(run.ctx.config,
                                           run.ctx.mix["seq_len"])
    return 100.0 * flops / (run.trace.window_s * len(run.trace.devices)
                            * run.peak["bf16_flops_per_s"])
