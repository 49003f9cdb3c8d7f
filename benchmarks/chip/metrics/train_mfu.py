"""Model FLOPs utilisation of the traced window: steps whose program
started in it, times tokens per step and FLOPs per token
(``work.train_flops_per_token``), over the window, the chips and the
bf16 peak, as a percentage."""
from chip import work
from chip.metrics import _train


def read(run):
    n = _train.steps_in_window(run)
    if not n:
        return None
    ex = run.outcome.extra
    flops = n * ex["tokens_per_step"] * work.train_flops_per_token(
        run.ctx.config, run.ctx.mix["seq_len"])
    chips = len(run.trace.devices)
    return 100.0 * flops / (run.trace.window_s * chips
                            * run.peak["bf16_flops_per_s"])
