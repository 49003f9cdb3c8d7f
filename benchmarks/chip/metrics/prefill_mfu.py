"""Prefill FLOPs (``work.prefill_flops``) of the prompts admitted in the
traced window over the device time of every program other than the
decode step (prefill, cache conversion, page writes) times the bf16
peak, as a percentage."""
from chip.metrics import _serve


def read(run):
    flops = sum(it.prefill_flops for it in _serve.traced_iters(run))
    t = _serve.other_program_s(run)
    if not flops or not t:
        return None
    return 100.0 * flops / (t * run.peak["bf16_flops_per_s"])
