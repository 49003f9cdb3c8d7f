"""``flash_bwd_roofline``: the flash backward's work counted by hand, and
its reader on a trace built by hand, where it finds the backward's kernels
by name and leaves the forward's to the forward rooflines."""
import re
from types import SimpleNamespace

import pytest

from chip import tracing, work
from chip.metrics import flash_bwd_roofline as fbr
from chip.metrics import flash_fwd_roofline
from chip.tests import tiny_deepseek as tiny

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# d=8, 2 heads of 4 (1 KV head), 2 layers
C = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
         intermediate_size=16, vocab_size=10, num_hidden_layers=2,
         compute_dtype="bfloat16")


def test_dense_work_by_hand():
    flops, nbytes = fbr.flash_bwd(C, rows=1, S=3, itemsize=2)
    # twice the forward: 2 * (4 * L * H * hd) * 6 causal pairs
    assert flops == 2 * work.flash_fwd(C, 1, 3, 2)[0] == 2 * 4 * 2 * 2 * 4 * 6
    # per token and layer: q, o, do, dq (H heads) and k, v, dk, dv (KV
    # heads) of 4 lanes in bf16, and an fp32 lse per head
    assert nbytes == 2 * 1 * 3 * ((4 * 2 + 4 * 1) * 4 * 2 + 4 * 2)


def test_latent_attention_work_by_hand():
    c = tiny.config()
    flops, nbytes = fbr.flash_bwd(c, rows=1, S=3, itemsize=2)
    # QK over 24 and PV over 16, 4 heads, 3 layers, 6 causal pairs, twice
    assert flops == 2 * 2 * 3 * 4 * (24 + 16) * 6
    # q, k, dq, dk of 24 and v, o, do, dv of 16 per head, and the lse
    assert nbytes == 3 * 1 * 3 * (4 * 4 * (24 + 16) * 2 + 4 * 4)


def _run(ops, config=C, rows=3, S=2048):
    dev = {"name": "/device:TPU:0", "ops": [list(o) for o in ops],
           "modules": [["jit_sharded_step(7)", 0, 900],
                       ["jit_sharded_step(7)", 1000, 900]]}
    ctx = SimpleNamespace(config=config, cell_params={"rows_per_worker": rows},
                          mix={"seq_len": S})
    return tracing.Run(trace=tracing.Trace([dev], [], (0, 2000)), ctx=ctx,
                       outcome=None, peak=PEAK)


def test_reader_times_the_backward_kernels_alone():
    # two steps: each a forward kernel of 100 ns and the dK/dV and dQ
    # kernels of 300 and 200 ns
    ops = []
    for t0 in (0, 1000):
        ops += [("attention_grad.7 [pallas]", t0, 100),
                ("flash_attention_bwd.24 [pallas]", t0 + 200, 300),
                ("flash_attention_bwd.25 [pallas]", t0 + 500, 200),
                ("fusion.3", t0 + 700, 50)]
    flops, nbytes = fbr.flash_bwd(C, 3, 2048, 2)
    least = max(flops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert fbr.read(_run(ops)) == pytest.approx(100 * 2 * least / 1000e-9)
    # the forward rooflines still read the forward's kernel alone
    names = [o[0] for o in ops]
    assert [n for n in names if re.search(flash_fwd_roofline.KERNEL, n)] \
        == ["attention_grad.7 [pallas]"] * 2


def test_reader_reads_nothing_without_the_kernels():
    # a program whose VJP is not the flash backward: nothing to read
    ops = [("attention_grad.7 [pallas]", 0, 100), ("fusion.3", 200, 500)]
    assert fbr.read(_run(ops)) is None
