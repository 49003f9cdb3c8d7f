"""The DeepSeek-V2 cell at a size the CPU holds: its own files with the
model cut to tiny widths (d 64, 4 heads of 16 + 8 / v 16, latent 32, 16
experts of which 4 are held, top-3, 2 shared), 64-token rows, float32
compute and a short window, and the harness's look for a chip skipped.

In float32 the program and the reference agree to rounding, so a fault
shows against the cell's own limits.  (In bfloat16 at these widths a
handful of top-k choices flip between the two and move a tiny model's
gradients by percents: the chip's readings at the cell's widths set the
limits.)"""
import os
import time

from chip import harness

CELL = "train.deepseek-v2-lite-16b.s4k"
WIDTHS = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              intermediate_size=128, moe_intermediate_size=32,
              n_routed_experts=4, n_shared_experts=2, num_experts_per_tok=3,
              vocab_size=256, num_hidden_layers=3,
              expert_share={"first": 0, "of": 16})
PROGRAM = dict(arch="deepseek-v2-lite-16b", d_model=64, num_heads=4,
               num_kv_heads=4, head_dim=24, kv_lora_rank=32, qk_nope_dim=16,
               qk_rope_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32,
               num_experts=16, experts_per_token=3, num_shared_experts=2,
               vocab_size=256, num_layers=3, experts_held=(0, 4))


def config(**over):
    """The configuration file's dict at the tiny widths."""
    c = harness.load_json(os.path.join(
        harness.HERE, "configs", "deepseek-v2-lite-5l-ep8.json"))
    c.update(WIDTHS, program=dict(PROGRAM), compute_dtype="float32", **over)
    return c


def program_config(**over):
    import dataclasses
    from repro.configs import get_config
    prog = dict(PROGRAM, **over)
    return dataclasses.replace(get_config(prog.pop("arch")), **prog)


def context(seed=20260101, control=False, seconds=2):
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cp = harness.load_json(os.path.join(harness.HERE, "cells",
                                        CELL + ".json"))
    over = {"config": config(),
            "cell_params": {"rows_per_worker": 2, "max_steps": 6,
                            "limits": cp["limits"]},
            "mix": {"seq_len": 64}}
    return harness.Context(spec, CELL, seed, seconds, False, control,
                           overrides=over)


def run(ctx):
    import jax
    return harness.execute(ctx, jax.devices()[:1], time.perf_counter())
