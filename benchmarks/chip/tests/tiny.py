"""A run of a benchmark cell at a size the CPU holds: the cell's own
files with the model cut to tiny widths and a short window, and the
harness's look for a chip skipped."""
import os
import time

from chip import harness

WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=4, vocab_size=256)
PROGRAM = dict(d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
               d_ff=128, vocab_size=256)
SERVE = "serve.stablelm-1.6b.chat"
TRAIN = "train.stablelm-1.6b.s2k"
# a serve cell whose files are in place but which BENCHMARK.json does not
# list yet (its program compiles inside the window)
UNLISTED = {SERVE: (
    {"name": SERVE, "config": "stablelm-2-1.6b-fullrope-nobias",
     "traffic": "chat", "chips": 1},
    {"name": "stablelm-2-1.6b-fullrope-nobias",
     "file": "benchmarks/chip/configs/stablelm-2-1.6b-fullrope-nobias.json"})}


def context(workload, seed=20260101, control=False, layers=2, seconds=2):
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    if workload in UNLISTED and all(w["name"] != workload
                                    for w in spec["workloads"]):
        cell, cfg = UNLISTED[workload]
        spec["workloads"].append(cell)
        spec["configs"].append(cfg)
    prog = dict(PROGRAM, arch="stablelm-1.6b", num_layers=layers)
    over = {"config": dict(WIDTHS, num_hidden_layers=layers, program=prog)}
    if workload == SERVE:
        over["cell_params"] = {
            "rate_per_s": 4.0, "warm_groups": 2,
            "engine": {"slots": 4, "max_len": 96, "page_size": 16},
            "check": {"tokens": 40, "rows_per_block": 4,
                      "max_logit_gap": 0.2}}
        over["mix"] = {
            "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64,
                       "buckets": [32, 64]},
            "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 24}}
    else:
        over["cell_params"] = {"rows_per_worker": 2, "max_steps": 6,
                               "limits": {"loss_rel_gap": 0.01,
                                          "grad_norm_gap": 0.05,
                                          "update_norm_gap": 0.05}}
        over["mix"] = {"seq_len": 32}
    return harness.Context(spec, workload, seed, seconds, False, control,
                           overrides=over)


def run(ctx):
    import jax
    return harness.execute(ctx, jax.devices()[:1], time.perf_counter())
