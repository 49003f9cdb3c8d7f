"""Routing of a MoE benchmark cell's first batch, program against reference:
how many top-k choices differ (boundary flips), and the program's
``moe_held_load`` and ``moe_dropped``.

  python3 tools/moe_flips.py --workload train.deepseek-v2-lite-16b.s4k \\
      --seed <n>

Runs on the chip the cell asks for, on the weights and the first step's
rows that ``benchmarks/chip/run.py`` makes from the same seed: the
program's forward in the configuration's compute dtype, the reference's
in float32 at "highest", one row at a time.  Prints one JSON line.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.models.moe as moe
    from chip import harness, traffic

    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    ctx = harness.Context(spec, args.workload, args.seed, 1, False)
    c, ref, cp = ctx.config, ctx.reference, ctx.cell_params
    K = c["num_experts_per_tok"]
    R = cp["rows_per_worker"]
    rows = traffic.train_rows(ctx.mix, (5 + cp["max_steps"]) * R, ctx.seed,
                              c["vocab_size"])
    batch = {k: jnp.asarray(v[:R]) for k, v in rows.items()}
    params = ref.init(c, ctx.key, c["dtype"])

    got, want = [], []
    real_route, real_moe = moe.route, ref._moe

    def route(w, x, cfg):
        gate, ids, aux = real_route(w, x, cfg)
        jax.debug.callback(lambda i: got.append(np.asarray(i)), ids,
                           ordered=True)
        return gate, ids, aux

    def ref_moe(c_, mp, h, q):
        ids = jax.lax.top_k(jax.nn.softmax(h @ mp["router"]["w"], -1), K)[1]
        jax.debug.callback(lambda i: want.append(np.asarray(i)), ids,
                           ordered=True)
        return real_moe(c_, mp, h, q)
    moe.route, ref._moe = route, ref_moe

    model = ctx.model
    compute = jnp.dtype(c["compute_dtype"])
    _, mets = jax.jit(lambda p, b: model.loss_fn(
        p, b, compute_dtype=compute))(params, batch)
    mets = {k: float(v) for k, v in mets.items()}
    jax.effects_barrier()
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: ref.forward(c, p, t)[0])
        for i in range(R):
            jax.block_until_ready(fwd(params, batch["tokens"][i:i + 1]))
    jax.effects_barrier()

    layers = len(got)
    prog = [g.reshape(R, -1, K) for g in got]
    refs = [np.concatenate(want[j::layers], 0) for j in range(layers)]
    flips, tokens = [], []
    for p_ids, r_ids in zip(prog, refs):
        diff = [len(set(a) - set(b)) for a, b in zip(
            p_ids.reshape(-1, K).tolist(), r_ids.reshape(-1, K).tolist())]
        flips.append(int(sum(diff)))
        tokens.append(int(sum(d > 0 for d in diff)))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "assignments_per_layer": int(prog[0].size),
                      "flipped_assignments": flips,
                      "tokens_with_a_flip": tokens,
                      "moe_held_load": mets.get("moe_held_load"),
                      "moe_dropped": mets.get("moe_dropped"),
                      "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
