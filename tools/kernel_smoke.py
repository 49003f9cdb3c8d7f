"""`make kernels` tier-1 gate: the kernel backend seam, every codec ×
backend cell on 4 virtual devices, plus one flash-attention forward/decode
cell — all Pallas kernels in interpret mode (this is a CPU correctness
gate; on TPU the same cells run compiled).

For each codec (none / onebit / terngrad / qsgd / dgc) the gate runs the
device engine for 2 BSP steps under ``wire="measured"`` with
``kernel_backend="ref"`` and ``"kernel"`` and asserts:

  * finite losses on both backends;
  * per-step losses agree within 1e-4 (bitwise for ``none``);
  * the measured wire bytes are bitwise identical — the backend knob can
    never change what goes on the wire.

The flash cell checks the training forward (kernel vs jnp oracle), its
flash-backward VJP, and the streaming decode kernel against the grouped
jnp decode, full-cache and ring-window.

  PYTHONPATH=src python tools/kernel_smoke.py
"""
import os
import sys

# virtual devices must be configured before jax import
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.launch.env import ensure_host_devices  # noqa: E402

ensure_host_devices(4)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.kernels import flash_attention as FA     # noqa: E402
from repro.train import Strategy                    # noqa: E402

KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (64, 1))
WORKERS = 4
STEPS = 2
CODECS = ("none", "onebit", "terngrad", "qsgd", "dgc")


def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    X = jax.random.normal(k, (16, 64))
    return {"X": X, "y": X @ W_TRUE}


def grad_fn(params, batch):
    def loss(p):
        return jnp.mean((batch["X"] @ p["W"] - batch["y"]) ** 2)
    return jax.value_and_grad(loss)(params)


P0 = {"W": jnp.zeros((64, 1)), "b": jnp.zeros((4096,))}


def codec_cells() -> None:
    for comp in CODECS:
        runs = {}
        for kb in ("ref", "kernel"):
            spec = f"bsp/ring/{comp}@{WORKERS}"
            eng = Strategy.parse(spec, lr=0.05, backend="device",
                                 wire="measured",
                                 kernel_backend=kb).build(grad_fn)
            runs[kb] = eng.run(P0, make_batch, STEPS)
        lr_ = [h["loss"] for h in runs["ref"][1]]
        lk = [h["loss"] for h in runs["kernel"][1]]
        assert all(np.isfinite(x) for x in lr_ + lk), comp
        if comp == "none":
            assert lr_ == lk, (comp, lr_, lk)
        else:
            ld = max(abs(a - b) for a, b in zip(lr_, lk))
            assert ld <= 1e-4, (comp, lr_, lk)
        assert runs["ref"][2] == runs["kernel"][2], (
            comp, runs["ref"][2], runs["kernel"][2])
        print(f"  codec {comp:9s} ref=kernel wire={runs['ref'][2]}  OK")


def flash_cell() -> None:
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, hd = 2, 64, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = FA.attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = FA.attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4

    g_k = jax.grad(lambda q: jnp.sum(
        FA.attention_grad(q, k, v, causal=True) ** 2))(q)
    g_r = jax.grad(lambda q: jnp.sum(
        FA.attention_ref(q, k, v, causal=True) ** 2))(q)
    assert float(jnp.max(jnp.abs(g_k - g_r))) < 1e-4

    qd = jax.random.normal(ks[0], (B, 1, H, hd))
    ck = jax.random.normal(ks[1], (B, 16, KV, hd))
    cv = jax.random.normal(ks[2], (B, 16, KV, hd))
    for window, pos in ((0, 11), (16, 23)):
        o_k = FA.decode(qd, ck, cv, jnp.int32(pos), window=window,
                        block_k=8)
        o_r = FA.decode_ref(qd, ck, cv, jnp.int32(pos), window=window)
        assert float(jnp.max(jnp.abs(o_k - o_r))) < 1e-5, window
    print("  flash fwd/grad/decode kernel=ref  OK")


def main() -> None:
    print(f"kernel backend seam gate: {len(CODECS)} codecs x 2 backends "
          f"on {WORKERS} devices + flash cell")
    codec_cells()
    flash_cell()
    print("kernel smoke OK")


if __name__ == "__main__":
    main()
