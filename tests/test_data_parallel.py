"""DataParallelEngine tests (PR 1 tentpole).

Covers: a real ``jax.shard_map`` on one device, engine-vs-simulator
equivalence on 8 virtual devices, kernel-vs-ref bit-identity through the
sharded compressed path, EF state round-trip, wire accounting, and the
TicTac bucket-order timeline model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm_scheduler import LinkModel


# ------------------------------------------------------------ shard_map
def test_jax_shard_map_runs():
    """``jax.shard_map`` with ``jax.lax.axis_size`` end to end (one
    device)."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("w",))
    f = jax.shard_map(
        lambda x: x * jax.lax.axis_size("w"), mesh=mesh,
        in_specs=P("w"), out_specs=P("w"), check_vma=False)
    out = f(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


# ------------------------------------------------------------ timeline model
def test_tictac_bucketed_overlap_beats_no_overlap():
    from repro.train import DataParallelConfig, DataParallelEngine
    params = {f"layer{i}": jnp.zeros((256, 256)) for i in range(12)}
    cfg = DataParallelConfig(num_workers=1, bucket_mb=0.5, order="tictac",
                             link=LinkModel(alpha_s=5e-6, beta_Bps=50e9),
                             back_s_per_byte=2e-11)
    eng = DataParallelEngine(cfg, grad_fn=lambda p, b: (jnp.float32(0), p))
    tl = eng.modeled_timeline(params)
    assert tl["n_buckets"] > 1
    assert tl["overlap_s"] < tl["no_overlap_s"]


def test_bucket_plan_covers_every_leaf_once():
    from repro.train import DataParallelConfig, DataParallelEngine
    params = {f"l{i}": jnp.zeros((64, 64)) for i in range(7)}
    eng = DataParallelEngine(
        DataParallelConfig(num_workers=1, bucket_mb=0.03),
        grad_fn=lambda p, b: (jnp.float32(0), p))
    buckets, order, fused = eng._bucket_plan(params)
    covered = sorted(i for b in buckets for i in b)
    assert covered == list(range(7))
    assert sorted(order) == list(range(len(fused)))


# ----------------------------------------------- sharded engine (subprocess)
SCRIPT_ENGINE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core import Compressor, SyncConfig, SyncEngine
from repro.data import LMDataConfig, make_lm_batches
from repro.models import build_model
from repro.train import DataParallelConfig, DataParallelEngine

cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=8, batch_size=2)
batches = make_lm_batches(data)
def grad_fn(p, batch):
    (loss, _), g = jax.value_and_grad(
        lambda pp: model.loss_fn(pp, batch, compute_dtype=jnp.float32),
        has_aux=True)(p)
    return loss, g

K, steps = 8, 3
# --- bsp/none: device-sharded engine == single-device simulator ---
dp = DataParallelEngine(DataParallelConfig(num_workers=K, lr=0.01), grad_fn)
p_dp, h_dp, w_dp = dp.run(params, batches, steps)
sim = SyncEngine(SyncConfig(mode="bsp", num_workers=K, lr=0.01), grad_fn)
p_sim, h_sim, w_sim = sim.run(params, batches, steps)
for a, b in zip(h_dp, h_sim):
    assert abs(a["loss"] - b["loss"]) <= 1e-4, (a, b)
pd = max(float(jnp.max(jnp.abs(x - y)))
         for x, y in zip(jax.tree.leaves(p_dp), jax.tree.leaves(p_sim)))
assert pd <= 1e-4, pd
assert w_dp == w_sim, (w_dp, w_sim)
print("ENGINE-MATCHES-SIM")

# --- compressed path: Pallas kernel vs jnp oracle, bit-identical losses ---
losses = {}
for backend in ("ref", "kernel"):
    eng = DataParallelEngine(
        DataParallelConfig(num_workers=K, lr=0.01, topology="butterfly",
                           compressor=Compressor("onebit",
                                                 backend=backend)),
        grad_fn)
    _, h, w = eng.run(params, batches, 2)
    losses[backend] = [x["loss"] for x in h]
    assert w == eng.wire_bytes_per_step(params) * 2, (
        w, eng.wire_bytes_per_step(params))
assert losses["ref"] == losses["kernel"], losses
print("KERNEL-REF-IDENTICAL")

# --- EF state round-trips: second run from engine state continues sane ---
eng = DataParallelEngine(
    DataParallelConfig(num_workers=K, lr=0.01,
                       compressor=Compressor("dgc", density=0.05)), grad_fn)
p1, h1, w1 = eng.run(params, batches, 2)
assert all(jnp.isfinite(jnp.float32(h["loss"])) for h in h1)
assert w1 == eng.wire_bytes_per_step(params) * 2
print("EF-WIRE-OK")
"""


def test_data_parallel_engine_8dev(multidevice):
    out = multidevice(SCRIPT_ENGINE, 8)
    assert "ENGINE-MATCHES-SIM" in out
    assert "KERNEL-REF-IDENTICAL" in out
    assert "EF-WIRE-OK" in out
