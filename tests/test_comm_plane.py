"""Unified communication plane tests (ISSUE 5 tentpole).

Covers: codec encode/decode roundtrips, schedule consistency (every
worker decodes identical bytes) and the EF telescoping invariant across
all topologies, the wire-byte property (onebit < terngrad < qsgd < none,
and measured-vs-critical-path-model agreement within the documented
error factors), bitwise ``bsp/*/none`` equivalence of the modeled and
measured modes, the dgc cached-wire regression, device SMA vs the
simulator, and the ISSUE acceptance cells (``bsp/ring/onebit@8`` with
``wire=measured`` at ≤0.25× fp32-ring bytes inside the loss band;
``ssp:2/ring/onebit@8:d4.t2`` staleness replay).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import make_codec
from repro.comm.transport import (model_error_factor, per_device_bytes,
                                  schedule_tx_bytes)

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:          # hypothesis is an optional dev dep
    HAVE_HYPOTHESIS = False

TOPOLOGIES = ("ring", "tree", "butterfly")
METHODS = ("onebit", "terngrad", "qsgd")


# ------------------------------------------------------------- codec units
@pytest.mark.parametrize("method", METHODS + ("dgc", "none"))
def test_codec_roundtrip_shape_and_finiteness(method):
    codec = make_codec(method) if method != "dgc" else \
        make_codec("dgc", density=0.1)
    seg = jax.random.normal(jax.random.PRNGKey(0), (700,))   # odd length
    planes = codec.encode(seg, jax.random.PRNGKey(1))
    dec = codec.decode(planes)[:700]
    assert dec.shape == seg.shape
    assert bool(jnp.all(jnp.isfinite(dec)))
    if method == "none":
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(seg))


def test_onebit_codec_pads_without_bias():
    """A segment of one sign must decode to its two-bin means with zero
    influence from the pad zeros."""
    codec = make_codec("onebit")
    seg = jnp.full((100,), 3.0)                  # 100 << LANE, all positive
    dec = codec.decode(codec.encode(seg))[:100]
    np.testing.assert_allclose(np.asarray(dec), 3.0, rtol=1e-6)


def test_dgc_codec_counts_only_valid_elements():
    codec = make_codec("dgc", density=0.1)
    seg = jax.random.normal(jax.random.PRNGKey(0), (500,))
    planes = codec.encode(seg)
    nnz = int(codec.sent_elems(planes))
    # ~10% of 500, never counting the 12 pad-row slots
    assert 40 <= nnz <= 75, nnz


# -------------------------------------------------- wire-byte property
def _check_wire_property(n, length):
    fp32 = {t: schedule_tx_bytes(t, n, length, make_codec("none"))
            for t in TOPOLOGIES}
    for topo in TOPOLOGIES:
        tx = {m: schedule_tx_bytes(topo, n, length, make_codec(m))
              for m in METHODS}
        # ordering: 1 bit < 2 bits < 8 bits < fp32, per worker
        assert tx["onebit"] < tx["terngrad"] < tx["qsgd"] < fp32[topo], \
            (topo, n, length, tx, fp32[topo])
        # the critical-path model divided by the documented error factor
        # predicts the measured mean-tx within the side-info/padding slack
        for m in METHODS:
            codec = make_codec(m)
            model = per_device_bytes(topo, n, codec.static_tx_bytes(length))
            predicted = model / model_error_factor(topo, n, exact=False)
            assert predicted == pytest.approx(tx[m], rel=0.25), \
                (topo, m, n, length, predicted, tx[m])


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(n=hst.sampled_from([2, 4, 8, 16]),
           length=hst.integers(min_value=64, max_value=4096))
    def test_wire_bytes_property(n, length):
        # per-chunk payloads below ~64 elements are dominated by row side
        # info (the same reason Compressor has min_channel); the property
        # holds from there up
        _check_wire_property(n, max(length, 64) * n)
else:
    @pytest.mark.parametrize("n,length", [(2, 2048), (4, 4096), (8, 8192)])
    def test_wire_bytes_property(n, length):     # hypothesis-free fallback
        _check_wire_property(n, length)


def test_model_error_factor_is_exact_for_none():
    """For the exact codec the documented factors reconcile the two byte
    measures exactly (no side-info slack)."""
    none = make_codec("none")
    L = 4096
    for n in (2, 4, 8):
        for topo in TOPOLOGIES + ("fully_connected",):
            tx = schedule_tx_bytes(topo, n, L, none)
            model = per_device_bytes(topo, n, 4 * L)
            assert model / model_error_factor(topo, n, exact=True) == \
                pytest.approx(tx, rel=1e-6), (topo, n)


# ---------------------------------------------- schedule consistency (4dev)
SCRIPT_SCHEDULES = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm.codecs import make_codec
from repro.comm.transport import compressed_allreduce, pad_for_schedule

n = 4
mesh = Mesh(np.array(jax.devices()[:n]), ("w",))
L = 1000
x = jax.random.normal(jax.random.PRNGKey(0), (n, L)) * (1 + jnp.arange(n)[:, None])
for topo in ("ring", "tree", "butterfly", "fully_connected"):
    for method in ("onebit", "terngrad", "qsgd", "dgc"):
        codec = make_codec(method) if method != "dgc" else make_codec("dgc", density=0.1)
        Pl = pad_for_schedule(L, n)
        def body(xx, kk):
            flat = jnp.pad(xx[0], (0, Pl - L))
            red, res, sent = compressed_allreduce(flat, "w", topo, codec, kk[0])
            return red[None, :L], res[None, :L], sent[None]
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("w"), P("w")),
                    out_specs=(P("w"), P("w"), P("w")), check_vma=False))
        red, res, sent = f(x, jax.random.split(jax.random.PRNGKey(1), n))
        red, res = np.asarray(red), np.asarray(res)
        # every worker must decode the *identical* reduced vector
        assert np.max(np.abs(red - red[0])) == 0.0, (topo, method)
        # EF telescoping: reduced + sum(residuals) == true sum (fp32 tol)
        true = np.asarray(jnp.sum(x, 0))
        gap = np.max(np.abs(red[0] + res.sum(0) - true)) / np.max(np.abs(true))
        assert gap < 1e-5, (topo, method, gap)
print("SCHEDULES-OK")
"""


def test_codec_schedules_consistent_and_telescoping_4dev(multidevice):
    assert "SCHEDULES-OK" in multidevice(SCRIPT_SCHEDULES, 4)


# --------------------------------- engine integration (subprocess, 4 devices)
SCRIPT_ENGINE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.train import Strategy

KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (64, 1))
def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    X = jax.random.normal(k, (16, 64))
    return {"X": X, "y": X @ W_TRUE}
def sparse_batch(t, w):
    # step 0 only the first feature is active -> gradient rows are exact
    # zeros -> dgc's quantile threshold degenerates and the sparse
    # payload balloons; later steps are dense
    b = make_batch(t, w)
    if t == 0:
        mask = jnp.zeros((64,)).at[0].set(1.0)
        X = b["X"] * mask
        return {"X": X, "y": X @ W_TRUE}
    return b
def grad_fn(params, batch):
    def loss(p):
        return jnp.mean((batch["X"] @ p["W"] - batch["y"]) ** 2)
    return jax.value_and_grad(loss)(params)
P0 = {"W": jnp.zeros((64, 1)), "b": jnp.zeros((8192,))}

# --- bsp/*/none: modeled and measured execute bitwise-identically ---
for arch in ("allreduce", "ps"):
    runs = {}
    for wire in ("modeled", "measured"):
        eng = Strategy(sync="bsp", arch=arch, workers=4, lr=0.05,
                       backend="device", wire=wire).build(grad_fn)
        runs[wire] = eng.run(P0, make_batch, 3)
    for a, b in zip(jax.tree.leaves(runs["modeled"][0]),
                    jax.tree.leaves(runs["measured"][0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss"] for h in runs["modeled"][1]] == \
           [h["loss"] for h in runs["measured"][1]], arch
print("NONE-BITWISE-OK")

# --- measured wire ordering through the real engine ---
wires = {}
for comp in ("onebit", "terngrad", "qsgd", "none"):
    eng = Strategy(sync="bsp", workers=4, lr=0.05, compression=comp,
                   backend="device", wire="measured").build(grad_fn)
    _, h, w = eng.run(P0, make_batch, 4)
    assert all(np.isfinite(e["loss"]) for e in h), comp
    wires[comp] = w
assert wires["onebit"] < wires["terngrad"] < wires["qsgd"] < wires["none"], wires
print("ORDERING-OK")

# --- dgc regression: measured bytes are recomputed per bucket per step,
# not cached from step 0 (the step-0 payload here is degenerate-dense) ---
eng = Strategy(sync="bsp", workers=4, lr=0.05, compression="dgc",
               density=0.05, backend="device", wire="measured").build(grad_fn)
st = eng.init(P0)
incs, prev = [], 0
for t in range(3):
    st, _ = eng.step(st, sparse_batch, t)
    incs.append(st["wire"] - prev)
    prev = st["wire"]
assert incs[0] != incs[1], incs   # cached step-0 accounting would repeat
assert incs[1] == incs[2] or abs(incs[1] - incs[2]) < incs[0], incs
print("DGC-PER-STEP-OK", incs)

# --- device SMA cross-validates the simulator (the CommPlan exchange) ---
sim = Strategy(sync="sma", workers=4, lr=0.05, backend="sim").build(grad_fn)
ps, hs, ws = sim.run(P0, make_batch, 6)
dev = Strategy(sync="sma", workers=4, lr=0.05, backend="device").build(grad_fn)
pd, hd, wd = dev.run(P0, make_batch, 6)
ld = max(abs(a["loss"] - b["loss"]) for a, b in zip(hs, hd))
pdiff = max(float(jnp.max(jnp.abs(x - y)))
            for x, y in zip(jax.tree.leaves(ps), jax.tree.leaves(pd)))
assert ld <= 1e-4 and pdiff <= 1e-4 and ws == wd, (ld, pdiff, ws, wd)
# and the SMA engine snapshots/reshards like every other cell
st = dev.init(P0)
st, _ = dev.step(st, make_batch, 0)
arrays, meta = dev.export_state(st)
st2 = dev.import_state(arrays, meta)
st2 = dev.reshard(st2, 2, step=1)
st2, ev = dev.step(st2, make_batch, 1)
assert np.isfinite(ev[0]["loss"])
print("SMA-DEVICE-OK")
"""


def test_comm_plane_engine_4dev(multidevice):
    out = multidevice(SCRIPT_ENGINE, 4)
    for marker in ("NONE-BITWISE-OK", "ORDERING-OK", "DGC-PER-STEP-OK",
                   "SMA-DEVICE-OK"):
        assert marker in out


# ------------------------- bf16 reduce precision × wire=measured accounting
SCRIPT_BF16_WIRE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.train import Strategy

KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (64, 1))
def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    X = jax.random.normal(k, (16, 64))
    return {"X": X, "y": X @ W_TRUE}
def grad_fn(params, batch):
    def loss(p):
        return jnp.mean((batch["X"] @ p["W"] - batch["y"]) ** 2)
    return jax.value_and_grad(loss)(params)
P0 = {"W": jnp.zeros((64, 1)), "b": jnp.zeros((8192,))}

def run(precision, comp="none"):
    eng = Strategy(sync="bsp", workers=4, lr=0.05, compression=comp,
                   optimizer="adamw", precision=precision,
                   backend="device", wire="measured").build(grad_fn)
    _, hist, _ = eng.run(P0, make_batch, 6)
    losses = [h["loss"] for h in hist]
    assert all(np.isfinite(l) for l in losses), (precision, comp)
    return losses, eng.metrics()["measured_step_tx_bytes"]

# --- none@bf16r: the uncompressed reduce travels in 2-byte words, so the
# measured grad exchange is exactly half the fp32 cell's ---
l32, b32 = run("fp32")
l16, b16 = run("bf16r")
assert b16 * 2 == b32, (b16, b32)
# and the loss trajectory holds a loose band around fp32 (bf16 mantissa)
for a, b in zip(l32, l16):
    assert abs(a - b) <= 0.25 * abs(a) + 1e-3, (l32, l16)
print(f"BF16R-HALF-WIRE-OK fp32={b32} bf16r={b16}")

# --- a lossy codec is precision-invariant on the wire: its planes are
# already 1-bit + fp32 scales, whatever dtype the reduce would have used ---
_, ob32 = run("fp32", "onebit")
_, ob16 = run("bf16r", "onebit")
assert ob16 == ob32, (ob16, ob32)
assert ob16 < b16, (ob16, b16)
print("BF16R-CODEC-OK")
"""


def test_bf16_reduce_wire_accounting_4dev(multidevice):
    out = multidevice(SCRIPT_BF16_WIRE, 4)
    assert "BF16R-HALF-WIRE-OK" in out
    assert "BF16R-CODEC-OK" in out


# -------------------------------- ISSUE acceptance (subprocess, 8 devices)
SCRIPT_ACCEPTANCE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.data import LMDataConfig, make_lm_batches
from repro.models import build_model
from repro.train import Strategy
from repro.parallel import make_tiny_transformer

# --- bsp/ring/onebit@8 wire=measured: <=0.25x fp32-ring bytes AND the
# seed-pinned loss-ratio band of the composition tests (test_system) ---
cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4)
batches = make_lm_batches(data)
def grad_fn(p, batch):
    (loss, _), g = jax.value_and_grad(
        lambda pp: model.loss_fn(pp, batch, compute_dtype=jnp.float32),
        has_aux=True)(p)
    return loss, g

eng = Strategy.parse("bsp/ring/onebit@8", lr=0.01, backend="device",
                     wire="measured").build(grad_fn)
p_final, hist, wire = eng.run(params, batches, 10)
m = eng.metrics()
ratio_bytes = m["measured_step_tx_bytes"] / m["fp32_step_tx_bytes"]
assert ratio_bytes <= 0.25, ratio_bytes
losses = [h["loss"] for h in hist]
assert all(np.isfinite(l) for l in losses)
loss_ratio = (sum(losses[-3:]) / 3) / (sum(losses[:3]) / 3)
assert loss_ratio < 1.001, loss_ratio       # the existing EF band
moved = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p_final),
                            jax.tree.leaves(params)))
assert moved > 0.0
print(f"ONEBIT-MEASURED-OK bytes_ratio={ratio_bytes:.4f} "
      f"loss_ratio={loss_ratio:.5f}")

# --- ssp:2/ring/onebit@8:d4.t2 runs end-to-end, staleness schedule
# matches the simulator exactly ---
sparams, smodel = make_tiny_transformer(stages=2, d_model=8, d_ff=16)
KEY = jax.random.PRNGKey(0)
def sbatches(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    x = jax.random.normal(k, (4, 8))
    return {"x": x, "y": x * 0.5}

sim = Strategy(sync="ssp", staleness=2, workers=4, lr=0.05,
               compression="onebit", backend="sim").build(smodel)
_, hs, ws = sim.run(sparams, sbatches, 3)
dev = Strategy.parse("ssp:2/ring/onebit@8:d4.t2", lr=0.05,
                     backend="device").build(smodel)
_, hd, wd = dev.run(sparams, sbatches, 3)
assert [e["worker"] for e in hd] == [e["worker"] for e in hs]
assert [e["max_staleness"] for e in hd] == [e["max_staleness"] for e in hs]
assert all(np.isfinite(e["loss"]) for e in hd)
assert ws == wd, (ws, wd)
# the uncompressed mesh cell additionally cross-validates losses <=1e-4
sim0 = Strategy(sync="ssp", staleness=2, workers=4, lr=0.05,
                backend="sim").build(smodel)
_, hs0, _ = sim0.run(sparams, sbatches, 3)
dev0 = Strategy.parse("ssp:2/ring/none@8:d4.t2", lr=0.05,
                      backend="device").build(smodel)
_, hd0, _ = dev0.run(sparams, sbatches, 3)
ld = max(abs(a["loss"] - b["loss"]) for a, b in zip(hs0, hd0))
assert ld <= 1e-4, ld
print("SSP-MESH-OK")
"""


def test_comm_plane_acceptance_8dev(multidevice):
    out = multidevice(SCRIPT_ACCEPTANCE, 8)
    assert "ONEBIT-MEASURED-OK" in out
    assert "SSP-MESH-OK" in out
