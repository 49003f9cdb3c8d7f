"""Hybrid-parallel subsystem tests (ISSUE 4 tentpole).

Covers: the mesh suffix grammar and its Strategy roundtrip, MeshPlan
construction (role-based tensor dims, local block shapes, ZeRO shard
sizes), the ZeRO memory model, and — in an 8-virtual-device subprocess —
the acceptance criteria: a ``d2.t2.s2`` mesh matching the single-device
stacked reference to ≤1e-4, ``dK.t1.s1`` bitwise-identical to the plain
data-parallel engine, ZeRO-3 cutting measured per-device param+optimizer
bytes by ~the data-axis factor, and ZeRO-3 AdamW surviving the
``crash:w1@5,resize:4@10`` elastic plan.
"""
import numpy as np
import pytest

from repro.parallel import (MeshSpec, parse_suffix, plan_mesh,
                            state_bytes_per_device, suffix_spec,
                            wire_bytes_per_device)
from repro.train import Strategy


# ------------------------------------------------------------- grammar
def test_mesh_suffix_parse_and_roundtrip():
    fields, named = parse_suffix("d2.t2.s2")
    assert fields["mesh"] == MeshSpec(2, 2, 2)
    assert named["mesh"] and not named["zero"]
    fields, named = parse_suffix("d4.z3.adamw")
    assert (fields["mesh"], fields["zero"], fields["optimizer"]) == \
        (MeshSpec(4, 1, 1), 3, "adamw")
    assert suffix_spec(MeshSpec(2, 2, 2), 3, "adamw", 6) == \
        "d2.t2.s2.z3.m6.adamw"
    assert suffix_spec(MeshSpec(4, 1, 1)) == ""     # trivial mesh: minimal


def test_mesh_suffix_rejects_bad_tokens():
    for bad in ("d2.q3", "d2.d4", "adamw.adamw", "sgd.adamw", "d", "z9x",
                ""):
        with pytest.raises(ValueError):
            parse_suffix(bad)
    # the stage token and the sgd optimizer token share a first letter —
    # they must not collide in the duplicate check
    fields, _ = parse_suffix("s2.sgd")
    assert fields["mesh"].stage == 2 and fields["optimizer"] == "sgd"


def test_strategy_mesh_spec_roundtrip():
    s = Strategy.parse("bsp/ring/onebit@8:d2.t2.s2")
    assert s.mesh == MeshSpec(2, 2, 2) and s.is_hybrid
    assert s.spec() == "bsp/allreduce/onebit@8:d2.t2.s2"
    assert Strategy.parse(s.spec()) == s
    z = Strategy.parse("bsp/ps/none@4:d4.z3.adamw")
    assert (z.zero, z.optimizer, z.is_hybrid) == (3, "adamw", True)
    assert Strategy.parse(z.spec()) == z


def test_trivial_mesh_normalizes_to_plain_data_parallel():
    s = Strategy.parse("bsp/allreduce/none@4:d4.t1.s1")
    assert s.mesh is None and not s.is_hybrid
    assert s.spec() == "bsp/allreduce/none@4"
    assert s == Strategy.parse("bsp/allreduce/none@4")


def test_mesh_field_rejects_non_axis_tokens():
    # Strategy(mesh="d4.z3") must not silently train un-sharded
    with pytest.raises(ValueError, match="non-axis"):
        Strategy(sync="bsp", arch="ps", workers=4, mesh="d4.z3")
    with pytest.raises(ValueError, match="non-axis"):
        MeshSpec.parse("d4.adamw")


def test_strategy_rejects_bad_hybrid_specs():
    for bad in ("bsp/ring/none@8:d2.t2",        # product != workers
                "bsp/ring/none@8:d2.t2.s2.z1",  # zero needs arch=ps
                "ssp/ring/none@8:d2.t2.s2",     # hybrid is bsp-only
                "bsp+backup:1/ring/none@8:d2.t2.s2",  # no backup on meshes
                "bsp+detect/ps/none@8:d8.z3.adamw",   # detect is inert here
                "bsp/ps/none@4:d4.z4",          # no such ZeRO level
                ):
        with pytest.raises(ValueError):
            Strategy.parse(bad)
    with pytest.raises(ValueError, match="device-only"):
        Strategy.parse("bsp/ps/none@4:d4.z2", backend="sim").resolve_backend()


def test_hybrid_cells_resolve_to_device_backend():
    s = Strategy.parse("bsp/ring/none@8:d2.t2.s2")
    assert s.resolve_backend() == "device"


# ------------------------------------------------------------ mesh plan
def _staged_params(layers=4, d=8, f=16):
    return {"w_up": np.zeros((layers, d, f), np.float32),
            "w_down": np.zeros((layers, f, d), np.float32)}


def test_plan_mesh_role_dims_and_local_shapes():
    plan = plan_mesh(_staged_params(), MeshSpec(2, 2, 2), staged=True,
                     bucket_mb=1e-4)
    # w_up is column-parallel (shard d_ff = dim 2), w_down row-parallel
    # (shard d_ff = dim 1); leading layer dim divides over 2 stages
    shapes = {tuple(x.shape) for x in
              [plan.local_example["w_up"], plan.local_example["w_down"]]}
    assert shapes == {(2, 8, 8), (2, 8, 8)}
    assert sorted(plan.tensor_dims) == [1, 2]
    assert plan.micro == 4                       # auto: 2 * stages
    # ZeRO shards: per-bucket local size / data axis, rounded up
    for n, m in zip(plan.bucket_sizes, plan.shard_sizes):
        assert m == -(-n // 2)


def test_plan_mesh_rejects_bad_geometry():
    with pytest.raises(ValueError, match="stage axis"):
        plan_mesh(_staged_params(layers=3), MeshSpec(1, 1, 2), staged=True)
    with pytest.raises(ValueError, match="divisible by tensor"):
        plan_mesh(_staged_params(f=6), MeshSpec(1, 4, 1), staged=True)
    with pytest.raises(ValueError, match="model-parallel"):
        plan_mesh({"u": np.zeros((4, 8, 8), np.float32)}, MeshSpec(1, 2, 1),
                  staged=True)


def test_zero_memory_model_scales_with_data_axis():
    plan = plan_mesh(_staged_params(), MeshSpec(4, 1, 1), staged=True,
                     bucket_mb=1e-4)
    z0 = state_bytes_per_device(plan, 0, "adamw")
    z1 = state_bytes_per_device(plan, 1, "adamw")
    z3 = state_bytes_per_device(plan, 3, "adamw")
    assert z0["opt"] == pytest.approx(2 * z0["params"], rel=0.01)
    assert z1["opt"] <= z0["opt"] / 3            # ~/4 with padding slack
    assert z3["total"] <= z0["total"] / 3
    # wire model: z2/z3 (RS+AG) never exceed z1 (AR+AG)
    assert wire_bytes_per_device(plan, 2) <= wire_bytes_per_device(plan, 1)


@pytest.mark.parametrize("spec", ["bsp/ring/none@1:d1.adamw",
                                  "bsp/ps/none@1:d1.z2.adamw"])
def test_bf16_moments_halve_optimizer_bytes(spec):
    """``qmom`` stores the AdamW moments in bfloat16: the engine's
    measured persistent optimizer bytes fall 1.8-2.2x against the fp32
    moments of the same cell, and the memory model's exactly 2x."""
    from repro.parallel import make_tiny_transformer
    params, model = make_tiny_transformer(2, 32, 64, seed=0)
    measured, modeled = {}, {}
    for moments, suffix in (("float32", ""), ("bfloat16", ".qmom")):
        strat = Strategy.parse(spec.replace(".adamw", suffix + ".adamw"),
                               lr=0.01, bucket_mb=1e-3, backend="device")
        assert strat.moments == moments
        inner = strat.build(model).inner
        measured[moments] = inner.per_device_state_bytes(
            inner.init(params))["opt"]
        modeled[moments] = state_bytes_per_device(
            inner.plan, strat.zero, "adamw", moments)["opt"]
    assert 1.8 <= measured["float32"] / measured["bfloat16"] <= 2.2, measured
    assert modeled["float32"] == 2 * modeled["bfloat16"], modeled


# -------------------------------------- 8-virtual-device acceptance run
SCRIPT_ACCEPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.train import Strategy, Trainer
from repro.parallel import make_tiny_transformer, stacked_grad_fn

S, D_MODEL, FF = 2, 8, 16
params, model = make_tiny_transformer(S, D_MODEL, FF, seed=0)
KEY = jax.random.PRNGKey(1)
W_T = jax.random.normal(KEY, (D_MODEL, D_MODEL))
def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    x = jax.random.normal(k, (8, D_MODEL))
    return {"x": x, "y": jnp.tanh(x @ W_T)}
LR, STEPS = 0.05, 4
gf = stacked_grad_fn(model)

def ref_run(d_axis):
    p, losses = params, []
    for t in range(STEPS):
        cat = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                           *[make_batch(t, w) for w in range(d_axis)])
        loss, g = gf(p, cat)
        losses.append(float(loss))
        p = jax.tree.map(lambda a, b: a - LR * b, p, g)
    return p, losses

# 1. the d2.t2.s2 acceptance mesh matches the single-device reference
p_ref, l_ref = ref_run(2)
eng = Strategy.parse("bsp/ring/none@8:d2.t2.s2", lr=LR, bucket_mb=1e-4,
                     backend="device").build(model)
p_dev, h_dev, wire = eng.run(params, make_batch, STEPS)
ld = max(abs(a - b["loss"]) for a, b in zip(l_ref, h_dev))
pd = max(float(jnp.max(jnp.abs(x - y))) for x, y in
         zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_dev)))
assert ld <= 1e-4 and pd <= 1e-4, (ld, pd)
assert wire > 0
print(f"MESH-REF-OK {ld:.2e} {pd:.2e}")

# 2. a dK.t1.s1 mesh is bitwise the plain data-parallel engine
for spec_a, spec_b in (("bsp/ring/onebit@4", "bsp/ring/onebit@4:d4.t1.s1"),):
    a = Strategy.parse(spec_a, lr=LR, bucket_mb=1e-4, backend="device").build(model)
    b = Strategy.parse(spec_b, lr=LR, bucket_mb=1e-4, backend="device").build(model)
    assert type(a.inner) is type(b.inner)
    pa, ha, wa = a.run(params, make_batch, 3)
    pb, hb, wb = b.run(params, make_batch, 3)
    assert [h["loss"] for h in ha] == [h["loss"] for h in hb]
    assert wa == wb
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print("TRIVIAL-MESH-BITWISE-OK")

# 3. measured ZeRO-3 per-device param+opt bytes drop ~the data factor,
#    and the z3 trajectory matches z0 exactly (same optimizer math)
D = 4
z0 = Strategy.parse("bsp/ring/none@4:d4.adamw", lr=LR, bucket_mb=1e-4,
                    backend="device").build(model)
z3 = Strategy.parse("bsp/ps/none@4:d4.z3.adamw", lr=LR, bucket_mb=1e-4,
                    backend="device").build(model)
st0, st3 = z0.inner.init(params), z3.inner.init(params)
b0 = z0.inner.per_device_state_bytes(st0)
b3 = z3.inner.per_device_state_bytes(st3)
ratio = b0["total"] / b3["total"]
assert ratio >= 0.8 * D, (b0, b3, ratio)
p0, h0, _ = z0.run(params, make_batch, 3)
p3, h3, _ = z3.run(params, make_batch, 3)
ld = max(abs(a["loss"] - b["loss"]) for a, b in zip(h0, h3))
assert ld <= 1e-5, ld
print(f"ZERO3-BYTES-OK ratio {ratio:.2f} (z0 {b0['total']} z3 {b3['total']})")

# 4. ZeRO-3 AdamW survives the crash:w1@5,resize:4@10 plan
import tempfile
strat = Strategy.parse("bsp/ps/none@4:d4.z3.adamw", lr=LR, bucket_mb=1e-4,
                       backend="device")
p_u, h_u, m_u = Trainer(strat).fit(model, params, make_batch, 12)
with tempfile.TemporaryDirectory() as d:
    p_e, h_e, m_e = Trainer(strat).fit(
        model, params, make_batch, 12, plan="crash:w1@5,resize:4@10",
        checkpoint_dir=d, checkpoint_every=3)
(r,) = m_e["recoveries"]
assert r["kind"] == "crash" and r["lost_worker"] == 1
assert m_e["resizes"] == 1 and m_e["final_workers"] == 4
lu, le = h_u[-1]["loss"], h_e[-1]["loss"]
assert np.isfinite(le) and le <= 4 * max(lu, h_u[0]["loss"] / 4)
print(f"ZERO3-ELASTIC-OK {le:.4f} vs {lu:.4f}")

# 5. crashing a device of a t*s>1 mesh drops its whole model-parallel
# block (one data replica: 8 -> 4 devices), and slow events map flat
# device ids onto data slots instead of raising
strat3d = Strategy.parse("bsp/ring/none@8:d2.t2.s2", lr=LR,
                         bucket_mb=1e-4, backend="device")
eng3d = strat3d.build(model)
assert eng3d.inner.crash_plan(5) == (4, (1,))
eng3d.set_slowdown(5, 2.0)
assert eng3d.inner.slowdowns == [1.0, 2.0]
try:
    eng3d.set_slowdown(9, 2.0)
    raise AssertionError("out-of-range slow event accepted")
except ValueError:
    pass
with tempfile.TemporaryDirectory() as d:
    p_c, h_c, m_c = Trainer(strat3d).fit(
        model, params, make_batch, 8, plan="crash:w5@4",
        checkpoint_dir=d, checkpoint_every=2)
(r,) = m_c["recoveries"]
assert r["kind"] == "crash" and m_c["final_workers"] == 4, m_c
assert np.isfinite(h_c[-1]["loss"])
print("MESH-CRASH-OK")
print("HYBRID-ACCEPT-OK")
"""


def test_hybrid_acceptance_8dev(multidevice):
    out = multidevice(SCRIPT_ACCEPT, 8)
    assert "MESH-REF-OK" in out
    assert "TRIVIAL-MESH-BITWISE-OK" in out
    assert "ZERO3-BYTES-OK" in out
    assert "ZERO3-ELASTIC-OK" in out
    assert "MESH-CRASH-OK" in out
    assert "HYBRID-ACCEPT-OK" in out
