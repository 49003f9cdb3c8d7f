"""Pipeline-schedule gradient tests: the ``lax.scan`` + ``ppermute``
pipelines of core/pipeline.py (GPipe and interleaved 1F1B) are
differentiable, and their loss/gradients match the unpipelined stacked
model to ≤1e-5 — including micro-batch counts that do not divide the
stage count, the m == s drain boundary, and the d2.t2.s2 composed mesh
through the full Strategy path.  Bubble/tick accounting is asserted
host-side.
"""
import pytest

from repro.core.pipeline import (bubble_fraction, gpipe_ticks,
                                 onefb_bubble_fraction, onefb_ticks)


# ----------------------------------------------------- bubble accounting
def test_gpipe_tick_and_bubble_accounting():
    # M micro-batches drain through S stages in M + S - 1 ticks
    assert gpipe_ticks(1, 4) == 4
    assert gpipe_ticks(4, 1) == 4
    assert gpipe_ticks(2, 3) == 4
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # more micro-batches amortize the bubble monotonically
    fracs = [bubble_fraction(4, m) for m in (1, 2, 4, 8, 16)]
    assert fracs == sorted(fracs, reverse=True)
    # tick count times per-tick work bounds the ideal speedup
    assert gpipe_ticks(4, 16) == 19          # vs 64 sequential stage calls


def test_onefb_tick_and_bubble_accounting():
    # v virtual chunks per device: v*M chunk-micro units drain through S
    # devices in v*M + S - 1 ticks; each tick does 1/v of a stage's work
    assert onefb_ticks(4, 8, interleave=2) == 19
    assert onefb_ticks(4, 8, interleave=1) == 11
    assert onefb_bubble_fraction(4, 8, 2) == pytest.approx(3 / 19)
    # plain (v=1) 1F1B has the same bubble *fraction* as GPipe — the
    # schedule reorders work but idles the same ramp ticks; only
    # interleaving shrinks the bubble
    for s, m in ((2, 4), (4, 8), (4, 6)):
        assert onefb_bubble_fraction(s, m, 1) == \
            pytest.approx(bubble_fraction(s, m))
        for v in (2, 4):
            assert onefb_bubble_fraction(s, m, v) < bubble_fraction(s, m)
    # more chunks amortize monotonically
    fracs = [onefb_bubble_fraction(4, 8, v) for v in (1, 2, 4, 8)]
    assert fracs == sorted(fracs, reverse=True)


@pytest.mark.parametrize("stages,micro,v", [(2, 8, 2), (4, 8, 2), (4, 6, 2),
                                            (2, 4, 4)])
def test_onefb_critical_path_under_gpipe(stages, micro, v):
    """Interleaved 1F1B against GPipe on the same stages and micro-batch
    count: its modeled critical path (ticks, each 1/v of a stage's work)
    and its analytic bubble are both strictly smaller."""
    assert onefb_ticks(stages, micro, v) / v < gpipe_ticks(stages, micro)
    assert onefb_bubble_fraction(stages, micro, v) < \
        bubble_fraction(stages, micro)


# --------------------------------------- pipeline grads vs stacked model
SCRIPT_GRADS = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.pipeline import (bubble_fraction, gpipe_forward,
                                 gpipe_ticks, stacked_forward)
from repro.parallel import make_tiny_transformer

D_MODEL, FF = 8, 16
KEY = jax.random.PRNGKey(7)

def run_case(n_stages, n_micro, mb):
    params, model = make_tiny_transformer(n_stages, D_MODEL, FF,
                                          seed=n_stages)
    stage_fn = lambda sp, x: model.stage_fn(sp, x)
    x = jax.random.normal(KEY, (n_micro, mb, D_MODEL))
    tgt = jax.random.normal(jax.random.fold_in(KEY, 1),
                            (n_micro, mb, D_MODEL))

    # ---- reference: unpipelined stacked forward + MSE loss and grads
    def ref_loss(p):
        y = stacked_forward(stage_fn, p, x)
        return jnp.mean((y - tgt) ** 2)
    l_ref, g_ref = jax.value_and_grad(ref_loss)(params)

    # ---- pipelined: shard_map over the stage axis, loss on last stage
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("stage",))
    def body(stacked):
        sp = stacked            # [chunk=1 layers...] via stage sharding
        def loss_fn(pl):
            outs = gpipe_forward(
                lambda spp, xx: stage_fn(
                    jax.tree.map(lambda l: l[0], spp), xx), pl, x, "stage")
            l = jnp.mean((outs - tgt) ** 2)
            me = jax.lax.axis_index("stage")
            from repro.parallel.staged import tensor_reduce
            l = jnp.where(me == n_stages - 1, l, 0.0)
            return tensor_reduce("stage")(l)
        return jax.value_and_grad(loss_fn)(sp)
    spec = jax.tree.map(lambda _: P("stage"), params)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                       out_specs=(P(), spec), check_vma=False)
    l_pipe, g_pipe = jax.jit(fn)(params)

    ld = abs(float(l_ref) - float(l_pipe))
    gd = max(float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pipe)))
    assert ld <= 1e-5, (n_stages, n_micro, ld)
    assert gd <= 1e-5, (n_stages, n_micro, gd)
    # bubble accounting: the executed schedule ran exactly
    # gpipe_ticks(S, M) ticks, of which (S-1)/(M+S-1) are idle
    ticks = gpipe_ticks(n_stages, n_micro)
    assert ticks == n_micro + n_stages - 1
    assert 0 <= bubble_fraction(n_stages, n_micro) < 1
    print(f"GRAD-OK S={n_stages} M={n_micro} ticks={ticks} "
          f"bubble={bubble_fraction(n_stages, n_micro):.3f} "
          f"ld={ld:.1e} gd={gd:.1e}")

# divisible and NON-divisible micro counts, 2 and 4 stages
for n_stages, n_micro in ((2, 1), (2, 3), (2, 4), (4, 3), (4, 6)):
    run_case(n_stages, n_micro, mb=4)
print("PIPELINE-GRADS-OK")
"""


def test_gpipe_grads_match_stacked_model(multidevice):
    out = multidevice(SCRIPT_GRADS, 4)
    assert out.count("GRAD-OK") == 5
    assert "PIPELINE-GRADS-OK" in out


# ------------------------------------- 1F1B grads vs stacked, core level
SCRIPT_ONEFB = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.pipeline import onefb_forward, stacked_forward
from repro.parallel.staged import tensor_reduce

KEY = jax.random.PRNGKey(3)

def run_case(n_stages, v, n_micro, layers_per_stage, mb=2):
    L = n_stages * layers_per_stage
    ks = jax.random.split(jax.random.fold_in(KEY, L*31 + v*7 + n_micro), 3)
    W = jax.random.normal(ks[0], (L, 8, 8)) * 0.3
    x = jax.random.normal(ks[1], (n_micro, mb, 8))
    tgt = jax.random.normal(ks[2], (n_micro, mb, 8))

    def stage_fn(sp, xx):
        for j in range(sp["W"].shape[0]):
            xx = jnp.tanh(xx @ sp["W"][j])
        return xx

    # reference: every layer its own "stage" of the stacked forward
    def ref_loss(p):
        y = stacked_forward(stage_fn, {"W": p["W"].reshape(L, 1, 8, 8)}, x)
        return jnp.mean((y - tgt) ** 2)
    l_ref, g_ref = jax.value_and_grad(ref_loss)({"W": W})

    # interleaved layout: device i holds chunks c = 0..v-1 with global
    # virtual stage c*S+i — permute rows device-major, chunk-major (the
    # same layout HybridEngine._permute_stacked applies at init)
    cl = layers_per_stage // v
    perm = np.concatenate([np.arange((c*n_stages + i)*cl,
                                     (c*n_stages + i + 1)*cl)
                           for i in range(n_stages) for c in range(v)])
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("stage",))
    def body(p):
        def loss_fn(pl):
            outs = onefb_forward(stage_fn, pl, x, "stage", interleave=v)
            l = jnp.mean((outs - tgt) ** 2)
            me = jax.lax.axis_index("stage")
            l = jnp.where(me == n_stages - 1, l, 0.0)
            return tensor_reduce("stage")(l)
        return jax.value_and_grad(loss_fn)(p)
    spec = {"W": P("stage")}
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                       out_specs=(P(), spec), check_vma=False)
    l_pipe, g_pipe = jax.jit(fn)({"W": W[perm]})
    g_pipe = np.asarray(g_pipe["W"])[np.argsort(perm)]

    ld = abs(float(l_ref) - float(l_pipe))
    gd = float(np.max(np.abs(np.asarray(g_ref["W"]) - g_pipe)))
    assert ld <= 1e-5, (n_stages, v, n_micro, ld)
    assert gd <= 1e-5, (n_stages, v, n_micro, gd)
    print(f"ONEFB-GRAD-OK S={n_stages} v={v} M={n_micro} "
          f"ld={ld:.1e} gd={gd:.1e}")

# interleaved + plain, divisible and NON-divisible micro counts, and the
# m == s drain boundary (1f1b needs m >= s)
for s, v, m in ((2, 2, 4), (2, 2, 8), (2, 1, 4), (4, 2, 6), (2, 2, 2),
                (2, 2, 3)):
    run_case(s, v, m, layers_per_stage=2)
print("ONEFB-GRADS-OK")
"""


def test_onefb_grads_match_stacked_model(multidevice):
    out = multidevice(SCRIPT_ONEFB, 4)
    assert out.count("ONEFB-GRAD-OK") == 6
    assert "ONEFB-GRADS-OK" in out


# ----------------------- schedules agree on the d2.t2.s2 composed mesh
SCRIPT_STRATEGY_1F1B = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.parallel.staged import make_tiny_transformer
from repro.train.strategy import Strategy, Trainer

params0, model = make_tiny_transformer(4, d_model=8, d_ff=16, seed=0)
rng = np.random.default_rng(0)
X = rng.standard_normal((16, 8)).astype(np.float32)
Y = rng.standard_normal((16, 8)).astype(np.float32)
batches = lambda t, w=0: {"x": jnp.asarray(X), "y": jnp.asarray(Y)}

def run(spec):
    p, hist, _ = Trainer(Strategy.parse(spec, lr=0.05)).fit(
        model, params0, batches, 3)
    return p, [e["loss"] for e in hist]

ref_p, ref_losses = run("bsp/ring/none@1")
for spec in ("bsp/ring/none@8:d2.t2.s2.m8",
             "bsp/ring/none@8:d2.t2.s2.m8.1f1b",
             "bsp/ring/none@8:d2.t2.s2.m8.1f1b.v1"):
    p, losses = run(spec)
    dl = max(abs(a - b) for a, b in zip(losses, ref_losses))
    dp = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(ref_p)))
    assert dl <= 1e-5 and dp <= 1e-5, (spec, dl, dp)
    print(f"MESH-SCHED-OK {spec} dl={dl:.1e} dp={dp:.1e}")
print("STRATEGY-1F1B-OK")
"""


def test_1f1b_matches_gpipe_and_stacked_on_composed_mesh(multidevice):
    out = multidevice(SCRIPT_STRATEGY_1F1B, 8)
    assert out.count("MESH-SCHED-OK") == 3
    assert "STRATEGY-1F1B-OK" in out
