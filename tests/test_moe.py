"""MoE dispatch invariants (the §Perf pair-3 code path)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models.moe import _capacity, moe_apply, moe_init


def _cfg(E=4, K=2, cap=8.0):
    return dataclasses.replace(
        get_config("kimi-k2-1t-a32b").reduced(),
        num_experts=E, experts_per_token=K, capacity_factor=cap,
        num_shared_experts=0, d_model=32, moe_d_ff=16)


def test_no_drop_equals_dense_computation():
    """With capacity >= all assignments, MoE output must equal the explicit
    per-token sum over its top-k experts."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, cfg.d_model))
    out, stats = moe_apply(p, x, cfg)

    # reference: dense evaluation of every expert for every token
    xt = x.reshape(-1, cfg.d_model)
    logits = xt @ p["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    gate, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    gate = gate / gate.sum(-1, keepdims=True)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", xt, p["w_up"])
    all_e = jnp.einsum("tef,efd->ted", h, p["w_down"])   # [T, E, d]
    ref = jnp.einsum("tkd,tk->td",
                     jnp.take_along_axis(all_e, ids[..., None], axis=1),
                     gate)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               np.asarray(ref), atol=1e-4)
    assert float(stats["aux"]) >= 0
    assert float(stats["dropped"]) == 0


def test_capacity_drops_are_bounded():
    """With capacity 1.0, each expert processes at most C tokens and the
    output stays finite (dropped tokens contribute zero, not NaN)."""
    cfg = _cfg(cap=1.0)
    key = jax.random.PRNGKey(2)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 3), (4, 8, cfg.d_model))
    out, _ = moe_apply(p, x, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 64), st.integers(1, 4), st.integers(2, 16))
def test_capacity_formula(T, K, E):
    C = _capacity(T, K, E, 1.0)
    assert C >= 1
    assert C * E >= T * K                 # no-overflow bound at factor 1.0


def test_aux_loss_penalizes_imbalance():
    """Router collapse (all tokens -> one expert) must cost more aux loss
    than a uniform router."""
    cfg = _cfg(E=4, K=1)
    key = jax.random.PRNGKey(4)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 5), (2, 16, cfg.d_model))
    # uniform router
    p_uniform = dict(p)
    p_uniform["router"] = {"w": jnp.zeros_like(p["router"]["w"])}
    aux_uniform = moe_apply(p_uniform, x, cfg)[1]["aux"]
    # collapsed router: huge bias toward expert 0
    w = jnp.zeros_like(p["router"]["w"]).at[:, 0].set(100.0)
    p_collapsed = dict(p)
    p_collapsed["router"] = {"w": w}
    aux_collapsed = moe_apply(p_collapsed, x, cfg)[1]["aux"]
    assert float(aux_collapsed) > float(aux_uniform)


def test_moe_grads_flow_to_experts_and_router():
    cfg = _cfg()
    key = jax.random.PRNGKey(6)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 7), (2, 6, cfg.d_model))

    def loss(pp):
        out, stats = moe_apply(pp, x, cfg)
        return jnp.sum(out ** 2) + stats["aux"]

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["w_gate"]).sum()) > 0
    assert float(jnp.abs(g["w_down"]).sum()) > 0
    assert float(jnp.abs(g["router"]["w"]).sum()) > 0


# ---- held-expert dispatch: gradients against a masked-dense formulation

def _held_cfg(K, n, E=16, first=4):
    return dataclasses.replace(_cfg(E=E, K=K), experts_held=(first, n))


def _masked_dense(p, x, cfg):
    """Every held expert on every token, masked by the token's gate."""
    from repro.models.moe import route
    first, n = cfg.experts_held
    xt = x.reshape(-1, cfg.d_model)
    gate, ids, _ = route(p["router"]["w"], x, cfg)
    held = jnp.arange(first, first + n)
    g = jnp.sum(jnp.where(ids[..., None] == held, gate[..., None], 0.0), 1)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", xt, p["w_up"])
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    return jnp.einsum("te,ted->td", g, y).reshape(x.shape)


def _nan_past(a, sizes):
    return jnp.where((jnp.arange(len(a)) < jnp.sum(sizes))[:, None], a,
                     jnp.nan)


@jax.custom_vjp
def _poisoned_grouped(lhs, rhs, sizes):
    """``ragged_dot`` with NaN in every row past ``sum(sizes)``, in the
    output and in the rows' cotangent, as the grouped matmul leaves them
    undefined; the weights' cotangent reads the grouped rows alone."""
    return _nan_past(jax.lax.ragged_dot(lhs, rhs, sizes), sizes)


def _poisoned_fwd(lhs, rhs, sizes):
    return _poisoned_grouped(lhs, rhs, sizes), (lhs, rhs, sizes)


def _poisoned_bwd(res, g):
    lhs, rhs, sizes = res
    live = (jnp.arange(len(lhs)) < jnp.sum(sizes))[:, None]
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                     jnp.where(live, lhs, 0.0), rhs)
    dlhs, drhs = vjp(jnp.where(live, g, 0.0))
    return _nan_past(dlhs, sizes), drhs, None


_poisoned_grouped.defvjp(_poisoned_fwd, _poisoned_bwd)

HELD_CASES = {          # (K, n, router skew)
    "n>K": (2, 4, None),
    "n<K": (3, 2, None),
    "n=K": (3, 3, None),
    "one-expert": (3, 4, "one"),    # every token to held expert 1
    "full-buffer": (3, 4, "full"),  # every choice a held expert
}


@pytest.mark.parametrize("poison", [False, True], ids=["plain", "poison"])
@pytest.mark.parametrize("case", list(HELD_CASES))
def test_held_experts_match_masked_dense_with_gradients(case, poison,
                                                        monkeypatch, request):
    """The held path's output and its gradients with respect to x, the
    router and the three expert weights equal autodiff of the masked-dense
    formulation; with NaN in the grouped matmuls' undefined rows
    (``poison``) nothing changes and everything stays finite."""
    import repro.models.moe as moe
    if poison:
        monkeypatch.setattr(moe, "_grouped", _poisoned_grouped)
        # the checkpointed FFN's trace is cached by function: drop it
        # before and after, so each case traces the ``_grouped`` it sets
        jax.clear_caches()
        request.addfinalizer(jax.clear_caches)
    K, n, skew = HELD_CASES[case]
    cfg = _held_cfg(K, n)
    first = cfg.experts_held[0]
    key = jax.random.PRNGKey(11)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, cfg.d_model))
    if skew:
        # logits ~10 above the rest: the choices are the held experts',
        # with softmax short of saturation so the router's gradient lives
        x = jnp.abs(x)
        w = p["router"]["w"]
        p["router"] = {"w": w.at[:, first + 1].add(0.4) if skew == "one"
                       else w.at[:, first:first + n].add(0.4)}
    ct = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

    def grads(f):
        def loss(pp, xx):
            out, stats = f(pp, xx)
            return jnp.sum(out * ct), (out, stats)
        return jax.jit(jax.grad(loss, (0, 1), has_aux=True))(p, x)
    with jax.default_matmul_precision("highest"):
        got_g, (out, stats) = grads(lambda pp, xx: moe_apply(pp, xx, cfg))
        want_g, (want, _) = grads(
            lambda pp, xx: (_masked_dense(pp, xx, cfg), {}))
    assert float(stats["dropped"]) == 0
    if skew == "full":
        ids = moe.route(p["router"]["w"], x, cfg)[1]
        assert bool(jnp.all((ids >= first) & (ids < first + n)))
    if skew == "one":
        assert float(stats["held_load"]) == pytest.approx(16 / 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    leaves = {"x": (got_g[1], want_g[1])}
    for k in ("w_gate", "w_up", "w_down"):
        leaves[k] = (got_g[0][k], want_g[0][k])
    leaves["router"] = (got_g[0]["router"]["w"], want_g[0]["router"]["w"])
    for name, (a, e) in leaves.items():
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(e))) > 0, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(e))), err_msg=name)


@pytest.mark.parametrize("K,n", [(2, 4), (3, 2)], ids=["n>K", "n<K"])
def test_held_experts_gradient_has_no_scatter_add(K, n):
    """Dispatch and combine are a permutation and its inverse, so the
    gradient of the held path, gate and ids given, gathers and never
    scatter-adds."""
    from repro.models.moe import _held_experts
    cfg = _held_cfg(K, n)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    T = 48
    xt = jnp.zeros((T, cfg.d_model))
    gate = jnp.zeros((T, K))
    ids = jnp.zeros((T, K), jnp.int32)

    def loss(p, xt, gate):
        return jnp.sum(_held_experts(p, xt, gate, ids, cfg)[0])
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(p, xt, gate))
    assert "gather" in jaxpr
    assert "scatter-add" not in jaxpr
