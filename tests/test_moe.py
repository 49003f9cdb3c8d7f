"""MoE dispatch invariants (the §Perf pair-3 code path)."""
import dataclasses

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
