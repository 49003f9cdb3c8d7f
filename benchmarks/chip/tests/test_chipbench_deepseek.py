"""DeepSeek-V2's program against its plain reference at a tiny size on the
CPU: loss, logits and gradients; the held experts' shares adding up to the
uncut layer; a router that sends every token to one held expert; and the
work counts of ``work_mla_moe`` by hand."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import work_mla_moe
from chip.reference import deepseek_v2_lm as ref
from chip.tests import tiny_deepseek as tiny


def _batch(rows=2, S=64, seed=0):
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, (rows, S + 1)), jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_program_matches_the_reference_loss_logits_and_gradients():
    from repro.models import build_model
    c, model = tiny.config(), build_model(tiny.program_config())
    params = ref.init(c, jax.random.PRNGKey(3))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, want)
    b = _batch()
    with jax.default_matmul_precision("highest"):
        got_logits = model.forward(params, b["tokens"],
                                   compute_dtype=jnp.float32)[0]
        exp_logits = ref.forward(c, params, b["tokens"])[0]
        (got, mets), g_got = jax.value_and_grad(
            lambda p: model.loss_fn(p, b, compute_dtype=jnp.float32),
            has_aux=True)(params)
        exp, g_exp = jax.value_and_grad(lambda p: ref.loss(c, p, b))(params)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(exp_logits),
                               rtol=2e-4, atol=2e-4)
    assert float(got) == pytest.approx(float(exp), rel=1e-5)
    assert float(mets["moe_dropped"]) == 0
    assert float(mets["moe_held_load"]) > 0
    for a, e in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_exp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=2e-3,
                                   atol=2e-5 * float(jnp.max(jnp.abs(e))))


def _moe_weights(key, cfg):
    from repro.models.moe import moe_init
    return moe_init(key, dataclasses.replace(cfg, experts_held=()))


def _held(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


def test_held_shares_add_up_to_the_uncut_layer():
    """Each of the four blocks of 4 held experts gives its part; with the
    shared experts counted once they add up to the reference's layer that
    holds all 16."""
    from repro.models.moe import moe_apply
    cfg = tiny.program_config()
    p = _moe_weights(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    parts = [moe_apply(_held(p, 4 * i, 4), x,
                       dataclasses.replace(cfg, experts_held=(4 * i, 4)))
             for i in range(4)]
    assert all(float(s["dropped"]) == 0 for _, s in parts)
    from repro.models.common import mlp_apply
    total = sum(o for o, _ in parts) - 3 * mlp_apply(p["shared"], x,
                                                     "swiglu")
    c = tiny.config(n_routed_experts=16)
    with jax.default_matmul_precision("highest"):
        want, aux = ref._moe(c, p, x, ref.identity)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    assert float(parts[0][1]["aux"]) == pytest.approx(float(aux), rel=1e-5)


def test_every_token_to_one_held_expert_is_dropless():
    """A router biased so that held expert 1 tops every token's choice:
    it gets all T tokens (T / (T K / E) = E / K of the balanced load),
    nothing is dropped, and the result is the reference's."""
    from repro.models.moe import moe_apply
    cfg = tiny.program_config(experts_held=(4, 4))
    p = _moe_weights(jax.random.PRNGKey(7), cfg)
    p["router"] = {"w": p["router"]["w"].at[:, 5].add(40.0)}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2, 64, 64)))
    out, stats = moe_apply(_held(p, 4, 4), x, cfg)
    assert float(stats["dropped"]) == 0
    assert float(stats["held_load"]) == pytest.approx(16 / 3)
    c = tiny.config(expert_share={"first": 4, "of": 16})
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(c, _held(p, 4, 4), x, ref.identity)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_work_counts_by_hand():
    c = tiny.config()
    # attention 64*4*24 + 64*(32+8) + 32*4*(16+16) + 4*16*64 = 16896 a layer
    assert work_mla_moe.mla_params(c) == 16896
    # 3 layers of attention, 1 dense 3*64*128, 2 MoE of router 64*16,
    # shared 2 * 3*64*32 and 3 * 4/16 = 0.75 of an expert, head 64*256
    expert = 3 * 64 * 32
    assert work_mla_moe.token_matmul_params(c) == \
        3 * 16896 + 3 * 64 * 128 + 2 * (64 * 16 + 2.75 * expert) + 64 * 256
    # S = 3: 6 causal pairs; QK over 24 and PV over 16, 4 heads, 3 layers
    pair = 2 * 3 * 4 * (24 + 16)
    assert work_mla_moe.attn_pair_flops(c) == pair
    assert work_mla_moe.train_flops_per_token(c, 3) == \
        6 * work_mla_moe.token_matmul_params(c) + 3 * pair * 6 / 3
    flops, nbytes = work_mla_moe.flash_fwd(c, rows=1, S=3, itemsize=2)
    assert flops == pair * 6
    assert nbytes == 3 * 3 * 4 * (2 * 24 + 2 * 16) * 2
    flops, nbytes = work_mla_moe.experts_train(c, tokens=8, itemsize=2)
    # 8 tokens make 6 assignments to the 4 held of 16 experts
    assert flops == 2 * 6 * 6 * expert
    assert nbytes == 2 * (4 * 4 * expert + 3 * 6 * 2 * 64) * 2
