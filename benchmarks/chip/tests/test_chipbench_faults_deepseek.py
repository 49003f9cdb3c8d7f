"""The DeepSeek-V2 cell's correctness check, driven through the rest of a
run at a size the CPU holds (``tiny_deepseek``): it passes the program as
it is, and fails, by the cell's own limits, the lower-precision control,
the program with its block broken in each of three ways, and the program
that leaves half of each batch out."""
import jax.numpy as jnp

from chip.tests import tiny_deepseek as tiny


def _run():
    return tiny.run(tiny.context())


def test_program_is_correct_and_control_is_not():
    res = _run()
    assert res["correct"], res["checks"]
    ctl = tiny.run(tiny.context(control=True))
    assert not ctl["correct"], ctl["checks"]


def test_latent_norm_left_out(monkeypatch):
    import repro.models.mla as mla
    real = mla.norm_apply
    monkeypatch.setattr(mla, "norm_apply", lambda kind, p, x, eps: x
                        if p is not None and x.shape[-1] == 32
                        else real(kind, p, x, eps))
    res = _run()
    assert not res["correct"], res["checks"]


def test_topk_gates_renormalised(monkeypatch):
    import repro.models.moe as moe
    real = moe.route

    def renorm(router_w, x, cfg):
        gate, ids, aux = real(router_w, x, cfg)
        return gate / gate.sum(-1, keepdims=True), ids, aux
    monkeypatch.setattr(moe, "route", renorm)
    res = _run()
    assert not res["correct"], res["checks"]


def test_one_held_experts_output_dropped(monkeypatch):
    import repro.models.moe as moe
    real = moe._expert_ffn

    def drop_first(xt, src, sizes, *w):
        y = real(xt, src, sizes, *w)
        return jnp.where((jnp.arange(y.shape[0]) < sizes[0])[:, None], 0, y)
    monkeypatch.setattr(moe, "_expert_ffn", drop_first)
    res = _run()
    assert not res["correct"], res["checks"]


def test_half_batch_left_out(monkeypatch):
    import repro.models.transformer as T
    real = T.loss_fn

    def half(params, cfg, batch, *a, **kw):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:n] for k, v in batch.items()},
                    *a, **kw)
    monkeypatch.setattr(T, "loss_fn", half)
    res = _run()
    assert not res["correct"], res["checks"]
