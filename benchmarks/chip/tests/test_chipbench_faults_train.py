"""The correctness check, driven through the rest of a run at a size the
CPU holds: it passes the program as it is, and fails it with the timed
path broken underneath in each way the cell can break, and fails the
lower-precision control."""
import jax
import jax.numpy as jnp
import pytest

from chip.tests import tiny


def checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_train_program_is_correct_and_control_is_not():
    res = tiny.run(tiny.context(tiny.TRAIN))
    assert res["correct"], res["checks"]
    ctl = tiny.run(tiny.context(tiny.TRAIN, control=True))
    assert not ctl["correct"], ctl["checks"]


def test_train_step_returns_state_unchanged(monkeypatch):
    from repro.comm.plan import CommPlan
    monkeypatch.setattr(CommPlan, "reduce_grads", lambda self, g: jax.tree.map(
        jnp.zeros_like, g))
    res = tiny.run(tiny.context(tiny.TRAIN))
    assert not res["correct"]
    c = checks(res)
    assert c["grad_norm_gap"] == pytest.approx(1.0)
    assert c["update_norm_gap"] == pytest.approx(1.0)


def test_train_half_batch_left_out(monkeypatch):
    import repro.models.transformer as T
    real = T.loss_fn

    def half(params, cfg, batch, *a, **kw):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:n] for k, v in batch.items()},
                    *a, **kw)
    monkeypatch.setattr(T, "loss_fn", half)
    res = tiny.run(tiny.context(tiny.TRAIN))
    assert not res["correct"], res["checks"]
