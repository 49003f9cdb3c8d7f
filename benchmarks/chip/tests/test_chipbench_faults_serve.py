"""The correctness check, driven through the rest of a run at a size the
CPU holds: it passes the program as it is, and fails it with the timed
path broken underneath in each way the cell can break, and fails the
lower-precision control."""
from chip.tests import tiny


def checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_serve_program_is_correct_and_control_is_not():
    res = tiny.run(tiny.context(tiny.SERVE))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    ctl = tiny.run(tiny.context(tiny.SERVE, control=True))
    c = checks(ctl)
    assert not ctl["correct"]
    assert c["control_logit_gap"] >= 3 * c["max_logit_gap"]


def test_serve_token_altered_where_produced(monkeypatch):
    import repro.serve.engine as E
    real = E.sample_tokens

    def off_by_one(logits, vocab, *a, **kw):
        return (real(logits, vocab, *a, **kw) + 1) % vocab
    monkeypatch.setattr(E, "sample_tokens", off_by_one)
    res = tiny.run(tiny.context(tiny.SERVE))
    assert not res["correct"]
    assert checks(res)["max_logit_gap"] > 0.2
