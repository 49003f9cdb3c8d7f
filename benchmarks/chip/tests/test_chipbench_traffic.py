"""Traffic generation and the statistics the end-to-end metrics use."""
import numpy as np
import pytest

from chip import stats, traffic

MIX = {"shape_seed": 7, "arrivals": {"kind": "poisson"},
       "prompt": {"median": 320, "sigma": 0.8, "min": 64, "max": 1024,
                  "buckets": [128, 192, 256, 320, 384, 512, 768, 1024]},
       "output": {"median": 48, "sigma": 0.7, "min": 8, "max": 256}}


def test_same_seed_same_schedule_other_seed_other_order():
    a = traffic.serve_schedule(MIX, 5.0, 40, 2**33 + 5, 1000)
    b = traffic.serve_schedule(MIX, 5.0, 40, 2**33 + 5, 1000)
    c = traffic.serve_schedule(MIX, 5.0, 40, 2**33 + 6, 1000)
    assert a.n == b.n == c.n == 200
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.prompt_len, c.prompt_len)
    assert not np.array_equal(a.due_s, c.due_s)
    # every seed offers the same work, in another order
    assert sorted(a.prompt_len) == sorted(c.prompt_len)
    assert sorted(a.output_len) == sorted(c.output_len)
    assert sorted(np.diff(a.due_s).round(9)) != [] and \
        np.isclose(sorted(np.diff(np.append(a.due_s, 40))),
                   sorted(np.diff(np.append(c.due_s, 40)))).all()


def test_schedule_fills_the_window_and_snaps_to_buckets():
    s = traffic.serve_schedule(MIX, 5.0, 40, 1, 1000)
    assert s.due_s[0] == 0 and s.due_s[-1] < 40
    assert np.all(np.diff(s.due_s) >= 0)
    assert set(s.prompt_len) <= set(MIX["prompt"]["buckets"])
    assert s.output_len.min() >= 8 and s.output_len.max() <= 256
    assert [len(p) for p in s.prompts] == list(s.prompt_len)


def test_gamma_arrivals_are_burstier():
    g = dict(MIX, arrivals={"kind": "gamma", "cv": 3.0})
    gaps = np.diff(traffic.serve_schedule(g, 50.0, 40, 1, 10).due_s)
    assert np.std(gaps) / np.mean(gaps) > 2.0


def test_percentile_is_over_every_value():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile(xs + [1000], 95) == 96
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_uses_statistics_quartiles():
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx((4.5 - 1.5) / 3)


def _loop_rule(reset, draws, vocab):
    out = np.empty_like(draws)
    for r in range(draws.shape[0]):
        cur = draws[r, 0]
        for t in range(draws.shape[1]):
            cur = draws[r, t] if reset[r, t] else (3 * cur + 7) % vocab
            out[r, t] = cur
    return out


def test_markov_rows_follow_the_pipeline_rule():
    """The vectorised rows equal a position-by-position loop of
    ``repro.data.pipeline``'s rule over the same resets and draws."""
    V, n, L = 100352, 3, 300
    got = traffic.markov_rows(np.random.default_rng(4), n, L, V)
    rng = np.random.default_rng(4)
    reset = rng.random((n, L)) < 0.1
    reset[:, 0] = True
    draws = rng.integers(0, V, size=(n, L))
    np.testing.assert_array_equal(got, _loop_rule(reset, draws, V))


def test_train_rows_shift_labels_by_one():
    rows = traffic.train_rows({"seq_len": 16}, 4, 9, 1000)
    assert rows["tokens"].shape == rows["labels"].shape == (4, 16)
    np.testing.assert_array_equal(rows["tokens"][:, 1:],
                                  rows["labels"][:, :-1])
