"""Nearest-rank p95 of time to first token over every request of the
traced run, from its scheduled send time: in a cell offered more than it
sustains, the queue's growth, which swings too widely to bound."""
from chip import stats


def read(run):
    ttft = run.outcome.extra.get("ttft_s")
    return 1e3 * stats.percentile(ttft, 95) if ttft else None
