"""Train cells: ``Strategy.build`` once, ``repro.train.strategy.fit`` on
the host's wall clock, and a check of the first three steps against the
configuration's plain reference.

Set-up builds the engine and drives it, through ``fit`` and the same
batch feed the window uses, over steps 1-3 on rows that all differ; the
first of them compiles.  It reads from the program's parameters, after
steps 1 and 3, each leaf's gradient norm (SGD: ``(p0 - p1) / lr``) and
change norm (``p3 - p0``), with ``p0`` made anew from the seed.  The
window is one more ``fit`` call on the same engine, of as many steps as
fill ``--seconds`` at the warm step time.  After it the program's state
is freed and the reference runs the same three steps in float32 at
"highest" precision, one row at a time.
"""
from __future__ import annotations

import time

import numpy as np

from chip import traffic
from chip.harness import Outcome, Span


def _leaf_norms(a, b):
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def _grad_fn(model, compute_dtype):
    import jax

    def grad_fn(p, batch):
        (loss, _), g = jax.value_and_grad(
            lambda pp: model.loss_fn(pp, batch, compute_dtype=compute_dtype),
            has_aux=True)(p)
        return loss, g
    return grad_fn


class Feed:
    """The ``batches(t, worker)`` callable handed to ``fit``: host rows
    made in set-up, step ``t`` of a call counted from ``offset``; each
    call is a ``train.batches`` span, and inside the window it drives the
    tracer."""

    def __init__(self, rows, workers, per_worker, spans, tracer, clock):
        self.rows, self.K, self.R = rows, workers, per_worker
        self.offset, self.spans, self.tracer = 0, spans, tracer
        self.clock, self.in_window = clock, False

    def __call__(self, t, w=0):
        import jax.numpy as jnp
        if w == 0 and self.in_window:
            self.tracer.poll(time.perf_counter() - self.clock[0])
        with Span(self.spans, "train.batches", t=self.offset + t, w=w):
            i = ((self.offset + t) * self.K + w) * self.R
            return {k: jnp.asarray(v[i:i + self.R])
                    for k, v in self.rows.items()}


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.train import Strategy
    from repro.train.strategy import fit

    c, ref, cp = ctx.config, ctx.reference, ctx.cell_params
    strategy = Strategy.parse(cp["strategy"], backend="device", lr=cp["lr"])
    K, R, S = strategy.workers, cp["rows_per_worker"], ctx.mix["seq_len"]
    tokens_per_step = K * R * S
    # rows for the three checked steps, the sizing steps and a window of
    # up to max_steps, all different
    n_steps = 3 + 2 + cp["max_steps"]
    rows = traffic.train_rows(ctx.mix, n_steps * K * R, ctx.seed,
                              c["vocab_size"])
    spans = []
    clock = [time.perf_counter()]
    feed = Feed(rows, K, R, spans, ctx.tracer, clock)
    compute = jnp.dtype(c["compute_dtype"])
    engine = strategy.build(_grad_fn(ctx.model, compute))

    p = ref.init(c, ctx.key, c["dtype"])
    ctx.check_layout(p)
    p0_norm = jax.jit(lambda p1: _leaf_norms(p1, ref.init(c, ctx.key,
                                                          c["dtype"])))
    losses, t_steps = [], []
    for t in range(5):
        feed.offset = t
        t0 = time.perf_counter()
        p, hist, mets = fit(engine, p, feed, 1)
        losses.append(hist[-1]["loss"])
        t_steps.append(time.perf_counter() - t0)
        if t == 0:
            g_norms = [float(x) / cp["lr"] for x in p0_norm(p)]
        if t == 2:
            d_norms = [float(x) for x in p0_norm(p)]
    step_s = float(np.median(t_steps[3:]))
    n_win = int(min(cp["max_steps"], max(1, round(ctx.seconds / step_s))))
    ctx.note(f"warm steps {t_steps!r} s; window of {n_win} steps of "
             f"{tokens_per_step} tokens")
    feed.offset = 5
    ctx.setup_done()
    with ctx.window():
        clock[0], feed.in_window = time.perf_counter(), True
        with Span(spans, "train.fit", steps=n_win):
            p, hist, mets = fit(engine, p, feed, n_win)
            jax.block_until_ready(p)
        wall = time.perf_counter() - clock[0]
    ctx.tracer.poll(float("inf"))
    memory_peak = ctx.memory_peak()
    ctx.note(f"window {wall!r} s; fit metrics {mets}")
    del p, engine, p0_norm
    ctx.free_device()

    e2e = {"train_tokens_per_s": n_win * tokens_per_step / wall}
    checks = _check(ctx, rows, K * R, losses[:3], g_norms, d_norms)
    return Outcome(e2e=e2e, attempted=n_win, failed=0,
                   memory_peak=memory_peak, checks=checks, spans=spans,
                   extra={"steps": n_win, "tokens_per_step": tokens_per_step,
                          "wall_s": wall})


def _ref_steps(ctx, rows, per_step, q):
    """Three SGD steps of the reference, one row at a time, returning the
    per-step losses, the first step's leaf gradient norms and the leaf
    change norms after three steps."""
    import jax
    import jax.numpy as jnp
    c, ref, lr = ctx.config, ctx.reference, ctx.cell_params["lr"]
    init32 = lambda: jax.tree.map(lambda a: a.astype(jnp.float32),
                                  ref.init(c, ctx.key, c["dtype"]))
    vg = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(c, p, b, q)))
    upd = jax.jit(lambda p, g, n: jax.tree.map(lambda a, b: a - lr * b / n,
                                               p, g))
    p, losses, g1 = init32(), [], None
    with jax.default_matmul_precision("highest"):
        for t in range(3):
            g_sum, l_sum = None, 0.0
            for i in range(t * per_step, (t + 1) * per_step):
                b = {k: jnp.asarray(v[i:i + 1]) for k, v in rows.items()}
                loss, g = vg(p, b)
                l_sum += float(loss)
                g_sum = g if g_sum is None else jax.tree.map(jnp.add,
                                                             g_sum, g)
                del g
            losses.append(l_sum / per_step)
            if t == 0:
                g1 = [float(jnp.sqrt(jnp.sum(jnp.square(x)))) / per_step
                      for x in jax.tree.leaves(g_sum)]
            p = upd(p, g_sum, per_step)
            del g_sum
        # p0 is made anew rather than kept, for the memory it would hold
        d = [float(x) for x in jax.jit(lambda pp: _leaf_norms(
            pp, init32()))(p)]
    return losses, g1, d


def leaf_gap(prog, refn, skip):
    """Worst leaf's |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's."""
    med = float(np.median(refn))
    return max(abs(a - b) / max(b, med)
               for i, (a, b) in enumerate(zip(prog, refn)) if i not in skip)


def _check(ctx, rows, per_step, losses, g_norms, d_norms):
    t0 = time.perf_counter()
    q = ctx.lower_precision() if ctx.control else ctx.reference.identity
    r_loss, r_g, r_d = _ref_steps(ctx, rows, per_step, q)
    if ctx.control:
        # the control stands in the program's place against the reference
        losses, g_norms, d_norms = r_loss, r_g, r_d
        r_loss, r_g, r_d = _ref_steps(ctx, rows, per_step,
                                      ctx.reference.identity)
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone; they are left out by this rule, not by name
    med = float(np.median(r_g))
    skip = {i for i, g in enumerate(r_g) if g < 1e-3 * med}
    ctx.note(f"reference 3 steps {time.perf_counter() - t0:.1f} s; losses "
             f"program {losses!r} reference {r_loss!r}; leaves left out "
             f"{sorted(skip)}")
    lim = ctx.cell_params["limits"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_loss))
    g_gap = leaf_gap(g_norms, r_g, skip)
    d_gap = leaf_gap(d_norms, r_d, skip)
    return [("loss_rel_gap", loss_gap, lim["loss_rel_gap"],
             loss_gap <= lim["loss_rel_gap"]),
            ("grad_norm_gap", g_gap, lim["grad_norm_gap"],
             g_gap <= lim["grad_norm_gap"]),
            ("update_norm_gap", d_gap, lim["update_norm_gap"],
             d_gap <= lim["update_norm_gap"])]
