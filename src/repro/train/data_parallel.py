"""Device-sharded data parallelism with compressed, bucketed,
topology-explicit communication (survey §3.3).

``SimSyncEngine`` (core/sync.py) *simulates* K workers on one device; this
module is the executable counterpart: N real (virtual-host) devices under
``shard_map``.  ``DeviceEngine`` executes the full synchronization ×
architecture cross-product of the survey's Table 1:

  sync=bsp        every step: per-worker gradients on the worker's batch
                  shard, compressed with per-worker error-feedback state,
                  exchanged bucket-by-bucket in ``CommPlan`` issue order —
                  one plan shared by the executed schedule and the
                  analytic timeline, so they cannot drift apart.
  sync=ssp | asp  the *simulator's own deterministic staleness schedule*
                  replayed on devices: each tick, every worker computes its
                  gradient against its stale pulled parameters in parallel
                  under shard_map; the host then applies the tick's firing
                  events in the simulator's event order (worker w fires
                  every periods[w] ticks; SSP blocks a worker more than
                  ``staleness`` clocks ahead).  Losses cross-validate
                  against ``SimSyncEngine`` on identical batch streams.
  sync=sma        CROSSBOW synchronous model averaging: per-worker
                  replicas live sharded, the center is a ``CommPlan``
                  exchange of the replicas themselves, and each replica
                  is pulled toward it (cross-validated vs the simulator).
  arch=allreduce  decentralized: bucketed topology-explicit exchange
                  (``repro.comm``), update replicated.
  arch=ps         centralized: the ZeRO-style reduce-scatter / shard-update
                  / all-gather path of ``core.parameter_server`` — each
                  worker plays parameter server for its 1/n shard.  Under
                  BSP it runs over the *same* fused-bucket plan and issue
                  order as allreduce; under SSP/ASP each firing worker's
                  push is a per-event reduce-scatter (no bucketing — one
                  gradient per event).

Wire accounting follows the config's ``wire`` mode (docs/comm.md):

  wire=modeled    compression is a per-worker ``roundtrip`` before a
                  full-precision exchange, and bytes are the compressor's
                  analytic accounting — identical to the simulator's, so
                  the two backends stay cross-validatable.
  wire=measured   the ``CommPlan`` schedule itself carries the encoded
                  segment payloads (encode → ppermute the planes →
                  decode-accumulate, per-worker EF inside the schedule)
                  and bytes are counted from those planes — recomputed
                  per bucket per step, so dgc's moving threshold shows up
                  in the accounting instead of a cached step-0 value.

``bsp/*/none`` is bit-identical under both modes (nothing to encode).
The modeled iteration timeline comes from the very bucket list executed
on device (``CommPlan.modeled_timeline``).

``DataParallelEngine`` is the deprecated PR-1 alias (BSP/allreduce only by
contract, though it accepts the extended config); construct engines via
``repro.train.Strategy(...).build(grad_fn)`` instead.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm.codecs import SPARSE_ELEM_BYTES
from repro.comm.plan import CommPlan, plan_buckets, scatter_flat
from repro.core.comm_scheduler import LayerCost, LinkModel
from repro.core.compression import Compressor, EF_METHODS
from repro.core.parameter_server import make_ps_step, sgd_update_fn
from repro.core.sync import (ElasticWorkerSet, default_periods,
                             firing_schedule, warn_deprecated)
from repro.elastic.backup import participation_weights
from repro.obs.trace import get_recorder, span

AXIS = "workers"

DEVICE_SYNCS = ("bsp", "ssp", "asp", "sma")   # device-executable sync models
ARCHS = ("allreduce", "ps")            # §3.3.1 architectures
WIRE_MODES = ("modeled", "measured")   # wire-byte accounting (docs/comm.md)

# the shared plan keyword set every engine forwards to CommPlan.plan
_plan_buckets = plan_buckets           # back-compat alias (pre-refactor name)
_scatter_flat = scatter_flat           # back-compat alias


@dataclasses.dataclass(frozen=True)
class DataParallelConfig:
    num_workers: int = 8
    lr: float = 0.1
    sync: str = "bsp"                # bsp | ssp | asp | sma
    arch: str = "allreduce"          # allreduce | ps
    staleness: int = 3               # SSP bound s
    # deterministic worker speeds: worker i finishes every periods[i] ticks
    periods: Optional[Tuple[int, ...]] = None
    topology: str = "ring"           # key into TOPOLOGIES
    compressor: Compressor = Compressor("none")
    backup: int = 0                  # BSP backup workers: drop the k slowest
    # measured straggler detection: per-worker step-time EMA replaces the
    # scheduled ranking in the backup drop set (elastic/detector.py)
    detect: bool = False
    bucket_mb: float = 4.0           # gradient bucket fusion size
    order: str = "tictac"            # "tictac" | "random" | "layer"
    link: LinkModel = LinkModel()
    # modeled backward-compute seconds per gradient byte (timeline model)
    back_s_per_byte: float = 2e-12
    wire: str = "modeled"            # modeled | measured (docs/comm.md)
    sma_mu: float = 0.1              # SMA correction strength
    seed: int = 0


def make_bucketed_allreduce(params_example, topology: str = "ring",
                            bucket_mb: float = 4.0, order: str = "tictac",
                            back_s_per_byte: float = 2e-12,
                            seed: int = 0, axis: str = AXIS):
    """Standalone grads->grads mean-allreduce for use inside ``shard_map``
    (e.g. as ``make_train_step(..., reduce_fn=...)``): leaves fused into
    ~bucket_mb buckets (backward order), issued in the chosen transfer
    order, each reduced with the topology-explicit schedule.  Thin
    wrapper over ``CommPlan`` (exact full-precision path)."""
    plan = CommPlan.plan(params_example, axis=axis, n=1, topology=topology,
                         bucket_mb=bucket_mb, order=order,
                         back_s_per_byte=back_s_per_byte, seed=seed)

    def reduce_grads(grads):
        return plan.reduce_grads(grads)

    reduce_grads.fused_layers = plan.fused
    reduce_grads.order = plan.order
    reduce_grads.plan = plan
    return reduce_grads


def make_bucketed_ps_update(params_example, lr: float,
                            bucket_mb: float = 4.0, order: str = "tictac",
                            back_s_per_byte: float = 2e-12,
                            seed: int = 0, axis: str = AXIS):
    """Centralized (params, grads) -> new params for use inside
    ``shard_map``: the same fused-bucket plan and issue order as
    ``make_bucketed_allreduce``, but each bucket takes the parameter-server
    path of ``core.parameter_server`` — reduce-scatter the bucket's summed
    gradient, SGD-update only my 1/n shard (the "server" work, ZeRO-style),
    and all-gather the updated shard back.  Traffic per device equals the
    ring allreduce; update FLOPs drop by n."""
    buckets, order_idx, fused = plan_buckets(
        params_example, bucket_mb, order, back_s_per_byte, seed)
    treedef = jax.tree.structure(params_example)
    leaf_shapes = [(tuple(x.shape), x.dtype)
                   for x in jax.tree.leaves(params_example)]

    def ps_update(params, grads):
        n = jax.lax.axis_size(axis)
        p_leaves = jax.tree.leaves(params)
        g_leaves = jax.tree.leaves(grads)
        # lists, NOT dicts: jax flattens dict keys in sorted order, which
        # would silently retrace the collectives in lexicographic bucket
        # order; list position preserves the planned issue order
        pb = [jnp.concatenate([p_leaves[i].astype(jnp.float32).reshape(-1)
                               for i in buckets[b]]) for b in order_idx]
        gb = [jnp.concatenate([g_leaves[i].astype(jnp.float32).reshape(-1)
                               for i in buckets[b]]) for b in order_idx]
        step = make_ps_step(sgd_update_fn(lr, mean_over=n), axis)
        new_pb, _ = step(pb, gb, None)
        out: List[Any] = [None] * len(p_leaves)
        for flat, b in zip(new_pb, order_idx):
            scatter_flat(flat, buckets[b], leaf_shapes, out)
        return jax.tree.unflatten(treedef, out)

    ps_update.fused_layers = fused
    ps_update.order = order_idx
    return ps_update


def make_sharded_train_step(train_step: Callable, mesh: Mesh,
                            compressed: bool):
    """Lift a ``make_train_step`` step (whose ``reduce_fn`` already
    all-reduces over ``AXIS``) into a jitted shard_map over the worker
    axis: batch is sharded, EF state (when compressing) stays per-worker,
    params/optimizer state are replicated, metrics come back worker-meaned.

    The returned function has the ``train_loop`` contract
    ``step(state, stacked_batch, rng) -> (state, metrics)`` — pass
    ``jit=False`` to ``train_loop`` since it is already compiled."""

    def body(state, batch, rng):
        batch = jax.tree.map(lambda x: x[0], batch)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(AXIS))
        if compressed:
            state = dict(state,
                         ef=jax.tree.map(lambda x: x[0], state["ef"]))
        new_state, mets = train_step(state, batch, rng)
        if compressed:
            new_state = dict(
                new_state,
                ef=jax.tree.map(lambda x: x[None], new_state["ef"]))
        mets = {k: jax.lax.pmean(jnp.asarray(v, jnp.float32), AXIS)
                for k, v in mets.items()}
        return new_state, mets

    ef_spec = P(AXIS) if compressed else P()
    state_spec = {"params": P(), "opt_state": P(), "step": P(),
                  "ef": ef_spec}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(state_spec, P(AXIS), P()),
                       out_specs=(state_spec, P()),
                       check_vma=False)
    return jax.jit(fn)


def async_replay_step(st, batches, t, bound: Optional[int], *, K: int,
                      compressor: Compressor, grad_fn: Callable,
                      apply_fn: Callable, ps_apply: Optional[Callable],
                      lr: float, event_wire: int,
                      eff_periods: Tuple[int, ...]):
    """Replay the simulator's deterministic tick schedule on devices —
    shared by ``DeviceEngine`` (flat worker axis) and ``HybridEngine``
    (the data axis of a mesh).  Gradient compute for the whole worker set
    runs data-parallel via ``grad_fn(pulled_stack, ef, batch, keys,
    fire)``; the tick's firing events then apply in the simulator's
    worker order (each pushing through the configured architecture)."""
    events = []
    while st["updates"] - st["updates_base"] < \
            (t + 1 - st["step_base"]) * K:
        st["tick"] += 1
        # the same deterministic schedule the simulator executes
        firing = firing_schedule(st["tick"], eff_periods,
                                 st["batch_idx"], bound)
        if not firing:
            continue
        fire = np.zeros((K,), np.float32)
        fire[firing] = 1.0
        # a worker's batch index only advances at its own events, so
        # its batch is cached until it fires (invalidated below)
        for w in range(K):
            if st["batch_cache"][w] is None:
                st["batch_cache"][w] = batches(st["batch_idx"][w], w)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *st["batch_cache"])
        # mirror the simulator's rng stream: one split per firing event
        keys = [jax.random.PRNGKey(0)] * K
        if compressor.method != "none":
            for w in firing:
                st["rng"], sub = jax.random.split(st["rng"])
                keys[w] = sub
        pulled_stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *st["pulled"])
        losses, grads, st["ef"] = grad_fn(
            pulled_stack, st["ef"], batch, jnp.stack(keys),
            jnp.asarray(fire))
        for w in firing:
            staleness = st["server_ver"] - st["pulled_ver"][w]
            if ps_apply is not None:
                onehot = np.zeros((K,), np.float32)
                onehot[w] = 1.0
                st["params"] = ps_apply(st["params"], grads,
                                        jnp.asarray(onehot))
            else:
                g_w = jax.tree.map(lambda x: x[w], grads)
                st["params"] = apply_fn(st["params"], g_w, lr)
            st["server_ver"] += 1
            st["updates"] += 1
            st["pulled"][w] = st["params"]   # pull = reference rebind
            st["pulled_ver"][w] = st["server_ver"]
            st["batch_idx"][w] += 1
            st["batch_cache"][w] = None
            st["wire"] += event_wire
            events.append(dict(step=st["updates"],
                               loss=float(losses[w]),
                               max_staleness=staleness, worker=w))
    return st, events


class DeviceEngine(ElasticWorkerSet):
    """Executable {bsp,ssp,asp,sma} × {allreduce,ps} over N host devices;
    drop-in comparable with ``SimSyncEngine``: ``init / step / finalize``
    plus a composed ``run`` with the same signature and the same
    ``(params, history, wire_bytes)`` triple."""

    def __init__(self, cfg: DataParallelConfig, grad_fn: Callable,
                 devices: Optional[Sequence] = None):
        if cfg.sync not in DEVICE_SYNCS:
            raise ValueError(
                f"sync={cfg.sync!r} is not device-executable "
                f"(supported: {DEVICE_SYNCS})")
        if cfg.arch not in ARCHS:
            raise ValueError(f"arch={cfg.arch!r} (supported: {ARCHS})")
        if cfg.wire not in WIRE_MODES:
            raise ValueError(f"wire={cfg.wire!r} (supported: {WIRE_MODES})")
        if cfg.sync == "sma":
            if cfg.compressor.method != "none":
                raise ValueError("sma exchanges replicas, not gradients — "
                                 "it has no compression path")
            if cfg.arch != "allreduce":
                raise ValueError("sma is a decentralized exchange; use "
                                 "arch='allreduce'")
        if cfg.backup and cfg.sync != "bsp":
            raise ValueError("backup workers compose with bsp only "
                             "(async modes have no round to drop from)")
        if cfg.backup >= cfg.num_workers:
            raise ValueError("backup k must leave at least one worker")
        self.cfg = cfg
        self.grad_fn = grad_fn
        self._devs = list(devices or jax.devices())
        if len(self._devs) < cfg.num_workers:
            raise ValueError(
                f"need {cfg.num_workers} devices, have {len(self._devs)} "
                "(run under XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        self.mesh = Mesh(np.array(self._devs[:cfg.num_workers]), (AXIS,))
        self.periods = cfg.periods or default_periods(cfg.num_workers)
        assert len(self.periods) == cfg.num_workers
        self.slowdowns: List[float] = [1.0] * cfg.num_workers
        self._dropped = 0
        self._init_detector(cfg.detect, cfg.num_workers)
        self._step_fn = None
        self._sma_fn = None
        self._plan: Optional[CommPlan] = None
        self._event_wire_cache: Optional[int] = None
        self._async_fns = None
        self._wire_total = 0
        # same replicated apply as the simulator uses (allreduce arch)
        self._apply = jax.jit(
            lambda p, g, lr: jax.tree.map(lambda a, b: a - lr * b, p, g))

    @property
    def comm_plan(self) -> Optional[CommPlan]:
        """The exchange plan the engine executes (None until a step or a
        wire query builds it)."""
        return self._plan

    @property
    def bsp_step(self):
        """The jitted BSP step ``(params, ef, batch, rngs, weights) ->
        (params, ef, losses, sent)``, every argument but ``params`` with a
        leading worker axis (None until the first BSP step)."""
        return self._step_fn

    @property
    def _ef_active(self) -> bool:
        return self.cfg.compressor.method in EF_METHODS

    # ------------------------------------------------------------- planning
    def _ensure_plan(self, params_example) -> CommPlan:
        """The engine's single ``CommPlan`` — built once per (params ×
        worker-count) and shared by the executed schedule, the timeline
        model, and both wire-accounting modes.  Invalidated on reshard."""
        if self._plan is None:
            cfg = self.cfg
            self._plan = CommPlan.plan(
                params_example, axis=AXIS, n=cfg.num_workers,
                topology=cfg.topology, compressor=cfg.compressor,
                wire=cfg.wire, bucket_mb=cfg.bucket_mb, order=cfg.order,
                back_s_per_byte=cfg.back_s_per_byte, seed=cfg.seed,
                link=cfg.link)
        return self._plan

    def _bucket_plan(self, params) -> Tuple[List[List[int]], List[int],
                                            List[LayerCost]]:
        plan = self._ensure_plan(params)
        return plan.buckets, plan.order, plan.fused

    def modeled_timeline(self, params) -> Dict[str, float]:
        """Iteration-time projections for the exact bucket plan this engine
        executes — the benchmark's no-overlap vs overlap comparison."""
        return self._ensure_plan(params).modeled_timeline()

    def per_event_wire_bytes(self, params) -> int:
        """Modeled bytes one worker puts on the wire per gradient push
        (compressor accounting; shape-static).  Identical for both
        architectures and to the simulator's accounting."""
        return self._ensure_plan(params).modeled_event_bytes(params)

    def wire_bytes_per_step(self, params) -> int:
        """Modeled bytes per BSP step summed over workers, like the
        simulator."""
        return self.per_event_wire_bytes(params) * self.cfg.num_workers

    # --------------------------------------------------------- bsp stepping
    def _build_step(self, params_example):
        cfg = self.cfg
        comp = cfg.compressor
        plan = self._ensure_plan(params_example)
        in_schedule = plan.in_schedule
        bucketed_ps = (make_bucketed_ps_update(
            params_example, cfg.lr, bucket_mb=cfg.bucket_mb,
            order=cfg.order, back_s_per_byte=cfg.back_s_per_byte,
            seed=cfg.seed) if cfg.arch == "ps" and not in_schedule
            else None)

        def sharded_step(params, ef, batch, rng, weight):
            # params replicated; ef/batch/rng/weight carry a worker axis.
            # weight is this worker's aggregation weight: 1 normally,
            # K/(K-k) for backup-round participants, 0 for dropped
            # stragglers (whose push never reaches the server and whose
            # EF state is therefore not consumed).
            batch = jax.tree.map(lambda x: x[0], batch)
            ef_in = (jax.tree.map(lambda x: x[0], ef)
                     if ef is not None else None)
            rng = rng[0]
            wt = weight[0]
            loss, grads = self.grad_fn(params, batch)
            sent = jnp.zeros((), jnp.int32)
            # device-trace scopes: ``exchange`` (weights, codec,
            # collectives; the parameter server's fused update too) and
            # ``optimizer`` (the allreduce arch's SGD update)
            with jax.named_scope("exchange"):
                if in_schedule:
                    # compressed payloads ride *inside* the schedule: the
                    # CommPlan encodes each bucket's compensated gradient,
                    # permutes the planes, and returns the per-worker hop
                    # residuals as the new EF contribution (docs/comm.md)
                    g_in = jax.tree.map(lambda x: x * wt, grads)
                    if cfg.arch == "ps":
                        new_params, ef_new, sent = plan.ps_exchange(
                            params, g_in, ef_in, rng, cfg.lr)
                    else:
                        avg, ef_new, sent = plan.exchange(g_in, ef_in, rng)
                else:
                    if comp.method != "none":
                        grads, ef_new, _wb = comp.roundtrip(grads, ef_in,
                                                            rng)
                    else:
                        ef_new = ef_in
                    grads = jax.tree.map(lambda x: x * wt, grads)
                    if cfg.arch == "ps":
                        new_params = bucketed_ps(params, grads)
                    else:
                        avg = plan.reduce_grads(grads)
            if cfg.arch != "ps":
                with jax.named_scope("optimizer"):
                    new_params = jax.tree.map(lambda p, g: p - cfg.lr * g,
                                              params, avg)
            if ef_new is not None:
                ef_out = jax.tree.map(
                    lambda new, old: jnp.where(wt > 0, new, old),
                    ef_new, ef_in)
                ef_out = jax.tree.map(lambda x: x[None], ef_out)
            else:
                ef_out = ef
            return (new_params, ef_out, loss[None], sent[None])

        ef_spec = P(AXIS) if self._ef_active else P()
        fn = jax.shard_map(sharded_step, mesh=self.mesh,
                           in_specs=(P(), ef_spec, P(AXIS), P(AXIS), P(AXIS)),
                           out_specs=(P(), ef_spec, P(AXIS), P(AXIS)),
                           check_vma=False)
        return jax.jit(fn)

    def _event_wire_bytes(self, params) -> int:
        if self._event_wire_cache is None:
            self._event_wire_cache = self.per_event_wire_bytes(params)
        return self._event_wire_cache

    def _step_bsp(self, st, batches, t):
        cfg = self.cfg
        K = cfg.num_workers
        if self._step_fn is None:
            self._step_fn = self._build_step(st["params"])
        plan = self._plan
        # backup workers: drop the k slowest — scheduled ranking, or the
        # measured step-time EMA once detection warms up (the same shared
        # backup_drop rule the simulator applies)
        drop = self.backup_drop(cfg.backup)
        weights = participation_weights(K, drop)
        rec = get_recorder()
        with span("train.step.feed"):
            if self.detector is not None:
                # per-worker batch fetch is the only per-worker host work
                # in the fused device step — measure it (a straggling
                # input pipeline is the detectable straggler here)
                per_worker = []
                for w in range(K):
                    t0 = time.perf_counter()
                    per_worker.append(batches(t, w))
                    self.detector.observe(w, time.perf_counter() - t0)
            else:
                per_worker = [batches(t, w) for w in range(K)]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *per_worker)
        with span("train.step.dispatch"):
            st["rng"], *subs = jax.random.split(st["rng"], K + 1)
            rngs, wts = jnp.stack(subs), jnp.asarray(weights)
            if rec.enabled:
                # the fused shard_map step cannot be split at runtime, so
                # the compute span covers its dispatch and the wait for
                # its results, and the exchange structure below is the
                # plan's deterministic model of what ran inside it
                rec.begin("compute", pid="train", tid="loop", cat="train",
                          clock=("train_step", t), workers=K, fused=True)
            params, ef, losses, sent = self._step_fn(
                st["params"], st["ef"], batch, rngs, wts)
        with span("train.step.wait"):
            # participant-mean loss, float64 like the simulator's
            # accounting; dgc's traced sparse payload, all workers
            part_losses = [float(losses[w]) for w in range(K)
                           if w not in drop]
            sent_elems = (int(np.sum(np.asarray(sent)))
                          if cfg.wire == "measured" else 0)
            if rec.enabled:
                rec.end(pid="train", tid="loop")
        st.update(params=params, ef=ef)
        if cfg.wire == "measured":
            # recomputed per bucket from the plan, every step: the
            # shape-static plane bytes of the whole schedule plus dgc's
            # per-step sparse payload
            wire_inc = plan.measured_step_tx_bytes(cfg.arch) * K \
                + SPARSE_ELEM_BYTES * sent_elems
        else:
            wire_inc = self._event_wire_bytes(st["params"]) \
                * (K - len(drop))
        st["wire"] += wire_inc
        if rec.enabled:
            plan.emit_trace(rec, arch=cfg.arch, clock=("train_step", t))
            rec.counter("wire_bytes", {"cumulative": int(st["wire"])},
                        pid="train", cat="comm", clock=("train_step", t))
        self._dropped += len(drop)
        ev = dict(step=t, loss=float(np.mean(part_losses)), max_staleness=0)
        if drop:
            ev["dropped"] = sorted(drop)
        return st, [ev]

    # ------------------------------------------------------------------ sma
    def _build_sma(self, params_example):
        cfg = self.cfg
        plan = self._ensure_plan(params_example)

        def sma_body(replicas, batch):
            r = jax.tree.map(lambda x: x[0], replicas)
            batch = jax.tree.map(lambda x: x[0], batch)
            loss, g = self.grad_fn(r, batch)
            # the center is a CommPlan exchange of the replicas themselves
            # (same bucket fusion + issue order as the gradient paths)
            center = plan.reduce_grads(r)
            mu = cfg.sma_mu
            new_r = jax.tree.map(
                lambda rr, zz, gg: rr - cfg.lr * gg - mu * (rr - zz),
                r, center, g)
            return (jax.tree.map(lambda x: x[None], new_r), loss[None])

        fn = jax.shard_map(sma_body, mesh=self.mesh,
                           in_specs=(P(AXIS), P(AXIS)),
                           out_specs=(P(AXIS), P(AXIS)),
                           check_vma=False)
        return jax.jit(fn)

    def _param_bytes(self, params_like) -> int:
        return sum(int(np.prod(s) or 1) * 4
                   for s, _ in self._ensure_plan(params_like).leaf_shapes)

    def _step_sma(self, st, batches, t):
        cfg = self.cfg
        K = cfg.num_workers
        if self._sma_fn is None:
            self._sma_fn = self._build_sma(
                jax.tree.map(lambda x: x[0], st["replicas"]))
        per_worker = [batches(t, w) for w in range(K)]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *per_worker)
        st["replicas"], losses = self._sma_fn(st["replicas"], batch)
        if cfg.wire == "measured":
            st["wire"] += self._plan.measured_step_tx_bytes("allreduce") * K
        else:
            # the simulator's accounting: one replica-sized push per worker
            st["wire"] += self._param_bytes(
                jax.tree.map(lambda x: x[0], st["replicas"])) * K
        ev = dict(step=t, loss=float(np.mean(np.asarray(losses))),
                  max_staleness=0)
        return st, [ev]

    # --------------------------------------------------- ssp / asp stepping
    def _build_async_fns(self, params_example):
        cfg = self.cfg
        comp = cfg.compressor

        def grad_body(pulled, ef, batch, key, fire):
            # every input carries a leading worker axis; each worker sees
            # its own row and computes against its *stale* pulled params
            pulled = jax.tree.map(lambda x: x[0], pulled)
            batch = jax.tree.map(lambda x: x[0], batch)
            key = key[0]
            fire = fire[0]
            loss, g = self.grad_fn(pulled, batch)
            if comp.method != "none":
                ef_w = (jax.tree.map(lambda x: x[0], ef)
                        if ef is not None else None)
                g, ef_new, _wb = comp.roundtrip(g, ef_w, key)
                if ef_new is not None:
                    # only firing workers consume their error-feedback state
                    ef_out = jax.tree.map(
                        lambda new, old: jnp.where(fire > 0, new, old),
                        ef_new, ef_w)
                    ef_out = jax.tree.map(lambda x: x[None], ef_out)
                else:
                    ef_out = ef
            else:
                ef_out = ef
            g = jax.tree.map(lambda x: x[None], g)
            return loss[None], g, ef_out

        ef_spec = P(AXIS) if self._ef_active else P()
        grad_fn = jax.jit(jax.shard_map(
            grad_body, mesh=self.mesh,
            in_specs=(P(AXIS), ef_spec, P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS), ef_spec),
            check_vma=False))

        ps_apply = None
        if cfg.arch == "ps":
            step = make_ps_step(sgd_update_fn(cfg.lr), AXIS)

            def ps_body(params, g_stack, onehot):
                # the firing worker pushes its gradient; everyone else
                # contributes exact zeros, so the reduce-scatter delivers
                # the push to each shard's owner, which updates and
                # all-gathers back — a literal single-worker PS push
                g_mine = jax.tree.map(lambda x: x[0], g_stack)
                o = onehot[0]
                contrib = jax.tree.map(lambda x: x * o, g_mine)
                new_params, _ = step(params, contrib, None)
                return new_params

            ps_apply = jax.jit(jax.shard_map(
                ps_body, mesh=self.mesh,
                in_specs=(P(), P(AXIS), P(AXIS)),
                out_specs=P(),
                check_vma=False))
        return grad_fn, ps_apply

    def _step_async(self, st, batches, t, bound: Optional[int]):
        cfg = self.cfg
        if self._async_fns is None:
            self._async_fns = self._build_async_fns(st["params"])
            self._event_wire = self.per_event_wire_bytes(st["params"])
        grad_fn, ps_apply = self._async_fns
        return async_replay_step(
            st, batches, t, bound, K=cfg.num_workers,
            compressor=cfg.compressor, grad_fn=grad_fn,
            apply_fn=self._apply, ps_apply=ps_apply, lr=cfg.lr,
            event_wire=self._event_wire,
            eff_periods=self.effective_periods())

    # -------------------------------------------------- engine protocol
    def init(self, params) -> Dict[str, Any]:
        cfg = self.cfg
        K = cfg.num_workers
        ef = (jax.tree.map(
            lambda x: jnp.zeros((K,) + x.shape, jnp.float32), params)
            if self._ef_active else None)
        st: Dict[str, Any] = dict(
            params=params, ef=ef, rng=jax.random.PRNGKey(cfg.seed), wire=0)
        if cfg.sync in ("ssp", "asp"):
            st.update(
                # per-worker pulled copies are reference rebinds (like the
                # simulator); they are stacked once per tick for shard_map
                pulled=[params] * K,
                pulled_ver=[0] * K,
                server_ver=0,
                tick=0,
                updates=0,
                batch_idx=[0] * K,
                batch_cache=[None] * K,
                # reshard rebases the step↔update accounting here (one
                # global step = K updates at the *current* K)
                updates_base=0,
                step_base=0,
            )
        elif cfg.sync == "sma":
            del st["params"]
            st["replicas"] = jax.tree.map(
                lambda x: jnp.stack([x] * K), params)
        return st

    def step(self, st, batches: Callable[[int, int], Any], t: int):
        sync = self.cfg.sync
        if sync == "bsp":
            st, ev = self._step_bsp(st, batches, t)
        elif sync == "ssp":
            st, ev = self._step_async(st, batches, t, self.cfg.staleness)
        elif sync == "sma":
            st, ev = self._step_sma(st, batches, t)
        else:
            st, ev = self._step_async(st, batches, t, None)
        self._wire_total = st["wire"]
        return st, ev

    def finalize(self, st):
        if self.cfg.sync == "sma":
            # replica average, like the simulator
            return jax.tree.map(lambda x: jnp.mean(x, axis=0),
                                st["replicas"])
        return st["params"]

    def wire_bytes(self) -> int:
        return self._wire_total

    def extra_metrics(self) -> Dict[str, Any]:
        m: Dict[str, Any] = {"wire_mode": self.cfg.wire}
        if self._plan is not None:
            m["measured_step_tx_bytes"] = \
                self._plan.measured_step_tx_bytes(self.cfg.arch)
            m["fp32_step_tx_bytes"] = self._plan.fp32_step_tx_bytes()
        return m

    def per_device_state_bytes(self, st) -> Dict[str, int]:
        """Measured persistent bytes per device — comparable with the
        hybrid engine's ``per_device_state_bytes``.  Plain
        SGD carries no optimizer state; params are replicated, EF
        residuals are per-worker."""
        K = self.cfg.num_workers
        params_like = (jax.tree.map(lambda x: x[0], st["replicas"])
                       if self.cfg.sync == "sma" else st["params"])
        params = sum(np.asarray(x).nbytes
                     for x in jax.tree.leaves(params_like))
        ef = (sum(np.asarray(x).nbytes
                  for x in jax.tree.leaves(st["ef"])) // K
              if st.get("ef") is not None else 0)
        return {"params": params, "opt": 0, "ef": ef, "total": params}

    # --------------------------------------------------- elastic interface
    # (set_slowdown / effective_periods / dropped_updates come from the
    # shared ElasticWorkerSet, so the schedule rule cannot diverge from
    # the simulator's)
    def reshard(self, st, new_workers: int, step: int = 0,
                lost: Tuple[int, ...] = ()):
        """Re-size the worker set N→M *in the same process*: rebuild the
        mesh over the first M live devices, invalidate the compiled step
        functions (the comm plan is re-planned for the new mesh on the
        next step), and remap per-worker state — survivors (old slots
        minus ``lost``, in order) keep their EF residuals and batch
        clocks, grown slots start with zero residuals at the batch
        frontier.  A reshard is a synchronization barrier: every async
        worker re-pulls the current params, and the step↔update
        accounting rebases at global step ``step``."""
        cfg = self.cfg
        if new_workers < 1:
            raise ValueError("new_workers must be >= 1")
        if cfg.backup >= new_workers:
            raise ValueError(f"backup k={cfg.backup} needs > k workers")
        if new_workers > len(self._devs):
            raise ValueError(
                f"resize to {new_workers} workers needs {new_workers} "
                f"devices, have {len(self._devs)}")
        bad = [w for w in lost if w < 0 or w >= cfg.num_workers]
        if bad:
            raise ValueError(f"lost workers {bad} out of range for "
                             f"{cfg.num_workers} workers")
        survivors = [w for w in range(cfg.num_workers) if w not in set(lost)]
        slots = survivors[:new_workers]
        grown = new_workers - len(slots)
        # survivors keep their speed identity (like their slowdowns and
        # EF state); grown slots take the default-schedule tail
        periods = tuple([self.periods[s] for s in slots]
                        + list(default_periods(new_workers))[len(slots):])
        self.cfg = cfg = dataclasses.replace(
            cfg, num_workers=new_workers, periods=periods)
        self.mesh = Mesh(np.array(self._devs[:new_workers]), (AXIS,))
        self.periods = periods
        self.slowdowns = [self.slowdowns[s] for s in slots] + [1.0] * grown
        if self.detector is not None:
            self.detector.reshard(slots, new_workers)
        self._step_fn, self._sma_fn = None, None
        self._plan, self._event_wire_cache = None, None
        self._async_fns = None
        if st.get("ef") is not None:
            def remap_rows(x):     # (K_old,)+s -> (M,)+s
                rows = ([x[s] for s in slots]
                        + [jnp.zeros_like(x[0])] * grown)
                return jnp.stack(rows)
            st["ef"] = jax.tree.map(remap_rows, st["ef"])
        if cfg.sync in ("ssp", "asp"):
            frontier = max([st["batch_idx"][s] for s in slots] or [0])
            st["pulled"] = [st["params"]] * new_workers
            st["pulled_ver"] = [st["server_ver"]] * new_workers
            st["batch_idx"] = ([st["batch_idx"][s] for s in slots]
                               + [frontier] * grown)
            st["batch_cache"] = [None] * new_workers
            st["updates_base"] = st["updates"]
            st["step_base"] = step
        elif cfg.sync == "sma":
            # survivors keep their replicas; grown slots start at the
            # pre-reshard center, exactly like the simulator
            def remap_replicas(x):
                center = jnp.mean(x, axis=0)
                rows = [x[s] for s in slots] + [center] * grown
                return jnp.stack(rows)
            st["replicas"] = jax.tree.map(remap_replicas, st["replicas"])
        # arrays committed to the old mesh's devices would clash with the
        # new mesh inside jit — pull them to host; the next step re-places
        # them on the resized mesh
        for key in ("params", "ef", "pulled", "rng", "replicas"):
            if st.get(key) is not None:
                st[key] = jax.device_get(st[key])
        return st

    def export_state(self, st) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Split the run-state into (array pytree, JSON-able meta) for
        ``repro.checkpoint`` — the inverse of ``import_state``.  The
        per-worker batch cache is dropped: batches are a pure function of
        (batch_idx, worker), so resume re-fetches identical tensors."""
        cfg = self.cfg
        arrays: Dict[str, Any] = {"ef": st["ef"], "rng": st["rng"]}
        if cfg.sync == "sma":
            arrays["replicas"] = st["replicas"]
        else:
            arrays["params"] = st["params"]
        meta: Dict[str, Any] = dict(
            backend="device", mode=cfg.sync, num_workers=cfg.num_workers,
            wire=int(st["wire"]), periods=list(self.periods),
            slowdowns=list(self.slowdowns), dropped=self._dropped,
            detector=(self.detector.state() if self.detector is not None
                      else None))
        if cfg.sync in ("ssp", "asp"):
            arrays["pulled"] = st["pulled"]
            meta.update(pulled_ver=list(st["pulled_ver"]),
                        server_ver=int(st["server_ver"]),
                        tick=int(st["tick"]), updates=int(st["updates"]),
                        batch_idx=list(st["batch_idx"]),
                        updates_base=int(st["updates_base"]),
                        step_base=int(st["step_base"]))
        return arrays, meta

    def import_state(self, arrays: Dict[str, Any], meta: Dict[str, Any]):
        """Rebuild the run-state from an ``export_state`` snapshot.  The
        engine must already be configured at ``meta['num_workers']``."""
        cfg = self.cfg
        if meta["num_workers"] != cfg.num_workers:
            raise ValueError(
                f"snapshot has {meta['num_workers']} workers, engine has "
                f"{cfg.num_workers}; reshard the engine first")
        # the worker speed schedule travels with the snapshot: a resharded
        # run's remapped periods must survive a cross-process restore
        self.periods = tuple(int(p) for p in meta["periods"])
        self.cfg = cfg = dataclasses.replace(cfg, periods=self.periods)
        self.slowdowns = [float(s) for s in meta["slowdowns"]]
        self._dropped = int(meta["dropped"])
        if self.detector is not None:
            self.detector.load_state(meta.get("detector"))
        st: Dict[str, Any] = dict(
            ef=arrays["ef"], rng=jnp.asarray(arrays["rng"]),
            wire=int(meta["wire"]))
        if cfg.sync == "sma":
            st["replicas"] = arrays["replicas"]
        else:
            st["params"] = arrays["params"]
        if cfg.sync in ("ssp", "asp"):
            st.update(pulled=arrays["pulled"],
                      pulled_ver=list(meta["pulled_ver"]),
                      server_ver=int(meta["server_ver"]),
                      tick=int(meta["tick"]), updates=int(meta["updates"]),
                      batch_idx=list(meta["batch_idx"]),
                      batch_cache=[None] * cfg.num_workers,
                      updates_base=int(meta["updates_base"]),
                      step_base=int(meta["step_base"]))
        self._wire_total = st["wire"]
        return st

    # ------------------------------------------------------------------ run
    def run(self, params, batches: Callable[[int, int], Any], steps: int):
        """batches(t, worker) -> batch pytree (same contract as
        ``SimSyncEngine.run``).  Returns (params, history, wire_bytes)."""
        st = self.init(params)
        hist: List[dict] = []
        for t in range(steps):
            st, ev = self.step(st, batches, t)
            hist.extend(ev)
        return self.finalize(st), hist, st["wire"]


class DataParallelEngine(DeviceEngine):
    """Deprecated PR-1 alias for ``DeviceEngine`` — kept so existing call
    sites keep working.  Use ``repro.train.Strategy(sync=..., arch=...,
    backend='device').build(grad_fn)`` which wraps the same engine
    (bitwise-identical results)."""

    def __init__(self, cfg: DataParallelConfig, grad_fn: Callable,
                 devices: Optional[Sequence] = None):
        warn_deprecated("DataParallelEngine",
                        "repro.train.Strategy(...).build(grad_fn)")
        super().__init__(cfg, grad_fn, devices)
