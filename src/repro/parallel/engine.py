"""The hybrid-parallel engine: one device-executed train step over a
data × tensor × stage mesh with ZeRO-sharded optimizer state.

``HybridEngine`` composes the three parallelization methods of the
survey's §3.2 — and the repo's three previously-disconnected modules —
into a single jitted ``shard_map`` over a 3-axis mesh:

  stage axis    ``core/pipeline.py``'s GPipe micro-batch schedule: each
                stage device holds its stage's parameters, activations
                flow through the ``lax.scan`` + ``ppermute`` loop forward
                AND backward (ppermute's transpose runs the reverse
                pipeline), micro-batch gradients accumulate in the scan.
  tensor axis   ``core/parallelism.py``'s role-based PartitionSpecs made
                explicit: each leaf is sharded on its role dimension
                (column-parallel on the output dim, row-parallel on the
                input dim) and the StagedModel places the two Megatron
                collectives (see parallel/staged.py).
  data axis     the existing bucketed / compressed / error-feedback
                exchange of ``train/data_parallel.py`` — same bucket
                planner, same compressor accounting — either as a
                topology-explicit allreduce (z0) or through the
                reduce-scatter/shard-update/all-gather ZeRO path of
                ``core/parameter_server.py`` (z1-z3, parallel/zero.py).

The engine speaks the same Engine/elastic protocol as the other two
backends (init / step / finalize, export_state / import_state / reshard),
so ``Trainer.fit(plan=...)`` checkpoint-recovers and resizes hybrid runs
— resizing rebuilds the *data* axis (tensor × stage geometry is a model
property and survives), and checkpoints carry the sharded optimizer
state.  BSP only: asynchrony composes with the data axis, not with the
pipeline schedule.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.comm.codecs import SPARSE_ELEM_BYTES, codec_for, make_codec
from repro.comm.plan import CommPlan, modeled_event_bytes
from repro.comm.transport import (compressed_allreduce,
                                  compressed_reduce_scatter,
                                  schedule_tx_bytes)
from repro.core.compression import Compressor, EF_METHODS
from repro.core.parameter_server import shard_of_flat
from repro.core.pipeline import (bubble_fraction, gpipe_forward, gpipe_ticks,
                                 onefb_bubble_fraction, onefb_forward,
                                 onefb_ticks)
from repro.core.precision import policy_for
from repro.obs.trace import get_recorder
from repro.core.sync import default_periods
from repro.launch.mesh import make_hybrid_mesh
from repro.parallel.mesh_plan import AXES, MeshPlan, MeshSpec, plan_mesh
from repro.parallel.staged import (StagedModel, is_staged_model,
                                   tensor_reduce)
from repro.parallel.zero import (flatten_bucket, init_opt_state,
                                 make_optimizer_step, make_zero_bucket_update,
                                 state_bytes_per_device,
                                 wire_bytes_per_device)
from repro.train.data_parallel import _scatter_flat, async_replay_step

DATA, TENSOR, STAGE = AXES

ASYNC_SYNCS = ("ssp", "asp")


def emit_pipeline_trace(rec, stages: int, micro: int, *,
                        schedule: str = "gpipe", interleave: int = 1,
                        pid: str = "pipeline", clock=None) -> None:
    """The pipeline schedule this step executed, as trace spans on the
    deterministic tick clock (docs/observability.md): a ``pipe`` parent
    span on ``pipeline/schedule`` carrying the schedule-specific analytic
    bubble fraction, and per-stage tracks ``stage<s>`` with one span per
    schedule tick — ``mb<k>`` while the stage device computes micro-batch
    k, and ``bubble`` for the fill/drain ticks where it sits idle.  Under
    GPipe stage s holds micro k = tick - s; under (interleaved) 1F1B
    device i is busy for its ``v * m`` consecutive chunk calls starting
    at tick i, computing micro ``(tick - i) mod m`` of chunk
    ``(tick - i) // m``.  The fused jitted step cannot be split at
    runtime, so like the CommPlan exchange spans this is the plan's own
    deterministic model of what executed;
    ``obs.analyze.pipeline_accounting`` measures the bubble fraction
    back off these spans."""
    if not rec.enabled:
        return
    if schedule == "1f1b":
        v = interleave
        ticks = onefb_ticks(stages, micro, v)
        analytic = onefb_bubble_fraction(stages, micro, v)
    else:
        v = 1
        ticks = gpipe_ticks(stages, micro)
        analytic = bubble_fraction(stages, micro)
    rec.begin("pipe", pid=pid, tid="schedule", cat="pipeline", clock=clock,
              stages=stages, micro=micro, ticks=ticks, schedule=schedule,
              interleave=v, analytic_bubble=round(analytic, 6))
    for s in range(stages):
        tid = f"stage{s}"
        for k in range(ticks):
            if schedule == "1f1b":
                active = s <= k < s + v * micro
                mb = (k - s) % micro
            else:
                mb = k - s
                active = 0 <= mb < micro
            name = f"mb{mb}" if active else "bubble"
            rec.begin(name, pid=pid, tid=tid, cat="pipeline",
                      clock=("pipe_tick", k), stage=s)
            rec.end(pid=pid, tid=tid)
    rec.end(pid=pid, tid="schedule")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    mesh: MeshSpec = MeshSpec()
    lr: float = 0.1
    compressor: Compressor = Compressor("none")
    zero: int = 0                    # ZeRO level 0-3 (data-axis sharding)
    optimizer: str = "sgd"           # sgd | adamw
    topology: str = "ring"           # z0 data-axis allreduce schedule
    bucket_mb: float = 4.0
    order: str = "tictac"
    micro_batches: int = 0           # 0 = auto (2*stages when pipelined)
    schedule: str = "gpipe"          # pipeline schedule: gpipe | 1f1b
    interleave: int = 0              # 1f1b virtual stages/device (0 = auto 2)
    precision: str = "fp32"          # fp32 | bf16 | bf16r (core/precision)
    moments: str = "float32"         # AdamW EMA storage: float32 | bfloat16
    # sync model over the DATA axis (docs/hybrid.md): bsp natively; ssp/
    # asp replay the simulator's staleness schedule per data slot, sma
    # keeps a replica per data slot — all three need stage=1, z0, sgd
    sync: str = "bsp"
    staleness: int = 3
    periods: Optional[Tuple[int, ...]] = None   # per data-slot speeds
    sma_mu: float = 0.1
    wire: str = "modeled"            # modeled | measured (docs/comm.md)
    seed: int = 0

    @property
    def num_workers(self) -> int:
        """Total devices — the elastic layer's worker count."""
        return self.mesh.size


class HybridEngine:
    """BSP over a d×t×s mesh with ZeRO-0/1/2/3 state sharding.

    The model is either a plain ``grad_fn(params, batch)`` (pure data
    axis: mesh must be dK.t1.s1) or a ``StagedModel`` with stage-stacked
    params (any mesh).  ``batches(t, w)`` is keyed by *data-parallel
    slot* w in [0, mesh.data) — the tensor/stage axes replicate the
    slot's batch."""

    def __init__(self, cfg: HybridConfig, model, devices: Optional[Sequence] = None):
        if cfg.zero not in (0, 1, 2, 3):
            raise ValueError(f"zero={cfg.zero} (want 0..3)")
        if cfg.optimizer not in ("sgd", "adamw"):
            raise ValueError(f"optimizer={cfg.optimizer!r}")
        if cfg.sync not in ("bsp",) + ASYNC_SYNCS + ("sma",):
            raise ValueError(f"sync={cfg.sync!r}")
        if cfg.wire not in ("modeled", "measured"):
            raise ValueError(f"wire={cfg.wire!r}")
        if cfg.sync != "bsp" and (cfg.mesh.stage != 1 or cfg.zero
                                  or cfg.optimizer != "sgd"):
            raise ValueError(
                f"sync={cfg.sync!r} composes with the data axis only: "
                "needs stage=1, zero=0, optimizer='sgd'")
        if cfg.schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"schedule={cfg.schedule!r} (want gpipe|1f1b)")
        if cfg.schedule == "1f1b" and cfg.mesh.stage < 2:
            raise ValueError(
                "schedule='1f1b' needs a pipeline (mesh stage >= 2)")
        if cfg.interleave and cfg.schedule != "1f1b":
            raise ValueError(
                f"interleave=v{cfg.interleave} only applies to the 1f1b "
                "schedule")
        if cfg.interleave < 0:
            raise ValueError(f"interleave={cfg.interleave} (want >= 1)")
        if cfg.moments not in ("float32", "bfloat16"):
            raise ValueError(
                f"moments={cfg.moments!r} (want float32|bfloat16)")
        self._policy = policy_for(cfg.precision)   # raises on unknown name
        if cfg.sync != "bsp" and cfg.precision != "fp32":
            raise ValueError(
                f"sync={cfg.sync!r} cells run fp32 (precision="
                f"{cfg.precision!r} composes with BSP only)")
        # effective 1f1b interleave: v virtual stages per device
        self._v = ((cfg.interleave or 2)
                   if cfg.schedule == "1f1b" else 1)
        self.staged = is_staged_model(model)
        if not self.staged and not cfg.mesh.is_trivial:
            raise ValueError(
                f"mesh {cfg.mesh.spec()} has tensor/stage axes; pass a "
                "repro.parallel.StagedModel (a bare grad_fn cannot be "
                "pipelined or tensor-sharded)")
        self.cfg = cfg
        self.model: Optional[StagedModel] = model if self.staged else None
        self.grad_fn: Optional[Callable] = None if self.staged else model
        self._devs = list(devices or jax.devices())
        if len(self._devs) < cfg.mesh.size:
            raise ValueError(
                f"mesh {cfg.mesh.spec()} needs {cfg.mesh.size} devices, "
                f"have {len(self._devs)} (run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        self.mesh = make_hybrid_mesh(self._devs, cfg.mesh.data,
                                     cfg.mesh.tensor, cfg.mesh.stage)
        self.plan: Optional[MeshPlan] = None
        self.periods = cfg.periods or default_periods(cfg.mesh.data)
        assert len(self.periods) == cfg.mesh.data
        self.slowdowns: List[float] = [1.0] * cfg.mesh.data
        self._step_fn = None
        self._async_fns = None
        self._sma_fn = None
        self._act_cell: List[int] = []
        self._dev_event_bytes: Optional[int] = None
        self._measured_tx: Optional[int] = None
        self._trace_plan: Optional[CommPlan] = None
        self._wire_total = 0
        self._leaf_meta = None           # (treedef, [(local_shape, dtype)])
        # same replicated apply as the flat engines (async data axis)
        self._apply = jax.jit(
            lambda p, g, lr: jax.tree.map(lambda a, b: a - lr * b, p, g))

    # ------------------------------------------------------------ helpers
    @property
    def data_streams(self) -> int:
        """Batch streams the engine consumes (the data axis size) — the
        elastic layer keys ``ElasticBatches`` on this, not on the total
        device count."""
        return self.cfg.mesh.data

    @property
    def _ef_active(self) -> bool:
        return self.cfg.compressor.method in EF_METHODS

    def _ensure_plan(self, params):
        if self.plan is None:
            self.plan = plan_mesh(
                params, self.cfg.mesh, staged=self.staged,
                bucket_mb=self.cfg.bucket_mb, order=self.cfg.order,
                micro_batches=self.cfg.micro_batches, seed=self.cfg.seed)
            leaves = jax.tree.leaves(params)
            locals_ = jax.tree.leaves(self.plan.local_example)
            self._leaf_meta = (
                jax.tree.structure(params),
                [(tuple(lo.shape), le.dtype)
                 for lo, le in zip(locals_, leaves)])
            if self.cfg.schedule == "1f1b":
                s = self.cfg.mesh.stage
                if self.plan.micro < s:
                    raise ValueError(
                        f"1f1b needs micro_batches >= stages (got "
                        f"m={self.plan.micro} < s={s}); the wrap-link "
                        "FIFO gap m - s must be >= 0")
                chunk = locals_[0].shape[0]
                if chunk % self._v:
                    raise ValueError(
                        f"1f1b interleave v{self._v}: per-stage layer "
                        f"count {chunk} not divisible into v virtual "
                        "stages")
        return self.plan

    # ------------------------------------------- 1f1b virtual-stage layout
    def _stage_perm(self, n_rows: int) -> np.ndarray:
        """Row permutation of a globally stacked leaf for interleaved
        1F1B: device i must hold virtual stages {c*S + i | c < v} as its
        v contiguous local chunks (chunk-major), so the existing
        contiguous stage slicing of ``_local_block`` / the P(STAGE)
        in-spec hands every device exactly the layers
        ``onefb_forward``'s per-chunk dynamic slice expects."""
        s, v = self.cfg.mesh.stage, self._v
        cl = n_rows // (s * v)
        idx: List[int] = []
        for i in range(s):
            for c in range(v):
                vs = c * s + i
                idx.extend(range(vs * cl, (vs + 1) * cl))
        return np.asarray(idx)

    def _permute_stacked(self, params, inverse: bool = False):
        """Reorder stacked-leaf rows into (or back out of) the 1f1b
        virtual-stage layout.  Identity for gpipe / v=1, so every
        existing cell's arrays are untouched."""
        if not self.staged or self._v == 1:
            return params

        def f(leaf):
            perm = self._stage_perm(np.shape(leaf)[0])
            if inverse:
                perm = np.argsort(perm)
            return jnp.asarray(leaf)[perm]
        return jax.tree.map(f, params)

    def _local_block(self, leaf, t_dim, s_idx: int, t_idx: int):
        """Host-side (s, t) block of a stacked leaf — the array one mesh
        coordinate holds: a contiguous chunk of layers along dim 0, a
        role-dim slice along the tensor axis."""
        x = np.asarray(leaf)
        if self.staged:
            chunk = x.shape[0] // self.cfg.mesh.stage
            x = x[s_idx * chunk:(s_idx + 1) * chunk]
        if self.cfg.mesh.tensor > 1 and t_dim is not None:
            m = x.shape[t_dim] // self.cfg.mesh.tensor
            x = np.take(x, range(t_idx * m, (t_idx + 1) * m), axis=t_dim)
        return x

    def _bucket_flat(self, params, b: int, s_idx: int, t_idx: int):
        """Host-side flat (s, t)-local bucket vector, padded over data."""
        plan = self.plan
        leaves = jax.tree.leaves(params)
        flat = np.concatenate(
            [self._local_block(leaves[i], plan.tensor_dims[i], s_idx,
                               t_idx).astype(np.float32).reshape(-1)
             for i in plan.buckets[b]])
        pad = plan.mesh.data * -(-flat.size // plan.mesh.data) - flat.size
        return np.pad(flat, (0, pad))

    def _shard_array(self, params, b: int) -> np.ndarray:
        """[D, S, T, m] array of per-rank flat shards for bucket ``b``."""
        cfg, plan = self.cfg, self.plan
        d, t, s = cfg.mesh.data, cfg.mesh.tensor, cfg.mesh.stage
        m = -(-plan.bucket_sizes[b] // d)
        out = np.zeros((d, s, t, m), np.float32)
        for si in range(s):
            for ti in range(t):
                out[:, si, ti, :] = self._bucket_flat(
                    params, b, si, ti).reshape(d, m)
        return out

    def _materialize_params(self, pshard_arrays: List[np.ndarray]):
        """Inverse of ``_shard_array``: rebuild the full stacked parameter
        pytree from the per-bucket [D, S, T, m] shard arrays (host side —
        checkpointing, finalize, reshard)."""
        cfg, plan = self.cfg, self.plan
        treedef, meta = self._leaf_meta
        t_dims = plan.tensor_dims
        s_ax, t_ax = cfg.mesh.stage, cfg.mesh.tensor
        # allocate full stacked leaves
        full = []
        for i, (lshape, dtype) in enumerate(meta):
            gshape = list(lshape)
            td = t_dims[i]
            if t_ax > 1 and td is not None:
                gshape[td] *= t_ax
            if self.staged:
                gshape[0] *= s_ax
            full.append(np.zeros(gshape, np.float32))
        for arr, b in zip(pshard_arrays, plan.order):
            n_b = plan.bucket_sizes[b]
            for si in range(s_ax):
                for ti in range(t_ax):
                    flat = np.asarray(arr)[:, si, ti, :].reshape(-1)[:n_b]
                    off = 0
                    for i in plan.buckets[b]:
                        lshape, dtype = meta[i]
                        size = int(np.prod(lshape)) if lshape else 1
                        block = flat[off:off + size].reshape(lshape)
                        off += size
                        td = t_dims[i]
                        sl = [slice(None)] * block.ndim
                        if self.staged:
                            chunk = lshape[0]
                            sl[0] = slice(si * chunk, (si + 1) * chunk)
                        if t_ax > 1 and td is not None:
                            m = block.shape[td]
                            sl[td] = slice(ti * m, (ti + 1) * m)
                        full[i][tuple(sl)] = block
        full = [f.astype(meta[i][1]) for i, f in enumerate(full)]
        return jax.tree.unflatten(treedef, full)

    # -------------------------------------------------------------- specs
    def _param_spec(self, t_dim, local_ndim: int):
        """PartitionSpec of one stacked leaf: layer dim over the stage
        axis + the role dim over the tensor axis, replicated over data
        (local and global rank agree — stage/tensor divide dims)."""
        if not self.staged:
            return P()
        axes: List[Optional[str]] = [None] * local_ndim
        axes[0] = STAGE
        if t_dim is not None and self.cfg.mesh.tensor > 1:
            axes[t_dim] = TENSOR
        return P(*axes)

    def _state_specs(self):
        plan, cfg = self.plan, self.cfg
        t_dims = plan.tensor_dims
        locals_ = jax.tree.leaves(plan.local_example)
        treedef = self._leaf_meta[0]
        p_specs = jax.tree.unflatten(
            treedef, [self._param_spec(td, lo.ndim)
                      for td, lo in zip(t_dims, locals_)])
        shard_spec = [P(DATA, STAGE, TENSOR) for _ in plan.order]
        if cfg.zero == 3:
            params_spec: Any = shard_spec
        else:
            params_spec = p_specs
        if cfg.optimizer == "adamw":
            if cfg.zero == 0:
                opt_spec: Any = {"m": p_specs, "v": p_specs, "t": P()}
            else:
                opt_spec = {"m": list(shard_spec), "v": list(shard_spec),
                            "t": P()}
        else:
            opt_spec = P()      # None pytree: placeholder spec
        ef_spec = (jax.tree.unflatten(
            treedef, [P(DATA, STAGE, TENSOR) for _ in locals_])
            if self._ef_active else P())
        return params_spec, opt_spec, ef_spec

    # ---------------------------------------------------------------- init
    def init(self, params) -> Dict[str, Any]:
        cfg = self.cfg
        plan = self._ensure_plan(params)
        # 1f1b interleaving holds params in virtual-stage row order for
        # the whole run (identity otherwise); finalize() restores it
        params = self._permute_stacked(params)
        st: Dict[str, Any] = dict(rng=jax.random.PRNGKey(cfg.seed), wire=0)
        D = cfg.mesh.data
        if cfg.sync in ASYNC_SYNCS:
            # async over the data axis: per-slot pulled copies of the
            # FULL stacked params (reference rebinds, like the flat
            # engines); EF state is per-slot over full leaves too, since
            # a slot's push is its assembled full gradient
            st.update(
                params=params, opt=None,
                ef=(jax.tree.map(
                    lambda x: jnp.zeros((D,) + x.shape, jnp.float32),
                    params) if self._ef_active else None),
                pulled=[params] * D, pulled_ver=[0] * D, server_ver=0,
                tick=0, updates=0, batch_idx=[0] * D,
                batch_cache=[None] * D, updates_base=0, step_base=0)
            return st
        if cfg.sync == "sma":
            st["replicas"] = jax.tree.map(
                lambda x: jnp.stack([x] * D), params)
            return st
        if cfg.zero == 3:
            st["params"] = [jnp.asarray(self._shard_array(params, b))
                            for b in plan.order]
        else:
            st["params"] = params
        if cfg.optimizer == "adamw":
            if cfg.zero == 0:
                st["opt"] = init_opt_state("adamw", params, cfg.moments)
            else:
                # one moment shard per bucket, in ISSUE order — aligned
                # with the p/g bucket lists the step function builds
                zeros = [jnp.zeros((cfg.mesh.data, cfg.mesh.stage,
                                    cfg.mesh.tensor,
                                    plan.shard_sizes[b]),
                                   jnp.dtype(cfg.moments))
                         for b in plan.order]
                st["opt"] = {"m": list(zeros),
                             "v": [jnp.zeros_like(z) for z in zeros],
                             "t": jnp.zeros((), jnp.int32)}
        else:
            st["opt"] = None
        if self._ef_active:
            d, t, s = cfg.mesh.data, cfg.mesh.tensor, cfg.mesh.stage
            st["ef"] = jax.tree.map(
                lambda lo: jnp.zeros((d, s, t) + lo.shape, jnp.float32),
                plan.local_example)
        else:
            st["ef"] = None
        return st

    # ---------------------------------------------------------------- step
    def _comm_plan(self) -> CommPlan:
        """The data-axis ``CommPlan`` over this device's local block
        structure — the same plan object (bucket fusion, issue order,
        codec, wire mode) the pure data-parallel engine executes."""
        cfg = self.cfg
        return CommPlan.plan(
            self.plan.local_example, axis=DATA, n=cfg.mesh.data,
            topology=cfg.topology, compressor=cfg.compressor,
            wire=cfg.wire, bucket_mb=cfg.bucket_mb, order=cfg.order,
            seed=cfg.seed, reduce_dtype=self._policy.reduce_dtype)

    def _measured_step_tx_bytes(self) -> int:
        """Shape-static measured bytes ONE device puts on the data axis
        per step, per bucket from the plan: z0 = the topology schedule;
        z1 = ring-allreduce grads + fp32 param all-gather; z2/z3 = the
        CommPlan ``ps`` accounting (RS grads + fp32 param all-gather)."""
        cfg, plan = self.cfg, self.plan
        d = cfg.mesh.data
        if d == 1:
            return 0
        comm = self._comm_plan()
        if cfg.zero == 0:
            return comm.measured_step_tx_bytes("allreduce")
        if cfg.zero >= 2:
            return comm.measured_step_tx_bytes("ps")
        # z1: compressed ring allreduce of grads + exact param all-gather
        codec = comm.codec if comm.in_schedule else make_codec("none")
        # bf16 reduce halves the exact grad words; params stay fp32
        scale = (comm.word_bytes / 4
                 if codec.exact and comm.word_bytes != 4 else 1.0)
        total = 0.0
        for b in plan.order:
            P = d * (-(-plan.bucket_sizes[b] // d))
            total += schedule_tx_bytes("ring", d, P, codec) * scale
            total += (d - 1) * 4 * (P // d)       # params travel exact
        return int(total)

    def _build_step(self):
        cfg, plan = self.cfg, self.plan
        model, grad_fn = self.model, self.grad_fn
        comp = cfg.compressor
        D, T, S = cfg.mesh.data, cfg.mesh.tensor, cfg.mesh.stage
        micro = plan.micro
        treedef, meta = self._leaf_meta
        sizes = [plan.bucket_sizes[b] for b in plan.order]
        comm = self._comm_plan()
        in_schedule = comm.in_schedule
        codec = codec_for(comp)
        gain = comp.ef_gain if comp.method == "onebit" else 1.0
        reduce0 = comm.reduce_grads if cfg.zero == 0 else None
        zero_update = (make_zero_bucket_update(
            plan, cfg.zero, cfg.optimizer, cfg.lr, axis=DATA,
            moment_dtype=cfg.moments)
            if cfg.zero else None)
        opt_step0 = (make_optimizer_step(cfg.optimizer, cfg.lr, cfg.moments)
                     if cfg.zero == 0 else None)
        tensor_axis = TENSOR if T > 1 else None
        policy = self._policy
        bf16_compute = policy.compute_dtype != "float32"
        bf16_reduce = policy.reduce_dtype != "float32"
        act_cell: List[int] = []

        def squeeze3(x):
            return x[0, 0, 0]

        def expand3(x):
            return jnp.expand_dims(x, (0, 1, 2))

        chunk = (jax.tree.leaves(plan.local_example)[0].shape[0]
                 if self.staged else 0)

        def local_params(pstate):
            if cfg.zero == 3:
                shards = [squeeze3(x) for x in pstate]
                out: List[Any] = [None] * len(meta)
                for shard, b, n_b in zip(shards, plan.order, sizes):
                    full = lax.all_gather(shard, DATA).reshape(-1)[:n_b]
                    _scatter_flat(full, plan.buckets[b],
                                  meta, out)
                return jax.tree.unflatten(treedef, out)
            return pstate

        def stage_call(sp, xx):
            # one stage device holds a contiguous chunk of layers
            for j in range(chunk):
                xx = model.stage_fn(jax.tree.map(lambda l: l[j], sp), xx,
                                    tensor_axis=tensor_axis)
            return xx

        cl = chunk // self._v if self.staged else 0

        def chunk_call(sp, xx):
            # one 1f1b virtual stage: the cl-layer chunk onefb_forward
            # sliced out of the device's (virtual-stage-ordered) block
            for j in range(cl):
                xx = model.stage_fn(jax.tree.map(lambda l: l[j], sp), xx,
                                    tensor_axis=tensor_axis)
            return xx

        def local_loss_and_grads(p_local, batch):
            if not self.staged:
                if not bf16_compute:
                    return grad_fn(p_local, batch)
                # bf16 compute, fp32 master weights: the cast transposes
                # cotangents back to fp32, and p_local stays the fp32
                # master copy the optimizer updates
                loss, grads = grad_fn(policy.cast_for_compute(p_local),
                                      batch)
                return loss, jax.tree.map(
                    lambda g: g.astype(jnp.float32), grads)

            def lloss(pl):
                if bf16_compute:
                    pl = policy.cast_for_compute(pl)
                x = model.inputs(batch)
                if bf16_compute:
                    x = x.astype(policy.cdt)
                bsz = x.shape[0]
                xm = x.reshape((micro, bsz // micro) + x.shape[1:])
                if not act_cell:
                    act_cell.append(int(np.prod(xm.shape[1:]))
                                    * int(jnp.dtype(xm.dtype).itemsize))
                if cfg.schedule == "1f1b":
                    outs = onefb_forward(chunk_call, pl, xm, STAGE,
                                         interleave=self._v)
                else:
                    outs = gpipe_forward(stage_call, pl, xm, STAGE)
                y = outs.reshape((bsz,) + x.shape[1:])
                loss = model.readout(y, batch).astype(jnp.float32)
                # only the last stage holds real outputs; the reduce
                # broadcasts its loss along the stage axis with identity
                # transpose (each stage's masked loss gets the plain
                # cotangent — the pipeline backward itself flows through
                # the ppermute chain inside the schedule)
                loss = jnp.where(lax.axis_index(STAGE) == S - 1, loss, 0.0)
                return tensor_reduce(STAGE)(loss)

            loss, grads = jax.value_and_grad(lloss)(p_local)
            return loss, grads

        def zero_buckets(pstate, opt, p_local):
            if cfg.zero == 3:
                p_buckets = [squeeze3(x) for x in pstate]
            else:
                p_leaves = jax.tree.leaves(p_local)
                p_buckets = [flatten_bucket(p_leaves, plan.buckets[b])
                             for b in plan.order]
            opt_l = opt
            if opt is not None:
                opt_l = {"m": [squeeze3(x) for x in opt["m"]],
                         "v": [squeeze3(x) for x in opt["v"]],
                         "t": opt["t"]}
            return p_buckets, opt_l

        def zero_unpack(new_buckets, opt_new, opt):
            if opt_new is not None:
                opt_new = {"m": [expand3(x) for x in opt_new["m"]],
                           "v": [expand3(x) for x in opt_new["v"]],
                           "t": opt_new["t"]}
            if cfg.zero == 3:
                p_out = [expand3(x) for x in new_buckets]
            else:
                out: List[Any] = [None] * len(meta)
                for flat, b in zip(new_buckets, plan.order):
                    _scatter_flat(flat, plan.buckets[b], meta, out)
                p_out = jax.tree.unflatten(treedef, out)
            return p_out, opt_new if opt is not None else opt

        def body(pstate, opt, ef, batch, key0):
            batch_l = jax.tree.map(lambda x: x[0], batch)
            p_local = local_params(pstate)
            loss, grads = local_loss_and_grads(p_local, batch_l)
            if bf16_reduce:
                # round the push to the bf16 wire words the measured
                # accounting counts (the exchange math re-widens to fp32)
                grads = policy.cast_for_reduce(grads)
            key = key0
            for ax in AXES:
                key = jax.random.fold_in(key, lax.axis_index(ax))
            sent = jnp.zeros((), jnp.int32)
            ef_l = jax.tree.map(squeeze3, ef) if ef is not None else None
            if in_schedule:
                # compressed payloads ride inside the data-axis schedule:
                # z0 through the CommPlan topology exchange, z1-z3 through
                # the compressed ring AR/RS of the ZeRO bucket update;
                # parameters always travel exact (docs/comm.md)
                if cfg.zero == 0:
                    avg, ef_new, sent = comm.exchange(grads, ef_l, key)
                    p_out, opt_new = opt_step0(p_local, avg, opt)
                    ef_out = (jax.tree.map(expand3, ef_new)
                              if ef_new is not None else ef)
                else:
                    g_leaves = jax.tree.leaves(grads)
                    if ef_l is not None:
                        e_leaves = jax.tree.leaves(ef_l)
                        cin = [g.astype(jnp.float32) + gain * e
                               for g, e in zip(g_leaves, e_leaves)]
                    else:
                        cin = g_leaves
                    g_buckets = [flatten_bucket(cin, plan.buckets[b])
                                 for b in plan.order]
                    p_buckets, opt_l = zero_buckets(pstate, opt, p_local)
                    resids: List[Any] = []
                    nz_acc: List[Any] = []
                    keybox = [key]

                    def grad_reduce(padded, _j):
                        keybox[0], sub = jax.random.split(keybox[0])
                        if cfg.zero == 1:
                            red, res, nz = compressed_allreduce(
                                padded, DATA, "ring", codec, sub)
                            shard = shard_of_flat(red, DATA)
                        else:
                            shard, res, nz = compressed_reduce_scatter(
                                padded, DATA, codec, sub)
                        resids.append(res)
                        nz_acc.append(nz)
                        return shard

                    new_buckets, opt_new = zero_update(
                        p_buckets, g_buckets, opt_l,
                        grad_reduce=grad_reduce)
                    sent = sum(nz_acc, sent)
                    p_out, opt_new = zero_unpack(new_buckets, opt_new, opt)
                    if ef_l is not None:
                        res_list: List[Any] = [None] * len(meta)
                        for res, b in zip(resids, plan.order):
                            _scatter_flat(res[:plan.bucket_sizes[b]],
                                          plan.buckets[b], meta, res_list)
                        res_tree = jax.tree.unflatten(treedef, res_list)
                        # telescoping EF: (g+e) - (g+gain*e) + hop residual
                        ef_new = jax.tree.map(
                            lambda e, r: (1.0 - gain) * e
                            + r.astype(jnp.float32), ef_l, res_tree)
                        ef_out = jax.tree.map(expand3, ef_new)
                    else:
                        ef_out = ef
            else:
                if comp.method != "none":
                    grads, ef_new, _wb = comp.roundtrip(grads, ef_l, key)
                    ef_out = (jax.tree.map(expand3, ef_new)
                              if ef_new is not None else ef)
                else:
                    ef_out = ef
                if cfg.zero == 0:
                    avg = reduce0(grads)
                    p_out, opt_new = opt_step0(p_local, avg, opt)
                else:
                    g_leaves = jax.tree.leaves(grads)
                    g_buckets = [flatten_bucket(g_leaves, plan.buckets[b])
                                 for b in plan.order]
                    p_buckets, opt_l = zero_buckets(pstate, opt, p_local)
                    new_buckets, opt_new = zero_update(p_buckets, g_buckets,
                                                       opt_l)
                    p_out, opt_new = zero_unpack(new_buckets, opt_new, opt)
            return p_out, opt_new, ef_out, loss[None], expand3(sent)

        params_spec, opt_spec, ef_spec = self._state_specs()
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(params_spec, opt_spec, ef_spec, P(DATA), P()),
            out_specs=(params_spec, opt_spec, ef_spec, P(DATA),
                       P(DATA, STAGE, TENSOR)),
            check_vma=False)
        return jax.jit(fn), act_cell

    def _modeled_event_bytes(self) -> int:
        """The compressor's analytic per-device push accounting over the
        local block structure — recomputed from the plan (host side),
        never captured from a step-0 trace."""
        if self._dev_event_bytes is None:
            self._dev_event_bytes = modeled_event_bytes(
                self.cfg.compressor, self.plan.local_example)
        return self._dev_event_bytes

    def _step_bsp(self, st, batches, t):
        cfg = self.cfg
        if self._step_fn is None:
            self._step_fn, self._act_cell = self._build_step()
            self._measured_tx = self._measured_step_tx_bytes()
        D = cfg.mesh.data
        per = [batches(t, w) for w in range(D)]
        if self.staged and cfg.mesh.stage > 1:
            bsz = int(np.shape(self.model.inputs(per[0]))[0])
            if bsz % self.plan.micro:
                raise ValueError(
                    f"batch size {bsz} not divisible into "
                    f"{self.plan.micro} micro-batches")
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        st["rng"], sub = jax.random.split(st["rng"])
        rec = get_recorder()
        if rec.enabled:
            with rec.span("compute", pid="train", tid="loop", cat="train",
                          clock=("train_step", t), mesh=cfg.mesh.spec(),
                          zero=cfg.zero, fused=True):
                params, opt, ef, losses, sent = self._step_fn(
                    st["params"], st["opt"], st["ef"], batch, sub)
                jax.block_until_ready(losses)
        else:
            params, opt, ef, losses, sent = self._step_fn(
                st["params"], st["opt"], st["ef"], batch, sub)
        st.update(params=params, opt=opt, ef=ef)
        if rec.enabled:
            if D > 1 and cfg.zero == 0:
                # z0 runs the CommPlan schedule on the data axis; z1-3
                # exchange through the ZeRO shard path instead, which the
                # per-step byte accounting (not bucket spans) covers
                if self._trace_plan is None:
                    self._trace_plan = self._comm_plan()
                self._trace_plan.emit_trace(rec, arch="allreduce",
                                            clock=("train_step", t))
            if self.staged and cfg.mesh.stage > 1:
                emit_pipeline_trace(rec, cfg.mesh.stage, self.plan.micro,
                                    schedule=cfg.schedule,
                                    interleave=self._v,
                                    clock=("train_step", t))
        if cfg.wire == "measured":
            # per bucket from the plan, every step: static plane bytes of
            # the data-axis schedule on every device + dgc's traced
            # per-step sparse payload
            st["wire"] += self._measured_tx * cfg.mesh.size \
                + SPARSE_ELEM_BYTES * int(np.sum(np.asarray(sent)))
        else:
            st["wire"] += self._modeled_event_bytes() * cfg.mesh.size
        if rec.enabled:
            rec.counter("wire_bytes", {"cumulative": int(st["wire"])},
                        pid="train", cat="comm", clock=("train_step", t))
        ev = dict(step=t, loss=float(np.mean(np.asarray(losses))),
                  max_staleness=0)
        return st, [ev]

    def step(self, st, batches: Callable[[int, int], Any], t: int):
        sync = self.cfg.sync
        if sync == "bsp":
            st, ev = self._step_bsp(st, batches, t)
        elif sync == "ssp":
            st, ev = self._step_async(st, batches, t, self.cfg.staleness)
        elif sync == "asp":
            st, ev = self._step_async(st, batches, t, None)
        else:
            st, ev = self._step_sma(st, batches, t)
        self._wire_total = st["wire"]
        return st, ev

    def finalize(self, st):
        if self.cfg.sync == "sma":
            return jax.tree.map(lambda x: jnp.mean(x, axis=0),
                                st["replicas"])
        if self.cfg.zero == 3:
            full = self._materialize_params(
                [np.asarray(x) for x in st["params"]])
            return self._permute_stacked(full, inverse=True)
        return self._permute_stacked(st["params"], inverse=True)

    def wire_bytes(self) -> int:
        return self._wire_total

    # -------------------------------------- async / sma over the data axis
    def effective_periods(self) -> Tuple[int, ...]:
        """Per data-slot speed schedule with straggler slowdowns folded
        in — the same rule as ``ElasticWorkerSet.effective_periods``."""
        return tuple(max(1, int(round(p * s)))
                     for p, s in zip(self.periods, self.slowdowns))

    def _slice_blocks(self, pl, t_idx):
        """This tensor rank's (stage=1) parameter blocks of the full
        stacked leaves — dynamic role-dim slices per the mesh plan."""
        plan, T = self.plan, self.cfg.mesh.tensor
        leaves = jax.tree.leaves(pl)
        locals_ = jax.tree.leaves(plan.local_example)
        out = []
        for leaf, t_dim, lo in zip(leaves, plan.tensor_dims, locals_):
            if T > 1 and t_dim is not None:
                m = lo.shape[t_dim]
                starts = [0] * leaf.ndim
                starts[t_dim] = t_idx * m
                leaf = lax.dynamic_slice(leaf, starts, lo.shape)
            out.append(leaf)
        return jax.tree.unflatten(self._leaf_meta[0], out)

    def _slot_loss_and_grads(self, pulled, batch):
        """Per data-slot loss/grads of the staged model at stage=1:
        tensor-sharded compute inside the slot, full gradients assembled
        with a tensor-axis psum (outside AD)."""
        model, T = self.model, self.cfg.mesh.tensor
        t_idx = lax.axis_index(TENSOR)
        chunk = jax.tree.leaves(self.plan.local_example)[0].shape[0]
        tensor_axis = TENSOR if T > 1 else None

        def lloss(pl):
            blocks = self._slice_blocks(pl, t_idx)
            xx = model.inputs(batch)
            for j in range(chunk):
                xx = model.stage_fn(
                    jax.tree.map(lambda l: l[j], blocks), xx,
                    tensor_axis=tensor_axis)
            return model.readout(xx, batch)

        loss, g = jax.value_and_grad(lloss)(pulled)
        if T > 1:
            # each rank's cotangent covers only its role-dim block; the
            # psum assembles the full gradient, replicated over tensor
            g = jax.tree.map(lambda x: lax.psum(x, TENSOR), g)
        return loss, g

    def _build_async_fns(self):
        cfg = self.cfg
        comp = cfg.compressor

        def grad_body(pulled, ef, batch, key, fire):
            pulled = jax.tree.map(lambda x: x[0], pulled)
            batch = jax.tree.map(lambda x: x[0], batch)
            key = key[0]
            fire = fire[0]
            loss, g = self._slot_loss_and_grads(pulled, batch)
            if comp.method != "none":
                ef_w = (jax.tree.map(lambda x: x[0], ef)
                        if ef is not None else None)
                g, ef_new, _wb = comp.roundtrip(g, ef_w, key)
                if ef_new is not None:
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

        ef_spec = P(DATA) if self._ef_active else P()
        return jax.jit(jax.shard_map(
            grad_body, mesh=self.mesh,
            in_specs=(P(DATA), ef_spec, P(DATA), P(DATA), P(DATA)),
            out_specs=(P(DATA), P(DATA), ef_spec),
            check_vma=False))

    def _full_param_event_bytes(self, params_like) -> int:
        """Per-event modeled bytes of one slot's push: the compressor's
        accounting over the FULL stacked leaves — exactly what the
        simulator reports for the same spec, so async hybrid wire
        accounting cross-validates."""
        return modeled_event_bytes(self.cfg.compressor, params_like)

    def _step_async(self, st, batches, t, bound: Optional[int]):
        cfg = self.cfg
        if self._async_fns is None:
            self._async_fns = self._build_async_fns()
            self._event_wire = self._full_param_event_bytes(st["params"])
        return async_replay_step(
            st, batches, t, bound, K=cfg.mesh.data,
            compressor=cfg.compressor, grad_fn=self._async_fns,
            apply_fn=self._apply, ps_apply=None, lr=cfg.lr,
            event_wire=self._event_wire,
            eff_periods=self.effective_periods())

    def _build_sma(self):
        cfg = self.cfg

        def sma_body(replicas, batch):
            r = jax.tree.map(lambda x: x[0], replicas)
            batch = jax.tree.map(lambda x: x[0], batch)
            loss, g = self._slot_loss_and_grads(r, batch)
            center = jax.tree.map(lambda x: lax.pmean(x, DATA), r)
            mu = cfg.sma_mu
            new_r = jax.tree.map(
                lambda rr, zz, gg: rr - cfg.lr * gg - mu * (rr - zz),
                r, center, g)
            return (jax.tree.map(lambda x: x[None], new_r), loss[None])

        return jax.jit(jax.shard_map(
            sma_body, mesh=self.mesh,
            in_specs=(P(DATA), P(DATA)),
            out_specs=(P(DATA), P(DATA)),
            check_vma=False))

    def _step_sma(self, st, batches, t):
        cfg = self.cfg
        D = cfg.mesh.data
        if self._sma_fn is None:
            self._sma_fn = self._build_sma()
            self._event_wire = self._full_param_event_bytes(
                jax.tree.map(lambda x: x[0], st["replicas"]))
        per = [batches(t, w) for w in range(D)]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        st["replicas"], losses = self._sma_fn(st["replicas"], batch)
        st["wire"] += self._event_wire * D
        ev = dict(step=t, loss=float(np.mean(np.asarray(losses))),
                  max_staleness=0)
        return st, [ev]

    # ------------------------------------------------------------- metrics
    def per_device_state_bytes(self, st) -> Dict[str, int]:
        """Measured persistent bytes per device, from the actual state
        arrays divided by their sharding factor — what docs/hybrid.md's
        memory math predicts and the ZeRO acceptance test asserts on."""
        cfg = self.cfg
        D, T, S = cfg.mesh.data, cfg.mesh.tensor, cfg.mesh.stage
        stacked_div = (S * T) if self.staged else 1
        shard_div = D * S * T
        out = {"params": 0, "opt": 0, "ef": 0}
        if cfg.sync == "sma":
            out["params"] = sum(np.asarray(x).nbytes // (D * stacked_div)
                                for x in jax.tree.leaves(st["replicas"]))
            out["total"] = out["params"]
            return out
        if cfg.zero == 3:
            out["params"] = sum(np.asarray(x).nbytes // shard_div
                                for x in st["params"])
        else:
            out["params"] = sum(np.asarray(x).nbytes // stacked_div
                                for x in jax.tree.leaves(st["params"]))
        if st["opt"] is not None:
            for k in ("m", "v"):
                leaves = jax.tree.leaves(st["opt"][k])
                div = stacked_div if cfg.zero == 0 else shard_div
                out["opt"] += sum(np.asarray(x).nbytes // div
                                  for x in leaves)
            out["opt"] += 4
        if st["ef"] is not None:
            out["ef"] = sum(np.asarray(x).nbytes // shard_div
                            for x in jax.tree.leaves(st["ef"]))
        out["total"] = out["params"] + out["opt"]
        return out

    def extra_metrics(self) -> Dict[str, Any]:
        cfg, plan = self.cfg, self.plan
        m: Dict[str, Any] = dict(
            mesh=cfg.mesh.spec(), zero=cfg.zero, optimizer=cfg.optimizer,
            wire_mode=cfg.wire)
        if cfg.schedule != "gpipe":
            m["schedule"] = cfg.schedule
            m["interleave"] = self._v
        if cfg.precision != "fp32":
            m["precision"] = cfg.precision
        if cfg.moments != "float32":
            m["moments"] = cfg.moments
        if plan is not None and cfg.sync == "bsp":
            m["modeled_data_bytes_per_dev"] = wire_bytes_per_device(
                plan, cfg.zero, grad_bytes=self._modeled_event_bytes())
            m["analytic_state_bytes"] = state_bytes_per_device(
                plan, cfg.zero, cfg.optimizer, cfg.moments)
            if self._measured_tx is not None:
                m["measured_step_tx_bytes"] = self._measured_tx
            if self._act_cell and cfg.mesh.stage > 1:
                if cfg.schedule == "1f1b":
                    ticks = onefb_ticks(cfg.mesh.stage, plan.micro, self._v)
                else:
                    ticks = gpipe_ticks(cfg.mesh.stage, plan.micro)
                m["modeled_pipeline_bytes_per_dev"] = \
                    self._act_cell[0] * ticks
                if cfg.mesh.tensor > 1:
                    t = cfg.mesh.tensor
                    m["modeled_tensor_bytes_per_dev"] = int(
                        self._act_cell[0] * ticks * 2 * (t - 1) / t)
        return m

    # --------------------------------------------------- elastic interface
    def set_slowdown(self, worker: int, factor: float):
        """Record a straggler event.  Plan worker ids are flat device
        indices; a device's slowdown is recorded against its data slot
        (devices are data-major, so slot = id // (t*s)).  The hybrid step
        is a single fused BSP program — there is no backup-drop path to
        feed — so the record only affects reshard bookkeeping."""
        ts = self.cfg.mesh.tensor * self.cfg.mesh.stage
        slot = worker // ts
        if not 0 <= slot < self.cfg.mesh.data:
            raise ValueError(f"worker {worker} out of range for mesh "
                             f"{self.cfg.mesh.spec()}")
        self.slowdowns[slot] = factor

    def crash_plan(self, worker: int) -> Tuple[int, Tuple[int, ...]]:
        """What losing device ``worker`` means for this mesh: its whole
        tensor × stage block (the model-parallel replica of one data
        slot) goes with it, so the run reshards to one fewer data
        replica.  The elastic trainer consults this instead of assuming
        flat worker = device - 1 semantics."""
        cfg = self.cfg
        if not 0 <= worker < cfg.mesh.size:
            raise ValueError(f"worker {worker} out of range for mesh "
                             f"{cfg.mesh.spec()}")
        ts = cfg.mesh.tensor * cfg.mesh.stage
        if cfg.mesh.data <= 1:
            raise ValueError(
                f"mesh {cfg.mesh.spec()} has a single data replica; "
                "losing a device leaves nothing to reshard to")
        return cfg.mesh.size - ts, (worker // ts,)

    def reshard(self, st, new_workers: int, step: int = 0,
                lost: Tuple[int, ...] = ()):
        """Resize the mesh to ``new_workers`` total devices by rebuilding
        the *data* axis (tensor × stage geometry is a property of the
        model and survives).  ZeRO shards are re-cut over the new data
        axis; survivor data slots keep their EF residuals."""
        cfg, plan = self.cfg, self.plan
        if cfg.sync != "bsp":
            raise ValueError(
                f"sync={cfg.sync!r} hybrid cells do not reshard yet "
                "(async/sma over a mesh is a fixed-geometry run)")
        ts = cfg.mesh.tensor * cfg.mesh.stage
        if new_workers < ts or new_workers % ts:
            raise ValueError(
                f"resize to {new_workers} devices does not factor over the "
                f"tensor*stage block of {ts} (mesh {cfg.mesh.spec()}); "
                "hybrid meshes resize along the data axis only")
        new_d = new_workers // ts
        if new_workers > len(self._devs):
            raise ValueError(
                f"resize to {new_workers} devices: have {len(self._devs)}")
        bad = [w for w in lost if w < 0 or w >= cfg.mesh.data]
        if bad:
            raise ValueError(f"lost data slots {bad} out of range for "
                             f"data axis {cfg.mesh.data}")
        survivors = [w for w in range(cfg.mesh.data) if w not in set(lost)]
        slots = survivors[:new_d]
        grown = new_d - len(slots)
        st = {k: (jax.device_get(v) if k not in ("wire",) else v)
              for k, v in st.items()}
        # re-cut the flat data-axis shards (params for z3, moments for z1+)
        old_plan = plan

        def recut(arrs: List[np.ndarray]) -> List[np.ndarray]:
            out = []
            for arr, b in zip(arrs, old_plan.order):
                arr = np.asarray(arr)
                n_b = old_plan.bucket_sizes[b]
                m_new = -(-n_b // new_d)
                _, S, T, _ = arr.shape
                new = np.zeros((new_d, S, T, m_new), arr.dtype)
                for si in range(S):
                    for ti in range(T):
                        flat = arr[:, si, ti, :].reshape(-1)[:n_b]
                        new[:, si, ti, :] = np.pad(
                            flat, (0, new_d * m_new - n_b)).reshape(
                                new_d, m_new)
                out.append(new)
            return out

        if cfg.zero == 3:
            st["params"] = recut(st["params"])
        if st["opt"] is not None and cfg.zero >= 1:
            st["opt"] = {"m": recut(st["opt"]["m"]),
                         "v": recut(st["opt"]["v"]), "t": st["opt"]["t"]}
        if st["ef"] is not None:
            def remap_rows(x):
                x = np.asarray(x)
                rows = ([x[s] for s in slots]
                        + [np.zeros_like(x[0])] * grown)
                return np.stack(rows)
            st["ef"] = jax.tree.map(remap_rows, st["ef"])
        new_mesh = MeshSpec(new_d, cfg.mesh.tensor, cfg.mesh.stage)
        self.cfg = cfg = dataclasses.replace(cfg, mesh=new_mesh)
        self.mesh = make_hybrid_mesh(self._devs, new_d, cfg.mesh.tensor,
                                     cfg.mesh.stage)
        self.slowdowns = [self.slowdowns[s] for s in slots] + [1.0] * grown
        # the bucket identity is a function of the local block structure
        # and survives; only the per-rank shard length changes
        self.plan = dataclasses.replace(
            old_plan, mesh=new_mesh,
            shard_sizes=[-(-n // new_d) for n in old_plan.bucket_sizes])
        self.periods = tuple(default_periods(new_d))
        self._step_fn, self._async_fns, self._sma_fn = None, None, None
        self._act_cell = []
        self._dev_event_bytes, self._measured_tx = None, None
        self._trace_plan = None
        return st

    def export_state(self, st) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        cfg = self.cfg
        if cfg.sync != "bsp":
            raise ValueError(
                f"sync={cfg.sync!r} hybrid cells do not snapshot yet; "
                "use the flat DeviceEngine (trivial mesh) for elastic "
                "async runs")
        arrays = {"params": st["params"], "opt": st["opt"], "ef": st["ef"],
                  "rng": st["rng"]}
        meta = dict(backend="hybrid", mesh=cfg.mesh.spec(), zero=cfg.zero,
                    optimizer=cfg.optimizer, num_workers=cfg.mesh.size,
                    wire=int(st["wire"]), slowdowns=list(self.slowdowns),
                    schedule=cfg.schedule, interleave=self._v,
                    precision=cfg.precision, moments=cfg.moments)
        return arrays, meta

    def import_state(self, arrays: Dict[str, Any], meta: Dict[str, Any]):
        cfg = self.cfg
        if meta["num_workers"] != cfg.mesh.size:
            raise ValueError(
                f"snapshot has {meta['num_workers']} devices, engine has "
                f"{cfg.mesh.size}; reshard the engine first")
        if meta["mesh"] != cfg.mesh.spec() or meta["zero"] != cfg.zero \
                or meta["optimizer"] != cfg.optimizer:
            raise ValueError(
                f"snapshot geometry {meta['mesh']}/z{meta['zero']}/"
                f"{meta['optimizer']} does not match engine "
                f"{cfg.mesh.spec()}/z{cfg.zero}/{cfg.optimizer}")
        # schedule/precision change the on-disk layout (virtual-stage row
        # order, moment dtype); pre-existing snapshots default to gpipe/fp32
        snap = (meta.get("schedule", "gpipe"), meta.get("interleave", 1),
                meta.get("precision", "fp32"), meta.get("moments", "float32"))
        mine = (cfg.schedule, self._v, cfg.precision, cfg.moments)
        if snap != mine:
            raise ValueError(
                f"snapshot schedule/precision {snap} does not match "
                f"engine {mine}")
        self.slowdowns = [float(s) for s in meta["slowdowns"]]
        st = dict(params=arrays["params"], opt=arrays["opt"],
                  ef=arrays["ef"], rng=jnp.asarray(arrays["rng"]),
                  wire=int(meta["wire"]))
        self._wire_total = st["wire"]
        return st

    # ------------------------------------------------------------------ run
    def run(self, params, batches: Callable[[int, int], Any], steps: int):
        st = self.init(params)
        hist: List[dict] = []
        rec = get_recorder()
        for t in range(steps):
            # same step spans train_loop emits for the flat engines, so
            # hybrid traces feed obs.analyze.step_attribution too
            if rec.enabled:
                with rec.span("step", pid="train", tid="loop", cat="train",
                              clock=("train_step", t), step=t):
                    st, ev = self.step(st, batches, t)
            else:
                st, ev = self.step(st, batches, t)
            hist.extend(ev)
        return self.finalize(st), hist, st["wire"]
