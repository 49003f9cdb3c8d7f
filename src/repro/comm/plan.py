"""``CommPlan``: the one communication plan every gradient exchange in
the repo executes.

A plan is built once per (parameter structure × worker axis) and owns:

  * the fused-bucket layout (backward-order fusion into ~``bucket_mb``
    buckets) and the TicTac/random/layer transfer **issue order** — the
    ``core/comm_scheduler`` logic, now behind one object shared by the
    executed schedule and the analytic timeline so they cannot drift;
  * the **topology** schedule (ring/tree/butterfly/…) each bucket is
    reduced with, via ``repro.comm.transport``;
  * the **codec** (``repro.comm.codecs``) and the ``wire`` mode:

      wire="modeled"   compression happens per worker *before* the
                       exchange (``Compressor.roundtrip``) and the
                       schedule moves full-precision payloads; wire bytes
                       are the compressor's analytic accounting (what the
                       simulator reports — the two backends stay
                       cross-validatable).
      wire="measured"  the schedule itself carries encoded planes
                       (encode → ppermute → decode-accumulate, per-worker
                       EF for the lossy hops) and wire bytes are counted
                       from those planes: shape-static parts at plan time
                       (``measured_step_tx_bytes``), dgc's data-dependent
                       sparse elements per step from the traced
                       ``sent_elems`` the exchange returns.

  ``bsp/*/none`` is identical under both modes: the exact codec routes
  through the legacy full-precision schedules, bit-for-bit.

``DeviceEngine`` (train/data_parallel.py) and the hybrid mesh's data axis
(parallel/engine.py, z0–z3) both consume this object — one planner, one
issue order, one accounting surface.  See docs/comm.md.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import SPARSE_ELEM_BYTES, SegmentCodec, codec_for
from repro.comm.transport import (SCHEDULES, compressed_allreduce,
                                  compressed_allreduce_ef,
                                  compressed_reduce_scatter,
                                  compressed_reduce_scatter_ef,
                                  fp32_schedule_bytes, pad_for_schedule,
                                  schedule_tx_bytes)
from repro.core.comm_scheduler import (LayerCost, LinkModel, bucketize,
                                       random_order, schedule_no_overlap,
                                       schedule_overlap, tictac_order)
from repro.core.compression import Compressor
from repro.core.parameter_server import all_gather_flat, shard_of_flat

WIRE_MODES = ("modeled", "measured")


def bucket_order(n: int, order: str, layers: Sequence[LayerCost],
                 seed: int) -> List[int]:
    if order == "tictac":
        return tictac_order(layers)
    if order == "random":
        return random_order(layers, seed)
    if order == "layer":
        return list(range(n))
    raise ValueError(order)


def plan_buckets(params_example, bucket_mb: float, order: str,
                 back_s_per_byte: float, seed: int
                 ) -> Tuple[List[List[int]], List[int], List[LayerCost]]:
    """Fuse gradient leaves (backward = reverse-pytree order) into buckets
    of ~bucket_mb and choose the transfer issue order.  This single plan
    is shared by the executed schedule (every architecture and mesh) and
    the analytic timeline model."""
    leaves = jax.tree.leaves(params_example)
    layers = [LayerCost(f"g{i}", back_s_per_byte * x.size * 4, x.size * 4)
              for i, x in enumerate(leaves)]
    fused = bucketize(layers, bucket_mb * 1e6)
    buckets = [[int(nm[1:]) for nm in b.name.split("+")] for b in fused]
    order_idx = bucket_order(len(fused), order, fused, seed)
    return buckets, order_idx, fused


def modeled_event_bytes(compressor: Compressor, params_example) -> int:
    """The compressor's analytic per-push accounting over
    ``params_example`` (what the simulator reports) — the single
    implementation every engine's modeled wire increment uses.  The
    count is static in the shapes, so the roundtrip is only traced:
    nothing is allocated or run on the device."""
    counted = []

    def trace(zeros):
        state = compressor.init_state(zeros)
        counted.append(compressor.roundtrip(zeros, state,
                                            jax.random.PRNGKey(0))[2])

    jax.eval_shape(trace, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.float32),
        params_example))
    return int(counted[0])


def scatter_flat(flat, idxs, leaf_shapes, out, dtype=None):
    """Split a fused bucket vector back into its leaves (into ``out``)."""
    off = 0
    for i in idxs:
        shape, leaf_dtype = leaf_shapes[i]
        size = int(np.prod(shape)) if shape else 1
        out[i] = flat[off:off + size].reshape(shape).astype(
            dtype or leaf_dtype)
        off += size
    return out


@dataclasses.dataclass
class CommPlan:
    """One executable exchange plan (see the module docstring)."""
    axis: str
    n: int                           # workers on the axis
    topology: str
    compressor: Compressor
    wire: str                        # modeled | measured
    buckets: List[List[int]]
    order: List[int]                 # issue order over bucket indices
    fused: List[LayerCost]
    treedef: Any
    leaf_shapes: List[Tuple[Tuple[int, ...], Any]]
    link: LinkModel = LinkModel()
    # dtype gradients travel in on the UNCOMPRESSED exchange: "bfloat16"
    # halves the wire words of the exact schedules (codec payloads are
    # already quantized planes and are unaffected; parameter all-gathers
    # always travel exact fp32)
    reduce_dtype: str = "float32"

    @classmethod
    def plan(cls, params_example, *, axis: str, n: int,
             topology: str = "ring",
             compressor: Compressor = Compressor("none"),
             wire: str = "modeled", bucket_mb: float = 4.0,
             order: str = "tictac", back_s_per_byte: float = 2e-12,
             seed: int = 0, link: LinkModel = LinkModel(),
             reduce_dtype: str = "float32") -> "CommPlan":
        if wire not in WIRE_MODES:
            raise ValueError(f"wire={wire!r} (want {WIRE_MODES})")
        if topology not in SCHEDULES:
            raise ValueError(f"unknown topology {topology!r}")
        buckets, order_idx, fused = plan_buckets(
            params_example, bucket_mb, order, back_s_per_byte, seed)
        treedef = jax.tree.structure(params_example)
        shapes = [(tuple(x.shape), x.dtype)
                  for x in jax.tree.leaves(params_example)]
        return cls(axis=axis, n=n, topology=topology, compressor=compressor,
                   wire=wire, buckets=buckets, order=order_idx, fused=fused,
                   treedef=treedef, leaf_shapes=shapes, link=link,
                   reduce_dtype=reduce_dtype)

    # ------------------------------------------------------------ derived
    @property
    def codec(self) -> SegmentCodec:
        return codec_for(self.compressor)

    @property
    def in_schedule(self) -> bool:
        """True when payloads are encoded inside the schedule (measured
        wire mode with a lossy method)."""
        return self.wire == "measured" and self.compressor.method != "none"

    @property
    def word_bytes(self) -> int:
        """Bytes per word of the uncompressed gradient exchange (4 fp32,
        2 when ``reduce_dtype="bfloat16"``)."""
        return int(jnp.dtype(self.reduce_dtype).itemsize)

    def _exact_tx(self, codec, length: int) -> float:
        """``static_tx_bytes`` with the reduce-dtype word width applied to
        the exact codec (NoneCodec counts 4 B/word; a bf16 exchange moves
        2 B/word).  Lossy codec planes are unaffected."""
        base = codec.static_tx_bytes(length)
        if codec.exact and self.word_bytes != 4:
            return base * self.word_bytes / 4
        return base

    def bucket_len(self, b: int) -> int:
        return sum(int(np.prod(s) or 1) for s, _ in
                   ((self.leaf_shapes[i]) for i in self.buckets[b]))

    def _cat(self, leaves, b: int):
        return jnp.concatenate(
            [leaves[i].astype(jnp.float32).reshape(-1)
             for i in self.buckets[b]])

    # ------------------------------------------------- exact (fp32) ops
    def reduce_grads(self, grads):
        """Full-precision bucketed mean-allreduce in plan issue order —
        the legacy exact path, bit-identical to the pre-refactor
        ``make_bucketed_allreduce``.  Call inside ``shard_map``."""
        reduce_leaf = SCHEDULES[self.topology]
        leaves = jax.tree.leaves(grads)
        n = jax.lax.axis_size(self.axis)
        rdt = jnp.dtype(self.reduce_dtype)
        out: List[Any] = [None] * len(leaves)
        for b in self.order:                   # the executed schedule
            flat = self._cat(leaves, b)
            if rdt != jnp.float32:
                flat = flat.astype(rdt)        # the bf16 wire words
            red = reduce_leaf(flat, self.axis).astype(jnp.float32) / n
            scatter_flat(red, self.buckets[b], self.leaf_shapes, out)
        return jax.tree.unflatten(self.treedef, out)

    # ---------------------------------------- codec-in-schedule exchange
    def exchange(self, grads, ef, key):
        """Mean-allreduce with encoded payloads inside the topology
        schedule.  ``ef`` is the worker's error-feedback pytree (None for
        the stateless quantizers), ``key`` drives the stochastic codecs.
        Returns ``(mean_grads, new_ef, sent_elems)`` — fold ``sent_elems``
        (a traced int32) into the step outputs for dgc's measured bytes.
        Call inside ``shard_map``."""
        comp, codec = self.compressor, self.codec
        gain = comp.ef_gain if comp.method == "onebit" else 1.0
        leaves = jax.tree.leaves(grads)
        ef_leaves = jax.tree.leaves(ef) if ef is not None else None
        out: List[Any] = [None] * len(leaves)
        new_ef: List[Any] = [None] * len(leaves)
        sent = jnp.zeros((), jnp.int32)
        for b in self.order:
            L = self.bucket_len(b)
            P = pad_for_schedule(L, self.n)
            g_flat = jnp.pad(self._cat(leaves, b), (0, P - L))
            key, sub = jax.random.split(key)
            if ef_leaves is not None:
                # hand the residual bucket down: the transport applies the
                # (over-relaxed) compensation, runs fused encode+EF hops,
                # and returns the telescoped next-step residual
                e_flat = jnp.pad(self._cat(ef_leaves, b), (0, P - L))
                red, new_e, nz = compressed_allreduce_ef(
                    g_flat, e_flat, self.axis, self.topology, codec, sub,
                    gain=gain)
                scatter_flat(new_e[:L], self.buckets[b],
                             self.leaf_shapes, new_ef, dtype=jnp.float32)
            else:
                red, _, nz = compressed_allreduce(
                    g_flat, self.axis, self.topology, codec, sub)
            sent = sent + nz
            scatter_flat(red[:L] / self.n, self.buckets[b],
                         self.leaf_shapes, out)
        out_tree = jax.tree.unflatten(self.treedef, out)
        ef_tree = (jax.tree.unflatten(self.treedef, new_ef)
                   if ef_leaves is not None else None)
        return out_tree, ef_tree, sent

    def ps_exchange(self, params, grads, ef, key, lr: float):
        """The centralized counterpart: compressed ring reduce-scatter of
        each gradient bucket (the PS push), SGD on my 1/n shard (the
        server work), full-precision all-gather of the updated shard (the
        pull — parameters travel exact).  Returns ``(new_params, new_ef,
        sent_elems)``.  Call inside ``shard_map``."""
        comp, codec = self.compressor, self.codec
        gain = comp.ef_gain if comp.method == "onebit" else 1.0
        p_leaves = jax.tree.leaves(params)
        g_leaves = jax.tree.leaves(grads)
        ef_leaves = jax.tree.leaves(ef) if ef is not None else None
        out: List[Any] = [None] * len(p_leaves)
        new_ef: List[Any] = [None] * len(p_leaves)
        sent = jnp.zeros((), jnp.int32)
        for b in self.order:
            L = self.bucket_len(b)
            P = pad_for_schedule(L, self.n)
            g_flat = jnp.pad(self._cat(g_leaves, b), (0, P - L))
            key, sub = jax.random.split(key)
            if ef_leaves is not None:
                e_flat = jnp.pad(self._cat(ef_leaves, b), (0, P - L))
                g_shard, new_e, nz = compressed_reduce_scatter_ef(
                    g_flat, e_flat, self.axis, codec, sub, gain=gain)
                scatter_flat(new_e[:L], self.buckets[b],
                             self.leaf_shapes, new_ef, dtype=jnp.float32)
            else:
                g_shard, _, nz = compressed_reduce_scatter(
                    g_flat, self.axis, codec, sub)
            sent = sent + nz
            p_flat = jnp.pad(self._cat(p_leaves, b), (0, P - L))
            p_shard = shard_of_flat(p_flat, self.axis)
            new_shard = p_shard - lr * (g_shard / self.n)
            full = all_gather_flat(new_shard, self.axis, L)
            scatter_flat(full, self.buckets[b], self.leaf_shapes, out)
        out_tree = jax.tree.unflatten(self.treedef, out)
        ef_tree = (jax.tree.unflatten(self.treedef, new_ef)
                   if ef_leaves is not None else None)
        return out_tree, ef_tree, sent

    # -------------------------------------------------------------- trace
    def hop_model(self, b: int, arch: str = "allreduce"
                  ) -> List[Tuple[str, float]]:
        """The per-hop wire model for one exchange of bucket ``b``: a list
        of (hop kind, mean per-worker tx bytes) mirroring exactly the
        aggregate ``schedule_tx_bytes`` / ``measured_step_tx_bytes``
        accounting, so the sum over hops equals the per-bucket measured
        bytes (shape-static part; dgc adds its traced sparse payload at
        the step level)."""
        import math
        codec = self.codec if self.in_schedule else codec_for(
            Compressor("none"))
        n = self.n
        if n == 1:
            return []
        L = self.bucket_len(b)
        P = pad_for_schedule(L, n)
        m = P // n
        e = lambda length: self._exact_tx(codec, length)
        if arch == "ps":
            # gradient RS encoded, parameter AG exact fp32 (docs/comm.md)
            return ([("rs", float(e(m)))] * (n - 1)
                    + [("ag", float(4 * m))] * (n - 1))
        topo = self.topology
        if topo in ("ring", "psum"):
            return ([("rs", float(e(m)))] * (n - 1)
                    + [("ag", float(e(m)))] * (n - 1))
        if topo == "butterfly":
            if codec.exact:
                return [("exchange", float(e(P)))] * int(math.log2(n))
            rs = [("rs", float(e((n >> (k + 1)) * m)))
                  for k in range(int(math.log2(n)))]
            return rs + [("ag", float(e(m)))] * (n - 1)
        if topo == "tree":
            half = (n - 1) / n * e(P)
            return [("reduce", float(half)), ("broadcast", float(half))]
        if topo == "fully_connected":
            return [("send", float(e(P)))] * (n - 1)
        raise ValueError(topo)

    def emit_trace(self, rec, *, arch: str = "allreduce",
                   pid: str = "train", tid: str = "loop",
                   clock=None) -> None:
        """Emit the exchange this plan just executed onto the trace
        timeline (docs/observability.md): an ``exchange`` span holding
        one span per fused bucket *in issue order*, each carrying its
        per-hop wire events.  The schedule runs inside jit, so these are
        the plan's own deterministic model of what executed — virtual
        clock only, byte-reproducible under fixed seeds."""
        if not rec.enabled:
            return
        comp = self.compressor
        # the modeled bounds the analyzer compares issue order against:
        # serial buckets (worst) vs TicTac-ordered overlap (best for
        # this plan) vs the order actually executed.  Rounded so traces
        # stay byte-stable (obs/analyze.overlap_efficiency).
        no_overlap_s = schedule_no_overlap(self.fused, self.link)
        tictac_s = schedule_overlap(self.fused, self.link,
                                    tictac_order(self.fused))
        issue_s = schedule_overlap(self.fused, self.link, self.order)
        rec.begin("exchange", pid=pid, tid=tid, cat="comm", clock=clock,
                  topology=self.topology, codec=comp.method,
                  backend=getattr(comp, "backend", "auto"),
                  wire_mode=self.wire, arch=arch,
                  n_buckets=len(self.buckets),
                  step_tx_bytes=self.measured_step_tx_bytes(arch),
                  modeled_no_overlap_us=round(no_overlap_s * 1e6, 3),
                  modeled_tictac_overlap_us=round(tictac_s * 1e6, 3),
                  modeled_issue_overlap_us=round(issue_s * 1e6, 3))
        for b in self.order:
            hops = self.hop_model(b, arch)
            rec.begin(f"bucket{b}", pid=pid, tid=tid, cat="comm",
                      elems=self.bucket_len(b),
                      padded=pad_for_schedule(self.bucket_len(b), self.n),
                      leaves=len(self.buckets[b]),
                      tx_bytes=int(sum(x for _, x in hops)))
            for h, (kind, nbytes) in enumerate(hops):
                # mean per-worker bytes can be fractional (tree halves);
                # keep the fraction so hop sums match the accounting
                rec.instant("hop", pid=pid, tid=tid, cat="comm",
                            hop=h, kind=kind, tx_bytes=round(nbytes, 3))
            rec.end(pid=pid, tid=tid)
        rec.end(pid=pid, tid=tid)

    # --------------------------------------------------------- accounting
    def modeled_timeline(self) -> Dict[str, float]:
        """Iteration-time projections for the exact bucket plan this
        engine executes — the no-overlap vs overlap comparison."""
        return {
            "no_overlap_s": schedule_no_overlap(self.fused, self.link),
            "overlap_s": schedule_overlap(self.fused, self.link,
                                          self.order),
            "n_buckets": len(self.fused),
        }

    def measured_step_tx_bytes(self, arch: str = "allreduce") -> int:
        """Shape-static measured bytes ONE worker puts on the wire per
        BSP step — recomputed per bucket from the plan (never cached from
        a step-0 trace).  For the exact codec this is the fp32 schedule;
        for ``ps`` the gradient RS is encoded and the parameter AG is
        fp32.  Add ``SPARSE_ELEM_BYTES * sent_elems`` for dgc."""
        codec = self.codec if self.in_schedule else codec_for(
            Compressor("none"))
        # bf16 reduce halves the exact codec's wire words (its accounting
        # is linear in length, so scaling the schedule total is exact);
        # lossy planes and the fp32 parameter all-gather are unaffected
        scale = (self.word_bytes / 4
                 if codec.exact and self.word_bytes != 4 else 1.0)
        total = 0.0
        for b in range(len(self.buckets)):
            L = self.bucket_len(b)
            P = pad_for_schedule(L, self.n)
            if arch == "ps":
                m = P // self.n
                rs = (self.n - 1) * codec.static_tx_bytes(m) * scale
                ag = (self.n - 1) * 4 * m          # params travel exact
                total += rs + ag
            else:
                total += schedule_tx_bytes(self.topology, self.n, P,
                                           codec) * scale
        return int(total)

    def measured_bytes(self, sent_elems: int) -> int:
        """Data-dependent measured bytes for ``sent_elems`` sparse
        elements (dgc's per-step payload)."""
        return int(sent_elems) * SPARSE_ELEM_BYTES

    def fp32_step_tx_bytes(self) -> int:
        """The full-precision schedule's per-worker tx bytes per step —
        the baseline compressed-payload ratios are quoted against."""
        total = 0.0
        for b in range(len(self.buckets)):
            P = pad_for_schedule(self.bucket_len(b), self.n)
            total += fp32_schedule_bytes(self.topology, self.n, P)
        return int(total)

    def modeled_event_bytes(self, params_example) -> int:
        """The compressor's analytic per-push accounting (what the
        simulator reports) — the ``wire="modeled"`` step increment."""
        return modeled_event_bytes(self.compressor, params_example)
