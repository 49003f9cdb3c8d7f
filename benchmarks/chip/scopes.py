"""Device time of the training step by the program's name scopes.

``load`` reads a traced run's ``.xplane.pb`` and keeps the operations of
the step programs (``jit_sharded_step``) that started inside the traced
window (the benchmark's ``bench.window`` annotation), each with its JAX
name stack and its **self time**: its interval less the operations
nested inside it (a layer scan's ``while`` contains its body's ops on
the same line).  An op without a name stack takes the stack of the op
it is nested in.

Each op's name stack is the ``op_name`` of its HLO instruction's
metadata (``jit(sharded_step)/transpose(jvp())/while/body/.../attention/
dot_general``).  A TPU v5e trace's op events carry only the instruction's
HLO text, without its metadata; the stack is looked up by instruction
name in the step program's HLO module, which the trace's
``/host:metadata`` plane holds as the stat ``Hlo Proto``.

``classify`` puts each op in one of four classes:

  optimizer  under the engine's ``optimizer`` scope;
  backward   under a ``transpose(...)`` transform: JAX names the ops of
             the backward pass so, inside any scope;
  forward    under ``jvp(...)`` (the differentiated loss's forward
             pass, scan bookkeeping included) or under a model scope;
  other      everything else, the engine's ``exchange`` scope among it.

The compact form (``save`` / ``read``) is what a test reads from a trace
recorded once on the chip, so every number is computed the same way on
both.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import re
from typing import Dict, List, Optional, Tuple

from chip import tracing

STEP = r"sharded_step"
MODEL_SCOPES = frozenset(("embed", "attention", "mlp", "norm", "head",
                          "loss"))
CLASSES = ("forward", "backward", "optimizer", "other")
_TRANSFORM = re.compile(r"^(?:\w+\()+|\)+$")


def scope_names(stack: str) -> List[str]:
    """The scopes of a name stack with JAX's transforms taken off:
    ``transpose(jvp(norm))`` -> ``norm``."""
    return [_TRANSFORM.sub("", part) for part in stack.split("/")]


def classify(stack: str) -> str:
    names = scope_names(stack)
    if "exchange" in names:
        return "other"
    if "optimizer" in names:
        return "optimizer"
    parts = stack.split("/")
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if any(p.startswith("jvp(") for p in parts) or \
            MODEL_SCOPES.intersection(names):
        return "forward"
    return "other"


def nesting(ops: List[Tuple[float, float]]):
    """For each (start, duration): its self time, the duration less the
    time of the ops nested directly inside it (an op that only overlaps
    an earlier one is not charged twice), and the index of the op it is
    nested in (-1 at the top)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [float(d) for _, d in ops]
    parent = [-1] * len(ops)
    stack: List[int] = []
    for i in order:
        s, d = ops[i]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = parent[i] = stack[-1]
            own[p] -= min(s + d, ops[p][0] + ops[p][1]) - s
        stack.append(i)
    return [max(0.0, x) for x in own], parent


@dataclasses.dataclass
class Scopes:
    window: Tuple[float, float]
    stacks: List[str]             # the distinct name stacks
    devices: List[dict]           # {"name", "steps", "ops": [[stack, self_ns]]}

    def per_step_ms(self, pred) -> Optional[float]:
        """Self time per step program of the ops whose name stack
        satisfies ``pred``, averaged over the devices; None where no op's
        does (a program without the scope)."""
        keep = [pred(s) for s in self.stacks]
        if not any(keep):
            return None
        vals = [sum(t for k, t in dev["ops"] if keep[k]) / dev["steps"]
                for dev in self.devices if dev["steps"]]
        return 1e-6 * sum(vals) / len(vals) if vals else None

    def class_ms(self, cls: str) -> Optional[float]:
        return self.per_step_ms(lambda s: classify(s) == cls)

    def scope_ms(self, scope: str) -> Optional[float]:
        return self.per_step_ms(lambda s: scope in scope_names(s))

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Scopes":
        return cls(tuple(d["window"]), d["stacks"], d["devices"])


# ------------------------------------------------------------- reading
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field, value) of a protobuf message's top level: an int for a
    varint, the bytes of a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield key >> 3, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def hlo_op_names(path: str, pattern: str = STEP) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata of the HLO modules whose
    name matches ``pattern``, from the ``Hlo Proto`` stats of the trace's
    ``/host:metadata`` plane (XSpace -> XPlane -> XEventMetadata -> XStat
    -> HloProto -> HloModuleProto -> HloComputationProto ->
    HloInstructionProto -> OpMetadata, read field by field)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    rx, out = re.compile(pattern), {}
    for fld, plane in _fields(space):
        if fld != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v) == b"/host:metadata"
                   for k, v in fields):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:          # map<int64, XStatMetadata>
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1)] = bytes(meta.get(2, b""))
        for k, entry in fields:
            if k != 4:          # map<int64, XEventMetadata>
                continue
            for k2, stat in _fields(dict(_fields(entry)).get(2, b"")):
                if k2 != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == b"Hlo Proto" and 6 in st:
                    _module_op_names(st[6], rx, out)
    return out


def _module_op_names(hlo_proto, rx, out) -> None:
    for k, module in _fields(hlo_proto):
        if k != 1:
            continue
        mod = list(_fields(module))
        name = next((bytes(v).decode() for k2, v in mod if k2 == 1), "")
        if not rx.search(name):
            continue
        for k2, comp in mod:
            if k2 != 3:
                continue
            for k3, inst in _fields(comp):
                if k3 != 2:
                    continue
                fi = dict(_fields(inst))
                meta = dict(_fields(fi.get(7, b"")))
                if 2 in meta:
                    out[bytes(fi.get(1, b"")).decode()] = \
                        bytes(meta[2]).decode()


@functools.lru_cache(maxsize=2)
def load(path: str) -> Scopes:
    """Read an ``.xplane.pb`` into the compact form (module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, devices = None, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise RuntimeError(f"trace {path} has no bench.window span")
    lo, hi = window
    rx, op_names = re.compile(STEP), hlo_op_names(path)
    stacks: Dict[str, int] = {}
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name
                and "SparseCore" not in plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if tracing.OPS_LINE not in lines or tracing.MODULES_LINE not in lines:
            continue
        progs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines[tracing.MODULES_LINE].events
                       if rx.search(e.name) and lo <= e.start_ns < hi)
        starts = [s for s, _ in progs]
        ops, names = [], []
        for e in lines[tracing.OPS_LINE].events:
            j = bisect.bisect_right(starts, e.start_ns) - 1
            if j < 0 or e.start_ns >= progs[j][1]:
                continue
            names.append(op_names.get(tracing.op_name(e.name).removesuffix(
                tracing.PALLAS), ""))
            ops.append((e.start_ns, e.duration_ns))
        own, parent = nesting(ops)
        # an op without a name stack (an async copy, a buffer the
        # compiler added) is part of the op it runs inside
        for i in sorted(range(len(ops)), key=lambda i: ops[i]):
            if not names[i] and parent[i] >= 0:
                names[i] = names[parent[i]]
        devices.append({"name": plane.name, "steps": len(progs), "ops": [
            [stacks.setdefault(n, len(stacks)), t]
            for n, t in zip(names, own)]})
    return Scopes(window, list(stacks), devices)


def of(run) -> Optional[Scopes]:
    """The reduction of ``run``'s own trace: its ``.xplane.pb``, or the
    compact form where the path names a ``.json``."""
    path = run.ctx.tracer.path()
    if path is None:
        return None
    return read(path) if path.endswith(".json") else load(path)


def save(sc: Scopes, path: str) -> None:
    with open(path, "w") as f:
        json.dump(sc.to_json(), f)


def read(path: str) -> Scopes:
    with open(path) as f:
        return Scopes.from_json(json.load(f))
