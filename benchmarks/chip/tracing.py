"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a traced run wrote and keeps, inside
the traced window (the benchmark's ``bench.window`` annotation):

  * per device plane, the operations of its ``XLA Ops`` line and the
    programs of its ``XLA Modules`` line, as [name, start_ns, dur_ns];
  * the benchmark's own host spans (``serve.*``, ``train.*``) with their
    arguments.

The same compact form is what a test reads from a trace recorded once on
the chip (``tests/data``), so every number below is computed the same
way on both.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PALLAS = " [pallas]"
HOST_PREFIXES = ("serve.", "train.", "bench.")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_list, b_list):
    """Parts of the (merged) intervals ``a_list`` not covered by the
    merged ``b_list``."""
    out, j = [], 0
    b_list = union(b_list)
    for a, b in union(a_list):
        cur = a
        while j < len(b_list) and b_list[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_list) and b_list[k][0] < b:
            if b_list[k][0] > cur:
                out.append((cur, b_list[k][0]))
            cur = max(cur, b_list[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


@dataclasses.dataclass
class Trace:
    devices: List[dict]           # {"name", "ops": [...], "modules": [...]}
    host: List[list]              # [name, start_ns, dur_ns, args]
    window: Tuple[float, float]   # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def op_intervals(self, dev, pred=None):
        return clip([(s, s + d) for n, s, d in dev["ops"]
                     if pred is None or pred(n)], *self.window)

    def busy_s(self, dev) -> float:
        return total(union(self.op_intervals(dev))) * 1e-9

    def idle_gaps(self, dev):
        return subtract([self.window], self.op_intervals(dev))

    def modules(self, dev, pattern: str):
        """[(start, dur)] of the programs whose name matches ``pattern``
        and that started inside the window."""
        rx = re.compile(pattern)
        return [(s, d) for n, s, d in dev["modules"]
                if rx.search(n) and self.window[0] <= s < self.window[1]]

    def op_time_s(self, dev, pattern: str) -> float:
        rx = re.compile(pattern)
        return total(union(self.op_intervals(
            dev, lambda n: bool(rx.search(n))))) * 1e-9

    def exposed_collective_s(self, dev) -> float:
        """Collective time during which no other operation runs."""
        coll = self.op_intervals(dev, lambda n: bool(COLLECTIVE.search(n)))
        comp = self.op_intervals(dev, lambda n: not COLLECTIVE.search(n))
        return total(subtract(coll, comp)) * 1e-9

    def spans(self, name: str):
        return [h for h in self.host if h[0] == name]

    def span_time_s(self, name: str) -> float:
        return total(clip([(s, s + d) for n, s, d, _ in self.spans(name)],
                          *self.window)) * 1e-9

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host,
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["devices"], d["host"], tuple(d["window"]))


def op_name(text: str) -> str:
    """An op event's name as the reduction matches it: the HLO instruction
    name without the rest of its text (the TPU trace names an op by its
    whole HLO line), marked ``[pallas]`` when it calls a Mosaic kernel."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + PALLAS if "tpu_custom_call" in text else name


def describe(path: str, out: str) -> None:
    """Every plane and line of a trace, with its event count and most
    frequent event names: what a reduction has to match, seen by hand."""
    from collections import Counter
    from jax.profiler import ProfileData
    with open(out, "w") as f:
        for plane in ProfileData.from_file(path).planes:
            f.write(f"PLANE {plane.name}\n")
            for ln in plane.lines:
                names = Counter(e.name for e in ln.events)
                f.write(f"  LINE {ln.name!r} {sum(names.values())} events: "
                        f"{names.most_common(12)}\n")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into the compact form, cut to the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            dev = {"name": plane.name, "ops": [], "modules": []}
            for key, ln in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if ln in lines:
                    dev[key] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                for e in lines[ln].events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     {k: v for k, v in e.stats
                                      if isinstance(v, (int, float))}])
    win = [h for h in host if h[0] == "bench.window"]
    if not devices or not win:
        raise RuntimeError(f"trace {path} has no device operations or no "
                           f"bench.window span")
    window = (win[0][1], win[0][1] + win[0][2])
    lo, hi = window
    for dev in devices:
        for key in ("ops", "modules"):
            dev[key] = [e for e in dev[key] if e[1] + e[2] > lo and e[1] < hi]
    host = [h for h in host if h[1] + h[2] > lo and h[1] < hi]
    return Trace(devices, host, window)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader is given."""
    trace: Trace
    ctx: object
    outcome: object
    peak: dict


def gap_labels(tr: Trace, dev) -> Dict[str, float]:
    """Idle seconds of ``dev`` by the benchmark host span that overlaps
    each gap most, the shorter span on a tie (``"none"`` where no span
    is open)."""
    import numpy as np
    spans = [(n, s, s + d) for n, s, d, _ in tr.host if n != "bench.window"]
    gaps = np.asarray(tr.idle_gaps(dev), float).reshape(-1, 2)
    out: Dict[str, float] = defaultdict(float)
    if not len(gaps):
        return {}
    if not spans:
        return {"none": float(np.sum(gaps[:, 1] - gaps[:, 0])) * 1e-9}
    st = np.asarray([s for _, s, _ in spans], float)
    en = np.asarray([e for _, _, e in spans], float)
    ov = (np.minimum(gaps[:, 1:2], en[None]) - np.maximum(gaps[:, :1],
                                                          st[None]))
    # prefer the largest overlap, then the shortest span
    score = np.where(ov > 0, ov - 1e-12 * (en - st)[None], -np.inf)
    best = np.argmax(score, axis=1)
    has = np.isfinite(score[np.arange(len(gaps)), best])
    for g, (a, b) in enumerate(gaps):
        label = spans[best[g]][0] if has[g] else "none"
        out[label] += (b - a) * 1e-9
    return dict(out)


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time by
    the host span open during it, summed over the devices."""
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for dev in tr.devices:
        for n, s, d in dev["ops"]:
            lo, hi = max(s, tr.window[0]), min(s + d, tr.window[1])
            if hi > lo:
                ops[n] += (hi - lo) * 1e-9
        for k, v in gap_labels(tr, dev).items():
            gaps[k] += v
    first = lambda d: sorted(([k, v] for k, v in d.items()),
                             key=lambda kv: -kv[1])[:top]
    return {"device_ops": first(ops), "idle_gaps": first(gaps)}


def save(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)


def read(path: str) -> Optional[Trace]:
    with open(path) as f:
        return Trace.from_json(json.load(f))
