"""Extract roofline terms from a lowered/compiled program.

``cost_analysis()`` provides HLO FLOPs and bytes; collective bytes are NOT
in cost_analysis, so we parse the optimized HLO text and convert each
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
into estimated per-device ring traffic:

  op               result bytes R, group size S   traffic per device
  all-reduce       R                               2 (S-1)/S * R
  all-gather       R (the gathered tensor)         (S-1)/S * R
  reduce-scatter   R (the shard)                   (S-1) * R   (input = S*R)
  all-to-all       R                               (S-1)/S * R
  collective-permute R                             R

Group size S is parsed from replica_groups=[G,S]<=[N] (iota form) or the
explicit {{...}} list; missing/odd formats fall back to S=2 semantics
(factor 1) so traffic is never silently inflated.
"""
from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
# iota form: replica_groups=[G,S]<=[N...]
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
# explicit form: replica_groups={{0,1,2,...},{...}}
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 2    # unknown: conservative (factor (S-1)/S ~ 1/2 .. 1)


def _result_bytes(line: str) -> int:
    lhs = line.split(" = ", 1)
    region = lhs[1] if len(lhs) == 2 else line
    m = re.search(r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                  r"collective-permute)", region)
    region = region[:m.start()] if m else region
    total = 0
    for dtype, dims in _SHAPE_RE.findall(region):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _traffic(op: str, result_bytes: int, s: int) -> float:
    if s <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (s - 1) / s * result_bytes
    if op == "all-gather":
        return (s - 1) / s * result_bytes
    if op == "reduce-scatter":
        return float(s - 1) * result_bytes
    if op == "all-to-all":
        return (s - 1) / s * result_bytes
    return float(result_bytes)      # collective-permute


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-type result bytes + per-device ring-traffic estimate."""
    out = {k: 0.0 for k in _COLLECTIVES}
    traffic = 0.0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue       # async pair: count the -start only
        op = m.group(1)
        b = _result_bytes(line)
        out[op] += b
        traffic += _traffic(op, b, _group_size(line))
    out["traffic_weighted"] = traffic
    return out


def summarize_cost(cost) -> Dict[str, float]:
    """Normalize compiled.cost_analysis() output across jax versions."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    out = {}
    for k in ("flops", "bytes accessed", "transcendentals",
              "optimal_seconds"):
        if k in cost:
            out[k.replace(" ", "_")] = float(cost[k])
    out.setdefault("flops", 0.0)
    out.setdefault("bytes_accessed", 0.0)
    return out


def summarize_memory(mem) -> Dict[str, float]:
    if mem is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = float(v)
    return out
