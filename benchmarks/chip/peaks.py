"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peak(device_kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; a device that is not
    in the table is an error, never a default."""
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row with its source to "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, pk: dict) -> tuple:
    """The least time the chip could take for ``flops`` and ``nbytes``,
    and which of the two bounds it (``"compute"`` or ``"bytes"``)."""
    tf = flops / pk["bf16_flops_per_s"]
    tb = nbytes / pk["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "bytes")
