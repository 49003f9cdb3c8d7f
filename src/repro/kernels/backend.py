"""Kernel backend seam: one resolution rule for every Pallas-vs-jnp choice.

Every hot spot with a Pallas kernel (the ``repro.kernels`` codec family,
flash attention) accepts a ``backend`` knob with three values:

  kernel   the Pallas implementation.  On TPU hardware it compiles to a
           fused Mosaic kernel; on CPU hosts it executes in interpret
           mode (``pl.pallas_call(interpret=True)``) — numerically the
           same program, used by the parity tests and smoke gates.
  ref      the pure-jnp oracle (the pre-seam production math).  XLA
           fuses the elementwise work, but nothing is hand-tiled.
  auto     ``kernel`` when the process has TPU devices, else ``ref``.
           Interpret-mode Pallas trades away the fusion win it exists
           for, so CPU hosts auto-fall back to the oracle and TPU hosts
           get the fused path — "as fast as the hardware allows" on both.

``REPRO_KERNEL_BACKEND=kernel|ref`` overrides ``auto`` for a whole
process (CI smoke gates and benchmarks use it to force the kernel path
on CPU).  Explicit ``backend="kernel"``/``"ref"`` always wins over the
environment.

The knob is threaded once per layer: ``Compressor.backend`` (modeled
per-worker roundtrip), ``SegmentCodec`` via ``codec_for`` (measured
payloads inside the collective schedules), ``Strategy.kernel_backend``
(spec-level selection for both), and ``ModelConfig.attn_backend`` /
the ``backend=`` kwarg of ``models.attention`` (flash attention).
See docs/kernels.md for the full matrix.
"""
from __future__ import annotations

import functools
import os

KERNEL_BACKENDS = ("auto", "kernel", "ref")


@functools.lru_cache(maxsize=None)
def _has_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str = "auto") -> str:
    """Resolve a backend knob to ``"kernel"`` or ``"ref"`` (module
    docstring).  Raises on unknown values so typos fail loudly at plan /
    construction time rather than silently running the wrong math."""
    if backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"backend={backend!r} (want one of {KERNEL_BACKENDS})")
    if backend != "auto":
        return backend
    env = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if env:
        if env not in ("kernel", "ref"):
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r} (want kernel|ref)")
        return env
    return "kernel" if _has_tpu() else "ref"


def kernel_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode (no TPU in the
    process).  Every ``interpret=`` default in ``repro.kernels`` call
    sites routes through this single rule."""
    return not _has_tpu()
