"""Process environment setup: virtual host devices and the compile cache.

Every tool, test, and benchmark in this repo that wants N devices on a
CPU host has to set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
*before the first jax import* — after jax initializes its backend the
flag is silently ignored and the run proceeds on 1 device until a mesh
constructor fails with a confusing "needs N devices" error.  This module
is the one implementation of that dance:

    from repro.launch.env import ensure_host_devices
    ensure_host_devices(8)       # before any jax import
    import jax

and, for the subprocess pattern (benchmarks / multi-device tests):

    subprocess.run([...], env=subprocess_env(8))

Allocator note (docs/hybrid.md): on hosts where glibc malloc fragments
under the engine's per-bucket arrays, preload tcmalloc *outside* the
process — an env var cannot retroactively swap the allocator of a
running interpreter::

    LD_PRELOAD=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
        PYTHONPATH=src python tools/hybrid_smoke.py

``subprocess_env`` forwards an LD_PRELOAD already present in the parent
environment, so one export covers a whole bench tree.

``call_on_devices(n, fn)`` runs a function that needs ``n`` devices from
a process that may have fewer: in process, in a virtual-device child on a
CPU host, or not at all on an accelerator host (the parent holds the
chip, so a child could not reach it).

``use_compile_cache()`` points JAX's persistent compilation cache at one
fixed directory; entry points call it from ``main()``, never at import.
"""
from __future__ import annotations

import inspect
import json
import os
import subprocess
import sys
from typing import Any, Callable, Dict, Optional

_FLAG = "--xla_force_host_platform_device_count"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
# the cache path is part of each entry's key, so it never moves
_CACHE_DIR = os.path.join(_REPO, ".jax_compile_cache")


def _with_flag(flags: str, n: int) -> str:
    """XLA_FLAGS value with the host-device-count flag ensured.  An
    explicit count already present (env override) wins."""
    if _FLAG in flags:
        return flags
    return f"{flags} {_FLAG}={n}".strip()


def ensure_host_devices(n: int) -> None:
    """Idempotently request ``n`` virtual host devices for this process.

    Must run before the first jax import; raises if jax's backend is
    already initialized (the flag would be silently ignored).  A count
    already present in ``XLA_FLAGS`` — e.g. set by an outer launcher or
    ``subprocess_env`` — is respected, not overwritten.
    """
    if n < 1:
        raise ValueError("device count must be >= 1")
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None and _FLAG not in os.environ.get("XLA_FLAGS", ""):
        backends = sys.modules.get("jax._src.xla_bridge")
        if backends is not None and getattr(backends, "_backends", None):
            raise RuntimeError(
                "ensure_host_devices() called after jax initialized its "
                "backend; XLA_FLAGS would be ignored.  Call it before the "
                "first jax import (see repro.launch.env docstring)")
    os.environ["XLA_FLAGS"] = _with_flag(os.environ.get("XLA_FLAGS", ""), n)


def subprocess_env(n: int,
                   base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """A copy of ``base`` (default ``os.environ``) with ``XLA_FLAGS``
    requesting ``n`` virtual host devices — the env to hand
    ``subprocess.run`` for a fresh multi-device child process.  Unlike
    ``ensure_host_devices`` this *overrides* any existing count: a child
    launched for n devices must get n devices regardless of the parent's
    own flag."""
    if n < 1:
        raise ValueError("device count must be >= 1")
    env = dict(os.environ if base is None else base)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(f"{_FLAG}=")]
    env["XLA_FLAGS"] = " ".join(flags + [f"{_FLAG}={n}"])
    return env


def call_on_devices(n: int, fn: Callable[[], Any], *,
                    timeout: float = 600) -> Optional[Any]:
    """``fn()`` run where ``n`` devices exist, or None where none can.

    In this process when it already has ``n`` devices.  On a CPU host
    with fewer, in a child with ``n`` virtual host devices that loads
    ``fn``'s file and prints ``fn()`` as JSON (so the result must be
    JSON-serializable).  An accelerator process with fewer devices gets
    None: it holds its chip, and a child could not reach it."""
    import jax
    if len(jax.devices()) >= n:
        return fn()
    if jax.default_backend() != "cpu":
        return None
    env = subprocess_env(n)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
    code = ("import json, runpy\n"
            f"fn = runpy.run_path({inspect.getfile(fn)!r})[{fn.__name__!r}]\n"
            "print(json.dumps(fn()))\n")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"{fn.__name__} on {n} virtual devices failed:\n"
                           f"{res.stderr[-3000:]}")
    return json.loads(res.stdout.splitlines()[-1])


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``<repo>/.jax_compile_cache``
    (git-ignored).  Call from an entry point's ``main()``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR
