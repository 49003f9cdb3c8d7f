"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles one kernel at the widths the system runs it (StableLM-2
attention, the ``[R, 256]`` codec rows, an untied LM head's vocab-long
EF rows) for a described ``v5e:2x2`` topology and checks that the program
calls the Mosaic kernel (``tpu_custom_call``).  Nothing runs: the TPU
compiler is installed on CPU hosts, and a kernel Mosaic refuses (a bad
relayout, an op it cannot legalize, a block over the VMEM limit) fails
here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm.transport import ring_allreduce

from repro.kernels.flash_attention import (attention_grad, flash_attention,
                                           flash_decode)
from repro.kernels.onebit.fused import onebit_encode_ef
from repro.kernels.onebit.onebit import onebit_compress
from repro.kernels.qsgd.qsgd import qsgd_compress
from repro.kernels.terngrad.terngrad import terngrad_ternarize
from repro.kernels.topk.topk import topk_compress

# StableLM-2 1.6B attention; codec rows as comm/codecs.py lays them out
B, S, H, HD = 1, 2048, 32, 64
ROWS, LANE = 8192, 256
D_MODEL, VOCAB = 2048, 100352


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile cannot be read back from the persistent
    # cache, so keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention(one_chip):
    q = _shape(one_chip, (B, S, H, HD), jnp.bfloat16)
    _assert_kernel(functools.partial(flash_attention, causal=True,
                                     interpret=False), q, q, q)


def test_attention_grad_fwd_bwd(one_chip):
    q = _shape(one_chip, (B, S, H, HD), jnp.bfloat16)

    def loss(q, k, v):
        out = attention_grad(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    # value_and_grad: the forward value keeps the kernel live (the VJP
    # replays the reference math)
    _assert_kernel(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, q, q)


@pytest.mark.parametrize("window", [0, 512], ids=["full", "window"])
def test_flash_decode(one_chip, window):
    q = _shape(one_chip, (B, 1, H, HD), jnp.bfloat16)
    cache = _shape(one_chip, (B, window or S, H, HD), jnp.bfloat16)
    pos = _shape(one_chip, (), jnp.int32)
    _assert_kernel(functools.partial(flash_decode, window=window,
                                     interpret=False), q, cache, cache, pos)


def test_flash_decode_vmapped_per_slot(one_chip):
    """As the serve step calls it: vmapped over batch slots, each slot at
    its own position."""
    slots = 4
    q = _shape(one_chip, (slots, 1, 1, H, HD), jnp.bfloat16)
    cache = _shape(one_chip, (slots, 1, S, H, HD), jnp.bfloat16)
    pos = _shape(one_chip, (slots,), jnp.int32)
    _assert_kernel(jax.vmap(functools.partial(flash_decode,
                                              interpret=False)),
                   q, cache, cache, pos)


@pytest.mark.parametrize("valid", [False, True], ids=["ef", "valid"])
def test_onebit_encode_ef(one_chip, valid):
    g = _shape(one_chip, (ROWS, LANE))
    if valid:
        v = _shape(one_chip, (ROWS, LANE), jnp.int8)
        _assert_kernel(lambda g, v: onebit_encode_ef(g, None, v,
                                                     interpret=False), g, v)
    else:
        _assert_kernel(lambda g, e: onebit_encode_ef(g, e, gain=2.0,
                                                     interpret=False), g, g)


def test_onebit_encode_ef_vocab_rows(one_chip):
    """Per-channel EF rows of an untied LM head are vocab-long: fewer rows
    per step and a raised VMEM limit keep the blocks in VMEM."""
    g = _shape(one_chip, (D_MODEL, VOCAB))
    _assert_kernel(lambda g, e: onebit_encode_ef(g, e, gain=2.0,
                                                 interpret=False), g, g)


def test_onebit_compress(one_chip):
    g = _shape(one_chip, (ROWS, LANE))
    _assert_kernel(functools.partial(onebit_compress, interpret=False), g, g)


def test_terngrad_ternarize(one_chip):
    g = _shape(one_chip, (ROWS, LANE))
    s = _shape(one_chip, ())
    _assert_kernel(functools.partial(terngrad_ternarize, interpret=False),
                   g, g, s)


def test_qsgd_compress(one_chip):
    g = _shape(one_chip, (ROWS, LANE))
    _assert_kernel(functools.partial(qsgd_compress, interpret=False), g, g)


def test_ring_chunks_stay_outside_the_tile(one_chip, topo):
    """The ring allreduce of an untied LM head's gradient over four chips
    addresses its chunks on a leading axis of ``[4, R, 128]`` rows, not
    as rows of ``[4, m]``: there the chunk index falls inside the TPU
    tile and the compile time grew with the bytes reduced."""
    import numpy as np
    mesh = Mesh(np.array(topo.devices), ("w",))
    n, size = len(topo.devices), D_MODEL * VOCAB
    g = jax.ShapeDtypeStruct((n, size), jnp.float32,
                             sharding=NamedSharding(mesh, P("w")))
    step = jax.shard_map(lambda x: ring_allreduce(x[0], "w")[None],
                         mesh=mesh, in_specs=P("w"), out_specs=P("w"),
                         check_vma=False)
    text = jax.jit(step).lower(g).compile().as_text()
    assert f"f32[{n},{size // n // 128},128]" in text
    assert f"f32[{n},{size // n}]" not in text


def test_topk_compress(one_chip):
    g = _shape(one_chip, (ROWS, LANE))
    t = _shape(one_chip, ())
    _assert_kernel(functools.partial(topk_compress, interpret=False),
                   g, g, t)
