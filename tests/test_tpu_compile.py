"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles one kernel at the widths the system runs it (StableLM-2
attention, DeepSeek-V2's latent attention and its training step, the
``[R, 256]`` codec rows, an untied LM head's vocab-long EF rows) for a
described ``v5e:2x2`` topology and checks that the program
calls the Mosaic kernel (``tpu_custom_call``).  Nothing runs: the TPU
compiler is installed on CPU hosts, and a kernel Mosaic refuses (a bad
relayout, an op it cannot legalize, a block over the VMEM limit) fails
here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm.transport import ring_allreduce

from repro.kernels.flash_attention import (attention_grad, flash_attention,
                                           flash_decode)
from repro.kernels.flash_attention.flash_attention import plan
from repro.kernels.onebit.fused import onebit_encode_ef
from repro.kernels.onebit.onebit import onebit_compress
from repro.kernels.qsgd.qsgd import qsgd_compress
from repro.kernels.terngrad.terngrad import terngrad_ternarize
from repro.kernels.topk.topk import topk_compress

# StableLM-2 1.6B attention at the train cell's 3 rows of 2048 tokens;
# codec rows as comm/codecs.py lays them out
B, S, H, HD = 3, 2048, 32, 64
WINDOW = 512
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
    """The planned blocks (512 rows, four heads a step) fit VMEM, causal
    and with a window that skips blocks before it."""
    q = _shape(one_chip, (B, S, H, HD), jnp.bfloat16)
    assert plan(S, H, H, HD, jnp.bfloat16).block_q == 512
    for window in (0, WINDOW):
        _assert_kernel(functools.partial(flash_attention, causal=True,
                                         window=window, interpret=False),
                       q, q, q)


def _kernel_names(text):
    """The instruction names of a compiled program's Mosaic kernels."""
    return [line.split("=", 1)[0].strip().removeprefix("ROOT ").lstrip("%")
            for line in text.split("\n")
            if 'custom_call_target="tpu_custom_call"' in line]


def _assert_fwd_bwd_kernels(q, k, v, **kw):
    """value_and_grad of ``attention_grad`` inside a one-layer scan, as the
    model's layer scan calls it: the forward's kernel is the one
    instruction named ``attention_grad`` (what the benchmark's forward
    rooflines read), and the backward's dK/dV and dQ kernels are named
    ``flash_attention_bwd`` (what ``flash_bwd_roofline`` reads)."""
    def loss(q, k, v):
        def layer(c, _):
            out = attention_grad(q, k, v, interpret=False, **kw)
            return c + jnp.sum(out.astype(jnp.float32)), None
        return jax.lax.scan(layer, 0.0, None, length=1)[0]
    names = _kernel_names(jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2))).lower(q, k, v).compile().as_text())
    fwd = [n for n in names if re.match(r"attention_grad\b", n)]
    bwd = [n for n in names if re.match(r"flash_attention_bwd\b", n)]
    assert len(fwd) == 1 and len(bwd) == 2 and len(names) == 3, names


def test_attention_grad_fwd_bwd(one_chip):
    """StableLM's attention at the train cell's 3 rows of 2048: the
    forward with its log-sum-exp and the Pallas backward fit VMEM, causal
    and with a window that skips blocks."""
    q = _shape(one_chip, (B, S, H, HD), jnp.bfloat16)
    assert plan(S, H, H, HD, jnp.bfloat16, backward=True).block_q == 512
    for window in (0, WINDOW):
        _assert_fwd_bwd_kernels(q, q, q, causal=True, window=window)


def test_attention_grad_fwd_bwd_mla(one_chip):
    """The same at DeepSeek-V2's latent attention, 2 rows of 4096: q/k
    heads of 192, v heads of 128, explicit scale."""
    q = _shape(one_chip, (2, 4096, 16, 192), jnp.bfloat16)
    v = _shape(one_chip, (2, 4096, 16, 128), jnp.bfloat16)
    _assert_fwd_bwd_kernels(q, q, v, causal=True, scale=0.1352)


def test_flash_attention_mla(one_chip):
    """DeepSeek-V2's latent attention at its cell's 2 rows of 4096 tokens:
    q/k heads of 192, v heads of 128, two heads a step, explicit scale."""
    q = _shape(one_chip, (2, 4096, 16, 192), jnp.bfloat16)
    v = _shape(one_chip, (2, 4096, 16, 128), jnp.bfloat16)
    assert plan(4096, 16, 16, 192, jnp.bfloat16, hdv=128).heads == 2
    _assert_kernel(functools.partial(flash_attention, causal=True,
                                     scale=0.1352, interpret=False), q, q, v)


def test_deepseek_train_step(topo, one_chip, monkeypatch):
    """The DeepSeek-V2 cell's whole training step (1 dense + 4 MoE layers
    holding 8 of 64 experts, vocabulary 12800, 2 rows of 4096 tokens, bf16
    compute) fits one chip, with the flash forward and backward and the
    held experts' grouped matmuls as Mosaic kernels, and with no scatter
    of d_model-wide rows in the held experts' dispatch or combine."""
    import dataclasses

    import repro.models.mla as mla
    import repro.models.moe as moe
    from repro.configs import get_config
    from repro.models import build_model
    from repro.train import Strategy

    for mod in (mla, moe):
        monkeypatch.setattr(mod, "kernel_interpret", lambda: False)
        monkeypatch.setattr(mod, "resolve_backend", lambda b: "kernel")
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              num_layers=5, vocab_size=12800,
                              experts_held=(0, 8))
    model = build_model(cfg)

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: model.loss_fn(q, b), has_aux=True)(p)
        return loss, g
    engine = Strategy.parse("bsp/allreduce/none@1", lr=0.1).build(
        grad_fn, devices=[topo.devices[0]]).inner
    rep, per = (NamedSharding(engine.mesh, P()),
                NamedSharding(engine.mesh, P("workers")))
    params = jax.tree.map(lambda a: _shape(rep, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {k: _shape(per, (1, 2, 4096), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = engine._build_step(params).lower(
        params, None, batch, _shape(per, (1, 2), jnp.uint32),
        _shape(per, (1,))).compile()
    mem = compiled.memory_analysis()
    print(f"deepseek step: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, peak "
          f"{mem.peak_memory_in_bytes / 1e9:.2f} GB")
    text = compiled.as_text()
    assert "jit(attention_grad)/pallas_call" in text
    assert "jit(flash_attention_bwd)/pallas_call" in text
    assert "/moe/experts/jit(gmm)/pallas_call" in text
    assert "jit(tgmm)/pallas_call" in text
    # dispatch and combine move d_model-wide rows by gathers alone, both
    # ways; the backward pass sorts nothing there
    moved = [ln for ln in text.splitlines()
             if re.search(r"/moe/(dispatch|combine)/", ln)]
    assert not [ln for ln in moved if re.search(r"\bscatter\(", ln)
                and re.search(r"\[[\d,]*\b2048\b", ln)]
    assert not [ln for ln in moved if re.search(r"\bsort\(", ln)
                and "transpose(" in ln]


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


def test_step_scopes_change_only_metadata(topo, one_chip, monkeypatch):
    """The training step compiled for the chip is the same program with
    and without the model's and the engine's ``jax.named_scope``s: they
    name ops in the device trace and change nothing else."""
    import contextlib
    import dataclasses
    import re

    import repro.models.attention as attention
    from repro.configs import get_config
    from repro.models import build_model
    from repro.train import Strategy

    monkeypatch.setattr(attention, "kernel_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_config("stablelm-1.6b"), d_model=256, num_heads=4,
        num_kv_heads=4, head_dim=HD, d_ff=512, vocab_size=512, num_layers=2,
        attn_backend="kernel")
    model = build_model(cfg)

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: model.loss_fn(q, b), has_aux=True)(p)
        return loss, g
    engine = Strategy.parse("bsp/allreduce/none@1", lr=0.1).build(
        grad_fn, devices=[topo.devices[0]]).inner
    rep, per = (NamedSharding(engine.mesh, P()),
                NamedSharding(engine.mesh, P("workers")))
    params = jax.tree.map(lambda a: _shape(rep, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {k: _shape(per, (1, 2, 256), jnp.int32)
             for k in ("tokens", "labels")}
    keys = _shape(per, (1, 2), jnp.uint32)
    weight = _shape(per, (1,))

    def compiled_text():
        jax.clear_caches()
        step = engine._build_step(params)
        text = step.lower(params, None, batch, keys, weight).compile() \
            .as_text()
        # the ops without their metadata and the source-location tables
        # (file, function, line) that it points into
        ops = [p for p in text.split("\n\n") if p.split("\n", 1)[0] not in
               ("FileNames", "FunctionNames", "FileLocations", "StackFrames")]
        return text, re.sub(r",? metadata=\{[^}]*\}", "", "\n\n".join(ops))

    scoped, scoped_bare = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_bare = compiled_text()
    assert "tpu_custom_call" in scoped
    assert "/attention/" in scoped and "/attention/" not in plain
    assert scoped_bare == plain_bare
