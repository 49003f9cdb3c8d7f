"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle,
the kernel-backend seam (fused encode+EF, codec planes, flash decode, the
trainable flash forward), and the strategy-level backend-parity acceptance
cells on virtual devices.

Hypothesis property tests live in tests/test_kernel_properties.py so these
sweeps run even without the optional dev dep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import make_codec
from repro.kernels import flash_attention as FA
from repro.kernels import onebit, qsgd, terngrad, topk
from repro.kernels.backend import resolve_backend

KEY = jax.random.PRNGKey(42)


# ------------------------------------------------------------ backend seam
def test_resolve_backend_contract(monkeypatch):
    """auto resolves per host (ref on this CPU container), explicit
    choices pass through, garbage is rejected, env overrides auto."""
    assert resolve_backend("kernel") == "kernel"
    assert resolve_backend("ref") == "ref"
    assert resolve_backend("auto") in ("kernel", "ref")
    with pytest.raises(ValueError):
        resolve_backend("bogus")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "kernel")
    assert resolve_backend("auto") == "kernel"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    assert resolve_backend("auto") == "ref"


# ------------------------------------------------------------ flash attention
def _qkv(B, S, H, KV, hd, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, KV, hd), dtype),
            jax.random.normal(ks[2], (B, S, KV, hd), dtype))


def _max_err(out, ref):
    return float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32))))


# block None: the blocks ``plan`` derives from the shape
@pytest.mark.parametrize("B,S,H,KV,hd,block", [
    pytest.param(1, 64, 4, 4, 32, 64, id="1-64-4-4-32"),
    pytest.param(2, 64, 4, 2, 32, 64, id="2-64-4-2-32"),
    pytest.param(1, 128, 8, 1, 64, 64, id="1-128-8-1-64"),
    pytest.param(2, 96, 4, 2, 64, 64, id="2-96-4-2-64"),
    pytest.param(1, 256, 2, 2, 128, 64, id="1-256-2-2-128"),
    # S not a multiple of the block: one planned block of S rounded up,
    # and a padded key tail behind explicit blocks
    pytest.param(1, 200, 4, 4, 32, None, id="1-200-4-4-32-planned"),
    pytest.param(1, 200, 4, 2, 32, 64, id="1-200-4-2-32-b64"),
    # whole blocks above the diagonal skipped (10 of 16 pairs compute)
    pytest.param(1, 512, 2, 2, 64, 128, id="1-512-2-2-64-b128"),
    # planned 512-row blocks, GQA folded (KV < H), one pair skipped
    pytest.param(1, 1024, 4, 2, 64, None, id="1-1024-4-2-64-planned"),
    pytest.param(2, 256, 8, 2, 64, None, id="2-256-8-2-64-planned"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, hd, block, dtype):
    q, k, v = _qkv(B, S, H, KV, hd, dtype)
    out = FA.attention(q, k, v, causal=True, block_q=block, block_k=block)
    ref = FA.attention_ref(q, k, v, causal=True)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert _max_err(out, ref) < tol


@pytest.mark.parametrize("window,S,block,dtype", [
    pytest.param(16, 128, 32, jnp.float32, id="16"),
    pytest.param(64, 128, 32, jnp.float32, id="64"),
    # a window shorter than the planned block: only the diagonal blocks
    pytest.param(16, 512, None, jnp.float32, id="16-S512-planned"),
    # windows spanning several blocks: blocks before them skipped
    pytest.param(200, 512, 128, jnp.bfloat16, id="200-S512-b128-bf16"),
    pytest.param(300, 1024, None, jnp.bfloat16, id="300-S1024-planned-bf16"),
])
def test_flash_attention_window(window, S, block, dtype):
    q, k, v = _qkv(1, S, 4, 2, 32, dtype)
    out = FA.attention(q, k, v, causal=True, window=window,
                       block_q=block, block_k=block)
    ref = FA.attention_ref(q, k, v, causal=True, window=window)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert _max_err(out, ref) < tol


@pytest.mark.parametrize("S,H,KV,hd,dtype,block,heads,pairs,grid", [
    (2048, 32, 32, 64, jnp.bfloat16, 512, 4, 10, 16),    # the train cell
    (48, 8, 2, 32, jnp.float32, 48, 8, 1, 1),            # a short prefill
    (1280, 16, 16, 128, jnp.bfloat16, 256, 2, 15, 25),   # 512 would pad
])
def test_flash_attention_plan(S, H, KV, hd, dtype, block, heads, pairs,
                              grid):
    """The plan's blocks for a shape, and its count of computed block
    pairs against a brute-force count of pairs holding a visible key."""
    from repro.kernels.flash_attention.flash_attention import plan
    pn = plan(S, H, KV, hd, dtype)
    assert (pn.block_q, pn.block_k, pn.heads) == (block, block, heads)
    assert (pn.pairs, pn.nq * pn.nk) == (pairs, grid)
    assert pn.heads * hd % 128 == 0 or pn.heads == H

    def brute(S, bq, bk, causal, window):
        qi = np.arange(S)[:, None]
        kj = np.arange(S)[None, :]
        vis = np.ones((S, S), bool)
        if causal:
            vis = (kj <= qi) & ((kj > qi - window) if window else True)
        return sum(vis[i:i + bq, j:j + bk].any()
                   for i in range(0, S, bq) for j in range(0, S, bk))

    for S_, b, causal, window in [(200, 64, True, 0), (512, 128, True, 0),
                                  (512, 128, True, 200), (300, 32, True, 16),
                                  (256, 64, False, 0), (1000, 96, True, 333),
                                  (48, None, True, 0), (1280, None, True, 0)]:
        for bq, bk in ((b, b), (b, b and 2 * b)):
            pn = plan(S_, H, KV, hd, dtype, causal=causal, window=window,
                      block_q=bq, block_k=bk)
            assert pn.pairs == brute(S_, pn.block_q, pn.block_k, causal,
                                     window)
            assert pn.nq * pn.block_q >= S_ and pn.nk * pn.block_k >= S_


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 64, 4, 32))
    v = jax.random.normal(ks[2], (2, 64, 4, 32))
    out = FA.attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = FA.attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
    # without the causal mask only the key-padding mask hides the padded
    # tail: 200 keys in 64-row blocks
    q, k, v = _qkv(1, 200, 4, 2, 32)
    out = FA.attention(q, k, v, causal=False, block_q=64, block_k=64)
    assert _max_err(out, FA.attention_ref(q, k, v, causal=False)) < 1e-4


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16),
                                           (False, 0)])
def test_flash_attention_grad_matches_ref(causal, window):
    """The trainable entry: flash forward, flash backward.  Both the
    value and every input gradient must match the jnp oracle under
    value_and_grad."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 48, 8, 32))
    k = jax.random.normal(ks[1], (2, 48, 2, 32))
    v = jax.random.normal(ks[2], (2, 48, 2, 32))

    def loss_k(q, k, v):
        return jnp.sum(FA.attention_grad(q, k, v, causal=causal,
                                         window=window) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(FA.attention_ref(q, k, v, causal=causal,
                                        window=window) ** 2)

    vk, gk = jax.value_and_grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    vr, gr = jax.value_and_grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(vk - vr)) < 1e-2
    for a, b in zip(gk, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def _vjps(q, k, v, g, **kw):
    """The kernel's and the fp32 oracle's (dq, dk, dv) for cotangent g."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    gk = jax.vjp(lambda *a: FA.attention_grad(*a, **kw), q, k, v)[1](g)
    gr = jax.vjp(lambda *a: FA.attention_ref(*a, **kw), *f32[:3])[1](f32[3])
    return gk, gr


# (B, S, H, KV, hd, dtype, causal, window); every block from the plan
@pytest.mark.parametrize("B,S,H,KV,hd,dtype,causal,window", [
    # 128-row blocks, the padded tail of 600 in a fifth, pairs skipped
    pytest.param(1, 600, 4, 2, 32, jnp.float32, True, 0, id="gqa-600-f32"),
    pytest.param(1, 1024, 4, 4, 64, jnp.bfloat16, True, 0,
                 id="1024-planned-bf16"),                  # 3 of 4 pairs
    pytest.param(1, 200, 4, 4, 32, jnp.bfloat16, False, 0,
                 id="noncausal-200-bf16"),                 # padded to 208
    pytest.param(2, 200, 4, 2, 32, jnp.float32, False, 0,
                 id="noncausal-200-f32"),
    pytest.param(1, 512, 4, 2, 32, jnp.float32, True, 16,
                 id="window16-512-f32"),      # shorter than a block
    pytest.param(1, 1024, 4, 2, 32, jnp.bfloat16, True, 200,
                 id="window200-1024-bf16"),   # spans several blocks
    # MQA: a block holds 4 of the 8 heads sharing the one KV head, so
    # dK/dV sum over two head blocks
    pytest.param(1, 256, 8, 1, 64, jnp.float32, True, 0, id="mqa-256-f32"),
    pytest.param(1, 200, 8, 1, 64, jnp.bfloat16, True, 0,
                 id="mqa-200-bf16"),
])
def test_flash_attention_bwd_matches_ref(B, S, H, KV, hd, dtype, causal,
                                         window):
    """The Pallas backward's dq, dk and dv against jax.vjp of the fp32
    oracle, for a random cotangent: float32 to rounding, bfloat16 (bf16
    P and dS on the MXU) to 2% of the largest gradient."""
    ks = jax.random.split(KEY, 4)
    q, k, v, g = (jax.random.normal(kk, shape, dtype) for kk, shape in zip(
        ks, [(B, S, H, hd), (B, S, KV, hd), (B, S, KV, hd), (B, S, H, hd)]))
    gk, gr = _vjps(q, k, v, g, causal=causal, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(gk, gr):
        assert a.shape == b.shape and a.dtype == dtype
        assert _max_err(a, b) < tol * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("causal,window,S", [(True, 0, 600), (True, 16, 512),
                                             (False, 0, 200)])
def test_flash_attention_fwd_lse(causal, window, S):
    """The training forward's log-sum-exp: the oracle's scaled, masked
    scores' logsumexp, for every head and row."""
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_fwd)
    q, k, v = _qkv(1, S, 4, 2, 32)
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 scale=0.3)
    assert lse.shape == (1, 4, S) and lse.dtype == jnp.float32
    s = 0.3 * jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2))
    qi, kj = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    vis = (kj <= qi) & ((kj > qi - window) if window else True) \
        if causal else jnp.ones((S, S), bool)
    ref = jax.nn.logsumexp(jnp.where(vis, s, -jnp.inf), axis=-1)
    assert _max_err(lse, ref) < 1e-4
    assert _max_err(o, FA.attention_ref(q, k, v, causal=causal,
                                        window=window, scale=0.3)) < 1e-4


@pytest.mark.parametrize("S,H,KV,hd,hdv,block,heads,pairs", [
    (2048, 32, 32, 64, 64, 512, 4, 10),       # the StableLM cell
    (4096, 16, 16, 192, 128, 512, 2, 36),     # the DeepSeek cell
    (256, 8, 1, 64, 64, 256, 4, 1),           # MQA: two head blocks a KV
])
def test_flash_attention_bwd_plan(S, H, KV, hd, hdv, block, heads, pairs):
    """The backward's plan at the cells' shapes, and its pairs, counted by
    k-block, against a brute-force count of block pairs holding a visible
    (query, key) pair: the forward's pairs, enumerated the other way."""
    from repro.kernels.flash_attention.flash_attention import plan
    pn = plan(S, H, KV, hd, jnp.bfloat16, hdv=hdv, backward=True)
    assert (pn.block_q, pn.block_k, pn.heads, pn.pairs) == \
        (block, block, heads, pairs)

    def brute(S, bq, bk, causal, window):
        qi = np.arange(S)[:, None]
        kj = np.arange(S)[None, :]
        vis = np.ones((S, S), bool)
        if causal:
            vis = (kj <= qi) & ((kj > qi - window) if window else True)
        return sum(vis[i:i + bq, j:j + bk].any()
                   for i in range(0, S, bq) for j in range(0, S, bk))

    for S_, b, causal, window in [(200, 64, True, 0), (600, None, True, 0),
                                  (512, 128, True, 200), (300, 32, True, 16),
                                  (256, 64, False, 0), (1000, 96, True, 333),
                                  (1024, None, True, 16), (48, None, True, 0)]:
        for bq, bk in ((b, b), (b, b and 2 * b), (b and 2 * b, b)):
            kw = dict(causal=causal, window=window, block_q=bq, block_k=bk)
            bw = plan(S_, H, KV, hd, jnp.float32, backward=True, **kw)
            assert bw.pairs == brute(S_, bw.block_q, bw.block_k, causal,
                                     window)
            assert bw.pairs == plan(S_, H, KV, hd, jnp.float32, **kw).pairs


@pytest.mark.parametrize("pos", [0, 5, 39])
def test_flash_decode_full_cache(pos):
    ks = jax.random.split(KEY, 3)
    B, H, KV, hd, L = 2, 8, 2, 64, 40
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    ck = jax.random.normal(ks[1], (B, L, KV, hd))
    cv = jax.random.normal(ks[2], (B, L, KV, hd))
    out = FA.decode(q, ck, cv, jnp.int32(pos), block_k=16)
    ref = FA.decode_ref(q, ck, cv, jnp.int32(pos))
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@pytest.mark.parametrize("pos", [0, 7, 23, 100])
def test_flash_decode_ring_window(pos):
    """Ring-buffer cache: slots masked by age exactly like the jnp decode
    path, including the partially-filled early steps."""
    ks = jax.random.split(KEY, 3)
    B, H, KV, hd, W = 2, 4, 2, 32, 16
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    ck = jax.random.normal(ks[1], (B, W, KV, hd))
    cv = jax.random.normal(ks[2], (B, W, KV, hd))
    out = FA.decode(q, ck, cv, jnp.int32(pos), window=W, block_k=8)
    ref = FA.decode_ref(q, ck, cv, jnp.int32(pos), window=W)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_attention_module_backend_parity():
    """models.attention routed through the seam: kernel and ref backends
    agree on forward (causal / windowed / encoder) and decode."""
    from repro.configs import get_config
    from repro.models import attention as attn
    cfg = get_config("tinyllama-1.1b").reduced()
    p = attn.attn_init(KEY, cfg)
    B, S = 2, 24
    x = jax.random.normal(KEY, (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for kw in (dict(causal=True), dict(causal=True, window=8),
               dict(causal=False)):
        o_r, _ = attn.attention_forward(p, x, pos, cfg, backend="ref", **kw)
        o_k, _ = attn.attention_forward(p, x, pos, cfg, backend="kernel",
                                        **kw)
        assert float(jnp.max(jnp.abs(o_r - o_k))) < 1e-4, kw
    xt = jax.random.normal(KEY, (B, 1, cfg.d_model))
    caches = {b: attn.init_cache(cfg, B, 8, jnp.float32) for b in
              ("ref", "kernel")}
    for t in range(4):
        outs = {}
        for b in ("ref", "kernel"):
            outs[b], caches[b] = attn.attention_decode(
                p, xt, jnp.int32(t), caches[b], cfg, backend=b)
        assert float(jnp.max(jnp.abs(outs["ref"] - outs["kernel"]))) < 1e-4


# ----------------------------------------------------------- compression
SHAPES = [(8, 128), (64, 256), (100, 512), (3, 1024)]


@pytest.mark.parametrize("R,C", SHAPES)
def test_onebit_kernel_vs_ref(R, C):
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (R, C))
    e = jax.random.normal(ks[1], (R, C)) * 0.3
    s_k, sc_k, ne_k = onebit.compress(g, e)
    s_r, sc_r, ne_r = onebit.onebit_ref(g, e)
    assert jnp.array_equal(s_k, s_r)
    assert jnp.allclose(sc_k, sc_r, atol=1e-6)
    assert jnp.allclose(ne_k, ne_r, atol=1e-5)


@pytest.mark.parametrize("R,C", SHAPES)
@pytest.mark.parametrize("symmetric", [False, True])
def test_onebit_fused_encode_ef_kernel_vs_ref(R, C, symmetric):
    """The fused single-pass encode+EF kernel (signs, bin means, recon,
    next residual from one read of g/e) is bitwise the jnp oracle."""
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (R, C))
    e = jax.random.normal(ks[1], (R, C)) * 0.3
    out_k = onebit.encode_ef(g, e, gain=2.0, symmetric=symmetric,
                             backend="kernel")
    out_r = onebit.encode_ef(g, e, gain=2.0, symmetric=symmetric,
                             backend="ref")
    for a, b in zip(out_k, out_r):
        assert jnp.array_equal(a, b)
    signs, sp, sn, recon, new_e = out_r
    # EF telescoping: recon + residual == g + e (any gain)
    np.testing.assert_allclose(np.asarray(recon + new_e), np.asarray(g + e),
                               atol=1e-5)


def test_onebit_fused_encode_ef_masks_invalid_lanes():
    """Pad lanes flagged invalid must transmit nothing: recon 0, and they
    never contaminate the bin means of real lanes."""
    g = jnp.ones((4, 128)) * 3.0
    valid = jnp.zeros((4, 128), jnp.int8).at[:, :100].set(1)
    for backend in ("ref", "kernel"):
        _, _, _, recon, _ = onebit.encode_ef(
            g, None, valid, backend=backend)
        assert np.all(np.asarray(recon[:, 100:]) == 0.0), backend
        np.testing.assert_allclose(np.asarray(recon[:, :100]), 3.0,
                                   rtol=1e-6)


@pytest.mark.parametrize("R,C", SHAPES)
def test_terngrad_qsgd_kernel_vs_ref(R, C):
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (R, C))
    u = jax.random.uniform(ks[1], (R, C))
    t_k, s_k = terngrad.compress(g, u)
    t_r, s_r = terngrad.terngrad_ref(g, u)
    assert jnp.array_equal(t_k, t_r) and jnp.allclose(s_k, s_r)
    q_k, n_k = qsgd.compress(g, u)
    q_r, n_r = qsgd.qsgd_ref(g, u)
    assert jnp.array_equal(q_k, q_r) and jnp.allclose(n_k, n_r)


@pytest.mark.parametrize("R,C", SHAPES)
def test_dispatch_entries_kernel_vs_ref(R, C):
    """The backend-dispatching ops entries (the ones the codecs call)
    agree across backends: terngrad.ternarize, qsgd.quantize,
    topk.sparsify."""
    ks = jax.random.split(KEY, 3)
    g = jax.random.normal(ks[0], (R, C))
    u = jax.random.uniform(ks[1], (R, C))
    e = jax.random.normal(ks[2], (R, C)) * 0.1
    sigma = 2.5 * jnp.std(g)
    gc = jnp.clip(g, -sigma, sigma)
    s = jnp.max(jnp.abs(gc))                 # scalar scale, codec-style
    assert jnp.array_equal(terngrad.ternarize(gc, u, s, backend="kernel"),
                           terngrad.ternarize(gc, u, s, backend="ref"))
    for a, b in zip(qsgd.quantize(g, u, backend="kernel"),
                    qsgd.quantize(g, u, backend="ref")):
        assert jnp.array_equal(a, b)
    th = topk.threshold_for_density(g, e, 0.05)
    for a, b in zip(topk.sparsify(g, e, th, backend="kernel"),
                    topk.sparsify(g, e, th, backend="ref")):
        assert jnp.array_equal(a, b)


@pytest.mark.parametrize("R,C", SHAPES)
@pytest.mark.parametrize("density", [0.01, 0.1])
def test_topk_kernel_vs_ref(R, C, density):
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (R, C))
    e = jax.random.normal(ks[1], (R, C)) * 0.1
    th = topk.threshold_for_density(g, e, density)
    o_k, ne_k = topk.compress(g, e, th)
    o_r, ne_r = topk.topk_ref(g, e, th)
    assert jnp.allclose(o_k, o_r) and jnp.allclose(ne_k, ne_r)
    kept = float((o_k != 0).mean())
    assert abs(kept - density) < 0.05


def test_pack_unpack_roundtrip():
    g = jax.random.normal(KEY, (16, 256))
    e = jnp.zeros_like(g)
    signs, _, _ = onebit.compress(g, e)
    words = onebit.pack_bits(signs)
    assert words.shape == (16, 8)           # 32x fewer words
    assert jnp.array_equal(onebit.unpack_bits(words, C=256), signs)


# --------------------------------------------------- codec backend parity
@pytest.mark.parametrize("method,kw", [
    ("onebit", {}), ("terngrad", {}), ("qsgd", {}),
    ("dgc", {"density": 0.05}),
])
def test_codec_backends_bitwise_identical(method, kw):
    """The CommPlan codecs produce bitwise-identical wire planes and EF
    residuals on both backends — what keeps measured wire accounting
    backend-independent."""
    seg = jax.random.normal(jax.random.PRNGKey(5), (700,))
    key = jax.random.PRNGKey(1)
    out = {}
    for backend in ("ref", "kernel"):
        codec = make_codec(method, backend=backend, **kw)
        planes, res = codec.encode_ef(seg, key)
        out[backend] = (planes, res, codec.decode(planes),
                        codec.sent_elems(planes))
    pr, rr, dr, sr = out["ref"]
    pk, rk, dk, sk = out["kernel"]
    assert sorted(pr) == sorted(pk)
    for name in pr:
        assert jnp.array_equal(pr[name], pk[name]), (method, name)
    assert jnp.array_equal(rr, rk)
    assert jnp.array_equal(dr, dk)
    assert int(sr) == int(sk)


def test_dgc_sent_elems_wire_accounting_backend_invariant():
    """Regression for the kernels/topk-backed selection: the traced
    sent_elems count (what measured wire bytes are billed from) must not
    move when the selection runs through the Pallas kernel, across
    densities and degenerate segments."""
    key = jax.random.PRNGKey(9)
    segs = [jax.random.normal(key, (2048,)),
            jnp.zeros((512,)),                       # degenerate: all-zero
            jnp.ones((300,)).at[7].set(100.0)]       # near-constant
    for density in (0.01, 0.05, 0.25):
        for seg in segs:
            counts = {}
            for backend in ("ref", "kernel"):
                codec = make_codec("dgc", density=density, backend=backend)
                counts[backend] = int(codec.sent_elems(codec.encode(seg)))
            assert counts["ref"] == counts["kernel"], (density, seg.shape)


# ------------------------------------- strategy backend parity (subprocess)
SCRIPT_BACKEND_PARITY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.train import Strategy

KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (64, 1))
def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    X = jax.random.normal(k, (16, 64))
    return {"X": X, "y": X @ W_TRUE}
def grad_fn(params, batch):
    def loss(p):
        return jnp.mean((batch["X"] @ p["W"] - batch["y"]) ** 2)
    return jax.value_and_grad(loss)(params)
P0 = {"W": jnp.zeros((64, 1)), "b": jnp.zeros((4096,))}

# --- compressed cells: kernel backend inside the existing loss bands ---
for comp in ("onebit", "terngrad", "qsgd"):
    runs = {}
    for kb in ("ref", "kernel"):
        eng = Strategy.parse(f"bsp/ring/{comp}@4", lr=0.05,
                             backend="device", wire="measured",
                             kernel_backend=kb).build(grad_fn)
        runs[kb] = eng.run(P0, make_batch, 3)
    lr_ = [h["loss"] for h in runs["ref"][1]]
    lk = [h["loss"] for h in runs["kernel"][1]]
    ld = max(abs(a - b) for a, b in zip(lr_, lk))
    assert ld <= 1e-4, (comp, lr_, lk)
    assert runs["ref"][2] == runs["kernel"][2], comp   # measured wire bytes
print("CODEC-BACKEND-PARITY-OK")

# --- none cells: the backend knob must be a bitwise no-op ---
for topo in ("ring", "tree", "butterfly"):
    runs = {}
    for kb in ("ref", "kernel"):
        eng = Strategy.parse(f"bsp/{topo}/none@4", lr=0.05,
                             backend="device", wire="measured",
                             kernel_backend=kb).build(grad_fn)
        runs[kb] = eng.run(P0, make_batch, 3)
    for a, b in zip(jax.tree.leaves(runs["ref"][0]),
                    jax.tree.leaves(runs["kernel"][0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss"] for h in runs["ref"][1]] == \
           [h["loss"] for h in runs["kernel"][1]], topo
    assert runs["ref"][2] == runs["kernel"][2], topo
print("NONE-BACKEND-BITWISE-OK")
"""


def test_strategy_kernel_backend_parity_4dev(multidevice):
    out = multidevice(SCRIPT_BACKEND_PARITY, 4)
    assert "CODEC-BACKEND-PARITY-OK" in out
    assert "NONE-BACKEND-BITWISE-OK" in out


# ---------------------------- ISSUE acceptance cell (subprocess, 8 devices)
SCRIPT_ONEBIT8 = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.data import LMDataConfig, make_lm_batches
from repro.models import build_model
from repro.train import Strategy

cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4)
batches = make_lm_batches(data)
def grad_fn(p, batch):
    (loss, _), g = jax.value_and_grad(
        lambda pp: model.loss_fn(pp, batch, compute_dtype=jnp.float32),
        has_aux=True)(p)
    return loss, g

runs = {}
for kb in ("ref", "kernel"):
    eng = Strategy.parse("bsp/ring/onebit@8", lr=0.01, backend="device",
                         wire="measured", kernel_backend=kb).build(grad_fn)
    _, hist, wire = eng.run(params, batches, 4)
    m = eng.metrics()
    runs[kb] = ([h["loss"] for h in hist], wire,
                m["measured_step_tx_bytes"] / m["fp32_step_tx_bytes"])
ld = max(abs(a - b) for a, b in zip(runs["ref"][0], runs["kernel"][0]))
assert ld <= 1e-4, (ld, runs["ref"][0], runs["kernel"][0])
assert runs["ref"][1] == runs["kernel"][1], runs   # bitwise wire bytes
assert runs["ref"][2] <= 0.05, runs["ref"][2]      # the 0.039x fp32-ring cell
print(f"ONEBIT8-BACKEND-OK loss_delta={ld:.2e} "
      f"bytes_ratio={runs['ref'][2]:.4f}")
"""


def test_onebit8_kernel_backend_acceptance(multidevice):
    out = multidevice(SCRIPT_ONEBIT8, 8)
    assert "ONEBIT8-BACKEND-OK" in out
