"""The flash-attention forward and backward at latent attention's shapes
(DeepSeek-V2): q/k heads of 192 (128 nope + 64 rope), v heads of 128, and
an explicit softmax scale, in interpret mode against the jnp oracle."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels.flash_attention.flash_attention import plan

# DeepSeek-V2's YaRN softmax scale: mscale(40, 0.707)^2 / sqrt(192)
SCALE = (0.1 * 0.707 * float(jnp.log(40.0)) + 1.0) ** 2 / 192 ** 0.5


def _qkv(B, S, H, hd, hdv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, H, hd), dtype),
            jax.random.normal(ks[2], (B, S, H, hdv), dtype))


def test_plan_at_deepseek_widths():
    """Two heads a step (384 q/k lanes, 256 v lanes), 512-row blocks, and
    StableLM's plan as it was."""
    pn = plan(4096, 16, 16, 192, jnp.bfloat16, hdv=128)
    assert (pn.block_q, pn.block_k, pn.heads, pn.kv_heads) == (512, 512, 2, 2)
    assert (pn.pairs, pn.nq * pn.nk) == (36, 64)
    assert plan(2048, 32, 32, 64, jnp.bfloat16) == plan(
        2048, 32, 32, 64, jnp.bfloat16, hdv=64)


@pytest.mark.parametrize("S,block", [(256, None), (200, 64)],
                         ids=["planned", "padded-b64"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_qk192_v128_scaled(S, block, dtype):
    q, k, v = _qkv(2, S, 4, 192, 128, dtype)
    out = FA.attention(q, k, v, causal=True, scale=SCALE, block_q=block,
                       block_k=block)
    ref = FA.attention_ref(q, k, v, causal=True, scale=SCALE)
    assert out.shape == (2, S, 4, 128)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < tol


def test_attention_grad_qk192_v128_scaled():
    """Flash forward and flash backward at the same shapes and scale:
    value and every input gradient match the oracle."""
    q, k, v = _qkv(1, 128, 2, 192, 128, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True,
                                          scale=SCALE) ** 2)
    vk, gk = jax.value_and_grad(loss(FA.attention_grad),
                                argnums=(0, 1, 2))(q, k, v)
    vr, gr = jax.value_and_grad(loss(FA.attention_ref),
                                argnums=(0, 1, 2))(q, k, v)
    assert abs(float(vk - vr)) < 1e-2 * float(vr)
    for a, b in zip(gk, gr):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


@pytest.mark.parametrize("S", [256, 200], ids=["planned", "padded"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_bwd_qk192_v128_scaled(S, dtype):
    """The Pallas backward's dq, dk (192 wide) and dv (128 wide) against
    jax.vjp of the fp32 oracle for a random cotangent, two heads a block
    and two blocks of heads: float32 to rounding, bfloat16 to 2% of the
    largest gradient."""
    q, k, v = _qkv(2, S, 4, 192, 128, dtype)
    g = jax.random.normal(jax.random.PRNGKey(8), (2, S, 4, 128), dtype)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    kw = dict(causal=True, scale=SCALE)
    gk = jax.vjp(lambda *a: FA.attention_grad(*a, **kw), q, k, v)[1](g)
    gr = jax.vjp(lambda *a: FA.attention_ref(*a, **kw), *f32[:3])[1](f32[3])
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(gk, gr):
        assert a.shape == b.shape and a.dtype == dtype
        err = jnp.max(jnp.abs(a.astype(jnp.float32) - b))
        assert float(err) < tol * float(jnp.max(jnp.abs(b)))
