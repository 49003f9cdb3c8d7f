"""Each configuration's plain reference against the program's forward on
the reference's own weights, at a tiny size in float32 on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip.reference import dense_lm

DENSE = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, vocab_size=256, num_hidden_layers=2,
             layer_norm_eps=1e-5, rope_theta=10000)
DENSE_PROG = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                  head_dim=16, d_ff=128, vocab_size=256)


@pytest.mark.parametrize("ref,c,arch,prog", [
    (dense_lm, DENSE, "stablelm-1.6b", DENSE_PROG),
], ids=["dense_lm"])
def test_reference_matches_the_program_forward(ref, c, arch, prog):
    from repro.configs import get_config
    from repro.models import build_model
    model = build_model(dataclasses.replace(get_config(arch), **prog))
    params = ref.init(c, jax.random.PRNGKey(3))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.forward(params, tokens, compute_dtype=jnp.float32)[0]
        exp = ref.forward(c, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-4,
                               atol=2e-4)
