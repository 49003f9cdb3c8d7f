"""Multi-device (8 virtual hosts) tests: allreduce topologies == psum,
PS push/pull == allreduce SGD, GPipe == sequential."""
import pytest

SCRIPT_TOPO = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.allreduce import TOPOLOGIES
mesh = Mesh(np.array(jax.devices()).reshape(8), ("w",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 40))
for name, fn in TOPOLOGIES.items():
    f = jax.shard_map(lambda a, _fn=fn: _fn(a[0], "w")[None], mesh=mesh,
                      in_specs=P("w", None), out_specs=P("w", None),
                      check_vma=False)
    out = f(x)
    expect = jnp.broadcast_to(x.sum(0)[None], (8, 40))
    err = float(jnp.max(jnp.abs(out - expect)))
    assert err < 1e-4, (name, err)
# odd-size tensor through ring (padding path)
y = jax.random.normal(jax.random.PRNGKey(1), (8, 37))
f = jax.shard_map(lambda a: TOPOLOGIES["ring"](a[0], "w")[None], mesh=mesh,
                  in_specs=P("w", None), out_specs=P("w", None),
                  check_vma=False)
err = float(jnp.max(jnp.abs(f(y) - y.sum(0)[None])))
assert err < 1e-4, err
print("TOPO-OK")
"""

SCRIPT_PS = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.parameter_server import make_ps_step
mesh = Mesh(np.array(jax.devices()).reshape(8), ("w",))
def update(p_sh, g_sh, opt):
    return jax.tree.map(lambda a, b: a - 0.1 * b, p_sh, g_sh), opt
ps = make_ps_step(update, "w")
pp = {"W": jax.random.normal(jax.random.PRNGKey(0), (13, 3)),
      "b": jnp.ones((5,))}
gg = jax.tree.map(lambda x: jnp.stack([x * 0 + i for i in range(8)]), pp)
f = jax.shard_map(lambda p, g: ps(p, jax.tree.map(lambda a: a[0], g), None)[0],
                  mesh=mesh, in_specs=(P(), P("w")), out_specs=P(),
                  check_vma=False)
newp = f(pp, gg)
expect = jax.tree.map(lambda x: x - 0.1 * sum(range(8)), pp)
for a, b in zip(jax.tree.leaves(newp), jax.tree.leaves(expect)):
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4
print("PS-OK")
"""

SCRIPT_PIPE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.pipeline import gpipe_forward, bubble_fraction
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("stage", "data"))
stacked = jnp.stack([jnp.eye(6) * (i + 1) + 0.01 * i for i in range(4)])
xm = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 6))
def stage_fn(w, x):
    return jnp.tanh(x @ w)
f = jax.shard_map(lambda w, x: gpipe_forward(stage_fn, w[0], x, "stage")[None],
                  mesh=mesh, in_specs=(P("stage"), P(None)),
                  out_specs=P("stage"), check_vma=False)
out = f(stacked, xm).sum(0)      # only last stage nonzero
seq = xm
for i in range(4):
    seq = jnp.tanh(seq @ stacked[i])
assert float(jnp.max(jnp.abs(out - seq))) < 1e-5
# gradient flows through the pipeline
g = jax.grad(lambda w: jnp.sum(f(w, xm) ** 2))(stacked)
assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).sum()) > 0
assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
print("PIPE-OK")
"""


def test_allreduce_topologies(multidevice):
    assert "TOPO-OK" in multidevice(SCRIPT_TOPO, 8)


def test_parameter_server(multidevice):
    assert "PS-OK" in multidevice(SCRIPT_PS, 8)


def test_gpipe(multidevice):
    assert "PIPE-OK" in multidevice(SCRIPT_PIPE, 8)
