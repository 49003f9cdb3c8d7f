"""Federated learning (survey §3.3.1(3)): FedAvg on IID vs Dirichlet
non-IID client splits, reproducing the degradation Nilsson et al. [130]
report for the non-IID regime.

  PYTHONPATH=src python examples/federated_noniid.py
"""
import jax
import jax.numpy as jnp

from repro.core.federated import FedConfig, run_fedavg
from repro.launch.env import use_compile_cache
from repro.data.partition import (dirichlet_partition, iid_partition,
                                  label_skew, make_classification_data)

N, DIM, CLASSES, CLIENTS = 1500, 16, 8, 10


def main():
    use_compile_cache()
    X, y = make_classification_data(N, DIM, CLASSES, seed=0)

    def grad_fn(params, batch):
        def loss(p):
            h = jnp.tanh(batch["X"] @ p["w1"])
            logits = h @ p["w2"]
            logz = jax.nn.logsumexp(logits, -1)
            ll = jnp.take_along_axis(logits, batch["y"][:, None], 1)[:, 0]
            return jnp.mean(logz - ll)
        return jax.value_and_grad(loss)(params)

    def clients_for(parts):
        import numpy as np
        fns = []
        for idx in parts:
            def fn(step, idx=idx):
                rng = np.random.RandomState(step)
                sel = idx[rng.randint(0, len(idx), size=min(32, len(idx)))]
                return {"X": jnp.asarray(X[sel]), "y": jnp.asarray(y[sel])}
            fns.append(fn)
        return fns

    cfg = FedConfig(num_clients=CLIENTS, clients_per_round=5, local_steps=4,
                    local_lr=0.1)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    p0 = {"w1": jax.random.normal(k1, (DIM, 32)) * 0.2,
          "w2": jax.random.normal(k2, (32, CLASSES)) * 0.2}

    for name, parts in [
            ("iid", iid_partition(N, CLIENTS, seed=0)),
            ("non-iid (alpha=0.1)", dirichlet_partition(y, CLIENTS, 0.1,
                                                        seed=0))]:
        _, hist = run_fedavg(p0, clients_for(parts), grad_fn, cfg, 15)
        print(f"{name:22s} skew={label_skew(parts, y):.2f}  "
              f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
