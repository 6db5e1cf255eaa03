"""Quickstart: train a MiRU classifier with DFA in ~30 seconds on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dfa import dfa_grads, sgd_kwta_update
from repro.core.miru import (MiRUConfig, init_dfa_feedback,
                             init_miru_params, miru_forward)
from repro.data.synthetic import make_permuted_tasks
from repro.utils import accuracy


def main():
    task = make_permuted_tasks(seed=0, n_tasks=1, n_train=800,
                               n_test=300)[0]
    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10, beta=0.8, lam=0.5)
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    psi = init_dfa_feedback(jax.random.PRNGKey(1), cfg)

    @jax.jit
    def step(params, xb, yb):
        loss, grads = dfa_grads(params, psi, cfg, xb, yb)
        params, _ = sgd_kwta_update(params, grads, lr=0.2, keep_frac=0.57,
                                    hidden_lr_scale=0.3)
        return params, loss

    rng = np.random.default_rng(0)
    for it in range(400):
        idx = rng.integers(0, task.x_train.shape[0], 64)
        params, loss = step(params, jnp.asarray(task.x_train[idx]),
                            jnp.asarray(task.y_train[idx]))
        if it % 100 == 0:
            logits, _ = miru_forward(params, cfg,
                                     jnp.asarray(task.x_test))
            acc = accuracy(logits, jnp.asarray(task.y_test))
            print(f"step {it:4d}  loss {float(loss):.3f}  "
                  f"test acc {float(acc):.3f}")

    logits, _ = miru_forward(params, cfg, jnp.asarray(task.x_test))
    print(f"final test accuracy (DFA + K-WTA): "
          f"{float(accuracy(logits, jnp.asarray(task.y_test))):.3f}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
