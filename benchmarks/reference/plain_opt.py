"""The optimizer of the reference's training steps, in plain ``jax.numpy``.

Adam as Kingma and Ba (2015) give it in algorithm 1: moments m and v from
nought, both corrected for their start, the step ``lr * m_hat / (sqrt(v_hat)
+ eps)``.  No weight decay, no schedule: a configuration that trains with
either has to bring a reference for it.  Written from the paper, no import
from the program; the hyperparameters come from the configuration file's
``check.optimizer``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ADAM_DEFAULTS = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}


def hyper(optimizer: dict) -> dict:
    """``check.optimizer`` of a configuration file, its defaults filled in."""
    if optimizer.get("name") != "adam":
        raise ValueError(f"the reference follows Adam only; the "
                         f"configuration states {optimizer.get('name')!r}")
    return {k: float(optimizer.get(k, d)) for k, d in ADAM_DEFAULTS.items()}


def adam_init(params):
    def zeros():        # a tree each: the step donates both
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.float32)}


def adam_update(grads, state, params, lr, b1, b2, eps):
    t = state["t"] + 1.0
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                     grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t))
        / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), params, m, v)
    return params, {"m": m, "v": v, "t": t}


def first_gradient(first_moment, b1: float):
    """The gradient the optimizer got in its first step, from its state
    after that step: m_1 = (1 - b1) * g_1, since m_0 = 0."""
    return jax.tree.map(lambda m: m / (1.0 - b1), first_moment)
