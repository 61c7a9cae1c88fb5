"""The configurations' optimizers, written from their definitions.

Shared by the plain references. ``spec`` is the configuration's
``assumed.updater`` object: ``{"kind": "nesterovs", "learning_rate", "momentum"}``
or ``{"kind": "adam", "learning_rate", "beta1", "beta2", "epsilon"}``.
Step numbers start at 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_state(spec, params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    if spec["kind"] == "nesterovs":
        return {"v": zeros}
    if spec["kind"] == "adam":
        return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params)}
    raise ValueError(f"unknown updater {spec['kind']!r}")


def apply(spec, grads, state, params, step):
    """-> (new params, new state)."""
    lr = spec["learning_rate"]
    if spec["kind"] == "nesterovs":
        mu = spec["momentum"]
        v = jax.tree.map(lambda v, g: mu * v - lr * g, state["v"], grads)
        new = jax.tree.map(lambda p, vn, g: p + mu * vn - lr * g,
                           params, v, grads)
        return new, {"v": v}
    if spec["kind"] == "adam":
        b1, b2, eps = spec["beta1"], spec["beta2"], spec["epsilon"]
        t = step + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                         state["v"], grads)
        a = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new = jax.tree.map(lambda p, m, v: p - a * m / (jnp.sqrt(v) + eps),
                           params, m, v)
        return new, {"m": m, "v": v}
    raise ValueError(f"unknown updater {spec['kind']!r}")


def first_moment(spec, state):
    """The state the optimizer keeps of the gradients it was handed: after
    one step it is the first gradient times a constant, so its norm against
    the reference's is the first gradient's."""
    return state["v"] if spec["kind"] == "nesterovs" else state["m"]
