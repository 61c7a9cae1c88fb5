"""What the plain references share: operand rounding for the lower
precisions, a PRNG key from any seed, and weights from a table of leaves."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def round_operand(x, precision):
    """Round one operand of a convolution or matrix product (accumulation
    stays float32): ``float32`` leaves it alone, ``bfloat16`` rounds it to
    bfloat16, ``fp8`` to float8_e4m3fn after scaling the tensor's largest
    magnitude to the format's largest value."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = _E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
        # straight-through: the backward pass sees the rounded operands of
        # the other side and an identity here, as a low-precision matmul does
        return x + lax.stop_gradient(q - x)
    raise ValueError(f"unknown precision {precision!r}")


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def make_weights(table, seed: int) -> dict:
    """``table``: ``[(leaf name, shape, init)]`` with init ``("normal",
    std)``, ``("const", value)``, ``"ones"`` or ``"zeros"``. All leaves
    float32, made on the device in one jitted call."""

    @jax.jit
    def make(key):
        leaves = {}
        for i, (name, shape, init) in enumerate(table):
            if init == "ones":
                leaves[name] = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                leaves[name] = jnp.zeros(shape, jnp.float32)
            elif init[0] == "const":
                leaves[name] = jnp.full(shape, init[1], jnp.float32)
            else:
                leaves[name] = init[1] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return leaves

    return make(seed_key(seed))
