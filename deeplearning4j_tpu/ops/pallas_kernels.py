"""Pallas TPU kernels for hot ops.

The SURVEY.md §7.2 M5 note ("+Pallas fused cell if needed for perf") and
§7.3 item 3 flag the LSTM cell as the op worth hand-fusing: per scan step
the lax path emits two matmuls plus a chain of elementwise gate ops, and
although XLA fuses most of the chain, the fused kernel keeps gates, state
update, and both matmuls in VMEM with one HBM round-trip per step.

Kernel strategy: single-block (whole operands in VMEM) — LSTM step
operands are [B,F]/[F,4U] sized, far under the ~16 MB VMEM budget for any
practical cell; ``fits_vmem`` guards the dispatch and callers fall back to
``nnops.lstm_cell`` above the budget or off-TPU. Forward-only: the scan
layers call this under ``jax.checkpoint``-free inference/streaming paths;
training keeps the lax cell (custom VJP for the kernel is not worth the
maintenance while XLA's fused backward is this close).

NEGATIVE RESULT (round 3, recorded so it is not retried): a fused
1x1-conv backward kernel (dX + dW from one pass over dY, f32 VMEM
accumulator across a row-tiled grid) was numerically correct but ~50%
SLOWER than XLA's derived backward on the real v5e chip (ResNet-50 step
54 -> 80 ms), and even rerouting the 1x1 forward from lax.conv to a dot
(no Pallas) cost ~20% — XLA's conv fusions carry layout/epilogue
decisions a naive contraction loses. Don't fight the conv pipeline with
hand kernels here; the remaining bwd HBM traffic is structural.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

_VMEM_BUDGET = 8 * 1024 * 1024  # conservative half of ~16MB VMEM


def available() -> bool:
    """Pallas TPU lowering available on the default backend?"""
    return jax.default_backend() == "tpu"


_trace = threading.local()


@contextlib.contextmanager
def gspmd_trace(mesh, model_axis=None):
    """Held around the TRACE of a program that GSPMD partitions over
    ``mesh`` (``ParallelWrapper``'s step, a serving engine's lowering on a
    mesh). The TPU compiler refuses such a program when it holds a Mosaic
    kernel ("Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map"), so while this is held the dispatchers
    take their reference path, counted ``*fallback_gspmd``, except where
    they open a ``shard_map`` themselves: the decode kernels do, over
    ``model_axis`` when the heads divide it. A mesh of one device
    partitions nothing and arms nothing. Trace-time state like the
    dispatch modes, and per thread: a trace runs on one thread, and an
    engine that another thread traces meanwhile keeps its kernels."""
    was = partitioned()
    _trace.held = None if mesh is None or mesh.devices.size < 2 \
        else (mesh, model_axis)
    try:
        yield
    finally:
        _trace.held = was


def partitioned():
    """``(mesh, model_axis)`` while this thread is inside
    :func:`gspmd_trace` over more than one device, else None."""
    return getattr(_trace, "held", None)


def fits_vmem(batch: int, n_in: int, units: int, bytes_per: int = 4) -> bool:
    total = (batch * n_in + batch * units * 2      # x, h, c
             + n_in * 4 * units + units * 4 * units  # W, RW
             + 4 * units                            # b
             + batch * 4 * units                    # z scratch
             + batch * units * 2) * bytes_per       # outputs
    return total < _VMEM_BUDGET


def _lstm_kernel(forget_bias, x_ref, h_ref, c_ref, w_ref, rw_ref, b_ref,
                 h_out, c_out):
    z = (jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
         + jnp.dot(h_ref[:], rw_ref[:], preferred_element_type=jnp.float32)
         + b_ref[:])
    u = z.shape[-1] // 4
    i = jax.nn.sigmoid(z[:, :u])
    f = jax.nn.sigmoid(z[:, u:2 * u] + forget_bias)
    o = jax.nn.sigmoid(z[:, 2 * u:3 * u])
    g = jnp.tanh(z[:, 3 * u:])
    c_new = f * c_ref[:].astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    h_out[:] = h_new.astype(h_out.dtype)
    c_out[:] = c_new.astype(c_out.dtype)


def lstm_cell_fused(x, h, c, w_ih, w_hh, b, forget_bias: float = 0.0,
                    interpret: bool = False):
    """Fused LSTM step (gate order [i,f,o,g], matching nnops.lstm_cell).

    All operands land in VMEM; both matmuls accumulate f32 on the MXU and
    the whole gate chain runs before anything returns to HBM. Raises
    ValueError when the operands exceed the VMEM budget — callers guard
    with :func:`fits_vmem` and fall back to the lax cell.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, F = x.shape
    U = w_hh.shape[0]
    if not fits_vmem(B, F, U, np.dtype(x.dtype).itemsize):
        raise ValueError(
            f"lstm_cell_fused operands exceed the VMEM budget "
            f"(B={B}, F={F}, U={U}); use nnops.lstm_cell")
    kernel = functools.partial(_lstm_kernel, float(forget_bias))
    spec = pl.BlockSpec(memory_space=pl.ANY if interpret else pltpu.VMEM)
    h_new, c_new = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, U), x.dtype),
                   jax.ShapeDtypeStruct((B, U), x.dtype)),
        in_specs=[spec] * 6,
        out_specs=(spec, spec),
        interpret=interpret,
        name="lstm_cell",
    )(x, h, c, w_ih, w_hh, b)
    return h_new, c_new
