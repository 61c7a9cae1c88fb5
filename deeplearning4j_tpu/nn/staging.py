"""How ``fit_on_device`` (``nn/engine.py``, both engines') stages a host
array: reshaped to ``[n_batches, batch, ...]`` and placed on the device
once a call, features cast to the network's dtype on the way.

A cast runs on a pool of up to 8 host threads, each ``np.copyto`` writing
one contiguous run of rows into its slice of one preallocated output:
numpy's cast releases the interpreter lock, so the runs cast in parallel,
and element by element the result is ``a.astype(dt)`` bit for bit. The
cast array is then uploaded whole, a byte copy; per-batch uploads joined
on the device would not be one (on a TPU the stack flushes subnormals and
drops a NaN's sign). Everything else (ids, labels, an array already in the
network's dtype) is staged as one array, as before.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import telemetry as _tel

#: the most host threads one cast uses
MAX_THREADS = 8

_STAGE = _tel.counter(
    "train.stage",
    "fit_on_device's stacked arrays, once each: cast on host threads "
    "(parallel, cast) or staged as one array (whole, no_cast)")


def cast_rows(a: np.ndarray, dt) -> np.ndarray:
    """``a.astype(dt)``, its rows split into ``min(rows, cpu count, 8)``
    contiguous runs, each cast on a pool thread into its slice of one
    output."""
    out = np.empty(a.shape, dt)
    n = max(1, min(len(a), os.cpu_count() or 1, MAX_THREADS))
    edges = [len(a) * k // n for k in range(n + 1)]
    with ThreadPoolExecutor(n, thread_name_prefix="fit-stage") as pool:
        for f in [pool.submit(np.copyto, out[i:j], a[i:j], casting="unsafe")
                  for i, j in zip(edges, edges[1:])]:
            f.result()
    return out


def stack(a: np.ndarray, nb: int, b: int, dt, cast: bool,
          span_labels: dict) -> jax.Array:
    """The first ``nb * b`` rows of ``a`` as one ``[nb, b, ...]`` device
    array, inside one ``train.phase.stage_s`` span. With ``cast`` a floating
    ``a`` takes the floating ``dt`` (features get the cast ``fit()`` applies
    in the forward pass; labels keep their precision, since the loss
    computes in float32 and pre-rounded regression targets would diverge
    from ``fit()``)."""
    with _tel.span("train.phase.stage_s", span_labels):
        a = a[:nb * b]
        if cast and np.issubdtype(a.dtype, np.floating) \
                and jnp.issubdtype(dt, jnp.floating) and a.dtype != dt:
            a = cast_rows(a, dt)
            _STAGE.inc(decision="parallel", why="cast")
        else:
            _STAGE.inc(decision="whole", why="no_cast")
        return jax.device_put(jnp.asarray(a.reshape((nb, b) + a.shape[1:])))
