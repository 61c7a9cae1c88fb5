"""Drive a plain reference through a cell's first steps.

Used after the window (float32, the comparison's reference side) and by
``tools/control.py`` (the reference in the program's place at a lower
precision, or with a fault planted). One jitted step, called once per batch:
value-and-gradient of the reference's loss, then the configuration's
optimizer from ``reference/optim.py``.
"""

from __future__ import annotations

import functools
import time

import jax

from . import compare
from ..reference import optim


def follow(ref, cfg, seed, batches, snapshots, precision="float32",
           fault=None, devices=None, log=None):
    """-> record (see ``compare``). ``batches`` is a list of tuples of device
    or host arrays, one per step; ``snapshots`` the 1-based steps after which
    the optimizer state and the parameters' change are kept. ``fault``:
    ``None``, ``"half_batch"`` (the second half of every batch is left out and
    the mean taken over the rest) or ``"no_exchange:<n>"`` (only the first of
    ``n`` equal shards reaches the update). On more than one of ``devices``
    the rows of every batch are laid over them and the weights copied to
    each, so that float32 at a four-chip cell's global batch fits; the
    reference's code is the same and the compiler adds the exchange."""
    spec = cfg["assumed"]["updater"]
    p0 = ref.init_weights(seed, cfg)
    params, state = p0, optim.init_state(spec, p0)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, i, batch):
        l, g = jax.value_and_grad(ref.loss)(params, batch, cfg, precision)
        new, state = optim.apply(spec, g, state, params, i)
        return new, state, l, compare.leaf_norms(g)

    @jax.jit
    def snap(params, p0, state):
        return (compare.leaf_norms(optim.first_moment(spec, state)),
                compare.leaf_norms(jax.tree.map(lambda a, b: a - b,
                                                params, p0)))

    # everything committed to its device before the first step, so that the
    # second step finds the first one's program (an uncommitted argument
    # beside committed ones is another signature, and another compile)
    home = jax.sharding.SingleDeviceSharding(
        devices[0] if devices else jax.devices()[0])
    p0, state = jax.device_put((p0, state), home)
    place = lambda batch: jax.device_put(batch, home)
    if devices is not None and len(devices) > 1:
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.array(devices), ("data",))
        rows = NamedSharding(mesh, PartitionSpec("data"))
        every = NamedSharding(mesh, PartitionSpec())
        p0, state = jax.device_put((p0, state), every)
        place = lambda batch: jax.device_put(batch, rows)
    rec = {"losses": [], "opt": {}, "delta": {}, "opt_t": {}, "delta_t": {}}
    # the initial weights are made twice so that the step may donate one copy
    params = jax.device_put(ref.init_weights(seed, cfg),
                            jax.tree.map(lambda a: a.sharding, p0))
    for i, batch in enumerate(batches):
        if fault == "half_batch":
            batch = tuple(a[: a.shape[0] // 2] for a in batch)
        elif fault and fault.startswith("no_exchange:"):
            n = int(fault.split(":")[1])
            batch = tuple(a[: a.shape[0] // n] for a in batch)
        t0 = time.perf_counter()
        params, state, l, gn = step(params, state, i, place(batch))
        rec["losses"].append(float(l))
        if log:
            log(f"reference step {i + 1}: {time.perf_counter() - t0:.1f}s")
        if i == 0:
            rec["grad1"] = compare.to_floats(gn)
        if i + 1 in snapshots:
            m, d = snap(params, p0, state)
            rec["opt"][i + 1] = compare.to_floats(m)
            rec["delta"][i + 1] = compare.to_floats(d)
            # the tensors themselves at the first and the last snapshot
            if i + 1 == min(snapshots):
                rec["opt_t"][i + 1] = jax.tree.map(
                    jax.numpy.copy, optim.first_moment(spec, state))
            if i + 1 == max(snapshots):
                rec["delta_t"][i + 1] = jax.tree.map(lambda a, b: a - b,
                                                     params, p0)
    return rec
