"""``ComputationGraph.fit(iterator)`` over an ``AsyncDataSetIterator`` with
device prefetch: the entry point every DL4J user calls. One call is one pass
over the host batches, a dispatch per step, nothing read back."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..harness.snapshots import Snapshots


class Entry:
    def __init__(self, program, net, data, traffic, devices):
        from deeplearning4j_tpu.data.dataset import (AsyncDataSetIterator,
                                                     NumpyDataSetIterator)
        self.program, self.net = program, net
        x, y = data
        self.batch = traffic["batch"]
        self.n = x.shape[0]
        self.follow = traffic["follow_steps"]
        self.snapshots = traffic["snapshots"]
        self.it = AsyncDataSetIterator(
            NumpyDataSetIterator(x, y, batch_size=self.batch),
            device_prefetch=True)
        self._last = None

    def _fit(self):
        self.net.fit(self.it)

    def first_steps(self) -> dict:
        """The window's own call, once, with a listener that reads the
        optimizer's state and the parameters' change after the followed
        steps and every step's loss."""
        net = self.net
        p0 = jax.tree.map(jnp.copy, self.program.params(net))
        snap = Snapshots(self.program.params, self.program.first_moment, p0,
                         self.snapshots, read_loss=lambda m: m._score)
        had = list(net._listeners)
        net.set_listeners(*had, snap)
        try:
            self._fit()
        finally:
            net.set_listeners(*had)
        return snap.record(snap.losses[:self.follow])

    def warm(self):
        self.call()

    def call(self):
        """One pass; then wait for the pass before it, so that at most one
        call's work is in flight when the window closes."""
        bad = 0
        if self._last is not None:
            bad = int(not np.isfinite(float(self._last)))
        self._fit()
        self._last = self.net._score
        return self.n, bad

    def sync(self):
        jax.block_until_ready(self.net.params)

    def release(self):
        self.net = self.it = None
