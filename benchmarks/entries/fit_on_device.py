"""``ComputationGraph.fit_on_device(xs, ys, epochs, batch_size)``: each call
casts and uploads the host batches once and scans them ``epochs`` times."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..harness.snapshots import Snapshots


class Entry:
    def __init__(self, program, net, data, traffic, devices):
        self.program, self.net = program, net
        self.x, self.y = data
        self.batch = traffic["batch"]
        self.epochs = traffic["epochs_per_call"]
        self.follow = traffic["follow_steps"]
        self.snapshots = traffic["snapshots"]
        if (self.snapshots != [traffic["batches"]]
                or self.follow != traffic["batches"]):
            raise ValueError("fit_on_device's smallest unit is one scanned "
                             "launch of all the batches: follow and snapshot "
                             "exactly that many steps")

    def first_steps(self) -> dict:
        """The window's own call with one epoch: the compiled launch of
        ``batches`` scanned steps, from the seed's weights."""
        net = self.net
        p0 = jax.tree.map(jnp.copy, self.program.params(net))
        snap = Snapshots(self.program.params, self.program.first_moment, p0,
                         self.snapshots)
        losses = net.fit_on_device(self.x, self.y, epochs=1,
                                   batch_size=self.batch)
        snap.take(net, self.follow)
        return snap.record(losses)

    def warm(self):
        """Nothing more: the first steps ran the one program the window
        runs."""

    def call(self):
        """One call; it returns when its losses are on the host.
        -> (examples completed, steps whose loss is not finite)"""
        losses = self.net.fit_on_device(self.x, self.y, epochs=self.epochs,
                                        batch_size=self.batch)
        return (self.epochs * self.x.shape[0],
                int((~np.isfinite(losses)).sum()))

    def sync(self):
        jax.block_until_ready(self.net.params)

    def release(self):
        self.net = self.x = self.y = None
