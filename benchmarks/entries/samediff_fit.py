"""``SameDiff.fit(feeds)`` over device-resident feed dicts: one compiled
step a feed, the loss read on the host after every step."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..harness.snapshots import Snapshots


class Entry:
    def __init__(self, program, sd, data, traffic, devices):
        self.program, self.sd = program, sd
        x, y = data
        b = traffic["batch"]
        self.feeds = [{program.INPUT: jax.device_put(jnp.asarray(x[i:i + b])),
                       "labels": jax.device_put(jnp.asarray(y[i:i + b]))}
                      for i in range(0, x.shape[0], b)]
        self.batch = b
        self.follow = traffic["follow_steps"]
        self.snapshots = traffic["snapshots"]

    def first_steps(self) -> dict:
        """The window's own call, once, with a listener that reads the
        parameters' change after the followed steps. The optimizer's state
        never leaves ``fit``, so the record has no ``opt``."""
        p0 = jax.tree.map(jnp.copy, self.program.params(self.sd))
        snap = Snapshots(self.program.params, None, p0, self.snapshots)
        hist = self.sd.fit(self.feeds, listeners=[snap])
        return snap.record(hist.losses[:self.follow])

    def warm(self):
        """Once more without the listener, as the window calls it."""
        self.sd.fit(self.feeds)

    def call(self):
        hist = self.sd.fit(self.feeds)
        bad = sum(not math.isfinite(l) for l in hist.losses)
        return len(self.feeds) * self.batch, bad

    def sync(self):
        jax.block_until_ready(self.program.params(self.sd))

    def release(self):
        self.sd = self.feeds = None
