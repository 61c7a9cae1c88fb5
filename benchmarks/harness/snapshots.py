"""A listener on the program's own ``iteration_done`` hook that keeps, after
the followed steps, the per-leaf norms the comparison reads, and host copies of
the optimizer's first moment at the first of them and of the parameters'
change at the last. Attached for the cell's first call only."""

from __future__ import annotations

import jax

from . import compare


class Snapshots:
    """``opt[step]`` and ``delta[step]`` as device scalars per leaf, and every
    step's loss as the program's lazy device scalar. ``first_moment`` is
    None where the entry point keeps the optimizer's state to itself."""

    def __init__(self, params, first_moment, p0, steps, read_loss=None):
        self.params, self.first_moment = params, first_moment
        self.p0, self.steps = p0, set(steps)
        self.read_loss = read_loss
        self.opt, self.delta, self.losses = {}, {}, []
        self.opt_t, self.delta_t = {}, {}
        self._delta = jax.jit(lambda p, p0: compare.leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, p0)))
        self._norms = jax.jit(compare.leaf_norms)
        self._diff = jax.jit(lambda p, p0: jax.tree.map(
            lambda a, b: a - b, p, p0))
        self._start = None

    def iteration_done(self, model, iteration, epoch):
        if self._start is None:
            self._start = iteration - 1
        if self.read_loss is not None:
            self.losses.append(self.read_loss(model))
        self.take(model, iteration - self._start)

    def take(self, model, step):
        """Keep what the comparison reads if ``step`` is a followed one."""
        if step in self.steps:
            p = self.params(model)
            if not self.delta:
                # once: the initial copy beside the parameters, wherever the
                # entry point has placed them since
                self.p0 = jax.device_put(
                    self.p0, jax.tree.map(lambda a: a.sharding, p))
            self.delta[step] = self._delta(p, self.p0)
            if step == max(self.steps):
                self.delta_t[step] = jax.device_get(self._diff(p, self.p0))
            if self.first_moment is not None:
                m = self.first_moment(model)
                self.opt[step] = self._norms(m)
                if step == min(self.steps):
                    self.opt_t[step] = jax.device_get(m)

    def on_epoch_end(self, model):
        pass

    def record(self, losses) -> dict:
        return {"losses": [float(l) for l in losses],
                "opt": {s: compare.to_floats(v) for s, v in self.opt.items()},
                "delta": {s: compare.to_floats(v)
                          for s, v in self.delta.items()},
                "opt_t": self.opt_t, "delta_t": self.delta_t}
