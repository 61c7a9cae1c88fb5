"""The attention operation's share of the busiest device's busy time inside
the traced window (``device_trace``): the operations under the ``attn.full``,
``attn.window`` and ``attn.latent`` scopes of ``ops/causal_attention.py``,
which hold the moves into the kernels' or the blocks' layout, the scores,
the softmax and the value product, forward, recomputed and backward: the
same thing in every decoder cell, on the masked kernels or on XLA's blocks.
The projections around it are not in it (``attn.latent.project`` neither).
Self times, joined by instruction name with the program's own scope tables
(``harness.scopes``). Left out where the program keeps no tables or names
no such scope."""

from benchmarks.harness import scopes


def read(ctx):
    att = scopes.shares(ctx)
    if att is None:
        return None
    took = sum(att["scope"].get(s, 0) for s in scopes.ATTENTION)
    if not took:
        return None
    return {"value": 100.0 * took / att["busy"], "unit": "%"}
