"""The linear-attention mixer's share of the busiest device's busy time
inside the traced window (``device_trace``): the operations under the
``attn.linear`` and ``attn.linear.project`` scopes of
``nn/layers/decoder.py`` ``GatedDeltaNetLayer`` (the three projections, the
convolution, the norms, the decay and ``beta``, the chunks' preparation, the
delta-rule scan and the gated norm), forward, recomputed and backward. The
two scopes are siblings, never nested, so an operation is in one of them at
most. Self times, joined by instruction name with the program's own scope
tables (``harness.scopes``). Left out where the program keeps no tables or
names no such scope."""

from benchmarks.harness import scopes

SCOPES = ("attn.linear", "attn.linear.project")


def read(ctx):
    att = scopes.shares(ctx)
    if att is None:
        return None
    took = sum(att["scope"].get(s, 0) for s in SCOPES)
    if not took:
        return None
    return {"value": 100.0 * took / att["busy"], "unit": "%"}
