"""The selected-key attention's share of the busiest device's busy time
inside the traced window (``device_trace``): the operations under the
``attn.sparse`` scope of ``ops/causal_attention.py`` (the moves into the
blocks' layout, every causal block's scores, the mask's slices, the softmax
and the value product, forward, recomputed and backward). The indexer and
the selection that make the mask are ``index_time_pct``'s; the projections
around them are in neither. Self times, joined by instruction name with the
program's own scope tables (``harness.scopes``). Left out where the program
keeps no tables or names no such scope."""

from benchmarks.harness import scopes


def read(ctx):
    att = scopes.shares(ctx)
    if att is None:
        return None
    took = att["scope"].get("attn.sparse", 0)
    if not took:
        return None
    return {"value": 100.0 * took / att["busy"], "unit": "%"}
