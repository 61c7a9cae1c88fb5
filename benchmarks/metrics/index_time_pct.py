"""The indexer's share of the busiest device's busy time inside the traced
window (``device_trace``): the operations under the ``attn.index`` scope of
``ops/sparse_attention.py`` and of the layer that calls it: the indexer's
three projections, the index products, their ReLU and weighted sum over the
index heads, the search for each row's ``topk``-th score, the mask and its
counts, forward and recomputed (no gradient passes through it, so it has no
backward). Self times, joined by instruction name with the program's own
scope tables (``harness.scopes``). Left out where the program keeps no tables
or names no such scope."""

from benchmarks.harness import scopes


def read(ctx):
    att = scopes.shares(ctx)
    if att is None:
        return None
    took = att["scope"].get("attn.index", 0)
    if not took:
        return None
    return {"value": 100.0 * took / att["busy"], "unit": "%"}
