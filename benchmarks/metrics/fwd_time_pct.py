"""The forward pass's share of the busiest device's busy time inside the
traced window (``device_trace``): the operations whose scope path lies
under the loss function's ``forward`` and is neither recomputed nor
transposed.
Self times, joined by instruction name with the program's own scope tables
(``harness.scopes``); with the other phases, ``sentinel``, ``clip`` and
``scope_unattributed_pct`` it adds up to 100. A fusion counts under the
phase of the instruction it is named after, whatever else is fused into it.
Left out where the program keeps no tables."""

from benchmarks.harness import scopes


def read(ctx):
    return scopes.phase_pct(ctx, "forward")
