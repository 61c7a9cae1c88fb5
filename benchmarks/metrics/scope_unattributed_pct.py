"""The share of the busiest device's busy time inside the traced window that
the program's scope tables leave unnamed (``device_trace``): operations that
join no instruction of a kept program by name and first result's shape, and
those that join one whose scope path lies under none of ``forward``,
``updater``, ``sentinel`` and ``clip`` (the compiler's own copies, the loop's
counters, the casts around the step). What is in it is said on stderr
(``harness.scopes``). Left out where the program keeps no tables."""

from benchmarks.harness import scopes


def read(ctx):
    return scopes.phase_pct(ctx, "other")
