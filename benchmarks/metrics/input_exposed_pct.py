"""Share of the traced window in which the busiest device was idle while the
program fetched or staged data: the idle time inside its
``train.phase.data_wait_s`` (each ``next()`` of the iterator) and
``train.phase.stage_s`` (host cast, reshape and placement) spans, laid over
the device's timeline by ``harness.spans`` (``program_counter``: the spans
are the program's). Left out where the program keeps no spans or the clocks
do not align. With ``step_host_exposed_pct`` and ``idle_unattributed_pct``
it adds up to ``device_idle_pct``."""

from benchmarks.harness import spans


def read(ctx):
    from deeplearning4j_tpu.runtime import telemetry
    return spans.exposed_pct(ctx["trace"], telemetry,
                             ("data_wait_s", "stage_s"))
