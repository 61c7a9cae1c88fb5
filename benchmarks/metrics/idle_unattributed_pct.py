"""Share of the traced window in which the busiest device was idle and no
``train.phase.*`` child span of a call covered the moment: the caller's own
code between calls, and whatever the entry points do outside their spans
(``program_counter``). It should stay near zero: a larger value means a
phase of the program has no span. Left out where the program keeps no spans
or the clocks do not align."""

from benchmarks.harness import spans


def read(ctx):
    from deeplearning4j_tpu.runtime import telemetry
    return spans.exposed_pct(ctx["trace"], telemetry, None)
