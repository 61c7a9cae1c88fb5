"""Share of the traced window in which the busiest device was idle while the
host worked on a step: the idle time inside the program's
``train.phase.prepare_s`` (key split, step counter, fault sites, a call's
carry and optimizer state), ``step_s`` (the enqueue), ``readback_s`` (the
host waits for a device value, then wakes) and ``listeners_s`` spans, laid
over the device's timeline by ``harness.spans`` (``program_counter``). Left
out where the program keeps no spans or the clocks do not align."""

from benchmarks.harness import spans


def read(ctx):
    from deeplearning4j_tpu.runtime import telemetry
    return spans.exposed_pct(ctx["trace"], telemetry,
                             ("prepare_s", "step_s", "readback_s",
                              "listeners_s"))
