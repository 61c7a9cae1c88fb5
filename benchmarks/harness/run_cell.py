"""The stages of one run of one cell, as functions of their arguments.

``run.py`` calls :func:`run` on the chip; ``tests/test_harness.py`` calls it
on the CPU with a tiny configuration, where the result names the device
``cpu`` and carries no device metric.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time

import jax

from . import compare, device, follow, peaks, trace, traffic
from .cell import metric_reader


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def _program_counters():
    """What the program counts, read at two moments and subtracted."""
    from deeplearning4j_tpu.runtime import telemetry as tel
    wait = tel.registry.get("train.phase.data_wait_s")
    comp = tel.registry.get("compile.events")
    return {
        "data_wait_s": sum(s for _, s, _ in wait.hist_series().values())
        if wait else 0.0,
        "program_compile_events": comp.total() if comp else 0,
    }


def build(cell, seed, devices):
    """Weights on the device from the seed, traffic on the host from the
    seed, the program's model, and the entry that drives it."""
    ref = cell.reference()
    weights = ref.init_weights(seed, cell.config)
    data = traffic.batches(seed, cell.config, cell.traffic)
    program = cell.program()
    model = program.build(cell.config, weights, cell.traffic)
    return cell.entry()(program, model, data, cell.traffic, devices)


def window(entry, seconds: float):
    """Calls until ``seconds`` have passed, then wait for the last result.
    -> examples completed, steps that failed, wall seconds, calls."""
    examples = failed = calls = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        n, bad = entry.call()
        examples += n
        failed += bad
        calls += 1
    entry.sync()
    return examples, failed, time.perf_counter() - t0, calls


def traced_window(entry, seconds: float):
    """The traced calls. The python tracer records this function's own span,
    which is how the reduction finds the traced window."""
    t0 = time.perf_counter()
    while True:
        entry.call()
        if time.perf_counter() - t0 >= seconds:
            break
    entry.sync()


def traced_segment(entry, seconds: float):
    """A few seconds more of the same calls under the profiler.
    -> trace.Reduced"""
    tmp = tempfile.mkdtemp(prefix="benchmark-trace-")
    try:
        # the host tracer records every chunk of the runtime's host-side
        # transposes, millions of events in one upload, which slows the very
        # upload it times and fills the disk (240 MB for 4 s): only the
        # device and the python tracer stay on
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            traced_window(entry, seconds)
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        reduced = trace.reduce_file(trace.newest_xplane(tmp))
        say(f"trace read in {time.perf_counter() - t0:.1f}s")
        return reduced
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def reference_record(cell, seed, devices=None):
    """The plain reference through the cell's first steps, float32."""
    x, y = traffic.batches(seed, cell.config, cell.traffic)
    steps = traffic.split(x, y, cell.traffic)[:cell.traffic["follow_steps"]]
    return follow.follow(cell.reference(), cell.config, seed, steps,
                         cell.traffic["snapshots"], devices=devices, log=say)


def run(cell, seed: int, seconds: float, traced: bool, devices, t_start,
        events, trace_seconds: float = 3.0, build_entry=None) -> dict:
    """One run. ``build_entry`` stands in for :func:`build` where a test
    breaks the timed path underneath."""
    on_chip = devices[0].platform == "tpu"
    info = device.describe(devices)

    entry = (build_entry or build)(cell, seed, devices)
    prog_record = entry.first_steps()
    entry.warm()
    entry.sync()
    before, ev0 = _program_counters(), events.snapshot()
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.1f}s: {ev0['requests']} programs, "
        f"{ev0['hits']} from the compile cache, "
        f"{ev0['seconds']:.1f}s compiling or loading (longest "
        f"{ev0['longest']})")

    examples, failed, secs, calls = window(entry, seconds)
    after, ev1 = _program_counters(), events.snapshot()
    in_window = ev1["requests"] - ev0["requests"]
    say(f"window {secs:.2f}s: {calls} calls, {examples} examples; "
        f"{in_window} programs compiled or loaded inside it, "
        f"{after['program_compile_events'] - before['program_compile_events']}"
        " compile events of the program's own")
    peak, limit = device.memory(devices)

    reduced = traced_segment(entry, trace_seconds) \
        if traced and on_chip else None
    entry.release()
    del entry
    gc.collect()
    jax.clear_caches()

    t_ref = time.perf_counter()
    ref_record = reference_record(cell, seed, devices)
    nums = compare.numbers(prog_record, ref_record)
    correct, compared = compare.judge(nums, cell.limits)
    if failed or not math.isfinite(examples / secs):
        correct = False
    say(f"reference and comparison {time.perf_counter() - t_ref:.1f}s")

    steps = examples // cell.traffic["batch"]
    ctx = {
        "cell": cell, "config": cell.config, "traffic": cell.traffic,
        "device": info, "chips": len(devices),
        "peaks": peaks.peaks_for(info["kind"]) if on_chip else None,
        "window": {"seconds": secs, "examples": examples, "calls": calls},
        "data_wait_s": after["data_wait_s"] - before["data_wait_s"],
        "memory": (peak, limit), "trace": reduced,
    }
    # a rate, a time or a share is a device metric: off the chip (the tests'
    # CPU drive) the line names the device and carries none
    metrics = {}
    if on_chip and traced:
        for name in cell.metric_names("per_layer"):
            got = metric_reader(name)(ctx)
            if got is not None:
                metrics[name] = got
    elif on_chip:
        rate_unit = next(m["unit"] for m in cell.bench["end_to_end"]
                         if m["name"] == "train_examples_per_s")
        metrics["train_examples_per_s"] = {"value": examples / secs,
                                           "unit": rate_unit}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    dev = dict(info)
    if peak is not None:
        dev["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": int(steps),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s()
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["compiled_in_window"] = in_window
    result["compared"] = compared
    for name, c in compared.items():
        if c["limit"] is not None:
            say(f"compared {name}: {c['value']} (limit {c['limit']})")
    say(f"correct: {bool(correct)}")
    return result
