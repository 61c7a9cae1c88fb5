"""The program's own names laid over the device's timeline.

Two things the program records reach the benchmark here, and both are read
only where the program has them (a program without them gives ``None``, and
the line leaves the metric out):

- **Spans.** The training entry points leave ``train.phase.*`` span events
  in ``telemetry.flight``, an in-memory ring: one ``call_s`` per public call
  and under it ``data_wait_s``, ``stage_s``, ``prepare_s``, ``step_s``,
  ``readback_s`` and ``listeners_s``, each with ``t0_ns``/``t1_ns`` on the
  wall clock and its ``span``/``parent`` ids. A profile's timestamps count
  from the profile's start, so :func:`align` first finds the offset between
  the two clocks from what both recorded: the python tracer's frame of the
  public entry point (``$graph.py:798 fit_on_device``) and the ``call_s``
  span opened at its top. :func:`idle_by_span` then splits the busiest
  device's idle time inside the traced window over the spans.
- **Kernel names.** Each ``pallas_call`` passes ``name=``, which becomes the
  custom call's instruction name (``%jvp_flash_fwd_.3 = ...``);
  :func:`kernel_time_pct` sums the device time of the instructions whose
  name holds one of the given parts.
"""

from __future__ import annotations

import bisect
import re
import statistics
import sys

from . import trace

FAMILY = "train.phase."
CALL = FAMILY + "call_s"
#: how the python tracer names the public entry points' own frames
ENTRY_FRAMES = (" fit_on_device", " fit")
#: a pair's two durations may differ by this share of the frame's
DURATION_TOLERANCE = 0.02
#: the pairs' offsets may differ by this much
OFFSET_TOLERANCE_NS = 500_000

_INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")


def say(msg: str) -> None:
    print(f"benchmark: spans: {msg}", file=sys.stderr, flush=True)


def entry_frames(reduced):
    """[(start, end)] of the entry points' frames inside the traced window,
    on the trace's clock, outermost only, in order."""
    lo, hi = reduced.window
    out = []
    for s, e, name, _ in sorted(reduced.host):
        if name.endswith(ENTRY_FRAMES) and lo <= s and e <= hi and \
                not (out and e <= out[-1][1]):
            out.append((s, e))
    return out


def align(reduced, span_events):
    """The offset in nanoseconds between the wall clock and the trace's
    (wall = trace + offset), or None, with the reason on stderr.

    The traced window is the last thing a run does with the program, so its
    calls are the newest ``call_s`` spans: they are paired, in order, with
    the entry points' frames inside the window. The median offset is
    returned only if each pair's durations agree within
    ``DURATION_TOLERANCE`` and the pairs' offsets within
    ``OFFSET_TOLERANCE_NS``."""
    frames = entry_frames(reduced)
    calls = [e for e in span_events if e["name"] == CALL]
    if not frames or len(calls) < len(frames):
        say(f"{len(frames)} entry frames in the window, {len(calls)} "
            f"{CALL} spans: nothing to pair")
        return None
    offsets = []
    for (fs, fe), call in zip(frames, calls[-len(frames):]):
        took = call["t1_ns"] - call["t0_ns"]
        if abs(took - (fe - fs)) > DURATION_TOLERANCE * (fe - fs):
            say(f"a call span of {took} ns against a frame of {fe - fs} ns: "
                "the spans and the frames do not pair")
            return None
        offsets.append(call["t0_ns"] - fs)
    spread = max(offsets) - min(offsets)
    if spread > OFFSET_TOLERANCE_NS:
        say(f"the {len(offsets)} pairs' offsets spread over {spread} ns: the "
            "two clocks do not agree over the window")
        return None
    say(f"{len(offsets)} calls paired, offsets spread over {spread} ns")
    # the low median is one of the offsets: their mean would not survive a
    # float (the wall clock counts 1.8e18 ns)
    return statistics.median_low(offsets)


def covered_ns(gaps, starts, intervals) -> int:
    """The nanoseconds of the sorted, disjoint ``gaps`` (``starts`` their
    starts) that fall inside the disjoint ``intervals``."""
    got = 0
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(gaps) and gaps[i][0] < e:
            got += max(0, min(gaps[i][1], e) - max(gaps[i][0], s))
            i += 1
    return got


def idle_by_span(reduced, span_events, offset: int):
    """{span name: idle seconds} of the busiest device inside the traced
    window, each idle moment given to the innermost ``train.phase.*`` span
    that covers it (a span's own time: its interval less its children's),
    with the rest under ``None``: the moments that no child span of a call
    covers."""
    lo, hi = reduced.window
    dev = max(reduced.devices.values(), key=lambda d: d["busy_ns"])
    gaps = trace.subtract([reduced.window], dev["busy"])
    starts = [s for s, _ in gaps]
    family = [(e, (e["t0_ns"] - offset, e["t1_ns"] - offset))
              for e in span_events if e["name"].startswith(FAMILY)]
    family = [(e, iv) for e, iv in family if iv[0] < hi and iv[1] > lo]
    children = {}
    for e, iv in family:
        children.setdefault(e.get("parent"), []).append(iv)
    out = {None: trace.total(gaps) / 1e9}
    for e, iv in family:
        if e["name"] == CALL:
            continue
        own = trace.subtract(trace.clip([iv], lo, hi),
                             trace.union(children.get(e["span"], [])))
        idle = covered_ns(gaps, starts, own) / 1e9
        out[e["name"]] = out.get(e["name"], 0.0) + idle
        out[None] -= idle
    return out


def exposed_pct(reduced, telemetry, names):
    """The body of the three readers that share the spans: the idle seconds
    inside the spans ``names`` (``None``: inside none) over the traced
    window, in percent. None where there is no trace, the program keeps no
    spans, the ring no longer reaches back to the window's start (it is
    bounded, and shared with compile and fault events), or the clocks do
    not align."""
    if reduced is None or not hasattr(telemetry, "spans"):
        return None
    events = telemetry.spans()
    offset = align(reduced, events)
    if offset is None:
        return None
    ring = telemetry.flight.events()
    if not ring or ring[0]["t"] * 1e9 > reduced.window[0] + offset:
        say("the ring's oldest event is younger than the window's start")
        return None
    idle = idle_by_span(reduced, events, offset)
    picked = idle[None] if names is None else sum(
        idle.get(FAMILY + n, 0.0) for n in names)
    return {"value": 100.0 * picked / reduced.window_s, "unit": "%"}


def kernel_time_pct(reduced, parts):
    """The device time, inside the traced window, of the operations whose
    instruction name holds one of ``parts``, over the busy time of the
    busiest device, on that device, in percent. None where there is no
    trace or no such operation ran."""
    if reduced is None:
        return None
    lo, hi = reduced.window
    dev = max(reduced.devices.values(), key=lambda d: d["busy_ns"])
    took = 0
    for s, e, name in dev["ops"]:
        m = _INSTRUCTION.match(name)
        if m and any(p in m.group(1) for p in parts):
            took += max(0, min(e, hi) - max(s, lo))
    if not took:
        return None
    return {"value": 100.0 * took / dev["busy_ns"], "unit": "%"}
