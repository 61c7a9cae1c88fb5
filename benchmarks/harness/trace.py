"""From a profiler trace to numbers: the reduction every PR shares.

Reads what ``jax.profiler.ProfileData`` gives (planes, their lines, events
with a start and a duration in nanoseconds) and nothing else, so a test can
hand in a small trace made of plain objects with the same attributes.

A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation that ran. The traced window is the host span of the
harness's ``traced_window`` function, which the python tracer records as
``$<file>:<line> traced_window`` (or an annotation named ``WINDOW_SPAN``);
where a trace has neither, it is the extent of the device events.
"""

from __future__ import annotations

import re

WINDOW_SPAN = "benchmark.window"
_WINDOW_FN = " traced_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
_OPS_LINE = "XLA Ops"


def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(short_name(name)))


_HLO = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = (\(?[a-z0-9]+\[[^\]]*\])?[^ ]* ?"
                  r".*?([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line. -> ``name kind
    first-result-shape`` with the site's numeric suffix dropped, so that the
    sites of one kind add up, and ``tpu_custom_call`` said where it is one."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    base, shape, kind = m.group(1), m.group(2) or "", m.group(3)
    if 'custom_call_target="tpu_custom_call"' in name:
        kind = "tpu_custom_call"
    return f"{base} {kind} {shape.lstrip('(')}".strip()


def self_times(ops):
    """[(name, self nanoseconds)]: each event's duration less that of the
    events nested inside it (a ``while`` holds its body's operations)."""
    out, stack = [], []
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][2], stack[-1][3]))
            stack.pop()
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([s, e, n, e - s])
    out.extend((n, t) for _, _, n, t in stack)
    return out


class Reduced:
    """What the metric readers read.

    ``window`` ``(start_ns, end_ns)``; ``devices`` ``{plane name: {"ops":
    [(start, end, name)], "busy_ns", "collective_exposed_ns"}}``; ``host``
    ``[(start, end, name, line)]`` of every host event at least a tenth of a
    millisecond long."""

    def __init__(self, profile):
        self.devices, self.host = {}, []
        spans = []
        for plane in profile.planes:
            if _DEVICE.match(plane.name):
                ops = []
                for line in plane.lines:
                    if line.name == _OPS_LINE:
                        ops = [(int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name)
                               for e in line.events]
                self.devices[plane.name] = {"ops": ops}
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == WINDOW_SPAN or e.name.endswith(_WINDOW_FN):
                            spans.append((int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
                        elif e.duration_ns >= 100_000:
                            self.host.append(
                                (int(e.start_ns),
                                 int(e.start_ns + e.duration_ns), e.name,
                                 line.name))
        every = [iv for d in self.devices.values() for iv in d["ops"]]
        if not every:
            raise ValueError("the trace holds no operation on any "
                             f"{_DEVICE.pattern} plane's {_OPS_LINE!r} line")
        if spans:
            self.window = (min(s for s, _ in spans), max(e for _, e in spans))
        else:
            self.window = (min(s for s, _, _ in every),
                           max(e for _, e, _ in every))
        lo, hi = self.window
        for d in self.devices.values():
            busy = clip(union((s, e) for s, e, _ in d["ops"]), lo, hi)
            coll = clip(union((s, e) for s, e, n in d["ops"]
                              if is_collective(n)), lo, hi)
            comp = clip(union((s, e) for s, e, n in d["ops"]
                              if not is_collective(n)), lo, hi)
            d["busy"] = busy
            d["busy_ns"] = total(busy)
            d["collective_ns"] = total(coll)
            d["collective_exposed_ns"] = total(subtract(coll, comp))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Mean over the devices of the seconds an operation ran."""
        return (sum(d["busy_ns"] for d in self.devices.values())
                / len(self.devices) / 1e9)

    def idle_share_fullest(self) -> float:
        """1 - busy share, on the device that was busy the longest."""
        busiest = max(d["busy_ns"] for d in self.devices.values())
        return 1.0 - busiest / (self.window[1] - self.window[0])

    def op_seconds(self, pattern: str):
        """{op name: [count, seconds]} over all devices for the operations
        whose name matches ``pattern``."""
        rx, out = re.compile(pattern), {}
        for d in self.devices.values():
            for s, e, n in d["ops"]:
                if rx.search(n):
                    c = out.setdefault(n, [0, 0.0])
                    c[0] += 1
                    c[1] += (e - s) / 1e9
        return out

    def top_ops(self, k: int = 10):
        """[[short name, seconds]] of the kinds of operation that took most
        device time of their own, on the busiest device."""
        dev = max(self.devices.values(), key=lambda d: d["busy_ns"])
        acc = {}
        for n, t in self_times(dev["ops"]):
            n = short_name(n)
            acc[n] = acc.get(n, 0.0) + t / 1e9
        return [[n, t] for n, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10):
        """[[what the host was doing, seconds]]: the idle time of the busiest
        device inside the window, summed by the name of the shortest host
        event that covers each gap's middle, longest first."""
        dev = max(self.devices.values(), key=lambda d: d["busy_ns"])
        gaps = subtract([self.window], dev["busy"])
        acc = {}
        for s, e in gaps:
            if e - s < 20_000:
                continue
            mid = (s + e) // 2
            cover = [(he - hs, n) for hs, he, n, _ in self.host
                     if hs <= mid < he]
            name = min(cover)[1] if cover else "(no host event)"
            acc[name] = acc.get(name, 0.0) + (e - s) / 1e9
        return [[n, t] for n, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def newest_xplane(trace_dir: str) -> str:
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str) -> Reduced:
    import jax
    return Reduced(jax.profiler.ProfileData.from_file(path))


def describe(profile, limit: int = 12) -> str:
    """Planes, lines and the first event names: for a look by hand."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = []
            for e in evs:
                if e.name not in names:
                    names.append(e.name)
                if len(names) >= limit:
                    break
            out.append(f"  line {line.name!r}: {len(evs)} events; {names}")
    return "\n".join(out)
