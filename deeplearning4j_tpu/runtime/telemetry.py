"""Unified telemetry: the process-wide MetricsRegistry (ISSUE 6 tentpole).

Five subsystems grew five private counter dicts — flash-attention
dispatch (`ops/flash_attention.py`), serving bucket/compile/shed counters
(`serving/engine.py`, `serving/batcher.py`), sentinel resilience counters
(`runtime/sentinel.py`), fault telemetry (`runtime/faults.py`), and
checkpoint save/restore latency (`parallel/checkpoint.py`) — with no
single way to scrape, correlate, or alert on them. TensorFlow's
production design (PAPERS.md, 1605.08695) treats run-time monitoring of
kernels, queues and servables as a first-class subsystem; this module is
that layer. Every pre-existing accessor (``flash_attention.counters()``,
``engine.stats()``, ``pi.stats()``, ``faults.telemetry_snapshot()``…)
stays callable and is now a *view* over this registry.

Five pieces:

- **MetricsRegistry** — thread-safe counters, gauges, and bounded
  timestamped-reservoir histograms (p50/p99 over lifetime or any recent
  window), namespaced ``subsystem.name`` with optional labels (the
  Prometheus client model: one :class:`Metric` per name, cells per label
  set). Per-instance surfaces (each ``InferenceEngine``…) use an
  auto-assigned instance label so the process-wide registry can still
  serve per-instance ``stats()``.
- **Span API** — ``with telemetry.span("serving.dispatch"):`` records a
  duration histogram under the span name and emits a structured event
  carrying trace/span/parent correlation ids (contextvar-propagated, so
  nested spans across threads correlate when the context flows) and both
  ends on the wall clock (``t0_ns``/``t1_ns``). The event lands in the
  in-memory ``flight`` ring, where :func:`spans` finds it: that is how a
  device trace is laid over the training loops' ``train.phase.*`` spans
  (``nn/caches.py``, "phase tracing"). An open span holds a
  ``jax.profiler.TraceAnnotation`` of its name.
- **Retrace tracker** — :func:`record_compile` is called by every
  lower+compile site (engine train-step builds, the serving engine's AOT
  bucket cache, the SameDiff fit-step spec cache) with its *cause*
  (``warmup`` / ``new_bucket`` / ``dtype_policy`` / ``workspace_mode`` /
  ``params_placement`` / ``first_build`` …). Steady-state training must
  show zero post-warmup events (regression-tested); before this tracker
  a silent retrace was invisible until the step time doubled.
- **Scope registry** — :func:`record_program` is handed the executable
  of each training program after its first dispatch (through
  :func:`record_dispatch`: both engines' ``fit`` / ``fit_on_device``,
  ``SameDiff.fit``, ``ParallelWrapper.fit``) and keeps the optimized HLO
  of the newest :data:`PROGRAMS_KEPT`; :func:`program_scopes` turns it,
  on request, into a table of every instruction with the program's scope
  path, its phase by :func:`scope_phase` (forward, recompute, backward,
  updater, sentinel, clip) and its vertex. A device trace names operations
  by HLO instruction and knows no scope; the benchmark's readers join the
  two (``benchmarks/harness/scopes.py``).
- **Export** — ``prometheus_text()`` (text exposition served by
  ``JsonModelServer GET /metrics``), ``event_log(path)`` (JSONL sink for
  spans + compile events), and ``snapshot()``.

Kill switch: ``DL4J_TPU_TELEMETRY=off`` (or :func:`set_enabled`) gates
the *timing* instrumentation — histogram observes, spans, step
annotations, the phase clocks in the fit/serving loops. Nothing in the
benchmark reads the switch (``bench.py`` and its ``telemetry_overhead``
A/B went in PR 33): it is an operator's, and ``tests/test_telemetry.py``
and ``tests/test_train_spans.py`` hold it to leaving no event and
bit-equal results. Counters and gauges ALWAYS record: they are functional
accounting (fault-injection ledgers, serving counters, compile counts)
that product code and tests read, and each costs one dict add; the scope
registry records under the switch too, once a program. Latency-derived
surfaces (``stats()`` percentiles, ``degraded_p99_ms`` health) go quiet
when disabled — documented, deliberate. stdlib-only at import time so every layer can
import this module without cycles (same contract as ``faults.py``).

Coverage floor: metrics registered at import time land in a ledger
(:func:`coverage_report`); ``tests/test_zz_coverage_floor.py`` asserts
every one of them is exercised by at least one tier-1 test — a metric
nobody can trip in a test is a metric nobody has ever read.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import re
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = [
    "MetricsRegistry", "registry", "counter", "gauge", "histogram",
    "enabled", "set_enabled", "span", "spans", "current_span", "event_log",
    "emit_event", "record_compile", "compile_events",
    "reset_compile_events", "record_program", "record_dispatch",
    "program_scopes", "scope_phase", "reset_programs",
    "step_annotation", "prometheus_text",
    "snapshot", "coverage_report",
    # per-request distributed tracing (ISSUE 13)
    "RequestTrace", "start_request_trace", "get_trace", "recent_traces",
    "phase_sink", "sink_phases", "stitch_event_logs", "format_timeline",
    # SLO + flight recorder (ISSUE 13)
    "SLO", "FlightRecorder", "flight",
]

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

#: Reservoir bound per histogram cell — matches the pre-registry
#: ``ParallelInference._latencies`` deque so windowed percentiles keep the
#: same fidelity the lifetime ones had.
RESERVOIR = 4096


def _label_key(labels: dict) -> Tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _HistCell:
    """One bounded reservoir of (monotonic-time, value) samples plus
    lifetime count/sum (the reservoir is bounded; count/sum are not)."""

    __slots__ = ("samples", "count", "sum")

    def __init__(self, maxlen: int = RESERVOIR):
        self.samples: deque = deque(maxlen=maxlen)
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float, now: float):
        self.samples.append((now, float(value)))
        self.count += 1
        self.sum += float(value)

    def values(self, window: Optional[float], now: float) -> List[float]:
        if window is None:
            return [v for _, v in self.samples]
        cut = now - float(window)
        return [v for t, v in self.samples if t >= cut]


def _percentile(vals: List[float], q: float) -> Optional[float]:
    return _percentile_sorted(sorted(vals), q)


def _percentile_sorted(s: List[float], q: float) -> Optional[float]:
    """``_percentile`` over an ALREADY-sorted list — export paths that
    need several quantiles of the same reservoir sort once and call
    this, instead of re-sorting per quantile."""
    if not s:
        return None
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1 - frac) + s[hi] * frac


class Metric:
    """One named metric; cells per label set. Obtain via
    ``registry.counter/gauge/histogram`` — never construct directly."""

    def __init__(self, reg: "MetricsRegistry", name: str, kind: str,
                 help: str = ""):
        self._reg = reg
        self.name = name
        self.kind = kind
        self.help = help
        self._cells: Dict[Tuple, object] = {}

    # -- write side ---------------------------------------------------------
    # counters and gauges are FUNCTIONAL accounting (fault-injection
    # ledgers, serving health inputs, compile counts — surfaces product
    # code and tests read) and always record: one dict add under a lock.
    # The DL4J_TPU_TELEMETRY=off kill switch gates only the *timing*
    # instrumentation (histogram observes, spans, step annotations) —
    # the per-step hot-path cost the telemetry_overhead bench A/Bs.
    def inc(self, n: float = 1, **labels) -> None:
        if self.kind != COUNTER:
            raise TypeError(f"{self.name} is a {self.kind}, not a counter")
        reg = self._reg
        key = _label_key(labels)
        with reg._lock:
            self._cells[key] = self._cells.get(key, 0) + n
            reg._touched.add(self.name)

    def set(self, value, **labels) -> None:
        if self.kind != GAUGE:
            raise TypeError(f"{self.name} is a {self.kind}, not a gauge")
        reg = self._reg
        key = _label_key(labels)
        with reg._lock:
            self._cells[key] = value
            reg._touched.add(self.name)

    def observe(self, value: float, **labels) -> None:
        if self.kind != HISTOGRAM:
            raise TypeError(f"{self.name} is a {self.kind}, not a histogram")
        reg = self._reg
        if not reg._enabled:
            return
        key = _label_key(labels)
        now = time.monotonic()
        with reg._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = _HistCell()
            cell.observe(value, now)
            reg._touched.add(self.name)

    # -- read side ----------------------------------------------------------
    def value(self, default=0, **labels):
        """Counter/gauge value for one label set (``default`` when the
        cell was never written — counters read naturally as 0)."""
        with self._reg._lock:
            v = self._cells.get(_label_key(labels), _MISSING)
        return default if v is _MISSING else v

    def total(self) -> float:
        """Sum over every cell (counters; process-wide aggregate of all
        instance labels)."""
        with self._reg._lock:
            return sum(v for v in self._cells.values()
                       if isinstance(v, (int, float)))

    def series(self) -> Dict[Tuple, object]:
        with self._reg._lock:
            return dict(self._cells)

    def hist_series(self) -> Dict[Tuple, Tuple[int, float, List[float]]]:
        """Materialized ``{label_key: (count, sum, [values])}`` for a
        histogram, copied under the lock. Export paths (snapshot /
        prometheus_text) must use this rather than iterating the live
        ``_HistCell.samples`` deques from ``series()`` — a concurrent
        ``observe()`` appending mid-iteration raises ``RuntimeError:
        deque mutated during iteration`` and fails the scrape."""
        with self._reg._lock:
            return {k: (c.count, c.sum, [v for _, v in c.samples])
                    for k, c in self._cells.items()}

    def values_list(self, window: Optional[float] = None, **labels
                    ) -> List[float]:
        """Histogram raw sample values (optionally only the last
        ``window`` seconds)."""
        now = time.monotonic()
        with self._reg._lock:
            cell = self._cells.get(_label_key(labels))
            return cell.values(window, now) if cell is not None else []

    def percentile(self, q: float, window: Optional[float] = None,
                   **labels) -> Optional[float]:
        return _percentile(self.values_list(window, **labels), q)

    def hist_snapshot(self, window: Optional[float] = None, **labels
                      ) -> dict:
        """{count, sum, p50, p99, mean, max} for one histogram cell.
        ``window`` restricts the reservoir to the last N seconds (count/
        sum stay lifetime when window is None, else windowed)."""
        now = time.monotonic()
        with self._reg._lock:
            cell = self._cells.get(_label_key(labels))
            if cell is None:
                return {"count": 0, "sum": 0.0, "p50": None, "p99": None,
                        "mean": None, "max": None}
            vals = cell.values(window, now)
            count = cell.count if window is None else len(vals)
            reservoir_sum = float(sum(vals))
            total = cell.sum if window is None else reservoir_sum
        vals.sort()
        return {"count": count, "sum": total,
                "p50": _percentile_sorted(vals, 50),
                "p99": _percentile_sorted(vals, 99),
                "mean": (reservoir_sum / len(vals)) if vals else None,
                "max": vals[-1] if vals else None}

    def labeled(self, **labels) -> "BoundMetric":
        return BoundMetric(self, labels)

    def zero(self, **labels) -> None:
        """Reset cells to their zero state (all cells when no labels are
        given). Declarations and the coverage ledger survive — this backs
        the pre-registry per-subsystem ``reset_counters()`` helpers."""
        with self._reg._lock:
            keys = [_label_key(labels)] if labels else list(self._cells)
            for k in keys:
                if k not in self._cells:
                    continue
                if self.kind == COUNTER:
                    self._cells[k] = 0
                elif self.kind == GAUGE:
                    del self._cells[k]
                else:
                    self._cells[k] = _HistCell()


_MISSING = object()


class BoundMetric:
    """A metric with labels pre-bound (what per-instance owners hold, so
    the hot path does one attribute call). The label KEY is computed once
    here — per-step write paths (fit-loop phase histograms, serving
    dispatch) skip the per-call dict build + sort of the kwargs path."""

    __slots__ = ("metric", "labels", "_key")

    def __init__(self, metric: Metric, labels: dict):
        self.metric = metric
        self.labels = dict(labels)
        self._key = _label_key(self.labels)

    def inc(self, n: float = 1) -> None:
        m = self.metric
        if m.kind != COUNTER:
            raise TypeError(f"{m.name} is a {m.kind}, not a counter")
        reg = m._reg
        with reg._lock:
            m._cells[self._key] = m._cells.get(self._key, 0) + n
            reg._touched.add(m.name)

    def set(self, value) -> None:
        m = self.metric
        if m.kind != GAUGE:
            raise TypeError(f"{m.name} is a {m.kind}, not a gauge")
        reg = m._reg
        with reg._lock:
            m._cells[self._key] = value
            reg._touched.add(m.name)

    def observe(self, value: float) -> None:
        m = self.metric
        if m.kind != HISTOGRAM:
            raise TypeError(f"{m.name} is a {m.kind}, not a histogram")
        reg = m._reg
        if not reg._enabled:
            return
        now = time.monotonic()
        with reg._lock:
            cell = m._cells.get(self._key)
            if cell is None:
                cell = m._cells[self._key] = _HistCell()
            cell.observe(value, now)
            reg._touched.add(m.name)

    def observe_many(self, values) -> None:
        """Histogram-observe a batch of values in ONE lock round with one
        shared timestamp — dispatcher hot paths record a coalesced
        batch's per-request latencies without taking the registry lock
        per request."""
        m = self.metric
        if m.kind != HISTOGRAM:
            raise TypeError(f"{m.name} is a {m.kind}, not a histogram")
        reg = m._reg
        if not reg._enabled or not values:
            return
        now = time.monotonic()
        with reg._lock:
            cell = m._cells.get(self._key)
            if cell is None:
                cell = m._cells[self._key] = _HistCell()
            for v in values:
                cell.observe(v, now)
            reg._touched.add(m.name)

    def value(self, default=0):
        return self.metric.value(default, **self.labels)

    def values_list(self, window: Optional[float] = None) -> List[float]:
        return self.metric.values_list(window, **self.labels)

    def percentile(self, q: float, window: Optional[float] = None):
        return self.metric.percentile(q, window, **self.labels)

    def hist_snapshot(self, window: Optional[float] = None) -> dict:
        return self.metric.hist_snapshot(window, **self.labels)


class MetricsRegistry:
    """Process-wide metric store. ``counter/gauge/histogram`` declare (or
    fetch) a metric by ``subsystem.name``; re-declaring with a different
    kind is an error (two subsystems colliding on a name is a bug worth
    failing loudly on)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics: Dict[str, Metric] = {}
        self._touched: set = set()     # process-lifetime; reset() keeps it
        self._enabled = os.environ.get(
            "DL4J_TPU_TELEMETRY", "on").lower() not in ("off", "0", "false")

    # -- declaration --------------------------------------------------------
    def _declare(self, name: str, kind: str, help: str) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Metric(self, name, kind, help)
            elif m.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"cannot re-register as {kind}")
            return m

    def counter(self, name: str, help: str = "") -> Metric:
        return self._declare(name, COUNTER, help)

    def gauge(self, name: str, help: str = "") -> Metric:
        return self._declare(name, GAUGE, help)

    def histogram(self, name: str, help: str = "") -> Metric:
        return self._declare(name, HISTOGRAM, help)

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    # -- enable/disable -----------------------------------------------------
    def set_enabled(self, on: bool) -> bool:
        """Flip recording globally; returns the previous state (the bench
        A/B and tests restore it)."""
        old = self._enabled
        self._enabled = bool(on)
        return old

    @property
    def is_enabled(self) -> bool:
        return self._enabled

    # -- maintenance --------------------------------------------------------
    def reset(self) -> None:
        """Zero every cell. Declarations and the touched ledger survive
        (the ledger accumulates across a whole test session, like the
        fault-site ledger)."""
        with self._lock:
            for m in self._metrics.values():
                m.zero()

    def locked(self):
        """The registry's reentrant lock, for callers that need a
        multi-op read-modify-write (e.g. a cross-kind compat shim) or a
        consistent read across several metrics to be atomic — inner
        inc/set/value calls re-acquire it safely."""
        return self._lock

    def discard_cells(self, **labels) -> int:
        """Remove every cell (across all metrics) whose label set contains
        ALL the given ``key=value`` pairs. Per-instance owners (serving
        engines, inference fronts) register a ``weakref.finalize`` calling
        this with their instance label, so a long-running process that
        churns models does not grow the registry — and ``/metrics`` —
        without bound. Returns the number of cells dropped."""
        want = set(_label_key(labels))
        n = 0
        with self._lock:
            for m in self._metrics.values():
                for k in [k for k in m._cells if want <= set(k)]:
                    del m._cells[k]
                    n += 1
        return n

    def coverage_report(self) -> dict:
        """The telemetry floor's input: ``untouched`` lists registered
        metrics no test (or production path under test) ever wrote."""
        with self._lock:
            registered = sorted(self._metrics)
            touched = sorted(self._touched & set(self._metrics))
        return {"registered": registered, "touched": touched,
                "untouched": sorted(set(registered) - set(touched))}

    # -- export -------------------------------------------------------------
    def snapshot(self, compact: bool = False) -> dict:
        """JSON-safe dump of every metric. ``compact=True`` (bench
        artifacts) aggregates counters across label sets and reduces
        histograms to count/p50/p99."""
        out = {}
        with self._lock:
            metrics = dict(self._metrics)
        for name, m in sorted(metrics.items()):
            if m.kind == HISTOGRAM:
                if compact:
                    # aggregate all cells into one distribution
                    vals, count, total = [], 0, 0.0
                    for c, s, vs in m.hist_series().values():
                        vals.extend(vs)
                        count += c
                        total += s
                    vals.sort()
                    out[name] = {"kind": m.kind, "count": count,
                                 "sum": total,
                                 "p50": _percentile_sorted(vals, 50),
                                 "p99": _percentile_sorted(vals, 99)}
                else:
                    series = {}
                    for k, (c, s, vs) in m.hist_series().items():
                        vs.sort()
                        series[json.dumps(dict(k))] = {
                            "count": c, "sum": s,
                            "p50": _percentile_sorted(vs, 50),
                            "p99": _percentile_sorted(vs, 99)}
                    out[name] = {"kind": m.kind, "series": series}
            else:
                if compact:
                    out[name] = {"kind": m.kind, "total": m.total()} \
                        if m.kind == COUNTER else \
                        {"kind": m.kind,
                         "series": {json.dumps(dict(k)): v
                                    for k, v in m.series().items()}}
                else:
                    out[name] = {"kind": m.kind,
                                 "series": {json.dumps(dict(k)): v
                                            for k, v in m.series().items()}}
        return out

    def prometheus_text(self) -> str:
        """Prometheus text exposition (format 0.0.4). Counters export with
        the ``_total`` convention; histograms export as summaries
        (``quantile`` label + ``_count``/``_sum``); gauges with a None
        value are skipped (unset)."""
        lines: List[str] = []
        with self._lock:
            metrics = dict(self._metrics)
        for name, m in sorted(metrics.items()):
            pname = _prom_name(name)
            if m.kind == COUNTER:
                pname += "_total"
                series = m.series()
                lines.append(f"# HELP {pname} {_prom_help(m)}")
                lines.append(f"# TYPE {pname} counter")
                if not series:
                    lines.append(f"{pname} 0")
                for k, v in sorted(series.items()):
                    lines.append(f"{pname}{_prom_labels(k)} {_prom_val(v)}")
            elif m.kind == GAUGE:
                series = m.series()
                lines.append(f"# HELP {pname} {_prom_help(m)}")
                lines.append(f"# TYPE {pname} gauge")
                for k, v in sorted(series.items()):
                    if v is None:
                        continue
                    if isinstance(v, bool):
                        v = int(v)
                    if not isinstance(v, (int, float)):
                        continue  # string gauges are not exposition-legal
                    lines.append(f"{pname}{_prom_labels(k)} {_prom_val(v)}")
            else:
                lines.append(f"# HELP {pname} {_prom_help(m)}")
                lines.append(f"# TYPE {pname} summary")
                for k, (count, total, vals) in sorted(
                        m.hist_series().items()):
                    vals.sort()
                    for q, qs in ((50, "0.5"), (99, "0.99")):
                        pv = _percentile_sorted(vals, q)
                        if pv is None:
                            continue
                        lines.append(
                            f"{pname}{_prom_labels(k + (('quantile', qs),))}"
                            f" {_prom_val(pv)}")
                    lines.append(
                        f"{pname}_count{_prom_labels(k)} {count}")
                    lines.append(
                        f"{pname}_sum{_prom_labels(k)} {_prom_val(total)}")
        return "\n".join(lines) + "\n"


def _prom_name(name: str) -> str:
    return "dl4j_" + re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_help(m: Metric) -> str:
    return (m.help or m.name).replace("\\", "\\\\").replace("\n", "\\n")


def _prom_labels(key: Tuple) -> str:
    if not key:
        return ""
    parts = []
    for k, v in key:
        v = str(v).replace("\\", "\\\\").replace('"', '\\"') \
            .replace("\n", "\\n")
        parts.append(f'{re.sub(r"[^a-zA-Z0-9_]", "_", str(k))}="{v}"')
    return "{" + ",".join(parts) + "}"


def _prom_val(v) -> str:
    f = float(v)
    if f != f:
        return "NaN"  # exposition-format literal; int(f) would raise
    if f in (float("inf"), float("-inf")):
        return "+Inf" if f > 0 else "-Inf"
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


#: THE process-wide registry (the "single MetricsRegistry" of ISSUE 6).
registry = MetricsRegistry()


def counter(name: str, help: str = "") -> Metric:
    return registry.counter(name, help)


def gauge(name: str, help: str = "") -> Metric:
    return registry.gauge(name, help)


def histogram(name: str, help: str = "") -> Metric:
    return registry.histogram(name, help)


def enabled() -> bool:
    """Hot loops guard their instrumentation on this — one bool read."""
    return registry._enabled


def set_enabled(on: bool) -> bool:
    return registry.set_enabled(on)


def prometheus_text() -> str:
    return registry.prometheus_text()


def snapshot(compact: bool = False) -> dict:
    return registry.snapshot(compact=compact)


def coverage_report() -> dict:
    return registry.coverage_report()


# ---------------------------------------------------------------- span API
class Span:
    """One timed region. ``trace_id`` groups a whole request/step tree;
    ``parent_id`` is the enclosing span (None at the root). ``t0_ns`` is
    the start on the wall clock (``time.time_ns``), which is what lets a
    profiler trace be laid over the spans; the duration comes from the
    monotonic clock, and the event's ``t1_ns`` is ``t0_ns`` plus it, so a
    stepped wall clock cannot turn a span inside out."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "labels", "t0", "t0_ns", "duration_s")

    def __init__(self, name, trace_id, span_id, parent_id, attrs,
                 labels=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.labels = labels
        self.t0_ns = time.time_ns()
        self.t0 = time.perf_counter()
        self.duration_s: Optional[float] = None


_span_ids = itertools.count(1)
_current_span: contextvars.ContextVar[Optional[Span]] = \
    contextvars.ContextVar("dl4j_tpu_span", default=None)


def current_span() -> Optional[Span]:
    return _current_span.get()


_profiler = None  # jax.profiler, resolved on first use; False = unavailable


def _jax_profiler():
    """``jax.profiler`` or False. The lookup resolves once — spans and step
    annotations run on every fit-loop step — and lazily, so this module
    stays stdlib-only at import time."""
    global _profiler
    if _profiler is None:
        try:
            import jax
            _profiler = jax.profiler
        except Exception:
            _profiler = False
    return _profiler


class _SpanCtx:
    __slots__ = ("span", "_token", "_ann", "_cancelled")

    def __init__(self, span: Span):
        self.span = span
        self._token = None
        self._ann = None
        self._cancelled = False

    def __enter__(self) -> Span:
        self._token = _current_span.set(self.span)
        # an operator's own trace (``ui.ProfilingListener``, default host
        # tracer) shows the program's spans under their names; outside a
        # profiler session this costs a fraction of a microsecond
        prof = _jax_profiler()
        if prof:
            self._ann = prof.TraceAnnotation(self.span.name)
            self._ann.__enter__()
        return self.span

    def cancel(self) -> None:
        """Record nothing when the block is left (a ``next()`` that found
        the iterator's end waited for no batch)."""
        self._cancelled = True

    def __exit__(self, *exc):
        sp = self.span
        sp.duration_s = time.perf_counter() - sp.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _current_span.reset(self._token)
        if registry._enabled and not self._cancelled:
            registry.histogram(sp.name).observe(sp.duration_s,
                                                **(sp.labels or {}))
            t1_ns = sp.t0_ns + int(sp.duration_s * 1e9)
            ev = {"t": t1_ns / 1e9, "type": "span", "name": sp.name,
                  "trace": sp.trace_id, "span": sp.span_id,
                  "parent": sp.parent_id, "duration_s": sp.duration_s,
                  "t0_ns": sp.t0_ns, "t1_ns": t1_ns,
                  **(sp.labels or {}), **sp.attrs}
            if exc and exc[0] is not None:
                ev["status"] = "error"
                ev["error"] = getattr(exc[0], "__name__", str(exc[0]))
            emit_event(ev)
            flight.record(ev)
        return False


class _NullSpanCtx:
    span = None

    def __enter__(self):
        return None

    def cancel(self):
        pass

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpanCtx()


def span(name: str, labels: Optional[dict] = None, **attrs):
    """``with telemetry.span("serving.dispatch", rows=n):`` — times the
    region into the ``name`` duration histogram and emits a correlated
    event. ``labels`` (LOW-cardinality only: instance ids, modes) become
    the histogram cell's labels so distinct instances don't blend into
    one p99; free-form ``attrs`` (row counts, shapes) go to the event
    log only. Nested spans inherit the trace id and point at their
    parent; a root span starts a fresh trace. The finished event (name,
    ``t0_ns``/``t1_ns`` on the wall clock, ``trace``/``span``/``parent``)
    lands in the ``flight`` ring, where :func:`spans` finds it. Disabled
    telemetry returns a no-op context (the body still runs; nothing is
    recorded)."""
    if not registry._enabled:
        return _NULL_SPAN
    parent = _current_span.get()
    sid = next(_span_ids)
    trace = parent.trace_id if parent is not None else sid
    return _SpanCtx(Span(name, trace, sid,
                         parent.span_id if parent is not None else None,
                         attrs, labels))


def spans(names=None, since_ns: int = 0) -> List[dict]:
    """The finished span events still in the ``flight`` ring, oldest
    first: those named in ``names`` (all when None) that ended at or after
    ``since_ns`` on the wall clock. The ring is bounded and shared with
    compile and fault events, so a reader that needs a whole interval
    checks that the ring's oldest event (``flight.events()[0]["t"]``) is
    older than the interval's start."""
    return [e for e in flight.events()
            if e.get("type") == "span" and e["t1_ns"] >= since_ns
            and (names is None or e["name"] in names)]


def step_annotation(step_num: int, name: str = "train"):
    """``jax.profiler.StepTraceAnnotation`` for one training step (or a
    no-op when telemetry is off / jax is unavailable): device traces
    captured by ``ui.profiler.ProfilingListener`` then carry the step
    number, so trace timelines line up with the step-phase histograms."""
    prof = _jax_profiler() if registry._enabled else False
    if not prof:
        return _NULL_SPAN
    try:
        return prof.StepTraceAnnotation(name, step_num=step_num)
    except Exception:
        return _NULL_SPAN


# ------------------------------------------------------------- event log
_event_lock = threading.Lock()
_event_sink = None          # open file object, or None


class _EventLog:
    """Handle returned by :func:`event_log` (context-manager friendly).
    ``close()`` only closes the sink this handle opened — if the process
    has since re-pointed the event log elsewhere, a stale handle (or a
    ``with`` block wrapping the re-point) must not kill the new sink."""

    def __init__(self, path: str, sink):
        self.path = path
        self._sink = sink

    def close(self):
        global _event_sink
        with _event_lock:
            if _event_sink is not self._sink:
                return  # re-pointed since; not ours to close
            _event_sink.close()
            _event_sink = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def event_log(path: Optional[str]) -> Optional[_EventLog]:
    """Start appending structured JSONL events (spans, compile events) to
    ``path``; ``event_log(None)`` (or ``.close()``) stops. One sink per
    process — re-pointing closes the previous file."""
    global _event_sink
    with _event_lock:
        if _event_sink is not None:
            _event_sink.close()
            _event_sink = None
        if path is None:
            return None
        _event_sink = open(path, "a", encoding="utf-8")
        sink = _event_sink
    return _EventLog(path, sink)


def close_event_log():
    event_log(None)


def emit_event(event: dict) -> None:
    """Append one event to the JSONL sink (no-op without a sink). Adds a
    wall-clock ``t`` so offline consumers can align multiple processes,
    and — on a multi-host run — the pod ``host`` coordinate, so
    :func:`stitch_event_logs` can merge per-host files without blending
    who emitted what (ISSUE 13 cross-host stitching)."""
    sink = _event_sink
    if sink is None:
        return
    rec = {"t": time.time(), **event}
    if _host["count"] > 1 and "host" not in rec:
        rec["host"] = _host["index"]
    line = json.dumps(rec, default=str)
    with _event_lock:
        if _event_sink is not sink:  # closed/re-pointed while we serialized
            return
        _event_sink.write(line + "\n")
        _event_sink.flush()


# ------------------------------------------------------- host identity
#: Pod anti-blending (ISSUE 10 satellite): on a multi-host run every
#: process keeps its OWN registry, but a pod-level scrape (or an artifact
#: that merges per-host registries) must be able to tell the hosts apart —
#: so host-scoped surfaces (``train.phase.*``, ``parallel.overlap.buckets``,
#: checkpoint latency) add a ``host=<process_index>`` label cell.
#: Single-process runs keep their historical unlabeled cells (host_labels()
#: is {}), so nothing changes off-pod. ``parallel/launcher.py`` calls
#: :func:`set_host` right after ``jax.distributed`` comes up; tests
#: simulate a pod by setting it directly.
_host = {"index": 0, "count": 1}


def set_host(index: int, count: int) -> None:
    """Declare this process's pod coordinates (process_index, process
    count). ``count <= 1`` returns labeling to the single-process mode.

    Pod tracing hook (ISSUE 13): with ``DL4J_TPU_EVENT_LOG=<base>`` set,
    a multi-host process re-points its JSONL event sink to
    ``<base>.host<index>.jsonl`` the moment its pod coordinates are known
    (the launcher calls this right after ``jax.distributed`` comes up) —
    each host writes its own file, and :func:`stitch_event_logs` merges
    them into one pod-level trace."""
    _host["index"] = int(index)
    _host["count"] = int(count)
    base = os.environ.get("DL4J_TPU_EVENT_LOG")
    if base and int(count) > 1:
        try:
            event_log(f"{base}.host{int(index)}.jsonl")
        except OSError:
            pass  # an unwritable trace dir must not take the pod down


def host_labels() -> dict:
    """``{"host": "<process_index>"}`` on a multi-host run, else ``{}`` —
    splat into ``labeled()`` calls for host-scoped cells."""
    if _host["count"] > 1:
        return {"host": str(_host["index"])}
    return {}


# -------------------------------------------------------- retrace tracker
#: Compile causes every site reports through record_compile(). Not
#: enforced as a closed set — but keep to these names where they apply so
#: dashboards can aggregate across sites.
COMPILE_CAUSES = ("first_build", "warmup", "new_bucket", "dtype_policy",
                  "workspace_mode", "params_placement", "init",
                  "invalidate", "config_change", "precision", "probe",
                  "lr_backoff", "autotune", "overlap", "quantize",
                  "host_loss", "schedule_tune", "fleet_retire")

_compile_counter = counter(
    "compile.events",
    "lower+compile events by site and cause (retrace tracker); "
    "steady-state training must show zero after warmup")
_compiles_lock = threading.Lock()
_compile_log: deque = deque(maxlen=1024)


def record_compile(site: str, cause: str, **detail) -> None:
    """Record one lower+compile event. ``site`` is the compiling cache
    (``train.step``, ``serving.engine``, ``samediff.fit_step`` …);
    ``cause`` says *why* the program wasn't already cached. Every event
    counts into ``compile.events{site=,cause=}``, lands in the bounded
    in-memory log (:func:`compile_events`), and goes to the JSONL event
    sink. Always records (compiles are rare and functional — never a hot
    path), so the retrace tracker keeps working under
    ``DL4J_TPU_TELEMETRY=off``."""
    _compile_counter.inc(site=site, cause=cause)
    ev = {"type": "compile", "site": site, "cause": cause, **detail}
    with _compiles_lock:
        _compile_log.append(ev)
    emit_event(ev)
    flight.record(ev)


def compile_events(site: Optional[str] = None) -> List[dict]:
    """The in-memory compile-event log (most recent 1024), optionally
    filtered by site. For zero-compile steady-state assertions, delta the
    ``compile.events`` counter total instead of ``len()`` of this log —
    once the bounded log saturates, an append evicts the oldest entry and
    ``len()`` stops growing even though a compile happened."""
    with _compiles_lock:
        evs = list(_compile_log)
    return [e for e in evs if site is None or e["site"] == site]


def reset_compile_events() -> None:
    with _compiles_lock:
        _compile_log.clear()


# --------------------------------------------------- program scope tables
#: A device trace names every operation by its HLO instruction and carries
#: none of the program's scopes; the optimized HLO of the executable that
#: ran carries them, in each instruction's ``op_name``. The sites that
#: dispatch a training program hand its executable to
#: :func:`record_program` once; :func:`program_scopes` turns what was kept
#: into plain tables a trace's events are joined with by instruction name.

PROGRAMS_KEPT = 8    #: programs whose tables are kept, the newest

#: what a scope path is counted as, first match in this order: under one of
#: ``gradient_tail``'s scopes; recomputed inside a checkpointed segment; the
#: transpose of the loss function's ``forward``; its forward
PHASES = ("updater", "sentinel", "clip", "recompute", "backward", "forward",
          "other")

_programs_lock = threading.Lock()
_programs: deque = deque(maxlen=PROGRAMS_KEPT)
_DISPATCHED = "_dl4j_tpu_program_recorded"

_HLO_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[^\]]*\]")
_HLO_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
#: a scope of the program's is a vertex's name or a word, dots and dashes
#: inside (``attn.latent.project``); an einsum's subscripts, which JAX puts
#: on the path too, are not
_SCOPE_NAME = re.compile(r"^[A-Za-z_][\w.\-]*$")
#: path components that are JAX's own (control flow, checkpointing, custom
#: derivatives), not scopes of the program
_JAX_STRUCTURE = re.compile(
    r"^(while|body|cond|closed_call|checkpoint|rematted_computation|"
    r"branch_\d+_fun|custom_vjp_call\w*|custom_jvp_call\w*|pjit|remat\w*)$")


def record_program(site: str, executable, **labels) -> None:
    """Keep the optimized HLO of a training program for
    :func:`program_scopes`. ``executable`` is the loaded executable the
    site runs (``compiled.runtime_executable()``); what is kept
    is its ``hlo_modules()``, host data that holds neither the executable
    nor a device buffer, and the newest :data:`PROGRAMS_KEPT` of them.
    Nothing is rendered or parsed here. ``labels`` are kept as given and
    must be plain data; ``vertices`` (names) lets the tables say which
    vertex or layer of the model a scope path lies under. Always records,
    like :func:`record_compile`: once a program, never a hot path."""
    modules = list(executable.hlo_modules())
    with _programs_lock:
        _programs.append({"site": site, "labels": labels,
                          "module": modules[0].name if modules else "",
                          "hlo": modules, "instructions": None})


def record_dispatch(site: str, fn, args, labels=None) -> None:
    """:func:`record_program` for the program that ``fn(*args)`` has just
    run, where it was a new one. Called after the call, outside the phase
    spans: a ``jax.jit`` function's executable comes through JAX's own
    lowering cache, which the call filled (only the avals of ``args`` are
    read, so donated arrays do), and nothing compiles twice. New is judged
    by the function's count of specialisations, kept on the function: a
    second batch shape of one step function is a second program and gets
    its own table, and every other call costs that one count. An
    ahead-of-time ``Compiled`` is its own executable, recorded once.
    ``labels`` is called for the labels, then only. A stand-in that is
    neither (a test's plain function) records nothing."""
    if hasattr(fn, "_cache_size"):
        compiled = fn._cache_size()
        if compiled == getattr(fn, _DISPATCHED, 0):
            return
        setattr(fn, _DISPATCHED, compiled)
        executable = fn.lower(*args).compile().runtime_executable()
    elif hasattr(fn, "runtime_executable"):
        if getattr(fn, _DISPATCHED, 0):
            return
        setattr(fn, _DISPATCHED, 1)
        executable = fn.runtime_executable()
    else:
        return
    record_program(site, executable, **(labels() if labels else {}))


def _scope_cores(scope: str) -> List[str]:
    """The path's components without the transformations around them:
    ``transpose(jvp(forward))`` -> ``forward``."""
    return [c.rsplit("(", 1)[-1].split(")", 1)[0] for c in scope.split("/")]


def scope_phase(scope: str, cores: Optional[List[str]] = None) -> str:
    """One of :data:`PHASES` for a scope path (an ``op_name`` without its
    ``jit(...)`` wrappers). THE rule, for every reader."""
    if cores is None:
        cores = _scope_cores(scope)
    for c in cores:
        if c in ("updater", "sentinel", "clip"):
            return c
    if "rematted_computation" in cores:
        return "recompute"
    if "transpose(" in scope:
        return "backward"
    return "forward" if "forward" in cores else "other"


def _parse_hlo(text: str, vertices=()) -> dict:
    """``{instruction: {shape, scope, scopes, phase, phases_inside,
    vertex}}`` of one module's text: every instruction but those of fused computations,
    which only give their fusion's ``phases_inside``."""
    vertices = frozenset(vertices)
    comps, fused, cur = {}, set(), None
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                cur = comps.setdefault(c.group(1), [])
            continue
        if cur is None:
            continue
        name, rest = m.groups()
        shape = _HLO_SHAPE.search(rest)
        opcode = _HLO_OPCODE.search(rest)
        op_name = _HLO_OP_NAME.search(rest)
        scope = "/".join(c for c in op_name.group(1).split("/")
                         if not c.startswith(("jit(", "pjit("))) \
            if op_name else ""
        calls = None
        if opcode is not None and opcode.group(1) == "fusion":
            called = _HLO_CALLS.search(rest)
            if called is not None:
                calls = called.group(1)
                fused.add(calls)
        cur.append((name, shape.group(0) if shape else "", scope, calls))
    inside = {c: sorted({scope_phase(s) for _, _, s, _ in comps.get(c, ())
                         if s} - {"other"}) for c in fused}

    def named_inside(comp, seen=()):
        """The scope of the instruction nearest the root of a fused
        computation that carries one, through the fusions nested in it."""
        for _, _, scope, calls in reversed(comps.get(comp, ())):
            if not scope and calls and calls not in seen:
                scope = named_inside(calls, seen + (comp,))
            if scope:
                return scope
        return ""

    table = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, shape, scope, calls in instrs:
            if calls and not scope:
                # a fusion the compiler made and named after nothing
                scope = named_inside(calls)
            cores = _scope_cores(scope)
            # the last component is the primitive's name
            scopes = [c for c in cores[:-1] if c in vertices
                      or (_SCOPE_NAME.match(c)
                          and not _JAX_STRUCTURE.match(c))]
            table[name] = {
                "shape": shape, "scope": scope, "scopes": scopes,
                "phase": scope_phase(scope, cores),
                "phases_inside": inside[calls] if calls else [],
                "vertex": next((c for c in scopes if c in vertices), None)}
    return table


def program_scopes(site: Optional[str] = None) -> List[dict]:
    """The kept programs' scope tables, oldest first: ``{site, labels,
    module, instructions: {name: {shape, scope, scopes, phase,
    phases_inside, vertex}}}`` of plain data. ``name`` is the HLO
    instruction's (a trace event's name starts with it), ``shape`` its
    first result's ``dtype[dims]``, ``scope`` its ``op_name`` path without
    the ``jit(...)`` wrappers (a fusion that carries none is named after
    the instruction nearest its root that does), ``scopes`` the program's
    own names on that path, in order (the transformations around a name,
    JAX's structural components, an einsum's subscripts and the primitive dropped), ``phase``
    :func:`scope_phase` of the path, ``phases_inside`` for a fusion the
    phases, ``other`` aside, of the instructions fused into it (a
    weight-gradient kernel with the sentinel's sum riding it lists both),
    ``vertex`` the first of ``scopes`` that the ``vertices`` label names. A
    program is rendered and parsed at its first request; the HLO is let go
    then."""
    with _programs_lock:
        kept = [p for p in _programs if site is None or p["site"] == site]
    for p in kept:
        if p["instructions"] is None:
            table = {}
            for module in p["hlo"]:
                table.update(_parse_hlo(module.to_string(),
                                        p["labels"].get("vertices", ())))
            p["instructions"], p["hlo"] = table, None
    return [{k: v for k, v in p.items() if k != "hlo"} for p in kept]


def reset_programs() -> None:
    with _programs_lock:
        _programs.clear()


# ---------------------------------------------------- per-request tracing
#: Contextvars die at the dispatcher's queue boundary (the submit thread's
#: context never reaches the dispatcher/decode worker), so request tracing
#: is EXPLICIT (ISSUE 13): ``start_request_trace`` returns a
#: :class:`RequestTrace` the serving fronts thread through their queues on
#: the request object itself. Each trace accumulates a stitched timeline —
#: one-shot: queue→coalesce→pad→execute→unpad→resolve; generative:
#: queue→prefill→per-decode-iteration — whose phase durations sum to the
#: request's measured latency (tier-1-asserted to within 10%). Finished
#: traces land in a bounded in-memory store (``GET /trace/<id>``), in the
#: JSONL event log (one ``type="trace"`` line per request), and in the
#: flight recorder.

TRACE_STORE_LIMIT = 256    #: finished+live traces kept for GET /trace/<id>
TRACE_EVENT_LIMIT = 512    #: timeline events per trace (then counted, dropped)

_trace_lock = threading.Lock()
_trace_seq = itertools.count(1)
_trace_store: "OrderedDict[str, RequestTrace]" = OrderedDict()


class _NullTrace:
    """No-op trace handed out when telemetry is disabled — the serving
    hot paths call ``.phase()``/``.finish()`` unconditionally."""

    __slots__ = ()
    trace_id = None

    def phase(self, *a, **k):
        return None

    def finish(self, *a, **k):
        return None


NULL_TRACE = _NullTrace()


class RequestTrace:
    """One request's stitched timeline. Append-only: the submitting thread
    writes the enqueue mark, the dispatcher/decode worker appends phases,
    and exactly one ``finish()`` stamps status + total duration (list
    append is GIL-atomic; phases are single-writer per lifecycle stage by
    construction). Phase durations are SECONDS; ``shared=True`` marks a
    phase whose wall time was shared with the other members of a
    coalesced batch (pad/execute/unpad)."""

    __slots__ = ("trace_id", "kind", "attrs", "t_start", "t_wall",
                 "events", "status", "error", "duration_s", "dropped",
                 "_done")

    def __init__(self, kind: str, attrs: dict):
        # host- and process-qualified so pod-merged logs can never
        # collide two hosts' traces (the span-int ids need host
        # qualification at stitch time; these are born unique)
        self.trace_id = f"{_host['index']}-{os.getpid():x}-" \
                        f"{next(_trace_seq):x}"
        self.kind = kind
        self.attrs = dict(attrs)
        self.t_start = time.perf_counter()
        self.t_wall = time.time()
        self.events: List[dict] = []
        self.status: Optional[str] = None
        self.error: Optional[str] = None
        self.duration_s: Optional[float] = None
        self.dropped = 0
        self._done = False

    def phase(self, name: str, duration_s: float, **attrs) -> None:
        """Append one timeline phase (bounded: past TRACE_EVENT_LIMIT the
        event is counted into ``dropped_events`` instead — a 10k-token
        generation must not grow its trace without bound)."""
        if len(self.events) >= TRACE_EVENT_LIMIT:
            self.dropped += 1
            return
        ev = {"phase": name, "duration_s": float(duration_s)}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)

    def finish(self, status: str = "ok", error: Optional[str] = None,
               **attrs) -> None:
        """Stamp the terminal status exactly once (shed / deadline /
        shutdown / failure paths all resolve their span — satellite
        requirement: no request ends without a terminal trace record).
        The once-only guard is locked: a shutdown() racing a resolving
        dispatch calls finish from two threads, and emitting both an
        "ok" and an "error" record for one trace would double-count in
        every consumer."""
        with _trace_lock:
            if self._done:
                return
            self._done = True
        self.status = status
        self.error = error
        self.duration_s = time.perf_counter() - self.t_start
        if attrs:
            self.attrs.update(attrs)
        rec = self.timeline()
        emit_event({"type": "trace", **rec})
        flight.record({"type": "trace", **rec})

    def timeline(self) -> dict:
        """JSON-safe stitched timeline (the ``GET /trace/<id>`` body and
        the JSONL ``type="trace"`` record)."""
        rec = {"trace": self.trace_id, "kind": self.kind,
               "t": self.t_wall, "status": self.status,
               "duration_s": self.duration_s,
               "phases": list(self.events),
               "dropped_events": self.dropped}
        if self.error is not None:
            rec["error"] = self.error
        if _host["count"] > 1:
            rec["host"] = _host["index"]
        rec.update(self.attrs)
        return rec


def start_request_trace(kind: str, trace_id: Optional[str] = None,
                        **attrs):
    """New :class:`RequestTrace` registered in the bounded store (oldest
    evicted). Returns :data:`NULL_TRACE` when telemetry is disabled — the
    fenced ``telemetry_overhead`` contract covers tracing too.

    ``trace_id`` (ISSUE 18): CONTINUE an existing request's timeline
    under its origin id instead of minting a fresh one — the decode pool
    adopts the prefill pool's trace id so one disaggregated request
    still yields ONE stitched timeline across both processes
    (:func:`stitch_event_logs` groups by id; :func:`merge_trace_records`
    folds the per-pool records)."""
    if not registry._enabled:
        return NULL_TRACE
    tr = RequestTrace(kind, attrs)
    if trace_id:
        tr.trace_id = str(trace_id)
    with _trace_lock:
        _trace_store[tr.trace_id] = tr
        while len(_trace_store) > TRACE_STORE_LIMIT:
            _trace_store.popitem(last=False)
    return tr


def get_trace(trace_id: str) -> Optional[dict]:
    """Stitched timeline of one (possibly still-running) request, or None
    when unknown/evicted."""
    with _trace_lock:
        tr = _trace_store.get(trace_id)
    return tr.timeline() if tr is not None else None


def recent_traces(n: int = 32) -> List[dict]:
    """Newest-first ``{trace, kind, status, duration_s}`` summaries of the
    trace store (the ``GET /traces`` listing)."""
    with _trace_lock:
        trs = list(_trace_store.values())[-int(n):]
    return [{"trace": t.trace_id, "kind": t.kind, "status": t.status,
             "duration_s": t.duration_s} for t in reversed(trs)]


# the dispatcher thread installs a collector around the engine call so the
# engine's internal pad/execute/unpad clocks reach every member request's
# trace without the engine knowing about batching (contextvar: the engine
# call runs IN the dispatcher thread, so the context flows)
_phase_sink: contextvars.ContextVar = \
    contextvars.ContextVar("dl4j_tpu_phase_sink", default=None)


def phase_sink():
    """The active per-call phase collector (``callable(name, seconds)``),
    or None. Engines report their request-lifecycle phase durations here
    IN ADDITION to the phase histograms."""
    return _phase_sink.get()


class _PhaseSinkCtx:
    __slots__ = ("_collector", "_token")

    def __init__(self, collector):
        self._collector = collector
        self._token = None

    def __enter__(self):
        self._token = _phase_sink.set(self._collector)
        return self._collector

    def __exit__(self, *exc):
        _phase_sink.reset(self._token)
        return False


def sink_phases(collector) -> "_PhaseSinkCtx":
    """``with telemetry.sink_phases(lambda name, s: ...):`` — collect the
    engine-internal phase durations of every engine call in the body."""
    return _PhaseSinkCtx(collector)


def stitch_event_logs(paths) -> dict:
    """Merge JSONL event logs (one per host on a pod — see
    :func:`set_host`) into one pod-level view: all events wall-clock
    sorted, grouped by host-qualified trace id. Request traces are born
    host-qualified; bare integer span trace ids get an explicit
    ``<host>:<id>`` prefix here so two hosts' span counters can never
    blend. Unparseable lines are skipped (a torn final line from a killed
    host must not poison the stitch)."""
    events: List[dict] = []
    for p in paths:
        try:
            fh = open(p, "r", encoding="utf-8")
        except OSError:
            continue
        with fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if isinstance(ev, dict):
                    events.append(ev)
    events.sort(key=lambda e: e.get("t", 0.0))
    traces: Dict[str, List[dict]] = {}
    for ev in events:
        tid = ev.get("trace")
        if tid is None:
            continue
        key = tid if isinstance(tid, str) else \
            f"{ev.get('host', 0)}:{tid}"
        traces.setdefault(key, []).append(ev)
    return {"events": events, "traces": traces,
            "hosts": sorted({e.get("host", 0) for e in events})}


def merge_trace_records(records) -> dict:
    """One request, ONE timeline (ISSUE 18): fold the per-pool
    ``type="trace"`` records a disaggregated request emits — the prefill
    pool finishes its half at handoff, the decode pool finishes the
    request under the SAME trace id — into a single timeline dict.
    Phases concatenate in record wall-clock order; ``duration_s`` sums
    the per-pool spans (inter-pool transport rides the decode side's
    ``handoff`` phase, so phases still sum to the request's measured
    latency within tolerance); status/error come from the LAST record
    (the pool that resolved the request)."""
    recs = sorted((dict(r) for r in records), key=lambda r: r.get("t", 0.0))
    if not recs:
        return {}
    out = dict(recs[0])
    out["phases"] = [p for r in recs for p in r.get("phases", ())]
    out["dropped_events"] = sum(int(r.get("dropped_events", 0))
                                for r in recs)
    out["duration_s"] = sum(float(r.get("duration_s") or 0.0)
                            for r in recs)
    out["status"] = recs[-1].get("status")
    if recs[-1].get("error") is not None:
        out["error"] = recs[-1]["error"]
    elif "error" in out:
        del out["error"]
    out["pools"] = [r.get("pool") for r in recs if r.get("pool")]
    return out


def format_timeline(timeline: dict) -> str:
    """Human-readable rendering of one stitched timeline (the
    ``make trace-demo`` output). Consecutive same-name phases (decode
    iterations) collapse into one ``xN`` line."""
    if not timeline:
        return "(no trace)"
    hdr = (f"trace {timeline.get('trace')} kind={timeline.get('kind')} "
           f"status={timeline.get('status')}")
    dur = timeline.get("duration_s")
    if dur is not None:
        hdr += f" duration={dur * 1e3:.2f}ms"
    if timeline.get("error"):
        hdr += f" error={timeline['error']}"
    lines = [hdr]
    groups: List[List[dict]] = []
    for ev in timeline.get("phases", ()):
        if groups and groups[-1][0].get("phase") == ev.get("phase"):
            groups[-1].append(ev)
        else:
            groups.append([ev])
    for g in groups:
        name = g[0].get("phase")
        total = sum(e.get("duration_s", 0.0) for e in g)
        line = f"  {name:<12} {total * 1e3:9.3f}ms"
        if len(g) > 1:
            line += f"  x{len(g)}"
        extras = {k: v for k, v in g[0].items()
                  if k not in ("phase", "duration_s")}
        if extras:
            line += "  " + " ".join(f"{k}={v}" for k, v in
                                    sorted(extras.items()))
        lines.append(line)
    if timeline.get("dropped_events"):
        lines.append(f"  (+{timeline['dropped_events']} dropped events)")
    total = sum(e.get("duration_s", 0.0)
                for e in timeline.get("phases", ()))
    lines.append(f"  {'= phases':<12} {total * 1e3:9.3f}ms")
    return "\n".join(lines)


# ------------------------------------------------------------------- SLO
_G_BURN = gauge(
    "slo.burn_rate",
    "error-budget burn rate per SLO objective and window (1.0 = burning "
    "exactly the budget; multi-window alarms page on sustained high burn)")
_C_SLO_ALARMS = counter(
    "slo.alarms", "multi-window burn-rate alarm activations per SLO")


class SLO:
    """Windowed SLO objective over request outcomes (ISSUE 13): a target
    p99 latency and/or error-rate budget, evaluated as **multi-window
    burn rates** (the SRE-workbook alerting shape) over its own
    timestamped sample reservoir.

    A request is *bad* when it failed, or when ``target_p99_ms`` is set
    and its latency exceeded the target. The budget is the allowed bad
    fraction (``target_error_rate``, else ``error_budget``); the burn
    rate of a window is ``bad_fraction / budget``. :meth:`alarm` returns

    - ``"fast_burn"`` — both the fast and slow windows burn at
      >= ``fast_burn`` (the page: budget exhausts in hours);
    - ``"slow_burn"`` — the slow window burns at >= ``slow_burn`` (the
      ticket: sustained budget bleed);
    - ``None`` — healthy (or not enough recent samples to judge).

    The serving fronts consult this inside their HEALTHY / DEGRADED /
    SHEDDING state machine: a firing alarm reports DEGRADED even when no
    individual request failed hard. Burn rates export through the
    ``slo.burn_rate{slo=,window=}`` gauge on every evaluation."""

    def __init__(self, name: str, target_p99_ms: Optional[float] = None,
                 target_error_rate: Optional[float] = None,
                 error_budget: float = 0.01,
                 fast_window_s: float = 60.0, slow_window_s: float = 600.0,
                 fast_burn: float = 14.4, slow_burn: float = 6.0,
                 min_samples: int = 8, reservoir: int = 8192):
        if target_p99_ms is None and target_error_rate is None:
            raise ValueError("an SLO needs target_p99_ms and/or "
                             "target_error_rate")
        self.name = str(name)
        self.target_p99_ms = target_p99_ms
        self.target_error_rate = target_error_rate
        self.budget = float(target_error_rate
                            if target_error_rate is not None
                            else error_budget)
        if self.budget <= 0:
            raise ValueError("the error budget must be positive")
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.fast_burn = float(fast_burn)
        self.slow_burn = float(slow_burn)
        self.min_samples = int(min_samples)
        self._samples: deque = deque(maxlen=int(reservoir))
        self._lock = threading.Lock()
        self._alarmed: Optional[str] = None

    def record(self, latency_s: float, ok: bool = True) -> None:
        with self._lock:
            self._samples.append(
                (time.monotonic(), float(latency_s), bool(ok)))

    def _window(self, window_s: float, now: float):
        with self._lock:
            sel = [(l, ok) for t, l, ok in self._samples
                   if t >= now - window_s]
        if len(sel) < self.min_samples:
            return None, len(sel)
        bad = sum(1 for l, ok in sel
                  if not ok or (self.target_p99_ms is not None
                                and l * 1e3 > self.target_p99_ms))
        return bad / len(sel), len(sel)

    def burn_rate(self, window_s: float) -> Optional[float]:
        """``bad_fraction / budget`` over the last ``window_s`` seconds
        (None below ``min_samples`` — a cold SLO must not flap alarms on
        two requests)."""
        frac, _n = self._window(window_s, time.monotonic())
        return None if frac is None else frac / self.budget

    def alarm(self) -> Optional[str]:
        fast = self.burn_rate(self.fast_window_s)
        slow = self.burn_rate(self.slow_window_s)
        _G_BURN.set(fast, slo=self.name, window="fast")
        _G_BURN.set(slow, slo=self.name, window="slow")
        state = None
        if fast is not None and slow is not None and \
                fast >= self.fast_burn and slow >= self.fast_burn:
            state = "fast_burn"
        elif slow is not None and slow >= self.slow_burn:
            state = "slow_burn"
        if state is not None and state != self._alarmed:
            _C_SLO_ALARMS.inc(slo=self.name, kind=state)
            flight.record({"type": "slo_alarm", "slo": self.name,
                           "kind": state, "fast_burn_rate": fast,
                           "slow_burn_rate": slow})
        self._alarmed = state
        return state

    def snapshot(self) -> dict:
        fast = self.burn_rate(self.fast_window_s)
        slow = self.burn_rate(self.slow_window_s)
        return {"name": self.name, "target_p99_ms": self.target_p99_ms,
                "target_error_rate": self.target_error_rate,
                "budget": self.budget,
                "burn_rate_fast": fast, "burn_rate_slow": slow,
                "alarm": self._alarmed}


# -------------------------------------------------------- flight recorder
_C_DUMPS = counter(
    "flight.dumps",
    "flight-recorder JSONL dumps by trigger kind (fault trip, serving "
    "failure, explicit)")


class FlightRecorder:
    """Bounded in-memory black box (ISSUE 13): the last N structured
    events — spans, compile events, fault trips, finished request traces,
    SLO alarms — ring-buffered as they happen, dumped to JSONL when
    something goes wrong. Triggers: any fault-site trip that FIRES
    (``runtime/faults.py``), an unhandled serving dispatch/decode
    failure, or an explicit :meth:`dump`.

    ``configure(dir=...)`` (or ``DL4J_TPU_FLIGHT_DIR``) points dumps at a
    directory (``flight_<n>_<reason>.jsonl``, header line first); without
    one, auto-dumps still capture to :attr:`last_dump` in memory. The
    dump header snapshots the fault counters and the ``sentinel.*`` /
    ``resilience.*`` registry cells, so the r10 resilience machinery's
    state at failure time rides along with the event ring."""

    def __init__(self, capacity: int = 2048,
                 min_interval_s: float = 1.0):
        self._ring: deque = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._dir = os.environ.get("DL4J_TPU_FLIGHT_DIR") or None
        self._seq = itertools.count(1)
        #: auto-dump rate limit, per reason: a hot path tripping the same
        #: fault (or shedding the same way) thousands of times must not
        #: rewrite the whole ring to a new file per event
        self.min_interval_s = float(min_interval_s)
        self._last_auto: Dict[str, float] = {}
        self.last_dump: Optional[dict] = None

    def configure(self, dir=_MISSING, capacity: Optional[int] = None,
                  min_interval_s: Optional[float] = None
                  ) -> "FlightRecorder":
        """``dir=None`` explicitly disables file dumps; OMITTING ``dir``
        keeps the current directory (so a capacity-only reconfigure
        cannot silently drop the ``DL4J_TPU_FLIGHT_DIR`` target)."""
        with self._lock:
            if dir is not _MISSING:
                self._dir = dir
            if capacity is not None:
                self._ring = deque(self._ring, maxlen=int(capacity))
            if min_interval_s is not None:
                self.min_interval_s = float(min_interval_s)
        return self

    def record(self, ev: dict) -> None:
        """Ring-append one event (cheap: the deque bounds itself; hot
        callers pass the dict they already built for the event log)."""
        if "t" not in ev:
            ev = {"t": time.time(), **ev}
        self._ring.append(ev)

    def events(self) -> List[dict]:
        return list(self._ring)

    def _state_header(self, reason: str, n_events: int) -> dict:
        header = {"type": "flight_dump", "reason": reason,
                  "t": time.time(), "events": n_events,
                  "host": _host["index"]}
        try:
            from . import faults as _faults
            header["fault_counters"] = _faults.counters()
        except Exception:
            pass
        counters = {}
        for name in registry.names():
            if name.startswith(("sentinel.", "resilience.", "faults.")):
                m = registry.get(name)
                if m is not None and m.kind != HISTOGRAM:
                    counters[name] = m.total() if m.kind == COUNTER \
                        else {json.dumps(dict(k)): v
                              for k, v in m.series().items()}
        header["counters"] = counters
        return header

    def dump(self, reason: str = "explicit",
             path: Optional[str] = None) -> dict:
        """Write the ring as JSONL (header line first). Returns the dump
        dict (``path`` is None when no directory/path is configured —
        the in-memory :attr:`last_dump` still captures everything)."""
        evs = list(self._ring)
        header = self._state_header(reason, len(evs))
        target = path
        if target is None and self._dir is not None:
            tag = re.sub(r"[^a-zA-Z0-9_.-]", "_", reason)
            target = os.path.join(
                self._dir, f"flight_{next(self._seq):04d}_{tag}.jsonl")
        if target is not None:
            os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
            with open(target, "w", encoding="utf-8") as f:
                f.write(json.dumps(header, default=str) + "\n")
                for ev in evs:
                    f.write(json.dumps(ev, default=str) + "\n")
        out = {"reason": reason, "path": target, "header": header,
               "events": evs}
        self.last_dump = out
        _C_DUMPS.inc(kind=reason.split(":", 1)[0])
        return out

    def auto_dump(self, reason: str) -> Optional[dict]:
        """Dump, rate-limited per reason (``min_interval_s``), and never
        let recorder trouble compound the original failure (disk full
        during an incident is exactly when this fires). Returns None
        when suppressed by the rate limit."""
        now = time.monotonic()
        with self._lock:
            last = self._last_auto.get(reason)
            if last is not None and now - last < self.min_interval_s:
                return None
            self._last_auto[reason] = now
        try:
            return self.dump(reason)
        except Exception as e:
            try:
                import logging
                logging.getLogger("deeplearning4j_tpu").warning(
                    "flight-recorder dump failed (%s: %s)",
                    type(e).__name__, e)
            except Exception:
                pass
            return None


#: THE process-wide flight recorder (spans/compiles/traces record into it
#: unconditionally-when-enabled; faults.trip() and the serving failure
#: paths trigger auto-dumps).
flight = FlightRecorder()
