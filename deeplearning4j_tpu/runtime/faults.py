"""Deterministic fault injection + the failure taxonomy (ISSUE 5 tentpole).

Every recovery path in the stack — divergence-sentinel step skips,
checkpoint corruption fallback, auto-resume after a crash, serving load
shed / retry — routes its failure point through this registry, so each
path is exercised deterministically in tier-1 on CPU instead of waiting
for a real preemption to find the bug (the TensorFlow OSDI-2016 position:
fault tolerance is only real when re-execution is testable).

Model:

- A **site** is a named failure point compiled into the product code
  (``trip("train.step")``). The full set is static (:data:`SITES`) so the
  coverage floor in ``tests/test_zz_coverage_floor.py`` can assert every
  site is triggered by at least one test — zero silent fallbacks.
- An **injection** arms a site: ``inject("train.step", error="crash",
  after=3, times=1)`` or env-driven ``DL4J_TPU_FAULTS=
  "train.step:error=crash:after=3"``. Deterministic by construction:
  triggering is counted per call (``after``/``times``), with an optional
  *seeded* probability for soak-style tests.
- ``trip(site)`` is the single product-side hook: counts the call,
  decides, then (in order) sleeps ``delay``, raises ``error``, or returns
  the armed injection for poison-style sites (caller corrupts its own
  data). With no armed injection it is a dict lookup — ``enabled()``
  lets hot loops skip even that.

Counters are never silent: per-site calls/fired counts (:func:`counters`),
plus a process-lifetime ledger of sites ever fired (:func:`coverage_report`)
that ``reset()`` does NOT clear — the floor reads it after the suite.

This module is stdlib-only at import time so every layer (nn, serving,
datavec, parallel) can import it without cycles.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import Dict, Optional

from . import telemetry as _tel

log = logging.getLogger("deeplearning4j_tpu")


# --------------------------------------------------------------- taxonomy
class FaultError(Exception):
    """Base class for injected faults (lets tests assert provenance)."""


class InjectedCrash(FaultError):
    """Preemption-shaped runtime failure (the injectable stand-in for a
    device loss / ``XlaRuntimeError`` / worker kill). Matched as
    transient by :func:`is_transient`, so auto-resume retries it."""


class InjectedIOError(FaultError, OSError):
    """Reader/storage I/O failure (bad record, lost mount)."""


class HostLoss(InjectedCrash):
    """A whole host dropped out of the pod (machine death / preemption of
    one worker). Unlike a plain :class:`InjectedCrash`, recovery needs the
    *control plane* rebuilt, not just a checkpoint restore: the surviving
    job re-runs ``launcher.reinitialize()`` (shutdown + ``jax.distributed``
    re-init — every live jax.Array dies with the old client) before the
    restore. ``run_resilient_fit`` routes this subtype through that path
    (ISSUE 10); it stays transient (subclass) so the restart budget and
    backoff apply unchanged."""


class TornWrite(FaultError):
    """A checkpoint write that was interrupted mid-flight."""


class CorruptCheckpoint(Exception):
    """Checkpoint failed checksum/manifest verification on restore."""


class DivergenceError(Exception):
    """The divergence sentinel escalated: K consecutive non-finite steps.
    Raised host-side by the resilience policy, caught by the resilient
    fit driver (rollback to last good checkpoint + optional LR backoff)."""


class DeadlineExceeded(Exception):
    """A serving request's deadline expired before dispatch."""


class QueueFull(Exception):
    """Serving queue above the load-shedding threshold: fast rejection
    instead of unbounded linger."""


class ShutdownError(RuntimeError):
    """The serving front was shut down while the request was queued or in
    flight. Subclasses RuntimeError for pre-ISSUE-5 caller compatibility."""


_ERROR_KINDS = {
    "crash": lambda site: InjectedCrash(f"injected crash at {site!r}"),
    "io": lambda site: InjectedIOError(f"injected I/O error at {site!r}"),
    "torn": lambda site: TornWrite(f"injected torn write at {site!r}"),
    "host_loss": lambda site: HostLoss(
        f"injected whole-host loss at {site!r}"),
}


def is_transient(exc: BaseException) -> bool:
    """Is this failure worth an automatic retry/resume? True for injected
    crashes/IO faults, real XLA runtime failures (device loss, preemption
    — matched by type NAME since jaxlib's exception type moved across
    versions), and host I/O errors from data pipelines. Deliberately NOT
    true for ValueError/TypeError-shaped bugs: retrying those loops
    forever on a programming error."""
    if isinstance(exc, (InjectedCrash, InjectedIOError)):
        return True
    for t in type(exc).__mro__:
        if t.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return isinstance(exc, (OSError, IOError, ConnectionError))


# --------------------------------------------------------------- registry
#: The static site set. Adding a product-side trip() requires adding its
#: name here; the coverage floor then requires a test that fires it.
SITES = frozenset({
    "train.step",         # host fit loop, before step dispatch (crash/preempt)
    "train.nonfinite",    # poison the batch -> non-finite grads (sentinel)
    "checkpoint.write",   # torn checkpoint write (corrupts a saved file)
    "data.record",        # reader error on one record/batch (skip-and-log)
    "serving.dispatch",   # transient executor failure (retried once)
    "serving.slow",       # injected dispatch latency (overload -> shedding)
    "serving.decode",     # continuous-batching decode iteration failure
    "serving.quantize",   # weight quantization failure -> f32 fallback
    "serving.page_pool",  # paged-KV page allocation failure / pressure
    "parallel.host_loss",  # whole host drops out of the pod (reinit+restore)
    # model-fleet hot-swap sites (ISSUE 20). Taxonomy mapping:
    "fleet.load",         # background checkpoint load/warm failure —
                          # TRANSIENT class: the watcher retries with
                          # backoff, exhaustion fails the step loudly and
                          # the incumbent keeps serving
    "fleet.swap",         # failure at the atomic flip point — rollback
                          # class: candidate marked FAILED, old version
                          # keeps serving, flight-recorder dump
    "fleet.canary",       # forced canary-gate trip — NOT an error:
                          # rollback is the designed outcome, nothing
                          # propagates to callers
})


class Injection:
    """One armed fault. Trigger rule, evaluated per ``trip()`` call:
    calls ``<= after`` never fire; afterwards up to ``times`` fires happen
    (every eligible call with ``p=1.0``, else a seeded coin per call)."""

    __slots__ = ("site", "error", "after", "times", "delay", "p",
                 "_rng", "calls", "fired")

    def __init__(self, site: str, *, error: Optional[str] = None,
                 after: int = 0, times: float = 1, delay: float = 0.0,
                 p: float = 1.0, seed: int = 0):
        if site not in SITES:
            raise ValueError(f"unknown fault site {site!r}; registered "
                             f"sites: {sorted(SITES)}")
        if error is not None and error not in _ERROR_KINDS:
            raise ValueError(f"unknown error kind {error!r}; expected one "
                             f"of {sorted(_ERROR_KINDS)}")
        self.site = site
        self.error = error
        self.after = int(after)
        self.times = float(times)          # float('inf') = every call
        self.delay = float(delay)
        self.p = float(p)
        self._rng = random.Random(seed)    # seeded: deterministic soak
        self.calls = 0
        self.fired = 0

    def should_fire(self) -> bool:
        self.calls += 1
        if self.calls <= self.after or self.fired >= self.times:
            return False
        if self.p < 1.0 and self._rng.random() >= self.p:
            return False
        self.fired += 1
        return True

    def make_error(self) -> Exception:
        return _ERROR_KINDS[self.error](self.site)


_lock = threading.Lock()
_active: Dict[str, Injection] = {}
# per-site calls/fired live in the process-wide MetricsRegistry (ISSUE 6);
# counters() below is the pre-registry view over them
_CALLS = _tel.counter("faults.calls",
                      "trip() evaluations per fault site")
_FIRED = _tel.counter("faults.fired",
                      "injections fired per fault site")
_ledger: set = set()       # sites ever fired this process; reset() keeps it


def inject(site: str, **kw) -> Injection:
    """Arm ``site`` (see :class:`Injection` for the trigger rule).
    Replaces any previous injection at the same site."""
    inj = Injection(site, **kw)
    with _lock:
        _active[site] = inj
    return inj


def clear(site: str) -> None:
    with _lock:
        _active.pop(site, None)


def enabled() -> bool:
    """Any injection armed? Hot loops guard their trip() calls on this —
    the steady-state cost of the whole registry is one bool read."""
    return bool(_active)


def trip(site: str) -> Optional[Injection]:
    """The product-side hook at a failure point. Counts the call; when the
    armed injection fires: sleeps ``delay`` (if any), raises ``error`` (if
    any), else returns the injection so the caller can poison its own data.
    Returns None when nothing fires."""
    if site not in SITES:
        raise ValueError(f"trip() at unregistered fault site {site!r}")
    with _lock:
        inj = _active.get(site)
        fire = inj is not None and inj.should_fire()
        if fire:
            _ledger.add(site)
    # calls+fired move as ONE unit vs a concurrent reset(): a reset
    # landing mid-trip can never zero calls but keep fired (fired>calls)
    with _tel.registry.locked():
        _CALLS.inc(site=site)
        if fire:
            _FIRED.inc(site=site)
    if not fire:
        return None
    log.warning("fault injection fired at %r (%d/%s)", site, inj.fired,
                inj.times)
    # black box (ISSUE 13): every fired trip lands in the flight-recorder
    # ring AND triggers a dump — the spans/compiles/traces leading up to
    # the fault are on disk before any recovery path runs
    _tel.flight.record({"type": "fault", "site": site,
                        "error": inj.error, "fired": inj.fired})
    _tel.flight.auto_dump(f"fault:{site}")
    if inj.delay:
        time.sleep(inj.delay)
    if inj.error is not None:
        raise inj.make_error()
    return inj


def counters() -> dict:
    """Per-site ``{site: {"calls": n, "fired": m}}`` since the last reset.
    A view over the MetricsRegistry (``faults.calls`` / ``faults.fired``,
    labeled by site) — same shape as the pre-registry dicts."""
    with _tel.registry.locked():  # one consistent read: fired <= calls
        calls = {k[0][1]: int(v) for k, v in _CALLS.series().items()}
        fired = {k[0][1]: int(v) for k, v in _FIRED.series().items()}
    # Metric.zero keeps cells at 0; drop them so counters() is {} right
    # after reset() (the pre-registry "since the last reset" contract —
    # consumers enumerate the keys to see which sites were exercised)
    return {s: {"calls": calls.get(s, 0), "fired": fired.get(s, 0)}
            for s in sorted(set(calls) | set(fired))
            if calls.get(s, 0) or fired.get(s, 0)}


def coverage_report() -> dict:
    """Process-lifetime fault-site coverage (the zz floor's input):
    ``unfired`` lists registered sites no test has ever triggered."""
    with _lock:
        fired = sorted(_ledger)
    return {"registered": sorted(SITES), "fired": fired,
            "unfired": sorted(SITES - set(fired))}


def reset() -> None:
    """Disarm everything and zero the per-run counters. The coverage
    ledger survives (it accumulates across the whole test session)."""
    with _lock:
        _active.clear()
    with _tel.registry.locked():  # pairs with trip()'s atomic inc unit
        _CALLS.zero()
        _FIRED.zero()


# -------------------------------------------------------------- telemetry
#: Cross-cutting resilience telemetry, written by the checkpointer and the
#: resilient fit driver, read by PerformanceListener / ui.StatsListener.
#: Since ISSUE 6 the storage is the process-wide MetricsRegistry
#: (``resilience.*`` counters/gauges); the bump/set/snapshot API is the
#: historical view over it, so every pre-existing caller keeps working and
#: the values scrape through ``GET /metrics``.
_TELEMETRY_ZERO = {
    "checkpoint_saves": 0,
    "checkpoint_last_save_latency_s": None,
    "restore_count": 0,
    "restore_fallbacks": 0,
    "auto_resumes": 0,
    "divergence_rollbacks": 0,
    "host_loss_recoveries": 0,
}
#: keys with a None zero are gauges (last-observed value), the rest are
#: monotonic counters
_TELEMETRY_GAUGES = {k for k, z in _TELEMETRY_ZERO.items() if z is None}
for _k in _TELEMETRY_ZERO:
    (_tel.gauge if _k in _TELEMETRY_GAUGES else _tel.counter)(
        f"resilience.{_k}")


def _telemetry_metric(key: str, gauge: bool):
    name = f"resilience.{key}"
    m = _tel.registry.get(name)
    if m is not None:  # declared (pre-known or first write): keep its kind
        return m
    return (_tel.gauge if gauge else _tel.counter)(name)


# The pre-registry dict accepted any key from either API (bump was
# ``d[k] += n``, set was ``d[k] = v``). The registry splits keys into
# counters and gauges on first write — so a key that crosses APIs keeps
# the old contract instead of raising TypeError on kind mismatch.
def telemetry_bump(key: str, n: int = 1) -> None:
    m = _telemetry_metric(key, gauge=False)
    if m.kind == _tel.GAUGE:  # first written via telemetry_set
        with _tel.registry.locked():  # atomic read-modify-write
            m.set((m.value(default=0) or 0) + n)
    else:
        m.inc(n)


def telemetry_set(key: str, value) -> None:
    m = _telemetry_metric(key, gauge=True)
    if m.kind == _tel.COUNTER:  # first written via telemetry_bump
        with _tel.registry.locked():  # no reader sees the transient zero
            m.zero()
            if value:
                m.inc(value)
    else:
        m.set(value)


def telemetry_snapshot() -> dict:
    out = {}
    for name in _tel.registry.names():
        if not name.startswith("resilience."):
            continue
        m = _tel.registry.get(name)
        key = name[len("resilience."):]
        if m.kind == _tel.GAUGE:
            out[key] = m.value(default=None)
        else:
            out[key] = int(m.total())
    for k, z in _TELEMETRY_ZERO.items():
        out.setdefault(k, z)
    return out


def telemetry_reset() -> None:
    for name in _tel.registry.names():
        if name.startswith("resilience."):
            _tel.registry.get(name).zero()


# ------------------------------------------------------------- env config
def configure_from_env(var: str = "DL4J_TPU_FAULTS") -> int:
    """Arm injections from an env spec — the ops-facing knob:
    ``DL4J_TPU_FAULTS="train.step:error=crash:after=3,serving.slow:delay=0.1"``.
    Fields after the site name are ``key=value`` pairs matching
    :class:`Injection` kwargs (``times=inf`` accepted). Returns the number
    of injections armed."""
    spec = os.environ.get(var, "").strip()
    if not spec:
        return 0
    n = 0
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        site, kw = fields[0], {}
        for f in fields[1:]:
            k, _, v = f.partition("=")
            if k == "error":
                kw[k] = v
            elif k in ("after", "seed"):
                kw[k] = int(v)
            elif k in ("times", "delay", "p"):
                kw[k] = float(v)
            else:
                raise ValueError(f"unknown fault spec field {k!r} in {part!r}")
        inject(site, **kw)
        n += 1
    return n


configure_from_env()
