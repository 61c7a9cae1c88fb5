"""Divergence-sentinel device helpers (ISSUE 5 tentpole, layer 1).

Non-finite detection of the loss and the global gradient norm is fused
*into* the compiled train step of every engine, in one place:
``nn/trainstep.py``'s ``gradient_tail``, which the engines'
``_build_train_step`` (``nn/engine.py``), SameDiff's ``__fit_step__`` and
the ParallelWrapper's sharded step (the engine's step) all run: the
skip decision is a ``lax.cond`` around the updater application, and the
bad-step bookkeeping is a tree of on-device int32 scalars threaded
through the step like the optimizer state. Steady state therefore adds
ZERO host syncs and ZERO retraces — the counters only reach the host
when somebody asks (``model.resilience_counters()``), which the
resilience policy does at its own cadence.

DL4J divergence (recorded in PARITY.md): DL4J surfaces NaN gradients as
an exception from the updater; here the step *skips* the update (params,
updater state and BN state keep their pre-step values), counts it, and
lets the host-side ``ResiliencePolicy`` escalate after K consecutive bad
steps — an exception inside a fused XLA program is not expressible.

This module lives in ``runtime`` (not ``parallel``) so the nn engines can
import it at module level without a package cycle; ``parallel/
resilience.py`` re-exports it as part of the policy API.
"""

from __future__ import annotations

import itertools
import weakref

import jax
import jax.numpy as jnp

from . import telemetry as _tel

_model_ids = itertools.count()

#: registry mirrors of the on-device counters (gauges: last-synced value).
#: Written ONLY at the deliberate resilience_counters() sync point — the
#: fused step itself never touches the host, and neither does telemetry.
_GAUGES = {n: _tel.gauge(f"sentinel.{n}",
                         "divergence-sentinel counter (last host sync)")
           for n in ("bad_total", "bad_consec", "clip_events")}

#: Counter slots carried through the step (a dict pytree of int32 scalars):
#: - bad_total:   lifetime count of skipped (non-finite) steps
#: - bad_consec:  consecutive skipped steps (reset by any good step) — the
#:                quantity ResiliencePolicy escalates on
#: - clip_events: steps on which gradient clipping actually engaged
COUNTERS = ("bad_total", "bad_consec", "clip_events")


def init_counters():
    """Fresh on-device counter tree (all zeros)."""
    return {n: jnp.zeros((), jnp.int32) for n in COUNTERS}


def counter_avals():
    """ShapeDtypeStructs matching :func:`init_counters` — for AOT
    lowering (``nn/memory.py`` accounts the REAL step, sentinel included)."""
    return {n: jax.ShapeDtypeStruct((), jnp.int32) for n in COUNTERS}


def finite_ok(loss, grads):
    """Traced predicate: is this step safe to apply? True iff the loss and
    the global gradient sum-of-squares are both finite. The sum of squares
    is accumulated in f32; an overflow to inf flags the step bad, which is
    the intended reading (a gradient that overflows f32 IS divergence)."""
    gss = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads))
    return jnp.isfinite(loss) & jnp.isfinite(gss)


def update_counters(counters, ok, clip_events=None):
    """Next counter tree given this step's verdict. Pure/traced."""
    bad = jnp.where(ok, 0, 1).astype(jnp.int32)
    return {
        "bad_total": counters["bad_total"] + bad,
        "bad_consec": jnp.where(ok, 0, counters["bad_consec"] + 1
                                ).astype(jnp.int32),
        "clip_events": counters["clip_events"] +
        (jnp.int32(0) if clip_events is None
         else jnp.asarray(clip_events, jnp.int32)),
    }


def guarded_apply(ok, apply_fn, params, opt_state):
    """``lax.cond`` the updater application on the sentinel verdict:
    good step -> ``apply_fn(params, opt_state)`` (the full updater +
    constraints pipeline), bad step -> identity (the non-finite gradient
    never touches params or updater state). Branch functions, not
    ``where``-selects, so the bad branch skips the update arithmetic
    entirely on backends that execute conditionals lazily."""
    return jax.lax.cond(
        ok,
        lambda args: apply_fn(*args),
        lambda args: args,
        (params, opt_state))


def to_host(counters) -> dict:
    """Counter tree -> python ints (the ONE deliberate sync point; callers
    choose the cadence). None/missing -> zeros."""
    if not counters:
        return {n: 0 for n in COUNTERS}
    return {k: int(v) for k, v in counters.items()}


class SentinelCounterMixin:
    """The model-side sentinel counter surface, shared by BOTH nn engines
    (via ``nn.caches.CompiledCacheMixin``) and ``SameDiff`` — one
    implementation so a new counter slot or a to_host change cannot
    drift between engines. ``_sentinel`` is NOT a compiled-trace cache:
    counters are values and survive dtype/workspace mutations."""

    _sentinel = None

    _tel_label = None

    @property
    def telemetry_label(self) -> str:
        """Stable per-model registry label (``model=<n>``) so per-model
        cells (phase histograms, sentinel gauges) from concurrent models
        don't blend or overwrite each other. Lazily assigned; a finalizer
        drops the cells when the model is collected so churn cannot grow
        the registry (or ``/metrics``) unboundedly."""
        if self._tel_label is None:
            self._tel_label = str(next(_model_ids))
            weakref.finalize(self, _tel.registry.discard_cells,
                             model=self._tel_label)
        return self._tel_label

    def _phase_labels(self) -> dict:
        """The labels of this model's ``train.phase.*`` spans and their
        histogram cells: ``model=<id>`` — plus ``host=<process_index>`` on
        a multi-host run, so a pod-level scrape/merge never blends the
        hosts' step-time distributions (ISSUE 10 satellite)."""
        return {"model": self.telemetry_label, **_tel.host_labels()}

    def _ensure_sentinel(self):
        if self._sentinel is None:
            self._sentinel = init_counters()
        return self._sentinel

    def resilience_counters(self) -> dict:
        """Host view of the divergence-sentinel counters (skipped-step
        totals, consecutive skips, clip events). THE deliberate sync
        point — the fused step itself never touches the host; call this
        at whatever cadence the caller can afford (the resilience policy
        reads a one-step-lagged counter so the check overlaps the
        in-flight step). Each sync also mirrors the values into the
        MetricsRegistry (``sentinel.*`` gauges) so they scrape through
        ``GET /metrics`` at whatever cadence the last reader chose."""
        c = to_host(self._sentinel)
        # gauges carry model=<id>: concurrent models syncing into one
        # unlabeled cell would overwrite each other, and a scrape could
        # show a healthy model's zeros while the other skips every step
        lbl = self.telemetry_label
        for n, g in _GAUGES.items():
            if n in c:
                g.set(c[n], model=lbl)
        return c

    def reset_resilience_counters(self):
        """Zero the sentinel counters (after a rollback the consecutive-
        bad count must not immediately re-escalate)."""
        self._sentinel = init_counters()
        return self
