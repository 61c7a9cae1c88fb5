"""Compiled-trace cache management shared by the two network engines.

``MultiLayerNetwork`` and ``ComputationGraph`` cache compiled callables
(train step, train-mode output, epoch scan, serving engine executables)
that bake the layer topology and the conf dtype policy in at trace time.
This mixin owns the one invalidation contract for both, so a new cache
site or mutation point gets fixed in exactly one place.
"""

from __future__ import annotations

from .. import dtypes as _dt
from ..runtime import telemetry as _tel
from ..runtime.sentinel import SentinelCounterMixin


_DONE = object()      # the end of an iterator in ``_timed_batches``


class _TimedDispatch:
    """The ``train.phase.step_s`` span of one dispatch (the call of the
    jitted step or epoch function until it returns: the enqueue, since the
    step is async; a growing value means the host loop, not the device, is
    the bottleneck) with the ``StepTraceAnnotation`` inside it, so device
    traces carry step numbers. Tiny hand-rolled context manager: this runs
    every fit-loop step."""

    __slots__ = ("span", "ann")

    def __init__(self, labels: dict, iteration: int):
        self.span = _tel.span("train.phase.step_s", labels, step=iteration)
        self.ann = _tel.step_annotation(iteration)

    def __enter__(self):
        self.span.__enter__()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        r = self.ann.__exit__(*exc)
        self.span.__exit__(*exc)
        return r


class CompiledCacheMixin(SentinelCounterMixin):
    """Invalidation + dtype-policy mutation + serving-engine access +
    the divergence-sentinel counter surface (SentinelCounterMixin —
    shared with SameDiff so the contract cannot drift)."""

    # attributes cleared together on invalidation; subclasses extend
    # (MultiLayerNetwork adds the rnn streaming pair)
    _cache_attrs = ("_train_step", "_train_output_fn", "_epoch_fn")

    #: why the NEXT compiled-fn build is happening (retrace tracker,
    #: ISSUE 6): set by _invalidate_compiled, consumed by the build sites
    #: so every recompile event carries its cause.
    _retrace_cause = None

    #: cache attr -> invalidation cause for every cache that existed when
    #: _invalidate_compiled fired, so SIBLING rebuilds are attributed too
    #: (lazily created instance dict; the class attr stays None)
    _stale_build_causes = None

    # telemetry_label (model=<id> registry label) is inherited from
    # SentinelCounterMixin so SameDiff shares the same contract

    def _replace_conf_dtype(self, dtype: str):
        """Return a conf carrying ``dtype`` WITHOUT mutating the current
        one in place — confs may be shared across nets, and a sibling's
        live traces must not see the new policy without their own
        invalidation."""
        raise NotImplementedError

    def _invalidate_compiled(self, cause: str = "invalidate"):
        """Drop every cached compiled function. MUST be called at any
        mutation that a live trace baked in — layer topology or the conf
        dtype policy (param *values* are traced arguments and need no
        invalidation; param avals retrace plain jits automatically, but
        the AOT serving engine and conf-dependent closures do not).
        ``cause`` feeds the retrace tracker: the rebuild of EVERY cache
        that existed at invalidation time records a compile event with
        this cause (same contract as the serving engine's per-bucket
        stale map)."""
        if self._stale_build_causes is None:
            self._stale_build_causes = {}
        # refresh pending entries too: a cache invalidated twice before
        # its rebuild is attributed to the most recent mutation
        for a in self._stale_build_causes:
            self._stale_build_causes[a] = cause
        for a in self._cache_attrs:
            if getattr(self, a, None) is not None:
                self._stale_build_causes[a] = cause
            setattr(self, a, None)
        self._retrace_cause = cause
        # every engine serving this model (the lazily-built default AND
        # externally constructed ones — engines self-register weakly)
        for eng in list(getattr(self, "_serving_engines", ())):
            eng.invalidate(cause=cause)

    def _consume_retrace_cause(self, cache_attr: str = None) -> str:
        """The cause for a compile event at a build site. A site that
        names its ``cache_attr`` reads the per-cache stale map first, so
        a sibling cache rebuilt AFTER another already consumed the
        one-shot armed cause (e.g. ``_epoch_fn`` rebuilt on the next
        ``fit_on_device`` long after ``set_dtype`` rebuilt
        ``_train_step``) is still attributed to the invalidation rather
        than reading as a ``first_build``. Falls back to the one-shot
        armed cause, else ``first_build``."""
        if cache_attr is not None and self._stale_build_causes:
            stale = self._stale_build_causes.pop(cache_attr, None)
            if stale is not None:
                self._retrace_cause = None
                return stale
        c = self._retrace_cause or "first_build"
        self._retrace_cause = None
        return c

    def _record_build(self, site: str, cache_attr: str = None,
                      **detail) -> None:
        """Report one compiled-fn (re)build to the retrace tracker."""
        _tel.record_compile(site, self._consume_retrace_cause(cache_attr),
                            model=type(self).__name__, **detail)

    def set_dtype(self, dtype: str):
        """Switch the network dtype policy in place (DL4J
        ``convertDataType``): params/state/updater state are cast to the
        new storage dtype (fp32 masters under a 16-bit compute policy)
        and every compiled trace is invalidated — the old traces baked
        the previous policy in and would silently serve it."""
        _dt.resolve(dtype)  # validate the name before mutating anything
        self.conf = self._replace_conf_dtype(dtype)
        pdt = _dt.param_dtype(dtype)
        self.params = _dt.cast_floating(self.params, pdt)
        self.state = _dt.cast_floating(self.state, pdt)
        if self.updater_state:
            self.updater_state = _dt.cast_floating(self.updater_state, pdt)
        self._invalidate_compiled(cause="dtype_policy")
        return self

    def set_workspace_mode(self, mode: str):
        """Switch the activation-checkpoint policy in place (DL4J
        ``setCacheMode``/workspace-mode role; see ``nn/memory.py``):
        ``none`` | ``full`` | ``dots_saveable`` | ``every_<k>``. The remat
        policy is baked into the compiled train/epoch programs at trace
        time, so every cached trace is invalidated — mutating the policy
        RETRACES instead of silently serving the old executable. (A
        ``ParallelWrapper`` built before the mutation holds its own step;
        rebuild it the same way as after ``set_dtype``.)"""
        from . import memory as _memory
        policy = _memory.resolve_policy(mode)  # validate before mutating
        self.conf = self._replace_conf_workspace_mode(policy.name)
        self._invalidate_compiled(cause="workspace_mode")
        return self

    def _replace_conf_workspace_mode(self, mode: str):
        # same copy-on-write contract as _replace_conf_dtype; both engines'
        # confs carry a plain `workspace_mode` str field
        import copy
        import dataclasses
        conf = self.conf
        if dataclasses.is_dataclass(conf):
            return dataclasses.replace(conf, workspace_mode=mode)
        conf = copy.copy(conf)
        conf.workspace_mode = mode
        return conf

    def memory_report(self, batch_size: int, accum_steps: int = 1,
                      seq_len=None) -> dict:
        """Compiled-HBM accounting for THIS model's train step at
        ``batch_size`` — AOT lower+compile (nothing executes) exposing
        XLA's ``memory_analysis()`` temp/argument/output bytes, the
        forward→backward ``activation_bytes`` the workspace_mode remat
        shrinks, and live ``device.memory_stats()``. See
        ``nn.memory.memory_report``."""
        from . import memory as _memory
        return _memory.memory_report(self, batch_size,
                                     accum_steps=accum_steps,
                                     seq_len=seq_len)

    def max_batch(self, bytes_limit=None, **kwargs):
        """Largest power-of-two batch whose train step fits in
        ``bytes_limit`` HBM (defaults to the device's live
        ``bytes_limit``), found by AOT lower+compile — no OOM probing.
        See ``nn.memory.max_batch``."""
        from . import memory as _memory
        return _memory.max_batch(self, bytes_limit, **kwargs)

    def attribution_report(self, batch_size: int, steps: int = 3,
                           accum_steps: int = 1, seq_len=None,
                           peaks=None, measured_s=None) -> dict:
        """``memory_report``'s roofline sibling (ISSUE 13): decompose
        this model's train-step time at ``batch_size`` into compute-
        bound / memory-bound / host-bound / unattributed seconds with an
        ``mfu_gap`` breakdown, from the AOT executable's
        ``cost_analysis()`` + a synced measurement (or a caller-supplied
        ``measured_s``). Reports are keyed and cached process-wide so a
        schedule tuner can rank remat/overlap/batch configs without
        re-measuring. See ``runtime.attribution.attribution_report``."""
        from ..runtime import attribution as _attr
        return _attr.attribution_report(
            self, batch_size, steps=steps, accum_steps=accum_steps,
            seq_len=seq_len, peaks=peaks, measured_s=measured_s)

    def tune_schedule(self, batch_size: int, apply: bool = True,
                      force: bool = False, **kwargs) -> dict:
        """Joint schedule search over THIS model's real train step
        (ISSUE 14, ``runtime/schedule.py``): workspace-mode remat policy
        x accum_steps x batch size, pruned by the AOT
        ``memory_report``/``max_batch`` oracle (never OOM-probes), seeded
        from cached ``attribution_report`` fractions, timed as real
        compiled steps (TPU only — CPU seeds a default entry unless
        ``force=True``), winner cached per (model-fingerprint, topology,
        dtype-policy) with JSON disk persistence
        (``DL4J_TPU_SCHEDULE_CACHE``). ``apply=True`` applies the winning
        ``workspace_mode`` through :meth:`set_workspace_mode` — one
        attributed retrace at the next build, zero steady-state compiles
        after; the winning batch size is a recommendation in the returned
        entry. ``DL4J_TPU_SCHEDULE_TUNE=off`` pins to cache/defaults."""
        from ..runtime import schedule as _sched
        return _sched.tune_schedule(self, batch_size, apply=apply,
                                    force=force, **kwargs)

    def audit_compiled(self, batch_size: int, accum_steps: int = 1,
                       seq_len=None, rules=None):
        """Tier B compiled-program audit (ISSUE 15,
        ``runtime/staticcheck.py``): trace/lower THIS model's REAL fused
        train step at ``batch_size`` (nothing executes) and check the
        program-shape invariants the r12/r18 reviews enforced by hand —
        no param-shaped 16-bit cast inside scan bodies, no host
        callbacks, donation actually applied in the lowered program, and
        no f32 matmuls under a 16-bit compute policy. Returns a list of
        ``staticcheck.Finding`` — empty means the compiled program is
        clean; tests and the bench assert ``audit_compiled(...) == []``
        instead of copy-pasting jaxpr greps."""
        from ..runtime import staticcheck as _sc
        return _sc.audit_model(self, batch_size, accum_steps=accum_steps,
                               seq_len=seq_len, rules=rules)

    def inference_engine(self, **kwargs):
        """The model's serving engine (``serving.engine.InferenceEngine``),
        created lazily; ``output()`` routes through it. Pass kwargs (e.g.
        ``mesh=``) on the first call to configure it."""
        if self._inference_engine is None:
            from ..serving.engine import InferenceEngine
            self._inference_engine = InferenceEngine(self, **kwargs)
        elif kwargs:
            raise ValueError("inference engine already built; call "
                             "inference_engine() without kwargs, or build "
                             "an InferenceEngine directly")
        return self._inference_engine

    # ---------------------------------------------------- phase tracing
    # The fit loops' phases are telemetry spans in the ``train.phase.*``
    # family, shared by both engines, ``ParallelWrapper`` and ``SameDiff``
    # so the semantics cannot drift: one ``call_s`` per public call (the
    # root of its trace id), and under it ``data_wait_s`` (each ``next()``
    # of the iterator), ``stage_s`` (host cast, reshape and placement of
    # the data), ``prepare_s`` (the rest of the host's work before a
    # dispatch), ``step_s`` (the enqueue), ``readback_s`` (the host waits
    # for a device value) and ``listeners_s``. A span observes the
    # histogram of its own name and leaves an event with both ends on the
    # wall clock in ``telemetry.flight``; disabled telemetry skips every
    # clock. No span sits inside a jitted function. Their labels are the
    # mixin's ``_phase_labels()``.

    @staticmethod
    def _timed_batches(it, labels):
        """Yield the batches of ``it``, each ``next()`` inside a
        ``train.phase.data_wait_s`` span."""
        src = iter(it)
        while True:
            wait = _tel.span("train.phase.data_wait_s", labels)
            with wait:
                ds = next(src, _DONE)
                if ds is _DONE:
                    wait.cancel()
                    return
            yield ds

    def _notify_listeners(self, labels, event: str, *args):
        """Call ``event`` (``iteration_done`` / ``on_epoch_end``) on every
        attached listener inside one ``train.phase.listeners_s`` span; no
        span where none is attached."""
        if self._listeners:
            with _tel.span("train.phase.listeners_s", labels):
                for cb in self._listeners:
                    getattr(cb, event)(self, *args)

    def _timed_dispatch(self, labels):
        """Context manager for ONE train-step dispatch: the ``step_s``
        span + step annotation (see ``_TimedDispatch``)."""
        return _TimedDispatch(labels, self.iteration)

    def _program_labels(self) -> dict:
        """The labels of this model's programs in the scope registry
        (``telemetry.record_dispatch``): ``vertices`` are the names its
        forward walk opens a ``jax.named_scope`` for, a vertex or layer
        each (``_scope_names``)."""
        return {"vertices": list(self._scope_names())}
