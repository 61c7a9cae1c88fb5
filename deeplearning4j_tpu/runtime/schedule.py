"""Joint schedule tuner over the REAL train step (ISSUE 14 tentpole).

ResNet-50 sits at 33.4% MFU against the >=35% north-star bar and the
knobs that move it — the workspace-mode remat policy, the ZeRO-1 overlap
bucket size, gradient-accumulation steps, and batch size — interact: the
overlap bucket that wins under ``dots_saveable`` is not the one that wins
under ``every_2``, and the biggest batch the oracle admits depends on
both. Tuning them per knob by hand (the r5 batch fine-sweep, the r12
default bucket) leaves the joint optimum on the table. This module is the
TVM-style answer (PAPERS.md 1802.04799) already proven for the flash
kernel's block shapes (``ops/autotune.py``), lifted from one kernel to
the WHOLE compiled train step:

- **Search space**: ``workspace_mode`` (``none``/``dots_saveable``/
  ``every_<k>``) x ``accum_steps`` x batch size x (ParallelWrapper only)
  ``overlap_bucket_mb`` — every candidate is the real fused step the fit
  loop would run, remat/sentinel/clip/sharding and all.
- **Oracle pruning (never OOM-probe)**: every (policy, accum, batch)
  combination is AOT lower+compiled first (``nn/memory.py`` — nothing
  executes, nothing allocates) and its ``memory_analysis`` peak checked
  against the device ``bytes_limit`` (or an explicit budget). Candidates
  that would not fit are pruned BEFORE any step runs, so the sweep cannot
  OOM the way execution-probing sweeps do.
- **Attribution seeding**: the search order comes from the r17
  ``attribution_report`` compute/memory/host fractions cached for the
  incumbent config (``runtime/attribution.py`` — built and cached for
  exactly this consumer): a memory-bound step tries coarser remat first,
  a host-bound step tries bigger batches first, instead of walking the
  brute-force product order. With a ``max_candidates`` budget the
  ordering decides what gets measured at all.
- **Measurement**: surviving candidates run as REAL compiled steps on
  synthetic zero batches timed to ``block_until_ready``, min over repeats,
  rounds interleaved across candidates so drift hits every
  candidate alike — the ``ops/autotune.py`` timing discipline. Every
  probe lower+compile is reported to the retrace tracker as
  ``record_compile(..., cause="schedule_tune")`` so warm steady state
  keeps its zero-compile assertion.
- **Cache**: winners are cached per ``(model-fingerprint, topology,
  dtype-policy)`` for the process lifetime, with the same JSON disk
  persistence (``DL4J_TPU_SCHEDULE_CACHE``, tmp+rename via
  ``ops.autotune.atomic_json_save``) and upgrade-never-pin merge rules as
  the flash cache: a ``source="default"`` seed is re-swept when a real
  sweep becomes possible; a swept disk entry beats an in-process default
  and never the other way around.

CPU/tier-1 contract (mirrors ``DL4J_TPU_AUTOTUNE``): sweeps run on TPU
only — a CPU timing of the step would tune for the CPU — unless the
caller passes ``force=True`` (tests / the CPU bench exercising the
machinery). ``DL4J_TPU_SCHEDULE_TUNE=off`` pins the tuner to cache hits
and default seeds, with zero probe compiles, even under ``force``.

Wiring: ``model.tune_schedule(batch)`` (MultiLayerNetwork /
ComputationGraph via ``nn/caches.py``) and
``ParallelWrapper.tune_schedule(batch)`` search, cache, and APPLY the
winner through the existing seams (``set_workspace_mode`` /
``set_overlap`` / ``set_accum_steps``) — one attributed retrace at the
next build, zero steady-state compiles after. The winning ``batch_size``
is a recommendation returned in the entry (the data pipeline owns the
actual batch; the tuner cannot re-batch an iterator). Applying only the
schedule knobs keeps the bit-equality contract: remat and overlap are
value-identical program restructurings (tested r9/r12), so a tuned model
trains bit-identically to the default one on the same batches.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import attribution as _attr
from . import telemetry as _tel

#: default remat-policy candidate set (the ISSUE 14 axis); every_2 stands
#: in for the every_<k> family — callers widen via ``policies=``
DEFAULT_POLICIES = ("none", "dots_saveable", "every_2")
DEFAULT_REPEATS = 3

_EVENTS = _tel.counter(
    "schedule.events",
    "joint schedule tuner events (hit / default / sweep / candidate / "
    "pruned)")
_RATIO_GAUGE = _tel.gauge(
    "schedule.tuned_ratio",
    "winner step time / incumbent-config step time of the last sweep "
    "(<= 1.0 by construction: the incumbent is always timed)")

_lock = threading.RLock()
_cache: Dict[tuple, dict] = {}
_env_cache_loaded = False
_state = {"mode": None}


def mode() -> str:
    """"auto" (sweep on TPU, or anywhere under ``force=True``) or "off"
    (cache hits and default seeds only — zero probe compiles). The
    ``DL4J_TPU_SCHEDULE_TUNE`` env var is read per call so an operator
    pin applies without a process restart; ``set_mode`` overrides it."""
    if _state["mode"] is not None:
        return _state["mode"]
    return os.environ.get("DL4J_TPU_SCHEDULE_TUNE", "auto") or "auto"


def set_mode(m: Optional[str]) -> Optional[str]:
    """Override the tuner mode ("auto"/"off"; None = defer to the env
    var). Returns the previous override."""
    if m is not None and m not in ("auto", "off"):
        raise ValueError(f"schedule tune mode {m!r} not in ('auto', 'off')")
    old = _state["mode"]
    _state["mode"] = m
    return old


def counters() -> dict:
    return {k: int(_EVENTS.value(event=k))
            for k in ("hit", "default", "sweep", "candidate", "pruned")}


def reset_counters() -> None:
    _EVENTS.zero()


# ------------------------------------------------------------------ keys
def _is_wrapper(target) -> bool:
    return hasattr(target, "mesh") and hasattr(target, "model")


def _model_of(target):
    return target.model if _is_wrapper(target) else target


def topology(target=None) -> str:
    """Backend + device kind + device count (+ mesh shape / shard_update
    for a ParallelWrapper) — the schedule that wins on one topology says
    nothing about another."""
    import jax
    devs = jax.devices()
    kind = str(getattr(devs[0], "device_kind", "")).replace(" ", "_") \
        or jax.default_backend()
    t = f"{jax.default_backend()}:{kind}:{len(devs)}"
    if target is not None and _is_wrapper(target):
        shape = "x".join(str(s) for s in target.mesh.devices.shape)
        t += (f":mesh{shape}:su{int(target.shard_update)}"
              f":ma{target.model_axis or '-'}")
    return t


def cache_key(target) -> tuple:
    """(model-fingerprint, topology, dtype-policy) — the unit a schedule
    winner transfers across: same program shape, same hardware, same
    precision policy."""
    m = _model_of(target)
    dtype = str(getattr(m.conf, "dtype", "FLOAT"))
    return (_attr.model_fingerprint(m), topology(target), dtype)


# ----------------------------------------------------------------- cache
def _cache_path() -> Optional[str]:
    return os.environ.get("DL4J_TPU_SCHEDULE_CACHE", "") or None


def _ensure_loaded() -> None:
    global _env_cache_loaded
    if _env_cache_loaded:
        return
    _env_cache_loaded = True
    p = _cache_path()
    if p and os.path.exists(p):
        try:
            load(p)
        except (OSError, ValueError, KeyError):
            pass  # a corrupt cache file must never block training


def _valid_entry(e) -> bool:
    """An entry must carry a resolvable config for ITS key — a stale or
    hand-edited disk cache must never apply garbage to a live model."""
    from ..nn import memory as _memory
    if not isinstance(e, dict):
        return False
    cfg = e.get("config")
    if not isinstance(cfg, dict):
        return False
    try:
        _memory.resolve_policy(cfg.get("workspace_mode"))
        if int(cfg.get("accum_steps", 1)) < 1:
            return False
        # batch_size REQUIRED: apply/_normalize_config read it —
        # an entry without it must never reach the cache
        if int(cfg["batch_size"]) < 1:
            return False
        mb = cfg.get("overlap_bucket_mb")
        if mb is not None and float(mb) <= 0:
            return False
    except (ValueError, TypeError, KeyError):
        return False
    return e.get("source") in ("sweep", "default")


def lookup(target) -> Optional[dict]:
    """The cache entry for a target's key, or None (no counter bump)."""
    with _lock:
        _ensure_loaded()
        e = _cache.get(cache_key(target))
        return dict(e) if e else None


def reset() -> None:
    """Drop the in-process cache (disk files untouched)."""
    global _env_cache_loaded
    with _lock:
        _cache.clear()
        _env_cache_loaded = True  # a reset cache stays reset (tests)


def cache_snapshot() -> dict:
    import jax
    with _lock:
        entries = [{"key": list(k), **v} for k, v in sorted(_cache.items())]
    return {"version": 1, "backend": jax.default_backend(),
            "entries": entries}


def save(path: Optional[str] = None) -> Optional[str]:
    """Persist the cache as JSON (tmp+rename — shared
    ``ops.autotune.atomic_json_save`` discipline). Returns the path, or
    None when no path is configured."""
    from ..ops.autotune import atomic_json_save
    path = path or _cache_path()
    if not path:
        return None
    return atomic_json_save(path, cache_snapshot())


def load(path: Optional[str] = None, merge: bool = True) -> int:
    """Load a JSON cache file; ``merge=False`` replaces the in-process
    cache. Merge rules mirror the flash cache: swept disk entries beat
    in-process default seeds; an in-process sweep is never downgraded by
    a disk default. Invalid entries are dropped, never served. Returns
    the entry count loaded."""
    path = path or _cache_path()
    if not path:
        return 0
    with open(path) as f:
        snap = json.load(f)
    n = 0
    with _lock:
        if not merge:
            _cache.clear()
        entries = snap.get("entries", []) if isinstance(snap, dict) else []
        for ent in entries:
            if not isinstance(ent, dict):
                continue  # corrupt/hand-edited entry: never served
            raw = ent.get("key")
            if not isinstance(raw, (list, tuple)) or len(raw) != 3:
                continue
            key = tuple(str(x) for x in raw)
            body = {k: v for k, v in ent.items() if k != "key"}
            if not _valid_entry(body):
                continue
            cur = _cache.get(key)
            if cur is not None and cur.get("source") != "default" \
                    and body.get("source") == "default":
                continue  # upgrade-never-pin: defaults never demote sweeps
            _cache[key] = body
            n += 1
    return n


# ------------------------------------------------------------ candidates
def _normalize_config(cfg: dict) -> dict:
    return {
        "workspace_mode": str(cfg.get("workspace_mode", "none") or "none"),
        "accum_steps": int(cfg.get("accum_steps", 1)),
        "batch_size": int(cfg["batch_size"]),
        "overlap": (None if cfg.get("overlap") is None
                    else bool(cfg["overlap"])),
        "overlap_bucket_mb": (None if cfg.get("overlap_bucket_mb") is None
                              else float(cfg["overlap_bucket_mb"])),
    }


def _config_tag(cfg: dict) -> str:
    tag = (f"{cfg['workspace_mode']}/acc{cfg['accum_steps']}"
           f"/b{cfg['batch_size']}")
    if cfg.get("overlap"):
        tag += f"/ov{cfg['overlap_bucket_mb']:g}mb"
    return tag


def incumbent_config(target, batch_size: int) -> dict:
    """The configuration the target would train with TODAY — always a
    candidate (its timing is the tuned-vs-default baseline, so the
    winner's ratio is <= 1.0 by construction) and never pruned."""
    m = _model_of(target)
    cfg = {"workspace_mode": getattr(m.conf, "workspace_mode", "none"),
           "accum_steps": 1, "batch_size": int(batch_size),
           "overlap": None, "overlap_bucket_mb": None}
    if _is_wrapper(target):
        cfg["accum_steps"] = int(target.accum_steps)
        cfg["overlap"] = bool(target.overlap_grads)
        cfg["overlap_bucket_mb"] = target.overlap_bucket_bytes / (1 << 20)
    return _normalize_config(cfg)


@contextlib.contextmanager
def _with_schedule(target, cfg: dict):
    """Temporarily point the target at a candidate schedule (conf
    workspace_mode on the model; accum/overlap/bucket on a wrapper) for
    the duration of one build+lower+trace — the model's own compiled
    caches are never touched (``_build_train_step``/``_build`` return
    fresh programs), so no invalidation and no retrace of the live step
    happens here."""
    m = _model_of(target)
    conf0 = m.conf
    m.conf = m._replace_conf(
        workspace_mode=_memory_policy_name(cfg["workspace_mode"]))
    saved = None
    if _is_wrapper(target):
        saved = (target.accum_steps, target.overlap_grads,
                 target.overlap_bucket_bytes)
        target.accum_steps = int(cfg["accum_steps"])
        if cfg["overlap"] is not None:
            target.overlap_grads = bool(cfg["overlap"])
        if cfg["overlap_bucket_mb"]:
            target.overlap_bucket_bytes = int(
                cfg["overlap_bucket_mb"] * (1 << 20))
    try:
        yield m
    finally:
        m.conf = conf0
        if saved is not None:
            (target.accum_steps, target.overlap_grads,
             target.overlap_bucket_bytes) = saved


def _memory_policy_name(mode) -> str:
    from ..nn import memory as _memory
    return _memory.resolve_policy(mode).name


def _remat_coarseness(policy: str) -> int:
    """How aggressively a policy sheds activations (ordering heuristic
    for the memory-bound seed): none < dots_saveable < every_<k, small
    first> < full."""
    if policy == "none":
        return 0
    if policy == "dots_saveable":
        return 1
    if policy.startswith("every_"):
        tail = policy[len("every_"):]
        return 1 + (int(tail) if tail.isdigit() else 1)
    return 1000  # full: checkpoint every block


class ScheduleTuner:
    """One joint search over a model's (or ParallelWrapper's) schedule
    space. Most callers want :func:`tune_schedule`, which adds the cache,
    mode gating, and apply step around ``search()``."""

    def __init__(self, target, batch_size: int, *,
                 bytes_limit: Optional[int] = None,
                 policies: Sequence[str] = DEFAULT_POLICIES,
                 accum_candidates: Sequence[int] = (1, 2),
                 batch_candidates: Optional[Sequence[int]] = None,
                 bucket_candidates: Optional[Sequence[float]] = None,
                 repeats: int = DEFAULT_REPEATS,
                 seq_len: Optional[int] = None,
                 max_candidates: Optional[int] = None):
        self.target = target
        self.model = _model_of(target)
        if not self.model.params and not self.model.state:
            self.model.init()
        self.batch_size = int(batch_size)
        self.seq_len = seq_len
        self.repeats = max(1, int(repeats))
        self.max_candidates = max_candidates
        self.policies = tuple(_memory_policy_name(p) for p in policies)
        self.accum_candidates = tuple(int(a) for a in accum_candidates)
        self.batch_candidates = tuple(
            int(b) for b in (batch_candidates or
                             (self.batch_size, 2 * self.batch_size)))
        self.bucket_candidates = bucket_candidates
        self.bytes_limit = bytes_limit
        if bytes_limit is None:
            from ..nn import memory as _memory
            dm = _memory.device_memory_stats()
            if dm and dm.get("bytes_limit"):
                self.bytes_limit = int(dm["bytes_limit"])
        self.incumbent = incumbent_config(target, self.batch_size)
        self.pruned: List[dict] = []
        self.seed_order = "default"
        # AOT executables from the oracle pass, reused for plain-model
        # timing so each surviving candidate compiles exactly once
        self._compiled: Dict[str, object] = {}

    # -------------------------------------------------------- enumeration
    def raw_candidates(self) -> List[dict]:
        """The joint product (deduped, incumbent guaranteed present and
        first). Wrapper batch candidates that don't divide the pad
        granularity are dropped here (they could never run unpadded)."""
        out, seen = [], set()

        def add(cfg):
            cfg = _normalize_config(cfg)
            tag = _config_tag(cfg)
            if tag not in seen:
                seen.add(tag)
                out.append(cfg)

        add(self.incumbent)
        wrapper = _is_wrapper(self.target)
        buckets: Sequence[Optional[float]] = (None,)
        if wrapper and self.incumbent["overlap"]:
            buckets = tuple(self.bucket_candidates or
                            (self.incumbent["overlap_bucket_mb"],))
        for p in self.policies:
            for a in self.accum_candidates:
                for b in self.batch_candidates:
                    if b % max(1, a):
                        continue
                    if wrapper:
                        gran = self.target._pad_granularity() \
                            // max(1, self.target.accum_steps) * a
                        if b % max(1, gran):
                            continue
                    for mb in buckets:
                        add({"workspace_mode": p, "accum_steps": a,
                             "batch_size": b,
                             "overlap": self.incumbent["overlap"],
                             "overlap_bucket_mb": mb
                             if mb is not None
                             else self.incumbent["overlap_bucket_mb"]})
        return out

    # ----------------------------------------------------------- seeding
    def _seed_fractions(self) -> Optional[dict]:
        """The incumbent config's cached attribution fractions (r17 built
        and cached them for exactly this read). NEVER computes — a cache
        miss means default ordering, not a measurement."""
        schedule = None
        if _is_wrapper(self.target):
            schedule = self.target._schedule_key_suffix()
        key = _attr.train_step_key(
            self.model, self.batch_size,
            self.incumbent["accum_steps"], self.seq_len, schedule=schedule)
        rep = _attr.cached_report(key)
        if rep and rep.get("fractions"):
            return rep["fractions"]
        return None

    def ordered_candidates(self) -> List[dict]:
        """Candidates in search order: attribution-seeded (memory-bound →
        coarser remat first, host-bound → bigger batch first), truncated
        to ``max_candidates``; the incumbent is always kept and always
        first (it is the ratio denominator)."""
        cands = self.raw_candidates()
        fr = self._seed_fractions()
        rest = [c for c in cands if _config_tag(c) !=
                _config_tag(self.incumbent)]
        if fr:
            mem, host = fr.get("memory", 0.0), fr.get("host", 0.0)
            comp = fr.get("compute", 0.0)
            if mem >= max(host, comp):
                self.seed_order = "memory"
                # coarser remat first: a memory-bound step wants fewer
                # live activations before anything else
                rest.sort(key=lambda c: (-_remat_coarseness(
                    c["workspace_mode"]), c["batch_size"]))
            elif host >= comp:
                self.seed_order = "host"
                rest.sort(key=lambda c: (-c["batch_size"],
                                         -c["accum_steps"]))
        ordered = [self.incumbent] + rest
        if self.max_candidates:
            ordered = ordered[:max(1, int(self.max_candidates))]
        return ordered

    # ------------------------------------------------------------ oracle
    def _oracle_peak(self, cfg: dict):
        """AOT lower+compile one (policy, accum, batch) combination and
        return (peak_bytes_or_None, compiled_or_None). Nothing executes —
        the 'never OOM-probe' half of the contract. The compile is
        reported to the retrace tracker before it runs."""
        from ..nn import memory as _memory
        _tel.record_compile("schedule.tune", "schedule_tune",
                            config=_config_tag(cfg), stage="oracle")
        with _with_schedule(self.target, cfg):
            if _is_wrapper(self.target):
                step_fn, _ = self.target._build()
                compiled = self.target._lower_step(
                    cfg["batch_size"], self.seq_len, step_fn=step_fn,
                    cause=None)  # already attributed schedule_tune above
            else:
                # cause=None: the oracle already attributed this compile
                # as schedule_tune above — don't double-count it as probe
                compiled = _memory._lower_train_step(
                    self.model, cfg["batch_size"], cfg["accum_steps"],
                    self.seq_len, cause=None)
        cm = _memory.compiled_memory(compiled)
        return (cm.get("peak_bytes") if cm else None), compiled

    def prune(self, cands: List[dict]) -> List[dict]:
        """Oracle pass: drop every candidate whose AOT peak exceeds the
        bytes limit (or whose peak is UNKNOWN while it grows the batch —
        'unknown' must never become 'let's try it and see'). The
        incumbent is exempt: it is the config already running."""
        survivors = []
        inc_tag = _config_tag(self.incumbent)
        for cfg in cands:
            tag = _config_tag(cfg)
            if tag == inc_tag:
                peak, compiled = self._oracle_peak(cfg)
                self._compiled[tag] = compiled
                survivors.append(cfg)
                continue
            peak, compiled = self._oracle_peak(cfg)
            if self.bytes_limit is not None:
                if peak is None and \
                        cfg["batch_size"] > self.incumbent["batch_size"]:
                    self.pruned.append({"config": dict(cfg),
                                        "peak_bytes": None,
                                        "reason": "unknown_peak"})
                    _EVENTS.inc(event="pruned")
                    continue
                if peak is not None and peak > self.bytes_limit:
                    self.pruned.append({"config": dict(cfg),
                                        "peak_bytes": int(peak),
                                        "reason": "over_limit"})
                    _EVENTS.inc(event="pruned")
                    continue
            self._compiled[tag] = compiled
            survivors.append(cfg)
        return survivors

    # ------------------------------------------------------------ timing
    def _runner(self, cfg: dict):
        """A zero-arg callable running ONE real step of this candidate
        to ``block_until_ready``. Fresh donated argument copies are
        built per call OUTSIDE the timed region (the step donates
        params/opt/state)."""
        import jax
        tag = _config_tag(cfg)
        compiled = self._compiled[tag]  # the oracle pass's AOT program —
        #                                 one compile per candidate, total
        if _is_wrapper(self.target):
            # _build() here only CONSTRUCTS the jit + placement closures
            # (no trace, no compile — execution goes through the AOT
            # executable below)
            with _with_schedule(self.target, cfg):
                _, shard_args = self.target._build()
            counter = {"i": 0}

            def make_args():
                counter["i"] += 1
                (params, opt, state, stepi, key, xs, ys, fm, lm,
                 sent) = _attr._train_step_args(
                    self.model, cfg["batch_size"], cfg["accum_steps"],
                    self.seq_len, counter["i"])
                xs, ys = self.target._host_share((xs, ys),
                                                 cfg["batch_size"])
                return shard_args(params, opt, state, sent, stepi, key,
                                  xs, ys, fm, lm)
        else:
            counter = {"i": 0}

            def make_args():
                counter["i"] += 1
                return _attr._train_step_args(
                    self.model, cfg["batch_size"], cfg["accum_steps"],
                    self.seq_len, counter["i"])

        def run(args):
            jax.block_until_ready(compiled(*args))
        return make_args, run

    def time_candidates(self, cands: List[dict]) -> List[dict]:
        """min-over-repeats seconds per candidate, rounds interleaved
        across candidates (drift hits all alike — the autotune/bench
        discipline)."""
        runners = {}
        for cfg in cands:
            tag = _config_tag(cfg)
            make_args, run = self._runner(cfg)
            run(make_args())  # settle (compiles were paid by the oracle)
            runners[tag] = (cfg, make_args, run)
            _EVENTS.inc(event="candidate")
        best = {tag: float("inf") for tag in runners}
        for _ in range(self.repeats):
            for tag, (cfg, make_args, run) in runners.items():
                args = make_args()  # arg prep outside the timed region
                t0 = time.perf_counter()
                run(args)
                best[tag] = min(best[tag], time.perf_counter() - t0)
        return [{"config": dict(cfg), "us": round(best[tag] * 1e6, 2)}
                for tag, (cfg, _m, _r) in runners.items()]

    # ------------------------------------------------------------ search
    def search(self) -> Optional[dict]:
        """prune → seed-order → time → winner entry (not cached here —
        :func:`tune_schedule` owns the cache)."""
        import jax
        ordered = self.ordered_candidates()
        survivors = self.prune(ordered)
        if not survivors:
            return None
        timings = self.time_candidates(survivors)
        by_tag = {_config_tag(t["config"]): t for t in timings}
        default_us = by_tag[_config_tag(self.incumbent)]["us"]
        winner = min(timings, key=lambda t: t["us"])
        ratio = winner["us"] / default_us if default_us else None
        if ratio is not None:
            _RATIO_GAUGE.set(ratio)
        _EVENTS.inc(event="sweep")
        return {
            "config": _normalize_config(winner["config"]),
            "source": "sweep",
            "us": winner["us"],
            "default_config": dict(self.incumbent),
            "default_us": default_us,
            "ratio_vs_default": round(ratio, 4) if ratio else None,
            "seed_order": self.seed_order,
            "candidates": timings,
            "pruned": list(self.pruned),
            "oracle": ("memory_analysis" if self.bytes_limit is not None
                       else "no_bytes_limit"),
            "bytes_limit": self.bytes_limit,
            "backend": jax.default_backend(),
        }


# -------------------------------------------------------------- frontend
def apply_entry(target, entry: dict) -> List[str]:
    """Apply a cache entry's winning config through the existing seams —
    ``set_workspace_mode`` on the model, ``set_overlap`` /
    ``set_accum_steps`` on a wrapper. Returns the list of knobs changed
    (each change arms ONE attributed retrace at the next build; an
    already-matching config changes nothing and retraces nothing).
    ``batch_size`` is NOT applied — the data pipeline owns it; adopt the
    recommendation by feeding that batch size."""
    cfg = _normalize_config(entry["config"])
    m = _model_of(target)
    changed = []
    current = _memory_policy_name(getattr(m.conf, "workspace_mode", "none"))
    if _memory_policy_name(cfg["workspace_mode"]) != current:
        m.set_workspace_mode(cfg["workspace_mode"])
        changed.append("workspace_mode")
        if _is_wrapper(target) and target._step is not None:
            # the wrapper's step baked the old policy in too
            target._step = None
            target._pending_step_cause = "workspace_mode"
    if _is_wrapper(target):
        if cfg["accum_steps"] != target.accum_steps:
            target.set_accum_steps(cfg["accum_steps"])
            changed.append("accum_steps")
        if cfg["overlap"] is not None and target.shard_update and (
                bool(cfg["overlap"]) != target.overlap_grads or
                (cfg["overlap"] and cfg["overlap_bucket_mb"] and
                 int(cfg["overlap_bucket_mb"] * (1 << 20)) !=
                 target.overlap_bucket_bytes)):
            target.set_overlap(bool(cfg["overlap"]),
                               bucket_mb=cfg["overlap_bucket_mb"])
            changed.append("overlap")
    return changed


def tune_schedule(target, batch_size: int, *, apply: bool = True,
                  force: bool = False, **kwargs) -> dict:
    """Joint schedule search for a model or ParallelWrapper (see the
    module docstring). Returns the cache entry; ``apply=True`` (default)
    applies the winner's schedule knobs through the existing seams.

    Sweeps run only on TPU in mode "auto" — CPU/tier-1 runs NEVER sweep
    (they seed a ``source="default"`` incumbent entry, upgraded by the
    first real sweep) — unless ``force=True`` explicitly opts a test or
    the CPU bench into timing. ``DL4J_TPU_SCHEDULE_TUNE=off`` wins over
    everything: cache hits and default seeds only, zero probe compiles."""
    import jax
    m = _model_of(target)
    if not m.params and not m.state:
        m.init()
    key = cache_key(target)
    md = mode()
    can_sweep = md == "auto" and (force or jax.default_backend() == "tpu")
    with _lock:
        _ensure_loaded()
        e = _cache.get(key)
        if e is not None and not _valid_entry(e):
            del _cache[key]
            e = None
        if e is not None and not (can_sweep and e.get("source") != "sweep"):
            _EVENTS.inc(event="hit")
            entry = dict(e)
            if apply:
                apply_entry(target, entry)
            return entry
    if can_sweep:
        entry = ScheduleTuner(target, batch_size, **kwargs).search()
    else:
        entry = None
    if entry is None:  # no sweep possible/allowed: seed the incumbent
        entry = {"config": incumbent_config(target, batch_size),
                 "source": "default",
                 "us": None, "default_us": None,
                 "ratio_vs_default": None,
                 "backend": jax.default_backend()}
        _EVENTS.inc(event="default")
    entry["key"] = list(key)
    with _lock:
        _cache[key] = {k: v for k, v in entry.items() if k != "key"}
    if md == "auto" and _cache_path():
        try:
            save()
        except OSError:
            pass  # persistence is best-effort; the process cache holds
    if apply:
        apply_entry(target, entry)
    return entry


# ------------------------------------------------------------ CI dry-run
def _dry_run(cache_path: Optional[str] = None) -> dict:
    """Makefile ``tune`` target: CPU dry-run on a toy model proving the
    cache machinery end to end — seed a default entry (CPU never
    sweeps), assert the cache FILE was written, drop the in-process
    cache, re-load from disk, and assert the second lookup is a HIT.
    Raises on any failed invariant (make exits non-zero)."""
    if cache_path:
        os.environ["DL4J_TPU_SCHEDULE_CACHE"] = cache_path
    path = _cache_path()
    if not path:
        raise SystemExit("set DL4J_TPU_SCHEDULE_CACHE (or pass --cache)")
    from ..nn.config import InputType, NeuralNetConfiguration
    from ..nn.layers.core import DenseLayer, OutputLayer
    from ..nn.model import MultiLayerNetwork
    from ..nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Adam(learning_rate=1e-3))
            .input_type(InputType.feed_forward(8))
            .list(DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=4)).build())
    net = MultiLayerNetwork(conf).init()
    reset()
    e1 = tune_schedule(net, 8, apply=False)
    assert e1["source"] in ("default", "sweep"), e1
    assert os.path.exists(path), f"cache file not written: {path}"
    reset()
    n = load(path)
    assert n >= 1, f"cache file re-load found no entries: {path}"
    before = counters()["hit"]
    e2 = tune_schedule(net, 8, apply=False)
    assert counters()["hit"] == before + 1, "re-load did not produce a hit"
    assert e2["config"] == e1["config"], (e1, e2)
    return {"cache_path": path, "entries": n, "entry": e2,
            "counters": counters()}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=_dry_run.__doc__)
    ap.add_argument("--cache", default=None,
                    help="cache file path (default: $DL4J_TPU_SCHEDULE_CACHE)")
    out = _dry_run(ap.parse_args().cache)
    print(json.dumps(out, indent=1, default=str))
