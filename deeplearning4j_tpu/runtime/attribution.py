"""MFU-attribution profiler (ISSUE 13 tentpole, subsystem 2).

"Where did the missing MFU go?" is unanswerable from one opaque step-time
histogram: ResNet-50 sits at 33.4% vs the >=35% bar (ROADMAP item 4) and
nothing says whether the gap is memory-bound kernels, host overhead, or
hardware contention. TVM's thesis (PAPERS.md 1802.04799) is that a
schedule tuner needs cost-model-grounded attribution as its *input*; this
module produces exactly that, for every warmed XLA program in the stack:

- **cost model**: the AOT executable's own ``cost_analysis()`` (flops and
  bytes accessed — XLA's HloCostAnalysis, available on CPU and TPU);
- **roofline**: device peaks (explicit / env overrides / the published
  table by exact ``device_kind``; an unknown device is an error) turn
  flops and bytes into ideal compute and memory seconds;
- **measurement**: the r11/r12 phase histograms (``serving.phase.*``) or
  a synced self-measurement of the compiled program.

The decomposition is a *partition* of the measured step time ``T``::

    compute_s = min(flops / peak_flops, T)        # the MFU numerator
    memory_s  = clamp(bytes/peak_bw - compute_s)  # memory-bound excess
    host_s    = measured host-side seconds        # pad/unpad, data wait
    other_s   = T - compute_s - memory_s - host_s # unattributed
                                                  # (kernel inefficiency,
                                                  # sync, contention)

so the four fractions sum to exactly 1.0 and ``mfu == compute_fraction``
— the ``mfu_gap`` breakdown is the other three fractions. Reports are
keyed by (program kind, model, config) and cached process-wide so
ROADMAP item 4's joint schedule tuner can rank remat/overlap/batch
configurations without re-measuring (``cached_report``/``report_keys``).

Surfaces: ``model.attribution_report(batch)`` (``memory_report``'s
sibling, both engines via ``nn/caches.py``) and the serving engines'
``attribution_report(bucket)`` / ``attribution_report(cache_len)``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import telemetry as _tel

__all__ = ["device_peaks", "cost_analysis", "attribute",
           "attribute_compiled", "attribute_jitted", "attribution_report",
           "cached_report", "report_keys", "model_fingerprint",
           "train_step_key"]

#: Published per-chip peaks keyed by the exact ``device_kind`` JAX reports.
#: "TPU v5 lite" is one v5e chip: 197 TFLOP/s bf16 (394 TOP/s is its int8
#: figure), 16 GB HBM at 819 GB/s (Google Cloud documentation, "TPU v5e").
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def _env_peak(name: str) -> Optional[float]:
    raw = os.environ.get(name)
    if not raw:
        return None
    v = float(raw)
    if not v > 0:
        raise ValueError(f"{name}={raw!r} is not a positive number")
    return v


def published_peaks() -> dict:
    """The :data:`DEVICE_PEAKS` row of device 0. A device that is not in
    the table is an error: a utilisation against a guessed peak is not a
    utilisation."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise LookupError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); pass explicit peaks")
    return DEVICE_PEAKS[kind]


def device_peaks(peaks: Optional[dict] = None) -> dict:
    """``{"flops_per_s", "bytes_per_s", "source"}`` for device 0.
    Resolution order: an explicit ``peaks`` dict, the
    ``DL4J_TPU_PEAK_FLOPS`` / ``DL4J_TPU_PEAK_BW`` env overrides, then
    :data:`DEVICE_PEAKS` by exact ``device_kind`` (an unknown device
    raises, see :func:`published_peaks`)."""
    if peaks is not None and peaks.get("flops_per_s") \
            and peaks.get("bytes_per_s"):
        return {"flops_per_s": float(peaks["flops_per_s"]),
                "bytes_per_s": float(peaks["bytes_per_s"]),
                "source": peaks.get("source", "explicit")}
    flops = _env_peak("DL4J_TPU_PEAK_FLOPS")
    bw = _env_peak("DL4J_TPU_PEAK_BW")
    if flops is not None and bw is not None:
        return {"flops_per_s": flops, "bytes_per_s": bw, "source": "env"}
    row = published_peaks()
    return {"flops_per_s": flops or row["flops_per_s"],
            "bytes_per_s": bw or row["bytes_per_s"],
            "source": "table" if flops is None and bw is None else "env"}


def cost_analysis(compiled) -> Optional[dict]:
    """``{"flops", "bytes_accessed"}`` from an AOT executable's
    ``cost_analysis()`` (handles the list-of-dicts form older jaxlibs
    return). None when the PJRT build exposes nothing usable — callers
    degrade to a flagged report, never raise."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {"flops": flops, "bytes_accessed": nbytes}


def attribute(flops: float, bytes_accessed: float,
              measured_s: Optional[float], host_s: Optional[float] = None,
              peaks: Optional[dict] = None) -> dict:
    """Partition a measured step time into compute/memory/host/other
    seconds (fractions sum to exactly 1.0 — see the module docstring).
    With ``measured_s`` None the report carries only the roofline lower
    bounds, flagged ``measured: False``."""
    pk = device_peaks(peaks)
    t_compute = flops / pk["flops_per_s"] if flops else 0.0
    t_memory = bytes_accessed / pk["bytes_per_s"] if bytes_accessed else 0.0
    out = {
        "flops": flops, "bytes_accessed": bytes_accessed,
        "peak_flops_per_s": pk["flops_per_s"],
        "peak_bytes_per_s": pk["bytes_per_s"],
        "peaks_source": pk["source"],
        "arithmetic_intensity": (flops / bytes_accessed)
        if bytes_accessed else None,
        "roofline_compute_s": t_compute,
        "roofline_memory_s": t_memory,
        "roofline_bound": "compute" if t_compute >= t_memory else "memory",
        "measured": measured_s is not None,
        "measured_s": measured_s,
    }
    if measured_s is None or measured_s <= 0:
        out.update({"compute_s": None, "memory_s": None, "host_s": None,
                    "other_s": None, "fractions": None, "mfu": None,
                    "mfu_gap": None})
        return out
    T = float(measured_s)
    compute_s = min(t_compute, T)
    memory_s = min(max(0.0, t_memory - compute_s), T - compute_s)
    host_s = min(max(0.0, float(host_s or 0.0)),
                 T - compute_s - memory_s)
    other_s = max(0.0, T - compute_s - memory_s - host_s)
    fr = {"compute": compute_s / T, "memory": memory_s / T,
          "host": host_s / T, "other": other_s / T}
    out.update({
        "compute_s": compute_s, "memory_s": memory_s,
        "host_s": host_s, "other_s": other_s,
        "fractions": fr,
        # MFU == the compute fraction by construction (clamped at 1.0)
        "mfu": fr["compute"],
        "mfu_gap": {"total": 1.0 - fr["compute"],
                    "memory": fr["memory"], "host": fr["host"],
                    "other": fr["other"]},
    })
    return out


def model_fingerprint(model) -> str:
    """Short stable digest of a model's parameter TREE (class + every leaf
    path/shape/dtype). Part of every cached report/schedule key: two
    models of the same class at the same batch are different programs
    when their topologies differ, and a report keyed only on the class
    name would serve one model's cached fractions to the other (the
    ISSUE 14 stale-seed bug class)."""
    import hashlib
    from jax.tree_util import keystr, tree_flatten_with_path
    flat, _ = tree_flatten_with_path(model.params)
    leaves = sorted(
        (keystr(path), tuple(getattr(a, "shape", ())),
         str(getattr(a, "dtype", "?")))
        for path, a in flat)
    raw = repr((type(model).__name__, leaves)).encode()
    return hashlib.sha256(raw).hexdigest()[:12]


def train_step_key(model, batch_size: int, accum_steps: int = 1,
                   seq_len: Optional[int] = None,
                   schedule: Optional[dict] = None) -> str:
    """Cache key for a train-step attribution report. Carries EVERYTHING
    that changes the compiled program the fractions describe: the model
    fingerprint, batch/accum, the dtype policy, the workspace/remat
    policy, and — via ``schedule`` (the ParallelWrapper path) — the
    sharding/overlap settings. A tuner reading cached fractions keyed
    without any of these would seed its search from a differently-
    scheduled program's numbers (ISSUE 14 satellite bugfix; regression:
    tests/test_attribution.py mutate-policy test)."""
    dtype = str(getattr(model.conf, "dtype", "FLOAT"))
    mode = str(getattr(model.conf, "workspace_mode", "none") or "none")
    key = (f"train.step:{type(model).__name__}:{model_fingerprint(model)}"
           f":b{batch_size}:acc{accum_steps}:{dtype}:{mode}")
    if seq_len:
        key += f":T{seq_len}"
    if schedule:
        key += "".join(f":{k}={schedule[k]}" for k in sorted(schedule))
    return key


#: process-wide report cache, keyed so ROADMAP item 4's schedule tuner
#: can rank configurations without re-measuring
_REPORTS: Dict[str, dict] = {}
_reports_lock = threading.Lock()


def _remember(key: Optional[str], rep: dict) -> dict:
    if key is not None:
        rep["key"] = key
        with _reports_lock:
            _REPORTS[key] = rep
    return rep


def cached_report(key: str) -> Optional[dict]:
    with _reports_lock:
        return _REPORTS.get(key)


def report_keys() -> List[str]:
    with _reports_lock:
        return sorted(_REPORTS)


def attribute_compiled(compiled, measured_s: Optional[float],
                       host_s: Optional[float] = None,
                       peaks: Optional[dict] = None,
                       key: Optional[str] = None) -> dict:
    """Attribution of one already-compiled AOT executable against an
    externally measured step time (the serving engines' entry point)."""
    cost = cost_analysis(compiled)
    if cost is None:
        rep = {"cost_available": False, "measured": measured_s is not None,
               "measured_s": measured_s, "fractions": None, "mfu": None,
               "mfu_gap": None}
        return _remember(key, rep)
    rep = attribute(cost["flops"], cost["bytes_accessed"], measured_s,
                    host_s=host_s, peaks=peaks)
    rep["cost_available"] = True
    return _remember(key, rep)


def attribute_jitted(fn, args, measured_s: float,
                     host_s: Optional[float] = None,
                     peaks: Optional[dict] = None,
                     key: Optional[str] = None) -> dict:
    """Attribution of a jitted callable on the avals of concrete ``args``
    (bench glue for steps measured elsewhere, e.g. the SameDiff BERT fit
    step): AOT lower+compile for ``cost_analysis`` only — nothing
    executes."""
    _tel.record_compile("attribution.jitted", "probe")
    lowered = fn.lower(*args)
    return attribute_compiled(lowered.compile(), measured_s,
                              host_s=host_s, peaks=peaks, key=key)


def _train_step_args(model, batch_size: int, accum_steps: int,
                     seq_len: Optional[int], step_index: int):
    """Concrete zero-batch arguments matching ``_lower_train_step``'s
    avals. Params/opt/state are fresh device copies per call — the
    compiled step donates them, so a measurement loop must hand over
    buffers it no longer needs."""
    import jax
    import jax.numpy as jnp
    from ..nn import memory as _memory
    from . import sentinel as _sent
    x, y = _memory._batch_avals(model, batch_size, seq_len)

    def zeros(avals):
        return jax.tree.map(
            lambda a: np.zeros(a.shape, a.dtype), avals,
            is_leaf=lambda a: hasattr(a, "shape"))

    xs = tuple(zeros(a) for a in x) if isinstance(x, tuple) else zeros(x)
    ys = tuple(zeros(a) for a in y) if isinstance(y, tuple) else zeros(y)
    fm = (None,) * len(x) if isinstance(x, tuple) else None
    lm = (None,) * len(y) if isinstance(y, tuple) else None
    params = jax.tree.map(jnp.copy, model.params)
    opt = jax.tree.map(jnp.copy, model.updater_state)
    state = jax.tree.map(jnp.copy, model.state)
    return (params, opt, state, np.int32(step_index),
            jax.random.PRNGKey(0), xs, ys, fm, lm,
            jax.tree.map(lambda a: np.zeros(a.shape, a.dtype),
                         _sent.counter_avals()))


def attribution_report(model, batch_size: int, steps: int = 3,
                       accum_steps: int = 1,
                       seq_len: Optional[int] = None,
                       peaks: Optional[dict] = None,
                       measured_s: Optional[float] = None) -> dict:
    """``memory_report``'s roofline sibling for a model's REAL fused
    train step: AOT lower+compile (retrace tracker sees a ``probe``),
    ``cost_analysis``, and — unless ``measured_s`` is passed (e.g. the
    bench's own min-over-chains estimator) — a synced self-measurement
    of ``steps`` executions on zero batches. The report key carries the
    schedule-relevant config (model, batch, dtype, workspace_mode,
    accum) so the tuner can rank configs from the cache."""
    import jax
    from ..nn import memory as _memory
    if not model.params and not model.state:
        model.init()
    # _lower_train_step records the probe compile itself (train.step/
    # probe) — attributing here too would double-count the event
    compiled = _memory._lower_train_step(model, batch_size, accum_steps,
                                         seq_len)
    host_s = None
    if measured_s is None:
        durs = []
        for i in range(max(1, int(steps)) + 1):
            args = _train_step_args(model, batch_size, accum_steps,
                                    seq_len, i)
            t0 = time.perf_counter()
            out = compiled(*args)
            jax.block_until_ready(out)
            durs.append(time.perf_counter() - t0)
        measured_s = min(durs[1:]) if len(durs) > 1 else durs[0]
    else:
        # an externally measured step (the fit loop / bench): the phase
        # histograms carry the host-side data-wait that belongs in the
        # host bucket when samples exist for this model. Pod runs label
        # these cells host=<process_index> too — splat host_labels() or
        # the lookup silently misses on multi-host
        lbl = getattr(model, "telemetry_label", None)
        if lbl is not None:
            host_s = _tel.histogram("train.phase.data_wait_s") \
                .percentile(50, model=lbl, **_tel.host_labels())
    dtype = str(getattr(model.conf, "dtype", "FLOAT"))
    mode = str(getattr(model.conf, "workspace_mode", "none"))
    key = train_step_key(model, batch_size, accum_steps, seq_len)
    rep = attribute_compiled(compiled, measured_s, host_s=host_s,
                             peaks=peaks, key=key)
    rep.update({"kind": "train_step", "batch_size": int(batch_size),
                "accum_steps": int(accum_steps), "dtype": dtype,
                "workspace_mode": mode})
    return rep
