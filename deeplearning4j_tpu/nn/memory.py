"""Workspace-mode rematerialization + compiled HBM accounting.

TPU-native equivalent of DL4J's Workspaces/CacheMode memory subsystem
(reference: ``nd4j .../memory/MemoryWorkspace.java``, ``deeplearning4j-nn
.../nn/conf/WorkspaceMode.java``/``CacheMode.java``† per SURVEY.md §2
"Memory mgmt"; reference mount was empty, citations upstream-relative,
unverified).

The reference manages *buffer* memory: arena allocators with alloc/spill
policies and per-layer activation caching. On TPU the arena half came free —
jit + buffer donation already give in-place reuse (SURVEY.md §3.1) — but
nothing controlled the **activation** memory that dominates peak HBM in
training: XLA saves every layer's forward activations for the backward
pass. This module adds the TPU-native control:

- **workspace_mode** (DL4J-parity name; ``CacheMode``'s activation-caching
  role): a training-config knob that applies ``jax.checkpoint`` (remat) at
  block granularity in the engines' fused train steps. Policies:

  - ``none``    — cache everything (today's behavior; DL4J CacheMode-ish).
  - ``full``    — checkpoint every block; only block-boundary activations
                  are kept, everything inside a block is recomputed in the
                  backward pass (``enabled`` is accepted as the DL4J
                  ``WorkspaceMode.ENABLED`` parity alias).
  - ``dots_saveable`` — checkpoint every block but let XLA keep matmul
                  outputs (``jax.checkpoint_policies.dots_saveable``):
                  recompute the cheap elementwise tail, keep the
                  MXU-expensive products.
  - ``every_<k>`` — checkpoint segments of ``k`` consecutive blocks
                  (classic sqrt-style trade: larger k = less memory, more
                  recompute).

  What a recomputing policy keeps of a segment: the live activations at its
  boundary, whatever its ``saveable`` rule allows, and every array tagged
  :data:`KEPT` (``jax.ad_checkpoint.checkpoint_name``). Two things are
  tagged today. The output of a causal attention no wider than twice its
  layer's input (``ops/causal_attention.py`` ``keep=``, asked for by the
  attention layers of ``nn/layers/decoder.py``), and on the kernel path the
  logsumexp beside it: the backward pass reads nothing else of an
  attention's forward, so with the output kept the segment's recomputation
  holds no score product (PERF.md, PR 38). And what a language-model head
  made of its gradients in the forward pass (``ops/lm_loss.py``: the hidden
  states' gradient, the weight's and the per-position cross-entropies), which
  is all its backward rule reads, so the recomputation holds no logits
  (PERF.md, PR 40). A program that tags nothing lowers as it did under
  ``policy=None``.

  A "block" is a layer (MultiLayerNetwork), a vertex (ComputationGraph),
  or an attention-anchored op segment (imported SameDiff graphs — see
  ``autodiff/remat.py``). Recorded divergences from the reference:
  no spill-to-host tier, and the granularity is a block, not a per-array
  alloc policy (PARITY.md).

- **compiled HBM accounting**: ``model.memory_report(batch_size)`` lowers
  and compiles the REAL train step ahead of time and reads XLA's
  ``memory_analysis()`` (temp/argument/output bytes) plus the
  backend-independent autodiff residual accounting
  (``saved_residuals`` — the bytes actually carried from forward to
  backward, the quantity remat shrinks) and live ``device.memory_stats()``
  telemetry. No step is executed and nothing is allocated.

- **max_batch() autotuning**: binary-search power-of-two batch sizes via
  the same AOT lower+compile against the device ``bytes_limit`` — the
  largest batch that FITS is known before any OOM can happen.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..runtime import telemetry as _tel

# ---------------------------------------------------------------- policies


@dataclasses.dataclass(frozen=True)
class RematPolicy:
    """Resolved workspace-mode policy. ``remat=False`` means the knob is
    off; ``every`` is the segment size in blocks; ``saveable`` is the
    ``jax.checkpoint`` policy applied INSIDE a segment (None = save
    nothing, recompute all)."""
    name: str
    remat: bool
    every: int = 1
    saveable: Optional[Callable] = None


_FIXED = {
    "none": RematPolicy("none", remat=False),
    "full": RematPolicy("full", remat=True, every=1),
    "dots_saveable": RematPolicy(
        "dots_saveable", remat=True, every=1,
        saveable=jax.checkpoint_policies.dots_saveable),
}

# DL4J spelling parity: WorkspaceMode.ENABLED/NONE
_ALIASES = {"enabled": "full"}


def workspace_modes() -> List[str]:
    """The registry's canonical policy names (``every_<k>`` is the
    parameterized fourth family)."""
    return sorted(_FIXED) + ["every_<k>"]


def resolve_policy(mode) -> RematPolicy:
    """Resolve a workspace-mode string (case-insensitive; None/"" = none)
    to a :class:`RematPolicy`. Raises ValueError for unknown names."""
    if mode is None or mode == "":
        return _FIXED["none"]
    if isinstance(mode, RematPolicy):
        return mode
    name = str(mode).strip().lower()
    name = _ALIASES.get(name, name)
    if name in _FIXED:
        return _FIXED[name]
    if name.startswith("every_"):
        tail = name[len("every_"):]
        if tail.isdigit() and int(tail) >= 1:
            return RematPolicy(name, remat=True, every=int(tail))
    raise ValueError(
        f"unknown workspace_mode {mode!r} — expected one of: "
        f"{', '.join(workspace_modes())} (e.g. 'every_2'), or 'enabled' "
        "(DL4J WorkspaceMode parity alias for 'full')")


#: the one name the recomputing policies keep
KEPT = "attention.kept"
_KEEP_TAGGED = jax.checkpoint_policies.save_only_these_names(KEPT)
_tracing = threading.local()


def recomputing() -> bool:
    """Whether this thread is tracing a segment of :func:`checkpoint`, where
    an array tagged :data:`KEPT` is kept for the backward pass."""
    return getattr(_tracing, "segments", 0) > 0


@contextlib.contextmanager
def _segment():
    _tracing.segments = getattr(_tracing, "segments", 0) + 1
    try:
        yield
    finally:
        _tracing.segments -= 1


def checkpoint(fn: Callable, policy: RematPolicy, *,
               prevent_cse: bool = True) -> Callable:
    """Wrap ``fn`` in ``jax.checkpoint`` under the policy's saveable rule
    joined with the arrays tagged :data:`KEPT` (identity when the policy is
    off). ``prevent_cse=False`` is for a segment in the body of a
    ``lax.scan``: there the forward pass and the backward pass are two
    loops, XLA cannot merge the recomputation into the forward pass, and
    the barriers that keep it from trying are in the way (``jax.checkpoint``
    says as much). With them a looped decoder's backward scan ran its
    attention blocks' operands through fast memory in slices and lost 5%
    where it should have gained 6 (PERF.md, PR 38)."""
    if not policy.remat:
        return fn
    keep = _KEEP_TAGGED if policy.saveable is None else \
        jax.checkpoint_policies.save_from_both_policies(policy.saveable,
                                                        _KEEP_TAGGED)
    remat = jax.checkpoint(fn, policy=keep, prevent_cse=prevent_cse)

    def call(*args):
        with _segment():
            return remat(*args)
    return call


def segment_ranges(n: int, every: int) -> List[Tuple[int, int]]:
    """[(start, end), ...] covering ``range(n)`` in chunks of ``every``."""
    every = max(1, int(every))
    return [(s, min(s + every, n)) for s in range(0, n, every)]


# ------------------------------------------------- policy coverage ledger
# Mirror of the ops-coverage ledger idea (tests/test_zz_coverage_floor.py):
# remat tests mark every policy family they exercised; the floor test
# asserts the whole registry is covered in full-suite runs.

_TESTED_POLICIES: set = set()


def mark_policy_tested(mode) -> None:
    name = resolve_policy(mode).name
    _TESTED_POLICIES.add("every" if name.startswith("every_") else name)


def policy_coverage_report() -> dict:
    known = set(_FIXED) | {"every"}
    tested = set(_TESTED_POLICIES)
    return {"known": sorted(known), "tested": sorted(tested),
            "untested": sorted(known - tested),
            "coverage": (len(known & tested) / len(known)) if known else 1.0}


# --------------------------------------------------------- live telemetry


def device_memory_stats(device=None) -> Optional[dict]:
    """PJRT ``memory_stats()`` of one device (default: device 0), reduced
    to the fields the dashboards/benches chart. Returns None on backends
    (CPU) that don't report them — callers degrade gracefully."""
    try:
        d = device if device is not None else jax.local_devices()[0]
        ms = d.memory_stats()
        if not ms:
            return None
        return {"bytes_in_use": int(ms.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(ms.get("bytes_limit", 0))}
    except Exception:
        return None


_MA_SUPPORTED = None


def memory_analysis_supported() -> bool:
    """Whether this PJRT build exposes ``Compiled.memory_analysis()``
    (probed once on a trivial program; a backend may lack the API
    or return None — tests skip-guard on this)."""
    global _MA_SUPPORTED
    if _MA_SUPPORTED is None:
        try:
            import jax.numpy as jnp
            # once-per-process trivial compile; attributed so even the
            # capability probe is visible to the retrace tracker
            _tel.record_compile("memory.probe", "probe")
            c = jax.jit(lambda x: x + 1).lower(
                jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
            ma = c.memory_analysis()
            _MA_SUPPORTED = ma is not None and \
                hasattr(ma, "temp_size_in_bytes")
        except Exception:
            _MA_SUPPORTED = False
    return _MA_SUPPORTED


def compiled_memory(compiled) -> Optional[dict]:
    """``memory_analysis()`` of an AOT-compiled program as a plain dict
    (None when the PJRT build doesn't expose it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        return None
    d = {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    # peak HBM estimate: arguments + temps + outputs, minus what aliases
    # the (donated) arguments — the quantity to hold under bytes_limit
    d["peak_bytes"] = (d["argument_bytes"] + d["temp_bytes"]
                       + d["output_bytes"] - d["alias_bytes"])
    return d


def residual_bytes(loss_fn: Callable, *args) -> Optional[dict]:
    """Forward→backward residual accounting of a differentiated function
    via ``jax.ad_checkpoint``'s ``saved_residuals`` (backend-independent:
    works on avals, nothing executes). ``activation_bytes`` counts only
    COMPUTED residuals — the saved activations remat trades for compute;
    argument residuals (weights, inputs) are live regardless of policy."""
    try:  # public in newer jax (jax.ad_checkpoint.saved_residuals)
        from jax.ad_checkpoint import saved_residuals  # type: ignore
    except ImportError:
        try:
            from jax._src.ad_checkpoint import saved_residuals
        except Exception:
            return None
    try:
        res = saved_residuals(loss_fn, *args)
    except Exception:
        return None
    total = act = count = 0
    for aval, src in res:
        nbytes = int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize \
            if getattr(aval, "shape", None) is not None else 0
        total += nbytes
        count += 1
        if "from the argument" not in str(src):
            act += nbytes
    return {"residual_bytes": total, "activation_bytes": act,
            "residual_count": count}


# --------------------------------------------------- engine AOT accounting


def _is_graph(model) -> bool:
    return hasattr(model.conf, "inputs")


def _batch_avals(model, batch_size: int, seq_len: Optional[int] = None):
    """(xs_avals, ys_avals) for one training batch of ``batch_size`` —
    feature avals from the config input shapes, label avals from an
    abstract forward pass (labels share the loss head's output shape).
    MultiLayerNetwork gets bare arrays, ComputationGraph tuples."""
    from .. import dtypes as _dt
    dt = _dt.resolve(model.conf.dtype)
    dt = dt if np.issubdtype(dt, np.floating) else np.dtype(np.float32)

    def x_aval(shape):
        shape = tuple(int(s) for s in shape)
        if len(shape) == 2:  # recurrent [T, F]: T may be dynamic (-1)
            t = shape[0] if shape[0] > 0 else (seq_len or 0)
            if t <= 0:
                raise ValueError("model has dynamic sequence length: pass "
                                 "seq_len= to memory_report/max_batch")
            shape = (t, shape[1])
        return jax.ShapeDtypeStruct((batch_size,) + shape, dt)

    params_avals = jax.eval_shape(lambda: model.params)
    state_avals = jax.eval_shape(lambda: model.state)
    if _is_graph(model):
        conf = model.conf
        xs = tuple(x_aval(conf.input_shapes[n]) for n in conf.inputs)
        outs = jax.eval_shape(
            lambda p, s, xs_: tuple(
                model._forward(p, dict(zip(conf.inputs, xs_)), s,
                               train=False, rng=None)[0][o]
                for o in conf.outputs),
            params_avals, state_avals, xs)
        ys = tuple(jax.ShapeDtypeStruct(o.shape, np.float32) for o in outs)
        return xs, ys
    if model.conf.input_shape is None:
        raise ValueError("config needs input_type(...) for memory accounting")
    x = x_aval(model.conf.input_shape)
    out = jax.eval_shape(
        lambda p, s, x_: model._forward(p, x_, s, train=False, rng=None)[0],
        params_avals, state_avals, x)
    return x, jax.ShapeDtypeStruct(out.shape, np.float32)


def _lower_train_step(model, batch_size: int, accum_steps: int = 1,
                      seq_len: Optional[int] = None,
                      cause: Optional[str] = "probe"):
    """AOT lower+compile of the engine's REAL fused train step at the
    given batch size (nothing executes, nothing is allocated on device).
    The compile is reported to the retrace tracker as ``cause`` (default
    ``probe``); a caller that records its own attributed event (the
    schedule tuner's ``schedule_tune``) passes ``cause=None``."""
    x, y = _batch_avals(model, batch_size, seq_len)
    params_avals = jax.eval_shape(lambda: model.params)
    state_avals = jax.eval_shape(lambda: model.state)
    opt_avals = jax.eval_shape(lambda: model.updater_state)
    step_aval = jax.ShapeDtypeStruct((), np.int32)
    key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    fm = (None,) * len(x) if isinstance(x, tuple) else None
    lm = (None,) * len(y) if isinstance(y, tuple) else None
    step = model._build_train_step(accum_steps)
    from ..runtime import sentinel as _sent
    if cause is not None:
        _tel.record_compile("train.step", cause,
                            model=type(model).__name__, batch=batch_size)
    # sentinel counters included: this accounts the REAL fused step the
    # fit loop runs (divergence sentinel and all)
    return step.lower(params_avals, opt_avals, state_avals,
                      step_aval, key_aval, x, y, fm, lm,
                      _sent.counter_avals()).compile()


def memory_report(model, batch_size: int, accum_steps: int = 1,
                  seq_len: Optional[int] = None) -> dict:
    """Compiled-HBM report for the model's train step at ``batch_size``:
    XLA ``memory_analysis()`` fields (+ ``peak_bytes``), the
    backend-independent forward→backward residual accounting
    (``activation_bytes`` is what the workspace_mode remat shrinks), and
    live device ``memory_stats()`` telemetry. Fields degrade to None on
    PJRT builds without the corresponding API."""
    if not model.params and not model.state:
        model.init()
    report = {
        "workspace_mode": str(getattr(model.conf, "workspace_mode", "none")),
        "batch_size": int(batch_size),
        "accum_steps": int(accum_steps),
        "temp_bytes": None, "argument_bytes": None, "output_bytes": None,
        "alias_bytes": None, "generated_code_bytes": None,
        "peak_bytes": None,
        "residual_bytes": None, "activation_bytes": None,
        "residual_count": None,
        "device": device_memory_stats(),
    }
    compiled = _lower_train_step(model, batch_size, accum_steps, seq_len)
    cm = compiled_memory(compiled)
    if cm:
        report.update(cm)
    x, y = _batch_avals(model, batch_size, seq_len)
    params_avals = jax.eval_shape(lambda: model.params)
    state_avals = jax.eval_shape(lambda: model.state)
    key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    fm = (None,) * len(x) if isinstance(x, tuple) else None
    lm = (None,) * len(y) if isinstance(y, tuple) else None
    rb = residual_bytes(model._build_loss_fn(), params_avals, state_avals,
                        key_aval, x, y, fm, lm)
    if rb:
        report.update(rb)
    return report


def max_batch(model, bytes_limit: Optional[int] = None, *,
              start: int = 1, limit: int = 65536,
              accum_steps: int = 1, seq_len: Optional[int] = None,
              fraction: float = 1.0) -> Optional[int]:
    """Largest power-of-two batch whose train step FITS in ``bytes_limit``
    HBM, found by AOT lower+compile (binary search over the exponent — no
    step runs, so no OOM probing). ``bytes_limit`` defaults to the live
    device ``memory_stats()['bytes_limit']``; on backends without the API
    it must be passed explicitly. ``fraction`` reserves headroom (serving
    arenas, fragmentation). Returns None when even ``start`` doesn't fit
    or the PJRT build exposes no ``memory_analysis``."""
    if bytes_limit is None:
        dm = device_memory_stats()
        if not dm or not dm.get("bytes_limit"):
            raise ValueError(
                "device reports no memory_stats()['bytes_limit'] — pass "
                "bytes_limit= explicitly on this backend")
        bytes_limit = dm["bytes_limit"]
    budget = int(bytes_limit * fraction)
    if not model.params and not model.state:
        model.init()

    def fits(b: int) -> Optional[bool]:
        cm = compiled_memory(_lower_train_step(model, b, accum_steps,
                                               seq_len))
        if cm is None:
            return None
        return cm["peak_bytes"] <= budget

    best = None
    b = max(1, int(start))
    while b <= limit:
        ok = fits(b)
        if ok is None:
            return None  # no memory_analysis on this PJRT build
        if not ok:
            break
        best = b
        b <<= 1
    return best
