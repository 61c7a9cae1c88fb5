"""Pallas block-shape autotuner for the flash-attention kernel (ISSUE 7).

What a shape gets when nobody tuned it is ``flash_attention.default_blocks``:
the largest tile that fits VMEM, the whole key row where it does (512 x 512
for BERT-base at 512 tokens, one tile a head). Every traced program gets
that default, because a sweep cannot run mid-trace, and ``fit`` traces its
step; the chip read it 8.9x faster than the 128 x 128 this module used to
seed (1.21 against 10.83 ms a layer; PERF.md section 6, PR 32). The tuner is for the user who wants to
check the rule against the device for a shape of their own: enumerate the
feasible schedule space (the TVM line of work, PAPERS.md, 1802.04799),
MEASURE each candidate, and cache the winner per shape key so the sweep
runs once; a warmed cache overrides the default. The one schedule knob is
the kernel's (block_q, block_k) tiling:

- **Key**: ``(Tq, Tk, head_dim, dtype, has_bias)`` — the quantities that
  change the kernel's grid, VMEM footprint, and MXU utilization. Batch and
  head count only scale the embarrassingly-parallel grid dimension and are
  normalized out of the sweep (relative block ranking transfers).
- **Candidates**: the largest few multiple-of-8 divisor blocks per axis
  (``axis_blocks``; the query axis up to ``flash_attention.MAX_BLOCK``, the
  key axis up to the whole row), cross-producted and filtered through the
  kernel's own ``fits_vmem_attention`` guard, with the default itself —
  every candidate is a shape the dispatcher itself would accept.
- **Measurement**: each candidate compiles the REAL train-shaped work
  (forward + custom-VJP backward through ``_flash``) and is timed to
  ``block_until_ready``; min over repeats. Sweeps only run on
  TPU — a CPU "timing" of the Pallas interpreter would tune for the
  interpreter — except when a test explicitly passes ``interpret=True`` to
  exercise the sweep machinery itself (marked slow in the suite).
- **Cache**: process-lifetime dict, persistable to disk as JSON the same
  way the serving engine's AOT bucket cache makes warmup a once-per-deploy
  cost (``DL4J_TPU_AUTOTUNE_CACHE=<path>`` auto-loads before the first
  lookup and auto-saves after every sweep). A key with no sweep yet is
  seeded with the dispatcher's default tiling and marked
  ``source="default"`` — CPU/tier-1 runs therefore NEVER sweep (guarded by
  a regression test).

Observability (ISSUE 7 satellite): every sweep compile goes through the
retrace tracker as ``record_compile("flash_attention.autotune",
cause="autotune")`` so warm-cache steady state keeps its zero-compile
assertion, and every lookup outcome bumps the
``flash_attention.autotune{event=}`` registry counter
(hit / default / sweep / sweep_candidate).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import telemetry as _tel

#: largest block the candidate enumeration considers for the decode
#: kernels' cache axis and the epilogue kernels' rows. The one-shot flash
#: kernels' candidates follow the default tiling's own limits: the query
#: axis up to ``flash_attention.MAX_BLOCK``, the key axis up to the whole row
MAX_BLOCK = 256
#: candidates per axis (the largest N feasible divisor blocks)
AXIS_CANDIDATES = 4

_EVENTS = _tel.counter(
    "flash_attention.autotune",
    "block-shape autotuner events (hit / default / sweep / sweep_candidate)")
_EP_EVENTS = _tel.counter(
    "fused_epilogues.autotune",
    "epilogue row-block autotuner events (hit / default / sweep / "
    "sweep_candidate)")

_lock = threading.RLock()
_cache: Dict[tuple, dict] = {}
_env_cache_loaded = False
_state = {"mode": os.environ.get("DL4J_TPU_AUTOTUNE", "auto")}


def mode() -> str:
    return _state["mode"]


def set_mode(m: str) -> str:
    """"auto" (cache miss on TPU with concrete operands sweeps inline),
    "off" (never sweep — cache hits and the default tiling only; explicit
    :func:`sweep` calls still work). Returns the previous mode."""
    if m not in ("auto", "off"):
        raise ValueError(f"autotune mode {m!r} not in ('auto', 'off')")
    old = _state["mode"]
    _state["mode"] = m
    return old


def counters() -> dict:
    """Lookup/sweep outcome counts — a view over the registry's
    ``flash_attention.autotune{event=}`` counter."""
    return {k: int(_EVENTS.value(event=k))
            for k in ("hit", "default", "sweep", "sweep_candidate")}


def reset_counters() -> None:
    _EVENTS.zero()


def epilogue_counters() -> dict:
    """Epilogue-tuner outcome counts — a view over the registry's
    ``fused_epilogues.autotune{event=}`` counter (ISSUE 16)."""
    return {k: int(_EP_EVENTS.value(event=k))
            for k in ("hit", "default", "sweep", "sweep_candidate")}


def reset_epilogue_counters() -> None:
    _EP_EVENTS.zero()


# ----------------------------------------------------------------- keys
def cache_key(tq: int, tk: int, d: int, dtype, has_bias: bool,
              decode: bool = False, page: int = 0) -> tuple:
    """``decode=True`` keys the decode kernel's tiling (block_q pinned to
    Tq — 1 for single-query decode, k for the speculative multi-query
    verify; only the cache-axis block is tuned) separately from the
    one-shot kernel — the same (Tq, Tk) shape prefers very different
    schedules when the query side is a handful of rows. ``page`` (paged
    KV serving, ISSUE 12): the cache is a page-table gather at this page
    granularity, so the winning cache-axis block differs from a
    contiguous cache of the same length — page size is part of the key
    (``page0`` = contiguous)."""
    base = (int(tq), int(tk), int(d), str(np.dtype(dtype)), bool(has_bias))
    if decode:
        base = base + ("decode",)
    if page:
        base = base + (f"page{int(page)}",)
    return base


def axis_blocks(t: int, cap: int = MAX_BLOCK,
                limit: int = AXIS_CANDIDATES) -> List[int]:
    """The largest ``limit`` multiple-of-8 blocks <= ``cap`` that divide
    ``t`` — the per-axis candidate set (descending)."""
    from . import flash_attention as _fa
    return list(itertools.islice(_fa.divisor_blocks(t, cap), limit))


def candidates(tq: int, tk: int, d: int, itemsize: int = 4,
               decode: bool = False,
               has_bias: bool = False) -> List[Tuple[int, int]]:
    """VMEM-feasible (block_q, block_k) candidates for one key — the cross
    product of the per-axis divisor blocks filtered through the kernel's
    ``fits_vmem_attention`` budget (every candidate is dispatchable).
    Decode keys pin ``block_q = 1`` (the kernel runs one query row) and
    enumerate only the cache-axis blocks."""
    from . import flash_attention as _fa
    out = []
    # decode keys pin the query block to the whole (small) query window:
    # 1 for single-query decode, k for the speculative Tq=k verify
    q_blocks = [int(tq)] if decode else axis_blocks(tq, _fa.MAX_BLOCK)
    for bq in q_blocks:
        for bk in axis_blocks(tk, MAX_BLOCK if decode else tk):
            if _fa.kv_block_ok(bk, tk, has_bias) and \
                    _fa.fits_vmem_attention(bq, bk, d, itemsize):
                out.append((bq, bk))
    default = _default_blocks(tq, tk, d, itemsize, decode, has_bias)
    if default is not None and default not in out:
        out.append(default)
    return out


def _default_blocks(tq: int, tk: int, d: int, itemsize: int,
                    decode: bool = False,
                    has_bias: bool = False) -> Optional[Tuple[int, int]]:
    """The one-shot kernels' default is the dispatcher's own rule,
    ``flash_attention.default_blocks``; the decode kernels keep the whole
    query window and a 128-target cache block."""
    from . import flash_attention as _fa
    if not decode:
        return _fa.default_blocks(tq, tk, d, itemsize, has_bias)
    bk = _fa.pick_kv_block(tk, has_bias=has_bias)
    return None if bk is None else (int(tq), bk)


# ---------------------------------------------------------------- cache
def atomic_json_save(path: str, snap: dict) -> str:
    """Persist a JSON-able cache snapshot via tmp+rename — a torn write
    must never corrupt the next process's load. Shared persistence
    discipline for the sweep-and-cache tuners (this module's flash-block
    cache and ``runtime/schedule.py``'s joint schedule cache)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=1)
    os.replace(tmp, path)
    return path


def _cache_path() -> Optional[str]:
    p = os.environ.get("DL4J_TPU_AUTOTUNE_CACHE", "")
    return p or None


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
            pass  # a corrupt cache file must never block dispatch


def lookup(tq, tk, d, dtype, has_bias,
           decode: bool = False, page: int = 0) -> Optional[dict]:
    """The cache entry for a key, or None (no counter bump)."""
    with _lock:
        _ensure_loaded()
        e = _cache.get(cache_key(tq, tk, d, dtype, has_bias, decode, page))
        return dict(e) if e else None


def _valid_blocks(blocks, tq, tk, d, dtype, decode: bool = False,
                  has_bias: bool = False) -> bool:
    """A cache entry's blocks must be usable for ITS key: multiple-of-8
    divisors within the VMEM budget (decode keys: ``block_q`` exactly the
    query-window size — the whole small-Tq grid row). Guards against
    stale/hand-edited disk caches — an invalid pair would silently
    truncate the kernel grid (``Tq // bq``) and produce wrong attention
    output."""
    from . import flash_attention as _fa
    try:
        bq, bk = int(blocks[0]), int(blocks[1])
    except (TypeError, ValueError, IndexError):
        return False
    q_ok = bq == int(tq) if decode \
        else (bq >= 8 and bq % 8 == 0 and tq % bq == 0)
    return (q_ok and bk >= 8 and bk % 8 == 0 and tk % bk == 0
            and _fa.kv_block_ok(bk, tk, has_bias)
            and _fa.fits_vmem_attention(bq, bk, d,
                                        np.dtype(dtype).itemsize))


def get_blocks(tq, tk, d, dtype, has_bias, *, concrete: bool = False,
               decode: bool = False, page: int = 0
               ) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for one attention shape key.

    A SWEPT cache hit returns the stored blocks. A miss (or a
    default-seeded entry) seeds and returns the default tiling
    (``flash_attention.default_blocks``; the decode kernels' 128-target
    cache block) — UNLESS ``concrete=True`` (the operands are real arrays,
    not tracers), the mode is "auto" and the backend is TPU, in which
    case it sweeps inline and returns the winner (a default seed left by
    an earlier traced dispatch is UPGRADED, not pinned forever). Dispatch
    under ``jit`` always passes ``concrete=False``: a sweep cannot run
    mid-trace, so warm the cache first (``warmup``/``sweep``/disk cache)
    to tune traced programs. Returns None when nothing tiles (caller
    falls back). Invalid entries (corrupt/stale disk cache) are dropped,
    never served. ``decode=True`` keys the decode kernels (``tq`` = the
    query window: 1 or the speculative k); ``page`` keys the paged-KV
    gather granularity separately from a contiguous cache."""
    key = cache_key(tq, tk, d, dtype, has_bias, decode, page)
    can_sweep = (concrete and _state["mode"] == "auto"
                 and jax.default_backend() == "tpu")
    with _lock:
        _ensure_loaded()
        e = _cache.get(key)
        if e is not None and not _valid_blocks(e.get("blocks"), tq, tk, d,
                                               dtype, decode, has_bias):
            del _cache[key]
            e = None
        # only a REAL timing sweep is authoritative on TPU: default seeds
        # AND interpreter-"swept" entries (whose timings tune nothing) are
        # upgraded when a real sweep is possible
        if e is not None and not (can_sweep
                                  and e.get("source") != "sweep"):
            _EVENTS.inc(event="hit")
            return tuple(e["blocks"])
    if can_sweep:
        e = sweep(tq, tk, d, dtype, has_bias, decode=decode, page=page)
        return tuple(e["blocks"]) if e else None
    default = _default_blocks(tq, tk, d, np.dtype(dtype).itemsize, decode,
                              has_bias)
    if default is None:
        return None
    with _lock:
        # pre-seed so repeated lookups are hits and CPU runs never sweep
        _cache.setdefault(key, {"blocks": list(default), "source": "default"})
    _EVENTS.inc(event="default")
    return default


# ------------------------------------------------- fused-epilogue keys
# The epilogue kernels (ops/fused_epilogues.py) expose one schedule knob:
# the row-block size of the (rows // block,) grid. Same sweep-and-cache
# discipline as the attention keys, same disk file, distinct key prefix
# ("epilogue", kind, rows, cols, dtype) and a distinct registry counter so
# the two kernel families' tuner health is separable on /metrics.

def epilogue_cache_key(kind: str, rows: int, cols: int, dtype) -> tuple:
    return ("epilogue", str(kind), int(rows), int(cols),
            str(np.dtype(dtype)))


def epilogue_candidates(kind: str, rows: int, cols: int,
                        dtype) -> List[int]:
    """Feasible row blocks for one epilogue key (descending): the largest
    few sublane-multiple divisors of ``rows`` that fit the kernel's VMEM
    budget — every candidate is a shape the dispatcher would accept."""
    from . import fused_epilogues as _fe
    mult = _fe._row_mult(dtype)
    itemsize = np.dtype(dtype).itemsize
    out: List[int] = []
    b = min(MAX_BLOCK, int(rows))
    b -= b % mult
    while b >= mult and len(out) < AXIS_CANDIDATES:
        if rows % b == 0 and _fe.fits_vmem_epilogue(b, cols, itemsize, kind):
            out.append(b)
        b -= mult
    return out


def _valid_epilogue_blocks(blocks, kind, rows, cols, dtype) -> bool:
    from . import fused_epilogues as _fe
    try:
        br = int(blocks[0])
    except (TypeError, ValueError, IndexError):
        return False
    mult = _fe._row_mult(dtype)
    return (br >= mult and br % mult == 0 and rows % br == 0
            and _fe.fits_vmem_epilogue(br, cols,
                                       np.dtype(dtype).itemsize, kind))


def epilogue_blocks(kind: str, rows: int, cols: int, dtype, *,
                    concrete: bool = False) -> Optional[int]:
    """Row block for one epilogue key — the :func:`get_blocks` contract
    (swept hit > inline sweep when concrete on TPU > seeded default),
    scalar-valued since the epilogue grid has one axis. Returns None when
    nothing tiles (the dispatcher already guarded, so only for degenerate
    keys)."""
    from . import fused_epilogues as _fe
    key = epilogue_cache_key(kind, rows, cols, dtype)
    can_sweep = (concrete and _state["mode"] == "auto"
                 and jax.default_backend() == "tpu")
    with _lock:
        _ensure_loaded()
        e = _cache.get(key)
        if e is not None and not _valid_epilogue_blocks(
                e.get("blocks"), kind, rows, cols, dtype):
            del _cache[key]
            e = None
        if e is not None and not (can_sweep and e.get("source") != "sweep"):
            _EP_EVENTS.inc(event="hit")
            return int(e["blocks"][0])
    if can_sweep:
        e = epilogue_sweep(kind, rows, cols, dtype)
        return int(e["blocks"][0]) if e else None
    default = _fe.row_block(rows, _fe._row_mult(dtype))
    if default is None:
        return None
    with _lock:
        _cache.setdefault(key, {"blocks": [int(default)],
                                "source": "default"})
    _EP_EVENTS.inc(event="default")
    return default


def _time_epilogue_candidate(kind, rows, cols, dtype, br, interpret,
                             repeats: int) -> float:
    """Seconds (min over repeats) for one fwd+bwd through the epilogue
    kernel at row block ``br`` on synthetic operands."""
    from . import fused_epilogues as _fe
    rng = np.random.default_rng(0)
    x2 = jnp.asarray(rng.normal(size=(rows, cols)) * 0.5, dtype)
    v1 = jnp.asarray(rng.normal(size=(1, cols)) * 0.5,
                     jnp.float32 if kind == "affine" else dtype)
    v2 = jnp.asarray(rng.normal(size=(1, cols)) * 0.5, v1.dtype)

    if kind == "ln":
        def loss(x_, g_, b_):
            y = _fe._ln_act(x_, g_, b_, 1e-6, "gelu", br, interpret)
            return jnp.sum(y.astype(jnp.float32))
    else:
        def loss(x_, g_, b_):
            y = _fe._affine_act(x_, g_, b_, "relu", br, interpret)
            return jnp.sum(y.astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    _tel.record_compile("fused_epilogues.autotune", "autotune",
                        blocks=[int(br)], kind=str(kind),
                        rows=int(rows), cols=int(cols))
    _EP_EVENTS.inc(event="sweep_candidate")

    def run():
        jax.block_until_ready(fn(x2, v1, v2))

    run()  # compile + settle
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def epilogue_sweep(kind: str, rows: int, cols: int, dtype, *,
                   interpret: bool = False,
                   repeats: int = 3) -> Optional[dict]:
    """Measure every candidate row block for one epilogue key and cache
    the winner — the :func:`sweep` contract (TPU-only unless
    ``interpret=True``; interpreter entries tagged for re-sweep)."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "autotune.epilogue_sweep() timings are only meaningful on TPU; "
            "CPU runs use pre-seeded defaults (pass interpret=True to "
            "exercise the sweep machinery in tests)")
    cands = epilogue_candidates(kind, rows, cols, dtype)
    if not cands:
        return None
    timings = []
    for br in cands:
        dt = _time_epilogue_candidate(kind, rows, cols, dtype, br,
                                      interpret, repeats)
        timings.append({"blocks": [int(br)], "us": round(dt * 1e6, 2)})
    best = min(timings, key=lambda t: t["us"])
    entry = {
        "blocks": best["blocks"],
        "source": "sweep_interpret" if interpret else "sweep",
        "us": best["us"],
        "candidates": timings,
        "backend": jax.default_backend(),
    }
    key = epilogue_cache_key(kind, rows, cols, dtype)
    with _lock:
        _cache[key] = entry
    _EP_EVENTS.inc(event="sweep")
    if _cache_path():
        try:
            save()
        except OSError:
            pass  # persistence is best-effort; the process cache holds
    return dict(entry)


def _norm_shape(shape) -> tuple:
    """Normalize a warmup/seed shape spec: 5-tuples are one-shot keys,
    6-tuples carry a trailing decode flag."""
    if len(shape) == 5:
        return tuple(shape) + (False,)
    tq, tk, d, dtype, has_bias, decode = shape
    return (tq, tk, d, dtype, has_bias, bool(decode))


def seed_defaults(shapes) -> None:
    """Pre-seed the default tiling for an iterable of
    ``(Tq, Tk, head_dim, dtype, has_bias[, decode])`` keys (no sweeps —
    the CPU/CI posture; on TPU use :func:`warmup`)."""
    for shape in shapes:
        tq, tk, d, dtype, has_bias, decode = _norm_shape(shape)
        get_blocks(tq, tk, d, dtype, has_bias, concrete=False,
                   decode=decode)


def warmup(shapes, *, interpret: bool = False) -> dict:
    """Sweep every unswept key in ``shapes`` (same tuples as
    :func:`seed_defaults`) — the serving-warmup analogue: pay every sweep
    before traffic/timing so steady state stays zero-compile. Keys whose
    cache entry is only a default SEED (e.g. left by an earlier traced
    dispatch) are swept too, not skipped. Off-TPU (unless
    ``interpret=True``), or under mode "off", missing keys seed defaults
    instead of sweeping. Returns {key: entry} for the keys swept."""
    out = {}
    can_sweep = interpret or (jax.default_backend() == "tpu"
                              and _state["mode"] == "auto")
    # what counts as already-tuned: a real sweep always; an interpreter
    # "sweep" only for another interpret warmup (its timings tune nothing
    # on a real chip — a TPU warmup re-sweeps it, per sweep()'s contract)
    done_sources = ("sweep", "sweep_interpret") if interpret else ("sweep",)
    for shape in shapes:
        tq, tk, d, dtype, has_bias, decode = _norm_shape(shape)
        e = lookup(tq, tk, d, dtype, has_bias, decode)
        if can_sweep and (e is None or
                          e.get("source") not in done_sources):
            out[cache_key(tq, tk, d, dtype, has_bias, decode)] = \
                sweep(tq, tk, d, dtype, has_bias, interpret=interpret,
                      decode=decode)
        else:
            get_blocks(tq, tk, d, dtype, has_bias, concrete=False,
                       decode=decode)
    return out


def reset() -> None:
    """Drop the in-process cache (disk files untouched)."""
    global _env_cache_loaded
    with _lock:
        _cache.clear()
        _env_cache_loaded = True  # a reset cache stays reset (tests)


def save(path: Optional[str] = None) -> Optional[str]:
    """Persist the cache as JSON (tmp+rename — a torn write must not
    corrupt the next process's load). Returns the path written, or None
    when no path is configured."""
    path = path or _cache_path()
    if not path:
        return None
    return atomic_json_save(path, cache_snapshot())


def load(path: Optional[str] = None, merge: bool = True) -> int:
    """Load a JSON cache file; ``merge=False`` replaces the in-process
    cache. Swept disk entries win over in-process default seeds; in-process
    sweeps win over disk defaults. Returns the entry count loaded."""
    path = path or _cache_path()
    if not path:
        return 0
    with open(path) as f:
        snap = json.load(f)
    n = 0
    with _lock:
        if not merge:
            _cache.clear()
        for ent in snap.get("entries", []):
            raw = ent["key"]
            if str(raw[0]) == "epilogue":
                kind, rows, cols = str(raw[1]), int(raw[2]), int(raw[3])
                dt = str(raw[4])
                key = epilogue_cache_key(kind, rows, cols, dt)
                if not _valid_epilogue_blocks(ent.get("blocks"), kind,
                                              rows, cols, dt):
                    continue  # stale/hand-edited entry: never serve it
                cur = _cache.get(key)
                if cur is not None and cur.get("source") != "default" \
                        and ent.get("source") == "default":
                    continue
                _cache[key] = {k: v for k, v in ent.items() if k != "key"}
                n += 1
                continue
            tail = [str(x) for x in raw[5:]]
            decode = "decode" in tail
            page = next((int(t[4:]) for t in tail
                         if t.startswith("page") and t[4:].isdigit()), 0)
            key = cache_key(int(raw[0]), int(raw[1]), int(raw[2]),
                            str(raw[3]), bool(raw[4]), decode, page)
            if not _valid_blocks(ent.get("blocks"), key[0], key[1],
                                 key[2], key[3], decode, key[4]):
                continue  # stale/hand-edited entry: never serve it
            cur = _cache.get(key)
            if cur is not None and cur.get("source") != "default" \
                    and ent.get("source") == "default":
                continue
            _cache[key] = {k: v for k, v in ent.items() if k != "key"}
            n += 1
    return n


def cache_snapshot() -> dict:
    """JSON-able view of the cache — embedded in bench artifacts so the
    blocks behind a kernel metric are part of the record."""
    with _lock:
        entries = [{"key": list(k), **v} for k, v in sorted(_cache.items())]
    return {"version": 1, "backend": jax.default_backend(),
            "entries": entries}


# ---------------------------------------------------------------- sweep
_SWEEP_GRID_ROWS = 16  # synthetic B*H: enough grid rows to fill the chip's
#                        cores; relative block ranking transfers to real B*H


def _time_candidate(tq, tk, d, dtype, has_bias, bq, bk, interpret,
                    repeats: int, decode: bool = False) -> float:
    """Seconds (min over repeats) for one fwd+bwd at (bq, bk) on synthetic
    operands — forward-only for ``decode`` keys (decode never trains).
    The compile is reported to the retrace tracker BEFORE the first call
    so a hung compile is still visible in compile_events()."""
    from . import flash_attention as _fa
    rng = np.random.default_rng(0)
    heads = 4
    g = _SWEEP_GRID_ROWS
    batch = g // heads
    scale = 1.0 / float(np.sqrt(d))
    q3 = jnp.asarray(rng.normal(size=(g, tq, d)) * 0.5, dtype)
    k3 = jnp.asarray(rng.normal(size=(g, tk, d)) * 0.5, dtype)
    v3 = jnp.asarray(rng.normal(size=(g, tk, d)) * 0.5, dtype)
    kb = None
    if has_bias:
        mask = np.ones((batch, tk), np.float32)
        mask[:, tk - tk // 8:] = 0.0
        kb = jnp.where(jnp.asarray(mask) > 0, 0.0,
                       np.float32(np.finfo(np.float32).min))[:, None, :]

    if decode:
        # the serving decode hot path: single/multi-query forward, ragged
        # cache occupancy as the mask (the same program decode_attention /
        # decode_multiquery_attention runs; tq > 1 = speculative verify)
        lo = max(1, min(tk // 2, max(1, tk - tq)))
        hi = max(lo + 1, tk - tq + 2)
        lengths = jnp.asarray(rng.integers(lo, hi, size=(batch,)), jnp.int32)

        if tq > 1:
            def fwd(q_, k_, v_):
                o = _fa._mq_impl(q_, k_, v_, lengths, scale, heads,
                                 bk, interpret)
                return (o,)
        else:
            kbd = _fa.length_bias(lengths, tk)[:, None, :]

            def fwd(q_, k_, v_):
                o, _, _ = _fa._fwd_impl(q_, k_, v_, kbd, scale, heads,
                                        bq, bk, interpret)
                return (o,)  # tuple like grad's output: run() reads gs[0]

        fn = jax.jit(fwd)
    else:
        def loss(q_, k_, v_):
            o = _fa._flash(q_, k_, v_, kb, scale, heads, bq, bk, interpret)
            return jnp.sum(o.astype(jnp.float32))

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    _tel.record_compile("flash_attention.autotune", "autotune",
                        blocks=[int(bq), int(bk)], tq=int(tq), tk=int(tk),
                        decode=bool(decode))
    _EVENTS.inc(event="sweep_candidate")

    def run():
        jax.block_until_ready(fn(q3, k3, v3))

    run()  # compile + settle
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(tq, tk, d, dtype, has_bias, *, interpret: bool = False,
          repeats: int = 3, decode: bool = False,
          page: int = 0) -> Optional[dict]:
    """Measure every candidate block shape for one key and cache the
    winner. TPU-only unless ``interpret=True`` (the slow-marked test path:
    exercises the sweep machinery through the Pallas interpreter, whose
    "timings" tune nothing — the entry is tagged so a real chip re-sweeps).
    ``decode=True`` sweeps the single-query decode kernel (forward only,
    block_q pinned to 1). Returns the cache entry, or None when nothing
    tiles."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "autotune.sweep() timings are only meaningful on TPU; CPU runs "
            "use pre-seeded defaults (pass interpret=True to exercise the "
            "sweep machinery through the Pallas interpreter in tests)")
    itemsize = np.dtype(dtype).itemsize
    cands = candidates(tq, tk, d, itemsize, decode=decode,
                       has_bias=has_bias)
    if not cands:
        return None
    timings = []
    for bq, bk in cands:
        dt = _time_candidate(tq, tk, d, dtype, has_bias, bq, bk,
                             interpret, repeats, decode=decode)
        timings.append({"blocks": [int(bq), int(bk)],
                        "us": round(dt * 1e6, 2)})
    best = min(timings, key=lambda t: t["us"])
    entry = {
        "blocks": best["blocks"],
        "source": "sweep_interpret" if interpret else "sweep",
        "us": best["us"],
        "candidates": timings,
        "backend": jax.default_backend(),
    }
    key = cache_key(tq, tk, d, dtype, has_bias, decode, page)
    with _lock:
        _cache[key] = entry
    _EVENTS.inc(event="sweep")
    if _cache_path():
        try:
            save()
        except OSError:
            pass  # persistence is best-effort; the process cache holds
    return dict(entry)
