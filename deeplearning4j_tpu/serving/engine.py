"""InferenceEngine: bucketed, AOT-compiled inference for serving.

The serving analog of the training engines' "one compiled program" thesis
(SURVEY.md §3.1): every inference entry point used to be a bare
``jax.jit`` that retraced on every distinct batch size and seq length —
fatal under ragged request traffic, where compiles (seconds) land *under
load*. This engine:

- pads the batch dimension (and the sequence dimension for recurrent
  nets) up to a small set of power-of-two **buckets**, so the number of
  compiled programs is O(log max_batch) instead of O(distinct sizes);
- compiles each bucket **ahead of time** via
  ``jax.jit(...).lower(...).compile()`` (``warmup()``), so no compile
  ever happens under traffic;
- unpads **mask-exactly**: padded batch rows never influence real rows
  (inference is per-example), and padded time steps are masked out
  through the layer stack's feature-mask path (recurrent carry gating,
  masked pooling/attention), then sliced off;
- counts bucket hits vs. compiles, per bucket — the serving health
  signal (a compile after warmup is a bug, and tests assert zero);
- optionally places the padded batch over the ``'data'`` axis of a
  device mesh via ``NamedSharding``, so one coalesced request batch
  spans the slice (composes with ``serving.batcher.ParallelInference``).

Works for both engines: ``MultiLayerNetwork`` (single input) and
``ComputationGraph`` (input tuple, output tuple) — both expose the pure
``_forward`` walk this wraps.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import dtypes as _dt
from ..ops import flash_attention as _fa
from ..ops import pallas_kernels as _pk
from ..ops import quantize as _q
from ..ops import sampling as _smp
from ..parallel import placement as _pl
from ..parallel.placement import QuantizedParamsMixin as _QuantizedParamsMixin
from ..runtime import telemetry as _tel

log = logging.getLogger("deeplearning4j_tpu")

# per-engine counters live in the process-wide MetricsRegistry (ISSUE 6),
# labeled by a monotonically assigned engine id so stats() keeps its
# per-instance semantics while `GET /metrics` scrapes every engine at once
_M_CALLS = _tel.counter("serving.engine.calls", "output() requests")
_M_HITS = _tel.counter("serving.engine.hits", "warm-bucket executable hits")
_M_COMPILES = _tel.counter("serving.engine.compiles",
                           "AOT bucket compiles (after warmup: a bug)")
_M_PADDED = _tel.counter("serving.engine.padded_rows",
                         "pad rows added by bucket rounding")
_M_BUCKET_HITS = _tel.counter("serving.engine.bucket_hits",
                              "executable hits per bucket shape")
# request-lifecycle phases inside the engine: pad -> execute -> unpad
_H_PAD = _tel.histogram("serving.phase.pad_s",
                        "host-side bucket padding time per engine call")
_H_EXEC = _tel.histogram("serving.phase.execute_s",
                         "device executable time per engine call")
_H_UNPAD = _tel.histogram("serving.phase.unpad_s",
                          "host-side unpad time per engine call")
# generative decode phases (ISSUE 8): prompt prefill per admitted request,
# one decode iteration over the whole slot batch
_H_PREFILL = _tel.histogram("serving.phase.prefill_s",
                            "prompt prefill time per admitted request")
_H_DECODE = _tel.histogram("serving.phase.decode_step_s",
                           "one decode iteration over the slot batch")
# disaggregated serving (ISSUE 18): KV-page migration — whole pages
# gathered to host / scattered from host in ONE device call per bucket
_H_KV_EXPORT = _tel.histogram(
    "serving.phase.kv_export_s",
    "KV-page export (device gather + host copy) per migrated request")
_H_KV_IMPORT = _tel.histogram(
    "serving.phase.kv_import_s",
    "KV-page import (host upload + device scatter) per adopted request")
# int8 post-training quantization (ISSUE 9): the calibration/dequant
# telemetry and the quantized-params source moved to
# parallel/placement.py with the rest of the placement machinery
# (ISSUE 17); the KV gauge stays here (generative engines only)
_G_Q_KV = _tel.gauge("serving.quantize.kv_bytes",
                     "decode KV-cache bytes at the current bucket")
# tensor-parallel serving (ISSUE 17): per-engine shard count, labeled
# engine= AND mesh= — the staticcheck mesh-label rule keys on both
_G_TP_SHARDS = _tel.gauge(
    "serving.engine.tp_shards",
    "model-axis shards serving this engine's params/KV (1 = unsharded)")
_engine_ids = itertools.count()


def next_bucket(n: int, minimum: int = 1) -> int:
    """Smallest power of two >= n (and >= minimum)."""
    b = max(1, int(minimum))
    while b < n:
        b <<= 1
    return b


def default_buckets(max_batch: int = 64, minimum: int = 1) -> List[int]:
    """Power-of-two ladder [minimum..max_batch]."""
    out, b = [], max(1, int(minimum))
    while b <= max_batch:
        out.append(b)
        b <<= 1
    return out


class InferenceEngine(_QuantizedParamsMixin):
    """Bucketed AOT-compiled ``output()`` for one model.

    Usage::

        eng = InferenceEngine(net)
        eng.warmup([1, 2, 4, 8, 16, 32])   # compile outside traffic
        y = eng.output(x)                  # any batch size: zero compiles
        eng.stats()                        # hits / compiles / per-bucket

    ``mesh``: a ``jax.sharding.Mesh`` with a ``'data'`` axis — the padded
    batch is placed over it (bucket floor rises to the axis size so every
    device holds equal rows); params/state replicate.

    ``quantize="int8"`` (ISSUE 9): post-training per-channel int8 weight
    quantization applied ONCE at warmup — every bucket executable
    compiles the quantized graph (int8 MXU matmul/conv passes, ~half the
    weight HBM), requests quantize their activations dynamically inside
    the program, and a later ``fit()`` requantizes host-side without a
    single new compile. Accuracy is gated, not assumed:
    ``eval.quantization.quantization_gate`` compares the two engines.
    """

    def __init__(self, model, mesh=None, data_axis: str = "data",
                 min_bucket: int = 1, quantize: Optional[str] = None,
                 model_axis: Optional[str] = "model",
                 pool_label: str = "default"):
        self.model = model
        # ISSUE 18: disaggregated topologies run several engines per
        # PROCESS ROLE (prefill pool vs decode pool); every serving.*
        # cell carries pool= beside engine= so pool-level dashboards
        # never blend phases across roles (staticcheck enforces it)
        self._pool_label = str(pool_label)
        self.mesh = mesh
        self.data_axis = data_axis
        self._placement_layer = None
        if mesh is not None:
            if data_axis not in mesh.axis_names:
                raise ValueError(f"mesh has no {data_axis!r} axis "
                                 f"(axes: {mesh.axis_names})")
            min_bucket = max(min_bucket, int(mesh.shape[data_axis]))
            # ISSUE 17: a mesh carrying a model axis (launcher.pod_mesh
            # (model=k)) serves tensor-parallel — params shard by the
            # placement layer's TP specs instead of replicating
            self._placement_layer = _pl.ParamsPlacement(
                mesh, model=model, model_axis=model_axis,
                data_axis=data_axis)
        self.min_bucket = max(1, int(min_bucket))
        self._is_graph = hasattr(model.conf, "inputs")
        self._input_shapes = self._model_input_shapes()
        # [T, F] input convention (InputType.recurrent) => the runtime
        # array is [B, T, F] and axis 1 is bucketable sequence; a config
        # without shapes (shapes=None) serves batch-bucketed only, deriving
        # per-request shapes (warmup then needs no traffic to have flowed)
        self._seq_input = [len(s) == 2 for s in self._input_shapes] \
            if self._input_shapes is not None else None
        self._compiled: Dict[Tuple, Any] = {}
        # bound bucket-hit cells, one per compiled key: the warm-hit path
        # runs per request, so the label string + sorted label key are
        # built once at compile time, not per call
        self._hit_cells: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._placed_params_src = None
        self._placed = None
        self._placement_src = None
        self._placement = None
        # counters are registry cells labeled by engine id (ISSUE 6); the
        # legacy attribute names survive as read-only properties below,
        # and a finalizer drops the cells when the engine is collected so
        # model churn cannot grow the registry (and /metrics) unboundedly
        self._id = str(next(_engine_ids))
        weakref.finalize(self, _tel.registry.discard_cells, engine=self._id)
        self._init_quantize(quantize)
        self._bind_quantize_cells()
        _pool = self._pool_label
        self._m_calls = _M_CALLS.labeled(engine=self._id, pool=_pool)
        self._m_hits = _M_HITS.labeled(engine=self._id, pool=_pool)
        self._m_compiles = _M_COMPILES.labeled(engine=self._id, pool=_pool)
        self._m_padded = _M_PADDED.labeled(engine=self._id, pool=_pool)
        # phase histograms carry engine= too: in a multi-engine process
        # (lazy default engine + ParallelWrapper.serving_engine(), or a
        # multi-model service) unlabeled cells would blend every engine's
        # pad/execute/unpad distribution into one unusable p99
        self._h_pad = _H_PAD.labeled(engine=self._id, pool=_pool)
        self._h_exec = _H_EXEC.labeled(engine=self._id, pool=_pool)
        self._h_unpad = _H_UNPAD.labeled(engine=self._id, pool=_pool)
        if self._placement_layer is not None:
            _G_TP_SHARDS.labeled(
                engine=self._id, mesh=_pl.mesh_key(mesh),
                pool=_pool,
            ).set(self._placement_layer.tp)
        # retrace tracker: why the next compile is happening (armed by
        # invalidate(cause=...), consumed by _get_compiled) + the aval
        # keys ever compiled, so a re-compile of a known bucket shape
        # under a new params placement is attributed to the placement
        self._invalidate_cause: Optional[str] = None
        self._known_avals: set = set()
        # aval keys that were warmed when invalidate(cause=) fired -> that
        # cause, so EVERY stale bucket's rebuild is attributed to the
        # invalidation (the one-shot _invalidate_cause alone would tag the
        # first rebuild and leave the rest reading as mystery new_buckets)
        self._stale_causes: Dict[Tuple, str] = {}
        # register with the model so _invalidate_compiled (set_dtype,
        # topology mutation) reaches EVERY engine serving it — including
        # ones built directly or via ParallelWrapper.serving_engine, not
        # just model.inference_engine(); weak so engines can be dropped
        try:
            if not hasattr(model, "_serving_engines"):
                model._serving_engines = weakref.WeakSet()
            model._serving_engines.add(self)
        except (AttributeError, TypeError):
            pass  # models with __slots__ / exotic proxies: opt out

    # ------------------------------------------------------------ model glue
    def _model_input_shapes(self) -> Optional[List[Tuple[int, ...]]]:
        conf = self.model.conf
        if self._is_graph:
            if set(conf.input_shapes) != set(conf.inputs):
                return None
            return [tuple(conf.input_shapes[n]) for n in conf.inputs]
        if conf.input_shape is None:
            return None
        return [tuple(conf.input_shape)]

    def _forward_fn(self):
        model = self.model
        if self._is_graph:
            names = list(model.conf.inputs)
            outputs = list(model.conf.outputs)

            def fwd(params, state, xs, masks):
                acts, _, _ = model._forward(
                    params, dict(zip(names, xs)), state, train=False,
                    rng=None,
                    masks={n: m for n, m in zip(names, masks)
                           if m is not None})
                return tuple(acts[o] for o in outputs)
        else:
            def fwd(params, state, xs, masks):
                out, _, _ = model._forward(
                    params, xs[0], state, train=False, rng=None,
                    mask=masks[0])
                return (out,)
        return fwd

    # ----------------------------------------------------------- compilation
    def _shardings(self, xs_avals, masks_avals):
        """Mesh placements for the request arrays: (xs, masks) sharding
        tuples over the data axis, or (None, None) without a mesh."""
        if self.mesh is None:
            return None, None
        data = NamedSharding(self.mesh, P(self.data_axis))
        xs_sh = tuple(data for _ in xs_avals)
        masks_sh = tuple(None if m is None else data for m in masks_avals)
        return xs_sh, masks_sh

    def _params_placement(self):
        """(fingerprint, params sharding tree, state sharding tree) of the
        arrays the executables will actually be fed (the mesh-placed trees
        when a mesh is configured). AOT executables are strict about input
        shardings, so a placement change — e.g. a ParallelWrapper.fit
        leaving replicated NamedSharding arrays behind — must key (and
        lower) its own executable rather than feed the old one.
        Identity-cached: fit() rebinds the params dict, so the leaf walk
        only reruns after an update. Quantized serving fingerprints the
        quantized tree (its avals are what the executables see)."""
        params, state = self._place_params()
        # strong refs + `is` checks, NOT id(): a freed dict's address can
        # be reused by a later params tree, which would serve stale copies
        if self._placement_src is not None and \
                self._placement_src[0] is params and \
                self._placement_src[1] is state:
            return self._placement
        shs = []

        def grab(leaf):
            sh = getattr(leaf, "sharding", None)
            shs.append(sh)
            return sh

        p_sh = jax.tree.map(grab, params)
        s_sh = jax.tree.map(grab, state)
        if any(s is None for s in shs):
            # host numpy leaves: no placement to pin; let jit default
            placement = ("host", None, None)
        else:
            placement = ("|".join(sorted(set(map(str, shs)))), p_sh, s_sh)
        self._placement_src = (params, state)
        self._placement = placement
        return placement

    def _key_of(self, xs_avals, masks_avals, fp) -> Tuple:
        return (tuple((tuple(a.shape), str(a.dtype)) for a in xs_avals),
                tuple(None if m is None else tuple(m.shape)
                      for m in masks_avals), fp)

    def _lower_bucket(self, xs_avals, masks_avals):
        """AOT-lowered (not yet compiled) program for one bucket, with the
        SAME sharding pinning as the serving executables — `_get_compiled`
        compiles these into the cache; `max_batch` compiles them for
        memory accounting only (identical program, so the per-device
        `memory_analysis` describes what serving will actually hold)."""
        _fp, p_sh, s_sh = self._params_placement()
        # quantized serving compiles over the quantized tree's avals
        # (int8 weights + f32 scales) — memory_analysis therefore
        # reports the REAL argument bytes, which is what max_batch's
        # "quantized weights ~double the serveable batch" delta measures.
        # Materialized OUTSIDE eval_shape: tracing the quantize walk
        # would cache tracer arrays in the params source.
        serving_params = self._serving_params()
        params_avals = jax.eval_shape(lambda: serving_params)
        state_avals = jax.eval_shape(lambda: self.model.state)
        xs_sh, masks_sh = self._shardings(xs_avals, masks_avals)
        in_sh = None
        if p_sh is not None:
            # pin the executable to the params' actual placement (keeps
            # TP-sharded leaves sharded; replicated stays replicated)
            in_sh = (p_sh, s_sh, xs_sh, masks_sh)
        fn = self._forward_fn()
        jitted = jax.jit(fn) if in_sh is None else \
            jax.jit(fn, in_shardings=in_sh)
        with self._tp_trace():
            return jitted.lower(params_avals, state_avals,
                                tuple(xs_avals), tuple(masks_avals))

    def _tp_trace(self):
        """Held for the duration of one trace/lower: on a mesh GSPMD
        partitions the program, so the kernel dispatchers route per-shard
        ``shard_map`` (decode, over the model axis) or the counted
        reference path, which GSPMD partitions, instead of tracing a
        Pallas kernel over sharded operands."""
        pl = self._placement_layer
        if pl is None:
            return contextlib.nullcontext()
        return _pk.gspmd_trace(pl.mesh, pl.model_axis)

    @staticmethod
    def _bucket_label(key: Tuple) -> str:
        return str([s for s, _ in key[0]])

    def _hit_cell(self, key: Tuple):
        """Bound ``serving.engine.bucket_hits`` cell for one compiled key
        (created on first use, cleared with ``_compiled``). Call under
        ``self._lock``."""
        cell = self._hit_cells.get(key)
        if cell is None:
            cell = self._hit_cells[key] = _M_BUCKET_HITS.labeled(
                engine=self._id, pool=self._pool_label,
                bucket=self._bucket_label(key))
        return cell

    def _get_compiled(self, xs_avals, masks_avals, _warmup=False):
        fp = self._params_placement()[0]
        key = self._key_of(xs_avals, masks_avals, fp)
        with self._lock:
            exe = self._compiled.get(key)
            if exe is not None:
                if not _warmup:
                    self._m_hits.inc()
                    self._hit_cell(key).inc()
                return exe
            # retrace tracker (ISSUE 6): attribute this lower+compile.
            # Priority: an armed invalidation cause (dtype_policy /
            # workspace_mode / ... — consumed once), else warmup, else a
            # known bucket shape re-compiling under a different params
            # placement, else a genuinely new bucket.
            aval_key = key[:2]
            stale = self._stale_causes.pop(aval_key, None)
            if stale is not None:
                cause = stale
                # the invalidation is now attributed; a later never-seen
                # shape is a genuine new_bucket, not this invalidation
                self._invalidate_cause = None
            elif self._invalidate_cause is not None:
                cause, self._invalidate_cause = self._invalidate_cause, None
            elif _warmup:
                cause = "warmup"
            elif aval_key in self._known_avals:
                cause = "params_placement"
            else:
                cause = "new_bucket"
            self._known_avals.add(aval_key)
            exe = self._lower_bucket(xs_avals, masks_avals).compile()
            self._compiled[key] = exe
            self._m_compiles.inc()
            _tel.record_compile("serving.engine", cause, engine=self._id,
                                bucket=self._bucket_label(key))
            if not _warmup:
                self._hit_cell(key).inc()
            return exe

    def _bucket_avals(self, b: int, t: Optional[int]):
        """(xs_avals, masks_avals) for one (batch bucket, seq bucket)."""
        dt = _dt.resolve(self.model.conf.dtype)
        dt = dt if np.issubdtype(dt, np.floating) else np.dtype(np.float32)
        xs_avals, masks_avals = [], []
        for shape, is_seq in zip(self._input_shapes, self._seq_input):
            if is_seq:
                xs_avals.append(jax.ShapeDtypeStruct((b, t, shape[1]), dt))
                masks_avals.append(jax.ShapeDtypeStruct((b, t), np.float32))
            else:
                xs_avals.append(jax.ShapeDtypeStruct((b,) + shape, dt))
                masks_avals.append(None)
        return xs_avals, masks_avals

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               seq_buckets: Optional[Sequence[int]] = None,
               bytes_limit: Optional[int] = None,
               checkpoint: Optional[str] = None) -> "InferenceEngine":
        """Compile every (batch bucket x seq bucket) executable now, via
        the AOT path — after this, requests whose padded shape lands on a
        warmed bucket never trigger a compile. ``seq_buckets`` applies to
        recurrent ([T, F]) inputs; defaults to the configured T when it is
        static, and is required when T is dynamic (-1).

        ``buckets="auto"``: autotune the ladder ceiling to the largest
        bucket whose serving program FITS the device ``bytes_limit``
        (:meth:`max_batch` — AOT memory accounting, no OOM probing);
        ``bytes_limit`` overrides the device's own limit (required on
        backends without ``memory_stats``).

        ``checkpoint=<dir>`` (ISSUE 17): restore the model from a pod
        ``TrainingCheckpointer`` directory first, so multi-host warmup is
        one call — restore host-side, place each host's addressable
        shards onto the serving mesh, AOT-compile every bucket."""
        if checkpoint is not None:
            _pl.load_checkpoint(self.model, checkpoint)
        if self._input_shapes is None:
            raise ValueError("model config has no input shapes "
                             "(input_type(...)); warmup cannot derive "
                             "avals — serve a request first or set shapes")
        if isinstance(buckets, str):
            if buckets != "auto":
                raise ValueError(f"unknown warmup bucket spec {buckets!r} "
                                 "(expected a list of sizes or 'auto')")
            top = self.max_batch(bytes_limit=bytes_limit,
                                 seq_buckets=seq_buckets)
            if top is None:
                raise ValueError(
                    "warmup(buckets='auto'): no bucket fits bytes_limit "
                    "(or this PJRT build exposes no memory_analysis)")
            buckets = default_buckets(top, minimum=self.min_bucket)
        if not buckets:
            # default ladder must reach min_bucket even past the 64 ceiling
            buckets = default_buckets(max(64, self.min_bucket),
                                      minimum=self.min_bucket)
        buckets = sorted(set(next_bucket(b, self.min_bucket)
                             for b in buckets))
        for b in buckets:
            for t in self._warmup_seq_lens(seq_buckets):
                xs_avals, masks_avals = self._bucket_avals(b, t)
                self._get_compiled(xs_avals, masks_avals, _warmup=True)
        return self

    def max_batch(self, bytes_limit: Optional[int] = None,
                  seq_buckets: Optional[Sequence[int]] = None,
                  limit: int = 4096, fraction: float = 1.0
                  ) -> Optional[int]:
        """Largest power-of-two batch bucket whose serving program fits in
        ``bytes_limit`` HBM across every seq bucket, found by AOT
        lower+compile + ``memory_analysis()`` (``nn/memory.py`` contract —
        nothing executes, so no OOM probing; probe compiles do NOT enter
        the executable cache or serving counters). ``bytes_limit`` defaults
        to the live device limit; pass it explicitly on backends without
        ``memory_stats``. Returns None when nothing fits or the PJRT build
        exposes no ``memory_analysis``."""
        from ..nn import memory as _memory
        if self._input_shapes is None:
            raise ValueError("model config has no input shapes "
                             "(input_type(...)); max_batch cannot derive "
                             "avals")
        if bytes_limit is None:
            dm = _memory.device_memory_stats()
            if not dm or not dm.get("bytes_limit"):
                raise ValueError(
                    "device reports no memory_stats()['bytes_limit'] — "
                    "pass bytes_limit= explicitly on this backend")
            bytes_limit = dm["bytes_limit"]
        budget = int(bytes_limit * fraction)

        def fits(b: int) -> Optional[bool]:
            for t in self._warmup_seq_lens(seq_buckets):
                xs_avals, masks_avals = self._bucket_avals(b, t)
                with self._lock:
                    # the SAME lowering the serving executables use (mesh
                    # in_shardings included) — per-device peak, per-device
                    # bytes_limit
                    compiled = self._lower_bucket(
                        xs_avals, masks_avals).compile()
                    # probes never enter the executable cache or serving
                    # counters, but the retrace tracker still sees every
                    # lower+compile so XLA compile time stays explainable
                    _tel.record_compile("serving.engine", "probe",
                                        engine=self._id,
                                        bucket=f"[{b}]", seq=t)
                cm = _memory.compiled_memory(compiled)
                if cm is None:
                    return None
                if cm["peak_bytes"] > budget:
                    return False
            return True

        best = None
        b = self.min_bucket
        while b <= limit:
            ok = fits(b)
            if ok is None or not ok:
                return best if ok is not None else None
            best = b
            b <<= 1
        return best

    def _warmup_seq_lens(self, seq_buckets):
        if not any(self._seq_input):
            return [None]
        if seq_buckets:
            return sorted(set(next_bucket(t) for t in seq_buckets))
        ts = [s[0] for s, q in zip(self._input_shapes, self._seq_input) if q]
        if any(t is None or t <= 0 for t in ts):
            raise ValueError("model has dynamic sequence length: pass "
                             "warmup(seq_buckets=[...])")
        return sorted(set(next_bucket(t) for t in ts))

    # -------------------------------------------------------------- dispatch
    def output(self, *inputs, lengths=None):
        """Run inference on a ragged-size request batch.

        ``inputs``: one array per model input, batch-first. ``lengths``:
        optional per-row true sequence lengths ``[B]`` for recurrent
        inputs (rows end-padded to a common T by a batcher) — padded
        steps are masked out of the computation exactly.

        Returns the unpadded output (list when the graph has several)."""
        xs = [np.asarray(x) for x in inputs]
        if self._input_shapes is not None and \
                len(xs) != len(self._input_shapes):
            raise ValueError(f"model takes {len(self._input_shapes)} "
                             f"inputs, got {len(xs)}")
        seq_flags = self._seq_input if self._seq_input is not None \
            else [False] * len(xs)
        n = xs[0].shape[0]
        dt = _dt.resolve(self.model.conf.dtype)
        b = next_bucket(n, self.min_bucket)
        self._m_calls.inc()
        if b != n:
            self._m_padded.inc(b - n)
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        xs_p, masks = [], []
        seq_lens = []
        for x, is_seq in zip(xs, seq_flags):
            if np.issubdtype(np.dtype(x.dtype), np.floating) and \
                    np.issubdtype(dt, np.floating) and x.dtype != dt:
                x = x.astype(dt)  # host-side: one executable per net dtype
            if is_seq:
                t = x.shape[1]
                tb = next_bucket(t)
                ln = np.full((n,), t, np.int64) if lengths is None \
                    else np.asarray(lengths)
                mask = (np.arange(tb)[None, :] <
                        ln[:, None]).astype(np.float32)
                if tb != t:
                    x = np.concatenate(
                        [x, np.zeros((n, tb - t) + x.shape[2:], x.dtype)],
                        axis=1)
                seq_lens.append((t, tb))
                if b != n:
                    x = np.concatenate(
                        [x, np.zeros((b - n,) + x.shape[1:], x.dtype)])
                    mask = np.concatenate(
                        [mask, np.zeros((b - n, tb), np.float32)])
                masks.append(mask)
            else:
                if b != n:
                    x = np.concatenate(
                        [x, np.zeros((b - n,) + x.shape[1:], x.dtype)])
                masks.append(None)
                seq_lens.append(None)
            xs_p.append(x)

        xs_avals = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs_p]
        masks_avals = [None if m is None else
                       jax.ShapeDtypeStruct(m.shape, m.dtype) for m in masks]
        # per-request tracing (ISSUE 13): when a dispatcher installed a
        # phase sink for this call, the same pad/execute/unpad durations
        # fan out into every member request's stitched timeline
        sink = _tel.phase_sink() if tel else None
        if tel:  # request-lifecycle phases: pad -> execute -> unpad.
            # pad ends BEFORE the executable lookup: a cold-bucket AOT
            # compile must read as a compile event, not as seconds of
            # "host padding" in this histogram
            d_pad = time.perf_counter() - t0
            self._h_pad.observe(d_pad)
            if sink is not None:
                sink("pad", d_pad)
        exe = self._get_compiled(xs_avals, masks_avals)
        if tel:
            t1 = time.perf_counter()
        params, state = self._place_params()
        if self.mesh is not None:
            xs_sh, masks_sh = self._shardings(xs_avals, masks_avals)
            xs_p = [jax.device_put(x, s) for x, s in zip(xs_p, xs_sh)]
            masks = [None if m is None else jax.device_put(m, s)
                     for m, s in zip(masks, masks_sh)]
        outs = exe(params, state, tuple(xs_p), tuple(masks))
        if tel:
            t2 = time.perf_counter()
            # np.asarray below syncs anyway; the execute phase measures
            # placement + dispatch (the transfer sync lands in unpad)
            self._h_exec.observe(t2 - t1)
            if sink is not None:
                sink("execute", t2 - t1)
        res = [self._unpad(np.asarray(o), n, seq_lens) for o in outs]
        if tel:
            d_unpad = time.perf_counter() - t2
            self._h_unpad.observe(d_unpad)
            if sink is not None:
                sink("unpad", d_unpad)
        return res if self._is_graph and len(res) > 1 else res[0]

    def _unpad(self, out, n, seq_lens):
        out = out[:n]
        # slice the time axis back only for per-timestep outputs whose
        # dim 1 matches the padded bucket EXACTLY ([B, T_bucket, ...]);
        # pooled heads ([B, C]) keep their shape. With several seq inputs
        # of DIFFERENT lengths the output↔input alignment is ambiguous —
        # return the padded time axis rather than guess and truncate.
        pairs = {p for p in seq_lens if p is not None}
        if len(pairs) == 1:
            t, tb = next(iter(pairs))
            if t != tb and out.ndim >= 3 and out.shape[1] == tb:
                out = out[:, :t]
        return out

    def _place_params(self):
        """Params/state ready for the executables — the placement layer's
        walk (ISSUE 17). Without a model axis, leaves already living on
        THIS mesh keep their sharding (a tensor-parallel leaf left behind
        by training stays sharded — replicating it would defeat TP and
        can OOM) and everything else replicates; with a TP mesh the
        layer's derived specs are forced (the AOT executables pin them as
        in_shardings). Re-placed once per params identity (fit() rebinds
        the dict, so identity tracks updates)."""
        model = self.model
        if self.mesh is None:
            return self._serving_params(), model.state
        return self._placement_layer.place(
            self._serving_params(), model.state,
            src=(model.params, model.state), keep_on_mesh=True)

    # ---------------------------------------------------------------- admin
    def invalidate(self, cause: str = "invalidate"):
        """Drop every compiled executable (model topology/dtype changed).
        ``cause`` (``dtype_policy`` / ``workspace_mode`` / ``init`` …)
        arms the retrace tracker: the rebuild of EVERY bucket that was
        warmed at invalidation time — and the next compile even for a
        never-seen shape — is attributed to this invalidation instead of
        reading as a mystery ``new_bucket``."""
        with self._lock:
            self._compiled.clear()
            self._hit_cells.clear()
            self._placed = None
            self._placed_params_src = None
            self._placement = None
            self._placement_src = None
            if self._placement_layer is not None:
                self._placement_layer.invalidate()
            self._invalidate_cause = cause
            # refresh EVERY pending stale entry too: a bucket invalidated
            # twice before its rebuild is attributed to the most recent
            # mutation, not the first one
            for ak in list(self._stale_causes) + list(self._known_avals):
                self._stale_causes[ak] = cause
            self._known_avals.clear()
            self._input_shapes = self._model_input_shapes()
            self._seq_input = [len(s) == 2 for s in self._input_shapes] \
                if self._input_shapes is not None else None

    # legacy counter attributes — views over the registry cells so every
    # pre-ISSUE-6 caller (tests, bench, ui listeners) keeps working
    @property
    def calls(self) -> int:
        return int(self._m_calls.value())

    @property
    def hits(self) -> int:
        return int(self._m_hits.value())

    @property
    def compiles(self) -> int:
        return int(self._m_compiles.value())

    @property
    def padded_rows(self) -> int:
        return int(self._m_padded.value())

    @property
    def bucket_hits(self) -> Dict[str, int]:
        out = {}
        for k, v in _M_BUCKET_HITS.series().items():
            labels = dict(k)
            if labels.get("engine") == self._id:
                out[labels["bucket"]] = int(v)
        return out

    def memory_report(self, bucket: int, seq_buckets=None) -> dict:
        """Compiled-HBM accounting of ONE serving bucket program (AOT
        lower+compile, nothing executes — ``nn/memory.py`` contract):
        ``memory_analysis`` fields plus the params-bytes split, so the
        quantized-vs-f32 weight and argument deltas are measured numbers
        (ISSUE 9 satellite). Probe compiles bypass the serving counters
        but still reach the retrace tracker (cause=``probe``)."""
        from ..nn import memory as _memory
        b = next_bucket(int(bucket), self.min_bucket)
        t = self._warmup_seq_lens(seq_buckets)[0]
        xs_avals, masks_avals = self._bucket_avals(b, t)
        with self._lock:
            compiled = self._lower_bucket(xs_avals, masks_avals).compile()
            _tel.record_compile("serving.engine", "probe",
                                engine=self._id, bucket=f"[{b}]")
        params = self._serving_params()
        total, qbytes = _q.quantized_bytes(params)
        report = {"bucket": b, "seq_len": t,
                  "quantize": self.quantize or "off",
                  "params_bytes": total,
                  "params_bytes_per_device": total,
                  "quantized_weight_bytes": qbytes,
                  "temp_bytes": None, "argument_bytes": None,
                  "output_bytes": None, "peak_bytes": None}
        pl = self._placement_layer
        if pl is not None:
            # ISSUE 17 satellite bugfix: under TP the per-device params
            # footprint is the SHARDED bytes, not the full tree — the
            # AOT memory_analysis above already accounts per-device
            # (the lowering pins the sharded in_shardings), and this
            # field makes the params split explicit
            report["params_bytes_per_device"] = _pl.tree_bytes_per_device(
                params, pl.param_shardings(params))
            report["tp_shards"] = pl.tp
            report["mesh"] = _pl.mesh_key(pl.mesh)
        cm = _memory.compiled_memory(compiled)
        if cm:
            report.update(cm)
        return report

    def attribution_report(self, bucket: int, seq_buckets=None,
                           measured_s: Optional[float] = None,
                           peaks=None) -> dict:
        """MFU attribution of ONE serving bucket program (ISSUE 13 —
        ``memory_report``'s roofline sibling): the AOT executable's
        ``cost_analysis()`` flops/bytes against this engine's measured
        per-call window — pad+execute+unpad p50s, with pad+unpad as the
        host seconds of that window. Serve (or warm and measure) traffic
        first, or pass ``measured_s`` explicitly — attribution without a
        measurement is a roofline estimate, flagged as such."""
        from ..runtime import attribution as _attr
        b = next_bucket(int(bucket), self.min_bucket)
        t = self._warmup_seq_lens(seq_buckets)[0]
        xs_avals, masks_avals = self._bucket_avals(b, t)
        fp = self._params_placement()[0]
        cache_key = self._key_of(xs_avals, masks_avals, fp)
        with self._lock:
            # reuse the warmed executable when the bucket is already
            # compiled; a cold bucket pays ONE probe compile and the
            # result is cached (it is byte-identical to the serving
            # executable, so this also pre-warms the bucket — the tuner
            # calls this repeatedly across configs)
            compiled = self._compiled.get(cache_key)
            if compiled is None:
                compiled = self._lower_bucket(xs_avals,
                                              masks_avals).compile()
                _tel.record_compile("serving.engine", "probe",
                                    engine=self._id, bucket=f"[{b}]")
                self._compiled[cache_key] = compiled
                self._known_avals.add(cache_key[:2])
            buckets_served = {k[0] for k in self._compiled}
        measurement_note = None
        host_s = None
        if measured_s is None:
            if len(buckets_served) > 1:
                # the phase histograms are labeled engine= only — with
                # several compiled bucket shapes their p50 BLENDS
                # buckets, and attributing bucket-b flops against a
                # mixed-bucket measurement would cache garbage for the
                # tuner. Degrade to a flagged roofline estimate instead.
                measurement_note = (
                    f"phase histograms blend {len(buckets_served)} "
                    "compiled bucket shapes; pass measured_s for this "
                    "bucket explicitly")
            else:
                # the measured window is the WHOLE engine call (pad +
                # execute + unpad), so the host phases are a subset of
                # it — carving host_s out of an execute-only window
                # would mis-attribute device time as host time
                ex = self._h_exec.percentile(50)
                pad = self._h_pad.percentile(50)
                unpad = self._h_unpad.percentile(50)
                if ex is not None:
                    host_s = (pad or 0.0) + (unpad or 0.0)
                    measured_s = ex + host_s
        # mesh-placed programs key their mesh shape + TP size into the
        # attribution cache (the r18 fingerprint-key rule): a TP decode
        # fraction must never seed — or be seeded by — a single-device one
        key = (f"serving.engine:{type(self.model).__name__}:"
               f"b{b}xt{t}:{self.quantize or 'f32'}")
        if self._placement_layer is not None:
            key += f":{self._placement_layer.suffix()}"
        rep = _attr.attribute_compiled(
            compiled, measured_s=measured_s, host_s=host_s, peaks=peaks,
            key=key)
        if measurement_note is not None:
            rep["measurement_note"] = measurement_note
        rep.update({"kind": "serving_bucket", "bucket": b, "seq_len": t,
                    "quantize": self.quantize or "off"})
        return rep

    def stats(self) -> dict:
        with self._lock:
            buckets = len(self._compiled)
        out = {
            "calls": self.calls,
            "hits": self.hits,
            "compiles": self.compiles,
            "padded_rows": self.padded_rows,
            "compiled_buckets": buckets,
            "bucket_hits": self.bucket_hits,
        }
        out.update(self._quantize_stats())
        return out


class DecodeState:
    """The live state of one in-flight decode batch: per-layer KV caches
    at the current cache-length bucket, plus per-slot valid lengths.
    Owned by the continuous batcher; every engine call is functional
    (state in, state out) so a failed dispatch never half-mutates it."""

    __slots__ = ("caches", "lengths", "cache_len")

    def __init__(self, caches, lengths, cache_len: int):
        self.caches = caches          # {layer: {"k": [S,H,C,d], "v": ...}}
        self.lengths = lengths        # [S] int32 device array
        self.cache_len = int(cache_len)


class HorizonChain:
    """Device-carried loop state between chained decode horizons
    (ISSUE 19): the next-step features, the live mask, the advanced
    lengths, and the threaded PRNG key — everything horizon i+1 needs to
    dispatch WITHOUT the host reading horizon i back first. All four are
    device arrays straight out of the previous executable call."""

    __slots__ = ("x_t", "active", "lengths", "key")

    def __init__(self, x_t, active, lengths, key):
        self.x_t = x_t
        self.active = active
        self.lengths = lengths
        self.key = key


class HorizonResult:
    """One in-flight multi-token decode horizon (ISSUE 19).

    ``toks``/``logits``/``actives`` are DEVICE arrays of shape
    ``[kmax, slots]`` / ``[kmax, slots, V]`` / ``[kmax, slots]`` where
    ``kmax >= k`` is the serving executable's capacity (rows ``>= k``
    are zero) — JAX's async dispatch means the executable call returned
    before the device finished, so the batcher can dispatch horizon i+1
    (via ``chain``) and run its host-side emission of horizon i-1 while
    this one computes. :meth:`fetch` is the single blocking device->host
    readback per horizon — one sync per k tokens instead of one per
    token. ``actives[j, s] == 1`` iff slot ``s`` really emitted token j
    (EOS mid-horizon or ``j >= k`` freezes the tail — per-slot emission
    is always a prefix; tail tokens/logits are garbage by the same
    contract as inactive decode rows)."""

    __slots__ = ("k", "chain", "_toks", "_logits", "_actives", "_eng",
                 "_t0", "_cached")

    def __init__(self, toks, logits, actives, chain, k, eng, t0):
        self._toks = toks
        self._logits = logits
        self._actives = actives
        self.chain = chain
        self.k = int(k)
        self._eng = eng
        self._t0 = t0
        self._cached = None

    def fetch(self):
        """Block until the horizon's device work completes and return
        host ``(toks [k, S], logits [k, S, V], actives [k, S])`` numpy.
        Observes ``serving.phase.decode_step_s`` once per horizon
        (dispatch -> readback-complete) on first call; idempotent."""
        if self._cached is None:
            out = (np.asarray(self._toks), np.asarray(self._logits),
                   np.asarray(self._actives))
            if self._t0 is not None and self._eng is not None:
                self._eng._h_decode.observe(time.perf_counter() - self._t0)
            self._cached = out
        return self._cached


class GenerativeEngine(_QuantizedParamsMixin):
    """Bucketed AOT-compiled autoregressive decode for one model
    (ISSUE 8 tentpole, layer 2): the generative sibling of
    :class:`InferenceEngine`, compiled per (slot-batch bucket x
    cache-length bucket x prompt-length bucket).

    - ``slots``: the decode batch capacity — every decode executable runs
      the full slot batch, so join/leave at token boundaries never
      changes a compiled shape (the continuous-batching contract).
    - ``prefill``: one admitted request's prompt fills its slot's cache
      rows via the one-shot flash kernel (prefix-LM: the prompt attends
      bidirectionally over itself) and returns the last valid position's
      logits — the first generated token's distribution.
    - ``decode``: one token for every slot in ONE executable call;
      inactive slots compute masked garbage that the active-mask keeps
      out of the persistent state (row independence is what lets
      requests join/leave without perturbing neighbours).
    - cache growth: crossing a power-of-two cache boundary re-buckets by
      host-side zero-padding (``grow``) — no compile, so a warmed bucket
      ladder keeps the steady state at zero post-warmup compiles.

    Counters/phases ride the same registry families as the one-shot
    engine (``serving.engine.*`` labeled ``engine=<id>``), plus
    ``serving.phase.prefill_s`` / ``serving.phase.decode_step_s``.

    ISSUE 9: ``quantize="int8"`` compiles every prefill/decode
    executable over the per-channel int8 params tree (quantized once at
    warmup, same contract as the one-shot engine); ``kv_cache="int8"``
    stores the KV buckets as int8 with per-row f32 scales beside them
    (``cache_insert`` quantizes on append) — half the cache HBM per
    slot, which composes with continuous batching to roughly double
    decode slot capacity per the r9 accounting.
    """

    def __init__(self, model, slots: int = 8,
                 quantize: Optional[str] = None,
                 kv_cache: Optional[str] = None,
                 mesh=None, data_axis: str = "data",
                 model_axis: Optional[str] = "model",
                 pool_label: str = "default"):
        self.model = model
        self.slots = int(slots)
        self._pool_label = str(pool_label)
        if kv_cache not in (None, "int8"):
            raise ValueError(f"unknown kv_cache mode {kv_cache!r} "
                             "(expected None or 'int8')")
        self.kv_cache = kv_cache
        # ISSUE 17: tensor-parallel decode over a pod mesh — params
        # shard by the placement layer's TP specs, the KV caches shard
        # their head axis, the slot batch replicates (per-slot rows are
        # the continuous batcher's join/leave unit, not a data shard)
        self.mesh = mesh
        self._placement_layer = None
        if mesh is not None:
            self._placement_layer = _pl.ParamsPlacement(
                mesh, model=model, model_axis=model_axis,
                data_axis=data_axis)
        self._compiled: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._invalidate_cause: Optional[str] = None
        self._known: set = set()
        self._id = str(next(_engine_ids))
        weakref.finalize(self, _tel.registry.discard_cells, engine=self._id)
        self._init_quantize(quantize)
        self._bind_quantize_cells()
        _pool = self._pool_label
        self._g_q_kv = _G_Q_KV.labeled(engine=self._id, pool=_pool)
        self._m_calls = _M_CALLS.labeled(engine=self._id, pool=_pool)
        self._m_hits = _M_HITS.labeled(engine=self._id, pool=_pool)
        self._m_compiles = _M_COMPILES.labeled(engine=self._id, pool=_pool)
        self._h_prefill = _H_PREFILL.labeled(engine=self._id, pool=_pool)
        self._h_decode = _H_DECODE.labeled(engine=self._id, pool=_pool)
        self._h_kv_export = _H_KV_EXPORT.labeled(engine=self._id,
                                                 pool=_pool)
        self._h_kv_import = _H_KV_IMPORT.labeled(engine=self._id,
                                                 pool=_pool)
        if self._placement_layer is not None:
            _G_TP_SHARDS.labeled(
                engine=self._id, mesh=_pl.mesh_key(mesh),
                pool=_pool,
            ).set(self._placement_layer.tp)
        try:
            if not hasattr(model, "_serving_engines"):
                model._serving_engines = weakref.WeakSet()
            model._serving_engines.add(self)
        except (AttributeError, TypeError):
            pass
        # the env pin disables KV quantization along with the weights —
        # one switch kills the whole int8 surface for CI. Frozen at
        # construction: the cache avals are baked into every executable,
        # so a mid-life mode flip must not flap them.
        self._kv_quant = kv_cache == "int8" and _q.mode() != "off"
        if kv_cache == "int8" and not self._kv_quant:
            self._m_q_fallback.inc()
            log.warning("DL4J_TPU_QUANT=off: kv_cache='int8' request "
                        "serves float caches")
        # trace-time sanity: an un-decodable stack should fail at
        # construction, not at the first warmup compile
        model.decode_cache_spec(1, 8, kv_quant=self._kv_quant)

    # ---------------------------------------------------------- state blobs
    def cache_bytes(self, cache_len: int, per_device: bool = False) -> int:
        """Decode-cache bytes at one bucket for the full slot batch —
        the quantity ``kv_cache="int8"`` halves (the measured basis of
        the "~2x decode slot capacity" claim; surfaced per state via the
        ``serving.quantize.kv_bytes`` gauge). ``per_device=True`` under a
        TP mesh divides head-sharded leaves by the model-axis size —
        each device holds H/k heads' rows (ISSUE 17)."""
        c = next_bucket(cache_len)
        spec = self.model.decode_cache_spec(self.slots, c,
                                            kv_quant=self._kv_quant)
        if per_device and self._placement_layer is not None:
            return _pl.tree_bytes_per_device(
                spec, self._placement_layer.cache_shardings(spec))
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(spec))

    def new_state(self, cache_len: int) -> DecodeState:
        """Fresh zeroed decode state at the given cache bucket."""
        c = next_bucket(cache_len)
        caches = self.model.init_decode_cache(self.slots, c,
                                              kv_quant=self._kv_quant)
        lengths = jnp.zeros((self.slots,), jnp.int32)
        if self.mesh is not None:
            pl = self._placement_layer
            caches = _pl.put_tree(caches, pl.cache_shardings(caches))
            lengths = _pl.put_full(np.zeros((self.slots,), np.int32),
                                   pl.replicated())
        self._g_q_kv.set(self.cache_bytes(c))
        return DecodeState(caches, lengths, c)

    def grow(self, state: DecodeState, cache_len: int) -> DecodeState:
        """Re-bucket the caches to a larger power-of-two length by
        HOST-side zero padding (``np.pad`` + device_put — no trace, no
        compile event; growth happens O(log T) times per sequence).
        Existing entries are preserved exactly (bit-parity tested)."""
        c2 = next_bucket(cache_len)
        if c2 <= state.cache_len:
            return state
        pad = c2 - state.cache_len

        def grow_leaf(a):
            # every cache leaf is [S, H, C, d] with C on axis 2 — the
            # int8 value buckets AND their [S, H, C, 1] scale buckets
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                raise RuntimeError(
                    "contiguous-cache grow() cannot host-gather a "
                    "multi-host sharded cache; warm a fixed cache bucket "
                    "(min == max) or serve through PagedGenerativeEngine "
                    "(its grow is a host page-table bump)")
            sh = a.sharding if isinstance(a, jax.Array) and \
                self.mesh is not None else None
            h = np.asarray(a)
            padded = np.pad(h, [(0, 0), (0, 0), (0, pad), (0, 0)])
            if sh is not None:
                # pad axis 2 is replicated in the cache spec, so the
                # original head sharding carries over unchanged
                return _pl.put_full(padded, sh)
            return jax.device_put(padded)

        self._g_q_kv.set(self.cache_bytes(c2))
        return DecodeState(jax.tree.map(grow_leaf, state.caches),
                           state.lengths, c2)

    # ----------------------------------------------------------- compilation
    def _params_avals(self):
        # quantized serving: the executables are compiled over (and fed)
        # the int8 params tree — same contract as the one-shot engine.
        # Materialized OUTSIDE eval_shape (tracing the quantize walk
        # would cache tracer arrays in the params source).
        serving_params = self._serving_params()
        return (jax.eval_shape(lambda: serving_params),
                jax.eval_shape(lambda: self.model.state))

    def _place_params(self):
        """Params/state ready for the executables (the placement layer's
        identity-cached TP walk when a mesh is configured — ISSUE 17)."""
        if self.mesh is None:
            return self._serving_params(), self.model.state
        return self._placement_layer.place(
            self._serving_params(), self.model.state,
            src=(self.model.params, self.model.state))

    def _tp_trace(self):
        """Held while one decode-family executable traces on a mesh
        (per-shard ``shard_map`` over the model axis, or the counted
        reference path that GSPMD partitions — zero silent fallbacks)."""
        pl = self._placement_layer
        if pl is None:
            return contextlib.nullcontext()
        return _pk.gspmd_trace(pl.mesh, pl.model_axis)

    def _tp_shardings(self, cache_avals):
        """(params, state, caches, replicated) sharding trees for one
        executable's in/out pinning: params by TP spec, KV caches
        head-sharded H/k per device, everything small replicated."""
        pl = self._placement_layer
        return (pl.param_shardings(self._serving_params()),
                pl.state_shardings(self.model.state),
                pl.cache_shardings(cache_avals),
                pl.replicated())

    def _put_arg(self, a):
        """Per-call small arguments (token windows, lengths, page
        tables): replicated onto the mesh — explicit, because multi-host
        AOT executables cannot place host numpy themselves."""
        if self.mesh is None:
            return a
        return _pl.put_full(np.asarray(a), self._placement_layer.replicated())

    def _feature_dim(self) -> int:
        shapes = self.model.conf.input_shape
        if shapes is None or len(shapes) != 2:
            raise ValueError("generative serving needs a recurrent "
                             "([T, F]) input_type on the model config")
        return int(shapes[1])

    def _get_compiled(self, key: Tuple, build, _warmup=False):
        with self._lock:
            exe = self._compiled.get(key)
            if exe is not None:
                if not _warmup:
                    self._m_hits.inc()
                return exe
            if self._invalidate_cause is not None:
                cause, self._invalidate_cause = self._invalidate_cause, None
            elif _warmup:
                cause = "warmup"
            else:
                cause = "new_bucket"
            exe = build().compile()
            self._compiled[key] = exe
            self._known.add(key)
            self._m_compiles.inc()
            _tel.record_compile("serving.engine", cause, engine=self._id,
                                bucket=str(list(key)))
            return exe

    def _prefill_exe(self, tp: int, c: int, _warmup=False):
        model = self.model
        S = self.slots
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)

        kv_quant = self._kv_quant

        def fn(params, mstate, caches, lengths, x, plen, slot):
            mini = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                model.decode_cache_spec(1, c, kv_quant=kv_quant))
            y, mini = model._prefill(params, x, mstate, mini, plen[None])
            d = y.shape[-1]
            logits = jax.lax.dynamic_slice(
                y, (0, plen - 1, 0), (1, 1, d))[0, 0]
            caches = jax.tree.map(
                lambda cc, m: jax.lax.dynamic_update_slice(
                    cc, m.astype(cc.dtype), (slot, 0, 0, 0)),
                caches, mini)
            lengths = jax.lax.dynamic_update_slice(
                lengths, plen[None].astype(lengths.dtype), (slot,))
            return caches, lengths, logits

        def build():
            p_avals, s_avals = self._params_avals()
            cache_avals = model.decode_cache_spec(S, c, kv_quant=kv_quant)
            jkw = {}
            if self.mesh is not None:
                p_sh, s_sh, c_sh, repl = self._tp_shardings(cache_avals)
                jkw["in_shardings"] = (p_sh, s_sh, c_sh, repl, repl,
                                       repl, repl)
                jkw["out_shardings"] = (c_sh, repl, repl)
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(
                    p_avals, s_avals, cache_avals,
                    jax.ShapeDtypeStruct((S,), jnp.int32),
                    jax.ShapeDtypeStruct((1, tp, f), dt),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))

        return self._get_compiled(("prefill", tp, c), build, _warmup)

    def _decode_exe(self, c: int, _warmup=False):
        model = self.model
        S = self.slots
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)
        kv_quant = self._kv_quant

        def fn(params, mstate, caches, lengths, x_t, active):
            # the active mask gates the cache WRITE inside cache_insert
            # (an O(slots*d) gathered no-op for inactive rows) — no
            # full-cache select pass; inactive rows' logits are garbage
            # the batcher never reads
            y, caches = model._decode_step(params, x_t, mstate, caches,
                                           lengths, write=active)
            lengths = lengths + active.astype(lengths.dtype)
            return caches, lengths, y[:, 0]

        def build():
            p_avals, s_avals = self._params_avals()
            cache_avals = model.decode_cache_spec(S, c, kv_quant=kv_quant)
            # the caches are DONATED: XLA aliases the in/out buffers so
            # the per-token hot path updates the HBM cache in place
            # instead of copying O(slots x C) bytes every iteration
            # (~40% of CPU decode-step time at C=128). The caller must
            # treat the passed DecodeState as consumed — the batcher
            # rebuilds fresh state if a decode dispatch ever throws.
            jkw = {"donate_argnums": (2,)}
            if self.mesh is not None:
                p_sh, s_sh, c_sh, repl = self._tp_shardings(cache_avals)
                jkw["in_shardings"] = (p_sh, s_sh, c_sh, repl, repl, repl)
                # caches keep their head sharding so donation aliases
                # the sharded buffers in place
                jkw["out_shardings"] = (c_sh, repl, repl)
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(
                    p_avals, s_avals, cache_avals,
                    jax.ShapeDtypeStruct((S,), jnp.int32),
                    jax.ShapeDtypeStruct((S, 1, f), dt),
                    jax.ShapeDtypeStruct((S,), jnp.int32))

        return self._get_compiled(("decode", c), build, _warmup)

    def _decode_multi_parts(self, c: int, kmax: int,
                            spec: _smp.SamplingSpec):
        """(fn, avals, cache_avals) for one multi-token horizon program
        (ISSUE 19 tentpole): a ``lax.fori_loop`` over ``k <= kmax``
        decode iterations — ``k`` is a RUNTIME scalar argument, so ONE
        compiled program per cache bucket serves EVERY horizon the
        scheduler picks (exact budget caps, k=1 under queue pressure)
        at zero post-warmup compiles. Samples on-device, featurizes the
        token through the model's embedding path on-device, and
        write-gates EOS-frozen slots — the logits never touch the host
        inside the horizon. The token/logits/emitted outputs are fixed
        ``[kmax, ...]`` buffers; rows ``>= k`` stay zero, so ``emitted``
        is a per-slot prefix mask whatever k ran. Shared by
        :meth:`_decode_multi_exe` and the staticcheck decode probe so
        ``make lint`` audits EXACTLY what serving runs."""
        model = self.model
        S = self.slots
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)
        kv_quant = self._kv_quant
        sample = spec.build()
        stochastic = spec.stochastic

        p_avals, s_avals = self._params_avals()
        cache_avals = model.decode_cache_spec(S, c, kv_quant=kv_quant)
        len_aval = jax.ShapeDtypeStruct((S,), jnp.int32)
        x_aval = jax.ShapeDtypeStruct((S, 1, f), dt)
        i32_aval = jax.ShapeDtypeStruct((S,), jnp.int32)
        # the loop carry must be shape-stable, so the output buffers are
        # allocated [kmax, ...] up front — which needs the logits dim
        # before tracing the body
        y_aval = jax.eval_shape(
            lambda p, m, cc, ll, xx, aa: model._decode_step(
                p, xx, m, cc, ll, write=aa)[0],
            p_avals, s_avals, cache_avals, len_aval, x_aval, i32_aval)
        V, ldt = int(y_aval.shape[-1]), y_aval.dtype

        def fn(params, mstate, caches, lengths, x_t, active, cap,
               eos_ids, temp, key, k):
            # cap: host-known budget exhaustion (max_new) the device
            # cannot detect — ANDed once so chained horizons stop
            # writing rows whose request already hit its token budget
            active = active * cap

            def body(i, carry):
                caches, lengths, x_t, active, key, toks, lgs, ems = carry
                if stochastic:
                    key, sub = jax.random.split(key)
                else:
                    sub = key
                y, caches = model._decode_step(params, x_t, mstate,
                                               caches, lengths,
                                               write=active)
                logits = y[:, 0]
                tok = sample(logits, sub, temp)
                emitted = active
                lengths = lengths + active.astype(lengths.dtype)
                # EOS freezes the slot for the REST of the horizon: the
                # EOS token itself is still emitted (emitted = pre-step
                # active), subsequent iterations write-gate the row so
                # its cache stays bit-identical to the host oracle's
                active = active * (1 - _smp.eos_hit(tok, eos_ids))
                x_t = model.decode_token_features(tok, dtype=dt)
                toks = jax.lax.dynamic_update_index_in_dim(
                    toks, tok.astype(jnp.int32), i, 0)
                lgs = jax.lax.dynamic_update_index_in_dim(
                    lgs, logits.astype(ldt), i, 0)
                ems = jax.lax.dynamic_update_index_in_dim(
                    ems, emitted, i, 0)
                return (caches, lengths, x_t, active, key,
                        toks, lgs, ems)

            init = (caches, lengths, x_t, active, key,
                    jnp.zeros((kmax, S), jnp.int32),
                    jnp.zeros((kmax, S, V), ldt),
                    jnp.zeros((kmax, S), jnp.int32))
            (caches, lengths, x_t, active, key,
             toks, logits, emitted) = jax.lax.fori_loop(0, k, body, init)
            return (caches, lengths, x_t, active, key,
                    toks, logits, emitted)

        avals = (p_avals, s_avals, cache_avals,
                 len_aval, x_aval, i32_aval, i32_aval, i32_aval,
                 jax.ShapeDtypeStruct((), jnp.float32),
                 jax.ShapeDtypeStruct((2,), jnp.uint32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return fn, avals, cache_avals

    def decode_multi_traceable(self, cache_len: int, k: int,
                               sampling: _smp.SamplingSpec = _smp.GREEDY):
        """(fn, avals) of the horizon program (``k`` = its kmax) — the
        staticcheck ``no-host-callback-in-decode`` jaxpr audit traces
        this."""
        c = next_bucket(int(cache_len))
        fn, avals, _ = self._decode_multi_parts(c, int(k), sampling)
        return fn, avals

    def _decode_multi_exe(self, c: int, kmax: int,
                          spec: _smp.SamplingSpec, _warmup=False):
        def build():
            fn, avals, cache_avals = self._decode_multi_parts(
                c, kmax, spec)
            # caches donated exactly like the single-step path — the
            # loop's carry updates the HBM cache in place per iteration
            jkw = {"donate_argnums": (2,)}
            if self.mesh is not None:
                p_sh, s_sh, c_sh, repl = self._tp_shardings(cache_avals)
                jkw["in_shardings"] = (p_sh, s_sh, c_sh) + (repl,) * 8
                jkw["out_shardings"] = (c_sh,) + (repl,) * 7
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(*avals)

        return self._get_compiled(
            ("decode_multi", c, kmax) + spec.static_key(), build, _warmup)

    def warmup(self, cache_buckets: Sequence[int],
               prompt_buckets: Sequence[int],
               checkpoint: Optional[str] = None,
               horizons: Sequence[int] = (),
               sampling: _smp.SamplingSpec = _smp.GREEDY
               ) -> "GenerativeEngine":
        """Compile every (prompt bucket x cache bucket) prefill and every
        cache-bucket decode executable outside traffic. After this, a
        generation whose prompt and total length stay within the warmed
        ladders never compiles (asserted by the bench/tier-1 suite).
        ``checkpoint=<dir>`` restores the model from a pod
        ``TrainingCheckpointer`` directory first (multi-host AOT warmup
        in one call — ISSUE 17). ``horizons`` (ISSUE 19): additionally
        compile the fused multi-token decode program per (cache bucket
        x horizon CAPACITY) under ``sampling`` — k is a runtime scalar,
        so warming just ``(max_horizon,)`` covers every adaptive k the
        scheduler can pick at zero post-warmup compiles."""
        if checkpoint is not None:
            _pl.load_checkpoint(self.model, checkpoint)
        cs = sorted(set(next_bucket(c) for c in cache_buckets))
        tps = sorted(set(next_bucket(t) for t in prompt_buckets))
        hs = sorted({int(h) for h in horizons if int(h) >= 1})
        for c in cs:
            if not hs:
                # a horizon front NEVER dispatches the single-step
                # program (k=1 rides the same kmax executable), so its
                # compile would be pure warmup wall-time; host-loop /
                # speculative fronts (horizons=()) still warm it
                self._decode_exe(c, _warmup=True)
            for h in hs:
                self._decode_multi_exe(c, h, sampling, _warmup=True)
            for tp in tps:
                if tp <= c:
                    self._prefill_exe(tp, c, _warmup=True)
        return self

    # -------------------------------------------------------------- dispatch
    def prefill(self, state: DecodeState, x, plen: int, slot: int):
        """Fill ``slot`` from one request's prompt. ``x``: [T, F] or
        [1, T, F] (host array; end-padded to the prompt bucket here);
        ``plen``: the true prompt length. Returns
        ``(state', logits [V])`` — the logits sample the FIRST generated
        token."""
        x = np.asarray(x)
        if x.ndim == 2:
            x = x[None]
        dt = _dt.resolve(self.model.conf.dtype)
        if np.issubdtype(x.dtype, np.floating) and x.dtype != dt:
            x = x.astype(dt)
        # pad to the smallest WARMED prompt bucket for this cache bucket
        # (a 3-token prompt lands on the warmed 16-bucket instead of
        # compiling a cold 4-bucket under traffic); next_bucket only when
        # nothing warmed fits
        with self._lock:
            warmed = sorted(k[1] for k in self._compiled
                            if k[0] == "prefill" and k[2] == state.cache_len
                            and k[1] >= x.shape[1])
        tp = warmed[0] if warmed else next_bucket(x.shape[1])
        if tp != x.shape[1]:
            x = np.concatenate(
                [x, np.zeros((1, tp - x.shape[1]) + x.shape[2:], x.dtype)],
                axis=1)
        if tp > state.cache_len:
            raise ValueError(f"prompt bucket {tp} exceeds the cache bucket "
                             f"{state.cache_len}; grow() first")
        self._m_calls.inc()
        exe = self._prefill_exe(tp, state.cache_len)
        params, mstate = self._place_params()
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        caches, lengths, logits = exe(
            params, mstate, state.caches, state.lengths,
            self._put_arg(x), self._put_arg(np.int32(plen)),
            self._put_arg(np.int32(slot)))
        logits = np.asarray(logits)
        if tel:
            self._h_prefill.observe(time.perf_counter() - t0)
        return DecodeState(caches, lengths, state.cache_len), logits

    def decode(self, state: DecodeState, x_t, active):
        """One token for every slot: ``x_t`` [S, 1, F] (inactive rows are
        ignored), ``active`` [S] 0/1. Returns ``(state', logits [S, V])``
        — inactive rows' logits are garbage by contract."""
        x_t = np.asarray(x_t)
        dt = _dt.resolve(self.model.conf.dtype)
        if np.issubdtype(x_t.dtype, np.floating) and x_t.dtype != dt:
            x_t = x_t.astype(dt)
        self._m_calls.inc()
        exe = self._decode_exe(state.cache_len)
        params, mstate = self._place_params()
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        caches, lengths, logits = exe(
            params, mstate, state.caches, state.lengths,
            self._put_arg(x_t),
            self._put_arg(np.asarray(active, np.int32)))
        logits = np.asarray(logits)
        if tel:
            self._h_decode.observe(time.perf_counter() - t0)
        return DecodeState(caches, lengths, state.cache_len), logits

    def _horizon_args(self, k, active_cap, eos_ids, sampling, key):
        S = self.slots
        cap = np.ones((S,), np.int32) if active_cap is None \
            else np.asarray(active_cap, np.int32)
        eos = np.full((S,), -1, np.int32) if eos_ids is None \
            else np.asarray(eos_ids, np.int32)
        temp = np.float32(sampling.temperature)
        if key is None:
            key = np.zeros((2,), np.uint32) if not sampling.stochastic \
                else np.asarray(jax.random.PRNGKey(0), np.uint32)
        if isinstance(key, jax.Array):
            # a chained device key: hand it straight to the executable —
            # np.asarray here would block on the in-flight horizon.
            key_arg = key
        else:
            key_arg = self._put_arg(np.asarray(key, np.uint32))
        return (self._put_arg(cap), self._put_arg(eos),
                self._put_arg(temp), key_arg)

    def _cast_x(self, x_t):
        x_t = np.asarray(x_t)
        dt = _dt.resolve(self.model.conf.dtype)
        if np.issubdtype(x_t.dtype, np.floating) and x_t.dtype != dt:
            x_t = x_t.astype(dt)
        return x_t

    def decode_multi(self, state: DecodeState, x_t, active, k: int, *,
                     eos_ids=None, active_cap=None,
                     sampling: _smp.SamplingSpec = _smp.GREEDY,
                     key=None, chain: Optional[HorizonChain] = None):
        """k tokens for every slot in ONE dispatch (ISSUE 19 tentpole):
        sample/featurize/EOS-freeze on-device; returns
        ``(state', HorizonResult)`` WITHOUT blocking — the caller reads
        tokens back via ``result.fetch()`` (one sync per horizon) and
        may dispatch the next horizon first from ``result.chain``
        (double-buffering). ``eos_ids`` [S] int32 per-slot EOS (-1 =
        none); ``active_cap`` [S] 0/1 host-known budget gate ANDed into
        the live mask; ``chain`` reuses the previous horizon's
        device-carried x_t/active/key so chained dispatch never touches
        the host. The passed state is CONSUMED (caches donated).

        k is a RUNTIME scalar of the compiled program: any warmed
        executable whose capacity kmax >= k serves the dispatch (the
        smallest such, mirroring prefill's warmed-bucket pick), so an
        exact budget-capped k never compiles post-warmup; only a k
        beyond every warmed capacity compiles a new kmax=k program
        (counted by ``compiles`` like any cold bucket)."""
        k = int(k)
        with self._lock:
            warmed = sorted(
                kk[2] for kk in self._compiled
                if kk[0] == "decode_multi" and kk[1] == state.cache_len
                and kk[2] >= k and tuple(kk[3:]) == sampling.static_key())
        kmax = warmed[0] if warmed else k
        exe = self._decode_multi_exe(state.cache_len, kmax, sampling)
        self._m_calls.inc()
        params, mstate = self._place_params()
        cap, eos, temp, key_arg = self._horizon_args(
            k, active_cap, eos_ids, sampling, key)
        if chain is not None:
            x_arg, a_arg, key_arg = chain.x_t, chain.active, chain.key
        else:
            x_arg = self._put_arg(self._cast_x(x_t))
            a_arg = self._put_arg(np.asarray(active, np.int32))
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else None
        caches, lengths, x2, a2, k2, toks, logits, emitted = exe(
            params, mstate, state.caches, state.lengths, x_arg, a_arg,
            cap, eos, temp, key_arg, self._put_arg(np.int32(k)))
        state2 = DecodeState(caches, lengths, state.cache_len)
        ch = HorizonChain(x2, a2, lengths, k2)
        return state2, HorizonResult(toks, logits, emitted, ch, k,
                                     self, t0)

    # ---------------------------------------------------------------- admin
    def invalidate(self, cause: str = "invalidate"):
        with self._lock:
            self._compiled.clear()
            if self._placement_layer is not None:
                self._placement_layer.invalidate()
            self._invalidate_cause = cause

    @property
    def calls(self) -> int:
        return int(self._m_calls.value())

    @property
    def hits(self) -> int:
        return int(self._m_hits.value())

    @property
    def compiles(self) -> int:
        return int(self._m_compiles.value())

    def stats(self) -> dict:
        with self._lock:
            buckets = len(self._compiled)
        out = {"calls": self.calls, "hits": self.hits,
               "compiles": self.compiles, "compiled_buckets": buckets,
               "slots": self.slots,
               "kv_cache": self.kv_cache if self._kv_quant else "off"}
        if self._placement_layer is not None:
            out["mesh"] = _pl.mesh_key(self.mesh)
            out["tp_shards"] = self._placement_layer.tp
        out.update(self._quantize_stats())
        return out

    def attribution_report(self, cache_len: int,
                           measured_s: Optional[float] = None,
                           peaks=None, horizon: Optional[int] = None,
                           host_s: Optional[float] = None) -> dict:
        """MFU attribution of the decode-step program at one cache bucket
        (ISSUE 13): ``cost_analysis()`` of the full-slot-batch decode
        executable vs the measured ``serving.phase.decode_step_s`` p50
        for this engine. Warm/serve first or pass ``measured_s``.
        ``horizon=k`` (ISSUE 19) attributes the fused k-token greedy
        horizon program instead; ``host_s`` feeds the measured host-side
        share of each step so the report's host fraction tracks what the
        horizon runtime actually eliminated."""
        from ..runtime import attribution as _attr
        c = next_bucket(int(cache_len))
        if horizon:
            exe = self._decode_multi_exe(c, int(horizon), _smp.GREEDY,
                                         _warmup=True)
        else:
            exe = self._decode_exe(c, _warmup=True)
        measurement_note = None
        if measured_s is None:
            with self._lock:
                decode_buckets = {k for k in self._compiled
                                  if k[0] == "decode"}
            if len(decode_buckets) > 1:
                # same anti-blending rule as the one-shot engine: the
                # decode histogram is per-engine, not per-cache-bucket
                measurement_note = (
                    f"decode histogram blends {len(decode_buckets)} "
                    "cache buckets; pass measured_s for this bucket "
                    "explicitly")
            else:
                measured_s = self._h_decode.percentile(50)
        # r18 fingerprint-key rule (ISSUE 17 satellite): a TP decode
        # step's cached fractions never blend with single-device ones
        key = (f"serving.decode:{type(self.model).__name__}:"
               f"s{self.slots}xc{c}:{self.quantize or 'f32'}")
        if horizon:
            key += f":h{int(horizon)}"
        if self._placement_layer is not None:
            key += f":{self._placement_layer.suffix()}"
        rep = _attr.attribute_compiled(
            exe, measured_s=measured_s, host_s=host_s, peaks=peaks,
            key=key)
        if measurement_note is not None:
            rep["measurement_note"] = measurement_note
        rep.update({"kind": "decode_step", "cache_len": c,
                    "slots": self.slots})
        if horizon:
            rep["horizon"] = int(horizon)
        return rep


class PagedDecodeState:
    """Live state of one paged decode batch (ISSUE 12): the device-side
    per-layer page POOLS, plus host-side per-slot lengths and the page
    table. The page table and lengths are plain numpy owned by the one
    decode worker thread; every engine call uploads the (mp-bucketed)
    table as a small int32 argument, so growth is a host array write —
    zero device copies."""

    __slots__ = ("caches", "lengths", "page_table", "mp", "page_size")

    def __init__(self, caches, lengths, page_table, mp: int,
                 page_size: int):
        self.caches = caches            # {layer: {"k": [NP,H,d], ...}}
        self.lengths = lengths          # np [S] int64 (host)
        self.page_table = page_table    # np [S, MP] int32 (host)
        self.mp = int(mp)               # current page-table width bucket
        self.page_size = int(page_size)

    @property
    def cache_len(self) -> int:
        """The logical cache bucket the decode executables see
        (``mp * page_size``) — the same contract as DecodeState."""
        return self.mp * self.page_size


class PagedGenerativeEngine(GenerativeEngine):
    """Paged-pool generative engine (ISSUE 12 tentpole): the slot caches
    become fixed-size HBM pages owned by a :class:`~.kv_pool.PagedKVPool`
    allocator, threaded through ``decode_attention`` as gather indices.

    - ``new_state()`` builds ONE pool of ``pages`` physical pages per
      layer (page 0 reserved as the zero page) — persistent KV HBM is
      the pool, not slots x max-bucket, so ragged occupancy and shared
      prefixes stop costing rounded-up private buckets.
    - ``prefill`` scatters the prompt's mini-cache rows through the
      slot's page-table rows (write-gated past the true prompt length);
      ``decode``/``verify`` run the layer walk with the page table as an
      argument — one executable per (window, table-width bucket), so
      join/leave/grow/fork never compile post-warmup.
    - ``grow()`` is a page-table width-bucket bump: a host int32 array
      re-slice, ZERO device copies (vs the contiguous engine's
      O(slots x C) host re-bucket).
    - ``verify(state, x_seq, active)`` is speculative decoding's target
      step: k tokens per slot through the fused Tq=k window-causal
      kernel (``decode_multiquery_dispatch``); accept/reject rollback is
      a host-side lengths truncation by the caller.
    - copy-on-write: the CALLER (batcher) asks :meth:`prepare_write`
      before dispatch; shared pages fork through one AOT page-copy
      executable (:meth:`fork`).
    """

    def __init__(self, model, slots: int = 8, pages: int = 64,
                 page_size: int = 16, max_cache_len: int = 256,
                 quantize: Optional[str] = None,
                 kv_cache: Optional[str] = None,
                 mesh=None, data_axis: str = "data",
                 model_axis: Optional[str] = "model",
                 pool_label: str = "default"):
        from .kv_pool import PagedKVPool
        super().__init__(model, slots=slots, quantize=quantize,
                         kv_cache=kv_cache, mesh=mesh, data_axis=data_axis,
                         model_axis=model_axis, pool_label=pool_label)
        self.page_size = next_bucket(page_size)
        self.max_cache_len = next_bucket(max_cache_len)
        if self.max_cache_len < self.page_size:
            self.max_cache_len = self.page_size
        self.max_pages_per_slot = self.max_cache_len // self.page_size
        self.pages = int(pages)
        self.pool = PagedKVPool(self.pages, self.page_size,
                                engine_id=self._id,
                                pool_label=self._pool_label)

    # ---------------------------------------------------------- state blobs
    def _pool_spec(self):
        return self.model.paged_cache_spec(self.pages, self.page_size,
                                           kv_quant=self._kv_quant)

    def pool_bytes(self, per_device: bool = False) -> int:
        """Total device bytes of the paged KV pool — the FIXED number the
        concurrent-streams-per-GB accounting divides into (contiguous
        slots each cost their full bucket; paged streams cost only their
        allocated pages). ``per_device=True`` accounts the head-sharded
        pool: each device holds H/k of every page payload (ISSUE 17)."""
        spec = self._pool_spec()
        if per_device and self._placement_layer is not None:
            return _pl.tree_bytes_per_device(
                spec, self._placement_layer.cache_shardings(spec))
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(spec))

    def new_state(self, cache_len: int = 0) -> PagedDecodeState:
        """Fresh zeroed pool + empty page table. ``cache_len`` picks the
        initial page-table width bucket (defaults to one page)."""
        caches = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                              self._pool_spec())
        if self.mesh is not None:
            pl = self._placement_layer
            caches = _pl.put_tree(caches, pl.cache_shardings(caches))
        mp = self._mp_bucket(cache_len)
        self._g_q_kv.set(self.pool_bytes())
        return PagedDecodeState(
            caches, np.zeros((self.slots,), np.int64),
            np.zeros((self.slots, self.max_pages_per_slot), np.int32),
            mp, self.page_size)

    def _mp_bucket(self, cache_len: int) -> int:
        c = next_bucket(max(int(cache_len), 1))
        mp = max(1, c // self.page_size)
        return min(next_bucket(mp), self.max_pages_per_slot)

    def grow(self, state: PagedDecodeState,
             cache_len: int) -> PagedDecodeState:
        """Page-table append: widen the table-width bucket the decode
        executables see. Host-only (the full-width numpy table already
        exists) — zero device copies, zero compiles when the bucket is
        warmed."""
        mp2 = self._mp_bucket(cache_len)
        if mp2 <= state.mp:
            return state
        return PagedDecodeState(state.caches, state.lengths,
                                state.page_table, mp2, state.page_size)

    # ------------------------------------------------- page-table plumbing
    def map_pages(self, state: PagedDecodeState, slot: int,
                  pages: Sequence[int]) -> None:
        """Install a slot's (freshly allocated or prefix-shared) pages
        into its page-table row, starting at logical page 0."""
        for j, p in enumerate(pages):
            state.page_table[slot, j] = int(p)

    def slot_pages(self, state: PagedDecodeState, slot: int) -> list:
        return [int(p) for p in state.page_table[slot] if p]

    def release_slot(self, state: PagedDecodeState, slot: int) -> list:
        """Clear a leaving slot's table row + length; returns the page
        ids for the caller to ``pool.release`` (shared pages survive
        through their other references)."""
        pages = self.slot_pages(state, slot)
        state.page_table[slot, :] = 0
        state.lengths[slot] = 0
        return pages

    def prepare_write(self, state: PagedDecodeState, slot: int,
                      n_tokens: int, ref_snapshot=None) -> list:
        """Make positions ``[lengths[slot], +n_tokens)`` exclusively
        writable: allocate missing pages, and mark shared pages for a
        copy-on-write fork (refcount > 1 — the prefix registry or a
        sibling stream still reads them). Returns ``(src, dst)`` page
        pairs for ONE batched :meth:`fork` call. Raises host-side on
        cache overflow (the clamped-scatter alternative would silently
        overwrite the last page).

        ``ref_snapshot`` (ISSUE 17 satellite): a ``pool.ref_snapshot()``
        refcount copy taken ONCE per admission round by the batcher so
        the per-page shared-ness probe stops taking the pool lock per
        candidate walk. Safe because only the calling decode worker can
        RAISE a page's refcount (lookup_prefix/retain are same-thread),
        so a stale snapshot can at worst over-fork — never lose a CoW
        fork. The snapshot is updated in place so repeated calls within
        one round stay consistent."""
        l = int(state.lengths[slot])
        P = self.page_size
        j_last = (l + int(n_tokens) - 1) // P
        if j_last >= self.max_pages_per_slot:
            raise ValueError(
                f"slot {slot} write of {n_tokens} at length {l} exceeds "
                f"max_cache_len {self.max_cache_len}")
        snap = ref_snapshot
        # Pass 1: plan — which logical rows need a fresh page, which
        # shared pages fork. No pool calls yet, so allocation is
        # all-or-nothing (one batched alloc below).
        plan = []         # (j, old_page_or_0)
        for j in range(l // P, j_last + 1):
            page = int(state.page_table[slot, j])
            if page == 0:
                plan.append((j, 0))
            else:
                shared = (int(snap[page]) > 1 if snap is not None
                          else self.pool.shared(page))
                if shared:
                    plan.append((j, page))
        if not plan:
            return []
        fresh_pages = self.pool.alloc(len(plan))
        forks = []
        released = []
        for (j, old), fresh in zip(plan, fresh_pages):
            state.page_table[slot, j] = fresh
            if snap is not None:
                snap[fresh] = 1
            if old:
                forks.append((old, fresh))
                released.append(old)
                if snap is not None:
                    snap[old] -= 1
        if released:
            self.pool.release(released)
        if forks:
            self.pool.note_fork(len(forks))
        return forks

    # ----------------------------------------------------------- compilation
    def _pprefill_exe(self, tp: int, _warmup=False):
        model = self.model
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)
        kv_quant = self._kv_quant

        def fn(params, mstate, pool, x, plen, rows):
            mini = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                model.decode_cache_spec(1, tp, kv_quant=kv_quant))
            y, mini = model._prefill(params, x, mstate, mini, plen[None])
            d = y.shape[-1]
            logits = jax.lax.dynamic_slice(
                y, (0, plen - 1, 0), (1, 1, d))[0, 0]
            # bucket-pad rows (pos >= plen) are write-gated: they may
            # point at the zero page or a shared partial page, and
            # scattering garbage there would corrupt other references
            gate = jnp.arange(tp) < plen

            def scatter(pool_leaf, mini_leaf):
                upd = jnp.transpose(mini_leaf[0], (1, 0, 2)) \
                    .astype(pool_leaf.dtype)              # [tp, H, d]
                upd = jnp.where(gate[:, None, None], upd, pool_leaf[rows])
                return pool_leaf.at[rows].set(upd)

            pool = jax.tree.map(scatter, pool, mini)
            return pool, logits

        def build():
            p_avals, s_avals = self._params_avals()
            pool_avals = self._pool_spec()
            jkw = {"donate_argnums": (2,)}
            if self.mesh is not None:
                p_sh, s_sh, pool_sh, repl = self._tp_shardings(pool_avals)
                jkw["in_shardings"] = (p_sh, s_sh, pool_sh, repl, repl,
                                       repl)
                jkw["out_shardings"] = (pool_sh, repl)
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(
                    p_avals, s_avals, pool_avals,
                    jax.ShapeDtypeStruct((1, tp, f), dt),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((tp,), jnp.int32))

        return self._get_compiled(("pprefill", tp), build, _warmup)

    def _pdecode_exe(self, kq: int, mp: int, _warmup=False):
        model = self.model
        S = self.slots
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)
        P = self.page_size

        def fn(params, mstate, pool, pt, lengths, x_t, active):
            y, pool = model._decode_step(params, x_t, mstate, pool,
                                         lengths, write=active,
                                         page_table=pt, page_size=P)
            return pool, y

        def build():
            p_avals, s_avals = self._params_avals()
            pool_avals = self._pool_spec()
            jkw = {"donate_argnums": (2,)}
            if self.mesh is not None:
                p_sh, s_sh, pool_sh, repl = self._tp_shardings(pool_avals)
                jkw["in_shardings"] = (p_sh, s_sh, pool_sh, repl, repl,
                                       repl, repl)
                jkw["out_shardings"] = (pool_sh, repl)
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(
                    p_avals, s_avals, pool_avals,
                    jax.ShapeDtypeStruct((S, mp), jnp.int32),
                    jax.ShapeDtypeStruct((S,), jnp.int32),
                    jax.ShapeDtypeStruct((S, kq, f), dt),
                    jax.ShapeDtypeStruct((S,), jnp.int32))

        return self._get_compiled(("pdecode", kq, mp), build, _warmup)

    def _pdecode_multi_parts(self, kmax: int, mp: int,
                             spec: _smp.SamplingSpec):
        """Paged twin of :meth:`_decode_multi_parts`: the page table is
        a loop-invariant argument (pages for the whole horizon are
        prepared by the batcher's CoW pass before dispatch), lengths
        advance in the carry so each iteration scatters into the right
        page rows. Like the contiguous twin, k is a RUNTIME scalar
        bounded by the program's ``kmax`` output capacity."""
        model = self.model
        S = self.slots
        f = self._feature_dim()
        dt = _dt.resolve(model.conf.dtype)
        P = self.page_size
        sample = spec.build()
        stochastic = spec.stochastic

        p_avals, s_avals = self._params_avals()
        pool_avals = self._pool_spec()
        pt_aval = jax.ShapeDtypeStruct((S, mp), jnp.int32)
        len_aval = jax.ShapeDtypeStruct((S,), jnp.int32)
        x_aval = jax.ShapeDtypeStruct((S, 1, f), dt)
        i32_aval = jax.ShapeDtypeStruct((S,), jnp.int32)
        y_aval = jax.eval_shape(
            lambda p, m, po, tb, ll, xx, aa: model._decode_step(
                p, xx, m, po, ll, write=aa, page_table=tb,
                page_size=P)[0],
            p_avals, s_avals, pool_avals, pt_aval, len_aval, x_aval,
            i32_aval)
        V, ldt = int(y_aval.shape[-1]), y_aval.dtype

        def fn(params, mstate, pool, pt, lengths, x_t, active, cap,
               eos_ids, temp, key, k):
            active = active * cap

            def body(i, carry):
                pool, lengths, x_t, active, key, toks, lgs, ems = carry
                if stochastic:
                    key, sub = jax.random.split(key)
                else:
                    sub = key
                y, pool = model._decode_step(params, x_t, mstate, pool,
                                             lengths, write=active,
                                             page_table=pt, page_size=P)
                logits = y[:, 0]
                tok = sample(logits, sub, temp)
                emitted = active
                lengths = lengths + active.astype(lengths.dtype)
                active = active * (1 - _smp.eos_hit(tok, eos_ids))
                x_t = model.decode_token_features(tok, dtype=dt)
                toks = jax.lax.dynamic_update_index_in_dim(
                    toks, tok.astype(jnp.int32), i, 0)
                lgs = jax.lax.dynamic_update_index_in_dim(
                    lgs, logits.astype(ldt), i, 0)
                ems = jax.lax.dynamic_update_index_in_dim(
                    ems, emitted, i, 0)
                return (pool, lengths, x_t, active, key,
                        toks, lgs, ems)

            init = (pool, lengths, x_t, active, key,
                    jnp.zeros((kmax, S), jnp.int32),
                    jnp.zeros((kmax, S, V), ldt),
                    jnp.zeros((kmax, S), jnp.int32))
            (pool, lengths, x_t, active, key,
             toks, logits, emitted) = jax.lax.fori_loop(0, k, body, init)
            return pool, lengths, x_t, active, key, toks, logits, emitted

        avals = (p_avals, s_avals, pool_avals, pt_aval,
                 len_aval, x_aval, i32_aval, i32_aval, i32_aval,
                 jax.ShapeDtypeStruct((), jnp.float32),
                 jax.ShapeDtypeStruct((2,), jnp.uint32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return fn, avals, pool_avals

    def decode_multi_traceable(self, cache_len: int, k: int,
                               sampling: _smp.SamplingSpec = _smp.GREEDY):
        mp = self._mp_bucket(int(cache_len))
        fn, avals, _ = self._pdecode_multi_parts(int(k), mp, sampling)
        return fn, avals

    def _pdecode_multi_exe(self, kmax: int, mp: int,
                           spec: _smp.SamplingSpec, _warmup=False):
        def build():
            fn, avals, pool_avals = self._pdecode_multi_parts(
                kmax, mp, spec)
            jkw = {"donate_argnums": (2,)}
            if self.mesh is not None:
                p_sh, s_sh, pool_sh, repl = self._tp_shardings(pool_avals)
                jkw["in_shardings"] = (p_sh, s_sh, pool_sh) + (repl,) * 9
                jkw["out_shardings"] = (pool_sh,) + (repl,) * 7
            with self._tp_trace():
                return jax.jit(fn, **jkw).lower(*avals)

        return self._get_compiled(
            ("pdecode_multi", kmax, mp) + spec.static_key(), build,
            _warmup)

    def _pfork_exe(self, _warmup=False):
        S = self.slots
        P = self.page_size

        def fn(pool, src, dst):
            offs = jnp.arange(P, dtype=jnp.int32)[None, :]
            rows_s = (src[:, None] * P + offs).reshape(-1)
            rows_d = (dst[:, None] * P + offs).reshape(-1)
            return jax.tree.map(
                lambda leaf: leaf.at[rows_d].set(leaf[rows_s]), pool)

        def build():
            pool_avals = self._pool_spec()
            jkw = {"donate_argnums": (0,)}
            if self.mesh is not None:
                pl = self._placement_layer
                pool_sh = pl.cache_shardings(pool_avals)
                jkw["in_shardings"] = (pool_sh, pl.replicated(),
                                       pl.replicated())
                jkw["out_shardings"] = pool_sh
            return jax.jit(fn, **jkw).lower(
                pool_avals,
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32))

        return self._get_compiled(("pfork",), build, _warmup)

    # -------------------------------------------- KV-page migration (ISSUE 18)
    def _pexport_exe(self, npg: int, _warmup=False):
        """Gather ``npg`` whole pages out of every layer pool in ONE
        device call: pages [npg] -> payload tree of [npg*P, H, d] blocks
        (plus the d=1 int8 scale rows when ``kv_cache="int8"``). NOT
        donated — the exporting pool keeps serving its pages (the prefix
        registry may still map them)."""
        P = self.page_size

        def fn(pool, pages):
            rows = _fa.page_rows(pages, P)
            return jax.tree.map(lambda leaf: _fa.page_export(leaf, rows),
                                pool)

        def build():
            pool_avals = self._pool_spec()
            jkw = {}
            if self.mesh is not None:
                pl = self._placement_layer
                jkw["in_shardings"] = (pl.cache_shardings(pool_avals),
                                       pl.replicated())
                # payload blocks leave the mesh: replicate so the host
                # copy below is one addressable read per leaf
                jkw["out_shardings"] = pl.replicated()
            return jax.jit(fn, **jkw).lower(
                pool_avals, jax.ShapeDtypeStruct((npg,), jnp.int32))

        return self._get_compiled(("pexport", npg), build, _warmup)

    def _pimport_exe(self, npg: int, _warmup=False):
        """Scatter ``npg`` whole migrated pages into every layer pool in
        ONE device call. Rows of padding entries (page id 0) are
        write-gated — they scatter back the value they gathered, so a
        short chunk can never corrupt the zero page. Donates the pool."""
        P = self.page_size

        def fn(pool, pages, payload):
            rows = _fa.page_rows(pages, P)
            gate = jnp.repeat(pages > 0, P)
            return jax.tree.map(
                lambda leaf, pay: _fa.page_import(leaf, rows, pay, gate),
                pool, payload)

        def build():
            pool_avals = self._pool_spec()
            payload_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    (npg * P,) + tuple(a.shape[1:]), a.dtype), pool_avals)
            jkw = {"donate_argnums": (0,)}
            if self.mesh is not None:
                pl = self._placement_layer
                pool_sh = pl.cache_shardings(pool_avals)
                jkw["in_shardings"] = (pool_sh, pl.replicated(),
                                       pl.replicated())
                jkw["out_shardings"] = pool_sh
            return jax.jit(fn, **jkw).lower(
                pool_avals, jax.ShapeDtypeStruct((npg,), jnp.int32),
                payload_avals)

        return self._get_compiled(("pimport", npg), build, _warmup)

    def _migrate_chunks(self, kind: str, n: int):
        """Chunk an ``n``-page migration over the warmed page-count
        buckets for executable family ``kind``: yields ``(bucket, take)``
        pairs — one device call each, never a call per page. Falls back
        to one ``next_bucket(n)`` compile (counted ``new_bucket``) when
        nothing is warmed."""
        with self._lock:
            warmed = sorted(k[1] for k in self._compiled if k[0] == kind)
        i = 0
        while i < n:
            rem = n - i
            if warmed:
                fits = [b for b in warmed if b >= rem]
                bucket = fits[0] if fits else warmed[-1]
            else:
                bucket = next_bucket(rem)
            take = min(bucket, rem)
            yield bucket, take
            i += take

    def export_pages(self, state: PagedDecodeState, pages: Sequence[int]):
        """Materialize whole pages as HOST numpy payload blocks (ISSUE 18
        migration, sender side): the tree mirrors ``paged_cache_spec``
        but each leaf is ``[len(pages)*page_size, H, d]`` rows in page
        order. One device gather per warmed chunk; one host copy per
        leaf."""
        pages = [int(p) for p in pages]
        if not pages:
            raise ValueError("export_pages needs at least one page")
        if any(p <= 0 or p >= self.pages for p in pages):
            raise ValueError(f"page ids out of range: {pages}")
        P = self.page_size
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        chunks = []
        i = 0
        for bucket, take in self._migrate_chunks("pexport", len(pages)):
            ids = np.zeros((bucket,), np.int32)
            ids[:take] = pages[i:i + take]
            exe = self._pexport_exe(bucket)
            self._m_calls.inc()
            payload = exe(state.caches, self._put_arg(ids))
            chunks.append(jax.tree.map(
                lambda a: np.asarray(a)[:take * P].copy(), payload))
            i += take
        if len(chunks) == 1:
            out = chunks[0]
        else:
            out = jax.tree.map(
                lambda *xs: np.concatenate(xs, axis=0), *chunks)
        if tel:
            self._h_kv_export.observe(time.perf_counter() - t0)
        return out

    def import_pages(self, state: PagedDecodeState, pages: Sequence[int],
                     payload) -> PagedDecodeState:
        """Install migrated payload blocks into freshly allocated page
        ids (ISSUE 18 migration, receiver side). ``payload`` must
        structurally match this engine's ``paged_cache_spec`` leaves
        (same layer tree, same [.., H, d] trailing dims, same dtypes) —
        mismatches raise before any device work."""
        pages = [int(p) for p in pages]
        if not pages:
            raise ValueError("import_pages needs at least one page")
        P = self.page_size
        spec = self._pool_spec()
        spec_leaves, spec_def = jax.tree.flatten(spec)
        pay_leaves, pay_def = jax.tree.flatten(payload)
        if pay_def != spec_def:
            raise ValueError(
                f"migrated payload tree does not match this engine's "
                f"paged cache layout: {pay_def} vs {spec_def}")
        want_rows = len(pages) * P
        for sl, pl_ in zip(spec_leaves, pay_leaves):
            pl_ = np.asarray(pl_)
            if tuple(pl_.shape) != (want_rows,) + tuple(sl.shape[1:]):
                raise ValueError(
                    f"migrated payload block {pl_.shape} does not match "
                    f"{(want_rows,) + tuple(sl.shape[1:])} (page_size/"
                    f"head-count/d mismatch between pools)")
            if np.dtype(pl_.dtype) != np.dtype(sl.dtype):
                raise ValueError(
                    f"migrated payload dtype {pl_.dtype} != pool dtype "
                    f"{sl.dtype} (kv_cache modes disagree across pools)")
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        caches = state.caches
        i = 0
        for bucket, take in self._migrate_chunks("pimport", len(pages)):
            ids = np.zeros((bucket,), np.int32)
            ids[:take] = pages[i:i + take]

            def slice_pad(a):
                a = np.asarray(a)[i * P:(i + take) * P]
                if bucket > take:
                    pad = np.zeros(((bucket - take) * P,) + a.shape[1:],
                                   a.dtype)
                    a = np.concatenate([a, pad], axis=0)
                return a

            exe = self._pimport_exe(bucket)
            self._m_calls.inc()
            caches = exe(caches, self._put_arg(ids),
                         jax.tree.map(lambda a: self._put_arg(slice_pad(a)),
                                      payload))
            i += take
        if tel:
            self._h_kv_import.observe(time.perf_counter() - t0)
        return PagedDecodeState(caches, state.lengths, state.page_table,
                                state.mp, state.page_size)

    def warmup(self, cache_buckets: Sequence[int],
               prompt_buckets: Sequence[int],
               speculate: Sequence[int] = (),
               checkpoint: Optional[str] = None,
               migrate_buckets: Sequence[int] = (),
               horizons: Sequence[int] = (),
               sampling: _smp.SamplingSpec = _smp.GREEDY
               ) -> "PagedGenerativeEngine":
        """Compile every (table-width bucket) decode executable — plus a
        Tq=k verify per ``speculate`` window — every prompt-bucket
        prefill, and the page-fork copy, outside traffic.

        ``checkpoint``: pod AOT warmup (ISSUE 17) — restore params from
        a ``TrainingCheckpointer`` directory first, so every host loads
        only its addressable shards before bucket compilation.

        ``migrate_buckets`` (ISSUE 18): page-count buckets for the
        KV-page export/import executables — disaggregated replicas pass
        the page counts their prompt buckets imply so migrations stay at
        zero post-warmup compiles; colocated engines skip the cost."""
        if checkpoint is not None:
            _pl.load_checkpoint(self.model, checkpoint)
        mps = sorted({self._mp_bucket(c) for c in cache_buckets})
        tps = sorted({next_bucket(t) for t in prompt_buckets})
        hs = sorted({int(h) for h in horizons if int(h) >= 1})
        for mp in mps:
            if not hs:
                # same rule as the contiguous engine: a horizon front
                # never dispatches the single-token window
                self._pdecode_exe(1, mp, _warmup=True)
            for h in hs:
                self._pdecode_multi_exe(h, mp, sampling, _warmup=True)
            for kq in speculate:
                if int(kq) > 1:
                    self._pdecode_exe(int(kq), mp, _warmup=True)
        for tp in tps:
            self._pprefill_exe(tp, _warmup=True)
        self._pfork_exe(_warmup=True)
        for npg in sorted({next_bucket(max(1, int(n)))
                           for n in migrate_buckets}):
            self._pexport_exe(npg, _warmup=True)
            self._pimport_exe(npg, _warmup=True)
        return self

    # -------------------------------------------------------------- dispatch
    def prefill(self, state: PagedDecodeState, x, plen: int, slot: int):
        """Fill ``slot``'s pages from one request's prompt. The slot's
        page-table row must already cover ``ceil(plen / page_size)``
        pages (the batcher allocates at admission). Returns
        ``(state', logits [V])``."""
        x = np.asarray(x)
        if x.ndim == 2:
            x = x[None]
        dt = _dt.resolve(self.model.conf.dtype)
        if np.issubdtype(x.dtype, np.floating) and x.dtype != dt:
            x = x.astype(dt)
        with self._lock:
            warmed = sorted(k[1] for k in self._compiled
                            if k[0] == "pprefill" and k[1] >= x.shape[1])
        tp = warmed[0] if warmed else next_bucket(x.shape[1])
        if tp != x.shape[1]:
            x = np.concatenate(
                [x, np.zeros((1, tp - x.shape[1]) + x.shape[2:], x.dtype)],
                axis=1)
        self._m_calls.inc()
        exe = self._pprefill_exe(tp)
        P = self.page_size
        pos = np.arange(tp)
        pages = state.page_table[slot, np.minimum(
            pos // P, self.max_pages_per_slot - 1)].astype(np.int64)
        rows = np.where(pages > 0, pages * P + pos % P, 0).astype(np.int32)
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        params, mstate = self._place_params()
        caches, logits = exe(params, mstate, state.caches,
                             self._put_arg(x),
                             self._put_arg(np.int32(plen)),
                             self._put_arg(rows))
        logits = np.asarray(logits)
        if tel:
            self._h_prefill.observe(time.perf_counter() - t0)
        state.lengths[slot] = int(plen)
        return PagedDecodeState(caches, state.lengths, state.page_table,
                                state.mp, state.page_size), logits

    def _dispatch_window(self, state: PagedDecodeState, x, active, kq: int):
        x = np.asarray(x)
        dt = _dt.resolve(self.model.conf.dtype)
        if np.issubdtype(x.dtype, np.floating) and x.dtype != dt:
            x = x.astype(dt)
        self._m_calls.inc()
        exe = self._pdecode_exe(kq, state.mp)
        pt = np.ascontiguousarray(state.page_table[:, :state.mp],
                                  dtype=np.int32)
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else 0.0
        params, mstate = self._place_params()
        caches, y = exe(params, mstate, state.caches,
                        self._put_arg(pt),
                        self._put_arg(state.lengths.astype(np.int32)),
                        self._put_arg(x),
                        self._put_arg(np.asarray(active, np.int32)))
        y = np.asarray(y)
        if tel:
            self._h_decode.observe(time.perf_counter() - t0)
        return PagedDecodeState(caches, state.lengths, state.page_table,
                                state.mp, state.page_size), y

    def decode(self, state: PagedDecodeState, x_t, active):
        """One token for every slot (paged). Advances ``lengths`` for
        active rows host-side; returns ``(state', logits [S, V])``."""
        state, y = self._dispatch_window(state, x_t, active, 1)
        state.lengths += np.asarray(active, np.int64)
        return state, y[:, 0]

    def verify(self, state: PagedDecodeState, x_seq, active):
        """Speculative verify: ``x_seq`` [S, k, F] (the pending token
        followed by k-1 draft tokens) in ONE bucketed step through the
        fused Tq=k path. ``lengths`` are NOT advanced — the caller
        truncates them to the accepted count (the paged rollback), which
        also invalidates the rejected tokens' cache rows. Returns
        ``(state', logits [S, k, V])``."""
        return self._dispatch_window(state, x_seq, active,
                                     int(np.asarray(x_seq).shape[1]))

    def pdecode_multi(self, state: PagedDecodeState, x_t, active, k: int,
                      *, eos_ids=None, active_cap=None,
                      sampling: _smp.SamplingSpec = _smp.GREEDY,
                      key=None, chain: Optional[HorizonChain] = None):
        """Paged k-token horizon (ISSUE 19): same contract as
        :meth:`GenerativeEngine.decode_multi`. Host ``lengths`` are NOT
        advanced here — the batcher syncs them from the fetched per-slot
        emit counts (mirroring the speculative rollback discipline); the
        device-carried lengths ride ``result.chain`` so a chained
        dispatch needs no host mirror. The caller must
        ``prepare_write(..., k)`` + ``fork`` BEFORE dispatch so every
        page the horizon can touch is exclusively writable. k is a
        runtime scalar: the smallest warmed capacity kmax >= k serves
        the dispatch, exactly like the contiguous path."""
        k = int(k)
        with self._lock:
            warmed = sorted(
                kk[1] for kk in self._compiled
                if kk[0] == "pdecode_multi" and kk[2] == state.mp
                and kk[1] >= k and tuple(kk[3:]) == sampling.static_key())
        kmax = warmed[0] if warmed else k
        exe = self._pdecode_multi_exe(kmax, state.mp, sampling)
        self._m_calls.inc()
        pt = np.ascontiguousarray(state.page_table[:, :state.mp],
                                  dtype=np.int32)
        params, mstate = self._place_params()
        cap, eos, temp, key_arg = self._horizon_args(
            k, active_cap, eos_ids, sampling, key)
        if chain is not None:
            x_arg, a_arg, key_arg = chain.x_t, chain.active, chain.key
            l_arg = chain.lengths
        else:
            x_arg = self._put_arg(self._cast_x(x_t))
            a_arg = self._put_arg(np.asarray(active, np.int32))
            l_arg = self._put_arg(state.lengths.astype(np.int32))
        tel = _tel.enabled()
        t0 = time.perf_counter() if tel else None
        pool, lengths, x2, a2, k2, toks, logits, emitted = exe(
            params, mstate, state.caches, self._put_arg(pt), l_arg,
            x_arg, a_arg, cap, eos, temp, key_arg,
            self._put_arg(np.int32(k)))
        state2 = PagedDecodeState(pool, state.lengths, state.page_table,
                                  state.mp, state.page_size)
        ch = HorizonChain(x2, a2, lengths, k2)
        return state2, HorizonResult(toks, logits, emitted, ch, k,
                                     self, t0)

    def fork(self, state: PagedDecodeState, pairs) -> PagedDecodeState:
        """Copy-on-write page copies: one batched executable call per
        ``slots``-sized chunk of (src, dst) pairs (padding entries copy
        the zero page onto itself — a no-op)."""
        if not pairs:
            return state
        exe = self._pfork_exe()
        caches = state.caches
        S = self.slots
        for i in range(0, len(pairs), S):
            chunk = pairs[i:i + S]
            src = np.zeros((S,), np.int32)
            dst = np.zeros((S,), np.int32)
            for j, (s_pg, d_pg) in enumerate(chunk):
                src[j], dst[j] = s_pg, d_pg
            caches = exe(caches, self._put_arg(src), self._put_arg(dst))
        return PagedDecodeState(caches, state.lengths, state.page_table,
                                state.mp, state.page_size)

    # ---------------------------------------------------------------- admin
    def stats(self) -> dict:
        out = super().stats()
        out["paged"] = self.pool.stats()
        out["pool_bytes"] = self.pool_bytes()
        if self._placement_layer is not None:
            out["pool_bytes_per_device"] = self.pool_bytes(per_device=True)
        return out
