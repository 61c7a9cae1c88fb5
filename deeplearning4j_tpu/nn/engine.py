"""The host side of both network engines, written once.

``MultiLayerNetwork`` (``nn/model.py``) and ``ComputationGraph``
(``nn/graph.py``) differ in their forward walks and loss heads. What they
do on the host around those is here: ``fit`` and ``fit_on_device`` with
their ``train.phase.*`` spans and fault sites, the train-step and epoch
builders over ``nn/trainstep.py``, regularization and clipping, listeners,
DL4J's flat-parameter view and serialization. An engine supplies only what
really differs, as small methods the code here calls:

- :meth:`NetworkEngine._keyed_layers`: ``(param key, layer or None)`` in
  DL4J order, which regularization, the frozen keys and the flat view read;
- the arity of a batch (one array, or a tuple over the graph's inputs and
  outputs): :meth:`~NetworkEngine._iterator`,
  :meth:`~NetworkEngine._stage_batch`, :meth:`~NetworkEngine._host_arrays`,
  :meth:`~NetworkEngine._pack` and :meth:`~NetworkEngine._epoch_masks`;
- the microbatch weight :meth:`~NetworkEngine._label_weight`;
- the hooks :meth:`~NetworkEngine._fit_by_solver`,
  :meth:`~NetworkEngine._count_passes` and
  :meth:`~NetworkEngine._publish_layer_counters`.

The step and epoch functions keep each engine's own call signature: bare
arrays for ``MultiLayerNetwork``, tuples for ``ComputationGraph``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dt
from .. import environment as _env
from ..runtime import telemetry as _tel
from . import caches as _caches
from . import staging as _staging

# DL4J param-name ordering inside a layer, for the flat view
# (LSTMParamInitializer order W, RW, b; PW is our peephole tensor;
# fw/bw are Bidirectional sub-trees)
_PARAM_ORDER = {"W": 0, "RW": 1, "PW": 2, "b": 3, "gamma": 4, "beta": 5,
                "fw": 6, "bw": 7}


def _param_paths(node, prefix=()):
    """Depth-first (name, ...) paths to array leaves inside one layer/vertex
    param dict, DL4J name order at each level (handles nested sub-trees like
    Bidirectional's fw/bw)."""
    if not isinstance(node, dict):
        return [prefix]
    out = []
    for k in sorted(node, key=lambda n: (_PARAM_ORDER.get(n, 99), n)):
        out.extend(_param_paths(node[k], prefix + (k,)))
    return out


def _get_path(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set_path(tree, path, value):
    """Set a leaf in a nested dict, copying the dicts along the path."""
    if len(path) == 1:
        new = dict(tree)
        new[path[0]] = value
        return new
    new = dict(tree)
    new[path[0]] = _set_path(tree[path[0]], path[1:], value)
    return new


def _is_loss_head(l) -> bool:
    """True when the (FrozenLayer-unwrapped) layer really implements
    loss_value — FrozenLayer delegates it unconditionally, so probe the
    wrapped layer, not the wrapper."""
    inner = getattr(l, "layer", None)
    while inner is not None and hasattr(l, "frozen"):
        l, inner = inner, getattr(inner, "layer", None)
    return hasattr(l, "loss_value")


class NetworkEngine(_caches.CompiledCacheMixin):
    """What ``MultiLayerNetwork`` and ``ComputationGraph`` share on the
    host. The subclass owns ``init()``, ``_forward`` and
    ``_build_loss_fn``."""

    def __init__(self, conf):
        self.conf = conf
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self.updater_state: Any = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self._listeners: List[Any] = []
        self._train_step = None
        self._train_output_fn = None
        self._epoch_fn = None
        self._inference_engine = None
        self._key = jax.random.PRNGKey(conf.seed)

    # ------------------------------------------- what each engine supplies
    def _keyed_layers(self):
        """Yield ``(param key, layer or None)`` for every layer or vertex,
        in DL4J order (a vertex that is not a layer yields None)."""
        raise NotImplementedError

    def _iterator(self, data, labels):
        """``fit``'s data as the engine's iterator of batches."""
        raise NotImplementedError

    def _stage_batch(self, ds):
        """One batch of :meth:`_iterator` -> the step's ``(x, y, fmask,
        lmask)`` as device arrays (None for an absent mask)."""
        raise NotImplementedError

    def _host_arrays(self, features, labels):
        """``fit_on_device``'s arguments -> ``([feature arrays], [label
        arrays])`` on the host."""
        raise NotImplementedError

    def _pack(self, arrays):
        """The stacked arrays of :meth:`_host_arrays`' lists as the epoch
        function takes them."""
        raise NotImplementedError

    def _epoch_masks(self):
        """The step's ``(fmask, lmask)`` of Nones: the epoch takes none."""
        raise NotImplementedError

    def _label_weight(self, x, y, fmask, lmask):
        """A microbatch's weight (``nn/microbatch.py``)."""
        raise NotImplementedError

    def _fit_by_solver(self, data, labels, epochs) -> bool:
        """``fit``'s preconditions once the net is initialised; where the
        conf asks for a solver in place of the fused step, train with it
        and -> True."""
        return False

    def _count_passes(self, steps: int):
        """Count what ``steps`` launched steps walked (graphs' repeated
        runs)."""

    def _publish_layer_counters(self):
        """Publish what layers counted on the device, after a readback."""

    # ------------------------------------------------------------ params
    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))

    def _regularization(self, params):
        """Per-layer l1/l2 on weights (DL4J regularizes W, not b, by
        default); a layer's own value, else the conf's."""
        total = 0.0
        for key, layer in self._keyed_layers():
            if getattr(layer, "frozen", False):
                continue  # FrozenLayer: no updates of any kind (DL4J)
            l1 = getattr(layer, "l1", 0.0) or self.conf.l1
            l2 = getattr(layer, "l2", 0.0) or self.conf.l2
            if not (l1 or l2):
                continue
            w = params.get(key, {}).get("W")
            if w is None:
                continue
            if l1:
                total = total + l1 * jnp.sum(jnp.abs(w))
            if l2:
                total = total + 0.5 * l2 * jnp.sum(jnp.square(w))
        return total

    def _uses_regularization(self) -> bool:
        """Any l1/l2 penalty configured (conf-level or per-layer)? Gates
        the mixed-precision cast hoist in ``_build_train_step`` — the
        regularization term reads the params the loss fn is handed, so the
        hoist (which hands it compute-dtype copies) only applies when the
        term is identically zero."""
        if self.conf.l1 or self.conf.l2:
            return True
        return any((getattr(l, "l1", 0.0) or getattr(l, "l2", 0.0))
                   for _, l in self._keyed_layers())

    def _clip(self, grads):
        """Gradient normalization/clipping; returns ``(grads, clip_events)``
        — the shared ``gradnorm.clip_with_events`` pipeline (the sentinel
        accumulates the events as telemetry)."""
        from . import gradnorm as _gn
        return _gn.clip_with_events(
            self.conf.gradient_normalization,
            self.conf.gradient_normalization_threshold,
            self.conf.gradient_clip_value, self.conf.gradient_clip_l2, grads)

    def quantize_params(self, mode: str = "int8") -> dict:
        """Post-training per-channel int8 quantization of the opted-in
        matmul/conv weights (ISSUE 9): every layer whose ``quantize_spec``
        names weights gets them replaced by ``ops.quantize.QuantizedTensor``;
        norms, biases, embeddings and merge vertices stay f32. Returns a NEW
        params tree (the model's own f32 params are untouched — training and
        f32 serving keep working); the serving engines call this at warmup
        (``InferenceEngine(quantize="int8")``) so every AOT bucket
        executable compiles the quantized graph."""
        if mode != "int8":
            raise ValueError(f"unknown quantization mode {mode!r} "
                             "(expected 'int8')")
        from ..ops import quantize as _q
        return _q.quantize_model_params(self)[0]

    # --------------------------------------------------------- train step
    def fused_updater_active(self) -> bool:
        """Does the train step fold the per-step f32->compute master cast
        into the updater write (ISSUE 16)? True under a 16-bit policy with
        no l1/l2 term (the regularization reads the params the loss fn is
        handed, so it must see f32 masters) and the fused-epilogue library
        enabled. When True the step carries a ``params_c`` compute copy
        alongside the masters and the standalone per-step cast sweep
        disappears from the compiled program."""
        from ..ops import fused_epilogues as _fe
        return _fe.route_updater(
            self.conf.dtype,
            has_penalty=self._uses_regularization()) is None

    def _build_train_step(self, accum_steps: int = 1, grad_transform=None,
                          fused_cast: bool = False):
        """Fused pure train step (the body is ``nn/trainstep.py``'s, shared
        from the gradient on with ``SameDiff``), jitted with the params,
        updater and layer-state buffers donated. Its batch is the engine's:
        ``x, y, fmask, lmask`` arrays, or tuples of them over a graph's
        inputs and outputs.

        ``accum_steps=k`` splits the batch into k microbatches and
        accumulates the mean gradient via ``lax.scan`` before the SINGLE
        updater application (see ``nn/microbatch.py`` for the exactness
        contract) — peak activation memory drops to one microbatch, so
        global batch can grow past HBM. The conf's ``workspace_mode`` remat
        policy (``nn/memory.py``) composes: inside each microbatch,
        intra-segment activations are recomputed in the backward pass
        instead of cached.

        ``grad_transform`` (value-identity, e.g. the collective-overlap
        sharding pins from ``parallel/overlap.py``) is applied to the raw
        gradients BEFORE clipping/sentinel — the earliest point the full
        tree exists, so a sharding constraint there moves the gradient
        collectives ahead of the global-norm joins.

        bf16 audit fix (r12): under a 16-bit dtype policy with
        ``accum_steps>1`` and no l1/l2 term, the fp32-master -> compute-
        dtype cast is HOISTED out of the microbatch scan — the masters are
        cast once per step and the scan body's ``cast_floating`` becomes an
        identity, instead of re-materializing a compute-dtype copy of every
        parameter k times per step. Gradients come back in the compute
        dtype and promote exactly into the f32 scan accumulator (the same
        values the per-microbatch cast-backward produced), then cast to the
        master dtype before clipping — bit-equivalent (tested).

        ``fused_cast=True`` (ISSUE 16, caller gates on
        :meth:`fused_updater_active`) compiles the FUSED MASTER-CAST
        variant: the signature gains a ``params_c`` compute-dtype copy
        after ``params``, the forward differentiates the copy
        (``_forward``'s ``cast_floating`` is identity on pre-cast leaves
        -> bit-equal forward), cotangents upcast exactly like the unfused
        cast's transpose, and ``apply_leafwise_cast`` emits next step's
        compute copy inside the same fusion that writes the f32 master —
        the standalone per-step cast sweep is gone from the program.
        Bit-parity of params AND updater state vs the unfused step is
        asserted in tests."""
        from .layers.wrappers import FrozenLayer
        from . import trainstep as _ts
        frozen_keys = frozenset(k for k, l in self._keyed_layers()
                                if isinstance(l, FrozenLayer))
        step = _ts.engine_step(
            self, self._build_loss_fn(), frozen_keys, self._label_weight,
            accum_steps, grad_transform, fused_cast)
        # donate params/opt/bn buffers: in-place update on device (workspace
        # arenas' moral equivalent, handled by XLA)
        return jax.jit(step,
                       donate_argnums=(0, 1, 2, 3) if fused_cast
                       else (0, 1, 2),
                       compiler_options=_env.engine_compiler_options())

    def _build_epoch_fn(self):
        """Compiled multi-batch trainer: ``lax.scan`` of the fused train step
        over a device-resident stack of batches — the whole epoch is ONE XLA
        program launch.

        Why this exists (TPU-first divergence from DL4J's per-batch fit
        loop): each host->device dispatch costs fixed latency (PJRT call
        overhead). Scanning on device removes it entirely and is how XLA-era
        trainers are meant to run epochs whose data fits in HBM.

        Under the fused master-cast updater (ISSUE 16) the scan carries
        the ``params_c`` compute copy — one cast per epoch launch, the
        rest emitted by the fused updater write; external signature
        unchanged (masters in, masters out). ``xs``/``ys`` are stacked
        arrays ``[n_batches, B, ...]`` in the engine's arity
        (:meth:`_pack`); masks are unsupported on this path."""
        # one dispatch decision per compiled program, as ``fit`` counts it
        from ..ops import fused_epilogues as _fe
        from . import trainstep as _ts
        _fe.dispatch_updater(self.conf.dtype,
                             has_penalty=self._uses_regularization())
        fused = self.fused_updater_active()
        step = self._build_train_step(fused_cast=fused).__wrapped__
        return jax.jit(_ts.build_epoch(step, fused,
                                       _dt.resolve(self.conf.dtype),
                                       self._epoch_masks()),
                       donate_argnums=(0, 1, 2, 3),
                       compiler_options=_env.engine_compiler_options())

    # ------------------------------------------------------------ training
    def fit_on_device(self, features, labels, epochs: int = 1,
                      batch_size: Optional[int] = None,
                      drop_remainder: bool = False) -> np.ndarray:
        """Train with the compiled on-device epoch loop (see
        ``_build_epoch_fn``). ``features``/``labels`` are arrays (or, for a
        multi-input/output graph, lists of arrays); they are reshaped to
        ``[n_batches, batch_size, ...]``, uploaded ONCE, and scanned over
        ``epochs`` times. A non-divisible dataset RAISES unless
        ``drop_remainder=True`` explicitly discards the tail (device loops
        need static shapes; silent data loss was r3's recorded footgun).
        Returns the loss history ``[epochs * n_batches]``. Masked datasets
        must use ``fit()``.

        A floating feature array that needs the network's dtype is cast in
        runs of rows on a pool of host threads (``nn/staging.py``), bit for
        bit ``a.astype(dtype)``; ids, labels and arrays already in that
        dtype are uploaded as they are. The counter ``train.stage{decision=,
        why=}`` says which each array took.
        """
        span_labels = self._phase_labels()
        with _tel.span("train.phase.call_s", span_labels,
                       entry=f"{type(self).__name__}.fit_on_device"):
            if not self.params and not self.state:
                self.init()
            feats, labs = self._host_arrays(features, labels)
            n = feats[0].shape[0]
            b = batch_size or n
            nb = n // b
            if nb == 0:
                raise ValueError(f"batch_size {b} exceeds dataset size {n}")
            if n % b and not drop_remainder:
                raise ValueError(
                    f"dataset size {n} is not divisible by batch_size {b}: "
                    f"the on-device scan would drop {n % b} examples. Pass "
                    "drop_remainder=True to accept that, or use fit() which "
                    "pads and masks the tail")
            dt = _dt.resolve(self.conf.dtype)
            xs = self._pack([_staging.stack(f, nb, b, dt, True, span_labels)
                             for f in feats])
            ys = self._pack([_staging.stack(l, nb, b, dt, False, span_labels)
                             for l in labs])
            if self._epoch_fn is None:
                self._epoch_fn = self._build_epoch_fn()
                self._record_build("train.epoch_fn", cache_attr="_epoch_fn")
            history = []
            for _ in range(epochs):
                with _tel.span("train.phase.prepare_s", span_labels):
                    self._key, sub = jax.random.split(self._key)
                    sentinel = self._ensure_sentinel()
                    start = jnp.int32(self.iteration)
                    args = (self.params, self.updater_state, self.state,
                            sentinel, start, sub, xs, ys)
                with self._timed_dispatch(span_labels):
                    (self.params, self.updater_state, self.state,
                     self._sentinel, losses) = self._epoch_fn(*args)
                _tel.record_dispatch("train.epoch_fn", self._epoch_fn, args,
                                     self._program_labels)
                del args
                self.iteration += nb
                self.epoch += 1
                self._count_passes(nb)
                # lazy device scalar — listeners calling score() get this
                # epoch's final loss without forcing a mid-chain host sync
                self._score = losses[-1]
                history.append(losses)
                self._notify_listeners(span_labels, "on_epoch_end")
            with _tel.span("train.phase.readback_s", span_labels):
                out = np.concatenate([np.asarray(h) for h in history])
                self._publish_layer_counters()
            self._score = float(out[-1])
            return out

    def fit(self, data, labels=None, epochs: int = 1, resilience=None):
        """DL4J ``fit()``: one fused train step a batch, over an iterator,
        one (Multi)DataSet, or ``(features, labels)`` arrays.

        ``resilience`` (a ``parallel.resilience.ResiliencePolicy``) wraps
        the epoch loop in the auto-resume driver: bounded retry-with-backoff
        on transient runtime failures (device loss / preemption-shaped
        ``XlaRuntimeError`` / iterator I/O errors) restoring model + updater
        + iterator state from the policy's crash-safe checkpointer, plus
        divergence escalation (rollback + LR backoff) after K consecutive
        sentinel-skipped steps."""
        if resilience is not None:
            from ..parallel.resilience import run_resilient_fit
            return run_resilient_fit(self, data, labels=labels,
                                     epochs=epochs, policy=resilience)
        if not self.params and not self.state:
            self.init()
        if self._fit_by_solver(data, labels, epochs):
            return self
        from ..runtime import faults as _faults
        it = self._iterator(data, labels)
        if self._train_step is None:
            self._train_step_fused = self.fused_updater_active()
            self._train_step = self._build_train_step(
                fused_cast=self._train_step_fused)
            # one dispatch decision per compiled step (zero silent
            # fallbacks — fused_epilogues.dispatch{decision=} discipline)
            from ..ops import fused_epilogues as _fe
            _fe.dispatch_updater(self.conf.dtype,
                                 has_penalty=self._uses_regularization())
            self._record_build("train.step", cache_attr="_train_step")
        fused = getattr(self, "_train_step_fused", False)
        # fused master-cast carry (ISSUE 16): ONE host-side cast per fit()
        # call; every later compute copy is emitted by the fused updater
        # write on-device (listener-side mutation of self.params mid-fit
        # is not supported under the fused step — same contract as
        # fit_on_device where the whole epoch is device-resident)
        params_c = _dt.cast_floating(
            self.params, _dt.resolve(self.conf.dtype)) if fused else None
        # the train.phase.* spans: shared scaffold on CompiledCacheMixin
        # (see caches.py, "phase tracing")
        span_labels = self._phase_labels()
        with _tel.span("train.phase.call_s", span_labels,
                       entry=f"{type(self).__name__}.fit"):
            for _ in range(epochs):
                for ds in self._timed_batches(it, span_labels):
                    with _tel.span("train.phase.stage_s", span_labels):
                        x, y, fm, lm = self._stage_batch(ds)
                    with _tel.span("train.phase.prepare_s", span_labels):
                        self._key, sub = jax.random.split(self._key)
                        if _faults.enabled():
                            _faults.trip("train.step")  # crash/preemption site
                            # float check FIRST: all-int inputs must not
                            # consume the injection's fire budget without
                            # poisoning anything
                            if any(jnp.issubdtype(a.dtype, jnp.floating)
                                   for a in jax.tree.leaves(x)) and \
                                    _faults.trip("train.nonfinite") \
                                    is not None:
                                x = jax.tree.map(
                                    lambda a: jnp.full_like(a, jnp.nan)
                                    if jnp.issubdtype(a.dtype, jnp.floating)
                                    else a, x)  # sentinel site
                        # traced, no retrace per step
                        step = jnp.asarray(self.iteration, dtype=jnp.int32)
                        sentinel = self._ensure_sentinel()
                        args = (self.params,) + ((params_c,) if fused else ()) \
                            + (self.updater_state, self.state, step, sub, x,
                               y, fm, lm, sentinel)
                    self._last_batch = x  # StatsListener activation sampling
                    with self._timed_dispatch(span_labels):
                        if fused:
                            (self.params, params_c, self.updater_state,
                             self.state, self._sentinel, loss) = \
                                self._train_step(*args)
                        else:
                            (self.params, self.updater_state, self.state,
                             self._sentinel, loss) = self._train_step(*args)
                    _tel.record_dispatch("train.step", self._train_step,
                                         args, self._program_labels)
                    del args
                    # keep the loss on device: score() syncs lazily, so the
                    # train loop never blocks on the host (async dispatch
                    # back-to-back)
                    self._score = loss
                    self.iteration += 1
                    self._count_passes(1)
                    self._notify_listeners(span_labels, "iteration_done",
                                           self.iteration, self.epoch)
                self.epoch += 1
                self._notify_listeners(span_labels, "on_epoch_end")
                it = self._iterator(data, labels)  # fresh pass
        return self

    # ----------------------------------------------------------- listeners
    def set_listeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def add_listener(self, l):
        self._listeners.append(l)
        return self

    # -------------------------------------------------- flat-param adapter
    def _flat_entries(self) -> List[Tuple[str, Tuple[str, ...]]]:
        """``(param key, path)`` of every leaf, DL4J layer then param-name
        order."""
        return [(k, path) for k, _ in self._keyed_layers()
                if k in self.params
                for path in _param_paths(self.params[k])]

    def params_flat(self) -> np.ndarray:
        """One contiguous fp vector, DL4J layer/param ordering."""
        parts = [np.asarray(_get_path(self.params[k], path)).ravel()
                 for k, path in self._flat_entries()]
        return np.concatenate(parts) if parts else np.zeros((0,), np.float32)

    def set_params_flat(self, vec):
        vec = np.asarray(vec)
        total = self.num_params()
        if vec.size != total:
            raise ValueError(f"param vector length {vec.size} != model {total}")
        off = 0
        new = dict(self.params)
        for k, path in self._flat_entries():
            a = _get_path(self.params[k], path)
            size = int(np.prod(a.shape))
            new[k] = _set_path(new[k], path, jnp.asarray(
                vec[off:off + size].reshape(a.shape), dtype=a.dtype))
            off += size
        self.params = new
        return self

    # --------------------------------------------------------------- serde
    def save(self, path, save_updater: bool = True, normalizer=None,
             iterator=None):
        from ..utils.serializer import save_model
        save_model(self, path, save_updater=save_updater,
                   normalizer=normalizer, iterator=iterator)

    @classmethod
    def load(cls, path, load_updater: bool = True):
        from ..utils.serializer import load_model
        model = load_model(path, load_updater=load_updater)
        if not isinstance(model, cls):
            raise TypeError(f"{path} holds a {type(model).__name__}, "
                            f"not a {cls.__name__}")
        return model
