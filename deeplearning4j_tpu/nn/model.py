"""Sequential network engine.

TPU-native equivalent of DL4J's ``MultiLayerNetwork`` (reference:
``deeplearning4j-nn .../nn/multilayer/MultiLayerNetwork.java``† per SURVEY.md
§2.4/§3.1; reference mount was empty, citation upstream-relative, unverified).

Architecture (the §3.1 "TPU translation"): DL4J's per-op
Java→JNI→kernel round trip per layer per iteration becomes ONE jitted XLA
program per (topology, shapes): forward + backward + updater fused, buffers
donated. The "helper seam" (cuDNN/oneDNN) does not exist — XLA owns kernels.

Param/state layout: pytree ``{"0": {"W": ..., "b": ...}, "1": {...}}`` keyed
by layer index (stringified, stable across JSON). DL4J's flattened contiguous
param buffer is NOT the storage format (pytree-native is the right call on
TPU — SURVEY.md §7.3 item 5); ``params_flat()``/``set_params_flat()`` provide
the flat VIEW for import/serialization parity, ordered layer-by-layer with
DL4J's param-name order (W, b, gamma, beta).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dt
from .. import environment as _env
from . import caches as _caches
from ..data.dataset import DataSet, DataSetIterator, NumpyDataSetIterator
from ..ops import losses as _loss
from ..runtime import telemetry as _tel
from .config import MultiLayerConfiguration
from .layers.core import LossLayer, OutputLayer

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


class MultiLayerNetwork(_caches.CompiledCacheMixin):
    # invalidation also drops the rnn streaming pair: a carry captured
    # under the old dtype policy must not feed a retraced step
    _cache_attrs = ("_train_step", "_train_output_fn", "_epoch_fn",
                    "_rnn_step_fn", "_rnn_stream")

    def _replace_conf_dtype(self, dtype: str):
        return dataclasses.replace(self.conf, dtype=dtype)

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self.updater_state: Any = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self._listeners: List[Any] = []
        self._train_step = None
        self._train_output_fn = None
        self._rnn_step_fn = None
        self._rnn_stream = None
        self._epoch_fn = None
        self._solver = None
        self._inference_engine = None
        self._key = jax.random.PRNGKey(conf.seed)
        self._out_layer = self.layers[-1] if self.layers else None
        if self.layers and not _is_loss_head(self._out_layer):
            # duck-typed: any layer exposing loss_value is a loss head
            # (OutputLayer, LossLayer, CenterLossOutputLayer, Yolo2Output…);
            # a net without one can still do output()
            self._out_layer = None

    # ------------------------------------------------------------------ init
    def init(self) -> "MultiLayerNetwork":
        if self.conf.input_shape is None:
            raise ValueError("config needs input_type(...) to initialize")
        # mixed precision: 16-bit net dtypes keep fp32 master params
        # (cast to the compute dtype inside _forward)
        dtype = _dt.param_dtype(self.conf.dtype)
        shape = tuple(self.conf.input_shape)
        key = jax.random.PRNGKey(self.conf.seed)
        params, state = {}, {}
        for i, layer in enumerate(self.layers):
            key, sub = jax.random.split(key)
            p, s, shape = layer.initialize(sub, shape, dtype)
            if p:
                params[str(i)] = p
            if s:
                state[str(i)] = s
        self.params = params
        self.state = state
        self.updater_state = self.conf.updater.init_state(params) \
            if self.conf.updater else {}
        self._solver = None
        self._invalidate_compiled(cause="init")
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))

    # --------------------------------------------------------------- forward
    def _forward(self, params, x, state, *, train, rng, mask=None,
                 collect=False, remat_policy=None):
        """Pure layer stack walk. Returns (out, new_state, mask), or
        (acts_list, new_state, mask) with ``collect=True`` (acts_list is
        [input, layer0_out, ...] — feedForward semantics).

        ``remat_policy`` (a resolved ``nn.memory.RematPolicy``) wraps the
        walk in per-segment ``jax.checkpoint`` so the backward pass
        recomputes intra-segment activations instead of keeping them —
        only the train-step loss path passes it (the workspace_mode knob);
        identical numerics, identical rng stream (tested)."""
        dt = _dt.resolve(self.conf.dtype)
        if jnp.issubdtype(dt, jnp.floating) and \
                jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) and \
                jnp.asarray(x).dtype != dt:
            x = jnp.asarray(x, dt)  # cast inputs to the network dtype (DL4J)
        if _dt.is_mixed(self.conf.dtype):
            # fp32 masters -> compute-dtype working copy; grads flow back
            # through the cast and land in fp32
            params = _dt.cast_floating(params, dt)
        if remat_policy is not None and remat_policy.remat and not collect:
            return self._forward_remat(params, x, state, train=train,
                                       rng=rng, mask=mask,
                                       policy=remat_policy)
        new_state = dict(state)
        acts = [x]
        # BN+act epilogue fold (ISSUE 16): feedForward (collect=True) keeps
        # the true per-layer activations; the training/inference walk folds
        fold, skip = ({}, frozenset()) if collect \
            else self._epilogue_fold_plan()
        scopes = self._scope_names()
        for i, layer in enumerate(self.layers):
            si = str(i)
            p = params.get(si, {})
            s = state.get(si, {})
            if rng is not None and getattr(layer, "stochastic", True):
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            if i in skip:
                continue  # activation folded into the previous BN; its
                # rng split above still ran, so the stream is unchanged
            kw = {"fold_act": fold[i]} if i in fold else {}
            with jax.named_scope(scopes[i]):
                x, s_new, mask = layer.apply(p, x, s, train=train, rng=sub,
                                             mask=mask, **kw)
            if collect:
                acts.append(x)
            if s_new:
                new_state[si] = s_new
        return (acts if collect else x), new_state, mask

    def _scope_names(self):
        """The names under which the walks scope each layer's forward: the
        layer's own, or ``layer<i>``."""
        return [layer.name or f"layer{i}"
                for i, layer in enumerate(self.layers)]

    def _epilogue_fold_plan(self):
        """Static BN+activation fold plan (ISSUE 16): every
        BatchNormalization immediately followed by a parameter-free
        ActivationLayer with a kernel-foldable activation gets the act
        folded into its ``fused_epilogues.bn_act`` epilogue
        (``fold -> {bn_index: act}``) and the ActivationLayer becomes a
        pass-through (``skip``). Purely structural — cached per model;
        the dispatcher still decides fuse-vs-fallback per shape/dtype at
        trace time (fallback is bit-identical, so the fold itself never
        changes numerics)."""
        cached = getattr(self, "_epilogue_fold", None)
        if cached is not None:
            return cached
        from ..ops import fused_epilogues as _fe
        from .layers.conv import BatchNormalization
        from .layers.core import ActivationLayer
        fold, skip = {}, set()
        for i, layer in enumerate(self.layers[:-1]):
            nxt = self.layers[i + 1]
            if (isinstance(layer, BatchNormalization)
                    and type(nxt) is ActivationLayer
                    and _fe.foldable_act(nxt.activation,
                                         getattr(nxt, "alpha", None))):
                fold[i] = nxt.activation
                skip.add(i + 1)
        self._epilogue_fold = (fold, frozenset(skip))
        return self._epilogue_fold

    def _forward_remat(self, params, x, state, *, train, rng, mask, policy):
        """The same layer walk, segmented into ``policy.every``-layer
        chunks each wrapped in ``jax.checkpoint``: XLA keeps only segment
        boundaries (plus whatever the policy's ``saveable`` rule allows —
        e.g. matmul outputs under ``dots_saveable``) and rematerializes the
        rest during the backward pass. The rng stream threads THROUGH the
        segments with the exact split sequence of the plain walk, so remat
        on/off is bit-equivalent even with dropout. ``params`` arrive
        already cast (``_forward`` handles dtype policy before dispatching
        here)."""
        from . import memory as _memory
        new_state = dict(state)
        fold, skip = self._epilogue_fold_plan()
        scopes = self._scope_names()
        for s, e in _memory.segment_ranges(len(self.layers), policy.every):
            seg = list(range(s, e))

            def seg_fn(seg_params, seg_state, x, mask, rng, _seg=tuple(seg)):
                ns = {}
                for i in _seg:
                    layer = self.layers[i]
                    si = str(i)
                    if rng is not None and getattr(layer, "stochastic", True):
                        rng, sub = jax.random.split(rng)
                    else:
                        sub = None
                    if i in skip:  # folded act: split consumed, apply no-op
                        continue
                    kw = {"fold_act": fold[i]} if i in fold else {}
                    with jax.named_scope(scopes[i]):
                        x, s_new, mask = layer.apply(
                            seg_params.get(si, {}), x, seg_state.get(si, {}),
                            train=train, rng=sub, mask=mask, **kw)
                    if s_new:
                        ns[si] = s_new
                return x, ns, mask, rng

            seg_params = {str(i): params[str(i)] for i in seg
                          if str(i) in params}
            seg_state = {str(i): state[str(i)] for i in seg
                         if str(i) in state}
            x, ns, mask, rng = _memory.checkpoint(seg_fn, policy)(
                seg_params, seg_state, x, mask, rng)
            new_state.update(ns)
        return x, new_state, mask

    def _regularization(self, params):
        """Per-layer l1/l2 on weights (DL4J regularizes W, not b, by default)."""
        total = 0.0
        for i, layer in enumerate(self.layers):
            if getattr(layer, "frozen", False):
                continue  # FrozenLayer: no updates of any kind (DL4J)
            l1 = getattr(layer, "l1", 0.0) or self.conf.l1
            l2 = getattr(layer, "l2", 0.0) or self.conf.l2
            if not (l1 or l2):
                continue
            p = params.get(str(i), {})
            w = p.get("W")
            if w is None:
                continue
            if l1:
                total = total + l1 * jnp.sum(jnp.abs(w))
            if l2:
                total = total + 0.5 * l2 * jnp.sum(jnp.square(w))
        return total

    def _clip(self, grads):
        """Gradient normalization/clipping; returns ``(grads, clip_events)``
        — the shared ``gradnorm.clip_with_events`` pipeline (the sentinel
        accumulates the events as telemetry)."""
        from . import gradnorm as _gn
        return _gn.clip_with_events(
            self.conf.gradient_normalization,
            self.conf.gradient_normalization_threshold,
            self.conf.gradient_clip_value, self.conf.gradient_clip_l2, grads)

    # ------------------------------------------------------------- train step
    def _build_loss_fn(self):
        """The pure training loss ``(params, bn_state, key, x, y, fmask,
        lmask) -> (loss, new_bn_state)`` the train step differentiates —
        factored out so ``nn/memory.py`` can account its forward→backward
        residuals without building a step. Applies the conf's
        ``workspace_mode`` remat policy to the forward walk."""
        out_layer = self._out_layer
        ol_key = str(len(self.layers) - 1)
        center_loss = hasattr(out_layer, "update_centers")
        from . import memory as _memory
        policy = _memory.resolve_policy(
            getattr(self.conf, "workspace_mode", None))

        def loss_fn(p, bn_state, key, x, y, fmask, lmask):
            # the scope names the forward's operations in a device trace;
            # its transpose shows as transpose(jvp(forward))
            with jax.named_scope("forward"):
                out, new_bn, out_mask = self._forward(
                    p, x, bn_state, train=True, rng=key, mask=fmask,
                    remat_policy=policy)
                # intersect, don't override: an explicit label mask (e.g. the
                # DP pad mask) and the propagated feature mask must BOTH hold
                lm = _loss.combine_masks(lmask, out_mask)
                if center_loss:
                    # CenterLossOutputLayer stashes its input features in the
                    # state aux channel; pull them out (the key must NOT leak
                    # into the persisted state tree) and EMA-update centers
                    # outside the gradient
                    st = dict(new_bn[ol_key])
                    feats = st.pop("__features__")
                    centers = bn_state[ol_key]["centers"]
                    st["centers"] = jax.lax.stop_gradient(
                        out_layer.update_centers(
                            centers, jax.lax.stop_gradient(feats), y))
                    new_bn = {**new_bn, ol_key: st}
                    data_loss = out_layer.loss_value(
                        out, y, mask=lm,
                        weights=getattr(out_layer, "loss_weights", None),
                        features=feats,
                        centers=jax.lax.stop_gradient(centers))
                else:
                    data_loss = out_layer.loss_value(
                        out, y, mask=lm,
                        weights=getattr(out_layer, "loss_weights", None))
                return data_loss + self._regularization(p), new_bn

        return loss_fn

    def _uses_regularization(self) -> bool:
        """Any l1/l2 penalty configured (conf-level or per-layer)? Gates
        the mixed-precision cast hoist in ``_build_train_step`` — the
        regularization term reads the params the loss fn is handed, so the
        hoist (which hands it compute-dtype copies) only applies when the
        term is identically zero."""
        if self.conf.l1 or self.conf.l2:
            return True
        return any((getattr(l, "l1", 0.0) or getattr(l, "l2", 0.0))
                   for l in self.layers)

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
        with ``ComputationGraph`` and, from the gradient on, ``SameDiff``).
        ``accum_steps=k`` splits the batch into k microbatches and
        accumulates the mean gradient via ``lax.scan`` before the SINGLE
        updater application (see ``nn/microbatch.py`` for the exactness
        contract) — peak activation memory drops to one microbatch, so
        global batch can grow past HBM. The conf's
        ``workspace_mode`` remat policy (``nn/memory.py``) composes: inside
        each microbatch, intra-segment activations are recomputed in the
        backward pass instead of cached.

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
        from . import microbatch as _micro
        from . import trainstep as _ts
        frozen_keys = frozenset(str(i) for i, l in enumerate(self.layers)
                                if isinstance(l, FrozenLayer))
        step = _ts.engine_step(
            self, self._build_loss_fn(), frozen_keys,
            lambda x, y, fm, lm: _micro.label_count_weight(lm),
            accum_steps, grad_transform, fused_cast)
        # donate params/opt/bn buffers: in-place update on device (workspace
        # arenas' moral equivalent, handled by XLA)
        return jax.jit(step,
                       donate_argnums=(0, 1, 2, 3) if fused_cast
                       else (0, 1, 2),
                       compiler_options=_env.engine_compiler_options())

    # ------------------------------------------------- on-device epoch loop
    def _build_epoch_fn(self):
        """lax.scan of the fused train step over a device-resident batch
        stack — one XLA launch per epoch (see ComputationGraph.
        _build_epoch_fn for the rationale; same contract, singular
        batch arity). When the fused master-cast updater is active
        (ISSUE 16) the scan body carries the compute-dtype ``params_c``
        copy: the masters are cast ONCE per epoch launch and every
        subsequent copy is emitted by the fused updater write — the
        per-scan-step cast sweep is gone. External signature unchanged
        (masters in, masters out)."""
        # one dispatch decision per compiled program, as ``fit`` counts it
        from ..ops import fused_epilogues as _fe
        from . import trainstep as _ts
        _fe.dispatch_updater(self.conf.dtype,
                             has_penalty=self._uses_regularization())
        fused = self.fused_updater_active()
        step = self._build_train_step(fused_cast=fused).__wrapped__
        return jax.jit(_ts.build_epoch(step, fused,
                                       _dt.resolve(self.conf.dtype),
                                       (None, None)),
                       donate_argnums=(0, 1, 2, 3),
                       compiler_options=_env.engine_compiler_options())

    def fit_on_device(self, features, labels, epochs: int = 1,
                      batch_size: Optional[int] = None,
                      drop_remainder: bool = False) -> np.ndarray:
        """Compiled on-device training (ComputationGraph.fit_on_device
        contract): data reshaped to [n_batches, B, ...], uploaded once,
        scanned per epoch; returns the loss history. A non-divisible
        dataset RAISES unless ``drop_remainder=True`` explicitly discards
        the tail (silent data loss was r3's recorded footgun — VERDICT
        weak #5). Masked datasets must use fit()."""
        span_labels = self._phase_labels()
        with _tel.span("train.phase.call_s", span_labels,
                       entry="MultiLayerNetwork.fit_on_device"):
            if not self.params and not self.state:
                self.init()
            x = np.asarray(features)
            y = np.asarray(labels)
            n = x.shape[0]
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

            def stack(a, cast):
                with _tel.span("train.phase.stage_s", span_labels):
                    a = a[:nb * b].reshape((nb, b) + a.shape[1:])
                    if cast and np.issubdtype(a.dtype, np.floating) and \
                            jnp.issubdtype(dt, jnp.floating):
                        a = a.astype(dt)
                    return jax.device_put(jnp.asarray(a))
            xs = stack(x, True)
            ys = stack(y, False)
            if getattr(self, "_epoch_fn", None) is None:
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
                self._score = losses[-1]  # lazy device scalar for listeners
                history.append(losses)
                self._notify_listeners(span_labels, "on_epoch_end")
            with _tel.span("train.phase.readback_s", span_labels):
                out = np.concatenate([np.asarray(h) for h in history])
            self._score = float(out[-1])
            return out

    def fit(self, data, labels=None, epochs: int = 1,
            resilience=None) -> "MultiLayerNetwork":
        """DL4J fit(): accepts DataSetIterator, DataSet, or (features, labels).

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
        if self._out_layer is None:
            raise ValueError("last layer must be an OutputLayer/LossLayer to fit()")
        algo = getattr(self.conf, "optimization_algo", "SGD") or "SGD"
        if algo.upper() not in ("SGD", "STOCHASTIC_GRADIENT_DESCENT"):
            return self._fit_with_solver(data, labels, epochs)
        from ..runtime import faults as _faults
        it = _as_iterator(data, labels)
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
                       entry="MultiLayerNetwork.fit"):
            for _ in range(epochs):
                for ds in self._timed_batches(it, span_labels):
                    with _tel.span("train.phase.stage_s", span_labels):
                        x = jnp.asarray(ds.features)
                        y = jnp.asarray(ds.labels)
                        fm = None if ds.features_mask is None \
                            else jnp.asarray(ds.features_mask)
                        lm = None if ds.labels_mask is None \
                            else jnp.asarray(ds.labels_mask)
                    with _tel.span("train.phase.prepare_s", span_labels):
                        self._key, sub = jax.random.split(self._key)
                        if _faults.enabled():
                            _faults.trip("train.step")  # crash/preemption site
                            # float check FIRST: a non-float input must not
                            # consume the injection's fire budget without
                            # poisoning anything
                            if jnp.issubdtype(x.dtype, jnp.floating) and \
                                    _faults.trip("train.nonfinite") \
                                    is not None:
                                x = jnp.full_like(x, jnp.nan)  # sentinel site
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
                    self._notify_listeners(span_labels, "iteration_done",
                                           self.iteration, self.epoch)
                self.epoch += 1
                self._notify_listeners(span_labels, "on_epoch_end")
                it = _as_iterator(data, labels)  # fresh pass
        return self

    def _fit_with_solver(self, data, labels, epochs: int
                         ) -> "MultiLayerNetwork":
        """DL4J Solver.optimize path (§3.1): LBFGS/CG/line-search per batch
        instead of the fused SGD step."""
        from ..optimize.solvers import Solver
        if self._solver is None:
            self._solver = Solver(
                self, self.conf.optimization_algo,
                iterations=getattr(self.conf, "solver_iterations", 5),
                max_line_search_iterations=getattr(
                    self.conf, "max_line_search_iterations", 5))
        it = _as_iterator(data, labels)
        for _ in range(epochs):
            for ds in it:
                x = jnp.asarray(ds.features)
                y = jnp.asarray(ds.labels)
                fm = None if ds.features_mask is None else \
                    jnp.asarray(ds.features_mask)
                lm = None if ds.labels_mask is None else \
                    jnp.asarray(ds.labels_mask)
                self._last_batch = x  # StatsListener activation sampling
                self._key, sub = jax.random.split(self._key)
                self._score = self._solver.optimize(x, y, fm, lm, key=sub)
                self.iteration += 1
                for cb in self._listeners:
                    cb.iteration_done(self, self.iteration, self.epoch)
            self.epoch += 1
            for cb in self._listeners:
                cb.on_epoch_end(self)
            it = _as_iterator(data, labels)
        return self

    def feed_forward(self, x, train: bool = False, rng=None):
        """Per-layer activations for input ``x`` (DL4J ``feedForward()``:
        returns the activation of every layer, input first). ``rng`` feeds
        stochastic layers when ``train=True`` (None = deterministic)."""
        acts, _, _ = self._forward(self.params, jnp.asarray(x), self.state,
                                   train=train, rng=rng, collect=True)
        return acts

    # ------------------------------------------------------------- inference
    def output(self, x, train: bool = False):
        """Forward pass to output activations (DL4J ``output()``).

        ``train=False`` (serving) routes through the bucketed AOT
        :meth:`inference_engine`, so ragged request sizes pad to a bounded
        bucket set instead of retracing per distinct batch size.
        ``train=True`` runs stochastic layers (dropout fires) with a fresh
        key from the model's rng stream — its own cached trace, keyed on
        the flag."""
        if not train:
            return self.inference_engine().output(x)
        fn = self._train_output_fn
        if fn is None:
            fn = self._train_output_fn = jax.jit(
                lambda params, state, x, rng: self._forward(
                    params, x, state, train=True, rng=rng)[0])
            self._record_build("train.output_fn",
                               cache_attr="_train_output_fn")
        self._key, sub = jax.random.split(self._key)
        return np.asarray(fn(self.params, self.state, jnp.asarray(x), sub))

    def predict(self, x) -> np.ndarray:
        """Class indices (DL4J ``predict()``)."""
        return np.argmax(self.output(x), axis=-1)

    def quantize_params(self, mode: str = "int8") -> dict:
        """Post-training per-channel int8 quantization of the opted-in
        matmul/conv weights (ISSUE 9): a layer walk mirroring the
        decode/remat pattern — every layer whose ``quantize_spec`` names
        weights gets them replaced by ``ops.quantize.QuantizedTensor``;
        norms, biases and embeddings stay f32. Returns a NEW params tree
        (the model's own f32 params are untouched — training and f32
        serving keep working); the serving engines call this at warmup
        (``InferenceEngine(quantize="int8")``) so every AOT bucket
        executable compiles the quantized graph."""
        if mode != "int8":
            raise ValueError(f"unknown quantization mode {mode!r} "
                             "(expected 'int8')")
        from ..ops import quantize as _q
        return _q.quantize_model_params(self)[0]

    # ----------------------------------------------------- rnnTimeStep state
    def rnn_time_step(self, x):
        """Stateful streaming inference (DL4J ``rnnTimeStep()``): feed
        [B,T,F] (or [B,F] for a single step) chunks; recurrent hidden state
        persists across calls until :meth:`rnn_clear_previous_state`."""
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]  # [B,1,F]
        if self._rnn_stream is None:
            self._rnn_stream = {}
        if self._rnn_step_fn is None:
            self._rnn_step_fn = self._build_rnn_step()
            self._record_build("train.rnn_step_fn",
                               cache_attr="_rnn_step_fn")
        out, self._rnn_stream = self._rnn_step_fn(
            self.params, self.state, x, self._rnn_stream)
        out = np.asarray(out)
        return out[:, -1, :] if (single and out.ndim == 3) else out

    def rnn_clear_previous_state(self):
        self._rnn_stream = None

    def _build_rnn_step(self):
        recurrent = {str(i): l for i, l in enumerate(self.layers)
                     if getattr(l, "is_recurrent", lambda: False)()}
        for si, l in recurrent.items():
            if not getattr(l, "supports_streaming", True):
                raise ValueError(
                    f"rnnTimeStep() is not supported with layer {si} "
                    f"({l.kind}): bidirectional layers need the full future "
                    "sequence (DL4J throws here too); use output() instead")

        def step(params, state, x, stream):
            if _dt.is_mixed(self.conf.dtype):
                cdt = _dt.resolve(self.conf.dtype)
                params = _dt.cast_floating(params, cdt)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
                    x = jnp.asarray(x, cdt)  # match _forward's input cast
            new_stream = dict(stream)
            for i, layer in enumerate(self.layers):
                si = str(i)
                p = params.get(si, {})
                s = state.get(si, {})
                if si in recurrent:
                    carry = stream.get(si)
                    if carry is None:
                        carry = layer.init_stream_state(p, x.shape[0])
                    x, carry = layer.scan_with_state(p, x, carry,
                                                     grad_path=False)
                    new_stream[si] = carry
                else:
                    x, _, _ = layer.apply(p, x, s, train=False, rng=None)
            return x, new_stream

        # not jitted with a fixed signature: stream dict shape varies on the
        # first call; jit would retrace once per (carry presence) pattern —
        # fine, there are at most two patterns
        return jax.jit(step)

    # -------------------------------------- autoregressive decode (ISSUE 8)
    # Pure prefill / one-token decode walks over the layer stack, threading
    # per-layer (k, v) KV caches + shared per-row lengths. Semantics:
    # prefix-LM — the prompt attends bidirectionally over itself (prefill =
    # ONE pass of the existing flash kernel), every generated token attends
    # over everything before it plus itself. ``serving.engine
    # .GenerativeEngine`` AOT-compiles these per (slot x cache-length x
    # prompt-length) bucket; the parity suite asserts N-step decode ==
    # :meth:`_full_context` recompute.
    def _decode_layer_plan(self, params):
        """(layer, 'cache'|'pointwise') per layer; raises for layers that
        can do neither — the decode walk must be exact, not best-effort."""
        plan = []
        for i, layer in enumerate(self.layers):
            p = params.get(str(i), {})
            if layer.decode_cache_spec(p, 1, 8, jnp.float32) is not None:
                plan.append((layer, "cache"))
            elif getattr(layer, "decode_pointwise", False):
                plan.append((layer, "pointwise"))
            else:
                raise ValueError(
                    f"layer {i} ({layer.kind!r}) cannot run in the "
                    "autoregressive decode walk (neither KV-cached nor "
                    "time-pointwise)")
        return plan

    def decode_cache_spec(self, batch: int, cache_len: int,
                          kv_quant: bool = False) -> dict:
        """{layer_index: {"k": aval, "v": aval}} for the KV-cached layers
        (compute dtype — what the decode executables actually hold).
        ``kv_quant`` (ISSUE 9): int8 cache values with per-row f32
        scales stored beside them — halves the cache HBM per slot."""
        dt = _dt.resolve(self.conf.dtype)
        spec = {}
        for i, layer in enumerate(self.layers):
            s = layer.decode_cache_spec(self.params.get(str(i), {}),
                                        batch, cache_len, dt,
                                        kv_quant=kv_quant)
            if s is not None:
                spec[str(i)] = s
        if not spec:
            raise ValueError("model has no KV-cached layers; nothing to "
                             "decode incrementally")
        return spec

    def init_decode_cache(self, batch: int, cache_len: int,
                          kv_quant: bool = False) -> dict:
        """Zero-initialized decode cache pytree for one slot batch."""
        return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                            self.decode_cache_spec(batch, cache_len,
                                                   kv_quant=kv_quant))

    def paged_cache_spec(self, n_pages: int, page_size: int,
                         kv_quant: bool = False) -> dict:
        """Paged-pool twin of :meth:`decode_cache_spec` (ISSUE 12):
        ``{layer_index: {"k": [n_pages*page_size, H, d] aval, ...}}`` —
        each KV-cached layer's cache as a pool of token rows owned by the
        serving page allocator instead of per-slot contiguous buckets.
        Int8 pools carry their per-row f32 scales as d=1 page payloads."""
        base = self.decode_cache_spec(1, 1, kv_quant=kv_quant)
        rows = int(n_pages) * int(page_size)
        return {si: {name: jax.ShapeDtypeStruct(
                        (rows, a.shape[1], a.shape[3]), a.dtype)
                     for name, a in leaves.items()}
                for si, leaves in base.items()}

    def init_paged_cache(self, n_pages: int, page_size: int,
                         kv_quant: bool = False) -> dict:
        """Zero-initialized paged KV pool pytree (page 0 = the reserved
        zero page the allocator points unallocated table entries at)."""
        return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                            self.paged_cache_spec(n_pages, page_size,
                                                  kv_quant=kv_quant))

    def decode_token_features(self, tokens, dtype=None):
        """On-device twin of the serving host featurizer: int32 token ids
        [B] -> next-step decode input [B, 1, F]. Must stay bit-identical
        to ``ContinuousBatcher._one_hot`` (``f[token % F] = 1.0``) so the
        fused multi-token decode loop matches the host oracle exactly."""
        shape = self.conf.input_shape
        if not (isinstance(shape, (tuple, list)) and len(shape) == 2):
            raise ValueError(
                "decode_token_features needs a recurrent [T, F] input "
                f"type; model input_shape is {shape!r}")
        f = int(shape[1])
        dt = _dt.resolve(self.conf.dtype) if dtype is None else dtype
        toks = jnp.asarray(tokens, jnp.int32) % f
        return jax.nn.one_hot(toks, f, dtype=dt)[:, None, :]

    def _decode_cast(self, params, x):
        dt = _dt.resolve(self.conf.dtype)
        if jnp.issubdtype(dt, jnp.floating) and \
                jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) and \
                jnp.asarray(x).dtype != dt:
            x = jnp.asarray(x, dt)
        if _dt.is_mixed(self.conf.dtype):
            params = _dt.cast_floating(params, dt)
        return params, x

    def _prefill(self, params, x, state, caches, lengths):
        """Prompt phase: ``x`` [B, T, F] end-padded, ``lengths`` [B] true
        prompt lengths. Fills the per-layer caches (positions [0, T) —
        rows past a row's length are masked by the decode-side length
        bias) and returns (y [B, T, out], new_caches)."""
        params, x = self._decode_cast(params, x)
        T = x.shape[1]
        lengths = jnp.asarray(lengths)
        mask = (jnp.arange(T)[None, :] <
                lengths[:, None]).astype(jnp.float32)
        new_caches = {}
        for i, (layer, kind) in enumerate(self._decode_layer_plan(params)):
            si = str(i)
            p = params.get(si, {})
            s = state.get(si, {})
            if kind == "cache":
                x, c = layer.prefill(p, x, s, cache=caches[si],
                                     lengths=lengths, mask=mask)
                new_caches[si] = c
            else:
                x, _, _ = layer.apply(p, x, s, train=False, rng=None,
                                      mask=mask)
        return x, new_caches

    def _decode_step(self, params, x, state, caches, lengths, write=None,
                     page_table=None, page_size=0):
        """One decode window: ``x`` [B, Tq, F] (Tq = 1 for plain decode,
        Tq = k for a speculative verify window — window-causal inside the
        attention layers), ``lengths`` [B] = tokens already cached BEFORE
        this window. Appends the window's k/v at positions ``lengths``
        onward (rows with ``write == 0`` keep their caches bit-identical
        — inactive serving slots) and returns (y [B, Tq, out],
        new_caches). The caller advances ``lengths`` afterwards.
        ``page_table``/``page_size`` (ISSUE 12): the caches are paged
        pools and the per-slot page table rides through the cached
        layers as gather/scatter indices."""
        params, x = self._decode_cast(params, x)
        lengths = jnp.asarray(lengths)
        new_caches = {}
        for i, (layer, kind) in enumerate(self._decode_layer_plan(params)):
            si = str(i)
            p = params.get(si, {})
            s = state.get(si, {})
            if kind == "cache":
                x, c = layer.decode_step(p, x, s, cache=caches[si],
                                         lengths=lengths, write=write,
                                         page_table=page_table,
                                         page_size=page_size)
                new_caches[si] = c
            else:
                x, c = layer.decode_step(p, x, s, cache=None,
                                         lengths=lengths)
        return x, new_caches

    def _full_context(self, params, x, state, prompt_lengths, lengths):
        """The naive full-recompute oracle (and the bench baseline): one
        quadratic forward over the whole running sequence under the
        prefix-LM mask — position j is visible to position i iff
        ``j < prompt_len`` (bidirectional prompt) or ``j <= i`` (causal
        generation), and j is within the row's ``lengths``. Equals the
        incremental prefill+decode path within dtype tolerance."""
        params, x = self._decode_cast(params, x)
        T = x.shape[1]
        prompt_lengths = jnp.asarray(prompt_lengths)
        lengths = jnp.asarray(lengths)
        ii = jnp.arange(T)[:, None]
        jj = jnp.arange(T)[None, :]
        allowed = ((jj < prompt_lengths[:, None, None]) | (jj <= ii)) \
            & (jj < lengths[:, None, None])
        neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
        bias = jnp.where(allowed[:, None], 0.0, neg)        # [B,1,T,T]
        key_bias = jnp.where(jnp.arange(T)[None, None, None, :] <
                             lengths[:, None, None, None], 0.0, neg)
        for i, (layer, kind) in enumerate(self._decode_layer_plan(params)):
            si = str(i)
            p = params.get(si, {})
            s = state.get(si, {})
            if kind == "cache":
                x = layer.full_context(p, x, s, bias=bias,
                                       key_bias=key_bias)
            else:
                x, _, _ = layer.apply(p, x, s, train=False, rng=None,
                                      mask=None)
        return x

    def score(self, ds: Optional[DataSet] = None) -> float:
        """Loss value; with no argument, the score of the last fit batch.
        Includes the l1/l2 regularization penalty, matching the fit-loop
        score (DL4J computeScore includes regularization on both paths)."""
        if ds is None:
            if self._score is not None and not isinstance(self._score, float):
                self._score = float(self._score)  # sync point, only on demand
            return self._score
        out, st, _ = self._forward(self.params, jnp.asarray(ds.features),
                                   self.state, train=True, rng=None,
                                   mask=None if ds.features_mask is None
                                   else jnp.asarray(ds.features_mask))
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        if hasattr(self._out_layer, "update_centers"):
            # same quantity as the fit loop: CE + center penalty
            ol_key = str(len(self.layers) - 1)
            loss = self._out_layer.loss_value(
                out, jnp.asarray(ds.labels), mask=lm,
                features=st[ol_key]["__features__"],
                centers=self.state[ol_key]["centers"])
        else:
            loss = self._out_layer.loss_value(
                out, jnp.asarray(ds.labels), mask=lm)
        return float(loss + self._regularization(self.params))

    def evaluate(self, data, labels=None):
        """Classification evaluation over an iterator (DL4J ``evaluate()``)."""
        from ..eval.evaluation import Evaluation
        ev = Evaluation()
        for ds in _as_iterator(data, labels):
            out = self.output(ds.features)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # -------------------------------------------------------------- listeners
    def set_listeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def add_listener(self, l):
        self._listeners.append(l)
        return self

    # ---------------------------------------------------- flat-param adapter
    def _flat_entries(self) -> List[Tuple[str, Tuple[str, ...]]]:
        out = []
        for i in range(len(self.layers)):
            si = str(i)
            if si in self.params:
                out.extend((si, path) for path in _param_paths(self.params[si]))
        return out

    def params_flat(self) -> np.ndarray:
        """One contiguous fp vector, DL4J layer/param ordering."""
        parts = [np.asarray(_get_path(self.params[si], path)).ravel()
                 for si, path in self._flat_entries()]
        return np.concatenate(parts) if parts else np.zeros((0,), np.float32)

    def set_params_flat(self, vec) -> "MultiLayerNetwork":
        vec = np.asarray(vec)
        total = self.num_params()
        if vec.size != total:
            raise ValueError(f"param vector length {vec.size} != model {total}")
        off = 0
        new = dict(self.params)
        for si, path in self._flat_entries():
            a = _get_path(self.params[si], path)
            size = int(np.prod(a.shape))
            new[si] = _set_path(new[si], path, jnp.asarray(
                vec[off:off + size].reshape(a.shape), dtype=a.dtype))
            off += size
        self.params = new
        return self

    # ------------------------------------------------------------------ serde
    def save(self, path, save_updater: bool = True, normalizer=None,
             iterator=None):
        from ..utils.serializer import save_model
        save_model(self, path, save_updater=save_updater,
                   normalizer=normalizer, iterator=iterator)

    @staticmethod
    def load(path, load_updater: bool = True):
        from ..utils.serializer import load_model
        model = load_model(path, load_updater=load_updater)
        if not isinstance(model, MultiLayerNetwork):
            raise TypeError(f"{path} holds a {type(model).__name__}, "
                            "not a MultiLayerNetwork")
        return model


def _is_loss_head(l) -> bool:
    """True when the (FrozenLayer-unwrapped) layer really implements
    loss_value — FrozenLayer delegates it unconditionally, so probe the
    wrapped layer, not the wrapper."""
    inner = getattr(l, "layer", None)
    while inner is not None and hasattr(l, "frozen"):
        l, inner = inner, getattr(inner, "layer", None)
    return hasattr(l, "loss_value")


def _as_iterator(data, labels=None) -> DataSetIterator:
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        return _SingleIterator(data)
    if labels is not None:
        return NumpyDataSetIterator(data, labels, batch_size=len(np.asarray(data)))
    raise TypeError(f"cannot make a DataSetIterator from {type(data)}")


class _SingleIterator(DataSetIterator):
    def __init__(self, ds: DataSet):
        self._ds = ds

    def batch_size(self):
        return self._ds.num_examples()

    def __iter__(self):
        yield self._ds
