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

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dt
from ..data.dataset import DataSet, DataSetIterator, NumpyDataSetIterator
from ..ops import losses as _loss
from . import engine as _engine
from . import microbatch as _micro
from .config import MultiLayerConfiguration
from .engine import _get_path, _is_loss_head  # noqa: F401 (_get_path: tests)


class MultiLayerNetwork(_engine.NetworkEngine):
    # invalidation also drops the rnn streaming pair: a carry captured
    # under the old dtype policy must not feed a retraced step
    _cache_attrs = ("_train_step", "_train_output_fn", "_epoch_fn",
                    "_rnn_step_fn", "_rnn_stream")

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.layers = conf.layers
        self._rnn_step_fn = None
        self._rnn_stream = None
        self._solver = None
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

    # ---------------------------------------- the shared host side's hooks
    def _keyed_layers(self):
        return ((str(i), l) for i, l in enumerate(self.layers))

    def _iterator(self, data, labels):
        return _as_iterator(data, labels)

    def _stage_batch(self, ds):
        return tuple(None if a is None else jnp.asarray(a)
                     for a in (ds.features, ds.labels, ds.features_mask,
                               ds.labels_mask))

    def _host_arrays(self, features, labels):
        return [np.asarray(features)], [np.asarray(labels)]

    def _pack(self, arrays):
        return arrays[0]

    def _epoch_masks(self):
        return None, None

    def _label_weight(self, x, y, fmask, lmask):
        return _micro.label_count_weight(lmask)

    def _fit_by_solver(self, data, labels, epochs) -> bool:
        """DL4J Solver.optimize path (§3.1): LBFGS/CG/line-search per batch
        instead of the fused SGD step, where the conf names one."""
        if self._out_layer is None:
            raise ValueError("last layer must be an OutputLayer/LossLayer to fit()")
        algo = getattr(self.conf, "optimization_algo", "SGD") or "SGD"
        if algo.upper() in ("SGD", "STOCHASTIC_GRADIENT_DESCENT"):
            return False
        from ..optimize.solvers import Solver
        if self._solver is None:
            self._solver = Solver(
                self, self.conf.optimization_algo,
                iterations=getattr(self.conf, "solver_iterations", 5),
                max_line_search_iterations=getattr(
                    self.conf, "max_line_search_iterations", 5))
        for _ in range(epochs):
            for ds in _as_iterator(data, labels):
                x, y, fm, lm = self._stage_batch(ds)
                self._last_batch = x  # StatsListener activation sampling
                self._key, sub = jax.random.split(self._key)
                self._score = self._solver.optimize(x, y, fm, lm, key=sub)
                self.iteration += 1
                for cb in self._listeners:
                    cb.iteration_done(self, self.iteration, self.epoch)
            self.epoch += 1
            for cb in self._listeners:
                cb.on_epoch_end(self)
        return True

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
