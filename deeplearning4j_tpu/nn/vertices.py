"""Graph vertices for the ComputationGraph DAG engine.

TPU-native equivalents of DL4J's ``GraphVertex`` runtime classes (reference:
``deeplearning4j-nn .../nn/graph/vertex/impl/{MergeVertex,ElementWiseVertex,
SubsetVertex,ScaleVertex,ShiftVertex,L2NormalizeVertex,StackVertex,
UnstackVertex,LastTimeStepVertex,ReverseTimeSeriesVertex,
DuplicateToTimeSeriesVertex,PreprocessorVertex}.java``† per SURVEY.md §2.4
row "ComputationGraph"; reference mount was empty, citations
upstream-relative, unverified).

Divergence from the reference (deliberate, TPU-first): DL4J vertices are
stateful runtime objects with doForward/doBackward pairs; here a vertex is a
pure config dataclass whose ``apply`` traces into the ONE fused XLA program —
backward comes from jax autodiff, epsilon-accumulation across fan-out is
handled by the chain rule, not hand-written vertex backprop.

Protocol (multi-input generalization of the Layer protocol):
- ``initialize(key, input_shapes: [tuple,...], dtype)
     -> (params, state, output_shape)``  — shapes EXCLUDE the batch dim.
- ``apply(params, xs: [Array,...], state, train, rng, masks: [mask,...])
     -> (y, new_state, out_mask)``
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp

from .layers.base import Layer

VERTICES: Dict[str, type] = {}


def vertex(kind: str):
    """Class decorator: dataclass vertex registered for serde."""
    def deco(cls):
        cls = dataclasses.dataclass(cls)
        cls.kind = kind
        VERTICES[kind] = cls
        return cls
    return deco


class GraphVertex:
    kind = "base"

    @property
    def stochastic(self):
        """Whether apply() consumes a PRNG key — the engine only splits keys
        for stochastic vertices (see Layer.stochastic for why). Built-in
        vertices are deterministic (exact-type check below, so user vertex
        subclasses keep the conservative True default); LayerVertex
        overrides this to delegate to its layer."""
        return type(self) not in _DETERMINISTIC_VERTICES

    def initialize(self, key, input_shapes: List[Tuple[int, ...]], dtype):
        """-> (params, state, output_shape)"""
        return {}, {}, tuple(input_shapes[0])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        """-> (y, new_state, out_mask)"""
        raise NotImplementedError

    def has_params(self) -> bool:
        return False

    # -- serde ---------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d = {"kind": self.kind}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            d[f.name] = list(v) if isinstance(v, tuple) else v
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "GraphVertex":
        d = dict(d)
        kind = d.pop("kind")
        if kind == "layer":
            return LayerVertex(layer=Layer.from_dict(d["layer"]))
        if kind not in VERTICES:
            raise ValueError(f"Unknown vertex kind {kind!r}; known: "
                             f"{sorted(VERTICES)}")
        cls = VERTICES[kind]
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: (tuple(v) if isinstance(v, list) else v)
                  for k, v in d.items() if k in names}
        return cls(**kwargs)


def _first_mask(masks):
    if not masks:
        return None
    for m in masks:
        if m is not None:
            return m
    return None


@vertex("layer")
class LayerVertex(GraphVertex):
    """Wraps a Layer as a vertex (DL4J ``LayerVertex``): single-input, or
    as many inputs as the layer's ``n_inputs`` says (a loss head that reads
    the token ids beside the hidden states), handed over as a list.

    Auto-flatten: when a Dense/Output layer receives a rank-3 CNN shape, the
    input is flattened first (DL4J's CnnToFeedForwardPreProcessor inserted by
    the graph builder). The decision is recomputed at initialize() from the
    propagated shape — not serialized.
    """
    layer: Layer = None

    def __post_init__(self):
        self._flatten = False

    @property
    def stochastic(self):
        return getattr(self.layer, "stochastic", True)

    def has_params(self) -> bool:
        return self.layer.has_params()

    def initialize(self, key, input_shapes, dtype):
        n = getattr(self.layer, "n_inputs", 1)
        if len(input_shapes) != n:
            raise ValueError(f"LayerVertex({self.layer.kind}) takes {n} "
                             f"input(s), got {len(input_shapes)}")
        if n > 1:
            return self.layer.initialize(
                key, [tuple(s) for s in input_shapes], dtype)
        from .layers.core import DenseLayer, OutputLayer
        shape = tuple(input_shapes[0])
        self._flatten = (isinstance(self.layer, (DenseLayer, OutputLayer))
                         and len(shape) == 3)
        if self._flatten:
            flat = 1
            for s in shape:
                flat *= int(s)
            shape = (flat,)
        return self.layer.initialize(key, shape, dtype)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None,
              fold_act=None):
        x = xs[0] if getattr(self.layer, "n_inputs", 1) == 1 else list(xs)
        if self._flatten:
            x = x.reshape(x.shape[0], -1)
        mask = _first_mask(masks)
        if fold_act is not None:  # BN+act epilogue fold (ISSUE 16)
            return self.layer.apply(params, x, state, train=train, rng=rng,
                                    mask=mask, fold_act=fold_act)
        return self.layer.apply(params, x, state, train=train, rng=rng,
                                mask=mask)

    def to_dict(self):
        return {"kind": "layer", "layer": self.layer.to_dict()}


@vertex("merge")
class MergeVertex(GraphVertex):
    """Concatenate along the feature/channel axis (DL4J ``MergeVertex``).

    The merge axis is the feature/channel axis of each activation kind:
    [B,F] -> 1; recurrent [B,T,F] -> 2; CNN -> 1 for NCHW, 3 for NHWC
    (DL4J is NCHW/[B,F,T]-centric and always merges axis 1; our recurrent
    convention is [B,T,F], recorded divergence).
    """
    data_format: str = "NCHW"

    def _axis(self, ndim):
        if ndim <= 3:
            return ndim - 1
        return 1 if self.data_format == "NCHW" else ndim - 1

    def initialize(self, key, input_shapes, dtype):
        shapes = [tuple(s) for s in input_shapes]
        for s in shapes[1:]:
            if len(s) != len(shapes[0]):
                raise ValueError(f"merge rank mismatch: {shapes}")
        ax = self._axis(len(shapes[0]) + 1) - 1  # shape tuples have no batch dim
        for s in shapes[1:]:
            for d in range(len(s)):
                if d != ax and int(s[d]) != int(shapes[0][d]):
                    raise ValueError(
                        f"merge non-concat dim {d} mismatch: {shapes}")
        merged = list(shapes[0])
        merged[ax] = sum(int(s[ax]) for s in shapes)
        return {}, {}, tuple(merged)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return (jnp.concatenate(xs, axis=self._axis(xs[0].ndim)), state,
                _first_mask(masks))


@vertex("elementwise")
class ElementWiseVertex(GraphVertex):
    """Pointwise combine: Add/Subtract/Product/Average/Max
    (DL4J ``ElementWiseVertex``). The residual-connection workhorse."""
    op: str = "add"

    def initialize(self, key, input_shapes, dtype):
        return {}, {}, tuple(input_shapes[0])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        op = self.op.lower()
        if op == "add":
            y = xs[0]
            for x in xs[1:]:
                y = y + x
        elif op == "subtract":
            if len(xs) != 2:
                raise ValueError("subtract takes exactly 2 inputs")
            y = xs[0] - xs[1]
        elif op in ("product", "mult"):
            y = xs[0]
            for x in xs[1:]:
                y = y * x
        elif op in ("average", "avg"):
            y = sum(xs) / len(xs)
        elif op == "max":
            y = xs[0]
            for x in xs[1:]:
                y = jnp.maximum(y, x)
        elif op == "min":
            y = xs[0]
            for x in xs[1:]:
                y = jnp.minimum(y, x)
        else:
            raise ValueError(f"unknown elementwise op {self.op!r}")
        return y, state, _first_mask(masks)


@vertex("dot_product")
class DotProductVertex(GraphVertex):
    """Batch dot product along one shared axis (Keras ``Dot(axes=k)`` for
    the equal-shape case — similarity heads, matching networks). Inputs
    [B, ..., n, ...] x2 -> contraction over ``axis`` with the axis kept as
    length 1 (Keras keeps a dim so downstream Dense sees rank 2)."""
    axis: int = -1

    def initialize(self, key, input_shapes, dtype):
        a = list(input_shapes[0])
        ax = self.axis
        # shapes exclude batch; axis is Keras-style counting batch as 0
        idx = (ax - 1) if ax > 0 else (len(a) + ax)
        a[idx] = 1
        return {}, {}, tuple(a)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        if len(xs) != 2:
            raise ValueError("Dot takes exactly 2 inputs")
        a, b = xs
        if a.shape != b.shape:
            raise ValueError(
                f"Dot supports equal-shape inputs, got {a.shape} vs "
                f"{b.shape} (matmul-style axes pairs not supported)")
        if a.ndim > 2:
            # Keras batch_dot on rank>=3 is a MATMUL-style (B, n, n)
            # contraction, not this elementwise sum — refuse loudly
            raise ValueError(
                f"Dot supports one non-batch dim, got rank {a.ndim} "
                "(batch_dot matmul semantics not implemented)")
        return (jnp.sum(a * b, axis=self.axis, keepdims=True), state,
                _first_mask(masks))


@vertex("subset")
class SubsetVertex(GraphVertex):
    """Feature-axis slice [from, to] inclusive (DL4J ``SubsetVertex``)."""
    from_idx: int = 0
    to_idx: int = 0
    data_format: str = "NCHW"

    def _axis(self, rank):
        # rank = dims WITHOUT batch; feature axis mirrors MergeVertex
        if rank <= 2:
            return rank - 1
        return 0 if self.data_format == "NCHW" else rank - 1

    def initialize(self, key, input_shapes, dtype):
        shape = list(input_shapes[0])
        shape[self._axis(len(shape))] = self.to_idx - self.from_idx + 1
        return {}, {}, tuple(shape)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        x = xs[0]
        ax = self._axis(x.ndim - 1) + 1  # batched
        idx = [slice(None)] * x.ndim
        idx[ax] = slice(self.from_idx, self.to_idx + 1)
        return x[tuple(idx)], state, _first_mask(masks)


@vertex("scale")
class ScaleVertex(GraphVertex):
    """y = x * scale (DL4J ``ScaleVertex``)."""
    scale: float = 1.0

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return xs[0] * self.scale, state, _first_mask(masks)


@vertex("shift")
class ShiftVertex(GraphVertex):
    """y = x + shift (DL4J ``ShiftVertex``)."""
    shift: float = 0.0

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return xs[0] + self.shift, state, _first_mask(masks)


@vertex("l2normalize")
class L2NormalizeVertex(GraphVertex):
    """y = x / max(||x||_2, eps) over all non-batch dims
    (DL4J ``L2NormalizeVertex``)."""
    eps: float = 1e-8

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        x = xs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True))
        return x / jnp.maximum(norm, self.eps), state, _first_mask(masks)


@vertex("stack")
class StackVertex(GraphVertex):
    """Stack minibatches along the batch (example) axis
    (DL4J ``StackVertex``) — used for weight-shared branches."""

    def initialize(self, key, input_shapes, dtype):
        return {}, {}, tuple(input_shapes[0])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        m = _first_mask(masks)
        ms = None
        if m is not None and masks and all(mi is not None for mi in masks):
            ms = jnp.concatenate(masks, axis=0)
        return jnp.concatenate(xs, axis=0), state, ms


@vertex("unstack")
class UnstackVertex(GraphVertex):
    """Take stack slice ``from_idx`` of ``stack_size`` along the batch axis
    (DL4J ``UnstackVertex``)."""
    from_idx: int = 0
    stack_size: int = 1

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        x = xs[0]
        step = x.shape[0] // self.stack_size
        sl = slice(self.from_idx * step, (self.from_idx + 1) * step)
        m = _first_mask(masks)
        return x[sl], state, None if m is None else m[sl]


@vertex("last_timestep")
class LastTimeStepVertex(GraphVertex):
    """[B,T,F] -> [B,F]: the last *unmasked* timestep per example
    (DL4J ``LastTimeStepVertex``)."""

    def initialize(self, key, input_shapes, dtype):
        t, f = input_shapes[0]
        return {}, {}, (int(f),)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        x = xs[0]  # [B,T,F]
        m = _first_mask(masks)
        if m is None:
            return x[:, -1, :], state, None
        # index of last nonzero mask entry per row
        idx = (x.shape[1] - 1
               - jnp.argmax(jnp.flip(m, axis=1) > 0, axis=1)).astype(jnp.int32)
        return jnp.take_along_axis(
            x, idx[:, None, None].repeat(x.shape[2], axis=2), axis=1
        )[:, 0, :], state, None


@vertex("reverse_timeseries")
class ReverseTimeSeriesVertex(GraphVertex):
    """Reverse the time axis of [B,T,F] (DL4J ``ReverseTimeSeriesVertex``).

    Divergence recorded: DL4J optionally right-aligns by an input mask; this
    reverses the full buffer (masked steps are zeros and remain masked)."""

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        m = _first_mask(masks)
        return (jnp.flip(xs[0], axis=1), state,
                None if m is None else jnp.flip(m, axis=1))


@vertex("duplicate_to_timeseries")
class DuplicateToTimeSeriesVertex(GraphVertex):
    """[B,F] -> [B,T,F] by repeating along a new time axis whose length
    comes from a reference time-series input (DL4J
    ``DuplicateToTimeSeriesVertex``). Inputs: [vector, reference_sequence]."""

    def initialize(self, key, input_shapes, dtype):
        f = int(input_shapes[0][-1])
        t = int(input_shapes[1][0])
        return {}, {}, (t, f)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        vec, ref = xs[0], xs[1]
        y = jnp.broadcast_to(vec[:, None, :],
                             (vec.shape[0], ref.shape[1], vec.shape[1]))
        return y, state, masks[1] if masks and len(masks) > 1 else None


@vertex("preprocessor")
class PreprocessorVertex(GraphVertex):
    """Standalone reshape/transpose preprocessor (DL4J ``PreprocessorVertex``).

    ``mode``: "cnn_to_ff" (flatten [C,H,W]->[C*H*W]), "ff_to_cnn"
    (reshape to ``target_shape``), "rnn_to_ff" ([B,T,F]->[B*T,F]),
    "ff_to_rnn" (inverse, timesteps from ``target_shape[0]``)."""
    mode: str = "cnn_to_ff"
    target_shape: Optional[Tuple[int, ...]] = None

    def initialize(self, key, input_shapes, dtype):
        s = tuple(int(v) for v in input_shapes[0])
        if self.mode == "cnn_to_ff":
            flat = 1
            for v in s:
                flat *= v
            return {}, {}, (flat,)
        if self.mode == "ff_to_cnn":
            return {}, {}, tuple(self.target_shape)
        if self.mode == "rnn_to_ff":
            return {}, {}, (s[-1],)
        if self.mode == "ff_to_rnn":
            return {}, {}, (int(self.target_shape[0]), s[-1])
        raise ValueError(self.mode)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        x = xs[0]
        b = x.shape[0]
        if self.mode == "cnn_to_ff":
            y = x.reshape(b, -1)
        elif self.mode == "ff_to_cnn":
            y = x.reshape((b,) + tuple(self.target_shape))
        elif self.mode == "rnn_to_ff":
            y = x.reshape(-1, x.shape[-1])
        elif self.mode == "ff_to_rnn":
            t = int(self.target_shape[0])
            y = x.reshape(-1, t, x.shape[-1])
        else:
            raise ValueError(self.mode)
        return y, state, _first_mask(masks)


@vertex("dot_product_attention")
class DotProductAttentionVertex(GraphVertex):
    """Scaled dot-product attention as a graph vertex (DL4J
    ``DotProductAttentionVertex`` / attention vertices under
    ``.../nn/graph/vertex/impl``†). Inputs: [queries, keys, values] as
    [B, T, F] (keys/values share T_k); optional 4th input = key keep-mask
    [B, T_k]. Parameter-free — projections belong to surrounding layers."""
    scaled: bool = True

    def initialize(self, key, input_shapes, dtype):
        tq = int(input_shapes[0][0])
        fv = int(input_shapes[2][-1])
        return {}, {}, (tq, fv)

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        import jax
        q, k, v = xs[0], xs[1], xs[2]
        scores = jnp.einsum("bqf,bkf->bqk", q, k)
        if self.scaled:
            scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
        key_mask = xs[3] if len(xs) > 3 else (
            masks[1] if masks and len(masks) > 1 and masks[1] is not None
            else None)
        if key_mask is not None:
            neg = jnp.finfo(scores.dtype).min
            scores = jnp.where(key_mask[:, None, :] > 0, scores, neg)
        att = jax.nn.softmax(scores, axis=-1)
        y = jnp.einsum("bqk,bkf->bqf", att, v)
        # output timesteps follow the QUERIES; the key mask only weights the
        # softmax — propagating it downstream would mis-mask a T_q sequence
        out_mask = masks[0] if masks else None
        return y, state, out_mask


#: Exact built-in vertex classes that never consume a PRNG key (all of
#: them; LayerVertex is excluded because its property delegates to the
#: wrapped layer). User GraphVertex subclasses are not in the set, so they
#: keep the conservative stochastic=True default and always receive a key.
_DETERMINISTIC_VERTICES = frozenset(
    cls for cls in VERTICES.values() if cls is not LayerVertex)
