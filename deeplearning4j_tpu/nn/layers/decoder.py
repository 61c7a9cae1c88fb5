"""The layers of a causal decoder on token ids: RMSNorm, rotary grouped-query
attention under a causal or sliding-window mask or with the open keys chosen
at run time by a learned indexer, latent attention (keys and values from one
low-rank projection, one rotary key for all heads), a gated feed-forward, a
sparse-expert feed-forward that is told which experts it holds, the
next-token loss head, and the head of a stack whose layers are walked
several times (it scores every pass and weighs the passes by a learned exit
distribution).

Every setting that differs between the layers of one stack (query heads,
rotary share, base and scaling, mask) is a field of the layer, so a builder
lays out full and window layers of different head counts from one class
(``models/laguna.py``, ``models/kanana.py``). Activations are ``[B, T,
features]``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import activations as _act
from ...ops import causal_attention as _ca
from ...ops import delta_rule as _dr
from ...ops import lm_loss as _lm
from ...ops import moe as _moe
from ...ops import sparse_attention as _sa
from ...runtime import telemetry as _tel
from .. import weights as _winit
from .base import Layer, layer


def _w(init, key, shape, dtype):
    return _winit.init(init, key, shape, shape[-2], shape[-1], dtype)


def _grew(now: dict, before, key: str):
    """What a uint32 count of a layer's state grew by since ``before`` (None:
    since zero); counts wrap at 2**32."""
    old = 0 if before is None else before[key]
    return (np.asarray(now[key], np.int64)
            - np.asarray(old, np.int64)) % (1 << 32)


def _keeps_output(heads_width: int, hidden: int) -> bool:
    """Whether an attention layer asks a recomputed segment to keep its
    heads' output, ``[B, T, heads_width]``, instead of running the attention
    forward again to rebuild it: where that is no more than twice the
    layer's input. The bound is about memory. A segment keeps one hidden
    state at its boundary anyway, and this lets attention add at most two
    more; on a v5e at 8,192 positions a 1x layer costs 67 MB and a 2x layer
    134 MB a sequence pair, and 3x and 4x layers (48 and 64 heads of 128 on
    a hidden size of 2,048) do not fit beside the rest (PERF.md, PR 38)."""
    return heads_width <= 2 * hidden


def _rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * g


@layer("rms_norm")
class RMSNormLayer(Layer):
    """``x / sqrt(mean(x^2) + eps) * g`` over the last axis, the statistics
    in float32."""
    decode_pointwise = True
    eps: float = 1e-6
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        return ({"g": jnp.ones((int(input_shape[-1]),), dtype)}, {},
                tuple(input_shape))

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _rms_norm(x, params["g"], self.eps), state, mask


@layer("causal_attention")
class CausalSelfAttentionLayer(Layer):
    """Rotary grouped-query self-attention under a causal mask, or with
    ``window`` a sliding-window one (key ``j`` open to query ``i`` where ``i -
    window < j <= i``). ``n_heads`` query heads share ``n_kv_heads``; the
    first ``rotary_dim`` of each head's ``head_size`` dimensions are rotated
    (0: all), with ``rope_type`` ``default`` or ``yarn``; ``none`` rotates
    nothing and makes no rotary tables (a layer with no positional encoding:
    only the causal mask orders it). ``qk_norm`` puts an RMSNorm over each
    head's ``head_size`` channels on queries and keys before the rotation
    (gains ``gq`` / ``gk`` ``[head_size]``, one for all heads, ``eps``,
    statistics in float32). ``gated`` multiplies the heads' output by
    ``sigmoid(x Wg)`` element-wise before the output projection. No biases.
    The scores are never materialised (``ops/causal_attention.py``)."""
    quantizable = True
    n_heads: int = 1
    n_kv_heads: int = 1
    head_size: int = 64
    window: Optional[int] = None
    gated: bool = False
    qk_norm: bool = False
    eps: float = 1e-6
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    rope_type: str = "default"
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    weight_init: str = "xavier"
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        f = int(input_shape[-1])
        hq, hkv = self.n_heads * self.head_size, self.n_kv_heads * self.head_size
        ks = jax.random.split(key, 5)
        params = {"Wq": _w(self.weight_init, ks[0], (f, hq), dtype),
                  "Wk": _w(self.weight_init, ks[1], (f, hkv), dtype),
                  "Wv": _w(self.weight_init, ks[2], (f, hkv), dtype),
                  "Wo": _w(self.weight_init, ks[3], (hq, f), dtype)}
        if self.gated:
            params["Wg"] = _w(self.weight_init, ks[4], (f, hq), dtype)
        if self.qk_norm:
            params["gq"] = jnp.ones((self.head_size,), dtype)
            params["gk"] = jnp.ones((self.head_size,), dtype)
        return params, {}, tuple(input_shape)

    def quantize_spec(self, params):
        return {k: 1 for k in params if k.startswith("W")}

    def inv_freq(self) -> Optional[np.ndarray]:
        """The rotary frequencies, or None where ``rope_type`` is ``none``."""
        if self.rope_type == "none":
            return None
        rot = self.rotary_dim or self.head_size
        if self.rope_type == "default":
            return _ca.default_inv_freq(rot, self.rope_theta)
        if self.rope_type == "yarn":
            return _ca.yarn_inv_freq(
                rot, self.rope_theta, self.rope_factor,
                self.rope_original_max_position, self.rope_beta_fast,
                self.rope_beta_slow)
        raise ValueError(f"unknown rope_type {self.rope_type!r}")

    def project(self, params, x):
        """-> ``q`` ``[B, T, H, d]``, ``k`` and ``v`` ``[B, T, KV, d]``,
        queries and keys normed (``qk_norm``) and rotated (unless
        ``rope_type`` is ``none``)."""
        B, T, _ = x.shape
        q = jnp.dot(x, params["Wq"]).reshape(B, T, self.n_heads, self.head_size)
        k = jnp.dot(x, params["Wk"]).reshape(B, T, self.n_kv_heads,
                                             self.head_size)
        v = jnp.dot(x, params["Wv"]).reshape(B, T, self.n_kv_heads,
                                             self.head_size)
        if self.qk_norm:
            q = _rms_norm(q, params["gq"], self.eps)
            k = _rms_norm(k, params["gk"], self.eps)
        inv_freq = self.inv_freq()
        if inv_freq is None:
            return q, k, v
        cos, sin = _ca.rotary_tables(T, inv_freq, self.rope_attention_factor)
        return _ca.apply_rotary(q, cos, sin), _ca.apply_rotary(k, cos, sin), v

    def attend(self, params, x, q, k, v, select=None):
        """The heads' output through the gate and the output projection."""
        B, T, _ = x.shape
        o = _ca.causal_attention(
            q, k, v, window=self.window, select=select,
            keep=_keeps_output(self.n_heads * self.head_size, x.shape[-1]),
            rope=self.rope_type)
        o = o.reshape(B, T, self.n_heads * self.head_size)
        if self.gated:
            o = o * jax.nn.sigmoid(jnp.dot(x, params["Wg"]))
        return jnp.dot(o, params["Wo"])

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        q, k, v = self.project(params, x)
        return self.attend(params, x, q, k, v), state, mask


_SPARSE_KEYS = _tel.counter(
    "sparse_attn.keys", "keys a selection left open, summed over the "
    "queries of the steps run, by layer")
_SPARSE_TIES = _tel.counter(
    "sparse_attn.ties", "query rows whose topk-th and next index scores "
    "were equal (the lower key index was opened), by layer")


@layer("sparse_select_attention")
class SparseSelectAttentionLayer(CausalSelfAttentionLayer):
    """Grouped-query attention whose open keys a learned indexer chooses at
    run time (DeepSeek-V3.2-Exp's lightning indexer). The indexer projects
    the layer's input to ``index_heads`` queries of ``index_head_size``, ONE
    index key of that size and a weight a head (``WqI``, ``WkI``, ``Ww``; no
    rotation, no norm), scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])`` in float32, and query ``t`` attends to the ``topk`` earlier
    keys of largest ``I[t, s]`` (all of them where ``t + 1 <= topk``), the
    same keys for every head (``ops/sparse_attention.py``). The choice
    carries no gradient and the scores enter the output through it alone,
    so the indexer's three matrices take a gradient of exactly nought: they
    are parameters a checkpoint fills, and the loss that would train them
    (the alignment of ``I`` with the attention's own probabilities) is not
    built. The state counts, since ``init``, the open keys summed over the
    queries and the rows with a tie at the ``topk``-th score;
    ``fit_on_device`` publishes their growth as ``sparse_attn.keys`` and
    ``sparse_attn.ties``."""
    index_heads: int = 16
    index_head_size: int = 64
    topk: int = 2048

    def initialize(self, key, input_shape, dtype):
        if self.window is not None:
            raise ValueError("a selection has no window")
        k_att, *ks = jax.random.split(key, 4)
        params, _, shape = super().initialize(k_att, input_shape, dtype)
        f, wi = int(input_shape[-1]), self.weight_init
        params.update(
            WqI=_w(wi, ks[0], (f, self.index_heads * self.index_head_size),
                   dtype),
            WkI=_w(wi, ks[1], (f, self.index_head_size), dtype),
            Ww=_w(wi, ks[2], (f, self.index_heads), dtype))
        return (params, {"keys": jnp.zeros((), jnp.uint32),
                         "ties": jnp.zeros((), jnp.uint32)}, shape)

    def index(self, params, x):
        """-> ``qI`` ``[B, T, Hi, di]``, ``kI`` ``[B, T, di]``, ``w`` ``[B,
        T, Hi]`` float32."""
        B, T, _ = x.shape
        with jax.named_scope("attn.index"):
            return (jnp.dot(x, params["WqI"]).reshape(
                        B, T, self.index_heads, self.index_head_size),
                    jnp.dot(x, params["WkI"]),
                    jnp.dot(x, params["Ww"],
                            preferred_element_type=jnp.float32))

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        q, k, v = self.project(params, x)
        select, keys, ties = _sa.open_keys(*self.index(params, x), self.topk)
        y = self.attend(params, x, q, k, v, select)
        if train and "keys" in state:
            state = {**state,
                     "keys": state["keys"] + keys,
                     "ties": state["ties"] + ties}
        return y, state, mask

    def publish_counters(self, vertex: str, now: dict, before) -> None:
        """Add what the state's counts grew by since ``before`` (None: since
        zero) to the ``sparse_attn.*`` counters; counts wrap at 2**32."""
        _SPARSE_KEYS.inc(int(_grew(now, before, "keys")), layer=vertex)
        _SPARSE_TIES.inc(int(_grew(now, before, "ties")), layer=vertex)


@layer("latent_attention")
class LatentAttentionLayer(Layer):
    """Multi-head latent attention as DeepSeek-V3 trains it
    (arXiv:2412.19437, section 2.1), without the query's low-rank
    projection. Queries are ``n_heads x (nope_head_size + rope_head_size)``.
    Keys and values come from ONE projection ``Wkva`` of width ``kv_rank +
    rope_head_size``: the first ``kv_rank`` go through an RMSNorm of their
    own (gain ``g_kv``, statistics in float32) and the up-projection
    ``Wkvb`` to ``n_heads x (nope_head_size + v_head_size)``; the last
    ``rope_head_size`` are one rotary key, rotated once and shared by every
    head. Rotary pairs are the interleaved ``(2i, 2i + 1)`` at base
    ``rope_theta``, no scaling. Scores over ``nope + rope`` channels scaled
    by their root, values and output ``v_head_size`` a head, causal mask.
    No biases. The scores are never materialised
    (``ops/causal_attention.py``); the latent is expanded, not absorbed:
    this is the training form, there is no cache."""
    n_heads: int = 1
    nope_head_size: int = 128
    rope_head_size: int = 64
    v_head_size: int = 128
    kv_rank: int = 512
    rope_theta: float = 10000.0
    eps: float = 1e-6
    weight_init: str = "xavier"
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        f, h = int(input_shape[-1]), self.n_heads
        qk = self.nope_head_size + self.rope_head_size
        ks = jax.random.split(key, 4)
        wi = self.weight_init
        return ({"Wq": _w(wi, ks[0], (f, h * qk), dtype),
                 "Wkva": _w(wi, ks[1],
                            (f, self.kv_rank + self.rope_head_size), dtype),
                 "g_kv": jnp.ones((self.kv_rank,), dtype),
                 "Wkvb": _w(wi, ks[2], (self.kv_rank, h * (
                     self.nope_head_size + self.v_head_size)), dtype),
                 "Wo": _w(wi, ks[3], (h * self.v_head_size, f), dtype)},
                {}, tuple(input_shape))

    def project(self, params, x):
        """-> ``q`` ``[B, T, H, nope + rope]``, ``k`` the same, ``v`` ``[B,
        T, H, v_head_size]``: the rotary part of ``k`` is the one shared
        key, the same for every head."""
        B, T, _ = x.shape
        h, nope, rope = self.n_heads, self.nope_head_size, self.rope_head_size
        q = jnp.dot(x, params["Wq"]).reshape(B, T, h, nope + rope)
        ckv = jnp.dot(x, params["Wkva"])
        latent = _rms_norm(ckv[..., :self.kv_rank], params["g_kv"], self.eps)
        kv = jnp.dot(latent, params["Wkvb"]).reshape(
            B, T, h, nope + self.v_head_size)
        cos, sin = _ca.rotary_tables(
            T, _ca.default_inv_freq(rope, self.rope_theta))
        q_pe = _ca.apply_rotary(_ca.deinterleave(q[..., nope:]), cos, sin)
        # one rotary key: rotated once, then read by every head
        k_pe = _ca.apply_rotary(
            _ca.deinterleave(ckv[..., self.kv_rank:])[:, :, None, :], cos,
            sin)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))],
            axis=-1)
        return q, k, kv[..., nope:]

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        with jax.named_scope("attn.latent.project"):
            q, k, v = self.project(params, x)
        o = _ca.causal_attention(
            q, k, v, kind="latent",
            keep=_keeps_output(self.n_heads * self.v_head_size, x.shape[-1]))
        return (jnp.dot(o.reshape(o.shape[:2] + (-1,)), params["Wo"]),
                state, mask)


_LINEAR_CHUNKS = _tel.counter(
    "linear_attn.chunks", "chunks of the gated delta rule scanned, one a "
    "value head and chunk of a sequence, summed over the steps run, by layer")


#: key heads whose part of a Gated DeltaNet layer is computed, and
#: recomputed in the backward pass, together: at 32,768 positions a whole
#: layer's backward took 5.1 GB of temporaries on a v5e, four key heads' 2.6
_KEY_HEADS_A_GROUP = 4


def _l2_normed(x):
    """``x`` over the root of its squares' sum and 1e-6 (the published
    ``l2norm``), in float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


@layer("gated_delta_net")
class GatedDeltaNetLayer(Layer):
    """Gated DeltaNet linear attention (arXiv:2412.06464) as Qwen3-Next lays
    it out. One projection ``in_proj_qkvz`` gives, for each of
    ``n_key_heads`` key heads in turn, its query and key (``key_size`` each)
    and the values and output gates ``z`` of the ``n_value_heads /
    n_key_heads`` value heads it serves (``value_size`` each); ``in_proj_ba``
    gives each value head's ``b`` and ``a`` in the same grouping. The
    queries, keys and values (``[q, k, v]`` over all heads) go through a
    causal depthwise convolution of ``conv_size`` positions (``conv1d``
    ``[channels, conv_size]``, no bias) and SiLU; queries and keys are
    L2-normed (eps 1e-6), queries scaled by ``key_size^-1/2``, and both
    repeated to the value heads (key head ``j`` serves value heads ``j r`` to
    ``j r + r - 1``). ``beta = sigmoid(b)`` and ``g = -exp(A_log)
    softplus(a + dt_bias)``, float32, drive the gated delta rule of
    ``ops/delta_rule.py`` (a float32 state of ``[key_size, value_size]`` a
    value head, zero at a sequence's start); its output is RMS-normed over
    each head's ``value_size`` (statistics in float32, gain ``norm``, shared
    by the heads, ``rms_eps``), times ``silu(z)``, and projected back by
    ``out_proj``. No biases. The layer runs four key heads (and the value
    heads they serve) at a time, each group's part from its slice of the
    projections to its rows of ``out_proj``, summed in float32; the backward
    pass recomputes a group at a time, so it holds one group's intermediates
    (``_KEY_HEADS_A_GROUP``). Inside a recomputed segment the layer's
    output is kept (``attention.kept{kind=linear}``), so the segment's
    recomputation runs none of it. Scopes: ``attn.linear.project`` (the three projections) and
    ``attn.linear`` (the rest). The state counts the chunks scanned since
    ``init``; ``fit_on_device`` publishes their growth as
    ``linear_attn.chunks``."""
    n_key_heads: int = 1
    n_value_heads: int = 1
    key_size: int = 128
    value_size: int = 128
    conv_size: int = 4
    rms_eps: float = 1e-6
    weight_init: str = "xavier"
    name: Optional[str] = None

    def _sizes(self):
        hk, hv = self.n_key_heads, self.n_value_heads
        if hv % hk:
            raise ValueError(f"{hv} value heads do not divide over {hk} key "
                             "heads")
        return hk, hv, hv // hk, self.key_size, self.value_size

    def initialize(self, key, input_shape, dtype):
        f = int(input_shape[-1])
        hk, hv, _, dk, dv = self._sizes()
        ks = jax.random.split(key, 5)
        wi = self.weight_init
        channels = 2 * hk * dk + hv * dv
        return ({"in_proj_qkvz": _w(wi, ks[0], (f, 2 * hk * dk + 2 * hv * dv),
                                    dtype),
                 "in_proj_ba": _w(wi, ks[1], (f, 2 * hv), dtype),
                 "conv1d": _w(wi, ks[2], (channels, self.conv_size), dtype),
                 "A_log": jnp.log(jax.random.uniform(
                     ks[3], (hv,), jnp.float32, 1e-3, 16.0)).astype(dtype),
                 "dt_bias": jnp.ones((hv,), dtype),
                 "norm": jnp.ones((dv,), dtype),
                 "out_proj": _w(wi, ks[4], (hv * dv, f), dtype)},
                {"chunks": jnp.zeros((), jnp.uint32)}, tuple(input_shape))

    def groups(self, params):
        """The layer's weights cut into its groups of
        ``_KEY_HEADS_A_GROUP`` key heads (each with the value heads it
        serves), stacked on a leading axis: the published grouped order makes
        each group's columns of ``in_proj_qkvz`` and ``in_proj_ba``, its
        channels of each of the convolution's ``q``, ``k`` and ``v`` parts and
        its rows of ``out_proj`` contiguous."""
        hk, hv, r, dk, dv = self._sizes()
        per = min(_KEY_HEADS_A_GROUP, hk)
        if hk % per:
            raise ValueError(f"{hk} key heads do not divide into groups of "
                             f"{per}")
        G = hk // per
        f = params["in_proj_qkvz"].shape[0]

        def cols(w):
            return w.reshape(f, G, -1).transpose(1, 0, 2)

        conv = params["conv1d"]
        q, k, v = (conv[:hk * dk], conv[hk * dk:2 * hk * dk],
                   conv[2 * hk * dk:])
        return {"in_proj_qkvz": cols(params["in_proj_qkvz"]),
                "in_proj_ba": cols(params["in_proj_ba"]),
                "conv1d": jnp.concatenate(
                    [a.reshape((G, -1) + a.shape[1:]) for a in (q, k, v)],
                    axis=1),
                "A_log": params["A_log"].reshape(G, -1),
                "dt_bias": params["dt_bias"].reshape(G, -1),
                "out_proj": params["out_proj"].reshape(G, -1, f)}

    def mix(self, params, x, hk):
        """The gated delta rule's inputs for ``hk`` key heads (``params``:
        one group's weights, :meth:`groups`): ``q``, ``k`` ``[B, T, Hv, dk]``
        (normed, scaled, repeated), ``v`` ``[B, T, Hv, dv]``, ``g``,
        ``beta`` ``[B, T, Hv]`` float32, and the output gates ``z`` ``[B, T,
        Hv, dv]``."""
        B, T, _ = x.shape
        _, _, r, dk, dv = self._sizes()
        hv = hk * r
        with jax.named_scope("attn.linear.project"):
            qkvz = jnp.dot(x, params["in_proj_qkvz"]).reshape(
                B, T, hk, 2 * dk + 2 * r * dv)
            ba = jnp.dot(x, params["in_proj_ba"]).reshape(B, T, hk, 2 * r)
        with jax.named_scope("attn.linear"):
            z = qkvz[..., 2 * dk + r * dv:].reshape(B, T, hv, dv)
            mixed = jnp.concatenate(
                [qkvz[..., :dk].reshape(B, T, hk * dk),
                 qkvz[..., dk:2 * dk].reshape(B, T, hk * dk),
                 qkvz[..., 2 * dk:2 * dk + r * dv].reshape(B, T, hv * dv)],
                axis=-1)
            K = self.conv_size
            padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
            w = params["conv1d"].astype(jnp.float32)
            conv = sum(padded[:, j:j + T].astype(jnp.float32) * w[:, j]
                       for j in range(K))
            mixed = jax.nn.silu(conv.astype(x.dtype))
            q = mixed[..., :hk * dk].reshape(B, T, hk, dk)
            k = mixed[..., hk * dk:2 * hk * dk].reshape(B, T, hk, dk)
            v = mixed[..., 2 * hk * dk:].reshape(B, T, hv, dv)
            q = (_l2_normed(q) * dk ** -0.5).astype(x.dtype)
            k = _l2_normed(k).astype(x.dtype)
            q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
            ba = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(B, T, hv))
            g = -jnp.exp(params["A_log"].astype(jnp.float32)) \
                * jax.nn.softplus(ba[..., r:].reshape(B, T, hv)
                                  + params["dt_bias"].astype(jnp.float32))
            return q, k, v, g, beta, z

    def group(self, params, norm, x, hk):
        """One group's share of the layer's output, ``[B, T, features]``
        float32: its heads' mix, delta rule and gated norm through its rows
        of ``out_proj``."""
        B, T, _ = x.shape
        q, k, v, g, beta, z = self.mix(params, x, hk)
        with jax.named_scope("attn.linear"):
            o = _dr.gated_delta_rule(q, k, v, g, beta)
            o = _rms_norm(o, norm, self.rms_eps) \
                * jax.nn.silu(z.astype(jnp.float32)).astype(o.dtype)
        with jax.named_scope("attn.linear.project"):
            return jnp.dot(o.reshape(B, T, -1), params["out_proj"],
                           preferred_element_type=jnp.float32)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        B, T, f = x.shape
        hk, hv, _, _, _ = self._sizes()
        per = min(_KEY_HEADS_A_GROUP, hk)
        one = jax.checkpoint(
            lambda w, x: self.group(w, params["norm"], x, per))

        def step(acc, w):
            return acc + one(w, x), None

        y, _ = jax.lax.scan(step, jnp.zeros((B, T, f), jnp.float32),
                            self.groups(params))
        y = y.astype(x.dtype)
        if _ca._keeps("linear", _keeps_output(f, f)):
            y = _ca._tag(y)
        if train and "chunks" in state:
            state = {**state, "chunks": state["chunks"] + jnp.uint32(
                B * hv * -(-T // _dr.CHUNK))}
        return y, state, mask

    def publish_counters(self, vertex: str, now: dict, before) -> None:
        """Add what the state's count grew by since ``before`` (None: since
        zero) to ``linear_attn.chunks``; counts wrap at 2**32."""
        _LINEAR_CHUNKS.inc(int(_grew(now, before, "chunks")), layer=vertex)


def _gated_ffn(x, w1, w3, w2, activation):
    return jnp.dot(_act.get(activation)(jnp.dot(x, w1)) * jnp.dot(x, w3), w2)


@layer("gated_dense")
class GatedDenseLayer(Layer):
    """``(act(x W1) * (x W3)) W2`` at width ``n_hidden``, no biases."""
    decode_pointwise = True
    quantizable = True
    n_hidden: int = 0
    activation: str = "swish"
    weight_init: str = "xavier"
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        f = int(input_shape[-1])
        ks = jax.random.split(key, 3)
        return ({"W1": _w(self.weight_init, ks[0], (f, self.n_hidden), dtype),
                 "W3": _w(self.weight_init, ks[1], (f, self.n_hidden), dtype),
                 "W2": _w(self.weight_init, ks[2], (self.n_hidden, f), dtype)},
                {}, tuple(input_shape))

    def quantize_spec(self, params):
        return {k: 1 for k in params}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return (_gated_ffn(x, params["W1"], params["W3"], params["W2"],
                           self.activation), state, mask)


_MOE_TOKENS = _tel.counter(
    "moe.tokens", "tokens routed to each expert held here, by layer")
_MOE_ASSIGNMENTS = _tel.counter(
    "moe.assignments", "token-to-expert assignments by where the expert is "
    "held: here (computed) or elsewhere (left out)")
_MOE_DROPPED = _tel.counter(
    "moe.dropped", "assignments to a held expert that were not computed "
    "(stays 0: the layer has no capacity)")


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


@layer("sparse_experts")
class SparseExpertLayer(Layer):
    """A routed feed-forward that is told which experts it holds.

    The router scores every token against all ``num_experts`` in float32
    (``scoring``: the ``sigmoid`` of each output, or the ``softmax`` over all
    of them), keeps the ``top_k`` largest and weights them ``routed_scale * s /
    sum(s)`` over the chosen. With ``early_router`` the layer takes two inputs,
    ``(m, a)``: the experts run on ``m`` and the router reads ``a`` (a tensor
    from before the layer's attention, which the builder wires in as a second
    edge); otherwise it reads the layer's one input. With ``select_bias`` the
    ``top_k`` are taken by ``s + bias`` and weighted by their unbiased ``s``;
    the bias ``[num_experts]`` is layer state (``state["select_bias"]``,
    float32, zeros until a checkpoint or a balancing rule sets it): no gradient
    reaches it and no updater sweeps it. Of the chosen experts this layer
    computes those in ``held = (first, count)``, gated feed-forwards ``(act(x
    W1) * (x W3)) W2`` of width ``n_hidden`` run as grouped products over the
    tokens routed to them with no token dropped (``ops/moe.py``), and adds a
    shared expert of width ``shared_hidden`` (0: none) that every token goes
    through; routed and shared experts take the one ``activation`` (``swish``:
    SwiGLU, ``relu``: ReGLU). With ``shared_gate`` the shared expert's output
    is scaled by ``sigmoid(x Wsg)``, ``Wsg`` ``[hidden, 1]``, a token at a
    time. ``held=None`` holds every expert. What experts
    held elsewhere would add is left out (``absent="left_out"``); with
    ``absent="stand_in"`` the held experts a token chose stand in for its
    chosen experts held elsewhere, their weights renormalised over
    themselves (``moe.held_share``), so the router's gradient is not pushed
    towards the held experts or away from them. The state counts, since
    ``init``, the tokens each held expert got and the assignments here,
    elsewhere and dropped; ``fit_on_device`` publishes their growth as
    ``moe.*`` counters with the losses it reads back."""
    num_experts: int = 8
    top_k: int = 2
    n_hidden: int = 0
    shared_hidden: int = 0
    held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    select_bias: bool = False
    scoring: str = "sigmoid"
    activation: str = "swish"
    early_router: bool = False
    absent: str = "left_out"
    shared_gate: bool = False
    weight_init: str = "xavier"
    name: Optional[str] = None

    @property
    def n_inputs(self) -> int:
        return 2 if self.early_router else 1

    def _held(self):
        if self.absent not in ("left_out", "stand_in"):
            raise ValueError(f"absent={self.absent!r}: left_out or stand_in")
        first, count = self.held or (0, self.num_experts)
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"held={self.held} is no range of "
                             f"{self.num_experts} experts")
        return int(first), int(count)

    def initialize(self, key, input_shape, dtype):
        if self.early_router:
            input_shape, routed_on = input_shape
            if tuple(routed_on) != tuple(input_shape):
                raise ValueError(
                    f"the router reads {tuple(routed_on)} beside experts on "
                    f"{tuple(input_shape)}: the two inputs differ in shape")
        f, h = int(input_shape[-1]), self.n_hidden
        _, count = self._held()
        ks = jax.random.split(key, 7)
        wi = self.weight_init
        params = {"Wr": _w(wi, ks[0], (f, self.num_experts), dtype),
                  "W1": _w(wi, ks[1], (count, f, h), dtype),
                  "W3": _w(wi, ks[2], (count, f, h), dtype),
                  "W2": _w(wi, ks[3], (count, h, f), dtype)}
        if self.shared_hidden:
            s = self.shared_hidden
            params.update(S1=_w(wi, ks[4], (f, s), dtype),
                          S3=_w(wi, ks[5], (f, s), dtype),
                          S2=_w(wi, ks[6], (s, f), dtype))
            if self.shared_gate:
                params["Wsg"] = _w(wi, jax.random.fold_in(key, 7), (f, 1),
                                   dtype)
        state = {"tokens": jnp.zeros((count,), jnp.uint32),
                 "here": jnp.zeros((), jnp.uint32),
                 "elsewhere": jnp.zeros((), jnp.uint32),
                 "dropped": jnp.zeros((), jnp.uint32)}
        if self.select_bias:
            state["select_bias"] = jnp.zeros((self.num_experts,), jnp.float32)
        return params, state, tuple(input_shape)

    def chunk_rows(self, n_tokens: int) -> int:
        """Rows of the sorted assignments walked at a time: a quarter over
        what a uniform routing sends here, so that one chunk is the rule and
        a second the exception."""
        _, count = self._held()
        expected = -(-n_tokens * self.top_k * count // self.num_experts)
        rows = min(-(-expected * 5 // 4), n_tokens * min(self.top_k, count))
        return _round_up(rows, 256 if rows >= 256 else 8)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        first, count = self._held()
        x, a = x if self.early_router else (x, None)
        lead, f = x.shape[:-1], x.shape[-1]
        xt = x.reshape(-1, f)
        top_e, w = _moe.route(
            xt if a is None else a.reshape(-1, f), params["Wr"], self.top_k,
            self.routed_scale,
            state["select_bias"] if self.select_bias else None,
            self.scoring, reads="own" if a is None else "early",
            act=self.activation)
        if self.absent == "stand_in":
            w = _moe.held_share(top_e, w, first, count)
        order, ends, tokens = _moe.plan(top_e, first, count)
        with jax.named_scope("moe.experts"):
            routed, done = _moe.held_experts(
                xt, w, params["W1"], params["W3"], params["W2"], order, ends,
                self.chunk_rows(xt.shape[0]), self.top_k, self.activation)
        with jax.named_scope("moe.combine"):
            y = routed
            if self.shared_hidden:
                shared = _gated_ffn(xt, params["S1"], params["S3"],
                                    params["S2"], self.activation)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(
                        jnp.dot(xt, params["Wsg"]))
                y = y + shared
            y = y.astype(x.dtype).reshape(lead + (f,))
        if train and "tokens" in state:
            here = ends[-1].astype(jnp.uint32)
            state = {
                **state,
                "tokens": state["tokens"] + tokens.astype(jnp.uint32),
                "here": state["here"] + here,
                "elsewhere": state["elsewhere"]
                + jnp.uint32(top_e.size) - here,
                "dropped": state["dropped"] + here - done.astype(jnp.uint32)}
        return y, state, mask

    def publish_counters(self, vertex: str, now: dict, before) -> None:
        """Add what the state's counts grew by since ``before`` (None: since
        zero) to the ``moe.*`` counters; counts wrap at 2**32."""
        grew = lambda key: _grew(now, before, key)
        first, _ = self._held()
        for i, n in enumerate(grew("tokens")):
            if n:
                _MOE_TOKENS.inc(int(n), layer=vertex, expert=str(first + i))
        _MOE_ASSIGNMENTS.inc(int(grew("here")), layer=vertex, where="here")
        _MOE_ASSIGNMENTS.inc(int(grew("elsewhere")), layer=vertex,
                             where="elsewhere")
        _MOE_DROPPED.inc(int(grew("dropped")), layer=vertex)


@layer("causal_lm_output")
class CausalLMOutputLayer(Layer):
    """The untied head of a causal language model with its loss: takes the
    hidden states and the token ids that went in, and scores position ``t``
    against token ``t + 1``. The loss is the mean cross-entropy, in float32,
    over the positions that have a next token (each row's last has none and
    is left out); the labels handed to ``fit`` are not read.

    Logits are float32 and are made a sequence at a time, and each sequence
    is visited once: where the loss is differentiated its logits' cotangent,
    the product back to the hidden states and the sequence's term of ``W``'s
    gradient are made while its logits exist (``ops/lm_loss.py``), and the
    backward pass scales them. A recomputed segment keeps those gradients
    and the per-position losses (``nn/memory.py`` ``KEPT``) and recomputes
    nothing of the head. In training ``apply`` returns the loss itself, a
    float32 scalar that ``loss_value`` hands on (only a scalar can be
    differentiated through this head, and not in forward mode), otherwise
    the probabilities ``[B, T, n_out]``. With ``block`` the logits are made
    ``block`` positions at a time instead (a sequence length it does not
    divide is taken a sequence at a time), each row's last position weighted
    nought: at 32,768 positions and a vocabulary of 18,992 a sequence's
    float32 logits are 2.5 GB."""
    n_inputs = 2
    quantizable = True
    n_out: int = 0
    block: Optional[int] = None
    weight_init: str = "xavier"
    name: Optional[str] = None

    def initialize(self, key, input_shapes, dtype):
        hidden, tokens = input_shapes
        f = int(hidden[-1])
        return ({"W": _w(self.weight_init, key, (f, self.n_out), dtype)}, {},
                tuple(hidden[:-1]) + (self.n_out,))

    def quantize_spec(self, params):
        return {"W": 1}

    def apply(self, params, xs, state, *, train=False, rng=None, mask=None):
        h, tokens = xs
        if not train:
            logits = jnp.dot(h, params["W"],
                             preferred_element_type=jnp.float32)
            return jax.nn.softmax(logits, axis=-1), state, mask

        B, T, d = h.shape
        tokens = jnp.asarray(tokens, jnp.int32)
        if self.block and T % self.block == 0:
            C = self.block
            w = jnp.where(jnp.arange(T) < T - 1, 1.0 / (B * (T - 1)), 0.0)
            loss, _ = _lm.weighted_cross_entropy(
                h.reshape(B * T // C, C, d), params["W"],
                jnp.roll(tokens, -1, axis=1).reshape(B * T // C, C),
                jnp.broadcast_to(w, (B, T)).reshape(B * T // C, C),
                layer="causal")
            return loss, state, None
        loss, _ = _lm.weighted_cross_entropy(
            h[:, :-1], params["W"], tokens[:, 1:],
            jnp.full((B, T - 1), 1.0 / (B * (T - 1)), jnp.float32),
            layer="causal")
        return loss, state, None

    def loss_value(self, loss, labels, mask=None, weights=None):
        return loss


# positions of one pass whose logits ``ExitWeightedLMOutputLayer`` holds at a
# time, and visits once: 805 MB of float32 logits at a vocabulary of 49,152
# beside their cotangent, and no width of the decoders here, so that a trace
# tells the head's blocks by their shape
LM_HEAD_BLOCK = 4096

_EXIT_MASS = _tel.counter(
    "loop.exit_mass", "exit probability summed over the positions that "
    "carry loss, by head and by pass of the repeated run it scores")


@layer("exit_weighted_lm_output")
class ExitWeightedLMOutputLayer(Layer):
    """The untied head of a causal language model whose layers are walked
    ``R`` times (a repeated run of a :class:`ComputationGraph`), with the
    entropy-regularised objective of arXiv:2510.25741's first training
    stage. It takes every pass's hidden state stacked ``[R, B, T, d]`` and
    the token ids. One weight ``W`` scores all passes, ``z(t) = h(t) W``; an
    exit gate reads each, ``g(t) = sigmoid(h(t) Wg + bg)``, one scalar a
    position. With ``ce(t, i)`` the cross-entropy of pass ``t`` at position
    ``i`` against token ``i + 1`` and the exit distribution ``p(1) = g(1)``,
    ``p(t) = g(t) prod_{j<t} (1 - g(j))``, ``p(R) = prod_{j<R} (1 -
    g(j))``, the loss is the mean over the positions that have a next token
    of ``sum_t p(t, i) ce(t, i) - beta H(p(., i))``.

    Logits are float32 and are made ``LM_HEAD_BLOCK`` positions of one pass
    at a time (a sequence length the block does not divide is taken a whole
    row at a time), and each block is visited once. The exit distribution
    comes first (the gate reads ``h``, not the logits), so the row weights
    ``p(t, i) / N`` are known before the blocks are walked: where the loss is
    differentiated a block's weighted ``softmax - onehot``, the product back
    to the hidden states and the block's term of ``W``'s gradient are made
    while its logits exist (``ops/lm_loss.py``), and the backward pass scales
    them; ``p``'s gradient arrives through the weights' cotangent, the
    cross-entropies. ``W``'s gradient is the sum over the passes' blocks in
    the dtype ``W`` arrives in. A recomputed segment keeps the hidden states'
    gradient, ``W``'s and the cross-entropies (``nn/memory.py`` ``KEPT``) and
    recomputes nothing of the blocks. The gate, the exit distribution (in log
    space) and the entropy are float32. In training ``apply`` returns the
    objective itself, a float32 scalar that ``loss_value`` hands on (only a
    scalar can be differentiated through this head, and not in forward mode),
    otherwise the last pass's probabilities ``[B, T, n_out]``: inference
    walks every pass and stops at none. The state sums, since ``init``, the
    exit probability of each pass over the positions with loss;
    ``fit_on_device`` publishes its growth as ``loop.exit_mass``."""
    n_inputs = 2
    reads_passes = True
    quantizable = True
    n_out: int = 0
    beta: float = 0.1
    weight_init: str = "xavier"
    name: Optional[str] = None

    def initialize(self, key, input_shapes, dtype):
        passes, tokens = input_shapes
        if len(passes) != len(tokens) + 2:
            raise ValueError(
                "the head reads the stacked passes of a repeated run "
                f"[R, T, d] beside the token ids [T], got {passes} and "
                f"{tokens}")
        f = int(passes[-1])
        k1, k2 = jax.random.split(key)
        return ({"W": _w(self.weight_init, k1, (f, self.n_out), dtype),
                 "Wg": _w(self.weight_init, k2, (f, 1), dtype),
                 "bg": jnp.zeros((1,), dtype)},
                {"exit_mass": jnp.zeros((int(passes[0]),), jnp.float32)},
                tuple(passes[1:-1]) + (self.n_out,))

    def quantize_spec(self, params):
        return {"W": 1}

    def exit_log_probs(self, params, h):
        """``h`` ``[R, ..., d]`` -> ``log p`` ``[R, ...]`` float32."""
        a = (jnp.dot(h, params["Wg"],
                     preferred_element_type=jnp.float32)[..., 0]
             + params["bg"].astype(jnp.float32))
        stay = jax.nn.log_sigmoid(-a)
        before = jnp.cumsum(stay, axis=0) - stay   # sum over the passes j < t
        return jnp.concatenate(
            [(jax.nn.log_sigmoid(a) + before)[:-1], before[-1:]], axis=0)

    def apply(self, params, xs, state, *, train=False, rng=None, mask=None):
        h, tokens = xs
        if not train:
            logits = jnp.dot(h[-1], params["W"],
                             preferred_element_type=jnp.float32)
            return jax.nn.softmax(logits, axis=-1), state, mask
        R, B, T, d = h.shape
        C = LM_HEAD_BLOCK if T % LM_HEAD_BLOCK == 0 else T
        nxt = jnp.roll(jnp.asarray(tokens, jnp.int32), -1, axis=1)
        n = B * (T - 1)
        with jax.named_scope("lm_head.exit"):
            logp = self.exit_log_probs(params, h)
            p = jnp.exp(logp)
            mass = jnp.sum(jax.lax.stop_gradient(p)[:, :, :-1], axis=(1, 2))
            # each row's last position has no next token and weighs nothing
            p = p * (jnp.arange(T) < T - 1)
            # - beta H(p) a position, with H(p) = - sum_t p log p
            spread = self.beta * jnp.sum(p * logp) / n
        with jax.named_scope("lm_head.passes"):
            # sum_t p ce over the positions, over their number
            loss, _ = _lm.weighted_cross_entropy(
                h.reshape(R * B * T // C, C, d), params["W"],
                jnp.tile(nxt.reshape(B * T // C, C), (R, 1)),
                (p / n).reshape(R * B * T // C, C), layer="exit_weighted")
        return (loss + spread,
                {**state, "exit_mass": state["exit_mass"] + mass}, None)

    def loss_value(self, loss, labels, mask=None, weights=None):
        return loss

    def publish_counters(self, vertex: str, now: dict, before) -> None:
        """Add what the state's sums grew by since ``before`` (None: since
        zero) to ``loop.exit_mass``; the sums are float32."""
        old = 0.0 if before is None else np.asarray(before["exit_mass"])
        for t, grew in enumerate(np.asarray(now["exit_mass"]) - old):
            _EXIT_MASS.inc(float(grew), layer=vertex, **{"pass": str(t + 1)})
