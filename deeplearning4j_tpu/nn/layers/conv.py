"""Convolutional / pooling / normalization layers.

TPU-native equivalents of DL4J CNN layer configs+impls (reference:
``deeplearning4j-nn .../nn/conf/layers/{ConvolutionLayer,SubsamplingLayer,
BatchNormalization,...}.java``†, impls under ``.../nn/layers/convolution/``
and ``.../nn/layers/normalization/``† per SURVEY.md §2.4; reference mount was
empty, citations upstream-relative, unverified).

Layout: ``data_format`` per layer, "NCHW" default (DL4J), "NHWC" for
TPU-preferred zoo configs (SURVEY.md §7.3 item 1). Weights are ALWAYS stored
OIHW ("W") + bias ("b") regardless of data format — import parity.
DL4J ConvolutionMode Same/Truncate maps to mode="same"/"truncate".
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from ...ops import activations as _act
from ...ops import nnops
from .. import weights as _winit
from .base import Layer, layer


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


from ...ops.nnops import _safe_root


def _conv_out(size, k, s, p, mode):
    if mode == "same":
        return -(-size // s)  # ceil
    return (size + 2 * p - k) // s + 1


@layer("conv2d")
class ConvolutionLayer(Layer):
    """DL4J ConvolutionLayer (2D). W: [nOut, nIn, kH, kW] (OIHW)."""
    quantizable = True  # int8 serving: per-output-channel W (ISSUE 9)
    n_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    mode: str = "truncate"          # DL4J ConvolutionMode: truncate|same|causal
    activation: str = "identity"
    weight_init: str = "relu"
    bias_init: float = 0.0
    has_bias: bool = True
    data_format: str = "NCHW"
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    def _cin(self, input_shape):
        return int(input_shape[0] if self.data_format == "NCHW" else input_shape[-1])

    def initialize(self, key, input_shape, dtype):
        kh, kw = _pair(self.kernel)
        c_in = self._cin(input_shape)
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        w = _winit.init(self.weight_init, key, (self.n_out, c_in, kh, kw),
                        fan_in, fan_out, dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        # effective kernel under dilation: (k-1)*d + 1 (same latent flaw as
        # the 3D layer had — initialize must agree with the runtime conv)
        ke_h, ke_w = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self.data_format == "NCHW":
            h, wd = int(input_shape[1]), int(input_shape[2])
            out = (self.n_out, _conv_out(h, ke_h, sh, ph, self.mode),
                   _conv_out(wd, ke_w, sw, pw, self.mode))
        else:
            h, wd = int(input_shape[0]), int(input_shape[1])
            out = (_conv_out(h, ke_h, sh, ph, self.mode),
                   _conv_out(wd, ke_w, sw, pw, self.mode), self.n_out)
        return params, {}, out

    def quantize_spec(self, params):
        return {"W": 0}  # OIHW: one scale per output channel

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        w = params["W"]
        from ...ops import quantize as _q
        if isinstance(w, _q.QuantizedTensor):  # int8 serving (ISSUE 9)
            y = _q.int8_conv(x, w, params.get("b"), stride=self.stride,
                             padding=self.padding, dilation=self.dilation,
                             mode=self.mode, data_format=self.data_format)
        else:
            # post-conv epilogue (ISSUE 16): the conv itself stays with XLA
            # (a hand-written conv kernel measured ~50% SLOWER than XLA's —
            # ops/pallas_kernels.py negative result), and on a TPU in
            # ``auto`` so does the bias+act tail of a rank-4 feature map:
            # the dispatcher counts ``fallback_conv_layout`` and reproduces
            # conv2d's internal reshape-add plus the catalog activation
            # bit-for-bit, which XLA fuses into the convolution (measured
            # on the chip: ops/fused_epilogues.py docstring).
            from ...ops import fused_epilogues as _fe
            y = nnops.conv2d(x, w, None, stride=self.stride,
                             padding=self.padding, dilation=self.dilation,
                             mode=self.mode, data_format=self.data_format)
            caxis = 1 if self.data_format == "NCHW" else -1
            return (_fe.bias_act(y, params.get("b"), act=self.activation,
                                 axis=caxis),
                    state, mask)
        return _act.get(self.activation)(y), state, mask


@layer("subsampling2d")
class SubsamplingLayer(Layer):
    """DL4J SubsamplingLayer: max/avg/pnorm pooling, no params."""
    kernel: Tuple[int, int] = (2, 2)
    stride: Optional[Tuple[int, int]] = None  # default = kernel (DL4J default 1? no: common zoo usage sets it; we default kernel)
    padding: Tuple[int, int] = (0, 0)
    pool_type: str = "max"          # max|avg|pnorm
    pnorm: float = 2.0
    mode: str = "truncate"
    data_format: str = "NCHW"
    name: Optional[str] = None

    def has_params(self):
        return False

    def initialize(self, key, input_shape, dtype):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride or self.kernel)
        ph, pw = _pair(self.padding)
        if self.data_format == "NCHW":
            c, h, w = (int(s) for s in input_shape)
            out = (c, _conv_out(h, kh, sh, ph, self.mode),
                   _conv_out(w, kw, sw, pw, self.mode))
        else:
            h, w, c = (int(s) for s in input_shape)
            out = (_conv_out(h, kh, sh, ph, self.mode),
                   _conv_out(w, kw, sw, pw, self.mode), c)
        return {}, {}, out

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        k = _pair(self.kernel)
        s = _pair(self.stride or self.kernel)
        if self.pool_type == "max":
            y = nnops.max_pool2d(x, k, s, self.padding, self.mode, self.data_format)
        elif self.pool_type == "avg":
            y = nnops.avg_pool2d(x, k, s, self.padding, self.mode, self.data_format)
        elif self.pool_type == "pnorm":
            y = nnops.pnorm_pool2d(x, k, s, self.padding, self.mode,
                                   self.data_format, self.pnorm)
        else:
            raise ValueError(self.pool_type)
        return y, state, mask


@layer("batchnorm")
class BatchNormalization(Layer):
    """DL4J BatchNormalization. Params gamma/beta; state mean/var (running).

    Running stats update uses DL4J's decay convention:
    running = decay*running + (1-decay)*batch.
    """
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    data_format: str = "NCHW"
    name: Optional[str] = None

    def _caxis(self, ndim):
        return 1 if (self.data_format == "NCHW" and ndim == 4) else -1

    def initialize(self, key, input_shape, dtype):
        n = int(input_shape[0] if (self.data_format == "NCHW" and len(input_shape) == 3)
                else input_shape[-1])
        params = {} if self.lock_gamma_beta else {
            "gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}
        state = {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}
        return params, state, tuple(input_shape)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              fold_act=None):
        # ``fold_act`` (ISSUE 16): activation folded into the BN epilogue
        # by the engines' fold plan (a following ActivationLayer becomes a
        # pass-through). Routed through ops.fused_epilogues.bn_act, whose
        # fallback is nnops.batch_norm + the catalog activation —
        # bit-identical to the unfused pair, and what a rank-4 feature map
        # gets on a TPU in ``auto`` (``fallback_conv_layout``): XLA fuses
        # the pair into the convolutions around it, 45.4 ms a ResNet-50
        # step against 85.0 with the kernel (ledger, PR 29 and PR 30).
        axis = self._caxis(x.ndim)
        reduce_axes = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
        gamma = params.get("gamma")
        beta = params.get("beta")
        if train:
            # moments in fp32: a bf16-accumulated mean over B*H*W elements
            # loses ~3 decimal digits; the normalization itself stays in the
            # compute dtype (stats cast back to x.dtype).
            # ONE-PASS moments (E[x^2] - mean^2, cuDNN-style) rather than
            # jnp.var's two-pass E[(x-mean)^2]: the two-pass form makes the
            # variance reduction data-depend on the mean, forcing XLA into a
            # second full HBM sweep of the conv output per BN layer. One-pass
            # lets both reductions fuse into a single sweep (measured: -10%
            # ResNet-50 step time). fp32 accumulation keeps the cancellation
            # error harmless at BN's operating magnitudes.
            from ... import dtypes as _dt
            xs = _dt.upcast_16(x)
            if mask is not None:
                # mask-aware moments: padded examples (ParallelWrapper
                # ragged-tail pad) and masked timesteps must not perturb
                # batch statistics. mask is [B] or [B,T] over the leading
                # dims; broadcast it across the remaining axes.
                m = jnp.asarray(mask, xs.dtype)
                while m.ndim < xs.ndim:
                    m = m[..., None]
                cnt = jnp.maximum(jnp.sum(
                    jnp.broadcast_to(m, xs.shape), axis=reduce_axes), 1.0)
                s1 = jnp.sum(xs * m, axis=reduce_axes)
                s2 = jnp.sum(jnp.square(xs) * m, axis=reduce_axes)
                mean = s1 / cnt
                var = jnp.maximum(s2 / cnt - jnp.square(mean), 0.0)
            else:
                n_red = 1
                for i in reduce_axes:
                    n_red *= x.shape[i]
                s1 = jnp.sum(xs, axis=reduce_axes)
                s2 = jnp.sum(jnp.square(xs), axis=reduce_axes)
                mean = s1 / n_red
                var = jnp.maximum(s2 / n_red - jnp.square(mean), 0.0)
            d = self.decay
            new_state = {"mean": (d * state["mean"]
                                  + (1 - d) * mean).astype(state["mean"].dtype),
                         "var": (d * state["var"]
                                 + (1 - d) * var).astype(state["var"].dtype)}
            from ...ops import fused_epilogues as _fe
            y = _fe.bn_act(x, gamma, beta, mean.astype(x.dtype),
                           var.astype(x.dtype), self.eps, axis,
                           act=fold_act or "identity")
            return y, new_state, mask
        from ...ops import fused_epilogues as _fe
        y = _fe.bn_act(x, gamma, beta,
                       state["mean"].astype(x.dtype),
                       state["var"].astype(x.dtype),
                       self.eps, axis, act=fold_act or "identity")
        return y, state, mask


@layer("lrn")
class LocalResponseNormalization(Layer):
    """DL4J LocalResponseNormalization (AlexNet-era)."""
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    data_format: str = "NCHW"
    name: Optional[str] = None

    def has_params(self):
        return False

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        y = nnops.local_response_normalization(x, self.k, self.n, self.alpha,
                                               self.beta, self.data_format)
        return y, state, mask


@layer("global_pool")
class GlobalPoolingLayer(Layer):
    """DL4J GlobalPoolingLayer: collapse spatial/time dims; mask-aware for
    time series (masked timesteps excluded, as in DL4J). ``pnorm`` is the
    p exponent for pool_type="pnorm"."""
    pool_type: str = "max"
    data_format: str = "NCHW"
    pnorm: float = 2.0
    name: Optional[str] = None

    def has_params(self):
        return False

    def initialize(self, key, input_shape, dtype):
        if len(input_shape) == 4:  # CNN3D [C,D,H,W] or [D,H,W,C]
            n = int(input_shape[0] if self.data_format in ("NCHW", "NCDHW")
                    else input_shape[-1])
        elif len(input_shape) == 3:  # CNN [C,H,W] or [H,W,C]
            n = int(input_shape[0] if self.data_format == "NCHW" else input_shape[-1])
        else:  # RNN [T, F] -> F
            n = int(input_shape[-1])
        return {}, {}, (n,)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if x.ndim == 3 and mask is not None:
            # time series [B,T,F] with mask [B,T]
            m = mask[..., None].astype(x.dtype)
            if self.pool_type == "avg":
                y = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
            elif self.pool_type == "max":
                neg = jnp.finfo(x.dtype).min
                y = jnp.max(jnp.where(m > 0, x, neg), axis=1)
            elif self.pool_type == "pnorm":
                y = _safe_root(jnp.sum((jnp.abs(x) * m) ** self.pnorm, axis=1),
                               self.pnorm)
            else:
                y = jnp.sum(x * m, axis=1)
            return y, state, None
        if x.ndim == 3:
            if self.pool_type == "avg":
                y = jnp.mean(x, axis=1)
            elif self.pool_type == "max":
                y = jnp.max(x, axis=1)
            elif self.pool_type == "pnorm":
                y = _safe_root(jnp.sum(jnp.abs(x) ** self.pnorm, axis=1),
                               self.pnorm)
            else:
                y = jnp.sum(x, axis=1)
            return y, state, None
        y = nnops.global_pool(x, self.pool_type, self.data_format, p=self.pnorm)
        return y, state, None


@layer("upsampling2d")
class Upsampling2D(Layer):
    """``interpolation``: "nearest" (DL4J Upsampling2D = repeat) or
    "bilinear" (Keras UpSampling2D option; half-pixel sampling, matching
    tf.image.resize)."""
    size: Tuple[int, int] = (2, 2)
    data_format: str = "NCHW"
    interpolation: str = "nearest"
    name: Optional[str] = None

    def has_params(self):
        return False

    def initialize(self, key, input_shape, dtype):
        sh, sw = _pair(self.size)
        if self.data_format == "NCHW":
            c, h, w = (int(s) for s in input_shape)
            return {}, {}, (c, h * sh, w * sw)
        h, w, c = (int(s) for s in input_shape)
        return {}, {}, (h * sh, w * sw, c)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if self.interpolation not in ("nearest", "bilinear"):
            raise ValueError(
                f"Upsampling2D interpolation={self.interpolation!r} not "
                "supported (nearest | bilinear)")
        if self.interpolation == "bilinear":
            from ...ops.random import resize_scale
            y = resize_scale(x, _pair(self.size), method="bilinear",
                             data_format=self.data_format)
            return y, state, mask
        return nnops.upsampling2d(x, self.size, self.data_format), state, mask


@layer("zeropad2d")
class ZeroPadding2D(Layer):
    """``padding``: (pad_h, pad_w) symmetric, or the Keras asymmetric form
    ((top, bottom), (left, right))."""
    padding: Tuple = (1, 1)
    data_format: str = "NCHW"
    name: Optional[str] = None

    def has_params(self):
        return False

    def initialize(self, key, input_shape, dtype):
        if isinstance(self.padding[0], (tuple, list)):
            (pt, pb), (pl, pr) = self.padding
            pt, pb, pl, pr = int(pt), int(pb), int(pl), int(pr)
        else:
            pt = pb = int(_pair(self.padding)[0])
            pl = pr = int(_pair(self.padding)[1])
        if self.data_format == "NCHW":
            c, h, w = (int(s) for s in input_shape)
            return {}, {}, (c, h + pt + pb, w + pl + pr)
        h, w, c = (int(s) for s in input_shape)
        return {}, {}, (h + pt + pb, w + pl + pr, c)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return nnops.zero_padding2d(x, self.padding, self.data_format), state, mask
