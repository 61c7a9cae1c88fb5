"""Recurrent layers: LSTM, GravesLSTM, SimpleRnn, Bidirectional wrapper,
RnnOutputLayer/RnnLossLayer, LastTimeStep wrapper.

TPU-native equivalents of DL4J's recurrent stack (reference:
``deeplearning4j-nn .../nn/conf/layers/{LSTM,GravesLSTM,SimpleRnn}.java``,
``.../nn/conf/layers/recurrent/{Bidirectional,LastTimeStep}.java``,
``.../nn/layers/recurrent/``† per SURVEY.md §2.7; reference mount was empty,
citations upstream-relative, unverified).

TPU-first design (SURVEY.md §2.7 "TPU build"): the whole sequence runs as ONE
``lax.scan`` whose per-step body is a fused [B, in+hidden]x[.,4u] matmul (the
MXU shape) — not DL4J's per-timestep Java loop over native calls. Masking is
carry-gating (``h_t = m_t*h_new + (1-m_t)*h_prev``), which also makes naive
buffer-flip bidirectionalism correct for end-padded sequences. Truncated BPTT
is a per-step ``stop_gradient`` on the carry at window boundaries — the same
gradient truncation DL4J gets from chunked fitting, without leaving the
compiled step.

Layout conventions (recorded divergences from DL4J):
- activations are [B, T, F] (time-second); DL4J is [B, F, T].
- param names follow LSTMParamInitializer: "W" [nIn,4u] input weights,
  "RW" [u,4u] recurrent weights, "b" [4u]; gate order [i,f,o,g]
  (DL4J LSTMBlockCell order). GravesLSTM keeps peepholes in a separate
  "PW" [3,u] tensor instead of DL4J's RW-appended columns.
- streaming state (``rnnTimeStep``) lives OUTSIDE params/state, managed by
  the model (`MultiLayerNetwork.rnn_time_step`), so fit() stays stateless
  across batches exactly like DL4J's feed-forward fit path.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import activations as _act
from ...ops import nnops
from .. import weights as _winit
from .base import Layer, layer
from .core import OutputLayer, LossLayer


def _scan_time(step, carry0, x, mask, tbptt):
    """Scan `step` over the time axis of x [B,T,F].

    step: (carry, (x_t, m_t, t)) -> (carry, y_t); mask gating happens inside
    `step`. tbptt: stop the gradient flowing through the carry every
    `tbptt` steps (window boundary), or None for full BPTT.
    """
    T = x.shape[1]
    xs = jnp.moveaxis(x, 1, 0)  # [T,B,F] scan layout
    ms = None if mask is None else jnp.moveaxis(mask, 1, 0)  # [T,B]
    ts = jnp.arange(T, dtype=jnp.int32)

    def body(carry, inp):
        t = inp[-1]
        if tbptt:
            carry = jax.lax.cond(t % tbptt == 0,
                                 lambda c: jax.tree.map(jax.lax.stop_gradient, c),
                                 lambda c: c, carry)
        return step(carry, inp)

    if ms is None:
        carry, ys = jax.lax.scan(body, carry0, (xs, jnp.zeros((T, 0)), ts))
    else:
        carry, ys = jax.lax.scan(body, carry0, (xs, ms, ts))
    return carry, jnp.moveaxis(ys, 0, 1)  # back to [B,T,u]


def _gate(m_t, new, prev):
    """Carry gating: masked steps keep the previous state (callers only gate
    when a real [B] mask slice is present)."""
    m = m_t[:, None].astype(new.dtype)
    return m * new + (1.0 - m) * prev


class _RecurrentLayer(Layer):
    """Shared streaming/scan plumbing for recurrent layers."""

    supports_streaming = True

    def is_recurrent(self) -> bool:
        return True

    def init_stream_state(self, params, batch: int):
        raise NotImplementedError

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        """(y [B,T,u], final_carry) — used by apply() (zero carry) and by the
        model's rnnTimeStep streaming (persisted carry). ``grad_path=False``
        marks calls that are never differentiated (inference/streaming),
        letting layers pick forward-only fused kernels."""
        raise NotImplementedError

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        carry = self.init_stream_state(params, x.shape[0])
        y, _ = self.scan_with_state(params, x, carry, mask)
        return y, state, mask


@layer("lstm")
class LSTM(_RecurrentLayer):
    """Standard (non-peephole) LSTM (DL4J LSTM / LSTMBlock helper path).

    ``use_pallas_cell=True`` opts the INFERENCE/STREAMING paths (output(),
    rnnTimeStep) into the fused Pallas cell (ops/pallas_kernels.py) when
    running on TPU and the operands fit VMEM; training always uses the lax
    cell (the Pallas kernel is forward-only — no custom VJP)."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"            # DL4J exposes it; cell uses tanh
    forget_bias: float = 1.0            # DL4J LSTM forgetGateBiasInit default
    weight_init: str = "xavier"
    tbptt_length: Optional[int] = None  # stamped from conf by the builder
    use_pallas_cell: bool = False
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        n_in = self.n_in or int(input_shape[-1])
        u = self.n_out
        k1, k2 = jax.random.split(key)
        w = _winit.init(self.weight_init, k1, (n_in, 4 * u), n_in, u, dtype)
        rw = _winit.init(self.weight_init, k2, (u, 4 * u), u, u, dtype)
        b = jnp.zeros((4 * u,), dtype)
        return ({"W": w, "RW": rw, "b": b}, {},
                input_shape[:-1] + (u,))

    def init_stream_state(self, params, batch):
        u = params["RW"].shape[0]
        dt = params["W"].dtype
        return (jnp.zeros((batch, u), dt), jnp.zeros((batch, u), dt))

    def _cell(self, grad_path: bool):
        if not grad_path and self.use_pallas_cell:
            from ...ops import pallas_kernels as pk
            # a Mosaic kernel cannot ride a GSPMD-partitioned program
            return pk.lstm_cell_fused if pk.available() \
                and pk.partitioned() is None else nnops.lstm_cell
        return nnops.lstm_cell

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        w, rw, b = params["W"], params["RW"], params["b"]
        fb = self.forget_bias
        cell = self._cell(grad_path)
        if cell is not nnops.lstm_cell:
            from ...ops import pallas_kernels as pk
            if not pk.fits_vmem(x.shape[0], w.shape[0], rw.shape[0],
                                np.dtype(x.dtype).itemsize):
                cell = nnops.lstm_cell

        def step(carry, inp):
            x_t, m_t, _ = inp
            h, c = carry
            h_new, c_new = cell(x_t, h, c, w, rw, b, forget_bias=fb)
            if m_t.shape[-1]:
                h_new = _gate(m_t, h_new, h)
                c_new = _gate(m_t, c_new, c)
            return (h_new, c_new), h_new

        return _scan_ret(step, carry, x, mask, self.tbptt_length)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        carry = self.init_stream_state(params, x.shape[0])
        # train=True is the gradient path: the fused Pallas cell is
        # forward-only, so it only serves inference/streaming
        y, _ = self.scan_with_state(params, x, carry, mask, grad_path=train)
        return y, state, mask


@layer("convlstm2d")
class ConvLSTM2D(_RecurrentLayer):
    """Convolutional LSTM over [B,T,H,W,C] NHWC sequences (Keras
    ``ConvLSTM2D``; Shi et al. 2015). No DL4J twin — imported Keras models
    are the use case. Gates are convolutions: z = conv(x_t, W) +
    conv(h_{t-1}, RW, same) + b, gate order [i,f,o,g] like our LSTM.

    Params (OIHW, matching the conv stack): W [4f, Cin, kh, kw],
    RW [4f, f, kh, kw], b [4f]. The recurrent conv is always 'same' over
    the output spatial size (Keras semantics). ``return_sequences=False``
    emits only the final state [B,H',W',f] (LastTimeStep cannot wrap 5-D
    streams, so the collapse lives in-layer)."""
    n_out: int = 0                      # filters
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    mode: str = "same"                  # input conv padding: same|truncate
    return_sequences: bool = True
    activation: str = "tanh"            # cell/output transform
    gate_activation: str = "sigmoid"    # Keras recurrent_activation
    weight_init: str = "xavier"
    tbptt_length: Optional[int] = None
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    supports_streaming = False

    def initialize(self, key, input_shape, dtype):
        t, h, w, c = (int(s) for s in input_shape)
        kh, kw = int(self.kernel[0]), int(self.kernel[1])
        sh, sw = int(self.stride[0]), int(self.stride[1])
        f = self.n_out
        k1, k2 = jax.random.split(key)
        wk = _winit.init(self.weight_init, k1, (4 * f, c, kh, kw),
                         c * kh * kw, f * kh * kw, dtype)
        rwk = _winit.init(self.weight_init, k2, (4 * f, f, kh, kw),
                          f * kh * kw, f * kh * kw, dtype)
        b = jnp.zeros((4 * f,), dtype)
        from .conv import _conv_out
        ho = _conv_out(h, kh, sh, 0, self.mode) if h > 0 else h
        wo = _conv_out(w, kw, sw, 0, self.mode) if w > 0 else w
        out = ((t, ho, wo, f) if self.return_sequences else (ho, wo, f))
        return {"W": wk, "RW": rwk, "b": b}, {}, out

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        wk, rwk, b = params["W"], params["RW"], params["b"]
        f = self.n_out
        B, T = x.shape[0], x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)  # [T,B,H,W,C]
        ms = None if mask is None else jnp.moveaxis(mask, 1, 0)
        # all input convs at once: big batched conv rides the MXU better
        # than T small ones and is time-invariant (safe to hoist)
        zx_all = nnops.conv2d(
            xs.reshape((T * B,) + x.shape[2:]), wk, None,
            stride=self.stride, padding=(0, 0), mode=self.mode,
            data_format="NHWC")
        zx_all = zx_all.reshape((T, B) + zx_all.shape[1:])
        ho, wo = zx_all.shape[2], zx_all.shape[3]
        h0 = jnp.zeros((B, ho, wo, f), x.dtype)
        c0 = jnp.zeros((B, ho, wo, f), x.dtype)
        ts = jnp.arange(T, dtype=jnp.int32)
        tbptt = self.tbptt_length
        gate = _act.get(self.gate_activation)
        act = _act.get(self.activation)

        def body(carry, inp):
            if tbptt:
                t = inp[-1]
                carry = jax.lax.cond(
                    t % tbptt == 0,
                    lambda cc: jax.tree.map(jax.lax.stop_gradient, cc),
                    lambda cc: cc, carry)
            hprev, cprev = carry
            zx_t, m_t = inp[0], inp[1]
            zh = nnops.conv2d(hprev, rwk, None, stride=(1, 1),
                              padding=(0, 0), mode="same",
                              data_format="NHWC")
            z = zx_t + zh + b
            i, fg, o, g = jnp.split(z, 4, axis=-1)
            c_new = gate(fg) * cprev + gate(i) * act(g)
            h_new = gate(o) * act(c_new)
            if m_t.shape[-1]:
                m = m_t[:, None, None, None].astype(h_new.dtype)
                h_new = m * h_new + (1.0 - m) * hprev
                c_new = m * c_new + (1.0 - m) * cprev
            return (h_new, c_new), h_new

        feed = (zx_all, jnp.zeros((T, 0)) if ms is None else ms, ts)
        (h_fin, _), ys = jax.lax.scan(body, (h0, c0), feed)
        if not self.return_sequences:
            return h_fin, state, None
        return jnp.moveaxis(ys, 0, 1), state, mask


@layer("graves_lstm")
class GravesLSTM(_RecurrentLayer):
    """Peephole LSTM (DL4J GravesLSTM; Graves 2013). Peepholes i,f from
    c_{t-1}, o from c_t; stored as "PW" [3,u] (recorded divergence — DL4J
    appends them to RW)."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"
    weight_init: str = "xavier"
    tbptt_length: Optional[int] = None
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        n_in = self.n_in or int(input_shape[-1])
        u = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        w = _winit.init(self.weight_init, k1, (n_in, 4 * u), n_in, u, dtype)
        rw = _winit.init(self.weight_init, k2, (u, 4 * u), u, u, dtype)
        pw = _winit.init(self.weight_init, k3, (3, u), u, u, dtype)
        return ({"W": w, "RW": rw, "PW": pw, "b": jnp.zeros((4 * u,), dtype)},
                {}, input_shape[:-1] + (u,))

    def init_stream_state(self, params, batch):
        u = params["RW"].shape[0]
        dt = params["W"].dtype
        return (jnp.zeros((batch, u), dt), jnp.zeros((batch, u), dt))

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        w, rw, pw, b = params["W"], params["RW"], params["PW"], params["b"]

        def step(carry, inp):
            x_t, m_t, _ = inp
            h, c = carry
            h_new, c_new = nnops.graves_lstm_cell(x_t, h, c, w, rw, b, pw)
            if m_t.shape[-1]:
                h_new = _gate(m_t, h_new, h)
                c_new = _gate(m_t, c_new, c)
            return (h_new, c_new), h_new

        return _scan_ret(step, carry, x, mask, self.tbptt_length)


@layer("gru")
class GRU(_RecurrentLayer):
    """GRU (gate order [z, r, h~], Keras/CuDNN convention). DL4J has no GRU
    layer — this exists for Keras/ONNX importer parity and as a first-class
    recurrent cell. ``reset_after=True`` (Keras v2 default) keeps a separate
    recurrent bias "rb" and applies the reset gate AFTER the recurrent
    matmul (CuDNN-compatible math); False is the classic formulation."""
    n_out: int = 0
    n_in: Optional[int] = None
    reset_after: bool = True
    weight_init: str = "xavier"
    tbptt_length: Optional[int] = None
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        n_in = self.n_in or int(input_shape[-1])
        u = self.n_out
        k1, k2 = jax.random.split(key)
        w = _winit.init(self.weight_init, k1, (n_in, 3 * u), n_in, u, dtype)
        rw = _winit.init(self.weight_init, k2, (u, 3 * u), u, u, dtype)
        params = {"W": w, "RW": rw, "b": jnp.zeros((3 * u,), dtype)}
        if self.reset_after:
            params["rb"] = jnp.zeros((3 * u,), dtype)
        return params, {}, input_shape[:-1] + (u,)

    def init_stream_state(self, params, batch):
        u = params["RW"].shape[0]
        return (jnp.zeros((batch, u), params["W"].dtype),)

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        w, rw, b = params["W"], params["RW"], params["b"]
        rb = params.get("rb")

        def step(carry, inp):
            x_t, m_t, _ = inp
            (h,) = carry
            h_new = nnops.gru_cell(x_t, h, w, rw, b, rb)
            if m_t.shape[-1]:
                h_new = _gate(m_t, h_new, h)
            return (h_new,), h_new

        return _scan_ret(step, carry, x, mask, self.tbptt_length)


@layer("simple_rnn")
class SimpleRnn(_RecurrentLayer):
    """Elman RNN: h_t = act(x W + h_{t-1} RW + b) (DL4J SimpleRnn)."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"
    weight_init: str = "xavier"
    tbptt_length: Optional[int] = None
    l1: float = 0.0
    l2: float = 0.0
    name: Optional[str] = None

    def initialize(self, key, input_shape, dtype):
        n_in = self.n_in or int(input_shape[-1])
        u = self.n_out
        k1, k2 = jax.random.split(key)
        w = _winit.init(self.weight_init, k1, (n_in, u), n_in, u, dtype)
        rw = _winit.init(self.weight_init, k2, (u, u), u, u, dtype)
        return ({"W": w, "RW": rw, "b": jnp.zeros((u,), dtype)}, {},
                input_shape[:-1] + (u,))

    def init_stream_state(self, params, batch):
        return (jnp.zeros((batch, params["RW"].shape[0]), params["W"].dtype),)

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        w, rw, b = params["W"], params["RW"], params["b"]
        act = _act.get(self.activation)

        def step(carry, inp):
            x_t, m_t, _ = inp
            (h,) = carry
            h_new = nnops.simple_rnn_cell(x_t, h, w, rw, b, activation=act)
            if m_t.shape[-1]:
                h_new = _gate(m_t, h_new, h)
            return (h_new,), h_new

        return _scan_ret(step, carry, x, mask, self.tbptt_length)


def _scan_ret(step, carry, x, mask, tbptt):
    """(final_carry, ys) -> (ys, final_carry) in layer return order."""
    final, ys = _scan_time(step, carry, x, mask, tbptt)
    return ys, final


@layer("bidirectional")
class Bidirectional(_RecurrentLayer):
    """Bidirectional wrapper around a recurrent layer config (DL4J
    ``Bidirectional(Mode, layer)``). Modes: concat|add|mul|average.

    The backward pass flips the time buffer; carry gating keeps end-padded
    (masked) steps from perturbing state, so the flip is mask-correct.
    GravesBidirectionalLSTM ≡ Bidirectional(GravesLSTM) here (recorded:
    DL4J has it as a distinct legacy class with shared-gate math).
    """
    layer: Any = None           # the wrapped recurrent Layer config
    mode: str = "concat"
    #: False = emit only the LAST output of each direction, merged — the
    #: forward direction's t=T-1 with the backward direction's t=0 (its own
    #: final state). Keras Bidirectional(return_sequences=False) semantics;
    #: a LastTimeStep over the merged sequence would wrongly take t=T-1 of
    #: the backward stream (its FIRST step).
    return_sequences: bool = True
    name: Optional[str] = None

    # rnnTimeStep is ill-defined for bidirectional nets (the backward pass
    # needs the full future); DL4J throws the same way
    supports_streaming = False

    @property
    def stochastic(self):
        return getattr(self.layer, "stochastic", True)

    def initialize(self, key, input_shape, dtype):
        k1, k2 = jax.random.split(key)
        p_fw, _, out = self.layer.initialize(k1, input_shape, dtype)
        p_bw, _, _ = self.layer.initialize(k2, input_shape, dtype)
        if self.mode == "concat":
            out = out[:-1] + (out[-1] * 2,)
        if not self.return_sequences:
            out = (out[-1],)
        return {"fw": p_fw, "bw": p_bw}, {}, out

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        carry = self.init_stream_state(params, x.shape[0])
        if self.return_sequences:
            y, _ = self.scan_with_state(params, x, carry, mask)
            return y, state, mask
        # per-direction final outputs, merged. Carry gating makes both ends
        # correct under end-padded masks: the forward stream holds its last
        # valid value through trailing pads, and the reversed stream's final
        # position is its state after the original t=0.
        y_fw, _ = self.layer.scan_with_state(params["fw"], x, carry[0], mask)
        x_rev = jnp.flip(x, axis=1)
        m_rev = None if mask is None else jnp.flip(mask, axis=1)
        y_bw, _ = self.layer.scan_with_state(params["bw"], x_rev, carry[1],
                                             m_rev)
        fw_last, bw_last = y_fw[:, -1], y_bw[:, -1]
        if self.mode == "concat":
            last = jnp.concatenate([fw_last, bw_last], axis=-1)
        elif self.mode == "add":
            last = fw_last + bw_last
        elif self.mode == "mul":
            last = fw_last * bw_last
        elif self.mode == "average":
            last = (fw_last + bw_last) / 2
        else:
            raise ValueError(f"unknown Bidirectional mode {self.mode!r}")
        return last, state, None

    def init_stream_state(self, params, batch):
        return (self.layer.init_stream_state(params["fw"], batch),
                self.layer.init_stream_state(params["bw"], batch))

    def scan_with_state(self, params, x, carry, mask=None, grad_path=True):
        y_fw, c_fw = self.layer.scan_with_state(params["fw"], x, carry[0],
                                                mask, grad_path=grad_path)
        x_rev = jnp.flip(x, axis=1)
        m_rev = None if mask is None else jnp.flip(mask, axis=1)
        y_bw, c_bw = self.layer.scan_with_state(params["bw"], x_rev,
                                                carry[1], m_rev,
                                                grad_path=grad_path)
        y_bw = jnp.flip(y_bw, axis=1)
        if self.mode == "concat":
            y = jnp.concatenate([y_fw, y_bw], axis=-1)
        elif self.mode == "add":
            y = y_fw + y_bw
        elif self.mode == "mul":
            y = y_fw * y_bw
        elif self.mode == "average":
            y = (y_fw + y_bw) / 2
        else:
            raise ValueError(f"unknown Bidirectional mode {self.mode!r}")
        return y, (c_fw, c_bw)

    def to_dict(self):
        return {"kind": "bidirectional", "mode": self.mode,
                "return_sequences": self.return_sequences,
                "layer": self.layer.to_dict(), "name": self.name}

    @staticmethod
    def _from_dict_fields(d):
        return {"mode": d.get("mode", "concat"),
                "return_sequences": d.get("return_sequences", True),
                "layer": Layer.from_dict(d["layer"]), "name": d.get("name")}


@layer("last_timestep")
class LastTimeStep(Layer):
    """[B,T,F] -> [B,F]: last unmasked timestep (DL4J ``LastTimeStep``
    wrapper — exposed as a standalone layer; the graph engine has the vertex
    equivalent)."""
    name: Optional[str] = None

    def has_params(self):
        return False

    def initialize(self, key, input_shape, dtype):
        return {}, {}, (int(input_shape[-1]),)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if mask is None:
            return x[:, -1, :], state, None
        idx = (x.shape[1] - 1
               - jnp.argmax(jnp.flip(mask, axis=1) > 0, axis=1)).astype(jnp.int32)
        y = jnp.take_along_axis(
            x, idx[:, None, None].repeat(x.shape[2], axis=2), axis=1)[:, 0, :]
        return y, state, None


@layer("rnn_output")
class RnnOutputLayer(OutputLayer):
    """Per-timestep dense + loss head on [B,T,F] (DL4J RnnOutputLayer).
    Inherits OutputLayer — last-axis matmul is already time-distributed; the
    loss averages over unmasked (example, timestep) pairs via the [B,T] mask
    (ops/losses._per_example)."""


@layer("rnn_loss")
class RnnLossLayer(LossLayer):
    """Param-free per-timestep loss head (DL4J RnnLossLayer)."""
