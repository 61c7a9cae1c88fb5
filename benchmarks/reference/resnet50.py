"""Plain ResNet-50 (He et al., arXiv:1512.03385, Table 1, 50-layer column).

The benchmark's reference for the ``resnet50`` configuration: weights from a
seed, forward pass, softmax cross-entropy, gradients and the Nesterov update,
in straightforward ``jax.numpy`` at float32 / ``highest``. It imports nothing
from the program under test and takes nothing the program made.

Layout: NHWC activations, OIHW convolution weights, ``[in, out]`` dense
weight. Leaves are named ``<layer>/<param>``.

Departures from the paper, all stated in ``configs/resnet50.json``: the
stride of a down-sampling bottleneck sits on its first 1x1 convolution (the
paper's v1 placement); BatchNorm uses the batch's own statistics with
``eps`` 1e-5; convolutions carry no bias (BatchNorm follows each); the last
BatchNorm of each residual branch starts at gamma 1/sqrt(blocks).

``precision`` selects how the operands of every convolution and matrix
product are rounded before the product (``common.round_operand``). The lower
precisions exist for the control of ``correct``: the reference put in the
program's place one precision below what the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .common import HI as _HI, make_weights, round_operand as _round_operand


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order; init is ``("normal",
    std)``, ``("const", value)``, ``"ones"`` or ``"zeros"``."""
    out = []

    n_res = sum(cfg["stage_blocks"])

    def conv_bn(name, c_in, c_out, k, last=False):
        fan_in = c_in * k * k
        out.append((f"{name}_conv/W", (c_out, c_in, k, k),
                    ("normal", (2.0 / fan_in) ** 0.5)))      # He
        # the last BatchNorm of a residual branch starts at 1/sqrt(blocks),
        # which keeps the residual sum's variance at that of one branch
        out.append((f"{name}_bn/gamma", (c_out,),
                    ("const", n_res ** -0.5) if last else "ones"))
        out.append((f"{name}_bn/beta", (c_out,), "zeros"))

    stem = cfg["stem_channels"]
    conv_bn("stem", cfg["channels"], stem, 7)
    c_in = stem
    exp = cfg["expansion"]
    for s, (n_blocks, ch) in enumerate(zip(cfg["stage_blocks"],
                                           cfg["stage_channels"])):
        for b in range(n_blocks):
            name = f"s{s}_b{b}"
            conv_bn(f"{name}_a", c_in, ch, 1)
            conv_bn(f"{name}_b", ch, ch, 3)
            conv_bn(f"{name}_c", ch, ch * exp, 1, last=True)
            if b == 0:
                conv_bn(f"{name}_proj", c_in, ch * exp, 1)
            c_in = ch * exp
    n_cls = cfg["num_classes"]
    out.append(("fc/W", (c_in, n_cls),
                ("normal", (2.0 / (c_in + n_cls)) ** 0.5)))   # Xavier
    out.append(("fc/b", (n_cls,), "zeros"))
    return out


def init_weights(seed: int, cfg) -> dict:
    """All float32 master weights, made on the device in one jitted call."""
    return make_weights(layer_table(cfg), seed)


def _conv(x, w, stride, pad, precision):
    return lax.conv_general_dilated(
        _round_operand(x, precision), _round_operand(w, precision),
        (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=_HI,
        preferred_element_type=jnp.float32)


def _bn(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _conv_bn(p, name, x, stride, pad, eps, precision, relu):
    y = _bn(_conv(x, p[f"{name}_conv/W"], stride, pad, precision),
            p[f"{name}_bn/gamma"], p[f"{name}_bn/beta"], eps)
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, name, x, stride, project, eps, precision):
    y = _conv_bn(p, f"{name}_a", x, stride, 0, eps, precision, True)
    y = _conv_bn(p, f"{name}_b", y, 1, 1, eps, precision, True)
    y = _conv_bn(p, f"{name}_c", y, 1, 0, eps, precision, False)
    if project:
        x = _conv_bn(p, f"{name}_proj", x, stride, 0, eps, precision, False)
    return jax.nn.relu(y + x)


def logits(p, x, cfg, precision="float32"):
    """[B, H, W, C] float32 images -> [B, classes] float32 logits. Each block
    is rematerialised in the backward pass so that float32 at the timed batch
    fits one chip beside nothing else."""
    eps = cfg["bn_eps"]

    def stem(p, x):
        y = _conv_bn(p, "stem", x, 2, 3, eps, precision, True)
        return lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))

    y = jax.checkpoint(stem)(p, x)
    for s, n_blocks in enumerate(cfg["stage_blocks"]):
        for b in range(n_blocks):
            block = functools.partial(
                _bottleneck, name=f"s{s}_b{b}",
                stride=2 if (s > 0 and b == 0) else 1, project=(b == 0),
                eps=eps, precision=precision)
            y = jax.checkpoint(lambda p, y, block=block: block(p, x=y))(p, y)
    y = jnp.mean(y, axis=(1, 2))
    return jnp.dot(_round_operand(y, precision),
                   _round_operand(p["fc/W"], precision),
                   precision=_HI) + p["fc/b"]


def loss(p, batch, cfg, precision="float32"):
    """Mean softmax cross-entropy against one-hot labels."""
    x, y = batch
    lg = logits(p, x.astype(jnp.float32), cfg, precision)
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(lg, axis=-1), axis=-1))
