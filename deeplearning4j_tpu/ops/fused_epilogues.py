"""Pallas TPU fused-epilogue kernel library + the fused master-cast updater.

The r17 ``mfu_gap`` attribution and the r18 ``master_cast_ms`` audit name
three memory-bound chains that XLA leaves as separate HBM round-trips and
that schedule tuning (r18) cannot recover — they are kernels that do not
exist yet. This module is those kernels (the TVM framing from PAPERS.md:
hand-fused operator *epilogues* with a sweep-and-cache tuner, never the
matmul/conv itself — the recorded negative result in ``pallas_kernels.py``
shows naive conv kernels lose to XLA's conv pipeline):

- :func:`bn_act` — batch-norm normalize + activation as one row-tiled
  affine kernel ``y = act(x*scale + shift)`` with scale/shift folded from
  the BN statistics outside the kernel ([C]-sized math, XLA's job). On a
  TPU in ``auto`` a convolution's feature map (rank 4, channel-last) does
  NOT take the kernel: it takes the reference path, counted
  ``fallback_conv_layout``. The premise that the kernel saves the conv
  output an HBM round trip came from a CPU cost model and is false on the
  chip: XLA folds normalize+activation into the convolutions' own
  fusions, and 92 custom calls in a ResNet-50 step cost layout copies,
  reshapes into the ``[rows, C]`` view and those fusions — 85.0 ms a step
  against 45.4, 1,407 examples/s against 2,498, with the kernels
  themselves at 83% of their roofline (``PERF_LEDGER.jsonl``, PR 29 and
  PR 30, ``resnet50.train.resident``; ``PERF.md`` sections 5 and 6).
  ``force`` still runs the kernel on any shape it can tile.
- :func:`bias_act` — conv/matmul bias + activation epilogue on the same
  affine kernel (scale absent); rank-4 feature maps as for ``bn_act``.
- :func:`layer_norm_act` — LayerNorm + affine + activation for the
  transformer blocks; spliced into TF-imported SameDiff graphs by
  ``autodiff/fusion.py``'s ``fuse_epilogues`` rewrite (the r8
  ``fuse_attention`` splice pattern).
- :func:`dispatch_updater` / ``nn/updaters.py`` ``apply_leaf_cast`` — the
  fused master-cast+updater step: the per-step f32->bf16 master cast is
  folded into the updater's parameter write (one fused sweep emits the f32
  master AND its bf16 compute copy), eliminating the standalone cast sweep
  ``master_cast_ms`` attributes. Pure XLA (no Pallas) — the win is program
  structure, so it applies on every backend.

All kernels carry custom VJPs. The affine backward recomputes the
pre-activation from x/scale/shift (no extra residuals — the activation
input never hits HBM); per-channel grads accumulate in f32 VMEM scratch
across the sequential row-block grid and flush on the last step (the
flash-attention dkv pattern). LayerNorm saves only the per-row mean/rstd,
lane-replicated like flash's softmax stats.

Dispatch follows the flash-attention house style: mode env pin
``DL4J_TPU_FUSED_EPILOGUES`` (auto/force/off), every decision bumps
``fused_epilogues.dispatch{decision=}`` (zero silent fallbacks), fallbacks
reproduce the EXACT pre-fusion formula (``nnops.batch_norm`` + the
activation catalog fn) so auto-mode on CPU is bit-identical to the
unfused layer stack. Row-block sizes ride ``ops/autotune.py``
sweep-and-cache entries keyed ``("epilogue", kind, rows, cols, dtype)``.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import register
from . import nnops
from . import activations as _activations
from .pallas_kernels import (_VMEM_BUDGET, available as _tpu_available,
                             partitioned as _partitioned)

_LANES = 128

# lazily bound so importing this module never requires pallas to load;
# kernel bodies reference this module-global (the flash_attention pattern)
pl = None


def _load_pallas():
    global pl
    from . import flash_attention as _fa
    _pl, pltpu = _fa._load_pallas()
    pl = _pl
    return _pl, pltpu


# --------------------------------------------------------------------------
# activation table: forward + derivative-from-preactivation, kernel-safe
# --------------------------------------------------------------------------

_SQRT_2_OVER_PI = 0.7978845608028654
_GELU_A = 0.044715
_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327

# canonical (lowercase, underscore-stripped) names the kernels implement.
# Only activations with a cheap closed-form derivative from the
# pre-activation qualify — the backward recomputes act'(z) instead of
# saving residuals. Parameterized activations (leakyrelu alpha, elu) fall
# back: their alpha plumbing is not worth a kernel variant.
_FOLDABLE = ("identity", "relu", "relu6", "tanh", "sigmoid", "gelu",
             "geluexact")


def _canon(act) -> str:
    return str(act).lower().replace("_", "")


def foldable_act(act, alpha=None) -> bool:
    """Can this activation ride a fused epilogue kernel?"""
    return alpha is None and _canon(act) in _FOLDABLE


def _act_fwd(act, z):
    """act(z), f32 in/out, inside the kernel."""
    if act == "identity":
        return z
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "relu6":
        return jnp.clip(z, 0.0, 6.0)
    if act == "tanh":
        return jnp.tanh(z)
    if act == "sigmoid":
        return jax.nn.sigmoid(z)
    if act == "gelu":  # tanh approximation (DL4J GELU)
        u = _SQRT_2_OVER_PI * (z + _GELU_A * z * z * z)
        return 0.5 * z * (1.0 + jnp.tanh(u))
    if act == "geluexact":  # ONNX erf form
        return 0.5 * z * (1.0 + jax.lax.erf(z * _INV_SQRT2))
    raise ValueError(f"unfoldable activation {act!r}")


def _act_grad(act, z):
    """d act/d z recomputed from the pre-activation (no residuals)."""
    if act == "identity":
        return jnp.ones_like(z)
    if act == "relu":
        # same subgradient as the reference _relu_outgrad: zero at z == 0
        return (z > 0.0).astype(z.dtype)
    if act == "relu6":
        return ((z > 0.0) & (z < 6.0)).astype(z.dtype)
    if act == "tanh":
        t = jnp.tanh(z)
        return 1.0 - t * t
    if act == "sigmoid":
        s = jax.nn.sigmoid(z)
        return s * (1.0 - s)
    if act == "gelu":
        u = _SQRT_2_OVER_PI * (z + _GELU_A * z * z * z)
        t = jnp.tanh(u)
        du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_A * z * z)
        return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du
    if act == "geluexact":
        cdf = 0.5 * (1.0 + jax.lax.erf(z * _INV_SQRT2))
        pdf = _INV_SQRT_2PI * jnp.exp(-0.5 * z * z)
        return cdf + z * pdf
    raise ValueError(f"unfoldable activation {act!r}")


def reference_act(act, alpha=None):
    """The exact catalog activation the fallback path applies — identical
    callable to what the unfused layer stack uses, so an auto-mode
    fallback is bit-for-bit the pre-fusion program."""
    act = _canon(act)
    if act == "geluexact":
        return lambda x: _activations.gelu(x, approximate=False)
    fn = _activations.get(act)
    if alpha is not None:
        return lambda x: fn(x, alpha)
    return fn


# --------------------------------------------------------------------------
# kernel bodies (grid = (row-blocks,), sequential — "arbitrary" semantics
# so the per-channel grad scratch accumulates safely across steps)
# --------------------------------------------------------------------------

def _fused_epilogue_affine_fwd(*refs, act, has_scale):
    if has_scale:
        x_ref, s_ref, b_ref, y_ref = refs
    else:
        x_ref, b_ref, y_ref = refs
        s_ref = None
    x = x_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)  # [1, C] broadcasts over rows
    z = x * s_ref[...].astype(jnp.float32) + b if has_scale else x + b
    y_ref[...] = _act_fwd(act, z).astype(y_ref.dtype)


def _fused_epilogue_affine_bwd(*refs, act, has_scale, nblocks):
    if has_scale:
        (x_ref, s_ref, b_ref, dy_ref,
         dx_ref, ds_ref, db_ref, ds_scr, db_scr) = refs
    else:
        x_ref, b_ref, dy_ref, dx_ref, db_ref, db_scr = refs
        s_ref = ds_ref = ds_scr = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        db_scr[...] = jnp.zeros_like(db_scr)
        if has_scale:
            ds_scr[...] = jnp.zeros_like(ds_scr)

    x = x_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if has_scale:
        s = s_ref[...].astype(jnp.float32)
        z = x * s + b
    else:
        z = x + b
    dz = dy * _act_grad(act, z)
    dx_ref[...] = ((dz * s) if has_scale else dz).astype(dx_ref.dtype)
    if has_scale:
        ds_scr[...] += jnp.sum(dz * x, axis=0, keepdims=True)
    db_scr[...] += jnp.sum(dz, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _flush():
        db_ref[...] = db_scr[...]
        if has_scale:
            ds_ref[...] = ds_scr[...]


def _fused_epilogue_ln_fwd(x_ref, g_ref, b_ref, y_ref, mu_ref, rs_ref, *,
                           act, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    z = (xhat * g_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    y_ref[...] = _act_fwd(act, z).astype(y_ref.dtype)
    rows = x.shape[0]
    mu_ref[...] = jnp.broadcast_to(mu, (rows, _LANES))
    rs_ref[...] = jnp.broadcast_to(rstd, (rows, _LANES))


def _fused_epilogue_ln_bwd(x_ref, g_ref, b_ref, mu_ref, rs_ref, dy_ref,
                           dx_ref, dg_ref, db_ref, dg_scr, db_scr, *,
                           act, nblocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...][:, :1]
    rstd = rs_ref[...][:, :1]
    xhat = (x - mu) * rstd
    g = g_ref[...].astype(jnp.float32)
    z = xhat * g + b_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * _act_grad(act, z)
    dg_scr[...] += jnp.sum(dz * xhat, axis=0, keepdims=True)
    db_scr[...] += jnp.sum(dz, axis=0, keepdims=True)
    dxh = dz * g
    m1 = jnp.mean(dxh, axis=1, keepdims=True)
    m2 = jnp.mean(dxh * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((dxh - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)

    @pl.when(i == nblocks - 1)
    def _flush():
        dg_ref[...] = dg_scr[...]
        db_ref[...] = db_scr[...]


def _compiler_params_rows(pltpu):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# --------------------------------------------------------------------------
# pallas_call wrappers (grid = (rows // block_rows,))
# --------------------------------------------------------------------------

def _affine_fwd_impl(x2, s2, b2, act, br, interpret):
    _pl, pltpu = _load_pallas()
    R, C = x2.shape
    n = R // br
    has_scale = s2 is not None
    vec = _pl.BlockSpec((1, C), lambda i: (0, 0))
    in_specs = [_pl.BlockSpec((br, C), lambda i: (i, 0))]
    args = [x2]
    if has_scale:
        in_specs.append(vec)
        args.append(s2)
    in_specs.append(vec)
    args.append(b2)
    kernel = functools.partial(_fused_epilogue_affine_fwd, act=act,
                               has_scale=has_scale)
    return _pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_shape=jax.ShapeDtypeStruct((R, C), x2.dtype),
        out_specs=_pl.BlockSpec((br, C), lambda i: (i, 0)),
        compiler_params=_compiler_params_rows(pltpu),
        interpret=interpret,
        name="affine_act_fwd",
    )(*args)


def _affine_bwd_impl(x2, s2, b2, dy, act, br, interpret):
    _pl, pltpu = _load_pallas()
    R, C = x2.shape
    n = R // br
    has_scale = s2 is not None
    vec = _pl.BlockSpec((1, C), lambda i: (0, 0))
    row = _pl.BlockSpec((br, C), lambda i: (i, 0))
    in_specs = [row] + ([vec, vec] if has_scale else [vec]) + [row]
    args = ([x2, s2, b2, dy] if has_scale else [x2, b2, dy])
    out_shape = [jax.ShapeDtypeStruct((R, C), x2.dtype)]
    out_specs = [row]
    scratch = []
    if has_scale:
        out_shape.append(jax.ShapeDtypeStruct((1, C), jnp.float32))
        out_specs.append(vec)
        scratch.append(pltpu.VMEM((1, C), jnp.float32))
    out_shape.append(jax.ShapeDtypeStruct((1, C), jnp.float32))
    out_specs.append(vec)
    scratch.append(pltpu.VMEM((1, C), jnp.float32))
    kernel = functools.partial(_fused_epilogue_affine_bwd, act=act,
                               has_scale=has_scale, nblocks=n)
    outs = _pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_shape=tuple(out_shape),
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        compiler_params=_compiler_params_rows(pltpu),
        interpret=interpret,
        name="affine_act_bwd",
    )(*args)
    if has_scale:
        dx, ds, db = outs
        return dx, ds, db
    dx, db = outs
    return dx, None, db


def _ln_fwd_impl(x2, g2, b2, eps, act, br, interpret):
    _pl, pltpu = _load_pallas()
    R, C = x2.shape
    n = R // br
    vec = _pl.BlockSpec((1, C), lambda i: (0, 0))
    row = _pl.BlockSpec((br, C), lambda i: (i, 0))
    stat = _pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_fused_epilogue_ln_fwd, act=act, eps=eps)
    return _pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[row, vec, vec],
        out_shape=(jax.ShapeDtypeStruct((R, C), x2.dtype),
                   jax.ShapeDtypeStruct((R, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((R, _LANES), jnp.float32)),
        out_specs=(row, stat, stat),
        compiler_params=_compiler_params_rows(pltpu),
        interpret=interpret,
        name="layer_norm_act_fwd",
    )(x2, g2, b2)


def _ln_bwd_impl(x2, g2, b2, mu, rstd, dy, eps, act, br, interpret):
    _pl, pltpu = _load_pallas()
    R, C = x2.shape
    n = R // br
    vec = _pl.BlockSpec((1, C), lambda i: (0, 0))
    row = _pl.BlockSpec((br, C), lambda i: (i, 0))
    stat = _pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_fused_epilogue_ln_bwd, act=act, nblocks=n)
    return _pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[row, vec, vec, stat, stat, row],
        out_shape=(jax.ShapeDtypeStruct((R, C), x2.dtype),
                   jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)),
        out_specs=(row, vec, vec),
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
        compiler_params=_compiler_params_rows(pltpu),
        interpret=interpret,
        name="layer_norm_act_bwd",
    )(x2, g2, b2, mu, rstd, dy)


# --------------------------------------------------------------------------
# custom VJPs
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _affine_act(x2, s2, b2, act, br, interpret):
    return _affine_fwd_impl(x2, s2, b2, act, br, interpret)


def _affine_act_fwd_rule(x2, s2, b2, act, br, interpret):
    # backward recomputes z from x/scale/shift: no residual beyond inputs
    return _affine_fwd_impl(x2, s2, b2, act, br, interpret), (x2, s2, b2)


def _affine_act_bwd_rule(act, br, interpret, res, dy):
    x2, s2, b2 = res
    dx, ds, db = _affine_bwd_impl(x2, s2, b2, dy, act, br, interpret)
    ds_out = None if s2 is None else ds.astype(s2.dtype)
    return dx, ds_out, db.astype(b2.dtype)


_affine_act.defvjp(_affine_act_fwd_rule, _affine_act_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ln_act(x2, g2, b2, eps, act, br, interpret):
    y, _, _ = _ln_fwd_impl(x2, g2, b2, eps, act, br, interpret)
    return y


def _ln_act_fwd_rule(x2, g2, b2, eps, act, br, interpret):
    y, mu, rstd = _ln_fwd_impl(x2, g2, b2, eps, act, br, interpret)
    return y, (x2, g2, b2, mu, rstd)


def _ln_act_bwd_rule(eps, act, br, interpret, res, dy):
    x2, g2, b2, mu, rstd = res
    dx, dg, db = _ln_bwd_impl(x2, g2, b2, mu, rstd, dy, eps, act, br,
                              interpret)
    return dx, dg.astype(g2.dtype), db.astype(b2.dtype)


_ln_act.defvjp(_ln_act_fwd_rule, _ln_act_bwd_rule)


# --------------------------------------------------------------------------
# shape/VMEM guards
# --------------------------------------------------------------------------

def row_block(rows: int, mult: int, target: int = 256) -> Optional[int]:
    """Largest row block <= target dividing ``rows``, multiple of ``mult``
    (8 sublanes for 4-byte dtypes, 16 for 2-byte); None when nothing
    tiles. The dispatch guard AND the autotune candidate generator both
    derive from this so a cached block can never stop tiling."""
    b = min(int(target), int(rows))
    b -= b % mult
    while b >= mult:
        if rows % b == 0:
            return b
        b -= mult
    return None


def _row_mult(dtype) -> int:
    return 16 if np.dtype(dtype).itemsize == 2 else 8


def fits_vmem_epilogue(br: int, cols: int, itemsize: int = 4,
                       kind: str = "affine") -> bool:
    """Worst-of-fwd/bwd per-grid-step VMEM estimate (dispatching commits
    the backward too); x2 for pipelining double-buffers."""
    core = (3 * br * cols * itemsize  # x, dy in + dx out blocks (bwd)
            + 4 * cols * 4            # scale/shift in + dscale/dshift out
            + 2 * cols * 4)           # f32 accumulation scratch
    if kind == "ln":
        core += 4 * br * _LANES * 4   # mu/rstd: fwd writes 2, bwd reads 2
    return 2 * core < _VMEM_BUDGET


# --------------------------------------------------------------------------
# dispatch: mode + counters (zero-silent-fallback observability)
# --------------------------------------------------------------------------

_COUNTER_KEYS = ("fused", "fallback_mode", "fallback_platform",
                 "fallback_act", "fallback_dtype", "fallback_shape",
                 "fallback_vmem", "fallback_gspmd", "fallback_conv_layout",
                 # master-cast+updater decisions ride the same registry
                 # counter so the whole library's mix is one metric family
                 "fused_updater", "fallback_updater_mode",
                 "fallback_updater_dtype", "fallback_updater_penalty")
from ..runtime import telemetry as _tel  # noqa: E402

_DISPATCH = _tel.counter(
    "fused_epilogues.dispatch",
    "fused-epilogue dispatch decisions at trace time (fused vs fallback_*)")
_state = {"mode": os.environ.get("DL4J_TPU_FUSED_EPILOGUES", "auto")}
_FUSABLE_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def mode() -> str:
    return _state["mode"]


def set_mode(m: str) -> str:
    """"auto" (TPU -> kernels for rank-2/3 sites, the exact unfused
    reference for a convolution's rank-4 feature map, where XLA's own
    epilogue measured 1.77x faster on the chip, ``fallback_conv_layout``;
    elsewhere -> the reference), "force" (kernels everywhere — Pallas
    interpret off-TPU; how the CPU tier-1 suite exercises the kernel
    code), "off" (reference everywhere, fused updater disabled). Returns
    the previous mode.

    Consulted at TRACE time, exactly like flash attention's mode: flip it
    BEFORE building/tracing, or invalidate compiled caches after."""
    if m not in ("auto", "force", "off"):
        raise ValueError(f"fused epilogues mode {m!r} not in "
                         "('auto', 'force', 'off')")
    old = _state["mode"]
    _state["mode"] = m
    return old


def _interpret() -> bool:
    """Interpret mode is asked for, never fallen into: ``force`` off-TPU
    (how the CPU tests reach the kernel code)."""
    return _state["mode"] == "force" and not _tpu_available()


def counters() -> dict:
    """Dispatch-decision counts (trace-time units, like flash attention:
    one count per compiled call-site, not per execution)."""
    return {k: int(_DISPATCH.value(decision=k)) for k in _COUNTER_KEYS}


def reset_counters() -> None:
    _DISPATCH.zero()


def route_elementwise(shape, dtype, axis=-1, act="identity", alpha=None,
                      kind="affine") -> Optional[str]:
    """None = fuse; otherwise the fallback counter key. Pure function of
    static facts (shape/dtype/act/mode/backend) so the staticcheck fusion
    probe and the layer fold planners share the dispatcher's exact
    decision."""
    if _state["mode"] == "off":
        return "fallback_mode"
    if not foldable_act(act, alpha):
        return "fallback_act"
    if _state["mode"] != "force" and not _tpu_available():
        return "fallback_platform"
    if _partitioned() is not None:
        return "fallback_gspmd"  # see pallas_kernels.gspmd_trace
    if jnp.dtype(dtype) not in [jnp.dtype(d) for d in _FUSABLE_DTYPES]:
        return "fallback_dtype"
    ndim = len(shape)
    if ndim < 2 or axis not in (-1, ndim - 1):
        return "fallback_shape"  # kernels are channel-last row-tiled
    if _state["mode"] == "auto" and kind == "affine" and ndim == 4:
        # a convolution's feature map: XLA's own epilogue is faster on the
        # chip than a custom call and the copies around it (module docstring)
        return "fallback_conv_layout"
    cols = int(shape[-1])
    rows = 1
    for s in shape[:-1]:
        rows *= int(s)
    if cols < 1 or _tpu_available() and cols % _LANES:
        return "fallback_shape"  # lane alignment on real hardware
    br = row_block(rows, _row_mult(dtype))
    if br is None:
        return "fallback_shape"
    if not fits_vmem_epilogue(br, cols, np.dtype(dtype).itemsize, kind):
        return "fallback_vmem"
    return None


def _collapse(x):
    cols = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= int(s)
    return x.reshape(rows, cols), rows, cols


def _tuned_row_block(kind, rows, cols, x):
    from . import autotune as _autotune
    br = _autotune.epilogue_blocks(
        kind, rows, cols, x.dtype,
        concrete=not isinstance(x, jax.core.Tracer))
    if br is not None and rows % br == 0 and br % _row_mult(x.dtype) == 0:
        return br
    return row_block(rows, _row_mult(x.dtype))


# --------------------------------------------------------------------------
# public fused ops
# --------------------------------------------------------------------------

def bn_act(x, gamma, beta, mean, var, eps=1e-5, axis=-1, act="identity",
           alpha=None):
    """Batch-norm normalize + activation epilogue. Fused route folds the
    statistics into per-channel scale/shift ([C]-sized prologue math left
    to XLA — gradients to gamma/beta/mean/var flow through it) and runs
    one row-tiled affine+act kernel over the conv output. Fallback is the
    EXACT legacy formula: ``nnops.batch_norm`` then the catalog
    activation — bit-identical to the unfused layer pair."""
    act_c = _canon(act)
    reason = route_elementwise(x.shape, x.dtype, axis, act, alpha)
    if reason is None:
        _DISPATCH.inc(decision="fused")
        x2, rows, cols = _collapse(x)
        inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        scale = inv if gamma is None else inv * gamma.astype(jnp.float32)
        shift = -mean.astype(jnp.float32) * scale
        if beta is not None:
            shift = beta.astype(jnp.float32) + shift
        br = _tuned_row_block("affine", rows, cols, x2)
        y = _affine_act(x2, scale.reshape(1, cols), shift.reshape(1, cols),
                        act_c, br, _interpret())
        return y.reshape(x.shape)
    _DISPATCH.inc(decision=reason)
    y = nnops.batch_norm(x, gamma, beta, mean, var, eps, axis)
    if act_c == "identity" and alpha is None:
        return y
    return reference_act(act, alpha)(y)


def bias_act(x, b=None, act="identity", axis=-1, alpha=None):
    """Bias + activation epilogue (the post-conv/post-matmul tail).
    ``b`` is a [C] vector over ``axis`` or None. Fallback reproduces the
    conv layers' legacy tail exactly: broadcast-add then the catalog
    activation."""
    act_c = _canon(act)
    if b is None and act_c == "identity" and alpha is None:
        return x  # nothing to fuse; keep the dispatch mix meaningful
    reason = route_elementwise(x.shape, x.dtype, axis, act, alpha)
    if reason is None:
        _DISPATCH.inc(decision="fused")
        x2, rows, cols = _collapse(x)
        bb = jnp.zeros((cols,), x.dtype) if b is None else b
        br = _tuned_row_block("affine", rows, cols, x2)
        y = _affine_act(x2, None, bb.reshape(1, cols), act_c, br,
                        _interpret())
        return y.reshape(x.shape)
    _DISPATCH.inc(decision=reason)
    if b is not None:
        shape = [1] * x.ndim
        shape[axis] = b.shape[0]
        x = x + b.reshape(shape)
    if act_c == "identity" and alpha is None:
        return x
    return reference_act(act, alpha)(x)


def layer_norm_act(x, gamma, beta, eps=1e-5, act="identity"):
    """LayerNorm (last axis) + affine + activation epilogue for the
    transformer blocks; ``fuse_epilogues(sd)`` splices TF-imported
    decompositions into this op. Fallback is ``nnops.layer_norm`` + the
    catalog activation."""
    act_c = _canon(act)
    reason = route_elementwise(x.shape, x.dtype, -1, act, None, kind="ln")
    if reason is None:
        _DISPATCH.inc(decision="fused")
        x2, rows, cols = _collapse(x)
        br = _tuned_row_block("ln", rows, cols, x2)
        y = _ln_act(x2, gamma.reshape(1, cols), beta.reshape(1, cols),
                    float(eps), act_c, br, _interpret())
        return y.reshape(x.shape)
    _DISPATCH.inc(decision=reason)
    y = nnops.layer_norm(x, gamma, beta, eps, axis=-1)
    if act_c == "identity":
        return y
    return reference_act(act)(y)


# catalog ops the SameDiff rewrite pass splices in (serde round-trips the
# names + attrs; execution resolves through the registry like every op)

@register("epilogue.layer_norm_act", category="normalization")
def layer_norm_act_op(x, gamma, beta, eps=1e-5, act="identity"):
    return layer_norm_act(x, gamma, beta, eps=eps, act=act)


@register("epilogue.bias_act", category="activation")
def bias_act_op(x, b=None, act="identity"):
    return bias_act(x, b, act=act)


# --------------------------------------------------------------------------
# fused master-cast + updater routing
# --------------------------------------------------------------------------

def route_updater(policy, *, has_penalty: bool = False) -> Optional[str]:
    """None = fold the f32->16-bit master cast into the updater's write
    (``nn/updaters.py`` ``apply_leaf_cast``); otherwise the fallback
    counter key. No platform gate: the fusion is pure XLA program
    structure (the cast rides the parameter-update sweep instead of its
    own HBM sweep at the top of the forward), a win on every backend.

    ``has_penalty``: engine train steps whose loss reads the f32 masters
    for l1/l2 terms keep the unfused split (the SameDiff path handles
    penalties by differentiating masters and compute copies separately,
    so it always passes False)."""
    if _state["mode"] == "off":
        return "fallback_updater_mode"
    from .. import dtypes as _dt
    if not _dt.is_mixed(policy):
        return "fallback_updater_dtype"
    if has_penalty:
        return "fallback_updater_penalty"
    return None


def dispatch_updater(policy, *, has_penalty: bool = False) -> Optional[str]:
    """Counted :func:`route_updater` — call once per train-step build."""
    reason = route_updater(policy, has_penalty=has_penalty)
    _DISPATCH.inc(decision=reason or "fused_updater")
    return reason
