"""The gated delta rule (Yang, Kautz and Hatamizadeh, *Gated Delta Networks*,
arXiv:2412.06464) in chunks, forward and backward.

Each value head keeps a state ``S`` ``[dk, dv]`` in float32, zero at the
start of a sequence. At position ``t``, with ``a_t = exp(g_t)`` (``g_t <=
0``) and ``0 < beta_t < 1``::

    S_t = a_t S_{t-1} + k_t (beta_t (v_t - a_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

In chunks of ``C`` positions (the WY form): with ``G`` the cumulative sum of
``g`` inside a chunk, ``A = exp(G)``, ``L[t, s] = beta_t exp(G_t - G_s) k_t .
k_s`` for ``s < t`` and ``Tri = (I + L)^-1``, the chunk's written values are
``U = U' - W S`` for the state ``S`` that enters it, where ``W = Tri diag(beta
A) K`` and ``U' = Tri diag(beta) V``; then ``O = (A Q) S + P U`` with ``P[t,
s] = exp(G_t - G_s) q_t . k_s`` for ``s <= t``, and the state leaving it is
``A_C S + Kd^T U`` with ``Kd = diag(A_C / A) K``. Everything but the three
products with ``S`` is independent of the other chunks: :func:`_prepare`
computes it for all chunks at once in XLA (the triangular solve in float32),
and differentiating it is XLA's. What is left is a scan over the chunks,
:func:`_scan`, with its own ``jax.custom_vjp``: the forward pass walks the
chunks once; the backward pass walks them again to rebuild the state that
entered each one (``delta_rule_states``), then in reverse carrying the
state's cotangent (``delta_rule_bwd``). The products take operands in the
inputs' dtype (bfloat16 in a bfloat16 model) and accumulate in float32; the
state and its cotangent stay float32.

Two paths run that scan, and :func:`gated_delta_rule` counts which a traced
site took (``linear_attn.dispatch``): on a TPU the Pallas kernels
``delta_rule_fwd``, ``delta_rule_states`` and ``delta_rule_bwd`` (grid: head
groups x chunks, each head's state in VMEM across its chunks), elsewhere the
same per-chunk steps under ``lax.scan``. ``flash_attention.set_mode`` is the
switch: ``force`` runs the kernels in interpret mode off the chip, ``off``
takes the scan everywhere.

The heads are prepared and scanned ``GROUP_HEADS`` at a time, each group
recomputed in the backward pass from its inputs: the states and the chunks'
float32 intermediates of one group are all the backward pass holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import flash_attention as _fa
from .pallas_kernels import (available as _tpu_available,
                             partitioned as _partitioned)
from ..runtime import telemetry as _tel

#: positions a chunk holds
CHUNK = 64
#: heads whose chunks are prepared and scanned together: the backward pass
#: holds one group's float32 intermediates at a time (a group of 8 at 32,768
#: positions: about 2 GB on a v5e, where all 32 heads of a layer at once
#: took 8.6 GB)
GROUP_HEADS = 4
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_T0 = (((0,), (0,)), ((), ()))          # contract the first axes: a^T b
_T1 = (((1,), (1,)), ((), ()))          # contract the last axes: a b^T

_DISPATCH = _tel.counter(
    "linear_attn.dispatch",
    "gated delta rule sites by the path their chunk scan took (kernel, xla) "
    "and why the XLA one (platform, mode, gspmd, shape), once a traced site")


# ------------------------------------------------------------- the chunks
def _prepare(q, k, v, g, beta):
    """Everything of the chunks that does not depend on the state. ``q``,
    ``k`` ``[R, N, C, dk]``, ``v`` ``[R, N, C, dv]``, ``g``, ``beta`` ``[R,
    N, C]`` float32. -> (``qa``, ``kd``, ``w`` ``[R, N, C, dk]``, ``u``
    ``[R, N, C, dv]``, ``p`` ``[R, N, C, C]`` in ``v``'s dtype; ``a`` ``[R,
    N, C]`` float32: ``A``, of which the scan reads each chunk's last)."""
    C = q.shape[2]
    G = jnp.cumsum(g, axis=-1)
    t = np.arange(C)
    incl = jnp.asarray(t[:, None] >= t[None, :])
    # exp(G_t - G_s) for s <= t, else 0; never exp of a positive number
    decay = jnp.exp(jnp.where(incl, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    dot = functools.partial(jnp.einsum, preferred_element_type=_F32,
                            precision=_HI)
    kk = dot("rntd,rnsd->rnts", k, k)
    L = jnp.where(jnp.asarray(t[:, None] > t[None, :]),
                  beta[..., :, None] * decay * kk, 0.0)
    A = jnp.exp(G)
    rhs = jnp.concatenate([(beta * A)[..., None] * k.astype(_F32),
                           beta[..., None] * v.astype(_F32)], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        L + jnp.eye(C, dtype=_F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    dk = k.shape[-1]
    p = decay * dot("rntd,rnsd->rnts", q, k)
    kd = jnp.exp(G[..., -1:] - G)[..., None] * k.astype(_F32)
    qa = A[..., None] * q.astype(_F32)
    dt = v.dtype
    return (qa.astype(dt), kd.astype(dt), sol[..., :dk].astype(dt),
            sol[..., dk:].astype(dt), p.astype(dt), A)


def _written(s, w, u):
    """The chunk's written values ``U = U' - W S`` (float32) for the state
    ``s`` that enters it, and ``s`` as the products' operand."""
    sb = s.astype(w.dtype)
    return u.astype(_F32) - jnp.dot(w, sb, preferred_element_type=_F32), sb


def _chunk_fwd(s, qa, kd, w, u, p, a_last):
    """One chunk of one head: -> (output ``[C, dv]`` float32, the state
    leaving it)."""
    uu, sb = _written(s, w, u)
    ub = uu.astype(u.dtype)
    o = (jnp.dot(qa, sb, preferred_element_type=_F32)
         + jnp.dot(p, ub, preferred_element_type=_F32))
    return o, a_last * s + jax.lax.dot_general(kd, ub, _T0,
                                               preferred_element_type=_F32)


def _chunk_next(s, kd, w, u, a_last):
    """The state leaving a chunk, without its output."""
    uu, _ = _written(s, w, u)
    return a_last * s + jax.lax.dot_general(kd, uu.astype(u.dtype), _T0,
                                            preferred_element_type=_F32)


def _chunk_bwd(s, ds, qa, kd, w, u, p, a_last, do):
    """One chunk of one head in reverse: ``s`` the state that entered it,
    ``ds`` the cotangent of the state that left it, ``do`` the output's.
    -> (``dqa``, ``dkd``, ``dw``, ``du``, ``dp`` float32, ``da`` ``[1,
    1]``: the cotangent of ``A_C``, and the cotangent of ``s``)."""
    uu, sb = _written(s, w, u)
    ub = uu.astype(u.dtype)
    dsb = ds.astype(w.dtype)
    du = (jax.lax.dot_general(p, do, _T0, preferred_element_type=_F32)
          + jnp.dot(kd, dsb, preferred_element_type=_F32))
    dub = du.astype(w.dtype)
    dqa = jax.lax.dot_general(do, sb, _T1, preferred_element_type=_F32)
    dp = jax.lax.dot_general(do, ub, _T1, preferred_element_type=_F32)
    dkd = jax.lax.dot_general(ub, dsb, _T1, preferred_element_type=_F32)
    dw = -jax.lax.dot_general(dub, sb, _T1, preferred_element_type=_F32)
    da = jnp.sum(jnp.sum(ds * s, axis=0, keepdims=True), axis=1,
                 keepdims=True)
    ds_in = (jax.lax.dot_general(qa, do, _T0, preferred_element_type=_F32)
             + a_last * ds
             - jax.lax.dot_general(w, dub, _T0, preferred_element_type=_F32))
    return dqa, dkd, dw, du, dp, da, ds_in


# ------------------------------------------------------------ XLA's scan
def _over_chunks(fn, carry, xs, reverse=False):
    """``lax.scan`` over the chunk axis (1) of ``[R, N, ...]`` arrays, the
    heads side by side."""
    moved = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), xs)
    carry, ys = jax.lax.scan(jax.vmap(fn), carry, moved, reverse=reverse)
    return carry, jax.tree.map(lambda y: jnp.moveaxis(y, 0, 1), ys)


def _zero_state(qa, u):
    return jnp.zeros((qa.shape[0], qa.shape[-1], u.shape[-1]), _F32)


def _fwd_xla(qa, kd, w, u, p, a):
    def step(s, x):
        o, s = _chunk_fwd(s, *x[:5], x[5][-1])
        return s, o.astype(u.dtype)
    return _over_chunks(step, _zero_state(qa, u), (qa, kd, w, u, p, a))[1]


def _states_xla(kd, w, u, a):
    def step(s, x):
        return _chunk_next(s, x[0], x[1], x[2], x[3][-1]), s
    return _over_chunks(step, _zero_state(kd, u), (kd, w, u, a))[1]


def _bwd_xla(qa, kd, w, u, p, a, states, do):
    def step(ds, x):
        qa, kd, w, u, p, a, s, do = x
        *grads, ds = _chunk_bwd(s, ds, qa, kd, w, u, p, a[-1], do)
        return ds, tuple(grads)
    _, grads = _over_chunks(step, _zero_state(qa, u),
                            (qa, kd, w, u, p, a, states, do), reverse=True)
    return grads


# ---------------------------------------------------------------- kernels
def _heads_per_step(rows: int) -> int:
    """Heads a grid step walks side by side: a step of one head's chunk is
    a few small products, so several share the step's fixed cost."""
    return next(h for h in (4, 2, 1) if rows % h == 0)


def _fwd_kernel(qa_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, o_ref, s_scr, *,
                hb):
    pl, _ = _fa._load_pallas()
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    for h in range(hb):
        o, s_scr[h] = _chunk_fwd(s_scr[h], qa_ref[h], kd_ref[h], w_ref[h],
                                 u_ref[h], p_ref[h], a_ref[0, h, c])
        o_ref[h] = o.astype(o_ref.dtype)


def _states_kernel(kd_ref, w_ref, u_ref, a_ref, s_ref, s_scr, *, hb):
    pl, _ = _fa._load_pallas()
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    for h in range(hb):
        s = s_scr[h]
        s_ref[h, 0] = s
        s_scr[h] = _chunk_next(s, kd_ref[h], w_ref[h], u_ref[h],
                               a_ref[0, h, c])


def _bwd_kernel(qa_ref, kd_ref, w_ref, u_ref, p_ref, a_ref, s_ref, do_ref,
                dqa_ref, dkd_ref, dw_ref, du_ref, dp_ref, da_ref, ds_scr, *,
                hb):
    pl, _ = _fa._load_pallas()
    C = qa_ref.shape[1]
    c = pl.num_programs(1) - 1 - pl.program_id(1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    for h in range(hb):
        dqa, dkd, dw, du, dp, da, ds_scr[h] = _chunk_bwd(
            s_ref[h, 0], ds_scr[h], qa_ref[h], kd_ref[h], w_ref[h], u_ref[h],
            p_ref[h], a_ref[0, h, c], do_ref[h])
        dqa_ref[h] = dqa.astype(dqa_ref.dtype)
        dkd_ref[h] = dkd.astype(dkd_ref.dtype)
        dw_ref[h] = dw.astype(dw_ref.dtype)
        du_ref[h] = du.astype(du_ref.dtype)
        dp_ref[h] = dp.astype(dp_ref.dtype)
        da_ref[h] = jnp.broadcast_to(da, (C, 1))


def _specs(pl, N, C, hb, widths, reverse=False):
    """Block specs of ``[R, N * C, width]`` arrays, one chunk of ``hb``
    heads a grid step; ``reverse`` walks the chunks last first."""
    at = (lambda r, c: (r, N - 1 - c, 0)) if reverse \
        else (lambda r, c: (r, c, 0))
    return [pl.BlockSpec((hb, C, wd), at) for wd in widths]


def _state_spec(pl, N, hb, dk, dv, reverse=False):
    at = (lambda r, c: (r, N - 1 - c, 0, 0)) if reverse \
        else (lambda r, c: (r, c, 0, 0))
    return pl.BlockSpec((hb, 1, dk, dv), at)


def _flat(x):
    """``[R, N, C, width]`` -> ``[R, N * C, width]``."""
    R, N, C = x.shape[:3]
    return x.reshape(R, N * C, -1)


def _last_spec(pl, pltpu, N, hb):
    """Each chunk's ``A_C``, read as scalars: a head group's ``[hb, N]`` in
    scalar memory for all its chunks."""
    return pl.BlockSpec((1, hb, N), lambda r, c: (r, 0, 0),
                        memory_space=pltpu.SMEM)


def _last(a, hb):
    """``A`` ``[R, N, C]`` -> each chunk's last, ``[R / hb, hb, N]``."""
    return a[..., -1].reshape(a.shape[0] // hb, hb, a.shape[1])


def _kernel_call(qa, kd, w, u, p, a, interpret):
    """``delta_rule_fwd``: the chunks' outputs ``[R, N, C, dv]``."""
    pl, pltpu = _fa._load_pallas()
    R, N, C, dk = qa.shape
    dv = u.shape[-1]
    hb = _heads_per_step(R)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb),
        grid=(R // hb, N),
        in_specs=_specs(pl, N, C, hb, (dk, dk, dk, dv, C))
        + [_last_spec(pl, pltpu, N, hb)],
        out_specs=_specs(pl, N, C, hb, (dv,))[0],
        out_shape=jax.ShapeDtypeStruct((R, N * C, dv), u.dtype),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_fa._compiler_params(pltpu, ("parallel",
                                                     "arbitrary")),
        interpret=interpret,
        name="delta_rule_fwd",
    )(*map(_flat, (qa, kd, w, u, p)), _last(a, hb))
    return out.reshape(R, N, C, dv)


def _states_call(kd, w, u, a, interpret):
    """``delta_rule_states``: the state that enters each chunk, ``[R, N, dk,
    dv]`` float32."""
    pl, pltpu = _fa._load_pallas()
    R, N, C, dk = kd.shape
    dv = u.shape[-1]
    hb = _heads_per_step(R)
    return pl.pallas_call(
        functools.partial(_states_kernel, hb=hb),
        grid=(R // hb, N),
        in_specs=_specs(pl, N, C, hb, (dk, dk, dv))
        + [_last_spec(pl, pltpu, N, hb)],
        out_specs=_state_spec(pl, N, hb, dk, dv),
        out_shape=jax.ShapeDtypeStruct((R, N, dk, dv), _F32),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_fa._compiler_params(pltpu, ("parallel",
                                                     "arbitrary")),
        interpret=interpret,
        name="delta_rule_states",
    )(*map(_flat, (kd, w, u)), _last(a, hb))


def _bwd_kernel_call(qa, kd, w, u, p, a, states, do, interpret):
    pl, pltpu = _fa._load_pallas()
    R, N, C, dk = qa.shape
    dv = u.shape[-1]
    hb = _heads_per_step(R)
    widths = (dk, dk, dk, dv, C)
    shapes = [jax.ShapeDtypeStruct((R, N * C, wd), x.dtype)
              for wd, x in zip(widths, (qa, kd, w, u, p))] + [
        jax.ShapeDtypeStruct((R, N * C, 1), _F32)]
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb),
        grid=(R // hb, N),
        in_specs=_specs(pl, N, C, hb, widths, reverse=True)
        + [_last_spec(pl, pltpu, N, hb),
           _state_spec(pl, N, hb, dk, dv, reverse=True)]
        + _specs(pl, N, C, hb, (dv,), reverse=True),
        out_specs=_specs(pl, N, C, hb, widths + (1,), reverse=True),
        out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_fa._compiler_params(pltpu, ("parallel",
                                                     "arbitrary")),
        interpret=interpret,
        name="delta_rule_bwd",
    )(*map(_flat, (qa, kd, w, u, p)), _last(a, hb), states, _flat(do))
    return tuple(gr.reshape(R, N, C, -1) for gr in grads[:5]) + (
        grads[5].reshape(R, N, C),)


# ------------------------------------------------------------------ scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(qa, kd, w, u, p, a, kernel, interpret):
    """The chunks' outputs ``[R, N, C, dv]`` in ``u``'s dtype."""
    if kernel:
        return _kernel_call(qa, kd, w, u, p, a, interpret)
    return _fwd_xla(qa, kd, w, u, p, a)


def _scan_fwd(qa, kd, w, u, p, a, kernel, interpret):
    return _scan(qa, kd, w, u, p, a, kernel, interpret), \
        (qa, kd, w, u, p, a)


def _scan_bwd(kernel, interpret, res, do):
    qa, kd, w, u, p, a = res
    if kernel:
        states = _states_call(kd, w, u, a, interpret)
        grads = _bwd_kernel_call(qa, kd, w, u, p, a, states, do, interpret)
    else:
        grads = _bwd_xla(qa, kd, w, u, p, a, _states_xla(kd, w, u, a), do)
        grads = grads[:5] + (jnp.broadcast_to(
            grads[5].reshape(a.shape[:2] + (1,)), a.shape),)
    *d, da = grads
    # only each chunk's last A enters the scan
    last = jnp.arange(a.shape[-1]) == a.shape[-1] - 1
    return tuple(x.astype(r.dtype) for x, r in zip(d, res)) + (
        jnp.where(last, da, 0.0),)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------- dispatch
def _xla_reason(dtype, dk: int, dv: int):
    """Why the scan takes the XLA path, or None where the kernels take it."""
    if _partitioned() is not None:
        return "gspmd"
    if _fa.mode() == "off":
        return "mode"
    if _fa.mode() != "force" and not _tpu_available():
        return "platform"
    if dtype not in (jnp.bfloat16, jnp.float32) or dk % 128 or dv % 128:
        return "shape"
    return None


def gated_delta_rule(q, k, v, g, beta):
    """``q``, ``k`` ``[B, T, H, dk]`` (already normed and scaled, one per
    value head), ``v`` ``[B, T, H, dv]``, ``g`` (``<= 0``) and ``beta``
    ``[B, T, H]`` -> ``o`` ``[B, T, H, dv]`` in ``v``'s dtype, every state
    starting at zero. A length ``CHUNK`` does not divide is padded at the
    end with positions that write nothing."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    why = _xla_reason(v.dtype, dk, dv)
    if why is None:
        _DISPATCH.inc(decision="kernel")
    else:
        _DISPATCH.inc(decision="xla", why=why)
    chunk = CHUNK
    N = -(-T // chunk)
    pad = N * chunk - T

    def heads(x, dtype=None):
        x = x if dtype is None else x.astype(dtype)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)                       # [B, H, T', ...]
        return x.reshape((B * H, N, chunk) + x.shape[3:])

    R = B * H
    rows = next(n for n in range(min(R, GROUP_HEADS), 0, -1) if R % n == 0)

    def group(xs):
        return _scan(*_prepare(*xs), why is None, _fa._interpret())

    xs = (heads(q), heads(k), heads(v), heads(g, _F32), heads(beta, _F32))
    o = jax.lax.map(jax.checkpoint(group), tuple(
        x.reshape((R // rows, rows) + x.shape[1:]) for x in xs))
    return jnp.moveaxis(o.reshape(B, H, N * chunk, dv), 1, 2)[:, :T]
