"""What happens to a gradient once it exists, written once.

``MultiLayerNetwork``, ``ComputationGraph`` and ``SameDiff`` each own a loss
function and a call signature; everything after the gradient is the same in
all three and lives here, in three pure pieces:

- :func:`gradient_tail`: ``grad_transform`` -> ``clip`` -> divergence
  sentinel -> guarded updater (+ constraints) -> BatchNorm-state commit ->
  sentinel counters. The only train-step caller of ``sentinel.finite_ok`` /
  ``guarded_apply`` / ``update_counters`` and of ``updaters.apply_leafwise``
  / ``apply_leafwise_cast``.
- :func:`engine_step`: the engines' gradient production (plain, the
  microbatch scan with the r12 cast hoist, the fused master-cast variant
  that differentiates ``params_c``) followed by the tail. The batch is
  passed through untouched, so one body serves ``MultiLayerNetwork``'s
  ``(x, y, fmask, lmask)`` and ``ComputationGraph``'s tuples of them.
- :func:`build_epoch`: ``lax.scan`` of such a step over a device-resident
  stack of batches, one XLA launch an epoch.

The functions returned are handed to ``jax.jit`` by the engines under the
names ``step_fn``, ``fused_step_fn`` and ``epoch_fn``: the HLO module is
named after them, and a new name is a new program text and a new entry in
the compile cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import dtypes as _dt
from ..runtime import sentinel as _sent
from . import constraints as _constraints
from . import memory as _memory
from . import microbatch as _micro
from . import updaters as _upd


def gradient_tail(updater, clip, *, cdt=None, upcast=None,
                  grad_transform=None, constraints=None,
                  frozen_keys=frozenset()):
    """-> ``tail(loss, grads, carry, opt_state, step, sentinel=None,
    bn=None)`` -> ``(carry, opt_state, bn, sentinel)``.

    ``carry`` is the parameter tree, or the pair ``(params, params_c)``
    under the fused master-cast updater (ISSUE 16): then the updater writes
    the ``cdt`` compute copy in the same fusion as the float32 master
    (``apply_leafwise_cast``), and where constraints rewrite the masters the
    copy is derived from them again. ``clip(grads) -> (grads, clip_events)``.
    ``grad_transform`` (value-identity scheduling structure, e.g. the
    collective-overlap pins of ``parallel/overlap.py``) sees the raw
    gradients first, the earliest point the full tree exists. ``upcast``: a
    dtype the gradient is cast to inside the updater's branch (the late
    cast of :func:`engine_step`), or None where it arrives in the masters'.

    DIVERGENCE SENTINEL (``runtime/sentinel.py``): a non-finite loss or
    global gradient norm ``lax.cond``-skips the updater and the commit of
    ``bn = (bn_state, new_bn)`` (the bad batch leaves no trace in any
    carried state) and bumps the on-device counters ``sentinel``: no host
    sync, no retrace, no exception (DL4J throws on NaN gradients;
    divergence recorded in PARITY.md). With ``sentinel=None`` (the callers'
    shorter form, for tests and tools) no counters come back.

    The scopes name the operations in a device trace (``clip``,
    ``sentinel``, ``updater``; the callers' ``forward`` and its transpose
    come before them)."""
    def tail(loss, grads, carry, opt_state, step, sentinel=None, bn=None):
        if grad_transform is not None:
            grads = grad_transform(grads)
        with jax.named_scope("clip"):
            grads, clip_events = clip(grads)

        def _apply(carry, opt_state):
            # leaf-wise, never a raveled flat buffer: the round-trip defeats
            # XLA's in-place update of donated parameters through the scan
            # carry (PERF.md, DIAG3_r05)
            g = grads if upcast is None else _dt.cast_floating(grads, upcast)
            if isinstance(carry, tuple):
                new_p, new_pc, new_opt = _upd.apply_leafwise_cast(
                    updater, g, opt_state, carry[0], step, cdt)
                if constraints:
                    new_p = _constraints.apply_constraints(
                        constraints, new_p, skip=frozen_keys)
                    new_pc = _dt.cast_floating(new_p, cdt)
                return (new_p, new_pc), new_opt
            new_p, new_opt = _upd.apply_leafwise(updater, g, opt_state,
                                                 carry, step)
            return _constraints.apply_constraints(
                constraints, new_p, skip=frozen_keys), new_opt

        with jax.named_scope("sentinel"):
            ok = _sent.finite_ok(loss, grads)
        with jax.named_scope("updater"):
            carry, opt_state = _sent.guarded_apply(ok, _apply, carry,
                                                   opt_state)
        if bn is not None:
            bn_state, new_bn = bn
            bn = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                              new_bn, bn_state) if bn_state else new_bn
        if sentinel is not None:
            sentinel = _sent.update_counters(sentinel, ok, clip_events)
        return carry, opt_state, bn, sentinel

    return tail


def engine_step(net, loss_fn, frozen_keys, weight_fn, accum_steps=1,
                grad_transform=None, fused_cast=False):
    """The pure train step of an engine, for ``jax.jit``: ``step_fn(params,
    opt_state, bn_state, step, key, x, y, fmask, lmask, sentinel=None)`` ->
    ``(params, opt_state, bn_state, [sentinel,] loss)``, or with
    ``fused_cast`` ``fused_step_fn`` with ``params_c`` after ``params`` in
    both. ``loss_fn`` is the engine's ``_build_loss_fn()``, ``frozen_keys``
    its frozen layers' keys in the parameter tree, ``weight_fn`` its
    microbatch weight (``nn/microbatch.py``); the updater, dtype policy,
    clipping, constraints and ``workspace_mode`` are read from ``net.conf``.
    The contracts (microbatch exactness, the cast hoist, the fused variant's
    bit parity) are in ``NetworkEngine._build_train_step``'s docstring
    (``nn/engine.py``).
    """
    conf = net.conf
    vg_fn = jax.value_and_grad(loss_fn, has_aux=True)
    cast_hoist = (accum_steps > 1 and _dt.is_mixed(conf.dtype)
                  and not net._uses_regularization())
    cdt = _dt.resolve(conf.dtype)
    pdt = _dt.param_dtype(conf.dtype)
    if fused_cast and accum_steps != 1:
        raise ValueError("fused_cast requires accum_steps == 1 "
                         "(the microbatch scan has its own hoist)")
    # The fused step's cotangents come back 16-bit and are upcast exactly
    # like the unfused cast's transpose. Under a recomputing workspace_mode
    # (the memory knob), where nothing reads the gradient between here and
    # the updater (no transform, no clipping; the sentinel upcasts what it
    # sums), the upcast moves into the updater's own sweep, so the float32
    # copy of the whole gradient is never held: 4 bytes a parameter of peak
    # memory. Not bit-equal to the early cast (there XLA may keep the
    # backward's float32 values unrounded; here the compute-dtype gradient
    # is what crosses into the updater), so the default mode keeps the early
    # cast.
    late_cast = (fused_cast
                 and _memory.resolve_policy(
                     getattr(conf, "workspace_mode", None)).remat
                 and grad_transform is None
                 and not conf.gradient_normalization
                 and conf.gradient_clip_value is None
                 and conf.gradient_clip_l2 is None)
    tail = gradient_tail(
        conf.updater, net._clip, cdt=cdt, upcast=pdt if late_cast else None,
        grad_transform=grad_transform, constraints=conf.constraints,
        frozen_keys=frozen_keys)

    def run(carry, opt_state, bn_state, step, key, batch, sentinel):
        # the forward differentiates the compute copy where there is one
        # (``_forward``'s cast_floating is the identity on it)
        params = carry[1] if fused_cast else carry
        if accum_steps == 1:
            (loss, new_bn), grads = vg_fn(params, bn_state, key, *batch)
        else:
            # r12 cast hoist: masters to the compute dtype once a step, not
            # once a microbatch; the 16-bit gradients promote exactly into
            # the scan's float32 accumulator
            vg_params = _dt.cast_floating(params, cdt) if cast_hoist \
                else params
            (loss, new_bn), grads = _micro.accumulate_gradients(
                vg_fn, vg_params, bn_state, key, accum_steps, batch,
                weight_fn=weight_fn)
        if cast_hoist or (fused_cast and not late_cast):
            grads = _dt.cast_floating(grads, pdt)
        carry, opt_state, bn_state, sentinel = tail(
            loss, grads, carry, opt_state, step, sentinel,
            (bn_state, new_bn))
        head = carry if fused_cast else (carry,)
        if sentinel is None:  # pre-sentinel call signature (tests/tools)
            return (*head, opt_state, bn_state, loss)
        return (*head, opt_state, bn_state, sentinel, loss)

    if fused_cast:
        def fused_step_fn(params, params_c, opt_state, bn_state, step, key,
                          x, y, fmask, lmask, sentinel=None):
            return run((params, params_c), opt_state, bn_state, step, key,
                       (x, y, fmask, lmask), sentinel)
        return fused_step_fn

    def step_fn(params, opt_state, bn_state, step, key, x, y, fmask, lmask,
                sentinel=None):
        return run(params, opt_state, bn_state, step, key,
                   (x, y, fmask, lmask), sentinel)
    return step_fn


def build_epoch(step, fused, cdt, masks):
    """``epoch_fn(params, opt_state, bn_state, sentinel, start_step, key,
    xs, ys)`` -> ``(params, opt_state, bn_state, sentinel, losses)``: the
    scan of ``step`` (an :func:`engine_step`, unjitted) over the stacked
    batches ``xs``/``ys`` ``[n_batches, B, ...]``. ``masks`` is the engine's
    ``(fmask, lmask)`` of Nones: this path takes no masks. When ``fused`` the
    scan carries the ``cdt`` compute copy: the masters are cast once a
    launch and every later copy is the fused updater's write; the signature
    stays masters in, masters out."""
    def epoch_fn(params, opt_state, bn_state, sentinel, start_step, key, xs,
                 ys):
        carry = (params, _dt.cast_floating(params, cdt)) if fused \
            else (params,)

        def body(c, xy):
            carry, opt_state, bn_state, sentinel, i = c
            bx, by = xy
            k = jax.random.fold_in(key, i)
            *carry, opt_state, bn_state, sentinel, loss = step(
                *carry, opt_state, bn_state, i, k, bx, by, *masks, sentinel)
            return (tuple(carry), opt_state, bn_state, sentinel, i + 1), loss

        (carry, opt_state, bn_state, sentinel, _), losses = jax.lax.scan(
            body, (carry, opt_state, bn_state, sentinel, start_step),
            (xs, ys))
        return carry[0], opt_state, bn_state, sentinel, losses

    return epoch_fn
