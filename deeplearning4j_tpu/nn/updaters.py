"""Gradient updaters (optimizers).

TPU-native equivalent of nd4j's ``GradientUpdater``/``IUpdater`` family
(reference: ``nd4j-api .../linalg/learning/**``† — Sgd, Adam, AdaMax,
AdaDelta, AdaGrad, AMSGrad, Nadam, Nesterovs, RmsProp, NoOp; per SURVEY.md
§2.2; reference mount was empty, citations upstream-relative, unverified).

Design: each updater is a pytree-wise pure function pair
(``init_state``, ``apply``) — the whole update fuses into the compiled train
step (DL4J reached the same place with per-block fused native updater ops;
XLA does the fusion here). State layouts (m/v/etc. per-param) mirror DL4J's
updater-state blocks so checkpoints can round-trip (SURVEY.md §7.3 item 6).

``apply`` returns the DELTA to subtract: ``params_new = params - delta``,
matching DL4J's StepFunction ``params.subi(update)`` convention.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import schedules as _sched

UPDATERS = {}


def _upd(name):
    def deco(cls):
        cls = dataclasses.dataclass(cls)
        cls.kind = name
        UPDATERS[name] = cls
        return cls
    return deco


def _tmap(fn, *trees):
    return jax.tree.map(fn, *trees)


class Updater:
    kind = "base"
    elementwise = True  # apply() is per-element (apply_leaf's shard contract)
    learning_rate: Any = 1e-3

    def lr_at(self, step):
        return _sched.resolve(self.learning_rate).value_at(step)

    def init_state(self, params):
        return {}

    def apply(self, grads, state, params, step):
        """-> (delta_to_subtract, new_state)"""
        raise NotImplementedError

    # -- config JSON round-trip --------------------------------------------
    def to_dict(self) -> Dict:
        d = {"kind": self.kind}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, _sched.Schedule):
                v = v.to_dict()
            d[f.name] = v
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d)
        cls = UPDATERS[d.pop("kind")]
        if isinstance(d.get("learning_rate"), dict):
            d["learning_rate"] = _sched.Schedule.from_dict(d["learning_rate"])
        return cls(**d)


def get(name_or_updater, **kwargs) -> Updater:
    if isinstance(name_or_updater, Updater):
        return name_or_updater
    key = str(name_or_updater).lower()
    if key not in UPDATERS:
        raise ValueError(f"Unknown updater {name_or_updater!r}; known: {sorted(UPDATERS)}")
    return UPDATERS[key](**kwargs)


@_upd("sgd")
class Sgd(Updater):
    learning_rate: Any = 0.1

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        return _tmap(lambda g: lr * g, grads), state


@_upd("nesterovs")
class Nesterovs(Updater):
    """SGD with Nesterov momentum (DL4J default momentum 0.9).

    Matches DL4J's NesterovsUpdater algebra:
    v_{t+1} = mu*v_t - lr*g ; delta = -(mu*v_{t+1} - lr*g) -- i.e. lookahead.
    """
    learning_rate: Any = 0.1
    momentum: float = 0.9

    def init_state(self, params):
        return {"v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        mu = self.momentum
        v_new = _tmap(lambda v, g: mu * v - lr * g, state["v"], grads)
        delta = _tmap(lambda vn, g: -(mu * vn - lr * g), v_new, grads)
        return delta, {"v": v_new}


@_upd("adagrad")
class AdaGrad(Updater):
    learning_rate: Any = 1e-1
    epsilon: float = 1e-6

    def init_state(self, params):
        return {"h": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        h = _tmap(lambda h, g: h + g * g, state["h"], grads)
        delta = _tmap(lambda h, g: lr * g / (jnp.sqrt(h) + self.epsilon), h, grads)
        return delta, {"h": h}


@_upd("rmsprop")
class RmsProp(Updater):
    learning_rate: Any = 1e-1
    decay: float = 0.95
    epsilon: float = 1e-8

    def init_state(self, params):
        return {"g2": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        g2 = _tmap(lambda a, g: self.decay * a + (1 - self.decay) * g * g,
                   state["g2"], grads)
        delta = _tmap(lambda a, g: lr * g / jnp.sqrt(a + self.epsilon), g2, grads)
        return delta, {"g2": g2}


@_upd("adadelta")
class AdaDelta(Updater):
    # AdaDelta has no learning rate (kept for interface uniformity; unused)
    learning_rate: Any = 1.0
    rho: float = 0.95
    epsilon: float = 1e-6

    def init_state(self, params):
        z = _tmap(jnp.zeros_like, params)
        return {"msg": z, "msdx": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        rho, eps = self.rho, self.epsilon
        msg = _tmap(lambda a, g: rho * a + (1 - rho) * g * g, state["msg"], grads)
        delta = _tmap(lambda a, dx, g: jnp.sqrt(dx + eps) / jnp.sqrt(a + eps) * g,
                      msg, state["msdx"], grads)
        msdx = _tmap(lambda dx, d: rho * dx + (1 - rho) * d * d, state["msdx"], delta)
        return delta, {"msg": msg, "msdx": msdx}


@_upd("adam")
class Adam(Updater):
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, params):
        return {"m": _tmap(jnp.zeros_like, params),
                "v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        t = step + 1
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        # DL4J AdamUpdater folds bias correction into the lr
        a = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        delta = _tmap(lambda m, v: a * m / (jnp.sqrt(v) + self.epsilon), m, v)
        return delta, {"m": m, "v": v}


@_upd("adamax")
class AdaMax(Updater):
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, params):
        return {"m": _tmap(jnp.zeros_like, params),
                "u": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        t = step + 1
        b1 = self.beta1
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        u = _tmap(lambda u, g: jnp.maximum(self.beta2 * u, jnp.abs(g)), state["u"], grads)
        a = lr / (1 - b1 ** t)
        delta = _tmap(lambda m, u: a * m / (u + self.epsilon), m, u)
        return delta, {"m": m, "u": u}


@_upd("amsgrad")
class AMSGrad(Updater):
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, params):
        z = _tmap(jnp.zeros_like, params)
        return {"m": z, "v": _tmap(jnp.zeros_like, params),
                "vhat": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        t = step + 1
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        vhat = _tmap(jnp.maximum, state["vhat"], v)
        a = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        delta = _tmap(lambda m, vh: a * m / (jnp.sqrt(vh) + self.epsilon), m, vhat)
        return delta, {"m": m, "v": v, "vhat": vhat}


@_upd("nadam")
class Nadam(Updater):
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, params):
        return {"m": _tmap(jnp.zeros_like, params),
                "v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr_at(step)
        t = step + 1
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        mc = 1 - b1 ** t
        vc = 1 - b2 ** t
        delta = _tmap(
            lambda m, v, g: lr / (jnp.sqrt(v / vc) + self.epsilon) *
            (b1 * m / mc + (1 - b1) * g / mc),
            m, v, grads)
        return delta, {"m": m, "v": v}


@_upd("noop")
class NoOp(Updater):
    learning_rate: Any = 0.0

    def apply(self, grads, state, params, step):
        return _tmap(jnp.zeros_like, grads), state


def apply_leaf(updater, grad, slots, param, step):
    """Pure SINGLE-TENSOR update: ``slots`` is this leaf's updater-state
    slice ``{slot_name: array}`` (e.g. Adam's ``{"m": m_leaf, "v":
    v_leaf}``), and the return is ``(new_param_leaf, new_slots)``.

    This is the contract point the cross-replica sharded weight update
    (ZeRO-1, ``ParallelWrapper(shard_update=True)``) relies on: every
    updater here is strictly **elementwise** (``updater.elementwise``), so
    applying the update to a 1/N shard of ``(grad, slots, param)`` produces
    exactly the matching shard of the full-tensor update — GSPMD can
    therefore reduce-scatter the gradient, run this update on each
    device's shard, and all-gather the fresh params, with bit-identical
    results (tested in tests/test_shard_update.py). A future per-tensor-
    norm updater (LARS-style, ``elementwise=False``) breaks the contract —
    the runtime guard lives in ``ParallelWrapper.__init__``, which rejects
    ``shard_update=True`` for non-elementwise updaters.

    A bare array is a single-leaf pytree, so ``updater.apply`` runs
    unchanged; Adam/RMSProp/AMSGrad/etc. all work with no per-updater code.
    """
    delta, new_slots = updater.apply(grad, slots, param, step)
    return param - delta, new_slots


def apply_leafwise(updater, grads, state, params, step):
    """Per-tensor updater application + subtraction — the form the engines'
    hot train steps use (one small XLA fusion per parameter tensor, which
    XLA schedules in place through the donated scan carry; a raveled flat
    buffer defeats that, PERF.md section 6, DIAG3_r05).

    Returns ``(new_params, new_state)``.
    """
    delta, new_state = updater.apply(grads, state, params, step)
    return _tmap(lambda p, d: p - d, params, delta), new_state


def _cast_leaf(p, compute_dtype):
    """Per-leaf rendition of ``dtypes.cast_floating``: floating leaves to
    the compute dtype, everything else (ints/bools, quantized tensors)
    untouched — the fused-cast outputs must be EXACTLY what a standalone
    ``cast_floating`` sweep over the fresh params would produce."""
    if getattr(p, "__quantized_tensor__", False):
        return p
    if hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating):
        return p.astype(compute_dtype)
    return p


def apply_leaf_cast(updater, grad, slots, param, step, compute_dtype):
    """:func:`apply_leaf` with the mixed-precision master cast folded into
    the parameter write: returns ``(new_param, new_param_compute,
    new_slots)`` where ``new_param_compute = new_param.astype(compute)``
    emitted by the SAME fusion that writes the f32 master (ISSUE 16 — the
    fused master-cast+updater step). The unfused program pays a separate
    full-params HBM sweep for this cast at the top of every forward
    (``master_cast_ms`` in the r18 BERT phase audit); here the cast rides
    the updater's write while ``new_param`` is still in registers.

    The f32 master arithmetic is untouched — ``new_param`` is
    bit-identical to :func:`apply_leaf`'s, and the compute copy is
    bit-identical to casting after the fact (f32->bf16 rounding of the
    same value) — so fused and unfused training trajectories match
    exactly (asserted in tests). Elementwise like :func:`apply_leaf`:
    the ZeRO-1 shard contract carries over to both outputs."""
    new_param, new_slots = apply_leaf(updater, grad, slots, param, step)
    return new_param, _cast_leaf(new_param, compute_dtype), new_slots


def apply_leafwise_cast(updater, grads, state, params, step, compute_dtype):
    """Tree-level :func:`apply_leaf_cast`: the form the engines' fused
    train steps use. Returns ``(new_params, new_params_compute,
    new_state)``."""
    new_params, new_state = apply_leafwise(updater, grads, state, params,
                                           step)
    new_params_c = _tmap(lambda p: _cast_leaf(p, compute_dtype), new_params)
    return new_params, new_params_c, new_state
