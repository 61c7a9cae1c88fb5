"""SameDiff-equivalent define-then-run graph autodiff layer.

TPU-native equivalent of nd4j's SameDiff (reference:
``nd4j-api .../autodiff/samediff/SameDiff.java``,
``.../autodiff/samediff/internal/{InferenceSession,TrainingSession}.java``,
``.../autodiff/samediff/serde/FlatBuffersMapper.java``† per SURVEY.md
§2.2/§3.3; reference mount was empty, citations upstream-relative,
unverified).

Architecture (the §3.3 "TPU translation"): the reference's dependency-tracked
op-at-a-time interpreter (ExecStep queue, ArrayCacheMemoryMgr) is replaced by
trace-once/compile-once: the recorded op list IS the program; executing it
under ``jax.jit`` hands XLA the whole graph for fusion, and the reference's
per-op ``doDiff`` gradient graph construction is ``jax.grad`` of the traced
function — no hand-written backward per op.

Variable kinds mirror SDVariable.VariableType: VARIABLE (trainable),
PLACEHOLDER (fed per call), CONSTANT (baked), ARRAY (op output).

Serialization: JSON graph-def (ops reference catalog names from
``deeplearning4j_tpu.ops``) + npz of VARIABLE/CONSTANT values, zipped — the
moral equivalent of the FlatBuffers ``.fb`` (format is ours; the contract —
graph+weights reload in a fresh process with identical outputs — is the
reference's). This layer is the compile target for the import frontends
(SURVEY.md §3.5).
"""

from __future__ import annotations

import io
import json
import zipfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops as _catalog
from ..runtime import telemetry as _tel

#: how a fit() call made its carry, constants' casts and optimizer state,
#: and when it read its losses: one count a call each
_FIT_PREPARE = _tel.counter(
    "samediff.fit.prepare",
    "SameDiff.fit calls by how the call's state was prepared (compiled: "
    "one launch)")
_FIT_READBACK = _tel.counter(
    "samediff.fit.readback",
    "SameDiff.fit calls by when a step's loss is read (deferred: behind "
    "the next launch, no listener attached; per_step: before it)")

VARIABLE = "VARIABLE"
PLACEHOLDER = "PLACEHOLDER"
CONSTANT = "CONSTANT"
ARRAY = "ARRAY"


class SDVariable:
    """Symbolic handle into a SameDiff graph (nd4j ``SDVariable``†)."""

    def __init__(self, sd: "SameDiff", name: str, kind: str,
                 shape: Optional[Tuple[int, ...]] = None):
        self.sd = sd
        self.name = name
        self.kind = kind
        self.shape = tuple(shape) if shape is not None else None

    # ---- operator sugar (each records a graph op) --------------------------
    def _bin(self, op, other, swap=False):
        other = self.sd._lift(other)
        a, b = (other, self) if swap else (self, other)
        return self.sd.call(op, a, b)

    def __add__(self, o):
        return self._bin("math.add", o)

    def __radd__(self, o):
        return self._bin("math.add", o, swap=True)

    def __sub__(self, o):
        return self._bin("math.sub", o)

    def __rsub__(self, o):
        return self._bin("math.sub", o, swap=True)

    def __mul__(self, o):
        return self._bin("math.mul", o)

    def __rmul__(self, o):
        return self._bin("math.mul", o, swap=True)

    def __truediv__(self, o):
        return self._bin("math.div", o)

    def __rtruediv__(self, o):
        return self._bin("math.div", o, swap=True)

    def __pow__(self, o):
        return self._bin("math.pow", o)

    def __neg__(self):
        return self.sd.call("math.neg", self)

    def __matmul__(self, o):
        return self._bin("linalg.mmul", o)

    # ---- common graph methods (SDVariable sugar) ---------------------------
    def mmul(self, other, **kw):
        return self.sd.call("linalg.mmul", self, self.sd._lift(other), **kw)

    def add(self, other):
        return self.__add__(other)

    def sub(self, other):
        return self.__sub__(other)

    def mul(self, other):
        return self.__mul__(other)

    def div(self, other):
        return self.__truediv__(other)

    def reshape(self, *shape):
        return self.sd.call("shape.reshape", self, attrs={"shape": list(shape)})

    def transpose(self, *axes):
        return self.sd.call("shape.transpose", self,
                            attrs={"axes": list(axes)} if axes else {})

    def sum(self, axis=None, keepdims=False):
        return self.sd.call("reduce.sum", self,
                            attrs={"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return self.sd.call("reduce.mean", self,
                            attrs={"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return self.sd.call("reduce.max", self,
                            attrs={"axis": axis, "keepdims": keepdims})

    def std(self, axis=None, keepdims=False):
        return self.sd.call("reduce.std", self,
                            attrs={"axis": axis, "keepdims": keepdims})

    def eval(self, feeds: Optional[Dict[str, Any]] = None):
        """Evaluate just this variable (session compile + execute)."""
        return self.sd.output(feeds or {}, [self.name])[self.name]


class _OpRecord:
    """One recorded op application. ``outputs`` is a list — multi-output ops
    (split/unstack/top_k, nd4j multi-output DynamicCustomOps) bind every
    element of the returned tuple to its own graph name. Control-flow
    records (op ``__cond__``/``__while__``/``__scan__``) carry their traced
    subgraphs in ``attrs`` (see SameDiff.cond)."""
    __slots__ = ("op", "inputs", "outputs", "attrs")

    def __init__(self, op: str, inputs: List[str], outputs, attrs: Dict[str, Any]):
        self.op = op
        self.inputs = inputs
        self.outputs = [outputs] if isinstance(outputs, str) else list(outputs)
        self.attrs = attrs

    @property
    def output(self) -> str:
        return self.outputs[0]

    def referenced(self) -> List[str]:
        """All graph names this record reads — its direct inputs plus, for
        control flow, everything its subgraphs read (captured parent
        references included; formals excluded is unnecessary for
        reachability since formals map back to inputs anyway)."""
        names = list(self.inputs)
        for key in ("true", "false", "cond", "body"):
            sub = self.attrs.get(key)
            if isinstance(sub, _Subgraph):
                for rec in sub.ops:
                    names.extend(rec.referenced())
        return names


class _Subgraph:
    """A traced sub-program for control flow: formal parameter names, result
    names, and the op list. Ops may reference names from the ENCLOSING graph
    (captured constants/variables) — at execution the subgraph environment
    is seeded with the parent environment."""
    __slots__ = ("params", "results", "ops")

    def __init__(self, params: List[str], results: List[str],
                 ops: List[_OpRecord]):
        self.params = list(params)
        self.results = list(results)
        self.ops = list(ops)

    def to_dict(self):
        return {"params": self.params, "results": self.results,
                "ops": [_op_to_dict(r) for r in self.ops]}

    @staticmethod
    def from_dict(d):
        return _Subgraph(d["params"], d["results"],
                         [_op_from_dict(od) for od in d["ops"]])


from ..runtime.sentinel import SentinelCounterMixin as _SentinelCounterMixin


class SameDiff(_SentinelCounterMixin):
    """The graph container + session (nd4j ``SameDiff`` / sessions†).
    Inherits the divergence-sentinel counter surface
    (``resilience_counters`` et al.) from the shared mixin."""

    def __init__(self):
        self._vars: Dict[str, SDVariable] = {}
        self._values: Dict[str, jnp.ndarray] = {}   # VARIABLE + CONSTANT
        self._ops: List[_OpRecord] = []             # creation order == topo
        self._counter = 0
        self._fn_cache: Dict[Tuple, Callable] = {}
        # fit()'s programs: the spec each was last built for, kept past the
        # mutators' pops so a rebuild can name its cause
        self._last_fit_specs: Dict[str, Tuple] = {}
        self.updater = None
        self.loss_name: Optional[str] = None
        self._listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self.train_config: Dict[str, Any] = {}
        self.dtype = "FLOAT"  # "BFLOAT16" = bf16 compute / fp32 masters
        # activation-checkpoint policy for the compiled fit step
        # (none | full | dots_saveable | every_<k> — autodiff/remat.py
        # segments the op list at attention anchors)
        self.workspace_mode = "none"
        # divergence-sentinel counter tree (runtime/sentinel.py), threaded
        # through the compiled fit step like the optimizer state
        self._sentinel = None

    # listener-facing Model protocol (Score/Collect/Checkpoint listeners)
    def score(self) -> float:
        return self._score

    def set_listeners(self, *listeners) -> "SameDiff":
        self._listeners = list(listeners)
        return self

    def add_listener(self, l) -> "SameDiff":
        self._listeners.append(l)
        return self

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ------------------------------------------------------------ variables
    def _fresh(self, base: str) -> str:
        self._counter += 1
        name = f"{base}_{self._counter}"
        while name in self._vars:
            self._counter += 1
            name = f"{base}_{self._counter}"
        return name

    def _register(self, name, kind, shape=None) -> SDVariable:
        if name in self._vars:
            raise ValueError(f"variable {name!r} already exists")
        v = SDVariable(self, name, kind, shape)
        self._vars[name] = v
        return v

    def placeholder(self, name: str, shape=None, dtype=jnp.float32) -> SDVariable:
        return self._register(name, PLACEHOLDER, shape)

    def var(self, name: str, value) -> SDVariable:
        """Trainable VARIABLE with an initial value."""
        arr = jnp.asarray(value)
        v = self._register(name, VARIABLE, arr.shape)
        self._values[name] = arr
        return v

    def constant(self, name: str, value) -> SDVariable:
        arr = jnp.asarray(value)
        v = self._register(name, CONSTANT, arr.shape)
        self._values[name] = arr
        return v

    def _lift(self, value) -> SDVariable:
        """Lift a python/numpy scalar or array into a CONSTANT."""
        if isinstance(value, SDVariable):
            return value
        return self.constant(self._fresh("const"), value)

    # ----------------------------------------------------------------- ops
    def call(self, op_name: str, *inputs: SDVariable, name: Optional[str] = None,
             attrs: Optional[Dict[str, Any]] = None, **kw_attrs) -> SDVariable:
        """Record a catalog op application; returns the output SDVariable."""
        if _catalog.lookup(op_name) is None:
            raise ValueError(f"unknown op {op_name!r} (not in the catalog)")
        attrs = dict(attrs or {})
        attrs.update(kw_attrs)
        for v in inputs:
            if v.name not in self._vars:
                raise ValueError(f"input {v.name!r} is not in this graph")
        out = name or self._fresh(op_name.split(".")[-1])
        v = self._register(out, ARRAY)
        self._ops.append(_OpRecord(op_name, [i.name for i in inputs], out, attrs))
        self._fn_cache.clear()
        return v

    def call_multi(self, op_name: str, *inputs: SDVariable, n_outputs: int,
                   name: Optional[str] = None,
                   attrs: Optional[Dict[str, Any]] = None,
                   **kw_attrs) -> Tuple[SDVariable, ...]:
        """Record a MULTI-OUTPUT catalog op (split/unstack/top_k/...; nd4j
        multi-output DynamicCustomOp equivalent). The op must return a
        tuple/list of ``n_outputs`` arrays. ``name`` may be a base string
        (outputs named ``<base>``, ``<base>__k``) or a sequence of
        ``n_outputs`` explicit names (importers bind source-graph tensor
        names this way); None entries get generated names."""
        if _catalog.lookup(op_name) is None:
            raise ValueError(f"unknown op {op_name!r} (not in the catalog)")
        if n_outputs < 1:
            raise ValueError("n_outputs must be >= 1")
        attrs = dict(attrs or {})
        attrs.update(kw_attrs)
        for v in inputs:
            if v.name not in self._vars:
                raise ValueError(f"input {v.name!r} is not in this graph")
        if isinstance(name, (list, tuple)):
            if len(name) != n_outputs:
                raise ValueError(
                    f"{len(name)} output names for n_outputs={n_outputs}")
            outs = [n or self._fresh(op_name.split(".")[-1]) for n in name]
        else:
            base = name or self._fresh(op_name.split(".")[-1])
            outs = [base if k == 0 else f"{base}__{k}"
                    for k in range(n_outputs)]
        vs = tuple(self._register(o, ARRAY) for o in outs)
        self._ops.append(_OpRecord(op_name, [i.name for i in inputs], outs, attrs))
        self._fn_cache.clear()
        return vs

    # ------------------------------------------------------------ control flow
    def _trace_subgraph(self, fn: Callable, formals: Sequence[SDVariable]):
        """Run a Python builder function while recording into a fresh op
        list. The builder receives this SameDiff (so constants/captured
        variables land in the shared registry) plus the formal SDVariables;
        it returns one SDVariable or a tuple."""
        outer_ops = self._ops
        self._ops = []
        try:
            res = fn(self, *formals)
        finally:
            sub_ops, self._ops = self._ops, outer_ops
        res_vars = list(res) if isinstance(res, (tuple, list)) else [res]
        return _Subgraph([f.name for f in formals],
                         [r.name for r in res_vars], sub_ops), res_vars

    def cond(self, pred: SDVariable, true_fn: Callable, false_fn: Callable,
             *operands: SDVariable, name: Optional[str] = None
             ) -> Tuple[SDVariable, ...]:
        """``lax.cond`` record (nd4j If/Switch-Merge equivalent). Both
        branch builders get ``(sd, *formal_operands)`` and must return
        structurally matching outputs. Returns the output SDVariables
        (tuple even for a single output)."""
        formals = [self._register(self._fresh("cond_arg"), ARRAY)
                   for _ in operands]
        sub_t, res_t = self._trace_subgraph(true_fn, formals)
        formals_f = [self._register(self._fresh("cond_arg"), ARRAY)
                     for _ in operands]
        sub_f, res_f = self._trace_subgraph(false_fn, formals_f)
        if len(res_t) != len(res_f):
            raise ValueError(
                f"cond branches return {len(res_t)} vs {len(res_f)} outputs")
        base = name or self._fresh("cond")
        outs = [base if k == 0 else f"{base}__{k}" for k in range(len(res_t))]
        vs = tuple(self._register(o, ARRAY) for o in outs)
        self._ops.append(_OpRecord(
            "__cond__", [pred.name] + [o.name for o in operands], outs,
            {"true": sub_t, "false": sub_f}))
        self._fn_cache.clear()
        return vs

    def while_loop(self, cond_fn: Callable, body_fn: Callable,
                   *loop_vars: SDVariable, name: Optional[str] = None
                   ) -> Tuple[SDVariable, ...]:
        """``lax.while_loop`` record (nd4j While equivalent). ``cond_fn``
        returns a scalar-bool SDVariable; ``body_fn`` returns new loop vars
        (same structure). Reverse-mode gradients through a while loop are
        not defined (same as JAX); use scan for differentiable loops."""
        formals_c = [self._register(self._fresh("while_arg"), ARRAY)
                     for _ in loop_vars]
        sub_c, res_c = self._trace_subgraph(cond_fn, formals_c)
        if len(res_c) != 1:
            raise ValueError("while_loop cond_fn must return one scalar bool")
        formals_b = [self._register(self._fresh("while_arg"), ARRAY)
                     for _ in loop_vars]
        sub_b, res_b = self._trace_subgraph(body_fn, formals_b)
        if len(res_b) != len(loop_vars):
            raise ValueError(
                f"while_loop body returns {len(res_b)} values for "
                f"{len(loop_vars)} loop vars")
        base = name or self._fresh("while")
        outs = [base if k == 0 else f"{base}__{k}"
                for k in range(len(loop_vars))]
        vs = tuple(self._register(o, ARRAY) for o in outs)
        self._ops.append(_OpRecord("__while__", [o.name for o in loop_vars],
                                   outs, {"cond": sub_c, "body": sub_b}))
        self._fn_cache.clear()
        return vs

    def scan(self, body_fn: Callable, carry: Sequence[SDVariable],
             xs: Sequence[SDVariable], name: Optional[str] = None
             ) -> Tuple[Tuple[SDVariable, ...], Tuple[SDVariable, ...]]:
        """``lax.scan`` record: ``body_fn(sd, *carry, *x_slices)`` returns
        ``(*new_carry, *y_slices)``. ``xs`` are scanned over their leading
        axis. Returns ``(final_carry_vars, stacked_y_vars)``. Differentiable
        (the TPU-native way to express sequential loops)."""
        carry = list(carry)
        xs = list(xs)
        formals = [self._register(self._fresh("scan_arg"), ARRAY)
                   for _ in range(len(carry) + len(xs))]
        sub, res = self._trace_subgraph(body_fn, formals)
        n_carry = len(carry)
        n_ys = len(res) - n_carry
        if n_ys < 0:
            raise ValueError("scan body must return at least the new carry")
        base = name or self._fresh("scan")
        outs = [base if k == 0 else f"{base}__{k}"
                for k in range(n_carry + n_ys)]
        vs = tuple(self._register(o, ARRAY) for o in outs)
        self._ops.append(_OpRecord(
            "__scan__", [c.name for c in carry] + [x.name for x in xs], outs,
            {"body": sub, "n_carry": n_carry}))
        self._fn_cache.clear()
        return vs[:n_carry], vs[n_carry:]

    # nd4j namespace sugar (sd.nn()/sd.math() style collapsed to methods)
    def relu(self, x, name=None):
        return self.call("act.relu", x, name=name)

    def sigmoid(self, x, name=None):
        return self.call("act.sigmoid", x, name=name)

    def tanh(self, x, name=None):
        return self.call("act.tanh", x, name=name)

    def softmax(self, x, name=None):
        return self.call("act.softmax", x, name=name)

    def mmul(self, a, b, name=None):
        return self.call("linalg.mmul", a, b, name=name)

    # ------------------------------------------------------------ execution
    def _compute(self, values: Dict[str, jnp.ndarray],
                 feeds: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Pure topo-order evaluation of the recorded program."""
        env: Dict[str, jnp.ndarray] = {}
        env.update(values)
        env.update(feeds)
        self._exec_ops(self._ops, env)
        return env

    def _exec_ops(self, ops: List[_OpRecord], env: Dict[str, jnp.ndarray]):
        """Execute a recorded op list into ``env`` (shared by subgraphs —
        the recursion point for control flow)."""
        for rec in ops:
            if rec.op == "__cond__":
                pred = jnp.asarray(env[rec.inputs[0]], bool).reshape(())
                operands = tuple(env[i] for i in rec.inputs[1:])
                t, f = rec.attrs["true"], rec.attrs["false"]
                res = jax.lax.cond(pred,
                                   self._subgraph_fn(t, env),
                                   self._subgraph_fn(f, env), operands)
            elif rec.op == "__while__":
                operands = tuple(env[i] for i in rec.inputs)
                c, b = rec.attrs["cond"], rec.attrs["body"]
                cf = self._subgraph_fn(c, env)
                bf = self._subgraph_fn(b, env)
                res = jax.lax.while_loop(
                    lambda vs: jnp.asarray(cf(vs)[0], bool).reshape(()),
                    bf, operands)
            elif rec.op == "__scan__":
                n_carry = int(rec.attrs["n_carry"])
                carry0 = tuple(env[i] for i in rec.inputs[:n_carry])
                xs = tuple(env[i] for i in rec.inputs[n_carry:])
                bf = self._subgraph_fn(rec.attrs["body"], env)

                def scan_body(carry, x_slices, _bf=bf, _n=n_carry):
                    out = _bf(tuple(carry) + tuple(x_slices))
                    return out[:_n], out[_n:]
                final, ys = jax.lax.scan(scan_body, carry0, xs)
                res = tuple(final) + tuple(ys)
            else:
                fn = _catalog.get(rec.op).fn
                args = [env[i] for i in rec.inputs]
                attrs = {k: _attr_in(v) for k, v in rec.attrs.items()}
                res = fn(*args, **attrs)
            if len(rec.outputs) == 1:
                env[rec.outputs[0]] = res if not isinstance(res, (tuple, list)) \
                    else res[0]
            else:
                if not isinstance(res, (tuple, list)) or \
                        len(res) != len(rec.outputs):
                    got = (len(res) if isinstance(res, (tuple, list))
                           else type(res).__name__)
                    raise ValueError(
                        f"op {rec.op!r} bound to {len(rec.outputs)} outputs "
                        f"but returned {got}")
                for o, r in zip(rec.outputs, res):
                    env[o] = r

    def _subgraph_fn(self, sub: _Subgraph, parent_env: Dict[str, jnp.ndarray]):
        """Callable over a tuple of operand values; the subgraph environment
        is seeded with a SNAPSHOT of the parent env so captured names
        (constants, variables, earlier results) resolve — they become
        closure constants of the traced branch, exactly lax semantics."""
        captured = dict(parent_env)

        def run(operand_vals):
            env = dict(captured)
            env.update(zip(sub.params, operand_vals))
            self._exec_ops(sub.ops, env)
            return tuple(env[r] for r in sub.results)
        return run

    def _session(self, targets: Tuple[str, ...]) -> Callable:
        """Compile-once-execute-many (InferenceSession equivalent): one jit
        program per requested target set."""
        key = targets
        if key not in self._fn_cache:
            def fn(values, feeds):
                env = self._compute(values, feeds)
                return {t: env[t] for t in targets}
            self._fn_cache[key] = jax.jit(fn)
        return self._fn_cache[key]

    def output(self, feeds: Dict[str, Any], targets: Sequence[str]) -> Dict[str, np.ndarray]:
        """Evaluate target variables under the given placeholder feeds."""
        missing = [n for n, v in self._vars.items()
                   if v.kind == PLACEHOLDER and n not in feeds]
        needed = self._needed_placeholders(targets)
        missing = [m for m in missing if m in needed]
        if missing:
            raise ValueError(f"missing placeholder feeds: {missing}")
        fn = self._session(tuple(targets))
        out = fn(self._values, {k: jnp.asarray(v) for k, v in feeds.items()
                                if k in needed})
        return {k: np.asarray(v) for k, v in out.items()}

    def _needed_placeholders(self, targets) -> set:
        """Backward reachability: which placeholders feed the targets
        (traverses control-flow subgraphs via _OpRecord.referenced)."""
        producers = {o: r for r in self._ops for o in r.outputs}
        need, stack = set(), list(targets)
        seen = set()
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            v = self._vars.get(n)
            if v is not None and v.kind == PLACEHOLDER:
                need.add(n)
            rec = producers.get(n)
            if rec:
                stack.extend(rec.referenced())
        return need

    # ------------------------------------------------------------- training
    def set_loss(self, loss: SDVariable) -> "SameDiff":
        self.loss_name = loss.name
        return self

    def set_updater(self, updater) -> "SameDiff":
        self.updater = updater
        return self

    def set_dtype(self, dtype) -> "SameDiff":
        """Training dtype policy — mirrors the nn engines' ``dtype=
        "BFLOAT16"`` (SameDiff TrainingConfig dtype†, SURVEY.md §7.3.8):
        under a 16-bit policy the compiled fit step keeps fp32 MASTER
        weights/updater state and runs the graph (matmuls included) in the
        compute dtype; gradients flow back through the cast and land in
        fp32. Affects ``fit`` only — ``exec``/``output``/``grad`` stay in
        the recorded dtypes (imported-graph inference parity)."""
        from .. import dtypes as _dt
        _dt.resolve(dtype)  # validate early
        self.dtype = dtype
        self._drop_fit_programs()
        return self

    def set_workspace_mode(self, mode) -> "SameDiff":
        """Activation-checkpoint policy for the compiled fit step
        (engine-parity knob — ``nn/memory.py`` policies): the recorded op
        list is segmented into transformer-block chunks at attention
        anchors (``autodiff/remat.py``) and each segment replays inside
        ``jax.checkpoint``, so the backward pass rematerializes block
        interiors instead of keeping them in HBM. The policy is part of
        the fit-step cache spec — mutating it retraces. Affects ``fit``
        only; ``exec``/``output``/``grad`` never remat (no backward pass
        to trade against)."""
        from ..nn import memory as _memory
        self.workspace_mode = _memory.resolve_policy(mode).name
        self._drop_fit_programs()
        return self

    def set_training_config(self, updater=None, l1: float = 0.0,
                            l2: float = 0.0,
                            gradient_clip_value: Optional[float] = None,
                            gradient_clip_l2: Optional[float] = None,
                            gradient_normalization: Optional[str] = None,
                            gradient_normalization_threshold: float = 1.0
                            ) -> "SameDiff":
        """nd4j ``TrainingConfig`` parity: updater + l1/l2 regularization
        over VARIABLEs + gradient clipping/normalization, all applied inside
        the compiled fit step. GradientNormalization 'per layer' means per
        VARIABLE here (SameDiff has no layer grouping — recorded)."""
        from ..nn import gradnorm as _gn
        _gn.validate(gradient_normalization)
        if updater is not None:
            self.updater = updater
        self.train_config = {
            "l1": float(l1), "l2": float(l2),
            "clip_value": gradient_clip_value,
            "clip_l2": gradient_clip_l2,
            "grad_norm": gradient_normalization,
            "grad_norm_threshold": float(gradient_normalization_threshold),
        }
        self._drop_fit_programs()
        return self

    def grad(self, feeds: Dict[str, Any],
             wrt: Optional[Sequence[str]] = None) -> Dict[str, np.ndarray]:
        """Gradients of the loss w.r.t. VARIABLEs (createGradFunction +
        execBackwards equivalent — here just jax.grad of the traced program)."""
        if self.loss_name is None:
            raise ValueError("set_loss(...) first")
        wrt = list(wrt or [n for n, v in self._vars.items()
                           if v.kind == VARIABLE])
        loss_name = self.loss_name

        def loss_fn(train_vals, other_vals, feeds):
            env = self._compute({**other_vals, **train_vals}, feeds)
            return env[loss_name]

        train = {n: self._values[n] for n in wrt}
        other = {n: v for n, v in self._values.items() if n not in train}
        g = jax.jit(jax.grad(loss_fn))(
            train, other, {k: jnp.asarray(v) for k, v in feeds.items()})
        return {k: np.asarray(v) for k, v in g.items()}

    def _cast_other_vals(self, other_vals):
        """bf16 audit fix (ISSUE 14 satellite, the r12 cast hoist's
        sibling): under a 16-bit dtype policy, cast the NON-trainable
        values (imported CONSTs, frozen weights) to the compute dtype
        ONCE, host-call-side, instead of re-casting them inside every
        compiled fit step — they never change between steps, so the
        per-step ``cast_floating`` over them was pure wasted bandwidth
        (for a frozen-encoder fine-tune, the entire encoder re-cast
        every step). The step's in-graph ``cast_floating`` stays as a
        safety net and is an IDENTITY (zero jaxpr eqns) for pre-cast
        leaves, so a caller handing raw f32 values still computes
        correctly — just without the hoist. Bit-equal to the un-hoisted
        program: the same cast, done once (tested, jaxpr-regressed).
        Identity under a non-mixed policy."""
        from .. import dtypes as _dt
        if not _dt.is_mixed(self.dtype):
            return other_vals
        return _dt.cast_floating(other_vals, _dt.resolve(self.dtype))

    def _fit_loss_fn(self, split_penalty: bool = False):
        """The pure training loss ``(train_vals, other_vals, feeds) ->
        scalar`` the fit step differentiates — factored out so
        :meth:`memory_report` can account its forward→backward residuals.
        Applies the ``workspace_mode`` remat policy: the op-list replay is
        segmented at attention anchors and each segment rematerializes in
        the backward pass (``autodiff/remat.py``).

        ``split_penalty=True`` returns the four-arg form ``(tv_penalty,
        tv_forward, other_vals, feeds)`` the fused master-cast updater
        step (ISSUE 16) differentiates: the forward reads the
        compute-dtype copies carried across steps (``cast_floating`` on
        them is an identity, so the traced forward is bit-equal to the
        unfused one) while l1/l2 penalties keep reading the f32 MASTERS
        — exactly the split the unfused program has. The default form is
        the split one applied to the same tree twice."""
        loss_name = self.loss_name
        tc = dict(self.train_config)
        from .. import dtypes as _dt
        from ..nn import memory as _memory
        mixed = _dt.is_mixed(self.dtype)
        cdt = _dt.resolve(self.dtype)
        policy = _memory.resolve_policy(getattr(self, "workspace_mode", None))

        def loss_split(tv_pen, tv, other_vals, feeds):
            # the scope names the forward's operations in a device trace;
            # its transpose shows as transpose(jvp(forward))
            with jax.named_scope("forward"):
                vals, fd = {**other_vals, **tv}, feeds
                if mixed:
                    # fp32 masters -> compute-dtype working copies; grads
                    # flow back through the cast into fp32 (engine parity).
                    # Identity (zero eqns) for pre-cast fused-carry leaves.
                    vals = _dt.cast_floating(vals, cdt)
                    fd = _dt.cast_floating(fd, cdt)
                if policy.remat:
                    from . import remat as _remat
                    env = _remat.compute_with_remat(self, vals, fd,
                                                    (loss_name,), policy)
                else:
                    env = self._compute(vals, fd)
                total = env[loss_name]
                if mixed:  # regularization/score accumulate in fp32
                    total = jnp.asarray(total, jnp.float32)
                if tc.get("l1"):
                    total = total + tc["l1"] * sum(
                        jnp.sum(jnp.abs(v)) for v in tv_pen.values())
                if tc.get("l2"):
                    total = total + 0.5 * tc["l2"] * sum(
                        jnp.sum(jnp.square(v)) for v in tv_pen.values())
                return total

        if split_penalty:
            return loss_split

        def loss_fn(tv, other_vals, feeds):
            return loss_split(tv, tv, other_vals, feeds)

        return loss_fn

    def _fit_spec(self):
        """The key of the compiled fit step: everything its trace bakes in.
        Loss/updater/train-config, the dtype policy, the workspace_mode
        remat policy, the Environment's f32 matmul-precision mode, and the
        VARIABLE set — mutating any of them must retrace instead of silently
        reusing the old executable (:meth:`_fit_step_cached` compares
        specs)."""
        from .. import environment as _envmod
        return ("fit", self.loss_name,
                json.dumps(self.updater.to_dict(), sort_keys=True,
                           default=str),
                json.dumps(self.train_config, sort_keys=True, default=str),
                str(self.dtype),
                str(getattr(self, "workspace_mode", "none")),
                str(_envmod.Environment.instance().f32_matmul_precision),
                tuple(n for n, v in self._vars.items()
                      if v.kind == VARIABLE),
                "fused_cast" if self.fused_updater_active() else "plain")

    def _make_fit_step(self):
        """(spec, jitted step fn) for the compiled fit step; the spec is
        :meth:`_fit_spec`'s. The step differentiates the loss here (its own
        split-penalty gradient under the fused master-cast updater) and
        hands the gradient to ``nn/trainstep.py``'s tail, the engines' own:
        clip, divergence sentinel, guarded updater, counters."""
        tc = dict(self.train_config)
        fused_cast = self.fused_updater_active()
        loss_fn = self._fit_loss_fn(split_penalty=fused_cast)
        penalty = bool(tc.get("l1")) or bool(tc.get("l2"))
        from .. import dtypes as _dt
        from ..nn import gradnorm as _gn
        from ..nn import trainstep as _ts

        def clip(grads):
            # the shared engine clip pipeline; per-VARIABLE grouping means
            # each leaf is wrapped as its own "layer" for the mode step
            # (value/L2 clip are tree-shape agnostic, so the wrap is safe)
            wrapped = {k: {"g": g} for k, g in grads.items()}
            wrapped, clip_events = _gn.clip_with_events(
                tc.get("grad_norm"), tc.get("grad_norm_threshold", 1.0),
                tc.get("clip_value"), tc.get("clip_l2"), wrapped)
            return {k: v["g"] for k, v in wrapped.items()}, clip_events

        tail = _ts.gradient_tail(self.updater, clip,
                                 cdt=_dt.resolve(self.dtype))

        def step(carry, opt_state, other_vals, step_i, feeds,
                 sentinel=None):
            if fused_cast:
                # FUSED MASTER-CAST UPDATER STEP (ISSUE 16): the first arg
                # is the ``(masters, compute_copies)`` carry from
                # _fit_carry(). The forward reads the pre-cast compute
                # copies (cast_floating on them is identity -> bit-equal
                # forward); cotangents come back 16-bit and are upcast
                # EXACTLY like the unfused cast's transpose (f32<-16-bit
                # convert is value-exact); the tail's updater emits the
                # fresh compute copy in the same fusion that writes the f32
                # master, so the standalone per-step master-cast sweep
                # disappears from the program.
                tv, tv_c = carry
                if penalty:
                    # penalties read the f32 masters (argnum 0), the
                    # forward reads the compute copies (argnum 1) — the
                    # exact split the unfused program differentiates; the
                    # two cotangent paths sum commutatively (bit-equal)
                    loss, (g_m, g_c) = jax.value_and_grad(
                        lambda a, b: loss_fn(a, b, other_vals, feeds),
                        argnums=(0, 1))(tv, tv_c)
                    grads = jax.tree.map(
                        lambda p, gm, gc: gm + gc.astype(p.dtype),
                        tv, g_m, g_c)
                else:
                    loss, g_c = jax.value_and_grad(
                        lambda b: loss_fn(tv, b, other_vals, feeds))(tv_c)
                    grads = jax.tree.map(lambda p, gc: gc.astype(p.dtype),
                                         tv, g_c)
            else:
                loss, grads = jax.value_and_grad(
                    lambda tv: loss_fn(tv, other_vals, feeds))(carry)
            carry, opt_state, _, sentinel = tail(loss, grads, carry,
                                                 opt_state, step_i, sentinel)
            if sentinel is None:  # pre-sentinel call signature
                return carry, opt_state, loss
            return carry, opt_state, sentinel, loss

        return self._fit_spec(), jax.jit(step, donate_argnums=(0, 1))

    # ------------------------------------------- fused master-cast carry
    def fused_updater_active(self) -> bool:
        """Does the compiled fit step use the fused master-cast updater
        (ISSUE 16)? True under a 16-bit dtype policy with the fused-
        epilogue library enabled (``DL4J_TPU_FUSED_EPILOGUES`` != off).
        When True the step's first argument is the ``(masters,
        compute_copies)`` tuple from :meth:`_fit_carry`, not the bare
        master dict — external drivers (bench) go through the carry
        helpers instead of assuming the plain signature."""
        from ..ops import fused_epilogues as _fe
        # the SameDiff step differentiates penalties against the masters
        # explicitly (split_penalty), so l1/l2 never forces a fallback
        return _fe.route_updater(self.dtype) is None

    def _fit_carry(self, train_vals):
        """The compiled step's first argument for ``train_vals``: the
        ``(masters, compute_copies)`` pair when the fused updater is
        active (the ONE remaining host-side cast — every subsequent step
        re-emits the copies from inside the updater), else the bare
        master dict."""
        if not self.fused_updater_active():
            return train_vals
        from .. import dtypes as _dt
        return (train_vals,
                _dt.cast_floating(train_vals, _dt.resolve(self.dtype)))

    @staticmethod
    def _carry_masters(carry):
        """The f32 masters view of a step carry (either signature)."""
        return carry[0] if isinstance(carry, tuple) else carry

    #: spec tuple positions -> retrace-tracker cause (see _make_fit_step
    #: for the tuple layout); anything else is a generic config change
    _SPEC_CAUSES = {4: "dtype_policy", 5: "workspace_mode", 6: "precision",
                    8: "fused_updater"}

    #: the two compiled programs of fit(), kept in ``_fn_cache`` under these
    #: keys and dropped together
    _FIT_PROGRAMS = ("__fit_step__", "__fit_prepare__")

    def _drop_fit_programs(self):
        """Release fit()'s compiled programs (the mutators of what
        :meth:`_fit_spec` holds call this: an old executable of a big graph
        is device memory)."""
        for key in self._FIT_PROGRAMS:
            self._fn_cache.pop(key, None)

    def _fit_program_cached(self, key, site, build):
        """``_fn_cache[key]``'s program if it was built for today's
        :meth:`_fit_spec`, else ``build()``'s, kept in its place. ONE of
        each is kept across fit() calls — re-jitting a large imported graph
        per call costs seconds (found fine-tuning BERT-base). Every rebuild
        reports to the retrace tracker under ``site`` with the spec field
        that changed as its cause — a silent retrace of a BERT-sized import
        is exactly what ISSUE 6 makes visible."""
        spec = self._fit_spec()
        cached = self._fn_cache.get(key)
        if cached is not None and cached[0] == spec:
            return cached[1]
        fn = build()
        # the mutators pop the cache to release the old executable, so the
        # cause diff runs against the last-built spec kept separately
        prev_spec = self._last_fit_specs.get(key)
        if prev_spec is None:
            cause = "first_build"
        else:
            changed = [i for i, (a, b) in enumerate(zip(prev_spec, spec))
                       if a != b]
            cause = next((self._SPEC_CAUSES[i] for i in changed
                          if i in self._SPEC_CAUSES), "config_change")
        _tel.record_compile(site, cause, loss=str(spec[1]))
        self._fn_cache[key] = (spec, fn)
        self._last_fit_specs[key] = spec
        return fn

    def _fit_step_cached(self):
        """The cached compiled fit step (built if absent/stale)."""
        def build():
            # dispatch accounting rides the cache miss: ONE decision count
            # per compiled step, not one per fit() call (mirrors the
            # kernel-side fused_epilogues.dispatch discipline: zero silent
            # fallbacks)
            from ..ops import fused_epilogues as _fe
            _fe.dispatch_updater(self.dtype)
            return self._make_fit_step()[1]

        return self._fit_program_cached("__fit_step__", "samediff.fit_step",
                                        build)

    def _fit_prepare_cached(self):
        """The cached compiled preparation of one fit() call: ``(train_vals,
        castable) -> (compute copies | None, cast castable, optimizer
        state)``, where ``castable`` is :meth:`_castable`'s share of the
        non-trainable values. It is :meth:`_fit_carry`,
        :meth:`_cast_other_vals` and ``updater.init_state`` traced into ONE
        program: done eagerly they are an ``astype`` a VARIABLE, one a
        floating constant and a ``zeros_like`` a state leaf, some 670
        dispatches a call for an imported BERT-base, during which the
        device waits. The same XLA converts and broadcasts, so bit-equal.
        The masters are arguments only: as results they would be copied."""
        def build():
            updater, fused = self.updater, self.fused_updater_active()

            def prepare(train_vals, castable):
                copies = self._fit_carry(train_vals)[1] if fused else None
                return (copies, self._cast_other_vals(castable),
                        updater.init_state(train_vals))
            return jax.jit(prepare)

        return self._fit_program_cached(
            "__fit_prepare__", "samediff.fit_prepare", build)

    def _castable(self, other_vals):
        """The leaves of ``other_vals`` that :meth:`_cast_other_vals`
        changes: only they go through the compiled preparation (a leaf that
        a jitted function returns as it came is a copy)."""
        from .. import dtypes as _dt
        if not _dt.is_mixed(self.dtype):
            return {}
        cdt = np.dtype(_dt.resolve(self.dtype))
        return {n: v for n, v in other_vals.items()
                if getattr(v, "dtype", cdt) != cdt
                and not getattr(v, "__quantized_tensor__", False)
                and jnp.issubdtype(v.dtype, jnp.floating)}

    def fit(self, feeds_iter, epochs: int = 1, listeners: Optional[List] = None
            ) -> "History":
        """Minibatch training. feeds_iter: iterable of feed dicts (or a single
        dict). Returns a History (loss curve + per-epoch averages — nd4j
        ``History``†). ``listeners`` (or ones attached via set_listeners)
        receive the same iteration_done/on_epoch_end callbacks as the nn
        engines; ``self`` quacks enough like a Model for Score/Collect/
        Checkpoint listeners (score(), iteration, epoch, save())."""
        if self.loss_name is None or self.updater is None:
            raise ValueError("set_loss(...) and set_updater(...) first")
        from ..nn.caches import _TimedDispatch
        from ..runtime import faults as _faults
        # the train.phase.* spans of the engines' fit loops (nn/caches.py,
        # "phase tracing"): one call_s, and per feed stage_s, prepare_s,
        # step_s and readback_s (+ listeners_s where one is attached)
        span_labels = self._phase_labels()
        with _tel.span("train.phase.call_s", span_labels,
                       entry="SameDiff.fit"):
            feeds_list = [feeds_iter] if isinstance(feeds_iter, dict) \
                else list(feeds_iter)
            with _tel.span("train.phase.prepare_s", span_labels):
                step = self._fit_step_cached()
                train_vals = {n: self._values[n]
                              for n, v in self._vars.items()
                              if v.kind == VARIABLE}
                other_vals = {n: v for n, v in self._values.items()
                              if n not in train_vals}
                # ONE launch a call (_fit_prepare_cached): the fused
                # master-cast carry's compute copies (ISSUE 16: built once
                # here, the fused updater re-emits them every step
                # on-device), the constants'/frozen values' cast to the
                # compute dtype (ISSUE 14 satellite: once a call, not once
                # a step; self._values keeps the f32 originals) and a fresh
                # optimizer state
                prepare = self._fit_prepare_cached()
                castable = self._castable(other_vals)
                copies, cast, opt_state = prepare(train_vals, castable)
                _tel.record_dispatch("samediff.fit_prepare", prepare,
                                     (train_vals, castable))
                carry = train_vals if copies is None else (train_vals, copies)
                other_vals.update(cast)
                _FIT_PREPARE.inc(decision="compiled")
            cbs = list(self._listeners) + list(listeners or [])
            # a listener reads score() and the published weights of ITS
            # step, so with one attached each loss is read before the next
            # launch; with none, nobody can tell, and the read of step k
            # waits behind the launch of step k+1: the device goes from
            # step to step without the host in between
            _FIT_READBACK.inc(decision="per_step" if cbs else "deferred")
            history = History()

            def read(loss):
                with _tel.span("train.phase.readback_s", span_labels):
                    loss = float(loss)
                history.losses.append(loss)
                self._score = loss

            i = self.iteration
            pending = None  # the newest step's loss, where reads are deferred
            for _ in range(epochs):
                for feeds in feeds_list:
                    with _tel.span("train.phase.stage_s", span_labels):
                        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
                    with _tel.span("train.phase.prepare_s", span_labels):
                        if _faults.enabled():
                            _faults.trip("train.step")  # crash/preemption site
                            # float check FIRST: all-int feeds must not
                            # consume the injection's fire budget without
                            # poisoning anything
                            if any(jnp.issubdtype(v.dtype, jnp.floating)
                                   for v in feeds.values()) and \
                                    _faults.trip("train.nonfinite") \
                                    is not None:
                                feeds = {
                                    k: jnp.full_like(v, jnp.nan)
                                    if jnp.issubdtype(v.dtype, jnp.floating)
                                    else v for k, v in feeds.items()}
                        # a host scalar of jnp.asarray(i, int32)'s aval: no
                        # primitive is dispatched for it
                        step_i = np.int32(i)
                        sentinel = self._ensure_sentinel()
                        args = (carry, opt_state, other_vals, step_i, feeds,
                                sentinel)
                    with _TimedDispatch(span_labels, i):
                        carry, opt_state, self._sentinel, loss = step(*args)
                    _tel.record_dispatch("samediff.fit_step", step, args)
                    del args
                    train_vals = self._carry_masters(carry)
                    i += 1
                    self.iteration = i
                    if cbs:
                        read(loss)
                        with _tel.span("train.phase.listeners_s",
                                       span_labels):
                            # listeners may save/inspect: publish updated
                            # weights
                            self._values.update(train_vals)
                            for cb in cbs:
                                cb.iteration_done(self, i, self.epoch)
                    else:
                        if pending is not None:
                            read(pending)
                        pending = loss
                self.epoch += 1
                if cbs:
                    with _tel.span("train.phase.listeners_s", span_labels):
                        self._values.update(train_vals)
                        for cb in cbs:
                            cb.on_epoch_end(self)
            if pending is not None:
                read(pending)
            n = len(feeds_list)
            history.epoch_losses = [
                sum(history.losses[e * n:(e + 1) * n]) / max(1, n)
                for e in range(epochs)]
            self._values.update(train_vals)
            # no cache clear: sessions/steps take values as ARGUMENTS, so
            # the updated weights flow through; only graph mutation (call())
            # clears
            return history

    def evaluate(self, data_iter, output_name: str,
                 evaluation=None):
        """nd4j ``SameDiff.evaluate`` equivalent: run ``output_name`` over
        an iterable of ``(feeds_dict, labels_array)`` pairs and accumulate
        a classification Evaluation (one-hot or index labels)."""
        from ..eval.evaluation import Evaluation
        ev = evaluation or Evaluation()
        for feeds, labels in data_iter:
            out = self.output(feeds, [output_name])[output_name]
            labels = np.asarray(labels)
            if labels.ndim == out.ndim - 1:  # index labels -> one-hot
                labels = np.eye(out.shape[-1],
                                dtype=np.float32)[labels.astype(int)]
            ev.eval(labels, out)
        return ev

    # ---------------------------------------------------- memory accounting
    def memory_report(self, feeds: Dict[str, Any]) -> dict:
        """Compiled-HBM accounting of the fit step for one example feed
        dict (arrays OR ``jax.ShapeDtypeStruct``s — only shapes/dtypes are
        read): AOT lower+compile of the REAL compiled step (nothing
        executes, nothing allocates) exposing XLA ``memory_analysis()``
        temp/argument/output bytes, the forward→backward
        ``activation_bytes`` the workspace_mode remat shrinks, and live
        device ``memory_stats()``. Engine-parity twin of
        ``MultiLayerNetwork.memory_report`` (``nn/memory.py``); fields
        degrade to None on PJRT builds without the API."""
        if self.loss_name is None or self.updater is None:
            raise ValueError("set_loss(...) and set_updater(...) first")
        from ..nn import memory as _memory
        step = self._fit_step_cached()
        train_names = [n for n, v in self._vars.items() if v.kind == VARIABLE]
        tv = {n: self._values[n] for n in train_names}
        # mirror fit()'s cast hoist so the lowered program IS the one the
        # fit loop runs (pre-cast other_vals avals)
        ov = self._cast_other_vals(
            {n: v for n, v in self._values.items() if n not in tv})
        tv_avals = jax.eval_shape(lambda: tv)
        # the step's first arg is the fused (masters, copies) carry when
        # the fused updater is active — lower the REAL signature
        carry_avals = jax.eval_shape(lambda: self._fit_carry(tv))
        ov_avals = jax.eval_shape(lambda: ov)
        opt_avals = jax.eval_shape(lambda: self.updater.init_state(tv))
        feeds_avals = {
            k: (v if isinstance(v, jax.ShapeDtypeStruct) else
                jax.ShapeDtypeStruct(np.asarray(v).shape,
                                     np.asarray(v).dtype))
            for k, v in feeds.items()}
        batch = next((int(a.shape[0]) for a in feeds_avals.values()
                      if len(a.shape)), None)
        report = {
            "workspace_mode": str(getattr(self, "workspace_mode", "none")),
            "batch_size": batch,
            "temp_bytes": None, "argument_bytes": None, "output_bytes": None,
            "alias_bytes": None, "generated_code_bytes": None,
            "peak_bytes": None,
            "residual_bytes": None, "activation_bytes": None,
            "residual_count": None,
            "device": _memory.device_memory_stats(),
        }
        from ..runtime import sentinel as _sent
        # sentinel counters included: accounts the REAL step fit() runs;
        # the accounting compile is attributed like every other probe
        _tel.record_compile("samediff.fit_step", "probe", batch=batch)
        compiled = step.lower(carry_avals, opt_avals, ov_avals,
                              jax.ShapeDtypeStruct((), jnp.int32),
                              feeds_avals, _sent.counter_avals()).compile()
        cm = _memory.compiled_memory(compiled)
        if cm:
            report.update(cm)
        rb = _memory.residual_bytes(self._fit_loss_fn(), tv_avals,
                                    ov_avals, feeds_avals)
        if rb:
            report.update(rb)
        return report

    # ------------------------------------------------------------ accessors
    def get_value(self, name: str) -> np.ndarray:
        return np.asarray(self._values[name])

    def set_value(self, name: str, value) -> None:
        if self._vars[name].kind not in (VARIABLE, CONSTANT):
            raise ValueError(f"{name} has no stored value")
        self._values[name] = jnp.asarray(value)
        self._fn_cache.clear()

    def variables(self) -> List[str]:
        return [n for n, v in self._vars.items() if v.kind == VARIABLE]

    # ------------------------------------------------------------ serde
    def to_json(self) -> str:
        return json.dumps({
            "format_version": 2,
            "model_class": "SameDiff",
            "variables": [{"name": v.name, "kind": v.kind,
                           "shape": list(v.shape) if v.shape else None}
                          for v in self._vars.values()],
            "ops": [_op_to_dict(r) for r in self._ops],
            "loss": self.loss_name,
            "updater": self.updater.to_dict() if self.updater else None,
            "training_config": self.train_config or None,
            "workspace_mode": self.workspace_mode,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "SameDiff":
        from ..nn import updaters as _upd
        d = json.loads(s)
        sd = SameDiff()
        for vd in d["variables"]:
            if vd["name"] in sd._vars:
                continue
            sd._register(vd["name"], vd["kind"],
                         tuple(vd["shape"]) if vd.get("shape") else None)
        for od in d["ops"]:
            sd._ops.append(_op_from_dict(od))
        sd.loss_name = d.get("loss")
        if d.get("updater"):
            sd.updater = _upd.Updater.from_dict(d["updater"])
        sd.train_config = d.get("training_config") or {}
        sd.workspace_mode = d.get("workspace_mode", "none")
        return sd

    def save(self, path: str) -> None:
        """graph.json + values.npz in a zip (the .fb-equivalent artifact).

        Values are stored under positional npz keys with a JSON name table:
        the shared tree serializer treats ``/`` as a nesting separator, but
        SameDiff names are FLAT and TF-imported graphs are full of slashes
        (``bert/encoder/...``)."""
        from ..utils.serializer import _tree_to_npz_bytes
        names = list(self._values.keys())
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("graph.json", self.to_json())
            zf.writestr("value_names.json", json.dumps(names))
            zf.writestr("values.npz", _tree_to_npz_bytes(
                {f"v{i}": self._values[n] for i, n in enumerate(names)}))

    @staticmethod
    def load(path: str) -> "SameDiff":
        from ..utils.serializer import _npz_bytes_to_tree
        with zipfile.ZipFile(path, "r") as zf:
            sd = SameDiff.from_json(zf.read("graph.json").decode())
            tree = _npz_bytes_to_tree(zf.read("values.npz"))
            if "value_names.json" in zf.namelist():
                names = json.loads(zf.read("value_names.json").decode())
                sd._values = {n: tree[f"v{i}"] for i, n in enumerate(names)}
            else:  # round-2 artifact: flat keys, no slashes in names
                sd._values = dict(tree)
        return sd


class History:
    """Training history (nd4j ``History``† — loss curve plus per-epoch
    aggregates; evaluations attach via listeners). Iterable/indexable as the
    per-iteration loss list for round-2 call-site compatibility."""

    def __init__(self):
        self.losses: List[float] = []        # one per iteration
        self.epoch_losses: List[float] = []  # mean loss per epoch

    def loss_curve(self) -> List[float]:
        return list(self.losses)

    def __len__(self):
        return len(self.losses)

    def __iter__(self):
        return iter(self.losses)

    def __getitem__(self, i):
        return self.losses[i]


def _attr_out(v):
    if isinstance(v, tuple):
        return list(v)
    if isinstance(v, _Subgraph):
        return {"__subgraph__": v.to_dict()}
    return v


def _attr_in(v):
    if isinstance(v, list):
        return tuple(v)
    return v


def _op_to_dict(r: _OpRecord) -> Dict[str, Any]:
    return {"op": r.op, "inputs": r.inputs, "outputs": list(r.outputs),
            "attrs": {k: _attr_out(v) for k, v in r.attrs.items()}}


def _op_from_dict(od: Dict[str, Any]) -> _OpRecord:
    attrs = {}
    for k, v in dict(od.get("attrs", {})).items():
        if isinstance(v, dict) and "__subgraph__" in v:
            v = _Subgraph.from_dict(v["__subgraph__"])
        attrs[k] = v
    outs = od["outputs"] if "outputs" in od else od["output"]  # v1 compat
    return _OpRecord(od["op"], list(od["inputs"]), outs, attrs)
