"""ComputationGraph: the DAG network engine.

TPU-native equivalent of DL4J's ``ComputationGraph`` +
``ComputationGraphConfiguration.GraphBuilder`` (reference:
``deeplearning4j-nn .../nn/graph/ComputationGraph.java`` and
``.../nn/conf/ComputationGraphConfiguration.java``† per SURVEY.md §2.4/§3.2;
reference mount was empty, citations upstream-relative, unverified).

Architecture (the §3.2 "TPU translation"): DL4J walks ``GraphVertex[]`` in
topological order calling doForward per vertex per iteration, then reverse
topo with hand-written epsilon accumulation. Here the SAME topo walk is a
pure function traced ONCE into a single fused XLA program
(forward + backward + updater, buffers donated); fan-out gradient
accumulation is the chain rule under ``jax.grad``, multi-output losses sum.

Usage mirrors DL4J::

    conf = (NeuralNetConfiguration.builder()
            .updater(Adam(1e-3))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.convolutional(3, 32, 32))
            .add_layer("conv1", ConvolutionLayer(...), "in")
            .add_vertex("res", ElementWiseVertex(op="add"), "conv1", "in")
            .add_layer("out", OutputLayer(...), "res")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    net.fit(multi_dataset_iterator, epochs=2)

Param/state layout: pytree keyed by VERTEX NAME (stable across JSON);
flat-param adapter orders by topological order then DL4J param-name order.
``fit``, ``fit_on_device`` and the rest of the host side are the two
engines' shared ``nn/engine.py``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dt
from ..data.dataset import (DataSet, DataSetIterator, MultiDataSet,
                            MultiDataSetIterator, NumpyMultiDataSetIterator)
from ..ops import losses as _loss
from ..runtime import telemetry as _tel
from . import constraints as _constraints
from . import engine as _engine
from . import microbatch as _micro
from . import updaters as _upd
from .layers.base import Layer
from .vertices import GraphVertex, LayerVertex


_LOOP_PASSES = _tel.counter(
    "loop.passes", "passes walked of a repeated run of vertices, by graph "
    "and run: the run's times a launched training step")


@dataclasses.dataclass(frozen=True)
class RepeatedRun:
    """A stretch of vertices the walk applies ``times`` times with one set
    of weights (resolved from ``ComputationGraphConfiguration.repeats``):
    ``carry`` names the one activation the stretch reads from outside, and
    on every pass after the first that name stands for the previous pass's
    ``output`` (the stretch's last vertex). ``name`` is what a later vertex
    reads to get every pass's output stacked ``[times, batch, ...]``."""
    name: str
    times: int
    carry: str
    output: str
    vertices: Tuple[str, ...]


def _scan_passes(body, x0, times: int):
    """``body`` applied ``times`` times from ``x0`` as one ``lax.scan``:
    -> (the last pass's output, every pass's output stacked)."""
    return jax.lax.scan(body, x0, None, length=times)


class ComputationGraphConfiguration:
    """Immutable DAG description (the thing that serializes)."""

    def __init__(self, *, inputs: List[str], outputs: List[str],
                 vertices: List[Tuple[str, GraphVertex, List[str]]],
                 input_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                 seed: int = 1234, dtype: str = "FLOAT", updater: Any = None,
                 l1: float = 0.0, l2: float = 0.0,
                 gradient_clip_value: Optional[float] = None,
                 gradient_clip_l2: Optional[float] = None,
                 gradient_normalization: Optional[str] = None,
                 gradient_normalization_threshold: float = 1.0,
                 tbptt_length: Optional[int] = None,
                 constraints: Any = None,
                 workspace_mode: str = "none",
                 repeats: Optional[Sequence[Dict[str, Any]]] = None):
        self.inputs = list(inputs)
        self.outputs = list(outputs)
        self.vertices = list(vertices)  # [(name, vertex, [input names])]
        self.input_shapes = dict(input_shapes or {})
        self.seed = seed
        self.dtype = dtype
        self.updater = updater
        self.l1 = l1
        self.l2 = l2
        self.gradient_clip_value = gradient_clip_value
        self.gradient_clip_l2 = gradient_clip_l2
        from . import gradnorm as _gn
        _gn.validate(gradient_normalization)
        self.gradient_normalization = gradient_normalization
        self.gradient_normalization_threshold = gradient_normalization_threshold
        self.tbptt_length = tbptt_length
        self.constraints = constraints
        from . import memory as _memory
        _memory.resolve_policy(workspace_mode)  # validate at build time
        self.workspace_mode = str(workspace_mode).strip().lower()
        # runs of vertices applied several times with one set of weights:
        # [{"name", "first", "last", "times"}], see GraphBuilder.repeat
        self.repeats = [
            {"name": str(r["name"]), "first": str(r["first"]),
             "last": str(r["last"]), "times": int(r["times"])}
            for r in (repeats or [])]
        self._validate()
        self._runs = self._resolve_repeats()

    def _validate(self):
        names = set(self.inputs)
        stacked = {r["name"] for r in self.repeats}
        for name, v, ins in self.vertices:
            if name in names:
                raise ValueError(f"duplicate vertex name {name!r}")
            for i in ins:
                if i not in names and i not in stacked and \
                        i not in {n for n, _, _ in self.vertices}:
                    raise ValueError(
                        f"vertex {name!r} input {i!r} is not a network input "
                        "or a declared vertex")
            names.add(name)
        for o in self.outputs:
            if o not in names:
                raise ValueError(f"output {o!r} is not a declared vertex")
        for k, r in enumerate(self.repeats):
            name = r["name"]
            if name in names or name in {x["name"]
                                         for x in self.repeats[:k]}:
                raise ValueError(f"repeated run {name!r}: the name is taken")
            if r["times"] < 1:
                raise ValueError(
                    f"repeated run {name!r}: times={r['times']}")
            for end in (r["first"], r["last"]):
                if end not in names or end in self.inputs:
                    raise ValueError(f"repeated run {name!r}: {end!r} is "
                                     "not a declared vertex")

    def _resolve_repeats(self) -> List["RepeatedRun"]:
        """Check every repeated run against the graph and -> the runs in
        the order the walk meets them. What a run cannot be is refused here,
        at build time: see :meth:`GraphBuilder.repeat`."""
        if not self.repeats:
            return []
        declared = [n for n, _, _ in self.vertices]
        reads = {n: list(ins) for n, _, ins in self.vertices}
        topo = self.topo_order()
        taken: Dict[str, str] = {}
        runs = []
        for r in self.repeats:
            name, times = r["name"], r["times"]
            lo, hi = declared.index(r["first"]), declared.index(r["last"])
            if lo > hi:
                raise ValueError(f"repeated run {name!r}: {r['last']!r} is "
                                 f"declared before {r['first']!r}")
            inside = declared[lo:hi + 1]
            for n in inside:
                if n in taken:
                    raise ValueError(f"vertex {n!r} is in the repeated runs "
                                     f"{taken[n]!r} and {name!r}")
                taken[n] = name
            at = sorted(topo.index(n) for n in inside)
            if at != list(range(at[0], at[0] + len(inside))):
                raise ValueError(
                    f"repeated run {name!r}: its vertices are not one "
                    "stretch of the topological order")
            members = set(inside)
            carried = {i for n in inside for i in reads[n]
                       if i not in members}
            if len(carried) != 1:
                raise ValueError(
                    f"repeated run {name!r} reads {sorted(carried)} from "
                    "outside: a run has one input, the activation it "
                    "carries from pass to pass")
            for n, ins in reads.items():
                if n in members:
                    continue
                for i in ins:
                    if i in members and i != r["last"]:
                        raise ValueError(
                            f"vertex {n!r} reads {i!r} inside the repeated "
                            f"run {name!r}: outside it only the last pass "
                            f"({r['last']!r}) and the stacked passes "
                            f"({name!r}) can be read")
            for o in self.outputs:
                if o in members and o != r["last"]:
                    raise ValueError(
                        f"network output {o!r} lies inside the repeated "
                        f"run {name!r}")
            runs.append(RepeatedRun(
                name=name, times=times, carry=carried.pop(),
                output=r["last"],
                vertices=tuple(n for n in topo if n in members)))
        runs.sort(key=lambda x: topo.index(x.vertices[0]))
        if self.input_shapes and set(self.input_shapes) >= set(self.inputs):
            self._check_repeated_vertices(runs, topo)
        return runs

    def _check_repeated_vertices(self, runs, topo):
        """With the input types known, initialise every vertex on avals
        (nothing is allocated) and refuse a run whose output is not shaped
        as its input, or that holds a vertex the walk cannot repeat: one
        with layer state (a pass would update it) or one that draws random
        numbers (the passes would share a key)."""
        by_vertex = {n: r for r in runs for n in r.vertices}
        vmap = {n: (v, ins) for n, v, ins in self.vertices}
        found: Dict[str, Any] = {}

        def walk(key):
            shapes = {k: tuple(v) for k, v in self.input_shapes.items()}
            for r in runs:
                shapes[r.name] = None
            for n in topo:
                v, ins = vmap[n]
                _, state, out = v.initialize(
                    key, [shapes[i] for i in ins], jnp.float32)
                shapes[n] = tuple(out)
                found[n] = bool(state)
                r = by_vertex.get(n)
                if r is not None and n == r.output:
                    shapes[r.name] = (r.times,) + shapes[n]
            found["shapes"] = shapes
            return 0

        jax.eval_shape(walk, jax.random.PRNGKey(0))
        shapes = found["shapes"]
        for r in runs:
            for n in r.vertices:
                if found[n]:
                    raise ValueError(
                        f"vertex {n!r} keeps layer state and lies inside "
                        f"the repeated run {r.name!r}: every pass would "
                        "update it, and a run walks stateless vertices only")
                if vmap[n][0].stochastic:
                    raise ValueError(
                        f"vertex {n!r} draws random numbers and lies inside "
                        f"the repeated run {r.name!r}: the passes would "
                        "share its key, and a run walks deterministic "
                        "vertices only")
            if shapes[r.output] != shapes[r.carry]:
                raise ValueError(
                    f"repeated run {r.name!r}: its output {r.output!r} is "
                    f"shaped {shapes[r.output]}, its input {r.carry!r} "
                    f"{shapes[r.carry]}; a pass must hand on what it took")

    def topo_order(self) -> List[str]:
        """Kahn topological order over vertex names (inputs excluded). The
        stacked passes of a repeated run exist once its last vertex has
        run."""
        done_by = {r["name"]: r["last"] for r in self.repeats}
        ins = {name: set(done_by.get(i, i) for i in inp
                         if i not in self.inputs)
               for name, _, inp in self.vertices}
        dependents: Dict[str, List[str]] = {}
        for name, _, inp in self.vertices:
            # dedupe: a vertex may consume an input twice
            for i in set(done_by.get(i, i) for i in inp):
                dependents.setdefault(i, []).append(name)
        ready = [n for n, deps in ins.items() if not deps]
        order: List[str] = []
        while ready:
            n = ready.pop(0)
            order.append(n)
            for d in dependents.get(n, []):
                ins[d].discard(n)
                if not ins[d]:
                    ready.append(d)
        if len(order) != len(self.vertices):
            cyc = sorted(set(ins) - set(order))
            raise ValueError(f"graph has a cycle involving {cyc}")
        return order

    # ------------------------------------------------------------------ serde
    def to_json(self) -> str:
        return json.dumps({
            "format_version": 1,
            "model_class": "ComputationGraph",
            "seed": self.seed,
            "dtype": self.dtype,
            "updater": self.updater.to_dict() if self.updater else None,
            "l1": self.l1, "l2": self.l2,
            "gradient_clip_value": self.gradient_clip_value,
            "gradient_clip_l2": self.gradient_clip_l2,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold":
                self.gradient_normalization_threshold,
            "tbptt_length": self.tbptt_length,
            "constraints": _constraints.encode_constraints(self.constraints),
            "workspace_mode": self.workspace_mode,
            "repeats": self.repeats,
            "network_inputs": self.inputs,
            "network_outputs": self.outputs,
            "input_shapes": {k: list(v) for k, v in self.input_shapes.items()},
            "vertices": [{"name": n, "inputs": list(i), "vertex": v.to_dict()}
                         for n, v, i in self.vertices],
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)
        return ComputationGraphConfiguration(
            inputs=d["network_inputs"],
            outputs=d["network_outputs"],
            vertices=[(vd["name"], GraphVertex.from_dict(vd["vertex"]),
                       list(vd["inputs"])) for vd in d["vertices"]],
            input_shapes={k: tuple(v) for k, v in d.get("input_shapes", {}).items()},
            seed=d.get("seed", 1234), dtype=d.get("dtype", "FLOAT"),
            updater=_upd.Updater.from_dict(d["updater"]) if d.get("updater") else None,
            l1=d.get("l1", 0.0), l2=d.get("l2", 0.0),
            gradient_clip_value=d.get("gradient_clip_value"),
            gradient_clip_l2=d.get("gradient_clip_l2"),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get(
                "gradient_normalization_threshold", 1.0),
            tbptt_length=d.get("tbptt_length"),
            constraints=_constraints.decode_constraints(d.get("constraints")),
            workspace_mode=d.get("workspace_mode", "none"),
            repeats=d.get("repeats"))


class GraphBuilder:
    """DL4J ``NeuralNetConfiguration.Builder().graphBuilder()`` equivalent."""

    def __init__(self, base=None):
        # base: a NeuralNetConfiguration builder carrying seed/updater/etc.
        self._base = base
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._vertices: List[Tuple[str, GraphVertex, List[str]]] = []
        self._input_shapes: Dict[str, Tuple[int, ...]] = {}
        self._repeats: List[Dict[str, Any]] = []

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_input_types(self, *shapes) -> "GraphBuilder":
        """Shapes (batch-free, InputType.* values) aligned with add_inputs order."""
        if len(shapes) != len(self._inputs):
            raise ValueError(f"{len(self._inputs)} inputs declared, "
                             f"{len(shapes)} input types given")
        for name, s in zip(self._inputs, shapes):
            self._input_shapes[name] = tuple(s)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        self._vertices.append((name, LayerVertex(layer=layer), list(inputs)))
        return self

    # DL4J spelling
    def layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        return self.add_layer(name, layer, *inputs)

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._vertices.append((name, vertex, list(inputs)))
        return self

    def repeat(self, name: str, first: str, last: str,
               times: int) -> "GraphBuilder":
        """Mark the vertices declared from ``first`` to ``last`` as one run
        that the walk applies ``times`` times with ONE set of weights. The
        run has one input, the single activation its vertices read from
        outside; on every later pass that name stands for the previous
        pass's ``last``. Outside the run ``last`` reads the final pass and
        ``name`` reads every pass's output stacked ``[times, batch, ...]``;
        no other vertex of the run can be read from outside. Parameters,
        updater state, ``num_params()`` and a checkpoint hold each weight
        once, and its gradient is the sum over the passes.
        ``workspace_mode="every_<k>"`` segments the run's vertices inside a
        pass, so the backward pass keeps the carried activation at every
        segment boundary of every pass and recomputes the rest.

        Refused at ``build()``: a pass whose output is not shaped as its
        input, a vertex inside that keeps layer state (BatchNorm's
        statistics, an expert layer's counts: every pass would update it)
        or draws random numbers (the passes would share its key), a second
        outside input, and a reader of the run's inside."""
        self._repeats.append({"name": name, "first": first, "last": last,
                              "times": int(times)})
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def build(self) -> ComputationGraphConfiguration:
        b = self._base
        vertices = self._vertices
        if b and b._tbptt:
            from .config import stamp_tbptt
            vertices = [
                (n, LayerVertex(layer=stamp_tbptt(v.layer, b._tbptt))
                 if isinstance(v, LayerVertex) else v, ins)
                for n, v, ins in vertices]
        return ComputationGraphConfiguration(
            inputs=self._inputs, outputs=self._outputs,
            vertices=vertices, input_shapes=self._input_shapes,
            seed=b._seed if b else 1234,
            dtype=b._dtype if b else "FLOAT",
            updater=b._updater if b else None,
            l1=b._l1 if b else 0.0, l2=b._l2 if b else 0.0,
            gradient_clip_value=b._clip_value if b else None,
            gradient_clip_l2=b._clip_l2 if b else None,
            gradient_normalization=b._grad_norm if b else None,
            gradient_normalization_threshold=(
                b._grad_norm_threshold if b else 1.0),
            tbptt_length=b._tbptt if b else None,
            constraints=(b._constraints or None) if b else None,
            workspace_mode=b._workspace_mode if b else "none",
            repeats=self._repeats)


class ComputationGraph(_engine.NetworkEngine):
    """DAG network engine (DL4J ``ComputationGraph``)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf)
        self._vertex_map: Dict[str, Tuple[GraphVertex, List[str]]] = {
            n: (v, ins) for n, v, ins in conf.vertices}
        self._topo = conf.topo_order()
        # the repeated run every vertex of one lies in: the walk enters a
        # run at its first vertex and skips the rest
        self._run_of: Dict[str, RepeatedRun] = {
            n: r for r in conf._runs for n in r.vertices}
        if conf._runs:
            import weakref
            weakref.finalize(self, _tel.registry.discard_cells,
                             graph=self.telemetry_label)
        self._layer_counts_seen: Dict[str, Any] = {}
        self._out_layers: Dict[str, Any] = {}
        for o in conf.outputs:
            v = self._vertex_map[o][0]
            lyr = v.layer if isinstance(v, LayerVertex) else None
            # duck-typed loss heads (OutputLayer, LossLayer, CenterLoss,
            # Yolo2Output, custom) — same probe as the sequential engine
            if lyr is not None and _engine._is_loss_head(lyr):
                self._out_layers[o] = lyr

    # ------------------------------------------------------------------ init
    def init(self) -> "ComputationGraph":
        if set(self.conf.input_shapes) != set(self.conf.inputs):
            missing = set(self.conf.inputs) - set(self.conf.input_shapes)
            raise ValueError(f"set_input_types missing for inputs {sorted(missing)}")
        # mixed precision: 16-bit net dtypes keep fp32 master params
        # (cast to the compute dtype inside _forward)
        dtype = _dt.param_dtype(self.conf.dtype)
        shapes: Dict[str, Tuple[int, ...]] = {
            k: tuple(v) for k, v in self.conf.input_shapes.items()}
        key = jax.random.PRNGKey(self.conf.seed)
        params, state = {}, {}
        for name in self._topo:
            v, ins = self._vertex_map[name]
            key, sub = jax.random.split(key)
            p, s, out_shape = v.initialize(sub, [shapes[i] for i in ins], dtype)
            if p:
                params[name] = p
            if s:
                state[name] = s
            shapes[name] = tuple(out_shape)
            run = self._run_of.get(name)
            if run is not None and name == run.output:
                # every pass's output, stacked before the batch axis
                shapes[run.name] = (run.times,) + shapes[name]
        self.params = params
        self.state = state
        self._layer_counts_seen = {}
        self._shapes = shapes
        self.updater_state = self.conf.updater.init_state(params) \
            if self.conf.updater else {}
        self._invalidate_compiled(cause="init")
        return self

    def summary(self) -> str:
        lines = [f"{'vertex':<24}{'type':<22}{'inputs':<30}{'out shape':<18}params"]
        for name in self._topo:
            v, ins = self._vertex_map[name]
            kind = (f"layer[{v.layer.kind}]" if isinstance(v, LayerVertex)
                    else v.kind)
            n = sum(int(np.prod(a.shape))
                    for a in jax.tree.leaves(self.params.get(name, {})))
            shape = getattr(self, "_shapes", {}).get(name, "?")
            run = self._run_of.get(name)
            lines.append(f"{name:<24}{kind:<22}{','.join(ins):<30}"
                         f"{str(shape):<18}{n}"
                         + (f"  (x{run.times}, {run.name})" if run else ""))
        for run in self.conf._runs:
            lines.append(
                f"repeated run {run.name!r}: {run.vertices[0]} .. "
                f"{run.output} walked {run.times} times with one set of "
                f"weights, carrying {run.carry}; each weight is counted "
                "once")
        lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)

    # --------------------------------------------------------------- forward
    def _forward(self, params, inputs: Dict[str, jax.Array], state, *,
                 train, rng, masks: Optional[Dict[str, Any]] = None,
                 remat_policy=None, fold_epilogues=True):
        """Pure topo walk. Returns ({vertex: activation}, new_state,
        {vertex: mask}) for output vertices.

        ``remat_policy`` (a resolved ``nn.memory.RematPolicy``) wraps the
        walk in per-segment ``jax.checkpoint`` — only the train-step loss
        path passes it (the workspace_mode knob); on that path the
        returned ``acts``/``masks`` dicts hold the network OUTPUT vertices
        only (the loss consumes nothing else)."""
        dt = _dt.resolve(self.conf.dtype)
        if jnp.issubdtype(dt, jnp.floating):
            inputs = {k: (jnp.asarray(v, dt)
                          if jnp.issubdtype(jnp.asarray(v).dtype,
                                            jnp.floating)
                          and jnp.asarray(v).dtype != dt else v)
                      for k, v in inputs.items()}  # cast to net dtype (DL4J)
        if _dt.is_mixed(self.conf.dtype):
            # fp32 masters -> compute-dtype working copy; grads flow back
            # through the cast and land in fp32
            params = _dt.cast_floating(params, dt)
        if remat_policy is not None and remat_policy.remat:
            return self._forward_remat(params, inputs, state, train=train,
                                       rng=rng, masks=masks,
                                       policy=remat_policy)
        acts: Dict[str, jax.Array] = dict(inputs)
        mks: Dict[str, Any] = dict(masks or {})
        new_state = dict(state)
        fold, skip = self._epilogue_fold_plan() if fold_epilogues \
            else ({}, frozenset())
        for name in self._topo:
            run = self._run_of.get(name)
            if run is not None:
                if name == run.vertices[0]:
                    self._walk_run(run, params, acts, mks, train=train)
                continue
            v, ins = self._vertex_map[name]
            if rng is not None and v.stochastic:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            if name in skip:  # folded act vertex: value passes through
                acts[name] = acts[ins[0]]
                mks[name] = mks.get(ins[0])
                continue
            kw = {"fold_act": fold[name]} if name in fold else {}
            with jax.named_scope(name):
                y, s_new, m = v.apply(
                    params.get(name, {}), [acts[i] for i in ins],
                    state.get(name, {}), train=train, rng=sub,
                    masks=[mks.get(i) for i in ins], **kw)
            acts[name] = y
            mks[name] = m
            if s_new:
                new_state[name] = s_new
        return acts, new_state, mks

    def _walk_run(self, run: RepeatedRun, params, acts, mks, *, train,
                  policy=None):
        """Apply a repeated run: ONE traced pass body under ``lax.scan``, so
        the compiled program holds the run's vertices once however many
        passes it makes. The body reads ``acts[run.carry]`` on the first
        pass and its own output after that; afterwards ``acts[run.output]``
        is the last pass and ``acts[run.name]`` every pass's output stacked
        ``[times, batch, ...]``. The parameters are the scan's constants:
        each weight's cotangent is summed over the passes in the dtype the
        walk holds it in (the compute dtype under mixed precision). The
        run's vertices keep no state and draw no random numbers (refused at
        build), and the carried activation's mask rides through unchanged.
        With a recomputing ``policy`` the body is cut into checkpointed
        segments like the walk outside it, so what the backward pass keeps
        of a pass is the live activations at its segment boundaries and what
        the segments keep by name, stacked over the passes by the scan;
        without one the same walk goes vertex by vertex and checkpoints
        nothing."""
        from . import memory as _memory
        policy = policy or _memory.resolve_policy("none")
        mask = mks.get(run.carry)

        def body(x, _):
            with jax.named_scope("loop.pass"):
                a, _, _, _ = self._walk_segments(
                    run.vertices, {run.output}, params, {}, {run.carry: x},
                    {run.carry: mask}, None, policy, train, prevent_cse=False)
            return a[run.output], a[run.output]

        acts[run.output], acts[run.name] = _scan_passes(
            body, acts[run.carry], run.times)
        mks[run.output] = mks[run.name] = mask

    def _epilogue_fold_plan(self):
        """Static BN+activation fold plan over the vertex graph
        (ISSUE 16): a LayerVertex(BatchNormalization) whose output is
        consumed ONLY by a LayerVertex(ActivationLayer) with a kernel-
        foldable activation (and is not itself a network output — a
        residual branch reading the pre-activation BN output blocks the
        fold) gets the act folded into its ``bn_act`` epilogue; the act
        vertex becomes a value pass-through. The dispatcher's fallback is
        bit-identical, so the fold never changes numerics."""
        cached = getattr(self, "_epilogue_fold", None)
        if cached is not None:
            return cached
        from ..ops import fused_epilogues as _fe
        from .layers.conv import BatchNormalization
        from .layers.core import ActivationLayer
        consumers: Dict[str, list] = {}
        for name in self._topo:
            _, ins = self._vertex_map[name]
            for i in ins:
                consumers.setdefault(i, []).append(name)
        outputs = set(self.conf.outputs)
        fold, skip = {}, set()
        for name in self._topo:
            v, _ = self._vertex_map[name]
            if not (isinstance(v, LayerVertex)
                    and isinstance(v.layer, BatchNormalization)):
                continue
            if name in outputs or len(consumers.get(name, [])) != 1:
                continue
            nxt = consumers[name][0]
            nv, _ = self._vertex_map[nxt]
            if (isinstance(nv, LayerVertex)
                    and type(nv.layer) is ActivationLayer
                    and _fe.foldable_act(nv.layer.activation,
                                         getattr(nv.layer, "alpha", None))):
                fold[name] = nv.layer.activation
                skip.add(nxt)
        self._epilogue_fold = (fold, frozenset(skip))
        return self._epilogue_fold

    def _forward_remat(self, params, inputs, state, *, train, rng, masks,
                       policy):
        """The same topo walk, segmented into ``policy.every``-vertex
        chunks each wrapped in ``jax.checkpoint``. The activation dict is
        pruned to the LIVE set at every segment boundary (names still read
        by later vertices, or network outputs) — those boundary values are
        what XLA keeps, with what the policy lets a segment keep by rule or
        by name (``nn/memory.py``: an attention's output where it is
        narrow); everything else inside a segment is rematerialized in the
        backward pass. Skip connections spanning segments ride through
        as checkpoint pass-through args. The rng stream threads through
        with the exact split sequence of the plain walk (remat on/off is
        bit-equivalent, dropout included). A repeated run is a stretch of
        its own: the vertices before it, the run (segmented inside its
        pass body, ``_walk_run``) and the vertices after it are segmented
        one after the other. ``params``/``inputs`` arrive already cast."""
        # the stretches of the topological order between repeated runs
        stretches: List[Any] = []
        for name in self._topo:
            run = self._run_of.get(name)
            if run is not None:
                if name == run.vertices[0]:
                    stretches.append(run)
                continue
            if not stretches or isinstance(stretches[-1], RepeatedRun):
                stretches.append([])
            stretches[-1].append(name)
        # read_after[k]: names read by anything after stretch k, plus the
        # network outputs
        read_after = [set(self.conf.outputs)]
        for st in reversed(stretches):
            nxt = set(read_after[-1])
            if isinstance(st, RepeatedRun):
                nxt.add(st.carry)
            else:
                for n in st:
                    nxt.update(self._vertex_map[n][1])
            read_after.append(nxt)
        read_after.reverse()
        acts: Dict[str, jax.Array] = dict(inputs)
        mks: Dict[str, Any] = dict(masks or {})
        new_state = dict(state)
        for k, st in enumerate(stretches):
            if isinstance(st, RepeatedRun):
                self._walk_run(st, params, acts, mks, train=train,
                               policy=policy)
                keep = read_after[k + 1]
                acts = {n: a for n, a in acts.items() if n in keep}
                mks = {n: m for n, m in mks.items() if n in keep}
                continue
            acts, mks, ns, rng = self._walk_segments(
                st, read_after[k + 1], params, state, acts, mks, rng,
                policy, train)
            new_state.update(ns)
        return acts, new_state, mks

    def _walk_segments(self, names, needed_end, params, state, acts, mks,
                       rng, policy, train, prevent_cse=True):
        """Walk ``names`` in checkpointed segments of ``policy.every``;
        ``needed_end`` is what is read once they are done. ``prevent_cse``
        is False where the walk is the body of a repeated run's scan, whose
        segments need no barrier against merging with the forward pass
        (``nn/memory.py`` ``checkpoint``). -> (the live activations, their
        masks, the state the vertices wrote, rng)."""
        from . import memory as _memory
        bounds = _memory.segment_ranges(len(names), policy.every)
        # needed_after[j] = names read by any vertex in bounds[j:], plus
        # ``needed_end`` — ONE right-to-left suffix pass (quadratic
        # per-segment rescans would bite trace time on imported graphs)
        needed_after = [set(needed_end)]
        for s, e in reversed(bounds):
            nxt = set(needed_after[-1])
            for n in names[s:e]:
                nxt.update(self._vertex_map[n][1])
            needed_after.append(nxt)
        needed_after.reverse()
        new_state = {}
        for j, (s, e) in enumerate(bounds):
            seg_names = tuple(names[s:e])
            # live set after this segment: anything a later vertex reads,
            # plus what is needed at the end
            live_out = tuple(sorted(
                (set(acts) | set(seg_names)) & needed_after[j + 1]))

            def seg_fn(seg_params, seg_state, carry_acts, carry_mks, rng,
                       _names=seg_names, _out=live_out):
                a = dict(carry_acts)
                m = dict(carry_mks)
                ns = {}
                fold, skip = self._epilogue_fold_plan()
                for name in _names:
                    v, ins = self._vertex_map[name]
                    if rng is not None and v.stochastic:
                        rng, sub = jax.random.split(rng)
                    else:
                        sub = None
                    if name in skip:  # folded act vertex: pass-through
                        a[name] = a[ins[0]]
                        m[name] = m.get(ins[0])
                        continue
                    kw = {"fold_act": fold[name]} if name in fold else {}
                    with jax.named_scope(name):
                        y, s_new, mk = v.apply(
                            seg_params.get(name, {}), [a[i] for i in ins],
                            seg_state.get(name, {}), train=train, rng=sub,
                            masks=[m.get(i) for i in ins], **kw)
                    a[name] = y
                    m[name] = mk
                    if s_new:
                        ns[name] = s_new
                return ({n: a[n] for n in _out},
                        {n: m.get(n) for n in _out}, ns, rng)

            seg_params = {n: params[n] for n in seg_names if n in params}
            seg_state = {n: state[n] for n in seg_names if n in state}
            acts, mks, ns, rng = _memory.checkpoint(
                seg_fn, policy, prevent_cse=prevent_cse)(
                    seg_params, seg_state, acts, mks, rng)
            new_state.update(ns)
        return acts, mks, new_state, rng

    def _build_loss_fn(self):
        """The pure training loss ``(params, bn_state, key, xs, ys, fms,
        lms) -> (loss, new_bn_state)`` the train step differentiates —
        factored out so ``nn/memory.py`` can account its forward→backward
        residuals without building a step. Applies the conf's
        ``workspace_mode`` remat policy to the topo walk."""
        outputs = self.conf.outputs
        out_layers = self._out_layers
        if set(out_layers) != set(outputs):
            bad = sorted(set(outputs) - set(out_layers))
            raise ValueError(
                f"output vertices {bad} are not Output/Loss layers; fit() "
                "needs a loss head on every network output")
        from . import memory as _memory
        policy = _memory.resolve_policy(
            getattr(self.conf, "workspace_mode", None))

        def loss_fn(p, bn_state, key, xs, ys, fms, lms):
            # the scope names the forward's operations in a device trace;
            # its transpose shows as transpose(jvp(forward))
            with jax.named_scope("forward"):
                inputs = dict(zip(self.conf.inputs, xs))
                masks = {n: m for n, m in zip(self.conf.inputs, fms)
                         if m is not None}
                acts, new_bn, mks = self._forward(
                    p, inputs, bn_state, train=True, rng=key, masks=masks,
                    remat_policy=policy)
                total = 0.0
                for o, y, lm in zip(outputs, ys, lms):
                    layer = out_layers[o]
                    # intersect explicit label mask with the propagated mask
                    m = _loss.combine_masks(lm, mks.get(o))
                    if hasattr(layer, "update_centers"):
                        # CenterLossOutputLayer: pull the stashed features
                        # out of the aux state channel (must not persist),
                        # EMA-update centers outside the gradient
                        st = dict(new_bn[o])
                        feats = st.pop("__features__")
                        centers = bn_state[o]["centers"]
                        st["centers"] = jax.lax.stop_gradient(
                            layer.update_centers(
                                centers, jax.lax.stop_gradient(feats), y))
                        new_bn = {**new_bn, o: st}
                        total = total + layer.loss_value(
                            acts[o], y, mask=m,
                            weights=getattr(layer, "loss_weights", None),
                            features=feats,
                            centers=jax.lax.stop_gradient(centers))
                    else:
                        total = total + layer.loss_value(
                            acts[o], y, mask=m,
                            weights=getattr(layer, "loss_weights", None))
                return total + self._regularization(p), new_bn

        return loss_fn

    # ---------------------------------------- the shared host side's hooks
    def _keyed_layers(self):
        for name in self._topo:
            v = self._vertex_map[name][0]
            yield name, (v.layer if isinstance(v, LayerVertex) else None)

    def _iterator(self, data, labels):
        return _as_multi_iterator(data, labels)

    def _stage_batch(self, mds):
        return tuple(tuple(None if a is None else jnp.asarray(a)
                           for a in part)
                     for part in (mds.features, mds.labels,
                                  mds.features_masks, mds.labels_masks))

    def _host_arrays(self, features, labels):
        return _host_list(features), _host_list(labels)

    def _pack(self, arrays):
        return tuple(arrays)

    def _epoch_masks(self):
        return ((None,) * len(self.conf.inputs),
                (None,) * len(self.conf.outputs))

    _label_weight = staticmethod(_micro.multi_output_weight)

    def _scope_names(self):
        """The names under which the walks scope each vertex's forward."""
        return self._topo

    def _count_passes(self, steps: int):
        """``loop.passes``: what the launched steps walked of each repeated
        run, counted on the host from the launches."""
        for run in self.conf._runs:
            _LOOP_PASSES.inc(steps * run.times, graph=self.telemetry_label,
                             run=run.name)

    def _publish_layer_counters(self):
        """Layers that count on the device (a sparse-expert layer's tokens
        per expert) keep the counts in their state, which came back from the
        scanned call with the losses: hand each its state and the one
        published last, and it adds the growth to its telemetry counters."""
        seen = self._layer_counts_seen
        for name, (v, _) in self._vertex_map.items():
            publish = getattr(getattr(v, "layer", None), "publish_counters",
                              None)
            if publish is not None and name in self.state:
                now = jax.device_get(self.state[name])
                publish(name, now, seen.get(name))
                seen[name] = now

    # ------------------------------------------------------------- inference
    def feed_forward(self, *inputs, train: bool = False, rng=None):
        """All vertex activations for the given inputs (DL4J
        ``ComputationGraph.feedForward()``): {vertex_name: activation}.
        ``rng`` feeds stochastic layers when ``train=True`` (None =
        deterministic)."""
        if len(inputs) != len(self.conf.inputs):
            raise ValueError(
                f"feed_forward takes {len(self.conf.inputs)} inputs "
                f"({self.conf.inputs}), got {len(inputs)}")
        ins = dict(zip(self.conf.inputs, inputs))
        # no epilogue fold here: feedForward exposes every vertex's true
        # activation (the fold would show the BN vertex post-activation)
        acts, _, _ = self._forward(self.params, ins, self.state,
                                   train=train, rng=rng,
                                   fold_epilogues=False)
        return acts

    def output(self, *inputs, train: bool = False):
        """Output activations for the network outputs. Returns a single array
        when the graph has one output, else a list (DL4J ``output()``).

        ``train=False`` (serving) routes through the bucketed AOT
        :meth:`inference_engine` — ragged request sizes pad to a bounded
        bucket set instead of retracing per distinct batch size.
        ``train=True`` runs stochastic layers with a fresh rng key —
        its own cached trace, keyed on the flag."""
        if not train:
            return self.inference_engine().output(*inputs)
        fn = self._train_output_fn
        if fn is None:
            outputs = self.conf.outputs

            def fwd(params, state, xs, rng):
                acts, _, _ = self._forward(
                    params, dict(zip(self.conf.inputs, xs)), state,
                    train=True, rng=rng)
                return tuple(acts[o] for o in outputs)

            fn = self._train_output_fn = jax.jit(fwd)
            self._record_build("train.output_fn",
                               cache_attr="_train_output_fn")
        xs = tuple(jnp.asarray(x) for x in inputs)
        self._key, sub = jax.random.split(self._key)
        outs = [np.asarray(o) for o in
                fn(self.params, self.state, xs, sub)]
        return outs[0] if len(outs) == 1 else outs

    def predict(self, *inputs) -> np.ndarray:
        out = self.output(*inputs)
        if isinstance(out, list):
            return [np.argmax(o, axis=-1) for o in out]
        return np.argmax(out, axis=-1)

    def score(self, data=None) -> float:
        """Loss of the last fit batch, or of the given (Multi)DataSet;
        includes the regularization term on both paths."""
        if data is None:
            if self._score is not None and not isinstance(self._score, float):
                self._score = float(self._score)
            return self._score
        mds = data if isinstance(data, MultiDataSet) else \
            MultiDataSet.from_dataset(data)
        acts, new_bn, mks = self._forward(
            self.params,
            {n: jnp.asarray(f) for n, f in zip(self.conf.inputs, mds.features)},
            self.state, train=True, rng=None,
            masks={n: jnp.asarray(m)
                   for n, m in zip(self.conf.inputs, mds.features_masks)
                   if m is not None})
        total = 0.0
        for o, y, lm in zip(self.conf.outputs, mds.labels, mds.labels_masks):
            layer = self._out_layers[o]
            m = _loss.combine_masks(
                None if lm is None else jnp.asarray(lm), mks.get(o))
            if hasattr(layer, "update_centers"):
                # same quantity as the fit loop: CE + center penalty
                total = total + layer.loss_value(
                    acts[o], jnp.asarray(y), mask=m,
                    features=new_bn[o]["__features__"],
                    centers=self.state[o]["centers"])
            else:
                total = total + layer.loss_value(acts[o], jnp.asarray(y),
                                                 mask=m)
        return float(total + self._regularization(self.params))

    def evaluate(self, data, labels=None, output: int = 0):
        """Classification evaluation on one network output."""
        from ..eval.evaluation import Evaluation
        ev = Evaluation()
        for mds in _as_multi_iterator(data, labels):
            out = self.output(*mds.features)
            if isinstance(out, list):
                out = out[output]
            ev.eval(mds.labels[output], out, mask=mds.labels_masks[output])
        return ev


def _host_list(a) -> List[np.ndarray]:
    """An array, or a list or tuple of them, as a list of host arrays."""
    return [np.asarray(x) for x in (a if isinstance(a, (list, tuple)) else [a])]


def _as_multi_iterator(data, labels=None) -> MultiDataSetIterator:
    if isinstance(data, MultiDataSetIterator):
        return data
    if isinstance(data, MultiDataSet):
        return _SingleMultiIterator(data)
    if isinstance(data, DataSet):
        return _SingleMultiIterator(MultiDataSet.from_dataset(data))
    if isinstance(data, DataSetIterator):
        return _DataSetIteratorAdapter(data)
    if labels is not None:
        f = _host_list(data)
        return NumpyMultiDataSetIterator(f, _host_list(labels),
                                         batch_size=f[0].shape[0])
    raise TypeError(f"cannot make a MultiDataSetIterator from {type(data)}")


class _SingleMultiIterator(MultiDataSetIterator):
    def __init__(self, mds: MultiDataSet):
        self._mds = mds

    def batch_size(self):
        return self._mds.num_examples()

    def __iter__(self):
        yield self._mds


class _DataSetIteratorAdapter(MultiDataSetIterator):
    """DL4J MultiDataSetIteratorAdapter: DataSetIterator -> MultiDataSet."""

    def __init__(self, it: DataSetIterator):
        self._it = it

    def batch_size(self):
        return self._it.batch_size()

    def reset(self):
        self._it.reset()

    def __iter__(self):
        for ds in self._it:
            yield MultiDataSet.from_dataset(ds)
