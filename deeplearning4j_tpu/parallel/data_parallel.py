"""Data-parallel training over a device mesh.

TPU-native equivalent of DL4J's ``ParallelWrapper`` + Spark
``SharedTrainingMaster`` + ``VoidParameterServer`` stack (reference:
``deeplearning4j-parallel-wrapper .../parallelism/ParallelWrapper.java``†,
``dl4j-spark-parameterserver``†, ``nd4j .../parameterserver/distributed/v2``†
per SURVEY.md §2.6/§2.8/§3.4; reference mount was empty, citations
upstream-relative, unverified).

The entire reference stack (trainer threads, threshold-encoded gradient
gossip over Aeron UDP, mesh organizer) collapses into GSPMD: the batch is
sharded over the mesh's ``data`` axis, parameters are replicated, and XLA
inserts the gradient AllReduce over ICI inside the ONE compiled step
(SURVEY.md §3.4 "TPU translation"). The *contract* kept from the reference:
same-step synchronized replicas, deterministic update application,
listener-visible aggregated stats.

Multi-host: the same compiled program runs on every host via
``jax.distributed.initialize`` (see ``parallel/launcher.py``); this module is
oblivious to host count — the mesh spans whatever ``jax.devices()`` reports.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import environment as _envmod
from ..data.dataset import DataSetIterator, MultiDataSet
from ..nn.model import MultiLayerNetwork, _as_iterator
from ..ops import pallas_kernels as _pk


def make_mesh(devices: Optional[Sequence] = None, axis: str = "data") -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (axis,))


def make_dp_tp_mesh(data: int, model: int,
                    devices: Optional[Sequence] = None) -> Mesh:
    """2-axis ``('data', 'model')`` mesh for combined data + tensor
    parallelism. Model-axis neighbors should be ICI-adjacent (the default
    device order is), since the per-layer collectives ride that axis."""
    devs = list(devices) if devices is not None else jax.devices()
    if data * model != len(devs):
        raise ValueError(f"data*model = {data * model} != "
                         f"{len(devs)} devices")
    return Mesh(np.array(devs).reshape(data, model), ("data", "model"))


class ParallelWrapper:
    """Data-parallel fit() over a mesh (name kept for reference parity).

    Usage mirrors DL4J::

        pw = ParallelWrapper(net)            # mesh over all devices
        pw.fit(iterator, epochs=2)

    Batches whose size is not divisible by the mesh size are padded to the
    next multiple and the padded examples are masked out of the loss (DL4J's
    prefetch splitter silently constrained batch%workers; pad-and-mask keeps
    every example contributing exactly once). A pad feature mask is
    synthesized alongside the loss mask, so train-mode BatchNorm computes
    mask-aware batch moments — padded rows perturb neither the loss nor
    the running statistics (the round-2 recorded artifact, now fixed;
    equivalence to the unpadded single-chip step is tested).

    ``shard_update=True`` (ZeRO-1, "Automatic Cross-Replica Sharding of
    Weight Update in Data-Parallel Training", Xu et al. 2020, PAPERS.md):
    the updater-state pytree and the weight-update computation are sharded
    over the ``data`` mesh axis instead of replicated — each parameter leaf
    gets its largest divisible dimension partitioned (composed with any
    tensor-parallel ``model_axis`` sharding), ``out_shardings`` pin the
    updated params back to their replicated/TP layout, and GSPMD emits the
    reduce-scatter → 1/N-shard update → all-gather pipeline inside the one
    compiled step (the TVM/GSPMD posture: sharding is a compiler
    annotation, not hand-written collectives). Update FLOPs and updater
    memory (Adam m/v ≈ 2x params) then scale with the per-device share,
    not the model. Numerically equivalent to the replicated path — every
    updater is elementwise (``nn.updaters.apply_leaf`` contract), so the
    shard of the update equals the update of the shard; non-elementwise
    updaters are rejected. Checkpoints gather on save and reshard lazily
    on restore (``parallel/checkpoint.py``), so round-trips across
    ``shard_update`` settings and topologies are exact.

    ``accum_steps=k``: gradient micro-accumulation — each global batch is
    split into k microbatches scanned on device (``nn/microbatch.py``),
    with ONE updater application (and, under ``shard_update``, one
    reduce-scatter/all-gather) per k microbatches, amortizing the update
    collectives exactly as the paper prescribes. Pad granularity becomes
    ``devices * accum_steps`` so microbatches stay equal-sized; microbatch
    losses/gradients combine as a mean WEIGHTED by unmasked label count,
    so a ragged tail whose padding lands unevenly across microbatches
    (even entire all-pad microbatches) still reproduces the unpadded step
    exactly (tested).

    ``overlap_grads=True`` (requires ``shard_update=True``): gradient
    leaves are bucketed by size in reverse layer order and each bucket is
    pinned to the ZeRO-1 update sharding at gradient-production time
    (``parallel/overlap.py``) — the reduce-scatter of early (deep-layer)
    buckets is issued while backward compute of earlier layers is still in
    flight, instead of all collectives waiting behind the clip/sentinel
    global-norm joins at the updater boundary. Pure scheduling structure
    (sharding constraints + ordering barriers): bit-equivalent to the
    unoverlapped path, composes with ``accum_steps`` and ``model_axis``
    (tested). ``overlap_bucket_mb`` caps the per-bucket payload (default
    4 MiB — the DDP bucketing sweet-spot neighborhood).
    """

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 model_axis: Optional[str] = None,
                 shard_update: bool = False, accum_steps: int = 1,
                 overlap_grads: bool = False,
                 overlap_bucket_mb: float = None,
                 dcn_hosts: Optional[int] = None):
        # model: MultiLayerNetwork or ComputationGraph (duck-typed: both
        # expose params/updater_state/state/_build_train_step with the same
        # pytree layout; only the batch-argument arity differs)
        #
        # model_axis: name of a mesh axis to TENSOR-PARALLEL the dense
        # family over (make_dp_tp_mesh): dense/output kernels [in, out]
        # shard over their out column, biases follow, everything else
        # (conv/BN/recurrent) replicates. GSPMD inserts the per-layer
        # collectives; updater state follows parameter sharding. This goes
        # BEYOND the reference (DL4J's parallelism is data-parallel only) —
        # the TPU-first extension SURVEY.md §3.4's translation invites.
        self.model = model
        self.mesh = mesh or make_mesh()
        self.model_axis = model_axis
        if model_axis is not None and model_axis not in self.mesh.axis_names:
            raise ValueError(f"model_axis {model_axis!r} not in mesh axes "
                             f"{self.mesh.axis_names}")
        self.shard_update = bool(shard_update)
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = int(accum_steps)
        if self.shard_update:
            if "data" not in self.mesh.axis_names:
                raise ValueError("shard_update needs a 'data' mesh axis to "
                                 f"shard over; mesh has {self.mesh.axis_names}")
            if model_axis == "data":
                raise ValueError("model_axis cannot be the 'data' axis the "
                                 "sharded update partitions over")
            upd = getattr(model.conf, "updater", None)
            if upd is not None and not getattr(upd, "elementwise", True):
                # the ZeRO-1 shard-equivalence contract (updaters.apply_leaf)
                # only holds for elementwise updaters: a per-tensor norm
                # computed over a 1/N shard is not the global norm
                raise ValueError(
                    f"shard_update requires an elementwise updater; "
                    f"{type(upd).__name__} is not")
        from . import overlap as _overlap
        if overlap_grads and not self.shard_update:
            # the collectives the overlap chunks/pipelines ARE the ZeRO-1
            # reduce-scatter/all-gather; the replicated path's one grad
            # all-reduce has no per-bucket shard layout to pin
            raise ValueError("overlap_grads=True requires shard_update=True "
                             "(it pipelines the ZeRO-1 collectives)")
        self.overlap_grads = bool(overlap_grads)
        self.overlap_bucket_bytes = int(
            (overlap_bucket_mb or _overlap.DEFAULT_BUCKET_MB) * (1 << 20))
        # dcn_hosts: DCN-group count along the data axis for the
        # hierarchical gradient collectives (ISSUE 10). None = auto-detect
        # from device process membership (a real pod mesh built by
        # launcher.pod_mesh); an explicit int simulates the hierarchy on a
        # single process's virtual devices (tests / bench) or overrides
        # detection on exotic topologies.
        self.dcn_hosts = dcn_hosts
        self._pending_step_cause = None
        self._step = None
        self._dense_key_cache = None
        from ..nn.graph import ComputationGraph
        self._is_graph = isinstance(model, ComputationGraph)

    def set_overlap(self, on: bool, bucket_mb: Optional[float] = None
                    ) -> "ParallelWrapper":
        """Toggle the gradient-collective overlap (``parallel/overlap.py``)
        in place. The bucketing/sharding pins are baked into the compiled
        step, so a change drops the cached step and the rebuild is
        attributed ``cause="overlap"`` in the retrace tracker."""
        on = bool(on)
        if on and not self.shard_update:
            raise ValueError("overlap_grads=True requires shard_update=True")
        changed = on != self.overlap_grads
        if bucket_mb is not None:
            nb = int(float(bucket_mb) * (1 << 20))
            if nb != self.overlap_bucket_bytes:
                self.overlap_bucket_bytes = nb
                # the bucket size is only baked into OVERLAP steps — a
                # change while overlap stays off must not retrace the
                # (bucket-free) program
                changed = changed or on
        self.overlap_grads = on
        if changed and self._step is not None:
            self._step = None
            self._pending_step_cause = "overlap"
        return self

    def set_accum_steps(self, k: int) -> "ParallelWrapper":
        """Change the gradient micro-accumulation factor in place (the
        ISSUE 14 schedule-tuner apply seam). The microbatch split is
        baked into the compiled step, so a change drops the cached step;
        the rebuild is attributed ``cause="config_change"`` (or whatever
        the tuner arms). Note accum_steps changes the summation ORDER of
        the gradient (weighted-mean recombination, ``nn/microbatch.py``):
        equal to accum_steps=1 to float tolerance, not bit-for-bit."""
        k = int(k)
        if k < 1:
            raise ValueError(f"accum_steps must be >= 1, got {k}")
        if k != self.accum_steps:
            self.accum_steps = k
            if self._step is not None:
                self._step = None
                self._pending_step_cause = \
                    self._pending_step_cause or "config_change"
        return self

    def tune_schedule(self, batch_size: int, apply: bool = True,
                      force: bool = False, **kwargs) -> dict:
        """Joint schedule search over THIS wrapper's sharded train step
        (ISSUE 14, ``runtime/schedule.py``): workspace-mode x accum_steps
        x GLOBAL batch size x ``overlap_bucket_mb`` (when the ZeRO-1
        overlap is on), oracle-pruned via AOT ``memory_analysis`` of the
        GSPMD program, attribution-seeded, timed as real sharded steps.
        ``apply=True`` routes the winner through the existing seams —
        ``model.set_workspace_mode`` / :meth:`set_overlap` /
        :meth:`set_accum_steps` — one attributed retrace each, zero
        steady-state compiles after. Batch size is a recommendation in
        the returned entry (the iterator owns the real batch)."""
        from ..runtime import schedule as _sched
        return _sched.tune_schedule(self, batch_size, apply=apply,
                                    force=force, **kwargs)

    def _dense_keys(self) -> set:
        """Top-level param keys (layer index / vertex name) whose layer is
        in the dense family — the only layers TP shards. Matching on the
        leaf name 'W' alone would also catch embedding tables and LSTM/GRU
        input kernels, whose per-step collectives hurt the TP path.
        Shared with the serving placement layer (ISSUE 17)."""
        from . import placement as _pl
        return _pl.dense_tp_keys(self.model)

    def _param_spec(self, path: tuple, arr) -> P:
        """PartitionSpec for one parameter leaf under tensor parallelism —
        the training contract: dense family only (``attn_heads=None``;
        serving extends the same derivation with the attention family
        through ``ParamsPlacement``)."""
        from . import placement as _pl
        if self.model_axis is None:
            return P()
        if self._dense_key_cache is None:
            self._dense_key_cache = self._dense_keys()
        return _pl.tp_param_spec(
            tuple(str(p) for p in path), arr, self.model_axis,
            int(self.mesh.shape[self.model_axis]), self._dense_key_cache)

    def _update_spec(self, path: tuple, arr) -> P:
        """PartitionSpec for one UPDATER-STATE leaf under the sharded weight
        update (ZeRO-1): on top of the parameter's own spec (replicated, or
        the TP spec when ``model_axis`` is set), the largest still-free
        dimension divisible by the data-axis size is partitioned over
        ``'data'`` — e.g. a dense kernel [in, out] with out >= in becomes
        ``P(None, 'data')`` plain, or ``P('data', 'model')`` under tensor
        parallelism (out taken by 'model', so 'data' lands on the in dim).
        Leaves with no divisible free dimension stay on the base spec
        (replicated update for that leaf — correct, just not sharded)."""
        base = self._param_spec(path, arr)
        n = self.mesh.shape["data"]
        ndim = getattr(arr, "ndim", 0)
        if n <= 1 or ndim == 0:
            return base
        taken = {i for i, ax in enumerate(base) if ax is not None}
        free = [d for d in range(ndim) if d not in taken]
        for d in sorted(free, key=lambda d: -arr.shape[d]):
            if arr.shape[d] % n == 0:
                spec = list(base) + [None] * (ndim - len(base))
                spec[d] = "data"
                return P(*spec)
        return base

    def _shardings(self, params, spec_fn):
        """NamedSharding tree matching the params pytree."""
        from jax.tree_util import tree_map_with_path

        def leaf(path, a):
            names = tuple(str(getattr(k, "key", k)) for k in path)
            return NamedSharding(self.mesh, spec_fn(names, a))
        return tree_map_with_path(leaf, params)

    def _param_shardings(self, params):
        return self._shardings(params, self._param_spec)

    def _update_shardings(self, params):
        return self._shardings(params, self._update_spec)

    def _sharding_trees(self):
        """(repl, data, params, updater-state-slot, opt_state, bn_state,
        params-structure) sharding trees for the step's carried arguments —
        the ONE place the opt-state placement rule lives, shared by
        ``_build`` (out_shardings / per-step placement) and
        ``memory_report`` (sharded avals for AOT lowering)."""
        from jax.tree_util import tree_structure
        repl = NamedSharding(self.mesh, P())
        data = NamedSharding(self.mesh, P("data"))
        p_sh = self._param_shardings(self.model.params)
        upd_sh = self._update_shardings(self.model.params) \
            if self.shard_update else p_sh
        p_struct = tree_structure(self.model.params)
        opt = self.model.updater_state
        if isinstance(opt, dict):
            opt_sh = {k: (upd_sh if tree_structure(sub) == p_struct
                          else jax.tree.map(lambda a: repl, sub))
                      for k, sub in opt.items()}
        else:
            opt_sh = jax.tree.map(lambda a: repl, opt)
        bn_sh = jax.tree.map(lambda a: repl, self.model.state)
        return repl, data, p_sh, upd_sh, opt_sh, bn_sh, p_struct

    def _build(self):
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("data"))

        # Same pure step; GSPMD partitions the batch dim and inserts the
        # gradient AllReduce. Donation mirrors the single-chip path.
        # out_shardings pin the UPDATED params/state to the input layout:
        # without the pin, GSPMD is free to pick different output shardings
        # for the updated tree than the inputs carried (observed r4 with
        # the then-fused updater's concat/slice chain), which would force a
        # host reshard every step — the pin keeps the TP layout stable
        # regardless of how the update arithmetic is expressed.
        #
        # shard_update=True: the OPT-STATE in/out shardings carry the
        # P('data')-partitioned specs instead of the param specs, while the
        # updated params stay pinned to their replicated/TP layout. GSPMD
        # then materializes the ZeRO-1 pipeline inside this one program:
        # the gradient arrives reduce-SCATTERED into the update's shard
        # layout, the m/v/delta arithmetic runs on each device's 1/N
        # share, and the params pin forces the all-gather of the fresh
        # weights — no hand-written collectives anywhere.
        # overlap_grads (ISSUE 7): bucket the gradient leaves (reverse
        # layer order, size-capped) and pin each bucket to the ZeRO-1
        # update sharding AT GRAD TIME — GSPMD then emits per-bucket
        # reduce-scatters before the clip/sentinel global-norm joins, where
        # the latency-hiding scheduler can run them under the remaining
        # backward compute. Value-identity: bit-equivalent to overlap off.
        grad_transform = None
        from . import overlap as _overlap
        from ..runtime import telemetry as _tel
        n_buckets = 0
        if self.overlap_grads:
            buckets = _overlap.make_buckets(self.model.params,
                                            self.overlap_bucket_bytes)
            upd_shardings = self._update_shardings(self.model.params)
            # multi-host (ISSUE 10): two-stage intra-host/DCN pins per
            # bucket, DCN-heavy buckets on their own issue chain so the
            # slow hops start as early as their grads exist without
            # gating the light reduce-scatters; on a single host
            # hierarchy is None and this is the flat r12 path
            hierarchy = _overlap.host_hierarchy(self.mesh, self.dcn_hosts)
            chains = _overlap.split_dcn_chains(buckets, upd_shardings) \
                if hierarchy is not None else None
            grad_transform = _overlap.overlap_transform(
                buckets, upd_shardings, hierarchy=hierarchy, chains=chains)
            n_buckets = len(buckets)
        # per-model labeled cell (anti-blending rule; 0 = overlap off for
        # THIS wrapper's current step) — the model's telemetry_label
        # finalizer discards it with the rest of the model= cells. On a
        # pod the cell additionally carries host=<process_index> so a
        # pod-wide scrape/merge keeps hosts apart (ISSUE 10 satellite).
        _overlap.BUCKETS_GAUGE.labeled(
            model=getattr(self.model, "telemetry_label",
                          type(self.model).__name__),
            **_tel.host_labels()).set(n_buckets)
        step = self.model._build_train_step(
            self.accum_steps, grad_transform=grad_transform).__wrapped__

        @functools.wraps(step)
        def pure(*args):
            # GSPMD partitions this program; the kernel dispatchers must
            # know while it is traced (ops/pallas_kernels.gspmd_trace)
            with _pk.gspmd_trace(mesh):
                return step(*args)
        from jax.tree_util import tree_structure
        from ..runtime import sentinel as _sent
        _, _, p_sh, upd_sh, opt_sh, bn_sh, p_struct = self._sharding_trees()
        # sentinel counters (divergence sentinel, runtime/sentinel.py) ride
        # along replicated — GSPMD reduces the finite-check across shards
        # inside the step, so every device agrees on skip-vs-apply
        sent_sh = {n: repl for n in _sent.COUNTERS}
        step_fn = jax.jit(
            pure, donate_argnums=(0, 1, 2),
            out_shardings=(p_sh, opt_sh, bn_sh, sent_sh, repl),
            compiler_options=_envmod.engine_compiler_options())

        multi_host = jax.process_count() > 1

        # FULL-VALUE placement (params / opt state / BN state / sentinel —
        # every host holds the entire logical value): the shared placement
        # layer's put (ISSUE 17); see placement.put_full for the
        # full-value vs host-shard contract (the (6,16)->(6,32) Adam-slot
        # incident lives in its docstring now).
        from .placement import put_full as put

        def shard_batch(t):
            """Batch-sharded placement for one array, a tuple of arrays
            (multi-input/-output graphs), or None (absent mask).
            Multi-host semantics differ from :func:`put`: the host-local
            batch (HostShardedIterator) IS this host's contiguous SHARD of
            the global batch, so ``make_array_from_process_local_data``
            reassembles the global array in host order."""
            if t is None:
                return None
            if isinstance(t, tuple):
                return tuple(shard_batch(a) for a in t)
            if isinstance(t, jax.Array) and t.sharding == data:
                return t
            if multi_host:
                return jax.make_array_from_process_local_data(
                    data, np.asarray(t))
            return jax.device_put(t, data)

        def shard_args(params, opt_state, bn_state, sentinel, step, key,
                       x, y, fm, lm):
            # params/opt structure and model_axis are fixed after init, so
            # the build-time sharding trees apply every step (after the
            # first step every put() is a pass-through anyway)
            params = jax.tree.map(put, params, p_sh)
            # updater state slots ("m"/"v"/"h"...) mirror the params tree —
            # place them on the update sharding (== the param sharding when
            # shard_update is off) so sharded state stays sharded, and a
            # replicated restore (checkpoint) re-shards lazily here
            opt_state = {
                k: (jax.tree.map(put, sub, upd_sh)
                    if tree_structure(sub) == p_struct
                    else jax.tree.map(lambda a: put(a, repl), sub))
                for k, sub in opt_state.items()
            } if isinstance(opt_state, dict) else jax.tree.map(
                lambda a: put(a, repl), opt_state)
            bn_state = jax.tree.map(lambda a: put(a, repl), bn_state)
            return (params, opt_state, bn_state,
                    put(step, repl), put(key, repl),
                    shard_batch(x), shard_batch(y),
                    shard_batch(fm), shard_batch(lm),
                    jax.tree.map(lambda a: put(a, repl), sentinel))

        return step_fn, shard_args

    def _lower_step(self, batch_size: int, seq_len=None, step_fn=None,
                    cause="probe"):
        """AOT lower+compile of a sharded train step at the GLOBAL
        ``batch_size`` (nothing executes). ``step_fn=None`` uses (and
        caches) THIS wrapper's step; an explicit ``step_fn`` (the
        schedule tuner's candidate builds) is lowered without touching
        the wrapper's cache. The compile is reported to the retrace
        tracker as ``cause`` (``None`` = the caller already attributed
        it, e.g. the tuner's ``schedule_tune``)."""
        from ..nn import memory as _memory
        from ..runtime import sentinel as _sent
        from ..runtime import telemetry as _tel
        m = self.model
        if cause is not None:
            _tel.record_compile("parallel.step", cause,
                                model=type(m).__name__, batch=batch_size)
        if not m.params:
            m.init()
        if step_fn is None:
            if self._step is None:
                self._step = self._build()
            step_fn, _ = self._step
        repl, data, p_sh, _, opt_sh, bn_sh, _ = self._sharding_trees()

        def sds(aval, sh):
            return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sh)

        x, y = _memory._batch_avals(m, batch_size, seq_len)
        x = jax.tree.map(lambda a: sds(a, data), x)
        y = jax.tree.map(lambda a: sds(a, data), y)
        fm = (None,) * len(x) if isinstance(x, tuple) else None
        lm = (None,) * len(y) if isinstance(y, tuple) else None
        return step_fn.lower(
            jax.tree.map(sds, jax.eval_shape(lambda: m.params), p_sh),
            jax.tree.map(sds, jax.eval_shape(lambda: m.updater_state),
                         opt_sh),
            jax.tree.map(sds, jax.eval_shape(lambda: m.state), bn_sh),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
            sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)), repl),
            x, y, fm, lm,
            jax.tree.map(lambda a: sds(a, repl),
                         _sent.counter_avals())).compile()

    def memory_report(self, batch_size: int, seq_len=None) -> dict:
        """Compiled-HBM accounting of THIS wrapper's sharded train step
        (GSPMD program — the per-device memory_analysis view) at the
        GLOBAL ``batch_size``, via AOT lower+compile (nothing executes).
        Same fields as ``model.memory_report`` (``nn/memory.py``); the
        conf's ``workspace_mode`` remat policy and ``shard_update``/
        ``accum_steps`` are all baked into the measured program."""
        from ..nn import memory as _memory
        m = self.model
        report = {
            "workspace_mode": str(getattr(m.conf, "workspace_mode", "none")),
            "batch_size": int(batch_size),
            "accum_steps": self.accum_steps,
            "shard_update": self.shard_update,
            "devices": int(self.mesh.devices.size),
            "temp_bytes": None, "argument_bytes": None, "output_bytes": None,
            "alias_bytes": None, "generated_code_bytes": None,
            "peak_bytes": None,
            "device": _memory.device_memory_stats(),
        }
        cm = _memory.compiled_memory(self._lower_step(batch_size, seq_len))
        if cm:
            report.update(cm)
        return report

    def _host_share(self, batch_args, batch_size: int):
        """Slice synthetic FULL-GLOBAL-size batch arrays down to THIS
        host's share before ``shard_args``: the multi-host contract of
        ``shard_batch`` is local-value-IS-the-shard
        (``make_array_from_process_local_data``), so feeding every host
        the full global batch would silently reassemble — and measure —
        a ``batch_size x process_count`` program while the cost model
        and cache key describe ``batch_size`` (the attribution/tuner
        measurement paths). Identity on a single process."""
        n = jax.process_count()
        if n <= 1:
            return batch_args
        if batch_size % n:
            raise ValueError(
                f"global batch {batch_size} does not divide over "
                f"{n} hosts — pass a host-divisible batch_size")
        share = batch_size // n
        return jax.tree.map(lambda a: a[:share], batch_args)

    def _schedule_key_suffix(self) -> dict:
        """The wrapper-schedule fields every cached attribution report
        must be keyed on (ISSUE 14 satellite bugfix): a report measured
        with overlap ON describes a differently-scheduled program than
        one with overlap OFF, and the tuner seeding from the cache must
        never read across that boundary."""
        from . import placement as _pl
        return {"su": int(self.shard_update),
                "ov": int(self.overlap_grads),
                "mb": self.overlap_bucket_bytes / (1 << 20),
                "mesh": _pl.mesh_key(self.mesh)}

    def attribution_report(self, batch_size: int, steps: int = 3,
                           seq_len=None, peaks=None,
                           measured_s=None) -> dict:
        """MFU attribution of THIS wrapper's sharded step at the GLOBAL
        ``batch_size`` (``runtime/attribution.py``): AOT
        ``cost_analysis`` + a synced self-measurement of ``steps`` real
        sharded executions on zero batches (or a caller-supplied
        ``measured_s``). The report key carries the full schedule —
        workspace_mode, accum_steps, shard_update, overlap on/off and
        bucket size, mesh shape — so the ISSUE 14 tuner can seed from
        cached fractions without ever reading a differently-scheduled
        program's numbers."""
        import time as _time

        from ..runtime import attribution as _attr
        from ..runtime import telemetry as _tel
        m = self.model
        if not m.params:
            m.init()
        if self._step is None:
            self._step = self._build()
        step_fn, shard_args = self._step
        # _lower_step records the probe compile itself (parallel.step/
        # probe) — attributing here too would double-count the event
        compiled = self._lower_step(batch_size, seq_len)
        if measured_s is None:
            durs = []
            for i in range(max(1, int(steps)) + 1):
                (params, opt, state, stepi, key, xs, ys, fm, lm,
                 sent) = _attr._train_step_args(
                    m, batch_size, self.accum_steps, seq_len, i)
                xs, ys = self._host_share((xs, ys), batch_size)
                args = shard_args(params, opt, state, sent, stepi, key,
                                  xs, ys, fm, lm)
                t0 = _time.perf_counter()
                out = step_fn(*args)
                jax.block_until_ready(out)
                durs.append(_time.perf_counter() - t0)
            measured_s = min(durs[1:]) if len(durs) > 1 else durs[0]
        key = _attr.train_step_key(m, batch_size, self.accum_steps,
                                   seq_len,
                                   schedule=self._schedule_key_suffix())
        rep = _attr.attribute_compiled(compiled, measured_s, peaks=peaks,
                                       key=key)
        rep.update({"kind": "parallel_step",
                    "batch_size": int(batch_size),
                    "accum_steps": self.accum_steps,
                    "shard_update": self.shard_update,
                    "overlap": self.overlap_grads,
                    "overlap_bucket_mb":
                        self.overlap_bucket_bytes / (1 << 20),
                    "devices": int(self.mesh.devices.size),
                    "workspace_mode":
                        str(getattr(m.conf, "workspace_mode", "none"))})
        return rep

    def on_host_loss(self) -> None:
        """Post-``launcher.reinitialize()`` repair (ISSUE 10): the old
        mesh's device objects belong to the torn-down backend client, so
        rebuild the mesh over the FRESH ``jax.devices()`` with the same
        shape/axes (host-major grouping preserved via ``pod_mesh``'s
        rule), and drop every compiled program that baked the dead
        devices in — the wrapper step and the model's own caches. The
        rebuild is attributed ``cause="host_loss"`` in the retrace
        tracker. Model STATE is not touched here: arrays from the old
        client are dead, and ``run_resilient_fit`` restores them from the
        checkpoint right after."""
        from . import launcher as _launcher
        shape = self.mesh.devices.shape
        if self.mesh.axis_names not in (("data",), ("data", "model")):
            raise RuntimeError(
                f"on_host_loss cannot rebuild a mesh with axes "
                f"{self.mesh.axis_names}; rebuild it yourself and assign "
                "wrapper.mesh before resuming")
        model_ax = shape[1] if len(shape) > 1 else 1
        rebuilt = _launcher.pod_mesh(model=model_ax)
        if rebuilt.devices.shape != shape:
            raise RuntimeError(
                f"post-host-loss topology changed: mesh was {shape}, "
                f"fresh devices give {rebuilt.devices.shape}; restore onto "
                "the new topology explicitly (TrainingCheckpointer restore "
                "is topology-independent)")
        self.mesh = rebuilt
        self._step = None
        self._pending_step_cause = "host_loss"
        if hasattr(self.model, "_invalidate_compiled"):
            self.model._invalidate_compiled(cause="host_loss")

    def serving_engine(self, **kwargs):
        """A ``serving.engine.InferenceEngine`` over THIS wrapper's mesh:
        train data-parallel, then serve the same slice — coalesced request
        batches shard over the ``'data'`` axis (bucket floor rises to the
        mesh size so every device holds equal rows). Keyword args pass
        through (e.g. ``min_bucket=``)."""
        from ..serving.engine import InferenceEngine
        if "data" not in self.mesh.axis_names:
            raise ValueError("serving_engine needs a 'data' mesh axis; "
                             f"mesh has {self.mesh.axis_names}")
        kwargs.setdefault("model_axis", self.model_axis or "model")
        return InferenceEngine(self.model, mesh=self.mesh, **kwargs)

    def fit(self, data, epochs: int = 1, resilience=None):
        if resilience is not None:
            from .resilience import run_resilient_fit
            return run_resilient_fit(self, data, epochs=epochs,
                                     policy=resilience)
        from ..runtime import faults as _faults
        from ..runtime import telemetry as _tel
        m = self.model
        if not m.params:
            m.init()
        if self._step is None:
            self._step = self._build()
            cause = self._pending_step_cause or (
                m._consume_retrace_cause()
                if hasattr(m, "_consume_retrace_cause") else "first_build")
            self._pending_step_cause = None
            _tel.record_compile("parallel.step", cause,
                                shard_update=self.shard_update,
                                overlap=self.overlap_grads)
        step_fn, shard_args = self._step
        # the engines' train.phase.* spans (nn/caches.py, "phase tracing"):
        # pod fits get the same cells as the engine fit loops — labeled
        # model= AND host= (ISSUE 10), so a pod-wide scrape shows every
        # host's step-time distribution apart
        span_labels = m._phase_labels()
        with _tel.span("train.phase.call_s", span_labels,
                       entry="ParallelWrapper.fit"):
            for _ in range(epochs):
                for batch in m._timed_batches(self._batches(data),
                                              span_labels):
                    x, y, fm, lm = batch
                    with _tel.span("train.phase.prepare_s", span_labels):
                        if _faults.enabled():
                            _faults.trip("train.step")  # crash/preemption site
                            # whole-host-loss site (ISSUE 10): deterministic
                            # injections fire on every process at the same
                            # step (SPMD), raising HostLoss —
                            # run_resilient_fit routes it through
                            # launcher.reinitialize() + restore
                            _faults.trip("parallel.host_loss")
                            # float check FIRST: all-int inputs must not
                            # consume the injection's fire budget without
                            # poisoning anything
                            if any(np.issubdtype(np.asarray(a).dtype,
                                                 np.floating)
                                   for a in jax.tree.leaves(x)) and \
                                    _faults.trip("train.nonfinite") \
                                    is not None:
                                x = jax.tree.map(
                                    lambda a: np.full_like(a, np.nan)
                                    if np.issubdtype(np.asarray(a).dtype,
                                                     np.floating)
                                    else a, x)  # sentinel site
                        m._key, sub = jax.random.split(m._key)
                        step = jnp.asarray(m.iteration, jnp.int32)
                        sentinel = m._ensure_sentinel()
                    with _tel.span("train.phase.stage_s", span_labels):
                        args = shard_args(
                            m.params, m.updater_state, m.state, sentinel,
                            step, sub, x, y, fm, lm)
                    with m._timed_dispatch(span_labels):
                        (m.params, m.updater_state, m.state, m._sentinel,
                         loss) = step_fn(*args)
                    _tel.record_dispatch("parallel.step", step_fn, args,
                                         m._program_labels)
                    m._score = loss
                    m.iteration += 1
                    m._notify_listeners(span_labels, "iteration_done",
                                        m.iteration, m.epoch)
                m.epoch += 1
                m._notify_listeners(span_labels, "on_epoch_end")
        return m

    def _pad_granularity(self) -> int:
        """Rows the per-host batch must divide into: this host's extent of
        the DATA axis (batches shard over 'data' only — the model axis
        replicates them, so padding to ``devices.size`` on a 2-D mesh
        over-padded) times ``accum_steps`` for the microbatch split."""
        data_size = self.mesh.shape.get("data", self.mesh.devices.size)
        return max(1, data_size // jax.process_count()) * self.accum_steps

    def _passthrough_batch(self, t, n: int):
        """Pre-placed device batches (AsyncDataSetIterator
        ``device_prefetch`` with a multi-host/global sharding) bypass the
        host-side pad path — a non-addressable global array can neither be
        np.asarray'd nor padded here. Their batch dim must already divide
        the GLOBAL data extent."""
        arrs = t if isinstance(t, tuple) else (t,)
        g = n * jax.process_count()
        for a in arrs:
            if a is not None and a.shape[0] % g:
                raise ValueError(
                    f"pre-placed device batch of {a.shape[0]} rows does not "
                    f"divide the global data extent {g}; size (or pre-pad) "
                    "device-prefetched batches to a multiple — host-side "
                    "pad-and-mask only applies to numpy batches")
        return t

    def _batches(self, data):
        """Yield (x, y, fm, lm) step arguments — arrays for the sequential
        engine, tuples-of-arrays for the graph engine — ragged tails padded
        to the data-axis extent and masked. Multi-host: batches are
        HOST-LOCAL shards (see launcher.HostShardedIterator), so the pad
        granularity is the per-host share of the data axis, keeping every
        host's shard equal-sized. With ``accum_steps=k`` the granularity
        multiplies by ``k`` so the microbatch split stays equal-sized.
        Already-global jax.Arrays (multi-host device prefetch) pass
        through untouched."""
        n = self._pad_granularity()

        def is_device_batch(a):
            first = a[0] if isinstance(a, tuple) else a
            return isinstance(first, jax.Array) and \
                not first.is_fully_addressable

        if self._is_graph:
            from ..nn.graph import _as_multi_iterator
            for mds in _as_multi_iterator(data):
                if any(is_device_batch(a) for a in mds.features
                       if a is not None):
                    yield (self._passthrough_batch(tuple(mds.features), n),
                           self._passthrough_batch(tuple(mds.labels), n),
                           tuple(mds.features_masks), tuple(mds.labels_masks))
                    continue
                fs = [np.asarray(a) for a in mds.features]
                ls = [np.asarray(a) for a in mds.labels]
                fms = [None if a is None else np.asarray(a)
                       for a in mds.features_masks]
                lms = [None if a is None else np.asarray(a)
                       for a in mds.labels_masks]
                rem = fs[0].shape[0] % n
                if rem:
                    fs, ls, fms, lms = _pad_and_mask_multi(
                        fs, ls, fms, lms, n - rem)
                yield (tuple(fs), tuple(ls), tuple(fms), tuple(lms))
        else:
            it: DataSetIterator = _as_iterator(data)
            for ds in it:
                if is_device_batch(ds.features):
                    yield (self._passthrough_batch(ds.features, n),
                           self._passthrough_batch(ds.labels, n),
                           ds.features_mask, ds.labels_mask)
                    continue
                x = np.asarray(ds.features)
                y = np.asarray(ds.labels)
                fm = None if ds.features_mask is None else np.asarray(ds.features_mask)
                lm = None if ds.labels_mask is None else np.asarray(ds.labels_mask)
                rem = x.shape[0] % n
                if rem:
                    x, y, fm, lm = _pad_and_mask(x, y, fm, lm, n - rem)
                yield (x, y, fm, lm)


def _synth_pad_feature_mask(x, pad):
    """Pad feature mask so mask-aware layers (train-mode BatchNorm moments)
    exclude the padded rows: per-timestep [B,T] for sequence inputs,
    per-example [B] otherwise. ``x`` is already zero-padded by ``pad``."""
    fm = np.ones(x.shape[:2] if x.ndim == 3 else (x.shape[0],), np.float32)
    if pad:  # fm[-0:] would zero the ENTIRE mask
        fm[-pad:] = 0.0
    return fm


def _pad_and_mask(x, y, fm, lm, pad):
    """Zero-pad `pad` examples onto the batch and mask them out of the loss.

    The label mask is the loss-weighting channel (losses average over the
    unmasked count, see ops/losses._per_example), so padded rows contribute
    zero loss and zero gradient.
    """
    def zpad(a):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths)

    x, y = zpad(x), zpad(y)
    if fm is not None:
        fm = zpad(fm)  # padded rows have all-zero feature mask
    else:
        fm = _synth_pad_feature_mask(x, pad)
    if lm is not None:
        lm = zpad(lm)  # padded rows masked (zeros)
    else:
        # synthesize a per-example pad mask; the loss INTERSECTS it with any
        # network-propagated mask (ops/losses.combine_masks), so real
        # sequences' masked timesteps stay excluded too
        lm = np.ones((y.shape[0],), dtype=np.float32)
        lm[-pad:] = 0.0
    return x, y, fm, lm


def _pad_and_mask_multi(fs, ls, fms, lms, pad):
    """Multi-input/-output variant of :func:`_pad_and_mask` for the graph
    engine: every feature/label array is zero-padded; label masks are padded
    or (when no mask exists anywhere) synthesized per output slot."""
    def zpad(a):
        return np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

    fs = [zpad(a) for a in fs]
    ls = [zpad(a) for a in ls]
    fms = [zpad(m) if m is not None else _synth_pad_feature_mask(x, pad)
           for x, m in zip(fs, fms)]
    out_lms = []
    for y, m in zip(ls, lms):
        if m is not None:
            out_lms.append(zpad(m))
        else:
            # per-example pad mask; intersected with any propagated mask by
            # the loss (ops/losses.combine_masks)
            lm = np.ones((y.shape[0],), dtype=np.float32)
            lm[-pad:] = 0.0
            out_lms.append(lm)
    return fs, ls, fms, out_lms
