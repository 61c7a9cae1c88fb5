"""Multi-host control plane: jax.distributed launcher + per-host data sharding.

TPU-native equivalent of the reference's multi-node orchestration layer
(reference: ``dl4j-spark-parameterserver .../SharedTrainingMaster.java``,
``nd4j .../parameterserver/distributed/v2/**`` — MeshOrganizer tree, Aeron
UDP transport, heartbeats — per SURVEY.md §2.8/§3.4; reference mount was
empty, citations upstream-relative, unverified).

The entire transport/mesh/codec stack collapses into the JAX control plane
(SURVEY.md §2.8 "TPU-native equivalent"): ``jax.distributed.initialize``
brings up the coordination service (the MeshOrganizer/heartbeat analog —
PJRT's distributed runtime does membership, barriers and health checks), and
the hot gradient path is XLA AllReduce over ICI/DCN emitted by GSPMD — no
parameter server, no gradient gossip. What this module keeps from the
reference's contract: every host runs the same program on the same step,
updates are deterministic, and each host reads its own shard of the data
(Spark's per-executor RDD partitions → :class:`HostShardedIterator`).

Typical pod usage (same script on every host)::

    from deeplearning4j_tpu.parallel import launcher
    launcher.initialize()                      # env-driven on TPU pods
    mesh = launcher.pod_mesh(model=4)          # DCN-aware data x model
    it = launcher.HostShardedIterator(base_iterator)
    ParallelWrapper(net, mesh, model_axis="model",
                    shard_update=True, overlap_grads=True).fit(it, ...)
"""

from __future__ import annotations

import inspect
import logging
import os
import socket
import time
from typing import Optional, Sequence

import numpy as np

from ..data.dataset import DataSet, DataSetIterator
from ..runtime import telemetry as _tel

log = logging.getLogger("deeplearning4j_tpu")

_initialized = False
_init_kwargs: Optional[dict] = None

#: bounded coordinator-connect budget (seconds) — an unreachable
#: coordinator must be a clear, *transient-classified* error, never a hang
#: (ISSUE 10 satellite); override per deploy with this env var
TIMEOUT_ENV = "DL4J_TPU_COORDINATOR_TIMEOUT_S"
DEFAULT_TIMEOUT_S = 60.0


def _coordinator_timeout() -> float:
    try:
        return float(os.environ.get(TIMEOUT_ENV, DEFAULT_TIMEOUT_S))
    except ValueError:
        return DEFAULT_TIMEOUT_S


def _check_coordinator_reachable(address: str, timeout: float) -> None:
    """Bounded TCP pre-check of the coordinator address for NON-zero
    processes (process 0 *hosts* the coordinator — it has nothing to
    connect to before ``jax.distributed.initialize`` binds it). Raises
    ``ConnectionError`` — transient in the fault taxonomy
    (``runtime.faults.is_transient``), so a supervisor/retry loop treats a
    not-yet-up or dead coordinator as retryable instead of fatal."""
    host, _, port = address.rpartition(":")
    try:
        port_no = int(port)
    except ValueError:
        # a malformed address must still surface as the documented
        # transient ConnectionError (supervisor retry contract), not a
        # bare int() ValueError
        raise ConnectionError(
            f"JAX coordinator address {address!r} has no usable port "
            "(expected host:port)")
    deadline = time.monotonic() + timeout
    last: Optional[BaseException] = None
    while time.monotonic() < deadline:
        try:
            with socket.create_connection(
                    (host or "127.0.0.1", port_no),
                    timeout=min(2.0, max(0.1, deadline - time.monotonic()))):
                return
        except OSError as e:
            last = e
            time.sleep(min(0.25, max(0.0, deadline - time.monotonic())))
    raise ConnectionError(
        f"JAX coordinator at {address!r} unreachable after {timeout:.1f}s "
        f"(last error: {last}); is process 0 up, and is the address "
        f"routable from this host?")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               timeout: Optional[float] = None) -> None:
    """Bring up the multi-host JAX runtime (idempotent).

    On TPU pods all arguments are auto-detected from the metadata/env by
    ``jax.distributed.initialize``; pass them explicitly for CPU/GPU
    clusters or simulated multi-host tests. Single-process callers may call
    this unconditionally: with no coordinator configured anywhere it is a
    no-op, so the same training script runs 1-host and N-host unchanged.

    Hardening (ISSUE 10): a configured-but-unreachable coordinator raises
    a clear ``ConnectionError`` within ``timeout`` seconds (default
    ``DL4J_TPU_COORDINATOR_TIMEOUT_S`` or 60) instead of hanging — the
    error is *transient* in the fault taxonomy so supervisors retry it.
    On CPU platforms the ``gloo`` cross-process collective implementation
    is selected automatically (without it jax 0.4.x silently builds a
    single-process client and ``process_count()`` stays 1 — the simulated
    pod the tests and bench use would quietly not be a pod).
    """
    global _initialized, _init_kwargs
    if _initialized:
        return
    import jax

    env_addr = os.environ.get("JAX_COORDINATOR_ADDRESS") \
        or os.environ.get("COORDINATOR_ADDRESS")
    if (coordinator_address is None and num_processes is None
            and env_addr is None and not _on_tpu_pod()):
        return  # single-process: nothing to initialize
    timeout = _coordinator_timeout() if timeout is None else float(timeout)
    addr = coordinator_address or env_addr
    env_pid = os.environ.get("JAX_PROCESS_ID") or os.environ.get("PROCESS_ID")
    pid = process_id if process_id is not None else (
        int(env_pid) if env_pid and env_pid.isdigit() else None)
    if addr and pid not in (None, 0):
        # process 0 hosts the coordinator service itself; everyone else
        # gets the bounded pre-check so a dead coordinator is an error,
        # not a silent initialization hang
        _check_coordinator_reachable(addr, timeout)
    # multi-process CPU collectives need gloo (jax 0.4.x): without it the
    # CPU client silently comes up single-process. Set UNCONDITIONALLY —
    # the flag only affects the CPU backend (TPU pods ignore it), and
    # gating on an explicit platform pin would leave the silent failure
    # in place for CPU clusters running on jax's default platform. No
    # jax.devices()/default_backend() probe here: those would instantiate
    # the very backend client distributed init must precede.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # flag absent on this jax version
        pass
    from jax._src import xla_bridge as _xb
    if _xb.backends_are_initialized():
        # a backend client predates us (something already touched jax);
        # distributed init must come first, so tear the client down. Any
        # jax.Array created before this point is invalidated — call
        # initialize() at program start, before building models.
        _clear_backends()
    kw = dict(coordinator_address=coordinator_address,
              num_processes=num_processes,
              process_id=process_id,
              local_device_ids=local_device_ids)
    sig = inspect.signature(jax.distributed.initialize).parameters
    if "initialization_timeout" in sig:
        kw["initialization_timeout"] = max(1, int(timeout))
    jax.distributed.initialize(**kw)
    _initialized = True
    _init_kwargs = kw
    if num_processes is not None and jax.process_count() != num_processes:
        # the pod "formed" but the backend client is not distributed
        # (e.g. a collectives-implementation gap on this backend): without
        # this check the job trains WRONG silently — host-sharded
        # iterators stop sharding, pod meshes collapse to one host
        raise RuntimeError(
            f"distributed init completed but jax.process_count() == "
            f"{jax.process_count()}, expected {num_processes}: the "
            "backend client did not attach to the coordination service "
            "(on CPU this usually means no cross-process collectives "
            "implementation is available)")
    _tel.set_host(jax.process_index(), jax.process_count())


def _clear_backends() -> None:
    """Tear down every live backend client AND the lru-cached process
    topology views. ``xla_bridge.process_count``/``process_index`` are
    ``@lru_cache``'d — if anything touched them before ``jax.distributed``
    came up (importing this package is enough: telemetry probes a device),
    the cached single-process answer SURVIVES ``_clear_backends`` and the
    whole pod trains while believing ``process_count() == 1`` (host-sharded
    iterators stop sharding, pod meshes collapse — observed, not
    hypothetical). Clearing the caches with the clients keeps the topology
    view and the backend in lockstep."""
    import jax
    from jax._src import xla_bridge as _xb
    _xb._clear_backends()
    jax.clear_caches()
    for fn in (getattr(_xb, "process_count", None),
               getattr(_xb, "process_index", None),
               getattr(_xb, "process_indices", None)):
        if fn is not None and hasattr(fn, "cache_clear"):
            fn.cache_clear()


def _on_tpu_pod() -> bool:
    """True when TPU pod env vars indicate a MULTI-host slice (single-host
    TPU VMs also set TPU_WORKER_HOSTNAMES — with one entry)."""
    if "MEGASCALE_COORDINATOR_ADDRESS" in os.environ:
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def shutdown() -> None:
    global _initialized
    if _initialized:
        import jax
        jax.distributed.shutdown()
        _initialized = False
        _tel.set_host(0, 1)


def reinitialize() -> bool:
    """Whole-host-loss recovery hook (fault site ``parallel.host_loss``):
    tear the distributed runtime down and bring it back up with the same
    arguments — every surviving process runs this at the same recovery
    point (SPMD: the injected/real loss surfaces on all of them), the
    backend client is rebuilt, and the coordination barrier inside
    ``jax.distributed.initialize`` re-forms the pod. All live jax.Arrays
    die with the old client, so the caller (``run_resilient_fit``) MUST
    restore model state from a checkpoint afterwards. Returns True when a
    distributed runtime was actually cycled (False = single-process no-op:
    arrays stay live, restore alone suffices)."""
    global _initialized
    if not _initialized or _init_kwargs is None:
        return False
    import jax
    try:
        jax.distributed.shutdown()
    except Exception as e:  # a dead partner can fail the clean shutdown
        log.warning("reinitialize: shutdown failed (%s: %s); proceeding "
                    "to re-init", type(e).__name__, e)
    _initialized = False
    _clear_backends()
    jax.distributed.initialize(**_init_kwargs)
    _initialized = True
    _tel.set_host(jax.process_index(), jax.process_count())
    log.warning("reinitialize: pod re-formed (process %d/%d)",
                jax.process_index(), jax.process_count())
    return True


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def is_multi_host() -> bool:
    return process_count() > 1


def global_mesh(axis: str = "data", devices: Optional[Sequence] = None):
    """Mesh over ALL devices of ALL hosts (the pod-wide data axis)."""
    from .data_parallel import make_mesh

    return make_mesh(devices, axis)


def _group_by_host(devices, hosts: Optional[int] = None):
    """``[[host0 devices...], [host1 devices...], ...]`` in process order,
    each inner list in local (ICI-adjacent) order. ``hosts=`` overrides
    the process grouping with equal contiguous blocks — the single-process
    simulation knob (virtual hosts on one process's virtual devices)."""
    if hosts is not None and hosts >= 1:
        if len(devices) % hosts:
            raise ValueError(f"{len(devices)} devices do not split into "
                             f"{hosts} equal virtual hosts")
        per = len(devices) // hosts
        return [list(devices[h * per:(h + 1) * per]) for h in range(hosts)]
    by_host: dict = {}
    for d in devices:
        by_host.setdefault(getattr(d, "process_index", 0), []).append(d)
    return [by_host[p] for p in sorted(by_host)]


def pod_mesh(model: int = 1, devices: Optional[Sequence] = None,
             hosts: Optional[int] = None, model_span: str = "host"):
    """2-D DCN-aware ``('data', 'model')`` multi-host mesh (ISSUE 10).

    Placement rule: the **model** (tensor-parallel) axis is laid over
    consecutive devices *within one host* — those are ICI-adjacent, and
    the per-layer TP collectives that ride the model axis every
    microsecond must never cross the slow DCN hop — while the **data**
    axis runs host-major across the pod (host h occupies the contiguous
    block ``[h*local, (h+1)*local)`` of the data axis). XLA's collective
    decomposition then splits the data-axis gradient collectives into an
    intra-host ICI stage and a cross-host DCN stage (the mesh ordering is
    what makes that decomposition legal — a data axis that interleaved
    hosts would force every hop onto DCN); ``parallel/overlap.py`` makes
    the same hierarchy explicit per gradient bucket.

    ``model`` must divide every host's local device count (a model axis
    spilling across hosts would put layer collectives on DCN — rejected,
    not silently accepted). ``model=1`` returns a 1-axis ``('data',)``
    mesh. ``hosts=`` carves one process's devices into that many virtual
    hosts (simulation/testing; on a real pod leave it None — process
    membership decides). Works unchanged through ``ParallelWrapper``:
    batch shards over ``'data'``, ``model_axis="model"`` composes, and
    ``shard_update``/``overlap_grads`` ride the data axis.

    ``model_span="pod"`` (ISSUE 17) lifts the one-host restriction: the
    model axis is laid host-major over the whole pod, so a model whose
    shards cannot fit one host's HBM still serves as a SINGLE sharded
    replica. The per-layer TP collectives then ride DCN — the documented
    tradeoff for pod serving, where "exists at all" beats "ICI-fast" and
    decode steps are latency-tolerant relative to a training step.
    ``model`` must divide the total device count; requires
    ``model_span`` in ``("host", "pod")``.
    """
    import jax
    from jax.sharding import Mesh

    if model_span not in ("host", "pod"):
        raise ValueError(
            f"model_span={model_span!r} not in ('host', 'pod')")
    devs = list(devices) if devices is not None else jax.devices()
    groups = _group_by_host(devs, hosts)
    locals_ = {len(g) for g in groups}
    if len(locals_) != 1:
        raise ValueError(
            f"ragged pod: per-host device counts differ "
            f"({sorted(len(g) for g in groups)}); a mesh needs equal hosts")
    local = locals_.pop()
    if model_span == "pod":
        total = len(groups) * local
        if model < 1 or total % model:
            raise ValueError(
                f"model={model} must divide the pod device count {total} "
                "when model_span='pod'")
        flat = [d for g in groups for d in g]
        data = total // model
        arr = np.empty((data, model), dtype=object)
        for row in range(data):
            arr[row, :] = flat[row * model:(row + 1) * model]
        if model == 1:
            return Mesh(arr[:, 0], ("data",))
        return Mesh(arr, ("data", "model"))
    if model < 1 or local % model:
        raise ValueError(
            f"model={model} must divide the per-host device count {local}: "
            "the model axis must stay inside one host (ICI-adjacent) — "
            "tensor-parallel collectives on the DCN hop would dominate the "
            "step (serve a too-big-for-one-host model with "
            "model_span='pod')")
    data = len(groups) * (local // model)
    arr = np.empty((data, model), dtype=object)
    row = 0
    for g in groups:
        for i in range(local // model):
            arr[row, :] = g[i * model:(i + 1) * model]
            row += 1
    if model == 1:
        return Mesh(arr[:, 0], ("data",))
    return Mesh(arr, ("data", "model"))


def make_global_array(local_data, mesh, spec):
    """Assemble a global jax.Array from this host's shard of the data.

    ``spec=P('data')`` treats ``local_data`` as this host's contiguous slice
    of the global batch (global batch = per-host batch x process_count);
    ``spec=P()`` treats it as a fully-replicated value (must be identical on
    every host). Single-host this degrades to a plain device_put.
    """
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    arr = np.asarray(local_data)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)


class HostShardedIterator(DataSetIterator):
    """Each host sees its contiguous 1/N slice of every global batch.

    The multi-host analog of Spark's per-executor partitions: the base
    iterator is assumed identical on every host (same seed → same shuffle
    permutation, guaranteed by NumpyDataSetIterator's (seed, epoch) perms),
    and host ``p`` takes rows ``[p*k, (p+1)*k)`` of each batch. Combined with
    :func:`make_global_array` / ParallelWrapper, the slices re-assemble into
    the global batch in host order. The restorable cursor delegates to the
    base, so checkpoint/resume works unchanged.
    """

    def __init__(self, base: DataSetIterator,
                 process_id: Optional[int] = None,
                 num_processes: Optional[int] = None):
        self._base = base
        self._pid = process_index() if process_id is None else process_id
        self._n = process_count() if num_processes is None else num_processes

    def batch_size(self) -> int:
        return max(1, self._base.batch_size() // self._n)

    def reset(self):
        self._base.reset()

    def state(self) -> dict:
        return self._base.state()

    def set_state(self, state: dict):
        self._base.set_state(state)

    def _slice(self, a, lo, hi):
        return None if a is None else a[lo:hi]

    def __iter__(self):
        from .data_parallel import _synth_pad_feature_mask
        for ds in self._base:
            b = ds.num_examples()
            # pad the global batch to a per-host-equal size; the extra rows
            # land on the tail hosts and are masked out of the loss
            k = (b + self._n - 1) // self._n
            ragged = k * self._n != b
            lo, hi = min(self._pid * k, b), min((self._pid + 1) * k, b)
            feats = ds.features[lo:hi]
            labels = self._slice(ds.labels, lo, hi)
            fm = self._slice(ds.features_mask, lo, hi)
            lm = self._slice(ds.labels_mask, lo, hi)
            short = k - feats.shape[0]
            if short:
                def zpad(a):
                    if a is None:
                        return None
                    return np.pad(a, [(0, short)] + [(0, 0)] * (a.ndim - 1))
                feats, labels, fm, lm = (zpad(feats), zpad(labels),
                                         zpad(fm), zpad(lm))
            if ragged:
                # EVERY host must synthesize the masks, not just the short
                # ones: hosts are SPMD — if some passed None and others an
                # array, the per-host programs (and their collectives) would
                # diverge and the step would hang at the first AllReduce.
                if lm is None:
                    # zero LOSS weight on the zero-padded rows: losses
                    # average over the unmasked count (the r6 weighted-
                    # microbatch rule, ops/losses._per_example), so the
                    # global multi-host step divides by the REAL example
                    # count and stays bit-comparable to single-host
                    lm = np.ones((k,), dtype=np.float32)
                    if short:
                        lm[-short:] = 0.0
                if fm is None:
                    # pad FEATURE mask too (same rule as the wrapper's
                    # _pad_and_mask): mask-aware layers — train-mode
                    # BatchNorm batch moments — must exclude the padded
                    # rows, or multi-host running stats drift from the
                    # single-host run even though the loss matches
                    fm = _synth_pad_feature_mask(feats, short)
            yield self._pp(DataSet(feats, labels, fm, lm))
