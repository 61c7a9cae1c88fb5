"""Model-fleet chaos drills (ISSUE 20): versioned registry routing,
checkpoint-watch hot-swap, SLO-gated canary with automatic rollback —
under deliberate abuse via the ``fleet.load`` / ``fleet.swap`` /
``fleet.canary`` fault sites (the zz coverage floor requires all three
to fire in this file) and under concurrent open-loop traffic.

The acceptance drill invariants, asserted throughout:
- no request is ever dropped without a TYPED error
  (QueueFull/DeadlineExceeded/ShutdownError/FleetError),
- a failed swap/load/canary leaves the incumbent serving BIT-IDENTICAL
  outputs — never a window with no servable model,
- every rollback produces a flight-recorder dump naming the candidate,
- the live serving path records ZERO post-warmup compile events across
  background loads, warmups, flips and rollbacks.
"""

import itertools
import json
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.parallel.checkpoint import TrainingCheckpointer
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.runtime import telemetry as tel
from deeplearning4j_tpu.runtime.faults import QueueFull
from deeplearning4j_tpu.serving import fleet as fleet_mod
from deeplearning4j_tpu.serving import (CanaryGate, CheckpointWatcher,
                                        FleetError, HealthState,
                                        JsonModelServer, ModelRegistry,
                                        ModelVersion)

TYPED = (QueueFull, faults.DeadlineExceeded, faults.ShutdownError,
         FleetError)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _mlp(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .input_type(InputType.feed_forward(6))
            .list(DenseLayer(n_out=12, activation="tanh"),
                  OutputLayer(n_out=3))
            .build())
    return MultiLayerNetwork(conf).init()


V = 16


def _lm(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=V, n_heads=2),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _x(n=3, seed=0):
    return np.random.RandomState(seed).randn(n, 6).astype(np.float32)


FK = {"max_batch_size": 4, "max_wait_ms": 1.0}


def _registry_with_live(name="m", seed=0, quota=None, **kw):
    reg = ModelRegistry(**kw)
    reg.add_version(name, 1, _mlp(seed), front_kwargs=dict(FK),
                    quota=quota)
    reg.set_live(name, 1)
    return reg


class _OpenLoop:
    """Concurrent open-loop traffic against one fleet model: every
    submitted request either resolves or fails with a TYPED error —
    anything else is an untyped drop, the drill's cardinal sin."""

    def __init__(self, reg, name="m", threads=3):
        self.reg, self.name = reg, name
        self.sent = 0
        self.untyped = []
        self.typed = 0
        self.outputs = []
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._run, args=(i,),
                                          daemon=True)
                         for i in range(threads)]

    def _run(self, i):
        x = _x(seed=i)
        while not self._stop.is_set():
            try:
                out = np.asarray(self.reg.output(self.name, x))
                with self._lock:
                    self.outputs.append((i, out))
            except TYPED:
                with self._lock:
                    self.typed += 1
            except Exception as e:  # noqa: BLE001 - the drill assertion
                with self._lock:
                    self.untyped.append(e)
            with self._lock:
                self.sent += 1
            time.sleep(0.002)

    def __enter__(self):
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=30)


# ----------------------------------------------------------- registry core
def test_registry_routes_by_model_and_pins_version():
    reg = ModelRegistry()
    reg.add_version("a", 1, _mlp(0), front_kwargs=dict(FK))
    reg.add_version("b", 1, _mlp(1), front_kwargs=dict(FK))
    reg.set_live("a", 1)
    reg.set_live("b", 1)
    try:
        x = _x()
        ya = np.asarray(reg.output("a", x))
        yb = np.asarray(reg.output("b", x))
        assert ya.shape == yb.shape == (3, 3)
        assert not np.array_equal(ya, yb)  # different models, one front
        # version pinning routes to the named version even mid-canary
        assert np.array_equal(
            np.asarray(reg.output("a", x, version=1)), ya)
        with pytest.raises(FleetError):
            reg.submit("nope", x)
        with pytest.raises(FleetError):
            reg.submit("a", x, version=9)
        # per-version telemetry cells carry model=/version=/pool=
        routed = tel.registry.get("serving.fleet.routed")
        keys = set(routed.series())
        assert any(dict(k).get("model") == "a" and
                   dict(k).get("version") == "1" and
                   "pool" in dict(k) for k in keys)
    finally:
        reg.shutdown()


def test_atomic_flip_under_open_loop_traffic():
    """The zero-downtime core: background-build v2, atomic flip, retire
    v1 — under concurrent traffic, with zero untyped drops and zero
    post-warmup compiles on either serving path."""
    reg = _registry_with_live()
    try:
        with _OpenLoop(reg) as load:
            time.sleep(0.15)
            # background load + warmup (the watcher's thread in prod)
            reg.add_version("m", 2, _mlp(7), front_kwargs=dict(FK))
            v1, v2 = reg.version("m", 1), reg.version("m", 2)
            assert v1.post_warmup_compiles == 0  # warm-up off-path
            reg.set_live("m", 2)
            time.sleep(0.15)
        assert not load.untyped, f"untyped drops: {load.untyped!r}"
        assert load.sent > 20
        assert v1.state == ModelVersion.RETIRED
        assert v2.state == ModelVersion.LIVE
        assert v2.post_warmup_compiles == 0
        assert reg.stats()["swaps"] == 2  # initial set_live + the flip
        # retirement dropped v1's executables
        assert v1.front.engine.stats()["compiled_buckets"] == 0
    finally:
        reg.shutdown()


def test_per_model_quota_feeds_shed_health():
    """Quota rejections are typed (QueueFull), counted, and flip ONLY
    the owning model's health to SHEDDING — the sibling model stays
    HEALTHY in the same registry."""
    reg = _registry_with_live("q", quota=0)
    reg.add_version("ok", 1, _mlp(3), front_kwargs=dict(FK))
    reg.set_live("ok", 1)
    try:
        with pytest.raises(QueueFull):
            reg.submit("q", _x())
        hz = reg.healthz()
        assert hz["models"]["q"]["health"] == HealthState.SHEDDING
        assert hz["models"]["ok"]["health"] == HealthState.HEALTHY
        assert hz["status"] == HealthState.SHEDDING  # worst-of live
        q = tel.registry.get("serving.fleet.quota_shed")
        assert q.total() >= 1
        # the sibling still serves
        assert np.asarray(reg.output("ok", _x())).shape == (3, 3)
    finally:
        reg.shutdown()


# ------------------------------------------------------------- HTTP front
def test_server_fleet_routing_and_per_model_healthz():
    """One JsonModelServer front-ends two models; routing by X-Model
    (+X-Model-Version pin), 404 on unknown names, and the ISSUE 20
    healthz bugfix: a SHEDDING canary does NOT 503 the front while the
    incumbent is HEALTHY — its state rides the per-model breakdown."""
    reg = ModelRegistry()
    reg.add_version("a", 1, _mlp(0), front_kwargs=dict(FK))
    reg.add_version("b", 1, _mlp(1), front_kwargs=dict(FK))
    reg.set_live("a", 1)
    reg.set_live("b", 1)
    srv = JsonModelServer(fleet=reg)
    port = srv.start()
    base = f"http://127.0.0.1:{port}"

    def post(path, body, headers=None):
        req = urllib.request.Request(
            base + path, json.dumps(body).encode(),
            {"Content-Type": "application/json", **(headers or {})})
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())

    try:
        x = _x().tolist()
        code, out = post("/predict", {"data": x}, {"X-Model": "a"})
        assert code == 200 and out["version"] == 1
        ya = np.asarray(out["output"])
        _, outb = post("/predict", {"data": x}, {"X-Model": "b"})
        assert not np.array_equal(ya, np.asarray(outb["output"]))
        code, out = post("/predict", {"data": x},
                         {"X-Model": "a", "X-Model-Version": "1"})
        assert code == 200
        # multi-model fleet: a request with no X-Model is a routing error
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/predict", {"data": x})
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/predict", {"data": x}, {"X-Model": "zz"})
        assert ei.value.code == 404
        # canary for "a" starts SHEDDING; the front must NOT go 503
        reg.add_version("a", 2, _mlp(9), front_kwargs=dict(FK))
        reg.start_canary("a", 2, CanaryGate(fraction=0.01, min_samples=4))
        reg.version("a", 2).front.note_shed()
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            hz = json.loads(r.read())
            assert r.status == 200
        assert hz["status"] == HealthState.HEALTHY
        assert hz["models"]["a"]["canary"]["health"] == \
            HealthState.SHEDDING
        assert hz["models"]["a"]["health"] == HealthState.HEALTHY
        # /stats exposes the fleet view
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            st = json.loads(r.read())
        assert set(st["models"]) == {"a", "b"}
        assert st["models"]["a"]["canary_version"] == 2
    finally:
        srv.stop()
        reg.shutdown()


# ----------------------------------------------------- checkpoint watcher
def test_watch_loop_hot_swaps_verified_checkpoint(tmp_path):
    """The hot-swap recipe end to end: a new manifest-verified step in
    the checkpoint directory deploys via background load+warm+flip; the
    incumbent records zero post-warmup compiles throughout; outputs
    after the flip are the restored model's."""
    ck = TrainingCheckpointer(str(tmp_path / "ckpt"), max_to_keep=4)
    net1 = _mlp(0)
    ck.save(net1, step=1, wait=True)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", ck, _mlp, front_kwargs=dict(FK),
                          interval_s=0.05)
    try:
        rep = w.poll()
        assert rep == {"step": 1, "decision": "flipped", "version": 1}
        x = _x()
        y1 = np.asarray(reg.output("m", x))
        np.testing.assert_allclose(y1, np.asarray(net1.output(x)),
                                   atol=1e-6)
        # train drift -> a new checkpoint; the daemon loop picks it up
        net2 = _mlp(1)  # different init == visibly different outputs
        ck.save(net2, step=2, wait=True)
        v1 = reg.version("m", 1)
        w.start()
        deadline = time.time() + 60
        while w.deployed_step != 2 and time.time() < deadline:
            time.sleep(0.05)
        assert w.deployed_step == 2
        assert v1.post_warmup_compiles == 0  # load+warm never touched it
        y2 = np.asarray(reg.output("m", x))
        assert not np.array_equal(y1, y2)
        np.testing.assert_allclose(y2, np.asarray(net2.output(x)),
                                   atol=1e-6)
        assert reg.version("m", 2).post_warmup_compiles == 0
    finally:
        w.stop()
        reg.shutdown()


def test_torn_checkpoint_skipped_loudly_then_recovers(tmp_path):
    """A torn write under the watch loop: the step is ineligible, the
    skip is counted (swap_events{event=skipped_torn}) and logged, the
    incumbent keeps serving bit-identically — and a later GOOD step
    still deploys."""
    ck = TrainingCheckpointer(str(tmp_path / "ckpt"), max_to_keep=4)
    ck.save(_mlp(0), step=1, wait=True)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", ck, _mlp, front_kwargs=dict(FK))
    try:
        w.poll()
        x = _x()
        y1 = np.asarray(reg.output("m", x))
        swap = tel.registry.get("serving.fleet.swap_events")
        torn0 = sum(v for k, v in swap.series().items()
                    if dict(k).get("event") == "skipped_torn")
        faults.inject("checkpoint.write", times=1)
        ck.save(_mlp(0), step=2, wait=True)
        faults.reset()
        assert w.poll() is None  # torn step 2: nothing deployable
        torn1 = sum(v for k, v in swap.series().items()
                    if dict(k).get("event") == "skipped_torn")
        assert torn1 == torn0 + 1
        assert reg.stats()["models"]["m"]["live_version"] == 1
        assert np.array_equal(np.asarray(reg.output("m", x)), y1)
        ck.save(_mlp(0), step=3, wait=True)
        rep = w.poll()
        assert rep["decision"] == "flipped" and rep["step"] == 3
        # the torn skip is loud ONCE, not re-counted every poll
        assert w.poll() is None
        torn2 = sum(v for k, v in swap.series().items()
                    if dict(k).get("event") == "skipped_torn")
        assert torn2 == torn1
    finally:
        reg.shutdown()


def test_fleet_load_transient_retries_then_lands(tmp_path):
    """``fleet.load`` mid-background-warmup, transient kind: the watcher
    retries with backoff and the swap still lands (load_retry counted)."""
    ck = TrainingCheckpointer(str(tmp_path / "ckpt"), max_to_keep=4)
    ck.save(_mlp(0), step=1, wait=True)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", ck, _mlp, front_kwargs=dict(FK),
                          load_retries=3, backoff_s=0.01)
    try:
        faults.inject("fleet.load", error="crash", times=2)
        rep = w.poll()
        assert rep["decision"] == "flipped"
        swap = tel.registry.get("serving.fleet.swap_events")
        retries = sum(v for k, v in swap.series().items()
                      if dict(k).get("event") == "load_retry")
        assert retries >= 2
    finally:
        reg.shutdown()


def test_fleet_load_exhaustion_leaves_incumbent_serving(tmp_path):
    """``fleet.load`` beyond the retry budget: the step is marked failed
    LOUDLY (load_failed + flight dump), the incumbent serves
    bit-identically, and the watcher does not retry the poisoned step
    forever."""
    ck = TrainingCheckpointer(str(tmp_path / "ckpt"), max_to_keep=4)
    ck.save(_mlp(0), step=1, wait=True)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", ck, _mlp, front_kwargs=dict(FK),
                          load_retries=1, backoff_s=0.01)
    tel.flight.configure(dir=str(tmp_path / "dumps"))
    try:
        w.poll()
        x = _x()
        y1 = np.asarray(reg.output("m", x))
        ck.save(_mlp(0), step=2, wait=True)
        faults.inject("fleet.load", error="crash", times=float("inf"))
        rep = w.poll()
        assert rep == {"step": 2, "decision": "load_failed"}
        faults.reset()
        assert reg.stats()["models"]["m"]["live_version"] == 1
        assert np.array_equal(np.asarray(reg.output("m", x)), y1)
        assert w.poll() is None  # failed step not retried in a loop
        dump = tel.flight.last_dump
        assert dump and dump["reason"] == "fleet.load:m@step2"
        assert any(r.get("type") == "fleet_load_failed"
                   and r.get("checkpoint_step") == 2
                   for r in dump["events"])
    finally:
        tel.flight.configure(dir=None)
        reg.shutdown()


def test_fleet_swap_failure_at_flip_point_rolls_back():
    """``fleet.swap`` at the flip: the candidate is FAILED, the OLD
    version keeps serving bit-identically (never a window with no
    servable model), and the failure produced a flight dump naming the
    candidate."""
    reg = _registry_with_live()
    try:
        x = _x()
        y1 = np.asarray(reg.output("m", x))
        reg.add_version("m", 2, _mlp(5), front_kwargs=dict(FK))
        faults.inject("fleet.swap", error="crash", times=1)
        with pytest.raises(faults.InjectedCrash):
            reg.set_live("m", 2)
        faults.reset()
        assert reg.stats()["models"]["m"]["live_version"] == 1
        assert reg.version("m", 2).state == ModelVersion.FAILED
        assert np.array_equal(np.asarray(reg.output("m", x)), y1)
        # a FAILED version is not pin-routable
        with pytest.raises(FleetError):
            reg.submit("m", x, version=2)
        dump = tel.flight.last_dump
        assert dump and dump["reason"] == "fleet.swap:m@v2"
        assert any(r.get("type") == "fleet_swap_failed"
                   and r.get("candidate_version") == 2
                   for r in dump["events"])
        swap = tel.registry.get("serving.fleet.swap_events")
        assert sum(v for k, v in swap.series().items()
                   if dict(k).get("event") == "swap_failed") >= 1
    finally:
        reg.shutdown()


# ------------------------------------------------------------------ canary
def _drive(reg, name="m", n=30, seed=0):
    x = _x(seed=seed)
    for _ in range(n):
        reg.output(name, x)
    time.sleep(0.15)  # done-callbacks record latency/outcomes async


def test_canary_promotes_on_all_gates_green(monkeypatch):
    # The p99 gate reads wall time, and a p99 over ~15 requests an arm is
    # the worst wake-up the host gave that arm: two identical versions on a
    # shared host differ by more than the gate's 1.25. Here the fleet's
    # clock ticks one millisecond a reading and each request's done-callback
    # has run before the next is submitted, so both arms measure the same
    # latency and the default gate is held to it.
    ticks = itertools.count()
    monkeypatch.setattr(fleet_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ticks) * 1e-3,
        monotonic=time.monotonic, sleep=time.sleep))

    def drive(n=30):
        x = _x()
        for _ in range(n):
            recorded = threading.Event()
            # callbacks run in the order added: the fleet's own came first
            reg.submit("m", x).add_done_callback(lambda f: recorded.set())
            assert recorded.wait(30)

    reg = _registry_with_live(seed=0)
    try:
        reg.add_version("m", 2, _mlp(0), front_kwargs=dict(FK))
        reg.start_canary("m", 2, CanaryGate(
            fraction=0.5, window_s=30, min_samples=8, promote_after=2))
        drive()
        r1 = reg.evaluate_canary("m")
        assert r1["decision"] == "green", r1
        assert r1["gates"]["p99_ratio"] is True, r1
        drive()
        r2 = reg.evaluate_canary("m")
        assert r2["decision"] == "promoted", r2
        assert reg.stats()["models"]["m"]["live_version"] == 2
        assert reg.version("m", 1).state == ModelVersion.RETIRED
        can = tel.registry.get("serving.fleet.canary_events")
        events = {dict(k).get("event") for k in can.series()}
        assert {"started", "green", "promoted"} <= events
    finally:
        reg.shutdown()


def test_canary_slow_candidate_rolls_back_on_p99():
    """The same network behind a front that holds every request 80 ms
    (against 1 ms) is a latency regression many times the default gate:
    the p99 gate alone is red and the candidate is rolled back."""
    # its own model name: flight dumps are rate-limited per reason, and
    # the next test reads the dump of a rollback of "m"
    reg = _registry_with_live(name="slow", seed=0)
    try:
        reg.add_version("slow", 2, _mlp(0), front_kwargs={
            "max_batch_size": 4, "max_wait_ms": 80.0})
        reg.start_canary("slow", 2, CanaryGate(
            fraction=0.5, window_s=30, min_samples=8))
        _drive(reg, name="slow")
        rep = reg.evaluate_canary("slow")
        assert rep["decision"] == "rolled_back", rep
        assert rep["gates"] == {"error_delta": True, "p99_ratio": False}
        assert reg.stats()["models"]["slow"]["live_version"] == 1
        assert reg.version("slow", 2).state == ModelVersion.ROLLED_BACK
    finally:
        reg.shutdown()


def test_canary_trip_rolls_back_within_one_window(tmp_path):
    """``fleet.canary`` (a forced trip — NOT an error): the very next
    evaluation rolls back, the incumbent was never demoted, and the
    flight dump attributes the rollback to the candidate version with
    its recent trace ids."""
    reg = _registry_with_live()
    tel.flight.configure(dir=str(tmp_path))
    try:
        x = _x()
        y1 = np.asarray(reg.output("m", x))
        reg.add_version("m", 2, _mlp(5), front_kwargs=dict(FK))
        reg.start_canary("m", 2, CanaryGate(fraction=0.5, min_samples=4,
                                            window_s=30))
        _drive(reg, n=20)
        faults.inject("fleet.canary", times=1)
        rep = reg.evaluate_canary("m")   # ONE evaluation window
        assert rep["decision"] == "rolled_back"
        assert rep["gates"]["injected"] is False
        assert reg.stats()["models"]["m"]["live_version"] == 1
        assert reg.version("m", 2).state == ModelVersion.ROLLED_BACK
        assert np.array_equal(np.asarray(reg.output("m", x)), y1)
        dump = tel.flight.last_dump
        assert dump and dump["reason"] == "fleet.canary:m@v2"
        rb = [r for r in dump["events"]
              if r.get("type") == "canary_rollback"]
        assert rb and rb[0]["candidate_version"] == 2
        assert rb[0]["candidate_traces"], \
            "rollback dump must carry the candidate's trace ids"
        assert reg.stats()["rollbacks"] == 1
    finally:
        tel.flight.configure(dir=None)
        reg.shutdown()


def test_canary_genuine_accuracy_regression_rolls_back():
    """No injection: a candidate whose probe accuracy is worse than the
    incumbent's beyond max_accuracy_drop trips the gate on its own."""
    reg = _registry_with_live()
    try:
        reg.add_version("m", 2, _mlp(5), front_kwargs=dict(FK))

        def probe(mv):
            return 0.95 if mv.version == 1 else 0.60

        reg.start_canary("m", 2, CanaryGate(
            fraction=0.5, min_samples=4, window_s=30,
            max_accuracy_drop=0.05, probe=probe))
        _drive(reg, n=20)
        rep = reg.evaluate_canary("m")
        assert rep["decision"] == "rolled_back"
        assert rep["gates"]["accuracy_delta"] is False
        assert reg.stats()["models"]["m"]["live_version"] == 1
    finally:
        reg.shutdown()


# ------------------------------------------------------------- generative
def test_generative_fleet_version_routes_and_swaps():
    """The registry wraps the generative flavor too: a ContinuousBatcher
    front behind the same routing/flip machinery, with TTFT/TPOT p99
    surfaces for the canary gate."""
    reg = ModelRegistry()
    reg.add_version("lm", 1, _lm(0), kind="generative",
                    front_kwargs={"slots": 2, "max_cache_len": 16,
                                  "min_cache_len": 16,
                                  "max_new_tokens": 4})
    reg.set_live("lm", 1)
    try:
        rng = np.random.default_rng(3)
        hs = [reg.submit_generate(
            "lm", tokens=list(rng.integers(0, V, 3)), max_new_tokens=3)
            for _ in range(4)]
        for h in hs:
            assert len(h.result(timeout=120)["tokens"]) >= 3
        time.sleep(0.1)
        mv = reg.version("lm", 1)
        assert mv.post_warmup_compiles == 0
        assert mv.ttft_p99() is not None
        # one-shot submit on a generative version is a typed error
        with pytest.raises(FleetError):
            reg.submit("lm", _x())
    finally:
        reg.shutdown()


# ------------------------------------------------------------ chaos drill
def test_chaos_drill_all_fleet_sites_under_load(tmp_path):
    """THE acceptance drill: faults injected at every ``fleet.*`` site
    during swaps-under-load (plus a torn checkpoint), with concurrent
    open-loop traffic. Zero untyped drops, the incumbent's outputs stay
    bit-identical across every failed swap, the tripped canary rolls
    back within one evaluation window with a dump naming the candidate,
    and the serving path records zero post-warmup compiles throughout."""
    ck = TrainingCheckpointer(str(tmp_path / "ckpt"), max_to_keep=8)
    ck.save(_mlp(0), step=1, wait=True)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", ck, _mlp, front_kwargs=dict(FK),
                          load_retries=1, backoff_s=0.01)
    tel.flight.configure(dir=str(tmp_path / "dumps"))
    try:
        assert w.poll()["decision"] == "flipped"
        incumbent = reg.version("m", 1)
        x = _x()
        y_ref = np.asarray(reg.output("m", x))
        with _OpenLoop(reg) as load:
            # -- drill 1: fleet.load exhausted mid-background-warmup --
            ck.save(_mlp(0), step=2, wait=True)
            faults.inject("fleet.load", error="crash",
                          times=float("inf"))
            assert w.poll()["decision"] == "load_failed"
            faults.reset()
            assert np.array_equal(np.asarray(reg.output("m", x)), y_ref)
            # -- drill 2: torn checkpoint under the watch loop --
            faults.inject("checkpoint.write", times=1)
            ck.save(_mlp(0), step=3, wait=True)
            faults.reset()
            assert w.poll() is None
            assert np.array_equal(np.asarray(reg.output("m", x)), y_ref)
            # -- drill 3: fleet.swap at the flip point --
            ck.save(_mlp(0), step=4, wait=True)
            faults.inject("fleet.swap", error="crash", times=1)
            assert w.poll()["decision"] == "swap_failed"
            faults.reset()
            swap_dump = tel.flight.last_dump
            assert np.array_equal(np.asarray(reg.output("m", x)), y_ref)
            # -- drill 4: canary trip -> rollback in ONE window --
            ck.save(_mlp(0), step=5, wait=True)
            w.gate = CanaryGate(fraction=0.3, min_samples=2, window_s=30)
            rep = w.poll()
            assert rep["decision"] == "canary_started"
            cand_v = rep["version"]
            faults.inject("fleet.canary", times=1)
            rep = w.poll()  # one watch iteration == one evaluation
            assert rep["decision"] == "rolled_back"
            faults.reset()
            time.sleep(0.1)
        # -- the drill invariants --
        assert not load.untyped, f"untyped drops: {load.untyped!r}"
        assert load.sent > 30
        assert reg.stats()["models"]["m"]["live_version"] == 1
        assert np.array_equal(np.asarray(reg.output("m", x)), y_ref)
        assert incumbent.post_warmup_compiles == 0
        # every failure produced its attributable dump
        assert swap_dump["reason"].startswith("fleet.swap:m@")
        dump = tel.flight.last_dump
        assert dump["reason"] == f"fleet.canary:m@v{cand_v}"
        assert reg.stats()["rollbacks"] == 1
        # all three fleet sites fired (feeds the zz coverage floor)
        fired = set(faults.coverage_report()["fired"])
        assert {"fleet.load", "fleet.swap", "fleet.canary"} <= fired
    finally:
        tel.flight.configure(dir=None)
        w.stop()
        reg.shutdown()
