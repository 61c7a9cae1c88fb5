"""Fault-tolerance suite (ISSUE 5): divergence sentinel, crash-safe
checkpoints, auto-resume, serving degradation — every recovery path
exercised deterministically on CPU through runtime/faults.py injections
(fixed seeds; the zz coverage floor asserts every registered fault site
fires somewhere in this file)."""

import json
import threading
import time
import urllib.request
import urllib.error

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import (AsyncDataSetIterator,
                                             NumpyDataSetIterator)
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel.checkpoint import TrainingCheckpointer
from deeplearning4j_tpu.parallel.resilience import ResiliencePolicy
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.runtime.faults import (CorruptCheckpoint,
                                               DeadlineExceeded,
                                               DivergenceError, InjectedCrash,
                                               QueueFull, ShutdownError)
from deeplearning4j_tpu.serving.batcher import (HealthState, InferenceMode,
                                                ParallelInference)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    faults.telemetry_reset()
    yield
    faults.reset()


def _conf(updater=None, **kw):
    return (NeuralNetConfiguration.builder().seed(7)
            .updater(updater or Adam(learning_rate=1e-2))
            .input_type(InputType.feed_forward(4))
            .list(DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    # learnable labels (a function of the features), so convergence
    # assertions measure training progress, not memorization of noise
    lab = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)
    y = np.eye(3, dtype=np.float32)[lab]
    return x, y


def _iter(n=64, bs=16, seed=5):
    x, y = _data(n)
    return NumpyDataSetIterator(x, y, batch_size=bs, shuffle=True, seed=seed)


# ---------------------------------------------------------------- registry
def test_injection_counting_after_times():
    inj = faults.inject("train.step", after=2, times=2)
    fired = [faults.trip("train.step") is not None for _ in range(6)]
    assert fired == [False, False, True, True, False, False]
    assert inj.calls == 6 and inj.fired == 2
    c = faults.counters()["train.step"]
    assert c["calls"] == 6 and c["fired"] == 2


def test_injection_error_kinds_and_unknown_site():
    faults.inject("train.step", error="crash")
    with pytest.raises(InjectedCrash):
        faults.trip("train.step")
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.inject("no.such.site")
    with pytest.raises(ValueError, match="unregistered fault site"):
        faults.trip("no.such.site")


def test_env_config(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FAULTS",
                       "train.step:error=crash:after=1, serving.slow:delay=0")
    assert faults.configure_from_env() == 2
    assert faults.trip("train.step") is None  # after=1: first call clean
    with pytest.raises(InjectedCrash):
        faults.trip("train.step")


def test_transient_matcher():
    assert faults.is_transient(InjectedCrash("x"))
    assert faults.is_transient(OSError("disk gone"))
    assert not faults.is_transient(ValueError("bug"))


# ---------------------------------------------------------------- sentinel
def test_sentinel_skips_nonfinite_and_training_converges():
    """Acceptance (a): injected non-finite gradient -> step skipped,
    counter incremented, training continues and converges."""
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    faults.inject("train.nonfinite", after=3, times=2)
    net.fit(it, epochs=6)
    c = net.resilience_counters()
    assert c["bad_total"] == 2 and c["bad_consec"] == 0
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(net.params))
    assert net.score() < 1.0  # converged past the initial ~log(3)=1.1
    assert net.iteration == 24  # no step lost, only skipped


def test_sentinel_skip_is_exact_noop_on_state():
    """A skipped step leaves params, updater state and step count values
    unchanged (the NaN batch leaves no trace)."""
    net = MultiLayerNetwork(_conf()).init()
    net.fit(_iter(), epochs=1)
    p0 = jax.tree.map(np.asarray, net.params)
    o0 = jax.tree.map(np.asarray, net.updater_state)
    faults.inject("train.nonfinite", times=1)
    net.fit(NumpyDataSetIterator(*_data(16), batch_size=16), epochs=1)
    assert net.resilience_counters()["bad_total"] == 1
    jax.tree.map(np.testing.assert_array_equal, net.params, p0)
    jax.tree.map(np.testing.assert_array_equal, net.updater_state, o0)


def test_sentinel_graph_engine():
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Sgd(learning_rate=0.05)).graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("h", DenseLayer(n_out=8, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "h")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    faults.inject("train.nonfinite", after=1, times=1)
    net.fit(_iter(), epochs=1)
    assert net.resilience_counters()["bad_total"] == 1
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(net.params))


def test_sentinel_samediff():
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    rng = np.random.default_rng(0)
    xv = rng.normal(size=(32, 2)).astype(np.float32)
    yv = (xv @ np.array([[2.0], [-3.0]], np.float32)) + 0.5
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    t = sd.placeholder("t", (None, 1))
    w = sd.var("w", np.zeros((2, 1), np.float32))
    b = sd.var("b", np.zeros((1,), np.float32))
    sd.set_loss((((x.mmul(w) + b) - t) ** 2.0).mean())
    sd.set_updater(Sgd(learning_rate=0.1))
    faults.inject("train.nonfinite", after=2, times=2)
    sd.fit([{"x": xv, "t": yv}], epochs=8)
    assert sd.resilience_counters()["bad_total"] == 2
    assert np.all(np.isfinite(sd.get_value("w")))


def test_sentinel_zero_retrace_and_no_host_sync():
    """Acceptance (zero added retraces / host syncs): the guarded step
    compiles ONCE across many iterations (counters thread as device
    values), and the fit loop leaves the score lazy on device."""
    net = MultiLayerNetwork(_conf()).init()
    net.fit(_iter(), epochs=3)
    assert net._train_step._cache_size() == 1
    assert isinstance(net._score, jax.Array)  # no implicit sync happened
    c = net.resilience_counters()  # the explicit sync point works
    assert c["bad_total"] == 0


def test_sentinel_equivalence_guarded_vs_baseline():
    """On finite data the guarded step is bit-identical to the
    sentinel-free baseline, written out here: the gradient of the engine's
    loss function through ``apply_leafwise`` (the lax.cond never takes the
    skip branch)."""
    from deeplearning4j_tpu.nn.updaters import apply_leafwise
    x, y = _data(32)
    args = (jnp.int32(0), jax.random.PRNGKey(0), jnp.asarray(x),
            jnp.asarray(y), None, None)
    a = MultiLayerNetwork(_conf()).init()
    b = MultiLayerNetwork(_conf()).init()

    @jax.jit
    def baseline(params, opt_state, bn_state, step, key, *batch):
        (_, _), grads = jax.value_and_grad(a._build_loss_fn(), has_aux=True)(
            params, bn_state, key, *batch)
        return apply_leafwise(a.conf.updater, grads, opt_state, params,
                              step)[0]

    pa = baseline(a.params, a.updater_state, a.state, *args)
    pb, _, _, _ = b._build_train_step()(
        b.params, b.updater_state, b.state, *args)
    jax.tree.map(np.testing.assert_array_equal, pa, pb)


def test_sentinel_parallel_wrapper_mesh():
    """Sentinel composes with the sharded step (ZeRO-1 8-device mesh):
    the injected bad batch is skipped consistently across shards."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    net = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(net, shard_update=True)
    x, y = _data(64)
    it = NumpyDataSetIterator(x, y, batch_size=32)
    faults.inject("train.nonfinite", after=1, times=1)
    pw.fit(it, epochs=1)
    c = net.resilience_counters()
    assert c["bad_total"] == 1
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(net.params))


def test_clip_events_counted():
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(learning_rate=0.5))
            .gradient_clip_l2(1e-4)  # tiny threshold: every step clips
            .input_type(InputType.feed_forward(4))
            .list(DenseLayer(n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(_iter(), epochs=1)
    assert net.resilience_counters()["clip_events"] == 4  # 64/16 steps


# ----------------------------------------------------- crash-safe ckpt
def test_checkpoint_manifest_written_and_verifies(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    net.fit(it, epochs=1)
    ck = TrainingCheckpointer(str(tmp_path))
    t0 = time.perf_counter()
    s = ck.save(net, iterator=it)  # non-blocking: manifest finalizes off-thread
    submit_time = time.perf_counter() - t0
    ck.wait_until_finished()
    assert submit_time < ck.last_save_latency_s + 0.5
    assert ck.verify(s) is True
    assert ck.verified_steps() == [s]
    assert ck.last_save_latency_s is not None


def test_torn_write_detected_and_fallback(tmp_path):
    """Acceptance (c): injected torn checkpoint write -> restore falls
    back to the last VERIFIED checkpoint, counted."""
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    ck = TrainingCheckpointer(str(tmp_path), max_to_keep=5)
    net.fit(it, epochs=1)
    ck.save(net, iterator=it, step=1)
    ck.wait_until_finished()  # step 1's manifest must land BEFORE arming
    good = jax.tree.map(np.asarray, net.params)
    faults.inject("checkpoint.write", times=1)
    net.fit(it, epochs=1)
    ck.save(net, iterator=it, step=2)  # torn
    ck.wait_until_finished()
    assert ck.verify(2) is False and ck.verify(1) is True
    net2 = MultiLayerNetwork(_conf()).init()
    assert ck.restore(net2) == 1
    assert ck.restore_fallbacks == 1
    jax.tree.map(np.testing.assert_array_equal, net2.params, good)
    # explicitly requesting the corrupt step raises
    with pytest.raises(CorruptCheckpoint):
        ck.restore(MultiLayerNetwork(_conf()).init(), step=2)


def test_all_checkpoints_corrupt_raises(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    net.fit(it, epochs=1)
    ck = TrainingCheckpointer(str(tmp_path))
    faults.inject("checkpoint.write", times=1)
    ck.save(net, iterator=it, step=1)
    with pytest.raises(CorruptCheckpoint, match="failed manifest"):
        ck.restore(MultiLayerNetwork(_conf()).init())


def test_async_save_never_blocks_and_round_trips(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    net.fit(it, epochs=1)
    ck = TrainingCheckpointer(str(tmp_path), async_save=True)
    t0 = time.perf_counter()
    s = ck.save(net, iterator=it)
    submit_time = time.perf_counter() - t0
    ck.wait_until_finished()
    assert submit_time < ck.last_save_latency_s + 0.5  # returned early
    assert ck.verify(s) is True
    net2 = MultiLayerNetwork(_conf()).init()
    assert ck.restore(net2) == s
    jax.tree.map(np.testing.assert_array_equal, net2.params, net.params)


def test_manifestless_checkpoint_not_preferred_over_verified(tmp_path):
    """Review regression: a checkpoint whose writer died before the
    manifest (verify() None) must NOT restore ahead of an older VERIFIED
    one; it is accepted only when nothing verifies."""
    import os

    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    ck = TrainingCheckpointer(str(tmp_path), max_to_keep=5)
    net.fit(it, epochs=1)
    ck.save(net, iterator=it, step=1)
    good = jax.tree.map(np.asarray, net.params)
    net.fit(it, epochs=1)
    ck.save(net, iterator=it, step=2)
    ck.wait_until_finished()
    os.remove(os.path.join(ck._step_dir(2), "manifest.sha256.json"))
    assert ck.verify(2) is None and ck.verify(1) is True
    net2 = MultiLayerNetwork(_conf()).init()
    assert ck.restore(net2) == 1  # the verified one wins
    jax.tree.map(np.testing.assert_array_equal, net2.params, good)
    # ...but with no verified checkpoint at all, manifest-less restores
    os.remove(os.path.join(ck._step_dir(1), "manifest.sha256.json"))
    assert ck.restore(MultiLayerNetwork(_conf()).init()) == 2


# ------------------------------------------------------------ auto-resume
def test_auto_resume_bit_equivalent(tmp_path):
    """Acceptance (b): injected crash mid-epoch -> auto-resume restores
    model+updater+iterator; final params BIT-equal an uninterrupted run,
    step-count exact."""
    ref = MultiLayerNetwork(_conf()).init()
    ref.fit(_iter(), epochs=3)

    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    faults.inject("train.step", error="crash", after=6, times=1)
    pol = ResiliencePolicy(checkpointer=str(tmp_path),
                           checkpoint_every_iterations=2, max_restarts=2)
    net.fit(it, epochs=3, resilience=pol)
    assert net.iteration == ref.iteration and net.epoch == ref.epoch
    jax.tree.map(np.testing.assert_array_equal, net.params, ref.params)
    jax.tree.map(np.testing.assert_array_equal, net.updater_state,
                 ref.updater_state)
    assert faults.telemetry_snapshot()["auto_resumes"] == 1


def test_resilient_fit_continues_previous_run_in_same_dir(tmp_path):
    """Review regression: a fresh model + a checkpoint directory holding a
    previous run is the preempted-job restart shape — the driver resumes
    the previous run up front instead of restoring stale state on the
    first failure (which would silently discard the new run's steps)."""
    a = MultiLayerNetwork(_conf()).init()
    pol = ResiliencePolicy(checkpointer=str(tmp_path))
    a.fit(_iter(), epochs=2, resilience=pol)
    assert a.epoch == 2
    # "restarted job": fresh process, same command, same directory
    b = MultiLayerNetwork(_conf()).init()
    pol2 = ResiliencePolicy(checkpointer=str(tmp_path))
    b.fit(_iter(), epochs=3, resilience=pol2)
    # continued from a's epoch-2 checkpoint to the 3-epoch target
    assert b.epoch == 3 and b.iteration == 12
    uninterrupted = MultiLayerNetwork(_conf()).init()
    uninterrupted.fit(_iter(), epochs=3)
    jax.tree.map(np.testing.assert_array_equal, b.params,
                 uninterrupted.params)


def test_auto_resume_budget_exhausted_reraises(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    faults.inject("train.step", error="crash", after=2, times=float("inf"))
    pol = ResiliencePolicy(checkpointer=str(tmp_path), max_restarts=2)
    with pytest.raises(InjectedCrash):
        net.fit(_iter(), epochs=2, resilience=pol)


def test_nontransient_error_not_retried(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    pol = ResiliencePolicy(checkpointer=str(tmp_path), max_restarts=5)

    class Boom(Exception):
        pass

    class _BadIter(NumpyDataSetIterator):
        def __iter__(self):
            raise Boom("programming error")

    x, y = _data(16)
    with pytest.raises(Boom):
        net.fit(_BadIter(x, y, batch_size=16), epochs=1, resilience=pol)
    assert faults.telemetry_snapshot()["auto_resumes"] == 0


def test_divergence_rollback_with_lr_backoff(tmp_path):
    """Sustained divergence escalates: rollback to last good checkpoint +
    LR backoff, then training completes."""
    net = MultiLayerNetwork(_conf(Adam(learning_rate=1e-2))).init()
    it = _iter()
    faults.inject("train.nonfinite", after=5, times=3)
    pol = ResiliencePolicy(checkpointer=str(tmp_path),
                           max_consecutive_bad_steps=3, lr_backoff=0.5,
                           max_restarts=2)
    net.fit(it, epochs=3, resilience=pol)
    assert net.conf.updater.learning_rate == pytest.approx(5e-3)
    assert net.epoch == 3
    tel = faults.telemetry_snapshot()
    assert tel["divergence_rollbacks"] == 1 and tel["restore_count"] >= 1
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(net.params))


def test_iterator_io_error_resumed(tmp_path):
    """Auto-resume also covers data-pipeline I/O failures (transient
    OSError out of the iterator)."""
    x, y = _data(64)

    class _FlakyIter(NumpyDataSetIterator):
        fail_at = [7]  # one batch into epoch 2

        def __iter__(self):
            for ds in super().__iter__():
                if self.fail_at and self._pos // self._bs + \
                        self._epoch * (64 // self._bs) >= self.fail_at[0]:
                    self.fail_at.pop()
                    raise OSError("injected I/O failure")
                yield ds

    it = _FlakyIter(x, y, batch_size=16, shuffle=True, seed=5)
    net = MultiLayerNetwork(_conf()).init()
    pol = ResiliencePolicy(checkpointer=str(tmp_path),
                           checkpoint_every_iterations=2, max_restarts=2)
    net.fit(it, epochs=3, resilience=pol)
    assert net.epoch == 3 and net.iteration == 12
    assert faults.telemetry_snapshot()["auto_resumes"] == 1


def test_auto_resume_parallel_wrapper(tmp_path):
    """fit(resilience=) on the ParallelWrapper: the sharded step crashes
    mid-run, restore covers the inner engine's state, training completes."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    net = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(net, shard_update=True)
    x, y = _data(64)
    it = NumpyDataSetIterator(x, y, batch_size=32)
    faults.inject("train.step", error="crash", after=3, times=1)
    pol = ResiliencePolicy(checkpointer=str(tmp_path),
                           checkpoint_every_iterations=1, max_restarts=2)
    pw.fit(it, epochs=3, resilience=pol)
    assert net.epoch == 3 and net.iteration == 6
    assert faults.telemetry_snapshot()["auto_resumes"] == 1


# ---------------------------------------------------------------- serving
def _serve_model():
    net = MultiLayerNetwork(_conf()).init()
    return net


def test_deadline_exceeded_fails_fast_batched():
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_wait_ms=1)
    x = np.zeros((2, 4), np.float32)
    fut = pi.submit(x, deadline_ms=-1.0)  # already expired
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=5)
    assert pi.deadline_expired == 1
    assert pi.stats()["deadline_expired"] == 1
    pi.shutdown()


def test_deadline_exceeded_sequential():
    pi = ParallelInference(_serve_model(), mode=InferenceMode.SEQUENTIAL)
    fut = pi.submit(np.zeros((1, 4), np.float32), deadline_ms=-1.0)
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=5)
    assert pi.health() == HealthState.DEGRADED
    pi.shutdown()


def test_transient_dispatch_retried_once():
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_wait_ms=1)
    faults.inject("serving.dispatch", error="crash", times=1)
    out = pi.output(np.zeros((2, 4), np.float32))
    assert out.shape == (2, 3)
    assert pi.retries == 1 and pi.failures == 0
    assert pi.health() == HealthState.DEGRADED
    pi.shutdown()


def test_second_transient_failure_propagates():
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_wait_ms=1)
    faults.inject("serving.dispatch", error="crash", times=2)
    with pytest.raises(InjectedCrash):
        pi.output(np.zeros((2, 4), np.float32))
    assert pi.retries == 1 and pi.failures == 1
    pi.shutdown()


def test_load_shedding_under_injected_overload():
    """Acceptance (d): under injected dispatch latency the queue passes
    the shedding threshold; excess requests get fast QueueFull, accepted
    requests complete with bounded latency, health reports SHEDDING."""
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_batch_size=2, max_wait_ms=1,
                           shed_queue_depth=3)
    # warm the engine so injected latency dominates dispatch time
    pi.output(np.zeros((2, 4), np.float32))
    faults.inject("serving.slow", delay=0.08, times=float("inf"))
    x = np.zeros((1, 4), np.float32)
    futures, shed = [], 0
    for _ in range(16):
        try:
            futures.append(pi.submit(x))
        except QueueFull:
            shed += 1
    assert shed > 0, "queue never passed the shedding threshold"
    assert pi.health() == HealthState.SHEDDING
    for f in futures:  # accepted requests all complete
        assert f.result(timeout=30).shape == (1, 3)
    st = pi.stats()
    assert st["shed"] == shed and st["health"] in (HealthState.SHEDDING,
                                                   HealthState.DEGRADED,
                                                   HealthState.HEALTHY)
    assert st["latency_ms_p99"] is not None and \
        st["latency_ms_p99"] < 10_000  # bounded, not unbounded linger
    pi.shutdown()


def test_shedding_applies_to_oversized_chunked_requests():
    """Review regression: an oversized (chunked) request must hit the
    shedding check BEFORE splitting — the heaviest traffic cannot evade
    overload protection."""
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_batch_size=2, shed_queue_depth=0)
    with pytest.raises(QueueFull):
        pi.submit(np.zeros((10, 4), np.float32))  # would be 5 chunks
    assert pi.shed == 1 and pi.queue_depth() == 0
    pi.shutdown()


def test_shutdown_fails_queued_futures_with_shutdown_error():
    """Satellite: shutdown() must FAIL queued/in-flight futures (typed),
    never leave them unresolved."""
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_batch_size=2, max_wait_ms=1)
    faults.inject("serving.slow", delay=0.05, times=float("inf"))
    futs = [pi.submit(np.zeros((1, 4), np.float32)) for _ in range(8)]
    pi.shutdown()
    for f in futs:
        try:
            f.result(timeout=10)  # either served before shutdown...
        except ShutdownError:
            pass  # ...or failed with the typed error — never stranded
    with pytest.raises(ShutdownError):
        pi.submit(np.zeros((1, 4), np.float32))


def test_submit_racing_shutdown_never_strands():
    """Satellite regression: submits racing shutdown() either resolve or
    raise ShutdownError within a bounded wait — no hang."""
    pi = ParallelInference(_serve_model(), mode=InferenceMode.BATCHED,
                           max_wait_ms=1)
    results = []

    def hammer():
        for _ in range(50):
            try:
                f = pi.submit(np.zeros((1, 4), np.float32))
                try:
                    f.result(timeout=10)
                    results.append("ok")
                except (ShutdownError, RuntimeError):
                    results.append("shutdown")
            except (ShutdownError, RuntimeError):
                results.append("rejected")

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    pi.shutdown()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "submit/output stranded past shutdown"
    assert len(results) == 200


def test_healthz_endpoint():
    from deeplearning4j_tpu.serving.server import JsonModelServer
    with JsonModelServer(_serve_model()) as srv:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            body = json.loads(r.read())
            assert r.status == 200 and body["status"] == HealthState.HEALTHY
    # shed_queue_depth=0 -> permanently SHEDDING: healthz 503, predict 429
    with JsonModelServer(_serve_model(), shed_queue_depth=0) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == HealthState.SHEDDING
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/predict",
            data=json.dumps({"data": [[0, 0, 0, 0]]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 429


# ------------------------------------------------------------- data layer
def test_async_iterator_skips_bad_records_within_cap():
    """Satellite: max_bad_records tolerates N bad batches (logged +
    counted), epoch completes with the good ones."""
    x, y = _data(64)
    it = AsyncDataSetIterator(NumpyDataSetIterator(x, y, batch_size=16),
                              max_bad_records=3)
    faults.inject("data.record", error="io", after=1, times=2)
    batches = list(it)
    assert len(batches) == 2  # 4 total, 2 skipped
    assert it.bad_records == 2
    assert it.stats() == {"bad_records": 2, "max_bad_records": 3}
    # next epoch is clean and full
    assert len(list(it)) == 4


def test_async_iterator_aborts_past_cap():
    x, y = _data(64)
    it = AsyncDataSetIterator(NumpyDataSetIterator(x, y, batch_size=16),
                              max_bad_records=1)
    faults.inject("data.record", error="io", times=3)
    with pytest.raises(OSError):
        list(it)
    assert it.bad_records == 1  # tolerated one, aborted on the second


def test_async_iterator_default_fail_fast():
    x, y = _data(32)
    it = AsyncDataSetIterator(NumpyDataSetIterator(x, y, batch_size=16))
    faults.inject("data.record", error="io", times=1)
    with pytest.raises(OSError):
        list(it)


def test_async_iterator_skip_keeps_resume_cursor_exact():
    """The skipped batch occupies its base-cursor position: a checkpoint
    taken after the skip resumes at the right batch (no replay, no gap)."""
    x, y = _data(64)
    base = NumpyDataSetIterator(x, y, batch_size=16)
    it = AsyncDataSetIterator(base, max_bad_records=2)
    faults.inject("data.record", error="io", after=1, times=1)  # 2nd bad
    got = []
    for i, ds in enumerate(it):
        got.append(ds)
        if i == 1:  # consumed batches 0 and 2 (1 was skipped)
            state = it.state()
            break
    assert state["consumed"] == 3  # 2 consumed + 1 skipped position
    it2 = AsyncDataSetIterator(NumpyDataSetIterator(x, y, batch_size=16))
    it2.set_state(state)
    rest = list(it2)
    assert len(rest) == 1
    np.testing.assert_array_equal(rest[0].features, x[48:])


# ------------------------------------------------------------ earlystopping
def test_earlystopping_invalid_score_wired_to_sentinel():
    from deeplearning4j_tpu.optimize.earlystopping import (
        DataSetLossCalculator, EarlyStoppingConfiguration,
        EarlyStoppingTrainer, InvalidScoreIterationTerminationCondition,
        MaxEpochsTerminationCondition)
    net = MultiLayerNetwork(_conf()).init()
    cfg = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(50)],
        iteration_termination_conditions=[
            InvalidScoreIterationTerminationCondition(max_bad_steps=2)],
        score_calculator=DataSetLossCalculator(_iter(32, 16, seed=9)))
    # sentinel skips keep the SCORE NaN only on the bad step; the
    # bad-step counter is what accumulates — inject non-consecutive skips
    faults.inject("train.nonfinite", after=2, times=2)
    result = EarlyStoppingTrainer(cfg, net, _iter()).fit()
    assert result.termination_reason == "IterationTerminationCondition"
    assert "InvalidScore" in result.termination_details
    assert net.resilience_counters()["bad_total"] >= 1


# ---------------------------------------------------------------- listeners
def test_performance_listener_reports_resilience(tmp_path):
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener
    msgs = []
    pl = PerformanceListener(frequency=4, batch_size=16,
                             printer=msgs.append)
    net = MultiLayerNetwork(_conf()).init()
    net.set_listeners(pl)
    it = _iter()
    ck = TrainingCheckpointer(str(tmp_path))
    faults.inject("train.nonfinite", after=1, times=1)
    net.fit(it, epochs=2)
    ck.save(net, iterator=it)
    ck.wait_until_finished()
    net.fit(it, epochs=1)
    assert pl.last_resilience is not None
    assert pl.last_resilience["bad_total"] == 1
    assert pl.last_resilience["checkpoint_saves"] == 1
    assert pl.last_resilience["checkpoint_last_save_latency_s"] > 0
    assert any("skipped 1 non-finite steps" in m for m in msgs)


def test_stats_listener_resilience_record():
    from deeplearning4j_tpu.ui.stats import StatsListener
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    storage = InMemoryStatsStorage()
    net = MultiLayerNetwork(_conf()).init()
    net.set_listeners(StatsListener(storage, frequency=1,
                                    collect_histograms=False,
                                    collect_activations=False))
    faults.inject("train.nonfinite", times=1)
    net.fit(_iter(), epochs=1)
    session = storage.list_sessions()[0]
    recs = [r for r in storage.get_records(session)
            if r.get("type") == "stats"]
    assert recs and recs[-1]["resilience"]["bad_total"] == 1


def test_serving_stats_listener_health():
    from deeplearning4j_tpu.ui.stats import ServingStatsListener
    pi = ParallelInference(_serve_model(), mode=InferenceMode.SEQUENTIAL)
    pi.output(np.zeros((1, 4), np.float32))
    rec = ServingStatsListener(pi).report()
    assert rec["health"] == HealthState.HEALTHY
    assert rec["shed"] == 0 and rec["retries"] == 0
    pi.shutdown()


# ------------------------------------------------------------- checkpoint+fit
def test_checkpoint_restores_sentinel_counters(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    it = _iter()
    faults.inject("train.nonfinite", times=1)
    net.fit(it, epochs=1)
    assert net.resilience_counters()["bad_total"] == 1
    ck = TrainingCheckpointer(str(tmp_path))
    ck.save(net, iterator=it)
    net2 = MultiLayerNetwork(_conf()).init()
    ck.restore(net2)
    assert net2.resilience_counters()["bad_total"] == 1
