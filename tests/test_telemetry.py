"""ISSUE 6: unified telemetry — MetricsRegistry, spans, retrace tracker,
step/request tracing, Prometheus export, and the observability satellites.

Covers the acceptance criteria:
- every pre-existing counter surface is served from the single registry
  and scrapes through ``GET /metrics`` as valid Prometheus text
  (parse-checked here with a small exposition-format parser);
- the retrace tracker records compile events with causes for dtype /
  workspace_mode / bucket / params-placement mutations, and steady-state
  training records ZERO post-warmup compiles;
- ``ParallelInference.stats(window=...)`` percentiles react to recent
  latency (and ``degraded_p99_ms`` degrades health on them);
- ``ProfilingListener`` re-arms (``every_n_iterations``) and closes a
  capture left open at training end;
- ``DL4J_TPU_PEAK_FLOPS`` makes MFU telemetry work on unknown devices.
"""

import json
import os
import re
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet, NumpyDataSetIterator
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.runtime import telemetry


def _net(seed=0, n_in=6, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.feed_forward(n_in))
            .list(DenseLayer(n_out=8, activation="tanh"),
                  OutputLayer(n_out=n_out, activation="softmax",
                              loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=32, n_in=6, n_out=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return DataSet(x, y)


# --------------------------------------------------------------- registry
def test_registry_counter_gauge_histogram_basics():
    r = telemetry.MetricsRegistry()
    c = r.counter("t.counter")
    c.inc()
    c.inc(2, site="a")
    assert c.value() == 1
    assert c.value(site="a") == 2
    assert c.total() == 3
    g = r.gauge("t.gauge")
    g.set(4.5)
    assert g.value() == 4.5
    assert g.value(default=None, other="x") is None
    h = r.histogram("t.hist")
    for v in range(100):
        h.observe(float(v))
    snap = h.hist_snapshot()
    assert snap["count"] == 100
    assert abs(snap["p50"] - 49.5) < 1.0
    assert snap["p99"] > 95
    # kind collision is a loud error, not silent aliasing
    with pytest.raises(ValueError):
        r.gauge("t.counter")
    # wrong-kind write is a loud error too
    with pytest.raises(TypeError):
        c.observe(1.0)


def test_registry_reset_zeroes_values_keeps_ledger():
    r = telemetry.MetricsRegistry()
    c = r.counter("t.reset")
    c.inc(5)
    assert r.coverage_report()["touched"] == ["t.reset"]
    r.reset()
    assert c.value() == 0
    assert "t.reset" in r.coverage_report()["touched"]  # ledger survives
    assert "t.reset" in r.names()                       # declaration too


def test_registry_thread_safety_smoke():
    r = telemetry.MetricsRegistry()
    c = r.counter("t.mt")
    h = r.histogram("t.mt.h")

    def work():
        for _ in range(500):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 4000
    assert h.hist_snapshot()["count"] == 4000


def test_export_is_safe_under_concurrent_observes():
    """prometheus_text()/snapshot() must copy reservoirs under the lock —
    iterating the live deques while another thread observes raised
    ``RuntimeError: deque mutated during iteration``, failing scrapes."""
    r = telemetry.MetricsRegistry()
    h = r.histogram("t.race")
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(float(i % 7), worker="w")
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(300):
            r.prometheus_text()
            r.snapshot(compact=True)
            r.snapshot()
    finally:
        stop.set()
        t.join(2.0)


def test_histogram_window_filters_old_samples():
    r = telemetry.MetricsRegistry()
    h = r.histogram("t.win")
    h.observe(100.0)
    time.sleep(0.25)
    h.observe(1.0)
    assert h.hist_snapshot()["count"] == 2
    recent = h.hist_snapshot(window=0.2)
    assert recent["count"] == 1
    assert recent["p99"] == 1.0  # the old 100.0 aged out


def test_set_enabled_gates_timing_not_accounting():
    """The kill switch gates TIMING instrumentation (histograms, spans)
    — counters/gauges are functional accounting (fault ledgers, serving
    health inputs) and always record."""
    r = telemetry.registry
    c = telemetry.counter("t.gate")
    g = telemetry.gauge("t.gate.g")
    h = telemetry.histogram("t.gate.h")
    prev = telemetry.set_enabled(False)
    try:
        c.inc(7)
        g.set(3)
        h.observe(1.0)
        with telemetry.span("t.gate.span"):
            pass
        assert c.value() == 7          # accounting still records
        assert g.value() == 3
        assert h.hist_snapshot()["count"] == 0   # timing gated
        assert telemetry.histogram("t.gate.span") \
            .hist_snapshot()["count"] == 0
    finally:
        telemetry.set_enabled(prev)
    h.observe(1.0)
    assert h.hist_snapshot()["count"] == 1
    with telemetry.span("t.gate.span"):
        pass
    assert telemetry.histogram("t.gate.span") \
        .hist_snapshot()["count"] == 1  # records again once re-enabled
    c.zero(), g.zero(), h.zero()
    assert r.is_enabled == prev


def test_registry_discard_cells_bounds_instance_churn():
    """Per-instance labeled cells are dropped when their owner is
    collected (weakref finalizer -> discard_cells), so model churn in a
    long-running service cannot grow the registry unboundedly."""
    import gc

    net = _net()
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    eng = InferenceEngine(net)
    eng.output(_data(n=2).features)
    eid = eng._id
    assert telemetry.counter("serving.engine.calls") \
        .value(engine=eid, pool="default") == 1
    del eng
    gc.collect()
    assert telemetry.counter("serving.engine.calls") \
        .value(default=None, engine=eid, pool="default") is None  # gone


# ------------------------------------------------------------------ spans
def test_span_nesting_and_duration_histogram(tmp_path):
    log_path = str(tmp_path / "events.jsonl")
    with telemetry.event_log(log_path):
        with telemetry.span("t.outer", kind="test") as outer:
            with telemetry.span("t.inner") as inner:
                assert inner.parent_id == outer.span_id
                assert inner.trace_id == outer.trace_id
                assert telemetry.current_span() is inner
            assert telemetry.current_span() is outer
        assert telemetry.current_span() is None
    events = [json.loads(line) for line in open(log_path)]
    spans = {e["name"]: e for e in events if e["type"] == "span"}
    assert spans["t.inner"]["parent"] == spans["t.outer"]["span"]
    assert spans["t.inner"]["trace"] == spans["t.outer"]["trace"]
    assert spans["t.outer"]["kind"] == "test"
    assert spans["t.outer"]["duration_s"] >= spans["t.inner"]["duration_s"]
    # durations landed in the registry histograms under the span names
    assert telemetry.histogram("t.outer").hist_snapshot()["count"] >= 1


def test_event_log_records_compile_events(tmp_path):
    log_path = str(tmp_path / "compiles.jsonl")
    with telemetry.event_log(log_path):
        telemetry.record_compile("t.site", "new_bucket", bucket="[8]")
    events = [json.loads(line) for line in open(log_path)]
    assert events and events[-1]["type"] == "compile"
    assert events[-1]["site"] == "t.site"
    assert events[-1]["cause"] == "new_bucket"
    assert telemetry.compile_events("t.site")[-1]["bucket"] == "[8]"


def test_event_log_stale_handle_close_keeps_new_sink(tmp_path):
    """A handle only closes the sink IT opened: after re-pointing the
    event log, closing the stale first handle (or exiting a ``with``
    block that wrapped the re-point) must not kill the new sink."""
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    h1 = telemetry.event_log(a)
    h2 = telemetry.event_log(b)   # re-points (closes a's sink)
    h1.close()                    # stale: must be a no-op for b
    telemetry.emit_event({"type": "probe"})
    h2.close()
    recs = [json.loads(line) for line in open(b)]
    assert any(r.get("type") == "probe" for r in recs), \
        "stale handle close dropped the active event sink"
    telemetry.emit_event({"type": "after"})  # sink closed: silent no-op
    assert not any(r.get("type") == "after"
                   for r in (json.loads(line) for line in open(b)))


# -------------------------------------------------------- retrace tracker
def test_engine_compile_causes_warmup_bucket_placement_dtype():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    net = _net()
    eng = net.inference_engine()
    x = _data(n=3).features

    def events():
        return [e for e in telemetry.compile_events("serving.engine")
                if e.get("engine") == eng._id]

    eng.warmup([4])
    assert [e["cause"] for e in events()] == ["warmup"]
    eng.output(x)  # pads onto the warmed 4-bucket: no new compile
    assert len(events()) == 1
    eng.output(_data(n=7).features)  # new bucket under traffic
    assert [e["cause"] for e in events()] == ["warmup", "new_bucket"]

    # params placement change: same aval bucket, different sharding
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    repl = NamedSharding(mesh, P())
    net.params = jax.tree.map(lambda a: jax.device_put(a, repl), net.params)
    eng.output(x)
    assert [e["cause"] for e in events()] == \
        ["warmup", "new_bucket", "params_placement"]

    # dtype-policy mutation invalidates and attributes EVERY stale
    # bucket's rebuild — not just the first (the rest used to read as
    # mystery new_buckets, misleading the retrace dashboard)
    net.set_dtype("FLOAT")
    eng.output(x)                       # stale 4-bucket rebuild
    eng.output(_data(n=7).features)     # stale 8-bucket rebuild
    assert [e["cause"] for e in events()][-2:] == \
        ["dtype_policy", "dtype_policy"]
    eng.output(_data(n=12).features)    # genuinely new 16-bucket
    assert events()[-1]["cause"] == "new_bucket"


def test_workspace_mode_mutation_records_train_step_compile():
    net = _net()
    ds = _data()
    before = len(telemetry.compile_events("train.step"))
    net.fit(ds, epochs=1)
    evs = telemetry.compile_events("train.step")[before:]
    assert [e["cause"] for e in evs] == ["init"]
    net.set_workspace_mode("every_1")
    net.fit(ds, epochs=1)
    evs = telemetry.compile_events("train.step")[before:]
    assert [e["cause"] for e in evs] == ["init", "workspace_mode"]


def test_sibling_cache_rebuild_attributed_after_invalidation():
    """set_dtype invalidates BOTH _train_step and _epoch_fn; the sibling
    cache rebuilt second must still read the invalidation cause, not
    first_build (per-cache stale map — the engine's per-bucket contract,
    applied to the model's compiled-fn caches)."""
    net = _net()
    ds = _data()
    net.fit(ds, epochs=1)                               # builds _train_step
    net.fit_on_device(ds.features, ds.labels, epochs=1,
                      batch_size=32)                    # builds _epoch_fn
    before = len(telemetry.compile_events())
    net.set_dtype("BFLOAT16")
    net.fit(ds, epochs=1)                               # consumes one-shot
    net.fit_on_device(ds.features, ds.labels, epochs=1, batch_size=32)
    causes = {(e["site"], e["cause"])
              for e in telemetry.compile_events()[before:]
              if e["site"].startswith("train.")}
    assert ("train.step", "dtype_policy") in causes
    assert ("train.epoch_fn", "dtype_policy") in causes


def test_samediff_fit_step_spec_change_causes():
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    from deeplearning4j_tpu.nn.updaters import Sgd as _Sgd

    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 4))
    lbl = sd.placeholder("lbl", (None, 2))
    w = sd.var("w", np.ones((4, 2), np.float32))
    sd.set_loss(((x.mmul(w) - lbl) ** 2.0).mean())
    sd.set_updater(_Sgd(learning_rate=0.01))
    feeds = {"x": np.ones((8, 4), np.float32),
             "lbl": np.zeros((8, 2), np.float32)}

    before = len(telemetry.compile_events("samediff.fit_step"))
    sd.fit([feeds], epochs=1)

    def causes():
        return [e["cause"]
                for e in telemetry.compile_events("samediff.fit_step")
                [before:]]
    assert causes() == ["first_build"]
    sd.fit([feeds], epochs=1)          # cached: no new event
    assert causes() == ["first_build"]
    sd.set_workspace_mode("every_1")
    sd.fit([feeds], epochs=1)
    assert causes() == ["first_build", "workspace_mode"]
    sd.set_dtype("BFLOAT16")
    sd.fit([feeds], epochs=1)
    assert causes() == ["first_build", "workspace_mode", "dtype_policy"]


def test_steady_state_training_records_zero_postwarmup_compiles():
    net = _net()
    it = NumpyDataSetIterator(_data(n=64).features, _data(n=64).labels,
                              batch_size=16)
    net.fit(it, epochs=2)  # warmup: first build happens here
    # delta the counter, not len(compile_events()): the bounded log
    # evicts at 1024 entries, so in a full-suite run len() can stay flat
    # across a real recompile and the assertion would go vacuous
    n_before = telemetry.counter("compile.events").total()
    evs_before = len(telemetry.compile_events())
    it = NumpyDataSetIterator(_data(n=64).features, _data(n=64).labels,
                              batch_size=16)
    net.fit(it, epochs=3)  # steady state
    assert telemetry.counter("compile.events").total() == n_before, (
        "steady-state training must not lower+compile anything: "
        f"{telemetry.compile_events()[evs_before:]}")


def test_faults_telemetry_bump_set_kind_interop():
    """The pre-registry dict accepted any key from either API; a key that
    crosses telemetry_set/telemetry_bump must keep that contract instead
    of raising TypeError on registry kind mismatch."""
    from deeplearning4j_tpu.runtime import faults

    faults.telemetry_set("t_interop_g", 5)
    faults.telemetry_bump("t_interop_g", 2)   # bump on a gauge: += still
    assert faults.telemetry_snapshot()["t_interop_g"] == 7
    faults.telemetry_bump("t_interop_c", 3)
    faults.telemetry_set("t_interop_c", 1)    # set on a counter: overwrite
    assert faults.telemetry_snapshot()["t_interop_c"] == 1


# -------------------------------------------------- step/request tracing
def test_fit_records_step_phase_histograms():
    # phase cells are labeled model=<id> so concurrently-training nets
    # don't blend distributions — a fresh net's cells start empty
    net = _net()
    lbl = net.telemetry_label
    it = NumpyDataSetIterator(_data(n=32).features, _data(n=32).labels,
                              batch_size=8)
    net.fit(it, epochs=1)
    assert telemetry.histogram("train.phase.step_s") \
        .hist_snapshot(model=lbl)["count"] == 4
    assert telemetry.histogram("train.phase.data_wait_s") \
        .hist_snapshot(model=lbl)["count"] >= 4


def test_serving_phases_and_dispatch_span_recorded():
    from deeplearning4j_tpu.serving.batcher import (InferenceMode,
                                                    ParallelInference)

    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_batch_size=8, max_wait_ms=2)
    try:
        outs = [pi.submit(_data(n=2, seed=s).features) for s in range(4)]
        for f in outs:
            f.result(timeout=10)
    finally:
        pi.shutdown()
    # engine-side phases are labeled engine=<id>,pool=<role> and the
    # dispatch span pi=<id>,pool=,mode= (multi-front processes and
    # disaggregated pools must not blend distributions)
    eid = pi.engine._id
    for name in ("serving.phase.pad_s", "serving.phase.execute_s",
                 "serving.phase.unpad_s"):
        assert telemetry.histogram(name) \
            .hist_snapshot(engine=eid, pool="default")["count"] >= 1, name
    assert telemetry.histogram("serving.dispatch") \
        .hist_snapshot(pi=pi._id, pool="default",
                       mode="batched")["count"] >= 1
    # queue/coalesce phases are per-instance labeled
    q = telemetry.histogram("serving.phase.queue_s") \
        .hist_snapshot(pi=pi._id, pool="default")
    assert q["count"] >= 4


def test_performance_listener_reports_phases_and_env_peak_flops(
        monkeypatch):
    from deeplearning4j_tpu.optimize.listeners import (PerformanceListener,
                                                       _detect_peak_flops)

    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2.5e12")
    assert _detect_peak_flops() == 2.5e12
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "bogus")
    with pytest.raises(ValueError):  # a bad override is not a peak
        _detect_peak_flops()
    monkeypatch.delenv("DL4J_TPU_PEAK_FLOPS")
    # no override on an unlisted device (the CPU): an error, not None —
    # but only where the peak would become an MFU
    with pytest.raises(LookupError, match="device_kind 'cpu'"):
        _detect_peak_flops()
    with pytest.raises(LookupError):
        PerformanceListener(batch_size=8, flops_per_example=1e6)
    assert PerformanceListener(batch_size=8).peak_flops is None

    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    msgs = []
    pl = PerformanceListener(frequency=2, batch_size=8,
                             flops_per_example=1e6,
                             printer=msgs.append)
    assert pl.peak_flops == 1e12
    net = _net()
    net.add_listener(pl)
    it = NumpyDataSetIterator(_data(n=48).features, _data(n=48).labels,
                              batch_size=8)
    net.fit(it, epochs=1)
    assert np.isfinite(pl.last_mfu)
    assert pl.last_phases is not None
    assert pl.last_phases["data_wait_count"] >= 1
    assert any("MFU" in m for m in msgs)


# ----------------------------------------------- pre-existing surfaces
def test_preexisting_surfaces_are_registry_views():
    import deeplearning4j_tpu.ops.flash_attention as fa
    from deeplearning4j_tpu.runtime import faults

    # flash-attention dispatch counters
    fa.reset_counters()
    prev = fa.set_mode("off")
    try:
        q = np.ones((1, 1, 8, 4), np.float32)
        fa.attention(q, q, q)
    finally:
        fa.set_mode(prev)
    assert fa.counters()["fallback_mode"] == 1
    assert telemetry.counter("flash_attention.dispatch") \
        .value(decision="fallback_mode") == 1

    # faults telemetry
    faults.telemetry_reset()
    faults.telemetry_bump("auto_resumes")
    assert faults.telemetry_snapshot()["auto_resumes"] == 1
    assert telemetry.counter("resilience.auto_resumes").total() == 1
    faults.telemetry_reset()

    # engine counters ride labeled registry cells
    net = _net()
    eng = net.inference_engine()
    eng.output(_data(n=3).features)
    assert eng.calls == 1
    assert eng.stats()["padded_rows"] == 1  # 3 -> 4 bucket
    assert telemetry.counter("serving.engine.calls") \
        .value(engine=eng._id, pool="default") == 1

    # sentinel counters mirror into gauges at the sync point, labeled
    # model=<id> so concurrent models can't overwrite each other's cell
    net.fit(_data(), epochs=1)
    rc = net.resilience_counters()
    assert telemetry.gauge("sentinel.bad_total").value(
        default=None, model=net.telemetry_label) == rc["bad_total"]


# ------------------------------------------------------------- /metrics
_PROM_METRIC_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? "
    r"(-?(?:[0-9]*\.)?[0-9]+(?:[eE][-+]?[0-9]+)?|NaN|[+-]Inf)$")


def _parse_prometheus(text):
    """Minimal exposition-format parser: validates every line and returns
    {family: set(metric line names)}. Raises on malformed lines."""
    families = {}
    typed = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, kind = rest.rsplit(" ", 1)
            assert kind in ("counter", "gauge", "summary", "histogram"), line
            typed[name] = kind
            continue
        if line.startswith("#"):
            assert line.startswith("# HELP "), f"bad comment line: {line}"
            continue
        m = _PROM_METRIC_RE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        name = m.group(1)
        base = re.sub(r"_(count|sum)$", "", name)
        assert name in typed or base in typed, \
            f"sample {name} has no # TYPE header"
        families.setdefault(base if base in typed else name, set()).add(name)
        if m.group(2):
            # labels: k="v" pairs, comma-separated
            body = m.group(2)[1:-1]
            assert re.fullmatch(
                r'([a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")'
                r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*', body), \
                f"malformed labels: {body!r}"
    return families


def test_metrics_endpoint_serves_valid_prometheus_text():
    import urllib.request

    from deeplearning4j_tpu.serving.server import JsonModelServer

    net = _net()
    # drive the surfaces so the scrape covers them
    net.fit(_data(), epochs=1)
    net.resilience_counters()
    with JsonModelServer(net, mode="sequential") as srv:
        # a live request so THIS server's latency reservoir has samples
        # (dead instances' cells are finalizer-discarded by design)
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/predict",
            data=json.dumps(
                {"data": _data(n=2).features.tolist()}).encode())
        req = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics")
        ctype = req.headers.get("Content-Type", "")
        text = req.read().decode()
    assert "text/plain" in ctype
    families = _parse_prometheus(text)
    # every pre-existing counter surface scrapes through the one endpoint
    for family in ("dl4j_serving_engine_calls_total",
                   "dl4j_serving_requests_total",
                   "dl4j_serving_request_latency_s",
                   "dl4j_flash_attention_dispatch_total",
                   "dl4j_faults_calls_total",
                   "dl4j_resilience_checkpoint_saves_total",
                   "dl4j_sentinel_bad_total",
                   "dl4j_compile_events_total",
                   "dl4j_train_phase_step_s"):
        assert family in families, (family, sorted(families)[:40])


def test_registry_snapshot_is_json_safe():
    snap = telemetry.snapshot(compact=True)
    json.dumps(snap)  # must not raise
    full = telemetry.snapshot(compact=False)
    json.dumps(full)
    assert "compile.events" in snap


# ------------------------------------------- windowed serving stats
def test_parallel_inference_windowed_stats_and_degraded_p99():
    from deeplearning4j_tpu.serving.batcher import (HealthState,
                                                    InferenceMode,
                                                    ParallelInference)

    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.SEQUENTIAL,
                           degraded_p99_ms=0.0001, health_window_s=0.35)
    try:
        pi.output(_data(n=2).features)
        st_all = pi.stats()
        assert st_all["latency_ms_p50"] is not None
        # any real request beats a 0.1us threshold -> DEGRADED on RECENT
        # latency alone (no failures/sheds happened)
        assert pi.health() == HealthState.DEGRADED
        assert pi.stats()["health"] == HealthState.DEGRADED
        # once the sample ages past the health window the state recovers —
        # the pre-ISSUE-6 lifetime percentiles could never do this
        time.sleep(0.45)
        assert pi.health() == HealthState.HEALTHY
        st_win = pi.stats(window=0.35)
        assert st_win["latency_ms_p50"] is None     # aged out
        assert st_win["window_s"] == 0.35
        assert pi.stats()["latency_ms_p50"] is not None  # lifetime intact
    finally:
        pi.shutdown()


# --------------------------------------------------- profiler re-arming
class _FakeProfiler:
    def __init__(self):
        self.starts = 0
        self.stops = 0

    def start_trace(self, logdir):
        self.starts += 1

    def stop_trace(self):
        self.stops += 1


def test_profiling_listener_rearms_and_stops_on_epoch_end(monkeypatch,
                                                          tmp_path):
    import jax

    from deeplearning4j_tpu.ui.profiler import ProfilingListener

    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)

    lst = ProfilingListener(str(tmp_path / "p"), start_iteration=1,
                            steps=2, every_n_iterations=3)
    net = _net()
    for it in range(1, 12):
        lst.iteration_done(net, it, 0)
    # windows: start@1 stop@3, re-arm -> start@6 stop@8, start@11...
    assert fake.starts >= 2, "every_n_iterations must re-arm the capture"
    assert lst.captures >= 2
    # leak fix: training ends inside an active window -> epoch end closes,
    # draining async-dispatched steps BEFORE stop_trace (same as the
    # in-loop close) so the epoch's last steps land in the capture
    assert lst._active
    synced = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda leaves: (synced.append(True), leaves)[1])
    lst.on_epoch_end(net)
    assert synced, "epoch-end close must sync before stopping the trace"
    assert not lst._active
    assert fake.stops == fake.starts

    # a truncated one-shot re-arms instead of latching _done on a
    # near-empty window (short epochs, window opens near the epoch end)
    fake3 = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake3.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake3.stop_trace)
    tr = ProfilingListener(str(tmp_path / "p3"), start_iteration=3, steps=3)
    for it in range(1, 5):       # epoch 1: iterations 1..4, window opens @3
        tr.iteration_done(net, it, 0)
    assert tr._active
    tr.on_epoch_end(net)         # truncated after 1/3 steps
    assert not tr._done, "truncated one-shot must re-arm, not latch done"
    for it in range(5, 9):       # epoch 2: full window 5..8
        tr.iteration_done(net, it, 1)
    tr.on_epoch_end(net)
    assert (fake3.starts, fake3.stops) == (2, 2)
    assert tr._done              # full window captured -> one-shot done

    # one-shot (historical default): exactly one capture, then done
    fake2 = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake2.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake2.stop_trace)
    one = ProfilingListener(str(tmp_path / "p2"), start_iteration=1, steps=1)
    for it in range(1, 8):
        one.iteration_done(net, it, 0)
    assert (fake2.starts, fake2.stops) == (1, 1)
    assert one._done


# ------------------------------------------------------ data pipeline
def test_async_iterator_bad_records_counted_in_registry():
    from deeplearning4j_tpu.data.dataset import AsyncDataSetIterator

    class Flaky:
        def __init__(self):
            self.n = 0

        def batch_size(self):
            return 4

        def state(self):
            return {"i": self.n}

        def set_state(self, s):
            self.n = s.get("i", 0)

        def reset(self):
            self.n = 0

        def __iter__(self):
            for i in range(4):
                if i == 1 and self.n == 0:
                    self.n = 1
                    raise ValueError("poisoned record")
                yield _data(n=4, seed=i)

    before = telemetry.counter("data.bad_records").total()
    it = AsyncDataSetIterator(Flaky(), max_bad_records=2)
    batches = list(it)
    assert it.stats()["bad_records"] == 1
    assert telemetry.counter("data.bad_records").total() == before + 1
    assert len(batches) >= 3
