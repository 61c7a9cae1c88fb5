"""The ``train.phase.*`` spans of the four training entry points (ISSUE 29):
counts and structure on tiny nets, no times. A span event carries both ends
on the wall clock and the ids that nest it; one public call is one trace id;
the spans add no host sync; disabled telemetry records nothing and changes
no result; the two histograms the listeners and the benchmark already read
keep their cells; and the step builders carry their name scopes.
"""

import time
from collections import Counter

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.autodiff.samediff import SameDiff
from deeplearning4j_tpu.data.dataset import NumpyDataSetIterator
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.optimize.listeners import PerformanceListener
from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
from deeplearning4j_tpu.runtime import telemetry

PHASE = "train.phase."
CHILDREN = ("data_wait_s", "stage_s", "prepare_s", "step_s", "readback_s",
            "listeners_s")


def _mln(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.feed_forward(6))
            .list(DenseLayer(n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.05))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(6))
            .add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .build())
    return ComputationGraph(conf).init()


def _samediff():
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 6))
    t = sd.placeholder("t", (None, 3))
    w = sd.var("w", np.full((6, 3), 0.01, np.float32))
    sd.set_loss(((x.mmul(w) - t) ** 2.0).mean())
    sd.set_updater(Sgd(learning_rate=0.1))
    return sd


def _xy(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _batches(n=32, batch=8):
    x, y = _xy(n)
    return NumpyDataSetIterator(x, y, batch_size=batch)


def _feeds(n=4):
    x, y = _xy(8 * n)
    return [{"x": x[i:i + 8], "t": y[i:i + 8]} for i in range(0, 8 * n, 8)]


class _Recorded:
    """The span events one block left in the ring, by the model's label."""

    def __init__(self, model):
        self.label = model.telemetry_label
        self.since = None
        self.events = []

    def __enter__(self):
        self.since = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.events = [e for e in telemetry.spans(since_ns=self.since)
                       if e.get("model") == self.label]

    def counts(self):
        return Counter(e["name"][len(PHASE):] for e in self.events
                       if e["name"].startswith(PHASE))

    def named(self, short):
        return [e for e in self.events if e["name"] == PHASE + short]


def _assert_one_call_tree(rec, entry):
    """One ``call_s`` that is the root of its trace id; every other span of
    the block has that trace id, sits inside the call's interval, and its
    parent is a span of the block."""
    (call,) = rec.named("call_s")
    assert call["entry"] == entry and call["parent"] is None
    assert call["trace"] == call["span"]
    ids = {e["span"] for e in rec.events}
    for e in rec.events:
        assert e["t0_ns"] <= e["t1_ns"]
        assert e["t1_ns"] - e["t0_ns"] == int(e["duration_s"] * 1e9)
        assert e["trace"] == call["trace"]
        if e is not call:
            assert e["parent"] in ids
            assert call["t0_ns"] <= e["t0_ns"] and e["t1_ns"] <= call["t1_ns"]
    return call


# ------------------------------------------------------------ fit_on_device
@pytest.mark.parametrize("make,entry", [
    (_mln, "MultiLayerNetwork.fit_on_device"),
    (_graph, "ComputationGraph.fit_on_device")], ids=["mln", "graph"])
def test_fit_on_device_spans(make, entry):
    net = make()
    x, y = _xy()
    with _Recorded(net) as rec:
        net.fit_on_device(x, y, epochs=3, batch_size=8)
    _assert_one_call_tree(rec, entry)
    # one stage per stacked array (features, labels), one launch an epoch,
    # and the one sync the call always had: the loss history's read
    assert rec.counts() == {"call_s": 1, "stage_s": 2, "prepare_s": 3,
                            "step_s": 3, "readback_s": 1}
    assert [e["step"] for e in rec.named("step_s")] == [0, 4, 8]


# --------------------------------------------------------------------- fit
@pytest.mark.parametrize("make,entry", [
    (_mln, "MultiLayerNetwork.fit"), (_graph, "ComputationGraph.fit")],
    ids=["mln", "graph"])
def test_fit_spans_and_no_readback(make, entry):
    net = make()
    with _Recorded(net) as rec:
        net.fit(_batches(), epochs=1)
    _assert_one_call_tree(rec, entry)
    assert rec.counts() == {"call_s": 1, "data_wait_s": 4, "stage_s": 4,
                            "prepare_s": 4, "step_s": 4}


def test_listeners_span_only_where_a_listener_is_attached():
    net = _mln()
    net.set_listeners(PerformanceListener(frequency=1, batch_size=8,
                                          collect_memory=False))
    with _Recorded(net) as rec:
        net.fit(_batches(), epochs=1)
    # one an iteration and one at the epoch's end
    assert rec.counts()["listeners_s"] == 5
    assert "readback_s" not in rec.counts()


# ---------------------------------------------------------------- SameDiff
def test_samediff_fit_spans():
    sd = _samediff()
    feeds = _feeds(4)
    with _Recorded(sd) as rec:
        sd.fit(feeds)
    _assert_one_call_tree(rec, "SameDiff.fit")
    # prepare: once for the call (carry, casts, optimizer state), once a feed
    assert rec.counts() == {"call_s": 1, "stage_s": 4, "prepare_s": 5,
                            "step_s": 4, "readback_s": 4}
    assert [e["step"] for e in rec.named("step_s")] == [0, 1, 2, 3]
    # no listener: the loss of step k is read behind the launch of step k+1
    # (ISSUE 34), so each readback_s but the last starts after the next
    # step_s has ended
    steps, reads = rec.named("step_s"), rec.named("readback_s")
    for k in range(3):
        assert reads[k]["t0_ns"] >= steps[k + 1]["t1_ns"]
    assert reads[3]["t0_ns"] >= reads[2]["t1_ns"]


def test_samediff_fit_spans_with_a_listener_keep_the_per_step_order():
    """A listener reads score() and the weights of ITS step: each loss is
    read before the next launch, as it always was."""
    class Listener:
        seen = []

        def iteration_done(self, model, iteration, epoch):
            self.seen.append((iteration, model.score()))

        def on_epoch_end(self, model):
            pass

    sd = _samediff()
    with _Recorded(sd) as rec:
        hist = sd.fit(_feeds(4), listeners=[Listener()])
    _assert_one_call_tree(rec, "SameDiff.fit")
    assert rec.counts() == {"call_s": 1, "stage_s": 4, "prepare_s": 5,
                            "step_s": 4, "readback_s": 4, "listeners_s": 5}
    steps, reads = rec.named("step_s"), rec.named("readback_s")
    for k in range(3):
        assert steps[k]["t1_ns"] <= reads[k]["t0_ns"]
        assert reads[k]["t1_ns"] <= steps[k + 1]["t0_ns"]
    assert Listener.seen == list(zip([1, 2, 3, 4], hist.losses))


# --------------------------------------------------------- ParallelWrapper
def test_parallel_wrapper_fit_spans_on_four_devices():
    net = _mln()
    pw = ParallelWrapper(net, mesh=Mesh(np.array(jax.devices()[:4]),
                                        ("data",)), shard_update=True)
    with _Recorded(net) as rec:
        pw.fit(_batches(), epochs=1)
    _assert_one_call_tree(rec, "ParallelWrapper.fit")
    assert rec.counts() == {"call_s": 1, "data_wait_s": 4, "stage_s": 4,
                            "prepare_s": 4, "step_s": 4}


# ------------------------------------------------------ nesting and the ids
def test_spans_nest_under_an_enclosing_span_and_across_entry_points():
    net, sd = _mln(), _samediff()
    x, y = _xy()
    with telemetry.span("test.outer") as outer:
        net.fit_on_device(x, y, epochs=1, batch_size=8)
        sd.fit(_feeds(2))
    calls = [e for e in telemetry.spans(("train.phase.call_s",))
             if e["trace"] == outer.trace_id]
    assert [c["entry"] for c in calls] == ["MultiLayerNetwork.fit_on_device",
                                           "SameDiff.fit"]
    assert all(c["parent"] == outer.span_id for c in calls)
    steps = [e for e in telemetry.spans(("train.phase.step_s",))
             if e["trace"] == outer.trace_id]
    assert {s["parent"] for s in steps} == {c["span"] for c in calls}


def test_spans_accessor_filters_by_name_and_time():
    net = _mln()
    x, y = _xy()
    net.fit_on_device(x, y, epochs=1, batch_size=8)
    t = time.time_ns()
    net.fit_on_device(x, y, epochs=2, batch_size=8)
    mine = [e for e in telemetry.spans(("train.phase.step_s",), since_ns=t)
            if e.get("model") == net.telemetry_label]
    assert len(mine) == 2 and all(e["t1_ns"] >= t for e in mine)
    assert {e["type"] for e in telemetry.spans()} == {"span"}


# ------------------------------------------------------- the switch is off
def test_disabled_telemetry_records_nothing_and_changes_no_result():
    x, y = _xy()

    def run():
        net = _mln(seed=3)
        losses = net.fit_on_device(x, y, epochs=2, batch_size=8)
        net.fit(_batches(), epochs=1)
        sd = _samediff()
        hist = sd.fit(_feeds(2))
        return (net.telemetry_label, sd.telemetry_label, losses,
                jax.tree.map(np.asarray, net.params), hist.losses,
                np.asarray(sd.get_value("w")))

    on = run()
    was = telemetry.set_enabled(False)
    try:
        t = time.time_ns()
        off = run()
        assert not [e for e in telemetry.spans(since_ns=t)
                    if e.get("model") in off[:2]]
        for name in CHILDREN + ("call_s",):
            h = telemetry.registry.get(PHASE + name)
            assert h is None or not any(
                dict(k).get("model") in off[:2] for k in h.hist_series())
    finally:
        telemetry.set_enabled(was)
    np.testing.assert_array_equal(on[2], off[2])
    jax.tree.map(np.testing.assert_array_equal, on[3], off[3])
    assert on[4] == off[4]
    np.testing.assert_array_equal(on[5], off[5])


# ------------------------------------------- what already read the clocks
def test_phase_histogram_cells_and_performance_listener_unchanged():
    net = _mln()
    lbl = net.telemetry_label
    pl = PerformanceListener(frequency=2, batch_size=8, collect_memory=False,
                             printer=lambda s: None)
    net.set_listeners(pl)
    net.fit(_batches(), epochs=1)
    wait = telemetry.histogram("train.phase.data_wait_s")
    step = telemetry.histogram("train.phase.step_s")
    # the cells are keyed by the model's label alone off a pod, as before
    assert wait.hist_snapshot(model=lbl)["count"] == 4
    assert step.hist_snapshot(model=lbl)["count"] == 4
    assert set(pl.last_phases) == {"data_wait_ms_p50", "step_dispatch_ms_p50",
                                   "data_wait_count"}
    assert pl.last_phases["data_wait_count"] >= 1
    assert pl.last_phases["step_dispatch_ms_p50"] > 0
    # a pod's cells carry the host beside the model
    telemetry.set_host(1, 2)
    try:
        net.fit(_batches(), epochs=1)
    finally:
        telemetry.set_host(0, 1)
    assert step.hist_snapshot(model=lbl, host="1")["count"] == 4
    assert {e["host"] for e in telemetry.spans(("train.phase.stage_s",))
            if e.get("model") == lbl and "host" in e} == {"1"}


def test_steady_state_training_records_no_compile_event():
    net, sd = _mln(), _samediff()
    x, y = _xy()
    net.fit(_batches(), epochs=1)
    net.fit_on_device(x, y, epochs=1, batch_size=8)
    sd.fit(_feeds(2))
    before = telemetry.counter("compile.events").total()
    net.fit(_batches(), epochs=2)
    net.fit_on_device(x, y, epochs=2, batch_size=8)
    sd.fit(_feeds(2), epochs=2)
    assert telemetry.counter("compile.events").total() == before


# --------------------------------------------------------- the name scopes
def _lowered_step_text(kind):
    from deeplearning4j_tpu.nn import memory
    from deeplearning4j_tpu.runtime import sentinel
    if kind == "samediff":
        sd = _samediff()
        _, step = sd._make_fit_step()
        tv = {"w": sd._values["w"]}
        feeds = {k: np.asarray(v) for k, v in _feeds(1)[0].items()}
        other = {n: v for n, v in sd._values.items() if n != "w"}
        return step.lower(sd._fit_carry(tv), sd.updater.init_state(tv),
                          other, np.int32(0), feeds,
                          sentinel.init_counters()).as_text(debug_info=True)
    net = _graph() if kind == "graph" else _mln()
    if kind == "parallel":
        pw = ParallelWrapper(net, mesh=Mesh(np.array(jax.devices()[:4]),
                                            ("data",)))
        return pw._lower_step(8).as_text()
    x, y = memory._batch_avals(net, 8)
    none = (None,) if kind == "graph" else None
    args = (net.params, net.updater_state, net.state, np.int32(0),
            jax.random.PRNGKey(0), x, y, none, none,
            sentinel.init_counters())
    return net._build_train_step(1).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["mln", "graph", "samediff", "parallel"])
def test_step_builders_carry_the_name_scopes(kind):
    """``forward`` names the loss function's operations (its transpose shows
    as ``transpose(jvp(forward))``), ``clip``, ``sentinel`` and ``updater``
    the calls after it: an operator's device trace, and a later reduction
    that keeps the operations' scope, can split the step by them."""
    text = _lowered_step_text(kind)
    for scope in ("jvp(forward)", "transpose(jvp(forward))", "sentinel",
                  "updater"):
        assert f"/{scope}/" in text, scope
