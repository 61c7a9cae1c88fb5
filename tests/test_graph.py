"""ComputationGraph DAG engine tests (SURVEY.md §2.4 ComputationGraph row,
§3.2 — vertices, topo order, multi-in/out, residual training, serde)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import (DataSet, MultiDataSet,
                                             NumpyMultiDataSetIterator)
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.layers.conv import (BatchNormalization,
                                               ConvolutionLayer,
                                               GlobalPoolingLayer)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.nn.vertices import (DuplicateToTimeSeriesVertex,
                                            ElementWiseVertex,
                                            L2NormalizeVertex,
                                            LastTimeStepVertex, MergeVertex,
                                            ReverseTimeSeriesVertex,
                                            ScaleVertex, ShiftVertex,
                                            StackVertex, SubsetVertex,
                                            UnstackVertex)


def _residual_conf(seed=0):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-2))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.convolutional(3, 8, 8))
            .add_layer("conv1", ConvolutionLayer(n_out=8, kernel=(3, 3),
                                                 padding=(1, 1),
                                                 activation="relu"), "in")
            .add_layer("conv2", ConvolutionLayer(n_out=8, kernel=(3, 3),
                                                 padding=(1, 1)), "conv1")
            .add_vertex("res", ElementWiseVertex(op="add"), "conv1", "conv2")
            .add_layer("bn", BatchNormalization(), "res")
            .add_layer("gp", GlobalPoolingLayer(pool_type="avg"), "bn")
            .add_layer("out", OutputLayer(n_out=4), "gp")
            .set_outputs("out")
            .build())


def _cnn_data(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 3, 8, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return x, y


# --------------------------------------------------------------- construction

def test_topo_order_respects_dependencies():
    conf = _residual_conf()
    order = conf.topo_order()
    assert order.index("conv1") < order.index("conv2")
    assert order.index("conv2") < order.index("res")
    assert order.index("res") < order.index("out")


def test_duplicate_input_vertex():
    """A vertex may consume the same input twice (x*x) — legal in DL4J."""
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Sgd(learning_rate=0.1))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(3))
            .add_layer("d1", DenseLayer(n_out=4, activation="tanh"), "in")
            .add_vertex("sq", ElementWiseVertex(op="product"), "d1", "d1")
            .add_layer("out", OutputLayer(n_out=2), "sq")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    x = np.random.default_rng(0).normal(size=(4, 3)).astype(np.float32)
    out = net.output(x)
    assert out.shape == (4, 2)


def test_merge_shape_mismatch_rejected():
    import jax
    with pytest.raises(ValueError, match="rank mismatch"):
        MergeVertex(data_format="NHWC").initialize(
            jax.random.PRNGKey(0), [(8, 8, 3), (16,)], np.float32)
    with pytest.raises(ValueError, match="non-concat dim"):
        MergeVertex().initialize(
            jax.random.PRNGKey(0), [(3, 8, 8), (2, 4, 4)], np.float32)


def test_cycle_detection():
    with pytest.raises(ValueError, match="cycle"):
        ComputationGraphConfiguration(
            inputs=["in"], outputs=["b"],
            vertices=[("a", ElementWiseVertex(op="add"), ["in", "b"]),
                      ("b", ElementWiseVertex(op="add"), ["a"])]).topo_order()


def test_unknown_input_rejected():
    with pytest.raises(ValueError, match="not a network input"):
        ComputationGraphConfiguration(
            inputs=["in"], outputs=["a"],
            vertices=[("a", ElementWiseVertex(op="add"), ["nope"])])


def test_summary_lists_vertices():
    net = ComputationGraph(_residual_conf()).init()
    s = net.summary()
    assert "res" in s and "elementwise" in s
    assert f"total params: {net.num_params()}" in s


# ------------------------------------------------------------------- training

def test_residual_graph_trains():
    x, y = _cnn_data(32)
    net = ComputationGraph(_residual_conf()).init()
    net.fit(DataSet(x, y), epochs=1)
    s0 = net.score()
    net.fit(DataSet(x, y), epochs=15)
    assert net.score() < s0


def test_graph_matches_sequential_when_linear():
    """A linear chain graph must produce identical training to the same
    MultiLayerNetwork (same seed => same init => same fused step math)."""
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    x = np.random.default_rng(3).normal(size=(16, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]

    mln_conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Sgd(learning_rate=0.1))
                .input_type(InputType.feed_forward(4))
                .list(DenseLayer(n_out=8, activation="tanh"),
                      OutputLayer(n_out=2)).build())
    mln = MultiLayerNetwork(mln_conf).init()

    cg_conf = (NeuralNetConfiguration.builder().seed(7)
               .updater(Sgd(learning_rate=0.1))
               .graph_builder()
               .add_inputs("in")
               .set_input_types(InputType.feed_forward(4))
               .add_layer("dense", DenseLayer(n_out=8, activation="tanh"), "in")
               .add_layer("out", OutputLayer(n_out=2), "dense")
               .set_outputs("out")
               .build())
    cg = ComputationGraph(cg_conf).init()

    mln.fit(DataSet(x, y), epochs=5)
    cg.fit(DataSet(x, y), epochs=5)
    # same layer kinds in same order with same seed stream => same params
    np.testing.assert_allclose(mln.params_flat(), cg.params_flat(),
                               rtol=1e-5, atol=1e-6)


def test_multi_input_multi_output():
    """Two inputs merged; two output heads; trained via MultiDataSet."""
    rng = np.random.default_rng(1)
    xa = rng.normal(size=(32, 4)).astype(np.float32)
    xb = rng.normal(size=(32, 6)).astype(np.float32)
    y1 = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
    y2 = rng.normal(size=(32, 2)).astype(np.float32)

    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Adam(learning_rate=1e-2))
            .graph_builder()
            .add_inputs("a", "b")
            .set_input_types(InputType.feed_forward(4), InputType.feed_forward(6))
            .add_layer("da", DenseLayer(n_out=8, activation="relu"), "a")
            .add_layer("db", DenseLayer(n_out=8, activation="relu"), "b")
            .add_vertex("merge", MergeVertex(), "da", "db")
            .add_layer("out1", OutputLayer(n_out=3), "merge")
            .add_layer("out2", OutputLayer(n_out=2, loss="mse",
                                           activation="identity"), "merge")
            .set_outputs("out1", "out2")
            .build())
    net = ComputationGraph(conf).init()
    mds = MultiDataSet([xa, xb], [y1, y2])
    net.fit(mds, epochs=1)
    s0 = net.score(mds)
    net.fit(mds, epochs=20)
    assert net.score(mds) < s0

    o1, o2 = net.output(xa, xb)
    assert o1.shape == (32, 3) and o2.shape == (32, 2)
    np.testing.assert_allclose(o1.sum(-1), 1.0, rtol=1e-4)  # softmax head

    it = NumpyMultiDataSetIterator([xa, xb], [y1, y2], batch_size=8)
    net.fit(it, epochs=1)  # iterator path works


def test_fit_requires_loss_heads():
    conf = (NeuralNetConfiguration.builder()
            .graph_builder().add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("d", DenseLayer(n_out=2), "in")
            .set_outputs("d").build())
    net = ComputationGraph(conf).init()
    with pytest.raises(ValueError, match="not Output/Loss"):
        net.fit(DataSet(np.zeros((4, 4), np.float32),
                        np.zeros((4, 2), np.float32)))


# ---------------------------------------------------------------------- serde

def test_graph_json_roundtrip():
    conf = _residual_conf()
    js = conf.to_json()
    conf2 = ComputationGraphConfiguration.from_json(js)
    assert conf2.to_json() == js
    assert [n for n, _, _ in conf2.vertices] == [n for n, _, _ in conf.vertices]


def test_graph_save_load(tmp_path):
    x, y = _cnn_data(16)
    net = ComputationGraph(_residual_conf()).init()
    net.fit(DataSet(x, y), epochs=3)
    path = os.path.join(tmp_path, "cg.zip")
    net.save(path)
    net2 = ComputationGraph.load(path)
    np.testing.assert_array_equal(net.output(x[:4]), net2.output(x[:4]))
    assert net2.iteration == net.iteration
    net2.fit(DataSet(x, y), epochs=1)  # resumable


# ------------------------------------------------------------ vertex oracles

def _apply(v, xs, masks=None, shapes=None):
    import jax
    if shapes is not None:
        v.initialize(jax.random.PRNGKey(0), shapes, np.float32)
    y, _, m = v.apply({}, [jnp.asarray(x) for x in xs], {}, masks=masks)
    return np.asarray(y), m


def test_merge_vertex_oracle(rng):
    a = rng.normal(size=(4, 3)).astype(np.float32)
    b = rng.normal(size=(4, 5)).astype(np.float32)
    y, _ = _apply(MergeVertex(), [a, b])
    np.testing.assert_array_equal(y, np.concatenate([a, b], axis=1))
    # CNN NCHW: channel axis 1
    c = rng.normal(size=(2, 3, 4, 4)).astype(np.float32)
    d = rng.normal(size=(2, 2, 4, 4)).astype(np.float32)
    y, _ = _apply(MergeVertex(), [c, d])
    assert y.shape == (2, 5, 4, 4)
    # NHWC: trailing axis
    y, _ = _apply(MergeVertex(data_format="NHWC"),
                  [c.transpose(0, 2, 3, 1), d.transpose(0, 2, 3, 1)])
    assert y.shape == (2, 4, 4, 5)
    # recurrent [B,T,F]: feature axis 2
    e = rng.normal(size=(2, 5, 3)).astype(np.float32)
    f = rng.normal(size=(2, 5, 4)).astype(np.float32)
    y, _ = _apply(MergeVertex(), [e, f])
    assert y.shape == (2, 5, 7)


@pytest.mark.parametrize("op,fn", [
    ("add", lambda a, b: a + b),
    ("subtract", lambda a, b: a - b),
    ("product", lambda a, b: a * b),
    ("average", lambda a, b: (a + b) / 2),
    ("max", np.maximum),
])
def test_elementwise_vertex_oracle(op, fn, rng):
    a = rng.normal(size=(4, 3)).astype(np.float32)
    b = rng.normal(size=(4, 3)).astype(np.float32)
    y, _ = _apply(ElementWiseVertex(op=op), [a, b])
    np.testing.assert_allclose(y, fn(a, b), rtol=1e-6)


def test_subset_scale_shift_l2norm(rng):
    a = rng.normal(size=(4, 10)).astype(np.float32)
    y, _ = _apply(SubsetVertex(from_idx=2, to_idx=5), [a])
    np.testing.assert_array_equal(y, a[:, 2:6])
    y, _ = _apply(ScaleVertex(scale=2.5), [a])
    np.testing.assert_allclose(y, a * 2.5, rtol=1e-6)
    y, _ = _apply(ShiftVertex(shift=-1.5), [a])
    np.testing.assert_allclose(y, a - 1.5, rtol=1e-6)
    y, _ = _apply(L2NormalizeVertex(), [a])
    np.testing.assert_allclose(np.linalg.norm(y, axis=1), 1.0, rtol=1e-5)


def test_stack_unstack(rng):
    a = rng.normal(size=(4, 3)).astype(np.float32)
    b = rng.normal(size=(4, 3)).astype(np.float32)
    y, _ = _apply(StackVertex(), [a, b])
    assert y.shape == (8, 3)
    u0, _ = _apply(UnstackVertex(from_idx=0, stack_size=2), [y])
    u1, _ = _apply(UnstackVertex(from_idx=1, stack_size=2), [y])
    np.testing.assert_array_equal(u0, a)
    np.testing.assert_array_equal(u1, b)


def test_last_timestep_mask(rng):
    x = rng.normal(size=(2, 5, 3)).astype(np.float32)
    mask = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], dtype=np.float32)
    y, m = _apply(LastTimeStepVertex(), [x], masks=[jnp.asarray(mask)])
    np.testing.assert_allclose(y[0], x[0, 2], rtol=1e-6)  # last unmasked = t2
    np.testing.assert_allclose(y[1], x[1, 4], rtol=1e-6)
    assert m is None
    y, _ = _apply(LastTimeStepVertex(), [x])  # no mask -> last step
    np.testing.assert_allclose(y, x[:, -1], rtol=1e-6)


def test_reverse_and_duplicate_timeseries(rng):
    x = rng.normal(size=(2, 5, 3)).astype(np.float32)
    y, _ = _apply(ReverseTimeSeriesVertex(), [x])
    np.testing.assert_array_equal(y, x[:, ::-1])
    v = rng.normal(size=(2, 4)).astype(np.float32)
    y, _ = _apply(DuplicateToTimeSeriesVertex(), [v, x])
    assert y.shape == (2, 5, 4)
    np.testing.assert_array_equal(y[:, 0], v)
    np.testing.assert_array_equal(y[:, 3], v)


# ------------------------------------------------------------- grad correctness

def test_graph_gradients_match_fd():
    """Analytic grads through Merge + ElementWise + shared fan-out match the
    f64 finite-difference oracle (GradientCheckUtil criterion)."""
    from deeplearning4j_tpu.utils.gradcheck import check_gradients

    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 3))
    y = np.eye(2)[rng.integers(0, 2, 4)]

    conf = (NeuralNetConfiguration.builder().seed(0)
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(3))
            .add_layer("d1", DenseLayer(n_out=4, activation="tanh"), "in")
            .add_layer("d2", DenseLayer(n_out=4, activation="sigmoid"), "d1")
            .add_vertex("ew", ElementWiseVertex(op="add"), "d1", "d2")
            .add_vertex("mg", MergeVertex(), "d1", "ew")
            .add_layer("out", OutputLayer(n_out=2), "mg")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()

    def loss_fn(params):
        acts, _, _ = net._forward(params, {"in": jnp.asarray(x)}, net.state,
                                  train=True, rng=None)
        return net._out_layers["out"].loss_value(acts["out"], jnp.asarray(y))

    ok, worst, failures = check_gradients(loss_fn, net.params,
                                          max_rel_error=1e-5)
    assert ok, f"worst rel err {worst}; failures {failures[:5]}"


# ------------------------------------------------------------------ zoo model

def test_resnet_small_trains_and_roundtrips(tmp_path):
    from deeplearning4j_tpu.models.resnet import (estimate_flops_per_example,
                                                  resnet)

    net = resnet(18, num_classes=4, input_shape=(16, 16, 3),
                 updater=Adam(learning_rate=1e-3)).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
    net.fit(DataSet(x, y), epochs=1)
    s0 = net.score()
    net.fit(DataSet(x, y), epochs=5)
    assert net.score() < s0
    assert estimate_flops_per_example(net) > 0
    path = os.path.join(tmp_path, "rn.zip")
    net.save(path)
    net2 = ComputationGraph.load(path)
    np.testing.assert_array_equal(net.output(x[:2]), net2.output(x[:2]))


def test_resnet50_imagenet_param_count():
    """Canonical ResNet-50 ImageNet parameter count — structure parity with
    the zoo model (25.557M params)."""
    from deeplearning4j_tpu.models.resnet import resnet50
    net = resnet50()
    net.init()
    assert net.num_params() == 25_557_032


# ------------------------------------------------- repeated runs of vertices

from deeplearning4j_tpu.nn.layers.core import DropoutLayer  # noqa: E402
from deeplearning4j_tpu.nn.vertices import GraphVertex, vertex  # noqa: E402
from deeplearning4j_tpu.runtime import telemetry as tel  # noqa: E402


@vertex("test_mean_passes")
class _MeanPasses(GraphVertex):
    """Test only: the mean over the stacked passes ``[R, B, ...]``."""

    def initialize(self, key, input_shapes, dtype):
        return {}, {}, tuple(input_shapes[0][1:])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return jnp.mean(xs[0], axis=0), state, None


def _looped(times=3, mode="none", inside=None, read="res", width=8,
            first="a", last="res"):
    """in -> pre -> [a -> b -> res = pre + b] x times -> out."""
    g = (NeuralNetConfiguration.builder().seed(4)
         .updater(Adam(learning_rate=1e-2)).workspace_mode(mode)
         .graph_builder().add_inputs("in").set_input_types((8,))
         .add_layer("pre", DenseLayer(n_out=8, activation="tanh"), "in")
         .add_layer("a", inside or DenseLayer(n_out=8, activation="tanh"),
                    "pre")
         .add_layer("b", DenseLayer(n_out=width, activation="tanh"), "a")
         .add_vertex("res", ElementWiseVertex(op="add"), "pre", "b"))
    if read == "loop":
        g = g.add_vertex("mean", _MeanPasses(), "loop")
        read = "mean"
    g = (g.add_layer("out", OutputLayer(n_out=3), read)
         .set_outputs("out").repeat("loop", first, last, times))
    return g.build()


def _unrolled(times=3):
    g = (NeuralNetConfiguration.builder().seed(4)
         .updater(Adam(learning_rate=1e-2)).graph_builder()
         .add_inputs("in").set_input_types((8,))
         .add_layer("pre", DenseLayer(n_out=8, activation="tanh"), "in"))
    h = "pre"
    for t in range(times):
        g = (g.add_layer(f"a{t}", DenseLayer(n_out=8, activation="tanh"), h)
             .add_layer(f"b{t}", DenseLayer(n_out=8, activation="tanh"),
                        f"a{t}")
             .add_vertex(f"res{t}", ElementWiseVertex(op="add"), h, f"b{t}"))
        h = f"res{t}"
    return (g.add_layer("out", OutputLayer(n_out=3), h).set_outputs("out")
            .build())


def _loop_data(n=16):
    rng = np.random.default_rng(1)
    return (rng.normal(size=(n, 8)).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _grads(net, x, y):
    import jax
    return jax.grad(lambda p: net._build_loss_fn()(
        p, net.state, None, (jnp.asarray(x),), (jnp.asarray(y),), (None,),
        (None,))[0])(net.params)


def test_repeated_run_equals_the_vertices_written_out():
    """Activations equal; the shared leaf's gradient is the sum of its
    copies'; every pass's output is stacked under the run's name."""
    x, y = _loop_data()
    net = ComputationGraph(_looped()).init()
    flat = ComputationGraph(_unrolled()).init()
    for name in flat.params:
        flat.params[name] = dict(net.params[name.rstrip("012")])
    acts, want = net.feed_forward(x), flat.feed_forward(x)
    assert acts["loop"].shape == (3, 16, 8)
    for t in range(3):
        np.testing.assert_allclose(acts["loop"][t], want[f"res{t}"],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(acts["res"], want["res2"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(net.output(x), flat.output(x), rtol=1e-5,
                               atol=1e-6)
    assert "a" not in acts   # the run's inside is not read from outside
    g, gf = _grads(net, x, y), _grads(flat, x, y)
    for v in ("a", "b"):
        for k in g[v]:
            np.testing.assert_allclose(
                g[v][k], sum(gf[f"{v}{t}"][k] for t in range(3)),
                rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(g["pre"]["W"], gf["pre"]["W"], rtol=1e-4,
                               atol=1e-6)
    assert net.num_params() == flat.num_params() - 2 * 2 * (8 * 8 + 8)


@pytest.mark.parametrize("mode", ["every_1", "every_2", "full"])
def test_repeated_run_recomputes_bit_for_bit(mode):
    """The segments fall inside the pass body; recomputation on and off
    agree bit for bit, through ``fit`` and ``fit_on_device``, with a later
    vertex reading the stacked passes."""
    x, y = _loop_data()
    nets = [ComputationGraph(_looped(mode=m, read="loop")).init()
            for m in ("none", mode)]
    hist = [n.fit_on_device(x, y, epochs=2, batch_size=8) for n in nets]
    assert (hist[0] == hist[1]).all() and hist[0][-1] < hist[0][0]
    for n in nets:
        n.fit(DataSet(x, y), epochs=1)
    for k in nets[0].params:
        for p in nets[0].params[k]:
            np.testing.assert_array_equal(nets[0].params[k][p],
                                          nets[1].params[k][p])


def test_repeated_run_keeps_only_boundary_carries():
    """Under ``every_1`` the backward pass keeps fewer bytes than without
    recomputation (the residual accounting of ``nn/memory.py``)."""
    from deeplearning4j_tpu.nn import memory
    kept = {m: memory.memory_report(
        ComputationGraph(_looped(mode=m, times=4)).init(), 8)
        ["activation_bytes"] for m in ("none", "every_1")}
    assert 0 < kept["every_1"] < kept["none"]


def test_repeated_run_serialises_and_counts_its_passes(tmp_path):
    conf = _looped(times=4, mode="every_2")
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.repeats == [{"name": "loop", "first": "a", "last": "res",
                              "times": 4}]
    assert again._runs == conf._runs and again.workspace_mode == "every_2"
    assert conf._runs[0].vertices == ("a", "b", "res")
    assert conf._runs[0].carry == "pre"
    x, y = _loop_data()
    net = ComputationGraph(again).init()
    assert "walked 4 times" in net.summary()
    net.fit(DataSet(x, y), epochs=2)
    net.fit_on_device(x, y, epochs=1, batch_size=8)
    series = tel.snapshot()["loop.passes"]["series"]
    # 2 steps of fit and 2 of fit_on_device, 4 passes each
    assert series[json.dumps({"graph": net.telemetry_label,
                              "run": "loop"})] == 16
    path = os.path.join(tmp_path, "loop.zip")
    net.save(path)
    loaded = ComputationGraph.load(path)
    assert loaded.conf._runs == net.conf._runs
    np.testing.assert_array_equal(net.output(x), loaded.output(x))


@pytest.mark.parametrize("why,kwargs", [
    ("keeps layer state", dict(inside=BatchNormalization())),
    ("draws random numbers", dict(inside=DropoutLayer(rate=0.5))),
    ("must hand on what it took", dict(width=6, last="b")),
    ("a run has one input", dict(first="b")),
    ("can be read", dict(read="b")),
    ("is not a declared vertex", dict(last="nope")),
    ("declared before", dict(first="res", last="a")),
    ("times=0", dict(times=0)),
])
def test_repeated_run_refusals(why, kwargs):
    """What a run cannot be is refused when the configuration is built."""
    with pytest.raises(ValueError, match=why):
        _looped(**kwargs)


def test_repeated_run_names_and_overlaps_are_checked():
    base = (NeuralNetConfiguration.builder().graph_builder()
            .add_inputs("in").set_input_types((8,))
            .add_layer("a", DenseLayer(n_out=8), "in")
            .add_layer("b", DenseLayer(n_out=8), "a")
            .add_layer("out", OutputLayer(n_out=3), "b").set_outputs("out"))
    with pytest.raises(ValueError, match="the name is taken"):
        base.repeat("a", "a", "b", 2).build()
    base._repeats.clear()
    with pytest.raises(ValueError, match="is in the repeated runs"):
        base.repeat("r1", "a", "b", 2).repeat("r2", "b", "b", 2).build()
    base._repeats.clear()
    with pytest.raises(ValueError, match="lies inside the repeated run"):
        base.set_outputs("a", "out").repeat("r", "a", "b", 2).build()
