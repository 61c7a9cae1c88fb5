"""``nn/staging.py``: how ``fit_on_device`` stages its arrays. A floating
array cast to the network's dtype is cast in runs of rows on host threads
and must equal ``a.astype(dt)`` bit for bit (NaN, infinities, subnormals,
ties: ``chip_smoke.py``'s edge values, which its staging phase holds the
chip to); everything else is staged exactly as the engines' own closure
staged it; both engines train as before; ``train.stage`` counts each
stacked array once by decision and reason."""

import sys
import threading

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from deeplearning4j_tpu.nn import staging
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.runtime import telemetry

BF16 = np.dtype(ml_dtypes.bfloat16)
F16 = np.dtype(np.float16)
LABELS = {"model": "test_staging"}
STAGE = telemetry.counter("train.stage")


def _todays_stack(a, nb, b, dt, cast):
    """The engines' staging closure before the pool: the reference."""
    a = a[:nb * b].reshape((nb, b) + a.shape[1:])
    if cast and np.issubdtype(a.dtype, np.floating) and \
            jnp.issubdtype(dt, jnp.floating):
        a = a.astype(dt)
    return jax.device_put(jnp.asarray(a))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


@pytest.mark.filterwarnings("ignore:overflow encountered in cast")
@pytest.mark.parametrize("tail", [0, 3], ids=["even", "ragged"])
@pytest.mark.parametrize("nb", [1, 2, 8, 13])
@pytest.mark.parametrize("src,dt", [(np.float32, BF16), (np.float64, BF16),
                                    (np.float32, F16)],
                         ids=["f32-bf16", "f64-bf16", "f32-f16"])
def test_cast_is_astype_bit_for_bit(smoke, src, dt, nb, tail):
    b = 4
    a = smoke.edge_array(src, dt, (nb * b + tail, 5, 3), seed=nb + tail)
    got = staging.stack(a, nb, b, dt, True, LABELS)
    want = a[:nb * b].reshape((nb, b, 5, 3)).astype(dt)
    assert got.dtype == dt and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the pool's cast alone, before any upload
    np.testing.assert_array_equal(
        _bits(staging.cast_rows(a[:nb * b], dt).reshape(want.shape)),
        _bits(want))


@pytest.mark.parametrize("a,dt,cast", [
    (np.arange(96, dtype=np.int32).reshape(24, 4), BF16, True),
    (np.eye(3, dtype=np.float32)[np.arange(24) % 3], BF16, False),
    (np.linspace(-2, 2, 72, dtype=np.float32).reshape(24, 3),
     np.dtype(np.float32), True),
    (np.linspace(-2, 2, 72).reshape(24, 3), np.dtype(np.float64), True),
    (np.linspace(-2, 2, 72).reshape(24, 3), BF16, False),
    (np.linspace(-2, 2, 72, dtype=np.float32).reshape(24, 3),
     np.dtype(np.int32), True),
    (np.linspace(-2, 2, 72, dtype=np.float32).reshape(24, 3).astype(BF16),
     BF16, True),
], ids=["ids", "labels", "same-f32", "same-f64", "f64-labels",
        "int-net", "same-bf16"])
def test_whole_staging_is_todays(a, dt, cast):
    before = STAGE.value(decision="whole", why="no_cast")
    got = staging.stack(a, 4, 6, dt, cast, LABELS)
    want = _todays_stack(a, 4, 6, dt, cast)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert STAGE.value(decision="whole", why="no_cast") == before + 1


def _mln(dtype):
    conf = (NeuralNetConfiguration.builder().seed(3).data_type(dtype)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.feed_forward(6))
            .list(DenseLayer(n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(dtype):
    conf = (NeuralNetConfiguration.builder().seed(3).data_type(dtype)
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


def _xy(n=40, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


@pytest.mark.parametrize("dtype", ["BFLOAT16", "FLOAT16", "FLOAT"])
@pytest.mark.parametrize("make", [_mln, _graph], ids=["mln", "graph"])
def test_fit_on_device_trains_as_before(make, dtype, monkeypatch):
    x, y = _xy()
    new = make(dtype).fit_on_device(x, y, epochs=2, batch_size=8)
    monkeypatch.setattr(
        staging, "stack",
        lambda a, nb, b, dt, cast, labels: _todays_stack(a, nb, b, dt, cast))
    old = make(dtype).fit_on_device(x, y, epochs=2, batch_size=8)
    assert new.shape == (10,) and np.all(np.isfinite(new))
    np.testing.assert_array_equal(new, old)


@pytest.mark.parametrize("dtype,batch,counts", [
    ("BFLOAT16", 8, {("parallel", "cast"): 1, ("whole", "no_cast"): 1}),
    ("BFLOAT16", 40, {("parallel", "cast"): 1, ("whole", "no_cast"): 1}),
    ("FLOAT", 8, {("whole", "no_cast"): 2}),
], ids=["cast", "one-batch", "same-dtype"])
@pytest.mark.parametrize("make", [_mln, _graph], ids=["mln", "graph"])
def test_stage_counts_each_stacked_array_once(make, dtype, batch, counts):
    net = make(dtype)
    x, y = _xy()
    cells = [("parallel", "cast"), ("whole", "no_cast")]
    before = {c: STAGE.value(decision=c[0], why=c[1]) for c in cells}
    for _ in range(2):
        net.fit_on_device(x, y, epochs=1, batch_size=batch)
    grew = {c: STAGE.value(decision=c[0], why=c[1]) - before[c]
            for c in cells}
    assert grew == {c: 2 * counts.get(c, 0) for c in cells}


def test_concurrent_stages_stay_bit_for_bit(smoke):
    """More staging threads than cores, switching often, each with its
    own pool: every result is its own array's cast, and every stage is
    counted."""
    arrays = [smoke.edge_array(np.float32, BF16, (24, 5, 3), seed=s)
              for s in range(12)]
    wants = [a.reshape((6, 4, 5, 3)).astype(BF16) for a in arrays]
    bad, done = [], []
    before = STAGE.value(decision="parallel", why="cast")

    def work(k):
        for _ in range(3):
            got = staging.stack(arrays[k], 6, 4, BF16, True, LABELS)
            if not np.array_equal(_bits(got), _bits(wants[k])):
                bad.append(k)
        done.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(len(arrays))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(len(arrays))) and not bad
    assert STAGE.value(decision="parallel", why="cast") == \
        before + 3 * len(arrays)
