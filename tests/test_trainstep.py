"""``nn/trainstep.py`` (ISSUE 33): the one train-step core under
``MultiLayerNetwork``, ``ComputationGraph`` and ``SameDiff``.

- the two engines, built from the same stack and the same weights, train to
  the same parameters and updater state, bit for bit, through ``fit`` and
  through ``fit_on_device``, in float32 and bfloat16, with and without a
  recomputing ``workspace_mode`` (under bfloat16 with recomputation both
  take the gradient's float32 upcast inside the updater's branch);
- the tail alone: a non-finite gradient leaves the carry, the optimizer
  state and the BatchNorm state as they were and moves the counters; a
  finite one moves them as ``apply_leafwise`` does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import NumpyDataSetIterator
from deeplearning4j_tpu.nn import trainstep
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork, _get_path
from deeplearning4j_tpu.nn.updaters import Adam, apply_leafwise
from deeplearning4j_tpu.runtime import sentinel


# ------------------------------------------------------------ engine parity
def _builder(dtype, mode):
    return (NeuralNetConfiguration.builder().seed(11)
            .updater(Adam(learning_rate=0.01))
            .data_type(dtype).workspace_mode(mode))


def _mln(dtype, mode):
    conf = (_builder(dtype, mode).input_type(InputType.feed_forward(6))
            .list(DenseLayer(n_out=16, activation="tanh"),
                  DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(dtype, mode):
    conf = (_builder(dtype, mode).graph_builder().add_inputs("in")
            .set_input_types(InputType.feed_forward(6))
            .add_layer("a", DenseLayer(n_out=16, activation="tanh"), "in")
            .add_layer("b", DenseLayer(n_out=16, activation="relu"), "a")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "b")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _flat(net, tree):
    """A parameter-shaped tree as one vector, in ``params_flat``'s order."""
    return np.concatenate([np.asarray(_get_path(tree[k], path)).ravel()
                           for k, path in net._flat_entries()])


def _xy(n=24):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    return x, np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]


def _upcasts_in_updater_branch(net):
    """Does the fused step upcast the gradient inside the ``cond`` that
    guards the updater (one bfloat16 -> float32 cast a leaf there), or
    before it?"""
    x, y = jnp.zeros((8, 6)), jnp.zeros((8, 3))
    graph = isinstance(net, ComputationGraph)
    batch = ((x,), (y,), (None,), (None,)) if graph else (x, y, None, None)
    params_c = jax.tree.map(lambda a: a.astype(jnp.bfloat16), net.params)
    jaxpr = jax.make_jaxpr(net._build_train_step(fused_cast=True))(
        net.params, params_c, net.updater_state, net.state, jnp.int32(0),
        jax.random.PRNGKey(0), *batch, sentinel.init_counters())

    def conds(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "cond":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from conds(sub)

    (cond,) = conds(jaxpr.jaxpr)
    casts = max(sum(e.primitive.name == "convert_element_type"
                    and e.invars[0].aval.dtype == jnp.bfloat16
                    and e.params["new_dtype"] == jnp.float32
                    for e in br.jaxpr.eqns)
                for br in cond.params["branches"])
    assert casts in (0, len(jax.tree.leaves(net.params)))
    return casts > 0


@pytest.mark.parametrize("entry", ["fit", "fit_on_device"])
@pytest.mark.parametrize("mode", ["none", "every_2"])
@pytest.mark.parametrize("dtype", ["FLOAT", "BFLOAT16"])
def test_engines_train_alike(dtype, mode, entry):
    mln, graph = _mln(dtype, mode), _graph(dtype, mode)
    graph.set_params_flat(mln.params_flat())
    x, y = _xy()
    for net in (mln, graph):
        if entry == "fit":      # three steps of 8
            net.fit(NumpyDataSetIterator(x, y, batch_size=8), epochs=1)
        else:                   # one scanned launch of the same three
            net.fit_on_device(x, y, epochs=1, batch_size=8)
        assert net.iteration == 3
    np.testing.assert_array_equal(mln.params_flat(), graph.params_flat())
    for slot in ("m", "v"):
        np.testing.assert_array_equal(
            _flat(mln, mln.updater_state[slot]),
            _flat(graph, graph.updater_state[slot]))
    if dtype == "BFLOAT16":
        # the engines share the rule: late under recomputation, early in
        # the default mode (the resident cell's program)
        late = mode != "none"
        assert _upcasts_in_updater_branch(mln) == late
        assert _upcasts_in_updater_branch(graph) == late


# ---------------------------------------------------------------- the tail
def _tail_case(fused, with_bn):
    params = {"0": {"W": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}}
    carry = (params, jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)) \
        if fused else params
    updater = Adam(learning_rate=0.1)
    bn = ({"0": {"mean": jnp.zeros(3)}}, {"0": {"mean": jnp.full(3, 2.0)}}) \
        if with_bn else None
    tail = trainstep.gradient_tail(
        updater, lambda g: (g, jnp.int32(1)), cdt=jnp.bfloat16)
    return params, carry, updater, updater.init_state(params), bn, tail


@pytest.mark.parametrize("with_bn", [False, True], ids=["no_bn", "bn"])
@pytest.mark.parametrize("fused", [False, True], ids=["params", "pair"])
def test_tail_skips_a_nonfinite_gradient(fused, with_bn):
    params, carry, _, opt, bn, tail = _tail_case(fused, with_bn)
    grads = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), params)
    counters = dict(sentinel.init_counters(), bad_consec=jnp.int32(4))
    new, new_opt, out_bn, sent = jax.jit(tail)(
        jnp.float32(1.0), grads, carry, opt, jnp.int32(0), counters, bn)
    for got, want in ((new, carry), (new_opt, opt)):
        assert jax.tree.structure(got) == jax.tree.structure(want)
        jax.tree.map(np.testing.assert_array_equal, got, want)
    if with_bn:     # the old state, not the batch's
        np.testing.assert_array_equal(out_bn["0"]["mean"], np.zeros(3))
    else:
        assert out_bn is None
    assert {k: int(v) for k, v in sent.items()} == {
        "bad_total": 1, "bad_consec": 5, "clip_events": 1}


@pytest.mark.parametrize("with_bn", [False, True], ids=["no_bn", "bn"])
@pytest.mark.parametrize("fused", [False, True], ids=["params", "pair"])
def test_tail_applies_a_finite_gradient(fused, with_bn):
    params, carry, updater, opt, bn, tail = _tail_case(fused, with_bn)
    grads = jax.tree.map(lambda a: 0.5 * jnp.ones_like(a), params)
    want_p, want_opt = apply_leafwise(updater, grads, opt, params,
                                      jnp.int32(0))
    counters = dict(sentinel.init_counters(), bad_consec=jnp.int32(4))
    new, new_opt, out_bn, sent = jax.jit(tail)(
        jnp.float32(1.0), grads, carry, opt, jnp.int32(0), counters, bn)
    if fused:
        new, new_c = new
        jax.tree.map(lambda c, p: np.testing.assert_array_equal(
            c, p.astype(jnp.bfloat16)), new_c, want_p)
    jax.tree.map(np.testing.assert_array_equal, new, want_p)
    jax.tree.map(np.testing.assert_array_equal, new_opt, want_opt)
    if with_bn:
        np.testing.assert_array_equal(out_bn["0"]["mean"], np.full(3, 2.0))
    assert {k: int(v) for k, v in sent.items()} == {
        "bad_total": 0, "bad_consec": 0, "clip_events": 1}
    # the shorter form: no counters in, none out
    assert jax.jit(tail)(jnp.float32(1.0), grads, carry, opt,
                         jnp.int32(0))[3] is None


def test_epoch_scan_is_the_step_repeated():
    """``build_epoch`` over a step == that step called batch by batch with
    ``fold_in(key, i)`` and the running counter."""
    net = _mln("FLOAT", "none")
    x, y = _xy()
    xs, ys = jnp.asarray(x).reshape(3, 8, 6), jnp.asarray(y).reshape(3, 8, 3)
    step = net._build_train_step().__wrapped__
    key = jax.random.PRNGKey(3)
    state = (net.params, net.updater_state, net.state)
    sent = sentinel.init_counters()
    want = []
    for i in range(3):
        *state, sent, loss = step(*state, jnp.int32(5 + i),
                                  jax.random.fold_in(key, 5 + i), xs[i],
                                  ys[i], None, None, sent)
        want.append(loss)
    epoch = trainstep.build_epoch(step, False, jnp.float32, (None, None))
    p, opt, bn, _, losses = jax.jit(epoch)(
        net.params, net.updater_state, net.state, sentinel.init_counters(),
        jnp.int32(5), key, xs, ys)
    np.testing.assert_allclose(losses, np.asarray(want), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                         atol=1e-7),
                 (p, opt), (state[0], state[1]))
