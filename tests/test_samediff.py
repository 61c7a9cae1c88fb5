"""SameDiff-equivalent graph layer tests (SURVEY.md §2.2 SameDiff rows,
§3.3): define-then-run graphs, sessions, autodiff training, serde with a
fresh-process reload check."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import PLACEHOLDER, VARIABLE, SameDiff
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.utils.gradcheck import check_gradients


def test_forward_matches_numpy(rng):
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 3))
    w = sd.var("w", rng.normal(size=(3, 4)).astype(np.float32))
    b = sd.var("b", np.zeros(4, np.float32))
    y = sd.tanh(x.mmul(w) + b, name="y")

    xv = rng.normal(size=(5, 3)).astype(np.float32)
    out = sd.output({"x": xv}, ["y"])["y"]
    want = np.tanh(xv @ sd.get_value("w") + sd.get_value("b"))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_operator_sugar_and_reduce(rng):
    sd = SameDiff.create()
    a = sd.var("a", rng.normal(size=(3, 4)))
    b = sd.var("b", rng.normal(size=(3, 4)))
    c = (a * 2.0 + b / 4.0 - 1.0) ** 2.0
    m = c.mean(name=None) if False else c.mean()
    out = m.eval()
    av, bv = sd.get_value("a"), sd.get_value("b")
    want = np.mean((av * 2 + bv / 4 - 1) ** 2)
    np.testing.assert_allclose(out, want, rtol=1e-5)


def test_session_caches_compiled_fn(rng):
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    w = sd.var("w", rng.normal(size=(2, 2)))
    y = x.mmul(w)
    f1 = sd._session((y.name,))
    f2 = sd._session((y.name,))
    assert f1 is f2  # compile once, execute many
    sd.relu(y)       # graph mutation invalidates the session cache
    assert sd._session((y.name,)) is not f1


def test_grad_matches_fd(rng):
    sd = SameDiff.create()
    x = sd.placeholder("x", (4, 3))
    w = sd.var("w", rng.normal(size=(3, 2)))
    b = sd.var("b", rng.normal(size=(2,)))
    loss = ((sd.sigmoid(x.mmul(w) + b) - 0.3) ** 2.0).sum()
    sd.set_loss(loss)

    xv = rng.normal(size=(4, 3))
    g = sd.grad({"x": xv})
    assert set(g) == {"w", "b"}

    def loss_fn(params):
        z = jnp.asarray(xv) @ params["w"] + params["b"]
        return jnp.sum((jax.nn_sigmoid(z) - 0.3) ** 2) if False else \
            jnp.sum((1 / (1 + jnp.exp(-z)) - 0.3) ** 2)

    import jax
    want = jax.grad(loss_fn)({"w": jnp.asarray(sd.get_value("w")),
                              "b": jnp.asarray(sd.get_value("b"))})
    np.testing.assert_allclose(g["w"], np.asarray(want["w"]), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(g["b"], np.asarray(want["b"]), rtol=1e-4,
                               atol=1e-6)


def test_fit_linear_regression(rng):
    true_w = np.array([[2.0], [-3.0]], np.float32)
    xv = rng.normal(size=(128, 2)).astype(np.float32)
    yv = xv @ true_w + 0.5

    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    t = sd.placeholder("t", (None, 1))
    w = sd.var("w", np.zeros((2, 1), np.float32))
    b = sd.var("b", np.zeros((1,), np.float32))
    pred = x.mmul(w) + b
    sd.set_loss(((pred - t) ** 2.0).mean())
    sd.set_updater(Sgd(learning_rate=0.1))

    losses = sd.fit({"x": xv, "t": yv}, epochs=200)
    assert losses[-1] < 1e-3 < losses[0]
    np.testing.assert_allclose(sd.get_value("w"), true_w, atol=0.05)
    np.testing.assert_allclose(sd.get_value("b"), [0.5], atol=0.05)


def _build_lenet_graph(rng):
    """LeNet as a raw SameDiff graph over catalog ops (conv2d/max_pool2d/
    reshape/mmul) — the M4 exit criterion model."""
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 1, 28, 28))
    c1w = sd.var("c1w", (rng.normal(size=(20, 1, 5, 5)) * 0.1).astype(np.float32))
    c1b = sd.var("c1b", np.zeros(20, np.float32))
    c2w = sd.var("c2w", (rng.normal(size=(50, 20, 5, 5)) * 0.05).astype(np.float32))
    c2b = sd.var("c2b", np.zeros(50, np.float32))
    fw = sd.var("fw", (rng.normal(size=(800, 10)) * 0.05).astype(np.float32))
    fb = sd.var("fb", np.zeros(10, np.float32))

    h = sd.call("conv2d", x, c1w, c1b)
    h = sd.relu(h)
    h = sd.call("maxpool2d", h, attrs={"kernel": [2, 2]})
    h = sd.call("conv2d", h, c2w, c2b)
    h = sd.relu(h)
    h = sd.call("maxpool2d", h, attrs={"kernel": [2, 2]})
    h = h.reshape(-1, 800)
    logits = h.mmul(sd._vars["fw"]) + sd._vars["fb"]
    out = sd.softmax(logits, name="out")
    return sd


def test_lenet_graph_runs(rng):
    sd = _build_lenet_graph(rng)
    xv = rng.normal(size=(2, 1, 28, 28)).astype(np.float32)
    out = sd.output({"x": xv}, ["out"])["out"]
    assert out.shape == (2, 10)
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-4)


def test_lenet_graph_fresh_process_roundtrip(rng, tmp_path):
    """M4 exit: export, reload in a FRESH process, identical outputs."""
    sd = _build_lenet_graph(rng)
    xv = rng.normal(size=(2, 1, 28, 28)).astype(np.float32)
    want = sd.output({"x": xv}, ["out"])["out"]

    model_path = os.path.join(tmp_path, "lenet_sd.zip")
    x_path = os.path.join(tmp_path, "x.npy")
    out_path = os.path.join(tmp_path, "out.npy")
    sd.save(model_path)
    np.save(x_path, xv)

    code = (
        "import numpy as np\n"
        "from deeplearning4j_tpu.autodiff import SameDiff\n"
        f"sd = SameDiff.load({model_path!r})\n"
        f"x = np.load({x_path!r})\n"
        "out = sd.output({'x': x}, ['out'])['out']\n"
        f"np.save({out_path!r}, out)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # the child stays off a chip
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), timeout=300)
    got = np.load(out_path)
    np.testing.assert_array_equal(got, want)


def test_json_roundtrip_and_kinds(rng):
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    w = sd.var("w", rng.normal(size=(2, 2)))
    k = sd.constant("k", np.float32(2.0))
    y = sd.relu(x.mmul(w) * k, name="y")
    sd.set_loss(y.sum())
    sd.set_updater(Adam(learning_rate=1e-3))

    js = sd.to_json()
    d = json.loads(js)
    assert d["model_class"] == "SameDiff"
    kinds = {v["name"]: v["kind"] for v in d["variables"]}
    assert kinds["x"] == PLACEHOLDER and kinds["w"] == VARIABLE

    sd2 = SameDiff.from_json(js)
    assert sd2.loss_name == sd.loss_name
    assert [r.op for r in sd2._ops] == [r.op for r in sd._ops]
    # values travel via save/load, not to_json
    sd2._values = dict(sd._values)
    xv = rng.normal(size=(3, 2)).astype(np.float32)
    np.testing.assert_array_equal(sd2.output({"x": xv}, ["y"])["y"],
                                  sd.output({"x": xv}, ["y"])["y"])


def test_errors():
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    y = sd.relu(x)
    with pytest.raises(ValueError, match="missing placeholder"):
        sd.output({}, [y.name])
    with pytest.raises(ValueError, match="unknown op"):
        sd.call("not.an.op", x)
    with pytest.raises(ValueError, match="set_loss"):
        sd.fit({"x": np.zeros((1, 2))})
    other = SameDiff.create()
    z = other.placeholder("z", (None, 2))
    with pytest.raises(ValueError, match="not in this graph"):
        sd.call("act.relu", z)
