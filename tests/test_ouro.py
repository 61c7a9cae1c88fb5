"""The looped decoder stack (Ouro's layout: sandwich norms, one run of layers
walked ``R`` times with one set of weights, a head that scores every pass and
weighs the passes by a learned exit distribution) against its plain
reference, at tiny widths on the CPU in float32 with seeded weights: one
layer, one pass, the R hidden states, the last pass's logits through
``output()``, the loss, every gradient leaf (the shared ones included), three
Adam steps through ``fit_on_device`` with and without recomputation, the run
against the same layers written out R times with copied weights, one leaf a
shared weight in ``num_params()``, ``summary()``, the JSON and the checkpoint,
the builder's refusals, the counters after one call, and the reference in
blocks against the reference in one block."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import optim
from benchmarks.reference import ouro_2_6b as ref
from deeplearning4j_tpu.models.decoder_stack import (PASSES,
                                                     VERTICES_PER_LAYER,
                                                     vertices_per_layer)
from deeplearning4j_tpu.models.ouro import ouro
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.layers.core import EmbeddingLayer
from deeplearning4j_tpu.nn.layers.decoder import (CausalSelfAttentionLayer,
                                                  ExitWeightedLMOutputLayer,
                                                  GatedDenseLayer,
                                                  RMSNormLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.nn.vertices import (ElementWiseVertex, GraphVertex,
                                            vertex)
from deeplearning4j_tpu.runtime import telemetry as tel

program = importlib.import_module("benchmarks.configs.ouro_2_6b.program")

T, B, LAYERS, PASSES_N, HIDDEN = 16, 2, 2, 3, 32
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(layers=LAYERS, passes=PASSES_N):
    """The published file at toy widths: ``layers`` layers of 4 heads of 8
    walked ``passes`` times."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=HIDDEN, num_attention_heads=4,
               num_key_value_heads=4, head_dim=8, intermediate_size=48,
               vocab_size=50, num_hidden_layers=layers,
               layer_types=["full_attention"] * layers,
               total_ut_steps=passes, compute_dtype="float32")
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3,
                          exit_gate_std=0.3, updater=ADAM)
    return cfg


def build(cfg, weights, workspace_mode=None):
    """The benchmark's own ``program.build`` (it recomputes a decoder layer
    application at a time); ``workspace_mode`` overrides that."""
    net = program.build(cfg, jax.tree.map(jnp.copy, weights), {"seq_len": T})
    if workspace_mode is not None:
        net.set_workspace_mode(workspace_mode)
    return net


def token_ids(cfg, rows=B, seed=3):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, T), dtype=np.int32)


def loss_and_grads(net, ids):
    y = jnp.ones((ids.shape[0], 1), jnp.float32)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            net._build_loss_fn(), has_aux=True)(
            net.params, net.state, None, (jnp.asarray(ids),), (y,), (None,),
            (None,))
    return loss, grads


@pytest.fixture(scope="module")
def world():
    cfg = tiny_cfg()
    weights = ref.init_weights(7, cfg)
    ids = token_ids(cfg)
    net = build(cfg, weights)
    loss, grads = loss_and_grads(net, ids)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(ref.loss)(
            weights, (ids, None), cfg, "float32")
    return dict(cfg=cfg, weights=weights, ids=ids, net=net, loss=loss,
                grads=program._flat(grads), ref_loss=ref_loss,
                ref_grads=ref_grads)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


@pytest.mark.parametrize("layers,passes", [(1, 1), (2, 1), (2, 3)],
                         ids=["one_layer", "one_pass", "every_pass"])
def test_hidden_states_match_the_reference(layers, passes):
    """One layer (and the norm that closes its pass), one pass of two
    layers, and all R passes: the stacked hidden states the head reads."""
    cfg = tiny_cfg(layers, passes)
    weights = ref.init_weights(11, cfg)
    ids = token_ids(cfg)
    got = build(cfg, weights).feed_forward(ids)
    with jax.default_matmul_precision("highest"):
        want = ref.hidden(weights, jnp.asarray(ids), cfg)
    assert got[PASSES].shape == (passes, B, T, HIDDEN)
    close(got[PASSES], want)
    close(got["norm"], want[-1])   # outside the run: the last pass


def test_one_layer_application_matches_the_reference():
    """A layer alone, without the pass's closing norm: the program's eight
    vertices on an input against ``ref.layer`` on it."""
    cfg = tiny_cfg(1, 1)
    weights = ref.init_weights(5, cfg)
    net = build(cfg, weights)
    x = np.random.default_rng(0).normal(size=(B, T, HIDDEN)) \
        .astype(np.float32)
    a = {"embed": jnp.asarray(x)}
    with jax.default_matmul_precision("highest"):
        for name in net.conf._runs[0].vertices[:vertices_per_layer(True)]:
            v, ins = net._vertex_map[name]
            a[name], _, _ = v.apply(net.params.get(name, {}),
                                    [a[i] for i in ins], {}, train=True)
        want = jnp.stack([ref.layer(weights, 0, row, cfg) for row in x])
    close(a["l0.mlp_res"], want)


def test_output_gives_the_last_pass(world):
    w, cfg, ids = world["weights"], world["cfg"], world["ids"]
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref.logits(w, jnp.asarray(ids), cfg), -1)
    close(world["net"].output(ids), want)


def test_loss_matches_the_reference(world):
    assert abs(float(world["loss"]) - float(world["ref_loss"])) \
        <= 1e-5 * float(world["ref_loss"])


@pytest.mark.parametrize("leaf", [n for n, _, _ in
                                  ref.layer_table(tiny_cfg())])
def test_every_gradient_leaf_matches_the_reference(world, leaf):
    """The layers' leaves get the sum over the passes, the head's the sum
    over the passes it scores, the gate's what the exit distribution and
    its entropy give."""
    assert set(world["grads"]) == set(world["ref_grads"])
    close(world["grads"][leaf], world["ref_grads"][leaf], tol=5e-4)


@pytest.mark.parametrize("mode", ["none", "every_8", "full"])
def test_three_adam_steps_follow_the_reference(world, mode):
    """Through ``fit_on_device``; recomputation on and off agree bit for
    bit."""
    cfg, weights = world["cfg"], world["weights"]
    ids = token_ids(cfg, rows=3 * B, seed=9)
    y = np.ones((3 * B, 1), np.float32)
    net = build(cfg, weights, workspace_mode=mode)
    with jax.default_matmul_precision("highest"):
        losses = net.fit_on_device(ids, y, epochs=1, batch_size=B)
        p, state = weights, optim.init_state(ADAM, weights)
        want = []
        for i in range(3):
            l, g = jax.value_and_grad(ref.loss)(
                p, (ids[i * B:(i + 1) * B],), cfg, "float32")
            p, state = optim.apply(ADAM, g, state, p, i)
            want.append(float(l))
    np.testing.assert_allclose(losses, want, rtol=2e-5)
    got = program.params(net)
    for leaf in p:
        close(np.asarray(got[leaf]) - np.asarray(weights[leaf]),
              np.asarray(p[leaf]) - np.asarray(weights[leaf]), tol=2e-2)
    if mode != "none":
        with jax.default_matmul_precision("highest"):
            plain = build(cfg, weights, workspace_mode="none")
            assert (plain.fit_on_device(ids, y, epochs=1, batch_size=B)
                    == losses).all()
        for leaf, v in program.params(plain).items():
            assert (np.asarray(v) == np.asarray(got[leaf])).all(), leaf


@vertex("test_stack_passes")
class _StackPasses(GraphVertex):
    """Test only: its inputs stacked before the batch axis."""

    def initialize(self, key, input_shapes, dtype):
        return {}, {}, (len(input_shapes),) + tuple(input_shapes[0])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return jnp.stack(xs), state, None


def written_out(cfg):
    """The same stack with every pass's layers as vertices of their own
    (``p<t>.l<i>.*``, ``p<t>.norm``), no repeated run."""
    eps = cfg["rms_norm_eps"]
    g = (NeuralNetConfiguration.builder().seed(0)
         .updater(Adam(learning_rate=1e-3)).graph_builder()
         .add_inputs("tokens").set_input_types((T,))
         .add_layer("embed", EmbeddingLayer(n_in=cfg["vocab_size"],
                                            n_out=HIDDEN), "tokens"))
    h, outs = "embed", []
    for t in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            p = f"p{t}.l{i}."
            g = (g.add_layer(p + "attn_norm", RMSNormLayer(eps=eps), h)
                 .add_layer(p + "attn", CausalSelfAttentionLayer(
                     n_heads=4, n_kv_heads=4, head_size=8,
                     rope_theta=float(cfg["rope_theta"])), p + "attn_norm")
                 .add_layer(p + "attn_post", RMSNormLayer(eps=eps),
                            p + "attn")
                 .add_vertex(p + "attn_res", ElementWiseVertex(op="add"), h,
                             p + "attn_post")
                 .add_layer(p + "mlp_norm", RMSNormLayer(eps=eps),
                            p + "attn_res")
                 .add_layer(p + "mlp", GatedDenseLayer(
                     n_hidden=cfg["intermediate_size"]), p + "mlp_norm")
                 .add_layer(p + "mlp_post", RMSNormLayer(eps=eps), p + "mlp")
                 .add_vertex(p + "mlp_res", ElementWiseVertex(op="add"),
                             p + "attn_res", p + "mlp_post"))
            h = p + "mlp_res"
        g = g.add_layer(f"p{t}.norm", RMSNormLayer(eps=eps), h)
        h = f"p{t}.norm"
        outs.append(h)
    g = (g.add_vertex("stack", _StackPasses(), *outs)
         .add_layer("lm_head", ExitWeightedLMOutputLayer(
             n_out=cfg["vocab_size"], beta=cfg["assumed"]["exit_beta"]),
             "stack", "tokens").set_outputs("lm_head"))
    return ComputationGraph(g.build()).init()


def test_the_run_equals_its_layers_written_out(world):
    """Activations equal; a shared leaf's gradient equals the sum of the
    gradients of its copies."""
    cfg, net, ids = world["cfg"], world["net"], world["ids"]
    flat = written_out(cfg)
    assert not flat.conf._runs
    copies = {}
    for name in flat.params:
        src = name.split(".", 1)[1] if name.startswith("p") and \
            name[1].isdigit() else name
        flat.params[name] = jax.tree.map(jnp.copy, net.params[src])
        copies.setdefault(src, []).append(name)
    with jax.default_matmul_precision("highest"):
        acts = flat.feed_forward(ids)
        run = net.feed_forward(ids)
    close(run[PASSES], acts["stack"], tol=1e-5)
    loss, grads = loss_and_grads(flat, ids)
    assert abs(float(loss) - float(world["loss"])) <= 1e-6 * float(loss)
    assert flat.num_params() > net.num_params()
    for src, names in copies.items():
        assert len(names) == (cfg["total_ut_steps"]
                              if src not in ("embed", "lm_head") else 1)
        for param in net.params[src]:
            close(world["grads"][f"{src}/{param}"],
                  sum(grads[n][param] for n in names), tol=5e-5)


def test_one_leaf_a_shared_weight(world, tmp_path):
    """``params``, the updater's state, ``num_params()``, ``summary()``, the
    configuration's JSON and a checkpoint hold each weight once."""
    cfg, net = world["cfg"], world["net"]
    d, w, v = HIDDEN, cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = 4 * d * d + 3 * d * w + 4 * d
    want = v * d + LAYERS * per_layer + d + d * v + d + 1
    assert net.num_params() == want
    assert sum(int(np.prod(l.shape)) for l in
               jax.tree.leaves(net.updater_state["m"])) == want
    summary = net.summary()
    assert f"walked {PASSES_N} times" in summary
    assert f"total params: {want}" in summary
    assert f"(x{PASSES_N}, {PASSES})" in summary
    # the JSON round trip keeps the run, and with it the walk
    again = ComputationGraphConfiguration.from_json(net.conf.to_json())
    assert again.repeats == net.conf.repeats == [
        {"name": PASSES, "first": "l0.attn_norm", "last": "norm",
         "times": PASSES_N}]
    assert again._runs == net.conf._runs
    assert json.loads(net.conf.to_json())["workspace_mode"] == "every_8"
    # the checkpoint round trip
    path = str(tmp_path / "looped.zip")
    net.save(path)
    loaded = ComputationGraph.load(path)
    assert loaded.num_params() == want and loaded.conf._runs == net.conf._runs
    for leaf, value in program.params(net).items():
        assert (np.asarray(program.params(loaded)[leaf])
                == np.asarray(value)).all(), leaf
    close(loaded.output(world["ids"]), net.output(world["ids"]), tol=1e-6)


def test_the_layout_says_how_many_vertices_a_layer_is(world):
    assert VERTICES_PER_LAYER == vertices_per_layer() == 6
    assert vertices_per_layer(post_norms=True) == 8
    assert importlib.import_module(
        "deeplearning4j_tpu.models.laguna").VERTICES_PER_LAYER == 6
    run = world["net"].conf._runs[0]
    assert len(run.vertices) == LAYERS * 8 + 1 and run.carry == "embed" \
        and run.output == "norm" and run.times == PASSES_N


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
    ("num_key_value_heads", 3), ("hidden_act", "gelu"),
    ("early_exit_threshold", 0.5)])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    cfg = dict(tiny_cfg(), **{key: value})
    with pytest.raises(NotImplementedError, match=key):
        ouro(cfg, T)


def test_counters_after_one_call(world):
    """``loop.passes`` counts R a step from the launches; ``loop.exit_mass``
    is the reference's exit distribution summed over the positions with
    loss, by pass."""
    cfg, weights = world["cfg"], world["weights"]
    ids = token_ids(cfg, rows=2 * B, seed=13)
    net = build(cfg, weights)
    passes0 = _series("loop.passes")
    mass0 = _series("loop.exit_mass")
    with jax.default_matmul_precision("highest"):
        net.fit_on_device(ids[:B], np.ones((B, 1), np.float32), epochs=1,
                          batch_size=B)
        g = ref.exit_gates(weights, ref.hidden(weights, jnp.asarray(ids[:B]),
                                               cfg))
        want = np.asarray(ref.exit_distribution(g))[:, :, :-1].sum((1, 2))
    label = json.dumps({"graph": net.telemetry_label, "run": PASSES})
    assert _series("loop.passes")[label] == PASSES_N
    mass = _series("loop.exit_mass")
    got = [mass[json.dumps({"layer": "lm_head", "pass": str(t + 1)})]
           - mass0.get(json.dumps({"layer": "lm_head", "pass": str(t + 1)}),
                       0.0) for t in range(PASSES_N)]
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(sum(got), B * (T - 1), rtol=1e-5)
    assert label not in passes0


def _series(name):
    return dict(tel.snapshot().get(name, {}).get("series", {}))


def test_reference_in_blocks_equals_reference_in_one_block(world,
                                                           monkeypatch):
    """``_Q_BLOCK`` cuts the sequence into chunks for memory alone."""
    cfg, weights, ids = world["cfg"], world["weights"], world["ids"]
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(ref, "_Q_BLOCK", 4)
        assert ref._chunks(T) == 4
        l4, g4 = jax.value_and_grad(ref.loss)(weights, (ids,), cfg)
        monkeypatch.setattr(ref, "_Q_BLOCK", T)
        l1, g1 = jax.value_and_grad(ref.loss)(weights, (ids,), cfg)
    assert abs(float(l4) - float(l1)) <= 1e-6 * float(l1)
    assert abs(float(l4) - float(world["ref_loss"])) <= 1e-6 * float(l1)
    for leaf in g1:
        close(g4[leaf], g1[leaf], tol=2e-5)


def test_the_head_in_blocks_equals_the_head_in_one_block(world, monkeypatch):
    """The program's head a block of positions at a time (the block divides
    the sequence) against a whole row at a time."""
    from deeplearning4j_tpu.nn.layers import decoder
    net, ids = world["net"], world["ids"]
    h = net.feed_forward(ids)[PASSES]
    params, state = net.params["lm_head"], net.state["lm_head"]
    layer = ExitWeightedLMOutputLayer(n_out=50)

    def value(block):
        monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", block)

        def f(p, h):
            out, st, _ = layer.apply(p, [h, jnp.asarray(ids)], state,
                                     train=True)
            return layer.loss_value(out, None), st["exit_mass"]
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(params, h)

    with jax.default_matmul_precision("highest"):
        (l4, m4), (gp4, gh4) = value(4)
        (l1, m1), (gp1, gh1) = value(T + 1)
    assert abs(float(l4) - float(l1)) <= 1e-6 * abs(float(l1))
    close(m4, m1, tol=1e-6)
    close(gh4, gh1, tol=2e-5)
    for k in gp1:
        close(gp4[k], gp1[k], tol=2e-5)
