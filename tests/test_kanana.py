"""The kanana-2 (DeepSeek-V3 block) decoder stack against its plain
reference, at tiny widths on the CPU in float32 with seeded weights: each
layer kind and the whole stack (logits, loss, every gradient leaf, three Adam
steps through ``fit_on_device`` with and without recomputation, the selection
bias bit-equal through them), selection on ``s + bias`` with weights from
``s``, the one rotary key all heads share, values narrower than the scored
width through one block and through blocked rows, the share test (the 16
shares of 8 experts, the shared expert once, add up to the uncut layer), the
builder's refusals, and the counters after one call."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kanana2_30b_a3b as ref
from benchmarks.reference import optim
from deeplearning4j_tpu.models.decoder_stack import VERTICES_PER_LAYER
from deeplearning4j_tpu.models.kanana import kanana2
from deeplearning4j_tpu.nn.layers.decoder import (LatentAttentionLayer,
                                                  SparseExpertLayer)
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.runtime import telemetry as tel

program = importlib.import_module(
    "benchmarks.configs.kanana2_30b_a3b.program")

T, B, LAYERS, HIDDEN = 16, 2, 3, 32
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(held=(0, 4), routed=16):
    """The published file at toy widths: a dense layer, then two expert
    layers; 4 heads of 8 + 4 scored channels and 6 value channels over a
    latent of 16; 2 of ``routed`` experts a token."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=HIDDEN, num_attention_heads=4, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=6, kv_lora_rank=16,
               intermediate_size=64, moe_intermediate_size=16,
               num_experts_per_tok=2, n_routed_experts=held[1], vocab_size=48,
               num_hidden_layers=LAYERS, compute_dtype="float32")
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=routed,
                             held=list(held))
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3,
                          select_bias_std=0.1, updater=ADAM)
    return cfg


def build(cfg, weights, workspace_mode=None):
    """The benchmark's own ``program.build`` (it recomputes a decoder layer
    at a time); ``workspace_mode`` overrides that."""
    net = program.build(cfg, weights, {"seq_len": T})
    if workspace_mode is not None:
        net.set_workspace_mode(workspace_mode)
    return net


@pytest.fixture(scope="module")
def world():
    cfg = tiny_cfg()
    weights = ref.init_weights(7, cfg)
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], (B, T),
                                            dtype=np.int32)
    net = build(cfg, weights)
    acts = net.feed_forward(ids)
    with jax.default_matmul_precision("highest"):
        loss_fn = net._build_loss_fn()
        y = np.ones((B, 1), np.float32)
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            net.params, net.state, None, (jnp.asarray(ids),),
            (jnp.asarray(y),), (None,), (None,))
        ref_loss, ref_grads = jax.value_and_grad(ref.loss)(
            weights, (ids, y), cfg, "float32")
    return dict(cfg=cfg, weights=weights, ids=ids, net=net, acts=acts,
                loss=loss, grads=program._flat(grads), ref_loss=ref_loss,
                ref_grads=ref_grads)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


@pytest.mark.parametrize("i", range(LAYERS),
                         ids=["latent+dense", "latent+sparse.1",
                              "latent+sparse.2"])
def test_each_layer_kind_matches_the_reference(world, i):
    """Layer ``i`` alone: the reference's layer on the program's own input
    to it gives the program's output."""
    w, cfg = world["weights"], world["cfg"]
    h_in = world["acts"]["embed" if i == 0 else f"l{i - 1}.mlp_res"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._layer(w, i, h_in[b], cfg, "float32")
                          for b in range(B)])
    close(world["acts"][f"l{i}.mlp_res"], want)


def test_stack_logits_match_the_reference(world):
    w, cfg, ids = world["weights"], world["cfg"], world["ids"]
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref.logits(w, jnp.asarray(ids), cfg), -1)
    close(world["acts"]["lm_head"], want)
    close(world["net"].output(ids), want)


def test_stack_loss_matches_the_reference(world):
    assert abs(float(world["loss"]) - float(world["ref_loss"])) \
        <= 1e-5 * float(world["ref_loss"])


LEAVES = [n for n, _, _ in ref.layer_table(tiny_cfg())]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(world, leaf):
    """The selection bias is a leaf of the reference alone: its gradient is
    exactly zero there (it enters through the indices of a top-k only), and
    the program holds it outside the parameters."""
    if leaf.endswith("/select_bias"):
        assert not np.asarray(world["ref_grads"][leaf]).any()
        assert leaf not in world["grads"]
        return
    close(world["grads"][leaf], world["ref_grads"][leaf], tol=5e-4)


@pytest.mark.parametrize("workspace", ["none", f"every_{VERTICES_PER_LAYER}"])
def test_three_adam_steps_through_fit_on_device(world, workspace):
    cfg, w = world["cfg"], world["weights"]
    rows = np.random.default_rng(5).integers(0, cfg["vocab_size"],
                                             (3 * B, T), dtype=np.int32)
    y = np.ones((3 * B, 1), np.float32)
    net = build(cfg, jax.tree.map(jnp.copy, w), workspace_mode=workspace)
    with jax.default_matmul_precision("highest"):
        losses = net.fit_on_device(rows, y, epochs=1, batch_size=B)
        p, state = w, optim.init_state(ADAM, w)
        want = []
        for s in range(3):
            batch = (rows[s * B:(s + 1) * B], y[:B])
            l, g = jax.value_and_grad(ref.loss)(p, batch, cfg, "float32")
            p, state = optim.apply(ADAM, g, state, p, s)
            want.append(float(l))
    np.testing.assert_allclose(losses, want, rtol=2e-5)
    got = program.params(net)
    assert set(got) == set(p)
    biases = [k for k in p if k.endswith("/select_bias")]
    assert len(biases) == LAYERS - 1
    for k in biases:
        # frozen on both sides, bit for bit: Adam leaves a zero gradient's
        # leaf alone, and the program's updater never sees it
        assert np.array_equal(np.asarray(got[k]), np.asarray(w[k]))
        assert np.array_equal(np.asarray(p[k]), np.asarray(w[k]))
        assert not np.asarray(program.first_moment(net)[k]).any()
    moved = [np.linalg.norm(np.asarray(got[k] - p[k]))
             / max(np.linalg.norm(np.asarray(p[k] - w[k])), 1e-12)
             for k in p if k not in biases]
    assert max(moved) < 2e-2, max(moved)
    close(program.first_moment(net)["l1.mlp/W1"], state["m"]["l1.mlp/W1"],
          tol=1e-3)


def test_selection_follows_the_biased_scores_and_weights_the_unbiased():
    """Two experts a token of four. The bias lifts expert 3 over expert 1 in
    the selection of every token; the weights are the chosen experts' plain
    sigmoid scores, normalised: the bias is in neither."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    wr = jnp.asarray([[2.0, 1.0, -1.0, 0.5], [0.0, 1.5, -2.0, 1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    plain_e, plain_w = moe.route(x, wr, 2, 2.448)
    e, w = moe.route(x, wr, 2, 2.448, bias)
    assert np.asarray(plain_e).tolist() == [[0, 1], [1, 3]]
    assert np.asarray(e).tolist() == [[3, 0], [3, 1]]       # by s + bias
    for t in range(2):
        chosen = s[t, np.asarray(e)[t]]
        np.testing.assert_allclose(np.asarray(w)[t],
                                   2.448 * chosen / chosen.sum(), rtol=1e-6)
    # the other reading (weigh by the biased scores) is a different number
    biased = (s + np.asarray(bias))[0, [3, 0]]
    assert abs(float(w[0, 0]) - 2.448 * biased[0] / biased.sum()) > 0.05
    # no gradient reaches the bias; the router's follows the unbiased scores
    g = jax.grad(lambda b: jnp.sum(moe.route(x, wr, 2, 2.448, b)[1] ** 2))(
        bias)
    assert not np.asarray(g).any()
    with jax.default_matmul_precision("highest"):
        cfg = dict(tiny_cfg(), num_experts_per_tok=2)
        ref_e, ref_w = ref._route({"Wr": wr, "select_bias": bias}, "", x, cfg,
                                  "float32")
    assert np.asarray(ref_e).tolist() == np.asarray(e).tolist()
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(w), rtol=1e-6)


def test_the_bias_changes_the_chosen_experts_of_the_tiny_stack(world):
    """The seeded bias is no bystander: in the stack the tests compare, it
    changes which experts some tokens choose."""
    w = world["weights"]
    b = world["acts"]["l1.mlp_norm"].reshape(-1, HIDDEN)
    plain, _ = moe.route(b, w["l1.mlp/Wr"], 2, 2.448)
    biased, _ = moe.route(b, w["l1.mlp/Wr"], 2, 2.448,
                          w["l1.mlp/select_bias"])
    differ = (np.sort(np.asarray(plain), 1)
              != np.sort(np.asarray(biased), 1)).any(1)
    assert 0 < differ.sum() < differ.size


def _latent_layer():
    return LatentAttentionLayer(n_heads=4, nope_head_size=8, rope_head_size=4,
                                v_head_size=6, kv_rank=16, rope_theta=1e6)


def test_the_rotary_key_is_one_for_all_heads(world):
    layer = _latent_layer()
    params = {k.split("/")[1]: v for k, v in world["weights"].items()
              if k.startswith("l0.attn/")}
    x = world["acts"]["l0.attn_norm"]
    q, k, v = layer.project(params, x)
    assert q.shape == k.shape == (B, T, 4, 12) and v.shape == (B, T, 4, 6)
    for head in range(1, 4):
        assert np.array_equal(np.asarray(k[:, :, head, 8:]),
                              np.asarray(k[:, :, 0, 8:]))
        assert not np.array_equal(np.asarray(k[:, :, head, :8]),
                                  np.asarray(k[:, :, 0, :8]))
        assert not np.array_equal(np.asarray(q[:, :, head, 8:]),
                                  np.asarray(q[:, :, 0, 8:]))
    # position 0 is not rotated: there the shared key is the projection's
    # last four channels, de-interleaved
    raw = np.asarray(x[:, 0] @ params["Wkva"])[:, 16:]
    np.testing.assert_allclose(np.asarray(k[:, 0, 0, 8:]),
                               raw[:, [0, 2, 1, 3]], rtol=1e-5, atol=1e-6)


def test_deinterleaved_rotation_scores_as_rotation_in_place():
    """HF's order (de-interleave, ``rotate_half``) and DeepSeek's (the pairs
    ``(2i, 2i + 1)`` in place) give every query-key product the same."""
    rng = jax.random.PRNGKey(8)
    q = jax.random.normal(rng, (1, 6, 3, 8))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 6, 1, 8))
    inv = ca.default_inv_freq(8, 1e6)
    cos, sin = ca.rotary_tables(6, inv)
    hf = lambda a: ca.apply_rotary(ca.deinterleave(a), cos, sin)
    ours = jnp.einsum("bqhd,bkgd->bhqk", hf(q), hf(k))
    in_place = jnp.einsum("qhd,kgd->hqk", ref._rotate(q[0], cos, sin),
                          ref._rotate(k[0], cos, sin))
    close(ours[0], in_place, tol=1e-5)
    assert np.asarray(ca.deinterleave(jnp.arange(8.0))).tolist() == \
        [0, 2, 4, 6, 1, 3, 5, 7]


@pytest.mark.parametrize("window,decision", [(None, "blocked_rows"),
                                             (8, "blocked_pairs")])
def test_values_narrower_than_the_scored_width(window, decision):
    """``q`` / ``k`` of 12 channels beside ``v`` of 6, one query head a KV
    head: one block, blocked rows and a direct softmax agree, gradients
    too; the site is counted under the kind it was given."""
    k0 = jax.random.PRNGKey(4)
    q = jax.random.normal(k0, (2, 32, 4, 12))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (2, 32, 4, 12))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (2, 32, 4, 6))

    def run(block):
        return lambda q, k, v: ca.causal_attention(
            q, k, v, window=window, block=block, kind="latent")

    counter = tel.registry.get("attention.dispatch")
    labels = dict(kind="latent", decision=decision, why="platform")
    before = counter.value(**labels)
    blocked = run(8)(q, k, v)
    assert counter.value(**labels) == before + 1
    assert blocked.shape == (2, 32, 4, 6)
    close(blocked, run(32)(q, k, v), tol=1e-5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(12.0)
    i, j = jnp.arange(32)[:, None], jnp.arange(32)[None, :]
    open_ = (j <= i) if window is None else (j <= i) & (j > i - window)
    direct = jnp.einsum("bhqk,bkhd->bqhd",
                        jax.nn.softmax(jnp.where(open_, s, -1e30), -1), v)
    close(blocked, direct, tol=1e-5)
    for arg in range(3):
        g = lambda block: jax.grad(
            lambda *a: jnp.sum(run(block)(*a) ** 2), argnums=arg)(q, k, v)
        close(g(8), g(32), tol=1e-5)


def _expert_layer(cfg, held):
    width = cfg["moe_intermediate_size"]
    return SparseExpertLayer(
        num_experts=cfg["deployment"]["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"], n_hidden=width,
        shared_hidden=cfg["n_shared_experts"] * width, held=held,
        routed_scale=cfg["routed_scaling_factor"], select_bias=True)


def _expert_params(w, pre, held=None):
    p = {k: w[pre + k] for k in ("Wr", "W1", "W3", "W2", "S1", "S3", "S2")}
    if held is not None:
        first, count = held
        for k in ("W1", "W3", "W2"):
            p[k] = p[k][first:first + count]
    return p


def test_the_shares_add_up_to_the_uncut_layer():
    """The deployment's 16 shares of 8 experts each, ``held=(8j, 8)``: their
    routed parts, with the shared experts counted once, are the whole layer
    as the reference computes it with all 128 experts held, 6 a token."""
    cfg = dict(tiny_cfg(held=(0, 128), routed=128), num_experts_per_tok=6)
    w = ref.init_weights(11, cfg)
    pre = "l1.mlp/"
    b = jax.random.normal(jax.random.PRNGKey(0), (B * T, HIDDEN), jnp.float32)
    bias = {"select_bias": w[pre + "select_bias"]}
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(w, pre, b, cfg, "float32")
        shared = ref._gated(b, w[pre + "S1"], w[pre + "S3"], w[pre + "S2"],
                            "float32")
        total, tokens = shared, 0
        for first in range(0, 128, 8):
            layer = _expert_layer(cfg, (first, 8))
            _, state, _ = layer.initialize(jax.random.PRNGKey(0),
                                           (T, HIDDEN), jnp.float32)
            assert state["select_bias"].shape == (128,)
            y, state, _ = layer.apply(_expert_params(w, pre, (first, 8)), b,
                                      {**state, **bias}, train=True)
            total = total + (y - shared)
            tokens += int(state["here"])
            assert np.array_equal(np.asarray(state["select_bias"]),
                                  np.asarray(bias["select_bias"]))
        uncut, _, _ = _expert_layer(cfg, None).apply(
            _expert_params(w, pre), b, bias)
    assert tokens == B * T * 6             # every choice is some share's
    close(total, whole)
    close(uncut, whole)


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536), ("n_group", 8),
                                       ("rope_scaling", {"type": "yarn"}),
                                       ("scoring_func", "softmax")])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        kanana2(dict(tiny_cfg(), **{key: value}), T)


def test_counters_after_one_call(world):
    cfg = world["cfg"]
    net = build(cfg, jax.tree.map(jnp.copy, world["weights"]))
    rows = np.random.default_rng(9).integers(0, cfg["vocab_size"],
                                             (2 * B, T), dtype=np.int32)

    def total(name, **labels):
        m = tel.registry.get(name)
        if m is None:
            return 0
        return sum(v for k, v in m.series().items()
                   if all((lk, lv) in k for lk, lv in labels.items()))

    names = ("moe.tokens", "moe.assignments", "moe.dropped")
    before = {n: total(n) for n in names}
    here0 = total("moe.assignments", where="here")
    latent0 = total("attention.dispatch", kind="latent")
    biased0 = total("moe.route", select="biased")
    plain0 = total("moe.route", select="plain")
    net.fit_on_device(rows, np.ones((2 * B, 1), np.float32), epochs=1,
                      batch_size=B)
    grew = {n: total(n) - before[n] for n in names}
    sparse, steps = LAYERS - 1, 2
    assert grew["moe.assignments"] == sparse * steps * B * T * 2
    assert grew["moe.tokens"] == total("moe.assignments", where="here") - here0
    assert 0 < grew["moe.tokens"] < grew["moe.assignments"]
    assert grew["moe.dropped"] == 0
    # once a traced site: every layer's attention, every expert layer's router
    assert total("attention.dispatch", kind="latent") - latent0 >= LAYERS
    assert total("moe.route", select="biased") - biased0 >= sparse
    assert total("moe.route", select="plain") == plain0
    layers = {dict(k).get("layer") for k in
              tel.registry.get("moe.tokens").series()}
    assert {f"l{i}.mlp" for i in range(1, LAYERS)} <= layers


@pytest.mark.parametrize("i", [0, 1], ids=["latent+dense", "latent+sparse"])
def test_reference_layer_in_chunks_equals_one_chunk(monkeypatch, i):
    """The reference's own chunking (latent, keys and values whole, then 8
    positions at a time) changes nothing."""
    cfg = tiny_cfg()
    w = ref.init_weights(17, cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (32, HIDDEN))

    def run(chunk):
        monkeypatch.setattr(ref, "_Q_BLOCK", chunk)
        with jax.default_matmul_precision("highest"):
            out = ref._layer(w, i, h, cfg, "float32")
            g = jax.grad(lambda w_, h_: jnp.sum(
                ref._layer(w_, i, h_, cfg, "float32") ** 2),
                argnums=(0, 1))(w, h)
        return out, g

    (one, g1), (many, g2) = run(32), run(8)
    close(many, one, tol=1e-5)
    close(g2[1], g1[1], tol=1e-5)
    for leaf in g1[0]:
        if leaf.startswith(f"l{i}."):
            close(g2[0][leaf], g1[0][leaf], tol=1e-5)
