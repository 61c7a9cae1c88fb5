"""The Laguna decoder stack against its plain reference, at tiny widths on
the CPU in float32 with seeded weights: every layer kind and the whole
five-layer stack (activations, logits, loss, every gradient leaf, three Adam
steps through ``fit_on_device``), the share test (the shares' routed parts
plus the shared expert once add up to the uncut layer), no dropped token
under a routing that sends every token to the same experts, window against
full attention, head counts and rotary settings against closed forms, the
blocked attention paths, and the counters after one call."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_xs2 as ref
from benchmarks.reference import optim
from deeplearning4j_tpu.models.laguna import (VERTICES_PER_LAYER,
                                              attention_layer, laguna)
from deeplearning4j_tpu.nn.layers.decoder import (CausalSelfAttentionLayer,
                                                  SparseExpertLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.runtime import telemetry as tel

T, B, LAYERS = 16, 2, 5
ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                           "factor": 64,
                           "original_max_position_embeddings": 4096,
                           "beta_slow": 1, "beta_fast": 64,
                           "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}


def tiny_cfg(held=(0, 4), routed=16):
    """The published layout at toy widths: full+dense, window+sparse x3,
    full+sparse; 4 and 6 query heads over 2 KV heads; a window of 4."""
    return {
        "vocab_size": 48, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": LAYERS, "num_key_value_heads": 2, "head_dim": 8,
        "rms_norm_eps": 1e-6, "num_experts": held[1],
        "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "gating": True,
        "sliding_window": 4, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": ROPE,
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
        "deployment": {"num_experts_routed": routed, "held": list(held)},
        "assumed": {"initializer_std": 0.3, "updater": ADAM},
    }


def build(cfg, weights, **kw):
    model = dict(cfg, num_experts=cfg["deployment"]["num_experts_routed"])
    net = laguna(model, T, held=tuple(cfg["deployment"]["held"]),
                 updater=Adam(learning_rate=ADAM["learning_rate"],
                              beta1=0.9, beta2=0.95, epsilon=1e-8),
                 **kw).init()
    nested = {}
    for name, value in weights.items():
        vertex, param = name.split("/")
        nested.setdefault(vertex, {})[param] = value
    assert {k: {p: v.shape for p, v in d.items()}
            for k, d in net.params.items()} == \
        {k: {p: v.shape for p, v in d.items()} for k, d in nested.items()}
    net.params = nested
    return net


def flat(tree):
    return {f"{v}/{p}": a for v, d in tree.items() for p, a in d.items()}


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
                loss=loss, grads=flat(grads), ref_loss=ref_loss,
                ref_grads=ref_grads)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


LAYER_IDS = ["full+dense", "window+sparse.1", "window+sparse.2",
             "window+sparse.3", "full+sparse"]


@pytest.mark.parametrize("i", range(LAYERS), ids=LAYER_IDS)
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


@pytest.mark.parametrize("leaf", [n for n, _, _ in ref.layer_table(tiny_cfg())])
def test_every_gradient_leaf_matches_the_reference(world, leaf):
    close(world["grads"][leaf], world["ref_grads"][leaf], tol=5e-4)


@pytest.mark.parametrize("workspace", ["none", f"every_{VERTICES_PER_LAYER}",
                                       "full"])
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
    got = flat(net.params)
    moved = [np.linalg.norm(np.asarray(got[k] - p[k]))
             / max(np.linalg.norm(np.asarray(p[k] - w[k])), 1e-12)
             for k in p]
    assert max(moved) < 2e-2, max(moved)
    close(flat(net.updater_state["m"])["l1.mlp/W1"], state["m"]["l1.mlp/W1"],
          tol=1e-3)


def _expert_layer(cfg, held):
    return SparseExpertLayer(
        num_experts=cfg["deployment"]["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"], n_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["shared_expert_intermediate_size"], held=held,
        routed_scale=cfg["moe_routed_scaling_factor"])


def _expert_params(w, pre, held=None):
    p = {k: w[pre + k] for k in ("Wr", "W1", "W3", "W2", "S1", "S3", "S2")}
    if held is not None:
        first, count = held
        for k in ("W1", "W3", "W2"):
            p[k] = p[k][first:first + count]
    return p


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their routed parts, with the
    shared expert counted once, are the whole layer as the reference
    computes it with all sixteen experts held."""
    cfg = tiny_cfg(held=(0, 16))
    w = ref.init_weights(11, cfg)
    b = jax.random.normal(jax.random.PRNGKey(0), (B * T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(w, "l1.mlp/", b, cfg, "float32")
        shared = ref._gated(b, w["l1.mlp/S1"], w["l1.mlp/S3"],
                            w["l1.mlp/S2"], "float32")
        total = shared
        for first in range(0, 16, 4):
            layer = _expert_layer(cfg, (first, 4))
            _, state, _ = layer.initialize(jax.random.PRNGKey(0), (T, 32),
                                           jnp.float32)
            y, _, _ = layer.apply(_expert_params(w, "l1.mlp/", (first, 4)),
                                  b, state)
            total = total + (y - shared)
        uncut, _, _ = _expert_layer(cfg, None).apply(
            _expert_params(w, "l1.mlp/"), b, {})
    close(total, whole)
    close(uncut, whole)


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    """Every token routed to experts 0 and 1: four times the rows a uniform
    routing sends here, walked in four chunks, equal to the reference."""
    cfg = tiny_cfg()
    w = dict(ref.init_weights(13, cfg))
    wr = np.zeros((32, 16), np.float32)
    wr[0, :2] = 10.0
    w["l1.mlp/Wr"] = jnp.asarray(wr)
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    b = b.at[:, 0].set(1.0)
    layer = _expert_layer(cfg, (0, 4))
    assert layer.chunk_rows(64) == 40          # 128 rows: four chunks
    _, state, _ = layer.initialize(jax.random.PRNGKey(0), (64, 32),
                                   jnp.float32)
    params = _expert_params(w, "l1.mlp/")
    with jax.default_matmul_precision("highest"):
        y, state, _ = layer.apply(params, b, state, train=True)
        want = ref._experts(w, "l1.mlp/", b, cfg, "float32")
        g = jax.grad(lambda p: jnp.sum(
            layer.apply(p, b, {}, train=True)[0] ** 2))(params)
        g_ref = jax.grad(lambda w_: jnp.sum(
            ref._experts(w_, "l1.mlp/", b, cfg, "float32") ** 2))(w)
    close(y, want)
    for k in ("W1", "W2", "Wr", "S1"):
        close(g[k], g_ref["l1.mlp/" + k], tol=5e-4)
    assert state["tokens"].tolist() == [64, 64, 0, 0]
    assert int(state["here"]) == 128 and int(state["elsewhere"]) == 0
    assert int(state["dropped"]) == 0


@pytest.mark.parametrize("routing,rows,mode", [
    ("router", 8, "auto"), ("router", 16, "force"),
    ("one_expert", 8, "auto"), ("one_expert", 16, "force")],
    ids=["router", "router_kernel", "one_expert", "one_expert_kernel"])
def test_chunks_do_not_change_the_result(monkeypatch, routing, rows, mode):
    """One chunk against chunks of ``rows``, the router's gradient
    included; with every token on one held expert too (two chunks or more).
    ``force`` takes the segment-sum kernel (interpret mode off the chip) in
    both, and both paths give the same sums."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    rng = jax.random.PRNGKey(2)
    x = jax.random.normal(rng, (32, 8))
    w1, w3 = jax.random.normal(rng, (2, 4, 8, 6)) * 0.3
    w2 = jax.random.normal(rng, (4, 6, 8)) * 0.3
    wr = jax.random.normal(rng, (8, 8))
    if routing == "one_expert":
        x = x.at[:, 0].set(1.0)
        wr = wr.at[0, 2].set(20.0)

    def run(x, rows):
        top_e, w = moe.route(x, wr, 2, 2.5)
        order, ends, tokens = moe.plan(top_e, 2, 4)
        out, n = moe.held_experts(x, w, w1, w3, w2, order, ends, rows, 2)
        return out, (n, ends[-1], tokens)

    monkeypatch.setattr(fa, "_state", {"mode": mode})
    (one, (n1, here, tokens)), (many, (n2, _, _)) = run(x, 64), run(x, rows)
    assert int(n1) == int(n2) == int(here)
    if routing == "one_expert":
        assert int(tokens[0]) == 32 and int(here) > 2 * rows
    close(one, many, tol=1e-5)
    g = lambda rows: jax.grad(lambda x: jnp.sum(run(x, rows)[0] ** 2))(x)
    g_many = g(rows)
    close(g(64), g_many, tol=1e-5)
    monkeypatch.setattr(fa, "_state", {"mode": "auto"})
    close(many, run(x, rows)[0], tol=1e-5)
    close(g_many, g(rows), tol=1e-5)


@pytest.mark.parametrize("t,same", [(8, True), (16, False)],
                         ids=["within_window", "beyond_window"])
def test_window_layer_against_full_layer(t, same):
    kw = dict(n_heads=4, n_kv_heads=2, head_size=8, gated=True)
    full = CausalSelfAttentionLayer(**kw)
    win = CausalSelfAttentionLayer(window=8, **kw)
    params, _, _ = full.initialize(jax.random.PRNGKey(0), (t, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 32))
    a, b = full.apply(params, x, {})[0], win.apply(params, x, {})[0]
    if same:
        close(a, b, tol=1e-6)
    else:
        close(a[:, :8], b[:, :8], tol=1e-6)
        assert float(jnp.abs(a[:, 8:] - b[:, 8:]).max()) > 1e-3


def test_head_counts_differ_by_layer(world):
    shapes = {k: v.shape for k, v in flat(world["net"].params).items()}
    for i, heads in enumerate([4, 6, 6, 6, 4]):
        assert shapes[f"l{i}.attn/Wq"] == (32, heads * 8)
        assert shapes[f"l{i}.attn/Wg"] == (32, heads * 8)
        assert shapes[f"l{i}.attn/Wk"] == (32, 16)
        assert shapes[f"l{i}.attn/Wo"] == (heads * 8, 32)
    assert shapes["l0.mlp/W1"] == (32, 64)
    assert shapes["l1.mlp/Wr"] == (32, 16)          # the router keeps its width
    assert shapes["l1.mlp/W1"] == (4, 32, 16)       # the experts held


PUBLISHED = {"head_dim": 128, "num_key_value_heads": 8, "sliding_window": 512,
             "gating": True, "rope_parameters": ROPE,
             "layer_types": ["full_attention", "sliding_attention"],
             "num_attention_heads_per_layer": [48, 64]}


def test_window_rotary_is_the_default_closed_form():
    layer = attention_layer(PUBLISHED, 1)
    assert (layer.n_heads, layer.window, layer.rotary_dim) == (64, 512, 128)
    want = 10000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(layer.inv_freq(), want, rtol=1e-12)
    inv, scale = ref._inv_freq(ROPE["sliding_attention"], 128)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert scale == 1.0


def test_full_rotary_is_yarn_in_closed_form():
    """64 rotated dimensions, base 5e5, factor 64 over 4,096 positions:
    pairs 0-5 turn more than 64 times and keep their frequency, pairs 16-31
    are slowed 64-fold, a linear ramp between."""
    layer = attention_layer(PUBLISHED, 0)
    assert (layer.n_heads, layer.window, layer.rotary_dim) == (48, None, 64)
    base = 500000.0 ** (-np.arange(32) / 32.0)
    low = math.floor(64 * math.log(4096 / (64 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (5, 16)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = base / 64 * ramp + base * (1 - ramp)
    got = layer.inv_freq()
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:6], base[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], base[16:] / 64, rtol=1e-12)
    inv, scale = ref._inv_freq(ROPE["full_attention"], 128)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert scale == layer.rope_attention_factor == 1.4158883083359672


def test_rotation_of_a_unit_vector():
    inv = ca.default_inv_freq(4, 10000.0)
    cos, sin = ca.rotary_tables(5, inv, 1.5)
    x = jnp.zeros((1, 5, 1, 6)).at[..., 0].set(1.0).at[..., 5].set(7.0)
    y = np.asarray(ca.apply_rotary(x, cos, sin))[0, :, 0]
    pos = np.arange(5)
    np.testing.assert_allclose(y[:, 0], 1.5 * np.cos(pos * inv[0]), atol=1e-6)
    np.testing.assert_allclose(y[:, 2], 1.5 * np.sin(pos * inv[0]), atol=1e-6)
    np.testing.assert_allclose(y[:, 1], 0, atol=1e-6)
    np.testing.assert_allclose(y[:, 5], 7.0)           # past the rotary share


@pytest.mark.parametrize("window,decision", [(None, "blocked_rows"),
                                             (8, "blocked_pairs"),
                                             (20, "blocked_rows")])
def test_blocked_attention_equals_one_block(window, decision):
    k0 = jax.random.PRNGKey(4)
    q = jax.random.normal(k0, (2, 32, 6, 8))
    k, v = jax.random.normal(jax.random.fold_in(k0, 1), (2, 2, 32, 2, 8))

    def run(block):
        return lambda q, k, v: ca.causal_attention(q, k, v, window=window,
                                                   block=block)

    # a sequence that tiles and goes to XLA says why: off the chip, in `auto`
    labels = dict(kind="full" if window is None else "window",
                  rope="default", decision=decision, why="platform")
    before = tel.registry.get("attention.dispatch").value(**labels)
    close(run(8)(q, k, v), run(32)(q, k, v), tol=1e-5)
    after = tel.registry.get("attention.dispatch").value(**labels)
    assert after == before + 1
    for arg in range(3):
        g = lambda block: jax.grad(
            lambda *a: jnp.sum(run(block)(*a) ** 2), argnums=arg)(q, k, v)
        close(g(8), g(32), tol=1e-5)


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

    names = ("moe.tokens", "moe.assignments", "moe.dropped",
             "attention.dispatch", "fused_epilogues.dispatch")
    before = {n: total(n) for n in names}
    here0 = total("moe.assignments", where="here")
    net.fit_on_device(rows, np.ones((2 * B, 1), np.float32), epochs=1,
                      batch_size=B)
    grew = {n: total(n) - before[n] for n in names}
    sparse, steps = 4, 2
    assert grew["moe.assignments"] == sparse * steps * B * T * 2
    assert grew["moe.tokens"] == total("moe.assignments", where="here") - here0
    assert 0 < grew["moe.tokens"] < grew["moe.assignments"]
    assert grew["moe.dropped"] == 0
    assert grew["attention.dispatch"] >= LAYERS     # once a traced site
    assert grew["fused_epilogues.dispatch"] == 1    # the updater's decision
    layers = {dict(k).get("layer") for k in
              tel.registry.get("moe.tokens").series()}
    assert {f"l{i}.mlp" for i in range(1, 5)} <= layers


@pytest.mark.parametrize("i", [0, 1], ids=["full+dense", "window+sparse"])
def test_reference_layer_in_chunks_equals_one_chunk(monkeypatch, i):
    """The reference's own chunking (keys and values whole, then 8 positions
    at a time; the padded slice a window reaches) changes nothing."""
    cfg = tiny_cfg()
    w = ref.init_weights(17, cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (32, 32))

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


def test_the_shared_layout_lists_the_vertices_and_leaves_laguna_had(world):
    """``laguna()`` over ``models/decoder_stack.py``: vertex names, order,
    kinds and inputs as they were written out before the layout was shared
    (PR 31), every leaf's shape as the reference's table has it, and no
    selection bias in any expert layer's state."""
    net = world["net"]
    want = [("embed", "embedding", ["tokens"])]
    below = "embed"
    for i, mlp in enumerate(["gated_dense"] + ["sparse_experts"] * 4):
        p = f"l{i}."
        want += [(p + "attn_norm", "rms_norm", [below]),
                 (p + "attn", "causal_attention", [p + "attn_norm"]),
                 (p + "attn_res", "elementwise", [below, p + "attn"]),
                 (p + "mlp_norm", "rms_norm", [p + "attn_res"]),
                 (p + "mlp", mlp, [p + "mlp_norm"]),
                 (p + "mlp_res", "elementwise", [p + "attn_res", p + "mlp"])]
        below = p + "mlp_res"
    want += [("norm", "rms_norm", [below]),
             ("lm_head", "causal_lm_output", ["norm", "tokens"])]
    got = [(n, v.layer.kind if hasattr(v, "layer") else v.kind, list(ins))
           for n, v, ins in net.conf.vertices]
    assert got == want
    assert net._topo == [n for n, _, _ in want]
    assert {k: tuple(v.shape) for k, v in flat(net.params).items()} == \
        {n: tuple(s) for n, s, _ in ref.layer_table(world["cfg"])}
    assert {k: sorted(s) for k, s in net.state.items()} == {
        f"l{i}.mlp": ["dropped", "elsewhere", "here", "tokens"]
        for i in range(1, LAYERS)}
    assert VERTICES_PER_LAYER == 6
