"""Workspace-mode rematerialization (ISSUE 4): the activation-checkpoint
policies must be NUMERICALLY INVISIBLE — remat on/off produces equal losses
and parameters on every engine/topology combination (dropout rng stream
included), composing with accum_steps, the on-device epoch scan, and the
ZeRO-1 sharded update on the 8-device CPU mesh (conftest) — while the
compiled-HBM accounting (``memory_report``/``max_batch``) shows the
activation bytes actually shrinking. memory_analysis-dependent assertions
skip-guard on PJRT builds without the API (ISSUE 4 satellite)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models.decoder_stack import decoder_stack
from deeplearning4j_tpu.nn import memory as memmod
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import decoder as decmod
from deeplearning4j_tpu.nn.layers.core import (DenseLayer, DropoutLayer,
                                               OutputLayer)
from deeplearning4j_tpu.nn.layers.decoder import (CausalSelfAttentionLayer,
                                                  GatedDenseLayer,
                                                  LatentAttentionLayer)
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.ops import lm_loss
from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
from deeplearning4j_tpu.runtime import telemetry as tel

ATOL = 1e-6
MODES = ("none", "full", "dots_saveable", "every_2")

needs_memory_analysis = pytest.mark.skipif(
    not memmod.memory_analysis_supported(),
    reason="this PJRT build exposes no Compiled.memory_analysis()")


def _mln_conf(mode, seed=11, dropout=False):
    layers = [DenseLayer(n_out=24, activation="tanh")]
    if dropout:
        layers.append(DropoutLayer(rate=0.25))
    layers += [DenseLayer(n_out=24, activation="relu"),
               DenseLayer(n_out=16, activation="tanh"),
               OutputLayer(n_out=4)]
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-2))
            .input_type(InputType.feed_forward(8))
            .workspace_mode(mode)
            .list(*layers).build())


def _graph_conf(mode, seed=12):
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-2))
            .workspace_mode(mode)
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .add_layer("d1", DenseLayer(n_out=16, activation="tanh"), "in")
            .add_layer("drop", DropoutLayer(rate=0.25), "d1")
            .add_layer("d2", DenseLayer(n_out=16, activation="tanh"), "drop")
            .add_layer("d3", DenseLayer(n_out=16, activation="relu"), "d2")
            .add_vertex("res", ElementWiseVertex(op="add"), "d1", "d3")
            .add_layer("out", OutputLayer(n_out=4), "res")
            .set_outputs("out")
            .build())


def _data(n=64, seed=0, nin=8, nout=4):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, nin)).astype(np.float32)
    y = np.eye(nout, dtype=np.float32)[rng.integers(0, nout, n)]
    return x, y


def _assert_tree_close(a, b, atol=ATOL):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=atol)


def _mini_transformer_sd(mode, blocks=3, d=32, seed=3):
    """Attention-shaped SameDiff graph: q/k/v mmul -> scale -> softmax ->
    ctx mmul -> 4x FFN per block (the importer spelling fusion/remat
    anchor on)."""
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    rng = np.random.default_rng(seed)
    sd = SameDiff.create()
    x = sd.placeholder("x")
    h = x
    for l in range(blocks):
        wq = sd.var(f"wq{l}", rng.normal(0, 0.1, (d, d)).astype(np.float32))
        wk = sd.var(f"wk{l}", rng.normal(0, 0.1, (d, d)).astype(np.float32))
        wv = sd.var(f"wv{l}", rng.normal(0, 0.1, (d, d)).astype(np.float32))
        wf = sd.var(f"wf{l}",
                    rng.normal(0, 0.1, (d, 4 * d)).astype(np.float32))
        wo = sd.var(f"wo{l}",
                    rng.normal(0, 0.1, (4 * d, d)).astype(np.float32))
        q, k, v = h.mmul(wq), h.mmul(wk), h.mmul(wv)
        s = sd.call("linalg.mmul", q, k, attrs={"transpose_b": True})
        s = s / float(np.sqrt(d))
        p = sd.softmax(s)
        ctx = sd.call("linalg.mmul", p, v)
        ff = sd.relu(ctx.mmul(wf))
        h = h + ff.mmul(wo)
    pooled = h.mean(axis=1)
    wc = sd.var("wc", rng.normal(0, 0.1, (d, 4)).astype(np.float32))
    y = sd.placeholder("y")
    sd.set_loss(sd.call("loss.softmax_ce_logits", y, pooled.mmul(wc)))
    sd.set_updater(Adam(learning_rate=1e-3))
    sd.set_workspace_mode(mode)
    return sd


def _sd_feeds(batch=8, T=16, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(batch, T, d)).astype(np.float32),
            "y": np.eye(4, dtype=np.float32)[rng.integers(0, 4, batch)]}


# ---- policy registry -------------------------------------------------------

def test_policy_registry():
    assert not memmod.resolve_policy(None).remat
    assert not memmod.resolve_policy("none").remat
    assert not memmod.resolve_policy("NONE").remat
    full = memmod.resolve_policy("FULL")
    assert full.remat and full.every == 1 and full.saveable is None
    # DL4J WorkspaceMode.ENABLED parity alias
    assert memmod.resolve_policy("enabled").name == "full"
    dots = memmod.resolve_policy("dots_saveable")
    assert dots.remat and dots.saveable is not None
    ek = memmod.resolve_policy("every_3")
    assert ek.remat and ek.every == 3
    for bad in ("bogus", "every_0", "every_x", "every_"):
        with pytest.raises(ValueError):
            memmod.resolve_policy(bad)
    assert "every_<k>" in memmod.workspace_modes()


def test_segment_ranges():
    assert memmod.segment_ranges(5, 2) == [(0, 2), (2, 4), (4, 5)]
    assert memmod.segment_ranges(3, 1) == [(0, 1), (1, 2), (2, 3)]
    assert memmod.segment_ranges(0, 4) == []


def test_builder_validates_workspace_mode():
    with pytest.raises(ValueError):
        NeuralNetConfiguration.builder().workspace_mode("bogus")


def test_config_json_round_trip_keeps_mode():
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    conf = _mln_conf("every_2")
    assert MultiLayerConfiguration.from_json(
        conf.to_json()).workspace_mode == "every_2"
    from deeplearning4j_tpu.nn.graph import ComputationGraphConfiguration
    gconf = _graph_conf("dots_saveable")
    assert ComputationGraphConfiguration.from_json(
        gconf.to_json()).workspace_mode == "dots_saveable"


# ---- engine equivalence ----------------------------------------------------

@pytest.mark.parametrize("mode", MODES[1:])
def test_mln_remat_loss_equivalence(mode):
    """Remat on/off is numerically invisible on the sequential engine —
    dropout included (the rng stream threads through segments with the
    plain walk's exact split sequence)."""
    memmod.mark_policy_tested(mode)
    x, y = _data()
    ds = DataSet(x, y)
    ref = MultiLayerNetwork(_mln_conf("none", dropout=True)).init()
    net = MultiLayerNetwork(_mln_conf(mode, dropout=True)).init()
    for _ in range(3):
        ref.fit(ds)
        net.fit(ds)
    assert net.score() == pytest.approx(ref.score(), abs=ATOL)
    _assert_tree_close(net.params, ref.params)


@pytest.mark.parametrize("mode", MODES[1:])
def test_graph_remat_loss_equivalence(mode):
    """Same on the DAG engine, with a skip connection SPANNING segment
    boundaries (liveness carry) and a dropout vertex (rng parity)."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    x, y = _data()
    ds = DataSet(x, y)
    ref = ComputationGraph(_graph_conf("none")).init()
    net = ComputationGraph(_graph_conf(mode)).init()
    for _ in range(3):
        ref.fit(ds)
        net.fit(ds)
    assert net.score() == pytest.approx(ref.score(), abs=ATOL)
    _assert_tree_close(net.params, ref.params)


def test_mln_remat_epoch_scan_equivalence():
    """The on-device epoch loop (lax.scan of the fused step) inherits the
    remat policy through _build_train_step — losses match none exactly."""
    x, y = _data(64)
    ref = MultiLayerNetwork(_mln_conf("none")).init()
    net = MultiLayerNetwork(_mln_conf("full")).init()
    h0 = ref.fit_on_device(x, y, epochs=2, batch_size=16)
    h1 = net.fit_on_device(x, y, epochs=2, batch_size=16)
    np.testing.assert_allclose(h1, h0, rtol=0, atol=ATOL)
    _assert_tree_close(net.params, ref.params)


def test_remat_accum_steps_equivalence():
    """remat composes with gradient micro-accumulation: accumulated remat
    step == accumulated plain step (same weighting, same scan)."""
    x, y = _data(32)
    args = (jnp.int32(0), jax.random.PRNGKey(0), jnp.asarray(x),
            jnp.asarray(y), None, None)
    ref = MultiLayerNetwork(_mln_conf("none")).init()
    net = MultiLayerNetwork(_mln_conf("full")).init()
    p0, _, _, l0 = ref._build_train_step(accum_steps=4)(
        ref.params, ref.updater_state, ref.state, *args)
    p1, _, _, l1 = net._build_train_step(accum_steps=4)(
        net.params, net.updater_state, net.state, *args)
    assert float(l1) == pytest.approx(float(l0), abs=ATOL)
    _assert_tree_close(p1, p0)


def test_remat_shard_update_mesh_equivalence():
    """remat + ZeRO-1 sharded update + accum on the 8-device mesh: the
    GSPMD pipeline must be oblivious to the checkpoint restructuring."""
    x, y = _data(64)
    ds = DataSet(x, y)
    ref = MultiLayerNetwork(_mln_conf("none")).init()
    ParallelWrapper(ref, shard_update=True, accum_steps=2).fit(ds, epochs=2)
    net = MultiLayerNetwork(_mln_conf("full")).init()
    ParallelWrapper(net, shard_update=True, accum_steps=2).fit(ds, epochs=2)
    assert net.score() == pytest.approx(ref.score(), abs=1e-5)
    _assert_tree_close(net.params, ref.params, atol=1e-5)


def test_remat_ragged_tail_matches_unpadded_step():
    """The r6 weighted-accumulation regression stays exact under remat:
    9 real rows on the 8-mesh with accum_steps=4 pad to 32 (two
    microbatches ALL padding) — the remat step must still reproduce the
    plain unpadded single step."""
    x, y = _data(9)
    ds = DataSet(x, y)
    ref = MultiLayerNetwork(_mln_conf("none")).init()
    ref.fit(ds, epochs=1)  # plain single-chip step on the 9 real rows
    net = MultiLayerNetwork(_mln_conf("full")).init()
    ParallelWrapper(net, accum_steps=4).fit(ds, epochs=1)
    _assert_tree_close(net.params, ref.params, atol=1e-5)
    _assert_tree_close(net.updater_state, ref.updater_state, atol=1e-5)


def test_set_workspace_mode_invalidates_and_retraces():
    """Mutating the policy in place must drop every cached trace (the old
    step baked the policy in) and keep training numerically on-track."""
    x, y = _data()
    ds = DataSet(x, y)
    net = MultiLayerNetwork(_mln_conf("none")).init()
    net.fit(ds)
    assert net._train_step is not None
    net.set_workspace_mode("every_2")
    assert net._train_step is None
    assert net.conf.workspace_mode == "every_2"
    net.fit(ds)  # retraces with remat, continues fine
    ref = MultiLayerNetwork(_mln_conf("none")).init()
    ref.fit(ds)
    ref.fit(ds)
    assert net.score() == pytest.approx(ref.score(), abs=ATOL)
    with pytest.raises(ValueError):
        net.set_workspace_mode("bogus")
    assert net.conf.workspace_mode == "every_2"  # failed set didn't mutate


# ---- SameDiff (imported-graph) engine --------------------------------------

def test_samediff_anchor_segmentation():
    from deeplearning4j_tpu.autodiff import remat as sdremat
    sd = _mini_transformer_sd("full")
    anchors = sdremat.attention_anchors(sd)
    assert len(anchors) == 3  # one per block (softmax matched via fusion)
    bounds = sdremat.segment_bounds(sd, memmod.resolve_policy("full"))
    assert bounds[0][0] == 0 and bounds[-1][1] == len(sd._ops)
    assert len(bounds) == 3
    # every_2: two anchors per segment -> 2 segments
    b2 = sdremat.segment_bounds(sd, memmod.resolve_policy("every_2"))
    assert len(b2) == 2
    # anchorless graph falls back to sqrt chunks covering everything
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    plain = SameDiff.create()
    px = plain.placeholder("x")
    w = plain.var("w", np.ones((4, 4), np.float32))
    out = px.mmul(w)
    for _ in range(6):
        out = plain.relu(out)
    bounds = sdremat.segment_bounds(plain, memmod.resolve_policy("full"))
    assert bounds[0][0] == 0 and bounds[-1][1] == len(plain._ops)
    assert all(e1 == s2 for (_, e1), (s2, _) in zip(bounds, bounds[1:]))


@pytest.mark.parametrize("mode", MODES[1:])
def test_samediff_remat_loss_equivalence(mode):
    memmod.mark_policy_tested(mode)
    feeds = _sd_feeds()
    ref = _mini_transformer_sd("none").fit([feeds], epochs=4)
    got = _mini_transformer_sd(mode).fit([feeds], epochs=4)
    np.testing.assert_allclose(got.losses, ref.losses, rtol=0, atol=ATOL)


def test_samediff_fused_attention_remat():
    """After fuse_attention the anchors are the fused_sdpa ops themselves;
    remat must train through the fused custom-VJP identically."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_attention
    feeds = _sd_feeds()
    ref = _mini_transformer_sd("none")
    rep = fuse_attention(ref)
    assert rep.matched == 3
    h0 = ref.fit([feeds], epochs=3)
    net = _mini_transformer_sd("full")
    assert fuse_attention(net).matched == 3
    from deeplearning4j_tpu.autodiff import remat as sdremat
    assert len(sdremat.attention_anchors(net)) == 3
    h1 = net.fit([feeds], epochs=3)
    np.testing.assert_allclose(h1.losses, h0.losses, rtol=0, atol=ATOL)


def test_samediff_policy_in_fit_spec():
    """Satellite: the workspace mode is part of the fit-step cache spec —
    stable policy reuses ONE compiled step (zero recompiles after warmup),
    mutating it clears the cache and retraces."""
    feeds = _sd_feeds()
    sd = _mini_transformer_sd("none")
    sd.fit(feeds, epochs=1)
    step1 = sd._fn_cache["__fit_step__"][1]
    sd.fit(feeds, epochs=2)
    assert sd._fn_cache["__fit_step__"][1] is step1  # no recompile
    sd.set_workspace_mode("full")
    assert "__fit_step__" not in sd._fn_cache  # remat-built fn cleared
    sd.fit(feeds, epochs=1)
    step2 = sd._fn_cache["__fit_step__"][1]
    assert step2 is not step1
    sd.fit(feeds, epochs=1)
    assert sd._fn_cache["__fit_step__"][1] is step2  # stable again
    with pytest.raises(ValueError):
        sd.set_workspace_mode("bogus")


def test_samediff_serde_keeps_mode(tmp_path):
    sd = _mini_transformer_sd("every_2")
    p = str(tmp_path / "t.sdz")
    sd.save(p)
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    assert SameDiff.load(p).workspace_mode == "every_2"


# ---- compiled HBM accounting ----------------------------------------------

def test_residual_accounting_reduction():
    """The backend-independent accounting: remat must cut the saved
    forward→backward activation bytes by >=30% on every engine (the
    ISSUE 4 acceptance bar; measured on the train-step loss itself)."""
    memmod.mark_policy_tested("none")
    memmod.mark_policy_tested("full")
    x, y = _data()
    for conf_fn, Model in (
            (_mln_conf, MultiLayerNetwork),
            (_graph_conf, None)):
        if Model is None:
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            Model = ComputationGraph
        r0 = Model(conf_fn("none")).init().memory_report(64)
        r1 = Model(conf_fn("full")).init().memory_report(64)
        assert r0["activation_bytes"] and r1["activation_bytes"]
        assert r1["activation_bytes"] < 0.7 * r0["activation_bytes"]
    # SameDiff engine, attention-anchored segmentation
    feeds = _sd_feeds()
    s0 = _mini_transformer_sd("none").memory_report(feeds)
    s1 = _mini_transformer_sd("full").memory_report(feeds)
    assert s1["activation_bytes"] < 0.7 * s0["activation_bytes"]
    assert s0["batch_size"] == 8


@needs_memory_analysis
def test_memory_report_compiled_fields():
    net = MultiLayerNetwork(_mln_conf("none")).init()
    rep = net.memory_report(32)
    assert rep["temp_bytes"] > 0
    assert rep["argument_bytes"] > 0
    assert rep["peak_bytes"] >= rep["temp_bytes"]
    assert rep["workspace_mode"] == "none"
    assert rep["batch_size"] == 32
    # device telemetry degrades gracefully (None on CPU)
    assert rep["device"] is None or "bytes_limit" in rep["device"]


@needs_memory_analysis
def test_max_batch_against_synthetic_limit():
    """Binary-search autotuning: the limit is set between the batch-16 and
    batch-32 footprints, so exactly 16 must come back — and nothing was
    executed (no OOM probing, just AOT compiles)."""
    net = MultiLayerNetwork(_mln_conf("none")).init()
    p16 = net.memory_report(16)["peak_bytes"]
    p32 = net.memory_report(32)["peak_bytes"]
    assert p32 > p16
    limit = (p16 + p32) // 2
    assert net.max_batch(limit, start=4, limit=256) == 16
    assert net.max_batch(p16 - 1, start=16, limit=256) is None


def test_max_batch_requires_limit_without_device_stats():
    net = MultiLayerNetwork(_mln_conf("none")).init()
    if memmod.device_memory_stats() is None:
        with pytest.raises(ValueError):
            net.max_batch()


@needs_memory_analysis
def test_parallel_wrapper_memory_report():
    net = MultiLayerNetwork(_mln_conf("full")).init()
    pw = ParallelWrapper(net, shard_update=True, accum_steps=2)
    rep = pw.memory_report(64)
    assert rep["temp_bytes"] > 0
    assert rep["shard_update"] is True and rep["accum_steps"] == 2
    assert rep["devices"] == 8
    assert rep["workspace_mode"] == "full"


@needs_memory_analysis
def test_serving_engine_max_batch_and_auto_warmup():
    """Serving-side autotune: max_batch honors an explicit bytes_limit,
    probe compiles never pollute the executable cache/counters, and
    warmup(buckets='auto') warms the ladder up to the autotuned ceiling."""
    from deeplearning4j_tpu.nn import memory as _memory
    net = MultiLayerNetwork(_mln_conf("none")).init()
    eng = net.inference_engine()
    xs, ms = eng._bucket_avals(16, None)
    cm = _memory.compiled_memory(
        jax.jit(eng._forward_fn()).lower(
            jax.eval_shape(lambda: net.params),
            jax.eval_shape(lambda: net.state),
            tuple(xs), tuple(ms)).compile())
    limit = cm["peak_bytes"] + 1
    from deeplearning4j_tpu.runtime import telemetry as _tel
    probes_before = _tel.counter("compile.events").value(
        site="serving.engine", cause="probe")
    assert eng.max_batch(bytes_limit=limit) == 16
    st = eng.stats()
    assert st["compiles"] == 0 and st["compiled_buckets"] == 0
    # probes bypass serving counters but the retrace tracker still sees
    # every lower+compile (cause="probe") so compile time stays explainable
    assert _tel.counter("compile.events").value(
        site="serving.engine", cause="probe") > probes_before
    eng.warmup(buckets="auto", bytes_limit=limit)
    assert eng.stats()["compiled_buckets"] == 5  # 1,2,4,8,16
    out = eng.output(np.zeros((5, 8), np.float32))
    assert out.shape == (5, 4)
    assert eng.stats()["compiles"] == 5  # serving never compiled again


def test_serving_max_batch_requires_limit_on_cpu():
    net = MultiLayerNetwork(_mln_conf("none")).init()
    eng = net.inference_engine()
    if memmod.device_memory_stats() is None:
        with pytest.raises(ValueError):
            eng.max_batch()


# ---- telemetry -------------------------------------------------------------

def test_performance_listener_memory_fields():
    """Satellite: PerformanceListener emits memory_stats fields per report
    interval and returns None gracefully on backends (CPU) without the
    API — the message never breaks either way."""
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener
    msgs = []
    pl = PerformanceListener(frequency=1, batch_size=64,
                             printer=msgs.append)
    x, y = _data()
    ds = DataSet(x, y)
    net = MultiLayerNetwork(_mln_conf("none")).init()
    net.set_listeners(pl)
    net.fit(ds)
    net.fit(ds)
    assert msgs  # reported at least once
    dm = memmod.device_memory_stats()
    if dm is None:
        assert pl.last_memory is None
        assert not any("hbm" in m for m in msgs)
    else:
        assert pl.last_memory["bytes_limit"] == dm["bytes_limit"]
        assert any("hbm" in m for m in msgs)


def test_device_memory_stats_shape():
    dm = memmod.device_memory_stats()
    if dm is not None:  # TPU/GPU path
        assert set(dm) == {"bytes_in_use", "peak_bytes_in_use",
                           "bytes_limit"}


def test_policy_ledger_marks():
    """Feed the coverage floor (test_zz_coverage_floor): every policy
    family in the registry is exercised by this file's equivalence tests."""
    for m in MODES:
        memmod.mark_policy_tested(m)
    rep = memmod.policy_coverage_report()
    assert not rep["untested"], rep
    assert rep["coverage"] == 1.0


# ---- the attention output a segment keeps (ISSUE 38) ----------------------
# A recomputing policy keeps arrays tagged ``memmod.KEPT``; the two attention
# layers of nn/layers/decoder.py tag their heads' output where it is no wider
# than twice the layer's input.

KEPT_T, KEPT_HIDDEN = 24, 32
ATTENTION = {
    "full": lambda heads=4: CausalSelfAttentionLayer(
        n_heads=heads, n_kv_heads=2, head_size=8),
    "window": lambda heads=4: CausalSelfAttentionLayer(
        n_heads=heads, n_kv_heads=2, head_size=8, window=8),
    "latent": lambda heads=4: LatentAttentionLayer(
        n_heads=heads, nope_head_size=8, rope_head_size=4, v_head_size=8,
        kv_rank=16),
}
#: (mask kind, positions, passes) -> the path ``causal_attention`` takes and
#: the products of one attention forward that leave the recomputation. In
#: one block the scores are what the backward pass keeps, so only the value
#: product goes; a blocked path's blocks are checkpoints of their own and
#: the whole forward goes: two products a block of rows (two blocks of 1,024
#: at 2,048 positions), two batched ones for a window inside a block (the
#: XLA block is 128 under a window of 8).
KEPT_CASES = {
    "full": ("full", KEPT_T, 1, "one_block", 1),
    "window": ("window", KEPT_T, 1, "one_block", 1),
    "latent": ("latent", KEPT_T, 1, "one_block", 1),
    "repeated_run": ("full", KEPT_T, 3, "one_block", 1),
    "full_blocked_rows": ("full", 2048, 1, "blocked_rows", 4),
    "window_blocked_pairs": ("window", 256, 1, "blocked_pairs", 2),
    "latent_blocked_rows": ("latent", 2048, 1, "blocked_rows", 4),
}


def _decoder(kind, mode="every_6", heads=4, passes=1, t=KEPT_T):
    net = decoder_stack(
        vocab_size=40, hidden_size=KEPT_HIDDEN, n_layers=2, eps=1e-6,
        attention=lambda i: ATTENTION[kind](heads),
        mlp=lambda i: GatedDenseLayer(n_hidden=48), seq_len=t,
        workspace_mode=mode, passes=passes, seed=38).init()
    ids = np.random.default_rng(38).integers(0, 40, (2, t), dtype=np.int32)
    return net, ids


def _decoder_grad(net, ids):
    """The jitted gradient of the net's own training loss, and its
    arguments."""
    loss_fn = net._build_loss_fn()
    y = jnp.ones((ids.shape[0], 1), jnp.float32)
    fn = jax.jit(jax.grad(lambda p: loss_fn(
        p, net.state, None, (jnp.asarray(ids),), (y,), (None,), (None,))[0]))
    return fn, net.params


def _kept(**labels):
    return tel.registry.get("attention.kept").value(**labels)


def _parent_checkpoint(fn, policy, prevent_cse=True):
    """``memory.checkpoint`` as it was before anything was kept by name,
    with the barrier against CSE around every segment."""
    return jax.checkpoint(fn, policy=policy.saveable) if policy.remat else fn


def _products(jaxpr):
    """``dot_general``s in a jaxpr, through every nested one (a scan's or a
    map's body counts once)."""
    return sum((eqn.primitive.name == "dot_general")
               + sum(_products(sub)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("case", list(KEPT_CASES))
def test_kept_output_leaves_the_gradients_bit_equal(monkeypatch, case):
    """A kept array is the array the forward pass computed, where the
    recomputation made an identical copy: every gradient leaf is equal to
    the last bit, on every path of ``causal_attention`` and where the
    segments lie in a repeated run's scanned pass body; and the compiled
    backward holds fewer FLOPs."""
    kind, t, passes, path, _ = KEPT_CASES[case]
    net, ids = _decoder(kind, passes=passes, t=t)
    before = _kept(kind=kind, decision="kept")
    path = dict(kind=kind, decision=path,
                **({} if path == "one_block" else {"why": "platform"}))
    took = tel.registry.get("attention.dispatch").value(**path)
    fn, params = _decoder_grad(net, ids)
    kept_flops = fn.lower(params).compile().cost_analysis()["flops"]
    kept = fn(params)
    assert _kept(kind=kind, decision="kept") > before
    assert tel.registry.get("attention.dispatch").value(**path) > took
    monkeypatch.setattr(decmod, "_keeps_output", lambda *a: False)
    before = _kept(kind=kind, decision="recomputed", why="wide")
    fn, params = _decoder_grad(net, ids)
    again_flops = fn.lower(params).compile().cost_analysis()["flops"]
    again = fn(params)
    assert _kept(kind=kind, decision="recomputed", why="wide") > before
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert kept_flops < again_flops


@pytest.mark.parametrize("case", list(KEPT_CASES))
def test_kept_output_takes_the_forward_out_of_the_recomputation(monkeypatch,
                                                                case):
    """Counted in the gradient's jaxpr: with the output kept, the products
    of the attention forward are gone from the segment's recomputation, in
    both layers, and no other product is."""
    kind, t, passes, _, gone = KEPT_CASES[case]
    net, ids = _decoder(kind, passes=passes, t=t)
    fn, params = _decoder_grad(net, ids)
    kept = _products(jax.make_jaxpr(fn)(params).jaxpr)
    monkeypatch.setattr(decmod, "_keeps_output", lambda *a: False)
    fn, params = _decoder_grad(net, ids)
    again = _products(jax.make_jaxpr(fn)(params).jaxpr)
    assert again - kept == 2 * gone, (kept, again)


@pytest.mark.parametrize("heads,times,decision", [
    (4, 1, "kept"), (8, 2, "kept"), (12, 3, "recomputed")],
    ids=["1x", "2x", "3x"])
@pytest.mark.parametrize("kind", ["full", "latent"])
def test_a_layer_keeps_up_to_twice_its_input(monkeypatch, kind, heads, times,
                                             decision):
    """The rule reads the layer's own shapes: heads x value width against
    the hidden size. A layer three times as wide recomputes as before
    (``why=wide``) and its program lowers to the text it had when the
    policies kept nothing by name: with the loss head's tags taken out
    (``ops/lm_loss.py`` keeps its gradients under the same name in every
    segment), so that attention's are the only ones."""
    assert heads * 8 == times * KEPT_HIDDEN
    monkeypatch.setattr(lm_loss, "checkpoint_name", lambda a, name: a)
    net, ids = _decoder(kind, heads=heads)
    labels = dict(kind=kind, decision=decision)
    if decision == "recomputed":
        labels["why"] = "wide"
    before = _kept(**labels)
    fn, params = _decoder_grad(net, ids)
    text = fn.lower(params).as_text()
    assert _kept(**labels) == before + 2                # one a layer
    monkeypatch.setattr(memmod, "checkpoint", _parent_checkpoint)
    fn, params = _decoder_grad(net, ids)
    parent = fn.lower(params).as_text()
    assert (text == parent) == (decision == "recomputed")


def test_nothing_is_kept_without_a_recomputing_policy():
    """Outside a recomputed segment nothing runs twice: the site counts
    ``why=no_policy`` and carries no tag, in training under ``none`` and in
    ``output()``."""
    net, ids = _decoder("full", mode=None)
    before = _kept(kind="full", decision="recomputed", why="no_policy")
    fn, params = _decoder_grad(net, ids)
    jaxpr = jax.make_jaxpr(fn)(params)
    assert "attention.kept" not in str(jaxpr)
    net.output(ids)
    assert _kept(kind="full", decision="recomputed",
                 why="no_policy") == before + 4
    assert not memmod.recomputing()


@pytest.mark.parametrize("mode", ["full", "dots_saveable", "every_2"])
def test_kept_output_under_every_recomputing_policy(monkeypatch, mode):
    """``full``, ``every_<k>`` and ``dots_saveable`` (joined with the name
    by ``save_from_both_policies``) all keep the tagged output: the backward
    holds fewer products than with the tag left off, and the gradients are
    equal."""
    memmod.mark_policy_tested(mode)
    net, ids = _decoder("full", mode=mode)
    fn, params = _decoder_grad(net, ids)
    kept_n = _products(jax.make_jaxpr(fn)(params).jaxpr)
    kept = fn(params)
    monkeypatch.setattr(decmod, "_keeps_output", lambda *a: False)
    fn, params = _decoder_grad(net, ids)
    again_n = _products(jax.make_jaxpr(fn)(params).jaxpr)
    again = fn(params)
    # one value product a layer; under dots_saveable every product was kept
    # already and there is nothing to gain
    assert again_n - kept_n == (0 if mode == "dots_saveable" else 2)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", ["full", "dots_saveable", "every_2"])
@pytest.mark.parametrize("engine", ["mln", "graph", "samediff"])
def test_untagged_programs_lower_as_before(monkeypatch, engine, mode):
    """A policy that keeps a name no array carries lowers to the program
    ``policy=None`` gave, byte for byte: a ``MultiLayerNetwork``, a
    ``ComputationGraph`` of dense layers and a SameDiff graph's segments,
    none of which tags anything. ``dots_saveable`` joined with the name
    decides as ``dots_saveable`` alone does, but jax then emits the SameDiff
    graph's identical private ``_where`` functions once a call site, so that
    policy is held to the same functions with the copies folded."""
    def text_of(lowered):
        if mode != "dots_saveable":
            return lowered.as_text()
        import re
        funcs = [re.sub(r"[\s}]+$", "", f) for f in re.sub(
            r"@(\w+?)_\d+\(", r"@\1(", lowered.as_text()).split(
                "\n  func.func ")]
        return funcs[:2], set(funcs[2:])           # header, main; the rest

    def lowered():
        if engine == "samediff":
            sd = _mini_transformer_sd(mode)
            names = [n for n, v in sd._vars.items() if v.kind == "VARIABLE"]
            tv = {n: sd._values[n] for n in names}
            ov = {n: v for n, v in sd._values.items() if n not in tv}
            loss_fn = sd._fit_loss_fn()
            return text_of(jax.jit(jax.grad(loss_fn)).lower(
                tv, ov, _sd_feeds()))
        x, y = _data(16)
        if engine == "mln":
            net = MultiLayerNetwork(_mln_conf(mode)).init()
            args = (jnp.asarray(x), jnp.asarray(y), None, None)
        else:
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            net = ComputationGraph(_graph_conf(mode)).init()
            args = ((jnp.asarray(x),), (jnp.asarray(y),), (None,), (None,))
        loss_fn = net._build_loss_fn()
        return text_of(jax.jit(jax.grad(lambda p: loss_fn(
            p, net.state, jax.random.PRNGKey(0), *args)[0])).lower(
                net.params))

    text = lowered()
    monkeypatch.setattr(memmod, "checkpoint", _parent_checkpoint)
    assert text == lowered()


def _remats(jaxpr, in_scan=False):
    """(prevent_cse, lies in a scan's body) of every ``jax.checkpoint``
    equation of a jaxpr, through the nested ones."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2":
            yield eqn.params["prevent_cse"], in_scan
        inside = in_scan or eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _remats(sub, inside)


@pytest.mark.parametrize("passes", [1, 3], ids=["walked_once", "run"])
def test_segments_in_a_run_carry_no_cse_barrier(passes):
    """The segments of a repeated run's pass body lie under a scan, where
    the forward and the backward pass are two loops and nothing can merge
    the recomputation into the forward: they are checkpointed with
    ``prevent_cse=False``. Every segment outside a scan keeps the barrier."""
    net, ids = _decoder("full", passes=passes)
    fn, params = _decoder_grad(net, ids)
    seen = set(_remats(jax.make_jaxpr(fn)(params).jaxpr))
    assert (True, False) in seen                    # embedding, head
    assert ((False, True) in seen) == (passes > 1)
    # (True, True) is the loss head's own checkpoint of its chunks
    assert (False, False) not in seen
