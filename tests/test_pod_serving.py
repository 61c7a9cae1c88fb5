"""Tensor-parallel serving over the pod mesh (ISSUE 17).

The acceptance suite for sharded-single-replica serving, all on CPU
(8 virtual devices from conftest):

- placement-layer unit rules: dense/attention Megatron specs, quantized
  scale placement, head-sharded cache trees, per-device byte accounting,
  mesh cache keys;
- ``pod_mesh(model_span="pod")`` spanning + rejection messages;
- engine parity: TP engines (one-shot, contiguous generative, paged)
  match the single-device oracle — logits within tolerance for the
  one-shot path, greedy tokens EXACTLY for decode (the psum reorders
  float adds, so the contract is token-level);
- int8 weights and int8 KV compose with TP;
- per-device bytes == full / k (memory_report, cache_bytes, pool_bytes);
- zero post-warmup compiles under TP traffic, shard_map dispatch
  counted, attribution keys carry the mesh suffix;
- the prepare_write refcount-snapshot fast path: same forks as the
  locked per-page probe, hammered by concurrent pool readers (no lost
  CoW fork);
- the staticcheck mesh-label rule (both directions);
- slow: the 2-process pod sim serving phase (bit-equal tokens vs the
  single-device oracle under a one-host bytes_limit).
"""

import threading

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import quantize as q
from deeplearning4j_tpu.parallel import launcher
from deeplearning4j_tpu.parallel import placement as pl
from deeplearning4j_tpu.runtime import telemetry as tel
from deeplearning4j_tpu.serving.engine import (GenerativeEngine,
                                               InferenceEngine,
                                               PagedGenerativeEngine)

V = 16


def _mesh(k=2):
    return launcher.pod_mesh(model=k, devices=jax.devices()[:k])


def _lm(seed=5, heads=4):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=32, n_heads=heads),
                  DenseLayer(n_out=32, activation="relu"),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _mlp(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .input_type(InputType.feed_forward(8))
            .list(DenseLayer(n_out=32, activation="relu"),
                  OutputLayer(n_out=4, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _greedy_paged(eng, prompts, steps, page):
    """Engine-direct greedy decode over the paged engine; returns the
    per-slot token streams and drains the pool afterwards."""
    B = len(prompts)
    eye = np.eye(V, dtype=np.float32)
    state = eng.new_state(eng.max_cache_len)
    toks = [[] for _ in range(B)]
    last = np.zeros(B, np.int64)
    for s, ptoks in enumerate(prompts):
        pages = eng.pool.alloc(-(-len(ptoks) // page))
        eng.map_pages(state, s, pages)
        state, logits = eng.prefill(state, eye[ptoks], len(ptoks), s)
        last[s] = int(np.argmax(logits))
        toks[s].append(int(last[s]))
    active = np.ones(B, np.int32)
    for _ in range(steps - 1):
        snap = eng.pool.ref_snapshot()
        pairs = []
        for s in range(B):
            pairs += eng.prepare_write(state, s, 1, ref_snapshot=snap)
        state = eng.fork(state, pairs)
        state, y = eng.decode(state, eye[last][:, None, :], active)
        last = np.argmax(np.asarray(y), axis=-1)
        for s in range(B):
            toks[s].append(int(last[s]))
    used = sorted({int(p) for p in state.page_table.ravel() if p > 0})
    eng.pool.release(used)
    return toks


def _greedy_contiguous(eng, prompts, steps, cache_len=32):
    B = len(prompts)
    eye = np.eye(V, dtype=np.float32)
    state = eng.new_state(cache_len)
    toks = [[] for _ in range(B)]
    last = np.zeros(B, np.int64)
    for s, ptoks in enumerate(prompts):
        state, logits = eng.prefill(state, eye[ptoks], len(ptoks), s)
        last[s] = int(np.argmax(logits))
        toks[s].append(int(last[s]))
    active = np.ones(B, np.int32)
    for _ in range(steps - 1):
        state, y = eng.decode(state, eye[last][:, None, :], active)
        last = np.argmax(np.asarray(y), axis=-1)
        for s in range(B):
            toks[s].append(int(last[s]))
    return toks


def _prompts(rng, B=2):
    return [rng.integers(0, V, int(n)) for n in rng.integers(5, 12, B)]


# ---------------------------------------------------------------------------
# placement-layer unit rules
# ---------------------------------------------------------------------------

def test_dense_tp_spec():
    """Dense family: W column-sharded, b sharded, non-dense replicated."""
    W = np.zeros((8, 32), np.float32)
    b = np.zeros((32,), np.float32)
    assert pl.tp_param_spec(("0", "W"), W, "model", 2, {"0"}) == \
        P(None, "model")
    assert pl.tp_param_spec(("0", "b"), b, "model", 2, {"0"}) == P("model")
    # unknown layer key / inactive TP replicate
    assert pl.tp_param_spec(("1", "W"), W, "model", 2, {"0"}) == P()
    assert pl.tp_param_spec(("0", "W"), W, None, 2, {"0"}) == P()
    assert pl.tp_param_spec(("0", "W"), W, "model", 1, {"0"}) == P()


def test_attention_tp_spec():
    """Attention: Wq/Wk/Wv column, Wo row (one psum), biases aligned;
    indivisible head counts replicate the whole layer."""
    W = np.zeros((32, 32), np.float32)
    b = np.zeros((32,), np.float32)
    heads = {"0": 4}
    for name in ("Wq", "Wk", "Wv"):
        assert pl.tp_param_spec(("0", name), W, "model", 2, set(),
                                heads) == P(None, "model")
    for name in ("bq", "bk", "bv"):
        assert pl.tp_param_spec(("0", name), b, "model", 2, set(),
                                heads) == P("model")
    assert pl.tp_param_spec(("0", "Wo"), W, "model", 2, set(), heads) == \
        P("model", None)
    assert pl.tp_param_spec(("0", "bo"), b, "model", 2, set(), heads) == P()
    # 3 heads % 2 shards != 0: every projection replicates
    for name in ("Wq", "Wo", "bq"):
        leaf = W if name[0] == "W" else b
        assert pl.tp_param_spec(("0", name), leaf, "model", 2, set(),
                                {"0": 3}) == P()


def test_model_introspection():
    net = _lm()
    assert pl.attention_tp_heads(net) == {"0": 4}
    dense = pl.dense_tp_keys(net)
    assert "1" in dense and "2" in dense and "0" not in dense


def test_quantized_scale_sharding():
    """Scale [channels] shards over the model axis iff the weight spec
    put the model axis on the quantized (out-channel) axis."""
    mesh = _mesh()
    qt = q.quantize_per_channel(np.ones((8, 32), np.float32), 1)
    qsh, ssh = pl.quantized_shardings(qt, P(None, "model"), mesh, "model")
    assert ssh.spec == P("model")
    # row-sharded Wo: quantized axis replicated -> scale replicates
    _, ssh = pl.quantized_shardings(qt, P("model", None), mesh, "model")
    assert ssh.spec == P()


def test_cache_sharding_tree():
    """Head axis (1) splits when divisible; page rows never shard."""
    mesh = _mesh()
    contig = np.zeros((2, 4, 32, 8), np.float32)    # [S, H, C, d]
    paged = np.zeros((64, 4, 8), np.float32)        # [rows, H, d]
    odd = np.zeros((64, 3, 8), np.float32)
    tree = pl.cache_sharding_tree(mesh, [contig, paged, odd], "model", 2)
    assert tree[0].spec == P(None, "model", None, None)
    assert tree[1].spec == P(None, "model", None)
    assert tree[2].spec == P()                       # 3 % 2 != 0


def test_tree_bytes_per_device():
    mesh = _mesh()
    full = np.zeros((8, 32), np.float32)
    sh = pl.sharding_tree(mesh, {"w": full},
                          lambda names, a: P(None, "model"))
    assert pl.tree_bytes_per_device({"w": full}, sh) == full.nbytes // 2
    repl = pl.sharding_tree(mesh, {"w": full}, lambda names, a: P())
    assert pl.tree_bytes_per_device({"w": full}, repl) == full.nbytes


def test_mesh_key_suffix():
    mesh = _mesh()
    assert pl.mesh_key(mesh) == "1x2"
    assert pl.mesh_suffix(mesh, "model") == "mesh=1x2:tp2"
    assert pl.mesh_suffix(mesh, None) == "mesh=1x2:tp1"


def test_pod_mesh_model_span():
    """model_span='pod' lays the model axis host-major over the whole
    pod; 'host' keeps the ICI-adjacency rejection (pointing at 'pod')."""
    mesh = launcher.pod_mesh(model=8, hosts=2, model_span="pod")
    assert dict(mesh.shape) == {"data": 1, "model": 8}
    with pytest.raises(ValueError, match="model_span='pod'"):
        launcher.pod_mesh(model=8, hosts=2)          # 8 > 4 per virtual host
    with pytest.raises(ValueError, match="must divide the pod"):
        launcher.pod_mesh(model=3, model_span="pod")
    with pytest.raises(ValueError, match="model_span"):
        launcher.pod_mesh(model=2, model_span="ici")


# ---------------------------------------------------------------------------
# engine parity + bytes + compile discipline
# ---------------------------------------------------------------------------

def test_inference_engine_tp_matches_single(rng):
    """One-shot TP output == replicated output (float tolerance), and
    memory_report accounts PER-DEVICE params bytes (the satellite
    bugfix)."""
    net = _mlp()
    x = rng.normal(size=(3, 8)).astype(np.float32)
    base = np.asarray(InferenceEngine(net).warmup([4]).output(x))
    eng = InferenceEngine(net, mesh=_mesh()).warmup([4])
    np.testing.assert_allclose(np.asarray(eng.output(x)), base,
                               atol=1e-5, rtol=1e-5)
    rep = eng.memory_report(4)
    assert rep["tp_shards"] == 2 and rep["mesh"] == "1x2"
    assert rep["params_bytes_per_device"] < rep["params_bytes"]


def test_generative_tp_greedy_parity(rng):
    """Contiguous generative engine under TP: greedy tokens equal the
    single-device oracle; per-device cache bytes halve."""
    net = _lm()
    prompts = _prompts(rng)
    single = GenerativeEngine(net, slots=2)
    single.warmup([32], [16])
    oracle = _greedy_contiguous(single, prompts, 8)
    eng = GenerativeEngine(net, slots=2, mesh=_mesh())
    eng.warmup([32], [16])
    assert _greedy_contiguous(eng, prompts, 8) == oracle
    assert eng.cache_bytes(32, per_device=True) * 2 == eng.cache_bytes(32)


@pytest.mark.parametrize("kv", [None, "int8"])
def test_paged_tp_greedy_parity(rng, kv):
    """Paged TP engine: greedy tokens equal the single-device paged
    oracle (f32 and int8 KV), pool bytes per device == full/2, ZERO
    post-warmup compiles, and the shard_map dispatch is counted."""
    net = _lm()
    prompts = _prompts(rng)
    kw = dict(slots=2, pages=32, page_size=8, max_cache_len=32,
              kv_cache=kv)
    single = PagedGenerativeEngine(net, **kw).warmup([32], [16])
    oracle = _greedy_paged(single, prompts, 8, 8)

    fa.reset_counters()
    eng = PagedGenerativeEngine(net, mesh=_mesh(), **kw).warmup([32], [16])
    ev0 = int(tel.registry.get("compile.events").total())
    assert _greedy_paged(eng, prompts, 8, 8) == oracle
    assert int(tel.registry.get("compile.events").total()) == ev0
    assert eng.pool_bytes(per_device=True) * 2 == eng.pool_bytes()
    assert eng.stats()["pool_bytes_per_device"] * 2 == eng.pool_bytes()
    counters = {k: v for k, v in fa.counters().items() if v}
    assert any(k.endswith(("tp_shard_map", "fallback_gspmd")) for k in counters)


def test_int8_weights_compose_with_tp(rng):
    """quantize='int8' + mesh: the QuantizedTensor flows through the
    placement walk (int8 payload sharded, f32 scales riding along) and
    greedy tokens still match the quantized single-device engine."""
    net = _lm()
    prompts = _prompts(rng)
    single = GenerativeEngine(net, slots=2, quantize="int8")
    single.warmup([32], [16])
    oracle = _greedy_contiguous(single, prompts, 8)
    eng = GenerativeEngine(net, slots=2, quantize="int8", mesh=_mesh())
    eng.warmup([32], [16])
    assert _greedy_contiguous(eng, prompts, 8) == oracle


def test_attribution_key_has_mesh_suffix():
    """TP attribution reports key on mesh shape + TP size (the r18
    fingerprint-key rule) so fractions never blend across topologies."""
    net = _mlp()
    eng = InferenceEngine(net, mesh=_mesh()).warmup([4])
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "test"}
    rep = eng.attribution_report(4, measured_s=1e-3, peaks=peaks)
    assert "mesh=1x2:tp2" in rep["key"]
    plain = InferenceEngine(net).warmup([4])
    assert "mesh=" not in plain.attribution_report(
        4, measured_s=1e-3, peaks=peaks)["key"]


def test_tp_shards_gauge_labeled_with_mesh():
    net = _mlp()
    eng = InferenceEngine(net, mesh=_mesh())
    series = tel.registry.get("serving.engine.tp_shards").series()
    hit = [dict(k) for k, v in series.items()
           if dict(k).get("engine") == eng._id]
    assert hit and hit[0]["mesh"] == "1x2"


# ---------------------------------------------------------------------------
# prepare_write snapshot fast path (satellite 6)
# ---------------------------------------------------------------------------

def _one_round(eng, state, snap_mode):
    """One admission round over a shared page: slot 0 forks, slot 1
    inherits exclusively. Returns the fork pairs."""
    pages = eng.pool.alloc(1)
    eng.map_pages(state, 0, pages)
    eng.pool.retain(pages)
    eng.map_pages(state, 1, pages)
    state.lengths[0] = state.lengths[1] = 4
    snap = eng.pool.ref_snapshot() if snap_mode else None
    f0 = eng.prepare_write(state, 0, 1, ref_snapshot=snap)
    f1 = eng.prepare_write(state, 1, 1, ref_snapshot=snap)
    eng.pool.release(eng.release_slot(state, 0))
    eng.pool.release(eng.release_slot(state, 1))
    return f0, f1


@pytest.mark.parametrize("snap_mode", [False, True])
def test_prepare_write_snapshot_matches_locked_probe(snap_mode):
    """The snapshot path makes the same fork decisions as the per-page
    locked probe: the shared page forks exactly once (slot 0), and the
    in-place snapshot update sees slot 1's page as exclusive."""
    eng = PagedGenerativeEngine(_lm(), slots=2, pages=16, page_size=8,
                                max_cache_len=32)
    state = eng.new_state(32)
    f0, f1 = _one_round(eng, state, snap_mode)
    assert len(f0) == 1 and f1 == []
    assert eng.pool.pages_in_use() == 0


def test_prepare_write_snapshot_hammer():
    """Concurrent pool readers (the contention prepare_write used to
    create per candidate page) never cause a lost or doubled CoW fork."""
    eng = PagedGenerativeEngine(_lm(), slots=2, pages=16, page_size=8,
                                max_cache_len=32)
    state = eng.new_state(32)
    forks0 = eng.pool.stats()["forks"]
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            eng.pool.ref_snapshot()
            eng.pool.stats()

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            f0, f1 = _one_round(eng, state, snap_mode=True)
            assert len(f0) == 1 and f1 == []
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert eng.pool.stats()["forks"] - forks0 == 200
    assert eng.pool.pages_in_use() == 0


# ---------------------------------------------------------------------------
# staticcheck: mesh-scoped metric labels
# ---------------------------------------------------------------------------

def test_staticcheck_mesh_label_rule(tmp_path):
    from deeplearning4j_tpu.runtime import staticcheck as sc
    bad = '''
from deeplearning4j_tpu.runtime import telemetry as _tel
_G = _tel.gauge("serving.engine.tp_shards", "x")
class E:
    def __init__(self):
        _G.labeled(engine="e1").set(2)
'''
    found = sc.check_source(bad, "fixture_bad.py",
                            rules=["mesh-scoped-metric-label"])
    assert [f.rule for f in found] == ["mesh-scoped-metric-label"]
    good = bad.replace('engine="e1"', 'engine="e1", mesh="1x2"')
    assert sc.check_source(good, "fixture_good.py",
                           rules=["mesh-scoped-metric-label"]) == []


# ---------------------------------------------------------------------------
# the 2-process pod sim serving phase (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pod_serving_sim(tmp_path):
    """2-process pod serves a model exceeding one host's simulated
    bytes_limit: greedy tokens bit-equal to the single-device oracle
    (f32 and int8 KV), per-host params < limit < full, zero post-warmup
    compiles — all asserted inside run_serving."""
    from deeplearning4j_tpu.parallel import multihost_sim as sim
    art = sim.run_serving(str(tmp_path))
    assert art["metric"] == "pod_serving_sim"
    for variant in art["variants"].values():
        assert variant["post_warmup_compile_events"] == 0
