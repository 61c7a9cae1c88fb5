"""Kernel autotuning + collective overlap (ISSUE 7): the flash-attention
block-shape autotuner (divisor blocks, candidate parity, CPU-never-sweeps
tier-1 guard, disk persistence, sweep machinery), the ZeRO-1 gradient-
bucket overlap path (bit-equivalence incl. accum_steps/model_axis
composition, compile-cause attribution), and the mixed-precision cast
hoist in the engines' microbatch scan (jaxpr regression + numerics)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import autotune as at
from deeplearning4j_tpu.ops import flash_attention as fa


@pytest.fixture
def clean_autotune():
    """Empty autotune cache + zeroed counters, restored mode."""
    at.reset()
    at.reset_counters()
    old = at.set_mode("auto")
    yield
    at.set_mode(old)
    at.reset()


@pytest.fixture
def force_mode():
    old = fa.set_mode("force")
    fa.reset_counters()
    yield
    fa.set_mode(old)


def _qkv(rng, B=2, H=2, Tq=64, Tk=64, d=16, dtype=np.float32):
    mk = lambda T: jnp.asarray(rng.normal(size=(B, H, T, d)), dtype=dtype)
    return mk(Tq), mk(Tk), mk(Tk)


# ---------------------------------------------------------------------------
# pick_block generalization (satellite: divisor blocks, multiple of 8)
# ---------------------------------------------------------------------------

def test_pick_block_divisor_blocks():
    """Any multiple-of-8 divisor <= target qualifies — not only powers of
    two; non-8-divisible lengths still return None."""
    assert fa.pick_block(128) == 128
    assert fa.pick_block(1024) == 128          # target cap holds
    assert fa.pick_block(96) == 96             # 96 = 3 * 32: now a block
    assert fa.pick_block(120) == 120           # 120 = 8 * 15
    assert fa.pick_block(24) == 24
    assert fa.pick_block(384) == 128           # divisible by the target
    assert fa.pick_block(8) == 8
    assert fa.pick_block(100) is None          # no multiple-of-8 divisor
    assert fa.pick_block(12) is None
    assert fa.pick_block(64, target=16) == 16  # explicit target respected
    # every returned block divides t and is a multiple of 8
    for t in (16, 24, 40, 96, 120, 128, 200, 256, 384, 520):
        b = fa.pick_block(t)
        if b is not None:
            assert t % b == 0 and b % 8 == 0 and b <= 128


def test_odd_seqlen_fuses_without_fallback(rng, force_mode, clean_autotune):
    """Fallback-counter regression (the satellite's acceptance): an odd
    sequence length that only tiles into a non-power-of-two block (120)
    now takes the kernel path — zero fallback_shape — and matches the
    reference."""
    q, k, v = _qkv(rng, Tq=120, Tk=120, d=16)
    out = fa.attention(q, k, v)
    c = fa.counters()
    assert c["fused"] == 1, c
    assert c["fallback_shape"] == 0, c
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.reference_attention(q, k, v)),
        atol=1e-5)
    # non-8-divisible still guards out loudly
    q2, k2, v2 = _qkv(rng, Tq=100, Tk=100, d=16)
    fa.attention(q2, k2, v2)
    assert fa.counters()["fallback_shape"] == 1


# ---------------------------------------------------------------------------
# autotuner: candidates, defaults, cache, persistence
# ---------------------------------------------------------------------------

def test_candidate_enumeration_properties():
    """Candidates are multiple-of-8 divisor pairs within the VMEM budget,
    include the dispatcher's target-128 default, and cap per axis."""
    cands = at.candidates(64, 64, 32)
    assert (64, 64) in cands                     # the default pair
    for bq, bk in cands:
        assert 64 % bq == 0 and 64 % bk == 0
        assert bq % 8 == 0 and bk % 8 == 0
        assert fa.fits_vmem_attention(bq, bk, 32)
    assert at.axis_blocks(120) == [120, 40, 24, 8]
    assert at.axis_blocks(1024) == [256, 128, 64, 32]
    assert len(at.axis_blocks(2048)) <= at.AXIS_CANDIDATES


@pytest.mark.parametrize("key", [
    (512, 512, 64, 2, True), (128, 128, 64, 2, True), (384, 384, 64, 4, False),
    (2048, 2048, 64, 2, True), (8192, 8192, 128, 2, False),
    (640, 640, 64, 2, True), (1200, 1200, 64, 4, False)],
    ids=lambda k: f"{k[0]}d{k[2]}b{k[3]}{'bias' if k[4] else ''}")
def test_candidates_and_seed_read_the_dispatchers_rule(key, clean_autotune):
    """The one-shot kernels' candidates follow the default tiling's own
    limits (query blocks up to ``flash_attention.MAX_BLOCK``, key blocks up
    to the whole row, so a sweep can try what the default takes), hold the
    default itself, and every one passes the shared VMEM guard;
    the seed of an unswept key is ``flash_attention.default_blocks``. The
    decode kernels keep their 128-target block and their own cap."""
    tq, tk, d, itemsize, has_bias = key
    dtype = {2: "bfloat16", 4: "float32"}[itemsize]
    default = fa.default_blocks(*key)
    cands = at.candidates(tq, tk, d, itemsize, has_bias=has_bias)
    assert default in cands
    assert max(bq for bq, _ in cands) == default[0] <= fa.MAX_BLOCK
    assert max(bk for _, bk in cands) >= default[1]
    for bq, bk in cands:
        assert tq % bq == 0 and tk % bk == 0
        assert fa.kv_block_ok(bk, tk, has_bias)
        assert fa.fits_vmem_attention(bq, bk, d, itemsize)
    assert at._default_blocks(tq, tk, d, itemsize, False, has_bias) == default
    assert at.get_blocks(tq, tk, d, dtype, has_bias) == default
    assert at.lookup(tq, tk, d, dtype, has_bias)["source"] == "default"
    # decode keys: as before this rule existed
    bk = fa.pick_kv_block(tk, has_bias=True)
    assert at._default_blocks(1, tk, d, itemsize, True, True) \
        == (None if bk is None else (1, bk))
    assert all(c[1] <= at.MAX_BLOCK for c in at.candidates(
        1, tk, d, itemsize, decode=True, has_bias=True))


@pytest.mark.parametrize("has_bias", [False, True])
def test_every_candidate_block_shape_parity(rng, has_bias):
    """Interpret-mode numerical parity for EVERY candidate block shape the
    autotuner may pick for a representative key (ISSUE 7 satellite):
    forward and gradient, against the einsum reference. With a key bias
    the candidates are held to the kv blocks the TPU lowering accepts."""
    B, H, T, d = 2, 2, 64, 16
    q, k, v = _qkv(rng, B=B, H=H, Tq=T, Tk=T, d=d)
    bias = None
    if has_bias:
        mask = np.ones((B, T), np.float32)
        mask[0, T // 2:] = 0.0
        bias = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0,
                         jnp.asarray(np.finfo(np.float32).min))
    ref = fa.reference_attention(q, k, v, bias)
    g_ref = jax.grad(lambda x: jnp.sum(
        fa.reference_attention(x, k, v, bias)))(q)
    cands = at.candidates(T, T, d, has_bias=has_bias)
    assert len(cands) >= 4  # a real sweep space, not a degenerate one
    assert all(fa.kv_block_ok(bk, T, has_bias) for _, bk in cands)
    if has_bias:
        assert {bk for _, bk in cands} == {T}
        with pytest.raises(ValueError, match="do not tile"):
            fa.flash_attention(q, k, v, bias, block_k=32, interpret=True)
    for bq, bk in cands:
        out = fa.flash_attention(q, k, v, bias, block_q=bq, block_k=bk,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, err_msg=f"blocks {bq}x{bk}")
        g = jax.grad(lambda x: jnp.sum(fa.flash_attention(
            x, k, v, bias, block_q=bq, block_k=bk, interpret=True)))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-5, err_msg=f"blocks {bq}x{bk}")


def test_cpu_runs_never_sweep(rng, force_mode, clean_autotune):
    """Tier-1 guard (ISSUE 7 satellite): exercising the kernel path on CPU
    seeds target-128 defaults into the cache — zero timing sweeps, zero
    autotune compile events — and repeat lookups are cache hits."""
    from deeplearning4j_tpu.runtime import telemetry

    ev_before = len(telemetry.compile_events("flash_attention.autotune"))
    q, k, v = _qkv(rng, Tq=64, Tk=64, d=16)
    fa.attention(q, k, v)                       # eager dispatch
    jax.jit(lambda a, b, c: fa.attention(a, b, c))(q, k, v)  # traced
    c = at.counters()
    assert c["sweep"] == 0 and c["sweep_candidate"] == 0, c
    assert c["default"] == 1 and c["hit"] >= 1, c
    snap = at.cache_snapshot()
    assert len(snap["entries"]) == 1
    ent = snap["entries"][0]
    assert ent["source"] == "default" and ent["blocks"] == [64, 64]
    assert len(telemetry.compile_events("flash_attention.autotune")) \
        == ev_before, "a CPU run produced autotune sweep compiles"


def test_autotune_lookup_prefers_swept_entry(rng, force_mode,
                                             clean_autotune):
    """A warm (hand-seeded, as a disk cache would) swept entry routes the
    default-block dispatch through ITS blocks — verified via the traced
    kernel grid."""
    key = at.cache_key(64, 64, 16, jnp.float32, False)
    with at._lock:
        at._cache[key] = {"blocks": [16, 32], "source": "sweep"}
    assert at.get_blocks(64, 64, 16, jnp.float32, False) == (16, 32)
    assert at.counters()["hit"] == 1
    # the kernel consumes the swept blocks: its pallas grid bakes
    # Tq/bq = 4 q-blocks and Tk/bk = 2 kv-blocks
    q, k, v = _qkv(rng, Tq=64, Tk=64, d=16)
    txt = str(jax.make_jaxpr(
        lambda a, b, c: fa.flash_attention(a, b, c, interpret=True))(q, k, v))
    assert "(4, 4, 2)" in txt, txt[:400]  # grid=(B*H, nq, nk)=(4, 4, 2)
    out = fa.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.reference_attention(q, k, v)),
        atol=1e-5)


def test_autotune_cache_persistence_roundtrip(tmp_path, clean_autotune):
    """save/load JSON round-trip; swept disk entries beat in-process
    default seeds, default disk entries never overwrite in-process
    sweeps."""
    p = str(tmp_path / "autotune.json")
    key = at.cache_key(128, 128, 64, jnp.bfloat16, True)
    with at._lock:
        at._cache[key] = {"blocks": [64, 128], "source": "sweep",
                          "us": 12.5}
    assert at.save(p) == p
    at.reset()
    assert at.lookup(128, 128, 64, jnp.bfloat16, True) is None
    assert at.load(p) == 1
    ent = at.lookup(128, 128, 64, jnp.bfloat16, True)
    assert ent["blocks"] == [64, 128] and ent["source"] == "sweep"
    # a default-seeded disk entry must not clobber an in-process sweep
    at.reset()
    with at._lock:
        at._cache[key] = {"blocks": [32, 32], "source": "sweep"}
    with open(p) as f:
        snap = json.load(f)
    snap["entries"][0]["source"] = "default"
    with open(p, "w") as f:
        json.dump(snap, f)
    at.load(p)
    assert at.lookup(128, 128, 64, jnp.bfloat16, True)["blocks"] == [32, 32]
    # corrupt file: load() raises, but the lazy env-path load swallows
    with open(p, "w") as f:
        f.write("{not json")
    with pytest.raises(ValueError):
        at.load(p)


def test_autotune_sweep_rejected_off_tpu(clean_autotune):
    """A timing sweep on CPU is a programming error (it would tune for the
    Pallas interpreter): loud RuntimeError unless interpret=True."""
    with pytest.raises(RuntimeError, match="only meaningful on TPU"):
        at.sweep(64, 64, 16, jnp.float32, False)


def test_invalid_cache_entries_never_served(rng, force_mode,
                                            clean_autotune, tmp_path):
    """Review-round hardening: a stale/hand-edited entry whose blocks do
    not tile the key (grid truncation -> wrong output) is dropped at
    lookup AND skipped at load — dispatch falls back to the defaults."""
    key = at.cache_key(64, 64, 16, jnp.float32, False)
    with at._lock:
        at._cache[key] = {"blocks": [48, 48], "source": "sweep"}  # 64%48!=0
    assert at.get_blocks(64, 64, 16, jnp.float32, False) == (64, 64)
    assert at.lookup(64, 64, 16, jnp.float32, False)["source"] == "default"
    # kernel output stays correct through the dispatcher
    q, k, v = _qkv(rng, Tq=64, Tk=64, d=16)
    np.testing.assert_allclose(
        np.asarray(fa.attention(q, k, v)),
        np.asarray(fa.reference_attention(q, k, v)), atol=1e-5)
    # load() refuses invalid entries wholesale
    p = str(tmp_path / "bad.json")
    with open(p, "w") as f:
        json.dump({"version": 1, "entries": [
            {"key": [64, 64, 16, "float32", False], "blocks": [48, 48],
             "source": "sweep"},
            {"key": [64, 64, 16, "float32", False], "blocks": [12, 64],
             "source": "sweep"}]}, f)
    at.reset()
    assert at.load(p) == 0
    # flash_attention's own belt: a poisoned entry injected after lookup
    # validation still cannot truncate the grid (falls back to defaults)
    assert fa.pick_block(64) == 64


def test_warmup_respects_mode_and_upgrades_default_seeds(clean_autotune,
                                                         monkeypatch):
    """Review-round hardening: (a) warmup/get_blocks never sweep under
    mode "off" even on TPU; (b) a default-seeded entry (left by an
    earlier traced dispatch) is UPGRADED by warmup / a concrete auto-mode
    lookup on TPU, not pinned forever."""
    swept = []

    def fake_sweep(tq, tk, d, dtype, has_bias, **kw):
        entry = {"blocks": [32, 32], "source": "sweep"}
        with at._lock:
            at._cache[at.cache_key(tq, tk, d, dtype, has_bias)] = entry
        swept.append((tq, tk))
        return dict(entry)

    monkeypatch.setattr(at, "sweep", fake_sweep)
    monkeypatch.setattr(at.jax, "default_backend", lambda: "tpu")
    # seed a default entry the way a traced dispatch would
    at.set_mode("off")
    assert at.get_blocks(64, 64, 16, jnp.float32, False) == (64, 64)
    # off: neither warmup nor a concrete lookup sweeps
    at.warmup([(64, 64, 16, jnp.float32, False)])
    assert at.get_blocks(64, 64, 16, jnp.float32, False,
                         concrete=True) == (64, 64)
    assert swept == []
    # auto: the default seed is upgraded by warmup...
    at.set_mode("auto")
    at.warmup([(64, 64, 16, jnp.float32, False)])
    assert swept == [(64, 64)]
    assert at.get_blocks(64, 64, 16, jnp.float32, False) == (32, 32)
    # ...and a concrete auto-mode lookup upgrades another default seed
    at.set_mode("off")
    at.get_blocks(96, 96, 16, jnp.float32, False)
    at.set_mode("auto")
    assert at.get_blocks(96, 96, 16, jnp.float32, False,
                         concrete=True) == (32, 32)
    assert swept == [(64, 64), (96, 96)]
    # swept entries are terminal: no re-sweep on later lookups
    at.get_blocks(96, 96, 16, jnp.float32, False, concrete=True)
    assert swept == [(64, 64), (96, 96)]
    # an interpreter-"swept" entry is NOT authoritative on a real chip
    # (its timings tuned the Pallas interpreter): TPU warmup re-sweeps it
    with at._lock:
        at._cache[at.cache_key(120, 120, 16, jnp.float32, False)] = {
            "blocks": [24, 24], "source": "sweep_interpret"}
    at.warmup([(120, 120, 16, jnp.float32, False)])
    assert swept[-1] == (120, 120)
    # ...but another interpret warmup treats it as done (idempotent tests)
    with at._lock:
        at._cache[at.cache_key(40, 40, 16, jnp.float32, False)] = {
            "blocks": [40, 40], "source": "sweep_interpret"}
    n = len(swept)
    at.warmup([(40, 40, 16, jnp.float32, False)], interpret=True)
    assert len(swept) == n


@pytest.mark.slow
def test_autotune_sweep_machinery_interpret(clean_autotune):
    """Sweep machinery end-to-end through the Pallas interpreter (slow;
    the timings tune nothing — the entry is tagged sweep_interpret): every
    candidate compiles through record_compile(cause="autotune"), the
    winner is a real candidate, and the cache auto-persists to the
    DL4J_TPU_AUTOTUNE_CACHE path."""
    import tempfile

    from deeplearning4j_tpu.runtime import telemetry

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "at.json")
        old = os.environ.get("DL4J_TPU_AUTOTUNE_CACHE")
        os.environ["DL4J_TPU_AUTOTUNE_CACHE"] = path
        try:
            before = len(telemetry.compile_events(
                "flash_attention.autotune"))
            entry = at.sweep(32, 32, 16, jnp.float32, True,
                             interpret=True, repeats=1)
            cands = at.candidates(32, 32, 16)
            assert tuple(entry["blocks"]) in cands
            assert entry["source"] == "sweep_interpret"
            assert len(entry["candidates"]) == len(cands)
            evs = telemetry.compile_events("flash_attention.autotune")[before:]
            assert len(evs) == len(cands)
            assert all(e["cause"] == "autotune" for e in evs)
            assert at.counters()["sweep"] == 1
            assert at.counters()["sweep_candidate"] == len(cands)
            with open(path) as f:
                snap = json.load(f)
            assert snap["entries"][0]["source"] == "sweep_interpret"
        finally:
            if old is None:
                os.environ.pop("DL4J_TPU_AUTOTUNE_CACHE", None)
            else:
                os.environ["DL4J_TPU_AUTOTUNE_CACHE"] = old


# ---------------------------------------------------------------------------
# collective overlap: bucketing + bit-equivalence + causes
# ---------------------------------------------------------------------------

from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.config import (InputType,  # noqa: E402
                                          NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.core import (DenseLayer,  # noqa: E402
                                               OutputLayer)
from deeplearning4j_tpu.nn.model import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.updaters import Adam  # noqa: E402
from deeplearning4j_tpu.parallel.data_parallel import (  # noqa: E402
    ParallelWrapper, make_dp_tp_mesh)
from deeplearning4j_tpu.parallel import overlap as ov  # noqa: E402


def _conf(seed=11, nin=8, nout=4):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-2))
            .input_type(InputType.feed_forward(nin))
            .list(DenseLayer(n_out=16, activation="tanh"),
                  DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=nout)).build())


def _data(n=32, seed=0, nin=8, nout=4):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, nin)).astype(np.float32)
    y = np.eye(nout, dtype=np.float32)[rng.integers(0, nout, n)]
    return DataSet(x, y)


def _assert_trees_equal(a, b):
    for p, q in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


def test_make_buckets_partition_and_order():
    """Every leaf lands in exactly one bucket, buckets respect the byte
    cap where possible, and the FIRST bucket holds the LAST layer's leaves
    (reverse layer order — backward availability order)."""
    net = MultiLayerNetwork(_conf()).init()
    leaf_paths = {tuple(str(getattr(k, "key", k)) for k in p)
                  for p, _ in jax.tree_util.tree_flatten_with_path(
                      net.params)[0]}
    buckets = ov.make_buckets(net.params, 600)  # ~a W leaf each
    got = [p for b in buckets for p in b]
    assert set(got) == leaf_paths and len(got) == len(leaf_paths)
    assert got[0][0] == "2"          # output layer first
    assert got[-1][0] == "0"         # input layer last
    # one giant bucket when the cap is huge
    assert len(ov.make_buckets(net.params, 1 << 30)) == 1
    # oversized single leaf still gets a bucket of its own
    assert all(b for b in ov.make_buckets(net.params, 1))
    with pytest.raises(ValueError, match="positive"):
        ov.make_buckets(net.params, 0)


def test_overlap_requires_shard_update():
    net = MultiLayerNetwork(_conf()).init()
    with pytest.raises(ValueError, match="shard_update"):
        ParallelWrapper(net, overlap_grads=True)
    pw = ParallelWrapper(net, shard_update=True)
    with pytest.raises(ValueError, match="shard_update"):
        ParallelWrapper(net, overlap_grads=True, shard_update=False)
    del pw


@pytest.mark.parametrize("accum", [1, 2])
def test_overlap_bit_equivalence(accum):
    """overlap_grads=True reproduces the unoverlapped sharded update
    BIT-exactly (params AND updater state) — the transform is scheduling
    structure only — incl. composition with accum_steps."""
    ds = _data()

    def run(overlap):
        net = MultiLayerNetwork(_conf()).init()
        pw = ParallelWrapper(net, shard_update=True, accum_steps=accum,
                             overlap_grads=overlap,
                             overlap_bucket_mb=0.001)  # force many buckets
        pw.fit(ds, epochs=3)
        return net

    a, b = run(False), run(True)
    _assert_trees_equal(a.params, b.params)
    _assert_trees_equal(a.updater_state, b.updater_state)


def test_overlap_bit_equivalence_with_model_axis():
    """Composes with tensor parallelism: 4x2 (data x model) mesh, sharded
    update + overlap vs sharded update alone."""
    ds = _data()

    def run(overlap):
        net = MultiLayerNetwork(_conf()).init()
        pw = ParallelWrapper(net, mesh=make_dp_tp_mesh(4, 2),
                             model_axis="model", shard_update=True,
                             overlap_grads=overlap, overlap_bucket_mb=0.001)
        pw.fit(ds, epochs=2)
        return net

    a, b = run(False), run(True)
    _assert_trees_equal(a.params, b.params)
    _assert_trees_equal(a.updater_state, b.updater_state)


def test_set_overlap_records_overlap_cause():
    """Toggling the overlap knob drops the cached step and attributes the
    rebuild cause="overlap" in the retrace tracker; the buckets gauge is
    written (telemetry floor)."""
    from deeplearning4j_tpu.runtime import telemetry

    net = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(net, shard_update=True)
    ds = _data(n=16)
    pw.fit(ds, epochs=1)
    before = len(telemetry.compile_events("parallel.step"))
    pw.fit(ds, epochs=1)  # warm: no rebuild
    assert len(telemetry.compile_events("parallel.step")) == before
    pw.set_overlap(True, bucket_mb=0.001)
    pw.fit(ds, epochs=1)
    evs = telemetry.compile_events("parallel.step")
    assert len(evs) == before + 1
    assert evs[-1]["cause"] == "overlap" and evs[-1]["overlap"] is True
    gauge = telemetry.registry.get("parallel.overlap.buckets")
    assert gauge.value(model=net.telemetry_label) >= 1
    # set_overlap with no change keeps the cached step
    pw.set_overlap(True)
    assert pw._step is not None
    # review-round hardening: turning overlap OFF zeroes this wrapper's
    # labeled gauge cell on rebuild (no stale bucket count), and a
    # bucket-size change while overlap stays off must not retrace the
    # bucket-free program
    pw.set_overlap(False)
    pw.fit(ds, epochs=1)
    assert gauge.value(model=net.telemetry_label) == 0
    assert pw._step is not None
    pw.set_overlap(False, bucket_mb=8)
    assert pw._step is not None


def test_engine_grad_transform_hook():
    """_build_train_step(grad_transform=) applies the transform to the raw
    gradients before clipping: a doubling transform doubles the Sgd delta
    exactly."""
    from deeplearning4j_tpu.nn.updaters import Sgd

    def conf():
        return (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(learning_rate=0.5))
                .input_type(InputType.feed_forward(8))
                .list(DenseLayer(n_out=8, activation="tanh"),
                      OutputLayer(n_out=4)).build())

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)])
    key = jax.random.PRNGKey(0)

    net = MultiLayerNetwork(conf()).init()
    p0 = jax.tree.map(jnp.copy, net.params)
    plain = net._build_train_step()(
        net.params, net.updater_state, net.state, jnp.int32(0), key,
        x, y, None, None)[0]
    net2 = MultiLayerNetwork(conf()).init()
    doubled = net2._build_train_step(
        grad_transform=lambda g: jax.tree.map(lambda a: 2.0 * a, g))(
        net2.params, net2.updater_state, net2.state, jnp.int32(0), key,
        x, y, None, None)[0]
    for base, a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(plain),
                          jax.tree.leaves(doubled)):
        np.testing.assert_allclose(np.asarray(base - b),
                                   2.0 * np.asarray(base - a), atol=1e-6)


# ---------------------------------------------------------------------------
# bf16 audit: mixed-precision cast hoist in the microbatch scan
# ---------------------------------------------------------------------------

def _bf16_conf(l2=0.0):
    # Sgd, not Adam: the numeric twins below compare accum_steps=4 vs 1,
    # whose bf16 grads differ by fp reassociation at ~1e-6 — Adam's
    # 1/(sqrt(v)+eps) would amplify that into the 1e-3 range on step 0
    # and the test would measure the amplifier, not the hoist
    from deeplearning4j_tpu.nn.updaters import Sgd
    b = (NeuralNetConfiguration.builder().seed(7).data_type("BFLOAT16")
         .updater(Sgd(learning_rate=0.1)))
    if l2:
        b = b.l2(l2)
    return (b.input_type(InputType.feed_forward(12))
            .list(DenseLayer(n_out=24, activation="tanh"),
                  OutputLayer(n_out=4)).build())


def _scan_bf16_param_converts(step, net, x, y):
    """convert_element_type->bf16 eqns INSIDE the scan whose output shape
    matches a parameter leaf — the per-microbatch master-cast the hoist
    removes."""
    key = jax.random.PRNGKey(0)
    jaxpr = jax.make_jaxpr(step.__wrapped__)(
        net.params, net.updater_state, net.state, jnp.int32(0), key,
        x, y, None, None)
    param_shapes = {tuple(l.shape) for l in jax.tree.leaves(net.params)}

    def walk(jx, inside_scan, acc):
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type" and inside_scan:
                ov_ = eqn.outvars[0]
                if str(ov_.aval.dtype) == "bfloat16" and \
                        tuple(ov_.aval.shape) in param_shapes:
                    acc.append(tuple(ov_.aval.shape))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(inner, inside_scan or
                         eqn.primitive.name == "scan", acc)
        return acc

    return walk(jaxpr.jaxpr, False, [])


def test_mixed_accum_cast_hoisted_out_of_scan(rng):
    """bf16 audit fix (ISSUE 7): under the 16-bit policy with accum_steps
    the fp32->bf16 master cast runs ONCE per step, not once per microbatch
    — the scan body contains zero param-shaped bf16 converts. The
    regularized conf (whose penalty reads the passed params) keeps the
    un-hoisted path, proving the gate."""
    x = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[
        np.random.default_rng(1).integers(0, 4, 16)])
    net = MultiLayerNetwork(_bf16_conf()).init()
    assert _scan_bf16_param_converts(
        net._build_train_step(accum_steps=4), net, x, y) == []
    net_l2 = MultiLayerNetwork(_bf16_conf(l2=1e-4)).init()
    assert len(_scan_bf16_param_converts(
        net_l2._build_train_step(accum_steps=4), net_l2, x, y)) > 0


def test_mixed_accum_matches_single_step(rng, monkeypatch):
    """The hoisted bf16 accum step is BIT-equal to the un-hoisted one (the
    pre-r12 program, forced by disabling the hoist gate) at the same
    accum_steps — the cast move is pure scheduling. A loose accum4-vs-
    accum1 sanity rides along (bf16 microbatch grads differ from the
    full-batch grad by rounding-point reassociation — pre-existing,
    unchanged by the hoist)."""
    x = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[
        np.random.default_rng(1).integers(0, 4, 16)])
    key = jax.random.PRNGKey(0)

    def run(accum, unhoist=False):
        net = MultiLayerNetwork(_bf16_conf()).init()
        if unhoist:
            # force the pre-r12 cast-inside-the-scan program; with no
            # l1/l2 configured the regularization term is identically 0.0
            # either way, so the two programs compute the same values
            monkeypatch.setattr(type(net), "_uses_regularization",
                                lambda self: True)
        step = net._build_train_step(accum_steps=accum)
        return step(net.params, net.updater_state, net.state,
                    jnp.int32(0), key, x, y, None, None)

    out_h = run(4)
    out_u = run(4, unhoist=True)
    monkeypatch.undo()
    assert float(out_h[-1]) == float(out_u[-1])
    for a, b in zip(jax.tree.leaves(out_h[0]), jax.tree.leaves(out_u[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out1 = run(1)
    assert float(out_h[-1]) == pytest.approx(float(out1[-1]), abs=1e-4)
    for a, b in zip(jax.tree.leaves(out_h[0]), jax.tree.leaves(out1[0])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-3)


def test_mixed_accum_graph_engine_hoist(rng, monkeypatch):
    """The ComputationGraph twin: hoisted bf16 accum is bit-equal to the
    un-hoisted program and its scan body is free of param-shaped bf16
    converts."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updaters import Sgd

    def conf():
        return (NeuralNetConfiguration.builder().seed(9)
                .data_type("BFLOAT16")
                .updater(Sgd(learning_rate=0.1))
                .graph_builder()
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(12))
                .add_layer("d1", DenseLayer(n_out=16, activation="tanh"),
                           "in")
                .add_layer("out", OutputLayer(n_out=4), "d1")
                .set_outputs("out")
                .build())

    x = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[
        np.random.default_rng(1).integers(0, 4, 16)])
    key = jax.random.PRNGKey(0)

    def run(unhoist=False):
        net = ComputationGraph(conf()).init()
        if unhoist:
            monkeypatch.setattr(type(net), "_uses_regularization",
                                lambda self: True)
        step = net._build_train_step(accum_steps=4)
        out = step(net.params, net.updater_state, net.state, jnp.int32(0),
                   key, (x,), (y,), (None,), (None,))
        return net, step, out

    net_h, step_h, out_h = run()
    _, _, out_u = run(unhoist=True)
    monkeypatch.undo()
    assert float(out_h[-1]) == float(out_u[-1])
    for a, b in zip(jax.tree.leaves(out_h[0]), jax.tree.leaves(out_u[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # scan body free of param-shaped bf16 converts (the hoist's signature)
    jaxpr = jax.make_jaxpr(step_h.__wrapped__)(
        net_h.params, net_h.updater_state, net_h.state, jnp.int32(0), key,
        (x,), (y,), (None,), (None,))
    param_shapes = {tuple(l.shape) for l in jax.tree.leaves(net_h.params)}
    bad = []

    def walk(jx, inside_scan):
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type" and inside_scan:
                ov_ = eqn.outvars[0]
                if str(ov_.aval.dtype) == "bfloat16" and \
                        tuple(ov_.aval.shape) in param_shapes:
                    bad.append(tuple(ov_.aval.shape))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(inner, inside_scan or
                         eqn.primitive.name == "scan")

    walk(jaxpr.jaxpr, False)
    assert bad == []


# ---------------------------------------------------------------------------
# bf16 audit fix (ISSUE 14 satellite): SameDiff other-vals cast hoist —
# the r12 scan hoist's sibling. Non-trainable values (imported CONSTs,
# frozen weights) are cast to the compute dtype ONCE at fit entry
# instead of inside every compiled step.
# ---------------------------------------------------------------------------

def _frozen_const_sd(seed=0):
    """A SameDiff graph with a NON-trainable float tensor (a frozen
    weight, the transfer-learning shape) feeding the trainable head."""
    from deeplearning4j_tpu.autodiff import SameDiff
    from deeplearning4j_tpu.nn.updaters import Adam

    rng = np.random.default_rng(seed)
    sd = SameDiff()
    x = sd.placeholder("x")
    wf = sd.constant("w_frozen",
                     rng.normal(size=(16, 16)).astype(np.float32))
    h = sd.call("linalg.mmul", x, wf, name="h0")
    h = sd.call("act.relu", h, name="h0r")
    w = sd.var("w", rng.normal(size=(16, 4)).astype(np.float32))
    logits = sd.call("linalg.mmul", h, w, name="logits")
    labels = sd.placeholder("labels")
    sd.set_loss(sd.call("loss.softmax_ce_logits", labels, logits))
    sd.set_updater(Adam(learning_rate=1e-3))
    sd.set_dtype("BFLOAT16")
    return sd


def _const_shaped_bf16_converts(sd, ov):
    """convert_element_type f32->bf16 eqns anywhere in the fit step whose
    shape matches a non-trainable tensor — the per-step cast the hoist
    removes."""
    from deeplearning4j_tpu.autodiff.samediff import VARIABLE
    tv = {n: sd._values[n] for n, v in sd._vars.items()
          if v.kind == VARIABLE}
    feeds = {"x": jnp.zeros((4, 16), jnp.float32),
             "labels": jnp.zeros((4, 4), jnp.float32)}
    _spec, step = sd._make_fit_step()
    opt = sd.updater.init_state(tv)
    # carry helper, not the bare dict: under the bf16 policy the fused
    # master-cast updater step (ISSUE 16) takes (masters, compute_copies)
    jaxpr = jax.make_jaxpr(step.__wrapped__)(
        sd._fit_carry(tv), opt, ov, jnp.int32(0), feeds)
    const_shapes = {(16, 16)}  # w_frozen; disjoint from every tv shape
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type" and \
                    str(eqn.outvars[0].aval.dtype) == "bfloat16" and \
                    str(eqn.invars[0].aval.dtype) == "float32" and \
                    tuple(eqn.outvars[0].aval.shape) in const_shapes:
                found.append(tuple(eqn.outvars[0].aval.shape))
            for v in eqn.params.values():
                if getattr(v, "jaxpr", None) is not None:
                    walk(v.jaxpr)
    walk(jaxpr.jaxpr)
    return found


def test_samediff_other_vals_cast_hoisted_out_of_step(rng):
    """With the hoist (pre-cast other_vals, the fit() path) the compiled
    step contains ZERO const-shaped f32->bf16 converts; handing raw f32
    other_vals still computes correctly through the in-step safety cast
    (exactly one convert) — the backward-compat contract."""
    from deeplearning4j_tpu.autodiff.samediff import VARIABLE
    sd = _frozen_const_sd()
    tv_names = {n for n, v in sd._vars.items() if v.kind == VARIABLE}
    ov_raw = {n: v for n, v in sd._values.items() if n not in tv_names}
    ov_cast = sd._cast_other_vals(ov_raw)
    assert str(ov_cast["w_frozen"].dtype) == "bfloat16"
    assert str(sd._values["w_frozen"].dtype) == "float32"  # master intact
    assert _const_shaped_bf16_converts(sd, ov_cast) == []
    assert len(_const_shaped_bf16_converts(sd, ov_raw)) >= 1


def test_samediff_other_vals_hoist_bit_equal(rng, monkeypatch):
    """fit() with the hoist is BIT-equal in every trained value to the
    pre-fix per-step-cast program (forced by disabling the hoist): the
    cast moved, the math did not."""
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    feeds = {"x": rng.normal(size=(4, 16)).astype(np.float32),
             "labels": np.eye(4, dtype=np.float32)[
                 np.random.default_rng(1).integers(0, 4, 4)]}
    h = _frozen_const_sd(seed=3)
    h.fit(dict(feeds), epochs=3)
    u = _frozen_const_sd(seed=3)
    monkeypatch.setattr(SameDiff, "_cast_other_vals",
                        lambda self, ov: ov)  # the pre-fix program
    u.fit(dict(feeds), epochs=3)
    monkeypatch.undo()
    assert h.variables() == u.variables()
    for n in h.variables():
        np.testing.assert_array_equal(np.asarray(h._values[n]),
                                      np.asarray(u._values[n]))


def test_samediff_cast_hoist_identity_for_f32_policy():
    sd = _frozen_const_sd()
    sd.set_dtype("FLOAT")
    ov = {"w_frozen": sd._values["w_frozen"]}
    out = sd._cast_other_vals(ov)
    assert out["w_frozen"] is ov["w_frozen"]  # no copy, no cast
