"""KV-cache autoregressive decode + continuous batching (ISSUE 8).

The acceptance suite for the generative serving path, all on CPU (the
decode kernel runs through the Pallas interpreter under mode "force"):

- decode-vs-recompute bit-parity: N incremental ``decode_step()`` calls
  must match the full-prefix ``reference_attention`` recompute
  (``_full_context`` — prefix-LM mask) within dtype tolerance, ragged
  lengths included;
- cache-bucket growth crosses a power-of-two boundary without losing
  state;
- join/leave-mid-batch continuous batching does not perturb other
  slots' outputs;
- deadline semantics (decided, ISSUE 8 satellite): continuous-batching
  deadlines bound enqueue->admission and RESTART at admission; the
  one-shot ``ParallelInference`` front keeps whole-request
  enqueue->dispatch deadlines (carried requests included);
- the ``serving.decode`` fault site, decode dispatch counters, the
  decode-phase histograms and the slot-occupancy gauge (telemetry
  floor entries).
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeplearning4j_tpu.ops as ops
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.attention import (
    LearnedSelfAttentionLayer, SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.ops import autotune as at
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.runtime import telemetry as tel
from deeplearning4j_tpu.serving import (ContinuousBatcher, DeadlineExceeded,
                                        GenerativeEngine, JsonModelServer,
                                        ParallelInference)

RNG = np.random.default_rng(7)
V = 16


@pytest.fixture
def force_mode():
    old = fa.set_mode("force")
    fa.reset_counters()
    yield
    fa.set_mode(old)


def _lm(dtype="float32", heads=2):
    conf = (NeuralNetConfiguration.builder().seed(0).data_type(dtype)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=V, n_heads=heads),
                  DenseLayer(n_out=24, activation="relu"),
                  SelfAttentionLayer(n_out=24, n_heads=heads),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _prompts(B, lo=2, hi=7, rng=RNG):
    plens = rng.integers(lo, hi, B)
    x = np.zeros((B, 8, V), np.float32)
    for b in range(B):
        x[b, :plens[b]] = np.eye(V, dtype=np.float32)[
            rng.integers(0, V, plens[b])]
    return x, plens


def _run_decode(net, prompt, plens, steps, C=16):
    """Incremental prefill + N decode steps; returns per-step outputs and
    the equivalent full-prefix recompute outputs."""
    B = prompt.shape[0]
    caches = net.init_decode_cache(B, C)
    y, caches = net._prefill(net.params, jnp.asarray(prompt), net.state,
                             caches, plens)
    y = np.asarray(y)
    lengths = plens.copy()
    seq = np.zeros((B, C, V), np.float32)
    seq[:, :prompt.shape[1]] = prompt
    got, want = [], []
    for step in range(steps):
        last = y[np.arange(B), lengths - 1] if step == 0 else y[:, 0]
        x_t = np.eye(V, dtype=np.float32)[np.argmax(last, -1)][:, None, :]
        y_t, caches = net._decode_step(net.params, jnp.asarray(x_t),
                                       net.state, caches,
                                       jnp.asarray(lengths))
        y = np.asarray(y_t)
        for b in range(B):
            seq[b, lengths[b]] = x_t[b, 0]
        lengths = lengths + 1
        oy = np.asarray(net._full_context(
            net.params, jnp.asarray(seq[:, :int(lengths.max())]),
            net.state, plens, lengths))
        got.append(y[:, 0])
        want.append(oy[np.arange(B), lengths - 1])
    return np.stack(got), np.stack(want)


# ---------------------------------------------------------------------------
# decode kernel + dispatcher
# ---------------------------------------------------------------------------

def test_decode_attention_kernel_matches_reference(rng, force_mode):
    """Single-query decode through the REAL kernel (interpret mode) ==
    the quadratic reference, ragged lengths included."""
    B, H, C, d = 3, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(B, H, 1, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, C, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, C, d)).astype(np.float32))
    lengths = jnp.asarray([5, 32, 1])
    y = fa.decode_dispatch(q, k, v, lengths)
    assert fa.counters()["decode_fused"] == 1
    ref = fa.reference_decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
    # the masked tail must not influence the output
    k2 = k.at[0, :, 5:].set(999.0)
    v2 = v.at[0, :, 5:].set(-999.0)
    y2 = fa.decode_dispatch(q, k2, v2, lengths)
    np.testing.assert_allclose(np.asarray(y2)[0], np.asarray(y)[0],
                               atol=1e-5)


def test_decode_dispatch_fallback_counters(rng):
    """Every decode routing decision is counted — zero silent fallbacks."""
    B, H, C, d = 2, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(B, H, 1, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, C, d)).astype(np.float32))
    v = k
    lengths = jnp.asarray([3, 16])
    fa.reset_counters()
    old = fa.mode()
    try:
        fa.set_mode("auto")
        fa.decode_dispatch(q, k, v, lengths)   # CPU: platform fallback
        assert fa.counters()["decode_fallback_platform"] == 1
        fa.set_mode("off")
        fa.decode_dispatch(q, k, v, lengths)
        assert fa.counters()["decode_fallback_mode"] == 1
        fa.set_mode("force")
        kq = jnp.asarray(rng.normal(size=(B, H, 12, d)).astype(np.float32))
        fa.decode_dispatch(q, kq, kq, lengths)  # C=12 does not tile
        assert fa.counters()["decode_fallback_shape"] == 1
        qi = q.astype(jnp.int32)
        fa.decode_dispatch(qi, k.astype(jnp.int32), v.astype(jnp.int32),
                           lengths)
        assert fa.counters()["decode_fallback_dtype"] == 1
        # ISSUE 12 satellite: Tq>1 no longer collapses into the shape
        # slug — a query-bank reference route gets its own decision, so
        # the speculative verify's fused/fallback mix stays separable
        q4 = jnp.concatenate([q, q], axis=2)    # Tq=2: reference path
        fa.decode_dispatch(q4, k, v, lengths)
        assert fa.counters()["decode_fallback_shape"] == 1
        assert fa.counters()["decode_fallback_multiquery"] == 1
    finally:
        fa.set_mode(old)


def test_cache_insert_semantics(rng):
    """Per-row insert position, write gating, and stale-length safety."""
    B, H, C, d = 3, 2, 8, 4
    cache = jnp.asarray(rng.normal(size=(B, H, C, d)).astype(np.float32))
    new = jnp.asarray(rng.normal(size=(B, H, 1, d)).astype(np.float32))
    lengths = jnp.asarray([0, 3, 7])
    out = np.asarray(fa.cache_insert(cache, new, lengths))
    for b, pos in enumerate([0, 3, 7]):
        np.testing.assert_array_equal(out[b, :, pos], np.asarray(new)[b, :, 0])
        mask = np.arange(C) != pos
        np.testing.assert_array_equal(out[b][:, mask],
                                      np.asarray(cache)[b][:, mask])
    # write mask: gated rows bit-identical; stale out-of-range length on a
    # gated row cannot corrupt anything (clamped write of the old value)
    out2 = np.asarray(fa.cache_insert(cache, new, jnp.asarray([0, 99, 7]),
                                      write=jnp.asarray([1, 0, 0])))
    np.testing.assert_array_equal(out2[1], np.asarray(cache)[1])
    np.testing.assert_array_equal(out2[2], np.asarray(cache)[2])
    np.testing.assert_array_equal(out2[0, :, 0], np.asarray(new)[0, :, 0])


def test_autotune_decode_key(tmp_path):
    """decode=True keys tune separately (block_q pinned 1), survive disk
    persistence, and never collide with the one-shot key."""
    at.reset()
    assert at.cache_key(1, 64, 16, np.float32, True, decode=True)[-1] == \
        "decode"
    b = at.get_blocks(1, 64, 16, np.float32, True, decode=True)
    assert b is not None and b[0] == 1 and 64 % b[1] == 0
    # one-shot key for the same (Tq=1, Tk) would not even tile (pick_block
    # can't produce a q block from Tq=1) — separate key spaces by design
    assert at.get_blocks(1, 64, 16, np.float32, True) is None
    assert at._valid_blocks([1, 32], 1, 64, 16, np.float32, decode=True)
    assert not at._valid_blocks([1, 32], 1, 64, 16, np.float32)
    cands = at.candidates(1, 64, 16, decode=True)
    assert cands and all(bq == 1 for bq, _ in cands)
    p = str(tmp_path / "tune.json")
    at.save(p)
    at.reset()
    n = at.load(p)
    assert n >= 1
    assert at.lookup(1, 64, 16, np.float32, True, decode=True) is not None
    at.reset()


# ---------------------------------------------------------------------------
# decode-vs-recompute parity (the tentpole acceptance criterion)
# ---------------------------------------------------------------------------

def test_decode_parity_ragged(rng):
    """N-step incremental decode == full-prefix recompute, ragged prompt
    lengths, f32 tolerance."""
    net = _lm()
    prompt, plens = _prompts(4)
    got, want = _run_decode(net, prompt, plens, steps=6)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_decode_parity_through_kernel(rng, force_mode):
    """Same parity with the REAL decode kernel (interpret mode) on the
    incremental side."""
    net = _lm()
    prompt, plens = _prompts(3)
    got, want = _run_decode(net, prompt, plens, steps=4)
    np.testing.assert_allclose(got, want, atol=2e-5)
    c = fa.counters()
    assert c["decode_fused"] >= 1, c


def test_decode_parity_bf16(rng):
    """dtype-tolerance parity under the bf16 policy."""
    net = _lm(dtype="bfloat16")
    prompt, plens = _prompts(3)
    got, want = _run_decode(net, prompt, plens, steps=4)
    np.testing.assert_allclose(got, want, atol=3e-2)


def test_learned_self_attention_decode_parity(rng):
    """LearnedSelfAttention threads (k, v, length) cache state too: its
    refreshed-summary decode equals recomputing over the valid prefix."""
    lyr = LearnedSelfAttentionLayer(n_out=8, n_heads=2, n_queries=3)
    params, state, _ = lyr.initialize(jax.random.PRNGKey(0), (8, V),
                                      jnp.float32)
    B, C = 2, 16
    plens = np.array([3, 5])
    x, _ = _prompts(B, rng=np.random.default_rng(3))
    spec = lyr.decode_cache_spec(params, B, C, jnp.float32)
    cache = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), spec)
    mask = (np.arange(8)[None] < plens[:, None]).astype(np.float32)
    _, cache = lyr.prefill(params, jnp.asarray(x), state, cache=cache,
                           lengths=jnp.asarray(plens), mask=mask)
    lengths = plens.copy()
    seq = np.zeros((B, C, V), np.float32)
    seq[:, :8] = x
    for step in range(3):
        x_t = np.asarray(
            np.random.default_rng(step).normal(size=(B, 1, V)),
            np.float32)
        y, cache = lyr.decode_step(params, jnp.asarray(x_t), state,
                                   cache=cache, lengths=jnp.asarray(lengths))
        for b in range(B):
            seq[b, lengths[b]] = x_t[b, 0]
        lengths = lengths + 1
        t = int(lengths.max())
        m2 = (np.arange(t)[None] < lengths[:, None]).astype(np.float32)
        ref, _, _ = lyr.apply(params, jnp.asarray(seq[:, :t]), state,
                              mask=jnp.asarray(m2))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=2e-5)


def test_non_decodable_layer_raises():
    """A recurrent layer is neither time-pointwise nor KV-cached: the
    decode walk refuses loudly instead of silently recomputing wrong."""
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(V, 8))
            .list(LSTM(n_out=8), OutputLayer(n_out=V)).build())
    net = MultiLayerNetwork(conf).init()
    with pytest.raises(ValueError, match="decode"):
        net.decode_cache_spec(2, 16)


# ---------------------------------------------------------------------------
# GenerativeEngine: buckets, growth, zero post-warmup compiles
# ---------------------------------------------------------------------------

def test_engine_bucket_growth_preserves_state(rng):
    """Crossing a power-of-two cache boundary re-buckets without losing
    state: the generation continues bit-identically vs a run that started
    on the big bucket."""
    net = _lm()
    eng = GenerativeEngine(net, slots=2)
    eng.warmup([8, 16], [8])
    prompt, plens = _prompts(1, 4, 6)

    def gen(c0, steps):
        st = eng.new_state(c0)
        st, logits = eng.prefill(st, prompt[0], int(plens[0]), 0)
        toks = [int(np.argmax(logits))]
        x = np.zeros((2, 1, V), np.float32)
        active = np.array([1, 0], np.int32)
        length = int(plens[0])
        for _ in range(steps - 1):
            x[0, 0] = np.eye(V, dtype=np.float32)[toks[-1]]
            if length >= st.cache_len:
                st = eng.grow(st, st.cache_len + 1)
            st, lg = eng.decode(st, x, active)
            length += 1
            toks.append(int(np.argmax(lg[0])))
        return toks

    steps = 10  # plen 4..5 + 9 decode tokens crosses the 8-bucket boundary
    small = gen(8, steps)
    big = gen(16, steps)
    assert small == big
    # growth itself is exact zero-padding
    st = eng.new_state(8)
    st, _ = eng.prefill(st, prompt[0], int(plens[0]), 0)
    before = jax.tree.map(np.asarray, st.caches)
    grown = eng.grow(st, 16)
    assert grown.cache_len == 16
    after = jax.tree.map(np.asarray, grown.caches)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert a.shape[2] == 16
        np.testing.assert_array_equal(a[:, :, :8], b)
        assert np.all(a[:, :, 8:] == 0)


def test_continuous_batching_zero_postwarmup_compiles(rng):
    """The steady-state acceptance criterion on tiny shapes: ragged
    prompts, staggered max_new_tokens, growth across a bucket — zero
    compile events after warmup."""
    net = _lm()
    cb = ContinuousBatcher(net, slots=2, max_cache_len=16, min_cache_len=8,
                           max_new_tokens=4)
    warm = cb.engine.compiles
    ev0 = int(tel.registry.get("compile.events").total())
    hs = [cb.submit(tokens=list(RNG.integers(0, V, 3)),
                    max_new_tokens=3 + (i % 3)) for i in range(5)]
    for h in hs:
        assert len(h.result(timeout=120)["tokens"]) >= 3
    assert cb.engine.compiles == warm
    assert int(tel.registry.get("compile.events").total()) == ev0
    st = cb.stats()
    assert st["tokens_generated"] >= 15
    assert st["slots_active"] == 0
    # telemetry floor surfaces: decode phases + slot gauge were written
    assert cb.engine._h_prefill.values_list()
    assert cb.engine._h_decode.values_list()
    cb.shutdown()


def test_join_leave_mid_batch_does_not_perturb(rng):
    """THE continuous-batching acceptance test: a request's token stream
    is identical whether it runs alone or with neighbours joining and
    leaving the in-flight batch at token boundaries."""
    net = _lm()
    tok_a = list(RNG.integers(0, V, 5))

    cb = ContinuousBatcher(net, slots=4, max_cache_len=16, min_cache_len=16,
                           max_new_tokens=8)
    alone = cb.submit(tokens=tok_a, max_new_tokens=8).result(
        timeout=120)["tokens"]

    # crowded run: A starts, B/C join mid-flight (shorter gens, so they
    # also LEAVE mid-flight while A keeps decoding)
    h_a = cb.submit(tokens=tok_a, max_new_tokens=8)
    stream = h_a.tokens(timeout=120)
    first = next(stream)
    h_b = cb.submit(tokens=list(RNG.integers(0, V, 2)), max_new_tokens=2)
    h_c = cb.submit(tokens=list(RNG.integers(0, V, 6)), max_new_tokens=3)
    crowded = [first] + list(stream)
    assert h_b.result(timeout=120)["tokens"]
    assert h_c.result(timeout=120)["tokens"]
    assert crowded == alone == h_a.result(timeout=1)["tokens"]
    cb.shutdown()


# ---------------------------------------------------------------------------
# deadlines, shedding, faults
# ---------------------------------------------------------------------------

def test_admission_deadline_expires_in_queue(rng):
    """deadline_ms bounds enqueue->admission: a request still queued when
    it expires fails fast with DeadlineExceeded and never prefills."""
    net = _lm()
    cb = ContinuousBatcher(net, slots=1, max_cache_len=32, min_cache_len=32,
                           max_new_tokens=24)
    blocker = cb.submit(tokens=[1, 2], max_new_tokens=24)
    starved = cb.submit(tokens=[3, 4], max_new_tokens=2, deadline_ms=1.0)
    with pytest.raises(DeadlineExceeded):
        starved.result(timeout=120)
    assert blocker.result(timeout=120)["tokens"]
    assert cb.stats()["deadline_expired"] == 1
    cb.shutdown()


def test_admission_deadline_restarts_at_admission(rng):
    """The decided multi-token semantics: once admitted, the clock
    restarts — a generation that takes far longer than deadline_ms still
    completes (deadline = per-request-admission, NOT per-token)."""
    net = _lm()
    faults.reset()
    cb = ContinuousBatcher(net, slots=1, max_cache_len=32, min_cache_len=32,
                           max_new_tokens=20, deadline_ms=150.0)
    faults.inject("serving.decode", delay=0.02, times=float("inf"))
    try:
        res = cb.submit(tokens=[1, 2, 3], max_new_tokens=20).result(
            timeout=120)
        # 20 tokens x >=20ms injected latency >> the 150ms deadline: only
        # the admission wait was bounded, the generation ran to completion
        assert len(res["tokens"]) == 20
        assert cb.stats()["deadline_expired"] == 0
    finally:
        faults.reset()
        cb.shutdown()


def test_parallel_inference_carried_request_keeps_deadline(rng):
    """The one-shot front's decided semantics: a carry-over request (it
    would overshoot the coalesced batch and leads the NEXT batch) keeps
    its ORIGINAL enqueue-based deadline — whole-request SLO, unlike the
    generative front's restart-at-admission."""
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.feed_forward(4))
            .list(DenseLayer(n_out=4), OutputLayer(n_out=2)).build())
    net = MultiLayerNetwork(conf).init()
    net.inference_engine().warmup([1, 2, 4])
    faults.reset()
    pi = ParallelInference(net, max_batch_size=4, max_wait_ms=20,
                           retry_transient=False)
    try:
        # slow down the FIRST dispatch so the carried request's deadline
        # lapses while batch 1 executes
        faults.inject("serving.slow", delay=0.25, times=1)
        f1 = pi.submit(np.zeros((3, 4), np.float32))
        f2 = pi.submit(np.zeros((2, 4), np.float32), deadline_ms=100.0)
        assert np.asarray(f1.result(timeout=60)).shape[0] == 3
        with pytest.raises(DeadlineExceeded):
            f2.result(timeout=60)
        assert pi.deadline_expired == 1
    finally:
        faults.reset()
        pi.shutdown()


def test_serving_decode_fault_site(rng):
    """The serving.decode failure path is deterministic in tier-1: one
    transient crash is retried (the iteration succeeds, counted); a
    persistent crash fails every in-flight request with the injected
    error and the batcher recovers for subsequent traffic."""
    net = _lm()
    faults.reset()
    cb = ContinuousBatcher(net, slots=2, max_cache_len=16, min_cache_len=16,
                           max_new_tokens=4)
    try:
        faults.inject("serving.decode", error="crash", times=1)
        res = cb.submit(tokens=[1, 2], max_new_tokens=4).result(timeout=120)
        assert len(res["tokens"]) == 4          # retried through
        assert cb.stats()["retries"] >= 1
        assert faults.counters()["serving.decode"]["fired"] == 1

        faults.inject("serving.decode", error="crash",
                      times=float("inf"))
        h = cb.submit(tokens=[3, 4], max_new_tokens=4)
        with pytest.raises(faults.InjectedCrash):
            h.result(timeout=120)
        faults.reset()
        # recovered: fresh state serves new traffic
        res = cb.submit(tokens=[5, 6], max_new_tokens=3).result(timeout=120)
        assert len(res["tokens"]) == 3
    finally:
        faults.reset()
        cb.shutdown()


def test_generate_shedding(rng):
    """Queue-depth shedding rejects in the caller's thread with
    QueueFull, same contract as the one-shot front."""
    from deeplearning4j_tpu.serving import QueueFull
    net = _lm()
    faults.reset()
    cb = ContinuousBatcher(net, slots=1, max_cache_len=32, min_cache_len=32,
                           max_new_tokens=16, shed_queue_depth=1)
    try:
        faults.inject("serving.decode", delay=0.02, times=float("inf"))
        cb.submit(tokens=[1], max_new_tokens=16)
        for _ in range(500):  # wait until the blocker owns the one slot
            if cb.active_slots() == 1:
                break
            time.sleep(0.005)
        cb.submit(tokens=[2], max_new_tokens=2)  # sits in the queue
        with pytest.raises(QueueFull):
            for _ in range(50):  # the queue holds >=1: must shed quickly
                cb.submit(tokens=[3], max_new_tokens=2)
                time.sleep(0.002)
        assert cb.stats()["shed"] >= 1
    finally:
        faults.reset()
        cb.shutdown()


def test_worker_survives_raising_sample_fn(rng):
    """A user-supplied sample_fn that raises must fail THAT request, not
    kill the decode thread — subsequent traffic keeps flowing (review
    finding: the worker loop needs a last-resort guard)."""
    net = _lm()
    calls = {"n": 0}

    def flaky_sample(logits):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("bad sampler")
        return int(np.argmax(logits))

    cb = ContinuousBatcher(net, slots=2, max_cache_len=16, min_cache_len=16,
                           max_new_tokens=3, sample_fn=flaky_sample)
    try:
        h1 = cb.submit(tokens=[1, 2], max_new_tokens=3)
        with pytest.raises(RuntimeError, match="bad sampler"):
            h1.result(timeout=120)
        # the worker is still alive and the slot was reclaimed
        res = cb.submit(tokens=[3, 4], max_new_tokens=3).result(timeout=120)
        assert len(res["tokens"]) == 3
        assert cb.active_slots() == 0
        assert cb.stats()["failures"] >= 1
    finally:
        cb.shutdown()


def test_samediff_decode_cache_full_raises(rng):
    """cached_sdpa clamps an out-of-range insert (XLA slice semantics) —
    DecodeGraph.decode_step must refuse host-side instead of silently
    overwriting the last cache row (review finding)."""
    from deeplearning4j_tpu.autodiff import fuse_attention
    from deeplearning4j_tpu.autodiff.decode import rewrite_for_decode

    NEG = np.float32(np.finfo(np.float32).min)
    B, H, d, Tp, C = 1, 1, 8, 4, 4
    sd = _mini_sd_transformer(rng, d)
    fuse_attention(sd)
    dg = rewrite_for_decode(sd, output="out")
    xp = rng.normal(size=(B, H, Tp, d)).astype(np.float32)
    kb = np.zeros((B, 1, 1, Tp), np.float32)
    _, caches = dg.prefill({"x": xp, "mask": kb}, np.array([4]), C)
    with pytest.raises(ValueError, match="cache full"):
        dg.decode_step({"x": xp[:, :, :1],
                        "mask": np.zeros((B, 1, 1, 1), np.float32)},
                       caches, np.array([4]))


# ---------------------------------------------------------------------------
# SameDiff decode rewrite
# ---------------------------------------------------------------------------

def _mini_sd_transformer(rng, d=8):
    from deeplearning4j_tpu.autodiff import SameDiff
    sd = SameDiff()
    x = sd.placeholder("x")          # [B,H,T,d] hidden states
    mask = sd.placeholder("mask")    # additive attention bias
    wq, wk, wv, wo = (sd.var(nm, rng.normal(size=(d, d)).astype(np.float32)
                             * 0.3) for nm in ("Wq", "Wk", "Wv", "Wo"))
    q = sd.call("linalg.mmul", x, wq, name="q")
    k = sd.call("linalg.mmul", x, wk, name="k")
    v = sd.call("linalg.mmul", x, wv, name="v")
    dk = sd.constant("dk", np.float32(np.sqrt(d)))
    scores = sd.call("linalg.mmul", q, k, name="scores",
                     attrs={"transpose_b": True})
    scaled = sd.call("math.div", scores, dk, name="scaled")
    masked = sd.call("math.add", scaled, mask, name="masked")
    probs = sd.call("act.softmax", masked, name="probs")
    ctx = sd.call("linalg.mmul", probs, v, name="ctx")
    sd.call("linalg.mmul", ctx, wo, name="out")
    return sd


def test_samediff_decode_rewrite_parity(rng):
    """fused_sdpa sites rewritten to cached_sdpa thread (k, v, length)
    state through the graph replay; N-step decode == the original fused
    graph recomputed over the full prefix under the prefix-LM mask."""
    from deeplearning4j_tpu.autodiff import fuse_attention
    from deeplearning4j_tpu.autodiff.decode import rewrite_for_decode

    NEG = np.float32(np.finfo(np.float32).min)
    B, H, d, Tp, C = 2, 2, 8, 8, 16
    sd = _mini_sd_transformer(rng, d)
    rep = fuse_attention(sd)
    assert rep.matched == 1
    dg = rewrite_for_decode(sd, output="out")
    assert dg.site_names() == ["ctx"]
    ops.mark_fwd_tested("attention.cached_sdpa")

    plens = np.array([5, 3])
    xp = rng.normal(size=(B, H, Tp, d)).astype(np.float32) * 0.5
    kb = np.where(np.arange(Tp)[None, None, None, :] <
                  plens[:, None, None, None], 0.0, NEG).astype(np.float32)
    y, caches = dg.prefill({"x": xp, "mask": kb}, plens, C)
    assert caches["ctx"]["k"].shape == (B, H, C, d)
    lengths = plens.copy()
    seq = np.zeros((B, H, C, d), np.float32)
    seq[:, :, :Tp] = xp
    for step in range(3):
        x_t = rng.normal(size=(B, H, 1, d)).astype(np.float32) * 0.5
        y, caches = dg.decode_step(
            {"x": x_t, "mask": np.zeros((B, 1, 1, 1), np.float32)},
            caches, lengths)
        for b in range(B):
            seq[b, :, lengths[b]] = x_t[b, :, 0]
        lengths = lengths + 1
        t = int(lengths.max())
        ii, jj = np.arange(t)[:, None], np.arange(t)[None, :]
        allowed = ((jj < plens[:, None, None]) | (jj <= ii)) \
            & (jj < lengths[:, None, None])
        bias = np.where(allowed[:, None], 0.0, NEG).astype(np.float32)
        ref = dg.base.output({"x": seq[:, :, :t], "mask": bias},
                             ["out"])["out"]
        np.testing.assert_allclose(y[:, :, 0],
                                   ref[np.arange(B), :, lengths - 1],
                                   atol=1e-5)


def test_samediff_decode_rewrite_requires_fused():
    from deeplearning4j_tpu.autodiff import SameDiff
    from deeplearning4j_tpu.autodiff.decode import rewrite_for_decode
    sd = SameDiff()
    sd.placeholder("x")
    with pytest.raises(ValueError, match="fused_sdpa"):
        rewrite_for_decode(sd, output="x")


# ---------------------------------------------------------------------------
# server streaming
# ---------------------------------------------------------------------------

def test_json_server_generate_streaming(rng):
    """POST /generate streams one NDJSON line per token, then the done
    line; non-streaming returns the full token list."""
    net = _lm()
    srv = JsonModelServer(net, generate=dict(
        slots=2, max_cache_len=16, min_cache_len=8, max_new_tokens=4))
    port = srv.start()
    try:
        body = json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 4,
                           "stream": True}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body), timeout=60)
        lines = [json.loads(x) for x in r.read().decode().splitlines() if x]
        assert lines[-1]["done"] is True
        assert [x["token"] for x in lines[:-1]] == lines[-1]["tokens"]
        assert len(lines[-1]["tokens"]) == 4

        body = json.dumps({"tokens": [5], "max_new_tokens": 2}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body), timeout=60)
        assert len(json.loads(r.read())["tokens"]) == 2
    finally:
        srv.stop()
