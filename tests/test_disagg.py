"""Disaggregated serving acceptance (ISSUE 18), all on CPU.

The tier-1 contract for the prefill/decode split:

- KV-page shipments round-trip the pickle-free wire format bit-exactly,
  f32 AND int8 (payload blocks + the d=1 scale rows);
- a migrated stream's greedy tokens are bit-equal to the un-migrated
  single-pool oracle in both kv modes;
- copy-on-write refcounts survive migration: forks after adoption never
  lose a fork, and draining every stream returns the pool to
  registry-only residency;
- structural mismatches between pools (page size, kv mode, head count,
  page count, wire version) reject LOUDLY before the request queues;
- ``deadline_ms`` RE-ARMS at decode-pool admission (the r13 contract
  extended): a slow handoff can never expire prefill work the origin
  pool already paid for, while the re-armed clock still bounds
  decode-queue wait;
- the router routes repeat prompts to their resident decode replica
  (no second prefill, no second migration) and exposes per-pool health;
- staticcheck's ``pool-scoped-metric-label`` rule fails an unlabeled
  pool cell (fixture positive/negative);
- the REAL two-process topology works: ``multihost_sim --disagg``
  ships pages over a socket and the decode process serves them
  (``run_disagg``, the fast tier-1 gate of ``make disagg-sim``).
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.runtime import staticcheck as sc
from deeplearning4j_tpu.runtime.faults import DeadlineExceeded
from deeplearning4j_tpu.serving import (ContinuousBatcher, DisaggRouter,
                                        KVShipment, PrefillReplica)

V = 16
PAGE = 8
CACHE = 32


def _lm(seed=0, heads=2):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=V, n_heads=heads),
                  DenseLayer(n_out=24, activation="relu"),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _prompt(toks):
    return np.eye(V, dtype=np.float32)[np.asarray(toks, np.int64)]


def _replica(net, kv_cache=None, **kw):
    kw.setdefault("pages", 32)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_cache_len", CACHE)
    kw.setdefault("prompt_buckets", [16])
    return PrefillReplica(net, kv_cache=kv_cache, **kw)


def _decoder(net, kv_cache=None, pool_label="decode", **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_cache_len", CACHE)
    kw.setdefault("pages", 32)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("migrate_buckets", [1, 2])
    return ContinuousBatcher(net, paged=True, kv_cache=kv_cache,
                             pool_label=pool_label, **kw)


# ---------------------------------------------------------------------------
# wire format: serialize -> ship -> adopt, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_cache", [None, "int8"])
def test_shipment_wire_roundtrip_bit_exact(kv_cache):
    """to_bytes/from_bytes is the identity on every payload leaf, the
    logits, and the handoff metadata — f32 and int8 (whose pools carry
    extra d=1 f32 scale leaves the header must preserve)."""
    net = _lm()
    pre = _replica(net, kv_cache=kv_cache)
    ship = pre.prefill(_prompt([1, 2, 3, 4, 5, 6, 7, 8, 9]))
    back = KVShipment.from_bytes(ship.to_bytes())
    assert back.page_size == ship.page_size
    assert back.plen == ship.plen == 9
    assert back.pages == ship.pages and len(back.pages) == 2
    assert back.kv_quant == (kv_cache == "int8")
    assert back.prefix_key == ship.prefix_key
    assert back.trace_id == ship.trace_id
    np.testing.assert_array_equal(np.asarray(back.logits),
                                  np.asarray(ship.logits))
    dtypes = set()
    for layer in ship.payload:
        assert set(back.payload[layer]) == set(ship.payload[layer])
        for name, arr in ship.payload[layer].items():
            got = back.payload[layer][name]
            assert got.dtype == np.asarray(arr).dtype
            np.testing.assert_array_equal(got, np.asarray(arr))
            dtypes.add(np.dtype(got.dtype).name)
    if kv_cache == "int8":
        # quantized pools ship int8 rows AND their f32 scale rows
        assert "int8" in dtypes and "float32" in dtypes
    else:
        assert dtypes == {"float32"}
    # adopting the deserialized shipment validates against a fresh pool
    back.validate_for(_decoder(net, kv_cache=kv_cache).engine)


# ---------------------------------------------------------------------------
# migrated greedy tokens == un-migrated single-pool oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_cache", [None, "int8"])
def test_migrated_tokens_match_colocated_oracle(kv_cache):
    net = _lm()
    pre = _replica(net, kv_cache=kv_cache)
    dec = _decoder(net, kv_cache=kv_cache)
    oracle = _decoder(net, kv_cache=kv_cache, pool_label="colocated")
    try:
        for toks in ([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8, 1, 8, 2]):
            x = _prompt(toks)
            ship = pre.prefill(x)
            want = oracle.submit(prompt=x).result()
            got = dec.submit_prefilled(ship).result()
            assert got["tokens"] == want["tokens"]
            assert len(got["tokens"]) == 6
        st = dec.stats()
        assert st["pool"] == "decode"
        assert st["engine"]["paged"]["adoptions"] >= 3
    finally:
        dec.shutdown()
        oracle.shutdown()


def test_fork_after_migration_preserves_cow(tmp_path):
    """CoW refcounts survive migration: two streams decoding off the
    SAME migrated prefix each fork privately (no lost forks, no
    cross-stream corruption), and draining every stream returns the
    pool to registry-only residency."""
    net = _lm()
    pre = _replica(net)
    dec = _decoder(net)
    oracle = _decoder(net, pool_label="colocated")
    toks = [3, 1, 4, 1, 5, 9]
    x = _prompt(toks)
    try:
        ship = pre.prefill(x)
        want = oracle.submit(prompt=x).result()["tokens"]
        first = dec.submit_prefilled(ship).result()
        assert first["tokens"] == want
        # two concurrent repeats hit the MIGRATED registry entry (no
        # re-migration) and fork the shared tail page on first write
        h1 = dec.submit(prompt=x)
        h2 = dec.submit(prompt=x)
        assert h1.result()["tokens"] == want
        assert h2.result()["tokens"] == want
        ps = dec.engine.pool.stats()
        assert ps["prefix_hits"] >= 2
        assert ps["forks"] >= 2          # one private fork per stream
        assert ps["adoptions"] == len(ship.pages)  # adopted exactly once
        # every stream drained: only the registry's own refs remain
        assert ps["pages_in_use"] == len(ship.pages)
    finally:
        dec.shutdown()
        oracle.shutdown()
        pre_stats = pre.stats()
    assert pre_stats["engine"]["paged"]["prefix_entries"] == 1


# ---------------------------------------------------------------------------
# loud structural rejection
# ---------------------------------------------------------------------------

def test_mismatched_shipment_rejected_loudly():
    net = _lm()
    pre = _replica(net)
    ship = pre.prefill(_prompt([1, 2, 3, 4, 5]))

    wrong_page = _decoder(net, page_size=16, migrate_buckets=[1])
    try:
        with pytest.raises(ValueError, match="page-size mismatch"):
            wrong_page.submit_prefilled(ship)
    finally:
        wrong_page.shutdown()

    wrong_kv = _decoder(net, kv_cache="int8")
    try:
        with pytest.raises(ValueError, match="quantization modes"):
            wrong_kv.submit_prefilled(ship)
    finally:
        wrong_kv.shutdown()

    wrong_heads = _decoder(_lm(heads=4))
    try:
        with pytest.raises(ValueError, match="head-count"):
            wrong_heads.submit_prefilled(ship)
    finally:
        wrong_heads.shutdown()

    dec = _decoder(net)
    try:
        # plen claims more tokens than the shipped pages can hold
        torn = KVShipment(ship.page_size, ship.plen + ship.page_size,
                          ship.pages, ship.payload, ship.logits)
        with pytest.raises(ValueError, match="pages for plen"):
            dec.submit_prefilled(torn)
    finally:
        dec.shutdown()

    blob = bytearray(ship.to_bytes())
    blob[8:9] = b"x"  # corrupt the JSON header
    with pytest.raises(Exception):
        KVShipment.from_bytes(bytes(blob))


# ---------------------------------------------------------------------------
# deadline re-arms at decode-pool admission (r13 extended)
# ---------------------------------------------------------------------------

def test_deadline_rearms_after_slow_handoff():
    """A handoff far longer than deadline_ms does NOT expire the
    request: the decode pool's clock starts at submit_prefilled, so the
    migrated stream completes — while the same budget still bounds
    decode-queue wait (a request stuck behind a busy slot expires)."""
    net = _lm()
    pre = _replica(net)
    dec = _decoder(net, slots=1)
    x = _prompt([3, 1, 4, 1, 5, 9])
    try:
        ship = pre.prefill(x)
        time.sleep(0.25)             # handoff 5x the deadline budget
        out = dec.submit_prefilled(ship, deadline_ms=50.0).result()
        assert len(out["tokens"]) == 6
        # ...but the re-armed clock is not a bypass: stall the single
        # slot with a long generation, and a queued migrated request
        # expires against its OWN decode-pool budget
        ship2 = pre.prefill(_prompt([2, 7, 1, 8, 2]))
        stall = dec.submit(prompt=x, max_new_tokens=24)
        h = dec.submit_prefilled(ship2, deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            h.result()
        stall.result()
        assert dec.stats()["deadline_expired"] >= 1
    finally:
        dec.shutdown()


# ---------------------------------------------------------------------------
# router: repeat prompts ride the resident replica, per-pool health
# ---------------------------------------------------------------------------

def test_router_migrates_once_then_hits_resident_replica():
    net = _lm()
    pre = _replica(net)
    d0 = _decoder(net)
    d1 = _decoder(net)
    oracle = _decoder(net, pool_label="colocated")
    x = _prompt([3, 1, 4, 1, 5, 9])
    try:
        want = oracle.submit(prompt=x).result()["tokens"]
        with DisaggRouter([pre], [d0, d1], max_new_tokens=6) as router:
            assert router.generate(prompt=x)["tokens"] == want
            st = router.stats()
            assert st["migrations"] == 1
            assert st["routed_prefill"] == 1
            assert st["routed_prefix_hit"] == 0
            # identical prompt again: routed to the RESIDENT decode
            # replica's own registry — no prefill, no second migration
            assert router.generate(prompt=x)["tokens"] == want
            st = router.stats()
            assert st["migrations"] == 1
            assert st["routed_prefix_hit"] == 1
            adoptions = sum(d.stats()["engine"]["paged"]["adoptions"]
                            for d in (d0, d1))
            assert adoptions == 1  # the one 1-page prompt, adopted once
            health = router.health()
            assert set(health) == {"router", "prefill", "decode"}
            assert all(v == "HEALTHY" for v in health.values())
    finally:
        d0.shutdown()
        d1.shutdown()
        oracle.shutdown()


# ---------------------------------------------------------------------------
# staticcheck: unlabeled pool cells fail lint
# ---------------------------------------------------------------------------

def rules_of(findings):
    return [f.rule for f in findings]


def test_pool_scoped_metric_label_positive_negative():
    bad = ("M = counter('serving.disagg.migrations', 'x')\n"
           "class R:\n"
           "    def __init__(self):\n"
           "        self.m = M.labeled(pi=self._id)\n"
           "        discard_cells\n")
    good = ("M = counter('serving.disagg.migrations', 'x')\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self.m = M.labeled(pi=self._id, pool='router')\n"
            "        discard_cells\n")
    other_family = ("M = counter('train.phase.step_s', 'x')\n"
                    "class R:\n"
                    "    def __init__(self):\n"
                    "        self.m = M.labeled(model=self._id)\n"
                    "        discard_cells\n")
    read_only = "v = counter('serving.disagg.migrations', 'x').value()\n"
    assert rules_of(sc.check_source(
        bad, rules=["pool-scoped-metric-label"])) \
        == ["pool-scoped-metric-label"]
    assert sc.check_source(good, rules=["pool-scoped-metric-label"]) == []
    assert sc.check_source(other_family,
                           rules=["pool-scoped-metric-label"]) == []
    assert sc.check_source(read_only,
                           rules=["pool-scoped-metric-label"]) == []


def test_package_passes_pool_rule():
    """Every serving.* cell in the REAL package binds pool= (or is
    baselined with a reason) — the lint gate ``make lint`` enforces."""
    rep = sc.run(rules=["pool-scoped-metric-label"])
    assert rep.findings == [], [str(f) for f in rep.findings]


# ---------------------------------------------------------------------------
# the REAL two-process topology (fast tier-1 gate of make disagg-sim)
# ---------------------------------------------------------------------------

def test_disagg_two_process_sim(tmp_path):
    """Tier-1 smoke of the full split (ISSUE 18 acceptance): a prefill
    PROCESS ships pages over a socket, a decode PROCESS adopts and
    serves them bit-equal to its colocated oracle in both kv modes, a
    repeat prompt rides the migrated registry entry, the stitched
    cross-process timeline tiles the measured latency, and neither pool
    compiles after warmup."""
    from deeplearning4j_tpu.parallel.multihost_sim import run_disagg
    art = run_disagg(str(tmp_path), timeout=280.0)
    assert art["value"] == 1.0
    assert art["post_warmup_compile_events"] == 0
    assert sorted(art["pools"]) == ["decode", "prefill"]
