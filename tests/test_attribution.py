"""ISSUE 13: the MFU-attribution profiler (``runtime/attribution.py``).

Acceptance: ``attribution_report`` decomposes step time into
compute/memory/host fractions with ``mfu_gap`` accounted — fractions sum
to ~1.0 — for the train step (``model.attribution_report``, both the
self-measured and externally-measured paths) and the serving engines'
bucket/decode programs, keyed for the schedule tuner's cache.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.runtime import attribution as attr
from deeplearning4j_tpu.runtime import telemetry as tel


def _net(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.feed_forward(32))
            .list(DenseLayer(n_out=64, activation="tanh"),
                  OutputLayer(n_out=8, activation="softmax",
                              loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "test"}


def _assert_partition(rep):
    fr = rep["fractions"]
    assert fr is not None
    assert abs(sum(fr.values()) - 1.0) < 1e-9
    assert all(0.0 <= v <= 1.0 for v in fr.values())
    assert rep["mfu"] == fr["compute"]
    gap = rep["mfu_gap"]
    assert abs(gap["total"] - (1.0 - fr["compute"])) < 1e-9
    assert abs(gap["memory"] + gap["host"] + gap["other"]
               - gap["total"]) < 1e-9


# ------------------------------------------------------------- pure math
def test_attribute_partition_exact_values():
    # 1e9 flops @ 1e12 flops/s = 1ms compute; 1e9 bytes @ 1e11 B/s =
    # 10ms memory -> 9ms memory-bound excess; 2ms host; rest "other"
    rep = attr.attribute(1e9, 1e9, measured_s=0.020, host_s=0.002,
                         peaks=PEAKS)
    assert abs(rep["compute_s"] - 0.001) < 1e-12
    assert abs(rep["memory_s"] - 0.009) < 1e-12
    assert abs(rep["host_s"] - 0.002) < 1e-12
    assert abs(rep["other_s"] - 0.008) < 1e-12
    assert rep["roofline_bound"] == "memory"
    assert abs(rep["arithmetic_intensity"] - 1.0) < 1e-12
    _assert_partition(rep)


def test_attribute_clamps_keep_partition():
    # measured FASTER than the roofline compute bound: compute fraction
    # clamps to 1.0, nothing goes negative
    rep = attr.attribute(1e9, 0.0, measured_s=1e-5, peaks=PEAKS)
    _assert_partition(rep)
    assert rep["mfu"] == 1.0
    # host_s larger than the remaining time clamps too
    rep2 = attr.attribute(1e9, 0.0, measured_s=0.002, host_s=1.0,
                          peaks=PEAKS)
    _assert_partition(rep2)
    assert rep2["other_s"] == 0.0


def test_attribute_unmeasured_is_flagged():
    rep = attr.attribute(1e9, 1e9, measured_s=None, peaks=PEAKS)
    assert rep["measured"] is False
    assert rep["fractions"] is None and rep["mfu"] is None
    assert rep["roofline_compute_s"] > 0


# -------------------------------------------------------------- device peaks
def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2e12")
    monkeypatch.setenv("DL4J_TPU_PEAK_BW", "3e11")
    pk = attr.device_peaks()
    assert pk["flops_per_s"] == 2e12
    assert pk["bytes_per_s"] == 3e11
    assert pk["source"] == "env"


def test_device_peaks_unknown_device_is_an_error(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("DL4J_TPU_PEAK_BW", raising=False)
    # CPU CI has no table row: no calibration, no guess
    with pytest.raises(LookupError, match="device_kind 'cpu'"):
        attr.device_peaks()
    with pytest.raises(LookupError, match="device_kind 'cpu'"):
        attr.attribute(1e9, 1e9, measured_s=0.01)


def test_device_peaks_table_is_keyed_by_exact_kind():
    # what one v5e chip reports; published bf16 peak and HBM bandwidth
    assert attr.DEVICE_PEAKS["TPU v5 lite"] == {
        "flops_per_s": 197e12, "bytes_per_s": 819e9}
    assert "v5" not in attr.DEVICE_PEAKS and "tpu v5 lite" not in \
        attr.DEVICE_PEAKS


# ---------------------------------------------------------- train step
def test_model_attribution_report_partitions_and_caches():
    net = _net()
    rep = net.attribution_report(8, steps=2, peaks=PEAKS)
    assert rep["kind"] == "train_step" and rep["batch_size"] == 8
    assert rep["cost_available"] is True
    assert rep["measured_s"] > 0
    _assert_partition(rep)
    # keyed + cached so a schedule tuner can rank without re-measuring
    # (r18: a model fingerprint sits between the class and the batch so
    # same-class different-topology models never share a report)
    assert rep["key"].startswith(
        f"train.step:MultiLayerNetwork:{attr.model_fingerprint(net)}:b8")
    assert attr.cached_report(rep["key"])["measured_s"] == \
        rep["measured_s"]
    assert rep["key"] in attr.report_keys()
    # the probe lands in the retrace tracker, not as a mystery compile
    assert any(e["cause"] == "probe"
               for e in tel.compile_events("train.step"))


def test_model_attribution_external_measurement():
    """The bench path: attribute against an externally measured step time
    (no self-measurement runs)."""
    net = _net(seed=1)
    rep = net.attribution_report(4, measured_s=0.05, peaks=PEAKS)
    assert rep["measured_s"] == 0.05
    _assert_partition(rep)


def test_cost_analysis_unavailable_degrades(monkeypatch):
    net = _net(seed=2)
    monkeypatch.setattr(attr, "cost_analysis", lambda c: None)
    rep = net.attribution_report(4, measured_s=0.01)
    assert rep["cost_available"] is False
    assert rep["fractions"] is None and rep["mfu"] is None


# ------------------------------------------------------------- serving
def test_engine_attribution_after_traffic():
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    net = _net(seed=3)
    eng = InferenceEngine(net)
    eng.warmup([8])
    x = np.zeros((8, 32), np.float32)
    for _ in range(3):
        eng.output(x)
    compiles = eng.compiles
    ev0 = int(tel.registry.get("compile.events").total())
    rep = eng.attribution_report(8, peaks=PEAKS)
    # the warmed bucket's executable is REUSED: no probe compile, no
    # serving-counter movement (the tuner calls this repeatedly)
    assert eng.compiles == compiles
    assert int(tel.registry.get("compile.events").total()) == ev0
    assert rep["kind"] == "serving_bucket" and rep["bucket"] == 8
    _assert_partition(rep)
    # the measured window is the WHOLE call: execute p50 + the host
    # pad+unpad p50s (host time is a subset of the window, not carved
    # out of device time)
    ex = eng._h_exec.percentile(50)
    pad = eng._h_pad.percentile(50) or 0.0
    unpad = eng._h_unpad.percentile(50) or 0.0
    assert abs(rep["measured_s"] - (ex + pad + unpad)) <= 1e-9
    assert 0 <= rep["host_s"] <= pad + unpad + 1e-12


def test_generative_decode_attribution_explicit_measurement():
    from deeplearning4j_tpu.serving.engine import GenerativeEngine

    V = 16
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=V, n_heads=2),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    eng = GenerativeEngine(net, slots=2)
    rep = eng.attribution_report(16, measured_s=0.005, peaks=PEAKS)
    assert rep["kind"] == "decode_step" and rep["cache_len"] == 16
    _assert_partition(rep)


def test_attribute_jitted_lowers_on_avals():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: a @ b)
    aval = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    rep = attr.attribute_jitted(fn, (aval, aval), measured_s=0.001,
                                peaks=PEAKS, key="t.jitted:mm64")
    _assert_partition(rep)
    # 2*64^3 flops at 1e12 flops/s
    assert abs(rep["roofline_compute_s"] - 2 * 64 ** 3 / 1e12) < 1e-9
    assert attr.cached_report("t.jitted:mm64") is not None


# --------------------------------------------------- ISSUE 14 key bugfix
def test_report_key_tracks_workspace_mode_mutation():
    """ISSUE 14 satellite bugfix regression: the cached report's key must
    include the workspace/remat policy — a tuner reading cached fractions
    after a policy mutation would otherwise seed its search from the
    OLD program's numbers. Mutate the policy -> fresh key, fresh report;
    the old report stays cached under its own key."""
    net = _net(seed=11)
    rep1 = net.attribution_report(4, measured_s=1e-3, peaks=PEAKS)
    assert ":none" in rep1["key"]
    net.set_workspace_mode("dots_saveable")
    rep2 = net.attribution_report(4, measured_s=2e-3, peaks=PEAKS)
    assert rep2["key"] != rep1["key"]
    assert ":dots_saveable" in rep2["key"]
    assert rep2["workspace_mode"] == "dots_saveable"
    old = attr.cached_report(rep1["key"])
    assert old is not None and old["measured_s"] == 1e-3
    assert attr.cached_report(rep2["key"])["measured_s"] == 2e-3


def test_report_key_tracks_model_fingerprint():
    """Two models of the same class but different topologies must never
    share a cached report (the fingerprint half of the key)."""
    a = _net(seed=0)
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.feed_forward(32))
            .list(DenseLayer(n_out=128, activation="tanh"),
                  OutputLayer(n_out=8, activation="softmax",
                              loss="mcxent"))
            .build())
    b = MultiLayerNetwork(conf).init()
    ra = a.attribution_report(4, measured_s=1e-3, peaks=PEAKS)
    rb = b.attribution_report(4, measured_s=1e-3, peaks=PEAKS)
    assert ra["key"] != rb["key"]
    assert attr.model_fingerprint(a) != attr.model_fingerprint(b)
    assert attr.model_fingerprint(a) == attr.model_fingerprint(_net(seed=0))


def test_wrapper_report_key_tracks_overlap_settings():
    """ParallelWrapper.attribution_report keys on the overlap/sharding
    schedule: overlap on vs off (and different bucket sizes) are
    differently-scheduled programs and must cache separately."""
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    net = _net(seed=4)
    pw = ParallelWrapper(net, shard_update=True)
    r_off = pw.attribution_report(8, measured_s=1e-3, peaks=PEAKS)
    pw.set_overlap(True, bucket_mb=2)
    r_on = pw.attribution_report(8, measured_s=1e-3, peaks=PEAKS)
    assert r_off["key"] != r_on["key"]
    assert "ov=0" in r_off["key"] and "ov=1" in r_on["key"]
    assert "mb=2" in r_on["key"]
    assert r_on["kind"] == "parallel_step" and r_on["overlap"] is True
    _assert_partition(r_on)
    # both survive in the cache under their own keys
    assert attr.cached_report(r_off["key"]) is not None
    assert attr.cached_report(r_on["key"]) is not None


def test_wrapper_report_self_measures_real_sharded_steps():
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    net = _net(seed=6)
    pw = ParallelWrapper(net)
    rep = pw.attribution_report(8, steps=2, peaks=PEAKS)
    assert rep["measured"] and rep["measured_s"] > 0
    _assert_partition(rep)
    # the measurement must not have perturbed the model (donated copies)
    assert net.params["0"]["W"].shape == (32, 64)
