"""Benchmark: prints ONE JSON line for the driver.

Headline (round 2+): ResNet-50 ComputationGraph training on the real chip,
reported as **MFU** (the BASELINE.md north-star metric: >=35%) plus
examples/sec and step time. Mixed precision per SURVEY.md §7.3 item 8:
dtype="BFLOAT16" means fp32 MASTER weights + updater state with bf16
compute — the exact policy the >=35% target is defined over.

Methodology notes (honesty over flattery):
- Training runs through the framework's compiled on-device epoch loop
  (``ComputationGraph._build_epoch_fn``: ``lax.scan`` of the fused
  train step over device-resident batches) — a first-class framework
  feature (tests/test_fit_on_device.py proves it bit-identical to the
  per-batch ``fit()`` path), not a bench-only construct. Distinct
  synthetic batches are uploaded ONCE before timing: this measures the
  compiled-step compute rate; input-pipeline transfer is excluded.
- Each measured chain ends by reading the loss history back to the host,
  which waits for the whole chain. The step time is the MIN over twelve
  128-step chains (timeit posture); ``step_time_median_ms`` is reported
  alongside so the spread is visible. Every step timed is a real
  on-device training step on its own batch.
- ``accuracy`` is null: synthetic data (zero-egress); LeNet-MNIST
  convergence is asserted in tests/test_model.py.
- ``vs_baseline`` is null: the reference publishes no numbers
  (BASELINE.md "unavailable"); 1.0-against-nothing would be dishonest.

Tuning record (r4/r5, interleaved A/Bs in one process; the numbers are in
BENCH_r04/r05 and DIAG*_r05 and predate the machine builders have now):
raising xla_tpu_scoped_vmem_limit_kib to 96 MiB lost ~1.7 MFU points
(rejected); 32-batch epoch launches change nothing. The fused flat-buffer
updater costs 8-13 MFU points on ResNet-50 (ravel/unravel defeats XLA's
donated in-place param update through the scan carry), and with the
leaf-wise updater batch 128 beats 256 (2x2 A/B, DIAG3_r05.json); a batch
fine-sweep (96..160, leaf-wise) puts the optimum at 128, with a sharp
cliff past it that tracks an XLA tiling boundary. Epoch-scan unroll 2/4 is
neutral (DIAG4_r05.json).
"""

import json
import time

import numpy as np

LOCAL_ARTIFACT = "BENCH_LOCAL_r06.json"


def _percentiles(samples):
    """(p50, p99) of a sample list, or (None, None) when empty — every
    bench reports tail latency alongside its min/median (serving needs the
    tail; training benches get it for free)."""
    if samples is None or len(samples) == 0:
        return None, None
    a = np.asarray(samples, dtype=np.float64)
    return float(np.percentile(a, 50)), float(np.percentile(a, 99))


def _emit(lines):
    """Print metric lines with the HEADLINE (ResNet MFU) LAST — the driver's
    ``parsed`` field takes the last JSON line, and round 4 lost the ResNet
    number to exactly that (BERT printed last + tail truncation). Also mirror
    every line to ``LOCAL_ARTIFACT`` so no truncation can eat a metric
    again. The artifact (not stdout — it can be large) additionally embeds
    a compact MetricsRegistry snapshot (ISSUE 6): every counter/histogram
    the benches drove, so a metric regression can be traced to e.g. a
    silent recompile without re-running."""
    order = sorted(lines, key=lambda d: d.get("metric") ==
                   "resnet50_train_mfu_pct")
    try:
        from deeplearning4j_tpu.ops import autotune as _autotune
        from deeplearning4j_tpu.runtime import telemetry as _telemetry
        # ISSUE 15 satellite: run the lint and embed its state — a bench
        # artifact records whether the tree it measured was clean, and
        # the staticcheck.findings{rule=,state=} counter lands in the
        # registry snapshot below. Import INSIDE the inner try: a broken
        # staticcheck must degrade this block alone, never the registry/
        # autotune snapshots that predate it
        try:
            from deeplearning4j_tpu.runtime import staticcheck as \
                _staticcheck
            _screp = _staticcheck.run()
            _sc_block = {"open": [f.as_dict() for f in _screp.findings],
                         "baselined": len(_screp.baselined),
                         "rules": _screp.rules,
                         "counter": _staticcheck.findings_snapshot()}
        except Exception as e:
            _sc_block = {"error": str(e)}
        artifact = order + [{
            "metric": "telemetry_registry_snapshot",
            "snapshot": _telemetry.snapshot(compact=True),
            "compile_events": _telemetry.compile_events()[-200:],
            # ISSUE 7 satellite: the autotune cache behind any kernel
            # metric is part of the record — a speedup claim without the
            # blocks that produced it is not reproducible
            "autotune_cache": _autotune.cache_snapshot(),
            "staticcheck": _sc_block,
        }]
    except Exception:
        artifact = order
    try:
        with open(LOCAL_ARTIFACT, "w") as f:
            json.dump(artifact, f, indent=1, default=str)
    except OSError:
        pass
    for line in order:
        print(json.dumps(line), flush=True)


def bench_resnet():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.resnet import (estimate_flops_per_example,
                                                  resnet50)
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.optimize.listeners import _detect_peak_flops

    rng = np.random.default_rng(0)
    nsteps = 8  # distinct device-resident batches per epoch chain link

    def run(batch):
        net = resnet50(updater=Sgd(learning_rate=0.1),
                       dtype="BFLOAT16").init()
        xs = jax.device_put(jnp.asarray(
            rng.normal(size=(nsteps, batch, 224, 224, 3)).astype(np.float32),
            dtype=jnp.bfloat16))
        ys = jax.device_put(jnp.asarray(
            np.eye(1000, dtype=np.float32)[
                rng.integers(0, 1000, (nsteps, batch))],
            dtype=jnp.bfloat16))
        xs.block_until_ready()
        ep = net._build_epoch_fn()
        key = jax.random.PRNGKey(0)

        def chain(k_epochs):
            params, opt, bn = jax.tree.map(
                jnp.copy, (net.params, net.updater_state, net.state))
            losses = None
            t0 = time.perf_counter()
            for e in range(k_epochs):
                params, opt, bn, losses = ep(
                    params, opt, bn, jnp.int32(e * nsteps),
                    jax.random.fold_in(key, e), (xs,), (ys,))
            fl = float(np.asarray(losses)[-1])  # forces the whole chain
            return time.perf_counter() - t0, fl, t0

        chain(1)  # compile + settle
        # Estimator: min over twelve 128-step chains, the closing readback
        # left IN the divisor (pessimistic direction).
        k = 16
        runs = [chain(k) for _ in range(12)]
        final_loss = runs[0][1]
        # per-chain record (start offset + wall) so a slow window can be
        # told from a regression in the artifact (r5 verdict item 1b)
        t_base = runs[0][2]
        chains = [{"t_off_s": round(r[2] - t_base, 1),
                   "step_ms": round(r[0] / (k * nsteps) * 1e3, 2)}
                  for r in runs]
        times = sorted(r[0] for r in runs)
        dt = times[0] / (k * nsteps)
        dt_median = times[len(times) // 2] / (k * nsteps)
        return net, dt, dt_median, final_loss, chains

    # Batch 128 (r5): the r4 batch-256 adoption was an artifact of the
    # fused-updater regression (see module docstring); with the leaf-wise
    # updater restored, 128 beats 256 by ~1.6 MFU points (DIAG3_r05.json).
    batch = 128
    while True:
        try:
            net, step_time, step_time_median, final_loss, chains = run(batch)
            break
        except Exception as e:  # OOM on small chips: halve and retry
            if batch <= 16 or "RESOURCE_EXHAUSTED" not in str(e).upper():
                raise
            batch //= 2

    step_p50, step_p99 = _percentiles([c["step_ms"] for c in chains])
    eps = batch / step_time
    fwd_flops = estimate_flops_per_example(net)
    peak = _detect_peak_flops()
    # 3x fwd approximates fwd+bwd (PerformanceListener convention)
    mfu = (3 * fwd_flops * eps / peak) if peak else None
    mfu_med = (3 * fwd_flops * (batch / step_time_median) / peak) \
        if peak else None

    # ISSUE 13: MFU attribution of the SAME measured step — cost_analysis
    # flops/bytes vs the min-chain step time, decomposed into compute/
    # memory/host/other fractions (sums to 1.0; "other" is the
    # inefficiency residue the schedule tuner hunts). Keyed in
    # the process-wide report cache; embedded here so the artifact
    # carries the decomposition next to the headline number.
    try:
        attribution = net.attribution_report(batch,
                                             measured_s=step_time)
    except Exception as e:  # never take the headline down
        attribution = {"error": f"{type(e).__name__}: {e}"[:300]}

    return {
        "metric": "resnet50_train_mfu_pct",
        "value": round(mfu * 100, 2) if mfu is not None else None,
        "unit": "%",
        "vs_baseline": None,
        "vs_baseline_reason": "reference publishes no benchmark numbers "
                              "(BASELINE.md: unavailable)",
        "model": "ResNet-50 ComputationGraph, NHWC, 224x224, bf16 compute / "
                 "fp32 master, on-device epoch loop, synthetic "
                 "device-resident data",
        "batch": batch,
        "examples_per_sec": round(eps, 1),
        "step_time_ms": round(step_time * 1e3, 2),
        "step_time_median_ms": round(step_time_median * 1e3, 2),
        "step_time_p50_ms": round(step_p50, 2) if step_p50 else None,
        "step_time_p99_ms": round(step_p99, 2) if step_p99 else None,
        "mfu_median_pct": round(mfu_med * 100, 2) if mfu_med else None,
        "chains": chains,
        "final_loss": round(final_loss, 3),
        "attribution": attribution,
        "fwd_gflops_per_example": round(fwd_flops / 1e9, 2),
        "peak_tflops_bf16": round(peak / 1e12, 1) if peak else None,
        "params": net.num_params(),
        "accuracy": None,
        "accuracy_reason": "synthetic data (zero-egress); LeNet synthetic-"
                           "MNIST accuracy >=0.95 asserted in tests/"
                           "test_lenet_mnist.py (>=0.99 tier arms when real "
                           "idx files are present)",
    }


def _bert_freezer():
    """(cfg, freeze) for the BERT-base bench: ``freeze(batch, seqlen)``
    re-traces ONE shared ``TFBertModel`` to a frozen GraphDef at the given
    shapes (the importer const-folds TF shape arithmetic, so every probed
    batch size needs its own freeze — weights are shared and irrelevant to
    throughput/memory)."""
    import os
    os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
    import tensorflow as tf
    from transformers import BertConfig, TFBertModel
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    cfg = BertConfig()  # bert-base-uncased geometry
    m = TFBertModel(cfg)

    def freeze(batch, seqlen):
        @tf.function
        def f(ids):
            return m(ids).last_hidden_state

        conc = f.get_concrete_function(
            tf.TensorSpec([batch, seqlen], tf.int32))
        frozen = convert_variables_to_constants_v2(conc)
        gd = frozen.graph.as_graph_def()
        iname = frozen.inputs[0].name.split(":")[0]
        oname = frozen.outputs[0].name.split(":")[0]
        return gd, iname, oname

    return cfg, freeze


def _bert_sd(gd, iname, oname, cfg, head_rng):
    """Import a frozen BERT GraphDef trainable, fuse attention, attach the
    mean-pool 2-class head + Adam. Returns (sd, fusion_report)."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_attention
    from deeplearning4j_tpu.modelimport.tensorflow import (
        TensorflowFrameworkImporter)
    from deeplearning4j_tpu.nn.updaters import Adam

    sd = TensorflowFrameworkImporter.import_graph_def(gd, trainable=True)
    # r8: rewrite the imported batch_matmul->scale->mask-add->softmax->
    # batch_matmul chains to the fused flash-attention op (ISSUE 3) —
    # the kernel reaches the flagship bench without touching importer code
    fusion_report = fuse_attention(sd)
    hidden = sd._vars[oname]
    pooled = hidden.mean(axis=1)
    w = sd.var("cls_W", head_rng.normal(0, 0.02, (cfg.hidden_size, 2))
               .astype(np.float32))
    b = sd.var("cls_b", np.zeros((2,), np.float32))
    logits = pooled.mmul(w) + b
    labels = sd.placeholder("labels")
    sd.set_loss(sd.call("loss.softmax_ce_logits", labels, logits))
    sd.set_updater(Adam(learning_rate=2e-5))
    return sd, fusion_report


def _bert_memory_autotune(freeze, cfg, base_batch, seqlen,
                          remat_mode="full", probe_limit=512):
    """Workspace-mode accounting for the BERT fit step (the ISSUE 4
    acceptance numbers): ``memory_report()`` temp/activation bytes at the
    base batch for workspace_mode none vs remat, and ``max_batch()``
    autotuning — the largest power-of-two batch whose AOT-lowered fit step
    fits the device ``bytes_limit``, probed per policy WITHOUT running a
    step (each probe re-freezes the TF graph: imported reshapes bake the
    batch). Returns the artifact sub-dict; max_batch fields stay None on
    backends without ``memory_stats`` (CPU verify runs)."""
    import jax
    from deeplearning4j_tpu.nn import memory as _memory

    rng = np.random.default_rng(7)

    def build(batch, mode):
        gd, iname, oname = freeze(batch, seqlen)
        sd, _ = _bert_sd(gd, iname, oname, cfg, rng)
        sd.set_dtype("BFLOAT16")
        sd.set_workspace_mode(mode)
        feeds_avals = {
            iname: jax.ShapeDtypeStruct((batch, seqlen), np.int32),
            "labels": jax.ShapeDtypeStruct((batch, 2), np.float32)}
        return sd, feeds_avals

    out = {"remat_mode": remat_mode, "base_batch": base_batch,
           "bytes_limit": None}
    for mode in ("none", remat_mode):
        sd, feeds_avals = build(base_batch, mode)
        rep = sd.memory_report(feeds_avals)
        key = "none" if mode == "none" else "remat"
        out[f"temp_bytes_{key}"] = rep["temp_bytes"]
        out[f"activation_bytes_{key}"] = rep["activation_bytes"]
        out[f"peak_bytes_{key}"] = rep["peak_bytes"]
        del sd
    if out.get("temp_bytes_none") and out.get("temp_bytes_remat"):
        out["temp_reduction_pct"] = round(
            100 * (1 - out["temp_bytes_remat"] / out["temp_bytes_none"]), 1)
    if out.get("activation_bytes_none") and out.get("activation_bytes_remat"):
        out["activation_reduction_pct"] = round(
            100 * (1 - out["activation_bytes_remat"]
                   / out["activation_bytes_none"]), 1)

    dm = _memory.device_memory_stats()
    out["max_batch_none"] = out["max_batch_remat"] = None
    if dm and dm.get("bytes_limit"):
        limit = out["bytes_limit"] = dm["bytes_limit"]
        for mode, key in (("none", "max_batch_none"),
                          (remat_mode, "max_batch_remat")):
            best, b = None, base_batch
            while b <= probe_limit:
                sd, feeds_avals = build(b, mode)
                rep = sd.memory_report(feeds_avals)
                del sd
                if rep["peak_bytes"] is None or rep["peak_bytes"] > limit:
                    break
                best = b
                b <<= 1
            out[key] = best
    return out


def _rederive_phase_split(f32_fwd_ms, f32_updater_ms, bf16_fwd_ms,
                          bf16_updater_ms, master_cast_ms):
    """Re-derive the bf16 phase split with the per-step master cast
    attributed to the phase that actually pays it (ISSUE 16 bugfix).

    The audit's ``upd`` runner times ``updater.apply`` on the MASTERS
    alone, so the f32->bf16 cast sweep never lands in the updater phase
    — it hides inside fwd (``loss_fn`` casts the masters on entry).
    That made ``bf16_vs_f32.updater`` overstate the updater phase and
    understate fwd, and it is exactly the accounting the fused
    master-cast updater changes: ``apply_leafwise_cast`` folds the cast
    into the updater write, so the honest comparison books
    ``master_cast_ms`` WITH the updater and WITHOUT fwd. Pure dict
    helper (unit-tested on literals); returns {} when the cast probe
    failed. Old fields stay untouched — these ride side by side."""
    if master_cast_ms is None:
        return {}
    cast = float(master_cast_ms)
    incl = float(bf16_updater_ms) + cast
    excl = max(float(bf16_fwd_ms) - cast, 1e-9)
    return {
        "bf16_updater_ms_incl_cast": round(incl, 3),
        "bf16_fwd_ms_excl_cast": round(excl, 3),
        "bf16_vs_f32_rederived": {
            "fwd": round(float(f32_fwd_ms) / excl, 3),
            "updater": round(float(f32_updater_ms) / incl, 3),
        },
    }


def _bert_phase_audit(sd, feeds, rounds=5):
    """Per-phase bf16-vs-f32 attribution (ISSUE 7 satellite): the fit
    step's three phases — fwd (loss only), fwd+bwd (``value_and_grad``),
    updater (apply on fixed gradients) — are timed as separate jitted
    programs per precision config, INTERLEAVED (drift hits both
    alike). bwd is attributed as vg - fwd. The ratios
    make the headline ``bf16_speedup_vs_f32`` arbitrable: a bf16 loss
    confined to the updater phase is cast/layout thrash around the f32
    masters, one confined to fwd is kernel/fusion coverage, etc."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.autodiff.samediff import VARIABLE

    train_names = [n for n, v in sd._vars.items() if v.kind == VARIABLE]

    def build(dtype):
        # both configs run the Environment's default matmul-precision
        # policy — the audit attributes the headline bf16-vs-DEFAULT-f32
        # ratio (the true-f32/HIGHEST baseline is the main bench's job)
        sd.set_dtype(dtype)
        loss_fn = sd._fit_loss_fn()
        fwd = jax.jit(loss_fn)
        vg = jax.jit(lambda tv, ov, fd: jax.value_and_grad(
            lambda t: loss_fn(t, ov, fd))(tv))
        updater = sd.updater
        upd = jax.jit(lambda g, opt, tv: updater.apply(
            g, opt, tv, jnp.int32(0)))
        tv = {n: jnp.copy(sd._values[n]) for n in train_names}
        # r18 cast hoist: non-trainable values pre-cast ONCE (fit()'s
        # path) — the audit times the program the fit loop actually runs
        ov = sd._cast_other_vals(
            {n: v for n, v in sd._values.items() if n not in tv})
        fd = {k: jnp.asarray(v) for k, v in feeds[0].items()}
        opt = updater.init_state(tv)
        # warm all three (compile + settle)
        float(fwd(tv, ov, fd))
        _, grads = vg(tv, ov, fd)
        float(jnp.sum(jax.tree.leaves(grads)[0].astype(jnp.float32)))
        delta, _ = upd(grads, opt, tv)
        float(jnp.sum(jax.tree.leaves(delta)[0].astype(jnp.float32)))

        def t_fwd():
            return float(fwd(tv, ov, fd))

        def t_vg():
            loss, g = vg(tv, ov, fd)
            return float(loss)

        def t_upd():
            d_, _ = upd(grads, opt, tv)
            return float(jnp.sum(jax.tree.leaves(d_)[0]
                                 .astype(jnp.float32)))
        return {"fwd": t_fwd, "vg": t_vg, "updater": t_upd}

    configs = {"f32": build("FLOAT"), "bf16": build("BFLOAT16")}
    times = {c: {p: [] for p in ("fwd", "vg", "updater")} for c in configs}
    for _ in range(rounds):  # interleaved: drift hits both alike
        for c, runners in configs.items():
            for p, fn in runners.items():
                t0 = time.perf_counter()
                fn()  # each runner forces its own host readback
                times[c][p].append(time.perf_counter() - t0)
    out = {}
    best = {c: {p: min(v) for p, v in ph.items()}
            for c, ph in times.items()}
    for c in configs:
        out[f"{c}_fwd_ms"] = round(best[c]["fwd"] * 1e3, 3)
        out[f"{c}_bwd_ms_attributed"] = round(
            (best[c]["vg"] - best[c]["fwd"]) * 1e3, 3)
        out[f"{c}_updater_ms"] = round(best[c]["updater"] * 1e3, 3)
    out["bf16_vs_f32"] = {
        "fwd": round(best["f32"]["fwd"] / best["bf16"]["fwd"], 3),
        "bwd": round(
            max(best["f32"]["vg"] - best["f32"]["fwd"], 1e-9)
            / max(best["bf16"]["vg"] - best["bf16"]["fwd"], 1e-9), 3),
        "updater": round(best["f32"]["updater"]
                         / best["bf16"]["updater"], 3),
    }
    # attribute the INHERENT residual cost of the mixed policy: the
    # per-step fp32-master -> bf16 cast of the trainable tree (what's
    # left in the fwd phase after the r12 scan hoist and the r18
    # other-vals hoist — it cannot be hoisted because the masters change
    # every step). If the headline ratio sits below 1.0, this number
    # says whether cast bandwidth alone explains it.
    try:
        from deeplearning4j_tpu import dtypes as _dtypes
        sd.set_dtype("BFLOAT16")
        tv_m = {n: jnp.copy(sd._values[n]) for n in train_names}
        cast = jax.jit(lambda t: _dtypes.cast_floating(t, jnp.bfloat16))
        jax.block_until_ready(cast(tv_m))
        casts = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            jax.block_until_ready(cast(tv_m))
            casts.append(time.perf_counter() - t0)
        out["master_cast_ms"] = round(min(casts) * 1e3, 3)
    except Exception as e:
        out["master_cast_ms"] = None
        out["master_cast_error"] = f"{type(e).__name__}: {e}"[:200]
    out.update(_rederive_phase_split(
        out["f32_fwd_ms"], out["f32_updater_ms"], out["bf16_fwd_ms"],
        out["bf16_updater_ms"], out["master_cast_ms"]))
    return out


def bench_bert():
    """Second driver-visible metric (round-4): BERT-base fine-tune
    throughput through the TF-import path (BASELINE.md row 4 — 'trains;
    samples/sec reported'). Full bert-base geometry (12 layers, hidden 768,
    12 heads, vocab 30522), randomly initialized offline (zero-egress —
    pretrained weights unavailable; throughput is weight-value-independent),
    frozen to a GraphDef, imported trainable, mean-pool + 2-class head,
    Adam. Same timing methodology as the ResNet line: device-resident
    chained steps via the cached compiled fit step, one readback per chain,
    min over chains with the readback left in the divisor.

    r5: the SameDiff dtype policy (``sd.set_dtype("BFLOAT16")`` — fp32
    masters, bf16 compute, engine parity) is benchmarked head-to-head with
    f32, INTERLEAVED chains; the headline value is the bf16 path. MFU uses analytic matmul
    FLOPs: per-example fwd = 2*P_matmul*T + 4*L*T^2*d with P_matmul =
    12*L*d^2 (QKVO + 2 FFN mats; embeddings/gathers excluded), x3 for
    fwd+bwd.

    r6 (ISSUE 4 satellite): the r5 ``bf16_speedup_vs_f32`` field measured
    0.987 and read as noise because its "f32" baseline already ran
    single-pass bf16 MXU matmuls (Environment "auto" -> DEFAULT precision
    on TPU). Three configs now run interleaved: bf16 policy, default-f32
    (renamed field ``bf16_speedup_vs_default_f32``, annotated), and a TRUE
    f32 baseline at HIGHEST matmul precision
    (``bf16_speedup_vs_true_f32``) — the policy gain is reported against
    the baseline that actually computes in f32.

    r6 tentpole: workspace-mode remat accounting + max-batch autotuning
    (``memory`` sub-dict + ``autotuned_*`` fields): temp/activation bytes
    none-vs-remat from ``memory_report()``, ``max_batch()`` per policy
    against the device bytes_limit (AOT probing, no OOM), and measured
    examples/sec at the autotuned batch with remat on.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import environment as _envmod
    from deeplearning4j_tpu.ops import autotune as at
    from deeplearning4j_tpu.ops import flash_attention as fa

    batch, seqlen = 32, 128
    cfg, freeze = _bert_freezer()
    fa.reset_counters()
    gd, iname, oname = freeze(batch, seqlen)
    rng = np.random.default_rng(0)
    sd, fusion_report = _bert_sd(gd, iname, oname, cfg, rng)

    # ISSUE 7: warm the block-shape autotune cache for the fused attention
    # sites' shapes BEFORE any timed chain — on TPU the sweeps compile
    # here (cause="autotune" in the retrace tracker) and the timed window
    # then traces the SWEPT blocks with zero further compiles; on CPU this
    # seeds the target-128 defaults (no sweeps — the tier-1 guard)
    head_d = cfg.hidden_size // cfg.num_attention_heads
    try:
        at.warmup([(seqlen, seqlen, head_d, jnp.bfloat16, True),
                   (seqlen, seqlen, head_d, jnp.float32, True)])
    except Exception:
        pass  # an autotune failure must never take the headline down

    nsteps = 4  # distinct batches per chain link
    feeds = []
    for _ in range(nsteps):
        ids = rng.integers(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32)
        y = np.eye(2, dtype=np.float32)[(ids.sum(axis=1) % 2)]
        feeds.append({iname: jax.device_put(jnp.asarray(ids)),
                      "labels": jax.device_put(jnp.asarray(y))})

    # compile + seed one cached step per precision config; the jitted fns
    # stay alive after cache eviction, enabling interleaved A/B
    from deeplearning4j_tpu.autodiff.samediff import VARIABLE
    from deeplearning4j_tpu.optimize.listeners import _detect_peak_flops
    train_names = [n for n, v in sd._vars.items() if v.kind == VARIABLE]

    def make_runner(dtype, f32_precision=None):
        # f32_precision overrides the Environment matmul-precision policy
        # for THIS runner's trace ("highest" = the true-f32 baseline); the
        # fit-step cache spec includes the mode, so each config retraces
        # into its own step
        env = _envmod.Environment.instance()
        prev = env.f32_matmul_precision
        if f32_precision is not None:
            env.f32_matmul_precision = f32_precision
        try:
            sd.set_dtype(dtype)
            sd.fit(dict(feeds[0]), epochs=1)
            step = sd._fn_cache["__fit_step__"][1]
        finally:
            env.f32_matmul_precision = prev
        # deep-copy: the fit step donates its train_vals/opt_state args, so
        # a later runner's sd.fit would delete arrays this one still holds.
        # other_vals pre-cast to the config's compute dtype (the r18 hoist
        # — matches the avals fit() traced the cached step with)
        train_vals = {n: jnp.copy(sd._values[n]) for n in train_names}
        other_vals = sd._cast_other_vals(
            {n: v for n, v in sd._values.items() if n not in train_vals})
        opt_state = sd.updater.init_state(train_vals)
        # fused master-cast updater (ISSUE 16): the bf16 step's first arg
        # is the (masters, compute_copies) carry — the carry helpers keep
        # this driver signature-agnostic
        state = {"tv": sd._fit_carry(train_vals), "opt": opt_state}

        def chain(k):
            t0 = time.perf_counter()
            loss = None
            i = 0
            tv, opt = state["tv"], state["opt"]
            for e in range(k):
                for fd in feeds:
                    tv, opt, loss = step(tv, opt, other_vals,
                                         jnp.asarray(i, jnp.int32), fd)
                    i += 1
            state["tv"], state["opt"] = tv, opt
            fl = float(loss)  # force the chain
            return time.perf_counter() - t0, fl

        chain(1)  # settle
        # avals of the fit-step call, captured NOW (the chains donate and
        # delete the live arrays): the ISSUE 13 attribution lowers the
        # same jitted step on these for cost_analysis — nothing executes
        try:
            step_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), getattr(a, "dtype",
                                         np.asarray(a).dtype)),
                (sd._fit_carry(train_vals), opt_state, other_vals,
                 jnp.asarray(0, jnp.int32), feeds[0]))
            step_info = (step, step_avals)
        except Exception:
            step_info = None
        return chain, state, step_info

    chain_f32, _, _ = make_runner("FLOAT")
    chain_f32h, _, _ = make_runner("FLOAT", f32_precision="highest")
    chain_b16, st16, step16 = make_runner("BFLOAT16")

    runs32, runs32h, runs16 = [], [], []
    for _ in range(6):  # interleaved: drift hits all configs alike
        runs32.append(chain_f32(8))
        runs32h.append(chain_f32h(8))
        runs16.append(chain_b16(8))
    steps_per_chain = 8 * nsteps

    def stats(runs):
        times = sorted(r[0] for r in runs)
        return (times[0] / steps_per_chain,
                times[len(times) // 2] / steps_per_chain)

    dt32, dt32_med = stats(runs32)
    dt32h, _dt32h_med = stats(runs32h)
    dt, dt_med = stats(runs16)
    bert_p50, bert_p99 = _percentiles(
        [r[0] / steps_per_chain * 1e3 for r in runs16])
    # snapshot BEFORE the autotune probes below re-trace the fused graph
    # per (mode, batch) — the field keeps its r5 meaning: dispatch decisions
    # of the headline timing configs only
    dispatch_counters = fa.counters()

    # per-phase bf16-vs-f32 attribution (ISSUE 7 satellite): fresh jitted
    # fwd / fwd+bwd / updater programs, interleaved — makes the headline
    # ratio arbitrable by phase in the artifact
    try:
        phase_audit = _bert_phase_audit(sd, feeds)
    except Exception as e:
        phase_audit = {"error": f"{type(e).__name__}: {e}"[:300]}

    # tentpole: workspace-mode memory accounting + max-batch autotune,
    # then measured throughput at the autotuned batch with remat on
    try:
        memory = _bert_memory_autotune(freeze, cfg, batch, seqlen)
    except Exception as e:
        memory = {"error": f"{type(e).__name__}: {e}"[:300]}
    autotuned_batch = memory.get("max_batch_remat")
    autotuned_eps = None
    if autotuned_batch and autotuned_batch > batch:
        gd_a, iname_a, oname_a = freeze(autotuned_batch, seqlen)
        sd_a, _ = _bert_sd(gd_a, iname_a, oname_a, cfg,
                           np.random.default_rng(1))
        sd_a.set_dtype("BFLOAT16")
        sd_a.set_workspace_mode(memory.get("remat_mode", "full"))
        feeds_a = []
        for _ in range(nsteps):
            ids = rng.integers(0, cfg.vocab_size,
                               (autotuned_batch, seqlen)).astype(np.int32)
            ya = np.eye(2, dtype=np.float32)[(ids.sum(axis=1) % 2)]
            feeds_a.append({iname_a: jax.device_put(jnp.asarray(ids)),
                            "labels": jax.device_put(jnp.asarray(ya))})
        sd_a.fit(dict(feeds_a[0]), epochs=1)  # compile + settle
        step_a = sd_a._fn_cache["__fit_step__"][1]
        tv0 = {n: jnp.copy(sd_a._values[n]) for n in sd_a.variables()}
        ov = sd_a._cast_other_vals(
            {n: v for n, v in sd_a._values.items() if n not in tv0})
        opt = sd_a.updater.init_state(tv0)
        tv = sd_a._fit_carry(tv0)  # fused-updater carry (ISSUE 16)
        times_a = []
        for _ in range(4):
            t0 = time.perf_counter()
            i = 0
            loss_a = None
            for _e in range(4):
                for fd in feeds_a:
                    tv, opt, loss_a = step_a(tv, opt, ov,
                                             jnp.asarray(i, jnp.int32), fd)
                    i += 1
            float(loss_a)  # force the chain
            times_a.append((time.perf_counter() - t0) / (4 * nsteps))
        autotuned_eps = round(autotuned_batch / min(times_a), 1)
        memory["autotuned_step_time_ms"] = round(min(times_a) * 1e3, 2)
        del sd_a, tv, ov, opt, feeds_a

    # analytic matmul FLOPs (docstring derivation)
    L, d = cfg.num_hidden_layers, cfg.hidden_size
    p_matmul = 12 * L * d * d
    fwd_flops = 2.0 * p_matmul * seqlen + 4.0 * L * seqlen ** 2 * d
    peak = _detect_peak_flops()
    mfu16 = 3 * fwd_flops * (batch / dt) / peak if peak else None
    mfu32 = 3 * fwd_flops * (batch / dt32) / peak if peak else None

    # ISSUE 13: cost-analysis attribution of the bf16 fit step against
    # the measured min-chain step time (fractions sum to 1.0; the
    # compute fraction is XLA-counted MFU vs the analytic mfu_pct above)
    try:
        from deeplearning4j_tpu.runtime import attribution as _attr
        if step16 is None:
            raise ValueError("fit-step avals were not capturable")
        step_fn, step_avals = step16
        attribution = _attr.attribute_jitted(
            step_fn, step_avals, measured_s=dt,
            key=f"samediff.fit_step:bert-base:b{batch}xT{seqlen}:bf16")
    except Exception as e:  # never take the metric down
        attribution = {"error": f"{type(e).__name__}: {e}"[:300]}

    return {
        "metric": "bert_base_finetune_examples_per_sec",
        "value": round(batch / dt, 1),
        "unit": "examples/sec",
        "vs_baseline": None,
        "vs_baseline_reason": "reference publishes no benchmark numbers "
                              "(BASELINE.md: unavailable)",
        "model": "BERT-base (12L/768H/12A, vocab 30522) via TF-GraphDef "
                 "import, trainable, mean-pool 2-class head, Adam",
        "precision": "bf16 compute / fp32 masters (sd.set_dtype BFLOAT16); "
                     "matmuls native bf16 MXU passes",
        "mfu_pct": round(mfu16 * 100, 2) if mfu16 is not None else None,
        "batch": batch,
        "seq_len": seqlen,
        "tokens_per_sec": round(batch * seqlen / dt, 0),
        "step_time_ms": round(dt * 1e3, 2),
        "step_time_median_ms": round(dt_med * 1e3, 2),
        "step_time_p50_ms": round(bert_p50, 2) if bert_p50 else None,
        "step_time_p99_ms": round(bert_p99, 2) if bert_p99 else None,
        "f32_examples_per_sec": round(batch / dt32, 1),
        "f32_mfu_pct": round(mfu32 * 100, 2) if mfu32 is not None else None,
        "f32_step_time_ms": round(dt32 * 1e3, 2),
        "f32_precision": "fp32 storage; matmul passes per Environment "
                         "policy auto->DEFAULT on TPU (single bf16 pass)",
        # renamed from r5's bf16_speedup_vs_f32: this baseline ALREADY runs
        # single-pass bf16 MXU matmuls, so ~1.0 is expected, not noise
        "bf16_speedup_vs_default_f32": round(dt32 / dt, 3),
        # ISSUE 7 acceptance headline, restored under its original name and
        # held to the HARDER baseline (default-f32 matmuls are already
        # bf16 MXU passes — any win here is pure storage/cast efficiency,
        # which is exactly what the r12 audit fixes target); the per-phase
        # attribution lives in phase_audit/bf16_phase_ratios
        "bf16_speedup_vs_f32": round(dt32 / dt, 3),
        "bf16_phase_ratios": phase_audit.get("bf16_vs_f32"),
        "phase_audit": phase_audit,
        "autotune_counters": at.counters(),
        "true_f32_examples_per_sec": round(batch / dt32h, 1),
        "true_f32_step_time_ms": round(dt32h * 1e3, 2),
        "true_f32_precision": "fp32 storage; matmul precision forced "
                              "HIGHEST (genuine f32 accumulation passes)",
        "bf16_speedup_vs_true_f32": round(dt32h / dt, 3),
        "memory": memory,
        "attribution": attribution,
        "autotuned_batch": autotuned_batch,
        "autotuned_examples_per_sec": autotuned_eps,
        "fwd_gflops_per_example": round(fwd_flops / 1e9, 2),
        "final_loss": round(runs16[0][1], 4),
        "params": int(sum(
            int(np.prod(v.shape))
            for v in sd._carry_masters(st16["tv"]).values())),
        "attention_sites_fused": fusion_report.matched,
        "attention_sites_unmatched": fusion_report.unmatched,
        "attention_dispatch": dispatch_counters,
    }


def _opt_bytes_per_device(opt):
    """Per-device updater-state footprint: one device's shard of every
    leaf (== full size when replicated)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(opt):
        shp = leaf.sharding.shard_shape(leaf.shape)
        total += int(np.prod(shp)) * leaf.dtype.itemsize
    return total


def _sharded_update_measure():
    """Sharded-vs-replicated weight update (ZeRO-1,
    ``ParallelWrapper(shard_update=True)``) on THIS process's devices:
    per-device Adam m/v bytes and step time both ways. Runs wherever
    ``len(jax.devices()) >= 4`` — the real pod path and the virtual-mesh
    subprocess share this code."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

    ndev = len(jax.devices())
    d = 512

    def build():
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(learning_rate=1e-3))
                .input_type(InputType.feed_forward(d))
                .list(DenseLayer(n_out=4 * d, activation="relu"),
                      DenseLayer(n_out=4 * d, activation="relu"),
                      OutputLayer(n_out=d)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    batch = 8 * ndev
    x = rng.normal(size=(batch, d)).astype(np.float32)
    y = np.eye(d, dtype=np.float32)[rng.integers(0, d, batch)]
    ds = DataSet(x, y)

    def run(shard, overlap=False):
        net = build()
        pw = ParallelWrapper(net, shard_update=shard, overlap_grads=overlap)
        pw.fit(ds, epochs=2)      # compile + settle
        float(net.score())        # force (block_until_ready unreliable here)
        # 4 chains of 5 steps: min keeps the least-contended estimate (the
        # prior 20-step single block), per-chain samples feed p50/p99
        chain_steps, per_step = 5, []
        for _ in range(4):
            t0 = time.perf_counter()
            pw.fit(ds, epochs=chain_steps)
            float(net.score())
            per_step.append((time.perf_counter() - t0) / chain_steps)
        return net, min(per_step), per_step

    net_r, dt_r, steps_r = run(False)
    bytes_r = _opt_bytes_per_device(net_r.updater_state)
    net_s, dt_s, steps_s = run(True)
    bytes_s = _opt_bytes_per_device(net_s.updater_state)
    # ISSUE 7: collective/compute overlap A/B for the sharded update —
    # same arithmetic (bit-equivalence tested), per-bucket early
    # reduce-scatter + issue-order chaining vs the plain GSPMD placement
    net_o, dt_o, steps_o = run(True, overlap=True)
    from deeplearning4j_tpu.runtime import telemetry as _telemetry
    # per-model labeled cells: the overlap run's count is the max across
    # the gauge's series (the other runs' cells read 0)
    n_buckets = int(max(_telemetry.registry.get(
        "parallel.overlap.buckets").series().values() or [0]))
    p50_r, p99_r = _percentiles([t * 1e3 for t in steps_r])
    p50_s, p99_s = _percentiles([t * 1e3 for t in steps_s])
    p50_o, p99_o = _percentiles([t * 1e3 for t in steps_o])

    return {
        "metric": "sharded_update",
        "value": round(bytes_r / bytes_s, 2),
        "unit": "x_per_device_updater_bytes_reduction",
        "model": f"MLP {d}-{4 * d}-{4 * d}-{d}, Adam, fp32",
        "devices": ndev,
        "params": net_r.num_params(),
        "opt_bytes_per_device_replicated": bytes_r,
        "opt_bytes_per_device_sharded": bytes_s,
        "step_time_ms_replicated": round(dt_r * 1e3, 2),
        "step_time_ms_sharded": round(dt_s * 1e3, 2),
        "step_time_p50_ms_replicated": round(p50_r, 2),
        "step_time_p99_ms_replicated": round(p99_r, 2),
        "step_time_p50_ms_sharded": round(p50_s, 2),
        "step_time_p99_ms_sharded": round(p99_s, 2),
        "sharded_step_speedup": round(dt_r / dt_s, 3),
        # overlap-on-vs-off for the sharded update (ISSUE 7 acceptance):
        # > 1.0 = the bucketed early-scatter path is faster; on the CPU
        # virtual mesh the collectives are memcpys and ~1.0 is expected —
        # the field exists so the real-chip driver run measures it
        "step_time_ms_sharded_overlap": round(dt_o * 1e3, 2),
        "step_time_p50_ms_sharded_overlap": round(p50_o, 2),
        "step_time_p99_ms_sharded_overlap": round(p99_o, 2),
        "overlap_step_ratio": round(dt_s / dt_o, 3),
        "overlap_buckets": n_buckets,
        "batch": batch,
    }


def bench_sharded_update():
    """ZeRO-1 sharded weight update metric. Needs >= 4 devices to mean
    anything; with fewer the measurement runs in a CPU-only subprocess
    (JAX_PLATFORMS=cpu, so it never asks for the chip its parent holds)
    on a virtual 8-device CPU mesh (the sharding math — bytes
    per device — is topology arithmetic and transfers; the step-time
    column there is CPU-relative, recorded as such)."""
    import jax
    if len(jax.devices()) >= 4:
        return _sharded_update_measure()

    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    code = ("import json, bench; "
            "print('@@RESULT@@' + json.dumps(bench._sharded_update_measure()))")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=900, cwd=os.path.dirname(os.path.abspath(__file__)))
    for line in out.stdout.splitlines():
        if line.startswith("@@RESULT@@"):
            d = json.loads(line[len("@@RESULT@@"):])
            d["note"] = ("single-device bench env: measured on a virtual "
                         "8-device CPU mesh subprocess; bytes/device is "
                         "topology arithmetic, step times are CPU-relative")
            return d
    raise RuntimeError("sharded-update subprocess produced no result: "
                       + out.stderr[-400:])


def bench_flash_attention():
    """Flash-attention metric (ISSUE 3): fused Pallas kernel vs the
    quadratic einsum path, seq-length sweep 128-2048, TRAIN-step shaped
    work (forward + backward via the kernel's custom VJP), p50/p99 via
    ``_percentiles``. Headline value = fused speedup at seq 1024.

    On TPU both paths are timed compiled; off-TPU (CPU tier/verify runs)
    the kernel only exists in Pallas interpret mode, which is a
    correctness vehicle, not a perf one — the metric is still emitted,
    recording interpret-mode parity numbers and the dispatch counters so
    the driver sees the kernel path exercised (value stays null).
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import autotune as at
    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.runtime import telemetry as tel

    rng = np.random.default_rng(0)
    on_tpu = jax.default_backend() == "tpu"
    fa.reset_counters()
    at.reset_counters()

    def qkv(B, H, T, d, dtype):
        mk = lambda: jnp.asarray(
            rng.normal(size=(B, H, T, d)) * 0.5, dtype=dtype)
        mask = np.ones((B, T), np.float32)
        mask[:, T - T // 8:] = 0.0  # ragged tail: exercise the key-bias path
        bias = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0,
                         np.float32(np.finfo(np.float32).min))
        return mk(), mk(), mk(), bias

    if not on_tpu:
        # interpret-mode parity only (kernel compiled per-shape by the
        # Pallas interpreter: keep it small and single-shape)
        B, H, T, d = 2, 4, 256, 64
        q, k, v, bias = qkv(B, H, T, d, jnp.float32)
        old = fa.set_mode("force")
        try:
            fused = fa.attention(q, k, v, bias)
            gf = jax.grad(lambda x: jnp.sum(fa.attention(x, k, v, bias)))(q)
        finally:
            fa.set_mode(old)
        ref = fa.reference_attention(q, k, v, bias)
        gr = jax.grad(
            lambda x: jnp.sum(fa.reference_attention(x, k, v, bias)))(q)
        return {
            "metric": "flash_attention",
            "value": None,
            "unit": "x_fused_vs_einsum_step_time_at_seq1024",
            "note": "CPU bench env: interpret-mode parity only (no kernel "
                    "timing off-TPU); speedup measured on the real chip",
            "fwd_max_abs_diff": float(jnp.max(jnp.abs(fused - ref))),
            "grad_max_abs_diff": float(jnp.max(jnp.abs(gf - gr))),
            "parity_shape": [B, H, T, d],
            "dispatch_counters": fa.counters(),
            # CPU runs seed target-128 defaults and NEVER sweep (the
            # tier-1 guard contract); the autotuned speedup column is a
            # real-chip quantity
            "autotuned_speedup_vs_default": None,
            "autotune_counters": at.counters(),
        }

    B, H, d = 4, 12, 64
    dtype = jnp.bfloat16
    rows = []

    def time_fn(fn, *args):
        # fn ends in a host readback each call, which waits for the
        # device; 12 samples feed min + p50/p99
        fn(*args)  # compile + settle
        samples = []
        for _ in range(12):
            t0 = time.perf_counter()
            fn(*args)
            samples.append(time.perf_counter() - t0)
        return samples

    for T in (128, 256, 512, 1024, 2048):
        q, k, v, bias = qkv(B, H, T, d, dtype)

        def train_shaped(path_fn):
            def loss(q_, k_, v_):
                return jnp.sum(
                    path_fn(q_, k_, v_, bias).astype(jnp.float32))
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

            def run(q_, k_, v_):
                gs = g(q_, k_, v_)
                return float(jnp.sum(gs[0].astype(jnp.float32)))
            return run

        fused_fn = train_shaped(fa.flash_attention)
        ref_fn = train_shaped(fa.reference_attention)
        t_f = time_fn(fused_fn, q, k, v)
        t_r = time_fn(ref_fn, q, k, v)
        f50, f99 = _percentiles([t * 1e3 for t in t_f])
        r50, r99 = _percentiles([t * 1e3 for t in t_r])
        rows.append({"seq": T,
                     "fused_ms_min": round(min(t_f) * 1e3, 3),
                     "fused_ms_p50": round(f50, 3),
                     "fused_ms_p99": round(f99, 3),
                     "einsum_ms_min": round(min(t_r) * 1e3, 3),
                     "einsum_ms_p50": round(r50, 3),
                     "einsum_ms_p99": round(r99, 3),
                     "speedup": round(min(t_r) / min(t_f), 3)})

    # ---- block-shape autotune A/B (ISSUE 7 tentpole): sweep the headline
    # shape, then time the swept blocks against the classic 128-target
    # defaults — the sweep compiles are attributed cause="autotune" in the
    # retrace tracker, and the timed window after it must be compile-free
    # (the warm-cache steady-state acceptance criterion)
    T_at = 1024
    entry = at.sweep(T_at, T_at, d, dtype, True)
    tuned_bq, tuned_bk = entry["blocks"]
    q, k, v, bias = qkv(B, H, T_at, d, dtype)

    def blocked(bq, bk, bias_):
        def loss(q_, k_, v_):
            return jnp.sum(fa.flash_attention(
                q_, k_, v_, bias_, block_q=bq,
                block_k=bk).astype(jnp.float32))
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def run(q_, k_, v_):
            gs = g(q_, k_, v_)
            return float(jnp.sum(gs[0].astype(jnp.float32)))
        return run

    tuned_fn = blocked(tuned_bq, tuned_bk, bias)
    default_fn = blocked(128, 128, bias)
    tuned_fn(q, k, v)    # compile before the zero-compile window
    default_fn(q, k, v)
    compiles_before = tel.registry.get("compile.events").total()
    t_tuned = time_fn(tuned_fn, q, k, v)
    t_default = time_fn(default_fn, q, k, v)
    post_warmup_compiles = \
        tel.registry.get("compile.events").total() - compiles_before

    # dispatch sanity on the layer entry point (counters in the artifact) —
    # the warm cache now routes the dispatcher through the SWEPT blocks
    fa.attention(q, k, v, bias)
    by_seq = {r["seq"]: r["speedup"] for r in rows}
    return {
        "metric": "flash_attention",
        "value": by_seq.get(1024),
        "unit": "x_fused_vs_einsum_step_time_at_seq1024",
        "model": f"MHA fwd+bwd, B={B} H={H} d={d}, bf16, ragged key mask, "
                 "custom-VJP flash kernel vs f32-softmax einsum",
        "sweep": rows,
        "speedup_at_2048": by_seq.get(2048),
        "autotuned_blocks": [tuned_bq, tuned_bk],
        "autotuned_step_ms_min": round(min(t_tuned) * 1e3, 3),
        "default_step_ms_min": round(min(t_default) * 1e3, 3),
        "autotuned_speedup_vs_default": round(min(t_default)
                                              / min(t_tuned), 3),
        "autotune_counters": at.counters(),
        "post_warmup_compile_events": int(post_warmup_compiles),
        "dispatch_counters": fa.counters(),
    }


def bench_fused_epilogues(rounds=13, steps_per_round=20):
    """Fused-epilogue library metric (ISSUE 16). Headline value = fused
    master-cast+updater step time over the unfused two-program sequence
    (updater sweep, then a standalone f32->bf16 cast sweep of the fresh
    masters) — the ONE fusion in the library whose win is measurable off-
    TPU, because it removes a full-params HBM round-trip rather than
    relying on Pallas codegen (the BN/LN/GeLU epilogue kernels only beat
    XLA on the real chip; off-TPU they run as interpret-mode parity
    fixtures, so this bench does not time them). Discipline matches
    flash-attention's: interleaved A/B chains, median of per-round
    ratios, ZERO post-warmup compile events via the ``compile.events``
    counter delta (the bounded log saturates; the counter does not), and
    the dispatch + autotune counters embedded in the artifact. Bit-parity
    of the resulting masters AND updater state is asserted in-bench
    before any timing — a fused step that drifts must fail the metric,
    not report a speedup. Pass = ratio < 1.0."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import dtypes as _dtypes
    from deeplearning4j_tpu.nn import updaters as _updaters
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.ops import autotune as at
    from deeplearning4j_tpu.ops import fused_epilogues as fe
    from deeplearning4j_tpu.runtime import telemetry as _tel

    rng = np.random.default_rng(16)
    # BERT-base tree SHAPE at hidden=256 (12 layers x 16 leaves: qkv/out
    # projections + biases, two LayerNorm pairs, the FFN pair, plus an
    # embedding table — 193 leaves, ~44 MB): the leaf COUNT is the point,
    # not just the bytes. The unfused sequence pays a second program
    # launch + a second ~200-leaf pytree dispatch every step, which is
    # exactly the overhead the fused single program removes; a
    # few-big-leaves toy tree would hide it
    params = {}
    H, F = 256, 1024
    shapes = [("q_w", (H, H)), ("q_b", (H,)), ("k_w", (H, H)),
              ("k_b", (H,)), ("v_w", (H, H)), ("v_b", (H,)),
              ("o_w", (H, H)), ("o_b", (H,)), ("ln1_g", (H,)),
              ("ln1_b", (H,)), ("ln2_g", (H,)), ("ln2_b", (H,)),
              ("f1_w", (H, F)), ("f1_b", (F,)), ("f2_w", (F, H)),
              ("f2_b", (H,))]
    for layer_i in range(12):
        for nm, shape in shapes:
            params[f"l{layer_i}_{nm}"] = jnp.asarray(
                rng.normal(size=shape).astype(np.float32))
    params["emb"] = jnp.asarray(
        rng.normal(size=(8192, H)).astype(np.float32))
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            rng.normal(size=p.shape).astype(np.float32)) * 1e-3, params)
    updater = Adam(learning_rate=1e-3)
    cdt = jnp.bfloat16

    # no donate_argnums on EITHER side: donation costs ~2x on the XLA CPU
    # runtime (measured; both configurations equally), which would bury
    # the A/B signal under an artifact the real TPU steps don't have
    upd = jax.jit(lambda g, opt, p, i: _updaters.apply_leafwise(
        updater, g, opt, p, i))
    cast = jax.jit(lambda p: _dtypes.cast_floating(p, cdt))
    fused = jax.jit(lambda g, opt, p, i: _updaters.apply_leafwise_cast(
        updater, g, opt, p, i, cdt))

    # bit-parity gate: K steps from identical trees; masters, updater
    # state AND compute copies must be bit-equal before timing starts
    pu, ou = params, updater.init_state(params)
    pf = jax.tree.map(jnp.copy, params)
    of = updater.init_state(params)
    for i in range(3):
        si = jnp.asarray(i, jnp.int32)
        pu, ou = upd(grads, ou, pu, si)
        pcu = cast(pu)
        pf, pcf, of = fused(grads, of, pf, si)
    for k in pu:
        bits = lambda a: np.asarray(a).view(np.uint32)
        assert np.array_equal(bits(pu[k]), bits(pf[k])), k
        assert np.array_equal(np.asarray(pcu[k], np.float32),
                              np.asarray(pcf[k], np.float32)), k
    for lu, lf in zip(jax.tree.leaves(ou), jax.tree.leaves(of)):
        assert np.array_equal(np.asarray(lu), np.asarray(lf))

    def run_unfused(k, st):
        p, opt = st
        t0 = time.perf_counter()
        for i in range(k):
            p, opt = upd(grads, opt, p, jnp.asarray(i, jnp.int32))
            pc = cast(p)
        jax.block_until_ready(pc)
        return time.perf_counter() - t0, (p, opt)

    def run_fused(k, st):
        p, opt = st
        t0 = time.perf_counter()
        for i in range(k):
            p, pc, opt = fused(grads, opt, p, jnp.asarray(i, jnp.int32))
        jax.block_until_ready(pc)
        return time.perf_counter() - t0, (p, opt)

    stu = (params, updater.init_state(params))
    stf = (jax.tree.map(jnp.copy, params), updater.init_state(params))
    _, stu = run_unfused(steps_per_round, stu)   # settle
    _, stf = run_fused(steps_per_round, stf)
    ev0 = int(_tel.registry.get("compile.events").total())
    ratios, t_unf, t_fus = [], [], []
    reps, chain = 3, max(steps_per_round // 3, 1)
    for _ in range(rounds):
        # tightly interleaved u/f/u/f/... chains; each arm's round time is
        # the MIN over its chains (timing noise on a shared host is
        # strictly additive — a burst inflates one chain, never
        # deflates one), then median-of-ratios across rounds on top
        tus, tfs = [], []
        for _r in range(reps):
            tu, stu = run_unfused(chain, stu)
            tf_, stf = run_fused(chain, stf)
            tus.append(tu / chain)
            tfs.append(tf_ / chain)
        t_unf.append(min(tus))
        t_fus.append(min(tfs))
        ratios.append(min(tfs) / min(tus))
    post_compiles = int(_tel.registry.get("compile.events").total()) - ev0

    # dispatch accounting: the decision the engines record once per
    # compiled step (plus the off/penalty fallbacks for the counter row)
    fe.dispatch_updater("BFLOAT16")
    median_ratio = float(np.median(ratios))
    p50, p99 = _percentiles(t_fus)
    return {
        "metric": "fused_epilogues",
        "value": round(median_ratio, 3),
        "unit": "x_fused_vs_unfused_master_cast_updater_step_time",
        "pass": bool(median_ratio < 1.0) and post_compiles == 0,
        "unfused_step_ms_min": round(min(t_unf) * 1e3, 3),
        "fused_step_ms_min": round(min(t_fus) * 1e3, 3),
        "fused_step_ms_p50": round(p50 * 1e3, 3),
        "fused_step_ms_p99": round(p99 * 1e3, 3),
        "ratio_rounds": [round(r, 3) for r in ratios],
        "bit_parity": "asserted (masters, updater state, compute copies)",
        "post_warmup_compile_events": int(post_compiles),
        "dispatch_counters": fe.counters(),
        "autotune_counters": at.epilogue_counters(),
        "params_mb": round(sum(int(np.prod(p.shape)) * 4
                               for p in jax.tree.leaves(params)) / 2**20, 1),
        "note": ("epilogue BN/LN/GeLU kernels are TPU-only wins; off-TPU "
                 "they run interpret-mode for parity (tests), so only the "
                 "pure-XLA fused updater is timed here"),
    }


def bench_workspace_remat():
    """Workspace-mode remat metric (ISSUE 4), runnable on ANY backend (the
    BERT-scale numbers live in bench_bert's ``memory`` sub-dict on the real
    chip): a deep MLP's REAL train step is AOT-lowered + compiled per
    policy — nothing executes — and the artifact records (a) the
    forward→backward activation-residual bytes remat removes, (b) XLA
    ``memory_analysis`` temp bytes, and (c) ``max_batch()`` autotuning
    against a SYNTHETIC bytes_limit (the none-policy peak at 2x the base
    batch), demonstrating the remat policy admits a strictly larger batch
    at the same limit. Headline value = activation-bytes reduction %."""
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    feat, hidden, depth, base_batch = 256, 1024, 12, 64

    def build(mode):
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(learning_rate=1e-3))
                .input_type(InputType.feed_forward(feat))
                .workspace_mode(mode)
                .list(*[DenseLayer(n_out=hidden, activation="relu")
                        for _ in range(depth)],
                      OutputLayer(n_out=16))
                .build())
        return MultiLayerNetwork(conf).init()

    nets = {m: build(m) for m in ("none", "full", "dots_saveable",
                                  "every_4")}
    reports = {m: n.memory_report(base_batch) for m, n in nets.items()}
    act = {m: r["activation_bytes"] for m, r in reports.items()}
    # headline: the sqrt-spacing policy (boundaries every 4 layers) — for
    # an MLP, per-layer "full" boundaries ARE the activations, so every_k
    # is where the win lives
    reduction = None
    if act["none"] and act["every_4"]:
        reduction = round(100 * (1 - act["every_4"] / act["none"]), 1)

    # synthetic limit: what the NONE policy needs at 2x the base batch —
    # none then tops out at 2x; remat admits strictly more where the
    # compiler's buffer accounting models remat liveness (TPU; XLA:CPU
    # reports policy-insensitive temps, recorded via the note)
    max_none = max_remat = limit = None
    if reports["none"]["peak_bytes"] is not None:
        limit = nets["none"].memory_report(2 * base_batch)["peak_bytes"]
        max_none = nets["none"].max_batch(limit, start=base_batch,
                                          limit=32 * base_batch)
        max_remat = nets["every_4"].max_batch(limit, start=base_batch,
                                              limit=32 * base_batch)
    note = None
    if limit is None:
        note = ("PJRT build exposes no memory_analysis; residual "
                "accounting only")
    elif reports["none"]["temp_bytes"] == reports["every_4"]["temp_bytes"]:
        note = ("this backend's memory_analysis does not model remat "
                "buffer liveness (XLA:CPU); policy-sensitive fields are "
                "activation_bytes here and temp/max_batch on TPU")
    return {
        "metric": "workspace_remat",
        "value": reduction,
        "unit": "pct_activation_bytes_reduction_every4_vs_none",
        "model": f"MLP {feat}-{hidden}x{depth}-16, fp32, Adam, AOT "
                 f"memory accounting at batch {base_batch}",
        "activation_bytes": act,
        "temp_bytes": {m: r["temp_bytes"] for m, r in reports.items()},
        "peak_bytes": {m: r["peak_bytes"] for m, r in reports.items()},
        "synthetic_bytes_limit": limit,
        "max_batch_none": max_none,
        "max_batch_remat": max_remat,
        "device_memory": reports["none"]["device"],
        "note": note,
    }


def bench_schedule_search():
    """Joint schedule tuner metric (ISSUE 14 tentpole): run
    ``runtime/schedule.py``'s search over the REAL train step of a
    ResNet-shaped and a BERT-shaped target — remat policy x accum_steps
    x batch (oracle-pruned, attribution-seeded, interleaved-timed) — and
    report the tuned-vs-default step-time ratio (<= 1.0 by construction:
    the incumbent config is always timed) plus the MFU delta from
    ``cost_analysis`` attribution at each config's measured time.

    Assertions carried in the artifact: ZERO OOM probes (every timed
    candidate passed the AOT byte oracle against the synthetic budget),
    ZERO post-warmup compile events after ``tune_schedule()`` applied the
    winner, and tuned-vs-default BIT-equality of params AND updater
    state (the applied knobs — remat — are value-identical program
    restructurings; batch/accum stay recommendations).

    On TPU the targets are ResNet-50 (batch 128 bf16) and a bert-base-ish
    self-attention encoder; on CPU, reduced-geometry twins exercise the
    identical machinery (``force=True`` opts the bench into CPU timing —
    tier-1's never-sweep guard covers the non-forced path) and the >=35%
    MFU claim is explicitly deferred to a TPU run."""
    import jax

    from deeplearning4j_tpu.nn import memory as _memory
    from deeplearning4j_tpu.runtime import attribution as _attr
    from deeplearning4j_tpu.runtime import schedule as _schedule
    from deeplearning4j_tpu.runtime import telemetry as _tel

    on_tpu = jax.default_backend() == "tpu"

    def resnet_factory():
        from deeplearning4j_tpu.models.resnet import resnet
        from deeplearning4j_tpu.nn.updaters import Sgd
        if on_tpu:
            return (lambda: resnet(50, updater=Sgd(learning_rate=0.1),
                                   dtype="BFLOAT16").init()), 128, dict(
                policies=("none", "dots_saveable", "every_2"),
                accum_candidates=(1,), batch_candidates=(128, 256),
                repeats=3), "ResNet-50 NHWC 224x224 bf16"
        return (lambda: resnet(18, num_classes=10,
                               input_shape=(32, 32, 3),
                               updater=Sgd(learning_rate=0.1)).init()), \
            8, dict(policies=("none", "dots_saveable"),
                    accum_candidates=(1,), batch_candidates=(8, 16),
                    repeats=2), "ResNet-18 NHWC 32x32 f32 (CPU scale)"

    def bert_factory():
        from deeplearning4j_tpu.nn.config import (InputType,
                                                  NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
        from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
        from deeplearning4j_tpu.nn.model import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updaters import Adam
        L, d, heads, T, batch = (4, 256, 4, 128, 32) if on_tpu \
            else (2, 64, 2, 32, 8)

        def build():
            conf = (NeuralNetConfiguration.builder().seed(0)
                    .data_type("BFLOAT16" if on_tpu else "FLOAT")
                    .updater(Adam(learning_rate=1e-4))
                    .input_type(InputType.recurrent(d, T))
                    .list(*[SelfAttentionLayer(n_out=d, n_heads=heads)
                            for _ in range(L)],
                          RnnOutputLayer(n_out=2))
                    .build())
            return MultiLayerNetwork(conf).init()
        return build, batch, dict(
            policies=("none", "dots_saveable", "every_2"),
            accum_candidates=(1, 2), batch_candidates=(batch, 2 * batch),
            repeats=3 if on_tpu else 2), \
            f"BERT-shaped encoder ({L}x SelfAttention d={d} T={T})"

    def config_mfu(net, cfg, us):
        """XLA-counted MFU of one candidate config at its measured time
        (a fresh AOT lower — nothing executes)."""
        if us is None:
            return None
        with _schedule._with_schedule(net, cfg):
            compiled = _memory._lower_train_step(
                net, cfg["batch_size"], cfg["accum_steps"])
        rep = _attr.attribute_compiled(compiled, us / 1e6)
        return round(rep["mfu"] * 100, 2) if rep.get("mfu") is not None \
            else None

    def bit_equal_check(factory, entry):
        """Params AND updater state bit-equal after one real step, tuned
        (applied remat knob) vs default schedule, identical inputs."""
        base_cfg = entry.get("default_config") or entry["config"]
        outs = []
        for tuned in (False, True):
            net = factory()
            if tuned:
                net.set_workspace_mode(entry["config"]["workspace_mode"])
            args = list(_attr._train_step_args(
                net, base_cfg["batch_size"], 1, None, 0))
            # same seeded REAL batch for both runs (zeros would still
            # exercise the step, but random data is the honest check)
            rs = np.random.default_rng(7)

            def rand(t):
                return jax.tree.map(
                    lambda a: rs.normal(size=np.shape(a)).astype(a.dtype)
                    if np.issubdtype(np.asarray(a).dtype, np.floating)
                    else a, t)
            args[5], args[6] = rand(args[5]), rand(args[6])
            step = net._build_train_step()
            outs.append(step(*args))
        for a, b in zip(jax.tree.leaves(outs[0][:2]),
                        jax.tree.leaves(outs[1][:2])):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                return False
        return True

    def run_target(name, factory, batch, kw):
        net = factory()
        # synthetic byte budget (1.5x the incumbent peak) so the oracle
        # genuinely prunes on every backend — the "never OOM-probe" half
        base_peak = net.memory_report(batch).get("peak_bytes")
        bytes_limit = int(base_peak * 1.5) if base_peak else None
        _schedule.reset()
        entry = net.tune_schedule(batch, force=not on_tpu,
                                  bytes_limit=bytes_limit, **kw)
        # every timed candidate passed the oracle: 0 OOM probes by
        # construction; report the count that WOULD have OOMed
        oom_probes = 0
        timed_tags = {json.dumps(t["config"], sort_keys=True)
                      for t in entry.get("candidates", ())}
        pruned_tags = {json.dumps(p["config"], sort_keys=True)
                      for p in entry.get("pruned", ())}
        assert not (timed_tags & pruned_tags), "pruned candidate was timed"
        # one attributed retrace, then zero steady-state compiles
        args = _attr._train_step_args(net, batch, 1, None, 0)
        net._train_step = net._build_train_step()
        net._record_build("train.step", cache_attr="_train_step")
        out = net._train_step(*args)
        jax.block_until_ready(out[-1])
        ev0 = int(_tel.registry.get("compile.events").total())
        for i in range(1, 4):
            out = net._train_step(*_attr._train_step_args(net, batch, 1,
                                                          None, i))
            jax.block_until_ready(out[-1])
        post_compiles = int(_tel.registry.get("compile.events").total()
                            - ev0)
        mfu_default = config_mfu(
            net, entry.get("default_config", entry["config"]),
            entry.get("default_us"))
        mfu_tuned = config_mfu(net, entry["config"], entry.get("us"))
        return {
            "model": name,
            "batch": batch,
            "tuned_config": entry["config"],
            "default_config": entry.get("default_config"),
            "ratio_tuned_vs_default": entry.get("ratio_vs_default"),
            "tuned_us": entry.get("us"),
            "default_us": entry.get("default_us"),
            "seed_order": entry.get("seed_order"),
            "candidates_timed": len(entry.get("candidates", ())),
            "candidates_pruned": len(entry.get("pruned", ())),
            "bytes_limit": bytes_limit,
            "oom_probes": oom_probes,
            "post_warmup_compile_events": post_compiles,
            "mfu_default_pct": mfu_default,
            "mfu_tuned_pct": mfu_tuned,
            "mfu_delta_pts": (round(mfu_tuned - mfu_default, 2)
                              if mfu_tuned is not None
                              and mfu_default is not None else None),
            "bit_equal_params_and_updater": bit_equal_check(factory,
                                                            entry),
        }

    results = {}
    for tag, fac in (("resnet", resnet_factory), ("bert", bert_factory)):
        factory, batch, kw, name = fac()
        results[tag] = run_target(name, factory, batch, kw)
    headline = results["resnet"]["ratio_tuned_vs_default"]
    return {
        "metric": "schedule_search",
        "value": headline,
        "unit": "x_tuned_vs_default_step_time_resnet",
        "targets": results,
        "schedule_counters": _schedule.counters(),
        "mfu_claim": ("measured on TPU — compare against the >=35% bar"
                      if on_tpu else
                      "CPU run: machinery + zero-OOM-probe + zero-post-"
                      "warmup-compile + bit-equality assertions only; "
                      "the >=35% MFU claim is deferred to a TPU run"),
    }


def bench_parallel_inference():
    """Serving metric (ISSUE 2): open-loop ragged-size synthetic load
    against (a) the naive per-request path — one jitted forward call +
    host readback per request, the pre-engine ``output()`` behavior,
    pre-warmed on every distinct size so it pays ZERO compiles in the
    measured window (charging the naive path compile time would flatter
    the engine dishonestly) — and (b) the batched serving stack:
    ``ParallelInference`` coalescing concurrent requests into bucketed,
    AOT-warmed ``InferenceEngine`` calls. Reports the throughput ratio
    (acceptance: >= 3x), per-request p50/p99 latency under the load, and
    the post-warmup compile count (acceptance: zero)."""
    import threading

    import jax

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serving import ParallelInference

    feat, n_requests, max_req = 64, 600, 16
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Adam(learning_rate=1e-3))
            .input_type(InputType.feed_forward(feat))
            .list(DenseLayer(n_out=256, activation="relu"),
                  DenseLayer(n_out=256, activation="relu"),
                  OutputLayer(n_out=10))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, max_req + 1, n_requests)
    reqs = [rng.normal(size=(int(s), feat)).astype(np.float32)
            for s in sizes]
    total_examples = int(sizes.sum())

    # ---- naive per-request path (the old output(): bare jit, readback
    # per call), pre-warmed per distinct exact size
    fwd = jax.jit(lambda p, s, x: net._forward(
        p, x, s, train=False, rng=None)[0])
    for s in sorted(set(int(v) for v in sizes)):
        np.asarray(fwd(net.params, net.state,
                       np.zeros((s, feat), np.float32)))
    t0 = time.perf_counter()
    for x in reqs:
        np.asarray(fwd(net.params, net.state, x))  # sync per request
    naive_wall = time.perf_counter() - t0

    # ---- batched engine path: AOT warmup, then the open-loop burst
    eng = net.inference_engine()
    eng.warmup([1, 2, 4, 8, 16, 32, 64, 128, 256])
    warm_compiles = eng.stats()["compiles"]
    pi = ParallelInference(net, max_batch_size=256, max_wait_ms=2,
                           queue_limit=1024)
    futs = [None] * n_requests
    n_feeders = 8

    def feeder(k):  # open loop: arrivals never wait on completions
        for i in range(k, n_requests, n_feeders):
            futs[i] = pi.submit(reqs[i])

    feeders = [threading.Thread(target=feeder, args=(k,), daemon=True)
               for k in range(n_feeders)]
    t0 = time.perf_counter()
    for th in feeders:
        th.start()
    for th in feeders:
        th.join(timeout=300)
    for f in futs:
        f.result(timeout=300)
    batched_wall = time.perf_counter() - t0
    st = pi.stats()
    pi.shutdown()
    post_warmup_compiles = st["engine"]["compiles"] - warm_compiles

    return {
        "metric": "parallel_inference_speedup",
        "value": round(naive_wall / batched_wall, 2),
        "unit": "x_throughput_vs_naive_per_request",
        "model": f"MLP {feat}-256-256-10, fp32, ragged request sizes "
                 f"1..{max_req}",
        "requests": n_requests,
        "examples": total_examples,
        "naive_requests_per_sec": round(n_requests / naive_wall, 1),
        "batched_requests_per_sec": round(n_requests / batched_wall, 1),
        "naive_examples_per_sec": round(total_examples / naive_wall, 1),
        "batched_examples_per_sec": round(total_examples / batched_wall, 1),
        # None under DL4J_TPU_TELEMETRY=off: latency reservoirs are
        # kill-switched timing instrumentation (documented to go quiet)
        "request_latency_p50_ms": None if st["latency_ms_p50"] is None
        else round(st["latency_ms_p50"], 2),
        "request_latency_p99_ms": None if st["latency_ms_p99"] is None
        else round(st["latency_ms_p99"], 2),
        "coalesced_rows_mean": None if st["batch_rows_mean"] is None
        else round(st["batch_rows_mean"], 1),
        "device_batches": st["batches"],
        "post_warmup_compiles": post_warmup_compiles,
        "warmup_compiles": warm_compiles,
    }


def bench_generative_serving():
    """Generative serving metric (ISSUE 8, CPU-capable): autoregressive
    generation throughput for (a) the NAIVE full-recompute loop — every
    token re-runs the whole prefix through one jitted forward (the only
    generation the pre-ISSUE-8 stack could express: O(T^2) attention work
    per sequence), batched in lockstep and pre-warmed per sequence bucket
    so the timed window pays zero compiles — versus (b) the KV-cache
    continuous-batching decode path: ``GenerativeEngine`` prefill once
    per request + one O(T) decode step per token through
    ``ContinuousBatcher``. Reports tokens/sec, per-output-token p50/p99,
    decode dispatch + autotune counters, and the post-warmup compile
    event count (acceptance: ZERO in the timed window, >= 5x tokens/sec
    at batch >= 4)."""
    import jax

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.ops import autotune as _autotune
    from deeplearning4j_tpu.ops import flash_attention as _fa
    from deeplearning4j_tpu.runtime import telemetry as _tel
    from deeplearning4j_tpu.serving import ContinuousBatcher

    V, B, gen_tokens, max_cache = 256, 8, 48, 128
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(V, 32))
            .list(SelfAttentionLayer(n_out=V, n_heads=4),
                  DenseLayer(n_out=512, activation="relu"),
                  DenseLayer(n_out=V, activation="identity"),
                  SelfAttentionLayer(n_out=V, n_heads=4),
                  DenseLayer(n_out=512, activation="relu"),
                  DenseLayer(n_out=V, activation="identity"),
                  SelfAttentionLayer(n_out=V, n_heads=4),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    plens = rng.integers(56, 65, B)
    prompts = [np.eye(V, dtype=np.float32)[rng.integers(0, V, int(p))]
               for p in plens]
    total_tokens = B * gen_tokens

    # ---- naive full-recompute generation, lockstep batch, bucketed T
    full = jax.jit(lambda p, s, x, pl, ln: net._full_context(
        p, x, s, pl, ln))
    max_total = int(plens.max()) + gen_tokens
    buckets = []
    b = 32
    while b < max_total * 2:
        if b >= int(plens.max()):
            buckets.append(b)
        if b >= max_total:
            break
        b <<= 1
    for tb in buckets:  # pre-warm every bucket outside the timed window
        np.asarray(full(net.params, net.state,
                        np.zeros((B, tb, V), np.float32),
                        plens, plens))
    def naive_run():
        seq = np.zeros((B, buckets[-1], V), np.float32)
        for i, p in enumerate(prompts):
            seq[i, :len(p)] = p
        lengths = plens.copy()
        step_times = []
        t0 = time.perf_counter()
        for _ in range(gen_tokens):
            tb = next(x for x in buckets if x >= int(lengths.max()))
            ts = time.perf_counter()
            y = np.asarray(full(net.params, net.state, seq[:, :tb],
                                plens, lengths))
            step_times.append(time.perf_counter() - ts)
            toks = np.argmax(y[np.arange(B), lengths - 1], axis=-1)
            seq[np.arange(B), lengths] = np.eye(V, dtype=np.float32)[toks]
            lengths = lengths + 1
        return time.perf_counter() - t0, step_times



    # ---- KV-cache continuous batching (dispatch decisions are counted
    # at TRACE time, so the counters reset BEFORE warmup compiles)
    _fa.reset_counters()
    ev0_probe = int(_tel.registry.get("compile.events").total())
    cb = ContinuousBatcher(net, slots=B, max_cache_len=max_cache,
                           min_cache_len=max_cache,
                           max_new_tokens=gen_tokens)
    warm_compiles = cb.engine.compiles
    ev0 = int(_tel.registry.get("compile.events").total())

    def cb_run():
        t0 = time.perf_counter()
        handles = [cb.submit(prompt=prompts[i]) for i in range(B)]
        for h in handles:
            h.result(timeout=600)
        return time.perf_counter() - t0

    # INTERLEAVED pairs, median-of-ratios headline: this container's CPU
    # throughput drifts ~1.5x across minutes (the telemetry bench
    # measured 0.94-1.07 NULL A/B inside one window), so timing the two
    # paths in separate windows would randomize the ratio — adjacent
    # naive/kv-cache runs see the same weather and their ratio is stable
    pairs = []
    for _ in range(3):
        nw, sts = naive_run()
        cw = cb_run()
        pairs.append((nw, cw, sts))
    ratios = sorted(nw / cw for nw, cw, _ in pairs)
    ratio = ratios[len(ratios) // 2]
    naive_wall, _, step_times = min(pairs, key=lambda p: p[0])
    cb_wall = min(cw for _, cw, _ in pairs)
    naive_p50, naive_p99 = _percentiles(step_times)
    ev1 = int(_tel.registry.get("compile.events").total())
    tpot = cb.engine._h_decode.values_list()  # per decode iteration ==
    #                                            per output token per slot
    tpot_p50, tpot_p99 = _percentiles(tpot)
    st = cb.stats()

    # ---- ISSUE 12: paged-pool + prefix-sharing A/B. Every stream
    # carries the SAME fleet-wide system prompt (90 tokens, deliberately
    # not page-aligned): the paged side prefills it ONCE, maps the
    # shared pages into all B streams, and copy-on-write forks only the
    # partial tail page on each stream's first generated token. Same
    # interleaved-pairs / median-of-ratios posture as above.
    P_page, sys_plen, sys_gen = 16, 90, 16
    sys_prompt = np.eye(V, dtype=np.float32)[rng.integers(0, V, sys_plen)]

    def run_front(front):
        t0 = time.perf_counter()
        handles = [front.submit(prompt=sys_prompt, max_new_tokens=sys_gen)
                   for _ in range(B)]
        for h in handles:
            h.result(timeout=600)
        return time.perf_counter() - t0

    cb_paged = ContinuousBatcher(net, slots=B, max_cache_len=max_cache,
                                 min_cache_len=max_cache,
                                 max_new_tokens=sys_gen,
                                 paged=True, page_size=P_page)
    ev_pg0 = int(_tel.registry.get("compile.events").total())
    paged_pairs = []
    for _ in range(3):
        cw = run_front(cb)
        pw = run_front(cb_paged)
        paged_pairs.append((cw, pw))
    pratios = sorted(cw / pw for cw, pw in paged_pairs)
    paged_ratio = pratios[len(pratios) // 2]
    ev_pg1 = int(_tel.registry.get("compile.events").total())
    pool_stats = cb_paged.stats()["page_pool"]
    # fixed-HBM-budget concurrency: KV bytes/token are identical on both
    # sides; the contiguous engine pins the full rounded bucket per
    # stream, the paged engine only its allocated pages — shared prefix
    # pages counted ONCE across the fleet (the measured pages_peak)
    tok_bytes = cb_paged.engine.bytes_per_token()
    contig_stream_bytes = max_cache * tok_bytes
    paged_stream_bytes = max(1, pool_stats["pages_peak"]) \
        * P_page * tok_bytes / B
    GB = float(1 << 30)
    streams_contig = GB / contig_stream_bytes
    streams_paged = GB / paged_stream_bytes
    prefix_total = pool_stats["prefix_hits"] + pool_stats["prefix_misses"]
    cb_paged.shutdown()

    # ---- speculative decoding: draft-propose / verify-k-in-one-step.
    # The draft here is the target itself (accept-rate ~1.0): CPU can
    # only show the MECHANISM + accounting — a deployment wires a small
    # distilled draft, and the accept-rate field is the signal to watch.
    cb_spec = ContinuousBatcher(net, slots=B, max_cache_len=max_cache,
                                min_cache_len=max_cache,
                                max_new_tokens=sys_gen,
                                paged=True, page_size=P_page,
                                draft_model=net, speculate_k=4)
    run_front(cb_spec)
    spec = cb_spec.stats()["speculative"]
    cb_spec.shutdown()
    # snapshot the whole bench's dispatch mix BEFORE the forced
    # multiquery probe resets the counter family
    dispatch_counters = {k: v for k, v in _fa.counters().items() if v}
    # the fused Tq=k verify path exists on this backend (dispatch
    # decision counted through the Pallas interpreter under force; the
    # timed runs above use whatever `auto` picks for this platform)
    _fa.reset_counters()
    _old_mode = _fa.set_mode("force")
    try:
        import jax.numpy as _jnp
        _q4 = _jnp.ones((1, 1, 4, 16), _jnp.float32)
        _k4 = _jnp.ones((1, 1, 32, 16), _jnp.float32)
        _fa.decode_multiquery_dispatch(_q4, _k4, _k4, _jnp.asarray([8]))
    finally:
        _fa.set_mode(_old_mode)
    mq_fused = _fa.counters()["decode_multiquery"]
    cb.shutdown()

    return {
        "metric": "generative_serving",
        "value": round(ratio, 2),
        "unit": "x_tokens_per_sec_kv_cache_vs_full_recompute",
        "pair_ratios": [round(r, 2) for r in ratios],
        "model": f"3x self-attention({V}, 4 heads) + MLP, vocab {V}, "
                 f"batch {B}, prompts {int(plens.min())}..{int(plens.max())}, "
                 f"{gen_tokens} tokens/request",
        "tokens": total_tokens,
        "naive_tokens_per_sec": round(total_tokens / naive_wall, 1),
        "kv_cache_tokens_per_sec": round(total_tokens / cb_wall, 1),
        "naive_step_p50_ms": None if naive_p50 is None
        else round(naive_p50 * 1e3, 2),
        "naive_step_p99_ms": None if naive_p99 is None
        else round(naive_p99 * 1e3, 2),
        # time-per-output-token: one decode iteration advances every
        # active slot by one token
        "tpot_p50_ms": None if tpot_p50 is None
        else round(tpot_p50 * 1e3, 2),
        "tpot_p99_ms": None if tpot_p99 is None
        else round(tpot_p99 * 1e3, 2),
        "slots": st["slots"],
        "tokens_generated": st["tokens_generated"],
        "warmup_compiles": warm_compiles,
        "warmup_compile_events": int(ev0 - ev0_probe),
        # acceptance: the timed window pays ZERO compiles
        "post_warmup_compile_events": int(ev1 - ev0),
        "decode_dispatch_counters": dispatch_counters,
        "autotune_counters": _autotune.counters(),
        # ---- ISSUE 12 artifact fields: paged pool / prefix / verify ----
        "paged": {
            "page_size": P_page,
            "kv_bytes_per_token": tok_bytes,
            "workload": f"{B} streams x identical {sys_plen}-token "
                        f"system prompt + {sys_gen} generated tokens "
                        f"(contiguous bucket {max_cache})",
            # interleaved paged-vs-contiguous pairs, median-of-ratios
            "tokens_per_sec_ratio_vs_contiguous": round(paged_ratio, 2),
            "pair_ratios": [round(r, 2) for r in pratios],
            # fixed-HBM-budget concurrency (the >=2x acceptance bar)
            "concurrent_streams_per_gb_contiguous":
                round(streams_contig, 1),
            "concurrent_streams_per_gb_paged": round(streams_paged, 1),
            "concurrent_streams_per_gb_ratio":
                round(streams_paged / streams_contig, 2),
            "pages_peak": pool_stats["pages_peak"],
            "prefix_hit_rate": round(
                pool_stats["prefix_hits"] / prefix_total, 3)
            if prefix_total else None,
            "prefix_hits": pool_stats["prefix_hits"],
            "cow_forks": pool_stats["forks"],
            # zero compiles across grow/fork/join/leave in the timed
            # paged window (acceptance)
            "post_warmup_compile_events": int(ev_pg1 - ev_pg0),
        },
        "speculative": {
            "k": spec["k"],
            "proposed": spec["proposed"],
            "accepted": spec["accepted"],
            "draft_accept_rate": None if spec["accept_rate"] is None
            else round(spec["accept_rate"], 3),
            "draft": "target-as-draft (mechanism check; wire a small "
                     "distilled draft in deployment)",
            "multiquery_fused_dispatch": int(mq_fused),
        },
    }


def bench_decode_loop(rounds=3):
    """ISSUE 19 metric (CPU-capable): the host-free decode runtime —
    adaptive multi-token horizons + double-buffering (``max_horizon=8``)
    vs the horizon-1 interleaved loop (one on-device k=1 dispatch and
    one host readback per token — the pre-ISSUE-19 steady state). Both
    arms sample greedily ON DEVICE; the A/B isolates exactly what the
    horizon runtime eliminates: per-token host dispatch/readback and the
    host<->device ping-pong between decode iterations.

    Hard-asserted in-bench: bit-identical greedy streams, adaptive
    tokens/sec ratio > 1.0 (interleaved pairs, median of ratios), and
    ZERO post-warmup compile events in both timed windows. The artifact
    embeds per-arm ``attribution_report``s (host fraction of a decode
    step, fed with the measured decode_host_s split) so the host share
    visibly shrinks, plus the horizon histogram and the
    dispatch-decision mix (every decision counted, nothing silent)."""
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.runtime import telemetry as _tel
    from deeplearning4j_tpu.serving import ContinuousBatcher

    V, B, gen_tokens, max_cache = 32, 4, 32, 64
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=V, n_heads=2),
                  DenseLayer(n_out=48, activation="relu"),
                  SelfAttentionLayer(n_out=48, n_heads=2),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, V, int(rng.integers(4, 9))))
               for _ in range(B)]
    tokens_per_run = B * gen_tokens

    def make(max_horizon):
        ev0 = int(_tel.registry.get("compile.events").total())
        cb = ContinuousBatcher(net, slots=B, max_cache_len=max_cache,
                               min_cache_len=max_cache,
                               max_new_tokens=gen_tokens,
                               max_horizon=max_horizon)
        warm_ev = int(_tel.registry.get("compile.events").total()) - ev0
        return cb, cb.engine.compiles, \
            int(_tel.registry.get("compile.events").total()), warm_ev

    def run(cb):
        t0 = time.perf_counter()
        handles = [cb.submit(tokens=p) for p in prompts]
        streams = [h.result(timeout=600)["tokens"] for h in handles]
        return time.perf_counter() - t0, streams

    cb1, warm1, ev1, warm_ev1 = make(1)
    cb8, warm8, ev8, warm_ev8 = make(8)
    pairs, streams1 = [], None
    for _ in range(rounds):
        w1, s1 = run(cb1)
        w8, s8 = run(cb8)
        # acceptance: the horizon loop + on-device EOS freeze is
        # bit-exact vs the per-token oracle, every round
        assert s8 == s1, "adaptive-horizon stream diverged from the " \
                         "horizon-1 oracle"
        streams1 = s1
        pairs.append((w1, w8))
    ratios = sorted(w1 / w8 for w1, w8 in pairs)
    ratio = ratios[len(ratios) // 2]
    assert ratio > 1.0, (
        f"adaptive horizons must beat the horizon-1 loop (got {ratio})")
    # acceptance: both timed windows paid ZERO compiles
    assert cb1.engine.compiles == warm1 and cb8.engine.compiles == warm8
    ev_now = int(_tel.registry.get("compile.events").total())
    assert ev_now == ev8, "post-warmup compile events in a timed window"

    def arm(cb):
        pi = dict(pi=cb._id, pool="default")
        dev = _tel.registry.get(
            "serving.phase.decode_device_s").values_list(**pi)
        host = _tel.registry.get(
            "serving.phase.decode_host_s").values_list(**pi)
        tpot = _tel.registry.get("serving.tpot_s").values_list(**pi)
        p50, p99 = _percentiles(tpot)
        dev_med = sorted(dev)[len(dev) // 2] if dev else None
        host_med = sorted(host)[len(host) // 2] if host else 0.0
        st = cb.stats()
        return {
            "tpot_p50_ms": None if p50 is None else round(p50 * 1e3, 3),
            "tpot_p99_ms": None if p99 is None else round(p99 * 1e3, 3),
            "dispatch_decisions": st["dispatch_decisions"],
            "tokens_per_s_window": round(st["tokens_per_s"], 1),
            "host_s_per_dispatch_p50": None if host_med is None
            else round(host_med, 6),
            "device_s_per_dispatch_p50": None if dev_med is None
            else round(dev_med, 6),
        }, dev_med, host_med

    a1, dev1, host1 = arm(cb1)
    a8, dev8, host8 = arm(cb8)
    hz = _tel.registry.get("serving.decode.horizon").hist_snapshot(
        pi=cb8._id, pool="default")
    w1_best = min(w for w, _ in pairs)
    w8_best = min(w for _, w in pairs)
    # MFU attribution of the actual programs each arm runs, fed with the
    # measured split — the headline "host fraction shrinks" evidence
    # lives in the artifact, not a narrative. Per-token accounting: the
    # device work per token is the same program either way (one decode
    # step, scanned or not), so the k=1 fetch wait — dispatch is
    # immediately followed by the blocking readback, no overlap to hide
    # it — measures device busy per token; EVERYTHING else in the wall
    # (python loop, dispatch prep, per-token readback sync, emission) is
    # the host share the horizon runtime amortizes over k tokens
    m1 = w1_best / tokens_per_run            # wall per token, horizon 1
    m8 = w8_best / tokens_per_run            # wall per token, adaptive
    d = dev1 or 0.0                          # device busy per token
    attr1 = cb1.engine.attribution_report(
        max_cache, measured_s=m1, horizon=1, host_s=max(0.0, m1 - d))
    # XLA's cost_analysis counts the compiled loop body ONCE, so the
    # horizon executable's roofline is already per-token — keep the
    # measured side per-token too
    attr8 = cb8.engine.attribution_report(
        max_cache, measured_s=m8, horizon=8, host_s=max(0.0, m8 - d))
    assert attr8["fractions"]["host"] < attr1["fractions"]["host"], (
        "horizon runtime must shrink the host fraction per token")
    cb1.shutdown()
    cb8.shutdown()
    return {
        "metric": "decode_loop",
        "value": round(ratio, 2),
        "unit": "x_tokens_per_sec_adaptive_horizon_vs_horizon1",
        "pair_ratios": [round(r, 2) for r in ratios],
        "model": f"2x self-attention({V}) + MLP, vocab {V}, "
                 f"slots {B}, {gen_tokens} tokens/request, "
                 f"cache bucket {max_cache}",
        "tokens_per_run": tokens_per_run,
        "horizon1_tokens_per_sec": round(tokens_per_run / w1_best, 1),
        "adaptive_tokens_per_sec": round(tokens_per_run / w8_best, 1),
        "greedy_bit_parity": True,
        "streams_sample": streams1[0][:8],
        "horizon_histogram": hz,
        "warmup_compile_events": {"horizon1": warm_ev1,
                                  "adaptive": warm_ev8},
        "post_warmup_compile_events": 0,
        "horizon1": a1,
        "adaptive": a8,
        # host fraction of one decode dispatch, measured split: the
        # horizon program amortizes ONE host readback over k tokens
        "attribution_horizon1": {
            k: attr1[k] for k in ("fractions", "host_s", "measured_s",
                                  "horizon") if k in attr1},
        "attribution_adaptive": {
            k: attr8[k] for k in ("fractions", "host_s", "measured_s",
                                  "horizon") if k in attr8},
    }


def bench_quantized_serving():
    """ISSUE 9 metric (CPU-capable): int8 post-training quantized serving
    vs the bf16 engine at MATCHED buckets. Three measured claims, none
    asserted blind:

    - throughput + p99: interleaved bf16/int8 request-loop pairs,
      median-of-ratios (same container-drift posture as the r13
      generative bench). On TPU the int8 MXU passes are the speed story;
      on CPU the honest win is capacity, reported next.
    - serveable-batch capacity: ``InferenceEngine.max_batch`` under one
      fixed ``bytes_limit`` for both engines — the r9 HBM accounting's
      "quantized weights ~double the batch" as a measured delta (int8
      weights halve the argument bytes the AOT ``memory_analysis``
      reports). Skip-guarded on PJRT builds without the API.
    - accuracy delta: the eval-stack gate (top-1 agreement vs the bf16
      engine — label-free serving parity), must pass the configured
      bound; plus ZERO compile events in the timed window.
    """
    from deeplearning4j_tpu.eval.quantization import accuracy_delta_gate
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.runtime import telemetry as _tel
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    feat, width, n_requests, req_b = 256, 1024, 120, 32
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Adam(learning_rate=1e-3))
            .data_type("BFLOAT16")
            .input_type(InputType.feed_forward(feat))
            .list(DenseLayer(n_out=width, activation="relu"),
                  DenseLayer(n_out=width, activation="relu"),
                  DenseLayer(n_out=width, activation="relu"),
                  OutputLayer(n_out=16))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    reqs = [rng.normal(size=(req_b, feat)).astype(np.float32)
            for _ in range(n_requests)]

    base = InferenceEngine(net).warmup([req_b])
    quant = InferenceEngine(net, quantize="int8").warmup([req_b])
    ev0 = int(_tel.registry.get("compile.events").total())

    def run(eng):
        lats = []
        t0 = time.perf_counter()
        for x in reqs:
            ts = time.perf_counter()
            np.asarray(eng.output(x))
            lats.append(time.perf_counter() - ts)
        return time.perf_counter() - t0, lats

    # interleaved pairs, median-of-ratios: adjacent runs see the same
    # container weather, so the ratio is stable where absolute walls
    # drift ~1.5x between windows
    pairs = []
    for _ in range(3):
        bw, bl = run(base)
        qw, ql = run(quant)
        pairs.append((bw, qw, bl, ql))
    ratios = sorted(bw / qw for bw, qw, _, _ in pairs)
    ratio = ratios[len(ratios) // 2]
    _, _, base_lats, quant_lats = min(pairs, key=lambda p: p[1])
    b_p50, b_p99 = _percentiles(base_lats)
    q_p50, q_p99 = _percentiles(quant_lats)
    post_warmup_events = int(
        _tel.registry.get("compile.events").total()) - ev0

    # capacity win under one fixed budget (probe compiles are cause=probe;
    # run AFTER the timed window so they cannot pollute the zero-compile
    # claim). The budget self-calibrates to the bf16 engine's own peak at
    # the request bucket (+5%): the bf16 ladder tops out near req_b and
    # the int8 delta under the SAME budget is the r9-accounting capacity
    # claim as a measured number.
    mem_base = base.memory_report(req_b)
    mem_quant = quant.memory_report(req_b)
    budget = None if mem_base["peak_bytes"] is None \
        else int(mem_base["peak_bytes"] * 1.05)
    mb_base = mb_quant = None
    if budget is not None:
        try:
            mb_base = base.max_batch(bytes_limit=budget, limit=1024)
            mb_quant = quant.max_batch(bytes_limit=budget, limit=1024)
        except ValueError:
            pass

    gate = accuracy_delta_gate(base.output, quant.output, reqs[:8],
                               max_delta=0.02, raise_on_fail=False)

    # headline: TPU = throughput (native int8 MXU passes); CPU = the
    # measured serveable-batch delta (the acceptance's "equivalent
    # measured HBM/batch-capacity win" — int8 matmul is not a CPU speed
    # path and pretending otherwise would be dishonest)
    import jax as _jax
    capacity_ratio = None if not (mb_base and mb_quant) \
        else round(mb_quant / mb_base, 2)
    if _jax.default_backend() == "tpu" or capacity_ratio is None:
        headline, unit = round(ratio, 3), "x_throughput_int8_vs_bf16_engine"
    else:
        headline = capacity_ratio
        unit = "x_max_batch_int8_vs_bf16_at_fixed_bytes_limit"

    return {
        "metric": "quantized_serving",
        "value": headline,
        "unit": unit,
        "throughput_ratio_int8_vs_bf16": round(ratio, 3),
        "pair_ratios": [round(r, 3) for r in ratios],
        "model": f"MLP {feat}-{width}x3-16 BFLOAT16, batch {req_b}, "
                 f"{n_requests} requests",
        "bf16_requests_per_sec": round(n_requests / min(
            bw for bw, _, _, _ in pairs), 1),
        "int8_requests_per_sec": round(n_requests / min(
            qw for _, qw, _, _ in pairs), 1),
        "bf16_latency_p50_ms": None if b_p50 is None
        else round(b_p50 * 1e3, 2),
        "bf16_latency_p99_ms": None if b_p99 is None
        else round(b_p99 * 1e3, 2),
        "int8_latency_p50_ms": None if q_p50 is None
        else round(q_p50 * 1e3, 2),
        "int8_latency_p99_ms": None if q_p99 is None
        else round(q_p99 * 1e3, 2),
        # accuracy is GATED, not asserted: delta = top-1 disagreement
        "accuracy_delta": round(gate.delta, 5),
        "accuracy_gate_max_delta": gate.max_delta,
        "accuracy_gate_passed": gate.passed,
        # acceptance: zero compiles in the timed window
        "post_warmup_compile_events": post_warmup_events,
        # the r9-accounting capacity claim, measured (None without
        # memory_analysis on this PJRT build)
        "max_batch_bf16": mb_base,
        "max_batch_int8": mb_quant,
        "max_batch_ratio": capacity_ratio,
        "max_batch_bytes_limit": budget,
        "params_bytes_f32_masters": mem_base["params_bytes"],
        "params_bytes_int8": mem_quant["params_bytes"],
        "argument_bytes_bf16": mem_base["argument_bytes"],
        "argument_bytes_int8": mem_quant["argument_bytes"],
        "quantized_sites": quant.stats().get("quantized_sites"),
        "quantize_dispatch_counters": {
            k: v for k, v in __import__(
                "deeplearning4j_tpu.ops.quantize",
                fromlist=["counters"]).counters().items() if v},
    }


def bench_pod_serving():
    """Tensor-parallel pod serving metric (ISSUE 17, CPU-capable): the
    same paged generative engine driven twice over identical greedy
    workloads — (a) single-device, (b) TP over a ``pod_mesh(model=2)``
    with params column/row-sharded, the KV page pool split over
    attention heads, and decode dispatched per-shard under ``shard_map``.
    CPU cannot show a TP speedup (virtual devices share the same cores
    and the shard_map orchestration is pure overhead), so the headline
    is honest mechanism accounting with three HARD assertions:

    - greedy tokens BIT-EQUAL between the TP and single-device engines
      on every interleaved pair (sharded-single-replica correctness);
    - per-device KV pool bytes == full pool bytes / k (the capacity
      story: a k-way pod serves a model k-x larger per device);
    - ZERO compile events in the timed window (multi-host AOT warmup
      covers every bucket the traffic touches).

    The dispatch counter mix is embedded so a TPU run can verify the
    head-sharded kernel path actually engaged (``decode_tp_shard_map``
    at trace time, never a silent fallback)."""
    import jax

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.ops import flash_attention as _fa
    from deeplearning4j_tpu.parallel import launcher
    from deeplearning4j_tpu.parallel import placement as _pl
    from deeplearning4j_tpu.runtime import telemetry as _tel
    from deeplearning4j_tpu.serving.engine import PagedGenerativeEngine

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "pod_serving needs >= 2 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=4 on CPU)")
    k = 2
    mesh = launcher.pod_mesh(model=k, devices=jax.devices()[:k])

    V, B, gen_tokens, PAGE, max_cache = 32, 4, 24, 8, 64
    conf = (NeuralNetConfiguration.builder().seed(5)
            .input_type(InputType.recurrent(V, 8))
            .list(SelfAttentionLayer(n_out=32, n_heads=4),
                  DenseLayer(n_out=32, activation="relu"),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(11)
    plens = rng.integers(6, 14, B)
    prompts = [rng.integers(0, V, int(p)) for p in plens]
    eye = np.eye(V, dtype=np.float32)

    # dispatch decisions are counted at TRACE time: reset BEFORE warmup
    _fa.reset_counters()
    ev_init = int(_tel.registry.get("compile.events").total())
    single = PagedGenerativeEngine(net, slots=B, pages=64, page_size=PAGE,
                                   max_cache_len=max_cache)
    tp_eng = PagedGenerativeEngine(net, slots=B, pages=64, page_size=PAGE,
                                   max_cache_len=max_cache, mesh=mesh)
    single.warmup([max_cache], [16])
    tp_eng.warmup([max_cache], [16])
    ev0 = int(_tel.registry.get("compile.events").total())

    def run(eng):
        state = eng.new_state(max_cache)
        toks = [[] for _ in range(B)]
        last = np.zeros(B, np.int64)
        t0 = time.perf_counter()
        for s, p in enumerate(prompts):
            pages = eng.pool.alloc(-(-len(p) // PAGE))
            eng.map_pages(state, s, pages)
            state, logits = eng.prefill(state, eye[p], len(p), s)
            last[s] = int(np.argmax(logits))
            toks[s].append(int(last[s]))
        active = np.ones(B, np.int32)
        for _ in range(gen_tokens - 1):
            snap = eng.pool.ref_snapshot()
            pairs = []
            for s in range(B):
                pairs += eng.prepare_write(state, s, 1, ref_snapshot=snap)
            state = eng.fork(state, pairs)
            state, y = eng.decode(state, eye[last][:, None, :], active)
            last = np.argmax(np.asarray(y), axis=-1)
            for s in range(B):
                toks[s].append(int(last[s]))
        wall = time.perf_counter() - t0
        # drain the pool so interleaved pairs never exhaust it (every
        # page is refcount-1 here: distinct prompts, forks release old)
        used = sorted({int(p) for p in state.page_table.ravel() if p > 0})
        eng.pool.release(used)
        return wall, toks

    # interleaved pairs, median-of-ratios (same container-drift posture
    # as the other serving benches)
    pairs, streams = [], None
    for _ in range(3):
        sw, s_toks = run(single)
        tw, t_toks = run(tp_eng)
        if s_toks != t_toks:
            raise AssertionError(
                f"TP greedy tokens diverged from single-device oracle: "
                f"{t_toks} != {s_toks}")
        streams = s_toks
        pairs.append((sw, tw))
    ratios = sorted(sw / tw for sw, tw in pairs)
    ratio = ratios[len(ratios) // 2]
    ev1 = int(_tel.registry.get("compile.events").total())
    if ev1 != ev0:
        raise AssertionError(
            f"{ev1 - ev0} compile events in the timed window (AOT "
            f"warmup must cover every bucket)")

    # per-device capacity: the head-sharded page pool splits its
    # payloads k ways (host int32 page tables are shard-agnostic)
    pool_full = tp_eng.pool_bytes()
    pool_dev = tp_eng.pool_bytes(per_device=True)
    if abs(pool_dev * k - pool_full) > pool_full * 0.02:
        raise AssertionError(
            f"per-device pool bytes {pool_dev} * {k} != {pool_full}")
    cache_full = tp_eng.cache_bytes(max_cache)
    cache_dev = tp_eng.cache_bytes(max_cache, per_device=True)

    dispatch = {kk: v for kk, v in _fa.counters().items() if v}
    if not any(kk.endswith(("tp_shard_map", "fallback_gspmd")) for kk in dispatch):
        raise AssertionError(
            f"no TP dispatch decision recorded: {dispatch}")
    total_tokens = B * gen_tokens

    return {
        "metric": "pod_serving",
        "value": round(ratio, 2),
        "unit": "x_tokens_per_sec_tp2_vs_single_device",
        "pair_ratios": [round(r, 2) for r in ratios],
        "mesh": _pl.mesh_key(mesh),
        "tp_shards": k,
        "model": f"self-attention({V}, 4 heads) + MLP, vocab {V}, "
                 f"{B} slots, page {PAGE}, {gen_tokens} tokens/stream",
        "tokens": total_tokens,
        "single_tokens_per_sec": round(
            total_tokens / min(sw for sw, _ in pairs), 1),
        "tp_tokens_per_sec": round(
            total_tokens / min(tw for _, tw in pairs), 1),
        # HARD-ASSERTED above: bit-equal greedy streams, every pair
        "greedy_parity": "bit_equal",
        "greedy_tail": [t[-4:] for t in (streams or [])],
        # the capacity claim: KV payload bytes per device = full / k
        "pool_bytes_full": pool_full,
        "pool_bytes_per_device": pool_dev,
        "cache_bytes_full": cache_full,
        "cache_bytes_per_device": cache_dev,
        "pool_stats": tp_eng.pool.stats(),
        "warmup_compile_events": int(ev0 - ev_init),
        # acceptance: the timed window pays ZERO compiles
        "post_warmup_compile_events": int(ev1 - ev0),
        "decode_dispatch_counters": dispatch,
    }


def bench_disaggregated_serving(rounds=3):
    """Disaggregated serving metric (ISSUE 18, CPU-capable): mixed-load
    TTFT tail for (a) a COLOCATED paged ``ContinuousBatcher`` — long
    prefills and steady decode share one worker loop, so every prefill
    admitted mid-stream stalls the decode iterations queued behind it —
    versus (b) the SPLIT topology: a ``PrefillReplica`` prefills long
    prompts off the decode worker's thread (standing in for the prefill
    pool's process; the two-process version is the ``multihost_sim
    --disagg`` tier-1 gate) and ships pages via ``submit_prefilled``,
    so the decode pool only ever pays a bucketed page adoption.

    Each round runs, interleaved colocated/split so both sides see the
    same CPU weather: a LOW window (steady short-prompt decode only —
    the per-side TPOT baseline) and a HIGH window (the same steady
    decode + a burst of long-prefill requests, arrivals interleaved).
    Headline = median over rounds of colocated/split INTERACTIVE-stream
    TTFT p99 under the mixed load (> 1.0 = split wins): a long request
    pays its own prefill on either topology, so the tail disaggregation
    removes is the one it put in front of everyone ELSE's first token.
    The flatness acceptance rides the TPOT ramp ratios: ramping prefill
    must inflate the split decode MEDIAN strictly less than the
    colocated one — enforced only on hosts with enough cores to seat
    the pools separately (a 1-2 core box time-slices both pools, so the
    ramps there are scheduler noise, reported but not gated).
    A pre-window probe migration checks the stitched-timeline contract
    (phases sum to the measured origin->resolution latency within 10%);
    the timed windows pay ZERO compiles (hard field)."""
    import os
    import tempfile
    import threading

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.runtime import telemetry as _tel
    from deeplearning4j_tpu.serving import ContinuousBatcher, PrefillReplica

    # prefill must DOMINATE the migration overhead for the split to pay
    # off (on TPUs the page export/import is DMA-cheap next to a long
    # prefill's compute; a toy prompt would invert that): 112-token
    # prompts on a 2-attention-layer net put ~T^2 attention work behind
    # every colocated admission, while the decode pool's adoption stays
    # one bucketed 14-page scatter
    V, PAGE, CACHE = 64, 8, 128
    N_SHORT, N_LONG = 6, 6
    PLEN_LONG, GEN_SHORT, GEN_LONG = 112, 16, 2
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(V, 16))
            .list(SelfAttentionLayer(n_out=V, n_heads=4),
                  DenseLayer(n_out=96, activation="relu"),
                  SelfAttentionLayer(n_out=V, n_heads=4),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(7)
    eye = np.eye(V, dtype=np.float32)

    def fresh_prompt(plen):
        # unique per request: a prefix-registry hit would turn the
        # prefill under test into a free lookup on EITHER side
        return eye[rng.integers(0, V, int(plen))]

    # slots cover the full mixed burst: TTFT then measures admission
    # interference (the thing disaggregation removes), not slot wait
    colo = ContinuousBatcher(net, slots=N_SHORT + N_LONG,
                             max_cache_len=CACHE, paged=True,
                             page_size=PAGE, max_new_tokens=GEN_SHORT,
                             pool_label="colocated")
    pre = PrefillReplica(net, pages=257, page_size=PAGE,
                         max_cache_len=CACHE, prompt_buckets=[16, CACHE])
    dec = ContinuousBatcher(net, slots=N_SHORT + N_LONG,
                            max_cache_len=CACHE, paged=True,
                            page_size=PAGE, max_new_tokens=GEN_SHORT,
                            pool_label="decode",
                            migrate_buckets=[-(-PLEN_LONG // PAGE)])

    def colo_short(i):
        return colo.submit(prompt=fresh_prompt(8))

    def colo_long(i):
        return colo.submit(prompt=fresh_prompt(PLEN_LONG),
                           max_new_tokens=GEN_LONG)

    def split_short(i):
        # steady decode residency lives on the HBM-rich pool directly
        return dec.submit(prompt=fresh_prompt(8))

    def split_long(i):
        ship = pre.prefill(fresh_prompt(PLEN_LONG))
        return dec.submit_prefilled(ship, max_new_tokens=GEN_LONG)

    def drive(submit_short, submit_long, with_longs):
        """One window: N_SHORT steady interactive streams (+ N_LONG
        long-prefill bursts when ramping), arrivals interleaved;
        per-request TTFT measured at the driver (submit -> first
        streamed token), collected separately per class — the split's
        claim is about the INTERACTIVE tail (a long request pays its
        own prefill on either topology; what disaggregation removes is
        that prefill landing in front of everyone else's first token)."""
        shorts, longs = [], []
        lock = threading.Lock()

        def one(submit, i, sink):
            t0 = time.perf_counter()
            h = submit(i)
            next(h.tokens(timeout=600))
            dt = time.perf_counter() - t0
            h.result(timeout=600)
            with lock:
                sink.append(dt)

        threads = []
        for i in range(max(N_SHORT, N_LONG)):
            if i < N_LONG and with_longs:
                threads.append(threading.Thread(
                    target=one, args=(submit_long, i, longs)))
            if i < N_SHORT:
                threads.append(threading.Thread(
                    target=one, args=(submit_short, i, shorts)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return shorts, longs

    def tpot_window(cb, fn):
        """Run ``fn`` and return the decode pool's per-token TPOT
        samples observed DURING it (values-list delta on the bound
        serving.tpot_s cell)."""
        n0 = len(cb._h_tpot.values_list())
        out = fn()
        return out, cb._h_tpot.values_list()[n0:]

    # ---- stitched-timeline probe (the cross-pool trace contract) ----
    with tempfile.TemporaryDirectory() as td:
        log = os.path.join(td, "events.jsonl")
        _tel.event_log(log)
        try:
            t_origin = time.perf_counter()
            ship = pre.prefill(fresh_prompt(PLEN_LONG), t_origin=t_origin)
            t_sub = time.perf_counter()
            h = dec.submit_prefilled(ship, max_new_tokens=GEN_LONG)
            h.result(timeout=600)
            latency = ship.elapsed_s + (time.perf_counter() - t_sub)
        finally:
            _tel.close_event_log()
        stitched = _tel.stitch_event_logs([log])
        recs = [r for r in stitched["traces"].get(ship.trace_id, [])
                if r.get("type") == "trace"]
        merged = _tel.merge_trace_records(recs)
        phase_sum = sum(p.get("duration_s", 0.0)
                        for p in merged.get("phases", []))
        stitch_ok = abs(phase_sum - latency) <= 0.10 * latency

    ev0 = int(_tel.registry.get("compile.events").total())

    # ---- interleaved rounds: LOW (baseline TPOT) then HIGH (ramp) ----
    ttft_ratios = []
    colo_low_tpot, colo_high_tpot = [], []
    split_low_tpot, split_high_tpot = [], []
    colo_high_ttft, split_high_ttft = [], []
    colo_long_ttft, split_long_ttft = [], []
    for _ in range(rounds):
        _, tp = tpot_window(colo, lambda: drive(colo_short, colo_long,
                                                False))
        colo_low_tpot += tp
        _, tp = tpot_window(dec, lambda: drive(split_short, split_long,
                                               False))
        split_low_tpot += tp
        (tt_c, tl_c), tp = tpot_window(
            colo, lambda: drive(colo_short, colo_long, True))
        colo_high_tpot += tp
        colo_high_ttft += tt_c
        colo_long_ttft += tl_c
        (tt_s, tl_s), tp = tpot_window(
            dec, lambda: drive(split_short, split_long, True))
        split_high_tpot += tp
        split_high_ttft += tt_s
        split_long_ttft += tl_s
        _, c99 = _percentiles(tt_c)
        _, s99 = _percentiles(tt_s)
        ttft_ratios.append(c99 / s99)
    ev1 = int(_tel.registry.get("compile.events").total())

    ttft_ratios.sort()
    ratio = ttft_ratios[len(ttft_ratios) // 2]
    c_lo50, c_lo99 = _percentiles(colo_low_tpot)
    c_hi50, c_hi99 = _percentiles(colo_high_tpot)
    s_lo50, s_lo99 = _percentiles(split_low_tpot)
    s_hi50, s_hi99 = _percentiles(split_high_tpot)
    _, c_tt99 = _percentiles(colo_high_ttft)
    _, s_tt99 = _percentiles(split_high_ttft)
    _, c_lg99 = _percentiles(colo_long_ttft)
    _, s_lg99 = _percentiles(split_long_ttft)
    split_flat = s_hi99 / s_lo99
    colo_flat = c_hi99 / c_lo99
    split_flat50 = s_hi50 / s_lo50
    colo_flat50 = c_hi50 / c_lo50
    # flatness is only falsifiable when the host can actually give the
    # pools separate cores: on a 1-2 core box every concurrent prefill
    # steals decode cycles by time-slicing REGARDLESS of topology, so
    # the ramp ratios are pure scheduler noise — report them, gate on
    # them only with >= 4 cores (the TTFT ratio gates everywhere: it
    # measures admission ORDERING, which survives time-slicing)
    cores = os.cpu_count() or 1
    flat_ok = (split_flat50 < colo_flat50) if cores >= 4 else True
    dec_stats = dec.stats()
    pre_stats = pre.stats()
    colo.shutdown()
    dec.shutdown()

    return {
        "metric": "disaggregated_serving",
        "value": round(ratio, 2),
        "unit": "x_mixed_load_interactive_ttft_p99_colocated_vs_split",
        "pair_ratios": [round(r, 2) for r in ttft_ratios],
        "workload": f"{N_SHORT} steady 8-token-prompt/{GEN_SHORT}-token "
                    f"interactive streams + {N_LONG} interleaved "
                    f"{PLEN_LONG}-token prefill bursts, {rounds} "
                    f"interleaved rounds",
        # the headline class: interactive streams' first token under the
        # prefill ramp (the long bursts pay their own prefill on either
        # topology and are reported below for context)
        "ttft_p99_ms_colocated": round(c_tt99 * 1e3, 2),
        "ttft_p99_ms_split": round(s_tt99 * 1e3, 2),
        "ttft_p99_ms_colocated_long": round(c_lg99 * 1e3, 2),
        "ttft_p99_ms_split_long": round(s_lg99 * 1e3, 2),
        # decode TPOT p99, LOW -> HIGH prefill load, per side: the
        # flatness acceptance (split stays put; colocated inflates
        # because prefills share its decode worker loop)
        "tpot_p99_ms_colocated_low": round(c_lo99 * 1e3, 2),
        "tpot_p99_ms_colocated_high": round(c_hi99 * 1e3, 2),
        "tpot_p99_ms_split_low": round(s_lo99 * 1e3, 2),
        "tpot_p99_ms_split_high": round(s_hi99 * 1e3, 2),
        # the relative-flatness acceptance — ramping prefill must
        # inflate the split decode median strictly less than the
        # colocated one — enforced only where the host can seat the
        # pools on separate cores (see tpot_ramp_gate)
        "tpot_p50_ramp_ratio_colocated": round(colo_flat50, 2),
        "tpot_p50_ramp_ratio_split": round(split_flat50, 2),
        "tpot_p99_ramp_ratio_colocated": round(colo_flat, 2),
        "tpot_p99_ramp_ratio_split": round(split_flat, 2),
        "tpot_ramp_gate": ("enforced" if cores >= 4 else
                           f"reported-only ({cores}-core host time-"
                           "slices both pools)"),
        # the cross-pool trace contract, measured on a live migration
        "stitched_phase_sum_within_10pct": bool(stitch_ok),
        "migrations": dec_stats["engine"]["paged"]["adoptions"],
        "prefill_pool": {"prefix_entries":
                         pre_stats["engine"]["paged"]["prefix_entries"],
                         "health": pre_stats["health"]},
        # acceptance: the timed windows pay ZERO compiles
        "post_warmup_compile_events": int(ev1 - ev0),
        "pass": bool(ratio > 1.0 and flat_ok and stitch_ok
                     and (ev1 - ev0) == 0),
    }


def bench_fleet_swap(pairs=3, steady_s=1.2):
    """Model-fleet hot-swap metric (ISSUE 20, CPU-capable): open-loop
    load threads drive one fleet model through ``pairs`` interleaved
    (steady-window, swap-window) rounds — each swap window background-
    builds + warms the next version and atomically flips to it mid-load.
    Each pair has three phases, all under load: a measured steady
    window; an UNMEASURED (but still drop-checked) build phase in which
    the candidate version builds + warms off the serving path — on a
    multi-core host this costs the serving path nothing (the incumbent's
    zero post-warmup compiles prove it never re-entered XLA), while on a
    1-core CI box the build's CPU time would otherwise masquerade as
    serving-tail inflation; and a measured during-swap window bracketing
    the atomic flip + drain + old-executable retirement — the phase a
    naive stop-the-world reload stalls. Headline: median of per-pair
    p99(during-swap)/p99(steady) ratios. Hard-asserted in-bench: the
    ratio <= 1.1 (the flip is invisible at the tail), requests_dropped
    == 0 across ALL phases (no typed shed, no untyped drop, ever), and
    zero post-warmup compiles on every incumbent across every background
    load/warm/flip. A forced canary rollback drill runs last so the
    artifact's swap/rollback counters carry both lifecycle directions."""
    import threading

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.runtime import faults
    from deeplearning4j_tpu.runtime import telemetry as tel
    from deeplearning4j_tpu.runtime.faults import (DeadlineExceeded,
                                                   QueueFull,
                                                   ShutdownError)
    from deeplearning4j_tpu.serving import (CanaryGate, FleetError,
                                            ModelRegistry)

    feat = 32

    def mk(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .input_type(InputType.feed_forward(feat))
                .list(DenseLayer(n_out=64, activation="relu"),
                      OutputLayer(n_out=10))
                .build())
        return MultiLayerNetwork(conf).init()

    fk = {"max_batch_size": 16, "max_wait_ms": 1.0}
    reg = ModelRegistry()
    reg.add_version("m", 1, mk(1), front_kwargs=dict(fk))
    reg.set_live("m", 1)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(4, feat)).astype(np.float32)
          for _ in range(4)]
    typed_shed, untyped = [], []

    def window(during=None, duration_s=steady_s):
        """Open-loop load window; returns per-request latencies (s).
        ``during`` (the swap) runs on THIS thread mid-window."""
        lats, stop = [], threading.Event()

        def worker(k):
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    reg.output("m", xs[k])
                    lats.append(time.perf_counter() - t0)
                except (QueueFull, DeadlineExceeded, ShutdownError,
                        FleetError) as e:
                    typed_shed.append(e)
                except Exception as e:  # noqa: BLE001 - the invariant
                    untyped.append(e)
                time.sleep(0.001)

        threads = [threading.Thread(target=worker, args=(k,),
                                    daemon=True) for k in range(4)]
        for t in threads:
            t.start()
        if during is not None:
            time.sleep(duration_s / 3)
            during()
            time.sleep(duration_s / 3)
        else:
            time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        return lats

    ratios, pwc_checks, n_requests = [], [], 0
    for i in range(pairs):
        steady = window()
        old_v, new_v = i + 1, i + 2
        incumbent = reg.version("m", old_v)
        # build phase: candidate builds + warms under load (drop-checked
        # via the shared typed/untyped lists, not latency-measured)
        window(during=lambda: reg.add_version(
            "m", new_v, mk(new_v), front_kwargs=dict(fk)))
        # invariant half 1: the background build/warm of new_v never
        # compiled anything on the incumbent's serving path
        pwc_checks.append(incumbent.post_warmup_compiles)
        during = window(during=lambda: reg.set_live("m", new_v))
        pwc_checks.append(reg.version("m", new_v).post_warmup_compiles)
        n_requests += len(steady) + len(during)
        ratios.append(float(np.percentile(during, 99)
                            / np.percentile(steady, 99)))
    ratio = float(np.median(ratios))

    # forced rollback drill: the counters must carry both directions
    last = pairs + 1
    reg.add_version("m", last + 1, mk(99), front_kwargs=dict(fk))
    reg.start_canary("m", last + 1,
                     CanaryGate(fraction=0.3, min_samples=2))
    faults.reset()
    faults.inject("fleet.canary", times=1)
    rb = reg.evaluate_canary("m")
    faults.reset()
    dump = tel.flight.last_dump
    st = reg.stats()
    reg.shutdown()

    assert ratio <= 1.1, (
        f"hot-swap visible at the tail: during/steady p99 ratio "
        f"{ratio:.3f} > 1.1 (per-pair {ratios})")
    assert not typed_shed and not untyped, (
        f"requests dropped during hot-swap: {len(typed_shed)} typed, "
        f"{len(untyped)} untyped ({(typed_shed + untyped)[:3]!r})")
    assert all(c == 0 for c in pwc_checks), (
        f"post-warmup compiles on a serving path: {pwc_checks}")
    assert rb["decision"] == "rolled_back" and st["rollbacks"] == 1
    assert dump and dump["reason"] == f"fleet.canary:m@v{last + 1}"

    return {
        "metric": "fleet_swap_p99_ratio",
        "value": round(ratio, 3),
        "unit": "x_p99_during_swap_vs_steady",
        "model": f"MLP {feat}-64-10 fp32, {pairs} hot-swap pairs under "
                 "4-thread open-loop load",
        "per_pair_ratios": [round(r, 3) for r in ratios],
        "requests": n_requests,
        "requests_dropped": len(typed_shed) + len(untyped),
        "post_warmup_compiles": max(pwc_checks),
        "swaps": st["swaps"],
        "rollbacks": st["rollbacks"],
        "rollback_dump_reason": dump["reason"],
        "pass": True,  # unreachable if any hard assert above fired
    }


def bench_multihost_scaling():
    """Pod-scale multi-host training (ISSUE 10): the 2-process CPU pod
    simulation — real subprocesses joined by ``jax.distributed`` (gloo
    over loopback standing in for DCN), each with virtual CPU devices —
    measuring ZeRO-1 + hierarchical-overlap training on the 2-D pod mesh:
    per-step time at 1 vs 2 hosts (weak scaling), zero post-warmup
    compile events, whole-host-loss resume bit-equality, and the 2->1
    changed-topology checkpoint restore through the verified-manifest
    path. Runs on CPU subprocesses regardless of the bench host's chip
    (the workers pin JAX_PLATFORMS=cpu), so the TPU driver run carries
    the same harness proof; step times are CPU-relative and labeled so.
    The artifact doubles as MULTICHIP_LOCAL_r07.json."""
    import tempfile

    from deeplearning4j_tpu.parallel.multihost_sim import run_simulation

    with tempfile.TemporaryDirectory() as td:
        return run_simulation(td, artifact_path="MULTICHIP_LOCAL_r07.json")


def bench_resilience():
    """ISSUE 5 metric (CPU-capable): (1) steady-state step-time overhead
    of the divergence sentinel — the guarded step (finite-check +
    lax.cond + on-device counters) vs the ``sentinel_guard=False``
    baseline program, interleaved A/B, must report ≈1.00x — and (2)
    recovery time after an injected mid-epoch kill: the wall-clock cost
    of the auto-resume restore (model + updater + iterator from the
    crash-safe checkpoint), plus a bit-equivalence check of the resumed
    run against an uninterrupted one."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import NumpyDataSetIterator
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.resilience import ResiliencePolicy
    from deeplearning4j_tpu.runtime import faults, sentinel

    def conf():
        return (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(learning_rate=1e-3))
                .input_type(InputType.feed_forward(256))
                .list(DenseLayer(n_out=512, activation="relu"),
                      DenseLayer(n_out=512, activation="relu"),
                      OutputLayer(n_out=10, activation="softmax",
                                  loss="mcxent"))
                .build())

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, 256)])

    # -- (1) sentinel steady-state overhead, interleaved A/B ----------------
    guarded = MultiLayerNetwork(conf()).init()
    base = MultiLayerNetwork(conf()).init()
    g_step = guarded._build_train_step()
    b_step = base._build_train_step(sentinel_guard=False)
    g_args = [guarded.params, guarded.updater_state, guarded.state]
    b_args = [base.params, base.updater_state, base.state]
    g_sent = sentinel.init_counters()
    key = jax.random.PRNGKey(0)

    def g_one(i):
        nonlocal g_sent
        out = g_step(*g_args, jnp.int32(i), key, x, y, None, None, g_sent)
        g_args[:] = out[:3]
        g_sent = out[3]
        return out[4]

    def b_one(i):
        out = b_step(*b_args, jnp.int32(i), key, x, y, None, None)
        b_args[:] = out[:3]
        return out[3]

    for i in range(3):  # warmup (compile both)
        g_one(i).block_until_ready()
        b_one(i).block_until_ready()
    gt, bt = [], []
    for i in range(30):  # interleaved: share thermal/noise conditions
        t0 = time.perf_counter()
        g_one(i + 3).block_until_ready()
        gt.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        b_one(i + 3).block_until_ready()
        bt.append(time.perf_counter() - t0)
    g_p50, g_p99 = _percentiles(gt)
    b_p50, b_p99 = _percentiles(bt)
    overhead = g_p50 / b_p50 if b_p50 else None

    # -- (2) recovery time after an injected mid-epoch kill -----------------
    faults.reset()
    faults.telemetry_reset()
    xs = np.asarray(x)
    ys = np.asarray(y)
    ref = MultiLayerNetwork(conf()).init()
    ref.fit(NumpyDataSetIterator(xs, ys, batch_size=32, shuffle=True,
                                 seed=3), epochs=2)
    net = MultiLayerNetwork(conf()).init()
    it = NumpyDataSetIterator(xs, ys, batch_size=32, shuffle=True, seed=3)
    restore_s = {}
    orig_restore = None
    try:  # the armed crash must NEVER leak into later benches
        with tempfile.TemporaryDirectory() as d:
            pol = ResiliencePolicy(checkpointer=d,
                                   checkpoint_every_iterations=2,
                                   max_restarts=2)
            ck = pol.resolve_checkpointer()
            orig_restore = ck.restore

            def timed_restore(*a, **kw):
                t0 = time.perf_counter()
                out = orig_restore(*a, **kw)
                restore_s["s"] = time.perf_counter() - t0
                return out

            ck.restore = timed_restore
            faults.inject("train.step", error="crash", after=11, times=1)
            t0 = time.perf_counter()
            net.fit(it, epochs=2, resilience=pol)
            total_s = time.perf_counter() - t0
    finally:
        faults.clear("train.step")
    bit_equal = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(net.params)))
    tel = faults.telemetry_snapshot()
    fault_counters = faults.counters()
    faults.reset()
    return {
        "metric": "resilience",
        "value": round(overhead, 4) if overhead else None,
        "unit": "x_sentinel_step_time_vs_unguarded",
        "sentinel_step_ms_p50": round(g_p50 * 1e3, 3),
        "sentinel_step_ms_p99": round(g_p99 * 1e3, 3),
        "baseline_step_ms_p50": round(b_p50 * 1e3, 3),
        "baseline_step_ms_p99": round(b_p99 * 1e3, 3),
        "recovery_restore_s": round(restore_s.get("s", float("nan")), 4),
        "recovery_total_fit_s": round(total_s, 3),
        "resumed_bit_equal_to_uninterrupted": bit_equal,
        "telemetry": {k: v for k, v in tel.items()
                      if isinstance(v, (int, float)) or v is None},
        "fault_counters": fault_counters,
    }


def bench_telemetry_overhead():
    """ISSUE 6 metric (CPU-capable): steady-state fit-loop step time with
    the MetricsRegistry recording (phase histograms, StepTraceAnnotation,
    counters) vs ``DL4J_TPU_TELEMETRY=off`` — the same interleaved-A/B
    pattern as the r10 ``resilience`` sentinel overhead. Acceptance:
    <=1.02x. Both arms run the SAME compiled step (telemetry is entirely
    host-side), so the ratio isolates the instrumentation cost."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import NumpyDataSetIterator
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.runtime import telemetry

    def conf():
        return (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(learning_rate=1e-3))
                .input_type(InputType.feed_forward(256))
                .list(DenseLayer(n_out=512, activation="relu"),
                      DenseLayer(n_out=512, activation="relu"),
                      OutputLayer(n_out=10, activation="softmax",
                                  loss="mcxent"))
                .build())

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(512, 256)).astype(np.float32)
    ys = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 512)]
    net = MultiLayerNetwork(conf()).init()

    def chain():
        """One epoch over 16 batches of 32 through the REAL fit loop (the
        instrumented path); returns seconds per step with the loss synced
        so async dispatch cannot flatter either arm."""
        it = NumpyDataSetIterator(xs, ys, batch_size=32)
        t0 = time.perf_counter()
        net.fit(it, epochs=1)
        float(jnp.asarray(net._score))  # force the chain
        return (time.perf_counter() - t0) / 16

    for _ in range(3):  # warmup: compile + settle caches/allocator
        chain()
    prev = telemetry.set_enabled(True)
    on_s, off_s, ratios = [], [], []
    try:
        # FENCED estimator: off on off on ... off — every ON chain is
        # ratioed against the MEAN of its two neighboring OFF chains,
        # which cancels linear throughput drift exactly (the plain
        # alternating-pairs estimator read 0.94–1.07 on the NULL A/B in
        # a shared CPU container; the fence reads 0.98–1.01 null
        # where the real instrumentation cost is ~13us on a ~5ms step).
        # Three fences pool 48 drift-cancelled ratios so the median's
        # standard error (~1.25*sigma/sqrt(n), sigma≈2.5% per ratio)
        # lands near 0.45% — the 1.02 bar is then >3 SE away from the
        # measured ~1.00, instead of one unlucky 16-ratio fence breaching
        # it on pure container noise. Headline = pooled median.
        for _ in range(3):
            seq = []
            for i in range(33):
                telemetry.set_enabled(bool(i % 2))
                seq.append(chain())
            on_s += seq[1::2]
            off_s += seq[0::2]
            ratios += [seq[i] / ((seq[i - 1] + seq[i + 1]) / 2)
                       for i in range(1, len(seq) - 1, 2)]
    finally:
        telemetry.set_enabled(prev)
    on_p50, on_p99 = _percentiles(on_s)
    off_p50, off_p99 = _percentiles(off_s)
    ratios.sort()
    ratio = ratios[len(ratios) // 2] if ratios else None
    return {
        "metric": "telemetry_overhead",
        "value": round(ratio, 4) if ratio else None,
        "unit": "x_step_time_telemetry_on_vs_off",
        "ratio_min_over_min": round(min(on_s) / min(off_s), 4),
        "on_step_ms_min": round(min(on_s) * 1e3, 3),
        "on_step_ms_p50": round(on_p50 * 1e3, 3),
        "on_step_ms_p99": round(on_p99 * 1e3, 3),
        "off_step_ms_min": round(min(off_s) * 1e3, 3),
        "off_step_ms_p50": round(off_p50 * 1e3, 3),
        "off_step_ms_p99": round(off_p99 * 1e3, 3),
        "registered_metrics": len(telemetry.registry.names()),
    }


if __name__ == "__main__":
    lines = [bench_resnet()]  # headline first: must not be blocked by BERT
    # emit the headline IMMEDIATELY: if bench_bert dies process-fatally
    # (libtpu abort, OOM kill — not catchable below) the headline is
    # already on stdout and in the artifact; on success it is re-emitted
    # so it is also the LAST line (the driver parses the last JSON line)
    _emit(lines)
    try:
        lines.append(bench_parallel_inference())
    except Exception as e:
        lines.append({
            "metric": "parallel_inference_speedup", "value": None,
            "unit": "x_throughput_vs_naive_per_request",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_sharded_update())
    except Exception as e:
        lines.append({
            "metric": "sharded_update", "value": None,
            "unit": "x_per_device_updater_bytes_reduction",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_flash_attention())
    except Exception as e:
        lines.append({
            "metric": "flash_attention", "value": None,
            "unit": "x_fused_vs_einsum_step_time_at_seq1024",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_fused_epilogues())
    except Exception as e:
        lines.append({
            "metric": "fused_epilogues", "value": None,
            "unit": "x_fused_vs_unfused_master_cast_updater_step_time",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_workspace_remat())
    except Exception as e:
        lines.append({
            "metric": "workspace_remat", "value": None,
            "unit": "pct_activation_bytes_reduction_every4_vs_none",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_schedule_search())
    except Exception as e:
        lines.append({
            "metric": "schedule_search", "value": None,
            "unit": "x_tuned_vs_default_step_time_resnet",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_generative_serving())
    except Exception as e:
        lines.append({
            "metric": "generative_serving", "value": None,
            "unit": "x_tokens_per_sec_kv_cache_vs_full_recompute",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_decode_loop())
    except Exception as e:
        lines.append({
            "metric": "decode_loop", "value": None,
            "unit": "x_tokens_per_sec_adaptive_horizon_vs_horizon1",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_quantized_serving())
    except Exception as e:
        lines.append({
            "metric": "quantized_serving", "value": None,
            "unit": "x_throughput_int8_vs_bf16_engine",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_pod_serving())
    except Exception as e:
        lines.append({
            "metric": "pod_serving", "value": None,
            "unit": "x_tokens_per_sec_tp2_vs_single_device",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_disaggregated_serving())
    except Exception as e:
        lines.append({
            "metric": "disaggregated_serving", "value": None,
            "unit": "x_mixed_load_interactive_ttft_p99_colocated_vs_split",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_resilience())
    except Exception as e:
        lines.append({
            "metric": "resilience", "value": None,
            "unit": "x_sentinel_step_time_vs_unguarded",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_multihost_scaling())
    except Exception as e:
        lines.append({
            "metric": "multihost_scaling", "value": None,
            "unit": "x_scaling_efficiency_1to2_hosts_weak",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_telemetry_overhead())
    except Exception as e:
        lines.append({
            "metric": "telemetry_overhead", "value": None,
            "unit": "x_step_time_telemetry_on_vs_off",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)
    try:
        lines.append(bench_bert())
    except Exception as e:  # keep the headline line valid if BERT fails
        lines.append({
            "metric": "bert_base_finetune_examples_per_sec",
            "value": None, "unit": "examples/sec",
            "error": f"{type(e).__name__}: {e}"[:300]})
    _emit(lines)  # prints the ResNet headline LAST (driver parses last line)
