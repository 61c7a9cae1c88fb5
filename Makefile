# Developer/CI entry points. The test suite itself is plain pytest (see
# ROADMAP.md "Tier-1 verify" for the canonical command).

PY ?= python

.PHONY: test test-fast lint multihost-sim multihost-smoke bench \
	bench-generative bench-kernels bench-pod-serving bench-disagg \
	bench-decode bench-fleet disagg-sim trace-demo tune

# ISSUE 15: JAX-aware static analysis (runtime/staticcheck.py) — the
# repo's hand-enforced invariants as machine-checked rules. Exits
# non-zero on any finding that is neither suppressed inline (with a
# reason) nor grandfathered in staticcheck_baseline.json (with a
# reason). `--format json` for the full schema; `--list-rules` to see
# the active rule set.
lint:
	env JAX_PLATFORMS=cpu $(PY) -m deeplearning4j_tpu.runtime.staticcheck

# fast (tier-1) suite — what CI gates on (lint runs first: a lint
# finding fails the build before the slower pytest pass starts)
test-fast: lint
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		-p no:cacheprovider

# everything, including the slow multi-process / import-corpus tests
test:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -p no:cacheprovider

# ISSUE 10: full 2-process pod simulation (real subprocesses joined by
# jax.distributed over loopback) — ZeRO-1 + hierarchical-overlap on the
# 2-D pod mesh, 1-vs-2-host scaling, host-loss resume bit-equality,
# 2->1 topology restore. Writes MULTICHIP_LOCAL_r07.json.
multihost-sim:
	$(PY) -m deeplearning4j_tpu.parallel.multihost_sim \
		--outdir .scratch/multihost_sim \
		--artifact MULTICHIP_LOCAL_r07.json

# the tier-1 smoke slice of the same harness: spawn the 2-process pod,
# train 2 steps, shut down cleanly
multihost-smoke:
	$(PY) -c "from deeplearning4j_tpu.parallel.multihost_sim import \
run_smoke; import json, tempfile; \
print(json.dumps(run_smoke(tempfile.mkdtemp())))"

bench:
	$(PY) bench.py

# ISSUE 12: the generative-serving metric standalone — paged-vs-
# contiguous A/B (concurrent streams/GB, prefix hit rate, CoW forks),
# speculative accept-rate, zero post-warmup compiles. CPU-capable.
bench-generative:
	env JAX_PLATFORMS=cpu $(PY) -c "import json, bench; \
print(json.dumps(bench.bench_generative_serving(), indent=1))"

# ISSUE 17: the tensor-parallel pod-serving metric standalone — TP-vs-
# single-device interleaved A/B on a 4-virtual-device CPU mesh, with
# greedy bit-parity, per-device pool-bytes == full/k, zero post-warmup
# compiles, and the shard_map dispatch mix all hard-asserted in-bench.
bench-pod-serving:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS=--xla_force_host_platform_device_count=4 \
		$(PY) -c "import json, bench; \
print(json.dumps(bench.bench_pod_serving(), indent=1))"

# ISSUE 19: the host-free decode metric standalone — adaptive
# multi-token horizons + double-buffering vs the horizon-1 interleaved
# loop (interleaved pairs, median of tokens/sec ratios), with greedy
# bit-parity, zero post-warmup compiles in both windows, the horizon
# histogram / dispatch-decision mix, and per-arm attribution reports
# showing the host fraction shrink — all hard-asserted in-bench.
bench-decode:
	env JAX_PLATFORMS=cpu $(PY) -c "import json, bench; \
print(json.dumps(bench.bench_decode_loop(), indent=1))"

# ISSUE 20: the model-fleet hot-swap metric standalone — open-loop
# load across interleaved (steady, during-swap) window pairs; hard-
# asserts in-bench that the median during/steady p99 ratio is <= 1.1,
# zero requests dropped, zero post-warmup compiles on any incumbent,
# and that the forced canary-rollback drill produced its flight dump
# (swap/rollback counters ride the artifact). CPU-capable.
bench-fleet:
	env JAX_PLATFORMS=cpu $(PY) -c "import json, bench; \
print(json.dumps(bench.bench_fleet_swap(), indent=1))"

# ISSUE 18: the disaggregated-serving metric standalone — colocated vs
# prefill/decode-split mixed-load A/B (interleaved rounds, median of
# per-round interactive-stream TTFT-p99 ratios, decode-TPOT ramp
# ratios under the prefill burst, stitched-timeline check, zero
# post-warmup compiles). CPU-capable.
bench-disagg:
	env JAX_PLATFORMS=cpu $(PY) -c "import json, bench; \
print(json.dumps(bench.bench_disaggregated_serving(), indent=1))"

# the REAL two-process topology behind it: a prefill process ships KV
# pages over a socket, a decode process adopts and serves them — greedy
# bit-parity vs the colocated oracle, migrated-prefix reuse, stitched
# cross-process timelines, zero post-warmup compiles (also the tier-1
# gate via tests/test_disagg.py::test_disagg_two_process_sim)
disagg-sim:
	$(PY) -m deeplearning4j_tpu.parallel.multihost_sim --disagg \
		--outdir .scratch/disagg_sim

# ISSUE 16: the fused-epilogue kernel-library metric standalone — the
# fused master-cast+updater step vs the unfused updater-then-cast-sweep
# sequence (interleaved A/B, median of per-round ratios, bit-parity
# asserted in-bench, zero post-warmup compiles). CPU-capable; the
# BN/LN/GeLU epilogue kernels themselves are TPU-only wins and are
# covered by interpret-mode parity tests instead.
bench-kernels:
	env JAX_PLATFORMS=cpu $(PY) -c "import json, bench; \
print(json.dumps(bench.bench_fused_epilogues(), indent=1))"

# ISSUE 14: joint schedule tuner dry-run on CPU with a toy model —
# seeds a default cache entry (CPU never sweeps), asserts the JSON
# cache file was written and re-loads into a hit. Exits non-zero on any
# failed invariant.
tune:
	env JAX_PLATFORMS=cpu \
		DL4J_TPU_SCHEDULE_CACHE=/tmp/dl4j_tpu_schedule_cache.json \
		$(PY) -m deeplearning4j_tpu.runtime.schedule

# ISSUE 13: tiny serve-and-trace loop — boots a JsonModelServer, POSTs a
# few /predict requests with the JSONL event log on, resolves one
# request at GET /trace/<id>, validates the JSONL schema, and
# pretty-prints the stitched timeline. Doubles as a schema smoke test.
trace-demo:
	env JAX_PLATFORMS=cpu $(PY) -m deeplearning4j_tpu.runtime.trace_demo
