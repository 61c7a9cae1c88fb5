# Developer/CI entry points. The test suite itself is plain pytest (see
# ROADMAP.md "Tier-1 verify" for the canonical command).

PY ?= python

.PHONY: test test-fast lint multihost-sim multihost-smoke disagg-sim \
	trace-demo tune

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

# the REAL two-process disaggregated-serving topology: a prefill process
# ships KV pages over a socket, a decode process adopts and serves them — greedy
# bit-parity vs the colocated oracle, migrated-prefix reuse, stitched
# cross-process timelines, zero post-warmup compiles (also the tier-1
# gate via tests/test_disagg.py::test_disagg_two_process_sim)
disagg-sim:
	$(PY) -m deeplearning4j_tpu.parallel.multihost_sim --disagg \
		--outdir .scratch/disagg_sim

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
