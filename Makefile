# Developer entry points (ref: the reference repo's makefile test/coverage
# targets). Everything runs on the virtual CPU mesh unless noted; the
# targets marked "on the chip" exit non-zero without a TPU.

PY ?= python

.PHONY: test test-all test-slow bench dryrun smoke chip-smoke \
	chip-smoke-rehearse fit-overhead telemetry-smoke analysis lint \
	verify-plans kernel-audit chaos serve-smoke perf-gate \
	nsa-needle-smoke plan-cache-smoke straggler-smoke

test: analysis chaos serve-smoke plan-cache-smoke straggler-smoke  ## fast tier: the correctness surface in < 5 min on one core
	$(PY) -m pytest tests/ -x -q -m "not slow"

test-all: analysis  ## everything: + model training, scale oracles, property suites
	$(PY) -m pytest tests/ -q

analysis: lint verify-plans kernel-audit  ## static passes: linter + plan verifier + kernel contract audit

lint:  ## AST repo rules (analysis/lint.py) over the package, with baseline
	$(PY) -m magiattention_tpu.analysis.lint

verify-plans:  ## R1-R5 plan verifier over the golden solver corpus (CPU)
	JAX_PLATFORMS=cpu $(PY) scripts/verify_plans.py

kernel-audit:  ## K1-K5 kernel contract audit over the golden config corpus (CPU)
	JAX_PLATFORMS=cpu $(PY) scripts/kernel_audit.py
	JAX_PLATFORMS=cpu $(PY) scripts/kernel_audit.py --selftest

test-slow:  ## only the slow tier (training / 262k-131k oracles / property)
	$(PY) -m pytest tests/ -q -m slow

chip-smoke:  ## on the chip: the CP training path end to end, one process, last line {"ok": true, "device": ...}
	$(PY) chip_smoke.py

chip-smoke-rehearse:  ## toy-size CPU rehearsal of chip_smoke.py (4 virtual devices, interpreted kernels; never prints the pass line)
	$(PY) chip_smoke.py --rehearse-cpu 4

bench:  ## on the chip: the one kernel-level headline number (ROADMAP A0 replaces it)
	$(PY) bench.py

dryrun:  ## 8-virtual-device multi-chip training-step validation
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

smoke:  ## on the chip: kernel census, one compile-and-compare case per pallas_call site
	$(PY) scripts/tpu_smoke.py

fit-overhead:  ## fit tile_policy.OVERHEAD_ELEMS from recorded sweeps
	$(PY) scripts/fit_tile_overhead.py

telemetry-smoke:  ## CPU telemetry round trip: JSONL + run-history store -> report, registry pins, then the perf gate
	$(PY) -m pytest tests/test_support/test_telemetry.py \
		tests/test_support/test_store.py \
		tests/test_support/test_registry.py -x -q
	$(PY) scripts/perf_gate.py

perf-gate:  ## fail on >10% bench regression vs prior run without a BENCH note
	$(PY) scripts/perf_gate.py

chaos:  ## fault-injection chaos matrix: every site recovers or raises typed
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
		$(PY) -m pytest tests/test_resilience -x -q -m chaos

nsa-needle-smoke:  ## needle-in-haystack retrieval through the gather-free NSA kernel (CPU interpret)
	JAX_PLATFORMS=cpu $(PY) examples/needle_1m.py --smoke

serve-smoke:  ## CPU continuous-batching end-to-end: engine bitwise vs replay
	JAX_PLATFORMS=cpu $(PY) scripts/serve_smoke.py

plan-cache-smoke:  ## two-process plan-store proof: warm start with zero solves + corruption heal
	JAX_PLATFORMS=cpu $(PY) scripts/plan_cache_smoke.py

straggler-smoke:  ## fake-clock straggler cycle: detect -> weighted re-solve -> recover (2 builds)
	JAX_PLATFORMS=cpu $(PY) scripts/straggler_smoke.py
