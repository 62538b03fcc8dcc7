# The machine-readable "this is how you run it" surface (the reference
# encodes the same contract in .github/workflows/main.yml:28-63: build the
# test binaries, run each on every platform).
#
#   make test       CPU tier: the full suite (incl. @slow macbeth-scale
#                   transcripts) on the 8-device virtual CPU mesh
#                   (tests/conftest.py forces the platform) — every
#                   sharding/collective path, no hardware needed.
#   make test-tpu   Hardware tier: @tpu-marked kernel/numerics tests on the
#                   real chip (compiles actual Pallas kernels).
#   make test-all   Both CPU tiers, then the TPU tier if a chip answers.
#   make native     Build the C++ host-runtime library (quant codecs, BPE).
#   make lint       The unified dlint static-analysis suite
#                   (python -m tools.dlint; catalog in LINTS.md): the
#                   trace-safety analyzer (closed-world jit entry through
#                   plan_scoped_jit / the shard_map shim, tracer-hazard
#                   detection in traced bodies, guarded-twin tripwire
#                   completeness), the thread-ownership analyzer
#                   (owner=loop/monitor/any call-graph checking,
#                   guarded-by lock discipline, lock-order cycles), and
#                   the name and registry rules (metric names, exception
#                   hygiene, route labels, failpoint sites, span phases,
#                   shard_map shim, ...). One rule:
#                   python -m tools.dlint --only RULE; CI summary: --json.
#   make quality-check  The quality-regression sentinel: the built-in
#                   fixture eval (deterministic tiny model over
#                   tests/goldens/eval_tiny.jsonl, every config in
#                   telemetry.EVAL_CONFIGS) checked against the
#                   committed QUALITY_BASELINE.json
#                   (tools/quality_baseline.py). Exits nonzero naming a
#                   perplexity regression beyond the documented
#                   tolerance or any bit-level parity drift between
#                   exact-parity configs. Re-record with
#                   `python tools/quality_baseline.py record` after a
#                   deliberate numerics change.
#   make graft      Compile-check the jittable entry + the 8-device
#                   multi-chip dry run (tp/pp/dp/sp/ep shardings).

PY ?= python

.PHONY: test test-tpu test-all native tsan quality-check graft lint clean

test:
	$(PY) -m pytest tests/ -q

test-tpu:
	DLLAMA_TESTS_TPU=1 $(PY) -m pytest tests/ -m tpu -q

test-all: test test-tpu

native:
	$(PY) -c 'from dllama_tpu import native; print(native.get_lib() or "native build unavailable (g++ missing?)")'

tsan:
	$(MAKE) -C dllama_tpu/native tsan
	TSAN_OPTIONS="halt_on_error=1 exitcode=66" ./dllama_tpu/native/tsan_stress

lint:
	$(PY) -m tools.dlint

quality-check:
	JAX_PLATFORMS=cpu $(PY) tools/quality_baseline.py check

graft:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) __graft_entry__.py

clean:
	$(MAKE) -C dllama_tpu/native clean
	rm -rf build dist *.egg-info
