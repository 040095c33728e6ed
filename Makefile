# Top-level build driver (the reference's Makefile + make/config.mk role).
# The Python/XLA compute path needs no build; `make` produces the native
# runtime libraries (RecordIO/image pipeline, C predict ABI, full C graph
# ABI) into mxnet_tpu/lib/.

all: native

native:
	$(MAKE) -C cpp all

examples: native
	$(MAKE) -C cpp example/predict_example example/capi_example

test: native
	python -m pytest tests/ -x -q

# Regenerate every surface derived from the op registry. Run this in the
# same change as ANY OpSpec edit — tests/test_bindings.py gates staleness.
manifest:
	python tools/gen_api_manifest.py
	python scala-package/generate_ops.py
	python R-package/generate_ops_r.py

# Fast pre-commit gate (<2 min): generated-surface freshness + operator
# registry sanity. Run before any end-of-round snapshot commit.
check:
	python -m pytest tests/test_bindings.py tests/test_attr.py tests/test_infer_shape.py -q

# On the chip machine only (each refuses any other platform), one
# command per chip-tool call. smoke: the quickest proof that the trainer
# and the serving engine still start on the chip; smoke4: the dp x tp
# trainer and the tp=4 engine on a four-chip host.
smoke:
	python chip_smoke.py

smoke4:
	python chip_smoke.py --chips 4

bench:
	python bench.py

# Direction-aware diff of two bench rounds (tools/bench_compare.py):
# exits nonzero when a judged key (tokens/s, *_ms, bytes_accessed, ...)
# regressed past the threshold. See doc/performance.md "Comparing
# bench rounds".
#   make benchdiff OLD=old/BENCH_extra.json NEW=BENCH_extra.json
#   make benchdiff OLD=a.json NEW=b.json THRESHOLD=10 KEYS=serving
benchdiff:
	@test -n "$(OLD)" -a -n "$(NEW)" || \
		{ echo "usage: make benchdiff OLD=<a.json> NEW=<b.json> [THRESHOLD=5] [KEYS=substr]"; exit 2; }
	python tools/bench_compare.py $(OLD) $(NEW) \
		$(if $(THRESHOLD),--threshold $(THRESHOLD)) \
		$(if $(KEYS),--keys $(KEYS))

# Fleet fault-injection sweep (doc/fault_tolerance.md "Fleet
# resilience"): the slow-marked randomized chaos schedules on top of
# the deterministic tier-1 fleet tests — kill/blackhole/slow/lost-
# submit storms against a live fleet, byte-identity and zero failed
# requests as the bar. Off the tier-1 path; run before serving-layer
# releases.
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_faults.py tests/test_fleet.py -q -m "slow or not slow"

# Pallas kernel tests standalone, interpret mode on CPU (doc/serving.md
# "Fused quantized kernels"): the paged-attention kernel suite plus the
# quantized-matmul / fused-decode kernel suite, without the rest of
# tier-1, plus their compiles for a described v5e chip
# (tests/test_chip_compile.py). Fast inner loop when hacking on
# ops/pallas_kernels.py.
kernels:
	JAX_PLATFORMS=cpu python -m pytest tests/test_pallas.py tests/test_pallas_quant.py tests/test_paged_attention.py tests/test_chip_compile.py -q

lint:
	python -m compileall -q mxnet_tpu tools example

# Observability drift gate standalone (doc/observability.md): every
# registered metric has a catalog row, every MXNET_* knob a doc entry
# (tools/lint_metrics.py) — doc drift fails fast without a tier-1 run.
lintobs:
	python tools/lint_metrics.py

clean:
	$(MAKE) -C cpp clean

.PHONY: all native examples test manifest check smoke smoke4 bench benchdiff chaos kernels lint lintobs clean
