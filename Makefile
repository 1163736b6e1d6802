# Build targets. `make native` builds the C++ graph engine into
# torchdistx_tpu/_lib/ (used automatically when present; TDX_NATIVE=0
# disables).

.PHONY: native native-test native-test-build native-cmake leak-check test chaos-test registry-smoke serve-smoke fleet-smoke obs-smoke reshard-smoke guardrails-smoke rollover-smoke soak-smoke bench-smoke bench-trend lint lint-native trace-summary wheel packaging-smoke docs examples clean

NATIVE_CXXFLAGS := -std=c++17 -O2 -fPIC -fvisibility=hidden \
	-Wall -Wextra -fstack-protector-strong
SAN ?=

native:
	mkdir -p torchdistx_tpu/_lib
	g++ $(NATIVE_CXXFLAGS) $(SAN) -shared \
	    -o torchdistx_tpu/_lib/libtdxgraph.so csrc/tdx_graph.cc

native-test-build:
	mkdir -p csrc/build
	g++ $(NATIVE_CXXFLAGS) $(SAN) -pthread \
	    -o csrc/build/test_graph csrc/tdx_graph.cc csrc/test_graph.cc

# Also the TSan lane: `make native-test SAN="-fsanitize=thread"` runs the
# concurrent record-while-materialize stress in csrc/test_graph.cc under
# the thread sanitizer (.github/workflows/ci.yaml `sanitize` job).
native-test: native-test-build
	./csrc/build/test_graph

native-cmake:
	cmake -S csrc -B csrc/build -G Ninja
	cmake --build csrc/build

# The reference's LSan-grep protocol (its _test_wheel.yaml:66-90): leak
# detection ON but exitcode forced 0 (the host runtime leaks too much for
# exit-code checking), then grep the report's stack frames for OUR
# library — a tdx_*/libtdxgraph frame inside a leak trace fails the
# build, anything else is tolerated.
leak-check:
	$(MAKE) native-test-build SAN="-fsanitize=address -fno-omit-frame-pointer"
	ASAN_OPTIONS=detect_leaks=1:exitcode=0 ./csrc/build/test_graph \
	    2> /tmp/tdx_lsan.log
	@if grep -E "#[0-9]+ .*(tdx_|libtdxgraph)" /tmp/tdx_lsan.log; then \
	    echo "LEAK with tdxgraph frames (full log: /tmp/tdx_lsan.log)"; \
	    exit 1; \
	else echo "leak-check OK: no tdxgraph frames in LSan output"; fi

test:
	python -m pytest tests/ -q

# The fault-injection suite (docs/robustness.md), INCLUDING the cases
# tier-1 excludes as `slow` (multi-second hang injection / drain
# subprocesses).  JAX_PLATFORMS=cpu: chaos scenarios are deterministic
# CPU reproductions; real-hardware recovery is soaked separately via
# `tools/soak.py --modes elastic --platform default` on the chip.
chaos-test: registry-smoke serve-smoke fleet-smoke guardrails-smoke rollover-smoke obs-smoke reshard-smoke
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py \
	    tests/test_materialize_chaos.py tests/test_failures.py \
	    tests/test_registry.py tests/test_serve.py tests/test_fleet.py \
	    tests/test_guardrails.py tests/test_rollover.py \
	    tests/test_flightrec.py tests/test_materialize_transport.py \
	    tests/test_live_ops.py tests/test_bench_trend.py \
	    tests/test_reshard.py \
	    -q -p no:cacheprovider

# Observability smoke (docs/observability.md §Flight recorder): an
# injected compile hang (watchdog-killed), an exhausted materialization
# ladder, a chaos serve fault, and an uncaught exception must each leave
# a schema-valid flight-recorder dump under TDX_FLIGHT_DIR that
# tools/tdx_trace.py renders (flight + fleet), with the periodic
# exporter writing %h-expanded metrics throughout.  CPU, bounded; part
# of `make chaos-test`.
obs-smoke:
	timeout -k 10 420 bash scripts/obs_smoke.sh

# Serving smoke (docs/serving.md): decode-program warm into a shared
# artifact registry, then a fresh-process replica bring-up with an
# EMPTY local cache that must perform zero local compiles and serve a
# scripted request storm whose outputs equal the unbatched oracle.
# CPU, bounded; part of `make chaos-test`.
serve-smoke:
	timeout -k 10 420 bash scripts/serve_smoke.sh

# Fleet smoke (docs/serving.md §Fleet): registry-warm 2-replica fleet
# bring-up with ZERO local compiles asserted, one replica chaos-killed
# mid-storm with every response still equal to the unbatched oracle,
# then a warm mid-run scale-up and a drain-based scale-down.  CPU,
# bounded; part of `make chaos-test`.
fleet-smoke:
	timeout -k 10 420 bash scripts/fleet_smoke.sh

# Guardrails smoke (docs/serving.md §Guardrails): registry-warm fleet
# under a permanently flapping replica with every guardrail armed —
# breaker trip + warm quarantine-and-respawn (zero local compiles),
# hedged dispatch, typed deadline rejections carrying oracle-prefix
# tokens, then a brownout shed/door-reject/hysteretic-exit pass — all
# with completed output equal to the unbatched oracle.  CPU, bounded;
# part of `make chaos-test`.
guardrails-smoke:
	timeout -k 10 420 bash scripts/guardrails_smoke.sh

# Rollover smoke (docs/serving.md §Weight rollover): run_elastic trains
# two committed checkpoints, then a registry-warm 2-replica fleet rolls
# blue-green onto step_2 MID-STORM — GREEN bring-up with zero local
# compiles, bitwise canary gate, shift, BLUE drains — every response
# oracle-equal for the version it was served under, zero rejections;
# then a bit-flipped step_2 is caught by the gate's verify arm,
# quarantined, with BLUE serving untouched.  CPU, bounded; part of
# `make chaos-test`.
rollover-smoke:
	timeout -k 10 420 bash scripts/rollover_smoke.sh

# Pod-scale registry smoke (docs/registry.md): a 2-process sharded warm
# against a shared artifact registry — disjoint compile shards verified
# from each process's per-program outcome report — then a fresh process
# with an EMPTY local TDX_CACHE_DIR that must materialize with zero
# local compiles (every program a registry fetch hit) and bitwise-equal
# outputs.  CPU, bounded; part of `make chaos-test`.
registry-smoke:
	timeout -k 10 420 bash scripts/registry_smoke.sh

# Topology-migration smoke (docs/robustness.md §Resharding): save a
# training state under a 1x4 fsdp layout, reshard_ctl.py-apply it to
# 2x2 gspmd2d AND 1x2 fsdp layouts (exit codes + independent
# leaf-by-leaf bitwise verify, plus a corrupted-destination negative
# gate), then a FRESH process restores the 2x2 result through the
# elastic loop and trains a step.  CPU, bounded; part of
# `make chaos-test`.
reshard-smoke:
	timeout -k 10 420 bash scripts/reshard_smoke.sh

# One short materialize-recovery soak cycle under tier-1 constraints
# (CPU, bounded wall clock): drives the self-healing materialization
# ladder end-to-end through tools/soak.py with a fixed fault plan —
# compile failure + slow execute survived bitwise on every seed.  The
# randomized long-running companion is `tools/soak.py --modes
# materialize --seconds 3600` (docs/robustness.md).
soak-smoke:
	JAX_PLATFORMS=cpu timeout -k 10 420 python tools/soak.py \
	    --modes materialize --seconds 120 --seeds 4 --workers 2 \
	    --start 910000 --fault-plan 'compile@1=raise;execute@2=slow:0.1'

# Fast CPU slice of bench.py under tier-1 constraints, so materialize-
# path regressions fail in CI instead of only in nightly bench: the
# engine A/B phase (small depth — the gate is bitwise parity and a sane
# engine split, not the full-scale speedup) plus the static schedule
# analysis.  Each phase prints one JSON line; the python step asserts
# the parity bit and the absence of an error key.
bench-smoke:
	JAX_PLATFORMS=cpu TDX_BENCH_PLATFORM=cpu TDX_PIPE_BENCH_LAYERS=32 \
	    TDX_PIPE_BENCH_REPEATS=1 timeout -k 10 540 \
	    python bench.py --phase materialize_pipeline | tail -1 \
	    | python -c "import json,sys; r=json.load(sys.stdin); \
	        assert r.get('bitwise_equal') is True, r; \
	        wc = r.get('warm_cache') or {}; \
	        assert wc.get('hit') and 'miss' not in wc, r; \
	        print('materialize_pipeline OK:', \
	              'speedup', r.get('pipeline_speedup'), \
	              'programs', r.get('n_programs'))"
	JAX_PLATFORMS=cpu TDX_BENCH_PLATFORM=cpu timeout -k 10 120 \
	    python bench.py --phase pp_bubble | tail -1 \
	    | python -c "import json,sys; r=json.load(sys.stdin); \
	        assert 'schedule_analysis' in r, r; print('pp_bubble OK')"
	JAX_PLATFORMS=cpu TDX_BENCH_PLATFORM=cpu TDX_BW_BENCH_MB=64 \
	    TDX_BW_BENCH_SLABS=16 TDX_BW_BENCH_REPEATS=2 timeout -k 10 360 \
	    python bench.py --phase materialize_bandwidth | tail -1 \
	    | python -c "import json,math,sys; r=json.load(sys.stdin); \
	        assert r.get('bitwise_equal') is True, r; \
	        u = r.get('materialize_link_utilization'); \
	        assert u is not None and math.isfinite(u) and u > 0, r; \
	        print('materialize_bandwidth OK:', \
	              'gbps', r.get('materialize_gbps'), \
	              'link_util', u, \
	              'overlap', r.get('transfer_overlap'))"
	JAX_PLATFORMS=cpu TDX_BENCH_PLATFORM=cpu TDX_SCHED_SHAPES=pp2_v2 \
	    TDX_SCHED_PARITY=1 TDX_SCHED_SEGMENTS=0 timeout -k 10 540 \
	    python bench.py --phase schedule_measured | tail -1 \
	    | python -c "import json,math,sys; \
	        r=json.load(sys.stdin)['schedule_measured']; \
	        s=r['shapes']['pp2_v2']; \
	        assert s.get('parity_bitwise') is True, s; \
	        mva=s.get('measured_vs_analytic'); \
	        assert mva is not None and math.isfinite(mva) and mva > 0, s; \
	        print('schedule_measured OK:', \
	              'parity_bitwise', s['parity_bitwise'], \
	              'measured_vs_analytic', mva, \
	              'seg_vs_uniform', s.get('segmented_vs_uniform'))"

# Bench-trajectory regression sentinel (docs/observability.md): render
# the per-headline-key trend across every BENCH_r*.json round and exit
# 1 if a gated key regressed vs its best comparable (same hardware
# class) prior round.
bench-trend:
	python tools/bench_trend.py

# One lint entry point for CI and humans (rule set lives in ruff.toml).
# Same degrade-to-skip protocol as `docs`: the dev image ships no ruff,
# CI installs it and fails loudly.
lint: lint-native
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	elif python -c "import ruff" 2>/dev/null; then \
		python -m ruff check .; \
	else \
		echo "lint skipped: ruff not installed (CI runs it)"; \
	fi

# C++ lint over csrc/ (style: .clang-format, checks: .clang-tidy).  Same
# degrade-to-skip protocol: the dev image ships no clang tools, CI
# installs them and fails loudly (ci.yaml `lint` job).
lint-native:
	@if command -v clang-format >/dev/null 2>&1; then \
		clang-format --dry-run --Werror \
		    csrc/tdx_graph.cc csrc/test_graph.cc csrc/include/tdx_graph.h; \
	else \
		echo "clang-format skipped: not installed (CI runs it)"; \
	fi
	@if command -v clang-tidy >/dev/null 2>&1; then \
		clang-tidy csrc/tdx_graph.cc csrc/test_graph.cc -- \
		    -std=c++17 -pthread; \
	else \
		echo "clang-tidy skipped: not installed (CI runs it)"; \
	fi

# Digest a telemetry trace directory (see docs/observability.md): top
# spans by self-time, compile-cache hit ratio, platform-fallback count.
# TDX_TRACE_DIR defaults to ./traces for symmetry with the env knob that
# produces the files.
trace-summary:
	python tools/tdx_trace.py summary $${TDX_TRACE_DIR:-traces}

# Build a wheel bundling the native engine (reference parity: its
# setup.py install_cmake wheel flow; setup.py itself runs `make native`).
wheel:
	python -m pip wheel --no-deps --no-build-isolation -w dist .

# Run the conda packaging pipeline's build + native install scripts into
# scratch prefixes and assert the package file partition (no conda-build
# needed; see packaging/conda/smoke.sh).
packaging-smoke:
	bash packaging/conda/smoke.sh

# Render the markdown docs into a Sphinx site (docs/conf.py).  The dev
# image ships no sphinx, so degrade to a skip locally; CI installs the
# toolchain and fails loudly (.github/workflows/docs.yaml).
docs:
	@if python -c "import sphinx, myst_parser" 2>/dev/null; then \
		python -m sphinx -b html docs docs/_build/html; \
	else \
		echo "docs build skipped: sphinx/myst-parser not installed (CI runs it)"; \
	fi

# Run every example end-to-end (each forces its own virtual CPU mesh;
# no accelerator needed).  Nightly CI runs this so the examples cannot
# rot against the library surface.
examples:
	@set -e; for ex in examples/*.py; do \
		echo "== $$ex"; \
		PYTHONPATH=. python "$$ex" > /tmp/tdx_ex.log 2>&1 \
		    || { tail -40 /tmp/tdx_ex.log; exit 1; }; \
	done; echo "all examples OK"

clean:
	rm -rf csrc/build torchdistx_tpu/_lib
