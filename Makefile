# Native components of ray_tpu. `make native` builds the CPython extension
# in-place; ray_tpu/_native auto-invokes this on first import if the .so is
# missing (g++ is part of the supported toolchain).

PY       ?= python3
PY_INC   := $(shell $(PY) -c "import sysconfig; print(sysconfig.get_paths()['include'])")
CXX      ?= g++
CXXFLAGS ?= -O2 -g -std=c++17 -fPIC -Wall -Wextra
LDLIBS   := -lpthread -lrt

STORE_SRC := src/store/rts_store.cc
EXT       := ray_tpu/_native/_rtstore.so
PUMP_SRC  := src/pump/rts_pump.cc
PUMP_EXT  := ray_tpu/_native/_rtpump.so

.PHONY: native native-test native-ubsan cpp-client clean check check-slow check-obs check-metrics rtlint perf-transfer perf-actor perf-native perf-dispatch train-smoke train-chaos chaos overload

# Static analysis: the rtlint distributed-invariant analyzer (pass
# catalog: python -m tools.rtlint --list). Exits non-zero on any
# finding that is neither baselined (tools/rtlint/baseline.json) nor
# pragma-suppressed (# rtlint: disable=<pass>).
rtlint:
	$(PY) -m tools.rtlint

# Observability lint (the "obs" pass group of rtlint; the old
# tools/check_metric_names.py entry point remains as an alias shim):
# every Counter/Gauge/Histogram the package declares at import time
# plus event emit sites, chaos registry, pickle bans, serve hot path.
check-obs:
	$(PY) -m tools.rtlint --passes obs

# Historical alias for check-obs.
check-metrics: check-obs

# Fast CPU smoke of the compiled training step (2-layer, chunk=1, one
# fused pjit step with donation): a pjit/scan regression fails here in
# seconds, before any run on the chip sees it.
train-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/run_train_smoke.py

# CI umbrella: the full static-analysis plane + the sanitized native
# build/tests + the compiled-train-step smoke. Tier-1 docs point here.
# (rtlint already includes the obs pass group, so check-obs is not
# repeated.)
check: rtlint native-test train-smoke

# Slow tier of `make check`: the multi-minute acceptance suites — the
# chaos partition matrix, the overload closed loop, and the elastic
# train-gang chaos run (gang restart + checkpoint fallback + rolling
# restart under an active fit -> MULTICHIP_r06.json).
check-slow: check chaos overload train-chaos

# Elastic gang lifecycle acceptance: multi-process jax.distributed
# rendezvous (2 procs x 4 virtual devices, GCS-KV-brokered
# coordinator), rank killed mid-step -> restart from the last COMMITTED
# checkpoint (trajectory must match an uninterrupted run), a
# checkpoint_io fault during save -> fall back to the previous commit,
# and Cluster.rolling_restart() under an active fit (<= 1 step lost).
# Records MULTICHIP_r06.json.
train-chaos:
	JAX_PLATFORMS=cpu $(PY) tools/run_train_chaos.py MULTICHIP_r06.json
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_train_elastic.py -q \
	  -p no:cacheprovider

# Chaos plane acceptance suite: the full fault-injection partition
# matrix (every registered point proves its advertised degradation path
# with exactly-once semantics) plus the drain + rolling-restart tests
# (every worker node of a live 3-node cluster replaced under a serving
# deployment with zero failed requests).
# The fencing half (tests/test_fencing.py + tools/run_fence_chaos.py)
# proves the asymmetric-partition scenario end to end — sticky
# heartbeat partition, node fenced at a membership epoch, actor
# restarted on a survivor with zero double-executions and zero stale
# results, zombie self-termination + fresh-incarnation rejoin — and
# records the numbers into OVERLOAD_r02.json.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py \
	  tests/test_fencing.py -q -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/run_fence_chaos.py OVERLOAD_r02.json

# Overload-control acceptance: the request-robustness test matrix
# (deadline refusal/cancellation, adaptive shedding, breaker
# open/half-open/close under chaos-armed latency on one replica) plus
# the closed-loop overload bench recorded to OVERLOAD_r01.json.
overload:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_overload.py -q \
	  -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/run_overload_bench.py OVERLOAD_r01.json

# Cross-node transfer bench: 2-node loopback, 256 MiB object through the
# striped data plane, JSON GB/s + concurrent control-plane ping p99.
perf-transfer:
	JAX_PLATFORMS=cpu $(PY) tools/run_transfer_bench.py

# Direct actor-call plane bench: loaded + unloaded sync round-trips over
# the GIL-free dispatch core (native pump + pending/waiter tables
# engaged AND RTPU_NO_NATIVE=1 fallback) vs the NM-mediated path, the
# per-phase GIL-handoff probe, the 1M-queued drain row with driver RSS,
# fallback-injection recovery, and the rpc dispatch micro-bench —
# merged into PERF_r09.json.
perf-actor:
	JAX_PLATFORMS=cpu $(PY) tools/run_actor_bench.py PERF_r09.json

# Native frame-pump bench: codec microbench vs pickle on the compact
# call frame, pump framing throughput, and the queued-task drain probe
# — merged into PERF_r09.json beside the perf-actor record.
perf-native:
	JAX_PLATFORMS=cpu $(PY) tools/run_native_bench.py PERF_r09.json

# Control-plane dispatch bench: per-op stage p50/p99 for the NM/GCS
# frame loops under a mixed workload (the numbers `rtpu rpc` shows),
# event-loop lag + GIL-proxy series liveness, and the obs_overhead row
# (instrumented vs RTPU_NO_DISPATCH_OBS=1, bar <= 3%).
perf-dispatch:
	JAX_PLATFORMS=cpu $(PY) tools/run_dispatch_bench.py PERF_r10_baseline.json

native: $(EXT) $(PUMP_EXT)

# C++ client frontend (ref analogue: the reference's cpp/ worker API):
# zero-copy arena object plane + JSON control channel. `make cpp-client`
# builds the demo driver tests/test_cpp_client.py runs.
build/rtpu_demo: cpp/rtpu_client.cc cpp/rtpu_demo.cc cpp/rtpu_client.h $(STORE_SRC)
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -Isrc/store -Icpp cpp/rtpu_client.cc cpp/rtpu_demo.cc \
	  $(STORE_SRC) -o $@ $(LDLIBS)

cpp-client: build/rtpu_demo

$(EXT): $(STORE_SRC) src/store/_rtstore_module.cc src/store/rts_store.h
	$(CXX) $(CXXFLAGS) -shared -I$(PY_INC) -Isrc/store \
	  $(STORE_SRC) src/store/_rtstore_module.cc -o $@ $(LDLIBS)

$(PUMP_EXT): $(PUMP_SRC) src/pump/_rtpump_module.cc src/pump/rts_pump.h
	$(CXX) $(CXXFLAGS) -shared -I$(PY_INC) -Isrc/pump \
	  $(PUMP_SRC) src/pump/_rtpump_module.cc -o $@ $(LDLIBS)

build/rts_store_test: $(STORE_SRC) src/store/rts_store_test.cc src/store/rts_store.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -Isrc/store $(STORE_SRC) src/store/rts_store_test.cc \
	  -o $@ $(LDLIBS)

build/rts_pump_test: $(PUMP_SRC) src/pump/rts_pump_test.cc src/pump/rts_pump.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -Isrc/pump $(PUMP_SRC) src/pump/rts_pump_test.cc \
	  -o $@ $(LDLIBS)

# CI-ready native gate: every C++ unit test (store + pump) plain AND
# under all three sanitizers — any report fails the target
# (halt_on_error / -fno-sanitize-recover). The pump test includes the
# ISSUE 12 pending-table stress (a pipelined submitter parked on the
# backpressure condvar vs a completer applying DONE frames, then an
# injected channel death mid-stream with exactly-once accounting) —
# the TSAN/ASAN/UBSAN builds are the lock-discipline gate for the
# GIL-free dispatch core.
native-test: build/rts_store_test build/rts_pump_test native-tsan native-asan native-ubsan
	./build/rts_store_test
	./build/rts_pump_test

clean:
	rm -rf build $(EXT) $(PUMP_EXT) .jax_cache

# Sanitizer builds of the C++ unit tests (ref analogue: the reference's
# TSAN/ASAN CI jobs over the C++ core). `make native-tsan native-asan`
# runs the store AND pump tests under each sanitizer.
build/rts_store_test_tsan: $(STORE_SRC) src/store/rts_store_test.cc src/store/rts_store.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=thread -Isrc/store $(STORE_SRC) \
	  src/store/rts_store_test.cc -o $@ $(LDLIBS)

build/rts_store_test_asan: $(STORE_SRC) src/store/rts_store_test.cc src/store/rts_store.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=address,undefined -Isrc/store $(STORE_SRC) \
	  src/store/rts_store_test.cc -o $@ $(LDLIBS)

build/rts_pump_test_tsan: $(PUMP_SRC) src/pump/rts_pump_test.cc src/pump/rts_pump.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=thread -Isrc/pump $(PUMP_SRC) \
	  src/pump/rts_pump_test.cc -o $@ $(LDLIBS)

build/rts_pump_test_asan: $(PUMP_SRC) src/pump/rts_pump_test.cc src/pump/rts_pump.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=address,undefined -Isrc/pump $(PUMP_SRC) \
	  src/pump/rts_pump_test.cc -o $@ $(LDLIBS)

native-tsan: build/rts_store_test_tsan build/rts_pump_test_tsan
	TSAN_OPTIONS=halt_on_error=1 ./build/rts_store_test_tsan
	TSAN_OPTIONS=halt_on_error=1 ./build/rts_pump_test_tsan

native-asan: build/rts_store_test_asan build/rts_pump_test_asan
	ASAN_OPTIONS=detect_leaks=1:halt_on_error=1 ./build/rts_store_test_asan
	ASAN_OPTIONS=detect_leaks=1:halt_on_error=1 ./build/rts_pump_test_asan

# Standalone UBSAN builds (the ASAN combo above folds undefined in, but
# a dedicated -fsanitize=undefined build catches UB that ASAN's shadow
# memory masks, and -fno-sanitize-recover=undefined turns every report
# into a hard failure instead of a log line).
build/rts_store_test_ubsan: $(STORE_SRC) src/store/rts_store_test.cc src/store/rts_store.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=undefined -fno-sanitize-recover=undefined \
	  -Isrc/store $(STORE_SRC) src/store/rts_store_test.cc -o $@ $(LDLIBS)

build/rts_pump_test_ubsan: $(PUMP_SRC) src/pump/rts_pump_test.cc src/pump/rts_pump.h
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -fsanitize=undefined -fno-sanitize-recover=undefined \
	  -Isrc/pump $(PUMP_SRC) src/pump/rts_pump_test.cc -o $@ $(LDLIBS)

native-ubsan: build/rts_store_test_ubsan build/rts_pump_test_ubsan
	UBSAN_OPTIONS=print_stacktrace=1:halt_on_error=1 ./build/rts_store_test_ubsan
	UBSAN_OPTIONS=print_stacktrace=1:halt_on_error=1 ./build/rts_pump_test_ubsan

sanitize: native-tsan native-asan native-ubsan
