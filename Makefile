# Developer targets.  `make sanitize` is the reference-parity TSan/ASan
# pass over the native code (reference: tsan_suppressions.txt + CI TSan
# suites): builds libt3fs_native with each sanitizer and runs the suites
# that exercise the four native components (chunk engine WAL/snapshot,
# usrbio shm rings, io_uring reader, socket movers) with the sanitizer
# runtime preloaded into python.

PY ?= python
TSAN_RT := $(shell g++ -print-file-name=libtsan.so)
ASAN_RT := $(shell g++ -print-file-name=libasan.so)
# "device"-codec params lazily import jax, whose nanobind bindings trip
# the preloaded sanitizer runtimes — the sanitizer pass targets the
# NATIVE code (engine WAL, usrbio rings, io_uring reader, socket
# movers), so those params are excluded (they run in the normal suite).
SAN_TESTS := tests/test_native_engine.py tests/test_usrbio.py \
             tests/test_engine_differential.py tests/test_chunk_engine.py \
             tests/test_storage_service.py tests/test_frame_reader.py
SAN_FILTER := -k "not device"

.PHONY: test lint sanitize sanitize-thread sanitize-address chip-smoke \
        ci ckpt-bench write-bench read-bench \
        kvcache-fleet-bench repair-drill usrbio-bench soak soak-smoke \
        health-smoke health-bench rebalance-drill rebalance-smoke \
        kv-distributor-bench kv-distributor-smoke \
        kvcache-scale-bench kvcache-scale-smoke

test:
	$(PY) -m pytest tests/ -x -q

# t3fslint: protocol-aware static analysis for the asyncio data plane
# (docs/static_analysis.md) — the Python-side twin of `make sanitize`.
# Exits non-zero on any unsuppressed finding; pure stdlib, no jax.
lint:
	$(PY) -m t3fs.analysis

# Checkpoint save/restore throughput (median of --runs fresh clusters
# per docs/bench_protocol.md); add --kill for the degraded-restore phase.
ckpt-bench:
	$(PY) -m benchmarks.ckpt_bench --json

# Write-pipeline A/B (ISSUE 4): p50 of 4 MiB 3-replica chain writes at
# concurrency 1, one JSON line with off/overlap/streamed side by side.
write-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.storage_bench --write-ab \
		--chunk-size 4194304 --replicas 3 --num-ops 16

# Hedged-read A/B (ISSUE 5): batched random reads against a fabric with
# one injected 10ms straggler node — off (load_balance, no hedging) vs
# on (adaptive selection + hedged reads), one JSON line side by side.
read-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.storage_bench --read-ab \
		--chunk-size 65536 --replicas 3 --num-ops 120

# KVCache serving-tier fleet bench (ISSUE 7, extended by ISSUE 20):
# 6 worker processes x 512 concurrent zipf sessions against one
# namespace, write-behind ON/OFF A/B, the GC removal-IOPS phase, and
# the admission-plane A/B (shm arena host scope vs per-process
# semaphores; ASSERTS the host-wide in-flight bound held), one JSON
# blob.
kvcache-fleet-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.kvcache_fleet_bench \
		--procs 6 --sessions 512 --turns 2 --admit-window 64 \
		--admission-ab --json

# KVCache scale bench (ISSUE 20): >= 100k live sessions, zipf tenant
# skew over sharded admission, ring data plane; replay-time/p99 curves
# vs session count plus the ledger-compaction A/B with a concurrent
# writer (gates: zero wrong bytes, zero lost keys, >= 5x faster replay
# at equal history depth).
kvcache-scale-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.kvcache_scale_bench --json

# CI-sized: short zipf storm + one forced compaction cycle; same
# correctness gates (zero wrong bytes, bounded replay), timing gate off.
kvcache-scale-smoke:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.kvcache_scale_bench \
		--smoke --json

# Ring-vs-rpc data plane A/B (ISSUE 12): 4 KiB random reads at qd64
# through the USRBIO shm ring, rpc batch path vs the registered-arena
# ring data plane; median-of-3 trials per plane, one JSON blob
# (acceptance: ring >= 2x rpc IOPS).
usrbio-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.usrbio_bench --data-plane-ab \
		--block-size 4096 --depth 64 --seconds 5 --json

# Repair drill (ISSUE 9): kill one node under live first-k read traffic,
# A/B full-k vs reduced-read (LRC sub-shard) rebuild on identical damage,
# paced and unpaced; headline = survivor bytes moved per lost byte ratio
# (target < 0.5) + foreground p99 per cell, one JSON blob.
repair-drill:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.repair_drill_bench \
		--stripes 12 --chunk-size 65536 --repair-mode both --json

# Mixed-workload soak (ISSUE 13): six drivers (zipf dataloader on rpc
# AND ring planes, EC checkpoint cycles, KVCache churn under eviction,
# metadata scans, mini GraySort) against one live 5-node fabric for
# 75 s per cell, faults OFF then ON (straggler, node crash + empty
# restart, disk bit-rot).  Grades Jain fairness, zero-wrong-bytes, and
# per-window progress; exits non-zero on any gate failure.  Minutes.
soak:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.soak_bench \
		--config configs/soak.toml --cells both --json

# ~20 s harness proof: 3 workloads, 1 straggler fault, same gates.
soak-smoke:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.soak_bench \
		--config configs/soak_smoke.toml --cells on --json

# Cluster health plane end-to-end (ISSUE 14): monitor + mgmtd + 3
# storage nodes under live reads; injects a 10 ms straggler, asserts it
# shows flagged in the mgmtd-pulled scorecard within one rollup window
# and clears after the fault lifts.  ~10 s; exits non-zero on a miss.
health-smoke:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.health_smoke

# Scorecard-priors A/B (ISSUE 14): cold-client first-read p99 under a
# known 10 ms straggler, priors on vs off (target >= 30% better), plus
# the steady-state p50 overhead guard (within 3% of plane-off).
health-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.health_bench --json

# Rebalance drill (ISSUE 15): node add + destination flap + graceful
# drain against a live cluster serving write-pipeline writes and first-k
# EC reads, A/B'd against an identical no-rebalance cell.  Gates: zero
# wrong bytes, zero foreground errors, drill p50 <= 1.3x baseline,
# rebalance bytes within the token-bucket budget, solver diff empty at
# the end.  Exits non-zero on any miss; one JSON blob.
rebalance-drill:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.rebalance_drill_bench --json

# ~1 min CI-sized drill: same storm, same gates, shorter windows.
rebalance-smoke:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.rebalance_drill_bench \
		--smoke --json

# KV data distributor A/B: mdtest-style metadata storm over bandwidth-
# capped WAL volumes; static vs distributor-on vs operator-presplit,
# plus kill/restart drills at both surgery kill-points.  Gates: steady
# throughput >= 1.5x static, p99 <= 1.2x presplit, zero lost/wrong on
# full read-back, monotonic map, drills converge.
kv-distributor-bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.kv_distributor_bench --json

# CI-sized: correctness gates only (auto-split, read-back, drills).
kv-distributor-smoke:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.kv_distributor_bench \
		--smoke --json

# The served path end to end on one TPU (fails without one): run it on
# the chip machine, never on the CPU box.
chip-smoke:
	$(PY) chip_smoke.py

# The CI gate (reference: .github/workflows/build.yml — deps -> build ->
# test): native build, the full suite in ONE pass, both sanitizer
# passes.  The r4
# deselect+retry loop for the two "flaky" tests is GONE: the flake was
# root-caused (r5) to DevCluster._wait_port's 20 s hang-detector firing
# on slow child startup under load, plus fixed sleeps racing the
# heartbeat timeout — both replaced with event-driven waits.
ci:
	$(MAKE) lint
	$(PY) -m t3fs.native.build
	$(PY) -m pytest tests/ -x -q
	$(MAKE) sanitize
	@echo "ci: green"

sanitize: sanitize-thread sanitize-address
	@echo "sanitize: both passes clean"

sanitize-thread:
	T3FS_SANITIZE=thread $(PY) -m t3fs.native.build
	T3FS_SANITIZE=thread LD_PRELOAD=$(TSAN_RT) \
	  TSAN_OPTIONS="suppressions=$(CURDIR)/t3fs/native/tsan_suppressions.txt halt_on_error=1 report_signal_unsafe=0" \
	  $(PY) -m pytest $(SAN_TESTS) $(SAN_FILTER) -x -q

sanitize-address:
	T3FS_SANITIZE=address $(PY) -m t3fs.native.build
	T3FS_SANITIZE=address LD_PRELOAD=$(ASAN_RT) \
	  ASAN_OPTIONS="detect_leaks=0 verify_asan_link_order=0 halt_on_error=1" \
	  $(PY) -m pytest $(SAN_TESTS) $(SAN_FILTER) -x -q
