# victorialogs_tpu build/test entry points.
#
# The native host core (victorialogs_tpu/native/libvlnative-<hash>.so, keyed
# on a hash of vlnative.cpp) also builds itself on first import; this
# target is for explicit/offline builds.

NATIVE_DIR := victorialogs_tpu/native

.PHONY: all native test race lint check help bench bench-bloom \
	bench-pipeline bench-cluster-obs bench-concurrent bench-emit \
	bench-explain bench-faults bench-ingest bench-journal \
	bench-standing bench-wire clean

all: native

help:
	@echo "victorialogs_tpu targets:"
	@echo "  make check    pre-push gate: lint + tier-1 suite + race smoke"
	@echo "  make lint     vlint static analysis + env-table drift + compile sweep"
	@echo "  make test     full test suite (fail-fast)"
	@echo "  make race     concurrency suites under both runtime sanitizers"
	@echo "  make native   build the native host core explicitly"
	@echo "  make bench-*  recorded performance rounds (see PERF.md)"

native:
	python -c "from victorialogs_tpu import native; import sys; sys.exit(not native.available())"

test:
	python -m pytest tests/ -x -q

# the concurrency suites under BOTH runtime sanitizers: the lock-order
# shim (VLINT_LOCK_ORDER=1, cross-validated against the static graph at
# session end) and the vlsan end-of-test invariant sweep (on by
# default; VLSAN=0 kills it).  This is the ROADMAP standing gate's
# "run periodically" instruction as one command.
race:
	VLINT_LOCK_ORDER=1 python -m pytest tests/test_storage_races.py \
		tests/test_ingest_mt.py tests/test_concurrent_ingest.py \
		tests/test_sched.py tests/test_chaos.py -q

# repo-native static analysis (tools/vlint/README.md) + the README
# env-table drift gate (generated from victorialogs_tpu/config.py) +
# a compile sweep.  Fails on any finding not in
# tools/vlint/baseline.json (which stays EMPTY: fix or annotate).
lint:
	python -m tools.vlint victorialogs_tpu/
	python -m tools.vlint --check-env-table
	python -m compileall -q victorialogs_tpu tools tests

# the single pre-push gate: static analysis (including the v3
# interprocedural graph passes), the tier-1 suite on the CPU backend,
# and a race-suite smoke under both runtime sanitizers.  Green here ==
# safe to push; `make race` remains the full concurrency soak.
check: lint
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'
	VLINT_LOCK_ORDER=1 python -m pytest tests/test_storage_races.py -q

bench:
	python bench.py

# prune throughput: per-block bloom loop vs batched plane probe at 10k
# blocks (filter-index subsystem; fails under 5x — see PERF.md)
bench-bloom:
	python tools/bench_bloom.py

# many-small-parts async pipeline: serial vs windowed vs packed on the
# jax-CPU backend (fails under 4x dispatch cut / 1.5x wall — PERF.md)
bench-pipeline:
	python tools/bench_pipeline.py --json BENCH_pipeline.json

# same bench + the concurrent-clients mode (8 threaded clients, p50/p99
# + aggregate rows/s, vl_active_queries sampled mid-run), the tenant-mix
# fairness round (2 heavy + 4 light clients, unmanaged VL_SCHED=0 vs
# managed: light p99 must not regress, aggregate within bounds) and the
# HTTP shed probe (capped tenant sheds 429 + Retry-After + counters) —
# PERF.md round 8
bench-concurrent:
	python tools/bench_pipeline.py --clients 8 --json BENCH_pipeline.json

# emit phase: per-row dicts + json.dumps vs the columnar native NDJSON
# path on the 32x2048 bench shape (fails under 2x — PERF.md)
bench-emit:
	python tools/bench_emit.py --json BENCH_emit.json

# self-telemetry journal overhead: bench-pipeline rows workload with
# the journal off (structurally zero, asserted) vs on (one query_done
# event per query, ingested into the same storage); fails past the
# PR 4 trace-overhead bound (10% + 2 ms) — PERF.md
bench-journal:
	python tools/bench_journal.py --json BENCH_journal.json

# query EXPLAIN + cost-model accountability: the continuous plan-time
# pricing pass must stay within the PR 4 trace-overhead bound
# (10% + 2 ms), explain=1 must be O(headers) (>=20x faster than
# execution, zero device dispatches), and the median cost-model
# relative error (duration/bytes) must stay under the recorded bounds
# — PERF.md round 11
bench-explain:
	python tools/bench_explain.py --json BENCH_explain.json

# cluster wire protocol: typed columnar frames vs legacy JSON frames on
# a real 2-node scatter-gather; asserts bit-identical hit sets, >=2x
# frontend rows/s, and zero typed frames under VL_WIRE_TYPED=0 —
# PERF.md round 10
bench-wire:
	python tools/bench_wire.py --json BENCH_wire.json

# network-chaos round on a real 3-node cluster + fault proxy: strict
# failure bounded by the deadline (refuse AND hang), partial-results
# exactness, breaker recovery latency, and the ingest-outage
# spool-replay zero-loss assertion — recorded into BENCH_faults.json
# (PERF.md chaos round)
bench-faults:
	python tools/bench_faults.py --json BENCH_faults.json

# cluster observability plane on a real 3-node cluster: rollup overhead
# (<=1.10x concurrent p50) + the rollup-vs-node-sum differential,
# federated active_queries completeness with parent_qid linkage, and
# cancel-propagation kill latency vs the disconnect-probe path —
# recorded into BENCH_cluster_obs.json (PERF.md round)
bench-cluster-obs:
	python tools/bench_cluster_obs.py --json BENCH_cluster_obs.json

# standing queries + per-part result cache: repeated-query round (2nd
# run must submit >=5x fewer dispatches, hit ratio >= 0.9, cached
# parts priced ~0 in EXPLAIN, post-flush run re-dispatches only the
# head part) and the 100-subscriber standing-panel round (ONE
# evaluation per refresh, every subscriber's delta == a fresh full
# evaluation) — PERF.md round
bench-standing:
	python tools/bench_standing.py --json BENCH_standing.json

# typed ingest wire format i1 end-to-end: library hot path (+4-core
# Amdahl projection), i1 codec encode/decode rates, typed-vs-legacy
# insert hop (>=3x, zero per-row json.loads pinned by counters),
# spool-replay chaos (zero rows lost, zero re-encodes), and the
# typed-vs-legacy stored-data differential — PERF.md round 16 — plus
# the sharded block-build round: columnar arena encode vs the list
# path (>=1.5x) and serial-vs-sharded insert hop against the 352k
# baseline (>=2x asserted only when >=2 cores) — PERF.md round 18
bench-ingest:
	python tools/bench_ingest.py --json BENCH_ingest.json

clean:
	rm -f $(NATIVE_DIR)/libvlnative*.so
