# Developer entry points. The container bakes the jax toolchain; no
# pip installs happen here.

PY := PYTHONPATH=src python

.PHONY: test test-slow bench-quick bench serve-smoke storage-smoke \
	skew-smoke chaos-smoke compress-smoke hypercube-smoke obs-smoke \
	cost-smoke ci

# fast tier: everything except the @slow tests (multi-device
# subprocesses, hypothesis sweeps) — those run in the second tier
test:
	$(PY) -m pytest -x -q -m "not slow"

# second tier: the differential property suite + distributed
# subprocess tests
test-slow:
	$(PY) -m pytest -x -q -m slow

# CI gate: both test tiers plus the quick benchmark smoke plus the
# serving, storage and skew smokes. The packed exchange's elision and
# correctness are gated by tests/test_dist.py in the slow tier.
# serve-smoke asserts the plan-cache warm path performs ZERO jax
# retracing (codegen.TRACE_STATS) and that cross-assignment CSE
# evaluates a shared join subplan exactly once.
# storage-smoke writes a dataset, reopens it, asserts query parity with
# the in-memory path, >=1 zone-map chunk skipped on a selective N.Param
# predicate, and zero warm retraces while chunk selection changes.
# skew-smoke drives the automatic skew pipeline end to end (persisted
# sketch -> table_stats -> SkewJoinP -> distributed execution):
# parity at every Zipf point, auto == plain plan at uniform, bounded
# measured partition imbalance + >=1.3x shuffled-row cut at high Zipf,
# and zero warm retraces across two different heavy-key sets (both the
# raw DistRunner rebind and the QueryService skew_hints path).
# chaos-smoke serves a request stream through the ServingRuntime under
# the seeded fault schedule (DESIGN.md "Fault model and recovery") and
# gates on: >=1 injection of every fault class, zero crashes, answers
# bit-for-bit identical to the fault-free run for all non-shed
# requests, and a simulated restart warm-replaying the persisted plan
# manifest with zero retraces (codegen.TRACE_STATS).
# compress-smoke gates the compressed-chunk tier (DESIGN.md "Compressed
# chunks and morsel streaming"): >=2x compression on label columns,
# bit-for-bit decode parity with raw storage, zone-map chunk skipping
# that never pays a decode, and a >=4-morsel out-of-core streamed query
# matching the one-shot result with zero warm retraces.
# hypercube-smoke gates the one-round multiway join (DESIGN.md
# "HyperCube exchange"): a 3-relation Zipf-2.0 chain on 8 virtual
# devices with parity vs the interpreter, STRICTLY fewer collectives
# than the binary cascade, receive-load imbalance <= 2.0, and zero
# retraces when the warm plan serves a new heavy-key set.
# obs-smoke gates the telemetry stack (DESIGN.md "Telemetry and
# EXPLAIN ANALYZE"): stored-dataset serving with the tracer ON keeps
# zero warm retraces while the trace tree carries
# query.execute/compile/decode spans; latency p50 <= p95 <= p99, all
# finite; a disabled span() costs < ~2us/call; observed rows persist
# through StatsFeedback into the dataset footer and round-trip as
# TableStats.effective_rows; and on 8 virtual devices EXPLAIN ANALYZE
# renders a SkewJoin with shipped rows + receive-load imbalance and
# the trace tree contains exchange spans from the shard_map region.
# cost-smoke gates the cost-based optimizer (DESIGN.md "Cost-based
# planning"): a Zipf-2.0 3-relation chain on 8 virtual devices whose
# program-written join order is the worst order — parity both modes,
# the costed order ships STRICTLY fewer rows over the wire, warm
# QueryService calls stay zero-retrace with estimates in the cache
# entry, and one EXPLAIN ANALYZE feedback round lands max Q-error <= 4.
ci: test test-slow bench-quick serve-smoke storage-smoke skew-smoke \
	chaos-smoke compress-smoke hypercube-smoke obs-smoke cost-smoke

serve-smoke:
	$(PY) -m benchmarks.serving --smoke

chaos-smoke:
	$(PY) -m benchmarks.serving --chaos

storage-smoke:
	$(PY) -m benchmarks.storage --smoke

skew-smoke:
	$(PY) -m benchmarks.skew --smoke

compress-smoke:
	$(PY) -m benchmarks.storage --compress-smoke

hypercube-smoke:
	$(PY) -m benchmarks.hypercube --smoke

obs-smoke:
	$(PY) -m benchmarks.obs --smoke

cost-smoke:
	$(PY) -m benchmarks.cost --smoke

# CPU-friendly perf smoke: runs every benchmark section except the
# 8-virtual-device skew subprocess, fails on any Python exception, and
# writes BENCH_<timestamp>.json (the cross-PR perf trajectory file).
bench-quick:
	$(PY) -m benchmarks.run --quick --skip-skew

bench:
	$(PY) -m benchmarks.run
