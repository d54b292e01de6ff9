# Repeatable entry points (VERDICT r4 #8: the randomized-evidence ritual
# must be a one-liner anyone can repeat).

.PHONY: test soak bench dryrun record-corpus historian-smoke \
	summarize-smoke trace-smoke pipeline-smoke \
	paged-smoke catchup-smoke obs-smoke ingest-smoke e2e-smoke \
	mega-smoke fleet-smoke bench-trend \
	lint-analysis \
	lint-changed lint-races lint-placement layer-check check

test:
	python -m pytest tests/ -q

# fluidlint: the AST + whole-program dataflow analyzer
# (fluidframework_tpu/analysis/, docs/static_analysis.md). Exits non-zero
# on any violation that is neither suppressed inline nor baselined; the
# last output line is the machine-readable trend summary
# {"violations": N, "baselined": M}. Incremental runs ride the
# fingerprint cache (.fluidlint_cache.json); the analyzer perf record
# (wall time, cache hits, counts) lands in BENCH_LINT_LAST.json so the
# bench tooling can stamp the trend.
lint-analysis:
	python -m fluidframework_tpu.analysis fluidframework_tpu/ \
		--bench-json BENCH_LINT_LAST.json

# Fast pre-commit scope: report only on files git sees as changed
# (worktree vs HEAD + untracked) while the whole-program layer still
# spans the package, so a donation-signature edit still re-checks its
# callers' files when they are in the diff. Race findings additionally
# re-report on every file sharing a thread root's reach with a changed
# file (locksets are whole-program).
lint-changed:
	python -m fluidframework_tpu.analysis fluidframework_tpu/ \
		--changed-only

# fluidlint v3's lockset race detector, focused on the server
# concurrency tier (docs/static_analysis.md "fluidlint v3"): thread-root
# discovery + whole-program held-lockset propagation behind
# SHARED_STATE_NO_LOCK / ATOMICITY_CHECK_THEN_ACT /
# LOCK_ORDER_INVERSION / SIGNAL_WITHOUT_LOCK. Exits non-zero on any
# unbaselined finding; the full rule set (and the same race rules) also
# runs under lint-analysis — this is the focused gate and its trend
# line (race_rules_wall_ms rides the lint bench record).
lint-races:
	python -m fluidframework_tpu.analysis fluidframework_tpu/server \
		fluidframework_tpu/telemetry \
		--rule SHARED_STATE_NO_LOCK --rule ATOMICITY_CHECK_THEN_ACT \
		--rule LOCK_ORDER_INVERSION --rule SIGNAL_WITHOUT_LOCK

# fluidlint v4's placement & sharding lattice, focused on the mesh tier
# (docs/static_analysis.md "fluidlint v4"): per-binding placement
# dataflow over mergetree/server/parallel behind MESH_DONATION_GATE /
# UNSPECCED_POOL / PSPEC_MISMATCH / HOST_READ_OF_SHARDED /
# SHARD_AXIS_DRIFT, proven against the partition-rule table
# (mergetree/partition_rules.py) that the runtime actually places with
# (testing/shardcheck.py verifies the same table at dispatch time).
# Exits non-zero on any unbaselined finding; the full rule set also
# runs under lint-analysis — this is the focused gate and its trend
# line (placement_rules_wall_ms rides the lint bench record).
lint-placement:
	python -m fluidframework_tpu.analysis fluidframework_tpu/mergetree \
		fluidframework_tpu/server fluidframework_tpu/parallel \
		--rule MESH_DONATION_GATE --rule UNSPECCED_POOL \
		--rule PSPEC_MISMATCH --rule HOST_READ_OF_SHARDED \
		--rule SHARD_AXIS_DRIFT

# Machine-enforced layering + import-time cycle detection
# (tools/layer_check.py): the dependency-DAG gate the reference repo
# runs as a build step, promoted from test-only to a first-class
# `make check` stage. Cycles are hard failures with the offending edge
# printed.
layer-check:
	python -m fluidframework_tpu.tools.layer_check

# CPU smoke of the incremental summarize path: tiny batch, 100%- vs
# 1%-dirty fused extraction, narrow-wire byte drop + bit-identity, and
# the MergeLaneStore blob cache. Exits non-zero if any acceptance
# property regresses; prints one JSON line with the backend stamped.
summarize-smoke:
	JAX_PLATFORMS=cpu python bench.py summarize-smoke

# CPU smoke of the tracing subsystem (docs/observability.md): a short
# ingest burst at sample=1 must yield a complete submit->broadcast
# trace carrying every named serving sub-span, the Prometheus
# exposition must parse with monotone histogram buckets, the serving-
# flush SLO verdict must appear in /health, and tracing overhead vs
# tracing-off on the same burst must stay under 2% (stamped into the
# record as trace_overhead_pct).
trace-smoke:
	JAX_PLATFORMS=cpu python bench.py trace-smoke

# CPU smoke of the deep-pipelined serving path (docs/serving_pipeline.md):
# identical raw-wire waves through a synchronous and a pipelined
# sequencer must emit a BIT-IDENTICAL stream with identical lane state,
# the flushes' megakernel rings must really defer to the next flush,
# and warm steady-state ingest must clear 1.3x the pinned BENCH_r05 CPU
# figure.
pipeline-smoke:
	JAX_PLATFORMS=cpu python bench.py pipeline-smoke

# CPU smoke of paged lane memory (docs/paged_memory.md): every document
# of the storm-doc ragged fleet must read back exactly the text its ops
# wrote with no rescue or overflow drop, and the warm gather-by-page-id
# ragged fleet must clear 1.5x the pinned BENCH_r07 bucketed figure
# (9,687 ops/s) at the same shapes.
paged-smoke:
	JAX_PLATFORMS=cpu python bench.py paged-smoke

# CPU smoke of the million-reader read path (docs/read_path.md): a
# client catching up via `summary + delta` (artifact adoption) must be
# content- and protocol-identical to scalar tail replay on a ragged
# contended fleet, warm per-client catch-up p50 must stay under 100 ms,
# one refresh epoch must cost <= 2 batched device dispatches with ZERO
# additional dispatches per connecting client, the int16 narrow delta
# wire must actually narrow, and sharded broadcast fan-out must deliver
# a hot document to every subscriber in per-doc order.
catchup-smoke:
	JAX_PLATFORMS=cpu python bench.py catchup-smoke

# CPU smoke of the device telemetry planes + compile observatory
# (docs/observability.md v2): telemetry-on serving must be BIT-IDENTICAL
# to telemetry-off (emit stream + lane planes), the stats plane must ride
# the existing readback (0 extra dispatches per window/burst), device-
# counted op totals must reconcile EXACTLY with the host-side counts,
# stats overhead must stay < 2% on the warm 512-doc serving shape, and the
# compile ledger (per-symbol compiles + cumulative compile ms) must be
# stamped top-level in BENCH_OBS_LAST.json.
obs-smoke:
	JAX_PLATFORMS=cpu python bench.py obs-smoke

# Per-metric trajectory over the committed BENCH_r*.json history; exits
# nonzero on a >20% regression vs the best comparable-host record
# (TPU records only — CPU hosts are not comparable to each
# other, the r05/r06 pin lesson). Report-only inside `make check`.
bench-trend:
	python bench.py trend

# Virtual-clocked open-loop overload harness (docs/overload.md): at 2x
# sustained overload the admission controller must shed instead of
# queueing unboundedly (peak queue bounded), hold the admitted-op flush
# SLO, keep goodput >= 80% of capacity, ride a stall crunch through
# SHED into DEGRADE and back to ACCEPT within 5s, and reproduce every
# fault-injection scenario bit-identically from its seed.
overload-smoke:
	JAX_PLATFORMS=cpu python bench.py overload-smoke

# Open-loop load generator over the sharded multi-partition ingest tier
# (docs/ingest_sharding.md): 4 logical partitions must compose — the
# per-partition busy-time service rates must sum to >= 2.5x the paired
# single-partition run (the artifact that lets per-process ops/s compose
# toward the ROADMAP's 1M/s story) — with every document's emit stream
# ORDER-identical to the single-partition path, partition queues bounded
# under 2x open-loop overload, sibling partitions unstarved when one
# partition runs hot, and latency percentiles + per-partition goodput
# stamped into BENCH_INGEST_LAST.json.
ingest-smoke:
	JAX_PLATFORMS=cpu python bench.py ingest-smoke

# Fleet-scale capacity soak over the WHOLE pipeline (docs/capacity.md):
# a seeded open-loop workload (Poisson writers over a Zipf fleet +
# catch-up readers) drives sharded ingest + sharded broadcast + scribe
# + the read path at once, chaos (partition crashes + reconnect
# avalanches) inside the measured envelope. The grader binary-searches
# the sustained admitted rate at which the admission ladder stays
# <= THROTTLE and the flush/reader SLOs hold, attributes the binding
# bottleneck per tier, and requires the capacity point to reproduce
# bit-identically run-twice. Stamps BENCH_E2E_LAST.json (the record
# `bench.py trend` gates between comparable hosts).
e2e-smoke:
	JAX_PLATFORMS=cpu python bench.py e2e-smoke

# The R10 serving megakernel (docs/serving_pipeline.md): a ragged
# contended fleet through the paged native pump must emit
# ORDER-identically to the per-window scan path, amortize dispatch to
# < 0.25 per served fast window with zero lowering fallbacks, and
# clear 2x the r08 paged pin min()'d against a paired in-process run
# of the r08 object-path serving architecture (the host-drift rule).
# Stamps BENCH_MEGA_LAST.json (gated by `bench.py trend`).
mega-smoke:
	JAX_PLATFORMS=cpu python bench.py mega-smoke

# The fleet observability surface (docs/observability.md v3): a real
# broker + deli-worker topology (separate OS processes) scraped by the
# FleetObservatory must yield /fleet/trace timelines whose spans come
# from BOTH processes (wire-propagated trace contexts) with process
# identity on every span, the worker's scraped broadcast-edge lag must
# equal the final persisted sequence number exactly, a chaos-on fleet
# soak's watermark marks must be bit-identical run twice with ingest
# lag drained to zero, and observability-on (sample=1 + a 20 Hz
# scraper) overhead on the live local pipeline must stay under 2%.
# Stamps BENCH_FLEET_LAST.json (folded into `bench.py trend`).
fleet-smoke:
	JAX_PLATFORMS=cpu python bench.py fleet-smoke

# The pre-merge gate: layering/cycles + static analysis (incl. the
# focused race and placement gates) + the summarize/trace/pipeline/paged/catchup/
# overload/obs/ingest/e2e/mega/fleet smokes + the bench trend
# (report-only here) + the full test suite.
check: layer-check lint-analysis lint-races lint-placement \
		summarize-smoke trace-smoke \
		pipeline-smoke paged-smoke catchup-smoke \
		overload-smoke obs-smoke ingest-smoke e2e-smoke mega-smoke \
		fleet-smoke test
	python bench.py trend --report-only

# The round-end randomized-evidence ritual: 50-trial soaks over every
# differential surface (bulk catch-up, serving fast path, matrix/
# directory lanes, interval catch-up) + the chaos seed sweep. Run before
# the final commit of a round; record the counts in the round notes.
soak:
	SOAK=1 SOAK_TRIALS=50 CHAOS_SWEEP=1 python -m pytest \
		tests/test_soak.py tests/test_chaos.py -q

bench:
	python bench.py

dryrun:
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

record-corpus:
	python -m fluidframework_tpu.testing.record_corpus

# Spawn the local topology with the historian cache tier in front of git
# storage and assert a reload serves from cache (hit rate > 0), commits
# invalidate, and a dead historian degrades to direct GitStore reads.
historian-smoke:
	JAX_PLATFORMS=cpu python -m fluidframework_tpu.testing.historian_smoke
