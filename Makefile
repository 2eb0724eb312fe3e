# Parity with the reference's 3-line Makefile (`make test` ran
# `mpirun -n 2 py.test -s`); here multi-chip is an 8-device virtual CPU
# mesh set up by tests/conftest.py — no cluster, no MPI.

# Default test path includes the bucketing parity + launch-count suite
# (tests/test_bucketing.py; `make bucket-smoke` runs just that gate),
# the gradient-lineage completeness gate (`make trace-smoke`), and the
# parameter-serving read-tier gate (`make read-smoke`).
test:
	python -m pytest tests/ -q
	$(MAKE) analyze
	$(MAKE) trace-smoke
	$(MAKE) read-smoke
	$(MAKE) read-native-smoke
	$(MAKE) agg-smoke
	$(MAKE) native-smoke
	$(MAKE) native-asan
	$(MAKE) obs-smoke
	$(MAKE) tree-smoke
	$(MAKE) control-smoke
	$(MAKE) topo-smoke
	$(MAKE) whatif-smoke
	$(MAKE) fresh-smoke
	$(MAKE) hop-smoke

# Flat-bucket aggregation gate: bit-exact parity of bucketed vs per-leaf
# steps (identity/cast codecs, both topologies) plus the CPU-backend
# launch-count assertion (bucketed step lowers to >=5x fewer collective
# ops than per-leaf), and the serialization wire-format tests. Wrapped
# by bench_gate: each run appends a timed row to
# benchmarks/results/bucket_smoke.jsonl and is gated against the median
# of previous runs (noise-tolerant: 100% wall tolerance).
bucket-smoke:
	python tools/bench_gate.py \
		--run "python -m pytest tests/test_bucketing.py tests/test_utils.py -q" \
		--tag bucket_smoke --out benchmarks/results/bucket_smoke.jsonl

# Recorder-overhead gate: short CPU trainer, recorder off vs on in
# interleaved blocks; writes smoke.jsonl + report.txt and FAILS if the
# enabled recorder costs >5% of the disabled step time
telemetry-smoke:
	python tools/telemetry_smoke.py

# Resilience gate (in the default `make test` path via
# tests/test_resilience.py; this target is the full double-run): a
# supervised 2-worker async job under a canned fault plan (worker crash,
# server crash, corrupted frame, drop/delay/duplicate) must complete
# with the loss improved, all recovery counters nonzero in /metrics, and
# an identical injected-event log on replay of the same plan + seed
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/chaos_smoke.jsonl \
		--metric 'chaos_smoke.wall_total_s:lower:1.5' \
		--metric 'chaos_smoke.loss_final:lower:0.75'

# Online-diagnosis gate: a 2-worker async run with injected delay faults
# on worker 1 must be ATTRIBUTED by the health layer — /health + ps_top
# name worker 1 slow and wire-bound, ps_worker_anomaly_total and a
# nonzero ps_staleness_p95 land in /metrics — and bench_gate.py must
# pass a self-comparison and fail a doctored 20% regression. The second
# command re-asserts the standing <=5% recorder-overhead budget.
diag-smoke:
	JAX_PLATFORMS=cpu python tools/diag_smoke.py
	python tools/telemetry_smoke.py

# Gradient-lineage gate (in the default `make test` path): a 2-worker
# async run with lineage armed must account for EVERY consumed push
# with a complete trace-ID row, the exact staleness rebuilt from the
# lineage must equal the serve loop's own accounting, the merged
# Chrome trace must contain cross-process flow arrows (worker push ->
# server consume, clock-skew corrected), and the lineage bookkeeping
# must fit the standing <=5% telemetry budget (the second command
# re-asserts the recorder half of that budget). Appends a bench_gate
# trajectory row to benchmarks/results/trace_smoke.jsonl.
trace-smoke:
	JAX_PLATFORMS=cpu python tools/trace_smoke.py
	python tools/telemetry_smoke.py

# Numerics gate (beside diag-smoke; tests/test_numerics.py covers the
# same paths in the default `make test` run): a NaN-injecting worker
# must be quarantined — exactly that worker — with a parseable
# postmortem on disk, online codec-fidelity probes must report nonzero
# rel-error for sign and ~0 for identity, and the fused gradient
# statistics must re-pass the <=5% telemetry-overhead budget
# (tools/telemetry_smoke.py --numerics runs inside the smoke).
numerics-smoke:
	JAX_PLATFORMS=cpu python tools/numerics_smoke.py

# Parameter-serving read-tier gate (in the default `make test` path):
# a burst of identical-version reads must coalesce onto ONE delta
# encode, the admission queue must shed past its configured depth with
# every reader completing via retry-after, delta-tracked state must be
# bit-exact vs a full read, an aged-out ring base must fall back to a
# full snapshot, and the armed snapshot ring must cost <=5% of the
# transport publish. Appends a bench_gate trajectory row to
# benchmarks/results/read_smoke.jsonl; the second command re-asserts
# the standing <=5% recorder-overhead budget with the tier armed.
read-smoke:
	JAX_PLATFORMS=cpu python tools/read_smoke.py
	python tools/telemetry_smoke.py

# Native read-plane gate (in the default `make test` path): the C++
# epoll tier must build + arm, answer with reply byte streams identical
# to the Python selectors loop (full/delta/not-modified), serve a
# concurrent full-read workload with a non-regressing p99 vs the Python
# loop (trajectory-gated ratio), shed at admission depth 1 with every
# reader completing via retry-after, and re-serve bit-exact bytes
# through a FollowerLoop replica hop with lag 0 and nonzero relay
# accounting. Skips cleanly without a toolchain / with PS_NO_NATIVE.
# Appends a bench_gate trajectory row to
# benchmarks/results/read_native_smoke.jsonl.
read-native-smoke:
	JAX_PLATFORMS=cpu python tools/read_native_smoke.py

# Homomorphic-aggregation gate (in the default `make test` path): a
# 2-process shm sync-barrier run over the top-k wire must fold every
# push into the compressed accumulator and decode exactly ONCE per
# published version (decodes_per_publish == 1 in metrics AND /health),
# the wire aggregate must equal decode-sum for the exact algebra,
# agg=off must really keep the legacy path, and agg_bench --quick's
# per-push cost gates must hold (sparse fold flat in model size,
# integer per-push accumulate beating a per-push decode). Appends a
# bench_gate trajectory row to benchmarks/results/agg_smoke.jsonl.
agg-smoke:
	JAX_PLATFORMS=cpu python tools/agg_smoke.py

# Hierarchical-aggregation gate (in the default `make test` path): a
# real 2-group/6-worker tree with a leader crash injected mid-fold must
# account EVERY worker push through every hop (composed at the root —
# trace IDs surviving the leader re-encode — or positively logged lost
# with the dead leader), fold with one decode per published version at
# the root and zero per-push decodes at leaders, recover via
# direct-to-root fallback + pinned-port respawn + rejoin, and pass
# tree_bench --quick's root-ingest flatness gates (8->64 workers at
# nonzero TPS_WAN_RTT_MS: tree <=1.3x vs star >=6x bytes/publish).
# Appends a bench_gate trajectory row to
# benchmarks/results/tree_smoke.jsonl.
tree-smoke:
	JAX_PLATFORMS=cpu python tools/tree_smoke.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/tree_smoke.jsonl \
		--metric 'tree_smoke.wall_total_s:lower:1.5' \
		--metric 'tree_smoke.decodes_per_publish:lower:0.01'

# Full-scale star-vs-tree root-ingest bench (the tree-smoke quick gates
# at measurement scale); rows + a bench_gate-gated trajectory in
# benchmarks/results/tree_bench.jsonl.
tree-bench:
	JAX_PLATFORMS=cpu python benchmarks/tree_bench.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/tree_bench.jsonl \
		--metric 'tree_bench.tree_growth_x:lower:0.3' \
		--metric 'tree_bench.star_growth_x:higher:0.3' \
		--metric 'tree_bench.tree_root_cpu_ms_per_publish_64w:lower:1.0'

# Full per-push server-cost bench over 1x/8x models (the agg-smoke
# quick gates at measurement scale); rows + a bench_gate-gated
# trajectory in benchmarks/results/agg_bench.jsonl.
agg-bench:
	JAX_PLATFORMS=cpu python benchmarks/agg_bench.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/agg_bench.jsonl \
		--metric 'agg_bench.sparse_flat_ratio:lower:1.0' \
		--metric 'agg_bench.int_speedup_min_x:higher:0.5' \
		--metric 'agg_bench.native_fold_speedup_int8_x:higher:0.5' \
		--metric 'agg_bench.native_push_speedup_topk_x:higher:0.5'

# Read-tier load bench: open-loop fleet of simulated readers — delta
# bytes economics (>=5x reduction gate), saturation sweeps through BOTH
# the Python selectors loop and the native C++ epoll tier (bounded
# served p99 past the admission limit on each; the native shed fraction
# at max load must not exceed the Python loop's), and a follower
# replica tree (1 root + 2 replicas serving 3x the reader population,
# replica lag settling <=2 versions). Full scale; `--quick` inside
# read-smoke-scale CI runs. Trajectory rows in
# benchmarks/results/read_bench.jsonl.
read-bench:
	JAX_PLATFORMS=cpu python benchmarks/read_bench.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/read_bench.jsonl \
		--metric 'read_bench.delta_reduction_x:higher:0.5' \
		--metric 'read_bench.p99_max_load_ms:lower:2.0' \
		--metric 'read_bench.native_p99_max_load_ms:lower:2.0' \
		--metric 'read_bench.tree_p99_ms:lower:2.0'

# needs a TPU (exits non-zero without one); on a machine with the chip
# run `python chip_smoke.py` first
bench:
	python bench.py

# Self-driving control-plane gate (in the default `make test` path): a
# canned straggler+NaN+overload run with the controller armed must
# downshift the codec identity->int8 mid-run through the wire-epoch
# handshake (zero frames lost on BOTH transports — in-flight old-epoch
# frames consumed, native TCP batch re-armed after retire), de-weight
# exactly the stale worker's pushes (AsySG-InCon LR scaling),
# quarantine then probation-readmit the NaN worker, and raise the
# read tier's admission depth until a pipelined reader storm completes
# shed-free. Every action row carries its triggering verdict,
# Controller.replay() over the persisted TSDB rows re-derives the
# sequence byte-identically, nothing flaps, and the controlled loss
# beats the same scenario uncontrolled — gated below via bench_gate
# (wall + loss ratio trajectory rows in
# benchmarks/results/control_smoke.jsonl).
control-smoke:
	JAX_PLATFORMS=cpu python tools/control_smoke.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/control_smoke.jsonl \
		--metric 'control_smoke.wall_total_s:lower:1.5' \
		--metric 'control_smoke.loss_ratio:lower:0.5'

# Structural-control gate (in the default `make test` path): topology
# as a control action, live. A slow_leader fold hotspot must be
# attributed (anatomy advisor + hot_hop), healed by a latched
# group_replan through run_tree's supervision lists (moved leaf
# repoints via control-topo.json, composed accounting exact across the
# transition), and the controlled round cadence must beat the same
# scenario left static. A seeded reader_storm against a pinned tiny
# admission depth must scale a serve_readonly replica OUT (fleet card
# registered, model served through the replica's own read port) and
# back IN once idle (card deregistered, verdict tier_idle). Zero
# flaps; Controller.replay re-derives the actions byte-identically.
# Gated below via bench_gate (wall + span-ratio trajectory rows in
# benchmarks/results/topo_smoke.jsonl).
topo-smoke:
	JAX_PLATFORMS=cpu python tools/topo_smoke.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/topo_smoke.jsonl \
		--metric 'topo_smoke.wall_total_s:lower:1.5' \
		--metric 'topo_smoke.span_ratio:lower:0.5'

# Read-path freshness gate (in the default `make test` path): a star
# run with a live two-hop replica chain beside it. Healthy-phase edge
# delivery ages must stay under the gate; the seeded slow-follower
# fault must ramp the edge's age-of-information until the controller
# trips exactly ONE latched edge_age_burn scale-out (freshness evidence
# on the action row, byte-identical replay from TSDB rows), and a
# worker push trace ID must resolve through the freshness flow events
# to the wall age at which the edge served the containing version.
fresh-smoke:
	JAX_PLATFORMS=cpu python tools/fresh_smoke.py
	python tools/bench_gate.py \
		--trajectory benchmarks/results/fresh_smoke.jsonl \
		--metric 'fresh_smoke.wall_total_s:lower:1.5' \
		--metric 'fresh_smoke.healthy_age_p95_ms:lower:2.0'

# Round-anatomy what-if gate (in the default `make test` path): a
# 3-worker sync run with 200 ms injected into worker 1's WIRE stage
# (fault kind wire_delay — the sleep sits between the frame's
# send_wall stamp and the bytes traveling) must be named by the
# advisor: wire ranked #1, its debottleneck projection matching the
# measured A/B round-time improvement within ±30%, the offline
# reconstruction from persisted lineage rows agreeing with the live
# engine, and the armed anatomy+lineage bookkeeping within the
# standing ≤5% telemetry budget (the second command re-asserts the
# recorder half). Appends a bench_gate trajectory row to
# benchmarks/results/whatif_smoke.jsonl.
whatif-smoke:
	JAX_PLATFORMS=cpu python tools/whatif_smoke.py
	python tools/telemetry_smoke.py

# Hop-anatomy gate (in the default `make test` path): an A/B tree run
# with a known slow_leader fold widening asserting the hop timeline
# measures it within ±30%, serial attribution reproduces the measured
# round wall, the streaming-headroom projection replays byte-
# identically from persisted hop-*.jsonl rows, and the root-side hop
# bookkeeping stays within the ≤5% telemetry budget. Appends a
# bench_gate trajectory row to benchmarks/results/hop_smoke.jsonl.
hop-smoke:
	JAX_PLATFORMS=cpu python tools/hop_smoke.py

# Static-analysis gate (in the default `make test` path): analyze_smoke
# runs `python -m tools.psanalyze` on the tree (must be SILENT — the
# six rules: thread-affinity, cfg-schema, metrics-surface,
# codec-contract, abi-drift, sidecar-registry) and then proves each
# rule still fires on its seeded defect (plus pragma suppression and a
# caught ASan overflow). Appends a bench_gate trajectory row to
# benchmarks/results/analyze_smoke.jsonl gating analyze wall time.
analyze:
	python tools/analyze_smoke.py

# Sanitizer-hardened native builds (native-asan is in the default
# `make test` path; see tools/native_sanitize.py): each mode compiles
# all three libraries with the sanitizer into native/_build/<mode>/,
# runs the native lifecycle drivers (precise leak check — no
# interpreter to suppress around), and for asan/ubsan re-runs the
# tests/test_native_fold.py parity suite + live batched ingest with
# the runtime LD_PRELOADed and LSan armed (tools/lsan.supp). The TSan
# leg drives the tcpps pump + psqueue seqlock as instrumented
# executables (LD_PRELOADing libtsan under uninstrumented CPython
# reports interpreter false positives).
native-asan:
	python tools/native_sanitize.py --mode asan

native-ubsan:
	python tools/native_sanitize.py --mode ubsan

native-tsan:
	python tools/native_sanitize.py --mode tsan

# -ffp-contract=off: the wc_fold_* kernels may not fuse multiply+add
# into FMAs — bit-exact parity with the numpy fallback (enforced by
# tests/test_native_fold.py and the native-smoke gate) pins separate
# f32 rounding. utils/native.py passes the same flag when it builds
# these libraries on demand.
native:
	mkdir -p native/_build
	g++ -O3 -std=c++17 -ffp-contract=off -shared -fPIC -o native/_build/libwirecodec.so native/wirecodec.cpp -lrt
	g++ -O3 -std=c++17 -ffp-contract=off -shared -fPIC -o native/_build/libpsqueue.so native/psqueue.cpp -lrt
	g++ -O3 -std=c++17 -ffp-contract=off -shared -fPIC -o native/_build/libtcpps.so native/tcpps.cpp -lrt

# Observability-plane gate (in the default `make test` path): a fully
# armed 2-worker run (metrics history + continuous profiler + SLO
# watchdog + fleet registration) must answer windowed /history queries
# with monotone timestamps matching the exact lineage distributions,
# show the serve-loop frames in the flamegraph + nonzero native fold
# cycle counters, stay within the standing ≤5% telemetry budget with
# EVERYTHING armed, trip exactly one SLO burn verdict on an injected
# straggler (zero on the healthy run, replayable from the persisted
# history), and cover every live shard + the read tier + a restarted
# supervisor generation in one /fleet scrape. Appends a bench_gate
# trajectory row to benchmarks/results/obs_smoke.jsonl; the second
# command re-asserts the recorder half of the telemetry budget.
obs-smoke:
	JAX_PLATFORMS=cpu python tools/obs_smoke.py
	python tools/telemetry_smoke.py

# Native fast-path gate (in the default `make test` path): both
# libraries must build and load with the fold/batch entry points, every
# fold-family codec must be BIT-exact native-vs-numpy over real
# CodecWire rounds, a live TcpPSServer must drain framed pushes through
# the C++ batched ingest (and reason-count a corrupt frame), and the
# native int8 fold must beat the numpy fallback >=1.5x at 1M elements.
# Appends a bench_gate trajectory row to
# benchmarks/results/native_smoke.jsonl.
native-smoke:
	JAX_PLATFORMS=cpu python tools/native_smoke.py

# host-CPU protocol/convergence benches; each emits JSON lines for
# benchmarks/results/
bench-protocol:
	python benchmarks/async_bench.py --model resnet18 --workers 2 \
		--fast-steps 6 --slow-steps 2 --slow-ms 2000
	python benchmarks/wan_bench.py
	python benchmarks/staleness_bench.py
	python benchmarks/convergence_bench.py

.PHONY: test bench bench-protocol native telemetry-smoke bucket-smoke chaos-smoke diag-smoke numerics-smoke trace-smoke read-smoke read-native-smoke read-bench agg-smoke agg-bench native-smoke obs-smoke tree-smoke tree-bench analyze native-asan native-ubsan native-tsan control-smoke topo-smoke whatif-smoke fresh-smoke hop-smoke
