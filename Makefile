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
# ops than per-leaf), and the serialization wire-format tests.
bucket-smoke:
	python -m pytest tests/test_bucketing.py tests/test_utils.py -q

# Resilience gate (in the default `make test` path via
# tests/test_resilience.py; this target is the full double-run): a
# supervised 2-worker async job under a canned fault plan (worker crash,
# server crash, corrupted frame, drop/delay/duplicate) must complete
# with the loss improved, all recovery counters nonzero in /metrics, and
# an identical injected-event log on replay of the same plan + seed
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py

# Online-diagnosis gate: a 2-worker async run with injected delay faults
# on worker 1 must be ATTRIBUTED by the health layer — /health + ps_top
# name worker 1 slow and wire-bound, ps_worker_anomaly_total and a
# nonzero ps_staleness_p95 land in /metrics.
diag-smoke:
	JAX_PLATFORMS=cpu python tools/diag_smoke.py

# Gradient-lineage gate (in the default `make test` path): a 2-worker
# async run with lineage armed must account for EVERY consumed push
# with a complete trace-ID row, the exact staleness rebuilt from the
# lineage must equal the serve loop's own accounting, the merged
# Chrome trace must contain cross-process flow arrows (worker push ->
# server consume, clock-skew corrected), and the lineage bookkeeping
# must fit the standing <=5% telemetry budget.
trace-smoke:
	JAX_PLATFORMS=cpu python tools/trace_smoke.py

# Numerics gate (beside diag-smoke; tests/test_numerics.py covers the
# same paths in the default `make test` run): a NaN-injecting worker
# must be quarantined — exactly that worker — with a parseable
# postmortem on disk, online codec-fidelity probes must report nonzero
# rel-error for sign and ~0 for identity.
numerics-smoke:
	JAX_PLATFORMS=cpu python tools/numerics_smoke.py

# Parameter-serving read-tier gate (in the default `make test` path):
# a burst of identical-version reads must coalesce onto ONE delta
# encode, the admission queue must shed past its configured depth with
# every reader completing via retry-after, delta-tracked state must be
# bit-exact vs a full read, an aged-out ring base must fall back to a
# full snapshot, and the armed snapshot ring must cost <=5% of the
# transport publish.
read-smoke:
	JAX_PLATFORMS=cpu python tools/read_smoke.py

# Native read-plane gate (in the default `make test` path): the C++
# epoll tier must build + arm, answer with reply byte streams identical
# to the Python selectors loop (full/delta/not-modified), answer every
# read of a concurrent full-read workload, shed at admission depth 1
# with every reader completing via retry-after, and re-serve bit-exact
# bytes through a FollowerLoop replica hop with lag 0 and nonzero relay
# accounting. Skips cleanly without a toolchain / with PS_NO_NATIVE.
read-native-smoke:
	JAX_PLATFORMS=cpu python tools/read_native_smoke.py

# Homomorphic-aggregation gate (in the default `make test` path): a
# 2-process shm sync-barrier run over the top-k wire must fold every
# push into the compressed accumulator and decode exactly ONCE per
# published version (decodes_per_publish == 1 in metrics AND /health),
# the wire aggregate must equal decode-sum for the exact algebra, and
# agg=off must really keep the legacy path.
agg-smoke:
	JAX_PLATFORMS=cpu python tools/agg_smoke.py

# Hierarchical-aggregation gate (in the default `make test` path): a
# real 2-group/6-worker tree with a leader crash injected mid-fold must
# account EVERY worker push through every hop (composed at the root —
# trace IDs surviving the leader re-encode — or positively logged lost
# with the dead leader), fold with one decode per published version at
# the root and zero per-push decodes at leaders, and recover via
# direct-to-root fallback + pinned-port respawn + rejoin.
tree-smoke:
	JAX_PLATFORMS=cpu python tools/tree_smoke.py

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
# beats the same scenario uncontrolled.
control-smoke:
	JAX_PLATFORMS=cpu python tools/control_smoke.py

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
topo-smoke:
	JAX_PLATFORMS=cpu python tools/topo_smoke.py

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

# Round-anatomy what-if gate (in the default `make test` path): a
# 3-worker sync run with 200 ms injected into worker 1's WIRE stage
# (fault kind wire_delay — the sleep sits between the frame's
# send_wall stamp and the bytes traveling) must be named by the
# advisor: wire ranked #1, its debottleneck projection matching the
# measured A/B round-time improvement within ±30%, the offline
# reconstruction from persisted lineage rows agreeing with the live
# engine, and the armed anatomy+lineage bookkeeping within the
# standing ≤5% telemetry budget.
whatif-smoke:
	JAX_PLATFORMS=cpu python tools/whatif_smoke.py

# Hop-anatomy gate (in the default `make test` path): an A/B tree run
# with a known slow_leader fold widening asserting the hop timeline
# measures it within ±30%, serial attribution reproduces the measured
# round wall, the streaming-headroom projection replays byte-
# identically from persisted hop-*.jsonl rows, and the root-side hop
# bookkeeping stays within the ≤5% telemetry budget.
hop-smoke:
	JAX_PLATFORMS=cpu python tools/hop_smoke.py

# Static-analysis gate (in the default `make test` path): analyze_smoke
# runs `python -m tools.psanalyze` on the tree (must be SILENT — the
# six rules: thread-affinity, cfg-schema, metrics-surface,
# codec-contract, abi-drift, sidecar-registry) and then proves each
# rule still fires on its seeded defect (plus pragma suppression and a
# caught ASan overflow).
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
# supervisor generation in one /fleet scrape.
obs-smoke:
	JAX_PLATFORMS=cpu python tools/obs_smoke.py

# Native fast-path gate (in the default `make test` path): both
# libraries must build and load with the fold/batch entry points, every
# fold-family codec must be BIT-exact native-vs-numpy over real
# CodecWire rounds, a live TcpPSServer must drain framed pushes through
# the C++ batched ingest (and reason-count a corrupt frame).
native-smoke:
	JAX_PLATFORMS=cpu python tools/native_smoke.py

# The speed of all this is measured in one place, on the chip:
# `python3 -m chipbench.run` (BENCHMARK.json; PERF.md says how to read it).

.PHONY: test native bucket-smoke chaos-smoke diag-smoke numerics-smoke trace-smoke read-smoke read-native-smoke agg-smoke native-smoke obs-smoke tree-smoke analyze native-asan native-ubsan native-tsan control-smoke topo-smoke whatif-smoke fresh-smoke hop-smoke
