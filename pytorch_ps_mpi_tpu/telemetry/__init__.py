"""Unified run-wide telemetry: flight recorder, metrics registry, exports.

The observability layer the reference never had (its only surface was the
wall-clock dict every ``step`` returned, ``ps.py:116-148``) and this repo
previously scattered across per-module shims (``utils/metrics.py``
timers, per-server ``metrics()`` dicts). One system, three faces:

- :class:`FlightRecorder` — bounded, thread-safe structured event/span
  log (monotonic timestamps, worker id, step, staleness) with JSONL
  export. A process-global recorder is installed with :func:`configure`;
  every instrumented call site guards on :func:`get_recorder` returning
  ``None``, so a disabled recorder costs one attribute read per step.
- :class:`MetricsRegistry` — counters, gauges, bucketed histograms with
  a Prometheus text rendering; :class:`PSServerTelemetry` gives the shm
  and TCP parameter servers one canonical metric schema, and
  :class:`MetricsHTTPServer` serves it at ``/metrics``.
- :mod:`trace export <.trace_export>` — merges every process's recorder
  spans into one Chrome/Perfetto timeline.
- :mod:`diagnosis <.diagnosis>` — the layer that turns the streams into
  ANSWERS: :class:`HealthMonitor` derives per-worker verdicts (EWMA +
  MAD anomaly flags, compute/wire/churn straggler attribution, sync-
  round critical-path gating) served as ``/health`` JSON beside
  ``/metrics`` and rendered live by ``tools/ps_top.py``.
- :mod:`lineage <.lineage>` — the layer that makes the streams CAUSAL:
  every framed gradient push carries a trace ID (worker, step, seq) +
  encode-site timestamp from the v2 frame header; the
  :class:`LineageTracker` bills every published version with the exact
  pushes that composed it, measures exact per-push e2e latency and
  staleness (replacing the PR 4 EWMA estimates), extracts stage-level
  sync-round critical paths, and feeds cross-process clock-skew
  estimation so the merged Chrome trace can draw flow arrows from a
  worker's push span to the server's consume span.
- :mod:`numerics <.numerics>` — the layer that watches the NUMBERS:
  :class:`NumericsMonitor` fuses gradient statistics into the lowered
  step programs (grad norms, NaN/Inf counts, update-to-weight ratio),
  tails online codec-fidelity probes (``Codec.fidelity_probe``),
  quarantines non-finite pushes with a skip/zero/abort policy, and
  writes divergence postmortems.

- :mod:`anatomy <.anatomy>` — the layer that makes the streams
  ACTIONABLE: :class:`RoundAnatomy` reconstructs every published
  version's causal DAG from the lineage rows (clock-offset-corrected,
  composed trailers expanding tree hops), extracts the exact per-round
  critical path with stage-level decomposition (produce / encode /
  wire / leader-fold / root-fold / optimizer-publish), and computes
  Coz-style what-if projections ("stage X 20% faster ⇒ round time
  −Y%") — live over the serve loop and offline over persisted rows.
- :mod:`timeseries <.timeseries>` — the layer that makes the streams
  RETAINED: :class:`MetricsHistory`, a dependency-free in-process TSDB
  (raw + 1 s/10 s/60 s downsampled rings per canonical metric key,
  sampled at the serve loop's tick cadence, persisted with bounded
  retention, served at ``/history``).
- :mod:`profiler <.profiler>` — the layer that watches the TIME:
  :class:`SamplingProfiler`, an always-on ~100 Hz collapsed-stack
  sampler with a hard self-overhead budget, plus the native fold/pump
  cycle counters (``wirecodec``/``tcpps``).
- :mod:`slo <.slo>` — the layer that turns history into ALERTS:
  :class:`SLOWatchdog`, multi-window burn-rate rules over the TSDB with
  bench-derived targets, latched replayable verdicts, and the
  ``ps_slo_*`` scrape instruments.
- :mod:`freshness <.freshness>` — the layer that makes the READ path
  causal: FRS1 birth records ride the PSR1 delta stream from root
  publish through every follower hop to the edge reader, and
  :class:`FreshnessTracker` turns them into publish→visible latency
  distributions, the age-of-information gauge, and flow events joined
  to write-path lineage.
- :mod:`hop anatomy <.hop_anatomy>` — the layer that opens the LEADER:
  :class:`HopAnatomy` reconstructs each leader hop round into sub-stage
  intervals (ingest_wait / validate / fold / finalize / encode /
  upstream_push / idle) from bounded native interval rings, computes
  per-leader busy fractions, and projects the streaming-headroom ratio
  — what a pipelined (ingest ⇄ fold ⇄ encode overlapped) hop would buy.
- :mod:`fleet <.fleet>` — the layer that merges the PANES:
  :class:`FleetMonitor` polls every registered endpoint (sharded
  servers, supervisor generations, the read tier) into one ``/fleet``
  snapshot with summed counters, worst-verdict rollup and per-shard
  skew detection; ``tools/ps_top.py --fleet`` renders it live.

``tools/telemetry_report.py`` turns a recorded JSONL into the per-phase
summary table; ``make obs-smoke`` gates the
observability plane end-to-end.
"""

import time as _time

_T0 = _time.monotonic()  # this package's own import: setup.import.telemetry

from typing import Dict, Optional

#: The ONE registry of JSONL sidecar prefixes written under the
#: telemetry directory.  A "sidecar" is any structured side channel that
#: is NOT a flight-recorder event log (``server.jsonl`` /
#: ``worker-N.jsonl``): its rows have no recorder name/kind, so letting
#: one into the recorder-span merge corrupts the trace and the report.
#: Every observability PR used to patch the exclusion list in TWO
#: hand-maintained places (``tools/telemetry_report.py`` dir mode and
#: ``examples/train_async._export_telemetry``); both now route through
#: this map, and ``tools/psanalyze``'s ``sidecar-registry`` rule makes
#: an UNDECLARED prefix a lint failure instead of a live-run surprise.
#:
#: prefix → report route: the ``tools/telemetry_report.py`` section the
#: file feeds (``None`` = operator-facing raw log with no report
#: section — excluded from report collection entirely).
SIDECAR_PREFIXES: Dict[str, Optional[str]] = {
    "faults-": None,          # injected-fault event logs (resilience)
    "beacon-": None,          # worker health beacons (diagnosis tails)
    "numerics-": "numerics",  # grad-norm trajectories + fidelity probes
    "lineage-": "lineage",    # per-version push compositions + hop rows
    "anatomy-": "anatomy",    # round-anatomy critical-path rows
    "timeseries-": "history",  # retained metric history (TSDB)
    "slo-": "slo",            # SLO verdict events
    "control-": "actions",    # controller action rows
    "freshness-": "freshness",  # publish→edge propagation + delivery rows
    "hop-": "hop",            # leader hop sub-stage occupancy rows
}


def sidecar_prefix(path: str) -> Optional[str]:
    """The declared sidecar prefix of a telemetry-dir ``.jsonl`` file
    name/path, or None for recorder files (``server.jsonl``,
    ``worker-N.jsonl``) and anything else."""
    import os as _os

    base = _os.path.basename(path)
    if not base.endswith(".jsonl"):
        return None
    for p in SIDECAR_PREFIXES:
        if base.startswith(p):
            return p
    return None


def is_sidecar(path: str) -> bool:
    """True when the file must stay OUT of the recorder-span merge."""
    return sidecar_prefix(path) is not None


from pytorch_ps_mpi_tpu.telemetry.recorder import (
    FlightRecorder,
    configure,
    disable,
    get_recorder,
    install,
    load_jsonl,
    record_event,
    setup_dropped,
    setup_event,
    setup_rows,
    setup_span,
    SetupPhases,
    span,
)
from pytorch_ps_mpi_tpu.telemetry.registry import (
    Counter,
    Gauge,
    HEALTH_FLEET_ROLLUP_KEYS,
    Histogram,
    MetricsRegistry,
    PS_SERVER_METRIC_KEYS,
    PSServerTelemetry,
    ps_server_metrics,
    ps_server_registry,
    staleness_quantile,
)
from pytorch_ps_mpi_tpu.telemetry.http_server import MetricsHTTPServer
from pytorch_ps_mpi_tpu.telemetry.diagnosis import (
    BeaconWriter,
    HealthMonitor,
)
from pytorch_ps_mpi_tpu.telemetry.lineage import (
    LineageTracker,
    clock_offsets_from_rows,
    estimate_clock_offset,
    load_lineage_rows,
    trace_id,
)
from pytorch_ps_mpi_tpu.telemetry.numerics import (
    NumericsMonitor,
    ProbeWriter,
    tree_stats,
    update_weight_ratio,
)
from pytorch_ps_mpi_tpu.telemetry.trace_export import (
    export_chrome_trace,
    merged_trace_events,
)
from pytorch_ps_mpi_tpu.telemetry.timeseries import (
    MetricsHistory,
    history_from_rows,
    load_timeseries_rows,
)
from pytorch_ps_mpi_tpu.telemetry.profiler import (
    SamplingProfiler,
    load_profile,
    merge_profiles,
    top_frames,
)
from pytorch_ps_mpi_tpu.telemetry.slo import SLOWatchdog
from pytorch_ps_mpi_tpu.telemetry.fleet import (
    FleetMonitor,
    deregister_endpoint,
    parse_prometheus_text,
    register_endpoint,
)
from pytorch_ps_mpi_tpu.telemetry.anatomy import (
    RoundAnatomy,
    anatomy_from_round_rows,
    anatomy_from_rows,
    load_anatomy_rows,
)
from pytorch_ps_mpi_tpu.telemetry.freshness import (
    FreshnessTracker,
    freshness_flow_events,
    load_fresh_rows,
)
from pytorch_ps_mpi_tpu.telemetry.hop_anatomy import (
    HopAnatomy,
    hop_anatomy_from_rows,
    hop_trace_events,
    load_hop_rows,
)

__all__ = [
    "SIDECAR_PREFIXES",
    "sidecar_prefix",
    "is_sidecar",
    "RoundAnatomy",
    "anatomy_from_round_rows",
    "anatomy_from_rows",
    "load_anatomy_rows",
    "FlightRecorder",
    "configure",
    "disable",
    "get_recorder",
    "install",
    "load_jsonl",
    "record_event",
    "setup_dropped",
    "setup_event",
    "setup_rows",
    "setup_span",
    "SetupPhases",
    "span",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "HEALTH_FLEET_ROLLUP_KEYS",
    "PS_SERVER_METRIC_KEYS",
    "PSServerTelemetry",
    "ps_server_metrics",
    "ps_server_registry",
    "staleness_quantile",
    "MetricsHTTPServer",
    "BeaconWriter",
    "HealthMonitor",
    "LineageTracker",
    "clock_offsets_from_rows",
    "estimate_clock_offset",
    "load_lineage_rows",
    "trace_id",
    "NumericsMonitor",
    "ProbeWriter",
    "tree_stats",
    "update_weight_ratio",
    "export_chrome_trace",
    "merged_trace_events",
    "MetricsHistory",
    "history_from_rows",
    "load_timeseries_rows",
    "SamplingProfiler",
    "load_profile",
    "merge_profiles",
    "top_frames",
    "SLOWatchdog",
    "FleetMonitor",
    "deregister_endpoint",
    "parse_prometheus_text",
    "register_endpoint",
    "FreshnessTracker",
    "freshness_flow_events",
    "load_fresh_rows",
    "HopAnatomy",
    "hop_anatomy_from_rows",
    "hop_trace_events",
    "load_hop_rows",
]

# pulled in through ps.py while the package itself is being imported:
# the child of its setup.import row
setup_event("setup.import.telemetry", kind="span", parent="setup.import",
            ts=_T0, dur=_time.monotonic() - _T0)
