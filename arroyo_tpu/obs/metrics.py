"""Per-task prometheus metrics with the reference's metric names.

Names match /root/reference/arroyo-types/src/lib.rs:734-739 exactly
(arroyo_worker_messages_recv, …) and labels match TaskInfo::
metric_label_map (lib.rs:579-585: operator_id, subtask_idx,
operator_name) so existing dashboards / the API's rate() queries port
unchanged.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from prometheus_client import (CollectorRegistry, Counter, Gauge,
                               Histogram, generate_latest)

MESSAGES_RECV = "arroyo_worker_messages_recv"
MESSAGES_SENT = "arroyo_worker_messages_sent"
BYTES_RECV = "arroyo_worker_bytes_recv"
BYTES_SENT = "arroyo_worker_bytes_sent"
TX_QUEUE_SIZE = "arroyo_worker_tx_queue_size"
TX_QUEUE_REM = "arroyo_worker_tx_queue_rem"

# flight-recorder instruments (this file is the single name registry —
# the docs table in docs/operations.md mirrors it)
EVENT_TIME_LAG = "arroyo_worker_event_time_lag_seconds"
WATERMARK_LAG = "arroyo_worker_watermark_lag_seconds"
BATCH_LATENCY = "arroyo_worker_batch_processing_seconds"
QUEUE_WAIT = "arroyo_worker_queue_wait_seconds"
BACKPRESSURE_TIME = "arroyo_worker_backpressure_seconds_total"
KERNEL_TIME = "arroyo_worker_kernel_seconds_total"
CHECKPOINT_DURATION = "arroyo_worker_checkpoint_duration_seconds"
CHECKPOINT_BYTES = "arroyo_worker_checkpoint_bytes"
FRAME_BYTES = "arroyo_worker_frame_bytes"
FLUSH_LATENCY = "arroyo_worker_flush_seconds"
# chaining/coalescing (PR 4): fused-task size per head operator, and the
# number of record batches merged per coalesced flush at a task's input
CHAIN_MEMBERS = "arroyo_chain_members"
COALESCE_BATCHES = "arroyo_worker_coalesce_batches"
# event-loop scheduling lag (obs/profiler.py watchdog): per-worker
# gauges refreshed ~1/s from the ticker's rolling lag window, plus the
# count of stalls past the watchdog threshold (blocking-call episodes)
EVENT_LOOP_LAG = "arroyo_worker_event_loop_lag_seconds"
EVENT_LOOP_STALLS = "arroyo_worker_event_loop_stalls_total"
# sharded data plane (parallel/shuffle.py): implicit resharding/transfer
# events on device-resident state (the "no resharding" invariant — this
# counter staying 0 in steady state is MEASURED, not hoped), and the
# on-device all_to_all exchanges that replaced host shuffles
RESHARDS_TOTAL = "arroyo_worker_reshards_total"
SHUFFLE_COLLECTIVES = "arroyo_worker_shuffle_collectives_total"

LABELS = ("job_id", "operator_id", "subtask_idx", "operator_name")

# lag can span ms (steady state) to minutes (recovery backlog)
LAG_BUCKETS = (0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
               30.0, 60.0, 300.0, 1800.0)
# per-batch host/device latencies: 100us up to multi-second stalls
LATENCY_BUCKETS = (0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                   0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)
BYTES_BUCKETS = (1e3, 1e4, 1e5, 1e6, 4e6, 1.6e7, 6.4e7, 2.56e8)

_BUCKETS = {
    EVENT_TIME_LAG: LAG_BUCKETS,
    WATERMARK_LAG: LAG_BUCKETS,
    BATCH_LATENCY: LATENCY_BUCKETS,
    QUEUE_WAIT: LATENCY_BUCKETS,
    # checkpoints span sub-second (tiny state) to minutes (large device
    # tables read back whole) — the lag buckets' 1800s ceiling fits;
    # the latency buckets would collapse everything past 10s into +Inf
    CHECKPOINT_DURATION: LAG_BUCKETS,
    CHECKPOINT_BYTES: BYTES_BUCKETS,
    FRAME_BYTES: BYTES_BUCKETS,
    FLUSH_LATENCY: LATENCY_BUCKETS,
    # batches-per-flush is a small count: 1 = passthrough (no merge)
    COALESCE_BATCHES: (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0,
                       32.0, 64.0),
}

# one registry per process (worker); the admin server renders it
REGISTRY = CollectorRegistry()
_lock = threading.Lock()
_counters: Dict[str, Counter] = {}
_gauges: Dict[str, Gauge] = {}
_histograms: Dict[str, Histogram] = {}


def _counter(name: str, help_: str) -> Counter:
    with _lock:
        if name not in _counters:
            _counters[name] = Counter(name, help_, LABELS,
                                      registry=REGISTRY)
        return _counters[name]


def _gauge(name: str, help_: str) -> Gauge:
    with _lock:
        if name not in _gauges:
            _gauges[name] = Gauge(name, help_, LABELS, registry=REGISTRY)
        return _gauges[name]


def _histogram(name: str, help_: str) -> Histogram:
    with _lock:
        if name not in _histograms:
            _histograms[name] = Histogram(
                name, help_, LABELS,
                buckets=_BUCKETS.get(name, Histogram.DEFAULT_BUCKETS),
                registry=REGISTRY)
        return _histograms[name]


def counter_for_task(task_info, name: str, help_: str = "") -> Counter:
    """counter_for_task (arroyo-metrics/src/lib.rs:9-21)."""
    return _counter(name, help_ or name).labels(
        job_id=task_info.job_id, operator_id=task_info.operator_id,
        subtask_idx=str(task_info.task_index),
        operator_name=getattr(task_info, "operator_name",
                              task_info.operator_id))


def gauge_for_task(task_info, name: str, help_: str = "") -> Gauge:
    """gauge_for_task (arroyo-metrics/src/lib.rs:23-35)."""
    return _gauge(name, help_ or name).labels(
        job_id=task_info.job_id, operator_id=task_info.operator_id,
        subtask_idx=str(task_info.task_index),
        operator_name=getattr(task_info, "operator_name",
                              task_info.operator_id))


def histogram_for_task(task_info, name: str, help_: str = "") -> Histogram:
    """Labeled histogram child for one subtask (same label scheme as the
    counters, so rate()/histogram_quantile() queries join on labels)."""
    return _histogram(name, help_ or name).labels(
        job_id=task_info.job_id, operator_id=task_info.operator_id,
        subtask_idx=str(task_info.task_index),
        operator_name=getattr(task_info, "operator_name",
                              task_info.operator_id))


class TaskMetrics:
    """Per-task instruments every subtask maintains: the reference's six
    flat counters/gauges (arroyo-worker/src/metrics.rs) plus the flight
    recorder's lag/latency/backpressure histograms."""

    def __init__(self, task_info):
        self.messages_recv = counter_for_task(
            task_info, MESSAGES_RECV, "records received by this subtask")
        self.messages_sent = counter_for_task(
            task_info, MESSAGES_SENT, "records sent by this subtask")
        self.bytes_recv = counter_for_task(
            task_info, BYTES_RECV, "serialized bytes received")
        self.bytes_sent = counter_for_task(
            task_info, BYTES_SENT, "serialized bytes sent")
        self.tx_queue_size = gauge_for_task(
            task_info, TX_QUEUE_SIZE, "outbound queue capacity")
        self.tx_queue_rem = gauge_for_task(
            task_info, TX_QUEUE_REM, "outbound queue remaining slots")
        self.event_time_lag = histogram_for_task(
            task_info, EVENT_TIME_LAG,
            "processing-time minus max event time per received batch")
        self.watermark_lag = histogram_for_task(
            task_info, WATERMARK_LAG,
            "processing-time minus the operator's input watermark")
        self.batch_latency = histogram_for_task(
            task_info, BATCH_LATENCY,
            "wall time spent in process_batch per batch")
        self.queue_wait = histogram_for_task(
            task_info, QUEUE_WAIT,
            "time the task loop waited for input per message")
        self.backpressure_time = counter_for_task(
            task_info, BACKPRESSURE_TIME,
            "cumulative seconds blocked sending to full downstream queues")
        self.kernel_time = counter_for_task(
            task_info, KERNEL_TIME,
            "cumulative seconds in device-kernel dispatch for this subtask")
        self.checkpoint_duration = histogram_for_task(
            task_info, CHECKPOINT_DURATION,
            "subtask checkpoint duration (sync phase)")
        self.checkpoint_bytes = histogram_for_task(
            task_info, CHECKPOINT_BYTES,
            "bytes written per subtask checkpoint")
        self.coalesce_batches = histogram_for_task(
            task_info, COALESCE_BATCHES,
            "record batches merged per coalesced flush at this task's "
            "input (1 = passed through unmerged)")


def render_metrics(registry: Optional[CollectorRegistry] = None) -> bytes:
    return generate_latest(registry or REGISTRY)


def snapshot(name_prefix: str = "arroyo_worker_") -> Dict[str, float]:
    """In-process scrape: {metric{label=...}: value} for API proxying."""
    out: Dict[str, float] = {}
    for fam in REGISTRY.collect():
        if not fam.name.startswith(name_prefix.rstrip("_")):
            continue
        for s in fam.samples:
            if s.name.endswith("_created"):
                continue
            labels = ",".join(f"{k}={v}" for k, v in sorted(
                s.labels.items()))
            out[f"{s.name}{{{labels}}}"] = s.value
    return out


TABLE_SIZE = "arroyo_worker_table_size_keys"
# the reference's labels plus job_id: without it, same-named operators of
# different jobs sharing a process registry would clobber each other
TABLE_LABELS = ("job_id", "operator_id", "task_id", "table_char")
_table_gauge: Optional[Gauge] = None


def table_size_gauge(task_info, table_char: str) -> Gauge:
    """Per-table key-count gauge (arroyo-state/src/metrics.rs
    TABLE_SIZE_GAUGE: name + labels match the reference exactly)."""
    global _table_gauge
    with _lock:
        if _table_gauge is None:
            _table_gauge = Gauge(TABLE_SIZE, "Number of keys in the table",
                                 TABLE_LABELS, registry=REGISTRY)
    return _table_gauge.labels(
        job_id=task_info.job_id,
        operator_id=task_info.operator_id,
        task_id=str(task_info.task_index),
        table_char=table_char)


# -- event-loop watchdog instruments (obs/profiler.py) -----------------------

# worker-level (no operator label — scheduling lag is a property of the
# process's event loop, every subtask on it shares the number); the
# quantile label distinguishes the p50/p99 gauges the watchdog refreshes
_EVENT_LOOP_LABELS = ("job_id", "quantile")
_event_loop_gauge: Optional[Gauge] = None
_event_loop_stalls: Optional[Counter] = None


def event_loop_lag_gauge(job_id: str, quantile: str) -> Gauge:
    """Scheduling-lag gauge child (quantile is 'p50' or 'p99') — how
    late the loop wakes a sleeping coroutine, sampled continuously by
    the profiler's watchdog ticker."""
    global _event_loop_gauge
    with _lock:
        if _event_loop_gauge is None:
            _event_loop_gauge = Gauge(
                EVENT_LOOP_LAG,
                "event-loop scheduling lag (watchdog ticker wake delay)",
                _EVENT_LOOP_LABELS, registry=REGISTRY)
    return _event_loop_gauge.labels(job_id=job_id or "", quantile=quantile)


def event_loop_stalls_counter(job_id: str) -> Counter:
    """Stall episodes past the watchdog threshold — each one had its
    blocking stack captured (admin /profile/phases?fmt=json)."""
    global _event_loop_stalls
    with _lock:
        if _event_loop_stalls is None:
            _event_loop_stalls = Counter(
                EVENT_LOOP_STALLS,
                "event-loop stalls past the watchdog threshold",
                ("job_id",), registry=REGISTRY)
    return _event_loop_stalls.labels(job_id=job_id or "")


# -- sharded-data-plane instruments (parallel/shuffle.py) --------------------

# process-level (no operator label: resharding is detected at kernel
# dispatch sites that may run off-task, e.g. executor-offloaded
# transfers; the profiler's per-operator `reshard` phase carries the
# attribution, these counters carry the invariant)
_PLAIN_LABELS = ("job_id",)
_plain_counters: Dict[str, Counter] = {}


def _plain_counter(name: str, help_: str, job_id: str = "") -> Counter:
    with _lock:
        if name not in _plain_counters:
            _plain_counters[name] = Counter(name, help_, _PLAIN_LABELS,
                                            registry=REGISTRY)
    return _plain_counters[name].labels(job_id=job_id)


def reshard_counter(job_id: str = "") -> Counter:
    """Device arrays re-placed because their resident sharding did not
    match a kernel's explicit in_sharding — the sharded data plane's
    zero-in-steady-state invariant (docs/operations.md runbook)."""
    return _plain_counter(
        RESHARDS_TOTAL,
        "device arrays resharded at a kernel boundary (0 = invariant holds)",
        job_id)


def shuffle_collective_counter(job_id: str = "") -> Counter:
    """On-device all_to_all exchanges carrying co-located SHUFFLE edges
    (each one is a host shuffle that never happened)."""
    return _plain_counter(
        SHUFFLE_COLLECTIVES,
        "on-device all_to_all shuffle exchanges", job_id)


JOIN_DEVICE_GATHER = "arroyo_worker_join_device_gather_rows"
JOIN_HOST_GATHER = "arroyo_worker_join_host_gather_rows"


def join_gather_counter(path: str, job_id: str = "") -> Counter:
    """Join payload rows materialized per gather path: ``device`` =
    through resident payload planes (one fused dispatch per partition),
    ``host`` = numpy fancy-index of the host mirror (cold partitions,
    keys-only rings, the string sticky fallback, the legacy layout).
    With device payloads on, hot partitions must report ZERO host rows
    — the payload-residency invariant as a number."""
    name = JOIN_DEVICE_GATHER if path == "device" else JOIN_HOST_GATHER
    return _plain_counter(
        name, f"join payload rows materialized via the {path} gather",
        job_id)


SESSION_DEVICE_MERGE = "arroyo_worker_session_device_merge_rows"
SESSION_HOST_MERGE = "arroyo_worker_session_host_merge_rows"


def session_merge_counter(path: str, job_id: str = "") -> Counter:
    """Session-interval rows merged per path: ``device`` = through the
    vectorized all-keys union dispatch (state/session_state.py),
    ``host`` = the per-key python merge (the clamp fallback, span
    overflows, and the whole stream under ARROYO_SESSION_STATE=legacy).
    config5-shape jobs riding host is THE slow-path signature — the
    triage runbook (docs/operations.md) keys off this split."""
    name = SESSION_DEVICE_MERGE if path == "device" else SESSION_HOST_MERGE
    return _plain_counter(
        name, f"session interval rows merged via the {path} path",
        job_id)


FACTOR_SHARED_PANES = "arroyo_factor_shared_panes"
FACTOR_DERIVED_WINDOWS = "arroyo_factor_derived_windows"
_factor_shared: Optional[Gauge] = None
_factor_derived: Optional[Gauge] = None


def factor_shared_panes_gauge(job_id: str) -> Gauge:
    """Shared factor-pane operators in the running plan (one per
    correlated-window group the cost model decided to share;
    graph/factor_windows.py) — 0 when nothing factored or
    ARROYO_FACTOR_WINDOWS=0."""
    global _factor_shared
    with _lock:
        if _factor_shared is None:
            _factor_shared = Gauge(
                FACTOR_SHARED_PANES,
                "shared factor-pane operators in the running plan",
                ("job_id",), registry=REGISTRY)
    return _factor_shared.labels(job_id=job_id)


def factor_derived_windows_gauge(job_id: str) -> Gauge:
    """Derived-window consumers rolling shared factor panes into their
    query's (width, slide) output — 0 when nothing factored."""
    global _factor_derived
    with _lock:
        if _factor_derived is None:
            _factor_derived = Gauge(
                FACTOR_DERIVED_WINDOWS,
                "derived-window consumers over shared factor panes",
                ("job_id",), registry=REGISTRY)
    return _factor_derived.labels(job_id=job_id)


MESH_CARRIED_SHUFFLES = "arroyo_mesh_carried_shuffles"
_mesh_carried: Optional[Gauge] = None


def mesh_carried_gauge(job_id: str) -> Gauge:
    """Chain-interior SHUFFLE edges whose keyed exchange rides the mesh
    state's on-device all_to_all (graph/chaining.py ``shuffle_edges``
    when the mesh is active) — 0 when the mesh is off or no chain
    crosses a shuffle."""
    global _mesh_carried
    with _lock:
        if _mesh_carried is None:
            _mesh_carried = Gauge(
                MESH_CARRIED_SHUFFLES,
                "chain-interior shuffle edges carried by the device mesh",
                ("job_id",), registry=REGISTRY)
    return _mesh_carried.labels(job_id=job_id)


# -- latency-observatory instruments (obs/latency.py) ------------------------

SINK_E2E_LATENCY = "arroyo_sink_e2e_latency_seconds"
SINK_E2E_QUANTILE = "arroyo_sink_e2e_latency_quantile_seconds"
DEVICE_STATE_BYTES = "arroyo_device_state_bytes"
SLO_VIOLATIONS = "arroyo_slo_violations_total"
SLO_BURN_RATE = "arroyo_slo_burn_rate"

# e2e latency spans sub-ms (hot chained path) to tens of seconds (a
# held watermark on a wide window) — the lag buckets fit
_BUCKETS[SINK_E2E_LATENCY] = LAG_BUCKETS

_SINK_QUANTILE_LABELS = ("job_id", "operator_id", "operator_name",
                         "quantile")
_sink_quantile_gauge: Optional[Gauge] = None
_device_state_gauge: Optional[Gauge] = None
_slo_violations: Optional[Counter] = None
_slo_burn: Optional[Gauge] = None


def sink_latency_histogram(task_info) -> Histogram:
    """Per-sink end-to-end (emit-minus-ingest) latency of sampled
    records — the measurement behind the ROADMAP-4 SLO."""
    return histogram_for_task(
        task_info, SINK_E2E_LATENCY,
        "sampled record end-to-end latency (sink emit minus source "
        "ingest wall-clock)")


def sink_latency_quantile_gauge(task_info, quantile: str) -> Gauge:
    """Rolling-window p50/p99 gauges the observatory refreshes per
    sampled observation (histogram_quantile needs a scraper; these are
    readable in-process and ride the heartbeat rollup)."""
    global _sink_quantile_gauge
    with _lock:
        if _sink_quantile_gauge is None:
            _sink_quantile_gauge = Gauge(
                SINK_E2E_QUANTILE,
                "rolling-window end-to-end latency quantile per sink",
                _SINK_QUANTILE_LABELS, registry=REGISTRY)
    return _sink_quantile_gauge.labels(
        job_id=task_info.job_id, operator_id=task_info.operator_id,
        operator_name=getattr(task_info, "operator_name",
                              task_info.operator_id),
        quantile=quantile)


def device_state_bytes_gauge(job_id: str, table: str) -> Gauge:
    """Per-job device-resident state bytes by table (join payload
    rings, keys-only ring slots, pane planes, shuffle stacks…) — the
    device-memory ledger groundwork for co-scheduled-job accounting
    (ROADMAP-1)."""
    global _device_state_gauge
    with _lock:
        if _device_state_gauge is None:
            _device_state_gauge = Gauge(
                DEVICE_STATE_BYTES,
                "device-resident state bytes by table",
                ("job_id", "table"), registry=REGISTRY)
    return _device_state_gauge.labels(job_id=job_id or "", table=table)


def slo_violations_counter(job_id: str) -> Counter:
    """SLO evaluations that found a dimension out of budget (each one
    also lands in the controller's violation ledger with the measured
    vs target numbers)."""
    global _slo_violations
    with _lock:
        if _slo_violations is None:
            _slo_violations = Counter(
                SLO_VIOLATIONS, "latency-SLO violation evaluations",
                ("job_id",), registry=REGISTRY)
    return _slo_violations.labels(job_id=job_id or "")


def slo_burn_rate_gauge(job_id: str) -> Gauge:
    """Violating fraction of SLO evaluations over the trailing burn
    window (0 = healthy, 1 = burning the whole budget every tick) —
    the autoscaler's latency signal."""
    global _slo_burn
    with _lock:
        if _slo_burn is None:
            _slo_burn = Gauge(
                SLO_BURN_RATE, "SLO burn rate over the trailing window",
                ("job_id",), registry=REGISTRY)
    return _slo_burn.labels(job_id=job_id or "")


# -- autoscaler instruments --------------------------------------------------

# controller-side: every policy evaluation lands in decisions (labeled by
# the resulting action incl. hold/veto), blocked recommendations in
# vetoes (labeled by reason), and completed rescales in actuations
AUTOSCALER_DECISIONS = "arroyo_autoscaler_decisions_total"
AUTOSCALER_VETOES = "arroyo_autoscaler_vetoes_total"
AUTOSCALER_ACTUATIONS = "arroyo_autoscaler_actuations_total"
AUTOSCALER_PARALLELISM = "arroyo_autoscaler_target_parallelism"

_AUTOSCALER_LABELS = {
    AUTOSCALER_DECISIONS: ("job_id", "action"),
    AUTOSCALER_VETOES: ("job_id", "reason"),
    AUTOSCALER_ACTUATIONS: ("job_id", "direction"),
}
_AUTOSCALER_HELP = {
    AUTOSCALER_DECISIONS: "autoscaler policy evaluations by action",
    AUTOSCALER_VETOES: "autoscaler recommendations blocked, by reason",
    AUTOSCALER_ACTUATIONS: "autoscaler-driven rescales that completed",
}
_autoscaler_counters: Dict[str, Counter] = {}
_autoscaler_parallelism: Optional[Gauge] = None


def autoscaler_counter(name: str, job_id: str, value: str) -> Counter:
    """Labeled child of one autoscaler counter family (name must be one
    of the AUTOSCALER_* counter constants)."""
    with _lock:
        if name not in _autoscaler_counters:
            _autoscaler_counters[name] = Counter(
                name, _AUTOSCALER_HELP[name], _AUTOSCALER_LABELS[name],
                registry=REGISTRY)
    labels = _AUTOSCALER_LABELS[name]
    return _autoscaler_counters[name].labels(**{labels[0]: job_id,
                                                labels[1]: value})


def autoscaler_parallelism_gauge(job_id: str, operator_id: str) -> Gauge:
    """The parallelism the autoscaler last targeted per operator — plot
    against the worker throughput families to see elasticity."""
    global _autoscaler_parallelism
    with _lock:
        if _autoscaler_parallelism is None:
            _autoscaler_parallelism = Gauge(
                AUTOSCALER_PARALLELISM,
                "operator parallelism last targeted by the autoscaler",
                ("job_id", "operator_id"), registry=REGISTRY)
    return _autoscaler_parallelism.labels(job_id=job_id,
                                          operator_id=operator_id)


CHECKPOINT_TABLE_SECONDS = "arroyo_worker_checkpoint_table_seconds"
CHECKPOINT_TABLE_BYTES = "arroyo_worker_checkpoint_table_bytes"
_table_ckpt_gauges: Dict[str, Gauge] = {}


def checkpoint_table_gauge(task_info, table_char: str, which: str) -> Gauge:
    """Per-table checkpoint cost gauges, refreshed at every barrier:
    ``which`` is 'seconds' (serialize+write wall time) or 'bytes'
    (compressed file size).  Same label scheme as table_size_gauge so
    dashboards join the three per-table families."""
    name = (CHECKPOINT_TABLE_SECONDS if which == "seconds"
            else CHECKPOINT_TABLE_BYTES)
    with _lock:
        if name not in _table_ckpt_gauges:
            _table_ckpt_gauges[name] = Gauge(
                name, f"last checkpoint {which} for the table",
                TABLE_LABELS, registry=REGISTRY)
    return _table_ckpt_gauges[name].labels(
        job_id=task_info.job_id,
        operator_id=task_info.operator_id,
        task_id=str(task_info.task_index),
        table_char=table_char)


# -- heartbeat-sized rollups -------------------------------------------------

# summary keys are metric names with the arroyo_worker_ prefix stripped;
# histograms contribute their _sum/_count pair (enough for avg + rate
# math controller-side without shipping every bucket)
_SUMMARY_SKIP_SUFFIXES = ("_bucket", "_created")

# lag/latency histograms and the queue gauges ALSO ship per-subtask
# values (`key@idx`): the controller's rollup takes the worst subtask,
# and summing across co-located subtasks first would average a single
# hot subtask away — the exact signal the rollup exists to carry
_PER_SUBTASK_FAMS = ("event_time_lag_seconds", "watermark_lag_seconds",
                     "batch_processing_seconds", "queue_wait_seconds",
                     "tx_queue_size", "tx_queue_rem")


def job_operator_summary(job_id: str) -> Dict[str, Dict[str, float]]:
    """Compact per-operator rollup of this process's registry for one job
    — what a worker attaches to its heartbeat so the controller can serve
    job-level aggregation without scraping workers over HTTP.  When the
    phase profiler is armed, its per-operator phase/wait seconds ride
    along as ``phase_seconds.<phase>`` / ``wait_seconds.<phase>`` keys,
    and worker-level (operator-less) families — the event-loop lag
    gauges — land under the pseudo-operator ``__worker__``."""
    out: Dict[str, Dict[str, float]] = {}
    prefix = "arroyo_worker_"
    for fam in REGISTRY.collect():
        if not fam.name.startswith(prefix.rstrip("_")):
            continue
        for s in fam.samples:
            if s.name.endswith(_SUMMARY_SKIP_SUFFIXES):
                continue
            if s.labels.get("job_id") != job_id:
                continue
            op = s.labels.get("operator_id", "") or "__worker__"
            key = s.name[len(prefix):] if s.name.startswith(prefix) else s.name
            q = s.labels.get("quantile")
            if q:  # event-loop lag gauges: one key per quantile child
                key = f"{key}_{q}"
            g = out.setdefault(op, {})
            g[key] = g.get(key, 0.0) + s.value
            sub = s.labels.get("subtask_idx")
            if sub is not None and key.startswith(_PER_SUBTASK_FAMS):
                sk = f"{key}@{sub}"
                g[sk] = g.get(sk, 0.0) + s.value
    from . import profiler as _profiler

    prof = _profiler.active()
    if prof is not None and (not prof.job_id or prof.job_id == job_id):
        for (op, phase), secs in prof.work_snapshot().items():
            out.setdefault(op, {})[f"phase_seconds.{phase}"] = round(secs, 6)
        for (op, phase), secs in prof.wait_snapshot().items():
            out.setdefault(op, {})[f"wait_seconds.{phase}"] = round(secs, 6)
    # latency-observatory ride-alongs (e2e_latency.*, wm_age_ms,
    # critical_path.*, device_bytes.*) — same mechanism as the profiler's
    from . import latency as _latency

    for op, keys in _latency.summary_ride_alongs(job_id).items():
        out.setdefault(op, {}).update(keys)
    return out
