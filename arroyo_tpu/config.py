"""Env-first configuration, mirroring the reference's env-var config system
(arroyo-types/src/lib.rs:78-201: TASK_SLOTS, CONTROLLER_ADDR, CHECKPOINT_URL,
ARTIFACT_URL, ``{SERVICE}__GRPC_PORT``...).  No config files; a typed settings
object reads the environment once, with the same defaults where the reference
defines them."""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v else default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name) or default


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def grpc_port(service: str, default: int) -> int:
    """``{SERVICE}__GRPC_PORT`` override pattern (arroyo-types lib.rs:195-201)."""
    return _env_int(f"{service.upper()}__GRPC_PORT", default)


@dataclass
class Config:
    # Worker / engine
    task_slots: int = field(default_factory=lambda: _env_int("TASK_SLOTS", 16))
    queue_size: int = field(default_factory=lambda: _env_int("QUEUE_SIZE", 64))
    # Batching policy for the columnar data plane (no reference analog: the
    # reference is per-record; these bound batch size/latency at the source).
    target_batch_size: int = field(
        default_factory=lambda: _env_int("BATCH_SIZE", 8192)
    )
    batch_linger_micros: int = field(
        default_factory=lambda: _env_int("BATCH_LINGER_MICROS", 10_000)
    )
    # Input-side micro-batch coalescing (engine/coalesce.py): merge
    # sub-target fragments at task inputs before dispatch.  Target rows
    # (0 = use target_batch_size) and the bounded linger a partial
    # buffer may wait for more input.  ARROYO_COALESCE=0 disables.
    coalesce_target: int = field(
        default_factory=lambda: _env_int("COALESCE_TARGET", 0)
    )
    coalesce_linger_micros: int = field(
        default_factory=lambda: _env_int("COALESCE_LINGER_MICROS", 2_000)
    )

    # Control plane
    controller_addr: str = field(
        default_factory=lambda: _env_str("CONTROLLER_ADDR", "http://localhost:9190")
    )
    node_id: Optional[str] = field(default_factory=lambda: os.environ.get("NODE_ID"))
    job_id: Optional[str] = field(default_factory=lambda: os.environ.get("JOB_ID"))
    run_id: Optional[str] = field(default_factory=lambda: os.environ.get("RUN_ID"))

    # Storage
    checkpoint_url: str = field(
        default_factory=lambda: _env_str("CHECKPOINT_URL", "file:///tmp/arroyo_tpu/checkpoints")
    )
    artifact_url: str = field(
        default_factory=lambda: _env_str("ARTIFACT_URL", "file:///tmp/arroyo_tpu/artifacts")
    )

    # Supervision (job_controller/mod.rs:30-32 defaults)
    # checkpoint retention: prune to the last N completed epochs after
    # every successful checkpoint and after every rescale restore point
    # (CHECKPOINTS_TO_KEEP accepted as a legacy alias)
    checkpoint_retention: int = field(
        default_factory=lambda: _env_int(
            "CHECKPOINT_RETENTION", _env_int("CHECKPOINTS_TO_KEEP", 3))
    )
    compact_every: int = field(default_factory=lambda: _env_int("COMPACT_EVERY", 2))
    heartbeat_interval_secs: float = field(
        default_factory=lambda: _env_float("HEARTBEAT_INTERVAL_SECS", 5.0)
    )
    heartbeat_timeout_secs: float = field(
        default_factory=lambda: _env_float("HEARTBEAT_TIMEOUT_SECS", 30.0)
    )
    checkpoint_interval_secs: float = field(
        default_factory=lambda: _env_float("CHECKPOINT_INTERVAL_SECS", 10.0)
    )

    # Device execution
    state_capacity: int = field(
        default_factory=lambda: _env_int("STATE_CAPACITY", 1 << 12)
    )  # initial per-subtask keyed-state slots (doubles on overflow;
    # benchmarks pre-size via STATE_CAPACITY to avoid growth recompiles)

    # Autoscaling (arroyo_tpu/autoscale): ARROYO_AUTOSCALE=0 is the
    # global escape hatch — no per-job control loops run at all.  With
    # the subsystem enabled, jobs still start with the loop inactive
    # unless ARROYO_AUTOSCALE_DEFAULT=1 (or the REST PUT enables them).
    autoscale_enabled: bool = field(
        default_factory=lambda: _env_bool("ARROYO_AUTOSCALE", True)
    )
    autoscale_default_on: bool = field(
        default_factory=lambda: _env_bool("ARROYO_AUTOSCALE_DEFAULT", False)
    )
    autoscale_interval_secs: float = field(
        default_factory=lambda: _env_float("AUTOSCALE_INTERVAL_SECS", 15.0)
    )

    # End-to-end latency observatory (obs/latency.py): deterministic
    # 1-in-N record-level sampling at sources (0 = observatory off), and
    # the per-pipeline declarative SLO the controller evaluates against
    # rollup quantiles (0 = that SLO dimension unset).  REST can override
    # the SLO per job after start.
    latency_sample_n: int = field(
        default_factory=lambda: _env_int("ARROYO_LATENCY_SAMPLE_N", 0)
    )
    slo_p99_ms: float = field(
        default_factory=lambda: _env_float("ARROYO_SLO_P99_MS", 0.0)
    )
    slo_staleness_ms: float = field(
        default_factory=lambda: _env_float("ARROYO_SLO_STALENESS_MS", 0.0)
    )
    slo_burn_window_secs: float = field(
        default_factory=lambda: _env_float("ARROYO_SLO_BURN_WINDOW_SECS", 60.0)
    )

    # Telemetry
    disable_telemetry: bool = field(
        default_factory=lambda: _env_bool("DISABLE_TELEMETRY", True)
    )

    # Admin/metrics
    admin_port: int = field(default_factory=lambda: _env_int("ADMIN_PORT", 9191))


_config: Optional[Config] = None


def config() -> Config:
    global _config
    if _config is None:
        _config = Config()
    return _config


def require_backend() -> str:
    """Initialise the JAX backend now and return its platform name,
    refusing the one fallback JAX performs by itself: with
    ``JAX_PLATFORMS`` unset, an installed accelerator plug-in that fails
    to start (no chip, or the chip is held by another process — a chip
    belongs to one process at a time) is logged at INFO and the CPU
    backend quietly takes over.  Here that is an error.  Running on the
    CPU is a choice the caller states with ``JAX_PLATFORMS=cpu``; naming
    a platform that cannot start already raises inside JAX."""
    import jax
    from jax._src import xla_bridge  # records the quiet failures (jax 0.9.0)

    backend = jax.default_backend()
    failed = dict(xla_bridge._backend_errors)
    if failed:
        raise RuntimeError(
            f"accelerator backend failed to initialise ({failed}); JAX "
            f"fell back to {backend!r} and this program does not — set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose")
    return backend


def reset_config() -> None:
    """Testing hook: force re-read of the environment."""
    global _config
    _config = None
