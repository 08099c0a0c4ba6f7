"""Ahead-of-time pipeline compilation.

The reference's compile stage turns a logical ``Program`` into a pipeline
binary before any worker is scheduled (arroyo-controller/src/compiler.rs:
92-259 generates a cargo workspace and runs ``cargo build``; the
arroyo-compiler-service keeps a warm build_dir).  In the TPU design
"compile" is ``jax.jit`` tracing, which is shape-driven and therefore
happens per batch-size bucket at runtime — so the AOT stage's jobs become:

1. **Fail early** (`compile_program`): construct every physical operator
   from the logical graph — connector configs, compiled SQL expressions,
   window state, UDF wiring — so a bad pipeline dies in the controller's
   Compiling state, not on a worker mid-schedule.  This is the same
   contract as the reference's compile stage (a pipeline that compiles is
   schedulable).
2. **Persist compiled programs** (`enable_persistent_cache`): XLA
   executables go to a shared on-disk cache, so re-submissions and worker
   restarts reuse compilations instead of re-tracing (the analog of the
   compiler service's warm build_dir + artifact re-use via the program
   graph hash, compiler.rs:57-90).
3. **Warm the fire** (`KeyedBinState.warm_fire`, which a window operator
   calls when it starts): the device kernels of a window's fire whose
   shapes follow from the plan and the configured state capacity alone
   (the scan and the compacted pick at each of its row buckets) are
   compiled then, not inside whichever fire first needs them: a fire's
   compile is a stall of seconds to a minute in the middle of a stream.
4. **Export jittable steps** (`serialize_step`/`deserialize_step`): a
   traced step (e.g. the mesh window update) serializes to portable
   StableHLO bytes via ``jax.export`` and can be stored to the artifact
   store and re-loaded without the Python closure — the closest analog of
   shipping the pipeline binary to object storage (compiler.rs:247-259).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Sequence


@dataclass
class CompileReport:
    """Outcome of the AOT build pass."""

    operators: Dict[str, str] = field(default_factory=dict)  # id -> class
    errors: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.errors


def compile_program(program) -> CompileReport:
    """Validate + physically build every operator of a logical program.

    Returns a report instead of raising: the controller FSM turns a
    non-ok report into a Failed transition with the collected messages
    (states/compiling.rs analog)."""
    from .build import build_operator

    report = CompileReport()
    for msg in program.validate():
        report.errors.append(msg)
    from ..analysis.plan_validator import plan_report

    report.errors.extend(
        d.render() for d in plan_report(program)["diagnostics"]
        if d.severity == "error")
    if report.errors:
        return report
    for node_id in program.topo_order():
        node = program.node(node_id)
        try:
            op = node.operator
            phys = build_operator(op)
            report.operators[node.operator_id] = type(phys).__name__
        except Exception as e:  # config/expression/connector errors
            report.errors.append(f"{node.operator_id}: {e}")
    return report


# The one place the compile cache lives when nobody says otherwise: a
# fixed, git-ignored directory inside the checkout, resolved from this
# package's own location.  The directory is part of the cache key, so a
# path derived from the host (or a temp dir) never hits on a fresh
# machine; a fixed one does for every process started from the checkout
# (bench.py, chip_smoke.py, LocalRunner, worker processes).
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Point jax at the on-disk compilation cache (idempotent) and return
    the directory in use: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX's
    own variable — the only way to place the cache from outside), else
    the fixed in-checkout directory."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return d


# ---------------------------------------------------------------------------
# Step export (StableHLO serialization)
# ---------------------------------------------------------------------------


def serialize_step(fn: Callable, example_args: Sequence[Any]) -> bytes:
    """Trace ``fn`` at the example arguments' shapes and serialize the
    result as portable StableHLO bytes (jax.export)."""
    import jax
    from jax import export as jax_export

    exported = jax_export.export(jax.jit(fn))(*example_args)
    return bytes(exported.serialize())


def deserialize_step(data: bytes) -> Callable:
    """Rehydrate a serialized step into a callable (no Python source
    needed — the artifact alone is executable, like the reference's
    shipped pipeline binary)."""
    from jax import export as jax_export

    exported = jax_export.deserialize(data)
    return exported.call


def store_step(url: str, name: str, data: bytes) -> str:
    """Write a serialized step to the artifact store (compiler.rs:247-259
    pushes pipeline binaries the same way).  Returns the artifact path."""
    from ..utils.storage import StorageProvider

    store = StorageProvider.for_url(url)
    path = f"artifacts/{name}.stablehlo"
    store.put(path, data)
    return f"{url.rstrip('/')}/{path}"


def load_step(url: str, name: str) -> Callable:
    from ..utils.storage import StorageProvider

    store = StorageProvider.for_url(url)
    return deserialize_step(store.get(f"artifacts/{name}.stablehlo"))
