"""Implicit host<->device sync detector for operator steady-state code.

Scope: ``ops/*.py`` and ``engine/operators_*.py`` — the per-batch hot
paths where an accidental device->host readback serializes the XLA
dispatch pipeline.  Flags:

- ``np.asarray(x)`` / ``np.array(x)`` on a non-literal — materializes
  device output on the host
- ``<x>.item()``, ``<x>.block_until_ready()``, ``jax.device_get(...)``
- ``float(x)`` / ``int(x)`` whose argument contains a ``jnp.*`` call
  (scalarizing a traced value forces a sync)

Functions whose names mark checkpoint/debug paths
(checkpoint/snapshot/restore/debug/on_start/on_close/pre_checkpoint)
are exempt — those are *supposed* to materialize state on the host.
Pre-existing intentional readbacks (pane emission) live in the
baseline; the gate exists to catch new ones.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional

from .core import Finding, call_name

PASS_ID = "host-sync"

_SCOPE_RE = re.compile(r"(^|/)(ops/[^/]+\.py|engine/operators_[^/]+\.py)$")
_EXEMPT_FN_RE = re.compile(
    r"checkpoint|snapshot|restore|debug|on_start|on_close|handle_commit"
    # latency-observatory stamp sites (obs/latency.py): _lat_track /
    # _lat_consume read the host wall clock (now_micros / monotonic) to
    # stamp or judge a sampled batch — host-clock reads, never a
    # device readback, so new flag kinds must not indict them
    r"|_lat_")


def in_scope(path: str) -> bool:
    return bool(_SCOPE_RE.search(path.replace("\\", "/")))


# dtype metadata, not device computation: scalarizing these never syncs
_JNP_METADATA = {"jnp.finfo", "jnp.iinfo", "jax.numpy.finfo",
                 "jax.numpy.iinfo"}


def _contains_jnp_call(node: ast.expr) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            name = call_name(sub)
            if (name.startswith("jnp.") or name.startswith("jax.numpy.")) \
                    and name not in _JNP_METADATA:
                return True
    return False


def _flag_for(call: ast.Call) -> Optional[tuple]:
    name = call_name(call)
    if name in ("np.asarray", "np.array", "numpy.asarray", "numpy.array"):
        if call.args and not isinstance(call.args[0], (ast.Constant,
                                                       ast.List,
                                                       ast.Tuple)):
            return ("asarray", f"{name}() forces a device->host "
                    "transfer when fed a device array")
    if name.endswith(".item") and not call.args:
        return ("item", ".item() scalarizes on the host — a blocking "
                "device sync")
    if name.endswith(".block_until_ready"):
        return ("block-until-ready", "block_until_ready() outside a "
                "checkpoint/debug path serializes dispatch")
    if name in ("jax.device_get",):
        return ("device-get", "jax.device_get() is an explicit host "
                "readback in steady-state code")
    if name in ("float", "int") and call.args \
            and _contains_jnp_call(call.args[0]):
        return ("scalarize", f"{name}() of a jnp expression forces a "
                "blocking device sync")
    return None


class _Scan(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []
        self.fn_stack: List[str] = []

    def _visit_fn(self, node) -> None:
        self.fn_stack.append(node.name)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _exempt(self) -> bool:
        return any(_EXEMPT_FN_RE.search(name) for name in self.fn_stack)

    def visit_Call(self, node: ast.Call) -> None:
        if not self._exempt():
            hit = _flag_for(node)
            if hit:
                code, msg = hit
                self.findings.append(
                    Finding(PASS_ID, code, self.path, node.lineno, msg))
        self.generic_visit(node)


def check(tree: ast.AST, lines, path: str,
          force: bool = False) -> List[Finding]:
    if not force and not in_scope(path):
        return []
    scan = _Scan(path)
    scan.visit(tree)
    return scan.findings
