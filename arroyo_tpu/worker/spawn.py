"""Shared worker-process spawn logic for every scheduler/daemon that
starts `python -m arroyo_tpu.worker.server` as an OS process."""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, Optional


def spawn_worker_process(job_id: str, controller_addr: str, slots: int,
                         extra_env: Optional[Dict[str, str]] = None
                         ) -> subprocess.Popen:
    """Start a worker OS process with the package importable from any
    cwd.  The environment is inherited as it is: in particular
    ``JAX_PLATFORMS`` passes through unchanged and none is added, so a
    worker on a chip host initialises the chip (and fails its job when
    another process holds it — see ``ProcessScheduler``), and a worker
    under ``JAX_PLATFORMS=cpu`` (tier-1 exports it) stays on the CPU."""
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env.update(extra_env or {})
    env.update({
        "CONTROLLER_ADDR": controller_addr,
        "JOB_ID": job_id,
        "TASK_SLOTS": str(slots),
        "PYTHONPATH": (pkg_root + os.pathsep + env["PYTHONPATH"]
                       if env.get("PYTHONPATH") else pkg_root),
    })
    return subprocess.Popen(
        [sys.executable, "-m", "arroyo_tpu.worker.server"], env=env)
