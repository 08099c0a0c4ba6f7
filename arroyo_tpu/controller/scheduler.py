"""Schedulers: how worker processes get started
(/root/reference/arroyo-controller/src/schedulers/mod.rs trait Scheduler
:47-68 — start_workers, stop_workers, workers_for_job).

* :class:`InProcessScheduler` — workers as asyncio tasks in the controller
  process (still real gRPC + TCP over loopback); the test/dev default, the
  analog of the reference's single-process mode.
* :class:`ProcessScheduler` — spawns ``python -m arroyo_tpu.worker.server``
  subprocesses (schedulers/mod.rs:77-233).
* :class:`KubernetesScheduler` — pod-per-worker on k8s/GKE TPU pools
  (kubernetes.rs analog; slots map to TPU chips per SURVEY §2 #34).
* :class:`NodeScheduler` — workers placed on a pool of
  ``arroyo_tpu.node`` daemons (schedulers/mod.rs:316-664 analog).
* :class:`NomadScheduler` — worker-per-Nomad-batch-job (nomad.rs analog).
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)


class Scheduler:
    async def start_workers(self, job_id: str, controller_addr: str,
                            n_workers: int, slots_per_worker: int) -> None:
        raise NotImplementedError

    async def stop_workers(self, job_id: str, force: bool = False) -> None:
        raise NotImplementedError

    def workers_for_job(self, job_id: str) -> List[str]:
        raise NotImplementedError

    def dead_workers(self, job_id: str) -> List[str]:
        """Workers of ``job_id`` that exited before the job was scheduled,
        as printable descriptions.  Only schedulers that own OS processes
        can tell; the others wait out the registration deadline."""
        return []

    async def reap(self, job_id: str, ext_ids: List[str]) -> None:
        """Kill workers left over from a PREVIOUS controller incarnation
        (identified by their persisted external ids).  Default no-op:
        in-process workers die with the controller, and the k8s/nomad
        reconcilers re-own replica sets by job label on start_workers."""


class InProcessScheduler(Scheduler):
    def __init__(self) -> None:
        self._tasks: Dict[str, List[asyncio.Task]] = {}
        self._servers: Dict[str, List] = {}

    async def start_workers(self, job_id, controller_addr, n_workers,
                            slots_per_worker):
        from ..worker.server import WorkerServer

        tasks, servers = [], []
        for _ in range(n_workers):
            w = WorkerServer(controller_addr, job_id, slots_per_worker)

            async def run(w=w):
                await w.start()
                await w.wait_done()

            tasks.append(asyncio.ensure_future(run()))
            servers.append(w)
        self._tasks[job_id] = self._tasks.get(job_id, []) + tasks
        self._servers[job_id] = self._servers.get(job_id, []) + servers

    async def stop_workers(self, job_id, force=False):
        for w in self._servers.pop(job_id, []):
            try:
                await w.shutdown()
            except Exception:
                pass
        for t in self._tasks.pop(job_id, []):
            t.cancel()

    def workers_for_job(self, job_id):
        return [w.worker_id for w in self._servers.get(job_id, [])]


class ProcessScheduler(Scheduler):
    """One OS process per worker (16 slots/node default in the reference).

    On a chip host each worker process claims the chip at start-up
    (worker/server.py ``main``) and a chip belongs to one process at a
    time, so ``n_workers`` beyond the number of chips cannot start: the
    extra workers exit non-zero before registering and the job FAILS at
    scheduling with their exit codes (``dead_workers``).  They do not
    hang and do not run on the CPU.  Assigning chips to workers (one
    visible device each) is future work."""

    def __init__(self) -> None:
        self._procs: Dict[str, List[subprocess.Popen]] = {}

    async def start_workers(self, job_id, controller_addr, n_workers,
                            slots_per_worker):
        from ..worker.spawn import spawn_worker_process

        procs = [spawn_worker_process(job_id, controller_addr,
                                      slots_per_worker)
                 for _ in range(n_workers)]
        self._procs[job_id] = self._procs.get(job_id, []) + procs

    async def stop_workers(self, job_id, force=False):
        for p in self._procs.pop(job_id, []):
            if force:
                p.kill()
            else:
                p.terminate()
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()

    def workers_for_job(self, job_id):
        return [f"pid-{p.pid}" for p in self._procs.get(job_id, [])
                if p.poll() is None]

    def dead_workers(self, job_id):
        return [f"pid-{p.pid} exited rc={p.returncode}"
                for p in self._procs.get(job_id, [])
                if p.poll() not in (None, 0)]

    async def reap(self, job_id, ext_ids):
        """SIGKILL orphaned worker pids from a crashed controller — but
        only when the pid still runs OUR worker entrypoint (pids recycle;
        killing a stranger would be a disaster)."""
        import os
        import signal

        for ext in ext_ids:
            if not ext.startswith("pid-"):
                continue
            try:
                pid = int(ext.split("-", 1)[1])
                # arroyolint: disable=async-blocking -- tiny procfs read on the rarely-run reap path
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmdline = f.read()
                if b"arroyo_tpu.worker.server" in cmdline:
                    os.kill(pid, signal.SIGKILL)
            except (OSError, ValueError):
                continue  # already gone


class KubernetesApiClient:
    """Minimal in-cluster Kubernetes API client (no external deps): reads
    the service-account token and talks to the API server over HTTPS.
    Tests inject a fake with the same three methods."""

    SA_DIR = "/var/run/secrets/kubernetes.io/serviceaccount"

    def __init__(self, api_server: Optional[str] = None,
                 token: Optional[str] = None,
                 namespace: Optional[str] = None):
        host = os.environ.get("KUBERNETES_SERVICE_HOST")
        port = os.environ.get("KUBERNETES_SERVICE_PORT", "443")
        self.api_server = api_server or (f"https://{host}:{port}"
                                         if host else None)
        self.token = token or self._read(f"{self.SA_DIR}/token")
        self.namespace = namespace or self._read(
            f"{self.SA_DIR}/namespace") or "default"

    @staticmethod
    def _read(path: str) -> Optional[str]:
        try:
            with open(path) as f:
                return f.read().strip()
        except OSError:
            return None

    def _request(self, method: str, path: str, body=None) -> dict:
        import json as _json
        import ssl
        import urllib.request

        if not self.api_server:
            raise RuntimeError(
                "not running in a Kubernetes cluster "
                "(KUBERNETES_SERVICE_HOST unset) and no api_server given")
        req = urllib.request.Request(
            self.api_server + path, method=method,
            data=_json.dumps(body).encode() if body is not None else None,
            headers={"Authorization": f"Bearer {self.token}",
                     "Content-Type": "application/json"})
        ctx = ssl.create_default_context(
            cafile=f"{self.SA_DIR}/ca.crt"
            if os.path.exists(f"{self.SA_DIR}/ca.crt") else None)
        with urllib.request.urlopen(req, context=ctx, timeout=30) as r:
            return _json.loads(r.read() or b"{}")

    def create_replicaset(self, manifest: dict) -> dict:
        ns = manifest["metadata"]["namespace"]
        return self._request(
            "POST", f"/apis/apps/v1/namespaces/{ns}/replicasets", manifest)

    def delete_replicasets(self, namespace: str, label_selector: str) -> dict:
        return self._request(
            "DELETE",
            f"/apis/apps/v1/namespaces/{namespace}/replicasets"
            f"?labelSelector={label_selector}&propagationPolicy=Background")

    def list_pods(self, namespace: str, label_selector: str) -> dict:
        return self._request(
            "GET", f"/api/v1/namespaces/{namespace}/pods"
            f"?labelSelector={label_selector}")


class KubernetesScheduler(Scheduler):
    """Pod-per-worker scheduling on Kubernetes / GKE TPU pools
    (kubernetes.rs:28-243 analog).

    One ReplicaSet per (job, run) with ``replicas = n_workers`` worker
    pods, each advertising the controller-assigned ``slots_per_worker``
    task slots (``K8S_WORKER_SLOTS`` is the default when the controller
    does not specify).  On TPU node pools, slots map to chips: set
    ``K8S_WORKER_TPU_CHIPS`` and the pod requests ``google.com/tpu``
    resources so the GKE TPU scheduler places one worker per TPU host.
    Env-templated like every other knob in the system (the reference's
    K8S_* env family, arroyo-types lib.rs:78-129)."""

    CLUSTER_LABEL = "cluster"
    JOB_ID_LABEL = "job_id"
    RUN_ID_LABEL = "run_id"

    def __init__(self, client=None):
        import json as _json

        self.client = client  # lazily constructed in-cluster if None
        self.namespace = os.environ.get("K8S_NAMESPACE", "default")
        self.name = os.environ.get("K8S_WORKER_NAME", "arroyo-tpu") + "-worker"
        self.image = os.environ.get(
            "K8S_WORKER_IMAGE", "arroyo-tpu-worker:latest")
        self.image_pull_policy = os.environ.get(
            "K8S_WORKER_IMAGE_PULL_POLICY", "IfNotPresent")
        self.service_account = os.environ.get(
            "K8S_WORKER_SERVICE_ACCOUNT_NAME", "default")
        self.labels = _json.loads(os.environ.get("K8S_WORKER_LABELS", "{}"))
        self.annotations = _json.loads(
            os.environ.get("K8S_WORKER_ANNOTATIONS", "{}"))
        self.tpu_chips = int(os.environ.get("K8S_WORKER_TPU_CHIPS", "0"))
        self.slots_per_pod = int(os.environ.get(
            "K8S_WORKER_SLOTS", str(self.tpu_chips or 4)))
        default_res = {"requests": {"cpu": "400m", "memory": "200Mi"}}
        if self.tpu_chips:
            default_res["limits"] = {"google.com/tpu": str(self.tpu_chips)}
        self.resources = _json.loads(os.environ.get(
            "K8S_WORKER_RESOURCES", _json.dumps(default_res)))
        self.node_selector = _json.loads(os.environ.get(
            "K8S_WORKER_NODE_SELECTOR", "{}"))
        self._jobs: Dict[str, str] = {}  # job_id -> label selector
        self._runs: Dict[str, int] = {}  # job_id -> run counter
        # per-incarnation suffix: a restarted CONTROLLER resets the
        # counter, and its run 1 must not collide with a still-terminating
        # ReplicaSet from the previous incarnation's run 1
        import uuid as _uuid

        self._incarnation = _uuid.uuid4().hex[:6]

    def _get_client(self):
        if self.client is None:
            self.client = KubernetesApiClient()
        return self.client

    def make_replicaset(self, job_id: str, controller_addr: str,
                        n_workers: int, slots_per_worker: int,
                        run_id: str = "0") -> dict:
        labels = {
            self.CLUSTER_LABEL: self.name,
            self.JOB_ID_LABEL: job_id,
            self.RUN_ID_LABEL: run_id,
            **self.labels,
        }
        slots = slots_per_worker or self.slots_per_pod
        if self.tpu_chips and slots != self.tpu_chips:
            logger.warning(
                "worker advertises %d slots but pods request %d TPU chips"
                " — slots should equal chips on TPU pools",
                slots, self.tpu_chips)
        env = [
            {"name": "PROD", "value": "true"},
            {"name": "TASK_SLOTS", "value": str(slots)},
            {"name": "JOB_ID", "value": job_id},
            {"name": "RUN_ID", "value": run_id},
            {"name": "CONTROLLER_ADDR", "value": controller_addr},
        ]
        if self.tpu_chips:
            # the mesh path shards keyed state over the pod's chips
            env.append({"name": "ARROYO_MESH", "value": "auto"})
        name = (f"{self.name}-"
                f"{job_id.lower().replace('_', '-')}-{run_id}")
        return {
            "apiVersion": "apps/v1",
            "kind": "ReplicaSet",
            "metadata": {
                "name": name,
                "namespace": self.namespace,
                "labels": labels,
                "annotations": dict(self.annotations),
            },
            "spec": {
                "replicas": n_workers,
                "selector": {"matchLabels": {
                    self.JOB_ID_LABEL: job_id,
                    self.RUN_ID_LABEL: run_id,
                }},
                "template": {
                    "metadata": {"labels": labels,
                                 "annotations": dict(self.annotations)},
                    "spec": {
                        "nodeSelector": dict(self.node_selector),
                        "serviceAccountName": self.service_account,
                        "containers": [{
                            "name": "worker",
                            "image": self.image,
                            "imagePullPolicy": self.image_pull_policy,
                            "command": ["python", "-m",
                                        "arroyo_tpu.worker.server"],
                            "resources": self.resources,
                            "env": env,
                            "ports": [
                                {"containerPort": 6900, "name": "rpc"},
                                {"containerPort": 6901, "name": "admin"},
                            ],
                        }],
                    },
                },
            },
        }

    async def start_workers(self, job_id, controller_addr, n_workers,
                            slots_per_worker):
        # run_id increments per (re)start so a restarted job never
        # collides with a still-terminating ReplicaSet of the same name
        # (the reference passes the DB run_id the same way)
        self._runs[job_id] = self._runs.get(job_id, 0) + 1
        rs = self.make_replicaset(
            job_id, controller_addr, n_workers, slots_per_worker,
            run_id=f"{self._runs[job_id]}-{self._incarnation}")
        sel = (f"{self.JOB_ID_LABEL}={job_id},"
               f"{self.RUN_ID_LABEL}="
               f"{rs['metadata']['labels'][self.RUN_ID_LABEL]}")
        self._jobs[job_id] = sel
        await asyncio.get_event_loop().run_in_executor(
            None, self._get_client().create_replicaset, rs)

    async def stop_workers(self, job_id, force=False):
        sel = self._jobs.pop(job_id, f"{self.JOB_ID_LABEL}={job_id}")
        client = self._get_client()
        await asyncio.get_event_loop().run_in_executor(
            None, client.delete_replicasets, self.namespace, sel)

    def workers_for_job(self, job_id):
        sel = self._jobs.get(job_id, f"{self.JOB_ID_LABEL}={job_id}")
        pods = self._get_client().list_pods(self.namespace, sel)
        return [p["metadata"]["name"] for p in pods.get("items", [])
                if p.get("status", {}).get("phase") in ("Running", "Pending")]


class NomadApiClient:
    """Minimal Nomad HTTP API client (no external deps), mirroring the
    three calls the reference scheduler makes (nomad.rs:38-103): submit a
    job, list jobs by prefix (with Meta), and stop a job.  Tests inject a
    fake with the same three methods."""

    def __init__(self, endpoint: Optional[str] = None):
        self.endpoint = endpoint or os.environ.get(
            "NOMAD_ENDPOINT", "http://localhost:4646")

    def _request(self, method: str, path: str, body=None) -> object:
        import json as _json
        import urllib.request

        req = urllib.request.Request(
            self.endpoint + path, method=method,
            data=_json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return _json.loads(r.read() or b"{}")

    def submit_job(self, job: dict) -> dict:
        return self._request("POST", "/v1/jobs", job)

    def list_jobs(self, prefix: str) -> list:
        return self._request("GET", f"/v1/jobs?meta=true&prefix={prefix}")

    def delete_job(self, name: str) -> dict:
        return self._request("DELETE", f"/v1/job/{name}")


class NomadScheduler(Scheduler):
    """Worker-per-Nomad-job scheduling (nomad.rs:18-278 analog).

    Each worker is a ``batch`` Nomad job named ``{job_id}-{run}-{worker}``
    with restart/reschedule disabled — failure handling belongs to the
    controller FSM, not Nomad (nomad.rs:155-162).  ``workers_for_job``
    lists by name prefix and skips dead jobs (nomad.rs:63-103).  Slots per
    Nomad node and per-slot resources follow the reference's constants,
    overridable via NOMAD_* env vars."""

    def __init__(self, client=None):
        self.client = client or NomadApiClient()
        self.datacenter = os.environ.get("NOMAD_DC", "dc1")
        self.cpu_per_slot = int(os.environ.get("NOMAD_CPU_PER_SLOT", "3400"))
        self.mem_per_slot = int(os.environ.get(
            "NOMAD_MEMORY_PER_SLOT_MB", "4000"))
        self._runs: Dict[str, int] = {}

    def make_job(self, job_id: str, run_id: int, worker_id: int,
                 controller_addr: str, slots: int) -> dict:
        env = {
            "PROD": "true",
            "TASK_SLOTS": str(slots),
            "WORKER_ID": str(worker_id),
            "NODE_ID": "1",
            "JOB_ID": job_id,
            "RUN_ID": str(run_id),
            "CONTROLLER_ADDR": controller_addr,
        }
        return {"Job": {
            "ID": f"{job_id}-{run_id}-{worker_id}",
            "Name": f"{job_id}-{run_id}-{worker_id}",
            "Type": "batch",
            "Datacenters": [self.datacenter],
            "Meta": {
                "job_id": job_id,
                "worker_id": str(worker_id),
                "run_id": str(run_id),
            },
            "TaskGroups": [{
                "Name": "worker",
                "Count": 1,
                # the controller owns failure handling (nomad.rs:155-162);
                # in the Nomad JSON API these policies live on the
                # TaskGroup, not the Job
                "RestartPolicy": {"Attempts": 0, "Mode": "fail"},
                "ReschedulePolicy": {"Attempts": 0, "Unlimited": False},
                "Tasks": [{
                    "Name": "worker",
                    "Driver": "exec",
                    "Config": {
                        "command": "python",
                        "args": ["-m", "arroyo_tpu.worker.server"],
                    },
                    "Env": env,
                    "Resources": {
                        "CPU": self.cpu_per_slot * slots,
                        "MemoryMB": self.mem_per_slot * slots,
                    },
                }],
            }],
        }}

    async def start_workers(self, job_id, controller_addr, n_workers,
                            slots_per_worker):
        import random

        run_id = self._runs[job_id] = self._runs.get(job_id, 0) + 1
        loop = asyncio.get_event_loop()
        for _ in range(n_workers):
            worker_id = random.getrandbits(32)
            job = self.make_job(job_id, run_id, worker_id, controller_addr,
                                slots_per_worker)
            await loop.run_in_executor(None, self.client.submit_job, job)

    def _live_jobs(self, job_id: str) -> List[dict]:
        run = self._runs.get(job_id)
        prefix = f"{job_id}-{run}-" if run is not None else f"{job_id}-"
        jobs = self.client.list_jobs(prefix)
        return [j for j in jobs if j.get("Status") != "dead"]

    async def stop_workers(self, job_id, force=False):
        loop = asyncio.get_event_loop()
        # the listing is a blocking HTTP call too: keep it off the loop
        live = await loop.run_in_executor(None, self._live_jobs, job_id)
        for j in live:
            name = j.get("Name") or j.get("ID")
            try:
                await loop.run_in_executor(None, self.client.delete_job, name)
            except Exception:
                logger.warning("failed to stop nomad job %s", name)

    def workers_for_job(self, job_id):
        return [j["Meta"]["worker_id"] for j in self._live_jobs(job_id)
                if j.get("Meta", {}).get("worker_id")]


class NodeScheduler(Scheduler):
    """Schedule workers onto a pool of node daemons
    (schedulers/mod.rs:316-664 NodeScheduler analog; daemons are
    arroyo_tpu.node.daemon processes).  The pool is env-configured:
    ``NODE_ADDRS=host1:9290,host2:9290`` (the reference's nodes register
    dynamically; a static pool keeps the control plane one-directional).
    Workers are round-robined across nodes."""

    def __init__(self, node_addrs: Optional[List[str]] = None):
        addrs = node_addrs or [
            a.strip() for a in os.environ.get("NODE_ADDRS", "").split(",")
            if a.strip()]
        if not addrs:
            raise ValueError("NodeScheduler needs NODE_ADDRS")
        self.node_addrs = addrs
        self._rr = 0
        # job_id -> list of (node_addr, worker_id)
        self._workers: Dict[str, List] = {}

    def _client(self, addr: str):
        from ..rpc.transport import RpcClient

        return RpcClient(addr, "NodeGrpc")

    async def start_workers(self, job_id, controller_addr, n_workers,
                            slots_per_worker):
        placed = self._workers.setdefault(job_id, [])
        for _ in range(n_workers):
            addr = self.node_addrs[self._rr % len(self.node_addrs)]
            self._rr += 1
            client = self._client(addr)
            try:
                resp = await client.call("StartWorker", {
                    "job_id": job_id,
                    "controller_addr": controller_addr,
                    "slots": slots_per_worker,
                })
            finally:
                await client.close()
            placed.append((addr, resp["worker_id"]))

    async def stop_workers(self, job_id, force=False):
        for addr, wid in self._workers.pop(job_id, []):
            client = self._client(addr)
            try:
                await client.call("StopWorker",
                                  {"worker_id": wid, "force": force})
            except Exception:
                logger.warning("StopWorker %s on %s failed", wid, addr)
            finally:
                await client.close()

    def workers_for_job(self, job_id):
        return [wid for _addr, wid in self._workers.get(job_id, [])]


def scheduler_from_env() -> Scheduler:
    """SCHEDULER env selection (schedulers/mod.rs:70-76 analog):
    'process' (default), 'kubernetes'/'k8s', or 'embedded'."""
    mode = os.environ.get("SCHEDULER", "process").lower()
    if mode in ("kubernetes", "k8s"):
        return KubernetesScheduler()
    if mode in ("embedded", "inprocess"):
        return InProcessScheduler()
    if mode == "node":
        return NodeScheduler()
    if mode == "nomad":
        return NomadScheduler()
    if mode in ("process", ""):
        return ProcessScheduler()
    # a typo must fail fast, not silently spawn subprocesses in the
    # controller container
    raise ValueError(f"unknown SCHEDULER {mode!r}; "
                     "expected process | kubernetes | embedded | node | nomad")
