"""REST API end-to-end: boot controller + ApiServer, exercise the public
HTTP surface the way the reference's integ binary does
(/root/reference/integ/src/main.rs:25-120): create a connection table,
create a pipeline, wait for Running, see checkpoints, stop gracefully.
"""

import asyncio
import json

import httpx
import pytest

from arroyo_tpu.api.rest import ApiServer
from arroyo_tpu.controller.controller import ControllerServer


@pytest.fixture()
def api_env(tmp_path, monkeypatch):
    monkeypatch.setenv("CHECKPOINT_URL", f"file://{tmp_path}/ckpt")

    async def boot():
        controller = ControllerServer()
        await controller.start()
        api = ApiServer(controller)
        port = await api.start()
        return controller, api, port

    loop = asyncio.new_event_loop()
    controller, api, port = loop.run_until_complete(boot())
    yield loop, controller, f"http://127.0.0.1:{port}"
    loop.run_until_complete(api.stop())
    loop.run_until_complete(controller.stop())
    loop.close()


def _run(loop, coro):
    return loop.run_until_complete(coro)


QUERY = """
CREATE TABLE impulse WITH (connector = 'impulse', event_rate = '1000',
  message_count = '5000', batch_size = '256');
SELECT counter, counter * 2 as doubled FROM impulse WHERE counter % 2 = 0
"""


def test_rest_lifecycle(api_env):
    loop, controller, base = api_env

    async def scenario():
        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            r = await c.get("/api/v1/ping")
            assert r.status_code == 200 and r.json()["pong"]

            # connector catalog
            r = await c.get("/v1/connectors")
            names = {x["id"] for x in r.json()["data"]}
            assert {"impulse", "nexmark", "kafka"} <= names

            # validate: good and bad SQL
            r = await c.post("/v1/pipelines/validate",
                             json={"query": QUERY})
            assert r.status_code == 200
            graph = r.json()["graph"]
            assert graph["nodes"] and graph["edges"]
            r = await c.post("/v1/pipelines/validate",
                             json={"query": "SELEC nonsense"})
            assert r.status_code == 400

            # create pipeline -> job runs
            r = await c.post("/v1/pipelines",
                             json={"name": "evens", "query": QUERY})
            assert r.status_code == 200, r.text
            pl = r.json()
            job_id = pl["jobs"][0]["id"]

            # poll job state through the API until terminal
            for _ in range(200):
                r = await c.get("/v1/jobs")
                job = next(j for j in r.json()["data"]
                           if j["id"] == job_id)
                if job["state"] in ("Finished", "Stopped", "Failed"):
                    break
                await asyncio.sleep(0.1)
            assert job["state"] == "Finished", job

            # pipeline listing + detail
            r = await c.get("/v1/pipelines")
            assert any(p["id"] == pl["id"] for p in r.json()["data"])
            r = await c.get(f"/v1/pipelines/{pl['id']}")
            assert r.json()["name"] == "evens"
            r = await c.get(f"/v1/pipelines/{pl['id']}/jobs")
            assert r.json()["data"][0]["id"] == job_id

            # errors endpoint: none for a clean run
            r = await c.get(f"/v1/pipelines/{pl['id']}/jobs/{job_id}/errors")
            assert r.json()["data"] == []

            # delete
            r = await c.request("DELETE", f"/v1/pipelines/{pl['id']}")
            assert r.status_code == 200
            r = await c.get(f"/v1/pipelines/{pl['id']}")
            assert r.status_code == 404

            # 404 / 405 semantics
            r = await c.get("/v1/nope")
            assert r.status_code == 404
            r = await c.request("DELETE", "/v1/jobs")
            assert r.status_code == 405

    _run(loop, scenario())


def test_connection_tables_and_sql_integration(api_env):
    loop, controller, base = api_env

    async def scenario():
        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            # unknown connector rejected
            r = await c.post("/v1/connection_tables", json={
                "name": "x", "connector": "noope", "config": {}})
            assert r.status_code == 400
            # invalid config rejected with 422
            r = await c.post("/v1/connection_tables", json={
                "name": "x", "connector": "impulse",
                "config": {"event_rate": "not-a-number"}})
            assert r.status_code == 422
            # test endpoint mirrors validation without persisting
            r = await c.post("/v1/connection_tables/test", json={
                "connector": "impulse", "config": {"event_rate": 10}})
            assert r.json()["ok"] is True

            # valid: saved table is visible to the SQL planner by name
            r = await c.post("/v1/connection_tables", json={
                "name": "ticks", "connector": "impulse",
                "config": {"event_rate": 1000, "message_count": 1000,
                           "batch_size": 128}})
            assert r.status_code == 200, r.text
            tid = r.json()["id"]
            r = await c.get("/v1/connection_tables")
            assert any(t["name"] == "ticks" for t in r.json()["data"])

            # duplicate name -> 409
            r = await c.post("/v1/connection_tables", json={
                "name": "ticks", "connector": "impulse",
                "config": {"event_rate": 1}})
            assert r.status_code == 409

            # pipeline referencing the saved table (no CREATE TABLE in SQL)
            r = await c.post("/v1/pipelines", json={
                "name": "from-saved",
                "query": "SELECT counter FROM ticks"})
            assert r.status_code == 200, r.text
            job_id = r.json()["jobs"][0]["id"]
            for _ in range(200):
                r = await c.get("/v1/jobs")
                job = next(j for j in r.json()["data"]
                           if j["id"] == job_id)
                if job["state"] in ("Finished", "Stopped", "Failed"):
                    break
                await asyncio.sleep(0.1)
            assert job["state"] == "Finished", job

            r = await c.request("DELETE", f"/v1/connection_tables/{tid}")
            assert r.status_code == 200
            r = await c.request("DELETE", f"/v1/connection_tables/{tid}")
            assert r.status_code == 404

    _run(loop, scenario())


def test_output_tailing_sse(api_env):
    """GrpcSink output reaches the REST SSE endpoint (jobs.rs:465+)."""
    loop, controller, base = api_env

    async def scenario():
        sql = """
        CREATE TABLE impulse WITH (connector = 'impulse',
          event_rate = '500', message_count = '3000', batch_size = '64');
        SELECT counter FROM impulse
        """
        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            r = await c.post("/v1/pipelines",
                             json={"name": "tail", "query": sql,
                                   "preview": True})
            assert r.status_code == 200, r.text
            job_id = r.json()["jobs"][0]["id"]

            rows = 0
            async with c.stream(
                    "GET", f"/v1/pipelines/{r.json()['id']}/jobs/{job_id}"
                    f"/output") as resp:
                assert resp.status_code == 200
                async for line in resp.aiter_lines():
                    if not line.startswith("data: "):
                        continue
                    event = json.loads(line[len("data: "):])
                    if event.get("done") or rows >= 100:
                        break
                    rows += len(event.get("rows", []))
            # the 6s paced run guarantees the subscription observes
            # live data, not just a clean termination
            assert rows >= 100, rows

    _run(loop, scenario())


def test_openapi_spec(api_env):
    """GET /api/v1/openapi.json describes the live route table."""
    loop, _ctrl, url = api_env

    async def fetch():
        async with httpx.AsyncClient() as c:
            return (await c.get(f"{url}/api/v1/openapi.json")).json()

    spec = _run(loop, fetch())
    assert spec["openapi"].startswith("3.")
    paths = spec["paths"]
    assert "/v1/pipelines" in paths
    assert "post" in paths["/v1/pipelines"] and "get" in paths["/v1/pipelines"]
    assert "/v1/pipelines/{id}" in paths
    assert paths["/v1/pipelines/{id}"]["get"]["parameters"][0]["name"] == "id"
    assert "/v1/connection_tables" in paths


def test_connection_profiles_and_schema_test(api_env):
    """Connection profiles (shared connector config merged into tables)
    and JSON-schema validation (connection_profiles.rs / test_schema)."""
    loop, _ctrl, url = api_env

    async def go():
        async with httpx.AsyncClient() as c:
            r = await c.post(f"{url}/v1/connection_profiles", json={
                "name": "kafka-prod", "connector": "kafka",
                "config": {"bootstrap_servers": "memory://prof"}})
            assert r.status_code == 200, r.text
            prof = r.json()
            listed = (await c.get(
                f"{url}/v1/connection_profiles")).json()["data"]
            assert [p["name"] for p in listed] == ["kafka-prod"]

            # table config merges the profile's connector settings
            r = await c.post(f"{url}/v1/connection_tables", json={
                "name": "evts", "connector": "kafka",
                "connection_profile_id": prof["id"],
                "config": {"topic": "t1"}})
            assert r.status_code == 200, r.text
            assert r.json()["config"]["bootstrap_servers"] == "memory://prof"

            # profile/connector mismatch is a conflict
            r = await c.post(f"{url}/v1/connection_tables", json={
                "name": "evts2", "connector": "impulse",
                "connection_profile_id": prof["id"], "config": {}})
            assert r.status_code == 409

            r = await c.post(
                f"{url}/v1/connection_tables/schemas/test", json={
                    "schema": {"type": "object", "properties": {
                        "id": {"type": "integer"},
                        "name": {"type": ["string", "null"]},
                        "at": {"type": "string", "format": "date-time"},
                        "nested": {"type": "object", "properties": {
                            "x": {"type": "number"}}},
                    }}})
            j = r.json()
            assert j["ok"], j
            types = {c_["name"]: c_["type"] for c_ in j["columns"]}
            assert types == {"id": "bigint", "name": "text",
                             "at": "timestamp", "nested.x": "double"}

            r = await c.post(
                f"{url}/v1/connection_tables/schemas/test",
                json={"schema": {"type": "array"}})
            assert not r.json()["ok"]

    _run(loop, go())


@pytest.mark.slow
def test_checkpoint_details_endpoint(api_env, tmp_path):
    """Per-operator checkpoint detail lists the parquet files an epoch
    wrote (get_checkpoint_details analog)."""
    loop, ctrl, url = api_env

    async def go():
        async with httpx.AsyncClient() as c:
            r = await c.post(f"{url}/v1/pipelines", json={
                "name": "ck", "query": """
CREATE TABLE impulse WITH (connector = 'impulse', event_rate = '3000',
  message_count = '100000', batch_size = '512');
SELECT counter % 5 as k, count(*) as cnt FROM impulse
GROUP BY 1, tumble(interval '1 second')"""})
            assert r.status_code == 200, r.text
            pid = r.json()["id"]
            jid = r.json()["jobs"][0]["id"]
            # wait for a finished checkpoint epoch
            epoch = None
            for _ in range(300):
                ck = (await c.get(
                    f"{url}/v1/pipelines/{pid}/jobs/{jid}/checkpoints")
                ).json()
                epoch = ck.get("last_successful_epoch")
                if epoch:
                    break
                await asyncio.sleep(0.1)
            assert epoch, ck
            r = await c.get(
                f"{url}/v1/pipelines/{pid}/jobs/{jid}/checkpoints/"
                f"{epoch}/operator_checkpoint_groups")
            j = r.json()
            assert j["epoch"] == epoch
            assert j["data"], j  # at least one operator wrote state
            assert all(g["bytes"] > 0 for g in j["data"])
            await c.patch(f"{url}/v1/pipelines/{pid}",
                          json={"stop": "immediate"})

    _run(loop, go())


@pytest.mark.slow
def test_rest_rescale_running_pipeline(api_env):
    """PATCH /v1/pipelines/{id} with a new parallelism on a RUNNING job
    drives the controller's live rescale (checkpoint-stop, re-shard,
    resume) through the public API; the job still finishes cleanly."""
    loop, controller, base = api_env

    sql = """
    CREATE TABLE impulse WITH (connector = 'impulse',
      event_rate = '8000', message_count = '40000', batch_size = '256',
      event_time_interval_micros = '1000');
    SELECT counter % 5 as bucket, TUMBLE(INTERVAL '1' SECOND) as window,
           count(*) as cnt
    FROM impulse GROUP BY 1, 2
    """

    async def scenario():
        async with httpx.AsyncClient(base_url=base) as c:
            r = await c.post("/v1/pipelines",
                             json={"name": "rescale-me", "query": sql})
            assert r.status_code == 200, r.text
            pl = r.json()
            job_id = pl["jobs"][0]["id"]

            # wait until Running, let it make progress
            for _ in range(200):
                r = await c.get("/v1/jobs")
                job = next(j for j in r.json()["data"] if j["id"] == job_id)
                if job["state"] == "Running":
                    break
                await asyncio.sleep(0.05)
            assert job["state"] == "Running", job
            await asyncio.sleep(0.8)

            r = await c.patch(f"/v1/pipelines/{pl['id']}",
                              json={"parallelism": 2})
            assert r.status_code == 200, r.text
            assert r.json()["parallelism"] == 2
            # the console distinguishes a LIVE rescale from a stored-
            # default update, and renders the refreshed graph
            assert r.json()["rescaled_jobs"] == [job_id]
            r = await c.get(f"/v1/pipelines/{pl['id']}")
            assert {n["parallelism"] for n in r.json()["graph"]["nodes"]} \
                == {2}

            # out-of-range parallelism is a 400, not an unbounded restart
            r = await c.patch(f"/v1/pipelines/{pl['id']}",
                              json={"parallelism": 9999})
            assert r.status_code == 400

            for _ in range(400):
                r = await c.get("/v1/jobs")
                job = next(j for j in r.json()["data"] if j["id"] == job_id)
                if job["state"] in ("Finished", "Stopped", "Failed"):
                    break
                await asyncio.sleep(0.1)
            assert job["state"] == "Finished", job

            # rescaling a pipeline whose job is terminal must not 500
            # (the FSM rejects transitions on terminal jobs): 200 with
            # an empty rescaled_jobs, and only the stored default moves
            r = await c.patch(f"/v1/pipelines/{pl['id']}",
                              json={"parallelism": 3})
            assert r.status_code == 200, r.text
            assert r.json()["rescaled_jobs"] == []

    _run(loop, scenario())


def test_rest_metrics_history_persists(api_env):
    """The API's sampler writes per-operator metrics history to sqlite
    and serves it back — a fresh console session (no in-browser state)
    can reconstruct throughput charts for a job that already ran."""
    loop, controller, base = api_env

    sql = """
    CREATE TABLE impulse WITH (connector = 'impulse',
      event_rate = '4000', message_count = '20000', batch_size = '256');
    SELECT counter, counter * 2 as doubled FROM impulse
    """

    async def scenario():
        async with httpx.AsyncClient(base_url=base) as c:
            r = await c.post("/v1/pipelines",
                             json={"name": "hist", "query": sql})
            assert r.status_code == 200, r.text
            pl = r.json()
            pid, job_id = pl["id"], pl["jobs"][0]["id"]

            # wait for the job to finish (several sampler ticks elapse)
            for _ in range(400):
                r = await c.get("/v1/jobs")
                job = next(j for j in r.json()["data"]
                           if j["id"] == job_id)
                if job["state"] in ("Finished", "Failed"):
                    break
                await asyncio.sleep(0.05)
            assert job["state"] == "Finished", job

            r = await c.get(
                f"/v1/pipelines/{pid}/jobs/{job_id}/metrics_history")
            assert r.status_code == 200
            data = r.json()["data"]
            assert data, "no metrics history sampled"
            # cumulative messages_sent must be monotone per operator and
            # show real progress (the 2s sampler may miss the final tick
            # before the job leaves the controller, so not the full count)
            monotone_ok, any_sent = True, 0.0
            for s in data:
                pts = s["points"]
                assert len(pts) >= 1
                for a, b in zip(pts, pts[1:]):
                    monotone_ok &= b[1] >= a[1]
                any_sent = max(any_sent, pts[-1][1])
            assert monotone_ok and any_sent >= 5000
    _run(loop, scenario())


@pytest.mark.slow
def test_generated_client_black_box_lifecycle(api_env):
    """Spec-validated, runtime-GENERATED client (api/client.py) drives a
    full pipeline lifecycle — every call goes through an operation the
    live /api/v1/openapi.json declares, the reference integ binary's
    generated-client discipline (integ/src/main.rs:25-120)."""
    loop, _ctrl, base = api_env

    from arroyo_tpu.api.client import (ApiError, generate_client,
                                       validate_spec)

    async def scenario():
        async with httpx.AsyncClient(timeout=30) as http:
            client = await generate_client(base, http)
            # the spec validated clean (generate_client raises otherwise);
            # prove the validator actually bites on a broken spec
            broken = json.loads(json.dumps(client.spec))
            broken["paths"]["/v1/pipelines/{id}"]["get"].pop("parameters")
            assert any("undeclared" in p for p in validate_spec(broken))

            assert (await client.ping())["pong"]
            ops = set(client.operations)
            assert {"create_pipeline", "list_jobs", "get_pipeline",
                    "delete_pipeline", "job_checkpoints",
                    "autoscaler_status", "autoscaler_update"} <= ops

            got = await client.validate_pipeline(body={"query": QUERY})
            assert got["graph"]["nodes"]

            pl = await client.create_pipeline(
                body={"name": "genclient", "query": QUERY})
            job_id = pl["jobs"][0]["id"]

            # autoscaler surface through the generated client: the job
            # starts with the loop disabled; a PUT round-trips a policy
            # knob merge and the enable flag
            st = await client.autoscaler_status(jid=job_id)
            assert st["enabled"] is False and st["decisions"] == []
            st = await client.autoscaler_update(
                jid=job_id, body={"enabled": True,
                                  "policy": {"high_water": 0.55}})
            assert st["enabled"] and st["policy"]["high_water"] == 0.55
            st = await client.autoscaler_update(jid=job_id,
                                                body={"enabled": False})
            assert st["enabled"] is False
            for _ in range(200):
                jobs = (await client.list_jobs())["data"]
                job = next(j for j in jobs if j["id"] == job_id)
                if job["state"] in ("Finished", "Stopped", "Failed"):
                    break
                await asyncio.sleep(0.1)
            assert job["state"] == "Finished", job

            detail = await client.get_pipeline(id=pl["id"])
            assert detail["name"] == "genclient"
            cks = await client.job_checkpoints(pid=pl["id"], jid=job_id)
            assert "data" in cks
            await client.delete_pipeline(id=pl["id"])
            try:
                await client.get_pipeline(id=pl["id"])
                assert False, "deleted pipeline still resolves"
            except ApiError as e:
                assert e.status == 404

    _run(loop, scenario())


def test_pipeline_detail_carries_graph_for_console_overlay(api_env):
    """/v1/pipelines/{id} returns the stored DAG (the console's live
    per-operator overlay renders it; list view stays lean)."""
    loop, _ctrl, base = api_env

    async def scenario():
        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            r = await c.post("/v1/pipelines",
                             json={"name": "dag", "query": QUERY})
            pid = r.json()["id"]
            detail = (await c.get(f"/v1/pipelines/{pid}")).json()
            g = detail["graph"]
            assert g and g["nodes"] and g["edges"]
            ids = {n["operator_id"] for n in g["nodes"]}
            assert all(e["src"] in ids and e["dst"] in ids
                       for e in g["edges"])
            listing = (await c.get("/v1/pipelines")).json()["data"]
            assert all("graph" not in p for p in listing)
            # console ships the overlay + checkpoint-detail machinery
            html = (await c.get("/")).text
            for needle in ("updateDagOverlay", "ov_bp_", "jobdag",
                           "ckptDetail", "operator_checkpoint_groups"):
                assert needle in html, needle

    _run(loop, scenario())


def test_preview_pipeline_streams_output_and_reaps(api_env):
    """preview: true (reference pipelines.rs:191-198) — connector sinks
    swap to the preview sink, parallelism forces 1, output streams via
    the SSE endpoint, and the job auto-stops after ttl_secs."""
    loop, ctrl, base = api_env

    q = """
    CREATE TABLE f WITH (connector = 'single_file',
      path = '/tmp/should_not_be_written.jsonl', type = 'sink');
    CREATE TABLE impulse WITH (connector = 'impulse',
      event_rate = '500', message_count = '3000', batch_size = '64');
    INSERT INTO f SELECT counter FROM impulse
    """

    async def scenario():
        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            r = await c.post("/v1/pipelines", json={
                "name": "pv", "query": q, "preview": True,
                "parallelism": 4, "ttl_secs": 20})
            assert r.status_code == 200, r.text
            pl = r.json()
            assert pl["preview"] is True
            g = pl["graph"]
            sinks = [n for n in g["nodes"] if "sink" in n["operator_id"]]
            assert sinks and all(n["parallelism"] == 1
                                 for n in g["nodes"])
            jid = pl["jobs"][0]["id"]
            # output reaches the SSE tail (preview sink -> controller);
            # the 6s paced run leaves plenty of stream to observe
            rows = []
            async with c.stream(
                    "GET",
                    f"/v1/pipelines/{pl['id']}/jobs/{jid}/output") as s:
                async for line in s.aiter_lines():
                    if line.startswith("data: "):
                        ev = json.loads(line[6:])
                        rows.extend(ev.get("rows") or [])
                        if ev.get("done") or len(rows) >= 300:
                            break
            assert len(rows) >= 300
            assert {r_["counter"] for r_ in rows} <= set(range(3000))
    _run(loop, scenario())
    import os
    assert not os.path.exists("/tmp/should_not_be_written.jsonl"), \
        "preview must not write to the real connector sink"


def test_preview_ttl_reaps_job(api_env):
    """A preview pipeline left running auto-stops after ttl_secs."""
    loop, ctrl, base = api_env

    q = """
    CREATE TABLE impulse WITH (connector = 'impulse',
      event_rate = '50', message_count = '10000000', batch_size = '32');
    SELECT counter FROM impulse
    """

    async def scenario():
        from arroyo_tpu.controller.state_machine import JobState

        async with httpx.AsyncClient(base_url=base, timeout=30) as c:
            r = await c.post("/v1/pipelines", json={
                "name": "reap", "query": q, "preview": True,
                "ttl_secs": 2})
            jid = r.json()["jobs"][0]["id"]
            state = await ctrl.wait_for_state(
                jid, JobState.STOPPED, JobState.FINISHED, timeout=45)
            assert state in (JobState.STOPPED, JobState.FINISHED), state

    _run(loop, scenario())


def test_cli_run_executes_sql(tmp_path):
    """`python -m arroyo_tpu run q.sql` executes locally and streams
    result rows as JSON lines (the reference binary's run UX)."""
    import os
    import subprocess
    import sys

    q = tmp_path / "q.sql"
    q.write_text(
        "CREATE TABLE impulse WITH (connector='impulse', "
        "event_rate='0', message_count='6', batch_size='2');"
        "SELECT counter FROM impulse WHERE counter % 2 = 0")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "arroyo_tpu", "run", str(q)],
        capture_output=True, text=True, timeout=240, env=env,
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-500:]
    rows = [json.loads(x) for x in r.stdout.strip().splitlines()]
    assert [row["counter"] for row in rows] == [0, 2, 4]


@pytest.mark.slow
def test_black_box_api_process(tmp_path):
    """Deploy-grade smoke: boot the real `api` role as an OS process
    (python -m arroyo_tpu api — controller + REST in one), drive a
    preview pipeline over plain HTTP through the spec-generated client,
    and observe streamed output.  The closest analog of running the
    reference's docker image and pointing integ at it."""
    import os
    import socket
    import subprocess
    import sys
    import time as _time

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    api_port, ctrl_port = free_port(), free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               API_PORT=str(api_port), API_HOST="127.0.0.1",
               CONTROLLER_PORT=str(ctrl_port),
               CONTROLLER_HOST="127.0.0.1",
               CHECKPOINT_URL=f"file://{tmp_path}/ckpt")
    proc = subprocess.Popen(
        [sys.executable, "-m", "arroyo_tpu", "api"], env=env,
        cwd="/root/repo", stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    base = f"http://127.0.0.1:{api_port}"
    try:
        from arroyo_tpu.api.client import generate_client

        async def scenario():
            async with httpx.AsyncClient(timeout=30) as http:
                for _ in range(100):  # wait for the process to listen
                    try:
                        r = await http.get(base + "/api/v1/ping")
                        if r.status_code == 200:
                            break
                    except httpx.TransportError:
                        await asyncio.sleep(0.2)
                else:
                    raise AssertionError("api process never came up")
                client = await generate_client(base, http)
                pl = await client.create_pipeline(body={
                    "name": "bb", "preview": True, "query": (
                        "CREATE TABLE impulse WITH (connector='impulse',"
                        " event_rate='0', message_count='500',"
                        " batch_size='64');"
                        "SELECT counter FROM impulse")})
                jid = pl["jobs"][0]["id"]
                for _ in range(150):
                    jobs = (await client.list_jobs())["data"]
                    job = next(j for j in jobs if j["id"] == jid)
                    if job["state"] in ("Finished", "Stopped", "Failed"):
                        break
                    await asyncio.sleep(0.2)
                assert job["state"] == "Finished", job

        asyncio.run(scenario())
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
