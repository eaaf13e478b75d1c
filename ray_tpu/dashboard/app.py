"""Dashboard server: cluster state as JSON + Prometheus text metrics.

Reference analog: dashboard/head.py:62 DashboardHead (+ the metrics
agent's Prometheus re-export, _private/metrics_agent.py:93).  One aiohttp
server inside a detached actor:

  GET /api/nodes | /api/actors | /api/tasks | /api/placement_groups
  GET /api/summary
  GET /metrics          (Prometheus text format)
"""

from __future__ import annotations

import asyncio
import threading
from typing import Optional

DASHBOARD_NAME = "RAYTPU_DASHBOARD"


def _merged_programs():
    """Fleet-wide program view: every live deployment's engine_stats()
    "programs" block merged over this process's own (mostly empty)
    registry — on a name collision the busiest replica view wins.
    Shared by /api/perf/programs and /api/perf/autopilot.  Returns
    (programs, per_deployment_blocks, devices)."""
    from ray_tpu._private import device_stats as ds

    devices = ds.device_memory_stats()
    programs = ds.get_registry().snapshot(
        n_devices=max(1, len(devices)))
    per_dep = {}
    try:
        from ray_tpu.serve import api as serve_api

        for name in serve_api.status():
            try:
                stats = serve_api.engine_stats(name, timeout=15)
            except Exception:  # noqa: BLE001 - no stats
                continue
            blocks = stats.get("programs")
            if not isinstance(blocks, dict):
                continue
            per_dep[name] = blocks
            for prog, blk in blocks.items():
                cur = programs.get(prog)
                if (cur is None or blk.get(
                        "compile_events", 0) >= cur.get(
                        "compile_events", 0)):
                    programs[prog] = blk
    except Exception:  # noqa: BLE001 - serve not running
        pass
    return programs, per_dep, devices


class DashboardActor:
    def __init__(self, host: str = "127.0.0.1", port: int = 8265):
        self.host = host
        self.port = port
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="dashboard")
        self._thread.start()
        self._started.wait(timeout=30)
        self._write_prom_service_discovery()

    def _write_prom_service_discovery(self) -> None:
        """Prometheus file-based service discovery (reference:
        _private/metrics_agent.py:340 PrometheusServiceDiscoveryWriter):
        point prometheus at
        <session_dir>/prom_metrics_service_discovery.json via
        file_sd_configs and it scrapes the cluster's /metrics."""
        import json
        import os

        from ray_tpu._private import worker_context

        node = worker_context.node()
        # the dashboard usually runs as a remote actor: no Node object in
        # this process, but every worker carries the session dir in env
        session_dir = (node.session_dir if node is not None
                       else os.environ.get("RAYTPU_SESSION_DIR", ""))
        if not session_dir:
            return
        path = os.path.join(session_dir,
                            "prom_metrics_service_discovery.json")
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump([{
                    "labels": {"job": "ray_tpu"},
                    "targets": [f"{self.host}:{self.port}"],
                }], f)
            os.replace(tmp, path)  # atomic: prometheus may be reading
        except OSError:
            pass

    def _state(self):
        from ray_tpu.util import state

        return state

    def _metrics_text(self) -> str:
        from ray_tpu.util import state

        lines = []
        nodes = state.list_nodes()
        lines.append("# TYPE raytpu_nodes gauge")
        lines.append(f"raytpu_nodes {sum(n['alive'] for n in nodes)}")
        for n in nodes:
            nid = n["node_id"][:12]
            for res, total in n["resources"].items():
                avail = n["available"].get(res, 0.0)
                name = res.lower().replace("-", "_")
                lines.append(
                    f'raytpu_resource_total{{node="{nid}",resource='
                    f'"{name}"}} {total}')
                lines.append(
                    f'raytpu_resource_available{{node="{nid}",resource='
                    f'"{name}"}} {avail}')
        actors = state.summarize_actors()
        lines.append("# TYPE raytpu_actors gauge")
        for st, count in actors["by_state"].items():
            lines.append(f'raytpu_actors{{state="{st}"}} {count}')
        tasks = state.summarize_tasks()
        lines.append("# TYPE raytpu_tasks_finished_total counter")
        lines.append(f"raytpu_tasks_finished_total {tasks['total']}")
        lines.append("# TYPE raytpu_task_execution_seconds_total counter")
        lines.append(f"raytpu_task_execution_seconds_total "
                     f"{tasks['total_execution_s']}")
        # Application-defined metrics published by every process
        # (ray_tpu.util.metrics -> GCS KV snapshots).
        from ray_tpu._private import worker_context
        from ray_tpu.util.metrics import collect_cluster_metrics

        cw = worker_context.maybe_core_worker()
        if cw is not None:
            try:
                lines.extend(collect_cluster_metrics(cw.kv_get,
                                                     cw.kv_keys))
            except Exception:  # noqa: BLE001 - metrics must not 500
                pass
        return "\n".join(lines) + "\n"

    def _serve(self):
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        state = self._state()

        def j(fn):
            async def handler(_req):
                data = await loop.run_in_executor(None, fn)
                return web.json_response(data)

            return handler

        async def metrics(_req):
            text = await loop.run_in_executor(None, self._metrics_text)
            return web.Response(text=text,
                                content_type="text/plain")

        app = web.Application()
        app.router.add_get("/api/nodes", j(state.list_nodes))
        app.router.add_get("/api/actors", j(state.list_actors))
        app.router.add_get("/api/tasks", j(state.list_tasks))
        app.router.add_get("/api/placement_groups",
                           j(state.list_placement_groups))
        app.router.add_get("/api/summary", j(lambda: {
            "tasks": state.summarize_tasks(),
            "actors": state.summarize_actors(),
            "nodes": len(state.list_nodes())}))
        app.router.add_get("/metrics", metrics)

        # Job submission REST (reference: dashboard/modules/job routes).
        from dataclasses import asdict

        from ray_tpu import job as job_api

        async def jobs_submit(req):
            body = await req.json()
            jid = await loop.run_in_executor(
                None, lambda: job_api.submit_job(
                    body["entrypoint"],
                    runtime_env=body.get("runtime_env"),
                    metadata=body.get("metadata"),
                    job_id=body.get("job_id")))
            return web.json_response({"job_id": jid})

        async def jobs_list(_req):
            jobs = await loop.run_in_executor(None, job_api.list_jobs)
            return web.json_response([asdict(i) for i in jobs])

        async def jobs_status(req):
            info = await loop.run_in_executor(
                None, lambda: job_api.get_job_info(
                    req.match_info["job_id"]))
            return web.json_response(asdict(info))

        async def jobs_logs(req):
            text = await loop.run_in_executor(
                None, lambda: job_api.get_job_logs(
                    req.match_info["job_id"]))
            return web.json_response({"logs": text})

        async def jobs_stop(req):
            ok = await loop.run_in_executor(
                None, lambda: job_api.stop_job(req.match_info["job_id"]))
            return web.json_response({"stopped": ok})

        app.router.add_post("/api/jobs", jobs_submit)
        app.router.add_get("/api/jobs", jobs_list)
        app.router.add_get("/api/jobs/{job_id}", jobs_status)
        app.router.add_get("/api/jobs/{job_id}/logs", jobs_logs)
        app.router.add_post("/api/jobs/{job_id}/stop", jobs_stop)

        # Declarative serve REST (reference: dashboard serve module,
        # PUT /api/serve/applications/ consuming ServeApplicationSchema).
        async def serve_apply(req):
            from ray_tpu.serve import schema as serve_schema

            body = await req.json()
            try:
                await loop.run_in_executor(
                    None, lambda: serve_schema.apply(body))
            except (ValueError, TypeError, KeyError, AttributeError,
                    ImportError) as e:
                # config/validation-class errors (bad types, unknown
                # import paths) are the CLIENT's fault: 400, not 500
                return web.json_response(
                    {"error": f"{type(e).__name__}: {e}"}, status=400)
            return web.json_response(
                await loop.run_in_executor(None, serve_schema.status))

        async def serve_status(_req):
            from ray_tpu.serve import schema as serve_schema

            return web.json_response(
                await loop.run_in_executor(None, serve_schema.status))

        app.router.add_put("/api/serve/applications", serve_apply)
        app.router.add_get("/api/serve/applications", serve_status)

        # Engine telemetry aggregation (serve/telemetry.py): one
        # engine_stats() snapshot per deployment whose replicas expose
        # it (LM engines); others report the reason they were skipped.
        async def serve_stats(_req):
            def _collect():
                from ray_tpu.serve import api as serve_api

                out = {}
                try:
                    deployments = serve_api.status()
                except Exception:  # noqa: BLE001 - serve not running
                    return out
                for name in deployments:
                    try:
                        out[name] = serve_api.engine_stats(name,
                                                           timeout=15)
                    except Exception as e:  # noqa: BLE001 - no stats
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/serve/stats", serve_stats)

        # SLO burn rates + flight-recorder occupancy (serve/slo.py,
        # _private/flightrec.py): the "slo"/"flightrec" blocks of each
        # deployment's engine_stats(), without the heavyweight rest —
        # the poll target for burn-rate dashboards and autoscalers.
        async def serve_slo(_req):
            def _collect():
                from ray_tpu.serve import api as serve_api

                out = {}
                try:
                    deployments = serve_api.status()
                except Exception:  # noqa: BLE001 - serve not running
                    return out
                for name in deployments:
                    try:
                        stats = serve_api.engine_stats(name,
                                                       timeout=15)
                        out[name] = {
                            "slo": stats.get("slo"),
                            "flightrec": stats.get("flightrec"),
                        }
                    except Exception as e:  # noqa: BLE001 - no stats
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/serve/slo", serve_slo)

        # kvscope (serve/kvscope.py): each deployment's "kv_scope"
        # block — KV pool occupancy ring, eviction forensics, HBM
        # ledger — without the heavyweight rest.  The dump feeds
        # `python -m ray_tpu.tools.kvscope report/timeline/export`
        # directly.
        async def serve_kvscope(_req):
            def _collect():
                from ray_tpu.serve import api as serve_api

                out = {}
                try:
                    deployments = serve_api.status()
                except Exception:  # noqa: BLE001 - serve not running
                    return out
                for name in deployments:
                    try:
                        stats = serve_api.engine_stats(name,
                                                       timeout=15)
                        out[name] = {
                            "kv_scope": stats.get("kv_scope"),
                            "kv_tier": stats.get("kv_tier"),
                        }
                    except Exception as e:  # noqa: BLE001 - no stats
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/serve/kvscope", serve_kvscope)

        # Fleet control plane (serve/router.py): every live
        # build_llm_fleet() in this process — routing policy mix,
        # pooled prefix hit rate, per-tenant SLO attainment, and the
        # autoscaler's current signals, keyed by fleet name.  The
        # document's "health" block is also served standalone at
        # /api/serve/health for liveness pollers.
        async def serve_fleet(_req):
            def _collect():
                from ray_tpu.serve.router import fleet_registry

                out = {}
                for name, fleet in fleet_registry().items():
                    try:
                        out[name] = fleet.fleet_stats()
                    except Exception as e:  # noqa: BLE001
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/serve/fleet", serve_fleet)

        # Healthwatch (serve/health.py): every live fleet's health
        # block only — per-replica liveness state, last-heartbeat age,
        # transition history, and detection latency — the poll target
        # for liveness dashboards.  The full fleet document above
        # (/api/serve/fleet) carries the same block under "health".
        async def serve_health(_req):
            def _collect():
                from ray_tpu.serve.router import fleet_registry

                out = {}
                for name, fleet in fleet_registry().items():
                    try:
                        out[name] = fleet._health_block()
                    except Exception as e:  # noqa: BLE001
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/serve/health", serve_health)

        # Trainwatch (train/telemetry.py + train/goodput.py): one
        # train_stats() snapshot per trainer that has stepped in THIS
        # process — step-time percentiles plus the anatomy / goodput /
        # health / checkpoint blocks, keyed by trainer name.
        async def train_stats_view(_req):
            def _collect():
                from ray_tpu.train.goodput import registered_trainers
                from ray_tpu.train.telemetry import train_stats

                out = {}
                for name in registered_trainers():
                    try:
                        out[name] = train_stats(name)
                    except Exception as e:  # noqa: BLE001
                        out[name] = {
                            "error": f"{type(e).__name__}: {e}"[:300]}
                return out

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/train/stats", train_stats_view)

        # Tracebus (ray_tpu/tools/tracebus.py): one request's causal
        # span tree — router.route → engine.queue/kv.reserve →
        # engine.prefill (+ matched device program dispatch) →
        # engine.decode — by trace id (full or prefix) or engine-local
        # id.  Fleets are scanned first (their find_request carries
        # the replica name); then every serve deployment exposing
        # request_trace.
        async def serve_trace(req):
            rid = req.match_info["request_id"]

            def _collect():
                from ray_tpu.serve.router import fleet_registry
                from ray_tpu.tools import tracebus

                snap = None
                for fleet in fleet_registry().values():
                    try:
                        snap = fleet.find_request(rid)
                    except Exception:  # noqa: BLE001
                        snap = None
                    if snap is not None:
                        break
                if snap is None:
                    import ray_tpu
                    from ray_tpu.serve import api as serve_api

                    try:
                        deployments = serve_api.status()
                    except Exception:  # noqa: BLE001
                        deployments = {}
                    for name in deployments:
                        try:
                            handle = serve_api.get_deployment_handle(
                                name)
                            snap = ray_tpu.get(
                                handle.method("request_trace")
                                .remote(rid), timeout=15)
                        except Exception:  # noqa: BLE001
                            snap = None
                        if snap is not None:
                            snap.setdefault("replica", name)
                            break
                if snap is None:
                    return None
                spans = tracebus.attach_device_spans(
                    tracebus.build_request_spans(snap), snap,
                    tracebus._device_programs())
                return dict(snap, spans=spans)

            data = await loop.run_in_executor(None, _collect)
            if data is None:
                return web.json_response(
                    {"error": f"request {rid!r} not found"},
                    status=404)
            return web.json_response(data)

        app.router.add_get("/api/serve/trace/{request_id}",
                           serve_trace)

        # Perf observatory (_private/device_stats.py): per-program
        # compiled cost model / recompile watchdog / live MFU, plus
        # per-chip allocator stats — the device-side complement of
        # /api/serve/stats.  Registries are per-process, so the
        # dashboard merges every live deployment's engine_stats()
        # "programs" block over its own (mostly empty) local registry;
        # on a name collision the busiest replica view wins, and the
        # raw per-deployment blocks stay under "deployments".
        async def perf_programs(_req):
            def _collect():
                from ray_tpu._private import device_stats as ds

                programs, per_dep, devices = _merged_programs()
                return {
                    "programs": programs,
                    "deployments": per_dep,
                    "devices": devices,
                    "peak_flops_per_chip": ds.peak_flops_per_chip(),
                }

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/perf/programs", perf_programs)

        # Autopilot (ray_tpu/tools/autopilot): the same merged program
        # view pushed through roofline attribution (which program is
        # the bottleneck, compute- vs HBM-bound) plus the ledger
        # verdict summary and the next planned sweep — the closed
        # tuning loop's state as one JSON document.
        async def perf_autopilot(req):
            budget = int(req.query.get("budget", 8))

            def _collect():
                from ray_tpu.tools.autopilot import (attribution,
                                                     verdict)

                programs, per_dep, _ = _merged_programs()
                # request-side evidence: the tracebus p99 critical
                # path over every live fleet's retained requests
                req_ev = None
                try:
                    from ray_tpu.serve.router import fleet_registry
                    from ray_tpu.tools import tracebus

                    reqs = []
                    for fleet in fleet_registry().values():
                        reqs.extend(fleet.trace_records())
                    if reqs:
                        req_ev = tracebus.request_evidence(
                            {"requests": reqs})
                except Exception:  # noqa: BLE001 - evidence optional
                    req_ev = None
                # memory-side evidence: the pooled kvscope block of
                # any live fleet (cache-thrash waste attribution)
                # plus its host-tier block (churn-absorption credit)
                kv_ev = None
                tier_ev = None
                try:
                    from ray_tpu.serve.router import fleet_registry

                    for fleet in fleet_registry().values():
                        fs = fleet.fleet_stats()
                        ks = fs.get("kv_scope")
                        kt = fs.get("kv_tier")
                        if ks and (ks.get("reprefill_waste_frac")
                                   or (kt or {}).get("tokens_restored")):
                            kv_ev = ks
                            if kt and kt.get("enabled"):
                                tier_ev = kt
                            break
                except Exception:  # noqa: BLE001 - evidence optional
                    kv_ev = None
                    tier_ev = None
                att = attribution.attribute(
                    programs, request_anatomy=req_ev, kv_scope=kv_ev,
                    kv_tier=tier_ev)
                try:
                    v = verdict.build_verdict(budget=budget,
                                              attribution=att)
                except Exception as e:  # noqa: BLE001 - no ledger
                    v = {"error": f"{type(e).__name__}: {e}"[:300],
                         "attribution": att}
                v["deployments"] = sorted(per_dep)
                return v

            return web.json_response(
                await loop.run_in_executor(None, _collect))

        app.router.add_get("/api/perf/autopilot", perf_autopilot)

        # On-demand profiler capture (util/state.py profile_device):
        # POST {"logdir": ..., "seconds": 1.0} traces this process for
        # the window and returns where the trace landed, or
        # {"ok": false, "error": ...} when the profiler will not run.
        async def perf_profile(req):
            try:
                body = await req.json()
            except Exception:  # noqa: BLE001 - empty body is fine
                body = {}
            logdir = str(body.get("logdir", "/tmp/raytpu_profile"))
            seconds = min(60.0, max(0.0,
                                    float(body.get("seconds", 1.0))))

            def _capture():
                import time as _time

                from ray_tpu.util.state import profile_device

                with profile_device(logdir):
                    _time.sleep(seconds)

            try:
                await loop.run_in_executor(None, _capture)
            except Exception as e:  # noqa: BLE001 - reported to caller
                return web.json_response(
                    {"ok": False, "logdir": logdir, "seconds": seconds,
                     "error": repr(e)})
            return web.json_response(
                {"ok": True, "logdir": logdir, "seconds": seconds})

        app.router.add_post("/api/perf/profile", perf_profile)

        # Structured events (reference: dashboard event module consuming
        # RAY_EVENT files, src/ray/util/event.h:41).
        async def events_list(req):
            from ray_tpu._private import events as ev

            recs = await loop.run_in_executor(
                None, lambda: ev.read_events(
                    limit=int(req.query.get("limit", 200)),
                    severity=req.query.get("severity"),
                    source=req.query.get("source")))
            return web.json_response(recs)

        app.router.add_get("/api/events", events_list)

        # Workflow event provider (reference:
        # workflow/http_event_provider.py — external systems POST an
        # event; in-cluster KVEventListeners wake on it).
        async def workflow_post_event(req):
            body = await req.json()
            name = body.get("name")
            if not name:
                return web.json_response(
                    {"error": "missing 'name'"}, status=400)

            def _post():
                from ray_tpu.workflow.events import post_event

                post_event(name, body.get("payload"))

            await loop.run_in_executor(None, _post)
            return web.json_response({"posted": name})

        app.router.add_post("/api/workflows/events", workflow_post_event)

        async def index(_req):
            from ray_tpu.dashboard.frontend import INDEX_HTML

            return web.Response(text=INDEX_HTML,
                                content_type="text/html")

        app.router.add_get("/", index)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, self.host, self.port)
        loop.run_until_complete(site.start())
        self._started.set()
        loop.run_forever()

    def address(self) -> str:
        return f"http://{self.host}:{self.port}"

    def ping(self) -> bool:
        return self._started.is_set()


def start_dashboard(port: int = 8265, host: str = "127.0.0.1") -> str:
    """Start (or find) the dashboard actor; returns its URL."""
    import ray_tpu

    ray_tpu._auto_init()
    try:
        actor = ray_tpu.get_actor(DASHBOARD_NAME)
    except Exception:  # noqa: BLE001
        actor = ray_tpu.remote(num_cpus=0.1, lifetime="detached",
                               name=DASHBOARD_NAME)(DashboardActor).remote(
            host, port)
    ray_tpu.get(actor.ping.remote(), timeout=60)
    return ray_tpu.get(actor.address.remote(), timeout=30)
