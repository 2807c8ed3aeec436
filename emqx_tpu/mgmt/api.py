"""REST management API (reference: apps/emqx_management/src/emqx_mgmt_api_*,
served at /api/v5 like the reference's minirest dashboard listener).

Endpoints:
  GET    /api/v5/status                       node + broker liveness
  GET    /api/v5/metrics                      counters
  GET    /api/v5/stats                        gauges
  GET    /api/v5/clients[?like=]              connected clients
  GET    /api/v5/clients/{clientid}
  DELETE /api/v5/clients/{clientid}           kick
  GET    /api/v5/subscriptions[?clientid=]
  GET    /api/v5/routes                       route table topics
  POST   /api/v5/publish                      {topic, payload, qos, retain}
  GET    /api/v5/banned  POST /api/v5/banned  DELETE /api/v5/banned/{kind}/{v}
  GET    /api/v5/retainer/messages
  DELETE /api/v5/retainer/message/{topic}
  GET    /api/v5/configs                      full running config

Auth: `Authorization: Bearer <api_key>` when dashboard.api_key is set
(emqx_mgmt_auth analog); open in dev mode otherwise.
"""

from __future__ import annotations

import asyncio
import base64
import dataclasses
import json
from typing import Optional

from aiohttp import web

from emqx_tpu.broker.banned import BanEntry
from emqx_tpu.broker.message import Message
from emqx_tpu.config.schema import to_dict
from emqx_tpu.utils.node import node_name


# the single route table: registration AND the OpenAPI document derive
# from it (emqx_dashboard_swagger generates both from one schema source)
ROUTES = [
    ("get", "/api/v5/status", "status", "Node and broker liveness", "node"),
    ("get", "/api/v5/cluster", "cluster_info", "Cluster membership", "node"),
    ("post", "/api/v5/nodes/drain", "node_drain",
     "Drain this node: stop accepting, park/hand off sessions "
     "(rolling-upgrade orchestration)", "node"),
    ("get", "/api/v5/metrics", "metrics", "Counter metrics", "metrics"),
    ("get", "/api/v5/metrics/hotpath", "metrics_hotpath",
     "Hot-path flight recorder: ingest/matcher/dispatch p50/p99, "
     "fallback rate, batch occupancy", "metrics"),
    ("get", "/api/v5/stats", "stats", "Gauge statistics", "metrics"),
    ("get", "/api/v5/clients", "clients", "List connected clients", "clients"),
    ("get", "/api/v5/clients/{clientid}", "client_one", "One client", "clients"),
    ("delete", "/api/v5/clients/{clientid}", "client_kick", "Kick a client", "clients"),
    ("get", "/api/v5/subscriptions", "subscriptions", "List subscriptions", "subscriptions"),
    ("get", "/api/v5/routes", "routes", "Route table topics", "routes"),
    ("post", "/api/v5/publish", "publish", "Publish a message", "publish"),
    ("get", "/api/v5/banned", "banned_list", "List bans", "banned"),
    ("post", "/api/v5/banned", "banned_add", "Add a ban", "banned"),
    ("delete", "/api/v5/banned/{kind}/{value}", "banned_del", "Remove a ban", "banned"),
    ("get", "/api/v5/retainer/messages", "retained_list", "List retained messages", "retainer"),
    ("delete", "/api/v5/retainer/message/{topic:.+}", "retained_del", "Delete retained message", "retainer"),
    ("get", "/api/v5/configs", "configs", "Full running config", "configs"),
    ("put", "/api/v5/configs/{path:.+}", "configs_update", "Update a config subtree at runtime", "configs"),
    ("get", "/api/v5/rules", "rules_list", "List rules", "rules"),
    ("post", "/api/v5/rules", "rules_create", "Create a rule", "rules"),
    ("get", "/api/v5/rules/{id}", "rules_one", "One rule", "rules"),
    ("delete", "/api/v5/rules/{id}", "rules_delete", "Delete a rule", "rules"),
    ("post", "/api/v5/rule_test", "rule_test", "Test a rule SQL", "rules"),
    ("get", "/api/v5/alarms", "alarms_list", "List alarms", "alarms"),
    ("delete", "/api/v5/alarms", "alarms_clear", "Clear deactivated alarms", "alarms"),
    ("get", "/api/v5/slow_subscriptions", "slow_subs_list", "Slow consumers top-k", "slow_subs"),
    ("delete", "/api/v5/slow_subscriptions", "slow_subs_clear", "Clear slow-subs records", "slow_subs"),
    ("get", "/api/v5/mqtt/topic_metrics", "topic_metrics_list", "Per-topic metrics", "topic_metrics"),
    ("post", "/api/v5/mqtt/topic_metrics", "topic_metrics_add", "Track a topic", "topic_metrics"),
    ("delete", "/api/v5/mqtt/topic_metrics/{topic:.+}", "topic_metrics_del", "Untrack a topic", "topic_metrics"),
    ("get", "/api/v5/prometheus/stats", "prometheus_stats", "Prometheus exposition", "metrics"),
    ("get", "/api/v5/semantic/filters", "semantic_list",
     "List embedding-filter subscriptions (docs/semantic_routing.md)",
     "semantic"),
    ("post", "/api/v5/semantic/filters", "semantic_attach",
     "Attach an embedding filter to an existing subscription",
     "semantic"),
    ("delete", "/api/v5/semantic/filters", "semantic_detach",
     "Detach embedding filters (?clientid=&topic_filter=)", "semantic"),
    ("get", "/api/v5/faults", "faults_list",
     "Armed fault-injection rules + degradation breaker states "
     "(docs/robustness.md)", "faults"),
    ("post", "/api/v5/faults", "faults_arm",
     "Arm a fault rule at a registered site (soak testing)", "faults"),
    ("delete", "/api/v5/faults", "faults_disarm",
     "Disarm fault rules (?site= for one, all otherwise)", "faults"),
    ("get", "/api/v5/profile", "profile_get",
     "Profiler snapshot: stage waterfall, the owner thread's section "
     "table and last stalls, hardware fingerprint, cached roofline "
     "(docs/observability.md)",
     "profile"),
    ("post", "/api/v5/profile", "profile_arm",
     "Arm a bounded jax.profiler trace capture {duration_s?, "
     "max_bytes?}, or {action: 'cost_harvest'} to (re)build the static "
     "cost matrix", "profile"),
    ("delete", "/api/v5/profile", "profile_disarm",
     "Stop the armed capture early (finalizes the trace directory)",
     "profile"),
    ("get", "/api/v5/trace/spans", "trace_spans",
     "Recent causal trace spans (publish -> batch -> device -> deliver "
     "ring buffer, OTLP-shaped)", "trace"),
    ("get", "/api/v5/trace", "trace_list", "List packet traces", "trace"),
    ("post", "/api/v5/trace", "trace_create", "Create a packet trace", "trace"),
    ("delete", "/api/v5/trace/{name}", "trace_delete", "Delete a trace", "trace"),
    ("put", "/api/v5/trace/{name}/stop", "trace_stop", "Stop a trace", "trace"),
    ("get", "/api/v5/trace/{name}/download", "trace_download", "Download trace log", "trace"),
    ("get", "/api/v5/exhooks", "exhooks_list", "List exhook servers", "exhook"),
    ("get", "/api/v5/gateways", "gateways_list", "List gateways", "gateways"),
    ("get", "/api/v5/gateways/{name}", "gateways_one", "One gateway", "gateways"),
    ("post", "/api/v5/gateways", "gateways_load", "Load a gateway", "gateways"),
    ("delete", "/api/v5/gateways/{name}", "gateways_unload", "Unload a gateway", "gateways"),
    ("get", "/api/v5/bridges", "bridges_list", "List bridges", "bridges"),
    ("post", "/api/v5/bridges", "bridges_create", "Create a bridge", "bridges"),
    ("delete", "/api/v5/bridges/{id}", "bridges_delete", "Delete a bridge", "bridges"),
    ("post", "/api/v5/bridges/{id}/restart", "bridges_restart", "Restart a bridge", "bridges"),
    ("get", "/api/v5/plugins", "plugins_list", "List plugins", "plugins"),
    ("post", "/api/v5/plugins/install", "plugins_install", "Install a plugin package", "plugins"),
    ("put", "/api/v5/plugins/{ref}/start", "plugins_start", "Start a plugin", "plugins"),
    ("put", "/api/v5/plugins/{ref}/stop", "plugins_stop", "Stop a plugin", "plugins"),
    ("delete", "/api/v5/plugins/{ref}", "plugins_delete", "Uninstall a plugin", "plugins"),
    ("get", "/api/v5/listeners", "listeners_list", "List listeners", "listeners"),
    ("post", "/api/v5/listeners", "listeners_create", "Create a listener", "listeners"),
    ("delete", "/api/v5/listeners/{id}", "listeners_delete", "Delete a listener", "listeners"),
    ("post", "/api/v5/listeners/{id}/stop", "listeners_stop", "Stop a listener", "listeners"),
    ("post", "/api/v5/listeners/{id}/start", "listeners_start", "Start a stopped listener", "listeners"),
    ("post", "/api/v5/listeners/{id}/restart", "listeners_restart", "Restart a listener", "listeners"),
    ("get", "/api/v5/authentication", "authn_list", "List authentication providers", "authentication"),
    ("post", "/api/v5/authentication", "authn_create", "Create an authentication provider", "authentication"),
    ("delete", "/api/v5/authentication/{id}", "authn_delete", "Remove an authentication provider", "authentication"),
    ("get", "/api/v5/authentication/{id}/users", "authn_users_list", "List builtin users", "authentication"),
    ("post", "/api/v5/authentication/{id}/users", "authn_users_add", "Add a builtin user", "authentication"),
    ("delete", "/api/v5/authentication/{id}/users/{user}", "authn_users_del", "Delete a builtin user", "authentication"),
    ("get", "/api/v5/authorization/sources", "authz_sources_list", "List authorization sources", "authorization"),
    ("post", "/api/v5/authorization/sources", "authz_sources_create", "Add an authorization source", "authorization"),
    ("delete", "/api/v5/authorization/sources/{type}", "authz_sources_delete", "Remove an authorization source", "authorization"),
    ("post", "/api/v5/authorization/sources/{type}/move", "authz_sources_move", "Reorder an authorization source", "authorization"),
    ("get", "/api/v5/api_key", "api_keys_list", "List API keys", "api_keys"),
    ("post", "/api/v5/api_key", "api_keys_create", "Create an API key (secret shown once)", "api_keys"),
    ("get", "/api/v5/api_key/{name}", "api_keys_get", "One API key", "api_keys"),
    ("put", "/api/v5/api_key/{name}", "api_keys_update", "Update an API key", "api_keys"),
    ("delete", "/api/v5/api_key/{name}", "api_keys_delete", "Delete an API key", "api_keys"),
    ("get", "/api/v5/telemetry/data", "telemetry_data", "Inspect the telemetry report", "telemetry"),
    ("get", "/api/v5/node_dump", "node_dump", "Full node state dump", "node"),
    ("get", "/api-docs", "api_docs", "This OpenAPI document", "meta"),
    ("post", "/api/v5/login", "login", "Obtain an admin JWT", "dashboard"),
    ("get", "/api/v5/monitor_current", "monitor_current", "Latest monitor sample", "dashboard"),
    ("get", "/api/v5/monitor_history", "monitor_history", "Monitor sample history", "dashboard"),
    ("get", "/api/v5/monitor", "monitor_ws", "Live monitor stream (WebSocket)", "dashboard"),
    ("get", "/", "index_page", "Status page", "dashboard"),
]

# reachable without credentials (login mints them; the page fetches the
# sample endpoint, which stays protected)
_PUBLIC_PATHS = {"/api/v5/login", "/"}


class MgmtApi:
    def __init__(self, app):
        self.app = app
        self.broker = app.broker
        self.cm = app.cm
        self._runner: Optional[web.AppRunner] = None
        self.port: Optional[int] = None

        from emqx_tpu.mgmt.api_keys import ApiKeyStore
        from emqx_tpu.mgmt.dashboard import DashboardAdmin, Monitor

        d = app.config.dashboard
        self.admin = DashboardAdmin(d.admins, ttl=d.jwt_ttl)
        self.api_keys = ApiKeyStore()
        # authn providers created over REST: id -> (provider, connector)
        self._authn_by_id = {}
        self.monitor = Monitor(
            app, interval=d.monitor_interval, history=d.monitor_history
        )

        w = web.Application(middlewares=[self._auth_middleware])
        w.add_routes(
            [
                getattr(web, method)(path, getattr(self, handler))
                for method, path, handler, _summary, _tag in ROUTES
            ]
        )
        self._webapp = w

    @web.middleware
    async def _auth_middleware(self, request, handler):
        key = self.app.config.dashboard.api_key
        needs_auth = bool(
            key or self.admin.has_admins() or self.api_keys.has_keys()
        )
        if needs_auth and request.path not in _PUBLIC_PATHS:
            auth = request.headers.get("Authorization", "")
            ok = bool(key) and auth == f"Bearer {key}"
            if not ok and auth.startswith("Bearer "):
                # admin JWT (emqx_dashboard_admin tokens)
                ok = self.admin.verify(auth[7:]) is not None
            if not ok and auth.startswith("Basic "):
                try:
                    decoded = base64.b64decode(auth[6:]).decode()
                    user, _, secret = decoded.partition(":")
                    # machine API keys (emqx_mgmt_auth), the static key as
                    # a password, or the legacy bare-key form (no colon)
                    ok = self.api_keys.verify(user, secret) or (
                        bool(key)
                        and (secret == key or (not secret and user == key))
                    )
                except Exception:
                    ok = False
            if not ok:
                return web.json_response(
                    {"code": "UNAUTHORIZED"}, status=401
                )
        return await handler(request)

    async def start(self, bind: str, port: int) -> None:
        self._runner = web.AppRunner(self._webapp)
        await self._runner.setup()
        site = web.TCPSite(self._runner, bind, port)
        await site.start()
        self.port = self._runner.addresses[0][1] if self._runner.addresses else port
        self.monitor.start()

    async def stop(self) -> None:
        await self.monitor.stop()
        if self._runner is not None:
            await self._runner.cleanup()

    # -- dashboard (emqx_dashboard admin/monitor analogs) ------------------
    async def login(self, request):
        try:
            body = await request.json()
            token = self.admin.login(body["username"], body["password"])
        except (ValueError, KeyError, TypeError):
            token = None
        if token is None:
            return web.json_response({"code": "BAD_USERNAME_OR_PWD"}, status=401)
        return web.json_response(
            {"token": token, "version": __import__("emqx_tpu").__version__}
        )

    async def monitor_current(self, request):
        return web.json_response(self.monitor.sample())

    async def monitor_history(self, request):
        return web.json_response({"data": self.monitor.samples})

    async def monitor_ws(self, request):
        ws = web.WebSocketResponse(heartbeat=30)
        await ws.prepare(request)
        q = self.monitor.subscribe()

        async def pump():
            try:
                await ws.send_json(self.monitor.sample())
                while True:
                    await ws.send_json(await q.get())
            except (ConnectionError, asyncio.CancelledError):
                pass

        task = asyncio.get_running_loop().create_task(pump())
        try:
            # drain client frames so the CLOSE handshake completes (a
            # handler parked only on q.get() would never see it)
            async for _ in ws:
                pass
        finally:
            task.cancel()
            self.monitor.unsubscribe(q)
        return ws

    async def index_page(self, request):
        from emqx_tpu.mgmt.dashboard import STATUS_PAGE

        return web.Response(text=STATUS_PAGE, content_type="text/html")

    # -- handlers ----------------------------------------------------------
    async def status(self, request):
        return web.json_response(
            {
                "node": node_name(),
                "status": "running",
                "version": __import__("emqx_tpu").__version__,
                "uptime_seconds": self.broker.metrics.snapshot()[
                    "uptime_seconds"
                ],
                "connections": self.cm.channel_count(),
                "subscriptions": self.broker.subscription_count(),
                "routes": len(self.broker.router),
                "retained": len(self.app.retainer),
            }
        )

    async def cluster_info(self, request):
        """Membership + route-table view (emqx_mgmt_api_nodes analog)."""
        node = getattr(self.app, "cluster_node", None)
        if node is None:
            return web.json_response(
                {"enabled": False, "nodes": [node_name()]}
            )
        return web.json_response(
            {
                "enabled": True,
                "name": node.name,
                "running_nodes": node.membership.running_nodes(),
                "stats": node.stats(),
            }
        )

    async def node_drain(self, request):
        """Rolling-upgrade drain (see BrokerApp.drain): body may name the
        handoff peer ({"peer": "n2@host"}); defaults to the first live
        peer. The caller stops/replaces the process afterwards."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            body = {}
        out = await self.app.drain(peer=body.get("peer"))
        return web.json_response(out)

    async def metrics(self, request):
        return web.json_response(self.broker.metrics.snapshot())

    async def metrics_hotpath(self, request):
        """Flight-recorder summary of the ingest -> matcher -> dispatch
        pipeline: histogram percentiles, fallback rates, batch occupancy
        (docs/observability.md). The before/after read for perf PRs."""
        from emqx_tpu.observe import provenance as _provenance
        from emqx_tpu.observe.profiler import (
            roofline_summary as _roofline_summary,
            waterfall as _waterfall,
        )

        m = self.broker.metrics
        _prof = getattr(self.app, "profiler", None)

        def hist(name, scale=1.0):
            h = m.histogram(name)
            if h is None or h.count == 0:
                return None
            return {
                "count": h.count,
                "mean": (h.sum / h.count) * scale,
                "p50": h.p50 * scale,
                "p95": h.p95 * scale,
                "p99": h.p99 * scale,
            }

        # read live (the mesh.shard.* gauges refresh every 30th tick):
        # the emptiest shard's fill and the table bytes on each device
        mesh_live = {}
        _dev = self.broker._device
        if self.broker.mesh is not None and hasattr(_dev, "shard_status"):
            _st = _dev.shard_status()
            mesh_live = {
                "shard_fill_min": _st.get("lane_fill_min"),
                "device_bytes": _st["device_bytes"],
            }
        routed_dev = m.get("messages.routed.device")
        routed_fb = m.get("messages.routed.device_fallback")
        routed_total = routed_dev + routed_fb
        occ = m.histogram("ingest.batch.occupancy")
        ing = getattr(self.broker, "ingest", None)
        slo = getattr(ing, "slo", None) if ing is not None else None
        out = {
            "ingest": {
                "batch_size": hist("ingest.batch.size"),
                "batch_occupancy_mean": (
                    occ.sum / occ.count if occ and occ.count else None
                ),
                "window_wait_ms": hist("ingest.window.wait.seconds", 1e3),
                "settle_ms": hist("ingest.settle.seconds", 1e3),
                "pipeline_depth": m.gauge("ingest.pipeline.depth"),
                "launch_errors": m.get("ingest.launch.errors"),
                "dispatch_errors": m.get("ingest.dispatch.errors"),
            },
            "slo": (
                {
                    # live controller state (broker/slo.py): the window
                    # it chose, the tail it observed, the ladder rung
                    # it stands on, and the lane depths behind it
                    **slo.to_json(),
                    "eval_windows": m.get("slo.eval.windows"),
                    "violations": m.get("slo.violations"),
                    "adjustments": m.get("slo.adjustments"),
                    "deferrals": m.get("slo.deferrals"),
                    "sheds": m.get("slo.shed"),
                    "olp_pressure": (
                        ing.olp.pressure()
                        if ing is not None and ing.olp is not None
                        else None
                    ),
                    "lane_depth": {
                        "control": m.gauge("ingest.lane.depth.control"),
                        "normal": m.gauge("ingest.lane.depth.normal"),
                        "low": m.gauge("ingest.lane.depth.low"),
                    },
                    "lane_settle_ms": {
                        "control": hist(
                            "ingest.lane.settle.seconds.control", 1e3
                        ),
                        "normal": hist(
                            "ingest.lane.settle.seconds.normal", 1e3
                        ),
                        "low": hist(
                            "ingest.lane.settle.seconds.low", 1e3
                        ),
                    },
                    "starvation_breaks": m.get(
                        "ingest.lane.starvation.breaks"
                    ),
                    "storm_deferred": m.get("retained.storm.deferred"),
                }
                if slo is not None
                else None
            ),
            "router": {
                "device_ms": hist("router.device.seconds", 1e3),
                "sync_ms": hist("router.sync.seconds", 1e3),
                "batch_size": hist("router.batch.size"),
                "prepare_dirty": m.get("router.prepare.dirty"),
                "sync_skipped": m.get("router.sync.skipped"),
            },
            "sub_table": {
                # subscriber-table representation (docs/serving_pipeline
                # "subscriber-table memory budget"): mode + live device
                # footprint straight from the table, overflow/flip
                # counters from the flight recorder
                **self.broker.subtab.status(),
                "overflow_rows": m.get("router.sparse.overflow.rows"),
                "rep_flips": m.get("router.sparse.flips"),
            },
            "segment": {
                "hot_fill": m.gauge("router.segment.hot.fill"),
                "hot_capacity": m.gauge("router.segment.hot.capacity"),
                "tombstones": m.gauge("router.segment.tombstones"),
                "compact_runs": m.get("router.compact.runs"),
                "compact_aborted": m.get("router.compact.aborted"),
                "compact_merged": m.get("router.compact.merged"),
                "compact_ms": hist("router.compact.seconds", 1e3),
                "compact_lag_s": m.gauge("router.compact.lag.seconds"),
            },
            "session": (
                {
                    **self.broker.session_store.status(),
                    "ack_rides": m.get("session.ack.rides"),
                    "ack_rows": m.get("session.ack.rows"),
                    "ack_scatters": m.get("session.ack.scatters"),
                    "sweeps_device": m.get("session.sweep.device"),
                    "sweeps_host": m.get("session.sweep.host"),
                    "redeliveries": m.get("session.redeliveries"),
                    "resumed": m.get("session.resume.replayed"),
                }
                if self.broker.session_store is not None
                else None
            ),
            "mesh": {
                "shape": (
                    f"{self.broker.mesh.shape['dp']}x"
                    f"{self.broker.mesh.shape['tp']}"
                    if self.broker.mesh is not None
                    else None
                ),
                "shard_label": self.broker.shard_label,
                "shard_count": m.gauge("mesh.shard.count"),
                "shard_fill_max": m.gauge("mesh.shard.fill"),
                **mesh_live,
                "scatter_launches": m.get("mesh.shard.scatter.launches"),
                "compact_runs": m.get("mesh.shard.compact.runs"),
                "rebalance_events": m.get("mesh.shard.rebalance"),
                "reroutes": m.get("mesh.shard.reroutes"),
            },
            "semantic": (
                {
                    **self.broker.semantic.status(),
                    "hits": m.get("semantic.hits"),
                    "topk_truncated": m.get("semantic.topk.truncated"),
                    "host_batches": m.get("semantic.host.batches"),
                    "host_matches": m.get("semantic.host.matches"),
                    "embed_rejected": m.get("semantic.embed.rejected"),
                }
                if self.broker.semantic is not None
                else None
            ),
            "rules": {
                "matched": m.get("rules.matched"),
                "passed": m.get("rules.passed"),
                "failed": m.get("rules.failed"),
                "dropped": m.get("rules.dropped"),
                "device_batches": m.get("rules.device.batches"),
                "host_batches": m.get("rules.host.batches"),
            },
            "fabric": {
                "slab_pub_frames": m.get("fabric.slab.pub.frames"),
                "slab_pub_records": m.get("fabric.slab.pub.records"),
                "slab_dlv_frames": m.get("fabric.slab.dlv.frames"),
                "slab_dlv_records": m.get("fabric.slab.dlv.records"),
                "zerocopy_records": m.get("ingest.zerocopy.records"),
                "zerocopy_deferred_bytes": m.get(
                    "ingest.zerocopy.deferred.bytes"
                ),
                "serialize_batches": m.get("dispatch.serialize.batches"),
                "serialize_frames": m.get("dispatch.serialize.frames"),
                "serialize_bytes": m.get("dispatch.serialize.bytes"),
                "raw_records": m.get("fabric.raw.records"),
                "parked_dropped": m.get("fabric.parked.dropped"),
                "flush_errors": m.get("fabric.flush.errors"),
            },
            "dispatch": {
                "fanout": hist("dispatch.fanout"),
                "routed_device": routed_dev,
                "routed_device_fallback": routed_fb,
                "fallback_rate": (
                    routed_fb / routed_total if routed_total else None
                ),
            },
            "device": {
                "compile_count": m.get("device.compile.count"),
                "compile_ms": hist("device.compile.seconds", 1e3),
                "compile_cache_size": m.gauge("device.compile.cache_size"),
                "hbm_bytes": m.gauge("device.hbm.bytes"),
                "transfer_bytes": m.get("device.transfer.bytes"),
            },
            "trace": {
                "spans_sampled": m.get("trace.spans.sampled"),
                "spans_dropped": m.get("trace.spans.dropped"),
            },
            "profile": {
                "waterfall": _waterfall(m),
                "capture_armed": _prof.armed if _prof else False,
                "captures": m.get("profile.captures"),
                "fingerprint": _provenance.fingerprint_key(),
                "proxy": _provenance.is_proxy(),
                "roofline": _roofline_summary(
                    _prof.cost_cached() if _prof else None
                ),
            },
            "alarms": {
                "tpu_fallback_rate_active": self.app.alarms.is_active(
                    "tpu_fallback_rate"
                ),
                "tpu_retrace_storm_active": self.app.alarms.is_active(
                    "tpu_retrace_storm"
                ),
                "slo_p99_violation_active": self.app.alarms.is_active(
                    "slo_p99_violation"
                ),
            },
        }
        return web.json_response(out)

    async def stats(self, request):
        return web.json_response(
            {
                "connections.count": self.cm.channel_count(),
                "subscriptions.count": self.broker.subscription_count(),
                "topics.count": len(self.broker.router),
                "retained.count": len(self.app.retainer),
                "delayed.count": len(self.app.delayed),
            }
        )

    def _client_json(self, ch):
        return {
            "clientid": ch.client_id,
            "username": ch.username,
            "proto_ver": ch.version,
            "clean_start": ch.clean_start,
            "keepalive": ch.keepalive,
            "connected_at": ch.connected_at,
            "peerhost": ch.conninfo.get("peerhost"),
            "subscriptions_cnt": len(ch.session.subscriptions)
            if ch.session
            else 0,
        }

    async def clients(self, request):
        like = request.query.get("like", "")
        out = [
            self._client_json(self.cm.get_channel(cid))
            for cid in self.cm.client_ids()
            if like in cid
        ]
        return web.json_response({"data": out, "meta": {"count": len(out)}})

    async def client_one(self, request):
        ch = self.cm.get_channel(request.match_info["clientid"])
        if ch is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response(self._client_json(ch))

    async def client_kick(self, request):
        ok = self.cm.kick_client(request.match_info["clientid"])
        if not ok:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({}, status=204)

    async def subscriptions(self, request):
        cid = request.query.get("clientid")
        out = [
            {
                "clientid": c,
                "topic": f,
                "qos": o.qos,
                "no_local": o.no_local,
            }
            for (c, f, o) in self.broker.subscriptions()
            if cid is None or c == cid
        ]
        return web.json_response({"data": out, "meta": {"count": len(out)}})

    async def routes(self, request):
        topics = self.broker.router.topics()
        return web.json_response(
            {"data": topics, "meta": {"count": len(topics)}}
        )

    async def publish(self, request):
        from emqx_tpu.ops import topics as T

        try:
            body = await request.json()
            topic = body["topic"]
            payload = body.get("payload", "")
            if not isinstance(topic, str) or not isinstance(payload, str):
                raise KeyError("topic/payload must be strings")
            T.validate(topic, kind="name")
            if body.get("payload_encoding") == "base64":
                payload = base64.b64decode(payload, validate=True)
            else:
                payload = payload.encode()
            qos = body.get("qos", 0)
            if isinstance(qos, bool) or not isinstance(qos, int) or qos not in (0, 1, 2):
                raise ValueError(f"invalid qos {qos!r}")
            retain = body.get("retain", False)
            if not isinstance(retain, bool):
                raise ValueError(f"invalid retain {retain!r}")
        except (json.JSONDecodeError, KeyError, ValueError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        # apublish: API publishes traverse the full async extension chain
        # (exhook message.publish) exactly like client traffic
        n = await self.broker.apublish(
            Message(
                topic=topic,
                payload=payload,
                qos=qos,
                retain=retain,
                from_client="mgmt_api",
            )
        )
        return web.json_response({"delivered": n})

    # -- rules (emqx_mgmt_api rules + emqx_rule_engine_api parity) ---------
    def _rule_json(self, rule):
        return {
            "id": rule.id,
            "sql": rule.sql,
            "enable": rule.enabled,
            "description": rule.description,
            "outputs": [o.name for o in rule.outputs],
            "metrics": rule.metrics.as_dict(),
        }

    async def rules_list(self, request):
        eng = self.app.rule_engine
        return web.json_response(
            {"data": [self._rule_json(r) for r in eng.rules()]}
        )

    async def rules_create(self, request):
        from emqx_tpu.rules import SqlParseError
        from emqx_tpu.rules.engine import Console, Republish

        eng = self.app.rule_engine
        try:
            body = await request.json()
            rule_id = str(body["id"])
            sql = str(body["sql"])
            outputs = []
            for spec in body.get("outputs", [{"function": "console"}]):
                fn = spec.get("function", "console")
                if fn == "republish":
                    args = spec.get("args", {})
                    outputs.append(
                        Republish(
                            topic=str(args["topic"]),
                            payload=str(args.get("payload", "${payload}")),
                            qos=int(args.get("qos", 0)),
                            retain=bool(args.get("retain", False)),
                        )
                    )
                elif fn == "console":
                    outputs.append(Console())
                else:
                    raise ValueError(f"unknown output function {fn!r}")
            rule = eng.create_rule(
                rule_id, sql, outputs, str(body.get("description", ""))
            )
            rule.enabled = bool(body.get("enable", True))
            eng.refresh_device()
        except (json.JSONDecodeError, KeyError, ValueError, TypeError, SqlParseError) as e:
            # ValueError also covers duplicate rule ids (create_rule)
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(self._rule_json(rule), status=201)

    async def rules_one(self, request):
        rule = self.app.rule_engine.get_rule(request.match_info["id"])
        if rule is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response(self._rule_json(rule))

    async def rules_delete(self, request):
        if not self.app.rule_engine.delete_rule(request.match_info["id"]):
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({}, status=204)

    async def rule_test(self, request):
        from emqx_tpu.rules import SqlParseError, test_sql
        from emqx_tpu.rules.runtime import RuleEvalError

        try:
            body = await request.json()
            rows = test_sql(str(body["sql"]), dict(body.get("context", {})))
        except (
            json.JSONDecodeError,
            KeyError,
            ValueError,
            TypeError,
            SqlParseError,
            RuleEvalError,
        ) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response({"match": rows is not None, "rows": rows})

    async def banned_list(self, request):
        return web.json_response(
            {
                "data": [
                    dataclasses.asdict(e) for e in self.app.banned.entries()
                ]
            }
        )

    async def banned_add(self, request):
        try:
            body = await request.json()
            kind = body["as"]
            if kind not in ("clientid", "username", "peerhost"):
                raise ValueError(f"invalid kind {kind!r}")
            self.app.banned.add(
                BanEntry(
                    kind=kind,
                    value=str(body["who"]),
                    by=str(body.get("by", "mgmt_api")),
                    reason=str(body.get("reason", "")),
                    until=float(body.get("until", float("inf"))),
                )
            )
        except (json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response({}, status=201)

    async def banned_del(self, request):
        ok = self.app.banned.delete(
            request.match_info["kind"], request.match_info["value"]
        )
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"}, status=204 if ok else 404
        )

    async def retained_list(self, request):
        # cursor-paged (a multi-million-message store must not dump in
        # one response; emqx_retainer_mnesia paged-read parity): pass
        # ?limit= and the meta.cursor of the previous page
        try:
            limit = min(int(request.query.get("limit", 10000)), 100000)
        except ValueError:
            limit = 10000
        cursor = request.query.get("cursor") or None
        msgs, nxt = self.app.retainer.messages_page(cursor, limit)
        return web.json_response(
            {
                "data": [m.topic for m in msgs],
                "meta": {
                    "count": len(self.app.retainer),
                    "limit": limit,
                    "cursor": nxt,
                    "hasnext": nxt is not None,
                },
            }
        )

    async def retained_del(self, request):
        ok = self.app.retainer.delete(request.match_info["topic"])
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"}, status=204 if ok else 404
        )

    async def configs(self, request):
        return web.json_response(to_dict(self.app.config))

    # -- observability (emqx_mgmt_api_alarms/trace, emqx_slow_subs REST,
    #    emqx_topic_metrics REST, emqx_prometheus scrape) ------------------
    async def alarms_list(self, request):
        q = request.query.get("activated")
        activated = None if q is None else q in ("true", "1")
        return web.json_response({"data": self.app.alarms.list(activated)})

    async def alarms_clear(self, request):
        n = self.app.alarms.delete_all_deactivated()
        return web.json_response({"cleared": n}, status=200)

    # -- semantic routing plane (broker/semantic.py,
    #    docs/semantic_routing.md) -----------------------------------------
    async def semantic_list(self, request):
        sem = self.broker.semantic
        if sem is None:
            return web.json_response(
                {"code": "NOT_ENABLED",
                 "message": "semantic.enable is off"}, status=404,
            )
        return web.json_response(
            {"status": sem.status(), "data": sem.entries()}
        )

    async def semantic_attach(self, request):
        """Attach an embedding filter to an EXISTING subscription:
        {clientid, topic_filter, embedding (JSON list | base64 f32le),
        threshold?}. The subscription then delivers on topic match AND
        similarity; re-POST replaces the embedding in place."""
        sem = self.broker.semantic
        if sem is None:
            return web.json_response(
                {"code": "NOT_ENABLED",
                 "message": "semantic.enable is off"}, status=404,
            )
        try:
            body = await request.json()
            cid = str(body["clientid"])
            tf = str(body["topic_filter"])
            from emqx_tpu.broker.semantic import decode_embedding

            vec = decode_embedding(body["embedding"], sem.table.dim)
            th = float(body.get("threshold", sem.default_threshold))
        except (json.JSONDecodeError, KeyError, ValueError,
                TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        b = self.broker
        entry = b._subs.get(tf) or {}
        sub = entry.get(cid)
        if sub is None or sub.slot < 0:
            return web.json_response(
                {"code": "NOT_FOUND",
                 "message": f"no subscription {tf!r} for {cid!r}"},
                status=404,
            )
        fid = b.router.filter_id(tf)
        if not sub.semantic and fid is not None:
            # the slot migrates from the fan-out table to the semantic
            # table — same transition the SUBSCRIBE path performs
            b.subtab.remove(fid, sub.slot)
        sub.semantic = True
        sem.attach(
            cid, sub.slot, vec, th,
            fid=-1 if fid is None else fid, scope=tf,
        )
        return web.json_response(
            {"slot": sub.slot, "threshold": th}, status=201
        )

    async def semantic_detach(self, request):
        """Detach filters; ?clientid= narrows to one client,
        &topic_filter= to one subscription (which reverts to plain
        fan-out delivery)."""
        sem = self.broker.semantic
        if sem is None:
            return web.json_response(
                {"code": "NOT_ENABLED"}, status=404
            )
        cid = request.query.get("clientid")
        tf = request.query.get("topic_filter")
        b = self.broker
        n = 0
        for item in list(sem.entries()):
            if cid is not None and item["clientid"] != cid:
                continue
            if tf is not None and item["topic_filter"] != tf:
                continue
            slot = item["slot"]
            sem.detach(slot)
            sub = (
                b._slot_subs[slot]
                if 0 <= slot < len(b._slot_subs)
                else None
            )
            if sub is not None and sub.semantic:
                sub.semantic = False
                fid = b.router.filter_id(sub.filter)
                if fid is not None:
                    b.subtab.add(fid, slot)
            n += 1
        return web.json_response({"detached": n})

    # -- fault injection + degradation (observe/faults.py,
    #    broker/degrade.py; docs/robustness.md) ----------------------------
    async def faults_list(self, request):
        out = self.app.faults.snapshot()
        deg = getattr(self.app, "degrade", None)
        out["degrade"] = deg.to_json() if deg is not None else None
        return web.json_response(out)

    async def faults_arm(self, request):
        """Arm one rule: {site, mode?, probability?, nth?, max_fires?,
        delay_ms?}. The injector validates site/mode/probability against
        the same registry the config loader enforces."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        if not isinstance(body, dict):
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        try:
            rule = self.app.faults.arm(
                str(body.get("site", "")),
                mode=str(body.get("mode", "raise")),
                probability=float(body.get("probability", 1.0)),
                nth=int(body.get("nth", 0)),
                max_fires=int(body.get("max_fires", 0)),
                delay_ms=float(body.get("delay_ms", 0.0)),
            )
        except (ValueError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(rule.to_json(), status=201)

    async def faults_disarm(self, request):
        site = request.query.get("site")
        self.app.faults.disarm(site)
        return web.Response(status=204)

    # -- performance provenance & device profiling (observe/profiler.py,
    #    observe/provenance.py; docs/observability.md) --------------------
    async def profile_get(self, request):
        from emqx_tpu.observe import provenance
        from emqx_tpu.observe.profiler import section_table, waterfall

        prof = self.app.profiler
        m = self.broker.metrics
        out = prof.snapshot()
        out["waterfall"] = waterfall(m)
        # the owner thread's time budget: sections, loop, last stalls
        out.update(section_table(m, prof.budget))
        from emqx_tpu.observe.device_watch import compiles_by_section

        out["compiles_by_section"] = compiles_by_section()
        out["fingerprint"] = provenance.fingerprint()
        cost = prof.cost_cached()
        if cost is not None:
            out["cost"] = cost
        return web.json_response(out)

    async def profile_arm(self, request):
        """Arm a bounded trace capture: {duration_s?, max_bytes?} (both
        clamped against the profiler's configured ceilings) and
        {python_tracer?} (frames in the trace; off by default: it about
        halves the host's rate while armed), or run the
        static cost harvest with {action: 'cost_harvest',
        max_configs_per_kernel?, refresh?} — the harvest compiles every
        contract kernel, so it runs on the executor, not the loop."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        prof = self.app.profiler
        if body.get("action") == "cost_harvest":
            import asyncio

            cap = body.get("max_configs_per_kernel")
            result = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: prof.cost_harvest(
                    int(cap) if cap else None,
                    refresh=bool(body.get("refresh", False)),
                ),
            )
            return web.json_response(
                {
                    "kernels": sorted({r["kernel"] for r in result["rows"]}),
                    "rows": len(result["rows"]),
                    "skipped": result["skipped"],
                    "proxy": result["proxy"],
                },
                status=201,
            )
        try:
            info = prof.arm(
                duration_s=body.get("duration_s"),
                max_bytes=body.get("max_bytes"),
                python_tracer=bool(body.get("python_tracer", False)),
            )
        except (RuntimeError, ValueError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(info, status=201)

    async def profile_disarm(self, request):
        entry = self.app.profiler.disarm(reason="rest")
        if entry is None:
            return web.Response(status=204)
        return web.json_response(entry)

    async def slow_subs_list(self, request):
        return web.json_response({"data": self.app.slow_subs.topk()})

    async def slow_subs_clear(self, request):
        self.app.slow_subs.clear()
        return web.Response(status=204)

    async def topic_metrics_list(self, request):
        return web.json_response(self.app.topic_metrics.metrics())

    async def topic_metrics_add(self, request):
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        topic = body.get("topic", "")
        try:
            created = self.app.topic_metrics.register(topic)
        except OverflowError:
            return web.json_response({"code": "QUOTA_EXCEEDED"}, status=409)
        except Exception:
            return web.json_response({"code": "BAD_TOPIC"}, status=400)
        if not created:
            return web.json_response({"code": "ALREADY_EXISTED"}, status=409)
        return web.json_response({"topic": topic}, status=201)

    async def topic_metrics_del(self, request):
        ok = self.app.topic_metrics.deregister(request.match_info["topic"])
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"}, status=204 if ok else 404
        )

    async def prometheus_stats(self, request):
        from emqx_tpu.observe.exporters import prometheus_exposition

        extra = {
            "connections.count": self.cm.channel_count(),
            "subscriptions.count": self.broker.subscription_count(),
            "topics.count": len(self.broker.router),
            "retained.count": len(self.app.retainer),
        }
        if self.app.os_mon is not None:
            extra["cpu.usage"] = self.app.os_mon.cpu_usage
            extra["mem.usage"] = self.app.os_mon.mem_usage
        if self.app.vm_mon is not None:
            extra["tasks.count"] = self.app.vm_mon.task_count
        from emqx_tpu.observe.profiler import flush

        # the section accumulators land in the registry at scrape
        flush(self.broker.metrics)
        body = prometheus_exposition(
            self.broker.metrics.snapshot(),
            extra,
            histograms=self.broker.metrics.histograms(),
        )
        return web.Response(text=body, content_type="text/plain")

    async def trace_spans(self, request):
        """Recent causal spans (observe/spans.py ring buffer), newest
        first, OTLP/JSON-shaped. Query: `limit` (default 100),
        `trace_id` (filter to one trace — follow a single publish
        through batch/device/deliver and across cluster forwards)."""
        rec = getattr(self.app, "spans", None)
        if rec is None:
            return web.json_response(
                {"data": [], "enabled": False}
            )
        try:
            limit = int(request.query.get("limit", 100))
        except ValueError:
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        return web.json_response(
            {
                "data": rec.recent(
                    limit=limit, trace_id=request.query.get("trace_id")
                ),
                "enabled": True,
                "sampled": self.broker.metrics.get("trace.spans.sampled"),
                "dropped": self.broker.metrics.get("trace.spans.dropped"),
            }
        )

    async def trace_list(self, request):
        return web.json_response({"data": self.app.trace.list()})

    async def trace_create(self, request):
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        try:
            spec = self.app.trace.create(
                name=body["name"],
                type=body["type"],
                value=body.get(body.get("type"), body.get("value", "")),
                start_at=body.get("start_at"),
                end_at=body.get("end_at"),
            )
        except KeyError:
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        except ValueError as e:
            code = (
                "ALREADY_EXISTED" if "existed" in str(e) else "BAD_REQUEST"
            )
            return web.json_response(
                {"code": code}, status=409 if code == "ALREADY_EXISTED" else 400
            )
        except OverflowError:
            return web.json_response({"code": "QUOTA_EXCEEDED"}, status=409)
        return web.json_response(
            {"name": spec.name, "type": spec.type, "status": spec.status()},
            status=201,
        )

    async def trace_delete(self, request):
        ok = self.app.trace.delete(request.match_info["name"])
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"}, status=204 if ok else 404
        )

    async def trace_stop(self, request):
        ok = self.app.trace.stop(request.match_info["name"])
        return web.json_response(
            {"status": "stopped"} if ok else {"code": "NOT_FOUND"},
            status=200 if ok else 404,
        )

    async def exhooks_list(self, request):
        ex = getattr(self.app, "exhook", None)
        return web.json_response({"data": ex.info() if ex else []})

    async def configs_update(self, request):
        """PUT /configs/{path}: runtime config update through the
        validated handler pipeline (emqx_config_handler + PUT /configs)."""
        from emqx_tpu.config.schema import ConfigError

        path = request.match_info["path"].replace("/", ".")
        try:
            value = await request.json()
        except ValueError:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": "invalid JSON"}, status=400
            )
        try:
            new_subtree = self.app.config_handler.update(path, value)
        except ConfigError as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        except Exception as e:
            return web.json_response(
                {"code": "UPDATE_FAILED", "message": str(e)}, status=500
            )
        return web.json_response(new_subtree)

    # -- plugins / telemetry (emqx_plugins + emqx_telemetry analogs) -------
    async def plugins_list(self, request):
        pm = self.app._plugin_manager()
        return web.json_response({"data": pm.list()})

    async def plugins_install(self, request):
        from emqx_tpu.plugins import PluginError

        body = await request.json()
        try:
            p = self.app._plugin_manager().install(body["path"])
        except (KeyError, PluginError, OSError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(
            {"name": p.name, "version": p.version}, status=201
        )

    async def plugins_start(self, request):
        from emqx_tpu.plugins import PluginError

        try:
            self.app._plugin_manager().start(request.match_info["ref"])
        except PluginError as e:
            return web.json_response(
                {"code": "NOT_FOUND", "message": str(e)}, status=404
            )
        return web.json_response({"status": "running"})

    async def plugins_stop(self, request):
        from emqx_tpu.plugins import PluginError

        try:
            self.app._plugin_manager().stop(request.match_info["ref"])
        except PluginError as e:
            return web.json_response(
                {"code": "NOT_FOUND", "message": str(e)}, status=404
            )
        return web.json_response({"status": "stopped"})

    async def plugins_delete(self, request):
        from emqx_tpu.plugins import PluginError

        try:
            self.app._plugin_manager().uninstall(request.match_info["ref"])
        except PluginError as e:
            return web.json_response(
                {"code": "NOT_FOUND", "message": str(e)}, status=404
            )
        return web.json_response({}, status=204)

    async def telemetry_data(self, request):
        t = self.app.telemetry
        if t is None:
            from emqx_tpu.observe.telemetry import Telemetry

            t = self.app.telemetry = Telemetry(self.app)
        return web.json_response(t.get_telemetry_data())

    async def node_dump(self, request):
        from emqx_tpu.utils.node_dump import collect

        return web.json_response(collect(self.app), dumps=lambda o: json.dumps(o, default=str))

    async def api_docs(self, request):
        from emqx_tpu import __version__
        from emqx_tpu.mgmt.openapi import build_spec

        spec = build_spec(
            [(m, p, s, t) for m, p, _h, s, t in ROUTES], __version__
        )
        return web.json_response(spec)

    # -- listeners (emqx_mgmt_api_listeners analog) ------------------------
    async def listeners_list(self, request):
        rows = self.app.listeners.describe()
        # worker-pool listeners (multi-process data plane) are owned by
        # the worker processes, not the in-process registry — surface
        # them so the operator sees every serving port
        rows += [
            pool.describe() for pool in getattr(self.app, "worker_pools", [])
        ]
        return web.json_response({"data": rows})

    @staticmethod
    def _listener_id(request):
        lid = request.match_info["id"]
        if ":" not in lid:
            raise ValueError("listener id is type:name")
        return lid.split(":", 1)

    async def listeners_create(self, request):
        from emqx_tpu.transport.listener import ListenerConfig

        try:
            body = await request.json()
            config = ListenerConfig(
                name=body.get("name", "default"),
                type=body.get("type", "tcp"),
                bind=body.get("bind", "127.0.0.1"),
                port=int(body.get("port", 1883)),
                max_connections=int(body.get("max_connections", 1_024_000)),
                ssl_certfile=body.get("ssl_certfile"),
                ssl_keyfile=body.get("ssl_keyfile"),
                ssl_cacertfile=body.get("ssl_cacertfile"),
                ssl_verify=bool(body.get("ssl_verify", False)),
            )
            l = await self.app.listeners.start_listener(
                config, self.app.channel_config
            )
        except (ValueError, TypeError, OSError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(
            {"id": f"{config.type}:{config.name}", "port": l.port},
            status=201,
        )

    async def listeners_delete(self, request):
        try:
            type_, name = self._listener_id(request)
        except ValueError as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        if not await self.app.listeners.delete_listener(type_, name):
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({}, status=204)

    async def _listener_action(self, request, action):
        try:
            type_, name = self._listener_id(request)
            if action == "stop":
                ok = await self.app.listeners.stop_listener(type_, name)
                if not ok:
                    return web.json_response(
                        {"code": "NOT_FOUND"}, status=404
                    )
            elif action == "start":
                await self.app.listeners.start_stopped(type_, name)
            else:
                await self.app.listeners.restart_listener(type_, name)
        except KeyError:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        except (ValueError, OSError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response({})

    async def listeners_stop(self, request):
        return await self._listener_action(request, "stop")

    async def listeners_start(self, request):
        return await self._listener_action(request, "start")

    async def listeners_restart(self, request):
        return await self._listener_action(request, "restart")

    # -- authentication chain (emqx_authn_api analog) ----------------------
    def _authn_chain(self):
        """The live AuthChain, created+attached on first REST use."""
        if self.app.authn is None:
            from emqx_tpu.broker.auth import AuthChain

            self.app.authn = AuthChain(
                [],
                allow_anonymous=self.app.config.authn.allow_anonymous,
            )
            self.app.authn.attach(self.app.hooks)
        return self.app.authn

    async def authn_list(self, request):
        chain = self.app.authn
        rows = []
        for p in chain.providers if chain else []:
            pid = getattr(p, "_api_id", None) or type(p).__name__
            rows.append(
                {"id": pid, "provider": type(p).__name__, "enable": True}
            )
        return web.json_response({"data": rows})

    async def _make_authn_provider(self, pid: str, body: dict):
        """-> (provider, connector|None); raises ValueError."""
        backend = pid.split(":", 1)[1] if ":" in pid else pid
        if backend == "built_in_database":
            from emqx_tpu.broker.auth import BuiltinDatabase

            db = BuiltinDatabase(
                user_id_type=body.get("user_id_type", "username"),
                algo=body.get("password_hash_algorithm", "pbkdf2"),
            )
            return db, None
        if backend == "jwt":
            from emqx_tpu.broker.auth import JwtAuth

            secret = body.get("secret")
            if not secret:
                raise ValueError("jwt provider needs 'secret'")
            return (
                JwtAuth(secret.encode(), body.get("verify_claims", {})),
                None,
            )
        if backend == "http":
            from emqx_tpu.auth.http import HttpAuthProvider

            if not body.get("url"):
                raise ValueError("http provider needs 'url'")
            return (
                HttpAuthProvider(
                    body["url"],
                    method=body.get("method", "POST"),
                    timeout=float(body.get("timeout", 5.0)),
                ),
                None,
            )
        if backend == "redis":
            from emqx_tpu.integration.redis import (
                RedisAuthProvider,
                RedisConnector,
            )

            server = body.get("server", "127.0.0.1:6379")
            host, _, port = server.partition(":")
            conn = RedisConnector(
                host=host or "127.0.0.1",
                port=int(port or 6379),
                db=int(body.get("database", 0)),
                password=body.get("password"),
            )
            await conn.start()
            return (
                RedisAuthProvider(
                    conn,
                    key_template=body.get("cmd_key", "mqtt_user:${username}"),
                    algo=body.get("password_hash_algorithm", "sha256"),
                ),
                conn,
            )
        if backend == "ldap":
            from emqx_tpu.integration.ldap import (
                LdapAuthProvider,
                LdapConnector,
            )

            server = body.get("server", "127.0.0.1:389")
            host, _, port = server.partition(":")
            conn = LdapConnector(
                host=host or "127.0.0.1",
                port=int(port or 389),
                bind_dn=body.get("bind_dn", ""),
                bind_password=body.get("bind_password", ""),
                base_dn=body.get("base_dn", ""),
            )
            await conn.start()
            return (
                LdapAuthProvider(
                    conn,
                    mode=body.get("method", "bind"),
                    dn_template=body.get(
                        "dn_template", "cn=${username},${base_dn}"
                    ),
                    filter_attr=body.get("filter_attr", "uid"),
                    hash_attr=body.get("hash_attr", "userPassword"),
                    algo=body.get("password_hash_algorithm", "plain"),
                ),
                conn,
            )
        if backend == "mongodb":
            from emqx_tpu.integration.mongodb import (
                MongoAuthProvider,
                MongoConnector,
            )

            server = body.get("server", "127.0.0.1:27017")
            host, _, port = server.partition(":")
            conn = MongoConnector(
                host=host or "127.0.0.1",
                port=int(port or 27017),
                username=body.get("username", ""),
                password=body.get("password", ""),
                database=body.get("database", "mqtt"),
                auth_source=body.get("auth_source", "admin"),
            )
            await conn.start()
            return (
                MongoAuthProvider(
                    conn,
                    collection=body.get("collection", "mqtt_user"),
                    filter_template=body.get("filter"),
                    algo=body.get("password_hash_algorithm", "sha256"),
                ),
                conn,
            )
        if backend in ("mysql", "postgresql", "pgsql"):
            from emqx_tpu.integration.sql_common import DEFAULT_AUTHN_QUERY

            if backend == "mysql":
                from emqx_tpu.integration.mysql import (
                    MysqlAuthProvider as Prov,
                    MysqlConnector as Conn,
                )
                default_port = 3306
            else:
                from emqx_tpu.integration.pgsql import (
                    PgsqlAuthProvider as Prov,
                    PgsqlConnector as Conn,
                )
                default_port = 5432
            server = body.get("server", "127.0.0.1")
            host, _, port = server.partition(":")
            conn = Conn(
                host=host or "127.0.0.1",
                port=int(port or default_port),
                user=body.get("username", ""),
                password=body.get("password", ""),
                database=body.get("database", ""),
            )
            await conn.start()
            return (
                Prov(
                    conn,
                    query=body.get("query", DEFAULT_AUTHN_QUERY),
                    algo=body.get("password_hash_algorithm", "sha256"),
                ),
                conn,
            )
        raise ValueError(f"unknown authn backend: {backend}")

    async def authn_create(self, request):
        try:
            body = await request.json()
            mechanism = body.get("mechanism", "password_based")
            backend = body.get("backend", "built_in_database")
            pid = f"{mechanism}:{backend}"
            if pid in self._authn_by_id:
                return web.json_response(
                    {"code": "ALREADY_EXISTS"}, status=409
                )
            provider, conn = await self._make_authn_provider(pid, body)
        except (ValueError, KeyError, TypeError, OSError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        provider._api_id = pid
        self._authn_by_id[pid] = (provider, conn)
        self._authn_chain().providers.append(provider)
        return web.json_response({"id": pid}, status=201)

    async def authn_delete(self, request):
        pid = request.match_info["id"]
        entry = self._authn_by_id.pop(pid, None)
        chain = self.app.authn
        if entry is None or chain is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        provider, conn = entry
        if provider in chain.providers:
            chain.providers.remove(provider)
        if conn is not None:
            try:
                await conn.stop()
            except Exception:
                pass
        return web.json_response({}, status=204)

    def _builtin_db(self, pid):
        from emqx_tpu.broker.auth import BuiltinDatabase

        entry = self._authn_by_id.get(pid)
        provider = entry[0] if entry else None
        if (
            provider is None
            and pid == "password_based:built_in_database"
            and self.app.authn is not None
        ):
            # the config-file-created builtin database has no REST id;
            # only the canonical id may address it
            for p in self.app.authn.providers:
                if isinstance(p, BuiltinDatabase):
                    return p
        return provider if isinstance(provider, BuiltinDatabase) else None

    async def authn_users_list(self, request):
        db = self._builtin_db(request.match_info["id"])
        if db is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({"data": db.users()})

    async def authn_users_add(self, request):
        db = self._builtin_db(request.match_info["id"])
        if db is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        try:
            body = await request.json()
            db.add_user(
                body["user_id"],
                body["password"],
                bool(body.get("is_superuser", False)),
            )
        except (ValueError, KeyError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response({"user_id": body["user_id"]}, status=201)

    async def authn_users_del(self, request):
        db = self._builtin_db(request.match_info["id"])
        if db is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        if not db.delete_user(request.match_info["user"]):
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({}, status=204)

    # -- authorization sources (emqx_authz_api_sources analog) -------------
    async def authz_sources_list(self, request):
        rows = []
        for s in self.app.authz.sources:
            rows.append(
                {
                    "type": getattr(s, "_api_type", type(s).__name__),
                    "enable": True,
                }
            )
        return web.json_response({"data": rows})

    async def authz_sources_create(self, request):
        try:
            body = await request.json()
            stype = body["type"]
            if any(
                getattr(s, "_api_type", None) == stype
                for s in self.app.authz.sources
            ):
                return web.json_response(
                    {"code": "ALREADY_EXISTS"}, status=409
                )
            source, conn = await self._make_authz_source(stype, body)
        except (ValueError, KeyError, TypeError, OSError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        source._api_type = stype
        source._api_conn = conn
        self.app.authz.add_source(source)
        return web.json_response({"type": stype}, status=201)

    async def _make_authz_source(self, stype: str, body: dict):
        if stype == "http":
            from emqx_tpu.auth.http import HttpAuthzSource

            if not body.get("url"):
                raise ValueError("http source needs 'url'")
            return (
                HttpAuthzSource(
                    body["url"],
                    method=body.get("method", "POST"),
                    timeout=float(body.get("timeout", 5.0)),
                ),
                None,
            )
        if stype == "redis":
            from emqx_tpu.integration.redis import (
                RedisAuthzSource,
                RedisConnector,
            )

            server = body.get("server", "127.0.0.1:6379")
            host, _, port = server.partition(":")
            conn = RedisConnector(
                host=host or "127.0.0.1",
                port=int(port or 6379),
                db=int(body.get("database", 0)),
                password=body.get("password"),
            )
            await conn.start()
            return (
                RedisAuthzSource(
                    conn,
                    key_template=body.get("cmd_key", "mqtt_acl:${username}"),
                ),
                conn,
            )
        if stype == "mongodb":
            from emqx_tpu.integration.mongodb import (
                MongoAuthzSource,
                MongoConnector,
            )

            server = body.get("server", "127.0.0.1:27017")
            host, _, port = server.partition(":")
            conn = MongoConnector(
                host=host or "127.0.0.1",
                port=int(port or 27017),
                username=body.get("username", ""),
                password=body.get("password", ""),
                database=body.get("database", "mqtt"),
                auth_source=body.get("auth_source", "admin"),
            )
            await conn.start()
            return (
                MongoAuthzSource(
                    conn,
                    collection=body.get("collection", "mqtt_acl"),
                    filter_template=body.get("filter"),
                ),
                conn,
            )
        if stype in ("mysql", "postgresql", "pgsql"):
            from emqx_tpu.integration.sql_common import DEFAULT_AUTHZ_QUERY

            if stype == "mysql":
                from emqx_tpu.integration.mysql import (
                    MysqlAuthzSource as Src,
                    MysqlConnector as Conn,
                )
                default_port = 3306
            else:
                from emqx_tpu.integration.pgsql import (
                    PgsqlAuthzSource as Src,
                    PgsqlConnector as Conn,
                )
                default_port = 5432
            server = body.get("server", "127.0.0.1")
            host, _, port = server.partition(":")
            conn = Conn(
                host=host or "127.0.0.1",
                port=int(port or default_port),
                user=body.get("username", ""),
                password=body.get("password", ""),
                database=body.get("database", ""),
            )
            await conn.start()
            return Src(conn, query=body.get("query", DEFAULT_AUTHZ_QUERY)), conn
        raise ValueError(f"unknown authz source type: {stype}")

    def _find_authz_source(self, stype: str):
        for s in self.app.authz.sources:
            if getattr(s, "_api_type", None) == stype:
                return s
        return None

    async def authz_sources_delete(self, request):
        s = self._find_authz_source(request.match_info["type"])
        if s is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        self.app.authz.sources.remove(s)
        conn = getattr(s, "_api_conn", None)
        if conn is not None:
            try:
                await conn.stop()
            except Exception:
                pass
        return web.json_response({}, status=204)

    async def authz_sources_move(self, request):
        s = self._find_authz_source(request.match_info["type"])
        if s is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        try:
            body = await request.json()
            position = body["position"]  # front | rear | before:T | after:T | index
        except (ValueError, KeyError, TypeError):
            return web.json_response({"code": "BAD_REQUEST"}, status=400)
        src = self.app.authz.sources
        # resolve the target index BEFORE mutating, so a bad position
        # leaves the evaluation order untouched
        if position == "front":
            idx = 0
        elif position == "rear":
            idx = len(src)  # after removal this is the end
        elif isinstance(position, str) and position.partition(":")[0] in (
            "before",
            "after",
        ):
            rel, _, other_type = position.partition(":")
            other = self._find_authz_source(other_type)
            if other is None or other is s:
                return web.json_response({"code": "BAD_REQUEST"}, status=400)
            idx = src.index(other) + (1 if rel == "after" else 0)
        else:
            try:
                idx = int(position)
            except (ValueError, TypeError):
                return web.json_response({"code": "BAD_REQUEST"}, status=400)
        cur = src.index(s)
        src.remove(s)
        if cur < idx:
            idx -= 1  # removal shifted everything after s left by one
        src.insert(min(max(idx, 0), len(src)), s)
        return web.json_response({})

    # -- API keys (emqx_mgmt_auth analog) -----------------------------------
    async def api_keys_list(self, request):
        return web.json_response({"data": self.api_keys.list()})

    async def api_keys_create(self, request):
        from emqx_tpu.mgmt.api_keys import DuplicateKey

        try:
            body = await request.json()
            rec = self.api_keys.create(
                body["name"],
                description=body.get("description", ""),
                enable=bool(body.get("enable", True)),
                expired_at=body.get("expired_at"),
            )
        except DuplicateKey as e:
            return web.json_response(
                {"code": "ALREADY_EXISTS", "message": str(e)}, status=409
            )
        except (ValueError, KeyError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(rec, status=201)

    async def api_keys_get(self, request):
        rec = self.api_keys.get(request.match_info["name"])
        if rec is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response(rec)

    async def api_keys_update(self, request):
        try:
            body = await request.json()
            rec = self.api_keys.update(
                request.match_info["name"],
                description=body.get("description"),
                enable=body.get("enable"),
                expired_at=body.get("expired_at", "unset"),
            )
        except (ValueError, TypeError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        if rec is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response(rec)

    async def api_keys_delete(self, request):
        if not self.api_keys.delete(request.match_info["name"]):
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response({}, status=204)

    # -- gateways (emqx_mgmt_api_gateway analog) ---------------------------
    def _gw_registry(self):
        if self.app.gateways is None:
            from emqx_tpu.app import _register_builtin_gateways
            from emqx_tpu.gateway.registry import GatewayRegistry

            self.app.gateways = GatewayRegistry(
                self.app.broker,
                self.app.hooks,
                retainer=getattr(self.app, "retainer", None),
            )
            _register_builtin_gateways(self.app.gateways)
        return self.app.gateways

    async def gateways_list(self, request):
        return web.json_response({"data": self._gw_registry().list()})

    async def gateways_one(self, request):
        gw = self._gw_registry().get(request.match_info["name"])
        if gw is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.json_response(gw.status())

    async def gateways_load(self, request):
        body = await request.json()
        try:
            gw = await self._gw_registry().load(
                body["type"], dict(body.get("opts", {})), name=body.get("name")
            )
        except (KeyError, ValueError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(gw.status(), status=201)

    async def gateways_unload(self, request):
        ok = await self._gw_registry().unload(request.match_info["name"])
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"},
            status=204 if ok else 404,
        )

    # -- bridges (emqx_mgmt_api_bridge analog) -----------------------------
    async def bridges_list(self, request):
        b = self.app.bridges
        return web.json_response({"data": b.list() if b else []})

    async def bridges_create(self, request):
        body = await request.json()
        try:
            inst = await self.app._bridge_manager().create(
                body["id"], dict(body.get("opts", {}))
            )
        except (KeyError, ValueError) as e:
            return web.json_response(
                {"code": "BAD_REQUEST", "message": str(e)}, status=400
            )
        return web.json_response(
            {"id": inst.id, "status": inst.status}, status=201
        )

    async def bridges_delete(self, request):
        b = self.app.bridges
        ok = b is not None and await b.remove(request.match_info["id"])
        return web.json_response(
            {} if ok else {"code": "NOT_FOUND"},
            status=204 if ok else 404,
        )

    async def bridges_restart(self, request):
        b = self.app.bridges
        ok = b is not None and await b.resources.restart(
            request.match_info["id"]
        )
        return web.json_response(
            {"status": b.resources.status(request.match_info["id"])}
            if ok
            else {"code": "NOT_FOUND"},
            status=200 if ok else 404,
        )

    async def trace_download(self, request):
        content = self.app.trace.read(request.match_info["name"])
        if content is None:
            return web.json_response({"code": "NOT_FOUND"}, status=404)
        return web.Response(
            text=content,
            content_type="text/plain",
            headers={
                "Content-Disposition": (
                    f'attachment; filename="{request.match_info["name"]}.log"'
                )
            },
        )
