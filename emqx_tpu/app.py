"""Application assembly + lifecycle: config -> running broker.

The emqx_machine analog (apps/emqx_machine/src/emqx_machine_boot.erl:
dependency-ordered app boot, signal handling): builds the broker kernel,
extensions, listeners, management API and periodic housekeeping from one
`AppConfig`, starts them in dependency order, and tears them down cleanly.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import List, Optional

from emqx_tpu.broker.auth import AuthChain, BuiltinDatabase, JwtAuth
from emqx_tpu.broker.authz import AclRule, Authorizer
from emqx_tpu.broker.auto_subscribe import AutoSubscribe, AutoSubscribeTopic
from emqx_tpu.broker.banned import Banned, Flapping
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.delayed import DelayedPublish
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.retainer import Retainer
from emqx_tpu.broker.rewrite import RewriteRule, TopicRewrite
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.shared_sub import SharedSub
from emqx_tpu.config.schema import AppConfig
from emqx_tpu.transport.listener import ListenerConfig, Listeners
from emqx_tpu.utils.node import node_name, set_node_name


def _register_builtin_gateways(registry) -> None:
    """Built-in protocol gateway types (apps/emqx_gateway/src/* impls)."""
    from emqx_tpu.gateway.coap import CoapGateway
    from emqx_tpu.gateway.exproto import ExprotoGateway
    from emqx_tpu.gateway.lwm2m import Lwm2mGateway
    from emqx_tpu.gateway.mqttsn import SnGateway
    from emqx_tpu.gateway.stomp import StompGateway

    registry.register_type("stomp", StompGateway)
    registry.register_type("mqttsn", SnGateway)
    registry.register_type("exproto", ExprotoGateway)
    registry.register_type("coap", CoapGateway)
    registry.register_type("lwm2m", Lwm2mGateway)


def attach_guards(hooks: Hooks, c: AppConfig):
    """Banned + flapping admission guards (emqx_banned / emqx_flapping)."""
    banned = Banned()
    banned.attach(hooks)
    flapping = (
        Flapping(
            banned,
            max_count=c.flapping.max_count,
            window=c.flapping.window_time,
            ban_time=c.flapping.ban_time,
        )
        if c.flapping.enable
        else None
    )
    if flapping:
        flapping.attach(hooks)
    return banned, flapping


def attach_authn(hooks: Hooks, c: AppConfig, channel_config: ChannelConfig):
    """Authn chain + SCRAM enhanced auth from config (emqx_authn analog).

    Shared by BrokerApp and the connection workers (transport/workers.py):
    each worker rebuilds the same chain from the same config, so admission
    semantics don't depend on which process accepted the socket."""
    scram = None
    authn = None
    if c.authn.enable:
        providers = []
        if c.authn.users:
            db = BuiltinDatabase(
                user_id_type=c.authn.user_id_type,
                algo=c.authn.password_hash,
            )
            for u in c.authn.users:
                db.add_user(u.user_id, u.password, u.is_superuser)
            providers.append(db)
        if c.authn.jwt_secret:
            providers.append(
                JwtAuth(c.authn.jwt_secret.encode(), c.authn.jwt_verify_claims)
            )
        if c.authn.http_url:
            from emqx_tpu.auth.http import HttpAuthProvider

            providers.append(
                HttpAuthProvider(
                    c.authn.http_url,
                    method=c.authn.http_method,
                    timeout=c.authn.http_timeout,
                )
            )
        if c.authn.jwks_endpoint:
            from emqx_tpu.auth.jwks import JwksAuthProvider

            providers.append(
                JwksAuthProvider(
                    c.authn.jwks_endpoint,
                    refresh_interval=c.authn.jwks_refresh_interval,
                    verify_claims=c.authn.jwks_verify_claims,
                )
            )
        authn = AuthChain(providers, allow_anonymous=c.authn.allow_anonymous)
        authn.attach(hooks)
    if c.authn.scram_enable:
        from emqx_tpu.auth.scram import ScramAuthenticator

        scram = ScramAuthenticator(iterations=c.authn.scram_iterations)
        for u in c.authn.scram_users:
            scram.add_user(u.user_id, u.password, u.is_superuser)
        channel_config.enhanced_auth[scram.METHOD] = scram
    return authn, scram


def attach_authz(hooks: Hooks, c: AppConfig):
    """ACL rules + file ACL + network authz sources (emqx_authz analog)."""
    authz_rules = [BrokerApp._acl_rule(r) for r in c.authz.rules]
    if c.authz.acl_file:
        from emqx_tpu.auth.file_acl import load as load_acl_file

        authz_rules.extend(load_acl_file(c.authz.acl_file))
    authz_sources = []
    if c.authz.http_url:
        from emqx_tpu.auth.http import HttpAuthzSource

        authz_sources.append(
            HttpAuthzSource(
                c.authz.http_url,
                method=c.authz.http_method,
                timeout=c.authz.http_timeout,
            )
        )
    authz = Authorizer(
        rules=authz_rules,
        no_match=c.authz.no_match,
        deny_action=c.authz.deny_action,
        sources=authz_sources,
    )
    authz.attach(hooks)
    return authz


def build_guard_hooks(c: AppConfig, hooks: Hooks) -> ChannelConfig:
    """Worker-process hook stack: the admission-relevant slice of the
    BrokerApp wiring (guards + authn + authz) against a fresh Hooks, plus
    the ChannelConfig the worker's channels run with. Everything else
    (retainer, rules, bridges, cluster) lives only in the router process."""
    channel_config = ChannelConfig(caps=c.mqtt, session=c.session)
    attach_guards(hooks, c)
    attach_authn(hooks, c, channel_config)
    attach_authz(hooks, c)
    return channel_config


class BrokerApp:
    def __init__(self, config: Optional[AppConfig] = None):
        self.config = config or AppConfig()
        c = self.config
        if c.node.name:
            set_node_name(c.node.name)

        from emqx_tpu.config.schema import LogConfig
        from emqx_tpu.observe import logfmt

        # logging is process-global: a second in-process app (cluster
        # tests, embedded brokers) with DEFAULT log config must not
        # clobber an earlier app's explicit handler setup
        if logfmt._handler is None or c.log != LogConfig():
            logfmt.setup_logging(c.log.level, c.log.formatter, c.log.to_file)

        # imported here, not at module level: connection workers import
        # this module for build_guard_hooks and must stay off jax
        from emqx_tpu.ops.matcher import MatcherConfig

        self.hooks = Hooks()
        self.router = Router(
            matcher_config=MatcherConfig(
                max_levels=c.router.max_levels,
                frontier=c.router.frontier,
                max_matches=c.router.max_matches,
                max_bytes=c.router.max_bytes,
                fanout_compact=c.router.fanout_compact,
                fanout_slots=c.router.fanout_slots,
                sub_table=c.router.sub_table,
                sparse_gather=c.router.sparse_gather,
                donate_buffers=c.router.donate_buffers,
                jit_cache_max=c.router.jit_cache_max,
            ),
            min_tpu_batch=c.router.min_tpu_batch,
            enable_tpu=c.router.enable_tpu,
        )
        self.broker = Broker(router=self.router, hooks=self.hooks)
        self.broker.shared = SharedSub(strategy=c.shared_subscription.strategy)
        if (
            c.router.enable_tpu
            and c.router.mesh_shape[0] > 0
            and c.router.mesh_shape[1] > 0
        ):
            # SPMD serving: the dispatch path runs dist_shape_route_step
            # over a (dp, tp) device mesh (parallel/mesh.py)
            from emqx_tpu.parallel.mesh import make_mesh

            dp, tp = c.router.mesh_shape
            self.broker.mesh = make_mesh(dp * tp, tp=tp)
            # every table owner shards through the same mesh: the lazy
            # match-only engine (Router.matcher) and the retained replay
            # index pick it up from here (segment-manager placements)
            self.router.mesh = self.broker.mesh
            # a sparse subscriber table partitions its slot column over
            # the 'tp' axis; setting the shard count up front avoids a
            # re-shard rebuild on the first prepare
            self.broker.subtab.set_shards(tp)
        if c.semantic.enable:
            # semantic routing plane (docs/semantic_routing.md):
            # embedding-filter subscriptions fused into the serving
            # launch; attached BEFORE the first dispatch builds the
            # device engine so the engine binds the semantic table
            from emqx_tpu.broker.semantic import SemanticRouting

            self.broker.semantic = SemanticRouting(
                dim=c.semantic.dim,
                topk=c.semantic.topk,
                threshold=c.semantic.threshold,
                dtype=c.semantic.dtype,
                shards=(
                    c.router.mesh_shape[1]
                    if self.broker.mesh is not None
                    else 1
                ),
                metrics=self.broker.metrics,
            )
        self.cm = ChannelManager(self.broker)
        # device-resident session store (broker/session_store.py): the
        # inflight/QoS state tables ride the same segment machinery as
        # subscriptions; ack clears fuse into serving launches. The
        # host-dict path stays the fallback (knob off = unchanged)
        if c.session.device_store and c.router.enable_tpu:
            from emqx_tpu.broker.session_store import SessionStore

            self.session_store = SessionStore(
                capacity=c.session.store_capacity,
                sweep_slots=c.session.store_sweep_slots,
                retry_interval=c.session.retry_interval,
                metrics=self.broker.metrics,
                mesh=self.broker.mesh,
            )
            self.broker.session_store = self.session_store
            self.cm.session_store = self.session_store
        else:
            self.session_store = None
        self.channel_config = ChannelConfig(caps=c.mqtt, session=c.session)
        # populated below once authn config is read (SCRAM enhanced auth)
        # rate limiting + overload protection (reference: emqx_limiter,
        # emqx_olp; wired into listeners like the esockd limiter adapter)
        from emqx_tpu.broker.limiter import LimiterServer
        from emqx_tpu.broker.olp import Olp
        from emqx_tpu.transport.listener import TransportContext

        self.limiters = LimiterServer(c.limiter)
        self.olp = Olp(
            enable=c.olp.enable,
            lag_watermark_ms=c.olp.lag_watermark_ms,
            cooldown=c.olp.cooldown,
            metrics=self.broker.metrics,
        )
        # fault injection (observe/faults.py): the process-wide injector
        # gets this broker's metrics for faults.injected accounting;
        # config-armed rules (default off) load here, runtime arming
        # goes through GET/POST /api/v5/faults
        from emqx_tpu.observe.faults import default_faults

        self.faults = default_faults
        self.faults.metrics = self.broker.metrics
        if c.faults.enable:
            for fr in c.faults.rules:
                self.faults.arm(
                    fr.site,
                    mode=fr.mode,
                    probability=fr.probability,
                    nth=fr.nth,
                    max_fires=fr.max_fires,
                    delay_ms=fr.delay_ms,
                )
        # device profiling + performance provenance (observe/profiler.py,
        # observe/provenance.py): the process-wide profiler gets this
        # broker's metrics; captures are REST-armed (POST /api/v5/profile)
        # and the housekeeping tick enforces their duration/byte bounds.
        # The hardware fingerprint gauges let dashboards refuse to
        # overlay runs from different silicon (proxy=1 means non-TPU).
        from emqx_tpu.observe import provenance
        from emqx_tpu.observe.profiler import default_profiler

        self.profiler = default_profiler
        self.profiler.metrics = self.broker.metrics
        self.profiler.trace_dir = c.observe.profile_trace_dir
        self.profiler.max_seconds = float(c.observe.profile_max_seconds)
        self.profiler.max_bytes = int(c.observe.profile_max_bytes)
        # the backend this broker routes on; None under --no-tpu, which
        # must not open (or need) a device. A backend that fails to
        # initialise raises here rather than serving from an unnamed one.
        self.fingerprint = None
        if c.router.enable_tpu:
            fp = self.fingerprint = provenance.fingerprint()
            self.broker.metrics.gauge_set(
                "provenance.proxy", 1 if fp["proxy"] else 0
            )
            self.broker.metrics.gauge_set(
                "provenance.device.count", fp["device_count"]
            )
        if c.force_gc.enable:
            from emqx_tpu.transport.congestion import ForcedGC

            _gc_count, _gc_bytes = c.force_gc.count, c.force_gc.bytes
            make_forced_gc = lambda: ForcedGC(_gc_count, _gc_bytes)  # noqa: E731
        else:
            make_forced_gc = None
        self.transport_ctx = TransportContext(
            limiters=self.limiters,
            olp=self.olp,
            alarms=None,  # filled in below once AlarmManager exists
            make_forced_gc=make_forced_gc,
        )
        self.listeners = Listeners(self.broker, self.cm, ctx=self.transport_ctx)
        if self.limiters.limited("message_routing"):
            # message_routing limiter: overload-drop at the publish gate
            # (the reference's routing limiter sheds load rather than queue)
            routing_limiter = self.limiters.connect("message_routing")

            def _routing_gate(msg, acc=None):
                m = acc if acc is not None else msg
                if not routing_limiter.try_acquire(1):
                    self.broker.metrics.inc("limiter.dropped.message_routing")
                    m.headers["allow_publish"] = False
                return ("ok", m)

            self.hooks.add(
                "message.publish", _routing_gate, priority=1000,
                tag="limiter.message_routing",
            )

        # extensions (reference L4, SURVEY.md §1)
        self.banned, self.flapping = attach_guards(self.hooks, c)

        self.retainer = Retainer(
            max_retained=c.retainer.max_retained_messages,
            max_payload=c.retainer.max_payload_size,
            device_threshold=c.retainer.device_threshold,
            enable_device=c.router.enable_tpu,
        )
        self.retainer.enabled = c.retainer.enable
        self.retainer.mesh = self.broker.mesh
        self.retainer.attach(self.hooks)

        self.delayed = DelayedPublish(
            self.broker, max_messages=c.delayed.max_delayed_messages
        )
        self.delayed.enabled = c.delayed.enable
        self.delayed.attach(self.hooks)

        if c.rewrite:
            TopicRewrite(
                [
                    RewriteRule(r.action, r.source_topic, r.re, r.dest_topic)
                    for r in c.rewrite
                ]
            ).attach(self.hooks)

        if c.auto_subscribe:
            AutoSubscribe(
                [
                    AutoSubscribeTopic(filter=s.topic, qos=s.qos)
                    for s in c.auto_subscribe
                ]
            ).attach(self.hooks)

        self.authn, self.scram = attach_authn(
            self.hooks, c, self.channel_config
        )

        # TLS-PSK identity store (emqx_psk analog)
        self.psk = None
        if c.psk.enable:
            from emqx_tpu.auth.psk import PskStore

            self.psk = PskStore()
            for ident, secret in c.psk.identities.items():
                self.psk.insert(ident, secret)
            if c.psk.file:
                self.psk.import_file(c.psk.file)
            self.transport_ctx.psk = self.psk

        # rule engine (reference L4: emqx_rule_engine)
        from emqx_tpu.rules.engine import Console, Republish, RuleEngine

        self.rule_engine = RuleEngine(self.broker)
        self.rule_engine.attach(self.hooks)
        if c.semantic.enable and c.semantic.rule_predicates:
            # device-compiled WHERE predicates (rules/compile.py):
            # eligible rules filter at match rate inside the serving
            # launch instead of post-dispatch Python rate
            self.rule_engine.attach_device()
        for spec in c.rules:
            outputs = []
            for o in spec.outputs or [None]:
                if o is None or o.function == "console":
                    outputs.append(Console())
                elif o.function == "bridge":
                    outputs.append(self._bridge_output(str(o.args.get("id", ""))))
                else:
                    a = o.args
                    outputs.append(
                        Republish(
                            topic=str(a.get("topic", "")),
                            payload=str(a.get("payload", "${payload}")),
                            qos=int(a.get("qos", 0)),
                            retain=bool(a.get("retain", False)),
                        )
                    )
            rule = self.rule_engine.create_rule(
                spec.id, spec.sql, outputs, spec.description
            )
            rule.enabled = spec.enable

        self.authz = attach_authz(self.hooks, c)

        # observability (reference L5 aux: SURVEY.md §5.1/§5.5)
        from emqx_tpu.observe.alarm import AlarmManager, FallbackRateWatch
        from emqx_tpu.observe.event_message import EventMessage
        from emqx_tpu.observe.exporters import StatsdExporter
        from emqx_tpu.observe.gc_policy import GcPolicy
        from emqx_tpu.observe.monitors import OsMon, SysMon, VmMon
        from emqx_tpu.observe.slow_subs import SlowSubs
        from emqx_tpu.observe.topic_metrics import TopicMetrics
        from emqx_tpu.observe.trace import TraceManager

        ob = c.observe
        self.alarms = AlarmManager(
            publish=lambda topic, payload: self.broker.publish(
                Message(topic=topic, payload=payload)
            ),
            size_limit=ob.alarm_size_limit,
            validity_period=ob.alarm_validity_period,
        )
        self.transport_ctx.alarms = self.alarms
        self.fallback_watch = (
            FallbackRateWatch(
                self.alarms,
                self.broker.metrics,
                threshold=ob.tpu_fallback_alarm_threshold,
                window=ob.tpu_fallback_alarm_window,
                min_rows=ob.tpu_fallback_alarm_min_rows,
            )
            if ob.tpu_fallback_alarm_enable and c.router.enable_tpu
            else None
        )
        self.sys_mon = SysMon(self.alarms) if ob.sys_mon_enable else None
        # the collector policy of this process: installed by `start`,
        # driven by the housekeeping tick
        self.gc_policy = GcPolicy(self.broker.metrics)
        self.os_mon = OsMon(self.alarms) if ob.os_mon_enable else None
        self.vm_mon = VmMon(self.alarms) if ob.vm_mon_enable else None
        self.slow_subs = SlowSubs(
            threshold_ms=ob.slow_subs.threshold_ms,
            top_k=ob.slow_subs.top_k_num,
            expire_interval=ob.slow_subs.expire_interval,
        )
        self.slow_subs.enabled = ob.slow_subs.enable
        self.slow_subs.attach(self.hooks)

        # license (lib-ee/emqx_license analog): verify + expiry alarms +
        # connection gate; community/unlimited when no key is configured
        from emqx_tpu import license as lic_mod

        if c.license.key:
            if not c.license.pubkey_n:
                from emqx_tpu.config.schema import ConfigError

                raise ConfigError(
                    "license.key is set but license.pubkey_n (hex modulus "
                    "of the verifier key) is missing"
                )
            pub = (int(c.license.pubkey_n, 16), c.license.pubkey_e)
            self.license = lic_mod.LicenseChecker(
                lic_mod.parse(c.license.key, pub), alarms=self.alarms
            )
        else:
            self.license = lic_mod.LicenseChecker(alarms=self.alarms)
        self.license.attach(self.hooks, self.cm)
        self.topic_metrics = TopicMetrics()
        self.topic_metrics.attach(self.hooks)
        self.event_message = EventMessage(
            self.broker,
            enabled={
                name
                for name in (
                    "client_connected",
                    "client_disconnected",
                    "session_subscribed",
                    "session_unsubscribed",
                    "message_delivered",
                    "message_acked",
                    "message_dropped",
                )
                if getattr(ob.event_message, name)
            },
        )
        self.event_message.attach(self.hooks)
        self.trace = TraceManager(base_dir=ob.trace_dir)
        self.trace.attach(self.hooks)
        # causal span tracing (observe/spans.py): head-sampled publish ->
        # batch -> device-step -> deliver spans; clients under an active
        # TraceSpec always sample (self.trace.should_sample)
        if ob.trace_spans_enable:
            from emqx_tpu.observe.spans import OtlpFileExporter, SpanRecorder

            self.spans = SpanRecorder(
                metrics=self.broker.metrics,
                sample_rate=ob.trace_sample_rate,
                sample_clients=ob.trace_sample_clients,
                sample_topics=ob.trace_sample_topics,
                seed=ob.trace_sample_seed,
                ring=ob.trace_span_ring,
                exporter=(
                    OtlpFileExporter(ob.trace_span_file)
                    if ob.trace_span_file
                    else None
                ),
                always_sample=self.trace.should_sample,
            )
            self.broker.spans = self.spans
        else:
            self.spans = None
        # graceful-degradation ladder (broker/degrade.py): device-path
        # breaker + retry policy; transitions emit degrade.* series and
        # span events so traces show WHY a message took the slow path
        if c.degrade.enable:
            from emqx_tpu.broker.degrade import DegradeController

            self.degrade = DegradeController(
                metrics=self.broker.metrics,
                spans=self.spans,
                max_retries=c.degrade.max_retries,
                backoff_base_s=c.degrade.backoff_base_ms / 1e3,
                backoff_max_s=c.degrade.backoff_max_ms / 1e3,
                failure_threshold=c.degrade.failure_threshold,
                open_secs=c.degrade.open_secs,
                probe_successes=c.degrade.probe_successes,
                shed_queue_batches=c.degrade.shed_queue_batches,
            )
            self.broker.degrade = self.degrade
        else:
            self.degrade = None
        # SLO-driven adaptive batching (broker/slo.py): the ingest
        # window becomes a controlled variable holding a p99 target;
        # the graded backpressure ladder (widen -> defer -> shed)
        # replaces the binary shed cliff. Attached to BatchIngest (and
        # the retained-storm feed) in start().
        if c.slo.enable and c.router.ingest_enable and c.router.enable_tpu:
            from emqx_tpu.broker.slo import SloController

            self.slo = SloController(
                metrics=self.broker.metrics,
                target_p99_ms=c.slo.target_p99_ms,
                min_window_us=c.slo.min_window_us,
                max_window_us=c.slo.max_window_us,
                initial_window_us=c.router.ingest_window_us,
                eval_interval_s=c.slo.eval_interval_ms / 1e3,
                min_samples=c.slo.min_samples,
                gain=c.slo.gain,
                hysteresis=c.slo.hysteresis,
                ladder_patience=c.slo.ladder_patience,
                defer_max_s=c.slo.defer_max_ms / 1e3,
                starvation_s=c.slo.starvation_ms / 1e3,
                shed_hard_mult=c.slo.shed_hard_mult,
                olp=self.olp,
                spans=self.spans,
            )
        else:
            self.slo = None
        self.slo_watch = None
        if self.slo is not None and c.slo.alarm_enable:
            from emqx_tpu.observe.alarm import SloViolationWatch

            # level-triggered page on SUSTAINED target misses (the
            # controller absorbs transient ones) — FallbackRateWatch's
            # sibling, checked from housekeeping
            self.slo_watch = SloViolationWatch(
                self.alarms,
                self.broker.metrics,
                threshold=c.slo.alarm_threshold,
                window=c.slo.alarm_window,
                min_windows=c.slo.alarm_min_windows,
            )
        # device runtime telemetry (observe/device_watch.py): compile /
        # retrace watch + HBM & transfer gauges, polled from housekeeping
        if c.router.enable_tpu:
            from emqx_tpu.observe.alarm import RetraceStormWatch
            from emqx_tpu.observe.device_watch import DeviceWatch

            self.device_watch = DeviceWatch(self.broker.metrics)
            self.retrace_watch = (
                RetraceStormWatch(
                    self.alarms,
                    self.broker.metrics,
                    threshold=ob.retrace_alarm_threshold,
                    window=ob.retrace_alarm_window,
                    warmup=ob.retrace_alarm_warmup,
                    sustain=ob.retrace_alarm_sustain,
                )
                if ob.retrace_alarm_enable
                else None
            )
        else:
            self.device_watch = None
            self.retrace_watch = None
        # background segment compaction (ops/segments.py): housekeeping
        # merges the shape-index hot segment into the packed table and
        # proactively grows the subscriber bitmaps on the compaction
        # executor — the subscribe path never pays an O(table) rebuild
        if c.router.enable_tpu:
            from emqx_tpu.ops.segments import SegmentCompactor

            self.segment_compactor = SegmentCompactor(
                metrics=self.broker.metrics,
                interval_s=c.router.compact_interval_s,
            )
        else:
            self.segment_compactor = None
        self.statsd = (
            StatsdExporter(
                self.broker.metrics,
                host=ob.statsd.server_host,
                port=ob.statsd.server_port,
                interval=ob.statsd.flush_interval,
            )
            if ob.statsd.enable
            else None
        )

        # durability (persistent sessions + disc-copies analog, SURVEY §5.4)
        if c.durability.enable:
            from emqx_tpu.broker.persistent_session import (
                DurableState,
                SessionPersistence,
            )
            from emqx_tpu.storage.kv import FileKv

            import os as _os

            from emqx_tpu.storage.wal import MessageWal

            kv = FileKv(c.durability.data_dir, fsync=c.durability.fsync)
            self.session_persistence = SessionPersistence(
                self.broker,
                self.cm,
                kv,
                self.channel_config.session,
                wal=MessageWal(
                    _os.path.join(c.durability.data_dir, "messages.wal"),
                    fsync=c.durability.fsync,
                ),
            )
            self.session_persistence.attach(self.hooks)
            segments = None
            if c.durability.segment_snapshot:
                # rolling-upgrade fast path: the device-table host state
                # (route index + bitmaps) checkpoints as a sidecar pickle
                # so a replacement process restores million-entry tables
                # instead of replaying every subscribe
                from emqx_tpu.ops.segments import SegmentStateSnapshot

                def _cap_segments():
                    state = {
                        "router": self.broker.router,
                        "subtab": self.broker.subtab,
                        "grouptab": self.broker.grouptab,
                    }
                    if self.session_store is not None:
                        # mass session resume = segment replay: the
                        # whole inflight/QoS table checkpoints as
                        # arrays; restore re-arms every window with one
                        # upload, zero per-session objects rebuilt
                        state["session_store"] = (
                            self.session_store.capture()
                        )
                    return state

                def _install_segments(state):
                    self.broker.router = state["router"]
                    self.broker.subtab = state["subtab"]
                    self.broker.grouptab = state["grouptab"]
                    if (
                        self.session_store is not None
                        and state.get("session_store") is not None
                    ):
                        self.session_store.install(
                            state["session_store"]
                        )
                    self.broker._device = None  # rebuilt on next batch

                segments = SegmentStateSnapshot(
                    _os.path.join(c.durability.data_dir, "segments.pkl"),
                    capture=_cap_segments,
                    install=_install_segments,
                )
            self.durable_state = DurableState(
                kv,
                retainer=self.retainer if c.retainer.enable else None,
                delayed=self.delayed if c.delayed.enable else None,
                banned=self.banned,
                degrade=self.degrade,
                segments=segments,
            )
        else:
            self.session_persistence = None
            self.durable_state = None

        # exhook gRPC sidecars (reference: emqx_exhook, SURVEY.md §2.2)
        if c.exhook:
            from emqx_tpu import __version__
            from emqx_tpu.exhook.manager import ExhookManager, ExhookServer

            self.exhook = ExhookManager(version=__version__)
            for spec in c.exhook:
                self.exhook.add_server(
                    ExhookServer(
                        name=spec.name or spec.url,
                        url=spec.url,
                        timeout=spec.timeout,
                        failed_action=spec.failed_action,
                    )
                )
            self.exhook.attach(self.hooks)
        else:
            self.exhook = None

        self.mgmt_server = None  # set by start() when dashboard.enable
        self.gateways = None  # GatewayRegistry, set by start() when configured
        self.bridges = None  # BridgeManager, set by start() when configured
        self.plugins = None  # PluginManager (lazy)
        self.telemetry = None  # Telemetry, set by start()
        self.config_handler = self._make_config_handler()
        self._tasks: List[asyncio.Task] = []
        self.worker_pools: List = []  # WorkerPool, set by start()
        self.started_at: Optional[float] = None

    @staticmethod
    def _acl_rule(spec) -> AclRule:
        who = spec.who
        if isinstance(who, str) and ":" in who:
            k, v = who.split(":", 1)
            who = {k: v}
        return AclRule(spec.permit, who, spec.action, list(spec.topics))

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        c = self.config
        # config-driven clustering (ekka autocluster analog): bus + node
        # wrap the broker BEFORE listeners accept, so the first subscribe
        # already replicates its route
        self.cluster_bus = None
        self.cluster_node = None
        if c.cluster.enable:
            from emqx_tpu.cluster.node import ClusterNode
            from emqx_tpu.cluster.tcp_transport import TcpBus

            self.cluster_bus = TcpBus(
                node_name(),
                host=c.cluster.bind,
                port=c.cluster.listen_port,
                send_retries=c.cluster.send_retries,
                send_backoff_s=c.cluster.send_backoff_ms / 1e3,
                send_deadline_s=c.cluster.send_deadline_s,
                metrics=self.broker.metrics,
                degrade=self.degrade,
            )
            self.cluster_node = ClusterNode(
                node_name(),
                self.cluster_bus,
                broker=self.broker,
                forward_mode=c.cluster.rpc_mode,
                loop=asyncio.get_running_loop(),
            )
            self.broker.cluster = self.cluster_node
            if self.broker.mesh is not None:
                # scale-out serving: advertise this node's slice of the
                # global subscriber-lane space; shard ownership + the
                # node-loss re-own ladder live in cluster/route_sync.py
                idx, total = c.cluster.shard_slice
                self.cluster_node.attach_mesh_slice(
                    c.router.mesh_shape, idx, total
                )
            if c.retainer.enable:
                # retained set/clear replicate cluster-wide + join-time
                # bootstrap (emqx_retainer_mnesia parity)
                self.cluster_node.attach_retainer(self.retainer, self.hooks)
            for s in c.cluster.seeds:
                self.cluster_bus.add_peer(s.node, s.host, s.port)
            if c.cluster.seeds:
                self._tasks.append(
                    asyncio.get_running_loop().create_task(
                        self._cluster_join([s.node for s in c.cluster.seeds])
                    )
                )
            # liveness: periodic heartbeat + failure detection (the
            # tests drive Membership.heartbeat() manually; a live app
            # needs the ticker)
            from emqx_tpu.cluster.membership import HEARTBEAT_INTERVAL

            async def _beat():
                while True:
                    await asyncio.sleep(HEARTBEAT_INTERVAL)
                    node = self.cluster_node
                    if node is None:
                        return
                    try:
                        await asyncio.get_running_loop().run_in_executor(
                            None, node.membership.heartbeat
                        )
                    except Exception:
                        pass

            self._tasks.append(
                asyncio.get_running_loop().create_task(_beat())
            )
        # publish batch aggregator: live connection traffic rides the device
        # route path (broker/ingest.py) once the loop is running
        if c.router.ingest_enable and c.router.enable_tpu:
            from emqx_tpu.broker.ingest import BatchIngest

            self.broker.ingest = BatchIngest(
                self.broker,
                max_batch=c.router.ingest_max_batch,
                window_us=c.router.ingest_window_us,
                pipeline=c.router.ingest_pipeline,
                olp=self.olp,
                slo=self.slo,
                qos0_low=self.slo is not None and c.slo.qos0_low_lane,
            )
            self.broker.ingest.start()
            if c.retainer.enable and c.retainer.storm_ride:
                # wildcard-subscribe replay storms ride the serving
                # pipeline's fused launch (broker/retained_feed.py) —
                # single-device AND mesh mode (the mesh engine fuses
                # them into dist_fused_step, chunk rows over 'dp');
                # the device retained index attaches lazily on first
                # eligible insert, so wire the feed through a factory
                from emqx_tpu.broker.retained_feed import RetainedStormFeed

                self.retainer.ensure_device()
                if self.retainer._device is not None:
                    feed = RetainedStormFeed(
                        self.retainer._device,
                        metrics=self.broker.metrics,
                        window_s=c.retainer.storm_window_us / 1e6,
                    )
                    # retained replays are tagged low-priority: on the
                    # SLO ladder's defer rung they sit launches out
                    # instead of deepening an already-violating tail
                    feed.slo = self.slo
                    self.retainer.storm_feed = feed
                    self.broker.retained_feed = feed
        # restore durable state BEFORE listeners accept clients
        if self.session_persistence is not None:
            restored = self.session_persistence.restore()
            if restored:
                self.broker.metrics.gauge_set("sessions.restored", restored)
        if self.durable_state is not None:
            self.durable_state.restore()
        if self.broker.ingest is not None:
            # pre-warm the route_step kernel BEFORE listeners accept (but
            # AFTER restore, so restored subscriptions set the table shapes
            # the compile keys on): first-contact compile on a real chip is
            # tens of seconds and must not land on live publishers
            try:
                dev = self.broker._device_router()
                args = dev.prepare()
                await asyncio.get_running_loop().run_in_executor(
                    None,
                    dev.route_prepared,
                    args,
                    ["warmup/a"] * max(1, c.router.min_tpu_batch),
                )
            except Exception:
                self.broker.metrics.inc("device.warmup.failed")
                logging.getLogger("emqx_tpu").exception(
                    "device route warmup failed; serving with cold kernel"
                )
        for spec in c.listeners:
            chan_cfg = self.channel_config
            if spec.mountpoint:
                # per-listener channel config: same caps/session, listener-
                # specific topic namespace (emqx_listeners.erl:232 analog)
                import dataclasses

                chan_cfg = dataclasses.replace(
                    chan_cfg, mountpoint=spec.mountpoint
                )
            if spec.workers > 0 and spec.type == "tcp":
                # multi-process host data plane: the workers own the
                # client port (SO_REUSEPORT); this process only runs the
                # routing core + fabric (transport/workers.py)
                from emqx_tpu.transport.workers import WorkerPool

                pool = WorkerPool(
                    self, spec.bind, spec.port, spec.workers, c
                )
                await pool.start()
                self.worker_pools.append(pool)
                continue
            await self.listeners.start_listener(
                ListenerConfig(
                    name=spec.name,
                    type=spec.type,
                    bind=spec.bind,
                    port=spec.port,
                    max_connections=spec.max_connections,
                    ssl_certfile=spec.ssl_certfile,
                    ssl_keyfile=spec.ssl_keyfile,
                    ssl_cacertfile=spec.ssl_cacertfile,
                    ssl_verify=spec.ssl_verify,
                ),
                chan_cfg,
            )
        if c.bridges:
            for bspec in c.bridges:
                await self._bridge_manager().create(
                    bspec.id, {**bspec.opts, "enable": bspec.enable}
                )
        if c.gateways:
            from emqx_tpu.gateway.registry import GatewayRegistry

            self.gateways = GatewayRegistry(
                self.broker, self.hooks, retainer=self.retainer,
                psk=self.psk,
            )
            _register_builtin_gateways(self.gateways)
            for gspec in c.gateways:
                if gspec.enable:
                    await self.gateways.load(
                        gspec.type, dict(gspec.opts), name=gspec.name
                    )
        if c.dashboard.enable:
            from emqx_tpu.mgmt.api import MgmtApi

            self.mgmt_server = MgmtApi(self)
            await self.mgmt_server.start(c.dashboard.bind, c.dashboard.port)
        self.started_at = time.time()
        self.olp.start()
        if self.statsd is not None:
            self.statsd.start()
        # runtime plugins (emqx_plugins analog): start configured refs.
        # one broken plugin must not abort broker boot — log and continue
        if c.plugins.start:
            pm = self._plugin_manager()
            for ref in c.plugins.start:
                try:
                    pm.start(ref)
                except Exception:
                    logging.getLogger("emqx_tpu").exception(
                        "plugin %s failed to start; continuing boot", ref
                    )
        # telemetry reporter (opt-in)
        from emqx_tpu.observe.telemetry import Telemetry

        import os as _os

        self.telemetry = Telemetry(
            self,
            enable=c.observe.telemetry.enable,
            url=c.observe.telemetry.url,
            interval=c.observe.telemetry.interval,
            uuid_path=(
                _os.path.join(c.durability.data_dir, "telemetry_uuid")
                if c.durability.enable
                else None
            ),
        )
        self.telemetry.start()
        self.gc_policy.install()
        self._tasks = [
            asyncio.ensure_future(self._housekeeping()),
            asyncio.ensure_future(self._sys_heartbeat()),
            asyncio.ensure_future(self._sys_stats()),
        ]

    def _make_config_handler(self, conf_log=None):
        """Runtime config-update pipeline (emqx_config_handler parity):
        per-subtree side-effect handlers with schema validation and
        rollback; see config/handler.py."""
        import dataclasses as _dc

        from emqx_tpu.config.handler import ConfigHandler

        def set_config(cfg):
            self.config = cfg

        h = ConfigHandler(lambda: self.config, set_config, conf_log=conf_log)

        def apply_mqtt(cfg):
            # patch the SHARED caps object in place: every live channel and
            # listener references it, so new limits apply immediately
            for f in _dc.fields(cfg.mqtt):
                setattr(
                    self.channel_config.caps, f.name, getattr(cfg.mqtt, f.name)
                )

        def apply_limiter(cfg):
            self.limiters.reconfigure(cfg.limiter)

        def apply_authz(cfg):
            self.authz.no_match = cfg.authz.no_match
            self.authz.deny_action = cfg.authz.deny_action
            self.authz.set_rules(
                [self._acl_rule(r) for r in cfg.authz.rules]
            )

        def apply_flapping(cfg):
            if self.flapping is not None:
                self.flapping.max_count = cfg.flapping.max_count
                self.flapping.window = cfg.flapping.window_time
                self.flapping.ban_time = cfg.flapping.ban_time

        def apply_log(cfg: AppConfig) -> None:
            from emqx_tpu.observe import logfmt

            logfmt.set_formatter(cfg.log.formatter)
            logfmt.set_level(cfg.log.level)

        h.register("mqtt", apply_mqtt)
        h.register("limiter", apply_limiter)
        h.register("authz", apply_authz)
        h.register("flapping", apply_flapping)
        h.register("log", apply_log)
        return h

    def _plugin_manager(self):
        if self.plugins is None:
            from emqx_tpu.plugins import PluginManager

            self.plugins = PluginManager(
                self, self.config.plugins.install_dir
            )
        return self.plugins

    def _bridge_manager(self):
        if self.bridges is None:
            from emqx_tpu.integration.bridge import BridgeManager

            self.bridges = BridgeManager(self.broker, self.hooks)
        return self.bridges

    def _bridge_output(self, bridge_id: str):
        """Lazy rule output: bridges may be created after the rule
        (config order, or via REST) — resolve at fire time."""
        from emqx_tpu.rules.engine import FunctionOutput

        def fn(row, ctx):
            if self.bridges is not None:
                self.bridges.send_row(bridge_id, row, ctx)

        return FunctionOutput(fn, name=f"bridge:{bridge_id}")

    async def _cluster_join(self, seeds: List[str]) -> None:
        """Dial seeds until one admits us (peers may still be booting)."""
        loop = asyncio.get_running_loop()
        for _attempt in range(120):
            for seed in seeds:
                try:
                    ok = await loop.run_in_executor(
                        None, self.cluster_node.join, seed
                    )
                    if ok:
                        logging.getLogger("emqx_tpu").info(
                            "joined cluster via %s", seed
                        )
                        return
                except Exception:
                    pass
            await asyncio.sleep(0.5)
        logging.getLogger("emqx_tpu").warning(
            "cluster join failed after all retries: %s", seeds
        )

    async def drain(self, cluster_node=None, peer: Optional[str] = None):
        """Rolling-restart drain (the relup analog, r3 verdict item 7;
        reference tooling: scripts/update_appup.escript + node evacuation):
        stop accepting, close live connections (persistent sessions park
        into the CM + WAL checkpoint), and — when this broker is a
        cluster member — hand every parked session to `peer` over the
        sess v2 protocol so the process can exit with zero message loss
        (ClusterNode.drain_to). The caller restarts/replaces the process;
        a restarted single node restores sessions from the WAL."""
        out = {"handed_off": 0}
        for pool in self.worker_pools:
            await pool.stop()
        self.worker_pools.clear()
        await self.listeners.stop_all()
        if self.gateways is not None:
            await self.gateways.unload_all()
            self.gateways = None
        out["detached_sessions"] = self.cm.detached_count()
        if self.session_persistence is not None:
            self.session_persistence.flush(force=True)
        node = cluster_node or getattr(self, "cluster_node", None)
        if node is not None:
            if not peer:
                peers = node.membership.peers()
                peer = peers[0] if peers else None
            if peer:
                # async variant: rpc round-trips off-loop so inbound
                # forwards keep banking mid-drain
                out["handed_off"] = await node.drain_to_async(peer)
                self.broker.cluster = None
                self.cluster_node = None
        self.broker.metrics.inc("node.drained")
        return out

    async def stop(self) -> None:
        if self.broker.ingest is not None:
            await self.broker.ingest.stop()
            self.broker.ingest = None
        if self.broker.retained_feed is not None:
            # unhook the storm feed: replays after stop fall back to the
            # synchronous CPU/device match path
            self.retainer.storm_feed = None
            self.broker.retained_feed = None
        for t in self._tasks:
            t.cancel()
        if self._tasks:
            await asyncio.gather(*self._tasks, return_exceptions=True)
        await self.olp.stop()
        if self.statsd is not None:
            await self.statsd.stop()
        if self.mgmt_server is not None:
            await self.mgmt_server.stop()
        if self.telemetry is not None:
            await self.telemetry.stop()
        if self.plugins is not None:
            self.plugins.stop_all()
        if self.gateways is not None:
            await self.gateways.unload_all()
        if self.bridges is not None:
            await self.bridges.close()
        for pool in self.worker_pools:
            await pool.stop()
        self.worker_pools.clear()
        await self.listeners.stop_all()
        if getattr(self, "cluster_node", None) is not None:
            try:
                self.cluster_node.leave()
            except Exception:
                pass
            self.cluster_node = None
        if getattr(self, "cluster_bus", None) is not None:
            self.cluster_bus.stop()
            self.cluster_bus = None
        # final checkpoint AFTER listeners close: connection teardown parks
        # live persistent sessions into cm._detached, so the snapshot
        # includes clients that were still connected at shutdown
        if self.session_persistence is not None:
            self.session_persistence.flush(force=True)
        if self.durable_state is not None:
            self.durable_state.flush()
        if self.sys_mon is not None:
            self.sys_mon.close()
        self.gc_policy.restore()
        if self.exhook is not None:
            self.exhook.shutdown()
        # external auth backends hold lazily-created HTTP sessions
        if self.authn is not None:
            for prov in self.authn.providers:
                closer = getattr(prov, "close", None)
                if closer is not None:
                    await closer()
        for src in self.authz.sources:
            closer = getattr(src, "close", None)
            if closer is not None:
                await closer()
        if self.spans is not None:
            self.spans.close()  # flush the OTLP file exporter buffer
        self.trace.close()

    def _heap_items(self) -> int:
        """What the long-lived heap holds, in items: the collector policy
        freezes when some arrived (observe/gc_policy.py)."""
        n = (
            self.broker.subscription_count()
            + self.cm.channel_count()
            + self.cm.detached_count()
            + len(self.retainer)
        )
        if self.cluster_node is not None:
            n += self.cluster_node.routes.stats()["routes.count"]
        return n

    async def _housekeeping(self) -> None:
        import logging

        c = self.config
        last_retainer_sweep = 0.0
        last_session_sweep = 0.0
        last_durability_flush = time.time()
        # mesh.shard.* accounting (scale-out serving): scatter launches
        # diff the segment managers' counters; the lane-fill scan walks
        # the subscriber matrix, so it runs every 30th tick only
        last_shard_launches = 0
        mesh_fill_tick = 0
        from emqx_tpu.observe import profiler as _prof

        while True:
            await asyncio.sleep(1.0)
            # section `housekeeping`: the whole synchronous tick
            _prof.begin("housekeeping")
            try:
                now = time.time()
                # delayed dues + detached-session deadlines are
                # MONOTONIC (clock-step immunity): let them read their
                # own clock instead of passing wall time
                self.delayed.tick()
                self.cm.sweep_expired()
                self.banned.sweep(now)
                if self.flapping is not None:
                    self.flapping.sweep(now)
                if now - last_retainer_sweep >= c.retainer.msg_clear_interval:
                    self.retainer.clear_expired(now)
                    last_retainer_sweep = now
                if self.sys_mon is not None:
                    self.sys_mon.check(now, self.profiler.budget)
                self.gc_policy.tick(
                    self._heap_items(), self.broker.released
                )
                if self.os_mon is not None:
                    self.os_mon.check(now)
                if self.vm_mon is not None:
                    self.vm_mon.check(now)
                self.slow_subs.sweep(now)
                self.alarms.sweep(now)
                if self.fallback_watch is not None:
                    self.fallback_watch.check(now)
                if self.slo_watch is not None:
                    self.slo_watch.check(now)
                if self.device_watch is not None:
                    self.device_watch.poll(now)
                # bounded profile captures: auto-disarm past the
                # deadline or the on-disk byte budget (profiler.tick
                # is a no-op while disarmed)
                self.profiler.tick()
                if self.retrace_watch is not None:
                    self.retrace_watch.check(now)
                dev = self.broker._device
                if self.segment_compactor is not None and dev is not None:
                    st = dev.segment_status()
                    m = self.broker.metrics
                    m.gauge_set("router.segment.hot.fill", st["hot_fill"])
                    m.gauge_set(
                        "router.segment.hot.capacity", st["hot_capacity"]
                    )
                    m.gauge_set(
                        "router.segment.tombstones", st["tombstones"]
                    )
                    st_sub = self.broker.subtab.status()
                    if st_sub["mode"] == "sparse":
                        m.gauge_set("router.sparse.bytes", st_sub["bytes"])
                        m.gauge_set(
                            "router.sparse.fill", st_sub["csr_fill"]
                        )
                        m.gauge_set(
                            "router.sparse.tombstones",
                            st_sub["csr_tombstones"],
                        )
                        m.gauge_set(
                            "router.sparse.hot.fill", st_sub["hot_fill"]
                        )
                    rc = self.config.router
                    owners = dev.compaction_owners(
                        hot_entries=rc.compact_hot_entries,
                        tombstone_frac=rc.compact_tombstone_frac,
                    )
                    if self.session_store is not None:
                        # fourth owner on the one compactor: purge acked
                        # (tombstoned) session rows off the critical path
                        owners.append(
                            self.session_store.compaction_owner(
                                tombstone_frac=rc.compact_tombstone_frac
                            )
                        )
                    self.segment_compactor.tick(owners)
                if (
                    dev is not None
                    and self.broker.mesh is not None
                    and hasattr(dev, "shard_status")
                ):
                    m = self.broker.metrics
                    launches = (
                        dev._shape_sync.delta_launches
                        + dev._bits_sync.delta_launches
                        + dev._nfa_sync.delta_launches
                    )
                    if launches > last_shard_launches:
                        m.inc(
                            "mesh.shard.scatter.launches",
                            launches - last_shard_launches,
                        )
                        last_shard_launches = launches
                    if mesh_fill_tick % 30 == 0:
                        st = dev.shard_status()
                        m.gauge_set("mesh.shard.count", st["shards"])
                        m.gauge_set(
                            "mesh.shard.fill",
                            st.get("lane_fill_max", 0.0),
                        )
                    mesh_fill_tick += 1
                if (
                    self.session_store is not None
                    and now - last_session_sweep
                    >= c.session.store_sweep_interval
                ):
                    # arm a retry/expiry sweep to ride the next serving
                    # launch (host fallback scan when idle / non-fusing)
                    dev2 = self.broker._device
                    self.session_store.tick(
                        fused_path=dev2 is not None
                        and getattr(
                            dev2, "supports_session_fusion", False
                        )
                    )
                    last_session_sweep = now
                self.trace.sweep(now)
                self.license.tick(now)
                self.topic_metrics.tick_rates(now)
                if (
                    self.session_persistence is not None
                    and now - last_durability_flush
                    >= c.durability.flush_interval
                ):
                    # non-forced: flush() itself knows when a write is
                    # needed (lifecycle hooks fired or detached queues live)
                    self.session_persistence.flush()
                    if self.durable_state is not None:
                        self.durable_state.flush()
                    last_durability_flush = now
            except asyncio.CancelledError:
                raise
            except Exception:
                # one bad tick must not kill periodic work for the process
                logging.getLogger("emqx_tpu").exception("housekeeping tick failed")
            finally:
                _prof.end()
                _prof.flush(self.broker.metrics)

    def _publish_sys(self, stats: dict) -> None:
        import logging

        for topic, payload in stats.items():
            try:
                self.broker.publish(
                    Message(topic=topic, payload=payload.encode(), qos=0)
                )
            except Exception:
                # a raising publish hook must not kill the $SYS loops
                logging.getLogger("emqx_tpu").exception("$SYS publish failed")

    async def _sys_heartbeat(self) -> None:
        """$SYS liveness beat: uptime/datetime at sys_heartbeat_interval
        (reference: emqx_sys.erl heartbeat vs. the slower info messages)."""
        import datetime

        prefix = f"$SYS/brokers/{node_name()}"
        while True:
            self._publish_sys(
                {
                    f"{prefix}/uptime": str(
                        int(time.time() - (self.started_at or time.time()))
                    ),
                    f"{prefix}/datetime": datetime.datetime.now(
                        datetime.timezone.utc
                    ).isoformat(),
                }
            )
            await asyncio.sleep(self.config.sys.sys_heartbeat_interval)

    async def _sys_stats(self) -> None:
        """$SYS broker info/stats topics (reference: emqx_sys.erl:70-95)."""
        from emqx_tpu import __version__

        prefix = f"$SYS/brokers/{node_name()}"
        while True:
            self._publish_sys(
                {
                    f"{prefix}/version": __version__,
                    f"{prefix}/clients/count": str(self.cm.channel_count()),
                    f"{prefix}/subscriptions/count": str(
                        self.broker.subscription_count()
                    ),
                    f"{prefix}/retained/count": str(len(self.retainer)),
                }
            )
            await asyncio.sleep(self.config.sys.sys_msg_interval)
