"""exhook gRPC sidecar tests.

Parity targets: emqx_exhook CT suites — provider handshake
(OnProviderLoaded hook registration), message rewrite via OnMessagePublish
STOP_AND_RETURN, sidecar-driven authenticate/authorize, lifecycle
notifications, failed_action fallback, topic-scoped message hooks
(SURVEY.md §2.2, exhook.proto:27-69).
"""

import asyncio
import threading
import time

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.exhook import hookprovider_pb2 as pb
from emqx_tpu.exhook.manager import ExhookManager, ExhookServer
from emqx_tpu.exhook.provider import HookProviderServicer, serve
from tests.test_broker_e2e import TestBed, async_test


class RecordingProvider(HookProviderServicer):
    """Records every call; rewrites messages on topic rw/*; denies
    username 'blocked'; denies subscribes to 'secret/#'."""

    def __init__(self, hooks=None):
        self.hooks = hooks
        self.calls = []

    def OnClientConnected(self, request, context):
        self.calls.append(("connected", request.clientinfo.clientid))
        return pb.EmptySuccess()

    def OnClientDisconnected(self, request, context):
        self.calls.append(("disconnected", request.clientinfo.clientid))
        return pb.EmptySuccess()

    def OnSessionSubscribed(self, request, context):
        self.calls.append(("subscribed", request.topic))
        return pb.EmptySuccess()

    def OnClientAuthenticate(self, request, context):
        self.calls.append(("authenticate", request.clientinfo.username))
        if request.clientinfo.username == "blocked":
            return self.stop_bool(False)
        return self.continue_()

    def OnClientAuthorize(self, request, context):
        kind = pb.ClientAuthorizeRequest.AuthorizeReqType.Name(
            request.type
        ).lower()
        self.calls.append(("authorize", kind, request.topic))
        if request.topic.startswith("secret/"):
            return self.stop_bool(False)
        return self.continue_()

    def OnMessagePublish(self, request, context):
        m = request.message
        self.calls.append(("publish", m.topic))
        if m.topic.startswith("rw/"):
            out = pb.Message()
            out.CopyFrom(m)
            out.payload = b"[sidecar] " + m.payload
            out.headers["rewritten"] = "true"
            return self.stop_message(out)
        return self.continue_()


def _mk_manager(port, **kw) -> ExhookManager:
    mgr = ExhookManager(version="test")
    ok = mgr.add_server(
        ExhookServer(name="test", url=f"127.0.0.1:{port}", **kw)
    )
    assert ok
    return mgr


def test_provider_load_handshake_and_hook_registration():
    prov = RecordingProvider(
        hooks=["message.publish", ("message.delivered", ["only/#"])]
    )
    server, port = serve(prov)
    try:
        mgr = _mk_manager(port)
        s = mgr.servers[0]
        assert s.loaded
        assert set(s.hooks) == {"message.publish", "message.delivered"}
        assert s.hooks["message.delivered"] == ["only/#"]
        assert s.topic_interested("message.delivered", "only/x")
        assert not s.topic_interested("message.delivered", "other/x")
        assert not s.topic_interested("client.connect", None)
        mgr.shutdown()
    finally:
        server.stop(None)


def _apub(broker, msg):
    return asyncio.run(broker.apublish(msg))


def test_message_publish_rewrite():
    prov = RecordingProvider()  # all hooks
    server, port = serve(prov)
    try:
        hooks = Hooks()
        broker = Broker(hooks=hooks)
        mgr = _mk_manager(port)
        mgr.attach(hooks)
        got = []
        broker.subscribe(
            "s1", "c1", "rw/t", __import__(
                "emqx_tpu.mqtt.packet", fromlist=["SubOpts"]
            ).SubOpts(),
            lambda m, o: got.append(m),
        )
        _apub(broker, Message(topic="rw/t", payload=b"original"))
        assert got[0].payload == b"[sidecar] original"
        assert got[0].headers.get("rewritten") == "true"
        # non-matching topic passes through untouched
        broker.subscribe(
            "s1", "c1", "plain/t", __import__(
                "emqx_tpu.mqtt.packet", fromlist=["SubOpts"]
            ).SubOpts(),
            lambda m, o: got.append(m),
        )
        _apub(broker, Message(topic="plain/t", payload=b"asis"))
        assert got[1].payload == b"asis"
        mgr.shutdown()
    finally:
        server.stop(None)


@async_test
async def test_exhook_auth_and_lifecycle_end_to_end():
    prov = RecordingProvider()
    server, port = serve(prov)
    try:
        async with TestBed() as bed:
            mgr = _mk_manager(port)
            mgr.attach(bed.broker.hooks)

            # lifecycle + allowed auth
            c = await bed.client("exh-ok", username="alice")
            await c.subscribe("norm/t", qos=1)
            await asyncio.sleep(0.1)
            assert ("connected", "exh-ok") in prov.calls
            assert ("subscribed", "norm/t") in prov.calls
            assert any(
                a[0] == "authenticate" and a[1] == "alice"
                for a in prov.calls
            )

            # sidecar denies this username at CONNECT
            from emqx_tpu.mqtt.client import MqttError

            with pytest.raises(MqttError):
                await bed.client("exh-bad", username="blocked")

            # sidecar denies publish to secret/*
            await c.publish("secret/x", b"no", qos=1)
            assert ("authorize", "publish", "secret/x") in prov.calls
            sub2 = await bed.client("exh-watch")
            await sub2.subscribe("secret/#")
            await c.publish("secret/x", b"no2", qos=1)
            with pytest.raises(asyncio.TimeoutError):
                await sub2.recv(0.3)

            await c.disconnect()
            await asyncio.sleep(0.1)
            assert ("disconnected", "exh-ok") in prov.calls
            await sub2.disconnect()
            mgr.shutdown()
    finally:
        server.stop(None)


def test_failed_action_deny_blocks_publish_when_sidecar_down():
    hooks = Hooks()
    broker = Broker(hooks=hooks)
    # port from a server we immediately stop -> connection refused
    prov = RecordingProvider()
    server, port = serve(prov)
    mgr = _mk_manager(port, failed_action="deny", timeout=0.3)
    mgr.attach(hooks)
    server.stop(None)
    time.sleep(0.1)
    n = _apub(broker, Message(topic="any/t", payload=b"x"))
    assert n == 0
    assert broker.metrics.get("messages.dropped") == 1
    mgr.shutdown()


def test_failed_action_ignore_passes_through_when_sidecar_down():
    hooks = Hooks()
    broker = Broker(hooks=hooks)
    prov = RecordingProvider()
    server, port = serve(prov)
    mgr = _mk_manager(port, failed_action="ignore", timeout=0.3)
    mgr.attach(hooks)
    server.stop(None)
    time.sleep(0.1)
    from emqx_tpu.mqtt import packet as pkt

    got = []
    broker.subscribe("s", "c", "t", pkt.SubOpts(), lambda m, o: got.append(m))
    _apub(broker, Message(topic="t", payload=b"through"))
    assert got and got[0].payload == b"through"
    mgr.shutdown()


def test_per_hook_metrics_counted():
    prov = RecordingProvider()
    server, port = serve(prov)
    try:
        hooks = Hooks()
        broker = Broker(hooks=hooks)
        mgr = _mk_manager(port)
        mgr.attach(hooks)
        _apub(broker, Message(topic="m/1", payload=b"a"))
        _apub(broker, Message(topic="m/2", payload=b"b"))
        metrics = mgr.servers[0].metrics["message.publish"]
        assert metrics["succeed"] == 2 and metrics["failed"] == 0
        info = mgr.info()[0]
        assert info["loaded"] and info["name"] == "test"
        mgr.shutdown()
    finally:
        server.stop(None)


def test_wire_compat_service_path_and_layout():
    """The gRPC seam must match the reference exactly so a provider binary
    built against apps/emqx_exhook/priv/protos/exhook.proto attaches
    unchanged."""
    from emqx_tpu.exhook.rpc import METHODS, SERVICE

    assert SERVICE == "emqx.exhook.v1.HookProvider"
    assert len(METHODS) == 21
    # spot-check reference field numbers (wire compatibility, not just names)
    vr = pb.ValuedResponse.DESCRIPTOR
    assert vr.fields_by_name["bool_result"].number == 3
    assert vr.fields_by_name["message"].number == 4
    ci = pb.ClientInfo.DESCRIPTOR
    assert ci.fields_by_name["password"].number == 4
    assert ci.fields_by_name["dn"].number == 12
    msg = pb.Message.DESCRIPTOR
    assert msg.fields_by_name["node"].number == 1
    assert msg.fields_by_name["topic"].number == 5
    assert msg.fields_by_name["headers"].number == 8
    assert pb.DESCRIPTOR.package == "emqx.exhook.v1"


def test_valued_response_continue_and_stop_semantics():
    """Reference merge_responsed_* semantics (emqx_exhook_handler.erl:
    341-359): CONTINUE applies the value and keeps folding; IGNORE skips;
    STOP_AND_RETURN applies the value and stops the chain."""

    class ContinueRewriter(HookProviderServicer):
        def OnMessagePublish(self, request, context):
            out = pb.Message()
            out.CopyFrom(request.message)
            out.payload = b"[A]" + bytes(out.payload)
            return pb.ValuedResponse(
                type=pb.ValuedResponse.ResponsedType.CONTINUE, message=out
            )

        def OnClientAuthenticate(self, request, context):
            # CONTINUE verdict: used, but later providers may override
            return pb.ValuedResponse(
                type=pb.ValuedResponse.ResponsedType.CONTINUE,
                bool_result=False,
            )

    class StopRewriter(HookProviderServicer):
        def OnMessagePublish(self, request, context):
            out = pb.Message()
            out.CopyFrom(request.message)
            out.payload = bytes(out.payload) + b"[B-stop]"
            return pb.ValuedResponse(
                type=pb.ValuedResponse.ResponsedType.STOP_AND_RETURN,
                message=out,
            )

        def OnClientAuthenticate(self, request, context):
            return pb.ValuedResponse(
                type=pb.ValuedResponse.ResponsedType.STOP_AND_RETURN,
                bool_result=True,
            )

    class NeverReached(HookProviderServicer):
        def __init__(self):
            self.publish_calls = 0

        def OnMessagePublish(self, request, context):
            self.publish_calls += 1
            return self.continue_()

    sA, pA = serve(ContinueRewriter())
    sB, pB = serve(StopRewriter())
    never = NeverReached()
    sC, pC = serve(never)
    try:
        hooks = Hooks()
        broker = Broker(hooks=hooks)
        mgr = ExhookManager(version="test")
        for name, port in (("a", pA), ("b", pB), ("c", pC)):
            assert mgr.add_server(
                ExhookServer(name=name, url=f"127.0.0.1:{port}")
            )
        mgr.attach(hooks)
        from emqx_tpu.mqtt import packet as pkt

        got = []
        broker.subscribe("s", "c", "t", pkt.SubOpts(), lambda m, o: got.append(m))
        _apub(broker, Message(topic="t", payload=b"x"))
        # A's CONTINUE rewrite applied, B's STOP rewrite applied, C never saw it
        assert got and got[0].payload == b"[A]x[B-stop]"
        assert never.publish_calls == 0

        # authenticate: A says deny-but-continue, B says allow-and-stop
        verdict = asyncio.run(
            hooks.arun_fold(
                "client.authenticate",
                ({"client_id": "c"}, {"password": b""}),
                None,
            )
        )
        assert isinstance(verdict, dict) and verdict["result"] == "allow"
        mgr.shutdown()
    finally:
        for srv in (sA, sB, sC):
            srv.stop(None)


def test_breaker_rejections_do_not_extend_cooldown():
    """PR 8 regression: calls rejected while the breaker is open count a
    failure but must NOT advance the ladder — re-tripping on every
    rejection would push _broken_until forward forever under steady
    traffic, and the breaker could never half-open."""
    s = ExhookServer("brk", "127.0.0.1:1", timeout=0.05,
                     breaker_threshold=2, breaker_cooldown=5.0)
    try:
        # two real failures (unreachable sidecar) trip the breaker
        for _ in range(2):
            ok, _resp = s.call("OnProviderLoaded", None, "client.connect")
            assert not ok
        with s._state_lock:
            deadline = s._broken_until
        assert deadline > time.monotonic()
        # a burst of rejected calls while open: failures counted,
        # deadline untouched
        for _ in range(5):
            ok, _resp = s.call("OnProviderLoaded", None, "client.connect")
            assert not ok
        with s._state_lock:
            assert s._broken_until == deadline
        assert s.metrics["client.connect"]["failed"] == 7
    finally:
        s.unload()
