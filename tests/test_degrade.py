"""Fault-injection harness + graceful-degradation ladder
(observe/faults.py, broker/degrade.py; docs/robustness.md).

The acceptance spine: injected `device.launch` failures -> bounded
retries -> CPU-trie degraded serving with IDENTICAL delivered recipient
sets -> half-open probe recovery, all visible in metrics and span
events. Plus the satellite contracts: delta-sync rollback to the last
good epoch, cluster send deadline/retry/dead-letter, ingest shedding,
per-row matcher errors, and the supervised olp sampler.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.degrade import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    Breaker,
    DegradeController,
    IngestShed,
)
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.router import Router
from emqx_tpu.config.schema import ConfigError, load_config
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe.faults import (
    SITES,
    FaultError,
    FaultInjector,
    default_faults,
)
from emqx_tpu.ops.matcher import MatcherConfig
from tests.test_broker_e2e import async_test


@pytest.fixture(autouse=True)
def _disarm_faults():
    """The default injector is process-global (the pipeline's fault
    sites consult it): no rule may outlive its test."""
    default_faults.disarm()
    yield
    default_faults.disarm()
    default_faults.metrics = None


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- fault injector ---------------------------------------------------------

def test_injector_validates_site_and_mode():
    inj = FaultInjector()
    with pytest.raises(ValueError):
        inj.arm("not.a.site")
    with pytest.raises(ValueError):
        inj.arm("device.launch", mode="explode")
    with pytest.raises(ValueError):
        inj.arm("device.launch", probability=1.5)


def test_injector_triggers_nth_max_fires_and_modes():
    m = Metrics()
    inj = FaultInjector(metrics=m)
    assert inj.hit("device.launch") is None  # disarmed: no-op
    inj.arm("device.launch", mode="raise", nth=2, max_fires=1)
    assert inj.hit("device.launch") is None  # call 1: not the 2nd
    with pytest.raises(FaultError):
        inj.hit("device.launch")  # call 2: fires
    assert inj.hit("device.launch") is None  # one-shot spent
    assert inj.hit("device.launch") is None
    assert m.get("faults.injected") == 1
    inj.arm("cluster.forward", mode="drop")
    assert inj.hit("cluster.forward") == "drop"
    inj.arm("router.delta_sync", mode="corrupt")
    assert inj.hit("router.delta_sync") == "corrupt"
    snap = inj.snapshot()
    assert snap["enabled"] and len(snap["rules"]) == 3
    assert set(snap["sites"]) == set(SITES)
    inj.disarm("cluster.forward")
    assert inj.hit("cluster.forward") is None
    inj.disarm()
    assert not inj.armed


def test_faults_config_rules_validate():
    with pytest.raises(ConfigError):
        load_config({"faults": {"rules": [{"site": "nope.site"}]}})
    with pytest.raises(ConfigError):
        load_config({
            "faults": {"rules": [{"site": "device.launch", "mode": "x"}]}
        })
    cfg = load_config({
        "faults": {
            "enable": True,
            "rules": [{"site": "device.launch", "mode": "delay",
                       "delay_ms": 5, "nth": 3}],
        }
    })
    assert cfg.faults.rules[0].site == "device.launch"


# -- breaker state machine ---------------------------------------------------

def test_breaker_ladder_closed_open_halfopen_closed():
    clk = FakeClock()
    m = Metrics()
    br = Breaker(
        "device",
        state_series="degrade.state.device",
        trips_series="degrade.trips.device",
        metrics=m,
        failure_threshold=2,
        open_secs=5.0,
        clock=clk,
    )
    assert br.state == CLOSED and br.allow()
    br.record_failure()
    assert br.state == CLOSED  # below threshold
    br.record_failure()
    assert br.state == OPEN and br.trips == 1
    assert m.gauge("degrade.state.device") == 2
    assert m.get("degrade.trips.device") == 1
    assert not br.allow()  # open: fast-fail
    clk.advance(5.1)
    assert br.state == HALF_OPEN
    assert br.allow()  # the single probe
    assert not br.allow()  # second caller: still degraded
    br.record_success()
    assert br.state == CLOSED
    assert m.get("degrade.probe.ok") == 1
    assert m.gauge("degrade.state.device") == 0


def test_breaker_failed_probe_restarts_dwell():
    clk = FakeClock()
    m = Metrics()
    br = Breaker("device", metrics=m, open_secs=3.0, clock=clk)
    br.record_failure()
    clk.advance(3.1)
    assert br.allow()  # probe admitted
    br.record_failure()
    assert br.state == OPEN
    assert m.get("degrade.probe.fail") == 1
    assert not br.allow()  # dwell restarted
    clk.advance(3.1)
    assert br.allow()
    br.record_success()
    assert br.state == CLOSED


def test_breaker_success_under_closed_resets_failure_streak():
    br = Breaker("device", failure_threshold=2)
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == CLOSED  # streak broken; never tripped


def test_controller_snapshot_restore_reenters_state():
    clk = FakeClock()
    deg = DegradeController(clock=clk, open_secs=7.0)
    deg.device.record_failure()
    deg.cluster_breaker("n2").record_failure()
    snap = deg.snapshot()
    assert snap["device"]["state"] == OPEN
    assert 0 < snap["device"]["open_remaining_s"] <= 7.0

    deg2 = DegradeController(clock=clk, open_secs=7.0)
    deg2.restore(snap)
    assert deg2.device.state == OPEN
    assert not deg2.device.allow()
    assert deg2.cluster_breaker("n2").state == OPEN
    clk.advance(7.1)
    assert deg2.device.allow()  # dwell carried over, then probes

    # half-open restores as probe-immediately
    deg3 = DegradeController(clock=clk)
    deg3.restore({"device": {"state": HALF_OPEN}})
    assert deg3.device.allow()


# -- the acceptance spine: launch failures -> retries -> CPU-trie serving
#    with identical recipient sets -> probe recovery ------------------------

def _serving_broker(deg=None, spans=None, min_batch=4):
    b = Broker(
        router=Router(MatcherConfig(), min_tpu_batch=min_batch),
        hooks=Hooks(),
    )
    b.degrade = deg
    b.spans = spans
    delivered = []
    for i in range(8):
        def mk(sid):
            return lambda m, o: delivered.append((sid, m.topic))
        b.subscribe(f"s{i}", f"c{i}", f"t/{i}/#", pkt.SubOpts(), mk(f"s{i}"))
        b.subscribe(
            f"w{i}", f"cw{i}", "t/+/leaf", pkt.SubOpts(), mk(f"w{i}")
        )
    return b, delivered


TOPICS = [f"t/{i % 8}/leaf" for i in range(16)]


@async_test
async def test_device_launch_failures_degrade_with_identical_deliveries():
    from emqx_tpu.observe.spans import SpanRecorder

    # healthy pass: the reference recipient set, via the device path
    b0, got0 = _serving_broker()
    ing0 = BatchIngest(b0, max_batch=64, window_us=200)
    b0.ingest = ing0
    ing0.start()
    counts0 = await asyncio.gather(
        *[ing0.enqueue(Message(topic=t, payload=b"p")) for t in TOPICS]
    )
    await ing0.stop()
    assert b0.metrics.get("messages.routed.device") == len(TOPICS)

    # degraded pass: every launch raises; publishes must still SUCCEED
    # through the CPU trie with the same recipients
    rec = SpanRecorder(sample_rate=1.0)
    deg = DegradeController(
        metrics=None, spans=rec, max_retries=2, backoff_base_s=0.001,
        open_secs=0.2,
    )
    b1, got1 = _serving_broker(deg=deg, spans=rec)
    deg.metrics = b1.metrics
    deg.device.metrics = b1.metrics
    default_faults.metrics = b1.metrics
    default_faults.arm("device.launch", mode="raise")
    ing1 = BatchIngest(b1, max_batch=64, window_us=200)
    b1.ingest = ing1
    ing1.start()
    # through the REAL publish entry so spans head-sample (rate 1.0) and
    # the batch span carries the degraded mark
    futs = [
        await b1.apublish_enqueue(
            Message(topic=t, payload=b"p", from_client="pub")
        )
        for t in TOPICS
    ]
    counts1 = await asyncio.gather(*futs)
    # bounded retries happened, then the breaker tripped
    assert b1.metrics.get("degrade.retries") == 2
    assert b1.metrics.get("degrade.fallback.batches") >= 1
    assert deg.device.trips == 1
    assert b1.metrics.get("faults.injected") == 3  # 1 launch + 2 retries

    # IDENTICAL delivered recipient sets, and per-message counts match
    assert sorted(got0) == sorted(got1)
    assert list(counts0) == list(counts1)
    assert b1.metrics.get("messages.routed.device") == 0

    # while open, batches degrade WITHOUT new device attempts
    injected_before = b1.metrics.get("faults.injected")
    more = await asyncio.gather(
        *[ing1.enqueue(Message(topic=t, payload=b"p")) for t in TOPICS]
    )
    assert list(more) == list(counts0)
    assert b1.metrics.get("faults.injected") == injected_before

    # clear the fault, wait out the dwell: the half-open probe re-warms
    # the device path and recovery closes the breaker
    default_faults.disarm()
    await asyncio.sleep(0.25)
    again = await asyncio.gather(
        *[ing1.enqueue(Message(topic=t, payload=b"p")) for t in TOPICS]
    )
    assert list(again) == list(counts0)
    assert deg.device.state == CLOSED
    assert b1.metrics.get("degrade.probe.ok") == 1
    assert b1.metrics.get("messages.routed.device") == len(TOPICS)
    await ing1.stop()

    # span events narrate the ladder: trip, probe, recovery
    trans = [
        s for s in rec.spans() if s.name == "degrade.transition"
    ]
    moves = [(s.attrs["from"], s.attrs["to"]) for s in trans]
    assert (CLOSED, OPEN) in moves
    assert (OPEN, HALF_OPEN) in moves
    assert (HALF_OPEN, CLOSED) in moves
    assert any(
        s.attrs.get("reason") == "launch"
        for s in trans
        if s.attrs["to"] == OPEN
    )
    # degraded batches are marked on their ingest batch spans
    assert any(
        s.attrs.get("degraded") for s in rec.spans()
        if s.name == "ingest.batch"
    )


def test_sync_dispatch_degrades_and_recovers():
    """The synchronous batch path (publish_batch / cluster inbound) gets
    the same gate: failure -> CPU fallback + trip, probe -> recovery."""
    deg = DegradeController(open_secs=0.05)
    b, got = _serving_broker(deg=deg)
    deg.metrics = b.metrics
    deg.device.metrics = b.metrics
    msgs = [Message(topic=t, payload=b"p") for t in TOPICS]
    base = b.dispatch_batch_folded(list(msgs))
    assert deg.device.state == CLOSED

    default_faults.arm("device.readback", mode="raise")
    got.clear()
    out = b.dispatch_batch_folded(list(msgs))
    assert out == base  # identical counts through the CPU trie
    assert deg.device.state == OPEN
    assert b.metrics.get("degrade.fallback.batches") == 1

    # open: no device attempt at all
    default_faults.disarm()
    out = b.dispatch_batch_folded(list(msgs))
    assert out == base
    assert b.metrics.get("degrade.fallback.batches") == 2

    time.sleep(0.06)
    out = b.dispatch_batch_folded(list(msgs))  # the half-open probe
    assert out == base
    assert deg.device.state == CLOSED


def test_without_controller_launch_failures_still_fail_batches():
    """Legacy contract preserved: no DegradeController attached -> a
    failed launch fails its batch's publishes (ingest counts it)."""

    async def run():
        b, _ = _serving_broker(deg=None)
        ing = BatchIngest(b, max_batch=64, window_us=200)
        b.ingest = ing
        ing.start()
        await ing.submit(Message(topic="t/0/leaf", payload=b"w"))  # warm
        default_faults.arm("device.launch", mode="raise")
        futs = [
            ing.enqueue(Message(topic=t, payload=b"p")) for t in TOPICS
        ]
        res = await asyncio.gather(*futs, return_exceptions=True)
        assert all(isinstance(r, FaultError) for r in res)
        assert b.metrics.get("ingest.dispatch.errors") >= 1
        await ing.stop()

    asyncio.run(run())


# -- delta-sync rollback -----------------------------------------------------

def test_delta_sync_failure_rolls_back_to_last_good_epoch():
    b, got = _serving_broker()
    msgs = [Message(topic=t, payload=b"p") for t in TOPICS]
    base = b.dispatch_batch_folded(list(msgs))  # good epoch snapshot
    dev = b._device_router()
    assert b.metrics.get("router.prepare.dirty") == 1

    # new subscription dirties the tables; the sync now fails — serving
    # must continue from the last good (stale-but-consistent) epoch
    hits = []
    b.subscribe("late", "cl", "t/0/#", pkt.SubOpts(),
                lambda m, o: hits.append(m.topic))
    default_faults.arm("router.delta_sync", mode="raise")
    got.clear()
    out = b.dispatch_batch_folded(list(msgs))
    assert out == base  # old recipients exactly; no torn table served
    assert not hits  # the new sub is NOT visible (stale epoch)...
    assert b.metrics.get("router.sync.rollback") == 1

    default_faults.disarm()
    out = b.dispatch_batch_folded(list(msgs))
    assert b.metrics.get("router.prepare.dirty") == 2
    assert hits  # ...and becomes visible the moment the sync heals
    assert out[0] == base[0] + 1

    # corrupt-epoch injection: the fresh snapshot is declared torn and
    # rolled back the same way (generation counters make this checkable)
    b.subscribe("late2", "cl2", "t/1/#", pkt.SubOpts(), lambda m, o: None)
    default_faults.arm("router.delta_sync", mode="corrupt")
    out2 = b.dispatch_batch_folded(list(msgs))
    assert out2 == out
    assert b.metrics.get("router.sync.rollback") == 2
    default_faults.disarm()
    prep = dev.prepare()
    assert prep is dev.prepare()  # healed + cached clean


@async_test
async def test_delta_sync_failure_with_no_good_epoch_degrades_to_cpu():
    deg = DegradeController(max_retries=0, open_secs=60.0)
    b, got = _serving_broker(deg=deg)
    deg.metrics = b.metrics
    deg.device.metrics = b.metrics
    default_faults.arm("router.delta_sync", mode="raise")
    ing = BatchIngest(b, max_batch=64, window_us=200)
    b.ingest = ing
    ing.start()
    counts = await asyncio.gather(
        *[ing.enqueue(Message(topic=t, payload=b"p")) for t in TOPICS]
    )
    await ing.stop()
    assert all(c > 0 for c in counts)  # delivered via the CPU trie
    assert deg.device.state == OPEN
    assert b.metrics.get("degrade.fallback.batches") >= 1


# -- ingest shed gate --------------------------------------------------------

@async_test
async def test_ingest_sheds_past_bound_when_breaker_open():
    deg = DegradeController(shed_queue_batches=1)
    b, _ = _serving_broker(deg=deg)
    deg.device.force(OPEN, 60.0)
    ing = BatchIngest(b, max_batch=4, olp=None)
    b.ingest = ing  # not started: the backlog stays put
    for i in range(4):
        ing.enqueue(Message(topic=f"t/{i}/leaf", payload=b"p"))
    fut = ing.enqueue(Message(topic="t/5/leaf", payload=b"p"))
    with pytest.raises(IngestShed):
        await fut
    assert b.metrics.get("ingest.shed") == 1
    assert len(ing._pending) == 4  # bounded: the shed never queued


@async_test
async def test_ingest_sheds_on_olp_overload_and_drop_fault():
    class FakeOlp:
        overloaded = True

        def is_overloaded(self):
            return self.overloaded

    deg = DegradeController(shed_queue_batches=1)
    b, _ = _serving_broker(deg=deg)
    olp = FakeOlp()
    ing = BatchIngest(b, max_batch=2, olp=olp)
    ing.enqueue(Message(topic="t/0/leaf", payload=b"p"))
    ing.enqueue(Message(topic="t/1/leaf", payload=b"p"))
    with pytest.raises(IngestShed):
        await ing.enqueue(Message(topic="t/2/leaf", payload=b"p"))
    olp.overloaded = False
    f = ing.enqueue(Message(topic="t/3/leaf", payload=b"p"))
    assert not f.done()  # calm + closed breaker: queued normally
    # the ingest.enqueue drop fault sheds unconditionally
    default_faults.arm("ingest.enqueue", mode="drop")
    with pytest.raises(IngestShed):
        await ing.enqueue(Message(topic="t/4/leaf", payload=b"p"))
    assert b.metrics.get("ingest.shed") == 2


# -- per-row matcher errors --------------------------------------------------

def test_match_batch_returns_per_row_errors_without_fallback():
    """The residual NFA's rows: the filter sits outside the shape index."""
    from emqx_tpu.models.router_model import DeviceRouter
    from emqx_tpu.ops.matcher import MatchError
    from emqx_tpu.ops.route_index import RouteIndex

    idx = RouteIndex(max_shapes=1)
    idx.add("seed/+/x")  # takes the one shape
    idx.add("a/#")
    assert idx.residual_count == 1
    matcher = DeviceRouter(idx, None, MatcherConfig(max_levels=4))
    deep = "a/" + "/".join("x" for _ in range(10))
    got = matcher.match_batch([deep, "a/b", deep], fallback=None)
    assert isinstance(got[0], MatchError) and got[0].topic == deep
    assert got[1] == ["a/#"]  # the oversized rows didn't poison this one
    assert isinstance(got[2], MatchError)


def test_device_router_match_batch_per_row_errors():
    from emqx_tpu.models.router_model import DeviceRouter
    from emqx_tpu.ops.matcher import MatchError
    from emqx_tpu.ops.route_index import RouteIndex

    idx = RouteIndex()
    idx.add("a/#")
    dev = DeviceRouter(idx, None, MatcherConfig(max_levels=4))
    deep = "a/" + "/".join("x" for _ in range(10))
    got = dev.match_batch([deep, "a/b"], fallback=None)
    assert isinstance(got[0], MatchError)
    assert got[1] == ["a/#"]


# -- retained storm fault site ----------------------------------------------

@async_test
async def test_retained_storm_fault_falls_back_to_cpu_walk():
    from emqx_tpu.broker.retained_feed import RetainedStormFeed

    class FakeIndex:
        def prepare_storm(self, filters):
            raise AssertionError("must not be reached when fault fires")

        def topic_at(self, r):
            return None

    m = Metrics()
    default_faults.metrics = m
    default_faults.arm("retained.storm", mode="raise")
    feed = RetainedStormFeed(FakeIndex(), metrics=m)
    fut = feed.submit("a/#")
    assert feed.take_job() is None
    assert await fut is None  # CPU-fallback signal, not an exception
    assert m.get("faults.injected") == 1


# -- cluster send: deadline + retry + dead-letter ----------------------------

def _bus_pair(**kw):
    from emqx_tpu.cluster.tcp_transport import TcpBus

    calls = []

    def handler(peer, payload):
        calls.append(payload)
        return ("ok", payload)

    m = Metrics()
    a = TcpBus("a", port=0, metrics=m, **kw)
    bbus = TcpBus("b", port=0, metrics=m)
    bbus.attach("b", handler)
    a.add_peer("b", bbus.host, bbus.port)
    return a, bbus, calls, m


def test_cluster_send_retries_through_transient_faults():
    a, bbus, calls, m = _bus_pair(
        send_retries=3, send_backoff_s=0.005, timeout=2.0
    )
    try:
        default_faults.arm("cluster.forward", mode="raise", max_fires=2)
        assert a.send("a", "b", "hello") == ("ok", "hello")
        assert calls == ["hello"]
        assert m.get("cluster.send.retries") == 2
        assert m.get("cluster.send.dead_letter") == 0
    finally:
        a.stop()
        bbus.stop()


def test_cluster_send_dead_letters_after_budget_and_breaker_fast_fails():
    from emqx_tpu.cluster.transport import NodeUnreachable

    deg = DegradeController(open_secs=60.0)
    a, bbus, calls, m = _bus_pair(
        send_retries=1, send_backoff_s=0.002, timeout=1.0, degrade=deg
    )
    deg.metrics = m
    try:
        default_faults.arm("cluster.forward", mode="drop")
        with pytest.raises(NodeUnreachable):
            a.send("a", "b", "x")
        assert m.get("cluster.send.dead_letter") == 1
        assert m.get("cluster.send.retries") == 1
        assert deg.cluster_breaker("b").state == OPEN
        # circuit open: the next send fails FAST, no retry train
        before = m.get("cluster.send.retries")
        with pytest.raises(NodeUnreachable):
            a.send("a", "b", "y")
        assert m.get("cluster.send.retries") == before
        assert m.get("cluster.send.dead_letter") == 2
        assert not calls
        # recovery: fault cleared + dwell forced over -> probe succeeds
        default_faults.disarm()
        deg.cluster_breaker("b").force(HALF_OPEN)
        assert a.send("a", "b", "z") == ("ok", "z")
        assert deg.cluster_breaker("b").state == CLOSED
        assert calls == ["z"]
    finally:
        a.stop()
        bbus.stop()


def test_cluster_send_deadline_bounds_the_attempt_train():
    a, bbus, _, m = _bus_pair(
        send_retries=50, send_backoff_s=0.01, send_deadline_s=0.05,
        timeout=1.0,
    )
    from emqx_tpu.cluster.transport import NodeUnreachable

    try:
        default_faults.arm("cluster.forward", mode="raise")
        t0 = time.monotonic()
        with pytest.raises(NodeUnreachable):
            a.send("a", "b", "x")
        assert time.monotonic() - t0 < 1.0  # deadline, not 50 retries
        assert m.get("cluster.send.dead_letter") == 1
    finally:
        a.stop()
        bbus.stop()


# -- exhook fault site -------------------------------------------------------

def test_exhook_call_fault_counts_as_sidecar_failure():
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from emqx_tpu.exhook.manager import ExhookServer

    srv = ExhookServer(name="x", url="127.0.0.1:1", timeout=0.2)
    default_faults.arm("exhook.call", mode="raise")
    ok, resp = srv.call("OnMessagePublish", object(), "message.publish")
    assert ok is False and resp is None
    assert srv.metrics["message.publish"]["failed"] == 1


# -- olp sampler supervision -------------------------------------------------

@async_test
async def test_olp_sampler_restarts_after_exception_and_exports_series():
    from emqx_tpu.broker.olp import Olp

    m = Metrics()
    olp = Olp(enable=True, lag_watermark_ms=0.001, sample_interval=0.01,
              cooldown=0.5, metrics=m)
    boom = {"n": 0}
    real = olp.note_lag

    def flaky(lag_ms):
        if boom["n"] == 0:
            boom["n"] += 1
            raise RuntimeError("sampler bug")
        real(lag_ms)

    olp.note_lag = flaky
    olp.start()
    first = olp._task
    for _ in range(200):
        await asyncio.sleep(0.01)
        if olp._task is not None and olp._task is not first and m.get(
            "olp.trips"
        ) > 0:
            break
    assert boom["n"] == 1  # it DID die once...
    assert olp._task is not None and not olp._task.done()  # ...and restarted
    assert olp.is_overloaded()  # tiny watermark: any lag trips
    assert m.get("olp.trips") >= 1
    assert m.gauge("olp.lag_ms") >= 0.0
    await olp.stop()
    assert olp._task is None


# -- REST control surface ----------------------------------------------------

@async_test
async def test_faults_rest_arm_fire_disarm():
    import aiohttp

    from emqx_tpu.app import BrokerApp

    app = BrokerApp(load_config({
        "listeners": [{"port": 0, "bind": "127.0.0.1"}],
        "dashboard": {"port": 0, "bind": "127.0.0.1"},
        "router": {"enable_tpu": False},
    }))
    await app.start()
    try:
        api = f"http://127.0.0.1:{app.mgmt_server.port}/api/v5"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{api}/faults") as r:
                doc = await r.json()
                assert doc["enabled"] is False
                assert doc["degrade"]["device"]["state"] == CLOSED
            async with s.post(
                f"{api}/faults",
                json={"site": "ingest.enqueue", "mode": "drop",
                      "max_fires": 1},
            ) as r:
                assert r.status == 201
            async with s.post(
                f"{api}/faults", json={"site": "bogus"}
            ) as r:
                assert r.status == 400
            async with s.get(f"{api}/faults") as r:
                doc = await r.json()
                assert doc["enabled"] is True
                assert doc["rules"][0]["site"] == "ingest.enqueue"
            async with s.delete(
                f"{api}/faults", params={"site": "ingest.enqueue"}
            ) as r:
                assert r.status == 204
            async with s.get(f"{api}/faults") as r:
                assert (await r.json())["enabled"] is False
    finally:
        await app.stop()
