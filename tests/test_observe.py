"""Observability tests: alarms, monitors, slow subs, topic metrics,
$event messages, Prometheus/StatsD exporters, packet trace.

Parity targets: emqx_alarm_SUITE, emqx_slow_subs (delivery.completed hook),
emqx_topic_metrics, emqx_event_message, emqx_prometheus scrape endpoint,
emqx_trace REST (SURVEY.md §5.1, §5.5).
"""

import asyncio
import json
import time

import pytest

from emqx_tpu.app import BrokerApp
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.config.schema import load_config
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.client import Client
from emqx_tpu.observe.alarm import AlarmManager
from emqx_tpu.observe.exporters import StatsdExporter, prometheus_exposition
from emqx_tpu.observe.monitors import OsMon, SysMon, VmMon
from emqx_tpu.observe.slow_subs import SlowSubs
from emqx_tpu.observe.topic_metrics import TopicMetrics
from tests.test_broker_e2e import async_test


# -- alarm manager ---------------------------------------------------------

def test_alarm_lifecycle_and_republish():
    published = []
    am = AlarmManager(publish=lambda t, p: published.append((t, p)))
    assert am.activate("high_cpu", {"usage": 0.95}, "cpu too hot")
    assert not am.activate("high_cpu")  # duplicate
    assert am.is_active("high_cpu")
    assert am.list(activated=True)[0]["name"] == "high_cpu"
    assert am.deactivate("high_cpu")
    assert not am.deactivate("high_cpu")
    assert not am.is_active("high_cpu")
    hist = am.list(activated=False)
    assert hist[0]["name"] == "high_cpu" and hist[0]["deactivated_at"]
    kinds = [t.rsplit("/", 1)[1] for t, _ in published]
    assert kinds == ["activate", "deactivate"]
    body = json.loads(published[0][1])
    assert body["details"] == {"usage": 0.95}


def test_alarm_history_cap_and_sweep():
    am = AlarmManager(size_limit=3, validity_period=10.0)
    for i in range(6):
        am.activate(f"a{i}")
        am.deactivate(f"a{i}")
    assert len(am.list(activated=False)) == 3
    # sweep far in the future clears history
    am.sweep(now=time.time() + 100)
    assert am.list(activated=False) == []
    assert am.delete_all_deactivated() == 0


# -- monitors --------------------------------------------------------------

def test_sysmon_event_loop_lag(monkeypatch):
    """`long_schedule` reads the loop's own measure: the longest run
    phase between two selects since the last tick (LoopBudget)."""
    from emqx_tpu.observe import profiler

    am = AlarmManager()
    sm = SysMon(am, long_schedule_ms=50.0)
    monkeypatch.setattr(
        profiler, "_now", iter([0.0, 1.0, 1.02, 2.0, 2.3, 3.0]).__next__
    )
    budget = profiler.LoopBudget()
    budget.enter_select(), budget.exit_select()  # idle 0.0 -> 1.0
    budget.enter_select(), budget.exit_select()  # ran 20 ms
    sm.check(time.time(), budget)
    assert not am.is_active("long_schedule")
    budget.enter_select(), budget.exit_select()  # ran 300 ms -> alarm
    sm.check(time.time(), budget)
    assert am.is_active("long_schedule")
    assert budget.take_longest_run() == 0.0  # the tick took it
    sm.close()
    budget.closed()  # this thread records every section again


def test_osmon_and_vmmon_populate_gauges():
    am = AlarmManager()
    om = OsMon(am, cpu_high_watermark=1.1)  # never alarms in test
    om.check(time.time())
    time.sleep(0.05)
    om.check(time.time())
    assert 0.0 <= om.cpu_usage <= 1.0
    assert 0.0 < om.mem_usage < 1.0
    vm = VmMon(am, max_tasks=10)
    vm.check(time.time())
    assert vm.fd_count > 0


def test_vmmon_task_watermark_alarm():
    am = AlarmManager()
    vm = VmMon(am, task_high_watermark=0.0, max_tasks=1)

    async def go():
        vm.check(time.time())

    asyncio.run(go())
    assert am.is_active("too_many_processes")


# -- slow subs -------------------------------------------------------------

def test_slow_subs_topk_and_expiry():
    ss = SlowSubs(threshold_ms=100.0, top_k=2, expire_interval=5.0)
    mk = lambda t: Message(topic=t)
    ss.on_delivery_completed({"client_id": "c1"}, mk("t/1"), 0.2)
    ss.on_delivery_completed({"client_id": "c2"}, mk("t/2"), 0.5)
    ss.on_delivery_completed({"client_id": "c3"}, mk("t/3"), 0.3)
    ss.on_delivery_completed({"client_id": "c4"}, mk("t/4"), 0.05)  # fast
    top = ss.topk()
    assert [e["clientid"] for e in top] == ["c2", "c3"]  # top-2 slowest
    ss.sweep(now=time.time() + 10)
    assert ss.topk() == []


@async_test
async def test_slow_subs_via_real_delivery():
    """Artificially old message timestamp -> delivery latency over threshold."""
    from tests.test_broker_e2e import TestBed

    async with TestBed() as bed:
        ss = SlowSubs(threshold_ms=50.0, top_k=5)
        ss.attach(bed.broker.hooks)
        sub = await bed.client("slow-sub")
        await sub.subscribe("s/t", qos=1)
        msg = Message(topic="s/t", payload=b"x", qos=1)
        msg.timestamp = time.time() - 1.0  # born 1s ago
        bed.broker.publish(msg)
        await sub.recv()
        await asyncio.sleep(0.1)  # PUBACK arrives -> delivery.completed
        top = ss.topk()
        assert top and top[0]["clientid"] == "slow-sub"
        assert top[0]["timespan"] >= 900
        await sub.disconnect()


@async_test
async def test_delivery_completed_qos2():
    """QoS2 deliveries complete at PUBCOMP with message metadata intact."""
    from tests.test_broker_e2e import TestBed

    async with TestBed() as bed:
        ss = SlowSubs(threshold_ms=50.0, top_k=5)
        ss.attach(bed.broker.hooks)
        acked = []
        bed.broker.hooks.add(
            "message.acked", lambda ci, m: acked.append((ci, m))
        )
        sub = await bed.client("q2-slow")
        await sub.subscribe("q2s/t", qos=2)
        msg = Message(topic="q2s/t", payload=b"x", qos=2)
        msg.timestamp = time.time() - 1.0
        bed.broker.publish(msg)
        await sub.recv()
        await asyncio.sleep(0.2)  # PUBREC/PUBREL/PUBCOMP handshake settles
        top = ss.topk()
        assert top and top[0]["clientid"] == "q2-slow"
        assert top[0]["topic"] == "q2s/t"
        assert acked and isinstance(acked[0][1], Message)
        assert acked[0][1].topic == "q2s/t"
        await sub.disconnect()


# -- topic metrics ---------------------------------------------------------

def test_topic_metrics_counting_and_rates():
    tm = TopicMetrics()
    hooks = Hooks()
    tm.attach(hooks)
    broker = Broker(hooks=hooks)
    assert tm.register("m/#")
    assert not tm.register("m/#")  # duplicate
    with pytest.raises(Exception):
        tm.register("bad/#/topic")
    broker.publish(Message(topic="m/1", qos=1))  # no subscribers -> dropped
    broker.publish(Message(topic="other", qos=0))
    got = tm.metrics("m/#")
    assert got["metrics"]["messages.in"] == 1
    assert got["metrics"]["messages.qos1.in"] == 1
    assert got["metrics"]["messages.dropped"] == 1
    tm.tick_rates(time.time() + 1)
    assert "messages.in.rate" in tm.metrics("m/#")["metrics"]
    assert tm.deregister("m/#")
    assert tm.metrics("m/#") is None


# -- histograms (hot-path flight recorder) ---------------------------------

def test_histogram_bucket_boundaries():
    from emqx_tpu.broker.metrics import Histogram

    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(106.65)
    # cumulative, with observations AT a bound landing in that bucket
    assert snap["buckets"] == [
        (0.1, 2), (1.0, 4), (10.0, 5), (float("inf"), 6),
    ]


def test_histogram_percentile_math():
    from emqx_tpu.broker.metrics import Histogram

    h = Histogram(buckets=(1.0, 2.0, 4.0))
    for _ in range(50):
        h.observe(0.5)
    for _ in range(50):
        h.observe(3.0)
    # p50 falls exactly at the end of the first bucket
    assert h.p50 == pytest.approx(1.0)
    # p99 interpolates inside the (2, 4] bucket
    assert 2.0 < h.p99 <= 4.0
    # quantiles landing in the +Inf bucket report the last finite bound
    h2 = Histogram(buckets=(1.0,))
    h2.observe(99.0)
    assert h2.p99 == 1.0
    # empty histogram
    assert Histogram(buckets=(1.0,)).p50 == 0.0


def test_histogram_concurrent_observe():
    import threading

    from emqx_tpu.broker.metrics import Histogram

    h = Histogram(buckets=(0.5, 1.5))
    N, T = 2000, 8

    def worker():
        for i in range(N):
            h.observe(1.0 if i % 2 else 2.0)

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == N * T
    assert snap["buckets"][-1] == (float("inf"), N * T)
    assert snap["sum"] == pytest.approx(1.5 * N * T)


def test_metrics_observe_uses_registry_buckets():
    from emqx_tpu.broker.metrics import Metrics, spec

    m = Metrics()
    m.observe("ingest.batch.size", 3)
    h = m.histogram("ingest.batch.size")
    assert h.bounds == tuple(spec("ingest.batch.size").buckets)
    m.observe_many("ingest.settle.seconds", [0.001, 0.002, 5.0])
    assert m.histogram("ingest.settle.seconds").count == 3


def test_registry_rejects_kind_conflicts():
    from emqx_tpu.broker import metrics as M

    M.declare("messages.received", M.COUNTER)  # same kind: no-op
    with pytest.raises(ValueError):
        M.declare("messages.received", M.GAUGE)
    assert M.kind_of("messages.received") == M.COUNTER
    assert M.kind_of("no.such.series") is None


# -- exporters -------------------------------------------------------------

def test_prometheus_exposition_format():
    from emqx_tpu.broker.metrics import Metrics

    m = Metrics()
    m.inc("messages.received", 7)
    m.gauge_set("subscriptions.count", 3)
    body = prometheus_exposition(m.snapshot(), {"connections.count": 2})
    assert "emqx_messages_received 7" in body
    assert "emqx_subscriptions_count 3" in body
    assert "emqx_connections_count 2" in body
    assert "# TYPE emqx_messages_received counter" in body
    assert "# TYPE emqx_connections_count gauge" in body


def test_prometheus_kind_from_registry_not_name_heuristic():
    from emqx_tpu.broker.metrics import Metrics

    m = Metrics()
    # names the old substring heuristic ("usage"/"uptime"/endswith count)
    # classified WRONG or by accident: kind now comes from declarations
    m.inc("messages.dropped.no_subscribers", 2)  # counter w/ dots
    body = prometheus_exposition(
        m.snapshot(),
        {"cpu.usage": 0.5, "retained.count": 4},
    )
    assert "# TYPE emqx_messages_dropped_no_subscribers counter" in body
    assert "# TYPE emqx_cpu_usage gauge" in body
    assert "# TYPE emqx_retained_count gauge" in body
    assert "# TYPE emqx_uptime_seconds gauge" in body
    # an undeclared series renders untyped rather than mis-typed
    body2 = prometheus_exposition({"some.adhoc.series": 1})
    assert "# TYPE emqx_some_adhoc_series untyped" in body2


def test_prometheus_histogram_exposition():
    from emqx_tpu.broker.metrics import Metrics

    m = Metrics()
    m.observe_many("router.device.seconds", [0.0002, 0.003, 0.03])
    body = prometheus_exposition(m.snapshot(), histograms=m.histograms())
    assert "# TYPE emqx_router_device_seconds histogram" in body
    assert 'emqx_router_device_seconds_bucket{le="0.00025"} 1' in body
    assert 'emqx_router_device_seconds_bucket{le="0.005"} 2' in body
    assert 'emqx_router_device_seconds_bucket{le="+Inf"} 3' in body
    assert "emqx_router_device_seconds_count 3" in body
    assert "emqx_router_device_seconds_sum 0.0332" in body


def test_statsd_render_counters_as_deltas():
    from emqx_tpu.broker.metrics import Metrics

    m = Metrics()
    m.inc("messages.received", 5)
    ex = StatsdExporter(m, interval=999)
    first = ex.render().decode()
    assert "emqx.messages.received:5|c" in first
    m.inc("messages.received", 2)
    second = ex.render().decode()
    assert "emqx.messages.received:2|c" in second  # delta, not total


@async_test
async def test_statsd_push_over_udp():
    import socket

    from emqx_tpu.broker.metrics import Metrics

    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(3)
    port = rx.getsockname()[1]
    m = Metrics()
    m.inc("packets.received", 9)
    ex = StatsdExporter(m, port=port, interval=999)
    assert ex.push() >= 1
    data = rx.recv(65536).decode()
    assert "emqx.packets.received:9|c" in data
    rx.close()
    await ex.stop()


# -- full app: REST + $event + trace --------------------------------------

def _app_config(tmp_path, **over):
    return load_config(
        {
            "listeners": [{"port": 0, "bind": "127.0.0.1"}],
            "dashboard": {"port": 0, "bind": "127.0.0.1"},
            "router": {"enable_tpu": False},
            "observe": {
                "slow_subs": {"threshold_ms": 0.0},
                "event_message": {"message_dropped": True},
                "trace_dir": str(tmp_path / "trace"),
            },
            **over,
        }
    )


@async_test
async def test_event_messages_and_observe_rest(tmp_path=None):
    import tempfile
    from pathlib import Path

    import aiohttp

    tmp_path = Path(tempfile.mkdtemp())
    app = BrokerApp(_app_config(tmp_path))
    await app.start()
    try:
        mqtt_port = list(app.listeners.list().values())[0].port
        api = f"http://127.0.0.1:{app.mgmt_server.port}/api/v5"

        watcher = Client("ev-watch", version=pkt.MQTT_V5)
        await watcher.connect("127.0.0.1", mqtt_port)
        await watcher.subscribe("$event/#")

        other = Client("ev-actor", version=pkt.MQTT_V5)
        await other.connect("127.0.0.1", mqtt_port)

        async def next_event_about(clientid):
            # the watcher also sees events about itself (e.g. its own
            # session_subscribed for $event/#) — skip those
            while True:
                ev = json.loads((await watcher.recv()).payload)
                if ev.get("clientid") == clientid:
                    return ev

        ev = await next_event_about("ev-actor")
        assert ev["clientid"] == "ev-actor"
        await other.subscribe("x/y")
        ev2 = await next_event_about("ev-actor")
        assert ev2["topic"] == "x/y"

        async with aiohttp.ClientSession() as s:
            # trace: create a topic trace, make traffic, download
            async with s.post(
                f"{api}/trace",
                json={"name": "t1", "type": "topic", "topic": "x/#"},
            ) as r:
                assert r.status == 201
            await other.publish("x/y", b"traced-payload", qos=1)
            await asyncio.sleep(0.1)
            async with s.get(f"{api}/trace/t1/download") as r:
                content = await r.text()
                assert "PUBLISH" in content and "x/y" in content
            async with s.get(f"{api}/trace") as r:
                traces = (await r.json())["data"]
                assert traces[0]["name"] == "t1"
                assert traces[0]["status"] == "running"
            # slow subs populated (threshold 0 -> everything is slow)
            async with s.get(f"{api}/slow_subscriptions") as r:
                data = (await r.json())["data"]
                assert any(e["clientid"] == "ev-actor" for e in data)
            # topic metrics register + count
            async with s.post(
                f"{api}/mqtt/topic_metrics", json={"topic": "x/#"}
            ) as r:
                assert r.status == 201
            await other.publish("x/z", b"counted")
            async with s.get(f"{api}/mqtt/topic_metrics") as r:
                tm = await r.json()
                assert tm[0]["metrics"]["messages.in"] == 1
            # prometheus scrape (histogram families included: the CPU-path
            # dispatch still records per-message fan-out)
            async with s.get(f"{api}/prometheus/stats") as r:
                body = await r.text()
                assert "emqx_messages_received" in body
                assert "emqx_connections_count 2" in body
                assert "# TYPE emqx_dispatch_fanout histogram" in body
                assert 'emqx_dispatch_fanout_bucket{le="+Inf"}' in body
            # hot-path flight recorder summary
            async with s.get(f"{api}/metrics/hotpath") as r:
                assert r.status == 200
                hp = await r.json()
                assert hp["dispatch"]["fanout"]["count"] >= 1
                assert hp["alarms"]["tpu_fallback_rate_active"] is False
            # alarms endpoint (activate one by hand)
            app.alarms.activate("test_alarm", {"k": 1}, "manual")
            async with s.get(f"{api}/alarms?activated=true") as r:
                data = (await r.json())["data"]
                assert data[0]["name"] == "test_alarm"
            # trace stop + delete
            async with s.put(f"{api}/trace/t1/stop") as r:
                assert r.status == 200
            async with s.delete(f"{api}/trace/t1") as r:
                assert r.status == 204

        await watcher.disconnect()
        await other.disconnect()
    finally:
        await app.stop()


# -- hot-path flight recorder ----------------------------------------------

def test_fallback_rate_alarm_trigger_and_clear():
    from emqx_tpu.broker.metrics import Metrics
    from emqx_tpu.observe.alarm import FallbackRateWatch

    m = Metrics()
    am = AlarmManager()
    w = FallbackRateWatch(am, m, threshold=0.5, window=1.0, min_rows=10)
    t = 1000.0
    assert w.check(t) is None  # first call only arms the baseline
    # window 1: 48/50 rows fell back -> alarm
    m.inc("messages.routed.device", 2)
    m.inc("messages.routed.device_fallback", 48)
    rate = w.check(t + 1.5)
    assert rate == pytest.approx(0.96)
    assert am.is_active(FallbackRateWatch.ALARM)
    details = am.list(activated=True)[0]["details"]
    assert details["fallback_rows"] == 48 and details["routed_rows"] == 50
    # window 2: healthy traffic -> alarm clears
    m.inc("messages.routed.device", 500)
    rate = w.check(t + 3.0)
    assert rate == pytest.approx(0.0)
    assert not am.is_active(FallbackRateWatch.ALARM)
    # window 3: idle (below min_rows) flaps NEITHER direction
    m.inc("messages.routed.device_fallback", 3)
    assert w.check(t + 4.5) is None
    assert not am.is_active(FallbackRateWatch.ALARM)


def test_ingest_flight_recorder_series():
    """A real batch through BatchIngest records size/occupancy/settle."""
    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.router import Router
    from emqx_tpu.mqtt import packet as pkt

    async def go():
        broker = Broker(router=Router(min_tpu_batch=1), hooks=Hooks())
        got = []
        broker.subscribe(
            "s1", "c1", "fr/+", pkt.SubOpts(), lambda msg, o: got.append(msg)
        )
        ing = BatchIngest(broker, max_batch=64, window_us=0)
        ing.start()
        futs = [
            ing.enqueue(Message(topic=f"fr/{i}", payload=b"x"))
            for i in range(8)
        ]
        counts = await asyncio.gather(*futs)
        await ing.stop()
        assert counts == [1] * 8 and len(got) == 8
        m = broker.metrics
        bs = m.histogram("ingest.batch.size")
        assert bs is not None and bs.count >= 1 and bs.sum == 8
        occ = m.histogram("ingest.batch.occupancy")
        assert occ is not None and 0 < occ.sum / occ.count <= 1.0
        st = m.histogram("ingest.settle.seconds")
        assert st is not None and st.count == 8 and st.p99 >= st.p50 >= 0
        assert m.get("ingest.launch.errors") == 0
        assert m.get("ingest.dispatch.errors") == 0

    asyncio.run(asyncio.wait_for(go(), 30))


def test_ingest_launch_error_counted():
    from emqx_tpu.broker.ingest import BatchIngest

    async def go():
        class BoomBroker:
            class router:
                min_tpu_batch = 1
                enable_tpu = True

            def adispatch_begin(self, msgs, forward=True, batch_span=None):
                raise RuntimeError("device on fire")

        ing = BatchIngest(BoomBroker(), window_us=0)
        ing.start()
        fut = ing.enqueue(Message(topic="t"))
        with pytest.raises(RuntimeError):
            await fut
        await ing.stop()
        assert ing.metrics.get("ingest.launch.errors") == 1

    asyncio.run(asyncio.wait_for(go(), 30))


def test_trace_expired_window_closes_file(tmp_path):
    from emqx_tpu.observe.trace import TraceManager

    tm = TraceManager(base_dir=str(tmp_path))
    now = time.time()
    tm.create("leaky", "topic", "a/#", end_at=now + 0.05)
    tm.create("waiting", "topic", "b/#", start_at=now + 3600)
    assert "leaky" in tm._files and "waiting" in tm._files
    time.sleep(0.06)
    # the hot logging path closes the expired spec's handle...
    tm.log("PUBLISH", {"topic": "a/b"})
    assert "leaky" not in tm._files
    # ...but never a waiting spec's (it starts later)
    assert "waiting" in tm._files
    # finished trace stays downloadable from disk
    assert tm.read("leaky") == ""
    # housekeeping sweep covers the no-traffic case too
    tm.create("leaky2", "clientid", "c", end_at=now + 0.05)
    tm.sweep(now=now + 10)
    assert "leaky2" not in tm._files
    tm.close()


@async_test
async def test_trace_clientid_filter(tmp_path=None):
    import tempfile
    from pathlib import Path

    tmp_path = Path(tempfile.mkdtemp())
    app = BrokerApp(_app_config(tmp_path))
    await app.start()
    try:
        mqtt_port = list(app.listeners.list().values())[0].port
        app.trace.create("bytarget", "clientid", "target-client")
        a = Client("target-client")
        await a.connect("127.0.0.1", mqtt_port)
        b = Client("other-client")
        await b.connect("127.0.0.1", mqtt_port)
        await a.subscribe("tt/1")
        await b.subscribe("tt/2")
        await asyncio.sleep(0.05)
        content = app.trace.read("bytarget")
        assert "target-client" in content
        assert "other-client" not in content
        assert "SUBSCRIBE" in content and "tt/1" in content
        await a.disconnect()
        await b.disconnect()
    finally:
        await app.stop()
