"""The device serving path: batch aggregator + bitmap fan-out to real subs.

Proves the flagship pipeline (tokenize + NFA match + subscriber bitmaps,
models/router_model.route_step) routes LIVE broker traffic — not just bench
batches. Reference analog: every publish crossing emqx_router:match_routes +
emqx_broker:do_dispatch (emqx_broker.erl:204-215, 505-530).
"""

import asyncio
import functools

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import SessionConfig
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.client import Client
from emqx_tpu.transport.listener import ListenerConfig, Listeners


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        asyncio.run(asyncio.wait_for(fn(*a, **kw), timeout=30))

    return wrapper


def _mk_broker(min_batch=1):
    return Broker(router=Router(min_tpu_batch=min_batch), hooks=Hooks())


def _sub(broker, sid, filt, sink, **opts):
    broker.subscribe(
        sid, sid, filt, pkt.SubOpts(**opts),
        lambda m, o, _s=sink: _s.append(m.topic),
    )


class TestDeviceDispatch:
    """dispatch_batch_folded: bitmaps -> real subscriber slots."""

    def test_plain_and_wildcard_fanout(self):
        b = _mk_broker()
        got_a, got_w, got_h = [], [], []
        _sub(b, "s1", "dev/1/temp", got_a)
        _sub(b, "s2", "dev/+/temp", got_w)
        _sub(b, "s3", "dev/#", got_h)
        msgs = [Message(topic=t, payload=b"") for t in
                ["dev/1/temp", "dev/2/temp", "other/x"]]
        counts = b.dispatch_batch_folded(msgs)
        assert counts == [3, 2, 0]
        assert got_a == ["dev/1/temp"]
        assert got_w == ["dev/1/temp", "dev/2/temp"]
        assert got_h == ["dev/1/temp", "dev/2/temp"]
        assert b.metrics.get("messages.routed.device") == 3

    def test_unsubscribe_clears_slot(self):
        b = _mk_broker()
        got = []
        _sub(b, "s1", "a/b", got)
        b.dispatch_batch_folded([Message(topic="a/b", payload=b"")])
        assert got == ["a/b"]
        b.unsubscribe("s1", "a/b")
        counts = b.dispatch_batch_folded([Message(topic="a/b", payload=b"")])
        assert counts == [0] and got == ["a/b"]

    def test_slot_reuse_after_unsubscribe(self):
        b = _mk_broker()
        g1, g2 = [], []
        _sub(b, "s1", "x/1", g1)
        b.unsubscribe("s1", "x/1")
        _sub(b, "s2", "x/2", g2)  # reuses the freed slot
        counts = b.dispatch_batch_folded(
            [Message(topic="x/1", payload=b""), Message(topic="x/2", payload=b"")]
        )
        assert counts == [0, 1]
        assert g1 == [] and g2 == ["x/2"]

    def test_shared_group_via_device(self):
        b = _mk_broker()
        got1, got2 = [], []
        _sub(b, "m1", "$share/g/t/1", got1)
        _sub(b, "m2", "$share/g/t/1", got2)
        counts = b.dispatch_batch_folded(
            [Message(topic="t/1", payload=b"") for _ in range(4)]
        )
        assert counts == [1, 1, 1, 1]
        # one member per message, load spread over the group
        assert len(got1) + len(got2) == 4

    def test_no_local_honored_on_device_path(self):
        b = _mk_broker()
        got = []
        b.subscribe("s1", "c1", "t", pkt.SubOpts(no_local=True),
                    lambda m, o: got.append(m.topic))
        counts = b.dispatch_batch_folded(
            [Message(topic="t", payload=b"", from_client="c1"),
             Message(topic="t", payload=b"", from_client="c2")]
        )
        assert counts == [0, 1] and got == ["t"]

    def test_matches_cpu_path_on_mixed_workload(self):
        bd = _mk_broker(min_batch=1)
        bc = _mk_broker(min_batch=10**9)  # always CPU
        filters = ["a/b", "a/+", "a/#", "+/b", "#", "$sys/x", "deep/" + "/".join("abcdefgh")]
        topics = ["a/b", "a/c", "b/b", "x", "$sys/x", "deep/a/b/c/d/e/f/g/h", "a"]
        sinks_d, sinks_c = {}, {}
        for i, f in enumerate(filters):
            sinks_d[f] = []
            sinks_c[f] = []
            _sub(bd, f"s{i}", f, sinks_d[f])
            _sub(bc, f"s{i}", f, sinks_c[f])
        msgs = [Message(topic=t, payload=b"") for t in topics]
        nd = bd.dispatch_batch_folded(list(msgs))
        nc = bc.dispatch_batch_folded(list(msgs))
        assert nd == nc
        for f in filters:
            assert sinks_d[f] == sinks_c[f], f

    def test_subscriber_growth_past_initial_width(self):
        b = _mk_broker()
        sinks = []
        for i in range(130):  # > 4 words of 32 slots
            s = []
            sinks.append(s)
            _sub(b, f"s{i}", f"t/{i}", s)
        all_sink = []
        _sub(b, "sw", "t/+", all_sink)
        counts = b.dispatch_batch_folded(
            [Message(topic=f"t/{i}", payload=b"") for i in range(130)]
        )
        assert counts == [2] * 130
        assert all(s for s in sinks)
        assert len(all_sink) == 130


class IngestBed:
    """Broker + TCP listener + running BatchIngest, like the app wires it."""

    __test__ = False

    def __init__(self, window_us=2000, min_batch=2):
        self.broker = _mk_broker(min_batch)
        self.cm = ChannelManager(self.broker)
        self.listeners = Listeners(self.broker, self.cm)
        self.port = None
        self._window_us = window_us

    async def __aenter__(self):
        self.broker.ingest = BatchIngest(self.broker, window_us=self._window_us)
        self.broker.ingest.start()
        l = await self.listeners.start_listener(
            ListenerConfig(port=0),
            ChannelConfig(session=SessionConfig(retry_interval=0.5)),
        )
        self.port = l.port
        return self

    async def __aexit__(self, *exc):
        await self.listeners.stop_all()
        await self.broker.ingest.stop()

    async def client(self, client_id="", **kw) -> Client:
        c = Client(client_id=client_id, **kw)
        await c.connect("127.0.0.1", self.port)
        return c


@async_test
async def test_live_sockets_route_through_device():
    """Concurrent real-socket publishers; deliveries flow the device path."""
    async with IngestBed() as tb:
        subs = []
        for i in range(4):
            s = await tb.client(f"sub{i}")
            await s.subscribe(f"room/{i}/+")
            subs.append(s)
        wild = await tb.client("wild")
        await wild.subscribe("room/#")

        pubs = [await tb.client(f"pub{i}") for i in range(4)]
        # all 20 publishes in flight at once: the aggregator's batch window
        # engages and the kernel sees real batches
        await asyncio.gather(
            *(
                pubs[i].publish(f"room/{i}/m{k}", b"x", qos=1)
                for i in range(4)
                for k in range(5)
            )
        )

        for i, s in enumerate(subs):
            got = [await asyncio.wait_for(s.recv(), 5) for _ in range(5)]
            assert sorted(m.topic for m in got) == [
                f"room/{i}/m{k}" for k in range(5)
            ]
        wgot = [await asyncio.wait_for(wild.recv(), 5) for _ in range(20)]
        assert len(wgot) == 20
        # the headline assertion: live traffic crossed the device kernel
        # (a couple of leading publishes may flush solo before the window
        # engages; the bulk must ride the device)
        assert tb.broker.metrics.get("messages.routed.device") >= 10
        for c in subs + pubs + [wild]:
            await c.disconnect()


@async_test
async def test_ingest_qos1_puback_reflects_dispatch():
    async with IngestBed() as tb:
        pub = await tb.client("p1")
        # no subscribers: still acked, delivery count 0 handled
        await pub.publish("nobody/home", b"x", qos=1)
        sub = await tb.client("s1")
        await sub.subscribe("nobody/home", qos=1)
        await pub.publish("nobody/home", b"y", qos=1)
        m = await asyncio.wait_for(sub.recv(), 5)
        assert m.payload == b"y" and m.qos == 1
        await pub.disconnect()
        await sub.disconnect()


@async_test
async def test_ingest_stop_drains_pending():
    b = _mk_broker()
    got = []
    _sub(b, "s1", "t", got)
    ing = BatchIngest(b, window_us=50_000)
    ing.start()
    task = asyncio.ensure_future(ing.submit(Message(topic="t", payload=b"")))
    await asyncio.sleep(0)  # enqueue before stop
    await ing.stop()
    assert await task == 1
    assert got == ["t"]


@async_test
async def test_ingest_pipeline_overlaps_and_settles_fifo():
    """With pipeline depth 2, batch N+1's LAUNCH happens while batch N's
    dispatch is still in flight — and settlement (delivery + PUBACK
    futures) stays strictly FIFO even when the later batch's device work
    finishes first (per-publisher delivery ordering across batches)."""
    events = []

    class SlowFastBroker:
        class router:
            min_tpu_batch = 1
            enable_tpu = True

        def __init__(self):
            self.n = 0

        def adispatch_begin(self, msgs, forward=True, batch_span=None):
            from emqx_tpu.broker.broker import PendingDispatch

            i = self.n
            self.n += 1
            events.append(("launch", i))
            delay = 0.2 if i == 0 else 0.0  # batch 0 slow, batch 1 fast
            loop = asyncio.get_running_loop()
            ready = loop.create_future()
            loop.call_later(
                delay,
                lambda: (
                    events.append(("device_done", i)),
                    ready.done() or ready.set_result(None),
                ),
            )

            async def complete():
                await ready
                # the FAN-OUT side effect: must stay FIFO across batches
                events.append(("fanout", i))
                return [1] * len(msgs)

            return PendingDispatch(ready, complete)

    b = SlowFastBroker()
    ing = BatchIngest(b, max_batch=4, window_us=0, pipeline=2)
    ing.start()
    futs = []
    for k in range(8):  # two full batches
        f = ing.enqueue(Message(topic=f"p/{k}"))
        f.add_done_callback(
            lambda _f, _i=k // 4: events.append(("settle", _i))
        )
        futs.append(f)
        if k == 3:
            await asyncio.sleep(0.05)  # let batch 0 launch first
    counts = await asyncio.gather(*futs)
    await ing.stop()
    assert counts == [1] * 8
    launches = [i for ev, i in events if ev == "launch"]
    settles = [i for ev, i in events if ev == "settle"]
    fanouts = [i for ev, i in events if ev == "fanout"]
    assert launches == [0, 1]
    # batch 1's device work finished FIRST (it's instant)...
    assert events.index(("device_done", 1)) < events.index(
        ("device_done", 0)
    )
    # ...but the host FAN-OUT (delivery) runs strictly FIFO...
    assert fanouts == [0, 1]
    # ...and so do the PUBACK futures
    assert settles == [0] * 4 + [1] * 4
    # overlap: batch 1 launched BEFORE batch 0's device work completed
    assert events.index(("launch", 1)) < events.index(("device_done", 0))


class StubPipelineBroker:
    """Scripted adispatch_begin: per-batch device delay + event log.

    Batches >= `device_at` messages behave like device dispatches
    (ready resolves after their scripted delay); smaller ones are CPU
    batches (ready pre-resolved, dispatch deferred to complete() — the
    PendingDispatch CPU-deferral contract in broker.adispatch_begin).
    """

    class router:
        min_tpu_batch = 1
        enable_tpu = True

    def __init__(self, events, delays=(), device_at=4):
        self.events = events
        self.delays = list(delays)
        self.device_at = device_at
        self.n = 0

    def adispatch_begin(self, msgs, forward=True, batch_span=None):
        from emqx_tpu.broker.broker import PendingDispatch

        i = self.n
        self.n += 1
        loop = asyncio.get_running_loop()
        is_dev = len(msgs) >= self.device_at
        self.events.append(("launch", i, len(msgs), is_dev))
        ready = loop.create_future()
        if is_dev:
            delay = self.delays[i] if i < len(self.delays) else 0.0
            loop.call_later(
                delay,
                lambda: (
                    self.events.append(("device_done", i)),
                    ready.done() or ready.set_result(None),
                ),
            )
        else:
            ready.set_result(None)

        async def complete():
            await ready
            self.events.append(("fanout", i))
            return [1] * len(msgs)

        return PendingDispatch(ready, complete)


@async_test
async def test_cross_batch_fifo_with_mixed_cpu_and_device_batches():
    """Satellite: per-publisher FIFO holds when a small CPU batch is
    launched while a SLOW device batch is in flight — the CPU batch's
    dispatch must defer to settle time (launch order), not run at
    launch, or publisher P's message #2 would deliver before #1."""
    events = []
    b = StubPipelineBroker(events, delays=[0.2], device_at=4)
    ing = BatchIngest(b, max_batch=4, window_us=0, pipeline=2)
    ing.start()
    futs = [ing.enqueue(Message(topic=f"p/{k}")) for k in range(4)]
    await asyncio.sleep(0.05)  # device batch 0 (slow) is in flight
    # publisher P's second message lands in a 1-message CPU batch that
    # launches while batch 0's device work is still pending
    futs.append(ing.enqueue(Message(topic="p/0")))
    await asyncio.gather(*futs)
    await ing.stop()
    launches = [e[1:] for e in events if e[0] == "launch"]
    fanouts = [e[1] for e in events if e[0] == "fanout"]
    assert launches[0] == (0, 4, True)
    assert launches[1][2] is False  # the small batch took the CPU path
    # the CPU batch was ready instantly but fanned out strictly AFTER
    # the slow device batch (FIFO settle = cross-batch ordering)
    assert fanouts == [0, 1]
    assert events.index(("fanout", 0)) > events.index(
        ("launch", 1, 1, False)
    )


@async_test
async def test_idle_device_launches_partial_batch():
    """Tentpole (c): once every in-flight dispatch's DEVICE work is
    done, a PARTIAL backlog launches immediately — before the settled
    batch's host fan-out — instead of waiting for a full batch or the
    settle boundary (the old rule left the device dark under mid-load).
    """
    events = []
    b = StubPipelineBroker(events, delays=[0.1, 0.0], device_at=2)
    ing = BatchIngest(b, max_batch=8, window_us=0, pipeline=2)
    ing.start()
    futs = [ing.enqueue(Message(topic=f"p/{k}")) for k in range(8)]
    await asyncio.sleep(0.02)  # batch 0 (full, slow device) in flight
    # partial backlog arrives while batch 0 is still ON DEVICE: must
    # NOT launch yet (dribble rule) ...
    futs += [ing.enqueue(Message(topic=f"q/{k}")) for k in range(3)]
    await asyncio.sleep(0.02)
    assert [e for e in events if e[0] == "launch"] == [
        ("launch", 0, 8, True)
    ]
    await asyncio.gather(*futs)
    await ing.stop()
    # ... but the moment batch 0's device work completed, the partial
    # launched BEFORE batch 0's host fan-out ran (overlap, not idle)
    i_done0 = events.index(("device_done", 0))
    i_launch1 = events.index(("launch", 1, 3, True))
    i_fanout0 = events.index(("fanout", 0))
    assert i_done0 < i_launch1 < i_fanout0


@async_test
async def test_launch_in_flight_enqueue_race_leaves_no_pending_waiter():
    """Satellite regression: the flusher's cancelled `_event.wait()`
    future must be retrieved (awaited) — before the fix every
    launch-in-flight/new-enqueue race left a cancelled-but-unawaited
    task that the loop reports as "Task was destroyed but it is
    pending" under load. Drives the race repeatedly (park on the
    (oldest_ready, event.wait) pair, then wake via BOTH arms) and
    asserts no stray Event.wait task survives in any state — and that
    stop() still completes promptly (the retrieval must not swallow
    the flusher's own cancellation)."""
    events = []
    b = StubPipelineBroker(events, delays=[0.05] * 64, device_at=2)
    ing = BatchIngest(b, max_batch=4, window_us=0, pipeline=2)
    ing.start()
    futs = []
    for round_ in range(4):
        # a non-full device batch goes in flight; the flusher parks in
        # the (oldest_ready, event.wait) race...
        futs += [ing.enqueue(Message(topic=f"r{round_}/{k}"))
                 for k in range(3)]
        await asyncio.sleep(0.01)
        # ...and a NEW enqueue wakes it (the race's other arm)
        futs.append(ing.enqueue(Message(topic=f"r{round_}/wake")))
        await asyncio.sleep(0.08)
    await asyncio.gather(*futs)
    # park the flusher in the race one final time and cancel it THERE:
    # the finally must retrieve its ev waiter without swallowing the
    # flusher's own cancellation (stop() would hang otherwise)
    futs2 = [ing.enqueue(Message(topic="final/a")),
             ing.enqueue(Message(topic="final/b"))]
    await asyncio.sleep(0.01)
    await asyncio.wait_for(ing.stop(), 5)
    await asyncio.gather(*futs2)
    stray = [
        t for t in asyncio.all_tasks()
        if "Event.wait" in repr(t.get_coro())
    ]
    assert stray == []
