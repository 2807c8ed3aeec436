"""The cluster's forward lanes, batched route replication and pushback
(`cluster.rpc_mode`): a four-node cluster delivers what one trie would, a
forward is applied exactly once whatever happens to its reply, a slow peer
delays only its own lane, and in `sync` a publish's acknowledgement waits
for the destination node's confirmation."""

from __future__ import annotations

import asyncio
import collections
import random
import threading
import time

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.cluster import make_cluster
from emqx_tpu.cluster.node import ClusterNode
from emqx_tpu.cluster.route_sync import ClusterRouteTable
from emqx_tpu.cluster.tcp_transport import TcpBus
from emqx_tpu.cluster.transport import LocalBus
from emqx_tpu.config.schema import ConfigError, load_config
from emqx_tpu.mqtt.packet import SubOpts
from emqx_tpu.observe.faults import default_faults


def poll(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def random_table(rng, n_subs, ids=6, js=5):
    """subscriber -> filters: both wildcard kinds, exact topics, and
    `device/{d}/#` overlays on top of another subscriber's filters."""
    table = {}
    for s in range(n_subs):
        fs = set()
        for _ in range(rng.randrange(2, 7)):
            i, j = rng.randrange(ids), rng.randrange(js)
            fs.add(rng.choice([
                f"device/{i}/+/{j}/#", f"device/{i}/+/{j}/leaf",
                f"device/{i}/mid/{j}/leaf", f"device/+/mid/{j}/#"]))
        if s % 3 == 0:
            fs.add(f"device/{rng.randrange(ids)}/#")
        table[s] = sorted(fs)
    return table


def random_topics(rng, n, ids=6, js=5):
    return [f"device/{rng.randrange(ids)}/mid/{rng.randrange(js)}/leaf"
            for _ in range(n)]


@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("seed", [7, 2_500_000_019])
def test_four_nodes_deliver_what_one_trie_would(mode, seed):
    rng = random.Random(seed)
    _, nodes = make_cluster(4, forward_mode=mode)
    try:
        table = random_table(rng, 24)
        got = {s: collections.Counter() for s in table}
        trie, holders = TopicTrie(), collections.defaultdict(list)
        for s, filters in table.items():
            node = nodes[s % 4]  # subscribers on every node

            def deliver(msg, opts, s=s):
                got[s][msg.payload] += 1
            for f in filters:
                node.subscribe(f"sid{s}", f"c{s}", f, SubOpts(qos=1), deliver)
                if not holders[f]:
                    trie.insert(f)
                holders[f].append(s)
        want = {s: collections.Counter() for s in table}
        topics = random_topics(rng, 120)
        for k, topic in enumerate(topics):
            for f in trie.match(topic):
                for s in holders[f]:
                    want[s][b"m%d" % k] += 1
        msgs = [Message(topic=t, payload=b"m%d" % k, qos=1)
                for k, t in enumerate(topics)]
        k = 0
        while k < len(msgs):  # single publishes and batches, from any node
            node = nodes[rng.randrange(4)]
            if rng.random() < 0.5:
                node.broker.publish(msgs[k])
                k += 1
            else:
                n = rng.randrange(2, 9)
                node.broker.publish_batch(msgs[k:k + n])
                k += n
        for node in nodes:
            node.flush()
        assert got == want
        assert sum(sum(c.values()) for c in want.values()) > 100
        for node in nodes:
            m = node.broker.metrics
            assert m.get("messages.forward.failed") == 0
            assert m.get("cluster.forward.duplicates") == 0
            assert m.gauge("cluster.forward.unconfirmed") == 0
        assert sum(n.broker.metrics.get("cluster.forward.messages")
                   for n in nodes) > 0
        assert sum(n.broker.metrics.get("cluster.route.ops")
                   for n in nodes) == 3 * sum(
                       len(set(f for s in table if s % 4 == i
                               for f in table[s])) for i in range(4))
    finally:
        for node in nodes:
            node.rpc.stop()


@pytest.mark.parametrize("seed", [3, 11, 2_500_000_023])
def test_interleaved_ops_through_apply_batch_equal_a_dict_and_the_v1_path(seed):
    rng = random.Random(seed)
    filters = [f"device/{i}/+/{j}/#" for i in range(5) for j in range(4)] \
        + [f"device/{i}/#" for i in range(5)] + ["plain/a", "plain/b"]
    origins = ["n1", "n2", "n3"]
    batched, one_by_one = ClusterRouteTable("n0"), ClusterRouteTable("n0")
    truth = set()
    for _ in range(40):
        origin = rng.choice(origins)
        ops = [(rng.choice(["add", "add", "delete"]), rng.choice(filters))
               for _ in range(rng.randrange(1, 30))]
        batched.apply_batch(ops, origin)
        for op, f in ops:  # the per-filter `route` v1 methods
            (one_by_one.add_route if op == "add"
             else one_by_one.delete_route)(f, origin)
            (truth.add if op == "add" else truth.discard)((f, origin))
        assert set(batched.routes()) == truth == set(one_by_one.routes())
        assert batched.stats() == one_by_one.stats() == {
            "routes.count": len(truth),
            "topics.count": len({f for f, _ in truth})}
    for topic in ["device/1/x/2/y", "device/3/mid/0/leaf", "plain/a", "device/4"]:
        want = collections.defaultdict(set)
        trie = TopicTrie()
        for f in {f for f, _ in truth}:
            trie.insert(f)
        for f in trie.match(topic):
            for g, n in truth:
                if g == f:
                    want[n].add(f)
        got = batched.match_dests(topic)
        assert {n: set(fs) for n, fs in got.items()} == dict(want)
        assert batched.match_dests_batch([topic]) == [got]
    assert set(batched.local_filters()) == set()
    batched.apply_batch([("add", "mine/#")], "n0")
    assert batched.local_filters() == ["mine/#"]
    assert batched.cleanup_node("n1") == len([1 for _, n in truth if n == "n1"])
    assert batched.stats()["routes.count"] == \
        len([1 for _, n in truth if n != "n1"]) + 1


def test_a_v1_only_peer_still_replicates_and_receives_forwards():
    bus = LocalBus()
    new = ClusterNode("new@x", bus, forward_mode="sync")
    old = ClusterNode("old@x", bus)
    for key in (("route", 2), ("broker", 2)):  # a node of the release before
        del old.rpc.registry._protos[key]
    try:
        assert old.join(new.name)
        got = []
        old.subscribe("s", "c", "v1/+/t", SubOpts(qos=1),
                      lambda m, o: got.append(m.payload))
        old.subscribe("s", "c", "v1/plain", SubOpts(qos=1),
                      lambda m, o: got.append(m.payload))
        old.flush()
        assert new.routes.has_route("v1/+/t") and new.routes.has_route("v1/plain")
        new.subscribe("s2", "c2", "other/#", SubOpts(), lambda m, o: None)
        new.flush()
        assert old.routes.has_route("other/#")  # v1 methods, filter by filter
        assert old.broker.metrics.get("cluster.route.batches") == 0
        assert new.broker.publish(Message(topic="v1/1/t", payload=b"a", qos=1)) == 1
        assert new.broker.publish_batch(
            [Message(topic="v1/plain", payload=b"b", qos=1),
             Message(topic="v1/2/t", payload=b"c", qos=1)]) == 2
        new.flush()
        assert sorted(got) == [b"a", b"b", b"c"]
        assert new.broker.metrics.get("messages.forward.failed") == 0
        old.unsubscribe("s", "v1/+/t")
        old.flush()
        assert not new.routes.has_route("v1/+/t")
    finally:
        new.rpc.stop()
        old.rpc.stop()


def tcp_nodes(n, loops=None, **bus_kw):
    buses = [TcpBus(f"n{i}@lanes", **bus_kw) for i in range(n)]
    nodes = [ClusterNode(b.node, b, forward_mode="sync",
                         loop=loops[i].loop if loops else None)
             for i, b in enumerate(buses)]
    for a in buses:
        for b in buses:
            if a is not b:
                a.add_peer(b.node, "127.0.0.1", b.port)
    for node in nodes[1:]:
        assert node.join(nodes[0].name)
    return buses, nodes


def close(buses, nodes, loops=()):
    for node in nodes:
        with node._lanes_lock:
            node._leaving = True
        node.rpc.stop()
        if node._fwd_pool is not None:
            node._fwd_pool.shutdown(wait=False)
            node._repl_pool.shutdown(wait=False)
    for bus in buses:
        bus.stop()
    for lt in loops:
        lt.stop()


def test_a_forward_whose_reply_is_lost_after_it_was_applied_is_delivered_once():
    buses, (a, b) = tcp_nodes(2, send_backoff_s=0.005)
    try:
        got = []
        b.subscribe("s", "c", "once/#", SubOpts(qos=1),
                    lambda m, o: got.append(m.payload))
        assert poll(lambda: a.routes.has_route("once/#"))
        a.broker.publish(Message(topic="once/0", payload=b"w", qos=1))
        a.flush()  # (the version handshake is behind us)
        assert got.pop() == b"w"
        # the request goes out, is applied, and its reply is thrown away:
        # the bus sends the same group again
        default_faults.arm("cluster.forward", mode="corrupt", max_fires=1)
        assert a.broker.publish(Message(topic="once/1", payload=b"x", qos=1)) == 1
        a.flush()
        assert got == [b"x"]
        assert b.broker.metrics.get("cluster.forward.duplicates") == 1
        assert a.broker.metrics.get("messages.forward.failed") == 0
        assert buses[0].metrics.get("cluster.send.retries") >= 1
        # the lane goes on from there: the next batch is a new one
        default_faults.disarm()
        a.broker.publish(Message(topic="once/2", payload=b"y", qos=1))
        a.flush()
        assert got == [b"x", b"y"]
        assert b.broker.metrics.get("cluster.forward.duplicates") == 1
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 0
    finally:
        default_faults.disarm()
        close(buses, [a, b])


class LoopThread:
    """An event loop of its own, as a live app's node has."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()

    def run(self, coro, timeout=10.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5.0)
        self.loop.close()


def hold_dispatch(node, gate):
    """`node`'s receiving half waits for `gate` before it dispatches."""
    inner = node.broker.adispatch_batch_folded

    async def held(msgs, forward=True):
        while not gate.is_set():
            await asyncio.sleep(0.01)
        return await inner(msgs, forward)
    node.broker.adispatch_batch_folded = held


def test_a_handler_slower_than_the_call_timeout_is_waited_for_not_sent_again():
    loops = [LoopThread(), LoopThread()]
    buses, (a, b) = tcp_nodes(2, loops, timeout=0.1, send_retries=1,
                              send_backoff_s=0.005)
    try:
        got, gate = [], threading.Event()
        dead_letters = buses[0].metrics.get("cluster.send.dead_letter")
        b.subscribe("s", "c", "slow/#", SubOpts(qos=1),
                    lambda m, o: got.append(m.payload))
        assert poll(lambda: a.routes.has_route("slow/#"))
        hold_dispatch(b, gate)

        async def publish():
            return a.forward_batch_remote(
                [Message(topic="slow/1", payload=b"x", qos=1)])
        assert loops[0].run(publish()) == [1]
        time.sleep(0.6)  # six call timeouts; the bus's own ladder is two
        assert got == [] and a.broker.metrics.gauge("cluster.forward.unconfirmed") == 1
        gate.set()
        assert poll(lambda: got == [b"x"])
        a.flush()
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 0
        assert a.broker.metrics.get("messages.forward.failed") == 0
        assert buses[0].metrics.get("cluster.send.dead_letter") == dead_letters
        assert a.broker.metrics.get("cluster.forward.retries") == 0
        assert b.broker.metrics.get("cluster.forward.duplicates") == 0
        h = a.broker.metrics.histogram("cluster.forward.confirm.seconds")
        assert h.count == 1 and h.sum >= 0.5
    finally:
        close(buses, [a, b], loops)


def app_cluster(n, mode):
    loops = [LoopThread() for _ in range(n)]
    bus = LocalBus()
    nodes = [ClusterNode(f"n{i}@app", bus, forward_mode=mode, loop=loops[i].loop)
             for i in range(n)]
    for node in nodes[1:]:
        assert node.join(nodes[0].name)
    return bus, nodes, loops


def test_a_stalled_peer_does_not_delay_the_forwards_to_a_third_node():
    bus, (a, b, c), loops = app_cluster(3, "async")
    try:
        got_b, got_c, gate = [], [], threading.Event()
        b.subscribe("s", "cb", "fan/#", SubOpts(qos=1),
                    lambda m, o: got_b.append(m.payload))
        c.subscribe("s", "cc", "fan/#", SubOpts(qos=1),
                    lambda m, o: got_c.append(m.payload))
        for node in (b, c):
            node.flush()
        assert a.routes.match_dests("fan/1").keys() == {b.name, c.name}
        hold_dispatch(b, gate)

        async def publish(k):
            return a.forward_batch_remote(
                [Message(topic=f"fan/{k}", payload=b"m%d" % k, qos=1)])
        for k in range(5):  # five batches queue behind b's first
            assert loops[0].run(publish(k)) == [2]
        assert poll(lambda: got_c == [b"m%d" % k for k in range(5)])
        assert got_b == []
        # c's five are confirmed (the reply follows the dispatch), b's wait
        assert poll(lambda: a.broker.metrics.gauge(
            "cluster.forward.unconfirmed") == 5)
        gate.set()
        assert poll(lambda: got_b == [b"m%d" % k for k in range(5)])
        a.flush()
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 0
        # b's queued batches left as one group behind the held one
        assert a.broker.metrics.get("cluster.forward.batches") == 10
    finally:
        gate.set()
        close([], [a, b, c], loops)


def test_in_sync_the_ack_future_waits_for_the_peers_confirmation():
    bus, (a, b), loops = app_cluster(2, "sync")
    try:
        got, gate = [], threading.Event()
        b.subscribe("s", "c", "ack/#", SubOpts(qos=1),
                    lambda m, o: got.append(m.payload))
        a.subscribe("s", "c", "ack/#", SubOpts(qos=1), lambda m, o: None)
        b.flush()
        hold_dispatch(b, gate)
        state = {}

        async def publish():
            r = await a.broker.apublish_enqueue(
                Message(topic="ack/1", payload=b"x", qos=1))
            state["r"] = r  # a future: the PUBACK is behind it
            return asyncio.isfuture(r) and not r.done()
        assert loops[0].run(publish()) is True
        time.sleep(0.2)
        assert not state["r"].done() and got == []
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 1
        gate.set()

        async def result():
            return await state["r"]
        assert loops[0].run(result()) == 2  # one local, one forwarded
        assert got == [b"x"]
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 0
        assert a.broker.metrics.get("messages.forward.failed") == 0
    finally:
        close([], [a, b], loops)


def test_in_async_the_count_comes_back_at_once():
    bus, (a, b), loops = app_cluster(2, "async")
    try:
        gate = threading.Event()
        b.subscribe("s", "c", "ack/#", SubOpts(qos=1), lambda m, o: None)
        b.flush()
        hold_dispatch(b, gate)

        async def publish():
            return await a.broker.apublish_enqueue(
                Message(topic="ack/1", payload=b"x", qos=1))
        assert loops[0].run(publish()) == 1
        gate.set()
        a.flush()
    finally:
        close([], [a, b], loops)


def test_route_batches_apply_in_slices_and_a_join_ships_its_routes_the_same_way():
    bus, (a, b), loops = app_cluster(2, "async")
    try:
        filters = [f"big/{i}/+/#" for i in range(3000)]

        async def subscribe_all():
            for f in filters:
                a.broker.subscribe("s", "c", f, SubOpts(), lambda m, o: None)
        loops[0].run(subscribe_all(), 30.0)
        a.flush()
        assert poll(lambda: b.routes.stats()["routes.count"] == 3000)
        m = b.broker.metrics
        assert m.get("cluster.route.ops") == 3000
        assert 1 <= m.get("cluster.route.batches") < 3000  # batched, not one each
        # a late joiner pulls the seed's replica and pushes its own routes
        late = ClusterNode("late@app", bus)
        late.subscribe("s", "c", "late/#", SubOpts(), lambda m, o: None)
        assert late.join(a.name)
        late.flush()
        assert late.routes.stats()["routes.count"] == 3001
        assert poll(lambda: a.routes.has_route("late/#")
                    and b.routes.has_route("late/#"))
        late.rpc.stop()
    finally:
        close([], [a, b], loops)


@pytest.mark.parametrize("mode, ok", [("async", True), ("sync", True),
                                      ("casts", False), ("", False)])
def test_cluster_rpc_mode_is_one_of_two_values(mode, ok):
    data = {"cluster": {"enable": True, "rpc_mode": mode}}
    if ok:
        assert load_config(data).cluster.rpc_mode == mode
    else:
        with pytest.raises(ConfigError, match="rpc_mode"):
            load_config(data)
    assert load_config({}).cluster.rpc_mode == "async"


def test_many_threads_feeding_one_lane_lose_nothing_and_keep_their_order():
    """The lanes' shared state (a lane's queue and its one worker, the
    unconfirmed count, the receiver's sequence record) under more feeders
    than cores and a short switch interval."""
    import sys

    bus, (a, b), loops = app_cluster(2, "sync")
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got = []
        b.subscribe("s", "c", "st/#", SubOpts(qos=1),
                    lambda m, o: got.append(m.payload))
        b.flush()
        feeders, each = 16, 150

        def feed(t):
            for k in range(each):
                a._unconfirmed_add(1)
                a._lane_put("fwd", b.name, (
                    [Message(topic=f"st/{t}", payload=b"%d.%d" % (t, k), qos=1)],
                    time.perf_counter(), None))
        threads = [threading.Thread(target=feed, args=(t,)) for t in range(feeders)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(30.0)
        assert not any(th.is_alive() for th in threads)
        a.flush(30.0)
        assert len(got) == feeders * each == len(set(got))  # once each
        for t in range(feeders):  # and in each feeder's order
            mine = [int(p.split(b".")[1]) for p in got if p.startswith(b"%d." % t)]
            assert mine == list(range(each))
        assert a.broker.metrics.gauge("cluster.forward.unconfirmed") == 0
        assert b.broker.metrics.get("cluster.forward.duplicates") == 0
        with b._fwd_in_lock:
            assert b._fwd_in[a.name]["done"] == a._lanes[("fwd", b.name)].seq
    finally:
        sys.setswitchinterval(was)
        close([], [a, b], loops)
