"""`$share` groups whose members sit on different nodes, served on the
device batch path of an in-process four-node cluster.

Every node that holds a member of a group receives each matching message
(the route forward) and picks on its own device; exactly one of those nodes
dispatches it (`ClusterNode.shared_leader`), so a group gets each message
once cluster-wide (the reference picks among every node's members,
emqx_shared_sub.erl:234-285). The judge here is a plain dict of groups ->
members: each message is owed to one member of every group whose filter
matches, and the delivered multiset of every group is compared exactly.
Membership rides the route lane (`route` v3): a node whose replica holds a
peer's routes holds its groups too.
"""

from __future__ import annotations

import random
import threading
from collections import Counter

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.cluster import make_cluster
from emqx_tpu.cluster.transport import LocalBus
from emqx_tpu.cluster import node as node_mod
from emqx_tpu.cluster.node import ClusterNode
from emqx_tpu.mqtt.packet import SubOpts
from emqx_tpu.ops import topics as T

from tests.test_cluster import FakeClock
from tests.test_cluster_lanes import app_cluster, close, poll

FILTERS = ("t/{a}/+", "t/#", "t/{a}/{b}", "t/+/{b}", "t/{a}/#")


def device_cluster(n=4):
    """n joined nodes whose brokers route every batch on the device."""
    bus, nodes = make_cluster(n, clock=FakeClock())
    for node in nodes:
        node.broker.router.enable_tpu = True
        node.broker.router.min_tpu_batch = 1
    return bus, nodes


@pytest.fixture
def cluster4():
    bus, nodes = device_cluster()
    yield bus, nodes
    for node in nodes:
        node.rpc.stop()


def random_groups(seed, n_nodes, n_groups=6):
    """{group: {"filter": real filter, "members": [(node index, sid)]}},
    members on random nodes (a group may hold two on one node)."""
    rng = random.Random(seed)
    groups = {}
    for g in range(n_groups):
        flt = rng.choice(FILTERS).format(a=rng.randrange(3), b=rng.randrange(3))
        members = [(rng.randrange(n_nodes), f"s{seed}-g{g}-m{m}")
                   for m in range(rng.randint(1, 4))]
        groups[f"g{g}"] = {"filter": flt, "members": members}
    return groups


def subscribe_groups(nodes, groups):
    """-> {sid: [delivered mids]} of every member."""
    got = {}
    for gname, g in groups.items():
        for node_i, sid in g["members"]:
            box = got[sid] = []
            nodes[node_i].subscribe(
                sid, sid, f"$share/{gname}/{g['filter']}", SubOpts(qos=1),
                lambda m, o, _b=box: _b.append(m.mid))
    return got


def publish(nodes, rng, batches=3, rows=64):
    """Batches of random topics, each from a random node, on the device
    path. -> {mid: topic}."""
    sent = {}
    for _ in range(batches):
        msgs = [Message(topic=f"t/{rng.randrange(3)}/{rng.randrange(3)}",
                        qos=1, from_client=f"pub{rng.randrange(5)}")
                for _ in range(rows)]
        sent.update((m.mid, m.topic) for m in msgs)
        rng.choice(nodes).broker.dispatch_batch_folded(msgs)
    for node in nodes:
        node.flush()
    return sent


def owed(groups, sent):
    """The plain reference: group -> multiset of the mids owed to it."""
    return {gname: Counter(mid for mid, topic in sent.items()
                           if T.match(topic, g["filter"]))
            for gname, g in groups.items()}


def delivered(groups, got):
    return {gname: Counter(mid for _, sid in g["members"] for mid in got[sid])
            for gname, g in groups.items()}


def membership(groups):
    """(real, group) -> the node indices that hold a member."""
    out = {}
    for gname, g in groups.items():
        out.setdefault((g["filter"], gname), set()).update(
            i for i, _ in g["members"])
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 2147483901])
def test_each_group_gets_each_matching_message_once_cluster_wide(cluster4, seed):
    _, nodes = cluster4
    groups = random_groups(seed, len(nodes))
    got = subscribe_groups(nodes, groups)
    sent = publish(nodes, random.Random(seed))
    assert delivered(groups, got) == owed(groups, sent)
    for node in nodes:
        m = node.broker.metrics
        assert m.get("messages.routed.device") > 0
        assert m.get("messages.routed.device_fallback") == 0


@pytest.mark.parametrize("seed", [1, 2147483901])
def test_led_and_deferred_add_up_to_the_picks_on_every_node(cluster4, seed):
    _, nodes = cluster4
    groups = random_groups(seed, len(nodes))
    subscribe_groups(nodes, groups)
    sent = publish(nodes, random.Random(seed))
    led = 0
    for node in nodes:
        m = node.broker.metrics
        picks = m.get("shared.picks")
        assert picks > 0
        assert m.get("shared.leader.led") + m.get("shared.leader.deferred") == picks
        led += m.get("shared.leader.led")
    # the nodes that led are the deliveries: one a (message, group) owed
    assert led == sum(sum(c.values()) for c in owed(groups, sent).values())


def test_a_lone_broker_counts_no_leadership():
    from emqx_tpu.broker.broker import Broker

    b = Broker()
    b.router.min_tpu_batch = 1
    got = []
    b.subscribe("s", "c", "$share/g/t/+", SubOpts(), lambda m, o: got.append(m))
    b.dispatch_batch_folded([Message(topic="t/1") for _ in range(8)])
    b.publish(Message(topic="t/2"))
    assert len(got) == 9 and b.metrics.get("shared.picks") == 8
    assert b.metrics.get("shared.leader.led") == 0
    assert b.metrics.get("shared.leader.deferred") == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_membership_is_complete_on_every_node_once_routes_are(cluster4, seed):
    _, nodes = cluster4
    groups = random_groups(seed, len(nodes), n_groups=10)
    subscribe_groups(nodes, groups)
    want = membership(groups)
    routes = {(f, nodes[i].name) for (f, _), held in want.items() for i in held}
    for node in nodes:
        assert set(node.routes.routes()) == routes
        assert {k: {nodes.index(next(n for n in nodes if n.name == x))
                    for x in v} for k, v in node._shared_nodes.items()} == want
        assert node.broker.metrics.gauge("shared.cluster.pairs") == len(want)


# one filter a group: a route's add is followed by its one group's join
LIVE_FILTERS = ("t/0/+", "t/+/1", "t/2/#", "t/#", "t/+/+", "t/1/2", "t/3/+", "t/+/3")


def test_on_live_loops_the_groups_land_with_the_routes_not_behind_the_worker(
        monkeypatch):
    """App mode: each node's rpc handlers run on an event loop of its own,
    its lanes and its replication worker on pools. Every node's
    replication worker is held busy and every loop is held while the
    groups subscribe, so a route lane gathers routes and joins into one
    mixed batch, which the receiver applies one op a slice (every route
    cut from its join by a turn of the loop). Once the routes are on every
    node the groups are too, the worker still held; then each group gets
    each matching message once cluster-wide."""
    monkeypatch.setattr(node_mod, "ROUTE_SLICE_MAX", 1)
    _, nodes, loops = app_cluster(4, "async")
    busy, held = threading.Event(), threading.Event()
    batches, slices = [], []
    try:
        for node in nodes:
            node._pool_submit(node._repl_pool, busy.wait, 30.0)
            node._route_slice = 1
            node.broker.router.enable_tpu = True
            node.broker.router.min_tpu_batch = 1

            def batch(peer, ops, _inner=node._aapply_routes):
                batches.append([op[0] for op in ops])
                return _inner(peer, ops)

            def routes_in(ops, peer, _inner=node.routes.apply_batch):
                slices.append(len(ops))
                return _inner(ops, peer)
            node._aapply_routes, node.routes.apply_batch = batch, routes_in
        for lt in loops:
            lt.loop.call_soon_threadsafe(held.wait, 10.0)
        rng = random.Random(43)
        groups = {f"g{k}": {"filter": f, "members": [
            (i, f"live-g{k}-m{i}") for i in sorted(rng.sample(range(4), rng.randint(2, 4)))]}
            for k, f in enumerate(LIVE_FILTERS)}
        got = subscribe_groups(nodes, groups)
        held.set()
        want = membership(groups)
        routes = {(f, nodes[i].name) for (f, _), at in want.items() for i in at}
        assert poll(lambda: all(set(n.routes.routes()) == routes for n in nodes))
        for node in nodes:
            node.flush()
        names = [n.name for n in nodes]
        for node in nodes:
            assert {k: {names.index(x) for x in v}
                    for k, v in node._shared_nodes.items()} == want, node.name
            assert node.broker.metrics.gauge("shared.cluster.pairs") == len(want)
        # a lane's batch held routes and joins, and was applied one op a slice
        assert any({"add", "join"} <= set(kinds) for kinds in batches)
        assert slices and set(slices) == {1}

        sent = {}
        for k in range(3):
            msgs = [Message(topic=f"t/{rng.randrange(4)}/{rng.randrange(4)}",
                            qos=1, from_client=f"pub{rng.randrange(5)}")
                    for _ in range(32)]
            sent.update((m.mid, m.topic) for m in msgs)

            async def pub(_node=nodes[k % 4], _msgs=msgs):
                return _node.broker.dispatch_batch_folded(_msgs)
            loops[k % 4].run(pub(), timeout=60.0)
        want_dlv = owed(groups, sent)
        total = sum(sum(c.values()) for c in want_dlv.values())
        assert poll(lambda: sum(map(len, got.values())) >= total, timeout=60.0)
        for node in nodes:
            node.flush()
        assert delivered(groups, got) == want_dlv
        for node in nodes:
            assert node.broker.metrics.get("messages.routed.device") > 0
    finally:
        busy.set()
        held.set()
        close([], nodes, loops)


def test_a_leave_moves_every_later_dispatch_to_the_survivors(cluster4):
    """Group `g` has members on nodes 0, 1 and 2, group `h` shares its
    filter on node 1. Node 1's member of `g` leaves: every later message
    reaches `g` once, on node 0 or 2, and `h` keeps its route on node 1."""
    _, nodes = cluster4
    got = {sid: [] for sid in ("g0", "g1", "g2", "h1")}

    def sub(node, sid, group):
        nodes[node].subscribe(sid, sid, f"$share/{group}/t/+/x", SubOpts(),
                              lambda m, o, _b=got[sid]: _b.append(m.mid))
    for node, sid in ((0, "g0"), (1, "g1"), (2, "g2")):
        sub(node, sid, "g")
    sub(1, "h1", "h")
    rng = random.Random(7)

    def volley():
        msgs = [Message(topic=f"t/{i}/x", from_client=f"p{rng.randrange(9)}")
                for i in range(64)]
        nodes[3].broker.dispatch_batch_folded(msgs)
        for node in nodes:
            node.flush()
        return Counter(m.mid for m in msgs)

    first = volley()
    assert Counter(got["g0"] + got["g1"] + got["g2"]) == first
    assert got["g1"], "node 1 led none of 64 messages"
    assert nodes[1].unsubscribe("g1", "$share/g/t/+/x")
    for node in nodes:
        assert node._shared_nodes[("t/+/x", "g")] == {nodes[0].name, nodes[2].name}
        assert nodes[1].name in node.routes.match_dests("t/1/x")  # h's route
    before = {sid: len(v) for sid, v in got.items()}
    second = volley()
    assert len(got["g1"]) == before["g1"]
    assert Counter(got["g0"][before["g0"]:] + got["g2"][before["g2"]:]) == second
    assert Counter(got["h1"]) == first + second


def test_route_lane_items_apply_in_order_and_are_timed():
    """One v3 batch: runs of routes and of memberships, applied in the
    lane's order; one section entry a membership op."""
    from emqx_tpu.observe import profiler as prof

    node = ClusterNode("n0@x", LocalBus())
    try:
        ops = [("add", "a/#"), ("join", "a/#", "g"), ("join", "a/#", "h"),
               ("add", "b"), ("leave", "a/#", "g"), ("delete", "a/#"),
               ("join", "b", "k")]
        before = dict(prof._acc().acc).get("cluster.shared.apply", [0, 0, 0])[2]
        assert node._proto_route_apply_batch("n1@x", ops) == len(ops)
        assert node.routes.routes() == [("b", "n1@x")]
        assert node._shared_nodes == {("a/#", "h"): {"n1@x"}, ("b", "k"): {"n1@x"}}
        assert node.broker.metrics.gauge("shared.cluster.pairs") == 2
        assert node.broker.metrics.get("cluster.route.ops") == len(ops)
        assert prof._acc().acc["cluster.shared.apply"][2] - before == 4
    finally:
        node.rpc.stop()


def test_a_peer_without_route_v3_still_learns_the_groups_by_cast():
    bus = LocalBus()
    new = ClusterNode("new@x", bus)
    old = ClusterNode("old@x", bus)
    del old.rpc.registry._protos[("route", 3)]  # a node of the release before
    try:
        assert old.join(new.name)
        new.subscribe("s", "c", "$share/g/v2/+", SubOpts(), lambda m, o: None)
        old.subscribe("s2", "c2", "$share/g/v2/+", SubOpts(), lambda m, o: None)
        for node in (new, old):
            node.flush()
        for node in (new, old):
            assert node._shared_nodes[("v2/+", "g")] == {new.name, old.name}
            assert set(node.routes.routes()) == {("v2/+", new.name), ("v2/+", old.name)}
        assert old.broker.metrics.get("cluster.route.batches") > 0  # v2 routes
        new.unsubscribe("s", "$share/g/v2/+")
        new.flush()
        assert old._shared_nodes[("v2/+", "g")] == {old.name}
        assert not old.routes.match_dests("v2/1").get(new.name)
    finally:
        new.rpc.stop()
        old.rpc.stop()
