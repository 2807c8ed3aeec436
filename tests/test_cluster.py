"""Multi-node cluster tests on the in-process harness.

Mirrors the reference's slave-node CT suites:
- emqx_router_helper_SUITE (route cleanup on nodedown)
- emqx_cluster_rpc_SUITE (3-node config txn log)
- emqx_broker forward path (cross-node publish)
plus BPAPI immutability (emqx_bpapi_static_checks parity).
"""

from __future__ import annotations

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.cluster import make_cluster
from emqx_tpu.cluster.membership import FAILURE_TIMEOUT
from emqx_tpu.cluster.rpc import RpcError
from emqx_tpu.mqtt.packet import SubOpts


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def collector():
    got = []

    def deliver(msg, opts):
        got.append(msg)

    return got, deliver


@pytest.fixture
def cluster3():
    clock = FakeClock()
    bus, nodes = make_cluster(3, clock=clock)
    yield bus, nodes, clock
    for n in nodes:
        n.rpc.stop()


def test_membership_full_mesh(cluster3):
    _, nodes, _ = cluster3
    names = sorted(n.name for n in nodes)
    for n in nodes:
        assert n.membership.running_nodes() == names


def test_cross_node_publish_exact(cluster3):
    _, (a, b, c), _ = cluster3
    got, deliver = collector()
    b.subscribe("s1", "c1", "t/1", SubOpts(qos=0), deliver)
    # route replicated to all nodes (replication rides b's sender queues,
    # so drain b before asserting the other nodes see the route)
    b.flush()
    for n in (a, b, c):
        assert n.routes.has_route("t/1")
    n_del = a.publish(Message(topic="t/1", payload=b"x"))
    a.flush()
    assert n_del == 1
    assert len(got) == 1 and got[0].payload == b"x"


def test_cross_node_publish_wildcard_sync_replication(cluster3):
    _, (a, b, c), _ = cluster3
    got, deliver = collector()
    c.subscribe("s1", "c1", "dev/+/temp/#", SubOpts(qos=1), deliver)
    # wildcard replication is synchronous: visible immediately, no flush
    assert a.routes.has_route("dev/+/temp/#")
    assert b.routes.has_route("dev/+/temp/#")
    n = a.publish(Message(topic="dev/3/temp/x", qos=1))
    assert n == 1  # qos1 forwards synchronously
    assert len(got) == 1


def test_local_and_remote_fanout_dedup(cluster3):
    """aggre parity: one forward per node even with many matching filters."""
    _, (a, b, c), _ = cluster3
    got_b, del_b = collector()
    b.subscribe("s1", "cb1", "t/#", SubOpts(), del_b)
    b.subscribe("s2", "cb2", "t/+", SubOpts(), del_b)
    got_a, del_a = collector()
    a.subscribe("s3", "ca1", "t/x", SubOpts(), del_a)
    n = a.publish(Message(topic="t/x", qos=1))
    assert n == 3
    assert len(got_a) == 1 and len(got_b) == 2


def test_unsubscribe_removes_replicated_route(cluster3):
    _, (a, b, c), _ = cluster3
    got, deliver = collector()
    b.subscribe("s1", "c1", "u/+", SubOpts(), deliver)
    assert a.routes.has_route("u/+")
    assert b.unsubscribe("s1", "u/+")
    assert not a.routes.has_route("u/+")
    assert a.publish(Message(topic="u/1")) == 0


def test_route_gc_on_nodedown(cluster3):
    """emqx_router_helper parity: dead node's routes purged everywhere."""
    bus, (a, b, c), clock = cluster3
    got, deliver = collector()
    c.subscribe("s1", "c1", "gone/#", SubOpts(), deliver)
    c.subscribe("s2", "c2", "gone/exact", SubOpts(), deliver)
    assert a.routes.has_route("gone/#")
    # c dies silently (no graceful leave)
    bus.detach(c.name)
    clock.advance(FAILURE_TIMEOUT + 1)
    a.membership.heartbeat()
    b.membership.heartbeat()
    assert not a.membership.is_alive(c.name)
    assert not a.routes.has_route("gone/#")
    assert not a.routes.has_route("gone/exact")
    assert not b.routes.has_route("gone/#")
    assert a.publish(Message(topic="gone/exact")) == 0


def test_graceful_leave(cluster3):
    _, (a, b, c), _ = cluster3
    c.membership.leave()
    assert not a.membership.is_alive(c.name)
    assert not b.membership.is_alive(c.name)


def test_node_rejoin_after_partition(cluster3):
    bus, (a, b, c), clock = cluster3
    bus.partition(a.name, c.name)
    bus.partition(b.name, c.name)
    clock.advance(FAILURE_TIMEOUT + 1)
    a.membership.heartbeat()
    c.membership.heartbeat()
    assert not a.membership.is_alive(c.name)
    assert not c.membership.is_alive(a.name)
    bus.heal(a.name, c.name)
    bus.heal(b.name, c.name)
    assert c.join(a.name)
    assert a.membership.is_alive(c.name)
    got, deliver = collector()
    c.subscribe("s1", "c1", "re/1", SubOpts(), deliver)
    c.flush()
    a.flush()
    assert a.publish(Message(topic="re/1", qos=1)) == 1


def test_late_join_pulls_route_dump():
    from emqx_tpu.cluster import ClusterNode, LocalBus

    bus = LocalBus()
    a = ClusterNode("a@x", bus)
    b = ClusterNode("b@x", bus)
    b.join("a@x")
    got, deliver = collector()
    a.subscribe("s1", "c1", "early/+", SubOpts(), deliver)
    # c joins after routes exist: must bootstrap the replica
    c = ClusterNode("c@x", bus)
    c.join("a@x")
    assert c.routes.has_route("early/+")
    assert c.publish(Message(topic="early/1", qos=1)) == 1
    assert len(got) == 1


def test_channel_registry_and_discard(cluster3):
    _, (a, b, c), _ = cluster3
    got, deliver = collector()
    b.register_channel("client-1", "s1")
    b.subscribe("s1", "client-1", "cr/1", SubOpts(), deliver)
    for n in (a, b, c):
        n.flush()
    assert a.lookup_channel("client-1") == (b.name, "s1")
    # same clientid reconnects at node c with clean_start: discard on b
    assert c.discard_session("client-1")
    c.flush()
    b.flush()
    assert b.lookup_channel("client-1") is None
    assert not a.routes.has_route("cr/1")


def test_publish_batch_cross_node(cluster3):
    _, (a, b, c), _ = cluster3
    got_b, del_b = collector()
    got_c, del_c = collector()
    b.subscribe("s1", "c1", "bat/+/x", SubOpts(), del_b)
    c.subscribe("s2", "c2", "bat/#", SubOpts(), del_c)
    msgs = [Message(topic=f"bat/{i}/x") for i in range(50)]
    n = a.publish_batch(msgs)
    a.flush()
    assert n == 100
    assert len(got_b) == 50 and len(got_c) == 50


def test_shared_sub_across_cluster(cluster3):
    """$share group: each message goes to ONE member on the owner node."""
    _, (a, b, c), _ = cluster3
    got1, del1 = collector()
    got2, del2 = collector()
    b.subscribe("s1", "c1", "$share/g/sh/t", SubOpts(), del1)
    b.subscribe("s2", "c2", "$share/g/sh/t", SubOpts(), del2)
    b.flush()  # route replication b->a is async; drain before publishing
    for i in range(10):
        assert a.publish(Message(topic="sh/t", qos=1)) == 1
    assert len(got1) + len(got2) == 10
    assert len(got1) > 0 and len(got2) > 0  # round-robin spread


def test_cluster_config_multicall(cluster3):
    _, (a, b, c), _ = cluster3
    applied = {n.name: [] for n in (a, b, c)}
    for n in (a, b, c):
        n.conf_log.register_handler(
            "set", lambda k, v, _n=n: applied[_n.name].append((k, v))
        )
    res = a.config_multicall("set", ("mqtt.max_qos", 2))
    assert all(not isinstance(v, tuple) or v[0] != "badrpc" for v in res.values())
    for name in applied:
        assert applied[name] == [("mqtt.max_qos", 2)]
    # second txn from a different initiator keeps global order
    b.config_multicall("set", ("mqtt.retain", False))
    for name in applied:
        assert applied[name][-1] == ("mqtt.retain", False)
    assert a.conf_log.cursor == b.conf_log.cursor == c.conf_log.cursor == 2


def test_config_catch_up_after_rejoin(cluster3):
    bus, (a, b, c), clock = cluster3
    for n in (a, b, c):
        n.conf_log.register_handler("noop", lambda *args: None)
    bus.partition(a.name, c.name)
    bus.partition(b.name, c.name)
    a.config_multicall("noop", (1,))
    a.config_multicall("noop", (2,))
    assert c.conf_log.cursor == 0
    bus.heal(a.name, c.name)
    bus.heal(b.name, c.name)
    c.join(a.name)
    assert c.conf_log.cursor == 2


def test_bpapi_version_negotiation_and_freeze(cluster3):
    _, (a, b, c), _ = cluster3
    # frozen proto: re-registering the same version must fail
    with pytest.raises(RpcError):
        a.rpc.registry.register("broker", 1, {})
    # negotiation picks the highest common version
    a.rpc.registry.register("demo", 1, {"f": lambda: "v1"})
    a.rpc.registry.register("demo", 2, {"f": lambda: "v2"})
    b.rpc.registry.register("demo", 1, {"f": lambda: "v1"})
    a.rpc.forget_peer(b.name)
    assert a.rpc.supported_version(b.name, "demo") == 1
    assert a.rpc.call(b.name, "demo", "f") == "v1"


def test_multicall_collects_badrpc(cluster3):
    bus, (a, b, c), _ = cluster3
    bus.partition(a.name, c.name)
    res = a.rpc.multicall(
        [b.name, c.name], "route", "dump"
    )
    assert isinstance(res[b.name], list)
    assert res[c.name][0] == "badrpc"


def test_shared_sub_members_on_different_nodes_exactly_once(cluster3):
    """$share group SPANNING nodes: every member node holds the message
    (route forwarding) and the per-message dispatcher rotation picks
    exactly ONE of them — each message delivered exactly once
    cluster-wide AND the group balances across nodes instead of
    starving non-leader members (emqx_shared_sub's cluster-wide pick)."""
    _, (a, b, c), _ = cluster3
    got_b, del_b = collector()
    got_c, del_c = collector()
    b.subscribe("sb", "cb", "$share/xg/xs/t", SubOpts(), del_b)
    c.subscribe("sc", "cc", "$share/xg/xs/t", SubOpts(), del_c)
    b.flush(); c.flush()
    assert b._shared_nodes[("xs/t", "xg")] >= {b.name, c.name}
    mids = []
    for i in range(24):
        m = Message(topic="xs/t", qos=1)
        mids.append(m.mid)
        assert a.publish(m) >= 1
    [n.flush() for n in (a, b, c)]
    # exactly once per message, across BOTH nodes' members
    seen = [m.mid for m in got_b] + [m.mid for m in got_c]
    assert sorted(seen) == sorted(mids)
    assert len(got_b) > 0 and len(got_c) > 0  # no node starves
    # one node's member leaves -> the survivor owns every dispatch
    b.unsubscribe("sb", "$share/xg/xs/t")
    [n.flush() for n in (a, b, c)]
    before = len(got_c)
    for i in range(5):
        a.publish(Message(topic="xs/t", qos=1))
    [n.flush() for n in (a, b, c)]
    assert len(got_c) == before + 5 and len(got_b) <= 24


def test_retained_bootstrap_paged_100k(cluster3):
    """A joiner bootstraps a >=100k-message retained store via the v2
    PAGED read — bounded pages, full convergence (the v1 single-reply
    dump capped at RETAIN_DUMP_CAP and truncated beyond it)."""
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.retainer import Retainer

    bus, (a, b, c), _ = cluster3
    ra = Retainer(max_retained=200_000, device_threshold=1 << 62)
    rb = Retainer(max_retained=200_000, device_threshold=1 << 62)
    a.attach_retainer(ra, a.broker.hooks)
    N = 100_500
    for i in range(N):
        ra._insert(
            Message(topic=f"bk/{i % 37}/x/{i}", payload=b"v", retain=True)
        )
    pages = []
    orig_call = b.rpc.call

    def counting_call(node, api, method, *args, **kw):
        r = orig_call(node, api, method, *args, **kw)
        if api == "retain" and method == "dump_page":
            pages.append(len(r[0]))
        return r

    b.rpc.call = counting_call
    b.attach_retainer(rb, b.broker.hooks)
    assert b.join(a.name)
    assert len(rb) == N  # full store converged
    assert max(pages) <= a.RETAIN_PAGE_MAX  # bounded chunks
    assert len(pages) >= N // a.RETAIN_PAGE_MAX  # genuinely paged


# -- mesh-shard ownership (scale-out serving, docs/scale_out.md) ------------


def test_shard_slices_advertise_and_converge(cluster3):
    """Each node advertises its slice of the global subscriber-lane
    space; every replica agrees on the ownership map (advertise casts +
    join-time dump), and the serving span label follows."""
    _, (a, b, c), _ = cluster3
    for i, n in enumerate((a, b, c)):
        shards = n.attach_mesh_slice((4, 2), i, 3)
        assert shards == [f"s{i}/3"]
    for n in (a, b, c):
        n.flush()  # advertise casts ride the async sender
    for n in (a, b, c):
        assert n.shards.owner("s0/3") == a.name
        assert n.shards.owner("s1/3") == b.name
        assert n.shards.owner("s2/3") == c.name
    assert a.broker.shard_label.startswith("s0/3")
    assert "dp4tp2" in a.broker.shard_label


def test_shard_slice_survives_join_bootstrap():
    """A LATE joiner pulls the ownership map from its seed (it never saw
    the earlier advertise casts)."""
    from emqx_tpu.cluster import make_cluster

    bus, (a, b) = make_cluster(2)
    try:
        a.attach_mesh_slice((2, 2), 0, 3)
        b.attach_mesh_slice((2, 2), 1, 3)
        for n in (a, b):
            n.flush()  # b's advertise must land on the seed pre-join
        from emqx_tpu.cluster.node import ClusterNode

        c = ClusterNode("late@cluster", bus)
        c.attach_mesh_slice((2, 2), 2, 3)
        assert c.join(a.name)
        assert c.shards.owner("s0/3") == a.name
        assert c.shards.owner("s1/3") == b.name
        # and the earlier nodes learned the late slice (its advertisement
        # is an asynchronous cast: drained before the look)
        c.flush()
        assert a.shards.owner("s2/3") == "late@cluster"
        c.rpc.stop()
    finally:
        for n in (a, b):
            n.rpc.stop()


def test_node_loss_reowns_shard_and_reroutes_publishes(cluster3):
    """Node loss: the dead owner's slice re-owns onto a rendezvous
    survivor (same answer on every replica, zero coordination), the
    rebalance counter moves, and a publish that still names the dead
    owner (stale replica entry) forwards to the successor instead of
    stalling behind the dead peer."""
    bus, (a, b, c), clock = cluster3
    for i, n in enumerate((a, b, c)):
        n.attach_mesh_slice((4, 2), i, 3)
    for n in (a, b, c):
        n.flush()  # drain advertise casts
    # c dies silently (no goodbye)
    bus.detach(c.name)
    clock.advance(FAILURE_TIMEOUT + 1)
    a.membership.heartbeat()
    b.membership.heartbeat()
    assert not a.membership.is_alive(c.name)
    new_owner = a.shards.owner("s2/3")
    assert new_owner in (a.name, b.name)  # adopted by a survivor
    assert b.shards.owner("s2/3") == new_owner  # deterministic everywhere
    assert a.broker.metrics.get("mesh.shard.rebalance") >= 1
    assert a.shards.successor_node(c.name) == new_owner

    # stale replica entry still naming the dead owner: the forward
    # reroutes to the successor's slice instead of dead-lettering
    a.routes.add_route("own/#", c.name)
    before = {
        n.name: n.broker.metrics.get("messages.received")
        for n in (a, b)
    }
    n_del = a.publish(Message(topic="own/x"))
    a.flush()
    succ = [n for n in (a, b) if n.name == new_owner][0]
    assert (
        succ.broker.metrics.get("messages.received")
        == before[new_owner] + 1
    )
    assert a.broker.metrics.get("mesh.shard.reroutes") >= 1


def test_returning_owner_reclaims_its_home_shards(cluster3):
    """The re-own is a lease, not a transfer: when the original owner
    rejoins and re-advertises, its home shards come back."""
    bus, (a, b, c), clock = cluster3
    for i, n in enumerate((a, b, c)):
        n.attach_mesh_slice((4, 2), i, 3)
    for n in (a, b, c):
        n.flush()  # drain advertise casts
    bus.detach(c.name)
    clock.advance(FAILURE_TIMEOUT + 1)
    a.membership.heartbeat()
    b.membership.heartbeat()
    assert a.shards.owner("s2/3") != c.name
    # c returns: re-attach its bus + rejoin + re-advertise (join does it)
    bus.attach(c.name, c._handle)
    assert c.join(a.name)
    c.flush()  # drain the re-advertise casts
    assert a.shards.owner("s2/3") == c.name
    assert b.shards.owner("s2/3") == c.name
