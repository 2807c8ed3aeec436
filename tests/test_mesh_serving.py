"""The mesh-serving seam: a LIVE broker whose DeviceRouter executes the
SPMD dist step, and a cluster whose forward path rides the device batch
dispatch (SURVEY §2.4 TPU mapping).

Runs on the virtual 8-device CPU mesh from conftest; the same layout the
driver's dryrun_multichip gate compiles (emqx_broker.erl:278-293 is the
reference forward regime).
"""

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.message import Message
from emqx_tpu.cluster.node import make_cluster
from emqx_tpu.mqtt import packet as pkt


def make_mesh():
    from emqx_tpu.parallel.mesh import make_mesh as mm

    return mm(8)


def collector():
    got = []
    return got, lambda m, o: got.append(m)


def mesh_broker(min_batch=8):
    b = Broker()
    b.mesh = make_mesh()
    b.router.enable_tpu = True
    b.router.min_tpu_batch = min_batch
    return b


def test_live_broker_serves_through_dist_step():
    """Subscribe real subscribers, push a batch through
    dispatch_batch_folded, and verify the mesh path delivered and the
    device counter moved."""
    b = mesh_broker()
    buckets = {}
    for i in range(16):
        got, deliver = collector()
        buckets[i] = got
        b.subscribe(f"s{i}", f"c{i}", f"dev/{i}/+/t", pkt.SubOpts(), deliver)
    wide, wdeliver = collector()
    b.subscribe("sw", "cw", "dev/#", pkt.SubOpts(), wdeliver)

    msgs = [Message(topic=f"dev/{i % 16}/x/t", payload=str(i).encode())
            for i in range(64)]
    n = b.dispatch_batch_folded(msgs)
    assert sum(n) == 64 * 2  # per-device sub + wildcard sub
    for i in range(16):
        assert len(buckets[i]) == 4, (i, len(buckets[i]))
    assert len(wide) == 64
    assert b.metrics.get("messages.routed.device") == 64
    # the router genuinely ran in mesh mode
    assert b._device.mesh is not None


def test_mesh_serving_equivalence_vs_host_path():
    """Same subs + messages on a mesh broker and a host-path broker must
    deliver identically."""
    mb = mesh_broker()
    hb = Broker()
    hb.router.enable_tpu = False
    got_m, got_h = {}, {}
    for i in range(8):
        for tag, b, got in (("m", mb, got_m), ("h", hb, got_h)):
            bucket, deliver = collector()
            got[i] = bucket
            b.subscribe(f"s{i}", f"c{i}", f"a/{i}/#", pkt.SubOpts(), deliver)
    msgs = [Message(topic=f"a/{i % 8}/leaf/{i}") for i in range(32)]
    nm = mb.dispatch_batch_folded(msgs)
    nh = hb.dispatch_batch_folded(msgs)
    assert nm == nh
    for i in range(8):
        assert [m.topic for m in got_m[i]] == [m.topic for m in got_h[i]]


def test_mesh_serving_fallback_rows():
    """Rows the kernel flags (too deep) must fall back per-row to the CPU
    path, still on the mesh broker."""
    b = mesh_broker()
    got, deliver = collector()
    b.subscribe("s1", "c1", "deep/#", pkt.SubOpts(), deliver)
    deep = "deep/" + "/".join(str(i) for i in range(20))  # > max_levels
    msgs = [Message(topic=deep)] * 4 + [
        Message(topic="deep/ok") for _ in range(12)
    ]
    n = b.dispatch_batch_folded(msgs)
    assert sum(n) == 16
    assert len(got) == 16
    assert b.metrics.get("messages.routed.device_fallback") == 4


def test_cluster_forward_rides_device_batch_path():
    """Two in-process nodes: node A (owner) forwards a batch to node B;
    B's forward handler dispatches through the device batch path and
    messages.routed.device increments on BOTH nodes."""
    _, nodes = make_cluster(2)
    a, b = nodes
    for n in nodes:
        n.broker.mesh = make_mesh()
        n.broker.router.enable_tpu = True
        n.broker.router.min_tpu_batch = 8

    got_b, deliver_b = collector()
    b.subscribe("sb", "cb", "f/+/x", pkt.SubOpts(), deliver_b)
    got_a, deliver_a = collector()
    a.subscribe("sa", "ca", "f/+/x", pkt.SubOpts(), deliver_a)
    b.flush()
    a.flush()
    assert a.routes.has_route("f/+/x")

    msgs = [Message(topic=f"f/{i}/x", payload=str(i).encode())
            for i in range(32)]
    total = a.publish_batch(msgs)
    a.flush()  # drain the async forward to B
    assert total == 64  # 32 local on A + 32 forwarded to B
    assert len(got_a) == 32
    assert len(got_b) == 32
    assert [m.payload for m in got_b] == [str(i).encode() for i in range(32)]
    # the forward batch rode B's device path
    assert b.broker.metrics.get("messages.routed.device") >= 32
    for n in nodes:
        n.rpc.stop()


def test_cluster_forward_device_and_shared_groups():
    """Forwarded batches hitting $share groups on the receiver still
    deliver one-per-group through the device path's host pick."""
    _, nodes = make_cluster(2)
    a, b = nodes
    b.broker.mesh = make_mesh()
    b.broker.router.enable_tpu = True
    b.broker.router.min_tpu_batch = 8

    g1, d1 = collector()
    g2, d2 = collector()
    b.subscribe("m1", "m1", "$share/g/q/t", pkt.SubOpts(), d1)
    b.subscribe("m2", "m2", "$share/g/q/t", pkt.SubOpts(), d2)
    b.flush()
    assert a.routes.has_route("q/t")

    msgs = [Message(topic="q/t", payload=str(i).encode()) for i in range(16)]
    a.publish_batch(msgs)
    a.flush()
    assert len(g1) + len(g2) == 16  # exactly one delivery per message
    assert len(g1) > 0 and len(g2) > 0  # load-balanced
    for n in nodes:
        n.rpc.stop()


def test_app_config_enables_mesh_serving():
    """router.mesh_shape wires SPMD serving into a full BrokerApp."""
    import asyncio

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.schema import load_config

    async def run():
        app = BrokerApp(load_config({
            "listeners": [{"port": 0, "bind": "127.0.0.1"}],
            "dashboard": {"enable": False},
            "router": {"mesh_shape": [4, 2], "min_tpu_batch": 8},
        }))
        await app.start()
        try:
            assert app.broker.mesh is not None
            assert app.broker.mesh.shape == {"dp": 4, "tp": 2}
            got, deliver = collector()
            app.broker.subscribe("s", "c", "m/#", pkt.SubOpts(), deliver)
            msgs = [Message(topic=f"m/{i}") for i in range(16)]
            n = app.broker.dispatch_batch_folded(msgs)
            assert sum(n) == 16 and len(got) == 16
            assert app.broker.metrics.get("messages.routed.device") >= 16
        finally:
            await app.stop()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_mesh_shape_config_validation():
    from emqx_tpu.config.schema import ConfigError, load_config

    with pytest.raises(ConfigError):
        load_config({"router": {"mesh_shape": [4, 0]}})
    with pytest.raises(ConfigError):
        load_config({"router": {"mesh_shape": [4, 3]}})
    with pytest.raises(ConfigError):
        load_config({"router": {"mesh_shape": [4]}})
    load_config({"router": {"mesh_shape": [0, 0]}})  # off is fine
    load_config({"router": {"mesh_shape": [4, 2]}})


def test_mesh_tables_synced_sharded_and_reused():
    """Mesh-mode mirrors upload straight into the canonical sharding and
    are NOT re-placed across batches; churn flows as delta scatters."""
    b = mesh_broker()
    got, deliver = collector()
    b.subscribe("s1", "c1", "k/#", pkt.SubOpts(), deliver)
    b.dispatch_batch_folded([Message(topic=f"k/{i}") for i in range(8)])
    dev = b._device
    bits1 = dev._bits_sync._arrays["sub_bitmaps"]
    # placed with the canonical lane sharding, not single-device
    assert "tp" in str(bits1.sharding.spec)
    b.dispatch_batch_folded([Message(topic=f"k/{i}") for i in range(8)])
    assert dev._bits_sync._arrays["sub_bitmaps"] is bits1  # no re-upload
    # a subscribe reaches the mirror as a delta scatter, sharding kept
    b.subscribe("s2", "c2", "k2/#", pkt.SubOpts(), lambda m, o: None)
    b.dispatch_batch_folded([Message(topic=f"k/{i}") for i in range(8)])
    bits2 = dev._bits_sync._arrays["sub_bitmaps"]
    assert "tp" in str(bits2.sharding.spec)
    assert len(got) == 24


def test_mesh_share_pick_through_dist_step():
    """$share groups resolve ON-DEVICE on the mesh path (r3 verdict 4):
    picks come back with the dp-sharded batch and the host does delivery
    + failover only — no host-side pick wall in mesh mode."""
    b = mesh_broker()
    got1, d1 = collector()
    got2, d2 = collector()
    b.subscribe("g1", "cg1", "$share/grp/sh/+/t", pkt.SubOpts(), d1)
    b.subscribe("g2", "cg2", "$share/grp/sh/+/t", pkt.SubOpts(), d2)
    # plain subscriber on the same filter space, to prove both halves
    # (bitmap fan-out + group pick) ride one dist step
    gotp, dp_ = collector()
    b.subscribe("sp", "cp", "sh/#", pkt.SubOpts(), dp_)

    msgs = [Message(topic=f"sh/{i % 4}/t", payload=str(i).encode())
            for i in range(32)]
    n = b.dispatch_batch_folded(msgs)
    # each message: exactly one group member + the plain subscriber
    assert sum(n) == 32 * 2
    assert len(got1) + len(got2) == 32
    assert len(gotp) == 32
    assert b.metrics.get("messages.routed.device") == 32
    assert b._device.mesh is not None
    # round_robin across a 2-member group over 32 messages must balance
    # EXACTLY with the cross-shard occurrence offset (16/16); a shard-
    # local occurrence would double-pick per dp shard and skew it
    assert len(got1) == 16 and len(got2) == 16, (len(got1), len(got2))


def test_retained_storm_rides_mesh_fused_launch():
    """Wildcard-subscribe replay storms fuse into the MESH launch
    (dist_fused_step): the storm's chunk rows scan sharded over 'dp',
    the match matrix rides the same coalesced readback, and the waiters
    get exactly the retained topics the CPU walk would have found."""
    import asyncio

    from emqx_tpu.broker.retained_feed import RetainedStormFeed
    from emqx_tpu.models.retained_index import DeviceRetainedIndex
    from emqx_tpu.ops import topics as T

    async def run():
        b = mesh_broker()
        ridx = DeviceRetainedIndex(mesh=b.mesh)
        stored = [f"ret/{i % 5}/t{i}" for i in range(50)]
        for t in stored:
            assert ridx.add(t)
        # a LONG window: the replay must ride the publish launch, not
        # the standalone flush timer
        feed = RetainedStormFeed(ridx, metrics=b.metrics, window_s=30.0)
        b.retained_feed = feed
        fut_all = feed.submit("ret/#")
        fut_three = feed.submit("ret/3/+")
        got, deliver = collector()
        b.subscribe("s1", "c1", "pub/#", pkt.SubOpts(), deliver)
        msgs = [Message(topic=f"pub/{i}") for i in range(16)]
        n = await b.adispatch_batch_folded(msgs)
        assert sum(n) == 16 and len(got) == 16
        replay_all = await asyncio.wait_for(fut_all, 30)
        replay_three = await asyncio.wait_for(fut_three, 30)
        assert sorted(replay_all) == sorted(stored)
        assert sorted(replay_three) == sorted(
            t for t in stored if T.match(t, "ret/3/+")
        )
        # fused into the serving launch, not flushed standalone
        assert b.metrics.get("retained.storm.fused") == 1
        assert b.metrics.get("retained.storm.flushed") == 0
        # and it really was the mesh engine
        from emqx_tpu.models.router_model import MeshServingRouter

        assert isinstance(b._device, MeshServingRouter)
        assert b._device.supports_retained_fusion
        # chunk mirrors uploaded pre-sharded over 'dp'
        chunks = ridx._seg._arrays
        assert chunks and all(
            "dp" in str(a.sharding.spec) for a in chunks.values()
        )

    asyncio.run(asyncio.wait_for(run(), 120))


def test_mesh_device_step_span_grows_shard_attrs():
    """`router.device_step` spans on the mesh engine carry mesh_shape +
    shard attrs, so a causal trace records WHICH slice served it."""
    from emqx_tpu.observe.spans import SpanRecorder

    b = mesh_broker()
    b.shard_label = "s0/2@dp4tp2"
    rec = SpanRecorder(sample_rate=1.0)
    b.spans = rec
    got, deliver = collector()
    b.subscribe("s1", "c1", "sp/#", pkt.SubOpts(), deliver)
    msgs = [Message(topic=f"sp/{i}") for i in range(16)]
    for m in msgs:  # span heads: the device-step span links to these
        rec.publish_begin(m)
    b.dispatch_batch_folded(msgs)
    steps = [s for s in rec.spans() if s.name == "router.device_step"]
    assert steps, "no device-step span recorded"
    attrs = steps[-1].attrs
    assert attrs.get("device.mesh_shape") == "4x2"
    assert attrs.get("device.shard") == "s0/2@dp4tp2"


def test_mesh_share_pick_matches_host_path():
    """Mesh-mode group delivery counts must equal the host path's for the
    same workload (per-member assignment may differ across strategies
    with entropy, so compare with round_robin which is deterministic)."""
    mb = mesh_broker()
    hb = Broker()
    hb.router.enable_tpu = False
    counts = {}
    for tag, b in (("m", mb), ("h", hb)):
        for mem in range(3):
            got, deliver = collector()
            counts[(tag, mem)] = got
            b.subscribe(
                f"s{mem}", f"c{mem}", "$share/g3/q/#", pkt.SubOpts(), deliver
            )
    msgs = [Message(topic=f"q/{i}") for i in range(30)]
    nm = mb.dispatch_batch_folded(msgs)
    nh = hb.dispatch_batch_folded(msgs)
    assert sum(nm) == sum(nh) == 30
    mtot = sorted(len(counts[("m", m)]) for m in range(3))
    htot = sorted(len(counts[("h", m)]) for m in range(3))
    assert mtot == htot == [10, 10, 10]
