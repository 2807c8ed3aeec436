"""Sparse fan-out compaction: O(matches) readback + vectorized dispatch.

The compaction stage (models/router_model.compact_fanout_slots) replaces
the dense [B, W] bitmap readback with per-row slot-id lists capped at
Kslot; rows past the cap fall back to a masked dense transfer. These
tests pin the contract:

- the kernel's slot lists are exactly the set bits (vs np.unpackbits);
- compact dispatch delivers the IDENTICAL recipient set as dense
  dispatch across random (filters, topics, Kslot), including forced
  overflow rows;
- the dense decode survives strided (non-contiguous) bitmap rows
  (regression: `bits.view(np.uint8)` raised on strided readback buffers);
- Kslot auto-sizing is p99-driven, pow2, grow-only;
- the readback flight-recorder series record.
"""

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.router import Router
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.ops.matcher import MatcherConfig


def _mk_broker(fanout_slots=0, fanout_compact=True, min_batch=1):
    return Broker(
        router=Router(
            MatcherConfig(
                fanout_slots=fanout_slots, fanout_compact=fanout_compact
            ),
            min_tpu_batch=min_batch,
        ),
        hooks=Hooks(),
    )


# -- kernel ------------------------------------------------------------------

def test_compact_kernel_matches_unpackbits():
    import jax.numpy as jnp

    from emqx_tpu.models.router_model import compact_fanout_slots

    rng = np.random.default_rng(7)
    B, W, K = 16, 8, 8
    bm = rng.integers(0, 1 << 32, size=(B, W), dtype=np.uint64)
    bm = np.where(rng.random((B, W)) < 0.75, 0, bm).astype(np.uint32)
    bm[0] = 0  # empty row
    bm[1] = 0xFFFFFFFF  # guaranteed overflow row (256 bits > K)
    slots, count, over = (
        np.asarray(a) for a in compact_fanout_slots(jnp.asarray(bm), K)
    )
    saw_overflow = saw_compact = False
    for i in range(B):
        ref = np.nonzero(
            np.unpackbits(bm[i].view(np.uint8), bitorder="little")
        )[0]
        assert count[i] == len(ref)
        assert bool(over[i]) == (len(ref) > K)
        got = slots[i][slots[i] >= 0]
        if over[i]:
            saw_overflow = True
            assert set(got.tolist()) <= set(ref.tolist())
        else:
            saw_compact = True
            # exact set, ascending order (word-major then bit order)
            assert np.array_equal(got, ref), (i, got, ref)
    assert saw_overflow and saw_compact


# -- property: compact == dense recipient sets -------------------------------

SEGS = ["a", "b", "c", "+", "#"]


def _rand_filter(rng):
    depth = int(rng.integers(1, 4))
    parts = []
    for lvl in range(depth):
        s = SEGS[int(rng.integers(0, len(SEGS)))]
        if s == "#" and lvl != depth - 1:
            s = "+"
        parts.append(s)
    return "/".join(parts)


def _rand_topic(rng):
    depth = int(rng.integers(1, 4))
    return "/".join(
        SEGS[int(rng.integers(0, 3))] for _ in range(depth)
    )


def _build(rng_seed, kslot, compact):
    rng = np.random.default_rng(rng_seed)
    b = _mk_broker(fanout_slots=kslot, fanout_compact=compact)
    got = []
    sid = 0
    for _ in range(12):
        f = _rand_filter(rng)
        for _ in range(int(rng.integers(1, 6))):
            name = f"s{sid}"
            sid += 1
            b.subscribe(
                name, name, f, pkt.SubOpts(),
                lambda m, o, _n=name: got.append((_n, m.topic)),
            )
    topics = [_rand_topic(rng) for _ in range(24)]
    # guaranteed low-fanout rows so every trial exercises the compact
    # path next to the overflow fallback: $-topics are unreachable from
    # the random wildcard filters (root-level +/# skip $, MQTT-5 4.7.2),
    # so these rows carry exactly 1 and 0 deliveries
    b.subscribe(
        "lone", "lone", "$sys/only", pkt.SubOpts(),
        lambda m, o: got.append(("lone", m.topic)),
    )
    topics += ["$sys/only", "$sys/nohit"]
    return b, got, topics


@pytest.mark.parametrize("seed,kslot", [(1, 2), (2, 4), (3, 2)])
def test_compact_vs_dense_identical_recipients(seed, kslot):
    """Same random workload through the forced-compact broker and the
    dense broker: byte-identical delivery sets, per-message counts
    equal. Tiny Kslot forces overflow rows through the masked dense
    fallback in the same batch as compact rows."""
    bc, got_c, topics = _build(seed, kslot, True)
    bd, got_d, _ = _build(seed, 0, False)
    msgs = [Message(topic=t) for t in topics]
    nc = bc.dispatch_batch_folded([Message(topic=t) for t in topics])
    nd = bd.dispatch_batch_folded(msgs)
    assert nc == nd
    assert sorted(got_c) == sorted(got_d)
    # the compact path really ran (dense broker must not have)
    assert bc.metrics.get("dispatch.compact.rows") > 0
    assert bd.metrics.get("dispatch.compact.rows") == 0


def test_forced_overflow_rows_fall_back_to_dense():
    b = _mk_broker(fanout_slots=2)
    got = []
    for i in range(10):
        name = f"s{i}"
        b.subscribe(
            name, name, "wide/+", pkt.SubOpts(),
            lambda m, o, _n=name: got.append(_n),
        )
    counts = b.dispatch_batch_folded(
        [Message(topic="wide/x"), Message(topic="none/y")]
    )
    assert counts == [10, 0]
    assert sorted(got) == sorted(f"s{i}" for i in range(10))
    assert b.metrics.get("dispatch.compact.overflow.rows") == 1
    assert b.metrics.get("dispatch.compact.rows") == 1
    h = b.metrics.histogram("dispatch.readback.bytes")
    assert h is not None and h.count == 1 and h.sum > 0


def test_no_local_honored_on_compact_path():
    b = _mk_broker(fanout_slots=4)
    got = []
    b.subscribe(
        "s1", "c1", "nl/t", pkt.SubOpts(no_local=True),
        lambda m, o: got.append(m.topic),
    )
    n = b.dispatch_batch_folded(
        [Message(topic="nl/t", from_client="c1")]
    )
    assert n == [0] and got == []
    n = b.dispatch_batch_folded(
        [Message(topic="nl/t", from_client="other")]
    )
    assert n == [1] and got == ["nl/t"]


def test_stale_snapshot_slot_reuse_on_compact_path():
    """Kernel ran against a snapshot whose slot has since been reused by
    an unrelated subscription: the per-delivery filter re-verify (now
    memoized per batch) must still block misdelivery."""
    b = _mk_broker(fanout_slots=4)
    got_old, got_new = [], []
    b.subscribe(
        "s1", "s1", "old/t", pkt.SubOpts(),
        lambda m, o: got_old.append(m.topic),
    )
    dev = b._device_router()
    args = dev.prepare()  # snapshot with s1 in slot 0
    b.unsubscribe("s1", "old/t")
    b.subscribe(  # reuses slot 0 with a DIFFERENT filter
        "s2", "s2", "new/t", pkt.SubOpts(),
        lambda m, o: got_new.append(m.topic),
    )
    msgs = [Message(topic="old/t")]
    results = dev.route_prepared(args, [m.topic for m in msgs])
    n = b._dispatch_device_results(msgs, results)
    assert n == [0] and got_old == [] and got_new == []


# -- strided dense decode (regression) ---------------------------------------

def test_dense_decode_survives_strided_rows():
    """`bits.view(np.uint8)` raises ValueError on non-contiguous rows —
    some backends hand back strided readback buffers."""
    b = _mk_broker(fanout_compact=False)
    got = []
    b.subscribe(
        "s1", "s1", "a/b", pkt.SubOpts(), lambda m, o: got.append(m.topic)
    )
    W = b.subtab.width_words
    bitmaps = np.zeros((4, W), np.uint32, order="F")
    bitmaps[0, 0] = 1  # slot 0 = s1
    row = bitmaps[0]
    assert not row.flags.c_contiguous  # the regression precondition
    n = b._dispatch_row(
        Message(topic="a/b"), row, np.empty(0, np.int32)
    )
    assert n == 1 and got == ["a/b"]


# -- Kslot auto-sizing -------------------------------------------------------

def test_kslot_auto_sizing_p99_pow2_grow_only():
    from emqx_tpu.models.router_model import (
        KSLOT_MIN,
        DeviceRouter,
        SubscriberTable,
    )
    from emqx_tpu.ops.route_index import RouteIndex

    m = Metrics()
    dev = DeviceRouter(RouteIndex(), SubscriberTable(), metrics=m)
    # cold histogram: the floor
    assert dev._fanout_kslot(width_words=1024) == KSLOT_MIN
    # warm at ~100 deliveries/message: p99-driven with 2x headroom
    for _ in range(400):
        m.observe("dispatch.fanout", 100)
    k1 = dev._fanout_kslot(1024)
    assert k1 >= 128 and (k1 & (k1 - 1)) == 0
    # grow-only: a later quiet period must not shrink (recompile churn)
    for _ in range(4000):
        m.observe("dispatch.fanout", 1)
    assert dev._fanout_kslot(1024) == k1
    # slot universe no wider than the cap: compaction off
    assert dev._fanout_kslot(width_words=2) == 0


def test_kslot_explicit_pin_and_disable():
    from emqx_tpu.models.router_model import DeviceRouter, SubscriberTable
    from emqx_tpu.ops.route_index import RouteIndex

    dev = DeviceRouter(
        RouteIndex(), SubscriberTable(), MatcherConfig(fanout_slots=5)
    )
    assert dev._fanout_kslot(2) == 8  # pow2-padded, pin beats the W gate
    dev = DeviceRouter(
        RouteIndex(), SubscriberTable(),
        MatcherConfig(fanout_compact=False),
    )
    assert dev._fanout_kslot(1024) == 0
    # match-only engines (no subscriber table) never compact
    dev = DeviceRouter(RouteIndex(), None)
    assert dev._fanout_kslot(1024) == 0
