"""Device-resident session & QoS state (docs/sessions.md).

Pins the subsystem's acceptance spine:
- the open-addressing (slot, pid) table: insert/lookup/clear/growth/
  bulk load, and compaction == fresh-build equivalence;
- write-through equivalence: a store-backed Session behaves EXACTLY
  like the host-dict Session (packets out, ack results, redelivery) —
  the degrade-ladder fallback property;
- fused ack clears: pending session writes ride a serving launch
  (session_route_step) with exactly ONE device->host transfer per
  batch — no extra launch, no extra readback (the PR 6 assertion);
- QoS2 handshake ordering across batch boundaries: a PUBREC landing
  while the originating batch's launch is still in flight never loses
  the rel-phase transition;
- device loss mid-inflight-window: launch faults between delivery and
  ack lose nothing — accepted QoS1 messages redeliver exactly once
  through the host-sweep fallback;
- mass resume as segment replay: capture/install re-arms every window
  with one full upload, no per-session objects;
- the monotonic-clock regression for broker/inflight.py (wall steps
  must not mass-expire or freeze windows).
"""

import asyncio
import functools
import pickle
import time

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.inflight import Inflight
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.broker.session_store import PID_SPACE, SessionStore
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe.faults import default_faults
from emqx_tpu.ops.session_table import (
    RESYNC,
    ST_AWAIT_REL,
    ST_PUBLISH,
    ST_PUBREL,
    SessionTable,
)


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        asyncio.run(asyncio.wait_for(fn(*a, **kw), timeout=60))

    return wrapper


@pytest.fixture(autouse=True)
def _disarm_faults():
    default_faults.disarm()
    yield
    default_faults.disarm()
    default_faults.metrics = None


def _mk_broker(min_batch=1):
    return Broker(router=Router(min_tpu_batch=min_batch), hooks=Hooks())


def _attach_store(b, **kw):
    kw.setdefault("capacity", 256)
    kw.setdefault("sweep_slots", 64)
    kw.setdefault("retry_interval", 30.0)
    store = SessionStore(metrics=b.metrics, **kw)
    b.session_store = store
    return store


def _session_sub(b, store, cid="c0", qos=1):
    """One store-backed subscriber session wired into broker fan-out."""
    sess = Session(cid, SessionConfig(), store=store)
    sent = []

    def deliver(m, o):
        sent.extend(sess.deliver(m, o))

    b.subscribe(cid, cid, "t/#", pkt.SubOpts(qos=qos), deliver)
    return sess, sent


def _msgs(n, qos=1):
    return [
        Message(topic=f"t/{i % 8}/x", payload=b"p", qos=qos)
        for i in range(n)
    ]


def _nomatch(n):
    """Batch with no subscribers: rides pending session writes (the
    rider) without generating new deliveries — a mirror 'flush'."""
    return [Message(topic=f"none/{i}", payload=b"p") for i in range(n)]


def _mirror(store):
    """The store's device mirror pulled to host (test readback only)."""
    import jax

    peek = store.manager.peek_delta(store.table)
    assert peek is not None, "mirror absent or needs a full resync"
    arrays, per, _pos, _epoch = peek
    assert not per, "mirror lags the host op-log"
    return jax.device_get(arrays)


# -- table unit --------------------------------------------------------------


class TestSessionTable:
    def test_insert_lookup_clear(self):
        t = SessionTable(capacity=64)
        r = t.insert(5, 100, ST_PUBLISH, 10, 3)
        assert t._find(5, 100) == r and t.live == 1
        assert t.lookup_batch([5, 5, 9], [100, 101, 100]).tolist() == [
            r, -1, -1,
        ]
        assert t.clear(r) == 3
        assert t.live == 0 and t.tombstones == 1
        assert t._find(5, 100) == -1

    def test_upsert_same_key_overwrites(self):
        t = SessionTable(capacity=64)
        r1 = t.insert(1, 7, ST_PUBLISH, 10, 1)
        r2 = t.insert(1, 7, ST_PUBREL, 20, -1)
        assert r1 == r2 and t.live == 1
        assert t.sess_state[r1] == ST_PUBREL

    def test_growth_preserves_entries(self):
        t = SessionTable(capacity=64)
        rows = {}
        for i in range(200):  # > 3/4 of 64 -> multiple doublings
            rows[(i, i % 50 + 1)] = t.insert(i, i % 50 + 1, ST_PUBLISH, i, -1)
        assert t.live == 200
        for (slot, pid) in rows:
            r = t._find(slot, pid)
            assert r >= 0 and t.sess_slot[r] == slot and t.sess_pid[r] == pid

    def test_bulk_insert_matches_scalar_inserts(self):
        a = SessionTable(capacity=256)
        b = SessionTable(capacity=256)
        n = 300
        slots = np.arange(n) % 64
        pids = np.arange(n) + 1
        for i in range(n):
            a.insert(int(slots[i]), int(pids[i]), ST_PUBLISH, i, i)
        b.bulk_insert(slots, pids, np.full(n, ST_PUBLISH), np.arange(n),
                      np.arange(n))
        assert a.live == b.live == n
        for i in range(n):
            ra = a._find(int(slots[i]), int(pids[i]))
            rb = b._find(int(slots[i]), int(pids[i]))
            assert ra >= 0 and rb >= 0
            assert a.sess_mid[ra] == b.sess_mid[rb] == i

    def test_due_and_expiry_scans(self):
        t = SessionTable(capacity=64)
        t.insert(1, 1, ST_PUBLISH, 0, -1)   # due at now=50, retry=30
        t.insert(1, 2, ST_PUBLISH, 40, -1)  # not due
        t.insert(1, 3, ST_PUBREL, 0, -1)    # rel phase: due too
        t.insert(1, 4, ST_AWAIT_REL, 0, -1)  # receiver side: never due
        due = t.due_rows(50, 30)
        assert sorted(t.sess_pid[due].tolist()) == [1, 3]
        t.set_expiry(7, 45)
        t.set_expiry(8, 60)
        assert t.expired_slots(50).tolist() == [7]

    def test_compaction_purges_tombstones_and_replays_journal(self):
        t = SessionTable(capacity=128)
        for i in range(40):
            t.insert(i, 1, ST_PUBLISH, i, i)
        for i in range(0, 40, 2):
            t.clear(t._find(i, 1))
        assert t.tombstones == 20
        cap = t.begin_compact()
        # mutations racing the (conceptually off-thread) build
        t.insert(100, 9, ST_PUBLISH, 99, -1)
        t.clear(t._find(1, 1))
        built = SessionTable.build_compact(cap)
        epoch = t.apply_compact(built)
        assert epoch == t.epoch
        assert t.tombstones <= 1  # journal clear may re-tombstone one
        assert t._find(100, 9) >= 0 and t._find(1, 1) == -1
        for i in range(3, 40, 2):
            assert t._find(i, 1) >= 0  # survivors relocated, still found
        for i in range(0, 40, 2):
            assert t._find(i, 1) == -1  # purged stay gone

    def test_compaction_aborts_on_structural_race(self):
        t = SessionTable(capacity=64)
        for i in range(10):
            t.insert(i, 1, ST_PUBLISH, 0, -1)
        cap = t.begin_compact()
        t.bulk_insert(  # epoch bump invalidates the capture
            np.arange(50) + 100, np.full(50, 2), np.full(50, ST_PUBLISH),
            np.zeros(50), np.full(50, -1),
        )
        built = SessionTable.build_compact(cap)
        assert t.apply_compact(built) is None

    def test_slot_growth_at_oplog_capacity_resyncs_instead_of_crashing(self):
        """Replay-audit finding: growing the expiry lane when the op-log
        sits exactly at OPLOG_MAX used to rewrite `oplog[-1]` right after
        `_log` bumped the epoch and CLEARED the log — IndexError on an
        empty list. The grow must fall back to the epoch bump (which
        already covers the re-upload)."""
        t = SessionTable(capacity=64, slots=64)
        t.OPLOG_MAX = 8
        for i in range(t.OPLOG_MAX):
            t._log("sess_ts", i, i)
        assert len(t.oplog) == t.OPLOG_MAX
        epoch0 = t.epoch
        t.set_expiry(200, 555)  # forces _grow_slots past capacity
        assert t.epoch == epoch0 + 1  # bump covered the grow
        assert t._scap >= 256 and t.slot_expiry[200] == 555
        # the post-grow write is the only delta the fresh epoch carries
        assert t.oplog == [("slot_expiry", 200, 555)]
        # below capacity the cheap path still rides the per-array marker
        t2 = SessionTable(capacity=64, slots=64)
        t2.set_expiry(100, 7)
        assert (RESYNC, "slot_expiry", 0) in t2.oplog
        assert t2.epoch == 0

    def test_double_clear_is_idempotent_and_replay_safe(self):
        """Replay-audit finding: clearing an already-tombstoned row used
        to double-decrement `live` and — with a compaction capture open —
        journal the TOMB sentinel as the slot, which `apply_compact`'s
        replay fed to `_find`/`_mix` where the negative value overflows
        uint64."""
        t = SessionTable(capacity=64)
        r = t.insert(3, 9, ST_PUBLISH, 10, 42)
        assert t.clear(r) == 42
        assert t.clear(r) == -1  # stale handle: no-op
        assert (t.live, t.tombstones) == (0, 1)
        assert t.oplog[-1] == ("sess_mid", r, -1)
        ver = t.version
        assert t.clear(r) == -1 and t.version == ver  # truly side-effect free
        # raced variant: the duplicate clear lands inside a capture
        for i in range(8):
            t.insert(i + 10, 1, ST_PUBLISH, i, i)
        cap = t.begin_compact()
        row = t._find(12, 1)
        assert t.clear(row) == 2
        t.clear(row)  # duplicate ack path — journals nothing
        built = SessionTable.build_compact(cap)
        assert t.apply_compact(built) == t.epoch  # no uint64 overflow
        assert t._find(12, 1) == -1 and t.live == 7


# -- monotonic clock (satellite: inflight.py regression) ---------------------


class TestInflightClock:
    def test_wall_clock_step_cannot_mass_expire(self, monkeypatch):
        mono = [1000.0]
        monkeypatch.setattr(time, "monotonic", lambda: mono[0])
        inf = Inflight(32)
        inf.insert(1, Message(topic="t", payload=b"x", qos=1))
        # wall clock leaps a year forward: nothing becomes due
        monkeypatch.setattr(time, "time", lambda: 4e9)
        assert inf.retry_due(30.0) == []
        # and a backward step cannot freeze the window either
        monkeypatch.setattr(time, "time", lambda: 0.0)
        mono[0] += 31.0
        assert [p for p, _ in inf.retry_due(30.0)] == [1]

    def test_codec_persists_ages_not_stamps(self, monkeypatch):
        from emqx_tpu.storage.codec import session_from_json, session_to_json

        mono = [500.0]
        monkeypatch.setattr(time, "monotonic", lambda: mono[0])
        s = Session("c", SessionConfig())
        s.deliver(Message(topic="t", payload=b"x", qos=1))
        mono[0] += 5.0
        snap = session_to_json(s)
        assert snap["inflight"][0]["age"] == pytest.approx(5.0, abs=0.1)
        mono[0] = 9000.0  # "another process"
        s2 = session_from_json(snap, SessionConfig())
        e = s2.inflight.get(snap["inflight"][0]["pid"])
        assert e.ts == pytest.approx(9000.0 - 5.0, abs=0.1)
        # legacy raw-stamp snapshots restore as fresh, never insta-due
        snap["inflight"][0].pop("age")
        snap["inflight"][0]["ts"] = 123456.0
        s3 = session_from_json(snap, SessionConfig())
        assert s3.inflight.retry_due(30.0) == []


# -- write-through equivalence (device store == dict store) ------------------


def _drive_session(sess):
    """One scripted QoS1/2 conversation; returns the observable trace."""
    trace = []
    pids = []
    for i in range(8):
        pkts = sess.deliver(
            Message(topic=f"q/{i}", payload=b"m", qos=1 + (i % 2))
        )
        trace.append([(p.qos, p.packet_id, p.dup) for p in pkts])
        pids.append(pkts[0].packet_id)
    # QoS1 acks for even indexes; QoS2 handshake for odd
    for i in range(0, 8, 2):
        acked, more = sess.puback(pids[i])
        trace.append((acked.topic if acked else None, len(more)))
    for i in range(1, 8, 2):
        trace.append(sess.pubrec(pids[i]))
    for i in range(1, 8, 2):
        done, more = sess.pubcomp(pids[i])
        trace.append((done.topic if done else None, len(more)))
    # incoming QoS2 dedup window
    trace.append(sess.await_rel(901))
    trace.append(sess.await_rel(901))  # duplicate
    trace.append(sess.release_rel(901))
    trace.append(sess.release_rel(901))
    return trace


class TestEquivalence:
    def test_store_session_equals_dict_session(self):
        plain = Session("eq", SessionConfig())
        store = SessionStore(capacity=256)
        backed = Session("eq", SessionConfig(), store=store)
        assert _drive_session(plain) == _drive_session(backed)
        # and the table drained to exactly the dict state: empty
        assert store.table.live == 0
        assert len(backed.inflight) == len(plain.inflight) == 0

    def test_table_mirrors_live_window(self):
        store = SessionStore(capacity=256)
        sess = Session("mw", SessionConfig(), store=store)
        pids = [
            sess.deliver(Message(topic="t", payload=b"x", qos=2))[0].packet_id
            for _ in range(3)
        ]
        sess.pubrec(pids[0])
        sess.await_rel(55)
        assert store.table.live == 4
        slot = sess.store_slot
        r = store.table._find(slot, pids[0])
        assert store.table.sess_state[r] == ST_PUBREL
        assert store.table.sess_mid[r] == -1  # payload freed at PUBREC
        r2 = store.table._find(slot, 55 + PID_SPACE)
        assert store.table.sess_state[r2] == ST_AWAIT_REL

    def test_redelivery_equivalence_sweep_vs_retry(self, monkeypatch):
        """The store sweep and the dict-path retry pick the SAME packets."""
        mono = [100.0]
        monkeypatch.setattr(time, "monotonic", lambda: mono[0])
        cfg = SessionConfig(retry_interval=30.0)
        plain = Session("rd", cfg)
        store = SessionStore(
            capacity=256, retry_interval=30.0, clock=lambda: mono[0]
        )
        backed = Session("rd", cfg, store=store)
        for s in (plain, backed):
            s.deliver(Message(topic="a", payload=b"1", qos=1))
            pid2 = s.deliver(
                Message(topic="b", payload=b"2", qos=2)
            )[0].packet_id
            s.pubrec(pid2)
        mono[0] += 31.0
        dict_out = sorted(
            (
                p.type,
                p.qos if p.type == pkt.PUBLISH else None,
                p.packet_id,
            )
            for p in plain.retry()
        )
        swept = []

        def resend(pid, state, msg):
            if state == ST_PUBREL:
                swept.append((pkt.PUBREL, None, pid))
            else:
                swept.append((pkt.PUBLISH, msg.qos, pid))
            return True

        store.bind(backed.store_slot, resend)
        n = store.host_sweep()
        assert n == 2
        assert sorted(swept) == dict_out
        # stamps refreshed: an immediate second sweep retransmits nothing
        assert store.host_sweep() == 0


# -- fused ack clears on the serving launch ----------------------------------


class TestFusedAckRide:
    @async_test
    async def test_acks_ride_one_launch_one_transfer(self):
        """Acceptance gate: session writes ride the batch's existing
        launch — exactly ONE device.transfer.bytes increment per batch,
        zero session scatter launches, mirror == host after the ride."""
        b = _mk_broker()
        store = _attach_store(b)
        sess, sent = _session_sub(b, store)
        # batch 1 establishes the mirror (full sync off the launch path)
        await b.adispatch_batch_folded(_msgs(8))
        assert len(sent) == 8
        pids = [p.packet_id for p in sent]
        for pid in pids[:4]:
            sess.puback(pid)
        incs = []
        real_inc = b.metrics.inc

        def spy(name, n=1):
            if name == "device.transfer.bytes":
                incs.append(n)
            return real_inc(name, n)

        b.metrics.inc = spy
        await b.adispatch_batch_folded(_msgs(8))  # rider rides this one
        assert len(incs) == 1, "session ride must not add a transfer"
        b.metrics.inc = real_inc
        assert b.metrics.get("session.ack.rides") == 1
        assert b.metrics.get("session.ack.rows") > 0
        assert store.manager.delta_launches == 0, (
            "ack deltas must not pay their own scatter launch"
        )
        # ack everything, flush with no-match batches (no new inserts):
        # the mirror converges on the host arrays exactly
        for p in sent[8:]:
            sess.puback(p.packet_id)
        await b.adispatch_batch_folded(_nomatch(4))
        await b.adispatch_batch_folded(_nomatch(4))
        assert store.manager.delta_launches == 0
        host = _mirror(store)
        t = store.table
        for lane in ("sess_slot", "sess_pid", "sess_state", "sess_ts",
                     "sess_mid"):
            assert (host[lane] == getattr(t, lane)).all(), lane

    @async_test
    async def test_device_sweep_rides_launch_and_redelivers(self):
        mono = [50.0]
        b = _mk_broker()
        store = _attach_store(b, retry_interval=1.0, clock=lambda: mono[0])
        sess, sent = _session_sub(b, store)
        resent = []
        store.bind(
            sess.store_slot,
            lambda pid, state, msg: resent.append((pid, state)) or True,
        )
        await b.adispatch_batch_folded(_msgs(6))
        await b.adispatch_batch_folded(_msgs(1))  # inserts ride
        assert store.table.live == 7
        mono[0] += 5.0  # everything past retry_interval
        store.request_sweep()
        await b.adispatch_batch_folded(_msgs(4))
        assert b.metrics.get("session.sweep.device") == 1
        assert b.metrics.get("session.redeliveries") >= 7
        assert sorted(p for p, _ in resent[:7]) == sorted(
            p.packet_id for p in sent[:7]
        )

    def test_one_rider_outstanding_and_abort_requeues(self):
        """Riders serialize (at most one in flight); an aborted rider's
        suffix rides the next take — nothing is lost."""
        store = SessionStore(capacity=128)
        s = Session("r1", SessionConfig(), store=store)
        s.deliver(Message(topic="a", payload=b"x", qos=1))
        assert store.take_rider() is None  # first: full sync, no suffix
        s.deliver(Message(topic="b", payload=b"x", qos=1))
        r1 = store.take_rider()
        assert r1 is not None and r1.rows > 0
        s.deliver(Message(topic="c", payload=b"x", qos=1))
        assert store.take_rider() is None  # serialized behind r1
        store.abort(r1)
        r2 = store.take_rider()
        assert r2 is not None and r2.pos > r1.pos
        # r2 re-carries r1's writes (same starting mirror position)
        assert set(r2.idxs) >= set(r1.idxs)


# -- QoS2 ordering across batch boundaries (satellite) -----------------------


class TestQoS2BatchOrdering:
    @async_test
    async def test_pubrec_during_stalled_launch_keeps_rel_phase(self):
        """PUBREC arriving while the originating publish's batch (and
        the rider carrying its insert) is still in flight must not lose
        the rel-phase transition — host stays authoritative, the mirror
        converges on the next ride."""
        b = _mk_broker()
        store = _attach_store(b)
        sess, sent = _session_sub(b, store, qos=2)
        ing = BatchIngest(b, max_batch=8, window_us=200)
        b.ingest = ing
        ing.start()
        futs = [
            await b.apublish_enqueue(m) for m in _msgs(4, qos=2)
        ]
        await asyncio.gather(*futs)
        assert len(sent) == 4
        pid = sent[0].packet_id
        # stall the NEXT launch (the one whose rider carries the insert)
        default_faults.arm("device.launch", mode="delay", delay_ms=80)
        futs = [await b.apublish_enqueue(m) for m in _nomatch(4)]
        await asyncio.sleep(0.02)  # launch taken + stalled in executor
        assert sess.pubrec(pid) is True  # mid-flight transition
        await asyncio.gather(*futs)
        default_faults.disarm()
        row = store.table._find(sess.store_slot, pid)
        assert store.table.sess_state[row] == ST_PUBREL
        # next bare launch carries the transition; mirror converges
        futs = [await b.apublish_enqueue(m) for m in _nomatch(4)]
        await asyncio.gather(*futs)
        await ing.stop()
        host = _mirror(store)
        assert host["sess_state"][row] == ST_PUBREL
        done, _ = sess.pubcomp(pid)
        assert done is not None and done.topic == sent[0].topic


# -- device loss mid-inflight-window (satellite: chaos extension) ------------


class TestDeviceLossMidInflight:
    @async_test
    async def test_launch_faults_between_delivery_and_ack_lose_nothing(self):
        from emqx_tpu.broker.degrade import DegradeController

        mono = [10.0]
        deg = DegradeController(
            metrics=None, max_retries=0, backoff_base_s=0.001,
            open_secs=60.0,
        )
        b = _mk_broker()
        deg.metrics = b.metrics
        deg.device.metrics = b.metrics
        b.degrade = deg
        store = _attach_store(b, retry_interval=1.0, clock=lambda: mono[0])
        sess, sent = _session_sub(b, store)
        resent = []
        store.bind(
            sess.store_slot,
            lambda pid, state, msg: resent.append((pid, msg.topic)) or True,
        )
        # accepted QoS1 deliveries, acks withheld: the window is open
        await b.adispatch_batch_folded(_msgs(6))
        assert store.table.live == 6
        # device dies mid-window: every launch fails, batches degrade to
        # the CPU trie; the rider aborts, nothing in the table is lost
        default_faults.metrics = b.metrics
        default_faults.arm("device.launch", mode="raise")
        counts = await b.adispatch_batch_folded(_msgs(4))
        assert sum(counts) == 4  # publishes SUCCEED via fallback
        assert b.metrics.get("degrade.fallback.batches") >= 1
        assert store.table.live == 10  # 6 old + 4 degraded-path inserts
        # redelivery flows through the HOST sweep while degraded:
        # every accepted message redelivers exactly once
        mono[0] += 5.0
        n = store.host_sweep()
        assert n == 10
        assert sorted(p for p, _ in resent) == sorted(
            p.packet_id for p in sent
        )
        assert store.host_sweep() == 0  # exactly once (stamps refreshed)
        # recovery: fault cleared — the next ride (a no-match flush
        # batch) carries the whole suffix, incl. the aborted rider's
        # writes, and the mirror reconverges on the host arrays
        default_faults.disarm()
        b.degrade = None
        await b.adispatch_batch_folded(_nomatch(2))
        host = _mirror(store)
        assert (host["sess_state"] == store.table.sess_state).all()
        assert (host["sess_pid"] == store.table.sess_pid).all()


# -- mass resume = segment replay --------------------------------------------


class TestMassResume:
    def test_capture_install_one_upload_rearms_every_window(self):
        mono = [5.0]
        store = SessionStore(
            capacity=1 << 13, sweep_slots=256, retry_interval=1.0,
            clock=lambda: mono[0],
        )
        n = 3000
        cids = [f"c{i}" for i in range(n)]
        msgs = [Message(topic=f"t/{i}", payload=b"m", qos=1)
                for i in range(n)]
        for cid, msg in zip(cids, msgs):
            store.inflight_insert(store.attach(cid), 1, msg, "publish")
        assert store.table.live == n
        state = pickle.loads(pickle.dumps(store.capture()))

        store2 = SessionStore(
            capacity=64, sweep_slots=256, retry_interval=1.0,
            clock=lambda: mono[0],
        )
        assert store2.install(state) == n
        assert store2.table.live == n
        # ONE full upload re-arms everything
        store2.manager.sync(store2.table)
        assert store2.manager.full_resyncs == 1
        # the whole restored population is redeliverable
        mono[0] += 50.0
        hits = []
        for cid in cids:
            store2.bind(
                store2.slot_of(cid),
                lambda pid, st, m: hits.append(m.topic) or True,
            )
        assert store2.host_sweep() == n
        assert len(set(hits)) == n

    def test_install_rebases_clock(self):
        mono = [100.0]
        store = SessionStore(capacity=256, retry_interval=30.0,
                             clock=lambda: mono[0])
        s = Session("cl", SessionConfig(), store=store)
        s.deliver(Message(topic="t", payload=b"x", qos=1))
        state = pickle.loads(pickle.dumps(store.capture()))
        mono[0] = 5000.0  # "restarted much later"
        store2 = SessionStore(capacity=64, retry_interval=30.0,
                              clock=lambda: mono[0])
        store2.install(state)
        # ages survive the rebase: the entry is not instantly due
        assert len(store2.table.due_rows(store2.now_ds(),
                                         store2.retry_ds)) == 0


# -- compaction owner --------------------------------------------------------


class TestSessionCompaction:
    def test_compactor_purges_and_offer_is_adopted(self):
        from emqx_tpu.ops.segments import SegmentCompactor

        store = SessionStore(capacity=256)
        sess = Session("cp", SessionConfig(max_inflight=256), store=store)
        pids = [
            sess.deliver(
                Message(topic=f"t/{i}", payload=b"x", qos=1)
            )[0].packet_id
            for i in range(120)
        ]
        store.manager.sync(store.table)
        for pid in pids[:100]:
            sess.puback(pid)
        owner = store.compaction_owner(tombstone_frac=0.25)
        assert owner.needs_compact()
        comp = SegmentCompactor()
        assert comp.compact_now(owner)
        assert store.table.tombstones == 0 and store.table.live == 20
        # next sync adopts the pre-uploaded buffers (no torn mirror)
        import jax

        arrays = store.manager.sync(store.table)
        host = jax.device_get(arrays)
        assert (host["sess_slot"] == store.table.sess_slot).all()
        for pid in pids[100:]:
            r = store.table._find(sess.store_slot, pid)
            assert r >= 0 and host["sess_pid"][r] == pid


# -- mesh placement ----------------------------------------------------------


class TestMeshPlacement:
    def test_session_rows_shard_over_dp_and_scatter_preserves_it(self):
        """On a mesh the session lanes upload sharded over 'dp' via the
        placement hook (PR 10 discipline) and delta scatters keep the
        layout; the mesh engine refuses riders (fusion is the
        single-device program — its mirrors ride the scatter path)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from emqx_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs a multi-device mesh")
        mesh = make_mesh(4, tp=2)
        store = SessionStore(capacity=256, mesh=mesh)
        s = Session("mp", SessionConfig(), store=store)
        s.deliver(Message(topic="t", payload=b"x", qos=1))
        arrays = store.manager.sync(store.table)
        assert arrays["sess_pid"].sharding.spec == P("dp")
        # delta scatters land pre-sharded too (placement re-pinned)
        s.deliver(Message(topic="u", payload=b"y", qos=1))
        arrays2 = store.manager.sync(store.table)
        assert store.manager.delta_launches == 1
        assert arrays2["sess_pid"].sharding.spec == P("dp")
        host = jax.device_get(arrays2)
        assert (host["sess_pid"] == store.table.sess_pid).all()
        # the broker's mesh engine gates the rider off
        b = _mk_broker()
        b.mesh = mesh
        dev = b._device_router()
        assert dev.supports_session_fusion is False
        assert dev.supports_retained_fusion is True


# -- channel/cm wiring -------------------------------------------------------


class TestLifecycleWiring:
    @async_test
    async def test_detach_arms_expiry_resume_rebinds(self):
        from emqx_tpu.broker.cm import ChannelManager

        b = _mk_broker()
        store = _attach_store(b)
        cm = ChannelManager(b, session_store=store)

        class Sink:
            def __init__(self):
                self.out = []

            def send_packet(self, p):
                self.out.append(p)

            def close(self, reason):
                pass

        from emqx_tpu.broker.channel import Channel, ChannelConfig

        cfg = ChannelConfig()
        cfg.session.expiry_interval = 3600
        ch = Channel(b, cm, Sink(), config=cfg)
        ch.client_id = "lw1"
        ch.clean_start = False
        sess, present = cm.open_session(ch)
        ch.session = sess
        ch.state = "connected"
        assert present is False
        slot = sess.store_slot
        assert store._bind.get(slot) == ch._store_resend
        assert store.table.slot_expiry[slot] == 0
        # detach: unbound + expiry lane armed; rows stay put
        sess.deliver(Message(topic="t", payload=b"x", qos=1))
        cm.on_channel_closed(ch, "sock_closed")
        assert slot not in store._bind
        assert store.table.slot_expiry[slot] > 0
        assert store.table.live == 1
        # resume on a new channel: rebind + expiry disarmed
        ch2 = Channel(b, cm, Sink(), config=cfg)
        ch2.client_id = "lw1"
        ch2.clean_start = False
        sess2, present2 = cm.open_session(ch2)
        assert present2 is True and sess2 is sess
        assert store._bind.get(slot) == ch2._store_resend
        assert store.table.slot_expiry[slot] == 0

    @async_test
    async def test_app_knob_wires_store_end_to_end(self, tmp_path=None):
        """`session.device_store` turns the subsystem on through the
        real app/config/socket path: sessions register slots, QoS1
        deliveries land in the table, detach arms the expiry lane."""
        from emqx_tpu.app import BrokerApp
        from emqx_tpu.config.schema import load_config
        from tests.minimqtt import MiniClient

        app = BrokerApp(
            load_config(
                {
                    "listeners": [{"port": 0, "bind": "127.0.0.1"}],
                    "dashboard": {"enable": False},
                    "router": {"enable_tpu": True, "min_tpu_batch": 1},
                    "session": {
                        "device_store": True,
                        "expiry_interval": 3600,
                        "store_capacity": 256,
                    },
                }
            )
        )
        await app.start()
        try:
            store = app.session_store
            assert store is not None
            assert app.broker.session_store is store
            assert app.cm.session_store is store
            port = list(app.listeners.list().values())[0].port
            sub = MiniClient("dsub", clean=False)
            await sub.connect("127.0.0.1", port)
            await sub.subscribe([("d/#", 1)])
            slot = store.slot_of("dsub")
            assert slot is not None and slot in store._bind
            pub = MiniClient("dpub")
            await pub.connect("127.0.0.1", port)
            await pub.publish("d/1", b"x", qos=1)
            got = await sub.recv(timeout=10)
            assert got["topic"] == "d/1" and got["qos"] == 1
            # MiniClient auto-acks: the row clears once the ack lands
            for _ in range(100):
                if store.table.live == 0:
                    break
                await asyncio.sleep(0.02)
            assert store.table.live == 0
            await sub.close()
            await asyncio.sleep(0.1)
            # detached with expiry: slot parked, expiry lane armed
            assert store.table.slot_expiry[slot] > 0
            assert slot not in store._bind
            await pub.close()
        finally:
            await app.stop()

    @async_test
    async def test_terminate_drops_rows_and_slot(self):
        from emqx_tpu.broker.cm import ChannelManager

        b = _mk_broker()
        store = _attach_store(b)
        cm = ChannelManager(b, session_store=store)

        class Sink:
            def send_packet(self, p):
                pass

            def close(self, reason):
                pass

        from emqx_tpu.broker.channel import Channel, ChannelConfig

        cfg = ChannelConfig()
        cfg.session.expiry_interval = 0  # clean: terminate on close
        ch = Channel(b, cm, Sink(), config=cfg)
        ch.client_id = "lw2"
        sess, _ = cm.open_session(ch)
        ch.session = sess
        ch.state = "connected"
        sess.deliver(Message(topic="t", payload=b"x", qos=1))
        assert store.table.live == 1
        cm.on_channel_closed(ch, "sock_closed")
        assert store.table.live == 0
        assert store.slot_of("lw2") is None
