"""Sparse (CSR) device fan-out: O(subscriptions) subscriber tables.

The CSR representation (ops/csr_table.py + the `sparse_fanout_slots`
kernel) replaces the dense ``[Fcap, W]`` bitmap matrix behind the SAME
compact readback contract. These tests pin:

- the kernel's slot unions are exactly the dense reference's set bits;
- sparse dispatch delivers IDENTICAL recipient sets to dense dispatch
  across randomized subscribe/unsubscribe/shared-group churn, forced
  Kslot overflow (host-built dense fallback rows), tombstoned
  resubscribes, and a compaction cycle racing an in-flight snapshot —
  on a single device AND on a 2x2 mesh (slot column sharded over 'tp');
- the `router.sub_table` policy: auto flips once on occupancy x width,
  pins respected, representation flips are ordinary epoch bumps that
  every holder survives (including pickle/restore);
- the background sparse compaction cycle is racetrack-clean while loop
  inserts race it;
- the hotpath REST block and flight-recorder series record.
"""

import pickle
import threading

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.models.router_model import SubscriberTable
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.ops.csr_table import CsrSegmentOwner, CsrTable
from emqx_tpu.ops.matcher import MatcherConfig
from emqx_tpu.ops.segments import DeviceSegmentManager, SegmentCompactor


def _mk_broker(mode="sparse", fanout_slots=0, min_batch=1, strategy=None):
    b = Broker(
        router=Router(
            MatcherConfig(sub_table=mode, fanout_slots=fanout_slots),
            min_tpu_batch=min_batch,
        ),
        hooks=Hooks(),
    )
    if strategy:
        from emqx_tpu.broker.shared_sub import SharedSub

        b.shared = SharedSub(strategy=strategy)
    return b


# -- kernel ------------------------------------------------------------------

def test_sparse_kernel_matches_dense_reference():
    """Random CSR tables (tombstones in both segments included): the
    kernel's slot unions equal the per-fid reference union, counts are
    exact, and overflow fires exactly past the cap."""
    import jax.numpy as jnp

    from emqx_tpu.ops.csr_table import sparse_fanout_slots

    rng = np.random.default_rng(11)
    st = SubscriberTable(mode="sparse")
    live = {}
    for fid in range(24):
        for s in rng.choice(512, size=int(rng.integers(0, 12)),
                            replace=False):
            st.add(fid, int(s))
            live.setdefault(fid, set()).add(int(s))
    # tombstone some, move others hot via remove+re-add
    for fid in list(live)[::3]:
        s = next(iter(live[fid]))
        st.remove(fid, s)
        live[fid].discard(s)
    sp = st.csr
    # force part of the table through a compaction so packed regions +
    # hot entries + packed tombstones all participate
    sp.apply_compact(CsrTable.build_compact(sp.begin_compact()))
    for fid in range(24, 30):
        st.add(fid, int(rng.integers(0, 512)))
        live.setdefault(fid, set()).add(None)  # placeholder, fixed below
    live = {f: set(sp.slots_of(f).tolist()) for f in range(30)}
    csr = {k: jnp.asarray(v) for k, v in st.device_snapshot().items()}
    B, K, kslot = 12, 6, 8
    matched = np.full((B, K), -1, np.int32)
    for i in range(B):
        fids = rng.choice(30, size=int(rng.integers(0, K)), replace=False)
        matched[i, : len(fids)] = fids
    slots, count, over, _live = (
        np.asarray(a)
        for a in sparse_fanout_slots(csr, jnp.asarray(matched), kslot)
    )
    for i in range(B):
        ref = set()
        for fid in matched[i][matched[i] >= 0]:
            ref |= live.get(int(fid), set())
        got = set(slots[i][slots[i] >= 0].tolist())
        if over[i]:
            assert len(ref) > kslot or count[i] > kslot
            assert got <= ref
        else:
            assert count[i] == len(ref), (i, count[i], ref)
            assert got == ref, (i, got, ref)


def test_sparse_kernel_requires_kslot():
    import jax.numpy as jnp

    from emqx_tpu.ops.csr_table import sparse_fanout_slots

    st = SubscriberTable(mode="sparse")
    st.add(0, 0)
    csr = {k: jnp.asarray(v) for k, v in st.device_snapshot().items()}
    with pytest.raises(ValueError, match="kslot"):
        sparse_fanout_slots(csr, jnp.zeros((2, 2), jnp.int32), 0)


# -- property: sparse == dense recipient sets --------------------------------

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
    return "/".join(SEGS[int(rng.integers(0, 3))] for _ in range(depth))


def _churn(b, got, rng, rounds=3, shared=True):
    """Randomized subscribe/unsubscribe/shared churn; returns live sids."""
    subs = {}
    sid = 0
    for r in range(rounds):
        for _ in range(14):
            f = _rand_filter(rng)
            if shared and rng.random() < 0.25:
                f = f"$share/g{int(rng.integers(0, 2))}/{f}"
            name = f"s{sid}"
            sid += 1
            b.subscribe(
                name, name, f, pkt.SubOpts(),
                lambda m, o, _n=name: got.append((_n, m.topic)),
            )
            subs[name] = f
        # tombstoned resubscribe: drop a third, re-add half of those
        drop = [n for i, n in enumerate(sorted(subs)) if i % 3 == r % 3]
        for n in drop:
            b.unsubscribe(n, subs[n])
        for n in drop[:: 2]:
            b.subscribe(
                n, n, subs[n], pkt.SubOpts(),
                lambda m, o, _n=n: got.append((_n, m.topic)),
            )
        for n in drop[1:: 2]:
            del subs[n]
    return subs


@pytest.mark.parametrize("seed,kslot", [(1, 2), (2, 4), (3, 0)])
def test_sparse_vs_dense_identical_recipients(seed, kslot):
    """Same randomized workload through a sparse-pinned broker and a
    dense broker: identical delivery sets and counts. Tiny Kslot forces
    overflow rows through the HOST-BUILT dense fallback in the same
    batch as compact rows (there is no device matrix to fetch)."""
    rng_s, rng_d = (np.random.default_rng(seed) for _ in range(2))
    bs, gs = _mk_broker("sparse", kslot), []
    bd, gd = _mk_broker("dense", kslot), []
    _churn(bs, gs, rng_s)
    _churn(bd, gd, rng_d)
    topics = [_rand_topic(np.random.default_rng(seed + 99))
              for _ in range(24)]
    ns = bs.dispatch_batch_folded([Message(topic=t) for t in topics])
    nd = bd.dispatch_batch_folded([Message(topic=t) for t in topics])
    assert ns == nd
    assert sorted(gs) == sorted(gd)
    assert bs.subtab.sparse and not bd.subtab.sparse
    # the compact path really ran (a tiny Kslot may overflow every row)
    assert (
        bs.metrics.get("dispatch.compact.rows")
        + bs.metrics.get("dispatch.compact.overflow.rows")
    ) > 0


def test_forced_overflow_rows_rebuild_from_host_table():
    b = _mk_broker("sparse", fanout_slots=2)
    got = []
    for i in range(10):
        b.subscribe(
            f"s{i}", f"s{i}", "wide/+", pkt.SubOpts(),
            lambda m, o, _n=f"s{i}": got.append(_n),
        )
    counts = b.dispatch_batch_folded(
        [Message(topic="wide/x"), Message(topic="none/y")]
    )
    assert counts == [10, 0]
    assert sorted(got) == sorted(f"s{i}" for i in range(10))
    assert b.metrics.get("router.sparse.overflow.rows") == 1
    assert b.metrics.get("dispatch.compact.overflow.rows") == 1
    # host-built rows are NOT a device transfer: the readback histogram
    # recorded only the compact arrays
    h = b.metrics.histogram("dispatch.readback.bytes")
    assert h is not None and h.count == 1


def test_compaction_mid_batch_keeps_inflight_snapshot_valid():
    """prepare() -> compaction cycle (epoch bump + offered buffers) ->
    route against the OLD args: the in-flight snapshot must still
    deliver (free_retired grace), and the next prepare adopts the
    compacted table with identical results."""
    b = _mk_broker("sparse")
    got = []
    for i in range(12):
        b.subscribe(
            f"s{i}", f"s{i}", f"c/{i % 4}", pkt.SubOpts(),
            lambda m, o, _n=f"s{i}": got.append(_n),
        )
    dev = b._device_router()
    args = dev.prepare()
    owner = [
        o for o in dev.compaction_owners(hot_entries=1)
        if o.key == "bitmaps"
    ][0]
    assert isinstance(owner, CsrSegmentOwner)
    assert SegmentCompactor().compact_now(owner)
    msgs = [Message(topic="c/1")]
    res_old = dev.route_prepared(args, ["c/1"])
    n_old = b._dispatch_device_results(msgs, res_old)
    got_old, got[:] = sorted(got), []
    res_new = dev.route_prepared(dev.prepare(), ["c/1"])
    n_new = b._dispatch_device_results(msgs, res_new)
    assert n_old == n_new == [3]
    assert got_old == sorted(got)
    assert b.subtab.csr.hot_fill == 0  # the merge really happened


# -- mesh --------------------------------------------------------------------

def _mesh(n=4, tp=2):
    from emqx_tpu.parallel.mesh import make_mesh

    return make_mesh(n, tp=tp)


@pytest.mark.parametrize("seed", [5, 6])
def test_mesh_sparse_vs_dense_identical_recipients(seed):
    """The same randomized churn served through the 2x2 mesh with the
    slot column sharded over 'tp': recipient sets equal the dense mesh
    path's, including shared groups and overflow rows."""
    mesh = _mesh()
    outs = []
    for mode in ("sparse", "dense"):
        rng = np.random.default_rng(seed)
        b, got = _mk_broker(mode, fanout_slots=4), []
        b.mesh = mesh
        _churn(b, got, rng)
        topics = [_rand_topic(np.random.default_rng(seed + 7))
                  for _ in range(16)]
        n = b.dispatch_batch_folded([Message(topic=t) for t in topics])
        outs.append((n, sorted(got), b))
    (ns, gs, bs), (nd, gd, _bd) = outs
    assert ns == nd
    assert gs == gd
    assert bs.subtab.shards == mesh.shape["tp"]
    st = bs._device_router().shard_status()
    assert st["sub_table"] == "sparse"


def test_mesh_attach_after_flip_reshards_on_first_prepare():
    """Subscriptions land sparse with shards=1; a mesh attached later
    re-partitions the slot column on the first prepare instead of
    failing the sharded upload."""
    b = _mk_broker("sparse")
    got = []
    for i in range(8):
        b.subscribe(
            f"s{i}", f"s{i}", f"t/{i}", pkt.SubOpts(),
            lambda m, o: got.append(m.topic),
        )
    assert b.subtab.shards == 1
    b.mesh = _mesh()
    n = b.dispatch_batch_folded([Message(topic="t/3")])
    assert n == [1] and got == ["t/3"]
    assert b.subtab.shards == b.mesh.shape["tp"]


# -- representation policy ---------------------------------------------------

def test_auto_policy_flips_once_on_occupancy_x_width(monkeypatch):
    t = SubscriberTable(mode="auto")
    monkeypatch.setattr(SubscriberTable, "AUTO_MIN_DENSE_BYTES", 1 << 14)
    for i in range(64):
        t.add(i, i)
    assert not t.sparse  # small: stays dense
    # single-subscriber topics at growing fid/slot ids: occupancy falls
    e0 = t.epoch
    for i in range(64, 600):
        t.add(i * 7, i * 101)
    assert t.sparse and t.flips == 1
    assert t.epoch > e0
    # grow-only: more churn never flips back in auto mode
    for i in range(600, 700):
        t.add(i, i)
    assert t.flips == 1
    assert t.live == 64 + (600 - 64) + 100


def test_mode_pins_and_flip_back_preserve_contents():
    t = SubscriberTable(mode="dense")
    pairs = [(i % 9, i) for i in range(40)]
    for f, s in pairs:
        t.add(f, s)
    t.set_mode("sparse")
    assert t.sparse and t.arr is None
    for f in range(9):
        want = {s for ff, s in pairs if ff == f}
        assert set(t.csr.slots_of(f).tolist()) == want
    t.remove(0, 0)
    t.set_mode("dense")  # the degrade fallback direction
    assert not t.sparse and t.arr is not None
    assert t.live == len(pairs) - 1
    assert not t.arr[0, 0] & np.uint32(1)
    assert t.flips == 2


def test_fanout_compact_off_pins_dense():
    b = Broker(
        router=Router(
            MatcherConfig(sub_table="sparse", fanout_compact=False),
            min_tpu_batch=1,
        ),
        hooks=Hooks(),
    )
    assert not b.subtab.sparse and b.subtab.mode == "dense"


def test_config_schema_validates_sub_table():
    from emqx_tpu.config.schema import AppConfig, ConfigError, _validate

    cfg = AppConfig()
    cfg.router.sub_table = "csr"
    with pytest.raises(ConfigError, match="sub_table"):
        _validate(cfg)
    cfg.router.sub_table = "sparse"
    cfg.router.fanout_compact = False
    with pytest.raises(ConfigError, match="fanout_compact"):
        _validate(cfg)


def test_flip_visibility_through_live_device_router():
    """A broker serving dense flips sparse mid-life (policy pin): the
    next prepare swaps the mirror manager and serves identical sets."""
    b = _mk_broker("dense")
    got = []
    for i in range(10):
        b.subscribe(
            f"s{i}", f"s{i}", f"f/{i % 2}", pkt.SubOpts(),
            lambda m, o, _n=f"s{i}": got.append(_n),
        )
    n0 = b.dispatch_batch_folded([Message(topic="f/0")])
    ref, got[:] = sorted(got), []
    b.subtab.set_mode("sparse")
    n1 = b.dispatch_batch_folded([Message(topic="f/0")])
    assert n0 == n1 == [5]
    assert sorted(got) == ref
    assert b.metrics.get("router.sparse.flips") == 1


def test_sparse_table_pickles_and_restores():
    t = SubscriberTable(mode="sparse")
    for i in range(50):
        t.add(i % 7, i)
    t.remove(3, 3)
    t2 = pickle.loads(pickle.dumps(t))
    assert t2.sparse and t2.live == t.live
    for f in range(7):
        assert np.array_equal(
            np.sort(t2.csr.slots_of(f)), np.sort(t.csr.slots_of(f))
        )
    # restored tables keep mutating + snapshotting correctly
    t2.add(3, 3)
    assert 3 in t2.csr.slots_of(3).tolist()
    assert set(t2.device_snapshot()) == {
        "csr_off", "csr_len", "csr_slots", "hot_fid", "hot_slot"
    }


# -- sparse delta sync through the segment manager ---------------------------

def test_sparse_churn_rides_fused_delta_scatters():
    from emqx_tpu.ops import segments as seg

    calls = []
    real = seg._segment_scatter

    def spy(flats, idxs, vals):
        calls.append(sorted(flats))
        return real(flats, idxs, vals)

    seg._segment_scatter = spy
    try:
        t = SubscriberTable(mode="sparse")
        man = DeviceSegmentManager(name="bits")
        t.add(0, 0)
        man.sync(t)  # full upload
        assert calls == []
        t.add(1, 5)
        t.remove(0, 0)
        out = man.sync(t)
        assert len(calls) == 1  # whole suffix in ONE launch
        for k, v in t.device_snapshot().items():
            assert np.array_equal(
                np.asarray(out[k]).reshape(-1), v.reshape(-1)
            ), k
    finally:
        seg._segment_scatter = real


# -- racetrack: sparse compaction discipline ---------------------------------

@pytest.mark.race
def test_sparse_compaction_racing_loop_inserts_is_silent():
    """A full CSR compaction cycle (capture on loop, numpy merge +
    upload on the compact thread, apply + journal replay on loop) racing
    loop-side subscribes must be racetrack-clean — same discipline as
    the shape-index cycle."""
    from emqx_tpu.observe.racetrack import RaceTracker

    t = SubscriberTable(mode="sparse")
    for i in range(256):
        t.add(i % 31, i)
    man = DeviceSegmentManager(name="bits")
    man.sync(t)
    tracker = RaceTracker()
    tracker.watch(t, name="SubscriberTable")
    tracker.watch(man, name="SegmentManager")
    tracker.arm()
    try:
        owner = CsrSegmentOwner(t, man, hot_entries=1)
        cap = owner.begin()
        done = threading.Event()
        box = {}

        def build():
            box["b"] = owner.build(cap)
            done.set()

        th = threading.Thread(target=build, name="segment-compact-t")
        th.start()
        # loop-side churn racing the build
        t.add(500, 999)
        t.remove(5, 5)
        assert done.wait(15)
        th.join(5)
        applied = owner.apply(box["b"])
        assert applied is not None
        epoch, bufs, pos, _merged = applied
        man.offer(epoch, bufs, pos)
        out = man.sync(t)
    finally:
        tracker.disarm()
    races = tracker.unwaived_reports()
    assert not races, "\n".join(r.render() for r in races)
    # journal replay preserved the racing mutations
    assert 999 in t.csr.slots_of(500).tolist()
    assert 5 not in t.csr.slots_of(5).tolist()
    for k, v in t.device_snapshot().items():
        assert np.array_equal(
            np.asarray(out[k]).reshape(-1), v.reshape(-1)
        ), k


# -- session fusion twin -----------------------------------------------------

def test_session_route_step_composes_with_sparse_tables():
    """The session-fused serving program accepts the CSR table set: the
    route half's compact outputs match the plain sparse program's."""
    import jax.numpy as jnp

    from emqx_tpu.models.router_model import (
        session_route_step,
        shape_route_step,
    )
    from emqx_tpu.ops import tokenizer as tok
    from emqx_tpu.ops.route_index import RouteIndex
    from emqx_tpu.ops.session_table import ROW_LANES, SessionTable

    idx = RouteIndex()
    subs = SubscriberTable(mode="sparse")
    for i in range(16):
        fid = idx.add(f"s/{i}/+")
        subs.add(fid, i)
    subs.pack(idx.num_filters_capacity)
    csr = {k: jnp.asarray(v) for k, v in subs.device_snapshot().items()}
    topics = [f"s/{i % 16}/x" for i in range(8)]
    mat, lens, _ = tok.encode_topics(topics, 64)
    kw = dict(
        m_active=idx.shapes.m_active(),
        with_nfa=idx.residual_count > 0,
        salt=idx.salt,
        kslot=8,
    )
    st = idx.shapes.device_snapshot()
    nt = idx.nfa.device_snapshot() if idx.residual_count else None
    plain = shape_route_step(st, nt, csr, mat, np.asarray(lens), **kw)
    sess = SessionTable(capacity=256, slots=64)
    tables = {k: jnp.asarray(v) for k, v in sess.device_snapshot().items()}
    idxs = {k: np.zeros(16, np.int32) for k in ROW_LANES}
    vals = {k: np.zeros(16, np.int32) for k in ROW_LANES}
    fused = session_route_step(
        st, nt, csr, mat, np.asarray(lens),
        tables, idxs, vals, np.asarray([1, 10], np.int32),
        sweep_k=0, **kw,
    )
    assert np.array_equal(
        np.asarray(plain["slots"]), np.asarray(fused["slots"])
    )
    assert np.array_equal(
        np.asarray(plain["slot_count"]), np.asarray(fused["slot_count"])
    )
    assert fused["session"] is not None


# -- REST --------------------------------------------------------------------

def test_hotpath_rest_grows_sub_table_block():
    import asyncio
    import json
    import types

    from emqx_tpu.mgmt.api import MgmtApi

    b = _mk_broker("sparse")
    for i in range(6):
        b.subscribe(
            f"s{i}", f"s{i}", f"r/{i}", pkt.SubOpts(), lambda m, o: None
        )
    b.dispatch_batch_folded([Message(topic="r/1")])

    class _Alarms:
        def is_active(self, name):
            return False

    stub = types.SimpleNamespace(
        broker=b, app=types.SimpleNamespace(alarms=_Alarms())
    )
    resp = asyncio.run(MgmtApi.metrics_hotpath(stub, None))
    doc = json.loads(resp.body.decode())
    st = doc["sub_table"]
    assert st["mode"] == "sparse"
    assert st["subscriptions"] == 6
    assert st["bytes"] > 0
    assert st["csr_tombstones"] == 0
    assert "overflow_rows" in st and "rep_flips" in st
