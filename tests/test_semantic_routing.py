"""Semantic routing plane (docs/semantic_routing.md).

Pins the embedding-filter subsystem end to end:

- `semantic_match_step` equals a numpy top-k reference over randomized
  tables (scoped/unscoped entries, tombstones, both segments);
- the union into the compact slot readback keeps the TOPIC contract
  byte-identical and never double-delivers;
- broker recipient sets (semantic ∪ topic) equal an independent numpy
  reference under randomized subscribe/unsubscribe/compaction churn,
  on a single device AND a 2x2 mesh, through forced Kslot overflow,
  and identically on the CPU degrade path;
- the SemanticTable compaction cycle is equivalent to a from-scratch
  rebuild and racetrack-clean while loop inserts race it;
- intake plumbing: wire formats, SUBSCRIBE lifecycle, config
  validation, REST endpoints, and the hotpath block.
"""

import asyncio
import json
import threading
import types

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.semantic import SemanticRouting, decode_embedding
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.ops import topics as T
from emqx_tpu.ops.matcher import MatcherConfig
from emqx_tpu.ops.segments import DeviceSegmentManager, SegmentCompactor
from emqx_tpu.ops.semantic_table import (
    SemanticSegmentOwner,
    SemanticTable,
    semantic_match_step,
)

DIM = 16


def _unit(rng, n=None):
    v = rng.normal(size=(n, DIM) if n else DIM).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def _mk_broker(dim=DIM, topk=8, threshold=0.5, min_batch=1, **mc):
    b = Broker(
        router=Router(MatcherConfig(**mc), min_tpu_batch=min_batch),
        hooks=Hooks(),
    )
    b.semantic = SemanticRouting(
        dim=dim, topk=topk, threshold=threshold, metrics=b.metrics
    )
    return b


def _msg(topic, emb=None, payload=b"{}"):
    m = Message(topic=topic, payload=payload, from_client="pub")
    if emb is not None:
        m.headers["semantic_embedding"] = np.asarray(emb, np.float32)
    return m


def _recorder(got, name):
    def deliver(msg, opts):
        got.setdefault(name, set()).add((msg.topic, msg.mid))

    return deliver


# -- kernel ------------------------------------------------------------------

def test_kernel_matches_numpy_topk_reference():
    """Randomized table (scoped + unscoped + tombstones in both
    segments): kernel winners == numpy top-k of qualifying entries,
    counts uncapped."""
    rng = np.random.default_rng(3)
    sem = SemanticTable(dim=DIM, topk=4)
    for i in range(40):
        sem.add(i, _unit(rng), float(rng.uniform(0.0, 0.6)),
                fid=-1 if i % 3 == 0 else i % 5)
    for i in range(0, 40, 7):
        sem.remove(i)
    st = {k: v.copy() for k, v in sem.device_snapshot().items()}
    B = 16
    q = _unit(rng, B)
    matched = np.full((B, 6), -1, np.int32)
    for b in range(B):
        matched[b, : b % 4] = rng.choice(5, size=b % 4, replace=False)
    slots_out, count = semantic_match_step(st, q, matched, 4)
    slots_out = np.asarray(slots_out)
    count = np.asarray(count)
    vecs, slots, fids, ths = sem.live_arrays()
    sims = q @ vecs.T
    for b in range(B):
        mrow = set(matched[b][matched[b] >= 0].tolist())
        ok = (sims[b] >= ths) & (
            (fids < 0) | np.isin(fids, list(mrow) or [-9])
        )
        assert count[b] == int(ok.sum())
        idx = np.nonzero(ok)[0]
        want = set(
            slots[idx[np.argsort(-sims[b][idx])[:4]]].tolist()
        ) if len(idx) else set()
        got = {s for s in slots_out[b].tolist() if s >= 0}
        assert got == want, (b, got, want)


def test_union_keeps_topic_contract_and_dedups():
    """`union_semantic_slots`: the first kslot columns stay
    byte-identical (slot_count/overflow semantics untouched) and a
    winner already in the topic part nulls out."""
    import jax.numpy as jnp

    from emqx_tpu.ops.semantic_table import union_semantic_slots

    slots = jnp.asarray([[1, 5, -1, -1], [2, 3, 4, 7]], jnp.int32)
    sem = jnp.asarray([[5, 9], [-1, 11]], jnp.int32)
    u = np.asarray(union_semantic_slots(slots, sem))
    assert np.array_equal(u[:, :4], np.asarray(slots))
    assert u[0].tolist()[4:] == [-1, 9]  # 5 deduped against topic part
    assert u[1].tolist()[4:] == [-1, 11]


# -- broker recipient property ----------------------------------------------

def _reference(subs, topic, emb, topk):
    """Independent recipient model over (sid, filter) subscriptions:
    plain topic matches + qualifying semantic entries (scope AND
    similarity), global top-k over ENTRIES."""
    out = set()
    qual = []
    for (sid, f), v in subs.items():
        if v == "plain":
            if T.match(topic, f):
                out.add(sid)
            continue
        _kind, vec, th = v
        if emb is None or not T.match(topic, f):
            continue
        sim = float(np.dot(emb, vec))
        if sim >= th:
            qual.append((sim, sid))
    qual.sort(reverse=True)
    out |= {sid for _s, sid in qual[:topk]}
    return out


def _churn_property(mesh=None, compact_every=0, kslot=0):
    rng = np.random.default_rng(11 if mesh is None else 13)
    b = _mk_broker(topk=8, threshold=0.45, fanout_slots=kslot)
    if mesh is not None:
        b.mesh = mesh
        b.semantic.table.reshard(mesh.shape["tp"])
    got = {}
    subs = {}  # (sid, filter) -> "plain" | ("sem", vec, th)
    topics = [f"s/{i}/t" for i in range(8)] + ["s/0/u", "x/y"]
    filters = ["s/#", "s/+/t", "x/y"] + [f"s/{i}/t" for i in range(4)]
    opts = pkt.SubOpts(qos=0)
    compactor = SegmentCompactor()
    owners = None
    for step in range(12):
        # churn wave: subscribes (plain + semantic) and unsubscribes
        for _ in range(6):
            sid = f"c{int(rng.integers(0, 24))}"
            f = filters[int(rng.integers(0, len(filters)))]
            if rng.random() < 0.3 and (sid, f) in subs:
                b.unsubscribe(sid, f)
                del subs[(sid, f)]
                continue
            if rng.random() < 0.5:
                vec = _unit(rng)
                th = float(rng.uniform(0.3, 0.7))
                b.subscribe(sid, sid, f, opts, _recorder(got, sid),
                            embedding=vec, sem_threshold=th)
                subs[(sid, f)] = ("sem", vec, th)
            else:
                b.subscribe(sid, sid, f, opts, _recorder(got, sid))
                subs[(sid, f)] = "plain"
        if compact_every and step % compact_every == compact_every - 1:
            if owners is None:
                owners = b._device_router().compaction_owners()
            for o in owners:
                if o.needs_compact():
                    compactor.compact_now(o)
        # publish a batch (some rows without embeddings)
        msgs, refs = [], []
        for _ in range(24):
            t = topics[int(rng.integers(0, len(topics)))]
            e = _unit(rng) if rng.random() < 0.8 else None
            msgs.append(_msg(t, e))
            refs.append((t, e))
        got.clear()
        b.dispatch_batch_folded(msgs)
        want = {}
        for m, (t, e) in zip(msgs, refs):
            for sid in _reference(subs, t, e, 8):
                want.setdefault(sid, set()).add((t, m.mid))
        assert got == want, (step, {
            k: got.get(k, set()) ^ want.get(k, set())
            for k in set(got) | set(want)
            if got.get(k, set()) != want.get(k, set())
        })
    return b


def test_recipients_equal_reference_under_churn_single_device():
    b = _churn_property(compact_every=4)
    assert b.metrics.get("semantic.hits") > 0


def test_recipients_equal_reference_under_churn_mesh_2x2():
    import jax

    from emqx_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    _churn_property(mesh=make_mesh(4, tp=2), compact_every=5)


def test_cpu_path_parity_with_device_path():
    """The host twin (degrade target) delivers the same recipient sets
    the fused kernel does."""
    rng = np.random.default_rng(5)
    b = _mk_broker(topk=8, threshold=0.4)
    got = {}
    opts = pkt.SubOpts(qos=0)
    b.subscribe("p1", "p1", "a/#", opts, _recorder(got, "p1"))
    for i in range(6):
        b.subscribe(f"m{i}", f"m{i}", "a/#", opts,
                    _recorder(got, f"m{i}"),
                    embedding=_unit(rng), sem_threshold=0.4)
    msgs = [_msg(f"a/{i}", _unit(rng)) for i in range(16)]
    b.dispatch_batch_folded(msgs)
    dev = {k: set(v) for k, v in got.items()}
    got.clear()
    b.router.enable_tpu = False
    # fresh Message objects with the same topics/embeddings
    msgs2 = [
        _msg(m.topic, m.headers["semantic_embedding"]) for m in msgs
    ]
    b.dispatch_batch_folded(msgs2)
    remap = {m2.mid: m1.mid for m1, m2 in zip(msgs, msgs2)}
    cpu = {
        k: {(t, remap[mid]) for t, mid in v} for k, v in got.items()
    }
    assert dev == cpu


def test_overflow_rows_keep_semantic_winners():
    """A row whose TOPIC fan-out overflows Kslot falls back to the
    dense row — the semantic winners must still deliver (they ride the
    device slot row; broker unions them back in, deduped)."""
    rng = np.random.default_rng(9)
    b = _mk_broker(topk=4, threshold=0.4, fanout_slots=8)
    got = {}
    opts = pkt.SubOpts(qos=0)
    for i in range(40):  # forces slot_count > kslot=8
        b.subscribe(f"p{i}", f"p{i}", "big/t", opts,
                    _recorder(got, f"p{i}"))
    vec = _unit(rng)
    b.subscribe("sem", "sem", "big/#", opts, _recorder(got, "sem"),
                embedding=vec, sem_threshold=0.9)
    m = _msg("big/t", vec)  # sim 1.0 with itself
    batch = [m] + [_msg("big/t") for _ in range(3)]
    b.dispatch_batch_folded(batch)
    assert ("big/t", m.mid) in got["sem"]
    for i in range(40):
        assert len(got[f"p{i}"]) == 4  # dense fallback intact
    assert b.metrics.get("dispatch.compact.overflow.rows") > 0


def test_topk_truncation_is_bounded_and_counted():
    rng = np.random.default_rng(21)
    b = _mk_broker(topk=4, threshold=0.0)
    got = {}
    opts = pkt.SubOpts(qos=0)
    vec = _unit(rng)
    for i in range(12):
        b.subscribe(f"s{i}", f"s{i}", "#", opts, _recorder(got, f"s{i}"),
                    embedding=vec, sem_threshold=-1.0)
    b.dispatch_batch_folded([_msg("t/x", vec) for _ in range(4)])
    delivered = sum(len(v) for v in got.values())
    assert delivered == 4 * 4  # topk recipients per message, no more
    assert b.metrics.get("semantic.topk.truncated") == 4


# -- table / compaction ------------------------------------------------------

def test_compaction_equals_rebuild_and_replays_journal():
    rng = np.random.default_rng(2)
    sem = SemanticTable(dim=DIM, topk=4)
    man = DeviceSegmentManager(name="semantic")
    for i in range(30):
        sem.add(i, _unit(rng), 0.5, fid=i % 3 - 1)
    for i in range(0, 30, 5):
        sem.remove(i)
    man.sync(sem)
    owner = SemanticSegmentOwner(sem, man, hot_entries=1)
    cap = owner.begin()
    # mutations racing the build journal + replay
    sem.add(100, _unit(rng), 0.2)
    sem.remove(7)
    built = owner.build(cap)
    applied = owner.apply(built)
    assert applied is not None
    epoch, bufs, pos, _merged = applied
    man.offer(epoch, bufs, pos)
    out = man.sync(sem)
    assert sem.hot_fill <= 2  # only the journaled add stays hot
    ent = dict((s, (f, t)) for s, f, t in sem.entries())
    assert 100 in ent and 7 not in ent and 0 not in ent
    for k, v in sem.device_snapshot().items():
        assert np.array_equal(np.asarray(out[k]), v), k


def test_interleaved_ops_equal_from_scratch():
    rng = np.random.default_rng(4)
    sem = SemanticTable(dim=DIM, topk=4)
    model = {}
    for i in range(200):
        slot = int(rng.integers(0, 40))
        if rng.random() < 0.3:
            sem.remove(slot)
            model.pop(slot, None)
        else:
            v = _unit(rng)
            th = float(rng.uniform(0, 1))
            fid = int(rng.integers(-1, 5))
            sem.add(slot, v, th, fid=fid)
            model[slot] = (v, th, fid)
        if i % 60 == 59:
            owner = SemanticSegmentOwner(
                sem, DeviceSegmentManager(name="semantic"),
                hot_entries=1,
            )
            cap = owner.begin()
            assert sem.apply_compact(SemanticTable.build_compact(cap))
    assert len(sem) == len(model)
    vecs, slots, fids, ths = sem.live_arrays()
    for j, slot in enumerate(slots.tolist()):
        v, th, fid = model[slot]
        assert np.allclose(vecs[j], v, atol=1e-6)
        assert ths[j] == pytest.approx(th)
        assert fids[j] == fid


@pytest.mark.race
def test_semantic_compaction_racing_loop_inserts_is_silent():
    """The SemanticTable compaction cycle (capture on loop, build on
    the compact thread, apply + journal replay on loop) racing
    loop-side inserts must be racetrack-clean — the same discipline as
    the shape/CSR cycles."""
    from emqx_tpu.observe.racetrack import RaceTracker

    rng = np.random.default_rng(6)
    sem = SemanticTable(dim=DIM, topk=4)
    man = DeviceSegmentManager(name="semantic")
    for i in range(64):
        sem.add(i, _unit(rng), 0.5)
    man.sync(sem)
    tracker = RaceTracker()
    tracker.watch(sem, name="SemanticTable")
    tracker.watch(man, name="SegmentManager")
    tracker.arm()
    try:
        owner = SemanticSegmentOwner(sem, man, hot_entries=1)
        cap = owner.begin()
        done = threading.Event()
        box = {}

        def build():
            box["b"] = owner.build(cap)
            done.set()

        th = threading.Thread(target=build, name="segment-compact-t")
        th.start()
        sem.add(500, _unit(rng), 0.4)
        sem.remove(5)
        assert done.wait(15)
        th.join(5)
        applied = owner.apply(box["b"])
        assert applied is not None
        epoch, bufs, pos, _m = applied
        man.offer(epoch, bufs, pos)
        out = man.sync(sem)
    finally:
        tracker.disarm()
    races = tracker.unwaived_reports()
    assert not races, "\n".join(r.render() for r in races)
    ent = {s for s, _f, _t in sem.entries()}
    assert 500 in ent and 5 not in ent
    for k, v in sem.device_snapshot().items():
        assert np.array_equal(np.asarray(out[k]), v), k


# -- composition -------------------------------------------------------------

def test_session_route_step_composes_with_semantic_tables():
    """The session-fused serving program accepts the semantic stage:
    its unioned slots match the plain program's."""
    from emqx_tpu.models.router_model import (
        SubscriberTable,
        session_route_step,
        shape_route_step,
    )
    from emqx_tpu.ops import tokenizer as tok
    from emqx_tpu.ops.route_index import RouteIndex
    from emqx_tpu.ops.session_table import ROW_LANES, SessionTable

    rng = np.random.default_rng(8)
    idx = RouteIndex()
    subs = SubscriberTable()
    for i in range(8):
        fid = idx.add(f"s/{i}/+")
        subs.add(fid, i)
    bits = subs.pack(idx.num_filters_capacity)
    sem = SemanticTable(dim=DIM, topk=4)
    for i in range(6):
        sem.add(64 + i, _unit(rng), 0.2)
    st_sem = {k: v.copy() for k, v in sem.device_snapshot().items()}
    topics = [f"s/{i % 8}/x" for i in range(8)]
    mat, lens, _ = tok.encode_topics(topics, 64)
    qv = _unit(rng, 8)
    kw = dict(
        m_active=idx.shapes.m_active(),
        with_nfa=idx.residual_count > 0,
        salt=idx.salt,
        kslot=8,
        sem_topk=4,
    )
    st = idx.shapes.device_snapshot()
    nt = idx.nfa.device_snapshot() if idx.residual_count else None
    plain = shape_route_step(
        st, nt, bits, mat, np.asarray(lens),
        None, None, None, None, st_sem, qv, None, None, **kw,
    )
    sess = SessionTable(capacity=256, slots=64)
    tables = {k: v.copy() for k, v in sess.device_snapshot().items()}
    idxs = {k: np.zeros(16, np.int32) for k in ROW_LANES}
    vals = {k: np.zeros(16, np.int32) for k in ROW_LANES}
    fused = session_route_step(
        st, nt, bits, mat, np.asarray(lens),
        tables, idxs, vals, np.asarray([1, 10], np.int32),
        None, None, None, None, st_sem, qv, None, None,
        sweep_k=0, **kw,
    )
    assert np.array_equal(
        np.asarray(plain["slots"]), np.asarray(fused["slots"])
    )
    assert np.array_equal(
        np.asarray(plain["sem_count"]), np.asarray(fused["sem_count"])
    )
    assert fused["session"] is not None


# -- intake / lifecycle ------------------------------------------------------

def test_embedding_wire_formats():
    import base64

    v = np.arange(4, dtype=np.float32)
    want = v / np.linalg.norm(v)
    assert np.allclose(decode_embedding(v.tolist(), 4), want)
    assert np.allclose(
        decode_embedding(json.dumps(v.tolist()), 4), want
    )
    b64 = base64.b64encode(v.tobytes()).decode()
    assert np.allclose(decode_embedding(b64, 4), want)
    with pytest.raises(ValueError):
        decode_embedding(b64, 8)  # dim mismatch
    with pytest.raises(Exception):
        decode_embedding("!!notbase64!!", 4)


def test_subscribe_lifecycle_moves_slot_between_tables():
    rng = np.random.default_rng(1)
    b = _mk_broker()
    got = {}
    opts = pkt.SubOpts(qos=0)
    b.subscribe("c", "c", "a/b", opts, _recorder(got, "c"))
    assert b.subtab.live == 1 and len(b.semantic.table) == 0
    # upgrade to semantic: slot migrates out of the fan-out table
    b.subscribe("c", "c", "a/b", opts, _recorder(got, "c"),
                embedding=_unit(rng), sem_threshold=0.9)
    assert b.subtab.live == 0 and len(b.semantic.table) == 1
    assert b.metrics.gauge("semantic.filters") == 1
    # downgrade back to plain
    b.subscribe("c", "c", "a/b", opts, _recorder(got, "c"))
    assert b.subtab.live == 1 and len(b.semantic.table) == 0
    # semantic again, then unsubscribe cleans the entry
    b.subscribe("c", "c", "a/b", opts, _recorder(got, "c"),
                embedding=_unit(rng))
    assert b.unsubscribe("c", "a/b")
    assert len(b.semantic.table) == 0
    assert b.metrics.gauge("semantic.filters") == 0


def test_shared_filters_reject_embeddings():
    b = _mk_broker()
    b.subscribe("c", "c", "$share/g/t/#", pkt.SubOpts(qos=0),
                lambda m, o: None, embedding=np.ones(DIM, np.float32))
    assert len(b.semantic.table) == 0
    assert b.metrics.get("semantic.subscribe.rejected") == 1


def test_config_validation():
    from emqx_tpu.config.schema import ConfigError, load_config

    load_config({"semantic": {"enable": True, "dim": 32, "topk": 4}})
    with pytest.raises(ConfigError):
        load_config({"semantic": {"dim": 0}})
    with pytest.raises(ConfigError):
        load_config({"semantic": {"topk": 0}})
    with pytest.raises(ConfigError):
        load_config({"semantic": {"threshold": 2.0}})
    with pytest.raises(ConfigError):
        load_config({"semantic": {"dtype": "fp8"}})
    with pytest.raises(ConfigError):
        load_config({
            "semantic": {"enable": True},
            "router": {"fanout_compact": False},
        })


def test_bfloat16_table_quantizes_at_upload():
    import ml_dtypes

    rng = np.random.default_rng(12)
    sem = SemanticTable(dim=DIM, topk=4, dtype="bfloat16")
    sem.add(1, _unit(rng), 0.3)
    snap = sem.device_snapshot()
    assert snap["sem_vec"].dtype == ml_dtypes.bfloat16
    assert snap["sem_thresh"].dtype == np.float32
    # the kernel accepts the quantized table (accumulates f32)
    sl, cnt = semantic_match_step(
        {k: np.asarray(v) for k, v in snap.items()},
        _unit(rng, 2), np.full((2, 4), -1, np.int32), 4,
    )
    assert np.asarray(sl).shape == (2, 4)


# -- REST / hotpath ----------------------------------------------------------

class _Req:
    def __init__(self, body=None, query=None):
        self._body = body
        self.query = query or {}

    async def json(self):
        if self._body is None:
            raise ValueError("no body")
        return self._body


def test_rest_attach_list_detach():
    from emqx_tpu.mgmt.api import MgmtApi

    rng = np.random.default_rng(14)
    b = _mk_broker()
    got = {}
    b.subscribe("c1", "c1", "a/#", pkt.SubOpts(qos=0),
                _recorder(got, "c1"))
    stub = types.SimpleNamespace(broker=b)
    vec = _unit(rng).tolist()
    resp = asyncio.run(MgmtApi.semantic_attach(stub, _Req({
        "clientid": "c1", "topic_filter": "a/#",
        "embedding": vec, "threshold": 0.6,
    })))
    assert resp.status == 201
    assert len(b.semantic.table) == 1 and b.subtab.live == 0
    resp = asyncio.run(MgmtApi.semantic_list(stub, _Req()))
    doc = json.loads(resp.body.decode())
    assert doc["status"]["filters"] == 1
    assert doc["data"][0]["clientid"] == "c1"
    assert doc["data"][0]["threshold"] == pytest.approx(0.6)
    # unknown subscription 404s
    resp = asyncio.run(MgmtApi.semantic_attach(stub, _Req({
        "clientid": "nope", "topic_filter": "a/#", "embedding": vec,
    })))
    assert resp.status == 404
    resp = asyncio.run(MgmtApi.semantic_detach(
        stub, _Req(query={"clientid": "c1"})
    ))
    assert json.loads(resp.body.decode())["detached"] == 1
    assert len(b.semantic.table) == 0 and b.subtab.live == 1


def test_hotpath_rest_grows_semantic_and_rules_blocks():
    from emqx_tpu.mgmt.api import MgmtApi

    rng = np.random.default_rng(15)
    b = _mk_broker()
    b.subscribe("c1", "c1", "a/#", pkt.SubOpts(qos=0),
                lambda m, o: None, embedding=_unit(rng),
                sem_threshold=0.2)
    b.dispatch_batch_folded([
        _msg("a/x", _unit(rng)) for _ in range(4)
    ])

    class _Alarms:
        def is_active(self, name):
            return False

    stub = types.SimpleNamespace(
        broker=b, app=types.SimpleNamespace(alarms=_Alarms())
    )
    resp = asyncio.run(MgmtApi.metrics_hotpath(stub, None))
    doc = json.loads(resp.body.decode())
    assert doc["semantic"]["filters"] == 1
    assert doc["semantic"]["dim"] == DIM
    assert "hits" in doc["semantic"]
    assert set(doc["rules"]) >= {
        "matched", "passed", "failed", "dropped", "device_batches",
    }
