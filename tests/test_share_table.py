"""A table of `$share` groups at a deployment's scale (ISSUE 34).

- `SharedSub.count()` is a running count: equal to a walk of the table after
  every step of a seeded sequence of shared and plain subscribe / re-subscribe
  / unsubscribe / disconnect / takeover, and it touches no group;
- the benchmark's table shape (device-range groups and fleet-wide groups, the
  group table's `gpf` of 4 exactly full) through `Broker` on its device path
  and on its host path gives, per receiver class, the plain reference's answer
  (`broker/trie.py` over the real filters, every group on a matching filter
  owed the message once): each owed group exactly one delivery, no one else;
- `GroupTable` through twelve doublings and op-log overflows equals a table
  grown to its final size first, and so does a mirror that follows the
  epoch / op-log contract the device sync follows;
- the new series: `shared.picks`, `shared.picks.stale`, the two gauges, the
  two sections' entries, `grouptab.uploads`.
Runs on the CPU backend from conftest."""

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import COUNTER, GAUGE, Metrics, kind_of
from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.models.router_model import GroupTable
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as P

OPTS = pkt.SubOpts(qos=1)


def walk(shared):
    return sum(len(g.members) for groups in shared._table.values()
               for g in groups.values())


def plain_walk(broker):
    return sum(len(entry) for entry in broker._subs.values())


# -- (a), (b): the running count -------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_the_running_count_equals_a_walk_after_every_step(seed):
    rng = np.random.default_rng(seed)
    broker = Broker()
    sids = [f"s{i}" for i in range(12)]
    shared = [f"$share/g{g}/t/{f}/+" for g in range(4) for f in range(5)] \
        + [f"$share/svc{k}/t/#" for k in range(4)]
    plain = [f"t/{f}/#" for f in range(5)]
    held = {s: set() for s in sids}
    deliver = lambda msg, opts: None  # noqa: E731
    for _ in range(1500):
        sid = sids[rng.integers(len(sids))]
        op = rng.choice(["sub", "sub", "sub", "plain", "unsub", "unsub",
                         "absent", "disconnect", "takeover"])
        if op in ("sub", "plain"):  # a re-subscribe where it is held already
            pool = shared if op == "sub" else plain
            flt = pool[rng.integers(len(pool))]
            broker.subscribe(sid, sid, flt, OPTS, deliver)
            held[sid].add(flt)
        elif op == "unsub" and held[sid]:
            flt = sorted(held[sid])[rng.integers(len(held[sid]))]
            assert broker.unsubscribe(sid, flt) is True
            held[sid].discard(flt)
        elif op == "absent":  # a filter the sid does not hold
            flt = shared[rng.integers(len(shared))]
            if flt not in held[sid]:
                assert broker.unsubscribe(sid, flt) is False
        elif op == "disconnect":  # the session dies: bulk clean-up
            broker.drop_session_subs(sid, sorted(held[sid]))
            held[sid].clear()
        elif op == "takeover":  # a new channel re-subscribes what the old held
            for flt in sorted(held[sid]):
                broker.subscribe(sid, sid, flt, OPTS, lambda msg, opts: None)
        want = sum(len(v) for v in held.values())
        assert broker.shared.count() == walk(broker.shared)
        assert broker.subscription_count() == want \
            == walk(broker.shared) + plain_walk(broker)
        assert broker.metrics.gauge("subscriptions.count") == want
    # every group emptied in the end: nothing left behind
    for sid in sids:
        broker.drop_session_subs(sid, sorted(held[sid]))
    assert broker.shared.count() == 0 == walk(broker.shared)
    assert broker.shared._table == {} and len(broker.grouptab) == 0
    assert broker.metrics.gauge("shared.subscriptions.count") == 0
    assert broker.metrics.gauge("grouptab.groups") == 0
    assert broker.metrics.gauge("subscriptions.count") == 0


class CountingTable(dict):
    """`SharedSub._table` with every read counted."""

    touched = 0


def _counted(name):
    def method(self, *a, **kw):
        self.touched += 1
        return getattr(dict, name)(self, *a, **kw)
    return method


for _name in ("__getitem__", "__iter__", "get", "values", "items", "keys",
              "__contains__", "__len__"):
    setattr(CountingTable, _name, _counted(_name))


def test_count_touches_no_group():
    broker = Broker()
    broker.shared._table = CountingTable()
    for m in range(4):
        for f in range(50):
            broker.subscribe(f"s{m}", f"c{m}", f"$share/g/t/{f}/#", OPTS, None)
    table = broker.shared._table
    assert table.touched > 0
    table.touched = 0
    assert broker.shared.count() == 200
    assert broker.subscription_count() == 200
    assert table.touched == 0
    assert walk(broker.shared) == 200 and table.touched > 0


# -- (c): the harness-shaped table against the plain reference -------------------


def shaped_table(rng):
    """-> [(class, sid, wire filter)], {real filter: [class, ...]}, ids, js.
    Group g{g} holds `ids` device ids and the real filters device/{d}/+/{j}/#
    over them; four fleet-wide groups share the one filter device/#."""
    groups, members, ids, js = (int(rng.integers(2, 5)), int(rng.integers(2, 4)),
                                2, 4)
    subs, owed = [], {}
    for g in range(groups):
        for d in range(g * ids, (g + 1) * ids):
            for j in range(js):
                real = f"device/{d}/+/{j}/#"
                owed.setdefault(real, []).append(f"g{g}")
                subs += [(f"g{g}", f"g{g}-{m}", f"$share/g{g}/{real}")
                         for m in range(members)]
    for k in range(4):  # GroupTable's gpf of 4 exactly full on one filter
        owed.setdefault("device/#", []).append(f"svc{k}")
        subs += [(f"svc{k}", f"svc{k}-{m}", f"$share/svc{k}/device/#")
                 for m in range(int(rng.integers(2, 6)))]
    return subs, owed, groups * ids + 2, js


@pytest.mark.parametrize("path", ["device", "host"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_each_owed_group_gets_exactly_one_delivery_and_no_one_else(path, seed):
    rng = np.random.default_rng(seed)
    subs, owed, n_ids, js = shaped_table(rng)
    broker, got = Broker(), []
    assert broker.shared.strategy == "round_robin"
    class_of = {}
    for cls, sid, flt in subs:
        class_of[sid] = cls
        broker.subscribe(sid, sid, flt, OPTS,
                         lambda msg, opts, sid=sid: got.append((sid, int(msg.payload))))
    assert broker.subscription_count() == len(subs) == broker.shared.count()
    assert broker.grouptab.gpf == 4  # exactly full, not grown
    reference = TopicTrie()
    for real in owed:
        reference.insert(real)
    # ids past the table's own are owed to the fleet-wide groups alone
    topics = [f"device/{rng.integers(0, n_ids)}/mid/{rng.integers(0, js)}/leaf"
              for _ in range(600)]
    msgs = [Message(topic=t, payload=b"%d" % i, qos=1) for i, t in enumerate(topics)]
    if path == "device":
        for part in np.array_split(np.arange(len(msgs)), 3):
            broker.publish_batch([msgs[i] for i in part])
        assert broker.metrics.get("messages.routed.device") == len(msgs)
        assert broker.metrics.get("shared.picks.stale") == 0
    else:
        for m in msgs:
            broker.publish(m)
        assert broker.metrics.get("shared.picks") == 0
    want = sorted((cls, i) for i, t in enumerate(topics)
                  for real in reference.match(t) for cls in owed[real])
    assert sorted((class_of[sid], i) for sid, i in got) == want
    assert len(want) >= 4 * len(msgs)
    if path == "device":
        assert broker.metrics.get("shared.picks") == len(want)
    # the members of a (group, real filter) pair share its messages
    per_pair = {}
    for sid, i in got:
        cls = class_of[sid]
        pair = (cls, None if cls.startswith("svc") else topics[i])
        per_pair.setdefault(pair, {}).setdefault(sid, 0)
        per_pair[pair][sid] += 1
    busy = [c for c in per_pair.values() if sum(c.values()) >= 40]
    assert busy and all(len(c) > 1 for c in busy)


# -- (d): the group table through its growth -------------------------------------


class Mirror:
    """What the device holds of a GroupTable, by the contract
    DeviceSegmentManager follows: a whole copy on an epoch change, the op-log's
    tail otherwise."""

    def __init__(self):
        self.arrays, self.epoch, self.pos, self.uploads = None, -1, 0, 0

    def sync(self, src):
        if self.arrays is None or self.epoch != src.epoch:
            self.arrays = {k: v.copy() for k, v in src.device_snapshot().items()}
            self.epoch, self.uploads = src.epoch, self.uploads + 1
        else:
            for name, idx, val in src.oplog[self.pos:]:
                self.arrays[name].reshape(-1)[idx] = val
        self.pos = len(src.oplog)


def fill(table, n_groups, mirror=None, every=0):
    """`n_groups` groups, one to a filter but for the last filter's four, each
    of two or three members; some dropped and their rows taken again."""
    def add(i):
        fid = min(i, n_groups - 4)
        gid = table.ensure_group(fid, f"f{fid}", f"g{i}")
        table.set_len(gid, 2 + i % 2)
        if mirror is not None and every and i % every == 0:
            mirror.sync(table)
    for i in range(n_groups):
        add(i)
    for i in range(0, n_groups - 4, n_groups // 7):
        table.drop_group(i, f"f{i}", f"g{i}")
    for i in range(0, n_groups - 4, n_groups // 7):
        add(i)


def test_a_group_table_grown_by_doubling_equals_one_built_at_its_size():
    n = 131_072 + 5  # past the twelfth doubling of 64 rows: 262,144
    grown, mirror = GroupTable(), Mirror()
    fill(grown, n, mirror, every=9_973)
    mirror.sync(grown)
    assert grown._gcap == grown._fcap == 64 << 12 and grown.gpf == 4
    # 12 doublings of each capacity, and the op-log passed OPLOG_MAX
    assert grown.epoch > 24 and len(grown.oplog) < grown.OPLOG_MAX
    assert 1 < mirror.uploads <= grown.epoch + 1
    built = GroupTable()
    built.pack_fcap(64 << 12)
    while built._gcap < 64 << 12:
        built._grow_gcap()
    fill(built, n)
    for name in ("filter_groups", "group_len", "group_rr", "group_sticky"):
        assert np.array_equal(getattr(grown, name), getattr(built, name)), name
        assert np.array_equal(mirror.arrays[name], getattr(built, name)), name
    assert len(grown) == len(built) == n
    for i in (0, 1, 63, 64, 65_535, 65_536, n - 5, n - 1):
        fid = min(i, n - 4)
        assert grown.gid_of(f"f{fid}", f"g{i}") == built.gid_of(f"f{fid}", f"g{i}")
        assert grown.info(grown.gid_of(f"f{fid}", f"g{i}")) == (f"f{fid}", f"g{i}")
    assert sorted(grown.filter_groups[n - 4].tolist()) == \
        sorted(grown.gid_of(f"f{n - 4}", f"g{i}") for i in range(n - 4, n))


# -- (e): the series -------------------------------------------------------------


def hist(m, name):
    h = m.histogram(name)
    return h.count if h is not None else 0


def test_the_series_are_declared():
    for name, kind in (("shared.picks", COUNTER), ("shared.picks.stale", COUNTER),
                       ("grouptab.uploads", COUNTER),
                       ("shared.subscriptions.count", GAUGE),
                       ("grouptab.groups", GAUGE)):
        assert kind_of(name) == kind, name
    assert {"broker.share_subscribe", "shared.dispatch_picked"} <= set(P.SECTIONS)


def test_picks_are_counted_and_a_dropped_groups_pick_is_stale():
    P.flush(Metrics())  # what earlier tests left in the accumulators
    broker = Broker()
    got = []
    for g in ("ga", "gb"):
        for m in range(2):
            broker.subscribe(f"{g}{m}", f"{g}{m}", f"$share/{g}/pick/+", OPTS,
                             lambda msg, opts: got.append(msg))
    broker.subscribe("p", "p", "pick/#", OPTS, lambda msg, opts: None)
    m = broker.metrics
    assert m.gauge("shared.subscriptions.count") == 4
    assert m.gauge("grouptab.groups") == 2 and m.gauge("subscriptions.count") == 5
    msgs = [Message(topic=f"pick/{i}", payload=b"x") for i in range(10)]
    dev = broker._device_router()
    args = dev.prepare()  # the snapshot holds both groups
    assert m.get("grouptab.uploads") == 1
    results = dev.route_prepared(args, [x.topic for x in msgs], [0] * 10)
    assert broker._dispatch_device_results(msgs, results) == [3] * 10
    assert (m.get("shared.picks"), m.get("shared.picks.stale")) == (20, 0)
    assert len(got) == 20
    # gb leaves while a batch is in flight: its picks deliver nothing
    for i in range(2):
        broker.unsubscribe(f"gb{i}", "$share/gb/pick/+")
    assert m.gauge("shared.subscriptions.count") == 2
    assert m.gauge("grouptab.groups") == 1 and m.gauge("subscriptions.count") == 3
    results = dev.route_prepared(args, [x.topic for x in msgs], [0] * 10)
    assert broker._dispatch_device_results(msgs, results) == [2] * 10
    assert (m.get("shared.picks"), m.get("shared.picks.stale")) == (40, 10)
    # a member whose deliverer raises is failed over, not stale; a group
    # whose every member raises took nothing
    def refuse(msg, opts):
        raise RuntimeError("nack")
    for i in range(2):
        broker.subscribe(f"ga{i}", f"ga{i}", "$share/ga/pick/+", OPTS, refuse)
    args = dev.prepare()
    assert m.get("grouptab.uploads") == 1  # deltas, no epoch bump
    results = dev.route_prepared(args, [x.topic for x in msgs], [0] * 10)
    assert broker._dispatch_device_results(msgs, results) == [1] * 10
    assert (m.get("shared.picks"), m.get("shared.picks.stale")) == (50, 20)
    P.flush(m)
    assert hist(m, "profile.section.shared.dispatch_picked.seconds") == 50
    assert hist(m, "profile.section.broker.share_subscribe.seconds") == 6


# -- the balance over many sparse (group, filter) pairs ---------------------------


@pytest.mark.parametrize("path", ["device", "host"])
def test_a_group_on_many_filters_with_a_message_each_shares_them(path):
    """A group is the pair (group name, real filter), each with a round-robin
    counter of its own. One service on 400 filters that see one message each:
    a counter that started at member 0 everywhere would hand all 400 to the
    first member; upstream's starts at a random member, and so does this."""
    broker, got = Broker(), []
    for m in range(4):
        for f in range(400):
            broker.subscribe(f"w{m}", f"w{m}", f"$share/svc/lean/{f}/#", OPTS,
                             lambda msg, opts, m=m: got.append(m))
    msgs = [Message(topic=f"lean/{f}/x", payload=b"x", qos=1) for f in range(400)]
    if path == "device":
        for part in np.array_split(np.arange(len(msgs)), 4):
            broker.publish_batch([msgs[i] for i in part])
        assert broker.metrics.get("messages.routed.device") == len(msgs)
    else:
        for m in msgs:
            broker.publish(m)
    counts = np.bincount(got, minlength=4)
    assert counts.sum() == 400
    assert counts.max() * 4 / 400 < 1.5, counts
    # and a pair's own messages still go round its members in turn
    for _ in range(8):
        broker.publish(Message(topic="lean/7/x", payload=b"x", qos=1))
    assert (np.bincount(got, minlength=4) - counts).tolist() == [2, 2, 2, 2]
