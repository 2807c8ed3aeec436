"""BASELINE config 2's table at a small size: the residual NFA engine inside
the served route step (models/router_model.py `shape_route_step_impl`,
`with_nfa`; docs/serving_pipeline.md "The residual engine").

Seeded random tables in the configuration's shape (8-level filters, a tenth
of them with one to three `+` over 65-92 wildcard shapes, a few hundred
subscribers) are served by `Broker.dispatch_batch_folded` and by
`BatchIngest`, over the dense matrix and over the CSR table, and have to
deliver exactly what the dictionary trie below delivers. Counts and sets,
never a time."""

import asyncio
import itertools
import random

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.ops.matcher import MatcherConfig
from emqx_tpu.ops.shape_index import MAX_SHAPES

LEVELS = 8
N_SUB = 200
EXACT_PER_SUB = 9
CAUSES = ("too_deep", "frontier_overflow", "match_overflow")


class Trie:
    """The plain reference: a dictionary trie (`+` one level, `#` the rest,
    `$`-topics hidden from a wildcard at the root). Shares nothing with the
    program."""

    def __init__(self):
        self.root = {}

    def insert(self, flt, owner):
        node = self.root
        for level in flt.split("/"):
            node = node.setdefault(level, {})
        node.setdefault(None, []).append(owner)

    def remove(self, flt, owner):
        node = self.root
        for level in flt.split("/"):
            node = node[level]
        node[None].remove(owner)

    def match(self, topic):
        """-> (filter, owner) of every subscription matching `topic`."""
        out, frontier = [], [(self.root, ())]
        levels = topic.split("/")
        for depth, level in enumerate(levels):
            nxt = []
            hide = depth == 0 and topic.startswith("$")
            for node, path in frontier:
                if "#" in node and not hide:
                    out += [("/".join(path + ("#",)), o)
                            for o in node["#"].get(None, ())]
                if level in node:
                    nxt.append((node[level], path + (level,)))
                if "+" in node and not hide:
                    nxt.append((node["+"], path + ("+",)))
            frontier = nxt
        for node, path in frontier:
            out += [("/".join(path), o) for o in node.get(None, ())]
            if "#" in node:
                out += [("/".join(path + ("#",)), o)
                        for o in node["#"].get(None, ())]
        return out


def topic_of(d, j):
    return f"plant/a{d % 7}/l{d % 13}/d{d}/tele/g{j % 3}/c{j}/val"


def overlap(f, g):
    a, b = f.split("/"), g.split("/")
    return len(a) == len(b) and all(
        x == y or "+" in (x, y) for x, y in zip(a, b))


def make_table(seed, n_shapes=None):
    """-> [(subscriber, filter)]: every subscriber's exact filters on its own
    device, and a tenth as many filters with one to three `+`, over
    `n_shapes` wildcard shapes (65-92 by the seed), no subscriber holding two
    filters that can match one topic."""
    rng = random.Random(seed)
    shapes = [p for n in (1, 2, 3)
              for p in itertools.combinations(range(LEVELS), n)]
    rng.shuffle(shapes)
    shapes = shapes[:n_shapes or rng.randint(MAX_SHAPES + 1, len(shapes))]
    held = {s: [topic_of(s, j) for j in range(EXACT_PER_SUB)]
            for s in range(N_SUB)}
    n_wild = max(len(shapes), N_SUB * EXACT_PER_SUB // 9)
    for n in range(n_wild):
        shape = shapes[n % len(shapes)]
        levels = topic_of(rng.randrange(N_SUB),
                          rng.randrange(EXACT_PER_SUB)).split("/")
        flt = "/".join("+" if i in shape else w for i, w in enumerate(levels))
        for s in rng.sample(range(N_SUB), N_SUB):
            if not any(overlap(flt, g) for g in held[s]):
                held[s].append(flt)
                break
        else:  # a fleet-wide filter: a client of its own holds it
            held[N_SUB + n] = [flt]
    return [(s, f) for s in sorted(held) for f in held[s]], len(shapes)


class Bed:
    """A broker holding a table, each subscriber a list of (topic, payload),
    and the reference beside it."""

    def __init__(self, table, sub_table, order="as_drawn", mesh=False):
        self.broker = Broker(
            router=Router(MatcherConfig(sub_table=sub_table), min_tpu_batch=1),
            hooks=Hooks())
        if mesh:  # the 8 virtual CPU devices of conftest.py
            from emqx_tpu.parallel.mesh import make_mesh

            self.broker.mesh = self.broker.router.mesh = make_mesh(8)
        self.trie = Trie()
        self.got = {}
        table = list(table)
        if order == "wild_first":  # which shapes end up residual differs
            table.sort(key=lambda sf: "+" not in sf[1])
        for s, flt in table:
            self.subscribe(s, flt)

    def subscribe(self, s, flt):
        sink = self.got.setdefault(s, [])
        self.broker.subscribe(
            f"s{s}", f"s{s}", flt, pkt.SubOpts(qos=1),
            lambda m, o, _s=sink: _s.append((m.topic, bytes(m.payload))))
        self.trie.insert(flt, s)

    def unsubscribe(self, s, flt):
        self.broker.unsubscribe(f"s{s}", flt)
        self.trie.remove(flt, s)

    @property
    def index(self):
        return self.broker.router.index

    def metric(self, name):
        return self.broker.metrics.get(name)

    def expected(self, msgs):
        """-> ({subscriber: [(topic, payload)]}, matches through filters that
        are residual now)"""
        want, residual = {}, 0
        for m in msgs:
            hits = self.trie.match(m.topic)
            for _, s in hits:
                want.setdefault(s, []).append((m.topic, bytes(m.payload)))
            residual += len({f for f, _ in hits} & self.index._residual)
        return want, residual

    def serve(self, msgs, entry="folded"):
        """One batch through the served path; -> the deliveries it made."""
        for sink in self.got.values():
            sink.clear()
        if entry == "folded":
            self.broker.dispatch_batch_folded(list(msgs))
        else:
            asyncio.run(self._ingest(msgs))
        return {s: sorted(v) for s, v in self.got.items() if v}

    async def _ingest(self, msgs):
        ing = BatchIngest(self.broker, max_batch=len(msgs), window_us=20000)
        self.broker.ingest = ing
        ing.start()
        try:
            await asyncio.wait_for(
                asyncio.gather(*(ing.enqueue(m) for m in msgs)), 120)
        finally:
            await ing.stop()


def messages(seed, n=192, extra=()):
    rng = random.Random(seed + 7)
    topics = [topic_of(rng.randrange(N_SUB), rng.randrange(EXACT_PER_SUB + 2))
              for _ in range(n)] + list(extra)
    return [Message(topic=t, payload=b"%d" % i, qos=1, from_client="pub")
            for i, t in enumerate(topics)]


def check_served(bed, msgs, entry="folded", flagged=0):
    """The batch's deliveries equal the reference's, and the counters say
    who served it."""
    m0 = {k: bed.metric(k) for k in (
        "route.nfa.matches", "messages.routed.device_fallback",
        "messages.routed.device")}
    want, residual = bed.expected(msgs)
    got = bed.serve(msgs, entry)
    assert got == {s: sorted(v) for s, v in want.items()}
    assert bed.metric("messages.routed.device_fallback") \
        - m0["messages.routed.device_fallback"] == flagged
    assert bed.metric("messages.routed.device") \
        - m0["messages.routed.device"] == len(msgs) - flagged
    return bed.metric("route.nfa.matches") - m0["route.nfa.matches"], residual


@pytest.mark.parametrize("entry", ["folded", "ingest"])
@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
@pytest.mark.parametrize("order", ["as_drawn", "wild_first"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_served_path_delivers_what_the_reference_does(
        seed, order, sub_table, entry):
    table, n_shapes = make_table(seed)
    assert MAX_SHAPES < n_shapes <= 128
    wild = [f for _, f in table if "+" in f]
    assert abs(len(wild) / len(table) - 0.1) < 0.01
    assert all(len(f.split("/")) == LEVELS for _, f in table)
    bed = Bed(table, sub_table, order)
    assert bed.broker.subtab.sparse == (sub_table == "sparse")
    assert bed.index.residual_count > 0
    msgs = messages(seed)
    nfa, residual = check_served(bed, msgs, entry)
    # every match a residual filter gave came from the NFA engine's columns
    assert nfa == residual > 0
    assert bed.metric("route.nfa.flagged") == 0
    dev = bed.broker._device_router()
    assert dev.prepare()[5] is True  # with_nfa
    gauge = bed.broker.metrics.gauge
    assert gauge("route.shapes.active") == bed.index.shapes.m_active() \
        == MAX_SHAPES
    assert gauge("route.residual.filters") == bed.index.residual_count


def test_the_subscribe_order_moves_the_residual_set_not_the_deliveries():
    table, _ = make_table(11)
    beds = [Bed(table, "dense", order) for order in ("as_drawn", "wild_first")]
    assert beds[0].index._residual != beds[1].index._residual
    msgs = messages(11)
    assert beds[0].serve(msgs) == beds[1].serve(msgs)


def frontier_filters():
    """64 filters of 8 levels with four to seven `+` among the first seven:
    all match `deep_topic(7)`, and 64 trie nodes are live at its last
    level."""
    words = "f0/f1/f2/f3/f4/f5/f6/end".split("/")
    return ["/".join("+" if i in p else w for i, w in enumerate(words))
            for n in (4, 5, 6, 7) for p in itertools.combinations(range(7), n)]


def hash_filters():
    """72 filters `<three levels, literal or +>/m3/.../#` of 4 to 12 levels:
    all match `deep_topic(12)` with 8 trie nodes live at a time."""
    words = [f"m{i}" for i in range(12)]
    return ["/".join(["+" if i in p else words[i] for i in range(3)]
                     + words[3:k] + ["#"])
            for k in range(3, 12)
            for n in range(4) for p in itertools.combinations(range(3), n)]


FLAGGED = {
    "too_deep": ([], "/".join(f"w{i}" for i in range(17))),
    "frontier_overflow": (frontier_filters(), "f0/f1/f2/f3/f4/f5/f6/end"),
    "match_overflow": (hash_filters(), "/".join(f"m{i}" for i in range(12))),
}


@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
@pytest.mark.parametrize("cause", CAUSES)
def test_a_flagged_row_is_counted_by_cause_and_served_by_the_cpu_trie(
        cause, sub_table):
    table, _ = make_table(21)
    bed = Bed(table, sub_table)
    filters, topic = FLAGGED[cause]
    for n, flt in enumerate(filters):  # the shape index is full: all residual
        bed.subscribe(1000 + n % 5, flt)
    bed.subscribe(2000, "#")  # so that the 17-level topic is owed a delivery
    assert set(filters) <= bed.index._residual
    msgs = messages(21, n=100, extra=[topic])
    want, _ = bed.expected(msgs)
    assert sum(1 for v in want.values() for t, _ in v if t == topic) \
        == len(filters) + 1
    check_served(bed, msgs, flagged=1)
    assert bed.metric("route.nfa.flagged") == 1
    assert {c: bed.metric(f"route.nfa.flagged.{c}") for c in CAUSES} \
        == {c: int(c == cause) for c in CAUSES}
    # the batch after it is the device's again, whole
    check_served(bed, messages(22, n=100))
    assert bed.metric("route.nfa.flagged") == 1


def test_the_mesh_engine_counts_the_same_series():
    table, _ = make_table(21)
    bed = Bed(table, "dense", mesh=True)
    msgs = messages(21, n=100, extra=[FLAGGED["too_deep"][1]])
    nfa, residual = check_served(bed, msgs, flagged=1)
    assert bed.broker._device_router().mesh is not None
    assert nfa == residual > 0
    assert bed.metric("route.nfa.flagged") \
        == bed.metric("route.nfa.flagged.too_deep") == 1
    assert bed.broker.metrics.gauge("route.residual.filters") \
        == bed.index.residual_count


@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
def test_a_residual_filter_leaves_and_another_arrives_between_two_batches(
        sub_table):
    table, _ = make_table(31)
    bed = Bed(table, sub_table)
    old = sorted(bed.index._residual)[0]
    holder = next(s for s, f in table if f == old)
    d, j = 5, 3
    levels = topic_of(d, j).split("/")
    new = "/".join("+" if i in (0, 2, 4, 6) else w
                   for i, w in enumerate(levels))  # four `+`: a new shape
    hit_old = "/".join("x" if w == "+" else w for w in old.split("/"))
    msgs = messages(31, n=100, extra=[hit_old, topic_of(d, j)])
    check_served(bed, msgs)
    assert (hit_old, b"100") in bed.got[holder]
    bed.unsubscribe(holder, old)
    bed.subscribe(3000, new)
    assert new in bed.index._residual and old not in bed.index._residual
    nfa, residual = check_served(bed, msgs)
    assert nfa == residual
    assert (hit_old, b"100") not in bed.got[holder]
    assert bed.got[3000] == [(topic_of(d, j), b"101")]
    assert bed.broker.metrics.gauge("route.residual.filters") \
        == bed.index.residual_count


@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
def test_dollar_topics_stay_hidden_from_a_leading_plus(sub_table):
    table, _ = make_table(41)
    bed = Bed(table, sub_table)
    bed.subscribe(4000, "+/a1/l1/+/tele/+/+/val")
    bed.subscribe(4001, "$SYS/+/l1/+/tele/+/+/val")
    assert {"+/a1/l1/+/tele/+/+/val",  # four `+` each: no shape has them
            "$SYS/+/l1/+/tele/+/+/val"} <= bed.index._residual
    msgs = messages(41, n=100, extra=[
        "$SYS/a1/l1/d1/tele/g1/c1/val", "plant/a1/l1/d1/tele/g1/c1/val"])
    check_served(bed, msgs)
    assert ("plant/a1/l1/d1/tele/g1/c1/val", b"101") in bed.got[4000]
    assert not any(t.startswith("$") for t, _ in bed.got[4000])
    assert bed.got[4001] == [("$SYS/a1/l1/d1/tele/g1/c1/val", b"100")]


@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
def test_exactly_64_shapes_need_no_residual_engine(sub_table):
    table, n_shapes = make_table(51, n_shapes=MAX_SHAPES - 1)  # + the exact one
    bed = Bed(table, sub_table)
    assert n_shapes + 1 == bed.index.shapes.num_active_shapes() == MAX_SHAPES
    assert bed.index.residual_count == 0
    nfa, residual = check_served(bed, messages(51))
    assert nfa == residual == 0
    assert bed.broker._device_router().prepare()[5] is False  # with_nfa
    assert bed.metric("route.nfa.matches") == 0
    assert bed.metric("route.nfa.flagged") == 0
    assert bed.broker.metrics.gauge("route.shapes.active") == MAX_SHAPES
    assert bed.broker.metrics.gauge("route.residual.filters") == 0
