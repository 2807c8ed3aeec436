"""Differential tests: the device match vs the authoritative CPU trie.

This is the round-1 analog of the reference's emqx_trie_SUITE +
emqx_router_SUITE correctness gates (SURVEY.md §7 stage 2): every behavior of
the device matcher must agree with `TopicTrie.match` (itself tested
brute-force against `topics.match`).

Two subjects. The differential cases drive the product's match-only
path, `Router.matcher` (a lazy `DeviceRouter`: shape index + residual
NFA). The cases that pin the NFA's own limits run the NFA kernel alone,
`batch_match_syms` over a `DeviceDeltaSync` mirror of an `NfaBuilder`,
as the served step runs it for residual filters.
"""

import random

import numpy as np
import pytest

from emqx_tpu.broker.router import Router
from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.ops import topics as T
from emqx_tpu.ops.matcher import MatcherConfig, batch_match_syms
from emqx_tpu.ops.nfa import MAX_PROBES, DeviceDeltaSync, NfaBuilder


class Pair:
    """The reference trie beside the product's route table."""

    def __init__(self, filters=(), cfg=None):
        self.trie = TopicTrie()
        self.router = Router(matcher_config=cfg)
        for f in filters:
            self.add(f)

    def add(self, f):
        self.trie.insert(f)
        self.router.add_route(f)

    def remove(self, f):
        self.trie.delete(f)
        self.router.delete_route(f)

    def check(self, topics_list, ctx=None):
        got = self.router.matcher.match_batch(
            topics_list, fallback=self.trie.match
        )
        for topic, names in zip(topics_list, got):
            assert sorted(names) == sorted(self.trie.match(topic)), (
                ctx, topic,
            )


def make_nfa(filters):
    trie = TopicTrie()
    builder = NfaBuilder()
    for f in filters:
        trie.insert(f)
        builder.add(f)
    return trie, builder


def nfa_check(trie, builder, topics_list, cfg=MatcherConfig(), sync=None):
    """The NFA kernel alone: host tokenize -> `batch_match_syms` over the
    delta-synced mirror; a flagged row takes the trie, as on the served
    path. -> the per-cause flag arrays, so a limit test can show that
    the limit was what sent the row back."""
    tables = (sync or DeviceDeltaSync()).sync(builder)
    rows = [builder.tokenize_host(t, cfg.max_levels) for t in topics_list]
    matched, mcount, flags, causes = batch_match_syms(
        tables,
        np.stack([r[0] for r in rows]),
        np.array([r[1] for r in rows], dtype=np.int32),
        np.array([r[2] for r in rows]),
        frontier=cfg.frontier,
        max_matches=cfg.max_matches,
        probes=max(cfg.probes, MAX_PROBES),
    )
    matched, mcount = np.asarray(matched), np.asarray(mcount)
    flags = np.asarray(flags)
    for i, topic in enumerate(topics_list):
        if flags[i]:
            continue  # the caller's fallback IS the trie
        names = [
            builder.filter_name(int(f)) for f in matched[i, : mcount[i]]
        ]
        assert sorted(n for n in names if n is not None) == sorted(
            trie.match(topic)
        ), topic
    causes = {k: np.asarray(v).tolist() for k, v in causes.items()}
    assert flags.tolist() == [
        any(c[i] for c in causes.values()) for i in range(len(rows))
    ]
    return causes


def test_basic_match():
    filters = ["a/b/c", "a/+/c", "a/#", "#", "+/b/c", "a/b/+", "x/y"]
    Pair(filters).check(
        ["a/b/c", "a/b", "a", "x/y", "x/z", "q", "a/q/c", "a/b/q"],
    )


def test_hash_parent_and_exact():
    Pair(["a/#", "a", "a/b/#"]).check(["a", "a/b", "a/b/c", "b"])


def test_dollar_topics():
    Pair(["#", "+/x", "$SYS/#", "$SYS/+", "$share-ish/x"]).check(
        ["$SYS/x", "$SYS", "n/x", "$share-ish/x", "$other/x", "$SYS/a/b"],
    )


def test_empty_levels_and_oov():
    Pair(["a/+/c", "a//c", "+/+", "//#"]).check(
        ["a//c", "a/zz/c", "/", "//", "a/", "/a", "never/seen"]
    )


def test_plus_only_and_root_hash():
    Pair(["+", "#", "+/+"]).check(["a", "a/b", "a/b/c", "$sys", "$sys/b"])


def test_delete_updates_tables():
    pair = Pair(["a/+", "a/b", "b/#"])
    pair.remove("a/+")
    pair.check(["a/b", "a/x", "b/q"])
    pair.remove("b/#")
    pair.check(["a/b", "a/x", "b/q", "b"])
    # re-add after delete (exercises the index's free lists)
    pair.add("a/+")
    pair.check(["a/b", "a/x"])


def test_too_deep_falls_back():
    cfg = MatcherConfig(max_levels=4)
    trie, builder = make_nfa(["a/#"])
    deep = "a/" + "/".join("x" * 1 for _ in range(10))
    causes = nfa_check(trie, builder, [deep, "a/b"], cfg)
    assert causes["too_deep"] == [True, False]


def test_frontier_overflow_falls_back():
    # many '+' branches at every level blow the frontier cap
    cfg = MatcherConfig(frontier=2)
    filters = []
    for a in ["+", "a", "b"]:
        for b in ["+", "a", "b"]:
            for c in ["+", "a", "b"]:
                filters.append(f"{a}/{b}/{c}")
    trie, builder = make_nfa(filters)
    causes = nfa_check(trie, builder, ["a/b/a", "b/b/b", "a/a/a"], cfg)
    assert all(causes["frontier_overflow"])


def test_match_overflow_falls_back():
    cfg = MatcherConfig(max_matches=2)
    trie, builder = make_nfa(["a/#", "a/+", "a/b", "#", "+/b"])
    causes = nfa_check(trie, builder, ["a/b", "q"], cfg)
    assert causes["match_overflow"] == [True, False]


def test_long_topic_falls_back():
    # the byte budget belongs to the served step's device tokenizer
    pair = Pair(["a/#"], MatcherConfig(max_bytes=32))
    long = "a/" + "y" * 100
    pair.check([long, "a/b"])
    assert pair.router.matcher.route([long, "a/b"]).flags.tolist() == [
        True, False,
    ]


def random_word(rng):
    return rng.choice(["a", "b", "c", "d", "sensor", "dev", "", "long-word-x"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_randomized_differential(seed):
    rng = random.Random(seed)
    filters = set()
    for _ in range(400):
        depth = rng.randint(1, 7)
        ws = []
        for i in range(depth):
            r = rng.random()
            if r < 0.15:
                ws.append("+")
            else:
                ws.append(random_word(rng))
        if rng.random() < 0.2:
            ws.append("#")
        f = "/".join(ws)
        try:
            T.validate(f)
            filters.add(f)
        except T.TopicValidationError:
            pass
    pair = Pair(sorted(filters))
    topics_list = []
    for _ in range(500):
        depth = rng.randint(1, 8)
        ws = [random_word(rng) for _ in range(depth)]
        if rng.random() < 0.1:
            ws[0] = "$" + ws[0]
        topics_list.append("/".join(ws))
    pair.check(topics_list)
    # now delete a random half and re-check
    for f in sorted(filters):
        if rng.random() < 0.5:
            pair.remove(f)
    pair.check(topics_list)


def test_host_tokenize_matches_device_path():
    # exercised indirectly above; here verify sym-level entry point too
    trie, builder = make_nfa(["dev/+/temp", "dev/1/temp"])
    tables = builder.pack().device_arrays()
    L = 8
    rows = [builder.tokenize_host(t, L) for t in ["dev/1/temp", "dev/9/hum"]]
    syms = np.stack([r[0] for r in rows])
    nwords = np.array([r[1] for r in rows], dtype=np.int32)
    dollar = np.array([r[2] for r in rows])
    matched, mcount, flags, causes = batch_match_syms(
        tables, syms, nwords, dollar, frontier=8, max_matches=8, probes=8
    )
    got = sorted(
        builder.filter_name(int(f))
        for f in np.asarray(matched)[0, : int(mcount[0])]
    )
    assert got == ["dev/+/temp", "dev/1/temp"]
    assert int(mcount[1]) == 0
    assert not bool(np.asarray(flags).any())
    for arr in causes.values():
        assert not bool(np.asarray(arr).any())


def test_invalid_add_does_not_corrupt_builder():
    # code-review finding: add('a/#/b') must fail without mutating state
    trie, builder = make_nfa(["a/b"])
    with pytest.raises(T.TopicValidationError):
        builder.add("a/#/b")
    builder.add("a/+")
    trie.insert("a/+")
    nfa_check(trie, builder, ["a/b", "a/x", "a"])
    assert builder.remove("a/+")


def test_literal_plus_in_topic_not_wildcard():
    # code-review finding: a literal '+'/'#' char in a (malformed) topic must
    # not walk the wildcard branch as an exact word
    pair = Pair(["a/+", "a/#"])
    assert sorted(pair.trie.match("a/+")) == ["a/#", "a/+"]  # via wildcards only
    pair.check(["a/+", "a/#", "a/b"])


def test_low_probe_config_is_clamped():
    """`DeviceRouter.__init__` raises a probe bound under the build-time
    one: with probes=1 the residual NFA's lookups would silently miss."""
    from emqx_tpu.models.router_model import DeviceRouter
    from emqx_tpu.ops.route_index import RouteIndex

    index = RouteIndex(max_shapes=1)
    index.add("seed/+/x/y")  # takes the one shape: the rest is residual
    for i in range(200):
        index.add(f"w{i}/x")
    assert index.residual_count == 200
    m = DeviceRouter(index, None, MatcherConfig(probes=1))
    assert m.config.probes == MAX_PROBES
    assert m.match_batch(["w34/x"], fallback=None) == [["w34/x"]]


@pytest.mark.parametrize("seed", [7, 21])
def test_churn_differential_delta_sync(seed):
    """Sustained subscribe/unsubscribe churn against ONE match engine.

    The device mirrors must track the host through delta scatters,
    tombstoned slots, slot reuse, growth, and epoch bumps
    (ops/segments.DeviceSegmentManager) — matching the CPU trie after
    every step.
    """
    rng = random.Random(seed)
    words = [f"w{i}" for i in range(40)] + ["+", "#"]
    pair = Pair(cfg=MatcherConfig(frontier=64, max_matches=64))
    live = []
    topics_pool = [
        "/".join(rng.choice(words[:40]) for _ in range(rng.randint(1, 5)))
        for _ in range(64)
    ]
    for step in range(30):
        # mutate: a few adds and removes per step
        for _ in range(rng.randint(1, 8)):
            f = "/".join(
                rng.choice(words) for _ in range(rng.randint(1, 5))
            )
            try:
                T.validate(f)
            except T.TopicValidationError:
                continue
            pair.add(f)
            live.append(f)
        for _ in range(rng.randint(0, 6)):
            if not live:
                break
            pair.remove(live.pop(rng.randrange(len(live))))
        pair.check(topics_pool, ctx=step)


def test_churn_epoch_growth():
    """Push one NFA mirror through table growth (epoch bump) mid-stream."""
    trie = TopicTrie()
    builder = NfaBuilder()
    sync = DeviceDeltaSync()
    # small tables first
    for i in range(4):
        trie.insert(f"a/{i}/+")
        builder.add(f"a/{i}/+")
    nfa_check(trie, builder, ["a/1/x"], sync=sync)
    epoch = builder.epoch
    # now >1024 filters: forces node-array growth + edge/vocab rehash
    for i in range(1500):
        trie.insert(f"grow/{i}/leaf")
        builder.add(f"grow/{i}/leaf")
    assert builder.epoch > epoch
    topics_list = [f"grow/{i}/leaf" for i in range(0, 1500, 97)] + ["a/2/q"]
    causes = nfa_check(trie, builder, topics_list, sync=sync)
    assert not any(any(c) for c in causes.values())


def test_oplog_cap_forces_epoch_resync():
    """More ops than OPLOG_MAX between syncs => consumer resyncs fully."""
    trie = TopicTrie()
    builder = NfaBuilder()
    builder.OPLOG_MAX = 64  # tiny, to hit the cap fast
    sync = DeviceDeltaSync()
    nfa_check(trie, builder, ["x"], sync=sync)  # prime the mirror
    resyncs = sync.full_resyncs
    for i in range(300):
        trie.insert(f"c/{i}/#")
        builder.add(f"c/{i}/#")
    topics_list = [f"c/{i}/deep/leaf" for i in range(0, 300, 13)]
    causes = nfa_check(trie, builder, topics_list, sync=sync)
    assert not any(any(c) for c in causes.values())
    assert sync.full_resyncs > resyncs


def test_insert_cost_is_delta_not_table():
    """The delta overlay promise: adding one filter after a sync costs a
    bounded number of op-log entries, not an O(table) repack."""
    builder = NfaBuilder()
    for i in range(2000):
        builder.add(f"base/{i}/+/leaf")
    sync = DeviceDeltaSync()
    sync.sync(builder)
    pos = len(builder.oplog)
    epoch = builder.epoch
    builder.add("base/new/+/leaf")
    assert builder.epoch == epoch, "single insert must not force a resync"
    # 4 words -> a handful of node/edge/vocab writes, not thousands
    assert len(builder.oplog) - pos < 32
