"""The residual NFA walk's widths (ops/nfa.py `walk_bounds` / `walk_widths`;
docs/serving_pipeline.md "The residual engine").

The walk carries a frontier of F lanes a row and appends to K match
columns; both are sized from the residual trie's exact bounds (rounded up
to a multiple of 8) under the budgets `MatcherConfig.frontier` /
`max_matches`, which stay the ceilings. The bounds have to be the most any
topic needs (brute force over every topic drawn from the trie's words and
one word it does not know), the walk at the sized widths has to answer as
the walk at the ceilings does, and the served path has to keep its
programs while filters only leave. Counts and sets, never a time."""

import itertools
import random

import jax
import numpy as np
import pytest

from emqx_tpu.models import router_model as rm
from emqx_tpu.ops import nfa as nfa_mod
from emqx_tpu.ops.matcher import MatcherConfig, batch_match_syms
from emqx_tpu.ops.nfa import DeviceDeltaSync, NfaBuilder, walk_bounds, \
    walk_widths
from emqx_tpu.ops.route_index import RouteIndex
from tests.test_plus_table import Bed, Trie, check_served, frontier_filters, \
    hash_filters, make_table, messages, topic_of

CEIL = MatcherConfig()
UNKNOWN = "zz"  # a word no filter has


def random_filters(seed):
    """1-12 filters of 1-4 levels over a, b, `$s`, `+`, a last `#`."""
    rng = random.Random(seed)
    out = set()
    for _ in range(rng.randint(1, 12)):
        n = rng.randint(1, 4)
        ws = [rng.choice(["a", "b", "$s", "+"]) for _ in range(n)]
        if rng.random() < 0.3:
            ws[-1] = "#"
        out.add("/".join(ws))
    return sorted(out)


def builder_of(filters):
    b = NfaBuilder()
    for f in filters:
        b.add(f)
    return b


def reference(filters, topic):
    """-> (live states after the widest level, filters matched) of the
    walk of `topic` over the plain dictionary trie"""
    trie = Trie()
    for f in filters:
        trie.insert(f, None)
    return trie.widest(topic, levels=64), len(trie.match(topic))


def every_topic(filters):
    """Every topic of up to one level more than the deepest filter, over
    the filters' words and one they do not have."""
    words = sorted({w for f in filters for w in f.split("/")} - {"+", "#"})
    words.append(UNKNOWN)
    deepest = max(len(f.split("/")) for f in filters)
    for n in range(1, deepest + 2):
        for ws in itertools.product(words, repeat=n):
            yield "/".join(ws)


@pytest.mark.parametrize("seed", range(16))
def test_the_bounds_are_the_most_any_topic_of_a_small_trie_needs(seed):
    filters = random_filters(seed)
    most = [0, 0]
    for topic in every_topic(filters):
        for i, v in enumerate(reference(filters, topic)):
            most[i] = max(most[i], v)
    assert walk_bounds(builder_of(filters)) == tuple(most), filters


def test_dollar_words_take_no_wildcard_at_the_root():
    # `+/x` and `#` never meet `$s/x`: one live state, one match
    assert walk_bounds(builder_of(["+/x", "#", "$s/x"])) == (1, 2)
    assert reference(["+/x", "#", "$s/x"], "$s/x") == (1, 1)
    assert reference(["+/x", "#", "$s/x"], "a/x") == (1, 2)


def residual_builder(seed, extra=()):
    """The residual trie of a `make_table` table (65-92 shapes, 8 levels),
    as `RouteIndex` hands it to the NFA engine."""
    table, _ = make_table(seed)
    idx = RouteIndex()
    for _, f in table:
        idx.add(f)
    for f in extra:
        idx.add(f)
    return idx


def sampled_topics(idx, seed, n=600):
    """Topics whose every level is one of that level's words among the
    residual filters, or a word none has; and the served traffic's."""
    rng = random.Random(seed)
    by_level = {}
    for f in idx._residual:
        for i, w in enumerate(f.split("/")):
            if w not in ("+", "#"):
                by_level.setdefault(i, set()).add(w)
    choices = [sorted(by_level.get(i, ())) + [UNKNOWN] for i in range(9)]
    out = ["/".join(rng.choice(choices[i]) for i in range(rng.randint(1, 9)))
           for _ in range(n)]
    return out + [m.topic for m in messages(seed, n=64)] \
        + [topic_of(d, j) for d in range(8) for j in range(3)]


@pytest.mark.parametrize("seed", [11, 13, 31, 41])
def test_the_bounds_cover_every_sampled_topic_of_the_plus_tables(seed):
    idx = residual_builder(seed)
    frontier, matches = walk_bounds(idx.nfa)
    filters = sorted(idx._residual)
    got = [reference(filters, t) for t in sampled_topics(idx, seed)]
    assert frontier >= max(w for w, _ in got) > 0
    assert matches >= max(m for _, m in got) > 0
    # at most one live state per prefix of a residual shape, one match
    # per shape
    shapes = {tuple(w == "+" for w in f.split("/")) for f in filters}
    assert matches <= len(shapes)
    assert walk_widths(idx.nfa, CEIL.frontier, CEIL.max_matches) == (8, 8)


def run_walk(builder, topics, frontier, max_matches):
    sync = DeviceDeltaSync()
    tables = sync.sync(builder)
    tok = [builder.tokenize_host(t, CEIL.max_levels) for t in topics]
    out = batch_match_syms(
        tables,
        jax.numpy.asarray(np.stack([s for s, _, _, _ in tok])),
        jax.numpy.asarray(np.array([n for _, n, _, _ in tok], np.int32)),
        jax.numpy.asarray(np.array([d for _, _, d, _ in tok])),
        frontier=frontier, max_matches=max_matches, probes=CEIL.probes)
    matched, mcount, flags, causes, walk = jax.device_get(out)
    return ([sorted(r[r >= 0].tolist()) for r in matched], mcount.tolist(),
            flags.tolist(), {k: v.tolist() for k, v in causes.items()},
            {k: int(v) for k, v in walk.items()})


@pytest.mark.parametrize("case", ["small", "plus", "frontier", "hash"])
def test_the_walk_at_the_sized_widths_answers_as_at_the_ceilings(case):
    if case == "small":
        filters = sorted({f for s in range(8) for f in random_filters(s)})
        builder = builder_of(filters)
        topics = list(itertools.islice(every_topic(filters), 0, None, 7))
    else:
        extra = {"plus": (), "frontier": frontier_filters(),
                 "hash": hash_filters()}[case]
        idx = residual_builder(13, extra)
        builder = idx.nfa
        topics = sampled_topics(idx, 13, n=200) + [
            "f0/f1/f2/f3/f4/f5/f6/end", "/".join(f"m{i}" for i in range(12)),
            "/".join(f"w{i}" for i in range(17)), "$SYS/a1/l1/d1"]
    widths = walk_widths(builder, CEIL.frontier, CEIL.max_matches)
    if case == "frontier":  # 64 live states: the frontier keeps its ceiling
        assert widths == (CEIL.frontier, CEIL.max_matches)
    elif case == "hash":  # 72 matches for one topic: so do the columns
        assert widths[1] == CEIL.max_matches
    else:
        assert widths[0] < CEIL.frontier and widths[1] < CEIL.max_matches
    sized = run_walk(builder, topics, *widths)
    ceil = run_walk(builder, topics, CEIL.frontier, CEIL.max_matches)
    assert sized == ceil
    assert any(ceil[2]) == (case in ("plus", "frontier", "hash"))


def test_past_its_work_budget_the_walk_keeps_the_ceilings(monkeypatch):
    filters = random_filters(3)
    assert walk_bounds(builder_of(filters), max_work=0) is None
    monkeypatch.setattr(nfa_mod, "WALK_BOUND_WORK", 0)
    assert walk_widths(builder_of(filters), CEIL.frontier,
                       CEIL.max_matches) == (CEIL.frontier, CEIL.max_matches)
    table, _ = make_table(11)
    bed = Bed(table, "dense")
    check_served(bed, messages(11))
    assert bed.broker.metrics.gauge("route.nfa.frontier_width") \
        == CEIL.frontier
    assert bed.broker.metrics.gauge("route.nfa.match_width") \
        == CEIL.max_matches


def served_programs():
    return sum(fn._cache_size() for fn in (
        rm.shape_route_step, rm.shape_route_step_donated))


def widths_of(bed):
    g = bed.broker.metrics.gauge
    return g("route.nfa.frontier_width"), g("route.nfa.match_width")


@pytest.mark.parametrize("sub_table", ["dense", "sparse"])
def test_the_served_path_walks_at_the_table_s_widths(sub_table):
    table, _ = make_table(31)
    bed = Bed(table, sub_table)
    msgs = messages(31)
    nfa, residual = check_served(bed, msgs)
    assert nfa == residual > 0
    assert widths_of(bed) == walk_widths(
        bed.index.nfa, CEIL.frontier, CEIL.max_matches) == (8, 8)
    assert bed.broker._device_router().prepare()[-1] == (8, 8)
    # a filter that widens the bound widens the walk
    for n, flt in enumerate(hash_filters()[:24]):
        bed.subscribe(5000 + n % 5, flt)
    check_served(bed, messages(32, extra=["/".join(
        f"m{i}" for i in range(12))]))
    assert widths_of(bed)[1] > 8
    assert bed.metric("route.nfa.flagged") == 0


def test_a_table_that_only_loses_filters_compiles_nothing_new():
    table, _ = make_table(13)
    bed = Bed(table, "dense")
    msgs = messages(13)
    check_served(bed, msgs)
    held = {f: s for s, f in table}
    for n, flt in enumerate(hash_filters()[:24]):  # widen the walk first
        bed.subscribe(5000 + n % 5, flt)
        held[flt] = 5000 + n % 5
    check_served(bed, msgs)
    widths, programs = widths_of(bed), served_programs()
    assert widths[1] > 8
    residual = sorted(bed.index._residual)
    for flt in residual[::2]:
        bed.unsubscribe(held[flt], flt)
    assert 0 < bed.index.residual_count < len(residual)
    check_served(bed, msgs)
    assert widths_of(bed) == widths
    assert served_programs() == programs


def test_the_mesh_engine_walks_at_the_one_chip_widths():
    table, _ = make_table(21)
    one, mesh = Bed(table, "dense"), Bed(table, "dense", mesh=True)
    msgs = messages(21, n=100)
    assert check_served(one, msgs) == check_served(mesh, msgs)
    assert mesh.broker._device_router().mesh is not None
    assert widths_of(mesh) == widths_of(one) == (8, 8)
    assert one.serve(msgs) == mesh.serve(msgs)
