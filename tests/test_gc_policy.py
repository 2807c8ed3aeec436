"""The collector policy of the owner process (observe/gc_policy.py).

Counts only, never a time. The collector is process-wide, so every test
leaves it as it found it (the `policy` fixture checks that):

- install / restore: thresholds and the frozen count as found, also with
  two apps in one process, also through `BrokerApp.start` / `stop`;
- growth freezes: after N subscriptions and one tick the generations hold
  a number of objects that does not depend on N;
- frozen objects die by reference count: a `Subscriber` at unsubscribe, a
  closed session's `Connection` / `Channel` / `Session` on every close path,
  with no pass of the collector and no thaw (the teardown breaks the cycles);
- releases thaw: past a quarter of the frozen items (and the floor) exactly
  one thaw pass, which reclaims a frozen cycle; a static table: no freeze.
"""

import asyncio
import gc
import pathlib
import weakref

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import SessionConfig
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import gc_policy
from emqx_tpu.observe.gc_policy import THAW_MIN_RELEASED, THRESHOLDS, GcPolicy
from emqx_tpu.transport.connection import Connection

from test_egress_coalesce import Reader, Writer, async_test


class Passes:
    """Counts the collector's passes (a `gc.callbacks` hook)."""

    def __init__(self):
        self.n = 0

    def __call__(self, phase, info):
        if phase == "start":
            self.n += 1


def _collector():
    """The collector's state after a full pass. (A pass of 3.12 moves the
    immortal objects it meets, a few hundred, into the frozen generation:
    `restore` thaws them and the next full pass puts them back.)"""
    gc.collect()
    return gc.get_threshold(), gc.get_freeze_count(), gc_policy._installed


@pytest.fixture
def policy():
    """An installed policy over its own registry; afterwards the collector
    is as it was."""
    found = _collector()
    p = GcPolicy(Metrics())
    p.install()
    p.passes = Passes()
    gc.callbacks.append(p.passes)
    try:
        yield p
    finally:
        gc.callbacks.remove(p.passes)
        p.restore()
        assert _collector() == found


def _broker():
    return Broker(router=Router(min_tpu_batch=10 ** 6), hooks=Hooks())


def _subscribe(b, n, start=0):
    opts = pkt.SubOpts(qos=1)
    for i in range(start, start + n):
        b.subscribe(f"s{i}", f"s{i}", f"device/{i}/+/{i % 7}/#", opts,
                    lambda msg, o: None)


# -- install / restore -------------------------------------------------------


def test_install_sets_the_thresholds_and_restore_puts_them_back():
    found = _collector()
    p = GcPolicy(Metrics())
    p.install()
    try:
        assert gc.get_threshold() == THRESHOLDS
        p.install()  # a second call is none
        assert gc_policy._installed == found[2] + 1
        p.tick(1, 0)
        assert gc.get_freeze_count() > found[1]
    finally:
        p.restore()
    assert _collector() == found
    p.restore()
    assert _collector() == found


def test_the_last_of_two_policies_restores():
    found = _collector()
    a, b = GcPolicy(Metrics()), GcPolicy(Metrics())
    a.install()
    b.install()
    try:
        a.tick(1, 0)
        frozen = gc.get_freeze_count()
        assert frozen > found[1]
        a.restore()
        # the other app still serves: its heap stays frozen
        assert gc.get_threshold() == THRESHOLDS
        assert gc.get_freeze_count() == frozen
    finally:
        a.restore()
        b.restore()
    assert _collector() == found


def test_a_policy_that_is_not_installed_does_nothing():
    found = _collector()
    m = Metrics()
    p = GcPolicy(m)
    p.tick(10, 0)
    assert m.get("owner.gc.freezes") == 0
    assert (gc.get_threshold(), gc.get_freeze_count()) == found[:2]


@async_test
async def test_app_start_installs_and_stop_restores():
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.schema import load_config
    from emqx_tpu.mqtt.client import Client

    found = _collector()
    app = BrokerApp(load_config({
        "listeners": [{"port": 0, "bind": "127.0.0.1"}],
        "dashboard": {"enable": False},
        "router": {"enable_tpu": False},
    }))
    assert gc.get_threshold() == found[0]  # constructing an app changes nothing
    await app.start()
    try:
        assert gc.get_threshold() == THRESHOLDS
        port = list(app.listeners.list().values())[0].port
        c = Client("gc-1")
        await c.connect("127.0.0.1", port)
        await c.subscribe("a/+/b", qos=1)
        m = app.broker.metrics
        for _ in range(60):  # the 1 Hz tick sees the session and the filter
            if m.get("owner.gc.freezes"):
                break
            await asyncio.sleep(0.1)
        assert m.get("owner.gc.freezes") >= 1
        assert m.gauge("owner.gc.frozen.objects") > 0
        assert gc.get_freeze_count() > found[1]
        assert m.get("owner.gc.thaws") == 0
        await c.disconnect()
    finally:
        await app.stop()
    assert _collector() == found


# -- growth freezes ----------------------------------------------------------


@pytest.mark.parametrize("n", [2_000, 20_000])
def test_after_n_subscriptions_and_a_tick_the_generations_hold_a_bounded_heap(
        policy, n):
    b = _broker()
    policy.tick(1, 0)  # the interpreter's and the test runner's own heap
    before = len(gc.get_objects())
    _subscribe(b, n)
    assert len(gc.get_objects()) > before + 3 * n  # the table is tracked
    policy.tick(b.subscription_count(), b.released)
    assert b.subscription_count() == n
    # what is left in the generations does not grow with the table
    assert len(gc.get_objects()) < before + 500
    m = policy.metrics
    assert m.get("owner.gc.freezes") == 2
    assert m.gauge("owner.gc.frozen.objects") > 3 * n
    # counted as they were frozen, less the immortals a pass moves itself
    assert 0 <= gc.get_freeze_count() - policy.frozen_objects < 1_000


def test_a_static_table_is_frozen_once(policy):
    b = _broker()
    _subscribe(b, 100)
    for _ in range(10):
        policy.tick(b.subscription_count(), b.released)
    m = policy.metrics
    assert m.get("owner.gc.freezes") == 1
    assert m.get("owner.gc.thaws") == 0
    assert policy.passes.n == 1  # the one young collection before the freeze


def test_the_gauge_is_set_at_every_tick_zero_included():
    from emqx_tpu.observe.exporters import prometheus_exposition

    m = Metrics()
    p = GcPolicy(m)
    p.install()
    try:
        assert "emqx_owner_gc_frozen_objects" not in prometheus_exposition(
            m.snapshot())
        p.tick(0, 0)
        assert "emqx_owner_gc_frozen_objects 0\n" in prometheus_exposition(
            m.snapshot())
    finally:
        p.restore()


# -- frozen objects die by reference count -----------------------------------


def test_a_frozen_subscriber_dies_at_unsubscribe_with_no_pass(policy):
    b = _broker()

    def deliver(msg, opts):
        pass

    b.subscribe("s", "s", "a/+/b", pkt.SubOpts(qos=1), deliver)
    # the Subscriber (slots, no weak references) owns the one other
    # reference to its deliverer: the deliverer dies when it does
    gone = weakref.ref(deliver)
    del deliver
    policy.tick(b.subscription_count(), b.released)
    frozen = gc.get_freeze_count()
    passes = policy.passes.n
    assert gone() is not None
    assert b.unsubscribe("s", "a/+/b")
    assert gone() is None
    assert gc.get_freeze_count() < frozen
    assert policy.passes.n == passes
    assert b.released == 1


async def _connect(b, cm, cid="c1", clean=True, expiry=0):
    reader, writer = Reader(), Writer()
    conn = Connection(
        b, cm, reader, writer,
        ChannelConfig(session=SessionConfig(expiry_interval=expiry)))
    task = asyncio.ensure_future(conn.run())
    reader.feed(pkt.Connect(client_id=cid, clean_start=clean))
    reader.feed(pkt.Subscribe(
        packet_id=1, filters=[(f"dev/{cid}/+", pkt.SubOpts(qos=1))]))
    for _ in range(50):
        if conn.channel.session is not None and \
                conn.channel.session.subscriptions:
            break
        await asyncio.sleep(0.01)
    assert conn.channel.session.subscriptions
    return conn, reader, task


def _eof(b, cm, reader):
    reader.eof()


def _disconnect(b, cm, reader):
    reader.feed(pkt.Disconnect())


def _kick(b, cm, reader):
    assert cm.kick_client("c1")
    reader.eof()  # what the closed socket reads


CLOSE_PATHS = {"eof": _eof, "disconnect": _disconnect, "kick": _kick,
               "never_connected": _eof}


@pytest.mark.parametrize("path", [
    "eof", "disconnect", "kick", "discarded_by_clean_start",
    "detached_then_expired", "never_connected"])
def test_a_closed_frozen_session_dies_without_a_thaw(policy, path):
    b = _broker()
    cm = ChannelManager(b)
    refs = {}

    async def main():
        if path == "never_connected":
            reader, writer = Reader(), Writer()
            conn = Connection(b, cm, reader, writer, ChannelConfig())
            task = asyncio.ensure_future(conn.run())
            await asyncio.sleep(0)
        else:
            detach = path == "detached_then_expired"
            conn, reader, task = await _connect(
                b, cm, clean=not detach, expiry=60 if detach else 0)
            refs["session"] = weakref.ref(conn.channel.session)
        refs["connection"] = weakref.ref(conn)
        refs["channel"] = weakref.ref(conn.channel)
        policy.tick(b.subscription_count() + cm.channel_count() + 1,
                    b.released)
        refs["passes"] = policy.passes.n
        if path == "discarded_by_clean_start":
            second, reader2, task2 = await _connect(b, cm)
            reader.eof()  # the discarded channel closed its socket
            await task
            reader2.eof()
            await task2
            del second
        elif path == "detached_then_expired":
            reader.eof()
            await task
            assert cm.detached_count() == 1 and b.subscription_count() == 1
            assert cm.sweep_expired(now=float("inf")) == 1
        else:
            CLOSE_PATHS[path](b, cm, reader)
            await task

    asyncio.run(asyncio.wait_for(main(), 30))
    assert b.subscription_count() == 0 and cm.channel_count() == 0
    assert gc.get_freeze_count() > 0
    alive = [k for k, r in refs.items() if k != "passes" and r() is not None]
    assert alive == []
    assert policy.passes.n == refs["passes"]
    assert policy.metrics.get("owner.gc.thaws") == 0


# -- releases thaw -----------------------------------------------------------


@pytest.mark.parametrize("live, released, thaws", [
    (8_000, 2_000, 0),  # a quarter exactly: not past it
    (8_000, 2_001, 1),
    (100, THAW_MIN_RELEASED, 0),  # a small table: the floor
    (100, THAW_MIN_RELEASED + 1, 1),
    (0, THAW_MIN_RELEASED + 1, 1),
])
def test_a_thaw_waits_for_a_quarter_of_the_frozen_items_and_the_floor(
        policy, live, released, thaws):
    policy.tick(max(live, 1), 0)
    held = Passes()  # any object that takes a weak reference
    cycle = [held]
    cycle.append(cycle)
    gone = weakref.ref(held)
    policy.tick(max(live, 1) + 1, 0)  # the cycle is frozen while referenced
    del cycle, held
    gc.collect()
    assert gone() is not None  # no pass reclaims a frozen cycle
    policy.tick(max(live, 1) + 1 - released, released)
    m = policy.metrics
    assert m.get("owner.gc.thaws") == thaws
    assert (gone() is None) == bool(thaws)
    if thaws:
        assert policy.frozen_objects == gc.get_freeze_count()
        # the count starts again
        policy.tick(max(live, 1) + 1 - released, released + 1)
        assert m.get("owner.gc.thaws") == 1


def test_a_churn_past_a_quarter_thaws_once_and_the_heap_returns(policy):
    b = _broker()
    cm = ChannelManager(b)
    _subscribe(b, 2_000)

    def live():
        return b.subscription_count() + cm.channel_count()

    async def churn(rounds, tick_every):
        for i in range(rounds):
            conn, reader, task = await _connect(b, cm, cid=f"c{i}")
            if i % tick_every == 0:
                policy.tick(live(), b.released)  # the session is frozen live
            reader.eof()
            await task

    async def main():
        # one round first: what a round allocates for good (series, caches)
        await churn(1, 1)
        policy.tick(live(), b.released)
        gc.collect()
        start = len(gc.get_objects()) + gc.get_freeze_count()
        # 2 releases a round (the subscription, the session)
        await churn(THAW_MIN_RELEASED // 2 - 1, 10)
        assert b.released == THAW_MIN_RELEASED  # at the floor, not past it
        policy.tick(live(), b.released)
        assert policy.metrics.get("owner.gc.thaws") == 0
        await churn(1, 1)
        policy.tick(live(), b.released)
        return start

    start = asyncio.run(asyncio.wait_for(main(), 120))
    m = policy.metrics
    assert m.get("owner.gc.thaws") == 1
    assert m.get("owner.gc.freezes") >= THAW_MIN_RELEASED // 20
    assert b.subscription_count() == 2_000 and cm.channel_count() == 0
    gc.collect()
    end = len(gc.get_objects()) + gc.get_freeze_count()
    # nothing a round made is left (the thaw pass may reclaim older garbage)
    assert -1_000 < end - start < 50, (start, end)


def test_a_trie_match_leaves_nothing_for_the_collector(policy):
    """A cluster's sender matches every message on the host trie of its
    route replica: a match that left a cycle behind (a nested function
    calling itself: 8 objects) was 10^5 objects of garbage a second."""
    from emqx_tpu.broker.trie import TopicTrie

    trie = TopicTrie()
    for i in range(50):
        trie.insert(f"device/{i}/+/{i % 5}/#")
        trie.insert(f"device/{i}/#")
    gc.collect()
    passes = policy.passes.n
    hits = sum(len(trie.match(f"device/{i % 50}/mid/{i % 5}/leaf"))
               for i in range(2_000))
    assert hits == 4_000
    assert policy.passes.n == passes  # under the thresholds: none started
    assert gc.collect() < 50  # unreachable objects found: the matches' none


# -- what the policy must not become ------------------------------------------


def test_nothing_disables_the_collector_and_nothing_configures_the_policy():
    root = pathlib.Path(gc_policy.__file__).resolve().parents[1]
    for path in root.rglob("*.py"):
        assert "gc.disable" not in path.read_text(), path
    src = pathlib.Path(gc_policy.__file__).read_text()
    assert "environ" not in src and "config" not in src
