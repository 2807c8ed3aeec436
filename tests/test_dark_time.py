"""The chip's dark time (observe/profiler.py `DarkLedger`): intervals in
which no launch is lit, each put down to what the owner thread ran, on a
hand-made timeline whose answer is known and on the real ingest path; the
intervals kept for `dark.json` while a capture is armed; and the
benchmark's mapping of them onto a trace's idle gaps
(benchmark/harness/dark_cover.py), which leaves trace_reduce's fields as
they were."""

import asyncio
import functools
import glob
import json
import os
import sys

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import DARK_CAUSES, Metrics, kind_of
from emqx_tpu.broker.router import Router
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import dark_cover, trace_reduce  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(P, "_now", c)
    return c


@pytest.fixture
def owner(clock):
    """This thread as the owner loop's, with a LoopBudget bound to it (its
    first select returns at 0); unbound again afterwards."""
    b = P.LoopBudget()
    b.exit_select()
    yield b
    b.closed()


def at(clock, t, fn, *a):
    clock.t = t
    return fn(*a)


def book(ledger, m):
    """-> (dark seconds, intervals, {cause: seconds}) flushed into `m` so
    far."""
    ledger.flush(m)
    h = m.histogram("device.dark.seconds")
    causes = {}
    for c in DARK_CAUSES:
        ch = m.histogram(f"device.dark.{c}.seconds")
        if ch is not None and ch.sum:
            causes[c] = ch.sum
    return (h.sum, h.count, causes) if h is not None else (0.0, 0, causes)


# -- the ledger on a hand-made timeline ------------------------------------------


def test_two_workers_overlapping_launches_and_their_causes(clock, owner):
    """Two dispatch workers whose launches overlap keep the chip lit; the
    dark stretch between them is split into what the owner thread ran,
    and the closing batch's take-to-enqueue is `encode`."""
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.5, led.took, 0)  # binds the owner; chip not dark: no view
    at(clock, 1.0, led.lit, 0)    # worker A: lit 1
    at(clock, 1.5, led.lit, 1)    # worker B, overlapping: lit 2
    at(clock, 2.0, led.unlit, 0)  # lit 1: still lit
    at(clock, 3.0, P.begin, "host_dispatch")
    at(clock, 4.0, led.unlit, 1)  # lit 0: dark from 4
    at(clock, 6.0, P.end)         # host_dispatch 3 s, 2 of them dark
    at(clock, 6.5, owner.enter_select, True)
    at(clock, 7.5, owner.exit_select)        # 1 s blocked: select
    at(clock, 8.0, P.begin, "channel.ack_in")
    at(clock, 8.5, P.begin, "egress.send")  # a child: counted in ack_in
    at(clock, 9.0, P.end)
    at(clock, 9.5, P.end)                   # ack_in 1.5 s
    at(clock, 10.0, led.took, 2)            # dark: the owner's view at 10
    at(clock, 10.0, P.begin, "ingest.take")
    at(clock, 10.25, P.end)                 # the take is encode, not owner
    at(clock, 12.0, led.lit, 2)             # closes [4, 12]
    seconds, n, causes = book(led, m)
    assert n == 1 and seconds == pytest.approx(8.0)
    assert causes == pytest.approx({
        "host_dispatch": 2.0, "select": 1.0, "channel.ack_in": 1.5,
        "other": 1.5, "encode": 2.0})
    # a take while lit: the batch's encode covers the whole dark interval
    at(clock, 12.5, led.took, 3)
    at(clock, 13.0, led.unlit, 2)           # dark from 13
    at(clock, 14.0, led.lit, 3)
    # a launch that came from no take: the owner's view splits it all
    at(clock, 15.0, led.unlit, 3)
    at(clock, 15.5, P.begin, "ingest.enqueue")
    at(clock, 16.0, P.end)
    at(clock, 17.0, led.lit, None)
    seconds, n, causes = book(led, m)
    assert n == 3 and seconds == pytest.approx(11.0)
    assert causes == pytest.approx({
        "host_dispatch": 2.0, "select": 1.0, "channel.ack_in": 1.5,
        "ingest.enqueue": 0.5, "other": 3.0, "encode": 3.0})


@pytest.mark.parametrize("flush_at", [None, 2.5, 4.5])
def test_causes_add_up_to_the_dark_seconds(clock, owner, flush_at):
    """However the flushes fall (one inside an open interval books its
    elapsed part, the close books the rest and counts it once), the causes
    add up to `device.dark.seconds` within 1e-9 s."""
    led = P.DarkLedger()
    m = Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.1, led.lit, 0)
    at(clock, 1.0, led.unlit, 0)
    at(clock, 1.2, P.begin, "channel.publish_in")
    at(clock, 1.9, P.end)
    at(clock, 2.1, owner.enter_select, True)
    at(clock, 3.3, owner.exit_select)
    at(clock, 3.4, P.begin, "host_dispatch")
    if flush_at is not None:
        at(clock, flush_at, led.flush, m)  # inside the interval
        assert m.histogram("device.dark.seconds").count == 0
    at(clock, 4.7, P.end)
    at(clock, 5.0, led.took, 1)
    at(clock, 5.3, led.lit, 1)
    at(clock, 6.0, led.flush, m)
    h = m.histogram("device.dark.seconds")
    assert h.count == 1 and h.sum == pytest.approx(4.3)
    parts = sum(m.histogram(f"device.dark.{c}.seconds").sum
                for c in DARK_CAUSES
                if m.histogram(f"device.dark.{c}.seconds") is not None)
    assert abs(parts - h.sum) < 1e-9
    assert m.histogram("device.dark.host_dispatch.seconds").sum == \
        pytest.approx(1.3)
    assert m.histogram("device.dark.encode.seconds").sum == pytest.approx(0.3)


def test_a_host_dispatch_that_straddles_a_boundary_is_split(clock, owner):
    """A settle open across either end of a dark interval is counted for
    the part inside it only, not moved whole to one side."""
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.0, led.lit, 0)
    at(clock, 1.0, P.begin, "host_dispatch")  # 1 s before the dark
    at(clock, 2.0, led.unlit, 0)              # dark from 2
    at(clock, 3.0, P.end)                     # 1 s of it dark
    at(clock, 3.5, P.begin, "host_dispatch")  # open across the close
    at(clock, 4.0, led.lit, None)             # closes [2, 4]: a view at 4
    seconds, n, causes = book(led, m)
    assert seconds == pytest.approx(2.0)
    assert causes == pytest.approx({"host_dispatch": 1.5, "other": 0.5})
    at(clock, 5.0, led.unlit, None)           # dark from 5, still open
    at(clock, 6.0, P.end)                     # 1 s of the second interval
    at(clock, 7.0, led.lit, None)
    seconds, n, causes = book(led, m)
    assert n == 2 and seconds == pytest.approx(4.0)
    assert causes["host_dispatch"] == pytest.approx(2.5)
    assert causes["other"] == pytest.approx(1.5)


@pytest.mark.parametrize("taken_at", [1.5, 3.5])
def test_a_flush_books_a_pending_take_as_encode(clock, owner, taken_at):
    """A launch that takes long to enqueue (a compile inside `launch`)
    holds the chip dark across flushes: from its take on, each flush books
    the stretch as `encode`, as the close does, not as the owner's select."""
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.0, led.lit, 0)
    if taken_at < 2.0:
        at(clock, taken_at, led.took, 1)      # taken while lit
    at(clock, 2.0, led.unlit, 0)              # dark from 2
    at(clock, 2.0, owner.enter_select, True)
    if taken_at > 2.0:
        at(clock, taken_at, owner.exit_select)
        at(clock, taken_at, led.took, 1)      # taken while dark
        at(clock, taken_at, owner.enter_select, True)
    at(clock, 4.0, led.flush, m)
    at(clock, 5.0, led.flush, m)
    at(clock, 6.0, owner.exit_select)
    at(clock, 6.0, led.lit, 1)
    seconds, n, causes = book(led, m)
    assert n == 1 and seconds == pytest.approx(4.0)
    encode = 6.0 - max(taken_at, 2.0)
    assert causes["encode"] == pytest.approx(encode)
    assert causes.get("select", 0.0) == pytest.approx(4.0 - encode)
    assert abs(sum(causes.values()) - seconds) < 1e-9


def test_a_take_that_lights_nothing_is_not_pending(clock, owner):
    """A batch served on the CPU (`untook`), or one whose launch failed and
    that a lit batch has left TAKES_BEHIND seqs behind, is not the take a
    flush books as encode."""
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.0, led.lit, 0)
    at(clock, 0.5, led.took, 1)
    at(clock, 0.5, led.untook, 1)             # served on the CPU
    at(clock, 1.0, led.unlit, 0)
    at(clock, 2.0, led.flush, m)
    at(clock, 3.0, led.lit, None)
    assert book(led, m)[2] == pytest.approx({"other": 2.0})
    at(clock, 4.0, led.took, 2)               # its launch will fail
    seq = 3 + P.TAKES_BEHIND
    at(clock, 4.0, led.took, seq)
    at(clock, 4.5, led.lit, seq)
    assert list(led._takes) == []
    at(clock, 5.0, led.unlit, None)
    at(clock, 6.0, led.flush, m)
    assert "encode" not in book(led, m)[2]


def test_a_section_outside_the_owner_set_is_other(clock, owner):
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.0, led.lit, 0)
    at(clock, 1.0, led.unlit, 0)
    at(clock, 1.0, P.begin, "t.dark.unknown")
    at(clock, 2.0, P.end)
    at(clock, 3.0, led.lit, None)
    assert book(led, m)[2] == pytest.approx({"other": 2.0})


def test_every_dark_series_is_declared():
    assert kind_of("device.dark.seconds") == "histogram"
    for cause in DARK_CAUSES:
        assert kind_of(f"device.dark.{cause}.seconds") == "histogram", cause
    assert {"select", "encode", "other", "host_dispatch",
            "ingest.finish"} <= set(DARK_CAUSES)
    assert not {"launch", "device_execute", "readback"} & set(DARK_CAUSES)
    for gone in ("profile.capture.seconds", "profile.capture.bytes"):
        assert kind_of(gone) is None, gone


# -- kept for dark.json only while armed ------------------------------------------


def test_intervals_are_kept_only_while_armed_and_bounded(clock, owner,
                                                         monkeypatch):
    monkeypatch.setattr(P, "DARK_KEPT", 4)
    led = P.DarkLedger()

    def one(t, seq):
        at(clock, t, led.took, seq)
        at(clock, t + 0.25, led.lit, seq)
        at(clock, t + 0.5, led.unlit, seq)

    one(0.0, 0)
    one(1.0, 1)
    assert led._kept is None  # disarmed: no list at all
    assert led.release() == ([], 0)
    led.keep()
    for i in range(2, 9):
        one(float(i), i)
    kept, dropped = led.release()
    assert len(kept) == 4 and dropped == 3
    assert led._kept is None
    first = kept[0]
    assert first["opened_by"] == 1 and first["closed_by"] == 2
    assert (first["start_ns"], first["end_ns"]) == (1_500_000_000,
                                                    2_250_000_000)
    assert sum(first["causes"].values()) == pytest.approx(0.75)
    assert first["causes"]["encode"] == pytest.approx(0.25)


def test_an_interval_open_at_disarm_is_kept_up_to_then(clock, owner):
    """The capture can end while the chip is dark: that interval comes
    last, cut at the release, with `closed_by` None, and nothing of it is
    booked twice (its close books it, once)."""
    led, m = P.DarkLedger(), Metrics()
    at(clock, 0.0, led.took, 0)
    at(clock, 0.5, led.lit, 0)
    at(clock, 1.0, led.keep)
    at(clock, 2.0, led.unlit, 0)              # dark from 2, still open
    at(clock, 2.5, P.begin, "host_dispatch")
    at(clock, 3.0, led.flush, m)              # books [2, 3] of it
    at(clock, 3.5, P.end)
    kept, dropped = at(clock, 4.0, led.release)
    assert dropped == 0 and len(kept) == 1
    [last] = kept
    assert last["opened_by"] == 0 and last["closed_by"] is None
    assert (last["start_ns"], last["end_ns"]) == (2_000_000_000,
                                                  4_000_000_000)
    assert last["causes"] == pytest.approx({"host_dispatch": 1.0,
                                            "select": 0.0, "other": 1.0})
    at(clock, 5.0, led.lit, None)
    seconds, n, causes = book(led, m)
    assert n == 1 and seconds == pytest.approx(3.0)
    assert sum(causes.values()) == pytest.approx(3.0)


def test_disarm_writes_dark_json_beside_the_trace_with_its_anchor(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    p = P.Profiler(metrics=Metrics(), trace_dir=str(tmp_path))
    led = P.dark_ledger
    try:
        info = p.arm(duration_s=20.0)
        led.lit(10_001)
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
        led.unlit(10_001)
        led.lit(10_002)
        led.unlit(10_002)
    finally:
        entry = p.disarm("test")
    assert entry["deleted"] is False
    assert P.dark_ledger._kept is None
    [xplane] = glob.glob(os.path.join(info["dir"], "**", "*.xplane.pb"),
                         recursive=True)
    with open(os.path.join(os.path.dirname(xplane), "dark.json")) as f:
        dark = json.load(f)
    assert dark["clock"]["perf_counter_ns"] == info["clock_ns"]
    assert dark["dropped"] == 0
    ours = [iv for iv in dark["intervals"] if iv["closed_by"] == 10_002]
    assert len(ours) == 1 and ours[0]["opened_by"] == 10_001
    assert ours[0]["end_ns"] >= ours[0]["start_ns"] >= info["clock_ns"]
    names = [e.name for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert any(n.startswith("emqx:clock") for n in names)


# -- the real ingest path ---------------------------------------------------------


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        asyncio.run(asyncio.wait_for(fn(*a, **kw), timeout=120))

    return wrapper


@async_test
async def test_a_served_run_has_encode_and_its_causes_add_up():
    """A CPU-served BatchIngest run: the dark intervals between its device
    launches carry `encode` and their causes add up to the total; the REST
    `dark` block gives the same totals and the shares."""
    P.flush(Metrics())  # what earlier tests left in the ledger
    b = Broker(router=Router(min_tpu_batch=8), hooks=Hooks())
    for i in range(8):
        b.subscribe(f"s{i}", f"c{i}", f"t/{i}/+", pkt.SubOpts(),
                    lambda m, o: None)
    ing = BatchIngest(b, max_batch=64, window_us=500)
    b.ingest = ing
    ing.start()
    for _ in range(4):
        rs = [await b.apublish_enqueue(
            Message(topic=f"t/{i % 8}/x", payload=b"p", from_client=f"p{i}"))
            for i in range(128)]
        await asyncio.gather(*[r for r in rs if not isinstance(r, int)])
    await ing.stop()
    m = b.metrics
    table = P.section_table(m)["dark"]
    h = m.histogram("device.dark.seconds")
    assert h is not None and h.count >= 2 and h.sum > 0
    assert m.histogram("device.dark.encode.seconds").sum > 0
    parts = sum(c["seconds"] for c in table["causes"].values())
    assert abs(parts - h.sum) < 1e-9
    assert table["intervals"] == h.count and table["seconds"] == h.sum
    assert sum(c["share"] for c in table["causes"].values()) == \
        pytest.approx(1.0)


# -- the benchmark's mapping onto the trace ----------------------------------------

MS = 1_000_000
PERF0 = 5_000_000_000_000  # perf_counter ns at the anchor
TRACE0 = 1_700_000_000 * 10**9  # the same moment on the trace's clock


def planes(anchor=True):
    ops = [("fusion.1", TRACE0 + 10 * MS, 20 * MS),
           ("fusion.1", TRACE0 + 60 * MS, 10 * MS),
           ("fusion.1", TRACE0 + 100 * MS, 5 * MS)]
    host = [("outer_loop", TRACE0 - 5 * MS, 120 * MS),
            ("owner:loop.busy", TRACE0 + 28 * MS, 80 * MS)]
    if anchor:
        host.insert(1, ("emqx:clock#perf_counter_ns=%d#" % PERF0, TRACE0, 1000))
    return [("/device:TPU:0", [("XLA Ops", ops),
                               ("XLA Modules", [("jit_step(1)", TRACE0 + 10 * MS,
                                                 95 * MS)])]),
            ("/host:CPU", [("python", host)])]


def dark():
    return {"clock": {"annotation": "emqx:clock", "perf_counter_ns": PERF0},
            "intervals": [
                {"start_ns": PERF0 + 31 * MS, "end_ns": PERF0 + 59 * MS,
                 "opened_by": 1, "closed_by": 2,
                 "causes": {"host_dispatch": 0.02, "encode": 0.008}},
                {"start_ns": PERF0 + 70 * MS, "end_ns": PERF0 + 100 * MS,
                 "opened_by": 2, "closed_by": 3,
                 "causes": {"select": 0.006, "encode": 0.024}}],
            "dropped": 0}


def test_the_anchor_leaves_trace_reduce_as_it_was():
    assert trace_reduce.reduce_planes(planes(anchor=True)) == \
        trace_reduce.reduce_planes(planes(anchor=False))


def test_dark_cover_maps_the_program_intervals_onto_the_gaps():
    ps = planes()
    gaps = dark_cover.longest_gaps(ps)
    reduced = trace_reduce.reduce_planes(ps)
    assert [(e - s) / 1e9 for s, e in gaps] == \
        [sec for _, sec in reduced["idle_gaps"]]
    # the gaps: [30, 60] and [70, 100] ms after the anchor, and the
    # window's edges [-5, 10] and [105, 115]
    assert gaps[:2] == [(TRACE0 + 30 * MS, TRACE0 + 60 * MS),
                        (TRACE0 + 70 * MS, TRACE0 + 100 * MS)]
    cover = dark_cover.dark_cover(gaps, ps, dark())
    rows = cover["gaps"]
    assert rows[0][0] == "host_dispatch" and rows[0][2] == pytest.approx(28 / 30)
    assert rows[1][0] == "encode" and rows[1][2] == pytest.approx(1.0)
    assert [r[0] for r in rows[2:]] == ["uncovered", "uncovered"]
    assert cover["covered_share"] == pytest.approx(58 / 85 * 100)
    assert dark_cover.dark_cover(gaps, planes(anchor=False), dark()) is None
    assert dark_cover.dark_cover(gaps, ps, None) is None
