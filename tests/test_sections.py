"""The owner thread's time budget (observe/profiler.py sections, PR 25).

Pins what docs/observability.md "The owner thread's time budget" names:

- a section's self time is its total less its children's, per thread,
  and an exception leaves the thread's stack balanced; no section holds
  an `await` (an async hook is awaited between two stretches);
- the budget closes: select + the loop thread's self time + other =
  the loop's wall, on a fake clock and on a real asyncio loop;
- a run phase over 0.5 s is a stall with its section's name; a GC pass
  lands in the owner.gc series; a compile counts by the section open on
  the compiling thread;
- disarmed, a section makes no TraceAnnotation; armed, the same sites
  lie in the xplane as `emqx:<name>` with the batch's seq, and no
  python frame does;
- every new series is declared and exported as `_sum` / `_count`;
- the silent session-queue drop has a counter; the allocator peak has
  a gauge with a fallback.
"""

import asyncio
import gc
import glob
import threading
import time

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import HISTOGRAM, Metrics, kind_of
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as P
from emqx_tpu.observe.exporters import prometheus_exposition


class Clock:
    """A clock that only moves when the test says so."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture()
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(P, "_now", c)
    return c


@pytest.fixture()
def metrics():
    """A registry that only sees what the test itself accumulates: what
    earlier tests left in the process's accumulators is flushed away."""
    P.flush(Metrics())
    return Metrics()


def hist(m, name):
    h = m.histogram(name)
    return (h.sum, h.count) if h is not None else (0.0, 0)


# -- accumulators ---------------------------------------------------------------


def test_nesting_gives_self_as_total_less_children(clock, metrics):
    with P.section("t.outer"):
        clock.t += 0.5
        with P.section("t.inner"):
            clock.t += 0.2
        with P.section("t.inner") as s:
            s.n = 3  # a read chunk: one section, three packets
            clock.t += 0.1
        clock.t += 0.25
    P.flush(metrics)
    assert hist(metrics, "profile.section.t.outer.seconds") == (
        pytest.approx(1.05), 1)
    assert hist(metrics, "profile.section.t.outer.self.seconds") == (
        pytest.approx(0.75), 1)
    assert hist(metrics, "profile.section.t.inner.seconds") == (
        pytest.approx(0.3), 4)
    assert hist(metrics, "profile.section.t.inner.self.seconds") == (
        pytest.approx(0.3), 4)
    # a second flush with nothing new moves nothing
    P.flush(metrics)
    assert hist(metrics, "profile.section.t.outer.seconds")[1] == 1


def test_two_threads_keep_separate_stacks(metrics):
    """A section open on one thread is no parent of another thread's."""
    entered, release = threading.Event(), threading.Event()
    seen = {}

    def worker():
        with P.section("t.thread.worker"):
            seen["worker"] = P.current_section()
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=worker)
    with P.section("t.thread.main"):
        t.start()
        assert entered.wait(5.0)
        assert P.current_section() == "t.thread.main"
        time.sleep(0.02)
        release.set()
        t.join(5.0)
        assert not t.is_alive()
    assert seen["worker"] == "t.thread.worker"
    P.flush(metrics)
    tot, n = hist(metrics, "profile.section.t.thread.main.seconds")
    slf, _ = hist(metrics, "profile.section.t.thread.main.self.seconds")
    assert n == 1 and slf == pytest.approx(tot)  # the worker is no child
    assert hist(metrics, "profile.section.t.thread.worker.seconds")[1] == 1


def test_an_exception_leaves_the_stack_balanced(metrics):
    depth = len(P._acc().stack)
    with pytest.raises(ValueError):
        with P.section("t.raise.outer"):
            with P.section("t.raise.inner"):
                raise ValueError("boom")
    assert len(P._acc().stack) == depth
    P.begin("t.raise.pair")
    try:
        try:
            raise KeyError("x")
        finally:
            P.end()
    except KeyError:
        pass
    assert len(P._acc().stack) == depth
    P.flush(metrics)
    for name in ("outer", "inner", "pair"):
        assert hist(metrics, f"profile.section.t.raise.{name}.seconds")[1] == 1


def test_fold_sync_hands_back_the_rest_of_an_async_chain():
    """`Hooks.fold_sync` runs the synchronous head of a chain and returns
    the coroutine that finishes it; `arun_fold` is the two together."""
    from emqx_tpu.broker.hooks import StopAndReturn

    h = Hooks()
    h.add("t.fold", lambda acc: acc + ["a"], priority=3)
    assert h.fold_sync("t.fold", (), []) == (["a"], None)

    async def slow(acc):
        await asyncio.sleep(0)
        return "ok", acc + ["b"]

    h.add("t.fold", slow, priority=2)
    h.add("t.fold", lambda acc: acc + ["c"], priority=1)
    acc, rest = h.fold_sync("t.fold", (), [])
    assert acc == ["a"] and rest is not None
    assert asyncio.run(rest) == ["a", "b", "c"]
    assert asyncio.run(h.arun_fold("t.fold", (), [])) == ["a", "b", "c"]

    def stop(acc):
        raise StopAndReturn(acc + ["stop"])

    h.add("t.fold", stop, priority=0)
    assert asyncio.run(h.arun_fold("t.fold", (), [])) == [
        "a", "b", "c", "stop"]
    h2 = Hooks()
    h2.add("t.fold", lambda acc: ("stop", "final"))
    h2.add("t.fold", lambda acc: "never", priority=-1)
    assert h2.fold_sync("t.fold", (), "x") == ("final", None)


@pytest.mark.parametrize("async_hook", [False, True])
def test_no_section_holds_the_await_of_an_async_hook(metrics, async_hook):
    """`ingest.enqueue` is one entry per message; where the
    message.publish chain has to be awaited the section ends before the
    await, so another task's time (and its sections) is not its own."""
    b = _mk_broker()
    seen = []

    async def other(gate):
        await gate.wait()
        with P.section("t.await.other"):
            seen.append(P.current_section())
            time.sleep(0.05)

    async def main():
        gate = asyncio.Event()
        task = asyncio.ensure_future(other(gate))

        async def slow_hook(msg):
            gate.set()
            await task  # the other task runs while this one waits
            return msg

        def quick_hook(msg):
            return msg

        b.hooks.add("message.publish", slow_hook if async_hook else quick_hook)
        n = await b.apublish_enqueue(Message(topic="t/1", payload=b"x"))
        assert n == 0  # no subscriber, no ingest: dispatched inline
        if not async_hook:
            gate.set()
            await task
        assert P.current_section() is None

    asyncio.run(main())
    P.flush(metrics)
    tot, n = hist(metrics, "profile.section.ingest.enqueue.seconds")
    slf, _ = hist(metrics, "profile.section.ingest.enqueue.self.seconds")
    assert n == 1  # the stretch after the await is the same entry
    assert tot < 0.04 and slf == pytest.approx(tot)
    assert seen == ["t.await.other"]  # no parent: `ingest.enqueue` was closed


# -- the loop's budget ------------------------------------------------------------


@pytest.fixture(autouse=True)
def _no_budget_keeps_the_thread():
    """A LoopBudget a test drove by hand stays bound to this thread:
    release it, so that a later test's `flush` reads its sections."""
    yield
    P._acc().budget = None


def drive(budget, clock, idle, work):
    """One loop iteration: `idle` seconds in select, then `work()`."""
    budget.enter_select()
    clock.t += idle
    budget.exit_select()
    work()


def test_the_budget_closes_on_a_fake_clock(clock, metrics):
    budget = P.LoopBudget()

    def some_work():
        with P.section("t.budget.a"):
            clock.t += 0.2
            with P.section("t.budget.b"):
                clock.t += 0.1
        clock.t += 0.05  # what no section names

    t0 = clock.t
    drive(budget, clock, 1.0, lambda: None)  # the first select binds
    t_bound = clock.t
    for idle in (0.5, 0.0, 2.0):
        drive(budget, clock, idle, some_work)
    P.flush(metrics)  # asks; the loop's series follow at the boundary
    assert hist(metrics, "owner.loop.run.seconds")[1] == 0
    budget.enter_select()  # closes the last run phase
    wall = clock.t - t_bound
    select_s, iterations = hist(metrics, "owner.loop.select.seconds")
    run_s, _ = hist(metrics, "owner.loop.run.seconds")
    other_s, _ = hist(metrics, "owner.loop.other.seconds")
    self_s = sum(
        hist(metrics, f"profile.section.t.budget.{n}.self.seconds")[0]
        for n in "ab")
    assert t_bound - t0 == pytest.approx(1.0)  # before the binding: no one's
    assert iterations == 4
    assert select_s == pytest.approx(2.5)
    assert run_s == pytest.approx(3 * 0.35)
    assert other_s == pytest.approx(3 * 0.05)
    assert select_s + self_s + other_s == pytest.approx(wall, rel=0.01)
    table = P.section_table(metrics, budget)
    assert table["sections"]["t.budget.a"]["busy_share"] == pytest.approx(
        0.2 / 0.35)
    assert table["loop"]["other_s"] == pytest.approx(3 * 0.05)


def test_a_flush_mid_iteration_reads_whole_iterations_only(clock, metrics):
    """The scrape runs inside an iteration: it only asks, and the loop's
    thread hands over its sections when that iteration has ended, so
    every series covers the same whole iterations."""
    budget = P.LoopBudget()
    drive(budget, clock, 0.1, lambda: None)

    def work():
        with P.section("t.whole.a"):
            clock.t += 0.2

    drive(budget, clock, 0.0, work)
    budget.enter_select()
    budget.exit_select()
    with P.section("t.whole.a"):
        clock.t += 0.2
        P.flush(metrics)
        assert hist(metrics, "profile.section.t.whole.a.seconds")[1] == 0
    clock.t += 0.1
    budget.enter_select()
    run_s, iterations = hist(metrics, "owner.loop.run.seconds")
    a_self, a_n = hist(metrics, "profile.section.t.whole.a.self.seconds")
    other_s, _ = hist(metrics, "owner.loop.other.seconds")
    assert (run_s, iterations, a_self, a_n) == (
        pytest.approx(0.5), 3, pytest.approx(0.4), 2)
    assert other_s == pytest.approx(0.1)


def test_the_budget_closes_on_a_real_loop(metrics, monkeypatch):
    """`loop_factory`'s loop: select + run = the loop's wall, and the run
    time = the sections' self time + other."""

    async def main():
        for _ in range(20):
            with P.section("t.real.work"):
                time.sleep(0.002)
            await asyncio.sleep(0.003)

    monkeypatch.setattr(P, "default_profiler", P.Profiler())
    t0 = time.perf_counter()
    asyncio.run(main(), loop_factory=P.loop_factory)
    wall = time.perf_counter() - t0
    budget = P.default_profiler.budget
    assert isinstance(budget, P.LoopBudget) and budget.iterations >= 20
    assert P.default_profiler.listeners == [budget]
    assert P._acc().budget is None  # the loop closed
    budget.flush(metrics)
    select_s, _ = hist(metrics, "owner.loop.select.seconds")
    run_s, _ = hist(metrics, "owner.loop.run.seconds")
    other_s, _ = hist(metrics, "owner.loop.other.seconds")
    self_s, n = hist(metrics, "profile.section.t.real.work.self.seconds")
    assert n == 20 and self_s >= 20 * 0.002
    assert select_s >= 0.05
    assert run_s == pytest.approx(self_s + other_s, rel=1e-6)
    # what lies outside (loop set-up and close) is small beside 100 ms
    assert select_s + run_s <= wall
    assert select_s + run_s >= wall * 0.9


def test_a_long_callback_is_a_stall_with_its_section(clock, metrics, caplog):
    budget = P.LoopBudget()
    drive(budget, clock, 0.1, lambda: None)

    def slow():
        with P.section("t.stall.slow"):
            clock.t += 0.55
            P.note_gc(0.2, 2)  # a full GC pass inside it
        clock.t += 0.05

    def quick():
        with P.section("t.stall.quick"):
            clock.t += 0.3

    with caplog.at_level("WARNING", logger="emqx_tpu.profiler"):
        drive(budget, clock, 0.1, slow)
        drive(budget, clock, 0.1, quick)
        P.flush(metrics)
        budget.enter_select()
    assert hist(metrics, "owner.loop.stall.seconds") == (
        pytest.approx(0.6), 1)
    (stall,) = budget.stalls
    assert stall["seconds"] == pytest.approx(0.6)
    assert stall["section"] == "t.stall.slow"
    assert stall["section_seconds"] == pytest.approx(0.55)
    assert stall["gc_seconds"] == pytest.approx(0.2)
    assert "t.stall.slow" in caplog.text and "stalled 0.600s" in caplog.text
    # the tick's reading for the long_schedule alarm is the same measure
    assert budget.take_longest_run() == pytest.approx(0.6)
    assert P.section_table(metrics, budget)["stalls"] == [stall]


def test_a_stall_outside_every_section_is_named_other(clock, metrics):
    budget = P.LoopBudget()
    drive(budget, clock, 0.1, lambda: None)

    def unnamed():
        clock.t += 0.7

    drive(budget, clock, 0.0, unnamed)
    budget.enter_select()
    assert budget.stalls[-1]["section"] == "other"
    assert budget.stalls[-1]["section_seconds"] == pytest.approx(0.7)


def test_a_forced_full_gc_lands_in_the_gen2_series(metrics):
    from emqx_tpu.observe.alarm import AlarmManager
    from emqx_tpu.observe.monitors import SysMon

    sm = SysMon(AlarmManager())
    try:
        junk = [[i] for i in range(50_000)]
        gc.collect(2)
        gc.collect(0)
        del junk
    finally:
        sm.close()
    P.flush(metrics)
    pause_s, passes = hist(metrics, "owner.gc.pause.seconds")
    gen2_s, gen2 = hist(metrics, "owner.gc.gen2.seconds")
    assert gen2 >= 1 and gen2_s > 0.0
    assert passes >= gen2 + 1 and pause_s >= gen2_s


@pytest.mark.parametrize("where,series", [
    ("launch", "device.compile.in_launch.count"),
    ("readback", "device.compile.in_readback.count"),
    ("host_dispatch", None),
])
def test_a_compile_counts_by_the_section_open_on_its_thread(where, series):
    import jax
    import jax.numpy as jnp

    from emqx_tpu.observe.device_watch import DeviceWatch

    m = Metrics()
    watch = DeviceWatch(m, registry={})
    watch.poll()
    salt = {"launch": 3, "readback": 5, "host_dispatch": 7}[where]
    with P.section(where):
        # a new program: never compiled before in this process
        jax.jit(lambda x: (x * salt + salt).sum())(
            jnp.ones((salt, 11))).block_until_ready()
    got = watch.poll()
    assert got["compiles"] >= 1
    assert m.get("device.compile.count") == got["compiles"]
    for name in ("device.compile.in_launch.count",
                 "device.compile.in_readback.count"):
        assert m.get(name) == (got["compiles"] if name == series else 0)


# -- annotations ------------------------------------------------------------------


def test_disarmed_a_section_makes_no_annotation(monkeypatch):
    import jax

    made = []

    class Spy:
        def __init__(self, *a, **kw):
            made.append((a, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    assert P.default_profiler.capture is None
    with P.section("t.disarmed", batch=1):
        P.begin("t.disarmed.pair")
        P.end()
    budget = P.LoopBudget()
    budget.enter_select(blocking=True)
    budget.exit_select()
    assert made == []
    # armed, the same sites make one each, named and with their ids
    monkeypatch.setattr(P.default_profiler, "capture", {"dir": "x"})
    with P.batch_ids(batch=9, rows=3):
        with P.section("t.armed"):
            pass
    assert made == [(("emqx:t.armed",), {"batch": 9, "rows": 3})]


def _mk_broker(min_batch=1):
    return Broker(router=Router(min_tpu_batch=min_batch), hooks=Hooks())


def test_armed_the_sections_lie_in_the_xplane(tmp_path, monkeypatch):
    """Armed on the CPU, a served device batch leaves `emqx:<section>`
    events with the batch's seq in the trace's host plane (the
    executor's sections too), and no python frame."""
    from jax.profiler import ProfileData

    prof = P.Profiler(trace_dir=str(tmp_path))
    monkeypatch.setattr(P, "default_profiler", prof)

    async def main():
        b = _mk_broker(min_batch=8)
        for i in range(8):
            b.subscribe(f"s{i}", f"c{i}", f"t/{i}/+", pkt.SubOpts(),
                        lambda m, o: None)
        ing = BatchIngest(b, max_batch=64, window_us=500)
        b.ingest = ing
        ing.start()
        # warm batch: the compile lands outside the capture
        await b.apublish_enqueue(Message(topic="t/0/w", payload=b"w"))
        await asyncio.sleep(0.2)
        first = ing._seq
        info = prof.arm(duration_s=20.0)
        assert info["python_tracer"] is False
        try:
            rs = [await b.apublish_enqueue(
                Message(topic=f"t/{i % 8}/x", payload=b"p"))
                for i in range(32)]
            await asyncio.gather(*[r for r in rs if not isinstance(r, int)])
            await asyncio.sleep(0.05)
        finally:
            entry = prof.disarm("test")
        await ing.stop()
        return first, ing._seq, entry

    first, last, entry = asyncio.run(main())
    assert entry["bytes"] < 8 << 20  # no python tracer: a small capture
    (path,) = glob.glob(f"{entry['dir']}/**/*.xplane.pb", recursive=True)
    events = {}  # name -> [stats]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                assert ".py:" not in e.name, f"python frame {e.name!r}"
                if e.name.startswith("emqx:"):
                    events.setdefault(e.name, []).append(dict(e.stats))
    for name in ("prepare", "launch", "device_execute", "readback",
                 "host_dispatch", "ingest.take", "ingest.finish",
                 "ingest.enqueue"):
        assert f"emqx:{name}" in events, (name, sorted(events))
    batches = {st["batch"] for st in events["emqx:host_dispatch"]}
    assert batches and batches <= set(range(first, last))
    for name in ("prepare", "launch", "device_execute", "readback"):
        assert {st["batch"] for st in events[f"emqx:{name}"]} == batches
    assert all(st["rows"] >= 8 for st in events["emqx:host_dispatch"])


def test_armed_the_loop_s_phases_are_in_the_trace(tmp_path, monkeypatch):
    """A stretch in which the loop only polls is ONE `owner:loop.busy`
    however many iterations it spans, a select that may block is an
    `owner:loop.select`: the operator's reading of a gap that no single
    section covers. They are no sections and carry no `emqx:` prefix."""
    from jax.profiler import ProfileData

    prof = P.Profiler(trace_dir=str(tmp_path))
    monkeypatch.setattr(P, "default_profiler", prof)

    async def main():
        await asyncio.sleep(0.01)
        prof.arm(duration_s=20.0)  # mid run phase, as the REST handler does
        try:
            for _ in range(50):  # fifty iterations, always a callback ready
                with P.section("t.busy.work"):
                    time.sleep(0.002)
                await asyncio.sleep(0)
            await asyncio.sleep(0.05)  # nothing ready: the loop blocks
            for _ in range(10):
                await asyncio.sleep(0)
        finally:
            return prof.disarm("test")

    entry = asyncio.run(main(), loop_factory=P.loop_factory)
    assert isinstance(prof.budget, P.LoopBudget)
    (path,) = glob.glob(f"{entry['dir']}/**/*.xplane.pb", recursive=True)
    spans = {"owner:loop.busy": [], "owner:loop.select": [],
             "emqx:t.busy.work": []}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert len(spans["emqx:t.busy.work"]) == 50
    busy = sorted(spans["owner:loop.busy"])
    assert len(busy) == 2  # before the blocking select, and after it
    first, last = busy[0], busy[-1]
    # the first busy stretch holds all fifty iterations' work
    assert first[1] - first[0] >= 50 * 0.002 * 1e9
    assert all(first[0] <= s and e <= first[1]
               for s, e in spans["emqx:t.busy.work"])
    waits = [w for w in spans["owner:loop.select"] if w[1] - w[0] >= 0.04e9]
    assert len(waits) == 1 and first[1] <= waits[0][0] <= waits[0][1] <= last[0]


def test_arm_takes_the_python_tracer_on_request(tmp_path):
    p = P.Profiler(metrics=Metrics(), trace_dir=str(tmp_path))
    heard = []

    class Listener:
        def capture_started(self):
            heard.append(("started", p.capture is not None))

        def capture_stopping(self):
            heard.append(("stopping", p.capture is not None))

    p.listeners.append(Listener())
    try:
        assert p.arm(duration_s=5.0, python_tracer=True)["python_tracer"]
    finally:
        p.disarm("test")
    assert heard == [("started", True), ("stopping", True)]
    assert p.disarm() is None and len(heard) == 2


# -- series -----------------------------------------------------------------------


NEW_HISTOGRAMS = [
    f"profile.section.{name}{suffix}.seconds"
    for name in P.SECTIONS for suffix in ("", ".self")
] + [
    "owner.loop.select.seconds", "owner.loop.run.seconds",
    "owner.loop.other.seconds", "owner.loop.stall.seconds",
    "owner.gc.pause.seconds", "owner.gc.gen2.seconds",
]


def test_every_new_series_is_declared_and_exported_as_sum_and_count():
    from emqx_tpu.broker.metrics import COUNTER, GAUGE

    m = Metrics()
    for name in NEW_HISTOGRAMS:
        assert kind_of(name) == HISTOGRAM, name
        m.add(name, 1.5, 3)
    for name, kind in (("device.compile.in_launch.count", COUNTER),
                       ("device.compile.in_readback.count", COUNTER),
                       ("session.mqueue.dropped", COUNTER),
                       ("egress.writes", COUNTER),
                       ("device.hbm.peak.bytes", GAUGE)):
        assert kind_of(name) == kind, name
    for gone in ("ingest.device.idle.seconds",
                 "device.kernel.shape_route_step.seconds"):
        assert kind_of(gone) is None, gone
    text = prometheus_exposition(m.snapshot(), histograms=m.histograms())
    for name in NEW_HISTOGRAMS:
        prom = "emqx_" + name.replace(".", "_")
        assert f"{prom}_sum 1.5\n" in text, name
        assert f"{prom}_count 3\n" in text, name
    # the six stage names are sections too, and the contract's names stay
    assert set(P.STAGES) - {"queue_wait"} <= set(P.SECTIONS)


def test_histogram_add_keeps_sum_and_count_together():
    m = Metrics()
    m.observe("owner.loop.run.seconds", 0.25)
    m.add("owner.loop.run.seconds", 1.0, 4)
    h = m.histogram("owner.loop.run.seconds")
    assert (h.sum, h.count) == (1.25, 5)
    assert h.snapshot()["buckets"][-1] == (float("inf"), 5)


# -- satellites -------------------------------------------------------------------


def test_a_full_session_queue_counts_its_drops():
    """`max_inflight` 1, `max_mqueue` 2 and five deliveries: one in
    flight, two queued, two dropped — counted, and hooked."""
    from emqx_tpu.broker.channel import Channel

    class Sink:
        def send_packet(self, p):
            pass

        def close(self, reason):
            pass

    b = _mk_broker()
    dropped = []
    b.hooks.add("message.dropped", lambda msg, why: dropped.append(
        (msg.payload, why)))
    ch = Channel(b, cm=None, sink=Sink())
    ch.state = "connected"
    ch.client_id = "c1"
    ch.session = Session("c1", SessionConfig(max_inflight=1, max_mqueue=2))
    ch.session.on_dropped = ch._queue_dropped
    for i in range(5):
        ch.handle_deliver(
            Message(topic="t/1", payload=b"%d" % i, qos=1), pkt.SubOpts(qos=1))
    assert b.metrics.get("session.mqueue.dropped") == 2
    assert ch.session.mqueue.dropped == 2
    assert dropped == [(b"1", "queue_full"), (b"2", "queue_full")]
    # the connection-less park counts too
    ch.state = "disconnected"
    ch.handle_deliver(
        Message(topic="t/1", payload=b"5", qos=1), pkt.SubOpts(qos=1))
    assert b.metrics.get("session.mqueue.dropped") == 3


def test_the_allocator_peak_falls_back_to_the_running_maximum(monkeypatch):
    from emqx_tpu.observe import device_watch

    m = Metrics()
    watch = device_watch.DeviceWatch(m, registry={})
    readings = iter([(100, None), (700, None), (300, None), (300, 900)])
    monkeypatch.setattr(device_watch, "hbm_bytes", lambda: next(readings))
    for want_live, want_peak in ((100, 100), (700, 700), (300, 700),
                                 (300, 900)):
        got = watch.poll()
        assert got["hbm_bytes"] == want_live
        assert m.gauge("device.hbm.bytes") == want_live
        assert m.gauge("device.hbm.peak.bytes") == want_peak
        assert got["hbm_peak_bytes"] == want_peak


def test_the_cpu_backend_reports_live_bytes_and_no_peak():
    import jax.numpy as jnp

    from emqx_tpu.observe.device_watch import hbm_bytes

    keep = jnp.ones((256, 256))
    live, peak = hbm_bytes()
    assert live >= keep.nbytes
    assert peak is None or peak >= live


# -- coarsened entries --------------------------------------------------------------


class _Reader:
    def __init__(self, chunks):
        self.chunks = list(chunks)

    async def read(self, n):
        return self.chunks.pop(0) if self.chunks else b""


class _Writer:
    def __init__(self):
        self.writes = []

    def get_extra_info(self, key):
        return ("127.0.0.1", 1)

    def write(self, data):
        self.writes.append(bytes(data))

    def writelines(self, segs):
        self.writes.append(b"".join(bytes(s) for s in segs))

    async def drain(self):
        pass

    def close(self):
        pass

    async def wait_closed(self):
        pass


def test_a_chunk_s_acks_are_one_section_and_one_write_batch(
        metrics, monkeypatch):
    """Three PUBACKs in one read chunk: one `ingress.decode` (three
    packets), one `channel.ack_in` (three acks) and inside it one
    `egress.send` for the three replacement PUBLISHes (serialised and
    appended: three entries); the chunk's end is one more `egress.send`
    with no entries, the one socket write that carries all three."""
    from emqx_tpu.broker.channel import ChannelConfig
    from emqx_tpu.broker.cm import ChannelManager
    from emqx_tpu.mqtt.frame import serialize
    from emqx_tpu.transport.connection import Connection

    acks = b"".join(
        serialize(_puback(pid), pkt.MQTT_V4) for pid in (1, 2, 3))
    writer = _Writer()
    b = _mk_broker()
    conn = Connection(b, ChannelManager(b), _Reader([acks]), writer,
                      ChannelConfig())
    ch = conn.channel
    ch.state = "connected"
    ch.client_id = "c1"
    ch.session = Session("c1", SessionConfig(max_inflight=3, max_mqueue=16))
    for i in range(6):  # three in flight, three queued behind them
        ch.handle_deliver(
            Message(topic="t/1", payload=b"%d" % i, qos=1), pkt.SubOpts(qos=1))
    # no loop runs yet: the sink writes through, one write per packet
    assert len(writer.writes) == 3 and len(ch.session.mqueue) == 3
    assert b.metrics.get("egress.writes") == 3
    P.flush(Metrics())  # the set-up's own sends are not this test's
    opened = []
    real_begin = P.begin
    monkeypatch.setattr(
        P, "begin", lambda name, **ids: (opened.append(name),
                                         real_begin(name, **ids))[1])
    asyncio.run(conn.run())
    assert opened == ["ingress.decode", "channel.ack_in", "egress.send",
                      "egress.send"]
    assert len(ch.session.mqueue) == 0
    # one socket write for the chunk, holding the three frames in order
    assert len(writer.writes) == 4
    assert b.metrics.get("egress.writes") == 4
    assert writer.writes[3] == b"".join(
        serialize(pkt.Publish(topic="t/1", payload=b"%d" % i, qos=1,
                              packet_id=pid), pkt.MQTT_V4)
        for i, pid in ((3, 4), (4, 5), (5, 6)))
    assert b.metrics.get("packets.received") == 3
    P.flush(metrics)
    assert hist(metrics, "profile.section.ingress.decode.seconds")[1] == 3
    assert hist(metrics, "profile.section.channel.ack_in.seconds")[1] == 3
    # `egress.send` entries are packets: the flush adds time, no entry
    assert hist(metrics, "profile.section.egress.send.seconds")[1] == 3
    assert b.metrics.get("packets.sent") == 6
    ack_s, _ = hist(metrics, "profile.section.channel.ack_in.seconds")
    ack_self, _ = hist(metrics, "profile.section.channel.ack_in.self.seconds")
    send_s, _ = hist(metrics, "profile.section.egress.send.seconds")
    # the serialising `egress.send` is `channel.ack_in`'s child, the
    # socket write at the chunk's end is not
    assert 0 < ack_s - ack_self < send_s


def _puback(pid):
    p = pkt.PubAck(packet_id=pid)
    p.type = pkt.PUBACK
    return p


@pytest.mark.parametrize("async_authz", [False, True])
def test_publish_in_is_one_entry_and_ends_before_an_awaited_authorizer(
        metrics, async_authz):
    from emqx_tpu.broker.channel import Channel

    class Sink:
        def send_packet(self, p):
            pass

        def close(self, reason):
            pass

    b = _mk_broker()
    open_at_await = []

    async def slow_authz(ci, action, topic, acc):
        open_at_await.append(P.current_section())
        await asyncio.sleep(0.03)  # the loop runs others meanwhile
        return "allow"

    def quick_authz(ci, action, topic, acc):
        open_at_await.append(P.current_section())
        return "allow"

    b.hooks.add("client.authorize", quick_authz, priority=1)
    if async_authz:
        b.hooks.add("client.authorize", slow_authz)
    ch = Channel(b, cm=None, sink=Sink())
    ch.state = "connected"
    ch.client_id = "c1"
    ch.session = Session("c1", SessionConfig())
    p = pkt.Publish(topic="t/1", payload=b"x", qos=0)
    msg = asyncio.run(ch._publish_admit(p))
    assert msg is not None and msg.topic == "t/1"
    assert P.current_section() is None
    # the synchronous head of the chain runs inside the section, the
    # coroutine's body when it is awaited: outside
    assert open_at_await == ["channel.publish_in"] + [None] * async_authz
    P.flush(metrics)
    tot, n = hist(metrics, "profile.section.channel.publish_in.seconds")
    assert n == 1 and tot < 0.02  # the 30 ms await is nobody's section


def test_the_stages_read_their_sections_on_the_loop_s_own_thread(metrics):
    """The synchronous route (`dispatch_batch_folded` -> `route`) runs
    launch / device_execute / readback on the calling thread: on a
    thread whose loop has a LoopBudget the stage histograms still read
    each section's real seconds, every batch."""
    budget = P.LoopBudget()
    budget.enter_select()
    budget.exit_select()  # binds this thread
    b = _mk_broker(min_batch=8)
    for i in range(8):
        b.subscribe(f"s{i}", f"c{i}", f"t/{i}/+", pkt.SubOpts(),
                    lambda m, o: None)
    for _ in range(3):
        b.dispatch_batch_folded(
            [Message(topic=f"t/{i % 8}/x", payload=b"p") for i in range(16)])
        budget.enter_select()
        budget.exit_select()
    m = b.metrics
    P.flush(m)
    budget.enter_select()
    assert m.get("messages.routed.device") >= 48
    for stage in ("launch", "device_execute", "readback"):
        st = m.histogram(f"profile.stage.{stage}.seconds")
        sec = m.histogram(f"profile.section.{stage}.seconds")
        assert st.count == 3 and sec.count == 3, stage
        assert st.sum > 0.0 and st.sum == pytest.approx(sec.sum), stage
    run_s, _ = hist(m, "owner.loop.run.seconds")
    assert hist(m, "profile.section.launch.self.seconds")[0] < run_s
