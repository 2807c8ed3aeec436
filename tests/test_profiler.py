"""Performance provenance & device profiling plane (PR 16).

Pins the observability contracts docs/observability.md ("Profiling &
provenance") names:

- the launch waterfall: every stage of the serving path (prepare ->
  queue-wait -> launch -> device-execute -> readback -> host-dispatch)
  records into its own histogram on the REAL BatchIngest path, and the
  stage means tile the measured enqueue->settle latency;
- the fused session stage rides the serving launch (its own counter
  and the launch's stage series say so);
- the disarmed profiler is structurally zero (racetrack discipline):
  no capture object, no trace directory, no series, no tick work;
- the REST arm/capture/disarm lifecycle with a REAL on-disk byte
  budget (an over-budget capture is deleted, not kept);
- the static cost harvest covers the ENTIRE contract registry via the
  audit's own config-matrix recipes;
- hardware fingerprints are stable within a process and proxy-tagged
  off TPU.
"""

import asyncio
import functools
import json
import os

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.broker.session_store import SessionStore
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import provenance
from emqx_tpu.observe.profiler import (
    STAGES,
    Profiler,
    harvest_cost,
    roofline_summary,
    waterfall,
)
from emqx_tpu.ops.contract import REGISTRY


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        asyncio.run(asyncio.wait_for(fn(*a, **kw), timeout=120))

    return wrapper


def _mk_broker(min_batch=1):
    return Broker(router=Router(min_tpu_batch=min_batch), hooks=Hooks())


def _sub_n(b, n, sink=None):
    for i in range(n):
        b.subscribe(
            f"s{i}", f"c{i}", f"t/{i}/+", pkt.SubOpts(),
            (lambda m, o: sink.append(m.topic)) if sink is not None
            else (lambda m, o: None),
        )


def _msgs(n, qos=0):
    return [
        Message(topic=f"t/{i % 8}/x", payload=b"p", qos=qos,
                from_client=f"pub{i}")
        for i in range(n)
    ]


# -- launch waterfall on the real ingest path --------------------------------


class TestWaterfall:
    @async_test
    async def test_stage_sums_tile_the_settle_latency(self):
        """Every waterfall stage records on the real enqueue->settle
        path, and the per-message stage means reconstruct the measured
        `ingest.settle.seconds` mean to within tolerance: the waterfall
        is an attribution of the SLO latency, not a parallel universe
        of timers."""
        b = _mk_broker(min_batch=8)
        _sub_n(b, 8)
        ing = BatchIngest(b, max_batch=64, window_us=500)
        b.ingest = ing
        ing.start()
        # warm batch: the jit compile lands outside the measured window
        await b.apublish_enqueue(Message(topic="t/0/w", payload=b"w"))
        await asyncio.sleep(0.2)
        rs = [await b.apublish_enqueue(m) for m in _msgs(256)]
        await asyncio.gather(*[r for r in rs if not isinstance(r, int)])
        await ing.stop()
        m = b.metrics
        wf = waterfall(m)
        assert set(wf) == set(STAGES)
        for stage in STAGES:
            assert wf[stage] is not None, f"stage {stage} never observed"
            assert wf[stage]["count"] > 0
            assert wf[stage]["p99"] >= wf[stage]["p50"] >= 0.0
        settle = m.histogram("ingest.settle.seconds")
        assert settle is not None and settle.count > 0
        settle_mean = settle.sum / settle.count
        # queue_wait is per-message; the remaining stages are per-batch
        # and shared by every message that rode the batch — their means
        # add directly onto the per-message queue wait
        stage_sum = sum(wf[s]["mean"] for s in STAGES)
        # tolerant tiling: executor hops / loop scheduling live in the
        # gaps, and histogram means are bucket-interpolated
        assert stage_sum <= settle_mean * 2.0 + 0.05, (
            stage_sum, settle_mean)
        assert stage_sum >= settle_mean * 0.2, (stage_sum, settle_mean)


    @async_test
    async def test_stages_count_per_batch_and_agree_with_their_sections(self):
        """The five busy stages are opened through the section helper and
        keep their meaning: one observation per device batch (queue_wait
        one per message), and the stage's seconds ARE its section's."""
        from emqx_tpu.observe import profiler as P

        P.flush(Metrics())  # what earlier tests accumulated
        b = _mk_broker(min_batch=8)
        _sub_n(b, 8)
        ing = BatchIngest(b, max_batch=64, window_us=500)
        b.ingest = ing
        ing.start()
        rs = [await b.apublish_enqueue(m) for m in _msgs(256)]
        await asyncio.gather(*[r for r in rs if not isinstance(r, int)])
        await ing.stop()
        m = b.metrics
        P.flush(m)
        batches = m.histogram("ingest.batch.size").count
        device_rows = m.get("messages.routed.device")
        assert batches >= 1 and device_rows >= 8
        per_batch = [s for s in STAGES if s != "queue_wait"]
        counts = {
            s: m.histogram(f"profile.stage.{s}.seconds").count
            for s in per_batch
        }
        assert len(set(counts.values())) == 1, counts
        assert 1 <= counts["launch"] <= batches
        assert m.histogram("profile.stage.queue_wait.seconds").count == 256
        for s in per_batch:
            stage = m.histogram(f"profile.stage.{s}.seconds")
            sec = m.histogram(f"profile.section.{s}.seconds")
            assert sec is not None and sec.count == stage.count, s
            assert sec.sum == pytest.approx(stage.sum), s
        # children nest: the executor's three stages have no children,
        # host_dispatch's self time is at most its total
        hd = m.histogram("profile.section.host_dispatch.seconds")
        hd_self = m.histogram("profile.section.host_dispatch.self.seconds")
        assert 0.0 < hd_self.sum <= hd.sum


# -- the fused session stage still rides the serving launch ------------------


class TestSessionRide:
    @async_test
    async def test_session_ride_counts_on_the_serving_launch(self):
        """The session-ack stage fuses into the serving launch: acks
        never pay a launch of their own (`session.ack.rides`), and the
        launch's stage series move once per launch with the rider in."""
        b = _mk_broker()
        store = SessionStore(metrics=b.metrics, capacity=256,
                             sweep_slots=64, retry_interval=30.0)
        b.session_store = store
        sess = Session("c0", SessionConfig(), store=store)
        sent = []

        def deliver(m, o):
            sent.extend(sess.deliver(m, o))

        b.subscribe("c0", "c0", "t/#", pkt.SubOpts(qos=1), deliver)
        await b.adispatch_batch_folded(_msgs(8, qos=1))
        for p in sent[:4]:
            sess.puback(p.packet_id)
        await b.adispatch_batch_folded(_msgs(8, qos=1))  # rider batch
        assert b.metrics.get("session.ack.rides") >= 1
        launches = b.metrics.histogram("profile.stage.launch.seconds")
        assert launches is not None and launches.count == 2


# -- disarmed profiler: structurally zero ------------------------------------


class TestDisarmedStructuralZero:
    def test_disarmed_is_inert(self, tmp_path):
        """Racetrack discipline: DISARMED means no capture object, no
        trace directory on disk, a no-op tick, and a None disarm —
        there is nothing for the hot path to even check."""
        m = Metrics()
        trace_dir = str(tmp_path / "captures")
        p = Profiler(metrics=m, trace_dir=trace_dir)
        assert p.capture is None
        assert p.armed is False
        assert not os.path.exists(trace_dir)  # nothing made eagerly
        p.tick()  # no-op while disarmed
        assert p.disarm() is None
        assert not os.path.exists(trace_dir)
        assert m.get("profile.captures") == 0
        snap = p.snapshot()
        assert snap["armed"] is False
        assert snap["capture"] is None
        assert snap["history"] == []
        assert snap["cost_harvested"] is False
        assert p.cost_cached() is None


# -- capture lifecycle + file budget -----------------------------------------


class TestCaptureLifecycle:
    def test_arm_capture_disarm_with_budget_kept(self, tmp_path):
        import jax
        import jax.numpy as jnp

        m = Metrics()
        p = Profiler(metrics=m, trace_dir=str(tmp_path))
        try:
            info = p.arm(duration_s=20.0)
            assert p.armed and os.path.isdir(info["dir"])
            with pytest.raises(RuntimeError):
                p.arm()  # one capture at a time (process-global trace)
            jax.block_until_ready(
                jnp.ones((64, 64)) @ jnp.ones((64, 64))
            )
        finally:
            entry = p.disarm("test")
        assert entry is not None
        assert entry["bytes"] > 0, "capture files must be non-empty"
        assert entry["deleted"] is False
        assert os.path.isdir(entry["dir"])
        assert p.capture is None
        assert m.get("profile.captures") == 1
        assert p.snapshot()["history"][-1]["reason"] == "test"

    def test_over_budget_capture_is_deleted(self, tmp_path):
        import jax
        import jax.numpy as jnp

        p = Profiler(metrics=Metrics(), trace_dir=str(tmp_path))
        try:
            info = p.arm(duration_s=20.0, max_bytes=1)  # clamps to 64 KiB
            assert info["max_bytes"] == 1 << 16
            jax.block_until_ready(
                jnp.ones((128, 128)) @ jnp.ones((128, 128))
            )
        finally:
            entry = p.disarm("budget-test")
        assert entry is not None and entry["bytes"] > 1 << 16
        assert entry["over_budget"] is True and entry["deleted"] is True
        assert not os.path.exists(entry["dir"]), (
            "over-budget captures must be removed from disk"
        )

    def test_tick_auto_disarms_past_deadline(self, tmp_path):
        import time as _time

        p = Profiler(metrics=Metrics(), trace_dir=str(tmp_path))
        p.arm(duration_s=0.1)
        p.tick(now=_time.time() + 5.0)  # housekeeping past the deadline
        assert p.capture is None
        hist = p.snapshot()["history"]
        assert hist and hist[-1]["reason"] == "deadline"

    @async_test
    async def test_rest_arm_capture_disarm_lifecycle(self, tmp_path):
        import aiohttp

        from emqx_tpu.app import BrokerApp
        from emqx_tpu.config.schema import load_config

        app = BrokerApp(load_config({
            "listeners": [{"port": 0, "bind": "127.0.0.1"}],
            "dashboard": {"port": 0, "bind": "127.0.0.1"},
            "observe": {"profile_trace_dir": str(tmp_path)},
        }))
        await app.start()
        try:
            api = f"http://127.0.0.1:{app.mgmt_server.port}/api/v5"
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{api}/profile") as r:
                    assert r.status == 200
                    snap = await r.json()
                    assert snap["armed"] is False
                    assert snap["fingerprint"]["proxy"] is True
                    assert set(snap["waterfall"]) == set(STAGES)
                    # the section table rides beside the waterfall
                    assert isinstance(snap["sections"], dict)
                    assert set(snap["loop"]) >= {
                        "select_s", "run_s", "other_s", "stall_s",
                        "gc_pause_s"}
                    assert snap["stalls"] == []
                async with s.post(
                    f"{api}/profile", json={"duration_s": 20.0}
                ) as r:
                    assert r.status == 201
                    info = await r.json()
                    assert info["dir"].startswith(str(tmp_path))
                    assert info["python_tracer"] is False  # the default
                async with s.post(f"{api}/profile", json={}) as r:
                    assert r.status == 400  # already armed
                # the armed state is visible in the hotpath block too
                async with s.get(f"{api}/metrics/hotpath") as r:
                    hp = await r.json()
                    assert hp["profile"]["capture_armed"] is True
                    assert hp["profile"]["proxy"] is True
                    assert hp["profile"]["fingerprint"]
                async with s.delete(f"{api}/profile") as r:
                    assert r.status == 200
                    entry = await r.json()
                    assert entry["reason"] == "rest"
                async with s.delete(f"{api}/profile") as r:
                    assert r.status == 204  # idempotent when disarmed
                async with s.get(f"{api}/profile") as r:
                    snap = await r.json()
                    assert snap["armed"] is False
                    assert len(snap["history"]) == 1
        finally:
            await app.stop()


# -- static cost harvest over the contract matrix ----------------------------


class TestCostHarvest:
    def test_harvest_covers_entire_contract_registry(self):
        """Every @device_contract kernel compiles through the audit's
        own harness recipes and yields a roofline row — a kernel the
        harvest cannot reach lands in `skipped`, never silently."""
        # populate the registry exactly as the audit does
        import emqx_tpu.models.router_model  # noqa: F401
        import emqx_tpu.ops.session_table  # noqa: F401
        import emqx_tpu.parallel.mesh  # noqa: F401

        assert len(REGISTRY) >= 12
        out = harvest_cost(max_configs_per_kernel=1)
        names = {r["kernel"] for r in out["rows"]}
        assert names == set(REGISTRY), (
            sorted(set(REGISTRY) - names), out["skipped"])
        for r in out["rows"]:
            assert r["flops"] >= 0.0
            assert r["bytes_accessed"] >= 0.0
            assert r["config"]
            # a CPU has no roofline: counts only, nothing rendered
            assert r["bound"] is None and r["attainable_flops"] is None
        assert out["proxy"] is True and out["peaks"] is None
        roof = roofline_summary(out)
        assert set(roof["kernels"]) == names
        assert roofline_summary(None) is None

    def test_profiler_caches_harvest(self):
        p = Profiler(metrics=Metrics())
        first = p.cost_harvest(max_configs_per_kernel=1)
        assert p.cost_cached() is first
        assert p.cost_harvest(max_configs_per_kernel=1) is first
        assert p.metrics.gauge("profile.cost.kernels") >= 12


# -- provenance fingerprints -------------------------------------------------


class TestProvenance:
    def test_fingerprint_is_stable_and_proxy_tagged(self):
        fp1 = provenance.fingerprint()
        fp2 = provenance.fingerprint()
        assert fp1 == fp2
        assert fp1 is not fp2  # callers get copies, not the cache
        for key in provenance.KEY_FIELDS:
            assert key in fp1, key
        # the tier-1 environment is never a TPU: proxy MUST be true
        assert fp1["platform"] != "tpu"
        assert fp1["proxy"] is True
        assert provenance.is_proxy() is True
        assert provenance.fingerprint_key(fp1) == \
            provenance.fingerprint_key(fp2)
        assert str(fp1["platform"]) in provenance.fingerprint_key(fp1)

    def test_resource_attrs(self):
        attrs = provenance.resource_attrs()
        assert attrs["hw.proxy"] is True
        assert attrs["hw.platform"] == provenance.fingerprint()["platform"]

    def test_span_exporter_carries_hw_resource_attrs(self, tmp_path):
        from emqx_tpu.observe.spans import OtlpFileExporter, Span

        path = str(tmp_path / "spans.jsonl")
        exp = OtlpFileExporter(path, flush_every=1)
        exp.export([Span(trace_id="t" * 32, span_id="s" * 16,
                         name="probe", start_ns=1, end_ns=2)])
        exp.flush()
        with open(path) as f:
            env = json.loads(f.readline())
        attrs = {
            a["key"]: a["value"]
            for a in env["resourceSpans"][0]["resource"]["attributes"]
        }
        assert attrs["service.name"] == {"stringValue": "emqx_tpu"}
        assert attrs["hw.proxy"] == {"boolValue": True}
        assert "hw.platform" in attrs and "hw.git_sha" in attrs
