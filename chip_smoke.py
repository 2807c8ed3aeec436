#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the TPU.

    python3 chip_smoke.py                 # the chip run (needs a TPU)
    python3 chip_smoke.py --mesh 2x2      # same, router.mesh_shape [2, 2]
    python3 chip_smoke.py --rehearse-cpu  # tiny CPU rehearsal, NOT a chip run

Drives `python -m emqx_tpu -c <generated config>` -> listener -> (workers)
fabric -> BatchIngest -> shape_route_step on the chip ->
_dispatch_device_results -> socket -> PUBACK, over TCP, with the in-repo
MQTT client, at BASELINE.json config 3 in the reference bench's own shape
(emqx_broker_bench.erl:25-33): 1,000 subscriber connections x 1,000 filters
`device/{i}/+/{j}/#` = 1,000,000 wildcard subscriptions, plus 100
`device/{i}/#` overlays; 48 QoS1 publishers, 64 B payloads, topics
`device/{i}/mid/{j}/leaf` with i Zipf(1.3) and j uniform from --seed.

One process per chip: this process never imports jax. The server child is
the only process that opens the device; its worker processes and this
driver stay off it. Every phase raises on failure; nothing here turns a
failed phase into exit 0. Wall times printed are a smoke's, not a
benchmark's. The last stdout line is one JSON object
`{"ok": true, "device": {"platform", "kind", "count"}}`.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import importlib.metadata
import json
import os
import re
import signal
import socket
import struct
import sys
import time
import urllib.request

import numpy as np

from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.compile_cache import cache_dir
from emqx_tpu.mqtt import codec_native
from emqx_tpu.mqtt.client import Client
from emqx_tpu.observe.profiler import DEVICE_PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# the deployment: BASELINE.json config 3 in the reference bench's shape
FULL = dict(subscribers=1000, filters_per_subscriber=1000, overlays=100,
            messages=65_536)
# --rehearse-cpu: control flow only (on-chip-measurement guide, section 1)
TINY = dict(subscribers=8, filters_per_subscriber=16, overlays=4,
            messages=16_320)  # 64 + 128 + ... + 8192: all but the top volley size
# A channel keeps at most 100 publishes riding the batch window
# (broker/channel.py PUB_PIPELINE_MAX, the reference's active-N): 16
# publishers could never have more than 1,600 pending, short of the 2,049 a
# batch in the top ingest bucket needs. 48 can.
N_PUBLISHERS = 48
PAYLOAD_BYTES = 64
# router.ingest_max_batch (4096) is the top ingest bucket; a batch that full
# only forms while an earlier launch is in flight, so the volleys that carry
# the bulk hold several buckets' worth of concurrent publishes
MAX_VOLLEY = 4 * 4096
# a cold compile of one route-step bucket can take minutes; the client's 5 s
# defaults would fail the run for the wrong reason
OP_TIMEOUT_S = 900.0
TIME_LIMIT_S = 1150.0  # the contract's 1200 s, less a margin
T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries() -> int:
    d = cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


# -- workload ---------------------------------------------------------------

def sub_filters(i: int, size: dict) -> list:
    """Subscriber i's filters. The overlay `device/{d}/#` is held by the
    subscriber half the table away from d, so no client ever holds two
    filters matching one topic (delivery per client stays a set question
    the trie answers, not a per-broker overlap policy)."""
    n = size["subscribers"]
    fs = [f"device/{i}/+/{j}/#" for j in range(size["filters_per_subscriber"])]
    d = (i - n // 2) % n
    if d < size["overlays"]:
        fs.append(f"device/{d}/#")
    return fs


def make_traffic(size: dict, seed: int):
    """-> (topics, payloads): i Zipf(1.3) clipped to the id space (the
    bench's `_zipf_ids`), j uniform, payload = 8-byte sequence number +
    random filler."""
    rng = np.random.default_rng(seed)
    n = size["messages"]
    ids = np.minimum(rng.zipf(1.3, size=n) - 1, size["subscribers"] - 1)
    nums = rng.integers(0, size["filters_per_subscriber"], size=n)
    filler = rng.integers(0, 256, size=(n, PAYLOAD_BYTES - 8), dtype=np.uint8)
    topics = [f"device/{i}/mid/{j}/leaf" for i, j in zip(ids, nums)]
    payloads = [struct.pack(">Q", k) + filler[k].tobytes() for k in range(n)]
    return topics, payloads


def volley_sizes(total: int) -> list:
    """Warm-up volleys climb the pow2 ingest buckets 64..4096, then
    MAX_VOLLEY-message volleys carry the rest."""
    sizes, left, v = [], total, 64
    while left > 0:
        take = min(v, left)
        sizes.append(take)
        left -= take
        v = min(v * 2, MAX_VOLLEY)
    return sizes


# -- the server child -------------------------------------------------------

class Server:
    """`python -m emqx_tpu -c <config>`: the only process that opens the
    chip. stdout is read line by line (backend, listeners, shutdown)."""

    def __init__(self, cfg_path: str, env: dict, log_path: str):
        self.cfg_path, self.env, self.log_path = cfg_path, env, log_path
        self.lines: list = []
        self.proc = None
        self._reader = None
        self._log = None

    async def start(self) -> None:
        self._log = open(self.log_path, "wb")
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "emqx_tpu", "-c", self.cfg_path,
            cwd=HERE, env=self.env, stdout=asyncio.subprocess.PIPE,
            stderr=self._log,
        )
        self._reader = asyncio.ensure_future(self._read())

    async def _read(self) -> None:
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                return
            self.lines.append(line.decode("utf-8", "replace").rstrip())

    async def wait_line(self, prefix: str, timeout: float) -> str:
        deadline = time.perf_counter() + timeout
        while True:
            for ln in self.lines:
                if ln.startswith(prefix):
                    return ln
            if self.proc.returncode is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before "
                    f"printing {prefix!r}; stderr tail:\n{self.log_tail()}"
                )
            if time.perf_counter() > deadline:
                raise TimeoutError(
                    f"server did not print {prefix!r} within {timeout:.0f}s; "
                    f"stderr tail:\n{self.log_tail()}"
                )
            await asyncio.sleep(0.1)

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def children(self) -> list:
        """pids whose parent is the server (its worker processes)."""
        out = []
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            except (OSError, IndexError, ValueError):
                continue  # raced an exiting process
            if ppid == self.proc.pid:
                out.append(int(pid))
        return out

    async def kill(self) -> None:
        """Unconditional teardown (finally-path): the smoke stops every
        process it started, whatever phase failed."""
        kids = self.children() if self.proc.returncode is None else []
        if self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()
        for pid in kids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        if self._reader is not None:
            self._reader.cancel()
        self._log.close()


def http_get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/v5{path}", timeout=120
    ) as r:
        return r.read()


async def arest(port: int, path: str) -> dict:
    return json.loads(await asyncio.to_thread(http_get, port, path))


async def device_batch_buckets(port: int) -> dict:
    """Device batches per pow2 ingest bucket: the `router.batch.size`
    histogram's bounds (metrics.SIZE_BUCKETS) are the padded batch sizes
    the route step compiles for (Bp = max(64, next_pow2(B)))."""
    text = (await asyncio.to_thread(
        http_get, port, "/prometheus/stats")).decode()
    cum = [(float(le), int(n)) for le, n in re.findall(
        r'^emqx_router_batch_size_bucket\{le="([0-9.e+]+)"\} (\d+)$',
        text, re.M)]
    out, prev = {}, 0
    for le, n in sorted(cum):
        if n - prev:
            out[max(64, int(le))] = out.get(max(64, int(le)), 0) + n - prev
        prev = n
    return out


def listener_of(k: int) -> str:
    """Connection k's listener: even on the in-process one, odd on the
    2-worker pool (subscribers by id, publishers by index)."""
    return "inproc" if k % 2 == 0 else "workers"


def delta(after: dict, before: dict, key: str):
    return after.get(key, 0) - before.get(key, 0)


async def connect(client: Client, port: int) -> None:
    """Workers bind their SO_REUSEPORT socket after the entrypoint prints
    the listener row: retry refused connects for a few seconds."""
    deadline = time.perf_counter() + 30.0
    while True:
        try:
            await client.connect("127.0.0.1", port, timeout=60.0)
            return
        except ConnectionRefusedError:
            if time.perf_counter() > deadline:
                raise
            await asyncio.sleep(0.25)


def compile_log(log_text: str) -> dict:
    """JAX_LOG_COMPILES lines from the server's stderr -> per-program
    {count, seconds}. A route step is keyed by its ingest bucket too: the
    rows of its topic-bytes operand (`uint8[B, max_bytes]`) in the
    "Compiling ... with global shapes" line that precedes it."""
    per: dict = collections.defaultdict(lambda: [0, 0.0])
    rows: dict = {}
    for m in re.finditer(
        r"Compiling (\S+) with global shapes and types (.*)"
        r"|Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec", log_text
    ):
        if m.group(1):
            b = re.search(r"uint8\[(\d+),\d+\]", m.group(2))
            rows[m.group(1)] = f" B={b.group(1)}" if b else ""
            continue
        key = m.group(3) + rows.get(m.group(3), "")
        per[key][0] += 1
        per[key][1] += float(m.group(4))
    return {k: {"count": c, "seconds": round(s, 2)} for k, (c, s) in per.items()}


# -- the run ----------------------------------------------------------------

async def run(args) -> dict:
    rehearsal = args.rehearse_cpu
    size = dict(TINY if rehearsal else FULL)
    reduced = {}
    for k in ("subscribers", "filters_per_subscriber", "messages"):
        v = getattr(args, k)
        if v is not None and v != size[k]:
            reduced[k] = {"target": size[k], "run": v, "limit": "argument"}
            size[k] = v
    size["overlays"] = min(size["overlays"], size["subscribers"])
    n_sub = size["subscribers"]
    n_filters = n_sub * size["filters_per_subscriber"] + size["overlays"]

    os.makedirs(OUT_DIR, exist_ok=True)
    ports = {"inproc": free_port(), "workers": free_port(), "rest": free_port()}
    cfg = {
        "listeners": [
            {"name": "inproc", "bind": "127.0.0.1", "port": ports["inproc"]},
            {"name": "pool", "bind": "127.0.0.1", "port": ports["workers"],
             "workers": 2},
        ],
        "dashboard": {"bind": "127.0.0.1", "port": ports["rest"]},
        # a Zipf-hot subscriber takes ~1/4 of every volley: its queue must
        # hold one volley (deployment setting; the 1000 default would drop)
        "session": {"max_mqueue": MAX_VOLLEY},
    }
    if args.mesh:
        cfg["router"] = {"mesh_shape": args.mesh_shape}
    cfg_path = os.path.join(OUT_DIR, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f, indent=1)

    env = dict(os.environ)
    env["JAX_LOG_COMPILES"] = "1"  # per-program compile seconds on stderr
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        if args.mesh:
            n_dev = int(np.prod(args.mesh_shape))
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={n_dev}"
            ).strip()

    say(f"{'CPU REHEARSAL (not a chip run)' if rehearsal else 'chip run'}: "
        f"{n_sub} subscribers x {size['filters_per_subscriber']} filters "
        f"+ {size['overlays']} overlays = {n_filters} subscriptions, "
        f"{size['messages']} messages, seed {args.seed}"
        + (f", mesh {args.mesh}" if args.mesh else ""))
    say(f"native codec built: {codec_native.available}; compile cache "
        f"{cache_dir()} ({cache_entries()} entries before)")

    server = Server(cfg_path, env, os.path.join(OUT_DIR, "server.stderr.log"))
    t_spawn = time.perf_counter()
    await server.start()
    try:
        return await drive(args, size, reduced, n_filters, ports, server,
                           t_spawn)
    finally:
        await server.kill()


async def drive(args, size, reduced, n_filters, ports, server, t_spawn):
    rehearsal = args.rehearse_cpu
    n_sub = size["subscribers"]

    # phase 1: the backend, named by the server before anything else
    line = await server.wait_line("emqx_tpu backend ", OP_TIMEOUT_S)
    m = re.match(r"emqx_tpu backend (\S+) \((.*)\) x(\d+)$", line)
    if m is None:
        raise RuntimeError(f"unparseable backend line: {line!r}")
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    say(f"server backend: {device}")
    if device["platform"] != "tpu" and not rehearsal:
        raise SystemExit(
            f"chip_smoke: no TPU — the server found platform "
            f"{device['platform']!r} ({device['kind']}). A CPU backend is "
            "never a pass; --rehearse-cpu is the explicit tiny rehearsal."
        )
    if not rehearsal and not any(
        sub in device["kind"].lower() for sub, _ in DEVICE_PEAKS
    ):
        raise RuntimeError(
            f"device_kind {device['kind']!r} is not in the peak table "
            "(emqx_tpu/observe/profiler.py DEVICE_PEAKS)"
        )

    # the reference, built while the server warms up: the CPU trie (no jax)
    trie = TopicTrie()
    owner = {}
    for i in range(n_sub):
        for flt in sub_filters(i, size):
            trie.insert(flt)
            owner[flt] = i
    if len(trie) != n_filters:
        raise RuntimeError(f"reference holds {len(trie)} != {n_filters}")
    topics, payloads = make_traffic(size, args.seed)

    await server.wait_line("emqx_tpu mgmt api on ", OP_TIMEOUT_S)
    start_s = time.perf_counter() - t_spawn
    fp = (await arest(ports["rest"], "/profile"))["fingerprint"]
    versions = {"jax": fp["jax"], "jaxlib": fp["jaxlib"],
                "libtpu": importlib.metadata.version("libtpu")}
    say(f"server up in {start_s:.1f}s (includes the start-up warm-up "
        f"compile); versions {versions}")

    # phase 2: connections, split across both listeners
    subs = [Client(client_id=f"smoke-sub-{i}", keepalive=0)
            for i in range(n_sub)]
    pubs = [Client(client_id=f"smoke-pub-{k}", keepalive=0)
            for k in range(N_PUBLISHERS)]
    for lo in range(0, n_sub, 50):
        await asyncio.gather(*[
            connect(subs[i], ports[listener_of(i)])
            for i in range(lo, min(lo + 50, n_sub))
        ])
    await asyncio.gather(*[connect(pubs[k], ports[listener_of(k)])
                           for k in range(N_PUBLISHERS)])
    workers = server.children()
    for pid in workers:
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
        if "jaxlib" in maps or "libtpu" in maps:
            raise RuntimeError(
                f"worker pid {pid} loaded jaxlib/libtpu: only the server "
                "may open the chip"
            )
    say(f"{n_sub} subscribers + {N_PUBLISHERS} publishers connected "
        f"(even ids on the in-process listener, odd on the 2-worker "
        f"pool); {len(workers)} worker processes, none loaded jaxlib")

    # phase 3: load the table over sockets, one multi-filter SUBSCRIBE each
    # (the 1 Hz DeviceWatch poll has counted the start-up compiles by now)
    await asyncio.sleep(1.5)
    hp0 = await arest(ports["rest"], "/metrics/hotpath")
    t_load = time.perf_counter()
    gate = asyncio.Semaphore(8)

    async def subscribe(i: int) -> int:
        fs = sub_filters(i, size)
        async with gate:
            ack = await subs[i].subscribe(fs, qos=1, timeout=OP_TIMEOUT_S)
        bad = [rc for rc in ack.reason_codes if rc != 1]
        if bad or len(ack.reason_codes) != len(fs):
            raise RuntimeError(f"subscriber {i}: SUBACK {ack.reason_codes[:8]}")
        return len(fs)

    loaded = sum(await asyncio.gather(*[subscribe(i) for i in range(n_sub)]))
    load_s = time.perf_counter() - t_load
    stats = await arest(ports["rest"], "/stats")
    if loaded != n_filters or stats["subscriptions.count"] != n_filters:
        raise RuntimeError(
            f"loaded {loaded}, broker holds {stats['subscriptions.count']}, "
            f"expected {n_filters}"
        )
    say(f"table loaded: {loaded} subscriptions over sockets in {load_s:.1f}s")

    # phase 4: QoS1 volleys; every publish carries an explicit timeout
    expected = [collections.Counter() for _ in range(n_sub)]
    for k, topic in enumerate(topics):
        for flt in trie.match(topic):
            expected[owner[flt]][k] += 1
    want_total = sum(sum(c.values()) for c in expected)
    m0 = await arest(ports["rest"], "/metrics")
    volleys = []
    sent = 0
    puback_by_listener = {"inproc": 0, "workers": 0}
    budget_hit = False
    first_upload = None
    for n in volley_sizes(size["messages"]):
        if time.perf_counter() - T0 > args.budget_s:
            budget_hit = True
            break
        before = await arest(ports["rest"], "/metrics")
        t_v = time.perf_counter()
        acks = await asyncio.gather(*[
            pubs[k % N_PUBLISHERS].publish(
                topics[k], payloads[k], qos=1, timeout=OP_TIMEOUT_S
            )
            for k in range(sent, sent + n)
        ])
        v_s = time.perf_counter() - t_v
        if len(acks) != n or any(a is None for a in acks):
            raise RuntimeError(f"volley of {n}: missing PUBACKs")
        for k in range(sent, sent + n):
            puback_by_listener[listener_of(k % N_PUBLISHERS)] += 1
        sent += n
        after = await arest(ports["rest"], "/metrics")
        volleys.append({
            "size": n, "seconds": round(v_s, 2),
            "routed_device": delta(after, before, "messages.routed.device"),
            "device_fallback": delta(
                after, before, "messages.routed.device_fallback"),
            "compiles": delta(after, before, "device.compile.count"),
        })
        say(f"volley {len(volleys):2d}: {volleys[-1]}")
        if first_upload is None and volleys[-1]["routed_device"] > 0:
            # the first volley the device served paid the first full
            # upload of the loaded table: the waterfall's prepare stage
            prep = (await arest(ports["rest"], "/metrics/hotpath"))[
                "profile"]["waterfall"]["prepare"]
            first_upload = {
                "volley_s": volleys[-1]["seconds"],
                "prepare_launches": prep["count"],
                "prepare_s": round(prep["mean"] * prep["count"], 2),
            }
    if budget_hit:
        reduced["messages"] = {
            "target": size["messages"], "run": sent,
            "limit": f"--budget-s {args.budget_s:.0f} reached",
        }
        for c in expected:  # the reference covers what was actually sent
            for k in [k for k in c if k >= sent]:
                del c[k]
        want_total = sum(sum(c.values()) for c in expected)
    if sent == 0:
        raise RuntimeError("time budget spent before the first volley")

    # phase 5: every delivery, exactly — per-subscriber multisets against
    # the CPU-trie reference
    def received() -> int:
        return sum(c.messages.qsize() for c in subs)

    last, last_t = -1, time.perf_counter()
    while received() < want_total:
        if received() != last:
            last, last_t = received(), time.perf_counter()
        elif time.perf_counter() - last_t > 120.0:
            raise RuntimeError(
                f"deliveries stalled at {last} of {want_total}"
            )
        await asyncio.sleep(0.2)
    await asyncio.sleep(1.0)  # anything beyond the reference would be a bug
    delivered = 0
    dlv_by_listener = {"inproc": 0, "workers": 0}
    for i, c in enumerate(subs):
        got = collections.Counter()
        while not c.messages.empty():
            p = c.messages.get_nowait()
            k = struct.unpack(">Q", p.payload[:8])[0]
            if p.topic != topics[k] or p.payload != payloads[k]:
                raise RuntimeError(f"subscriber {i}: corrupt delivery {k}")
            got[k] += 1
        if got != expected[i]:
            miss = sum((expected[i] - got).values())
            extra = sum((got - expected[i]).values())
            raise RuntimeError(
                f"subscriber {i}: {miss} missing, {extra} unexpected "
                f"deliveries vs the CPU-trie reference"
            )
        delivered += sum(got.values())
        dlv_by_listener[listener_of(i)] += sum(got.values())
    say(f"published {sent}, PUBACKed {sent}, delivered {delivered} == "
        f"reference {want_total}, per-subscriber multisets exact")
    if min(puback_by_listener.values()) == 0 or min(
        dlv_by_listener.values()
    ) == 0:
        raise RuntimeError(
            f"a listener carried no traffic: pubacks {puback_by_listener}, "
            f"deliveries {dlv_by_listener}"
        )

    # phase 6: the device really served it (housekeeping polls at 1 Hz)
    await asyncio.sleep(2.5)
    m1 = await arest(ports["rest"], "/metrics")
    hp = await arest(ports["rest"], "/metrics/hotpath")
    routed_dev = delta(m1, m0, "messages.routed.device")
    routed_fb = delta(m1, m0, "messages.routed.device_fallback")
    routed = {"device": routed_dev, "device_fallback": routed_fb,
              "cpu_small_batch": sent - routed_dev - routed_fb}
    must_be_zero = [
        "messages.routed.device_fallback", "degrade.fallback.batches",
        "degrade.trips.device", "degrade.retries", "degrade.state.device",
        "device.warmup.failed", "ingest.launch.errors",
        "ingest.dispatch.errors", "ingest.shed", "slo.shed",
        "messages.dispatch_error", "delivery.errors", "messages.dropped",
        "fabric.flush.errors", "fabric.parked.dropped",
    ]
    nonzero = {k: m1[k] for k in must_be_zero if m1.get(k, 0) != 0}
    if not rehearsal and m1.get("provenance.proxy") != 0:
        nonzero["provenance.proxy"] = m1.get("provenance.proxy")
    if nonzero:
        raise RuntimeError(f"counters that must be zero: {nonzero}")
    if routed_dev < 0.9 * sent:
        raise RuntimeError(
            f"only {routed_dev} of {sent} publishes routed on the device "
            f"(< 90%): {routed}"
        )
    if not m1.get("device.hbm.bytes", 0) > 0:
        raise RuntimeError("device.hbm.bytes is not > 0")
    if hp["profile"]["proxy"] != rehearsal:
        raise RuntimeError(f"hotpath proxy flag {hp['profile']['proxy']}")
    # reported, not gated: which buckets a volley lands in depends on how
    # far the publishers run ahead of the server
    buckets = await device_batch_buckets(ports["rest"])
    fabric = {k: hp["fabric"][k] for k in (
        "slab_pub_records", "slab_dlv_records")}
    if min(fabric.values()) == 0:
        raise RuntimeError(f"worker fabric carried no records: {fabric}")
    mesh = None
    if args.mesh:
        mesh = hp["mesh"]
        want = int(np.prod(args.mesh_shape))
        dev_bytes = mesh["device_bytes"]
        # (a tiny rehearsal table fills lanes from slot 0 and leaves the
        # upper 'tp' slice empty: the fill gate is the chip run's)
        if (mesh["shape"] != args.mesh or mesh["shard_count"] != want
                or len(dev_bytes) != want or min(dev_bytes.values()) <= 0
                or not (rehearsal or mesh["shard_fill_min"] > 0)):
            raise RuntimeError(f"mesh placement: {mesh}")

    comp0, comp1 = hp0["device"], hp["device"]

    def comp_seconds(dev_block) -> float:
        c = dev_block["compile_ms"]
        return 0.0 if c is None else c["mean"] * c["count"] / 1e3

    setup = {
        "server_start_s": round(start_s, 1),
        "table_load_s": round(load_s, 1),
        "first_upload": first_upload,
        "compile_count_total": comp1["compile_count"],
        "compile_seconds_total": round(comp_seconds(comp1), 1),
        "compile_count_at_start": comp0["compile_count"],
        "compile_seconds_at_start": round(comp_seconds(comp0), 1),
        # reported, not gated: each distinct live-row count B slices its
        # own small readback program (models/router_model.py _readback)
        "compiles_after_warmup_volleys": sum(
            v["compiles"] for v in volleys if v["size"] == MAX_VOLLEY
        ),
    }

    # phase 7: SIGTERM -> clean "shutting down", exit 0, no worker left.
    # Every connection's teardown unsubscribes filter by filter, so a clean
    # stop at 1M subscriptions takes minutes: wait out the time limit.
    workers = server.children()
    t_stop = time.perf_counter()
    server.proc.send_signal(signal.SIGTERM)
    rc = await asyncio.wait_for(
        server.proc.wait(),
        max(120.0, TIME_LIMIT_S - (time.perf_counter() - T0)),
    )
    setup["clean_stop_s"] = round(time.perf_counter() - t_stop, 1)
    await asyncio.sleep(0.5)
    if rc != 0 or "shutting down" not in server.lines:
        raise RuntimeError(
            f"server exit rc={rc}, stdout tail {server.lines[-3:]}; "
            f"stderr tail:\n{server.log_tail()}"
        )
    left = [pid for pid in workers if os.path.exists(f"/proc/{pid}")]
    if left:
        raise RuntimeError(f"worker processes left behind: {left}")
    say("SIGTERM: clean 'shutting down', exit 0, no worker left behind")

    per_program = compile_log(server.log_tail(n=1 << 26))
    summary = {
        "chip_run": not rehearsal,
        "device": device,
        "versions": versions,
        "native_codec_built": codec_native.available,
        "subscriptions_loaded": loaded,
        "published": sent, "pubacked": sent, "delivered": delivered,
        "by_listener": {"pubacks": puback_by_listener,
                        "deliveries": dlv_by_listener, "fabric": fabric},
        "routed": routed,
        "sub_table": {k: hp["sub_table"].get(k)
                      for k in ("mode", "bytes", "rep_flips")},
        "segment": {k: hp["segment"].get(k)
                    for k in ("compact_runs", "compact_merged", "hot_fill")},
        # bucket 64 needs a batch of exactly min_tpu_batch rows: the
        # server's own start-up warm-up is what crosses it
        "device_batches_per_ingest_bucket": buckets,
        "hbm_bytes": m1["device.hbm.bytes"],
        "mesh": mesh,
        "setup_wall_s (a smoke's, not a benchmark's)": setup,
        "compile_cache": {"dir": cache_dir(), "entries_after": cache_entries()},
        "compile_seconds_per_program": dict(sorted(
            per_program.items(), key=lambda kv: -kv[1]["seconds"])[:24]),
        "reduced": reduced,
    }
    print(json.dumps(summary, indent=1), flush=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({**summary, "volleys": volleys,
                   "compile_seconds_all_programs": per_program}, f, indent=1)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="serve on a device mesh, e.g. 2x2 on a 4-chip host")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the control flow; its "
                    "output is marked as not a chip run")
    ap.add_argument("--budget-s", type=float, default=900.0,
                    help="stop sending volleys past this many seconds (the "
                    "cut is printed under `reduced`)")
    for k in ("subscribers", "filters_per_subscriber", "messages"):
        ap.add_argument(f"--{k.replace('_', '-')}", type=int, default=None,
                        help="cut of scale; printed under `reduced`")
    args = ap.parse_args(argv)
    args.mesh_shape = [int(x) for x in args.mesh.split("x")] if args.mesh else None
    summary = asyncio.run(run(args))
    if "jax" in sys.modules:
        raise RuntimeError("chip_smoke's own process imported jax")
    last = {"ok": True, "device": summary["device"]}
    if not summary["chip_run"]:
        last["chip_run"] = False
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
