#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the deployment the cell's configuration describes: one `python -m
emqx_tpu -c <generated config>` (the only process that opens the chip), or,
where the configuration's file has a `nodes` block, that many broker nodes,
each on a chip of its own and joined into one cluster. Loads the cell's table
over sockets from jax-free generator processes, warms up, measures for
--seconds, drains, kills the nodes, judges the run against the plain reference
and prints one JSON line. It fails, and never falls back, off a TPU or on a
device kind its peak table lacks.
--rehearse-cpu is the tiny CPU rehearsal of the control flow: marked as not a
chip run, it prints counts and no time, rate or share."""

import argparse
import glob
import importlib
import json
import multiprocessing
import os
import re
import shutil
import subprocess
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from harness import controls, loadgen, manifest_check, roofline, server, verify  # noqa: E402
from harness.reference import Matcher  # noqa: E402
from harness.traffic import Table  # noqa: E402

START_TIMEOUT_S = 900.0
JOIN_TIMEOUT_S = 120.0
ROUTES_TIMEOUT_S = 600.0
TRACE_MAX_S = 28.0  # the program caps a capture at 30 s


def say(msg):
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def fill(node, values):
    """The broker config's {placeholders} (ports, the work directory)."""
    if isinstance(node, dict):
        return {k: fill(v, values) for k, v in node.items()}
    if isinstance(node, list):
        return [fill(v, values) for v in node]
    if isinstance(node, str) and "{" in node:
        out = node.format(**values)
        return int(out) if out.isdigit() else out
    return node


PORTS = ("inproc", "pool", "rest")


def plan_nodes(config, work, environ, rehearsal, free_port=server.free_port):
    """The deployment as data: one entry per broker node with its name, ports,
    work directory, generated broker config, environment and files. Without a
    `nodes` block in the configuration's file: one unnamed node in `work`.
    With one, {"count": N, "name": "<template>", "env": {...}}: node i is
    named `name` with {node_index} = i, works in `work`/node<i>, draws a
    cluster port besides, gets `env` (its templates filled; the variables
    that pin it to one chip) unless this is the CPU rehearsal, and its
    `cluster.seeds` are written here from the list: node 0 has none, and
    every other node has node 0 first (the one it joins), then the rest (so
    that it holds every peer's address)."""
    block = config.get("nodes")
    plans = []
    for i in range(block["count"] if block else 1):
        ports = {k: free_port() for k in PORTS + (("cluster",) if block else ())}
        node_work = os.path.join(work, f"node{i}") if block else work
        values = {"work_dir": node_work, **{f"port_{k}": v for k, v in ports.items()}}
        if block:
            values["node_index"] = i
            values["node_name"] = block["name"].format(node_index=i)
        env = dict(environ)
        env.pop("BENCH_RUN", None)
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        elif block:
            env.update({k: v.format(**values) for k, v in block.get("env", {}).items()})
        plans.append({
            "name": values.get("node_name", ""), "ports": ports,
            "work": node_work, "broker": fill(config["broker"], values),
            "cfg_path": os.path.join(node_work, "broker.json"),
            "log_path": os.path.join(node_work, "server.stderr.log"), "env": env})
    for plan in plans[1:]:
        plan["broker"]["cluster"]["seeds"] = [
            {"node": p["name"], "host": p["broker"]["cluster"].get("bind", "127.0.0.1"),
             "port": p["ports"]["cluster"]}
            for p in plans[:1] + [q for q in plans[1:] if q is not plan]]
    return plans


def node_of(k, n):
    """The placement rule: connection k of a kind (subscribers, publishers)
    is on node k mod N. Numpy arrays of k as well."""
    return k % n


def place(k, plans, listener_split):
    """The port of connection k of a kind: on its node (`node_of`),
    listener (k div N) mod L of `listener_split`."""
    node = plans[node_of(k, len(plans))]
    return node["ports"][listener_split[k // len(plans) % len(listener_split)]]


class Generators:
    """The generator processes and their pipes."""

    def __init__(self):
        self.ctx = multiprocessing.get_context("spawn")
        self.procs = []  # (kind, process, pipe)

    def start(self, kind, target, spec):
        ours, theirs = self.ctx.Pipe()
        p = self.ctx.Process(target=target, args=(theirs, spec), daemon=True)
        p.start()
        theirs.close()
        self.procs.append((kind, p, ours))

    def pipes(self, kind):
        return [pipe for k, _, pipe in self.procs if k == kind]

    def gather(self, kind, what, timeout):
        """One `what` message from every process of `kind`."""
        out = []
        for pipe in self.pipes(kind):
            if not pipe.poll(timeout):
                raise TimeoutError(f"{kind} generator: no {what!r} in {timeout:.0f}s")
            msg = pipe.recv()
            if msg[0] != what:
                raise RuntimeError(f"{kind} generator: {msg!r:.300} (wanted {what!r})")
            out.append(msg)
        return out

    def send(self, kind, msg):
        for pipe in self.pipes(kind):
            pipe.send(msg)

    def stop(self):
        for _, p, pipe in self.procs:
            pipe.close()
            p.join(5.0)
            if p.is_alive():
                p.kill()
                p.join()


def split(items, n):
    return [items[i::n] for i in range(n)]


def run(args, hooks=None):
    """`hooks` is for the tests under benchmark/tests, which break the timed
    path underneath a rehearsal: `traffic(traffic)` may shorten waits,
    `config(config)` may give the configuration a `nodes` block,
    `subscriber_port(port)` may put a proxy before a listener,
    `cluster_port(port)` one before a node's cluster bus (the port its peers
    are told), `loaded(ports)` runs once the table is loaded, with node 0's
    ports."""
    hooks = hooks or {}
    manifest, faults = manifest_check.load_and_check(ROOT)
    if faults:
        raise SystemExit("BENCHMARK.json is not sound:\n  " + "\n  ".join(faults))
    if not os.path.isfile(os.path.join(ROOT, "emqx_tpu", "__main__.py")):
        raise SystemExit("benchmark: the program (emqx_tpu/) is not in this checkout")
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no cell {args.workload!r} in BENCHMARK.json")
    config = load_json("configs", cell["config"] + ".json")
    hooks.get("config", lambda c: None)(config)
    traffic = load_json("traffic", (args.traffic or cell["traffic"]) + ".json")
    rehearsal = args.rehearse_cpu
    if rehearsal:  # the tiny table cannot take the cell's load: its own, smaller
        traffic.update(traffic.get("rehearsal", {}))
    hooks.get("traffic", lambda t: None)(traffic)
    table_spec = config["rehearsal_table" if rehearsal else "table"]
    table = Table(table_spec, args.seed)

    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    plans = plan_nodes(config, work, os.environ, rehearsal)
    via = hooks.get("cluster_port", lambda port: port)
    for plan in plans:
        for seed in plan["broker"].get("cluster", {}).get("seeds", ()):
            seed["port"] = via(seed["port"])
        os.makedirs(plan["work"], exist_ok=True)
        with open(plan["cfg_path"], "w") as f:
            json.dump(plan["broker"], f, indent=1)
    dep = server.Deployment(ROOT, plans)
    gens = Generators()
    try:
        return drive(args, manifest, cell, config, traffic, table, dep, gens, hooks)
    finally:
        dep.kill()
        gens.stop()


def start(dep, cell, args):
    """Step 1: every node up on the backend it has to be on and, where there
    are several, joined and each on a chip of its own. -> (device, peaks)"""
    rehearsal = args.rehearse_cpu
    dep.start(START_TIMEOUT_S)
    found = []
    for n in dep.nodes:
        line = n.wait_line("emqx_tpu backend ", START_TIMEOUT_S)
        m = re.match(r"emqx_tpu backend (\S+) \((.*)\) x(\d+)$", line)
        if m is None:
            raise RuntimeError(f"{n.tag}unparseable backend line {line!r}")
        found.append({"platform": m.group(1), "kind": m.group(2),
                      "count": int(m.group(3))})
    device = {**found[0], "count": sum(d["count"] for d in found)}
    peaks = None
    if not rehearsal:
        clustered = len(found) > 1  # a node of a cluster is one chip
        if any(d["platform"] != "tpu" or d["kind"] != device["kind"]
               or (clustered and d["count"] != 1) for d in found) \
                or device["count"] < cell["chips"]:
            raise SystemExit(
                f"benchmark: cell {cell['name']} needs {cell['chips']} TPU chip(s)"
                f"{', one to a node' if clustered else ''}; the server found "
                f"{found if clustered else device}. No result is printed off the chip.")
        peaks = roofline.peaks_for(device["kind"])
    say(f"{'CPU REHEARSAL (not a chip run) ' if rehearsal else ''}"
        f"{cell['name']} seed {args.seed}: server backend {device}"
        + (f" on {len(found)} nodes" if len(found) > 1 else ""))
    for n in dep.nodes:
        n.wait_line("emqx_tpu mgmt api on ", START_TIMEOUT_S)
    if len(dep.nodes) > 1:
        dep.wait_joined(JOIN_TIMEOUT_S)
        held = [] if rehearsal else [server.device_files(n.proc.pid) for n in dep.nodes]
        if not all(held) or any(a & b for i, a in enumerate(held) for b in held[:i]):
            raise SystemExit(
                f"benchmark: every node has to hold a chip of its own; the nodes' "
                f"device files are {[sorted(h) for h in held]}. No result is printed.")
    return device, peaks


def workers_of(dep):
    """Each node's listener-pool workers; none may have opened the chip."""
    per_node = [n.children() for n in dep.nodes]
    for pid in sum(per_node, []):
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
        if "libtpu" in maps or "jaxlib" in maps:
            raise RuntimeError(f"worker {pid} loaded jaxlib/libtpu: only the "
                               "server may open the chip")
    return per_node


def wait_routes(dep, want, t_subscribe, timeout):
    """Step 2's second equality: every node's replica of the cluster's route
    table holds the `want` (filter, node) pairs the placement gives. Route
    replication runs behind the SUBACKs; on running out of time the error
    carries how far it got, which is the replication rate. The count walks
    the whole replica on the owner's loop, the one that applies the
    replication: so the next look comes after half the time the routes still
    missing would take at the rate so far, 30 s at the most."""
    t0 = time.monotonic()
    while True:
        dep.check_alive()
        got = [c["stats"]["routes.count"] for c in dep.cluster()]
        now = time.monotonic()
        if all(g == want for g in got):
            return now - t0
        if now - t0 > timeout:
            gc = [p.get("emqx_owner_gc_pause_seconds_sum") for p in dep.scrapes()]
            raise RuntimeError(
                f"route replication: every node should hold {want} routes; "
                f"{now - t0:.0f}s after the last SUBACK ({now - t_subscribe:.0f}s "
                f"after the first SUBSCRIBE) they hold {got}, "
                f"{[round(g / (now - t_subscribe), 1) for g in got]} routes/s per "
                f"node; RSS bytes {[server.rss_bytes(n.proc.pid) for n in dep.nodes]}, "
                f"owner.gc.pause seconds {gc}")
        rate = max(min(got), 1) / (now - t_subscribe)
        time.sleep(min(max(0.5 * (want - min(got)) / rate, 0.5), 30.0,
                       max(timeout - (now - t0), 0.0) + 0.1))


def drive(args, manifest, cell, config, traffic, table, dep, gens, hooks):
    rehearsal = args.rehearse_cpu
    n_sub, n_pub = table.n_sub, traffic["publishers"]
    device, peaks = start(dep, cell, args)
    nodes, plans = dep.nodes, dep.plans

    # generators: subscribers partitioned among processes; nodes, and on a
    # node its listeners, alternate (`place`)
    split_of = config["listener_split"]
    prefix = f"b{args.seed}"
    base = {"table": table.spec, "seed": args.seed, "prefix": prefix}
    t_up = time.monotonic() - T0
    sub_port = hooks.get("subscriber_port", lambda port: port)
    for part in split(list(range(n_sub)), traffic["subscriber_processes"]):
        gens.start("sub", loadgen.subscriber_main, {
            **base, "qos": traffic["qos"],
            "subs": [(s, sub_port(place(s, plans, split_of))) for s in part]})
    for part in split(list(range(n_pub)), traffic["publisher_processes"]):
        gens.start("pub", loadgen.publisher_main, {
            **base, "traffic": traffic,
            "pubs": [(c, place(c, plans, split_of)) for c in part]})

    # the reference is built while the generators connect and the table
    # loads: one trie over all receiver classes (Table: a plain subscriber, or
    # a `$share` group, owed each matching message once) with their real
    # filters, whatever node holds them (a cluster has to deliver exactly what
    # one broker would), and the (filter, node) pairs every node's replica of
    # the route table has to hold
    matcher, routes = Matcher(), set()
    for cls, conns, filters in table.classes():
        for flt in filters:
            matcher.insert(flt, cls)
            if len(nodes) > 1:
                routes.update((flt, node_of(s, len(nodes))) for s in conns)
    n_filters = table.n_filters()
    if matcher.count != table.n_class_filters():
        raise RuntimeError(f"reference holds {matcher.count} filters, not "
                           f"{table.n_class_filters()}")
    gens.gather("sub", "connected", START_TIMEOUT_S)
    gens.gather("pub", "connected", START_TIMEOUT_S)
    workers = workers_of(dep)
    t_conn = time.monotonic() - T0
    t_subscribe = time.monotonic()
    gens.send("sub", ("subscribe",))
    loaded = sum(msg[1] for msg in gens.gather("sub", "subscribed", START_TIMEOUT_S))
    held = [json.loads(server.rest(n.ports["rest"], "/stats"))["subscriptions.count"]
            for n in nodes]
    if loaded != n_filters or sum(held) != n_filters:
        raise RuntimeError(f"loaded {loaded}, broker holds {held}, expected {n_filters}")
    t_loaded = time.monotonic() - T0
    routes_wait_s = wait_routes(dep, len(routes), t_subscribe, ROUTES_TIMEOUT_S) \
        if len(nodes) > 1 else None
    hooks.get("loaded", lambda p: None)(nodes[0].ports)
    say(f"server up {t_up:.1f}s, {n_sub + n_pub} connections {t_conn:.1f}s, "
        f"{n_filters} subscriptions loaded over sockets {t_loaded:.1f}s"
        + ("" if routes_wait_s is None else
           f", {len(routes)} routes on every node {routes_wait_s:.1f}s later"))

    def sleep_until(t):
        while time.monotonic() < t:
            dep.check_alive()
            time.sleep(min(0.05, max(0.0, t - time.monotonic())))

    def buckets():
        return server.sum_buckets(dep.batch_buckets())

    # warm-up: a closed loop, stage by stage, at in-flight counts that fill
    # the ingest buckets the cell's window uses, so that their route-step
    # programs are compiled (or loaded from the cache) before it. The first
    # launch after a load is slow (compile, upload): a stage marked
    # `until_first_batch` ends early once a device has served a batch. The
    # whole warm-up with the settling has a fixed length, so set-up is steady,
    # but for a checkout's first run, which compiles: a stage that names its
    # `bucket` is held beyond its seconds, `cold_s` at the most, until that
    # bucket has served a batch, so that no program is compiled in the window.
    t_warm = time.monotonic()
    buckets_loaded = buckets()
    for stage in traffic["warmup"]:
        gens.send("pub", ("cap", stage["in_flight"]))
        t_end = time.monotonic() + stage["seconds"]
        while stage.get("until_first_batch") and time.monotonic() < t_end - 0.5:
            sleep_until(time.monotonic() + 0.5)
            if buckets() != buckets_loaded:
                break
        else:
            sleep_until(t_end)
        while "bucket" in stage and time.monotonic() < t_end + stage["cold_s"] \
                and buckets().get(stage["bucket"], 0) \
                == buckets_loaded.get(stage["bucket"], 0):
            sleep_until(time.monotonic() + 0.5)
    buckets_warm = buckets()
    t_loop = time.monotonic() + 0.2
    t_open = max(t_loop + traffic["settle_s"], t_warm + traffic["settle_s"]
                 + sum(stage["seconds"] for stage in traffic["warmup"]))
    t_close = t_open + args.seconds
    # A traced run reads its counters and CPU shares over the window up to
    # the arming of the profiler, whose python tracer slows the host
    # severalfold, and keeps the cell's loop going under the capture until
    # every node's device has served a batch in it (one every ~5 s untraced in
    # a wide fan-out, far fewer traced) and `trace_s` have passed, 28 s at the
    # most.
    t_mark = t_open + args.seconds / 2.0 if args.trace else t_close
    gens.send("sub", ("window", t_open, t_mark))
    gens.send("pub", ("run", {
        "t_loop": t_loop, "t_open": t_open, "t_mark": t_mark,
        "t_close": t_mark + TRACE_MAX_S + 2.0 if args.trace else t_close}))

    sleep_until(t_open)
    setup_s = time.monotonic() - T0
    pids = [{"owner": [n.proc.pid], "workers": w} for n, w in zip(nodes, workers)]

    def cpu():
        return [{k: [server.cpu_seconds(p) for p in v] for k, v in node.items()}
                for node in pids]
    cpu0 = cpu()
    proms0 = dep.scrapes()
    sleep_until(t_mark)
    cpu1 = cpu()
    proms1 = dep.scrapes()
    armed = None
    if args.trace:
        def batches():  # so far, node by node
            return [sum(b.values()) for b in dep.batch_buckets()]
        before = batches()
        armed = dep.each(lambda n: json.loads(server.rest(
            n.ports["rest"], "/profile", {"duration_s": TRACE_MAX_S + 1.0})))
        while True:
            sleep_until(time.monotonic() + 1.0)
            traced = time.monotonic() - t_mark
            if traced >= TRACE_MAX_S or (traced >= traffic["trace_s"] and all(
                    b > a for a, b in zip(before, batches()))):
                break
        dep.each(lambda n: server.rest(n.ports["rest"], "/profile", method="DELETE"))
        t_close = time.monotonic()
        gens.send("pub", ("close", t_close))
    say("window closed; draining")

    # drain: every PUBACK, then every delivery the reference expects
    deadline = t_close + traffic["drain_s"]
    sent = {}
    for pipe in gens.pipes("pub"):
        if pipe.poll(max(0.0, deadline - time.monotonic())):
            msg = pipe.recv()
            if msg[0] != "drained":
                raise RuntimeError(f"publisher generator: {msg!r:.300}")
            sent.update(msg[1])
    # (the reference answers what was sent while the last deliveries arrive;
    # waiting for its count, not for a quiet second, outlasts a broker stall)
    want = None
    if len(sent) == n_pub:
        want = verify.expected(matcher, table, traffic, args.seed, sent)
    while want is not None and time.monotonic() < deadline:
        gens.send("sub", ("count",))
        if sum(msg[1] for msg in gens.gather("sub", "count", 30.0)) >= len(want[0]):
            break
        time.sleep(0.1)
    time.sleep(0.5)  # anything beyond the reference's count would be a fault
    texts_end = dep.each(lambda n: server.rest(
        n.ports["rest"], "/prometheus/stats").decode())
    proms_end = [server.parse_prom(text) for text in texts_end]
    # live bytes at the window's edges and after the drain: the program
    # exports no allocator peak
    hbm_peaks = [max(p.get("emqx_device_hbm_bytes", 0.0) for p in of_node)
                 for of_node in zip(proms0, proms1, proms_end)]
    rss = [server.rss_bytes(n.proc.pid) for n in nodes]
    gens.send("pub", ("stop",))
    gens.send("sub", ("stop",))
    pub_results = [msg[1] for msg in gens.gather("pub", "result", 120.0)]
    sub_results = [msg[1] for msg in gens.gather("sub", "result", 120.0)]
    dep.kill()
    say("server killed; judging against the reference")

    def judge(control=None):
        return verify.judge(matcher, table, traffic, args.seed, pub_results,
                            sub_results, (t_open, t_close),
                            list(zip(proms0, proms1)),
                            server.sum_scrapes(proms_end), want=want,
                            control=control, rehearsal=rehearsal)
    j = judge()
    lat = j["latency_ms_sorted"]
    # end-to-end values by the names BENCHMARK.json may give them (the two
    # latencies are from the due time in an open loop, the send time in a
    # closed one; no cell reports them yet, see PERF.md section 7)
    values = {
        "setup_s": setup_s,
        "deliveries_per_s": j["deliveries_in_window"] / args.seconds,
        "delivery_p50_ms": verify.percentile(lat, 50) if len(lat) else None,
        "delivery_p99_ms": verify.percentile(lat, 99) if len(lat) else None,
    }
    late = np.concatenate([p["late_s"] for p in pub_results])
    shares = [r["cpu_share"] for r in pub_results + sub_results
              if r["cpu_share"] is not None]
    window_s = t_mark - t_open
    procs = [{k: [(b - a) / window_s for a, b in zip(c0[k], c1[k])] for k in c0}
             for c0, c1 in zip(cpu0, cpu1)]
    # every node's capture reduced, each in a child of its own
    traces = [reduce_trace(n.plan["work"], a, n.tag) for n, a in zip(nodes, armed)] \
        if args.trace else [None] * len(nodes)
    ctx = context([n.plan["name"] for n in nodes], proms0, proms1, procs, traces)
    ctx.update({
        "window_s": window_s, "peaks": peaks,
        "loadgen": {
            "cpu_share_max": max(shares) if shares else None,
            "late_p99_ms": verify.percentile(np.sort(late), 99) * 1e3
            if len(late) else None}})
    if len(nodes) == 1:
        # a message's matches and bytes as the reference counts them are the
        # work of a device that holds the whole table. Over N nodes each
        # device holds the subscriptions placed on its node, and no figure
        # here says what reached it: nothing for a roofline to stand on
        ctx["work"] = {"fan_mean": j["window_fan_mean"],
                       "topic_bytes_mean": j["window_topic_bytes_mean"]}

    def cells_of(e):
        return e.get("workloads") or [w["name"] for w in manifest["workloads"]]

    layers = {}
    for e in manifest["per_layer"]:
        if cell["name"] in cells_of(e):
            spec = load_json("metrics", e["name"] + ".json")
            reader = importlib.import_module("readers." + spec["reader"])
            v = reader.read(spec["args"], ctx)
            if v is not None:
                layers[e["name"]] = {"value": v, "unit": e["unit"]}
    metrics = {}
    if rehearsal:
        pass  # a CPU run yields no time, rate or share
    elif args.trace:
        metrics = layers
    else:
        for e in manifest["end_to_end"]:
            if cell["name"] in cells_of(e):
                metrics[e["name"]] = {"value": values[e["name"]], "unit": e["unit"]}
    device["memory_peak_bytes"] = int(max(hbm_peaks))
    result = {"correct": j["correct"], "attempted": j["attempted"],
              "failed": j["failed"], "metrics": metrics, "device": device}
    if rehearsal:
        result["chip_run"] = False
    if args.trace and ctx["trace"]:
        device["busy_s"], device["window_s"] = busy_of(traces)
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["counts"] = {k: j[k] for k in (
        "sent_total", "deliveries_total", "deliveries_in_window", "redelivered",
        "window_fan_mean", "broker_faults",
        "deliveries_by_second")}
    if not rehearsal:
        result["counts"]["latency_ms"] = {
            k[9:-3]: values[k] for k in ("delivery_p50_ms", "delivery_p99_ms")}
    result["counts"]["setup_parts_s"] = None if rehearsal else {
        "server_up": t_up, "connected": t_conn, "table_loaded": t_loaded,
        "window_open": setup_s}
    def bucket_delta(a, b):
        return {str(k): b[k] - a.get(k, 0) for k in sorted(b) if b[k] - a.get(k, 0)}
    result["counts"]["batches_per_bucket"] = {
        "warm_up": bucket_delta(buckets_loaded, buckets_warm),
        "settle_and_window": bucket_delta(buckets_warm, server.sum_buckets(
            server.batch_buckets_of(text) for text in texts_end))}
    if len(nodes) > 1:  # the deployment node by node
        device["nodes"] = [
            {"name": n.plan["name"], "count": 1, "memory_peak_bytes": int(peak),
             **({"busy_s": t["busy_s"], "window_s": t["window_s"]} if t else {})}
            for n, peak, t in zip(nodes, hbm_peaks, traces)]
        result["counts"]["cluster"] = {
            "routes_per_node": len(routes),
            "routes_wait_s": None if rehearsal else routes_wait_s,
            "subscriptions": held, "rss_bytes": rss}
        if want is not None and len(want[0]):
            # of the deliveries due, those whose subscriber is on another
            # node than their publisher (the generators' side: `place`; a
            # group is counted where its first member is)
            subs, pubs = want[0] >> verify.SEQ_BITS, (want[0] & verify.SEQ_MASK) % n_pub
            result["counts"]["cluster"]["cross_node_share"] = float(
                (node_of(subs, len(nodes)) != node_of(pubs, len(nodes))).mean())
    if not rehearsal and not args.trace:  # what an untraced run can read of the layers
        result["counts"]["layers"] = {k: v["value"] for k, v in layers.items()}
    if "share" in j:  # a cell with groups: who received, and how evenly
        result["counts"]["share"] = j["share"]
    if "rate_schedule" in traffic:
        result["counts"]["stages"] = verify.stages(
            j, traffic["rate_schedule"], pub_results[0]["t_loop"])
    if args.control:  # the same run judged again with the control in its place
        jc = judge(controls.CONTROLS[args.control])
        result["control"] = {"name": args.control, "correct": jc["correct"],
                             "checks": jc["checks"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in j["checks"].items()}
    return result


def context(names, proms0, proms1, procs, traces):
    """What the readers see of N nodes: their series added up
    (`server.sum_scrapes`: sums, and the fullest device for the gauges), one
    owner share per node and all nodes' workers (the readers take the
    busiest), the trace of the node whose device was busiest; and under
    `nodes` each node's own, for a reader that looks at one node. Over
    several nodes a ratio of sums is the deployment's mean, and `trace` is
    one node's: never divide the one by the other."""
    return {
        "prom0": server.sum_scrapes(proms0), "prom1": server.sum_scrapes(proms1),
        "proc": {k: sum((p[k] for p in procs), []) for k in ("owner", "workers")},
        "trace": max((t for t in traces if t), key=lambda t: t["busy_s"],
                     default=None),
        "nodes": [{"name": name, "prom0": p0, "prom1": p1, "proc": proc, "trace": t}
                  for name, p0, p1, proc, t
                  in zip(names, proms0, proms1, procs, traces)]}


def busy_of(traces):
    """-> (busy_s, window_s) of the deployment, averaged over the chips used:
    a node whose capture holds no device event was busy for 0 s of the window
    the others traced."""
    read = [t for t in traces if t]
    return (sum(t["busy_s"] for t in read) / len(traces),
            sum(t["window_s"] for t in read) / len(read))


def reduce_trace(work, armed, tag=""):
    """The capture's .xplane.pb -> busy, idle, programs, in a child that can
    only see the CPU: this process never imports jax."""
    found = glob.glob(os.path.join(armed["dir"], "**", "*.xplane.pb"), recursive=True)
    if not found:
        say(f"{tag}no .xplane.pb under {armed['dir']}: the capture was not kept")
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"), found[0]],
        env=env, capture_output=True, text=True, timeout=200)
    if out.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{out.stderr[-2000:]}")
    trace = json.loads(out.stdout.strip().splitlines()[-1])
    say(f"{tag}trace {os.path.getsize(found[0])} bytes: " + (
        "no device plane with events" if trace is None else
        f"busy {trace['busy_s']:.4f}s of {trace['window_s']:.3f}s"))
    shutil.rmtree(os.path.join(work, "profile_traces"), ignore_errors=True)
    return trace


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal; marked as not a chip run")
    ap.add_argument("--traffic", default=None,
                    help="another traffic file than the cell's (exploring, "
                         "e.g. the knee's sweep; never in the benchmark's own runs)")
    ap.add_argument("--control", choices=sorted(controls.CONTROLS), default=None,
                    help="also judge a control in the program's place and print "
                         "its numbers (never in the benchmark's own runs)")
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    result = run(args)
    if "jax" in sys.modules or "emqx_tpu" in sys.modules:
        raise RuntimeError("the benchmark's driver imported jax or the program")
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
