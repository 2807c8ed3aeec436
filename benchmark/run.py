#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts `python -m emqx_tpu -c <generated config>` (the only process that opens
the chip), loads the cell's table over sockets from jax-free generator
processes, warms up, measures for --seconds, drains, kills the server, judges
the run against the plain reference and prints one JSON line. It fails, and
never falls back, off a TPU or on a device kind its peak table lacks.
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
    `subscriber_port(port)` may put a proxy before a listener, `loaded(ports)`
    runs once the table is loaded."""
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
    ports = {k: server.free_port() for k in ("inproc", "pool", "rest")}
    broker_cfg = fill(config["broker"], {
        "port_inproc": ports["inproc"], "port_pool": ports["pool"],
        "port_rest": ports["rest"], "work_dir": work})
    cfg_path = os.path.join(work, "broker.json")
    with open(cfg_path, "w") as f:
        json.dump(broker_cfg, f, indent=1)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    srv = server.Server(ROOT, cfg_path, os.path.join(work, "server.stderr.log"), env)
    gens = Generators()
    try:
        return drive(args, manifest, cell, config, traffic, table, ports, work,
                     srv, gens, hooks)
    finally:
        srv.kill()
        gens.stop()


def drive(args, manifest, cell, config, traffic, table, ports, work, srv, gens,
          hooks):
    rehearsal = args.rehearse_cpu
    n_sub, n_pub = table.n_sub, traffic["publishers"]
    line = srv.wait_line("emqx_tpu backend ", START_TIMEOUT_S)
    m = re.match(r"emqx_tpu backend (\S+) \((.*)\) x(\d+)$", line)
    if m is None:
        raise RuntimeError(f"unparseable backend line {line!r}")
    device = {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}
    peaks = None
    if not rehearsal:
        if device["platform"] != "tpu" or device["count"] < cell["chips"]:
            raise SystemExit(
                f"benchmark: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
                f"the server found {device}. No result is printed off the chip.")
        peaks = roofline.peaks_for(device["kind"])
    say(f"{'CPU REHEARSAL (not a chip run) ' if rehearsal else ''}"
        f"{cell['name']} seed {args.seed}: server backend {device}")

    # generators: subscribers partitioned among processes, listeners alternate
    listeners = [ports[name] for name in config["listener_split"]]
    prefix = f"b{args.seed}"
    base = {"table": table.spec, "seed": args.seed, "prefix": prefix}
    srv.wait_line("emqx_tpu mgmt api on ", START_TIMEOUT_S)
    t_up = time.monotonic() - T0
    sub_port = hooks.get("subscriber_port", lambda port: port)
    for part in split(list(range(n_sub)), traffic["subscriber_processes"]):
        gens.start("sub", loadgen.subscriber_main, {
            **base, "qos": traffic["qos"],
            "subs": [(s, sub_port(listeners[s % len(listeners)])) for s in part]})
    for part in split(list(range(n_pub)), traffic["publisher_processes"]):
        gens.start("pub", loadgen.publisher_main, {
            **base, "traffic": traffic,
            "pubs": [(c, listeners[c % len(listeners)]) for c in part]})

    # the reference is built while the generators connect and the table loads
    matcher = Matcher()
    for s in range(n_sub):
        for flt in table.filters_of(s):
            matcher.insert(flt, s)
    n_filters = table.n_filters()
    if matcher.count != n_filters:
        raise RuntimeError(f"reference holds {matcher.count} filters, not {n_filters}")
    gens.gather("sub", "connected", START_TIMEOUT_S)
    gens.gather("pub", "connected", START_TIMEOUT_S)
    workers = srv.children()
    for pid in workers:
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
        if "libtpu" in maps or "jaxlib" in maps:
            raise RuntimeError(f"worker {pid} loaded jaxlib/libtpu: only the "
                               "server may open the chip")
    t_conn = time.monotonic() - T0
    gens.send("sub", ("subscribe",))
    loaded = sum(msg[1] for msg in gens.gather("sub", "subscribed", START_TIMEOUT_S))
    held = json.loads(server.rest(ports["rest"], "/stats"))["subscriptions.count"]
    if loaded != n_filters or held != n_filters:
        raise RuntimeError(f"loaded {loaded}, broker holds {held}, expected {n_filters}")
    t_loaded = time.monotonic() - T0
    hooks.get("loaded", lambda p: None)(ports)
    say(f"server up {t_up:.1f}s, {n_sub + n_pub} connections {t_conn:.1f}s, "
        f"{n_filters} subscriptions loaded over sockets {t_loaded:.1f}s")

    def sleep_until(t):
        while time.monotonic() < t:
            if srv.proc.poll() is not None:
                raise RuntimeError(f"server died rc={srv.proc.returncode}:\n{srv.log_tail()}")
            time.sleep(min(0.05, max(0.0, t - time.monotonic())))

    # warm-up: a closed loop, stage by stage, at in-flight counts that fill
    # the ingest buckets the cell's window uses, so that their route-step
    # programs are compiled (or loaded from the cache) before it. The first
    # launch after a load is slow (compile, upload): a stage marked
    # `until_first_batch` ends early once the device has served a batch. The
    # whole warm-up with the settling has a fixed length, so set-up is steady.
    t_warm = time.monotonic()
    buckets_loaded = server.batch_buckets(ports["rest"])
    for stage in traffic["warmup"]:
        gens.send("pub", ("cap", stage["in_flight"]))
        t_end = time.monotonic() + stage["seconds"]
        while stage.get("until_first_batch") and time.monotonic() < t_end - 0.5:
            sleep_until(time.monotonic() + 0.5)
            if server.batch_buckets(ports["rest"]) != buckets_loaded:
                break
        else:
            sleep_until(t_end)
    buckets_warm = server.batch_buckets(ports["rest"])
    t_loop = time.monotonic() + 0.2
    t_open = max(t_loop + traffic["settle_s"], t_warm + traffic["settle_s"]
                 + sum(stage["seconds"] for stage in traffic["warmup"]))
    t_close = t_open + args.seconds
    # A traced run reads its counters and CPU shares over the window up to
    # the arming of the profiler, whose python tracer slows the host
    # severalfold, and keeps the cell's loop going under the capture until the
    # device has served a batch in it (one every ~5 s untraced in a wide
    # fan-out, far fewer traced) and `trace_s` have passed, 28 s at the most.
    t_mark = t_open + args.seconds / 2.0 if args.trace else t_close
    gens.send("sub", ("window", t_open, t_mark))
    gens.send("pub", ("run", {
        "t_loop": t_loop, "t_open": t_open, "t_mark": t_mark,
        "t_close": t_mark + TRACE_MAX_S + 2.0 if args.trace else t_close}))

    sleep_until(t_open)
    setup_s = time.monotonic() - T0
    pids = {"owner": [srv.proc.pid], "workers": workers}
    cpu0 = {k: [server.cpu_seconds(p) for p in v] for k, v in pids.items()}
    prom0 = server.scrape(ports["rest"])
    sleep_until(t_mark)
    cpu1 = {k: [server.cpu_seconds(p) for p in v] for k, v in pids.items()}
    prom1 = server.scrape(ports["rest"])
    armed = None
    if args.trace:
        before = sum(server.batch_buckets(ports["rest"]).values())
        armed = json.loads(server.rest(
            ports["rest"], "/profile", {"duration_s": TRACE_MAX_S + 1.0}))
        while True:
            sleep_until(time.monotonic() + 1.0)
            traced = time.monotonic() - t_mark
            served = sum(server.batch_buckets(ports["rest"]).values()) - before
            if traced >= TRACE_MAX_S or (traced >= traffic["trace_s"] and served):
                break
        server.rest(ports["rest"], "/profile", method="DELETE")
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
    prom_text_end = server.rest(ports["rest"], "/prometheus/stats").decode()
    prom_end = server.parse_prom(prom_text_end)
    # live bytes at the window's edges and after the drain: the program
    # exports no allocator peak
    hbm_peak = max(p.get("emqx_device_hbm_bytes", 0.0)
                   for p in (prom0, prom1, prom_end))
    gens.send("pub", ("stop",))
    gens.send("sub", ("stop",))
    pub_results = [msg[1] for msg in gens.gather("pub", "result", 120.0)]
    sub_results = [msg[1] for msg in gens.gather("sub", "result", 120.0)]
    srv.kill()
    say("server killed; judging against the reference")

    def judge(control=None):
        return verify.judge(matcher, table, traffic, args.seed, pub_results,
                            sub_results, (t_open, t_close), (prom0, prom1),
                            prom_end, want=want, control=control,
                            rehearsal=rehearsal)
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
    ctx = {
        "prom0": prom0, "prom1": prom1, "window_s": t_mark - t_open, "peaks": peaks,
        "proc": {k: [(b - a) / (t_mark - t_open) for a, b in zip(cpu0[k], cpu1[k])]
                 for k in pids},
        "loadgen": {
            "cpu_share_max": max(shares) if shares else None,
            "late_p99_ms": verify.percentile(np.sort(late), 99) * 1e3
            if len(late) else None},
        "work": {"fan_mean": j["window_fan_mean"],
                 "topic_bytes_mean": j["window_topic_bytes_mean"]},
        "trace": None,
    }
    if args.trace:
        ctx["trace"] = reduce_trace(work, armed)

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
    device["memory_peak_bytes"] = int(hbm_peak)
    result = {"correct": j["correct"], "attempted": j["attempted"],
              "failed": j["failed"], "metrics": metrics, "device": device}
    if rehearsal:
        result["chip_run"] = False
    if args.trace and ctx["trace"]:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
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
        "settle_and_window": bucket_delta(buckets_warm, server.batch_buckets_of(prom_text_end))}
    if not rehearsal and not args.trace:  # what an untraced run can read of the layers
        result["counts"]["layers"] = {k: v["value"] for k, v in layers.items()}
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


def reduce_trace(work, armed):
    """The capture's .xplane.pb -> busy, idle, programs, in a child that can
    only see the CPU: this process never imports jax."""
    found = glob.glob(os.path.join(armed["dir"], "**", "*.xplane.pb"), recursive=True)
    if not found:
        say(f"no .xplane.pb under {armed['dir']}: the capture was not kept")
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"), found[0]],
        env=env, capture_output=True, text=True, timeout=200)
    if out.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{out.stderr[-2000:]}")
    trace = json.loads(out.stdout.strip().splitlines()[-1])
    say(f"trace {os.path.getsize(found[0])} bytes: " + (
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
