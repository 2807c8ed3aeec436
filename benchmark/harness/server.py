"""The system under test as a child: `python -m emqx_tpu -c <config>`, the
only process that opens the chip. This file starts it, reads its REST
surfaces, samples /proc, and kills it with its workers (SIGKILL: a clean stop
at a million subscriptions takes two minutes and no cell measures it)."""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

CLK_TCK = os.sysconf("SC_CLK_TCK")


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, root, cfg_path, log_path, env):
        self.log_path = log_path
        self.lines = []
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "emqx_tpu", "-c", cfg_path], cwd=root,
            env=env, stdout=subprocess.PIPE, stderr=self._log)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            self.lines.append(line.decode("utf-8", "replace").rstrip())

    def wait_line(self, prefix, timeout):
        deadline = time.monotonic() + timeout
        while True:
            for ln in list(self.lines):
                if ln.startswith(prefix):
                    return ln
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before "
                    f"{prefix!r}; stderr tail:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"server did not print {prefix!r} in {timeout:.0f}s; "
                    f"stderr tail:\n{self.log_tail()}")
            time.sleep(0.05)

    def log_tail(self, n=3000):
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - n))
            return f.read().decode("utf-8", "replace")

    def children(self):
        """pids whose parent is the server: its listener-pool workers."""
        out = []
        for pid in os.listdir("/proc"):
            if pid.isdigit():
                try:
                    with open(f"/proc/{pid}/stat") as f:
                        ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, IndexError, ValueError):
                    continue  # raced an exiting process
                if ppid == self.proc.pid:
                    out.append(int(pid))
        return out

    def kill(self):
        kids = self.children() if self.proc.poll() is None else []
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for pid in kids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        for pid in kids:  # re-parented to init: wait until each is gone
            deadline = time.monotonic() + 10.0
            while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
                time.sleep(0.02)
        self._reader.join(5.0)
        self._log.close()


def cpu_seconds(pid):
    """user + system CPU seconds of one process, from /proc/<pid>/stat."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / CLK_TCK


def rest(port, path, body=None, method=None, timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v5{path}",
        data=None if body is None else json.dumps(body).encode(),
        method=method or ("GET" if body is None else "POST"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


_PROM_LINE = re.compile(r"^(emqx_[A-Za-z0-9_]+) ([-+0-9.eE]+|nan|inf)$", re.M)


def parse_prom(text):
    """Prometheus exposition -> {series: value}; histogram buckets (the lines
    with labels) are left out, sums and counts are kept."""
    return {name: float(v) for name, v in _PROM_LINE.findall(text)}


def scrape(port):
    return parse_prom(rest(port, "/prometheus/stats").decode())


_BATCH_BUCKET = re.compile(
    r'^emqx_router_batch_size_bucket\{le="([0-9.e+]+)"\} (\d+)$', re.M)


def batch_buckets(port):
    return batch_buckets_of(rest(port, "/prometheus/stats").decode())


def batch_buckets_of(text):
    """Device batches so far per pow2 ingest bucket, from the program's
    `router.batch.size` histogram: a batch of B rows runs the route-step
    program compiled for max(64, next_pow2(B))."""
    out, prev = {}, 0
    for le, n in sorted((float(le), int(n))
                        for le, n in _BATCH_BUCKET.findall(text)):
        b = max(64, int(le))
        out[b] = out.get(b, 0) + n - prev
        prev = n
    return out
