"""`correct` comes out false when it should. The controls (the reference's
own answer with one stated guarantee broken) fail the comparison at a size a
test can hold, and whole rehearsal runs on the CPU (the harness's look for a
chip skipped, the rest of a run driven) come out not correct with the timed
path broken underneath: an answer altered where it is produced, publishes
refused at the ingest, and the device path failing so that the degrade
ladder serves the window."""

import argparse
import json
import os
import socket
import sys
import threading
import urllib.request

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from harness import controls, verify  # noqa: E402


def small_answer():
    rng = np.random.default_rng(7)
    n_seq = 30000
    fan = rng.integers(1, 4, size=n_seq)
    seq = np.repeat(np.arange(n_seq), fan)
    sub = rng.integers(0, 50, size=len(seq))
    crc = rng.integers(0, 2**32, size=n_seq, dtype=np.uint32)
    return (sub.astype(np.int64) << verify.SEQ_BITS) | seq, crc, fan


def test_the_reference_in_its_own_place_is_exact():
    keys, crc, _ = small_answer()
    seq = keys & ((1 << verify.SEQ_BITS) - 1)
    assert verify.compare(keys, crc, keys[::-1], crc[seq][::-1])[:3] == (0, 0, 0)


@pytest.mark.parametrize("name", ["altered_payload", "at_most_once", "stale_table"])
def test_control_is_not_correct(name):
    """(The two controls of a table with groups: test_share.py.)"""
    keys, crc, fan = small_answer()
    got_keys, got_crc, _ = controls.CONTROLS[name](keys, crc, fan, None)
    missing, unexpected, corrupt, _ = verify.compare(keys, crc, got_keys, got_crc)
    assert missing + unexpected + corrupt >= 1


class AlteringProxy:
    """TCP proxy before a listener that flips the last payload bit of the
    `nth` PUBLISH the broker sends down one connection: an answer altered
    where it is produced."""

    def __init__(self, target_port, nth=5):
        self.target, self.nth, self.altered = target_port, nth, 0
        self.lock = threading.Lock()
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                client, _ = self.sock.accept()
            except OSError:
                return
            upstream = socket.create_connection(("127.0.0.1", self.target))
            for s in (client, upstream):
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._pump, args=(client, upstream, False),
                             daemon=True).start()
            threading.Thread(target=self._pump, args=(upstream, client, True),
                             daemon=True).start()

    def _pump(self, src, dst, tamper):
        buf, seen = b"", 0
        try:
            while True:
                chunk = src.recv(1 << 16)
                if not chunk:
                    break
                if not tamper:
                    dst.sendall(chunk)
                    continue
                buf += chunk
                out = bytearray()
                while len(buf) >= 2:  # whole packets only, so framing is known
                    rl, shift, i = 0, 0, 1
                    while i < len(buf):
                        rl |= (buf[i] & 0x7F) << shift
                        shift += 7
                        i += 1
                        if not buf[i - 1] & 0x80:
                            break
                    else:
                        break
                    if len(buf) < i + rl:
                        break
                    packet = bytearray(buf[:i + rl])
                    buf = buf[i + rl:]
                    if packet[0] & 0xF0 == 0x30:
                        seen += 1
                        with self.lock:
                            if seen == self.nth and not self.altered:
                                packet[-1] ^= 1
                                self.altered += 1
                    out += packet
                dst.sendall(bytes(out))
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.close()
                except OSError:
                    pass


def rehearse(workload, hooks, seed=424242):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=3.0, trace=0,
                              rehearse_cpu=True, control=None, traffic=None)

    def traffic(t):
        t["drain_s"] = 6.0
        t["settle_s"] = 1.0
        t["warmup"] = [{"in_flight": 4, "seconds": 2.0}]
    return bench_run.run(args, {"traffic": traffic, **hooks})


def arm(ports, rule):
    req = urllib.request.Request(
        f"http://127.0.0.1:{ports['rest']}/api/v5/faults",
        data=json.dumps(rule).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 201


def test_a_sound_rehearsal_is_correct_and_reports_no_device_metric():
    r = rehearse("fanout_1k.sat", {})
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert list(r)[-1] == "checks"
    assert r["checks"]["missing"] == {"value": 0, "limit": 0}


def test_an_altered_delivery_is_not_correct():
    proxies = {}

    def via(port):
        if port not in proxies:
            proxies[port] = AlteringProxy(port)
        return proxies[port].port
    r = rehearse("fanout_1k.sat", {"subscriber_port": via})
    assert sum(p.altered for p in proxies.values()) >= 1
    assert r["checks"]["corrupt"]["value"] >= 1
    assert r["correct"] is False


def test_refused_publishes_are_not_correct():
    r = rehearse("fanout_1k.sat", {"loaded": lambda ports: arm(
        ports, {"site": "ingest.enqueue", "mode": "drop", "nth": 50})})
    assert r["correct"] is False
    assert r["checks"]["broker_faults"]["value"] >= 1  # ingest.shed


def test_the_degrade_ladder_serving_the_window_is_not_correct():
    r = rehearse("mixed_1m.sat", {"loaded": lambda ports: arm(
        ports, {"site": "device.readback", "mode": "raise"})})
    assert r["correct"] is False
    assert r["checks"]["device_share_min"]["value"] < \
        r["checks"]["device_share_min"]["limit"]
