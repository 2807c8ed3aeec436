"""Not a test: the cost of one shared SUBSCRIBE as the broker's table of
groups grows, in process, no device and no socket (a shape on the host it runs
on, not a rate of the served path). `python3 benchmark/tests/probe_share_load.py
[filters in thousands]`. PERF.md section 7 has what it showed."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

from emqx_tpu.broker.broker import Broker  # noqa: E402
from emqx_tpu.mqtt import packet as pkt  # noqa: E402


def main(thousands):
    shared, plain, opts = Broker(), Broker(), pkt.SubOpts(qos=1)
    t0 = last = time.monotonic()
    for j in range(thousands):
        for d in range(1000):
            for m in range(4):
                shared.subscribe(f"s{d // 4}-{m}", f"c{d // 4}-{m}",
                                 f"$share/g{d // 4}/device/{d}/+/{j}/#", opts, None)
        now = time.monotonic()
        print(f"{1000 * (j + 1)} real filters, {4000 * (j + 1)} shared "
              f"subscriptions: the last 4,000 took {(now - last) * 250:.0f} us each, "
              f"{now - t0:.1f} s in all", flush=True)
        last = now
    t = time.monotonic()
    n = shared.shared.count()
    print(f"SharedSub.count() = {n}: {(time.monotonic() - t) * 1e3:.2f} ms a call")
    t = time.monotonic()
    for j in range(thousands):
        for d in range(1000):
            plain.subscribe(f"s{d}", f"c{d}", f"device/{d}/+/{j}/#", opts, None)
    print(f"{1000 * thousands} plain subscriptions: "
          f"{(time.monotonic() - t) * 1e3 / thousands:.0f} us each")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
