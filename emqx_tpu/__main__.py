"""Broker entrypoint: `python -m emqx_tpu [-c config.json] [--port 1883]`.

The `bin/emqx foreground` analog (reference: bin/emqx:75-110). Boots the
full application (broker kernel, extensions, listeners, management API,
housekeeping) from a config file plus EMQX_TPU__* env overrides and runs
until SIGINT/SIGTERM.
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="emqx_tpu", description=__doc__)
    ap.add_argument("-c", "--config", default=None, help="JSON config file")
    ap.add_argument("--host", default=None, help="override listener bind")
    ap.add_argument("--port", type=int, default=None, help="override listener port")
    ap.add_argument(
        "--no-tpu", action="store_true",
        help="route on the CPU trie only (skip JAX/TPU engine)",
    )
    ap.add_argument(
        "--no-dashboard", action="store_true", help="disable the REST API"
    )
    args = ap.parse_args(argv)
    # the owner's loop times its own select(): idle, busy and stalls of
    # the main thread are series (observe/profiler.py LoopBudget)
    from emqx_tpu.observe.profiler import loop_factory

    return asyncio.run(serve(args), loop_factory=loop_factory)


async def serve(args) -> int:
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.schema import load_file

    config = load_file(args.config)
    if args.host is not None:
        config.listeners[0].bind = args.host
    if args.port is not None:
        config.listeners[0].port = args.port
    if args.no_tpu:
        config.router.enable_tpu = False
    if args.no_dashboard:
        config.dashboard.enable = False
    if config.router.enable_tpu:
        from emqx_tpu.compile_cache import place_compile_cache

        place_compile_cache()

    app = BrokerApp(config)
    fp = app.fingerprint
    print(
        "emqx_tpu backend "
        + (
            f"{fp['platform']} ({fp['device_kind']}) x{fp['device_count']}"
            if fp
            else "none (--no-tpu: CPU trie only)"
        ),
        flush=True,
    )
    await app.start()
    for l in app.listeners.list().values():
        print(
            f"emqx_tpu listener {l.config.type}:{l.config.name} on "
            f"{l.config.bind}:{l.port}",
            flush=True,
        )
    for pool in app.worker_pools:
        row = pool.describe()
        print(
            f"emqx_tpu listener {row['id']} on {row['bind']} "
            f"({row['workers']} workers)",
            flush=True,
        )
    if app.mgmt_server is not None:
        print(
            f"emqx_tpu mgmt api on {config.dashboard.bind}:{app.mgmt_server.port}",
            flush=True,
        )
    if app.cluster_bus is not None:
        print(
            f"emqx_tpu cluster bus on "
            f"{app.cluster_bus.host}:{app.cluster_bus.port}",
            flush=True,
        )

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    print("shutting down", flush=True)
    await app.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
