#!/usr/bin/env bash
# ci_gate.sh — THE single pre-merge command (docs/concurrency.md,
# docs/static_analysis.md). Three gates, in the order that fails fastest:
#
#   1. tpu_lint + the consolidated tier-B audit in ONE invocation
#      (`--audit`): all 16 AST checkers, the device-contract audit
#      (jaxpr tracing on CPU), the replication replay audit
#      (shadow-replica convergence + seeded incomplete-log control),
#      and the wire-compatibility audit (golden-corpus replay through
#      current decoders + seeded drift control + live layout
#      cross-check — docs/static_analysis.md "Tier B")
#   2. tier-1 pytest                      (`-m "not slow"`; the race-marked
#      racetrack suite is part of tier-1 and runs with the detector armed)
#   3. the race suite alone, verbose      (`-m race`) — redundant with (2)
#      but isolates the concurrency rig's verdict in its own section of
#      the log, so a race report is never buried in a 500-test dot wall
#
# All three run on the CPU. The chip is proved separately, through the
# chip tool: `python chip_smoke.py`, and speed is what the benchmark's
# cells measure there (`python3 benchmark/run.py --workload <cell>
# --seed <n> --seconds 30 --trace <0|1>`; BENCHMARK.json).
#
# Fast mode for the inner loop (pre-push, not pre-merge):
#
#   tools/ci_gate.sh --fast     # lint scoped to git-touched files
#                               # (--changed-only --jobs 8) + the
#                               # bounded tier-B smoke (`--audit
#                               # --smoke`: replay capped at 8 rounds,
#                               # full corpus replay, contracts
#                               # skipped) + race suite
#
# Exit non-zero on the first failing gate.
set -euo pipefail

cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

FAST=0
for arg in "$@"; do
    case "$arg" in
        --fast) FAST=1 ;;
        *) echo "usage: tools/ci_gate.sh [--fast]" >&2; exit 2 ;;
    esac
done

banner() { printf '\n== %s ==\n' "$*"; }

profile_smoke() {
    # arm -> one real batch through ingest -> disarm -> assert the
    # jax.profiler capture landed non-empty and under budget
    python - <<'PY'
import asyncio, tempfile

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe.profiler import Profiler


async def main():
    broker = Broker(router=Router(min_tpu_batch=8), hooks=Hooks())
    prof = Profiler(metrics=broker.metrics, trace_dir=tempfile.mkdtemp())
    sink = []
    for i in range(8):
        broker.subscribe(f"s{i}", f"c{i}", f"p/{i}", pkt.SubOpts(),
                         lambda m, o: sink.append(m.topic))
    ing = BatchIngest(broker, max_batch=64, window_us=500)
    broker.ingest = ing
    ing.start()
    prof.arm(duration_s=20.0)
    rs = [await broker.apublish_enqueue(
        Message(topic=f"p/{i % 8}", payload=b"x", from_client=f"b{i}"))
        for i in range(64)]
    await asyncio.gather(*[r for r in rs if not isinstance(r, int)])
    entry = prof.disarm("smoke")
    await ing.stop()
    assert entry is not None and entry["bytes"] > 0 \
        and not entry["deleted"], entry
    print(f"profile smoke ok: {entry['bytes']} bytes -> {entry['dir']}")


asyncio.run(main())
PY
}

if [ "$FAST" = 1 ]; then
    banner "tpu_lint (changed files)"
    python -m tools.analysis --changed-only --jobs 8
    banner "profile smoke (arm -> batch -> disarm)"
    profile_smoke
    banner "tier-B smoke (bounded replay + full wirecompat corpus)"
    python -m tools.analysis --audit --smoke --checks oplog
    banner "race suite (racetrack armed)"
    python -m pytest tests/ -q -m race -p no:cacheprovider
    exit 0
fi

banner "tpu_lint + tier-B audit (contracts, replay, wirecompat)"
python -m tools.analysis --jobs 8 --audit

banner "tier-1 tests"
python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider

banner "race suite (racetrack armed)"
python -m pytest tests/ -m race -p no:cacheprovider

banner "ci_gate: all gates green"
