"""The table of peaks and the route step's work, counted from the traffic and
not from the program: what any implementation has to move to route one launch
of the cell's messages. A lower bound on work, so no share can pass 100 %."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

ENTRY_BYTES = 8  # one matched table entry: a filter id and a subscriber slot
SLOT_BYTES = 4   # one delivery slot written out
ROW_OUT_BYTES = 4  # per message: its count of deliveries


def peaks_for(device_kind):
    """An unknown device kind is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind_substring"]
    for sub, peaks in table.items():
        if sub in device_kind.lower():
            return peaks
    raise LookupError(
        f"device_kind {device_kind!r} is not in benchmark/harness/peaks.json")


def route_step_work(rows, topic_bytes_mean, matches_mean):
    """One launch routing `rows` messages: topic bytes in, the matched table
    entries read, one slot out per delivery and one count per message; one
    byte comparison per topic byte as the operations."""
    return {
        "bytes": rows * (topic_bytes_mean + matches_mean * ENTRY_BYTES
                         + matches_mean * SLOT_BYTES + ROW_OUT_BYTES),
        "ops": rows * topic_bytes_mean,
    }


def least_seconds(work, peaks):
    """-> (seconds, which peak bounds it)."""
    by_ops = work["ops"] / peaks["peak_ops_per_s"]
    by_bytes = work["bytes"] / peaks["peak_bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
