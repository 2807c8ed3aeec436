"""Controls: the reference's own answer put in the program's place with one
guarantee the configuration states broken. Each has to come out as not
correct. `--control <name>` on run.py reads one at the cell's own size after
a real run has been judged; the benchmark's own runs never pass it."""

import numpy as np

from harness.verify import SEQ_MASK


def _as_received(keys, crc_of_seq):
    seq = keys & SEQ_MASK
    return keys, crc_of_seq[seq], np.zeros(len(keys))


def at_most_once(exp_keys, exp_crc, fan):
    """QoS1 broken: one delivery in ten thousand is never made (at least the
    first), as a path that stops tracking acknowledgements would lose them."""
    keep = np.ones(len(exp_keys), bool)
    keep[::10000] = False
    return _as_received(exp_keys[keep], exp_crc)


def stale_table(exp_keys, exp_crc, fan):
    """Exact matching broken: messages that match more than one filter reach
    only the first (a table that has not caught up with its overlays). Where
    every message matches one filter, the last message's delivery goes."""
    seq = exp_keys & SEQ_MASK
    order = np.argsort(seq, kind="stable")
    first = np.ones(len(seq), bool)
    first[order[1:]] = seq[order[1:]] != seq[order[:-1]]
    if first.all():
        first[order[-1]] = False
    return _as_received(exp_keys[first], exp_crc)


def altered_payload(exp_keys, exp_crc, fan):
    """One delivery carries one flipped payload bit."""
    keys, crc, t = _as_received(exp_keys, exp_crc)
    crc = crc.copy()
    crc[len(crc) // 2] ^= 1
    return keys, crc, t


CONTROLS = {"at_most_once": at_most_once, "stale_table": stale_table,
            "altered_payload": altered_payload}
