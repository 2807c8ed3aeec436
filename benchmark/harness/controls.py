"""Controls: the reference's own answer put in the program's place with one
guarantee the configuration states broken. Each has to come out as not
correct. `--control <name>` on run.py reads one at the cell's own size after
a real run has been judged; the benchmark's own runs never pass it.

A control is given the reference's answer keyed by receiver class and returns
deliveries keyed by connection, as the sockets would: a class is named by its
first connection (traffic.py, Table), so the answer as it stands is every
delivery made to a plain subscriber or to its group's first member. The last
two controls break what a configuration of `$share` groups states, and need a
table that has groups."""

import numpy as np

from harness.verify import SEQ_BITS, SEQ_MASK


def _as_received(keys, crc_of_seq):
    seq = keys & SEQ_MASK
    return keys, crc_of_seq[seq], np.zeros(len(keys))


def at_most_once(exp_keys, exp_crc, fan, table):
    """QoS1 broken: one delivery in ten thousand is never made (at least the
    first), as a path that stops tracking acknowledgements would lose them."""
    keep = np.ones(len(exp_keys), bool)
    keep[::10000] = False
    return _as_received(exp_keys[keep], exp_crc)


def stale_table(exp_keys, exp_crc, fan, table):
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


def altered_payload(exp_keys, exp_crc, fan, table):
    """One delivery carries one flipped payload bit."""
    keys, crc, t = _as_received(exp_keys, exp_crc)
    crc = crc.copy()
    crc[len(crc) // 2] ^= 1
    return keys, crc, t


def _members(exp_keys, table):
    """-> the member count of every delivery's class (1: a plain subscriber)."""
    if not table.groups:
        raise ValueError("this control breaks a group's guarantee: the table has none")
    return table.members_of[exp_keys >> SEQ_BITS]


def round_robin(exp_keys, exp_crc, fan, table):
    """No control: the reference's own one-of-N answer, a group's k-th
    delivery made to its member k mod N. It has to come out correct."""
    members = _members(exp_keys, table)
    order = np.argsort(exp_keys >> SEQ_BITS, kind="stable")
    cls = (exp_keys >> SEQ_BITS)[order]
    starts = np.flatnonzero(np.r_[True, cls[1:] != cls[:-1]])
    kth = np.arange(len(cls)) - np.repeat(starts, np.diff(np.r_[starts, len(cls)]))
    keys = exp_keys.copy()
    keys[order] += (kth % members[order]) << SEQ_BITS
    return _as_received(keys, exp_crc)


def every_member(exp_keys, exp_crc, fan, table):
    """The pick broken into a plain fan-out: every member of a matched group
    receives the message (what subscribing without the prefix would do)."""
    members = _members(exp_keys, table)
    starts = np.cumsum(members) - members
    nth = np.arange(members.sum()) - np.repeat(starts, members)
    return _as_received(np.repeat(exp_keys, members) + (nth << SEQ_BITS), exp_crc)


def one_member(exp_keys, exp_crc, fan, table):
    """The balance broken: every delivery of a group goes to its first
    member (what the `sticky` strategy does). Each group still gets each
    message once, so only the members' shares can tell."""
    _members(exp_keys, table)
    return _as_received(exp_keys, exp_crc)


CONTROLS = {"at_most_once": at_most_once, "stale_table": stale_table,
            "altered_payload": altered_payload, "every_member": every_member,
            "one_member": one_member}
