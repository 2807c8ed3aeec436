"""The one general generator: a deployment's subscription table from its
configuration file, and a publisher's message stream from a traffic file.
Everything is drawn from --seed; nothing here knows a cell by name.

Every seed gets the same work in another order: a publisher's ranks (and, in
an open loop, its arrival gaps) are a fixed pool drawn from the traffic file's
`base_seed`, permuted by the run's seed, and the seed also draws which device
id carries which popularity rank (within its listener and generator process:
`rank_to_id`), so fan-out per message and the load on each listener have one
distribution whatever the seed."""

import struct
import zlib

import numpy as np

from harness import share

FILLER_ROWS = 4096


class Table:
    """`config["table"]`: `subscribers` connections; each family is a filter
    template. `per_subscriber` families give subscriber s the filters with
    {s} = s and {j} = 0..per_subscriber-1. `count` families put one filter on
    each of the `count` most popular device ids d (rank -> id by the seed),
    held by subscriber (d + holder_offset) mod the plain subscribers, so that
    no client holds two filters matching one topic. `share` families
    (share.py) are `$share` groups: each takes a block of connections of its
    own after the plain subscribers, so a connection is a plain subscriber
    or a member of exactly one group, and within a group no two real
    filters match one topic.

    What a run is judged by is the *receiver class*: a plain subscriber is
    its own, a group's members are one, named by the first member's
    connection (`class_of`). `classes()` gives each class's real filters,
    which is what the reference is given; `filters_of` is what a connection
    puts on the wire."""

    def __init__(self, spec, seed):
        self.spec = spec
        self.n_sub = spec["subscribers"]
        self.rank_to_id = rank_to_id(spec["id_space"], seed)
        in_groups = sum(f["groups"] * f["members"] for f in spec["families"]
                        if "share" in f)
        self.n_plain = self.n_sub - in_groups
        if self.n_plain < 0:
            raise ValueError(f"{in_groups} group members in {self.n_sub} connections")
        self.groups = share.groups(spec["families"], self.n_plain)
        self.class_of = np.arange(self.n_sub, dtype=np.int64)
        self.members_of = np.ones(self.n_sub, np.int64)  # by class
        self._group_at = {}
        for group in self.groups:
            first, members = group[:2]
            self.class_of[first:first + members] = first
            self.members_of[first] = members
            self._group_at[first] = group

    def _plain_filters(self, s):
        out = []
        for fam in self.spec["families"]:
            if "per_subscriber" in fam:
                out += [fam["filter"].format(s=s, j=j)
                        for j in range(fam["per_subscriber"])]
            elif "count" in fam:
                for d in self.rank_to_id[:fam["count"]]:
                    if (int(d) + fam["holder_offset"]) % self.n_plain == s:
                        out.append(fam["filter"].format(d=int(d)))
        return out

    def filters_of(self, s):
        if s < self.n_plain:
            return self._plain_filters(s)
        _, _, name, real = self._group_at[int(self.class_of[s])]
        return [share.wire(name, flt) for flt in real]

    def n_filters(self):
        """Subscriptions: every member of a group holds each of its filters."""
        return self.n_class_filters() + sum(
            (members - 1) * len(real) for _, members, _, real in self.groups)

    def classes(self):
        """-> (class, its connections, its real filters) of every class."""
        for s in range(self.n_plain):
            yield s, range(s, s + 1), self._plain_filters(s)
        for first, members, _, real in self.groups:
            yield first, range(first, first + members), real

    def n_class_filters(self):
        """What the reference holds: one entry per class and real filter."""
        return sum(
            fam["per_subscriber"] * self.n_plain if "per_subscriber" in fam
            else fam.get("count", 0) for fam in self.spec["families"]) \
            + sum(len(real) for _, _, _, real in self.groups)


RANK_CLASSES = 4


def rank_to_id(n, seed):
    """Which id carries which popularity rank: a permutation from the seed
    that keeps an id's residue mod RANK_CLASSES equal to its rank's. Listeners
    alternate by id and generator processes take ids mod their number, so the
    hot ranks load the same listener and process whatever the seed: with a
    free permutation the million-row cell read 8.0k dlv/s with the hottest
    subscriber on the in-process listener and 9.6–10.6k with it on the
    worker pool (my chip runs, PR 23)."""
    rng = np.random.default_rng([seed, 1])
    out = np.empty(n, np.int64)
    for c in range(min(RANK_CLASSES, n)):
        ids = np.arange(c, n, RANK_CLASSES)
        out[c::RANK_CLASSES] = rng.permutation(ids)
    return out


def _draw(spec, space, rng, n):
    """`spec["n"]` names the table's key that holds the size of the space
    drawn from, so a rehearsal table shrinks the draw with it."""
    if spec["draw"] == "zipf":
        # the program's bench `_zipf_ids`: heavy tail clipped to the id space
        return np.minimum(rng.zipf(spec["a"], size=n) - 1, space[spec["n"]] - 1)
    if spec["draw"] == "uniform":
        return rng.integers(0, space[spec["n"]], size=n)
    raise ValueError(f"unknown draw {spec['draw']!r}")


class Stream:
    """Publisher connection `conn`'s messages: message k has the global
    sequence number k * publishers + conn, topic from the pools, payload =
    8-byte sequence number + a filler row."""

    def __init__(self, traffic, table, seed, conn):
        self.t, self.conn = traffic, conn
        self.n_pub = traffic["publishers"]
        pool = traffic["pool"]
        base = np.random.default_rng([traffic["base_seed"], conn])
        run = np.random.default_rng([seed, 2, conn])
        ranks = _draw(traffic["topic"]["i"], table.spec, base, pool)
        self.ids = table.rank_to_id[ranks[run.permutation(pool)]]
        self.js = (_draw(traffic["topic"]["j"], table.spec, run, pool)
                   if "j" in traffic["topic"] else np.zeros(pool, np.int64))
        self.template = traffic["topic"]["template"]
        if traffic["loop"] == "open":
            # a unit-rate Poisson process, run through the rate's integral:
            # `rate_msgs_per_s`, or `rate_schedule` [[seconds, msgs/s], ...]
            # (the last rate holds on); seconds after the open loop starts
            unit = np.cumsum(base.exponential(1.0, size=pool)[run.permutation(pool)])
            sched = traffic.get("rate_schedule") or [[1.0, traffic["rate_msgs_per_s"]]]
            edges, mass = [0.0], [0.0]
            for dur, rate in sched + [[1e7, sched[-1][1]]]:
                edges.append(edges[-1] + dur)
                mass.append(mass[-1] + dur * rate / self.n_pub)
            self.due = np.interp(unit, mass, edges)
        self.filler = filler_rows(seed, traffic["payload_bytes"])

    def topic(self, k):
        k %= len(self.ids)
        return self.template.format(i=int(self.ids[k]), j=int(self.js[k]))

    def seq(self, k):
        return k * self.n_pub + self.conn

    def payload(self, k):
        s = self.seq(k)
        return struct.pack(">Q", s) + self.filler[s % FILLER_ROWS]

    def crc(self, k):
        """What a subscriber computes over a delivery of message k."""
        return zlib.crc32(self.payload(k), zlib.crc32(self.topic(k).encode()))


def filler_rows(seed, payload_bytes):
    rows = np.random.default_rng([seed, 3]).integers(
        0, 256, size=(FILLER_ROWS, payload_bytes - 8), dtype=np.uint8)
    return [r.tobytes() for r in rows]
