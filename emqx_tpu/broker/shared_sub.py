"""Shared subscriptions: $share/<group>/<topic> load-balanced dispatch.

Parity with the reference (apps/emqx/src/emqx_shared_sub.erl:61-66
strategies, :234-285 pick logic): strategies random | round_robin | sticky |
hash_clientid | hash_topic, group membership registry, and one-of-N dispatch
per message per group. The reference's per-message ACK/NACK redispatch
(:118-130) maps to `dispatch` retrying the remaining members when a
deliverer raises.

A single real topic filter can carry several groups plus plain subscribers;
the broker routes the REAL filter and calls `dispatch_groups` alongside
normal fan-out.
"""

from __future__ import annotations

import random as _random
from typing import Dict, List, Optional, Tuple

from emqx_tpu.utils.tracepoints import tp


def stable_hash(s: Optional[str]) -> int:
    """FNV-1a 32-bit over the utf-8 bytes. Deterministic across runs and
    identical to the device-side pick input, unlike Python's randomized
    ``hash()`` (the reference uses erlang:phash2 the same way,
    emqx_shared_sub.erl:234-285)."""
    h = 0x811C9DC5
    for b in (s or "").encode("utf-8", "surrogatepass"):
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h


class _Group:
    __slots__ = ("members", "rr_index", "sticky_sid")

    def __init__(self, rr_start: int = 0) -> None:
        self.members: Dict[str, object] = {}  # sid -> Subscriber
        self.rr_index = rr_start
        self.sticky_sid: Optional[str] = None


class SharedSub:
    def __init__(self, strategy: str = "round_robin"):
        self.strategy = strategy
        # real_filter -> {group -> _Group}
        self._table: Dict[str, Dict[str, _Group]] = {}
        # running member count over every (real filter, group): count()
        # feeds a gauge on every SUBSCRIBE, so a walk of the table there
        # makes loading N shared subscriptions O(N^2). Only subscribe /
        # unsubscribe below may add or drop a member
        self._count = 0
        self._rng = _random.Random(0xEC0)
        # cluster mode: (real, group, msg) -> bool; exactly one member
        # node dispatches each message. Every member node already holds
        # the message (route forwarding), so rotating the dispatcher
        # per message balances the group cluster-wide with zero extra
        # RPC (the reference picks among cluster-wide members,
        # emqx_shared_sub.erl:234-285)
        self.leader_check = None
        # the leader check's answers since the broker last took them:
        # [led, deferred] (counters shared.leader.*, one add a batch);
        # written by the dispatching thread, the loop's in a served broker
        self.leader_counts = [0, 0]

    def _is_leader(self, real: str, group: str, msg=None) -> bool:
        lc = self.leader_check
        if lc is None:
            return True
        led = lc(real, group, msg)
        self.leader_counts[0 if led else 1] += 1
        return led

    # -- membership -------------------------------------------------------
    def subscribe(self, group: str, real: str, sub) -> bool:
        groups = self._table.setdefault(real, {})
        g = groups.get(group)
        created = False
        if g is None:
            # round_robin starts at a random member, as upstream's does
            # (emqx_shared_sub.erl:234-285: `rand:uniform(Count) - 1` for
            # a (group, topic) without a counter yet). A group is the pair
            # (group name, real filter): a service subscribed on a
            # thousand filters that see a message or two each would
            # otherwise hand every one of them to its first member
            g = groups[group] = _Group(self._rng.randrange(1 << 16))
            created = True
        if sub.sid not in g.members:
            self._count += 1
        g.members[sub.sid] = sub
        return created

    def unsubscribe(self, group: str, real: str, sid: str) -> Tuple[bool, bool]:
        """-> (removed, group_now_empty)"""
        groups = self._table.get(real)
        if not groups or group not in groups:
            return False, False
        g = groups[group]
        removed = g.members.pop(sid, None) is not None
        if removed:
            self._count -= 1
        if g.sticky_sid == sid:
            g.sticky_sid = None
        empty = not g.members
        if empty:
            del groups[group]
            if not groups:
                del self._table[real]
        return removed, empty

    def count(self) -> int:
        """Shared subscriptions held (members over all groups), O(1)."""
        return self._count

    def subscriptions(self) -> List[Tuple[str, str, object]]:
        out = []
        for real, groups in self._table.items():
            for gname, g in groups.items():
                for sub in g.members.values():
                    out.append(
                        (sub.client_id, f"$share/{gname}/{real}", sub.opts)
                    )
        return out

    def subscriptions_sids(self) -> List[Tuple[str, str]]:
        """(sid, original $share filter) pairs — worker-fabric cleanup."""
        out = []
        for real, groups in self._table.items():
            for gname, g in groups.items():
                for sid in g.members:
                    out.append((sid, f"$share/{gname}/{real}"))
        return out

    def route_filter(self, group: str, real: str) -> str:
        """The filter registered in the route table for a shared sub."""
        return real

    # -- dispatch ---------------------------------------------------------
    def _pick(self, g: _Group, msg) -> List[str]:
        """Ordered candidate sids: first is the pick, rest are failover."""
        sids = list(g.members.keys())
        if not sids:
            return []
        s = self.strategy
        if s == "random":
            self._rng.shuffle(sids)
            return sids
        if s == "sticky":
            if g.sticky_sid in g.members:
                first = g.sticky_sid
            else:
                first = self._rng.choice(sids)
                g.sticky_sid = first
            rest = [x for x in sids if x != first]
            return [first] + rest
        if s == "hash_clientid":
            i = stable_hash(msg.from_client) % len(sids)
        elif s == "hash_topic":
            i = stable_hash(msg.topic) % len(sids)
        else:  # round_robin
            i = g.rr_index % len(sids)
            g.rr_index += 1
        return sids[i:] + sids[:i]

    # -- device-pick delivery (the host half of SURVEY hard part (d)) ------
    def group(self, real: str, gname: str) -> Optional[_Group]:
        groups = self._table.get(real)
        return groups.get(gname) if groups else None

    def dispatch_picked(
        self, real: str, gname: str, idx: int, msg, hand=None,
        refused: Optional[int] = None,
    ) -> int:
        """Deliver to the device-picked member index, host keeping only
        ack/retry failover (emqx_shared_sub.erl:165-189 redispatch). The
        pick came from a table snapshot, so an out-of-range idx (members
        left since) just means failover order starts elsewhere.

        `hand(sub, msg, (real, gname, idx))` in the place of the member's
        deliverer: the broker's settle-time fan-out, which collects a
        connection's deliveries of one batch into a run
        (`Broker.DeliveryRuns.hand`). The member then counts as having
        taken the message; where its run gives the message back, the
        broker calls again with `refused`, the candidates from the pick
        on that already refused it: the failover goes on behind them, and
        the round-robin counter, advanced at the hand-over, stays."""
        g = self.group(real, gname)
        if g is None or not g.members:
            return 0
        if not self._is_leader(real, gname, msg):
            return 0  # another node's members own this message's pick
        sids = list(g.members.keys())
        i = idx % len(sids) if sids else 0
        candidates = sids[i:] + sids[:i]
        pick = (real, gname, idx) if hand is not None else None
        for sid in candidates[refused:] if refused else candidates:
            sub = g.members.get(sid)
            if sub is None:
                continue
            try:
                if hand is None:
                    sub.deliver(msg, sub.opts)
                else:
                    hand(sub, msg, pick)
                tp("shared.delivered", sid=sid, mid=str(msg.mid))
                if self.strategy == "sticky":
                    g.sticky_sid = sid
                elif self.strategy == "round_robin" and refused is None:
                    g.rr_index += 1
                return 1
            except Exception:
                tp("shared.nack", sid=sid, mid=str(msg.mid))
                continue
        return 0

    def dispatch_groups(self, real: str, msg) -> int:
        """Deliver to ONE member of each group subscribed at `real`.

        A deliverer raising is the NACK analog: the next candidate is tried
        (emqx_shared_sub redispatch, emqx_shared_sub.erl:165-189).
        """
        groups = self._table.get(real)
        if not groups:
            return 0
        n = 0
        for gname, g in groups.items():
            if not self._is_leader(real, gname, msg):
                continue  # another node's members own this message's pick
            for sid in self._pick(g, msg):
                sub = g.members.get(sid)
                if sub is None:
                    continue
                try:
                    sub.deliver(msg, sub.opts)
                    tp("shared.delivered", sid=sid, mid=str(msg.mid))
                    n += 1
                    break
                except Exception:
                    tp("shared.nack", sid=sid, mid=str(msg.mid))
                    continue  # NACK -> failover to next member
        return n

    def has_groups(self, real: str) -> bool:
        return real in self._table
