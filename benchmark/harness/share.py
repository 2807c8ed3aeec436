"""The receiver semantics a deployment of `$share` groups brings, and the
only place the harness knows them: a group's members all SUBSCRIBE
`$share/<group>/<real filter>`, and a message that matches the real filter is
owed to the group once, to any one member. Table (traffic.py) lays the
families out through `groups`, the judge (verify.py) reads the balance
through `member_share`; everything else sees receiver classes (Table's
docstring) and no group. Imports nothing of the program: the prefix is taken
apart here."""

import numpy as np

PREFIX = "$share/"
# a group's balance is judged once it has this many deliveries (the
# configuration's guarantee states the same number)
GROUP_MIN_DELIVERIES = 1000


def parse(flt):
    """`$share/<group>/<real filter>` -> (group, real filter); a plain
    filter -> (None, filter)."""
    if not flt.startswith(PREFIX):
        return None, flt
    group, sep, real = flt[len(PREFIX):].partition("/")
    if not sep or not group or not real or "+" in group or "#" in group:
        raise ValueError(f"not a shared subscription: {flt!r}")
    return group, real


def wire(group, real):
    """What a member of `group` sends in its SUBSCRIBE."""
    return f"{PREFIX}{group}/{real}"


def groups(families, first):
    """The share families of a table, laid out from connection `first` on,
    family by family and group by group: -> [(first member's connection,
    members, group name, real filters)]. A share family is {"share": "<name
    template over {g}>", "groups": G, "members": M, "filter": "<template over
    {d} and {j}>", "ids": I, "per_id": J}: group g of the family covers the
    device ids g*I .. g*I+I-1 and holds the real filters with {d} over them
    and {j} = 0..J-1 (`ids`, `per_id`: 1 where not given, for a filter that
    names neither)."""
    out = []
    for fam in families:
        if "share" not in fam:
            continue
        ids, per_id = fam.get("ids", 1), fam.get("per_id", 1)
        for g in range(fam["groups"]):
            out.append((first, fam["members"], fam["share"].format(g=g), [
                fam["filter"].format(d=d, j=j)
                for d in range(g * ids, (g + 1) * ids) for j in range(per_id)]))
            first += fam["members"]
    return out


def member_share(subs, table):
    """`subs`: the receiving connection of every delivery of the run. ->
    (over every group with GROUP_MIN_DELIVERIES or more, or the fullest group
    where none has as many, the largest of: the fullest member's share of its
    group's deliveries times the member count, 1.0 = even; counts for the
    result's line). Groups are contiguous blocks of connections after the
    plain subscribers, in `table.groups`' order."""
    firsts = np.array([g[0] for g in table.groups], np.int64)
    members = np.array([g[1] for g in table.groups], np.int64)
    per_member = np.bincount(subs, minlength=table.n_sub)[:table.n_sub]
    total = np.add.reduceat(per_member, firsts)
    fullest = np.maximum.reduceat(per_member, firsts)
    if not total.max():
        return 0.0, {"groups": len(firsts), "groups_receiving": 0}
    judged = total >= min(GROUP_MIN_DELIVERIES, total.max())
    top = int(np.argmax(total))
    of_top = per_member[firsts[top]:firsts[top] + members[top]]
    return float((fullest[judged] * members[judged] / total[judged]).max()), {
        "groups": len(firsts), "groups_receiving": int((total > 0).sum()),
        "groups_judged": int(judged.sum()),
        "fullest_group": {"name": table.groups[top][2], "deliveries": int(total[top]),
                          "lightest_member": int(of_top.min()),
                          "fullest_member": int(of_top.max())}}
