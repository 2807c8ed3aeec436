"""`$share` groups as data (harness/share.py). A share family's arithmetic at
the rehearsal size, at `share_1m`'s own and at BASELINE config 4's full
million; the tables of the accepted cells read what they read; the judge on
synthetic records: the reference's own one-of-N answer is correct, and every
control gives the verdict it must by the check it must; the harness's answer
per receiver class against the program's own `Broker` + `SharedSub` in
process, on its device path and its host path; whole CPU rehearsals of
`share_1m.sat`, sound and with the timed path broken underneath."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from harness import controls, share, verify  # noqa: E402
from harness.reference import Matcher  # noqa: E402
from harness.traffic import Table  # noqa: E402
from test_correct import AlteringProxy, rehearse  # noqa: E402


def load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


SHARE = load("configs", "share_1m")
# BASELINE config 4 before the cut to the run's time limit (PERF.md section 4)
FULL = {"subscribers": 1040, "id_space": 1000, "j_space": 250, "families": [
    {"share": "g{g}", "groups": 250, "members": 4, "filter": "device/{d}/+/{j}/#",
     "ids": 4, "per_id": 250},
    {"share": "svc{g}", "groups": 4, "members": 10, "filter": "device/#"}]}
TABLES = {"rehearsal": SHARE["rehearsal_table"], "cell": SHARE["table"], "full": FULL}


@pytest.mark.parametrize("size", sorted(TABLES))
def test_a_share_family_is_data(size):
    spec = TABLES[size]
    table = Table(spec, 77)
    fams = [f for f in spec["families"] if "share" in f]
    assert table.n_plain == 0 and table.n_sub == sum(
        f["groups"] * f["members"] for f in fams)
    # every connection is in exactly one class, a class is its first member
    assert len(table.groups) == sum(f["groups"] for f in fams)
    seen = np.zeros(table.n_sub, int)
    for first, members, name, real in table.groups:
        seen[first:first + members] += 1
        assert (table.class_of[first:first + members] == first).all()
        assert table.members_of[first] == members
    assert (seen == 1).all()
    # what goes on the wire is the class's real filters behind its own prefix
    classes = list(table.classes())
    assert [c for c, _, _ in classes] == [g[0] for g in table.groups]
    on_wire = 0
    for (cls, conns, real), (_, members, name, _) in zip(classes, table.groups):
        assert list(conns) == list(range(cls, cls + members))
        for s in (conns[0], conns[-1]):  # each member sends the same
            wire = table.filters_of(s)
            assert [share.parse(f) for f in wire] == [(name, r) for r in real]
        on_wire += members * len(real)
    assert table.n_filters() == on_wire
    assert table.n_class_filters() == sum(len(real) for _, _, real in classes)
    if size == "full":
        assert (table.n_filters(), table.n_class_filters()) == (1_000_040, 250_004)
    if size == "cell":
        assert table.n_filters() == SHARE["subscriptions"]
        assert table.n_sub + load("traffic", "share_1m.sat")["publishers"] \
            == SHARE["connections"]


@pytest.mark.parametrize("size", ["rehearsal", "cell"])
def test_no_class_holds_two_filters_that_match_one_topic(size):
    """And every topic of the cell's traffic is owed to its device's group and
    to every fleet-wide one."""
    spec = TABLES[size]
    table = Table(spec, 3)
    matcher = Matcher()
    for cls, _, real in table.classes():
        for flt in real:
            matcher.insert(flt, cls)
    assert matcher.count == table.n_class_filters()
    wide = sum(f["groups"] for f in spec["families"] if "ids" not in f)
    rng = np.random.default_rng(5)
    for d, j in zip(rng.integers(0, spec["id_space"], 400),
                    rng.integers(0, spec["j_space"], 400)):
        owners = matcher.match(f"device/{d}/mid/{j}/leaf")
        assert len(set(owners)) == len(owners) == 1 + wide


@pytest.mark.parametrize("name", ["mixed_1m", "fanout_1k", "cluster_4n"])
def test_a_plain_table_reads_what_it_read(name):
    """No share family: every subscriber is its own class, the reference is
    given what goes on the wire, and the counts are the parent's."""
    config = load("configs", name)
    table = Table(config["rehearsal_table"], 11)
    assert table.groups == [] and table.n_plain == table.n_sub
    assert (table.class_of == np.arange(table.n_sub)).all()
    assert [(c, list(conns), f) for c, conns, f in table.classes()] == \
        [(s, [s], table.filters_of(s)) for s in range(table.n_sub)]
    assert table.n_class_filters() == table.n_filters() == sum(
        len(table.filters_of(s)) for s in range(table.n_sub))
    assert Table(config["table"], 11).n_filters() == config["subscriptions"]


@pytest.mark.parametrize("flt", [
    "device/#", "$share/g1/device/1/+/2/#", "$share/svc0/device/#", "$share/a/b",
    "$sharex/g/t", "$SYS/brokers", "$share/g//t", "$share/+/t", "$share/g", "$share//t",
    "$share/g/", "$queue/t"])
def test_the_prefix_parser_is_the_programs(flt):
    from emqx_tpu.ops import topics as T
    try:
        want = T.parse_share(flt)
    except T.TopicValidationError:
        with pytest.raises(ValueError):
            share.parse(flt)
    else:
        assert share.parse(flt) == want
        if want[0] is not None:
            assert share.wire(*want) == flt


def synthetic(table_spec, traffic_name, seed=9, per_conn=400):
    """A run that never was: every publisher sent `per_conn` messages, all
    acknowledged, the window over all of them, the device served everything.
    -> judge(control) with the reference's one-of-N answer, or a control, as
    what the sockets received."""
    traffic = load("traffic", traffic_name)
    traffic.update(traffic.get("rehearsal", {}))
    table = Table(table_spec, seed)
    matcher = Matcher()
    for cls, _, real in table.classes():
        for flt in real:
            matcher.insert(flt, cls)
    pubs = [{"conns": {c: {"send_t": np.full(per_conn, 1.0),
                           "ack_t": np.full(per_conn, 1.5), "due_t": None}
                       for c in range(traffic["publishers"])}}]
    proms = [({"emqx_messages_received": 0.0, "emqx_messages_routed_device": 0.0},
              {"emqx_messages_received": 100.0, "emqx_messages_routed_device": 100.0})]

    nothing = {"seq": np.zeros(0, np.int64), "crc": np.zeros(0, np.uint32),
               "read_sub": np.zeros(0, np.int32), "read_n": np.zeros(0, np.int32),
               "read_t": np.zeros(0), "dup_at": np.zeros(0, np.int64)}

    def judge(control):
        return verify.judge(matcher, table, traffic, seed, pubs, [nothing],
                            (0.0, 2.0), proms, {}, control=control)
    return table, judge


def own_answer(table):
    return controls.round_robin if table.groups else \
        (lambda keys, crc, fan, table: controls._as_received(keys, crc))


SIX = ["missing", "unexpected", "corrupt", "unacked", "broker_faults",
       "device_share_min"]


def test_the_references_own_one_of_n_answer_is_correct():
    table, judge = synthetic(SHARE["rehearsal_table"], "share_1m.sat")
    j = judge(own_answer(table))
    assert list(j["checks"]) == SIX + ["share_member_share_max"]
    assert j["correct"] is True and j["failed"] == 0 and j["attempted"] > 0
    assert j["window_fan_mean"] == 3.0  # its device's group and two services
    value, limit = j["checks"]["share_member_share_max"]
    assert 1.0 <= value < 1.05 and limit == 1.5
    assert j["share"]["groups"] == j["share"]["groups_receiving"] == 6
    top = j["share"]["fullest_group"]
    assert top["name"].startswith("svc")
    assert top["fullest_member"] - top["lightest_member"] <= 1


FAILS_BY = {"every_member": {"unexpected"},
            "one_member": {"share_member_share_max"},
            "at_most_once": {"missing", "share_member_share_max"},
            "stale_table": {"missing", "share_member_share_max"},
            "altered_payload": {"corrupt", "share_member_share_max"}}


@pytest.mark.parametrize("name", sorted(controls.CONTROLS))
def test_every_control_fails_by_the_check_it_must(name):
    """`every_member` by `unexpected`, `one_member` by the members' shares
    alone; the older three are the answer made to each group's first member
    with one guarantee broken, so the shares fail beside their own check."""
    assert sorted(FAILS_BY) == sorted(controls.CONTROLS)
    _, judge = synthetic(SHARE["rehearsal_table"], "share_1m.sat")
    j = judge(controls.CONTROLS[name])
    failed = {k for k, (v, lim) in j["checks"].items()
              if (v < lim if k.endswith("_min") else v > lim)}
    assert j["correct"] is False and failed == FAILS_BY[name]
    if name == "every_member":
        assert j["checks"]["missing"][0] == 0
        # one member of each group was owed it: the others are all unexpected
        assert j["checks"]["unexpected"][0] == j["sent_total"] * (1 + 2 * 2)
        assert j["checks"]["share_member_share_max"][0] == pytest.approx(1.0)
    if name == "one_member":
        assert j["checks"]["share_member_share_max"][0] == 3.0  # a service's first of 3


@pytest.mark.parametrize("cell", ["mixed_1m.sat", "fanout_1k.sat", "cluster_4n.sat"])
def test_an_accepted_cell_keeps_its_six_checks_and_its_controls(cell):
    config = load("configs", cell.split(".")[0])
    table, judge = synthetic(config["rehearsal_table"], cell)
    j = judge(own_answer(table))
    assert list(j["checks"]) == SIX and j["correct"] is True and "share" not in j
    assert [lim for _, lim in j["checks"].values()] == \
        [0, 0, 0, 0, 0, load("traffic", cell)["device_share_min"]]
    for name in ("at_most_once", "stale_table", "altered_payload"):
        assert judge(controls.CONTROLS[name])["correct"] is False
    for name in ("every_member", "one_member"):  # no group to break
        with pytest.raises(ValueError):
            judge(controls.CONTROLS[name])


@pytest.mark.parametrize("path", ["device", "host"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_class_level_answer_is_the_programs_own(path, seed):
    """`Broker` + `SharedSub` in process on the CPU, `round_robin`, a seeded
    table of a few groups: every message once per matching group, to a member,
    the members' counts within one of each other (the device path picks from
    a base synced once a batch: within one per batch published)."""
    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.message import Message
    from emqx_tpu.mqtt import packet as pkt

    rng = np.random.default_rng(seed)
    spec = {"subscribers": 0, "id_space": 8, "j_space": 4, "families": [
        {"share": "g{g}", "groups": int(rng.integers(2, 5)),
         "members": int(rng.integers(2, 4)), "filter": "device/{d}/+/{j}/#",
         "ids": 2, "per_id": 4},
        {"share": "svc{g}", "groups": int(rng.integers(1, 4)),
         "members": int(rng.integers(2, 6)), "filter": "device/#"}]}
    spec["subscribers"] = sum(f["groups"] * f["members"] for f in spec["families"])
    table = Table(spec, seed)
    matcher = Matcher()
    for cls, _, real in table.classes():
        for flt in real:
            matcher.insert(flt, cls)
    broker, got = Broker(), []
    assert broker.shared.strategy == "round_robin"
    for s in range(table.n_sub):
        for flt in table.filters_of(s):
            broker.subscribe(f"s{s}", f"c{s}", flt, pkt.SubOpts(qos=1),
                             lambda msg, opts, s=s: got.append((s, int(msg.payload))))
    assert broker.subscription_count() == table.n_filters()
    # ids 0..7 of which the table covers the first 2 * groups
    topics = [f"device/{rng.integers(0, 8)}/mid/{rng.integers(0, 4)}/leaf"
              for _ in range(600)]
    msgs = [Message(topic=t, payload=b"%d" % i, qos=1) for i, t in enumerate(topics)]
    batches = 3
    if path == "device":
        for part in np.array_split(np.arange(len(msgs)), batches):
            broker.publish_batch([msgs[i] for i in part])
        assert broker.metrics.snapshot()["messages.routed.device"] == len(msgs)
    else:
        for m in msgs:
            broker.publish(m)
    want = sorted((cls, i) for i, t in enumerate(topics) for cls in matcher.match(t))
    assert sorted((int(table.class_of[s]), i) for s, i in got) == want
    # upstream's group is the pair (group name, real filter), each with a
    # round-robin counter of its own: the balance within one holds per pair
    wide = {g[0] for g in table.groups if len(g[3]) == 1}
    per_pair = {}
    for s, i in got:
        cls = int(table.class_of[s])
        pair = (cls, None if cls in wide else topics[i])
        per_pair.setdefault(pair, np.zeros(table.members_of[cls], int))[s - cls] += 1
    for mine in per_pair.values():
        assert mine.max() - mine.min() <= (batches if path == "device" else 1)
    value, counts = share.member_share(np.array([s for s, _ in got]), table)
    assert counts["groups_receiving"] <= counts["groups"] == len(table.groups)
    assert value < 1.25


def test_a_rehearsal_of_share_1m_sat_is_correct_end_to_end():
    """Its warm-up as a first run's: the first stage is held beyond its
    second until its bucket has served a batch, the second gives up on a
    bucket that never fills."""
    def traffic(t):
        t.update(drain_s=6.0, settle_s=1.0, warmup=[
            {"in_flight": 4, "seconds": 1.0, "bucket": 256, "cold_s": 30.0},
            {"in_flight": 4, "seconds": 1.0, "bucket": 8192, "cold_s": 1.0}])
    r = rehearse("share_1m.sat", {"traffic": traffic})
    assert r["counts"]["batches_per_bucket"]["warm_up"]["256"] >= 1
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert list(r["checks"]) == SIX + ["share_member_share_max"]
    assert r["counts"]["window_fan_mean"] == 3.0
    assert r["counts"]["deliveries_total"] == 3 * r["counts"]["sent_total"]
    assert r["counts"]["share"]["groups_receiving"] == 6
    assert r["checks"]["share_member_share_max"]["value"] < 1.5


def test_the_balance_broken_underneath_is_not_correct():
    """The program's own `sticky` strategy in `round_robin`'s place: every
    group still gets every message once; only the members' shares tell."""
    def sticky(config):
        config["broker"]["shared_subscription"]["strategy"] = "sticky"
    r = rehearse("share_1m.sat", {"config": sticky})
    failed = [k for k, c in r["checks"].items() if k != "device_share_min"
              and c["value"] > c["limit"]]
    assert r["correct"] is False and failed == ["share_member_share_max"]
    assert r["checks"]["share_member_share_max"]["value"] > 1.9


def test_an_altered_delivery_to_a_member_is_not_correct():
    proxies = {}

    def via(port):
        if port not in proxies:
            proxies[port] = AlteringProxy(port)
        return proxies[port].port
    r = rehearse("share_1m.sat", {"subscriber_port": via})
    assert sum(p.altered for p in proxies.values()) >= 1
    assert r["checks"]["corrupt"]["value"] >= 1 and r["correct"] is False
