"""The comparison that decides `correct`, and the reduction from the
generators' records to the end-to-end metrics. The reference (reference.py)
answers every message the run sent, on the seed's own table; what subscriber
sockets received is compared with it as multisets per receiver class
(traffic.py, Table: a plain subscriber is its own class, a `$share` group's
members are one, so `missing` is also a group that did not get a message owed
to it and `unexpected` a second member that got it too). Exact: every limit is
0, except the share of the window's messages the device routed, which has the
cell's own floor (traffic file, `device_share_min`), and, where the traffic
file states it, the fullest member's share of its group's deliveries
(`share_member_share_max`, share.py)."""

import numpy as np

from harness import share
from harness.traffic import Stream

SEQ_BITS = 40  # a delivery's key: receiver class << SEQ_BITS | sequence number
SEQ_MASK = (1 << SEQ_BITS) - 1

# any of these above zero means the degrade ladder, a shed, a drop or an
# error carried part of the run: the device did not serve it whole
MUST_BE_ZERO = (
    "messages.routed.device_fallback", "degrade.fallback.batches",
    "degrade.trips.device", "degrade.retries", "degrade.state.device",
    "device.warmup.failed", "ingest.launch.errors", "ingest.dispatch.errors",
    "ingest.shed", "slo.shed", "messages.dispatch_error", "delivery.errors",
    "messages.dropped", "fabric.flush.errors", "fabric.parked.dropped",
    "provenance.proxy", "messages.forward.failed",
)


def prom_name(series):
    return "emqx_" + series.replace(".", "_")


def expected(matcher, table, traffic, seed, sent):
    """sent: {conn: messages sent}. -> (keys of every delivery due, crc by
    sequence number, matches per message by sequence number, topic bytes by
    sequence number)."""
    n_pub = traffic["publishers"]
    n_seq = max(sent.values()) * n_pub
    crc = np.zeros(n_seq, np.uint32)
    fan = np.zeros(n_seq, np.int32)
    topic_bytes = np.zeros(n_seq, np.int32)
    cache, subs, seqs = {}, [], []
    for conn, count in sent.items():
        st = Stream(traffic, table, seed, conn)
        for k in range(count):
            topic = st.topic(k)
            owners = cache.get(topic)
            if owners is None:
                owners = cache[topic] = np.array(
                    matcher.match(topic), np.int64)
            s = st.seq(k)
            crc[s] = st.crc(k)
            fan[s] = len(owners)
            topic_bytes[s] = len(topic)
            subs.append(owners)
            seqs.append(np.full(len(owners), s, np.int64))
    keys = (np.concatenate(subs) << SEQ_BITS) | np.concatenate(seqs)
    return keys, crc, fan, topic_bytes


def received(sub_results):
    """-> (keys by connection, not yet by class; crc; receipt time;
    redelivered) over every delivery the subscriber sockets got; DUP-flagged
    redeliveries (legal under QoS1) are counted and left out of the
    multisets."""
    keys, crcs, ts, dups = [], [], [], 0
    for r in sub_results:
        keep = np.ones(len(r["seq"]), bool)
        keep[r["dup_at"]] = False
        dups += len(r["dup_at"])
        sub = np.repeat(r["read_sub"].astype(np.int64), r["read_n"])
        keys.append(((sub << SEQ_BITS) | r["seq"])[keep])
        crcs.append(r["crc"][keep])
        ts.append(np.repeat(r["read_t"], r["read_n"])[keep])
    return np.concatenate(keys), np.concatenate(crcs), np.concatenate(ts), dups


def compare(exp_keys, exp_crc, got_keys, got_crc):
    """-> (missing, unexpected, corrupt, sequence numbers short of a delivery)."""
    both = np.concatenate([exp_keys, got_keys])
    uniq, inv = np.unique(both, return_inverse=True)
    diff = np.bincount(inv[:len(exp_keys)], minlength=len(uniq)) \
        - np.bincount(inv[len(exp_keys):], minlength=len(uniq))
    seq = got_keys & SEQ_MASK
    known = seq < len(exp_crc)
    corrupt = int((~known).sum()
                  + (got_crc[known] != exp_crc[seq[known]]).sum())
    short = uniq[diff > 0] & SEQ_MASK
    return (int(diff[diff > 0].sum()), int(-diff[diff < 0].sum()), corrupt,
            short)


def percentile(sorted_values, q):
    """Nearest-rank percentile of an ascending array."""
    i = min(len(sorted_values) - 1,
            max(0, int(np.ceil(q / 100.0 * len(sorted_values))) - 1))
    return float(sorted_values[i])


def judge(matcher, table, traffic, seed, pub_results, sub_results, window,
          prom_windows, prom_after, want=None, control=None, rehearsal=False):
    """-> dict with `checks` {name: [value, limit]}, `correct`, `attempted`,
    `failed`, the end-to-end values and what the roofline needs.
    `prom_windows` has, for each broker node, its counters at the window's
    two edges (the device's share is of the window's messages, and the
    lowest node's own share is held to the floor); `prom_after`, the nodes'
    counters added up at the end of the drain, has those that must read zero
    over the whole run. `control`
    names a guarantee to break in the reference's own answer put in the
    program's place (tests and my chip runs only; see controls.py)."""
    t_open, t_close = window
    n_pub = traffic["publishers"]
    sent = {c: len(r["send_t"]) for p in pub_results
            for c, r in p["conns"].items()}
    n_seq = max(sent.values()) * n_pub
    ref_t = np.full(n_seq, np.nan)   # due time (open loop) or send time
    ack_t = np.full(n_seq, np.nan)
    was_sent = np.zeros(n_seq, bool)
    for p in pub_results:
        for c, r in p["conns"].items():
            idx = np.arange(sent[c]) * n_pub + c
            was_sent[idx] = True
            ack_t[idx] = r["ack_t"]
            ref_t[idx] = r["send_t"]
            if r["due_t"] is not None:
                due = ~np.isnan(r["due_t"])
                ref_t[idx[due]] = r["due_t"][due]
    # `want`: the reference's answer where the drain already computed it
    exp_keys, exp_crc, fan, topic_bytes = want if want is not None \
        else expected(matcher, table, traffic, seed, sent)
    got_keys, got_crc, got_t, redelivered = received(sub_results)
    if control is not None:
        got_keys, got_crc, got_t = control(exp_keys, exp_crc, fan, table)
    got_subs = got_keys >> SEQ_BITS
    got_keys = (table.class_of[got_subs] << SEQ_BITS) | (got_keys & SEQ_MASK)
    missing, unexpected, corrupt, short = compare(
        exp_keys, exp_crc, got_keys, got_crc)
    unacked = int((was_sent & np.isnan(ack_t)).sum())

    def device_share(at_open, at_mark):
        def delta(series):
            return at_mark.get(prom_name(series), 0.0) \
                - at_open.get(prom_name(series), 0.0)
        rx = delta("messages.received")
        return delta("messages.routed.device") / rx if rx else 0.0

    faults = {k: prom_after[prom_name(k)] for k in MUST_BE_ZERO
              if prom_after.get(prom_name(k), 0.0) != 0.0
              and not (rehearsal and k == "provenance.proxy")}

    in_win = was_sent & (ref_t >= t_open) & (ref_t < t_close)
    bad = np.zeros(n_seq, bool)
    bad[short[short < n_seq]] = True
    bad |= np.isnan(ack_t)
    out = {
        "attempted": int(in_win.sum()),
        "failed": int((in_win & bad).sum()),
        "sent_total": int(was_sent.sum()),
        "deliveries_total": int(len(got_keys)),
        "redelivered": redelivered,
        "broker_faults": faults,
        "window_fan_mean": float(fan[in_win].mean()) if in_win.any() else 0.0,
        "window_topic_bytes_mean":
            float(topic_bytes[in_win].mean()) if in_win.any() else 0.0,
    }
    got_seq = got_keys & SEQ_MASK
    ok_seq = got_seq < n_seq
    in_window_rx = (got_t >= t_open) & (got_t < t_close)
    out["deliveries_in_window"] = int(in_window_rx.sum())
    out["deliveries_by_second"] = np.bincount(
        (got_t[in_window_rx] - t_open).astype(np.int64),
        minlength=int(np.ceil(t_close - t_open))).tolist()
    lat = (got_t[ok_seq] - ref_t[got_seq[ok_seq]])[in_win[got_seq[ok_seq]]]
    # a message that failed counts as beyond any limit: one +inf per failure
    lat = np.sort(np.concatenate(
        [lat, np.full(out["failed"], np.inf)])) * 1e3
    out["latency_ms_sorted"] = lat
    ok = got_seq[ok_seq]  # for `stages`: every delivery's due time and latency
    out["delivery_ref_t"], out["delivery_lat_s"] = ref_t[ok], got_t[ok_seq] - ref_t[ok]
    out["checks"] = {
        "missing": [missing, 0],
        "unexpected": [unexpected, 0],
        "corrupt": [corrupt, 0],
        "unacked": [unacked, 0],
        "broker_faults": [len(faults), 0],
        "device_share_min": [min(device_share(*w) for w in prom_windows),
                             traffic["device_share_min"]],
    }
    if "share_member_share_max" in traffic:
        value, out["share"] = share.member_share(got_subs, table)
        out["checks"]["share_member_share_max"] = [
            value, traffic["share_member_share_max"]]
    out["correct"] = all(v >= lim if name.endswith("_min") else v <= lim
                         for name, (v, lim) in out["checks"].items())
    return out


def stages(j, schedule, t_loop):
    """A rate schedule's stages, one row each: the rate offered, deliveries
    due, their p50 and p99 latency from the due time, and the median latency
    of the stage's first and last quarter (a backlog that grows shows there)."""
    rows, t = [], t_loop
    ref, lat = j["delivery_ref_t"], j["delivery_lat_s"]
    for dur, rate in schedule:
        def med(lo, hi):
            x = lat[(ref >= lo) & (ref < hi)]
            return float(np.median(x)) * 1e3 if len(x) else None
        x = np.sort(lat[(ref >= t) & (ref < t + dur)]) * 1e3
        rows.append({
            "rate_msgs_per_s": rate, "seconds": dur, "deliveries": len(x),
            "p50_ms": percentile(x, 50) if len(x) else None,
            "p99_ms": percentile(x, 99) if len(x) else None,
            "first_quarter_p50_ms": med(t, t + dur / 4),
            "last_quarter_p50_ms": med(t + 3 * dur / 4, t + dur)})
        t += dur
    return rows
