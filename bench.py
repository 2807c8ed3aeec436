"""North-star benchmark: wildcard route-match throughput + latency on TPU.

Sweeps the BASELINE.md configs (the reference's emqx_broker_bench analog,
apps/emqx/src/emqx_broker_bench.erl:25-33, scaled up):

  exact_1k    — 1k exact-topic subs (BASELINE config 1)
  plus_100k   — 100k subs, 10% single-level '+', 8-level topics (config 2)
  mixed_1m    — 1M subs, reference bench shape device/{id}/+/{num}/# plus
                broad 'device/{id}/#' overlays, Zipf-distributed publish
                topics, real fan-out (config 3)
  share_10m   — 10M wildcard subs with 8 subscriber slots per filter, so
                every match pays an 8-bit fan-out bitmap OR (config 4 at
                the north-star 10M scale; $share pick itself is
                host-side). This is the HEADLINE metric.

For each: sustained throughput (per-batch dispatch of the fused
shape_route_step — the serving-path engine: tokenize -> shape-hash match
(O(#shapes) fused-row probes, ops/shape_index.py) -> residual NFA walk when
needed -> subscriber-bitmap fanout -> stats, inputs staged in HBM) and
per-batch latency percentiles (p50/p99 of dispatch + block_until_ready).

Baseline: the same workload walked topic-by-topic on the CPU trie
(`emqx_tpu.broker.trie.TopicTrie`), the in-process semantics-equivalent of
the reference's per-message ETS walk. (The BEAM/ETS original is not runnable
in this image; `detail.baseline` names the proxy.)

Also measured: insert rate into the incremental RouteIndex (delta-overlay
path — inserts are O(words), not O(table); emqx_trie.erl:66-119 analog) and
single-subscribe device-sync latency.

Runs only on a TPU: every process that opens the device checks
`jax.devices()[0].platform == "tpu"` first and exits non-zero naming the
platform it found otherwise. The parent (`python bench.py`) never
imports jax — one process per chip — and takes the device string and
fingerprint from its sweep child's JSON.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "fingerprint_key": ..., ...}
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import sys
import time
from typing import Optional

import numpy as np

# -- persistent caches -------------------------------------------------------
# The sweep's wall is dominated by rebuilding identical artifacts every
# run: 10M-filter table builds and in-process Python-trie CPU baselines.
# Both are deterministic functions of the workload definition, so they
# cache on disk keyed by a fingerprint of the defining source +
# parameters; any code change invalidates the key and the artifact
# rebuilds. A cold cache still completes (the budget skip logic below is
# unchanged) — the cache only decides HOW MUCH of the sweep fits the
# budget. XLA's own compilation cache is NOT placed here: see
# emqx_tpu/compile_cache.py (JAX_COMPILATION_CACHE_DIR, else one fixed
# path in the checkout).
CACHE_DIR = os.environ.get(
    "BENCH_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache"),
)


def _open_device() -> dict:
    """First thing every device-owning entry does: place the compile
    cache, open the backend, and refuse anything but a TPU. Returns the
    hardware fingerprint."""
    from emqx_tpu.compile_cache import place_compile_cache
    from emqx_tpu.observe.provenance import fingerprint

    place_compile_cache()
    fp = fingerprint()
    if fp["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; jax found platform "
            f"{fp['platform']!r} ({fp['device_kind']}). Run it on the "
            "chip; a CPU run yields no number of record."
        )
    return fp


def _cache_path(tag: str, *fingerprint) -> str:
    h = hashlib.sha256()
    for part in fingerprint:
        h.update(repr(part).encode())
    os.makedirs(CACHE_DIR, exist_ok=True)
    return os.path.join(CACHE_DIR, f"{tag}-{h.hexdigest()[:16]}")


def _cache_get_json(path: str):
    try:
        with open(path + ".json") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _cache_put_json(path: str, obj) -> None:
    tmp = f"{path}.json.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path + ".json")


BATCH = 8192
MAX_BYTES = 64
CFG = dict(max_levels=8, frontier=16, max_matches=16, probes=8)
CPU_SAMPLE = 10_000
TIMED_BATCHES = 24
REPEATS = 3
LAT_BATCHES = 16
# full-sweep wall budget (the driver kills the whole run at its own gate
# timeout; r3's lesson is to NEVER let one config starve the capture).
# Each config emits a BENCH_PARTIAL stderr line the moment it completes,
# and main() skips remaining configs when the budget is nearly spent.
BUDGET_S = float(__import__("os").environ.get("BENCH_BUDGET_S", 1100))

_T0 = time.perf_counter()


def _mark(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _workload_fingerprint():
    """Anything that defines a config's workload: a change rebuilds."""
    return (
        inspect.getsource(build_config),
        inspect.getsource(_build_mixed_10m),
        BATCH, TIMED_BATCHES, CPU_SAMPLE, MAX_BYTES,
        sorted(CFG.items()),
    )


def _tables_fingerprint():
    """Everything the device tables are a function of (module sources):
    a change to any indexing/kernel code invalidates cached tables."""
    from emqx_tpu.models import router_model
    from emqx_tpu.ops import nfa, route_index, shape_index, tokenizer

    return tuple(
        inspect.getsource(m)
        for m in (route_index, shape_index, nfa, tokenizer, router_model)
    )


def _zipf_ids(rng, n, k):
    """n Zipf-ish ids in [0, k) (heavy head, long tail)."""
    z = rng.zipf(1.3, size=n)
    return np.minimum(z - 1, k - 1)


def build_config(name, rng):
    """-> (filters, topics, subs_per_filter)."""
    if name == "exact_1k":
        filters = [f"sensor/{i}/state" for i in range(1000)]
        ids = rng.integers(0, 1000, size=BATCH * TIMED_BATCHES)
        topics = [f"sensor/{i}/state" for i in ids]
        return filters, topics, 1
    if name == "plus_100k":
        # 90k exact 8-level + 10k single-'+' filters over the same space
        filters = []
        for i in range(90_000):
            a, b, c, d = i % 30, (i // 30) % 50, (i // 1500) % 60, i // 90_000 + i % 7
            filters.append(f"org/{a}/dev/{b}/ch/{c}/m/{d}")
        for i in range(10_000):
            a, b, c = i % 30, (i // 30) % 50, i % 60
            lvl = i % 4
            parts = ["org", str(a), "dev", str(b), "ch", str(c), "m", str(i % 7)]
            parts[1 + 2 * lvl] = "+"
            filters.append("/".join(parts))
        aa = rng.integers(0, 30, size=BATCH * TIMED_BATCHES)
        bb = rng.integers(0, 50, size=BATCH * TIMED_BATCHES)
        cc = rng.integers(0, 60, size=BATCH * TIMED_BATCHES)
        dd = rng.integers(0, 7, size=BATCH * TIMED_BATCHES)
        topics = [
            f"org/{a}/dev/{b}/ch/{c}/m/{d}" for a, b, c, d in zip(aa, bb, cc, dd)
        ]
        return filters, topics, 1
    if name == "mixed_1m":
        # reference bench shape at 1M + broad '#' overlays for fan-out
        filters = [
            f"device/{i}/+/{j}/#" for i in range(1000) for j in range(1000)
        ]
        filters += [f"device/{i}/#" for i in range(100)]  # hot-id overlays
        ids = _zipf_ids(rng, BATCH * TIMED_BATCHES, 1000)
        nums = rng.integers(0, 1000, size=BATCH * TIMED_BATCHES)
        topics = [f"device/{i}/mid/{j}/leaf" for i, j in zip(ids, nums)]
        return filters, topics, 1
    if name == "share_10m":
        # the north-star scale (BASELINE config 4): 10M wildcard subs,
        # 8 subscriber slots per filter = the $share-group fan-out load
        # at the routing plane
        filters = [
            f"device/{i}/+/{j}/#"
            for i in range(10_000)
            for j in range(1000)
        ]
        ids = _zipf_ids(rng, BATCH * TIMED_BATCHES, 10_000)
        nums = rng.integers(0, 1000, size=BATCH * TIMED_BATCHES)
        topics = [f"device/{i}/mid/{j}/leaf" for i, j in zip(ids, nums)]
        return filters, topics, 8
    if name == "mixed_10m":
        return _build_mixed_10m(rng)
    raise ValueError(name)


def _build_mixed_10m(rng):
    """Shape-DIVERSE 10M-subscription table (r2 verdict item 2):

    - 66 distinct wildcard shapes over an 8-level space: 2 dense overlay
      families every topic matches (guaranteeing matches/topic >= 2) +
      64 sparse mask families ('+' and '#' in varying positions/depths)
    - the last 2 families overflow the 64-shape device table, forcing
      the residual-NFA engine onto the hot path for every batch
    - publish topics Zipf over the FULL id space
    """
    n_topics = BATCH * TIMED_BATCHES
    A, C = 10_000, 100
    filters = [f"v/{a}/#" for a in range(A)]  # dense overlay 1
    filters += [  # dense overlay 2: matches every topic with c < C
        f"v/{a}/+/{c}/#" for a in range(A) for c in range(C)
    ]
    # 64 sparse mask families over levels [v, a, b, c, d, e, f, g].
    # Validity: every '+' position must be < depth (a wildcard past the
    # filter's last level silently collapses the shape into a shallower
    # family); families dedupe on (positions, depth). (2,) at depth 4
    # is excluded — it IS dense overlay 2's shape.
    cands = []
    for plus_pos in (1, 2, 3, 4, 5, 6):
        for depth in (4, 5, 6, 7, 8):
            cands.append(((plus_pos,), depth))
    for combo in ((1, 3), (2, 4), (1, 4), (2, 5), (3, 5), (1, 5), (3, 6),
                  (4, 6), (2, 6), (1, 6)):
        for depth in (6, 7, 8):
            cands.append((combo, depth))
    for combo in ((1, 3, 5), (2, 4, 6), (1, 2, 4), (3, 4, 6), (1, 4, 6),
                  (2, 3, 5), (1, 3, 6), (2, 4, 5), (1, 2, 5), (2, 3, 6)):
        for depth in (7, 8):
            cands.append((combo, depth))
    seen = {(frozenset((2,)), 4)}  # overlay 2's shape
    masks = []
    for plus, depth in cands:
        key = (frozenset(plus), depth)
        if max(plus) < depth and key not in seen:
            seen.add(key)
            masks.append((tuple(plus), depth))
    masks = masks[:64]
    assert len(masks) == 64, len(masks)
    id_digits = [A, 500, C, 400, 300, 200, 100]  # per-level id spaces
    # family sizes are bounded by each family's literal-tuple space —
    # shallow wildcard families simply cannot carry 150k DISTINCT
    # filters — so the sparse budget is distributed space-aware and the
    # roomy (deep) families absorb the remainder. Levels draw ids
    # INDEPENDENTLY (a single shared draw makes the tuple periodic with
    # the lcm of the digit spaces and nearly every filter a duplicate).
    budget = 10_000_000 - len(filters)
    per_family = budget // 64
    spaces = []
    for plus, depth in masks:
        sp = 1
        for lvl in range(1, depth):
            if lvl not in plus:
                sp *= id_digits[min(lvl - 1, 6)]
        spaces.append(sp)
    sizes = [min(per_family, max(1000, sp // 2)) for sp in spaces]
    shortfall = budget - sum(sizes)
    roomy = [i for i, sp in enumerate(spaces) if sp > 20 * per_family]
    for i in roomy:
        sizes[i] += shortfall // len(roomy)
    # last two families stay smaller so the residual NFA (where they
    # land after the 64-shape device table fills) builds quickly
    sizes[62] = min(sizes[62], 50_000)
    sizes[63] = min(sizes[63], 50_000)
    for fam, ((plus, depth), sz) in enumerate(zip(masks, sizes)):
        cols = {
            lvl: rng.integers(0, id_digits[min(lvl - 1, 6)], size=sz)
            for lvl in range(1, depth)
            if lvl not in plus
        }
        for k in range(sz):
            parts = ["v"]
            for lvl in range(1, depth):
                parts.append("+" if lvl in plus else str(cols[lvl][k]))
            if depth < 8:
                parts.append("#")
            filters.append("/".join(parts))
    aa = _zipf_ids(rng, n_topics, A)
    rest = [rng.integers(0, d, size=n_topics) for d in id_digits[1:]]
    topics = [
        f"v/{a}/{b}/{c}/{d}/{e}/{f}/{g}"
        for a, b, c, d, e, f, g in zip(aa, *rest)
    ]
    return filters, topics, 2


def _expected_matches(index, topic: str, res_trie, shape_names) -> int:
    """Independent host-side match count at any table scale: invert each
    registered shape against the topic (O(#shapes) string ops + set
    lookups) + a CPU trie walk over the residual filters. Avoids building
    a 10M-filter Python trie just to spot-check the device kernel."""
    ws = topic.split("/")
    nw = len(ws)
    dollar = topic.startswith("$")
    n = 0
    for (mask, plen, hh), _sid in index.shapes._shape_ids.items():
        if hh:
            if nw < plen:
                continue
        elif nw != plen:
            continue
        rootwild = (plen == 0 and hh) or (plen > 0 and not (mask & 1))
        if dollar and rootwild:
            continue
        parts = [ws[l] if (mask >> l) & 1 else "+" for l in range(plen)]
        if hh:
            parts.append("#")
        if "/".join(parts) in shape_names:
            n += 1
    return n + len(res_trie.match(topic))


def bench_config(name, rng, measure_updates=False):
    import jax
    import jax.numpy as jnp

    from emqx_tpu.models.router_model import SubscriberTable, shape_route_step
    from emqx_tpu.ops.nfa import _next_pow2
    from emqx_tpu.ops.route_index import RouteIndex
    from emqx_tpu.ops.tokenizer import encode_topics

    # table-artifact fast path: share_10m needs no live index (no update
    # phase), so its 215s build caches as a .npz of the device tables +
    # staged topics; the timed loops, latency, and the device-vs-host
    # correctness comparison still run fresh on the chip every sweep
    art_path = None
    if name == "share_10m" and not measure_updates:
        art_path = _cache_path(
            "tables-share_10m", _workload_fingerprint(),
            _tables_fingerprint(),
        )
        res = _bench_from_artifact(name, art_path)
        if res is not None:
            return res

    _mark(f"{name}: building")
    filters, topics, spf = build_config(name, rng)

    index = RouteIndex()
    subs = SubscriberTable(max_subscribers=max(256, spf * 32))
    t0 = time.perf_counter()
    fids = index.bulk_add(filters)  # vectorized cold-start load
    fid_arr = np.repeat(np.asarray(fids, dtype=np.int64), spf)
    slot_arr = (
        np.arange(len(filters) * spf, dtype=np.int64) % (spf * 32)
    )
    subs.bulk_add(fid_arr, slot_arr)
    insert_s = time.perf_counter() - t0
    _mark(f"{name}: index built in {insert_s:.1f}s")
    if name == "mixed_10m":
        # the workload's whole point: full shape table + live residual NFA
        assert index.shapes.m_active() == 64, index.shapes.m_active()
        assert index.residual_count > 0, "residual NFA not engaged"

    shape_tables = {
        k: jax.device_put(v.copy())
        for k, v in index.shapes.device_snapshot().items()
    }
    with_nfa = index.residual_count > 0
    nfa_tables = (
        {
            k: jax.device_put(v.copy())
            for k, v in index.nfa.device_snapshot().items()
        }
        if with_nfa
        else None
    )
    m_active = index.shapes.m_active()
    sub_bitmaps = jax.device_put(
        subs.pack(index.num_filters_capacity).copy()
    )
    hbm_mb = (
        sum(v.nbytes for v in index.shapes.device_snapshot().values())
        + (
            sum(v.nbytes for v in index.nfa.device_snapshot().values())
            if with_nfa
            else 0
        )
        + subs.arr.nbytes
    ) / 1e6

    step = lambda bm, ln: shape_route_step(  # noqa: E731
        shape_tables,
        nfa_tables,
        sub_bitmaps,
        bm,
        ln,
        m_active=m_active,
        with_nfa=with_nfa,
        salt=index.salt,
        **CFG,
    )

    bytes_mat, lengths, too_long = encode_topics(topics, MAX_BYTES)
    assert not too_long.any()
    stage = [
        (
            jax.device_put(bytes_mat[b * BATCH : (b + 1) * BATCH]),
            jax.device_put(lengths[b * BATCH : (b + 1) * BATCH]),
        )
        for b in range(TIMED_BATCHES)
    ]
    _mark(f"{name}: tables+stage up ({len(filters)} filters), compiling")
    out = step(*stage[0])  # warmup / compile
    jax.block_until_ready(out)
    _mark(f"{name}: compiled; timing")

    # sustained throughput: the timed loop keeps ONLY the step dispatches
    # (no per-batch scalar retention). Three independent timing loops,
    # median reported — the r2 verdict flagged a 2x builder-vs-driver
    # swing on single measurements.
    rates = []
    for _rep in range(3):
        t0 = time.perf_counter()
        last = None
        for _ in range(REPEATS):
            for bm, ln in stage:
                last = step(bm, ln)
        jax.block_until_ready(last["stats"]["matches"])
        tpu_s = time.perf_counter() - t0
        rates.append(BATCH * TIMED_BATCHES * REPEATS / tpu_s)
    tpu_rps = float(np.median(rates))

    _mark(f"{name}: throughput done; latency")
    # per-batch latency: serialized dispatch + readback
    lats = []
    for b in range(LAT_BATCHES):
        bm, ln = stage[b % TIMED_BATCHES]
        t1 = time.perf_counter()
        jax.block_until_ready(step(bm, ln))
        lats.append(time.perf_counter() - t1)
    lats = np.array(lats)

    _mark(f"{name}: latency done; updates={measure_updates}")
    upd_s = None
    vis_ms = None
    if measure_updates:
        upd_s, vis_ms = _measure_updates(index, nfa_tables, with_nfa)
    res = _bench_config_tail(
        name, index, filters, topics, spf, insert_s, stage, step, tpu_rps,
        lats, upd_s, vis_ms, hbm_mb, shape_tables, nfa_tables, sub_bitmaps,
    )
    check = res.pop("_check", None)
    if art_path is not None and check is not None:
        try:
            _save_table_artifact(
                art_path, index, subs, bytes_mat, lengths, spf, res, check
            )
        except Exception as e:  # cache write is never a gate
            _mark(f"{name}: artifact save failed ({e!r}); continuing")
    return res


def _save_table_artifact(art_path, index, subs, bytes_mat, lengths, spf,
                         res, check) -> None:
    """Persist device tables + staged topics + the host-verified
    correctness reference (the 256 per-topic match counts the tail just
    checked against an independent host-side count)."""
    snap = index.shapes.device_snapshot()
    nfa_snap = (
        index.nfa.device_snapshot() if index.residual_count > 0 else {}
    )
    t0 = time.perf_counter()
    # tmp must END in .npz (np.savez appends it otherwise and the
    # atomic rename would miss the real file)
    tmp = f"{art_path}.{os.getpid()}.tmp.npz"
    np.savez(
        tmp,
        **{f"shape_{k}": v for k, v in snap.items()},
        **{f"nfa_{k}": v for k, v in nfa_snap.items()},
        subs=subs.pack(index.num_filters_capacity),
        bytes_mat=bytes_mat,
        lengths=lengths,
    )
    os.replace(tmp, art_path + ".npz")
    _cache_put_json(
        art_path,
        {
            "salt": int(index.salt),
            "m_active": int(index.shapes.m_active()),
            "spf": spf,
            "result": res,
            "check": check,
        },
    )
    _mark(f"artifact saved in {time.perf_counter() - t0:.1f}s")


def _bench_from_artifact(name, art_path):
    """Cache-hit runner: rebuild step() from the persisted tables and run
    the TIMED phases fresh on the chip. Returns None on any miss."""
    meta = _cache_get_json(art_path)
    if meta is None or not os.path.exists(art_path + ".npz"):
        return None
    import jax

    from emqx_tpu.models.router_model import shape_route_step

    _mark(f"{name}: loading cached tables")
    z = np.load(art_path + ".npz")
    shape_tables = {
        k[6:]: jax.device_put(z[k]) for k in z.files
        if k.startswith("shape_")
    }
    nfa_tables = {
        k[4:]: jax.device_put(z[k]) for k in z.files if k.startswith("nfa_")
    } or None
    sub_bitmaps = jax.device_put(z["subs"])
    bytes_mat, lengths = z["bytes_mat"], z["lengths"]
    hbm_mb = (
        sum(z[k].nbytes for k in z.files
            if k.startswith(("shape_", "nfa_")))
        + z["subs"].nbytes
    ) / 1e6
    m_active, salt = meta["m_active"], meta["salt"]
    with_nfa = nfa_tables is not None

    step = lambda bm, ln: shape_route_step(  # noqa: E731
        shape_tables, nfa_tables, sub_bitmaps, bm, ln,
        m_active=m_active, with_nfa=with_nfa, salt=salt, **CFG,
    )
    stage = [
        (
            jax.device_put(bytes_mat[b * BATCH : (b + 1) * BATCH]),
            jax.device_put(lengths[b * BATCH : (b + 1) * BATCH]),
        )
        for b in range(TIMED_BATCHES)
    ]
    _mark(f"{name}: cached tables up; compiling")
    jax.block_until_ready(step(*stage[0]))
    _mark(f"{name}: compiled; timing")
    rates = []
    for _rep in range(3):
        t0 = time.perf_counter()
        last = None
        for _ in range(REPEATS):
            for bm, ln in stage:
                last = step(bm, ln)
        jax.block_until_ready(last["stats"]["matches"])
        rates.append(BATCH * TIMED_BATCHES * REPEATS
                     / (time.perf_counter() - t0))
    tpu_rps = float(np.median(rates))
    lats = []
    for b in range(LAT_BATCHES):
        bm, ln = stage[b % TIMED_BATCHES]
        t1 = time.perf_counter()
        jax.block_until_ready(step(bm, ln))
        lats.append(time.perf_counter() - t1)
    lats = np.array(lats)
    # correctness: the device must reproduce the match counts that were
    # verified against the independent host-side count when the artifact
    # was built (tables + topics are deterministic)
    o = step(*stage[0])
    flags0 = np.asarray(o["flags"])[:256]
    mcount0 = np.asarray(o["mcount"])[:256]
    want = np.asarray(meta["check"]["mcount256"])
    wflags = np.asarray(meta["check"]["flags256"])
    ok = (flags0.astype(bool) == wflags.astype(bool)).all() and (
        mcount0[~flags0.astype(bool)] == want[~wflags.astype(bool)]
    ).all()
    assert ok, f"{name}: cached-table correctness mismatch"
    total_matches = int(np.asarray(o["mcount"]).sum())
    total_fanout = int(
        np.unpackbits(
            np.ascontiguousarray(np.asarray(o["bitmaps"])).view(np.uint8)
        ).sum()
    )
    out = dict(meta["result"])
    out.update(
        {
            "tpu_rps": round(tpu_rps, 1),
            "batch_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
            "batch_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
            "matches_per_topic": round(total_matches / BATCH, 3),
            "fanout_bits_per_topic": round(total_fanout / BATCH, 3),
            "hbm_mb": round(hbm_mb, 1),
            "speedup": round(tpu_rps / out["cpu_trie_rps"], 2),
            "cached_tables": True,
        }
    )
    return out


def _measure_updates(index, nfa_tables, with_nfa):
    """Update-sync + subscribe-visibility measurements (mixed configs)."""
    import jax  # noqa: F401  (device work below)

    from emqx_tpu.models.router_model import shape_route_step
    from emqx_tpu.ops.tokenizer import encode_topics

    # delta-overlay update cost: one subscribe + device sync, post-warm
    # (incl. host-mirror materialization, which the cold bulk load
    # defers — a live broker pays it on its first churn op, not per op)
    from emqx_tpu.ops.nfa import DeviceDeltaSync

    phase_t0 = time.perf_counter()
    PHASE_CAP_S = 120.0  # this OPTIONAL phase must not starve the
    # remaining configs
    sync = DeviceDeltaSync()
    sync.sync(index.shapes)
    index.add("warmmat/0/+/x/#")  # materialize lazy host mirrors
    sync.sync(index.shapes)
    t1 = time.perf_counter()
    n_upd = 20  # enough for a stable mean; 50 cost ~90s at 10M scale
    done_upd = 0
    for i in range(n_upd):
        index.add(f"delta/{i}/+/x/#")
        sync.sync(index.shapes)
        done_upd += 1
        if (
            done_upd >= 5
            and time.perf_counter() - phase_t0 > PHASE_CAP_S / 2
        ):
            break  # 5+ samples give a usable mean
    upd_s = (time.perf_counter() - t1) / done_upd
    if time.perf_counter() - phase_t0 > PHASE_CAP_S:
        _mark("updates: phase cap hit; skipping visibility measure")
        return upd_s, None

    # SUBSCRIBE-VISIBILITY at full scale (r3 verdict item 6): wall
    # time from a fresh subscribe (host add) to a ROUTED batch whose
    # kernel provably matches it — the serving pipeline syncs deltas
    # at every batch's prepare(), so this is the whole non-delivery
    # window a new subscriber can observe. Uses a shape family the
    # table already holds (a NEW shape would pay a one-off ~10-40s
    # XLA recompile, which is a different, once-per-shape cost).
    vtopic = ["delta/vis/q/x/tail"] * BATCH
    vb, vl, _ = encode_topics(vtopic, MAX_BYTES)

    def vis_step(tabs):
        return shape_route_step(
            tabs,
            nfa_tables,
            None,
            vb,
            vl,
            m_active=index.shapes.m_active(),
            with_nfa=with_nfa,
            salt=index.salt,
            **CFG,
        )

    # warm the (tables, batch, no-bitmaps) signature: the one-off XLA
    # compile (~4s) is a different cost than the per-subscribe window
    o = vis_step(sync.sync(index.shapes))
    assert int(np.asarray(o["mcount"])[0]) == 0  # not subscribed yet
    t1 = time.perf_counter()
    index.add("delta/vis/+/x/#")
    vo = vis_step(sync.sync(index.shapes))
    vmc = int(np.asarray(vo["mcount"])[0])
    vis_ms = (time.perf_counter() - t1) * 1e3
    assert vmc >= 1, "fresh subscription not visible to the kernel"
    return upd_s, vis_ms


def _bench_config_tail(name, index, filters, topics, spf, insert_s, stage,
                       step, tpu_rps, lats, upd_s, vis_ms, hbm_mb,
                       shape_tables, nfa_tables, sub_bitmaps):
    import jax  # noqa: F401

    _mark(f"{name}: cpu baseline + correctness")
    # flagged rows (frontier / depth overflow) fall back per-row on the
    # serving path, so they are excluded from count comparisons.
    # match/fanout averages come from THIS batch's pulled outputs (one
    # 8192-topic batch gives a 3-decimal average)
    o = step(*stage[0])
    flags0 = np.asarray(o["flags"])
    mcount0 = np.asarray(o["mcount"])
    total_matches = int(mcount0.sum())
    # ascontiguousarray: a backend may hand back strided buffers
    total_fanout = int(
        np.unpackbits(
            np.ascontiguousarray(np.asarray(o["bitmaps"])).view(np.uint8)
        ).sum()
    )
    n_topics_pass = BATCH
    flag_rate = float(flags0.mean())
    assert flag_rate < 0.01, (name, flag_rate)
    from emqx_tpu.broker import trie as _trie_mod
    from emqx_tpu.broker.trie import TopicTrie

    cpu_subsample = 10 if len(filters) > 2_000_000 else 1
    # CPU-baseline measurement cache: the in-process Python trie is a
    # deterministic function of (workload, trie code, subsample) — the
    # 1M-filter builds were 90-150s of every sweep. On a hit, the
    # device-vs-host correctness check switches to the shape-inversion
    # count (the same independent check the 10M configs always use).
    cpu_key = _cache_path(
        f"cpu-{name}", _workload_fingerprint(),
        inspect.getsource(_trie_mod), cpu_subsample,
    )
    cpu_cached = _cache_get_json(cpu_key)
    trie = None
    if cpu_cached is not None:
        cpu_rps = cpu_cached["cpu_rps"]
        _mark(f"{name}: cpu baseline from cache ({cpu_rps:.0f} rps)")
    else:
        trie = TopicTrie()
        for f in filters[::cpu_subsample]:
            trie.insert(f)
        sample = topics[:CPU_SAMPLE]
        t1 = time.perf_counter()
        sum(len(trie.match(t)) for t in sample)
        cpu_s = time.perf_counter() - t1
        cpu_rps = len(sample) / cpu_s
        _cache_put_json(cpu_key, {"cpu_rps": cpu_rps})
    if trie is not None and cpu_subsample == 1:
        # matched counts must agree with the trie on a workload sample
        for i in range(256):
            if not flags0[i]:
                assert mcount0[i] == len(trie.match(topics[i])), (name, i)
    else:
        # independent host check via shape inversion (set lookups) +
        # residual trie — works at any scale, no full python trie build
        res_trie = TopicTrie()
        for f in index._residual:
            res_trie.insert(f)
        # live filter names homed in the shape engine. PR 9 removed the
        # shape index's name dict (`_cold`) — the arrays ARE the mirror
        # — but this check still read it, so BOTH 10M configs have
        # failed their correctness spot-check (and dropped out of every
        # sweep) since then. Names come from the fid registry minus the
        # NFA-resident residuals.
        shape_names = {
            f for f in index._ids if f is not None
        } - index._residual
        for i in range(256):
            if not flags0[i]:
                want = _expected_matches(
                    index, topics[i], res_trie, shape_names
                )
                assert mcount0[i] == want, (name, i, int(mcount0[i]), want)

    del stage, shape_tables, nfa_tables, sub_bitmaps
    out = {
        # DISTINCT filters actually indexed (duplicates dedupe on add),
        # not the generated-list length
        "subscriptions": len(index) * spf,
        "distinct_shapes": index.shapes.m_active(),
        "residual_nfa_filters": index.residual_count,
        "flagged_row_rate": round(flag_rate, 5),
        "tpu_rps": round(tpu_rps, 1),
        "cpu_trie_rps": round(cpu_rps, 1),
        "cpu_trie_subsample": cpu_subsample,
        "speedup": round(tpu_rps / cpu_rps, 2),
        "batch_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
        "batch_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
        "matches_per_topic": round(total_matches / n_topics_pass, 3),
        "fanout_bits_per_topic": round(total_fanout / n_topics_pass, 3),
        "insert_rps": round(len(filters) / insert_s, 1),
        "table_build_s": round(insert_s, 1),
        "hbm_mb": round(hbm_mb, 1),
    }
    if upd_s is not None:
        out["update_sync_ms"] = round(upd_s * 1e3, 3)
    if vis_ms is not None:
        out["subscribe_visibility_ms"] = round(vis_ms, 3)
    # host-verified per-topic counts: consumed by the table-artifact
    # cache as the cache-hit correctness reference (popped before emit)
    out["_check"] = {
        "mcount256": mcount0[:256].astype(int).tolist(),
        "flags256": flags0[:256].astype(int).tolist(),
    }
    return out


# mixed_10m (the HEADLINE: shape-diverse 10M table, residual NFA forced,
# update-sync measured — r3 verdict item 3) runs FIRST in its own fresh
# process; every config emits a BENCH_PARTIAL stderr line on completion
# so a gate timeout still leaves captured numbers (r3 verdict item 1d)
# priority order = skip order inverted: when the wall budget runs out,
# whatever remains is skipped, so the verdict-critical configs (10M
# scales, e2e serving, retained storm) run first and the small
# single-shape tables absorb the squeeze
CONFIGS = [
    "mixed_10m",
    "serving",  # e2e_serving + serving_dispatch (headline)
    "churn_storm",  # O(delta) update path at 10M subs (ROADMAP item 2)
    "session_storm",  # device-resident session/QoS state (item 2 half 2)
    "conn_scaling",  # slab protocol plane: 10k->1M client curve + codec
    "agentic_fabric",  # semantic routing plane (ROADMAP item 3)
    "share_10m",
    "retained_5m",
    "mixed_1m",
    "plus_100k",
    "exact_1k",
]
# run only if budget remains after the required sweep
EXTRAS = ["retained_spot", "chaos_soak", "latency_frontier"]

# per-config minimum-remaining-budget to attempt it (measured warm-cache
# costs + margin; the old blanket 120/170s threshold skipped the ~20s
# tail configs whenever the 10M configs ate the headroom). A config is
# attempted iff this much budget remains, and its child is killed at
# the remaining budget, so an estimate being wrong degrades to ONE
# skipped config, never a blown gate.
MIN_BUDGET_S = {
    "mixed_10m": 300,
    "serving": 280,  # e2e (2 points) + serving_dispatch, one process
    "churn_storm": 240,  # 10M cold build + churn/visibility phases
    "session_storm": 110,  # 1M-session resume + redelivery flood
    "conn_scaling": 400,  # 4-point curve (2 distinct-topic points incl.
    # 1M-topic CSR) + drain-to-quiescence + codec micro
    "agentic_fabric": 90,  # 2 scenarios x (device + host-filter) pass
    "share_10m": 120,
    "retained_5m": 110,
    "mixed_1m": 60,
    "plus_100k": 45,
    "exact_1k": 30,
    "retained_spot": 20,
    "chaos_soak": 45,
    "latency_frontier": 45,  # calibrate + 5 paced points + storm wave
}


def bench_retained(rng):
    """BASELINE config 5: wildcard replay storm over 5M retained topics.

    The DeviceRetainedIndex inverts the routing kernel (stored topics =
    the batch, the subscribe filter = a one-entry shape table); baseline
    is the retainer's CPU trie walk (`emqx_retainer` match_messages
    analog, emqx_retainer_mnesia.erl:146-152).
    """
    import time as _t

    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.retainer import Retainer
    from emqx_tpu.models.retained_index import CHUNK, DeviceRetainedIndex

    N = 5_000_000
    # Concurrent wildcard subscribers in one replay storm, every filter
    # DISTINCT: cross-site device queries ``site/+/dev/{d}/ch/#``. The
    # leading wildcard is the hard replay case — a prefix trie cannot
    # bound the walk, so the CPU reference traverses every site branch
    # PER subscriber (emqx_retainer_mnesia.erl:146-152 match_messages has
    # the same behavior); prefix-bounded filters are cheap for both
    # sides. One O(store) device pass answers all 2048 queries at once.
    STORM = 8192
    SITES = 2048
    DEVIDS = 100003  # device-id universe (prime, so ids spread evenly)
    _mark("retained_5m: building topics")
    topics = [
        f"site/{i % SITES}/dev/{i % DEVIDS}/ch/{i}" for i in range(N)
    ]
    dev = DeviceRetainedIndex(max_bytes=MAX_BYTES, max_levels=8)
    t0 = _t.perf_counter()
    dev.bulk_add(topics)
    build_s = _t.perf_counter() - t0
    _mark(f"retained_5m: device index built in {build_s:.1f}s; warm storm")
    filters = [f"site/+/dev/{d}/ch/#" for d in range(STORM)]
    # warm at FULL storm width (the jit program is keyed on the filter
    # table's size bucket — an 8-filter warm would leave the 512-filter
    # storm paying a fresh XLA compile), then run one throwaway storm
    # so the timed storms below (min of 2) measure the steady state a
    # long-lived retainer serves in.
    dev.warm(filters)
    dev.match_many(filters)

    storm_s = None
    for _ in range(2):
        t0 = _t.perf_counter()
        res = dev.match_many(filters)
        s = _t.perf_counter() - t0
        storm_s = s if storm_s is None else min(storm_s, s)
    total = sum(len(v) for v in res.values())

    _mark("retained_5m: device done; cpu trie baseline (direct, 2.5M)")
    # CPU baseline measured DIRECTLY (no sample-and-scale: the r4 spot
    # check measured the walk growing only ~1.3x per 5x store — the old
    # linear extrapolation OVERSTATED the cpu cost ~4x). A half-size
    # 2.5M store keeps the build inside the budget and is CONSERVATIVE:
    # sublinear growth means the true 5M walk costs more than measured.
    # The measurement caches (pure CPU, deterministic in workload +
    # retainer code): the 2.5M store build was ~150s of every sweep.
    from emqx_tpu.broker import retainer as _ret_mod

    CPU_N = N // 2
    cpu_key = _cache_path(
        "cpu-retained_5m", N, SITES, DEVIDS, STORM, CPU_N,
        inspect.getsource(_ret_mod),
    )
    cached = _cache_get_json(cpu_key)
    if cached is not None:
        cpu_per_sub_s = cached["cpu_per_sub_s"]
        _mark("retained_5m: cpu baseline from cache")
    else:
        cpu = Retainer(max_retained=CPU_N, device_threshold=1 << 62)
        for t in topics[:CPU_N]:
            cpu._insert(Message(topic=t, payload=b"r", retain=True))
        t0 = _t.perf_counter()
        for f in filters[:4]:
            cpu.match(f)
        cpu_per_sub_s = (_t.perf_counter() - t0) / 4  # DIRECT, unscaled
        _cache_put_json(cpu_key, {"cpu_per_sub_s": cpu_per_sub_s})
    cpu_storm_s = cpu_per_sub_s * STORM
    hbm_mb = sum(b.nbytes for b in dev._host_b) / 1e6
    return {
        "retained_topics": N,
        "storm_subscribers": STORM,
        "unique_filters": len(set(filters)),
        "storm_s": round(storm_s, 2),
        "per_subscriber_ms": round(storm_s / STORM * 1e3, 3),
        "cpu_store_topics": CPU_N,
        "cpu_trie_direct_per_subscriber_ms": round(cpu_per_sub_s * 1e3, 1),
        "speedup": round(cpu_storm_s / storm_s, 1),
        "speedup_note": (
            "cpu baseline walked DIRECTLY on a 2.5M store (conservative:"
            " retained_spot measured the walk growing sublinearly, so"
            " the true 5M walk costs more; the pre-r4 linear"
            " extrapolation overstated the baseline ~4x)"
        ),
        "matched_pairs": total,
        "bulk_load_s": round(build_s, 1),
        "hbm_mb": round(hbm_mb, 1),
    }



def bench_retained_spot() -> dict:
    """UNSCALED CPU-baseline linearity check (r3 verdict item 9):
    retained_5m's speedup divides by a baseline measured on a 1/10-size
    store and scaled linearly. This config validates that scaling with
    two DIRECT measurements of the same leading-wildcard walk — a 500k
    store and a 5x-larger 2.5M store — and reports the measured growth
    ratio against the linear prediction (5.0). No sampling, no scaling:
    each walk runs on the store it's measured on."""
    import time as _t

    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.retainer import Retainer

    SITES = 2048
    DEVIDS = 100003
    FILTERS = [f"site/+/dev/{d}/ch/#" for d in (7, 1009, 4021)]

    # pure-CPU validator, deterministic in (workload, retainer code):
    # the whole result caches — two store builds were ~150s per sweep
    from emqx_tpu.broker import retainer as _ret_mod

    key = _cache_path(
        "retained_spot", SITES, DEVIDS, FILTERS,
        inspect.getsource(_ret_mod),
        inspect.getsource(bench_retained_spot),
    )
    cached = _cache_get_json(key)
    if cached is not None:
        _mark("retained_spot: result from cache (pure-CPU validator)")
        return dict(cached, cached_result=True)

    def build_and_walk(n):
        cpu = Retainer(max_retained=n, device_threshold=1 << 62)
        for i in range(n):
            cpu._insert(
                Message(
                    topic=f"site/{i % SITES}/dev/{i % DEVIDS}/ch/{i}",
                    payload=b"r",
                    retain=True,
                )
            )
        per = []
        for f in FILTERS:
            t0 = _t.perf_counter()
            res = cpu.match(f)
            per.append((_t.perf_counter() - t0, len(res)))
        return per

    _mark("retained_spot: 500k store direct walk")
    small = build_and_walk(500_000)
    _mark("retained_spot: 2.5M store direct walk")
    big = build_and_walk(2_500_000)
    s_ms = [round(s * 1e3, 2) for s, _ in small]
    b_ms = [round(s * 1e3, 2) for s, _ in big]
    ratios = [
        round(b / s, 2) for (s, _), (b, _) in zip(small, big) if s > 0
    ]
    res = {
        "filters_walked": FILTERS,
        "store_500k_per_subscriber_ms": s_ms,
        "store_2500k_per_subscriber_ms": b_ms,
        "measured_growth_ratio": ratios,
        "linear_prediction": 5.0,
        "note": (
            "direct (unscaled) walks at two store sizes validate the "
            "linear extrapolation behind retained_5m's scaled cpu "
            "baseline; a measured ratio near 5.0 confirms the "
            "per-subscriber walk is linear in store size for this "
            "leading-wildcard family"
        ),
    }
    _cache_put_json(key, res)
    return res


E2E_WORKER_COUNTS = (0, 4)  # host data-plane scaling curve (r3 item 2)
# driver counts SHRUNK to fit the budget (r3/r4: e2e skipped or timed
# out — a headline metric that never lands is worth less than a smaller
# one that always does): 2 driver processes, 16 publishers, 24k msgs
N_PUB = 16
N_SUB = 8
PER_PUB = 1500  # 24k timed messages per point
N_DRIVERS = 2


def e2e_driver(port: int, n_pub: int, n_sub: int, per_pub: int,
               expect_total: int, tag: str) -> None:
    """Load-driver child process: its own event loop + sockets, so the
    measured broker never competes with the load generator for a core.
    Prints READY, waits for GO on stdin, floods, prints one JSON line."""
    import asyncio
    import struct as _struct

    from emqx_tpu.mqtt.client import Client

    async def run():
        subs = []
        for i in range(n_sub):
            # keepalive 0: subscribers only receive, and the in-repo
            # client has no auto-ping loop — a long run would otherwise
            # get them keepalive-kicked mid-measurement
            c = Client(client_id=f"bs-{tag}-{i}", keepalive=0)
            await c.connect("127.0.0.1", port)
            await c.subscribe("bench/+/t", qos=0)
            subs.append(c)
        pubs = []
        for i in range(n_pub):
            c = Client(client_id=f"bp-{tag}-{i}", keepalive=0)
            await c.connect("127.0.0.1", port)
            pubs.append(c)
        print("READY", flush=True)
        await asyncio.get_running_loop().run_in_executor(
            None, sys.stdin.readline
        )

        async def pump(p, i):
            for j in range(per_pub):
                await p.publish(
                    f"bench/{tag}{i}/t",
                    _struct.pack("!d", time.perf_counter()) + b"x",
                    qos=0,
                )
                if j % 200 == 0:  # yield so the loop serves deliveries
                    await asyncio.sleep(0)

        async def drain(c):
            got = 0
            while got < expect_total:
                m = await c.recv(600)  # recv's DEFAULT timeout is 5s
                if m.payload[-1:] == b"x":
                    got += 1
            return got

        t0 = time.perf_counter()
        await asyncio.wait_for(
            asyncio.gather(
                *[pump(p, i) for i, p in enumerate(pubs)],
                *[drain(c) for c in subs],
            ),
            1200,
        )
        wall = time.perf_counter() - t0
        for c in subs + pubs:
            await c.disconnect()
        print(json.dumps({"wall": wall, "sent": n_pub * per_pub}))

    asyncio.run(run())


def _e2e_point(workers: int, deadline: Optional[float] = None) -> dict:
    """One scaling-curve point: broker with `workers` connection workers
    (0 = classic in-process listener), load from N_DRIVERS processes.
    `deadline` (absolute perf_counter stamp) bounds every long wait so a
    degraded run yields a partial capture instead of a gate kill."""
    import asyncio
    import struct as _struct
    import subprocess

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.schema import load_config
    from emqx_tpu.mqtt.client import Client

    async def run():
        import socket as _socket

        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        app = BrokerApp(load_config({
            "listeners": [
                {"port": port, "bind": "127.0.0.1", "workers": workers}
            ],
            "dashboard": {"enable": False},
        }))
        await app.start()
        if workers:
            await app.worker_pools[0].wait_ready()
        _mark(f"e2e[w={workers}]: pre-compiling ingest batch buckets")
        # each pow2 ingest bucket is a fresh XLA compile (~40-60s cold);
        # compile them all before the timed run — through the ACTUAL
        # serving entry (adispatch_begin -> donated/fused jit), not the
        # sync path, or the timed flood pays the donated program's
        # compile inside the window (exactly how e2e died in r03/r04)
        from emqx_tpu.broker.message import Message as _Msg

        size = app.broker.router.min_tpu_batch
        while size <= app.config.router.ingest_max_batch:
            await app.broker.adispatch_begin(
                [_Msg(topic="warmup/bucket") for _ in range(size)]
            )
            size *= 2
        # ALSO warm the subscribe->delta-sync->route path: the scatter
        # upload program is a separate XLA compile (~40s cold on a real
        # chip) that must not land inside the timed flood
        wc = Client(client_id="warm-sub", keepalive=0)
        await wc.connect("127.0.0.1", port)
        await wc.subscribe("bench/+/t", qos=0)
        wp = Client(client_id="warm-pub", keepalive=0)
        await wp.connect("127.0.0.1", port)
        await asyncio.sleep(0.5)
        for i in range(app.broker.router.min_tpu_batch + 8):
            await wp.publish("bench/w/t", b"warm", qos=0)
        got_warm = 0
        try:
            while got_warm < app.broker.router.min_tpu_batch:
                await wc.recv(180)
                got_warm += 1
        except asyncio.TimeoutError:
            pass
        assert got_warm >= app.broker.router.min_tpu_batch, got_warm
        await wc.disconnect()
        await wp.disconnect()

        total = N_PUB * PER_PUB
        loop = asyncio.get_running_loop()

        def left() -> float:
            if deadline is None:
                return 1200.0
            return max(30.0, deadline - time.perf_counter())

        async def one_flood():
            procs = []
            try:
                for d in range(N_DRIVERS):
                    procs.append(subprocess.Popen(
                        [sys.executable, __file__, "_e2e_driver",
                         str(port),
                         str(N_PUB // N_DRIVERS), str(N_SUB // N_DRIVERS),
                         str(PER_PUB), str(total), f"d{d}"],
                        stdin=subprocess.PIPE,
                        stdout=subprocess.PIPE,
                        text=True,
                    ))

                def _wait_ready():
                    for p in procs:
                        line = p.stdout.readline().strip()
                        assert line == "READY", line

                await asyncio.wait_for(
                    loop.run_in_executor(None, _wait_ready), 120
                )
                await asyncio.sleep(1.0)  # fabric SUB propagation
                for p in procs:
                    p.stdin.write("GO\n")
                    p.stdin.flush()

                cap = min(1300.0, left())

                def _collect(p):
                    out, _ = p.communicate(timeout=cap)
                    lines = out.strip().splitlines()
                    if not lines or p.returncode != 0:
                        raise RuntimeError(
                            f"e2e driver rc={p.returncode} "
                            f"out={out[-500:]!r}"
                        )
                    return json.loads(lines[-1])

                stats = []
                for p in procs:
                    stats.append(
                        await loop.run_in_executor(None, _collect, p)
                    )
                return max(st["wall"] for st in stats)
            finally:
                # a timed-out flood must not leave drivers flooding the
                # broker under the NEXT point's measurement
                for p in procs:
                    if p.poll() is None:
                        p.kill()

        _mark(f"e2e[w={workers}]: flood x {N_DRIVERS} drivers "
              f"({total} msgs x {N_SUB} subscribers)")
        wall = await asyncio.wait_for(one_flood(), left())
        rate = total / wall

        # paced socket-to-socket latency (incl. ingest window + fabric
        # hop) from this otherwise-idle parent, at ~25% of sustained rate
        _mark(f"e2e[w={workers}]: paced latency phase")
        lc = Client(client_id="lat-sub", keepalive=0)
        await lc.connect("127.0.0.1", port)
        await lc.subscribe("bench/lat/t", qos=0)
        lp = Client(client_id="lat-pub", keepalive=0)
        await lp.connect("127.0.0.1", port)
        await asyncio.sleep(0.5)
        lats = []
        PACED = 200
        interval = max(1.0 / max(rate * 0.25, 10.0), 0.002)
        for _ in range(PACED):
            await lp.publish(
                "bench/lat/t",
                _struct.pack("!d", time.perf_counter()) + b"p",
                qos=0,
            )
            try:
                m = await lc.recv(60)  # recv's DEFAULT timeout is 5s
                (ts,) = _struct.unpack("!d", m.payload[:8])
                lats.append(time.perf_counter() - ts)
            except asyncio.TimeoutError:
                break
            await asyncio.sleep(interval)
        await lc.disconnect()
        await lp.disconnect()
        lats = np.array(lats) if lats else np.array([float("nan")])
        met = app.broker.metrics
        point = {
            "workers": workers,
            "e2e_msgs_per_s": round(rate, 1),
            "e2e_deliveries_per_s": round(total * N_SUB / wall, 1),
            "e2e_paced_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
            "e2e_paced_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
            "routed_device": met.get("messages.routed.device"),
            "routed_device_fallback": met.get(
                "messages.routed.device_fallback"
            ),
        }
        await app.stop()
        return point

    return asyncio.run(run())


def bench_e2e(deadline: Optional[float] = None) -> dict:
    """End-to-end SERVING throughput — the HEADLINE metric (ROADMAP
    item 1): concurrent socket publishers -> MQTT codec -> (worker
    fabric ->) ingest batch window -> device route_step -> session
    delivery, measured at the subscriber sockets, with multi-process
    load drivers and a worker-count scaling curve. Reference regime:
    emqx_broker.erl:204-215 end-to-end, process-per-connection host.

    Reliability contract (r3/r4 lesson — this config skipped or timed
    out and the trajectory lost its headline point): every long wait is
    bounded by `deadline`, a failed/skipped point degrades to a partial
    result carrying `"timeout": true`, and the batch-bucket programs
    precompile through the real serving entry before the timed window.
    """
    points, incomplete = [], []
    for w in E2E_WORKER_COUNTS:
        if deadline is not None and time.perf_counter() > deadline - 90:
            incomplete.append({"workers": w, "skipped": "budget"})
            _mark(f"e2e[w={w}]: SKIPPED (budget)")
            continue
        try:
            points.append(_e2e_point(w, deadline))
            _mark(f"e2e point done: {points[-1]}")
        except Exception as e:  # noqa: BLE001 — partial > nothing
            incomplete.append({"workers": w, "error": repr(e)})
            _mark(f"e2e[w={w}]: FAILED ({e!r}); continuing")
    if not points:
        return {
            "timeout": True,
            "e2e_msgs_per_s": None,
            "incomplete_points": incomplete,
        }
    best = max(points, key=lambda p: p["e2e_msgs_per_s"])
    base = next(
        (p for p in points if p["workers"] == 0), points[0]
    )["e2e_msgs_per_s"]
    res = {
        "publishers": N_PUB,
        "subscribers": N_SUB,
        "messages": N_PUB * PER_PUB,
        "deliveries": N_PUB * PER_PUB * N_SUB,
        "e2e_msgs_per_s": best["e2e_msgs_per_s"],
        "e2e_deliveries_per_s": best["e2e_deliveries_per_s"],
        "e2e_paced_p50_ms": best["e2e_paced_p50_ms"],
        "e2e_paced_p99_ms": best["e2e_paced_p99_ms"],
        "best_workers": best["workers"],
        "scaling_curve": points,
        "vs_single_process": round(
            best["e2e_msgs_per_s"] / base, 2
        ) if base else None,
        "note": (
            "multi-process host data plane: N connection workers on a "
            "shared SO_REUSEPORT port + batched fabric into the router "
            "process (transport/workers.py); load generated by separate "
            "driver processes; paced latencies include the ingest batch "
            "window and the fabric hop"
        ),
    }
    if incomplete:
        res["timeout"] = True
        res["incomplete_points"] = incomplete
    return res


def bench_serving_suite(deadline: Optional[float] = None) -> dict:
    """e2e_serving + serving_dispatch in ONE process, across every
    internal config (worker counts, dense vs compact readback, table
    shapes) with no per-process restart between them. This is the
    process-survival gate for the serving pipeline: bounded jit caches
    (router.jit_cache_max), explicit device-buffer frees on table
    growth (DeviceDeltaSync free_retired), and the bounded dispatch
    executor must hold a long-lived process steady where the r02/r04
    sweeps needed a fresh process per config."""
    out = {"single_process": True}
    try:
        out["e2e_serving"] = bench_e2e(deadline)
    except Exception as e:  # noqa: BLE001 — partial > nothing
        out["e2e_serving"] = {"timeout": True, "error": repr(e)}
    _mark(f"serving: e2e done {json.dumps(out['e2e_serving'])[:300]}")
    try:
        out["serving_dispatch"] = bench_serving()
    except Exception as e:  # noqa: BLE001
        out["serving_dispatch"] = {"timeout": True, "error": repr(e)}
    return out


def bench_serving() -> dict:
    """Broker-level serving benchmark (`serving_dispatch`): publish_batch
    -> deliveries/sec through BatchIngest + device route + host fan-out
    with CPU-deliverable subscriber stubs, at the mixed_1m fan-out shape
    (device/{i}/+/{j}/# families + broad device/{i}/# overlays, Zipf
    publish topics; scaled so the host subscribe loop stays in budget).

    Runs the SAME workload twice — dense-bitmap readback vs sparse
    fan-out compaction — and reports `serving_rps` plus
    `readback_mb_per_batch` for both, from the `dispatch.readback.bytes`
    flight-recorder series. The reduction factor is the compaction win
    this benchmark exists to track (O(matches) vs O(B x slot universe)
    crossing the host<->device link)."""
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.ops.matcher import MatcherConfig

    N_DEV, N_MID = 400, 80  # 32k '+/#'-shaped filters, one sub each
    N_OVERLAY = 64  # hot-id 'device/{i}/#' overlays
    N_MSGS = 16384
    MAX_BATCH = 4096

    rng = np.random.default_rng(1905)
    ids = _zipf_ids(rng, N_MSGS, N_DEV)
    nums = rng.integers(0, N_MID, size=N_MSGS)
    topics = [f"device/{i}/mid/{j}/leaf" for i, j in zip(ids, nums)]

    def build(compact: bool, sub_table: str = "dense"):
        b = Broker(
            router=Router(
                MatcherConfig(
                    fanout_compact=compact, sub_table=sub_table
                ),
                min_tpu_batch=64,
            ),
            hooks=Hooks(),
        )
        delivered = [0]

        def deliver(m, o):
            delivered[0] += 1

        sid = 0
        for i in range(N_DEV):
            for j in range(N_MID):
                b.subscribe(
                    f"s{sid}", f"c{sid}", f"device/{i}/+/{j}/#",
                    pkt.SubOpts(), deliver,
                )
                sid += 1
        for i in range(N_OVERLAY):
            b.subscribe(
                f"s{sid}", f"c{sid}", f"device/{i}/#", pkt.SubOpts(),
                deliver,
            )
            sid += 1
        return b, delivered

    async def run_pass(compact: bool, sub_table: str = "dense") -> dict:
        b, delivered = build(compact, sub_table)
        ing = BatchIngest(b, max_batch=MAX_BATCH, window_us=500)
        b.ingest = ing
        ing.start()
        # compile + table upload outside the timed window (a live broker
        # pays this once at boot, not per batch)
        await ing.submit(Message(topic="device/0/mid/0/warm"))
        t0 = time.perf_counter()
        futs = [
            ing.enqueue(Message(topic=t, payload=b"p")) for t in topics
        ]
        counts = await asyncio.gather(*futs)
        wall = time.perf_counter() - t0
        await ing.stop()
        h = b.metrics.histogram("dispatch.readback.bytes")
        mb_per_batch = (
            h.sum / h.count / 1e6 if h is not None and h.count else None
        )
        return {
            "mode": (
                "sparse" if sub_table == "sparse"
                else "compact" if compact else "dense"
            ),
            "serving_rps": round(sum(counts) / wall, 1),
            "msgs_per_s": round(N_MSGS / wall, 1),
            "deliveries": int(sum(counts)),
            "delivered_stub": delivered[0],
            "readback_mb_per_batch": (
                round(mb_per_batch, 4) if mb_per_batch else None
            ),
            "compact_rows": b.metrics.get("dispatch.compact.rows"),
            "overflow_rows": b.metrics.get(
                "dispatch.compact.overflow.rows"
            ),
            "width_words": b.subtab.width_words,
            "sub_table_bytes": b.subtab.table_bytes(),
        }

    _mark("serving_dispatch: dense pass")
    dense = asyncio.run(run_pass(False))
    _mark(f"serving_dispatch: dense done {dense}")
    compact = asyncio.run(run_pass(True))
    _mark(f"serving_dispatch: compact done {compact}")
    sparse = asyncio.run(run_pass(True, sub_table="sparse"))
    _mark(f"serving_dispatch: sparse done {sparse}")
    # identical delivery work is the correctness floor for the comparison
    assert dense["deliveries"] == compact["deliveries"], (dense, compact)
    assert dense["deliveries"] == sparse["deliveries"], (dense, sparse)
    red = (
        round(dense["readback_mb_per_batch"]
              / compact["readback_mb_per_batch"], 1)
        if dense["readback_mb_per_batch"] and compact["readback_mb_per_batch"]
        else None
    )
    return {
        "subscriptions": N_DEV * N_MID + N_OVERLAY,
        "messages": N_MSGS,
        "serving_rps": compact["serving_rps"],
        "readback_mb_per_batch": compact["readback_mb_per_batch"],
        "readback_mb_per_batch_dense": dense["readback_mb_per_batch"],
        "readback_reduction_x": red,
        "dense": dense,
        "compact": compact,
        # the CSR subscriber table serving the SAME workload: identical
        # deliveries, O(subscriptions) memory (docs/serving_pipeline.md
        # "subscriber-table memory budget")
        "sparse": sparse,
        "sparse_vs_dense_rps_x": (
            round(sparse["serving_rps"] / dense["serving_rps"], 2)
            if dense["serving_rps"]
            else None
        ),
        "sub_table_bytes_sparse": sparse["sub_table_bytes"],
        "sub_table_bytes_dense": dense["sub_table_bytes"],
        "note": (
            "deliveries/sec through the real BatchIngest -> device route"
            " -> host fan-out pipeline with stub deliverers; readback"
            " series from dispatch.readback.bytes (docs/observability.md"
            " 'readback budget'). readback_mb_per_batch is the tracked"
            " quantity: on a host-local backend the transfer is a memcpy"
            " and the byte saving does not show up in rps, while on a"
            " real host<->device link the dense bitmap readback is the"
            " per-batch wall the compaction removes"
        ),
    }




def bench_agentic_fabric(deadline: Optional[float] = None) -> dict:
    """`agentic_fabric` config (docs/semantic_routing.md): the mixed
    topic + semantic workload — agentic clients subscribing by MEANING
    (embedding filters, scoped and unscoped) alongside ordinary topic
    subscriptions, with per-message embeddings, through the REAL
    serving entry (BatchIngest -> fused step -> dispatch). Scenario
    shapes follow the broker-benchmarking methodology (PAPERS.md
    "Benchmarking Message Brokers for IoT Edge Computing"):

    - **fan_out**: 8 hot rooms, topic subscribers per room + semantic
      subscribers scoped to the room tree — every message fans to its
      room AND its meaning-cluster;
    - **fan_in**: 4096 distinct device topics draining into a few
      wildcard subscribers + unscoped semantic listeners.

    Each scenario runs twice: the fused DEVICE pass (similarity matmul
    + rule WHERE masks inside the serving launch) and the HOST-FILTER
    pass (identical topic pipeline; semantic filtering applied
    post-dispatch at Python/numpy rate — what the plane replaces).
    Reports `semantic_routing_rps` (device, both scenarios combined)
    and `semantic_vs_host_filter_x`, with identical delivery counts as
    the correctness floor. A compiled rule predicate
    (`WHERE payload.p = 1`) rides the device pass to exercise the
    in-launch mask path."""
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.broker.semantic import SemanticRouting
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.ops.matcher import MatcherConfig
    from emqx_tpu.rules.engine import FunctionOutput, RuleEngine

    DIM, TOPK, THRESH = 32, 16, 0.70
    N_ROOMS, N_PLAIN, N_SEM = 8, 1024, 384
    N_MSGS, MAX_BATCH = 8192, 2048
    rng = np.random.default_rng(2209)
    cents = rng.normal(size=(N_ROOMS, DIM)).astype(np.float32)
    cents /= np.linalg.norm(cents, axis=1, keepdims=True)

    def _near(c):
        n = rng.normal(size=DIM).astype(np.float32)
        n /= np.linalg.norm(n)
        v = cents[c] + 0.25 * n  # same-cluster sims ~0.94, cross ~N(0, .18)
        return (v / np.linalg.norm(v)).astype(np.float32)

    scen_msgs = {
        "fan_out": [
            (f"agents/room/{i % N_ROOMS}/evt", _near(i % N_ROOMS),
             i % 4)
            for i in range(N_MSGS)
        ],
        "fan_in": [
            (f"agents/dev/{int(rng.integers(0, 4096))}/out",
             _near(i % N_ROOMS), i % 4)
            for i in range(N_MSGS)
        ],
    }
    sem_specs = {
        "fan_out": [
            (f"agents/room/{i % N_ROOMS}/#", _near(i % N_ROOMS))
            for i in range(N_SEM)
        ],
        "fan_in": [("#", _near(i % N_ROOMS)) for i in range(N_SEM)],
    }
    plain_specs = {
        "fan_out": [
            f"agents/room/{i % N_ROOMS}/#" for i in range(N_PLAIN)
        ],
        "fan_in": [f"agents/dev/+/out" for _ in range(16)],
    }

    def build(scen: str, semantic: bool):
        b = Broker(
            router=Router(MatcherConfig(), min_tpu_batch=64),
            hooks=Hooks(),
        )
        counts = {"plain": 0, "sem": 0}

        def mk(kind):
            def deliver(m, o):
                counts[kind] += 1

            return deliver

        if semantic:
            b.semantic = SemanticRouting(
                dim=DIM, topk=TOPK, threshold=THRESH,
                metrics=b.metrics,
            )
        sid = 0
        for f in plain_specs[scen]:
            b.subscribe(f"p{sid}", f"p{sid}", f, pkt.SubOpts(),
                        mk("plain"))
            sid += 1
        if semantic:
            for f, vec in sem_specs[scen]:
                b.subscribe(
                    f"s{sid}", f"s{sid}", f, pkt.SubOpts(), mk("sem"),
                    embedding=vec, sem_threshold=THRESH,
                )
                sid += 1
        return b, counts

    async def device_pass(scen: str) -> dict:
        b, counts = build(scen, semantic=True)
        eng = RuleEngine(b)
        eng.attach(b.hooks)
        fired = [0]
        eng.create_rule(
            "agentic", '''SELECT qos FROM "agents/#" WHERE payload.p = 1''',
            [FunctionOutput(lambda row, ctx: fired.__setitem__(
                0, fired[0] + 1
            ))],
        )
        eng.attach_device()
        ing = BatchIngest(b, max_batch=MAX_BATCH, window_us=500)
        b.ingest = ing
        ing.start()
        await ing.submit(Message(topic="agents/room/0/warm"))
        t0 = time.perf_counter()
        futs = []
        # the REAL publish entry (apublish_enqueue): hook fold + rule
        # deferral markers + batch window, i.e. what a connection pays
        for t, e, pv in scen_msgs[scen]:
            m = Message(
                topic=t, payload=b'{"p": %d}' % pv, from_client="pub"
            )
            m.headers["semantic_embedding"] = e
            r = await b.apublish_enqueue(m)
            if not isinstance(r, int):
                futs.append(r)
        cnt = await asyncio.gather(*futs)
        wall = time.perf_counter() - t0
        await ing.stop()
        return {
            "msgs_per_s": round(N_MSGS / wall, 1),
            "deliveries": int(sum(cnt)),
            "plain_deliveries": counts["plain"],
            "sem_deliveries": counts["sem"],
            "sem_hits": b.metrics.get("semantic.hits"),
            "rule_fired": fired[0],
            "rule_device_batches": b.metrics.get(
                "rules.device.batches"
            ),
        }

    async def host_filter_pass(scen: str) -> dict:
        """Identical topic pipeline; semantic filtering applied AFTER
        dispatch at host rate — the post-dispatch-Python baseline the
        fused plane replaces (same recipients, measured honestly)."""
        b, counts = build(scen, semantic=False)
        eng = RuleEngine(b)
        eng.attach(b.hooks)
        fired = [0]
        eng.create_rule(
            "agentic",
            'SELECT qos FROM "agents/#" WHERE payload.p = 1',
            [FunctionOutput(lambda row, ctx: fired.__setitem__(
                0, fired[0] + 1
            ))],
        )  # NO attach_device: WHERE evaluates per message in the fold
        hostsem = SemanticRouting(dim=DIM, topk=TOPK, threshold=THRESH)
        slot = 0
        for f, vec in sem_specs[scen]:
            hostsem.attach(f"h{slot}", slot, vec, THRESH, fid=-1,
                           scope=f)
            slot += 1
        ing = BatchIngest(b, max_batch=MAX_BATCH, window_us=500)
        b.ingest = ing
        ing.start()
        await ing.submit(Message(topic="agents/room/0/warm"))
        msgs = []
        for t, e, pv in scen_msgs[scen]:
            m = Message(
                topic=t, payload=b'{"p": %d}' % pv, from_client="pub"
            )
            m.headers["semantic_embedding"] = e
            msgs.append(m)
        sem_n = 0
        t0 = time.perf_counter()
        futs = []
        for m in msgs:
            r = await b.apublish_enqueue(m)
            if not isinstance(r, int):
                futs.append(r)
        cnt = await asyncio.gather(*futs)
        for lo in range(0, N_MSGS, MAX_BATCH):
            for slots in hostsem.host_route(msgs[lo : lo + MAX_BATCH]):
                sem_n += len(slots)
        wall = time.perf_counter() - t0
        await ing.stop()
        return {
            "msgs_per_s": round(N_MSGS / wall, 1),
            "plain_deliveries": counts["plain"],
            "sem_deliveries": sem_n,
            "rule_fired": fired[0],
        }

    out = {"scenarios": {}}
    dev_rps, host_rps = [], []
    for scen in ("fan_out", "fan_in"):
        if deadline is not None and time.perf_counter() > deadline - 20:
            out["scenarios"][scen] = {"timeout": True}
            continue
        dev = asyncio.run(device_pass(scen))
        _mark(f"agentic_fabric {scen} device: {dev}")
        host = asyncio.run(host_filter_pass(scen))
        _mark(f"agentic_fabric {scen} host-filter: {host}")
        # correctness floor: identical topic work; semantic counts may
        # differ only by knife-edge threshold ties (f32 matmul vs the
        # numpy twin's summation order) — bounded tightly, and the
        # differential property tests pin exactness at small scale
        assert dev["plain_deliveries"] == host["plain_deliveries"], (
            scen, dev, host,
        )
        tol = max(8, dev["sem_deliveries"] // 200)
        assert abs(
            dev["sem_deliveries"] - host["sem_deliveries"]
        ) <= tol, (scen, dev, host)
        dev_rps.append(dev["msgs_per_s"])
        host_rps.append(host["msgs_per_s"])
        out["scenarios"][scen] = {"device": dev, "host_filter": host}
    if dev_rps:
        out["semantic_routing_rps"] = round(
            sum(dev_rps) / len(dev_rps), 1
        )
        out["semantic_vs_host_filter_x"] = (
            round(
                (sum(dev_rps) / len(dev_rps))
                / max(1e-9, sum(host_rps) / len(host_rps)),
                2,
            )
        )
    out.update({
        "dim": DIM, "topk": TOPK, "threshold": THRESH,
        "semantic_filters": N_SEM, "plain_subs": len(
            plain_specs["fan_out"]
        ),
        "messages_per_scenario": N_MSGS,
        "note": (
            "mixed topic+semantic workload through the REAL serving "
            "entry (apublish_enqueue -> BatchIngest -> fused step -> "
            "dispatch); the host-filter pass runs the identical topic "
            "pipeline + rule workload with semantic similarity and "
            "rule WHERE applied at host rate (the post-dispatch-Python "
            "baseline the plane replaces). Delivery counts asserted "
            "identical. On a CPU-only jax backend the fused matmul is "
            "emulated host-side, so the ratio there measures pipeline "
            "overhead, not MXU rate — the TPU capture is the number of "
            "record."
        ),
    })
    return out


def bench_chaos_soak() -> dict:
    """`chaos_soak` config (docs/robustness.md): steady QoS1 publish
    load through the REAL ingest -> device-route -> dispatch pipeline
    while faults fire on a schedule — device launch failures, torn
    delta-syncs, admission drops — asserting the degradation ladder's
    contract as a regression gate, not a bench footnote:

    - ZERO message loss for accepted QoS>=1 publishes (degraded batches
      serve the identical recipient sets from the CPU trie; sheds are
      backpressure the publisher SEES, never silence);
    - bounded p99 settle latency during degradation;
    - recovery back toward baseline RPS after the faults clear (the
      half-open probe re-warms the device path; the ratio is recorded
      in the BENCH json).
    """
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.degrade import DegradeController, IngestShed
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.observe.faults import default_faults
    from emqx_tpu.ops.matcher import MatcherConfig

    N_DEV, N_MID = 50, 8  # 400 '+/#' filters, one sub each
    N_MSGS = 4096  # per phase
    MAX_BATCH = 512
    OPEN_SECS = 0.3

    rng = np.random.default_rng(2207)
    ids = _zipf_ids(rng, N_MSGS, N_DEV)
    nums = rng.integers(0, N_MID, size=N_MSGS)
    topics = [f"device/{i}/mid/{j}/leaf" for i, j in zip(ids, nums)]

    b = Broker(
        router=Router(MatcherConfig(), min_tpu_batch=64), hooks=Hooks()
    )
    deg = DegradeController(
        metrics=b.metrics,
        max_retries=2,
        backoff_base_s=0.002,
        backoff_max_s=0.05,
        open_secs=OPEN_SECS,
    )
    b.degrade = deg
    default_faults.metrics = b.metrics
    delivered = [0]

    def deliver(m, o):
        delivered[0] += 1

    sid = 0
    for i in range(N_DEV):
        for j in range(N_MID):
            b.subscribe(
                f"s{sid}", f"c{sid}", f"device/{i}/+/{j}/#",
                pkt.SubOpts(), deliver,
            )
            sid += 1

    async def phase(ing, tag: str) -> dict:
        lats = []
        loss = 0
        shed = 0
        t0 = time.perf_counter()
        futs = []
        for t in topics:
            te = time.perf_counter()
            f = ing.enqueue(Message(topic=t, payload=b"p", qos=1))
            f.add_done_callback(
                lambda _f, te=te: lats.append(time.perf_counter() - te)
            )
            futs.append(f)
        res = await asyncio.gather(*futs, return_exceptions=True)
        wall = time.perf_counter() - t0
        for r in res:
            if isinstance(r, IngestShed):
                shed += 1  # backpressure the publisher SAW — not loss
            elif isinstance(r, BaseException) or r < 1:
                loss += 1  # accepted but not delivered = real loss
        lats.sort()
        out = {
            "rps": round((N_MSGS - shed) / wall, 1),
            "p99_ms": round(lats[int(0.99 * (len(lats) - 1))] * 1e3, 2)
            if lats
            else None,
            "loss": loss,
            "shed": shed,
        }
        _mark(f"chaos_soak: {tag} {json.dumps(out)}")
        return out

    async def run() -> dict:
        from emqx_tpu.observe.racetrack import RaceTracker

        ing = BatchIngest(b, max_batch=MAX_BATCH, window_us=500)
        b.ingest = ing
        ing.start()
        await ing.submit(  # compile outside the timed phases
            Message(topic="device/0/mid/0/warm", payload=b"w", qos=1)
        )
        baseline = await phase(ing, "baseline")

        # racetrack: register the shared hot-state, then arm through the
        # fault waves — zero unwaived reports joins the soak's gate.
        # Registration while disarmed instruments NOTHING (asserted on
        # the live Metrics class), so the disarmed overhead on
        # serving_rps is structurally zero, under the <1% budget.
        rt = RaceTracker(metrics=b.metrics)
        rt.watch(b.metrics, name="Metrics")
        rt.watch(deg.device, name="Breaker")
        if b._device is not None:
            rt.watch(b._device, name="DeviceRouter")
        assert type(b.metrics).__name__ == "Metrics", (
            "disarmed racetrack must leave watched classes untouched"
        )
        rt.arm()

        # wave 1: every device launch fails -> retries -> breaker opens
        # -> CPU-trie serving for the rest of the wave
        default_faults.arm("device.launch", mode="raise")
        wave_launch = await phase(ing, "fault:device.launch")
        default_faults.disarm("device.launch")

        # wave 2: torn delta-syncs (subscribe churn dirties the tables;
        # every dirty sync is declared corrupt -> epoch rollback) plus
        # probabilistic admission drops (sheds, visible backpressure)
        await asyncio.sleep(OPEN_SECS + 0.1)  # let the probe recover
        b.subscribe("churn", "cchurn", "device/0/#", pkt.SubOpts(), deliver)
        default_faults.arm("router.delta_sync", mode="corrupt")
        default_faults.arm(
            "ingest.enqueue", mode="drop", probability=0.02
        )
        wave_sync = await phase(ing, "fault:delta_sync+shed")
        default_faults.disarm()

        # recovery: dwell out the breaker, then measure a clean wave
        await asyncio.sleep(OPEN_SECS + 0.1)
        recovered = await phase(ing, "recovered")

        # wave 3 (docs/sessions.md): device loss MID-INFLIGHT-WINDOW.
        # QoS1 deliveries land in store-backed session windows (acks
        # withheld), then device.launch faults fire BETWEEN delivery
        # and ack. The zero-loss gate extends to the windows: every
        # accepted message redelivers EXACTLY once through the
        # fallback sweep while the device path is down.
        from emqx_tpu.broker.session import Session, SessionConfig
        from emqx_tpu.broker.session_store import SessionStore

        mono = [0.0]
        store = SessionStore(
            capacity=8192, sweep_slots=4096, retry_interval=1.0,
            metrics=b.metrics, clock=lambda: mono[0],
        )
        b.session_store = store
        sess = Session(
            "soak-inflight", SessionConfig(max_inflight=4096),
            store=store,
        )
        resent: list = []
        store.bind(
            sess.store_slot,
            lambda pid, st, msg: resent.append(pid) or True,
        )
        b.subscribe(
            "soak-inflight", "soak-inflight", "inflight/#",
            pkt.SubOpts(qos=1),
            lambda msg, o: sess.deliver(msg, o),
        )
        await asyncio.gather(*[
            ing.enqueue(
                Message(topic=f"inflight/a/{i}", payload=b"p", qos=1)
            )
            for i in range(256)
        ])
        # the windows are OPEN (unacked) when the device dies; the
        # in-flight session rider aborts, batches degrade to the trie
        default_faults.arm("device.launch", mode="raise")
        await asyncio.gather(*[
            ing.enqueue(
                Message(topic=f"inflight/b/{i}", payload=b"p", qos=1)
            )
            for i in range(256)
        ])
        default_faults.disarm()
        inflight_rows = store.table.live
        assert inflight_rows == 512, inflight_rows
        mono[0] += 5.0  # everything past the retry interval
        n_re = store.host_sweep()  # degraded: the host fallback scan
        assert n_re == 512, f"redelivered {n_re}/512 inflight windows"
        assert store.host_sweep() == 0, "redelivery must be exactly-once"
        mid_inflight = {
            "inflight_rows": inflight_rows,
            "redelivered_exactly_once": n_re,
        }
        _mark(f"chaos_soak: mid_inflight {json.dumps(mid_inflight)}")
        # dwell out the wave-3 trip; the post wave's probe re-closes
        await asyncio.sleep(OPEN_SECS + 0.1)
        post_inflight = await phase(ing, "post-inflight-recovery")

        # wave 4 (docs/robustness.md "SLO controller"): OVERLOAD — a
        # QoS0 firehose floods the low lane WHILE the device breaker is
        # open (every launch raises) and QoS2 handshakes + $SYS
        # heartbeats flow on the control lane. Gates: the ladder widens
        # (breaker-open widens BEFORE anything sheds), control-lane p99
        # stays bounded, zero accepted-QoS1 loss.
        from emqx_tpu.broker.slo import RUNG_WIDEN, SloController

        slo = SloController(
            metrics=b.metrics,
            target_p99_ms=5.0,
            max_window_us=5000,
            eval_interval_s=0.01,
            min_samples=64,
            ladder_patience=2,
        )
        max_rung = [0]
        _set_rung = slo._set_rung

        def _track_rung(rung, reason):
            _set_rung(rung, reason)
            max_rung[0] = max(max_rung[0], rung)

        slo._set_rung = _track_rung
        ing.slo = slo
        ing.qos0_low = True
        b.subscribe(
            "sys-w", "sys-w", "$SYS/brokers/heartbeat",
            pkt.SubOpts(qos=1), deliver,
        )
        default_faults.arm("device.launch", mode="raise")
        ctrl_loss = [0]
        ctrl_lats: list = []

        async def _firehose():
            futs = []
            for i in range(2 * N_MSGS):
                futs.append(
                    ing.enqueue(
                        Message(topic=topics[i % N_MSGS], payload=b"f",
                                qos=0)
                    )
                )
                if i % 512 == 511:
                    await asyncio.sleep(0)
            return await asyncio.gather(*futs, return_exceptions=True)

        async def _control():
            for i in range(100):
                te = time.perf_counter()
                res = await asyncio.gather(
                    # QoS2 handshake publish + $SYS heartbeat: both ride
                    # the control lane (lane_of: qos==2 / $SYS prefix)
                    ing.enqueue(
                        Message(topic=topics[i % N_MSGS], payload=b"h",
                                qos=2)
                    ),
                    ing.enqueue(
                        Message(topic="$SYS/brokers/heartbeat",
                                payload=b"1", qos=1)
                    ),
                    return_exceptions=True,
                )
                ctrl_lats.append(time.perf_counter() - te)
                for r in res:
                    if not isinstance(r, IngestShed) and (
                        isinstance(r, BaseException) or r < 1
                    ):
                        ctrl_loss[0] += 1
                await asyncio.sleep(0.002)

        fire_res, _ = await asyncio.gather(_firehose(), _control())
        default_faults.disarm()
        fire_sheds = sum(1 for r in fire_res if isinstance(r, IngestShed))
        ctrl_lats.sort()
        ctrl_p99_ms = round(
            ctrl_lats[int(0.99 * (len(ctrl_lats) - 1))] * 1e3, 2
        )
        # the overload gates: breaker-open escalated the ladder to at
        # least `widen` (graded backpressure BEFORE drops), the control
        # lane's tail stayed bounded under the firehose + open breaker,
        # and every accepted QoS>=1 publish delivered
        assert max_rung[0] >= RUNG_WIDEN, max_rung[0]
        assert ctrl_loss[0] == 0, f"control-lane loss {ctrl_loss[0]}"
        assert ctrl_p99_ms <= 2500.0, (
            f"control-lane p99 {ctrl_p99_ms}ms unbounded under overload"
        )
        overload = {
            "firehose_msgs": 2 * N_MSGS,
            "firehose_sheds": fire_sheds,
            "control_p99_ms": ctrl_p99_ms,
            "control_qos_loss": ctrl_loss[0],
            "max_ladder_rung": max_rung[0],
            "deferrals": b.metrics.get("slo.deferrals"),
            "slo_sheds": b.metrics.get("slo.shed"),
        }
        _mark(f"chaos_soak: overload {json.dumps(overload)}")
        ing.slo = None  # detach before the drain (stop() settles all)
        # dwell out the wave-4 trip, then a clean phase re-probes the
        # breaker closed (the existing recovery invariant must survive
        # the overload wave too)
        await asyncio.sleep(OPEN_SECS + 0.1)
        post_overload = await phase(ing, "post-overload-recovery")
        await ing.stop()
        rt.disarm()
        races = rt.unwaived_reports()
        assert not races, "racetrack reports under chaos:\n" + "\n".join(
            r.render() for r in races
        )
        m = b.metrics

        # wave 5 (replication readiness, docs/static_analysis.md
        # "Tier B"): the shadow-replica audit rides the soak — bounded
        # randomized churn across all five mirrored owners with a
        # compaction racing loop inserts, gated on array-exact
        # convergence AND the seeded incomplete-log control detected
        from emqx_tpu.observe.replay_check import run_replay_audit

        replay = run_replay_audit(seed=2207, rounds=12, metrics=m)
        assert not replay["divergence"], replay["divergence"]
        assert replay["negative_detected"], (
            "seeded incomplete-log write went undetected"
        )
        replay_probe = {
            "owners": len(replay["owners"]),
            "syncs": m.get("replay.syncs"),
            "captures": m.get("replay.captures"),
            "compactions": replay["compactions"],
            "divergence": 0,
            "negative_detected": True,
        }
        _mark(f"chaos_soak: replay {json.dumps(replay_probe)}")
        ratio = (
            round(recovered["rps"] / baseline["rps"], 3)
            if baseline["rps"]
            else None
        )
        total_loss = (
            baseline["loss"] + wave_launch["loss"] + wave_sync["loss"]
            + recovered["loss"] + post_inflight["loss"]
            + post_overload["loss"] + ctrl_loss[0]
        )
        # the regression gate: accepted QoS1 publishes never vanish,
        # degradation keeps p99 bounded (no wedged-pipeline stall), and
        # the process comes back without a restart
        assert total_loss == 0, f"lost {total_loss} accepted messages"
        assert deg.device.state == "closed", deg.device.state
        bound_ms = max(5000.0, 10.0 * (baseline["p99_ms"] or 0.0))
        for wave in (wave_launch, wave_sync):
            assert wave["p99_ms"] is not None and wave["p99_ms"] <= bound_ms, (
                wave,
                bound_ms,
            )
        assert ratio is not None and ratio >= 0.3, (
            f"recovery rps ratio {ratio} below floor"
        )
        return {
            "messages_per_phase": N_MSGS,
            "subscriptions": sid,
            "qos1_loss": total_loss,
            "baseline": baseline,
            "fault_device_launch": wave_launch,
            "fault_delta_sync": wave_sync,
            "recovered": recovered,
            "fault_mid_inflight": mid_inflight,
            "post_inflight_recovery": post_inflight,
            "fault_overload": overload,
            "post_overload_recovery": post_overload,
            "replay_probe": replay_probe,
            "recovery_rps_ratio": ratio,
            "degrade": {
                "trips": m.get("degrade.trips.device"),
                "retries": m.get("degrade.retries"),
                "fallback_batches": m.get("degrade.fallback.batches"),
                "probe_ok": m.get("degrade.probe.ok"),
                "sync_rollbacks": m.get("router.sync.rollback"),
                "sheds": m.get("ingest.shed"),
                "faults_injected": m.get("faults.injected"),
            },
            "racetrack": {
                "unwaived_reports": len(races),
                "events": m.get("racetrack.events"),
                "disarmed_overhead_pct": 0.0,
                "note": (
                    "armed through the fault waves over Metrics, the"
                    " device breaker, and the DeviceRouter prepare"
                    " cache; disarmed registration leaves classes"
                    " untouched, so the disarmed serving-path cost is"
                    " structurally zero (<1% gate)"
                ),
            },
            "note": (
                "steady QoS1 load with scheduled faults: launch raise"
                " wave trips the breaker into CPU-trie serving (zero"
                " loss), corrupt delta-syncs roll back to the last good"
                " epoch, probabilistic admission drops surface as sheds"
                " (publisher-visible backpressure), the overload wave"
                " (QoS0 firehose + open breaker vs QoS2/$SYS control"
                " lane) holds control-lane p99 bounded with the SLO"
                " ladder escalated to widen-or-beyond, and the half-open"
                " probe recovers the device path; recovery_rps_ratio is"
                " recovered/baseline in ONE process — the 'degrades"
                " until restart' pathology is the regression this gate"
                " exists to catch"
            ),
        }

    return asyncio.run(run())


def bench_latency_frontier(deadline: Optional[float] = None) -> dict:
    """`latency_frontier` config (docs/robustness.md "SLO controller"):
    the measured latency-vs-throughput frontier the repo never had —
    paced load from 10% to 100% of calibrated max through the REAL
    ingest -> route -> dispatch pipeline with the SloController
    adapting the window each flush cycle. CI-asserted gates in the
    chaos_soak style:

    - p99 < 5 ms at 10% load (the idle-side contract: the adaptive
      window decays toward immediate partial launches);
    - frontier monotone: p99 non-decreasing (25% noise slack) as
      offered load grows — overload degrades gracefully, never cliffs;
    - priority lanes under a storm: at 100% load a QoS0 firehose floods
      the low lane while QoS2 handshakes run closed-loop on the control
      lane; control-lane p99 stays bounded and zero accepted-QoS1 loss.
    """
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.degrade import DegradeController, IngestShed
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.broker.slo import SloController
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.ops.matcher import MatcherConfig

    N_SUBS = 64
    MAX_BATCH = 512
    TARGET_P99_MS = 5.0
    LOADS = (0.10, 0.25, 0.50, 0.75, 1.00)
    POINT_S = 2.5  # measured stretch per load point
    WARM_S = 0.6  # controller-adaptation stretch (unmeasured)

    b = Broker(
        router=Router(MatcherConfig(), min_tpu_batch=64), hooks=Hooks()
    )
    deg = DegradeController(metrics=b.metrics)
    b.degrade = deg
    delivered = [0]

    def deliver(m, o):
        delivered[0] += 1

    for i in range(N_SUBS):
        b.subscribe(
            f"s{i}", f"c{i}", f"lf/{i}/#", pkt.SubOpts(qos=1), deliver
        )
    topics = [f"lf/{i % N_SUBS}/leaf" for i in range(4096)]

    async def run() -> dict:
        slo = SloController(
            metrics=b.metrics,
            target_p99_ms=TARGET_P99_MS,
            max_window_us=20_000,
            initial_window_us=1000,
            eval_interval_s=0.02,
            min_samples=64,
            ladder_patience=2,
        )
        ing = BatchIngest(
            b, max_batch=MAX_BATCH, window_us=1000, slo=slo, qos0_low=True
        )
        b.ingest = ing
        ing.start()
        # warm the serving jits outside every timed stretch
        await asyncio.gather(*[
            ing.enqueue(Message(topic=t, payload=b"w", qos=1))
            for t in topics[:MAX_BATCH]
        ])

        # -- calibrate: open-loop service rate -----------------------------
        # enqueue a fixed burst as fast as the loop allows and time the
        # FULL settle: count/wall is the pipeline's service rate at full
        # batching — the frontier's 100% point offers exactly this
        N_CAL = 30_000
        t0 = time.perf_counter()
        futs = []
        for j in range(N_CAL):
            futs.append(
                ing.enqueue(
                    Message(topic=topics[j % 4096], payload=b"p", qos=1)
                )
            )
            if j % 512 == 511:
                await asyncio.sleep(0)
                while ing._backlog() > 4 * MAX_BATCH:
                    # keep the calibration burst under the shed ladder's
                    # hard valve: we're measuring service rate, not the
                    # admission gate
                    await asyncio.sleep(0.001)
        await asyncio.gather(*futs)
        max_rps = N_CAL / (time.perf_counter() - t0)
        _mark(f"latency_frontier: calibrated max_rps={max_rps:.0f}")

        async def paced(frac: float, dur: float, record: bool):
            """Open-loop pacing at frac*max_rps; returns (lats, sheds,
            loss, achieved_rps)."""
            lats: list = []
            futs: list = []
            rate = max_rps * frac
            tick = 0.002
            acc = 0.0
            n_sent = 0

            def _mk_rec(te):
                # settle latency for DELIVERED publishes only: a shed
                # resolves instantly and would fake a low tail
                def _cb(f):
                    if not f.cancelled() and f.exception() is None:
                        lats.append(time.perf_counter() - te)

                return _cb

            t_start = time.perf_counter()
            while time.perf_counter() - t_start < dur:
                acc += rate * tick
                burst = int(acc)
                acc -= burst
                for _ in range(burst):
                    te = time.perf_counter()
                    f = ing.enqueue(
                        Message(
                            topic=topics[n_sent % 4096], payload=b"p",
                            qos=1,
                        )
                    )
                    if record:
                        f.add_done_callback(_mk_rec(te))
                    futs.append(f)
                    n_sent += 1
                await asyncio.sleep(tick)
            res = await asyncio.gather(*futs, return_exceptions=True)
            wall = time.perf_counter() - t_start
            sheds = sum(1 for r in res if isinstance(r, IngestShed))
            loss = sum(
                1
                for r in res
                if not isinstance(r, IngestShed)
                and (isinstance(r, BaseException) or r < 1)
            )
            return lats, sheds, loss, (n_sent - sheds) / wall

        frontier = []
        total_loss = 0
        for frac in LOADS:
            await paced(frac, WARM_S, record=False)  # let the window adapt
            lats, sheds, loss, rps = await paced(frac, POINT_S, record=True)
            total_loss += loss
            lats.sort()
            point = {
                "load": frac,
                "offered_rps": round(max_rps * frac, 1),
                "achieved_rps": round(rps, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 3)
                if lats
                else None,
                "p99_ms": round(
                    lats[int(0.99 * (len(lats) - 1))] * 1e3, 3
                )
                if lats
                else None,
                "sheds": sheds,
                "window_us": round(slo.window_s * 1e6, 1),
                "rung": slo.rung,
            }
            frontier.append(point)
            _mark(f"latency_frontier: {json.dumps(point)}")

        # -- storm wave: priority lanes at 100% load -----------------------
        n_fire = min(16384, max(2048, int(max_rps * 1.5)))
        ctrl_lats: list = []
        ctrl_loss = [0]

        async def _firehose():
            futs = []
            for i in range(n_fire):
                futs.append(
                    ing.enqueue(
                        Message(
                            topic=topics[i % 4096], payload=b"f", qos=0
                        )
                    )
                )
                if i % 512 == 511:
                    await asyncio.sleep(0)
            return await asyncio.gather(*futs, return_exceptions=True)

        async def _control():
            for i in range(100):
                te = time.perf_counter()
                f = ing.enqueue(
                    Message(topic=f"lf/{i % N_SUBS}/leaf", payload=b"h",
                            qos=2)
                )
                g = ing.enqueue(
                    Message(topic=f"lf/{(i + 1) % N_SUBS}/leaf",
                            payload=b"s", qos=1,
                            headers={"ingest_lane": "control"})
                )
                res = await asyncio.gather(f, g, return_exceptions=True)
                ctrl_lats.append(time.perf_counter() - te)
                for r in res:
                    if not isinstance(r, IngestShed) and (
                        isinstance(r, BaseException) or r < 1
                    ):
                        ctrl_loss[0] += 1
                await asyncio.sleep(0.002)

        fire_res, _ = await asyncio.gather(_firehose(), _control())
        fire_sheds = sum(
            1 for r in fire_res if isinstance(r, IngestShed)
        )
        await ing.stop()
        ctrl_lats.sort()
        ctrl_p99_ms = round(
            ctrl_lats[int(0.99 * (len(ctrl_lats) - 1))] * 1e3, 2
        )
        storm = {
            "firehose_msgs": n_fire,
            "firehose_sheds": fire_sheds,
            "control_p99_ms": ctrl_p99_ms,
            "control_qos_loss": ctrl_loss[0],
            "deferrals": b.metrics.get("slo.deferrals"),
            "starvation_breaks": b.metrics.get(
                "ingest.lane.starvation.breaks"
            ),
        }
        _mark(f"latency_frontier: storm {json.dumps(storm)}")

        # -- CI gates (chaos_soak style: hard asserts) ---------------------
        p99s = [p["p99_ms"] for p in frontier]
        assert all(v is not None for v in p99s), frontier
        assert p99s[0] < TARGET_P99_MS, (
            f"p99 at 10% load {p99s[0]}ms >= {TARGET_P99_MS}ms"
        )
        for a, c in zip(p99s, p99s[1:]):
            # monotone with 25% noise slack; points BOTH under the
            # target are the frontier's flat region (every sub-target
            # tail is "meeting the SLO" — sub-ms jitter there is not an
            # inversion)
            assert c >= 0.75 * a or (
                a < TARGET_P99_MS and c < TARGET_P99_MS
            ), f"frontier not monotone: {p99s}"
        assert p99s[-1] >= p99s[0], f"frontier inverted: {p99s}"
        assert total_loss == 0, f"lost {total_loss} accepted QoS1 msgs"
        assert ctrl_loss[0] == 0, (
            f"control-lane loss under storm: {ctrl_loss[0]}"
        )
        assert ctrl_p99_ms <= 2500.0, (
            f"control-lane p99 {ctrl_p99_ms}ms unbounded under storm"
        )
        return {
            "max_rps": round(max_rps, 1),
            "frontier": frontier,
            "p99_ms_at_10pct": p99s[0],
            "p99_ms_at_100pct": p99s[-1],
            "storm": storm,
            "qos1_loss": total_loss,
            "slo": {
                "eval_windows": b.metrics.get("slo.eval.windows"),
                "violations": b.metrics.get("slo.violations"),
                "adjustments": b.metrics.get("slo.adjustments"),
                "sheds": b.metrics.get("slo.shed"),
            },
            "note": (
                "paced open-loop load at 10-100% of the calibrated "
                "open-loop service rate through apublish-equivalent "
                "enqueues; "
                "p50/p99 are enqueue->settle (the publisher-visible "
                "latency incl. the adaptive window). Gates: p99@10% < "
                "5ms, monotone frontier (25% slack), bounded control-"
                "lane p99 + zero accepted-QoS1 loss under the QoS0 "
                "storm wave. CPU capture; the TPU run is the number of "
                "record (kernel-rps precedent)."
            ),
        }

    return asyncio.run(run())


def bench_session_storm(deadline: Optional[float] = None) -> dict:
    """`session_storm` config (ROADMAP item 2, docs/sessions.md): a
    reconnect storm WITH per-client delivery guarantees intact.

    Phases, all against the device-resident `SessionStore`:

    1. build — N sessions each holding one unacked QoS1 inflight row,
       bulk-placed into the open-addressing (slot, pid) table (one
       epoch bump), then mass-disconnected (state lives ONLY in the
       table — zero per-session Python objects);
    2. resume — capture/install the store (the crashed-broker shape)
       and re-arm EVERY window with ONE full upload (segment replay);
       `resume_visibility_ms` is install -> first device-swept
       redelivery landing through the REAL pipeline (the window a
       reconnected client cannot be retried in);
    3. redelivery flood — device sweeps ride serving launches
       (`session_ack_step` fused into `session_route_step`: no extra
       launch, no extra readback), each sweep returning up to
       `sweep_slots` due rows; the flood drains when every session has
       been retransmitted EXACTLY once (asserted), reporting
       `redelivery_rps`.

    The host-dict equivalence property (device store == dict store
    ack/redelivery behavior) is pinned in tier-1
    (tests/test_session_store.py), not re-measured here.
    """
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.broker.session_store import SessionStore
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.ops.nfa import _next_pow2

    N = int(os.environ.get("BENCH_SESSION_N", 1_000_000))
    SWEEP_K = 16384

    mono = [0.0]
    _mark(f"session_storm: building {N} sessions (1 QoS1 inflight each)")
    t0 = time.perf_counter()
    store = SessionStore(
        capacity=_next_pow2(2 * N), sweep_slots=SWEEP_K,
        retry_interval=1.0, clock=lambda: mono[0],
    )
    cids = [f"c{i}" for i in range(N)]
    # payload bytes are shared (the slab stores refs); pids cycle the
    # 16-bit space so (slot, pid) keys stay unique per session
    shared = Message(topic="dev/offline", payload=b"m", qos=1)
    rows = store.bulk_load(
        cids, [shared] * N, pids=(np.arange(N) % 65535) + 1
    )
    lost = int((rows < 0).sum())
    build_s = time.perf_counter() - t0
    _mark(
        f"session_storm: built in {build_s:.1f}s (table cap "
        f"{store.table._cap}, lost {lost}); mass-disconnecting"
    )
    assert lost == 0, f"{lost} rows lost in bulk placement"
    # mass disconnect: nothing to tear down — no channel or Session
    # object exists; the inflight state IS the table
    state = store.capture()

    # -- resume: a fresh broker restores the store as a segment replay --
    b = Broker(router=Router(min_tpu_batch=32), hooks=Hooks())
    store2 = SessionStore(
        capacity=64, sweep_slots=SWEEP_K, retry_interval=1.0,
        metrics=b.metrics, clock=lambda: mono[0],
    )
    b.session_store = store2
    b.subscribe("drv", "drv", "drive/#", pkt.SubOpts(), lambda m, o: None)

    class BatchSink:
        """Channel-shaped resend sink: the store's sweep routes ALL of
        a channel's due rows through `_store_resend_batch` in one call
        (docs/protocol_plane.md), and this sink pays the REAL per-row
        serialization — one slab-serializer pass building every dup
        PUBLISH frame — so `redelivery_rps` measures the batched host
        resend plane, wire bytes included, not a counting stub."""

        def __init__(self):
            self.count = 0
            self.bytes = 0
            self.first = None

        def resend(self, pid, st, msg):  # legacy per-row (unused path)
            self.count += 1
            return True

        def _store_resend_batch(self, items):
            from emqx_tpu.mqtt import slab_serializer as SS

            pubs = [
                (m.topic_bytes(), m.payload_view(), m.qos, m.retain,
                 True, pid, None)
                for pid, _st, m in items
            ]
            slab, _offs = SS.serialize_pub_slab(pubs)
            self.count += len(items)
            self.bytes += len(slab)
            if self.first is None:
                self.first = time.perf_counter()
            return [True] * len(items)

    sink = BatchSink()
    redelivered = [0]
    first_hit = [None]

    t1 = time.perf_counter()
    resumed = store2.install(state)
    for slot in range(len(store2._slot_cid)):
        store2._bind[slot] = sink.resend
    install_s = time.perf_counter() - t1
    assert resumed == N, (resumed, N)
    mono[0] += 60.0  # every window is long past its retry interval

    async def flood() -> dict:
        ing = BatchIngest(b, max_batch=256, window_us=200)
        b.ingest = ing
        ing.start()
        # warm: first launch pays the full table upload (THE replay)
        await ing.submit(Message(topic="drive/warm", payload=b"w", qos=0))
        t2 = time.perf_counter()
        sweeps = 0
        while sink.count < N:
            if deadline is not None and time.perf_counter() > deadline:
                break
            store2.request_sweep()
            futs = [
                ing.enqueue(Message(topic=f"drive/{i}", payload=b"p"))
                for i in range(64)
            ]
            await asyncio.gather(*futs)
            sweeps += 1
        wall = time.perf_counter() - t2
        await ing.stop()
        return {"wall": wall, "sweeps": sweeps}

    fl = asyncio.run(flood())
    m = b.metrics
    redelivered[0] = sink.count
    first_hit[0] = sink.first
    complete = redelivered[0] >= N

    # -- host resend plane in isolation (the PR 11 ceiling) --------------
    # The 38.3k resends/s ROADMAP tail named the HOST plane: per-row
    # Python resend callbacks + per-packet serialize + per-row stamp
    # logging. Measure that plane alone (stamps force-re-armed; device
    # mirror resyncs on the next sweep — measurement only), batched vs
    # legacy per-row, so the >=5x gate compares like with like on the
    # same CPU config and carries its own in-run baseline.
    t2 = store2.table

    def _rearm(rows_due: int) -> None:
        live = np.nonzero(t2.sess_slot >= 0)[0]
        t2.sess_ts[live] = store2.now_ds()  # all fresh (not due)
        t2.sess_ts[live[:rows_due]] = -(1 << 20)  # force-due subset
        t2._bump()

    plane = {}
    sink2 = BatchSink()
    _rearm(N)
    for slot in range(len(store2._slot_cid)):
        store2._bind[slot] = sink2.resend
    tp0 = time.perf_counter()
    sent = store2.host_sweep()
    plane_wall = time.perf_counter() - tp0
    plane["resend_plane_rps"] = round(sent / max(plane_wall, 1e-9), 1)
    plane["resend_plane_rows"] = sent
    # legacy per-row baseline on a 65536-row subset (the full table at
    # ~38k/s would eat half the config budget)
    legacy_n = min(N, 65536)
    hits = [0]

    def legacy_cb(pid, st, msg):
        hits[0] += 1
        from emqx_tpu.mqtt.frame import serialize as _ser

        _ser(
            pkt.Publish(topic=msg.topic, payload=msg.payload, qos=msg.qos,
                        retain=msg.retain, dup=True, packet_id=pid,
                        properties=dict(msg.properties)),
            pkt.MQTT_V4,
        )
        return True

    _rearm(legacy_n)
    for slot in range(len(store2._slot_cid)):
        store2._bind[slot] = legacy_cb
    tp1 = time.perf_counter()
    store2.host_sweep()
    legacy_wall = time.perf_counter() - tp1
    # NOTE: this is per-row callbacks ON the new vectorized sweep (the
    # re-verify mask + memoized dispatch lifted both paths); the PR 11
    # baseline (38.3k/s) additionally paid per-row field walks + per-row
    # stamp logging, which no longer exist to measure in-run
    plane["resend_plane_per_row_rps"] = round(
        hits[0] / max(legacy_wall, 1e-9), 1
    )
    plane["resend_plane_per_row_rows"] = hits[0]
    out = {
        "sessions": N,
        "build_s": round(build_s, 2),
        "sessions_resumed": resumed,
        "resume_install_ms": round(install_s * 1e3, 2),
        "resume_visibility_ms": round(
            (first_hit[0] - t1) * 1e3, 2
        ) if first_hit[0] else None,
        "resumed_per_s": round(N / max(install_s, 1e-9), 1),
        "redelivered": redelivered[0],
        "redelivery_rps": round(redelivered[0] / max(fl["wall"], 1e-9), 1),
        "redelivery_frame_bytes": sink.bytes,
        # PR 11's 38.3k resends/s named the HOST resend plane (per-row
        # callbacks); the slab-batched plane's gate is >=5x on the same
        # CPU config, with the in-run legacy baseline alongside
        **plane,
        "redelivery_vs_pr11_x": round(
            plane["resend_plane_rps"] / 38300.0, 2
        ),
        "sweep_launches": fl["sweeps"],
        "sweep_slots": SWEEP_K,
        "ack_rides": m.get("session.ack.rides"),
        "device_sweeps": m.get("session.sweep.device"),
        "extra_scatter_launches": store2.manager.delta_launches,
        "full_uploads": store2.manager.full_resyncs,
        "timeout": not complete,
        "note": (
            "mass disconnect -> reconnect-with-session -> QoS1"
            " redelivery flood. Resume is ONE full table upload (the"
            " segment replay; zero per-session Python objects"
            " rebuilt); the flood's retry scans are device sweeps"
            " fused into serving launches (session_ack_step riding"
            " session_route_step: extra_scatter_launches stays 0)."
            " Each session redelivers exactly once — the sweep"
            " refreshes the retransmit stamp on device AND host."
        ),
    }
    if complete:
        assert redelivered[0] == N, (redelivered[0], N)
        assert store2.manager.delta_launches == 0, (
            "ack/sweep path paid its own scatter launch"
        )
    _mark(f"session_storm: {json.dumps(out)}")
    return out


def _codec_micro() -> dict:
    """Codec-path microbench: slab vs per-record Python vs native C on
    the same 1024-record batches (propless — the hot-path shape). Rates
    are records/s for one pack+unpack round trip."""
    from emqx_tpu.broker.message import Message
    from emqx_tpu.transport import fabric as F

    msgs = [
        Message(topic=f"bench/dev{i % 64}/t{i}", payload=b"m" * 64,
                qos=i % 3, from_client=f"c{i % 16}")
        for i in range(1024)
    ]
    dlv = [(m, [i, i + 1]) for i, m in enumerate(msgs)]

    def rate(fn, reps=8):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return round(reps * len(msgs) / (time.perf_counter() - t0), 1)

    out = {
        "records": len(msgs),
        "pub_slab_rps": rate(
            lambda: F.unpack_pub_slab(F.pack_pub_slab(msgs, 1)[5:])
            .records()
        ),
        "pub_python_rps": rate(
            lambda: F._py_unpack_pub_batch(
                F._py_pack_pub_batch(msgs, 1)[5:]
            )
        ),
        "dlv_slab_rps": rate(
            lambda: [
                F.unpack_dlv_slab(f[5:]).records()
                for f in F.pack_dlv_slabs(dlv)
            ]
        ),
        "dlv_python_rps": rate(
            lambda: [
                F._py_unpack_dlv_batch(f[5:])
                for f in F._py_pack_dlv_batches(dlv)
            ]
        ),
        # slab SCAN rate without record materialization — the serving
        # path's actual cost (records() exists for compat/tests only)
        "pub_slab_scan_rps": rate(
            lambda: F.unpack_pub_slab(F.pack_pub_slab(msgs, 1)[5:])
        ),
    }
    from emqx_tpu.mqtt import codec_native as _nc

    if _nc.pack_dlv_frames is not None:
        out["pub_native_rps"] = rate(
            lambda: _nc.unpack_pub_batch(_nc.pack_pub_batch(msgs, 1)[5:])
        )
        out["dlv_native_rps"] = rate(
            lambda: [
                _nc.unpack_dlv_batch(f[5:])
                for f in _nc.pack_dlv_frames(dlv, F.MAX_BODY)
            ]
        )
    return out


# (connections, distinct topics) points: the topic-space axis is the
# CSR unlock (ops/csr_table.py) — 1M DISTINCT single-subscriber topics
# needed a ~128GB dense [fids, slot_words] matrix before the sparse
# subscriber table (router.sub_table), which stores O(subscriptions).
# The (1M, 4096) point keeps the r05-era shared-topic fleet shape
# (fan-out ~244) for curve continuity; each point now also reports the
# MEASURED sub_table_bytes next to the dense-equivalent formula bytes.
CONN_SCALING_POINTS = (
    (10_000, 4096),
    (100_000, 100_000),
    (1_000_000, 4096),
    (1_000_000, 1_000_000),
)
CONN_SCALING_MSGS = 16_384
CONN_SCALING_WORKERS = 4


def bench_conn_scaling(deadline: Optional[float] = None) -> dict:
    """`conn_scaling` config (docs/protocol_plane.md): the protocol
    plane's connection-count scaling curve — 10k -> 1M simulated
    clients over the worker plane.

    Each point builds a fresh router process in miniature: a Broker +
    BatchIngest + WorkerFabric whose N clients are real fabric
    subscriptions (the SUB json path, one client per subscription,
    spread over that point's K-topic space) on W simulated worker links
    (socketpairs with draining readers — the worker processes are
    simulated, the WIRE is real). The measured flood then drives the
    REAL router-side slab path end-to-end: packed T_PUBB_S frames ->
    vectorized unpack -> SlabMessage ingest -> device route_step ->
    dispatch -> outbox fan-out -> slab DLV frames on the socketpairs.
    `msgs_per_s` is publish-settle throughput at that connection count
    (fan-out = N/K); `deliveries_per_s` spans the full drain-to-
    quiescence window. The DISTINCT-topic points (100k and 1M topics,
    one subscriber each) exist because of the CSR subscriber table
    (router.sub_table auto-flips): they record the MEASURED
    sub_table_bytes next to the ~128GB dense-equivalent formula bytes.
    The codec microbench (slab vs per-record vs native-C) rides along.
    """
    import asyncio
    import json as _json
    import socket as _socket

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.transport import fabric as F
    from emqx_tpu.transport.workers import WorkerFabric

    rng = np.random.default_rng(7)
    points = []

    async def one_point(n_conns: int, K: int) -> dict:
        b = Broker(router=Router(min_tpu_batch=32), hooks=Hooks())

        class _App:
            broker = b
            cm = None
            retainer = None
            config = None

        fab = WorkerFabric(_App(), "/tmp/bench-conn-scaling.sock")
        socks = []
        drainers = []
        drained = [0]

        async def drain(reader):
            while True:
                data = await reader.read(1 << 20)
                if not data:
                    return
                drained[0] += len(data)

        for wid in range(CONN_SCALING_WORKERS):
            a, c = _socket.socketpair()
            _r, w = await asyncio.open_connection(sock=a)
            rd, _w2 = await asyncio.open_connection(sock=c)
            fab._writers[wid] = w
            drainers.append(asyncio.ensure_future(drain(rd)))
            socks.append((w, _w2))
        t0 = time.perf_counter()
        # N clients = N fabric subscriptions over the real SUB path
        # (each worker proxies its share; retained replay off), spread
        # over the K-topic space. Worker id mixes in i >> 12 so one
        # topic's subscribers spread over workers (i % W alone aliases
        # whenever W divides K, collapsing every fan-out onto one
        # worker's DLV stream)
        W = CONN_SCALING_WORKERS
        for i in range(n_conns):
            fab._on_sub(
                (i + (i >> 12)) % W,
                _json.dumps({
                    "h": i, "sid": f"s{i}", "cid": f"s{i}",
                    "f": f"c/{i % K}", "qos": 0, "nr": True,
                }).encode(),
            )
        build_s = time.perf_counter() - t0
        sub_mode = b.subtab.status()["mode"]
        sub_bytes = b.subtab.table_bytes()
        ing = BatchIngest(b, max_batch=512, window_us=200)
        b.ingest = ing
        ing.start()

        class _W:  # ack sink for the PUBB path
            def is_closing(self):
                return False

            def write(self, data):
                pass

        # warm: compile the 512-bucket through the real serving entry
        warm = [
            Message(topic=f"c/{int(i)}", payload=b"w")
            for i in rng.integers(0, K, 512)
        ]
        futs = [ing.enqueue(m) for m in warm]
        await asyncio.gather(*futs)
        await asyncio.sleep(0.05)
        m0_dlv = b.metrics.get("fabric.slab.dlv.records")
        m0_del = b.metrics.get("messages.delivered")
        t1 = time.perf_counter()
        targets = rng.integers(0, K, CONN_SCALING_MSGS)
        wsink = _W()
        for lo in range(0, CONN_SCALING_MSGS, 512):
            msgs = [
                Message(topic=f"c/{int(i)}", payload=b"p" * 32, qos=1,
                        from_client="pub")
                for i in targets[lo : lo + 512]
            ]
            await fab._on_pub_slab(wsink, F.pack_pub_slab(msgs, lo)[5:])
        # PUBB acks resolve when every batch settled (ingest futures)
        if fab._tasks:
            await asyncio.gather(*list(fab._tasks))
        wall = time.perf_counter() - t1
        # drain the delivery plane to QUIESCENCE (r05 regression: one
        # 50ms sleep let roughly one outbox flush tick run, so the DLV
        # ring / deliveries_per_s saturated at whatever one tick could
        # pack instead of measuring the plane): keep ticking until the
        # outboxes + parked queues are empty AND the drained byte count
        # stops moving, under an explicit budget, and SAY when the
        # budget was hit instead of publishing a capped number.
        drain_budget = 20.0
        t_dr = time.perf_counter()
        last_bytes = -1
        while time.perf_counter() - t_dr < drain_budget:
            quiet = (
                not fab._outbox
                and not fab._raw_outbox
                and not fab._parked
                and drained[0] == last_bytes
            )
            if quiet:
                break
            last_bytes = drained[0]
            await asyncio.sleep(0.05)
        drain_s = time.perf_counter() - t_dr
        drain_complete = (
            not fab._outbox and not fab._raw_outbox and not fab._parked
        )
        await ing.stop()
        for d in drainers:
            d.cancel()
        for w, w2 in socks:
            w.close()
            w2.close()
        dlv = b.metrics.get("fabric.slab.dlv.records") - m0_dlv
        raw = b.metrics.get("fabric.raw.records")
        delivered = b.metrics.get("messages.delivered") - m0_del
        # dense-equivalent bytes: what the pre-CSR [Fcap, W] matrix
        # would allocate for this point (pow2 axes, 4B words)
        from emqx_tpu.ops.nfa import _next_pow2

        nf = _next_pow2(max(64, K))
        nw = max(2, _next_pow2((n_conns + 31) // 32))
        return {
            "connections": n_conns,
            "topics": K,
            "build_s": round(build_s, 2),
            "subscribe_rps": round(n_conns / max(build_s, 1e-9), 1),
            "msgs_per_s": round(CONN_SCALING_MSGS / wall, 1),
            "deliveries_per_s": round(delivered / (wall + drain_s), 1),
            "fanout_mean": round(delivered / CONN_SCALING_MSGS, 1),
            "dlv_records": int(dlv),
            "raw_records": int(raw),
            "drain_s": round(drain_s, 2),
            "drain_complete": drain_complete,
            "drained_bytes": drained[0],
            "sub_table_mode": sub_mode,
            "sub_table_bytes": sub_bytes,
            "sub_table_bytes_per_sub": round(sub_bytes / n_conns, 1),
            "dense_equiv_bytes": nf * nw * 4,
            "zerocopy_records": b.metrics.get("ingest.zerocopy.records"),
        }

    for n, k in CONN_SCALING_POINTS:
        if deadline is not None and time.perf_counter() > deadline - 30:
            points.append({"connections": n, "topics": k,
                           "skipped": "budget"})
            _mark(f"conn_scaling[{n}/{k}t]: SKIPPED (budget)")
            continue
        try:
            points.append(asyncio.run(one_point(n, k)))
            _mark(f"conn_scaling point done: {points[-1]}")
        except Exception as e:  # noqa: BLE001 — partial > nothing
            points.append({"connections": n, "topics": k,
                           "error": repr(e)})
            _mark(f"conn_scaling[{n}/{k}t]: FAILED ({e!r}); continuing")
    good = [p for p in points if "msgs_per_s" in p]
    out = {
        "curve": points,
        "workers": CONN_SCALING_WORKERS,
        "messages_per_point": CONN_SCALING_MSGS,
        "best_msgs_per_s": max(
            (p["msgs_per_s"] for p in good), default=None
        ),
        "msgs_per_s_at_1m": next(
            (p["msgs_per_s"] for p in good
             if p["connections"] == 1_000_000), None
        ),
        "sub_table_bytes_at_1m_distinct": next(
            (p["sub_table_bytes"] for p in good
             if p["connections"] == 1_000_000
             and p["topics"] >= 100_000), None
        ),
        "codec_micro": _codec_micro(),
        "note": (
            "simulated clients over the worker plane: real fabric"
            " subscriptions + real slab wire frames over socketpair"
            " links; worker PROCESSES simulated (their sockets are the"
            " drain side). msgs_per_s = publish->settle through slab"
            " unpack -> zero-copy ingest -> device route -> slab DLV"
            " pack; deliveries_per_s over the full drain-to-quiescence"
            " window. The topics axis is the CSR unlock: distinct-"
            "topic points carry measured sub_table_bytes next to the"
            " dense-equivalent formula bytes (1M distinct topics ="
            " ~128GB dense, O(subscriptions) sparse)."
        ),
    }
    _mark(f"conn_scaling: {json.dumps(out)[:400]}")
    return out


def bench_churn_storm(rng, deadline: Optional[float] = None) -> dict:
    """`churn_storm` config (ROADMAP item 2): million-user churn against
    a 10M-subscription table on the SEGMENTED update path.

    Three phases, all against one live index + one DeviceSegmentManager:

    1. mass reconnect — waves of fresh subscribes absorbed by the shape
       hot segment (warm `bulk_add`: vectorized placement, no packed
       rebuild) and synced to the device per wave; reports
       `churn_inserts_per_s` (target > 1M/s);
    2. subscribe visibility — single subscribe -> delta sync -> a routed
       batch that provably matches it; reports the median + p99 wall
       (`subscribe_visibility_ms`, target < 10ms). This is the window a
       reconnecting client cannot receive messages;
    3. churn correctness under compaction — unsubscribe/resubscribe a
       slab, run a background-style compaction cycle mid-churn, and
       assert the device agrees with `T.match` on probe topics.
    """
    import time as _t

    from emqx_tpu.models.router_model import shape_route_step
    from emqx_tpu.ops import topics as T
    from emqx_tpu.ops.route_index import RouteIndex
    from emqx_tpu.ops.segments import (
        DeviceSegmentManager,
        SegmentCompactor,
        ShapeSegmentOwner,
    )
    from emqx_tpu.ops.tokenizer import encode_topics

    N = int(os.environ.get("BENCH_CHURN_N", 10_000_000))
    WAVES = 12
    # a network-blip reconnect storm is ~all EXISTING subscriptions
    # re-attaching; genuinely new filters are the small tail
    RESUB = 131072  # reconnecting clients re-subscribing EXISTING filters
    FRESH = 2048  # genuinely new filters per wave (the hot-segment path)

    _mark(f"churn_storm: cold-building {N} subscriptions")
    filters = [
        f"dev/{i}/+/t{i % 7}/#" if i % 3 else f"dev/{i}/s{i % 11}"
        for i in range(N)
    ]
    index = RouteIndex()
    t0 = _t.perf_counter()
    index.bulk_add(filters)
    build_s = _t.perf_counter() - t0
    del filters
    man = DeviceSegmentManager(free_retired=True)
    t0 = _t.perf_counter()
    tabs = man.sync(index.shapes)
    upload_s = _t.perf_counter() - t0
    _mark(
        f"churn_storm: built in {build_s:.1f}s, uploaded in "
        f"{upload_s:.1f}s; warming the probe program"
    )

    CFGS = dict(max_levels=8, frontier=16, max_matches=16, probes=8)
    vb, vl, _ = encode_topics(["dev/churn0/q/t0/tail"] * 256, MAX_BYTES)

    def vis_step(tabs_):
        return shape_route_step(
            tabs_, None, None, vb, vl,
            m_active=index.shapes.m_active(),
            with_nfa=False, salt=index.salt, **CFGS,
        )

    import jax

    jax.block_until_ready(vis_step(tabs)["mcount"])

    # -- phase 1: mass reconnect. A network-blip storm is mostly clients
    # RE-subscribing filters the table already holds (refcount hits +
    # bitmap writes) plus a tail of genuinely new filters (the hot-
    # segment path). Waves are pre-built so the measured wall is the
    # update path, not f-string workload generation.
    _mark(
        f"churn_storm: {WAVES} reconnect waves x "
        f"({RESUB} resub + {FRESH} fresh)"
    )
    rng2 = np.random.default_rng(0xC4)
    waves = []
    for w in range(WAVES):
        ids = rng2.integers(0, N, size=RESUB)
        batch = [
            f"dev/{i}/+/t{i % 7}/#" if i % 3 else f"dev/{i}/s{i % 11}"
            for i in ids
        ]
        batch += [f"churn/{w}/{k}/+/x/#" for k in range(FRESH)]
        waves.append(batch)
    epoch0 = index.shapes.epoch
    t0 = _t.perf_counter()
    for batch in waves:
        index.bulk_add(batch)
        tabs = man.sync(index.shapes)
    jax.block_until_ready(tabs["shape_hot"])
    churn_s = _t.perf_counter() - t0
    churn_rps = WAVES * (RESUB + FRESH) / churn_s
    assert index.shapes.epoch == epoch0, (
        "mass reconnect forced a packed rebuild — the hot segment "
        "failed to absorb the storm"
    )
    # fresh-only component rate (the pure hot-segment insert path)
    fresh_batch = [f"churnf/{k}/+/x/#" for k in range(FRESH)]
    t0 = _t.perf_counter()
    index.bulk_add(fresh_batch)
    tabs = man.sync(index.shapes)
    jax.block_until_ready(tabs["shape_hot"])
    fresh_rps = FRESH / (_t.perf_counter() - t0)

    # -- phase 2: subscribe -> routable visibility ----------------------
    vis = []
    for k in range(11):
        f = f"dev/churn{k}/+/t0/#"
        t1 = _t.perf_counter()
        index.add(f)
        out = vis_step(man.sync(index.shapes))
        mc = int(np.asarray(out["mcount"])[0])
        vis.append((_t.perf_counter() - t1) * 1e3)
        if k == 0:
            assert mc >= 1, "fresh subscription not visible to the kernel"
    vis = np.array(vis[1:])  # wave 0 may pay one-off jit/bucket warmup
    vis_ms = float(np.median(vis))

    # -- phase 3: unsubscribe/resubscribe + compaction under churn ------
    _mark("churn_storm: tombstone/resubscribe + background compaction")
    for k in range(512):
        index.remove(f"churn/0/{k}/+/x/#")
    for k in range(0, 512, 2):
        index.add(f"churn/0/{k}/+/x/#")
    tombs = index.shapes.packed_tombstones
    hot_before = index.shapes.hot_live
    owner = ShapeSegmentOwner(index.shapes, man, hot_entries=1)
    t0 = _t.perf_counter()
    assert SegmentCompactor().compact_now(owner)
    compact_s = _t.perf_counter() - t0
    tabs = man.sync(index.shapes)  # adopts the offered packed buffer
    probe = (
        ["churn/0/1/q/x/deep", "churn/0/2/q/x/deep", "dev/5/q/t5/deep"]
        * 86
    )[:256]
    pb, pl, _ = encode_topics(probe, MAX_BYTES)
    out = shape_route_step(
        tabs, None, None, pb, pl,
        m_active=index.shapes.m_active(),
        with_nfa=False, salt=index.salt, **CFGS,
    )
    mc = np.asarray(out["mcount"])[: len(probe)]
    cands = [f"churn/0/{j}/+/x/#" for j in (1, 2)] + ["dev/5/+/t5/#"]
    for i, t in enumerate(probe[:3]):
        # rebuild-equivalence spot check: count LIVE filters matching
        # (churn/0/1 was tombstoned and must stay dead; churn/0/2 was
        # tombstoned then resubscribed and must match again)
        want = sum(
            1 for f in cands
            if index.filter_id(f) is not None and T.match(t, f)
        )
        assert int(mc[i]) == want, (t, int(mc[i]), want)

    return {
        "subscriptions": len(index),
        "table_build_s": round(build_s, 1),
        "initial_upload_s": round(upload_s, 1),
        "churn_inserts": WAVES * (RESUB + FRESH),
        "churn_inserts_per_s": round(churn_rps, 1),
        "fresh_inserts_per_s": round(fresh_rps, 1),
        "churn_waves": WAVES,
        "resub_per_wave": RESUB,
        "fresh_per_wave": FRESH,
        "subscribe_visibility_ms": round(vis_ms, 3),
        "subscribe_visibility_p99_ms": round(
            float(np.percentile(vis, 99)), 3
        ),
        "compact_s": round(compact_s, 2),
        "compact_merged": hot_before,
        "tombstones_purged": tombs,
        "hot_fill_after_compact": index.shapes.hot_live,
        "delta_launches": man.delta_launches,
        "full_resyncs": man.full_resyncs,
        "note": (
            "mass reconnect + resubscribe against a 10M-sub table on the"
            " segmented update path: subscribes land in the hot segment"
            " (vectorized bulk placement, one small re-upload per wave),"
            " unsubscribes tombstone in place, and compaction merges"
            " hot->packed off the critical path (offered device buffer"
            " adopted by the next sync). targets: >1M inserts/s, <10ms"
            " subscribe->routable visibility, rebuild-equivalent"
            " recipient sets (asserted)"
        ),
    }


def hotpath_stats(waterfall_view: bool = False) -> None:
    """`--hotpath-stats`: drive a small in-process publish workload through
    the real ingest -> device-route -> dispatch pipeline, then print ONE
    JSON line of flight-recorder numbers (batch_p50/p99 from the new
    histograms, fallback rate, batch occupancy). This is the before/after
    read for hot-path perf PRs — same series the /metrics/hotpath REST
    endpoint and the Prometheus scrape export on a live broker.

    The same workload additionally runs a second time with causal span
    recording attached at the DEFAULT sampling rate
    (observe.trace_sample_rate), and the serving_rps delta is reported as
    `span_overhead` — the acceptance gate is < 5% at default sampling."""
    import asyncio

    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.ingest import BatchIngest
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.router import Router
    from emqx_tpu.config.schema import ObserveConfig
    from emqx_tpu.mqtt import packet as pkt
    from emqx_tpu.observe.spans import SpanRecorder

    N_SUBS = 32
    N_MSGS = 4096
    MAX_BATCH = 256

    async def drive(with_spans: bool):
        """One pass of the workload; returns (broker, wall_s, counts)."""
        broker = Broker(router=Router(min_tpu_batch=8), hooks=Hooks())
        if with_spans:
            # the DEFAULT sampling config, exactly as the app wires it
            broker.spans = SpanRecorder(
                metrics=broker.metrics,
                sample_rate=ObserveConfig().trace_sample_rate,
            )
        sink = []
        for i in range(N_SUBS):
            broker.subscribe(
                f"s{i}", f"c{i}", f"hot/{i}/+", pkt.SubOpts(),
                lambda m, o: sink.append(m.topic),
            )
        ing = BatchIngest(broker, max_batch=MAX_BATCH, window_us=500)
        broker.ingest = ing
        ing.start()
        # warm the compile outside the recorded window? No — the flight
        # recorder's job is to SHOW the cold-start spike; report both by
        # warming first and resetting nothing (p99 includes the compile
        # only if it landed inside the run, exactly like a live broker)
        await ing.submit(Message(topic="hot/0/warm", payload=b"w"))
        t0 = time.perf_counter()
        results = [
            await broker.apublish_enqueue(
                Message(
                    topic=f"hot/{i % N_SUBS}/x", payload=b"p",
                    # distinct clients => every publish is a fresh
                    # sampling decision (flow-consistent hash would
                    # otherwise collapse the workload to 32 flows)
                    from_client=f"bench{i}",
                )
            )
            for i in range(N_MSGS)
        ]
        futs = [r for r in results if not isinstance(r, int)]
        counts = list(await asyncio.gather(*futs))
        wall = time.perf_counter() - t0
        await ing.stop()
        return broker, wall, counts

    async def run():
        # throwaway pass: jit compiles land here, so the spans-off vs
        # spans-on comparison below is warm-vs-warm (the first measured
        # pass still reports its own cold numbers on a fresh process
        # via the histograms when the warm pass didn't cover a shape)
        await drive(with_spans=False)
        broker, wall, counts = await drive(with_spans=False)
        # second pass, spans on at default sampling: the overhead read
        b2, wall_spans, counts2 = await drive(with_spans=True)
        assert sum(counts) == sum(counts2), (sum(counts), sum(counts2))
        rps_off = sum(counts) / wall
        rps_on = sum(counts2) / wall_spans
        span_overhead = {
            "serving_rps_spans_off": round(rps_off, 1),
            "serving_rps_spans_on": round(rps_on, 1),
            "sample_rate": ObserveConfig().trace_sample_rate,
            "spans_sampled": b2.metrics.get("trace.spans.sampled"),
            "overhead_pct": round(100.0 * (1.0 - rps_on / rps_off), 2),
        }
        m = broker.metrics

        def hist_ms(name):
            h = m.histogram(name)
            if h is None or h.count == 0:
                return None
            return {
                "count": h.count,
                "p50_ms": round(h.p50 * 1e3, 3),
                "p99_ms": round(h.p99 * 1e3, 3),
            }

        def hist_raw(name):
            h = m.histogram(name)
            if h is None or h.count == 0:
                return None
            return {
                "count": h.count,
                "mean": round(h.sum / h.count, 3),
                "p50": round(h.p50, 3),
                "p99": round(h.p99, 3),
            }

        dev = m.get("messages.routed.device")
        fb = m.get("messages.routed.device_fallback")
        batch_lat = m.histogram("router.device.seconds")
        waterfall = None
        if waterfall_view:
            # `--waterfall`: the per-launch stage breakdown (prepare ->
            # queue-wait -> launch -> device-execute -> readback ->
            # host-dispatch), the same series the /metrics/hotpath REST
            # `profile` block serves
            from emqx_tpu.observe.profiler import STAGES

            waterfall = {
                s: hist_ms(f"profile.stage.{s}.seconds") for s in STAGES
            }
        from emqx_tpu.observe.provenance import stamp as _stamp

        print(
            json.dumps(
                _stamp({
                    "metric": "hotpath_flight_recorder",
                    "value": round(
                        batch_lat.p50 * 1e3, 3
                    ) if batch_lat and batch_lat.count else None,
                    "unit": "batch_p50_ms",
                    "detail": {
                        "messages": N_MSGS,
                        "deliveries": int(sum(counts)),
                        "msgs_per_s": round(N_MSGS / wall, 1),
                        "batch_p50_ms": hist_ms("router.device.seconds"),
                        "ingest_settle": hist_ms("ingest.settle.seconds"),
                        "ingest_window_wait": hist_ms(
                            "ingest.window.wait.seconds"
                        ),
                        "batch_size": hist_raw("ingest.batch.size"),
                        "batch_occupancy": hist_raw(
                            "ingest.batch.occupancy"
                        ),
                        "pipeline_depth": m.gauge("ingest.pipeline.depth"),
                        "routed_device": dev,
                        "routed_device_fallback": fb,
                        "fallback_rate": round(fb / (dev + fb), 5)
                        if dev + fb
                        else None,
                        "dispatch_fanout": hist_raw("dispatch.fanout"),
                        "span_overhead": span_overhead,
                        "waterfall": waterfall,
                    },
                })
            )
        )

    asyncio.run(run())


def _run_config(name: str, deadline: Optional[float] = None) -> dict:
    """Run one named config in THIS process and return its result dict."""
    known = CONFIGS + EXTRAS + ["e2e_serving", "serving_dispatch"]
    rng = np.random.default_rng(42 + known.index(name))
    if name == "retained_5m":
        return bench_retained(rng)
    if name == "retained_spot":
        return bench_retained_spot()
    if name == "chaos_soak":
        return bench_chaos_soak()
    if name == "latency_frontier":
        return bench_latency_frontier(deadline)
    if name == "churn_storm":
        return bench_churn_storm(rng, deadline)
    if name == "session_storm":
        return bench_session_storm(deadline)
    if name == "conn_scaling":
        return bench_conn_scaling(deadline)
    if name == "agentic_fabric":
        return bench_agentic_fabric(deadline)
    if name == "serving":
        return bench_serving_suite(deadline)
    if name == "e2e_serving":  # standalone debug entry
        return bench_e2e(deadline)
    if name == "serving_dispatch":  # standalone debug entry
        return bench_serving()
    return bench_config(
        name,
        rng,
        measure_updates=name in ("mixed_1m", "mixed_10m"),
    )


def run_one(name: str) -> None:
    """Child-process entry: one config, one JSON line on stdout."""
    if name == "_e2e_driver":
        e2e_driver(
            int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
            int(sys.argv[5]), int(sys.argv[6]), sys.argv[7],
        )
        return
    _open_device()
    from emqx_tpu.observe.provenance import stamp

    # standalone wall budget: the serving suite bounds its own waits so a
    # degraded run emits a partial JSON instead of dying to a kill
    child_budget = os.environ.get("BENCH_CHILD_BUDGET_S")
    deadline = (
        time.perf_counter() + float(child_budget) - 10.0
        if child_budget
        else None
    )
    # every per-config JSON line carries the hardware fingerprint: a
    # number with no provenance is not a number of record
    print(json.dumps(stamp(_run_config(name, deadline))))


def _store_result(results: dict, name: str, res: dict) -> None:
    if name == "serving":
        # the serving suite carries both configs; surface them under
        # their own keys so downstream reads stay stable
        for sub in ("e2e_serving", "serving_dispatch"):
            if isinstance(res.get(sub), dict):
                results[sub] = res[sub]
    else:
        results[name] = res


def run_sweep() -> int:
    """Child-process entry: the WHOLE config sweep in ONE process — the
    long-lived-process shape production serves in, and the only process
    that opens the chip.

    Emits a `BENCH_DEVICE <json>` stderr line once the backend is open
    and one `BENCH_PARTIAL <name> <json>` line per completed config (the
    parent recovers these if this process dies mid-sweep), then a final
    combined JSON line on stdout. A config that raises is recorded under
    `failed` and the sweep goes on; the exit code is non-zero if any did.
    """
    import jax

    device = {"fingerprint": _open_device(), "device": str(jax.devices()[0])}
    _mark("BENCH_DEVICE " + json.dumps(device))
    results: dict = {}
    skipped: list = []
    failed: list = []
    for name in CONFIGS + EXTRAS:
        left = BUDGET_S - (time.perf_counter() - _T0)
        if left < MIN_BUDGET_S.get(name, 120):
            skipped.append(name)
            _mark(f"{name}: SKIPPED (budget: {left:.0f}s left)")
            continue
        deadline = time.perf_counter() + left - 15.0
        # deadline-aware configs (the serving suite) also read this env
        os.environ["BENCH_CHILD_BUDGET_S"] = str(max(10, left - 15))
        try:
            res = _run_config(name, deadline)
        except Exception as e:  # noqa: BLE001 — keep sweeping, fail at exit
            failed.append(name)
            _mark(f"{name}: FAILED ({e!r}); continuing")
            continue
        _store_result(results, name, res)
        # partial capture: a later crash must not erase this result
        _mark(f"BENCH_PARTIAL {name} " + json.dumps(res))
    print(json.dumps(
        {"results": results, "skipped": skipped, "failed": failed, **device}
    ))
    return 1 if failed else 0


def main() -> int:
    # ONE child process runs the WHOLE sweep (run_sweep) and is the only
    # process that opens the chip. The parent stays off jax: it enforces
    # the gate budget, recovers BENCH_PARTIAL lines if the child dies,
    # and takes the device string and fingerprint from the child's JSON.
    import re
    import subprocess

    if len(sys.argv) > 1:
        if sys.argv[1] == "--hotpath-stats":
            _open_device()
            hotpath_stats(waterfall_view="--waterfall" in sys.argv[2:])
            return 0
        if sys.argv[1] == "--configs":
            # explicit subset run: `bench.py --configs chaos_soak[,..]`
            # — one JSON line per named config, in this process
            for n in sys.argv[2].split(","):
                run_one(n.strip())
            return 0
        if sys.argv[1] == "_sweep":
            return run_sweep()
        run_one(sys.argv[1])
        return 0

    results = {}
    skipped = []
    failed = []
    device = None
    child_rc = None
    stderr_text = ""
    try:
        proc = subprocess.run(
            [sys.executable, __file__, "_sweep"],
            capture_output=True,
            text=True,
            timeout=BUDGET_S + 60,
        )
        child_rc = proc.returncode
        stderr_text = proc.stderr
        sys.stderr.write(proc.stderr)
        lines = proc.stdout.strip().splitlines()
        if lines and lines[-1].startswith("{"):
            doc = json.loads(lines[-1])
            results = doc["results"]
            skipped = doc["skipped"]
            failed = doc["failed"]
            device = {k: doc[k] for k in ("fingerprint", "device")}
        else:
            _mark(f"sweep child FAILED rc={proc.returncode}; recovering "
                  f"partials (tail: {proc.stdout[-300:]!r})")
    except subprocess.TimeoutExpired as e:
        stderr_text = (
            (e.stderr or b"").decode("utf-8", "replace")
            if isinstance(e.stderr, bytes)
            else (e.stderr or "")
        )
        sys.stderr.write(stderr_text)
        _mark("sweep child TIMED OUT; recovering partials")
    if device is None:
        # the child died mid-sweep: it named its device once the backend
        # was open, and every completed config left a BENCH_PARTIAL line
        m = re.search(r"BENCH_DEVICE (\{.*)$", stderr_text, re.M)
        if m is None:
            _mark("sweep child never opened a TPU; nothing to report")
            return child_rc or 1
        device = json.loads(m.group(1))
        done = set()
        for m in re.finditer(
            r"BENCH_PARTIAL (\S+) (\{.*)$", stderr_text, re.M
        ):
            try:
                _store_result(results, m.group(1), json.loads(m.group(2)))
                done.add(m.group(1))
            except ValueError:
                continue
        skipped = [n for n in CONFIGS + EXTRAS if n not in done]
    fp = device["fingerprint"]

    # HEADLINE = end-to-end serving throughput: socket-to-socket msgs/s.
    # Kernel match throughput stays in detail. If e2e itself was
    # skipped/timed out, value is null but the capture still parses.
    e2e = results.get("e2e_serving") or {}
    e2e_rate = e2e.get("e2e_msgs_per_s")
    kern = results.get("mixed_10m") or results.get("share_10m") or {
        "tpu_rps": None, "speedup": None
    }
    churn = results.get("churn_storm") or {}
    conn = results.get("conn_scaling") or {}
    sess = results.get("session_storm") or {}
    full_doc = {
                "metric": "e2e_serving_msgs_per_s",
                "value": e2e_rate,
                "unit": "msgs/s",
                "fingerprint": fp,
                "proxy": bool(fp["proxy"]),
                "detail": {
                    "device": device["device"],
                    "batch": BATCH,
                    "e2e_timeout": e2e.get("timeout", False),
                    "e2e_best_workers": e2e.get("best_workers"),
                    "e2e_paced_p50_ms": e2e.get("e2e_paced_p50_ms"),
                    "e2e_paced_p99_ms": e2e.get("e2e_paced_p99_ms"),
                    "serving_rps": results.get(
                        "serving_dispatch", {}
                    ).get("serving_rps"),
                    "readback_mb_per_batch": results.get(
                        "serving_dispatch", {}
                    ).get("readback_mb_per_batch"),
                    "readback_reduction_x": results.get(
                        "serving_dispatch", {}
                    ).get("readback_reduction_x"),
                    "kernel_tpu_rps_10m": kern["tpu_rps"],
                    "kernel_speedup_vs_cpu_trie": kern["speedup"],
                    "share_10m_tpu_rps": results.get(
                        "share_10m", {}
                    ).get("tpu_rps"),
                    "update_sync_ms_10m": kern.get("update_sync_ms"),
                    "subscribe_visibility_ms_10m": kern.get(
                        "subscribe_visibility_ms"
                    ),
                    "insert_rps_10m": kern.get("insert_rps"),
                    # segmented update path (churn_storm, ROADMAP item 2)
                    "churn_inserts_per_s": churn.get(
                        "churn_inserts_per_s"
                    ),
                    "subscribe_visibility_ms": churn.get(
                        "subscribe_visibility_ms"
                    ),
                    # device-resident session state (session_storm)
                    "sessions_resumed": results.get(
                        "session_storm", {}
                    ).get("sessions_resumed"),
                    "session_resume_visibility_ms": results.get(
                        "session_storm", {}
                    ).get("resume_visibility_ms"),
                    "session_redelivery_rps": sess.get("redelivery_rps"),
                    "session_redelivery_vs_pr11_x": sess.get(
                        "redelivery_vs_pr11_x"
                    ),
                    # slab protocol plane (conn_scaling,
                    # docs/protocol_plane.md)
                    "conn_scaling_curve": conn.get("curve"),
                    "conn_msgs_per_s_at_1m": conn.get(
                        "msgs_per_s_at_1m"
                    ),
                    # CSR subscriber table (docs/serving_pipeline.md
                    # "subscriber-table memory budget"): the measured
                    # O(S) footprint at the 1M-distinct-topic point +
                    # the dense-vs-sparse serving comparison
                    "sub_table_bytes_at_1m_distinct": conn.get(
                        "sub_table_bytes_at_1m_distinct"
                    ),
                    # NB: the sweep flattens "serving" into e2e_serving
                    # + serving_dispatch result keys before this point
                    "serving_sparse_vs_dense_rps_x": results.get(
                        "serving_dispatch", {}
                    ).get("sparse_vs_dense_rps_x"),
                    # semantic routing plane (agentic_fabric,
                    # docs/semantic_routing.md): device-fused
                    # embedding routing vs the post-dispatch host
                    # filter it replaces
                    "semantic_routing_rps": results.get(
                        "agentic_fabric", {}
                    ).get("semantic_routing_rps"),
                    "semantic_vs_host_filter_x": results.get(
                        "agentic_fabric", {}
                    ).get("semantic_vs_host_filter_x"),
                    "codec_micro": conn.get("codec_micro"),
                    # SLO-driven adaptive batching (latency_frontier,
                    # docs/robustness.md): the latency-vs-throughput
                    # frontier the broker differentiates on
                    "latency_frontier": results.get(
                        "latency_frontier", {}
                    ).get("frontier"),
                    "latency_p99_ms_at_10pct": results.get(
                        "latency_frontier", {}
                    ).get("p99_ms_at_10pct"),
                    "latency_p99_ms_at_100pct": results.get(
                        "latency_frontier", {}
                    ).get("p99_ms_at_100pct"),
                    "frontier_control_p99_ms_under_storm": results.get(
                        "latency_frontier", {}
                    ).get("storm", {}).get("control_p99_ms")
                    if results.get("latency_frontier")
                    else None,
                    "skipped_configs": skipped,
                    "failed_configs": failed,
                    "wall_s": round(time.perf_counter() - _T0, 1),
                    # the note reflects the ACTUAL run (r4 shipped a
                    # hardcoded "all swept" string in a 2/8 capture)
                    "note": (
                        f"captured {len(results)} result(s): "
                        + (", ".join(results) if results else "none")
                        + (
                            f"; SKIPPED: {', '.join(skipped)}"
                            if skipped
                            else "; zero skips"
                        )
                        + (
                            f"; FAILED: {', '.join(failed)}"
                            if failed
                            else ""
                        )
                        + ". headline = e2e serving msgs/s (socket-to-"
                        "socket incl. the ingest window), best worker-"
                        "count point; the FULL sweep ran in ONE child "
                        "process (segment tables: O(delta) scatters + "
                        "free_retired grace + bounded jit caches keep a "
                        "long-lived process steady — the per-config "
                        "respawn is gone). churn_storm reports the "
                        "segmented update path (churn_inserts_per_s / "
                        "subscribe_visibility_ms at 10M subs). kernel "
                        "numbers remain in detail/configs."
                    ),
                    "configs": results,
                },
            }
    # The FULL document goes to chiprun_out/bench_full.json (the one
    # directory the chip tool brings back; git-ignored) and the FINAL
    # stdout line is a compact summary that always fits a tail capture.
    from emqx_tpu.observe.provenance import fingerprint_key

    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out"
    )
    os.makedirs(out_dir, exist_ok=True)
    full_path = os.path.join(out_dir, "bench_full.json")
    with open(full_path, "w") as f:
        json.dump(full_doc, f, indent=1)
    _mark(f"full sweep detail -> {full_path}")
    d = full_doc["detail"]
    curve = [
        {k: p.get(k) for k in ("connections", "msgs_per_s")}
        for p in (d.get("conn_scaling_curve") or [])
    ]
    print(
        json.dumps(
            {
                "metric": full_doc["metric"],
                "value": full_doc["value"],
                "unit": "msgs/s",
                # provenance rides the compact line too: a tail capture
                # alone says what silicon produced the headline
                "proxy": full_doc["proxy"],
                "fingerprint_key": fingerprint_key(fp),
                "detail": {
                    "device": d["device"],
                    "e2e_best_workers": d["e2e_best_workers"],
                    "e2e_paced_p50_ms": d["e2e_paced_p50_ms"],
                    "e2e_paced_p99_ms": d["e2e_paced_p99_ms"],
                    "serving_rps": d["serving_rps"],
                    "kernel_tpu_rps_10m": d["kernel_tpu_rps_10m"],
                    "kernel_speedup_vs_cpu_trie": d[
                        "kernel_speedup_vs_cpu_trie"
                    ],
                    "churn_inserts_per_s": d["churn_inserts_per_s"],
                    "session_redelivery_rps": d["session_redelivery_rps"],
                    "session_redelivery_vs_pr11_x": d[
                        "session_redelivery_vs_pr11_x"
                    ],
                    "conn_scaling_curve": curve,
                    "skipped_configs": skipped,
                    "failed_configs": failed,
                    "wall_s": d["wall_s"],
                    "note": (
                        f"captured {len(results)} result(s); full "
                        "detail (all configs, codec microbench, "
                        "scaling curves) in chiprun_out/bench_full.json"
                    ),
                },
            }
        )
    )
    return 1 if (failed or child_rc) else 0


if __name__ == "__main__":
    sys.exit(main())
