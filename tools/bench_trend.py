"""Fingerprint-grouped benchmark trend report + regression gate.

Loads a directory of driver capture files (`BENCH_r*.json` /
`MULTICHIP_r*.json` wrappers), groups every run by its hardware
fingerprint (observe/provenance.py), and
compares each metric ONLY against the most recent earlier run with the
SAME fingerprint. Cross-fingerprint comparison is rejected outright: a
throughput delta between a TPU v5p run and a 1-core CPU proxy run is
not a regression, it is a hardware swap, and the honest answer is "not
comparable" — not a percentage.

Captures that carry no fingerprint are backfilled `fingerprint: null,
proxy: true` and filed under the `legacy` group, which is never
comparable to anything (including itself — an unattributed number has
no provenance to match on). The tree holds no capture files; the ledger
(PERF_LEDGER.jsonl) supersedes this tool (ROADMAP D1).

Regression rule: a metric regresses when it moves in its BAD direction
(lower for throughput/speedup series, higher for latency/footprint
series) by more than its threshold fraction vs the last same-
fingerprint value. Thresholds are deliberately loose by default (25%):
this gate catches cliffs, not noise — the SLO lanes own the fine
percentiles.

Usage:
    python -m tools.bench_trend               # markdown report, exit 0
    python -m tools.bench_trend --check       # exit 1 on any regression
    python -m tools.bench_trend --dir PATH    # trajectory directory
    python -m tools.bench_trend --threshold 0.4
    python -m tools.bench_trend --out trend.md
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

# metric-name heuristics for the BAD direction. Default is higher-is-
# better (throughput trajectory); these mark lower-is-better. The `_ms`
# arm must NOT match `_msgs_per_s` — hence the lookahead.
_LOWER_RE = re.compile(
    r"_ms(?:_|$)|latency|_seconds|_bytes|overhead_pct"
)

# never gated: bookkeeping, wall budgets, identifiers, curve blobs
_SKIP_KEYS = {
    "n",
    "rc",
    "wall_s",
    "e2e_timeout",
    "e2e_best_workers",
    "skipped_configs",
    "note",
    "device",
    "batch",
    "baseline",
    "configs",
    "fingerprint",
    "proxy",
    "fingerprint_key",
}

DEFAULT_THRESHOLD = 0.25
# per-metric overrides where the default is wrong for the series' noise
THRESHOLDS: Dict[str, float] = {
    # e2e serving rides a subprocess socket harness — noisier than the
    # kernel series, so give it extra headroom before flagging
    "e2e_serving_msgs_per_s": 0.35,
}

LEGACY_KEY = "legacy"


def lower_is_better(name: str) -> bool:
    return _LOWER_RE.search(name) is not None


def threshold_for(name: str, default: float) -> float:
    return THRESHOLDS.get(name, default)


def _last_json_line(text: str) -> Optional[Dict]:
    """Extract the last parseable one-line JSON object from a tail
    capture (the driver wrappers store stdout tails, where the final
    line is bench.py's compact summary — when the run survived)."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            return doc
    return None


def _numeric_items(d: Dict, prefix: str = "") -> List[Tuple[str, float]]:
    out: List[Tuple[str, float]] = []
    for k, v in d.items():
        if k in _SKIP_KEYS:
            continue
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out.append((prefix + k, float(v)))
    return out


def _harvest_metrics(doc: Dict) -> Dict[str, float]:
    """Flatten one bench summary doc to {metric_name: value}."""
    out: Dict[str, float] = {}
    metric = doc.get("metric")
    value = doc.get("value")
    if isinstance(metric, str) and isinstance(value, (int, float)) \
            and not isinstance(value, bool):
        out[metric] = float(value)
    detail = doc.get("detail")
    if isinstance(detail, dict):
        for name, v in _numeric_items(detail):
            out[name] = v
    return out


def _fingerprint_key(fp: Optional[Dict]) -> str:
    if not isinstance(fp, dict):
        return LEGACY_KEY
    from emqx_tpu.observe.provenance import fingerprint_key

    try:
        return fingerprint_key(fp)
    except Exception:  # noqa: BLE001 — malformed stamp => legacy
        return LEGACY_KEY


def load_run(path: str) -> Optional[Dict[str, Any]]:
    """One trajectory file -> a run record, or None when unreadable.

    Handles all three committed shapes: the driver wrapper
    (`{n, cmd, rc, tail, parsed}`), bench.py's own full document
    (`{metric, value, detail, ...}`), and the multichip wrapper
    (`{n_devices, rc, ok, skipped, tail}`)."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(raw, dict):
        return None
    base = os.path.basename(path)
    m = re.search(r"_r(\d+)", base)
    rnd = int(m.group(1)) if m else raw.get("n")
    run: Dict[str, Any] = {
        "source": base,
        "round": rnd,
        "kind": "multichip" if base.startswith("MULTICHIP") else "bench",
        "ok": True,
        "metrics": {},
    }
    doc: Optional[Dict] = None
    if "tail" in raw:  # driver / multichip wrapper
        run["ok"] = (raw.get("rc") == 0) and not raw.get("skipped")
        doc = _last_json_line(raw.get("tail") or "")
        # provenance stamped on the wrapper itself wins over the tail's
        if isinstance(raw.get("fingerprint"), dict):
            doc = dict(doc or {})
            doc["fingerprint"] = raw["fingerprint"]
            doc["proxy"] = raw.get("proxy", True)
    elif "metric" in raw or "detail" in raw:  # a bare bench document
        doc = raw
    if doc is not None:
        run["metrics"] = _harvest_metrics(doc)
        fp = doc.get("fingerprint")
    else:
        fp = raw.get("fingerprint")
    if not isinstance(fp, dict):
        # legacy backfill: pre-provenance captures have no fingerprint;
        # they are kept in the report but are never comparable
        fp = None
    run["fingerprint"] = fp
    run["proxy"] = bool(doc.get("proxy", True)) if doc else True
    if fp is not None:
        run["proxy"] = bool(fp.get("proxy", run["proxy"]))
    run["key"] = _fingerprint_key(fp)
    return run


def load_trajectory(root: str) -> List[Dict[str, Any]]:
    paths = sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json"))
        + glob.glob(os.path.join(root, "MULTICHIP_r*.json"))
    )
    runs = [load_run(p) for p in paths]
    runs = [r for r in runs if r is not None]

    def order(r):
        return (r["round"] if r["round"] is not None else 10**6,
                r["source"])

    runs.sort(key=order)
    return runs


def compare(runs: List[Dict[str, Any]], default_threshold: float
            ) -> Dict[str, Any]:
    """Walk the trajectory; for every bench run, diff each metric
    against the last SAME-fingerprint run that carried it. Returns
    {regressions, improvements, deltas, rejected} where `rejected`
    counts would-be comparisons refused for provenance reasons."""
    last_by_key: Dict[str, Dict[str, Tuple[float, str]]] = {}
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    deltas: List[Dict[str, Any]] = []
    rejected = 0
    for run in runs:
        if run["kind"] != "bench" or not run["metrics"]:
            continue
        key = run["key"]
        if key == LEGACY_KEY:
            # no provenance => nothing to anchor a comparison to; the
            # run still seeds nothing (legacy never baselines legacy)
            rejected += 1
            continue
        prev = last_by_key.setdefault(key, {})
        other_keys = [k for k in last_by_key if k != key and k !=
                      LEGACY_KEY]
        if other_keys and not prev:
            # a fingerprint flip mid-trajectory: every metric of this
            # run WOULD have compared against the other group
            rejected += 1
        for name, value in run["metrics"].items():
            if name in prev:
                base, base_src = prev[name]
                entry = {
                    "metric": name,
                    "value": value,
                    "baseline": base,
                    "baseline_source": base_src,
                    "source": run["source"],
                    "fingerprint_key": key,
                }
                if base != 0:
                    worse = (
                        (base - value) / abs(base)
                        if not lower_is_better(name)
                        else (value - base) / abs(base)
                    )
                    entry["delta_pct"] = round(
                        100.0 * (value - base) / abs(base), 2
                    )
                    thr = threshold_for(name, default_threshold)
                    if worse > thr:
                        entry["threshold_pct"] = round(100.0 * thr, 1)
                        regressions.append(entry)
                    elif worse < -thr:
                        improvements.append(entry)
                deltas.append(entry)
            prev[name] = (value, run["source"])
    return {
        "regressions": regressions,
        "improvements": improvements,
        "deltas": deltas,
        "rejected": rejected,
    }


def render_markdown(runs: List[Dict[str, Any]], cmp: Dict[str, Any]
                    ) -> str:
    lines = ["# Benchmark trend (fingerprint-grouped)", ""]
    groups: Dict[str, List[Dict]] = {}
    for r in runs:
        groups.setdefault(r["key"], []).append(r)
    for key in sorted(groups):
        rs = groups[key]
        proxy = any(r["proxy"] for r in rs)
        label = "legacy (no fingerprint — never comparable)" \
            if key == LEGACY_KEY else f"`{key}`"
        lines.append(f"## Fingerprint {label}"
                     + (" — PROXY (non-TPU)" if proxy else ""))
        lines.append("")
        lines.append("| round | source | kind | ok | metrics |")
        lines.append("|---|---|---|---|---|")
        for r in rs:
            head = ", ".join(
                f"{k}={v:.4g}" for k, v in sorted(r["metrics"].items())
                [:4]
            ) or "—"
            lines.append(
                f"| {r['round']} | {r['source']} | {r['kind']} | "
                f"{'yes' if r['ok'] else 'NO'} | {head} |"
            )
        lines.append("")
    lines.append(f"Cross-fingerprint / unattributable comparisons "
                 f"rejected: {cmp['rejected']}")
    lines.append("")
    if cmp["regressions"]:
        lines.append("## REGRESSIONS")
        lines.append("")
        for e in cmp["regressions"]:
            lines.append(
                f"- **{e['metric']}**: {e['value']:.4g} vs "
                f"{e['baseline']:.4g} ({e['delta_pct']:+.1f}%, "
                f"threshold {e['threshold_pct']}%) — {e['source']} vs "
                f"{e['baseline_source']}"
            )
        lines.append("")
    else:
        lines.append("No regressions against same-fingerprint "
                     "baselines.")
        lines.append("")
    if cmp["improvements"]:
        lines.append("## Improvements")
        lines.append("")
        for e in cmp["improvements"]:
            lines.append(
                f"- {e['metric']}: {e['value']:.4g} vs "
                f"{e['baseline']:.4g} ({e['delta_pct']:+.1f}%) — "
                f"{e['source']} vs {e['baseline_source']}"
            )
        lines.append("")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="trajectory directory (default: repo root)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any same-fingerprint regression "
                         "is flagged")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="default fractional regression threshold")
    ap.add_argument("--out", default=None,
                    help="write the markdown report here (default: "
                         "stdout)")
    args = ap.parse_args(argv)
    root = args.dir or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    runs = load_trajectory(root)
    if not runs:
        print(f"bench_trend: no trajectory files under {root}",
              file=sys.stderr)
        return 0 if not args.check else 0
    cmp = compare(runs, args.threshold)
    report = render_markdown(runs, cmp)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(report + "\n")
    else:
        print(report)
    if args.check and cmp["regressions"]:
        print(
            f"bench_trend: {len(cmp['regressions'])} regression(s) vs "
            "same-fingerprint baselines (see report)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
