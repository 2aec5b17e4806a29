"""Trend rendering + regression sentinel over the telemetry ledger.

Usage::

    python -m torchsnapshot_tpu.telemetry.timeline <ledger-root-url>
    python -m torchsnapshot_tpu.telemetry.timeline /path/ledger.jsonl
    python -m torchsnapshot_tpu.inspect <base> --timeline

Where ledger.py is the durable record, this is the reader that answers
the longitudinal questions: per-step trends of take seconds, GB/s,
budget-stall %, retries, manifest churn (incremental efficiency), and
goodput fraction — plus a **rolling-baseline regression sentinel**: for
every metric, each point is compared against the median/MAD of the
preceding window; a deviation in the *bad* direction past
``max(k * 1.4826 * MAD, rel_floor * |median|, min_dev)`` flags a
regression naming the metric and the first bad step. Median/MAD is the
robust choice here: one earlier outlier must not inflate the baseline
into hiding a real drift (the classic failure of mean/stddev baselines
on noisy shared-tenancy links).

The sentinel also folds the doctor-rule firing history recorded per
take — "retry-storm fired at steps 40, 45, 50" is a trend even when no
single metric trips.

Exit codes: 0 = healthy; 1 = regression flagged; 2 = usage / no data.
"""

import argparse
import json
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

# (dotted field, label, bad direction, min absolute deviation,
#  per-metric relative floor — None defers to the CLI's --rel-floor).
# Normalized metrics (fractions, ratios in [0, 1]) carry a tight
# relative floor of their own: a goodput drop from 0.97 to 0.60 is a
# major regression that a 50%-of-median floor would wave through.
_MetricDef = Tuple[str, str, str, float, Optional[float]]
_TAKE_METRICS: List[_MetricDef] = [
    ("wall_s", "take seconds", "high", 0.05, None),
    ("gbps", "take GB/s", "low", 0.0, None),
    ("stall_pct", "budget stall %", "high", 10.0, None),
    ("retries", "storage retries", "high", 5.0, None),
    ("churn.efficiency", "incremental efficiency", "low", 0.1, 0.15),
    # Codec stage (chunkstore.py): stored/logical bytes through the
    # per-chunk codec — a RISING ratio means compression is buying
    # less (codec misconfigured, payload entropy shifted). None (no
    # codec ran) is missing data, never a regression.
    ("churn.codec_ratio", "codec ratio", "high", 0.02, 0.2),
    # The WINDOWED fraction (since the previous ledger record, stamped
    # at append time): the cumulative fraction flattens as a run grows,
    # so late-run overhead creep would hide inside it.
    ("goodput.window_fraction", "goodput fraction", "low", 0.02, 0.1),
]
_RESTORE_METRICS: List[_MetricDef] = [
    ("wall_s", "restore seconds", "high", 0.05, None),
    ("gbps", "restore GB/s", "low", 0.0, None),
    # Consume profile: consume GB/s as a fraction of the H2D probe.
    # Dropping means consume is falling further behind the hardware
    # bound. Null (no probe, or a record that predates the profile) is
    # missing data, never a regression.
    ("consume.h2d_fraction", "consume/H2D fraction", "low", 0.02, 0.3),
]
# Drain event records (kind "tierdown", appended by the hot tier when a
# committed root fully tiers down): the durability-lag trend — the RPO
# exposure window creeping up across a run is exactly the regression
# this sentinel exists to name.
_DRAIN_METRICS: List[_MetricDef] = [
    ("durability_lag_s", "durability lag s", "high", 0.05, None),
]


def _get(doc: Dict[str, Any], dotted: str) -> Optional[float]:
    cur: Any = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return float(cur) if isinstance(cur, (int, float)) else None


def _median(values: List[float]) -> float:
    return float(statistics.median(values))


# ------------------------------------------------------------- the sentinel


def detect_regressions(
    points: List[Tuple[str, Optional[float]]],
    direction: str,
    *,
    window: int = 8,
    min_history: int = 3,
    mad_k: float = 5.0,
    rel_floor: float = 0.5,
    min_dev: float = 0.0,
) -> Optional[Dict[str, Any]]:
    """First regression in a ``(label, value)`` series, or None.

    Missing values (``None`` — a record that predates the metric) are
    excluded from baselines and never flagged: missing data is not
    zero."""
    present: List[Tuple[str, float]] = [
        (lab, v) for lab, v in points if v is not None
    ]
    for i, (label, value) in enumerate(present):
        baseline = [v for _, v in present[max(0, i - window) : i]]
        if len(baseline) < min_history:
            continue
        med = _median(baseline)
        mad = _median([abs(v - med) for v in baseline])
        threshold = max(
            mad_k * 1.4826 * mad, rel_floor * abs(med), min_dev
        )
        deviation = (value - med) if direction == "high" else (med - value)
        if deviation > threshold:
            return {
                "label": label,
                "value": round(value, 6),
                "baseline_median": round(med, 6),
                "baseline_mad": round(mad, 6),
                "deviation": round(deviation, 6),
                "threshold": round(threshold, 6),
                "direction": direction,
            }
    return None


def run_sentinel(
    series: Dict[str, List[Tuple[str, Optional[float]]]],
    metric_defs: List[_MetricDef],
    **knobs: Any,
) -> List[Dict[str, Any]]:
    findings = []
    for field, label, direction, min_dev, rel_floor in metric_defs:
        metric_knobs = dict(knobs)
        if rel_floor is not None:
            metric_knobs["rel_floor"] = min(
                rel_floor, metric_knobs.get("rel_floor", rel_floor)
            )
        hit = detect_regressions(
            series.get(field, []),
            direction,
            min_dev=min_dev,
            **metric_knobs,
        )
        if hit is not None:
            findings.append(dict(hit, metric=label, field=field))
    return findings


# ------------------------------------------------------------ ledger mode


def _record_label(record: Dict[str, Any], index: int) -> str:
    step = record.get("step")
    return f"step {step}" if step is not None else f"#{index}"


def build_series(
    records: List[Dict[str, Any]],
    metric_defs: List[_MetricDef],
) -> Dict[str, List[Tuple[str, Optional[float]]]]:
    series: Dict[str, List[Tuple[str, Optional[float]]]] = {}
    for i, record in enumerate(records):
        label = _record_label(record, i)
        for field, *_ in metric_defs:
            value = _get(record, field)
            if (
                field == "churn.efficiency"
                and (record.get("churn") or {}).get("basis") == "full"
            ):
                # A deliberate full take (full_period, first save) has
                # efficiency 0 by construction, not by regression — it
                # is missing data for the dedup-efficiency trend.
                value = None
            series.setdefault(field, []).append((label, value))
    return series


def doctor_history(
    records: List[Dict[str, Any]],
) -> Dict[str, List[str]]:
    """rule id -> labels of the records it fired on."""
    out: Dict[str, List[str]] = {}
    for i, record in enumerate(records):
        for rule in record.get("doctor") or []:
            out.setdefault(rule, []).append(_record_label(record, i))
    return out


def _fmt(v: Optional[float], spec: str = "8.3f") -> str:
    return format(v, spec) if isinstance(v, (int, float)) else " " * (
        int(spec.split(".")[0]) - 1
    ) + "—"


def render_ledger(records: List[Dict[str, Any]]) -> List[str]:
    lines = [
        f"{'record':>9s} {'kind':>10s} {'wall_s':>8s} {'GB/s':>8s} "
        f"{'stall%':>7s} {'retry':>5s} {'churn':>6s} {'goodput':>7s} "
        f"{'durlag':>7s} {'c/h2d':>6s}  doctor"
    ]
    for i, r in enumerate(records):
        doctor = ",".join(r.get("doctor") or []) or "-"
        goodput_col = _get(r, "goodput.window_fraction")
        if goodput_col is None:
            goodput_col = _get(r, "goodput.goodput_fraction")
        lines.append(
            f"{_record_label(r, i):>9s} {str(r.get('kind', '?')):>10s} "
            f"{_fmt(r.get('wall_s'))} {_fmt(r.get('gbps'), '8.4f')} "
            f"{_fmt(_get(r, 'stall_pct'), '7.1f')} "
            f"{_fmt(r.get('retries'), '5.0f')} "
            f"{_fmt(_get(r, 'churn.efficiency'), '6.2f')} "
            f"{_fmt(goodput_col, '7.3f')} "
            f"{_fmt(_get(r, 'durability_lag_s'), '7.2f')} "
            f"{_fmt(_get(r, 'consume.h2d_fraction'), '6.2f')}  {doctor}"
        )
    return lines


def analyze_ledger(
    records: List[Dict[str, Any]], **knobs: Any
) -> Dict[str, Any]:
    takes = [r for r in records if r.get("kind") in ("take", "async_take")]
    restores = [r for r in records if r.get("kind") == "restore"]
    drains = [r for r in records if r.get("kind") == "tierdown"]
    findings = (
        run_sentinel(
            build_series(takes, _TAKE_METRICS), _TAKE_METRICS, **knobs
        )
        + run_sentinel(
            build_series(restores, _RESTORE_METRICS),
            _RESTORE_METRICS,
            **knobs,
        )
        + run_sentinel(
            build_series(drains, _DRAIN_METRICS), _DRAIN_METRICS, **knobs
        )
    )
    return {
        "n_records": len(records),
        "n_takes": len(takes),
        "n_restores": len(restores),
        "n_drains": len(drains),
        "doctor_history": doctor_history(records),
        "regressions": findings,
    }


# -------------------------------------------------------------------- CLI


def _render_findings(result: Dict[str, Any]) -> List[str]:
    lines = []
    history = result.get("doctor_history") or {}
    if history:
        lines.append("doctor-rule history:")
        for rule, labels in sorted(history.items()):
            lines.append(
                f"  {rule}: fired {len(labels)}x ({', '.join(labels)})"
            )
    regressions = result.get("regressions") or []
    if not regressions:
        lines.append("sentinel: no regression — trends within baseline")
    else:
        lines.append(f"sentinel: {len(regressions)} regression(s)")
        for r in regressions:
            arrow = "rose" if r["direction"] == "high" else "fell"
            lines.append(
                f"  REGRESSION {r['metric']}: {arrow} to {r['value']:g} at "
                f"{r['label']} (baseline median {r['baseline_median']:g}, "
                f"deviation {r['deviation']:g} > threshold "
                f"{r['threshold']:g})"
            )
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m torchsnapshot_tpu.telemetry.timeline",
        description="Render per-step checkpoint telemetry trends from a "
        "ledger and run the rolling-baseline regression sentinel.",
    )
    parser.add_argument(
        "path",
        help="ledger root URL (reads <path>/.telemetry/ledger.jsonl) or "
        "a ledger .jsonl file",
    )
    parser.add_argument("--json", action="store_true", help="emit JSON")
    parser.add_argument(
        "--window", type=int, default=8, help="rolling baseline size"
    )
    parser.add_argument(
        "--min-history",
        type=int,
        default=3,
        help="records required before a point is judged",
    )
    parser.add_argument(
        "--mad-k",
        type=float,
        default=5.0,
        help="MAD multiplier for the deviation threshold",
    )
    parser.add_argument(
        "--rel-floor",
        type=float,
        default=0.5,
        help="minimum deviation as a fraction of the baseline median",
    )
    args = parser.parse_args(argv)
    knobs = {
        "window": args.window,
        "min_history": args.min_history,
        "mad_k": args.mad_k,
        "rel_floor": args.rel_floor,
    }

    from . import ledger as _ledger

    try:
        records, skipped = _ledger.read_records(args.path)
    except Exception as e:
        print(f"error reading ledger at {args.path}: {e}", file=sys.stderr)
        return 2
    if not records:
        print(
            f"no ledger records at {args.path} (no committed takes, or "
            f"not a ledger root)",
            file=sys.stderr,
        )
        return 2
    result = analyze_ledger(records, **knobs)
    result["n_torn_lines_skipped"] = skipped
    if args.json:
        result["records"] = records
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        if skipped:
            print(
                f"note: {skipped} torn/corrupt ledger line(s) skipped",
                file=sys.stderr,
            )
        for line in render_ledger(records) + _render_findings(result):
            print(line)
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
