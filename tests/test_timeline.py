"""timeline sentinel + goodput accountant + checkpoint-budget doctor
rule (ISSUE 5: the readers layered on the telemetry ledger)."""

import json
import time

import pytest

from torchsnapshot_tpu import telemetry
from torchsnapshot_tpu.telemetry import doctor, goodput, ledger, timeline


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    goodput.reset()
    yield
    telemetry.reset()
    goodput.reset()


# ------------------------------------------------------------- sentinel


def _series(values):
    return [(f"step {i}", v) for i, v in enumerate(values)]


def test_sentinel_flags_spike_with_first_bad_step():
    hit = timeline.detect_regressions(
        _series([1.0, 1.1, 0.9, 1.0, 1.05, 5.0, 6.0]), "high"
    )
    assert hit is not None
    assert hit["label"] == "step 5"  # FIRST bad point, not the worst
    assert hit["value"] == 5.0
    assert hit["baseline_median"] == pytest.approx(1.0, abs=0.11)


def test_sentinel_low_direction():
    hit = timeline.detect_regressions(
        _series([2.0, 2.1, 1.9, 2.0, 0.4]), "low"
    )
    assert hit is not None and hit["label"] == "step 4"
    assert (
        timeline.detect_regressions(_series([2.0, 2.1, 1.9, 2.0, 2.2]), "low")
        is None
    )


def test_sentinel_needs_history():
    # Two points of history are not enough to judge the third.
    assert (
        timeline.detect_regressions(_series([1.0, 1.0, 99.0]), "high")
        is None
    )


def test_sentinel_skips_missing_values():
    # None = missing data (a skipped bench section), never zero: it
    # neither flags nor pollutes the baseline.
    hit = timeline.detect_regressions(
        _series([1.0, None, 1.1, 0.9, None, 1.0, 4.0]), "high"
    )
    assert hit is not None and hit["label"] == "step 6"
    assert (
        timeline.detect_regressions(
            _series([1.0, 1.1, 0.9, None, None, None]), "high"
        )
        is None
    )


def test_sentinel_robust_to_one_earlier_outlier():
    # Median/MAD: one early spike must not inflate the baseline into
    # hiding a later sustained drift, nor flag the healthy tail.
    values = [1.0, 1.1, 0.9, 8.0, 1.0, 0.95, 1.05, 1.0]
    hit = timeline.detect_regressions(_series(values), "high")
    assert hit is not None and hit["label"] == "step 3"
    # The outlier inside the window does not poison the median: the
    # tail (baselines that include the 8.0) stays healthy.
    tail_hit = timeline.detect_regressions(_series(values[4:]), "high")
    assert tail_hit is None


def test_sentinel_min_dev_floor():
    # Tiny absolute wiggles below min_dev never flag, whatever the MAD.
    assert (
        timeline.detect_regressions(
            _series([0.010, 0.010, 0.010, 0.012]), "high", min_dev=0.05
        )
        is None
    )


# ------------------------------------------------------------ ledger CLI


def _take_record(step, wall_s=0.1, gbps=1.0, **over):
    record = {
        "format_version": 1,
        "kind": "take",
        "ts_epoch_s": 1700000000.0 + step,
        "path": f"/run/step-{step}",
        "step": step,
        "take_id": f"t{step}",
        "world_size": 2,
        "wall_s": wall_s,
        "bytes": int(gbps * (1 << 30) * wall_s),
        "gbps": gbps,
        "stall_s": 0.0,
        "stall_pct": 0.0,
        "retries": 0,
        "faults": 0,
        "phases": {"capture_s": wall_s / 2, "write_s": wall_s / 2},
        "goodput": {"goodput_fraction": 0.97, "window_fraction": 0.97},
        "churn": {"efficiency": 0.8, "basis": "incremental"},
        "doctor": [],
    }
    record.update(over)
    return record


def _write_ledger(path, records):
    path.write_text(
        "".join(ledger.encode_line(r) + "\n" for r in records)
    )
    return str(path)


def test_timeline_healthy_ledger_exits_zero(tmp_path, capsys):
    f = _write_ledger(
        tmp_path / "ledger.jsonl",
        [_take_record(i) for i in range(20)],
    )
    assert timeline.main([f]) == 0
    out = capsys.readouterr().out
    assert "no regression" in out


def test_timeline_throughput_regression_exits_one(tmp_path, capsys):
    records = [_take_record(i) for i in range(19)]
    records.append(_take_record(19, gbps=0.2))
    f = _write_ledger(tmp_path / "ledger.jsonl", records)
    assert timeline.main([f]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION take GB/s" in out
    assert "step 19" in out


def test_timeline_goodput_drift_and_doctor_history(tmp_path, capsys):
    records = [_take_record(i) for i in range(8)]
    records += [
        _take_record(
            8 + i,
            goodput={"goodput_fraction": 0.60, "window_fraction": 0.60},
            doctor=["checkpoint-overhead-above-budget"],
        )
        for i in range(2)
    ]
    f = _write_ledger(tmp_path / "ledger.jsonl", records)
    assert timeline.main([f]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION goodput fraction" in out
    assert "checkpoint-overhead-above-budget: fired 2x" in out


def test_timeline_json_output(tmp_path, capsys):
    records = [_take_record(i) for i in range(6)]
    records.append(_take_record(6, wall_s=2.0))
    f = _write_ledger(tmp_path / "ledger.jsonl", records)
    assert timeline.main([f, "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["n_takes"] == 7
    (finding,) = [
        r for r in doc["regressions"] if r["field"] == "wall_s"
    ]
    assert finding["label"] == "step 6"
    assert len(doc["records"]) == 7


def test_timeline_no_data_exits_two(tmp_path, capsys):
    empty = tmp_path / "ledger.jsonl"
    empty.write_text("")
    assert timeline.main([str(empty)]) == 2
    assert timeline.main([str(tmp_path / "nothing-here")]) == 2
    capsys.readouterr()


def test_timeline_skips_torn_lines(tmp_path, capsys):
    records = [_take_record(i) for i in range(5)]
    raw = "".join(ledger.encode_line(r) + "\n" for r in records)
    f = tmp_path / "ledger.jsonl"
    f.write_text(raw + '{"torn": ')
    assert timeline.main([str(f)]) == 0
    err = capsys.readouterr().err
    assert "torn/corrupt ledger line(s) skipped" in err


# --------------------------------------------------------------- goodput


def test_goodput_attribution():
    acct = goodput.GoodputAccountant()
    acct.step()
    time.sleep(0.03)
    with acct.blocked("sync_take"):
        time.sleep(0.05)
    acct.step()
    snap = acct.snapshot()
    assert snap["steps"] == 2
    assert snap["train_s"] == pytest.approx(0.03, abs=0.02)
    assert snap["by_mode"]["sync_take"] == pytest.approx(0.05, abs=0.02)
    assert 0 < snap["goodput_fraction"] < 1
    assert snap["checkpoint_overhead_pct"] == pytest.approx(
        100 - 100 * snap["goodput_fraction"], abs=0.01
    )


def test_goodput_nested_blocked_counts_once():
    acct = goodput.GoodputAccountant()
    with acct.blocked("sync_take"):
        with acct.blocked("restore"):
            time.sleep(0.03)
    snap = acct.snapshot()
    assert "restore" not in snap["by_mode"]
    assert snap["by_mode"]["sync_take"] == pytest.approx(0.03, abs=0.02)


def test_goodput_snapshot_includes_open_interval():
    acct = goodput.GoodputAccountant()
    with acct.blocked("sync_take"):
        time.sleep(0.03)
        snap = acct.snapshot()  # a flight summary built mid-take
        assert snap["by_mode"]["sync_take"] >= 0.02
    assert acct.snapshot()["by_mode"]["sync_take"] >= 0.02


def test_goodput_exports_metrics():
    goodput.step()
    time.sleep(0.02)
    with goodput.blocked("drain_wait"):
        time.sleep(0.01)
    goodput.step()
    snap = telemetry.snapshot()
    assert snap["tpusnapshot_goodput_train_seconds_total"] > 0
    assert (
        snap['tpusnapshot_goodput_checkpoint_seconds_total{mode="drain_wait"}']
        > 0
    )
    assert 0 < snap["tpusnapshot_goodput_fraction"] < 1


# ------------------------------------------------- doctor budget rule


def _goodput_report(overhead_pct, window_s=100.0):
    ckpt = window_s * overhead_pct / 100.0
    return {
        "kind": "take",
        "world_size": 1,
        "ranks": [
            {
                "rank": 0,
                "wall_s": 1.0,
                "goodput": {
                    "train_s": window_s - ckpt,
                    "checkpoint_s": ckpt,
                    "by_mode": {"sync_take": ckpt},
                    "checkpoint_overhead_pct": overhead_pct,
                    "goodput_fraction": 1 - overhead_pct / 100.0,
                },
            }
        ],
        "totals": {},
    }


def test_doctor_checkpoint_overhead_rule(monkeypatch):
    findings = doctor.diagnose_report(_goodput_report(8.0))
    rules = {f.rule for f in findings}
    assert "checkpoint-overhead-above-budget" in rules
    (finding,) = [
        f for f in findings if f.rule == "checkpoint-overhead-above-budget"
    ]
    assert finding.severity == "warn"
    assert finding.evidence["budget_pct"] == 5.0
    # 2x the budget escalates to critical.
    (critical,) = [
        f
        for f in doctor.diagnose_report(_goodput_report(12.0))
        if f.rule == "checkpoint-overhead-above-budget"
    ]
    assert critical.severity == "critical"
    # Within budget, or too little evidence: silent.
    assert not [
        f
        for f in doctor.diagnose_report(_goodput_report(3.0))
        if f.rule == "checkpoint-overhead-above-budget"
    ]
    assert not [
        f
        for f in doctor.diagnose_report(_goodput_report(8.0, window_s=1.0))
        if f.rule == "checkpoint-overhead-above-budget"
    ]
    # The env budget moves the line.
    monkeypatch.setenv("TPUSNAPSHOT_CKPT_BUDGET_PCT", "20")
    assert not [
        f
        for f in doctor.diagnose_report(_goodput_report(8.0))
        if f.rule == "checkpoint-overhead-above-budget"
    ]


def test_ledger_digest_carries_doctor_rules():
    record = ledger.digest_from_report(_goodput_report(15.0))
    assert "checkpoint-overhead-above-budget" in record["doctor"]
    assert record["goodput"]["checkpoint_overhead_pct"] == 15.0
