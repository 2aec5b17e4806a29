"""bench.py's exit discipline: whatever the link does, the bench's stdout
carries exactly one parsed JSON summary line (VERDICT r4 #1: the r4
artifact was an rc=124 kill with no JSON) — and the exit code is 0 only
for a run that completed with every section that ran succeeding.

The deadline tests run bench.py as a subprocess on CPU with
TPUSNAPSHOT_BENCH_THROTTLE_GBPS simulating a slow link; they are marked
``slow`` (each burns tens of seconds of real wall-clock by design). The
exit-code tests drive ``bench.main`` in-process with a stub body and are
fast.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO_ROOT, "bench.py")


def _run_bench(tmp_path, budget_s: int, throttle_gbps: float, nbytes: int):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache"),
            "TPUSNAPSHOT_BENCH_THROTTLE_GBPS": str(throttle_gbps),
            "TPUSNAPSHOT_BENCH_TOTAL_BUDGET_S": str(budget_s),
            "TPUSNAPSHOT_BENCH_BYTES": str(nbytes),
            "TPUSNAPSHOT_BENCH_DIR": str(tmp_path),
        }
    )
    proc = subprocess.run(
        [sys.executable, _BENCH],
        env=env,
        capture_output=True,
        text=True,
        timeout=budget_s + 60,  # the bench must beat this comfortably
        cwd=_REPO_ROOT,
    )
    # Both scenarios are aborts: the summary is printed AND the exit
    # code says the run did not complete.
    assert proc.returncode == 1, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])
    assert doc["metric"] == "snapshot_take_GBps"
    return doc, proc


@pytest.mark.slow
def test_bench_supervisor_emits_when_stuck_in_one_call(tmp_path):
    """A link so slow the WARMUP take cannot finish inside the budget:
    the body thread is stuck inside one blocking Snapshot.take, so only
    the supervisor can emit. rc=1 + parsed JSON + abort reason."""
    # 100 MiB warmup at 0.002 GB/s ≈ 50 s > the 40 s budget.
    doc, proc = _run_bench(
        tmp_path, budget_s=40, throttle_gbps=0.002, nbytes=256 << 20
    )
    assert doc["degraded"] is True
    assert doc["abort"] and "stuck" in doc["abort"]
    assert doc["wall_s"] <= 50  # emitted at the deadline, not the kill
    assert "HARD DEADLINE" in proc.stderr


@pytest.mark.slow
def test_bench_phase_gate_aborts_gracefully_with_partial_results(tmp_path):
    """A link that carries the warmup and one take but not the restore:
    the body's own deadline gate fires between phases, so the summary
    carries the CERTIFIED take numbers plus the abort reason."""
    # Warmup ~10 s, one 512 MiB take ~25 s at 0.02 GB/s, then the
    # restore gate (needs 60 s) fails against the ~90 s budget.
    doc, _ = _run_bench(
        tmp_path, budget_s=90, throttle_gbps=0.02, nbytes=512 << 20
    )
    assert doc["degraded"] is True
    assert doc["abort"] is not None
    # The take DID complete and its numbers are in the artifact.
    assert doc["n_take_runs"] >= 1
    assert doc["value"] is not None and doc["value"] > 0
    assert doc["take_vs_ceiling"] is not None
    assert doc["wall_s"] <= 95


@pytest.fixture
def bench(monkeypatch, tmp_path):
    """bench.py imported in-process with clean shared state, its bench
    directory under tmp_path and the compile cache left alone."""
    import torchsnapshot_tpu.utils.compile_cache as compile_cache

    monkeypatch.syspath_prepend(_REPO_ROOT)
    import bench as bench_mod

    monkeypatch.setattr(
        compile_cache, "configure_compile_cache", lambda checkout: "unused"
    )
    monkeypatch.setenv("TPUSNAPSHOT_BENCH_DIR", str(tmp_path))
    bench_mod._RESULTS.clear()
    bench_mod._EMITTED.clear()
    yield bench_mod
    bench_mod._RESULTS.clear()
    bench_mod._EMITTED.clear()


def _summary(capsys) -> dict:
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    return json.loads(lines[0])


def test_bench_exits_nonzero_when_a_section_that_ran_fails(
    bench, monkeypatch, capsys
):
    """The in-situ stall loop runs in the bench's own process (one
    process per chip); when it raises, the section records ok=false and
    the process exit code is non-zero — after the summary is printed."""
    import benchmarks.in_situ_stall as stall

    def _boom(**kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(stall, "run_stall", _boom)

    def _body(bench_dir):
        bench._RESULTS["hot_tier"] = {"ok": True}
        bench._RESULTS["step_stall"] = bench._run_stall_bench(reduced=True)
        bench._RESULTS["abort"] = None
        bench._emit_summary()

    monkeypatch.setattr(bench, "_bench_body", _body)
    assert bench.main() == 1
    doc = _summary(capsys)
    assert doc["failed_sections"] == ["step_stall"]
    assert doc["step_stall"]["ok"] is False
    assert "device lost" in doc["step_stall"]["error"]
    assert doc["abort"] is None


def test_bench_exits_zero_when_sections_pass_or_were_skipped(
    bench, monkeypatch, capsys
):
    def _body(bench_dir):
        bench._RESULTS["hot_tier"] = {"ok": True}
        bench._RESULTS["wire"] = {
            "ok": False,
            "skipped": "deadline",
            "error": "skipped: hard deadline",
        }
        bench._RESULTS["abort"] = None
        bench._emit_summary()

    monkeypatch.setattr(bench, "_bench_body", _body)
    assert bench.main() == 0
    assert _summary(capsys)["failed_sections"] == []


def test_bench_exits_nonzero_when_the_body_raises(bench, monkeypatch, capsys):
    def _body(bench_dir):
        raise RuntimeError("no accelerator")

    monkeypatch.setattr(bench, "_bench_body", _body)
    assert bench.main() == 1
    doc = _summary(capsys)
    assert "no accelerator" in doc["abort"] and doc["degraded"] is True
