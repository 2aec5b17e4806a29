"""snapmem: the unified host-memory plane — domain registry/window
mechanics, the leak sentinel's exit contract over a synthetic ledger,
the faultline ``mem_pressure`` rule deterministically tripping
``host-memory-overcommit``, real take/restore flight-report
reconciliation, ``ops --mem`` fleet merging, and the doctor/slo rules
(PR 20 acceptance criteria)."""

import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, telemetry
from torchsnapshot_tpu.staging_pool import StagingPool
from torchsnapshot_tpu.telemetry import doctor as _doctor
from torchsnapshot_tpu.telemetry import memwatch
from torchsnapshot_tpu.telemetry import metrics as _metrics
from torchsnapshot_tpu.telemetry import ops as scope_ops


@pytest.fixture(autouse=True)
def _fresh_memwatch():
    telemetry.reset()
    memwatch.reset()
    yield
    memwatch.reset()
    telemetry.reset()


class _Model:
    def __init__(self, params):
        self.params = params

    def state_dict(self):
        return self.params

    def load_state_dict(self, sd):
        self.params = sd


# ------------------------------------------------------------- registry


def test_domain_charge_release_and_high_water():
    d = memwatch.register("t.a", cap_bytes=1000)
    d.charge(700)
    d.release(300)
    snap = memwatch.snapshot()
    assert snap["domains"]["t.a"]["used_bytes"] == 400
    assert snap["domains"]["t.a"]["high_water_bytes"] == 700
    assert snap["domains"]["t.a"]["cap_bytes"] == 1000
    assert snap["committed_bytes"] == 400
    d.close()
    assert "t.a" not in memwatch.snapshot()["domains"]


def test_same_name_instances_aggregate():
    a = memwatch.register("t.multi", cap_bytes=100)
    b = memwatch.register("t.multi", cap_bytes=100)
    a.set_used(30)
    b.set_used(50, pinned_bytes=20)
    entry = memwatch.snapshot()["domains"]["t.multi"]
    assert entry["used_bytes"] == 80
    assert entry["pinned_bytes"] == 20
    a.close()
    b.close()


def test_provider_domain_and_external_exclusion():
    memwatch.register_provider("t.poll", lambda: (256, 0, 512))
    memwatch.register_provider(
        "t.remote", lambda: (4096, 4096, None), external=True
    )
    snap = memwatch.snapshot()
    assert snap["domains"]["t.poll"]["used_bytes"] == 256
    assert snap["domains"]["t.remote"]["external"]
    # External bytes are reported but never counted as this process's
    # committed host memory.
    assert snap["committed_bytes"] == 256
    memwatch.unregister_provider("t.poll")
    memwatch.unregister_provider("t.remote")


def test_window_collects_per_domain_high_water_and_counters():
    d = memwatch.register("t.win", cap_bytes=None, watch_residual="used")
    token = memwatch.window_begin()
    d.charge(900)
    d.counter("hits", 2)
    d.release(900)
    block = memwatch.window_collect(token)
    dom = block["domains"]["t.win"]
    assert dom["high_water_bytes"] == 900
    assert dom["end_used_bytes"] == 0
    assert dom["residual_bytes"] == 0
    assert dom["counters"] == {"hits": 2}
    assert memwatch.reconcile(block) == []
    d.close()


def test_window_survives_domain_closed_mid_window():
    token = memwatch.window_begin()
    d = memwatch.register("t.gone", cap_bytes=4096)
    d.charge(2048)
    d.close()
    block = memwatch.window_collect(token)
    assert block["domains"]["t.gone"]["high_water_bytes"] == 2048
    assert block["domains"]["t.gone"]["cap_bytes"] == 4096


def test_host_budget_env_override(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_HOST_MEM_BUDGET", str(123 << 20))
    budget, source = memwatch.host_budget_bytes()
    assert budget == 123 << 20
    assert source == "env"
    block = memwatch.sample_block()
    assert block["budget_bytes"] == 123 << 20


def test_forecast_overcommit_records_event(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_HOST_MEM_BUDGET", str(1 << 20))
    token = memwatch.window_begin()
    ev = memwatch.forecast(1 << 30, kind="take")
    assert ev is not None and ev["overcommit"]
    block = memwatch.window_collect(token)
    assert block.get("forecasts")
    finding = _doctor.memory_pressure_finding(block, source="test")
    assert finding is not None
    assert finding.rule == "host-memory-overcommit"
    assert finding.severity == "warn"  # forecast only, nothing landed


def test_reconcile_flags_over_cap_domain():
    bad = {
        "domains": {"x": {"high_water_bytes": 200, "cap_bytes": 100}},
        "high_water_bytes": 200,
    }
    assert any("exceeds cap" in v for v in memwatch.reconcile(bad))


# --------------------------------------------------------- leak sentinel


def _leak_records(n=6):
    """A synthetic ledger series with one injected never-releasing
    domain and one healthy domain that returns to baseline."""
    return [
        {
            "format_version": 1,
            "kind": "take",
            "ts_epoch_s": 1000.0 + i,
            "memory": {
                "domains": {
                    "leaky.retainer": {
                        "residual_bytes": (i + 1) * (2 << 20)
                    },
                    "healthy.pool": {
                        "residual_bytes": 0 if i % 2 else 1024
                    },
                }
            },
        }
        for i in range(n)
    ]


def _write_ledger(path, records):
    from torchsnapshot_tpu.telemetry import ledger as _ledger

    path.write_text(
        "\n".join(_ledger.encode_line(r) for r in records) + "\n"
    )


def test_leak_sentinel_names_injected_domain():
    findings = memwatch.leak_findings(_leak_records())
    assert len(findings) == 1
    assert findings[0].rule == "memory-leak-suspected"
    assert findings[0].evidence["domain"] == "leaky.retainer"


def test_leak_sentinel_cli_exit_contract(tmp_path):
    leaky = tmp_path / "leaky.jsonl"
    _write_ledger(leaky, _leak_records())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "torchsnapshot_tpu.telemetry.memwatch",
            str(leaky),
            "--json",
        ],
        capture_output=True,
        text=True,
        env=env,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"][0]["rule"] == "memory-leak-suspected"
    assert (
        doc["findings"][0]["evidence"]["domain"] == "leaky.retainer"
    ), doc

    # A flat residual (retention, not growth) exits 0.
    flat = tmp_path / "flat.jsonl"
    _write_ledger(
        flat,
        [
            {
                "format_version": 1,
                "kind": "take",
                "ts_epoch_s": 1000.0 + i,
                "memory": {
                    "domains": {
                        "steady.pool": {"residual_bytes": 4 << 20}
                    }
                },
            }
            for i in range(8)
        ],
    )
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "torchsnapshot_tpu.telemetry.memwatch",
            str(flat),
        ],
        capture_output=True,
        text=True,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # An unreadable path exits 2.
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "torchsnapshot_tpu.telemetry.memwatch",
            str(tmp_path / "nope" / "missing.jsonl"),
        ],
        capture_output=True,
        text=True,
        env=env,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr


def test_memwatch_self_test():
    assert memwatch._self_test() == 0


# ----------------------------------------------------- faultline fault


def test_mem_pressure_fault_trips_overcommit():
    from torchsnapshot_tpu.faultline.schedule import (
        FaultController,
        FaultSchedule,
    )

    d = memwatch.register("staging_pool", cap_bytes=1 << 20)
    d.set_used(4096, pinned_bytes=4096)
    ctl = FaultController(
        FaultSchedule().mem_pressure("staging_pool", 100)
    )
    # Before the fault fires: healthy.
    assert (
        _doctor.memory_pressure_finding(memwatch.sample_block()) is None
    )
    ctl.on_op("write", "some/object")
    snap = memwatch.snapshot()
    assert snap["domains"]["staging_pool"]["cap_bytes"] == 100
    finding = _doctor.memory_pressure_finding(
        memwatch.sample_block(), source="test"
    )
    assert finding is not None
    assert finding.rule == "host-memory-overcommit"
    assert finding.severity == "critical"
    assert finding.evidence["over_cap_domains"][0]["domain"] == (
        "staging_pool"
    )
    # The injected cap override is a fault, not an accounting bug:
    # reconciliation of a window block stays clean.
    token = memwatch.window_begin()
    assert memwatch.reconcile(memwatch.window_collect(token)) == []
    d.close()


# -------------------------------------------------- real take / restore


def test_take_restore_reports_carry_reconciling_memory(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(
        "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", str(8 << 20)
    )
    from torchsnapshot_tpu import staging_pool as _pool

    _pool.reset_staging_pool()
    snap_path = str(tmp_path / "snap")
    rng = np.random.RandomState(0)
    params = {"w": rng.randn(64 * 1024).astype(np.float32)}
    Snapshot.take(snap_path, {"model": _Model(dict(params))})
    dest = _Model({"w": np.zeros_like(params["w"])})
    Snapshot(snap_path).restore({"model": dest})
    np.testing.assert_array_equal(dest.params["w"], params["w"])

    for fname, expect_domain in (
        (".report.json", "scheduler.write"),
        (".report.restore.json", "staging_pool"),
    ):
        with open(os.path.join(snap_path, fname)) as f:
            report = json.load(f)
        mem = report["ranks"][0].get("memory")
        assert isinstance(mem, dict), f"{fname} missing memory block"
        assert expect_domain in mem["domains"], (
            fname,
            sorted(mem["domains"]),
        )
        assert mem.get("rss_bytes"), f"{fname} must record RSS"
        assert memwatch.reconcile(mem) == []
        # The report rules see the same block the sentinel reads.
        assert _doctor._merged_memory(report), fname

    # The ledger digest rolls the same windows up for trend tooling.
    from torchsnapshot_tpu.telemetry import ledger as _ledger

    records, _ = _ledger.read_records(snap_path)
    by_kind = {r.get("kind"): r for r in records}
    for kind in ("take", "restore"):
        assert (by_kind[kind].get("memory") or {}).get("domains"), (
            by_kind[kind]
        )
    _pool.reset_staging_pool()


# ------------------------------------------------------------ ops --mem


def _scope_line(rank, mem):
    return json.dumps(
        {"format_version": 1, "rank": rank, "ts": 1.0, "memory": mem}
    )


def _mem_block(used, cap, hwm, budget=1 << 30):
    return {
        "domains": {
            "staging_pool": {
                "used_bytes": used,
                "pinned_bytes": used,
                "cap_bytes": cap,
                "high_water_bytes": hwm,
            }
        },
        "committed_bytes": used,
        "high_water_bytes": hwm,
        "budget_bytes": budget,
        "budget_source": "env",
        "rss_bytes": 10 << 20,
        "headroom_bytes": budget - used,
    }


def test_ops_mem_merges_ranks_and_flags_overcommit(tmp_path):
    ops_dir = tmp_path / "liveops"
    ops_dir.mkdir()
    (ops_dir / "rank0.scope.jsonl").write_text(
        _scope_line(0, _mem_block(1024, 4096, 2048)) + "\n"
    )
    (ops_dir / "rank1.scope.jsonl").write_text(
        _scope_line(1, _mem_block(8192, 4096, 8192)) + "\n"
    )
    fleet = scope_ops.collect_fleet_mem(str(ops_dir), [], [])
    assert fleet["reachable"] == 2
    merged = fleet["domains"]["staging_pool"]
    assert merged["members"] == 2
    assert merged["used_bytes"] == 1024 + 8192
    assert merged["high_water_bytes"] == 2048 + 8192
    findings = scope_ops.fleet_mem_findings(fleet)
    assert any(
        f.rule == "host-memory-overcommit" and f.severity == "critical"
        for f in findings
    ), findings
    # CLI exit contract: the over-cap rank makes the view exit 1.
    assert scope_ops.main([str(ops_dir), "--mem"]) == 1


def test_ops_mem_healthy_exits_zero(tmp_path, capsys):
    ops_dir = tmp_path / "liveops"
    ops_dir.mkdir()
    (ops_dir / "rank0.scope.jsonl").write_text(
        _scope_line(0, _mem_block(1024, 4096, 2048)) + "\n"
    )
    assert scope_ops.main([str(ops_dir), "--mem"]) == 0
    out = capsys.readouterr().out
    assert "fleet memory:" in out
    assert "staging_pool" in out


def test_ops_mem_all_unreachable_exits_two(tmp_path):
    # One dead server target, no trainer path: the view is dark.
    rc = scope_ops.main(
        ["--mem", "--wire", "127.0.0.1:1", "--wire-timeout", "2"]
    )
    assert rc == 2


# ----------------------------------------------------------- doctor/slo


def _report_with_memory(mem, kind="restore"):
    return {"kind": kind, "ranks": [{"rank": 0, "memory": mem}]}


def test_doctor_rule_memory_leak_single_report():
    mem = {
        "domains": {
            "staging_pool": {
                "high_water_bytes": 8 << 20,
                "residual_bytes": 4 << 20,
            }
        },
        "high_water_bytes": 8 << 20,
    }
    findings = _doctor.diagnose_report(_report_with_memory(mem))
    leak = [f for f in findings if f.rule == "memory-leak-suspected"]
    assert leak and leak[0].evidence["domain"] == "staging_pool"


def test_doctor_rule_staging_pool_thrash():
    mem = {
        "domains": {
            "staging_pool": {
                "high_water_bytes": 4096,
                "cap_bytes": 4096,
                "residual_bytes": 0,
                "counters": {"hits": 1, "misses": 5, "waits": 3},
            }
        },
        "high_water_bytes": 4096,
    }
    findings = _doctor.diagnose_report(_report_with_memory(mem))
    thrash = [f for f in findings if f.rule == "staging-pool-thrash"]
    assert thrash, findings
    assert thrash[0].evidence["waits"] == 3
    # A pool mostly serving hits is healthy no matter the waits=0.
    mem["domains"]["staging_pool"]["counters"] = {
        "hits": 50,
        "misses": 2,
        "waits": 0,
    }
    findings = _doctor.diagnose_report(_report_with_memory(mem))
    assert not [f for f in findings if f.rule == "staging-pool-thrash"]


def test_doctor_rule_cache_cap_misfit_thrash_and_oversize():
    thrash = _doctor.cache_misfit_finding(
        {
            "hits": 10,
            "misses": 40,
            "evictions": 30,
            "inserts": 40,
            "cap_bytes": 1000,
            "high_water_bytes": 990,
        }
    )
    assert thrash is not None and thrash.rule == "cache-cap-misfit"
    assert "thrashing" in thrash.title
    oversize = _doctor.cache_misfit_finding(
        {
            "hits": 50,
            "misses": 10,
            "evictions": 0,
            "inserts": 10,
            "cap_bytes": 100000,
            "high_water_bytes": 100,
        }
    )
    assert oversize is not None and "oversized" in oversize.title
    healthy = _doctor.cache_misfit_finding(
        {
            "hits": 45,
            "misses": 15,
            "evictions": 2,
            "inserts": 15,
            "cap_bytes": 1000,
            "high_water_bytes": 600,
        }
    )
    assert healthy is None


def test_slo_live_memory_rule_self_test():
    from torchsnapshot_tpu.telemetry import slo as _slo

    assert _slo._self_test() == 0


# -------------------------------------------------------- domain wiring


def test_staging_pool_publishes_domain_and_gauges():
    from torchsnapshot_tpu.staging_pool import StagingPool

    pool = StagingPool(capacity_bytes=1 << 20)
    lease = pool.acquire(4096)
    entry = memwatch.snapshot()["domains"]["staging_pool"]
    assert entry["pinned_bytes"] >= 4096
    assert entry["cap_bytes"] == 1 << 20
    lease.release()
    stats = pool.stats()
    assert stats["high_water_bytes"] >= 4096
    entry = memwatch.snapshot()["domains"]["staging_pool"]
    assert entry["pinned_bytes"] == 0  # leased bytes returned


def test_byte_lru_publishes_domain_and_counters():
    from torchsnapshot_tpu.snapserve.cache import ByteLRU

    cache = ByteLRU(cap_bytes=8192)
    cache.put("k1", b"x" * 4096)
    assert cache.get("k1") is not None
    assert cache.get("absent") is None
    entry = memwatch.snapshot()["domains"]["snapserve.cache"]
    assert entry["used_bytes"] == 4096
    assert entry["cap_bytes"] == 8192
    stats = cache.stats()
    assert stats["high_water_bytes"] >= 4096
    token = memwatch.window_begin()
    cache.put("k2", b"y" * 4096)
    block = memwatch.window_collect(token)
    counters = block["domains"]["snapserve.cache"]["counters"]
    assert counters.get("inserts") == 1


def test_scheduler_registers_transient_write_domain(tmp_path):
    # A plain take registers scheduler.write for the window and closes
    # it after: nothing may linger in the global registry.
    Snapshot.take(
        str(tmp_path / "snap"),
        {"model": _Model({"w": np.zeros(16, dtype=np.float32)})},
    )
    assert "scheduler.write" not in memwatch.snapshot()["domains"]


# -------------------------------------- collector callbacks take no lock
#
# The collector runs finalisers on whatever thread allocates, also
# inside that thread's own critical sections. Each case plants a
# collection INSIDE the metrics registry's lock and drops, beforehand,
# something only the collector can free: a cyclic owner of a MemDomain,
# or an unreleased lease in a cycle. A finaliser that asks for the
# registry's lock there waits on its own thread for ever.


class _CollectInsideItems(dict):
    """Planted as ``REGISTRY._metrics``: ``items()`` and ``snapshot()``
    walk it with the lock held."""

    def items(self):
        gc.collect()
        time.sleep(0.05)  # let the other thread's calls queue on the lock
        return super().items()


class _CollectInsideInit(_metrics.Gauge):
    """A metric constructor: ``_get_or_create`` calls it between its
    check and its insert, with the lock held."""

    def __init__(self):
        gc.collect()
        time.sleep(0.05)
        super().__init__()


def _drop_cyclic_pool():
    pool = StagingPool(capacity_bytes=1 << 20)
    pool.acquire(4096).release()  # 4096 B retained: the domain is not empty
    pool.cycle = pool


def _drop_cyclic_lease(pool):
    box = [pool.acquire(4096)]
    box.append(box)


def _collect_inside(region, pool):
    registry = _metrics.REGISTRY
    if region == "get_or_create":
        _drop_cyclic_pool()
        registry._get_or_create("t_planted", _CollectInsideInit, {})
        return
    if region == "items":
        _drop_cyclic_pool()
    else:
        _drop_cyclic_lease(pool)
    planted = _CollectInsideItems(registry._metrics)
    registry._metrics = planted
    try:
        registry.items() if region == "items" else registry.snapshot()
    finally:
        registry._metrics = dict(planted)


@pytest.mark.time_limit(10)
@pytest.mark.parametrize("thread", ["same", "second"])
@pytest.mark.parametrize("region", ["items", "get_or_create", "lease_in_snapshot"])
def test_collection_inside_the_registry_lock_does_not_deadlock(region, thread):
    pool = StagingPool(capacity_bytes=1 << 20)
    switch_interval = sys.getswitchinterval()
    gc.collect()
    gc.disable()  # the planted collection is the one that finds them
    try:
        if thread == "same":
            _collect_inside(region, pool)
        else:
            # The drain is safe against ordinary calls made meanwhile by
            # a thread that holds nothing.
            t = threading.Thread(
                target=_collect_inside, args=(region, pool), daemon=True
            )
            sys.setswitchinterval(1e-5)
            t.start()
            while t.is_alive():
                memwatch.snapshot()
                pool.acquire(512).release()
                telemetry.gauge("t_other").set(1)
            t.join(timeout=5)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch_interval)
        gc.enable()
    # The next ordinary entries settle what the collector handed off,
    # and every reading is what an immediate close / release gives.
    stats = pool.stats()
    entry = memwatch.snapshot()["domains"]["staging_pool"]
    assert stats["in_use_bytes"] == 0
    assert entry["instances"] == 1  # the dropped pool's domain is closed
    assert entry["pinned_bytes"] == 0
    assert entry["used_bytes"] == stats["free_bytes"]
    assert memwatch._TOTAL_USED == stats["free_bytes"]
    used = telemetry.gauge(_metrics.MEM_DOMAIN_USED, domain="staging_pool")
    assert used.value == stats["free_bytes"]


def test_dropped_owner_and_lease_settle_at_the_next_entry():
    credited = []
    pool = StagingPool(capacity_bytes=1 << 20)
    lease = pool.acquire(4096)
    lease.set_budget_release(credited.append, 4096)
    del lease  # an error path dropping its plan: no release()
    assert pool.stats() == {
        "free_bytes": 4096,
        "in_use_bytes": 0,
        "capacity_bytes": 1 << 20,
        "high_water_bytes": 4096,
    }
    assert credited == [4096]  # the budget re-credit fired, once
    del pool
    gc.collect()
    assert "staging_pool" not in memwatch.snapshot()["domains"]
    assert memwatch._TOTAL_USED == 0
    used = telemetry.gauge(_metrics.MEM_DOMAIN_USED, domain="staging_pool")
    assert used.value == 0


def test_the_package_has_one_finaliser_and_one_del():
    """The rule (docs/OBSERVABILITY.md, Metrics) holds because the
    collector's callbacks are few enough to read: a new one is added
    here, after its reach has been walked for locks."""
    import re
    from pathlib import Path

    import torchsnapshot_tpu

    root = Path(torchsnapshot_tpu.__file__).parent
    callback = re.compile(
        r"weakref\.(?:finalize|ref|proxy|Weak\w+)\(|def __del__\("
    )
    found = sorted(
        (str(path.relative_to(root)), m.group(0))
        for path in root.rglob("*.py")
        for m in callback.finditer(path.read_text())
    )
    assert found == [
        ("staging_pool.py", "def __del__("),
        ("telemetry/memwatch.py", "weakref.finalize("),
    ]
