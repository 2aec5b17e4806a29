#!/usr/bin/env python
"""Host-memory observability smoke for CI: snapmem's headline
contracts against a REAL take + restore and a REAL second process.

Three things a dashboard cannot fake, each asserted end to end:

1. **Flight reports carry a reconciling memory block.** A real take
   and restore (staging pool enabled) must land ``.report.json`` /
   ``.report.restore.json`` whose per-rank ``memory`` blocks name the
   live domains, record the process RSS, and pass
   :func:`memwatch.reconcile` (no domain high-water over its cap, no
   aggregate inconsistency).
2. **Ledger digests carry the memory rollup.** The telemetry ledger's
   digest for both ops must hold the cross-rank ``memory`` totals the
   trend tooling consumes.
3. **`ops --mem` merges processes.** A snapserve server subprocess
   (its ``stats`` RPC piggybacks the memory block) plus this process's
   trainer statusfile must merge into one fleet view with >=2
   reachable members, exit 0 while healthy, and exit 1 once the server
   is killed (``fleet-member-unreachable``).

Exit 0 on success, nonzero on any violated contract. Runs in a few
seconds on CPU (JAX_PLATFORMS=cpu).
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The pool domain needs traffic: force the restore staging pool on.
os.environ.setdefault(
    "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", str(32 * 1024 * 1024)
)

# Runnable as `python tools/mem_smoke.py` from the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from torchsnapshot_tpu import Snapshot, telemetry  # noqa: E402
from torchsnapshot_tpu.telemetry import ledger as _ledger  # noqa: E402
from torchsnapshot_tpu.telemetry import memwatch  # noqa: E402
from torchsnapshot_tpu.telemetry import ops as scope_ops  # noqa: E402
from torchsnapshot_tpu.telemetry import sampler as _sampler  # noqa: E402
from torchsnapshot_tpu.telemetry.report import (  # noqa: E402
    REPORT_FNAME,
    RESTORE_REPORT_FNAME,
)


class _Model:
    def __init__(self, params):
        self.params = params

    def state_dict(self):
        return self.params

    def load_state_dict(self, sd):
        self.params = sd


def _load_report(snap_path: str, fname: str) -> dict:
    with open(os.path.join(snap_path, fname)) as f:
        return json.load(f)


def _check_report_memory(report: dict, op: str) -> dict:
    ranks = report.get("ranks") or []
    assert ranks, f"{op} report has no rank summaries"
    mem = ranks[0].get("memory")
    assert isinstance(mem, dict) and mem.get("domains"), (
        f"{op} report rank summary must carry a memory block: "
        f"{list(ranks[0])}"
    )
    assert mem.get("rss_bytes"), f"{op} memory block must record RSS"
    violations = memwatch.reconcile(mem)
    assert not violations, (
        f"{op} memory block must reconcile, got: {violations}"
    )
    return mem


def main() -> int:
    import subprocess
    import time

    telemetry.reset()
    memwatch.reset()
    base = tempfile.mkdtemp(prefix="mem-smoke-")
    snap_path = os.path.join(base, "snap")

    # --- contract 1: take + restore flight reports reconcile ---------
    rng = np.random.RandomState(0)
    params = {
        "w": rng.randn(256 * 1024).astype(np.float32),
        "b": rng.randn(4096).astype(np.float32),
    }
    Snapshot.take(snap_path, {"model": _Model(dict(params))})
    dest = _Model({k: np.zeros_like(v) for k, v in params.items()})
    Snapshot(snap_path).restore({"model": dest})
    np.testing.assert_array_equal(dest.params["w"], params["w"])

    take_mem = _check_report_memory(
        _load_report(snap_path, REPORT_FNAME), "take"
    )
    restore_mem = _check_report_memory(
        _load_report(snap_path, RESTORE_REPORT_FNAME), "restore"
    )
    assert "staging_pool" in restore_mem["domains"], (
        f"pool-enabled restore must record the staging_pool domain: "
        f"{sorted(restore_mem['domains'])}"
    )
    print(
        f"flight reports reconcile: take domains "
        f"{sorted(take_mem['domains'])}, restore domains "
        f"{sorted(restore_mem['domains'])}, restore rss "
        f"{restore_mem['rss_bytes'] / 1024**2:.0f}MB"
    )

    # --- contract 2: ledger digests carry the memory rollup ----------
    records, _ = _ledger.read_records(snap_path)
    by_kind = {r.get("kind"): r for r in records}
    for op in ("take", "restore"):
        mem = (by_kind.get(op) or {}).get("memory")
        assert isinstance(mem, dict) and mem.get("domains"), (
            f"{op} ledger digest must carry the memory rollup: "
            f"{by_kind.get(op)}"
        )
    print("ledger digests carry per-domain memory rollups for both ops")

    # --- contract 3: ops --mem merges >=2 real processes -------------
    ops_dir = os.path.join(base, "liveops")
    os.makedirs(ops_dir)
    sample = _sampler.RuntimeSampler(rank=0).build_sample()
    assert isinstance(sample.get("memory"), dict), (
        "this process's sampler must publish its memory block"
    )
    with open(os.path.join(ops_dir, "rank0.scope.jsonl"), "w") as f:
        f.write(json.dumps(sample) + "\n")

    pf = os.path.join(base, "port")
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "torchsnapshot_tpu.snapserve.server",
            "--addr",
            "127.0.0.1:0",
            "--port-file",
            pf,
        ],
        # A read-plane sidecar never takes the chip (one process per chip).
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        for _ in range(300):
            if os.path.exists(pf):
                break
            time.sleep(0.1)
        with open(pf) as f:
            addr = f.read().strip()

        fleet = scope_ops.collect_fleet_mem(ops_dir, [addr], [])
        with_mem = [
            m
            for m in fleet["members"]
            if m.get("ok") and isinstance(m.get("memory"), dict)
        ]
        assert len(with_mem) >= 2, (
            f"fleet memory view must merge >=2 processes: "
            f"{fleet['members']}"
        )
        assert fleet["domains"], "merged domain table must not be empty"
        rc = scope_ops.main([ops_dir, "--mem", "--wire", addr])
        assert rc == 0, f"healthy fleet memory view must exit 0, got {rc}"
        proc.kill()
        proc.wait(timeout=30)
        rc = scope_ops.main([ops_dir, "--mem", "--wire", addr])
        assert rc == 1, f"a dead member must exit 1, got {rc}"
        print(
            f"ops --mem merged {len(with_mem)} processes "
            f"({len(fleet['domains'])} domains); exit contract 0 -> 1 ok"
        )
    finally:
        if proc.poll() is None:
            proc.kill()

    print("mem smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
