#!/usr/bin/env python
"""Wire-observability smoke for CI: snapflight's headline contracts
against REAL subprocesses.

Three things a dashboard cannot fake, each asserted end to end:

1. **Blackbox after a kill.** A 3-member snapserve fleet plus one
   snapwire peer take live traffic; one fleet member is SIGKILLed
   mid-conversation. The surviving client's flight recorder must dump
   a ``*.blackbox.jsonl`` that parses (torn-tail tolerant), holds the
   victim's last RPCs with their trace ids, and records the degrade
   mark for the dead member.
2. **Ops fleet exit-code contract.** ``ops --wire`` over the same
   fleet returns 0 while healthy, 1 once a member is down
   (``fleet-member-unreachable``), and 2 when every target is dark.
3. **Doctor rule on injected pressure.** A scripted ``slow_wire``
   fault under a short per-RPC deadline deterministically trips the
   ``deadline-margin-collapsing`` rule on the take's wire window.

Exit 0 on success, 1 on any violated contract. Runs in a few seconds
on CPU (JAX_PLATFORMS=cpu).
"""

import os
import signal
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Runnable as `python tools/wire_smoke.py` from the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WIRETAP_DIR = tempfile.mkdtemp(prefix="wire-smoke-blackbox-")
os.environ["TPUSNAPSHOT_WIRETAP_DIR"] = WIRETAP_DIR
# Fail fast against the SIGKILLed member: one short deadline, a tiny
# retry budget, and no lingering down-cooldown between ops invocations.
os.environ["TPUSNAPSHOT_REPLICATION_DEADLINE_S"] = "2"
os.environ["TPUSNAPSHOT_REPLICATION_RETRY_BUDGET_S"] = "1"

from torchsnapshot_tpu import snapserve, tracing, wiretap  # noqa: E402
from torchsnapshot_tpu.fingerprint import fingerprint_host  # noqa: E402
from torchsnapshot_tpu.hottier.peer import spawn_peer  # noqa: E402
from torchsnapshot_tpu.hottier.transport import (  # noqa: E402
    RemotePeer,
    clear_wire_faults,
    script_wire_fault,
)
from torchsnapshot_tpu.hottier.transport import (  # noqa: E402
    HostLostError,
)
from torchsnapshot_tpu.telemetry import ops as scope_ops  # noqa: E402
from torchsnapshot_tpu.telemetry.doctor import diagnose_report  # noqa: E402


def main() -> int:
    import subprocess
    import time

    wiretap.reset()
    base = tempfile.mkdtemp(prefix="wire-smoke-")

    # --- a real 3-member fleet + 1 peer, all subprocesses ------------
    procs, addrs = [], []
    for i in range(3):
        pf = os.path.join(base, f"port-{i}")
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "torchsnapshot_tpu.snapserve.server",
                    "--addr",
                    "127.0.0.1:0",
                    "--port-file",
                    pf,
                ],
                # Read-plane sidecars never take the chip (one process
                # per chip).
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
        )
        for _ in range(300):
            if os.path.exists(pf):
                break
            time.sleep(0.1)
        with open(pf) as f:
            addrs.append(f.read().strip())
    peer_proc, peer_addr, _ = spawn_peer(
        host_id=1, capacity_bytes=1 << 24, register=False
    )
    peer = RemotePeer(host_id=1, addr=peer_addr)
    print(f"fleet on {','.join(addrs)}; peer on {peer_addr}")

    try:
        # Live traffic, all under one trace id so the blackbox joins
        # the snapxray timeline.
        with tracing.trace_scope("take") as trace_id:
            for addr in addrs:
                snapserve.ping_server(addr, timeout_s=10.0)
            payload = b"w" * 4096
            peer.put(
                "obj",
                payload,
                tag=fingerprint_host(payload),
                root="memory://wire-smoke/run",
            )

            # Contract 2a: healthy fleet -> exit 0.
            spec = ",".join(addrs)
            rc = scope_ops.main(["--wire", spec, "--wire-peers", f"1={peer_addr}"])
            assert rc == 0, f"healthy fleet must exit 0, got {rc}"

            # Contract 1: SIGKILL member 1 mid-conversation; the
            # survivor's next RPC fails, degrades, and dumps.
            victim, victim_addr = procs[1], addrs[1]
            victim.kill()
            victim.wait(timeout=30)
            assert victim.returncode == -signal.SIGKILL
            try:
                snapserve.ping_server(victim_addr, timeout_s=2.0)
            except Exception:
                pass
            wiretap.note_degrade("fleet_member_down", peer=victim_addr)

        dumps = [
            os.path.join(WIRETAP_DIR, n)
            for n in os.listdir(WIRETAP_DIR)
            if n.endswith(".blackbox.jsonl")
        ]
        assert dumps, f"no blackbox dump in {WIRETAP_DIR}"
        records, skipped = wiretap.read_blackbox(dumps[0])
        assert skipped == 0, f"clean dump must parse whole: {skipped}"
        assert records[0].get("kind") == "blackbox_header", records[0]
        events = [r for r in records if "op" in r]
        victim_rpcs = [e for e in events if e.get("peer") == victim_addr]
        assert victim_rpcs, "survivor blackbox must hold the victim's RPCs"
        assert any(e.get("outcome") == "ok" for e in victim_rpcs)
        assert any(e.get("outcome") != "ok" for e in victim_rpcs)
        assert any(e.get("trace") == trace_id for e in victim_rpcs), (
            "blackbox events must join the snapxray trace by trace id"
        )
        marks = [r for r in records if "mark" in r]
        assert any(m["mark"] == "fleet_member_down" for m in marks), marks
        print(
            f"blackbox: {len(events)} events, {len(victim_rpcs)} on the "
            f"victim, degrade mark present, trace ids join {trace_id}"
        )

        # Contract 2b/2c: one member down -> 1; whole fleet dark -> 2.
        rc = scope_ops.main(["--wire", spec])
        assert rc == 1, f"one dead member must exit 1, got {rc}"
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)
        peer_proc.kill()
        peer_proc.wait(timeout=30)
        rc = scope_ops.main(
            ["--wire", spec, "--wire-peers", f"1={peer_addr}"]
        )
        assert rc == 2, f"an all-dark fleet must exit 2, got {rc}"
        print("ops --wire exit contract: 0 healthy, 1 degraded, 2 dark")
    finally:
        peer.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if peer_proc.poll() is None:
            peer_proc.kill()

    # --- contract 3: injected slow_wire trips the doctor rule --------
    from torchsnapshot_tpu.hottier.peer import start_local_peer

    os.environ["TPUSNAPSHOT_REPLICATION_DEADLINE_S"] = "0.2"
    os.environ["TPUSNAPSHOT_REPLICATION_RETRY_BUDGET_S"] = "10"
    server, _ = start_local_peer(host_id=7, register=False)
    slow = RemotePeer(host_id=7, addr=server.addr)
    token = wiretap.window_begin()
    try:
        script_wire_fault("slow_wire", host=7, seconds=0.6)
        payload = b"s" * 1024
        try:
            slow.put(
                "slow-obj",
                payload,
                tag=fingerprint_host(payload),
                root="memory://wire-smoke/slow",
            )
        except HostLostError:  # pragma: no cover - budget raced
            pass
    finally:
        clear_wire_faults()
        slow.close()
        server.stop()
    window = wiretap.window_collect(token)
    report = {"kind": "take", "ranks": [{"rank": 0, "wire": window}]}
    findings = [
        f
        for f in diagnose_report(report)
        if f.rule == "deadline-margin-collapsing"
    ]
    assert findings, (
        f"injected slow_wire must trip deadline-margin-collapsing: "
        f"{window}"
    )
    assert findings[0].severity == "critical", findings[0]
    print(
        "doctor: deadline-margin-collapsing fired on injected slow_wire "
        f"({findings[0].evidence.get('deadline_misses')} miss(es))"
    )
    print("wire smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
