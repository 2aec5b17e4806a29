"""Phases inside staging and inside consume (telemetry/consume_profile.py).

One accumulator for both pipelines: a take leaves ``stage_phases`` in
its report, whose sub-steps with ``other`` sum to the thread-seconds
inside ``_stage_sync``, wherever the staging ran; a restore reports
``verify_wait`` apart from ``verify`` and brackets its read pipeline
with ``restore.plan`` / ``restore.finalize``; notes are always on, spans
only while ``tracing`` is enabled; two operations in flight keep their
notes apart.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench.spans import read_spans
from torchsnapshot_tpu import Snapshot, io_preparer, staging_pool, tracing
from torchsnapshot_tpu.telemetry import consume_profile as _cprof

CHUNK = 64 << 10
# One leaf of four chunks, one of two, and two that are not chunked.
SHAPES = {"big": (4 * CHUNK // 4,), "mid": (2 * CHUNK // 4,), "small": (16,), "tiny": (3,)}
N_CHUNKS = 6
N_CHUNKED_LEAVES = 2
N_LEAVES = len(SHAPES)
STAGE_WORK = ("alloc", "slice", "d2h", "copy", "checksum")


class _Holder:
    def __init__(self, sd):
        self.sd = sd

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        self.sd = sd


@pytest.fixture(autouse=True)
def _chunked_and_untraced(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")
    monkeypatch.setenv("TPUSNAPSHOT_TRANSFER_CHUNK_BYTES", str(CHUNK))
    staging_pool.reset_staging_pool()
    assert not tracing.enabled()
    yield
    if tracing.enabled():
        tracing.disable()
    staging_pool.reset_staging_pool()


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        name: jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for name, shape in SHAPES.items()
    }


def _zeros(state):
    return {"m": _Holder({k: jnp.zeros_like(v) for k, v in state.items()})}


def _rank0(path, fname):
    with open(os.path.join(path, fname)) as f:
        report = json.load(f)
    return next(s for s in report["ranks"] if s)


def _spans(trace_path):
    """``name -> [(begin_us, end_us)]`` of a flushed trace."""
    return {
        name: [(b * 1e6, e * 1e6) for b, e in intervals]
        for name, intervals in read_spans(trace_path).items()
    }


def _assert_block_sums(block, wall_key, beside=()):
    substeps = block["substeps"]
    inside = sum(
        e["seconds"] for name, e in substeps.items() if name not in beside
    )
    # Each sub-step is rounded to a microsecond on its way to the report.
    assert inside == pytest.approx(block[wall_key], abs=1e-5 * len(substeps))
    assert substeps["other"]["seconds"] >= 0
    assert block["accounted_s"] <= block[wall_key] + 1e-5 * len(substeps)


# ------------------------------------------------------------- the take


@pytest.mark.parametrize("stage", ["host", "auto"])
def test_async_take_leaves_stage_phases_that_sum_to_the_staging_wall(
    tmp_path, stage
):
    state = _state()
    path = str(tmp_path / "snap")
    Snapshot.async_take(path, {"m": _Holder(state)}, stage=stage).wait()
    summary = _rank0(path, ".report.json")
    assert summary["capture_route"] == (
        "host_staging" if stage == "host" else "device_clones"
    )
    block = summary["stage_phases"]
    substeps = block["substeps"]
    _assert_block_sums(block, "stage_s", beside=("clone",))
    state_bytes = sum(v.nbytes for v in state.values())
    assert substeps["slice"]["count"] == N_CHUNKS
    assert substeps["d2h"]["count"] == N_CHUNKS + N_LEAVES - N_CHUNKED_LEAVES
    assert substeps["d2h"]["bytes"] == state_bytes
    assert substeps["alloc"]["count"] == N_CHUNKED_LEAVES
    assert substeps["fetch_wait"]["count"] == N_CHUNKED_LEAVES
    assert substeps["checksum"]["count"] == N_LEAVES
    assert substeps["checksum"]["bytes"] == state_bytes
    # The clone attempt lies beside the wall, and only where one was made.
    assert ("clone" in substeps) == (stage == "auto")
    # What was staged is what was saved.
    target = _zeros(state)
    Snapshot(path).restore(target)
    for name, value in state.items():
        np.testing.assert_array_equal(
            np.asarray(target["m"].sd[name]), np.asarray(value)
        )


def test_sync_take_reports_stage_phases_too(tmp_path):
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder(_state())})
    block = _rank0(path, ".report.json")["stage_phases"]
    _assert_block_sums(block, "stage_s")
    assert block["substeps"]["checksum"]["count"] == N_LEAVES


def test_traced_fallback_capture_has_one_span_a_chunk_inside_the_host_stage(
    tmp_path, monkeypatch
):
    """The route of a state too full to clone: the clone attempt fails,
    then everything is staged inside the call."""
    monkeypatch.setattr(io_preparer, "device_clone", lambda arrays: None)
    trace = str(tmp_path / "trace.json")
    path = str(tmp_path / "snap")
    tracing.enable(trace)
    pending = Snapshot.async_take(path, {"m": _Holder(_state())})
    returned_at = (time.monotonic() - tracing._t0) * 1e6
    pending.wait()
    tracing.disable()
    assert _rank0(path, ".report.json")["capture_route"] == "host_staging"
    spans = _spans(trace)
    assert len(spans["capture.clone"]) == 1
    assert len(spans["capture_host_stage"]) == 1
    assert len(spans["stage.slice"]) == N_CHUNKS
    assert len(spans["stage.d2h"]) == N_CHUNKS + N_LEAVES - N_CHUNKED_LEAVES
    assert len(spans["stage.copy"]) == N_CHUNKS + N_LEAVES
    assert len(spans["stage.alloc"]) == N_CHUNKED_LEAVES
    assert len(spans["stage.checksum"]) == N_LEAVES
    (host_begin, host_end), = spans["capture_host_stage"]
    (clone_begin, clone_end), = spans["capture.clone"]
    assert clone_end <= host_begin and host_end <= returned_at
    for name in STAGE_WORK:
        for begin, end in spans[f"stage.{name}"]:
            assert host_begin <= begin <= end <= host_end, name


def test_traced_clone_capture_stages_in_the_drain(tmp_path):
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"m": _Holder(_state())})
    returned_at = (time.monotonic() - tracing._t0) * 1e6
    pending.wait()
    tracing.disable()
    spans = _spans(trace)
    assert "capture_host_stage" not in spans
    (_, clone_end), = spans["capture.clone"]
    assert clone_end <= returned_at
    assert len(spans["stage.slice"]) == N_CHUNKS
    for name in STAGE_WORK:
        assert all(begin >= clone_end for begin, _ in spans[f"stage.{name}"])


def test_with_tracing_disabled_no_event_is_appended(tmp_path):
    state = _state()
    path = str(tmp_path / "snap")
    Snapshot.async_take(path, {"m": _Holder(state)}, stage="host").wait()
    Snapshot(path).restore(_zeros(state))
    assert tracing._events is None
    # The notes were taken all the same.
    assert _rank0(path, ".report.json")["stage_phases"]["substeps"]["d2h"]
    assert _rank0(path, ".report.restore.json")["consume_profile"]["substeps"]
    # And nothing waits in a buffer for the next enable.
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    tracing.disable()
    with open(trace) as f:
        assert json.load(f)["traceEvents"] == []


def test_two_takes_in_flight_keep_their_notes_apart(tmp_path):
    """The second take is called while the first one's drain still
    stages from its clones."""
    small = {"w": jnp.ones((2 * CHUNK // 4,), jnp.float32)}
    large = {"w": jnp.ones((8 * CHUNK // 4,), jnp.float32)}
    first = Snapshot.async_take(str(tmp_path / "a"), {"m": _Holder(large)})
    second = Snapshot.async_take(str(tmp_path / "b"), {"m": _Holder(small)})
    first.wait()
    second.wait()
    for name, state in (("a", large), ("b", small)):
        substeps = _rank0(str(tmp_path / name), ".report.json")["stage_phases"][
            "substeps"
        ]
        assert substeps["d2h"]["bytes"] == state["w"].nbytes
        assert substeps["slice"]["count"] == state["w"].nbytes // CHUNK


# ---------------------------------------------------------- the restore


@pytest.fixture
def streamed(tmp_path, monkeypatch):
    """A snapshot whose one large leaf restores as eight streamed parts."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(CHUNK))
    state = {
        "w": jnp.asarray(
            np.random.default_rng(3).standard_normal(8 * CHUNK // 4), jnp.float32
        ),
        "b": jnp.ones((16,), jnp.float32),
    }
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder(state)})
    return path, state


def test_streamed_restore_reports_verify_wait_apart_from_verify(streamed):
    path, state = streamed
    target = _zeros(state)
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(state["w"])
    )
    profile = _rank0(path, ".report.restore.json")["consume_profile"]
    substeps = profile["substeps"]
    parts = state["w"].nbytes // CHUNK
    assert substeps["verify_wait"]["count"] == parts
    assert substeps["verify_wait"]["bytes"] == state["w"].nbytes
    # The fold alone: every byte of both leaves once, the wait elsewhere.
    assert substeps["verify"]["bytes"] == sum(v.nbytes for v in state.values())
    for name in ("view", "h2d_submit"):
        assert substeps[name]["count"] == parts
    assert substeps["executor_wait"]["count"] == substeps["loop_wait"]["count"]
    _assert_block_sums(
        profile, "consume_s", beside=_cprof.OVERLAP_SUBSTEPS
    )


def test_restore_plan_ends_before_the_first_read_and_finalize_follows_the_last_consume(
    streamed, tmp_path
):
    path, state = streamed
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    Snapshot(path).restore(_zeros(state))
    tracing.disable()
    spans = _spans(trace)
    (restore_begin, restore_end), = spans["Snapshot.restore"]
    (plan_begin, plan_end), = spans["restore.plan"]
    # The span opens where ``restore`` was entered, before its own root span.
    assert plan_begin <= restore_begin
    assert plan_end <= min(begin for begin, _ in spans["read"])
    last_consume = max(end for _, end in spans["consume"])
    finalize = sorted(spans["restore.finalize"])
    assert finalize[0][0] >= last_consume
    assert finalize[-1][1] <= restore_end
    # A read's open and its read lie inside the scheduler's read span.
    reads = spans["read"]
    for name in ("read.open", "read.io"):
        inside = [
            (b, e)
            for b, e in spans[name]
            if any(rb <= b and e <= re for rb, re in reads)
        ]
        assert len(inside) == len(reads), name
    phases = _rank0(path, ".report.restore.json")["phases"]
    assert phases["plan_s"] > 0 and phases["finalize_s"] > 0


def test_spans_of_the_reads_carry_the_restores_trace_id(streamed, tmp_path):
    path, state = streamed
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    Snapshot(path).restore(_zeros(state))
    tracing.disable()
    with open(trace) as f:
        events = json.load(f)["traceEvents"]
    (root,) = [
        e for e in events if e["name"] == "Snapshot.restore" and e["ph"] == "b"
    ]
    trace_id = root["args"]["trace"]
    for name in ("read.io", "consume.verify_wait", "consume.loop_wait", "restore.plan"):
        begun = [e for e in events if e["name"] == name and e["ph"] == "b"]
        assert begun and all(e["args"]["trace"] == trace_id for e in begun), name


def test_two_restores_in_flight_keep_their_notes_apart(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(CHUNK))
    states = {
        "a": {"w": jnp.ones((4 * CHUNK // 4,), jnp.float32)},
        "b": {"w": jnp.ones((16 * CHUNK // 4,), jnp.float32)},
    }
    for name, state in states.items():
        Snapshot.take(str(tmp_path / name), {"m": _Holder(state)})
    gate = threading.Barrier(2)
    errors = []

    def restore(name):
        try:
            gate.wait(timeout=30)
            Snapshot(str(tmp_path / name)).restore(_zeros(states[name]))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=restore, args=(n,)) for n in states]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    for name, state in states.items():
        substeps = _rank0(str(tmp_path / name), ".report.restore.json")[
            "consume_profile"
        ]["substeps"]
        parts = state["w"].nbytes // CHUNK
        assert substeps["verify"]["bytes"] == state["w"].nbytes
        assert substeps["verify_wait"]["count"] == parts
        assert substeps["h2d_overlap"]["bytes"] == state["w"].nbytes


# ------------------------------------------------------ the accumulator


def test_a_profile_is_handed_only_to_its_own_kind():
    assert _cprof.current() is None and _cprof.current("stage") is None
    with _cprof.scope("stage") as profile:
        assert _cprof.current("stage") is profile
        # A consumer built inside a take notes into no restore.
        assert _cprof.current() is None
    assert _cprof.current("stage") is None


def test_a_stage_block_sums_to_its_own_wall():
    profile = _cprof.PhaseProfile("stage")
    assert profile.block() is None
    with _cprof.wall(profile):
        with _cprof.substep(profile, "d2h", 10):
            time.sleep(0.01)
        time.sleep(0.01)
    profile.note("clone", 5.0)
    block = profile.block()
    assert block["substeps"]["d2h"]["bytes"] == 10
    assert block["stage_s"] >= 0.02
    assert block["substeps"]["other"]["seconds"] >= 0.009
    _assert_block_sums(block, "stage_s", beside=("clone",))


def test_interval_records_a_span_between_two_clock_readings(tmp_path):
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    begin = time.monotonic()
    with tracing.trace_scope("restore") as trace_id:
        tracing.interval("restore.plan", begin, begin + 0.25, bytes=3)
    tracing.disable()
    with open(trace) as f:
        first, second = json.load(f)["traceEvents"]
    assert (first["ph"], second["ph"]) == ("b", "e")
    assert first["id"] == second["id"] and first["name"] == "restore.plan"
    assert second["ts"] - first["ts"] == pytest.approx(0.25e6)
    assert first["args"] == {"bytes": 3, "trace": trace_id}
    # Disabled: nothing is recorded, nothing raises.
    tracing.interval("restore.plan", begin, begin + 1.0)
