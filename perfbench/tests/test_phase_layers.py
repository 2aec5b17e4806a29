"""The readers of the phase metrics (PR 30): each gives a number on a
traced toy run of its loop kind, under both toy jobs, and nothing where
the program recorded no such span; the metrics a cell printed before are
all still printed.

``test_job_nemotron_h.py`` holds the per-layer lists of
``gpt3-6.7b.save_in_loop`` and of the Nemotron cell to each other, entry
for entry, so ``BENCHMARK.json`` lists the staging metrics for the
four-chip cell alone; their readers serve any save cell, and the toys
here list them through a copy of the manifest, as a later PR would."""

import copy
import os
import time

import pytest

from perfbench import harness, manifest, phase_spans
from perfbench.tests.toy import manifest_of_a_later_pr, toy_manifest

STAGE_METRICS = [
    "capture_clone_ms",
    "stage_slice_thread_s",
    "stage_d2h_thread_s",
    "stage_copy_thread_s",
    "stage_checksum_thread_s",
    "stage_threads_busy",
]
RESTORE_METRICS = [
    "restore_plan_ms",
    "restore_finalize_ms",
    "restore_reads_in_flight",
    "restore_read_io_thread_s",
    "restore_verify_thread_s",
    "restore_verify_wait_thread_s",
    "restore_h2d_busy_share",
]
# What a traced toy run of each loop kind printed at PR 28 (no device
# plane on the CPU backend, so no ``device_idle_pct``).
PRINTED_BEFORE = {
    "save_in_loop": [
        "save_durable_s",
        "step_p95_ms",
        "step_free_ms",
        "step_in_drain_ms",
        "loop_ms_lost_per_save",
        "save_call_blocked_ms",
        "capture_fallbacks",
        "drain_d2h_share",
        "drain_storage_share",
        "write_busy_share",
        "stored_bytes_ratio",
    ],
    "kill_resume": [
        "restore_h2d_share",
        "read_busy_share",
        "first_step_after_restore_ms",
    ],
}
NEW = {"save_in_loop": STAGE_METRICS, "kill_resume": RESTORE_METRICS}
TRACED_CELLS = [
    "toy.save_in_loop",
    "toy-mixed.save_in_loop",
    "toy.kill_resume",
    "toy-mixed.kill_resume",
]


def manifest_with_staging_metrics_in_every_save_cell() -> dict:
    m = copy.deepcopy(toy_manifest())
    save_cells = [w["name"] for w in m["workloads"] if w["name"].endswith(".save_in_loop")]
    for metric in m["per_layer"]:
        if metric["name"] in STAGE_METRICS:
            metric["workloads"] = save_cells
    return m


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    lines = {}

    def line_of(name):
        if name not in lines:
            tmp = tmp_path_factory.mktemp(name.replace(".", "-"))
            cell = manifest.resolve_cell(
                manifest_with_staging_metrics_in_every_save_cell(), name
            )
            lines[name] = harness.run_cell(
                cell,
                seed=5,
                seconds=1.0,
                trace=True,
                devices=jax.devices()[: cell.chips],
                started_at=time.monotonic(),
                out_dir=str(tmp / "out"),
                roots_parent=str(tmp / "roots"),
            )
        return lines[name]

    return line_of


@pytest.mark.parametrize("name", TRACED_CELLS)
def test_every_new_reader_gives_a_number_and_no_old_name_is_lost(name, traced):
    line = traced(name)
    assert line["correct"] is True, line
    kind = name.split(".")[1]
    for metric in PRINTED_BEFORE[kind] + NEW[kind]:
        assert metric in line["metrics"], (metric, sorted(line["metrics"]))
        assert line["metrics"][metric]["value"] >= 0
    values = {m: line["metrics"][m]["value"] for m in NEW[kind]}
    if kind == "save_in_loop":
        # The toy's clones fit, so its staging runs in the drain; every
        # leaf is fetched and checksummed there, by at least one thread.
        assert values["capture_clone_ms"] > 0
        assert values["stage_d2h_thread_s"] > 0
        assert values["stage_checksum_thread_s"] > 0
        assert values["stage_threads_busy"] >= 1.0
    else:
        assert values["restore_plan_ms"] > 0 and values["restore_finalize_ms"] > 0
        assert values["restore_read_io_thread_s"] > 0
        assert values["restore_reads_in_flight"] >= 1.0
        assert values["restore_verify_thread_s"] > 0
        assert 0 <= values["restore_h2d_busy_share"] <= 100


def _reader(name):
    path = os.path.join(manifest.BENCH_DIR, "layers", name + ".py")
    return manifest.load_module(path).read


@pytest.mark.parametrize("name", STAGE_METRICS + RESTORE_METRICS)
def test_a_reader_finds_nothing_where_the_program_recorded_no_such_span(name):
    read = _reader(name)
    saves = [{"step": 3, "blocked_s": 0.1, "durable_s": 1.0}]
    cycles = [{"restore_s": 1.0, "first_step_s": 0.1}]
    # An untraced run, a traced run of a program without the spans (the
    # parent commit), and a run of the other loop kind.
    old_spans = {
        "write": [(0.0, 1.0)],
        "read": [(0.0, 1.0)],
        "consume.verify": [(0.2, 0.4)],
        "capture_host_stage": [(0.0, 0.5)],
    }
    for obs in (
        {"saves": saves, "cycles": cycles, "state_bytes": 8},
        {"saves": saves, "cycles": cycles, "state_bytes": 8, "spans": {}},
        {"saves": saves, "cycles": cycles, "state_bytes": 8, "spans": old_spans},
        {"state_bytes": 8, "spans": {"stage.d2h": [(0, 1)], "restore.plan": [(0, 1)]}},
    ):
        assert read(obs) is None, obs


def test_thread_seconds_count_every_thread_and_the_union_counts_the_clock():
    spans = {
        "stage.d2h": [(0.0, 1.0), (0.5, 1.5)],
        "stage.copy": [(1.0, 2.0)],
        "stage.fetch_wait": [(0.0, 2.0)],
    }
    assert phase_spans.thread_seconds(spans, "stage.d2h") == 2.0
    assert phase_spans.thread_seconds(spans, "stage.d2h", "stage.copy") == 3.0
    assert phase_spans.thread_seconds(spans, "stage.slice") == 0.0
    assert phase_spans.threads_at_once(spans, "stage.d2h", "stage.copy") == 1.5
    assert phase_spans.threads_at_once(spans, "stage.slice") is None
    obs = {"saves": [{}, {}], "spans": spans}
    assert phase_spans.stage_thread_seconds_per_save(obs, "d2h") == 1.0
    # A program that staged and dispatched no slice spent 0 s there.
    assert phase_spans.stage_thread_seconds_per_save(obs, "slice") == 0.0
    # A waiting staging thread is not a working one.
    read = _reader("stage_threads_busy")
    assert read(obs) == 1.5


def check_the_entries_name_their_cells(m):
    """Each of the thirteen entries found by name, once, wherever it
    stands in ``per_layer``."""
    names = [x["name"] for x in m["per_layer"]]
    for name in STAGE_METRICS + RESTORE_METRICS:
        assert names.count(name) == 1, name
    for x in [x for x in m["per_layer"] if x["name"] in STAGE_METRICS + RESTORE_METRICS]:
        assert x["source"] == "program_span"
        if x["name"] in STAGE_METRICS:
            assert x["workloads"] == ["gpt3-6.7b-tp4.save_in_loop"]
            assert x["moves"] == "loop_steps_per_s"
        else:
            assert x["workloads"] == ["gpt3-6.7b.kill_resume"]
            assert x["moves"] == "resume_s"


def test_the_new_entries_stand_at_the_end_and_name_their_cells():
    check_the_entries_name_their_cells(manifest.load_manifest())


# The check above, run again on a copy to which a later change's
# configuration, cell and per-layer entry are appended.
def test_every_manifest_check_holds_once_a_later_pr_has_appended():
    check_the_entries_name_their_cells(manifest_of_a_later_pr())
