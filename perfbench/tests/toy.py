"""The toy cells of the tests, added to a copy of the manifest the way a
later PR adds a cell: new files (under ``tests/data``) plus new entries,
and no edit to a file that is there."""

import copy
import os
import time

from perfbench import harness, manifest

DATA = "perfbench/tests/data"
# name -> (config, traffic, chips, the real cell whose metrics it reports)
TOY_CELLS = {
    "toy.save_in_loop": ("toy", "toy_save_in_loop", 1, "gpt3-6.7b.save_in_loop"),
    "toy.kill_resume": ("toy", "toy_kill_resume", 1, "gpt3-6.7b.kill_resume"),
    "toy-tp4.save_in_loop": (
        "toy-tp4",
        "toy_save_in_loop_tp4",
        4,
        "gpt3-6.7b.save_in_loop",
    ),
    # The second job (``data/jobs/toy_mixed.py``, named by its configurations).
    "toy-mixed.save_in_loop": ("toy-mixed", "toy_save_in_loop", 1, "gpt3-6.7b.save_in_loop"),
    "toy-mixed.kill_resume": ("toy-mixed", "toy_kill_resume", 1, "gpt3-6.7b.kill_resume"),
    "toy-mixed-tp4.save_in_loop": (
        "toy-mixed-tp4",
        "toy_save_in_loop_tp4",
        4,
        "gpt3-6.7b.save_in_loop",
    ),
    # A restore onto another layout: saved on dp1 x tp4, restored on dp2 x tp2.
    "toy-tp4.reshard_resume": (
        "toy-tp4",
        "toy_reshard_resume",
        4,
        "gpt3-6.7b-tp4.reshard_resume",
    ),
}


# What a later ``model_config`` PR brings: a configuration that names a
# job of its own, and its cells. ``test_manifest.py`` holds the accepted
# manifest with these entries added to every check it has.
CELLS_OF_A_LATER_PR = {
    name: TOY_CELLS[name]
    for name in ("toy-mixed.save_in_loop", "toy-mixed.kill_resume")
}


def manifest_with(cells) -> dict:
    """A copy of the accepted manifest with ``cells`` (as ``TOY_CELLS``
    spells them) added: one more directory of ``paths``, entries, no edit."""
    m = copy.deepcopy(manifest.load_manifest())
    m["paths"].append(DATA)
    for config in sorted({config for config, _, _, _ in cells.values()}):
        m["configs"].append(
            {
                "name": config,
                "source": "none: a toy for the tests",
                "file": f"{DATA}/configs/{config}.json",
                "reduced": [],
                "why": "control flow on the CPU backend",
            }
        )
    for name, (config, traffic, chips, like) in cells.items():
        m["workloads"].append(
            {
                "name": name,
                "config": config,
                "traffic": traffic,
                "chips": chips,
                "why": "toy",
            }
        )
        for section in ("end_to_end", "per_layer"):
            for metric in m[section]:
                if like in metric.get("workloads", ()):
                    metric["workloads"].append(name)
    return m


# What a later change appends to each list of ``BENCHMARK.json``: a
# configuration (``data/configs/toy-appended.json``, a copy of a toy
# file), one cell on a job and a loop kind that are there, that cell's
# name in an end-to-end metric's list, and a per-layer entry naming it.
# Every check of the accepted entries has to hold on this copy as on the
# manifest itself: none may ask where an entry stands.
APPENDED_CELL = "toy-appended.kill_resume"


def manifest_of_a_later_pr() -> dict:
    m = copy.deepcopy(manifest.load_manifest())
    m["paths"].append(DATA)
    m["configs"].append(
        {
            "name": "toy-appended",
            "source": "none: a toy for the tests",
            "file": f"{DATA}/configs/toy-appended.json",
            "reduced": [],
            "why": "an entry appended after every accepted one",
        }
    )
    m["workloads"].append(
        {
            "name": APPENDED_CELL,
            "config": "toy-appended",
            "traffic": "toy_kill_resume",
            "chips": 1,
            "why": "toy",
        }
    )
    (resume,) = [x for x in m["end_to_end"] if x["name"] == "resume_s"]
    resume["workloads"].append(APPENDED_CELL)
    m["per_layer"].append(
        {"name": "toy_reader", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "device", "moves": "resume_s",
         "workloads": [APPENDED_CELL]}
    )
    return m


def toy_manifest() -> dict:
    return manifest_with(TOY_CELLS)


def toy_job(name, seed=5):
    """The cell and its job, made as ``harness.Run`` makes it."""
    import jax

    cell = manifest.resolve_cell(toy_manifest(), name)
    job = manifest.load_module(cell.job_path).make_job(
        cell.config, jax.devices()[: cell.chips], seed
    )
    return cell, job


def run_toy(name, seed=5, seconds=1.0, trace=False, out_dir=None, roots_parent=None):
    import jax

    cell = manifest.resolve_cell(toy_manifest(), name)
    return harness.run_cell(
        cell,
        seed=seed,
        seconds=seconds,
        trace=trace,
        devices=jax.devices()[: cell.chips],
        started_at=time.monotonic(),
        out_dir=str(out_dir),
        roots_parent=str(roots_parent),
    )
