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
}


def toy_manifest() -> dict:
    m = copy.deepcopy(manifest.load_manifest())
    m["paths"].append(DATA)
    for config in ("toy", "toy-tp4"):
        m["configs"].append(
            {
                "name": config,
                "source": "none: a toy for the tests",
                "file": f"{DATA}/configs/{config}.json",
                "reduced": [],
                "why": "control flow on the CPU backend",
            }
        )
    for name, (config, traffic, chips, like) in TOY_CELLS.items():
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
