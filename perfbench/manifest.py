"""Finds what a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a configuration, a traffic mix or a metric by name:
a later PR adds ``configs/<x>.json``, ``traffic/<y>.json``,
``layers/<metric>.py`` (or a loop kind, ``loops/<kind>.py``) under one of
the manifest's ``paths`` and the matching entries in ``BENCHMARK.json``,
and edits no file that is there.
"""

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    loop_path: str
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    reader_paths: Dict[str, str]


def load_manifest(path: str = None) -> Dict[str, Any]:
    with open(path or os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def _find(manifest: Dict[str, Any], root: str, relative: str) -> str:
    """The first ``<path>/<relative>`` that exists under the manifest's
    ``paths``."""
    tried = []
    for path in manifest["paths"]:
        candidate = os.path.join(root, path, relative)
        if os.path.isfile(candidate):
            return candidate
        tried.append(candidate)
    raise FileNotFoundError(f"none of {tried} exists")


def metrics_of(
    manifest: Dict[str, Any], section: str, cell_name: str
) -> List[Dict[str, Any]]:
    """The metrics of ``section`` that the cell reports: those that list
    it under ``workloads``, and those that list nothing."""
    return [
        m
        for m in manifest[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def resolve_cell(
    manifest: Dict[str, Any], cell_name: str, root: str = CHECKOUT
) -> Cell:
    entries = [w for w in manifest["workloads"] if w["name"] == cell_name]
    if not entries:
        known = [w["name"] for w in manifest["workloads"]]
        raise KeyError(f"no workload {cell_name!r}; the manifest has {known}")
    (entry,) = entries
    (config_entry,) = [
        c for c in manifest["configs"] if c["name"] == entry["config"]
    ]
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    with open(_find(manifest, root, f"traffic/{entry['traffic']}.json")) as f:
        traffic = json.load(f)
    per_layer = metrics_of(manifest, "per_layer", cell_name)
    return Cell(
        name=cell_name,
        chips=entry["chips"],
        config_name=entry["config"],
        config=config,
        traffic_name=entry["traffic"],
        traffic=traffic,
        loop_path=_find(manifest, root, f"loops/{traffic['loop']}.py"),
        end_to_end=metrics_of(manifest, "end_to_end", cell_name),
        per_layer=per_layer,
        reader_paths={
            m["name"]: _find(manifest, root, f"layers/{m['name']}.py")
            for m in per_layer
        },
    )


def load_module(path: str):
    """Import one file by path (metric names hold dots, so readers are
    not importable by name)."""
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, CHECKOUT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
