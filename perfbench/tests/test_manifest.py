"""``BENCHMARK.json`` against the contract it was written to, and every
name in it resolved to a file."""

import json
import os
import re

import pytest

from perfbench import manifest
from perfbench.tests.toy import TOY_CELLS, toy_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "d_model", "d_ff", "d_head")


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(m):
    assert set(m) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])
    assert len(m["command"]) <= 32 and all(one_line(w) for w in m["command"])
    full = len(m["workloads"]) * 14 + 2
    budget = full * (m["run_seconds"] + 60) + len(m["workloads"]) * 180 + 1200
    assert budget <= 43200
    # and with the full 24 cells that later PRs may bring
    assert (24 * 14 + 2) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(m):
    assert 1 <= len(m["configs"]) <= 24
    names = [c["name"] for c in m["configs"]]
    files = [c["file"] for c in m["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert c["name"] in used
        assert len(c["reduced"]) <= 16
        with open(os.path.join(manifest.CHECKOUT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS)
            assert config["published"][key] != config[key]
        # every width is as published (GPT-3 6.7B, Brown et al. table 2.1)
        assert (config["d_model"], config["n_heads"], config["d_head"], config["d_ff"]) == (
            4096, 32, 128, 16384,
        )
        assert config["max_seq_len"] == 2048


def test_workloads(m):
    assert 1 <= len(m["workloads"]) <= 24
    names = [w["name"] for w in m["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert w["config"] in {c["name"] for c in m["configs"]}


def test_metrics(m):
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    every = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in every]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in m["workloads"]}
    for x in every:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
        assert set(x.get("workloads", ())) <= cells
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    setup = [x for x in m["end_to_end"] if x["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    layers = {x["layer"] for x in m["per_layer"]}
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert one_line(x["layer"]) and x["layer"] in layers


def test_every_cell_reports_enough_and_every_arrow_lands(m):
    by_name = {x["name"]: x for x in m["end_to_end"]}
    for w in m["workloads"]:
        e2e = [x["name"] for x in manifest.metrics_of(m, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(m, "per_layer", w["name"])
    for x in m["per_layer"]:
        moved = by_name[x["moves"]]
        for cell in x.get("workloads", [w["name"] for w in m["workloads"]]):
            assert "workloads" not in moved or cell in moved["workloads"], (
                f"{x['name']} moves {x['moves']}, which {cell} does not report"
            )


def test_every_name_resolves_to_a_file(m):
    for w in m["workloads"]:
        cell = manifest.resolve_cell(m, w["name"])
        assert os.path.isfile(cell.loop_path)
        assert set(cell.reader_paths) == {x["name"] for x in cell.per_layer}
        for path in cell.reader_paths.values():
            assert callable(manifest.load_module(path).read)
        assert callable(manifest.load_module(cell.loop_path).run)
        assert cell.traffic["loop"] and cell.config["n_layers"] >= 1


def test_files_under_paths_are_named_from_allowed_characters(m):
    for path in m["paths"]:
        for directory, dirs, files in os.walk(os.path.join(manifest.CHECKOUT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(directory, name), manifest.CHECKOUT)
                assert PATH.match(rel), rel


def test_a_cell_is_added_as_files_and_entries_alone():
    """The toy cells come in through a copy of the manifest and files of
    their own, with no edit to a file the benchmark has."""
    m = toy_manifest()
    for name, (config, traffic, chips, _) in TOY_CELLS.items():
        cell = manifest.resolve_cell(m, name)
        assert cell.chips == chips and cell.config_name == config
        assert cell.traffic_name == traffic
        assert "tests/data" in os.path.relpath(
            os.path.join(manifest.CHECKOUT, "perfbench/tests/data/traffic", traffic + ".json"),
            manifest.CHECKOUT,
        )
        assert cell.end_to_end and cell.per_layer
    # a per-layer metric too: a reader of its own, found by name
    m["per_layer"].append(
        {"name": "toy_reader", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "device", "moves": "resume_s",
         "workloads": ["toy.kill_resume"]}
    )
    cell = manifest.resolve_cell(m, "toy.kill_resume")
    reader = manifest.load_module(cell.reader_paths["toy_reader"])
    assert reader.read({"cycles": [1, 2, 3]}) == 3 and reader.read({}) is None
