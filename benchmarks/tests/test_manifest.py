"""BENCHMARK.json against the contract's limits and the harness's files."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"][-1].startswith("benchmarks/")
    cells = len(manifest["workloads"])
    # 2 + 14 runs a cell at the full 24 cells must fit the check
    runs = 2 + 14 * 24
    assert (runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert 1 <= cells <= 24


def test_names_and_units(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_configs_and_cells(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    four = 0
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        four += w["chips"] == 4
        cell = json.load(open(os.path.join(BENCH, "workloads",
                                           w["name"] + ".json")))
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        # every number the reference compares is limited in every cell
        import reference
        assert set(cell["limits"]) == set(reference.NUMBERS)
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert used == set(configs)
    assert four <= max(1, len(manifest["workloads"]) // 4)
    files = set()
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["reduced"] == c["reduced"] and doc["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))


def test_every_moves_is_reported_where_the_metric_is(manifest):
    """A per-layer metric's ``moves`` is an end-to-end metric that each of
    its cells reports, and every cell has set-up, another end-to-end metric
    and a per-layer metric."""
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        where = set(m.get("workloads", e2e[m["moves"]]))
        assert where <= set(cells)
        assert where <= e2e[m["moves"]], m
    for c in cells:
        assert sum(c in v for k, v in e2e.items() if k != "setup_s") >= 1
        assert any(c in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_layer_metric_files_match_the_manifest(manifest):
    """Each per-layer metric is a file of its own naming a reader that
    exists, and says what the manifest says."""
    import readers

    by_name = {m["name"]: m for m in manifest["per_layer"]}
    seen = set()
    for fn in os.listdir(os.path.join(BENCH, "layer_metrics")):
        doc = json.load(open(os.path.join(BENCH, "layer_metrics", fn)))
        assert fn == doc["name"] + ".json"
        assert doc["reader"] in readers.READERS
        entry = by_name[doc["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert doc[key] == entry[key], (doc["name"], key)
        assert doc.get("workloads") == entry.get("workloads")
        seen.add(doc["name"])
    assert seen == set(by_name)
