"""``BENCHMARK.json`` names only what ``bench/`` holds: each cell's
configuration, traffic, generator, query and driver, and each per-layer
metric's reader, found by name."""
import json
import os
import re

import pytest

import graph as G
import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_bench()


def test_cells_resolve(bench):
    for w in bench["workloads"]:
        _w, cfg, traffic = harness.resolve(bench, w["name"])
        assert os.path.isfile(os.path.join(G.HERE, "gen",
                                           cfg["generator"] + ".py"))
        driver = G.load_module("drivers", traffic["driver"])
        assert callable(driver.run) and not harness._traffic(traffic, driver)
        for kind in traffic["mix"]:
            query = G.load_module("queries", kind)
            assert callable(query.reference) and callable(query.control)
        assert cfg["trials"] >= 1
        assert harness.metrics_of(bench["end_to_end"], w["name"])
        assert harness.metrics_of(bench["per_layer"], w["name"])


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(G.load_module("metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_names_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    assert {e["name"] for e in bench["end_to_end"]} >= {"setup_s"}
