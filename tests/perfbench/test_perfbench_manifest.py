"""BENCHMARK.json against the contract's form, the files it names, and the
benchmark's copies of the upstream templates."""

import json
import os
import re
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.manifest import Manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
UPSTREAM = os.path.join(ROOT, "kubernetes_tpu", "perf", "config")


def cells_of(metric, reporting=None):
    if "workloads" in metric:
        return set(metric["workloads"])
    if reporting is not None:
        return set(reporting)
    return {w["name"] for w in DOC["workloads"]}


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51 and isinstance(DOC["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for word in DOC["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")
    # the full check with all 24 cells has to fit
    assert (2 + 14 * 24) * (DOC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_name_and_unit_is_well_formed():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for ent in DOC[group]:
            assert NAME.match(ent["name"]), ent["name"]
            names.append((group in ("end_to_end", "per_layer"), ent["name"]))
    assert len(names) == len(set(names))
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])


def test_cells_name_configurations_and_mixes_that_exist():
    m = Manifest()
    configs = {c["name"] for c in DOC["configs"]}
    used = set()
    pairs = set()
    for w in DOC["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
        assert m.traffic(w["traffic"])["kind"]
        assert m.config(w["config"])["name"] == w["config"]
    assert used == configs and len(pairs) == len(DOC["workloads"])
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= max(1, len(DOC["workloads"]) // 2)
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in DOC["paths"])


def test_every_cell_reports_setup_one_more_end_to_end_and_a_layer():
    m = Manifest()
    assert any(e["name"] == "setup_s" and "workloads" not in e for e in DOC["end_to_end"])
    for w in DOC["workloads"]:
        e2e = [x["name"] for x in m.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert m.metrics_for(w["name"], "per_layer")


def test_a_per_layer_metric_moves_one_end_to_end_metric_that_its_cells_report():
    e2e = {m["name"]: cells_of(m) for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert cells_of(m, e2e[m["moves"]]) <= e2e[m["moves"]], m["name"]
    layers = {}
    for m in DOC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())    # letter for letter


def test_every_metric_has_a_reader_of_its_own():
    m = Manifest()
    for group in ("end_to_end", "per_layer"):
        for ent in DOC[group]:
            assert callable(m.reader(group, ent["name"]))
    readers = {f[:-3] for f in os.listdir(os.path.join(ROOT, "perfbench", "metrics"))
               if f.endswith(".py")}
    assert readers >= {ent["name"] for ent in DOC["per_layer"]}


@pytest.mark.parametrize("name", ["node-default.yaml", "pod-default.yaml",
                                  "pod-with-topology-spreading.yaml"])
def test_template_copies_equal_the_ports_templates_today(name):
    with open(os.path.join(ROOT, "perfbench", "configs", "templates", name)) as f:
        mine = yaml.safe_load(f)
    with open(os.path.join(UPSTREAM, name)) as f:
        theirs = yaml.safe_load(f)
    assert mine == theirs


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_test_case_parameters_equal_the_ports_performance_config(config):
    doc = Manifest().config(config)
    with open(os.path.join(UPSTREAM, "performance-config.yaml")) as f:
        cases = {c["name"]: c for c in yaml.safe_load(f)}
    mine, theirs = doc["test_case"], cases[doc["test_case"]["name"]]
    assert mine["workloadTemplate"] == theirs["workloadTemplate"]
    assert mine.get("defaultPodTemplatePath") == theirs.get("defaultPodTemplatePath")
    want = next(w for w in theirs["workloads"] if w["name"] == doc["workload"])
    assert mine["workloads"] == [want]
    assert "performance-config.yaml" in next(
        c["source"] for c in DOC["configs"] if c["name"] == config
    )
    assert set(doc["reduced"]) == set(next(
        c["reduced"] for c in DOC["configs"] if c["name"] == config
    ))
