"""BENCHMARK.json against the benchmark contract, and discovery by name:
configurations, traffic mixes, generators, metric readers and peaks are
found from their names alone, including ones added as new files in a
throwaway root."""
import json
import os
import re

import numpy as np
import pytest

from conftest import REPO
from harness import deploy
from harness.spec import Spec, SpecError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def test_top_level_keys_and_command(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["command"] == ["python3", "bench/run.py"]
    assert doc["paths"] == ["bench"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entry_keys(doc):
    names = []
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        names.append(m["name"])
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert all(NAME.match(n) for n in names), names


def test_every_cell_reports_what_the_contract_asks(doc, spec):
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    pairs = {(w["config"], w["traffic"]) for w in doc["workloads"]}
    assert len(pairs) == len(doc["workloads"])
    for w in doc["workloads"]:
        cell = spec.cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (m["name"], w["name"])
    for m in doc["per_layer"]:
        assert set(m["workloads"]) <= {w["name"] for w in doc["workloads"]}
    assert {w["config"] for w in doc["workloads"]} == \
        {c["name"] for c in doc["configs"]}


def test_every_name_finds_its_file(doc, spec):
    for c in doc["configs"]:
        cfg = spec.config(c["name"])
        assert set(cfg["checks"]) == {"recall_at_10", "dist_gap", "missing"}
        assert cfg["reduced"] == c["reduced"]
    for w in doc["workloads"]:
        mix = spec.traffic(w["traffic"])
        assert callable(spec.generator(mix).make_schedule)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    assert spec.peaks("TPU v5 lite")["hbm_byte_per_s"] == 819e9


def test_settings_the_harness_would_not_apply_are_refused(spec, doc):
    for c in doc["configs"]:
        deploy.check_config(spec.config(c["name"]))
    cfg = spec.config(doc["configs"][0]["name"])
    for group, key in (("serve", "result_cache_mb"), ("search", "nprobe"),
                       ("index", "ef_construction"), ("corpus", "skew")):
        bad = {**cfg, group: {**cfg[group], key: 1}}
        with pytest.raises(SpecError, match=key):
            deploy.check_config(bad)
    with pytest.raises(SpecError, match="kind"):
        deploy.check_config({**cfg, "index": {**cfg["index"], "kind": "ivf"}})


def test_unknown_names_are_errors(spec):
    with pytest.raises(SpecError):
        spec.cell("no_such_cell")
    with pytest.raises(SpecError):
        spec.config("no_such_config")
    with pytest.raises(SpecError):
        spec.traffic("no_such_mix")
    with pytest.raises(SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(SpecError):
        spec.peaks("TPU v99")
    with pytest.raises(SpecError):
        spec.predicate({"predicate": "no_such_predicate"})


def test_a_new_cell_is_new_files_only(tiny_root):
    """A configuration, a mix and a per-layer metric added as new files and
    new entries are found with no edit to any existing file."""
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "configs", "sift1m_l5x3_pq4.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "throwaway_cfg"
    with open(os.path.join(bench, "configs", "throwaway_cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "throwaway.mix.json"), "w") as f:
        json.dump({"generator": "schedule", "loop": "closed", "clients": 3,
                   "predicate": "throwaway_pred", "query_pool": 16}, f)
    with open(os.path.join(bench, "predicates", "throwaway_pred.py"),
              "w") as f:
        f.write("def meets(rows, q):\n    return rows[..., 0] == q[..., 0]\n"
                "def program(q):\n    return []\n")
    with open(os.path.join(bench, "metrics", "throwaway_metric.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "throwaway_cfg", "source": "x",
                           "file": "bench/configs/throwaway_cfg.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "throwaway.cell", "config":
                             "throwaway_cfg", "traffic": "throwaway.mix",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "throwaway_metric", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "serve", "moves": "setup_s",
                             "workloads": ["throwaway.cell"]})
    with open(path, "w") as f:
        json.dump(doc, f)

    cell = Spec(tiny_root).cell("throwaway.cell")
    assert cell.config["name"] == "throwaway_cfg"
    assert cell.traffic["clients"] == 3
    pred = Spec(tiny_root).predicate(cell.traffic)
    assert pred.program([1, 2]) == [] and pred.meets(
        np.array([[1, 0], [2, 1]]), np.array([1, 1])).tolist() == [True, False]
    assert [m["name"] for m in cell.per_layer] == ["throwaway_metric"]
    assert Spec(tiny_root).metric_reader("throwaway_metric").read(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"recall_at_10", "setup_s"}
