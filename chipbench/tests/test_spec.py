"""BENCHMARK.json against the files it names: a new configuration, mix or
per-layer metric is a new file plus an entry, and nothing else."""

import glob
import json
import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(spec.BENCHMARK) as f:
        return json.load(f)


def test_keys_names_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in names
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200


def test_per_layer_entries_are_their_files(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for entry in bench["per_layer"]:
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        on_file = spec.layer_metric(entry["name"])
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert entry[key] == on_file[key], (entry["name"], key)
        assert entry["moves"] in e2e
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert hasattr(spec.reader(on_file["reader"]), "read")
    on_disk = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(spec.HERE, "layer_metrics", "*.json"))}
    assert on_disk == {m["name"] for m in bench["per_layer"]}


def test_every_cell_loads_and_configs_state_what_they_run(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == cell.config["chips"] == w["chips"]
        assert hasattr(spec.generator(cell.traffic["generator"]), "schedule")
        assert [m["name"] for m in cell.end_to_end] == [
            m["name"] for m in bench["end_to_end"]]
        assert [m["name"] for m in cell.per_layer] == [
            m["name"] for m in bench["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])]
        assert cell.per_layer, "every cell reports a per-layer metric"
    for c in bench["configs"]:
        on_file = spec.load_config(c["file"])
        assert on_file["name"] == c["name"] and on_file["source"] == c["source"]
        assert on_file["reduced"] == c["reduced"]
        assert on_file["paxos"]["max_groups"] % 128 == 0
        assert on_file["populate_groups"] < on_file["paxos"]["max_groups"]
        assert len(on_file["guarantees"]) == 3
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")


def test_every_configuration_names_a_reference_that_exists(bench):
    files = glob.glob(os.path.join(spec.HERE, "configs", "*.json"))
    assert len(files) >= 5
    assert {os.path.join(spec.ROOT, c["file"]) for c in bench["configs"]} \
        <= set(files)
    for path in files:
        config = spec.load_config(path)
        assert hasattr(spec.reference(config["reference"]), "check_run"), path
    # a reference is independent of the program
    for path in glob.glob(os.path.join(spec.HERE, "references", "*.py")):
        with open(path) as f:
            assert not re.search(r"^\s*(from|import)\s+gigapaxos", f.read(),
                                 re.M), path


def test_a_traffic_file_names_a_generator_and_may_carry_a_preload():
    files = glob.glob(os.path.join(spec.HERE, "traffic", "*.json"))
    assert len(files) >= 2
    for path in files:
        traffic = spec.load_traffic(os.path.basename(path)[:-5])
        assert hasattr(spec.generator(traffic["generator"]), "schedule")
        assert set(traffic.get("preload", {"key", "value_bytes"})) == {
            "key", "value_bytes"}


def test_the_rehearsal_configuration_is_nothing_but_a_file(bench):
    path = "chipbench/configs/rehearsal-3r-4k.json"
    assert path not in {c["file"] for c in bench["configs"]}
    cell = spec.rehearsal_cell(path, "open1k-put-uniform")
    assert cell.config["paxos"]["max_groups"] == 4096
    here = os.path.join(spec.ROOT, "chipbench")
    code = "".join(open(p).read() for p in glob.glob(
        os.path.join(here, "*.py")) + glob.glob(os.path.join(here, "*", "*.py"))
        if os.sep + "tests" + os.sep not in p)
    for w in bench["workloads"] + bench["configs"]:
        assert w["name"] not in code, f"harness code names {w['name']}"
    assert "rehearsal-3r-4k" not in code
