"""What ``BENCHMARK.json`` says, resolved to the files under ``chipbench/``.

The harness holds no cell's name or number: a cell names a configuration and
a traffic mix, each of which is one data file found by that name; a
per-layer metric is one file under ``layer_metrics/`` found by its name.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # metric entries of BENCHMARK.json reported here
    per_layer: list    # layer-metric files (dicts) reported here


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def layer_metric(name: str) -> dict:
    m = _load(os.path.join(HERE, "layer_metrics", f"{name}.json"))
    if m.get("name") != name:
        raise SpecError(f"layer_metrics/{name}.json names itself "
                        f"{m.get('name')!r}")
    return m


def load_config(path: str) -> dict:
    return _load(os.path.join(ROOT, path))


def load_traffic(name: str) -> dict:
    return _load(os.path.join(HERE, "traffic", f"{name}.json"))


def load_cell(workload: str, benchmark_path: str = BENCHMARK) -> Cell:
    bench = _load(benchmark_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"BENCHMARK.json has no workload {workload!r}; it has "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_config(configs[w["config"]]["file"]),
        traffic=load_traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[layer_metric(m["name"]) for m in bench["per_layer"]
                   if _applies(m, workload)],
    )


def rehearsal_cell(config_file: str, traffic: str,
                   benchmark_path: str = BENCHMARK) -> Cell:
    """A cell that is in no ``workloads`` entry: a configuration file and a
    traffic mix named outright (``run.py --config-file --traffic``), with
    every metric the benchmark has.  For rehearsals off the chip."""
    bench = _load(benchmark_path)
    config = load_config(config_file)
    return Cell(
        name=f"{config['name']}.{traffic}",
        chips=int(config["chips"]),
        config=config,
        traffic=load_traffic(traffic),
        end_to_end=list(bench["end_to_end"]),
        per_layer=[layer_metric(m["name"]) for m in bench["per_layer"]],
    )


def generator(kind: str):
    """``generators/<kind>.py``: ``schedule(params, seed, seconds, n_names,
    n_entries) -> Schedule``."""
    return importlib.import_module(f"chipbench.generators.{kind}")


def reference(name: str):
    """``references/<name>.py``: ``check_run(ops_by_name, tables_of,
    readback, initial) -> [problem strings]`` (``references/__init__.py``);
    a configuration file names its own under ``"reference"``."""
    return importlib.import_module(f"chipbench.references.{name}")


def reader(name: str):
    """``readers/<name>.py``: ``read(run, **args) -> float | None``."""
    return importlib.import_module(f"chipbench.readers.{name}")
