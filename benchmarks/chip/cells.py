"""The benchmark's cells, found by name.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration, whose file ``configs/<config>.json`` BENCHMARK.json gives,
and a traffic mix, read from ``traffic/<traffic>.json``. Its limits on the
numbers that decide ``correct`` are in ``limits/<cell>.json``. A per-layer
metric is read by ``metrics/<metric>.py``. A configuration's model family
is ``reference/<family>.py``, by the file's ``"reference"``. Adding any
of these takes new files and BENCHMARK.json entries, and no edit here.
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return _load(os.path.join(root, "BENCHMARK.json"))


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name, bench=None, here=HERE):
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    root = os.path.dirname(os.path.dirname(here))
    traffic = _load(os.path.join(here, "traffic", w["traffic"] + ".json"))
    if traffic["chips"] != w["chips"]:
        raise ValueError(f"{name}: traffic {w['traffic']} is for "
                         f"{traffic['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    limits_path = os.path.join(here, "limits", name + ".json")
    return {
        "name": name,
        "chips": w["chips"],
        "config": _load(os.path.join(root, conf["file"])),
        "reduced": conf["reduced"],
        "traffic": traffic,
        "limits": _load(limits_path) if os.path.exists(limits_path) else None,
        "end_to_end": [m for m in bench["end_to_end"] if _reports(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _reports(m, name)],
    }
