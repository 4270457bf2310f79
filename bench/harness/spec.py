"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``bench/``:

    bench/configs/<config>.json     sizes, bit widths, build recipe
    bench/models/<family>.py        builds it and holds its reference
    bench/traffic/<traffic>.json    parameters of the load generator
    bench/metrics/<metric>.py       ``read(ctx) -> float | None``

so a later cell adds files and a ``workloads`` entry, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    family: object  # the config's model module
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load(workload: str, root: str = ROOT) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _read(os.path.join(root, cfg_entry["file"]))
    traffic = _read(os.path.join(root, "bench", "traffic",
                                 f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    family = importlib.import_module(f"bench.models.{config['family']}")
    return Cell(workload, int(w["chips"]), config, traffic, family, e2e,
                per_layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
