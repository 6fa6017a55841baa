"""One cell of BENCHMARK.json with the files it names: the configuration,
the traffic mix (perfbench/traffic/<traffic>.json) and a reader for each
metric the cell reports (perfbench/end_to_end/<name>.py,
perfbench/layer_metrics/<name>.py).

Which metrics a cell reports is BENCHMARK.json's to say: a metric with a
`workloads` list in the cells it names, one without in every cell that
reports the metric it `moves`.  A new cell is its files and entries there,
its name in the lists of the metrics it reports; no code here changes."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix_path: str
    end_to_end: list[dict]       # metric entries this cell reports
    per_layer: list[dict]

    def reader_path(self, metric: dict, traced: bool) -> str:
        sub = "layer_metrics" if traced else "end_to_end"
        return os.path.join(HERE, sub, metric["name"] + ".py")


def _applies(metric: dict, cell: str, e2e_names: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=w["chips"], config=config,
                mix_path=os.path.join(root, "perfbench", "traffic",
                                      w["traffic"] + ".json"),
                end_to_end=e2e, per_layer=per_layer)
