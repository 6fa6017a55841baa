"""What one run measured, as the metric readers see it.

Each metric of BENCHMARK.json has a reader of its own,
perfbench/end_to_end/<name>.py or perfbench/layer_metrics/<name>.py, with
one function `read(r: Readings) -> float | None`.  A reader that finds
nothing to read returns None and the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field

import numpy as np

WRITE_SPANS = ("Storage.write", "Storage.write_packed", "Storage.update",
               "Storage.update_packed")
READ_SPANS = ("Storage.batch_read",)


@dataclass
class Readings:
    config: dict
    groups: list                 # traffic.Group
    fault: dict                  # the mix's fault, {} for none
    stats: dict                  # group index -> traffic.GroupStats
    window_s: float              # measured window, to the last reply
    setup_s: float
    loop_lag: list[float]        # ticker oversleep samples, window only
    counters: dict[str, int]     # codec counter deltas over the window
    spans: list[dict] = field(default_factory=list)      # traced run only
    trace: object = None         # trace_reduce.TraceSummary, traced run
    trace_counters: dict[str, int] = field(default_factory=dict)
    peaks: dict | None = None    # peaks.json entry of the device kind

    def op_stats(self, op: str) -> list:
        return [self.stats[g.index] for g in self.groups if g.op == op]

    def server_spans(self, names: tuple[str, ...]) -> list[dict]:
        return [s for s in self.spans
                if s.get("kind") == "server" and s["name"] in names]


def mb_per_s(r: Readings, op: str) -> float | None:
    stats = r.op_stats(op)
    if not stats:
        return None
    return sum(s.bytes for s in stats) / r.window_s / 1e6


def latency_ms(r: Readings, op: str, q: float) -> float | None:
    lat = [x for s in r.op_stats(op) for x in s.latencies]
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def mean_ms(values: list[float]) -> float | None:
    return 1e3 * sum(values) / len(values) if values else None


def load_reader(path: str):
    """The `read` function of one metric's file."""
    name = "perfbench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
