"""Find a cell's pieces by name: its configuration, its traffic mix, its
metrics and their readers.  Nothing here names a cell, a mix or a metric;
a new one is a new file and a new entry of ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

#: The checkout's root: this file is ``<root>/bench/spec.py``.
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic mix's parameters
    chips: int
    end_to_end: list[dict]  # the metrics this cell reports with --trace 0
    per_layer: list[dict]   # ... and with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _reports(m, name) and m["moves"] in reported]
    return Cell(name, config, traffic, int(w["chips"]), e2e, layer)


def reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of a per-layer metric: the file
    ``bench/layer_metrics/<name>.py``, or for a name split by cell kind
    (``host_dispatch_ms.sat``) the file of its stem."""
    d = root / "bench" / "layer_metrics"
    for stem in (metric, metric.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            mod_spec = importlib.util.spec_from_file_location(
                f"bench_layer_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} under {d}")
