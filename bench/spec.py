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


def check_cell(config: dict, chips: int) -> None:
    """Refuse a cell the harness cannot judge: a set whose slaves are not
    one a chip (ODYS deploys a slave a node), and online ingest into a set
    of more than one slave (the reference answers ingest for one slave)."""
    ns = config["service"]["ns"]
    if ns != chips:
        raise ValueError(f"a set of ns={ns} slaves on {chips} chips: the benchmark "
                         "runs one slave a chip")
    if ns > 1 and config.get("ingest") is not None:
        raise ValueError(f"online ingest into a set of ns={ns} slaves: the reference "
                         "answers ingest for one slave only")


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
    check_cell(config, int(w["chips"]))
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
