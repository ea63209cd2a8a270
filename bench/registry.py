"""Finds everything of a cell by name, in files of its own:

* ``<bench>/workloads/<cell>.json``: the cell -- ``config``, ``traffic``,
  the offered load and the warm-up, and a one-line ``why``;
* ``<bench>/configs/<config>.json``: the model's published keys, the
  ``model`` sizes as run, the ``engine`` settings, ``reduced``,
  ``assumed`` and ``departures``;
* ``<bench>/traffic/<mix>.json``: the loop kind and its parameters;
* ``<bench>/metrics/<metric>.py``: one reader per metric, ``read(run)``;
* ``<bench>/peaks.json``: the chip's peaks, keyed by ``device_kind``;
* ``BENCHMARK.json`` beside ``<bench>``: which metrics each cell reports.

Adding a cell, a configuration, a mix or a metric is adding its files (and
its entry in ``BENCHMARK.json``); no code here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

__all__ = ["BENCH", "Cell", "load_cell", "load_reader", "load_peaks"]

#: the benchmark's directory in this checkout
BENCH = Path(__file__).resolve().parent


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path

    @property
    def model(self) -> Dict:
        return self.config["model"]

    @property
    def engine(self) -> Dict:
        return self.config["engine"]

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end


def _for_cell(entries: List[Dict], cell: str) -> List[Dict]:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def load_cell(name: str, bench_dir: Path = BENCH,
              spec: Optional[Dict] = None) -> Cell:
    """The cell ``name``; ``spec`` is the parsed ``BENCHMARK.json`` (read
    from beside ``bench_dir`` when not given)."""
    if spec is None:
        spec = _json(bench_dir.parent / "BENCHMARK.json")
    entry = {w["name"]: w for w in spec["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    workload = _json(bench_dir / "workloads" / f"{name}.json")
    for k in ("config", "traffic"):
        if workload[k] != entry[k]:
            raise ValueError(f"{name}: {k} {workload[k]!r} in its file, "
                             f"{entry[k]!r} in BENCHMARK.json")
    config = _json(bench_dir / "configs" / f"{workload['config']}.json")
    traffic = dict(_json(bench_dir / "traffic" /
                         f"{workload['traffic']}.json"),
                   name=workload["traffic"])
    return Cell(name, workload, config, traffic, int(entry["chips"]),
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name), bench_dir)


def load_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """The ``read(run)`` function of metric ``name``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, bench_dir: Path = BENCH) -> Dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    table = _json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{bench_dir / 'peaks.json'}")
    return table[device_kind]
