"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell ``<name>`` is ``bench/workloads/<name>.json`` (rate, batcher, check
limits, trace stretch); its configuration is ``bench/configs/<config>.json``
and its traffic mix ``bench/traffic/<traffic>.json``; each metric it
reports is read by ``bench/metrics/<metric>.py``.  The configuration's
``family`` names two modules: ``bench/families/<family>.py``, all that the
harness knows of the family's block (the file's keys, the sizes, the
seeded leaves and their layout in the program's tree, the operations and
bytes of the served steps, the scopes its layers carry), and
``bench/reference/<family>.py``, the plain forward pass that decides
``correct``.

Adding a configuration of a new family is adding files and entries in
``BENCHMARK.json``, with no edit to a file that is there:

- ``bench/configs/<name>.json``
- ``bench/families/<family>.py``
- ``bench/reference/<family>.py``
- ``bench/workloads/<cell>.json``
- ``bench/metrics/<metric>.py`` for a metric no file reads yet

Nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict             # the cell's entry in BENCHMARK.json
    params: Dict            # bench/workloads/<name>.json
    config: Dict            # bench/configs/<config>.json
    mix: Dict               # bench/traffic/<traffic>.json
    end_to_end: List[Dict]  # the end-to-end metrics this cell reports
    per_layer: List[Dict]   # the per-layer metrics this cell reports

    @property
    def family(self) -> ModuleType:
        return family(self.config["family"])

    @property
    def dims(self) -> Dict:
        """The sizes the harness works with; every family gives at least
        ``layers``, ``d``, ``vocab`` and ``heads``."""
        return self.family.dims(self.config)

    @property
    def pad(self) -> int:
        """Prompts are right-padded to the mix's longest prompt."""
        return self.mix["prompt"]["max"]

    @property
    def max_out(self) -> int:
        return self.mix["output"]["max"]

    @property
    def max_len(self) -> int:
        """Cache slots: the longest prompt plus the longest answer."""
        return self.pad + self.max_out


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def family(name: str) -> ModuleType:
    """``bench/families/<name>.py``."""
    return importlib.import_module(f"bench.families.{name}")


def load(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, entry=entry,
        params=_json(root / "bench" / "workloads" / f"{name}.json"),
        config=_json(root / configs[entry["config"]]["file"]),
        mix=_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = ROOT) -> Dict:
    table = _json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]
