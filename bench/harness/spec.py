"""Everything the harness runs is found by name, in files of its own.

* ``BENCHMARK.json`` at the checkout root lists the cells (``workloads``)
  and the metrics;
* ``bench/configs/<config>.json`` holds a configuration as it is run;
* ``bench/traffic/<mix>.json`` holds a traffic mix: the ``driver`` kind
  (``stream``, ``nsga2`` or ``serving``) and its parameters;
* ``bench/metrics/<metric>.py`` is the reader of one per-layer metric,
  a module with ``read(run) -> float | None``;
* ``bench/peaks.json`` holds the published peaks, keyed by the
  ``device_kind`` the chip reports.

Adding a configuration, a mix or a metric is adding a file and an entry
in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name that no file answers, or a file that is not well formed."""


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads``, with its configuration, its traffic
    mix and the metrics it reports."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = load_traffic(w["traffic"], root)
    e2e = tuple(m for m in bench["end_to_end"] if _reported_in(m, name))
    layer = tuple(m for m in bench["per_layer"] if _reported_in(m, name))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_traffic(mix: str, root: pathlib.Path = ROOT) -> dict:
    traffic = _load_json(root / "bench" / "traffic" / f"{mix}.json")
    if "driver" not in traffic:
        raise SpecError(f"traffic mix {mix!r} names no driver")
    return traffic


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks_for(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json (known: "
                        f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
