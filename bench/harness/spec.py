"""Everything the harness runs is found by name, in files of its own.

* ``BENCHMARK.json`` at the checkout root lists the cells (``workloads``)
  and the metrics;
* ``bench/configs/<config>.json`` holds a configuration as it is run;
* ``bench/traffic/<mix>.json`` holds a traffic mix: the ``driver`` kind
  (``stream``, ``nsga2`` or ``serving``) and its parameters;
* ``bench/metrics/<metric>.py`` is the reader of one per-layer metric,
  a module with ``read(run) -> float | None``;
* ``bench/layers/<model>.py`` is a layer model: how the rows of a
  network that names it (``"layer_model"``; ``row_stationary``, the conv
  mapping, where the network names none) map onto the array and what
  each costs.  It exports ``layer_macs(rows)`` (the MACs of each layer on
  the kernel's layer axis; its length is the network's layer count),
  ``table(hw, rows, modes)`` (the ``(N, L)`` ``compute`` cycles,
  ``dram_b`` bytes and ``pj`` energy without leakage, and the network's
  ``macs``), ``kernel_work(rows)`` (the sweep kernel's operations per
  config and layer-table bytes for these rows) and
  ``program_network(network)`` (the program's ``Workload``, the only
  function of the file that imports the program);
* ``bench/peaks.json`` holds the published peaks, keyed by the
  ``device_kind`` the chip reports.

Adding a configuration, a mix or a metric is adding a file and an entry
in ``BENCHMARK.json``; adding a layer model is adding a file that a
configuration names.  No file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import types

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
DEFAULT_LAYER_MODEL = "row_stationary"


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
    networks(config, root)          # every layer model has its file
    traffic = load_traffic(w["traffic"], root)
    e2e = tuple(m for m in bench["end_to_end"] if _reported_in(m, name))
    layer = tuple(m for m in bench["per_layer"] if _reported_in(m, name))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


@dataclasses.dataclass(frozen=True)
class Network:
    """One network of a configuration with the layer model that reads its
    rows; ``n_layers`` is its length on the kernel's layer axis."""

    name: str
    rows: list
    model: types.ModuleType
    n_layers: int

    def program(self):
        """The program's workload for this network, which has to hold as
        many layers as the layer model counts."""
        workload = self.model.program_network(self)
        if len(workload.layers) != self.n_layers:
            raise SpecError(f"network {self.name!r}: the program's workload "
                            f"has {len(workload.layers)} layers, its layer "
                            f"model counts {self.n_layers}")
        return workload


def load_layer_model(name: str, root: pathlib.Path = ROOT):
    """The module ``bench/layers/<name>.py``."""
    path = root / "bench" / "layers" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no layer model {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_layers_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def networks(config: dict, root: pathlib.Path = ROOT) -> tuple[Network, ...]:
    """The configuration's networks, each with its layer model."""
    out = []
    for net in config["networks"]:
        model = load_layer_model(net.get("layer_model", DEFAULT_LAYER_MODEL),
                                 root)
        out.append(Network(net["name"], net["layers"], model,
                           len(model.layer_macs(net["layers"]))))
    return tuple(out)


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
