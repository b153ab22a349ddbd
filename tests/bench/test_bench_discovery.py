"""Cells, configurations, traffic mixes, metric readers and peaks are
found by name, and a new one of each is a new file."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
DRIVER_KINDS = ("stream", "nsga2", "serving")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = spec.load_cell(cell)
    assert c.traffic["driver"] in DRIVER_KINDS
    assert c.config["networks"] and c.config["limits"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.load_reader(m["name"]))


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric")


def test_peaks_are_keyed_by_device_kind_and_an_unknown_device_is_an_error():
    v5e = spec.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks_for("cpu")


def _digests(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_adding_a_config_a_mix_and_a_metric_edits_no_existing_file(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    config = json.loads(
        (ROOT / "bench" / "configs" / "qappa-resnet50.json").read_text())
    config["name"] = "qappa-vgg16"
    suite = json.loads(
        (ROOT / "bench" / "configs" / "qappa-suite.json").read_text())
    config["networks"] = suite["networks"][:1]
    (tmp_path / "bench" / "configs" / "qappa-vgg16.json").write_text(
        json.dumps(config))
    mix = dict(json.loads((ROOT / "bench" / "traffic"
                           / "serving-steady.json").read_text()))
    mix["metric"] = "campaign_s.serving-heavy"
    mix["arrivals"] = dict(mix["arrivals"], rate_rps=12.0, n_requests=96,
                           slo_s=2.5)
    (tmp_path / "bench" / "traffic" / "serving-heavy.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "fleet.calls.py").write_text(
        "def read(run):\n"
        "    return float(sum(s['name'] == 'fleet.simulate'"
        " for s in run.spans))\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qappa-vgg16",
                                 file="bench/configs/qappa-vgg16.json"))
    bench["workloads"].append(dict(name="vgg16.serving-heavy",
                                   config="qappa-vgg16",
                                   traffic="serving-heavy", chips=1,
                                   why="Poisson 12 rps, 96 requests"))
    bench["per_layer"].append(dict(name="fleet.calls", unit="calls",
                                   better="lower", source="program_span",
                                   layer="fleet simulator",
                                   moves="campaign_s.serving-heavy",
                                   workloads=["vgg16.serving-heavy"]))
    bench["end_to_end"].append(dict(name="campaign_s.serving-heavy",
                                    unit="s", better="lower", bound=0.25,
                                    source="host_clock",
                                    workloads=["vgg16.serving-heavy"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("vgg16.serving-heavy", root=tmp_path)
    assert cell.config["networks"][0]["name"] == "vgg16"
    assert cell.traffic["arrivals"]["rate_rps"] == 12.0
    assert [m["name"] for m in cell.per_layer][-1] == "fleet.calls"
    assert {m["name"] for m in cell.end_to_end} == {
        "campaign_s.serving-heavy", "setup_s"}
    read = spec.load_reader("fleet.calls", root=tmp_path)
    assert read(type("R", (), {"spans": [{"name": "fleet.simulate"}]})) == 1
    after = _digests(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before

    from harness.search import draw_arrivals
    arrival, prompt, decode = draw_arrivals(cell.traffic["arrivals"], 5)
    assert len(arrival) == 96 and (sorted(arrival) == arrival).all()


def _bench_only_tree(tmp_path: pathlib.Path) -> pathlib.Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.mark.parametrize("where", ["checkout", "bench-only"])
def test_run_exits_nonzero_and_prints_no_result_without_a_tpu(where,
                                                             tmp_path):
    root = ROOT if where == "checkout" else _bench_only_tree(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.stream",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
