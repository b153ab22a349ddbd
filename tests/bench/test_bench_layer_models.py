"""Layer models are found by name: the conv model gives bit for bit what
the harness computed before it was a file of its own, and a network
that brings its own model is new files only."""

import json
import pathlib
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import reference, spec, work  # noqa: E402
from harness.reference import (ACT_BITS, MAC_ENERGY_PJ,  # noqa: E402
                               WEIGHT_BITS)
from harness.stream import hardware_grid  # noqa: E402

CONFIGS = ("qappa-resnet50-wide", "qappa-resnet50", "qappa-suite")


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


# ---------------------------------------------------------------------------
# The harness's conv layer model as it stood in bench/harness/reference.py
# and bench/harness/work.py before layer models were files, kept frozen.
# ---------------------------------------------------------------------------

def _frozen_rf_energy(bits):
    return 0.035 * np.sqrt(np.maximum(bits / 8192.0, 0.03125)) + 0.015


def _frozen_sram_energy(bits):
    return 0.09 * np.sqrt(np.maximum(bits / 8192.0, 0.03125)) + 0.04


def _frozen_cdiv(a, b):
    return -(-a // b)


def frozen_layer_fields(layer) -> dict:
    _, h, w, c, k, r, s, stride, batch = layer
    e = max(1, (h - r) // stride + 1)
    f = max(1, (w - s) // stride + 1)
    return dict(h=h, w=w, c=c, k=k, r=r, s=s, e=e, f=f, n=batch,
                macs=batch * k * c * r * s * e * f)


def frozen_layer_table(hw: dict, layers, modes: np.ndarray) -> dict:
    rows, cols, glb_kb = hw["rows"], hw["cols"], hw["glb_kb"]
    e_spad_pj = _frozen_rf_energy(hw["spad_bits"].astype(np.float64))
    e_glb_pj = _frozen_sram_energy(hw["glb_bits"].astype(np.float64))
    shape = (len(rows), len(layers))
    tab = {"compute": np.zeros(shape, np.int64),
           "dram_b": np.zeros(shape, np.int64),
           "pj": np.zeros(shape)}
    total_macs = 0
    for j, layer in enumerate(layers):
        x = frozen_layer_fields(layer)
        r, s, e, f, c, k, n = (x[v] for v in "r s e f c k n".split())
        ab, wb = ACT_BITS[modes[:, j]], WEIGHT_BITS[modes[:, j]]
        sets_fit = np.maximum(1, rows // r)
        c_sim = np.minimum(c, sets_fit)
        k_sim = np.maximum(1, sets_fit // c_sim)
        fit_horz = np.minimum(e, cols)
        n_e, n_c, n_k = (_frozen_cdiv(e, fit_horz), _frozen_cdiv(c, c_sim),
                         _frozen_cdiv(k, k_sim))
        compute = n * n_e * n_c * n_k * s * f
        ifmap_el = n * c * x["h"] * x["w"]
        weight_el = k * c * r * s
        ofmap_el = n * k * e * f
        ifmap_b = ifmap_el * ab // 8
        glb_half = glb_kb * 1024 // 2
        filt_one = np.maximum(1, c * r * s * wb // 8)
        n_k_glb = _frozen_cdiv(k, np.maximum(1, glb_half // filt_one))
        restream = np.where(ifmap_b <= glb_half, 1, n_k_glb)
        dram_b = ifmap_b * restream + weight_el * wb // 8 + ofmap_el * ab // 8
        dram_el = ifmap_el * restream + weight_el + ofmap_el
        filt_res = np.maximum(1, hw["filt"] // max(1, s))
        glb_el = (2 * dram_el + ifmap_el * _frozen_cdiv(n_k, filt_res)
                  + weight_el * np.maximum(1, n_e // np.minimum(n_e, filt_res))
                  + 2 * ofmap_el * np.maximum(
                      0, np.where(hw["psum"] >= f, 0, n_c - 1)))
        macs = x["macs"]
        tab["compute"][:, j] = compute
        tab["dram_b"][:, j] = dram_b
        tab["pj"][:, j] = (macs * MAC_ENERGY_PJ[modes[:, j]]
                           + 3 * macs * e_spad_pj + glb_el * e_glb_pj)
        total_macs += macs
    tab["macs"] = total_macs
    return tab


def frozen_accuracy_noise(modes, layers, table):
    macs = np.array([frozen_layer_fields(l)["macs"] for l in layers],
                    dtype=np.float64)
    return (table[modes] * (macs / macs.sum())).sum(axis=1)


def frozen_kernel_ops(n, l, w):
    return 78 * n * l + 6 * n * w


def frozen_kernel_bytes(n, l, w, mixed):
    per_layer = 3 if mixed else 0
    cols = (15 - per_layer) * n + per_layer * n * l
    return 4 * (cols + 10 * l + 6 * n * w)


# ---------------------------------------------------------------------------
# Bit identity of row_stationary
# ---------------------------------------------------------------------------

def _grid_hardware(config: dict, prec: str = "f64") -> dict:
    """Every hardware point of the configuration's grid, each with a
    bandwidth from its range or its levels."""
    g = hardware_grid(config)
    n = len(g["type"])
    levels = config.get("dram_bw_levels")
    bw = (np.resize(np.asarray(levels, np.float64), n) if levels else
          np.random.default_rng(7).uniform(*config["dram_bw_gbps"], size=n))
    return reference.hardware(g["type"], g["rows"], g["cols"], g["ifmap"],
                              g["filt"], g["psum"], g["glb_kb"], bw,
                              prec=prec)


def _mode_sets(hw: dict, n_layers: int) -> dict:
    """Every PE mode on every layer, each config's own type, and a mix."""
    n = len(hw["type"])
    sets = {f"all-{t}": np.full((n, n_layers), i, np.int64)
            for i, t in enumerate(reference.PE_TYPES)}
    sets["native"] = np.repeat(hw["type"][:, None], n_layers, axis=1)
    sets["mixed"] = np.random.default_rng(11).integers(
        0, len(reference.PE_TYPES), size=(n, n_layers))
    return sets


NETWORKS = [(c, i) for c in CONFIGS
            for i in range(len(_config(c)["networks"]))]


@pytest.mark.parametrize("config_name,index", NETWORKS,
                         ids=[f"{c}-{_config(c)['networks'][i]['name']}"
                              for c, i in NETWORKS])
def test_row_stationary_is_the_frozen_conv_model_bit_for_bit(config_name,
                                                             index):
    config = _config(config_name)
    net = spec.networks(config)[index]
    rows = config["networks"][index]["layers"]
    assert "layer_model" not in config["networks"][index]
    assert pathlib.Path(net.model.__file__).name == "row_stationary.py"
    assert net.n_layers == len(rows)
    noise = {p: reference.noise_table(p) for p in ("f64", "bf16")}
    for prec in ("f64", "bf16"):
        hw = _grid_hardware(config, prec)
        for label, modes in _mode_sets(hw, len(rows)).items():
            want = frozen_layer_table(hw, rows, modes)
            got = net.model.table(hw, net.rows, modes)
            assert got.keys() == want.keys()
            assert got["macs"] == want["macs"]
            for k in ("compute", "dram_b", "pj"):
                assert got[k].dtype == want[k].dtype, (label, k)
                assert np.array_equal(got[k], want[k]), (label, k)
            agg = reference.evaluate(hw, net, modes, prec)
            frozen = reference.aggregate(want, hw, prec)
            for k, v in frozen.items():
                assert np.array_equal(agg[k], v), (label, prec, k)
            assert np.array_equal(
                reference.accuracy_noise(modes, net, noise[prec]),
                frozen_accuracy_noise(modes, rows, noise[prec])), label
    assert net.model.layer_macs(net.rows) == [
        frozen_layer_fields(r)["macs"] for r in rows]


CALLS = [("qappa-resnet50-wide", (32768, 54, 1, False)),
         ("qappa-resnet50", (64, 54, 1, True)),
         ("qappa-suite", (5, 107, 3, True)),
         ("qappa-suite", (4096, 107, 3, True))]


@pytest.mark.parametrize("config_name,call", CALLS)
def test_work_counts_from_the_layer_models_equal_the_frozen_counts(
        config_name, call):
    n, l, w, mixed = call
    nets = spec.networks(_config(config_name))
    assert sum(net.n_layers for net in nets) == l and len(nets) >= w
    assert work.kernel_ops(n, l, w, nets) == frozen_kernel_ops(n, l, w)
    assert work.kernel_ops(n, l, w) == frozen_kernel_ops(n, l, w)
    assert work.kernel_bytes(n, l, w, mixed, nets) \
        == frozen_kernel_bytes(n, l, w, mixed)
    assert work.kernel_bytes(n, l, w, mixed) \
        == frozen_kernel_bytes(n, l, w, mixed)
    peaks = spec.peaks_for("TPU v5 lite")
    assert work.roofline([call] * 3, 1e-3, peaks, nets) \
        == work.roofline([call] * 3, 1e-3, peaks)
    with pytest.raises(ValueError):
        work.kernel_ops(n, l + 1, w, nets)


# ---------------------------------------------------------------------------
# A network that brings its own layer model is new files only
# ---------------------------------------------------------------------------

FC_BATCHED = '''"""Toy layer model: a row ``[name, c, k, tokens, count]`` is ``count``
fully connected layers of ``c`` inputs and ``k`` outputs over ``tokens``
rows, priced as 1x1 convolutions on the row-stationary mapping."""

import pathlib

from harness import spec

_CONV = spec.load_layer_model(
    "row_stationary", pathlib.Path(__file__).resolve().parents[2])


def _conv_rows(rows):
    return [[f"{name}.{i}", 1, 1, c, k, 1, 1, 1, tokens]
            for name, c, k, tokens, count in rows for i in range(count)]


def layer_macs(rows):
    return _CONV.layer_macs(_conv_rows(rows))


def table(hw, rows, modes):
    return _CONV.table(hw, _conv_rows(rows), modes)


def kernel_work(rows):
    return _CONV.kernel_work(_conv_rows(rows))


def program_network(network):
    from repro.core.workloads import Workload, fc
    return Workload(network.name, tuple(
        fc(f"{name}.{i}", c, k, batch=tokens)
        for name, c, k, tokens, count in network.rows
        for i in range(count)))
'''

FC_ROWS = [["qkv", 512, 1536, 16, 3], ["out", 512, 512, 16, 2],
           ["head", 512, 1000, 1, 1]]


def _as_conv_rows(rows):
    return [[f"{name}.{i}", 1, 1, c, k, 1, 1, 1, tokens]
            for name, c, k, tokens, count in rows for i in range(count)]


def _bench_bytes(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _plug_in(tmp_path: pathlib.Path, layer_model: str):
    """A copy of ``bench/`` with the toy layer model and a config and cell
    whose network names ``layer_model``."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _bench_bytes(tmp_path)
    (tmp_path / "bench" / "layers" / "fc_batched.py").write_text(FC_BATCHED)
    config = _config("qappa-resnet50")
    config["name"] = "qappa-fc"
    config["networks"] = [dict(name="fc-toy", layer_model=layer_model,
                               layers=FC_ROWS)]
    (tmp_path / "bench" / "configs" / "qappa-fc.json").write_text(
        json.dumps(config))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][1], name="qappa-fc",
                                 file="bench/configs/qappa-fc.json"))
    bench["workloads"].append(dict(name="fc.serving", config="qappa-fc",
                                   traffic="serving-steady", chips=1,
                                   why="a toy network of batched fc layers"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def test_a_network_with_its_own_layer_model_is_new_files_only(tmp_path):
    before = _plug_in(tmp_path, "fc_batched")
    cell = spec.load_cell("fc.serving", root=tmp_path)
    (net,) = spec.networks(cell.config, root=tmp_path)
    assert pathlib.Path(net.model.__file__) \
        == tmp_path / "bench" / "layers" / "fc_batched.py"
    conv_config = dict(cell.config, networks=[dict(
        name="fc-toy", layers=_as_conv_rows(FC_ROWS))])
    (conv,) = spec.networks(conv_config)
    assert pathlib.Path(conv.model.__file__).name == "row_stationary.py"
    assert net.n_layers == conv.n_layers == 6

    hw = _grid_hardware(cell.config)
    table = reference.noise_table()
    for modes in _mode_sets(hw, net.n_layers).values():
        got = reference.evaluate(hw, net, modes)
        want = reference.evaluate(hw, conv, modes)
        for k, v in want.items():
            assert np.array_equal(got[k], v), k
        assert np.array_equal(reference.accuracy_noise(modes, net, table),
                              reference.accuracy_noise(modes, conv, table))

    workload = net.program()
    assert workload == conv.program()
    assert [l.batch for l in workload.layers] == [16] * 5 + [1]

    assert work.kernel_ops(7, 6, 1, (net,)) == work.kernel_ops(7, 6, 1)
    assert work.kernel_bytes(7, 6, 1, True, (net,)) \
        == work.kernel_bytes(7, 6, 1, True)

    after = _bench_bytes(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_an_unknown_layer_model_is_a_spec_error_naming_its_path(tmp_path):
    _plug_in(tmp_path, "no_such_model")
    missing = tmp_path / "bench" / "layers" / "no_such_model.py"
    with pytest.raises(spec.SpecError, match=re.escape(str(missing))):
        spec.load_cell("fc.serving", root=tmp_path)
    with pytest.raises(spec.SpecError, match="no_such_model"):
        spec.load_layer_model("no_such_model")
