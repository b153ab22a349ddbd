"""The moonlight.decode cell: its layer model expands the published
config.json keys into the program's rows, prices them as the program's
exact path does, and its comparison with the reference holds a sound
run to correct and the control and every fault to not correct."""

import json
import pathlib
import sys

import numpy as np
import pytest

import benchcase

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec, work  # noqa: E402
from harness.stream import Driver  # noqa: E402

CELL = "moonlight.decode"
SMALL = {"chunk_size": 4096}
CATALOG_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
                "moe_layer_freq", "intermediate_size", "n_routed_experts",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "vocab_size")
TINY = {"hidden_size": 64, "num_hidden_layers": 2,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "intermediate_size": 96, "n_routed_experts": 4,
        "moe_intermediate_size": 32, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "num_attention_heads": 4,
        "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 256,
        "batch": 4, "context": 32}


def _config() -> dict:
    return spec.load_cell(CELL).config


def test_the_network_holds_the_published_keys():
    config = _config()
    (net,) = config["networks"]
    assert net["layer_model"] == "mla_moe_decode"
    layers = net["layers"]
    for key in CATALOG_KEYS:
        assert layers[key] == config[key], key
    assert (layers["batch"], layers["context"]) == (64, 4096)


def test_expansion_and_program_agree_row_by_row():
    (net,) = spec.networks(_config())
    assert net.n_layers == 322
    rows = net.model.gemms(net.rows)
    workload = net.program()
    assert len(rows) == len(workload.layers) == 322
    for (name, m, k, n, g), layer, macs in zip(
            rows, workload.layers, net.model.layer_macs(net.rows)):
        assert (name, m, k, n, g) == (layer.name, layer.h, layer.c,
                                      layer.k, layer.groups)
        assert macs == layer.macs == g * m * k * n
    assert round(sum(net.model.layer_macs(net.rows)) / 1e9, 2) == 288.27


def test_work_counts_the_group_products_and_field():
    (net,) = spec.networks(_config())
    assert work.kernel_ops(32768, 322, 1, (net,)) \
        == 81 * 32768 * 322 + 6 * 32768
    assert work.kernel_bytes(32768, 322, 1, False, (net,)) \
        == 4 * (15 * 32768 + 11 * 322 + 6 * 32768)


def _tiny_driver():
    config = dict(_config())
    config["networks"] = [dict(config["networks"][0], layers=TINY)]
    return Driver(config, {"driver": "stream", "chunk_size": 1024},
                  seed=2 ** 33 + 21)


def test_table_is_the_programs_exact_path():
    from repro.core.dse_batch import (_make_cfg_lay, _sweep_kernel,
                                      _workload_batch)
    from repro.core.synthesis import synthesize_soa
    driver = _tiny_driver()
    net = driver.network
    assert net.n_layers == 22
    h, bw = driver._draw(0, 0)
    soa = driver._soa(h, bw)
    cfg, lay = _make_cfg_lay(soa, synthesize_soa(soa),
                             _workload_batch(driver.workload))
    out = _sweep_kernel(np, cfg, lay, outputs="full")
    dynamic = _sweep_kernel(np, dict(cfg, leak_mw=0 * cfg["leak_mw"]), lay,
                            outputs="full")
    hw = driver._hardware(h, bw)
    modes = np.repeat(hw["type"][:, None], net.n_layers, axis=1)
    tab = net.model.table(hw, net.rows, modes)
    assert tab["macs"] == driver.workload.total_macs
    assert np.array_equal(tab["compute"], out["compute_cycles"])
    assert np.array_equal(tab["dram_b"], out["dram_bytes"])
    np.testing.assert_allclose(tab["pj"], dynamic["energy_pj"], rtol=1e-12)
    ref = driver._reference(h, bw)
    for i, m in enumerate(("perf_per_area", "energy_j", "latency_s",
                           "throughput_gmacs")):
        np.testing.assert_allclose(ref[:, i], out[m], rtol=1e-12)


def test_sound_run_is_correct():
    r = benchcase.run_small(CELL, SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_control_is_not_correct():
    r = benchcase.run_small(CELL, SMALL, control=True)
    assert not r["correct"]
    c = r["checks"]["rel_err"]
    assert c["value"] > 10 * c["limit"]


@pytest.mark.parametrize("fault", benchcase.FAULTS)
def test_fault_is_not_correct(fault):
    with benchcase.stream_fault(fault):
        r = benchcase.run_small(CELL, SMALL)
    assert not r["correct"], r["checks"]


def test_the_config_file_is_json_with_the_catalog_numbers():
    path = ROOT / "bench" / "configs" / "moonlight-16b-a3b-decode.json"
    config = json.loads(path.read_text())
    assert config["source"] == ("https://huggingface.co/moonshotai/"
                                "Moonlight-16B-A3B/blob/main/config.json")
    assert config["num_hidden_layers"] == 27
    assert config["n_routed_experts"] == 64
    assert config["vocab_size"] == 163840
