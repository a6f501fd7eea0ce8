"""The benchmark's data files: every configuration and cell loads, names
what exists, keeps the published widths; BENCHMARK.json and the files
agree; a cell is added by adding a file."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import cells, harness  # noqa: E402
from chipbench.reference import algorithm  # noqa: E402

HERE = ROOT / "chipbench"
CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))
CELLS = sorted(p.stem for p in (HERE / "workloads").glob("*.json"))
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# published widths (sources in each file); no cut may touch them
WIDTHS = {
    "mamba2_780m": {"d_model": 1536, "ssm_state": 128, "ssm_head_dim": 64,
                    "ssm_expand": 2, "ssm_chunk": 256, "ssm_conv": 4},
}
CELL_KEYS = {"config", "chips", "workers", "cohort", "per_worker_batch",
             "seq", "n_byz", "attack", "rule", "trim_ratio", "clip_alpha",
             "p", "gamma", "placement", "batches", "rounds", "algorithm_key",
             "g0", "limits"}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_keeps_published_widths(name):
    config = cells.load_config(name)
    assert (HERE / "reference" / f"{config['family']}.py").is_file()
    for key, value in WIDTHS[name].items():
        assert config["model"][key] == value, key
    published = config["published"]
    assert set(config["reduced"]) <= set(published)
    from repro.configs.registry import get_config

    get_config(config["arch"]).replace(**config["model"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_names_a_known_config(name):
    cell, config = cells.load_cell(name)
    assert set(cell) - {"name"} == CELL_KEYS
    assert cell["config"] in CONFIGS
    assert 1 <= cell["cohort"] <= cell["workers"]
    assert cell["n_byz"] < cell["workers"]
    assert set(cell["limits"]) == {"update_gap", "change_gap"}
    assert cell["workers"] * cell["per_worker_batch"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_cell_key_is_chosen_for_its_traffic(name):
    cell, _ = cells.load_cell(name)
    assert cell["algorithm_key"] == algorithm.traffic_key(
        cell, harness.COMPARED_STEPS)


def test_benchmark_json_matches_the_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert {c["name"] for c in BENCH["configs"]} == set(CONFIGS)
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert c["reduced"] == cells.load_config(c["name"])["reduced"]
    assert {w["name"] for w in BENCH["workloads"]} == set(CELLS)
    for w in BENCH["workloads"]:
        cell, _ = cells.load_cell(w["name"])
        assert (w["config"], w["chips"]) == (cell["config"], cell["chips"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert "setup_s" in e2e


def test_kernel_files_name_a_layer():
    layers = cells.kernel_layers()
    assert {"clip_aggregate", "coordinate_median"} <= set(layers)
    assert set(layers.values()) == {"aggregation"}


def test_peaks_are_keyed_by_device_kind():
    v5e = cells.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("TPU v99")


def test_a_cell_is_added_by_adding_a_file(tmp_path):
    root = tmp_path / "chipbench"
    for sub in ("configs", "workloads", "metrics", "kernels"):
        shutil.copytree(HERE / sub, root / sub)
    cell = json.loads((HERE / "workloads" /
                       "mamba2_780m.full_w4_s2048.json").read_text())
    cell.update(seq=1024, per_worker_batch=4)
    (root / "workloads" / "mamba2_780m.full_w4_s1024.json").write_text(
        json.dumps(cell))
    loaded, config = cells.load_cell("mamba2_780m.full_w4_s1024", root)
    assert (loaded["seq"], loaded["per_worker_batch"]) == (1024, 4)
    assert config == cells.load_config("mamba2_780m")
    bench = {"end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
             "per_layer": [{"name": "agg_roofline.train", "unit": "%",
                            "workloads": ["mamba2_780m.full_w4_s2048"]},
                           {"name": "idle_share.train", "unit": "%"}]}
    assert cells.metric_entries(bench, loaded["name"], trace=True) == \
        [("idle_share.train", "%")]
    run = {"tokens_per_step": 16384, "steps": 10, "window_s": 8.0}
    assert cells.read_metric("tokens_per_s", run, root) == 20480.0
