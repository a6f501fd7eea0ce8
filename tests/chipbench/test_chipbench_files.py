"""The benchmark's data files: every configuration and cell loads, names
what exists, keeps the published widths; BENCHMARK.json and the files
agree; a cell, and a configuration with its family, kernels and scopes,
are added by adding files."""
import json
import re
import shutil
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import cells, counts, harness, scoped  # noqa: E402
from chipbench.reference import algorithm  # noqa: E402

HERE = ROOT / "chipbench"
CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))
CELLS = sorted(p.stem for p in (HERE / "workloads").glob("*.json"))
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# a published key that names a width: no cut may touch it
WIDTH_KEY = re.compile(r"_dim$|_rank$|hidden_size|intermediate|latent|state"
                       r"|proj|head_?dim|expand|per_tok")
CELL_KEYS = {"config", "chips", "workers", "cohort", "per_worker_batch",
             "seq", "n_byz", "attack", "rule", "trim_ratio", "clip_alpha",
             "p", "gamma", "placement", "batches", "rounds", "algorithm_key",
             "g0", "limits"}


def check_published_widths(config):
    """Every width the file declares (``widths``: model key -> published
    key) is the published one, and no cut names a width."""
    published, widths = config["published"], config["widths"]
    assert widths
    for key, pub in widths.items():
        assert config["model"][key] == published[pub], key
    assert set(config["reduced"]) <= set(published)
    assert not set(config["reduced"]) & set(widths.values())
    assert not [k for k in config["reduced"] if WIDTH_KEY.search(k)]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_keeps_published_widths(name):
    config = cells.load_config(name)
    assert (HERE / "reference" / f"{config['family']}.py").is_file()
    check_published_widths(config)
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
    assert layers["clip_aggregate"] == layers["coordinate_median"] == \
        "aggregation"
    assert all(isinstance(v, str) and v for v in layers.values())


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


def test_a_configuration_is_added_by_adding_files(tmp_path, monkeypatch):
    """A new family (its reference module), a configuration with its
    widths, a cell, a kernel of another layer and a scope: each is found
    by name, and no file that was there changes."""
    root = tmp_path / "chipbench"
    for sub in ("configs", "workloads", "metrics", "kernels", "scopes"):
        shutil.copytree(HERE / sub, root / sub)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    import jax.numpy as jnp

    family = types.ModuleType("chipbench.reference.toy_moe")
    family.init = lambda key, m, dtype: {
        "embed": jnp.zeros((m["vocab"], m["d_model"]), dtype),
        "experts": jnp.zeros((m["n_layers"], m["n_experts"], m["d_model"],
                              m["d_ff"]), dtype)}
    family.loss = lambda params, tokens, m, q: jnp.float32(0)
    family.forward_flops_per_token = lambda m, seq: m["n_layers"] * (
        m["top_k"] * 4 * m["d_model"] * m["d_ff"]) + 2 * m["d_model"] \
        * m["vocab"]
    monkeypatch.setitem(sys.modules, family.__name__, family)
    config = {"family": "toy_moe", "arch": "toy-moe",
              "published": {"hidden_size": 64, "moe_intermediate_size": 32,
                            "num_experts_per_tok": 2, "n_routed_experts": 16,
                            "num_hidden_layers": 12},
              "widths": {"d_model": "hidden_size",
                         "d_ff": "moe_intermediate_size",
                         "top_k": "num_experts_per_tok"},
              "reduced": ["n_routed_experts", "num_hidden_layers"],
              "model": {"n_layers": 3, "d_model": 64, "d_ff": 32,
                        "top_k": 2, "n_experts": 8, "vocab": 128,
                        "dtype": "bfloat16"}}
    (root / "configs" / "toy_moe.json").write_text(json.dumps(config))
    cell = json.loads((HERE / "workloads" /
                       "mamba2_780m.full_w4_s2048.json").read_text())
    (root / "workloads" / "toy_moe.full_w4_s2048.json").write_text(
        json.dumps(dict(cell, config="toy_moe")))
    (root / "kernels" / "expert_matmul.json").write_text(
        json.dumps({"layer": "experts (models/moe.py)"}))
    (root / "scopes" / "moe.json").write_text(
        json.dumps({"layer": "experts (models/moe.py)"}))

    loaded, got = cells.load_cell("toy_moe.full_w4_s2048", root)
    assert got == config and loaded["config"] == "toy_moe"
    check_published_widths(got)
    for cut in (["hidden_size"], ["moe_intermediate_size"]):
        with pytest.raises(AssertionError):
            check_published_widths(dict(got, reduced=cut))
    assert counts.forward_flops_per_token(got, 2048) == \
        3 * 2 * 4 * 64 * 32 + 2 * 64 * 128
    assert counts.parameters(got) == 128 * 64 + 3 * 8 * 64 * 32
    kernels = cells.kernel_layers(root)
    assert kernels["expert_matmul"] == "experts (models/moe.py)"
    assert kernels["clip_aggregate"] == "aggregation"
    scopes = scoped.scope_layers(root)
    assert scopes["moe"] == "experts (models/moe.py)"
    assert {k: v for k, v in scopes.items() if k != "moe"} == \
        scoped.scope_layers()
    assert {p: p.read_bytes() for p in before} == before
