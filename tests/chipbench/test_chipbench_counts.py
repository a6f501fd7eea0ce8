"""The FLOP and byte counts the per-layer metrics divide by, against hand
counts for one layer of each configuration."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import cells, counts  # noqa: E402


def test_mamba2_layer_by_hand():
    m = cells.load_config("mamba2_780m")["model"]
    d, di, N, nh, K, Q = 1536, 3072, 128, 48, 4, 256
    in_proj = 2 * d * (2 * di + 2 * N + nh)  # z, x, B, C, dt
    out_proj = 2 * di * d
    conv = 2 * K * (di + 2 * N)
    # intra-chunk: a token sees (Q+1)/2 positions on average, each a C.B
    # dot (2N) and a head-weighted x (2 di); inter-chunk: C.h and the
    # state update, 2 N di each
    ssd = (Q + 1) * (2 * N + 2 * di) // 2 + 4 * N * di
    assert counts.mamba2_layer_flops(m, 2048) == \
        in_proj + out_proj + conv + ssd == 31_667_328


def test_forward_adds_output_projection():
    c = cells.load_config("mamba2_780m")
    assert counts.forward_flops_per_token(c, 2048) == \
        4 * 31_667_328 + 2 * 1536 * 50280


@pytest.mark.parametrize("cohort, want", [(4, 0.125 * 4 + 0.875 * 8),
                                          (1, 0.125 * 4 + 0.875 * 2)])
def test_required_evaluations(cohort, want):
    cell = {"workers": 4, "cohort": cohort, "p": 0.125}
    assert counts.required_evaluations(cell) == pytest.approx(want)


def test_model_flops_per_step():
    cell, config = cells.load_cell("mamba2_780m.full_w4_s2048")
    per_token = 3 * counts.forward_flops_per_token(config, 2048)
    assert counts.model_flops_per_step(cell, config) == pytest.approx(
        per_token * 2 * 2048 * 7.5)


@pytest.mark.parametrize("name, params", [("mamba2_780m", 213_038_144)])
def test_parameters_and_aggregation_bytes(name, params):
    config = cells.load_config(name)
    assert counts.parameters(config) == params
    cell = {"workers": 4, "chips": 1}
    leaves = counts.message_leaves(config)
    assert counts.aggregation_bytes(cell, config) == \
        sum(5 * s * b for s, b in leaves)
    assert counts.aggregation_bytes(dict(cell, chips=4), config) == \
        counts.aggregation_bytes(cell, config) / 4
