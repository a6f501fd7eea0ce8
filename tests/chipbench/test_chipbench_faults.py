"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath
(a state left unchanged, half of the batch left out, an answer altered
where it is produced) or with the reference in the next precision below
the configuration's in the program's place, it does not.  The limits are
the mamba2 cell's own."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import cells, compare, faults, harness  # noqa: E402

TINY = {"family": "mamba2", "arch": "mamba2-780m",
        "model": {"n_layers": 2, "d_model": 64, "vocab": 256,
                  "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
                  "ssm_chunk": 16, "ssm_conv": 4, "dtype": "bfloat16"}}
BENCH = {"end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}
SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def cell():
    cell, _ = cells.load_cell("mamba2_780m.full_w4_s2048")
    return dict(cell, seq=64, batches=8)


def _run(cell, wrap=None):
    import jax

    return harness.run_cell(cell, TINY, SEED, 0.3, False,
                            jax.devices()[:1], bench=BENCH,
                            age_at_start=0.0, t_start=time.perf_counter(),
                            wrap=wrap)


def test_sound_run_is_correct(cell):
    result, lines = _run(cell)
    assert result["correct"], lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert lines[-3].startswith("update_gap ")


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_under_the_timed_path_is_caught(cell, fault):
    result, lines = _run(cell, wrap=faults.FAULTS[fault])
    assert not result["correct"], lines


def test_control_is_caught(cell):
    import jax

    trainer = harness.Trainer(cell, TINY, SEED, jax.devices()[:1])
    tokens, moved = trainer.tokens, trainer.moved
    del trainer
    ref = harness.reference(cell, TINY, SEED, tokens)
    control = harness.reference(cell, TINY, SEED, tokens, control=True)
    as_program = {"update_norms": [{"diff": r["update_norms"],
                                    "full": r["update_norms"]}
                                   for r in control["rounds"]],
                  "change_norms": control["change_norms"][
                      np.asarray(moved)]}
    correct, checks = compare.verdict(
        compare.gaps(as_program, ref, moved), cell["limits"])
    assert not correct, checks
