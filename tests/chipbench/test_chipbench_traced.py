"""The work counters over a window on the CPU at a tiny size: a
``harness.Trainer`` built with the counters in its state counts its
first steps' and the window's rounds as a recount from the cell's key
gives them, and the useful-evaluation share is read from them, also by a
whole traced run of the harness."""
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import cells, harness, scoped  # noqa: E402
from chipbench.reference import algorithm  # noqa: E402

TINY = {"family": "mamba2", "arch": "mamba2-780m",
        "model": {"n_layers": 2, "d_model": 64, "vocab": 256,
                  "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
                  "ssm_chunk": 16, "ssm_conv": 4, "dtype": "bfloat16"}}
SEED = 2 ** 31 + 19


def _cell():
    cell, _ = cells.load_cell("mamba2_780m.pp_w4c1_s512")
    return dict(cell, seq=64, batches=8)


def _recount(cell, first, steps):
    """(full rounds, Byzantine-only rounds, sampled rows) of steps
    ``first`` to ``first + steps`` as the cell's key draws them."""
    W, byz = cell["workers"], np.arange(cell["workers"]) >= \
        cell["workers"] - cell["n_byz"]
    key, full, byz_only, sampled_rows = \
        jax.random.PRNGKey(cell["algorithm_key"]), 0, 0, 0
    for k in range(first + steps):
        key, is_full, sampled = algorithm.schedule(key, cell["p"], W,
                                                   cell["cohort"])
        if k < first:
            continue
        full += is_full
        byz_only += not is_full and not np.any(sampled & ~byz)
        sampled_rows += int(sampled.sum())
    return full, byz_only, sampled_rows


def test_window_counts_its_rounds():
    cell = _cell()
    trainer = harness.Trainer(cell, TINY, SEED, jax.devices()[:1],
                              counters=True)
    assert scoped.counters(trainer.state._replace(stats=None)) is None
    W = cell["workers"]
    first = scoped.counters(trainer.state)
    full, _, sampled_rows = _recount(cell, 0, harness.FIRST_STEPS)
    assert (first["rounds_full"], first["rows_sampled"]) == \
        (full, sampled_rows)
    with jax.set_mesh(trainer.mesh):
        times, _ = trainer.window(0.3)
    counted = {k: v - first[k]
               for k, v in scoped.counters(trainer.state).items()}
    steps = len(times)
    full, byz_only, sampled_rows = _recount(cell, harness.FIRST_STEPS,
                                            steps)
    assert counted["rounds_full"] == full
    assert counted["rounds_byzantine_only"] == byz_only
    assert counted["rows_sampled"] == sampled_rows
    assert counted["worker_evals"] == W * (2 * steps - full)
    needed = W * full + 2 * (sampled_rows - W * full)
    share = scoped.layer_metrics(None, counted, W)
    assert share == {"useful_eval_share.train":
                     100.0 * needed / counted["worker_evals"]}


def test_traced_run_reads_the_window_counters():
    """A traced run builds the step with the counters and reads the
    useful share of the window's steps alone, not of the first steps; the
    scope readers find no TPU ops here and read nothing."""
    cell = _cell()
    names = ("useful_eval_share.train", "fwd_bwd_ms.train")
    bench = {"end_to_end": [],
             "per_layer": [{"name": n, "unit": "%"} for n in names]}
    result, lines = harness.run_cell(
        dict(cell, name="mamba2_780m.pp_w4c1_s512"), TINY, SEED, 0.3, True,
        jax.devices()[:1], bench=bench, age_at_start=0.0,
        t_start=time.perf_counter())
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    steps, W = result["attempted"], cell["workers"]
    full, _, sampled_rows = _recount(cell, harness.FIRST_STEPS, steps)
    needed = W * full + 2 * (sampled_rows - W * full)
    assert result["metrics"] == {"useful_eval_share.train": {
        "value": 100.0 * needed / (W * (2 * steps - full)), "unit": "%"}}
