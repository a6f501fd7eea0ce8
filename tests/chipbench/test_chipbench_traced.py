"""The work counters over a window on the CPU at a tiny size: a
``harness.Trainer`` started on the counters (``scoped.with_counters``)
counts the window's rounds as a recount from the cell's key gives them,
and the useful-evaluation share is read from them."""
import sys
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


def test_window_counts_its_rounds():
    cell, _ = cells.load_cell("mamba2_780m.pp_w4c1_s512")
    cell = dict(cell, seq=64, batches=8)
    kept = {}

    def keep(step_fn, W):
        kept["step"] = step_fn
        return step_fn

    trainer = harness.Trainer(cell, TINY, SEED, jax.devices()[:1],
                              wrap=keep)
    assert scoped.counters(trainer.state) is None
    scoped.with_counters(trainer, kept["step"])
    with jax.set_mesh(trainer.mesh):
        times, _ = trainer.window(0.3)
    counted = scoped.counters(trainer.state)
    steps = len(times)
    W, byz = cell["workers"], np.arange(cell["workers"]) >= \
        cell["workers"] - cell["n_byz"]
    key, full, byz_only, sampled_rows = \
        jax.random.PRNGKey(cell["algorithm_key"]), 0, 0, 0
    for k in range(harness.FIRST_STEPS + steps):
        key, is_full, sampled = algorithm.schedule(key, cell["p"], W,
                                                   cell["cohort"])
        if k < harness.FIRST_STEPS:
            continue
        full += is_full
        byz_only += not is_full and not np.any(sampled & ~byz)
        sampled_rows += int(sampled.sum())
    assert counted["rounds_full"] == full
    assert counted["rounds_byzantine_only"] == byz_only
    assert counted["rows_sampled"] == sampled_rows
    assert counted["worker_evals"] == W * (2 * steps - full)
    needed = W * full + 2 * (sampled_rows - W * full)
    share = scoped.layer_metrics(None, counted, W)
    assert share == {"useful_eval_share.train":
                     100.0 * needed / counted["worker_evals"]}
