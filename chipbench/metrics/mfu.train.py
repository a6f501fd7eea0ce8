"""Model FLOPs the traced window's steps required over the window, the
chips and their bf16 peak.  Per step: 3 x forward FLOPs per token x tokens
per worker x the worker evaluations a step needs in expectation,
p W + (1 - p) 2 C (``counts``); recompute is not counted."""
from chipbench import cells, counts


def read(run):
    t = run["trace"]
    if not t or not t["devices"]:
        return None
    peak = cells.peaks(run["device_kind"])["bf16_flops_per_s"]
    flops = counts.model_flops_per_step(run["cell"], run["config"])
    return 100.0 * flops * t["steps"] / (t["window_s"] * run["chips"] * peak)
