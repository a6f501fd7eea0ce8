"""Median device duration of the window's difference-round steps: the
modules that ran an op under the ``round_diff`` name scope
(``scoped.layer_metrics``)."""
from chipbench import scoped


def read(run):
    return scoped.layer_metrics(run["trace"]).get("diff_round_ms.train")
