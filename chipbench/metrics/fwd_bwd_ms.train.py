"""Device time per step of the ops with the ``worker_grads`` name scope on
their path: every worker's forward and backward pass and its recompute, per
chip (``scoped.layer_metrics``)."""
from chipbench import scoped


def read(run):
    return scoped.layer_metrics(run["trace"]).get("fwd_bwd_ms.train")
