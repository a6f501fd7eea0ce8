"""Device time per step of the ops with the ``attack`` name scope on their
path: the Byzantine workers' messages formed from the honest ones, per chip
(``scoped.layer_metrics``)."""
from chipbench import scoped


def read(run):
    return scoped.layer_metrics(run["trace"]).get("attack_ms.train")
