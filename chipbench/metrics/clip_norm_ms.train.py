"""Device time per step of the ops with the ``clip_norm`` name scope on
their path: the clip radius's norm and the rows' norms and factors, per
chip (``scoped.layer_metrics``)."""
from chipbench import scoped


def read(run):
    return scoped.layer_metrics(run["trace"]).get("clip_norm_ms.train")
