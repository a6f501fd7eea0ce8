"""Device time per step of the kernels ``kernels/`` assigns to the
aggregation layer, per chip."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"] or "aggregation" not in t["layer_s"]:
        return None
    return 1e3 * t["layer_s"]["aggregation"] / t["steps"]
