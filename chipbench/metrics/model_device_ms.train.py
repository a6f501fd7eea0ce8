"""Device busy time per step less the aggregation kernels and the
collectives: the per-worker forward and backward passes, the attack stage,
the clip norms and the x/g update, per chip."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"]:
        return None
    rest = t["busy_s"] - t["layer_s"].get("aggregation", 0.0) \
        - t["collective_s"]
    return 1e3 * rest / t["steps"]
