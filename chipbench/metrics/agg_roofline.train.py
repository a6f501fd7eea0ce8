"""Share of the aggregation kernels' time that the least HBM traffic of one
aggregation needs at the chip's bandwidth: every worker's row read once and
the aggregate written once, (W d + d) x bytes of this chip's coordinates
(``counts.aggregation_bytes``).  Full-participation cells only, where every
round aggregates all W rows."""
from chipbench import cells, counts


def read(run):
    t = run["trace"]
    cell = run["cell"]
    if (not t or not t["devices"] or cell["cohort"] != cell["workers"]
            or not t["layer_s"].get("aggregation")):
        return None
    bw = cells.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = counts.aggregation_bytes(cell, run["config"]) / bw
    return 100.0 * least / (t["layer_s"]["aggregation"] / t["steps"])
