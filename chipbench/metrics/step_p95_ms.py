"""95th percentile of every step's host-clock time in the window, each
from dispatch to the end of ``block_until_ready``."""
import statistics


def read(run):
    times = run["step_times"]
    if len(times) < 2:
        return None
    return 1e3 * statistics.quantiles(times, n=100, method="inclusive")[94]
