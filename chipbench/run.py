#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``chipbench/workloads/<cell>.json``.  With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics of ``BENCHMARK.json``,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  It needs a TPU with as many chips as the cell asks for: on any
other platform it exits non-zero and prints no result.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import cells, harness  # noqa: E402


def main(argv=None):
    age = harness.process_age()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, config = cells.load_cell(args.workload)
    bench = cells.load_benchmark()
    if bench is None:
        raise SystemExit("chipbench: no BENCHMARK.json in the checkout")
    harness.import_program()
    devices = harness.chips_or_exit(cell["chips"])
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else .jax_cache/
    result, lines = harness.run_cell(
        cell, config, args.seed, args.seconds, bool(args.trace), devices,
        bench=bench, age_at_start=age - (time.perf_counter() - T_START),
        t_start=T_START)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
