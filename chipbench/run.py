#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``chipbench/workloads/<cell>.json``.  With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics of ``BENCHMARK.json``,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  It needs a TPU with as many chips as the cell asks for: on any
other platform it exits non-zero and prints no result.

The run itself is a child process; this one never touches JAX.  A child
that finds the step missing from the compilation cache (the cell's first
run in a checkout) compiles it, fills the cache through its set-up and
stops; a second child then measures with every program loaded from the
cache, as every later run does.  Compiling in the measured process would
leave the device's memory peak 0.397 GiB higher in one cell (PERF.md,
section 2), so the first run would read apart from the rest.  ``setup_s``
counts from this process's start, both children's set-up included.
"""
import argparse
import ctypes
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import cells  # noqa: E402

CHILD = "CHIPBENCH_LAUNCHED_AT"  # the launcher's start, CLOCK_MONOTONIC s
CACHED = "CHIPBENCH_STEP_CACHED"  # set for the child after a compiling one
COMPILED = 75  # a child's exit code: it compiled the step, run again


def process_age():
    """Seconds since this process started (Linux)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _die_with_parent():
    PR_SET_PDEATHSIG = 1
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG,
                                            int(signal.SIGKILL))


def launch(argv):
    """Runs the child, twice where the first compiled the step; returns the
    measuring child's exit code.  Its output is the child's own."""
    env = dict(os.environ)
    env[CHILD] = repr(time.monotonic() - process_age())
    cmd = [sys.executable, str(Path(__file__).resolve()), *argv]
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(128 + signal.SIGTERM))
    for attempt in range(2):
        proc = subprocess.Popen(cmd, env=env, preexec_fn=_die_with_parent)
        try:
            rc = proc.wait()
        finally:
            if proc.poll() is None:
                proc.terminate()
                proc.wait()
        if rc != COMPILED:
            return rc
        env[CACHED] = "1"
    return 1


def measure(args):
    from chipbench import harness

    age = time.monotonic() - float(os.environ[CHILD])
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
        t_start=T_START, stop_if_compiled=CACHED not in os.environ)
    if result is None:
        return COMPILED
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    args = parse(argv)
    cells.load_cell(args.workload)  # an unknown cell fails here
    if CHILD not in os.environ:
        return launch(argv)
    return measure(args)


if __name__ == "__main__":
    sys.exit(main())
