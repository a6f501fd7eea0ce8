#!/usr/bin/env python3
"""Read the two ends a cell's limits are set between, on the chip, in one
process (the program's compile is paid once):

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out chiprun_out/calib.jsonl]

For every seed: the program's first steps against the reference (the lower
reading).  For every control seed besides: the reference in the next
precision below the configuration's (the control), the reference with half
of each worker's batch left out, and the program with its largest
estimator leaf doubled where the step produces it (the faults; a state left
unchanged reads 1 by the measure and needs no run).  Prints one JSON line
per reading.
"""
import argparse
import json
import sys
import time

import numpy as np
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import cells, compare, faults, harness  # noqa: E402


def as_program(ref, moved):
    return {"update_norms": [{"diff": r["update_norms"],
                              "full": r["update_norms"]}
                             for r in ref["rounds"]],
            "change_norms": np.asarray(ref["change_norms"])[np.asarray(moved)]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell, config = cells.load_cell(args.workload)
    cell["name"] = args.workload
    harness.import_program()
    devices = harness.chips_or_exit(cell["chips"])
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else .jax_cache/
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec["cell"] = args.workload
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        tr = harness.Trainer(cell, config, seed, devices)
        numbers, moved, tokens = tr.numbers, tr.moved, tr.tokens
        del tr
        t1 = time.perf_counter()
        mem = {k: v for k, v in (devices[0].memory_stats() or {}).items()
               if "bytes" in k}
        ref = harness.reference(cell, config, seed, tokens)
        t2 = time.perf_counter()
        emit({"seed": seed, "kind": "program", "program_s": t1 - t0,
              "reference_s": t2 - t1, "memory_after_free": mem,
              "rounds": [{k: r[k] for k in ("full", "radius",
                                             "message_norms",
                                             "clip_factors")}
                         for r in ref["rounds"]],
              **compare.gaps(numbers, ref, moved)})
        if seed not in control_seeds:
            continue
        for kind, kw in (("control", {"control": True}),
                         ("half_batch", {"batch_fault": True})):
            other = harness.reference(cell, config, seed, tokens, **kw)
            emit({"seed": seed, "kind": kind,
                  **compare.gaps(as_program(other, moved), ref, moved)})
        tr = harness.Trainer(cell, config, seed, devices,
                             wrap=faults.altered)
        emit({"seed": seed, "kind": "altered",
              **compare.gaps(tr.numbers, ref, moved)})
        del tr
    return 0


if __name__ == "__main__":
    sys.exit(main())
