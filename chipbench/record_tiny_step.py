#!/usr/bin/env python3
"""Record the small trace of the program's scoped train step that the
tests of the trace reduction read, on one TPU chip.

    python3 chipbench/record_tiny_step.py [out dir]

A tiny Mamba-2 (2 layers, d_model 64, vocabulary 256) under cell 2's
composition (W = 4, C = 1, ALIE, coordinate median behind the clip, naive
placement, Pallas), p = 1/2, with the work counters in its state; three
steps set it up, then three more run under the profiler inside the
harness's host spans.
Writes ``tiny_step_v5e.xplane.pb.gz`` and the compiled step's text
``tiny_step_v5e.hlo.txt.gz`` (default: ``chipbench/testdata/``) and prints
the reduction.
"""
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import cells, harness, scoped  # noqa: E402

TINY = {"family": "mamba2", "arch": "mamba2-780m",
        "model": {"n_layers": 2, "d_model": 64, "vocab": 256,
                  "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
                  "ssm_chunk": 16, "ssm_conv": 4, "dtype": "bfloat16"}}
KEY = 7  # rounds 4-6 from it: difference, full, Byzantine-only difference
STEPS = 3
SEED = 2 ** 31 + 11


def main(out=cells.HERE / "testdata"):
    import jax

    harness.import_program()
    devices = harness.chips_or_exit(1)
    cell, _ = cells.load_cell("mamba2_780m.pp_w4c1_s512")
    cell = dict(cell, seq=64, batches=8, p=0.5, algorithm_key=KEY)
    trainer = harness.Trainer(cell, TINY, SEED, devices, counters=True)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-tiny-")
    Ann = jax.profiler.TraceAnnotation
    try:
        with jax.set_mesh(trainer.mesh):
            jax.profiler.start_trace(trace_dir)
            for _ in range(STEPS):
                with Ann("bench.step"):
                    trainer.state = trainer.compiled(
                        trainer.state,
                        trainer.feeds[trainer.k % len(trainer.feeds)])
                with Ann("bench.block"):
                    jax.block_until_ready(trainer.state)
                with Ann("bench.host"):
                    trainer.k += 1
            jax.profiler.stop_trace()
        xplane = max(Path(trace_dir).rglob("*.xplane.pb"))
        out = Path(out)
        with open(xplane, "rb") as f, \
                gzip.open(out / "tiny_step_v5e.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
        text = trainer.compiled.as_text()
        with gzip.open(out / "tiny_step_v5e.hlo.txt.gz", "wt") as g:
            g.write(text)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    red = scoped.reduce(scoped.load(out / "tiny_step_v5e.xplane.pb.gz"),
                        paths=scoped.op_paths(text))
    print(json.dumps({"counters": scoped.counters(trainer.state),
                      "reduced": red,
                      "bytes": {p.name: p.stat().st_size
                                for p in out.glob("tiny_step_v5e.*")}},
                     default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
