"""Microbenchmarks: Pallas aggregation kernels (interpret mode on CPU) vs
their pure-jnp references, plus the fused clip->aggregate server step.

On CPU the interpret-mode timings are NOT performance data (the kernels
target TPU); the derived column reports the HBM-traffic model instead:
bytes_touched / HBM_BW = the roofline floor the kernel is designed to hit.

Both the unmasked and the masked (partial-participation) variants are
timed — the engine only ever runs the masked shape, so that is the row
that matters.  Results are also written to ``BENCH_kernels.json`` so the
perf trajectory accumulates across PRs (see benchmarks/report.py).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregators import make_aggregator
from repro.kernels import (
    bucketed_coordinate_median,
    centered_clip,
    clip_then_aggregate,
    clip_then_centered_clip,
    clip_then_geometric_median,
    clip_then_krum,
    clipped_diff,
    coordinate_median,
    geometric_median,
    krum,
)
from repro.kernels.krum import row_tile
from repro.kernels.ref import (
    clip_then_aggregate_ref,
    clip_then_geometric_median_ref,
    clip_then_krum_ref,
    clipped_diff_ref,
    coordinate_median_ref,
    geometric_median_ref,
    krum_ref,
)

HBM_BW = 819e9  # bytes/s (TPU v5e)
ICI_BW = 90e9  # bytes/s per-chip interconnect (TPU v5e, ~2 usable links)
BENCH_JSON = "BENCH_kernels.json"

# the 8-fake-device robust_aggregate rows and the gated
# traffic_model_pipeline block share one problem size (W workers, d
# coordinates cut into PIPE_BLOCKS superleaf chunks) — a single source
# of truth so the modeled fused_bytes always corresponds to the
# measured robust_agg_pipelined row
PAIR_W = 4
PIPE_BLOCKS = 4


def _pair_d(quick: bool) -> int:
    return 1 << (12 if quick else 15)


def _time(fn, *args, iters=5):
    """Best-of-``iters`` wall time in us.  The min is the standard robust
    estimator for microbenchmarks: scheduler/GC interference only ever
    ADDS time, and the regression gate (check_regression.py) needs
    run-to-run stability far more than it needs the mean."""
    fn(*args)  # compile / warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best * 1e6


def _floor_us(num_bytes: float) -> float:
    return num_bytes / HBM_BW * 1e6


def traffic_model(n: int, d: int, itemsize: int = 4) -> dict:
    """Modeled HBM streams of the diff-round server step (clip at lambda
    then robust-aggregate the (n, d) message matrix).

    unfused: norm-reduction read + clip read/write (materializes the
    clipped matrix) + aggregation read, plus the (d,) output.
    fused:   two streaming passes over the matrix, plus the (d,) output.
    """
    nd = n * d * itemsize
    out = d * itemsize
    unfused = 4 * nd + out
    fused = 2 * nd + out
    return {
        "n": n,
        "d": d,
        "unfused_bytes": unfused,
        "fused_bytes": fused,
        "traffic_reduction": unfused / fused,
        "unfused_tpu_floor_us": _floor_us(unfused),
        "fused_tpu_floor_us": _floor_us(fused),
    }


def traffic_model_krum(n: int, d: int, itemsize: int = 4) -> dict:
    """Clip -> Krum / multi-Krum server step.  Unfused: norm read + clip
    read/write (materializing the clipped matrix) + Gram matmul read +
    winner-reconstruction read of the clipped matrix (multi-Krum's
    weighted row-sum / the bucketed winner gather) = 5 streams.  Fused:
    TWO streams — the Gram pass (clip factors and distances are (n, n)
    algebra on diag(G)) and the tile-wise winner row-sum pass that
    reconstructs any selection outcome in-register — plus the (d,)
    output."""
    nd = n * d * itemsize
    out = d * itemsize
    unfused = 5 * nd + out
    fused = 2 * nd + out
    return {
        "n": n, "d": d,
        "unfused_bytes": unfused, "fused_bytes": fused,
        "traffic_reduction": unfused / fused,
        "unfused_tpu_floor_us": _floor_us(unfused),
        "fused_tpu_floor_us": _floor_us(fused),
    }


def traffic_model_krum_apply(n: int, d: int, itemsize: int = 4) -> dict:
    """The Krum winner-reconstruction (apply) pass in isolation.

    full:   the tile-wise weighted row-sum streams ALL n rows — required
            for multi-Krum weights and bucketed winner means.
    onehot: plain (unbucketed) Krum's combination is one-hot, so the
            scalar-prefetch ``select_row`` kernel streams ONLY the sublane
            tile group holding the winner — ``row_tile`` rows (8 of f32,
            16 of bf16, or all n when n is smaller) instead of n, plus
            the (d,) output either way.
    """
    out = d * itemsize
    full = n * d * itemsize + out
    rows = row_tile(n, jnp.dtype(f"float{8 * itemsize}"))
    onehot = rows * d * itemsize + out
    return {
        "n": n, "d": d,
        "full_bytes": full,
        "fused_bytes": onehot,  # gated: losing the fast path grows this
        "traffic_reduction": full / onehot,
        "full_tpu_floor_us": _floor_us(full),
        "onehot_tpu_floor_us": _floor_us(onehot),
    }


def traffic_model_pipeline(n_blocks: int, chunk: int, W: int,
                           itemsize: int = 4,
                           rule_streams: int = 2) -> dict:
    """Modeled steady-state cost of the sharded server step's block loop
    (launch/train.py ``robust_aggregate``), per chip.

    Per uniform superleaf block of ``chunk`` coordinates: the all_to_all
    scatter + all_gather move ~2 * chunk * (W-1)/W words over the
    interconnect, and the fused clip->aggregate kernel streams the
    (W, chunk/W) block ``rule_streams`` times from HBM (2 for the
    CM/TM/Krum fused paths).

    sequential: every block pays comm + compute back to back —
                n_blocks * (comm + compute).
    pipelined:  the double-buffered schedule issues block i+1's scatter
                while block i's kernel runs: prologue comm + (n_blocks-1)
                * max(comm, compute) steady state + epilogue compute.
                Steady-state block cost ~ max(comm, compute) instead of
                comm + compute.
    """
    comm_bytes = 2.0 * chunk * (W - 1) / W * itemsize
    compute_bytes = float(rule_streams) * chunk * itemsize
    comm_us = comm_bytes / ICI_BW * 1e6
    compute_us = compute_bytes / HBM_BW * 1e6
    seq = n_blocks * (comm_us + compute_us)
    pipe = comm_us + (n_blocks - 1) * max(comm_us, compute_us) + compute_us
    return {
        "n_blocks": n_blocks, "chunk": chunk, "W": W,
        "comm_bytes_per_block": comm_bytes,
        "compute_bytes_per_block": compute_bytes,
        "fused_bytes": n_blocks * compute_bytes,  # gated: un-fusing grows it
        "comm_us_per_block": comm_us,
        "compute_us_per_block": compute_us,
        "sequential_block_us": comm_us + compute_us,
        "steady_state_block_us": max(comm_us, compute_us),
        "sequential_step_us": seq,
        "pipelined_step_us": pipe,
        "overlap_speedup": seq / pipe,
    }


def traffic_model_iterative(n: int, d: int, iters: int,
                            itemsize: int = 4) -> dict:
    """Clip -> {CenteredClip, Weiszfeld GM} server step.

    unfused: norm read + clip read/write + 2 reads per iteration (one
    for the row norms/distances, one for the re-weighted update).
    fused (VMEM-resident, the mesh-trainer shape): ONE stream — factors
    applied in-register, all iterations on the resident block.
    fused (coordinate-tiled, large d): the clip materialization is still
    saved but each round streams twice -> 2*iters streams.
    """
    nd = n * d * itemsize
    out = d * itemsize
    unfused = (3 + 2 * iters) * nd + out
    fused_resident = 1 * nd + out
    fused_tiled = 2 * iters * nd + out
    return {
        "n": n, "d": d, "iters": iters,
        "unfused_bytes": unfused,
        "fused_resident_bytes": fused_resident,
        "fused_tiled_bytes": fused_tiled,
        "traffic_reduction_resident": unfused / fused_resident,
        "traffic_reduction_tiled": unfused / fused_tiled,
        "unfused_tpu_floor_us": _floor_us(unfused),
        "fused_resident_tpu_floor_us": _floor_us(fused_resident),
        "fused_tiled_tpu_floor_us": _floor_us(fused_tiled),
    }


def run(quick: bool = False, out_json: str = BENCH_JSON):
    rows = []
    n, d = 16, 1 << (12 if quick else 16)
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask_np = np.zeros(n, bool)
    mask_np[: n // 4] = True  # 25% participation — the engine's C/n regime
    rng.shuffle(mask_np)
    mask = jnp.asarray(mask_np)

    # --- coordinate median: unmasked AND masked (the engine shape) ---------
    us_ref = _time(jax.jit(coordinate_median_ref), xs)
    us_ker = _time(coordinate_median, xs)
    floor_us = _floor_us(n * d * 4 + d * 4)
    rows.append(("kernel_cm_ref_jnp", us_ref, f"d={d}"))
    rows.append(("kernel_cm_pallas_interp", us_ker, f"tpu_floor_us={floor_us:.1f}"))
    us_ref = _time(jax.jit(coordinate_median_ref), xs, mask)
    us_ker = _time(coordinate_median, xs, mask)
    rows.append(("kernel_cm_masked_ref_jnp", us_ref, f"d={d};C={n // 4}"))
    rows.append(
        ("kernel_cm_masked_pallas_interp", us_ker, f"tpu_floor_us={floor_us:.1f}")
    )

    # --- worker-side clipped diff (masked RandK) ---------------------------
    g1 = jnp.asarray(rng.randn(d).astype(np.float32))
    g2 = jnp.asarray(rng.randn(d).astype(np.float32))
    km = jnp.asarray((rng.rand(d) > 0.5).astype(np.float32))
    us_ref = _time(jax.jit(lambda a, b, m: clipped_diff_ref(a, b, 1.0, m, 2.0)), g1, g2, km)
    us_ker = _time(lambda a, b, m: clipped_diff(a, b, 1.0, m, 2.0), g1, g2, km)
    floor_us = _floor_us(3 * d * 4)
    rows.append(("kernel_clipdiff_ref_jnp", us_ref, f"d={d}"))
    rows.append(
        ("kernel_clipdiff_pallas_interp", us_ker, f"tpu_floor_us={floor_us:.1f}")
    )

    # --- fused clip->aggregate (the diff-round server step) ----------------
    tm = traffic_model(n, d)
    lam = 1.5

    def unfused(x, m):
        out, _ = clip_then_aggregate_ref(x, lam, m)
        return out

    def fused(x, m):
        out, _ = clip_then_aggregate(x, lam, m)
        return out

    us_ref = _time(jax.jit(unfused), xs, mask)
    us_ker = _time(fused, xs, mask)
    rows.append(
        (
            "kernel_clipagg_unfused_jnp",
            us_ref,
            f"tpu_floor_us={tm['unfused_tpu_floor_us']:.1f}",
        )
    )
    rows.append(
        (
            "kernel_clipagg_fused_pallas_interp",
            us_ker,
            f"tpu_floor_us={tm['fused_tpu_floor_us']:.1f};"
            f"traffic_x{tm['traffic_reduction']:.2f}",
        )
    )

    # fused bucketed variant through the dispatch layer (mask-aware, the
    # exact path ByzVRMarinaPP.step takes with backend="pallas")
    agg = make_aggregator("cm", bucket_s=2, backend="pallas")
    key = jax.random.PRNGKey(0)

    @jax.jit
    def engine_step(x, m):
        return agg.clip_then_aggregate(x, lam, mask=m, key=key)

    us_eng = _time(engine_step, xs, mask)
    rows.append(
        (
            "kernel_clipagg_bucketed_pallas_interp",
            us_eng,
            f"tpu_floor_us={tm['fused_tpu_floor_us']:.1f}",
        )
    )

    # --- remaining kernels, so --smoke really covers every Pallas kernel --
    us_cc = _time(lambda x, m: centered_clip(x, m, tau=10.0, iters=5), xs, mask)
    rows.append(
        (
            "kernel_cclip_pallas_interp",
            us_cc,
            f"tpu_floor_us={_floor_us(5 * n * d * 4):.1f}",
        )
    )
    us_bcm = _time(
        lambda x, k, m: bucketed_coordinate_median(x, k, m, s=2), xs, key, mask
    )
    rows.append(
        (
            "kernel_bucketcm_pallas_interp",
            us_bcm,
            f"tpu_floor_us={_floor_us(n * d * 4 + d * 4):.1f}",
        )
    )

    # --- krum: MXU Gram kernel vs jnp, plus the 1-stream fused clip path --
    tmk = traffic_model_krum(n, d)
    us_ref = _time(jax.jit(lambda x, m: krum_ref(x, m, 1)), xs, mask)
    us_ker = _time(lambda x, m: krum(x, m, byz_bound=1), xs, mask)
    rows.append(("kernel_krum_ref_jnp", us_ref, f"d={d}"))
    rows.append(
        (
            "kernel_krum_pallas_interp",
            us_ker,
            f"tpu_floor_us={_floor_us(n * d * 4):.1f}",
        )
    )
    us_fk = _time(
        lambda x, m: clip_then_krum(x, lam, m, byz_bound=1)[0], xs, mask
    )
    rows.append(
        (
            "kernel_clipkrum_fused_pallas_interp",
            us_fk,
            f"tpu_floor_us={tmk['fused_tpu_floor_us']:.1f};"
            f"traffic_x{tmk['traffic_reduction']:.2f}",
        )
    )
    # multi-krum exercises the weighted-average winner reconstruction —
    # since PR 3 a tile-wise kernel pass, not a host full-matrix gather
    us_fmk = _time(
        lambda x, m: clip_then_krum(
            x, lam, m, byz_bound=1, m_select=3, multi=True
        )[0],
        xs, mask,
    )
    rows.append(
        (
            "kernel_clipmultikrum_fused_pallas_interp",
            us_fmk,
            f"tpu_floor_us={tmk['fused_tpu_floor_us']:.1f};"
            f"traffic_x{tmk['traffic_reduction']:.2f}",
        )
    )
    # the on-chip winner gather pass in isolation (one matrix stream);
    # jitted here — in production it is traced inside the fused pipeline
    from repro.kernels.ops import select_row, weighted_row_sum

    w_row = jnp.asarray(rng.rand(n).astype(np.float32))
    us_apply = _time(jax.jit(weighted_row_sum), xs, w_row)
    rows.append(
        (
            "kernel_krumapply_pallas_interp",
            us_apply,
            f"tpu_floor_us={_floor_us(n * d * 4 + d * 4):.1f}",
        )
    )
    # plain Krum's one-hot apply: the scalar-prefetch select_row kernel
    # streams only the sublane tile group holding the winner — row_tile*d
    # elements instead of n*d
    tma = traffic_model_krum_apply(n, d)
    us_onehot = _time(
        jax.jit(select_row), xs, jnp.int32(3), jnp.float32(0.5)
    )
    rows.append(
        (
            "kernel_krumapply_onehot_pallas_interp",
            us_onehot,
            f"tpu_floor_us={tma['onehot_tpu_floor_us']:.1f};"
            f"traffic_x{tma['traffic_reduction']:.2f}",
        )
    )

    # --- geometric median (Weiszfeld) + fused clip variants -----------------
    tmi = traffic_model_iterative(n, d, iters=8)
    us_ref = _time(jax.jit(lambda x, m: geometric_median_ref(x, 8, 1e-8, m)), xs, mask)
    us_ker = _time(lambda x, m: geometric_median(x, m, iters=8), xs, mask)
    rows.append(("kernel_gm_ref_jnp", us_ref, f"d={d};iters=8"))
    rows.append(
        (
            "kernel_gm_pallas_interp",
            us_ker,
            f"tpu_floor_us={tmi['fused_resident_tpu_floor_us']:.1f}",
        )
    )
    us_fgm = _time(
        lambda x, m: clip_then_geometric_median(x, lam, m, iters=8)[0], xs, mask
    )
    rows.append(
        (
            "kernel_clipgm_fused_pallas_interp",
            us_fgm,
            f"tpu_floor_us={tmi['fused_resident_tpu_floor_us']:.1f};"
            f"traffic_x{tmi['traffic_reduction_resident']:.2f}",
        )
    )

    # --- fused clip -> centered-clip (resident; the mesh-trainer shape) ----
    us_fcc = _time(
        lambda x, m: clip_then_centered_clip(x, lam, m, tau=10.0, iters=5)[0],
        xs, mask,
    )
    tmc = traffic_model_iterative(n, d, iters=5)
    rows.append(
        (
            "kernel_clipcclip_fused_pallas_interp",
            us_fcc,
            f"tpu_floor_us={tmc['fused_resident_tpu_floor_us']:.1f};"
            f"traffic_x{tmc['traffic_reduction_resident']:.2f}",
        )
    )

    # --- sharded vs naive robust_aggregate (multi-device subprocess) -------
    rows.extend(_sharded_pair_rows(quick))

    payload = {
        "rows": [
            {"name": r[0], "us_per_call": round(r[1], 1), "derived": r[2]}
            for r in rows
        ],
        "traffic_model": tm,
        "traffic_model_krum": tmk,
        "traffic_model_krum_apply": tma,
        "traffic_model_iterative": {"cclip5": tmc, "gm8": tmi},
        # the mesh trainer's block loop, at the exact problem size the
        # robust_agg_*_8dev subprocess rows measure
        "traffic_model_pipeline": traffic_model_pipeline(
            n_blocks=PIPE_BLOCKS, chunk=_pair_d(quick) // PIPE_BLOCKS,
            W=PAIR_W,
        ),
        "quick": quick,
    }
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


_SHARDED_PAIR_SCRIPT = r"""
import os, json, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import AggregatorSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

d = int(sys.argv[1])
mesh = make_debug_mesh(4, 2)
rng = np.random.RandomState(0)
tree = {"g": jnp.asarray(rng.randn(4, d).astype(np.float32))}
mask = jnp.asarray([True, True, False, True])
key = jax.random.PRNGKey(0)
rows = []

# the perf-gate rows are NAMED by canonical ServerPlan JSON and the
# configs rebuilt from it (to_json -> from_json -> ByzTrainConfig
# .from_plan), so every gate run exercises the public plan entry point
def plan_json(placement, blocks="sequential", sle=0):
    return ServerPlan(
        aggregate=AggregatorSpec("cm"),
        schedule=ScheduleSpec(placement=placement, blocks=blocks,
                              superleaf_elems=sle, backend="pallas"),
    ).to_json()

configs = [
    ("naive", plan_json("naive")),
    ("sharded", plan_json("sharded")),
    # the double-buffered schedule over uniform superleaf chunks — the
    # perf gate exercises the pipelined path on every PR
    ("pipelined", plan_json("sharded", "pipelined", d // 4)),
]
with jax.set_mesh(mesh):
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
    for sched, pj in configs:
        cfg = ByzTrainConfig.from_plan(ServerPlan.from_json(pj))
        fn = jax.jit(lambda t, m, k, cfg=cfg: robust_aggregate(
            t, m, k, mesh=mesh, cfg=cfg, radius=jnp.float32(1.5)))
        jax.block_until_ready(fn(tree, mask, key))  # compile
        t0 = time.time()
        for _ in range(5):
            jax.block_until_ready(fn(tree, mask, key))
        rows.append((sched, (time.time() - t0) / 5 * 1e6))
print("BENCH_JSON:" + json.dumps(rows))
"""


def _sharded_pair_rows(quick: bool):
    """Time the fused robust_aggregate under both collective schedules on
    an 8-fake-device mesh (subprocess: device count locks at jax init).
    Derived column: modeled per-chip collective bytes (W*shard naive vs
    2*shard sharded)."""
    import os
    import subprocess
    import sys

    d = _pair_d(quick)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run(
            [sys.executable, "-c", _SHARDED_PAIR_SCRIPT, str(d)],
            capture_output=True, text=True, timeout=600, env=env,
        )
        line = next(
            l for l in r.stdout.splitlines() if l.startswith("BENCH_JSON:")
        )
        pairs = json.loads(line[len("BENCH_JSON:"):])
    except Exception:  # noqa: BLE001 — benchmark row, not a test
        # emit the CANONICAL row names with 0.0 so check_regression sees
        # the rows vanish (o > 0, n <= 0 fails the gate) instead of a
        # silently-skipped rename
        return [
            (f"robust_agg_{sched}_fused_8dev", 0.0, "SKIP(subprocess failed)")
            for sched in ("naive", "sharded", "pipelined")
        ]
    W, shard = PAIR_W, d // 8
    coll = {
        "naive": W * shard * 4,
        "sharded": 2 * shard * 4,
        "pipelined": 2 * shard * 4,
    }
    tmp = traffic_model_pipeline(n_blocks=PIPE_BLOCKS,
                                 chunk=d // PIPE_BLOCKS, W=W)
    derived = {
        sched: f"W=4;d={d};coll_bytes_per_chip={coll[sched]}"
        for sched in coll
    }
    # the pipelined row carries the modeled overlap: steady-state block
    # cost max(comm, compute) vs the sequential comm + compute
    derived["pipelined"] += (
        f";model_seq_us={tmp['sequential_step_us']:.2f}"
        f";model_pipe_us={tmp['pipelined_step_us']:.2f}"
        f";model_overlap_x{tmp['overlap_speedup']:.2f}"
    )
    return [
        (f"robust_agg_{sched}_fused_8dev", us, derived[sched])
        for sched, us in pairs
    ]
