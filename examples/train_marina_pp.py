import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ^ before any jax import: on the CPU this demo runs the REAL distributed
#   trainer on 8 faked devices — mesh (data=4, model=2): 4 workers, one
#   byzantine.  On accelerators the flag does nothing and the mesh is built
#   from the devices present.

"""End-to-end driver: train a ~100M-parameter transformer with
Byz-VR-MARINA-PP on the distributed mesh trainer for a few hundred steps.

This exercises the FULL production path: the same make_train_step /
sharding rules / robust-aggregation collective schedule that the 256-chip
dry-run lowers — on a mesh over the devices present (2-way tensor
parallel from 8 devices up, the rest workers), with one bit-flipping
byzantine worker, trained on the synthetic token pipeline.

    PYTHONPATH=src python examples/train_marina_pp.py --steps 200
    PYTHONPATH=src python examples/train_marina_pp.py --steps 8 --smoke
    # one chip: four workers share it (naive placement)
    PYTHONPATH=src python examples/train_marina_pp.py --workers 4 \
        --agg-schedule naive
"""
import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save
from repro.data.pipeline import make_batch_iterator
from repro.launch.cli import add_plan_args, plan_from_args
from repro.launch.mesh import make_local_mesh, num_workers
from repro.launch.train import (
    ByzTrainConfig,
    MeshTrainState,
    make_train_step,
    state_specs,
)
from repro.models import ModelConfig, apply_train, init_params, param_count
from repro.sharding.rules import batch_specs


def build_config(smoke: bool) -> ModelConfig:
    if smoke:
        return ModelConfig(
            name="tiny", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
            d_ff=256, vocab=512, remat=False, dtype="float32",
        )
    # ~100M params: 12L, d=640, vocab 32k
    return ModelConfig(
        name="repro-100m", n_layers=12, d_model=640, n_heads=10,
        n_kv_heads=2, d_ff=2048, vocab=32000, head_dim=64, remat=False,
        dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--per-worker-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-byz", type=int, default=1)
    ap.add_argument("--workers", type=int, default=0,
                    help="number of workers (0: one per device of the worker "
                         "axis; more than that needs --agg-schedule naive)")
    ap.add_argument("--ckpt-dir", default="")
    # The full server-step composition comes from the shared ServerPlan
    # flag group (repro.launch.cli): --aggregator/--agg-schedule/
    # --schedule/--superleaf-elems/--backend/--plan-json.  "pallas" on
    # CPU runs in interpret mode — same math, what the equivalence tests
    # use; the sharded placement then runs the fused clip->aggregate
    # kernel on each chip's (W, d/W) block.
    add_plan_args(ap)
    args = ap.parse_args()

    cfg = build_config(args.smoke)
    mesh = make_local_mesh(model=2 if len(jax.devices()) >= 8 else 1)
    W = args.workers or num_workers(mesh)
    print(f"model {cfg.name}: {param_count(cfg)/1e6:.1f}M params; "
          f"{W} workers ({args.n_byz} byzantine), mesh {dict(mesh.shape)}")

    plan = plan_from_args(args, byz_bound=args.n_byz, clip_alpha=2.0)
    tc = ByzTrainConfig.from_plan(
        plan,
        gamma=0.3 if args.smoke else 0.1,
        p=0.125,
        n_byz=args.n_byz,
        attack="bf",
        n_workers=W,
    )
    step_fn = make_train_step(cfg, mesh, tc)

    it = make_batch_iterator(cfg, W * args.per_worker_batch, args.seq)
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch0 = next(it)
        g0 = jax.grad(lambda p: apply_train(p, cfg, batch0)[0])(params)
        state = MeshTrainState(
            params=params, g=g0, key=jax.random.PRNGKey(1), step=jnp.int32(0)
        )
        sspecs = state_specs(mesh, cfg, state, tc)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), sspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        state = jax.device_put(state, shardings)
        jstep = jax.jit(step_fn)
        eval_loss = jax.jit(lambda p, b: apply_train(p, cfg, b)[0])

        losses = []
        t0 = time.time()
        for k in range(args.steps):
            state = jstep(state, next(it))
            if k % 10 == 0 or k == args.steps - 1:
                loss = float(eval_loss(state.params, batch0))
                losses.append(loss)
                print(f"step {k:4d}  loss {loss:.4f}  "
                      f"({(time.time()-t0)/(k+1):.2f}s/step)")
        assert losses[-1] < losses[0], "training must reduce the loss"
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, state.params)
        print("checkpoint:", path)
    print("OK")


if __name__ == "__main__":
    main()
