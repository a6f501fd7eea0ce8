"""Distributed Byz-VR-MARINA-PP trainer for the production mesh.

Mapping: worker == (pod, data) mesh index, so a worker's data shard and
its gradient live on the same chips and no batch moves between workers;
per-worker variance-reduced gradients are computed with ``jax.vmap(..,
spmd_axis_name=worker_axes)`` (so XLA pins the worker dim to the data axes
and never replicates it), then clipped/compressed messages are robustly
aggregated ACROSS the worker axes by the trainer's ``ServerPlan`` — the
declarative clip -> compress -> bucket -> aggregate -> schedule
composition of :mod:`repro.api`.  ``plan.build(mesh)`` compiles the plan
into the mesh ``ServerStep``; the collective schedules themselves
(naive / sharded placement, sequential / pipelined double-buffered block
order, superleaf packing, whole-tree two-phase selection) live in
:mod:`repro.api.mesh_exec` and are documented there.

``ByzTrainConfig`` carries the trainer-side knobs (stepsize, cohort,
attack, sharding mode) plus the ``plan=ServerPlan(...)`` aggregation
composition; ``plan=None`` builds the sharded coordinate-median default
(``resolve_plan``).  The old string knobs (``aggregator``, ``backend``,
``agg_schedule``, ...) are gone — construct a ``ServerPlan`` (see the
README migration table).

``robust_aggregate`` remains the long-standing functional entry point and
now simply runs ``plan.build(mesh)`` on the config's resolved plan.

The step names its parts with ``jax.named_scope`` (``round_full``,
``round_diff``, ``worker_grads``, ``compress``, ``attack``, ``clip_norm``,
``aggregate``, ``update``): metadata in the compiled program's ``op_name``,
which a profile reads; nothing runs for them.  A state whose ``stats`` is
a ``TrainStats`` (``init_train_stats()``) also counts the work each step
did; ``stats=None`` compiles the step without the counters.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import (
    AggregatorSpec,
    ClipSpec,
    PlanError,
    ScheduleSpec,
    ServerPlan,
)
from repro.api.mesh_exec import leaf_agg_of
from repro.core.tree_utils import tree_norm
from repro.models.model import ModelConfig, apply_train, init_params
from repro.sharding import constraints as cons
from repro.sharding.rules import batch_specs, param_specs, state_sharding
from .mesh import num_workers, worker_axes

__all__ = [
    "ByzTrainConfig",
    "MeshTrainState",
    "TrainStats",
    "init_train_stats",
    "make_train_step",
    "robust_aggregate",
    "abstract_state",
    "resolve_plan",
]

F32 = jnp.float32
_BIG = F32(3.4e37)


@dataclasses.dataclass(frozen=True)
class ByzTrainConfig:
    gamma: float = 3e-4
    p: float = 0.125  # Bernoulli full-grad probability
    n_byz: int = 0  # trailing workers are byzantine
    C: int = 0  # sampled cohort size (0 => all workers)
    # THE aggregation composition: a repro.api.ServerPlan.  None builds
    # the sharded-placement coordinate-median default with
    # lambda = 2.0 * ||x+ - x|| clipping and byz_bound = n_byz
    # (``resolve_plan``).
    plan: Optional[ServerPlan] = None
    attack: str = "bf"  # "none" | "bf" | "gauss"
    shard_mode: str = "tp"  # "tp" | "fsdp_tp" | "zero3"
    # Workers normally enumerate over every batch-like mesh axis
    # (pod x data).  For FSDP-scale models on the multi-pod mesh, set
    # ("pod",) so each pod is ONE worker and "data" stays free for FSDP —
    # per-worker gradients then shard over data x model and fit HBM.  The
    # step holds W per-worker gradient trees (2W on a difference round),
    # each as large as the parameters: with one worker per data index and
    # the model split over "model" alone, a large model's gradient shards
    # outgrow a chip's HBM.
    worker_axes_override: tuple = ()
    # Number of workers.  0 => one per device of the worker axes.  More
    # workers than devices (a multiple of them) share devices through the
    # vmap over the worker axis; that needs the naive placement.
    n_workers: int = 0
    seed: int = 0

    @classmethod
    def from_plan(cls, plan: ServerPlan, **overrides) -> "ByzTrainConfig":
        """Config with ``plan`` as the aggregation composition.  The plan
        is the source of truth for every aggregation stage; trainer-owned
        knobs (``gamma``, ``p``, ``n_byz``, ``attack``, ``shard_mode``,
        and ``C``/``worker_axes_override`` when the plan leaves
        cohort/worker_axes unset) come from overrides."""
        return cls(plan=plan, **overrides)


def resolve_plan(cfg: ByzTrainConfig) -> ServerPlan:
    """The config's ServerPlan: explicit ``cfg.plan``, or the default
    trainer composition — coordinate-wise median on the sharded placement,
    clipping at lambda = 2.0 * ||x+ - x||."""
    if cfg.plan is not None:
        return cfg.plan
    return ServerPlan(
        aggregate=AggregatorSpec("cm", trim_ratio=0.25, byz_bound=cfg.n_byz),
        clip=ClipSpec(alpha=2.0),
        schedule=ScheduleSpec(
            placement="sharded",
            worker_axes=tuple(cfg.worker_axes_override),
        ),
        cohort=cfg.C or None,
    )


class TrainStats(NamedTuple):
    """Cumulative int32 counts of the work the steps did."""
    rounds_full: jnp.ndarray  # full-gradient rounds
    rows_sampled: jnp.ndarray  # cohort rows, all rounds
    rounds_byzantine_only: jnp.ndarray  # difference rounds, no honest row
    rows_clipped: jnp.ndarray  # sampled rows whose clip factor is below 1
    worker_evals: jnp.ndarray  # per-worker gradients evaluated


def init_train_stats() -> TrainStats:
    return TrainStats(*(jnp.zeros((), jnp.int32) for _ in TrainStats._fields))


class MeshTrainState(NamedTuple):
    params: object  # x^k
    g: object  # g^k (gradient-shaped)
    key: jax.Array
    step: jnp.ndarray
    stats: Optional[TrainStats] = None  # None: the step counts nothing


# ---------------------------------------------------------------------------
# aggregation entry points (back-compat wrappers over the ServerPlan API)
# ---------------------------------------------------------------------------

def _make_leaf_agg(cfg: ByzTrainConfig):
    """Per-chip aggregation over the worker axis for ONE leaf, resolved
    from the config's plan — the single-leaf semantics used by direct
    callers and tests (the mesh step itself routes selection rules through
    the whole-tree two-phase path; see repro.api.mesh_exec)."""
    return leaf_agg_of(resolve_plan(cfg).build_aggregator())


def robust_aggregate(tree_w, mask, key, *, mesh, cfg: ByzTrainConfig,
                     base_specs=None, radius=None):
    """Aggregate a worker-stacked pytree (leaves (W, ...)) into the
    aggregated pytree (leaves (...)) under the config's resolved
    ServerPlan — equivalent to ``resolve_plan(cfg).build(mesh)(...)``.

    ``radius``: when set, every worker message is l2-clipped at ``radius``
    by its *global* tree norm before aggregation (the Algorithm-1 server
    re-clip fused into the per-chip kernels).  ``base_specs``: the
    unstacked grad PartitionSpecs (see ``repro.api.mesh_exec``)."""
    step = resolve_plan(cfg).build(mesh)
    return step(tree_w, mask=mask, key=key, radius=radius,
                base_specs=base_specs)


# ---------------------------------------------------------------------------
# worker-side messages
# ---------------------------------------------------------------------------

def _leafwise_randk(key, tree, frac):
    """Unbiased leafwise RandK (keep ceil(frac*size) coords, scale 1/frac)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        d = leaf.size
        kk = max(1, int(frac * d))
        scores = jax.random.uniform(k, (d,))
        thresh = jax.lax.top_k(scores, kk)[0][-1]
        mask = (scores >= thresh).reshape(leaf.shape)
        out.append(leaf * mask.astype(leaf.dtype) * jnp.asarray(d / kk, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _attack_stage(cfg: ByzTrainConfig):
    """The worker-stacked attack stage (repro.scenarios.TreeAttackStage)
    for the config's attack — the full registry (bf/sf/lf/alie/ipm/gauss)
    runs leafwise at mesh scale; ``cfg.attack`` may be a registry name or
    a pre-built ``repro.core.attacks.Attack`` (e.g. from a ScenarioSpec).
    Iterate-reading (shb) and adaptive attacks are simulation-engine
    features and rejected here with a pointed error."""
    from repro.scenarios.stage import TreeAttackStage

    stage = TreeAttackStage(cfg.attack)
    if stage.attack.needs_iterates:
        raise PlanError(
            f"attack {stage.attack.name!r} reads the iterates (x0, x_now); "
            "the mesh trainer does not track x0 — pick a message-level "
            "attack (bf/sf/lf/alie/ipm/gauss) or run shb through the "
            "simulation engines (repro.core)"
        )
    return stage


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

def make_train_step(model_cfg: ModelConfig, mesh, cfg: ByzTrainConfig):
    """Build the jittable train_step for the mesh.

    The aggregation composition is the config's resolved ServerPlan,
    compiled once via ``plan.build(mesh)``; the plan also supplies the
    clip stage (lambda = alpha * gamma * ||g||) and the compression
    fraction, so the trainer contains no aggregation wiring of its own.
    """
    plan = resolve_plan(cfg)
    attack_stage = _attack_stage(cfg)
    # cohort and worker axes are trainer-owned knobs when the plan leaves
    # them unset; an explicit plan.cohort / plan.schedule.worker_axes wins
    waxes = (tuple(plan.schedule.worker_axes)
             or tuple(cfg.worker_axes_override) or worker_axes(mesh))
    slots = 1
    for a in waxes:
        slots *= mesh.shape[a]
    W = cfg.n_workers or slots
    if W % slots:
        raise PlanError(
            f"n_workers={W} must be a multiple of the {slots} devices on "
            f"the worker axes {waxes}"
        )
    server = plan.build(mesh, n_workers=W)
    C = plan.cohort or cfg.C or W
    spmd = waxes if len(waxes) > 1 else (waxes[0] if waxes else None)

    compress_frac = 0.0
    if plan.compress is not None:
        if plan.compress.kind != "rand_fraction":
            raise PlanError(
                "the mesh trainer's worker-side compression is leafwise "
                "RandK by fraction; use CompressSpec(kind='rand_fraction', "
                f"frac=...), got kind={plan.compress.kind!r}"
            )
        compress_frac = plan.compress.frac

    def loss_fn(params, wbatch):
        loss, _aux = apply_train(params, model_cfg, wbatch)
        return loss

    def per_worker_grads(params, wbatches):
        """(the W per-worker gradients, how many were evaluated)."""
        gfn = lambda b: jax.grad(loss_fn)(params, b)
        with jax.named_scope("worker_grads"):
            if spmd is None:
                return jax.vmap(gfn)(wbatches), W
            ctx = (
                cons.override_data_axes(("model",))
                if cfg.shard_mode == "zero3"
                else cons.override_data_axes(("pod", "data"))
            )
            with cons.suspend_data_axis(waxes), ctx:
                return jax.vmap(gfn, spmd_axis_name=spmd)(wbatches), W

    pspecs_cache = {}

    def base_specs_of(tree_w):
        """Unstacked grad PartitionSpecs (worker axes stripped)."""
        grad_constraint(tree_w)  # ensure cache is built
        stripped = jax.tree_util.tree_map(
            lambda sp: P(*sp[1:]), pspecs_cache["g"],
            is_leaf=lambda x: isinstance(x, P),
        )
        return stripped

    def grad_constraint(tree_w):
        """Pin worker dim to the worker axes; param dims per TP rules."""
        if not waxes:
            return tree_w
        key = "g"
        if key not in pspecs_cache:
            shapes = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tree_w
            )
            base = param_specs(mesh, model_cfg, shapes, mode=cfg.shard_mode)
            wspec = waxes if len(waxes) > 1 else waxes[0]

            def _with_worker(spec):
                # the worker dim consumes ``waxes``; drop them from the
                # per-param dims (a mesh axis may appear only once)
                def strip(entry):
                    if entry is None:
                        return None
                    if isinstance(entry, (tuple, list)):
                        kept = tuple(a for a in entry if a not in waxes)
                        return kept if len(kept) > 1 else (kept[0] if kept else None)
                    return None if entry in waxes else entry

                return P(wspec, *(strip(e) for e in spec))

            pspecs_cache[key] = jax.tree_util.tree_map(
                _with_worker, base, is_leaf=lambda x: isinstance(x, P),
            )
        return jax.lax.with_sharding_constraint(
            tree_w,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspecs_cache[key],
                is_leaf=lambda x: isinstance(x, P),
            ),
        )

    def train_step(state: MeshTrainState, batch):
        key, k_bern, k_cohort, k_q, k_att, k_agg = jax.random.split(state.key, 6)
        c_k = jax.random.bernoulli(k_bern, cfg.p)
        counting = state.stats is not None

        # x^{k+1} = x^k - gamma g^k ; lambda = alpha ||x+ - x|| = alpha*gamma*||g||
        with jax.named_scope("update"):
            params_new = jax.tree_util.tree_map(
                lambda x, g: (x - cfg.gamma * g.astype(F32)).astype(x.dtype),
                state.params,
                state.g,
            )
        if server.clips and plan.clip.radius is not None:
            lam = jnp.float32(plan.clip.radius)
        else:
            alpha = plan.clip.alpha if server.clips else 0.0
            with jax.named_scope("clip_norm"):
                lam = alpha * cfg.gamma * tree_norm(state.g)

        # cohort mask over workers; byz mask static
        perm = jax.random.permutation(k_cohort, W)
        rank = jnp.zeros((W,), jnp.int32).at[perm].set(jnp.arange(W, dtype=jnp.int32))
        size = jnp.where(c_k, W, C)  # full cohort on full-grad rounds
        sampled = rank < size
        byz = jnp.arange(W) >= (W - cfg.n_byz)

        # reshape batch to per-worker leading dim
        wbatch = jax.tree_util.tree_map(
            lambda l: l.reshape((W, l.shape[0] // W) + l.shape[1:]), batch
        )

        grads_new, evals_new = per_worker_grads(params_new, wbatch)
        grads_new = grad_constraint(grads_new)

        def diff_branch(_):
            with jax.named_scope("round_diff"):
                grads_old, evals_old = per_worker_grads(state.params, wbatch)
                grads_old = grad_constraint(grads_old)

                def compress(i, d_i):
                    if compress_frac > 0.0:
                        d_i = _leafwise_randk(
                            jax.random.fold_in(k_q, i), d_i, compress_frac
                        )
                    return d_i

                with jax.named_scope("compress"):
                    diff = jax.tree_util.tree_map(
                        lambda a, b: a - b, grads_new, grads_old
                    )
                    honest = jax.vmap(compress, in_axes=(0, 0))(
                        jnp.arange(W), diff)
                # the in-graph omniscient attack stage: byzantine rows see
                # the sampled honest messages of THIS round (ALIE/IPM
                # statistics computed per leaf == per coordinate of the
                # full message)
                with jax.named_scope("attack"):
                    msgs = attack_stage.corrupt_tree(
                        honest, good_mask=~byz, sampled=sampled, key=k_att
                    )
                msgs = grad_constraint(msgs)
                # server-side clip (Alg.1 l.10) fused into the aggregation:
                # one batched norm pass + factors applied in-register by
                # the per-chip clip_then_aggregate, never materializing the
                # clipped message tree
                agg = server(msgs, mask=sampled, key=k_agg,
                             base_specs=base_specs_of(msgs),
                             radius=lam if server.clips else None,
                             with_factors=counting)
                if counting:
                    agg, factors = agg
                with jax.named_scope("update"):
                    g_new = jax.tree_util.tree_map(
                        lambda g, a: (g.astype(F32)
                                      + a.astype(F32)).astype(g.dtype),
                        state.g,
                        agg,
                    )
                if not counting:
                    return g_new
                # the factors the fused clip already computed
                clipped = jnp.sum(sampled & (factors < 1.0), dtype=jnp.int32)
                return g_new, clipped, jnp.int32(evals_old)

        def full_branch(_):
            with jax.named_scope("round_full"):
                with jax.named_scope("attack"):
                    msgs = attack_stage.corrupt_tree(
                        grads_new, good_mask=~byz, sampled=sampled, key=k_att
                    )
                msgs = grad_constraint(msgs)
                # full-gradient rounds aggregate RAW gradients (Alg. 1): no
                # clip even under a static-radius plan
                g_new = server.aggregate(msgs, mask=sampled, key=k_agg,
                                         base_specs=base_specs_of(msgs))
                if not counting:
                    return g_new
                return g_new, jnp.int32(0), jnp.int32(0)

        out = jax.lax.cond(c_k, full_branch, diff_branch, operand=None)
        stats = None
        if counting:
            g_new, clipped, evals_old = out
            s = state.stats
            byz_only = ~c_k & ~jnp.any(sampled & ~byz)
            stats = TrainStats(
                rounds_full=s.rounds_full + c_k.astype(jnp.int32),
                rows_sampled=s.rows_sampled
                + jnp.sum(sampled, dtype=jnp.int32),
                rounds_byzantine_only=s.rounds_byzantine_only
                + byz_only.astype(jnp.int32),
                rows_clipped=s.rows_clipped + clipped,
                worker_evals=s.worker_evals + evals_new + evals_old,
            )
        else:
            g_new = out
        return MeshTrainState(
            params=params_new, g=g_new, key=key, step=state.step + 1,
            stats=stats,
        )

    return train_step


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------

def abstract_state(model_cfg: ModelConfig, cfg: ByzTrainConfig):
    """ShapeDtypeStruct state (no allocation) for dry-run lowering."""
    pshapes = jax.eval_shape(partial(init_params, cfg=model_cfg), jax.random.PRNGKey(0))
    g = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), pshapes
    )
    return MeshTrainState(
        params=pshapes,
        g=g,
        key=jax.ShapeDtypeStruct((2,), jnp.uint32),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )


def state_specs(mesh, model_cfg: ModelConfig, state, cfg: ByzTrainConfig):
    ps = param_specs(mesh, model_cfg, state.params, mode=cfg.shard_mode)
    return MeshTrainState(
        params=ps,
        g=jax.tree_util.tree_map(lambda s: s, ps, is_leaf=lambda x: isinstance(x, P)),
        key=P(),
        step=P(),
    )


# ---------------------------------------------------------------------------
# CLI launcher:  python -m repro.launch.train --arch minitron-8b --smoke ...
# ---------------------------------------------------------------------------

def main():
    import argparse
    import time

    from repro.configs.registry import get_config, get_smoke_config
    from repro.data.pipeline import make_batch_iterator
    from .cache import enable_compile_cache
    from .cli import (add_attack_args, add_plan_args, plan_from_args,
                      scenario_from_args)
    from .mesh import make_local_mesh

    ap = argparse.ArgumentParser(description="Byz-VR-MARINA-PP mesh trainer")
    ap.add_argument("--arch", default="minitron_8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced f32 config (CPU-runnable)")
    ap.add_argument("--workers", type=int, default=0,
                    help="number of workers (0: one per device of the worker "
                         "axis; more than that needs --agg-schedule naive)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.1)
    ap.add_argument("--n-byz", type=int, default=1)
    ap.add_argument("--shard-mode", default="tp")
    ap.add_argument("--ckpt-dir", default="")
    add_plan_args(ap)  # --aggregator/--agg-schedule/--schedule/... (shared)
    add_attack_args(ap, attack="bf")  # --attack/--byz-frac/--z-max (shared)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        model_cfg = get_smoke_config(args.arch).replace(dtype="float32", remat=False)
    else:
        model_cfg = get_config(args.arch)
    mesh = make_local_mesh()

    W = args.workers or num_workers(mesh)
    scenario = scenario_from_args(args)
    n_byz = scenario.n_byz(W) if scenario.byz_frac is not None else args.n_byz
    plan = plan_from_args(args, byz_bound=n_byz, clip_alpha=2.0)
    tc = ByzTrainConfig.from_plan(
        plan, gamma=args.gamma, n_byz=n_byz, attack=scenario.build(),
        shard_mode=args.shard_mode, n_workers=W,
    )
    print(f"[train] {model_cfg.name} on mesh {dict(mesh.shape)} "
          f"({W} workers, {tc.n_byz} byzantine, "
          f"agg={plan.aggregate.rule})")
    step_fn = make_train_step(model_cfg, mesh, tc)
    it = make_batch_iterator(model_cfg, W * args.per_worker_batch, args.seq)
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), model_cfg)
        batch0 = next(it)
        g0 = jax.grad(lambda p: apply_train(p, model_cfg, batch0)[0])(params)
        state = MeshTrainState(params=params, g=g0,
                               key=jax.random.PRNGKey(1), step=jnp.int32(0))
        jstep = jax.jit(step_fn)
        eval_loss = jax.jit(lambda p, b: apply_train(p, model_cfg, b)[0])
        t0 = time.time()
        for k in range(args.steps):
            state = jstep(state, next(it))
            if k % 10 == 0 or k == args.steps - 1:
                print(f"[train] step {k:4d} loss "
                      f"{float(eval_loss(state.params, batch0)):.4f} "
                      f"({(time.time()-t0)/(k+1):.2f}s/step)")
    if args.ckpt_dir:
        from repro.checkpoint import save

        print("[train] checkpoint:", save(args.ckpt_dir, args.steps, state.params))


if __name__ == "__main__":
    main()
