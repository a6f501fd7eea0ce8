"""The train step's name scopes and work counters.

The scopes are metadata: every scope the benchmark reads reaches the
compiled program's ``op_name``, through vmap, grad and the round's
``lax.cond`` branches.  The counters (``TrainStats``) are off unless the
state carries them, change no number of the step, and count what a host
recount from the step's key gives: the Bernoulli round draw, the cohort's
rank rule, the Byzantine-only difference rounds, the clipped rows and the
per-worker gradient evaluations."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AggregatorSpec, ClipSpec, ScheduleSpec, ServerPlan
from repro.configs.registry import get_smoke_config
from repro.data.pipeline import make_batch_iterator
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import (ByzTrainConfig, MeshTrainState, TrainStats,
                                init_train_stats, make_train_step)
from repro.models import apply_train, init_params

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import scoped  # noqa: E402

W, C, N_BYZ, P = 4, 1, 2, 0.5
STEPS = 6
KEY = 4  # its first six rounds: full, honest and Byzantine-only difference


def _config(clip):
    plan = ServerPlan(
        aggregate=AggregatorSpec("cm", byz_bound=N_BYZ),
        clip=clip,
        schedule=ScheduleSpec(placement="naive", backend="pallas"),
    )
    return ByzTrainConfig.from_plan(plan, gamma=0.05, p=P, n_byz=N_BYZ, C=C,
                                    attack="bf", n_workers=W)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mamba2_780m").replace(dtype="float32",
                                                  remat=False)
    mesh = make_debug_mesh(1, 1)
    it = make_batch_iterator(cfg, W, 32, seed=0)
    batches = [next(it) for _ in range(STEPS)]
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        g0 = jax.grad(lambda p, b: apply_train(p, cfg, b)[0])(params,
                                                               batches[0])
    state0 = MeshTrainState(params=params, g=g0,
                            key=jax.random.PRNGKey(KEY), step=jnp.int32(0))
    return cfg, mesh, batches, state0


def _run(setup, clip, stats):
    cfg, mesh, batches, state = setup
    state = state._replace(stats=stats)
    with jax.set_mesh(mesh):
        step = jax.jit(make_train_step(cfg, mesh, _config(clip)))
        for b in batches:
            state = step(state, b)
    return state


def recount(key, steps, p, workers, cohort, n_byz):
    """The counters' values after ``steps`` rounds from ``key``, each
    round drawn as the step draws it; rows_clipped as if every sampled
    row of a difference round were clipped."""
    byz = np.arange(workers) >= workers - n_byz
    n = dict.fromkeys(TrainStats._fields, 0)
    for _ in range(steps):
        key, k_bern, k_cohort, _, _, _ = jax.random.split(key, 6)
        full = bool(jax.random.bernoulli(k_bern, p))
        perm = np.asarray(jax.random.permutation(k_cohort, workers))
        rank = np.empty(workers, np.int64)
        rank[perm] = np.arange(workers)
        sampled = rank < (workers if full else cohort)
        n["rounds_full"] += full
        n["rows_sampled"] += int(sampled.sum())
        n["rounds_byzantine_only"] += (not full) and not (sampled
                                                          & ~byz).any()
        n["rows_clipped"] += 0 if full else int(sampled.sum())
        n["worker_evals"] += workers * (1 if full else 2)
    return n


def test_every_benchmark_scope_reaches_the_compiled_op_names(setup):
    cfg, mesh, batches, state = setup
    state = state._replace(stats=init_train_stats())
    with jax.set_mesh(mesh):
        text = jax.jit(make_train_step(
            cfg, mesh, _config(ClipSpec(alpha=2.0)))).lower(
                state, batches[0]).compile().as_text()
    scopes = scoped.scope_layers()
    seen = set()
    backward = False
    for path in scoped.op_paths(text).values():
        seen |= scoped.scope_of(path, scopes)[1]
        backward |= "worker_grads" in path and scoped.BACKWARD in path
    assert seen == set(scopes)
    assert backward  # the gradient's ops keep the scope under transpose(


def test_counters_off_by_default_and_change_no_number(setup):
    clip = ClipSpec(alpha=2.0)
    off = _run(setup, clip, None)
    on = _run(setup, clip, init_train_stats())
    assert off.stats is None
    assert isinstance(on.stats, TrainStats)
    for a, b in zip(jax.tree_util.tree_leaves((off.params, off.g)),
                    jax.tree_util.tree_leaves((on.params, on.g))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("clip,clips", [
    (ClipSpec(alpha=1e6), False),  # a radius no row reaches
    (ClipSpec(radius=1e-6), True),  # a static radius every row exceeds
], ids=["never_clips", "static_radius_clips"])
def test_counters_equal_the_host_recount(setup, clip, clips):
    want = recount(jax.random.PRNGKey(KEY), STEPS, P, W, C, N_BYZ)
    # the rounds hold every kind the counters tell apart
    assert 0 < want["rounds_full"] < STEPS
    assert want["rounds_byzantine_only"] > 0
    assert want["rounds_full"] + want["rounds_byzantine_only"] < STEPS
    if not clips:
        want["rows_clipped"] = 0
    got = _run(setup, clip, init_train_stats()).stats
    assert {k: int(v) for k, v in got._asdict().items()} == want
    assert (got.rows_clipped > 0) == clips
