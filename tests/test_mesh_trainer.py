"""Distributed-trainer tests.

Device count locks at first jax init, so multi-device tests run in
subprocesses with XLA_FLAGS set.  In-process tests cover the worker-axis
aggregation semantics on a single device (naive schedule).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr_utils import iter_eqns_outside_kernels as _iter_eqns_outside_kernels
from repro.api import AggregatorSpec, BucketSpec, ScheduleSpec, ServerPlan
from repro.launch.train import ByzTrainConfig, _make_leaf_agg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(
    os.environ,
    PYTHONPATH=os.path.join(REPO, "src"),
    REPRO_XLA_FLAGS="--xla_force_host_platform_device_count=8",
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
)


def _mk_cfg(name, *, placement="naive", blocks="sequential", backend="jnp",
            superleaf_elems=0, n_byz=0, trim_ratio=0.25, bucket_s=0):
    """Plan-based config builder; a ``bucket_<rule>`` name is shorthand
    for ``rule`` + BucketSpec(2) (the registry lists below keep the
    historical spellings for readability)."""
    if name.startswith("bucket_"):
        name, bucket_s = name[len("bucket_"):], bucket_s or 2
    plan = ServerPlan(
        aggregate=AggregatorSpec(name, trim_ratio=trim_ratio,
                                 byz_bound=n_byz),
        bucket=BucketSpec(s=bucket_s) if bucket_s else None,
        schedule=ScheduleSpec(placement=placement, blocks=blocks,
                              superleaf_elems=superleaf_elems,
                              backend=backend),
    )
    return ByzTrainConfig.from_plan(plan, n_byz=n_byz)


# ---------------------------------------------------------------------------
# leaf-aggregation semantics (in process) — _make_leaf_agg routes through
# the core dispatch layer, so these pin the mesh-trainer-visible behavior
# ---------------------------------------------------------------------------

def _leaf_agg(name, backend="jnp", **cfg_kw):
    return _make_leaf_agg(_mk_cfg(name, backend=backend, **cfg_kw))


def test_leaf_agg_cm_matches_numpy_any_rank():
    rng = np.random.RandomState(0)
    leaf = rng.randn(9, 3, 4).astype(np.float32)
    mask = np.array([1, 1, 0, 1, 0, 1, 1, 0, 1], bool)
    out = _leaf_agg("cm")(
        jnp.asarray(leaf), jnp.asarray(mask), jax.random.PRNGKey(0)
    )
    assert out.shape == (3, 4)
    np.testing.assert_allclose(np.asarray(out), np.median(leaf[mask], axis=0), atol=1e-6)


def test_leaf_agg_tm_subset():
    rng = np.random.RandomState(1)
    leaf = rng.randn(10, 5).astype(np.float32)
    mask = np.ones(10, bool)
    out = _leaf_agg("tm", trim_ratio=0.2)(
        jnp.asarray(leaf), jnp.asarray(mask), jax.random.PRNGKey(0)
    )
    s = np.sort(leaf, axis=0)
    expected = s[2:8].mean(axis=0)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_leaf_agg_mean():
    leaf = jnp.arange(12.0).reshape(4, 3)
    mask = jnp.asarray([True, False, True, False])
    out = _leaf_agg("mean")(leaf, mask, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray((leaf[0] + leaf[2]) / 2))


def test_leaf_agg_full_registry_backends_agree():
    """Every mesh aggregator name resolves on both backends and agrees,
    with and without precomputed clip factors (the fused server step)."""
    rng = np.random.RandomState(2)
    leaf = jnp.asarray(rng.randn(8, 3, 5).astype(np.float32))
    mask = jnp.asarray([1, 1, 1, 0, 1, 1, 0, 1], bool)
    key = jax.random.PRNGKey(7)
    factors = jnp.asarray(rng.rand(8).astype(np.float32))
    for name in ("cm", "tm", "mean", "cclip", "rfa", "krum", "multi_krum",
                 "bucket_cm", "bucket_krum", "bucket_rfa"):
        aj = _leaf_agg(name, backend="jnp", n_byz=1)
        ap = _leaf_agg(name, backend="pallas", n_byz=1)
        np.testing.assert_allclose(
            np.asarray(aj(leaf, mask, key)), np.asarray(ap(leaf, mask, key)),
            atol=2e-5, err_msg=name,
        )
        np.testing.assert_allclose(
            np.asarray(aj(leaf, mask, key, factors=factors)),
            np.asarray(ap(leaf, mask, key, factors=factors)),
            atol=2e-5, err_msg=f"{name} factors",
        )


def test_leaf_agg_bucketed_cm_resists_outlier_minority():
    rng = np.random.RandomState(3)
    good = rng.randn(10, 4).astype(np.float32)
    byz = 1e6 * np.ones((2, 4), np.float32)
    leaf = jnp.asarray(np.concatenate([good, byz]))
    out = _leaf_agg("bucket_cm", bucket_s=2)(
        leaf, jnp.ones(12, bool), jax.random.PRNGKey(1)
    )
    assert np.abs(np.asarray(out)).max() < 10.0


# ---------------------------------------------------------------------------
# multi-device subprocess tests
# ---------------------------------------------------------------------------

def _run(cmd, timeout=540):
    return subprocess.run(
        cmd, env=ENV, cwd=REPO, capture_output=True, text=True, timeout=timeout
    )


@pytest.mark.slow
def test_distributed_trainer_example_runs_and_learns():
    # 6 steps is not enough on this jax's RNG stream (the byzantine-attacked
    # loss wobbles up before descending; it is below the start by step ~40
    # and deterministic given the fixed seeds), so give it 80.
    r = _run([sys.executable, "examples/train_marina_pp.py", "--steps", "80", "--smoke"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dryrun_smoke_single_and_multipod_mesh():
    # single-"pod" debug mesh
    r = _run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke", "--arch",
         "deepseek_7b", "--shape", "train_4k", "--mesh", "4x2",
         "--out-dir", "/tmp/test_dryrun"]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "all combinations lowered and compiled OK" in r.stdout
    # multi-pod debug mesh (pod=2, data=2, model=2)
    r = _run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke", "--arch",
         "jamba_v01_52b", "--shape", "decode_32k", "--mesh", "2x2x2",
         "--out-dir", "/tmp/test_dryrun"]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "all combinations lowered and compiled OK" in r.stdout


@pytest.mark.slow
def test_sharded_vs_naive_aggregation_equivalence():
    """The beyond-paper all_to_all schedule must produce aggregates equal
    to the paper-faithful naive schedule (multi-device) — for EVERY
    registry rule, on both backends, with and without the fused server
    clip.  Non-coordinate-wise rules rely on the cross-shard psum of row
    statistics threaded through ``reduce_fn``."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import AggregatorSpec, BucketSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

def mk_cfg(agg, sched, backend, inner="sequential", sle=0):
    rule, s = (agg[7:], 2) if agg.startswith("bucket_") else (agg, 0)
    plan = ServerPlan(
        aggregate=AggregatorSpec(rule, byz_bound=1),
        bucket=BucketSpec(s=s) if s else None,
        schedule=ScheduleSpec(placement=sched, blocks=inner,
                              superleaf_elems=sle, backend=backend))
    return ByzTrainConfig.from_plan(plan, n_byz=1)

mesh = make_debug_mesh(4, 2)
rng = np.random.RandomState(0)
tree = {
    "a": jnp.asarray(rng.randn(4, 6, 32).astype(np.float32)),
    "b": {"c": jnp.asarray(rng.randn(4, 17).astype(np.float32))},
}
mask = jnp.asarray([True, True, False, True])
key = jax.random.PRNGKey(0)
with jax.set_mesh(mesh):
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
    for agg in ("cm", "tm", "mean", "cclip", "rfa", "krum", "multi_krum",
                "bucket_cm", "bucket_krum"):
        for radius in (jnp.float32(3.0), None):
            outs = {}
            for backend in ("jnp", "pallas"):
                for sched in ("naive", "sharded"):
                    cfg = mk_cfg(agg, sched, backend)
                    outs[(backend, sched)] = jax.jit(
                        lambda t, m, k: robust_aggregate(
                            t, m, k, mesh=mesh, cfg=cfg, radius=radius)
                    )(tree, mask, key)
            ref = outs[("jnp", "naive")]
            for which, v in outs.items():
                for la, lb in zip(jax.tree_util.tree_leaves(ref),
                                  jax.tree_util.tree_leaves(v)):
                    np.testing.assert_allclose(
                        np.asarray(la), np.asarray(lb), atol=3e-5,
                        err_msg=f"{agg} clip={radius is not None} {which}")
print("EQUIV_OK")
"""
    r = _run([sys.executable, "-c", script])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EQUIV_OK" in r.stdout


def test_naive_placement_keeps_grad_sharding():
    """On a (data, model) mesh the naive placement gathers the worker dim
    only: each chip aggregates all W rows of its own model-axis shard
    (per-chip block (W, 6, 16) of a (W, 6, 32) leaf), the result keeps
    the grad sharding, and the row statistics of the non-coordinate-wise
    rules are psum'd over the model axis — equal to the replicated
    naive result and to the sharded placement."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import AggregatorSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

mesh = make_debug_mesh(2, 2)
rng = np.random.RandomState(0)
tree = {"a": jnp.asarray(rng.randn(2, 6, 32).astype(np.float32)),
        "b": jnp.asarray(rng.randn(2, 17).astype(np.float32))}
specs = {"a": P(None, "model"), "b": P()}
mask = jnp.ones((2,), bool)
key = jax.random.PRNGKey(0)

def cfg(agg, sched, sle):
    return ByzTrainConfig.from_plan(ServerPlan(
        aggregate=AggregatorSpec(agg),
        schedule=ScheduleSpec(placement=sched, backend="pallas",
                              superleaf_elems=sle)))

def agg_fn(c, bs):
    return jax.jit(lambda t, m, k: robust_aggregate(
        t, m, k, mesh=mesh, cfg=c, radius=jnp.float32(3.0), base_specs=bs))

with jax.set_mesh(mesh):
    t = {k: jax.device_put(v, NamedSharding(mesh, P("data", *specs[k])))
         for k, v in tree.items()}
    for agg in ("cm", "krum", "cclip"):
        for sle in (0, 64):
            got = agg_fn(cfg(agg, "naive", sle), specs)(t, mask, key)
            assert got["a"].sharding.spec == P(None, "model"), got["a"].sharding
            for other in (agg_fn(cfg(agg, "naive", sle), None)(t, mask, key),
                          agg_fn(cfg(agg, "sharded", sle), specs)(t, mask, key)):
                for k in tree:
                    np.testing.assert_allclose(
                        np.asarray(other[k]), np.asarray(got[k]), atol=3e-5,
                        err_msg=f"{agg} superleaf={sle} {k}")
    jp = jax.make_jaxpr(lambda t, m, k: robust_aggregate(
        t, m, k, mesh=mesh, cfg=cfg("cm", "naive", 0), base_specs=specs)
    )(t, mask, key)
    (sm,) = [e for e in jp.jaxpr.eqns if e.primitive.name == "shard_map"]
    shapes = [v.aval.shape for v in sm.params["jaxpr"].invars]
    assert shapes[:2] == [(2, 6, 16), (2, 17)], shapes
print("NAIVE_SHARD_OK")
"""
    r = _run([sys.executable, "-c", script])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NAIVE_SHARD_OK" in r.stdout


@pytest.mark.slow
def test_whole_tree_mesh_krum_matches_engine_whole_message_bitwise():
    """Algorithm 1 applies the robust aggregator to the WHOLE message.
    The sharded mesh schedule must therefore select ONE whole-tree
    krum/multi-Krum winner: iterating the server recursion g += Agg(msgs)
    on an 8-device mesh must reproduce the engine-style whole-message
    aggregation (Aggregator on the raveled tree) with BITWISE-equal
    trajectory traces, on both backends, with and without the fused
    server clip — and the jaxpr must never materialize the stacked
    (W, d_total) message."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.aggregators import make_aggregator
from repro.core.clipping import clip_factor
from repro.core.tree_utils import tree_norm
from repro.api import AggregatorSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

def mk_cfg(agg, backend):
    plan = ServerPlan(
        aggregate=AggregatorSpec(agg, byz_bound=1),
        schedule=ScheduleSpec(placement="sharded", backend=backend))
    return ByzTrainConfig.from_plan(plan, n_byz=1)

mesh = make_debug_mesh(4, 2)
W = 4
rng = np.random.RandomState(0)
base = {
    "a": jnp.asarray(rng.randn(W, 6, 32).astype(np.float32)),
    "b": {"c": jnp.asarray(rng.randn(W, 17).astype(np.float32))},
}
d_total = 6 * 32 + 17
mask = jnp.asarray([True, True, False, True])
key = jax.random.PRNGKey(0)
byz = jnp.arange(W) == 1  # a sampled byzantine sending -3x

@jax.jit
def messages(g, k):
    # deterministic worker messages depending on the running estimate so
    # a single selection mismatch compounds through the whole trace
    honest = jax.tree_util.tree_map(
        lambda b, gg: b + 0.3 * gg[None].astype(np.float32), base, g)
    return jax.tree_util.tree_map(
        lambda h: jnp.where(
            byz.reshape((-1,) + (1,) * (h.ndim - 1)), -3.0 * h, h),
        honest)

@jax.jit
def gfactors(msgs):
    # same global per-worker tree-norm clip factors the mesh path
    # computes (single source of truth with robust_aggregate)
    return clip_factor(
        jax.vmap(tree_norm)(msgs), jnp.float32(2.5)
    ).astype(jnp.float32)

# The aggregation operators are jitted in isolation and the (shared)
# g += agg recursion runs op-by-op: the claim under test is that the
# sharded whole-tree aggregation IS the whole-message operator, and
# jitting whole divergent step programs would let XLA contract the
# winner-scale multiply into the update add (an fma) differently per
# program — a 1-ulp artifact of the test harness, not of the operator.
for backend in ("jnp", "pallas"):
    for agg_name in ("krum", "multi_krum"):
        for clip in (True, False):
            cfg = mk_cfg(agg_name, backend)
            eng = make_aggregator(agg_name, backend=backend, byz_bound=1)
            radius = jnp.float32(2.5) if clip else None
            jmesh = jax.jit(lambda t, m, k: robust_aggregate(
                t, m, k, mesh=mesh, cfg=cfg, radius=radius))
            if clip:
                jeng = jax.jit(lambda t, m, k, f: eng.clip_then_aggregate(
                    t, jnp.float32(2.5), mask=m, key=k, factors=f))
            else:
                jeng = jax.jit(lambda t, m, k, f: eng(t, mask=m, key=k))

            g1 = jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape[1:]),
                                        base)
            g2 = g1
            tr1, tr2 = [], []
            with jax.set_mesh(mesh):
                for t in range(8):
                    k = jax.random.fold_in(key, t)
                    m1, m2 = messages(g1, k), messages(g2, k)
                    a1 = jmesh(m1, mask, k)
                    a2 = jeng(m2, mask, k, gfactors(m2))
                    g1 = jax.tree_util.tree_map(lambda a, b: a + b, g1, a1)
                    g2 = jax.tree_util.tree_map(lambda a, b: a + b, g2, a2)
                    for g, tr in ((g1, tr1), (g2, tr2)):
                        tr.append(np.concatenate([
                            np.asarray(l).ravel()
                            for l in jax.tree_util.tree_leaves(g)]))
            assert np.array_equal(np.stack(tr1), np.stack(tr2)), (
                backend, agg_name, clip,
                np.abs(np.stack(tr1) - np.stack(tr2)).max())
            print("BITWISE", backend, agg_name, "clip" if clip else "plain")

# the sharded whole-tree path must never build the stacked message
cfg = mk_cfg("krum", "pallas")
with jax.set_mesh(mesh):
    jaxpr = jax.make_jaxpr(
        lambda t, m, k: robust_aggregate(t, m, k, mesh=mesh, cfg=cfg,
                                         radius=jnp.float32(2.5))
    )(base, mask, key)
bad = [str(v.aval) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
       if getattr(v.aval, "shape", None) == (W, d_total)]
assert not bad, f"stacked (W, d_total) message materialized: {bad}"
print("NO_STACKED_BUFFER")
print("WHOLE_TREE_OK")
"""
    r = _run([sys.executable, "-c", script], timeout=540)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "WHOLE_TREE_OK" in r.stdout
    assert "NO_STACKED_BUFFER" in r.stdout
    assert r.stdout.count("BITWISE") == 8  # 2 backends x 2 rules x 2 clip


@pytest.mark.slow
def test_pipelined_schedule_registry_bitwise_8dev():
    """Acceptance gate for the double-buffered server step: on the
    8-device mesh the pipelined schedule must be BITWISE-equal to the
    sequential oracle for the WHOLE aggregator registry — it emits the
    same per-block ops, only the collective issue order differs — both
    over ragged per-leaf blocks and packed superleaf chunks."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import AggregatorSpec, BucketSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

def mk_cfg(agg, sched, sle):
    rule, s = (agg[7:], 2) if agg.startswith("bucket_") else (agg, 0)
    plan = ServerPlan(
        aggregate=AggregatorSpec(rule, byz_bound=1),
        bucket=BucketSpec(s=s) if s else None,
        schedule=ScheduleSpec(placement="sharded", blocks=sched,
                              superleaf_elems=sle, backend="pallas"))
    return ByzTrainConfig.from_plan(plan, n_byz=1)

mesh = make_debug_mesh(4, 2)
rng = np.random.RandomState(0)
tree = {
    "a": jnp.asarray(rng.randn(4, 6, 32).astype(np.float32)),
    "b": {"c": jnp.asarray(rng.randn(4, 17).astype(np.float32))},
}
mask = jnp.asarray([True, True, False, True])
key = jax.random.PRNGKey(0)
radius = jnp.float32(3.0)
with jax.set_mesh(mesh):
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
    for agg in ("cm", "tm", "mean", "cclip", "rfa", "krum", "multi_krum",
                "bucket_cm", "bucket_krum", "bucket_rfa"):
        for sle in (0, 24):
            outs = {}
            for sched in ("sequential", "pipelined"):
                cfg = mk_cfg(agg, sched, sle)
                outs[sched] = jax.jit(
                    lambda t, m, k: robust_aggregate(
                        t, m, k, mesh=mesh, cfg=cfg, radius=radius)
                )(tree, mask, key)
            for la, lb in zip(jax.tree_util.tree_leaves(outs["sequential"]),
                              jax.tree_util.tree_leaves(outs["pipelined"])):
                assert np.array_equal(np.asarray(la), np.asarray(lb)), (
                    agg, sle)
        print("BITWISE", agg, flush=True)
print("PIPELINE_REGISTRY_OK")
"""
    r = _run([sys.executable, "-c", script], timeout=540)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "PIPELINE_REGISTRY_OK" in r.stdout
    assert r.stdout.count("BITWISE") == 10


@pytest.mark.slow
def test_trajectory_naive_sharded_pipelined_krum_cclip_8dev():
    """Multi-step server recursion g += Agg(msgs(g)) on the 8-device
    mesh: the sharded-sequential and pipelined schedules must produce
    BITWISE-equal trajectories (selection and iteration rules alike),
    and both must track the paper-faithful naive schedule."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate

mesh = make_debug_mesh(4, 2)
W = 4
rng = np.random.RandomState(0)
base = {
    "a": jnp.asarray(rng.randn(W, 6, 32).astype(np.float32)),
    "b": {"c": jnp.asarray(rng.randn(W, 17).astype(np.float32))},
}
mask = jnp.asarray([True, True, False, True])
key = jax.random.PRNGKey(0)
byz = jnp.arange(W) == 1

@jax.jit
def messages(g, k):
    honest = jax.tree_util.tree_map(
        lambda b, gg: b + 0.3 * gg[None].astype(np.float32), base, g)
    return jax.tree_util.tree_map(
        lambda h: jnp.where(
            byz.reshape((-1,) + (1,) * (h.ndim - 1)), -3.0 * h, h),
        honest)

from repro.api import AggregatorSpec, ScheduleSpec, ServerPlan

for agg in ("krum", "centered_clip"):
    name = {"centered_clip": "cclip"}.get(agg, agg)
    traces = {}
    for sched, inner in (("naive", "sequential"),
                         ("sharded", "sequential"),
                         ("sharded", "pipelined")):
        plan = ServerPlan(
            aggregate=AggregatorSpec(name, byz_bound=1),
            schedule=ScheduleSpec(placement=sched, blocks=inner,
                                  backend="pallas"))
        cfg = ByzTrainConfig.from_plan(plan, n_byz=1)
        jagg = jax.jit(lambda t, m, k: robust_aggregate(
            t, m, k, mesh=mesh, cfg=cfg, radius=jnp.float32(2.5)))
        g = jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape[1:]), base)
        tr = []
        with jax.set_mesh(mesh):
            for t in range(6):
                k = jax.random.fold_in(key, t)
                a = jagg(messages(g, k), mask, k)
                g = jax.tree_util.tree_map(lambda x, y: x + y, g, a)
                tr.append(np.concatenate([
                    np.asarray(l).ravel()
                    for l in jax.tree_util.tree_leaves(g)]))
        traces[(sched, inner)] = np.stack(tr)
    assert np.array_equal(traces[("sharded", "sequential")],
                          traces[("sharded", "pipelined")]), name
    np.testing.assert_allclose(
        traces[("naive", "sequential")], traces[("sharded", "sequential")],
        atol=3e-5, err_msg=name)
    print("TRAJ_OK", name, flush=True)
print("TRAJECTORY_OK")
"""
    r = _run([sys.executable, "-c", script], timeout=540)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "TRAJECTORY_OK" in r.stdout
    assert r.stdout.count("TRAJ_OK") == 2


def test_whole_tree_selection_in_process_naive_matches_engine():
    """Single-device fast check of the same contract: the naive schedule's
    whole-tree two-phase path equals the engine's whole-message krum on a
    multi-leaf tree, bitwise, both backends (the sharded variant is the
    slow subprocess test above)."""
    from repro.core.aggregators import make_aggregator
    from repro.core.clipping import clip_factor
    from repro.core.tree_utils import tree_norm
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import robust_aggregate

    mesh = make_debug_mesh(1, 1)
    rng = np.random.RandomState(7)
    tree = {
        "a": jnp.asarray(rng.randn(6, 3, 8).astype(np.float32)),
        "b": {"c": jnp.asarray(rng.randn(6, 17).astype(np.float32))},
    }
    mask = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    key = jax.random.PRNGKey(0)
    radius = jnp.float32(2.0)
    factors = clip_factor(jax.vmap(tree_norm)(tree), radius).astype(
        jnp.float32
    )
    with jax.set_mesh(mesh):
        for backend in ("jnp", "pallas"):
            for name in ("krum", "multi_krum", "bucket_krum"):
                cfg = _mk_cfg(name, placement="naive", backend=backend,
                              n_byz=1)
                got = robust_aggregate(
                    tree, mask, key, mesh=mesh, cfg=cfg, radius=radius
                )
                eng = make_aggregator(
                    name.replace("bucket_", ""),
                    bucket_s=2 if name.startswith("bucket_") else 0,
                    backend=backend, byz_bound=1,
                )
                want = eng.clip_then_aggregate(
                    tree, radius, mask=mask, key=key, factors=factors
                )
                for la, lb in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want),
                ):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb),
                        err_msg=f"{backend} {name}",
                    )


def test_sharded_fused_path_jaxpr_no_standalone_clipped_matrix():
    """With backend="pallas" the sharded schedule's server clip must run
    INSIDE the fused clip_then_aggregate kernel: the jaxpr contains the
    fused kernel launch and no elementwise multiply materializing the
    clipped (W, chunk) message block outside a kernel."""
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import robust_aggregate

    mesh = make_debug_mesh(1, 1)  # single-device mesh: tracing only
    rng = np.random.RandomState(0)
    tree = {"a": jnp.asarray(rng.randn(1, 8, 64).astype(np.float32))}
    mask = jnp.ones((1,), bool)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        cfg = _mk_cfg("cm", placement="sharded", backend="pallas")
        jaxpr = jax.make_jaxpr(
            lambda t, m, k: robust_aggregate(
                t, m, k, mesh=mesh, cfg=cfg, radius=jnp.float32(2.0)
            )
        )(tree, mask, key)
    text = str(jaxpr)
    # the fused kernel is launched ...
    assert "pallas_call" in text
    assert "name=clip_aggregate" in text
    # ... and no multiply outside a kernel produces the (W, chunk) clipped
    # message block (W = 1 worker, chunk = the full 8*64 flat block here)
    w, chunk = 1, 8 * 64
    bad = [
        eqn
        for eqn in _iter_eqns_outside_kernels(jaxpr.jaxpr)
        if eqn.primitive.name == "mul"
        and any(
            getattr(v.aval, "shape", None) == (w, chunk)
            for v in eqn.outvars
        )
    ]
    assert not bad, f"clipped matrix materialized outside kernel: {bad}"


def test_several_workers_share_one_device():
    """W = 4 workers on a one-device mesh (the one-chip trainer): the
    naive placement vmaps the per-worker gradients over the W rows.
    With only full-gradient rounds and no clip, g^{k+1} is the
    coordinate median of the four per-worker gradients, each the
    gradient of the loss on its quarter of the batch at x^{k+1}; the
    trainer's robust composition (cm + alpha = 2 clip, one bit-flip
    worker) runs a few steps and stays finite."""
    from repro.api import ClipSpec
    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import make_batch_iterator
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import MeshTrainState, make_train_step
    from repro.models import apply_train, init_params

    cfg = get_smoke_config("mamba2_780m").replace(dtype="float32",
                                                  remat=False)
    mesh = make_debug_mesh(1, 1)
    W = 4
    it = make_batch_iterator(cfg, W * 2, 64, seed=0)
    batch = next(it)

    def loss(p, b):
        return apply_train(p, cfg, b)[0]

    cm_plan = ServerPlan(
        aggregate=AggregatorSpec("cm"),
        schedule=ScheduleSpec(placement="naive", backend="pallas"),
    )
    robust_plan = ServerPlan(
        aggregate=AggregatorSpec("cm", byz_bound=1),
        clip=ClipSpec(alpha=2.0),
        schedule=ScheduleSpec(placement="naive", backend="pallas"),
    )
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        g0 = jax.grad(loss)(params, batch)
        state0 = MeshTrainState(params=params, g=g0,
                                key=jax.random.PRNGKey(1), step=jnp.int32(0))

        tc = ByzTrainConfig.from_plan(cm_plan, gamma=0.1, p=1.0, n_byz=0,
                                      attack="none", n_workers=W)
        out = jax.jit(make_train_step(cfg, mesh, tc))(state0, batch)
        quarters = [
            jax.tree_util.tree_map(lambda l: l[2 * i:2 * i + 2], batch)
            for i in range(W)
        ]
        per_worker = [jax.grad(loss)(out.params, q) for q in quarters]
        want = jax.tree_util.tree_map(
            lambda *g: np.median(np.stack(g), axis=0), *per_worker
        )
        for a, b in zip(jax.tree_util.tree_leaves(out.g),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), b,
                                       rtol=1e-4, atol=1e-6)

        tc = ByzTrainConfig.from_plan(robust_plan, gamma=0.1, p=0.5,
                                      n_byz=1, attack="bf", n_workers=W)
        step = jax.jit(make_train_step(cfg, mesh, tc))
        state = state0
        for _ in range(3):
            state = step(state, next(it))
        assert all(bool(jnp.isfinite(l).all())
                   for l in jax.tree_util.tree_leaves(state))
        assert np.isfinite(float(loss(state.params, batch)))


def test_worker_count_plan_errors():
    """More workers than devices needs the naive placement, and the
    workers must fill the devices evenly; both refusals are PlanErrors
    raised when the step is built, before anything is traced."""
    from repro.api import PlanError
    from repro.configs.registry import get_smoke_config
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import make_train_step

    cfg = get_smoke_config("mamba2_780m")
    # the default plan is the sharded placement: one worker per device
    with pytest.raises(PlanError, match="one worker per device"):
        make_train_step(cfg, make_debug_mesh(1, 1),
                        ByzTrainConfig(n_workers=4))
    naive = ServerPlan(aggregate=AggregatorSpec("cm"),
                       schedule=ScheduleSpec(placement="naive"))
    two_slots = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
    with pytest.raises(PlanError, match="must be a multiple of the 2"):
        make_train_step(cfg, two_slots,
                        ByzTrainConfig.from_plan(naive, n_workers=3))


def test_train_cfg_validation():
    from repro.launch.train import resolve_plan

    # the default plan is the documented sharded coordinate-median
    plan = resolve_plan(ByzTrainConfig())
    assert plan.schedule.placement == "sharded"
    assert plan.aggregate.rule == "cm"
    # bad rules fail at SPEC construction, before any config exists
    with pytest.raises(ValueError, match="unknown aggregator"):
        AggregatorSpec("nope")


def test_cclip_leaf_agg_matches_core():
    import numpy as np

    from repro.core.aggregators import centered_clip as core_cclip

    rng = np.random.RandomState(11)
    leaf = jnp.asarray(rng.randn(8, 3, 5).astype(np.float32))
    mask = jnp.asarray([1, 1, 1, 0, 1, 1, 0, 1], bool)
    out = _leaf_agg("cclip")(leaf, mask, jax.random.PRNGKey(0))
    ref = core_cclip(tau=10.0, iters=5)(
        jnp.reshape(leaf, (8, -1)), mask=mask
    ).reshape(3, 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_mesh_trainer_robustness_end_to_end():
    """On the 8-device mesh with 1/4 byzantine worker sending 10x gaussian
    noise, CM aggregation keeps training; plain mean is disrupted."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import AggregatorSpec, ScheduleSpec, ServerPlan
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, MeshTrainState, make_train_step
from repro.models import ModelConfig, apply_train, init_params
from repro.data.pipeline import make_batch_iterator

cfg = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab=256, remat=False, dtype="float32")
mesh = make_debug_mesh(4, 2)
finals = {}
for agg in ("cm", "mean"):
    if agg == "cm":
        # the default plan: sharded CM with the alpha=2.0 server clip
        tc = ByzTrainConfig(gamma=0.3, n_byz=1, attack="gauss", p=0.125)
    else:
        plan = ServerPlan(aggregate=AggregatorSpec("mean"),
                          schedule=ScheduleSpec(placement="naive"))
        tc = ByzTrainConfig.from_plan(plan, gamma=0.3, n_byz=1,
                                      attack="gauss", p=0.125)
    step = make_train_step(cfg, mesh, tc)
    it = make_batch_iterator(cfg, 8, 64, seed=3)
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch0 = next(it)
        g0 = jax.grad(lambda p: apply_train(p, cfg, batch0)[0])(params)
        state = MeshTrainState(params=params, g=g0, key=jax.random.PRNGKey(1),
                               step=jnp.int32(0))
        jstep = jax.jit(step)
        for _ in range(25):
            state = jstep(state, next(it))
        finals[agg] = float(apply_train(state.params, cfg, batch0)[0])
print("FINALS", finals)
assert finals["cm"] < 5.6, finals   # robust agg learns (init ~ ln 256 = 5.55)
assert finals["cm"] < finals["mean"] - 0.05, finals  # and beats plain mean
print("ROBUST_OK")
"""
    r = _run([sys.executable, "-c", script], timeout=540)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    assert "ROBUST_OK" in r.stdout
