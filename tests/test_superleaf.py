"""Superleaf packing + pipelined-schedule tests.

``tree_superleaf_pack`` re-cuts a ragged worker-stacked pytree into
uniform (n, chunk_elems) chunks — the block layout the double-buffered
``robust_aggregate`` schedule runs on.  These tests pin:

- the pack -> unpack round trip is the identity (ragged shapes, stacked
  0-d scalars, dtype mix, grouping);
- packed aggregation is BITWISE-identical to the per-leaf path for the
  coordinate-wise and selection rules on both backends (per-coordinate
  math is partition-independent; the whole-tree Gram is additive over
  any partition);
- the pipelined schedule is bitwise-identical to the sequential oracle
  (same per-block ops, only the issue order differs) for the whole
  registry — in-process on a 1-device mesh here; the >= 8-device mesh
  variant lives in tests/test_mesh_trainer.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    AggregatorSpec,
    BucketSpec,
    PlanError,
    ScheduleSpec,
    ServerPlan,
)
from repro.core.tree_utils import tree_superleaf_pack
from repro.launch.mesh import make_debug_mesh
from repro.launch.train import ByzTrainConfig, robust_aggregate


def _cfg(rule, *, bucket_s=0, placement="naive", blocks="sequential",
         superleaf_elems=0, backend="auto", n_byz=0):
    plan = ServerPlan(
        aggregate=AggregatorSpec(rule, byz_bound=n_byz),
        bucket=BucketSpec(s=bucket_s) if bucket_s else None,
        schedule=ScheduleSpec(placement=placement, blocks=blocks,
                              superleaf_elems=superleaf_elems,
                              backend=backend),
    )
    return ByzTrainConfig.from_plan(plan, n_byz=n_byz)

# ragged on purpose: odd widths, a stacked 0-d scalar, a dtype mix
N = 6


def _ragged_tree(n=N, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(n, 3, 5).astype(np.float32)),
        "scalar": jnp.asarray(rng.randn(n).astype(np.float32)),  # 0-d param
        "nested": {
            "b16": jnp.asarray(rng.randn(n, 17), jnp.bfloat16),
            "odd": jnp.asarray(rng.randn(n, 2, 1, 3).astype(np.float32)),
        },
    }


def test_pack_unpack_roundtrip_is_identity():
    tree = _ragged_tree()
    for chunk in (1, 7, 16, 1000):
        chunks, groups, unpack = tree_superleaf_pack(tree, chunk)
        assert all(c.shape == (N, chunk) for c in chunks)
        assert len(groups) == len(chunks)
        # aggregate == "take worker 2's row": unpack must reproduce
        # worker 2's subtree bitwise, dtypes restored
        got = unpack([c[2] for c in chunks])
        want = jax.tree_util.tree_map(lambda l: l[2], tree)
        assert (
            jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want)
        )
        for la, lb in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            assert la.dtype == lb.dtype
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_pack_handles_size_zero_leaf_alone_in_its_group():
    """A size-0 leaf alone in its (group, dtype) bucket packs to ZERO
    chunks; unpack must reconstruct it as an empty array instead of
    concatenating an empty row list."""
    tree = {
        "a": jnp.ones((4, 3), jnp.float32),
        "empty": jnp.zeros((4, 0), jnp.bfloat16),  # own dtype bucket
    }
    chunks, _, unpack = tree_superleaf_pack(tree, 8)
    assert len(chunks) == 1  # only the f32 group produced a chunk
    got = unpack([c[0] for c in chunks])
    assert got["empty"].shape == (0,) and got["empty"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got["a"]), np.ones(3))


def test_pack_grouping_separates_groups():
    tree = {"a": jnp.ones((4, 10)), "b": jnp.zeros((4, 3)),
            "c": 2.0 * jnp.ones((4, 5))}
    # flatten order a, b, c; a and c share a group
    chunks, groups, unpack = tree_superleaf_pack(
        tree, 8, group_ids=["g0", "g1", "g0"]
    )
    # g0: 15 cols -> 2 chunks; g1: 3 cols -> 1 chunk
    assert groups == ["g0", "g0", "g1"]
    # no chunk mixes values from different groups
    g1 = np.asarray(chunks[2])
    assert np.all(g1[:, :3] == 0.0) and np.all(g1[:, 3:] == 0.0)
    got = unpack([c[0] for c in chunks])
    np.testing.assert_array_equal(np.asarray(got["c"]), 2.0 * np.ones(5))


def test_pack_validation_errors():
    tree = _ragged_tree()
    with pytest.raises(ValueError):
        tree_superleaf_pack({}, 8)
    with pytest.raises(ValueError):
        tree_superleaf_pack(tree, 0)
    with pytest.raises(ValueError):
        tree_superleaf_pack(tree, 8, group_ids=["only-one"])
    with pytest.raises(ValueError):
        tree_superleaf_pack(
            {"a": jnp.ones((3, 2)), "b": jnp.ones((4, 2))}, 8
        )
    chunks, _, unpack = tree_superleaf_pack(tree, 8)
    with pytest.raises(ValueError):
        unpack([c[0] for c in chunks[:-1]])


# ---------------------------------------------------------------------------
# packed aggregation == per-leaf aggregation (naive path, both backends)
# ---------------------------------------------------------------------------

_EXACT_RULES = (("cm", 0), ("tm", 0), ("mean", 0), ("krum", 0),
                ("multi_krum", 0), ("cm", 2), ("krum", 2))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_packed_naive_aggregate_bitwise_equals_per_leaf(backend):
    """Coordinate-wise rules are partition-independent per coordinate and
    selection rules make ONE whole-tree decision from the (additive)
    Gram, so superleaf packing must not change a single bit of their
    naive-path output — including through the fused server clip and the
    dtype mix (bf16 leaves aggregate through the same f32 math either
    way)."""
    tree = _ragged_tree()
    mask = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    key = jax.random.PRNGKey(3)
    mesh = make_debug_mesh(1, 1)
    with jax.set_mesh(mesh):
        for name, bucket_s in _EXACT_RULES:
            for radius in (jnp.float32(2.0), None):
                outs = {}
                for chunk in (0, 13, 64):
                    cfg = _cfg(name, bucket_s=bucket_s, backend=backend,
                               n_byz=1, superleaf_elems=chunk)
                    outs[chunk] = robust_aggregate(
                        tree, mask, key, mesh=mesh, cfg=cfg, radius=radius
                    )
                for chunk in (13, 64):
                    for la, lb in zip(
                        jax.tree_util.tree_leaves(outs[0]),
                        jax.tree_util.tree_leaves(outs[chunk]),
                    ):
                        assert la.dtype == lb.dtype
                        np.testing.assert_array_equal(
                            np.asarray(la), np.asarray(lb),
                            err_msg=f"{name} s={bucket_s} chunk={chunk} "
                                    f"clip={radius is not None}",
                        )


# ---------------------------------------------------------------------------
# pipelined == sequential (sharded path).  In-process on the 1-device
# mesh this exercises the multi-block pipeline/packing mechanics (the
# collectives are trivial at W=1); the >= 8-device registry-wide bitwise
# test is the slow subprocess test in tests/test_mesh_trainer.py.
# ---------------------------------------------------------------------------

# one rule per structural class (coordinate-wise / iterative / one-hot
# selection / bucketed multi-row selection); the whole registry runs in
# the slow 8-device subprocess test
_ALL_RULES = (("cm", 0), ("cclip", 0), ("krum", 0), ("krum", 2))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_pipelined_schedule_bitwise_equals_sequential_inprocess(backend):
    """The double-buffered schedule emits the same per-block ops as the
    sequential oracle in a different issue order — outputs must be
    bitwise-identical, ragged and packed."""
    tree = jax.tree_util.tree_map(lambda l: l[:1], _ragged_tree())
    mask = jnp.ones((1,), bool)
    key = jax.random.PRNGKey(3)
    mesh = make_debug_mesh(1, 1)
    with jax.set_mesh(mesh):
        for name, bucket_s in _ALL_RULES:
            for chunk in (0, 16):
                outs = {}
                for sched in ("sequential", "pipelined"):
                    cfg = _cfg(name, bucket_s=bucket_s,
                               placement="sharded", blocks=sched,
                               superleaf_elems=chunk, backend=backend)
                    outs[sched] = jax.jit(
                        lambda t, m, k, cfg=cfg: robust_aggregate(
                            t, m, k, mesh=mesh, cfg=cfg,
                            radius=jnp.float32(2.0),
                        )
                    )(tree, mask, key)
                for la, lb in zip(
                    jax.tree_util.tree_leaves(outs["sequential"]),
                    jax.tree_util.tree_leaves(outs["pipelined"]),
                ):
                    np.testing.assert_array_equal(
                        np.asarray(la.astype(jnp.float32)),
                        np.asarray(lb.astype(jnp.float32)),
                        err_msg=f"{name} s={bucket_s} chunk={chunk}",
                    )


def test_schedule_and_shape_validation():
    mesh = make_debug_mesh(1, 1)
    tree = {"a": jnp.ones((2, 4))}
    # malformed schedules fail at SPEC construction (PlanError is a
    # ValueError), before any aggregation runs
    with pytest.raises(PlanError, match="unknown schedule"):
        ScheduleSpec(blocks="nope")
    with pytest.raises(PlanError, match="superleaf_elems"):
        ScheduleSpec(superleaf_elems=-1)
    with pytest.raises(ValueError, match="one row per worker"):
        # 2 rows on a 1-worker mesh: the sharded scatter would silently
        # drop a worker
        robust_aggregate(
            tree, jnp.ones(2, bool), jax.random.PRNGKey(0), mesh=mesh,
            cfg=_cfg("cm", placement="sharded"),
        )
