"""Mesh execution of a built ServerPlan: the collective schedules.

This is the distributed half of ``ServerPlan.build(mesh)`` — the naive and
sharded placements, the sequential / pipelined (double-buffered) block
schedules, superleaf packing, and the whole-tree two-phase selection
contract.  It was extracted verbatim from ``repro.launch.train``'s
``robust_aggregate`` when the ServerPlan API became the single entry
point; the semantics (and the bitwise guarantees pinned by
tests/test_mesh_trainer.py and tests/test_superleaf.py) are unchanged:

  naive    — the paper's parameter-server semantics: gather every worker's
             message (XLA all-gathers the worker dim), aggregate everywhere;
             each chip keeps its tensor-parallel shard of every row.
             Collective bytes per chip ~ W * |shard|.
  sharded  — beyond-paper scatter-aggregate-gather: all_to_all the worker
             messages so each chip owns all W values for 1/W-th of its
             coordinates, aggregate locally, all_gather the result.
             Collective bytes per chip ~ 2 * |shard|; peak memory W x lower.

Both placements compute the identical (delta, c)-robust aggregation for
the WHOLE aggregator registry: coordinate-wise rules shard trivially, and
the non-coordinate-wise ones (krum, centered-clip, Weiszfeld GM) get
their global row statistics via a per-leaf psum hook (``reduce_fn``)
threaded into the per-chip aggregation.  The server-side clip (Alg.1
l.10) is fused into the aggregation: ``radius=...`` computes per-worker
global tree norms in one batched pass and the per-chip
``Aggregator.clip_then_aggregate`` applies the factors in-register during
the aggregation read — the clipped message tree never materializes.

Selection rules (krum/multi_krum, plain or bucketed) are WHOLE-TREE:
one (W, W) Gram accumulated across the per-leaf loop (per-leaf psum over
each leaf's own shard axes), one whole-tree selection, winner applied
leafwise — the stacked (W, d_total) message never exists on any schedule.

``ScheduleSpec.blocks`` picks the inner block order ("sequential", the
equivalence oracle, or "pipelined" — block i+1's all_to_all issued and
``jax.lax.optimization_barrier``-pinned before block i's aggregation
kernel; bitwise-equal, steady-state block cost ~ max(comm, compute)) and
``ScheduleSpec.superleaf_elems`` the block partition (ragged per-tensor
leaves, or uniform superleaf chunks packed per shard-axes group).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.clipping import clip_factor
from ..core.tree_utils import tree_norm, tree_superleaf_pack
from ..launch.mesh import worker_axes as _default_worker_axes
from .plan import PlanError, ScheduleSpec

__all__ = [
    "run_mesh_aggregate",
    "leaf_agg_of",
    "mesh_worker_axes",
    "mesh_worker_count",
    "schedule_map",
]

F32 = jnp.float32
_BIG = F32(3.4e37)


def mesh_worker_axes(mesh, worker_axes_override: tuple = ()) -> tuple:
    """The mesh axes the plan's workers enumerate over."""
    return tuple(worker_axes_override) or _default_worker_axes(mesh)


def mesh_worker_count(mesh, worker_axes_override: tuple = ()) -> int:
    """Number of workers the plan's worker axes enumerate on ``mesh``."""
    W = 1
    for a in mesh_worker_axes(mesh, worker_axes_override):
        W *= mesh.shape[a]
    return W


def leaf_agg_of(agg):
    """Per-chip aggregation over the worker axis of one (W, ...) leaf,
    built on the dispatch layer: flattens to the kernels' (n, d) shape;
    with ``factors`` it routes through ``Aggregator.clip_then_aggregate``
    (the fused server step — no clipped matrix in HBM)."""

    def leaf_agg(leaf, mask, key, factors=None, reduce_fn=None):
        mat = leaf.reshape(leaf.shape[0], -1)
        if factors is None:
            out = agg(mat, mask=mask, key=key, reduce_fn=reduce_fn)
        else:
            out = agg.clip_then_aggregate(
                mat, _BIG, mask=mask, key=key, factors=factors,
                reduce_fn=reduce_fn,
            )
        return out.reshape(leaf.shape[1:])

    return leaf_agg


def _spec_axes(spec):
    """Mesh axes a PartitionSpec shards over (flattened)."""
    axes = []
    for entry in spec:
        if isinstance(entry, (tuple, list)):
            axes.extend(a for a in entry if a is not None)
        elif entry is not None:
            axes.append(entry)
    return tuple(axes)


@lru_cache(maxsize=None)
def _psum_reduce(axis_names: tuple):
    """One partial per axes tuple: ``reduce_fn`` is a *static* jit arg of
    the kernel wrappers and partials hash by identity, so a fresh partial
    per leaf/trace would defeat their jit caches (per-leaf re-lowering
    and unbounded cache growth)."""
    return partial(jax.lax.psum, axis_name=axis_names)


def _worker_message_norms(tree_w):
    """Per-worker *global* message norms (worker axis 0): the tree_norm
    each worker's whole message would report, batched — single source of
    truth with the lam = alpha*gamma*tree_norm(g) radius."""
    return jax.vmap(tree_norm)(tree_w)


def schedule_map(produce, consume, n, pipelined: bool):
    """``outs[i] = consume(i, produce(i))`` over ``n`` blocks.

    ``pipelined=False``: strictly in order (produce i, consume i,
    produce i+1, ...).  ``pipelined=True``: the two-stage software
    pipeline — prologue issues produce(0); in steady state produce(i+1)
    is emitted BEFORE consume(i) and schedule-pinned to it with
    ``jax.lax.optimization_barrier`` (consumers of block i's buffer
    depend on block i+1's produce having been issued), so XLA keeps the
    next block's collective in flight while the current block's kernel
    runs; the epilogue consumes the last buffer.  Identity on values:
    both orders emit exactly the same per-block ops, so results are
    bitwise-equal — only the issue order differs."""
    if n == 0:
        return []
    if not pipelined or n == 1:
        return [consume(i, produce(i)) for i in range(n)]
    outs = []
    pending = produce(0)
    for i in range(n):
        cur = pending
        if i + 1 < n:
            nxt = produce(i + 1)
            cur, nxt = jax.lax.optimization_barrier((cur, nxt))
            pending = nxt
        outs.append(consume(i, cur))
    return outs


def _reduce_over(axes: tuple):
    """psum over ``axes`` as a ``reduce_fn`` (None when no axis shards
    the block: the row statistics are already global)."""
    return _psum_reduce(axes) if axes else None


def _naive_aggregate(tree_w, mask, key, factors, *, agg, leaf_agg,
                     two_phase, chunk_elems, use_factors, stat_axes):
    """The naive placement's aggregation of a whole worker-stacked tree on
    one chip: every worker's row of this chip's grad shard.  No
    collectives to overlap: ``blocks`` is a no-op here, but superleaf
    packing still applies (uniform per-chunk dispatch).  ``stat_axes``
    (per flattened leaf) are the mesh axes its grad spec shards; a psum
    over them gives the non-coordinate-wise rules their global row
    statistics, as in the sharded placement."""
    factors = factors if use_factors else None
    if chunk_elems > 0:
        blocks, block_axes, unpack = tree_superleaf_pack(
            tree_w, chunk_elems, group_ids=stat_axes
        )
    else:
        leaves, treedef = jax.tree_util.tree_flatten(tree_w)
        blocks = [l.reshape(l.shape[0], -1) for l in leaves]
        block_axes = stat_axes

        def unpack(rows):
            return jax.tree_util.tree_unflatten(
                treedef,
                [r.reshape(l.shape[1:]) for r, l in zip(rows, leaves)],
            )
    if two_phase:
        stats = None
        for b, axes in zip(blocks, block_axes):
            g = agg.accumulate_stats(b, reduce_fn=_reduce_over(axes))
            stats = g if stats is None else stats + g
        sel = agg.finalize(stats, mask=mask, key=key, factors=factors)
        rows = agg.apply_selection(blocks, sel)
    else:
        rows = [
            leaf_agg(b, mask, key, factors=factors,
                     reduce_fn=_reduce_over(axes))
            for b, axes in zip(blocks, block_axes)
        ]
    return unpack(rows)


def run_mesh_aggregate(tree_w, mask, key, *, mesh, agg, spec: ScheduleSpec,
                       base_specs=None, radius=None, with_factors=False):
    """Aggregate a worker-stacked pytree (leaves (W, ...)) into the
    aggregated pytree (leaves (...)) under ``spec`` on ``mesh``.

    ``agg`` is the plan's dispatch-layer ``Aggregator``; ``radius``, when
    set, l2-clips every worker message at that radius by its *global*
    tree norm before aggregation (the Algorithm-1 server re-clip as a
    2-stream fused step — batched norm pass, then per-chip
    ``clip_then_aggregate`` with precomputed factors).  ``with_factors``
    also returns those (n,) factors (ones without a radius), so a caller
    can count clipped rows without a second pass over the messages.

    ``base_specs``: PartitionSpec pytree of the UNSTACKED leaves (the grad
    sharding).  The sharded placement runs a fully-manual shard_map
    matching the exact grad sharding so the in-kernel flatten is
    chip-local — flattening a model-sharded dim under auto propagation
    silently all-gathers it.  The all_to_all lands a chip-local (W, d/W)
    block on every chip — exactly the fused kernel's input shape.

    The norm pass runs under the ``clip_norm`` name scope and the rest
    under ``aggregate``: metadata that a profile reads, nothing that runs.
    """
    n_rows = jax.tree_util.tree_leaves(tree_w)[0].shape[0]
    use_factors = radius is not None
    if use_factors:
        with jax.named_scope("clip_norm"):
            factors = clip_factor(_worker_message_norms(tree_w),
                                  radius).astype(F32)
    else:
        factors = jnp.ones((n_rows,), F32)
    with jax.named_scope("aggregate"):
        out = _aggregate_placed(tree_w, mask, key, factors, mesh=mesh,
                                agg=agg, spec=spec, base_specs=base_specs,
                                use_factors=use_factors)
    return (out, factors) if with_factors else out


def _aggregate_placed(tree_w, mask, key, factors, *, mesh, agg,
                      spec: ScheduleSpec, base_specs, use_factors):
    """``run_mesh_aggregate``'s aggregation on the spec's placement, with
    the clip factors already computed."""
    leaf_agg = leaf_agg_of(agg)
    two_phase = agg.supports_two_phase
    pipelined = spec.blocks == "pipelined"
    chunk_elems = int(spec.superleaf_elems)
    waxes = mesh_worker_axes(mesh, spec.worker_axes)
    W = mesh_worker_count(mesh, spec.worker_axes)
    n_rows = jax.tree_util.tree_leaves(tree_w)[0].shape[0]

    if base_specs is None:
        base_specs = jax.tree_util.tree_map(
            lambda l: P(*([None] * (l.ndim - 1))), tree_w
        )
    spec_leaves = jax.tree_util.tree_leaves(
        base_specs, is_leaf=lambda x: isinstance(x, P)
    )

    if spec.placement == "naive" or not waxes:
        if mesh.size == 1:
            # nothing to partition; a shard_map would only cost eager
            # callers a whole-program compile per call
            return _naive_aggregate(
                tree_w, mask, key, factors, agg=agg, leaf_agg=leaf_agg,
                two_phase=two_phase, chunk_elems=chunk_elems,
                use_factors=use_factors, stat_axes=[()] * len(spec_leaves),
            )
        # a manual shard_map, because a Pallas TPU kernel cannot be
        # partitioned automatically: the worker dim is replicated (XLA
        # all-gathers it), every other dim keeps its grad sharding, so
        # each chip aggregates all W rows of its own shard
        naive = partial(
            _naive_aggregate, agg=agg, leaf_agg=leaf_agg,
            two_phase=two_phase, chunk_elems=chunk_elems,
            use_factors=use_factors,
            stat_axes=[_spec_axes(sp) for sp in spec_leaves],
        )
        in_specs = jax.tree_util.tree_map(
            lambda s: P(None, *s), base_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.shard_map(
            naive,
            mesh=mesh,
            in_specs=(in_specs, P(), P(), P()),
            out_specs=base_specs,
            axis_names=set(mesh.axis_names),
            check_vma=False,
        )(tree_w, mask, key, factors)

    if n_rows != W:
        # the sharded placement shards the worker axis over ``waxes``; a
        # row-count mismatch would silently drop (or duplicate) workers
        # in the per-chip scatter
        raise PlanError(
            f"the sharded placement needs one row per worker and one worker "
            f"per device: leaves "
            f"carry {n_rows} worker rows but the mesh has {W} devices on "
            f"its worker axes {waxes}; use placement='naive' to run "
            "several workers per device"
        )
    wspec = waxes if len(waxes) > 1 else waxes[0]
    in_specs = jax.tree_util.tree_map(
        lambda s: P(wspec, *s), base_specs, is_leaf=lambda x: isinstance(x, P)
    )

    # every axis referenced by the specs must be marked manual
    referenced = set(waxes)
    for sp in jax.tree_util.tree_leaves(
        base_specs, is_leaf=lambda x: isinstance(x, P)
    ):
        for entry in sp:
            if isinstance(entry, (tuple, list)):
                referenced.update(entry)
            elif entry is not None:
                referenced.add(entry)
    all_axes = referenced | (
        {"model"} if "model" in mesh.axis_names else set()
    )

    def body(t, m, k, f):
        leaves, treedef = jax.tree_util.tree_flatten(t)
        # Each block's coordinates are spread over the worker axes (the
        # all_to_all chunks) plus whatever axes its grad spec shards — a
        # psum over exactly those gives the non-coordinate-wise rules
        # their global row statistics, making the sharded placement equal
        # to the naive full-vector semantics for the whole registry.
        stat_axes = [tuple(waxes) + _spec_axes(sp) for sp in spec_leaves]
        if chunk_elems > 0:
            # uniform superleaf chunks, grouped by shard axes so every
            # chunk keeps ONE well-defined cross-shard psum
            packed, block_axes, unpack = tree_superleaf_pack(
                t, chunk_elems, group_ids=stat_axes
            )
            flats = [p[0] for p in packed]  # chip-local (chunk,) vectors
            shapes = None
        else:
            flats = [l[0].reshape(-1) for l in leaves]  # chip-local
            block_axes = stat_axes
            shapes = [l.shape[1:] for l in leaves]
            unpack = None
        sizes = [fl.shape[0] for fl in flats]
        pads = [(-s) % W for s in sizes]

        def scatter(i):
            """Chip-local flat block i -> the (W, size/W) all_to_all
            block (the fused kernel's exact input shape)."""
            flat = flats[i]  # chip-local: no hidden resharding
            if pads[i]:
                flat = jnp.pad(flat, (0, pads[i]))
            m = flat.shape[0] // W
            # exchange (W, m/128, 128) blocks: the TPU compiler spends
            # minutes on an all_to_all whose minor dim is a whole leaf
            # (213 s vs 1 s for a 77M-element bf16 leaf on v5e:2x2)
            lanes = 128 if m % 128 == 0 else m
            sw = flat.reshape(W, m // lanes, lanes)
            for ax in waxes:  # all_to_all over each worker axis in turn
                n_ax = mesh.shape[ax]
                sw = sw.reshape((n_ax, -1) + sw.shape[1:])
                sw = jax.lax.all_to_all(sw, ax, split_axis=0, concat_axis=0)
                sw = sw.reshape((-1,) + sw.shape[2:])
            return sw.reshape(W, m)

        def gather(aggd, i):
            out = aggd
            for ax in reversed(waxes):
                out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
            if pads[i]:
                out = out[: sizes[i]]
            return out

        if two_phase:
            # whole-tree selection: accumulate ONE (W, W) Gram across the
            # block loop (additive; per-block psum over that block's own
            # shard axes makes each term global), select once, apply the
            # winner/weights blockwise.  Pipelined, the i+1 scatter flies
            # while block i's Gram kernel runs; the apply phase then
            # overlaps each block's apply kernel with the previous
            # block's all_gather.
            scat = []

            def consume_gram(i, sw):
                scat.append(sw)
                return agg.accumulate_stats(
                    sw, reduce_fn=_psum_reduce(block_axes[i])
                )
            grams = schedule_map(scatter, consume_gram, len(flats),
                                 pipelined)
            stats = grams[0]
            for g in grams[1:]:
                stats = stats + g
            sel = agg.finalize(
                stats, mask=m, key=k, factors=f if use_factors else None
            )
            rows = schedule_map(
                lambda i: agg.apply_selection(scat[i], sel),
                lambda i, applied: gather(applied, i),
                len(flats), pipelined,
            )
        else:
            def consume_agg(i, sw):
                aggd = leaf_agg(
                    sw, m, k,
                    factors=f if use_factors else None,
                    reduce_fn=_psum_reduce(block_axes[i]),
                )  # (size/W,)
                return gather(aggd, i)
            rows = schedule_map(scatter, consume_agg, len(flats),
                                pipelined)

        if unpack is not None:
            return unpack(rows)
        outs = [r.reshape(shp) for r, shp in zip(rows, shapes)]
        return jax.tree_util.tree_unflatten(treedef, outs)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(in_specs, P(), P(), P()),
        out_specs=base_specs,
        axis_names=all_axes,
        check_vma=False,
    )
    return smapped(tree_w, mask, key, factors)
