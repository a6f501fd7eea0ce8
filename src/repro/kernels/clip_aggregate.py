"""Pallas TPU kernel: fused server-side clip -> robust-aggregate.

The Byz-VR-MARINA-PP server step (Algorithm 1) re-clips every received
message at radius lambda and aggregates the clipped (n, d) matrix with a
masked coordinate-median / trimmed-mean (optionally composed with
Bucketing).  Unfused this costs ~4 gradient-matrix HBM streams: a norm
reduction read, a scale read+write materializing the clipped matrix, and
the aggregation read.  The fused path streams the matrix exactly twice and
never materializes the clipped matrix in HBM:

  pass 1  wide (n, width) VMEM blocks (``stream_geometry``: about
          ``BLOCK_BYTES`` a grid step) -> per-row partial sum-of-squares
          (one f32 per row per block, lanes past d masked off);
          host-side sqrt + min{1, lambda/norm} gives the n scalar clip
          factors.
  pass 2  re-streams each block chunk by chunk (``select_stream``),
          applies the per-row factors in-register, and immediately runs
          the masked selection network (CM or trimmed mean).  Neither
          pass pads the (n, d) operand: the grid is cdiv(d, width).
          With ``bucket_idx`` pass 2 instead walks (n, TILE_D) tiles of
          the padded matrix, first permuting rows and averaging buckets
          of ``bucket_s`` in VMEM (Bucketing fusion); its norm pass takes
          the same tiles.

HBM traffic drops from ~4*n*d to ~2*n*d streamed words.  Setting
``use_clip=False`` skips pass 1 entirely (plain kernel aggregation for the
full-gradient rounds); ``radius=+inf`` keeps pass 1 but recovers plain
aggregation exactly (all factors 1), which is the ``use_clipping=False``
engine path.

Row semantics match ``repro.core.aggregators`` exactly (numpy median
tie-handling, mask-weighted bucket means, empty buckets masked out), so a
backend swap preserves trajectories bit-for-tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .coordinate_median import (TILE_D, _pad_to, _select_masked,
                                chunk_offset, for_chunks, store_tile_partial,
                                stream_geometry, stream_rows, tile_partials)

F32 = jnp.float32
_BIG = 3.4e37
_EPS = 1e-30


def clip_factor(norm, radius):
    """min{1, radius/norm} with clip(0)=0 semantics (factor of 1 at 0).

    The single source of truth for the clip factor: the jnp reference path
    (repro.core.clipping) imports it from here, so the fused kernel and the
    reference backend can never drift apart."""
    return jnp.minimum(1.0, radius / jnp.maximum(norm, _EPS))


def _rownorm_kernel(x_ref, o_ref, *, d, chunk):
    n, width = x_ref.shape
    base = pl.program_id(0) * width

    def body(c, acc):
        off = chunk_offset(c, chunk)
        x = x_ref[:, pl.ds(off, chunk)].astype(F32)  # (n, chunk)
        lane = base + off + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        return acc + jnp.where(lane < d, x * x, 0.0)

    acc = for_chunks(width // chunk, body, jnp.zeros((n, chunk), F32))
    store_tile_partial(o_ref, jnp.sum(acc, axis=1, keepdims=True))


def _clip_bucket_agg_kernel(
    idx_ref, factor_ref, mask_ref, x_ref, o_ref, *, s, trim_ratio
):
    x = x_ref[...].astype(F32)  # (n_p, td)
    f = factor_ref[...].astype(F32)  # (n_p, 1)
    m = mask_ref[...].astype(F32)  # (n_p, 1)
    idx = idx_ref[...][:, 0]  # (n_p,)
    n_p, td = x.shape
    nb = n_p // s
    xp = jnp.take(x * f, idx, axis=0)
    mp = jnp.take(m, idx, axis=0)
    xb = xp.reshape(nb, s, td)
    mb = mp.reshape(nb, s, 1)
    cnt_b = jnp.sum(mb, axis=1)  # (nb, 1)
    means = jnp.sum(xb * mb, axis=1) / jnp.maximum(cnt_b, 1.0)
    bucket_ok = (cnt_b > 0.5).astype(F32)
    vals = jnp.where(bucket_ok > 0.5, means, _BIG)
    out = _select_masked(vals, bucket_ok, trim_ratio=trim_ratio)
    o_ref[...] = out.astype(o_ref.dtype)


def _row_norms(xp, grid, n, interpret, reduce_fn=None, width=TILE_D):
    """Per-row l2 norms via block-partial sums of squares over ``grid``
    (n, width) blocks of ``xp`` (the last may run past its end: those lanes
    count 0).  ``reduce_fn`` (e.g. a psum over shard_map axes) turns
    block-local partial sums into global ones when ``xp`` is one coordinate
    shard of a larger row."""
    out_spec, out_shape = tile_partials(n, grid)
    # a block wider than a tile is a whole number of tiles; a narrower one
    # is the whole leaf
    kernel = functools.partial(_rownorm_kernel, d=xp.shape[1],
                               chunk=min(width, TILE_D))
    partial_ssq = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((n, width), lambda i: (0, i))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="clip_row_norms",
    )(xp)
    ssq = jnp.sum(partial_ssq[:, :grid], axis=1)  # (n,)
    if reduce_fn is not None:
        ssq = reduce_fn(ssq)
    return jnp.sqrt(ssq)


@functools.partial(
    jax.jit,
    static_argnames=(
        "trim_ratio", "bucket_s", "use_clip", "reduce_fn", "interpret"
    ),
)
def clip_then_aggregate(
    xs,
    radius,
    mask=None,
    bucket_idx=None,
    factors=None,
    *,
    trim_ratio: float = -1.0,
    bucket_s: int = 1,
    use_clip: bool = True,
    reduce_fn=None,
    interpret: bool = False,
):
    """Fused Agg({clip_radius(x_i)}_{i in mask}) over the rows of (n, d).

    ``trim_ratio < 0`` -> coordinate median, else trimmed mean.  With
    ``bucket_s >= 2`` and ``bucket_idx`` (an int32 row-gather of length n,
    shared across all coordinate tiles) the clipped rows are bucket-averaged
    before the selection, reproducing Bucketing o CM/TM.  ``use_clip=False``
    skips the norm pass (plain kernel aggregation, factors = 1).
    ``factors`` (n,) also skips the norm pass and applies the given
    per-row scales instead — the sharded trainer precomputes them from
    global per-worker tree norms (a chip-local block norm would be wrong).
    ``reduce_fn`` (static) reduces the pass-1 row sums-of-squares across
    coordinate shards (a psum inside shard_map) so clipping uses global
    norms when ``xs`` is one shard of a wider row; CM/TM themselves are
    coordinate-wise, so the selection needs no reduction.

    Returns ``(aggregated (d,), row_norms (n,) or None)``.
    """
    n, d = xs.shape
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    mask = mask.astype(jnp.float32)

    def clip_factors(xp, width):
        """(factors (n,), norms (n,) or None) over (n, width) blocks."""
        if not use_clip:
            return jnp.ones((n,), F32), None
        if factors is not None:
            return factors.astype(F32), None
        grid = pl.cdiv(xp.shape[1], width)
        norms = _row_norms(xp, grid, n, interpret, reduce_fn, width)
        return clip_factor(norms, radius).astype(F32), norms

    if bucket_s < 2:
        f, norms = clip_factors(xs, stream_geometry(n, d, xs.dtype.itemsize)[0])
        out = stream_rows(xs, mask, f, trim_ratio=trim_ratio,
                          name="clip_aggregate", interpret=interpret)
        return out, norms

    # Bucketing walks (n, TILE_D) tiles of the padded matrix
    xp, pad = _pad_to(xs, TILE_D, axis=1)
    f, norms = clip_factors(xp, TILE_D)
    grid = xp.shape[1] // TILE_D
    if bucket_idx is None:
        bucket_idx = jnp.arange(n, dtype=jnp.int32)
    pad_rows = (-n) % bucket_s
    n_p = n + pad_rows
    if pad_rows:
        # Padded rows are zero with mask 0; padded idx entries point at
        # them, matching aggregators._bucketing (permute then pad).
        xp = jnp.pad(xp, ((0, pad_rows), (0, 0)))
        mask = jnp.pad(mask, (0, pad_rows))
        f = jnp.pad(f, (0, pad_rows), constant_values=1.0)
        bucket_idx = jnp.concatenate(
            [
                bucket_idx.astype(jnp.int32),
                jnp.arange(n, n_p, dtype=jnp.int32),
            ]
        )
    out = pl.pallas_call(
        functools.partial(
            _clip_bucket_agg_kernel, s=bucket_s, trim_ratio=trim_ratio
        ),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),  # idx: resident
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),  # factors: resident
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),  # mask: resident
            pl.BlockSpec((n_p, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), xs.dtype),
        interpret=interpret,
        name="clip_bucket_aggregate",
    )(bucket_idx.reshape(n_p, 1), f.reshape(n_p, 1),
      mask.reshape(n_p, 1), xp)
    out = out[0]
    return (out[:d] if pad else out), norms
