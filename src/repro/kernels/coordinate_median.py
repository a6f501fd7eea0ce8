"""Pallas TPU kernel: masked coordinate-wise median / trimmed mean over
workers.

The server aggregation streams (n_workers, d) with d ~ 1e8..1e11 and tiny
n (<= 64): a memory-bound reduction.  TPU mapping (vs. GPU per-coordinate
warp sorts): tile the coordinate axis into lane-aligned VMEM blocks of
(n, TILE_D) and compute order statistics with an O(n^2) comparison-count
selection network over the sublane axis — for n <= 64 this is cheaper than
a bitonic sort and vectorizes perfectly across the 128-lane VPU.

Masking (partial participation) pushes unsampled rows to +BIG so they sort
to the top; ranks are made unique with index tie-breaking, so the selected
order statistics match numpy median semantics exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
_BIG = 3.4e37
TILE_D = 512  # lanes: 512 = 4 * 128; sublanes: n (padded to 8)
LANES = 128


def tile_partials(rows, grid):
    """Lane-dense layout for one partial per row per grid step.

    A ``(rows, 1)`` block per step is not a legal TPU block (its lane dim
    is neither a multiple of 128 nor the array's).  Instead step ``i``
    writes lane ``i % 128`` of the resident ``(rows, 128)`` block
    ``i // 128`` (``store_tile_partial``); the caller slices the first
    ``grid`` columns back out, so the partials and their reduction order
    are exactly those of a ``(rows, grid)`` array.  The grid axis must run
    in order (the default "arbitrary" semantics).  Returns the kernel's
    ``(out_spec, out_shape)``."""
    cols = -(-grid // LANES) * LANES
    return (
        pl.BlockSpec((rows, LANES), lambda i, *_: (0, i // LANES)),
        jax.ShapeDtypeStruct((rows, cols), F32),
    )


def store_tile_partial(o_ref, col):
    """Write this grid step's ``(rows, 1)`` partials into its lane of the
    ``tile_partials`` block; the other lanes keep what earlier steps
    wrote."""
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(lane == pl.program_id(0) % LANES, col, o_ref[...])


def _ranks(vals, n):
    """Unique ranks of each row per coordinate: (n, td) int32."""
    vi = vals[:, None, :]  # (n, 1, td)
    vj = vals[None, :, :]  # (1, n, td)
    less = (vj < vi).astype(jnp.int32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n, 1), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n, 1), 1)
    tie = ((vj == vi) & (jj < ii)).astype(jnp.int32)
    return jnp.sum(less + tie, axis=1)  # (n, td)


def _select_masked(vals, ok_mask_f32, *, trim_ratio):
    """Masked order-statistic selection over rows of a (m, td) block.

    ``vals`` must already hold +BIG in masked-out rows.  ``trim_ratio < 0``
    selects the numpy-style median (average of the two middle order
    statistics); otherwise the symmetric trimmed mean.  Shared by the
    standalone CM/TM kernels and the fused clip->aggregate kernel
    (clip_aggregate.py) — one source of truth for tie/trim handling.
    """
    m_rows = vals.shape[0]
    cnt = jnp.sum(ok_mask_f32, dtype=F32).astype(jnp.int32)
    rank = _ranks(vals, m_rows)
    if trim_ratio < 0:
        lo = (cnt - 1) // 2
        hi = cnt // 2
        pick = (rank == lo).astype(F32) + (rank == hi).astype(F32)
        return 0.5 * jnp.sum(vals * pick, axis=0, keepdims=True)
    t = jnp.minimum(
        jnp.ceil(trim_ratio * cnt.astype(F32)).astype(jnp.int32),
        (cnt - 1) // 2,
    )
    keep = ((rank >= t) & (rank < cnt - t)).astype(F32)
    denom = jnp.maximum(cnt - 2 * t, 1).astype(F32)
    return jnp.sum(vals * keep, axis=0, keepdims=True) / denom


def _cm_kernel(mask_ref, x_ref, o_ref):
    x = x_ref[...].astype(F32)  # (n, td)
    m = mask_ref[...].astype(F32)  # (n, 1)
    vals = jnp.where(m > 0.5, x, _BIG)
    o_ref[...] = _select_masked(vals, m, trim_ratio=-1.0).astype(o_ref.dtype)


def _tm_kernel(mask_ref, x_ref, o_ref, *, trim_ratio):
    x = x_ref[...].astype(F32)
    m = mask_ref[...].astype(F32)
    vals = jnp.where(m > 0.5, x, _BIG)
    o_ref[...] = _select_masked(vals, m, trim_ratio=trim_ratio).astype(
        o_ref.dtype
    )


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("trim_ratio", "interpret"))
def coordinate_median(xs, mask=None, *, trim_ratio: float = -1.0, interpret: bool = False):
    """(n, d) -> (d,): masked CM (trim_ratio < 0) or trimmed mean.

    Tiles d into (n, TILE_D) VMEM blocks; one grid step per tile.
    """
    n, d = xs.shape
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    mask = mask.astype(jnp.float32).reshape(n, 1)
    xp, pad = _pad_to(xs, TILE_D, axis=1)
    dp = xp.shape[1]
    grid = dp // TILE_D
    kernel = (
        _cm_kernel
        if trim_ratio < 0
        else functools.partial(_tm_kernel, trim_ratio=trim_ratio)
    )
    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # mask: resident
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), xs.dtype),
        interpret=interpret,
        name="coordinate_median" if trim_ratio < 0 else "trimmed_mean",
    )(mask, xp)
    out = out[0]
    return out[:d] if pad else out
