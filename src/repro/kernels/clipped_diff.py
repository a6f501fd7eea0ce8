"""Pallas TPU kernel: fused gradient-difference -> RandK mask -> clip.

Worker-side message construction (Algorithm 1, line 8) touches three
gradient-sized streams (g_new, g_old, out) plus a sparsity mask.  Unfused,
XLA materializes the difference and the masked difference as separate HBM
round-trips; the fused kernel makes one pass computing the masked scaled
difference AND its per-tile partial sum-of-squares (for the clip norm), then
a second lightweight pass applies the scalar clip factor.  HBM traffic:
5 gradient streams -> 3.

Tiling: 1-D coordinate stream in (8, TILE) f32/bf16 VMEM blocks (sublane 8 x
lane TILE, TILE = 1024 lanes => 8*1024 elements per step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .coordinate_median import store_tile_partial, tile_partials

F32 = jnp.float32
SUB = 8
TILE = 1024
BLOCK = SUB * TILE


def _diff_kernel(gn_ref, go_ref, keep_ref, scale_ref, d_ref, ssq_ref):
    gn = gn_ref[...].astype(F32)
    go = go_ref[...].astype(F32)
    keep = keep_ref[...].astype(F32)
    scale = scale_ref[0]
    d = (gn - go) * keep * scale
    d_ref[...] = d.astype(d_ref.dtype)
    store_tile_partial(ssq_ref, jnp.sum(d * d).reshape(1, 1))


def _scale_kernel(d_ref, f_ref, o_ref):
    o_ref[...] = (d_ref[...].astype(F32) * f_ref[0]).astype(o_ref.dtype)


def _pad_flat(x):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, SUB, TILE), pad


@functools.partial(jax.jit, static_argnames=("interpret",))
def clipped_diff(g_new, g_old, radius, keep_mask, scale, *, interpret: bool = False):
    """Fused clip_radius((g_new - g_old) * keep_mask * scale).

    Arrays may be any shape (flattened internally).  ``keep_mask`` is the
    RandK keep pattern (1.0/0.0), ``scale`` its unbiasedness factor d/k.
    Returns (clipped (same shape/dtype as g_new), norm ()).
    """
    shape, dtype = g_new.shape, g_new.dtype
    gn, pad = _pad_flat(g_new)
    go, _ = _pad_flat(g_old)
    km, _ = _pad_flat(keep_mask.astype(g_new.dtype))
    grid = gn.shape[0]
    scale_arr = jnp.full((1,), scale, F32)
    ssq_spec, ssq_shape = tile_partials(1, grid)

    d_masked, ssq = pl.pallas_call(
        _diff_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
            ssq_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(gn.shape, dtype),
            ssq_shape,
        ],
        interpret=interpret,
        name="clipped_diff",
    )(gn, go, km, scale_arr)

    norm = jnp.sqrt(jnp.sum(ssq[0, :grid]))
    factor = jnp.minimum(1.0, radius / jnp.maximum(norm, 1e-30)).astype(F32)

    out = pl.pallas_call(
        _scale_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, SUB, TILE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(gn.shape, dtype),
        interpret=interpret,
        name="clipped_diff_scale",
    )(d_masked, factor.reshape(1))

    flat = out.reshape(-1)
    if pad:
        flat = flat[: g_new.size]
    return flat.reshape(shape), norm
