"""Pallas TPU kernels: smoothed Weiszfeld geometric median (RFA).

The geometric median (Pillutla et al., 2022) iterates

    z <- sum_i w_i x_i / max(sum_i w_i, eps),   w_i = m_i / sqrt(||x_i - z||^2 + eps)

— the same VMEM-residency-vs-coordinate-tiling trade-off as CenteredClip,
so the two share the tiled cross-tile norm machinery (centered_clip.py):

  resident  whole (n_p, d) block + all iterations in one kernel, with the
            server clip factors and Bucketing applied in-register;
  tiled     per round: one grid pass accumulating per-row partial sums of
            squares of (x*f - z), host-side O(n) weight computation, one
            grid pass forming the re-weighted mean — 2 streams per round,
            never materializing the clipped matrix.

Semantics match ``repro.core.aggregators._geometric_median`` (eps inside
the sqrt, eps-guarded weight sum) so a backend swap preserves
trajectories.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .centered_clip import (
    _bucket_means_block,
    diff_row_ssq,
    run_clip_then_iterative,
)
from .coordinate_median import TILE_D

F32 = jnp.float32


def _gm_resident_kernel(idx_ref, f_ref, m_ref, x_ref, o_ref, *, s, iters,
                        eps):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)  # (n_p, d)
    m = m_ref[...].astype(F32)  # (n_p, 1)
    if s >= 2:
        x, m = _bucket_means_block(x, m, idx_ref[...][:, 0], s)
    z0 = jnp.sum(x * m, axis=0, keepdims=True) / jnp.maximum(
        jnp.sum(m), 1.0
    )

    def body(_, z):
        diff = x - z
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True) + eps)
        w = m / dist
        return jnp.sum(x * w, axis=0, keepdims=True) / jnp.maximum(
            jnp.sum(w), eps
        )

    z = jax.lax.fori_loop(0, iters, body, z0)
    o_ref[...] = z.astype(o_ref.dtype)


def _gm_update_kernel(wsum_ref, w_ref, f_ref, x_ref, o_ref):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)
    num = jnp.sum(x * w_ref[...].astype(F32), axis=0, keepdims=True)
    o_ref[...] = (num / wsum_ref[0, 0]).astype(o_ref.dtype)


def _gm_tiled(xp, mask_f, factors, *, iters, eps, interpret,
              reduce_fn=None):
    n, dp = xp.shape
    grid = dp // TILE_D
    z = jnp.sum(
        xp.astype(F32) * (factors * mask_f)[:, None], axis=0, keepdims=True
    ) / jnp.maximum(jnp.sum(mask_f), 1.0)
    f_col = factors.reshape(n, 1).astype(F32)
    for _ in range(iters):
        ssq = diff_row_ssq(xp, z, factors, interpret=interpret,
                           reduce_fn=reduce_fn)
        dist = jnp.sqrt(ssq + eps)
        w = (mask_f / dist).reshape(n, 1)
        wsum = jnp.maximum(jnp.sum(w), eps).reshape(1, 1)
        z = pl.pallas_call(
            _gm_update_kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0)),  # wsum: resident
                pl.BlockSpec((n, 1), lambda i: (0, 0)),  # weights: resident
                pl.BlockSpec((n, 1), lambda i: (0, 0)),  # factors: resident
                pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, dp), F32),
            interpret=interpret,
            name="geometric_median_update",
        )(wsum, w, f_col, xp)
    return z[0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "iters", "eps", "bucket_s", "use_clip", "reduce_fn", "interpret"
    ),
)
def clip_then_geometric_median(
    xs,
    radius,
    mask=None,
    bucket_idx=None,
    factors=None,
    *,
    iters: int = 8,
    eps: float = 1e-8,
    bucket_s: int = 1,
    use_clip: bool = True,
    reduce_fn=None,
    interpret: bool = False,
):
    """Fused per-row clip at ``radius`` -> (optional Bucketing) ->
    Weiszfeld geometric median over the rows of (n, d).  See
    ``run_clip_then_iterative`` (centered_clip.py) for the shared driver
    and the ``factors``/``reduce_fn`` contract.  Returns
    ``(aggregated (d,), row_norms (n,) or None)``."""
    return run_clip_then_iterative(
        xs, radius, mask, bucket_idx, factors,
        bucket_s=bucket_s, use_clip=use_clip, reduce_fn=reduce_fn,
        interpret=interpret,
        resident_kernel=lambda s: functools.partial(
            _gm_resident_kernel, s=s, iters=iters, eps=eps
        ),
        name="geometric_median_resident",
        tiled_fn=lambda xp, m, f, rfn: _gm_tiled(
            xp, m, f, iters=iters, eps=eps, interpret=interpret,
            reduce_fn=rfn,
        ),
    )


@functools.partial(jax.jit, static_argnames=("iters", "eps", "interpret"))
def geometric_median(xs, mask=None, *, iters: int = 8, eps: float = 1e-8,
                     interpret: bool = False):
    """(n, d) -> (d,) smoothed Weiszfeld geometric median (mask-aware)."""
    out, _ = clip_then_geometric_median(
        xs, 0.0, mask, iters=iters, eps=eps, use_clip=False,
        interpret=interpret,
    )
    return out
