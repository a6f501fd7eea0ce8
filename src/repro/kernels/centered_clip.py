"""Pallas TPU kernels: CenteredClip fixed-point iterations.

CenteredClip (Karimireddy et al., 2021) iterates
    v <- v + (1/n) sum_i min(1, tau/||x_i - v||) (x_i - v).

Two regimes, selected by VMEM footprint:

  resident  ``resident_elems(n_p, d)`` fits the VMEM budget: the
            whole problem stays in one block and all ``iters`` rounds
            run inside a single kernel invocation.  The optional
            server clip (per-row factors from the shared pass-1
            row-norm accumulator in clip_aggregate.py) and Bucketing
            (resident ``bucket_idx`` row-gather + mask-weighted bucket
            means) are applied in-register before the iteration — the
            clipped matrix never exists in HBM.
  tiled     larger d streams (n, TILE_D) blocks with a cross-tile norm
            reduction: each round runs one grid pass accumulating per-row
            partial sums of squares of (x*f - v), a host-side O(n) sqrt /
            scale step, and one grid pass applying the update to the
            (1, d) iterate.  2 streams per round — the same traffic the
            pure-jnp reference needs, but with explicit VMEM tiling and
            clip factors applied in-register.  (This replaces the old
            silent fallback to ``centered_clip_ref``, which violated the
            backend contract in ops.py for large d.)

Row semantics match ``repro.core.aggregators._centered_clip`` /
``_bucketing`` exactly, so a backend swap preserves trajectories.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .clip_aggregate import _row_norms, clip_factor
from .coordinate_median import (TILE_D, _pad_to, store_tile_partial,
                                tile_partials)

F32 = jnp.float32
MAX_VMEM_ELEMS = 1 << 20  # resident_elems(n_p, d) floats: ~4 MB of VMEM


def resident_elems(n_p, d):
    """VMEM floats the resident kernel holds for an (n_p, d) problem: the
    rows fill whole 8-row sublane tiles, and each (1, d) iterate or
    temporary fills a tile of its own (two tiles' worth)."""
    return (-(-n_p // 8) * 8 + 16) * d


# ---------------------------------------------------------------------------
# in-register helpers (shared with geometric_median.py)
# ---------------------------------------------------------------------------

def _bucket_means_block(x, m, idx, s):
    """Mask-weighted bucket means of a VMEM-resident block.

    ``x`` (n_p, td) with clip factors already applied, ``m`` (n_p, 1),
    ``idx`` (n_p,) the resident row-gather.  Returns (means (nb, td),
    bucket mask (nb, 1)) — aggregators._bucketing semantics (empty buckets
    masked out).
    """
    n_p, td = x.shape
    nb = n_p // s
    xp = jnp.take(x, idx, axis=0)
    mp = jnp.take(m, idx, axis=0)
    xb = xp.reshape(nb, s, td)
    mb = mp.reshape(nb, s, 1)
    cnt = jnp.sum(mb, axis=1)  # (nb, 1)
    means = jnp.sum(xb * mb, axis=1) / jnp.maximum(cnt, 1.0)
    return means, (cnt > 0.5).astype(F32)


def _pad_bucket_aux(mask, factors, bucket_idx, n, bucket_s):
    """Row-pad the per-row bucketing auxiliaries to a bucket_s multiple:
    mask with 0 (padded rows never sampled), factors with 1, bucket_idx
    extended with the padded positions — the aggregators._bucketing
    permute-then-pad semantics, shared by every kernel that composes with
    Bucketing (cclip/GM here, the Krum Gram algebra in krum.py).
    Returns (mask, factors, bucket_idx (int32), pad_rows)."""
    if bucket_idx is None:
        bucket_idx = jnp.arange(n, dtype=jnp.int32)
    bucket_idx = bucket_idx.astype(jnp.int32)
    pad_rows = (-n) % bucket_s if bucket_s >= 2 else 0
    if pad_rows:
        n_p = n + pad_rows
        mask = jnp.pad(mask, (0, pad_rows))
        factors = jnp.pad(factors, (0, pad_rows), constant_values=1.0)
        bucket_idx = jnp.concatenate(
            [bucket_idx, jnp.arange(n, n_p, dtype=jnp.int32)]
        )
    return mask, factors, bucket_idx, pad_rows


def _prep_rows(xs, mask, factors, bucket_idx, bucket_s):
    """Row-pad xs and its auxiliaries to a bucket_s multiple (padded rows
    zero with mask 0, matching aggregators._bucketing)."""
    n = xs.shape[0]
    mask, factors, bucket_idx, pad_rows = _pad_bucket_aux(
        mask, factors, bucket_idx, n, bucket_s
    )
    if pad_rows:
        xs = jnp.pad(xs, ((0, pad_rows), (0, 0)))
    return xs, mask, factors, bucket_idx


# ---------------------------------------------------------------------------
# resident kernel: clip + bucket + all iterations in one invocation
# ---------------------------------------------------------------------------

def _cclip_resident_kernel(idx_ref, f_ref, m_ref, x_ref, o_ref, *, s, tau,
                           iters):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)  # (n_p, d)
    m = m_ref[...].astype(F32)  # (n_p, 1)
    if s >= 2:
        x, m = _bucket_means_block(x, m, idx_ref[...][:, 0], s)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    v0 = jnp.sum(x * m, axis=0, keepdims=True) / denom  # (1, d)

    def body(_, v):
        diff = x - v
        nrm = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True) + 1e-30)
        scale = jnp.minimum(1.0, tau / nrm) * m
        return v + jnp.sum(diff * scale, axis=0, keepdims=True) / denom

    v = jax.lax.fori_loop(0, iters, body, v0)
    o_ref[...] = v.astype(o_ref.dtype)


def _run_resident(kernel, xs, mask_f, factors, bucket_idx, interpret, name):
    n_p, d = xs.shape
    out = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec((n_p, 1), lambda: (0, 0)),  # idx: resident
            pl.BlockSpec((n_p, 1), lambda: (0, 0)),  # factors: resident
            pl.BlockSpec((n_p, 1), lambda: (0, 0)),  # mask: resident
            pl.BlockSpec((n_p, d), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), xs.dtype),
        interpret=interpret,
        name=name,
    )(
        bucket_idx.reshape(n_p, 1),
        factors.reshape(n_p, 1).astype(F32),
        mask_f.reshape(n_p, 1),
        xs,
    )
    return out[0]


# ---------------------------------------------------------------------------
# tiled machinery: cross-tile norm reduction (shared with geometric_median)
# ---------------------------------------------------------------------------

def _diff_ssq_kernel(f_ref, z_ref, x_ref, o_ref):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)  # (n, td)
    z = z_ref[...].astype(F32)  # (1, td)
    diff = x - z
    store_tile_partial(o_ref, jnp.sum(diff * diff, axis=1, keepdims=True))


def _cclip_update_kernel(den_ref, s_ref, f_ref, z_ref, x_ref, o_ref):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)
    z = z_ref[...].astype(F32)
    diff = x - z
    upd = jnp.sum(diff * s_ref[...].astype(F32), axis=0, keepdims=True)
    o_ref[...] = (z + upd / den_ref[0, 0]).astype(o_ref.dtype)


def _bucket_means_kernel(idx_ref, f_ref, m_ref, x_ref, o_ref, *, s):
    x = x_ref[...].astype(F32) * f_ref[...].astype(F32)
    means, _ = _bucket_means_block(
        x, m_ref[...].astype(F32), idx_ref[...][:, 0], s
    )
    o_ref[...] = means


def diff_row_ssq(xp, z, factors, *, interpret, reduce_fn=None):
    """Per-row ||x*f - z||^2 via tile-partial sums: (n, dp) -> (n,) f32.

    ``reduce_fn`` (a psum over shard_map axes) promotes the block-local
    sums to global ones when ``xp`` holds one coordinate shard per chip —
    the hook that makes the sharded trainer's iterative aggregation equal
    to the full-vector semantics."""
    n, dp = xp.shape
    grid = dp // TILE_D
    out_spec, out_shape = tile_partials(n, grid)
    partial = pl.pallas_call(
        _diff_ssq_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # factors: resident
            pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="diff_row_ssq",
    )(factors.reshape(n, 1), z, xp)
    ssq = jnp.sum(partial[:, :grid], axis=1)
    return ssq if reduce_fn is None else reduce_fn(ssq)


def bucket_means_tiled(xp, mask_f, factors, bucket_idx, s, *, interpret):
    """Streaming mask-weighted bucket means: (n_p, dp) -> (nb, dp) f32,
    clip factors applied in-register; plus the bucket mask (nb,)."""
    n_p, dp = xp.shape
    nb = n_p // s
    grid = dp // TILE_D
    means = pl.pallas_call(
        functools.partial(_bucket_means_kernel, s=s),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_p, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((nb, TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nb, dp), F32),
        interpret=interpret,
        name="bucket_means",
    )(
        bucket_idx.reshape(n_p, 1),
        factors.reshape(n_p, 1).astype(F32),
        mask_f.reshape(n_p, 1),
        xp,
    )
    mp = jnp.take(mask_f, bucket_idx)
    cnt = jnp.sum(mp.reshape(nb, s), axis=1)
    return means, (cnt > 0.5).astype(F32)


def _cclip_tiled(xp, mask_f, factors, *, tau, iters, interpret,
                 reduce_fn=None):
    n, dp = xp.shape
    grid = dp // TILE_D
    denom = jnp.maximum(jnp.sum(mask_f), 1.0)
    v = jnp.sum(
        xp.astype(F32) * (factors * mask_f)[:, None], axis=0, keepdims=True
    ) / denom
    den = denom.reshape(1, 1)
    f_col = factors.reshape(n, 1).astype(F32)
    for _ in range(iters):
        ssq = diff_row_ssq(xp, v, factors, interpret=interpret,
                           reduce_fn=reduce_fn)
        nrm = jnp.sqrt(ssq + 1e-30)
        scale = (jnp.minimum(1.0, tau / nrm) * mask_f).reshape(n, 1)
        v = pl.pallas_call(
            _cclip_update_kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0)),  # denom: resident
                pl.BlockSpec((n, 1), lambda i: (0, 0)),  # scale: resident
                pl.BlockSpec((n, 1), lambda i: (0, 0)),  # factors: resident
                pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
                pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, dp), F32),
            interpret=interpret,
            name="centered_clip_update",
        )(den, scale, f_col, v, xp)
    return v[0]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def run_clip_then_iterative(
    xs, radius, mask, bucket_idx, factors, *, bucket_s, use_clip,
    reduce_fn, interpret, resident_kernel, tiled_fn, name,
):
    """Shared driver for the fused clip -> (Bucketing) -> iterative
    aggregation kernels (CenteredClip here, Weiszfeld GM in
    geometric_median.py): the norm pass / ``factors`` handling, row prep
    and the resident-vs-coordinate-tiled VMEM dispatch live in ONE place;
    only the iteration bodies differ.

    ``resident_kernel(s)`` -> the whole-problem VMEM kernel for bucket
    size ``s``; ``tiled_fn(xp, mask_f, factors, reduce_fn)`` -> the
    (1, dp) iterate of the streaming schedule; ``name`` names the
    resident kernel.  ``factors`` (n,) skips
    the norm pass (precomputed per-row scales, e.g. the sharded
    trainer's global tree-norm factors); ``use_clip=False`` is the plain
    aggregation.  ``reduce_fn`` reduces every per-row sum-of-squares
    across coordinate shards (a psum inside shard_map) and forces the
    stat-separated tiled schedule, since the resident kernel cannot host
    a collective mid-iteration.  Returns
    ``(aggregated (d,), row_norms (n,) or None)``.
    """
    n, d = xs.shape
    mask_f = jnp.ones((n,), F32) if mask is None else mask.astype(F32)
    norms = None
    if use_clip:
        if factors is None:
            xp_n, _ = _pad_to(xs, TILE_D, axis=1)
            norms = _row_norms(
                xp_n, xp_n.shape[1] // TILE_D, n, interpret, reduce_fn
            )
            factors = clip_factor(norms, radius).astype(F32)
        else:
            factors = factors.astype(F32)
    else:
        factors = jnp.ones((n,), F32)

    xs_p, mask_f, factors, bucket_idx = _prep_rows(
        xs, mask_f, factors, bucket_idx, bucket_s
    )
    n_p = xs_p.shape[0]
    s = bucket_s if bucket_s >= 2 else 1

    if reduce_fn is None and resident_elems(n_p, d) <= MAX_VMEM_ELEMS:
        out = _run_resident(
            resident_kernel(s), xs_p, mask_f, factors, bucket_idx, interpret,
            name,
        )
        return out, norms

    xp, pad = _pad_to(xs_p, TILE_D, axis=1)
    if s >= 2:
        means, bucket_ok = bucket_means_tiled(
            xp, mask_f, factors, bucket_idx, s, interpret=interpret
        )
        nb = means.shape[0]
        v = tiled_fn(means, bucket_ok, jnp.ones((nb,), F32), reduce_fn)
    else:
        v = tiled_fn(xp, mask_f, factors, reduce_fn)
    out = (v[:d] if pad else v).astype(xs.dtype)
    return out, norms


@functools.partial(
    jax.jit,
    static_argnames=(
        "tau", "iters", "bucket_s", "use_clip", "reduce_fn", "interpret"
    ),
)
def clip_then_centered_clip(
    xs,
    radius,
    mask=None,
    bucket_idx=None,
    factors=None,
    *,
    tau: float = 10.0,
    iters: int = 5,
    bucket_s: int = 1,
    use_clip: bool = True,
    reduce_fn=None,
    interpret: bool = False,
):
    """Fused per-row clip at ``radius`` -> (optional Bucketing) ->
    CenteredClip(tau, iters) over the rows of (n, d).  See
    ``run_clip_then_iterative`` for the ``factors``/``reduce_fn``
    contract.  Returns ``(aggregated (d,), row_norms (n,) or None)``."""
    return run_clip_then_iterative(
        xs, radius, mask, bucket_idx, factors,
        bucket_s=bucket_s, use_clip=use_clip, reduce_fn=reduce_fn,
        interpret=interpret,
        resident_kernel=lambda s: functools.partial(
            _cclip_resident_kernel, s=s, tau=tau, iters=iters
        ),
        name="centered_clip_resident",
        tiled_fn=lambda xp, m, f, rfn: _cclip_tiled(
            xp, m, f, tau=tau, iters=iters, interpret=interpret,
            reduce_fn=rfn,
        ),
    )


@functools.partial(jax.jit, static_argnames=("tau", "iters", "interpret"))
def centered_clip(xs, mask=None, *, tau: float = 10.0, iters: int = 5,
                  interpret: bool = False):
    """(n, d) -> (d,) CenteredClip aggregate (mask-aware)."""
    out, _ = clip_then_centered_clip(
        xs, 0.0, mask, tau=tau, iters=iters, use_clip=False,
        interpret=interpret,
    )
    return out
