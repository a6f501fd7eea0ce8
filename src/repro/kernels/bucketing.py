"""Pallas TPU kernel: fused Bucketing o coordinate-median aggregation.

Bucketing (Karimireddy et al., 2022) averages a random permutation of the
worker rows in buckets of s, then applies the inner aggregator.  Fusing the
bucket-mean into the median kernel saves one full (n, d) HBM round-trip:
the (n, TILE_D) block is permuted/averaged in VMEM and the selection
network runs on the (n/s, TILE_D) bucket means in-place.

The permutation is computed host-side per round (it must be shared across
all coordinate tiles) and passed as an int32 row-gather index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .coordinate_median import TILE_D, _pad_to, _ranks

F32 = jnp.float32
_BIG = 3.4e37


def _bucket_cm_kernel(perm_ref, mask_ref, x_ref, o_ref, *, s):
    x = x_ref[...].astype(F32)  # (n, td)
    perm = perm_ref[...][:, 0]  # (n,)
    m = mask_ref[...].astype(F32)  # (n, 1)
    n, td = x.shape
    nb = n // s
    xp = jnp.take(x, perm, axis=0)
    mp = jnp.take(m, perm, axis=0)
    xb = xp.reshape(nb, s, td)
    mb = mp.reshape(nb, s, 1)
    cnt = jnp.sum(mb, axis=1)  # (nb, 1)
    means = jnp.sum(xb * mb, axis=1) / jnp.maximum(cnt, 1.0)
    bucket_ok = cnt > 0.5
    vals = jnp.where(bucket_ok, means, _BIG)
    bcnt = jnp.sum(bucket_ok.astype(F32)).astype(jnp.int32)
    rank = _ranks(vals, nb)
    lo = (bcnt - 1) // 2
    hi = bcnt // 2
    pick = (rank == lo).astype(F32) + (rank == hi).astype(F32)
    o_ref[...] = (0.5 * jnp.sum(vals * pick, axis=0, keepdims=True)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def bucketed_coordinate_median(
    xs, key, mask=None, *, s: int = 2, interpret: bool = False
):
    """(n, d) -> (d,) Bucketing(s) o masked coordinate-median.

    ``key``: PRNG key for the bucketing permutation (one per round).
    n is padded to a multiple of s with masked-out rows.
    """
    n, d = xs.shape
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    mask = mask.astype(jnp.float32)
    pad_rows = (-n) % s
    if pad_rows:
        xs = jnp.pad(xs, ((0, pad_rows), (0, 0)))
        mask = jnp.pad(mask, (0, pad_rows))
    n_p = xs.shape[0]
    perm = jax.random.permutation(key, n_p).astype(jnp.int32).reshape(n_p, 1)
    xp, pad = _pad_to(xs, TILE_D, axis=1)
    dp = xp.shape[1]
    out = pl.pallas_call(
        functools.partial(_bucket_cm_kernel, s=s),
        grid=(dp // TILE_D,),
        in_specs=[
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),  # perm: resident
            pl.BlockSpec((n_p, 1), lambda i: (0, 0)),  # mask: resident
            pl.BlockSpec((n_p, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), xs.dtype),
        interpret=interpret,
        name="bucketed_coordinate_median",
    )(perm, mask.reshape(n_p, 1), xp)
    out = out[0]
    return out[:d] if pad else out
