"""Pallas TPU kernel: Krum / multi-Krum via an MXU-tiled Gram matrix.

Krum (Blanchard et al., 2017) scores every worker by the summed squared
distance to its cnt-B-2 nearest sampled neighbours and returns the best row
(multi-Krum: the average of the best-scored rows).  The only d-sized work
in the O(n^2 d) pairwise distances is the (n, n) Gram matrix, because

    ||x_i - x_j||^2 = ||x_i||^2 + ||x_j||^2 - 2 <x_i, x_j>,

so the kernel computes G = X X^T as one MXU matmul per (n, TILE_D) VMEM
block, accumulated tile-wise over the coordinate axis — a single HBM
stream over the message matrix for ANY d (no large-d fallback).  The
compositions the server step needs are Gram algebra, not extra streams:

  clip at lambda   G_c = f f^T o G  with  f_i = min{1, lambda/||x_i||};
                   row norms are sqrt(diag G) — pass 1 is free.
  Bucketing        G_b = M G M^T    with  M the (nb, n) mask-weighted
                   bucket-mean operator over the resident ``bucket_idx``
                   row order (aggregators._bucketing semantics).

Only the winner reconstruction touches xs again, and it too is a kernel:
every selection outcome (Krum winner, multi-Krum average, bucketed winner
means) is a weighted row-sum over the original rows, so one tile-wise
``weighted_row_sum`` pass streams (n, TILE_D) blocks and combines them
in-register — no host-level full-matrix row gather on the fused path.

The selection itself is exposed as a two-phase contract so callers can
defer the decision across *several* matrices sharing the same rows (the
mesh trainer's per-parameter-leaf loop): ``gram_matrix`` per block, sum
the (n, n) Grams (the Gram is additive over the coordinate axis), then
``krum_select_from_gram`` once on the total and ``apply_row_selection``
per block.  ``clip_then_krum`` is exactly that pipeline for a single
matrix.

Distance masking / neighbour counting / tie-breaking live in the pure-jnp
helpers below, which ``repro.core.aggregators`` imports for its jnp
backend too, so EXACT ties (duplicate rows, mutual-nearest-neighbour
symmetric ties — ``g_eff`` is kept exactly symmetric for this) resolve
identically on both backends.  The Gram values themselves may differ in
final ulps between the tile-accumulated kernel and jnp's single matmul
for d > TILE_D, so two *distinct* scores separated by less than that
noise could in principle rank differently — the cross-backend bitwise
trajectory tests (tests/test_backend_trajectory.py) cover the regime the
engine runs in.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .centered_clip import _pad_bucket_aux
from .clip_aggregate import clip_factor
from .coordinate_median import TILE_D, _pad_to

F32 = jnp.float32
_BIG = 3.4e37
# Every f32 matmul of the selection runs at full f32 precision: Krum's
# distances are ||x_i||^2 + ||x_j||^2 - 2 <x_i, x_j>, a difference of
# near-equal large terms, and the TPU's default f32 matmul (bf16 passes)
# leaves errors larger than the distances between close rows.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# selection helpers — the single source of truth shared with the jnp backend
# ---------------------------------------------------------------------------

def masked_pairwise_d2(gram, sq, mask_b):
    """(n, n) squared distances from a Gram matrix; invalid pairs (either
    endpoint unsampled, or the diagonal) pushed to +BIG."""
    n = gram.shape[0]
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    d2 = jnp.maximum(d2, 0.0)
    pair_ok = mask_b[:, None] & mask_b[None, :] & ~jnp.eye(n, dtype=bool)
    return jnp.where(pair_ok, d2, _BIG)


def krum_scores(d2, mask_b, byz_bound: Optional[int]):
    """Krum score per row: sum of the cnt-B-2 smallest valid distances
    (at least 1 neighbour); unsampled rows score +BIG."""
    n = d2.shape[0]
    cnt = jnp.sum(mask_b)
    b = jnp.asarray(byz_bound if byz_bound is not None else 0, jnp.int32)
    d2_sorted = jnp.sort(d2, axis=1)
    csum = jnp.cumsum(jnp.where(d2_sorted >= _BIG, 0.0, d2_sorted), axis=1)
    k_nb = jnp.clip(cnt - b - 2, 1, n - 1)
    return jnp.where(mask_b, csum[:, k_nb - 1], _BIG)


def multi_krum_selection(scores, mask_b, byz_bound: Optional[int],
                         m_select: int):
    """Boolean selection of the best-scored sampled rows; size defaults to
    cnt - B - 2 (Damaskinos et al., 2019), clipped to [1, n]."""
    n = scores.shape[0]
    cnt = jnp.sum(mask_b)
    b = jnp.asarray(byz_bound if byz_bound is not None else 0, jnp.int32)
    m_sel = jnp.clip(
        jnp.asarray(m_select, jnp.int32) if m_select else cnt - b - 2, 1, n
    )
    order = jnp.argsort(scores)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return (rank < m_sel) & mask_b


# ---------------------------------------------------------------------------
# the kernel: tile-accumulated Gram matrix
# ---------------------------------------------------------------------------

def _gram_kernel(x_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(F32)  # (n, td)
    g = jnp.dot(x, x.T, preferred_element_type=F32,
                precision=HIGHEST)  # MXU (n, n)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = g

    @pl.when(i > 0)
    def _accumulate():
        o_ref[...] = o_ref[...] + g


def gram_matrix(xs, *, interpret: bool = False):
    """(n, d) -> (n, n) f32 Gram matrix in one tiled streaming pass."""
    n = xs.shape[0]
    xp, _ = _pad_to(xs, TILE_D, axis=1)
    grid = xp.shape[1] // TILE_D
    return pl.pallas_call(
        _gram_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((n, TILE_D), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), F32),
        interpret=interpret,
        name="krum_gram",
    )(xp)


def _cross_gram_kernel(a_ref, b_ref, o_ref):
    i = pl.program_id(0)
    a = a_ref[...].astype(F32)  # (n, td)
    b = b_ref[...].astype(F32)  # (n, td)
    g = jnp.dot(a, b.T, preferred_element_type=F32,
                precision=HIGHEST)  # MXU (n, n)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = g

    @pl.when(i > 0)
    def _accumulate():
        o_ref[...] = o_ref[...] + g


def cross_gram(a, b, *, interpret: bool = False):
    """(n, d), (n, d) -> (n, n) f32 cross-Gram A B^T, tiled exactly like
    ``gram_matrix`` (same TILE_D grid, same per-tile MXU dot, same
    accumulation order) so ``cross_gram(x, x)`` is bitwise-equal to
    ``gram_matrix(x)`` — the invariant the incremental cohort ingest path
    (repro.serve) relies on.  Both operands keep the FULL cohort row
    count: a chunk update embeds its rows in a zero (n, d) matrix rather
    than shrinking the matmul, because XLA's per-entry reduction order —
    hence the final-ulp bits — depends on the operand shapes."""
    n = a.shape[0]
    ap, _ = _pad_to(a, TILE_D, axis=1)
    bp, _ = _pad_to(b, TILE_D, axis=1)
    grid = ap.shape[1] // TILE_D
    return pl.pallas_call(
        _cross_gram_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), F32),
        interpret=interpret,
        name="krum_cross_gram",
    )(ap, bp)


# ---------------------------------------------------------------------------
# the winner-gather kernel: tile-wise weighted row-sum
# ---------------------------------------------------------------------------

def _row_combine_kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(F32)  # (n, td)
    w = w_ref[...].astype(F32)  # (n, 1)
    # zero-weight rows contribute exactly 0, not 0 * x: a non-finite
    # payload in an unselected/unsampled row (byzantines may send inf)
    # must not poison the combination with 0 * inf = NaN — the row-take
    # this pass replaces never read those rows at all
    contrib = jnp.where(w != 0.0, x * w, 0.0)
    o_ref[...] = jnp.sum(contrib, axis=0, keepdims=True)  # (1, td)


def weighted_row_sum(xs, w_row, *, interpret: bool = False):
    """(n, d), (n,) -> (d,) f32: sum_i w_i * x_i as one tile-wise
    streaming pass — the winner-reconstruction kernel.  Every Krum
    outcome is such a combination (Krum: one-hot(winner) * factor;
    multi-Krum: the selection weights; bucketed winners: the winning
    rows of the bucket-mean operator), so no path gathers rows on the
    host or materializes a weighted copy of the matrix."""
    n = xs.shape[0]
    xp, pad = _pad_to(xs, TILE_D, axis=1)
    grid = xp.shape[1] // TILE_D
    out = pl.pallas_call(
        _row_combine_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # weights: resident
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), F32),
        interpret=interpret,
        name="weighted_row_sum",
    )(w_row.astype(F32).reshape(n, 1), xp)
    out = out[0]
    return out[: xs.shape[1]] if pad else out


# ---------------------------------------------------------------------------
# the single-row fast path: scalar-prefetch winner-row stream
# ---------------------------------------------------------------------------

def _select_row_kernel(row_ref, scale_ref, x_ref, o_ref):
    # x_ref's block is the (row_tile, TILE_D) sublane tile group holding
    # the winner: the index_map below uses the scalar-prefetched winner
    # index as the ROW block coordinate, so the DMA engine only streams
    # that group's tiles, and the winner row is picked in-register (every
    # other row contributes an exact 0, and its payload is never read).
    x = x_ref[...].astype(F32)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    picked = rows == row_ref[0] % x.shape[0]
    x = jnp.sum(jnp.where(picked, x, 0.0), axis=0, keepdims=True)
    s = scale_ref[0]
    # same non-finite guard as _row_combine_kernel: a zero clip factor
    # must produce exactly 0 even if a byzantine winner row carries inf
    o_ref[...] = jnp.where(s != 0.0, x * s, 0.0)


def row_tile(n, dtype):
    """Rows in the smallest row block a TPU can stream from an (n, d)
    array: one sublane tile (8 rows of 32-bit words, 16 of bf16), or all
    n rows when n is smaller.  HBM holds the array in (8, 128) tiles of
    32-bit words, so a single row cannot be sliced out of it."""
    return min(n, 8 * (4 // jnp.dtype(dtype).itemsize))


def select_row(xs, winner, scale, *, interpret: bool = False):
    """(n, d), () int32, () f32 -> (d,) f32: stream ONLY the sublane tile
    group holding row ``winner`` (``row_tile`` rows) via a
    scalar-prefetch index_map, and return that row scaled by ``scale``.

    This is the plain (unbucketed) Krum apply pass: the selection is a
    one-hot row combination, so streaming the other rows through
    ``weighted_row_sum`` just multiplies them by zero.  The winner index
    is prefetched into SMEM before the grid runs and picks the row block,
    cutting the apply pass from n*d to row_tile*d streamed elements.
    Bitwise-equal to the one-hot ``weighted_row_sum`` (both compute
    x[winner] * scale in f32 with the same zero-factor guard).
    """
    n = xs.shape[0]
    rb = row_tile(n, xs.dtype)
    xp, pad = _pad_to(xs, TILE_D, axis=1)
    grid = xp.shape[1] // TILE_D
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((rb, TILE_D),
                         lambda i, row, scale: (row[0] // rb, i)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda i, row, scale: (0, i)),
    )
    out = pl.pallas_call(
        _select_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), F32),
        interpret=interpret,
        name="krum_select_row",
    )(
        jnp.clip(winner, 0, n - 1).astype(jnp.int32).reshape(1),
        scale.astype(F32).reshape(1),
        xp,
    )
    out = out[0]
    return out[: xs.shape[1]] if pad else out


# ---------------------------------------------------------------------------
# selection as (n, n) algebra — phase 2 of the two-phase contract
# ---------------------------------------------------------------------------

class RowSelection(NamedTuple):
    """The outcome of a Krum/multi-Krum selection, decoupled from the
    message coordinates so it can be applied to any matrix sharing the
    row space (each parameter leaf, each coordinate shard).

    ``weights``/``denom``: the row combination sum_i w_i x_i / denom that
    reconstructs the aggregate (clip factors and bucket means folded in).
    ``winner``/``scale``: the argmin row and its clip factor — equivalent
    information for plain (unbucketed) Krum, letting reference backends
    keep an exact dynamic row-take instead of the weighted sum.
    """

    weights: jax.Array  # (n,) f32
    denom: jax.Array  # () f32
    winner: jax.Array  # () int32
    scale: jax.Array  # () f32


def _bucket_operator(bucket_idx, mask_f, factors, n_p, s):
    """The (nb, n_p) mask-weighted bucket-mean matrix M (clip factors
    folded in) plus the per-bucket sampled counts."""
    nb = n_p // s
    idx_r = bucket_idx.reshape(nb, s)
    memb = jax.nn.one_hot(idx_r, n_p, dtype=F32)  # (nb, s, n_p)
    memb = memb * jnp.take(mask_f, idx_r)[:, :, None]
    e = jnp.sum(memb, axis=1)  # (nb, n_p): membership * mask
    cnt = jnp.sum(e, axis=1)  # (nb,)
    m_op = e * factors[None, :] / jnp.maximum(cnt, 1.0)[:, None]
    return m_op, cnt


def selection_is_onehot(multi: bool, bucket_s: int) -> bool:
    """Whether ``krum_select_from_gram``'s row combination is one-hot —
    plain (unbucketed, non-multi) Krum.  THE static predicate gating the
    ``select_row`` single-row fast path; every caller must use it so a
    future selection variant cannot leave a stale copy claiming a
    multi-row combination is one-hot."""
    return (not multi) and bucket_s < 2


def krum_select_from_gram(
    gram,
    mask=None,
    radius=None,
    factors=None,
    bucket_idx=None,
    *,
    byz_bound: Optional[int] = None,
    m_select: int = 0,
    multi: bool = False,
    bucket_s: int = 1,
    use_clip: bool = True,
):
    """Krum/multi-Krum selection given the (n, n) Gram matrix of the
    messages — pure row-space algebra, no d-sized operand.

    ``gram`` may be the Gram of one matrix or the SUM of Grams over any
    partition of the coordinates (parameter leaves, shards): the Gram is
    additive, so the selection is then the whole-message decision.  Clip
    factors come from ``factors`` if given, else from ``diag(gram)`` at
    ``radius`` (``use_clip=False``: no clipping); Bucketing is the
    ``M G M^T`` triple product over the resident ``bucket_idx`` order.
    Returns ``(RowSelection, row_norms (n,) or None)``.
    """
    n = gram.shape[0]
    mask_b = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    mask_f = mask_b.astype(F32)
    norms = None
    if use_clip:
        if factors is None:
            norms = jnp.sqrt(jnp.maximum(jnp.diagonal(gram), 0.0))
            factors = clip_factor(norms, radius).astype(F32)
        else:
            factors = factors.astype(F32)
    else:
        factors = jnp.ones((n,), F32)

    if bucket_s >= 2:
        mask_f, factors_p, bucket_idx, pad_rows = _pad_bucket_aux(
            mask_f, factors, bucket_idx, n, bucket_s
        )
        n_p = n + pad_rows
        if pad_rows:
            gram = jnp.pad(gram, ((0, pad_rows), (0, pad_rows)))
        m_op, cnt = _bucket_operator(
            bucket_idx, mask_f, factors_p, n_p, bucket_s
        )
        # Gram of clipped bucket means
        g_eff = jnp.dot(jnp.dot(m_op, gram, precision=HIGHEST), m_op.T,
                        precision=HIGHEST)
        # the fp triple product is not exactly symmetric; Krum's
        # argmin-first tie-breaking on symmetric ties (mutual nearest
        # neighbours) needs d2[i,j] == d2[j,i] exactly
        g_eff = 0.5 * (g_eff + g_eff.T)
        mask_eff = cnt > 0.5
    else:
        g_eff = gram * (factors[:, None] * factors[None, :])
        mask_eff = mask_b

    sq_eff = jnp.diagonal(g_eff)
    d2 = masked_pairwise_d2(g_eff, sq_eff, mask_eff)
    scores = krum_scores(d2, mask_eff, byz_bound)

    if not multi:
        winner = jnp.argmin(scores)
        scale = factors[jnp.minimum(winner, n - 1)]
        if bucket_s < 2:
            # one-hot * factor: the weighted row-sum reproduces the
            # direct row-take bitwise (zero terms are exact)
            w_row = (
                jnp.arange(n, dtype=jnp.int32) == winner
            ).astype(F32) * scale
        else:
            # the winning bucket mean IS a row of the bucket operator
            w_row = m_op[winner][:n]
        sel = RowSelection(
            weights=w_row, denom=jnp.asarray(1.0, F32),
            winner=winner.astype(jnp.int32), scale=scale,
        )
        return sel, norms

    msel = multi_krum_selection(scores, mask_eff, byz_bound, m_select)
    w_sel = msel.astype(F32)
    denom = jnp.maximum(jnp.sum(w_sel), 1.0)
    if bucket_s < 2:
        w_row = w_sel * factors
    else:
        # selected-bucket means as one weighted row-sum over the raw rows
        w_row = jnp.dot(w_sel, m_op, precision=HIGHEST)[:n]
    sel = RowSelection(
        weights=w_row, denom=denom,
        winner=jnp.argmin(scores).astype(jnp.int32),
        scale=jnp.asarray(1.0, F32),
    )
    return sel, norms


def apply_row_selection(xs, selection: RowSelection, *,
                        onehot: bool = False, interpret: bool = False):
    """Apply a RowSelection to a coordinate block sharing its row space:
    the final tile-wise kernel pass of the fused Krum path (one streaming
    read of ``xs``, combination in-register).

    ``onehot=True`` (valid exactly when the selection is plain unbucketed
    Krum's one-hot combination — the caller knows this statically from
    ``multi``/``bucket_s``) takes the single-row fast path: the
    scalar-prefetch ``select_row`` kernel streams only the sublane tile
    group holding the winner, row_tile*d elements instead of n*d, with
    bitwise-identical output."""
    if onehot:
        out = select_row(
            xs, selection.winner, selection.scale, interpret=interpret
        )
    else:
        out = weighted_row_sum(xs, selection.weights, interpret=interpret)
    return (out / selection.denom).astype(xs.dtype)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=(
        "byz_bound", "m_select", "multi", "bucket_s", "use_clip",
        "reduce_fn", "interpret"
    ),
)
def clip_then_krum(
    xs,
    radius,
    mask=None,
    bucket_idx=None,
    factors=None,
    *,
    byz_bound: Optional[int] = None,
    m_select: int = 0,
    multi: bool = False,
    bucket_s: int = 1,
    use_clip: bool = True,
    reduce_fn=None,
    interpret: bool = False,
):
    """Fused Krum/multi-Krum over per-row l2-clipped messages.

    One Gram streaming pass; clip factors (from diag G, or precomputed
    ``factors``) and Bucketing are applied as (n, n) algebra
    (``krum_select_from_gram``); the winner/weighted-average is
    reconstructed by the tile-wise ``weighted_row_sum`` kernel — a second
    streaming pass, never a host-level row gather.  ``reduce_fn``
    (static) sums the (n, n) Gram across coordinate shards (a psum
    inside shard_map): distances — and therefore the selection — then
    match the full-vector semantics exactly even though each chip only
    streams its own (n, d/W) block.  Returns
    ``(aggregated (d,), row_norms (n,) or None)``; ``use_clip=False``
    gives the plain aggregation (factors = 1, norms = None).
    """
    gram = gram_matrix(xs, interpret=interpret)
    if reduce_fn is not None:
        gram = reduce_fn(gram)
    selection, norms = krum_select_from_gram(
        gram, mask, radius, factors, bucket_idx,
        byz_bound=byz_bound, m_select=m_select, multi=multi,
        bucket_s=bucket_s, use_clip=use_clip,
    )
    # plain unbucketed Krum's combination is one-hot: stream only the
    # winner's sublane tile group (row_tile rows) instead of all n rows
    out = apply_row_selection(
        xs, selection, onehot=selection_is_onehot(multi, bucket_s),
        interpret=interpret,
    )
    return out, norms


def krum(xs, mask=None, *, byz_bound: Optional[int] = None,
         interpret: bool = False):
    """(n, d) -> (d,) plain (unclipped) kernel-backed Krum."""
    out, _ = clip_then_krum(
        xs, 0.0, mask, byz_bound=byz_bound, use_clip=False,
        interpret=interpret,
    )
    return out


def multi_krum(xs, mask=None, *, byz_bound: Optional[int] = None,
               m_select: int = 0, interpret: bool = False):
    """(n, d) -> (d,) plain kernel-backed multi-Krum (mean of best rows)."""
    out, _ = clip_then_krum(
        xs, 0.0, mask, byz_bound=byz_bound, m_select=m_select, multi=True,
        use_clip=False, interpret=interpret,
    )
    return out
