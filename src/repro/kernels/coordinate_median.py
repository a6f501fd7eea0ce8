"""Pallas TPU kernel: masked coordinate-wise median / trimmed mean over
workers.

The server aggregation streams (n_workers, d) with d ~ 1e8..1e11 and tiny
n (<= 64): a memory-bound reduction.  TPU mapping (vs. GPU per-coordinate
warp sorts): each grid step DMAs one wide (n, width) block of about
``BLOCK_BYTES`` (``stream_geometry``: 65,536 lanes at n = 4 bf16, so the
77M-coordinate embedding leaf takes 1,179 steps where 512-lane tiles took
150,840, each paying the pipeline's fixed per-step cost), and a
``fori_loop`` walks the block in lane chunks (``select_stream``):

  n < 8 and d % 128 == 0   a (n, 64, 128) chunk: each row fills 8 whole
      f32 vregs instead of n of their 8 sublanes.  The result is stored
      lane-dense into a (d / 128, 128) output, which reshapes to (d,) as
      a bitcast.
  otherwise   rows on sublanes, (n, TILE_D) chunks (the rank temporaries
      stay (n, n, TILE_D), so n = 64 fits VMEM) and a (1, d) output.

Either way an O(n^2) comparison-count selection network over the rows
(``_select_masked``) picks the order statistics.

The grid is ``cdiv(d, width)`` with a partial last block (no pad copy of the
(n, d) operand); out-of-range lanes compute garbage that the block's write
back drops.

Masking (partial participation) pushes unsampled rows to +BIG so they sort
to the top; ranks are made unique with index tie-breaking, so the selected
order statistics match numpy median semantics exactly, and both layouts
select the same values bit for bit.
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
SUBLANES = 8  # f32 rows of a vreg
DENSE_CHUNK = 8 * SUBLANES * LANES  # lanes of a dense chunk: 8 vregs a row
BLOCK_BYTES = 1 << 19  # (n, d) input bytes a streaming grid step DMAs


def stream_geometry(n, d, itemsize):
    """``(width, chunk)`` lanes of the streaming kernels' block and of the
    inner loop's chunk, from what the call observes.

    With n < 8 rows and d a multiple of 128 the chunk is ``DENSE_CHUNK``
    lanes (the dense layout, ``select_stream``); otherwise it is
    ``TILE_D``, and a leaf of at most one tile is one block and one chunk
    of width d.  ``width`` is the multiple of ``chunk`` nearest below
    ``BLOCK_BYTES`` of input, capped at d rounded up to a chunk."""
    if n < SUBLANES and d % LANES == 0:
        chunk = DENSE_CHUNK
    elif d <= TILE_D:
        return d, d
    else:
        chunk = TILE_D
    width = max(chunk, BLOCK_BYTES // (n * itemsize) // chunk * chunk)
    return min(width, pl.cdiv(d, chunk) * chunk), chunk


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
    """Unique ranks of each row per coordinate, shaped like ``vals``
    ((n, td) or (n, s, 128)): rank_i = #{j: v_j < v_i} + #{j < i: v_j ==
    v_i} (int32)."""
    vi = vals[:, None]  # (n, 1, ...)
    vj = vals[None, :]  # (1, n, ...)
    less = (vj < vi).astype(jnp.int32)
    tail = (1,) * (vals.ndim - 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n) + tail, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n) + tail, 1)
    tie = ((vj == vi) & (jj < ii)).astype(jnp.int32)
    return jnp.sum(less + tie, axis=1)


def _select_masked(vals, ok_mask_f32, *, trim_ratio):
    """Masked order-statistic selection over the rows (axis 0) of a
    (m, td) or (m, s, 128) chunk; returns the chunk with axis 0 kept as 1.

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


def select_stream(mask_ref, *refs, chunk, trim_ratio):
    """The streaming kernel: masked selection over an (n, width) block,
    ``chunk`` lanes at a time.

    ``refs`` are ``[factor_ref,] x_ref, o_ref``; ``mask_ref`` and the
    optional ``factor_ref`` are (n, 1), and each chunk's rows are scaled by
    the factors and pushed to +BIG where masked out.  A ``DENSE_CHUNK``
    (n < 8) becomes (n, s, 128), each row filling s whole vregs, and its
    result goes to s rows of the lane-dense (width / 128, 128) output
    block.  Otherwise the rows sit on sublanes and the result goes to the
    matching lanes of the (1, width) output block.  Shared by the CM/TM
    kernels and the fused clip->aggregate kernel (clip_aggregate.py)."""
    *factor_ref, x_ref, o_ref = refs
    n, width = x_ref.shape
    dense = chunk == DENSE_CHUNK
    s = chunk // LANES
    shape = (n, s, LANES) if dense else (n, chunk)
    col = (n,) + (1,) * (len(shape) - 1)
    m = mask_ref[...].astype(F32).reshape(col)
    f = factor_ref[0][...].astype(F32).reshape(col) if factor_ref else None

    def body(c, carry):
        off = chunk_offset(c, chunk)
        x = x_ref[:, pl.ds(off, chunk)].astype(F32).reshape(shape)
        if f is not None:
            x = x * f
        out = _select_masked(jnp.where(m > 0.5, x, _BIG), m,
                             trim_ratio=trim_ratio)
        if dense:
            o_ref[pl.ds(chunk_offset(c, s), s), :] = out[0].astype(o_ref.dtype)
        else:
            o_ref[:, pl.ds(off, chunk)] = out.astype(o_ref.dtype)
        return carry

    for_chunks(width // chunk, body)


def for_chunks(count, body, carry=0):
    """``carry = body(c, carry)`` for c in range(count): a ``fori_loop``,
    or one call with c = 0 where count is 1, so that a block of a lane
    count that is no multiple of 128 is read at the static offset 0."""
    if count == 1:
        return body(0, carry)
    return jax.lax.fori_loop(0, count, body, carry)


def chunk_offset(c, size):
    """Start of chunk ``c`` of ``size`` lanes or rows, marked as a multiple
    of ``size`` for the TPU compiler where c is traced."""
    return c * size if isinstance(c, int) else pl.multiple_of(c * size, size)


def stream_rows(xs, mask, factors=None, *, trim_ratio, name, interpret):
    """(n, d) -> (d,) through ``select_stream`` over ``stream_geometry``
    blocks of ``xs``.

    ``mask`` and ``factors`` (or None) are (n,) f32, resident in every
    step.  The grid is ``cdiv(d, width)``: the last block may run past d,
    and its out-of-range outputs are dropped on write.  The dense layout's
    output is (d / 128, 128), which reshapes to (d,) as a bitcast."""
    n, d = xs.shape
    width, chunk = stream_geometry(n, d, xs.dtype.itemsize)
    if chunk == DENSE_CHUNK:
        out_spec = pl.BlockSpec((width // LANES, LANES), lambda i: (i, 0))
        out_shape = (d // LANES, LANES)
    else:
        out_spec = pl.BlockSpec((1, width), lambda i: (0, i))
        out_shape = (1, d)
    resident = [r.reshape(n, 1) for r in (mask, factors) if r is not None]
    out = pl.pallas_call(
        functools.partial(select_stream, chunk=chunk, trim_ratio=trim_ratio),
        grid=(pl.cdiv(d, width),),
        in_specs=[pl.BlockSpec((n, 1), lambda i: (0, 0)) for _ in resident]
        + [pl.BlockSpec((n, width), lambda i: (0, i))],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, xs.dtype),
        interpret=interpret,
        name=name,
    )(*resident, xs)
    return out.reshape(d)


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

    Streams wide (n, width) blocks (``stream_geometry``); no pad of ``xs``.
    """
    n = xs.shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    return stream_rows(
        xs,
        mask.astype(jnp.float32),
        trim_ratio=trim_ratio,
        name="coordinate_median" if trim_ratio < 0 else "trimmed_mean",
        interpret=interpret,
    )
