"""Public jit'd wrappers around the Pallas kernels.

On TPU the kernels run compiled; on CPU (the test platform) they execute
in ``interpret=True`` mode — the kernel bodies run in Python with
identical semantics, which is what the allclose sweeps in
tests/test_kernels.py rely on.  Any other platform is an error: the
kernels are written for the TPU, and interpreting them elsewhere would
hide that they never ran compiled.  Callers never pass ``interpret``
themselves.

Backend contract (``repro.core.aggregators.make_aggregator(backend=...)``;
the declarative entry point selecting it is
``repro.api.ServerPlan.schedule.backend`` — plans compile to aggregators
through this same dispatch, so the coverage matrix below is also the
plan-level backend contract):

- ``backend="jnp"``    — pure-jnp aggregation everywhere (the reference
  path; always available, used inside vmap/shard_map/pjit freely).
- ``backend="pallas"`` — every registry rule is kernel-backed.  The
  (aggregator x fused x sharded) coverage matrix:

  =================  ==============  =====================  ============
  rule               plain kernel    fused clip->aggregate  Bucketing
  =================  ==============  =====================  ============
  cm / trimmed_mean  selection net   2-stream 2-pass        resident
                     (wide blocks)   (clip_aggregate.py)    row-gather
  mean               TM(t=0) blocks  same 2-stream kernel   row-gather
  krum / multi_krum  MXU Gram tile   2 streams: Gram pass   Gram algebra
                     (krum.py)       (factors = f(diag G),  M G M^T
                                     G_c = ff^T o G) +
                                     tile-wise winner
                                     row-sum pass
  centered_clip      resident or     factors in-register    in-register
                     d-tiled iters   (no clipped matrix)    bucket means
  rfa (Weiszfeld)    resident or     factors in-register    in-register
                     d-tiled iters   (no clipped matrix)    bucket means
  =================  ==============  =====================  ============

  No rule silently falls back to jnp, and the iterative kernels no longer
  fall back to the reference for large d — they switch to an explicit
  coordinate-tiled schedule with a cross-tile norm reduction.  All fused
  wrappers additionally accept precomputed per-row ``factors`` which skip
  the norm pass: the sharded trainer (launch/train.py) clips by *global*
  per-worker tree norms, which a chip-local block cannot compute, so it
  passes factors into the per-chip fused kernel inside shard_map.

  Krum/multi-Krum additionally export the TWO-PHASE selection contract
  (whole-tree selection across a per-leaf loop): ``krum_gram`` per
  coordinate block, SUM the (n, n) Grams (the Gram is additive over any
  coordinate partition — leaves, shards, superleaf chunks), then
  ``krum_select_from_gram`` once on the total and ``krum_apply`` (the
  tile-wise winner row-sum kernel) per block.  Both phases also consume
  PACKED CHUNK LISTS (the ``tree_superleaf_pack`` layout the pipelined
  mesh schedule runs on): ``krum_gram`` of a list accumulates the blocks'
  Grams in order, ``krum_apply`` of a list applies the selection per
  chunk.  Plain (unbucketed) Krum's apply is a one-hot combination, so
  ``krum_apply(..., onehot=True)`` takes the scalar-prefetch
  ``select_row`` kernel that streams ONLY the sublane tile group holding
  the winner — ``row_tile`` rows (8 f32 / 16 bf16, or all n when n is
  smaller) instead of n.  ``clip_then_krum`` is that pipeline for a
  single matrix; winner reconstruction never gathers rows on the host.
- ``backend="auto"``   — picks ``pallas`` on the TPU and ``jnp`` on the
  CPU; any other platform is an error.  On CPU the pallas choice still
  *works* (interpret mode) and is what the equivalence tests use.

The backend probe is memoized at module level: the default jax backend
cannot change within a process, and ``jax.default_backend()`` initializes
the platform on every call — too expensive for a per-kernel-invocation
check.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref  # noqa: F401  (re-exported for convenience)
from .bucketing import bucketed_coordinate_median as _bucketed_cm
from .centered_clip import centered_clip as _centered_clip
from .centered_clip import clip_then_centered_clip as _clip_then_cclip
from .clip_aggregate import clip_then_aggregate as _clip_then_aggregate
from .clipped_diff import clipped_diff as _clipped_diff
from .coordinate_median import coordinate_median as _coordinate_median
from .geometric_median import clip_then_geometric_median as _clip_then_gm
from .geometric_median import geometric_median as _geometric_median
from .krum import RowSelection  # noqa: F401  (re-exported)
from .krum import apply_row_selection as _apply_row_selection
from .krum import clip_then_krum as _clip_then_krum
from .krum import cross_gram as _cross_gram
from .krum import gram_matrix as _gram_matrix
from .krum import krum as _krum
from .krum import krum_select_from_gram  # noqa: F401  (pure row-space jnp)
from .krum import multi_krum as _multi_krum
from .krum import select_row as _select_row
from .krum import selection_is_onehot  # noqa: F401  (re-exported)
from .krum import weighted_row_sum as _weighted_row_sum

__all__ = [
    "coordinate_median",
    "trimmed_mean",
    "clipped_diff",
    "clip_then_aggregate",
    "centered_clip",
    "clip_then_centered_clip",
    "geometric_median",
    "clip_then_geometric_median",
    "krum",
    "multi_krum",
    "clip_then_krum",
    "krum_gram",
    "krum_cross_gram",
    "krum_select_from_gram",
    "krum_apply",
    "select_row",
    "selection_is_onehot",
    "accumulate_stats_blocks",
    "apply_selection_blocks",
    "weighted_row_sum",
    "RowSelection",
    "bucketed_coordinate_median",
    "ref",
]

_INTERPRET: Optional[bool] = None


def kernel_platform() -> str:
    """The default platform, which must be one the kernels support:
    ``tpu`` (compiled) or ``cpu`` (interpret mode)."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels run compiled on 'tpu' or interpreted on "
            f"'cpu'; the default platform is {platform!r}"
        )
    return platform


def _interpret() -> bool:
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = kernel_platform() == "cpu"
    return _INTERPRET


def coordinate_median(xs, mask=None):
    return _coordinate_median(xs, mask, trim_ratio=-1.0, interpret=_interpret())


def trimmed_mean(xs, mask=None, trim_ratio: float = 0.1):
    return _coordinate_median(
        xs, mask, trim_ratio=trim_ratio, interpret=_interpret()
    )


def clipped_diff(g_new, g_old, radius, keep_mask, scale):
    return _clipped_diff(
        g_new, g_old, radius, keep_mask, scale, interpret=_interpret()
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
):
    """Fused per-row clip at ``radius`` -> masked CM/TM (optionally over
    ``bucket_s``-buckets in the ``bucket_idx`` row order).  ``factors``
    skips the norm pass and applies the given per-row scales; ``reduce_fn``
    makes the pass-1 norms global across coordinate shards (see the
    backend contract above).  Returns
    (aggregated (d,), row_norms (n,) or None)."""
    return _clip_then_aggregate(
        xs,
        radius,
        mask,
        bucket_idx,
        factors,
        trim_ratio=trim_ratio,
        bucket_s=bucket_s,
        use_clip=use_clip,
        reduce_fn=reduce_fn,
        interpret=_interpret(),
    )


def centered_clip(xs, mask=None, *, tau: float = 10.0, iters: int = 5):
    return _centered_clip(
        xs, mask, tau=tau, iters=iters, interpret=_interpret()
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
):
    """Fused clip -> (Bucketing) -> CenteredClip.  Returns
    (aggregated (d,), row_norms (n,) or None)."""
    return _clip_then_cclip(
        xs,
        radius,
        mask,
        bucket_idx,
        factors,
        tau=tau,
        iters=iters,
        bucket_s=bucket_s,
        use_clip=use_clip,
        reduce_fn=reduce_fn,
        interpret=_interpret(),
    )


def geometric_median(xs, mask=None, *, iters: int = 8, eps: float = 1e-8):
    return _geometric_median(
        xs, mask, iters=iters, eps=eps, interpret=_interpret()
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
):
    """Fused clip -> (Bucketing) -> Weiszfeld geometric median.  Returns
    (aggregated (d,), row_norms (n,) or None)."""
    return _clip_then_gm(
        xs,
        radius,
        mask,
        bucket_idx,
        factors,
        iters=iters,
        eps=eps,
        bucket_s=bucket_s,
        use_clip=use_clip,
        reduce_fn=reduce_fn,
        interpret=_interpret(),
    )


def krum(xs, mask=None, *, byz_bound: Optional[int] = None):
    return _krum(xs, mask, byz_bound=byz_bound, interpret=_interpret())


def multi_krum(xs, mask=None, *, byz_bound: Optional[int] = None,
               m_select: int = 0):
    return _multi_krum(
        xs, mask, byz_bound=byz_bound, m_select=m_select,
        interpret=_interpret(),
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
):
    """Fused clip -> (Bucketing) -> Krum / multi-Krum via one Gram stream.
    Returns (aggregated (d,), row_norms (n,) or None)."""
    return _clip_then_krum(
        xs,
        radius,
        mask,
        bucket_idx,
        factors,
        byz_bound=byz_bound,
        m_select=m_select,
        multi=multi,
        bucket_s=bucket_s,
        use_clip=use_clip,
        reduce_fn=reduce_fn,
        interpret=_interpret(),
    )


def accumulate_stats_blocks(stats_fn, xs, reduce_fn=None):
    """THE chunk-list adapter for two-phase phase 1: run ``stats_fn``
    over one (n, d) block, or accumulate it in list order over a packed
    chunk list (the ``tree_superleaf_pack`` layout).  Shared by the
    dispatch-layer ``krum_gram`` and ``Aggregator.accumulate_stats`` so
    the two layers' chunk semantics cannot diverge."""
    if isinstance(xs, (list, tuple)):
        stats = None
        for block in xs:
            g = stats_fn(block, reduce_fn=reduce_fn)
            stats = g if stats is None else stats + g
        if stats is None:
            raise ValueError("accumulate_stats: empty chunk list")
        return stats
    return stats_fn(xs, reduce_fn=reduce_fn)


def apply_selection_blocks(apply_fn, xs, selection):
    """Chunk-list adapter for two-phase phase 3: apply a finalized
    selection to one block, or per-chunk over a packed list (returns the
    per-chunk outputs).  Shared by ``krum_apply`` and
    ``Aggregator.apply_selection``."""
    if isinstance(xs, (list, tuple)):
        return [apply_fn(block, selection) for block in xs]
    return apply_fn(xs, selection)


def _krum_gram_one(xs, reduce_fn=None):
    gram = _gram_matrix(xs, interpret=_interpret())
    return reduce_fn(gram) if reduce_fn is not None else gram


def krum_gram(xs, reduce_fn=None):
    """(n, d) -> (n, n) f32 Gram block via the tile-accumulated MXU
    kernel — phase 1 of the two-phase Krum contract.  ``reduce_fn`` (a
    psum inside shard_map) turns a chip-local block Gram into the global
    one; summing the results over parameter leaves gives the whole-tree
    Gram (the Gram is additive over any coordinate partition).

    ``xs`` may also be a LIST of packed coordinate chunks (the
    ``tree_superleaf_pack`` layout): the chunks' Grams are accumulated in
    list order, one kernel launch per chunk."""
    return accumulate_stats_blocks(_krum_gram_one, xs, reduce_fn=reduce_fn)


def krum_cross_gram(a, b):
    """(n, d), (n, d) -> (n, n) f32 cross-Gram A B^T via the same
    TILE_D-tiled MXU grid as ``krum_gram`` — ``krum_cross_gram(x, x)``
    is bitwise-equal to ``krum_gram(x)``.  Phase-1 building block of the
    INCREMENTAL cohort ingest path (repro.serve): with a chunk embedded
    at its slot rows in a zero (n, d) matrix and the running row buffer
    as the second operand, the off-diagonal blocks come out with the same
    per-entry reduction order as the one-shot Gram."""
    return _cross_gram(a, b, interpret=_interpret())


def krum_apply(xs, selection, *, onehot: bool = False):
    """Apply a RowSelection to a coordinate block (or a list of packed
    chunks — one apply pass per chunk): the final tile-wise winner
    row-sum kernel pass (one streaming read, no host gather).

    ``onehot=True`` — valid exactly when the caller statically knows the
    selection is plain unbucketed Krum's one-hot combination
    (``selection_is_onehot``) — streams only the sublane tile group
    holding the winner via the scalar-prefetch ``select_row`` kernel
    (row_tile*d elements instead of n*d), bitwise-equal to the full pass."""
    return apply_selection_blocks(
        lambda block, sel: _apply_row_selection(
            block, sel, onehot=onehot, interpret=_interpret()
        ),
        xs,
        selection,
    )


def select_row(xs, winner, scale):
    """(n, d), () int32, () f32 -> (d,) f32: the single-row fast path —
    stream ONLY the sublane tile group holding the winner via a
    scalar-prefetch index_map (row_tile*d streamed elements;
    ``weighted_row_sum`` of a one-hot reads n*d)."""
    return _select_row(xs, winner, scale, interpret=_interpret())


def weighted_row_sum(xs, w_row):
    """(n, d), (n,) -> (d,) f32 tile-wise weighted row-sum kernel."""
    return _weighted_row_sum(xs, w_row, interpret=_interpret())


def bucketed_coordinate_median(xs, key, mask=None, *, s: int = 2):
    return _bucketed_cm(xs, key, mask, s=s, interpret=_interpret())
