"""(delta, c)-robust aggregation rules (Definition 2.1) and Bucketing.

All aggregators operate on a stacked matrix ``xs`` of shape (n, d) — one row
per worker — and return the aggregated vector of shape (d,).  Every rule
also supports an optional boolean ``mask`` of shape (n,) selecting the
*sampled* cohort S_k (partial participation under SPMD static shapes: all
workers compute, only sampled rows aggregate).  ``mask=None`` means all rows.

The registry records for each rule:

  - whether it satisfies Def 2.1 on its own or only composed with Bucketing
    (Karimireddy et al., 2022), and
  - the bounded-output constant F_A of Assumption 2.3
    (Krum/GM: 1; CM: sqrt(d); mean: 1), used by theory.py for stepsizes.

Aggregations are pure-jnp so the same code runs inside vmap / shard_map /
pjit; the Pallas kernels in repro.kernels implement the hot (n,d)->d paths
with explicit VMEM tiling and are verified against these references.

``make_aggregator(..., backend=)`` selects which implementation backs the
returned rule: ``"jnp"`` (reference), ``"pallas"`` (kernel-backed — the
registry is kernel-complete: CM/TM/mean via the selection-network tiles,
krum/multi-krum via the MXU Gram kernel, centered-clip and Weiszfeld GM
via the resident/coordinate-tiled iteration kernels, each including the
fused server-side clip->aggregate used by the engine's difference rounds
and the Bucketing composition), or ``"auto"`` (pallas iff running on
TPU).  See repro.kernels.ops for the full contract and coverage matrix.

Krum selection semantics (distance masking, neighbour counting,
tie-breaking) are shared helpers in repro.kernels.krum used by BOTH
backends, so exact ties resolve identically under a backend swap (see
kernels/krum.py for the ulp-level caveat on near-ties of distinct
scores).

Selection rules (krum/multi_krum, plain or bucketed) additionally expose
a TWO-PHASE contract so callers that loop over several coordinate blocks
sharing the same rows (the mesh trainer's per-parameter-leaf loop) can
make ONE whole-message decision without materializing the stacked
matrix:

    stats  = sum(agg.accumulate_stats(block) for block in blocks)
    sel    = agg.finalize(stats, mask=..., key=..., factors=...)
    outs   = [agg.apply_selection(block, sel) for block in blocks]

``accumulate_stats`` returns the (n, n) Gram contribution of a block
(additive over any coordinate partition), ``finalize`` runs the shared
selection algebra once on the total, and ``apply_selection`` applies the
resulting row combination to each block (on the pallas backend: the
tile-wise winner row-sum kernel, or — for plain unbucketed Krum, whose
combination is one-hot — the scalar-prefetch single-row kernel that
streams only the sublane tile group holding the winner).  Both phases also consume PACKED CHUNK
LISTS (``tree_utils.tree_superleaf_pack``): ``accumulate_stats`` of a
list sums the chunks' Grams in order, ``apply_selection`` of a list
returns the per-chunk outputs — the layout the pipelined mesh schedule
runs on.  ``Aggregator.supports_two_phase`` reports availability;
``clip_then_aggregate`` remains the one-shot equivalent for a single
matrix.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as _kops
from ..kernels.krum import (
    HIGHEST as _HIGHEST,
    RowSelection,
    krum_scores as _krum_scores,
    krum_select_from_gram as _krum_select_from_gram,
    masked_pairwise_d2 as _masked_pairwise_d2,
    multi_krum_selection as _multi_krum_selection,
)
from .clipping import clip as _clip
from .tree_utils import tree_batch_ravel

__all__ = [
    "Aggregator",
    "RowSelection",
    "mean",
    "coordinate_median",
    "trimmed_mean",
    "geometric_median",
    "krum",
    "multi_krum",
    "centered_clip",
    "bucketing",
    "make_aggregator",
    "resolve_backend",
    "RULE_ALIASES",
]

_BIG = jnp.float32(3.4e37)  # +inf stand-in that survives arithmetic


def _full_mask(xs, mask):
    if mask is None:
        return jnp.ones((xs.shape[0],), dtype=bool)
    return mask.astype(bool)


# ---------------------------------------------------------------------------
# basic rules
# ---------------------------------------------------------------------------

def _mean(xs, mask=None, key=None, reduce_fn=None):
    m = _full_mask(xs, mask).astype(xs.dtype)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    return jnp.sum(xs * m[:, None], axis=0) / denom


def _masked_sorted(xs, mask):
    """Sort each column ascending with un-sampled rows pushed to +inf.

    Returns (sorted values (n,d), count of sampled rows)."""
    m = _full_mask(xs, mask)
    vals = jnp.where(m[:, None], xs.astype(jnp.float32), _BIG)
    return jnp.sort(vals, axis=0), jnp.sum(m)


def _coordinate_median(xs, mask=None, key=None, reduce_fn=None):
    """Coordinate-wise median over the sampled rows (numpy semantics: the
    average of the two middle order statistics for even counts).
    ``reduce_fn`` is accepted (uniform rule signature) but unused:
    coordinate-wise rules are exact on coordinate shards."""
    s, cnt = _masked_sorted(xs, mask)
    lo = (cnt - 1) // 2
    hi = cnt // 2
    v_lo = jnp.take_along_axis(s, jnp.full((1, s.shape[1]), lo), axis=0)[0]
    v_hi = jnp.take_along_axis(s, jnp.full((1, s.shape[1]), hi), axis=0)[0]
    return (0.5 * (v_lo + v_hi)).astype(xs.dtype)


def _trimmed_mean(xs, mask=None, key=None, reduce_fn=None, *,
                  trim_ratio: float = 0.1):
    """Coordinate-wise trimmed mean: drop ceil(trim_ratio*cnt) smallest and
    largest entries per coordinate, average the rest.  Satisfies Def 2.1
    (Allouah et al., 2023) when trim_ratio >= delta."""
    s, cnt = _masked_sorted(xs, mask)
    n = s.shape[0]
    t = jnp.ceil(trim_ratio * cnt).astype(jnp.int32)
    t = jnp.minimum(t, (cnt - 1) // 2)
    idx = jnp.arange(n)[:, None]
    keep = (idx >= t) & (idx < cnt - t)
    denom = jnp.maximum(cnt - 2 * t, 1)
    sv = jnp.where(keep, s, 0.0)
    return (jnp.sum(sv, axis=0) / denom).astype(xs.dtype)


def _geometric_median(xs, mask=None, key=None, reduce_fn=None, *,
                      iters: int = 8, eps: float = 1e-8):
    """Geometric median via smoothed Weiszfeld fixed-point iterations
    (Pillutla et al., 2022 — "RFA").  F_A = 1 (stays in the convex hull).

    ``reduce_fn`` reduces the per-row squared distances across coordinate
    shards (a psum inside shard_map) so the iteration runs on global
    distances when ``xs`` is one chip's coordinate block."""
    m = _full_mask(xs, mask).astype(jnp.float32)
    x32 = xs.astype(jnp.float32)
    z0 = jnp.sum(x32 * m[:, None], axis=0) / jnp.maximum(jnp.sum(m), 1.0)

    def body(_, z):
        ssq = jnp.sum((x32 - z[None]) ** 2, axis=1)
        if reduce_fn is not None:
            ssq = reduce_fn(ssq)
        dist = jnp.sqrt(ssq + eps)
        w = m / dist
        return jnp.sum(x32 * w[:, None], axis=0) / jnp.maximum(jnp.sum(w), eps)

    z = jax.lax.fori_loop(0, iters, body, z0)
    return z.astype(xs.dtype)


def _krum_scores_of(x32, mask_b, reduce_fn, byz_bound):
    """Krum scores of the rows of ``x32``: jnp Gram matrix (psum-reduced
    across coordinate shards when ``reduce_fn`` is set) fed into the
    selection helpers shared with the pallas backend (repro.kernels.krum)
    — masking, neighbour count and tie-breaking live in ONE place."""
    gram = jnp.dot(x32, x32.T, precision=_HIGHEST)
    if reduce_fn is not None:
        gram = reduce_fn(gram)
        sq = jnp.diagonal(gram)  # global row ssq comes from the reduction
    else:
        sq = jnp.sum(x32 * x32, axis=1)
    d2 = _masked_pairwise_d2(gram, sq, mask_b)
    return _krum_scores(d2, mask_b, byz_bound)


def _krum(xs, mask=None, key=None, reduce_fn=None, *,
          byz_bound: Optional[int] = None):
    """Krum (Blanchard et al., 2017): return the row minimizing the summed
    squared distance to its n-B-2 nearest sampled neighbours.  F_A = 1."""
    m = _full_mask(xs, mask)
    x32 = xs.astype(jnp.float32)
    scores = _krum_scores_of(x32, m, reduce_fn, byz_bound)
    winner = jnp.argmin(scores)
    return xs[winner]


def _multi_krum(xs, mask=None, key=None, reduce_fn=None, *,
                byz_bound: Optional[int] = None, m_select: int = 0):
    """Multi-Krum (Damaskinos et al., 2019): average the m rows with the
    best Krum scores.  m defaults to cnt - B - 2."""
    m0 = _full_mask(xs, mask)
    x32 = xs.astype(jnp.float32)
    scores = _krum_scores_of(x32, m0, reduce_fn, byz_bound)
    sel = _multi_krum_selection(scores, m0, byz_bound, m_select)
    w = sel.astype(jnp.float32)
    return (
        jnp.sum(x32 * w[:, None], axis=0) / jnp.maximum(jnp.sum(w), 1.0)
    ).astype(xs.dtype)


def _centered_clip(
    xs, mask=None, key=None, reduce_fn=None, *, tau: float = 10.0,
    iters: int = 5
):
    """CenteredClip (Karimireddy et al., 2021):
       v <- v + mean_i clip_tau(x_i - v), iterated.  F_A depends on tau; with
       v0 = masked mean it stays within tau*iters of the hull => bounded."""
    m = _full_mask(xs, mask).astype(jnp.float32)
    x32 = xs.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    v0 = jnp.sum(x32 * m[:, None], axis=0) / denom

    def body(_, v):
        diff = x32 - v[None]
        ssq = jnp.sum(diff * diff, axis=1)
        if reduce_fn is not None:
            ssq = reduce_fn(ssq)
        nrm = jnp.sqrt(ssq + 1e-30)
        scale = jnp.minimum(1.0, tau / nrm)
        upd = jnp.sum(diff * (scale * m)[:, None], axis=0) / denom
        return v + upd

    v = jax.lax.fori_loop(0, iters, body, v0)
    return v.astype(xs.dtype)


# ---------------------------------------------------------------------------
# Bucketing (Algorithm 2, Karimireddy et al., 2022)
# ---------------------------------------------------------------------------

def _bucket_order(key, mask, n):
    """The row order Bucketing aggregates in: a random permutation stably
    re-sorted so sampled rows come first (dense buckets).  Shared by the
    jnp `_bucketing` and the pallas fused path — the backends' trajectory
    equivalence depends on this being the single source of truth."""
    if key is None:
        key = jax.random.PRNGKey(0)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    perm = jax.random.permutation(key, n)
    order = jnp.argsort(jnp.where(m[perm], 0, 1), stable=True)
    return perm[order]


def _bucketing(xs, mask=None, key=None, reduce_fn=None, *, s: int = 2,
               inner=None):
    """Randomly permute rows, average buckets of size ``s``, apply ``inner``.

    With a mask, bucket means are taken over sampled members only and empty
    buckets are masked out of the inner aggregation — this preserves the
    ARAgg property over the sampled cohort.
    """
    if inner is None:
        inner = _coordinate_median
    n = xs.shape[0]
    m = _full_mask(xs, mask)
    idx = _bucket_order(key, mask, n)
    xp = xs[idx]
    mp = m[idx]
    n_buckets = -(-n // s)
    pad = n_buckets * s - n
    xp = jnp.pad(xp, ((0, pad), (0, 0)))
    mp = jnp.pad(mp, ((0, pad),))
    xb = xp.reshape(n_buckets, s, -1)
    mb = mp.reshape(n_buckets, s).astype(xs.dtype)
    cntb = jnp.sum(mb, axis=1)
    means = jnp.sum(xb * mb[:, :, None], axis=1) / jnp.maximum(cntb, 1.0)[:, None]
    bucket_mask = cntb > 0
    # bucket means are linear, hence exact per coordinate shard; only the
    # inner rule needs the cross-shard reduction
    return inner(means, mask=bucket_mask, reduce_fn=reduce_fn)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Aggregator:
    """A named aggregation rule with its theory constants.

    ``f_a(d)``: the Assumption-2.3 bound ||A(x_1..x_n)|| <= F_A max||x_i||.
    ``is_aragg``: satisfies Def 2.1 agnostically (possibly via bucketing).
    ``backend``: which implementation backs ``fn`` ("jnp" or "pallas").
    ``fused_clip_fn``: when set (pallas CM/TM), computes
    Agg({clip_radius(x_i)}) in one fused kernel pass-pair without
    materializing the clipped matrix; ``clip_then_aggregate`` falls back to
    per-row clip + ``fn`` otherwise.

    ``xs`` may be an (n, d) matrix or a pytree whose leaves carry a leading
    worker axis; pytrees are flattened into ONE contiguous (n, d) buffer
    (single kernel launch) and the result is unflattened.

    ``stats_fn``/``finalize_fn``/``apply_fn``: the two-phase selection
    contract (module docstring) for rules that can defer their decision
    across several coordinate blocks of one logical message; None for
    rules without a deferred form (coordinate-wise and iterative rules).
    """

    name: str
    fn: Callable
    f_a: Callable[[int], float]
    is_aragg: bool
    c_const: float  # the c in (delta, c)-RAgg (literature values)
    backend: str = "jnp"
    fused_clip_fn: Optional[Callable] = None
    stats_fn: Optional[Callable] = None
    finalize_fn: Optional[Callable] = None
    apply_fn: Optional[Callable] = None
    update_stats_fn: Optional[Callable] = None

    @property
    def supports_two_phase(self) -> bool:
        """Whether accumulate_stats/finalize/apply_selection are usable."""
        return self.stats_fn is not None

    def __call__(self, xs, mask=None, key=None, reduce_fn=None):
        """``reduce_fn`` reduces row statistics (norms, distances, Gram)
        across coordinate shards — a psum when ``xs`` is one chip's block
        inside shard_map; coordinate-wise rules ignore it."""
        if not hasattr(xs, "ndim"):
            mat, unravel_row = tree_batch_ravel(xs)
            return unravel_row(
                self.fn(mat, mask=mask, key=key, reduce_fn=reduce_fn)
            )
        return self.fn(xs, mask=mask, key=key, reduce_fn=reduce_fn)

    def clip_then_aggregate(self, xs, radius, mask=None, key=None,
                            factors=None, reduce_fn=None):
        """Agg over per-row l2-clipped messages (the Algorithm-1 server step
        for difference rounds).  Fused on the pallas backend.

        ``factors`` (n,) supplies precomputed per-row clip scales instead
        of clipping by the row norms of ``xs`` — the sharded trainer clips
        by *global* per-worker tree norms that a per-chip block cannot
        see, so it computes the factors once and passes them down here.
        ``reduce_fn`` as in ``__call__``."""
        if not hasattr(xs, "ndim"):
            mat, unravel_row = tree_batch_ravel(xs)
            return unravel_row(
                self.clip_then_aggregate(
                    mat, radius, mask=mask, key=key, factors=factors,
                    reduce_fn=reduce_fn,
                )
            )
        if self.fused_clip_fn is not None:
            return self.fused_clip_fn(
                xs, radius, mask=mask, key=key, factors=factors,
                reduce_fn=reduce_fn,
            )
        if factors is not None:
            clipped = (xs * factors[:, None]).astype(xs.dtype)
        else:
            clipped = jax.vmap(lambda v: _clip(v, radius))(xs)
        return self.fn(clipped, mask=mask, key=key, reduce_fn=reduce_fn)

    # -- two-phase selection (whole-message decision over many blocks) --

    def _require_two_phase(self):
        if self.stats_fn is None:
            raise NotImplementedError(
                f"aggregator {self.name!r} has no two-phase selection form"
            )

    def accumulate_stats(self, xs, reduce_fn=None):
        """Phase 1: the selection statistics contribution of one (n, d)
        coordinate block — for Krum rules the (n, n) Gram, which is
        additive over any coordinate partition of the message, so the
        caller sums the returns across its blocks.  ``xs`` may also be a
        LIST of packed chunks (``tree_superleaf_pack``): the chunks'
        contributions are accumulated in list order.  ``reduce_fn`` (a
        psum inside shard_map) makes a chip-local block's contribution
        global."""
        self._require_two_phase()
        return _kops.accumulate_stats_blocks(
            self.stats_fn, xs, reduce_fn=reduce_fn
        )

    def update_stats(self, stats, buffer, chunk_emb, chunk_mask):
        """Incremental phase 1 for STREAMING row arrival (repro.serve):
        fold a chunk of newly-arrived rows into the running (n, n) stats.

        ``buffer`` is the (n, d) cohort row buffer with the chunk's rows
        already scattered in; ``chunk_emb`` is the chunk embedded at its
        slot rows in a zero (n, d) matrix; ``chunk_mask`` is the (n,)
        bool chunk membership.  The cross product is computed at the
        FULL cohort shape (never a shrunken (c, d) matmul) so every
        entry's reduction order matches the one-shot ``accumulate_stats``
        — after the last row arrives the stats are bitwise-equal to the
        one-shot Gram of the full buffer, on both backends.  The price
        is n*n*d FLOPs per chunk instead of c*n*d."""
        self._require_two_phase()
        return self.update_stats_fn(stats, buffer, chunk_emb, chunk_mask)

    def finalize(self, stats, mask=None, key=None, radius=None,
                 factors=None):
        """Phase 2: run the selection once on the accumulated stats.

        Clipping semantics match ``clip_then_aggregate``: ``factors``
        supplies precomputed per-row scales (the sharded trainer's global
        tree norms); else ``radius`` clips by the row norms recovered
        from the stats (diag of the Gram); neither -> no clipping.
        Returns an opaque selection (a RowSelection pytree for Krum) to
        feed ``apply_selection``."""
        self._require_two_phase()
        return self.finalize_fn(
            stats, mask=mask, key=key, radius=radius, factors=factors
        )

    def apply_selection(self, xs, selection):
        """Phase 3: apply the finalized row combination to one (n, d)
        coordinate block (pallas: the tile-wise winner row-sum kernel,
        or the single-row scalar-prefetch kernel for plain Krum's
        one-hot combination), or to a LIST of packed chunks (returns the
        per-chunk outputs).  Whole-message aggregate = concat over
        blocks of the returns."""
        self._require_two_phase()
        return _kops.apply_selection_blocks(self.apply_fn, xs, selection)


def mean() -> Aggregator:
    return Aggregator("mean", _mean, lambda d: 1.0, False, 0.0)


def coordinate_median() -> Aggregator:
    return Aggregator(
        "cm", _coordinate_median, lambda d: math.sqrt(d), False, 1.0
    )


def trimmed_mean(trim_ratio: float = 0.1) -> Aggregator:
    return Aggregator(
        f"tm{trim_ratio}",
        partial(_trimmed_mean, trim_ratio=trim_ratio),
        lambda d: math.sqrt(d),
        True,
        1.0,
    )


def geometric_median(iters: int = 8) -> Aggregator:
    return Aggregator(
        "rfa", partial(_geometric_median, iters=iters), lambda d: 1.0, False, 1.0
    )


def krum(byz_bound: Optional[int] = None) -> Aggregator:
    return Aggregator(
        "krum", partial(_krum, byz_bound=byz_bound), lambda d: 1.0, False, 1.0
    )


def multi_krum(byz_bound: Optional[int] = None, m_select: int = 0) -> Aggregator:
    return Aggregator(
        "multikrum",
        partial(_multi_krum, byz_bound=byz_bound, m_select=m_select),
        lambda d: 1.0,  # average of input rows stays in the hull
        False,
        1.0,
    )


def centered_clip(tau: float = 10.0, iters: int = 5) -> Aggregator:
    return Aggregator(
        "cclip",
        partial(_centered_clip, tau=tau, iters=iters),
        lambda d: 1.0 + 0.0 * d,  # v0 in hull, each iter moves <= tau
        True,
        1.0,
    )


def bucketing(inner: Aggregator, s: int = 2) -> Aggregator:
    """Bucketing o inner — upgrades CM/GM/Krum to (delta,c)-ARAgg."""
    return Aggregator(
        f"bucket{s}_{inner.name}",
        partial(_bucketing, s=s, inner=inner.fn),
        inner.f_a,  # bucket means stay in the hull
        True,
        inner.c_const if inner.c_const > 0 else 1.0,
    )


_DEFAULT_TRIM = 0.1

# legacy mesh-config spellings -> canonical registry names.  The ServerPlan
# API (repro.api) normalizes through this same table, so the two layers'
# name spaces cannot diverge.
RULE_ALIASES = {
    "tm": "trimmed_mean",
    "cclip": "centered_clip",
    "gm": "rfa",
}

_FACTORY = {
    "mean": lambda **kw: mean(),
    "cm": lambda **kw: coordinate_median(),
    "trimmed_mean": lambda **kw: trimmed_mean(
        float(kw.get("trim_ratio", _DEFAULT_TRIM))
    ),
    "rfa": lambda **kw: geometric_median(int(kw.get("iters", 8))),
    "geometric_median": lambda **kw: geometric_median(int(kw.get("iters", 8))),
    "krum": lambda **kw: krum(kw.get("byz_bound")),
    "multi_krum": lambda **kw: multi_krum(
        kw.get("byz_bound"), int(kw.get("m_select", 0))
    ),
    "centered_clip": lambda **kw: centered_clip(
        float(kw.get("tau", 10.0)), int(kw.get("iters", 5))
    ),
}


# ---------------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------------

def resolve_backend(backend: str) -> str:
    """Resolve "auto" to the concrete backend for this process: the
    kernels on the TPU, the jnp reference on the CPU (an error on any
    other platform)."""
    if backend == "auto":
        return "pallas" if _kops.kernel_platform() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(
            f"unknown backend {backend!r}; have 'jnp', 'pallas', 'auto'"
        )
    return backend


def _make_pallas_fns(kernel_fn, bucket_s: int, **kernel_kwargs):
    """Kernel-backed (aggregate, fused clip+aggregate) pair from one of the
    ``clip_then_*`` kernels, optionally composed with Bucketing via the
    shared ``_bucket_order`` row-gather — same math as the jnp rules.

    ``kernel_fn(xs, radius, mask, bucket_idx, factors, *, bucket_s,
    use_clip, **kw) -> (out, norms)``."""

    def _idx(key, mask, n):
        return _bucket_order(key, mask, n) if bucket_s >= 2 else None

    def aggregate(xs, mask=None, key=None, reduce_fn=None):
        out, _ = kernel_fn(
            xs, 0.0, mask, _idx(key, mask, xs.shape[0]),
            bucket_s=max(bucket_s, 1), use_clip=False, reduce_fn=reduce_fn,
            **kernel_kwargs,
        )
        return out

    def fused_clip(xs, radius, mask=None, key=None, factors=None,
                   reduce_fn=None):
        out, _ = kernel_fn(
            xs, radius, mask, _idx(key, mask, xs.shape[0]), factors,
            bucket_s=max(bucket_s, 1), use_clip=True, reduce_fn=reduce_fn,
            **kernel_kwargs,
        )
        return out

    return aggregate, fused_clip


def _make_pallas_cm_fns(trim_ratio: float, bucket_s: int):
    """CM/TM/mean specialization: routes the bucket-free plain aggregation
    through the standalone CM/TM kernels (no factor pass at all)."""
    aggregate_f, fused_clip = _make_pallas_fns(
        _kops.clip_then_aggregate, bucket_s, trim_ratio=trim_ratio
    )

    def aggregate(xs, mask=None, key=None, reduce_fn=None):
        # reduce_fn unused: CM/TM are coordinate-wise (exact per shard)
        if bucket_s < 2:
            if trim_ratio < 0:
                return _kops.coordinate_median(xs, mask)
            return _kops.trimmed_mean(xs, mask, trim_ratio=trim_ratio)
        return aggregate_f(xs, mask=mask, key=key)

    return aggregate, fused_clip


def _krum_two_phase_fns(*, byz_bound, m_select, multi, bucket_s,
                        pallas: bool):
    """(stats_fn, finalize_fn, apply_fn, update_stats_fn) for
    krum/multi-krum on either backend.  The finalize algebra is the single shared
    ``krum_select_from_gram`` — masking, neighbour counting, Bucketing
    and tie-breaking live in ONE place — so the two backends (and the
    one-shot ``clip_then_krum``) can never select different rows.  Only
    the Gram computation and the apply pass differ: jnp matmul / exact
    dynamic row-take vs the MXU Gram kernel and the tile-wise winner
    row-sum kernel."""
    bs = max(bucket_s, 1)

    onehot = _kops.selection_is_onehot(multi, bs)
    if pallas:
        stats_fn = _kops.krum_gram
        cross_fn = _kops.krum_cross_gram
        # plain unbucketed Krum's combination is one-hot: the apply pass
        # streams only the winner's sublane tile group (select_row)
        apply_fn = partial(_kops.krum_apply, onehot=onehot)
    else:
        def stats_fn(xs, reduce_fn=None):
            x32 = xs.astype(jnp.float32)
            gram = jnp.dot(x32, x32.T, precision=_HIGHEST)
            return reduce_fn(gram) if reduce_fn is not None else gram

        def cross_fn(a, b):
            return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32).T,
                           precision=_HIGHEST)

        def apply_fn(xs, sel):
            x32 = xs.astype(jnp.float32)
            if onehot:
                # exact dynamic row-take: bitwise-identical to the
                # one-shot jnp rule's clipped[winner]
                take = jnp.take(x32, sel.winner, axis=0) * sel.scale
                return take.astype(xs.dtype)
            w = sel.weights[:, None]
            # match the kernel: zero-weight rows contribute exactly 0 so
            # a non-finite unselected payload cannot NaN the combination
            out = jnp.sum(jnp.where(w != 0.0, x32 * w, 0.0), axis=0)
            return (out / sel.denom).astype(xs.dtype)

    def update_stats_fn(stats, buffer, chunk_emb, chunk_mask):
        cm = chunk_mask.astype(bool)
        # full-cohort-shape cross product: the chunk rows embedded at
        # their slots against the whole buffer, same operand shapes as
        # the one-shot Gram so every entry's reduction order matches
        blk = cross_fn(chunk_emb, buffer)
        touch = cm[:, None] | cm[None, :]
        # where/set (not add) merge: stale entries are REPLACED, so a
        # resubmitted row and -0.0 payloads stay bitwise-faithful
        return jnp.where(touch, jnp.where(cm[:, None], blk, blk.T), stats)

    def finalize_fn(stats, mask=None, key=None, radius=None, factors=None):
        n = stats.shape[0]
        bucket_idx = _bucket_order(key, mask, n) if bs >= 2 else None
        use_clip = factors is not None or radius is not None
        sel, _ = _krum_select_from_gram(
            stats, mask, radius, factors, bucket_idx,
            byz_bound=byz_bound, m_select=m_select, multi=multi,
            bucket_s=bs, use_clip=use_clip,
        )
        return sel

    return stats_fn, finalize_fn, apply_fn, update_stats_fn


def make_aggregator(
    name: str, bucket_s: int = 0, backend: str = "jnp", **kwargs
) -> Aggregator:
    """Build an aggregator by name, optionally composed with Bucketing
    (``bucket_s >= 2``) and backed by the requested ``backend``
    ("jnp" | "pallas" | "auto"; see module docstring).

    The declarative entry point to the whole composition (clip ->
    compress -> bucket -> aggregate -> schedule) is
    ``repro.api.ServerPlan``; this factory is its aggregate+bucket stage."""
    name = RULE_ALIASES.get(name, name)
    if name not in _FACTORY:
        raise ValueError(f"unknown aggregator {name!r}; have {sorted(_FACTORY)}")
    resolved = resolve_backend(backend)
    agg = _FACTORY[name](**kwargs)
    if bucket_s and bucket_s >= 2:
        agg = bucketing(agg, s=bucket_s)
    two_phase = {}
    if name in ("krum", "multi_krum"):
        sfn, ffn, afn, ufn = _krum_two_phase_fns(
            byz_bound=kwargs.get("byz_bound"),
            m_select=int(kwargs.get("m_select", 0)),
            multi=(name == "multi_krum"),
            bucket_s=bucket_s if bucket_s else 0,
            pallas=(resolved == "pallas"),
        )
        two_phase = dict(
            stats_fn=sfn, finalize_fn=ffn, apply_fn=afn, update_stats_fn=ufn
        )
    if resolved != "pallas":
        return dataclasses.replace(agg, **two_phase) if two_phase else agg
    bs = bucket_s if bucket_s else 0
    if name in ("cm", "trimmed_mean", "mean"):
        # mean == trimmed mean with t = ceil(0 * cnt) = 0 dropped rows
        trim = (
            -1.0
            if name == "cm"
            else 0.0
            if name == "mean"
            else float(kwargs.get("trim_ratio", _DEFAULT_TRIM))
        )
        fn, fused = _make_pallas_cm_fns(trim, bs)
    elif name == "centered_clip":
        fn, fused = _make_pallas_fns(
            _kops.clip_then_centered_clip, bs,
            tau=float(kwargs.get("tau", 10.0)),
            iters=int(kwargs.get("iters", 5)),
        )
    elif name in ("rfa", "geometric_median"):
        fn, fused = _make_pallas_fns(
            _kops.clip_then_geometric_median, bs,
            iters=int(kwargs.get("iters", 8)),
        )
    elif name in ("krum", "multi_krum"):
        fn, fused = _make_pallas_fns(
            _kops.clip_then_krum, bs,
            byz_bound=kwargs.get("byz_bound"),
            m_select=int(kwargs.get("m_select", 0)),
            multi=(name == "multi_krum"),
        )
    else:  # pragma: no cover — registry and dispatch lists must agree
        raise AssertionError(f"no pallas dispatch for {name!r}")
    return dataclasses.replace(
        agg, fn=fn, fused_clip_fn=fused, backend="pallas", **two_phase
    )
