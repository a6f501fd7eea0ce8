"""Sweeps + property tests for the Krum/multi-Krum Gram kernel, the
Weiszfeld geometric-median kernel, and the fused clip->iterative paths —
pallas (interpret mode) vs the pure-jnp oracles in repro.kernels.ref,
under partial-participation masks, ragged d, bf16, bucketing and
lambda=+inf, mirroring tests/test_kernels.py's CM/TM sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from _jaxpr_utils import block_shapes, pallas_calls
from _jaxpr_utils import iter_eqns_outside_kernels as _eqns_outside_kernels

from repro.kernels import (
    centered_clip,
    clip_then_centered_clip,
    clip_then_geometric_median,
    clip_then_krum,
    geometric_median,
    krum,
    multi_krum,
)
from repro.kernels.ref import (
    centered_clip_ref,
    clip_then_centered_clip_ref,
    clip_then_geometric_median_ref,
    clip_then_krum_ref,
    geometric_median_ref,
    krum_ref,
    multi_krum_ref,
)

SHAPES = [(3, 64), (8, 512), (11, 700), (16, 1024), (5, 1), (32, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return (
        dict(atol=3e-2, rtol=3e-2)
        if dtype == jnp.bfloat16
        else dict(atol=1e-5, rtol=1e-5)
    )


def _mask(rng, n):
    m = np.zeros(n, bool)
    m[: max(3, n // 2)] = True
    rng.shuffle(m)
    return jnp.asarray(m)


# ---------------------------------------------------------------------------
# krum / multi-krum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_krum_sweep(shape, dtype, masked):
    rng = np.random.RandomState(hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape), dtype)
    mask = _mask(rng, shape[0]) if masked else None
    out = krum(xs, mask, byz_bound=1)
    ref = krum_ref(xs, mask, 1)
    # krum returns an exact input row -> bitwise unless the Gram ulp noise
    # flips the winner, which the shared selection helpers prevent
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("m_select", [0, 3])
def test_multi_krum_sweep(shape, m_select):
    rng = np.random.RandomState(1 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mask = _mask(rng, shape[0])
    out = multi_krum(xs, mask, byz_bound=1, m_select=m_select)
    ref = multi_krum_ref(xs, mask, 1, m_select)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_krum_selects_honest_row_under_outliers():
    rng = np.random.RandomState(3)
    good = rng.randn(8, 300).astype(np.float32) * 0.1
    byz = 100.0 + rng.randn(3, 300).astype(np.float32)
    xs = jnp.asarray(np.concatenate([good, byz]))
    out = np.asarray(krum(xs, byz_bound=3))
    assert np.linalg.norm(out[None] - good, axis=1).min() < 1e-6


@pytest.mark.parametrize(
    "n,d,s", [(10, 300, 2), (11, 700, 3), (16, 1024, 2), (8, 64, 4)]
)
@pytest.mark.parametrize("multi", [False, True], ids=["krum", "multikrum"])
def test_fused_clip_krum_bucketed_sweep(n, d, s, multi):
    rng = np.random.RandomState(n * 13 + s)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.25)
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    out, _ = clip_then_krum(
        xs, 1.2, mask, idx, byz_bound=1, bucket_s=s, multi=multi
    )
    ref, _ = clip_then_krum_ref(
        xs, 1.2, mask, idx, byz_bound=1, bucket_s=s, multi=multi
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# the on-chip winner gather: tile-wise weighted row-sum pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_weighted_row_sum_sweep(shape, dtype):
    from repro.kernels.ops import weighted_row_sum

    rng = np.random.RandomState(9 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.rand(shape[0]).astype(np.float32))
    out = weighted_row_sum(xs, w)
    ref = jnp.sum(xs.astype(jnp.float32) * w[:, None], axis=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref),
        **(dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16
           else dict(atol=0, rtol=0)),
    )


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
@pytest.mark.parametrize("bucket_s", [1, 3], ids=["flat", "bucketed"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("multi", [False, True], ids=["krum", "multikrum"])
def test_two_phase_selection_matches_fused_one_shot(
    masked, bucket_s, dtype, multi
):
    """gram -> select -> tile-wise apply over SPLIT coordinate blocks must
    reproduce the one-shot fused kernel on the concatenated matrix — the
    whole-tree contract the mesh trainer runs on (masks, bucketing, bf16)."""
    from repro.kernels.ops import (
        krum_apply, krum_gram, krum_select_from_gram,
    )

    n, d1, d2 = 9, 130, 517
    rng = np.random.RandomState(17 * bucket_s + multi)
    a = jnp.asarray(rng.randn(n, d1), dtype)
    b = jnp.asarray(rng.randn(n, d2), dtype)
    xs = jnp.concatenate([a, b], axis=1)
    mask = _mask(rng, n) if masked else None
    idx = (
        jnp.asarray(rng.permutation(n).astype(np.int32))
        if bucket_s >= 2 else None
    )
    factors = jnp.asarray(rng.rand(n).astype(np.float32))

    one, _ = clip_then_krum(
        xs, 1.2, mask, idx, factors, byz_bound=1, bucket_s=bucket_s,
        multi=multi,
    )
    gram = krum_gram(a) + krum_gram(b)  # Gram is additive over blocks
    sel, _ = krum_select_from_gram(
        gram, mask, None, factors, idx, byz_bound=1, bucket_s=bucket_s,
        multi=multi,
    )
    two = jnp.concatenate([krum_apply(a, sel), krum_apply(b, sel)])
    # identical factors -> identical selection algebra -> identical
    # per-coordinate apply arithmetic: bitwise, even in bf16
    np.testing.assert_array_equal(
        np.asarray(one, np.float32), np.asarray(two, np.float32)
    )


@pytest.mark.parametrize("multi", [False, True], ids=["krum", "multikrum"])
def test_nonfinite_unsampled_row_cannot_poison_apply_pass(multi):
    """A byzantine/unsampled row sending inf must not NaN the winner
    reconstruction: zero-weight rows contribute exactly 0 in the
    row-combine kernel, never 0 * inf (the row-take this pass replaced
    never read those rows)."""
    rng = np.random.RandomState(11)
    xs = np.asarray(rng.randn(6, 200), np.float32)
    xs[2] = np.inf  # unsampled row
    mask = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    out, _ = clip_then_krum(
        jnp.asarray(xs), 1.5, mask, byz_bound=1, multi=multi
    )
    assert np.isfinite(np.asarray(out)).all()
    ref, _ = clip_then_krum_ref(
        jnp.asarray(xs)[np.asarray(mask)], 1.5, None, byz_bound=1,
        multi=multi,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("multi", [False, True], ids=["krum", "multikrum"])
@pytest.mark.parametrize("bucket_s", [1, 2], ids=["flat", "bucketed"])
def test_winner_reconstruction_is_kernel_pass_not_host_gather(multi, bucket_s):
    """The fused path's winner reconstruction must be the tile-wise
    row-sum kernel: outside pallas bodies the jaxpr contains no gather /
    dynamic-slice producing a d-sized operand (the old host-level row
    gather), and there are exactly two kernel launches (Gram + apply)."""
    n, d = 8, 1100
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    jaxpr = jax.make_jaxpr(
        lambda x, i: clip_then_krum(
            x, 1.2, None, i, byz_bound=1, bucket_s=bucket_s, multi=multi
        )[0]
    )(xs, idx)
    launches = sum(
        1
        for eqn in _eqns_outside_kernels(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
    )
    assert launches == 2, f"expected Gram + apply launches, got {launches}"
    bad = [
        eqn
        for eqn in _eqns_outside_kernels(jaxpr.jaxpr)
        if eqn.primitive.name in ("gather", "dynamic_slice")
        and any(
            max(getattr(v.aval, "shape", (0,)) or (0,)) >= d
            for v in eqn.outvars
        )
    ]
    assert not bad, f"host-level d-sized row gather on the fused path: {bad}"


def test_fused_krum_lambda_inf_recovers_plain():
    rng = np.random.RandomState(5)
    xs = jnp.asarray(rng.randn(9, 700).astype(np.float32))
    out, norms = clip_then_krum(xs, jnp.inf, byz_bound=2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(krum(xs, byz_bound=2)), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(norms), np.linalg.norm(np.asarray(xs), axis=1), rtol=1e-5
    )


# ---------------------------------------------------------------------------
# the single-row apply fast path (plain unbucketed Krum)
# ---------------------------------------------------------------------------

def test_onehot_apply_bitwise_equals_weighted_row_sum():
    """select_row (the scalar-prefetch winner-row stream) must reproduce
    the one-hot weighted_row_sum bitwise — including a zero clip factor
    on an inf-carrying winner row (0, never 0 * inf = NaN)."""
    from repro.kernels.krum import select_row, weighted_row_sum

    rng = np.random.RandomState(4)
    n, d = 7, 530
    xs = np.asarray(rng.randn(n, d), np.float32)
    xs[5] = np.inf
    xs = jnp.asarray(xs)
    for winner, scale in ((2, 0.73), (0, 1.0), (5, 0.0), (6, 1e-8)):
        w_row = (
            jnp.arange(n) == winner
        ).astype(jnp.float32) * jnp.float32(scale)
        full = weighted_row_sum(xs, w_row, interpret=True)
        fast = select_row(
            xs, jnp.int32(winner), jnp.float32(scale), interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(full), np.asarray(fast),
            err_msg=f"winner={winner} scale={scale}",
        )
        assert np.isfinite(np.asarray(fast)).all() or scale != 0.0


@pytest.mark.parametrize(
    "multi,bucket_s,expect_onehot",
    [(False, 1, True), (True, 1, False), (False, 2, False)],
    ids=["krum-flat", "multikrum", "krum-bucketed"],
)
def test_onehot_apply_only_streams_winner_row(multi, bucket_s, expect_onehot):
    """Plain unbucketed Krum's fused apply pass must be the
    scalar-prefetch select_row kernel whose x-block is the one sublane
    tile group holding the winner (8 f32 rows of n = 32) — the DMA
    streams row_tile * d elements, not n*d; multi-Krum and bucketed
    selections (genuine multi-row combinations) must keep the full
    row-sum pass."""
    from repro.kernels.coordinate_median import TILE_D
    from repro.kernels.krum import row_tile

    n, d = 32, 1100
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    jaxpr = jax.make_jaxpr(
        lambda x, i: clip_then_krum(
            x, 1.2, None, i, byz_bound=1, bucket_s=bucket_s, multi=multi
        )[0]
    )(xs, idx)
    text = str(jaxpr)
    if expect_onehot:
        assert "name=krum_select_row" in text
        assert "name=weighted_row_sum" not in text
        # structural traffic assertion: the apply kernel maps its x operand
        # in (row_tile, TILE_D) blocks — one sublane tile group, not the
        # (n, TILE_D) full-matrix block of the row-sum pass — and writes
        # one (1, TILE_D) output row per step
        calls = pallas_calls(jaxpr.jaxpr, "krum_select_row")
        assert len(calls) == 1, calls
        shapes = block_shapes(calls[0])
        rb = row_tile(n, xs.dtype)
        assert rb == 8 < n
        assert shapes == [(rb, TILE_D), (1, TILE_D)], shapes
    else:
        assert "name=weighted_row_sum" in text
        assert "name=krum_select_row" not in text


def test_onehot_apply_traffic_model():
    """The modeled apply-pass traffic must count what select_row streams
    (the bench gate pins fused_bytes): the winner's sublane tile group of
    row_tile rows instead of all n — 8 f32 rows of n = 16 — and no cut
    at all where the tile group is the whole matrix (bf16, n = 4)."""
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from benchmarks.bench_kernels import traffic_model_krum_apply

    n, d = 16, 1 << 16
    tm = traffic_model_krum_apply(n, d)
    assert tm["fused_bytes"] == (8 + 1) * d * 4  # winner tile in + (d,) out
    assert tm["full_bytes"] == (n + 1) * d * 4
    assert tm["traffic_reduction"] == pytest.approx((n + 1) / 9)
    tm = traffic_model_krum_apply(4, d, itemsize=2)
    assert tm["fused_bytes"] == tm["full_bytes"] == (4 + 1) * d * 2


# ---------------------------------------------------------------------------
# geometric median
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_geometric_median_sweep(shape, masked):
    rng = np.random.RandomState(2 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mask = _mask(rng, shape[0]) if masked else None
    out = geometric_median(xs, mask, iters=8)
    ref = geometric_median_ref(xs, 8, 1e-8, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_geometric_median_resists_one_outlier():
    xs = np.zeros((5, 40), dtype=np.float32)
    xs[-1] = 1e6
    out = np.asarray(geometric_median(jnp.asarray(xs), iters=64))
    assert np.linalg.norm(out) < 1.0


@pytest.mark.parametrize("shape", [(8, 512), (11, 700), (32, 130)], ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_fused_clip_gm_sweep(shape, dtype):
    rng = np.random.RandomState(4 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape), dtype)
    mask = _mask(rng, shape[0])
    out, norms = clip_then_geometric_median(xs, 1.5, mask, iters=6)
    ref, rnorms = clip_then_geometric_median_ref(xs, 1.5, mask, iters=6)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(
        np.asarray(norms, np.float32),
        np.asarray(rnorms, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


@pytest.mark.parametrize("n,d,s", [(10, 300, 2), (11, 700, 3), (8, 64, 4)])
def test_fused_clip_gm_bucketed_sweep(n, d, s):
    rng = np.random.RandomState(n * 7 + s)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.25)
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    out, _ = clip_then_geometric_median(xs, 1.1, mask, idx, bucket_s=s)
    ref, _ = clip_then_geometric_median_ref(xs, 1.1, mask, idx, bucket_s=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# centered clip: fused variant + the large-d tiled schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 512), (11, 700), (32, 130)], ids=str)
@pytest.mark.parametrize("tau", [0.5, 100.0])
def test_fused_clip_cclip_sweep(shape, tau):
    rng = np.random.RandomState(6 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mask = _mask(rng, shape[0])
    out, _ = clip_then_centered_clip(xs, 1.4, mask, tau=tau, iters=5)
    ref, _ = clip_then_centered_clip_ref(xs, 1.4, mask, tau=tau, iters=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("n,d,s", [(10, 300, 2), (11, 700, 3)])
def test_fused_clip_cclip_bucketed_sweep(n, d, s):
    rng = np.random.RandomState(n * 5 + s)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.25)
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    out, _ = clip_then_centered_clip(xs, 1.1, mask, idx, bucket_s=s, tau=3.0)
    ref, _ = clip_then_centered_clip_ref(
        xs, 1.1, mask, idx, bucket_s=s, tau=3.0
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_cclip_large_d_tiled_no_ref_fallback(fused):
    """(n+2)*d above the VMEM budget must take the coordinate-tiled
    kernel schedule (cross-tile norm reduction), not a silent jnp-ref
    fallback — and still match the oracle."""
    rng = np.random.RandomState(7)
    n, d = 8, 150_000  # (n+2)*d = 1.5e6 > 1<<20
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray([1, 1, 0, 1, 1, 0, 1, 1], bool)
    if fused:
        out, _ = clip_then_centered_clip(xs, 40.0, mask, tau=2.0, iters=3)
        ref, _ = clip_then_centered_clip_ref(xs, 40.0, mask, tau=2.0, iters=3)
    else:
        out = centered_clip(xs, mask, tau=2.0, iters=3)
        ref = centered_clip_ref(xs, 2.0, 3, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    # and the tiled path really is kernel-backed: the jaxpr of the wrapped
    # call contains pallas_call launches
    jaxpr = str(
        jax.make_jaxpr(
            lambda x, m: clip_then_centered_clip(
                x, 40.0, m, tau=2.0, iters=3
            )[0].sum()
            if fused
            else centered_clip(x, m, tau=2.0, iters=3).sum()
        )(xs, mask)
    )
    assert "pallas_call" in jaxpr


def test_gm_large_d_tiled_matches_ref():
    rng = np.random.RandomState(8)
    n, d = 6, 200_000
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    out = geometric_median(xs, iters=3)
    ref = geometric_median_ref(xs, 3, 1e-8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# property tests (hypothesis; deterministic fallback shim in this container)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(4, 18),
    d=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_masked_krum_matches_oracle(n, d, seed):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.4) if rng.rand() < 0.7 else None
    b = int(rng.randint(0, max(1, n // 3)))
    out = krum(xs, mask, byz_bound=b)
    ref = krum_ref(xs, mask, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(4, 18),
    d=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_masked_multi_krum_matches_oracle(n, d, seed):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.4)
    out = multi_krum(xs, mask, byz_bound=1)
    ref = multi_krum_ref(xs, mask, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(3, 16),
    d=st.integers(1, 257),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_masked_gm_fused_matches_oracle(n, d, seed):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.4) if rng.rand() < 0.7 else None
    radius = float(rng.rand() * 3 + 0.2) if rng.rand() < 0.8 else np.inf
    out, _ = clip_then_geometric_median(xs, radius, mask, iters=5)
    ref, _ = clip_then_geometric_median_ref(xs, radius, mask, iters=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
