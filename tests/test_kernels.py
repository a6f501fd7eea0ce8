"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Sweeps shapes (odd/even worker counts, lane-aligned and ragged coordinate
counts) and dtypes (f32, bf16) as required for kernel sign-off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import centered_clip, clipped_diff, coordinate_median, trimmed_mean
from repro.kernels.ref import (
    centered_clip_ref,
    clipped_diff_ref,
    coordinate_median_ref,
    trimmed_mean_ref,
)

SHAPES = [(3, 64), (8, 512), (11, 700), (16, 1024), (5, 1), (32, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else dict(atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_coordinate_median_sweep(shape, dtype):
    rng = np.random.RandomState(hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape), dtype)
    out = coordinate_median(xs)
    ref = coordinate_median_ref(xs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_coordinate_median_masked_sweep(shape):
    rng = np.random.RandomState(1 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mask = np.zeros(shape[0], bool)
    mask[: max(1, shape[0] // 2)] = True
    rng.shuffle(mask)
    out = coordinate_median(xs, jnp.asarray(mask))
    ref = coordinate_median_ref(xs, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # also equals numpy median over the selected subset
    np.testing.assert_allclose(
        np.asarray(out), np.median(np.asarray(xs)[mask], axis=0), atol=1e-5
    )


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("trim", [0.1, 0.25])
def test_trimmed_mean_sweep(shape, trim):
    rng = np.random.RandomState(2 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    out = trimmed_mean(xs, trim_ratio=trim)
    ref = trimmed_mean_ref(xs, trim_ratio=trim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize(
    "n", [100, 8192, 8193, 100000], ids=lambda n: f"d{n}"
)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_clipped_diff_sweep(n, dtype):
    rng = np.random.RandomState(n % 2**31)
    gn = jnp.asarray(rng.randn(n), dtype)
    go = jnp.asarray(rng.randn(n), dtype)
    km = jnp.asarray((rng.rand(n) > 0.5).astype(np.float32), dtype)
    out, norm = clipped_diff(gn, go, 2.5, km, 3.0)
    rout, rnorm = clipped_diff_ref(gn, go, 2.5, km, 3.0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(rout, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(float(norm), float(rnorm), rtol=1e-2)
    assert float(jnp.linalg.norm(out.astype(jnp.float32))) <= 2.5 * 1.05


def test_clipped_diff_multidim_shapes():
    rng = np.random.RandomState(9)
    gn = jnp.asarray(rng.randn(4, 33, 7).astype(np.float32))
    go = jnp.asarray(rng.randn(4, 33, 7).astype(np.float32))
    km = jnp.ones_like(gn)
    out, _ = clipped_diff(gn, go, 1e9, km, 1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gn - go), atol=1e-5)
    assert out.shape == gn.shape


@pytest.mark.parametrize("shape", [(4, 128), (9, 257), (16, 1024)], ids=str)
@pytest.mark.parametrize("tau", [0.5, 100.0])
def test_centered_clip_sweep(shape, tau):
    rng = np.random.RandomState(3 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    out = centered_clip(xs, tau=tau, iters=6)
    ref = centered_clip_ref(xs, tau, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(2, 20),
    d=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_kernel_cm_equals_numpy(n, d, seed):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, d).astype(np.float32)
    out = coordinate_median(jnp.asarray(xs))
    np.testing.assert_allclose(np.asarray(out), np.median(xs, axis=0), atol=1e-5)


# ---------------------------------------------------------------------------
# fused Bucketing o CM kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,s", [(10, 300, 2), (11, 700, 3), (16, 1024, 2), (8, 64, 4)])
def test_bucketed_cm_sweep(n, d, s):
    from repro.kernels import bucketed_coordinate_median
    from repro.kernels.ref import bucketed_cm_ref

    rng = np.random.RandomState(n * 31 + s)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.2)
    key = jax.random.PRNGKey(n)
    out = bucketed_coordinate_median(xs, key, mask, s=s)
    n_p = n + ((-n) % s)
    perm = jax.random.permutation(key, n_p).astype(jnp.int32)
    ref = bucketed_cm_ref(xs, perm, mask, s=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fused_clip_aggregate_lambda_inf_recovers_plain_aggregation():
    from repro.kernels import clip_then_aggregate

    rng = np.random.RandomState(21)
    xs = jnp.asarray(rng.randn(9, 700).astype(np.float32))
    out, norms = clip_then_aggregate(xs, jnp.inf)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(coordinate_median_ref(xs)), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(norms),
        np.linalg.norm(np.asarray(xs), axis=1),
        rtol=1e-5,
    )
    # use_clip=False (skipped norm pass) agrees with the +inf radius path
    out2, norms2 = clip_then_aggregate(xs, 0.0, use_clip=False)
    assert norms2 is None
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out), atol=1e-6)


@pytest.mark.parametrize(
    "shape", [(3, 64), (8, 512), (11, 700), (16, 1024), (5, 1), (32, 130)],
    ids=str,
)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_fused_clip_aggregate_cm_sweep(shape, dtype, masked):
    from repro.kernels import clip_then_aggregate
    from repro.kernels.ref import clip_then_aggregate_ref

    rng = np.random.RandomState(5 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape), dtype)
    mask = None
    if masked:
        m = np.zeros(shape[0], bool)
        m[: max(1, shape[0] // 2)] = True
        rng.shuffle(m)
        mask = jnp.asarray(m)
    out, norms = clip_then_aggregate(xs, 1.5, mask)
    rout, rnorms = clip_then_aggregate_ref(xs, 1.5, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(rout, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(
        np.asarray(norms, np.float32),
        np.asarray(rnorms, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


@pytest.mark.parametrize("trim", [0.1, 0.25])
@pytest.mark.parametrize("shape", [(8, 512), (11, 700), (32, 130)], ids=str)
def test_fused_clip_aggregate_trimmed_sweep(shape, trim):
    from repro.kernels import clip_then_aggregate
    from repro.kernels.ref import clip_then_aggregate_ref

    rng = np.random.RandomState(6 + hash(shape) % 2**31)
    xs = jnp.asarray(rng.randn(*shape).astype(np.float32))
    mask = jnp.asarray(rng.rand(shape[0]) > 0.3)
    out, _ = clip_then_aggregate(xs, 2.0, mask, trim_ratio=trim)
    rout, _ = clip_then_aggregate_ref(xs, 2.0, mask, trim_ratio=trim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=1e-5)


@pytest.mark.parametrize(
    "n,d,s", [(10, 300, 2), (11, 700, 3), (16, 1024, 2), (8, 64, 4)]
)
def test_fused_clip_aggregate_bucketed_sweep(n, d, s):
    from repro.kernels import clip_then_aggregate
    from repro.kernels.ref import clip_then_aggregate_ref

    rng = np.random.RandomState(n * 17 + s)
    xs = jnp.asarray(rng.randn(n, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) > 0.25)
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    out, _ = clip_then_aggregate(xs, 1.2, mask, idx, bucket_s=s)
    rout, _ = clip_then_aggregate_ref(xs, 1.2, mask, idx, bucket_s=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=1e-5)


def test_fused_clip_aggregate_output_is_clipped_scale():
    """Every aggregated coordinate lies within the clipped rows' hull, so
    the output norm cannot exceed sqrt(d) * lambda (CM's F_A bound)."""
    from repro.kernels import clip_then_aggregate

    rng = np.random.RandomState(33)
    d = 256
    xs = jnp.asarray(100.0 * rng.randn(7, d).astype(np.float32))
    lam = 0.5
    out, _ = clip_then_aggregate(xs, lam)
    assert float(jnp.linalg.norm(out)) <= np.sqrt(d) * lam * (1 + 1e-5)


def test_bucketed_cm_resists_outlier_minority():
    from repro.kernels import bucketed_coordinate_median

    rng = np.random.RandomState(7)
    good = rng.randn(10, 256).astype(np.float32)
    byz = 1e6 * np.ones((2, 256), np.float32)
    xs = jnp.asarray(np.concatenate([good, byz]))
    out = bucketed_coordinate_median(xs, jax.random.PRNGKey(0), s=2)
    assert float(jnp.abs(out).max()) < 10.0


@pytest.mark.parametrize("platform,interpret,auto",
                         [("cpu", True, "jnp"), ("tpu", False, "pallas"),
                          ("gpu", None, None)])
def test_kernel_platform_fallbacks_only_on_cpu(monkeypatch, platform,
                                               interpret, auto):
    """Interpret mode and the jnp ``auto`` choice belong to the CPU alone:
    the TPU compiles the kernels, and any other platform is refused
    rather than silently interpreted."""
    from repro.core.aggregators import resolve_backend
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(ops, "_INTERPRET", None)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
        with pytest.raises(RuntimeError, match="'gpu'"):
            resolve_backend("auto")
    else:
        assert ops._interpret() is interpret
        assert resolve_backend("auto") == auto


# ---- wide-block streaming geometry (coordinate_median / clip_aggregate) --

def _tile_oracle(xs, mask, factors, trim_ratio):
    """The (n, TILE_D)-tile kernels' arithmetic over the whole matrix: rows
    on sublanes, ranks by index tie-break, the pick summed over rows.  The
    ops are coordinate-wise, so this is the 512-lane-tile result."""
    from repro.kernels.coordinate_median import _BIG, _select_masked

    m = mask.astype(jnp.float32).reshape(-1, 1)
    x = xs.astype(jnp.float32)
    if factors is not None:
        x = x * factors.astype(jnp.float32).reshape(-1, 1)
    vals = jnp.where(m > 0.5, x, _BIG)
    return _select_masked(vals, m, trim_ratio=trim_ratio)[0].astype(xs.dtype)


def _stream_d(n, spec):
    """A coordinate count, or one at the block width of (n, bf16) rows."""
    from repro.kernels.coordinate_median import stream_geometry

    if isinstance(spec, int):
        return spec
    width = stream_geometry(n, 1 << 30, 2)[0]
    return {"width-128": width - 128, "width": width, "width+1": width + 1,
            "width+128": width + 128}[spec]


@pytest.mark.parametrize(
    "d",
    [1, 48, 130, 3328, 3328 * 4 + 48, "width-128", "width", "width+1",
     "width+128"],
    ids=str,
)
@pytest.mark.parametrize("n", [1, 4, 8, 64])
@pytest.mark.parametrize(
    "op", ["cm", "cm_masked", "trimmed_mean", "clip_factors", "clip_norms"]
)
def test_stream_kernels_match_ref_and_tile_result(op, n, d):
    """Wide blocks with a partial last block (no pad) give the reference's
    aggregate and, bit for bit, the 512-lane-tile kernels' selection; the
    norm pass's row norms differ from the reference in reduction order
    only."""
    from repro.kernels import clip_then_aggregate
    from repro.kernels.clip_aggregate import clip_factor
    from repro.kernels.ref import clip_then_aggregate_ref

    d = _stream_d(n, d)
    rng = np.random.RandomState(n * 1009 + d % 997)
    xs = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    mask = np.ones(n, np.float32)
    if op == "cm_masked":
        # one of four rows sampled (all of one row where n == 1)
        mask = (np.arange(n) % 4 == 1 % n).astype(np.float32)
    mask = jnp.asarray(mask)
    factors = None
    tol = _tol(jnp.bfloat16)
    trim = 0.25 if op == "trimmed_mean" else -1.0
    if op in ("cm", "cm_masked"):
        out = coordinate_median(xs, mask)
        ref = coordinate_median_ref(xs, mask > 0.5)
    elif op == "trimmed_mean":
        out = trimmed_mean(xs, mask, trim_ratio=trim)
        ref = trimmed_mean_ref(xs, mask > 0.5, trim_ratio=trim)
    elif op == "clip_factors":
        factors = jnp.asarray(rng.uniform(0.3, 1.0, n), jnp.float32)
        out, norms = clip_then_aggregate(xs, 1.0, mask, factors=factors)
        assert norms is None
        ref = coordinate_median_ref(
            (xs.astype(jnp.float32) * factors[:, None]).astype(jnp.bfloat16),
            mask > 0.5,
        )
    else:
        radius = 0.5 * float(np.sqrt(d))
        out, norms = clip_then_aggregate(xs, radius, mask)
        ref, rnorms = clip_then_aggregate_ref(xs, radius, mask > 0.5)
        np.testing.assert_allclose(
            np.asarray(norms), np.asarray(rnorms), rtol=1e-5
        )
        factors = clip_factor(norms, radius)
    assert out.shape == (d,) and out.dtype == xs.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol
    )
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(_tile_oracle(xs, mask, factors, trim), np.float32),
    )


@pytest.mark.parametrize(
    "d", [77_230_080, 39_616_512], ids=["embedding", "in_proj"]
)
@pytest.mark.parametrize("call", ["clip_factors", "clip_norms", "cm"])
def test_stream_kernels_grid_is_wide_and_pad_free(call, d):
    """At the trainer's largest leaves (n = 4 bf16 rows of mamba2-780m's
    (vocab, d_model) embedding and a (4, 1536, 6448) in_proj) each
    streaming kernel runs at most 2,048 grid steps, where 512-lane tiles
    took 150,840 and 77,376, and nothing pads the (n, d) operand."""
    import math

    from _jaxpr_utils import iter_eqns_outside_kernels, pallas_calls

    from repro.kernels import clip_then_aggregate

    n = 4
    x = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((n,), jnp.float32)
    if call == "cm":
        fn, names = (lambda x, m, f: coordinate_median(x, m)), [
            "coordinate_median"]
    elif call == "clip_factors":
        fn, names = (lambda x, m, f: clip_then_aggregate(
            x, 1.0, m, factors=f)), ["clip_aggregate"]
    else:
        fn, names = (lambda x, m, f: clip_then_aggregate(x, 1.0, m)), [
            "clip_row_norms", "clip_aggregate"]
    jaxpr = jax.make_jaxpr(fn)(x, rows, rows).jaxpr
    for name in names:
        (eqn,) = pallas_calls(jaxpr, name)
        steps = math.prod(eqn.params["grid_mapping"].grid)
        assert steps <= 2048, (name, steps)
    assert not [
        e for e in iter_eqns_outside_kernels(jaxpr)
        if e.primitive.name in ("pad", "concatenate")
    ]


@pytest.mark.parametrize("n", [2, 3, 5, 6, 7])
def test_dense_median_every_row_count(n):
    """The dense (n, 64, 128) layout's median at every other n below 8,
    with all, some and one row sampled, over two blocks and a partial
    third."""
    from repro.kernels import clip_then_aggregate
    from repro.kernels.coordinate_median import stream_geometry

    d = 2 * stream_geometry(n, 1 << 30, 2)[0] + 128
    rng = np.random.RandomState(40 + n)
    xs = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    factors = jnp.asarray(rng.uniform(0.3, 1.0, n), jnp.float32)
    for mask in (np.ones(n), rng.rand(n) > 0.5, np.arange(n) == n - 1):
        mask = jnp.asarray(mask, jnp.float32)
        out, _ = clip_then_aggregate(xs, 1.0, mask, factors=factors)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(_tile_oracle(xs, mask, factors, -1.0), np.float32),
        )
