"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel's body in Python and accepts block shapes,
slices and VMEM footprints that the TPU compiler refuses.  These tests
hand each kernel of the trainer's and the server's main path to the
v5e compiler (no chip needed: the topology is described, not attached)
at the per-chip message size of ``chip_smoke.py`` and assert that the
compiled program contains the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU library, and it keeps it until it
exits), and the persistent compilation cache is off around the compiles
(an entry written for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.centered_clip import (MAX_VMEM_ELEMS,
                                        clip_then_centered_clip,
                                        resident_elems)
from repro.kernels.clip_aggregate import clip_then_aggregate
from repro.kernels.clipped_diff import clipped_diff
from repro.kernels.coordinate_median import coordinate_median
from repro.kernels.geometric_median import clip_then_geometric_median
from repro.kernels.krum import clip_then_krum, cross_gram

_CFG = get_config("mamba2-780m")
# chip_smoke.py trains mamba2-780m at its published widths; the largest
# message leaf it aggregates in one kernel call is the (vocab, d_model)
# embedding, in the config's bf16
D_TRAIN = _CFG.vocab * _CFG.d_model
# the message rows chip_smoke.py's aggregation-server phase ingests (f32)
D_SERVE = 1 << 22
# coordinate counts that are no multiple of 128: the streaming kernels'
# partial last block (a block wider than the whole leaf at n = 4 and 8),
# and a leaf of under one tile (mamba2's (layers, heads) float32 A_log)
RAGGED = ((3328 * 4 + 48, jnp.bfloat16), (4 * 48, jnp.float32))
COHORTS = (4, 8, 64)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "kernel was not compiled as a TPU kernel"


# name -> fn(xs, radius, mask, factors)
_TRAIN_KERNELS = {
    # the trainer's default plan: cm over factors from global tree norms
    "cm_clip_factors": lambda x, r, m, f: clip_then_aggregate(
        x, r, m, None, f),
    # the engine form: the clip norm pass, then cm
    "cm_clip_norms": lambda x, r, m, f: clip_then_aggregate(
        x, r, m),
    "trimmed_mean": lambda x, r, m, f: clip_then_aggregate(
        x, r, m, None, f, trim_ratio=0.25),
    # the full rounds' plain median
    "coordinate_median": lambda x, r, m, f: coordinate_median(x, m),
    # Gram + the winner-row select pass
    "krum": lambda x, r, m, f: clip_then_krum(x, r, m, byz_bound=1),
    # Gram + the weighted row-sum pass
    "multi_krum": lambda x, r, m, f: clip_then_krum(
        x, r, m, byz_bound=1, multi=True),
    "centered_clip": lambda x, r, m, f: clip_then_centered_clip(x, r, m),
    "geometric_median": lambda x, r, m, f: clip_then_geometric_median(
        x, r, m),
}


def _row_args(one_chip, n, d, dtype):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (spec((n, d), dtype), spec((), jnp.float32),
            spec((n,), jnp.float32), spec((n,), jnp.float32))


@pytest.mark.parametrize("n", COHORTS)
@pytest.mark.parametrize("kernel", sorted(_TRAIN_KERNELS))
def test_train_kernel_compiles_at_message_size(one_chip, kernel, n):
    """Each fused kernel on an (n, D_TRAIN) bf16 message block."""
    _assert_kernel(_compile(
        _TRAIN_KERNELS[kernel], *_row_args(one_chip, n, D_TRAIN, jnp.bfloat16)
    ))


@pytest.mark.parametrize("d,dtype", RAGGED, ids=["d13360_bf16", "d192_f32"])
@pytest.mark.parametrize("n", COHORTS)
@pytest.mark.parametrize(
    "kernel",
    ["cm_clip_factors", "cm_clip_norms", "trimmed_mean", "coordinate_median"],
)
def test_stream_kernel_compiles_with_partial_block(one_chip, kernel, n, d,
                                                   dtype):
    """The wide-block kernels on a ragged d: the partial last block, the
    norm pass's lane mask, a one-chunk block read at a static offset, and
    the VMEM footprint at every cohort size."""
    _assert_kernel(_compile(
        _TRAIN_KERNELS[kernel], *_row_args(one_chip, n, d, dtype)
    ))


@pytest.mark.parametrize("n", COHORTS)
@pytest.mark.parametrize("kernel", ["centered_clip", "geometric_median"])
def test_iterative_kernel_compiles_at_resident_cutover(one_chip, kernel, n):
    """The largest d that takes the VMEM-resident schedule fits VMEM."""
    d = MAX_VMEM_ELEMS // resident_elems(n, 1)
    assert resident_elems(n, d) <= MAX_VMEM_ELEMS < resident_elems(n, d + 1)
    _assert_kernel(_compile(
        _TRAIN_KERNELS[kernel], *_row_args(one_chip, n, d, jnp.float32)
    ))


@pytest.mark.parametrize("n", COHORTS)
def test_serve_cross_gram_compiles(one_chip, n):
    """The server's incremental Gram ingest on (n, D_SERVE) f32 rows."""
    x = _row_args(one_chip, n, D_SERVE, jnp.float32)[0]
    _assert_kernel(_compile(cross_gram, x, x))


def test_clipped_diff_compiles(one_chip):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    g = spec((D_TRAIN,), jnp.bfloat16)
    _assert_kernel(_compile(
        lambda a, b, r, k: clipped_diff(a, b, r, k, 2.0),
        g, g, spec((), jnp.float32), g,
    ))
