"""The reference's storage rounding and the control's float8 are real
roundings (``reduce_precision``, ``round``), equal to the dtype casts they
stand for, so that no compiler can fold them away as a cast round trip."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.reference import algorithm  # noqa: E402


def _values():
    rng = np.random.RandomState(5)
    x = rng.standard_normal(50_000).astype(np.float32)
    x *= np.float32(10.0) ** rng.uniform(-8, 3, x.shape).astype(np.float32)
    return jnp.asarray(x)


@pytest.mark.parametrize("control", [False, True])
def test_storage_equals_the_casts(control):
    x = _values()
    tree = {"bf16": x, "f32": x}
    dtypes = {"bf16": jnp.dtype(jnp.bfloat16), "f32": jnp.dtype(jnp.float32)}
    got = algorithm.storage(dtypes, control)(tree)
    bf16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / 448.0
    fp8 = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    want = {"bf16": fp8 if control else bf16, "f32": bf16 if control else x}
    for k in tree:
        # x / s and back may differ from the cast's in the last float32 bit;
        # one float8 step is 2^-9 s or more
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-6 * float(s))


def test_storage_jaxpr_has_no_cast_round_trip():
    dtypes = {"a": jnp.dtype(jnp.bfloat16)}
    for control in (False, True):
        jaxpr = jax.make_jaxpr(algorithm.storage(dtypes, control))(
            {"a": jnp.ones(8, jnp.float32)})
        assert "convert_element_type" not in str(jaxpr)
        assert "reduce_precision" in str(jaxpr)
