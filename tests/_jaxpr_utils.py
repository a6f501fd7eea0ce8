"""Shared jaxpr-inspection helper for the kernel/mesh structure tests."""
import jax.extend.core as jex_core

_CORE_TYPES = (jex_core.Jaxpr, jex_core.ClosedJaxpr)


def iter_eqns_outside_kernels(jaxpr):
    """All eqns reachable from ``jaxpr`` WITHOUT descending into
    pallas_call bodies (whose in-register ops never touch HBM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if isinstance(v, _CORE_TYPES):
                inner = v.jaxpr if hasattr(v, "jaxpr") else v
                yield from iter_eqns_outside_kernels(inner)
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def pallas_calls(jaxpr, name):
    """Every pallas_call eqn reachable from ``jaxpr`` whose kernel was
    launched with ``name=name``."""
    return [
        eqn for eqn in iter_eqns_outside_kernels(jaxpr)
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name
    ]


def block_shapes(eqn):
    """The (in..., out...) block shapes of a pallas_call eqn as int
    tuples."""
    return [
        tuple(getattr(b, "block_size", b) for b in bm.block_shape)
        for bm in eqn.params["grid_mapping"].block_mappings
    ]
