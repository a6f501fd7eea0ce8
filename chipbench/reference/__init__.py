"""Plain float32 references of the models and of Algorithm 1's step.

Nothing here imports the program under test.  Each model module offers
``init(key, model, dtype)`` (the benchmark's weights, in the program's
parameter layout) and ``loss(params, tokens, model, q)`` (mean next-token
cross-entropy in float32).  ``q`` rounds every matmul operand: the identity
for the reference, a lower precision for the control.
"""
import importlib


def model_module(family: str):
    return importlib.import_module(f"chipbench.reference.{family}")
