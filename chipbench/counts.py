"""Operations and bytes the measured work needs, counted from the
configuration's shapes (never from the compiler or the program).

Model FLOPs count one forward pass as 2 x multiply-adds of every matmul a
token needs, with the causal intra-chunk SSD term counted over the (Q+1)/2
positions a token sees on average; a
training evaluation is 3 forward passes (forward and backward), and
rematerialized recompute is not counted.
"""
import jax
import jax.numpy as jnp

from .reference import model_module


def mamba2_layer_flops(m, seq):
    d, N, K = m["d_model"], m["ssm_state"], m["ssm_conv"]
    di = m["ssm_expand"] * d
    nh = di // m["ssm_head_dim"]
    Q = min(m["ssm_chunk"], seq)
    proj = 2 * d * (2 * di + 2 * N + nh) + 2 * di * d
    conv = 2 * K * (di + 2 * N)
    ssd = (Q + 1) * (N + di) + 4 * N * di
    return proj + conv + ssd


LAYER_FLOPS = {"mamba2": mamba2_layer_flops}


def forward_flops_per_token(config, seq):
    m = config["model"]
    layer = LAYER_FLOPS[config["family"]](m, seq)
    return m["n_layers"] * layer + 2 * m["d_model"] * m["vocab"]


def required_evaluations(cell):
    """Worker evaluations (one worker's batch through forward and
    backward) a step needs, in expectation: W on a full-gradient round
    (probability p), 2 C on a difference round."""
    W = cell["workers"]
    C = cell["cohort"] or W
    return cell["p"] * W + (1 - cell["p"]) * 2 * C


def model_flops_per_step(cell, config):
    tokens_per_worker = cell["per_worker_batch"] * cell["seq"]
    return (3 * forward_flops_per_token(config, cell["seq"])
            * tokens_per_worker * required_evaluations(cell))


def message_leaves(config):
    """[(size, itemsize)] of the message (= parameter) leaves."""
    model = model_module(config["family"])
    dtype = jnp.dtype(config["model"]["dtype"])
    shapes = jax.eval_shape(
        lambda k: model.init(k, config["model"], dtype),
        jax.random.PRNGKey(0))
    return [(l.size, l.dtype.itemsize)
            for l in jax.tree_util.tree_leaves(shapes)]


def parameters(config):
    return sum(s for s, _ in message_leaves(config))


def aggregation_bytes(cell, config):
    """Least HBM traffic of one aggregation on one chip: read every worker's
    row once and write the aggregate once, of this chip's 1/chips share of
    the coordinates."""
    W = cell["workers"]
    return sum((W + 1) * s * b for s, b in message_leaves(config)) \
        / cell["chips"]
