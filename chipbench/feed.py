"""What the benchmark hands the program: weights, g^0 and batches, made on
the device from ``--seed``.

The token generator is a copy of the program's synthetic stream
(``repro.data.pipeline.token_runs``): each token is, with probability
3/4, its predecessor plus one, else a fresh draw from a Zipf-ish marginal
over the configuration's vocabulary (its slice, where it is sliced).
"""
import re

import jax
import jax.numpy as jnp

from .reference import model_module


def seed_key(seed: int, stream: int):
    """A key for one stream of the run; seeds may exceed 32 bits."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def token_runs(key, batch: int, seq: int, vocab: int, follow: float = 0.75):
    k1, k2, k3 = jax.random.split(key, 3)
    fresh = jnp.minimum(
        jax.random.randint(k1, (batch, seq), 0, vocab),
        jax.random.randint(k2, (batch, seq), 0, vocab),
    )
    t = jnp.arange(seq)
    restart = ~jax.random.bernoulli(k3, follow, (batch, seq)) | (t == 0)
    start = jax.lax.cummax(jnp.where(restart, t, 0), axis=1)
    first = jnp.take_along_axis(fresh, start, axis=1)
    return ((first + (t - start)) % vocab).astype(jnp.int32)


def batches(seed: int, cell: dict, vocab: int):
    """(n_batches, workers * per_worker_batch, seq) int32 tokens, one
    jitted call; batch k feeds step k, rows all differ."""
    n, rows, seq = cell["batches"], cell["workers"] * cell["per_worker_batch"], \
        cell["seq"]
    make = jax.jit(lambda k: token_runs(k, n * rows, seq, vocab)
                   .reshape(n, rows, seq))
    return make(seed_key(seed, 1))


def leaf_paths(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def weights(seed: int, config: dict):
    """The benchmark's weights in the program's layout and dtypes."""
    model = model_module(config["family"])
    dtype = jnp.dtype(config["model"]["dtype"])
    return jax.jit(lambda k: model.init(k, config["model"], dtype))(
        seed_key(seed, 0))


def moved_leaves(params, cell: dict):
    """Per leaf, whether g^0 is set on it."""
    pat = re.compile(cell["g0"]["leaves"])
    return [bool(pat.search(p)) for p in leaf_paths(params)]


def g0(seed: int, params, cell: dict):
    """Algorithm 1's initial estimator g^0.

    On the leaves whose path matches ``cell["g0"]["leaves"]``,
    g^0 = (rel / gamma) * rms(x^0 leaf) * N(0, 1), so that the first step
    x^1 = x^0 - gamma g^0 moves those leaves by ``rel`` of their size;
    zero on every other leaf, where g^k then holds the aggregates alone."""
    scale = cell["g0"]["rel"] / cell["gamma"]
    moved = moved_leaves(params, cell)
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def make(key, leaves):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, on, x in zip(keys, moved, leaves):
            if on:
                x32 = x.astype(jnp.float32)
                rms = jnp.sqrt(jnp.mean(x32 * x32))
                out.append((scale * rms * jax.random.normal(
                    k, x.shape, jnp.float32)).astype(x.dtype))
            else:
                out.append(jnp.zeros_like(x))
        return out

    return jax.tree_util.tree_unflatten(
        treedef, jax.jit(make)(seed_key(seed, 2), leaves))
