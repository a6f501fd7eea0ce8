"""Algorithm 1 (Byz-VR-MARINA-PP with clipped differences) in plain
float32, over the plain reference models.

One step from (x^k, g^k):

    x^{k+1} = x^k - gamma g^k
    c_k ~ Bernoulli(p); the cohort is all W workers when c_k, else C of them
    full round (c_k):  g^{k+1} = Agg_{sampled}(attack(grad f_i(x^{k+1})))
    else:              m_i = attack(grad f_i(x^{k+1}) - grad f_i(x^k))
                       lambda = alpha gamma ||g^k||
                       g^{k+1} = g^k + Agg_{sampled}(min(1, lambda/||m_i||) m_i)

The coin, the cohort and the keys come from the step's key exactly as the
step under test draws them (``schedule``), so both follow the same rounds.
x and g are held in the storage precision the configuration states
(``store``) and every step's arithmetic is float32.  Agg is the
coordinate-wise median over the sampled rows; the Byzantine workers are
the last ``n_byz``; the attacks are bit flip and ALIE (z = 1.5).
"""
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ALIE_Z = 1.5


def schedule(key, p: float, W: int, C: int):
    """(next key, full round?, sampled mask) of one step."""
    key, k_bern, k_cohort, _, _, _ = jax.random.split(key, 6)
    full = bool(jax.random.bernoulli(k_bern, p))
    perm = np.asarray(jax.random.permutation(k_cohort, W))
    rank = np.empty(W, np.int64)
    rank[perm] = np.arange(W)
    return key, full, rank < (W if full else C)


def traffic_key(cell, compared: int):
    """The smallest key from which a run's rounds stand for the cell's
    traffic: over its first ``cell["rounds"]`` rounds the full rounds
    number p x rounds, and where C < W the difference rounds that sample
    only Byzantine workers number their expected share, each to within one;
    and of the ``compared`` rounds the first is a difference round that
    samples an honest worker and the last a full round, so that the
    comparison sees a difference from g^0 and every worker's gradient and
    attack at once."""
    W, C, n = cell["workers"], cell["cohort"], cell["rounds"]
    byz = np.arange(W) >= W - cell["n_byz"]
    byz_only = math.comb(cell["n_byz"], C) / math.comb(W, C)
    for seed in itertools.count():
        key, kinds = jax.random.PRNGKey(seed), []
        for k in range(n):
            key, full, sampled = schedule(key, cell["p"], W, C)
            kinds.append("full" if full else
                         "byz_only" if not np.any(sampled & ~byz) else "diff")
            if k == compared - 1 and (kinds[0] != "diff"
                                      or kinds[-1] != "full"):
                break
        else:
            n_full = kinds.count("full")
            n_diff = n - n_full
            if abs(n_full - round(cell["p"] * n)) <= 1 and (
                    C == W or abs(kinds.count("byz_only")
                                  - round(byz_only * n_diff)) <= 1):
                return seed


def _fp8(x):
    """float8_e4m3fn rounding with a per-tensor scale (amax to 448): three
    mantissa bits, and steps of 2^-9 below the least normal 2^-6.  Rounded
    with ``reduce_precision`` and ``round``, not an ``astype`` round trip,
    which the TPU compiler may fold away."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = x / s
    y = jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 512.0) / 512.0,
                  jax.lax.reduce_precision(y, exponent_bits=5,
                                           mantissa_bits=3))
    return y * s


@jax.custom_vjp
def fp8_round(x):
    """A matmul operand in scaled float8 for the control; its gradient is
    rounded the same way, as scaled fp8 training does."""
    return _fp8(x)


fp8_round.defvjp(lambda x: (_fp8(x), None), lambda _, ct: (_fp8(ct),))


def _bf16(x):
    """bfloat16 rounding (to nearest even), kept in float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# per stored dtype: (its rounding, the control's one precision below)
_ROUND = {jnp.dtype(jnp.float32): (lambda x: x, _bf16),
          jnp.dtype(jnp.bfloat16): (_bf16, _fp8)}


def storage(dtypes, control: bool):
    """Round a float32 tree to the precision its leaves are held in: the
    configured dtype, or for the control the next one below it."""
    def one(x, dt):
        return _ROUND[jnp.dtype(dt)][control](x)

    return jax.jit(lambda t: jax.tree_util.tree_map(one, t, dtypes))


_leaf_norms = jax.jit(lambda t: jnp.stack(
    [jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
     for l in jax.tree_util.tree_leaves(t)]))
_sub = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))
_axpy = jax.jit(lambda a, x, y: jax.tree_util.tree_map(
    lambda u, v: u - a * v, x, y))


def leaf_norms(tree):
    return np.asarray(_leaf_norms(tree), np.float64)


@jax.jit
def _alie(rows, good_sampled):
    w = good_sampled.astype(F32)[:, None]
    denom = jnp.maximum(jnp.sum(w), 1.0)
    mu = jnp.sum(rows * w, axis=0) / denom
    var = jnp.sum(jnp.square(rows - mu) * w, axis=0) / denom
    return mu - ALIE_Z * jnp.sqrt(var + 1e-12)


def attack(rows, name, byz, sampled):
    """rows: (W, ...) float32 messages of one leaf."""
    bad = np.flatnonzero(byz)
    if name == "bf":
        return rows.at[bad].multiply(-1.0)
    if name == "alie":
        flat = rows.reshape(rows.shape[0], -1)
        payload = _alie(flat, jnp.asarray(~byz & sampled))
        return flat.at[bad].set(payload).reshape(rows.shape)
    raise ValueError(f"the reference has no attack {name!r}")


@jax.jit
def _row_ss(rows):
    return jnp.sum(jnp.square(rows.reshape(rows.shape[0], -1)), axis=1)


@jax.jit
def _clipped_median(rows, factors, sampled):
    """Coordinate-wise median of the sampled rows, each scaled by its clip
    factor (unsampled rows sort last and are not counted)."""
    flat = rows.reshape(rows.shape[0], -1) * factors[:, None]
    s = jnp.sort(jnp.where(sampled[:, None], flat, jnp.inf), axis=0)
    n = jnp.sum(sampled)
    lo = jnp.take(s, (n - 1) // 2, axis=0)
    hi = jnp.take(s, n // 2, axis=0)
    return (0.5 * (lo + hi)).reshape(rows.shape[1:])


def run(x0, g0, key, tokens, cell, grad, dtypes, *, control=False, steps=2,
        batch_fault=False):
    """Follow ``steps`` steps from (x0, g0) on tokens[k] (W*b, S) and
    return what the benchmark compares: per step the round kind and the
    norms, per leaf, of g^{k+1} - g^k on a difference round and of
    g^{k+1} on a full round; then the per-leaf norms of
    x^{steps+1} - x^0, the parameters after one more update.  ``grad``
    is the jitted gradient of the reference loss (params, tokens)."""
    W, C = cell["workers"], cell["cohort"] or cell["workers"]
    gamma, alpha = cell["gamma"], cell["clip_alpha"]
    byz = np.arange(W) >= W - cell["n_byz"]
    store = storage(dtypes, control)
    to32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda l: l.astype(F32), t))
    x, g = store(to32(x0)), store(to32(g0))
    x0_32 = x
    rounds = []
    for k in range(steps):
        key, full, sampled = schedule(key, cell["p"], W, C)
        x_new = store(_axpy(gamma, x, g))
        lam = alpha * gamma * float(np.sqrt(np.sum(leaf_norms(g) ** 2)))
        wtok = np.asarray(tokens[k]).reshape(W, -1, tokens[k].shape[-1])
        host = []  # each worker's message leaves, staged in host memory
        for i in range(W):
            t = wtok[i]
            if batch_fault:  # half of the batch left out
                t = t[: t.shape[0] // 2] if t.shape[0] > 1 \
                    else t[:, : t.shape[1] // 2]
            m = grad(x_new, t)
            if not full:
                m = _sub(m, grad(x, t))
            host.append([np.asarray(l) for l in jax.tree_util.tree_leaves(m)])
            del m
        treedef = jax.tree_util.tree_structure(x)

        def rows(j):
            return attack(jnp.asarray(np.stack([h[j] for h in host])),
                          cell["attack"], byz, sampled)

        n_leaves = len(host[0])
        norms = np.sqrt(sum(np.asarray(_row_ss(rows(j)), np.float64)
                            for j in range(n_leaves)))
        factors = np.ones(W) if full else np.minimum(
            1.0, lam / np.maximum(norms, 1e-30))
        f32, mask = jnp.asarray(factors, F32), jnp.asarray(sampled)
        agg = [_clipped_median(rows(j), f32, mask) for j in range(n_leaves)]
        del host
        agg = jax.tree_util.tree_unflatten(treedef, agg)
        g_new = store(agg) if full else store(
            jax.tree_util.tree_map(jnp.add, g, agg))
        update = leaf_norms(g_new) if full else leaf_norms(_sub(g_new, g))
        rounds.append({"full": full, "sampled": sampled.tolist(),
                       "radius": lam, "message_norms": norms.tolist(),
                       "clip_factors": factors.tolist(),
                       "update_norms": update})
        x, g = x_new, g_new
    change = leaf_norms(_sub(store(_axpy(gamma, x, g)), x0_32))
    return {"rounds": rounds, "change_norms": change}
