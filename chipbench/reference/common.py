"""Pieces the reference models share."""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def identity(x):
    return x


def rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def mm(q, a, b):
    """``a @ b`` on rounded operands, accumulated in float32."""
    return jnp.matmul(q(a), q(b), preferred_element_type=F32)


def next_token_loss(q, h, unembed, tokens, chunk=512):
    """Mean cross-entropy of predicting tokens[:, 1:] from h[:, :-1], the
    logits of ``chunk`` positions at a time (recomputed for the gradient,
    so that the reference fits beside what the device still holds)."""
    h, targets = h[:, :-1], tokens[:, 1:]

    @jax.checkpoint
    def nll(hc, tc):
        logits = mm(q, hc, unembed)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    total = sum(nll(h[:, s:s + chunk], targets[:, s:s + chunk])
                for s in range(0, h.shape[1], chunk))
    return total / targets.size


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def layer_slice(stacked, i):
    return jax.tree_util.tree_map(lambda a: a[i], stacked)
