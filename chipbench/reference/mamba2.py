"""Mamba-2 language model (SSD, arXiv:2405.21060) in plain float32.

Each block is rematerialized for the gradient, which keeps the reference's
memory to one block's activations.

Block: x + out_proj(rmsnorm(y * silu(z))), y the SSD scan of the
conv-activated (x, B, C) channels with per-head decay A = -exp(A_log),
step dt = softplus(dt + dt_bias) and skip D; the model is embedding,
``n_layers`` such blocks with a pre-norm each, a final norm and an untied
output projection.  The scan is computed in chunks of ``ssm_chunk``:
quadratic inside a chunk, a carried state between chunks, which is exact.
"""
import math

import jax
import jax.numpy as jnp

from .common import (F32, layer_slice, mm, next_token_loss, normal,
                     rmsnorm)


def dims(m):
    d_inner = m["ssm_expand"] * m["d_model"]
    return d_inner, d_inner // m["ssm_head_dim"]


def init(key, m, dtype):
    d, V, L = m["d_model"], m["vocab"], m["n_layers"]
    N, K = m["ssm_state"], m["ssm_conv"]
    di, nh = dims(m)
    conv_dim = di + 2 * N
    ks = jax.random.split(key, 5)
    lk = jax.random.split(ks[2], 3)
    mixer = {
        "in_proj": normal(lk[0], (L, d, 2 * di + 2 * N + nh),
                          1 / math.sqrt(d), dtype),
        "conv_w": normal(lk[1], (L, K, conv_dim), 0.1, dtype),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=F32)), (L, nh)),
        "D": jnp.ones((L, nh), F32),
        "dt_bias": jnp.full((L, nh), math.log(math.expm1(0.01)), F32),
        "norm": {"scale": jnp.ones((L, di), dtype)},
        "out_proj": normal(lk[2], (L, di, d), 1 / math.sqrt(di), dtype),
    }
    return {
        "embed": normal(ks[0], (V, d), 0.02, dtype),
        "body": ({"norm1": {"scale": jnp.ones((L, d), dtype)},
                  "mixer": mixer},),
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "unembed": normal(ks[3], (d, V), 0.02, dtype),
    }


def ssd(q, x, dt, A, B, C, Q):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s.

    x: (b, S, H, P); dt: (b, S, H); A: (H,); B, C: (b, S, N)."""
    b, S, H, P = x.shape
    nc = S // Q
    xr = x.reshape(b, nc, Q, H, P)
    dtr = dt.reshape(b, nc, Q, H)
    Br = B.reshape(b, nc, Q, -1)
    Cr = C.reshape(b, nc, Q, -1)
    cum = jnp.cumsum(dtr * A, axis=2)  # (b, nc, Q, H)
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,t,s,H)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    cb = jnp.einsum("bctn,bcsn->bcts", q(Cr), q(Br),
                    preferred_element_type=F32)
    weights = cb[..., None] * decay * dtr[:, :, None, :, :]
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", q(weights), q(xr),
                         preferred_element_type=F32)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dtr  # (b, nc, Q, H)
    chunk_states = jnp.einsum("bcsn,bcsh,bcshp->bchpn", q(Br), q(to_end),
                              q(xr), preferred_element_type=F32)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (b, nc, H)

    def carry(h, inp):
        state, dec = inp
        return h * dec[:, :, None, None] + state, h

    _, h_before = jax.lax.scan(
        carry, jnp.zeros((b, H, P, B.shape[-1]), F32),
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_before = jnp.moveaxis(h_before, 0, 1)  # (b, nc, H, P, N)
    y_inter = jnp.einsum("bctn,bchpn,bcth->bcthp", q(Cr), q(h_before),
                         jnp.exp(cum), preferred_element_type=F32)
    return (y_intra + y_inter).reshape(b, S, H, P)


def mixer(q, p, x, m):
    b, S, _ = x.shape
    di, nh = dims(m)
    N, P, K = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    proj = mm(q, x, p["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], \
        proj[..., 2 * di + 2 * N:]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, S, nh, P)
    B, C = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(q, xs, dt, -jnp.exp(p["A_log"]), B, C, m["ssm_chunk"])
    y = y + p["D"][:, None] * xs
    y = rmsnorm(y.reshape(b, S, di) * jax.nn.silu(z), p["norm"]["scale"])
    return mm(q, y, p["out_proj"])


def loss(params, tokens, m, q):
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0)
        body = params["body"][0]
        for i in range(m["n_layers"]):
            lp = layer_slice(body, i)
            x = x + jax.checkpoint(lambda x, lp: mixer(
                q, lp["mixer"], rmsnorm(x, lp["norm1"]["scale"]), m))(x, lp)
        h = rmsnorm(x, params["final_norm"]["scale"])
        return next_token_loss(q, h, params["unembed"], tokens)
