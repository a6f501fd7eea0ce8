"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) in pure JAX.

Chunked SSD algorithm for training/prefill:

  Within each chunk of length Q the output is a masked (causal, decay-
  weighted) attention-like quadratic form; across chunks a recurrent state
  h (heads, head_dim, d_state) is carried by a lax.scan.  This is the
  TPU-native mapping of the paper's "quadratic intra-chunk, linear inter-
  chunk" scheme: the quadratic part is MXU einsums over (Q, Q) tiles, the
  recurrence touches only the (H, P, N) state.

Decode: single-step SSM recurrence + rolling conv state, O(1) per token —
this is what makes `long_500k` native for SSM/hybrid architectures.

Layout follows Mamba-2: input projection produces [z (gate), x, B, C, dt];
depthwise causal conv over the (x, B, C) channels; A is a per-head scalar
decay (negative), D a per-head skip.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.sharding.constraints import maybe_constrain
from .layers import F32, dense_init, init_rmsnorm, rmsnorm

__all__ = ["init_mamba2", "mamba2_forward", "mamba2_decode_step", "init_ssm_state"]


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads


def init_mamba2(key, cfg, dtype):
    d = cfg.d_model
    d_inner, nh = _dims(cfg)
    N = cfg.ssm_state
    conv_dim = d_inner + 2 * N
    ks = jax.random.split(key, 6)
    d_in_proj = 2 * d_inner + 2 * N + nh  # z, x, B, C, dt
    return {
        "in_proj": dense_init(ks[0], d, d_in_proj, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), F32) * 0.1).astype(
            dtype
        ),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, nh, dtype=F32)
        ),  # A = -exp(A_log), per head
        "D": jnp.ones((nh,), F32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nh,), 0.01, F32))),  # softplus^-1
        "norm": init_rmsnorm(d_inner, dtype),
        "out_proj": dense_init(ks[2], d_inner, d, dtype, scale=1.0 / math.sqrt(d_inner)),
    }


def _split_proj(cfg, proj):
    d_inner, nh = _dims(cfg)
    N = cfg.ssm_state
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner : 2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N :]
    return z, xbc, dt


def _causal_conv(w, b, xbc, conv_state=None):
    """Depthwise causal conv1d over time.  xbc: (B, S, C).  Returns
    (out, new_conv_state).  conv_state: (B, K-1, C) rolling buffer."""
    K = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xbc[:, : K - 1])
    else:
        pad = conv_state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)  # (B, S+K-1, C)
    out = sum(xp[:, i : i + xbc.shape[1]] * w[i][None, None] for i in range(K))
    new_state = xp[:, -(K - 1) :] if K > 1 else None
    return jax.nn.silu((out + b[None, None]).astype(F32)).astype(xbc.dtype), new_state


def _ssd_chunked(cfg, xh, dt, B_mat, C_mat, A, init_state=None):
    """Chunked SSD scan.

    xh: (B, S, H, P); dt: (B, S, H) (post-softplus); B_mat/C_mat: (B, S, N);
    A: (H,) negative decay.  Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, P = xh.shape
    N = B_mat.shape[-1]
    Q = min(cfg.ssm_chunk, S)
    n_chunks = -(-S // Q)
    pad = n_chunks * Q - S
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))

    def reshape_chunks(t):
        return t.reshape((Bsz, n_chunks, Q) + t.shape[2:])

    xc, dtc = reshape_chunks(xh), reshape_chunks(dt)
    Bc, Cc = reshape_chunks(B_mat), reshape_chunks(C_mat)

    dA = dtc * A[None, None, None, :]  # (B, nc, Q, H)  (negative)
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative log-decay

    def chunk_fn(h_prev, inputs):
        """h_prev: (B, H, P, N); one chunk of inputs."""
        xq, dtq, bq, cq, dAq, cumq = inputs
        # decay matrices
        seg = cumq[:, :, None, :] - cumq[:, None, :, :]  # (B,Q,Q,H) log decay i<-j
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        # mask BEFORE the exp: above the diagonal seg is a growing positive
        # sum that overflows exp to inf on strong decays, and the masked
        # where's gradient would then be 0 * inf = NaN
        seg = jnp.where(causal[None, :, :, None], seg, -jnp.inf)
        L = jnp.exp(seg)  # (B,Q,Q,H)
        # intra-chunk (quadratic) term: y_i += sum_j L_ij (C_i.B_j) dt_j x_j
        CB = jnp.einsum("bqn,bpn->bqp", cq, bq, preferred_element_type=F32)  # (B,Q,Q)
        W = CB[:, :, :, None] * L  # (B,Q,Q,H)
        y_intra = jnp.einsum(
            "bqjh,bjh,bjhp->bqhp", W, dtq, xq.astype(F32), preferred_element_type=F32
        )
        # inter-chunk: contribution of carried state
        decay_in = jnp.exp(cumq)  # (B,Q,H)
        y_inter = jnp.einsum(
            "bqn,bhpn,bqh->bqhp", cq, h_prev, decay_in, preferred_element_type=F32
        )
        # state update: h_new = decay_total * h_prev + sum_j decay_j->end B_j dt_j x_j
        total = jnp.exp(cumq[:, -1:, :])  # (B,1,H)
        decay_out = jnp.exp(cumq[:, -1:, :] - cumq)  # (B,Q,H)
        dBx = jnp.einsum(
            "bqn,bqh,bqhp->bhpn",
            bq,
            dtq * decay_out,
            xq.astype(F32),
            preferred_element_type=F32,
        )
        h_new = h_prev * total[:, 0, :, None, None] + dBx
        return h_new, (y_intra + y_inter).astype(xh.dtype)

    h0 = (
        jnp.zeros((Bsz, H, P, N), F32)
        if init_state is None
        else init_state.astype(F32)
    )
    inputs = tuple(
        jnp.moveaxis(t, 1, 0) for t in (xc, dtc, Bc, Cc, dA, cum)
    )
    h_final, ys = jax.lax.scan(chunk_fn, h0, inputs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, n_chunks * Q, H, P)
    return y[:, :S], h_final


class SSMState(NamedTuple):
    h: jnp.ndarray  # (B, H, P, N) recurrent state
    conv: jnp.ndarray  # (B, K-1, conv_dim) rolling conv buffer


def init_ssm_state(cfg, batch: int, dtype=jnp.float32) -> SSMState:
    d_inner, nh = _dims(cfg)
    N = cfg.ssm_state
    conv_dim = d_inner + 2 * N
    return SSMState(
        h=jnp.zeros((batch, nh, cfg.ssm_head_dim, N), F32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    )


def mamba2_forward(params, cfg, x, *, state: Optional[SSMState] = None):
    """Full-sequence forward (training / prefill).  Returns (out, new_state)."""
    Bsz, S, d = x.shape
    d_inner, nh = _dims(cfg)
    N, P = cfg.ssm_state, cfg.ssm_head_dim

    proj = x @ params["in_proj"]
    z, xbc, dt = _split_proj(cfg, proj)
    conv_in_state = state.conv if state is not None else None
    xbc, new_conv = _causal_conv(params["conv_w"], params["conv_b"], xbc, conv_in_state)
    xs = xbc[..., :d_inner].reshape(Bsz, S, nh, P)
    B_mat = xbc[..., d_inner : d_inner + N].astype(F32)
    C_mat = xbc[..., d_inner + N :].astype(F32)
    dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"][None, None])  # (B,S,H)
    A = -jnp.exp(params["A_log"])  # (H,)

    xs = maybe_constrain(xs, "data", None, "heads", None)
    y, h_final = _ssd_chunked(
        cfg, xs, dt, B_mat, C_mat, A, None if state is None else state.h
    )
    y = y + params["D"][None, None, :, None] * xs.astype(F32)
    y = y.reshape(Bsz, S, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z.astype(F32)).astype(x.dtype))
    out = y @ params["out_proj"]
    new_state = None
    if state is not None:
        new_state = SSMState(h=h_final, conv=new_conv.astype(state.conv.dtype))
    return out, new_state


def mamba2_decode_step(params, cfg, x, state: SSMState):
    """Single-token decode.  x: (B, 1, d).  Returns (out (B,1,d), new_state)."""
    Bsz = x.shape[0]
    d_inner, nh = _dims(cfg)
    N, P = cfg.ssm_state, cfg.ssm_head_dim

    proj = x[:, 0] @ params["in_proj"]  # (B, dproj)
    z, xbc, dt = _split_proj(cfg, proj)
    # rolling conv: append, convolve last position, shift buffer
    K = cfg.ssm_conv
    window = jnp.concatenate([state.conv.astype(xbc.dtype), xbc[:, None]], axis=1)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"])
    xbc = jax.nn.silu((conv_out + params["conv_b"][None]).astype(F32)).astype(x.dtype)
    new_conv = window[:, 1:]

    xs = xbc[..., :d_inner].reshape(Bsz, nh, P).astype(F32)
    B_mat = xbc[..., d_inner : d_inner + N].astype(F32)  # (B,N)
    C_mat = xbc[..., d_inner + N :].astype(F32)
    dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"][None])  # (B,H)
    A = -jnp.exp(params["A_log"])

    decay = jnp.exp(dt * A[None])  # (B,H)
    h_new = state.h * decay[:, :, None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xs, B_mat
    )
    y = jnp.einsum("bhpn,bn->bhp", h_new, C_mat) + params["D"][None, :, None] * xs
    y = y.reshape(Bsz, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z.astype(F32)).astype(x.dtype))
    out = (y @ params["out_proj"])[:, None]
    return out, SSMState(h=h_new, conv=new_conv.astype(state.conv.dtype))
