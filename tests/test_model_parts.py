"""Deep correctness oracles for the model-zoo building blocks.

- Mamba-2 SSD chunked scan vs a naive per-timestep recurrence
- MoE scatter dispatch vs a loop-over-experts reference
- chunked flash-style attention vs plain softmax(QK^T)V
- chunked cross-entropy vs direct log_softmax
- MLA absorbed decode vs the expanded formulation (same layer params)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model import ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import attention, init_mla, mla_forward

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# SSD vs sequential recurrence
# ---------------------------------------------------------------------------

def _ssd_sequential(xh, dt, B_mat, C_mat, A, h0=None):
    """Naive O(S) state recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,
    y_t = C_t . h_t   (per head/headdim)."""
    Bsz, S, H, P = xh.shape
    N = B_mat.shape[-1]
    h = np.zeros((Bsz, H, P, N), np.float64) if h0 is None else np.array(h0, np.float64)
    ys = np.zeros((Bsz, S, H, P), np.float64)
    xh, dt = np.asarray(xh, np.float64), np.asarray(dt, np.float64)
    B_mat, C_mat, A = np.asarray(B_mat, np.float64), np.asarray(C_mat, np.float64), np.asarray(A, np.float64)
    for t in range(S):
        decay = np.exp(dt[:, t] * A[None])  # (B,H)
        inp = np.einsum("bh,bhp,bn->bhpn", dt[:, t], xh[:, t], B_mat[:, t])
        h = h * decay[:, :, None, None] + inp
        ys[:, t] = np.einsum("bhpn,bn->bhp", h, C_mat[:, t])
    return ys, h


@pytest.mark.parametrize("seq,chunk", [(8, 4), (16, 4), (13, 8), (32, 32)])
def test_ssd_chunked_matches_sequential(seq, chunk):
    cfg = ModelConfig(
        name="t", n_layers=1, d_model=32, n_heads=1, n_kv_heads=1, d_ff=0,
        vocab=16, mixer_pattern=("ssm",), mlp_pattern=("none",),
        ssm_state=8, ssm_head_dim=4, ssm_chunk=chunk, dtype="float32",
    )
    rng = np.random.RandomState(0)
    Bsz, H, P, N = 2, 3, 4, 8
    xh = jnp.asarray(rng.randn(Bsz, seq, H, P).astype(np.float32))
    dt = jnp.asarray(rng.rand(Bsz, seq, H).astype(np.float32) * 0.5)
    Bm = jnp.asarray(rng.randn(Bsz, seq, N).astype(np.float32))
    Cm = jnp.asarray(rng.randn(Bsz, seq, N).astype(np.float32))
    A = -jnp.asarray(rng.rand(H).astype(np.float32) + 0.1)
    y, h = ssm_mod._ssd_chunked(cfg, xh, dt, Bm, Cm, A)
    y_ref, h_ref = _ssd_sequential(xh, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=2e-4, atol=2e-4)


def test_ssd_carried_state_across_calls():
    """Splitting a sequence across two forward calls with carried state must
    equal one full pass (prefill-then-decode consistency for SSM)."""
    cfg = ModelConfig(
        name="t", n_layers=1, d_model=32, n_heads=1, n_kv_heads=1, d_ff=0,
        vocab=16, mixer_pattern=("ssm",), mlp_pattern=("none",),
        ssm_state=8, ssm_head_dim=4, ssm_chunk=4, dtype="float32",
    )
    rng = np.random.RandomState(1)
    Bsz, S, H, P, N = 1, 12, 2, 4, 8
    xh = jnp.asarray(rng.randn(Bsz, S, H, P).astype(np.float32))
    dt = jnp.asarray(rng.rand(Bsz, S, H).astype(np.float32) * 0.5)
    Bm = jnp.asarray(rng.randn(Bsz, S, N).astype(np.float32))
    Cm = jnp.asarray(rng.randn(Bsz, S, N).astype(np.float32))
    A = -jnp.asarray(rng.rand(H).astype(np.float32) + 0.1)
    y_full, h_full = ssm_mod._ssd_chunked(cfg, xh, dt, Bm, Cm, A)
    y1, h1 = ssm_mod._ssd_chunked(cfg, xh[:, :8], dt[:, :8], Bm[:, :8], Cm[:, :8], A)
    y2, h2 = ssm_mod._ssd_chunked(
        cfg, xh[:, 8:], dt[:, 8:], Bm[:, 8:], Cm[:, 8:], A, init_state=h1
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_full),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=2e-4, atol=2e-4)


def test_ssd_gradients_finite_under_strong_decay():
    """A chunk whose summed decay exceeds float32's exp range (sum dt*|A|
    > 88 over the chunk, as large dt does at the published chunk of 256)
    must still have finite gradients: the above-diagonal segment sums are
    masked before the exp, so no inf meets the mask's zero gradient."""
    cfg = ModelConfig(
        name="t", n_layers=1, d_model=32, n_heads=1, n_kv_heads=1, d_ff=0,
        vocab=16, mixer_pattern=("ssm",), mlp_pattern=("none",),
        ssm_state=8, ssm_head_dim=4, ssm_chunk=16, dtype="float32",
    )
    rng = np.random.RandomState(2)
    Bsz, S, H, P, N = 1, 16, 2, 4, 8
    xh = jnp.asarray(rng.randn(Bsz, S, H, P).astype(np.float32))
    dt = jnp.full((Bsz, S, H), 8.0, jnp.float32)  # 16 * 8 * 16 >> 88
    Bm = jnp.asarray(rng.randn(Bsz, S, N).astype(np.float32))
    Cm = jnp.asarray(rng.randn(Bsz, S, N).astype(np.float32))
    A = -jnp.asarray([1.0, 16.0], jnp.float32)

    def loss(xh, dt, Bm, Cm):
        y, h = ssm_mod._ssd_chunked(cfg, xh, dt, Bm, Cm, A)
        return jnp.sum(y) + jnp.sum(h)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(xh, dt, Bm, Cm)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    y, h = ssm_mod._ssd_chunked(cfg, xh, dt, Bm, Cm, A)
    y_ref, h_ref = _ssd_sequential(xh, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MoE dispatch vs loop-over-experts
# ---------------------------------------------------------------------------

def test_moe_scatter_matches_expert_loop():
    cfg = ModelConfig(
        name="m", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2, d_ff=8,
        vocab=16, mlp_pattern=("moe",), n_experts=4, experts_per_token=2,
        dtype="float32", capacity_factor=64.0,  # no drops
    )
    params = moe_mod.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
    out = moe_mod.moe_forward(params, cfg, x, capacity_factor=64.0)

    # reference: run every expert densely, combine with the same gates
    xt = x.reshape(-1, 16)
    logits = xt @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, 2)
    gates = gates / gates.sum(-1, keepdims=True)
    expert_outs = []
    for e in range(4):
        g = jax.nn.silu(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e])
        expert_outs.append(g @ params["w_down"][e])
    expert_outs = jnp.stack(expert_outs)  # (E, T, D)
    T = xt.shape[0]
    ref = jnp.zeros_like(xt)
    for kk in range(2):
        ref = ref + expert_outs[ids[:, kk], jnp.arange(T)] * gates[:, kk][:, None]
    np.testing.assert_allclose(
        np.asarray(out.out.reshape(-1, 16)), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_moe_capacity_drops_tokens():
    """With capacity_factor << 1 most tokens are dropped => output shrinks."""
    cfg = ModelConfig(
        name="m", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2, d_ff=8,
        vocab=16, mlp_pattern=("moe",), n_experts=4, experts_per_token=2,
        dtype="float32",
    )
    params = moe_mod.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
    full = moe_mod.moe_forward(params, cfg, x, capacity_factor=64.0)
    tight = moe_mod.moe_forward(params, cfg, x, capacity_factor=0.1)
    assert float(jnp.linalg.norm(tight.out)) < float(jnp.linalg.norm(full.out))


# ---------------------------------------------------------------------------
# attention vs plain softmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Tk,chunk_hit", [(48, False), (4096, True)])
def test_chunked_attention_matches_plain(Tk, chunk_hit):
    rng = np.random.RandomState(3)
    B, Tq, H, KV, hd = 1, 8, 4, 2, 16
    q = jnp.asarray(rng.randn(B, Tq, H, hd).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Tk, KV, hd).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Tk, KV, hd).astype(np.float32))
    out = attention(q, k, v, causal=True, q_offset=Tk - Tq, chunk=1024)
    # plain reference
    kr = np.repeat(np.asarray(k), H // KV, axis=2)
    vr = np.repeat(np.asarray(v), H // KV, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kr) / np.sqrt(hd)
    q_pos = (Tk - Tq) + np.arange(Tq)
    mask = np.arange(Tk)[None, :] <= q_pos[:, None]
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, vr)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MLA absorbed decode vs expanded path
# ---------------------------------------------------------------------------

def test_mla_absorbed_decode_equals_expanded_math():
    cfg = ModelConfig(
        name="mla", n_layers=1, d_model=64, n_heads=4, n_kv_heads=4, d_ff=64,
        vocab=16, attn_kind="mla", q_lora_rank=24, kv_lora_rank=16,
        qk_rope_dim=8, head_dim=16, dtype="float32",
    )
    params = init_mla(KEY, cfg, jnp.float32)
    B, S = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, 64))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    # full-sequence (expanded) output at the last position
    out_full, _ = mla_forward(params, cfg, x, positions=positions)
    # incremental decode through the absorbed path
    cache = {
        "ckv": jnp.zeros((B, S, cfg.kv_lora_rank)),
        "krope": jnp.zeros((B, S, cfg.qk_rope_dim)),
    }
    for t in range(S):
        out_t, cache = mla_forward(
            params, cfg, x[:, t : t + 1],
            positions=jnp.full((B, 1), t), cache=cache, cache_index=t,
        )
    np.testing.assert_allclose(
        np.asarray(out_t[:, 0]), np.asarray(out_full[:, -1]), rtol=1e-3, atol=1e-3
    )


# ---------------------------------------------------------------------------
# chunked CE
# ---------------------------------------------------------------------------

def test_chunked_ce_matches_direct():
    from repro.models.model import _chunked_ce

    cfg = ModelConfig(
        name="c", n_layers=1, d_model=8, n_heads=1, n_kv_heads=1, d_ff=8,
        vocab=11, logit_chunk=3, dtype="float32",
    )
    rng = np.random.RandomState(5)
    B, S = 2, 7
    h = jnp.asarray(rng.randn(B, S, 8).astype(np.float32))
    un = jnp.asarray(rng.randn(8, 11).astype(np.float32))
    tgt = jnp.asarray(rng.randint(0, 11, (B, S)))
    valid = jnp.asarray(rng.rand(B, S) > 0.3)
    loss = _chunked_ce(cfg, h, un, tgt, valid)
    logits = np.asarray(h) @ np.asarray(un)
    lse = jax.nn.logsumexp(jnp.asarray(logits), axis=-1)
    gold = np.take_along_axis(logits, np.asarray(tgt)[..., None], axis=-1)[..., 0]
    nll = (np.asarray(lse) - gold) * np.asarray(valid)
    ref = nll.sum() / np.asarray(valid).sum()
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
    # gradient flows
    g = jax.grad(lambda hh: _chunked_ce(cfg, hh, un, tgt, valid))(h)
    assert float(jnp.abs(g).max()) > 0
