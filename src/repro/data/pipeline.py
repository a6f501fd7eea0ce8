"""Synthetic data pipeline for the model zoo.

Offline container => deterministic synthetic streams.  ``synthetic_batch``
fabricates a batch matching a ModelConfig's input_kind (tokens / audio
frames / tokens+vision); ``TokenStream`` provides an infinite, seeded,
shard-aware iterator used by the example drivers — the same interface a real
corpus loader would expose (per-host sharding, epoch bookkeeping).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import ModelConfig

__all__ = ["synthetic_batch", "TokenStream", "make_batch_iterator"]


def token_runs(key, batch: int, seq: int, vocab: int, follow: float = 0.75):
    """(batch, seq) int32 tokens with next-token structure: each token is,
    with probability ``follow``, its predecessor plus one (mod ``vocab``),
    else a fresh draw from a Zipf-ish marginal.  I.i.d. tokens would leave
    a language model nothing to learn beyond that marginal (a fraction of
    a nat), too little for a loss curve to show training; runs give it a
    next-token rule worth several nats."""
    k1, k2, k3 = jax.random.split(key, 3)
    fresh = jnp.minimum(
        jax.random.randint(k1, (batch, seq), 0, vocab),
        jax.random.randint(k2, (batch, seq), 0, vocab),
    )
    t = jnp.arange(seq)
    restart = ~jax.random.bernoulli(k3, follow, (batch, seq)) | (t == 0)
    start = jax.lax.cummax(jnp.where(restart, t, 0), axis=1)
    first = jnp.take_along_axis(fresh, start, axis=1)
    return (first + (t - start)) % vocab


def synthetic_batch(key, cfg: ModelConfig, batch: int, seq: int) -> Dict:
    """One fabricated batch for the given architecture."""
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.input_kind == "frames":
        return {
            "frames": jax.random.normal(k1, (batch, seq, cfg.frame_dim), cfg.jdtype),
            "targets": jax.random.randint(k2, (batch, seq), 0, cfg.vocab),
            "mask": jax.random.bernoulli(k3, 0.65, (batch, seq)),
        }
    out = {"tokens": token_runs(k1, batch, seq, cfg.vocab)}
    if cfg.input_kind == "tokens+vision":
        out["vision"] = jax.random.normal(
            k3, (batch, cfg.n_vision_tokens, cfg.d_model), cfg.jdtype
        )
    return out


@dataclasses.dataclass
class TokenStream:
    """Infinite seeded stream, shardable by (shard_id, num_shards)."""

    cfg: ModelConfig
    batch: int
    seq: int
    seed: int = 0
    shard_id: int = 0
    num_shards: int = 1

    def __iter__(self) -> Iterator[Dict]:
        step = 0
        while True:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), step),
                self.shard_id + self.num_shards * 131071,
            )
            yield synthetic_batch(key, self.cfg, self.batch, self.seq)
            step += 1


def make_batch_iterator(cfg: ModelConfig, batch: int, seq: int, seed: int = 0):
    return iter(TokenStream(cfg, batch, seq, seed=seed))
