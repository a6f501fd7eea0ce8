"""Serving path: two products on the same launcher.

1. **Model serving** — batched prefill and incremental decode on the mesh.
   Decode shapes lower ``serve_step`` — ONE new token against a KV cache
   of ``seq_len`` (``decode_32k``: batch 128 × cache 32768; ``long_500k``:
   batch 1 × 524288 context, sliding-window/SSM cache).  The batch dim
   shards over the worker (data) axes, the cache length dim over "model"
   (see repro.sharding.rules.cache_specs).

2. **Robust scoring** — batch-of-clients robustness filtering as a
   service, built on ``repro.api.ServerPlan.build()``: each request
   carries an (n, d) matrix of client updates; the endpoint runs the
   plan's full clip -> bucket -> aggregate composition (the same fused
   kernels the trainer uses) and returns the robust aggregate plus
   per-client diagnostics (distance-to-aggregate outlier score, clip
   factor, message norm).  Because the request is self-contained there is
   no iterate pair, so plans must clip with a static ``ClipSpec(radius=)``
   (or not at all) — ``make_scoring_step`` validates this at build time.

3. **Streaming aggregation** — the continuous-batching server loop
   (``repro.serve``): clients submit rows one at a time, the server
   accumulates them into per-round cohorts (incremental Gram for the
   selection rules), closes a round on a cohort-size or deadline
   trigger, and fans the aggregate out to every submitter's ticket.
   Late rows follow the configured stale policy (drop, or defer into
   the next round with a staleness-discounted weight).

    python -m repro.launch.serve --mode score --aggregator krum \
        --requests 8 --clients 16 --dim 4096 --clip-radius 5.0
    python -m repro.launch.serve --mode stream --aggregator krum \
        --clients 16 --dim 4096 --rounds 8 --cohort-size 12
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api import PlanError, ServerPlan
from repro.core.clipping import clip_factor
from repro.models.model import (
    ModelConfig,
    apply_decode,
    apply_prefill,
    init_cache,
    init_params,
)

__all__ = [
    "make_prefill_step",
    "make_serve_step",
    "abstract_serve_inputs",
    "make_scoring_step",
    "abstract_scoring_inputs",
]


# ---------------------------------------------------------------------------
# model serving (decode path)
# ---------------------------------------------------------------------------

def make_prefill_step(model_cfg: ModelConfig):
    def prefill_step(params, batch):
        return apply_prefill(params, model_cfg, batch)

    return prefill_step


def make_serve_step(model_cfg: ModelConfig):
    """serve_step(params, batch, cache, cache_index) -> (next_token, logits, cache)."""

    def serve_step(params, batch, cache, cache_index):
        logits, new_cache = apply_decode(params, model_cfg, batch, cache, cache_index)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return serve_step


def abstract_serve_inputs(model_cfg: ModelConfig, batch: int, cache_len: int):
    """ShapeDtypeStructs for (params, batch, cache, cache_index)."""
    params = jax.eval_shape(partial(init_params, cfg=model_cfg), jax.random.PRNGKey(0))
    b = {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
    if model_cfg.input_kind == "tokens+vision":
        b["vision"] = jax.ShapeDtypeStruct(
            (batch, model_cfg.n_vision_tokens, model_cfg.d_model), model_cfg.jdtype
        )
    cache = jax.eval_shape(lambda: init_cache(model_cfg, batch, cache_len))
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    return params, b, cache, idx


# ---------------------------------------------------------------------------
# robust scoring (ServerPlan path)
# ---------------------------------------------------------------------------

def make_scoring_step(plan: ServerPlan):
    """Compile ``plan`` into a batched robust-scoring endpoint.

    ``scoring_step(batch_xs, batch_mask=None, key=None)`` takes a
    (B, n, d) batch of requests — B independent cohorts of n client
    update vectors — and returns a dict of per-request results:

      aggregate   (B, d)  the plan's robust aggregate of each request
      distance    (B, n)  per-client l2 distance to the aggregate (the
                          outlier score: byzantine payloads that the rule
                          rejected land far from it)
      clip_factor (B, n)  the server-clip scale each client received
                          (1.0 everywhere for plans without a clip stage)
      norm        (B, n)  per-client message norms

    ``batch_mask`` (B, n) marks the participating clients of each request
    (partial participation); None means all.  Requests are mapped with
    ``lax.map`` so the fused per-request kernels stay exactly the shapes
    the trainer runs.

    Default arguments are canonicalized BEFORE the jit boundary: calls
    with ``batch_mask=None`` / ``key=None`` and calls passing the
    equivalent arrays share ONE compiled program (the jitted inner
    function is exposed as ``scoring_step.jitted``; its ``_cache_size()``
    stays 1 across default/explicit call mixes of one request shape).
    """
    if plan.schedule.placement != "naive":
        raise PlanError(
            "the scoring endpoint aggregates each request whole-message "
            "in-process; use ScheduleSpec(placement='naive') — the "
            "sharded placement is a mesh-trainer schedule"
        )
    if plan.clip is not None and plan.clip.radius is None:
        raise PlanError(
            "scoring requests carry no iterate pair, so the "
            "data-dependent ClipSpec(alpha) radius is undefined here; "
            "use ClipSpec(radius=...) for a static server clip, or drop "
            "the clip stage"
        )
    step = plan.build()

    def score_one(xs, mask, key):
        x32 = xs.astype(jnp.float32)
        agg = step(xs, mask=mask, key=key)  # static clip radius applies
        a32 = agg.astype(jnp.float32)
        dist = jnp.sqrt(jnp.sum((x32 - a32[None, :]) ** 2, axis=1))
        norms = jnp.sqrt(jnp.sum(x32 * x32, axis=1))
        if plan.clip is not None:
            fac = clip_factor(norms, jnp.float32(plan.clip.radius))
        else:
            fac = jnp.ones_like(norms)
        return {
            "aggregate": a32,
            "distance": dist,
            "clip_factor": fac,
            "norm": norms,
        }

    @jax.jit
    def _score_batch(batch_xs, batch_mask, key):
        keys = jax.random.split(key, batch_xs.shape[0])
        return jax.lax.map(
            lambda args: score_one(*args), (batch_xs, batch_mask, keys)
        )

    def scoring_step(batch_xs, batch_mask=None, key: Optional[jax.Array] = None):
        # canonicalize the optional arguments BEFORE the jit boundary:
        # None and the equivalent explicit arrays must hit one trace
        batch_xs = jnp.asarray(batch_xs)
        B, n = batch_xs.shape[0], batch_xs.shape[1]
        if key is None:
            key = jax.random.PRNGKey(0)
        if batch_mask is None:
            batch_mask = jnp.ones((B, n), bool)
        else:
            batch_mask = jnp.asarray(batch_mask).astype(bool)
        return _score_batch(batch_xs, batch_mask, key)

    scoring_step.jitted = _score_batch
    return scoring_step


def abstract_scoring_inputs(batch: int, n_clients: int, dim: int,
                            dtype=jnp.float32):
    """ShapeDtypeStructs for (batch_xs, batch_mask, key)."""
    return (
        jax.ShapeDtypeStruct((batch, n_clients, dim), dtype),
        jax.ShapeDtypeStruct((batch, n_clients), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )


# ---------------------------------------------------------------------------
# CLI launcher:
#   python -m repro.launch.serve --arch jamba_v01_52b            (decode)
#   python -m repro.launch.serve --mode score --aggregator krum  (scoring)
# ---------------------------------------------------------------------------

def _main_decode(args):
    import time

    from repro.configs.registry import get_smoke_config

    cfg = get_smoke_config(args.arch)
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    params = init_params(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_serve_step(cfg))
    cache = init_cache(cfg, args.batch, args.tokens + 1)
    tok = jax.random.randint(jax.random.PRNGKey(1), (args.batch, 1), 0, cfg.vocab)
    t0 = time.time()
    for t in range(args.tokens):
        batch = {"tokens": tok}
        if cfg.input_kind == "tokens+vision":
            batch["vision"] = jnp.zeros(
                (args.batch, cfg.n_vision_tokens, cfg.d_model), cfg.jdtype
            )
        nxt, _, cache = step(params, batch, cache, t)
        tok = nxt[:, None]
    print(f"[serve] {cfg.name}: {args.tokens} tokens x batch {args.batch} in "
          f"{time.time()-t0:.2f}s")


def _main_score(args):
    import time

    import numpy as np

    from .cli import plan_from_args

    plan = plan_from_args(
        args, byz_bound=args.n_byz,
        clip_radius=args.clip_radius if args.clip_radius > 0 else None,
    )
    # make_scoring_step jits internally (with canonicalized defaults);
    # wrapping it in another jit would only add a second trace cache
    scoring = make_scoring_step(plan)
    B, n, d = args.requests, args.clients, args.dim
    rng = np.random.RandomState(0)
    xs = rng.randn(B, n, d).astype(np.float32)
    # trailing n_byz clients of every request send 100x payloads
    if args.n_byz:
        xs[:, n - args.n_byz:, :] *= 100.0
    key = jax.random.PRNGKey(2)
    jax.block_until_ready(scoring(jnp.asarray(xs), key=key))  # compile
    t0 = time.time()
    # same arg structure as the warm-up call, or jit would retrace here
    out = jax.block_until_ready(scoring(jnp.asarray(xs), key=key))
    wall = time.time() - t0
    dist = np.asarray(out["distance"])
    flagged = (dist > np.median(dist, axis=1, keepdims=True) * 3.0).sum(1)
    print(f"[serve] scored {B} requests x {n} clients x d={d} "
          f"(rule={plan.aggregate.rule}) in {wall*1e3:.1f} ms "
          f"({wall/B*1e3:.2f} ms/request)")
    print(f"[serve] outliers flagged per request: {flagged.tolist()}")


def _main_stream(args):
    """The stream-mode server loop: synthetic open-loop byzantine
    clients mounting real registry attacks
    (``repro.scenarios.SyntheticCohort``), optional fault injection
    (``--fault-json``), per-round result emission (``--emit-rounds``),
    and crash-safe checkpoint/resume (``--ckpt-dir`` / ``--resume``).

    Determinism contract: the client stream is STATELESS — block b of n
    submissions is drawn from ``RandomState([seed, b])``, so any cursor
    position regenerates its row without replaying the stream — and
    every checkpoint stores (server state, submission cursor) at a pump
    boundary.  A run SIGKILLed at any instant and restarted with
    ``--resume`` therefore replays the lost submissions exactly and
    closes every round with an aggregate bitwise-identical to the
    uninterrupted run's."""
    import json as _json
    import os
    import time

    import numpy as np

    from repro.scenarios import SyntheticCohort
    from repro.serve import AggregationServer, FaultInjector, ServeConfig
    from repro.serve import recovery

    from .cli import fault_plan_from_args, plan_from_args, scenario_from_args

    n, d = args.clients, args.dim
    scenario = scenario_from_args(args)
    n_byz = (scenario.n_byz(n) if scenario.byz_frac is not None
             else args.n_byz)
    plan = plan_from_args(
        args, byz_bound=n_byz,
        clip_radius=args.clip_radius if args.clip_radius > 0 else None,
    )
    cfg = ServeConfig(
        n_slots=n, dim=d,
        cohort_size=args.cohort_size or None,
        deadline=args.deadline_ms / 1e3 if args.deadline_ms > 0 else None,
        stale_policy=args.stale_policy,
        stale_discount=args.stale_discount,
        duplicate_policy=args.duplicate_policy,
        min_fill=args.min_fill,
        seed=args.seed,
    )
    server = AggregationServer(plan, cfg)
    fault_plan = fault_plan_from_args(args)
    front = server
    if fault_plan is not None and fault_plan.active:
        front = FaultInjector(fault_plan, server)
        print(f"[serve] fault injection ON: {fault_plan.to_json()}")

    cohort = SyntheticCohort(
        scenario.build(), n_slots=n, dim=d, n_byz=n_byz,
        z_max=scenario.z_max,
    )
    cursor = 0  # total synthetic submissions so far (slot = cursor % n)
    extra_template = {"cursor": np.int64(0)}
    if args.ckpt_dir and args.resume:
        restored = recovery.restore_server(
            server, args.ckpt_dir, extra_template=extra_template
        )
        if restored is not None:
            step, extra = restored
            cursor = int(np.asarray(extra["cursor"]))
            print(f"[serve] resumed from checkpoint step {step} "
                  f"(round {server.round_id}, cursor {cursor})")
        else:
            print(f"[serve] --resume but no usable checkpoint in "
                  f"{args.ckpt_dir!r}; starting fresh")
    ckpt = None
    if args.ckpt_dir:
        ckpt = recovery.ServerCheckpointer(
            server, args.ckpt_dir, every=args.ckpt_every
        )

    emit = None
    if args.emit_rounds:
        emit = open(args.emit_rounds, "a")

    def emit_round(r):
        if emit is None:
            return
        emit.write(_json.dumps({
            "round_id": r.round_id,
            "close_reason": r.close_reason,
            "cohort_fill": r.cohort_fill,
            "degraded": r.degraded,
            "fallback_reason": r.fallback_reason,
            # bitwise-exact wire form for the kill-and-resume equality
            # check (float formatting would round)
            "aggregate_hex": np.asarray(r.aggregate, np.float32)
            .tobytes().hex(),
        }) + "\n")
        emit.flush()
        os.fsync(emit.fileno())

    block, block_rows = -1, None
    while server.metrics.rounds_closed < args.rounds:
        # synthetic open-loop clients: slots submit round-robin, the
        # trailing n_byz running the scenario's attack over this block's
        # honest rows; block b is a pure function of (seed, b), so resume
        # at any cursor regenerates the stream without replaying it
        b, slot = divmod(cursor, n)
        if b != block:
            block_rows = cohort.round_rows(
                np.random.RandomState([args.seed, b])
            )
            block = b
        front.submit(slot, block_rows[slot])
        cursor += 1
        closed = front.pump()
        for r in closed:
            emit_round(r)
        if ckpt is not None and closed:
            ckpt.observe(len(closed), extra={"cursor": np.int64(cursor)})
        if args.pump_sleep_ms > 0:
            time.sleep(args.pump_sleep_ms / 1e3)
    if emit is not None:
        emit.close()

    m = server.metrics.snapshot()
    print(f"[serve] streamed {m['rows_ingested']} rows -> "
          f"{m['rounds_closed']} rounds "
          f"({m['rounds_degraded']} degraded, rule={plan.aggregate.rule}, "
          f"attack={cohort.attack.name} x{n_byz}, "
          f"cohort_size={cfg.resolved_cohort_size}/{n})")
    for k, v in sorted(m.items()):
        print(f"[serve]   {k} = {v}")
    if isinstance(front, FaultInjector):
        for k, v in sorted(front.stats.snapshot().items()):
            print(f"[serve]   fault.{k} = {v}")


def main():
    import argparse

    from .cli import add_attack_args, add_fault_args, add_plan_args

    ap = argparse.ArgumentParser(description="serving driver")
    ap.add_argument("--mode", default="decode",
                    choices=["decode", "score", "stream"])
    # decode-mode flags
    ap.add_argument("--arch", default="minitron_8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=24)
    # scoring/stream-mode flags (+ the shared ServerPlan group)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--dim", type=int, default=4096)
    ap.add_argument("--n-byz", type=int, default=2)
    ap.add_argument("--clip-radius", type=float, default=0.0,
                    help="> 0: static server clip radius of the scoring "
                         "plan (ClipSpec(radius=...))")
    # stream-mode flags (repro.serve.ServeConfig)
    ap.add_argument("--rounds", type=int, default=4,
                    help="stream mode: rounds to run before exiting")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="stream mode: close a round after this many "
                         "distinct rows (0: wait for every client)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="stream mode: close a non-empty round after "
                         "this many ms (0: no deadline)")
    ap.add_argument("--stale-policy", default="drop",
                    choices=["drop", "defer"],
                    help="stream mode: what to do with rows of an "
                         "already-closed round")
    ap.add_argument("--stale-discount", type=float, default=0.5,
                    help="stream mode: defer policy weight per round of "
                         "staleness")
    ap.add_argument("--duplicate-policy", default="last_wins",
                    choices=["first_wins", "last_wins", "reject"],
                    help="stream mode: resolution when a slot resubmits "
                         "into the same round")
    ap.add_argument("--min-fill", type=int, default=1,
                    help="stream mode: deadline closes below this fill "
                         "use the clipping-only fallback aggregate "
                         "(degraded round)")
    ap.add_argument("--seed", type=int, default=0,
                    help="stream mode: seed of the synthetic client "
                         "stream and of the server's aggregator key")
    ap.add_argument("--ckpt-dir", default="",
                    help="stream mode: directory for crash-safe server "
                         "snapshots (empty: no checkpointing)")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="stream mode: snapshot once per this many "
                         "closed rounds")
    ap.add_argument("--resume", action="store_true",
                    help="stream mode: resume from the newest complete "
                         "checkpoint in --ckpt-dir (fresh start if none)")
    ap.add_argument("--emit-rounds", default="",
                    help="stream mode: append one JSON line per closed "
                         "round (bitwise aggregate hex) to this file")
    ap.add_argument("--pump-sleep-ms", type=float, default=0.0,
                    help="stream mode: sleep after each pump (testing "
                         "knob: widens the kill window for the "
                         "kill-and-resume test)")
    add_plan_args(ap, placement="naive")
    add_attack_args(ap, attack="gauss")  # stream mode's synthetic byz rows
    add_fault_args(ap)
    args = ap.parse_args()
    from .cache import enable_compile_cache

    enable_compile_cache()
    if args.mode == "score":
        _main_score(args)
    elif args.mode == "stream":
        _main_stream(args)
    else:
        _main_decode(args)


if __name__ == "__main__":
    main()
