#!/usr/bin/env python3
"""Run the robust trainer's main path once on a TPU and check what comes out.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the four-chip placements, only

One chip: mamba2-780m at its published widths (d_model 1536, ssm_state
128, the whole 50,280-token vocabulary, bf16), cut to ``LAYERS`` layers,
trained by ``repro.launch.train.make_train_step`` with W = 4 workers on
the chip, one of them Byzantine (bit flip), under the trainer's default
plan (coordinate-wise median, clip at alpha = 2) on the Pallas kernels.
The step is compiled and warmed up (set-up time), then ``STEPS`` steps
run, each timed on the host clock around ``block_until_ready``.  Then:

- every loss and every parameter is finite;
- the compiled step contains ``tpu_custom_call`` (the kernels ran
  compiled, not interpreted);
- the plan's ServerStep on one step's message tree agrees between the
  Pallas and the jnp backend;
- a krum AggregationServer closes a few rounds through submit/pump with
  no executor fault and no degraded round, each equal to the one-shot
  jnp ServerStep on the same rows.

Four chips (``--chips 4``): a (data=4, model=1) mesh, one worker per chip;
the sharded and pipelined placements aggregate one step's message tree
and are compared with the naive placement.

The script needs a TPU: on any other platform it exits non-zero and
prints no result.  Its last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "mamba2-780m"
LAYERS = 4  # depth cut; widths and vocabulary are the published ones
SEQ = 2048
PER_WORKER_BATCH = 2
WORKERS = 4
N_BYZ = 1
GAMMA = 0.01
STEPS = 5
SERVE_DIM = 1 << 22  # message coordinates per server row (f32)
SERVE_SLOTS = 16
SERVE_BYZ = 4
SERVE_ROUNDS = 3
# A bf16 result may differ by one bf16 ulp (at most 2^-7 of the value)
# between two programs that compute it from the same rows: the f32
# clip-norm sums are reduced in a different order by the two backends /
# placements, and a last-bit change of a clip factor can move the f32
# median across a bf16 rounding boundary.  The floor covers results that
# are tiny next to the leaf's largest entry (the median of values of both
# signs cancels).
BF16_RTOL = 2.0 ** -7
CANCEL_FLOOR = 2.0 ** -16


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def _import_repo():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py: no repro package under {src}")
    sys.path.insert(0, str(src))


def _tpu_devices():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found {devs[0].platform!r}"
        )
    return devs


def cm_plan(placement="naive", blocks="sequential", backend="pallas"):
    """The trainer's default composition (``resolve_plan``) on the given
    schedule."""
    from repro.api import AggregatorSpec, ClipSpec, ScheduleSpec, ServerPlan

    return ServerPlan(
        aggregate=AggregatorSpec("cm", trim_ratio=0.25, byz_bound=N_BYZ),
        clip=ClipSpec(alpha=2.0),
        schedule=ScheduleSpec(placement=placement, blocks=blocks,
                              backend=backend),
    )


def model_config(layers):
    from repro.configs.registry import get_config

    return get_config(ARCH).replace(n_layers=layers)


def _tree_all_finite(tree):
    import jax
    import jax.numpy as jnp

    return bool(jax.jit(lambda t: jnp.all(jnp.stack([
        jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree_util.tree_leaves(t)
    ])))(tree))


def _assert_close(name, got, want):
    """Leafwise |got - want| <= BF16_RTOL * |want| + CANCEL_FLOOR * the
    leaf's largest |want|; returns the largest absolute difference."""
    import jax
    import numpy as np

    worst = 0.0
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0],
        jax.tree_util.tree_leaves(want),
    ):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        scale = float(np.max(np.abs(w))) or 1.0
        np.testing.assert_allclose(
            g, w, rtol=BF16_RTOL, atol=CANCEL_FLOOR * scale,
            err_msg=f"{name} {jax.tree_util.keystr(path)}",
        )
        worst = max(worst, float(np.max(np.abs(g - w))))
    return worst


def _message_fn(cfg):
    """(params, worker batches) -> worker-stacked gradients with the last
    N_BYZ rows bit-flipped: the message tree of one full round."""
    import jax

    from repro.models.model import apply_train

    def msgs(params, wbatch):
        grads = jax.vmap(
            lambda b: jax.grad(lambda p: apply_train(p, cfg, b)[0])(params)
        )(wbatch)
        return jax.tree_util.tree_map(
            lambda g: g.at[g.shape[0] - N_BYZ:].multiply(-1), grads
        )

    return msgs


def _worker_batches(batch, w):
    import jax

    return jax.tree_util.tree_map(
        lambda l: l.reshape((w, l.shape[0] // w) + l.shape[1:]), batch
    )


def run_train(*, layers=LAYERS, seq=SEQ, batch=PER_WORKER_BATCH,
              steps=STEPS, cfg=None):
    """Build, warm up and time the train step on one device, and check
    what it produced.  Returns what ``check_backends`` needs."""
    import jax
    import jax.numpy as jnp

    from repro.core.tree_utils import tree_norm
    from repro.data.pipeline import make_batch_iterator
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import (ByzTrainConfig, MeshTrainState,
                                    make_train_step)
    from repro.models.model import apply_train, init_params

    cfg = cfg or model_config(layers)
    mesh = make_local_mesh()
    plan = cm_plan()
    tc = ByzTrainConfig.from_plan(plan, gamma=GAMMA, n_byz=N_BYZ,
                                  attack="bf", n_workers=WORKERS)
    log(f"model {cfg.name}: L={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} {cfg.dtype}; W={WORKERS} ({N_BYZ} byzantine) "
        f"seq={seq} batch={batch}/worker; mesh {dict(mesh.shape)}")

    t0 = time.perf_counter()
    it = make_batch_iterator(cfg, WORKERS * batch, seq)
    batches = [next(it) for _ in range(steps + 1)]
    jax.block_until_ready(batches)
    msgs_fn = jax.jit(_message_fn(cfg))
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
        # g^0: the aggregated first-round gradients (plain mean here)
        g0 = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda l: jnp.mean(l.astype(jnp.float32), 0).astype(l.dtype), m
        ))(msgs_fn(params, _worker_batches(batches[0], WORKERS)))
        state = MeshTrainState(params=params, g=g0,
                               key=jax.random.PRNGKey(1), step=jnp.int32(0))
        jax.block_until_ready(state)
        t_init = time.perf_counter() - t0

        t0 = time.perf_counter()
        step = jax.jit(make_train_step(cfg, mesh, tc), donate_argnums=0)
        compiled = step.lower(state, batches[0]).compile()
        t_compile = time.perf_counter() - t0
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo, "no Pallas kernel in the step"
        mem = compiled.memory_analysis()
        log(f"set-up: data+init {t_init:.3f} s, step compile "
            f"{t_compile:.3f} s; step memory: args "
            f"{mem.argument_size_in_bytes} B, temp {mem.temp_size_in_bytes} B")

        eval_loss = jax.jit(lambda p, b: apply_train(p, cfg, b)[0])
        t0 = time.perf_counter()
        state = compiled(state, batches[0])
        jax.block_until_ready(state)
        log(f"warm-up step {time.perf_counter() - t0:.6f} s, loss "
            f"{float(eval_loss(state.params, batches[0]))!r}")

        losses = []
        for k in range(1, steps + 1):
            t0 = time.perf_counter()
            state = compiled(state, batches[k])
            jax.block_until_ready(state)
            dt = time.perf_counter() - t0
            loss = float(eval_loss(state.params, batches[0]))
            losses.append(loss)
            tokens = WORKERS * batch * seq
            log(f"step {k}: {dt:.6f} s ({tokens} tokens), loss {loss!r}, "
                f"|g| {float(tree_norm(state.g))!r}")
        stats = jax.devices()[0].memory_stats() or {}
        log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
        assert all(jnp.isfinite(jnp.asarray(losses))), losses
        assert _tree_all_finite(state.params), "non-finite parameter"
        assert _tree_all_finite(state.g), "non-finite g"
        log("losses and parameters finite; step HLO has tpu_custom_call")
    return state, mesh, cfg, batches[0], msgs_fn


def check_backends(state, mesh, cfg, batch0, msgs_fn):
    """Phase 5: the plan's ServerStep on one message tree, pallas vs jnp.

    The jnp backend runs on the f32 form of the bf16 messages: on bf16
    rows it rounds the clipped rows and the two middle values' sum to
    bf16 before the final cast, while the kernel computes in f32 and
    rounds once, so the f32 jnp step is the reference the kernel meets
    to one bf16 ulp."""
    import jax
    import jax.numpy as jnp

    from repro.core.tree_utils import tree_norm

    with jax.set_mesh(mesh):
        msgs = msgs_fn(state.params, _worker_batches(batch0, WORKERS))
        norms = jax.jit(jax.vmap(tree_norm))(msgs)
        # clip about half the rows: the radius is their median norm
        radius = jnp.median(norms)
        mask = jnp.ones((WORKERS,), bool)
        key = jax.random.PRNGKey(2)
        msgs32 = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32),
                                        msgs)
        outs = {}
        for backend, m in (("pallas", msgs), ("jnp", msgs32)):
            server = cm_plan(backend=backend).build(mesh, n_workers=WORKERS)
            run = jax.jit(lambda m, r, s=server: s(m, mask=mask, key=key,
                                                  radius=r))
            outs[backend] = jax.block_until_ready(run(m, radius))
    worst = _assert_close("pallas vs jnp", outs["pallas"], outs["jnp"])
    log(f"ServerStep pallas (bf16) vs jnp (f32) on one message tree "
        f"(radius {float(radius)!r}, row norms "
        f"{[float(n) for n in norms]}): max |diff| {worst!r}, within one "
        "bf16 ulp")


def check_server(*, dim=SERVE_DIM, slots=SERVE_SLOTS, rounds=SERVE_ROUNDS):
    """Phase 6: a krum AggregationServer with a static radius."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import AggregatorSpec, ClipSpec, ScheduleSpec, ServerPlan
    from repro.serve import AggregationServer, ServeConfig

    def plan(backend):
        return ServerPlan(
            aggregate=AggregatorSpec("krum", byz_bound=SERVE_BYZ),
            clip=ClipSpec(radius=1.5 * np.sqrt(dim)),
            schedule=ScheduleSpec(placement="naive", backend=backend),
        )

    server = AggregationServer(plan("pallas"),
                               ServeConfig(n_slots=slots, dim=dim))
    reference = plan("jnp").build()
    rng = np.random.default_rng(0)
    center = rng.standard_normal(dim, dtype=np.float32)
    # one honest row lies much nearer the others than the rest do, so the
    # Krum winner is decided by a wide margin and both backends must pick it
    spread = np.full((slots, 1), 0.1, np.float32)
    for r in range(rounds):
        spread[:] = 0.1
        spread[r % (slots - SERVE_BYZ)] = 0.02
        rows = center + spread * rng.standard_normal((slots, dim),
                                                     dtype=np.float32)
        rows[slots - SERVE_BYZ:] *= -10.0  # byzantine: flipped, scaled
        t0 = time.perf_counter()
        tickets = [server.submit(i, rows[i]) for i in range(slots)]
        results = server.pump()
        dt = time.perf_counter() - t0
        assert len(results) == 1 and all(t.done for t in tickets), results
        res = results[0]
        assert not res.degraded, res.fallback_reason
        key = jax.random.fold_in(jax.random.PRNGKey(0), res.round_id)
        want = np.asarray(reference(jnp.asarray(rows), key=key))
        np.testing.assert_allclose(res.aggregate, want, rtol=1e-6,
                                   atol=1e-6)
        # ... and both picked the planted winner, unclipped
        np.testing.assert_array_equal(res.aggregate,
                                      rows[r % (slots - SERVE_BYZ)])
        log(f"server round {res.round_id}: {slots} rows of {dim}, submit "
            f"to close {dt:.6f} s, equal to the one-shot jnp step and to "
            "the planted winner")
    m = server.metrics
    assert m.executor_faults == 0 and m.rounds_degraded == 0, m.snapshot()
    log(f"server: {m.rounds_closed} rounds, executor_faults "
        f"{m.executor_faults}, rounds_degraded {m.rounds_degraded}")


def run_four_chips(*, layers=LAYERS, seq=SEQ, batch=PER_WORKER_BATCH,
                   cfg=None, repeats=3):
    """The sharded and pipelined placements against the naive one on a
    (data=4, model=1) mesh, one worker per chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.tree_utils import tree_norm
    from repro.data.pipeline import make_batch_iterator
    from repro.launch.mesh import make_local_mesh, num_workers
    from repro.models.model import init_params

    cfg = cfg or model_config(layers)
    mesh = make_local_mesh()
    w = num_workers(mesh)
    assert w == WORKERS, f"the four-chip phase needs {WORKERS} devices"
    log(f"model {cfg.name}: L={cfg.n_layers}, W={w} on mesh "
        f"{dict(mesh.shape)}, seq={seq} batch={batch}/worker")
    rows = NamedSharding(mesh, P("data"))
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
        batch0 = next(make_batch_iterator(cfg, w * batch, seq))
        wbatch = jax.device_put(_worker_batches(batch0, w), rows)
        msgs = jax.jit(_message_fn(cfg), out_shardings=rows)(params, wbatch)
        norms = jax.jit(jax.vmap(tree_norm))(msgs)
        radius = jnp.median(norms)
        mask = jnp.ones((w,), bool)
        key = jax.random.PRNGKey(2)
        outs = {}
        for placement, blocks in (("naive", "sequential"),
                                  ("sharded", "sequential"),
                                  ("sharded", "pipelined")):
            name = f"{placement}/{blocks}"
            server = cm_plan(placement, blocks).build(mesh)
            run = jax.jit(lambda m, r, s=server: s(m, mask=mask, key=key,
                                                  radius=r))
            t0 = time.perf_counter()
            compiled = run.lower(msgs, radius).compile()
            t_compile = time.perf_counter() - t0
            assert "tpu_custom_call" in compiled.as_text(), name
            outs[name] = jax.block_until_ready(compiled(msgs, radius))
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(msgs, radius))
                times.append(time.perf_counter() - t0)
            log(f"{name}: compile {t_compile:.3f} s, server step "
                f"{[round(t, 6) for t in times]} s")
    for name in ("sharded/sequential", "sharded/pipelined"):
        same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
            jax.tree_util.tree_leaves(outs[name]),
            jax.tree_util.tree_leaves(outs["naive/sequential"])))
        worst = _assert_close(name, outs[name], outs["naive/sequential"])
        log(f"{name} vs naive/sequential: bitwise equal {same}, max "
            f"|diff| {worst!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip placement phase")
    args = ap.parse_args(argv)
    _import_repo()
    devs = _tpu_devices()

    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            train = run_train()
            check_backends(*train)
            del train
            check_server()
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
