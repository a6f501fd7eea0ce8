"""One run of one cell: set-up, the measured window, the check against
the reference, and the result line.

Set-up builds one object, the program's compiled step
(``repro.launch.train.make_train_step`` on ``ServerPlan.build(mesh)``)
with its state, and drives it from the seed through its first three
steps, which are also its warm-up; the window then drives that same
object step after step, each ended with ``block_until_ready`` before the
next is dispatched.  Once the window has closed and the device memory has
been read, the program's state is freed and the plain reference follows
the first two steps from the same weights, g^0 and batches.
"""
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import cells, compare, feed, tracing
from .reference import algorithm, model_module
from .reference.common import identity

COMPARED_STEPS = 2  # the reference follows two steps ...
FIRST_STEPS = COMPARED_STEPS + 1  # ... and the third update gives x^3


def log(msg):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def memory_in_use(devices):
    return {k: v for k, v in (devices[0].memory_stats() or {}).items()
            if k in ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
                     "peak_bytes_reserved")}


def process_age():
    """Seconds since this process started (Linux)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def import_program():
    src = cells.CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: no program (repro package) under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def host_load():
    """The process's CPU seconds, its thread count and, where the cgroup
    says, how often and how long the CPU quota throttled it (Linux)."""
    t = os.times()
    with open("/proc/self/status") as f:
        threads = next(int(l.split()[1]) for l in f
                       if l.startswith("Threads:"))
    load = {"cpu_s": t.user + t.system, "threads": threads}
    try:
        with open("/sys/fs/cgroup/cpu.stat") as f:
            stat = dict(l.split() for l in f)
        load["throttled"] = int(stat["nr_throttled"])
        load["throttled_s"] = int(stat["throttled_usec"]) / 1e6
    except (OSError, KeyError, ValueError):
        pass
    return load


def chips_or_exit(n):
    """The first ``n`` TPU devices; exits with no result when there are
    none or fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs[:n]


def _leaf_norm_fns():
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    norms = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(l.astype(f32))))
         for l in jax.tree_util.tree_leaves(t)]))
    diff_norms = jax.jit(lambda a, b: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(u.astype(f32) - v.astype(f32))))
         for u, v in zip(jax.tree_util.tree_leaves(a),
                         jax.tree_util.tree_leaves(b))]))
    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    finite = jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(l)) for l in jax.tree_util.tree_leaves(t)])))
    return norms, diff_norms, copy, finite


class Trainer:
    """The program's compiled step and its state, after the first steps.

    ``wrap`` plants a fault under the timed path (see ``faults``)."""

    def __init__(self, cell, config, seed, devices, wrap=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType

        from repro.api import AggregatorSpec, ClipSpec, ScheduleSpec, \
            ServerPlan
        from repro.configs.registry import get_config
        from repro.launch.train import ByzTrainConfig, MeshTrainState, \
            make_train_step
        from repro.models.model import init_params

        W = cell["workers"]
        self.mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                                  axis_types=(AxisType.Auto,) * 2,
                                  devices=devices)
        model_cfg = get_config(config["arch"]).replace(**config["model"])
        plan = ServerPlan(
            aggregate=AggregatorSpec(cell["rule"],
                                     trim_ratio=cell["trim_ratio"],
                                     byz_bound=cell["n_byz"]),
            clip=ClipSpec(alpha=cell["clip_alpha"]),
            schedule=ScheduleSpec(placement=cell["placement"],
                                  backend="pallas"),
        )
        tc = ByzTrainConfig.from_plan(
            plan, gamma=cell["gamma"], p=cell["p"], n_byz=cell["n_byz"],
            C=0 if cell["cohort"] == W else cell["cohort"],
            attack=cell["attack"], n_workers=W)
        step_fn = make_train_step(model_cfg, self.mesh, tc)
        if wrap is not None:
            step_fn = wrap(step_fn, W)
        norms, diff_norms, copy, self._finite = _leaf_norm_fns()
        with jax.set_mesh(self.mesh):
            x0 = feed.weights(seed, config)
            want = jax.eval_shape(lambda k: init_params(k, model_cfg),
                                  jax.random.PRNGKey(0))
            got = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), x0)
            if jax.tree_util.tree_structure(got) != \
                    jax.tree_util.tree_structure(want) or any(
                        (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                        zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want))):
                raise ValueError("the benchmark's weights do not match the "
                                 "program's parameter layout")
            g0 = feed.g0(seed, x0, cell)
            self.tokens = feed.batches(seed, cell, config["model"]["vocab"])
            n = self.tokens.shape[0]
            self.feeds = [{"tokens": t} for t in jax.jit(
                lambda t: tuple(t[i] for i in range(n)))(self.tokens)]
            state = MeshTrainState(
                params=x0, g=g0,
                key=jax.random.PRNGKey(cell["algorithm_key"]),
                step=jnp.int32(0))
            t0 = time.perf_counter()
            self.compiled = jax.jit(step_fn, donate_argnums=0).lower(
                state, self.feeds[0]).compile()
            self.memory = self.compiled.memory_analysis()
            log(f"step compiled or loaded in {time.perf_counter() - t0:.3f} s;"
                f" arguments {self.memory.argument_size_in_bytes} B, "
                f"temporaries {self.memory.temp_size_in_bytes} B, peak "
                f"{self.memory.peak_memory_in_bytes} B")
            self.moved = feed.moved_leaves(x0, cell)
            self.paths = feed.leaf_paths(x0)
            x0_moved = copy([l for l, m in zip(jax.tree_util.tree_leaves(x0),
                                               self.moved) if m])
            updates = []
            for k in range(FIRST_STEPS):
                prev = copy(state.g)
                state = self.compiled(state, self.feeds[k])
                updates.append({"diff": diff_norms(state.g, prev),
                                "full": norms(state.g)})
                del prev
            change = diff_norms(
                [l for l, m in zip(jax.tree_util.tree_leaves(state.params),
                                   self.moved) if m], x0_moved)
            del x0_moved
            self.numbers = {
                "update_norms": [{k: np.asarray(v, np.float64)
                                  for k, v in u.items()}
                                 for u in updates[:COMPARED_STEPS]],
                "change_norms": np.asarray(change, np.float64),
            }
        self.state = state
        self.k = FIRST_STEPS

    def window(self, seconds, trace_dir=None):
        """Steps until ``seconds`` have passed; returns the step times and
        the window's length, host clock."""
        import jax

        n = len(self.feeds)
        times = []
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        Ann = jax.profiler.TraceAnnotation
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with Ann("bench.step"):
                self.state = self.compiled(self.state, self.feeds[self.k % n])
            with Ann("bench.block"):
                jax.block_until_ready(self.state)
            t1 = time.perf_counter()
            with Ann("bench.host"):
                times.append(t1 - t0)
                self.k += 1
            if t1 - start >= seconds:
                break
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return times, t1 - start

    def finite(self):
        return bool(self._finite((self.state.params, self.state.g)))


def memory_peak_bytes(devices):
    """The fullest chip's peak: buffers in use plus the region the runtime
    reserves for the programs' temporaries, which ``peak_bytes_in_use``
    alone leaves out."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0))
    return peak


_GRADS = {}


def reference(cell, config, seed, tokens, *, control=False,
              batch_fault=False):
    """What the reference (or, with ``control``, the reference in the next
    precision below the configuration's) gives for the first steps."""
    import jax

    x0 = feed.weights(seed, config)
    g0 = feed.g0(seed, x0, cell)
    key = (config["family"], json.dumps(config["model"], sort_keys=True),
           control)
    if key not in _GRADS:
        model = model_module(config["family"])
        q = algorithm.fp8_round if control else identity
        m = config["model"]
        _GRADS[key] = jax.jit(jax.grad(lambda p, t: model.loss(p, t, m, q)))
    dtypes = jax.tree_util.tree_map(lambda l: l.dtype, x0)
    return algorithm.run(
        x0, g0, jax.random.PRNGKey(cell["algorithm_key"]),
        [tokens[k] for k in range(COMPARED_STEPS)], cell, _GRADS[key],
        dtypes, control=control, steps=COMPARED_STEPS,
        batch_fault=batch_fault)


def run_cell(cell, config, seed, seconds, trace, devices, *, bench,
             age_at_start, t_start, wrap=None):
    """One run; returns (result dict, check lines)."""
    import jax

    trainer = Trainer(cell, config, seed, devices, wrap=wrap)
    setup_s = age_at_start + (time.perf_counter() - t_start)
    log(f"set-up {setup_s:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    try:
        load0 = host_load()
        with jax.set_mesh(trainer.mesh):
            times, window_s = trainer.window(seconds, trace_dir)
            finite = trainer.finite()
        load1 = host_load()
        slow = sorted(range(len(times)), key=times.__getitem__)[-3:]
        throttled = (f"throttled {load1['throttled'] - load0['throttled']} "
                     f"times for {load1['throttled_s'] - load0['throttled_s']:.3f}"
                     f" s" if "throttled" in load1 else "no cgroup cpu.stat")
        log(f"host over the window: {load1['cpu_s'] - load0['cpu_s']:.2f} "
            f"CPU s, {load1['threads']} threads, {throttled}; slowest steps "
            + ", ".join(f"{i}: {times[i]:.4f} s" for i in slow))
        peak = memory_peak_bytes(devices)
        numbers, moved, paths = trainer.numbers, trainer.moved, \
            trainer.paths
        tokens = np.asarray(trainer.tokens[:COMPARED_STEPS])
        del trainer
        gc.collect()
        log(f"device memory after the window, program freed: "
            f"{memory_in_use(devices)}")
        reduced = None
        if trace:
            xplanes = sorted(p for p in _walk(trace_dir)
                             if p.endswith(".xplane.pb"))
            reduced = tracing.reduce(tracing.load(xplanes[-1]))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.perf_counter()
    ref = reference(cell, config, seed, tokens)
    log(f"reference: {time.perf_counter() - t0:.3f} s")
    values = compare.gaps(numbers, ref, moved)
    correct, checks = compare.verdict(values, cell["limits"])
    correct = correct and finite
    run = {
        "cell": cell, "config": config, "chips": len(devices),
        "device_kind": devices[0].device_kind, "setup_s": setup_s,
        "steps": len(times), "window_s": window_s, "step_times": times,
        "tokens_per_step": (cell["workers"] * cell["per_worker_batch"]
                            * cell["seq"]),
        "memory_peak_bytes": peak, "trace": reduced,
    }
    metrics = {}
    for name, unit in cells.metric_entries(bench, cell["name"], trace):
        value = cells.read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(times),
              "failed": 0 if finite else len(times), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = dict(checks, finite={"value": int(finite),
                                            "limit": 1})
    paths = np.asarray(paths)
    lines = [f"rounds compared: " + ", ".join(
        "full" if r["full"] else "difference" for r in ref["rounds"]),
        "leaves nought to rounding, left out: " + "; ".join(
            ", ".join(paths[~np.asarray(moved)][compare.left_out(
                np.asarray(r["update_norms"])[~np.asarray(moved)])])
            if not r["full"] else ", ".join(
                paths[compare.left_out(r["update_norms"])])
            for r in ref["rounds"]),
        f"clip: " + "; ".join(
            f"radius {r['radius']!r} message norms {r['message_norms']} "
            f"factors {r['clip_factors']}" for r in ref["rounds"]),
        f"step times: {len(times)} samples in {window_s!r} s"]
    lines += [f"{n} {c['value']!r} limit {c['limit']!r}"
              for n, c in result["checks"].items()]
    return result, lines


def _walk(top):
    for root, _, files in os.walk(top):
        for f in files:
            yield os.path.join(root, f)
