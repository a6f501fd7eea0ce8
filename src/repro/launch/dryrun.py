import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)
# ^ MUST run before any jax import: jax locks the device count on first init.
#   (REPRO_XLA_FLAGS lets the test-suite subprocess use a small device count.)

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) combination this lowers and
compiles the appropriate step function — train_step (Byz-VR-MARINA-PP),
prefill_step, or serve_step — against ShapeDtypeStruct inputs (no
allocation), prints ``compiled.memory_analysis()`` / ``cost_analysis()`` and
parses the collective traffic out of the optimized HLO.  Artifacts are
written as JSON for the roofline analysis (benchmarks.roofline).

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both
  python -m repro.launch.dryrun --smoke --mesh 2x2   # CPU test entry
"""
import argparse
import json
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import (
    AggregatorSpec,
    ClipSpec,
    CompressSpec,
    ScheduleSpec,
    ServerPlan,
)
from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.configs.shapes import SHAPES, decode_variant, input_specs, mode_for
from repro.launch.mesh import make_production_mesh, worker_axes
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import (
    ByzTrainConfig,
    abstract_state,
    make_train_step,
    resolve_plan,
    state_specs,
)
from repro.models.model import init_params, param_count
from repro.sharding.rules import batch_specs, cache_specs, needs_fsdp, param_specs

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(type_str: str) -> int:
    m = _TYPE_RE.search(type_str)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    nbytes = _DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_WHILE_RE = re.compile(r"while\(.*?condition=%([\w.\-]+), body=%([\w.\-]+)")
_CALL_RE = re.compile(r"(?:calls|to_apply|true_computation|false_computation)=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"= s32\[\] constant\((\d+)\)")
_OP_RE = re.compile(
    r"= (.*?) (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?[\w.\-]*\("
)


def parse_collectives(hlo_text: str) -> dict:
    """Per-chip collective traffic from optimized HLO, by op kind.

    Scan/while bodies execute trip-count many times but appear once in the
    text, so bytes are multiplied by loop trip counts: each ``while`` op
    names its condition computation, whose largest s32 constant is the trip
    count (the counter-compare pattern XLA emits for lax.scan).

    Byte conventions per op (documented in EXPERIMENTS.md):
      all-gather / all-to-all / collective-permute: result bytes
      all-reduce:      2 x result bytes (reduce + broadcast phases)
      reduce-scatter:  result bytes x group_size (streams the full operand)
    """
    # ---- pass 1: split into computations, gather per-computation facts
    comps: dict = {}
    cur = "__top__"
    comps[cur] = {"bytes": {k: 0 for k in _COLLECTIVES},
                  "counts": {k: 0 for k in _COLLECTIVES},
                  "whiles": [], "calls": [], "consts": []}
    for raw in hlo_text.splitlines():
        s = raw.strip()
        m = _COMP_RE.match(s)
        if m and not s.startswith("%!"):
            cur = m.group(1)
            comps[cur] = {"bytes": {k: 0 for k in _COLLECTIVES},
                          "counts": {k: 0 for k in _COLLECTIVES},
                          "whiles": [], "calls": [], "consts": []}
            continue
        c = comps[cur]
        for mm in _CONST_RE.finditer(s):
            c["consts"].append(int(mm.group(1)))
        for mm in _WHILE_RE.finditer(s):
            c["whiles"].append((mm.group(1), mm.group(2)))
        for mm in _CALL_RE.finditer(s):
            c["calls"].append(mm.group(1))
        for mm in _BRANCH_RE.finditer(s):
            for name in mm.group(1).split(","):
                c["calls"].append(name.strip().lstrip("%"))
        om = _OP_RE.search(s)
        if om:
            kind = om.group(2)
            rb = sum(
                _tensor_bytes(f"{dt}[{dims}]")
                for dt, dims in _TYPE_RE.findall(om.group(1))
            )
            if kind == "all-reduce":
                rb *= 2
            elif kind == "reduce-scatter":
                g = re.search(r"replica_groups=\{\{([\d,]+)\}", s)
                gs = len(g.group(1).split(",")) if g else 1
                rb *= gs
            c["bytes"][kind] += rb
            c["counts"][kind] += 1

    # ---- pass 2: walk the call graph from the entry with multipliers
    def trip_count(cond_name: str) -> int:
        cond = comps.get(cond_name)
        if not cond:
            return 1
        cands = [c for c in cond["consts"] if c > 1]
        return max(cands) if cands else 1

    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    seen_stack = set()

    def walk(name: str, mult: int):
        comp = comps.get(name)
        if comp is None or name in seen_stack:
            return
        seen_stack.add(name)
        for k in _COLLECTIVES:
            out[k] += comp["bytes"][k] * mult
            counts[k] += comp["counts"][k] * mult
        for cond, body in comp["whiles"]:
            walk(body, mult * trip_count(cond))
        for callee in comp["calls"]:
            walk(callee, mult)
        seen_stack.discard(name)

    # entry computation: the last one defined, by HLO convention, is ENTRY;
    # walk every computation not referenced anywhere as a fallback root set
    referenced = set()
    for c in comps.values():
        for cond, body in c["whiles"]:
            referenced.update((cond, body))
        referenced.update(c["calls"])
    roots = [n for n in comps if n not in referenced]
    for r in roots:
        walk(r, 1)
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def _memory_dict(ma) -> dict:
    return {
        k: int(getattr(ma, k))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }


def _cost_dict(ca) -> dict:
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    keep = {}
    for k, v in (ca or {}).items():
        if k in ("flops", "transcendentals", "bytes accessed") or k.startswith(
            "bytes accessed"
        ):
            keep[k] = float(v)
    return keep


def run_one(arch: str, shape_name: str, *, multi_pod: bool, smoke: bool = False,
            mesh=None, train_cfg: "ByzTrainConfig | None" = None,
            out_dir: str = "experiments/dryrun", verbose: bool = True,
            no_remat: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if no_remat:
        cfg = cfg.replace(remat=False)
    mode = mode_for(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name,
        "multi_pod": multi_pod, "mode": mode, "smoke": smoke,
    }
    if mode is None:
        result["skipped"] = "encoder-only architecture has no decode step"
        return result

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.ravel())
    result["mesh"] = "x".join(str(s) for s in mesh.devices.shape)
    result["n_chips"] = n_chips

    if train_cfg is None:
        fsdp = not smoke and needs_fsdp(cfg)
        shard_mode = "fsdp_tp" if fsdp else "tp"
        # FSDP-scale archs on the multi-pod mesh: one worker per pod, so
        # "data" stays free for FSDP and per-worker gradients fit HBM
        # (DESIGN.md "per-worker-gradient memory wall").
        wover = ("pod",) if (fsdp and multi_pod) else ()
        train_cfg = ByzTrainConfig(
            shard_mode=shard_mode, worker_axes_override=wover, n_byz=1
        )
    plan = resolve_plan(train_cfg)
    result["shard_mode"] = train_cfg.shard_mode
    result["agg_schedule"] = plan.schedule.placement
    result["params"] = param_count(cfg)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if mode == "train":
            state = abstract_state(cfg, train_cfg)
            sspecs = state_specs(mesh, cfg, state, train_cfg)
            step = make_train_step(cfg, mesh, train_cfg)
            specs = input_specs(cfg, shape)
            baxes = tuple(train_cfg.worker_axes_override) or worker_axes(mesh)
            if train_cfg.shard_mode == "zero3":
                baxes = baxes + ("model",)
            bspecs = batch_specs(mesh, specs, baxes)
            in_sh = (
                jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), sspecs,
                                       is_leaf=lambda x: isinstance(x, P)),
                jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspecs,
                                       is_leaf=lambda x: isinstance(x, P)),
            )
            lowered = jax.jit(step, in_shardings=in_sh).lower(state, specs)
        elif mode == "prefill":
            pstep = make_prefill_step(cfg)
            specs = input_specs(cfg, shape)
            pshapes = jax.eval_shape(partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
            pspec = param_specs(mesh, cfg, pshapes, mode=train_cfg.shard_mode)
            bspecs = batch_specs(mesh, specs, worker_axes(mesh))
            in_sh = tuple(
                jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), sp,
                                       is_leaf=lambda x: isinstance(x, P))
                for sp in (pspec, bspecs)
            )
            lowered = jax.jit(pstep, in_shardings=in_sh).lower(pshapes, specs)
        else:  # decode
            dcfg = decode_variant(cfg, shape)
            sstep = make_serve_step(dcfg)
            specs = input_specs(cfg, shape)
            pshapes = jax.eval_shape(partial(init_params, cfg=dcfg), jax.random.PRNGKey(0))
            pspec = param_specs(mesh, dcfg, pshapes, mode=train_cfg.shard_mode)
            bspecs = batch_specs(mesh, specs["batch"], worker_axes(mesh))
            cspecs = cache_specs(mesh, dcfg, specs["cache"])
            to_sh = lambda sp: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), sp,
                is_leaf=lambda x: isinstance(x, P),
            )
            lowered = jax.jit(
                sstep,
                in_shardings=(to_sh(pspec), to_sh(bspecs), to_sh(cspecs),
                              NamedSharding(mesh, P())),
            ).lower(pshapes, specs["batch"], specs["cache"], specs["cache_index"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    result.update(
        memory=_memory_dict(ma),
        cost=_cost_dict(ca),
        collectives=coll,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={result['mesh']} mode={mode} "
              f"shard={train_cfg.shard_mode} agg={plan.schedule.placement}")
        print(f"  memory_analysis: {ma}")
        print(f"  cost_analysis: flops={result['cost'].get('flops', 0):.3e} "
              f"bytes={result['cost'].get('bytes accessed', 0):.3e}")
        print(f"  collectives: {coll['bytes']} (total {coll['total_bytes']:.3e} B)")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "multipod" if multi_pod else "pod"
        if plan.schedule.placement != "sharded":
            suffix += f"_{plan.schedule.placement}"
        if train_cfg.shard_mode == "zero3":
            suffix += "_zero3"
        if plan.compress is not None and plan.compress.kind == "rand_fraction":
            suffix += f"_rk{plan.compress.frac}"
        if no_remat:
            suffix += "_noremat"
        if smoke:
            suffix += "_smoke"
        path = os.path.join(out_dir, f"{arch.replace('.', '')}_{shape_name}_{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        result["artifact"] = path
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="false", choices=["false", "true", "both"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="", help="override mesh, e.g. 2x2 (data x model)")
    ap.add_argument("--agg-schedule", default="sharded", choices=["sharded", "naive"])
    ap.add_argument("--shard-mode", default="",
                    choices=["", "tp", "fsdp_tp", "zero3"])
    ap.add_argument("--compress-frac", type=float, default=0.0)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"false": [False], "true": [True], "both": [False, True]}[args.multi_pod]

    mesh = None
    if args.mesh:
        dims = [int(x) for x in args.mesh.split("x")]
        from repro.launch.mesh import make_debug_mesh

        mesh = (
            make_debug_mesh(data=dims[0], model=dims[1])
            if len(dims) == 2
            else make_debug_mesh(pod=dims[0], data=dims[1], model=dims[2])
        )

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tc = None
                if args.shard_mode or args.agg_schedule != "sharded" or args.compress_frac:
                    cfg0 = get_smoke_config(arch) if args.smoke else get_config(arch)
                    sm = args.shard_mode or (
                        "fsdp_tp" if (not args.smoke and needs_fsdp(cfg0)) else "tp"
                    )
                    # Mirror resolve_plan()'s default, overriding only the
                    # placement / compress stages the flags control.
                    plan = ServerPlan(
                        aggregate=AggregatorSpec("cm", trim_ratio=0.25,
                                                 byz_bound=1),
                        clip=ClipSpec(alpha=2.0),
                        compress=(
                            CompressSpec(kind="rand_fraction",
                                         frac=args.compress_frac)
                            if args.compress_frac else None
                        ),
                        schedule=ScheduleSpec(placement=args.agg_schedule),
                    )
                    tc = ByzTrainConfig(shard_mode=sm, plan=plan, n_byz=1)
                try:
                    run_one(arch, shape, multi_pod=mp, smoke=args.smoke, mesh=mesh,
                            train_cfg=tc, out_dir=args.out_dir,
                            no_remat=args.no_remat)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((arch, shape, mp, repr(e)[:300]))
                    print(f"[dryrun] FAIL {arch} x {shape} mp={mp}: {e!r}"[:500])
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES")
        raise SystemExit(1)
    print("[dryrun] all combinations lowered and compiled OK")


if __name__ == "__main__":
    main()
