"""What the robust step records about itself, read from a traced run: its
name scopes, its work counters, and the runtime's ``run_id`` events.

``tracing.reduce`` gives the benchmark's per-step device numbers from the
trace alone.  This module reads what the program adds beside them:

- ``load(xplane)`` keeps what ``tracing.load`` keeps, with each module's
  ``run_id``, the host events of every thread, and per ``run_id`` the
  runtime's ``DoEnqueueProgram`` and ``CompleteCallbacks`` events;
- ``reduce(events, paths=op_paths(compiled.as_text()))`` is
  ``tracing.reduce`` (every number of it unchanged) plus busy time per
  innermost name scope (``scope_s``, summing to busy time) and the part of
  it reverse-mode AD derived (``backward_s``), each module's round kind
  (``modules``), the ``run_id`` clock bracket (``clock``), and the idle
  gaps labelled ``in_step`` (inside a module), ``launch`` (after the next
  module's enqueue) or ``host:<event>`` (before it, named by the host
  event the host was in);
- ``with_counters(trainer, step_fn)`` starts a ``harness.Trainer``'s state
  on the program's ``TrainStats`` and ``counters(state)`` reads them;
- ``layer_metrics`` turns a reduction and the counters' change over a
  window into the per-layer numbers they give;
- ``program_digest`` hashes a compiled program without what only says
  where it came from.

Device and host clocks differ by an offset.  The window's edges keep
``tracing.clock_offset``, the least lead of a step's module over its
dispatch.  The gap labels take the middle of the ``run_id`` bracket
(``bracket``): a module starts no earlier than its enqueue and ends no
later than the runtime sees it complete.
"""
import base64
import gzip
import hashlib
import json
import re
import statistics

from . import tracing
from .cells import HERE

ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
UNSCOPED = "unscoped"
BACKWARD = "transpose("  # how JAX names what reverse-mode AD derives
ROUND_KINDS = {"round_full": "full", "round_diff": "diff"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$", re.M)
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_METADATA_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_PATH_SPLIT = re.compile(r"[/()]")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_FRAME_TABLE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)$")
_KERNEL_BODY = re.compile(r'"body":"([^"]*)"')


def scope_layers(root=HERE):
    """{name scope of the program: layer} from ``scopes.json``."""
    with open(root / "scopes.json") as f:
        return json.load(f)


def _profile(path):
    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path):
    """The events of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    devices, host, threads = {}, [], []
    enqueue, complete = {}, {}
    for plane in _profile(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [
                        [tracing.op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns,
                         "tpu_custom_call" in ev.name]
                        for ev in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [
                        [ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats).get("run_id")]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = [ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                    threads.append(span)
                    if ev.name.startswith("bench."):
                        host.append(span)
                    elif ev.name in (ENQUEUE, COMPLETE):
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is None:
                            continue
                        # the earliest enqueue and the latest completion of
                        # a run_id: the bracket stays sound on any device
                        if ev.name == ENQUEUE:
                            enqueue[run_id] = min(enqueue.get(run_id, span[1]),
                                                  span[1])
                        else:
                            complete[run_id] = max(
                                complete.get(run_id, span[1]), span[1])
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1]),
            "threads": sorted(threads, key=lambda s: s[1]),
            "enqueue": enqueue, "complete": complete}


def op_paths(hlo_text):
    """{instruction name: op_name} from a compiled program's text
    (``compiled.as_text()``): the name stack each op was traced under.

    An instruction the compiler made has no ``op_name`` (a copy, a
    prefetch, a layout change run as a loop of slices): it takes the path
    of the nearest instruction that uses its result, else of the nearest
    one it reads, else that of the instruction that calls its
    computation."""
    comp_names = set(_COMPUTATION.findall(hlo_text))
    paths, users, operands, caller, comp_of = {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rhs = m.group(1), m.group(2)
        comp_of[name] = comp
        meta = _METADATA_OP_NAME.search(rhs)
        if meta:
            paths[name] = meta.group(1)
        refs = _REF.findall(rhs.split(", metadata={", 1)[0])
        operands[name] = [r for r in refs if r not in comp_names]
        for r in operands[name]:
            users.setdefault(r, []).append(name)
        for r in refs:
            if r in comp_names:
                caller.setdefault(r, name)
    resolved = dict(paths)

    def nearest(start, edges):
        seen, queue = {start}, list(edges.get(start, ()))
        while queue:
            n = queue.pop(0)
            if n in seen or comp_of.get(n) != comp_of[start]:
                continue
            seen.add(n)
            if n in paths:
                return paths[n]
            queue += edges.get(n, ())
        return None

    def resolve(name, depth=0):
        if name in resolved:
            return resolved[name]
        path = nearest(name, users) or nearest(name, operands)
        if path is None and depth < 16 and comp_of[name] in caller:
            path = resolve(caller[comp_of[name]], depth + 1)
        resolved[name] = path
        return path

    for name in comp_of:
        resolve(name)
    return {k: v for k, v in resolved.items() if v is not None}


def load_op_paths(path):
    """``op_paths`` of a compiled program's text kept in a (gzipped)
    file."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return op_paths(f.read())


def scope_of(path, scopes):
    """(innermost scope of ``path`` among ``scopes`` or ``UNSCOPED``,
    every scope on it): the path's components, read through the names of
    transformations (``transpose(jvp(...))``), matched whole."""
    found = [t for t in _PATH_SPLIT.split(path or "") if t in scopes]
    return (found[-1] if found else UNSCOPED), set(found)


def bracket(modules, enqueue, complete):
    """(least, most) device time minus host time that the runtime's events
    allow: each module starts no earlier than the enqueue of its
    ``run_id`` and ends no later than the start of its completion
    callbacks.  None where no module carries a matched ``run_id``."""
    lo, hi = [], []
    for m in modules:
        rid = m[2] if len(m) > 2 else None
        if rid in enqueue:
            hi.append(m[0] - enqueue[rid])
        if rid in complete:
            lo.append(m[1] - complete[rid])
    if not lo or not hi:
        return None
    return max(lo), min(hi)


def innermost(threads, a, b):
    """The innermost host event (the shortest) covering most of [a, b),
    else the one that covers the largest part of it; None where no event
    overlaps it."""
    over = [(min(e[2], b) - max(e[1], a), e[2] - e[1], e[0])
            for e in threads if e[1] < b and e[2] > a]
    if not over:
        return None
    most = [o for o in over if 2 * o[0] > b - a]
    if most:
        return min(most, key=lambda o: o[1])[2]
    return max(over, key=lambda o: (o[0], -o[1]))[2]


def gap_label(gap, modules, events, offset):
    """``in_step``, ``launch`` or ``host:<event>`` for an idle gap (device
    clock), with ``offset`` device minus host time."""
    a, b = gap
    mid = (a + b) / 2
    if any(m[0] <= mid < m[1] for m in modules):
        return "in_step"
    enqueue, complete = events.get("enqueue", {}), events.get("complete", {})
    threads = events.get("threads", events["host"])
    nxt = min((m for m in modules if m[0] >= mid), default=None)
    prev = max((m for m in modules if m[1] <= mid), default=None,
               key=lambda m: m[1])
    lo, hi = a - offset, b - offset  # the gap on the host clock
    if nxt is not None and len(nxt) > 2 and nxt[2] in enqueue:
        e = enqueue[nxt[2]]
        if hi - max(lo, e) > min(hi, e) - lo:
            return "launch"
        hi = min(hi, e)
        if prev is not None and len(prev) > 2 and prev[2] in complete \
                and complete[prev[2]] < hi:
            lo = max(lo, complete[prev[2]])
    return f"host:{innermost(threads, lo, hi)}"


def scope_times(leaves, lo, hi, paths, scopes):
    """({scope: busy ns}, {scope: busy ns of ops AD derived}) over [lo,
    hi): each instant of the union of the leaf ops' intervals goes to the
    op that covers it first, so the times sum to the busy time."""
    times, backward = {}, {}
    covered = lo
    for o in sorted(leaves, key=lambda o: o[1]):
        a, b = max(o[1], covered, lo), min(o[2], hi)
        if b <= a:
            continue
        covered = b
        path = paths.get(o[0])
        scope, _ = scope_of(path, scopes)
        times[scope] = times.get(scope, 0) + b - a
        if path and BACKWARD in path:
            backward[scope] = backward.get(scope, 0) + b - a
    return times, backward


def module_kinds(modules, ops, lo, hi, paths):
    """[(round kind, duration ns)] of the modules that start in [lo, hi):
    ``full`` or ``diff`` where the module ran an op under ``round_full``
    or ``round_diff``, else ``other``."""
    starts = sorted((o[1], o[0]) for o in ops)
    out, j = [], 0
    for m in sorted(modules):
        if not lo <= m[0] < hi:
            continue
        while j < len(starts) and starts[j][0] < m[0]:
            j += 1
        kind = "other"
        k = j
        while k < len(starts) and starts[k][0] < m[1] and kind == "other":
            _, found = scope_of(paths.get(starts[k][1]), ROUND_KINDS)
            kind = ROUND_KINDS[found.pop()] if found else "other"
            k += 1
        out.append((kind, m[1] - m[0]))
    return out


def reduce(events, kernels=None, paths=None, scopes=None):
    """``tracing.reduce`` with the name scopes, round kinds, clock bracket
    and labelled idle gaps, averaged over the devices of the trace.

    ``paths`` ({instruction name: op_name}, ``op_paths``) gives each op
    its name scope, among ``scopes`` (``scopes.json``); an op it does not
    name is ``unscoped``."""
    out = tracing.reduce(events, kernels)
    scopes = scope_layers() if scopes is None else scopes
    paths = paths or {}
    steps = [s for s in events["host"] if s[0] == "bench.step"]
    blocks = [s for s in events["host"] if s[0] == "bench.block"]
    w0, w1 = steps[0][1], blocks[-1][2]
    n_dev = out["devices"]
    out.update(scope_s={}, backward_s={}, modules=[], clock=[])
    gaps = []
    for dev in events["devices"].values():
        c = tracing.clock_offset(dev["modules"], steps)
        lo, hi = w0 + c, w1 + c
        leaves = [o for o, _, leaf in tracing.self_times(
            [o for o in dev["ops"] if o[2] > o[1]]) if leaf]
        busy = tracing.union(tracing.clip_to([[o[1], o[2]] for o in leaves],
                                             lo, hi))
        times, backward = scope_times(leaves, lo, hi, paths, scopes)
        for into, part in ((out["scope_s"], times),
                           (out["backward_s"], backward)):
            for k, ns in part.items():
                into[k] = into.get(k, 0.0) + ns * 1e-9 / n_dev
        out["modules"] += [[k, ns * 1e-9] for k, ns in module_kinds(
            dev["modules"], dev["ops"], lo, hi, paths)]
        br = bracket(dev["modules"], events.get("enqueue", {}),
                     events.get("complete", {}))
        out["clock"].append({"least_lead_ns": c, "bracket_ns": br})
        label_offset = c if br is None else (br[0] + br[1]) / 2
        idle = sorted(tracing.subtract([[lo, hi]], busy),
                      key=lambda g: g[0] - g[1])
        for a, b in idle[:10]:
            gaps.append([gap_label([a, b], dev["modules"], events,
                                   label_offset), (b - a) * 1e-9])
    out["idle_gaps"] = sorted(gaps, key=lambda e: -e[1])[:10]
    return out


def with_counters(trainer, step_fn):
    """Start ``trainer``'s state (a ``harness.Trainer`` built with a
    ``wrap`` that kept its ``step_fn``) on the program's work counters
    from zero, and compile its step for that state."""
    import jax

    from repro.launch.train import init_train_stats

    with jax.set_mesh(trainer.mesh):
        trainer.state = trainer.state._replace(stats=init_train_stats())
        trainer.compiled = jax.jit(step_fn, donate_argnums=0).lower(
            trainer.state, trainer.feeds[0]).compile()


def counters(state):
    """{counter: count so far}, or None where the state counts nothing."""
    import jax

    stats = getattr(state, "stats", None)
    if stats is None:
        return None
    return {k: int(v) for k, v in
            zip(stats._fields, jax.device_get(tuple(stats)))}


def layer_metrics(reduced, counted=None, workers=None):
    """The per-layer numbers the name scopes and counters give, where they
    are there to read: device time per step under ``worker_grads``,
    ``attack`` and ``clip_norm`` (ms, per chip), the median duration of
    the difference-round modules (ms), and the share of the per-worker
    gradient evaluations Algorithm 1 needs (W per full round and two per
    sampled row of a difference round) among those ``counted`` over the
    window (%)."""
    out = {}
    scope_s = reduced.get("scope_s", {}) if reduced else {}
    for name, scope in (("fwd_bwd_ms.train", "worker_grads"),
                        ("attack_ms.train", "attack"),
                        ("clip_norm_ms.train", "clip_norm")):
        if scope in scope_s:
            out[name] = 1e3 * scope_s[scope] / reduced["steps"]
    diff = [s for kind, s in (reduced or {}).get("modules", [])
            if kind == "diff"]
    if diff:
        out["diff_round_ms.train"] = 1e3 * statistics.median(diff)
    if counted and counted["worker_evals"]:
        W, full = workers, counted["rounds_full"]
        needed = W * full + 2 * (counted["rows_sampled"] - W * full)
        out["useful_eval_share.train"] = \
            100.0 * needed / counted["worker_evals"]
    return out


def program_digest(hlo_text):
    """sha256 of a compiled program's text without what only says where it
    came from: the ``metadata={...}`` of each op, the stack-frame tables
    they index, and the source locations inside each Pallas kernel's
    serialized body (two checkouts at different paths, or with moved
    lines, compile the same program to the same digest)."""
    from jaxlib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return '"body":"' + hashlib.sha256(asm.encode()).hexdigest() + '"'

    text = "\n".join(l for l in hlo_text.splitlines()
                     if not _FRAME_TABLE.match(l))
    text = _KERNEL_BODY.sub(body, _METADATA.sub("", text))
    return hashlib.sha256(text.encode()).hexdigest()
