"""From a profiler trace to per-step device numbers.

``reduce`` takes, per TPU device, the ops of its "XLA Ops" line and its
"XLA Modules" line, and the harness's own host spans (``bench.step``
around the dispatch, ``bench.block`` around ``block_until_ready``,
``bench.host`` around the loop's bookkeeping), as ``scoped.load`` reads
them from a trace.  It turns them into busy time (the union of the
intervals of ops that contain no other op), time per kernel name (a Pallas
kernel's op is named by the kernel's ``name=``), collective time and the
part of it during which no other op runs, and the ops with the most self
time; ``scoped.reduce`` adds the name scopes and the idle gaps.  Device
and host clocks differ by an offset, taken as the least lead of a step's
module over its dispatch.
"""
import re

from .cells import kernel_layers

_SUFFIX = re.compile(r"\.\d+$")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


def op_name(text: str) -> str:
    """'%clip_aggregate.23 = bf16[...] custom-call(...)' -> 'clip_aggregate.23'"""
    return text.split(" = ", 1)[0].lstrip("%")


def kernel_of(name: str) -> str:
    return _SUFFIX.sub("", name)


def union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip_to(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, minus):
    """Parts of the (disjoint, sorted) ``intervals`` not covered by the
    (disjoint, sorted) ``minus``."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(minus) and minus[j][1] <= cur:
            j += 1
        k = j
        while k < len(minus) and minus[k][0] < b:
            if minus[k][0] > cur:
                out.append([cur, minus[k][0]])
            cur = max(cur, minus[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def self_times(ops):
    """[(op, self time, leaf?)] where an op's self time is its duration
    less that of the ops nested directly inside it; a leaf has no op nested
    inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0.0] * len(ops)
    leaf = [True] * len(ops)
    stack = []
    for i in order:
        while stack and ops[stack[-1]][2] <= ops[i][1]:
            stack.pop()
        if stack and ops[i][2] <= ops[stack[-1]][2]:
            child[stack[-1]] += ops[i][2] - ops[i][1]
            leaf[stack[-1]] = False
        stack.append(i)
    return [(ops[i], ops[i][2] - ops[i][1] - child[i], leaf[i])
            for i in range(len(ops))]


def clock_offset(modules, steps):
    """Device time minus host time.  The k-th step's module runs after the
    k-th dispatch span starts, so the offset is at most each module's lead
    over its dispatch; the least lead is taken."""
    leads = [m[0] - s[1] for m, s in zip(sorted(modules), steps)]
    return min(leads) if leads else 0


def reduce(events, kernels=None):
    """Per-step numbers averaged over the devices of the trace."""
    kernels = kernel_layers() if kernels is None else kernels
    steps = [s for s in events["host"] if s[0] == "bench.step"]
    blocks = [s for s in events["host"] if s[0] == "bench.block"]
    if not steps or not blocks:
        raise ValueError("the trace holds no bench.step / bench.block span")
    w0, w1 = steps[0][1], blocks[-1][2]
    n_dev = len(events["devices"])
    out = {"steps": len(steps), "window_s": (w1 - w0) * 1e-9,
           "devices": n_dev, "busy_s": 0.0, "kernel_s": {},
           "layer_s": {}, "collective_s": 0.0, "collective_exposed_s": 0.0}
    top = {}
    for dev in events["devices"].values():
        c = clock_offset(dev["modules"], steps)
        lo, hi = w0 + c, w1 + c
        timed = self_times([o for o in dev["ops"] if o[2] > o[1]])
        leaves = [(o, s) for o, s, leaf in timed if leaf]
        busy = union(clip_to([[o[1], o[2]] for o, _ in leaves], lo, hi))
        out["busy_s"] += length(busy) * 1e-9 / n_dev
        coll = union(clip_to([[o[1], o[2]] for o, _ in leaves
                              if o[0].startswith(COLLECTIVES)], lo, hi))
        rest = union(clip_to([[o[1], o[2]] for o, _ in leaves
                              if not o[0].startswith(COLLECTIVES)], lo, hi))
        out["collective_s"] += length(coll) * 1e-9 / n_dev
        out["collective_exposed_s"] += length(subtract(coll, rest)) \
            * 1e-9 / n_dev
        for o, s, _ in timed:
            if not lo <= o[1] < hi:
                continue
            name = kernel_of(o[0]) if o[3] else o[0]
            top[name] = top.get(name, 0.0) + s * 1e-9 / n_dev
            if o[3]:
                out["kernel_s"][name] = out["kernel_s"].get(name, 0.0) \
                    + s * 1e-9 / n_dev
    for k, s in out["kernel_s"].items():
        layer = kernels.get(k, k)
        out["layer_s"][layer] = out["layer_s"].get(layer, 0.0) + s
    out["device_ops"] = sorted(([n, s] for n, s in top.items()),
                               key=lambda e: -e[1])[:10]
    return out
