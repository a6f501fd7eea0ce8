"""What the program records about itself, read from a trace: the clock
bracket of the runtime's run_id events, idle gaps labelled by host event,
name scopes, round kinds and the per-layer readers of them
(``chipbench/scoped.py``, ``chipbench/metrics/``), on two small traces
recorded on a TPU v5e chip and on synthetic events.  The recorded traces
are each three steps under the harness's host spans: a jitted program
holding one Pallas kernel (``coordinate_median``) and two XLA fusions, and
a tiny scoped train step with its compiled text."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import scoped, tracing  # noqa: E402

TRACE = ROOT / "chipbench" / "testdata" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return scoped.load(TRACE)


def test_reduce_extends_tracing_reduce(events):
    """Every number of ``tracing.reduce`` is kept as it was; the idle gaps
    are the window's longest stretches with no op on the device, and fall
    where the host was sleeping between steps, after the runtime saw the
    module complete and before it enqueued the next."""
    kernels = {"coordinate_median": "aggregation"}
    old = tracing.reduce(events, kernels=kernels)
    red = scoped.reduce(events, kernels=kernels)
    for k, v in old.items():
        assert red[k] == v, k
    dev = events["devices"]["/device:TPU:0"]
    steps = [s for s in events["host"] if s[0] == "bench.step"]
    c = tracing.clock_offset(dev["modules"], steps)
    blocks = [s for s in events["host"] if s[0] == "bench.block"]
    lo, hi = steps[0][1] + c, blocks[-1][2] + c
    idle = tracing.subtract([[lo, hi]], tracing.union(tracing.clip_to(
        [[o[1], o[2]] for o in dev["ops"]], lo, hi)))
    assert [g[1] for g in red["idle_gaps"]] == sorted(
        ((b - a) * 1e-9 for a, b in idle), reverse=True)[:10]
    assert red["idle_gaps"][0][0] == "host:$time sleep"
    assert red["scope_s"] == {"unscoped": pytest.approx(red["busy_s"])}
    assert red["within_s"] == {}


def test_run_id_bracket_on_the_recorded_trace(events):
    """The runtime's enqueue and completion events, matched to each module
    by run_id, bound the device-minus-host offset; the least lead of a
    module over its dispatch span lies outside those bounds."""
    dev = events["devices"]["/device:TPU:0"]
    assert [m[2] for m in dev["modules"]] == [11, 12, 13]
    assert sorted(events["enqueue"]) == sorted(events["complete"]) == \
        [11, 12, 13]
    lo, hi = scoped.bracket(dev["modules"], events["enqueue"],
                             events["complete"])
    assert (round(lo / 1e6, 3), round(hi / 1e6, 3)) == (-1.826, -1.368)
    steps = [s for s in events["host"] if s[0] == "bench.step"]
    least_lead = tracing.clock_offset(dev["modules"], steps)
    assert round(least_lead / 1e6, 3) == -1.133
    assert not lo <= least_lead <= hi
    # the host events of every thread are kept, the runtime's among them
    names = {e[0] for e in events["threads"]}
    assert {"DoEnqueueProgram", "CompleteCallbacks", "bench.host",
            "PythonRefManager::CollectGarbage"} <= names


def _two_step_events(**extra):
    """Two modules on the device clock (offset 0): run 1 [100, 200) with
    an idle hole [140, 150) inside it, run 2 [400, 500).  The host saw run
    1 complete at 210, sat in ``bench.host`` / ``sleep`` until 300, and
    enqueued run 2 at 330."""
    ops = [["fusion.1", 100, 140, False], ["fusion.2", 150, 200, False],
           ["fusion.3", 400, 500, False]]
    host = [["bench.step", 90, 95], ["bench.block", 95, 205],
            ["bench.host", 205, 300], ["bench.step", 300, 340],
            ["bench.block", 340, 505]]
    threads = sorted(host + [["sleep", 215, 295],
                             ["DoEnqueueProgram", 330, 335],
                             ["CompleteCallbacks", 210, 212]],
                     key=lambda e: e[1])
    events = {"devices": {"d": {"ops": ops, "modules": [[100, 200, 1],
                                                         [400, 500, 2]]}},
              "host": host, "threads": threads, "enqueue": {1: 92, 2: 330},
              "complete": {1: 210, 2: 506}}
    events.update(extra)
    return events


def test_gap_labels_in_step_host_and_launch():
    events = _two_step_events()
    modules = events["devices"]["d"]["modules"]
    assert scoped.gap_label([140, 150], modules, events, 0) == "in_step"
    # [200, 400): 130 of it before run 2's enqueue, 70 after: the host;
    # from completion (210) to enqueue (330) ``sleep`` covers most
    assert scoped.gap_label([200, 400], modules, events, 0) == \
        "host:sleep"
    # the same gap with the enqueue early: the device waited on the launch
    early = _two_step_events(enqueue={1: 92, 2: 220})
    assert scoped.gap_label([200, 400], modules, early, 0) == "launch"
    # the labels read the host clock through the offset
    assert scoped.gap_label([1200, 1400], [[m[0] + 1000, m[1] + 1000, m[2]]
                                            for m in modules],
                             events, 1000) == "host:sleep"
    # the reduction labels by the middle of the run_id bracket [-6, 8];
    # the window (least lead 10) ends in the last bench.block
    red = scoped.reduce(events, kernels={})
    assert red["clock"] == [{"least_lead_ns": 10, "bracket_ns": (-6, 8)}]
    assert sorted(g[0] for g in red["idle_gaps"]) == \
        ["host:bench.block", "host:sleep", "in_step"]


def test_gap_label_without_run_ids_names_the_host_event():
    events = {"host": [["bench.step", 0, 5], ["bench.host", 60, 90]]}
    assert scoped.gap_label([60, 90], [[0, 50]], events, 0) == \
        "host:bench.host"
    assert scoped.gap_label([200, 300], [[0, 50]], events, 0) == \
        "host:None"


SCOPES = {"round_full": "step", "round_diff": "step",
          "worker_grads": "model", "attack": "attack",
          "clip_norm": "clip", "aggregate": "aggregation"}


def test_scope_attribution_and_round_kinds():
    """Each instant of busy time goes to one leaf op's innermost scope;
    the part under ``transpose(`` is the backward pass; a module's round
    kind is that of the round scope it ran."""
    paths = {
        "fusion.1": "jit(train_step)/worker_grads/vmap(jvp(while))/body/dot",
        "fusion.2": "jit(train_step)/worker_grads/transpose(jvp(while))/x",
        "fusion.3": "jit(train_step)/round_diff/worker_grads/vmap(mul)",
        "fusion.4": "jit(train_step)/round_diff/attack/convert",
        "fusion.5": "jit(train_step)/round_diff/aggregate/clip_norm/sqrt",
        "clip_aggregate.6": "jit(train_step)/round_diff/aggregate/pallas",
        "fusion.7": "jit(train_step)/round_full/attack/convert",
        "while.9": "jit(train_step)/worker_grads/while",
    }
    ops = [["while.9", 0, 30, False],  # holds fusion.1 and .2: no leaf
           ["fusion.1", 0, 10, False], ["fusion.2", 10, 30, False],
           ["fusion.3", 30, 40, False], ["fusion.4", 40, 45, False],
           ["fusion.5", 45, 47, False], ["clip_aggregate.6", 47, 60, True],
           ["copy.8", 55, 62, False],  # overlaps the kernel by 5
           ["fusion.1", 100, 110, False], ["fusion.7", 110, 120, False]]
    events = {"devices": {"d": {"ops": ops,
                                "modules": [[0, 62], [100, 120]]}},
              "host": [["bench.step", 0, 1], ["bench.block", 1, 130]]}
    red = scoped.reduce(events, kernels={"clip_aggregate": "aggregation"},
                        paths=paths, scopes=SCOPES)
    ns = {k: round(v * 1e9) for k, v in red["scope_s"].items()}
    assert ns == {"worker_grads": 50, "attack": 15, "clip_norm": 2,
                  "aggregate": 13, "unscoped": 2}
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"])
    # a scope takes the time of every op with it on its path: ``aggregate``
    # holds ``clip_norm``, the rounds hold what ran in them
    assert {k: round(v * 1e9) for k, v in red["within_s"].items()} == \
        {"worker_grads": 50, "attack": 15, "clip_norm": 2, "aggregate": 15,
         "round_diff": 30, "round_full": 10}
    assert {k: round(v * 1e9) for k, v in red["backward_s"].items()} == \
        {"worker_grads": 20}
    assert [(k, round(s * 1e9)) for k, s in red["modules"]] == \
        [("diff", 62), ("full", 20)]
    # without the program's op names every op is unscoped
    bare = scoped.reduce(events, kernels={}, scopes=SCOPES)
    assert bare["scope_s"] == {"unscoped": pytest.approx(red["busy_s"])}
    assert bare["within_s"] == {}
    assert [k for k, _ in bare["modules"]] == ["other", "other"]


HLO = """HloModule jit_step, is_scheduled=true

%body.3 (p.1: (u32[], f32[8])) -> (u32[], f32[8]) {
  %p.1 = (u32[], f32[8]{0}) parameter(0)
  %slice.4 = f32[4]{0} dynamic-slice(%p.1), dynamic_slice_sizes={4}
  ROOT %tuple.5 = (u32[], f32[8]{0}) tuple(%slice.4)
}

ENTRY %main.9 (x.1: f32[4]) -> f32[1,8] {
  %x.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%f, \
metadata={op_name="jit(step)/worker_grads/add" source_file="m.py"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}) copy-start(%fusion.1)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %while.6 = (u32[], f32[8]{0}) while(%copy-done.2), condition=%c, \
body=%body.3
  %clip_aggregate.7 = bf16[1,8]{1,0} custom-call(%while.6), \
custom_call_target="tpu_custom_call", metadata={op_type="pallas" \
op_name="jit(step)/aggregate/pallas_call"}
  ROOT %copy.8 = bf16[1,8]{1,0} copy(%clip_aggregate.7)
}
"""  # a backslash at a line's end joins it to the next


def test_op_paths_from_compiled_text():
    """Ops carry their op_name; an op the compiler made takes the path of
    the op that uses its result (the prefetch and the layout loop feed the
    kernel), else of the op it reads (the last copy), and an op of a
    called computation that of its caller."""
    paths = scoped.op_paths(HLO)
    agg = "jit(step)/aggregate/pallas_call"
    assert paths["fusion.1"] == "jit(step)/worker_grads/add"
    assert paths["clip_aggregate.7"] == agg
    for name in ("copy-start.2", "copy-done.2", "while.6", "slice.4",
                 "tuple.5"):
        assert paths[name] == agg, name
    assert paths["copy.8"] == agg
    assert paths["x.1"] == "jit(step)/worker_grads/add"
    assert scoped.scope_of("a/transpose(jvp(worker_grads))/b",
                           SCOPES) == ("worker_grads", {"worker_grads"})
    assert scoped.scope_of("a/worker_grads_extra/b", SCOPES)[0] == \
        "unscoped"


# a tiny scoped train step recorded on a v5e chip
# (chipbench/record_tiny_step.py): three window steps (difference, full,
# Byzantine-only difference) with the work counters on, and the compiled
# step's text
SCOPED = ROOT / "chipbench" / "testdata" / "tiny_step_v5e.xplane.pb.gz"
SCOPED_HLO = ROOT / "chipbench" / "testdata" / "tiny_step_v5e.hlo.txt.gz"


@pytest.fixture(scope="module")
def step():
    events = scoped.load(SCOPED)
    return events, scoped.reduce(events,
                                 paths=scoped.load_op_paths(SCOPED_HLO))


def test_a_nested_scope_leaves_within_s_as_it_was(step):
    """A scope a later program names inside ``worker_grads`` (here, on
    the rematerialized ops) takes their time from ``worker_grads`` in
    ``scope_s``, which still sums to busy time, and leaves
    ``within_s["worker_grads"]``, which the readers read, as it was."""
    events, red = step
    paths = {k: v.replace("/worker_grads/", "/worker_grads/ssm/")
             if "checkpoint" in v else v
             for k, v in scoped.load_op_paths(SCOPED_HLO).items()}
    scopes = dict(scoped.scope_layers(), ssm="models/ssm.py")
    nested = scoped.reduce(events, paths=paths, scopes=scopes)
    assert nested["within_s"]["worker_grads"] == \
        red["within_s"]["worker_grads"]
    assert 0 < nested["scope_s"]["ssm"] == nested["within_s"]["ssm"] \
        < red["scope_s"]["worker_grads"]
    assert nested["scope_s"]["worker_grads"] + nested["scope_s"]["ssm"] \
        == pytest.approx(red["scope_s"]["worker_grads"], rel=1e-12)
    assert sum(nested["scope_s"].values()) == pytest.approx(
        red["busy_s"], rel=1e-12)
    assert scoped.layer_metrics(nested) == scoped.layer_metrics(red)


def test_scoped_step_scopes_sum_to_busy_time(step):
    _, red = step
    assert red["steps"] == 3 and red["devices"] == 1
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"],
                                                         rel=1e-12)
    assert {"worker_grads", "attack", "clip_norm", "aggregate",
            "update"} <= set(red["scope_s"])
    assert red["scope_s"]["unscoped"] < 0.05 * red["busy_s"]
    assert 0 < red["backward_s"]["worker_grads"] \
        < red["scope_s"]["worker_grads"]
    assert [k for k, _ in red["modules"]] == ["diff", "full", "diff"]
    assert red["layer_s"]["aggregation"] < red["scope_s"]["aggregate"]


def test_scoped_step_gaps_are_labelled(step):
    events, red = step
    dev = events["devices"]["/device:TPU:0"]
    assert all(m[2] in events["enqueue"] and m[2] in events["complete"]
               for m in dev["modules"])
    lo, hi = red["clock"][0]["bracket_ns"]
    assert lo <= hi and not lo <= red["clock"][0]["least_lead_ns"] <= hi
    labels = {g[0].split(":")[0] for g in red["idle_gaps"]}
    assert labels == {"host", "launch", "in_step"}


# the five per-layer readers the benchmark had before the name scopes, on
# the recorded traces, as the accepted reduction computes them
READ_BEFORE = {
    "tiny_v5e": {
        "idle_share.train": 96.88375834892821,
        "mfu.train": 5350.035058101706,
        "agg_kernel_ms.train": 0.07340800000000001,
        "agg_roofline.train": 3543.4903176838657,
        "model_device_ms.train": 0.0031969999999999946,
    },
    "tiny_step_v5e": {
        "idle_share.train": 82.58263120111837,
        "mfu.train": 7453.652793044264,
        "agg_kernel_ms.train": 0.040587,
        "agg_roofline.train": 6408.961914912096,
        "model_device_ms.train": 0.2667366666666667,
    },
}


@pytest.mark.parametrize("trace,metric", [
    (t, m) for t in sorted(READ_BEFORE) for m in sorted(READ_BEFORE[t])])
def test_existing_readers_read_as_before(events, step, trace, metric):
    """Bit for bit, from ``tracing.reduce`` and from ``scoped.reduce``,
    with the program's op names as the harness gives them and without."""
    from chipbench import cells

    cell, config = cells.load_cell("mamba2_780m.full_w4_s2048")
    recorded = events if trace == "tiny_v5e" else step[0]
    paths = scoped.load_op_paths(SCOPED_HLO) if trace == "tiny_step_v5e" \
        else {}
    for reduced in (tracing.reduce(recorded), scoped.reduce(recorded),
                    scoped.reduce(recorded, paths=paths)):
        run = {"cell": cell, "config": config, "chips": 1,
               "device_kind": "TPU v5 lite", "trace": reduced}
        assert cells.read_metric(metric, run) == READ_BEFORE[trace][metric]


def test_layer_metrics_of_the_scoped_step(step):
    """Per step and chip, the scopes' device time and the median
    difference round; the useful share from counters of a window of 12
    full and 92 difference rounds with one row sampled in each (W = 4,
    every worker evaluated at both points)."""
    _, red = step
    got = scoped.layer_metrics(red)
    for name, scope in (("fwd_bwd_ms.train", "worker_grads"),
                        ("attack_ms.train", "attack"),
                        ("clip_norm_ms.train", "clip_norm")):
        assert got[name] == 1e3 * red["within_s"][scope] / 3
    diff = sorted(s for k, s in red["modules"] if k == "diff")
    assert got["diff_round_ms.train"] == 1e3 * (diff[0] + diff[1]) / 2
    assert "useful_eval_share.train" not in got
    counted = {"rounds_full": 12, "rows_sampled": 4 * 12 + 92,
               "rounds_byzantine_only": 23, "rows_clipped": 0,
               "worker_evals": 4 * 12 + 8 * 92}
    share = scoped.layer_metrics(red, counted, workers=4)
    assert share["useful_eval_share.train"] == 100.0 * 232 / 784
    assert scoped.layer_metrics(None) == {}


# the per-layer readers of the name scopes and counters on the recorded
# step, with the counters of its three window rounds (difference, full,
# Byzantine-only difference; W = 4, C = 1)
TINY_COUNTED = {"rounds_full": 1, "rows_sampled": 1 + 4 + 1,
                "rounds_byzantine_only": 1, "rows_clipped": 0,
                "worker_evals": 4 * (2 * 3 - 1)}
READ_SCOPED = {
    "fwd_bwd_ms.train": 0.23396800000000004,
    "attack_ms.train": 0.004729666666666667,
    "clip_norm_ms.train": 0.007412666666666668,
    "diff_round_ms.train": 0.3786195,
    "useful_eval_share.train": 100.0 * (4 + 2 * 2) / 20,
}


@pytest.mark.parametrize("metric", sorted(READ_SCOPED))
def test_scope_readers_on_the_scoped_step(step, metric):
    """Each reader gives ``layer_metrics``' number, as recorded; without
    the program's op names or counters it reads nothing."""
    from chipbench import cells

    cell, config = cells.load_cell("mamba2_780m.pp_w4c1_s512")
    events, red = step
    run = {"cell": cell, "config": config, "chips": 1,
           "device_kind": "TPU v5 lite", "trace": red,
           "counters": TINY_COUNTED}
    got = cells.read_metric(metric, run)
    assert got == READ_SCOPED[metric]
    assert got == scoped.layer_metrics(red, TINY_COUNTED, 4)[metric]
    bare = dict(run, trace=scoped.reduce(events), counters=None)
    assert cells.read_metric(metric, bare) is None
