"""The trace reduction, on a small trace recorded on a TPU v5e chip: three
steps of a jitted program holding one Pallas kernel (``coordinate_median``
over a (4, 131072) bf16 matrix) and two XLA fusions, under the harness's
host spans."""
from pathlib import Path

import pytest

import sys

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import tracing  # noqa: E402

TRACE = ROOT / "chipbench" / "testdata" / \
    "tiny_v5e.xplane.pb"
# the three coordinate_median ops of the recorded trace, in ns
KERNEL_NS = (73250, 73831, 73143)


@pytest.fixture(scope="module")
def events():
    return tracing.load(TRACE)


def test_load_keeps_device_ops_modules_and_host_spans(events):
    assert list(events["devices"]) == ["/device:TPU:0"]
    dev = events["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 3
    assert len(dev["ops"]) == 12
    kernels = [o for o in dev["ops"] if o[3]]
    assert [tracing.kernel_of(o[0]) for o in kernels] == \
        ["coordinate_median"] * 3
    assert [o[2] - o[1] for o in kernels] == list(KERNEL_NS)
    names = [s[0] for s in events["host"]]
    assert names.count("bench.step") == names.count("bench.block") == 3


def test_reduce_kernel_sums_busy_and_idle(events):
    red = tracing.reduce(events, kernels={"coordinate_median": "aggregation"})
    assert red["steps"] == 3 and red["devices"] == 1
    assert red["kernel_s"]["coordinate_median"] == pytest.approx(
        sum(KERNEL_NS) * 1e-9)
    assert red["layer_s"] == {"aggregation": red["kernel_s"][
        "coordinate_median"]}
    ops = events["devices"]["/device:TPU:0"]["ops"]
    busy_ns = tracing.length(tracing.union([[o[1], o[2]] for o in ops]))
    assert red["busy_s"] == pytest.approx(busy_ns * 1e-9)
    # the window spans the host's three steps (~7.4 ms); the device ran
    # ~0.24 ms of it
    assert 0.006 < red["window_s"] < 0.009
    assert 0.95 < 1 - red["busy_s"] / red["window_s"] < 1
    assert red["collective_s"] == 0 and red["collective_exposed_s"] == 0
    assert red["device_ops"][0][0] == "coordinate_median"
    # the gaps fall where the host was sleeping between steps
    assert red["idle_gaps"][0][0] == "bench.host"


def test_clock_offset_takes_least_lead(events):
    dev = events["devices"]["/device:TPU:0"]
    steps = [s for s in events["host"] if s[0] == "bench.step"]
    c = tracing.clock_offset(dev["modules"], steps)
    assert c == min(m[0] - s[1] for m, s in zip(dev["modules"], steps))
    # every module starts no earlier than its dispatch, on the host clock
    assert all(m[0] - c >= s[1] for m, s in zip(dev["modules"], steps))


def test_nested_ops_count_once():
    ops = [["while.1", 0, 100, False], ["fusion.1", 10, 40, False],
           ["kern.3", 50, 90, True]]
    times = {o[0]: (s, leaf) for o, s, leaf in tracing.self_times(ops)}
    assert times["while.1"] == (30, False)
    assert times["fusion.1"] == (30, True)
    assert times["kern.3"] == (40, True)


def test_collective_exposure():
    host = [["bench.step", 0, 5], ["bench.block", 5, 1000]]
    ops = [["fusion.1", 0, 400, False],
           ["all-to-all.2", 300, 600, False],  # 100 hidden, 200 exposed
           ["clip_aggregate.4", 600, 700, True],
           ["all-gather.5", 800, 900, False]]  # all exposed
    events = {"devices": {"/device:TPU:0": {"ops": ops,
                                            "modules": [[0, 1000]]}},
              "host": host}
    red = tracing.reduce(events, kernels={"clip_aggregate": "aggregation"})
    assert red["collective_s"] == pytest.approx(400e-9)
    assert red["collective_exposed_s"] == pytest.approx(300e-9)
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["layer_s"]["aggregation"] == pytest.approx(100e-9)


def test_unassigned_kernel_keeps_its_name():
    ops = [["mystery_kernel.1", 0, 50, True]]
    events = {"devices": {"d": {"ops": ops, "modules": [[0, 50]]}},
              "host": [["bench.step", 0, 1], ["bench.block", 1, 60]]}
    red = tracing.reduce(events, kernels={})
    assert red["layer_s"] == {"mystery_kernel": pytest.approx(50e-9)}
    assert red["device_ops"] == [["mystery_kernel", pytest.approx(50e-9)]]


def test_interval_helpers():
    assert tracing.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert tracing.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tracing.clip_to([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
