"""The trace reduction: busy union, top device operations, idle gaps
labelled by the harness's host spans.  Exact arithmetic on a synthetic
trace, and the reduction of a small trace recorded on a TPU v5e
(bench/tests/record_trace.py)."""
import dataclasses
import pathlib
from typing import List

import pytest

from bench import xplane

DATA = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"
MS = 1_000_000


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Profile:
    planes: List[Plane]


def test_reduction_on_a_synthetic_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.step", -5 * MS, 4 * MS),     # the first step: dropped
        Ev("bench.step", 0, 9 * MS), Ev("bench.traffic", 9 * MS, 3 * MS),
        Ev("bench.step", 12 * MS, 8 * MS), Ev("other", 0, 30 * MS)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(123)", -2 * MS, 32 * MS)]),
        Line("XLA Ops", [
            Ev("while.1", -1 * MS, 6 * MS),        # clipped to 0-5 ms
            Ev("fusion.1", -MS // 2, 5 * MS // 2),  # in the loop, to 2 ms
            Ev("fusion.2", 2 * MS, 2 * MS),        # in the loop
            Ev("dot", 6 * MS, 2 * MS),
            Ev("fusion.1", 13 * MS, 5 * MS),
            Ev("dot", 25 * MS, 5 * MS)])])         # after the last step
    out = xplane.reduce(Profile([host, dev]))
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.005 + 0.002 + 0.005)
    # self time: the loop keeps what its body does not cover
    assert out["device_ops"] == [
        ["jit_step/fusion.1", pytest.approx(0.007)],
        ["jit_step/fusion.2", pytest.approx(0.002)],
        ["jit_step/dot", pytest.approx(0.002)],
        ["jit_step/while.1", pytest.approx(0.001)]]
    # gaps 5-6 ms (in a step), 8-13 ms (mostly the harness's own
    # bookkeeping) and 18-20 ms (in a step), longest first
    assert [[n, round(s, 9)] for n, s in out["idle_gaps"]] == [
        ["bench.traffic", 0.005], ["bench.step", 0.002],
        ["bench.step", 0.001]]
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_op_label_reads_the_hlo_text():
    name = ("%sort.5 = (f32[4,512]{1,0:T(4,128)S(1)}, s32[4,512]{1,0}) "
            "sort(f32[4,512]{1,0} %fusion.17), dimensions={1}")
    assert xplane.op_label(name, "jit_f") == "jit_f/sort.5 sort f32[4,512]"
    name = ("%fusion.197 = bf16[33,16,2,16]{3,2,1,0:T(2,128)(2,1)S(1)} "
            "fusion(bf16[33,16,2,16]{3,2,1,0} %bitcast.194), kind=kCustom")
    assert xplane.op_label(name, "jit__lambda") \
        == "jit__lambda/fusion.197 fusion bf16[33,16,2,16]"


def test_a_trace_without_steps_reduces_to_nothing():
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [Ev("dot", 0, MS)])])
    out = xplane.reduce(Profile([dev]))
    assert out == {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                   "idle_gaps": []}
    host = Plane("/host:CPU", [Line("python", [Ev("bench.step", 0, MS)])])
    out = xplane.reduce(Profile([host, dev]))
    assert out == {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                   "idle_gaps": []}


def test_reduction_of_a_trace_recorded_on_the_chip():
    """The toy dense cell, 0.2 s of window on one v5e: the host drives a
    toy model, so the chip is idle most of the time."""
    out = xplane.reduce_file(DATA)
    assert out["busy_s"] == pytest.approx(0.001670171, rel=1e-6)
    assert out["window_s"] == pytest.approx(0.217730871, rel=1e-6)
    assert out["device_ops"][0][0] == \
        "jit__unknown/_unknown_.1 custom-call f32[4,8,128]"
    assert 0 < len(out["device_ops"]) <= xplane.TOP
    assert all(s > 0 for _, s in out["device_ops"])
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"] * 1.000001
    assert out["idle_gaps"], "a host-driven engine leaves the chip idle"
    assert all(n.startswith("bench.") or n == "host"
               for n, _ in out["idle_gaps"])
    assert sum(s for _, s in out["idle_gaps"]) <= \
        out["window_s"] - out["busy_s"] + 1e-9
