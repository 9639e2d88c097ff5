"""The trace reduction: unions, gaps, program and collective time, on a
hand-built trace and on a small trace recorded on the chip."""
import glob
import os

import pytest

from lib import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


class _E:
    def __init__(self, name, start_ns, dur_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, dur_ns


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _PD:
    def __init__(self, planes):
        self.planes = planes


def _fake():
    ms = 1_000_000
    host = _P("/host:CPU", [_L("python", [_E("bench.window", 10 * ms,
                                             100 * ms)])])
    ops = [_E("fusion.1", 0, 20 * ms),              # half before the window
           _E("fusion.2", 30 * ms, 10 * ms),
           _E("all-gather.3", 35 * ms, 10 * ms),    # overlaps fusion.2
           _E("fusion.4", 100 * ms, 20 * ms)]       # runs past the window
    mods = [_E("jit_a", 0, 20 * ms), _E("jit_b", 30 * ms, 15 * ms),
            _E("jit_a", 100 * ms, 20 * ms)]
    dev = _P("/device:TPU:0", [_L("XLA Ops", ops), _L("XLA Modules", mods)])
    other = _P("/device:TPU:1", [_L("XLA Ops", [_E("x", 0, 10 ** 9)])])
    return xtrace.reduce_profile(_PD([host, dev, other]), [0],
                                 window_host_t0=5.0)


def test_union_and_merge():
    assert xtrace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert xtrace.union_length([]) == 0
    assert xtrace.merge([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_busy_idle_programs_and_collectives():
    r = _fake()
    assert r.window == pytest.approx((0.010, 0.110))
    assert r.host_offset == pytest.approx(0.010 - 5.0)
    # busy inside the window: 10..20, 30..45, 100..110 ms
    assert r.busy_s == pytest.approx(0.035)
    assert r.window_s == pytest.approx(0.100)
    progs = r.program_time()
    assert progs["jit_a"] == pytest.approx((0.020, 2))
    assert progs["jit_b"] == pytest.approx((0.015, 1))
    assert r.executions() == 3
    assert r.op_time(xtrace.COLLECTIVE.search) == pytest.approx(0.010)
    assert [t for g in r.gaps() for t in g] == pytest.approx(
        [0.020, 0.030, 0.045, 0.100])


def test_breakdown_names_gaps_by_host_spans():
    r = _fake()
    # a host span on the perf_counter clock covering most of 45..100 ms
    spans = [("engine.commit", 5.0 + 0.040, 5.0 + 0.095),
             ("engine.serialize", 5.0 + 0.018, 5.0 + 0.031)]
    b = r.breakdown(spans)
    assert b["device_ops"][0][0] == "jit_a"
    assert b["idle_gaps"][0] == ["engine.commit", pytest.approx(0.055)]
    assert b["idle_gaps"][1] == ["engine.serialize", pytest.approx(0.010)]


def test_no_window_is_an_error():
    dev = _P("/device:TPU:0", [_L("XLA Ops", [_E("f", 0, 5)])])
    with pytest.raises(ValueError):
        xtrace.reduce_profile(_PD([dev]), [0], 0.0)


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    from jax.profiler import ProfileData
    r = xtrace.reduce_profile(ProfileData.from_file(path), [0], 0.0)
    assert 0 < r.busy_s <= r.window_s
    assert r.executions() >= 1
    b = r.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["device_ops"]) <= r.window_s * 1.0001
