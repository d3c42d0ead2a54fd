"""Trace reduction on a hand-built trace whose answers are known."""
import pytest

from bench import tracereduce as tr
from bench.tests.test_bench_flops import BWD_POST, MIDPOINT
from bench.tracereduce import Event, Line, Plane

MS = 1e6  # ns


def _ev(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


def _fixture():
    """Two chips and a host over a 100 ms window (two 50 ms steps).

    chip 0: fusion 0-30, alf kernel 30-40, all-reduce 35-60 (exposed
            40-60), fusion 70-95; idle 60-70 and 95-100.
    chip 1: fusion 0-50 and 50-90 (back to back), alf kernel 85-88
            inside the second; idle 90-100.
    host:   bench.step 0-50 and 50-100; bench.dispatch 58-68 and 0-5.
    """
    chip0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev("jit_train_step", 0, 100)]),
        Line("XLA Ops", [_ev("fusion.1", 0, 30),
                         _ev(MIDPOINT, 30, 10),
                         _ev("all-reduce.3", 35, 25),
                         _ev("fusion.2", 70, 25),
                         _ev("fusion.9", 120, 10)])])   # after the window
    chip1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [_ev("while.4", 0, 90), _ev("fusion.1", 0, 50),
                         _ev("fusion.2", 50, 40), _ev(BWD_POST, 85, 3)])])
    host = Plane("/host:CPU", [Line("python", [
        _ev("bench.step", 0, 50), _ev("bench.step", 50, 50),
        _ev("bench.dispatch", 0, 5), _ev("bench.dispatch", 58, 10),
        _ev("other", 0, 100)])])
    core = Plane("/device:TPU:0 SparseCore 0", [
        Line("XLA Ops", [_ev("fusion.7", 0, 100)])])
    return [chip0, chip1, host, core]


def test_merge_and_subtract():
    assert tr.merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert tr.total(tr.clip([(0, 10), (20, 30)], 5, 25)) == 10


def test_reduce_busy_idle_and_window():
    s = tr.reduce(_fixture())
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(0.1)
    # chip 0 busy 0-60 and 70-95 = 85 ms; chip 1 busy 0-90 = 90 ms
    assert s["busy_s"] == pytest.approx((0.085 + 0.090) / 2)


def test_reduce_ops_by_self_time():
    s = tr.reduce(_fixture())
    ops = s["ops"]
    assert ops["fusion.1"]["seconds"] == pytest.approx(0.040)  # 30 + 50 / 2
    assert ops["fusion.1"]["count"] == 1.0
    assert ops["fusion.2"]["seconds"] == pytest.approx((0.025 + 0.037) / 2)
    assert ops["while.4"]["seconds"] == pytest.approx(0.0)   # all children
    assert ops[MIDPOINT]["seconds"] == pytest.approx(0.005)
    assert "fusion.9" not in ops               # outside the window
    assert "fusion.7" not in ops               # not a chip's plane
    assert s["top_ops"][0] == ["fusion.1", pytest.approx(0.040)]
    names = dict(s["top_ops"])
    assert "branch_0_fun.57 custom-call (f32[131072,128], f32[131072,128], " \
        "f32[131072,128], f32[131072,128])" in names


def test_self_times_of_nested_events():
    evs = [_ev("while", 0, 10), _ev("a", 1, 2), _ev("b", 4, 3),
           _ev("c", 5, 1), _ev("d", 20, 5)]
    assert [round(t / MS, 9) for t in tr.self_times(evs)] == [5, 2, 2, 1, 5]


def test_reduce_exposed_collectives():
    s = tr.reduce(_fixture())
    assert s["collective_exposed_s"] == pytest.approx(0.020 / 2)


def test_reduce_idle_gaps_labelled_by_host_span():
    s = tr.reduce(_fixture())
    gaps = sorted((n, round(sec * 1e3, 6)) for n, sec in s["idle_gaps"])
    assert gaps == [("bench.dispatch", 10.0), ("bench.step", 5.0),
                    ("bench.step", 10.0)]
    assert s["idle_by_span"]["bench.dispatch"] == pytest.approx(0.005)


def test_reduce_without_chip_or_window_is_empty():
    host = _fixture()[2]
    assert tr.reduce([host]) == {}
    chip = _fixture()[0]
    assert tr.reduce([chip]) == {}
    assert tr.reduce([chip], window=(0, 100 * MS))["chips"] == 1
