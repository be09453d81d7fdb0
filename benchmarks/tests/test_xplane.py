"""The reduction from trace to metrics: on a trace recorded on the chip
(``nexmark_q5.sat``, a 1 s window on a TPU v5 lite, PR 25) and on planes
made by hand."""
import os

import pytest

from benchmarks.harness import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "nexmark_q5_sat_1s.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(xplane.read_planes(TRACE))


def test_recorded_window_is_cut_by_the_marks(recorded):
    assert recorded["marks_found"]
    assert xplane.reduce_window(xplane.read_planes(TRACE)) == recorded
    assert recorded["window_s"] == pytest.approx(0.977, abs=0.005)
    assert recorded["n_devices"] == 1


def test_recorded_busy_and_idle(recorded):
    # 229 launches of some 36 us each in the window
    assert recorded["busy_s"] == pytest.approx(0.00828, rel=0.01)
    assert recorded["idle_share"] == pytest.approx(0.9915, abs=0.0005)
    assert 0 < recorded["busy_s"] < recorded["window_s"]


def test_recorded_op_names_are_short_and_ranked(recorded):
    ops = recorded["device_ops"]
    assert ops[0][0] == "jit_run:fusion"
    assert len(ops) <= 10
    assert all(" " not in n and "%" not in n for n, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) <= recorded["busy_s"] * 1.0001


def test_recorded_gaps_say_what_the_host_was_doing(recorded):
    gaps = recorded["idle_gaps"]
    assert len(gaps) == 10
    assert all(n.startswith(("bench/", "program")) for n, _ in gaps)
    assert gaps[0][1] == pytest.approx(0.00545, abs=0.0002)


def planes(device_events, host_events, second_device=None):
    p = {"/device:TPU:0": {"XLA Ops": device_events,
                           "XLA Modules": [("jit_step(7)", 0.0, 1e12)],
                           "Async XLA Ops": [("%never-read = x", 0.0, 1e12)]},
         "/host:CPU": {"python3": host_events}}
    if second_device is not None:
        p["/device:TPU:1"] = {"XLA Ops": second_device}
    return p


def test_busy_is_a_union_and_the_window_clips_it():
    ms = 1e6
    dev = [("%a = f32[] add()", 90 * ms, 110 * ms),       # half before
           ("%b = f32[] fusion(), kind=kLoop", 200 * ms, 300 * ms),
           ("%c = f32[] copy()", 250 * ms, 320 * ms),     # overlaps b
           ("%all-reduce.1 = f32[] all-reduce()", 900 * ms, 950 * ms),
           ("%late = f32[] add()", 1100 * ms, 1300 * ms)]  # one ms inside
    host = [(xplane.OPEN_MARK, 100 * ms, 100 * ms),
            (xplane.CLOSE_MARK, 1101 * ms, 1101 * ms),
            ("bench/source", 400 * ms, 500 * ms),
            ("bench/sink", 600 * ms, 605 * ms)]
    r = xplane.reduce(planes(dev, host))
    assert r["window_s"] == pytest.approx(1.001)
    assert r["busy_s"] == pytest.approx(0.010 + 0.120 + 0.050 + 0.001)
    assert r["idle_share"] == pytest.approx(1 - 0.181 / 1.001)
    names = dict(r["device_ops"])
    assert names["jit_step:b"] == pytest.approx(0.1)
    assert "jit_step:all-reduce.1" in names
    # the longest gap, 320..900 ms, has its middle after bench/sink ended
    assert r["idle_gaps"][0] == ["program/after_bench/sink",
                                 pytest.approx(0.580)]
    # 110..200 ms: nothing of the benchmark's had run yet
    assert ["program", pytest.approx(0.090)] in r["idle_gaps"]


def test_a_gap_inside_a_benchmark_span_is_the_benchmarks():
    ms = 1e6
    dev = [("%a = x", 0, 10 * ms), ("%b = x", 510 * ms, 520 * ms)]
    host = [("bench/source", 100 * ms, 400 * ms)]
    r = xplane.reduce(planes(dev, host))
    assert r["idle_gaps"][0][0] == "bench/source"
    # no marks: the window is the whole trace, and a run refuses it
    assert not r["marks_found"]
    assert r["window_s"] == pytest.approx(0.520)
    with pytest.raises(ValueError, match="lacks bench/window_open"):
        xplane.reduce_window(planes(dev, host))
    with pytest.raises(ValueError, match="lacks"):
        xplane.reduce_window(planes(dev, host + [(xplane.OPEN_MARK, 0, 0)]))


def test_several_devices_average_busy_and_report_the_fullest():
    ms = 1e6
    host = [(xplane.OPEN_MARK, 0, 0), (xplane.CLOSE_MARK, 1000 * ms, 0)]
    r = xplane.reduce(planes([("%a = x", 0, 100 * ms)], host,
                             second_device=[("%a = x", 0, 300 * ms)]))
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(0.2)
    assert r["idle_share"] == pytest.approx(0.7)


def test_a_trace_without_a_device_operation_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"/host:CPU": {"python3": [("x", 0, 1)]}})
