"""perf.trace_reduce: busy, idle gaps and their host labels, on
synthetic op lists and on a small trace recorded on a TPU v5e."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402

FIXTURE = os.path.join(ROOT, "tests", "perf", "data", "fixture.xplane.pb")


US = 1000  # ns


def test_busy_is_the_union_of_op_intervals():
    ops = [("a", 0, 100 * US), ("b", 50 * US, 150 * US), ("a", 300 * US, 400 * US)]
    r = trace_reduce.reduce_ops([ops], 1000 * US)
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["device_ops"][0] == ["a", pytest.approx(200e-6)]
    # gaps: 150-300 and 400-1000 us, the longest first
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(600e-6), pytest.approx(150e-6)]


def test_ops_are_clipped_to_the_window_and_averaged_over_chips():
    chip0 = [("x", -50 * US, 50 * US), ("y", 900 * US, 1200 * US)]
    chip1 = [("x", 0, 500 * US)]
    r = trace_reduce.reduce_ops([chip0, chip1], 1000 * US)
    assert r["busy_s"] == pytest.approx((150 + 500) / 2 * 1e-6)
    # idle only where neither chip ran: 500-900 us
    assert r["idle_gaps"] == [["none", pytest.approx(400e-6)]]


def test_gaps_shorter_than_a_microsecond_are_not_named():
    r = trace_reduce.reduce_ops([[("a", 0, 10 * US), ("b", 10 * US + 2, 20 * US)]], 20 * US)
    assert r["idle_gaps"] == []


def test_gaps_take_the_highest_priority_host_stage():
    ops = [("k", 0, 10 * US), ("k", 100 * US, 110 * US)]
    intervals = [("event", 0, 200 * US), ("decision", 40 * US, 90 * US)]
    r = trace_reduce.reduce_ops([ops], 200 * US, intervals, ["decision", "event"])
    assert r["idle_gaps"][0][0] == "decision"
    assert r["idle_gaps"][1][0] == "event"


def test_nothing_to_read_gives_none():
    assert trace_reduce.reduce_ops([], 1000) is None
    assert trace_reduce.reduce_ops([[("a", 2000, 3000)]], 1000) is None


# recorded on a TPU v5e (my chip run, PR 22): three calls of one jitted
# program, a 50 ms sleep, one call of another, a 20 ms sleep
FIXTURE_WINDOW_NS = 115_075_727


def test_chip_fixture_busy_ops_and_gaps():
    path = trace_reduce.find_trace(os.path.dirname(FIXTURE))
    assert path == FIXTURE
    chips = trace_reduce.load_device_ops(FIXTURE)
    assert len(chips) == 1 and chips[0]
    assert all(name.startswith("jit__lambda:") for name, _, _ in chips[0])
    r = trace_reduce.reduce(FIXTURE, FIXTURE_WINDOW_NS)
    assert 0 < r["busy_s"] < 0.001
    assert r["window_s"] == pytest.approx(0.115075727)
    assert len(r["device_ops"]) <= trace_reduce.TOP
    assert r["device_ops"][0][0].startswith("jit__lambda:fusion")
    # the longest idle stretch is the 50 ms sleep between the programs
    longest = r["idle_gaps"][0][1]
    assert 0.04 < longest < 0.06
    assert sum(g[1] for g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9


def test_op_names_drop_fingerprint_and_hlo_text():
    name = trace_reduce.op_name(
        "jit__srlg_what_if_device(123456)", "%while.11 = (s32[16384,392]) while(...)"
    )
    assert name == "jit__srlg_what_if_device:while.11"
