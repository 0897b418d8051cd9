"""The reduction from a profiler trace to device metrics, on a small
trace recorded on the CPU (tests/data/cpu_job.xplane.pb: three rounds of
two jitted programs with sleeps between them, inside a ``bench.job``
annotation).  On the CPU the XLA client thread stands in for a device."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import devtrace  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_job.xplane.pb")
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLAPjRtCpuClient",
           module_line=r"^tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def tr():
    return devtrace.Trace(DATA, **CPU)


def test_union_merges_overlaps():
    assert devtrace._union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]) == [
        [0, 3], [5, 9], [10, 11]]


def test_busy_is_the_union_inside_the_job(tr):
    a, b = tr.job
    assert tr.window_s() == pytest.approx((b - a) / 1e9)
    evs = tr.ops["/host:CPU"]
    # brute force at 1 us resolution
    us = set()
    for s, e, _ in evs:
        s, e = max(s, a), min(e, b)
        us.update(range(int(s) // 1000, -(-int(e) // 1000)))
    busy = tr.busy_s()["/host:CPU"]
    assert 0 < busy < tr.window_s()
    assert busy == pytest.approx(len(us) / 1e6, abs=len(evs) * 2e-6)
    idle = tr.idle_pct()["/host:CPU"]
    assert idle == pytest.approx(100 * (1 - busy / tr.window_s()))
    # the sleeps (3 x 30 ms) leave the job mostly idle
    assert idle > 50


def test_gaps_are_named_by_the_open_host_span(tr):
    a, b = tr.job
    mid = (a + b) // 2
    spans = [("streamed.total", a, b), ("streamed.tail", a, mid)]
    gaps = tr.idle_gaps(spans, offset_ns=0, k=10)
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert {g[0] for g in gaps} <= {"streamed.tail", "streamed.total"}
    # every gap plus the busy time fills the job when all gaps are kept
    all_gaps = tr.idle_gaps(spans, 0, k=10**6)
    total = sum(g[1] for g in all_gaps) + tr.busy_s()["/host:CPU"]
    assert total == pytest.approx(tr.window_s(), rel=1e-6)


def test_programs_by_pattern(tr):
    assert tr.program_s(["dot_general"]) > 0
    assert tr.program_s(["no_such_program"]) is None
    top = tr.top_programs(3)
    assert len(top) <= 3 and top == sorted(top, key=lambda t: -t[1])


def test_no_device_planes_reads_nothing():
    t = devtrace.Trace(DATA)  # the TPU selectors match nothing on a CPU
    assert t.busy_s() == {} and t.idle_pct() == {}
    assert t.program_s(["dot"]) is None
    assert t.idle_gaps([], 0) == []
