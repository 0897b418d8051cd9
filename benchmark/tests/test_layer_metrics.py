"""The readers of the realign-tail and pass-C metrics, on a small profile
recorded on the CPU (tests/data/prep_job.xplane.pb, made by
``data/make_prep_job.py``: ``bqsr.observe.prep`` spans recorded by the
program's tracer inside a ``bench.job`` annotation, one of them before
the job).  On the CPU the XLA client thread stands in for a device, as
in test_devtrace.py."""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, ".."))

import devtrace  # noqa: E402

DATA = os.path.join(HERE, "data")
PREP = os.path.join(DATA, "prep_job.xplane.pb")
BARE = os.path.join(DATA, "cpu_job.xplane.pb")
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLAPjRtCpuClient",
           module_line=r"^tf_XLAPjRtCpuClient")
SPAN = "bqsr.observe.prep"


def _metric(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"test_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def idle_mod():
    return _metric("observe_prep_idle_s_per_mread")


def _ctx(path, reads=1_000_000):
    tr = devtrace.Trace(path, **CPU)
    return dict(trace=tr, idle_pct=tr.idle_pct(),
                traced_job={"reads": reads, "snap": {"counters": {}}})


def _point_at(mod, monkeypatch, path):
    monkeypatch.setattr(mod.devtrace, "find_xplane", lambda d: path)


def test_prep_intervals_are_clipped_to_the_job(idle_mod):
    tr = devtrace.Trace(PREP, **CPU)
    a, b = tr.job
    got = idle_mod.host_intervals(PREP, SPAN, tr.job)
    # three rounds of two preps inside the job; the one before it is cut
    assert len(got) == 6
    assert all(a <= s < e <= b for s, e in got)
    wall = sum(e - s for s, e in got) / 1e9
    assert 0.06 < wall < tr.window_s()


def test_idle_under_prep_is_never_more_than_the_prep_wall(idle_mod,
                                                          monkeypatch):
    _point_at(idle_mod, monkeypatch, PREP)
    ctx = _ctx(PREP)
    tr = ctx["trace"]
    prep = idle_mod.host_intervals(PREP, SPAN, tr.job)
    wall_s = sum(e - s for s, e in prep) / 1e9
    v = idle_mod.read(ctx, {"span": SPAN})
    assert v is not None and 0 < v <= wall_s  # 1 Mread: s/Mread == s
    # brute force at 1 us resolution: prep microseconds with no op
    busy = set()
    for s, e, _ in tr._clip(tr.ops["/host:CPU"]):
        busy.update(range(int(s) // 1000, -(-int(e) // 1000)))
    idle_us = sum(1 for s, e in prep
                  for u in range(int(s) // 1000, int(e) // 1000)
                  if u not in busy)
    assert v == pytest.approx(idle_us / 1e6, abs=len(prep) * 4e-6 + 2e-5)


def test_idle_under_counts_only_uncovered_time(idle_mod):
    busy = [[2, 4], [6, 12], [14, 15]]
    assert idle_mod.idle_under([(0, 10)], busy) == 10 - 2 - 4
    assert idle_mod.idle_under([(0, 1), (5, 7), (11, 20)], busy) == 1 + 1 + 7
    assert idle_mod.idle_under([(0, 10)], []) == 10


def test_a_trace_without_annotations_reads_none(idle_mod, monkeypatch):
    _point_at(idle_mod, monkeypatch, BARE)
    assert idle_mod.read(_ctx(BARE), {"span": SPAN}) is None
    assert idle_mod.read({}, {"span": SPAN}) is None


@pytest.mark.parametrize("name,counter", [
    ("apply_device_ns_per_residue", "bqsr.apply.residues"),
    ("observe_device_ns_per_residue", "bqsr.observe.residues"),
])
def test_apply_device_time_per_residue(name, counter):
    mod = _metric(name)
    spec = {"patterns": ["dot_general"], "counter": counter}
    ctx = _ctx(PREP)
    assert mod.read(ctx, spec) is None  # the counter is missing
    ctx["traced_job"]["snap"]["counters"][counter] = 1000
    dev_s = ctx["trace"].program_s(["dot_general"])
    assert mod.read(ctx, spec) == pytest.approx(dev_s * 1e9 / 1000)
    assert mod.read(ctx, dict(spec, patterns=["no_such"])) is None
    assert mod.read({}, spec) is None


def test_realign_host_sums_the_phase_spans():
    mod = _metric("realign_host_s_per_mread")
    spec = {"spans": ["realign.targets", "realign.decide"]}

    def job(spans):
        return {"reads": 500_000, "snap": {"spans": {
            k: {"count": 1, "total_s": v} for k, v in spans.items()}}}

    ctx = {"layer_jobs": [
        job({"realign.targets": 0.25, "realign.decide": 0.5,
             "streamed.tail": 9.0}),
        job({"realign.targets": 0.25}),
    ]}
    assert mod.read(ctx, spec) == pytest.approx(1.0)
    ctx = {"layer_jobs": [job({"streamed.tail": 9.0})]}
    assert mod.read(ctx, spec) is None
