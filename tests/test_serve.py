"""Multi-job transform service (adam_tpu/serve): admission control,
weighted fairness, quarantine isolation, graceful drain + durable
journals, and whole-process crash recovery (docs/ROBUSTNESS.md
"Fault-isolated multi-job scheduling").

The pipeline-backed tests run the REAL streamed transform on the numpy
backend (fast, deterministic) and byte-compare every concurrent/
resumed output against a solo fault-free run — the service's core
contract is that scheduling changes where and when work runs, never
the bytes."""

import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from adam_tpu.serve import (
    DONE,
    INTERRUPTED,
    QUARANTINED,
    Admitted,
    Busy,
    JobScheduler,
    JobSpec,
    WeightedInterleaver,
)
from adam_tpu.serve import scheduler as sched_mod
from adam_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parts_hash(d):
    return {
        f: hashlib.sha256(
            open(os.path.join(d, f), "rb").read()
        ).hexdigest()
        for f in sorted(os.listdir(d)) if f.startswith("part-")
    }


@pytest.fixture(scope="module")
def serve_input(tmp_path_factory):
    """One synthetic input + its solo fault-free baseline (numpy
    backend, window_reads=512) shared by every pipeline-backed test."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from make_synth_sam import make_sam

    work = tmp_path_factory.mktemp("serve")
    path = str(work / "in.sam")
    make_sam(path, 4096, 100)
    solo = str(work / "solo.adam")
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "numpy"
    try:
        from adam_tpu.pipelines.streamed import transform_streamed

        transform_streamed(path, solo, window_reads=512)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return {"input": path, "baseline": _parts_hash(solo)}


@pytest.fixture()
def numpy_backend(monkeypatch):
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "numpy")


def _spec(jid, serve_input, tmp_path, **kw):
    return JobSpec(
        job_id=jid, input=serve_input["input"],
        output=str(tmp_path / f"{jid}.adam"), window_reads=512, **kw,
    )


# ---------------------------------------------------------------------------
# Fairness interleaver
# ---------------------------------------------------------------------------
def _arbitrate(inter, n):
    """Drive n grant decisions with EVERY lane pinned as waiting — the
    deterministic saturated-backlog view of the WFQ arbitration (the
    threaded paths free-run whenever contention lapses, by design:
    work conservation means a lone waiter never queues, so ratio
    assertions need a pinned backlog)."""
    order = []
    for _ in range(n):
        with inter._lock:
            for seq, lane in enumerate(inter._lanes.values(), 1):
                if lane.waiting_seq is None:
                    lane.waiting_seq = seq
            lane = inter._next_waiter_locked()
            t = inter._tenants[lane.tenant]
            inter._vtime = t.vt
            t.vt += 1.0 / t.weight
            lane.waiting_seq = None
        order.append(lane.job)
    return order


def test_interleaver_weighted_ratio():
    """Two saturated tenants at weights 3:1 interleave exactly 3:1."""
    inter = WeightedInterleaver()
    inter.register("a", tenant="A", weight=3.0)
    inter.register("b", tenant="B", weight=1.0)
    order = _arbitrate(inter, 40)
    assert order.count("a") == 30 and order.count("b") == 10
    # and the interleave is fine-grained, not a 30-then-10 block
    assert "b" in order[:5] and "a" in order[-5:]


def test_interleaver_tenant_shares_allocation():
    """Two jobs of one tenant split that tenant's share — they never
    double it against a single-job tenant of equal weight."""
    inter = WeightedInterleaver()
    inter.register("t1-a", tenant="T1", weight=1.0)
    inter.register("t1-b", tenant="T1", weight=1.0)
    inter.register("t2-z", tenant="T2", weight=1.0)
    order = _arbitrate(inter, 60)
    # equal tenant weights -> tenant T2 owns half the grants even
    # though it runs one job to T1's two
    assert order.count("t2-z") == 30
    assert order.count("t1-a") + order.count("t1-b") == 30


def test_interleaver_threaded_contention_liveness():
    """Concurrent turn() callers all make progress and every grant is
    recorded (the threaded path of the same arbitration)."""
    inter = WeightedInterleaver()
    inter.register("a", tenant="A", weight=2.0)
    inter.register("b", tenant="B", weight=1.0)

    def hammer(jid, n):
        for _ in range(n):
            inter.turn(jid)

    ts = [
        threading.Thread(target=hammer, args=(j, 50))
        for j in ("a", "b")
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive()
    grants = inter.grant_history()
    assert grants.count("a") == 50 and grants.count("b") == 50


def test_interleaver_solo_and_cancel():
    from adam_tpu.pipelines.streamed import RunCancelled

    inter = WeightedInterleaver()
    inter.register("solo")
    for _ in range(5):
        inter.turn("solo")  # work-conserving: grants immediately
    assert inter.grant_history() == ["solo"] * 5
    inter.cancel()
    with pytest.raises(RunCancelled):
        inter.turn("solo")
    inter.turn("never-registered")  # unregistered jobs free-run


# ---------------------------------------------------------------------------
# Admission control (scheduler with a stubbed pipeline)
# ---------------------------------------------------------------------------
@pytest.fixture()
def stub_transform(monkeypatch):
    """Replace the streamed pipeline with a gate-controlled stub so
    admission tests are timing-free."""
    release = threading.Event()
    started = []

    def fake(inp, out, **kw):
        started.append(out)
        assert release.wait(30), "stub never released"
        return {"n_reads": 0, "windows_fresh": 0}

    monkeypatch.setattr(sched_mod.streamed_mod, "transform_streamed",
                        fake)
    return {"release": release, "started": started}


def test_admission_capacity_and_typed_busy(tmp_path, stub_transform):
    sched = JobScheduler(str(tmp_path / "root"), max_jobs=2)
    try:
        mk = lambda jid: JobSpec(job_id=jid, input="in", output="out")
        assert isinstance(sched.submit(mk("j1")), Admitted)
        assert isinstance(sched.submit(mk("j2")), Admitted)
        got = sched.submit(mk("j3"))
        assert isinstance(got, Busy) and got.kind == "capacity"
        dup = sched.submit(mk("j1"))
        assert isinstance(dup, Busy) and dup.kind == "duplicate"
        # a freed slot admits again
        stub_transform["release"].set()
        assert sched.wait(timeout=30)
        assert isinstance(sched.submit(mk("j3")), Admitted)
        assert sched.wait(timeout=30)
        st = sched.status()["jobs"]
        assert {st[j]["state"] for j in ("j1", "j2", "j3")} == {DONE}
    finally:
        stub_transform["release"].set()
        sched.close()


def test_admission_rejects_while_draining(tmp_path, stub_transform):
    sched = JobScheduler(str(tmp_path / "root"), max_jobs=4)
    try:
        assert isinstance(
            sched.submit(JobSpec(job_id="j1", input="in", output="out")),
            Admitted,
        )
        sched.request_drain()
        got = sched.submit(
            JobSpec(job_id="j2", input="in", output="out")
        )
        assert isinstance(got, Busy) and got.kind == "draining"
        stub_transform["release"].set()
        assert sched.wait(timeout=30)
    finally:
        stub_transform["release"].set()
        sched.close()


def test_submit_blocking_deadline_surfaces_busy(
    tmp_path, stub_transform,
):
    """ISSUE-11 satellite: a full scheduler no longer spins
    `submit_blocking` forever — `deadline_s` bounds the wait through
    retry.call_with_deadline and a typed Busy(capacity) surfaces."""
    from adam_tpu.api.transform_service import TransformService

    svc = TransformService(str(tmp_path / "root"), max_jobs=1)
    try:
        mk = lambda jid: JobSpec(job_id=jid, input="in", output="out")
        assert isinstance(svc.submit(mk("hold")), Admitted)
        t0 = time.monotonic()
        got = svc.submit_blocking(mk("waiter"), deadline_s=0.5,
                                  poll_s=0.05)
        took = time.monotonic() - t0
        assert isinstance(got, Busy) and got.kind == "capacity"
        assert 0.4 <= took < 5.0, took
        # non-capacity rejections return immediately, deadline unused
        t0 = time.monotonic()
        dup = svc.submit_blocking(mk("hold"), deadline_s=30.0)
        assert isinstance(dup, Busy) and dup.kind == "duplicate"
        assert time.monotonic() - t0 < 5.0
        # a freed slot admits within the deadline
        stub_transform["release"].set()
        assert svc.wait(timeout=30)
        got = svc.submit_blocking(mk("waiter"), deadline_s=30.0)
        assert isinstance(got, Admitted)
        assert svc.wait(timeout=30)
    finally:
        stub_transform["release"].set()
        svc.close()


def test_spec_validation_and_manifest(tmp_path):
    with pytest.raises(ValueError):
        JobSpec(job_id="../evil", input="a", output="b").validate()
    with pytest.raises(ValueError):
        JobSpec(job_id="ok", input="a", output="b",
                weight=0).validate()
    from adam_tpu.api.transform_service import load_jobs_manifest

    mpath = tmp_path / "jobs.json"
    mpath.write_text(json.dumps({"jobs": [
        {"job_id": "a", "input": "i", "output": "o", "weight": 2.0},
    ]}))
    (spec,) = load_jobs_manifest(str(mpath))
    assert spec.job_id == "a" and spec.weight == 2.0
    mpath.write_text(json.dumps({"jobs": [
        {"job_id": "a", "input": "i", "output": "o", "nope": 1},
    ]}))
    with pytest.raises(ValueError, match="unknown field"):
        load_jobs_manifest(str(mpath))
    mpath.write_text(json.dumps({"jobs": [
        {"job_id": "a", "input": "i", "output": "o"},
        {"job_id": "a", "input": "i", "output": "p"},
    ]}))
    with pytest.raises(ValueError, match="duplicate"):
        load_jobs_manifest(str(mpath))


def test_pool_lease_bookkeeping():
    from adam_tpu.parallel.device_pool import DevicePool

    pool = DevicePool(limit=2)
    lease = pool.lease(job="jobX")
    assert lease.n == pool.n and lease.devices == pool.devices
    assert [lz.job for lz in pool.active_leases()] == ["jobX"]
    assert lease.device(0) is pool.device(0)
    lease.release()
    lease.release()  # idempotent
    assert pool.active_leases() == []
    assert lease.released


# ---------------------------------------------------------------------------
# Shared 2-device pool: concurrent jobs byte-identical to solo runs
# ---------------------------------------------------------------------------
def test_shared_pool_two_jobs_with_transient_faults(
    tmp_path, serve_input, monkeypatch,
):
    """The ISSUE-10 acceptance scenario: two concurrent jobs share one
    2-virtual-device pool under a transient device.dispatch fault spec
    and each output is byte-identical to its solo single-job run (the
    numpy solo baseline is valid by backend parity, PARITY.md)."""
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "device")
    monkeypatch.setenv("ADAM_TPU_RETRY_BACKOFF_S", "0.001")
    faults.install("device.dispatch=transient,every=7")
    try:
        sched = JobScheduler(str(tmp_path / "root"), max_jobs=2,
                             devices=2)
        a = _spec("pa", serve_input, tmp_path, tenant="A", weight=2.0)
        b = _spec("pb", serve_input, tmp_path, tenant="B", weight=1.0)
        assert isinstance(sched.submit(a), Admitted)
        assert isinstance(sched.submit(b), Admitted)
        assert sched.wait(timeout=600)
        st = sched.status()["jobs"]
        assert all(v["state"] == DONE for v in st.values()), st
        pool = sched._pool
        assert pool is not None and pool.n == 2
        assert pool.active_leases() == []
        for jid in ("pa", "pb"):
            assert _parts_hash(
                str(tmp_path / f"{jid}.adam")
            ) == serve_input["baseline"], jid
        sched.close()
    finally:
        faults.clear()


# ---------------------------------------------------------------------------
# Quarantine: one poison job, byte-identical survivors
# ---------------------------------------------------------------------------
def test_quarantine_leaves_survivors_byte_identical(
    tmp_path, serve_input, numpy_backend,
):
    faults.install("sched.job_crash=permanent,device=bad")
    try:
        sched = JobScheduler(str(tmp_path / "root"), max_jobs=2,
                             job_retries=1)
        ok = _spec("ok", serve_input, tmp_path, tenant="A")
        bad = _spec("bad", serve_input, tmp_path, tenant="B")
        assert isinstance(sched.submit(ok), Admitted)
        assert isinstance(sched.submit(bad), Admitted)
        assert sched.wait(timeout=300)
        st = sched.status()["jobs"]
        assert st["ok"]["state"] == DONE
        assert st["bad"]["state"] == QUARANTINED
        assert st["bad"]["attempts"] == 2  # 1 + job_retries
        assert "PermanentFault" in st["bad"]["error"]
        # the survivor's output is byte-identical to its solo run
        assert _parts_hash(
            str(tmp_path / "ok.adam")
        ) == serve_input["baseline"]
        # quarantine frees the slot and holds no lease
        assert sched.status()["active_leases"] == []
        faults.clear()
        retry = _spec("again", serve_input, tmp_path)
        assert isinstance(sched.submit(retry), Admitted)
        assert sched.wait(timeout=300)
        assert sched.status()["jobs"]["again"]["state"] == DONE
        # the quarantined record is durable on disk for the operator
        doc = json.load(
            open(tmp_path / "root" / "bad" / "JOB.json")
        )
        assert doc["state"] == QUARANTINED and doc["attempts"] == 2
        sched.close()
    finally:
        faults.clear()


def test_quarantine_is_sticky_across_restart(
    tmp_path, serve_input, numpy_backend,
):
    faults.install("sched.job_crash=permanent,device=poison")
    try:
        root = str(tmp_path / "root")
        sched = JobScheduler(root, max_jobs=2, job_retries=0)
        assert isinstance(
            sched.submit(_spec("poison", serve_input, tmp_path)),
            Admitted,
        )
        assert sched.wait(timeout=120)
        assert sched.status()["jobs"]["poison"]["state"] == QUARANTINED
        sched.close()
    finally:
        faults.clear()
    # restart: the recovery scan must NOT resume a quarantined job
    sched2 = JobScheduler(root, max_jobs=2)
    assert sched2.recover() == []
    assert sched2.status()["jobs"]["poison"]["state"] == QUARANTINED
    sched2.close()


# ---------------------------------------------------------------------------
# Graceful drain + whole-process restart-resume (in-process)
# ---------------------------------------------------------------------------
def test_drain_then_recover_resumes_bit_identically(
    tmp_path, serve_input, numpy_backend,
):
    root = str(tmp_path / "root")
    sched = JobScheduler(root, max_jobs=2)
    a = _spec("da", serve_input, tmp_path, tenant="A", weight=2.0)
    b = _spec("db", serve_input, tmp_path, tenant="B", weight=1.0)
    assert isinstance(sched.submit(a), Admitted)
    assert isinstance(sched.submit(b), Admitted)
    time.sleep(0.2)
    assert sched.drain(timeout=120)
    st = sched.status()["jobs"]
    for jid in ("da", "db"):
        assert st[jid]["state"] in (INTERRUPTED, DONE), st[jid]
        # drain durability: the on-disk record matches what wait()
        # reported — JOB.json is fsync'd before wait() unblocks
        doc = json.load(open(os.path.join(root, jid, "JOB.json")))
        assert doc["state"] == st[jid]["state"]
    sched.close()

    # "restart the process": a fresh scheduler over the same run-root
    sched2 = JobScheduler(root, max_jobs=2)
    resumed = sched2.recover()
    assert set(resumed) == {
        jid for jid in ("da", "db") if st[jid]["state"] == INTERRUPTED
    }
    assert sched2.wait(timeout=300)
    st2 = sched2.status()["jobs"]
    assert all(v["state"] == DONE for v in st2.values()), st2
    for jid in ("da", "db"):
        assert _parts_hash(
            str(tmp_path / f"{jid}.adam")
        ) == serve_input["baseline"], jid
    sched2.close()


# ---------------------------------------------------------------------------
# SIGTERM drain of the real serve CLI (subprocess)
# ---------------------------------------------------------------------------
_DRIVER = """\
import sys
from adam_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
"""


def _serve_cmd(root, jobs_file):
    return [sys.executable, "-c", _DRIVER, "serve", root,
            "--jobs", jobs_file, "--max-jobs", "2"]


def _serve_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ADAM_TPU_BQSR_BACKEND"] = "numpy"
    env.setdefault("ADAM_TPU_NO_COMPILE_CACHE", "1")
    env.pop("ADAM_TPU_FAULTS", None)
    return env


def test_sigterm_drain_exits_zero_then_resumes(tmp_path, serve_input):
    """SIGTERM mid-flight: exit 0 with durable journals; rerunning the
    same command resumes every job to a byte-identical finish."""
    root = str(tmp_path / "root")
    jobs_file = str(tmp_path / "jobs.json")
    outs = {jid: str(tmp_path / f"{jid}.adam") for jid in ("sa", "sb")}
    with open(jobs_file, "w") as fh:
        json.dump({"jobs": [
            {"job_id": jid, "input": serve_input["input"],
             "output": outs[jid], "window_reads": 512}
            for jid in outs
        ]}, fh)
    proc = subprocess.Popen(
        _serve_cmd(root, jobs_file), env=_serve_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    # wait until both jobs are live (their heartbeat files appear),
    # then request the drain
    deadline = time.monotonic() + 60
    hbs = [os.path.join(root, jid, "heartbeat.ndjson") for jid in outs]
    while time.monotonic() < deadline:
        if all(os.path.isfile(p) for p in hbs):
            break
        if proc.poll() is not None:
            break  # tiny input: the run may simply have finished
        time.sleep(0.05)
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0, out.decode(errors="replace")
    # resume to completion (no-op when the first run finished)
    rc = subprocess.run(
        _serve_cmd(root, jobs_file), env=_serve_env(), cwd=REPO,
        capture_output=True,
    )
    assert rc.returncode == 0, rc.stdout + rc.stderr
    for jid, out_dir in outs.items():
        assert _parts_hash(out_dir) == serve_input["baseline"], jid
        doc = json.load(open(os.path.join(root, jid, "JOB.json")))
        assert doc["state"] == DONE


# ---------------------------------------------------------------------------
# RunJournal.peek (the recovery scan's read-only view)
# ---------------------------------------------------------------------------
def test_run_journal_peek(tmp_path):
    from adam_tpu.pipelines.checkpoint import RunJournal

    assert RunJournal.peek(str(tmp_path)) is None  # no journal
    j = RunJournal(str(tmp_path), "fp", str(tmp_path / "out"))
    j.confirm_plan(3)
    j.record_window(0, "part-r-00000.parquet")
    got = RunJournal.peek(str(tmp_path))
    assert got == {"fingerprint": "fp", "n_windows": 3, "completed": 1}
    # torn journal -> None, not an exception
    with open(os.path.join(str(tmp_path), RunJournal.JOURNAL_NAME),
              "w") as fh:
        fh.write("{torn")
    assert RunJournal.peek(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# Trace context: one job = one trace across drain/restart attempts
# (docs/OBSERVABILITY.md "Trace context")
# ---------------------------------------------------------------------------
def test_one_job_one_trace_across_drain_and_recovery(
    tmp_path, serve_input, numpy_backend,
):
    import re

    from adam_tpu.utils import telemetry as tele

    root = str(tmp_path / "root")
    sched = JobScheduler(root, max_jobs=2)
    spec = _spec("tj", serve_input, tmp_path)
    assert spec.trace_id is None
    assert isinstance(sched.submit(spec), Admitted)
    # admission minted the trace and persisted it durably
    tid = spec.trace_id
    assert tid and re.fullmatch(r"[0-9a-f]{16}", tid)
    doc = json.load(open(os.path.join(root, "tj", "JOB.json")))
    assert doc["spec"]["trace_id"] == tid
    time.sleep(0.2)
    assert sched.drain(timeout=120)
    first_state = sched.status()["jobs"]["tj"]["state"]
    assert first_state in (INTERRUPTED, DONE)
    sched.close()

    # "restart the process": the recovered spec keeps the SAME trace —
    # however many attempts, one job is one trace
    sched2 = JobScheduler(root, max_jobs=2)
    resumed = sched2.recover()
    if first_state == INTERRUPTED:
        assert resumed == ["tj"]
    assert sched2.wait(timeout=300)
    st = sched2.status()["jobs"]["tj"]
    assert st["state"] == DONE
    assert st["spec"]["trace_id"] == tid
    doc2 = json.load(open(os.path.join(root, "tj", "JOB.json")))
    assert doc2["spec"]["trace_id"] == tid
    sched2.close()

    # the trace is queryable and complete: the scheduler's per-attempt
    # umbrella spans are stamped with it (every attempt, same trace)
    ev = tele.TRACE.events_for_trace(tid)
    sched_runs = [e for e in ev if e["name"] == tele.SPAN_SCHED_JOB]
    assert sched_runs and all(
        e["args"]["job"] == "tj" for e in sched_runs
    )
    # export determinism: two exports of the same trace are byte-equal
    # (what "byte-stable across recovery replay" means for the /trace
    # surface — the view is a pure function of the recorded events)
    d1 = json.dumps(tele.TRACE.to_chrome_trace(tid), sort_keys=True)
    d2 = json.dumps(tele.TRACE.to_chrome_trace(tid), sort_keys=True)
    assert d1 == d2
    # and tracing never touched the output bytes
    assert _parts_hash(str(tmp_path / "tj.adam")) \
        == serve_input["baseline"]
