"""Fault-injection matrix: the streamed multi-chip pipeline must
survive the failures it will actually see.

The 8 virtual CPU devices (tests/conftest.py) stand in for an 8-chip
topology.  Every recovery path — transient dispatch retry, permanent
fault -> eviction -> survivor replay, last-device loss -> host backend,
hung-fetch deadline, killed-mid-write crash consistency — must leave
the output **bit-identical** to a fault-free single-chip run: the
barrier merges are window-ordered and the device/host kernels are
bit-parity twins, so recovery changes where work runs, never what it
computes.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from adam_tpu.parallel import device_pool as dp
from adam_tpu.utils import faults
from adam_tpu.utils import retry as retry_mod
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Every test starts and ends with faults disarmed, fast retry
    backoff, and the global tracer untouched."""
    os.environ["ADAM_TPU_RETRY_BACKOFF_S"] = "0.001"
    was_recording = tele.TRACE.recording
    yield
    faults.clear()
    os.environ.pop("ADAM_TPU_RETRY_BACKOFF_S", None)
    tele.TRACE.recording = was_recording


# ---------------------------------------------------------------------------
# Fault-spec grammar + point mechanics
# ---------------------------------------------------------------------------
def test_fault_spec_parse_and_validation():
    cs = faults.parse_spec(
        "device.dispatch=transient,every=3;"
        "device.dispatch=permanent,device=1,times=1;"
        "device.fetch=delay:2.5,after=4;"
        "parquet.write=transient,p=0.5,seed=7"
    )
    assert [c.site for c in cs] == [
        "device.dispatch", "device.dispatch", "device.fetch",
        "parquet.write",
    ]
    assert cs[0].every == 3 and cs[0].action == "transient"
    assert cs[1].device == "1" and cs[1].times == 1
    assert cs[2].action == "delay" and cs[2].delay_s == 2.5
    assert cs[3].p == 0.5
    for bad in (
        "nope.site=transient",       # unknown point
        "device.dispatch=explode",   # unknown action
        "device.dispatch",           # missing action
        "device.dispatch=transient,every=zero",  # bad option value
        "device.dispatch=transient,wat=1",       # unknown option
        "device.dispatch=delay:soon",            # bad delay
    ):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_kill_action_parses_and_sigkills_self(monkeypatch):
    """The ``kill`` action (the chaos harness's weapon at the
    ``proc.kill`` site) parses and delivers SIGKILL to the process
    itself — captured here instead of actually dying."""
    (c,) = faults.parse_spec("proc.kill=kill,device=pass_c,after=1,times=1")
    assert c.site == "proc.kill" and c.action == "kill"
    assert c.device == "pass_c" and c.after == 1 and c.times == 1
    sent = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: sent.append((pid, sig)))
    faults.install("proc.kill=kill,device=pass_a,after=1,times=1")
    faults.point("proc.kill", device="ingest")   # wrong phase: no count
    faults.point("proc.kill", device="pass_a")   # arrival 1: after=1 skips
    assert sent == []
    faults.point("proc.kill", device="pass_a")   # arrival 2: fires
    assert sent == [(os.getpid(), signal.SIGKILL)]
    faults.point("proc.kill", device="pass_a")   # times=1 spent
    assert len(sent) == 1
    with pytest.raises(ValueError):
        faults.parse_spec("device.dispatch=kill:9")  # kill takes no arg


def test_point_disabled_is_noop_and_deterministic_when_armed():
    faults.clear()
    faults.point("device.dispatch")  # disarmed: must do nothing
    faults.install("device.dispatch=transient,every=3,times=2")
    fired = []
    for i in range(12):
        try:
            faults.point("device.dispatch")
            fired.append(False)
        except faults.TransientFault:
            fired.append(True)
    # arrivals 3 and 6 fire; times=2 silences 9 and 12
    assert [i + 1 for i, f in enumerate(fired) if f] == [3, 6]
    # device filter: non-matching attributions don't advance the clause
    faults.install("device.dispatch=permanent,device=5")
    faults.point("device.dispatch", device=3)
    with pytest.raises(faults.PermanentFault):
        faults.point("device.dispatch", device=5)


def test_same_site_clauses_all_count_arrivals():
    """Every clause on a site sees every arrival — an earlier clause
    firing must not make later clauses' every/after schedules drift
    from real arrival counts (the documented 'Nth time any call
    reaches this site' semantics)."""
    faults.install(
        "device.dispatch=transient,every=2;"
        "device.dispatch=permanent,after=5"
    )
    kinds = []
    for _ in range(6):
        try:
            faults.point("device.dispatch")
            kinds.append("-")
        except faults.TransientFault:
            kinds.append("T")
        except faults.PermanentFault:
            kinds.append("P")
    # arrivals 2/4/6 match clause 1; arrival 6 ALSO passes clause 2's
    # after=5, but the first matching clause wins — and clause 2 saw
    # all 6 arrivals, so arrival 7 (odd, > 5) fires it
    assert kinds == ["-", "T", "-", "T", "-", "T"]
    with pytest.raises(faults.PermanentFault):
        faults.point("device.dispatch")


def test_xla_runtime_error_retryability_by_status():
    """Only transient XLA statuses retry; deterministic device errors
    (OOM, bad argument) must surface to the eviction path on first
    sight instead of burning the retry budget."""

    class XlaRuntimeError(Exception):
        pass

    assert retry_mod.is_retryable(
        XlaRuntimeError("UNAVAILABLE: connection reset by peer")
    )
    assert retry_mod.is_retryable(
        XlaRuntimeError("INTERNAL: RPC stream closed")
    )
    assert not retry_mod.is_retryable(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")
    )
    assert not retry_mod.is_retryable(
        XlaRuntimeError("INVALID_ARGUMENT: shape mismatch")
    )


def test_seeded_probabilistic_clause_reproduces():
    def run():
        faults.install("device.dispatch=transient,p=0.4,seed=42")
        out = []
        for _ in range(20):
            try:
                faults.point("device.dispatch")
                out.append(0)
            except faults.TransientFault:
                out.append(1)
        return out

    a, b = run(), run()
    assert a == b and 1 in a and 0 in a


# ---------------------------------------------------------------------------
# Retry / deadline primitives
# ---------------------------------------------------------------------------
def test_retry_call_transient_then_success():
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise faults.TransientFault("flaky")
        return "ok"

    policy = retry_mod.RetryPolicy(attempts=3, backoff_s=0.0)
    assert retry_mod.retry_call(flaky, site="t", policy=policy) == "ok"
    assert calls[0] == 3


def test_retry_call_permanent_not_retried_and_budget_exhausts():
    calls = [0]

    def dead():
        calls[0] += 1
        raise faults.PermanentFault("dead chip")

    policy = retry_mod.RetryPolicy(attempts=5, backoff_s=0.0)
    with pytest.raises(faults.PermanentFault):
        retry_mod.retry_call(dead, site="t", policy=policy)
    assert calls[0] == 1  # permanent: exactly one attempt

    calls[0] = 0

    def always_transient():
        calls[0] += 1
        raise faults.TransientFault("still down")

    with pytest.raises(faults.TransientFault):
        retry_mod.retry_call(always_transient, site="t", policy=policy)
    assert calls[0] == 5  # the full budget, then the original error


def test_retry_jitter_deterministic_per_site():
    """ISSUE-10 satellite: seeded, per-site deterministic backoff
    jitter — a pure function of (seed, site, attempt), so a fixed seed
    reproduces the exact sleep schedule while sites decorrelate; the
    default stays jitter-free (factor exactly 1.0)."""
    # default: off
    assert retry_mod.jitter_factor("a", 1) == 1.0
    assert retry_mod.jitter_factor("a", 1, seed=7, amount=0.0) == 1.0
    # deterministic: same (seed, site, attempt) -> same factor
    f1 = retry_mod.jitter_factor("device.fetch", 1, seed=7, amount=0.5)
    f2 = retry_mod.jitter_factor("device.fetch", 1, seed=7, amount=0.5)
    assert f1 == f2 and 1.0 <= f1 < 1.5
    # decorrelated: different sites / seeds / attempts differ
    others = {
        retry_mod.jitter_factor("device.dispatch", 1, seed=7, amount=0.5),
        retry_mod.jitter_factor("device.fetch", 2, seed=7, amount=0.5),
        retry_mod.jitter_factor("device.fetch", 1, seed=8, amount=0.5),
    }
    assert f1 not in others and len(others) == 3


def test_retry_jitter_sleeps_scaled_and_decisions_unchanged(monkeypatch):
    """Jitter stretches the SLEEP only: attempt counts and outcomes
    are identical to the jitter-free run, and the slept durations are
    exactly backoff * jitter_factor for the fixed seed."""
    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    calls = [0]

    def always_transient():
        calls[0] += 1
        raise faults.TransientFault("down")

    policy = retry_mod.RetryPolicy(attempts=3, backoff_s=0.1,
                                   jitter=0.5, jitter_seed=7)
    with pytest.raises(faults.TransientFault):
        retry_mod.retry_call(always_transient, site="s", policy=policy)
    assert calls[0] == 3  # same decisions as jitter-free
    expected = [
        0.1 * retry_mod.jitter_factor("s", 1, seed=7, amount=0.5),
        0.2 * retry_mod.jitter_factor("s", 2, seed=7, amount=0.5),
    ]
    assert sleeps == pytest.approx(expected)
    # and the whole schedule reproduces for the same seed
    sleeps2 = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps2.append)
    calls[0] = 0
    with pytest.raises(faults.TransientFault):
        retry_mod.retry_call(always_transient, site="s", policy=policy)
    assert sleeps2 == sleeps


def test_retry_jitter_env_knobs(monkeypatch):
    monkeypatch.setenv("ADAM_TPU_RETRY_JITTER", "0.25")
    monkeypatch.setenv("ADAM_TPU_RETRY_JITTER_SEED", "42")
    p = retry_mod.RetryPolicy.from_env()
    assert p.jitter == 0.25 and p.jitter_seed == 42
    monkeypatch.setenv("ADAM_TPU_RETRY_JITTER", "nope")
    monkeypatch.setenv("ADAM_TPU_RETRY_JITTER_SEED", "also-nope")
    p = retry_mod.RetryPolicy.from_env()  # typo degrades to default
    assert p.jitter == 0.0 and p.jitter_seed == 0


def test_call_with_deadline_timeout_and_passthrough():
    assert retry_mod.call_with_deadline(lambda: 7, 5.0, site="t") == 7
    with pytest.raises(retry_mod.DeadlineExceeded):
        retry_mod.call_with_deadline(
            lambda: time.sleep(3), 0.05, site="t"
        )
    with pytest.raises(ZeroDivisionError):  # worker errors relay as-is
        retry_mod.call_with_deadline(lambda: 1 / 0, 5.0, site="t")
    assert retry_mod.is_retryable(retry_mod.DeadlineExceeded("x"))


def test_transfer_thread_floor_independent_of_affinity(monkeypatch):
    """ROADMAP satellite: chunked fetch overlap is GIL-released RPC
    wait — the pool must keep >= 2 I/O threads even on a 1-core
    affinity mask."""
    from adam_tpu.utils import transfer

    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0},
                        raising=False)
    assert transfer._max_threads() == 2


# ---------------------------------------------------------------------------
# DevicePool eviction unit behavior
# ---------------------------------------------------------------------------
def test_pool_eviction_round_robin_and_exhaustion():
    pool = dp.DevicePool(limit=4)
    tr = tele.Tracer(recording=True)
    assert [pool.device_index(i) for i in range(4)] == [0, 1, 2, 3]
    assert pool.evict(pool.devices[1], reason="test", tracer=tr)
    assert not pool.evict(pool.devices[1], tracer=tr)  # already dead
    assert pool.evict(None) is False                   # nothing to evict
    # survivors round-robin; indices still name ORIGINAL pool slots
    assert len(pool.alive_devices()) == 3
    assert [pool.device_index(i) for i in range(4)] == [0, 2, 3, 0]
    assert pool.n == 4  # the configured fan-out does not shrink
    assert tr.snapshot()["counters"][tele.C_DEVICE_EVICTED] == 1
    for d in pool.alive_devices():
        pool.evict(d, tracer=tr)
    with pytest.raises(dp.AllDevicesEvicted):
        pool.device(0)
    assert tr.snapshot()["counters"][tele.C_DEVICE_EVICTED] == 4


def test_prewarm_skips_evicted_devices():
    dp.reset_prewarm_cache()
    try:
        pool = dp.DevicePool(limit=3)
        pool.evict(pool.devices[2], reason="test")
        seen = []
        entries = [(("k", 1), lambda dev: seen.append(dev.id))]
        assert pool.prewarm(entries) == 2
        assert sorted(seen) == [0, 1]
    finally:
        dp.reset_prewarm_cache()


# ---------------------------------------------------------------------------
# Crash-consistent writes + writer-pool error propagation
# ---------------------------------------------------------------------------
def _tiny_dataset():
    from adam_tpu.formats.batch import pack_reads
    from adam_tpu.io.sam import SamHeader

    recs = [
        dict(name=f"r{i}", flags=0, contig_idx=0, start=100 + i, mapq=60,
             cigar="10M", seq="ACGTACGTAC", qual="I" * 10,
             read_group_idx=-1)
        for i in range(8)
    ]
    batch, side = pack_reads(recs)
    return batch, side, SamHeader()


def test_part_writer_pool_atomic_success_leaves_no_staging(tmp_path):
    from adam_tpu.io.parquet import TMP_DIR_NAME, PartWriterPool

    batch, side, header = _tiny_dataset()
    pool = PartWriterPool(n_encoders=1, inflight_parts=2)
    for i in range(3):
        pool.submit(str(tmp_path / f"part-r-{i:05d}.parquet"), batch,
                    side, header)
    pool.close()
    names = sorted(os.listdir(tmp_path))
    assert names == [f"part-r-{i:05d}.parquet" for i in range(3)]
    assert not (tmp_path / TMP_DIR_NAME).exists()


def test_part_writer_pool_write_error_original_traceback(tmp_path):
    """close() re-raises the FIRST worker exception itself (traceback
    intact), submit() fails fast afterwards, and no unpublished staging
    files survive — and nothing deadlocks on the submit gate."""
    from adam_tpu.io.parquet import TMP_DIR_NAME, PartWriterPool

    batch, side, header = _tiny_dataset()
    faults.install("parquet.write=transient")
    pool = PartWriterPool(n_encoders=1, inflight_parts=2)
    pool.submit(str(tmp_path / "part-r-00000.parquet"), batch, side,
                header)
    # wait for the worker failure, then submit must fail fast (chained
    # to the original) instead of queueing behind a dead writer
    deadline = time.time() + 10
    while pool.failed is None and time.time() < deadline:
        time.sleep(0.01)
    assert pool.failed is not None
    with pytest.raises(RuntimeError) as ei:
        pool.submit(str(tmp_path / "part-r-00001.parquet"), batch, side,
                    header)
    assert isinstance(ei.value.__cause__, faults.TransientFault)
    with pytest.raises(faults.TransientFault) as ei2:
        pool.close()
    # the original exception object: its traceback walks the worker
    assert ei2.value.__traceback__ is not None
    assert not (tmp_path / TMP_DIR_NAME).exists()
    assert not list(tmp_path.glob("*.parquet"))


def test_save_alignments_atomic_publish(tmp_path):
    from adam_tpu.io import parquet as pq_io

    batch, side, header = _tiny_dataset()
    out = tmp_path / "single.adam"
    pq_io.save_alignments(str(out), batch, side, header)
    assert out.exists()
    assert not (tmp_path / pq_io.TMP_DIR_NAME).exists()


def test_checkpoint_manifest_atomic_and_tolerant(tmp_path):
    from adam_tpu.pipelines.checkpoint import StageCheckpointer

    d = str(tmp_path / "ck")
    ck = StageCheckpointer(d, ["a", "b"])
    ck.mark("a")
    # atomic write: the temp name never survives a successful mark
    assert not os.path.exists(os.path.join(d, "MANIFEST.json.tmp"))
    with open(os.path.join(d, "MANIFEST.json")) as fh:
        assert json.load(fh)["completed"] == ["a"]
    # corrupt manifest: resume treats it as no checkpoint, not a crash
    with open(os.path.join(d, "MANIFEST.json"), "w") as fh:
        fh.write('{"stages": ["a", "b", TRUNC')
    ck2 = StageCheckpointer(d, ["a", "b"])
    assert ck2.last_completed() is None
    ck2.mark("a")  # and the next mark heals it atomically
    with open(os.path.join(d, "MANIFEST.json")) as fh:
        assert json.load(fh)["completed"] == ["a"]


def test_checkpoint_mark_idempotent_and_fingerprint_invalidates(tmp_path):
    """`mark()` never grows duplicate completed entries, and a manifest
    recorded under a different input/flag fingerprint is ignored (a
    recompute) instead of silently reloading stale stage stores."""
    from adam_tpu.pipelines.checkpoint import StageCheckpointer

    d = str(tmp_path / "ck")
    ck = StageCheckpointer(d, ["a", "b"], fingerprint="fp1")
    ck.mark("a")
    ck.mark("a")  # rerun double-mark: no duplicate
    with open(os.path.join(d, "MANIFEST.json")) as fh:
        m = json.load(fh)
    assert m["completed"] == ["a"] and m["fingerprint"] == "fp1"
    # the stage store must exist for resume (last_completed filters)
    open(os.path.join(d, "a.adam"), "w").write("x")
    assert StageCheckpointer(d, ["a", "b"],
                             fingerprint="fp1").last_completed() == "a"
    # changed input/flags -> different fingerprint -> no resume
    ck2 = StageCheckpointer(d, ["a", "b"], fingerprint="fp2")
    assert ck2.last_completed() is None
    # a legacy manifest without a fingerprint is equally untrusted
    with open(os.path.join(d, "MANIFEST.json"), "w") as fh:
        json.dump({"stages": ["a", "b"], "completed": ["a"]}, fh)
    assert StageCheckpointer(d, ["a", "b"],
                             fingerprint="fp1").last_completed() is None
    # ... but a fingerprint-less caller (the legacy API) still resumes
    assert StageCheckpointer(d, ["a", "b"]).last_completed() == "a"


def test_compose_and_input_fingerprints(tmp_path):
    from adam_tpu.pipelines import checkpoint as ck

    p = str(tmp_path / "in.sam")
    open(p, "w").write("@HD\tVN:1.5\nr1\t0\tc\t1\t60\t4M\t*\t0\t0\tACGT\tIIII\n")
    f1 = ck.input_fingerprint(p)
    assert f1 == ck.input_fingerprint(p)  # stable
    # content identity, not path identity
    p2 = str(tmp_path / "moved.sam")
    os.rename(p, p2)
    assert ck.input_fingerprint(p2) == f1
    open(p2, "a").write("r2\t4\t*\t0\t0\t*\t*\t0\t0\tACGT\tIIII\n")
    assert ck.input_fingerprint(p2) != f1
    # flag composition: value changes and array-content changes flip it
    base = {"input": f1, "window_reads": 256,
            "known": np.arange(4, dtype=np.int64)}
    fp = ck.compose_fingerprint(base)
    assert fp == ck.compose_fingerprint(dict(base))
    assert fp != ck.compose_fingerprint({**base, "window_reads": 512})
    assert fp != ck.compose_fingerprint(
        {**base, "known": np.arange(1, 5, dtype=np.int64)}
    )


# ---------------------------------------------------------------------------
# Streamed matrix on the virtual mesh (bit-compared to a fault-free run)
# ---------------------------------------------------------------------------
def _parts_hash(out_dir: str) -> dict:
    return {
        f: hashlib.sha256(
            open(os.path.join(out_dir, f), "rb").read()
        ).hexdigest()
        for f in os.listdir(out_dir) if f.startswith("part-")
    }


@pytest.fixture(scope="module")
def wgs_input(tmp_path_factory):
    from make_wgs_sam import make_wgs

    d = tmp_path_factory.mktemp("faults")
    path = str(d / "in.sam")
    make_wgs(path, 2048, 100, n_contigs=2, contig_len=30_000,
             indel_every=800, snp_every=400)
    return d, path


@pytest.fixture(scope="module")
def clean_baseline(wgs_input):
    """Fault-free single-chip reference run (device backend)."""
    from adam_tpu.pipelines.streamed import transform_streamed

    d, path = wgs_input
    out = str(d / "clean1.adam")
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
    try:
        transform_streamed(path, out, window_reads=256, devices=1)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return _parts_hash(out)


def _faulted_run(path, out, spec, devices, env=None):
    from adam_tpu.pipelines.streamed import transform_streamed

    os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
    os.environ.update(env or {})
    was = tele.TRACE.recording
    tele.TRACE.recording = True
    tele.TRACE.reset()
    faults.install(spec)
    try:
        stats = transform_streamed(path, out, window_reads=256,
                                   devices=devices)
    finally:
        faults.clear()
        snap = tele.TRACE.snapshot()
        tele.TRACE.recording = was
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
        for k in env or {}:
            os.environ.pop(k, None)
    return stats, snap


def test_streamed_acceptance_transient_plus_permanent(
    wgs_input, clean_baseline
):
    """The ISSUE acceptance scenario: every 3rd dispatch faults
    transiently and device 1 dies permanently, on the full 8-device
    mesh — the run completes, output is bit-identical to the fault-free
    single-chip run, device.evicted == 1 and retry.attempts > 0."""
    d, path = wgs_input
    out = str(d / "acc8.adam")
    stats, snap = _faulted_run(
        path, out,
        "device.dispatch=transient,every=3;"
        "device.dispatch=permanent,device=1,times=1",
        devices=8,
    )
    assert stats["n_devices"] == 8
    assert snap["counters"][tele.C_DEVICE_EVICTED] == 1
    assert snap["counters"][tele.C_RETRY_ATTEMPTS] > 0
    assert snap["counters"][tele.C_FAULT_INJECTED] > 0
    assert _parts_hash(out) == clean_baseline


def test_streamed_fetch_failure_evicts_and_replays(
    wgs_input, clean_baseline
):
    """Persistent fetch failures from one chip spend the retry budget,
    evict it, and replay its windows on survivors under the
    device.pool.replay span."""
    d, path = wgs_input
    out = str(d / "fetch4.adam")
    _stats, snap = _faulted_run(
        path, out, "device.fetch=transient,device=1", devices=4,
    )
    assert snap["counters"][tele.C_DEVICE_EVICTED] == 1
    assert snap["spans"][tele.SPAN_POOL_REPLAY]["count"] >= 1
    assert _parts_hash(out) == clean_baseline


def test_streamed_last_device_loss_falls_back_to_host(
    wgs_input, clean_baseline
):
    """Permanent faults kill both pool devices; the run degrades to the
    native/numpy host backend and still matches bit-for-bit."""
    d, path = wgs_input
    out = str(d / "lost2.adam")
    _stats, snap = _faulted_run(
        path, out, "device.dispatch=permanent", devices=2,
    )
    assert snap["counters"][tele.C_DEVICE_EVICTED] == 2
    assert _parts_hash(out) == clean_baseline


def test_streamed_mid_stream_device_loss_keeps_window_order(
    wgs_input, clean_baseline
):
    """The device path dies while older windows are still in flight in
    pass A's pending queue (after=4 skips the first windows' dispatches)
    — the pending windows must drain BEFORE the failing window's host
    summary, or the resolve barrier's window-offset slices apply
    duplicate flags to the wrong rows."""
    d, path = wgs_input
    out = str(d / "midloss2.adam")
    _stats, snap = _faulted_run(
        path, out, "device.dispatch=permanent,after=4", devices=2,
    )
    assert snap["counters"][tele.C_DEVICE_EVICTED] == 2
    assert _parts_hash(out) == clean_baseline


def test_streamed_hung_fetch_times_out_and_retries(
    wgs_input, clean_baseline
):
    """A hung fetch RPC (injected 5 s stall) trips the deadline
    watchdog, surfaces as a retryable timeout, and the retried fetch
    completes the run unchanged."""
    d, path = wgs_input
    out = str(d / "hang2.adam")
    _stats, snap = _faulted_run(
        path, out, "device.fetch=delay:5,times=1", devices=2,
        env={"ADAM_TPU_FETCH_TIMEOUT_S": "0.3"},
    )
    assert snap["counters"][tele.C_RETRY_ATTEMPTS] >= 1
    assert snap["counters"].get(tele.C_DEVICE_EVICTED, 0) == 0
    assert _parts_hash(out) == clean_baseline


def test_streamed_killed_mid_write_leaves_no_partial_parts(
    wgs_input, clean_baseline
):
    """SIGKILL while a part write is in flight: the output directory
    holds no *.tmp and no truncated part (unpublished writes live in
    the ignored _temporary staging dir), and a rerun starts clean and
    produces the bit-identical full output."""
    import pyarrow.parquet as pq

    d, path = wgs_input
    out = str(d / "killed.adam")
    driver = (
        "import sys\n"
        "from adam_tpu.pipelines.streamed import transform_streamed\n"
        "transform_streamed(sys.argv[1], sys.argv[2], window_reads=256)\n"
    )
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # host backend: the crash path under test is the writer
        "ADAM_TPU_BQSR_BACKEND": "numpy",
        # part 0 publishes, then part 1's write stalls 30 s: a
        # deterministic kill window with one part published and one
        # unpublished in flight
        "ADAM_TPU_FAULTS": "parquet.write=delay:30,after=1,times=1",
    })
    proc = subprocess.Popen(
        [sys.executable, "-c", driver, path, out],
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    staging = os.path.join(out, "_temporary")
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if os.path.isdir(out) and any(
                f.startswith("part-") for f in os.listdir(out)
            ):
                break
            if proc.poll() is not None:
                pytest.fail("driver exited before publishing a part")
            time.sleep(0.05)
        time.sleep(0.3)  # let the stalled write reach mid-flight
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    # crash consistency: no torn/temp parts visible to readers
    top = os.listdir(out)
    assert not [f for f in top if f.endswith(".tmp")], top
    for f in top:
        if f.startswith("part-"):
            pq.read_table(os.path.join(out, f))  # parses = not truncated
    # rerun over the same output dir: stale staging purged, full output
    from adam_tpu.pipelines.streamed import transform_streamed

    os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
    try:
        transform_streamed(path, out, window_reads=256, devices=1)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    assert not os.path.isdir(staging)
    assert _parts_hash(out) == clean_baseline
