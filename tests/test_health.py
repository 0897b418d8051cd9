"""Device health scoreboard, hedged dispatch, and the SDC audit
(docs/ROBUSTNESS.md "Device health, hedging, and SDC audit").

The quiet-failure matrix: a straggler chip must get hedged around
(byte-identically), a bit-flipping chip must get caught by the audit
and quarantined with the published output still byte-identical to a
clean run, and the scoreboard's state machine (healthy -> suspect ->
probation -> evicted, with the cooldown + known-answer re-admission
probe) must drive placement without ever changing output bytes.
"""

import hashlib
import os
import sys
import threading
import time

import numpy as np
import pytest

from adam_tpu.parallel import device_pool as dp
from adam_tpu.utils import faults
from adam_tpu.utils import health as health_mod
from adam_tpu.utils import retry as retry_mod
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test starts and ends with a fresh board, disarmed faults,
    fast backoff, and the global tracer untouched."""
    os.environ["ADAM_TPU_RETRY_BACKOFF_S"] = "0.001"
    health_mod.reset_board()
    was_recording = tele.TRACE.recording
    yield
    faults.clear()
    health_mod.reset_board()
    retry_mod.clear_cancel_event()
    for k in ("ADAM_TPU_RETRY_BACKOFF_S", "ADAM_TPU_HEDGE_FACTOR",
              "ADAM_TPU_AUDIT_RATE", "ADAM_TPU_AUDIT_SEED",
              "ADAM_TPU_HEDGE_MIN_S", "ADAM_TPU_HEDGE_MIN_SAMPLES"):
        os.environ.pop(k, None)
    tele.TRACE.recording = was_recording


# ---------------------------------------------------------------------------
# Scoreboard state machine (fake clock)
# ---------------------------------------------------------------------------
class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _board(**kw):
    clock = _Clock()
    b = health_mod.HealthBoard(
        clock=clock, suspect_score=3.0, probation_score=6.0,
        decay_halflife_s=30.0, cooldown_s=10.0, latency_factor=4.0,
        **kw,
    )
    return b, clock


def test_scoreboard_demotion_and_decay():
    b, clock = _board()
    tr = tele.Tracer(recording=True)
    assert b.state("cpu:0") == health_mod.HEALTHY
    for _ in range(5):
        b.note_retry("cpu:0", tracer=tr)      # 5 x 0.5 = 2.5 < 3
    assert b.state("cpu:0") == health_mod.HEALTHY
    b.note_retry("cpu:0", tracer=tr)          # 3.0 -> suspect
    assert b.state("cpu:0") == health_mod.SUSPECT
    assert not b.blocked("cpu:0")             # suspect still places
    snap = tr.snapshot()
    assert snap["counters"][tele.C_HEALTH_DEMOTED] == 1
    assert snap["health"]["cpu:0"]["state"] == health_mod.SUSPECT
    # decay walks a suspect back to healthy (half-life 30s)
    clock.t += 120.0
    assert b.state("cpu:0") == health_mod.HEALTHY


def test_scoreboard_probation_excludes_and_probe_readmits():
    b, clock = _board()
    tr = tele.Tracer(recording=True)
    for _ in range(4):
        b.note_timeout("cpu:1", tracer=tr)    # 4 x 1.5 = 6 -> probation
    assert b.state("cpu:1") == health_mod.PROBATION
    assert b.blocked("cpu:1")
    assert tr.snapshot()["counters"][tele.C_HEALTH_PROBATION] == 1
    # cooldown not elapsed: nothing due
    assert b.due_probes() == []
    clock.t += 10.0
    assert b.due_probes() == ["cpu:1"]
    # cooldown restarted: a failing probe can't hot-loop
    assert b.due_probes() == []
    b.readmit("cpu:1", tracer=tr)
    assert b.state("cpu:1") == health_mod.HEALTHY
    assert not b.blocked("cpu:1")
    assert tr.snapshot()["counters"][tele.C_HEALTH_READMITTED] == 1


def test_scoreboard_quarantine_and_probe_failure():
    b, clock = _board()
    tr = tele.Tracer(recording=True)
    b.quarantine("cpu:2", reason="sdc audit mismatch", tracer=tr)
    assert b.state("cpu:2") == health_mod.PROBATION
    assert b.blocked("cpu:2")
    clock.t += 10.0
    assert b.due_probes() == ["cpu:2"]
    b.probe_failed("cpu:2", tracer=tr)
    assert b.state("cpu:2") == health_mod.EVICTED
    assert b.blocked("cpu:2")
    # evicted is terminal: no more probes ever
    clock.t += 100.0
    assert b.due_probes() == []
    snap = tr.snapshot()
    assert snap["counters"][tele.C_HEALTH_PROBE_FAILED] == 1
    assert snap["health"]["cpu:2"]["state"] == health_mod.EVICTED


def test_latency_breach_penalizes_straggler_only():
    b, _clock = _board()
    tr = tele.Tracer(recording=True)
    # build the pooled histogram: 20 normal walls across two devices
    for i in range(20):
        b.observe_latency("bqsr.apply", f"cpu:{i % 2}", 0.01, tracer=tr)
    assert b.state("cpu:0") == health_mod.HEALTHY
    # one chip starts stretching every window to 100 x the pool
    for _ in range(6):
        b.observe_latency("bqsr.apply", "cpu:1", 1.0, tracer=tr)
    assert b.state("cpu:1") == health_mod.PROBATION
    assert b.state("cpu:0") == health_mod.HEALTHY
    # the breached walls stayed OUT of the pooled histogram, so the
    # hedge threshold still reflects the healthy tail
    os.environ["ADAM_TPU_HEDGE_FACTOR"] = "3"
    thr = b.hedge_threshold("bqsr.apply")
    assert thr is not None and thr < 0.5


def test_single_blip_charges_once_not_its_decay_tail():
    """One transient stall (GC pause, network hiccup) must cost ONE
    latency penalty — not one per healthy window while the EWMA's
    decay tail stays above the bound — or a single blip walks a
    healthy chip to probation."""
    b, _clock = _board()
    tr = tele.Tracer(recording=True)
    for _ in range(20):
        b.observe_latency("bqsr.apply", "cpu:0", 0.01, tracer=tr)
    b.observe_latency("bqsr.apply", "cpu:1", 1.0, tracer=tr)  # the blip
    for _ in range(10):  # healthy again, but the EWMA decays slowly
        b.observe_latency("bqsr.apply", "cpu:1", 0.01, tracer=tr)
    assert b.status()["cpu:1"]["signals"]["latency"] == 1
    assert b.state("cpu:1") == health_mod.HEALTHY


def test_cold_start_straggler_caught_by_peer_comparison():
    """A chip slow from its FIRST window contaminates the pooled p99
    it is judged against (half the warmup samples on a 2-device pool),
    so the pooled bound alone would never flag it — the cross-device
    peer-EWMA check must."""
    b, _clock = _board()
    tr = tele.Tracer(recording=True)
    for _ in range(10):
        b.observe_latency("bqsr.apply", "cpu:0", 0.01, tracer=tr)
        b.observe_latency("bqsr.apply", "cpu:1", 0.1, tracer=tr)
    assert b.state("cpu:1") == health_mod.PROBATION
    assert b.state("cpu:0") == health_mod.HEALTHY
    assert "peer" in b.status()["cpu:1"]["reason"]


def test_due_probes_candidates_preserve_foreign_dueness():
    """A pool claims (and restarts the cooldown of) only devices it
    can actually probe: another pool's due device stays due for the
    pool that CAN reach it."""
    b, clock = _board()
    b.quarantine("cpu:9")
    clock.t += 10.0
    assert b.due_probes(candidates=["cpu:0"]) == []  # not claimed
    assert b.due_probes(candidates=["cpu:9"]) == ["cpu:9"]
    assert b.due_probes(candidates=["cpu:9"]) == []  # cooldown restarted


def test_hedge_loss_walks_straggler_to_probation():
    """A chip so slow that EVERY window hedges produces no completed
    wall for observe_latency — the lost races themselves must feed the
    scoreboard, or the straggler hides behind the rescue forever."""
    b, _clock = _board()
    tr = tele.Tracer(recording=True)
    for _ in range(5):
        b.note_hedge_lost("cpu:1", "bqsr.apply", tracer=tr)  # 5 x 1.0
    assert b.state("cpu:1") == health_mod.SUSPECT
    b.note_hedge_lost("cpu:1", "bqsr.apply", tracer=tr)      # 6 x 1.0
    assert b.state("cpu:1") == health_mod.PROBATION
    assert b.blocked("cpu:1")
    row = b.status()["cpu:1"]
    assert row["signals"]["latency"] == 6
    assert "hedge" in row["reason"]


def test_hedge_threshold_gating():
    b, _clock = _board()
    assert b.hedge_threshold("bqsr.apply") is None  # factor unset
    os.environ["ADAM_TPU_HEDGE_FACTOR"] = "2"
    assert b.hedge_threshold("bqsr.apply") is None  # no samples
    for _ in range(health_mod.MIN_LATENCY_SAMPLES):
        b.observe_latency("bqsr.apply", "cpu:0", 0.2)
    thr = b.hedge_threshold("bqsr.apply")
    assert thr is not None and thr >= 0.2  # ~2 x p99, floored
    # the floor keeps micro-walls from hedging every window
    b2, _ = _board()
    for _ in range(health_mod.MIN_LATENCY_SAMPLES):
        b2.observe_latency("k", "cpu:0", 1e-6)
    assert b2.hedge_threshold("k") >= 0.05


def test_audit_due_is_deterministic_and_rate_shaped():
    assert not health_mod.audit_due(5, rate=0.0)
    assert health_mod.audit_due(5, rate=1.0)
    picked = [w for w in range(400)
              if health_mod.audit_due(w, rate=0.25, seed=3)]
    again = [w for w in range(400)
             if health_mod.audit_due(w, rate=0.25, seed=3)]
    assert picked == again                      # pure function
    assert 60 <= len(picked) <= 140             # ~0.25 of 400
    other = [w for w in range(400)
             if health_mod.audit_due(w, rate=0.25, seed=4)]
    assert picked != other                      # seed moves the sample


def test_known_answer_probe_passes_on_real_device():
    import jax

    assert health_mod.probe_known_answer(jax.local_devices()[0])


# ---------------------------------------------------------------------------
# Pool integration: placement filtering, availability fallback, probes
# ---------------------------------------------------------------------------
def test_pool_placement_skips_probation_devices():
    pool = dp.DevicePool(limit=4)
    key1 = dp._device_key(pool.devices[1])
    pool.health.quarantine(key1)
    alive = pool.alive_devices()
    assert pool.devices[1] not in alive and len(alive) == 3
    # survivors() (the prewarm set) still includes the probation chip
    assert pool.devices[1] in pool.survivors()
    # placement round-robins over the healthy subset only
    seen = {dp._device_key(pool.device(i)) for i in range(8)}
    assert key1 not in seen


def test_pool_availability_beats_health():
    pool = dp.DevicePool(limit=2)
    for d in pool.devices:
        pool.health.quarantine(dp._device_key(d))
    # every survivor blocked -> the filter yields, placement continues
    assert pool.alive_devices() == pool.survivors()
    assert pool.device(0) is not None


def test_pool_probe_readmits_and_evicts(monkeypatch):
    pool = dp.DevicePool(limit=2)
    b = pool.health
    b.cooldown_s = 0.0
    key0 = dp._device_key(pool.devices[0])
    b.quarantine(key0)
    monkeypatch.setattr(health_mod, "probe_known_answer", lambda d: True)
    pool.device(0)  # placement runs the due probe
    assert b.state(key0) == health_mod.HEALTHY
    # now a probe that fails: probation -> evicted through pool.evict
    b.quarantine(key0)
    monkeypatch.setattr(health_mod, "probe_known_answer", lambda d: False)
    pool.device(0)
    assert b.state(key0) == health_mod.EVICTED
    assert pool.devices[0] not in pool.survivors()


def test_mesh_healthy_subset():
    from adam_tpu.parallel.partitioner import healthy_subset

    b, _clock = _board()
    devs = ["cpu:0", "cpu:1", "cpu:2"]
    assert healthy_subset(devs, b) == devs
    b.quarantine("cpu:1")
    assert healthy_subset(devs, b) == ["cpu:0", "cpu:2"]
    b.quarantine("cpu:0")
    b.quarantine("cpu:2")
    assert healthy_subset(devs, b) == devs  # availability fallback


# ---------------------------------------------------------------------------
# hedged_call unit matrix
# ---------------------------------------------------------------------------
def test_hedged_call_primary_fast_path():
    tr = tele.Tracer(recording=True)
    out, winner, fired = dp.hedged_call(
        lambda: "primary", lambda: "hedge", 5.0, tracer=tr
    )
    assert (out, winner, fired) == ("primary", "primary", False)
    assert tele.C_HEDGE_FIRED not in tr.snapshot()["counters"]


def test_hedged_call_hedge_wins_and_counters_reconcile():
    tr = tele.Tracer(recording=True)
    release = threading.Event()

    def slow_primary():
        release.wait(5.0)
        return "primary"

    out, winner, fired = dp.hedged_call(
        slow_primary, lambda: "hedge", 0.05, tracer=tr
    )
    release.set()
    assert (out, winner, fired) == ("hedge", "hedge", True)
    c = tr.snapshot()["counters"]
    assert c[tele.C_HEDGE_FIRED] == 1 and c[tele.C_HEDGE_WON] == 1
    assert c.get(tele.C_HEDGE_WASTED, 0) == 0
    assert c[tele.C_HEDGE_FIRED] == (
        c[tele.C_HEDGE_WON] + c.get(tele.C_HEDGE_WASTED, 0)
    )


def test_hedged_call_primary_beats_slow_hedge():
    tr = tele.Tracer(recording=True)

    def primary():
        time.sleep(0.1)
        return "primary"

    def hedge():
        time.sleep(0.5)
        return "hedge"

    out, winner, fired = dp.hedged_call(primary, hedge, 0.02, tracer=tr)
    assert (out, winner, fired) == ("primary", "primary", True)
    c = tr.snapshot()["counters"]
    assert c[tele.C_HEDGE_FIRED] == 1
    assert c[tele.C_HEDGE_WASTED] == 1
    assert c.get(tele.C_HEDGE_WON, 0) == 0


def test_hedged_call_hedge_failure_falls_back_to_primary():
    tr = tele.Tracer(recording=True)

    def primary():
        time.sleep(0.1)
        return "primary"

    def bad_hedge():
        raise RuntimeError("no alternate device")

    out, winner, fired = dp.hedged_call(primary, bad_hedge, 0.02,
                                        tracer=tr)
    assert (out, winner, fired) == ("primary", "primary", True)


def test_hedged_call_primary_error_propagates():
    def primary():
        raise ValueError("chip error")

    with pytest.raises(ValueError, match="chip error"):
        dp.hedged_call(primary, lambda: "hedge", 5.0,
                       tracer=tele.Tracer(recording=True))


# ---------------------------------------------------------------------------
# corrupt action + pass= selector (the fault grammar's data channel)
# ---------------------------------------------------------------------------
def test_corrupt_grammar_validation():
    (c,) = faults.parse_spec("device.fetch=corrupt,every=3,seed=9")
    assert c.action == "corrupt" and c.every == 3 and c.seed == 9
    with pytest.raises(ValueError):
        faults.parse_spec("device.dispatch=corrupt")  # not corrupt-capable
    with pytest.raises(ValueError):
        faults.parse_spec("parquet.write=corrupt")
    (c2,) = faults.parse_spec("device.fetch=delay:1,pass=apply")
    assert c2.pass_name == "apply"


def test_corrupt_array_flips_one_bit_deterministically():
    faults.install("device.fetch=corrupt,every=1,seed=5,times=1")
    a = np.arange(64, dtype=np.uint8)
    out = faults.corrupt_array("device.fetch", a)
    assert out is not a
    diff = np.bitwise_xor(out, a)
    assert diff.sum() > 0
    # exactly one bit flipped
    assert sum(bin(int(v)).count("1") for v in diff) == 1
    # times=1 spent: the next arrival passes through untouched
    out2 = faults.corrupt_array("device.fetch", a)
    assert out2 is a
    # same seed reproduces the same flip
    faults.install("device.fetch=corrupt,every=1,seed=5,times=1")
    again = faults.corrupt_array("device.fetch",
                                 np.arange(64, dtype=np.uint8))
    assert np.array_equal(again, out)


def test_corrupt_array_never_raises_on_scalar_results():
    """The data channel's contract: corrupt never raises — a 0-d fetch
    result (a scalar) flips a bit instead of blowing up the fetch with
    a view-cast ValueError."""
    faults.install("device.fetch=corrupt,every=1")
    a = np.int64(7) + np.zeros((), np.int64)  # 0-d array
    out = faults.corrupt_array("device.fetch", a)
    assert out.shape == () and int(out) != 7
    # object arrays pass through silently (nothing to flip)
    obj = np.array([object()])
    assert faults.corrupt_array("device.fetch", obj) is obj


def test_corrupt_ignores_point_channel_and_honors_pass():
    faults.install("device.fetch=corrupt,every=1")
    # the exception channel never fires corrupt clauses
    faults.point("device.fetch")  # must not raise or count the arrival
    a = np.zeros(8, np.int64)
    with tele.pass_scope("a"):
        same = faults.corrupt_array(
            "device.fetch", a, pass_name="a"
        )
    faults.install("device.fetch=corrupt,every=1,pass=apply")
    untouched = faults.corrupt_array("device.fetch", a, pass_name="a")
    assert untouched is a                     # wrong pass: no arrival
    flipped = faults.corrupt_array("device.fetch", a, pass_name="apply")
    assert not np.array_equal(flipped, a)
    assert same is not None


def test_device_fetch_routes_through_corrupt(monkeypatch):
    """A corrupt clause at device.fetch flips bits in a REAL fetched
    device array — the injection the audit must catch."""
    import jax

    x = jax.device_put(np.arange(256, dtype=np.uint8),
                       jax.local_devices()[0])
    from adam_tpu.utils.transfer import device_fetch

    faults.install("device.fetch=corrupt,every=1,times=1")
    got = device_fetch(x)
    clean = np.arange(256, dtype=np.uint8)
    assert not np.array_equal(got, clean)
    faults.clear()
    assert np.array_equal(device_fetch(x), clean)


# ---------------------------------------------------------------------------
# Drain-aware retry backoff (satellite)
# ---------------------------------------------------------------------------
def test_retry_backoff_sleep_is_drain_aware():
    ev = threading.Event()
    calls = []

    def failing():
        calls.append(1)
        raise faults.TransientFault("flaky")

    policy = retry_mod.RetryPolicy(attempts=5, backoff_s=30.0,
                                   max_backoff_s=30.0)
    t0 = time.monotonic()
    threading.Timer(0.2, ev.set).start()
    with pytest.raises(faults.TransientFault):
        retry_mod.retry_call(failing, site="t", policy=policy, cancel=ev)
    took = time.monotonic() - t0
    # the drain interrupted the 30s backoffs almost immediately, but
    # the attempt budget still ran out back to back — failure
    # semantics are untouched (a one-off transient mid-drain would
    # still absorb instead of surfacing as a spurious device failure)
    assert took < 5.0
    assert len(calls) == 5


def test_retry_cancel_event_registration_scoping():
    ev1, ev2 = threading.Event(), threading.Event()
    retry_mod.set_cancel_event(ev1)
    assert retry_mod.cancel_event() is ev1
    retry_mod.set_cancel_event(ev2)
    # clearing with the OLD event must not remove the new registration
    retry_mod.clear_cancel_event(ev1)
    assert retry_mod.cancel_event() is ev2
    retry_mod.clear_cancel_event(ev2)
    assert retry_mod.cancel_event() is None


def test_retry_uses_installed_event_when_set():
    ev = threading.Event()
    ev.set()
    retry_mod.set_cancel_event(ev)

    def failing():
        raise faults.TransientFault("flaky")

    policy = retry_mod.RetryPolicy(attempts=5, backoff_s=30.0)
    t0 = time.monotonic()
    with pytest.raises(faults.TransientFault):
        retry_mod.retry_call(failing, site="t", policy=policy)
    assert time.monotonic() - t0 < 2.0


# ---------------------------------------------------------------------------
# Mid-run quota throttle (satellite)
# ---------------------------------------------------------------------------
def test_quota_throttle_defers_then_grants():
    from adam_tpu.serve.quota import QuotaManager

    clock = _Clock()
    tr = tele.Tracer(recording=True)
    qm = QuotaManager("t1:bytes=1000", window_s=60.0, clock=clock,
                      tracer=tr)
    # under budget: zero-cost fast path, no deferral counted
    assert qm.throttle("t1", sleep=lambda s: None) == 0.0
    qm.charge("t1", nbytes=2000)
    slept = []

    def fake_sleep(s):
        slept.append(s)
        clock.t += 10.0  # each poll advances the fake clock

    deferred = qm.throttle("t1", sleep=fake_sleep, tracer=tr)
    # the charge aged out of the 60s window after ~6 polls
    assert deferred >= 60.0 and slept
    assert qm.check("t1") is None  # the grant can proceed now
    assert tr.snapshot()["counters"][tele.C_QUOTA_DEFERRED] == 1


def test_quota_throttle_stops_on_drain_and_bound():
    from adam_tpu.serve.quota import QuotaManager

    clock = _Clock()
    qm = QuotaManager("t1:bytes=10", window_s=1000.0, clock=clock)
    qm.charge("t1", nbytes=100)
    # should_stop wins immediately
    assert qm.throttle(
        "t1", should_stop=lambda: True, sleep=lambda s: None
    ) == 0.0
    # the bound caps a stuck budget
    def fake_sleep(s):
        clock.t += 5.0

    deferred = qm.throttle("t1", max_wait_s=20.0, sleep=fake_sleep)
    assert 20.0 <= deferred <= 30.0
    assert qm.check("t1") is not None  # still over budget: bounded, not stuck


def test_scheduler_pacer_defers_over_budget_tenant(tmp_path):
    """The pacer seam defers an over-budget tenant's grant and counts
    sched.quota.deferred — the unit twin of the serve-level smoke."""
    from adam_tpu.serve.job import JobSpec
    from adam_tpu.serve.quota import QuotaManager, THROTTLE_POLL_S
    from adam_tpu.serve.scheduler import JobScheduler

    sched = JobScheduler(str(tmp_path / "root"), max_jobs=1,
                         quota=QuotaManager("tA:bytes=100",
                                            window_s=0.4))
    try:
        was = tele.TRACE.recording
        tele.TRACE.recording = True
        spec = JobSpec(job_id="j1", tenant="tA", input="x", output="y")
        sched._interleaver.register("j1", tenant="tA")
        pace = sched._job_pacer(spec)
        sched.quota.charge("tA", nbytes=1000)  # blow the budget
        t0 = time.monotonic()
        pace("pass_a", 0, 50)  # must defer until the window expires
        took = time.monotonic() - t0
        assert took >= 0.2
        _c, _ = tele.TRACE.counters_and_gauges()
        assert _c.get(tele.C_QUOTA_DEFERRED, 0) >= 1
        # next grant is in budget again: fast path
        t0 = time.monotonic()
        pace("pass_a", 1, 10)
        assert time.monotonic() - t0 < 0.2
    finally:
        tele.TRACE.recording = was
        sched.close()


# ---------------------------------------------------------------------------
# End-to-end: straggler hedge + SDC audit on the real streamed pipeline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def wgs_input(tmp_path_factory):
    from make_wgs_sam import make_wgs

    d = tmp_path_factory.mktemp("health")
    path = str(d / "in.sam")
    make_wgs(path, 2048, 100, n_contigs=2, contig_len=30_000,
             indel_every=800, snp_every=400)
    return d, path


def _parts_hash(out_dir):
    out = {}
    for f in sorted(os.listdir(out_dir)):
        if f.startswith("part-") and f.endswith(".parquet"):
            with open(os.path.join(out_dir, f), "rb") as fh:
                out[f] = hashlib.sha256(fh.read()).hexdigest()
    assert out
    return out


@pytest.fixture(scope="module")
def clean_baseline(wgs_input):
    from adam_tpu.pipelines.streamed import transform_streamed

    d, path = wgs_input
    out = str(d / "clean1.adam")
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
    try:
        transform_streamed(path, out, window_reads=256, devices=1)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return _parts_hash(out)


def _run(path, out, spec, devices, env=None):
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
        board = health_mod.BOARD.status()
    finally:
        faults.clear()
        snap = tele.TRACE.snapshot()
        tele.TRACE.recording = was
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
        for k in env or {}:
            os.environ.pop(k, None)
    return stats, snap, board


def test_streamed_sdc_audit_catches_corrupt_and_quarantines(
    wgs_input, clean_baseline
):
    """ISSUE acceptance: a seeded ``corrupt`` injection at
    ``device.fetch`` is caught by the audit (device.audit.mismatch >
    0), the offending device quarantines, and the published output is
    byte-identical to a fault-free run."""
    d, path = wgs_input
    out = str(d / "sdc2.adam")
    stats, snap, board = _run(
        path, out,
        "device.fetch=corrupt,pass=apply,every=3,seed=11",
        devices=2,
        env={"ADAM_TPU_AUDIT_RATE": "1.0"},
    )
    c = snap["counters"]
    assert c.get(tele.C_FAULT_INJECTED, 0) >= 1
    assert c.get(tele.C_AUDIT_SAMPLED, 0) >= stats["windows_fresh"]
    assert c.get(tele.C_AUDIT_MISMATCH, 0) >= 1
    # every flip was caught: no corrupt byte survived to disk
    assert _parts_hash(out) == clean_baseline
    # the producing chip went through probation (quarantine)
    assert c.get(tele.C_HEALTH_PROBATION, 0) >= 1
    assert any(
        row["state"] in (health_mod.PROBATION, health_mod.EVICTED)
        for row in board.values()
    )
    # the health section rode into the snapshot for the analyzer
    assert snap["health"]


def test_streamed_audit_clean_run_no_mismatch(wgs_input, clean_baseline):
    """Audit on, no corruption: every sampled window verifies, nothing
    quarantines, output identical — the audit itself never perturbs
    the published bytes."""
    d, path = wgs_input
    out = str(d / "audit_clean.adam")
    stats, snap, board = _run(
        path, out, None, devices=2,
        env={"ADAM_TPU_AUDIT_RATE": "0.5", "ADAM_TPU_AUDIT_SEED": "7"},
    )
    c = snap["counters"]
    assert c.get(tele.C_AUDIT_SAMPLED, 0) >= 1
    assert c.get(tele.C_AUDIT_MISMATCH, 0) == 0
    assert c.get(tele.C_HEALTH_PROBATION, 0) == 0
    assert _parts_hash(out) == clean_baseline


def test_streamed_hedge_rescues_straggler_byte_identically(
    wgs_input, clean_baseline
):
    """ISSUE acceptance: an injected straggler (seeded delay on one
    device's pass-C fetches) makes the hedge fire; the winner's bytes
    match the un-hedged run bit-for-bit and the hedge counters
    reconcile (fired == won + wasted)."""
    d, path = wgs_input
    out = str(d / "hedge2.adam")
    stats, snap, board = _run(
        path, out,
        # stall device 1's apply-pass fetches only once the latency
        # pool is warm (>= ADAM_TPU_HEDGE_MIN_SAMPLES pooled walls —
        # the hedge threshold needs a p99 first): each stalled fetch
        # then exceeds factor x p99 and the hedge re-runs the window
        # on device 0.  after=6 skips the first ~3 of device 1's
        # windows (the packed finish fetches ~2 payload slices per
        # window), past the 4-sample floor on this 8-window run.
        "device.fetch=delay:1.0,device=1,pass=apply,after=6",
        devices=2,
        env={
            "ADAM_TPU_HEDGE_FACTOR": "3",
            "ADAM_TPU_HEDGE_MIN_S": "0.05",
            "ADAM_TPU_HEDGE_MIN_SAMPLES": "4",
        },
    )
    c = snap["counters"]
    assert c.get(tele.C_HEDGE_FIRED, 0) >= 1, c
    assert c.get(tele.C_HEDGE_WON, 0) >= 1, c
    assert c[tele.C_HEDGE_FIRED] == (
        c.get(tele.C_HEDGE_WON, 0) + c.get(tele.C_HEDGE_WASTED, 0)
    )
    assert _parts_hash(out) == clean_baseline


def test_streamed_latency_is_the_dispatch_to_fetch_wall(
    wgs_input, clean_baseline, monkeypatch
):
    """The board reads each pass-C window's wall from its dispatch to
    the end of its fetch.  A pool pass dispatches up to two windows per
    device before its first fetch; a wall that started at the fetch
    would charge the first-fetched window's whole kernel to its chip
    alone, and read the peers, whose kernels ran meanwhile, as ~0."""
    from adam_tpu.pipelines import bqsr as bqsr_mod

    pause = 0.05
    walls = []
    real_observe = health_mod.BOARD.observe_latency
    real_dispatch = bqsr_mod.apply_recalibration_dispatch

    def observe(kernel, device, seconds, tracer=None):
        walls.append(seconds)
        return real_observe(kernel, device, seconds, tracer=tracer)

    def dispatch(*a, **k):
        handle = real_dispatch(*a, **k)
        time.sleep(pause)  # host work between this dispatch and the next
        return handle

    monkeypatch.setattr(health_mod.BOARD, "observe_latency", observe)
    monkeypatch.setattr(bqsr_mod, "apply_recalibration_dispatch", dispatch)
    d, path = wgs_input
    out = str(d / "walls.adam")
    _run(path, out, None, devices=2)
    assert len(walls) >= 4
    # every wall holds its own window's dispatch; the first fetched
    # holds the dispatches of the three windows queued behind it too
    assert min(walls) >= pause
    assert walls[0] >= 4 * pause
    assert _parts_hash(out) == clean_baseline


def test_heartbeat_carries_device_health_field():
    tr = tele.Tracer(recording=True)
    hb = tele.Heartbeat([tr], sink="stderr", interval_s=60.0)
    line = hb.sample()
    assert tuple(line.keys()) == tele.HEARTBEAT_FIELDS
    assert line["schema"] == "adam_tpu.heartbeat/7"
    assert line["device_health"] is None  # nothing tracked yet
    health_mod.BOARD.quarantine("cpu:3")
    line2 = hb.sample()
    assert line2["device_health"]["cpu:3"] == health_mod.PROBATION


def test_analyzer_renders_device_health_section():
    from adam_tpu.utils.analyzer import analyze, render_report

    tr = tele.Tracer(recording=True)
    tr.record_health("cpu:1", health_mod.PROBATION, 6.0,
                     "sdc audit mismatch on window 3")
    tr.count(tele.C_AUDIT_SAMPLED, 10)
    tr.count(tele.C_AUDIT_MISMATCH, 2)
    tr.count(tele.C_HEDGE_FIRED, 3)
    tr.count(tele.C_HEDGE_WON, 2)
    tr.count(tele.C_HEDGE_WASTED, 1)
    tr.count(tele.C_HEALTH_PROBATION, 1)
    report = analyze(tr.snapshot())
    h = report["health"]
    assert h["devices"]["cpu:1"]["state"] == health_mod.PROBATION
    assert h["audit_mismatch"] == 2 and h["hedge_fired"] == 3
    text = render_report(report)
    assert "Device health" in text
    assert "cpu:1: probation" in text
    assert "3 fired" in text and "2 mismatch(es)" in text
    assert "silent data corruption" in text
