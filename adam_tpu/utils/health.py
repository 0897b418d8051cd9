"""Per-device health scoreboard: healthy → suspect → probation → evicted.

The fault stack so far (faults / retry / eviction / replay) handles
devices that fail LOUDLY — exceptions retry, hung fetches time out,
dead chips evict and replay.  This module defends against devices that
misbehave QUIETLY, the two failure shapes fleet experience says are
real at scale:

* **stragglers** (Dean & Barroso, "The Tail at Scale") — a chip that
  stretches every window to p99×10 without ever erroring.  Hedged
  dispatch (``ADAM_TPU_HEDGE_FACTOR``; wired in pipelines/streamed.py)
  speculatively re-runs an overdue window on another device, and the
  scoreboard demotes the chip whose latency EWMA stays degraded.
* **silent data corruptors** (Dixit et al., "Silent Data Corruptions
  at Scale") — a chip that returns bit-flipped results that would
  otherwise publish as corrupt Parquet.  The SDC audit
  (``ADAM_TPU_AUDIT_RATE``) dual-computes a deterministic sample of
  windows on the host parity twin and bit-compares; a mismatch
  quarantines the device here and the window replays from the host
  copy, so the published part is clean.

The scoreboard is a decaying penalty score per device, fed by the
signals the pipeline already records:

=================  ======  ==========================================
signal             weight  source
=================  ======  ==========================================
retry              0.5     transient dispatch/fetch failures absorbed
                           by the backoff wrappers (utils/transfer
                           feeds the device-attributed fetch retries)
timeout            1.5     ``DeadlineExceeded`` fetch watchdog trips
latency breach     1.0     a dispatch+fetch wall above
                           ``ADAM_TPU_HEALTH_LATENCY_FACTOR`` × the
                           kernel's pooled p99 (the per-kernel
                           histogram machinery telemetry already uses),
                           or a per-(kernel, device) EWMA that stays
                           above it
audit mismatch     —       straight to **probation** (quarantine):
                           wrong bits are never a score debate
=================  ======  ==========================================

State machine (score thresholds, exponential decay with half-life
``ADAM_TPU_HEALTH_DECAY_S``):

* ``healthy`` → ``suspect`` at score ≥ ``ADAM_TPU_HEALTH_SUSPECT``
  (still placeable — an early warning, visible in the health section);
* ``suspect`` → ``probation`` at score ≥ ``ADAM_TPU_HEALTH_PROBATION``
  (or immediately via :meth:`HealthBoard.quarantine`): the device is
  **excluded from placement** (``DevicePool.alive_devices`` filters it,
  mesh construction skips it, scheduler leases never see it) but NOT
  evicted — its jit executables stay warm;
* ``probation`` → ``healthy`` after the ``ADAM_TPU_HEALTH_COOLDOWN_S``
  cooldown **and** a passing re-admission probe — a prewarmed
  known-answer dispatch (:func:`probe_known_answer`) whose result must
  come back bit-exact;
* ``probation`` → ``evicted`` when the probe fails: the chip is dead
  hardware, handed to the normal ``DevicePool.evict`` path.

Availability beats health: the filter never empties the placeable set —
when every survivor is blocked the pool serves them anyway (the audit
still keeps published bytes clean), and the LAST device is never
health-blocked.

One process-wide board (:data:`BOARD`, the ``TRACE`` pattern) spans
runs and jobs: a chip that corrupted tenant A's window must not serve
tenant B five seconds later.  All knobs follow the tolerant
``ADAM_TPU_*`` parsing contract.  Reference: docs/ROBUSTNESS.md
"Device health, hedging, and SDC audit".
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from typing import Optional

from adam_tpu.utils import telemetry as tele
from adam_tpu.utils.retry import env_float

log = logging.getLogger(__name__)

HEALTHY = "healthy"
SUSPECT = "suspect"
PROBATION = "probation"
EVICTED = "evicted"

#: Signal weights (module docstring table).
W_RETRY = 0.5
W_TIMEOUT = 1.5
W_LATENCY = 1.0

_DEF_SUSPECT = 3.0
_DEF_PROBATION = 6.0
_DEF_DECAY_S = 30.0
_DEF_COOLDOWN_S = 30.0
_DEF_LATENCY_FACTOR = 4.0
#: Pooled-histogram sample floor before latency judgments fire (a p99
#: over 3 samples is noise) and before a hedge threshold exists.
#: ``ADAM_TPU_HEDGE_MIN_SAMPLES`` overrides (short runs on slow media
#: may want a warmer trigger; the tolerant-parsing contract applies).
MIN_LATENCY_SAMPLES = 8


def min_latency_samples() -> int:
    from adam_tpu.utils.retry import _env_int

    return _env_int("ADAM_TPU_HEDGE_MIN_SAMPLES", MIN_LATENCY_SAMPLES)
#: Hedge threshold floor (seconds): never hedge on sub-noise walls
#: even when the observed p99 is tiny (virtual CPU devices fetch in
#: microseconds — factor × p99 alone would hedge every window).
_DEF_HEDGE_MIN_S = 0.05
#: EWMA smoothing for the per-(kernel, device) dispatch latency.
_EWMA_ALPHA = 0.25


def device_key(device) -> str:
    """Stable scoreboard key for a device: the ``platform:id`` form
    ``parallel/device_pool._device_key`` uses (one vocabulary across
    the prewarm cache, eviction set and this board); strings pass
    through (test fixtures, ``"mesh"``/``"default"`` attributions)."""
    if device is None:
        return "default"
    if isinstance(device, str):
        return device
    return f"{getattr(device, 'platform', '?')}:{getattr(device, 'id', id(device))}"


def hedge_factor() -> float:
    """``ADAM_TPU_HEDGE_FACTOR`` (default 0 = hedging off): hedge when
    an in-flight window's dispatch+fetch wall exceeds this multiple of
    the kernel's observed p99."""
    v = env_float("ADAM_TPU_HEDGE_FACTOR", 0.0)
    return v if v > 0 else 0.0


def audit_rate() -> float:
    """``ADAM_TPU_AUDIT_RATE`` (default 0 = audit off), clamped to
    [0, 1]: the fraction of windows deterministically sampled for
    dual-compute bit comparison."""
    v = env_float("ADAM_TPU_AUDIT_RATE", 0.0)
    return min(max(v, 0.0), 1.0)


def audit_due(window: int, rate: Optional[float] = None,
              seed: Optional[int] = None) -> bool:
    """Whether window ``window`` is audited — a pure function of
    (seed, window index), NOT of placement, arrival order or wall
    clock, so a ``--resume`` re-audits exactly the windows the killed
    run would have audited (the window plan is fingerprint-stable,
    docs/ROBUSTNESS.md "Durable window-granular resume")."""
    r = audit_rate() if rate is None else rate
    if r <= 0:
        return False
    if r >= 1:
        return True
    if seed is None:
        from adam_tpu.utils.retry import _env_seed

        seed = _env_seed("ADAM_TPU_AUDIT_SEED", 0)
    digest = hashlib.sha256(f"{seed}:{int(window)}".encode()).digest()
    unit = int.from_bytes(digest[:8], "big") / float(1 << 64)
    return unit < r


class _Device:
    __slots__ = ("score", "state", "t_score", "since", "probes",
                 "signals", "reason", "ewma")

    def __init__(self, now: float):
        self.score = 0.0
        self.state = HEALTHY
        self.t_score = now
        self.since = now
        self.probes = 0
        self.signals = {"retry": 0, "timeout": 0, "latency": 0,
                        "mismatch": 0}
        self.reason = ""
        self.ewma: dict = {}  # kernel -> EWMA seconds


class HealthBoard:
    """The per-device health scoreboard (module docstring)."""

    def __init__(self, clock=time.monotonic,
                 suspect_score: Optional[float] = None,
                 probation_score: Optional[float] = None,
                 decay_halflife_s: Optional[float] = None,
                 cooldown_s: Optional[float] = None,
                 latency_factor: Optional[float] = None):
        self._clock = clock
        self.suspect_score = (
            suspect_score if suspect_score is not None
            else env_float("ADAM_TPU_HEALTH_SUSPECT", _DEF_SUSPECT)
        )
        self.probation_score = (
            probation_score if probation_score is not None
            else env_float("ADAM_TPU_HEALTH_PROBATION", _DEF_PROBATION)
        )
        self.decay_halflife_s = max(1e-3, (
            decay_halflife_s if decay_halflife_s is not None
            else env_float("ADAM_TPU_HEALTH_DECAY_S", _DEF_DECAY_S)
        ))
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None
            else env_float("ADAM_TPU_HEALTH_COOLDOWN_S", _DEF_COOLDOWN_S)
        )
        self.latency_factor = (
            latency_factor if latency_factor is not None
            else env_float("ADAM_TPU_HEALTH_LATENCY_FACTOR",
                           _DEF_LATENCY_FACTOR)
        )
        self._lock = threading.Lock()
        self._dev: dict[str, _Device] = {}
        # state transitions staged under the lock, fired as
        # "health.transition" incident bundles AFTER release (the
        # bundle snapshots this very board via status(), which takes
        # the lock — firing inline would deadlock)
        self._pending_incidents: list = []
        # per-kernel pooled latency histogram (telemetry's fixed
        # log-spaced buckets, so the p99 math is the shared machinery)
        self._lat: dict[str, dict] = {}
        #: lock-free fast-path gate for the probe hook: the earliest
        #: monotonic time any probation device becomes probe-due
        #: (float read is GIL-atomic; inf = nothing to probe)
        self.next_probe_due = float("inf")

    # ---- internals (caller holds the lock) -----------------------------
    def _slot_locked(self, key: str) -> _Device:
        d = self._dev.get(key)
        if d is None:
            d = self._dev[key] = _Device(self._clock())
        return d

    def _decay_locked(self, d: _Device, now: float) -> None:
        dt = max(0.0, now - d.t_score)
        if dt > 0 and d.score > 0:
            d.score *= 0.5 ** (dt / self.decay_halflife_s)
            if d.score < 1e-6:
                d.score = 0.0
        d.t_score = now
        # decay can walk a suspect back to healthy; probation/evicted
        # only leave through the probe path
        if d.state == SUSPECT and d.score < 0.5 * self.suspect_score:
            d.state = HEALTHY
            d.since = now

    def _penalize_locked(self, key: str, weight: float, signal: str,
                         reason: str, tracer) -> None:
        now = self._clock()
        d = self._slot_locked(key)
        self._decay_locked(d, now)
        d.score += weight
        d.signals[signal] = d.signals.get(signal, 0) + 1
        if d.state in (PROBATION, EVICTED):
            return
        if d.score >= self.probation_score:
            self._enter_probation_locked(key, d, now, reason, tracer)
        elif d.score >= self.suspect_score and d.state == HEALTHY:
            d.state = SUSPECT
            d.since = now
            d.reason = reason
            tracer.count(tele.C_HEALTH_DEMOTED)
            tracer.record_health(key, SUSPECT, d.score, reason)
            self._pending_incidents.append((key, SUSPECT, reason, tracer))
            log.warning(
                "device %s health: healthy -> suspect (score %.1f, %s)",
                key, d.score, reason,
            )

    def _enter_probation_locked(self, key: str, d: _Device, now: float,
                                reason: str, tracer) -> None:
        d.state = PROBATION
        d.since = now
        d.reason = reason
        self.next_probe_due = min(
            self.next_probe_due, now + self.cooldown_s
        )
        tracer.count(tele.C_HEALTH_PROBATION)
        tracer.record_health(key, PROBATION, d.score, reason)
        self._pending_incidents.append((key, PROBATION, reason, tracer))
        log.error(
            "device %s health: PROBATION (score %.1f, %s) — excluded "
            "from placement; re-admission probe after %.0fs cooldown",
            key, d.score, reason, self.cooldown_s,
        )

    def _flush_incidents(self) -> None:
        """Fire the staged ``health.transition`` incident bundles.
        Called by every public feed AFTER its lock release — the bundle
        writer snapshots this board (``status()`` takes the lock) and
        must never run under it.  Best-effort like all recording."""
        with self._lock:
            if not self._pending_incidents:
                return
            pending = self._pending_incidents
            self._pending_incidents = []
        from adam_tpu.utils import incidents

        for key, state, reason, tracer in pending:
            incidents.maybe_record(
                "health.transition", device=key, tracer=tracer,
                reason=f"device {key} -> {state}: {reason}",
            )

    # ---- signal feeds --------------------------------------------------
    def note_retry(self, device, site: str = "", tracer=None) -> None:
        """A transient, retried failure attributed to ``device`` (the
        backoff wrappers absorb it; the board remembers it)."""
        with self._lock:
            self._penalize_locked(
                device_key(device), W_RETRY, "retry",
                f"retried failure at {site or 'device rpc'}",
                tracer if tracer is not None else tele.TRACE,
            )
        self._flush_incidents()

    def note_timeout(self, device, site: str = "", tracer=None) -> None:
        """A fetch-deadline watchdog trip attributed to ``device``."""
        with self._lock:
            self._penalize_locked(
                device_key(device), W_TIMEOUT, "timeout",
                f"deadline exceeded at {site or 'device.fetch'}",
                tracer if tracer is not None else tele.TRACE,
            )
        self._flush_incidents()

    def observe_latency(self, kernel: str, device, seconds: float,
                        tracer=None) -> None:
        """One window's dispatch+fetch wall on ``device`` for
        ``kernel``: feeds the pooled per-kernel histogram (the hedge
        threshold's p99) and the per-(kernel, device) EWMA; a wall — or
        an EWMA — above ``latency_factor`` × pooled p99 penalizes the
        device as a straggler."""
        s = float(seconds)
        key = device_key(device)
        with self._lock:
            h = self._lat.get(kernel)
            if h is None:
                h = self._lat[kernel] = tele._new_hist()
            d = self._slot_locked(key)
            prev = d.ewma.get(kernel)
            ew = s if prev is None else (
                _EWMA_ALPHA * s + (1 - _EWMA_ALPHA) * prev
            )
            d.ewma[kernel] = ew
            breach = None
            pool_sample = True
            if h["count"] >= min_latency_samples():
                p99 = tele._hist_quantile(h, 0.99) or 0.0
                bound = self.latency_factor * p99
                if bound > 0 and s > bound:
                    # the breached observation does NOT enter the
                    # pooled histogram: a straggler must not drag the
                    # fleet's p99 up until its own tail reads as normal
                    breach = "pooled p99"
                    pool_sample = False
                elif bound > 0 and ew > bound and (
                    prev is None or prev <= bound
                ):
                    # the EWMA crossed INTO excursion without the
                    # sample itself breaching: charge once at the
                    # crossing, never on the decay tail — one transient
                    # blip must not bill the ~log(ew/bound)/log(1-a)
                    # healthy windows it takes the average to recover
                    # (sustained stragglers keep charging through the
                    # per-sample branch above)
                    breach = "pooled p99"
                if breach is None:
                    # cross-device check: a chip slow from its FIRST
                    # window contaminates the pooled p99 it is judged
                    # against (half the warmup samples on a 2-device
                    # pool sit in its own tail), so it can never breach
                    # the pooled bound — but its peers' EWMAs it cannot
                    # touch.  A sample AND EWMA both above
                    # latency_factor x the best peer's EWMA for the
                    # same kernel is a straggler no matter what it did
                    # to the pool (single-device pools and collective
                    # attributions have no peers: no-op).
                    peer = min(
                        (
                            o.ewma[kernel]
                            for ok, o in self._dev.items()
                            if ok != key and kernel in o.ewma
                        ),
                        default=0.0,
                    )
                    rel = self.latency_factor * peer
                    if rel > 0 and s > rel and ew > rel:
                        breach = "best peer EWMA"
                        pool_sample = False
            if pool_sample:
                tele._hist_observe(h, s)
            if breach:
                self._penalize_locked(
                    key, W_LATENCY, "latency",
                    f"{kernel} wall {s * 1e3:.1f}ms above "
                    f"{self.latency_factor:g}x {breach}",
                    tracer if tracer is not None else tele.TRACE,
                )
        self._flush_incidents()

    def note_hedge_lost(self, device, kernel: str = "", tracer=None) -> None:
        """``device`` lost a hedge race: its window re-dispatched COLD
        on a peer (host re-ship + dispatch + fetch) and the peer still
        finished first.  This is the strongest straggler evidence there
        is — and the only latency signal available for a primary that
        never finished (its true wall is unknowable, only "longer than
        the whole race"; ``observe_latency`` has nothing true to
        record).  Weighted like a latency breach, so a chip slow enough
        to keep losing hedges walks to probation without ever
        erroring — hedging rescues its windows, the scoreboard retires
        the chip."""
        with self._lock:
            self._penalize_locked(
                device_key(device), W_LATENCY, "latency",
                f"lost hedge race on {kernel or 'dispatch'}",
                tracer if tracer is not None else tele.TRACE,
            )
        self._flush_incidents()

    def quarantine(self, device, reason: str = "", tracer=None) -> None:
        """Straight to probation — the SDC audit's verdict (wrong bits
        are never a score debate), also the mesh-degradation hook."""
        key = device_key(device)
        with self._lock:
            now = self._clock()
            d = self._slot_locked(key)
            d.signals["mismatch"] = d.signals.get("mismatch", 0) + 1
            if d.state in (PROBATION, EVICTED):
                return
            d.score = max(d.score, self.probation_score)
            d.t_score = now
            self._enter_probation_locked(
                key, d, now, reason or "quarantined",
                tracer if tracer is not None else tele.TRACE,
            )
        self._flush_incidents()

    def mark_evicted(self, device, tracer=None) -> None:
        """The pool evicted this chip (spent retry budget or failed
        probe): terminal state, never placeable again."""
        key = device_key(device)
        with self._lock:
            d = self._slot_locked(key)
            if d.state == EVICTED:
                return
            d.state = EVICTED
            d.since = self._clock()
            tr = tracer if tracer is not None else tele.TRACE
            tr.record_health(key, EVICTED, d.score, d.reason)
            self._pending_incidents.append(
                (key, EVICTED, d.reason or "evicted by the pool", tr)
            )
        self._flush_incidents()

    # ---- placement queries --------------------------------------------
    def state(self, device) -> str:
        with self._lock:
            d = self._dev.get(device_key(device))
            if d is None:
                return HEALTHY
            self._decay_locked(d, self._clock())
            return d.state

    def blocked(self, device) -> bool:
        """True when ``device`` must be excluded from placement
        (probation or evicted).  Cheap miss path: unknown devices are
        healthy without allocating a slot."""
        with self._lock:
            d = self._dev.get(device_key(device))
            if d is None or d.state in (HEALTHY, SUSPECT):
                return False
            return True

    def hedge_threshold(self, kernel: str) -> Optional[float]:
        """Seconds after which an in-flight ``kernel`` window should be
        hedged: ``ADAM_TPU_HEDGE_FACTOR`` × the kernel's pooled p99,
        floored at ``ADAM_TPU_HEDGE_MIN_S``.  None while hedging is off
        or fewer than :data:`MIN_LATENCY_SAMPLES` walls are pooled (a
        cold p99 is noise — never hedge on it)."""
        factor = hedge_factor()
        if factor <= 0:
            return None
        with self._lock:
            h = self._lat.get(kernel)
            if h is None or h["count"] < min_latency_samples():
                return None
            p99 = tele._hist_quantile(h, 0.99)
        if not p99:
            return None
        return max(
            factor * p99, env_float("ADAM_TPU_HEDGE_MIN_S",
                                    _DEF_HEDGE_MIN_S),
        )

    # ---- probation cooldown + re-admission probe -----------------------
    def probe_maybe_due(self) -> bool:
        """Lock-free fast-path gate for the per-window placement call:
        False when no probation device can possibly be probe-due (the
        overwhelmingly common case), so callers skip building their
        candidate set entirely.  One clock read against one
        GIL-atomic float."""
        return self._clock() >= self.next_probe_due

    def due_probes(self, candidates=None) -> list:
        """Probation device keys whose cooldown has elapsed.  Each
        returned key's cooldown restarts immediately, so a failing (or
        crashed) probe cannot hot-loop; callers run the probe and call
        :meth:`readmit` or :meth:`probe_failed`.

        ``candidates`` (devices or keys) restricts the claim to devices
        the caller can actually probe: a pool must not consume — and
        restart the cooldown of — another pool's device's due-ness,
        or a multi-pool process would postpone that device's
        re-admission forever without ever running its probe.  A
        not-claimed due device keeps its elapsed cooldown (the board
        stays probe-ready for whoever CAN reach it)."""
        now = self._clock()
        if now < self.next_probe_due:
            return []
        cand = (
            None if candidates is None
            else {device_key(c) for c in candidates}
        )
        due = []
        with self._lock:
            nxt = float("inf")
            for key, d in self._dev.items():
                if d.state != PROBATION:
                    continue
                if (cand is None or key in cand) and (
                    now - d.since >= self.cooldown_s
                ):
                    due.append(key)
                    d.since = now
                    d.probes += 1
                nxt = min(nxt, d.since + self.cooldown_s)
            self.next_probe_due = nxt
        return due

    def readmit(self, device, tracer=None) -> None:
        """A probation device passed its known-answer probe: score
        resets and it rejoins the placeable pool."""
        key = device_key(device)
        tr = tracer if tracer is not None else tele.TRACE
        with self._lock:
            d = self._dev.get(key)
            if d is None or d.state != PROBATION:
                return
            d.state = HEALTHY
            d.score = 0.0
            d.since = self._clock()
            d.t_score = d.since
            d.reason = ""
            tr.count(tele.C_HEALTH_READMITTED)
            tr.record_health(key, HEALTHY, 0.0, "probe passed")
        log.warning(
            "device %s health: re-admission probe passed — rejoining "
            "the pool", key,
        )

    def probe_failed(self, device, tracer=None) -> None:
        """The re-admission probe returned wrong bits or raised: the
        chip graduates from probation to evicted (the caller routes it
        through ``DevicePool.evict`` so replay bookkeeping engages)."""
        key = device_key(device)
        tr = tracer if tracer is not None else tele.TRACE
        with self._lock:
            d = self._slot_locked(key)
            d.state = EVICTED
            d.since = self._clock()
            tr.count(tele.C_HEALTH_PROBE_FAILED)
            tr.record_health(key, EVICTED, d.score,
                             "re-admission probe failed")
            self._pending_incidents.append(
                (key, EVICTED, "re-admission probe failed", tr)
            )
        self._flush_incidents()
        log.error(
            "device %s health: re-admission probe FAILED — evicting",
            key,
        )

    # ---- reporting -----------------------------------------------------
    def states(self) -> dict:
        """``{device key: state}`` for every tracked device (the
        heartbeat's ``device_health`` field; {} when nothing tracked)."""
        with self._lock:
            now = self._clock()
            out = {}
            for key, d in self._dev.items():
                self._decay_locked(d, now)
                out[key] = d.state
            return out

    def status(self) -> dict:
        """Full per-device view (scheduler status / debugging)."""
        with self._lock:
            now = self._clock()
            out = {}
            for key, d in sorted(self._dev.items()):
                self._decay_locked(d, now)
                out[key] = {
                    "state": d.state,
                    "score": round(d.score, 3),
                    "signals": dict(d.signals),
                    "probes": d.probes,
                    "reason": d.reason,
                }
            return out

    def publish(self, tracer) -> None:
        """Record every tracked device's current state into ``tracer``'s
        health ledger (the run-end snapshot the analyzer's "Device
        health" section renders).  ``transition=False``: publishing a
        state the board already held is not movement — only live
        transition events count, or a serve process would inflate the
        count by one per job publish."""
        for key, row in self.status().items():
            tracer.record_health(key, row["state"], row["score"],
                                 row["reason"], transition=False)

    def reset(self) -> None:
        """Test hook: forget every device and latency pool."""
        with self._lock:
            self._dev.clear()
            self._lat.clear()
            self._pending_incidents.clear()
            self.next_probe_due = float("inf")


#: The process-wide board (the ``telemetry.TRACE`` pattern): health is
#: a property of the HARDWARE, so it must span runs, jobs and tenants.
BOARD = HealthBoard()


def reset_board() -> None:
    """Test hook: clear the process-wide board."""
    BOARD.reset()


# ---------------------------------------------------------------------------
# Known-answer re-admission probe
# ---------------------------------------------------------------------------
_PROBE_JIT = None
_PROBE_ARGS = None


def probe_product(x, y):
    """The probe's device program: an int32 matmul.  Its operands stay
    below 127 over 64 terms, so int32 holds the exact product; a 64-bit
    dot does not compile for a TPU."""
    return x @ y


def probe_known_answer(device) -> bool:
    """The re-admission probe: one small **integer** matmul dispatched
    on ``device`` whose result must come back bit-exact against the
    host numpy product (int32 accumulation is exact on every backend —
    no float tolerance to hide a flipped mantissa bit behind).  The jit
    executable compiles once per process and is prewarmed by the first
    probe; the fetch rides ``transfer.device_fetch`` (deadline watchdog
    + retry), so a hung probation chip reads as a failed probe, not a
    wedged pool.  Returns False on ANY failure — a probe must never
    escalate."""
    global _PROBE_JIT, _PROBE_ARGS
    try:
        import jax
        import numpy as np

        from adam_tpu.utils.transfer import device_fetch

        if _PROBE_ARGS is None:
            rng = np.random.default_rng(0xADA)
            _PROBE_ARGS = (
                rng.integers(0, 127, size=(64, 64), dtype=np.int32),
                rng.integers(0, 127, size=(64, 64), dtype=np.int32),
            )
        a, b = _PROBE_ARGS
        expect = a.astype(np.int64) @ b.astype(np.int64)
        if _PROBE_JIT is None:
            _PROBE_JIT = jax.jit(probe_product)
        da = jax.device_put(a, device)
        db = jax.device_put(b, device)
        got = device_fetch(_PROBE_JIT(da, db))
        return bool(np.array_equal(np.asarray(got), expect))
    except Exception as e:
        log.warning("known-answer probe failed to run: %s", e)
        return False
