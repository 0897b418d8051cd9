"""Retry-with-backoff and deadline wrappers for device RPC call sites.

Device dispatch and fetch can fail transiently (a runtime status such
as ``UNAVAILABLE``) or hang.  Before this module, the streamed pipeline
had exactly one ``except`` on those paths (the prewarm's
warn-and-degrade) — any transient dispatch error or hung fetch killed a
whole multi-window run.  Two primitives fix that:

* :func:`retry_call` — run a callable, retrying **retryable** failures
  with exponential backoff.  Retryable means: injected
  :class:`~adam_tpu.utils.faults.TransientFault`, a
  :class:`DeadlineExceeded` fetch timeout, connection-layer ``OSError``
  subclasses, and jax's ``XlaRuntimeError`` with a transient status.  Injected ``PermanentFault`` and
  everything else (a real bug would be "everything else") re-raise on
  first sight — retrying a deterministic error just triples its latency.
  Every retry counts ``retry.attempts`` on the global tracer.
* :func:`call_with_deadline` — run a callable on a watchdog thread and
  raise :class:`DeadlineExceeded` (retryable) if it exceeds a deadline,
  so a hung fetch RPC becomes a bounded, retryable timeout instead of a
  wedged run.  The abandoned thread is a daemon: it cannot block
  process exit, and its late result is discarded.

Policy knobs (all tolerantly parsed — an env typo degrades to the
default with a warning, the house rule for every ``ADAM_TPU_*`` var):

* ``ADAM_TPU_RETRY_ATTEMPTS`` — total tries per call (default 3).
* ``ADAM_TPU_RETRY_BACKOFF_S`` — first backoff sleep (default 0.05 s,
  doubling per retry).
* ``ADAM_TPU_RETRY_MAX_BACKOFF_S`` — backoff ceiling (default 2 s).
* ``ADAM_TPU_RETRY_JITTER`` — optional backoff jitter fraction
  (default 0 = off) with ``ADAM_TPU_RETRY_JITTER_SEED`` (default 0):
  each retry sleep stretches by up to this fraction, derived
  **deterministically** from (seed, site, attempt) via
  :func:`jitter_factor`.

The default backoff is jitter-free: the recovery paths must be
reproducible under the fault-injection matrix, and the call sites are
per-window (tens per run), not contended.  The jitter knob exists for
the multi-job service (``adam_tpu/serve``): N quarantine-retrying jobs
sharing one device pool would otherwise back off in lock-step and
re-collide on every retry wave.  Because the jitter is a pure function
of (seed, site, attempt) — no RNG state, no wall clock — a jittered
run is still bit-reproducible end to end: only sleep durations change,
never the retry decisions or the computed bytes.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
import time
from typing import Callable, Optional

from adam_tpu.utils.faults import PermanentFault, TransientFault

log = logging.getLogger(__name__)


class DeadlineExceeded(TimeoutError):
    """A watchdogged call outlived its deadline (retryable)."""


def env_float(name: str, default: float) -> float:
    """Tolerantly parsed float env var (warn + default on a typo — the
    house rule for every ``ADAM_TPU_*`` tuning var); shared with the
    transfer layer's fetch-deadline knob."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        log.warning("%s=%r is not a float; using default %s", name, raw,
                    default)
        return default


def env_toggle(name: str, default: bool) -> bool:
    """Tolerantly parsed boolean env toggle — THE shared parser for
    ``ADAM_TPU_*`` on/off knobs (packed columns, writer adaptivity, …):
    ``auto``/unset -> ``default``; ``1/on/true`` and ``0/off/false``
    force; anything else warns (naming the full accepted set) and keeps
    the default."""
    raw = os.environ.get(name, "").strip().lower()
    if raw in ("", "auto"):
        return default
    if raw in ("1", "on", "true"):
        return True
    if raw in ("0", "off", "false"):
        return False
    log.warning(
        "%s=%r is not one of (auto, 0/off/false, 1/on/true); using the "
        "default", name, raw,
    )
    return default


def _env_seed(name: str, default: int) -> int:
    """Any-int env var (seeds may legitimately be 0 or negative)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        log.warning("%s=%r is not an int; using default %s", name, raw,
                    default)
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        v = int(raw)
        return v if v >= 1 else default
    except ValueError:
        log.warning("%s=%r is not a positive int; using default %s", name,
                    raw, default)
        return default


def jitter_factor(site: str, attempt: int, *, seed: int = 0,
                  amount: float = 0.0) -> float:
    """Deterministic backoff stretch for one (site, attempt) pair.

    Returns a multiplier in ``[1, 1 + amount)`` derived from a sha256 of
    ``seed:site:attempt`` — a pure function, so a fixed seed reproduces
    the exact sleep schedule run after run (the recovery-path
    bit-reproducibility contract survives), while different sites (and
    different seeds, e.g. one per job in the multi-job service)
    decorrelate so concurrent retry waves don't re-collide in
    lock-step.  ``amount=0`` (the default) is exactly 1.0 — the
    jitter-free documented behavior."""
    if amount <= 0:
        return 1.0
    digest = hashlib.sha256(
        f"{seed}:{site}:{attempt}".encode()
    ).digest()
    unit = int.from_bytes(digest[:8], "big") / float(1 << 64)
    return 1.0 + amount * unit


# ---------------------------------------------------------------------------
# Drain-aware backoff: a process-wide cancellation event retry sleeps
# wait on.  Before this, an `adam-tpu serve` SIGTERM drain could stall
# up to ADAM_TPU_RETRY_MAX_BACKOFF_S per in-flight retry — each backoff
# was a blind time.sleep.  The multi-job scheduler registers its drain
# event here (serve/scheduler.py); when it fires, every sleeping retry
# wakes immediately and runs its REMAINING attempts with only a small
# bounded pause (_DRAIN_RETRY_PAUSE_S) between them.  Only the long
# exponential sleeps stall a drain — the attempts themselves are cheap,
# and keeping them preserves failure semantics: a one-off transient
# that arrives during a drain still absorbs (the window completes and
# the job stops cleanly at its boundary), instead of surfacing as a
# device failure that would spuriously evict a healthy chip on the
# process-wide health scoreboard (utils/health.py — mark_evicted is
# terminal).  docs/ROBUSTNESS.md "Fault-isolated multi-job scheduling".
# ---------------------------------------------------------------------------
_CANCEL_EVENT: Optional[threading.Event] = None
_CANCEL_LOCK = threading.Lock()
#: Pause between attempts once the cancel event fired: long enough for
#: a short transient to clear across the remaining attempts, bounded so
#: a drain never stalls more than attempts x this per in-flight retry.
_DRAIN_RETRY_PAUSE_S = 0.05


def set_cancel_event(event: Optional[threading.Event]) -> None:
    """Install (or, with None, remove) the process-wide retry-sleep
    cancellation event.  Idempotent; the scheduler owns its lifetime."""
    global _CANCEL_EVENT
    with _CANCEL_LOCK:
        _CANCEL_EVENT = event


def clear_cancel_event(event: Optional[threading.Event] = None) -> None:
    """Remove the installed cancellation event — but only when it is
    still ``event`` (or unconditionally with None): two schedulers in
    one process must not clear each other's registration."""
    global _CANCEL_EVENT
    with _CANCEL_LOCK:
        if event is None or _CANCEL_EVENT is event:
            _CANCEL_EVENT = None


def cancel_event() -> Optional[threading.Event]:
    with _CANCEL_LOCK:
        return _CANCEL_EVENT


class RetryPolicy:
    """Attempt/backoff tuning for one family of call sites."""

    __slots__ = ("attempts", "backoff_s", "max_backoff_s", "jitter",
                 "jitter_seed")

    def __init__(self, attempts: int = 3, backoff_s: float = 0.05,
                 max_backoff_s: float = 2.0, jitter: float = 0.0,
                 jitter_seed: int = 0):
        self.attempts = max(1, attempts)
        self.backoff_s = max(0.0, backoff_s)
        self.max_backoff_s = max(0.0, max_backoff_s)
        self.jitter = max(0.0, jitter)
        self.jitter_seed = jitter_seed

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            attempts=_env_int("ADAM_TPU_RETRY_ATTEMPTS", 3),
            backoff_s=env_float("ADAM_TPU_RETRY_BACKOFF_S", 0.05),
            max_backoff_s=env_float("ADAM_TPU_RETRY_MAX_BACKOFF_S", 2.0),
            jitter=env_float("ADAM_TPU_RETRY_JITTER", 0.0),
            jitter_seed=_env_seed("ADAM_TPU_RETRY_JITTER_SEED", 0),
        )


#: XLA status prefixes that mark a *transient* runtime failure.  Deterministic statuses — RESOURCE_EXHAUSTED
#: (a window that OOMs on one chip OOMs on every chip), INVALID_ARGUMENT,
#: NOT_FOUND — must NOT retry: retrying them only multiplies the latency
#: of the eviction/host-fallback path that actually resolves them.
_TRANSIENT_XLA_STATUSES = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED", "ABORTED",
    "UNKNOWN", "INTERNAL",
)


def is_retryable(exc: BaseException) -> bool:
    """Default transient/permanent classification (module docstring)."""
    if isinstance(exc, PermanentFault):
        return False
    if isinstance(exc, (TransientFault, DeadlineExceeded, ConnectionError)):
        return True
    # jaxlib's XlaRuntimeError (matched by name so a CPU-only host
    # never imports jaxlib for this), but only its transient statuses — the status code leads
    # the message ("UNAVAILABLE: connection reset ...")
    if type(exc).__name__ == "XlaRuntimeError":
        msg = str(exc).lstrip()
        return msg.startswith(_TRANSIENT_XLA_STATUSES)
    return False


def retry_call(
    fn: Callable,
    *,
    site: str,
    policy: Optional[RetryPolicy] = None,
    retryable: Callable[[BaseException], bool] = is_retryable,
    cancel: Optional[threading.Event] = None,
):
    """Call ``fn()``; retry retryable failures with exponential backoff.

    Raises the last failure when the attempt budget is exhausted — the
    caller (the device-eviction path, usually) decides what a spent
    budget means.  ``site`` labels the log lines and groups nothing
    else; the ``retry.attempts`` counter is global.

    Backoff sleeps are **drain-aware**: they wait on ``cancel`` (or the
    process-wide event installed via :func:`set_cancel_event`) instead
    of sleeping blind, and a set event collapses this and every
    remaining backoff sleep to a small bounded pause — a graceful
    drain never waits out an exponential backoff.  The attempt budget
    is untouched, so a transient that would have been absorbed still
    absorbs and no spurious device failure surfaces mid-drain.
    """
    if policy is None:
        policy = RetryPolicy.from_env()
    backoff = policy.backoff_s
    attempt = 1
    while True:
        try:
            return fn()
        except BaseException as e:
            if attempt >= policy.attempts or not retryable(e):
                if attempt >= policy.attempts and retryable(e):
                    # the budget was genuinely spent on retryable
                    # failures (a permanent error on attempt 1 is NOT
                    # an incident — it never consumed the budget):
                    # snapshot the evidence before the eviction path
                    # the caller runs next churns the ring
                    from adam_tpu.utils import incidents

                    incidents.maybe_record(
                        "retry.exhausted",
                        reason="site=%s attempts=%d last=%s"
                               % (site, attempt, e),
                    )
                raise
            from adam_tpu.utils import telemetry as tele

            tele.TRACE.count(tele.C_RETRY_ATTEMPTS)
            # deterministic per-site jitter (off by default): stretches
            # the SLEEP only — attempt counts and outcomes are
            # untouched, so recovery stays bit-reproducible
            sleep_s = backoff * jitter_factor(
                site, attempt, seed=policy.jitter_seed,
                amount=policy.jitter,
            )
            log.warning(
                "%s failed (attempt %d/%d): %s — retrying in %.3fs",
                site, attempt, policy.attempts, e, sleep_s,
            )
            if sleep_s > 0:
                ev = cancel if cancel is not None else cancel_event()
                if ev is not None:
                    if ev.wait(sleep_s):
                        # a drain fired mid-wait (or was already set):
                        # keep a SMALL bounded pause between the
                        # remaining attempts — zero-delay retries would
                        # burn the whole budget in microseconds and turn
                        # a clears-in-100ms transient into a spurious
                        # device failure; attempts x 50ms can never
                        # stall the drain
                        time.sleep(min(sleep_s, _DRAIN_RETRY_PAUSE_S))
                else:
                    time.sleep(sleep_s)
            backoff = min(backoff * 2, policy.max_backoff_s)
            attempt += 1


def call_with_deadline(fn: Callable, timeout_s: float, *, site: str):
    """Run ``fn()`` on a watchdog daemon thread with a deadline.

    Returns ``fn``'s result, re-raises its exception, or raises
    :class:`DeadlineExceeded` after ``timeout_s`` — in which case the
    worker thread is abandoned (daemonized, so it can't pin process
    exit) and whatever it eventually produces is discarded.  A thread
    per call is deliberate: the deadline wraps per-window device
    fetches (tens per run), and a shared pool would let one hung RPC
    starve the watchdog for every later fetch.
    """
    if not timeout_s or timeout_s <= 0:
        return fn()
    box: list = []

    def run():
        try:
            box.append((True, fn()))
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box.append((False, e))

    t = threading.Thread(target=run, daemon=True,
                         name=f"deadline:{site}")
    t.start()
    t.join(timeout_s)
    if not box:
        raise DeadlineExceeded(
            f"{site} exceeded its {timeout_s:.1f}s deadline (hung RPC?)"
        )
    ok, val = box[0]
    if ok:
        return val
    raise val
