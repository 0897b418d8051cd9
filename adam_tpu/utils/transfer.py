"""Device->host transfer helpers.

A large array is fetched as several row slices on a thread pool, so
more than one transfer is in flight at a time; whether that pays on an
attached v5e over a single transfer is not measured yet.

Every fetch is also a **resilience boundary** (docs/ROBUSTNESS.md):

* a fault point (``device.fetch``) so the injection matrix can drive
  the recovery paths deterministically;
* a deadline watchdog (``ADAM_TPU_FETCH_TIMEOUT_S``, default 300 s,
  ``0`` disables) so a hung transfer RPC surfaces as a retryable
  :class:`~adam_tpu.utils.retry.DeadlineExceeded` instead of wedging
  the run;
* an internal retry-with-backoff for transient failures, so callers
  only ever see a fetch error after the budget is spent — at which
  point the device-eviction path (pipelines/streamed.py) takes over.

Host-resident numpy inputs short-circuit all of it: the watchdog and
retry wrap RPCs, not memcpys.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from adam_tpu.utils import faults
from adam_tpu.utils import retry as retry_mod

_MIN_CHUNK_BYTES = 8 * 1024 * 1024
_DEFAULT_FETCH_TIMEOUT_S = 300.0


def _max_threads() -> int:
    """Fetch-pool thread cap: bounded by the cores this process may
    actually run on, **floored at 2**.  The hosted environment schedules
    ONE core (``os.sched_getaffinity(0) == {0}``); the old fixed cap of
    8 made every large fetch spin up 8 threads that competed with the
    PartWriterPool's encode threads for that single core.  But the
    chunked overlap is GIL-released RPC *wait*, not CPU work — capping
    at the affinity count regressed the 1-core target to a serial fetch
    and gave back the measured ~2x (ROADMAP "re-measure chunked
    device_fetch under the affinity cap"), so the floor keeps two RPCs
    in flight regardless of affinity.

    ``ADAM_TPU_FETCH_THREADS`` overrides the floor (clamped to [1, 8])
    for the chip experiment the ``device.d2h.bps`` throughput
    histogram makes decidable: if the histogram shows the link
    idling between chunk turnarounds at floor 2, set 4 and re-measure —
    no code change required.  The CPU-leg measurement (docs/PERF.md
    "fetch-pool I/O floor") could NOT justify raising the default: its
    fetch wall is kernel-execution wait, not link idle."""
    raw = os.environ.get("ADAM_TPU_FETCH_THREADS", "").strip()
    if raw:
        try:
            return max(1, min(8, int(raw)))
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "ADAM_TPU_FETCH_THREADS=%r is not an int; using the "
                "affinity-derived default", raw,
            )
    try:
        n = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):  # non-Linux fallback
        n = os.cpu_count() or 1
    return max(2, min(8, n))


_MAX_THREADS = _max_threads()


def _fetch_timeout_s() -> float:
    """The fetch deadline (seconds; <= 0 disables the watchdog)."""
    return retry_mod.env_float(
        "ADAM_TPU_FETCH_TIMEOUT_S", _DEFAULT_FETCH_TIMEOUT_S
    )


def _map_daemon(fn, items: list) -> list:
    """``ThreadPoolExecutor.map`` twin on daemon threads.  The chunked
    fetch runs under the deadline watchdog, which ABANDONS it on
    timeout — but concurrent.futures joins its (non-daemon) workers at
    interpreter shutdown, so a genuinely hung RPC would wedge the
    recovered process at exit.  Daemon threads cannot."""
    results = [None] * len(items)
    errs = [None] * len(items)

    def run(k, item):
        try:
            results[k] = fn(item)
        except BaseException as e:  # noqa: BLE001 — relayed below
            errs[k] = e

    threads = [
        threading.Thread(target=run, args=(k, item), daemon=True,
                         name="device-fetch-chunk")
        for k, item in enumerate(items)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errs:
        if e is not None:
            raise e
    return results


def _resident_device(x):
    """The device an array lives on (None when indeterminable) — the
    fault point's ``device=K`` filter and eviction logs key on it.
    Arrays spanning SEVERAL devices (the mesh partitioner's sharded or
    replicated outputs: ``x.device`` is a Sharding, not a Device)
    return the string ``"mesh"`` — the same collective attribution
    their dispatch spans carry."""
    try:
        d = getattr(x, "device", None)
        if d is not None and not callable(d):
            if getattr(d, "id", None) is None:  # a Sharding object
                devs = getattr(x, "devices", None)
                ds = devs() if callable(devs) else set()
                if len(ds) > 1:
                    return "mesh"
                if ds:
                    return next(iter(ds))
            return d
        ds = x.devices()
        if len(ds) > 1:
            return "mesh"
        return next(iter(ds))
    except Exception:
        return None


def _fetch_chunked(x, threads: int, pass_name=None) -> np.ndarray:
    """One fetch attempt (the pre-resilience device_fetch body).  The
    fetched result passes through the fault grammar's data channel
    (``corrupt`` clauses at ``device.fetch`` — the silent-data-
    corruption injection the SDC audit must catch); the disabled cost
    is one module-global branch.  ``pass_name`` is the caller thread's
    telemetry pass scope (this body runs on the deadline watchdog
    thread, which carries none of its own)."""
    dev = _resident_device(x)
    faults.point("device.fetch", device=dev, pass_name=pass_name)
    nbytes = getattr(x, "nbytes", 0)
    if nbytes < 2 * _MIN_CHUNK_BYTES or x.ndim == 0:
        return faults.corrupt_array("device.fetch", np.asarray(x),
                                    device=dev, pass_name=pass_name)
    n = x.shape[0]
    n_chunks = min(threads, max(1, int(nbytes // _MIN_CHUNK_BYTES)), n)
    if n_chunks <= 1:
        return faults.corrupt_array("device.fetch", np.asarray(x),
                                    device=dev, pass_name=pass_name)
    bounds = [n * i // n_chunks for i in range(n_chunks + 1)]
    slices = [x[bounds[i]: bounds[i + 1]] for i in range(n_chunks)]
    parts = _map_daemon(np.asarray, slices)
    return faults.corrupt_array(
        "device.fetch", np.concatenate(parts, axis=0), device=dev,
        pass_name=pass_name,
    )


def device_fetch(x, threads: int = _MAX_THREADS,
                 deadline_s: float | None = None) -> np.ndarray:
    """Fetch a (possibly device-resident) array to host numpy.

    Device-resident inputs get the full resilience stack (deadline
    watchdog + transient retry, module docstring); host numpy inputs
    return as-is with none of it.  ``deadline_s`` overrides the
    ``ADAM_TPU_FETCH_TIMEOUT_S`` default for this call.
    """
    if isinstance(x, np.ndarray):
        return x
    timeout = _fetch_timeout_s() if deadline_s is None else deadline_s

    from adam_tpu.utils import telemetry as tele

    # the pass scope is thread-local and the attempt body runs on the
    # watchdog thread: capture it HERE so the fault grammar's pass=
    # selector sees the pipeline pass this fetch belongs to
    pass_name = tele.current_pass()

    def attempt():
        if timeout and timeout > 0:
            return retry_mod.call_with_deadline(
                lambda: _fetch_chunked(x, threads, pass_name), timeout,
                site="device.fetch",
            )
        return _fetch_chunked(x, threads, pass_name)

    def retryable(e: BaseException) -> bool:
        # the health scoreboard remembers what the retry wrappers
        # absorb: device-attributed transient failures and watchdog
        # trips feed the per-device score (utils/health.py) before the
        # backoff hides them.  Only REAL single-device attributions
        # feed it: a None (indeterminable) or "mesh" (collective)
        # source would accrue penalties on a phantom key no pool can
        # ever probe or exclude.
        ok = retry_mod.is_retryable(e)
        if ok:
            dev = _resident_device(x)
            if dev is not None and getattr(dev, "id", None) is not None:
                from adam_tpu.utils import health as health_mod

                if isinstance(e, retry_mod.DeadlineExceeded):
                    health_mod.BOARD.note_timeout(
                        dev, site="device.fetch"
                    )
                else:
                    health_mod.BOARD.note_retry(dev, site="device.fetch")
        return ok

    if not tele.TRACE.recording:
        return retry_mod.retry_call(attempt, site="device.fetch",
                                    retryable=retryable)
    # latency histogram over every device->host fetch (seconds,
    # retries included — the caller-visible latency): the barrier-2
    # and pass-C walls wait on the fetch TAIL, which the scalar span
    # totals cannot show.  The d2h transfer ledger rides the same
    # timing: bytes + throughput attributed to the resident device and
    # the active pipeline pass (pass_scope), so the analyzer can report
    # link utilization per direction.
    t0 = time.monotonic()
    out = None
    try:
        out = retry_mod.retry_call(attempt, site="device.fetch",
                                   retryable=retryable)
        return out
    finally:
        dur = time.monotonic() - t0
        tele.TRACE.observe(tele.H_FETCH_SECONDS, dur)
        if out is not None:
            dev = _resident_device(x)
            dev_id = None
            if dev is not None:
                dev_id = getattr(dev, "id", None)
                if dev_id is None:
                    dev_id = str(dev)
            tele.TRACE.record_transfer(
                "d2h", getattr(out, "nbytes", 0), dur, device=dev_id,
            )
