"""Compile ledger: executable-cache hit/miss accounting at every jit
dispatch site.

The device pool's prewarm (``parallel/device_pool.py``) exists so cold
XLA compiles (12-20 s each for a full v5e window program,
tests/test_chip_compile.py) never land inside a timed window — but until this module
existed nothing *measured* whether it succeeded.  The PERF.md "prewarm
coverage boundary" (residual-window grids, the realigned tail part,
wider merged tables) was known only by inference from suspiciously slow
windows.

This ledger makes it a first-class observable: every streamed jit
dispatch site (markdup columns, BQSR observe scatter-add, BQSR apply
table-gather, the realign sweep GEMMs) wraps its dispatch in
:func:`track`, keyed by the same ``(kernel, *grid dims)`` tuples the
prewarm entries use and the same per-device cache key
(``device_pool._device_key``) the prewarm cache uses — so the ledger's
notion of "warm" agrees with the prewarm's by construction.

* First dispatch of a (kernel, shape, device) triple in this process →
  **cache miss**: ``device.compile.cache_misses`` counts it, the
  ``device.compile.seconds`` histogram records the dispatch wall (trace
  + compile dominate a cold jit call; execution enqueues async), and an
  entry lands in the snapshot's ``compiles`` section.  A miss recorded
  *outside* a prewarm scope additionally counts
  ``device.compile.in_window`` and is flagged ``in_window=True`` — a
  cold compile that serialized inside a timed window, the exact event
  the analyzer's warning section surfaces.
* Every later dispatch of the triple → **cache hit**
  (``device.compile.cache_hits``), one set-membership check.

The seen-set is process-wide (like the prewarm cache): the bench's
warmup → timed-run pattern records the timed run's dispatches as hits,
which is precisely the claim the prewarm makes.  A dispatch that raises
(fault injection, dead chip) discards its claim so the retry re-measures.
"""

from __future__ import annotations

import threading
import time

from adam_tpu.utils import telemetry as tele

#: (kernel key, device key) pairs whose executable
#: this process has already built — mirrors device_pool._PREWARMED,
#: which seeds it.
_SEEN: set = set()
_LOCK = threading.Lock()

_PREWARM_TLS = threading.local()


def reset() -> None:
    """Test hook: forget every compiled triple."""
    with _LOCK:
        _SEEN.clear()


class prewarm_scope:
    """Marks the current thread as compiling under a prewarm: misses
    recorded inside it are *expected* compiles, outside it they are
    in-window cold compiles (reentrant, like device_pool.replay_scope)."""

    def __enter__(self):
        _PREWARM_TLS.depth = getattr(_PREWARM_TLS, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _PREWARM_TLS.depth -= 1
        return False


def in_prewarm() -> bool:
    return getattr(_PREWARM_TLS, "depth", 0) > 0


def device_cache_key(device) -> str:
    """The per-device half of the ledger key — device_pool's
    ``_device_key`` for explicit devices, ``"default"`` for the
    single-chip default-device path (no pool → no prewarm → its first
    dispatch genuinely compiles in-window, and the ledger says so).
    Strings pass through: the mesh partitioner's collective executables
    are keyed per mesh width (``"mesh:<n>"``), not per member chip."""
    if device is None:
        return "default"
    if isinstance(device, str):
        return device
    from adam_tpu.parallel.device_pool import _device_key

    return _device_key(device)


def claim(key: tuple, device=None) -> None:
    """Assert a (kernel, shape, device) triple warm without
    recording anything — the prewarm's dedupe-skip path calls this so
    the ledger seen-set re-agrees with the prewarm cache.  The two can
    diverge after a faulted run: a dispatch that RAISES gives its track
    claim back (so the retry re-measures) while the jit executable it
    built stays cached and the prewarm cache keeps the triple — without
    this re-seed, the next clean run's first dispatch of the triple
    would read as a false in-window cold compile."""
    with _LOCK:
        _SEEN.add((key, device_cache_key(device)))


class track:
    """Context manager for one jit dispatch: times the call and records
    hit/miss against the process-wide seen-set.

    ``key`` is the prewarm-entry key tuple ``(kernel_name, *dims)``;
    ``device`` the jax device (or None for the default device).  The
    claim is taken on entry (so concurrent dispatches of one triple
    record one miss, not n) and discarded if the dispatch raises —
    a transiently-failed compile must stay a miss for the retry.
    """

    __slots__ = ("_key", "_dims", "_dev", "_cache_key", "_t0", "_miss")

    def __init__(self, key: tuple, device=None):
        self._key = key
        self._dims = tuple(key[1:])
        self._dev = device
        self._cache_key = None
        self._miss = False

    def __enter__(self):
        # membership maintenance is unconditional (a warmup run without
        # --metrics-json still warms the jit cache, and the timed run's
        # ledger must know that); only counters/entries gate on recording
        self._cache_key = (self._key, device_cache_key(self._dev))
        with _LOCK:
            self._miss = self._cache_key not in _SEEN
            _SEEN.add(self._cache_key)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # failed dispatch: nothing compiled — give the claim back
            with _LOCK:
                _SEEN.discard(self._cache_key)
            return False
        dur = time.monotonic() - self._t0
        if not self._miss:
            tele.TRACE.count(tele.C_COMPILE_HITS)
            return False
        tele.TRACE.record_compile(
            str(self._key[0]), self._dims, device_cache_key(self._dev),
            dur, in_window=not in_prewarm(),
        )
        return False
