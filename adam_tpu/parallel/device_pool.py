"""Per-window device pool: round-robin dispatch across attached chips.

The streamed flagship (pipelines/streamed.py) drives its per-residue
passes as asynchronous device dispatches, but until this module existed
every dispatch landed on ``jax.devices()[0]`` — one chip did all the
work while the other attached devices idled (the MULTICHIP dry-runs
attach 8).  Windows are independent until the two global barriers, so
their device work can fan out: window *i*'s markdup reductions, observe
scatter-adds and apply table-gathers run on device ``i % n`` while the
single host core keeps doing what only it can (tokenize / encode /
write).

Three pieces:

* :class:`DevicePool` — resolves the device set (``--devices N`` /
  ``ADAM_TPU_DEVICES``, capped at what is attached), hands out the
  round-robin device for a window, and places host arrays onto it
  (``jax.device_put`` commits the inputs, so the jit dispatch follows
  them to the chip).
* **Compile prewarm** — :meth:`DevicePool.prewarm` compiles the
  grid-quantized kernel set once per device, concurrently, *before*
  the first window's device work.  Cold remote compiles cost 20-40 s
  each (docs/PERF.md) and the jit executable cache is keyed per
  device, so without this every chip after the first would pay its
  compiles inside a timed window.  A process-wide cache dedupes:
  re-running the pipeline in the same process (the bench's warmup ->
  timed-run pattern) skips already-warm (kernel, shape, device) triples.
* **Merge shape** — the pool never merges anything itself: per-device
  observe histograms and markdup columns flow back through the same
  compact per-window parts the single-chip path uses, and the merge
  barriers sum them host-side (``bqsr.merge_observations`` fetches each
  part from whichever device holds it).  This is the host-side analog
  of ``parallel/dist.distributed_observe``'s psum — same reduction, no
  mesh required, bitwise order-stable because parts merge in window
  order.

The pool is only engaged by the ``device`` backend with ``n > 1``; the
``n == 1`` case returns ``None`` from :func:`make_pool` and the caller
keeps its single-device path untouched.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional, Sequence

import numpy as np

from adam_tpu.utils import faults
from adam_tpu.utils import health as health_mod
from adam_tpu.utils import retry as retry_mod
from adam_tpu.utils import telemetry as tele

log = logging.getLogger(__name__)


class AllDevicesEvicted(RuntimeError):
    """Every pool device has been evicted; callers fall back to the
    ``native``/``numpy`` host backend (bit-identical by the backend
    parity contract, tests/test_backend_parity.py)."""

#: Process-wide prewarm cache: (entry key, device id) triples already
#: compiled+invoked.  Keyed per device because the jit executable cache
#: is — warming device 0 does nothing for device 3.
_PREWARMED: set = set()
_PREWARM_LOCK = threading.Lock()


def reset_prewarm_cache() -> None:
    """Test hook: forget which (kernel, shape, device) triples are warm."""
    with _PREWARM_LOCK:
        _PREWARMED.clear()


def resolve_device_count(requested: Optional[int] = None) -> int:
    """How many devices the streamed pipeline should fan out over.

    Order: explicit ``requested`` (the ``--devices`` flag), then
    ``ADAM_TPU_DEVICES``, then every attached device.  Always capped at
    the attached count and floored at 1; a request beyond the topology
    is capped with a warning, not an error (the same command line must
    work on an 8-chip pod and a 1-chip dev box).  Only an explicit
    ``requested < 1`` raises — a malformed env value (non-int, zero,
    negative) warns and falls back to all attached, the same degradation
    every other ``ADAM_TPU_*`` tuning var gets: an env typo must not
    crash a pipeline mid-run.
    """
    if requested is not None and requested < 1:
        raise ValueError(f"--devices must be >= 1 (got {requested})")
    if requested is None:
        raw = os.environ.get("ADAM_TPU_DEVICES", "").strip()
        if raw:
            try:
                requested = int(raw)
            except ValueError:
                requested = None
            if requested is not None and requested < 1:
                requested = None
            if requested is None:
                log.warning(
                    "ADAM_TPU_DEVICES=%r is not a positive int; using all "
                    "attached devices", raw,
                )
    import jax

    try:
        # local_devices, not devices: in a multi-process run this host
        # may only address a slice of the global topology, and the pool
        # must never round-robin onto a chip it cannot drive
        attached = len(jax.local_devices())
    except Exception:
        attached = 1
    if requested is None:
        return max(1, attached)
    if requested > attached:
        log.warning(
            "--devices %d requested but only %d attached; using %d",
            requested, attached, attached,
        )
    return max(1, min(requested, attached))


def _attr_id(dev):
    """The span ``device=`` attribution value for one device: its jax
    id, falling back to ``str(dev)`` — never None, so attribution can't
    silently drop out of the ``device_spans`` aggregation or the
    per-chip Chrome-trace tracks on an exotic backend."""
    dev_id = getattr(dev, "id", None)
    return dev_id if dev_id is not None else str(dev)


# Thread-local replay depth: the streamed recovery paths enter a
# replay_scope() around each replayed window, so every nested dispatch/
# fetch span — in ANY layer, without threading a flag through the bqsr/
# markdup APIs — picks up a ``replay=1`` attr from span_attrs and
# aggregates under the survivor's ``<k>:replay`` device_spans key
# instead of conflating with its organic work.
_REPLAY_TLS = threading.local()


class replay_scope:
    """Marks the current thread as replaying an evicted device's window
    (reentrant; see :func:`span_attrs`)."""

    def __enter__(self):
        _REPLAY_TLS.depth = getattr(_REPLAY_TLS, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _REPLAY_TLS.depth -= 1
        return False


def in_replay() -> bool:
    """True while the current thread is inside a :class:`replay_scope`."""
    return getattr(_REPLAY_TLS, "depth", 0) > 0


def span_attrs(device=None) -> dict:
    """Span attrs for a dispatch/fetch call site: ``{}`` on the
    single-device path (no attribution noise), ``{"device": <id>}``
    otherwise — plus ``replay=1`` inside a :class:`replay_scope`, so
    replayed work aggregates under ``<k>:replay`` and never conflates
    with the survivor's own occupancy.  The one helper every layer
    (markdup, bqsr, streamed) shares, so per-chip attribution cannot
    diverge between passes."""
    if device is None:
        return {}
    attrs = {"device": _attr_id(device)}
    if in_replay():
        attrs["replay"] = 1
    return attrs


def putter(device=None):
    """The host->device placement callable every dispatch site shares:
    ``jnp.asarray`` (default device, uncommitted — the single-chip
    behavior) when ``device`` is None, else a committed
    ``jax.device_put`` onto the given chip so the following jit call
    dispatches there.

    Every placement is also the h2d half of the transfer ledger: bytes
    counted from the host array, wall from the put call (submit-side —
    device_put may complete the copy asynchronously, so the throughput
    histogram is a lower bound on transfer time, not an upper; the
    byte totals are exact either way), attributed to the target device
    and the active :func:`~adam_tpu.utils.telemetry.pass_scope`."""
    if device is None:
        import jax.numpy as jnp

        base = jnp.asarray
        dev_id = None
    else:
        import jax

        def base(x, _dev=device):
            return jax.device_put(x, _dev)

        dev_id = _attr_id(device)

    def put(x):
        if not tele.TRACE.recording:
            return base(x)
        t0 = time.monotonic()
        out = base(x)
        tele.TRACE.record_transfer(
            "h2d", getattr(x, "nbytes", 0), time.monotonic() - t0,
            device=dev_id,
        )
        return out

    return put


def donation_ok(device=None) -> bool:
    """Whether jit buffer donation actually pays on ``device`` (the
    default device when None): CPU runtimes ignore ``donate_argnums``
    with a warning, so the donating jit variants — distinct executables
    — are only built, warmed and dispatched on real accelerators.  The
    ONE donation decision shared by every dispatch site AND the prewarm
    entry builders, so the two can never pick different variants (which
    would cold-compile the dispatched twin inside a window)."""
    from adam_tpu.parallel.mesh import on_tpu

    return on_tpu(device)


class ResidentWindow:
    """One window's ingest-resident device payload: the five arrays
    every per-residue pass reads (``bases``/``quals`` [g, gl] and
    ``lengths``/``flags``/``read_group_idx`` [g], grid-padded), placed
    host->device ONCE when the window is tokenized and dispatched
    against by markdup keys (pass A), BQSR observe (pass B) and the
    recalibration apply (pass C) — the ingest-once H2D contract
    (docs/PERF.md "Device-resident windows"): the ledger's per-pass h2d
    collapses to one ``ingest`` entry per window, and the later passes
    ship only their genuinely per-pass inputs (bit-packed MD masks,
    post-split validity bools).

    The duplicate flags resolved at barrier 1 mutate only the HOST
    batch — safe, because the device kernels read ``flags`` solely for
    the orientation bits (reverse/paired/second-of-pair), which markdup
    never changes; duplicate-dependent filtering enters through the
    per-pass ``read_ok`` mask computed host-side from the updated
    flags.  The same reasoning covers the pass-B candidate split: it
    MASKS rows (geometry preserved), and the updated ``valid``/
    ``has_qual`` bools ship per pass.

    **Pinned or transient** — how long a handle lives is the only
    policy.  A handle pinned at ingest (best-effort) lives until its
    part submits in pass C: the streamed pipeline holds ONE base
    reference and releases it then, so HBM frees window by window.  A
    pass whose target holds no live handle (the pin failed, the device
    was evicted, a donating attempt consumed the handle, the mesh
    degraded, or a hedge twin runs on another chip) places a transient
    one from the host-retained ingest copy (``pipelines/streamed.py``
    keeps each window's decoded batch until its part publishes — the
    replay source of truth, docs/ROBUSTNESS.md) and releases it as soon
    as the dispatch that needed it returns; the runtime keeps the
    buffers of the queued computation alive until it completes.
    :meth:`retain` exists for a consumer that must pin the handle
    across a genuinely concurrent boundary (none does today — wire it
    before adding one).  :meth:`drop` is the fault path (device
    evicted, mesh degraded): the handle dies and the next pass places
    a transient one on a survivor."""

    FIELDS = ("bases", "quals", "lengths", "flags", "read_group_idx")

    def __init__(self, window: int, device, arrays: dict, g: int,
                 gl: int, nbytes: int):
        self.window = window
        self.device = device  # a jax device, None (default), or "mesh"
        self.g = g
        self.gl = gl
        self.nbytes = nbytes
        self._arrays = arrays
        self._refs = 1
        self._consumed = False
        self._lock = threading.Lock()

    @property
    def alive(self) -> bool:
        with self._lock:
            return self._arrays is not None and not self._consumed

    def get(self, name: str):
        """The resident device array for FIELD ``name`` (raises once
        released/dropped — callers check :attr:`alive` first)."""
        with self._lock:
            if self._arrays is None:
                raise RuntimeError(
                    f"resident window {self.window} already released"
                )
            return self._arrays[name]

    def args(self) -> tuple:
        """The five resident arrays in kernel-signature order."""
        return tuple(self.get(f) for f in self.FIELDS)

    def mark_consumed(self) -> None:
        """A donating dispatch consumed the bases/quals buffers: the
        handle stops offering them (a retry after a partial donating
        execution places a transient handle from the host copy, never
        re-passes a deleted buffer)."""
        with self._lock:
            self._consumed = True

    def retain(self) -> None:
        with self._lock:
            if self._arrays is not None:
                self._refs += 1

    def release(self) -> bool:
        """Drop one reference; True when this call freed the arrays."""
        with self._lock:
            if self._arrays is None:
                return False
            self._refs -= 1
            if self._refs > 0:
                return False
            self._arrays = None
            return True

    def drop(self) -> bool:
        """Force-release regardless of refcount (eviction / mesh
        degradation); True when the arrays were still held."""
        with self._lock:
            held = self._arrays is not None
            self._arrays = None
            self._refs = 0
            return held


def resident_arrays(b, g: int, gl: int, put) -> tuple:
    """The five :attr:`ResidentWindow.FIELDS` arrays of the numpy batch
    view ``b``, padded to the (``g`` rows, ``gl`` lanes) grid and placed
    with ``put`` -> ``(arrays in FIELDS order, host bytes)``.  The one
    place a window's bases/quals cross host->device: the pinned and
    transient handles below and in ``partitioner.mesh_resident_window``,
    and the in-memory API's single-device legs, all place through it."""
    from adam_tpu.formats import schema
    from adam_tpu.formats.batch import pad_rows_np

    host = (
        pad_rows_np(b.bases, g, schema.BASE_PAD, cols=gl),
        pad_rows_np(b.quals, g, schema.QUAL_PAD, cols=gl),
        pad_rows_np(b.lengths, g, 0),
        pad_rows_np(b.flags, g, schema.FLAG_UNMAPPED),
        pad_rows_np(b.read_group_idx, g, -1),
    )
    return (
        tuple(put(a) for a in host), sum(int(a.nbytes) for a in host)
    )


def make_resident_window(b, window: int, device=None) -> ResidentWindow:
    """Place one window's resident payload on ``device`` (a pool chip;
    ``None`` = the single-chip default device).  ``b`` is the window
    batch's numpy view; arrays pad to the (pow2-rows, lane-aligned)
    grid the markdup/observe/apply kernels take.  The streamed pipeline
    calls this for the pin at ingest (under ``telemetry.pass_scope(
    "ingest")``, so the h2d ledger books it there) and for a pass's
    transient handle (booked under that pass)."""
    from adam_tpu.formats.batch import grid_cols, grid_rows

    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)
    arrays, nbytes = resident_arrays(b, g, gl, putter(device))
    return ResidentWindow(
        window, device, dict(zip(ResidentWindow.FIELDS, arrays)), g, gl,
        nbytes,
    )


class DevicePool:
    """Round-robin window -> device placement over an explicit device set.

    ``pool.device(i)`` is the device for window ``i`` (``i % n``);
    ``pool.put(tree, i)`` commits host arrays onto it so the following
    jit call dispatches there.  Per-device occupancy/skew reporting
    comes from the ``device=<id>`` span attribution (the snapshot's
    ``device_spans`` section), not from pool-side counters.

    Placement is a pure function of the caller's index — the pool keeps
    no dispatch history.  That statelessness is what both recovery
    layers lean on: eviction replay re-asks for a window's device and
    simply receives the next survivor, and a durable RESUME
    (``pipelines/checkpoint.RunJournal``) that skips journaled windows
    never perturbs where the remaining windows land, because nothing
    here depends on which windows were actually dispatched.  The
    bit-identity invariant never rests on placement anyway (the barrier
    merges are window-ordered and the backends are parity twins), so
    skipped windows, evictions and resumes compose freely.
    """

    def __init__(self, devices: Optional[Sequence] = None,
                 limit: Optional[int] = None):
        import jax

        devs = (
            list(devices) if devices is not None
            else list(jax.local_devices())
        )
        if limit is not None:
            devs = devs[: max(1, limit)]
        if not devs:
            raise ValueError("DevicePool needs at least one device")
        self.devices = devs
        # eviction state: self.devices stays the full original set (so
        # per-device replicas like pass C's dev_tables keep stable
        # indices); round-robin placement runs over the survivors
        self._dead: set = set()
        # live leases (the multi-job scheduler's per-job handles);
        # shares the eviction lock — both are rare-path bookkeeping
        self._leases: set = set()
        self._evict_lock = threading.Lock()
        # the process-wide device-health scoreboard (utils/health.py):
        # placement consults it (probation devices are excluded until
        # their re-admission probe passes), eviction informs it.  One
        # board across pools/leases — health is a hardware property.
        self.health = health_mod.BOARD

    # ---- multi-tenant leasing (adam_tpu/serve) -------------------------
    def lease(self, job: Optional[str] = None) -> "PoolLease":
        """A job-scoped handle onto this shared pool (see
        :class:`PoolLease`).  The pool tracks live leases only so the
        scheduler can PROVE a finished or quarantined job holds no
        devices — placement itself stays stateless."""
        lease = PoolLease(self, job=job)
        with self._evict_lock:
            self._leases.add(lease)
        return lease

    def _drop_lease(self, lease: "PoolLease") -> None:
        with self._evict_lock:
            self._leases.discard(lease)

    def active_leases(self) -> list:
        """Live leases, for the scheduler's status view."""
        with self._evict_lock:
            return list(self._leases)

    @property
    def n(self) -> int:
        """The configured fan-out (evictions do not shrink it — it is
        the stats/queue-depth constant, not the live device count)."""
        return len(self.devices)

    # ---- eviction + health --------------------------------------------
    def survivors(self) -> list:
        """Devices not hard-evicted (health-filter-free): the prewarm
        set — probation devices keep their executables warm so a
        re-admitted chip never cold-compiles inside a window."""
        with self._evict_lock:
            return [
                d for d in self.devices if _device_key(d) not in self._dead
            ]

    def alive_devices(self) -> list:
        """The PLACEABLE device set: survivors minus health-blocked
        (probation/evicted on the scoreboard) chips.  Availability
        beats health: when the scoreboard would empty the set, the
        blocked survivors serve anyway — a poolwide false alarm must
        degrade observability, not the run (the SDC audit still guards
        the pass-C payload those devices produce)."""
        alive = self.survivors()
        if len(alive) <= 1:
            return alive
        ok = [d for d in alive if not self.health.blocked(d)]
        return ok if ok else alive

    def evict(self, device, reason: str = "", tracer=None) -> bool:
        """Remove a failed device from round-robin placement.

        Returns True when this call actually evicted (False: already
        dead, or ``device`` is None — the single-chip default-device
        path has nothing to evict).  Counts ``device.evicted`` on
        ``tracer`` (the streamed run tracer, so the count lands in the
        ``--metrics-json`` snapshot) or the global TRACE.
        """
        if device is None:
            return False
        key = _device_key(device)
        with self._evict_lock:
            if key in self._dead:
                return False
            self._dead.add(key)
            left = len(self.devices) - len(self._dead)
        log.error(
            "evicting device %s after spent retry budget%s; %d of %d "
            "pool device(s) remain%s", key,
            f" ({reason})" if reason else "", left, len(self.devices),
            "" if left else " — falling back to the host backend",
        )
        (tracer if tracer is not None else tele.TRACE).count(
            tele.C_DEVICE_EVICTED
        )
        self.health.mark_evicted(device, tracer=tracer)
        return True

    def _maybe_probe(self, tracer=None) -> None:
        """Run due re-admission probes (probation devices whose
        cooldown elapsed): a passing known-answer dispatch re-admits
        the chip into placement, a failing one graduates it to a real
        eviction.  The ``probe_maybe_due`` fast path is one lock-free
        clock compare — taken BEFORE building the survivor set, so the
        per-window placement call stays cheap."""
        if not self.health.probe_maybe_due():
            return
        survivors = self.survivors()
        # claim only THIS pool's devices: a foreign probation device's
        # cooldown must stay claimable by the pool that can probe it
        due = set(self.health.due_probes(survivors))
        if not due:
            return
        for dev in survivors:
            if _device_key(dev) not in due:
                continue
            if health_mod.probe_known_answer(dev):
                self.health.readmit(dev, tracer=tracer)
            else:
                self.health.probe_failed(dev, tracer=tracer)
                self.evict(dev, reason="re-admission probe failed",
                           tracer=tracer)

    def device_index(self, window: int) -> int:
        """Index of window's device in the ORIGINAL pool order (stable
        under eviction — per-device replicas are keyed by it)."""
        return self.devices.index(self.device(window))

    def device(self, window: int):
        self._maybe_probe()
        alive = self.alive_devices()
        if not alive:
            raise AllDevicesEvicted(
                f"all {len(self.devices)} pool devices evicted"
            )
        return alive[window % len(alive)]

    def device_id(self, window: int):
        """The span ``device=<id>`` attribution value for window's
        device (consistent across every layer via :func:`span_attrs`'s
        ``_attr_id``; on a single host the ids are the pool ordinals)."""
        return _attr_id(self.device(window))

    def put(self, tree, window: int):
        """Commit a pytree of host arrays onto window's device
        (through :func:`putter`, so the h2d ledger sees every leaf)."""
        import jax

        return jax.tree.map(putter(self.device(window)), tree)

    # ---- compile prewarm ----------------------------------------------
    def prewarm(self, entries: Sequence[tuple], tracer=None) -> int:
        """Compile the kernel set on every pool device, concurrently.

        ``entries``: ``(key, fn)`` pairs where ``key`` names a
        (kernel, grid-quantized shape) combination and ``fn(device)``
        builds dummy device-resident args and invokes the kernel to
        completion (populating the per-device jit executable cache).
        Each (key, device) triple compiles **exactly once per process**
        — the bench's warmup run pays the cold compiles, the timed run's
        prewarm finds everything warm and is a no-op.  Returns the
        number of (entry, device) compiles actually performed; spans
        carry ``device=<k>`` attribution and land in ``tracer`` (the
        streamed run tracer) so the telemetry snapshot proves the
        compiles happened outside the timed windows.
        """
        from adam_tpu.utils import compile_ledger

        tr = tracer if tracer is not None else tele.TRACE
        todo: list[tuple] = []
        claimed: set = set()
        with _PREWARM_LOCK:
            # claim under the lock so concurrent prewarms don't compile
            # the same triple twice; a failed compile DISCARDS its claim
            # below — a transient compile/RPC failure must stay
            # retryable, or the next run pays the cold compile inside a
            # timed window with no signal.  Evicted devices are skipped;
            # health-PROBATION devices are still warmed (survivors, not
            # alive_devices) so a probe re-admission never cold-compiles
            # inside the window it rejoins on.  Replayed windows
            # re-prewarm on survivors via the same process-wide cache
            # (already-warm triples dedupe to no-ops).
            for key, fn in entries:
                for dev in self.survivors():
                    cache_key = (key, _device_key(dev))
                    if cache_key not in _PREWARMED and cache_key not in claimed:
                        claimed.add(cache_key)
                        todo.append((key, fn, dev, cache_key))
                    else:
                        # already warm in this process: re-seed the
                        # compile ledger, whose claim a faulted run's
                        # raising dispatch may have handed back
                        compile_ledger.claim(key, dev)
            _PREWARMED.update(claimed)
        if not todo:
            return 0

        def _one(item):
            key, fn, dev, cache_key = item

            def compile_once():
                faults.point("pool.prewarm", device=dev)
                fn(dev)

            from adam_tpu.utils import compile_ledger

            try:
                with tr.span(
                    tele.SPAN_POOL_PREWARM_COMPILE,
                    device=_attr_id(dev), kernel=str(key[0]),
                ), compile_ledger.prewarm_scope(), \
                        tele.pass_scope("prewarm"), \
                        compile_ledger.track(key, dev):
                    # transient compile/RPC failures retry in place
                    # (exponential backoff) before degrading to the
                    # warn-and-compile-in-window fallback below.  The
                    # compile-ledger claim inside the prewarm scope is
                    # what lets the first REAL dispatch of this triple
                    # record a cache hit — and an in-window miss at a
                    # dispatch site is, by elimination, a shape the
                    # prewarm never covered (the coverage boundary).
                    retry_mod.retry_call(
                        compile_once, site="device.pool.prewarm"
                    )
            except Exception:
                # prewarm is purely an optimization: a transient
                # compile/RPC failure must not abort a run that would
                # otherwise succeed (the shape just compiles in-window
                # later).  Discard the claim so a future prewarm retries.
                with _PREWARM_LOCK:
                    _PREWARMED.discard(cache_key)
                log.warning(
                    "prewarm of %s on device %s failed; the shape will "
                    "compile at first dispatch instead",
                    key, _device_key(dev), exc_info=True,
                )
                return 0
            tr.count(tele.C_POOL_PREWARM_COMPILES)
            return 1

        # one thread per device: the compiles are remote-service RPCs
        # (GIL released), so n devices' 20-40 s cold compiles overlap
        # instead of serializing into an n * 30 s stall
        with ThreadPoolExecutor(max_workers=self.n) as ex:
            return sum(ex.map(_one, todo))


class PoolLease:
    """One job's handle onto a shared :class:`DevicePool`.

    The multi-job transform service (``adam_tpu/serve``) runs N
    concurrent streamed jobs against ONE pool; each job receives a
    lease instead of the pool itself.  The lease is interface-identical
    to the pool for everything the streamed pipeline touches
    (``device``/``device_index``/``put``/``prewarm``/``evict``/
    ``alive_devices``/``devices``/``n``) and adds exactly two things:

    * **attribution** — ``job`` labels eviction log lines, so a shared
      chip dying under tenant A's dispatch is debuggable;
    * **release bookkeeping** — :meth:`release` returns the lease to
      the pool (idempotent; called by the scheduler when the job
      reaches any terminal state, quarantine included), so
      ``DevicePool.active_leases`` can prove a quarantined job holds
      no devices.

    Eviction itself stays SHARED: a chip that spent one tenant's retry
    budget is dead hardware for every tenant, and each job replays only
    its own in-flight windows through its own recovery paths — the
    fault-isolation contract (docs/ROBUSTNESS.md) needs no per-lease
    device state for that, precisely because placement is stateless.
    """

    def __init__(self, pool: DevicePool, job: Optional[str] = None):
        self._pool = pool
        self.job = job
        self._released = threading.Event()

    # ---- pool interface (duck-typed by pipelines/streamed.py) ----------
    @property
    def devices(self) -> list:
        return self._pool.devices

    @property
    def n(self) -> int:
        return self._pool.n

    def alive_devices(self) -> list:
        return self._pool.alive_devices()

    def device(self, window: int):
        return self._pool.device(window)

    def device_index(self, window: int) -> int:
        return self._pool.device_index(window)

    def device_id(self, window: int):
        return self._pool.device_id(window)

    def put(self, tree, window: int):
        return self._pool.put(tree, window)

    def prewarm(self, entries: Sequence[tuple], tracer=None) -> int:
        return self._pool.prewarm(entries, tracer=tracer)

    def evict(self, device, reason: str = "", tracer=None) -> bool:
        if self.job and device is not None:
            reason = f"job {self.job}: {reason}" if reason else (
                f"job {self.job}"
            )
        return self._pool.evict(device, reason=reason, tracer=tracer)

    # ---- lease lifecycle ----------------------------------------------
    @property
    def released(self) -> bool:
        return self._released.is_set()

    def release(self) -> None:
        """Return this lease to the pool (idempotent)."""
        if not self._released.is_set():
            self._released.set()
            self._pool._drop_lease(self)


def _device_key(dev) -> str:
    """Stable per-device cache key (id is unique within a process).
    Delegates to :func:`adam_tpu.utils.health.device_key` — the ONE
    key vocabulary shared by the prewarm cache, the eviction set and
    the health scoreboard; a divergence would silently stop the
    board's placement filter from matching pool devices (health.py
    cannot import this module, hence the direction)."""
    return health_mod.device_key(dev)


def make_pool(requested: Optional[int] = None) -> Optional[DevicePool]:
    """Resolve the device count and build a pool — or ``None`` for the
    single-device topologies, so callers fall back to the existing
    single-chip path with zero behavior change."""
    n = resolve_device_count(requested)
    if n <= 1:
        return None
    return DevicePool(limit=n)


# --------------------------------------------------------------------------
# Hedged dispatch (docs/ROBUSTNESS.md "Device health, hedging, and SDC
# audit"): rescue an in-flight window from a straggler chip.
# --------------------------------------------------------------------------
def hedged_call(primary_fn, hedge_fn, threshold_s: float, tracer=None):
    """Run ``primary_fn()`` on a watchdog thread; if it is still in
    flight after ``threshold_s`` (the kernel's
    ``ADAM_TPU_HEDGE_FACTOR`` × p99, Dean & Barroso's hedged-request
    discipline), run ``hedge_fn()`` — the same window re-dispatched on
    another alive device from the host-retained ingest copy — on the
    calling thread.  **First completed result wins**; output is
    byte-identical either way because the kernels are deterministic
    parity twins, so the race decides latency, never bytes.

    Returns ``(result, winner, fired)`` where ``winner`` is
    ``"primary"`` (hedge never fired, or fired and lost) or
    ``"hedge"``, and ``fired`` whether the speculative dispatch
    launched (so callers can keep hedge-inflated walls out of their
    latency statistics).  Counters: ``device.hedge.fired`` when the
    hedge launches, ``device.hedge.won`` when its result is used,
    ``device.hedge.wasted`` when the primary beat it (fired = won +
    wasted).  A hedge that RAISES falls back to waiting out the
    primary — hedging is an optimization and must never turn a slow
    window into a failed one; a primary that raises after a losing
    hedge surfaces its own error to the caller's normal recovery path
    (a primary error swallowed by a WINNING hedge is deliberate: the
    window was rescued, and a genuinely sick chip keeps feeding the
    scoreboard through its other signals).
    """
    tr = tracer if tracer is not None else tele.TRACE
    box: list = []
    done = threading.Event()
    # the primary runs on a helper thread, which carries none of the
    # caller's thread-local telemetry pass/trace scopes — capture and
    # re-enter them there, so the transfer ledger's per-pass attribution
    # (and the fault grammar's pass= selector) see the same pass the
    # un-hedged call would have, and the primary's spans stay stamped
    # with the caller's job trace
    caller_pass = tele.current_pass()
    caller_trace = tele.current_trace()

    def run_primary():
        try:
            with tele.trace_scope(caller_trace):
                if caller_pass is not None:
                    with tele.pass_scope(caller_pass):
                        box.append((True, primary_fn()))
                else:
                    box.append((True, primary_fn()))
        except BaseException as e:  # noqa: BLE001 — relayed below
            box.append((False, e))
        done.set()

    t = threading.Thread(target=run_primary, daemon=True,
                         name="hedge-primary")
    t.start()
    if done.wait(threshold_s):
        ok, val = box[0]
        if ok:
            return val, "primary", False
        raise val
    # the primary is officially late: speculate
    tr.count(tele.C_HEDGE_FIRED)
    from adam_tpu.utils import incidents

    incidents.maybe_record(
        "hedge.fired", trace_id=caller_trace or tr.trace, tracer=tr,
        reason="in-flight window exceeded its %.3fs hedge threshold"
               % threshold_s,
    )
    try:
        hedged = hedge_fn()
    except Exception as e:
        log.warning(
            "hedged re-dispatch failed (%s); waiting out the primary", e,
        )
        # the speculative attempt was launched and discarded: it counts
        # as wasted, keeping fired == won + wasted even on this path
        tr.count(tele.C_HEDGE_WASTED)
        done.wait()
        ok, val = box[0]
        if ok:
            return val, "primary", True
        raise val
    if done.is_set() and box and box[0][0]:
        # the primary finished while the hedge computed: first result
        # wins, the speculative copy is the wasted one
        tr.count(tele.C_HEDGE_WASTED)
        return box[0][1], "primary", True
    tr.count(tele.C_HEDGE_WON)
    return hedged, "hedge", True


# --------------------------------------------------------------------------
# Streamed-pipeline kernel set
# --------------------------------------------------------------------------
def dummy_like(field, shape, fill=0) -> np.ndarray:
    """Prewarm dummy argument: ``fill`` at ``shape`` in FIELD's dtype —
    shapes AND dtypes must match the real dispatches bit-for-bit or the
    jit cache treats the warm call as a different program.  Shared by
    every prewarm entry builder (here and the mesh entries in
    parallel/partitioner.py), so a kernel-signature change has one
    dummy-construction idiom to keep in sync."""
    dt = np.asarray(field).dtype
    return np.full(shape, fill, dtype=dt)


# Per-kernel dummy argument tuples — THE single source of truth for
# each kernel's prewarm signature, shared by the pool entries below and
# the mesh entries in parallel/partitioner.py (which only differ in the
# row count ``g``: the mesh pads it to a device-count multiple).  A
# kernel-signature change edits exactly one of these.
def markdup_dummy_args(b, g: int, gl: int, gc: int) -> tuple:
    """markdup_columns_local's 8 args at grid (g rows, gc cigar ops,
    gl lanes)."""
    from adam_tpu.formats import schema

    _z = dummy_like
    return (
        _z(b.start, (g,), -1), _z(b.end, (g,), -1),
        _z(b.flags, (g,), schema.FLAG_UNMAPPED),
        _z(b.cigar_ops, (g, gc), schema.CIGAR_PAD),
        _z(b.cigar_lens, (g, gc)), _z(b.cigar_n, (g,)),
        _z(b.quals, (g, gl), schema.QUAL_PAD), _z(b.lengths, (g,)),
    )


def observe_dummy_args(b, g: int, gl: int) -> tuple:
    """observe_packed_body's 8 array args at grid (g rows, gl lanes):
    the resident five, the two bit-packed MD masks and the read filter
    — static (n_rg, gl) follow at the call site."""
    from adam_tpu.formats import schema

    _z = dummy_like
    npk = -(-gl // 8)
    return (
        _z(b.bases, (g, gl), schema.BASE_PAD),
        _z(b.quals, (g, gl), schema.QUAL_PAD),
        _z(b.lengths, (g,)),
        _z(b.flags, (g,), schema.FLAG_UNMAPPED),
        _z(b.read_group_idx, (g,), -1),
        np.zeros((g, npk), np.uint8), np.zeros((g, npk), np.uint8),
        np.zeros((g,), bool),
    )


def apply_dummy_args(b, g: int, gl: int) -> tuple:
    """apply_pack2_body's 7 per-row args at grid (g rows, gl lanes) —
    the u8 table dummy (shape depends on the solved width) and the
    statics (gl, g*gl) follow at the call site."""
    from adam_tpu.formats import schema

    _z = dummy_like
    return (
        _z(b.bases, (g, gl), schema.BASE_PAD),
        _z(b.quals, (g, gl), schema.QUAL_PAD),
        _z(b.lengths, (g,)),
        _z(b.flags, (g,), schema.FLAG_UNMAPPED),
        _z(b.read_group_idx, (g,), -1),
        np.zeros((g,), bool), np.zeros((g,), bool),
    )


def fused_bc_dummy_args(b, g: int, gl: int) -> tuple:
    """fused_bc_body's 10 per-row args at grid (g rows, gl lanes) —
    the observe signature, then the apply side's ``has_qual``/``valid``;
    the u8 table dummy and the statics (n_rg, gl, g*gl) follow at the
    call site."""
    return observe_dummy_args(b, g, gl) + (
        np.zeros((g,), bool), np.zeros((g,), bool),
    )


def streamed_prewarm_entries(
    b, n_rg: int, *, mark_duplicates: bool = True, recalibrate: bool = True,
    fused_n_cyc: int | None = None,
) -> list[tuple]:
    """The grid-quantized kernel set the streamed device path dispatches,
    as prewarm entries derived from the first window's numpy view ``b``
    (shapes AND dtypes must match the real dispatches bit-for-bit or the
    jit cache treats the warm call as a different program).

    One program per pass, each the variant a dispatch against a
    :class:`ResidentWindow` calls (the donating twin where
    :func:`donation_ok`): the markdup key/score reductions (pass A), the
    bit-packed-mask observe (pass B), and the fused bases+quals pack2
    apply (pass C) at window 0's own table width — pass C re-warms at
    the solved width through :func:`apply_prewarm_entry`.
    ``fused_n_cyc`` (the known table's cycle width) additionally warms
    the fused B→C megakernel the known-table tier dispatches
    (docs/PERF.md "Megakernel tier").
    """
    import jax

    from adam_tpu.formats.batch import (
        grid_cigar_cols, grid_cols, grid_rows,
    )

    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)
    gc = grid_cigar_cols(
        b.cigar_ops.shape[1] if b.cigar_ops.ndim == 2 else 1
    )

    entries: list[tuple] = []
    if mark_duplicates:
        def warm_markdup(dev, g=g, gl=gl, gc=gc):
            from adam_tpu.pipelines.markdup import get_columns_jit

            args = tuple(
                jax.device_put(a, dev)
                for a in markdup_dummy_args(b, g, gl, gc)
            )
            jax.block_until_ready(
                get_columns_jit(donate=donation_ok(dev))(*args)
            )

        entries.append((("markdup.columns", g, gc, gl), warm_markdup))

    if recalibrate:
        entries.append(observe_packed_prewarm_entry(b, n_rg))
        # pass A can only assume the solved table will match window 0's
        # grid width; pass C re-warms with the REAL merged width (same
        # key space, so uniform-lmax inputs dedupe it to a no-op)
        entries.append(apply_prewarm_entry(b, n_rg, 2 * gl + 1))
        if fused_n_cyc is not None:
            # the fused B→C megakernel, at the KNOWN table's real
            # cycle width (never 2*gl+1: the known table's geometry is
            # the cohort's, not this window's)
            entries.append(fused_bc_prewarm_entry(b, n_rg, fused_n_cyc))
    return entries


def _warm_variant(kind: str, args_fn, statics: tuple):
    """A prewarm thunk for one ``bqsr.jit_variant`` kind: the variant
    its dispatch calls on ``dev`` (donating where :func:`donation_ok`),
    run to completion on ``args_fn()``'s dummies."""
    import jax

    def warm(dev):
        from adam_tpu.pipelines.bqsr import jit_variant

        placed = tuple(jax.device_put(a, dev) for a in args_fn())
        jax.block_until_ready(
            jit_variant(kind, donation_ok(dev))(*placed, *statics)
        )

    return warm


def observe_packed_prewarm_entry(b, n_rg: int) -> tuple:
    """Prewarm entry for the pass-B observe (``bqsr.observe_packed_body``,
    bit-packed masks, donating its mask temporaries where
    :func:`donation_ok`) at one batch view's grid shape.  Also the
    long-tail re-warm: residual windows and the realigned tail part land
    on grids window 0 never saw, so the streamed pipeline re-prewarms
    on first sight of a new shape through the same process-wide dedupe
    cache (already-warm shapes are free)."""
    from adam_tpu.formats.batch import grid_cols, grid_rows

    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)
    return (("bqsr.observe_packed", g, gl, n_rg), _warm_variant(
        "observe_packed", lambda: observe_dummy_args(b, g, gl), (n_rg, gl),
    ))


def fused_bc_prewarm_entry(b, n_rg: int, table_n_cyc: int) -> tuple:
    """Prewarm entry for the fused B→C megakernel
    (``bqsr.fused_bc_body``) keyed by the known table's real cycle
    width — dispatched when the recalibration table is available at
    ingest (known-sites runs, discovered-table resumes)."""
    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.pipelines.bqsr import N_DINUC, N_QUAL

    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)
    return (("bqsr.fused_bc", g, gl, n_rg, table_n_cyc), _warm_variant(
        "fused_bc",
        lambda: fused_bc_dummy_args(b, g, gl) + (np.zeros(
            (n_rg, N_QUAL, table_n_cyc, N_DINUC), np.uint8
        ),),
        (n_rg, gl, g * gl),
    ))


def apply_prewarm_entry(b, n_rg: int, table_n_cyc: int) -> tuple:
    """Pass-C prewarm entry: the fused bases+quals pack2 apply
    (``bqsr.apply_pack2_body``) keyed by the table's real cycle width.
    ``merge_observations`` widens the table to the maximum window grid,
    which can exceed the window-0 width the pass-A prewarm assumed —
    so pass C re-warms at the SOLVED width; the shared key space
    dedupes the uniform-lmax common case to a no-op against the
    process-wide cache."""
    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.pipelines.bqsr import N_DINUC, N_QUAL

    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)
    return (("bqsr.apply_pack2", g, gl, n_rg, table_n_cyc), _warm_variant(
        "apply_pack2",
        lambda: apply_dummy_args(b, g, gl) + (np.zeros(
            (n_rg, N_QUAL, table_n_cyc, N_DINUC), np.uint8
        ),),
        (gl, g * gl),
    ))


# --------------------------------------------------------------------------
# Realign sweep fan-out: weighted round-robin over the pool/mesh devices
# --------------------------------------------------------------------------
#: Process-wide probe cache: device key -> TFLOP/s (one probe per
#: device per process; the probe kernel compiles once and is tiny).
_PROBE_TFLOPS: dict = {}
_PROBE_LOCK = threading.Lock()


def probe_device_tflops(device) -> float:
    """One small timed f32 matmul on ``device`` -> TFLOP/s (cached per
    process).  Deliberately light: it only needs RELATIVE skew between
    chips to pace the sweep scheduler, not an absolute ceiling."""
    key = _device_key(device)
    with _PROBE_LOCK:
        got = _PROBE_TFLOPS.get(key)
    if got is not None:
        return got
    try:
        import jax
        import jax.numpy as jnp

        n = 1024
        a = jax.device_put(jnp.ones((n, n), jnp.float32), device)
        jax.block_until_ready(a @ a)  # compile + first run
        # best-of-3: a single timed rep caught mid-stall would mislabel
        # the device for the whole process (the cache below is
        # permanent) and skew the sweep schedule WORSE than plain
        # round-robin
        best_dt = float("inf")
        for _ in range(3):
            t0 = time.monotonic()
            jax.block_until_ready(a @ a)
            best_dt = min(best_dt, max(time.monotonic() - t0, 1e-9))
        tf = 2 * n**3 / best_dt / 1e12
    except Exception:
        # NOT cached: a transient probe error must not permanently
        # disable probe pacing for the process — the next schedule
        # construction re-probes
        return 0.0
    with _PROBE_LOCK:
        _PROBE_TFLOPS[key] = tf
    return tf


def sweep_weights(devices) -> list[float]:
    """Relative throughput weight per device for the sweep scheduler.

    Order: ``ADAM_TPU_SWEEP_TFLOPS`` (comma-separated floats; entry k
    weights device **id** k, so eviction-shrunk device lists still pace
    the right chips; ids past the list fall back to the mean, malformed
    values degrade to equal weights), then a one-time in-process matmul
    probe on TPU devices, then equal weights (virtual-CPU test
    meshes are symmetric by construction — probing them measures
    scheduler noise).
    """
    n = len(devices)
    raw = os.environ.get("ADAM_TPU_SWEEP_TFLOPS", "").strip()
    if raw:
        try:
            vals = [float(v) for v in raw.split(",") if v.strip()]
            if vals and all(v > 0 for v in vals):
                mean = sum(vals) / len(vals)
                # match by device ID, not list position: after an
                # eviction the caller passes the SURVIVORS, and a
                # positional match would pace every chip with its dead
                # neighbor's weight
                out = []
                for i, d in enumerate(devices):
                    dev_id = getattr(d, "id", i)
                    out.append(
                        vals[dev_id]
                        if isinstance(dev_id, int) and 0 <= dev_id < len(vals)
                        else mean
                    )
                return out
        except ValueError:
            pass
        log.warning(
            "ADAM_TPU_SWEEP_TFLOPS=%r is not a comma list of positive "
            "floats; using equal weights", raw,
        )
        return [1.0] * n
    from adam_tpu.parallel.mesh import on_tpu

    if any(on_tpu(d) for d in devices):
        probed = [probe_device_tflops(d) for d in devices]
        if all(v > 0 for v in probed):
            return probed
    return [1.0] * n


class SweepSchedule:
    """Deterministic deficit round-robin over a device set: chunk ``k``
    goes to the device with the largest accumulated credit
    (``weight share × chunks seen − chunks assigned``), so a chip with
    2× the probe throughput receives 2× the sweep chunks.  Equal
    weights degrade to plain round-robin.  Placement never affects the
    sweep VALUES (each chunk is self-contained), so pacing is free to
    chase the grant skew run by run."""

    def __init__(self, devices, weights=None):
        self.devices = list(devices)
        w = list(weights) if weights is not None else sweep_weights(
            self.devices
        )
        total = sum(w) or 1.0
        self._share = [v / total for v in w]
        self._credit = [0.0] * len(self.devices)

    def next_device(self):
        for i, s in enumerate(self._share):
            self._credit[i] += s
        i = max(range(len(self._credit)), key=lambda k: self._credit[k])
        self._credit[i] -= 1.0
        return self.devices[i]
