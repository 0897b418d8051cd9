"""Continuous cross-job window batching (docs/SERVING.md "Continuous
batching & quotas").

PR 10's scheduler interleaves concurrent jobs at *window* granularity:
every job's window is its own mesh/pool dispatch, so N small jobs pay N
per-window dispatch overheads while each one under-fills the device
grid — exactly when the multi-tenant story needs the grid full.  This
module is the vLLM/Orca-style move for the streamed flagship: a
:class:`WindowCoalescer` sits between the `JobScheduler` and the
execution seam, collects ready windows from concurrent jobs (WFQ-
ordered by the fairness interleaver's tenant clocks, bounded batching
delay ``ADAM_TPU_BATCH_WAIT_MS``), and merges them into **one fused
dispatch per pass**:

* the fused grid is ONE ``[N_total, L]`` stack of per-job row blocks —
  each block is the job's own grid-quantized window (its
  :class:`~adam_tpu.parallel.device_pool.ResidentWindow` device arrays
  when the handle is alive on the target device, so coalescing does
  not re-ship ingested payloads; the host-retained ingest copy
  otherwise), concatenated *inside* the fused jit so the executable
  set stays keyed by the bucket-quantized block shapes;
* pass-B observe histograms accumulate into **per-job segments** of one
  scatter-add: each job's read-group indices offset into a disjoint
  band of the fused table, so slicing its band back out is bitwise the
  histogram its solo dispatch would have produced (integer scatter-adds
  over disjoint bins commute with concatenation);
* pass-C applies gather from one rg-concatenated table and emit one
  flat packed qual payload whose **per-job byte ranges are exact** (the
  row-prefix pack is a prefix concatenation in row order), so each
  job's Arrow parts stay byte-identical to its solo run.

Fault contract: a fused dispatch that fails past its retry budget fails
only the tickets it carried — every affected job falls back to its own
solo dispatch path (which owns eviction/replay/host-degrade), so a
poison window quarantines its job while survivors replay from their
host ingest copies, byte-identically (``sched.batch.fallbacks`` counts
the windows that took the detour).  The ``sched.batch`` fault point
arrives once per fused dispatch; ``proc.kill device=batch`` is the
chaos harness's mid-batch kill phase.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

import numpy as np

from adam_tpu.utils import faults
from adam_tpu.utils import telemetry as tele

log = logging.getLogger(__name__)

#: Bounded batching delay (milliseconds): how long the coalescer holds
#: a group open for more jobs' windows before dispatching what it has.
DEFAULT_BATCH_WAIT_MS = 25.0

#: Backstop on a blocking ticket wait: the dispatcher failing all
#: tickets on any error makes this unreachable in practice, but a
#: wedged device RPC must surface as a fallback, not a hang.
_RESULT_TIMEOUT_S = 600.0

#: Tickets per fused dispatch, capped: the fused executable is keyed
#: by the per-ticket block-shape tuple, so unbounded group sizes would
#: grow the executable set one compile per distinct ticket COUNT (pass
#: B defers a job's whole window set at once).  8 keeps the compile
#: ledger's bounded-set contract while still fusing multiple windows
#: per job; overflow tickets simply form the next group.
MAX_GROUP_TICKETS = 8


def batch_wait_ms() -> float:
    """``ADAM_TPU_BATCH_WAIT_MS`` (default 25 ms; malformed or negative
    values warn and keep the default — ``utils/retry.env_float``, the
    shared tuning-var parser)."""
    from adam_tpu.utils.retry import env_float

    v = env_float("ADAM_TPU_BATCH_WAIT_MS", DEFAULT_BATCH_WAIT_MS)
    if v < 0:
        log.warning(
            "ADAM_TPU_BATCH_WAIT_MS=%s is negative; using default "
            "%.0fms", v, DEFAULT_BATCH_WAIT_MS,
        )
        return DEFAULT_BATCH_WAIT_MS
    return v


def batching_enabled(default: bool = False) -> bool:
    """``ADAM_TPU_BATCH`` toggle (default off — batching changes
    latency shape, so the operator opts in; ``adam-tpu serve --batch``
    sets it)."""
    from adam_tpu.utils.retry import env_toggle

    return env_toggle("ADAM_TPU_BATCH", default)


class CoalesceError(RuntimeError):
    """A ticket's fused dispatch failed (or the coalescer is stopping):
    the caller falls back to its solo dispatch path."""


class _Future:
    """Event-backed single-value future (no cancellation: the
    dispatcher resolves or fails every ticket it accepts).
    ``dataset`` carries the apply ticket's pre-recalibration dataset so
    a failed fused dispatch can re-apply solo without re-pinning the
    window anywhere else."""

    __slots__ = ("_ev", "_value", "_error", "dataset")

    def __init__(self):
        self._ev = threading.Event()
        self._value = None
        self._error = None
        self.dataset = None

    def set_result(self, value) -> None:
        self._value = value
        self._ev.set()

    def set_error(self, exc: BaseException) -> None:
        self._error = exc
        self._ev.set()

    def result(self, timeout: float = _RESULT_TIMEOUT_S):
        if not self._ev.wait(timeout):
            raise CoalesceError(
                f"fused dispatch did not resolve within {timeout:.0f}s"
            )
        if self._error is not None:
            raise self._error
        return self._value


class _Ticket:
    __slots__ = (
        "kind", "key", "job", "tenant", "window", "seq", "t_submit",
        "n", "g", "gl", "payload", "fut", "trace",
    )

    def __init__(self, kind, key, job, tenant, window, seq, n, g, gl,
                 payload, trace=None):
        self.kind = kind
        self.key = key
        self.job = job
        self.tenant = tenant
        self.window = window
        self.seq = seq
        self.trace = trace  # the submitting job's trace_id (fan-in link)
        self.t_submit = time.monotonic()
        self.n = n          # real rows
        self.g = g          # grid rows (the block's leading dim)
        self.gl = gl
        self.payload = payload
        self.fut = _Future()


# --------------------------------------------------------------------------
# Fused kernel bodies (module-level traceable functions; the jits are
# built lazily below).  Each takes per-ticket column tuples and
# concatenates INSIDE the trace, so per-job ResidentWindow arrays feed
# the fused grid without a host round-trip and the executable is keyed
# by the bucket-quantized block shapes.
# --------------------------------------------------------------------------
def _fused_markdup_body(cols):
    """cols: per-ticket (start, end, flags, ops, lens, n_ops, quals,
    lengths), each block ``[g_i(, gc/gl)]`` — one fused [N_total] run of
    the markdup reductions (per-row integer math: each row's five/score
    is independent of every other row, so block slices are bitwise the
    solo columns)."""
    import jax.numpy as jnp

    from adam_tpu.pipelines.markdup import markdup_columns_local

    cat = [jnp.concatenate(xs, axis=0) for xs in zip(*cols)]
    return markdup_columns_local(*cat)


def _fused_observe_body(cols, masks, segs, n_rg: int, lmax: int):
    """cols: per-ticket (bases, quals, lengths, flags, read_group_idx);
    masks: per-ticket (res_bits, mm_bits, read_ok) with the MD masks
    bit-packed (colpack, 8x); segs: per-ticket ``(rg_base, n_rg_i)``
    (static).  Each ticket's read-group indices resolve (null bin =
    its own ``n_rg_i - 1``) then offset by ``rg_base`` into a disjoint
    band of the fused histogram — ONE scatter-add, per-job segments
    bitwise the solo histograms."""
    import jax.numpy as jnp

    from adam_tpu.ops.colpack import unpack_mask_body
    from adam_tpu.pipelines.bqsr import observe_kernel

    parts = []
    for (bases, quals, lengths, flags, rg), \
            (res_pk, mm_pk, read_ok), (base, nri) in zip(
                cols, masks, segs):
        residue_ok = unpack_mask_body(res_pk, lmax)
        is_mm = unpack_mask_body(mm_pk, lmax)
        rg_off = (
            jnp.where(rg >= 0, rg, nri - 1).astype(jnp.int32) + base
        )
        parts.append((bases, quals, lengths, flags, rg_off,
                      residue_ok, is_mm, read_ok))
    cat = [jnp.concatenate(xs, axis=0) for xs in zip(*parts)]
    return observe_kernel.__wrapped__(*cat, n_rg, lmax)


def _fused_apply_body(cols, extras, table, segs, lmax: int,
                      pack_size: int):
    """cols: per-ticket (bases, quals, lengths, flags, read_group_idx);
    extras: per-ticket (has_qual, valid); table: the rg-concatenated
    (cycle-centered) fused table; segs as in the observe body.  One
    fused table gather, then the on-device SANGER encode + row-prefix
    pack over ``pack_size`` (static, the fused grid area) — the flat
    payload's per-job byte ranges are exact prefix sums, so slicing
    them back out is bitwise each job's solo packed qual column."""
    import jax.numpy as jnp

    from adam_tpu.ops.colpack import pack_rows_body, sanger_body
    from adam_tpu.pipelines.bqsr import apply_table_body

    parts = []
    for (bases, quals, lengths, flags, rg), (hq, vd), (base, nri) in zip(
            cols, extras, segs):
        rg_off = (
            jnp.where(rg >= 0, rg, nri - 1).astype(jnp.int32) + base
        )
        parts.append((bases, quals, lengths, flags, rg_off, hq, vd))
    cat = [jnp.concatenate(xs, axis=0) for xs in zip(*parts)]
    new_q = apply_table_body(*cat, table, lmax)
    lengths_cat, hq_cat, vd_cat = cat[2], cat[5], cat[6]
    pack_lens = jnp.where(vd_cat & hq_cat, lengths_cat, 0)
    return pack_rows_body(sanger_body(new_q), pack_lens, pack_size)


_FUSED_JITS: dict = {}
_FUSED_JITS_LOCK = threading.Lock()


def fused_jit(kind: str):
    """Lazily-built module-level jit for one fused body (one wrapper
    per kind, shared by warm + dispatch so both hit one executable
    cache — the markdup/observe/apply twins of ``bqsr.jit_variant``)."""
    fn = _FUSED_JITS.get(kind)
    if fn is not None:
        return fn
    with _FUSED_JITS_LOCK:
        fn = _FUSED_JITS.get(kind)
        if fn is not None:
            return fn
        import jax

        if kind == "markdup":
            fn = jax.jit(_fused_markdup_body)
        elif kind == "observe":
            fn = jax.jit(
                _fused_observe_body,
                static_argnames=("segs", "n_rg", "lmax"),
            )
        elif kind == "apply":
            fn = jax.jit(
                _fused_apply_body,
                static_argnames=("segs", "lmax", "pack_size"),
            )
        else:
            raise ValueError(f"unknown fused kind {kind!r}")
        _FUSED_JITS[kind] = fn
    return fn


def _zeros_like_tree(tree):
    """Host-zeros twin of a (possibly device-resident) arg pytree —
    the warm call's dummy payload (shapes/dtypes only matter)."""
    if isinstance(tree, (tuple, list)):
        return tuple(_zeros_like_tree(x) for x in tree)
    return np.zeros(tree.shape, tree.dtype)


class CoalescerClient:
    """One job's bound handle onto the shared coalescer — what the
    scheduler passes into ``transform_streamed(coalescer=...)`` so the
    pipeline never needs to know its own job identity."""

    def __init__(self, coalescer: "WindowCoalescer", job: str,
                 tenant: str, trace: Optional[str] = None):
        self._c = coalescer
        self.job = job
        self.tenant = tenant
        self.trace = trace

    def submit_markdup(self, window, batch, resident=None) -> _Future:
        return self._c.submit_markdup(
            self.job, self.tenant, window, batch, resident
        )

    def submit_observe(self, window, ds, known_snps=None,
                       resident=None) -> _Future:
        return self._c.submit_observe(
            self.job, self.tenant, window, ds, known_snps, resident
        )

    def submit_apply(self, window, ds, table, resident=None) -> _Future:
        return self._c.submit_apply(
            self.job, self.tenant, window, ds, table, resident,
        )


class WindowCoalescer:
    """Cross-job fused-dispatch engine (module docstring).

    ``pool``: the scheduler's shared DevicePool (None on single-device
    topologies — fused dispatches then run on the default device).
    ``interleaver``: the WFQ fairness interleaver whose tenant clocks
    order tickets inside a fused grid.  ``quota``: an optional
    :class:`~adam_tpu.serve.quota.QuotaManager` charged per fused
    dispatch with each tenant's byte/compute share."""

    def __init__(self, pool=None, wait_ms: Optional[float] = None,
                 interleaver=None, quota=None, tracer=None):
        self.pool = pool
        self.wait_s = (
            batch_wait_ms() if wait_ms is None else float(wait_ms)
        ) / 1e3
        self.interleaver = interleaver
        self.quota = quota
        self.tracer = tracer if tracer is not None else tele.TRACE
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._jobs: dict = {}   # job -> tenant (the eligible set)
        self._job_traces: dict = {}  # job -> trace_id (fan-in links)
        self._pending: list = []
        self._seq = 0
        self._rr = 0            # fused-dispatch round-robin cursor
        self._stopped = False
        self._warmed: set = set()
        # fused-table placements keyed by (job-sorted (job, table
        # identity) tuple, n_cyc, device): per-job solved tables are
        # constant for a run, so the pad-center+concat+h2d happens once
        # per job-set instead of once per fused pass-C dispatch.  The
        # cached VALUES hold the table objects, so the identity ids in
        # the keys can never collide with a recycled address.
        self._table_cache: dict = {}
        self._thread = threading.Thread(
            target=self._run, name="adam-tpu-coalescer", daemon=True
        )
        self._thread.start()

    # ---- job lifecycle (scheduler-side) --------------------------------
    def client(self, job: str, tenant: str = "default",
               trace: Optional[str] = None) -> CoalescerClient:
        """Register a job as coalesce-eligible and return its bound
        client (the scheduler calls this at admission).  ``trace`` is
        the job's trace_id: every ticket the client submits carries it,
        and the fused-dispatch span links back to it (the fan-in edge
        a job-scoped trace export follows across the batch)."""
        with self._lock:
            self._jobs[job] = tenant
            if trace is not None:
                self._job_traces[job] = trace
            self._cond.notify_all()
        return CoalescerClient(self, job, tenant, trace)

    def deregister(self, job: str) -> None:
        """Drop a job from the eligible set (idempotent); groups
        waiting on its windows flush at their next check."""
        with self._lock:
            self._jobs.pop(job, None)
            self._job_traces.pop(job, None)
            self._cond.notify_all()

    def stop(self) -> None:
        """Stop the dispatcher: pending groups flush immediately, new
        submissions raise (callers fall back solo)."""
        with self._lock:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=30.0)

    # ---- ticket submission (pipeline-side, via CoalescerClient) --------
    def _submit(self, kind, key, job, tenant, window, n, g, gl,
                payload) -> _Future:
        with self._lock:
            if self._stopped:
                raise CoalesceError("coalescer is stopped")
            self._seq += 1
            t = _Ticket(kind, key, job, tenant, window, self._seq,
                        n, g, gl, payload,
                        trace=self._job_traces.get(job))
            self._pending.append(t)
            self._cond.notify_all()
        return t.fut

    def submit_markdup(self, job, tenant, window, batch,
                       resident=None) -> _Future:
        from adam_tpu.formats import schema
        from adam_tpu.formats.batch import (
            grid_cigar_cols, grid_cols, grid_rows, pad_rows_np,
        )

        b = batch.to_numpy()
        g = grid_rows(b.n_rows)
        gl = grid_cols(b.lmax)
        gc = grid_cigar_cols(
            b.cigar_ops.shape[1] if b.cigar_ops.ndim == 2 else 1
        )
        payload = {
            "b": b,
            "resident": resident,
            # the markdup-specific columns always ship (exactly the
            # solo resident dispatch's per-pass inputs)
            "fresh": (
                pad_rows_np(b.start, g, -1),
                pad_rows_np(b.end, g, -1),
                pad_rows_np(b.cigar_ops, g, schema.CIGAR_PAD, cols=gc),
                pad_rows_np(b.cigar_lens, g, 0, cols=gc),
                pad_rows_np(b.cigar_n, g, 0),
            ),
        }
        return self._submit(
            "markdup", ("markdup", gl, gc), job, tenant, window,
            b.n_rows, g, gl, payload,
        )

    def submit_observe(self, job, tenant, window, ds, known_snps=None,
                       resident=None) -> _Future:
        from adam_tpu.formats.batch import grid_cols, grid_rows, pad_rows_np
        from adam_tpu.ops.colpack import pack_mask_bits
        from adam_tpu.pipelines import bqsr as bqsr_mod

        # the host-side mask prep runs on the JOB's thread (parallel
        # across jobs); the dispatcher thread only fuses and dispatches
        with tele.TRACE.span(
            tele.SPAN_BQSR_OBSERVE_PREP, reads=int(ds.batch.n_rows)
        ):
            b, read_ok, residue_ok, is_mm, n_rg = bqsr_mod.observe_inputs(
                ds, known_snps
            )
            bqsr_mod.count_observe_residues(b, read_ok)
            g = grid_rows(b.n_rows)
            gl = grid_cols(b.lmax)
            payload = {
                "b": b,
                "resident": resident,
                "n_rg": n_rg,
                "masks": (
                    pack_mask_bits(
                        pad_rows_np(residue_ok, g, False, cols=gl)
                    ),
                    pack_mask_bits(pad_rows_np(is_mm, g, False, cols=gl)),
                    pad_rows_np(read_ok, g, False),
                ),
            }
        return self._submit(
            "observe", ("observe", gl), job, tenant, window,
            b.n_rows, g, gl, payload,
        )

    def submit_apply(self, job, tenant, window, ds, table,
                     resident=None) -> _Future:
        # the table's cycle half-width is NOT threaded through: the
        # fused gather derives it from the (pad-centered, concatenated)
        # fused table's own shape, exactly like apply_table_body
        from adam_tpu.formats.batch import grid_cols, grid_rows, pad_rows_np
        from adam_tpu.ops.colpack import pack_lengths
        from adam_tpu.pipelines import bqsr as bqsr_mod

        b = ds.batch.to_numpy()
        bqsr_mod.count_apply_residues(b)
        g = grid_rows(b.n_rows)
        gl = grid_cols(b.lmax)
        payload = {
            "ds": ds,
            "b": b,
            "resident": resident,
            "table": np.ascontiguousarray(table, np.uint8),
            "extras": (
                pad_rows_np(b.has_qual, g, False),
                pad_rows_np(b.valid, g, False),
            ),
            "pack_lens": pack_lengths(b.lengths, b.valid, b.has_qual),
        }
        fut = self._submit(
            "apply", ("apply", gl), job, tenant, window,
            b.n_rows, g, gl, payload,
        )
        fut.dataset = ds
        return fut

    # ---- the dispatcher thread -----------------------------------------
    def _wfq_rank(self, t: _Ticket):
        """WFQ ordering inside a fused grid: the fairness interleaver's
        tenant virtual clock first (smaller clock = more underserved
        tenant = earlier rows), submission order within a tenant."""
        vt = None
        if self.interleaver is not None:
            vt = self.interleaver.tenant_clock(t.tenant)
        return (vt if vt is not None else 0.0, t.tenant, t.seq)

    def _take_group_locked(self) -> Optional[list]:
        """The oldest pending (kind, key) group once it is ripe:
        every eligible job is accounted for (in THIS group, or
        demonstrably busy with a pending ticket of a different
        bucket — a job mid-flight on another (kind, key) cannot
        contribute here before its own group resolves, so waiting for
        it only adds latency), the bounded delay expired, or the
        coalescer is stopping.  None = keep waiting.  Caller holds
        the lock."""
        if not self._pending:
            return None
        head = min(self._pending, key=lambda t: t.seq)
        grp = []
        busy_elsewhere = set()
        for t in self._pending:
            if (t.kind, t.key) == (head.kind, head.key):
                grp.append(t)
            else:
                busy_elsewhere.add(t.job)
        jobs_in = {t.job for t in grp}
        ripe = (
            self._stopped
            or (jobs_in | busy_elsewhere) >= set(self._jobs)
            or time.monotonic() - head.t_submit >= self.wait_s
        )
        if not ripe:
            return None
        if len(grp) > MAX_GROUP_TICKETS:
            # oldest first; the overflow stays pending and forms the
            # next group (already past its deadline, so no added wait)
            grp = sorted(grp, key=lambda t: t.seq)[:MAX_GROUP_TICKETS]
        drop = set(id(t) for t in grp)
        self._pending = [t for t in self._pending if id(t) not in drop]
        return grp

    def _run(self) -> None:
        while True:
            with self._lock:
                while True:
                    grp = self._take_group_locked()
                    if grp is not None:
                        break
                    if self._stopped and not self._pending:
                        return
                    if not self._pending:
                        # idle: sleep until a submit/deregister/stop
                        # notifies — no polling on a quiet service
                        self._cond.wait()
                        continue
                    # wake at the head ticket's deadline (or on a new
                    # ticket / a deregistration / stop)
                    head = min(self._pending, key=lambda t: t.seq)
                    timeout = max(
                        1e-3,
                        head.t_submit + self.wait_s - time.monotonic(),
                    )
                    self._cond.wait(min(timeout, 0.05))
            self._dispatch_group(grp)

    def _target_device(self, grp: list):
        """The fused dispatch's device: the first alive resident
        handle's pin (so coalescing consumes resident arrays in place),
        else a round-robin pool survivor, else the default device."""
        for t in grp:
            rw = t.payload.get("resident")
            if rw is not None and rw.alive and not isinstance(
                rw.device, str
            ):
                return rw.device
        if self.pool is not None:
            alive = self.pool.alive_devices()
            if alive:
                self._rr += 1
                return alive[self._rr % len(alive)]
        return None

    def _ticket_resident(self, t: _Ticket, device):
        """The ticket's usable resident handle on ``device`` (solo
        validity rules: alive, same pin, same grid), else None — the
        block then re-ships from the host ingest copy."""
        rw = t.payload.get("resident")
        if rw is not None and rw.alive and rw.device is device \
                and rw.g == t.g and rw.gl == t.gl:
            return rw
        return None

    def _resident_cols(self, t: _Ticket, device, put):
        """The five kernel columns for one ticket: the ResidentWindow
        arrays in place when usable, else the grid-padded host copy
        placed fresh (the placement itself books the re-ship in the
        h2d transfer ledger, under the ``batch`` pass bucket)."""
        from adam_tpu.parallel.device_pool import resident_arrays

        rw = self._ticket_resident(t, device)
        if rw is not None:
            return rw.args()
        # the ticket's handle is dead or pinned on another chip than the
        # group's target: the block places from the host-retained
        # ingest copy, bitwise the same rows
        return resident_arrays(t.payload["b"], t.g, t.gl, put)[0]

    def warm_fused_executable(self, kind, jitfn, args, statics, key,
                              device) -> None:
        """First-sight prewarm of a fused shape: run the jit on a
        zeros twin of the args under a prewarm scope, so the REAL
        dispatch records a cache hit and ``device.compile.in_window``
        stays 0 on batched runs (the coalescer's analog of the pool's
        first-sight re-prewarm)."""
        from adam_tpu.parallel.device_pool import putter, span_attrs
        from adam_tpu.utils import compile_ledger

        cache_key = (key, compile_ledger.device_cache_key(device))
        with self._lock:
            if cache_key in self._warmed:
                return
            self._warmed.add(cache_key)
        put = putter(device)

        def place(tree):
            if isinstance(tree, tuple):
                return tuple(place(x) for x in tree)
            return put(tree)

        try:
            with self.tracer.span(
                tele.SPAN_POOL_PREWARM_COMPILE, kernel=str(key[0]),
                **span_attrs(device),
            ), compile_ledger.prewarm_scope(), \
                    tele.pass_scope("prewarm"), \
                    compile_ledger.track(key, device):
                jitfn(*(place(_zeros_like_tree(a)) for a in args),
                      **statics)
        except Exception:
            with self._lock:
                self._warmed.discard(cache_key)
            log.warning(
                "fused prewarm of %s failed; the shape compiles at "
                "dispatch instead", key, exc_info=True,
            )

    def _dispatch_group(self, grp: list) -> None:
        """Fuse + dispatch one group; resolve every ticket's future
        (failures fail the whole group — each caller falls back to its
        solo path, which owns eviction/replay)."""
        grp.sort(key=self._wfq_rank)
        kind = grp[0].kind
        # the fan-in span: a fused dispatch serves MANY job traces at
        # once, so instead of claiming one it links every contributing
        # (job, window, trace) — events_for_trace / the gateway /trace
        # surface resolve these links so each job's export crosses the
        # fused-batch boundary (docs/OBSERVABILITY.md "Trace context")
        links = [
            {"job": t.job, "window": t.window, "trace": t.trace}
            for t in grp
        ]
        try:
            faults.point("sched.batch", device=kind)
            # chaos-harness kill point: one arrival per fused dispatch
            faults.point("proc.kill", device="batch")
            with self.tracer.span(
                tele.SPAN_BATCH_FUSED, kind=kind, windows=len(grp),
                links=links,
            ), tele.pass_scope("batch"):
                if kind == "markdup":
                    results, wall = self._fuse_markdup(grp)
                elif kind == "observe":
                    results, wall = self._fuse_observe(grp)
                else:
                    results, wall = self._fuse_apply(grp)
        except BaseException as e:
            self.tracer.count(tele.C_BATCH_FALLBACKS, len(grp))
            log.warning(
                "fused %s dispatch of %d window(s) failed (%s); every "
                "carried job re-dispatches solo", kind, len(grp), e,
            )
            err = CoalesceError(
                f"fused {kind} dispatch failed: {type(e).__name__}: {e}"
            )
            for t in grp:
                t.fut.set_error(err)
            return
        rows_occ = sum(t.n for t in grp)
        rows_disp = sum(t.g for t in grp)
        tr = self.tracer
        tr.count(tele.C_BATCH_DISPATCHES)
        tr.count(tele.C_BATCH_WINDOWS, len(grp))
        tr.count(tele.C_BATCH_ROWS_OCCUPIED, rows_occ)
        tr.count(tele.C_BATCH_ROWS_DISPATCHED, rows_disp)
        tr.observe(tele.H_BATCH_FILL, rows_occ / max(rows_disp, 1))
        tr.gauge(tele.G_BATCH_JOBS, len({t.job for t in grp}))
        if self.quota is not None:
            # the COMPUTE leg of the tenant's budget: each ticket's
            # rows-weighted share of the fused DISPATCH+FETCH wall —
            # the executors time exactly that region, so first-sight
            # compiles (the prewarm above) and host pad/placement prep
            # never bill against a tenant's compute budget.  The byte
            # leg is charged at the grant seam (the scheduler's pacer
            # wrapper charges every window's payload size); the fused
            # h2d books in the transfer ledger's `batch` bucket, never
            # as a second byte charge.
            for t in grp:
                self.quota.charge(
                    t.tenant, compute_s=wall * t.n / max(rows_occ, 1),
                )
        for t, res in zip(grp, results):
            t.fut.set_result(res)

    # ---- the three fused executors -------------------------------------
    def _fuse_markdup(self, grp: list):
        from adam_tpu.parallel.device_pool import putter
        from adam_tpu.utils import compile_ledger
        from adam_tpu.utils import retry as _retry
        from adam_tpu.utils.transfer import device_fetch

        device = self._target_device(grp)
        put = putter(device)
        cols = []
        for t in grp:
            start, end, ops, lens, n_ops = t.payload["fresh"]
            _bases, quals, lengths, flags, _rg = self._resident_cols(
                t, device, put
            )
            per = (put(start), put(end), flags, put(ops), put(lens),
                   put(n_ops), quals, lengths)
            cols.append(per)
        jitfn = fused_jit("markdup")
        key = (
            "batch.markdup",
            tuple((t.g, t.gl, grp[0].key[2]) for t in grp),
        )
        args = (tuple(cols),)
        self.warm_fused_executable(
            "markdup", jitfn, args, {}, key, device
        )

        def dispatch():
            faults.point("device.dispatch", device=device)
            return jitfn(tuple(cols))

        t_d = time.monotonic()
        with compile_ledger.track(key, device):
            five, score = _retry.retry_call(
                dispatch, site="sched.batch.dispatch"
            )
        five = device_fetch(five)
        score = device_fetch(score)
        wall = time.monotonic() - t_d
        self.tracer.count(tele.C_DEVICE_DISPATCHED)
        self.tracer.count(tele.C_DEVICE_FETCHED)
        results = []
        r0 = 0
        for t in grp:
            results.append((
                np.asarray(five[r0:r0 + t.n]),
                np.asarray(score[r0:r0 + t.n]),
            ))
            r0 += t.g
        return results, wall

    def _fuse_observe(self, grp: list):
        from adam_tpu.parallel.device_pool import putter
        from adam_tpu.utils import compile_ledger
        from adam_tpu.utils import retry as _retry
        from adam_tpu.utils.transfer import device_fetch

        device = self._target_device(grp)
        put = putter(device)
        gl = grp[0].gl
        cols = []
        masks = []
        segs = []
        base = 0
        for t in grp:
            cols.append(self._resident_cols(t, device, put))
            res_pk, mm_pk, rok = t.payload["masks"]
            masks.append((put(res_pk), put(mm_pk), put(rok)))
            segs.append((base, t.payload["n_rg"]))
            base += t.payload["n_rg"]
        n_rg_total = base
        jitfn = fused_jit("observe")
        key = (
            "batch.observe",
            tuple((t.g, t.payload["n_rg"]) for t in grp), gl,
        )
        statics = {
            "segs": tuple(segs), "n_rg": n_rg_total, "lmax": gl,
        }
        args = (tuple(cols), tuple(masks))
        self.warm_fused_executable(
            "observe", jitfn, args, statics, key, device
        )

        def dispatch():
            faults.point("device.dispatch", device=device)
            return jitfn(tuple(cols), tuple(masks), **statics)

        t_d = time.monotonic()
        with compile_ledger.track(key, device):
            total, mism = _retry.retry_call(
                dispatch, site="sched.batch.dispatch"
            )
        # ONE compact fetch for the whole group; each job's band is its
        # solo histogram, so the barrier merge stays bit-identical
        total = device_fetch(total)
        mism = device_fetch(mism)
        wall = time.monotonic() - t_d
        self.tracer.count(tele.C_DEVICE_DISPATCHED)
        self.tracer.count(tele.C_DEVICE_FETCHED)
        results = []
        for (b0, nri), t in zip(segs, grp):
            results.append((
                np.ascontiguousarray(total[b0:b0 + nri]),
                np.ascontiguousarray(mism[b0:b0 + nri]),
                gl,
            ))
        return results, wall

    def _fuse_apply(self, grp: list):
        from adam_tpu.ops.colpack import fetch_grid
        from adam_tpu.parallel.device_pool import putter
        from adam_tpu.utils import compile_ledger
        from adam_tpu.utils import retry as _retry
        from adam_tpu.utils.transfer import device_fetch

        device = self._target_device(grp)
        put = putter(device)
        gl = grp[0].gl
        # fused table: every job's solved table centered into the
        # widest cycle axis (exactly merge_observations' centering, so
        # each job's gathers land on its own cells), concatenated on
        # the read-group axis in JOB-SORTED order — the band layout is
        # independent of the WFQ row order, so the placement cache
        # below hits across dispatches of the same job set
        job_tables = {
            j: tb for j, tb in sorted(
                {t.job: t.payload["table"] for t in grp}.items()
            )
        }
        n_cyc = max(tb.shape[2] for tb in job_tables.values())
        cache_key = (
            tuple((j, id(tb)) for j, tb in job_tables.items()),
            n_cyc, compile_ledger.device_cache_key(device),
        )
        cached = self._table_cache.get(cache_key)
        if cached is not None:
            _tables, table_dev, bands = cached
        else:
            tparts = []
            bands = {}
            base = 0
            for j, tbl in job_tables.items():
                off = (n_cyc - tbl.shape[2]) // 2
                wide = tbl
                if off:
                    wide = np.zeros(
                        (tbl.shape[0], tbl.shape[1], n_cyc,
                         tbl.shape[3]),
                        np.uint8,
                    )
                    wide[:, :, off:off + tbl.shape[2], :] = tbl
                tparts.append(wide)
                bands[j] = (base, tbl.shape[0])
                base += tbl.shape[0]
            fused_table = np.ascontiguousarray(
                np.concatenate(tparts, axis=0)
            )
            with tele.pass_scope("table"):
                table_dev = put(fused_table)
            if len(self._table_cache) >= 8:
                self._table_cache.clear()
            self._table_cache[cache_key] = (
                tuple(job_tables.values()), table_dev, bands,
            )
        segs = [bands[t.job] for t in grp]
        cols = []
        extras = []
        for t in grp:
            cols.append(self._resident_cols(t, device, put))
            hq, vd = t.payload["extras"]
            extras.append((put(hq), put(vd)))
        size = sum(t.g for t in grp) * gl
        jitfn = fused_jit("apply")
        key = (
            "batch.apply",
            tuple((t.g, t.payload["table"].shape[0]) for t in grp),
            gl, n_cyc,
        )
        statics = {"segs": tuple(segs), "lmax": gl, "pack_size": size}
        args = (tuple(cols), tuple(extras), table_dev)
        self.warm_fused_executable(
            "apply", jitfn, args, statics, key, device
        )

        def dispatch():
            faults.point("device.dispatch", device=device)
            return jitfn(tuple(cols), tuple(extras), table_dev,
                         **statics)

        t_d = time.monotonic()
        with compile_ledger.track(key, device):
            out = _retry.retry_call(dispatch, site="sched.batch.dispatch")
        self.tracer.count(tele.C_DEVICE_DISPATCHED)
        results = []
        totals = [int(t.payload["pack_lens"].sum()) for t in grp]
        cut = min(size, fetch_grid(sum(totals)))
        payload = device_fetch(out[:cut])
        self.tracer.count(tele.C_DEVICE_FETCHED)
        off = 0
        for t, total_t in zip(grp, totals):
            # the per-job packed-column payload split: the fused pack's
            # byte ranges are exact prefix sums, so this slice IS the
            # job's solo packed payload
            sl = np.ascontiguousarray(payload[off:off + total_t])
            off += total_t
            results.append((
                t.payload["ds"], t.payload["b"],
                ("packed", [(sl, total_t)], t.payload["pack_lens"]),
            ))
        return results, time.monotonic() - t_d
