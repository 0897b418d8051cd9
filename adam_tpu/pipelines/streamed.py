"""Streamed, overlapped flagship transform.

The reference's ``Bam2ADAM`` queue-and-workers design
(adam-cli/src/main/scala/org/bdgenomics/adam/cli/Bam2ADAM.scala:55-111)
promoted to the whole ``transform`` pipeline
(adam-cli/.../Transform.scala:101-163): instead of load-everything then
run-each-stage-serially, the input is tokenized in windows and the
pipeline runs as three overlapped passes with two global barriers:

  pass A   ingest thread tokenizes window i+1 (threaded C++) while the
           main thread computes window i's duplicate-marking summary and
           indel-event list — compact per-row columns, never [N, L]
           temporaries.
  barrier  global duplicate resolution (one lexsort cascade over the
           spliced summaries) and global target merge — the same
           decisions the single-batch path makes, so window edges are
           invisible (a duplicate group or realignment target spanning
           two windows resolves exactly as in one batch).
  pass B   per-window realignment-candidate split (pre-BQSR quals, as
           the reference composes: markdup -> realign -> BQSR,
           Transform.scala:121-144) + BQSR observation of each window's
           remainder under the resolved duplicate flags.
  tail     rows mapped to realignment targets (gathered across all
           windows, so boundary-spanning targets see all their reads)
           realign together — device sweep kernels — then the realigned
           part is observed with its POST-realignment alignments (the
           composition-order-visible piece of adamBQSR-after-realign).
  barrier  merge histograms, solve the recalibration table.
  pass C   per-window recalibration apply, while a double-buffered
           writer pool encodes finished windows to Parquet part files
           (the Spark executor part-file layout: ``out.adam/part-*``);
           the realigned part applies and lands in the final part file.
           On the device backend the apply is a chip-side table gather,
           double-buffered: window i+1's gather runs while window i's
           recalibrated quals fetch and its part encodes.

With a chip attached the per-residue passes default to the device
kernels (``ADAM_TPU_BQSR_BACKEND`` overrides; see
:func:`adam_tpu.pipelines.bqsr.bqsr_backend`): the markdup [N, L]
key/score reductions dispatch during pass A's ingest overlap, every
window's BQSR observe scatter-adds on device and is fetched (compact
histograms only) at the merge barrier, and pass C gathers recalibrated
quals on device.

With more than one chip attached the device work additionally fans out
across a :class:`adam_tpu.parallel.device_pool.DevicePool`: window *i*'s
markdup reductions, observe scatter-adds and apply table-gathers land on
device ``i % n`` (``--devices N`` / ``ADAM_TPU_DEVICES`` select; the
``n == 1`` topology keeps the single-chip path bit-for-bit), each device
runs a double buffer deep in-flight queue, the solved recalibration
table is replicated once per device, and the per-device observe
histograms merge host-side at the barrier in window order — so the
multi-chip output is bit-identical to the single-chip one.  A compile
prewarm on the first window compiles the grid-quantized kernel set once
per device concurrently, so 20-40 s cold remote compiles never land
inside a window.

Wall-clock goal: max(stage) instead of sum(stages) — host codecs and
device kernels run at the same time, which is what a TPU-attached host
should be doing.
"""

from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import queue
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

from adam_tpu.api.datasets import AlignmentDataset
from adam_tpu.parallel import device_pool as dp_mod
from adam_tpu.utils import faults
from adam_tpu.utils import health as health_mod
from adam_tpu.utils import telemetry as tele
from adam_tpu.utils.transfer import device_fetch

log = logging.getLogger(__name__)

_SENTINEL = object()

#: Sentinel for "the device path is gone — run this window on the host
#: backend" (returned by the per-run ``_pick_device`` closure after the
#: last pool device is evicted, or after the single default chip fails).
_HOST = object()

#: Sentinel for "this window's observe was submitted to the cross-job
#: coalescer and its future parked on the caller's ``defer`` list" —
#: pass B submits every window before resolving any, so the coalescer
#: sees the whole window set and the job thread never serializes on a
#: single fused dispatch.
_DEFERRED = object()


class RunCancelled(BaseException):
    """Cooperative stop at a window boundary (the multi-job service's
    graceful drain, docs/ROBUSTNESS.md "Fault-isolated multi-job
    scheduling"): raised out of the per-window ``pacer`` hook.  In
    pass C the pipeline closes the writer pool GRACEFULLY first — every
    part already submitted publishes durably and journals — then
    re-raises, so a drained job's journal resumes exactly where the
    drain stopped it.  A ``BaseException`` on purpose: the device
    recovery paths catch ``Exception`` broadly, and a drain request
    must never be mistaken for a chip failure."""


def _ingest_windows(path: str, window_reads: int, out_q: queue.Queue,
                    abort: threading.Event, tr: tele.Tracer):
    """Ingest thread body: tokenize windows, push (batch, side, header).

    ``abort`` unblocks the bounded put when the consumer dies mid-stream
    — otherwise the thread (and the decoded input it holds) would be
    pinned for the life of the process.  ``tr`` records one
    ``streamed.tokenize`` span per window on this thread's track.
    """

    def put(item) -> bool:
        while not abort.is_set():
            try:
                out_q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    try:
        p = str(path)
        base = p[:-3] if p.endswith(".gz") else p
        from adam_tpu.io import sam as sam_io

        if base.endswith(".bam"):
            it = sam_io.iter_bam_batches(p, batch_reads=window_reads)
        else:
            it = sam_io.iter_sam_batches(p, batch_reads=window_reads)
        i = 0
        while True:
            with tr.span(tele.SPAN_TOKENIZE, window=i):
                item = next(it, _SENTINEL)
            if item is _SENTINEL:
                break
            if not put(item):
                return
            # chaos-harness kill point: one arrival per tokenized window
            faults.point("proc.kill", device="ingest")
            i += 1
        put(_SENTINEL)
    except BaseException as e:  # surface in the consumer
        put(e)


def _part_path(out_dir: str, part_idx: int) -> str:
    # the io/parquet part-naming contract: the numeric index IS the
    # window index (realigned tail part = n_windows), so the streamed
    # run journal can map published parts back onto the window plan
    from adam_tpu.io.parquet import part_path

    return part_path(out_dir, part_idx)


def _write_part(out_dir: str, part_idx: int, ds: AlignmentDataset,
                compression: str) -> None:
    """Synchronous single-part write (the sharded/multihost executors'
    sink; the streamed pipeline itself goes through PartWriterPool)."""
    from adam_tpu.io import parquet

    parquet.save_alignments(
        _part_path(out_dir, part_idx),
        ds.batch, ds.sidecar, ds.header, compression=compression,
    )


def _start_heartbeat(tr: tele.Tracer, progress: Optional[str],
                     include_global: bool = True):
    """Build+start the live progress heartbeat, or None (the default —
    zero construction, the spans' disabled-overhead contract).

    Samples the run tracer AND the global TRACE (retry/fault counters
    and the transfer ledger land on the latter); when no other
    observability sink already enabled global recording, it is flipped
    on for the heartbeat's lifetime and :func:`_stop_heartbeat`
    restores the flag AND resets the tracer — a ``--progress``-only run
    neither exports nor accumulates global telemetry, so back-to-back
    library runs in one process can't sum each other's counters into
    the beat.

    ``include_global=False`` (the multi-job service) samples the run
    tracer alone: concurrent jobs absorb their tracers into the global
    TRACE as they finish, and a survivor's beat summing that shared
    state would count its neighbors' work as its own."""
    sink = progress if progress is not None else tele.progress_sink_from_env()
    if not sink:
        return None
    hb = tele.Heartbeat(
        [tr, tele.TRACE] if include_global else [tr], sink
    )
    hb._hb_restore_recording = include_global and not tele.TRACE.recording
    if hb._hb_restore_recording:
        tele.TRACE.recording = True
    hb.start()
    return hb


def _stop_heartbeat(hb, ok: bool = True) -> None:
    """Idempotent heartbeat teardown (final ``done`` line + recording
    restore) — called from the normal finish path *before* the run
    tracer folds into the global TRACE (a post-absorb sample would
    double-count every counter) and again from the wrapper's
    ``finally`` for the exception paths, which pass ``ok=False`` so
    the final line does not read as a completed run."""
    if hb is None:
        return
    hb.stop(ok=ok)
    if getattr(hb, "_hb_restore_recording", False):
        tele.TRACE.recording = False
        # recording was OFF before this run, so nothing else is reading
        # the global tracer: drop what the heartbeat window recorded
        # into it, or a later run in the same process (library use,
        # tests) would sum this run's parquet counters into its own
        tele.TRACE.reset()
        hb._hb_restore_recording = False


def _inflight_per_device(queues: list) -> dict:
    """Heartbeat provider body: per-device in-flight depth sampled from
    the live dispatch deques (read-only; a concurrent mutation mid-
    iteration just skips this beat — the next one resamples)."""
    per: dict = {}
    for dq, dev_idx in queues:
        try:
            items = list(dq)
        except RuntimeError:
            continue
        for item in items:
            dev = item[dev_idx]
            key = "default" if dev is None else str(dp_mod._attr_id(dev))
            per[key] = per.get(key, 0) + 1
    return per


def transform_streamed(
    path: str,
    out_path: str,
    *,
    mark_duplicates: bool = True,
    recalibrate: bool = True,
    realign: bool = True,
    known_snps=None,
    known_indels=None,
    consensus_model: str = "reads",
    window_reads: int = 262_144,
    compression: str = "zstd",
    n_writers: int = 3,
    max_indel_size: int | None = None,
    max_consensus_number: int | None = None,
    lod_threshold: float | None = None,
    max_target_size: int | None = None,
    dump_observations: Optional[str] = None,
    known_table: Optional[tuple] = None,
    devices: Optional[int] = None,
    partitioner: Optional[str] = None,
    progress: Optional[str] = None,
    run_dir: Optional[str] = None,
    resume: bool = False,
    pacer=None,
    device_pool=None,
    coalescer=None,
    trace: Optional[str] = None,
) -> dict:
    """Run the flagship transform as a streamed, overlapped pipeline.

    Output is a Parquet part-file directory (the reference's Spark
    executor layout); ``adam_tpu.io.context.load_alignments`` reads it
    back as one dataset.  Returns phase wall-times + read count.

    ``devices`` caps the device-pool fan-out (default: every attached
    device, or ``ADAM_TPU_DEVICES``); only the ``device`` backend uses
    it, and ``devices=1`` is exactly the single-chip path.

    ``partitioner`` selects how device work places across those chips
    (``--partitioner`` / ``ADAM_TPU_PARTITIONER``): ``"pool"`` (the
    default) round-robins whole windows, ``"mesh"`` shards every
    window's [N, L] arrays over a ``batch``
    :class:`~jax.sharding.Mesh`, ``psum``s the pass-B observe
    histograms on-device so barrier 2 fetches ONE merged table instead
    of one per window, and keeps the solved recalibration table
    device-resident through pass C.  Output is bit-identical across
    modes; a mesh failure degrades to the pool path mid-run
    (``device.mesh.degraded``), preserving the eviction/replay
    contract (docs/ROBUSTNESS.md).

    ``progress`` names a live-heartbeat sink (``"stderr"`` or a file
    path; default: ``ADAM_TPU_PROGRESS``, off when unset): a daemon
    thread emits one NDJSON line (schema
    :data:`~adam_tpu.utils.telemetry.HEARTBEAT_FIELDS`) every
    ``ADAM_TPU_PROGRESS_INTERVAL_S`` seconds.

    ``run_dir`` enables the durable window-granular resume journal
    (docs/ROBUSTNESS.md): each output window is recorded complete after
    its part's atomic+fsync'd publish, observe histograms and the
    solved recalibration table persist as atomic sidecars, and with
    ``resume=True`` a rerun after an arbitrary process kill skips the
    completed windows — bit-identical to an uninterrupted run.  A
    resume whose input content, flag composition or window plan differs
    from the journal's fingerprint is refused with a clean restart
    (stale parts discarded), never mixed output.

    ``pacer`` and ``device_pool`` are the multi-job service's seams
    (``adam_tpu/serve``): ``pacer(phase, index)`` is called once per
    window at the pass-A and pass-C boundaries — the scheduler's
    fairness interleaver blocks there to weight windows across
    concurrent jobs, and raises :class:`RunCancelled` to stop the run
    gracefully at that boundary (parts already submitted still publish
    and journal).  ``device_pool`` (a
    :class:`~adam_tpu.parallel.device_pool.DevicePool` or
    :class:`~adam_tpu.parallel.device_pool.PoolLease`) substitutes a
    shared pool for the run's own, so concurrent jobs place windows on
    the same chips; pacing and pool sharing change only where and when
    work runs, never the output bytes.

    ``coalescer`` (a :class:`~adam_tpu.serve.batching.CoalescerClient`)
    routes this run's per-window device dispatches through the
    scheduler's cross-job :class:`~adam_tpu.serve.batching.WindowCoalescer`
    so concurrent jobs' windows merge into ONE fused dispatch per pass
    (docs/SERVING.md "Continuous batching & quotas").  Device backend +
    pool partitioner only (the mesh already fuses the device set per
    window); a coalesced window that fails falls back to this run's own
    solo dispatch path — byte-identical output either way.

    ``trace`` is the run's trace context (docs/OBSERVABILITY.md): the
    job-scoped trace_id minted at gateway/scheduler admission.  Solo
    runs mint their own, so every run is traceable.  The run tracer
    stamps every span it records with it, and it selects this run's
    events in the gateway ``/trace`` export and incident bundles.
    Tracing changes attribution metadata only, never output bytes.

    ``known_table`` is a pre-solved recalibration table ``(u8[n_rg,
    N_QUAL, n_cyc, N_DINUC] ndarray, gl)`` — the known-sites workflow,
    where the table shipped with the cohort instead of being discovered
    from this input.  It REPLACES the solved table at barrier 2 (the
    observe pass and the histogram merge still run, so
    ``dump_observations`` and the resume sidecars see the same
    artifacts), and it arms the fused B→C megakernel tier
    (docs/PERF.md "Megakernel tier"): with the applied table known at
    ingest, each eligible window's observe scatter-add and apply+pack
    gather compose into ONE donated dispatch
    (``bqsr.fused_bc_dispatch``), eliminating the per-window barrier-2
    round-trip.  Output bytes are identical fused or not
    (``ADAM_TPU_FUSED_BC=0`` is the unfused A/B leg).
    """
    from adam_tpu.utils import incidents

    # Per-run tracer, ALWAYS recording: the returned stats dict is a
    # derived view of its span data (telemetry.streamed_stats_view), so
    # the two can never disagree.  The handful of stage/window spans it
    # records per run is negligible next to the work; it folds into the
    # global TRACE at the end when telemetry is enabled.
    tr = tele.Tracer(recording=True)
    if trace is None:
        trace = tele.mint_trace_id()
    tr.set_trace(trace)
    tele.activate_trace(trace)
    # solo runs with a durable run dir arm the incident recorder on it;
    # under the scheduler it is already armed on the service run root
    # (install-first wins — a job must not re-point the service's)
    armed_incidents = False
    if run_dir is not None and not incidents.installed():
        incidents.install(run_dir)
        armed_incidents = True
    # a paced run is a multi-job service job: its heartbeat must carry
    # job-scoped counters only (see _start_heartbeat's include_global)
    hb = _start_heartbeat(tr, progress, include_global=pacer is None)
    try:
        stats = _transform_streamed_impl(
            path, out_path, tr, hb,
            mark_duplicates=mark_duplicates, recalibrate=recalibrate,
            realign=realign, known_snps=known_snps,
            known_indels=known_indels, consensus_model=consensus_model,
            window_reads=window_reads, compression=compression,
            n_writers=n_writers, max_indel_size=max_indel_size,
            max_consensus_number=max_consensus_number,
            lod_threshold=lod_threshold, max_target_size=max_target_size,
            dump_observations=dump_observations, known_table=known_table,
            devices=devices,
            partitioner=partitioner, run_dir=run_dir, resume=resume,
            pacer=pacer, device_pool=device_pool, coalescer=coalescer,
        )
        # perf-ledger booking (utils/perfledger.py): every completed
        # run books its bench-diff keys — into the armed service root
        # under the scheduler (one longitudinal history per service),
        # else this run's own durable run_dir.  The sentinel judges
        # the new entry against the rolling median baseline; a flagged
        # regression emits a perf.regression bundle and charges the
        # SLO budget.  Booking failures never fail the run.
        from adam_tpu.utils import perfledger

        ledger_root = perfledger.ledger_root() or run_dir
        if ledger_root is not None and perfledger.booking_enabled():
            try:
                perfledger.sentinel(
                    ledger_root, tr.snapshot(), run_id=trace,
                )
            except Exception:
                log.warning("perf-ledger booking failed", exc_info=True)
        return stats
    except BaseException:
        # crashed run: the final heartbeat line must carry ok=false —
        # a tailing consumer reading done=true as "completed" would
        # otherwise mark a failed run green
        _stop_heartbeat(hb, ok=False)
        raise
    finally:
        # normal completion already stopped it (inside _finish_trace,
        # before the absorb); this is a no-op backstop
        _stop_heartbeat(hb)
        tele.deactivate_trace(trace)
        if armed_incidents:
            incidents.uninstall()


def _transform_streamed_impl(
    path: str,
    out_path: str,
    tr: tele.Tracer,
    hb,
    *,
    mark_duplicates: bool,
    recalibrate: bool,
    realign: bool,
    known_snps,
    known_indels,
    consensus_model: str,
    window_reads: int,
    compression: str,
    n_writers: int,
    max_indel_size: int | None,
    max_consensus_number: int | None,
    lod_threshold: float | None,
    max_target_size: int | None,
    dump_observations: Optional[str],
    known_table: Optional[tuple],
    devices: Optional[int],
    partitioner: Optional[str],
    run_dir: Optional[str],
    resume: bool,
    pacer=None,
    device_pool=None,
    coalescer=None,
) -> dict:
    from adam_tpu.parallel import partitioner as part_mod
    from adam_tpu.pipelines import bqsr as bqsr_mod
    from adam_tpu.pipelines import markdup as md_mod
    from adam_tpu.pipelines import realign as realign_mod

    # live in-flight deques the heartbeat provider samples: (deque,
    # index of the device element in its items)
    hb_queues: list = []
    t_start_ns = time.monotonic_ns()
    stats: dict = {}
    # one backend decision for every per-residue pass in this run: the
    # device kernels (BQSR observe/apply scatter-gathers, markdup [N, L]
    # reductions) are the default when a chip is attached, the host
    # kernels otherwise; ADAM_TPU_BQSR_BACKEND overrides
    backend = bqsr_mod.bqsr_backend()
    use_device = backend == "device"
    stats["bqsr_backend"] = backend
    # multi-chip fan-out: window i's device work round-robins to device
    # i % n; None means single-device (the pre-pool path, bit-for-bit)
    dpool = None
    if use_device:
        # a shared pool (the multi-job service's lease) substitutes for
        # the run's own — same duck-typed surface, shared eviction state
        dpool = (
            device_pool if device_pool is not None
            else dp_mod.make_pool(devices)
        )
    stats["n_devices"] = dpool.n if dpool is not None else (
        1 if use_device else 0
    )
    # device health / hedging / SDC audit (utils/health.py,
    # docs/ROBUSTNESS.md "Device health, hedging, and SDC audit"): the
    # process-wide scoreboard feeds placement (probation devices are
    # excluded until their re-admission probe passes — and the mesh
    # construction below spans only the healthy subset); pass C hedges
    # in-flight windows past ADAM_TPU_HEDGE_FACTOR x the apply kernel's
    # observed p99, and deterministically samples ADAM_TPU_AUDIT_RATE
    # of windows for a host dual-compute bit comparison — a mismatch
    # quarantines the producing chip and replays the window from the
    # host copy, so the published part is clean either way.
    health_board = health_mod.BOARD
    sdc_audit_rate = health_mod.audit_rate() if use_device else 0.0
    stats["audit_rate"] = sdc_audit_rate
    # execution partitioner (--partitioner / ADAM_TPU_PARTITIONER):
    # "pool" round-robins whole windows; "mesh" shards every window
    # over a batch Mesh spanning the same device set, psums the
    # observe histograms on-device and keeps the solved table resident
    # through pass C.  The pool stays constructed either way — it IS
    # the degrade target when the mesh path fails mid-run.
    exec_mode = part_mod.resolve_execution_mode(partitioner)
    mesh_part = None
    if use_device and exec_mode == "mesh":
        try:
            import jax

            if device_pool is not None:
                # a shared-pool job's mesh spans exactly the leased
                # device set, so collectives never touch chips outside
                # the scheduler's pool
                mesh_devs = list(device_pool.devices)
            else:
                n_mesh = dp_mod.resolve_device_count(devices)
                mesh_devs = jax.local_devices()[:n_mesh]
            # mesh construction consults the health scoreboard: a
            # collective spans every mesh device, so ONE probation
            # chip would poison every window — build the mesh over the
            # healthy subset (all-blocked falls back to the full set;
            # availability beats health, and the pool degrade path
            # still owns mid-run failures)
            mesh_devs = part_mod.healthy_subset(mesh_devs, health_board)
            mesh_part = part_mod.MeshPartitioner(mesh_devs)
        except Exception as e:
            log.warning(
                "mesh partitioner unavailable (%s); using the pool path",
                e,
            )
    exec_state = {
        "mesh": mesh_part,
        "mode": "mesh" if mesh_part is not None else "pool",
    }
    stats["partitioner"] = exec_state["mode"]
    # device-resident windows (docs/PERF.md "Device-resident windows"):
    # on the device backend every markdup/observe/apply dispatch takes
    # a ResidentWindow — pinned at ingest (per pool device, or one
    # mesh-sharded placement) and kept until its part submits, or
    # placed transient for one pass where the target holds no live
    # handle.  The later passes ship only their per-pass inputs
    # (bit-packed MD masks, post-split validity bools), and pass C
    # fetches both columns packed (the fused bases+quals pack2).
    stats["resident_windows"] = 0
    # cross-job window batching (serve/batching.py): the scheduler's
    # coalescer client merges this job's per-window dispatches with its
    # neighbors' into one fused dispatch per pass.  Device backend
    # only; the per-hook guards additionally skip it while the mesh
    # partitioner is live (the mesh already fuses the device set).
    if not use_device:
        coalescer = None
    stats["batched"] = coalescer is not None

    def _win_nbytes(b) -> int:
        """A window's grant size (bytes) for the fairness ring / quota
        leg: the per-residue payload the device passes actually move."""
        try:
            return int(b.bases.nbytes) + int(b.quals.nbytes)
        except AttributeError:
            return 0
    # pass-B windows folded into the mesh's device-resident observe
    # accumulator, kept referenced so a degrade can replay them through
    # the pool/host path; the host-side merge lists live up here too so
    # the degrade hook can append to them from any pass
    mesh_obs: list = []
    obs_parts: list = []
    obs_replays: list = []
    obs_windows: list = []
    # megakernel tier (docs/PERF.md): window idx -> (producing device |
    # "mesh", packed2 apply handle) stashed by the fused B→C dispatch in
    # pass B — pass C pops and FETCHES these instead of dispatching an
    # apply.  A window absent here takes the separate-pass path.
    fused_handles: dict = {}
    if use_device:
        tr.gauge(tele.G_POOL_DEVICES, stats["n_devices"])
    if hb is not None:
        # HBM sampling keys must match the device=<k> span attribution,
        # so the heartbeat polls exactly the run's device set
        if mesh_part is not None:
            hb.set_devices(mesh_part.devices)
        elif dpool is not None:
            hb.set_devices(dpool.devices)
        hb.set_provider(lambda: {
            "inflight_per_device": _inflight_per_device(hb_queues),
            # live mode, not the launch mode: a degraded mesh run
            # reports "pool" from its next beat on
            "partitioner": exec_state["mode"] if use_device else None,
        })
    os.makedirs(out_path, exist_ok=True)
    # purge a crashed run's staging dir: io/parquet publishes each part
    # by atomic rename out of out_path/_temporary, so a SIGKILL'd run
    # leaves its torn files THERE (readers ignore the _-prefixed dir),
    # never as truncated part-*.parquet — and a rerun starts clean
    from adam_tpu.io.parquet import purge_stale_staging

    purge_stale_staging(out_path)

    # ---- resilience (docs/ROBUSTNESS.md): a device that spends its
    # retry budget is evicted and its in-flight windows replay on the
    # survivors; when the last device is gone, every remaining
    # per-residue pass runs on the native/numpy host backend.  Output
    # stays bit-identical on every path: the barrier merges are
    # window-ordered and the backends are bit-parity twins
    # (tests/test_backend_parity.py).
    res = {"device_lost": False}

    def _host_backend() -> str:
        from adam_tpu import native

        return "native" if native.available() else "numpy"

    # ---- device-resident windows: handle registry + lifecycle ----------
    # window index -> its handle pinned at ingest; ``transients`` holds
    # the per-pass handles in flight.  The live-bytes ledger backs the
    # no-HBM-growth invariant (gauge returns to 0 as pass C releases)
    resident_map: dict = {}
    transients: set = set()
    resident_live = {"bytes": 0, "made": 0}

    def _placed(rw):
        resident_live["bytes"] += rw.nbytes
        resident_live["made"] += 1
        tr.count(tele.C_RESIDENT_WINDOWS)
        tr.count(tele.C_RESIDENT_BYTES, rw.nbytes)
        tr.gauge(tele.G_RESIDENT_LIVE, resident_live["bytes"])

    def _place(win, batch, target):
        b = batch.to_numpy()
        if isinstance(target, part_mod.MeshPartitioner):
            return part_mod.mesh_resident_window(b, win, target)
        return dp_mod.make_resident_window(b, win, target)

    def _make_resident(win, ds):
        """Pin window ``win``'s resident payload at ingest (on its
        round-robin pool device, or mesh-sharded).  Best-effort: after
        a failed placement each pass places a transient handle."""
        if not use_device or res["device_lost"]:
            return
        target = exec_state["mesh"]
        if target is None:
            target = _pick_device(win)
            if target is _HOST:
                return
        try:
            # the one per-window h2d: attributed to the ledger's
            # ``ingest`` bucket, which the analyzer's residency verdict
            # compares against the (≈0) observe/apply buckets
            with tele.pass_scope("ingest"):
                rw = _place(win, ds.batch, target)
        except Exception as e:
            log.warning(
                "resident placement of window %d failed (%s); its "
                "passes place transient handles", win, e,
            )
            return
        resident_map[win] = rw
        _placed(rw)

    def _acquire(win, batch, target):
        """THE handle helper every dispatch and recovery site goes
        through: window ``win``'s pinned handle when it is alive on
        ``target`` (a pool device, None for the default device, or the
        live MeshPartitioner), else a transient one placed on
        ``target`` from the host-retained ingest copy ``batch``."""
        rw = resident_map.get(win)
        if rw is not None and rw.alive and (
            rw.device == "mesh"
            if isinstance(target, part_mod.MeshPartitioner)
            else rw.device is target
        ):
            return rw
        rw = _place(win, batch, target)
        transients.add(rw)
        _placed(rw)
        return rw

    @contextlib.contextmanager
    def _handle_for(win, batch, target):
        """:func:`_acquire` for one dispatch: a transient handle is
        released when the block exits (the runtime keeps the buffers of
        the queued computation alive until it completes)."""
        rw = _acquire(win, batch, target)
        try:
            yield rw
        finally:
            if rw in transients:
                _retire(rw)

    def _retire(rw, drop=False):
        """Release a handle (pinned: after its pass-C fetch or at the
        skip sites; transient: after its dispatch); ``drop`` marks a
        fault-path drop (eviction, degrade) for the counters."""
        if rw in transients:
            transients.discard(rw)
        elif resident_map.get(rw.window) is rw:
            del resident_map[rw.window]
        else:
            return
        rw.drop() if drop else rw.release()
        resident_live["bytes"] -= rw.nbytes
        tr.count(
            tele.C_RESIDENT_EVICTED if drop else tele.C_RESIDENT_RELEASED
        )
        tr.gauge(tele.G_RESIDENT_LIVE, resident_live["bytes"])

    def _release_resident(win):
        rw = resident_map.get(win)
        if rw is not None:
            _retire(rw)

    def _drop_resident_on(dev):
        """An evicted device takes its handles with it: the later
        passes of its windows place transient handles on survivors."""
        for rw in [*resident_map.values(), *transients]:
            if rw.device is dev:
                _retire(rw, drop=True)

    def _drop_all_resident():
        for rw in [*resident_map.values(), *transients]:
            _retire(rw, drop=True)

    def _evict_or_lose(dev, exc) -> bool:
        """Evict a failed device; True = survivors remain, False = the
        device path is gone (callers fall back to the host backend)."""
        _drop_resident_on(dev)
        if dpool is not None:
            dpool.evict(dev, reason=str(exc), tracer=tr)
            if dpool.alive_devices():
                return True
        else:
            log.error(
                "device path failed (%s); running the rest of this "
                "pipeline on the %s host backend", exc, _host_backend(),
            )
        res["device_lost"] = True
        _drop_all_resident()
        return False

    def _pick_device(win):
        """Window's round-robin device: a jax device (pool), None (the
        single-chip default device), or _HOST once the path is lost."""
        if res["device_lost"]:
            return _HOST
        if dpool is None:
            return None
        try:
            return dpool.device(win)
        except dp_mod.AllDevicesEvicted:
            res["device_lost"] = True
            return _HOST

    def _on_survivors(win, device_fn, host_fn):
        """THE recovery loop, shared by every dispatch/replay site: run
        ``device_fn(dev)`` on window's round-robin device, evicting and
        walking to the next survivor on failure (transient retries
        already happened inside the call), ``host_fn()`` once the
        device path is lost."""
        while True:
            dev = _pick_device(win)
            if dev is _HOST:
                return host_fn()
            try:
                return device_fn(dev)
            except Exception as e:
                _evict_or_lose(dev, e)

    def _mesh_degrade(exc, where: str = ""):
        """A mesh collective failed past its retry budget: abandon the
        mesh for the rest of the run (the accumulator on a dying device
        set is no longer trustworthy) and fall back to the pool path —
        bit-identical by the backend-parity contract.  Windows already
        folded into the accumulator replay through the pool/host
        observe under a ``device.pool.replay`` umbrella, so a dead
        mesh costs the replayed windows, never the run."""
        mp = exec_state["mesh"]
        if mp is None:
            return
        exec_state["mesh"] = None
        exec_state["mode"] = "pool"
        stats["partitioner"] = "pool"
        # mesh-sharded resident handles die with the mesh: the pool
        # path takes their windows over on transient handles placed
        # from the host-retained ingest copy (docs/ROBUSTNESS.md)
        _drop_all_resident()
        # fused B→C outputs sharded over the dying mesh are no longer
        # trustworthy either: forget them, so pass C re-applies those
        # windows through the separate-pass pool/host path
        fused_handles.clear()
        tr.count(tele.C_MESH_DEGRADED)
        log.error(
            "mesh partitioner failed%s (%s); degrading to the pool path"
            "%s", f" at {where}" if where else "", exc,
            (f" and replaying {len(mesh_obs)} accumulated window(s)"
             if mesh_obs else ""),
        )
        mp.reset_accumulator()
        if mesh_obs:
            with tr.span(tele.SPAN_POOL_REPLAY, device="mesh"), \
                    dp_mod.replay_scope():
                for i, w in list(mesh_obs):
                    got = _observe_window(i, w)
                    if got is not None:
                        obs_parts.append(got[0])
                        obs_replays.append(got[1])
                        obs_windows.append(i)
            mesh_obs.clear()
    if known_indels is not None and consensus_model == "reads":
        # supplying known indels implies the knowns consensus model (the
        # reference's -known_indels flag semantics; realign_indels only
        # consults the table under that model)
        consensus_model = "knowns"
    mis, mcn, lod, mts = realign_mod.resolve_tuning(
        max_indel_size, max_consensus_number, lod_threshold, max_target_size
    )

    # ---- durable window-granular resume (docs/ROBUSTNESS.md) -----------
    # The journal fingerprints input content identity + the full
    # output-bit-affecting flag composition (the backend/device count is
    # deliberately EXCLUDED: the kernels are bit-parity twins, so a
    # resume on different hardware is still bit-identical).  Window
    # completion is recorded only after a part's durable publish, via
    # the writer pool's on_published hook below.
    journal = None
    if run_dir:
        from adam_tpu.pipelines import checkpoint as ck_mod

        fp = ck_mod.compose_fingerprint({
            "schema": "adam_tpu.streamed/1",
            "input": ck_mod.input_fingerprint(path),
            "mark_duplicates": mark_duplicates,
            "recalibrate": recalibrate,
            "realign": realign,
            "consensus_model": consensus_model,
            "window_reads": window_reads,
            "compression": compression,
            "max_indel_size": mis,
            "max_consensus_number": mcn,
            "lod_threshold": lod,
            "max_target_size": mts,
            "known_snps": known_snps,
            "known_indels": known_indels,
            # a known-sites table changes the applied (= output) bytes:
            # content-digested into the fingerprint, so a resume under a
            # different table is refused instead of mixing output.  The
            # key is absent (not None) without one — discovered-table
            # journals keep their pre-existing fingerprints.
            **({"known_table": (
                hashlib.sha256(
                    np.ascontiguousarray(known_table[0], np.uint8)
                    .tobytes()
                ).hexdigest(),
                int(known_table[1]),
            )} if known_table is not None else {}),
        })
        journal = ck_mod.RunJournal(
            run_dir, fp, out_path, resume=resume, tracer=tr
        )

    # ---- megakernel tier (docs/PERF.md "Megakernel tier") -------------
    # With the applied table known BEFORE pass B — a known-sites run, or
    # a -dump_observations resume whose journal already holds the solved
    # table (re-observing only for the merge artifacts) — each eligible
    # window's observe and apply+pack fuse into one donated dispatch on
    # its pinned handle (device backend); the cross-job coalescer owns
    # its own fusion, so a coalesced run keeps the separate passes.
    fused_table = None
    if (
        recalibrate and use_device and coalescer is None
        and bqsr_mod.fused_bc_enabled()
    ):
        if known_table is not None:
            fused_table = (
                np.ascontiguousarray(known_table[0], np.uint8),
                int(known_table[1]),
            )
        elif journal is not None and journal.resumed and dump_observations:
            # the dump forces a full re-observe (resume_table stays
            # None below), but the journal's solved table — identical
            # to what this merge will re-solve, same input + sidecar
            # histograms — is already the applied table
            lt = journal.load_table()
            if lt is not None:
                fused_table = (
                    np.ascontiguousarray(lt[0], np.uint8), int(lt[1])
                )
    stats["fused_bc"] = fused_table is not None
    tr.gauge(tele.G_FUSED_BC, 1 if fused_table is not None else 0)

    # ---- pass A: ingest || summaries + events --------------------------
    in_q: queue.Queue = queue.Queue(maxsize=3)
    abort = threading.Event()
    ingest = threading.Thread(
        target=_ingest_windows, args=(path, window_reads, in_q, abort, tr),
        daemon=True,
    )
    ingest.start()

    windows: list[AlignmentDataset] = []
    summaries: list[dict] = []
    events = []
    header = None
    n_reads = 0
    # device in-flight queue of (window idx, ds, lazy (five, score)):
    # depth 2 on the single-device path (the classic double buffer);
    # with a pool, a double buffer PER device (2n) — round-robin keeps
    # the drain order == window order, so summaries stay window-ordered
    # and the duplicate resolve is bitwise independent of n
    md_depth = 2 if dpool is None else 2 * dpool.n
    pend_cols: deque = deque()
    hb_queues.append((pend_cols, 2))  # items: (win, ds, dev, cols)

    def _md_dispatch(win, batch, coalesce=True):
        """Dispatch one window's [N, L] markdup reductions -> (device,
        lazy cols), walking to the next survivor after a spent retry
        budget; None = compute the summary on the host instead.  Under
        the mesh partitioner the window shards across every device at
        once (device tag ``"mesh"``); a mesh failure degrades to the
        pool path and re-dispatches here.  With a coalescer attached
        (and the pool partitioner live) the window submits to the
        cross-job batch instead — device tag ``"batch"``, cols a
        future; a coalesce failure re-enters here with
        ``coalesce=False``."""
        mp = exec_state["mesh"]
        if (
            coalesce and coalescer is not None and mp is None
            and not res["device_lost"]
        ):
            try:
                fut = coalescer.submit_markdup(
                    win, batch, resident_map.get(win)
                )
                return "batch", fut
            except Exception as e:
                log.warning(
                    "coalesced markdup submit of window %d failed "
                    "(%s); dispatching solo", win, e,
                )
        if mp is not None:
            try:
                with _handle_for(win, batch, mp) as rw:
                    cols = md_mod.markdup_columns_dispatch(
                        batch, mesh=mp, resident=rw
                    )
                tr.count(tele.C_DEVICE_DISPATCHED)
                tr.count(tele.C_MESH_DISPATCHED)
                return "mesh", cols
            except Exception as e:
                _mesh_degrade(e, "pass-A markdup")

        def on_device(dev):
            # a replay on a survivor places a transient handle there
            with _handle_for(win, batch, dev) as rw:
                cols = md_mod.markdup_columns_dispatch(
                    batch, device=dev, resident=rw
                )
            tr.count(tele.C_DEVICE_DISPATCHED)
            return dev, cols

        return _on_survivors(win, on_device, lambda: None)

    def _summarize(win, ds, dev, cols):
        if dev == "batch":
            # coalesced window: the future resolves to host (five,
            # score) slices bitwise the solo columns; a fused-dispatch
            # failure falls back to this window's own solo path (which
            # owns eviction/replay/host-degrade)
            try:
                five, score = cols.result()
            except Exception as e:
                log.warning(
                    "coalesced markdup of window %d fell back to the "
                    "solo dispatch (%s)", win, e,
                )
                nxt = _md_dispatch(win, ds.batch, coalesce=False)
                if nxt is None:
                    summaries.append(md_mod.row_summary(ds))
                    return
                dev, cols = nxt
            else:
                summaries.append(md_mod.row_summary(
                    ds, five_prime=five, score=score
                ))
                return
        while cols is not None:
            try:
                with tr.span(tele.SPAN_MD_FETCH):
                    five = np.asarray(device_fetch(cols[0]))
                    score = np.asarray(device_fetch(cols[1]))
            except Exception as e:
                # fetch failed past the transfer layer's retry budget:
                # evict the chip (or abandon the mesh) and replay the
                # window's reductions on what remains (the loop
                # re-fetches), host when nothing is left
                with tr.span(tele.SPAN_POOL_REPLAY, window=win,
                             **dp_mod.span_attrs(dev)), \
                        dp_mod.replay_scope():
                    if dev == "mesh":
                        _mesh_degrade(e, "pass-A markdup fetch")
                    else:
                        _evict_or_lose(dev, e)
                    nxt = _md_dispatch(win, ds.batch)
                if nxt is None:
                    break
                dev, cols = nxt
                continue
            tr.count(tele.C_DEVICE_FETCHED)
            summaries.append(
                md_mod.row_summary(ds, five_prime=five, score=score)
            )
            return
        summaries.append(md_mod.row_summary(ds))

    # ---- long-tail shape prewarm ---------------------------------------
    # The window-0 prewarm covers only window 0's grid; residual windows
    # (a short final ingest window drops to a smaller pow2 row grid) and
    # the realigned tail part land on shapes it never saw and used to
    # cold-compile INSIDE their window (the measured grid-1024 0.26 s
    # `device.compile.in_window` entry, docs/PERF.md).  Re-prewarm on
    # FIRST SIGHT of each new grid shape instead — the process-wide
    # dedupe cache makes repeats (and warm bench runs) free.
    seen_grid_shapes: set = set()

    def _prewarm_window_shapes(ds):
        mp = exec_state["mesh"]
        if (mp is None and dpool is None) or res["device_lost"]:
            return
        b = ds.batch.to_numpy()
        from adam_tpu.formats.batch import (
            grid_cigar_cols, grid_cols, grid_rows,
        )

        key = (
            grid_rows(b.n_rows), grid_cols(b.lmax),
            grid_cigar_cols(
                b.cigar_ops.shape[1] if b.cigar_ops.ndim == 2 else 1
            ),
            exec_state["mode"],
        )
        if key in seen_grid_shapes:
            return
        seen_grid_shapes.add(key)
        n_rg = len(ds.read_groups) + 1
        # the umbrella records the WALL (the stats view subtracts
        # it back out of the enclosing pass's row)
        with tr.span(tele.SPAN_POOL_PREWARM):
            if mp is not None:
                entries = []
                if mark_duplicates:
                    entries.append(
                        part_mod.mesh_markdup_prewarm_entry(b, mp)
                    )
                if recalibrate:
                    entries.append(
                        part_mod.mesh_observe_packed_prewarm_entry(
                            b, n_rg, mp
                        )
                    )
                    if fused_table is not None:
                        # the megakernel the fused tier will dispatch,
                        # at the KNOWN table's cycle width
                        entries.append(
                            part_mod.mesh_fused_bc_prewarm_entry(
                                b, n_rg, fused_table[0].shape[2], mp
                            )
                        )
                mp.prewarm(entries, tracer=tr)
            else:
                from adam_tpu.parallel.device_pool import (
                    streamed_prewarm_entries,
                )

                dpool.prewarm(
                    streamed_prewarm_entries(
                        b, n_rg, mark_duplicates=mark_duplicates,
                        recalibrate=recalibrate,
                        fused_n_cyc=(
                            fused_table[0].shape[2]
                            if fused_table is not None else None
                        ),
                    ),
                    tracer=tr,
                )

    def _prewarm_observe_shape(ds):
        """Tail hook: warm the observe kernel at the REALIGNED part's
        grid before its in-window dispatch (its row/lane grid rarely
        matches any ingest window's)."""
        mp = exec_state["mesh"]
        if (
            not recalibrate or res["device_lost"]
            or (mp is None and dpool is None)
        ):
            return
        b = ds.batch.to_numpy()
        n_rg = len(ds.read_groups) + 1
        with tr.span(tele.SPAN_POOL_PREWARM):
            if mp is not None:
                entries = [
                    part_mod.mesh_observe_packed_prewarm_entry(b, n_rg, mp)
                ]
                if fused_table is not None:
                    entries.append(
                        part_mod.mesh_fused_bc_prewarm_entry(
                            b, n_rg, fused_table[0].shape[2], mp
                        )
                    )
                mp.prewarm(entries, tracer=tr)
            else:
                entries = [dp_mod.observe_packed_prewarm_entry(b, n_rg)]
                if fused_table is not None:
                    entries.append(
                        dp_mod.fused_bc_prewarm_entry(
                            b, n_rg, fused_table[0].shape[2]
                        )
                    )
                dpool.prewarm(entries, tracer=tr)

    # transfer-ledger pass attribution: every h2d put / d2h fetch on
    # this thread inside the scope lands under the pass's bucket in the
    # snapshot's ``transfers`` section (prewarm shadows with its own)
    with tr.span(tele.SPAN_PASS_A), tele.pass_scope("a"):
        try:
            while True:
                item = in_q.get()
                if item is _SENTINEL:
                    break
                if isinstance(item, BaseException):
                    raise item
                batch, side, header = item
                ds = AlignmentDataset(batch, side, header)
                windows.append(ds)
                win = len(windows) - 1
                # reads counted PER WINDOW (not once at pass-A exit):
                # the live heartbeat's reads/s derives from this counter
                # mid-ingest; the end-of-run total is identical
                n_window_reads = int(batch.valid.sum())
                n_reads += n_window_reads
                tr.count(tele.C_READS_INGESTED, n_window_reads)
                tr.count(tele.C_WINDOWS_INGESTED)
                # chaos-harness kill point: one arrival per pass-A window
                faults.point("proc.kill", device="pass_a")
                # multi-job fairness / graceful drain: the scheduler's
                # interleaver grants this job one window (or raises
                # RunCancelled at this boundary — nothing is in flight
                # for this window yet, so the resume re-runs it).  The
                # grant carries the window's byte size, so the fairness
                # ring can reason in bytes-per-grant (quota Retry-After)
                if pacer is not None:
                    pacer("pass_a", win, _win_nbytes(batch))
                # compile the grid-quantized kernel set for this
                # window's grid shape BEFORE its device work — a
                # 20-40 s cold remote compile must never serialize
                # inside a window.  First sight of each shape only
                # (window 0 plus any residual-grid stragglers);
                # process-wide cache makes warm runs a no-op.
                if use_device:
                    _prewarm_window_shapes(ds)
                    # ingest-once H2D: the window's resident payload
                    # places NOW — markdup keys, observe and apply all
                    # dispatch against this one placement
                    _make_resident(win, ds)
                if mark_duplicates:
                    # dispatch window i's [N, L] key/score reductions
                    # (on device i % n under a pool), then drain the
                    # oldest in-flight window once the queue is full —
                    # its columns had the whole queue depth to compute
                    # on their chip.  _md_dispatch handles eviction and
                    # returns None on the host paths.
                    disp = _md_dispatch(win, batch) if use_device else None
                    if disp is not None:
                        pend_cols.append((win, ds) + disp)
                        tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend_cols))
                        if len(pend_cols) >= md_depth:
                            _summarize(*pend_cols.popleft())
                    else:
                        # the device path may have just died with OLDER
                        # windows still in flight: drain them first —
                        # summaries must stay window-ordered, or the
                        # resolve barrier's offset slices apply
                        # duplicate flags to the wrong windows' rows
                        while pend_cols:
                            _summarize(*pend_cols.popleft())
                        _summarize(win, ds, None, None)
                if realign:
                    events.append(
                        realign_mod.extract_indel_event_arrays(
                            batch.to_numpy(), max_indel_size=mis
                        )
                    )
            while pend_cols:
                _summarize(*pend_cols.popleft())
        except BaseException:
            abort.set()
            raise
        ingest.join()
    stats["n_reads"] = n_reads
    # pin/validate the window plan and fix the resumable set: a window
    # (or the realigned tail part, index n_windows) whose part the
    # journal records as durably published is skipped in pass C
    if journal is not None:
        journal.confirm_plan(len(windows))
    done_parts = (
        journal.completed_windows() if journal is not None else frozenset()
    )
    n_resumed = len(done_parts)
    stats["windows_resumed"] = n_resumed
    if n_resumed:
        tr.count(tele.C_RESUME_WINDOWS_SKIPPED, n_resumed)
        # no total in the message: whether a realigned tail part exists
        # (the +1) is not known until the candidate split
        log.info(
            "resume: %d output window(s) already durably published; "
            "re-executing only the remainder", n_resumed,
        )
    if hb is not None:
        hb.set_total(len(windows))
    if header is None or not windows:
        tr.add_span(tele.SPAN_TOTAL, t_start_ns,
                    time.monotonic_ns() - t_start_ns)
        stats.update(tele.streamed_stats_view(tr.snapshot()))
        _finish_trace(tr, stats, hb)
        return stats

    # ---- barrier 1: resolve duplicates + merge targets ----------------
    def _resolve_sort_device():
        """Where the duplicate-resolve lexsort runs: a pool/mesh device
        (the packed summary keys sort on-chip via dist.device_lexsort,
        bitwise the host permutation) or None for the host np.lexsort.
        ``ADAM_TPU_RESOLVE_SORT={device,host}`` overrides the
        device-when-available default."""
        mode = os.environ.get("ADAM_TPU_RESOLVE_SORT", "").strip().lower()
        if mode and mode not in ("device", "host"):
            # the tuning-var contract every other ADAM_TPU_* knob keeps:
            # a typo warns and degrades to the default, never silently
            # does something else
            log.warning(
                "ADAM_TPU_RESOLVE_SORT=%r is not one of ('device', "
                "'host'); using the device-when-available default", mode,
            )
            mode = ""
        if mode == "host":
            return None
        if not use_device:
            # explicit override only: host backends keep the host sort
            # unless the operator asks for the default jax device
            return "default" if mode == "device" else None
        if res["device_lost"]:
            return None
        mp = exec_state["mesh"]
        if mp is not None:
            return mp.devices[0]
        if dpool is not None:
            alive = dpool.alive_devices()
            return alive[0] if alive else None
        return "default"

    with tr.span(tele.SPAN_RESOLVE), tele.pass_scope("resolve"):
        if mark_duplicates and summaries:
            sort_dev = _resolve_sort_device()
            sort_info: dict = {}
            dup = md_mod.resolve_duplicates(
                md_mod.concat_summaries(summaries), sort_device=sort_dev,
                sort_info=sort_info,
            )
            # gauge the OUTCOME, not the request: device_lexsort falls
            # back to the host np.lexsort internally on failure, and the
            # analyzer's "[device sort]" tag must never claim a win the
            # host sort actually delivered
            tr.gauge(
                tele.G_RESOLVE_DEVICE_SORT,
                1 if sort_info.get("device_sort") else 0,
            )
            off = 0
            for i, w in enumerate(windows):
                n = w.batch.n_rows
                b = w.batch.to_numpy()
                new_flags = md_mod.apply_duplicate_flags(
                    np.asarray(b.flags), dup[off : off + n]
                )
                windows[i] = w.with_batch(b.replace(flags=new_flags))
                off += n
            del summaries
        targets = (
            realign_mod.merge_events(
                np.concatenate(events, axis=0) if events
                else np.zeros((0, 5), np.int64),
                header.seq_dict.names, mts,
            )
            if realign
            else []
        )

    # ---- pass B: candidate split (pre-BQSR, reference order) ----------
    # (obs_parts/obs_replays/obs_windows — the host-side merge lists,
    # window-index attributed — are defined up top so the mesh degrade
    # hook can replay into them from any pass)
    with tr.span(tele.SPAN_SPLIT):
        candidates: list[AlignmentDataset] = []
        window_valid: list[int] = []
        for i, w in enumerate(windows):
            n_valid = w.batch.n_rows
            if targets:
                cand, w, n_valid = realign_mod.split_realign_candidates(
                    w, targets, header.seq_dict.names
                )
                if cand is not None:
                    candidates.append(cand)
                windows[i] = w
            window_valid.append(n_valid)

    # post-barrier-2 resume: the solved recalibration table persisted by
    # a previous run short-circuits the whole observe pass — a crash
    # after barrier 2 resumes straight into pass C without re-observing
    # anything.  -dump_observations forces a full re-merge (the CSV is
    # derived from the merged histograms, which the table alone cannot
    # reproduce); per-window sidecars still spare the device work.
    resume_table = None
    if journal is not None and recalibrate and not dump_observations:
        resume_table = journal.load_table()

    def _observe_host(w):
        total, mism, _rg, g = bqsr_mod._observe_device(
            w, known_snps, _host_backend() if use_device else backend
        )
        return device_fetch(total), device_fetch(mism), g

    def _obs_replay(i, w, dev):
        """Recovery hook for window i's barrier fetch: evict the chip
        that held its lazy histograms and recompute on a survivor (the
        host backend when none remain), returning host arrays."""

        def on_device(nd):
            with _handle_for(i, w.batch, nd) as rw:
                total, mism, _rg, g = bqsr_mod._observe_device(
                    w, known_snps, backend, device=nd, resident=rw
                )
                return device_fetch(total), device_fetch(mism), g

        def replay(exc):
            with tr.span(tele.SPAN_POOL_REPLAY, window=i,
                         **dp_mod.span_attrs(dev)), dp_mod.replay_scope():
                _evict_or_lose(dev, exc)
                return _on_survivors(i, on_device, lambda: _observe_host(w))

        return replay

    def _observe_window(i, w, defer=None, coalesce=True):
        """Observe one window -> ((total, mism, g), replay hook) for
        the host-side merge, or **None when the histograms were folded
        into the mesh's device-resident accumulator** (nothing comes
        home until barrier 2 fetches the one merged table), or
        ``_DEFERRED`` when the window rode the cross-job coalescer and
        its future was parked on ``defer`` (pass B resolves them after
        every window has submitted).  Walks
        dispatch failures to the next survivor and to the host backend
        when the pool is gone; a mesh failure degrades to the pool path
        and replays the accumulated windows.  A histogram persisted by
        a previous run (the barrier sidecars) loads instead of
        recomputing — identical int64 sums, so the merge stays
        bit-identical.  ``coalesce=False`` skips the coalescer (the
        fused-failure fallback re-enters here solo)."""
        if journal is not None and journal.resumed:
            got = journal.load_observation(i)
            if got is not None:
                tr.count(tele.C_RESUME_HISTOGRAMS_LOADED)
                return (np.asarray(got[0]), np.asarray(got[1]),
                        got[2]), None
        if not use_device:
            return _observe_host(w), None
        # megakernel tier: with the applied table already known, this
        # window's observe AND its pass-C apply+pack ride ONE donated
        # dispatch — the packed2 handle parks in fused_handles for pass
        # C to FETCH (no second dispatch).  Any ineligibility (no live
        # resident handle, table narrower than the window's grid)
        # returns None from the dispatch and the window falls through
        # to the separate passes below, bitwise identical by
        # construction (fused_bc_body is a pure composition of the two
        # pass bodies).
        if fused_table is not None and not res["device_lost"]:
            rw = resident_map.get(i)
            if rw is not None:
                # chaos-harness kill point: the mid-fused-dispatch leg
                # of the kill-and-resume matrix (nothing persisted yet
                # — a resume replays the window, fused or not)
                faults.point("proc.kill", device="fused_bc")
                mp_f = exec_state["mesh"]
                if mp_f is not None:
                    try:
                        with tele.pass_scope("observe"):
                            got = bqsr_mod.fused_bc_dispatch(
                                w, fused_table[0], known_snps, backend,
                                mesh=mp_f, resident=rw,
                            )
                            if got is not None:
                                handle, (total, mism, _rg, g) = got
                                mp_f.accumulate(total, mism, g)
                    except Exception as e:
                        _mesh_degrade(e, "pass-B fused dispatch")
                        # fall through: separate passes on the pool
                    else:
                        if got is not None:
                            mesh_obs.append((i, w))
                            fused_handles[i] = ("mesh", handle)
                            tr.count(tele.C_DEVICE_DISPATCHED)
                            tr.count(tele.C_MESH_DISPATCHED)
                            tr.count(tele.C_FUSED_DISPATCHED)
                            return None
                else:
                    try:
                        with tele.pass_scope("observe"):
                            got = bqsr_mod.fused_bc_dispatch(
                                w, fused_table[0], known_snps, backend,
                                device=rw.device, resident=rw,
                            )
                    except Exception as e:
                        # past the retry budget: evict the pinned chip
                        # (its resident handles drop with it) and fall
                        # through to the separate-pass survivor walk
                        _evict_or_lose(rw.device, e)
                    else:
                        if got is not None:
                            handle, (total, mism, _rg, g) = got
                            fused_handles[i] = (rw.device, handle)
                            tr.count(tele.C_DEVICE_DISPATCHED)
                            tr.count(tele.C_FUSED_DISPATCHED)
                            # histograms merge exactly like the solo
                            # observe's; a failed barrier fetch evicts
                            # and recomputes through the same hook
                            return (
                                (total, mism, g),
                                _obs_replay(i, w, rw.device),
                            )
        mp = exec_state["mesh"]
        if mp is not None:
            try:
                with tele.pass_scope("observe"), \
                        _handle_for(i, w.batch, mp) as rw:
                    total, mism, _rg, g = bqsr_mod._observe_device(
                        w, known_snps, backend, mesh=mp, resident=rw,
                    )
                    mp.accumulate(total, mism, g)
                mesh_obs.append((i, w))
                tr.count(tele.C_DEVICE_DISPATCHED)
                tr.count(tele.C_MESH_DISPATCHED)
                return None
            except Exception as e:
                _mesh_degrade(e, "pass-B observe")
                # fall through: this window re-dispatches on the pool

        if coalesce and coalescer is not None \
                and exec_state["mesh"] is None \
                and not res["device_lost"]:
            # cross-job batching: this window's observe rides a fused
            # dispatch; its read-group band of the fused histogram is
            # bitwise the solo scatter-add, so the barrier merge (and
            # everything downstream) cannot tell the difference.  Any
            # failure falls through to the solo pool path below.
            try:
                fut = coalescer.submit_observe(
                    i, w, known_snps, resident_map.get(i)
                )
            except Exception as e:
                log.warning(
                    "coalesced observe submit of window %d failed "
                    "(%s); dispatching solo", i, e,
                )
            else:
                if defer is not None:
                    defer.append((i, w, fut))
                    return _DEFERRED
                try:
                    with tele.pass_scope("observe"):
                        got = fut.result()
                except Exception as e:
                    log.warning(
                        "coalesced observe of window %d fell back to "
                        "the solo dispatch (%s)", i, e,
                    )
                else:
                    return got, None

        def on_device(dev):
            with _handle_for(i, w.batch, dev) as rw:
                total, mism, _rg, g = bqsr_mod._observe_device(
                    w, known_snps, backend, device=dev, resident=rw,
                )
            tr.count(tele.C_DEVICE_DISPATCHED)
            return (total, mism, g), _obs_replay(i, w, dev)

        with tele.pass_scope("observe"):
            return _on_survivors(
                i, on_device, lambda: (_observe_host(w), None)
            )

    def _observe_remainders():
        # non-candidate rows are untouched by realignment, so their
        # observations are identical on either side of it — which lets
        # this host pass hide under the realign sweeps' device drain.
        # On the device backend the histograms come back LAZY: every
        # window's scatter-add queues on the chip and the compact
        # tables are fetched together at the merge barrier.
        if resume_table is not None:
            # the solved table is already persisted: no observation can
            # change it, so the pass is pure waste on a resume
            return
        with tr.span(tele.SPAN_OBSERVE):
            if recalibrate:
                # coalesced windows park their futures here and resolve
                # AFTER every window has submitted: the coalescer sees
                # the job's whole window set at once (maximal fusion)
                # and the job thread keeps the solo path's overlap
                # instead of serializing on each fused dispatch
                deferred: list = []
                for i, w in enumerate(windows):
                    if window_valid[i]:
                        # chaos-harness kill point: one arrival per
                        # observed window — the mid-pass-B leg of the
                        # kill-and-resume matrix (nothing persisted
                        # yet: a resume replays every un-persisted
                        # observation, resident or not)
                        faults.point("proc.kill", device="pass_b")
                        # pool: window i's scatter-add queues on device
                        # i % n and its compact table merges host-side
                        # at the barrier.  mesh: the window shards over
                        # EVERY device, the histograms psum on-device
                        # and fold into the device-resident accumulator
                        # (_observe_window returns None) — barrier 2
                        # fetches one merged table, not one per window.
                        got = _observe_window(i, w, defer=deferred)
                        if got is _DEFERRED:
                            continue
                        if got is not None:
                            obs_parts.append(got[0])
                            obs_replays.append(got[1])
                            obs_windows.append(i)
                for i, w, fut in deferred:
                    try:
                        with tele.pass_scope("observe"):
                            got = (fut.result(), None)
                    except Exception as e:
                        log.warning(
                            "coalesced observe of window %d fell back "
                            "to the solo dispatch (%s)", i, e,
                        )
                        got = _observe_window(i, w, coalesce=False)
                    if got is not None:
                        obs_parts.append(got[0])
                        obs_replays.append(got[1])
                        obs_windows.append(i)

    # ---- tail: realign the gathered candidates (observing remainders
    # under the device wait), then observe the realigned part with its
    # post-realignment alignments (markdup -> realign -> BQSR, the
    # reference's Transform composition) ---------------------------------
    with tr.span(tele.SPAN_TAIL):
        realigned: Optional[AlignmentDataset] = None
        # resume fast path for the realign tail: when the realigned part
        # (index n_windows) is already durably published AND its
        # contribution to the recalibration table is recoverable (the
        # solved table itself, or its persisted observe histogram), the
        # whole candidate realign — the GEMM sweeps — is skippable.  The
        # sidecar is LOADED here, not just probed: an unreadable sidecar
        # must force the re-realign, or the merged table would silently
        # miss the realigned part's observations.
        skip_realign = False
        r_obs = None
        if (
            candidates and journal is not None and journal.resumed
            and len(windows) in done_parts
        ):
            if not recalibrate or resume_table is not None:
                skip_realign = True
            else:
                r_obs = journal.load_observation(len(windows))
                skip_realign = r_obs is not None
        if candidates and not skip_realign:
            cand = AlignmentDataset.concat(candidates)
            tr.count(tele.C_CANDIDATE_ROWS, int(cand.batch.n_rows))
            # fan the sweep GEMM buckets across the run's device set
            # (probe-paced weighted round-robin) instead of queueing them
            # all on the default device while the rest of the pool idles
            sweep_devs = None
            if use_device and not res["device_lost"]:
                if exec_state["mesh"] is not None:
                    sweep_devs = exec_state["mesh"].devices
                elif dpool is not None:
                    alive = dpool.alive_devices()
                    sweep_devs = alive if len(alive) > 1 else None
            with tele.pass_scope("sweep"):
                # the sweep scope covers the realign GEMM dispatch+drain;
                # the overlapped observe pass shadows it with its own scope
                realigned = realign_mod.realign_indels(
                    cand,
                    consensus_model=consensus_model,
                    known_indels=known_indels,
                    max_indel_size=mis,
                    max_consensus_number=mcn,
                    lod_threshold=lod,
                    max_target_size=mts,
                    overlap_work=_observe_remainders,
                    sweep_devices=sweep_devs,
                )
            if (
                recalibrate and realigned.batch.n_rows
                and resume_table is None
            ):
                with tr.span(tele.SPAN_OBSERVE_REALIGNED):
                    # the realigned part's grid shape rarely matches any
                    # ingest window's: warm its observe kernel before
                    # the dispatch
                    _prewarm_observe_shape(realigned)
                    # the realigned part is a window too: place it
                    # resident so its observe AND its pass-C apply
                    # dispatch off one ingest placement, like every
                    # streamed window
                    _make_resident(len(windows), realigned)
                    got = _observe_window(len(windows), realigned)
                    if got is not None:
                        obs_parts.append(got[0])
                        obs_replays.append(got[1])
                        obs_windows.append(len(windows))
            # subtract the observe wall from the tail ONLY when realign
            # reports it genuinely ran under the sweeps' device drain — on
            # the serial paths (Python fallback, no dispatched sweeps) the
            # old unconditional subtraction understated realign's serial
            # wall and inflated the derived cfg4 bench line
            hidden = bool(
                getattr(_observe_remainders, "overlap_ran_in_dispatch", False)
            )
        elif skip_realign:
            # journaled realigned part + recoverable table contribution:
            # observe the remaining windows (persisted histograms load, the
            # rest recompute) and splice the realigned part's persisted
            # histogram in at its window-plan position — the same part
            # order the uninterrupted run merges
            _observe_remainders()
            if r_obs is not None:
                tr.count(tele.C_RESUME_HISTOGRAMS_LOADED)
                obs_parts.append(
                    (np.asarray(r_obs[0]), np.asarray(r_obs[1]), r_obs[2])
                )
                obs_replays.append(None)
                obs_windows.append(len(windows))
            hidden = False
        else:
            _observe_remainders()
            # no realignment ran: the tail wall IS the observe pass
            hidden = False
    tr.gauge(tele.G_OBSERVE_HIDDEN, 1 if hidden else 0)
    stats["observe_overlap_hidden"] = hidden

    # ---- barrier 2: merge histograms, solve the table ------------------
    table = None
    gl = 0
    _mp_b2 = exec_state["mesh"]
    have_acc = _mp_b2 is not None and _mp_b2.has_accumulated()
    if resume_table is not None:
        # post-barrier-2 resume: the persisted table IS the barrier's
        # output (solved from the identical window histograms), so the
        # merge and solve are skipped wholesale
        table = np.ascontiguousarray(resume_table[0], np.uint8)
        gl = int(resume_table[1])
        tr.add_span(tele.SPAN_SOLVE, time.monotonic_ns(), 0)
    elif recalibrate and (obs_parts or have_acc):
        # chaos-harness kill point: barrier-2 entry (nothing persisted
        # yet — a resume replays every un-persisted observation)
        faults.point("proc.kill", device="barrier2")

        if have_acc:
            # THE mesh payoff: one compact merged table per distinct
            # grid width comes home — not one fetched copy per window.
            # A failed fetch degrades: the accumulated windows replay
            # through the pool/host observe into obs_parts.
            try:
                with tele.pass_scope("observe"):
                    acc_parts = exec_state["mesh"].fetch_accumulated(tr)
                tr.count(tele.C_DEVICE_FETCHED, len(acc_parts))
                mesh_obs.clear()
                for tt, mm, g_acc in acc_parts:
                    obs_parts.append(
                        (np.asarray(tt), np.asarray(mm), int(g_acc))
                    )
                    obs_replays.append(None)
                    # no single source window: the accumulator sums
                    # many — None suppresses the per-window sidecar
                    obs_windows.append(None)
            except Exception as e:
                _mesh_degrade(e, "barrier-2 accumulator fetch")

        def _persist_obs(win, tt, mm, g):
            # one atomic sidecar per window, written at the barrier as
            # each histogram becomes host-resident (idempotent: windows
            # whose sidecar loaded above rewrite nothing).  Best-effort
            # — the sidecars only ACCELERATE a resume; a full disk on
            # the run dir must not kill an otherwise healthy run.
            if journal is None:
                return
            try:
                journal.save_observation(win, tt, mm, g)
            except OSError as e:
                log.warning(
                    "observe sidecar persist failed for window %d: %s",
                    win, e,
                )

        # count only the parts that are genuinely device-resident at
        # the barrier — after a mid-run degradation some (or all) parts
        # are host-computed and the merge fetches nothing for them
        n_dev_parts = sum(
            1 for t, _m, _g in obs_parts if not isinstance(t, np.ndarray)
        )
        with tr.span(tele.SPAN_OBS_MERGE), tele.pass_scope("observe"):
            total, mism, gl = bqsr_mod.merge_observations(
                obs_parts, replays=obs_replays, tracer=tr,
                window_ids=obs_windows, on_part=_persist_obs,
            )
        if n_dev_parts:
            tr.count(tele.C_DEVICE_FETCHED, n_dev_parts)
        # the replay hooks close over every window's dataset: release
        # them NOW or pass C's free-as-we-go (windows[idx] = None)
        # frees nothing and peak residency becomes ALL windows at once
        obs_parts.clear()
        obs_replays.clear()
        # solve excludes the fetch: the stage rows are disjoint and sum
        # to the barrier wall
        with tr.span(tele.SPAN_SOLVE):
            if dump_observations:
                bqsr_mod.dump_observation_csv(
                    total, mism, header.read_groups.names + ["null"], gl,
                    dump_observations,
                )
            if known_table is not None:
                # known-sites run: the supplied table IS the applied
                # table, fused or not — the solve is skipped, while the
                # merge above still produced the sidecars/CSV a
                # discovered-table run would.  The table's own grid
                # width replaces the merge's (its cycle axis geometry,
                # not this input's, centers the apply gather).
                table = np.ascontiguousarray(known_table[0], np.uint8)
                gl = int(known_table[1])
            else:
                table = bqsr_mod.solve_recalibration_table(total, mism)
        if journal is not None:
            try:
                journal.save_table(table, gl)
            except OSError as e:
                log.warning("recalibration-table persist failed: %s", e)
        # chaos-harness kill point: barrier-2 exit (table persisted — a
        # resume goes straight into pass C)
        faults.point("proc.kill", device="barrier2")
    else:
        if recalibrate and known_table is not None:
            # no observations to merge (e.g. every window resumed), but
            # the known table still applies in pass C
            table = np.ascontiguousarray(known_table[0], np.uint8)
            gl = int(known_table[1])
        tr.add_span(tele.SPAN_SOLVE, time.monotonic_ns(), 0)

    # ---- pass C: apply || encode || part writes ------------------------
    # Three overlapped resources: the chip (device table gathers,
    # double-buffered so window i+1's gather runs while window i
    # fetches), the host CPU (OQ stash + arrow encode in the pool's
    # encoder threads), and the disk (the pool's dedicated write thread).
    from adam_tpu.io.parquet import PartWriterPool

    # the realigned part applies and submits FIRST: it is the largest
    # part, so its encode+write should overlap the window applies
    # instead of draining serially after them.  Windows whose part the
    # journal records as durably published are skipped outright — the
    # resume's whole point — and their decoded batches freed now.
    parts: list = []
    if realigned is not None and len(windows) not in done_parts:
        parts.append((len(windows), realigned))
    parts.extend(
        (i, w) for i, w in enumerate(windows)
        if window_valid[i] and i not in done_parts
    )
    for i in done_parts:
        if i < len(windows):
            windows[i] = None
    # windows with no part to write (resumed, or fully realigned away)
    # have no pass-C fetch to release them at — release their resident
    # handles now, so HBM tracks exactly the parts still in flight
    _part_idxs = {idx for idx, _w in parts}
    for win in list(resident_map):
        if win not in _part_idxs:
            _release_resident(win)
    stats["windows_fresh"] = len(parts)
    if hb is not None:
        # the part count THIS process will write (residual windows drop
        # out, the realigned part joins, resumed windows are skipped):
        # the heartbeat's ETA extrapolates parts_written against this —
        # windows_total itself stays the pass-A window count, so a
        # progress ratio can never exceed 1
        hb.set_parts_total(len(parts))

    from adam_tpu.io.parquet import part_index as parquet_part_index

    def _on_published(p):
        # writer-pool publish hook (write thread): the part's bytes are
        # durably on disk — record its window complete in the journal
        idx = parquet_part_index(p)
        if idx is not None:
            journal.record_window(idx, os.path.basename(p))

    # 3 parts in flight to start: one writing, one encoding, one being
    # applied/submitted — each stage's resource stays busy.  Under
    # adaptive sizing the pool may widen admission while submits gate,
    # but never past 2x this bound (each admitted part pins a decoded
    # window, so the cap is a memory bound as much as a concurrency
    # one — io/parquet.PartWriterPool).
    pool = PartWriterPool(
        n_encoders=max(1, n_writers - 1), inflight_parts=3,
        compression=compression,
        on_published=_on_published if journal is not None else None,
        tracer=tr,
    )

    def _submit(idx, ds, packed=None):
        # multi-job fairness / graceful drain: one grant per output
        # part.  A RunCancelled here is caught by the pass-C wrapper
        # below, which closes the writer pool GRACEFULLY — this part is
        # lost (it re-executes on resume) but every previously
        # submitted part still publishes and journals.
        if pacer is not None:
            pacer("pass_c", idx, _win_nbytes(ds.batch))
        # chaos-harness kill point: one arrival per fresh part submit
        faults.point("proc.kill", device="pass_c")
        pool.submit(_part_path(out_path, idx), ds.batch, ds.sidecar,
                    ds.header, packed=packed)

    # ---- SDC audit (shared by the pool, mesh and coalesced pass-C
    # paths — docs/ROBUSTNESS.md "Device health, hedging, and SDC
    # audit"): every path that publishes device-produced bytes is
    # auditable, or ADAM_TPU_AUDIT_RATE would silently protect only
    # solo pool runs while the multi-tenant serving modes ship
    # unaudited bits.
    def _host_audit_apply(w):
        return bqsr_mod.apply_recalibration(w, table, gl, _host_backend())

    def _audit_matches(done, p_packed, host_ds) -> bool:
        """Bit-compare the device-produced pass-C result against the
        host parity twin's recompute — the SDC audit's verdict.
        Packed payloads compare in the packed domain (the very bytes
        the Arrow column publishes), matrix results compare the whole
        post-apply qual matrix."""
        from adam_tpu.formats import schema
        from adam_tpu.io.arrow_pack import pack_matrix_host

        hb = host_ds.batch.to_numpy()
        if p_packed is None:
            return np.array_equal(
                np.asarray(done.batch.to_numpy().quals),
                np.asarray(hb.quals),
            )
        pq = getattr(p_packed, "quals", p_packed)
        exp_q = pack_matrix_host(
            np.asarray(hb.quals),
            bqsr_mod._apply_pack_lens(hb),
            schema.QUAL_SANGER_LUT256,
        )
        if not (np.array_equal(pq.buf, exp_q.buf)
                and np.array_equal(pq.lens, exp_q.lens)):
            return False
        pb = getattr(p_packed, "bases", None)
        if pb is not None:
            # the bases half rides its own fetch: audit it too, or a
            # flipped base byte would publish undetected
            exp_b = pack_matrix_host(
                np.asarray(hb.bases),
                bqsr_mod._apply_pack_lens_bases(hb),
                schema.BASE_DECODE_LUT256,
            )
            if not (np.array_equal(pb.buf, exp_b.buf)
                    and np.array_equal(pb.lens, exp_b.lens)):
                return False
        return True

    def _audit_result(p_idx, prod_dev, pre_ds, done, p_packed):
        """Dual-compute audit of a sampled window: recompute
        ``pre_ds`` (the window's pre-apply dataset) on the host parity
        twin and bit-compare.  A mismatch counts
        ``device.audit.mismatch`` and replaces the result with the
        host recompute — the published part is clean either way; when
        a single producing chip is attributable (``prod_dev``, the
        pool path) it is additionally QUARANTINED through the
        scoreboard (its resident handles drop, later windows avoid it
        until a clean re-admission probe).  Mesh collectives and
        coalesced dispatches have no single producing chip — their
        mismatches republish and count, and the operator reads the
        counter.  Returns the (possibly replaced) ``(done,
        p_packed)``."""
        tr.count(tele.C_AUDIT_SAMPLED)
        with tr.span(
            tele.SPAN_AUDIT_CHECK, window=p_idx,
            **(dp_mod.span_attrs(prod_dev) if prod_dev is not None
               else {}),
        ):
            host_ds = _host_audit_apply(pre_ds)
            matched = _audit_matches(done, p_packed, host_ds)
        if matched:
            return done, p_packed
        tr.count(tele.C_AUDIT_MISMATCH)
        log.error(
            "SDC audit: window %d's device result does not match the "
            "host recompute — %s and publishing the host bytes", p_idx,
            f"quarantining device {dp_mod._attr_id(prod_dev)}"
            if prod_dev is not None
            else "no single producing chip to quarantine",
        )
        if prod_dev is not None:
            health_board.quarantine(
                prod_dev,
                reason=f"sdc audit mismatch on window {p_idx}",
                tracer=tr,
            )
            _drop_resident_on(prod_dev)
        from adam_tpu.utils import incidents

        incidents.maybe_record(
            "audit.mismatch",
            device=dp_mod._attr_id(prod_dev)
            if prod_dev is not None else None,
            window=p_idx, trace_id=tr.trace, tracer=tr,
            reason="SDC dual-compute mismatch on window %d; host bytes "
                   "published" % p_idx,
        )
        return host_ds, None

    def _apply_on(idx, w, tbl, device=None, mesh=None):
        """Dispatch window ``idx``'s pass-C pack2 on ``device`` or the
        ``mesh`` -> handle.  A handle that a failed donating attempt
        consumed is replaced by a transient one, once; a second
        consumption is the device's failure."""
        target = mesh if mesh is not None else device
        for _ in range(2):
            with _handle_for(idx, w.batch, target) as rw:
                h = bqsr_mod.apply_recalibration_dispatch(
                    w, tbl, gl, backend, device=device, mesh=mesh,
                    resident=rw,
                )
            if h is not None:
                return h
        raise RuntimeError(
            f"window {idx}: failed donating dispatches consumed its "
            "resident handles"
        )

    def _apply_parts_mesh(plist):
        """Mesh pass C: the solved table places ONCE, replicated, and
        stays device-resident while every window's [N, L] gather shards
        over the mesh (double-buffered: window j+1's collective runs
        while window j fetches).  Returns the parts still to do — ``[]``
        on success, or (on a mesh failure) the in-flight + undispatched
        remainder for the pool path to finish, bit-identically."""
        mp = exec_state["mesh"]
        try:
            # the once-per-run table placement gets its own transfer
            # bucket: the "apply" bucket stays per-window traffic, so
            # the analyzer's ingest-only verdict compares marginals
            with tele.pass_scope("table"):
                tbl_dev = mp.put_replicated(
                    np.ascontiguousarray(table, np.uint8)
                )
            # re-warm the mesh apply against the SOLVED table's real
            # width, one entry per distinct window grid shape (the
            # pool path's apply_prewarm_entry semantics)
            seen_dims = {}
            for item in plist:
                bw = item[1].batch
                seen_dims.setdefault((bw.n_rows, bw.lmax), item[1])
            with tr.span(tele.SPAN_POOL_PREWARM_C):
                mp.prewarm([
                    part_mod.mesh_apply_prewarm_entry(
                        w.batch.to_numpy(), table.shape[0],
                        table.shape[2], mp,
                    )
                    for w in seen_dims.values()
                ], tracer=tr)
        except Exception as e:
            _mesh_degrade(e, "pass-C table placement")
            rem = list(plist)
            for j in range(len(plist)):
                plist[j] = None  # only the handed-off list may pin
            return rem
        pend: deque = deque()
        hb_queues.append((pend, 1))  # items: (idx, "mesh", handle)
        k = 0

        def _remainder(exc, where):
            # hand the un-finished work to the pool: in-flight handles
            # still carry their pre-recalibration datasets
            _mesh_degrade(exc, where)
            rem = [
                (i, bqsr_mod.apply_handle_dataset(h))
                for i, _tag, h in pend
            ]
            pend.clear()
            rem.extend(p for p in plist[k:] if p is not None)
            # the handed-off list must be the ONLY thing pinning the
            # remaining windows: the pool loop frees rem entries as it
            # dispatches, but the original parts list would keep every
            # dataset resident through the rest of pass C
            for j in range(len(plist)):
                plist[j] = None
            return rem

        while k < len(plist) or pend:
            # every device works each window, so the classic depth-2
            # double buffer is the whole pipeline depth
            if k < len(plist) and len(pend) < 2:
                idx, w = plist[k]
                fh = fused_handles.pop(idx, None)
                if fh is not None:
                    # megakernel tier: pass B's fused dispatch already
                    # produced this window's packed columns — the
                    # handle joins the in-flight queue FETCH-ONLY (no
                    # second dispatch, no dispatch count)
                    pend.append((idx, "mesh", fh[1]))
                    tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend))
                    plist[k] = None
                    k += 1
                    continue
                try:
                    with tr.span(
                        tele.SPAN_APPLY_DISPATCH, window=idx,
                        device="mesh",
                    ):
                        handle = _apply_on(idx, w, tbl_dev, mesh=mp)
                except Exception as e:
                    return _remainder(e, "pass-C apply dispatch")
                tr.count(tele.C_DEVICE_DISPATCHED)
                tr.count(tele.C_MESH_DISPATCHED)
                pend.append((idx, "mesh", handle))
                tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend))
                plist[k] = None  # must not pin every window
                k += 1
                continue
            p_idx, _tag, p_handle = pend[0]
            try:
                with tr.span(
                    tele.SPAN_APPLY_FETCH, window=p_idx, device="mesh",
                ):
                    done, p_packed = (
                        bqsr_mod.apply_recalibration_finish_packed(
                            p_handle
                        )
                    )
            except Exception as e:
                return _remainder(e, "pass-C apply fetch")
            pend.popleft()
            tr.count(tele.C_DEVICE_FETCHED)
            # SDC audit: mesh collectives have no single producing chip
            # to quarantine, but a sampled mismatch still counts and
            # the host bytes still publish
            if sdc_audit_rate > 0 and health_mod.audit_due(
                p_idx, sdc_audit_rate
            ):
                done, p_packed = _audit_result(
                    p_idx, None,
                    bqsr_mod.apply_handle_dataset(p_handle),
                    done, p_packed,
                )
            # OUTSIDE the mesh try blocks, like the pool path: a writer-
            # pool fail-fast error is an output failure, not a mesh
            # failure — it must abort the run with its own attribution,
            # never trigger a degrade-and-replay
            _submit(p_idx, done, p_packed)
            # refcounted release after pass C: the window's device
            # arrays free as its part submits (the host copy lives on
            # in the writer pool until the part publishes)
            _release_resident(p_idx)
            if p_idx < len(windows):
                windows[p_idx] = None  # free as we go
        return []

    def _apply_parts_pool(plist):
        # replicate the solved u8 table once per pool device
        # (~4 MB each) instead of re-shipping it per window
        dev_tables = None
        if dpool is not None:
            tbl_c = np.ascontiguousarray(table, np.uint8)
            # replicas keyed by ORIGINAL pool index (stable
            # under eviction); dead devices get no replica —
            # _pick_device never hands them out.  Placed via
            # putter so the per-device table replication shows
            # up in the h2d transfer ledger.
            alive_now = dpool.alive_devices()
            # own transfer bucket (once-per-run, not per-window): see
            # the mesh table placement above
            with tele.pass_scope("table"):
                dev_tables = [
                    dp_mod.putter(d)(tbl_c) if d in alive_now
                    else None
                    for d in dpool.devices
                ]
            # re-warm the apply gather against the SOLVED
            # table's real width: merge_observations can widen
            # the table past window 0's grid, which pass A's
            # prewarm assumed — uniform-lmax inputs dedupe this
            # to a no-op against the process-wide cache.  One
            # entry per distinct window grid shape.
            from adam_tpu.parallel.device_pool import (
                apply_prewarm_entry,
            )

            seen_dims = {}
            for item in plist:
                bw = item[1].batch
                seen_dims.setdefault(
                    (bw.n_rows, bw.lmax), item[1]
                )
            # umbrella wall for the re-warm: the stats view
            # folds it into prewarm_s and subtracts it from
            # apply_split_s, so compile time never shows up as
            # host encode/submit time
            with tr.span(tele.SPAN_POOL_PREWARM_C):
                dpool.prewarm([
                    apply_prewarm_entry(
                        w.batch.to_numpy(), table.shape[0], table.shape[2],
                    )
                    for w in seen_dims.values()
                ], tracer=tr)
        # in-flight queue of (part idx, device, handle): depth
        # 2 single-device (the classic double buffer); with a
        # pool a double buffer per device — window j+1's gather
        # on chip B runs while window j fetches from chip A
        apply_depth = 2 if dpool is None else 2 * dpool.n
        pend_q: deque = deque()
        hb_queues.append((pend_q, 1))  # items: (idx, dev, handle)
        # window -> monotonic time of its pass-C dispatch.  The health
        # board reads each window's dispatch+fetch wall: a pool pass
        # dispatches up to two windows per device before its first
        # fetch, so that fetch waits out its whole kernel while the
        # peers' fetches find theirs done, and a fetch-only wall would
        # read the chip of the first-fetched window as a straggler
        sent_at: dict = {}

        def _host_apply(w):
            return bqsr_mod.apply_recalibration(
                w, table, gl, _host_backend()
            )

        def _device_table(dev):
            if dpool is None:
                return table
            i = dpool.devices.index(dev)
            if dev_tables[i] is None:
                # a device with no replica joined placement mid-pass
                # (a health-probation chip re-admitted by its probe):
                # place its table copy now, once
                with tele.pass_scope("table"):
                    dev_tables[i] = dp_mod.putter(dev)(
                        np.ascontiguousarray(table, np.uint8)
                    )
            return dev_tables[i]

        def _apply_sync(p_idx, w):
            """Synchronous dispatch+fetch -> (dataset, packed | None)
            on the survivor walk (the same pack2 on a handle the
            dispatch loop runs), host backend when the device path is
            gone — byte-identical output either way."""

            def on_device(nd):
                return bqsr_mod.apply_recalibration_finish_packed(
                    _apply_on(p_idx, w, _device_table(nd), device=nd)
                )

            return _on_survivors(
                p_idx, on_device, lambda: (_host_apply(w), None)
            )

        def _replay_apply(p_idx, dev, w, exc):
            """Window p_idx's apply died on ``dev``: evict it and re-run
            dispatch+fetch synchronously on a survivor."""
            with tr.span(tele.SPAN_POOL_REPLAY, window=p_idx,
                         **dp_mod.span_attrs(dev)), \
                    dp_mod.replay_scope():
                _evict_or_lose(dev, exc)
                return _apply_sync(p_idx, w)

        def _hedge_redispatch(p_idx, p_dev, p_handle):
            """The speculative twin of a late window -> (closure, nd):
            synchronous dispatch+fetch on another alive device ``nd``,
            from the host-retained dataset (the PR 13 replay contract)
            — output bytes identical by kernel determinism + backend
            parity.  Raises when no alternate device exists (the
            caller then never fires the hedge)."""
            others = [
                d for d in dpool.alive_devices() if d is not p_dev
            ] if dpool is not None else []
            if not others:
                raise RuntimeError("no alternate device to hedge on")
            nd = others[p_idx % len(others)]
            w = bqsr_mod.apply_handle_dataset(p_handle)

            def run():
                with tr.span(
                    tele.SPAN_APPLY_DISPATCH, window=p_idx, hedge=1,
                    **dp_mod.span_attrs(nd),
                ):
                    h = _apply_on(p_idx, w, _device_table(nd), device=nd)
                return bqsr_mod.apply_recalibration_finish_packed(h)

            return run, nd

        def _fetch_one():
            p_idx, p_dev, p_handle = pend_q.popleft()
            if p_dev == "batch":
                # coalesced window: the future resolves to a standard
                # dispatch handle whose payload is already host-
                # resident (the coalescer fetched the fused output
                # once and split it per job)
                p_packed = None
                try:
                    handle = p_handle.result()
                    with tr.span(
                        tele.SPAN_APPLY_FETCH, window=p_idx,
                        device="batch",
                    ):
                        done, p_packed = (
                            bqsr_mod.apply_recalibration_finish_packed(
                                handle
                            )
                        )
                except Exception as e:
                    log.warning(
                        "coalesced apply of window %d fell back to "
                        "the solo dispatch (%s)", p_idx, e,
                    )
                    done, p_packed = _apply_sync(p_idx, p_handle.dataset)
                else:
                    # SDC audit, fused-fetch success only: a fused
                    # dispatch has no single producing chip to
                    # quarantine, but a sampled mismatch still counts
                    # and the host bytes still publish.  The fallback
                    # branch may have applied on the HOST — auditing
                    # host bytes against a host recompute can never
                    # mismatch and would just double the window's cost
                    if sdc_audit_rate > 0 and health_mod.audit_due(
                        p_idx, sdc_audit_rate
                    ):
                        done, p_packed = _audit_result(
                            p_idx, None, p_handle.dataset, done,
                            p_packed,
                        )
                _submit(p_idx, done, p_packed)
                _release_resident(p_idx)
                return
            attrs = dp_mod.span_attrs(p_dev)
            p_packed = None
            prod_dev = p_dev  # the device whose bits we end up using
            # hedged dispatch (Dean & Barroso): once the apply kernel
            # has a pooled p99, an in-flight window past
            # ADAM_TPU_HEDGE_FACTOR x p99 speculatively re-dispatches
            # on another alive device from the host-retained copy —
            # first result wins, bytes identical by parity
            thr = None
            if (
                dpool is not None and not res["device_lost"]
                and p_dev is not None
                and len(dpool.alive_devices()) > 1
            ):
                thr = health_board.hedge_threshold("bqsr.apply")
            try:
                t_fetch = time.monotonic()
                # a fetch-only window (the fused tier's) counts from here
                t_sent = sent_at.pop(p_idx, t_fetch)
                hedged = False
                with tr.span(
                    tele.SPAN_APPLY_FETCH, window=p_idx, **attrs
                ):
                    if thr is None:
                        done, p_packed = (
                            bqsr_mod.apply_recalibration_finish_packed(
                                p_handle
                            )
                        )
                    else:
                        nd_box: list = []

                        def _hedge_fn():
                            run, nd = _hedge_redispatch(
                                p_idx, p_dev, p_handle
                            )
                            nd_box.append(nd)
                            return run()

                        (done, p_packed), winner, hedged = (
                            dp_mod.hedged_call(
                                lambda: (
                                    bqsr_mod
                                    .apply_recalibration_finish_packed(
                                        p_handle
                                    )
                                ),
                                _hedge_fn, thr, tracer=tr,
                            )
                        )
                        if winner == "hedge":
                            prod_dev = nd_box[0]
                            # the primary lost to a COLD re-dispatch on
                            # a peer: the strongest straggler signal —
                            # without it a chip whose every window
                            # hedges would never accrue a latency
                            # penalty (its own wall never finishes, so
                            # observe_latency has nothing true to feed)
                            health_board.note_hedge_lost(
                                p_dev, "bqsr.apply", tracer=tr
                            )
                tr.count(tele.C_DEVICE_FETCHED)
                if not hedged and p_dev is not None:
                    # feed the scoreboard's per-kernel latency pool
                    # (hedge-inflated walls stay out of it; a LOST
                    # race penalizes through note_hedge_lost above).
                    # Only REAL device attributions feed it — the
                    # poolless default-device path's None would accrue
                    # EWMAs on a phantom "default" key that no pool can
                    # probe and that the cross-device best-peer check
                    # would read as a (stale, fast) peer in a LATER
                    # pooled run on this process-wide board
                    health_board.observe_latency(
                        "bqsr.apply", p_dev,
                        time.monotonic() - t_sent, tracer=tr,
                    )
            except Exception as e:
                # the replay re-applies synchronously: pack2 on a
                # survivor's handle, or the host backend's matrix
                done, p_packed = _replay_apply(
                    p_idx, p_dev,
                    bqsr_mod.apply_handle_dataset(p_handle), e,
                )
            else:
                # SDC audit: a deterministic ADAM_TPU_AUDIT_RATE sample
                # of windows dual-computes on the host parity twin and
                # bit-compares; a mismatch quarantines the producing
                # chip and the HOST bytes publish
                if sdc_audit_rate > 0 and health_mod.audit_due(
                    p_idx, sdc_audit_rate
                ):
                    done, p_packed = _audit_result(
                        p_idx, prod_dev,
                        bqsr_mod.apply_handle_dataset(p_handle),
                        done, p_packed,
                    )
            _submit(p_idx, done, p_packed)
            # refcounted release after pass C: the window's device
            # arrays free as its part submits (the host copy lives on
            # in the writer pool until the part publishes)
            _release_resident(p_idx)

        for j in range(len(plist)):
            idx, w = plist[j]
            plist[j] = None  # the list must not pin every window

            if coalescer is not None and not res["device_lost"]:
                # cross-job batching: the window's apply rides a fused
                # dispatch (per-job table band + per-job payload split
                # on the fetch); the future joins the same in-flight
                # queue as a solo handle, so the double buffer and the
                # writer-pool overlap are unchanged
                try:
                    fut = coalescer.submit_apply(
                        idx, w, table, resident=resident_map.get(idx),
                    )
                except Exception as e:
                    log.warning(
                        "coalesced apply submit of window %d failed "
                        "(%s); dispatching solo", idx, e,
                    )
                else:
                    pend_q.append((idx, "batch", fut))
                    tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend_q))
                    del w
                    if idx < len(windows):
                        windows[idx] = None  # free as we go
                    if len(pend_q) >= apply_depth:
                        _fetch_one()
                    continue

            fh = fused_handles.pop(idx, None)
            if fh is not None:
                # megakernel tier: the packed columns are already on
                # the producing chip from pass B's fused dispatch —
                # fetch-only (no second dispatch, no dispatch count).
                # A failed fetch takes _fetch_one's normal replay path:
                # evict and re-apply separately on a survivor/host,
                # byte-identical by the parity contract.
                pend_q.append((idx, fh[0], fh[1]))
                tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend_q))
                del w
                if idx < len(windows):
                    windows[idx] = None  # free as we go
                if len(pend_q) >= apply_depth:
                    _fetch_one()
                continue

            def _dispatch_one(dev, idx=idx, w=w):
                sent_at[idx] = time.monotonic()
                with tr.span(
                    tele.SPAN_APPLY_DISPATCH, window=idx,
                    **dp_mod.span_attrs(dev),
                ):
                    handle = _apply_on(
                        idx, w, _device_table(dev), device=dev
                    )
                tr.count(tele.C_DEVICE_DISPATCHED)
                return dev, handle

            # round-robin by WINDOW index (not parts position): the
            # resident handle was pinned at ingest by _pick_device(win),
            # and an index mismatch here would place a transient handle
            # for every window (placement never affects output bytes)
            got = _on_survivors(idx, _dispatch_one, lambda: None)
            if got is None:  # device path lost: apply host-side
                _submit(idx, _host_apply(w))
            else:
                pend_q.append((idx,) + got)
                tr.gauge(tele.G_DEVICE_INFLIGHT, len(pend_q))
            del w
            if idx < len(windows):
                windows[idx] = None  # free as we go
            if len(pend_q) >= apply_depth:
                _fetch_one()
        while pend_q:
            _fetch_one()

    try:
        # the span wraps apply+submit only; the device dispatch/fetch
        # walls inside it are their own DISJOINT child spans, so the
        # derived apply_split_s (pass C minus dispatch minus fetch) sums
        # with them to the pass wall instead of double-counting it
        with tr.span(tele.SPAN_PASS_C), tele.pass_scope("apply"):
            if table is not None and use_device and not res["device_lost"]:
                todo = parts
                if exec_state["mesh"] is not None:
                    todo = _apply_parts_mesh(parts)
                if todo:
                    _apply_parts_pool(todo)
            else:
                # host path — also the full-degradation path: with the
                # device backend lost, the per-residue apply runs on
                # the native/numpy twin (bit-identical by parity)
                apply_backend = (
                    _host_backend() if use_device else backend
                )
                for j in range(len(parts)):
                    idx, w = parts[j]
                    parts[j] = None  # the list must not pin every window
                    if table is not None:
                        w = bqsr_mod.apply_recalibration(
                            w, table, gl, apply_backend
                        )
                    if idx < len(windows):
                        windows[idx] = None  # free as we go
                    _submit(idx, w)
                    _release_resident(idx)
    except RunCancelled:
        # graceful drain at a pass-C boundary: close the pool NON-abort
        # so every part already submitted encodes, publishes durably
        # and journals via on_published — the drained job's resume
        # starts exactly past them.  A worker error surfacing from the
        # drain-time close replaces the cancellation (it is a real
        # output failure, not a drain artifact).
        with tr.span(tele.SPAN_WRITE_WAIT):
            pool.close()
        raise
    except BaseException:
        try:  # drain the pool + discard its unpublished temp parts,
            # but surface the apply-path error
            pool.close(abort=True)
        except BaseException:
            pass
        raise
    with tr.span(tele.SPAN_WRITE_WAIT):
        pool.close()
    # backstop: any handle pass C had no fetch to release (edge paths)
    # frees here, so the live-bytes gauge ends at 0 on every clean run
    for win in list(resident_map):
        _release_resident(win)
    stats["resident_windows"] = resident_live["made"]
    if use_device:
        # run-end health publish: every tracked device's scoreboard
        # state lands in the snapshot's `health` section (the
        # analyzer's "Device health" rows), beside the transition
        # counters recorded as they happened
        health_board.publish(tr)
    tr.add_span(tele.SPAN_TOTAL, t_start_ns,
                time.monotonic_ns() - t_start_ns)

    # Timing keys are a DERIVED VIEW of the run tracer's span data —
    # the span-derived view and the stats dict cannot disagree.
    stats.update(tele.streamed_stats_view(tr.snapshot()))
    _finish_trace(tr, stats, hb)
    return stats


def _finish_trace(tr: tele.Tracer, stats: dict, hb=None) -> None:
    """End-of-run telemetry plumbing: stop the heartbeat (BEFORE the
    absorb below — a post-absorb sample would double-count every
    counter the run tracer shares with the global TRACE), mirror the
    derived stage walls into the named-timer registry (so
    ``-print_metrics`` decomposes the streamed flagship the way the
    reference's Metrics listener decomposes a Spark job) and fold the
    run tracer's events/metrics into the global TRACE when telemetry
    is enabled."""
    from adam_tpu.utils import instrumentation as ins

    _stop_heartbeat(hb)

    for key, label in (
        ("prewarm_s", "Streamed Device Prewarm (per-device compiles)"),
        ("ingest_pass_s", "Streamed Pass A (ingest + summaries)"),
        ("md_cols_fetch_s", "Streamed MarkDup Columns (device fetch)"),
        ("resolve_s", "Streamed Barrier (dup resolve + targets)"),
        ("split_s", "Streamed Pass B (candidate split)"),
        ("observe_s", "Streamed BQSR Observe (inside the realign tail)"),
        ("realign_s", "Streamed Tail (realign net of overlap)"),
        ("obs_merge_fetch_s", "Streamed Observe Merge (device fetch)"),
        ("solve_s", "Streamed Barrier (solve recalibration)"),
        ("apply_device_dispatch_s", "Streamed Pass C (device dispatch)"),
        ("apply_device_fetch_s", "Streamed Pass C (device fetch)"),
        ("apply_split_s", "Streamed Pass C (apply)"),
        ("write_wait_s", "Streamed Write Wait"),
    ):
        if key in stats:
            ins.TIMERS.add(label, int(stats[key] * 1e9))
    if tele.TRACE.recording:
        tele.TRACE.absorb(tr)
