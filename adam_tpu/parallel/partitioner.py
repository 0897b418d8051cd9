"""Genome-coordinate partitioning + streamed execution partitioners.

Genome-coordinate half — semantics of ``rdd/GenomicPartitioners.scala``:

* :func:`position_partition` — GenomicPositionPartitioner.getPartition
  (:63-85): map (contig, pos) to one of N partitions by cumulative genome
  offset, with one extra partition for unmapped reads (partition N).
* :func:`region_partition` — GenomicRegionPartitioner (:102-121):
  fixed-size coordinate bins per contig.

Both return plain arrays so the result can drive either a host-side
scatter into per-device shards or a device all_to_all exchange.

Execution half — how the streamed flagship places per-window device
work (``--partitioner {pool,mesh}`` / ``ADAM_TPU_PARTITIONER``):

* ``pool`` — the PR-3 round-robin :class:`~adam_tpu.parallel.
  device_pool.DevicePool`: window *i*'s kernels land whole on device
  ``i % n``, per-device observe histograms fetch to the host and merge
  in window order at barrier 2.  The fault-tolerance layer
  (eviction/replay, docs/ROBUSTNESS.md) lives here.
* ``mesh`` — :class:`MeshPartitioner`, the SPMD mode: every window's
  [N, L] arrays shard their read-row axis over a 1-D ``batch``
  :class:`jax.sharding.Mesh` spanning ALL the devices, the pass-B
  observe histograms ``psum`` on-device and accumulate into a
  device-resident running table, and only THE merged table (one
  compact [n_rg, 94, 2gl+1, 17] pair per distinct grid width) crosses
  to the host at barrier 2 — instead of one fetched copy per window,
  the measured 74%-of-wall barrier-2 cost (docs/PERF.md).  The solved
  recalibration table is placed once, replicated, and stays
  device-resident through pass C.  On any device failure the mode
  **degrades to the pool path** (bit-identically — the kernels are the
  same math; windows already folded into a suspect accumulator replay
  through the pool/host observe), so PR 4's eviction/replay contract
  holds unchanged.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from functools import cached_property, partial
from typing import Optional, Sequence

import numpy as np

from adam_tpu.models.dictionaries import SequenceDictionary

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class GenomeBins:
    """Fixed-size genome binning (ShuffleRegionJoin.scala:140-193).

    Bin ids stack per contig in dictionary order; ``invert`` recovers the
    bin's region. This is the static genome->shard mapping shared by
    :func:`region_partition` and the shuffle region join.
    """

    bin_size: int
    seq_dict: SequenceDictionary

    @cached_property
    def bins_per_contig(self) -> np.ndarray:
        # every contig owns at least one bin, so contigs with undeclared
        # (0) length still have a home in the bin-id space
        return np.maximum(-(-self.seq_dict.lengths // self.bin_size), 1)

    @cached_property
    def bin_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.bins_per_contig)])

    @property
    def num_bins(self) -> int:
        return int(self.bin_offsets[-1])

    def start_bin(self, contig_idx, start):
        ci = np.asarray(contig_idx)
        local = np.asarray(start) // self.bin_size
        return self.bin_offsets[ci] + np.minimum(
            local, self.bins_per_contig[ci] - 1
        )

    def end_bin(self, contig_idx, end):
        """Bin of the last covered base (end is exclusive). Clamped to the
        contig's last bin so intervals overhanging a declared contig
        length never spill into the next contig's bin-id range."""
        ci = np.asarray(contig_idx)
        local = np.maximum(np.asarray(end) - 1, 0) // self.bin_size
        return self.bin_offsets[ci] + np.minimum(
            local, self.bins_per_contig[ci] - 1
        )

    def invert(self, bin_id: int):
        """bin id -> (contig_idx, start, end) region of the bin."""
        contig = int(np.searchsorted(self.bin_offsets, bin_id, "right") - 1)
        local = bin_id - int(self.bin_offsets[contig])
        start = local * self.bin_size
        end = max(
            min(start + self.bin_size, int(self.seq_dict.lengths[contig])),
            start,
        )
        return contig, start, end

    def dedupe_region(self, bin_id: int):
        """Like :meth:`invert`, but the last bin of each contig extends to
        +inf: overhanging intervals clamp into that bin, and their starts
        must still satisfy the at-least-one-side-starts-here join rule."""
        contig, start, end = self.invert(bin_id)
        if bin_id == int(self.bin_offsets[contig + 1]) - 1:
            end = np.iinfo(np.int64).max
        return contig, start, end


def position_partition(
    seq_dict: SequenceDictionary,
    contig_idx,
    pos,
    num_partitions: int,
) -> np.ndarray:
    """Partition id per read; unmapped (contig_idx < 0) -> num_partitions.

    Mapped reads land in int(num_partitions * flattened / total_length),
    the cumulative-offset binning of the reference.
    """
    contig_idx = np.asarray(contig_idx)
    pos = np.asarray(pos)
    offsets = seq_dict.offsets
    total = max(seq_dict.total_length, 1)
    safe_idx = np.clip(contig_idx, 0, max(len(seq_dict) - 1, 0))
    flat = offsets[safe_idx] + np.maximum(pos, 0)
    part = (num_partitions * flat) // total
    part = np.clip(part, 0, num_partitions - 1)
    return np.where(contig_idx < 0, num_partitions, part).astype(np.int64)


def region_partition(
    seq_dict: SequenceDictionary,
    contig_idx,
    pos,
    partition_size: int,
) -> np.ndarray:
    """Fixed-size bin id, unique across contigs (bins stack per contig)."""
    contig_idx = np.asarray(contig_idx)
    pos = np.asarray(pos)
    bins = GenomeBins(partition_size, seq_dict)
    safe_idx = np.clip(contig_idx, 0, max(len(seq_dict) - 1, 0))
    out = bins.start_bin(safe_idx, np.maximum(pos, 0))
    return np.where(contig_idx < 0, -1, out).astype(np.int64)


def shard_rows_by_position(
    seq_dict: SequenceDictionary, contig_idx, pos, n_shards: int
) -> list[np.ndarray]:
    """Row indices per shard (unmapped rows appended to the last shard),
    the host-side scatter used to feed a genome-sharded mesh."""
    part = position_partition(seq_dict, contig_idx, pos, n_shards)
    part = np.where(part >= n_shards, n_shards - 1, part)
    return [np.flatnonzero(part == s) for s in range(n_shards)]


def partition_by_contig(contig_idx, n_partitions: int | None = None):
    """Partition rows by contig (rdd/ReferencePartitioner.scala): every
    row of a contig lands on the same partition.

    -> i32[N] partition ids in [0, n_partitions); unplaced rows (-1
    contig) go to the last partition.  Defaults to one partition per
    contig present.
    """
    contig_idx = np.asarray(contig_idx)
    uniq = np.unique(contig_idx[contig_idx >= 0])
    if n_partitions is None:
        n_partitions = max(1, len(uniq)) + 1
    # rank-encode before the modulo: raw ids can be sparse/high, which
    # would collide distinct contigs while leaving partitions empty
    rank = np.searchsorted(uniq, np.clip(contig_idx, 0, None))
    part = np.where(
        contig_idx >= 0,
        rank % max(1, n_partitions - 1),
        n_partitions - 1,
    )
    return part.astype(np.int32)


def shard_rows_by_contig(contig_idx, n_shards: int):
    """Row-index lists per shard under contig partitioning."""
    part = partition_by_contig(contig_idx, n_shards)
    return [np.flatnonzero(part == s) for s in range(n_shards)]


# ==========================================================================
# Streamed execution partitioners (--partitioner {pool,mesh})
# ==========================================================================
EXECUTION_MODES = ("pool", "mesh")


def resolve_execution_mode(override: Optional[str] = None) -> str:
    """Resolve the streamed pipeline's execution partitioner.

    Order: explicit ``override`` (the ``--partitioner`` flag — invalid
    values are a hard error), then ``ADAM_TPU_PARTITIONER`` (invalid
    values warn and degrade to ``pool``, the tuning-var contract), then
    ``pool`` — the fault-tolerance-hardened default; ``mesh`` is the
    opt-in SPMD mode.
    """
    v = (override or "").strip().lower()
    if v:
        if v not in EXECUTION_MODES:
            raise ValueError(
                f"--partitioner={v!r}: expected one of {EXECUTION_MODES}"
            )
        return v
    v = os.environ.get("ADAM_TPU_PARTITIONER", "").strip().lower()
    if v and v not in EXECUTION_MODES:
        log.warning(
            "ADAM_TPU_PARTITIONER=%r is not one of %s; using 'pool'",
            v, EXECUTION_MODES,
        )
        v = ""
    return v or "pool"


def healthy_subset(devices: Sequence, board=None) -> list:
    """The device subset the mesh should span, per the health
    scoreboard (utils/health.py): probation/evicted chips are excluded
    at CONSTRUCTION time — a collective spans every mesh device, so
    one quietly-bad chip would poison every window, and the mesh has
    no per-chip eviction to fall back on (docs/ROBUSTNESS.md
    "Mesh-mode degradation").  Falls back to the full set when the
    board would empty it (availability beats health) and never shrinks
    below one device."""
    if board is None:
        from adam_tpu.utils.health import BOARD as board
    devs = list(devices)
    ok = [d for d in devs if not board.blocked(d)]
    if ok and len(ok) < len(devs):
        log.warning(
            "mesh construction excluded %d health-blocked device(s); "
            "spanning the %d healthy one(s)", len(devs) - len(ok),
            len(ok),
        )
    return ok if ok else devs


# ---- mesh jit wrappers (module level: ONE executable cache per shape,
# shared by the prewarm and every window's dispatch) -----------------------
def _mesh_specs(n_args: int):
    from jax.sharding import PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS

    return tuple(P(BATCH_AXIS) for _ in range(n_args))


def _mesh_observe_packed_jit_builder(donate: bool):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS

    def run(bases, quals, lengths, flags, rg, res_pk, mm_pk, rd_ok,
            n_rg, lmax, mesh):
        from adam_tpu.pipelines.bqsr import observe_packed_body

        @partial(
            shard_map, mesh=mesh, in_specs=_mesh_specs(8),
            out_specs=(P(), P()), check_vma=False,
        )
        def body(b, q, le, fl, r, rp, mp, ok):
            # each shard unpacks its own bit-packed mask rows then runs
            # the exact observe scatter-add; i64 psum keeps the merge
            # bitwise order-free (the plain mesh observe's contract)
            total, mism = observe_packed_body(
                b, q, le, fl, r, rp, mp, ok, n_rg, lmax
            )
            return (
                jax.lax.psum(total, BATCH_AXIS),
                jax.lax.psum(mism, BATCH_AXIS),
            )

        return body(bases, quals, lengths, flags, rg, res_pk, mm_pk, rd_ok)

    kw = {"static_argnames": ("n_rg", "lmax", "mesh")}
    if donate:
        # the bit-packed masks are per-pass temporaries: dead after the
        # unpack, so donating them trims the observe HBM footprint
        kw["donate_argnums"] = (5, 6)
    return partial(jax.jit, **kw)(run)


def _mesh_apply_pack2_jit_builder(donate: bool):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS

    def run(bases, quals, lengths, flags, rg, has_qual, valid, table,
            lmax, mesh):
        from adam_tpu.pipelines.bqsr import apply_pack2_body

        @partial(
            shard_map, mesh=mesh, in_specs=_mesh_specs(7) + (P(),),
            out_specs=(P(BATCH_AXIS), P(BATCH_AXIS)), check_vma=False,
        )
        def body(b, q, le, fl, r, hq, v, tbl):
            # the bases half of the packed tail: each shard fuses the
            # gather with BOTH column packs over its own row block; the
            # two global flat outputs are shard payloads in shard order
            # (== row order), so the host-side per-shard slices of each
            # reproduce the single-device packs
            return apply_pack2_body(
                b, q, le, fl, r, hq, v, tbl, lmax,
                b.shape[0] * b.shape[1],
            )

        return body(bases, quals, lengths, flags, rg, has_qual, valid, table)

    kw = {"static_argnames": ("lmax", "mesh")}
    if donate:
        # the resident quals buffer becomes the packed qual column and
        # the resident bases buffer the packed base column (byte sizes
        # match exactly: [g, gl] u8 vs [g*gl] u8 each)
        kw["donate_argnums"] = (0, 1)
    return partial(jax.jit, **kw)(run)


def _mesh_markdup_jit_builder():
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS

    @partial(jax.jit, static_argnames=("mesh",))
    def run(start, end, flags, ops, lens, n_ops, quals, lengths, mesh):
        from adam_tpu.pipelines.markdup import markdup_columns_local

        @partial(
            shard_map, mesh=mesh, in_specs=_mesh_specs(8),
            out_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
            check_vma=False,
        )
        def body(s, e, f, o, ln, n, q, le):
            return markdup_columns_local(s, e, f, o, ln, n, q, le)

        return body(start, end, flags, ops, lens, n_ops, quals, lengths)

    return run


def _mesh_fused_bc_jit_builder(donate: bool):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS

    def run(bases, quals, lengths, flags, rg, res_pk, mm_pk, rd_ok,
            has_qual, valid, table, n_rg, lmax, mesh):
        from adam_tpu.pipelines.bqsr import (
            apply_pack2_body, observe_packed_body,
        )

        @partial(
            shard_map, mesh=mesh, in_specs=_mesh_specs(10) + (P(),),
            out_specs=(P(), P(), P(BATCH_AXIS), P(BATCH_AXIS)),
            check_vma=False,
        )
        def body(b, q, le, fl, r, rp, mp, ok, hq, v, tbl):
            # the megakernel tier's mesh twin: each shard runs the
            # bit-packed-mask observe AND the fused apply+pack over its
            # own row block in ONE collective; histograms psum i64
            # (order-free), the two flat packed outputs stay
            # row-sharded in shard order (== row order)
            total, mism = observe_packed_body(
                b, q, le, fl, r, rp, mp, ok, n_rg, lmax
            )
            pq, pb = apply_pack2_body(
                b, q, le, fl, r, hq, v, tbl, lmax,
                b.shape[0] * b.shape[1],
            )
            return (
                jax.lax.psum(total, BATCH_AXIS),
                jax.lax.psum(mism, BATCH_AXIS),
                pq, pb,
            )

        return body(bases, quals, lengths, flags, rg, res_pk, mm_pk,
                    rd_ok, has_qual, valid, table)

    kw = {"static_argnames": ("n_rg", "lmax", "mesh")}
    if donate:
        # same aliases as the separate passes: resident bases/quals
        # become the packed columns, the bit-packed masks are dead
        # after the in-kernel unpack
        kw["donate_argnums"] = (0, 1, 5, 6)
    return partial(jax.jit, **kw)(run)


_MESH_JITS: dict = {}
_MESH_JITS_LOCK = threading.Lock()


def _mesh_jit(kind: str, donate: bool = False):
    """Lazily-built module-level mesh jits (one executable cache each,
    shared by prewarm and dispatch — the device_pool get_columns_jit
    discipline), keyed by (kind, donate)."""
    key = (kind, donate)
    fn = _MESH_JITS.get(key)
    if fn is None:
        with _MESH_JITS_LOCK:
            fn = _MESH_JITS.get(key)
            if fn is None:
                if kind == "markdup":
                    fn = _mesh_markdup_jit_builder()
                else:
                    fn = {
                        "observe_packed": _mesh_observe_packed_jit_builder,
                        "apply_pack2": _mesh_apply_pack2_jit_builder,
                        "fused_bc": _mesh_fused_bc_jit_builder,
                    }[kind](donate)
                _MESH_JITS[key] = fn
    return fn


class MeshPartitioner:
    """SPMD execution mode for the streamed pipeline (module docstring).

    Holds the 1-D ``batch`` mesh over the run's device set, the row/
    replicated shardings, and the device-resident pass-B observe
    accumulator — one running (total, mism) i64 pair per distinct grid
    width, so barrier 2 fetches table-scale bytes however many windows
    streamed through.  All placement goes through :meth:`put_rows` /
    :meth:`put_replicated`, which feed the h2d transfer ledger with the
    bytes split per member device (sharded) or counted once per device
    (replicated) — "mesh dispatch sites attributed per device" in
    ``adam-tpu analyze``.  Dispatch *spans* carry ``device="mesh"``:
    collective work occupies every device at once, so it gets its own
    track instead of a fabricated per-chip split.
    """

    def __init__(self, devices: Sequence):
        from adam_tpu.parallel.mesh import batch_mesh

        self.devices = list(devices)
        if not self.devices:
            raise ValueError("MeshPartitioner needs at least one device")
        self.mesh = batch_mesh(self.devices)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from adam_tpu.parallel.mesh import batch_row_sharding

        self._rows = batch_row_sharding(self.mesh)
        self._rep = NamedSharding(self.mesh, P())
        # gl -> [total, mism] replicated device i64 arrays (no lock:
        # observe dispatch and the barrier fetch both run on the
        # streamed pipeline's single driver thread)
        self._acc: dict = {}
        self._dev_ids = [
            getattr(d, "id", i) for i, d in enumerate(self.devices)
        ]

    @property
    def n(self) -> int:
        return len(self.devices)

    def ledger_key(self) -> str:
        """The compile-ledger 'device' key for mesh executables: one
        per mesh width — a 2-device and an 8-device mesh compile
        different programs."""
        return f"mesh:{self.n}"

    def rows_for(self, g: int) -> int:
        """Row count the mesh needs: ``g`` padded up to a multiple of
        the device count (pow2 grids over pow2 meshes are unchanged)."""
        return -(-int(g) // self.n) * self.n

    # ---- placement (the h2d side of the transfer ledger) --------------
    def _put(self, x, sharding, bytes_per_device: int):
        import jax

        from adam_tpu.utils import telemetry as tele

        if not tele.TRACE.recording:
            return jax.device_put(x, sharding)
        t0 = time.monotonic()
        out = jax.device_put(x, sharding)
        dur = time.monotonic() - t0
        for dev_id in self._dev_ids:
            tele.TRACE.record_transfer(
                "h2d", bytes_per_device, dur / self.n, device=dev_id,
            )
        return out

    def put_rows(self, x):
        """Place one row-sharded array (leading axis must divide by
        ``n`` — pad with :meth:`rows_for` first)."""
        nbytes = getattr(x, "nbytes", 0)
        return self._put(x, self._rows, nbytes // self.n)

    def put_replicated(self, x):
        """Place one fully-replicated array (each device holds a copy,
        and the ledger charges each its copy)."""
        return self._put(x, self._rep, getattr(x, "nbytes", 0))

    # ---- pass B: observe + on-device accumulate ------------------------
    def accumulate(self, total, mism, gl: int) -> None:
        """Fold one window's lazy histograms into the device-resident
        running table for its grid width (i64 adds: bitwise identical
        to the pool path's host-side window-order merge)."""
        import jax.numpy as jnp

        acc = self._acc.get(int(gl))
        if acc is None:
            self._acc[int(gl)] = [total, mism]
        else:
            acc[0] = jnp.add(acc[0], total)
            acc[1] = jnp.add(acc[1], mism)

    def has_accumulated(self) -> bool:
        return bool(self._acc)

    def fetch_accumulated(self, tracer=None) -> list:
        """Barrier 2: bring the merged tables home — ONE compact
        (total, mism, gl) per distinct grid width, each through the
        chunked transfer helper (d2h ledger + ``device.fetch.observe``
        span, ``device="mesh"`` attributed).  Clears the accumulator."""
        from adam_tpu.utils import telemetry as tele
        from adam_tpu.utils.transfer import device_fetch

        tr = tracer if tracer is not None else tele.TRACE
        out = []
        try:
            for gl in sorted(self._acc):
                total, mism = self._acc[gl]
                with tr.span(tele.SPAN_OBS_FETCH, device="mesh"):
                    out.append(
                        (device_fetch(total), device_fetch(mism), gl)
                    )
        finally:
            self._acc.clear()
        return out

    def reset_accumulator(self) -> None:
        self._acc.clear()

    # ---- pass A: markdup columns ---------------------------------------
    def markdup_window(self, arrays: tuple):
        """Row-sharded [N, L] markdup reductions over the 8 host arrays
        of the kernel signature -> lazy (five, score) row-sharded device
        arrays (the mesh prewarm's seam; windows dispatch through
        :meth:`markdup_window_resident`)."""
        placed = tuple(self.put_rows(a) for a in arrays)
        # adam-tpu: noqa[dispatch-ledger] reason=the mesh prewarm, its only caller, wraps this dispatch in its own track keyed mesh.markdup
        return _mesh_jit("markdup")(*placed, mesh=self.mesh)

    def markdup_window_resident(self, rw, fresh: tuple):
        """Resident-window markdup dispatch: quals/lengths/flags come
        from ``rw``'s batch-sharded placement (one ingest h2d, reused
        by every pass) and only the markdup-specific ``fresh``
        (start, end, cigar ops/lens/n) host arrays ship."""
        start, end, ops, lens, n_ops = (
            self.put_rows(a) for a in fresh
        )
        # adam-tpu: noqa[dispatch-ledger] reason=every caller (markdup_columns_dispatch mesh branch) wraps this dispatch in its own track keyed mesh.markdup
        return _mesh_jit("markdup")(
            start, end, rw.get("flags"), ops, lens, n_ops,
            rw.get("quals"), rw.get("lengths"), mesh=self.mesh,
        )

    # ---- resident windows (ingest-once H2D) ----------------------------
    def observe_packed_window(self, placed: tuple, n_rg: int, gl: int):
        """Dispatch the bit-packed-mask observe collective over
        already-placed arrays (the resident dispatch and the prewarm
        share this seam) -> lazy replicated (total, mism)."""
        # adam-tpu: noqa[dispatch-ledger] reason=every caller (bqsr._observe_impl mesh resident branch and the mesh prewarm) wraps this dispatch in its own track keyed mesh.observe_packed
        return _mesh_jit(
            "observe_packed", donate=self.apply_supports_donation()
        )(*placed, n_rg=n_rg, lmax=gl, mesh=self.mesh)

    def observe_window_resident(self, rw, res_pk, mm_pk, read_ok,
                                n_rg: int, gl: int):
        """Resident-window observe: bases/quals/lengths/flags/rg come
        from ``rw``; only the bit-packed per-pass masks and the read
        filter ship (8x + 1x small — the observe h2d ≈ 0 contract)."""
        placed = rw.args() + (
            self.put_rows(res_pk), self.put_rows(mm_pk),
            self.put_rows(read_ok),
        )
        return self.observe_packed_window(placed, n_rg, gl)

    # ---- pass C: apply with the device-resident table ------------------
    def apply_supports_donation(self) -> bool:
        # buffer donation is a no-op (with a warning) on CPU runtimes:
        # keep the virtual-device test legs quiet and donate on the chip
        from adam_tpu.parallel.mesh import on_tpu

        return all(on_tpu(d) for d in self.devices)

    def apply_pack2_placed(self, placed: tuple, table_dev, gl: int):
        """Dispatch the fused apply + bases+quals pack collective over
        already-placed arrays (resident dispatch and prewarm share this
        seam) -> lazy ``(packed_quals, packed_bases)`` flat u8[g*gl]
        row-sharded pairs."""
        # adam-tpu: noqa[dispatch-ledger] reason=every caller (bqsr apply_pack2 mesh branch and the mesh prewarm) wraps this dispatch in its own track keyed mesh.apply_pack2
        return _mesh_jit(
            "apply_pack2", donate=self.apply_supports_donation()
        )(*placed, table_dev, lmax=gl, mesh=self.mesh)

    def apply_pack2_window(self, rw, has_qual, valid, table_dev,
                           gl: int):
        """Resident-window fused apply + BOTH column packs (the bases
        half of the packed tail): ships only ``has_qual``/``valid``;
        the packed qual AND base payloads come home via
        :meth:`packed_payload_slices` on each output."""
        placed = rw.args() + (
            self.put_rows(has_qual), self.put_rows(valid),
        )
        return self.apply_pack2_placed(placed, table_dev, gl)

    def fused_bc_placed(self, placed: tuple, table_dev, n_rg: int,
                        gl: int):
        """Dispatch the fused B→C megakernel collective over
        already-placed arrays (resident dispatch and prewarm share this
        seam) -> lazy ``(total, mism, packed_quals, packed_bases)`` —
        replicated i64 histograms plus the two row-sharded flat
        payloads."""
        # adam-tpu: noqa[dispatch-ledger] reason=every caller (bqsr.fused_bc_dispatch mesh branch and the mesh prewarm) wraps this dispatch in its own track keyed mesh.fused_bc
        return _mesh_jit(
            "fused_bc", donate=self.apply_supports_donation()
        )(*placed, table_dev, n_rg=n_rg, lmax=gl, mesh=self.mesh)

    def fused_bc_window(self, rw, res_pk, mm_pk, read_ok, has_qual,
                        valid, table_dev, n_rg: int, gl: int):
        """Resident-window fused B→C: bases/quals/lengths/flags/rg come
        from ``rw``; the bit-packed masks, read filter and post-split
        bools are the only per-window h2d, and ONE collective yields
        the window's histograms AND both packed columns."""
        if isinstance(table_dev, np.ndarray):
            table_dev = self.put_replicated(
                np.ascontiguousarray(table_dev, np.uint8)
            )
        placed = rw.args() + (
            self.put_rows(res_pk), self.put_rows(mm_pk),
            self.put_rows(read_ok), self.put_rows(has_qual),
            self.put_rows(valid),
        )
        return self.fused_bc_placed(placed, table_dev, n_rg, gl)

    def packed_payload_slices(self, packed, lens_gm: np.ndarray,
                              gl: int) -> list:
        """Lazy ``(device slice, true bytes)`` pairs covering each
        shard's real packed payload (``lens_gm``: per-row packed byte
        counts padded to the mesh row grid — host-resident, so the
        split needs no device round trip).  Slice lengths are
        bucket-quantized (``colpack.fetch_grid``) so a run compiles a
        handful of slice programs, not one per window; the fetch side
        trims each bucket to its true size.  Empty shards contribute
        no slice; concatenating the trimmed payloads in order
        reproduces the single-device pack."""
        from adam_tpu.ops.colpack import fetch_grid

        rows_local = len(lens_gm) // self.n
        seg = rows_local * gl
        out = []
        for k in range(self.n):
            t_k = int(lens_gm[k * rows_local:(k + 1) * rows_local].sum())
            if t_k:
                cut = min(seg, fetch_grid(t_k))
                out.append((packed[k * seg: k * seg + cut], t_k))
        return out

    # ---- compile prewarm ----------------------------------------------
    def prewarm(self, entries: Sequence[tuple], tracer=None) -> int:
        """Compile the mesh kernel set before the first window's
        dispatch — the mesh analog of ``DevicePool.prewarm``, sharing
        its process-wide dedupe cache keyed by (entry key,
        :meth:`ledger_key`) so warm shapes are never re-compiled.
        ``entries``: ``(key, fn)`` pairs where ``fn(None)`` invokes the
        mesh jit to completion on dummy data."""
        from adam_tpu.parallel import device_pool as dp
        from adam_tpu.utils import compile_ledger
        from adam_tpu.utils import telemetry as tele

        tr = tracer if tracer is not None else tele.TRACE
        todo = []
        with dp._PREWARM_LOCK:
            for key, fn in entries:
                cache_key = (key, self.ledger_key())
                if cache_key not in dp._PREWARMED:
                    dp._PREWARMED.add(cache_key)
                    todo.append((key, fn, cache_key))
                else:
                    # already warm: re-seed the ledger claim a faulted
                    # run's raising dispatch may have handed back (the
                    # pool prewarm's dedupe-skip does the same)
                    compile_ledger.claim(key, self.ledger_key())
        done = 0
        for key, fn, cache_key in todo:
            try:
                with tr.span(
                    tele.SPAN_POOL_PREWARM_COMPILE, device="mesh",
                    kernel=str(key[0]),
                ), compile_ledger.prewarm_scope(), \
                        tele.pass_scope("prewarm"), \
                        compile_ledger.track(key, self.ledger_key()):
                    fn(None)
            except Exception:
                with dp._PREWARM_LOCK:
                    dp._PREWARMED.discard(cache_key)
                log.warning(
                    "mesh prewarm of %s failed; the shape will compile "
                    "at first dispatch instead", key, exc_info=True,
                )
                continue
            tr.count(tele.C_POOL_PREWARM_COMPILES)
            done += 1
        return done


def mesh_resident_window(b, window: int, part: MeshPartitioner):
    """Place one window's resident payload as batch-sharded mesh arrays
    (the mesh analog of ``device_pool.make_resident_window``): one
    ``NamedSharding`` placement, reused by every shard_map pass — the
    pin at ingest, or a pass's transient handle.  Rows pad to the mesh
    width; callers set the ``telemetry.pass_scope`` the h2d ledger books
    it under."""
    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.parallel.device_pool import ResidentWindow, resident_arrays

    gm = part.rows_for(grid_rows(b.n_rows))
    gl = grid_cols(b.lmax)
    arrays, nbytes = resident_arrays(b, gm, gl, part.put_rows)
    return ResidentWindow(
        window, "mesh", dict(zip(ResidentWindow.FIELDS, arrays)), gm, gl,
        nbytes,
    )


def mesh_observe_packed_prewarm_entry(b, n_rg: int,
                                      part: MeshPartitioner) -> tuple:
    """Prewarm entry for the mesh bit-packed-mask observe jit (the
    pass-B dispatch) at one window's grid shape — the pool entry's
    dummy args (``device_pool.observe_dummy_args``), only the row count
    pads to the mesh width."""
    import jax

    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.parallel.device_pool import observe_dummy_args

    g = part.rows_for(grid_rows(b.n_rows))
    gl = grid_cols(b.lmax)

    def warm(_dev, g=g, gl=gl):
        placed = tuple(
            part.put_rows(a) for a in observe_dummy_args(b, g, gl)
        )
        jax.block_until_ready(
            part.observe_packed_window(placed, n_rg, gl)
        )

    return (("mesh.observe_packed", g, gl, n_rg), warm)


def mesh_markdup_prewarm_entry(b, part: MeshPartitioner) -> tuple:
    """Prewarm entry for the mesh markdup-columns jit at one window's
    grid shape (``device_pool.markdup_dummy_args``)."""
    import jax

    from adam_tpu.formats.batch import (
        grid_cigar_cols, grid_cols, grid_rows,
    )
    from adam_tpu.parallel.device_pool import markdup_dummy_args

    g = part.rows_for(grid_rows(b.n_rows))
    gl = grid_cols(b.lmax)
    gc = grid_cigar_cols(
        b.cigar_ops.shape[1] if b.cigar_ops.ndim == 2 else 1
    )

    def warm(_dev, g=g, gl=gl, gc=gc):
        jax.block_until_ready(
            part.markdup_window(markdup_dummy_args(b, g, gl, gc))
        )

    return (("mesh.markdup", g, gc, gl), warm)


def mesh_apply_prewarm_entry(b, n_rg: int, n_cyc: int,
                             part: MeshPartitioner) -> tuple:
    """Prewarm entry for the mesh pack2 apply jit keyed by the SOLVED
    table's real cycle width (the pass-C re-warm,
    ``device_pool.apply_prewarm_entry`` semantics;
    ``device_pool.apply_dummy_args``)."""
    import jax

    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.parallel.device_pool import apply_dummy_args
    from adam_tpu.pipelines.bqsr import N_DINUC, N_QUAL

    g = part.rows_for(grid_rows(b.n_rows))
    gl = grid_cols(b.lmax)

    def warm(_dev, g=g, gl=gl):
        tbl = part.put_replicated(
            np.zeros((n_rg, N_QUAL, n_cyc, N_DINUC), np.uint8)
        )
        placed = tuple(
            part.put_rows(a) for a in apply_dummy_args(b, g, gl)
        )
        jax.block_until_ready(part.apply_pack2_placed(placed, tbl, gl))

    return (("mesh.apply_pack2", g, gl, n_rg, n_cyc), warm)


def mesh_fused_bc_prewarm_entry(b, n_rg: int, n_cyc: int,
                                part: MeshPartitioner) -> tuple:
    """Prewarm entry for the mesh fused B→C megakernel keyed by the
    known table's real cycle width (``device_pool.fused_bc_dummy_args``
    — the single dummy-construction idiom per kernel signature)."""
    import jax

    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.parallel.device_pool import fused_bc_dummy_args
    from adam_tpu.pipelines.bqsr import N_DINUC, N_QUAL

    g = part.rows_for(grid_rows(b.n_rows))
    gl = grid_cols(b.lmax)

    def warm(_dev, g=g, gl=gl):
        tbl = part.put_replicated(
            np.zeros((n_rg, N_QUAL, n_cyc, N_DINUC), np.uint8)
        )
        placed = tuple(
            part.put_rows(a) for a in fused_bc_dummy_args(b, g, gl)
        )
        jax.block_until_ready(
            part.fused_bc_placed(placed, tbl, n_rg, gl)
        )

    return (("mesh.fused_bc", g, gl, n_rg, n_cyc), warm)
