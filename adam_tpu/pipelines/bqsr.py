"""Base Quality Score Recalibration (BQSR).

Two-pass algorithm with the exact semantics of the reference's
``rdd/read/recalibration/`` package:

* **Observe** (BaseQualityRecalibration.scala:55-85): canonical reads
  (primary, mapped, not duplicate, qual present, 0 < mapq < 255, passed
  vendor QC) contribute one observation per residue that has quality > 0,
  a regular ACGT base, a reference position (not an insertion/soft-clip)
  and is not masked by the known-SNPs table.  The covariate key is
  (read group, reported quality, cycle, dinucleotide)
  (CycleCovariate.scala:23-49, DinucCovariate.scala:24-66).
* **Recalibrate** (Recalibrator.scala:28-165): every read with qualities
  gets per-residue recalibrated quality from the log-space delta stack
  global -> per-quality -> per-cycle/per-dinuc, bounded to Q50
  (RecalibrationTable), applied only to residues with reported quality >=
  Q5 (minAcceptableQuality, BaseQualityRecalibration.scala:50).

TPU formulation: the covariate key space is **dense** — (rg, 94 quals,
2L+1 cycles, 17 dinucs) — so the reference's HashMap-aggregate becomes a
scatter-add histogram on device, combined across chips with a `psum`, and
the recalibration table lookups become marginal reductions + gathers:
no strings, no hashing, one fused kernel per pass.
"""

from __future__ import annotations

import os
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from adam_tpu.api.datasets import AlignmentDataset
from adam_tpu.formats import schema
from adam_tpu.models.snp_table import SnpTable
from adam_tpu.ops import cigar as cigar_ops
from adam_tpu.ops.mdtag import batch_md_arrays
from adam_tpu.ops.phred import PHRED_TO_ERROR
from adam_tpu.parallel.mesh import on_tpu
from adam_tpu.utils import telemetry as _tele
from adam_tpu.utils.transfer import device_fetch

N_QUAL = 94  # valid phred range 0..93 (QualityScore.scala)
N_DINUC = 17  # 16 (prev,cur) pairs + index 16 = None ("NN")
DINUC_NONE = 16
MIN_ACCEPTABLE_QUALITY = 5
MAX_QUAL = 50


# --------------------------------------------------------------------------
# Per-residue kernel backend selection
# --------------------------------------------------------------------------
BACKENDS = ("device", "native", "numpy")


def bqsr_backend(override: Optional[str] = None) -> str:
    """Resolve the per-residue pass backend: ``device`` (jit scatter/
    gather kernels on the attached chip), ``native`` (threaded C++ host
    walks), or ``numpy`` (pure-host vectorized twins).

    Order: explicit ``override`` arg, then ``ADAM_TPU_BQSR_BACKEND``,
    then the topology default — **device on a TPU**, native on
    CPU-only hosts with the toolchain, numpy otherwise.
    """
    b = (override or os.environ.get("ADAM_TPU_BQSR_BACKEND", "")).strip().lower()
    if b:
        if b not in BACKENDS:
            src = (
                "backend argument" if override
                else "ADAM_TPU_BQSR_BACKEND"
            )
            raise ValueError(
                f"{src}={b!r}: expected one of {BACKENDS}"
            )
        return b
    if on_tpu():
        return "device"
    from adam_tpu import native

    return "native" if native.available() else "numpy"


# --------------------------------------------------------------------------
# Covariates (device)
# --------------------------------------------------------------------------
def compute_cycles(lengths, flags, lmax: int):
    """Sequencer cycle per residue -> i32[N, L].

    (initial, increment): forward/first (1, +1); forward/second (-1, -1);
    reverse/first (L, -1); reverse/second (-L, +1) — CycleCovariate.scala:31-49;
    'second' means paired && secondOfPair, everything else is 'first'.
    """
    rev = (flags & schema.FLAG_REVERSE) != 0
    second = ((flags & schema.FLAG_PAIRED) != 0) & (
        (flags & schema.FLAG_SECOND_OF_PAIR) != 0
    )
    L = lengths.astype(jnp.int32)
    initial = jnp.where(
        rev,
        jnp.where(second, -L, L),
        jnp.where(second, -1, 1),
    )
    increment = jnp.where(rev, jnp.where(second, 1, -1), jnp.where(second, -1, 1))
    pos = jnp.arange(lmax, dtype=jnp.int32)[None, :]
    return initial[:, None] + increment[:, None] * pos


def compute_cycles_np(lengths, flags, lmax: int):
    """Host twin of :func:`compute_cycles` (vectorized numpy)."""
    rev = (np.asarray(flags) & schema.FLAG_REVERSE) != 0
    second = ((np.asarray(flags) & schema.FLAG_PAIRED) != 0) & (
        (np.asarray(flags) & schema.FLAG_SECOND_OF_PAIR) != 0
    )
    L = np.asarray(lengths).astype(np.int32)
    initial = np.where(rev, np.where(second, -L, L), np.where(second, -1, 1))
    increment = np.where(rev, np.where(second, 1, -1), np.where(second, -1, 1))
    pos = np.arange(lmax, dtype=np.int32)[None, :]
    return initial[:, None] + increment[:, None] * pos


def compute_dinucs_np(bases, lengths, flags, lmax: int):
    """Host twin of :func:`compute_dinucs` (vectorized numpy)."""
    comp = np.asarray(schema.BASE_COMPLEMENT)
    bases = np.asarray(bases)
    rev = ((np.asarray(flags) & schema.FLAG_REVERSE) != 0)[:, None]
    prev_f = np.pad(bases[:, :-1], ((0, 0), (1, 0)),
                    constant_values=schema.BASE_N)
    next_b = np.pad(bases[:, 1:], ((0, 0), (0, 1)),
                    constant_values=schema.BASE_N)
    cur = np.where(rev, comp[bases], bases)
    prev = np.where(rev, comp[next_b], prev_f)
    i = np.arange(lmax)[None, :]
    lens = np.asarray(lengths)
    in_read = i < lens[:, None]
    first_machine = np.where(rev, i == (lens[:, None] - 1), i == 0)
    regular = (cur < 4) & (prev < 4)
    ok = in_read & ~first_machine & regular
    idx = prev.astype(np.int32) * 4 + cur.astype(np.int32)
    return np.where(ok, idx, DINUC_NONE)


def compute_dinucs(bases, lengths, flags, lmax: int):
    """Dinucleotide index per residue -> i32[N, L] in [0, 16].

    Forward: (seq[i-1], seq[i]); reverse: (comp(seq[i+1]), comp(seq[i])) —
    i.e. the machine-order previous base (DinucCovariate.scala:24-50).
    None (index 16) at the machine-order first base or when either base
    is not a regular ACGT.
    """
    comp = jnp.asarray(schema.BASE_COMPLEMENT)
    rev = ((flags & schema.FLAG_REVERSE) != 0)[:, None]
    cur_f = bases
    prev_f = jnp.pad(bases[:, :-1], ((0, 0), (1, 0)), constant_values=schema.BASE_N)
    next_b = jnp.pad(bases[:, 1:], ((0, 0), (0, 1)), constant_values=schema.BASE_N)
    cur = jnp.where(rev, comp[cur_f], cur_f)
    prev = jnp.where(rev, comp[next_b], prev_f)
    i = jnp.arange(lmax)[None, :]
    in_read = i < lengths[:, None]
    first_machine = jnp.where(rev, i == (lengths[:, None] - 1), i == 0)
    regular = (cur < 4) & (prev < 4)
    ok = in_read & ~first_machine & regular
    idx = prev.astype(jnp.int32) * 4 + cur.astype(jnp.int32)
    return jnp.where(ok, idx, DINUC_NONE)


# --------------------------------------------------------------------------
# Observation pass
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_rg", "lmax"))
def observe_kernel(
    bases, quals, lengths, flags, read_group_idx,
    residue_ok, is_mismatch, read_ok,
    n_rg: int, lmax: int,
):
    """Scatter-add residue observations into the dense covariate histogram.

    Returns (total, mismatches) as i64[n_rg, N_QUAL, 2*lmax+1, N_DINUC].
    """
    n_cyc = 2 * lmax + 1
    cycles = compute_cycles(lengths, flags, lmax)
    dinucs = compute_dinucs(bases, lengths, flags, lmax)
    q = jnp.clip(quals.astype(jnp.int32), 0, N_QUAL - 1)
    # reads without a read group get their own bin (index n_rg - 1 of the
    # n_rg = len(groups)+1 bins), like the reference's null readGroup key
    rg = jnp.where(read_group_idx >= 0, read_group_idx, n_rg - 1).astype(jnp.int32)
    include = residue_ok & read_ok[:, None]

    # i32 keys and counts: int64 is emulated on the TPU vector unit and
    # the scatter-add dominates the pass; a single batch shard can't
    # overflow 2^31 observations (callers psum in i64 across shards)
    flat_key = (
        ((rg[:, None] * N_QUAL + q) * n_cyc + (cycles + lmax)) * N_DINUC + dinucs
    ).astype(jnp.int32)
    size = n_rg * N_QUAL * n_cyc * N_DINUC
    flat_key = jnp.where(include, flat_key, 0).ravel()
    ones = include.astype(jnp.int32).ravel()
    mm = (include & is_mismatch).astype(jnp.int32).ravel()
    total = jnp.zeros(size, jnp.int32).at[flat_key].add(ones)
    mism = jnp.zeros(size, jnp.int32).at[flat_key].add(mm)
    shape = (n_rg, N_QUAL, n_cyc, N_DINUC)
    return (
        total.reshape(shape).astype(jnp.int64),
        mism.reshape(shape).astype(jnp.int64),
    )


def observe_packed_body(
    bases, quals, lengths, flags, read_group_idx,
    res_bits, mm_bits, read_ok,
    n_rg: int, lmax: int,
):
    """Traceable observe pass over **bit-packed** per-pass masks
    (``res_bits``/``mm_bits``: u8[N, L/8] from
    ``colpack.pack_mask_bits``) — the resident-window dispatch variant:
    bases/quals/lengths/flags/rg come from the window's ingest-resident
    device arrays, and the only per-residue h2d payload is the two
    packed masks (8x smaller than the booleans the plain kernel
    ships).  Unpacks on device, then runs the exact scatter-add of
    :func:`observe_kernel` — bitwise the same histograms."""
    from adam_tpu.ops.colpack import unpack_mask_body

    residue_ok = unpack_mask_body(res_bits, lmax)
    is_mismatch = unpack_mask_body(mm_bits, lmax)
    return observe_kernel.__wrapped__(
        bases, quals, lengths, flags, read_group_idx,
        residue_ok, is_mismatch, read_ok, n_rg, lmax,
    )


#: Lazily-built jit variants keyed by (kind, donate): the donating
#: twins are DISTINCT executables from the plain ones (donation is part
#: of the jit wrapper), so the prewarm must warm exactly the variant a
#: dispatch will call — both sides resolve through this one registry
#: with the same (kind, donate) decision, which is what keeps the
#: compile ledger's donated-signature executables deduped against the
#: prewarm (device.compile.in_window stays 0).
_JIT_VARIANTS: dict = {}
_JIT_VARIANTS_LOCK = threading.Lock()


def jit_variant(kind: str, donate: bool = False):
    """The jit for one streamed kernel ``kind`` (``observe_packed`` /
    ``apply_pack2`` / ``fused_bc``) with or without buffer donation.
    Donation aliases the dead-after-apply inputs into the outputs (the
    resident quals buffer becomes the packed qual column, the resident
    bases buffer the packed base column; the observe variant donates
    its per-pass mask temporaries), halving pass-C's per-window HBM
    footprint — only offered where the runtime honors it
    (``device_pool.donation_ok``; CPU runtimes warn and copy).

    Keyed by ``(kind, donate)``."""
    key = (kind, bool(donate))
    fn = _JIT_VARIANTS.get(key)
    if fn is not None:
        return fn
    with _JIT_VARIANTS_LOCK:
        fn = _JIT_VARIANTS.get(key)
        if fn is not None:
            return fn
        body, statics, donums = {
            "observe_packed": (
                observe_packed_body, ("n_rg", "lmax"), (5, 6)
            ),
            "apply_pack2": (apply_pack2_body, ("lmax", "size"), (0, 1)),
            "fused_bc": (
                fused_bc_body, ("n_rg", "lmax", "size"), (0, 1, 5, 6)
            ),
        }[kind]
        kw = {"static_argnames": statics}
        if donate:
            kw["donate_argnums"] = donums
        fn = partial(jax.jit, **kw)(body)
        _JIT_VARIANTS[key] = fn
    return fn


def observe_kernel_np(
    bases, quals, lengths, flags, read_group_idx,
    residue_ok, is_mismatch, read_ok,
    n_rg: int, lmax: int,
):
    """Host twin of :func:`observe_kernel` (bincount over the same fused
    i32 covariate keys) — the ``numpy`` backend and the differential
    oracle for the device scatter-add."""
    n_cyc = 2 * lmax + 1
    cycles = compute_cycles_np(lengths, flags, lmax)
    dinucs = compute_dinucs_np(bases, lengths, flags, lmax)
    q = np.clip(np.asarray(quals).astype(np.int32), 0, N_QUAL - 1)
    rg = np.where(
        np.asarray(read_group_idx) >= 0, np.asarray(read_group_idx), n_rg - 1
    ).astype(np.int32)
    include = np.asarray(residue_ok) & np.asarray(read_ok)[:, None]
    flat_key = (
        ((rg[:, None] * N_QUAL + q) * n_cyc + (cycles + lmax)) * N_DINUC
        + dinucs
    ).astype(np.int64)
    size = n_rg * N_QUAL * n_cyc * N_DINUC
    shape = (n_rg, N_QUAL, n_cyc, N_DINUC)
    total = np.bincount(flat_key[include], minlength=size).astype(np.int64)
    mism = np.bincount(
        flat_key[include & np.asarray(is_mismatch)], minlength=size
    ).astype(np.int64)
    return total.reshape(shape), mism.reshape(shape)


class ObservationTable:
    """Dense covariate histogram + CSV emission compatible with the
    reference's ObservationTable.toCSV (GATK-style)."""

    def __init__(self, total: np.ndarray, mismatches: np.ndarray,
                 rg_names: list[str], lmax: int):
        self.total = np.asarray(total)
        self.mismatches = np.asarray(mismatches)
        self.rg_names = rg_names
        self.lmax = lmax

    @staticmethod
    def _dinuc_str(idx: int) -> str:
        if idx == DINUC_NONE:
            return "NN"
        return "ACGT"[idx // 4] + "ACGT"[idx % 4]

    @staticmethod
    def empirical_quality(total, mismatches):
        """Bayes with Beta(1,1): (1+mm)/(2+total) -> phred with Scala
        math.round = floor(x+0.5) (ObservationTable.scala:55-59,
        PhredUtils rounding).  Vectorized numpy."""
        p = (1.0 + np.asarray(mismatches)) / (2.0 + np.asarray(total))
        return np.floor(-10.0 * np.log10(p) + 0.5).astype(np.int64)

    def to_csv(self) -> str:
        lines = ["ReadGroup,ReportedQ,Cycle,Dinuc,TotalCount,MismatchCount,EmpiricalQ,IsSkipped"]
        rg_idx, q_idx, c_idx, d_idx = np.nonzero(self.total)
        totals = self.total[rg_idx, q_idx, c_idx, d_idx]
        mms = self.mismatches[rg_idx, q_idx, c_idx, d_idx]
        emp = self.empirical_quality(totals, mms)
        for rg, q, c, d, t, m, e in zip(rg_idx, q_idx, c_idx, d_idx, totals, mms, emp):
            fields = [
                self.rg_names[rg],
                str(int(q)),
                str(int(c) - self.lmax),
                self._dinuc_str(int(d)),
                str(int(t)),
                str(int(m)),
                str(int(e)),
            ]
            if d == DINUC_NONE:
                fields.append("**")
            lines.append(",".join(fields))
        return "\n".join(lines)


def _observe_device(
    ds: AlignmentDataset, known_snps: Optional[SnpTable] = None,
    backend: Optional[str] = None, device=None, mesh=None, resident=None,
):
    """Run the observation pass -> (total, mism, rg_names, lmax).

    Backend dispatch (:func:`bqsr_backend`):

    * ``device`` — the jit scatter-add histogram (:func:`observe_kernel`)
      on the attached chip.  The histograms come back **lazy** (device
      arrays): per-window dispatches queue asynchronously and callers
      fetch the compact [n_rg, 94, 2L+1, 17] tables at the merge barrier
      (the sharded psum variant lives in parallel/dist.distributed_observe).
    * ``native`` — the threaded C++ cigar/MD walk; histograms are host
      numpy arrays and downstream table math stays host-side.  Falls
      back to the device kernel when the toolchain is unavailable.
    * ``numpy`` — :func:`observe_kernel_np`, the pure-host oracle.

    ``device``: explicit jax device for the ``device`` backend's
    scatter-add (the multi-chip pool's round-robin target); ``None``
    keeps the default device.  ``resident``: the window's device
    payload (``device_pool.ResidentWindow``, pinned at ingest or placed
    for the pass) — the streamed leg: bases/quals/lengths/flags/rg
    dispatch off the handle and only the bit-packed per-pass masks ship
    (``colpack.pack_mask_bits``) into :func:`observe_packed_body`.
    ``mesh``: a :class:`~adam_tpu.parallel.partitioner.MeshPartitioner`
    (with a mesh-sharded ``resident``) — the scatter-add runs per shard
    and the histograms ``psum`` on-device; the returned (total, mism)
    are lazy *replicated* device arrays the streamed pipeline folds
    into its device-resident accumulator instead of fetching per
    window.  Without a handle the ``device`` backend runs the in-memory
    API's single-device leg (:func:`observe_kernel`).  Downstream
    consumers dispatch on ``isinstance(total, np.ndarray)`` so each
    path stays on its side of the device link."""
    backend = bqsr_backend(backend)
    from adam_tpu.parallel.device_pool import span_attrs

    # span carries the resolved backend so device-vs-host attribution is
    # visible per window in the flight recorder; mesh dispatches land on
    # the collective "mesh" track (they occupy every device at once)
    attrs = {"device": "mesh"} if mesh is not None else span_attrs(device)
    with _tele.TRACE.span(
        _tele.SPAN_BQSR_OBSERVE, backend=backend,
        reads=int(ds.batch.n_rows), **attrs,
    ):
        return _observe_impl(ds, known_snps, backend, device, mesh,
                             resident)


def observe_read_mask(b, has_md: np.ndarray) -> np.ndarray:
    """The canonical-read filter of the observe pass (primary, mapped,
    not duplicate, qual present, 0 < mapq < 255, passed vendor QC, MD
    present) -> bool[N].  ONE copy of the expression, shared by the
    solo dispatch below and the cross-job coalescer's fused grid
    (serve/batching.py) — bitwise the same filter on either path."""
    flags = np.asarray(b.flags)
    return (
        np.asarray(b.valid)
        & ((flags & schema.FLAG_UNMAPPED) == 0)
        & ((flags & (schema.FLAG_SECONDARY | schema.FLAG_SUPPLEMENTARY)) == 0)
        & ((flags & schema.FLAG_DUPLICATE) == 0)
        & ((flags & schema.FLAG_FAILED_QC) == 0)
        & np.asarray(b.has_qual)
        & (np.asarray(b.mapq) > 0)
        & (np.asarray(b.mapq) != 255)
        & has_md
    )


def observe_residue_mask(
    ds: AlignmentDataset, b, known_snps: Optional[SnpTable]
) -> np.ndarray:
    """The per-residue observe filter (q > 0, regular ACGT base,
    aligned to reference, not a known SNP) -> bool[N, L] — shared by
    the device/numpy solo paths and the coalescer's fused payload."""
    ref_pos = cigar_ops.reference_positions_np(
        b.cigar_ops, b.cigar_lens, b.cigar_n, b.start, b.lmax
    )
    quals = np.asarray(b.quals)
    rok = (
        (quals > 0) & (quals < schema.QUAL_PAD)
        & (np.asarray(b.bases) < 4) & (ref_pos >= 0)
    )
    if known_snps is not None and len(known_snps):
        rok &= ~known_snp_mask(
            known_snps, ds.seq_dict.names, b.contig_idx, ref_pos, b.start
        )
    return rok


def known_snp_mask(
    known_snps: SnpTable, contig_names, contig_idx, ref_pos, start
) -> np.ndarray:
    """One observe call's known-SNP mask -> bool[N, L], timed by the span
    ``bqsr.observe.snp_mask`` and counted by ``bqsr.snp_mask.rows``,
    ``.hit_rows`` (rows whose reference span holds a site) and
    ``.fallback_rows`` (rows masked by the ``searchsorted`` route)."""
    with _tele.TRACE.span(_tele.SPAN_BQSR_SNP_MASK, reads=len(ref_pos)):
        got = known_snps.mask_routed(
            contig_names, np.asarray(contig_idx), ref_pos, start
        )
    if _tele.TRACE.recording:
        _tele.TRACE.count(_tele.C_SNP_MASK_ROWS, len(ref_pos))
        _tele.TRACE.count(_tele.C_SNP_MASK_HIT_ROWS, got.hit_rows)
        _tele.TRACE.count(_tele.C_SNP_MASK_FALLBACK_ROWS, got.fallback_rows)
    return got.mask


def observe_inputs(ds: AlignmentDataset, known_snps=None) -> tuple:
    """Host-side observe-pass inputs for one window ->
    ``(b, read_ok, residue_ok, is_mm, n_rg)`` — exactly the arrays the
    device scatter-add consumes.  The cross-job coalescer
    (serve/batching.py) builds its fused ``[N_total, L]`` grid from
    these, so a coalesced window's per-job histogram slice is bitwise
    the solo kernel's output."""
    b = ds.batch.to_numpy()
    is_mm, _, has_md = batch_md_arrays(
        ds.batch, ds.sidecar, need_ref_codes=False
    )
    read_ok = observe_read_mask(b, has_md)
    residue_ok = observe_residue_mask(ds, b, known_snps)
    return b, read_ok, residue_ok, is_mm, len(ds.read_groups) + 1


def count_observe_residues(b, read_ok) -> None:
    """``bqsr.observe.residues``: the summed length of the reads the
    observe filter admits — O(N), no pass over [N, L]."""
    if _tele.TRACE.recording:
        _tele.TRACE.count(
            _tele.C_OBSERVE_RESIDUES,
            int(np.asarray(b.lengths)[np.asarray(read_ok)].sum()),
        )


def count_apply_residues(b) -> None:
    """``bqsr.apply.residues``: the summed length of the reads whose
    qualities pass C rewrites (valid, with qualities)."""
    if _tele.TRACE.recording:
        keep = np.asarray(b.valid) & np.asarray(b.has_qual)
        _tele.TRACE.count(
            _tele.C_APPLY_RESIDUES, int(np.asarray(b.lengths)[keep].sum())
        )


def _observe_impl(
    ds: AlignmentDataset, known_snps: Optional[SnpTable], backend: str,
    device=None, mesh=None, resident=None,
):
    # the host half (masks, padding, bit-packing) is one span; the
    # dispatch it hands back runs after it
    with _tele.TRACE.span(
        _tele.SPAN_BQSR_OBSERVE_PREP, reads=int(ds.batch.n_rows)
    ):
        b, read_ok, run = _observe_prepare(
            ds, known_snps, backend, device, mesh, resident
        )
    total, mism = run()
    from adam_tpu.formats.batch import grid_cols

    gl = grid_cols(b.lmax)
    rg_names = ds.read_groups.names + ["null"]
    # visit accounting (BaseQualityRecalibration.scala:99-123's logging)
    # — host-resident histograms only: summing a device-backend result
    # here would block on the scatter-add and fetch per window,
    # silently defeating the lazy dispatch the device path exists for
    import logging

    log = logging.getLogger(__name__)
    if isinstance(total, np.ndarray) and log.isEnabledFor(logging.INFO):
        n_visited = int(np.asarray(total).sum())
        log.info(
            "BQSR observe: %d reads eligible of %d; %d residues visited, "
            "%d residues filtered",
            int(read_ok.sum()), int(np.asarray(b.valid).sum()),
            n_visited,
            int(read_ok.sum() * b.lmax) - n_visited,
        )
    return total, mism, rg_names, gl


def _observe_prepare(
    ds: AlignmentDataset, known_snps: Optional[SnpTable], backend: str,
    device, mesh, resident,
):
    """Build one observe call's inputs -> ``(b, read_ok, run)``;
    ``run()`` dispatches the backend's kernel -> ``(total, mism)``."""
    b = ds.batch.to_numpy()
    from adam_tpu import native
    from adam_tpu.formats.batch import grid_cols, grid_rows
    from adam_tpu.formats.strings import StringColumn

    n = b.n_rows
    md_col = StringColumn.of(ds.sidecar.md)
    use_native = (
        backend == "native" and native.available() and len(md_col) >= n
    )
    if use_native:
        # the native walk parses each read's MD inline — no host-side
        # [N, L] mismatch mask, no vectorized MD tokenize pass
        is_mm = None
        has_md = md_col.valid[:n] & np.asarray(b.valid)
    else:
        is_mm, _, has_md = batch_md_arrays(
            ds.batch, ds.sidecar, need_ref_codes=False
        )

    read_ok = observe_read_mask(b, has_md)
    count_observe_residues(b, read_ok)

    # one extra bin for RG-less reads (the reference's null readGroup)
    n_rg = len(ds.read_groups) + 1
    g = grid_rows(b.n_rows)
    gl = grid_cols(b.lmax)

    def kernel_dispatch(is_mm):
        # device/numpy backends: residue filter built host-side (the
        # module-level helper, shared with the cross-job coalescer)
        residue_ok = observe_residue_mask(ds, b, known_snps)
        return _observe_kernel_dispatch(
            b, residue_ok, is_mm, read_ok, n_rg, g, gl, backend, device,
            mesh, resident,
        )

    if not use_native:
        return b, read_ok, kernel_dispatch(is_mm)
    snp_keys = None
    if known_snps is not None and len(known_snps):
        # known-SNP masking runs inside the native kernel's cigar walk
        # (sorted site-key binary search per residue) — the [N, L] i64
        # position matrix (~3 GB at WGS batch sizes) never materializes
        snp_keys = known_snps.site_keys(ds.seq_dict.names)

    def dispatch_native():
        nat = native.bqsr_observe(
            b.bases, b.quals, b.lengths, b.flags, b.read_group_idx,
            b.cigar_ops, b.cigar_lens, b.cigar_n,
            None, is_mm, read_ok, n_rg, gl,
            contig_idx=b.contig_idx, start=b.start, snp_keys=snp_keys,
            md_buf=md_col.buf, md_off=md_col.offsets[: n + 1],
        )
        if nat is not None:
            return nat  # host arrays: downstream table math stays host
        mm, _, _hm = batch_md_arrays(
            ds.batch, ds.sidecar, need_ref_codes=False
        )
        return kernel_dispatch(mm)()

    return b, read_ok, dispatch_native


def _observe_kernel_dispatch(
    b, residue_ok, is_mm, read_ok, n_rg: int, g: int, gl: int,
    backend: str, device, mesh, resident,
):
    """Pad and bit-pack the observe masks for ``backend`` -> a zero-arg
    ``run`` that dispatches -> ``(total, mism)``.  Each device ``run``
    keeps its own ``compile_ledger.track`` with the key literal, which
    the dispatch-ledger rule cross-checks against the prewarm registry."""
    lmax = b.lmax
    if backend == "numpy":
        def run_numpy():
            total, mism = observe_kernel_np(
                b.bases, b.quals, b.lengths, b.flags, b.read_group_idx,
                residue_ok, is_mm, read_ok, n_rg, lmax,
            )
            # center the [-lmax, lmax] cycle slots inside the grid-width
            # table so every backend returns the same [.., 2*gl+1, ..]
            # shape (merge_observations pads by gl, not lmax)
            if gl != lmax:
                shape = (n_rg, N_QUAL, 2 * gl + 1, N_DINUC)
                t2 = np.zeros(shape, np.int64)
                m2 = np.zeros(shape, np.int64)
                off = gl - lmax
                t2[:, :, off : off + 2 * lmax + 1, :] = total
                m2[:, :, off : off + 2 * lmax + 1, :] = mism
                total, mism = t2, m2
            return total, mism

        return run_numpy
    from adam_tpu.formats.batch import pad_rows_np
    from adam_tpu.parallel.device_pool import (
        donation_ok, putter, resident_arrays,
    )
    from adam_tpu.utils import compile_ledger, faults
    from adam_tpu.utils import retry as _retry

    site = "bqsr.observe.dispatch"
    rw = resident
    if rw is None:
        if mesh is not None:
            raise ValueError("the mesh observe dispatches on a resident "
                             "window handle")
        # the in-memory API's single-device leg: the plain kernel over
        # this call's own placement of the row arrays and bool masks
        _put = putter(device)
        masks = (
            pad_rows_np(residue_ok, g, False, cols=gl),
            pad_rows_np(is_mm, g, False, cols=gl),
            pad_rows_np(read_ok, g, False),
        )

        def dispatch():
            # ship + scatter-add as one retryable unit: re-running
            # the commit and the jit dispatch is idempotent
            faults.point("device.dispatch", device=device)
            rows, _nbytes = resident_arrays(b, g, gl, _put)
            return observe_kernel(
                *rows, *(_put(m) for m in masks), n_rg, gl
            )

        def run():
            # adam-tpu: noqa[dispatch-ledger] reason=the in-memory API's single-device leg: no streamed pass dispatches it, so no prewarm entry builds its key
            with compile_ledger.track(("bqsr.observe", g, gl, n_rg), device):
                return _retry.retry_call(dispatch, site=site)

        return run

    # the streamed leg: the five row arrays come from the window's
    # resident handle and only the bit-packed masks + read filter ship
    # (placed per attempt, so a consumed mask never re-enters a retry)
    from adam_tpu.ops.colpack import pack_mask_bits

    res_pk = pack_mask_bits(pad_rows_np(residue_ok, rw.g, False, cols=gl))
    mm_pk = pack_mask_bits(pad_rows_np(is_mm, rw.g, False, cols=gl))
    rd_pad = pad_rows_np(read_ok, rw.g, False)
    if mesh is not None:
        def dispatch_mesh_resident():
            faults.point("device.dispatch")
            return mesh.observe_window_resident(
                rw, res_pk, mm_pk, rd_pad, n_rg, gl
            )

        def run_mesh_resident():
            with compile_ledger.track(
                ("mesh.observe_packed", rw.g, gl, n_rg), mesh.ledger_key()
            ):
                return _retry.retry_call(dispatch_mesh_resident, site=site)

        return run_mesh_resident

    _put = putter(device)

    def dispatch_resident():
        faults.point("device.dispatch", device=device)
        return jit_variant("observe_packed", donation_ok(device))(
            *rw.args(), _put(res_pk), _put(mm_pk), _put(rd_pad), n_rg, gl,
        )

    def run_resident():
        # ledger key == observe_packed_prewarm_entry's key
        with compile_ledger.track(
            ("bqsr.observe_packed", g, gl, n_rg), device
        ):
            return _retry.retry_call(dispatch_resident, site=site)

    return run_resident


def build_observation_table(
    ds: AlignmentDataset, known_snps: Optional[SnpTable] = None
) -> ObservationTable:
    total, mism, rg_names, lmax = _observe_device(ds, known_snps)
    return ObservationTable(
        device_fetch(total), device_fetch(mism), rg_names, lmax
    )


# --------------------------------------------------------------------------
# Recalibration pass
# --------------------------------------------------------------------------
@jax.jit
def recalibration_phred_table(total, mismatches):
    """Materialize the recalibrated quality for every covariate combination
    -> i32[RG, Q, C, D].

    The log-space delta stack (Recalibrator.scala:79-127) is a pure
    function of the covariate key, so it is evaluated once per *table
    cell* rather than per residue — the device analog of the reference
    building a RecalibrationTable on the driver and applying it as a
    lookup.  With E = empirical error (Bayes (1+mm)/(2+total)) and offsets
    accumulating residue logP + previous deltas, missing entries
    (total==0) contribute delta 0; the per-cycle and per-dinuc deltas
    share the same offset.  All transcendentals live on table shapes
    (~1e6 cells), which keeps the x64 XLA fusion tiny — compiling the old
    per-residue [N, L] f64 log stack took minutes on CPU.
    """
    err = jnp.asarray(PHRED_TO_ERROR)

    def emp_log(t, m):  # ln of bayesian error probability
        return jnp.log((1.0 + m) / (2.0 + t))

    # marginals
    g_t = total.sum(axis=(1, 2, 3))  # [RG]
    g_m = mismatches.sum(axis=(1, 2, 3))
    q_levels = jnp.arange(N_QUAL)
    q_t = total.sum(axis=(2, 3))  # [RG, Q]
    q_m = mismatches.sum(axis=(2, 3))
    g_exp = (err[q_levels][None, :] * q_t).sum(axis=1)  # [RG] expected mismatches
    c_t = total.sum(axis=3)  # [RG, Q, C]
    c_m = mismatches.sum(axis=3)
    d_t = total.sum(axis=2)  # [RG, Q, D]
    d_m = mismatches.sum(axis=2)

    residue_logp = jnp.log(err[q_levels])  # [Q]

    g_present = g_t > 0  # [RG]
    global_delta = jnp.where(
        g_present,
        emp_log(g_t, g_m) - jnp.log(g_exp / jnp.maximum(g_t, 1)),
        0.0,
    )

    q_present = g_present[:, None] & (q_t > 0)  # [RG, Q]
    offset1 = residue_logp[None, :] + global_delta[:, None]  # [RG, Q]
    quality_delta = jnp.where(q_present, emp_log(q_t, q_m) - offset1, 0.0)

    offset2 = offset1 + quality_delta  # [RG, Q]
    cyc_delta = jnp.where(
        q_present[:, :, None] & (c_t > 0),
        emp_log(c_t, c_m) - offset2[:, :, None],
        0.0,
    )
    din_delta = jnp.where(
        q_present[:, :, None] & (d_t > 0),
        emp_log(d_t, d_m) - offset2[:, :, None],
        0.0,
    )

    log_p = (
        offset2[:, :, None, None]
        + cyc_delta[:, :, :, None]
        + din_delta[:, :, None, :]
    )
    max_logp = jnp.log(err[MAX_QUAL])
    bounded = jnp.minimum(0.0, jnp.maximum(max_logp, log_p))
    # QualityScore.fromErrorProbability(exp(boundedLogP)) — shared rounding
    from adam_tpu.ops.phred import error_probability_to_phred

    return error_probability_to_phred(jnp.exp(bounded))


def recalibration_phred_table_np(total, mismatches) -> np.ndarray:
    """Host twin of :func:`recalibration_phred_table` (same f64 math on
    the small table shapes; differential-tested for bit parity)."""
    err = np.asarray(PHRED_TO_ERROR)
    total = np.asarray(total, np.float64)
    mismatches = np.asarray(mismatches, np.float64)

    def emp_log(t, m):
        return np.log((1.0 + m) / (2.0 + t))

    g_t = total.sum(axis=(1, 2, 3))
    g_m = mismatches.sum(axis=(1, 2, 3))
    q_levels = np.arange(N_QUAL)
    q_t = total.sum(axis=(2, 3))
    q_m = mismatches.sum(axis=(2, 3))
    g_exp = (err[q_levels][None, :] * q_t).sum(axis=1)
    c_t = total.sum(axis=3)
    c_m = mismatches.sum(axis=3)
    d_t = total.sum(axis=2)
    d_m = mismatches.sum(axis=2)

    residue_logp = np.log(err[q_levels])
    g_present = g_t > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        global_delta = np.where(
            g_present,
            emp_log(g_t, g_m) - np.log(g_exp / np.maximum(g_t, 1)),
            0.0,
        )
        q_present = g_present[:, None] & (q_t > 0)
        offset1 = residue_logp[None, :] + global_delta[:, None]
        quality_delta = np.where(q_present, emp_log(q_t, q_m) - offset1, 0.0)
        offset2 = offset1 + quality_delta
        cyc_delta = np.where(
            q_present[:, :, None] & (c_t > 0),
            emp_log(c_t, c_m) - offset2[:, :, None],
            0.0,
        )
        din_delta = np.where(
            q_present[:, :, None] & (d_t > 0),
            emp_log(d_t, d_m) - offset2[:, :, None],
            0.0,
        )
    log_p = (
        offset2[:, :, None, None]
        + cyc_delta[:, :, :, None]
        + din_delta[:, :, None, :]
    )
    bounded = np.minimum(0.0, np.maximum(np.log(err[MAX_QUAL]), log_p))
    return np.floor(-10.0 * np.log10(np.exp(bounded)) + 0.5).astype(np.int32)


@partial(jax.jit, static_argnames=("lmax",))
def recalibrate_kernel(
    bases, quals, lengths, flags, read_group_idx, has_qual, valid,
    total, mismatches, lmax: int,
):
    """Apply the recalibration table to every residue -> new quals u8[N, L].

    Per-residue work is a single 4-d table gather keyed on
    (rg, reported qual, cycle, dinuc) plus the apply-mask
    (minAcceptableQuality Q5 floor, BaseQualityRecalibration.scala:50).
    """
    phred_table = recalibration_phred_table(total, mismatches)

    n_rg = total.shape[0]
    # RG-less reads use the dedicated last bin, symmetric with observe
    rg = jnp.where(read_group_idx >= 0, read_group_idx, n_rg - 1).astype(jnp.int32)
    q = jnp.clip(quals.astype(jnp.int32), 0, N_QUAL - 1)
    cycles = compute_cycles(lengths, flags, lmax) + lmax
    dinucs = compute_dinucs(bases, lengths, flags, lmax)

    new_q = phred_table[rg[:, None], q, cycles, dinucs]

    in_read = jnp.arange(lmax)[None, :] < lengths[:, None]
    apply_mask = (
        in_read
        & (quals >= MIN_ACCEPTABLE_QUALITY)
        & (quals < schema.QUAL_PAD)
        & has_qual[:, None]
        & valid[:, None]
    )
    return jnp.where(apply_mask, new_q, quals).astype(jnp.uint8)


def apply_table_body(
    bases, quals, lengths, flags, read_group_idx, has_qual, valid,
    phred_table, lmax: int,
):
    """Traceable body of the table application (shared by the plain
    kernel, the fused apply+pack2 kernel below, the mesh shard_map
    bodies and the coalescer's fused grid — ONE copy of the math, so
    every path is bitwise the same gather)."""
    n_rg = phred_table.shape[0]
    gl = (phred_table.shape[2] - 1) // 2
    rg = jnp.where(read_group_idx >= 0, read_group_idx, n_rg - 1).astype(jnp.int32)
    q = jnp.clip(quals.astype(jnp.int32), 0, N_QUAL - 1)
    cycles = compute_cycles(lengths, flags, lmax) + gl
    dinucs = compute_dinucs(bases, lengths, flags, lmax)
    new_q = phred_table[rg[:, None], q, cycles, dinucs]
    in_read = jnp.arange(lmax)[None, :] < lengths[:, None]
    apply_mask = (
        in_read
        & (quals >= MIN_ACCEPTABLE_QUALITY)
        & (quals < schema.QUAL_PAD)
        & has_qual[:, None]
        & valid[:, None]
    )
    return jnp.where(apply_mask, new_q, quals).astype(jnp.uint8)


@partial(jax.jit, static_argnames=("lmax",))
def apply_table_kernel(
    bases, quals, lengths, flags, read_group_idx, has_qual, valid,
    phred_table, lmax: int,
):
    """Apply a pre-solved u8 recalibration table on device -> u8[N, L].

    The per-residue work is one 4-d gather keyed on (rg, reported qual,
    cycle, dinuc) plus the Q5-floor apply mask — the device half of the
    streamed pipeline's pass C (the table itself was solved at the merge
    barrier).  The table's cycle axis spans [-gl, gl] with
    gl = (n_cyc - 1) // 2 >= lmax, so smaller windows gather from the
    middle of a wider merged table."""
    return apply_table_body(
        bases, quals, lengths, flags, read_group_idx, has_qual, valid,
        phred_table, lmax,
    )


def apply_pack2_body(
    bases, quals, lengths, flags, read_group_idx, has_qual, valid,
    phred_table, lmax: int, size: int,
):
    """Traceable fused apply + BOTH column packs — the bases half of
    the packed tail (deferred by PR 12 until the window was
    device-resident): with bases already on device from ingest, one
    dispatch gathers the recalibrated quals, SANGER-encodes and packs
    them, AND decodes + packs the base codes, so pass C ships two flat
    encode-ready columns (``sum(lengths)`` bytes each) and the host
    never walks either [N, L] matrix.  Returns
    ``(packed_quals, packed_bases)``, each u8[size]."""
    from adam_tpu.ops.colpack import (
        base_decode_body, pack_rows_body, sanger_body,
    )

    new_q = apply_table_body(
        bases, quals, lengths, flags, read_group_idx, has_qual, valid,
        phred_table, lmax,
    )
    qual_lens = jnp.where(valid & has_qual, lengths, 0)
    base_lens = jnp.where(valid, lengths, 0)
    return (
        pack_rows_body(sanger_body(new_q), qual_lens, size),
        pack_rows_body(base_decode_body(bases), base_lens, size),
    )


def apply_pack2_kernel(
    bases, quals, lengths, flags, read_group_idx, has_qual, valid,
    phred_table, lmax: int, size: int,
):
    """Jit entry point over :func:`apply_pack2_body` (the streamed
    pass-C dispatch; the donating twin lives in :func:`jit_variant`)."""
    return jit_variant("apply_pack2", False)(
        bases, quals, lengths, flags, read_group_idx, has_qual, valid,
        phred_table, lmax, size,
    )


def fused_bc_body(
    bases, quals, lengths, flags, read_group_idx,
    res_bits, mm_bits, read_ok, has_qual, valid,
    phred_table, n_rg: int, lmax: int, size: int,
):
    """Traceable fused pass B→C — the megakernel tier's body.

    When the solved recalibration table is already known at dispatch
    time (known-sites runs; discovered-table resumes re-observing for
    the observation dump), the observe scatter-add and the fused
    apply+pack compose into ONE executable over the window's resident
    arrays: the window's histograms AND both flat encode-ready columns
    come out of a single dispatch, and the barrier-2 host round-trip
    (fetch table → re-dispatch apply) disappears from the per-window
    path.  Functionally pure composition of
    :func:`observe_packed_body` (which sees the ORIGINAL quals — same
    as the unfused ordering) and :func:`apply_pack2_body`, so the
    outputs are bitwise the separate passes' outputs.

    Returns ``(total, mism, packed_quals, packed_bases)``."""
    total, mism = observe_packed_body(
        bases, quals, lengths, flags, read_group_idx,
        res_bits, mm_bits, read_ok, n_rg, lmax,
    )
    pq, pb = apply_pack2_body(
        bases, quals, lengths, flags, read_group_idx, has_qual, valid,
        phred_table, lmax, size,
    )
    return total, mism, pq, pb


def fused_bc_kernel(
    bases, quals, lengths, flags, read_group_idx,
    res_bits, mm_bits, read_ok, has_qual, valid,
    phred_table, n_rg: int, lmax: int, size: int,
):
    """Jit entry point over :func:`fused_bc_body` (the donating twin —
    resident bases/quals become the packed columns, the mask
    temporaries are consumed — lives in :func:`jit_variant`)."""
    return jit_variant("fused_bc", False)(
        bases, quals, lengths, flags, read_group_idx,
        res_bits, mm_bits, read_ok, has_qual, valid,
        phred_table, n_rg, lmax, size,
    )


def merge_observations(parts: list[tuple], replays=None,
                       tracer=None, window_ids=None,
                       on_part=None) -> tuple:
    """Sum per-window (total, mism, gl) histograms into one global
    (total, mism, gl) — the host-side analog of the sharded psum.

    Cycle slots are centered (index = cycle + gl, table width 2*gl+1),
    so windows with smaller lmax pad into the middle of the widest
    window's table.  Device-resident parts (the lazy ``device`` observe
    backend) are fetched here, at the barrier, via the chunked transfer
    helper — each is a compact [n_rg, 94, 2g+1, 17] table, never [N, L].
    Each device-resident fetch records one ``device.fetch.observe``
    span (``device=<k>`` + ``window=<i>`` attributed) on ``tracer``
    (default: the global TRACE), so whether the n per-window fetches
    serialize on the host thread at barrier 2 — the ROADMAP
    "observe-fetch serialization" item — is directly measurable from a
    trace instead of inferred from the barrier wall.

    ``replays``: optional per-part recovery hooks (parallel list; None
    entries = no hook).  When a part's fetch still fails after the
    transfer layer's retry budget, ``replays[k](exc)`` must return a
    replacement host-resident ``(total, mism, g)`` — the streamed
    pipeline's hook evicts the failed device and recomputes the window
    on a survivor or the host backend, so a dead chip costs one window
    replay instead of the whole run.

    ``window_ids``: optional parallel list of true window indices for
    the span attribution — residual windows drop out of ``parts``, so
    the part position ``k`` is NOT the window index whenever any
    window had zero valid rows.  A ``None`` entry marks a part with no
    single source window (the mesh partitioner's fetched accumulator
    sums many windows): ``on_part`` is skipped for it — a multi-window
    histogram must never persist as one window's sidecar.

    ``on_part``: optional ``on_part(window, total, mism, g)`` callback
    invoked with each part's HOST-resident histogram as it merges
    (after any replay) — the streamed run journal persists its durable
    observe sidecars here, at the barrier, the one point where every
    histogram crosses to the host anyway.
    """
    from adam_tpu.parallel.device_pool import span_attrs
    from adam_tpu.utils.transfer import _resident_device, device_fetch

    tr = tracer if tracer is not None else _tele.TRACE
    gl = max(p[2] for p in parts)
    n_cyc = 2 * gl + 1
    s0 = parts[0][0].shape  # .shape is metadata — no transfer
    shape = (s0[0], s0[1], n_cyc, s0[3])
    total = np.zeros(shape, np.int64)
    mism = np.zeros(shape, np.int64)
    for k, (t, m, g) in enumerate(parts):
        try:
            if isinstance(t, np.ndarray):
                # host-resident part (host backend or a replayed
                # window): nothing crosses the device link — no span,
                # or the "fetch" attribution would count memcpys
                tt = device_fetch(t)
                mm = device_fetch(m)
            else:
                attrs = span_attrs(_resident_device(t))
                win = window_ids[k] if window_ids is not None else k
                with tr.span(_tele.SPAN_OBS_FETCH, window=win, **attrs):
                    tt = device_fetch(t)
                    mm = device_fetch(m)
        except Exception as e:
            replay = replays[k] if replays is not None else None
            if replay is None:
                raise
            tt, mm, g = replay(e)
            tt = np.asarray(tt)
            mm = np.asarray(mm)
        win_id = window_ids[k] if window_ids is not None else k
        if on_part is not None and win_id is not None:
            on_part(win_id, tt, mm, g)
        off = gl - g
        total[:, :, off : off + 2 * g + 1, :] += tt
        mism[:, :, off : off + 2 * g + 1, :] += mm
    return total, mism, gl


def solve_recalibration_table(total, mism) -> np.ndarray:
    """Observation histograms -> compact u8 phred table (the global
    barrier step between the observe and apply passes)."""
    if isinstance(total, np.ndarray):
        return recalibration_phred_table_np(total, mism).astype(np.uint8)
    # adam-tpu: noqa[dispatch-ledger] reason=once-per-run barrier solve on table shapes; a ledger key would demand a solved-width prewarm entry before the solve exists (ROADMAP device-resident windows item)
    tbl = recalibration_phred_table(total, mism)
    return device_fetch(tbl.astype(jnp.uint8))


def dump_observation_csv(total, mism, rg_names, lmax, path) -> None:
    """Write the merged observation histogram as the reference's
    ObservationTable CSV (shared by the monolithic, streamed and sharded
    drivers so the format lives in one place)."""
    obs = ObservationTable(np.asarray(total), np.asarray(mism), rg_names, lmax)
    with open(path, "w") as fh:
        fh.write(obs.to_csv())


def recalibrate_base_qualities(
    ds: AlignmentDataset,
    known_snps: Optional[SnpTable] = None,
    dump_observation_table: Optional[str] = None,
    backend: Optional[str] = None,
) -> AlignmentDataset:
    total, mism, rg_names, lmax = _observe_device(ds, known_snps, backend)
    if dump_observation_table:
        dump_observation_csv(
            device_fetch(total), device_fetch(mism), rg_names, lmax,
            dump_observation_table,
        )
    # the delta-stack table is built from the psum-able histograms, but
    # the *solved* table is compact (n_rg x 94 x cycles x 17, ~4 MB) —
    # table math runs wherever the histograms live: host arrays (the
    # native-observe path) stay host; device arrays use the device
    # kernel and fetch only the tiny u8 table
    phred_table = solve_recalibration_table(total, mism)
    return apply_recalibration(ds, phred_table, lmax, backend)


def apply_recalibration_dispatch(
    ds: AlignmentDataset, phred_table: np.ndarray, gl: int,
    backend: Optional[str] = None, device=None, mesh=None, resident=None,
):
    """Start the per-residue table application for one window -> opaque
    handle for :func:`apply_recalibration_finish_packed`, or ``None``
    when a donating attempt consumed ``resident`` and failed (the caller
    places a fresh handle and dispatches again).

    On the ``device`` backend with a ``resident`` handle (a
    ``device_pool.ResidentWindow`` — the streamed pass C) this
    *dispatches* the fused apply + bases+quals pack
    (:func:`apply_pack2_body`) without blocking: the five row arrays come
    from the handle, only the post-split ``has_qual``/``valid`` bools
    ship, and the handle finishes as ``(ds, io.arrow_pack.PackedColumns)``
    — both flat encode-ready columns, ``sum(lengths)`` bytes each, never
    the [N, L] matrix.  The streamed pipeline double-buffers: window i's
    result is fetched (and its part encoded) while window i+1's gather
    runs on the chip.  ``device`` is the handle's pool chip; ``mesh`` (a
    ``MeshPartitioner``, with a mesh-sharded handle) runs the same body
    per shard.  ``phred_table`` may be a device-resident array (the pool
    replicates the solved table once per device; under ``mesh`` it is
    the replicated placement from ``MeshPartitioner.put_replicated``).
    Where the runtime honors donation the handle's quals/bases buffers
    become the packed outputs.

    Without a handle, the ``device`` backend runs the in-memory API's
    single-device leg (:func:`apply_table_kernel`, finishing as a
    dataset); the other backends compute eagerly and the handle is just
    the result."""
    backend = bqsr_backend(backend)
    from adam_tpu.parallel.device_pool import span_attrs

    attrs = {"device": "mesh"} if mesh is not None else span_attrs(device)
    with _tele.TRACE.span(
        _tele.SPAN_BQSR_APPLY_DISPATCH, backend=backend, **attrs,
    ):
        return _apply_dispatch_impl(
            ds, phred_table, gl, backend, device, mesh, resident
        )


def _apply_pack_lens(b) -> np.ndarray:
    """Host copy of the fused kernel's per-row packed byte counts (the
    offsets side of the Arrow layout — derived here, never fetched)."""
    from adam_tpu.ops.colpack import pack_lengths

    return pack_lengths(b.lengths, b.valid, b.has_qual)


def _apply_pack_lens_bases(b) -> np.ndarray:
    """Per-row packed byte counts for the bases column (every valid row
    carries its sequence, qual-less or not)."""
    from adam_tpu.ops.colpack import pack_lengths

    return pack_lengths(b.lengths, b.valid)


def fused_bc_enabled(default: bool = True) -> bool:
    """Resolve the ``ADAM_TPU_FUSED_BC`` toggle for the megakernel
    tier: ``auto``/unset -> ``default`` (on wherever a window is
    eligible), ``1/on/true`` and ``0/off/false`` force; a typo warns
    and keeps the default (``utils/retry.env_toggle``, the shared
    tuning-var contract).  The off position is the smoke harness's
    unfused A/B leg."""
    from adam_tpu.utils.retry import env_toggle

    return env_toggle("ADAM_TPU_FUSED_BC", default)


def fused_bc_dispatch(
    ds: AlignmentDataset, phred_table: np.ndarray,
    known_snps: Optional[SnpTable] = None, backend: Optional[str] = None,
    device=None, mesh=None, resident=None,
):
    """One fused B→C dispatch for a window whose recalibration table is
    already solved (known-sites runs; discovered-table resumes that
    re-observe for the dump) -> ``(handle, (total, mism, rg_names,
    gl))``, or ``None`` when the fused tier can't take this window.

    The handle is exactly :func:`apply_recalibration_dispatch`'s
    ``packed2`` shape (finished by
    :func:`apply_recalibration_finish_packed`); the histograms are the
    lazy device arrays the observe path would have produced — both out
    of ONE donated executable over the window's ingest-resident
    arrays, so the separate observe dispatch, the barrier-2 apply
    re-dispatch and the round-trip between them all collapse.

    Eligibility: device backend, a live matching ``ResidentWindow``
    handle, and a table at least as wide as the window's column grid
    (``n_cyc >= 2*gl+1`` — the merged table always is for tables
    discovered from the same input).  Anything else returns ``None``
    and the caller falls back to the separate-pass path, which is
    bitwise identical by construction (:func:`fused_bc_body` is a pure
    composition of the two pass bodies)."""
    backend = bqsr_backend(backend)
    if backend != "device" or resident is None:
        return None
    from adam_tpu.formats.batch import grid_cols, grid_rows, pad_rows_np
    from adam_tpu.ops.colpack import fetch_grid, pack_mask_bits
    from adam_tpu.parallel.device_pool import (
        donation_ok, putter, span_attrs,
    )
    from adam_tpu.utils import compile_ledger, faults
    from adam_tpu.utils import retry as _retry

    b = ds.batch.to_numpy()
    n = b.n_rows
    L = b.lmax
    g = grid_rows(n)
    glc = grid_cols(L)
    n_rg = len(ds.read_groups) + 1
    if phred_table.shape[0] != n_rg or phred_table.shape[2] < 2 * glc + 1:
        return None
    n_cyc = phred_table.shape[2]
    rw = resident
    rg_names = ds.read_groups.names + ["null"]

    attrs = {"device": "mesh"} if mesh is not None else span_attrs(device)
    with _tele.TRACE.span(
        _tele.SPAN_FUSED_BC, backend=backend,
        reads=int(ds.batch.n_rows), **attrs,
    ):
        gm = mesh.rows_for(g) if mesh is not None else g
        if mesh is not None:
            live = rw.device == "mesh"
        else:
            live = rw.device is device
        if not (live and rw.alive and rw.g == gm and rw.gl == glc):
            return None
        # the observe half's host prep (and the apply half's per-row
        # lengths): one bqsr.observe.prep, ended before the dispatch
        with _tele.TRACE.span(_tele.SPAN_BQSR_OBSERVE_PREP, reads=n):
            is_mm, _, has_md = batch_md_arrays(
                ds.batch, ds.sidecar, need_ref_codes=False
            )
            read_ok = observe_read_mask(b, has_md)
            residue_ok = observe_residue_mask(ds, b, known_snps)
            pack_lens_q = _apply_pack_lens(b)
            pack_lens_b = _apply_pack_lens_bases(b)
            res_pk = pack_mask_bits(
                pad_rows_np(residue_ok, gm, False, cols=glc)
            )
            mm_pk = pack_mask_bits(pad_rows_np(is_mm, gm, False, cols=glc))
            rd_pad = pad_rows_np(read_ok, gm, False)
            hq_pad = pad_rows_np(b.has_qual, gm, False)
            vd_pad = pad_rows_np(b.valid, gm, False)
            if mesh is not None:
                lens_q_pad = pad_rows_np(pack_lens_q, gm, 0)
                lens_b_pad = pad_rows_np(pack_lens_b, gm, 0)
            else:
                total_q = int(pack_lens_q.sum())
                total_b = int(pack_lens_b.sum())
                cut_q = min(g * glc, fetch_grid(total_q))
                cut_b = min(g * glc, fetch_grid(total_b))

        if mesh is not None:
            def dispatch_mesh_fused():
                faults.point("device.dispatch")
                if not rw.alive:
                    # donated shards died under a half-run attempt:
                    # the caller runs the separate passes instead
                    return None
                try:
                    total, mism, pq, pb = mesh.fused_bc_window(
                        rw, res_pk, mm_pk, rd_pad, hq_pad, vd_pad,
                        phred_table, n_rg, glc,
                    )
                except BaseException:
                    if mesh.apply_supports_donation():
                        rw.mark_consumed()
                    raise
                if mesh.apply_supports_donation():
                    rw.mark_consumed()
                return total, mism, (
                    mesh.packed_payload_slices(pq, lens_q_pad, glc),
                    mesh.packed_payload_slices(pb, lens_b_pad, glc),
                )

            with compile_ledger.track(
                ("mesh.fused_bc", gm, glc, n_rg, n_cyc),
                mesh.ledger_key(),
            ):
                got = _retry.retry_call(
                    dispatch_mesh_fused, site="bqsr.fused_bc.dispatch"
                )
            if got is None:
                return None
            count_observe_residues(b, read_ok)
            count_apply_residues(b)
            total, mism, (q_slices, b_slices) = got
            handle = (ds, b, ("packed2", q_slices, pack_lens_q,
                              b_slices, pack_lens_b))
            return handle, (total, mism, rg_names, glc)

        _put = putter(device)

        def _placed_table():
            if isinstance(phred_table, np.ndarray):
                return _put(np.ascontiguousarray(phred_table, np.uint8))
            return phred_table  # device-resident (pool-replicated)

        def dispatch_fused():
            faults.point("device.dispatch", device=device)
            if not rw.alive:
                return None
            donate = donation_ok(device)
            try:
                total, mism, pq, pb = jit_variant("fused_bc", donate)(
                    *rw.args(), _put(res_pk), _put(mm_pk), _put(rd_pad),
                    _put(hq_pad), _put(vd_pad), _placed_table(),
                    n_rg, glc, g * glc,
                )
            except BaseException:
                if donate:
                    rw.mark_consumed()
                raise
            if donate:
                rw.mark_consumed()
            return total, mism, pq[:cut_q], pb[:cut_b]

        # ledger key == fused_bc_prewarm_entry's key
        with compile_ledger.track(
            ("bqsr.fused_bc", g, glc, n_rg, n_cyc), device
        ):
            got = _retry.retry_call(
                dispatch_fused, site="bqsr.fused_bc.dispatch"
            )
        if got is None:
            return None
        count_observe_residues(b, read_ok)
        count_apply_residues(b)
        total, mism, pq, pb = got
        handle = (ds, b, ("packed2", [(pq, total_q)], pack_lens_q,
                          [(pb, total_b)], pack_lens_b))
        return handle, (total, mism, rg_names, glc)


def _apply_dispatch_impl(
    ds: AlignmentDataset, phred_table, gl: int, backend: str, device=None,
    mesh=None, resident=None,
):
    b = ds.batch.to_numpy()
    count_apply_residues(b)
    if backend != "device":
        from adam_tpu import native

        new_quals = None
        if backend == "native":
            new_quals = native.bqsr_apply(
                b.bases, np.asarray(b.quals), b.lengths, b.flags,
                b.read_group_idx, b.has_qual, b.valid, phred_table, gl,
            )
        if new_quals is None:
            new_quals = _apply_table_np(b, phred_table, gl)
        return ds, b, new_quals
    from adam_tpu.formats.batch import grid_cols, grid_rows, pad_rows_np
    from adam_tpu.ops.colpack import fetch_grid
    from adam_tpu.parallel.device_pool import (
        donation_ok, putter, resident_arrays,
    )
    from adam_tpu.utils import compile_ledger, faults
    from adam_tpu.utils import retry as _retry

    n = b.n_rows
    glc = grid_cols(b.lmax)
    n_rg = phred_table.shape[0]
    n_cyc = phred_table.shape[2]
    _put = putter(device)

    def _placed_table():
        if isinstance(phred_table, np.ndarray):
            return _put(np.ascontiguousarray(phred_table, np.uint8))
        return phred_table  # device-resident (pool-replicated)

    rw = resident
    if rw is None:
        if mesh is not None:
            raise ValueError("the mesh apply dispatches on a resident "
                             "window handle")
        # the in-memory API's single-device leg: the plain gather over
        # this call's own placement, fetched as the [N, L] matrix
        g = grid_rows(n)

        def dispatch():
            faults.point("device.dispatch", device=device)
            rows, _nbytes = resident_arrays(b, g, glc, _put)
            return apply_table_kernel(
                *rows, _put(pad_rows_np(b.has_qual, g, False)),
                _put(pad_rows_np(b.valid, g, False)), _placed_table(),
                glc,
            )[:n, :b.lmax]  # device-side slice: fetch only real cells

        # adam-tpu: noqa[dispatch-ledger] reason=the in-memory API's single-device leg: no streamed pass dispatches it, so no prewarm entry builds its key
        with compile_ledger.track(("bqsr.apply", g, glc, n_rg, n_cyc), device):
            new_dev = _retry.retry_call(dispatch, site="bqsr.apply.dispatch")
        return ds, b, new_dev

    # the streamed leg: both flat columns come home off the resident
    # arrays, each fetched as its exact payload
    hq_pad = pad_rows_np(b.has_qual, rw.g, False)
    vd_pad = pad_rows_np(b.valid, rw.g, False)
    pack_lens_q = _apply_pack_lens(b)
    pack_lens_b = _apply_pack_lens_bases(b)
    if mesh is not None:
        lens_q_pad = pad_rows_np(pack_lens_q, rw.g, 0)
        lens_b_pad = pad_rows_np(pack_lens_b, rw.g, 0)
        donate = mesh.apply_supports_donation()

        def dispatch_mesh_pack2():
            faults.point("device.dispatch")
            if not rw.alive:
                return None  # consumed by a half-run donating attempt
            try:
                pq, pb = mesh.apply_pack2_window(
                    rw, hq_pad, vd_pad, phred_table, glc
                )
            finally:
                # the donating collective consumes the resident shards,
                # on success and (possibly) mid-failure alike: the handle
                # must never offer them again
                if donate:
                    rw.mark_consumed()
            return (
                mesh.packed_payload_slices(pq, lens_q_pad, glc),
                mesh.packed_payload_slices(pb, lens_b_pad, glc),
            )

        with compile_ledger.track(
            ("mesh.apply_pack2", rw.g, glc, n_rg, n_cyc), mesh.ledger_key(),
        ):
            got = _retry.retry_call(
                dispatch_mesh_pack2, site="bqsr.apply.dispatch"
            )
    else:
        g = rw.g
        total_q = int(pack_lens_q.sum())
        total_b = int(pack_lens_b.sum())
        # bucketed device-side slices (over-fetch < 6.25%, the fetch
        # trims): an exact per-window size would compile one slice
        # program per window
        cut_q = min(g * glc, fetch_grid(total_q))
        cut_b = min(g * glc, fetch_grid(total_b))
        donate = donation_ok(device)

        def dispatch_pack2():
            faults.point("device.dispatch", device=device)
            if not rw.alive:
                return None  # consumed by a half-run donating attempt
            try:
                pq, pb = jit_variant("apply_pack2", donate)(
                    *rw.args(), _put(hq_pad), _put(vd_pad),
                    _placed_table(), glc, g * glc,
                )
            finally:
                if donate:
                    rw.mark_consumed()
            return [(pq[:cut_q], total_q)], [(pb[:cut_b], total_b)]

        # ledger key == apply_prewarm_entry's key
        with compile_ledger.track(
            ("bqsr.apply_pack2", g, glc, n_rg, n_cyc), device
        ):
            got = _retry.retry_call(
                dispatch_pack2, site="bqsr.apply.dispatch"
            )
    if got is None:
        return None
    q_slices, b_slices = got
    return ds, b, ("packed2", q_slices, pack_lens_q, b_slices, pack_lens_b)


def apply_handle_dataset(handle) -> AlignmentDataset:
    """The pre-recalibration dataset inside a dispatch handle — what a
    recovery path re-dispatches when the handle's device died before
    :func:`apply_recalibration_finish` could fetch it."""
    return handle[0]


def _handle_is_packed(handle) -> bool:
    payload = handle[2]
    return isinstance(payload, tuple) and payload[0] in (
        "packed", "packed2"
    )


def apply_recalibration_finish(handle) -> AlignmentDataset:
    """Fetch a dispatched window (chunked transfer for device results)
    and finish the host half: stash pre-recalibration quals as OQ."""
    from adam_tpu.utils.transfer import device_fetch

    if _handle_is_packed(handle):
        return apply_recalibration_finish_packed(handle)[0]
    ds, b, new_quals = handle
    with _tele.TRACE.span(_tele.SPAN_BQSR_APPLY_FETCH):
        new_quals = device_fetch(new_quals)
    return _stash_orig_quals(ds, b, new_quals)


def apply_recalibration_finish_packed(handle):
    """Finish one dispatched window -> ``(dataset, PackedQuals | None)``.

    A streamed ``packed2`` handle fetches both flat encode-ready
    columns — ``sum(lengths)`` bytes each, one slice per resident shard
    — and returns them as a :class:`~adam_tpu.io.arrow_pack.PackedColumns`
    beside the dataset (whose batch keeps its PRE-recalibration quals:
    the OQ stash is the only remaining consumer of the matrix, and the
    writer encodes both columns straight off the packed buffers).  The
    coalescer's ``packed`` handle carries the qual column only and
    returns a ``PackedQuals``.  A plain handle behaves exactly like
    :func:`apply_recalibration_finish` and returns ``packed=None``."""
    from adam_tpu.io.arrow_pack import PackedColumns, PackedQuals
    from adam_tpu.utils.transfer import device_fetch

    if not _handle_is_packed(handle):
        return apply_recalibration_finish(handle), None

    def _fetch_col(slices, pack_lens):
        # each slice is bucket-quantized (colpack.fetch_grid) so slice
        # programs stay few; the true payload size rides alongside and
        # the host trims the bucket tail here
        parts = [
            np.asarray(device_fetch(s))[:t] for s, t in slices
        ]
        if len(parts) == 1:
            buf = parts[0]
        elif parts:
            buf = np.concatenate(parts)
        else:  # every row column-less: a valid, all-null column
            buf = np.zeros(0, np.uint8)
        return PackedQuals(buf, pack_lens)

    payload = handle[2]
    if payload[0] == "packed2":
        ds, b, (_tag, q_slices, q_lens, b_slices, b_lens) = handle
        with _tele.TRACE.span(_tele.SPAN_BQSR_APPLY_FETCH):
            packed = PackedColumns(
                quals=_fetch_col(q_slices, q_lens),
                bases=_fetch_col(b_slices, b_lens),
            )
        return _stash_orig_quals(ds, b), packed
    ds, b, (_tag, slices, pack_lens) = handle
    with _tele.TRACE.span(_tele.SPAN_BQSR_APPLY_FETCH):
        packed_q = _fetch_col(slices, pack_lens)
    return _stash_orig_quals(ds, b), packed_q


def apply_recalibration(
    ds: AlignmentDataset, phred_table: np.ndarray, gl: int,
    backend: Optional[str] = None,
) -> AlignmentDataset:
    """Apply a solved recalibration table to one batch/window (the
    Recalibrator.scala:28-60 pass): gather new quals from the compact
    table, stash originals as OQ.  ``gl`` is the table's grid-aligned
    lane count (cycle slots span [-gl, gl])."""
    with _tele.TRACE.span(
        _tele.SPAN_BQSR_APPLY_HOST, backend=bqsr_backend(backend)
    ):
        return apply_recalibration_finish(
            apply_recalibration_dispatch(ds, phred_table, gl, backend)
        )


def _apply_table_np(b, phred_table: np.ndarray, gl: int) -> np.ndarray:
    """Numpy twin of the table application (the ``numpy`` backend and
    the native-unavailable fallback)."""
    n_rg = phred_table.shape[0]
    n_cyc = phred_table.shape[2]
    L = b.lmax
    quals = np.asarray(b.quals)
    rg = np.where(
        np.asarray(b.read_group_idx) >= 0, np.asarray(b.read_group_idx),
        n_rg - 1,
    ).astype(np.int32)
    # fused i32 flat index into the raveled table: one gather,
    # minimal [N, L] temporaries
    idx = compute_cycles_np(b.lengths, b.flags, L)
    idx += gl
    q32 = np.minimum(quals, N_QUAL - 1).astype(np.int32)
    q32 += rg[:, None] * N_QUAL
    q32 *= n_cyc
    idx += q32
    del q32
    idx *= N_DINUC
    idx += compute_dinucs_np(b.bases, b.lengths, b.flags, L)
    new_q = phred_table.ravel()[idx]
    del idx
    in_read = np.arange(L)[None, :] < np.asarray(b.lengths)[:, None]
    apply_mask = (
        in_read
        & (quals >= MIN_ACCEPTABLE_QUALITY)
        & (quals < schema.QUAL_PAD)
        & np.asarray(b.has_qual)[:, None]
        & np.asarray(b.valid)[:, None]
    )
    return np.where(apply_mask, new_q, quals).astype(np.uint8)


def _stash_orig_quals(
    ds: AlignmentDataset, b, new_quals: np.ndarray | None = None
) -> AlignmentDataset:
    """Install recalibrated quals and stash the pre-recalibration matrix
    as OQ (setOrigQual, Recalibrator.scala:36-40) — vectorized: encode
    the old qual matrix as a string column and merge it into rows that
    had no OQ yet.  ``new_quals=None`` (the packed pass-C path) stashes
    OQ only and keeps the batch's quals untouched: the recalibrated
    column travels as the packed payload, never as a matrix."""
    from dataclasses import replace as dc_replace

    from adam_tpu import native
    from adam_tpu.formats.strings import StringColumn

    side = ds.sidecar
    old_oq = StringColumn.of(side.orig_quals)
    set_mask = (
        np.asarray(b.valid) & np.asarray(b.has_qual) & ~old_oq.valid
    )
    stash_lens = np.where(set_mask, np.asarray(b.lengths), 0)
    nat = native.lut_compact_rows(
        np.asarray(b.quals), stash_lens, schema.QUAL_SANGER_LUT256
    )
    if nat is not None:
        # fused LUT+compact pass — no [N, L] ASCII temporary (in-read
        # quals are <= 93, so the clamp in the LUT never fires on them)
        stashed = StringColumn(nat[0], nat[1], set_mask.copy())
    else:
        qmat = (np.asarray(b.quals) + schema.SANGER_OFFSET).astype(np.uint8)
        stashed = StringColumn.from_matrix(qmat, stash_lens, set_mask.copy())
    if not old_oq.valid.any():
        merged = stashed  # no pre-existing OQ anywhere: stash wholesale
    else:
        merged = StringColumn.where(set_mask, stashed, old_oq)
    new_side = dc_replace(side, orig_quals=merged)
    if new_quals is None:
        return ds.with_batch(b, new_side)
    return ds.with_batch(
        b.replace(quals=np.asarray(new_quals)), new_side
    )
